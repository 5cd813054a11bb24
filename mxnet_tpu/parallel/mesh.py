"""Device-mesh construction helpers + the trace-time mesh context.

``trace_mesh``/``current_trace_mesh`` let mesh-aware ops (ring attention
dispatch in ops/attention.py) discover the SPMD mesh while the trainer's
step is being traced — the op registry's apply signature carries no mesh,
and threading one through every op would leak parallelism into the single-
device API."""
from __future__ import annotations

import contextlib
import contextvars

import numpy as np

__all__ = ["make_mesh", "local_mesh", "trace_mesh", "current_trace_mesh",
           "MeshSpec", "parse_mesh_spec"]


class MeshSpec:
    """Device-free mesh description: axis names and sizes, nothing else.

    The static-analysis passes (analysis/shard_lint.py, memory_plan.py)
    reason about a *planned* mesh — ``dp=8,model=2`` on a CPU dev box that
    has no 16 devices to build a real ``jax.sharding.Mesh`` from. A
    ``MeshSpec`` carries exactly the two attributes ``ShardingRules`` and
    the lint passes read (``axis_names``, ``shape``), so the same rules
    object drives both the real trainer mesh and the abstract plan."""

    __slots__ = ("shape", "axis_names")

    def __init__(self, axes):
        """``axes``: dict name -> size (ordering is axis order), or an
        iterable of (name, size) pairs."""
        self.shape = {str(k): int(v) for k, v in dict(axes).items()}
        if not self.shape:
            raise ValueError("MeshSpec needs at least one axis")
        for name, size in self.shape.items():
            if size < 1:
                raise ValueError("mesh axis %r has size %d" % (name, size))
        self.axis_names = tuple(self.shape)

    @property
    def size(self):
        return int(np.prod(list(self.shape.values())))

    @classmethod
    def of(cls, mesh):
        """Coerce a real ``jax.sharding.Mesh`` (or another MeshSpec) to a
        MeshSpec — the lint passes' common currency."""
        if isinstance(mesh, cls):
            return mesh
        return cls({name: mesh.shape[name] for name in mesh.axis_names})

    def __repr__(self):
        return "MeshSpec(%s)" % ",".join(
            "%s=%d" % (n, s) for n, s in self.shape.items())


def parse_mesh_spec(spec):
    """Parse ``"dp=8,model=2"`` (the graphlint ``--mesh`` syntax) into a
    ``MeshSpec``. Also accepts a dict or an existing MeshSpec/Mesh."""
    if isinstance(spec, str):
        axes = {}
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            if "=" not in part:
                raise ValueError(
                    "--mesh expects AXIS=SIZE[,AXIS=SIZE...], got %r" % spec)
            name, size = part.split("=", 1)
            name = name.strip()
            if name in axes:
                # a typo'd 'dp=2,dp=8' must not silently lint a wrong mesh
                raise ValueError("mesh axis %r given twice in %r"
                                 % (name, spec))
            axes[name] = int(size)
        return MeshSpec(axes)
    if isinstance(spec, dict):
        return MeshSpec(spec)
    return MeshSpec.of(spec)


_TRACE_MESH = contextvars.ContextVar("mxtpu_trace_mesh", default=None)


def current_trace_mesh():
    """The mesh of the SPMD step currently being traced, or None."""
    return _TRACE_MESH.get()


@contextlib.contextmanager
def trace_mesh(mesh):
    tok = _TRACE_MESH.set(mesh)
    try:
        yield mesh
    finally:
        _TRACE_MESH.reset(tok)


def make_mesh(shape=None, axis_names=("data", "model"), devices=None):
    """Build a ``jax.sharding.Mesh``.

    ``shape`` maps axis name → size (dict) or is a tuple aligned with
    ``axis_names``. Unspecified trailing axes default to size 1; a single
    ``-1`` entry absorbs the remaining devices. With no shape at all, every
    device lands on the first axis (pure data parallelism)."""
    import jax

    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if shape is None:
        sizes = [n] + [1] * (len(axis_names) - 1)
    elif isinstance(shape, dict):
        axis_names = tuple(shape.keys())
        sizes = list(shape.values())
    else:
        sizes = list(shape)
        if len(sizes) < len(axis_names):
            sizes += [1] * (len(axis_names) - len(sizes))
    if sizes.count(-1) > 1:
        raise ValueError("at most one mesh axis may be -1")
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        if n % known:
            raise ValueError("mesh shape %s does not divide %d devices" % (sizes, n))
        sizes[sizes.index(-1)] = n // known
    if int(np.prod(sizes)) != n:
        raise ValueError("mesh shape %s != %d devices" % (sizes, n))
    dev_array = np.asarray(devices).reshape(sizes)
    return jax.sharding.Mesh(dev_array, tuple(axis_names))


def local_mesh(n_devices=None, axis_names=("data",)):
    """Mesh over the first ``n_devices`` local devices, one axis by default."""
    import jax

    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return make_mesh((len(devices),) + (1,) * (len(axis_names) - 1), axis_names, devices)
