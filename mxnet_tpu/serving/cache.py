"""Persistent per-bucket executable cache (docs/SERVING.md).

The serving analogue of the training side's one-executable-per-step
discipline. Each (model, input-shape bucket, dtype) gets ONE grad-less
executor, bound and compiled at warmup and kept hot for the life of the
process — a request never pays bind/trace/compile. After ``seal()`` a
lookup miss (a shape no warmed bucket covers — the request that WOULD have
recompiled) is a hard ``MXNetError`` carrying the GL201-203 retrace-guard
diagnosis, so a production server can never silently degrade into
per-request compilation.

Persistence (TVM's measure-and-cache discipline, PAPERS.md): the warmed
bucket set is written as a JSON manifest under
``{cache_dir}/{device_kind}/{model_key}.json`` so the next process warms
the same buckets without being told. The XLA *artifacts* themselves survive
restarts through JAX's persistent compilation cache, whose location is
decided in ``mxnet_tpu/compile_cache.py`` (``JAX_COMPILATION_CACHE_DIR``, else
a fixed directory in the checkout) — never here.

The program store. JAX keys that cache on the LOWERED module, so a process
that starts on a warm cache still binds, traces and lowers every program
before it can ask for it (and imports what tracing needs: Pallas, 1.5 to 2 s).
So each bucket's program is also kept EXPORTED (``jax.export``), in a
directory of the compile cache's (``compile_cache.program_store()``: on where
that cache is on, moved and emptied with it), under a key of everything its
lowering could depend on (``_StoredProgram``). A process that finds it runs
``jax.jit(exported.call)`` under the same name with the same donation and
traces nothing; one that does not traces, exports, writes the blob and runs
that same ``jax.jit(exported.call)``: one module text whoever made it, so the
compile cache holds ONE artifact a program and every later process hits it.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import re
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..base import MXNetError
from .. import compile_cache as _compile_cache
from .. import telemetry as _tm

__all__ = ["PersistentExecutableCache", "serve_cache_dir"]

log = logging.getLogger("mxnet_tpu.serving")

def serve_cache_dir():
    """The configured on-disk cache root (``MXNET_SERVE_CACHE_DIR``), or
    None when persistence is off (the default)."""
    d = os.environ.get("MXNET_SERVE_CACHE_DIR", "").strip()
    return d or None


def _device_kind():
    import jax

    try:
        kind = jax.devices()[0].device_kind
    except Exception:
        kind = "unknown"
    return re.sub(r"[^A-Za-z0-9_.-]+", "_", str(kind))


def _shape_key(input_shapes):
    return tuple(sorted((str(n), tuple(int(d) for d in s))
                        for n, s in input_shapes.items()))


_SOURCE_DIGEST = None


def _source_digest():
    """A digest of this package's own source files, read once a process: two
    checkouts that differ in one byte of one file share no stored program
    (the operators' lowering is code, and no version number follows it)."""
    global _SOURCE_DIGEST
    if _SOURCE_DIGEST is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        digest = hashlib.sha256()
        for folder, dirs, files in os.walk(root):
            dirs.sort()
            for name in sorted(f for f in files if f.endswith(".py")):
                path = os.path.join(folder, name)
                digest.update(os.path.relpath(path, root).encode() + b"\0")
                with open(path, "rb") as f:
                    digest.update(f.read() + b"\0")
        _SOURCE_DIGEST = digest.hexdigest()
    return _SOURCE_DIGEST


def _program_key(exe, specs, label, donated, device):
    """Everything the lowering of a bound executor's forward program could
    depend on, as one text: the symbol, every argument's and aux state's
    name, shape and type (and the key's), the donated names, the label, the
    versions of jax and jaxlib, the platform and device kind, this package's
    sources (``_source_digest``), every ``MXNET_*`` environment variable and
    the jax settings a lowering reads."""
    import jax
    import jaxlib

    named = lambda names, structs: [
        (n, list(s.shape), str(s.dtype)) for n, s in zip(names, structs)]
    return json.dumps({
        "symbol": exe._symbol.tojson(),
        "args": named(exe._prog.arg_names, specs[0]),
        "aux": named(exe._prog.aux_names, specs[1]),
        "rng": named(["rng"], specs[2:]),
        "donated": list(donated), "label": label,
        "jax": [jax.__version__, jaxlib.__version__],
        "device": [device.platform, device.device_kind],
        "source": _source_digest(),
        "env": sorted((k, v) for k, v in os.environ.items()
                      if k.startswith("MXNET_")),
        "config": [str(getattr(jax.config, name)) for name in (
            "jax_enable_x64", "jax_default_matmul_precision",
            "jax_default_prng_impl", "jax_numpy_dtype_promotion")],
    }, sort_keys=True)


class _StoredProgram:
    """One bucket's forward program in the program store (the module's
    docstring): what ``executor._GraphProgram.store`` holds. ``specs``: what
    the program is called with, ``(args, aux, rng)``; ``key``: a text that
    names everything its lowering could depend on (``_program_key``). The
    file is named by the key's digest and holds it again before the blob.

    A file that is not there is a ``miss``; one that cannot be read, holds
    another key or is refused by ``deserialize`` is ``stale`` (logged, then
    treated as a miss and written over); neither is ever an error, and a
    program that cannot be exported is run as traced. Counters
    ``serving.program_store.hit`` / ``.miss`` / ``.stale``, one an
    executable bound."""

    _MAGIC = b"mxprog1\n"

    def __init__(self, root, key, specs, platform):
        self.specs = specs
        self._key = hashlib.sha256(key.encode()).digest()
        self._path = os.path.join(root, self._key.hex()[:40] + ".mxprog")
        self._platform = platform

    @classmethod
    def of(cls, root, exe, label, donated):
        """The place in the store under ``root`` of the forward program of
        ``exe``, a bound executor whose program has this label and takes
        these arguments donated."""
        import jax

        from .. import random as _random

        spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        arrays = lambda arrs: tuple(spec(a._jax()) for a in arrs)
        specs = (arrays(exe.arg_arrays), arrays(exe.aux_arrays),
                 spec(_random._constant_key()))
        device = exe._ctx.jax_device
        return cls(root, _program_key(exe, specs, label, donated, device),
                   specs, device.platform)

    def _count(self, what):
        if _tm.enabled():
            _tm.counter("serving.program_store." + what).inc()

    def _stale(self, why):
        log.warning("serving: stored program %s is stale (%s); tracing it "
                    "again", self._path, why)
        self._count("stale")

    def _load(self):
        """The program the store holds under this key, or None."""
        from jax import export

        try:
            with open(self._path, "rb") as f:
                blob = f.read()
        except FileNotFoundError:
            return self._count("miss")
        except OSError as exc:
            return self._stale(exc)
        head = self._MAGIC + self._key
        if not blob.startswith(head):
            return self._stale("it holds another key")
        try:
            exported = export.deserialize(bytearray(blob[len(head):]))
        except Exception as exc:  # a torn or foreign blob
            return self._stale(exc)
        self._count("hit")
        return exported

    def _export(self, traced, specs):
        """``traced`` exported at ``specs`` and written to the store (whole or
        not at all: ``tmp`` + ``os.replace``), or None where it cannot be
        exported; a store that cannot be written is logged, no more."""
        from jax import export

        try:
            exported = export.export(
                traced, platforms=[self._platform])(*specs)
            blob = exported.serialize()
        except Exception as exc:
            log.warning("serving: program %s cannot be exported (%s); it "
                        "runs as traced", self._path, exc)
            return None
        tmp = "%s.%d.tmp" % (self._path, os.getpid())
        try:
            os.makedirs(os.path.dirname(self._path), exist_ok=True)
            with open(tmp, "wb") as f:
                f.write(self._MAGIC + self._key + bytes(blob))
            os.replace(tmp, self._path)
        except OSError as exc:
            log.warning("serving: could not store program %s (%s)",
                        self._path, exc)
        return exported

    def program(self, traced, name, specs=None, donate_argnums=()):
        """What runs in place of the jitted ``traced``, which is called with
        ``specs`` (``self.specs`` unless it takes them in another order): the
        stored program (loaded, or exported from ``traced`` just now) under
        ``jax.jit`` with the same name and donation."""
        import jax

        from ..executor import _named

        exported = self._load()
        if exported is None:
            exported = self._export(traced, specs or self.specs)
        if exported is None:
            return traced

        def call(*args):
            return exported.call(*args)

        return jax.jit(_named(call, name), donate_argnums=donate_argnums)


class PersistentExecutableCache:
    """One pre-compiled grad-less executor per input-shape bucket.

    ``arg_params``/``aux_params`` are {name: NDArray-or-ndarray}; every
    symbol argument that is not a param is an INPUT whose shape the bucket
    key carries. ``model_key`` names the on-disk manifest (defaults to a
    digest of the symbol JSON + dtype). ``program_label`` names the
    compiled program in a profiler trace (``jit_<label>``; executor.py
    ``_GraphProgram.label``). ``dtype`` types every input;
    ``input_dtypes`` ({name: dtype}) names the inputs that differ (a
    bfloat16 KV pool beside float32 token ids and masks). ``donated`` names
    the inputs every bucket's program takes DONATED (a decode step's cache:
    executor.py ``_GraphProgram.donated``): after a ``forward`` the arrays
    they held are dead, the warm one's included, and the owner hands the
    outputs that took their place back.
    """

    def __init__(self, symbol, arg_params=None, aux_params=None, ctx=None,
                 dtype="float32", model_key=None, cache_dir=None,
                 max_executables=None, program_label=None,
                 input_dtypes=None, donated=()):
        from ..context import current_context

        self._sym = symbol
        self._program_label = program_label
        self._donated = tuple(donated)
        self._ctx = ctx or current_context()
        self._dtype = str(dtype)
        self._input_dtypes = {n: str(t) for n, t in
                              sorted((input_dtypes or {}).items())}
        self._arg_params = dict(arg_params or {})
        self._aux_params = dict(aux_params or {})
        # ONE set of param/aux device arrays shared by every bucket
        # executor (a per-bucket simple_bind would hold len(buckets) full
        # weight copies); populated lazily by the first _bind
        self._shared_args: Dict[str, object] = {}
        self._shared_aux: Optional[Dict[str, object]] = None
        # LRU bound for UNSEALED use (the predict API's open-ended reshape
        # surface): past the cap the least-recently-used executor is
        # dropped so distinct shapes can't grow device memory without
        # bound. A sealed cache is fixed-size by construction and never
        # evicts. None/0 = unbounded.
        self._max_exes = int(max_executables or 0) or None
        self._exes: "OrderedDict[tuple, object]" = OrderedDict()
        self._lock = _tm.named_rlock("serving.cache")
        self._sealed = False
        typed = self._dtype + ("|%r" % self._input_dtypes
                               if self._input_dtypes else "")
        digest = hashlib.sha1(
            (symbol.tojson() + "|" + typed).encode()).hexdigest()[:16]
        self._model_key = re.sub(r"[^A-Za-z0-9_.-]+", "_",
                                 model_key or digest)
        self._digest = digest
        self._cache_dir = cache_dir if cache_dir is not None \
            else serve_cache_dir()

    # ------------------------------------------------------------- binding
    @property
    def input_names(self) -> List[str]:
        params = set(self._arg_params)
        return [n for n in self._sym.list_arguments() if n not in params]

    @property
    def sealed(self):
        return self._sealed

    def keys(self):
        with self._lock:
            return list(self._exes)

    def _infer_full(self, input_shapes, symbol=None):
        """Full static shape/type inference at these input shapes (the
        param/aux hints come from the checkpoint) — no bind, no compile.
        ``symbol``: entries of the graph to infer instead of its outputs."""
        from ..base import np_dtype

        shapes = {n: tuple(s) for n, s in input_shapes.items()}
        types = {}
        arg_names = set(self._sym.list_arguments())
        for n, v in self._arg_params.items():
            if n not in arg_names:
                continue  # extra checkpoint entries are ignored, as in
                # the predict API's allow_extra_params behavior
            shapes.setdefault(n, tuple(v.shape))
            types[n] = np.dtype(getattr(v, "dtype", self._dtype)).name
        for n in shapes:
            types.setdefault(n, self._input_dtypes.get(n, self._dtype))
        return (symbol or self._sym)._infer_impl(
            shapes, {k: np_dtype(v) for k, v in types.items()},
            partial=False)

    def output_shapes(self, input_shapes) -> List[tuple]:
        """Statically inferred output shapes at these input shapes.
        Pure inference: safe to probe batch sizes that are not buckets."""
        return [tuple(s) for s in self._infer_full(input_shapes)[1]]

    def _bind(self, input_shapes):
        from ..ndarray import zeros

        arg_name_list = self._sym.list_arguments()
        res = self._infer_full(input_shapes)
        arg_shapes, _, aux_shapes, arg_types, _, aux_types = res
        inputs = set(self.input_names)
        args = {}
        for n, s, t in zip(arg_name_list, arg_shapes, arg_types):
            if n in inputs:
                # input slots are per-bucket: their shape IS the cache key
                args[n] = zeros(s, ctx=self._ctx, dtype=t)
                continue
            arr = self._shared_args.get(n)
            if arr is None:
                arr = zeros(s, ctx=self._ctx, dtype=t)
                if n in self._arg_params:
                    arr[:] = self._arg_params[n]
                self._shared_args[n] = arr
            args[n] = arr
        if self._shared_aux is None:
            self._shared_aux = {}
            for n, s, t in zip(self._sym.list_auxiliary_states(),
                               aux_shapes, aux_types):
                arr = zeros(s, ctx=self._ctx, dtype=t)
                if n in self._aux_params:
                    arr[:] = self._aux_params[n]
                self._shared_aux[n] = arr
        # each bucket gets its OWN graph program (no shared_exec): sharing
        # the jit entry would classify buckets 2..N's warmup compiles as
        # retraces in telemetry, polluting the zero-retrace contract
        exe = self._sym.bind(self._ctx, args, args_grad=None,
                             grad_req="null",
                             aux_states=dict(self._shared_aux))
        # the program is jitted at its first forward: name it before that
        exe._prog.label = self._program_label
        exe._prog.donated = self._donated
        root = _compile_cache.program_store()
        if root is not None:
            exe._prog.store = _StoredProgram.of(
                root, exe, self._program_label, self._donated)
        return exe

    def _retrace_diagnosis(self):
        try:
            from ..analysis import lint

            rep = lint(self._sym, passes=["retrace_guard"])
            return "; ".join("%s: %s" % (d.code, d.message) for d in rep) \
                or ("no GL201-203 pattern in the graph: the shape change "
                    "came from the caller (an unwarmed bucket)")
        except Exception as exc:  # diagnosis must never mask the miss
            return "retrace-guard diagnosis failed: %s" % exc

    def executable(self, input_shapes):
        """Get (or, before ``seal()``, bind+compile) the executor for this
        exact input-shape bucket. A post-seal miss is a hard error: it is
        precisely the call that would have retraced."""
        key = _shape_key(input_shapes)
        exe = self._exes.get(key)
        if exe is not None:
            if self._max_exes and not self._sealed:
                with self._lock:  # LRU recency only matters when evicting
                    if key in self._exes:
                        self._exes.move_to_end(key)
            if _tm.enabled():
                _tm.counter("serving.executable_hit").inc()
            return exe
        with self._lock:
            exe = self._exes.get(key)
            if exe is not None:
                if _tm.enabled():
                    _tm.counter("serving.executable_hit").inc()
                return exe
            if self._sealed:
                raise MXNetError(
                    "serving: post-warmup executable-cache miss for input "
                    "shapes %s (warmed buckets: %s). A miss here would "
                    "retrace+recompile on the request path; retrace-guard "
                    "diagnosis: %s"
                    % (dict(input_shapes),
                       [dict(k) for k in self._exes],
                       self._retrace_diagnosis()))
            with _tm.span("serving.compile", model=self._model_key,
                          shapes=str(dict(input_shapes))):
                exe = self._bind(input_shapes)
                # force the XLA compile NOW (bind only traces lazily):
                # warmup pays it, the request path never does
                exe.forward(is_train=False)
                np.asarray(exe.outputs[0].asnumpy())
            if _tm.enabled():
                _tm.counter("serving.executable_compile").inc()
            self._exes[key] = exe
            if self._max_exes and not self._sealed \
                    and len(self._exes) > self._max_exes:
                old_key, _ = self._exes.popitem(last=False)
                log.info("serving: evicted LRU executable %s from %r "
                         "(cap %d)", dict(old_key), self._model_key,
                         self._max_exes)
                if _tm.enabled():
                    _tm.counter("serving.executable_evict").inc()
            if _tm.enabled():
                # after any eviction, so the gauge is the true live count
                _tm.gauge("serving.executables").set(len(self._exes))
            return exe

    # -------------------------------------------------------------- warmup
    def warmup(self, bucket_shapes: Optional[Sequence[dict]] = None,
               seal=True):
        """Pre-compile one executable per bucket. ``bucket_shapes`` is a
        list of {input_name: shape} dicts; None replays the persisted
        manifest (restart path). Returns the number of warmed buckets.

        Warming ZERO buckets (no/stale manifest on the restart path, or an
        empty list) neither seals nor persists: sealing an empty cache
        would turn every future request into a hard miss with no way back
        — the caller must warm explicit buckets instead."""
        if bucket_shapes is None:
            bucket_shapes = self._load_manifest()
        if not bucket_shapes:
            log.warning("serving: warmup(%s) found no buckets for %r; "
                        "cache left UNSEALED (an empty sealed cache would "
                        "reject every request)",
                        "manifest" if bucket_shapes == [] else bucket_shapes,
                        self._model_key)
            return 0
        with _tm.span("serving.warmup", model=self._model_key,
                      buckets=len(bucket_shapes)):
            for shapes in bucket_shapes:
                self.executable(shapes)
        if seal:
            self.seal()
        self._save_manifest()
        return len(bucket_shapes)

    def seal(self):
        """Freeze the bucket set: from now on any lookup miss raises."""
        self._sealed = True

    # --------------------------------------------------------- persistence
    def _manifest_path(self):
        if not self._cache_dir:
            return None
        return os.path.join(self._cache_dir, _device_kind(),
                            self._model_key + ".json")

    def _save_manifest(self):
        path = self._manifest_path()
        if path is None:
            return
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            buckets = [{n: list(s) for n, s in key} for key in self._exes]
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"model_key": self._model_key,
                           "digest": self._digest, "dtype": self._dtype,
                           "device_kind": _device_kind(),
                           "buckets": buckets}, f, indent=1)
            os.replace(tmp, path)
        except OSError as exc:
            log.warning("serving: could not persist manifest %s (%s)",
                        path, exc)

    def _load_manifest(self):
        path = self._manifest_path()
        if path is None or not os.path.exists(path):
            return []
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError) as exc:
            log.warning("serving: unreadable manifest %s (%s)", path, exc)
            return []
        if rec.get("digest") != self._digest:
            # a different model (or dtype) under the same key: stale
            log.warning("serving: manifest %s digest mismatch "
                        "(model changed); ignoring", path)
            return []
        return [{n: tuple(s) for n, s in b.items()}
                for b in rec.get("buckets", [])]

    # ------------------------------------------------------------ hot swap
    def snapshot_params(self, arg_names=None, aux_names=None):
        """Host-side copies of the named (default: all) loaded arg/aux
        params, consistent under the swap lock — the pre-swap snapshot a
        rollback restores (the fleet replica's ``reload`` takes one
        before applying, so a fleet rollout abort can put the old
        weights back). Unknown names are skipped: ``swap_params`` would
        have refused them before writing anything, so they cannot need
        restoring. Returns ``(arg_params, aux_params)``."""

        def _host(v):
            return np.array(getattr(v, "asnumpy", lambda: v)())

        with self._lock:
            args = {n: _host(self._arg_params[n])
                    for n in (self._arg_params if arg_names is None
                              else arg_names)
                    if n in self._arg_params}
            aux = {n: _host(self._aux_params[n])
                   for n in (self._aux_params if aux_names is None
                             else aux_names)
                   if n in self._aux_params}
        return args, aux

    @staticmethod
    def _swap_value(name, value, target, what):
        """Validate ONE incoming swap value against its target buffer:
        shape must match exactly and the value must be materializable in
        the target's dtype. Both checks (and the cast) happen here, in the
        validation phase, so the later write loop cannot raise halfway and
        leave a mixed old/new weight set."""
        host = np.asarray(getattr(value, "asnumpy", lambda: value)())
        want = tuple(getattr(target, "shape", None) or np.shape(target))
        if tuple(host.shape) != want:
            raise MXNetError(
                "serving: swap_params shape mismatch for %r: got %s, %s "
                "has %s — a reshape would retrace; reload refused"
                % (name, tuple(host.shape), what, want))
        dtype = getattr(target, "dtype", None) or np.asarray(target).dtype
        try:
            return np.asarray(host, dtype=dtype)
        except (TypeError, ValueError) as exc:
            raise MXNetError(
                "serving: swap_params value for %r is not castable to the "
                "bound dtype %s (%s) — reload refused"
                % (name, np.dtype(dtype).name, exc)) from exc

    def swap_params(self, arg_params, aux_params=None):
        """Hitless weight swap (docs/RESILIENCE.md): overwrite the SHARED
        param/aux buffers every bucket executor reads, in place. Shapes
        must match exactly (values are cast to the bound dtype) — a shape
        or unknown-key mismatch raises BEFORE anything is written, so a
        failed swap leaves the old weights fully intact. Same
        shapes/dtypes means the executables' jit signatures are untouched:
        ZERO retraces. jax arrays are immutable, so the in-place NDArray
        assignment allocates fresh device buffers — an in-flight batch
        still materializing against the old buffers is double-buffered by
        construction. Keys absent from ``arg_params`` keep their current
        values (partial swaps are legal)."""
        with self._lock:
            input_names = set(self.input_names)
            updates = []
            for store, incoming, what in (
                    (self._shared_args, arg_params or {}, "argument"),
                    (self._shared_aux, aux_params or {}, "aux state")):
                for n, v in incoming.items():
                    if n in input_names:
                        raise MXNetError(
                            "serving: swap_params(%r) names a model INPUT, "
                            "not a parameter" % n)
                    cur = (store or {}).get(n)
                    if cur is None:
                        # not bound yet (pre-warmup swap): stage into the
                        # source dicts so the first bind picks it up below
                        src = self._arg_params if what == "argument" \
                            else self._aux_params
                        if n not in src:
                            raise MXNetError(
                                "serving: swap_params got unknown %s %r "
                                "(loaded params: %s...)"
                                % (what, n, sorted(src)[:8]))
                        host = self._swap_value(n, v, src[n],
                                                "the loaded checkpoint")
                        updates.append((None, host, n, what))
                        continue
                    updates.append((cur,
                                    self._swap_value(n, v, cur,
                                                     "the loaded model"),
                                    n, what))
            # validation passed for EVERY key — now write (all or nothing)
            for cur, host, n, what in updates:
                if cur is None:
                    (self._arg_params if what == "argument"
                     else self._aux_params)[n] = host
                else:
                    cur[:] = host
                    # keep the source dict consistent for any later bind
                    (self._arg_params if what == "argument"
                     else self._aux_params)[n] = host
        return len(updates)

    # ------------------------------------------------------------- running
    def run(self, inputs: Dict[str, np.ndarray]):
        """One batch through the bucket executable matching the inputs'
        exact shapes. Returns the outputs as numpy arrays."""
        exe = self.executable({n: np.shape(v) for n, v in inputs.items()})
        for n, v in inputs.items():
            exe.arg_dict[n][:] = v
        exe.forward(is_train=False)
        return [o.asnumpy() for o in exe.outputs]
