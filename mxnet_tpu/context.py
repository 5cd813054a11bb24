"""Device contexts.

TPU-native re-design of the reference's ``Context`` (include/mxnet/base.h:116-207,
python/mxnet/context.py). A ``Context`` names a logical device: ``cpu(i)``,
``tpu(i)``, or ``gpu(i)``. On this build the accelerator is a TPU; ``gpu(i)``
is accepted for script compatibility and resolves to the TPU chip when no GPU
exists, so reference training scripts run unmodified with their ``--gpus`` flags.

Each Context resolves lazily to a concrete ``jax.Device``. ``cpu(i)`` for i>0
maps onto virtual host devices when ``--xla_force_host_platform_device_count``
is set (the multi-device-without-hardware test trick, SURVEY.md §4), else all
cpu ids alias device 0 — same semantics as the reference where cpu dev_id is a
hint (include/mxnet/base.h:141-143). An accelerator id is not a hint:
``tpu(i)``/``gpu(i)`` with ``i`` past the visible chips raises ``MXNetError``.
"""
from __future__ import annotations

import threading

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_tpus"]


class Context:
    """Logical device context, usable as a ``with`` scope like the reference."""

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in Context.devstr2type:
                raise MXNetError("unknown device type %r" % (device_type,))
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self) -> str:
        return Context.devtype2str[self.device_typeid]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __enter__(self):
        self._old_ctx = getattr(Context._default_ctx, "value", None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # -- JAX resolution ----------------------------------------------------
    @property
    def jax_device(self):
        """Resolve to a concrete jax.Device."""
        import jax

        if self.device_type in ("cpu", "cpu_pinned"):
            # local_devices: in a multi-process job each process may only
            # address its own devices (jax.devices() lists the whole job's)
            devs = jax.local_devices(backend="cpu")
            return devs[self.device_id % len(devs)]
        # an accelerator id names one chip: tpu(3) on a one-chip host is an
        # error, never chip 0 under another name
        accels = _accelerator_devices()
        if not 0 <= self.device_id < len(accels):
            raise MXNetError(
                "%r: this process sees %d accelerator device(s)"
                % (self, len(accels)))
        return accels[self.device_id]

    def empty_cache(self):  # parity with later mxnet; no-op under PJRT
        pass


def _accelerator_devices():
    """This process's non-CPU devices. A backend that was asked for
    (``JAX_PLATFORMS``) and failed to initialise raises from here — that is
    an error, not "no accelerator"."""
    import jax

    return [d for d in jax.local_devices() if d.platform != "cpu"]


def cpu(device_id=0):
    return Context("cpu", device_id)


def gpu(device_id=0):
    """GPU context; resolves to the TPU on GPU-less TPU hosts (compat shim)."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def num_gpus():
    return len(_accelerator_devices())


def num_tpus():
    return len(_accelerator_devices())


def current_context() -> Context:
    ctx = getattr(Context._default_ctx, "value", None)
    if ctx is None:
        import os

        forced = os.environ.get("MXNET_DEFAULT_CONTEXT", "")
        if forced:
            name, _, idx = forced.partition(":")
            ctx = Context(name, int(idx or 0))
        else:
            # TPU-first: default to the accelerator when present, else cpu.
            ctx = tpu(0) if _accelerator_devices() else cpu(0)
        Context._default_ctx.value = ctx
    return ctx
