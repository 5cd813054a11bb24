"""Device capability table (bf16 peak FLOP/s) for MFU accounting.

The reference never needed this — CUDA exposes clock×cores — but TPU peak
comes from public spec sheets keyed on ``device_kind``. Used by bench.py and
callback.Speedometer's MFU display.
"""
from .base import MXNetError

__all__ = ["bf16_peak_flops"]

# public spec-sheet numbers, keyed by the exact ``jax.Device.device_kind``
_PEAK = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
    "TPU v7": 2307e12,
}


def bf16_peak_flops(device_kind):
    """bf16 peak of exactly this device kind. An unknown kind is an error:
    a utilisation computed against a neighbour's peak is a wrong number."""
    try:
        return _PEAK[device_kind]
    except KeyError:
        raise MXNetError(
            "no bf16 peak known for device kind %r (known: %s)"
            % (device_kind, ", ".join(sorted(_PEAK)))) from None
