"""Random number API.

Replaces the reference's python/mxnet/random.py + per-device mshadow::Random
resources (src/resource.cc:144 ResourceRandom). State is a single JAX PRNG key
split per draw — functional and reproducible across backends, unlike the
stateful per-device generators of the reference.
"""
from __future__ import annotations

import numpy as np

__all__ = ["seed", "uniform", "normal"]

_KEY = None
_CONSTANT_KEY = None


def _next_key():
    global _KEY
    import jax

    if _KEY is None:
        _KEY = jax.random.PRNGKey(np.random.randint(0, 2**31 - 1))
    _KEY, sub = jax.random.split(_KEY)
    return sub


def _constant_key():
    """One key, made once a process, for a program that has no random node
    and so ignores its key: the same shape and type as a drawn one (no
    retrace), and the global stream stays where it was."""
    global _CONSTANT_KEY
    if _CONSTANT_KEY is None:
        import jax

        _CONSTANT_KEY = jax.random.PRNGKey(0)
    return _CONSTANT_KEY


def _next_seed() -> int:
    """A fresh host-side integer seed derived from the global key (for numpy-
    based initializers like Orthogonal that need CPU linear algebra)."""
    import jax

    return int(jax.random.randint(_next_key(), (), 0, 2**31 - 1))


def seed(seed_state: int):
    """Seed the global generator (reference: mx.random.seed → MXRandomSeed)."""
    global _KEY
    import jax

    _KEY = jax.random.PRNGKey(int(seed_state))
    np.random.seed(int(seed_state) & 0x7FFFFFFF)


def refresh_backend():
    """Re-materialize the global key on the CURRENT backend (elastic
    re-form, docs/FAULT_TOLERANCE.md): the key's device buffer belongs to
    the torn-down backend, and if its last ``split`` dispatched into the
    failed collective era its definition event is poisoned — the first
    post-re-form draw would then die with the OLD generation's transport
    error. A key whose buffer is unreadable is dropped; the next draw
    re-seeds (weights/optimizer state come from the checkpoint, so RNG
    continuity across a crash is best-effort by design)."""
    global _KEY, _CONSTANT_KEY
    _CONSTANT_KEY = None  # its buffer is the old backend's too; remade on use
    if _KEY is None:
        return
    import jax.numpy as jnp

    try:
        host = np.asarray(_KEY)
    except Exception:
        _KEY = None
        return
    _KEY = jnp.asarray(host)


def uniform(low=0.0, high=1.0, shape=(1,), ctx=None, dtype=np.float32, out=None):
    from .ndarray import imperative_invoke
    from .context import current_context

    attrs = {"low": low, "high": high, "shape": shape, "dtype": dtype}
    return imperative_invoke("random_uniform", [], attrs, ctx=ctx or current_context(), out=out)[0]


def normal(loc=0.0, scale=1.0, shape=(1,), ctx=None, dtype=np.float32, out=None):
    from .ndarray import imperative_invoke
    from .context import current_context

    attrs = {"loc": loc, "scale": scale, "shape": shape, "dtype": dtype}
    return imperative_invoke("random_normal", [], attrs, ctx=ctx or current_context(), out=out)[0]
