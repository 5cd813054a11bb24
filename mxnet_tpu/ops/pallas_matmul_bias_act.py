"""Matmul with a bias + activation epilogue, as a Pallas TPU kernel.

The matmul+bias+act chain is the shape "Operator Fusion in XLA" (PAPERS.md)
calls out as the one XLA reassociates poorly around the MXU: the bias add
and activation are a separate elementwise pass that re-reads the matmul
output from HBM. This kernel applies both on the f32 MXU accumulator while
the output tile is still in VMEM — one HBM write for the activated output,
zero extra reads:

    C = act(A @ Wᵀ + b)        A: (M, K)  W: (N, K)  b: (N,)

W rides in the framework's FullyConnected layout (N, K); the kernel
contracts over each operand's axis 1 directly (``dot_general``), so no
transpose materializes. Grid (N/bn, M/bm) with K whole per tile.

Backward is deliberately XLA (``custom_vjp``): dpre is recovered FROM THE
ACTIVATED OUTPUT (relu: mask(y>0); sigmoid: y(1−y); tanh: 1−y²; softrelu:
1−e^{−y}), so no pre-activation stash exists — the three backward matmuls
are plain MXU ops XLA already schedules well. Gating is the pattern
engine's job (``ops/fusion_patterns.py`` + the fusion_tune measured
verdict); this module only refuses shapes that do not tile (``supported``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["matmul_bias_act", "supported", "block_candidates",
           "ACTIVATIONS"]

# activation -> (apply on f32, derivative from the ACTIVATED output)
ACTIVATIONS = {
    "relu": (lambda p: jnp.maximum(p, 0.0),
             lambda y: (y > 0).astype(jnp.float32)),
    "sigmoid": (jax.nn.sigmoid, lambda y: y * (1.0 - y)),
    "tanh": (jnp.tanh, lambda y: 1.0 - y * y),
    # y = log1p(e^p)  =>  act'(p) = sigmoid(p) = 1 - e^{-y}
    "softrelu": (lambda p: jnp.logaddexp(p, 0.0),
                 lambda y: 1.0 - jnp.exp(-y)),
}


def supported(m, k, n, act, block_m=512, block_n=256, itemsize=2):
    """Whether (M, K) @ (N, K)ᵀ tiles within the VMEM budget (K whole per
    tile, bm % 8, bn % 128)."""
    if act not in ACTIVATIONS:
        return False
    bm, bn = min(block_m, m), min(block_n, n)
    vmem = (bm * k + k * bn) * itemsize + bm * bn * 4 + bn * 4
    return (m % bm == 0 and n % bn == 0 and bm % 8 == 0 and bn % 128 == 0
            and vmem <= 12 * 1024 * 1024)


def _kernel(a_ref, w_ref, b_ref, y_ref, *, act):
    p = jax.lax.dot_general(a_ref[...], w_ref[...],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    p = p + b_ref[...].astype(jnp.float32)
    y_ref[...] = ACTIVATIONS[act][0](p).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("act", "block_m", "block_n",
                                             "interpret"))
def _fwd_call(a, w, b, act, block_m, block_n, interpret):
    import jax.experimental.pallas as pl

    M, K = a.shape
    N = w.shape[0]
    bm, bn = min(block_m, M), min(block_n, N)
    assert supported(M, K, N, act, bm, bn, itemsize=a.dtype.itemsize), (
        a.shape, w.shape, a.dtype, act)
    m_tiles, n_tiles = M // bm, N // bn

    from jax.experimental.pallas import tpu as pltpu

    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=(pltpu.GridDimensionSemantics.PARALLEL,
                             pltpu.GridDimensionSemantics.PARALLEL))
    return pl.pallas_call(
        functools.partial(_kernel, act=act),
        grid=(n_tiles, m_tiles),
        in_specs=[
            pl.BlockSpec((bm, K), lambda n, m: (m, 0)),
            pl.BlockSpec((bn, K), lambda n, m: (n, 0)),
            pl.BlockSpec((1, bn), lambda n, m: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda n, m: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        compiler_params=params,
        interpret=interpret,
    )(a, w, b.reshape(1, N))


def _interpret_mode():
    return jax.default_backend() != "tpu"


def block_candidates(m, k, n, act, itemsize=2):
    """The bounded (block_m, block_n) schedule space the autotuner measures
    for this shape (docs/PERF.md §15): the planner default first, then the
    supported variants with a DISTINCT effective tiling (a variant that
    clamps to the same (bm, bn) as the default would measure the identical
    program twice)."""
    seen, out = set(), []
    for bm, bn in ((512, 256), (256, 256), (512, 128), (256, 128),
                   (128, 256), (1024, 256), (512, 512)):
        eff = (min(bm, m), min(bn, n))
        if eff in seen or not supported(m, k, n, act, bm, bn, itemsize):
            continue
        seen.add(eff)
        out.append((bm, bn))
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def matmul_bias_act(a, w, b, act="relu", block_m=512, block_n=256):
    """``act(a @ w.T + b)`` with the epilogue fused into the matmul tile.

    a: (M, K), w: (N, K), b: (N,); output keeps ``a.dtype``, epilogue math
    in f32 from the MXU accumulator. Callers gate with ``supported()``;
    ``block_m``/``block_n`` are the autotuner's schedule axis (defaults =
    the planner-default tiling). Interpret mode engages automatically
    off-TPU (parity tests on CPU).
    """
    return _fwd_call(a, w, b, act, block_m, block_n, _interpret_mode())


def _mba_fwd(a, w, b, act, block_m, block_n):
    y = _fwd_call(a, w, b, act, block_m, block_n, _interpret_mode())
    return y, (a, w, b, y)


def _mba_bwd(act, block_m, block_n, saved, dy):
    a, w, b, y = saved
    dpre = dy.astype(jnp.float32) * ACTIVATIONS[act][1](
        y.astype(jnp.float32))
    dpre_c = dpre.astype(a.dtype)
    da = jax.lax.dot_general(dpre_c, w, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32).astype(a.dtype)
    dw = jax.lax.dot_general(dpre_c, a, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32).astype(w.dtype)
    db = jnp.sum(dpre, axis=0)
    return da, dw, db.astype(b.dtype)


matmul_bias_act.defvjp(_mba_fwd, _mba_bwd)
