"""LayerNorm + affine as a differentiable Pallas TPU kernel.

The ``norm_residual`` fusion pattern's kernel lowering: the transformer
zoo's 9-op LayerNorm composition (mean → center → var → rsqrt → scale →
shift) reads its input from HBM three times and writes two normalized
intermediates under XLA; this kernel does the whole normalization on one
resident (block_rows, D) tile in VMEM — one read of x, one write of y.
The per-row moments (mean, rstd) are emitted as tiny (R, 1) side outputs
so the backward re-derives x̂ without re-reducing.

Backward is a second Pallas kernel over the same row tiling: rows are
independent, so every grid step computes its block's dx in VMEM and emits
per-block partial dgamma/dbeta tiles ((8·n_blocks, D), summed by XLA — a
cheap reduction instead of a serialized accumulator, keeping the grid fully
parallel).

Layout: x flattened to (R, D) rows; gamma/beta (D,). ``supported`` gates
on the TPU tiling constraints (D lane-aligned, row blocks sublane-aligned);
``block_candidates`` enumerates the bounded schedule space the autotuner
measures (docs/PERF.md §15). Runs anywhere under Pallas interpret mode,
which is how the CPU tests exercise it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["layer_norm_affine", "supported", "choose_block_rows",
           "block_candidates"]

_ROW_BLOCKS = (256, 128, 64, 32, 16, 8)
_VMEM_BUDGET = 12 * 1024 * 1024


def _rows_of(shape):
    r = 1
    for d in shape[:-1]:
        r *= int(d)
    return r


def choose_block_rows(shape, itemsize=4):
    """The planner-default row-block height: the largest sublane-aligned
    divisor of R whose (br, D) working set (x, y, f32 temps) fits VMEM.
    None when nothing tiles (callers fall back to XLA)."""
    cands = block_candidates(shape, itemsize)
    return cands[0] if cands else None


def block_candidates(shape, itemsize=4):
    """Every valid row-block height for this shape, largest first — the
    bounded schedule space ``fusion_tune`` measures (the head of the list
    is the default candidate)."""
    if len(shape) < 2:
        return []
    R, D = _rows_of(shape), int(shape[-1])
    if D % 128 or R < 8:
        return []
    out = []
    for br in _ROW_BLOCKS:
        if R % br:
            continue
        # x tile + y tile (io dtype, double-buffered) + f32 working copy
        est = 2 * 2 * br * D * itemsize + br * D * 4 + 2 * D * 4
        if est <= _VMEM_BUDGET:
            out.append(br)
    return out


def supported(shape, itemsize=4):
    """Whether this input tiles onto the kernel grid at all."""
    return bool(block_candidates(shape, itemsize))


# --------------------------------------------------------------------- forward
def _fwd_kernel(x_ref, g_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)                # (br, D)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    cent = x - mean
    var = jnp.mean(cent * cent, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = cent * rstd
    g = g_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    y_ref[...] = (xhat * g + b).astype(y_ref.dtype)
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _fold_rows(v):
    """(br, D) f32 → (8, D): row r accumulates into sublane r % 8."""
    return jnp.sum(v.reshape(-1, 8, v.shape[-1]), axis=0)


def _bwd_kernel(x_ref, g_ref, mean_ref, rstd_ref, dy_ref, dx_ref,
                dg_ref, db_ref):
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    mean, rstd = mean_ref[...], rstd_ref[...]
    xhat = (x - mean) * rstd
    g = g_ref[...].astype(jnp.float32)
    # per-block partial parameter grads, one (8, D) tile per grid step: rows
    # fold onto the 8 sublanes (whole-vreg adds, no cross-sublane reduce) and
    # XLA sums the tiles. A (1, D) block would not lower — Mosaic wants the
    # last two block dims in multiples of (8, 128).
    dg_ref[...] = _fold_rows(dy * xhat)
    db_ref[...] = _fold_rows(dy)
    dxhat = dy * g
    m1 = jnp.mean(dxhat, axis=-1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dx_ref[...] = (rstd * (dxhat - m1 - xhat * m2)).astype(dx_ref.dtype)


def _compiler_params(interpret):
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=(pltpu.GridDimensionSemantics.PARALLEL,))


def _fwd_call(x2, gamma, beta, eps, br, interpret):
    from jax.experimental import pallas as pl

    R, D = x2.shape
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(R // br,),
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, D), x2.dtype),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(x2, gamma.reshape(1, D), beta.reshape(1, D))


def _bwd_call(x2, gamma, mean, rstd, dy2, br, interpret):
    from jax.experimental import pallas as pl

    R, D = x2.shape
    nb = R // br
    dx, dg_part, db_part = pl.pallas_call(
        _bwd_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, D), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((8, D), lambda i: (i, 0)),
            pl.BlockSpec((8, D), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, D), x2.dtype),
            jax.ShapeDtypeStruct((nb * 8, D), jnp.float32),
            jax.ShapeDtypeStruct((nb * 8, D), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(x2, gamma.reshape(1, D), mean, rstd, dy2)
    return dx, jnp.sum(dg_part, axis=0), jnp.sum(db_part, axis=0)


# ------------------------------------------------------------------ custom vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ln(x2, gamma, beta, eps, br, interpret):
    return _fwd_call(x2, gamma, beta, eps, br, interpret)[0]


def _ln_fwd(x2, gamma, beta, eps, br, interpret):
    y, mean, rstd = _fwd_call(x2, gamma, beta, eps, br, interpret)
    return y, (x2, gamma, mean, rstd)


def _ln_bwd(eps, br, interpret, res, dy):
    x2, gamma, mean, rstd = res
    dx, dg, db = _bwd_call(x2, gamma, mean, rstd, dy, br, interpret)
    return dx, dg.astype(gamma.dtype), db.astype(gamma.dtype)


_ln.defvjp(_ln_fwd, _ln_bwd)


def _interpret_mode():
    return jax.default_backend() != "tpu"


def layer_norm_affine(x, gamma, beta, eps=1e-5, block_rows=None,
                      interpret=None):
    """``(x − E[x]) · rsqrt(Var[x] + eps) · gamma + beta`` over the last
    axis, one VMEM-resident tile per row block. Differentiable
    (custom_vjp Pallas backward). Callers gate with ``supported()``;
    ``block_rows`` overrides the planner default (the autotuner's schedule
    axis)."""
    shape = x.shape
    D = int(shape[-1])
    if interpret is None:
        interpret = _interpret_mode()
    br = block_rows if block_rows is not None else choose_block_rows(
        shape, jnp.dtype(x.dtype).itemsize)
    if br is None or br not in block_candidates(
            shape, jnp.dtype(x.dtype).itemsize):
        raise ValueError("layer_norm_affine: shape %s does not tile at "
                         "block_rows=%r (gate with supported())"
                         % (shape, block_rows))
    y = _ln(x.reshape(-1, D), gamma, beta, float(eps), int(br),
            bool(interpret))
    return y.reshape(shape)
