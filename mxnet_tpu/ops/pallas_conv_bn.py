"""Fused conv + BatchNorm Pallas TPU kernel stack (round-5 performance work).

docs/PERF.md's roofline analysis pins the ResNet-50 step at the v5e HBM
roofline (72.3 GB/step at 809 of 819 GB/s): every path to >=0.35 MFU is a
bytes-cut, and the one remaining lever is the hand-fused conv+BN kernel —
the TPU counterpart of the reference's vendor conv kernels
(/root/reference/src/operator/cudnn_convolution-inl.h) behind its published
speed table (example/image-classification/README.md:149-156).

This module is that kernel. For NCHW activations viewed as ``(B, K, H*W)``
(a free reshape — no transposes), one Pallas kernel computes

    c[b, n, hw] = sum_k w[n, k] * xn[b, k, hw]            (1x1 conv = matmul)
    c[b, n, hw] = sum_{k,t} w[t, n, k] * shift_t(xn)[b, k, hw]   (3x3, 9 taps)

with three fusions XLA cannot do (a convolution cannot be a fusion producer):

- **prologue**: ``xn = relu(x * scale + shift)`` applied in VMEM — the
  upstream BatchNorm+ReLU output is never materialized in HBM. In the
  pre-activation ResNet chain (BN -> relu -> Conv, models/resnet.py) this
  deletes one full activation write + read per edge.
- **residual epilogue**: ``c += res`` read tile-wise — the bottleneck-block
  skip add costs no separate read-read-write pass.
- **stats epilogue**: per-channel ``sum(c)`` and ``sum(c^2)`` accumulated
  from the f32 MXU accumulator across the (B,) grid sweep — the downstream
  BatchNorm's statistics pass re-reads nothing.

Layout: grid ``(N/bn, B)`` (channel stripes parallel, batch sweep carries
the stats accumulator); blocks keep the whole HW extent per instance (every
ResNet-50 @224 shape fits VMEM this way — see ``choose_blocks``). The 3x3
taps are static-slice rolls of the VMEM-resident xn tile with
host-precomputed edge masks applied to the dot *result* (a per-column mask
commutes with the contraction over K).

The autodiff boundary is exactly this kernel (``conv_block`` is a
custom_vjp). The backward has its own Pallas kernel family (the ``bwd``
argument selects it): one fused dgrad+wgrad kernel over grid ``(K/bk, B)``
that consumes the output cotangent tile-wise, folds the stats cotangents
(``dc_eff = dc + ds + 2*c*dq`` from the saved output) and the BN-prologue
backward (``relu'(xn) * scale * dxn``) in VMEM — neither the effective
cotangent nor the pre-activation gradient is ever materialized in HBM — and
accumulates ``dw[t, n, k] = sum_{b,hw} dc_eff·xn`` from the same resident
tiles in an f32 accumulator across the B sweep. Two residual policies:

- **recompute** (default): the backward re-derives ``xn = relu(x*scale +
  shift)`` from the raw input tile it streams anyway (for ``dscale``) —
  zero extra HBM traffic, a few VPU ops per element.
- **stash**: the forward emits ``xn`` as an extra output (one HBM write)
  and the backward streams it back, skipping the prologue recompute. Costs
  bytes, saves VPU — per-shape measurement (``tools/fused_stats_bench.py``)
  decides, like TVM's learned schedule tables.

``bwd="xla"`` keeps the round-5 behavior: ``jax.vjp`` of the equivalent XLA
convolution (the primal conv is dead code and DCE'd). All BN scalar math
(mean/var/normalize, moving-stat updates) stays in plain JAX in the graph
pass (executor fusion plan) so gradients flow through it naturally.
Numerics note: the kernel's statistics come from the f32 accumulator
*before* the bf16 round of c; XLA's unfused lowering reduces the rounded
activations — they differ at the bf16-epsilon level, inside BN's eps regime.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["conv_block", "supported", "plan_blocks", "choose_blocks",
           "bn_candidates", "plan_bwd_blocks", "choose_bwd_blocks"]

_VMEM_BUDGET = 12 * 1024 * 1024
# Mosaic's default scoped-VMEM limit on v5e. The backward planner's estimate
# is an upper bound of the scoped allocation (measured against the compiler's
# own figures: it runs 15-30% over), so it is held to the limit itself.
_SCOPED_VMEM_LIMIT = 16 * 1024 * 1024


def choose_blocks(B, K, N, HW, itemsize, taps=1, prologue=False, res=False,
                  emit_xn=False):
    """Pick the channel-stripe width ``bn`` (largest divisor of N, multiple
    of 8, that keeps the per-instance VMEM working set under budget) for the
    whole-HW tiling. Returns None if no stripe fits. ``emit_xn`` budgets the
    stash policy's extra xn output stream."""
    cands = bn_candidates(B, K, N, HW, itemsize, taps=taps,
                          prologue=prologue, res=res, emit_xn=emit_xn)
    return cands[0] if cands else None


def bn_candidates(B, K, N, HW, itemsize, taps=1, prologue=False, res=False,
                  emit_xn=False):
    """Every channel-stripe width that tiles within the VMEM budget,
    largest (the planner default) first — the forward kernel's bounded
    schedule space the autotuner measures (docs/PERF.md §15)."""
    out = []
    for bn in (512, 256, 128, 64, 32, 16, 8):
        if N % bn:
            continue
        est = (
            2 * K * HW * itemsize          # x tile, double-buffered
            + 2 * bn * HW * itemsize       # c tile, double-buffered
            + bn * HW * 4                  # f32 accumulator
            + taps * bn * K * itemsize     # weight stripe
            + (2 * bn * HW * itemsize if res else 0)  # residual stream, db
            + (K * HW * itemsize if (prologue or taps > 1) else 0)  # xn temp
            + (K * HW * itemsize if taps > 1 else 0)                # shifted temp
            + (taps * HW * 4 if taps > 1 else 0)                    # masks
            + (2 * K * HW * itemsize if emit_xn else 0)  # stashed xn out, db
        )
        if est <= _VMEM_BUDGET:
            out.append(bn)
    return out


def strided_dims(H, W, stride):
    """Post-stride spatial dims as the forward computes them: the kernel
    slices ``x[:, :, ::s, ::s]``, which keeps ``ceil(H/s)`` rows for odd H
    (matching XLA's pad-0 stride-s output). Every consumer of a strided
    shape — ``plan_blocks``, ``fusion.gate``, the WINS-table key — must use
    THIS arithmetic; a floor here once sent odd spatial dims near the VMEM
    budget into an in-jit assert instead of the XLA fallback."""
    return (H + stride[0] - 1) // stride[0], (W + stride[1] - 1) // stride[1]


def _conv_geometry(x_shape, w_shape, stride, itemsize):
    """Shared structural gate of the fwd and bwd planners: (B, K, N, HW,
    taps) for an eligible call, else None."""
    if len(x_shape) != 4 or len(w_shape) != 4 or itemsize > 4:
        return None
    B, K, H, W = x_shape
    N, K2, kh, kw = w_shape
    if K != K2:
        return None
    if (kh, kw) == (1, 1):
        if stride[0] != stride[1] or stride[0] not in (1, 2):
            return None
        H, W = strided_dims(H, W, stride)
        taps = 1
    elif (kh, kw) == (3, 3):
        if stride != (1, 1):
            return None
        taps = 9
    else:
        return None
    if K % 8 or H * W < 8:
        return None
    return B, K, N, H * W, taps


def plan_blocks(x_shape, w_shape, stride=(1, 1), itemsize=2, prologue=True,
                res=False, emit_xn=False):
    """The kernel's tiling decision for a concrete call: the channel-stripe
    width ``bn``, or None when this conv cannot (or should not) run on the
    Pallas path. This is the single source of truth — ``supported`` and the
    forward both call it with the SAME flags (itemsize, prologue, residual,
    xn stash), so a call that passes the gate can never hit an internal
    assert instead of the XLA fallback."""
    geo = _conv_geometry(x_shape, w_shape, stride, itemsize)
    if geo is None:
        return None
    B, K, N, HW, taps = geo
    return choose_blocks(B, K, N, HW, itemsize, taps=taps,
                         prologue=prologue, res=res, emit_xn=emit_xn)


def _lanes(n):
    """Extent of a minor dim in VMEM: padded to whole 128-lane vregs."""
    return -(-n // 128) * 128


def choose_bwd_blocks(B, K, N, HW, itemsize, taps=1, prologue=False,
                      res=False, stash=False):
    """Pick the input-channel stripe width ``bk`` for the fused backward
    (dgrad+wgrad) kernel — largest divisor of K keeping the per-instance
    VMEM working set under the compiler's limit — or None when the backward
    cannot run on the Pallas path. The estimate counts what Mosaic
    allocates: every pipelined block twice (double buffering), minor dims
    padded to 128 lanes (HW = 49 occupies 128; an (N, 1) column occupies
    (N, 128)), the dw accumulator beside its output block. An estimate that
    missed these passed shapes the compiler then refused for scoped VMEM."""
    hw = _lanes(HW)
    col = 128 * 4                       # one row of an (n, 1) f32 block
    for bk in (512, 256, 128, 64, 32, 16, 8):
        # bk is the LANE dim of the (N, bk) weight stripe and the dw block:
        # Mosaic takes whole-array or 128-multiple lane blocks only
        if K % bk or (bk != K and bk % 128):
            continue
        wk = taps * N * _lanes(bk)      # weight-stripe elements
        est = (
            2 * 2 * N * hw * itemsize       # dc + c tiles, double-buffered
            + 2 * 2 * N * col               # ds + dq columns, db
            + N * hw * (4 + itemsize)       # dc_eff f32 + rounded copy
            + 2 * wk * itemsize             # weight stripe, db
            + 2 * bk * hw * itemsize        # x tile, double-buffered
            + (2 * bk * hw * itemsize if stash else 0)      # stashed xn
            + bk * hw * 4                   # da f32 accumulator
            + (bk * hw * 4 if taps > 1 else 0)              # rolled part
            + (N * hw * itemsize if taps > 1 else 0)        # masked cot.
            + (2 * taps * 8 * hw * 4 if taps > 1 else 0)    # edge masks, db
            + 2 * bk * hw * itemsize        # dx tile, double-buffered
            + 3 * wk * 4                    # dw accumulator + out block, db
            + (7 * bk * col if prologue else 0)  # scale/shift/dscale/dshift
            + (2 * N * hw * itemsize if res else 0)         # dres tile, db
        )
        if est <= _SCOPED_VMEM_LIMIT:
            return bk
    return None


def plan_bwd_blocks(x_shape, w_shape, stride=(1, 1), itemsize=2,
                    prologue=True, res=False, stash=False):
    """Tiling decision for the fused backward kernel (the ``choose_blocks``
    counterpart of the dgrad/wgrad family): the K-stripe width ``bk``, or
    None when the backward must take the XLA fallback. Single source of
    truth for the backward gate — ``fusion.bwd_mode`` and the backward
    dispatcher both call it with the same flags."""
    geo = _conv_geometry(x_shape, w_shape, stride, itemsize)
    if geo is None:
        return None
    B, K, N, HW, taps = geo
    return choose_bwd_blocks(B, K, N, HW, itemsize, taps=taps,
                             prologue=prologue, res=res, stash=stash)


def supported(x_shape, w_shape, stride=(1, 1), itemsize=2, prologue=True,
              res=False):
    """Whether the Pallas path can run this conv at all (the per-shape
    win/lose decision against XLA is the WINS table in
    fused_conv_bn_table.py, not this predicate). Defaults assume the bf16
    training path with a prologue — pass the real flags for exact answers."""
    return plan_blocks(x_shape, w_shape, stride, itemsize, prologue,
                       res) is not None


def _shift_masks(H, W):
    """(9, 1, HW) f32 validity masks for the 3x3 taps at pad=1. Tap t =
    (dy+1)*3 + (dx+1) reads input position (h+dy, w+dx); a flattened-HW roll
    wraps row edges, so the mask zeroes every column whose source falls
    outside the image."""
    row = np.arange(H * W) // W
    col = np.arange(H * W) % W
    masks = np.zeros((9, 1, H * W), np.float32)
    for dy in (-1, 0, 1):
        for dx in (-1, 0, 1):
            ok = ((row + dy >= 0) & (row + dy < H)
                  & (col + dx >= 0) & (col + dx < W))
            masks[(dy + 1) * 3 + (dx + 1), 0] = ok
    return masks


def _roll_cols(a, s, hw):
    """xs[:, j] = a[:, (j + s) % hw] via static slices (Mosaic-friendly)."""
    s %= hw
    if s == 0:
        return a
    return jnp.concatenate([a[:, s:], a[:, :s]], axis=1)


def _kernel(*refs, b_steps, bn, hw, taps, shifts, relu, has_prologue,
            has_res, emit_xn=False, emit_stats=True):
    import jax.experimental.pallas as pl

    it = iter(refs)
    x_ref = next(it)
    w_ref = next(it)
    mask_ref = next(it) if taps > 1 else None
    scale_ref = next(it) if has_prologue else None
    shift_ref = next(it) if has_prologue else None
    res_ref = next(it) if has_res else None
    c_ref = next(it)
    sum_ref = next(it) if emit_stats else None
    sq_ref = next(it) if emit_stats else None
    xn_ref = next(it) if emit_xn else None
    acc_s, acc_q = it if emit_stats else (None, None)

    b = pl.program_id(1)

    if emit_stats:
        @pl.when(b == 0)
        def _init():
            acc_s[...] = jnp.zeros_like(acc_s)
            acc_q[...] = jnp.zeros_like(acc_q)

    xn = x_ref[0]  # (K, HW)
    if has_prologue:
        xn = xn * scale_ref[...] + shift_ref[...]
        if relu:
            xn = jnp.maximum(xn, jnp.zeros_like(xn))
    if emit_xn:
        # stash policy: the normalized activation goes to HBM for the
        # backward. The (b, 0, 0) block is revisited once per n stripe;
        # every visit writes the SAME value (xn is computed per instance
        # anyway), so the duplicate write-backs are benign.
        xn_ref[0] = xn

    if taps == 1:
        c32 = jnp.dot(w_ref[...], xn, preferred_element_type=jnp.float32)
    else:
        c32 = jnp.zeros((bn, hw), jnp.float32)
        for t in range(taps):
            part = jnp.dot(w_ref[t], _roll_cols(xn, shifts[t], hw),
                           preferred_element_type=jnp.float32)
            c32 = c32 + part * mask_ref[t]
    if has_res:
        c32 = c32 + res_ref[0].astype(jnp.float32)
    c_ref[0] = c32.astype(c_ref.dtype)
    if emit_stats:
        acc_s[...] += jnp.sum(c32, axis=1, keepdims=True)
        acc_q[...] += jnp.sum(c32 * c32, axis=1, keepdims=True)

        @pl.when(b == b_steps - 1)
        def _flush():
            sum_ref[...] = acc_s[...]
            sq_ref[...] = acc_q[...]


@functools.partial(jax.jit, static_argnames=("kernel_hw", "stride", "relu",
                                             "interpret", "emit_xn",
                                             "emit_stats", "bn_override"))
def _conv_block_fwd_impl(x, w, scale, shift, res, *, kernel_hw, stride,
                         relu, interpret, emit_xn=False, emit_stats=True,
                         bn_override=None):
    """Pallas forward. x (B,K,H,W); w (N,K,kh,kw); scale/shift (K,) or None;
    res (B,N,H',W') or None. Returns (c, ssum, ssq) plus the materialized
    prologue activation xn (post-stride shape) when ``emit_xn`` (the
    backward stash policy). ``emit_stats=False`` (grad-less inference)
    elides the statistics epilogue entirely and returns just ``c``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    assert emit_stats or not emit_xn  # xn stash is a backward-only policy
    B, K, H, W = x.shape
    N = w.shape[0]
    kh, kw = kernel_hw
    if (kh, kw) == (1, 1) and stride != (1, 1):
        x = x[:, :, :: stride[0], :: stride[1]]
        B, K, H, W = x.shape
    HW = H * W
    taps = kh * kw
    dt = x.dtype
    has_prologue = scale is not None
    cands = bn_candidates(B, K, N, HW, dt.itemsize, taps=taps,
                          prologue=has_prologue, res=res is not None,
                          emit_xn=emit_xn)
    # the autotuner's measured stripe wins when it still tiles; anything
    # else (stale schedule, flag drift) silently demotes to the planner pick
    bn = bn_override if bn_override in cands else (
        cands[0] if cands else None)
    assert bn is not None, (x.shape, w.shape)  # callers gate via plan_blocks
    n_tiles = N // bn

    x3 = x.reshape(B, K, HW)
    inputs = [x3]
    in_specs = [pl.BlockSpec((1, K, HW), lambda n, b: (b, 0, 0))]
    if taps == 1:
        inputs.append(w.reshape(N, K))
        in_specs.append(pl.BlockSpec((bn, K), lambda n, b: (n, 0)))
        shifts = (0,)
    else:
        # (N,K,3,3) -> (9, N, K): tap-major so each w_ref[t] is a (bn, K)
        # stripe with K in lanes
        inputs.append(jnp.transpose(w.reshape(N, K, taps), (2, 0, 1)))
        in_specs.append(pl.BlockSpec((taps, bn, K), lambda n, b: (0, n, 0)))
        inputs.append(jnp.asarray(_shift_masks(H, W)))
        in_specs.append(pl.BlockSpec((taps, 1, HW), lambda n, b: (0, 0, 0)))
        shifts = tuple(dy * W + dx for dy in (-1, 0, 1) for dx in (-1, 0, 1))
    if has_prologue:
        inputs.append(scale.astype(dt).reshape(K, 1))
        inputs.append(shift.astype(dt).reshape(K, 1))
        in_specs.append(pl.BlockSpec((K, 1), lambda n, b: (0, 0)))
        in_specs.append(pl.BlockSpec((K, 1), lambda n, b: (0, 0)))
    if res is not None:
        inputs.append(res.reshape(B, N, HW))
        in_specs.append(pl.BlockSpec((1, bn, HW), lambda n, b: (b, n, 0)))

    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=(pltpu.GridDimensionSemantics.PARALLEL,
                             pltpu.GridDimensionSemantics.ARBITRARY))
    out_specs = [pl.BlockSpec((1, bn, HW), lambda n, b: (b, n, 0))]
    out_shape = [jax.ShapeDtypeStruct((B, N, HW), dt)]
    scratch = []
    if emit_stats:
        out_specs += [pl.BlockSpec((bn, 1), lambda n, b: (n, 0)),
                      pl.BlockSpec((bn, 1), lambda n, b: (n, 0))]
        out_shape += [jax.ShapeDtypeStruct((N, 1), jnp.float32),
                      jax.ShapeDtypeStruct((N, 1), jnp.float32)]
        scratch = [pltpu.VMEM((bn, 1), jnp.float32),
                   pltpu.VMEM((bn, 1), jnp.float32)]
    if emit_xn:
        out_specs.append(pl.BlockSpec((1, K, HW), lambda n, b: (b, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, K, HW), dt))
    outs = pl.pallas_call(
        functools.partial(
            _kernel, b_steps=B, bn=bn, hw=HW, taps=taps, shifts=shifts,
            relu=relu, has_prologue=has_prologue, has_res=res is not None,
            emit_xn=emit_xn, emit_stats=emit_stats),
        grid=(n_tiles, B),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=interpret,
    )(*inputs)
    if not emit_stats:
        return outs[0].reshape(B, N, H, W)
    c, s, q = outs[:3]
    if emit_xn:
        return (c.reshape(B, N, H, W), s[:, 0], q[:, 0],
                outs[3].reshape(B, K, H, W))
    return c.reshape(B, N, H, W), s[:, 0], q[:, 0]


_DNUMS = ("NCHW", "OIHW", "NCHW")


def _preferred(dtype):
    """preferred_element_type for the XLA conv — only when it matches the
    input dtype. Requesting f32 output from a bf16 conv makes jax.vjp's
    transpose call conv(g_f32, w_bf16), which this jax version rejects; the
    backend accumulates bf16 convs in f32 internally either way, so the
    explicit request only ever mattered for the output rounding point."""
    pet = jnp.promote_types(dtype, jnp.float32)
    return pet if pet == dtype else None


def _xla_conv(x, w, scale, shift, res, kernel_hw, stride, relu):
    """The pure-XLA reference of the fused forward (also the fallback path
    and the backward's differentiation target)."""
    if scale is not None:
        bshape = (1, -1, 1, 1)
        xn = x * scale.astype(x.dtype).reshape(bshape) \
            + shift.astype(x.dtype).reshape(bshape)
        if relu:
            xn = jnp.maximum(xn, 0)
    else:
        xn = x
    pad = (kernel_hw[0] - 1) // 2
    c = jax.lax.conv_general_dilated(
        xn, w, window_strides=stride, padding=[(pad, pad), (pad, pad)],
        dimension_numbers=_DNUMS,
        preferred_element_type=_preferred(x.dtype),
    ).astype(x.dtype)
    if res is not None:
        c = c + res
    return c


def _stats_of(c):
    c32 = c.astype(jnp.float32)
    return jnp.sum(c32, axis=(0, 2, 3)), jnp.sum(c32 * c32, axis=(0, 2, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def conv_block(x, w, scale, shift, res, kernel_hw=(1, 1), stride=(1, 1),
               relu=False, use_pallas=True, bwd="xla", bn=None):
    """Fused (prologue-normalized) conv (+residual) with statistics epilogue.

    Returns ``(c, ssum, ssq)``: the conv output (x.dtype) and per-channel
    f32 sum / sum-of-squares over (B, H, W). ``scale``/``shift`` (or None)
    fold the upstream BN+ReLU into the kernel; ``res`` (or None) is added
    into the output tile before the statistics. Differentiable in x, w,
    scale, shift, res.

    ``bwd`` selects the backward lowering: ``"xla"`` (jax.vjp of the
    unfused conv), ``"recompute"`` (fused Pallas dgrad/wgrad, prologue
    re-derived in VMEM) or ``"stash"`` (fused Pallas backward streaming the
    forward-materialized xn). Non-"xla" modes silently demote — stash →
    recompute when the forward could not emit xn, and either → "xla" when
    ``plan_bwd_blocks`` cannot tile the shape. ``bn`` overrides the forward
    channel-stripe width (the autotuner's measured schedule; an invalid
    override demotes to the planner pick).
    """
    c, s, q = _conv_block_fwd(x, w, scale, shift, res, kernel_hw, stride,
                              relu, use_pallas, bwd, bn)[0]
    return c, s, q


def conv_block_infer(x, w, scale, shift, kernel_hw=(1, 1), stride=(1, 1),
                     relu=False):
    """Grad-less inference forward: the same fused prologue+conv kernel
    with the statistics epilogue elided (at ``is_train=False`` every
    downstream BN normalizes with its moving stats, so ssum/ssq would be
    dead outputs the opaque kernel still had to compute). Returns just
    ``c``; NOT differentiable — serving/predict paths only."""
    return _conv_block_fwd_impl(x, w, scale, shift, None,
                                kernel_hw=kernel_hw, stride=stride,
                                relu=relu, interpret=_interpret_mode(),
                                emit_stats=False)


def _interpret_mode():
    return jax.default_backend() != "tpu"


def _conv_block_fwd(x, w, scale, shift, res, kernel_hw, stride, relu,
                    use_pallas, bwd="xla", bn=None):
    planned = use_pallas and plan_blocks(
        x.shape, w.shape, stride, itemsize=x.dtype.itemsize,
        prologue=scale is not None, res=res is not None) is not None
    # the stash policy is decided at FORWARD time (the extra xn output);
    # it needs the Pallas forward, a prologue to stash, a forward that
    # still fits VMEM WITH the xn output stream, and a tileable backward —
    # any miss silently demotes to recompute
    stash = (bwd == "stash" and planned and scale is not None
             and plan_blocks(
                 x.shape, w.shape, stride, itemsize=x.dtype.itemsize,
                 prologue=True, res=res is not None,
                 emit_xn=True) is not None
             and plan_bwd_blocks(
                 x.shape, w.shape, stride, itemsize=x.dtype.itemsize,
                 prologue=True, res=res is not None, stash=True) is not None)
    xn = None
    if planned:
        outs = _conv_block_fwd_impl(
            x, w, scale, shift, res, kernel_hw=kernel_hw, stride=stride,
            relu=relu, interpret=_interpret_mode(), emit_xn=stash,
            bn_override=bn)
        if stash:
            c, s, q, xn = outs
        else:
            c, s, q = outs
    else:
        c = _xla_conv(x, w, scale, shift, res, kernel_hw, stride, relu)
        s, q = _stats_of(c)
    return (c, s, q), (x, w, scale, shift, res, c, xn)


# ------------------------------------------------------------------ backward
def _bwd_kernel(*refs, b_steps, bk, hw, taps, shifts, relu, has_prologue,
                has_res, stash):
    """Fused dgrad+wgrad: one instance owns a (bk, HW) input-channel stripe
    at one batch element. The stats cotangents fold into the output
    cotangent in VMEM (dc_eff is never in HBM), dgrad contracts the weight
    stripe against it, wgrad accumulates dw from the SAME resident dc_eff
    and xn tiles across the B sweep, and the prologue backward (relu mask,
    scale, dscale/dshift reductions) runs on the f32 da before the single
    dx write."""
    import jax.experimental.pallas as pl
    from jax import lax

    it = iter(refs)
    dc_ref = next(it)                               # (1, N, HW)
    c_ref = next(it)                                # (1, N, HW)
    ds_ref = next(it)                               # (N, 1) f32
    dq_ref = next(it)                               # (N, 1) f32
    w_ref = next(it)                                # (N, bk) | (taps, N, bk)
    mask_ref = next(it) if taps > 1 else None       # (taps, 1, HW) f32
    x_ref = next(it)                                # (1, bk, HW)
    xn_ref = next(it) if stash else None            # (1, bk, HW)
    scale_ref = next(it) if has_prologue else None  # (bk, 1)
    shift_ref = next(it) if has_prologue else None  # (bk, 1)
    dx_ref = next(it)                               # (1, bk, HW)
    dw_ref = next(it)                               # (taps, N, bk) f32
    dsc_ref = next(it) if has_prologue else None    # (bk, 1) f32
    dsh_ref = next(it) if has_prologue else None    # (bk, 1) f32
    dres_ref = next(it) if has_res else None        # (1, N, HW)
    acc_w = next(it)                                # (taps, N, bk) f32
    acc_sc = next(it) if has_prologue else None     # (bk, 1) f32
    acc_sh = next(it) if has_prologue else None     # (bk, 1) f32

    b = pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        acc_w[...] = jnp.zeros_like(acc_w)
        if has_prologue:
            acc_sc[...] = jnp.zeros_like(acc_sc)
            acc_sh[...] = jnp.zeros_like(acc_sh)

    dt = x_ref.dtype
    # fold the statistics cotangents into the output cotangent:
    # d/dc [ sum(c) . ds + sum(c^2) . dq ] = ds + 2 c dq   (per channel)
    dce32 = (dc_ref[0].astype(jnp.float32) + ds_ref[...]
             + 2.0 * c_ref[0].astype(jnp.float32) * dq_ref[...])
    if has_res:
        # the residual add passes the effective cotangent straight through.
        # The (b, 0, 0) block is revisited once per k stripe with identical
        # data, like the forward's stash write — benign duplicate writes.
        dres_ref[0] = dce32.astype(dt)
    # round to the activation dtype for the MXU dots, matching the XLA
    # path's bf16 cotangent
    dce = dce32.astype(dt)

    x = x_ref[0]
    if stash:
        xn = xn_ref[0]
    elif has_prologue:
        xn = x * scale_ref[...] + shift_ref[...]
        if relu:
            xn = jnp.maximum(xn, jnp.zeros_like(xn))
    else:
        xn = x

    cdims = (((0,), (0,)), ((), ()))  # (N, bk) . (N, HW) -> (bk, HW)
    wdims = (((1,), (1,)), ((), ()))  # (N, HW) . (bk, HW) -> (N, bk)
    if taps == 1:
        da = lax.dot_general(w_ref[...], dce, cdims,
                             preferred_element_type=jnp.float32)
        acc_w[0] += lax.dot_general(dce, xn, wdims,
                                    preferred_element_type=jnp.float32)
    else:
        # exact transpose of the forward's roll+mask formulation: the mask
        # rides on the (N, HW) side, the inverse roll lands the tap's
        # contribution back on its source column
        da = jnp.zeros((bk, hw), jnp.float32)
        for t in range(taps):
            m = (dce * mask_ref[t]).astype(dt)
            part = lax.dot_general(w_ref[t], m, cdims,
                                   preferred_element_type=jnp.float32)
            da = da + _roll_cols(part, -shifts[t], hw)
            acc_w[t] += lax.dot_general(m, _roll_cols(xn, shifts[t], hw),
                                        wdims,
                                        preferred_element_type=jnp.float32)

    if has_prologue:
        if relu:
            # compare in f32: the v5e VPU has no bf16 compare
            da = da * (xn.astype(jnp.float32) > 0).astype(jnp.float32)
        dx_ref[0] = (da * scale_ref[...].astype(jnp.float32)).astype(dt)
        # per-channel reductions in the f32 accumulator (a bf16 reduce over
        # B*HW elements would lose the gradient's low bits)
        acc_sc[...] += jnp.sum(da * x.astype(jnp.float32), axis=1,
                               keepdims=True)
        acc_sh[...] += jnp.sum(da, axis=1, keepdims=True)
    else:
        dx_ref[0] = da.astype(dt)

    @pl.when(b == b_steps - 1)
    def _flush():
        dw_ref[...] = acc_w[...]
        if has_prologue:
            dsc_ref[...] = acc_sc[...]
            dsh_ref[...] = acc_sh[...]


@functools.partial(jax.jit, static_argnames=("kernel_hw", "stride", "relu",
                                             "has_res", "interpret"))
def _conv_block_bwd_impl(x, w, scale, shift, c, dc, ds, dq, xn, *,
                         kernel_hw, stride, relu, has_res, interpret):
    """Pallas fused backward. x (B,K,H,W) raw input; xn (post-stride shape)
    or None (recompute); c/dc (B,N,H',W'); ds/dq (N,) f32. Returns
    (dx, dw, dscale, dshift, dres) with dscale/dshift/dres None when the
    prologue/residual is absent."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, K, Hf, Wf = x.shape
    N = w.shape[0]
    kh, kw = kernel_hw
    strided = (kh, kw) == (1, 1) and stride != (1, 1)
    if strided:
        x = x[:, :, :: stride[0], :: stride[1]]
    B, K, H, W = x.shape
    HW = H * W
    taps = kh * kw
    dt = x.dtype
    has_prologue = scale is not None
    stash = xn is not None
    bk = choose_bwd_blocks(B, K, N, HW, dt.itemsize, taps=taps,
                           prologue=has_prologue, res=has_res, stash=stash)
    assert bk is not None, (x.shape, w.shape)  # callers gate via plan_bwd_blocks
    k_tiles = K // bk

    inputs = [dc.reshape(B, N, HW), c.reshape(B, N, HW),
              ds.reshape(N, 1), dq.reshape(N, 1)]
    in_specs = [pl.BlockSpec((1, N, HW), lambda k, b: (b, 0, 0)),
                pl.BlockSpec((1, N, HW), lambda k, b: (b, 0, 0)),
                pl.BlockSpec((N, 1), lambda k, b: (0, 0)),
                pl.BlockSpec((N, 1), lambda k, b: (0, 0))]
    if taps == 1:
        inputs.append(w.reshape(N, K))
        in_specs.append(pl.BlockSpec((N, bk), lambda k, b: (0, k)))
        shifts = (0,)
    else:
        inputs.append(jnp.transpose(w.reshape(N, K, taps), (2, 0, 1)))
        in_specs.append(pl.BlockSpec((taps, N, bk), lambda k, b: (0, 0, k)))
        inputs.append(jnp.asarray(_shift_masks(H, W)))
        in_specs.append(pl.BlockSpec((taps, 1, HW), lambda k, b: (0, 0, 0)))
        shifts = tuple(dy * W + dx for dy in (-1, 0, 1) for dx in (-1, 0, 1))
    inputs.append(x.reshape(B, K, HW))
    in_specs.append(pl.BlockSpec((1, bk, HW), lambda k, b: (b, k, 0)))
    if stash:
        inputs.append(xn.reshape(B, K, HW))
        in_specs.append(pl.BlockSpec((1, bk, HW), lambda k, b: (b, k, 0)))
    if has_prologue:
        inputs.append(scale.astype(dt).reshape(K, 1))
        inputs.append(shift.astype(dt).reshape(K, 1))
        in_specs.append(pl.BlockSpec((bk, 1), lambda k, b: (k, 0)))
        in_specs.append(pl.BlockSpec((bk, 1), lambda k, b: (k, 0)))

    out_specs = [pl.BlockSpec((1, bk, HW), lambda k, b: (b, k, 0)),
                 pl.BlockSpec((taps, N, bk), lambda k, b: (0, 0, k))]
    out_shape = [jax.ShapeDtypeStruct((B, K, HW), dt),
                 jax.ShapeDtypeStruct((taps, N, K), jnp.float32)]
    if has_prologue:
        out_specs += [pl.BlockSpec((bk, 1), lambda k, b: (k, 0)),
                      pl.BlockSpec((bk, 1), lambda k, b: (k, 0))]
        out_shape += [jax.ShapeDtypeStruct((K, 1), jnp.float32),
                      jax.ShapeDtypeStruct((K, 1), jnp.float32)]
    if has_res:
        out_specs.append(pl.BlockSpec((1, N, HW), lambda k, b: (b, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, N, HW), dt))
    scratch = [pltpu.VMEM((taps, N, bk), jnp.float32)]
    if has_prologue:
        scratch += [pltpu.VMEM((bk, 1), jnp.float32),
                    pltpu.VMEM((bk, 1), jnp.float32)]

    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=(pltpu.GridDimensionSemantics.PARALLEL,
                             pltpu.GridDimensionSemantics.ARBITRARY))
    outs = pl.pallas_call(
        functools.partial(
            _bwd_kernel, b_steps=B, bk=bk, hw=HW, taps=taps, shifts=shifts,
            relu=relu, has_prologue=has_prologue, has_res=has_res,
            stash=stash),
        grid=(k_tiles, B),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=interpret,
    )(*inputs)
    it = iter(outs)
    dx = next(it).reshape(B, K, H, W)
    if strided:
        dx = jnp.zeros((B, K, Hf, Wf), dt).at[
            :, :, :: stride[0], :: stride[1]].set(dx)
    dw = next(it)  # (taps, N, K) f32
    if taps == 1:
        dw = dw[0].reshape(N, K, 1, 1)
    else:
        dw = jnp.transpose(dw, (1, 2, 0)).reshape(N, K, kh, kw)
    dw = dw.astype(w.dtype)
    dscale = next(it)[:, 0] if has_prologue else None
    dshift = next(it)[:, 0] if has_prologue else None
    dres = next(it).reshape(c.shape) if has_res else None
    return dx, dw, dscale, dshift, dres


def _conv_block_bwd(kernel_hw, stride, relu, use_pallas, bwd, bn, saved,
                    cts):
    x, w, scale, shift, res, c, xn = saved
    dc, ds, dq = cts
    has_prologue = scale is not None
    has_res = res is not None

    mode = bwd if use_pallas else "xla"
    if mode == "stash" and xn is None:
        mode = "recompute"  # forward could not emit xn (fallback/no prologue)
    if mode in ("recompute", "stash") and plan_bwd_blocks(
            x.shape, w.shape, stride, itemsize=x.dtype.itemsize,
            prologue=has_prologue, res=has_res,
            stash=(mode == "stash")) is None:
        mode = "xla"
    if mode != "xla":
        return _conv_block_bwd_impl(
            x, w, scale, shift, c, dc, ds, dq,
            xn if mode == "stash" else None,
            kernel_hw=kernel_hw, stride=stride, relu=relu, has_res=has_res,
            interpret=_interpret_mode())

    # fold the statistics cotangents into the output cotangent:
    # d/dc [ sum(c) . ds + sum(c^2) . dq ] = ds + 2 c dq   (per channel)
    bshape = (1, -1, 1, 1)
    dc_eff = (dc.astype(jnp.float32)
              + ds.reshape(bshape)
              + 2.0 * c.astype(jnp.float32) * dq.reshape(bshape)
              ).astype(c.dtype)

    if has_prologue:
        xn = x * scale.astype(x.dtype).reshape(bshape) \
            + shift.astype(x.dtype).reshape(bshape)
        if relu:
            xn = jnp.maximum(xn, 0)
    else:
        xn = x

    pad = (kernel_hw[0] - 1) // 2

    def conv_only(xn, w):
        return jax.lax.conv_general_dilated(
            xn, w, window_strides=stride, padding=[(pad, pad), (pad, pad)],
            dimension_numbers=_DNUMS,
            preferred_element_type=_preferred(x.dtype),
        ).astype(x.dtype)

    # the recomputed primal is dead code (only dc_eff uses c, and that is the
    # saved output) — XLA DCEs the duplicate convolution, keeping just the
    # transposed data/weight grads; xn's recompute is fusible elementwise.
    _, vjp_fn = jax.vjp(conv_only, xn, w)
    dxn, dw = vjp_fn(dc_eff)
    if has_prologue:
        if relu:
            dxn = dxn * (xn > 0).astype(dxn.dtype)
        dx = dxn * scale.astype(dxn.dtype).reshape(bshape)
        # per-channel reductions with explicit f32 accumulators (a bf16
        # reduce over B*H*W elements would lose the gradient's low bits)
        dxn32 = dxn.astype(jnp.float32)
        dscale = jnp.sum(dxn32 * x.astype(jnp.float32), axis=(0, 2, 3))
        dshift = jnp.sum(dxn32, axis=(0, 2, 3))
    else:
        dx, dscale, dshift = dxn, None, None
    return dx, dw, dscale, dshift, (dc_eff if has_res else None)


conv_block.defvjp(_conv_block_fwd, _conv_block_bwd)
