"""Blockwise (online-softmax) attention as a differentiable Pallas TPU kernel.

``softmax(QKᵀ·scale + mask)V`` in blocks that never leave the chip: a block of
queries stays in VMEM while the keys and values stream past it ``block_k`` at a
time, and the running maximum, sum and accumulator (float32) are carried across
the key steps in VMEM scratch. The (T, S) scores are never written to HBM:
O(T·D) memory instead of O(T·S).

The arithmetic is the dense path's (``ops/attention.py``), not a wider one:
both products take their operands in the type they ARRIVE in (one bfloat16 pass
on the matrix unit for bfloat16 inputs, float32 operands for float32 inputs)
with a float32 accumulator; the scale multiplies the float32 scores after the
product; the probabilities are rounded to the values' type for the second
product, which is what the chip's default matmul precision does to the dense
path's float32 probabilities.

Causal calls compute and fetch nothing above the diagonal: for a block of
queries the key axis ends at the diagonal (the body runs under ``pl.when`` and
the key/value ``index_map`` is clamped to the last block the queries need, so
the pipeline fetches no block the body skips); a block wholly below the
diagonal is not masked at all. The mask is bottom-right aligned for S >= T, as
the dense path's is; causal with S < T is refused by ``supported()`` (rows with
no key at all have no softmax).

A SELECTION: an optional mask (B, T, S) int8 that every head of a batch row
shares (a learned sparse attention's chosen keys). Its ``(block_q, block_k)``
block rides beside the key block, under the same clamp, and a score survives
where it is causal AND selected; a block below the diagonal is then masked
too. The masked value is finite, so a row whose first key blocks hold nothing
selected carries garbage until its first real key arrives, whose maximum
rescales it to exactly nothing (``alpha = exp(_NEG_INF - m) = 0``).

A WINDOW: ``window=W`` (causal calls) lets query r attend the W keys
``r + offset - W < j <= r + offset``, itself among them. It is one more bound
on the key axis: for a block of queries the key axis STARTS at the block that
holds the first row's oldest key and ends at the diagonal's, the grid's key
axis is as long as the most blocks a query block can visit
(``window_key_blocks``) and the key/value ``index_map`` is clamped to
``[first, last]``; a block the window's lower edge crosses is masked as one
the diagonal crosses is, a block wholly inside both is not masked at all. T
need not be a multiple of W. The forward kernel alone knows a window: its
caller keeps a backward of its own (``ops.attention``'s band), as a
selection's does. What the kernel still refuses: a SINK (one more logit in
the denominator), a mesh of several devices (``attention_form``).

Grouped queries: ``k`` / ``v`` may carry fewer heads (B, Hkv, S, D) than ``q``
(B, H, T, D). The keys are never repeated: the H / Hkv query heads a key/value
head serves are folded into the ROWS of that head's query block, so one grid
step multiplies ``group x block_q`` rows with one key block. ``v`` may be
narrower or wider than ``k`` (a latent attention's 128 under a key of 192): the
output takes the value's width.

Training-ready: ``jax.custom_vjp`` with recompute-style backward kernels (the dq
and dk/dv passes re-derive the probabilities from the saved logsumexp rather
than storing P). ``MultiHeadAttention`` reaches the kernel through ONE rule,
``ops.attention.attention_form``, which reads shapes, attributes and the
backend (the kernel on the chip, the dense path everywhere else). It runs
anywhere under Pallas interpret mode, which is how the CPU tests exercise it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["flash_attention", "supported", "blocks", "block_schedules",
           "window_key_blocks", "window_pairs_scored"]

_NEG_INF = -1e30

_LANES = 128

# the VMEM a call's blocks may take together (the chip has 128 MiB; the
# compiler's own default limit is 16, asked for by name where a call needs
# more)
_VMEM_BUDGET = 24 << 20


def _sublanes(dtype):
    """Rows of one tile of ``dtype`` on the chip: 8 of float32, 16 of
    bfloat16."""
    return 32 // jnp.dtype(dtype).itemsize


def block_bytes(block_q, block_k, group, dk, dv, dtype, selected=False):
    """The VMEM one grid step of the forward kernel holds, from its blocks
    alone: the query and output blocks and the key and value blocks, each
    twice (the pipeline's two buffers), the float32 scores and probabilities
    and the probabilities once more in the values' type, the scratch
    (accumulator, maximum and sum a row, a lane tile wide each) and, with a
    selection, its int8 block twice."""
    size = jnp.dtype(dtype).itemsize
    rows = group * block_q
    pad = lambda d: -(-d // _LANES) * _LANES
    return (2 * rows * (pad(dk) + pad(dv)) * size
            + 2 * block_k * (pad(dk) + pad(dv)) * size
            + rows * block_k * (4 + 4 + size)
            + rows * (pad(dv) + 2 * _LANES) * 4
            + (2 * block_q * block_k if selected else 0))


# the rule's two sizes, settled on the chip (``blocks``)
_ROWS = 1024
_BLOCK_K = 1024


def blocks(t, s, group, dk, dv, dtype, selected=False, window=0):
    """``(block_q, block_k)`` of the forward kernel for ``t`` queries a head
    over ``s`` keys, ``group`` query heads a key/value head, a key of ``dk``
    and a value of ``dv`` numbers of ``dtype``, under a selection's mask or
    not (its int8 block counts in the bytes and tiles in 32 rows); None where
    no block tiles the shape. THE rule of the kernel's tiling, from the
    shape, the operands' bytes and the VMEM budget alone (no option, no
    model's name), written from chip runs at the cells' shapes (``PERF.md``
    section 6, PR 49).

    ``block_k`` is the largest divisor of ``s`` in whole lane tiles up to
    ``_BLOCK_K``, and a step holds up to ``_ROWS`` rows, ``group x block_q``
    (``block_q`` a divisor of ``t`` in whole sublane tiles, so that the group
    folds into the rows without a re-layout), halved while the step's blocks
    overflow ``_VMEM_BUDGET``. Both are 1,024 because a step's cost is its
    ROWS' as much as its scores': the running maximum, sum and the
    accumulator's rescaling are a pass over ``rows`` lane-sparse vectors each
    whatever ``block_k`` is, so a wide key block amortises them (OLMoE's
    layer, 16 heads x 2,048 x 128: 0.68 ms at keys of 256, 0.34 at 512, 0.23
    at 1,024 under 1,024 rows; the rows matter little, 0.25 at 512), and past
    1,024 the diagonal's waste wins (0.30 at 2,048: a causal call computes
    whole blocks the diagonal crosses). At t = 1,024 a head is ONE step.
    Splitting a step's softmax into row chunks inside a loop, to keep the
    scores in registers, read 2 to 4.6 times SLOWER at every shape and is
    not here.

    Under a ``window`` (causal) the key block stays 1,024 and ``block_q`` is
    held to the window's whole lane tiles: a query block visits the key
    blocks from its first row's oldest key to its diagonal's, so its cost is
    the key blocks it VISITS (the rows' pass, each visit) before the keys it
    scores. Key blocks of 1,024 over a window of 512 are visited 1.5 times a
    query block (half the query blocks reach back into a second one), blocks
    of 512 twice and of 256 three times: laguna's layer (72 over 8 heads x
    8,192, a group of 9) read 4.23 ms at (64, 1,024), 4.94 at (64, 512),
    5.80 at (64, 256), 9.60 at (64, 128), though 1,024 scores 2.9 times the
    needed pairs and 256 1.5 times; phi4flash's 0.63 at (256, 1,024), 0.72
    at (128, 512), 0.93 at (128, 256). A query block longer than the window
    widens the span its key blocks must cover: dots3's layer (64 heads, a
    key of 256, W 513) read 5.59 ms at (1,024, 1,024), 4.96 at (512, 1,024),
    4.76 at (512, 512) (``PERF.md`` section 6, PR 60)."""
    unit = _sublanes(jnp.int8 if selected else dtype)
    most_q = _ROWS // group
    if window:
        most_q = min(most_q, -(-(window - 1) // _LANES) * _LANES)
    bk = _largest_divisor(s, _BLOCK_K, _LANES) or _largest_divisor(s, s, unit)
    bq = _largest_divisor(t, max(most_q, unit), unit)
    if not bq or not bk:
        return None
    while block_bytes(bq, bk, group, dk, dv, dtype, selected) > _VMEM_BUDGET:
        if bq * group >= bk and bq % (2 * unit) == 0:
            bq //= 2
        elif bk % (2 * _LANES) == 0:
            bk //= 2
        else:
            return None
    return bq, bk


def _largest_divisor(n, most, unit):
    """The largest divisor of ``n`` that is a multiple of ``unit`` and at most
    ``most``; 0 where there is none."""
    for d in range(min(most, n) // unit * unit, 0, -unit):
        if n % d == 0:
            return d
    return 0


def block_schedules(q_shape, k_shape, causal=False):
    """Every valid (block_q, block_k) tiling for these shapes, planner
    default first — the bounded schedule space the autotuner measures
    (docs/PERF.md §15). Blocks are pre-clamped to (T, S) so each entry is
    a distinct effective tiling."""
    T, S = q_shape[2], k_shape[2]
    seen, out = set(), []
    for bq, bk in ((128, 128), (128, 256), (256, 128), (64, 128),
                   (128, 64), (64, 64), (256, 256), (1024, 1024),
                   (512, 1024), (512, 512), (32, 32)):
        eff = (min(bq, T), min(bk, S))
        if eff in seen or not supported(q_shape, k_shape, causal=causal,
                                        block_q=bq, block_k=bk):
            continue
        seen.add(eff)
        out.append(eff)
    return out


def supported(q_shape, k_shape, causal=False, block_q=128, block_k=128):
    """Whether shapes tile cleanly onto the kernel grid."""
    B, H, T, D = q_shape
    S = k_shape[2]
    if causal and S < T:
        # bottom-right alignment would fully mask rows r < T-S; the online
        # softmax has no valid key for them — use the XLA path instead
        return False
    if k_shape[1] < 1 or H % k_shape[1]:
        return False
    bq, bk = min(block_q, T), min(block_k, S)
    # block dims must stay sublane-aligned (8 for f32) or Mosaic rejects them
    return (T % bq == 0 and S % bk == 0 and bq % 8 == 0 and bk % 8 == 0
            and D % 8 == 0)


# the float32 scores the dense path makes, B x H x T x S x 4 bytes, up to which
# it stays dense on the chip (``takes``)
_DENSE_SCORES = 64 << 20


# the float32 scores a window's band makes, B x H x T x 2W x 4 bytes, up to
# which the band stays XLA's on the chip (``takes``)
_BAND_SCORES = 128 << 20


def takes(query, key, value, selected=False, window=0):
    """Whether the chip runs causal attention over these operands, plain or
    under a selection's mask, as this kernel rather than in XLA's form
    (shapes and types alone; each operand carries ``.shape`` and ``.dtype``:
    ``query`` (B, H, T, dk), ``key`` (B, Hkv, S, dk), ``value`` (B, Hkv, S,
    dv)): what ``attention_form`` asks.

    One type throughout, bfloat16 or float32; S >= T; T and S whole lane
    tiles and head widths whole sublane tiles (what Mosaic tiles); a tiling
    (``blocks``); and float32 scores of more than ``_DENSE_SCORES`` bytes.
    Up to there the chip keeps the dense path's scores in its vector memory
    and its fused program is the faster one: at 32 MiB of scores (32 heads x
    512 x 512; 8 x 1,024 x 1,024, widths of 64) a layer standing alone read
    0.039 and 0.035 ms dense against the kernel's 0.070 and 0.038 at its
    best blocks, at 128 MiB (32 x 1,024 x 1,024) 0.59-0.66 dense against
    0.17-0.20, and the gap widens from there (``PERF.md`` section 6, PR 49).

    ``window`` = W > 0 asks for a causal call under a window (no selection):
    0 < W < T and a band, B x H x T x 2W float32 scores, of more than
    ``_BAND_SCORES`` bytes. XLA's band wins below: a layer standing alone
    read 0.19-0.21 ms as a band against the kernel's 0.36 at its best blocks
    at 32 MiB (16 heads x 2,048, W 128), 0.23 against 0.50 at 64 MiB, 0.88
    against 1.00 at 128 MiB (mimo's 64 over 8 heads of 192 / 128 x 2,048, W
    128, without its sink); the kernel from there: 1.38 against 0.88 at 256
    MiB (32 over 8 heads x 4,096, W 256), 1.66 against 0.63 at phi4flash's
    320 MiB, 13.7 against 5.0 at dots3's 2.0 GiB and 11.8 against 4.2 at
    laguna's 2.3 GiB, whose band runs a few blocks at a time through HBM
    (``PERF.md`` section 6, PR 60)."""
    if not (query.dtype == key.dtype == value.dtype
            and query.dtype in (jnp.bfloat16, jnp.float32)):
        return False
    (b, h, t, dk), (_, hkv, s, _), dv = query.shape, key.shape, value.shape[3]
    if hkv < 1 or h % hkv or s < t or t % _LANES or s % _LANES \
            or dk % 8 or dv % 8 or 4 * b * h * t * s <= _DENSE_SCORES:
        return False
    if window and (selected or not 0 < window < t
                   or 4 * b * h * t * 2 * window <= _BAND_SCORES):
        return False
    return blocks(t, s, h // hkv, dk, dv, query.dtype, selected,
                  window) is not None


def _causal_mask(s, iq, jk, block_q, block_k, offset, window=0):
    """Bottom-right-aligned causal mask for one tile of scores, ``s``
    (groups x block_q, block_k): the query at row r of ANY group sees key
    cols <= r + (S - T) and, under a ``window``, cols > r + (S - T) -
    window."""
    group = s.shape[0] // block_q
    rows = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (group, block_q, block_k), 1).reshape(s.shape)
    cols = jk * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    seen = cols <= rows + offset
    if window:
        seen = jnp.logical_and(seen, cols > rows + offset - window)
    return jnp.where(seen, s, _NEG_INF)


def _last_key_block(iq, block_q, block_k, offset):
    """The last key block the queries of block ``iq`` see: the one that holds
    the last row's diagonal column."""
    return ((iq + 1) * block_q - 1 + offset) // block_k


def _first_key_block(iq, block_q, block_k, offset, window):
    """The first key block the queries of block ``iq`` see under a window:
    the one that holds the first row's oldest key."""
    return jnp.maximum(iq * block_q + offset - window + 1, 0) // block_k


def _key_blocks_visited(t, s, block_q, block_k, window):
    """The key blocks each block of ``block_q`` queries visits under a causal
    ``window`` over ``t`` queries and ``s`` keys in blocks of ``block_k``,
    from the block of its first row's oldest key to its diagonal's (plain
    Python over shapes)."""
    offset = s - t
    return [_last_key_block(iq, block_q, block_k, offset)
            - max(iq * block_q + offset - window + 1, 0) // block_k + 1
            for iq in range(t // block_q)]


def window_key_blocks(t, s, block_q, block_k, window):
    """The most key blocks one block of queries visits under a window: the
    length of the windowed kernel's key axis."""
    return max(_key_blocks_visited(t, s, block_q, block_k, window))


def window_pairs_scored(t, s, block_q, block_k, window):
    """The (query, key) pairs the windowed kernel scores a head: every block
    a query block visits, whole."""
    return block_q * block_k * sum(
        _key_blocks_visited(t, s, block_q, block_k, window))


def _first_query_block(jk, block_q, block_k, offset):
    """The first query block that sees any key of block ``jk``."""
    return jnp.maximum(jk * block_k - offset, 0) // block_q


# --------------------------------------------------------------------- forward
def _selected_mask(s, sel, block_q):
    """One tile of scores, ``s`` (groups x block_q, block_k), under a
    selection's block ``sel`` (block_q, block_k) int8: every group's rows
    share it."""
    group = s.shape[0] // block_q
    sel = jnp.broadcast_to(sel.astype(jnp.int32)[None],
                           (group,) + sel.shape).reshape(s.shape)
    return jnp.where(sel != 0, s, _NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, block_q, block_k,
                nk, offset, with_lse, selected, window=0):
    """``rest``: the selection's block (``selected`` alone), the output, the
    logsumexp (``with_lse`` alone), then the scratch. Under a ``window`` grid
    step ``jk`` is key block ``first + jk`` of the query block's own
    ``[first, last]``."""
    from jax.experimental import pallas as pl

    rest = list(rest)
    sel_ref = rest.pop(0) if selected else None
    o_ref = rest.pop(0)
    lse_ref = rest.pop(0) if with_lse else None
    m_scr, l_scr, acc_scr = rest
    iq, jk = pl.program_id(1), pl.program_id(2)
    rows = acc_scr.shape[0]                           # groups x block_q

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked, at=jk):
        q = q_ref[0].reshape(rows, q_ref.shape[-1])   # the group into rows
        k, v = k_ref[0], v_ref[0]                     # (block_k, dk | dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = _causal_mask(s, iq, at, block_q, block_k, offset, window)
        if selected:
            s = _selected_mask(s, sel_ref[0], block_q)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if window:
        # key block ``at`` of [first, last]: clear where it lies wholly at or
        # below the diagonal of the block's FIRST row and wholly inside the
        # window of its LAST row; masked where either edge crosses it; past
        # ``last`` it does not run
        at = _first_key_block(iq, block_q, block_k, offset, window) + jk
        runs = at <= _last_key_block(iq, block_q, block_k, offset)
        clear = jnp.logical_and(
            (at + 1) * block_k - 1 <= iq * block_q + offset,
            at * block_k >= (iq + 1) * block_q + offset - window)
        pl.when(jnp.logical_and(runs, clear))(lambda: step(False, at))
        pl.when(jnp.logical_and(runs, jnp.logical_not(clear)))(
            lambda: step(True, at))
    elif causal:
        # a block wholly at or below the diagonal of its FIRST row needs no
        # causal mask (a selection's it takes all the same); one the diagonal
        # crosses is masked; one above it does not run
        below = (jk + 1) * block_k - 1 <= iq * block_q + offset
        crossed = jnp.logical_and(
            jnp.logical_not(below),
            jk <= _last_key_block(iq, block_q, block_k, offset))
        pl.when(below)(lambda: step(False))
        pl.when(crossed)(lambda: step(True))
    else:
        step(False)

    @pl.when(jk == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).reshape(o_ref.shape[1:]).astype(
            o_ref.dtype)
        if with_lse:
            lse_ref[0] = (m_scr[...] + jnp.log(l)).reshape(lse_ref.shape[1:])


# ------------------------------------------------------------------- backward
def _recompute(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, iq, jk, *,
               scale, causal, block_q, block_k, offset):
    """What both backward kernels re-derive of one (block_q, block_k) tile from
    the saved logsumexp, float32: ``(q * scale, k, do, p, ds)``, the
    probabilities and the scores' gradient."""
    q = q_ref[0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)                # (block_q, dv)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if causal:
        s = _causal_mask(s, iq, jk, block_q, block_k, offset)
    p = jnp.exp(s - lse_ref[0])                       # lse, delta (block_q, 1)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return q, k, do, p, p * (dp - delta_ref[0])


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, nk, **tile):
    from jax.experimental import pallas as pl

    iq, jk = pl.program_id(1), pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def step():
        _, k, _, _, ds = _recompute(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                    delta_ref, iq, jk, **tile)
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if tile["causal"]:  # a block above the diagonal adds exact zeros: skipped
        pl.when(jk <= _last_key_block(iq, tile["block_q"], tile["block_k"],
                                      tile["offset"]))(step)
    else:
        step()

    @pl.when(jk == nk - 1)
    def _finish():
        dq_ref[0] = (dq_scr[...] * tile["scale"]).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, nq, **tile):
    from jax.experimental import pallas as pl

    jk, iq = pl.program_id(1), pl.program_id(2)      # q streams innermost

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step():
        q, _, do, p, ds = _recompute(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                     delta_ref, iq, jk, **tile)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if tile["causal"]:  # the queries before a key block's diagonal never see it
        pl.when(iq >= _first_query_block(jk, tile["block_q"], tile["block_k"],
                                         tile["offset"]))(step)
    else:
        step()

    @pl.when(iq == nq - 1)
    def _finish():
        # q already carries the scale factor; dk needs none on top
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------- pallas glue
def _compiler_params(n_parallel, vmem_bytes=0):
    from jax.experimental.pallas import tpu as pltpu

    sem = (pltpu.GridDimensionSemantics.PARALLEL,) * n_parallel + (
        pltpu.GridDimensionSemantics.ARBITRARY,)
    # the compiler's own limit is 16 MiB; a step that needs more asks by name
    limit = {"vmem_limit_bytes": vmem_bytes + (8 << 20)} \
        if vmem_bytes > (12 << 20) else {}
    return pltpu.CompilerParams(dimension_semantics=sem, **limit)


def _fwd_call(q, k, v, causal, scale, block_q, block_k, interpret,
              with_lse=True, selected=None, window=0):
    """The forward kernel over ``q`` (BHkv, G, T, dk), ``k`` (BHkv, S, dk) and
    ``v`` (BHkv, S, dv): ``(out (BHkv, G, T, dv), lse (BHkv, G, T, 1) | None)``.
    The logsumexp is the backward's; a call nobody differentiates leaves it
    out. ``selected`` (B, T, S) int8, absent at TRACE time for a plain call
    (whose program is then what it was without the word): batch row ``bh //
    (BHkv / B)``'s mask for every head of it, its block fetched beside the
    key block's. ``window`` > 0 (static; causal, no selection): the key axis
    of the grid is a query block's own ``[first, last]`` blocks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, G, T, D = q.shape
    S, Dv = k.shape[1], v.shape[2]
    nq, nk = T // block_q, S // block_k
    offset = S - T
    if window:
        nk = window_key_blocks(T, S, block_q, block_k, window)
    kern = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, nk=nk, offset=offset, with_lse=with_lse,
        selected=selected is not None, window=window)
    if window:
        # a query block's key axis starts at the block of its first row's
        # oldest key and stays on its last block past the diagonal
        kv_block = lambda bh, iq, jk: (bh, jnp.minimum(
            _first_key_block(iq, block_q, block_k, offset, window) + jk,
            _last_key_block(iq, block_q, block_k, offset)), 0)
        live = sum(min(r + offset + 1, window) for r in range(T))
    elif causal:
        # past the diagonal the index stays on the last block the queries
        # need: the pipeline fetches nothing for a step that does not run
        kv_block = lambda bh, iq, jk: (bh, jnp.minimum(
            jk, _last_key_block(iq, block_q, block_k, offset)), 0)
        live = T * (T + 1) // 2 + T * offset
    else:
        kv_block = lambda bh, iq, jk: (bh, jk, 0)
        live = T * S
    size = q.dtype.itemsize
    in_specs = [
        pl.BlockSpec((1, G, block_q, D), lambda bh, iq, jk: (bh, 0, iq, 0)),
        pl.BlockSpec((1, block_k, D), kv_block),
        pl.BlockSpec((1, block_k, Dv), kv_block),
    ]
    operands, nbytes = (q, k, v), size * BH * (G * T * (D + Dv)
                                               + S * (D + Dv))
    if selected is not None:
        # a batch row's mask for each of its heads, re-read a head
        heads = BH // selected.shape[0]
        in_specs.append(pl.BlockSpec(
            (1, block_q, block_k), lambda bh, iq, jk: (
                bh // heads, iq, kv_block(bh, iq, jk)[1])))
        operands, nbytes = operands + (selected,), nbytes + BH * live
    out_specs = [pl.BlockSpec((1, G, block_q, Dv),
                              lambda bh, iq, jk: (bh, 0, iq, 0))]
    out_shape = [jax.ShapeDtypeStruct((BH, G, T, Dv), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, G, block_q, 1),
                                      lambda bh, iq, jk: (bh, 0, iq, 0)))
        out_shape.append(jax.ShapeDtypeStruct((BH, G, T, 1), jnp.float32))
    out = pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((G * block_q, 1), jnp.float32),
            pltpu.VMEM((G * block_q, 1), jnp.float32),
            pltpu.VMEM((G * block_q, Dv), jnp.float32),
        ],
        compiler_params=None if interpret else _compiler_params(
            2, block_bytes(block_q, block_k, G, D, Dv, q.dtype,
                           selected is not None)),
        cost_estimate=pl.CostEstimate(
            flops=2 * BH * G * live * (D + Dv),
            transcendentals=BH * G * live, bytes_accessed=nbytes),
        interpret=interpret,
        name="window_attention" if window else "flash_attention",
    )(*operands)
    return out[0], (out[1] if with_lse else None)


def _bwd_call(q, k, v, o, lse, do, causal, scale, block_q, block_k, interpret):
    """The two backward kernels over one key/value head a query head: ``q``
    (BH, T, dk), ``k`` (BH, S, dk), ``v`` (BH, S, dv), ``o`` and ``do``
    (BH, T, dv), ``lse`` (BH, T, 1)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    S, Dv = k.shape[1], v.shape[2]
    nq, nk = T // block_q, S // block_k
    offset = S - T
    # delta_i = sum_d dO_i O_i — cheap elementwise, fused by XLA
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk,
                          offset=offset),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, iq, jk: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, iq, jk: (bh, jk, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda bh, iq, jk: (bh, jk, 0)),
            pl.BlockSpec((1, block_q, Dv), lambda bh, iq, jk: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, iq, jk: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, iq, jk: (bh, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, iq, jk: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=None if interpret else _compiler_params(2),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nq=nq,
                          offset=offset),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, jk, iq: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, jk, iq: (bh, jk, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda bh, jk, iq: (bh, jk, 0)),
            pl.BlockSpec((1, block_q, Dv), lambda bh, jk, iq: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, jk, iq: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, jk, iq: (bh, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda bh, jk, iq: (bh, jk, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda bh, jk, iq: (bh, jk, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), k.dtype),
            jax.ShapeDtypeStruct((BH, S, Dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
        ],
        compiler_params=None if interpret else _compiler_params(2),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ------------------------------------------------------------------ custom vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    return _fwd_call(q, k, v, causal, scale, block_q, block_k, interpret,
                     with_lse=False)[0]


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    o, lse = _fwd_call(q, k, v, causal, scale, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, do):
    """The backward kernels know one key/value head a query head: a group's
    key and value are repeated for them and its gradients summed (float32).
    The forward's query block is a group's share of a step's rows; the
    backward's blocks are the ungrouped rule's, or the forward's where the
    caller named them."""
    q, k, v, o, lse = res
    BH, G, T, D = q.shape
    S, Dv = k.shape[1], v.shape[2]
    if G > 1:
        block_q, block_k = blocks(T, S, 1, D, Dv, q.dtype) or (block_q,
                                                               block_k)
    heads = lambda a: a.reshape((BH * G,) + a.shape[2:])
    rep = lambda a: jnp.repeat(a, G, axis=0) if G > 1 else a
    dq, dk, dv = _bwd_call(heads(q), rep(k), rep(v), heads(o), heads(lse),
                           heads(do), causal, scale, block_q, block_k,
                           interpret)
    if G > 1:
        dk, dv = (a.astype(jnp.float32).reshape((BH, G) + a.shape[1:]).sum(1)
                  .astype(a.dtype) for a in (dk, dv))
    return dq.reshape(q.shape), dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_selected(q, k, v, selected, causal, scale, block_q, block_k,
                    interpret):
    return _fwd_call(q, k, v, causal, scale, block_q, block_k, interpret,
                     with_lse=False, selected=selected)[0]


def _flash_selected_bwd(*_):
    raise NotImplementedError(
        "flash_attention(selected=) is not differentiable: the backward "
        "kernels know the causal mask alone. MultiHeadAttention(topk=) "
        "keeps the XLA form's backward")


_flash_selected.defvjp(lambda *a: (_flash_selected(*a), None),
                       _flash_selected_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_window(q, k, v, window, scale, block_q, block_k, interpret):
    return _fwd_call(q, k, v, True, scale, block_q, block_k, interpret,
                     with_lse=False, window=window)[0]


def _flash_window_bwd(*_):
    raise NotImplementedError(
        "flash_attention(window=) is not differentiable: the backward "
        "kernels know the causal mask alone. MultiHeadAttention(window=) "
        "keeps the band's backward")


_flash_window.defvjp(lambda *a: (_flash_window(*a), None), _flash_window_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret",
                                             "window"))
def flash_attention(q, k, v, causal=False, scale=0.0, block_q=None,
                    block_k=None, interpret=False, selected=None, window=0):
    """softmax(QKᵀ·scale)V of ``q`` (B, H, T, dk) over ``k`` (B, Hkv, S, dk)
    and ``v`` (B, Hkv, S, dv), Hkv dividing H, streamed through VMEM: (B, H,
    T, dv). Differentiable (custom_vjp backward kernels). ``block_q`` /
    ``block_k`` default to the rule's (``blocks``); a named block is clamped
    to the shape.

    ``selected`` (B, T, S), nonzero where query t of batch row b may attend
    key s, the same for every head: a score survives where it is causal (if
    ``causal``) AND selected; every row must keep a key. The forward kernel
    alone knows it: such a call is not differentiable (its caller keeps a
    backward of its own, ``ops.attention``).

    ``window`` = W > 0 (static; ``causal`` and no selection): query r attends
    the W keys ``r + (S - T) - W < j <= r + (S - T)``, itself among them,
    and the kernel visits a query block's own key blocks alone
    (``window_key_blocks``). The forward kernel alone knows it too."""
    B, H, T, D = q.shape
    Hkv, S, Dv = k.shape[1], k.shape[2], v.shape[3]
    if window and (not causal or selected is not None):
        raise ValueError("flash_attention(window=) takes causal calls "
                         "without a selection")
    if causal and S < T:
        raise ValueError(
            "flash_attention(causal=True) requires S >= T (got T=%d, S=%d): "
            "bottom-right alignment would fully mask rows < T-S; use the "
            "XLA attention path for these shapes" % (T, S))
    if scale <= 0:
        scale = 1.0 / np.sqrt(D)
    G = H // Hkv
    if block_q is None or block_k is None:
        ruled = blocks(T, S, G, D, Dv, q.dtype, selected is not None, window)
        if ruled is None:
            raise ValueError(
                "flash_attention: no block tiles %d queries over %d keys "
                "(%s); use the XLA attention path for these shapes"
                % (T, S, q.dtype))
        block_q, block_k = (block_q or ruled[0]), (block_k or ruled[1])
    block_q = min(block_q, T)
    block_k = min(block_k, S)
    heads = (q.reshape(B * Hkv, G, T, D), k.reshape(B * Hkv, S, D),
             v.reshape(B * Hkv, S, Dv))
    if window:
        out = _flash_window(*heads, window, float(scale), block_q, block_k,
                            interpret)
    elif selected is None:
        out = _flash(*heads, causal, float(scale), block_q, block_k,
                     interpret)
    else:
        out = _flash_selected(*heads, selected.astype(jnp.int8), causal,
                              float(scale), block_q, block_k, interpret)
    return out.reshape(B, H, T, Dv)
