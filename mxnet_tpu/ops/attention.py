"""Attention operator (the Transformer building block).

The reference (2017 MXNet 0.9.5) predates Transformers; its README's stretch
config (BASELINE.md Transformer-base MT) needs one. Registered as a single
fused op rather than a symbol-level composition of batch_dot/softmax so XLA
sees the whole softmax(QKᵀ)V contraction at once — the same reasoning that
made the reference wrap cuDNN kernels as one op. The sequence-parallel
(ring) execution of this op lives in parallel/ring_attention.py.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..base import MXNetError
from .registry import AttrSpec, register

# the additive mask of a pool slot a row does not hold: exp(-1e9) is 0 exactly
_NEG = np.float32(-1e9)

# trace-time dispatch counters, a form of ``attention_form`` each
# (observability for tests and the multichip dryrun: proves the seq-parallel
# path, or the kernel, actually engaged)
DISPATCH_COUNTS = {"ring": 0, "kernel": 0, "band": 0, "dense": 0,
                   "sparse": 0, "sparse_kernel": 0, "window_kernel": 0}

# float32 scores a blockwise form (a ragged band, a learned sparse selection)
# makes at once: the query blocks are sized from it
_SCORE_BYTES = 512 << 20


def _backend():
    """Where the program being traced will run: Mosaic runs on the chip
    alone. (A test that compiles for the chip from the CPU holds this to
    ``"tpu"``.)"""
    return jax.default_backend()


def _band_block(t, window):
    """The block of a band over ``t`` positions under ``window``: a block of
    b queries scores its own b keys and the b before them, so b is the
    window where that divides ``t``, else one less (query r of a block then
    reaches back to key r of the block before, exactly ``window - 1``
    positions) where THAT divides ``t``; 0 where neither does."""
    if not 0 < window < t:
        return 0
    if t % window == 0:
        return window
    return window - 1 if window > 1 and t % (window - 1) == 0 else 0


def attention_form(query, key, value, causal, window=0, sink=False,
                   mesh=None, topk=0):
    """THE rule that names the form ``MultiHeadAttention`` runs in, from the
    operands' shapes and types, the attributes, the mesh being traced under
    and the backend; no caller, option or environment variable does. Each
    operand carries ``.shape`` and ``.dtype``: ``query`` (B, H, T, dk), ``key``
    (B, Hkv, S, dk), ``value`` (B, Hkv, S, dv).

    ``"ring"``: plain self-attention (no window, no sink, equal head counts)
    traced under a ``mesh`` with a ``seq`` axis that divides T:
    ``parallel/ring_attention.py``.

    ``"sparse"``: ``topk`` > 0, a learned selection: every query attends the
    ``topk`` causal keys its indexer scores highest (all of them while it has
    no more), in query blocks, so neither the index logits nor the scores of
    all T x S pairs are made whole (``_sparse_attention``): every backend
    but the chip, a mesh of several devices, shapes the kernel refuses.

    ``"sparse_kernel"``: the same selection on the chip (one device), where
    ``pallas_attention.takes`` the operands under a mask: the query blocks
    make the SELECTION alone, a mask (B, T, S) int8 all heads share
    (``_selection``: the same index scores, the same ``_topk_mask``, so the
    same keys), and ONE call of the blockwise kernel a layer applies it to
    its blocks in VMEM beside the causal mask; the (heads, block, S) float32
    scores of a query block are never written. Differentiated, it is
    ``"sparse"``'s backward.

    ``"window_kernel"``: a window of W < T positions on the chip (one
    device, no sink), where ``pallas_attention.takes`` the operands under the
    window: ONE call of the blockwise kernel a layer, whose key axis runs
    from the block of a query block's oldest key to its diagonal's; operands
    in the type they arrive in, float32 sums, no score written, T any whole
    number of lane tiles (no multiple of W). ``takes`` refuses a band small
    enough that XLA's wins. Differentiated, it is the band's (or the dense
    form's) backward.

    ``"band"``: a window of W < T positions, T a multiple of W or of W - 1
    (``_band_block``): T x 2W scores (``_band_attention``): every backend
    but the chip, a sink, a mesh of several devices, what ``takes`` refuses.

    ``"kernel"``: plain CAUSAL attention over S >= T keys on the chip, where
    ``pallas_attention.takes`` the operands: blockwise with an online softmax,
    the (heads, T, S) scores never written, nothing above the diagonal
    computed or fetched; grouped heads and a value narrower than the key
    among them. Never under a ``mesh`` of several devices: the compiler
    partitions the dense path over a mesh and cannot partition a kernel (it
    would gather the operands whole onto every device).

    ``"dense"``: everything else, the scores of all T x S pairs in float32:
    every backend but the chip (a test that wants the kernel holds this rule
    and runs it interpreted), a step traced over several devices, cross- and
    bidirectional attention, a sink, a ragged window, and on the chip the
    shapes the kernel refuses or does not
    win (``pallas_attention.takes`` says which, with the chip runs that
    decided)."""
    b, h, t, _ = query.shape
    hkv, s = key.shape[1], key.shape[2]
    alone = _backend() == "tpu" and (mesh is None or mesh.size == 1)
    if topk > 0:
        if alone:
            from . import pallas_attention as kernel

            if kernel.takes(query, key, value, selected=True):
                return "sparse_kernel"
        return "sparse"
    plain = window <= 0 and not sink
    if plain and h == hkv and mesh is not None \
            and "seq" in mesh.axis_names and mesh.shape["seq"] > 1 \
            and s == t and t % mesh.shape["seq"] == 0 \
            and ("data" not in mesh.axis_names
                 or b % mesh.shape["data"] == 0):
        return "ring"
    if window > 0 and causal and not sink and alone:
        from . import pallas_attention as kernel

        if kernel.takes(query, key, value, window=window):
            return "window_kernel"
    if _band_block(t, window):
        return "band"
    if plain and causal and alone:
        from . import pallas_attention as kernel

        if kernel.takes(query, key, value):
            return "kernel"
    return "dense"


def _multi_head_attention_out(attrs, inputs):
    """``MultiHeadAttention``'s output from its operands' shapes: (B, H, T,
    the value's width) in the query's type. Shape inference asks this and
    traces no form: the kernel's would import Pallas to say the same."""
    query, _, value = inputs[:3]
    return [(query.shape[:3] + (value.shape[3],), query.dtype)]


@register(
    "_contrib_MultiHeadAttention",
    attrs={
        "causal": AttrSpec("bool", default=False),
        "scale": AttrSpec("float", default=-1.0),
        "window": AttrSpec("int", default=0),
        "sink": AttrSpec("bool", default=False),
        "topk": AttrSpec("int", default=0),
    },
    input_names=lambda attrs: ("query", "key", "value") + (
        ("sink",) if attrs.get("sink") else ()) + (
        ("index_query", "index_key", "index_weight")
        if attrs.get("topk", 0) > 0 else ()),
    aliases=("MultiHeadAttention",),
    infer=_multi_head_attention_out,
)
def _multi_head_attention(attrs, query, key, value, *more):
    """softmax(QKᵀ·scale + mask)V over (B, H, T, D) tensors, in the form
    ``attention_form`` names from the shapes, the attributes and the backend.
    The dense forms compute in fp32 for a stable softmax regardless of the IO
    dtype (bf16 fast path). On the chip, plain causal attention runs
    blockwise (``ops/pallas_attention.py``): the same mathematics in the same
    types (both products one pass of the matrix unit in the operands' type
    with a float32 accumulator, the softmax float32), so the forms differ by
    the order of a float32 sum. So does a WINDOW layer's prefill there
    (``"window_kernel"``: the same kernel, its key axis a query block's own
    blocks), where the band is large enough that the kernel wins; a sink, a
    mesh of several devices and a backward stay XLA's band.

    Sequence parallelism: when traced inside an SPMD step whose mesh has a
    ``seq`` axis (parallel.make_mesh({"data": dp, "seq": sp})), self-attention
    dispatches to ring attention (parallel/ring_attention.py) — q stays put,
    k/v blocks rotate over ICI via ppermute, softmax accumulates online.
    Disable with MXNET_RING_ATTENTION=0 (the call is then dense).

    Grouped queries: ``key`` / ``value`` may carry fewer heads (B, Hkv, S, D)
    than ``query`` (B, H, T, D), Hkv dividing H; key/value head j then serves
    query heads j * H/Hkv .. (j + 1) * H/Hkv - 1. The group is an axis of the
    query that both contractions carry (the keys are never repeated), of
    size 1 where the head counts are equal. ``value`` may be narrower or
    wider than ``key`` (latent attention's 128 under a 192-wide key): the
    output takes the value's width.

    ``window`` = W > 0 (causal self-attention only) lets position t attend
    the W positions t - W < j <= t, itself among them. ``sink=True`` takes a
    fourth input ``sink`` (H,), one logit a query head that joins the
    softmax's denominator and carries no value: ``p_j = exp(s_j - m) /
    (sum_k exp(s_k - m) + exp(b_h - m))``, ``m`` the maximum over the scores
    and ``b_h``. Both take the dense path. A windowed call over T > W
    positions, T a multiple of W, scores a BAND: a block of W queries
    against its own and the previous block of keys, T x 2W scores instead
    of T x T, the same positions under the same mask (the blocks are read
    from the shapes; a shorter or ragged call masks the full scores). Where
    T is a multiple of W - 1 and not of W the blocks are W - 1 wide
    (``_band_block``), and a band whose scores pass ``_SCORE_BYTES`` is
    computed a run of blocks at a time.

    ``topk`` = K > 0 (causal self-attention only) takes three more inputs, an
    INDEXER's: ``index_query`` (B, Hi, T, di), ``index_key`` (B, 1, T, di)
    and ``index_weight`` (B, T, Hi). Query t scores key s <= t
    ``I[t, s] = sum_j index_weight[t, j] * relu(index_query[t, j] .
    index_key[s])`` (products in the operands' type with a float32
    accumulator, the rest float32) and attends the K keys of largest I
    alone, all of them while t < K (``_sparse_attention``)."""
    import os

    sink = more[0] if attrs.get("sink") else None
    topk = attrs.get("topk", 0)
    b, h, t, d = query.shape
    hkv, s_len = key.shape[1], key.shape[2]
    _kv_groups(h, hkv, "MultiHeadAttention")
    window = attrs.get("window", 0)
    if window > 0 and not (attrs["causal"] and s_len == t):
        raise MXNetError("MultiHeadAttention: a window needs causal "
                         "self-attention, got causal=%s over %d queries and "
                         "%d keys" % (attrs["causal"], t, s_len))
    from ..parallel.mesh import current_trace_mesh

    mesh = current_trace_mesh()
    if topk > 0 and not (attrs["causal"] and s_len == t and window <= 0
                         and sink is None):
        raise MXNetError("MultiHeadAttention: topk needs plain causal "
                         "self-attention (no window, no sink), got causal=%s "
                         "over %d queries and %d keys"
                         % (attrs["causal"], t, s_len))
    form = attention_form(query, key, value, attrs["causal"], window,
                          sink is not None, mesh, topk)
    if form == "ring" and os.environ.get("MXNET_RING_ATTENTION", "1") != "1":
        form = "dense"
    DISPATCH_COUNTS[form] += 1
    if form == "ring":
        from ..parallel.ring_attention import ring_attention

        out = ring_attention(
            query.transpose(0, 2, 1, 3), key.transpose(0, 2, 1, 3),
            value.transpose(0, 2, 1, 3), mesh, seq_axis="seq",
            causal=attrs["causal"],
            scale=attrs["scale"] if attrs["scale"] > 0 else None,
            batch_axis="data" if "data" in mesh.axis_names else None)
        return out.transpose(0, 2, 1, 3)
    if form == "kernel":
        from . import pallas_attention as pa

        # off the chip (a test that holds the rule to the kernel) Pallas
        # interprets it
        return pa.flash_attention(
            query, key, value, causal=True, scale=max(attrs["scale"], 0.0),
            interpret=_backend() != "tpu")
    scale = attrs["scale"] if attrs["scale"] > 0 else 1.0 / np.sqrt(d)
    if form == "sparse":
        return _sparse_attention(query, key, value, *more[-3:], topk, scale)
    if form == "sparse_kernel":
        return _sparse_kernel_attention(query, key, value, *more[-3:], topk,
                                        scale, _backend() != "tpu")
    if form == "window_kernel":
        return _window_kernel_attention(query, key, value, window, scale,
                                        _backend() != "tpu")
    return _xla_attention(query, key, value, sink, attrs["causal"], window,
                          scale)


def _xla_attention(query, key, value, sink, causal, window, scale):
    """XLA's forms over float32 operands (``attention_form``'s ``"band"`` and
    ``"dense"``): a band where a block tiles T under the window
    (``_band_block``, ``_band_attention``), else the scores of all T x S
    pairs under the causal and the window's mask."""
    b, h, t, d = query.shape
    hkv, s_len = key.shape[1], key.shape[2]
    q = query.astype("float32").reshape(b, hkv, h // hkv, t, d)
    if sink is not None:
        sink = sink.astype("float32").reshape(hkv, h // hkv)
    if _band_block(t, window):
        out = _band_attention(q, key, value, window, scale, sink)
        return out.reshape(b, h, t, value.shape[-1]).astype(query.dtype)
    s = jnp.einsum("bkgqd,bkud->bkgqu", q, key.astype("float32")) * scale
    if causal:
        # bottom-right aligned so a rectangular (decode) call — T queries over
        # S >= T keys — lets each query see all S-T+q past keys
        mask = jnp.tril(jnp.ones((t, s_len), bool), k=s_len - t)
        if window > 0:
            mask &= ~jnp.tril(jnp.ones((t, s_len), bool), k=-window)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1) if sink is None \
        else _sink_softmax(s, sink[None, :, :, None, None])
    out = jnp.einsum("bkgqu,bkud->bkgqd", p, value.astype("float32"))
    return out.reshape(b, h, t, value.shape[-1]).astype(query.dtype)


def _window_kernel(query, key, value, window, scale, interpret):
    """A window layer's attention in the blockwise kernel's blocks
    (``attention_form``'s ``"window_kernel"``): ONE ``flash_attention`` under
    the window. Off the chip (a test that holds the rule) Pallas
    ``interpret``s it."""
    from . import pallas_attention as pa

    return pa.flash_attention(query, key, value, causal=True, scale=scale,
                              interpret=interpret, window=window)


# the kernel has no backward under a window: differentiated, the form is the
# band's (the dense form's where no block tiles T)
_window_kernel_attention = jax.custom_vjp(_window_kernel,
                                          nondiff_argnums=(3, 4, 5))


def _window_kernel_fwd(*operands_and_attrs):
    return _window_kernel(*operands_and_attrs), operands_and_attrs[:3]


def _window_kernel_bwd(window, scale, _interpret, operands, cotangent):
    return jax.vjp(lambda q, k, v: _xla_attention(
        q, k, v, None, True, window, scale), *operands)[1](cotangent)


_window_kernel_attention.defvjp(_window_kernel_fwd, _window_kernel_bwd)


def _sink_softmax(s, sink):
    """``softmax`` of float32 scores ``s`` over their last axis with one more
    logit, ``sink`` (broadcast against ``s``, its last axis 1), in the
    denominator: the sink takes weight and gives no value, so the weights
    that come back sum to less than 1."""
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), sink)
    e = jnp.exp(s - m)
    return e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sink - m))


def _blocks_of(n, whole_bytes):
    """The largest divisor of ``n`` blocks to take at once so that the
    float32 scores made at once, ``whole_bytes`` for all ``n``, stay inside
    ``_SCORE_BYTES`` (1 where one block alone is past it)."""
    return next((c for c in range(n, 0, -1) if n % c == 0
                 and whole_bytes * c <= _SCORE_BYTES * n), 1)


def _band_attention(q, k, v, w, scale, sink):
    """Causal attention under a window of ``w`` positions as a band: ``q``
    (B, Hkv, G, T, d) float32 in T / b blocks of b queries (``_band_block``:
    b is w, or w - 1), each against its own block of keys and the one before
    it, so the scores are T x 2b, float32; ``k`` and ``v`` (B, Hkv, T, x)
    in any type, computed in float32. Query r of a block sits b + r - j
    positions after key j of its 2b; it attends where that is in [0, w).
    The first block has no block before it: the zeros that stand there are
    masked as the future is. A band whose scores pass ``_SCORE_BYTES`` is
    computed a run of blocks at a time (``lax.map``), each run with the
    block before its first."""
    b, hkv, g, t, d = q.shape
    blk = _band_block(t, w)
    nb = t // blk
    ahead = blk + jnp.arange(blk)[:, None] - jnp.arange(2 * blk)[None, :]
    live = (ahead >= 0) & (ahead < w)
    first = live & (jnp.arange(2 * blk)[None, :] >= blk)

    def run(q, k, v, before_k, before_v, at):
        """``n`` blocks from block ``at`` on: q (B, Hkv, G, n, b, d), k and v
        (B, Hkv, n, b, x) float32, ``before_*`` the block before the first
        (B, Hkv, 1, b, x)."""
        banded = lambda a, before: jnp.concatenate([jnp.concatenate(
            [before, a[:, :, :-1]], axis=2), a], axis=3)
        s = jnp.einsum("bkgnqd,bknud->bkgnqu", q, banded(k, before_k)) * scale
        mask = jnp.where((at + jnp.arange(q.shape[3]))[:, None, None] == 0,
                         first, live)
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1) if sink is None \
            else _sink_softmax(s, sink[None, :, :, None, None, None])
        return jnp.einsum("bkgnqu,bknud->bkgnqd", p, banded(v, before_v))

    blocks = lambda a: a.reshape(a.shape[:-2] + (nb, blk, a.shape[-1]))
    n = _blocks_of(nb, 4 * b * hkv * g * t * 2 * blk)
    f32 = lambda a: a.astype(jnp.float32)
    if n == nb:     # the whole band at once, keys and values float32 whole
        kb, vb = blocks(f32(k)), blocks(f32(v))
        out = run(blocks(q), kb, vb, jnp.zeros_like(kb[:, :, :1]),
                  jnp.zeros_like(vb[:, :, :1]), 0)
        return out.reshape(b, hkv, g, t, v.shape[-1])
    qb, kb, vb = blocks(q), blocks(k), blocks(v)
    # the block before a run's first: block 0's is zeros
    shifted = lambda a: jnp.concatenate(
        [jnp.zeros_like(a[:, :, :1]), a], axis=2)
    ks, vs = shifted(kb), shifted(vb)

    def one(at):
        cut = lambda a, axis, start, size: jax.lax.dynamic_slice_in_dim(
            a, start, size, axis)
        k_run, v_run = (f32(cut(a, 2, at, n + 1)) for a in (ks, vs))
        return run(cut(qb, 3, at, n), k_run[:, :, 1:], v_run[:, :, 1:],
                   k_run[:, :, :1], v_run[:, :, :1], at)

    # (runs, B, Hkv, G, n, b, dv) -> (B, Hkv, G, T, dv)
    out = jax.lax.map(one, jnp.arange(0, nb, n))
    return jnp.moveaxis(out, 0, 3).reshape(b, hkv, g, t, v.shape[-1])


def index_scores(index_query, index_weight, index_key):
    """A sparse-attention INDEXER's scores: ``index_query`` (..., Q, Hi, di),
    ``index_weight`` (..., Q, Hi) and ``index_key`` (..., S, di) give
    ``I[q, s] = sum_j w[q, j] * relu(q[q, j] . k[s])`` (..., Q, S) float32:
    the products in the operands' type with a float32 accumulator, the
    ReLU, the weights and the sum over the indexer's heads float32."""
    logits = jnp.einsum("...qhd,...sd->...qhs", index_query, index_key,
                        preferred_element_type=jnp.float32)
    return jnp.einsum("...qhs,...qh->...qs", jax.nn.relu(logits),
                      index_weight.astype(jnp.float32))


def _topk_mask(score, topk):
    """``jax.lax.top_k``'s set over the last axis of ``score`` (..., S) as a
    mask: what lies above its ``topk``-th value, and of the values that tie
    there the positions up to the last one it took (the lower ones)."""
    values, at = jax.lax.top_k(score, topk)
    kth = values[..., -1:]
    last = jnp.max(jnp.where(values == kth, at, -1), axis=-1, keepdims=True)
    return (score > kth) | ((score == kth) & (
        jnp.arange(score.shape[-1], dtype=at.dtype) <= last))


def _selected_blocks(heads, index_query, index_key, index_weight, topk,
                     each):
    """A learned selection over T causal positions, a block of queries at a
    time: the indexer's ``index_query`` (B, Hi, T, di), ``index_key`` (B, 1,
    T, di) and ``index_weight`` (B, T, Hi). ``each(t0, upto, seen)`` is
    called a block (inside ``lax.map``): ``seen`` (B, block, ``upto``), true
    where the block's query ``t0 + r`` attends key s < ``upto``: the
    ``topk`` keys s <= t of largest ``index_scores`` (``jax.lax.top_k``'s
    set: of keys that tie at the last place the lower positions;
    ``_topk_mask``), every key while t < ``topk``. What the calls give back
    comes stacked, (blocks, ...) in the blocks' order.

    The blocks are sized so that the float32 scores of ``heads`` heads a
    block stay inside ``_SCORE_BYTES``, and run in up to eight groups, each
    over the keys up to its own last query, ``upto`` (seven sixteenths of the
    pairs above the diagonal are never scored), one after another."""
    b, _, t, _ = index_query.shape
    iq = index_query.transpose(0, 2, 1, 3)              # (B, T, Hi, di)
    ik = index_key[:, 0]
    rows = max(1, _SCORE_BYTES // (4 * b * heads * t))
    blk = next(c for c in range(min(rows, t), 0, -1) if t % c == 0)
    nb = t // blk
    groups = next(c for c in (8, 4, 2, 1) if nb % c == 0)

    def group(first, upto):
        """Query blocks ``first`` .. over keys 0 .. ``upto`` - 1."""
        ikeys = ik[:, :upto]
        at_key = jnp.arange(upto, dtype=jnp.int32)[None, :]

        def one(block):
            t0 = block * blk
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, t0, blk, 1)
            seen = (at_key <= t0 + jnp.arange(
                blk, dtype=jnp.int32)[:, None])[None]
            if upto > topk:
                seen = seen & _topk_mask(jnp.where(seen, index_scores(
                    cut(iq), cut(index_weight), ikeys), -jnp.inf), topk)
            return each(t0, upto, seen)

        return jax.lax.map(one, first + jnp.arange(nb // groups))

    return jnp.concatenate([group(j * (nb // groups), (j + 1) * (t // groups))
                            for j in range(groups)], axis=0)


def _sparse_attention(query, key, value, index_query, index_key,
                      index_weight, topk, scale):
    """Causal attention over a learned selection (``_selected_blocks``):
    ``query`` (B, H, T, d), ``key`` (B, Hkv, T, d), ``value`` (B, Hkv, T,
    dv) give (B, H, T, dv) in the query's type. Both products run in the
    operands' type with a float32 accumulator, the softmax's maximum,
    exponentials and sum float32, the exponentials rounded to the values'
    type for the second product and the context divided by their sum after
    it (the kernel's arithmetic). The selection is a MASK over the scores of
    a block of queries, (B, H, block, ``upto``) float32 inside
    ``_SCORE_BYTES``."""
    b, h, t, d = query.shape
    hkv = key.shape[1]
    q = query.reshape(b, hkv, h // hkv, t, d)

    def attend(t0, upto, seen):
        keys, values = key[:, :, :upto], value[:, :, :upto]
        s = jnp.einsum("bkgqd,bksd->bkgqs", jax.lax.dynamic_slice_in_dim(
            q, t0, seen.shape[1], 3), keys,
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(seen[:, None, None], s, -jnp.inf)
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        out = jnp.einsum("bkgqs,bksd->bkgqd", e.astype(values.dtype), values,
                         preferred_element_type=jnp.float32)
        return out / jnp.sum(e, axis=-1, keepdims=True)

    out = _selected_blocks(h, index_query, index_key, index_weight, topk,
                           attend)
    # (blocks, B, Hkv, G, blk, dv) -> (B, H, T, dv)
    return jnp.moveaxis(out, 0, 3).reshape(
        b, h, t, value.shape[-1]).astype(query.dtype)


def _selection(heads, index_query, index_key, index_weight, topk):
    """``_selected_blocks``' selection whole, (B, T, T) int8: 1 where query
    t attends key s, the mask every head shares (64 MiB at 8,192 positions,
    in place of a block's float32 scores of ``heads`` heads). Blocks, groups
    and arithmetic are ``_sparse_attention``'s, so are the selected keys."""
    t = index_query.shape[2]
    rows = _selected_blocks(
        heads, index_query, index_key, index_weight, topk,
        lambda t0, upto, seen: jnp.pad(seen.astype(jnp.int8), (
            (0, 0), (0, 0), (0, t - upto))))
    # (blocks, B, blk, T) -> (B, T, T)
    return jnp.moveaxis(rows, 0, 1).reshape(rows.shape[1], t, t)


def _sparse_kernel(query, key, value, index_query, index_key, index_weight,
                   topk, scale, interpret):
    """``_sparse_attention`` with the masked attention in the blockwise
    kernel's blocks (``attention_form``'s ``"sparse_kernel"``): the layer's
    ``_selection``, then ONE ``flash_attention`` under it. Off the chip (a
    test that holds the rule) Pallas ``interpret``s it."""
    from . import pallas_attention as pa

    return pa.flash_attention(
        query, key, value, causal=True, scale=scale, interpret=interpret,
        selected=_selection(query.shape[1], index_query, index_key,
                            index_weight, topk))


# the kernel has no backward under a selection: differentiated, the form is
# ``_sparse_attention``
_sparse_kernel_attention = jax.custom_vjp(_sparse_kernel,
                                          nondiff_argnums=(6, 7, 8))


def _sparse_kernel_fwd(*operands_and_attrs):
    return _sparse_kernel(*operands_and_attrs), operands_and_attrs[:6]


def _sparse_kernel_bwd(topk, scale, _interpret, operands, cotangent):
    return jax.vjp(lambda *a: _sparse_attention(*a, topk, scale),
                   *operands)[1](cotangent)


_sparse_kernel_attention.defvjp(_sparse_kernel_fwd, _sparse_kernel_bwd)


def _kv_groups(heads, kv_heads, what):
    """Query heads a key/value head serves; 1 where the counts are equal."""
    if kv_heads < 1 or heads % kv_heads:
        raise MXNetError("%s: %d query heads do not divide over %d key/value "
                         "heads" % (what, heads, kv_heads))
    return heads // kv_heads


@register(
    "_contrib_RMSNorm",
    attrs={"eps": AttrSpec("float", default=1e-5)},
    input_names=("data", "gamma"),
    aliases=("RMSNorm",),
)
def _rms_norm(attrs, data, gamma):
    """``x / sqrt(mean(x^2) + eps) * gamma`` over the last axis; the
    statistics are float32 whatever the IO dtype."""
    x = data.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x / jnp.sqrt(ms + attrs["eps"]) * gamma.astype(jnp.float32)
    return y.astype(data.dtype)


def yarn_inv_freq(dim, base, factor, original_max_position, beta_fast=32.0,
                  beta_slow=1.0):
    """YaRN's ``dim / 2`` inverse frequencies (float64), the same at every
    position (static, ``truncate`` on): ``e_i = base^(-2i/dim)`` where a
    feature turns more than ``beta_fast`` times over the
    ``original_max_position`` positions of the first training (extrapolated:
    left as they are), ``e_i / factor`` where it turns fewer than
    ``beta_slow`` times (interpolated: the new context squeezed into the
    old), a linear blend between. ``corr(n) = dim ln(L0 / (2 pi n)) /
    (2 ln base)`` is the index of the feature that turns n times;
    ``low = max(floor(corr(beta_fast)), 0)``, ``high = min(ceil(corr(
    beta_slow)), dim - 1)``, ``ramp_i = clip((i - low) / (high - low), 0,
    1)``, ``inv_freq_i = e_i / factor * ramp_i + e_i * (1 - ramp_i)``."""
    corr = lambda turns: dim * np.log(
        original_max_position / (turns * 2 * np.pi)) / (2 * np.log(base))
    low = max(int(np.floor(corr(beta_fast))), 0)
    high = min(int(np.ceil(corr(beta_slow))), dim - 1)
    plain = float(base) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low if high > low else 0.001), 0, 1)
    return plain / factor * ramp + plain * (1 - ramp)


@register(
    "_contrib_RotaryEmbedding",
    attrs={"base": AttrSpec("float", default=10000.0),
           "interleaved": AttrSpec("bool", default=False),
           "rotary_dim": AttrSpec("int", default=0),
           "yarn_factor": AttrSpec("float", default=0.0),
           "yarn_original_max_position": AttrSpec("int", default=0),
           "yarn_beta_fast": AttrSpec("float", default=32.0),
           "yarn_beta_slow": AttrSpec("float", default=1.0),
           "attention_factor": AttrSpec("float", default=1.0)},
    input_names=("data", "positions"),
    aliases=("RotaryEmbedding",),
)
def _rotary_embedding(attrs, data, positions):
    """Rotary position embedding of ``data`` (B, H, T, dh) at ``positions``
    (B, T), which are DATA (a decode step's lanes each sit at their own):
    the half-split rotation (``rotate_half``: feature i pairs with
    i + dh/2), ``inv_freq_i = base^(-2i/dh)``. ``interleaved`` pairs
    feature 2i with 2i + 1 instead (``rope_interleave`` of
    ``model_type: deepseek_v3``), same frequencies. Angles, sine and cosine
    are float32 whatever the IO dtype. ``rotary_dim`` = r > 0 rotates the
    FIRST r features of a head alone, as a head of r features (pairs
    (i, i + r/2), ``inv_freq_i = base^(-2i/r)``); the other dh - r pass
    through (``partial_rotary_factor``). ``yarn_factor`` = s > 0 takes
    YaRN's inverse frequencies over the rotated features instead
    (``yarn_inv_freq``: ``base``, s, ``yarn_original_max_position``,
    ``yarn_beta_fast``, ``yarn_beta_slow``) and multiplies sine and cosine
    by ``attention_factor``, so a rotated feature leaves scaled and a
    feature that passes through does not."""
    part = attrs.get("rotary_dim", 0)
    if part and part != data.shape[-1]:
        if part % 2 or not 0 < part < data.shape[-1]:
            raise MXNetError("RotaryEmbedding: rotary_dim %d must be even "
                             "and inside a head of %d features"
                             % (part, data.shape[-1]))
        turned = _rotary_embedding(dict(attrs, rotary_dim=0),
                                   data[..., :part], positions)
        return jnp.concatenate([turned, data[..., part:]], axis=-1)
    dh = data.shape[-1]
    yarn = attrs.get("yarn_factor", 0.0) > 0
    if yarn:
        inv_freq = jnp.asarray(yarn_inv_freq(
            dh, attrs["base"], attrs["yarn_factor"],
            attrs["yarn_original_max_position"], attrs["yarn_beta_fast"],
            attrs["yarn_beta_slow"]), jnp.float32)
    else:
        inv_freq = attrs["base"] ** (
            -jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = positions.astype(jnp.float32)[:, None, :, None] * inv_freq
    # (the plain path's lowered text is every cell's compile-cache key: it
    # takes each function of the angle where it always did)
    turned = (lambda f: f(angle) * jnp.float32(attrs["attention_factor"])) \
        if yarn else (lambda f: f(angle))
    if attrs.get("interleaved"):
        x = data.astype(jnp.float32).reshape(data.shape[:-1] + (dh // 2, 2))
        x1, x2 = x[..., 0], x[..., 1]
        cos, sin = turned(jnp.cos), turned(jnp.sin)
        y = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return y.reshape(data.shape).astype(data.dtype)
    cos = jnp.concatenate([turned(jnp.cos), turned(jnp.cos)], axis=-1)
    sin = jnp.concatenate([turned(jnp.sin), turned(jnp.sin)], axis=-1)
    x = data.astype(jnp.float32)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    y = x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
    return y.astype(data.dtype)


# the chip's lanes: a pool whose row is whole tiles of them is kept a page at
# a time (``pool_shape``)
_LANES = 128


def pool_shape(heads, width, slots, page_size):
    """The shape a cache entry of kind ``"pool"`` is bound in, ``heads``
    key/value heads of ``width`` numbers over ``slots`` slots in pages of
    ``page_size``; THE one rule of the pools' layout, and what every operator
    here reads a pool's layout back from (``_paged``: its last dimension).

    A token's row of all its heads side by side, ``heads * width``, that is a
    multiple of the chip's 128 lanes: ``(frames, page_size, heads * width)``,
    PAGE-MAJOR. A page is then one contiguous piece that carries every head,
    the chip keeps the buffer row-major, and a kernel copies a lane's live
    pages and nothing else (``ops/pallas_paged_read.py``). Any other row (a
    latent cache's one head of 576; a toy model's heads of 8):
    ``(heads, slots, width)``, HEAD-MAJOR, which the chip keeps slots-minor
    where ``width`` is narrow. No row is padded to get there."""
    if pool_paged(heads, width):
        return (slots // page_size, page_size, heads * width)
    return (heads, slots, width)


def pool_paged(heads, width):
    """Whether a pool of ``heads`` heads of ``width`` is kept page-major."""
    return (heads * width) % _LANES == 0


def _paged(pool):
    """Whether ``pool`` (a shape, or anything with one) is bound page-major:
    ``pool_shape``'s rule, read back from the last dimension (a head-major
    pool's is never a multiple of 128: its row would be one)."""
    return getattr(pool, "shape", pool)[-1] % _LANES == 0


def pool_slots(shape):
    """The slots of a pool of this shape, in either layout."""
    return shape[0] * shape[1] if _paged(shape) else shape[1]


@register(
    "_contrib_KVPoolWrite",
    input_names=("pool", "rows", "onehot"),
    aliases=("KVPoolWrite",),
)
def _kv_pool_write(attrs, pool, rows, onehot):
    """The write into the shared KV pool of the paged decoder: ``pool``
    (H, S, dh), ``rows`` (R, H, dh) and ``onehot`` (R, S), row r's slot,
    give ``pool * (1 - sum_r onehot) + einsum('rs,rhd->hsd', onehot, rows)``
    in the pool's dtype. Rows are the lanes of a decode step or the
    positions of a chunk; their slots are disjoint, so the matmul with the
    one-hots IS the scatter, and an all-zero one-hot row writes nothing. A
    page-major pool (``pool_shape``: (frames, page, H * dh)) is the same
    blend over its (S, H * dh) rows, element for element.

    A stored row is the row bit for bit. On the chip a float32 matmul at
    the default precision would round it to bfloat16, so the contraction
    asks for ``Precision.HIGHEST``: the one-hot is exact in one bfloat16
    piece and the row's three pieces add back to the float32 value. A
    bfloat16 pool is exact in one pass: a sum of one row and zeros rounds
    nowhere, whatever the accumulator."""
    dt = pool.dtype
    keep = (1.0 - jnp.sum(onehot, axis=0)).astype(dt)
    if _paged(pool):
        flat = pool.reshape(-1, pool.shape[2])
        written = jnp.einsum(
            "rs,rw->sw", onehot.astype(dt),
            rows.astype(dt).reshape(rows.shape[0], pool.shape[2]),
            precision=jax.lax.Precision.HIGHEST)
        return (flat * keep[:, None] + written).reshape(pool.shape)
    written = jnp.einsum("rs,rhd->shd", onehot.astype(dt), rows.astype(dt),
                         precision=jax.lax.Precision.HIGHEST)
    return pool * keep[None, :, None] + written.transpose(1, 0, 2)


# slots a slot-indexed write into a HEAD-MAJOR pool reads and writes back
# around a row's slot: the chip's tile of a pool it keeps slots-minor, so a
# run is whole tiles
_WRITE_RUN = 128


def _slot_write_inputs(attrs):
    return [name % i for i in range(attrs.get("num_pools", 1))
            for name in ("pool_%d", "rows_%d")] + ["write_slot"]


def pool_write_form(pools):
    """THE rule that names the form ``KVPoolSlotWrite`` writes a call's pools
    in, from their shapes alone, on every backend; no caller, option or
    environment variable does. ``pools`` carry ``.shape``, in the operator's
    order.

    ``"scatter"``: one XLA scatter a PAGE-MAJOR pool over its (slots, H * dh)
    rows puts all the call's rows in at once (``_scatter_rows``).

    ``"loop"``: no pool of the call is page-major; one loop over the rows
    updates the run of slots around each row's in every HEAD-MAJOR pool.

    A call of both layouts is named by its page-major pools' form; its
    head-major pools keep their loop."""
    return "scatter" if any(_paged(pool) for pool in pools) else "loop"


def _scatter_rows(pools, rows, slot):
    """``rows[p][r]`` at slot ``slot[r]`` of page-major ``pools[p]``, one XLA
    scatter a pool over its (slots, H * dh) rows, the slot the one scattered
    index. A row that writes nothing (a negative slot; an earlier row whose
    slot a later row names, so that the later one stays) is sent past the
    pool's end, each to an index of its own, and dropped, as is a row whose
    slot lies past the pool's end already."""
    lane = jnp.arange(slot.shape[0], dtype=jnp.int32)
    later = (slot[None, :] == slot[:, None]) & (lane[None, :] > lane[:, None])
    dead = (slot < 0) | jnp.any(later, axis=1)
    out = []
    for pool, new in zip(pools, rows):
        slots, width = pool.shape[0] * pool.shape[1], pool.shape[2]
        flat = pool.reshape(slots, width).at[
            jnp.where(dead, slots + lane, slot)].set(
                new.reshape(-1, width), mode="drop", unique_indices=True)
        out.append(flat.reshape(pool.shape))
    return out


@register(
    "_contrib_KVPoolSlotWrite",
    attrs={"num_pools": AttrSpec("int", default=1)},
    input_names=_slot_write_inputs,
    num_outputs=lambda attrs: attrs.get("num_pools", 1),
    aliases=("KVPoolSlotWrite",),
)
def _kv_pool_slot_write(attrs, *inputs):
    """``KVPoolWrite`` for rows that each name their slot, into
    ``num_pools`` pools at once (a layer's K and V): ``pool_i`` and
    ``rows_i`` (R, H, dh), pair after pair, then ``write_slot`` (R, 1), row
    r's slot as an index (the float32 the executor binds its inputs in,
    exact below 2^24 slots), negative for a row that writes nothing (a lane
    that rides along). What comes back is every pool with slot ``slot_r``
    holding ``rows[r]`` in the pool's dtype, row after row: a stored row is
    the row bit for bit, and where two rows name one slot the later one
    stays.

    Nothing here is a pool's size, every pool is written in its own layout
    (``pool_shape``) and form (``pool_write_form``), and a program that takes
    the pools DONATED updates them in place. A slot past the pool's end
    writes nothing either, in both layouts; no caller sends one.

    A PAGE-MAJOR pool (frames, page, H * dh) takes ALL the call's rows in ONE
    device operation, an XLA scatter over its (slots, H * dh) rows
    (``_scatter_rows``). The chip keeps such a pool row-major, a token's row
    is one contiguous piece of it, and nothing is re-laid around a write by
    index. Until PR 55 a loop wrote them a row at a time, a slice, a select
    and an update each: 6,144 turns a step where a token keeps 384 rows
    (``PERF.md`` section 6, PR 55).

    A HEAD-MAJOR pool (H, S, dh), in ONE loop over the rows for all of them:
    the aligned run of ``_WRITE_RUN`` slots that holds the slot is read, the
    row put in by ``where`` and the run written back. An update one slot
    wide, or a scatter over the slot axis, says the same, but the chip keeps
    a narrow head-major pool slots-minor and re-lays the WHOLE buffer out
    around either, twice a buffer. Those writes are bound by their count, not
    their bytes, and the loop's shape was chosen on the chip (``PERF.md``
    section 6, PR 37): a run of one tile beats a page of 16 slots, one loop a
    layer beats one a pool, and the index arithmetic stays inside the
    loop."""
    pools, rows = list(inputs[0:-1:2]), inputs[1:-1:2]
    n_rows = rows[0].shape[0]
    if n_rows == 0:
        return tuple(pools)
    slot = inputs[-1].reshape(-1).astype(jnp.int32)
    rows = [r.astype(p.dtype) for r, p in zip(rows, pools)]
    paged = [i for i, pool in enumerate(pools) if _paged(pool)]
    for i, pool in zip(paged, _scatter_rows(
            [pools[i] for i in paged], [rows[i] for i in paged], slot)):
        pools[i] = pool
    head_major = [i for i in range(len(pools)) if i not in paged]
    if not head_major:
        return tuple(pools)

    # a head-major pool's run (the pools of a call have the same slots)
    slots = pools[head_major[0]].shape[1]
    run = min(_WRITE_RUN, slots)
    in_run = jnp.arange(run, dtype=jnp.int32)[None, :, None]

    def write_head_major(pool, new, r):
        # the last run is cut short by the pool's end: start it earlier
        base = jnp.minimum(jnp.maximum(slot[r], 0) // run * run, slots - run)
        at_slot = in_run == slot[r] - base
        heads, _, dh = pool.shape
        old = jax.lax.dynamic_slice(pool, (0, base, 0), (heads, run, dh))
        row = jax.lax.dynamic_index_in_dim(new, r, 0, keepdims=False)
        return jax.lax.dynamic_update_slice(
            pool, jnp.where(at_slot, row[:, None, :], old), (0, base, 0))

    def write(r, loop_pools):
        return tuple(write_head_major(pool, rows[i], r)
                     for i, pool in zip(head_major, loop_pools))

    for i, pool in zip(head_major, jax.lax.fori_loop(
            0, n_rows, write, tuple(pools[i] for i in head_major))):
        pools[i] = pool
    return tuple(pools)


def pool_read_bytes(query, pool_k, pool_v, page_table, page_size):
    """(whole, own): the bytes either XLA form of ``KVPoolAttention``'s read
    moves through the chip's memory over HEAD-MAJOR pools (Hkv, S, d), from
    the operands' shapes and types alone (``pool_read_form`` says what each
    operand is, and asks this of head-major pools only: a page-major pool's
    read on the chip is the kernel's). Every pool is counted ON ITS OWN: a
    key pool and a value pool may differ in width, and so do their bytes and
    the padding of their copies.

    Whole: the pools once and the float32 scores, R x H x S, three times
    (written, read by the softmax, read by the context). Own pages: the
    pools re-laid from slots-minor to rows (read and written), then a copy
    of R x Hkv x max_pages x page_size rows a pool, the minor dimension
    padded to the chip's 128 lanes (a 192-wide key to 256, a 128-wide value
    not at all), written by the gather and read by it and by both
    contractions, and the small scores."""
    rows, heads = query.shape[:2]
    own_slots = page_table.shape[1] * page_size
    pool_bytes = copy_bytes = 0
    for pool in (pool_k,) if pool_v is None else (pool_k, pool_v):
        hkv, _, d = pool.shape
        size = jnp.dtype(pool.dtype).itemsize
        pool_bytes += int(np.prod(pool.shape)) * size
        copy_bytes += rows * hkv * own_slots * -(-d // 128) * 128 * size
    whole = pool_bytes + 3 * 4 * rows * heads * pool_k.shape[1]
    own = 2 * pool_bytes + 4 * copy_bytes + 3 * 4 * rows * heads * own_slots
    return whole, own


def pool_read_form(query, pool_k, pool_v, page_table, page_size,
                   selected=None):
    """THE rule that names the form of ``KVPoolAttention``'s read, from the
    operands' shapes and types and the backend; no caller, option or
    environment variable does. Each operand carries ``.shape`` and
    ``.dtype``: ``query`` (R, H, dk), the pools in either layout
    (``pool_shape``), each of its own width (``pool_v`` None where the value
    is read from the key's pool), ``page_table`` (R, max_pages) or None for a
    read that was handed no table.

    ``"whole_pool"``: every row scores every slot under its mask. A read
    with no table (a chunk's rows), and a head-major pool whose own pages
    would move more bytes than the pool does (``pool_read_bytes``: a toy
    model's narrow heads).

    ``"own_pages"``: XLA gathers the frames a row's table names, all
    ``max_pages`` of them, and scores those. A head-major pool that is small
    beside its scores (one wide latent row read by every head), and a
    page-major pool wherever the kernel cannot run: on the CPU, or where
    ``pallas_paged_read.supported`` refuses the operands (one pool that is
    key and value both; a page that is no whole tile).

    ``"kernel"``: ``pallas_paged_read.paged_read`` copies a row's live pages,
    up to its own context, out of page-major pools: two pools, on the
    chip.

    ``"selected"``: the read was handed ``selected`` (R, K), the positions of
    a row's own context an indexer chose (``SparseIndexSelect``): XLA gathers
    those K rows of the pool through the row's table and scores them alone,
    whatever the pool's layout."""
    if selected is not None:
        return "selected"
    if page_table is None or page_size < 1:
        return "whole_pool"
    pools = (pool_k,) if pool_v is None else (pool_k, pool_v)
    if all(_paged(pool) for pool in pools):
        from . import pallas_paged_read as kernel

        if pool_v is not None and _backend() == "tpu" \
                and kernel.supported(query, pool_k, pool_v):
            return "kernel"
        return "own_pages"
    if any(_paged(pool) for pool in pools):   # one of each: no byte count
        return "own_pages"
    whole, own = pool_read_bytes(query, pool_k, pool_v, page_table, page_size)
    return "own_pages" if own < whole else "whole_pool"


def _pool_softmax_context(scores, scale, mask, values, contraction):
    """``softmax(scores * scale + mask)`` in float32, then the context's
    contraction with a float32 accumulator: what the XLA forms of the pool's
    read share."""
    p = jax.nn.softmax(scores * scale + mask, axis=-1)
    return jnp.einsum(contraction, p, values,
                      preferred_element_type=jnp.float32)


def _context_slots(pos_idx, write_slot):
    """A row's context in slots, (R,) int32: ``pos + 1`` where it writes
    (its own slot included), none where its write slot is negative."""
    return jnp.where(write_slot.reshape(-1) >= 0,
                     pos_idx.reshape(-1).astype(jnp.int32) + 1, 0)


def _whole_pool(pool, hkv):
    """``pool`` with its heads an axis, and that operand's einsum subscript
    over (heads k, slots s, width d): a page-major pool's rows split into
    their heads, a view."""
    if _paged(pool):
        return pool.reshape(-1, hkv, pool.shape[2] // hkv), "skd"
    return pool, "ksd"


def _own_pages(pool, table, page, hkv):
    """``pool`` at the frames ``table`` (R, max_pages) names, a row's pages
    in order, and that operand's einsum subscript over (heads k, rows r,
    slots u, width d): (Hkv, R, max_pages * page, d) of a head-major pool,
    (R, max_pages * page, Hkv, d) of a page-major one, whose frames are its
    first axis. The host's table is in bounds (frames, zeros past them), so
    nothing is clipped or filled: the default mode adds a ``select`` over the
    whole copy."""
    rows, max_pages = table.shape
    if _paged(pool):
        own = pool.at[table].get(mode="promise_in_bounds")
        return own.reshape(rows, max_pages * page, hkv, -1), "rukd"
    _, slots, d = pool.shape
    own = pool.reshape(hkv, slots // page, page, d).at[:, table].get(
        mode="promise_in_bounds")
    return own.reshape(hkv, rows, max_pages * page, d), "krud"


def _selected_rows(pool, slots, hkv):
    """``pool`` at ``slots`` (R, K), a row's chosen slots, and that operand's
    einsum subscript over (heads k, rows r, chosen u, width d): (Hkv, R, K,
    d) of a head-major pool, (R, K, Hkv, d) of a page-major one. The slots
    are in bounds (a row's own frames), so nothing is clipped or filled."""
    if _paged(pool):
        own = pool.reshape(-1, pool.shape[2]).at[slots].get(
            mode="promise_in_bounds")
        return own.reshape(slots.shape + (hkv, -1)), "rukd"
    return pool.at[:, slots].get(mode="promise_in_bounds"), "krud"


def _pool_heads(query, pool_k):
    """The key/value heads of ``pool_k`` (either layout) under ``query``
    (R, H, dk)."""
    return pool_k.shape[2] // query.shape[2] if _paged(pool_k) \
        else pool_k.shape[0]


def _kv_pool_attention_out(attrs, inputs):
    """``KVPoolAttention``'s output from its operands' shapes: (R, H, the
    value's width) in the query's type. Shape inference asks this and traces
    no read: the kernel's form would import Pallas to say the same."""
    query, pool_k, pool_v = inputs[:3]
    width = attrs.get("value_dim", 0) or (
        pool_v.shape[2] // _pool_heads(query, pool_k) if _paged(pool_v)
        else pool_v.shape[2])
    return [(query.shape[:2] + (width,), query.dtype)]


@register(
    "_contrib_KVPoolAttention",
    attrs={"scale": AttrSpec("float", default=-1.0),
           "value_dim": AttrSpec("int", default=0),
           "page_size": AttrSpec("int", default=0),
           "selected": AttrSpec("bool", default=False)},
    input_names=lambda attrs: ("query", "pool_k", "pool_v", "mask") + (
        ("page_table", "pos_idx", "write_slot")
        if attrs.get("page_size", 0) > 0 else ()) + (
        ("selected",) if attrs.get("selected") else ()),
    aliases=("KVPoolAttention",),
    infer=_kv_pool_attention_out,
)
def _kv_pool_attention(attrs, query, pool_k, pool_v, mask, page_table=None,
                       pos_idx=None, write_slot=None, selected=None):
    """The read of the shared KV pool: every row of ``query`` (R, H, dh)
    attends ``pool_k`` / ``pool_v`` (H, S, dh) under its own additive
    ``mask`` (R, S): ``softmax(einsum('rhd,hsd->rhs') * scale + mask)`` then
    ``einsum('rhs,hsd->rhd')``. Both contractions run on the matrix unit at
    the default matmul precision (what ``_multi_head_attention`` gives the
    same tokens in the prefill) with a float32 accumulator, and the softmax
    is float32 whatever the pool's dtype. A fully masked row comes out
    finite: the softmax subtracts the row's maximum first. A pool of fewer
    heads (Hkv, S, dh) than the query's serves them in groups, as
    ``MultiHeadAttention`` does: the group is an axis of the query that both
    contractions carry (size 1 where the counts are equal), so the pool is
    read once and never repeated. A pool bound page-major (``pool_shape``:
    (frames, page, Hkv * dh), slot s at frame ``s // page``) is the same
    pool: its rows are split into their heads, a view.

    ``value_dim`` > 0 takes the value from the first ``value_dim`` columns
    of ``pool_v``, which may then BE ``pool_k``: a latent cache keeps one
    row a token, [c | k_r], that is the key whole and the value in its
    first columns. The context is contracted over the whole row and cut
    after, so the pool is one operand of both matmuls and is never sliced
    into a copy. The output is (R, H, value width) either way.

    ``page_size`` > 0 hands the read what ``mask`` was made of (a decode
    step's ``KVPageMask``): ``page_table`` (R, max_pages), ``pos_idx`` and
    ``write_slot`` (R, 1). The read may then take only the frames a row's
    table names and never read ``mask`` (a program none of whose reads does
    builds none): XLA gathers them into (R, max_pages * page_size) slots a
    row and scores those, the first ``pos + 1`` of them live and none where
    the write slot is negative; or, over page-major pools on the chip, the
    kernel of ``ops/pallas_paged_read.py`` copies the live ones and stops
    there. The same mathematics in the same types: a slot outside a row's
    context weighs exactly 0 in every form, so they differ by the order of a
    float32 sum (a row with NO context is finite in every form and nobody's
    to read: the mean of whatever the slots hold, or zeros from the kernel).
    ``pool_read_form`` chooses, from the shapes and types of the operands and
    the backend; no caller does.

    ``selected=True`` (with ``page_size``) takes one more input ``selected``
    (R, K): positions of the row's own context, -1 where it has fewer than K
    (``SparseIndexSelect``). The row attends THOSE rows of the pool and no
    others: position p is slot ``table[p // page] * page + p % page``, the K
    rows are gathered and scored under a mask of the -1s."""
    scale = attrs["scale"] if attrs["scale"] > 0 \
        else 1.0 / np.sqrt(query.shape[-1])
    r, h, dh = query.shape
    hkv = _pool_heads(query, pool_k)
    q = query.reshape(r, hkv, _kv_groups(h, hkv, "KVPoolAttention"), dh)
    page = attrs.get("page_size", 0)
    shared = pool_v is pool_k
    form = pool_read_form(query, pool_k, None if shared else pool_v,
                          page_table, page, selected)
    if form == "selected":
        at = selected.astype(jnp.int32)
        chosen = jnp.maximum(at, 0)
        slots = jnp.take_along_axis(page_table.astype(jnp.int32),
                                    chosen // page, axis=1) * page \
            + chosen % page
        own_k, sub_k = _selected_rows(pool_k, slots, hkv)
        own_v, sub_v = (own_k, sub_k) if shared \
            else _selected_rows(pool_v, slots, hkv)
        s = jnp.einsum("rkgd,%s->rkgu" % sub_k, q, own_k,
                       preferred_element_type=jnp.float32)
        out = _pool_softmax_context(
            s, scale, jnp.where(at >= 0, jnp.float32(0),
                                _NEG)[:, None, None, :],
            own_v, "rkgu,%s->rkgd" % sub_v)
    elif form == "kernel":
        from .pallas_paged_read import paged_read

        # off the chip (a test that holds the rule to the kernel) Pallas
        # interprets it
        out = paged_read(query, pool_k, pool_v, page_table.astype(jnp.int32),
                         _context_slots(pos_idx, write_slot),
                         scale=float(scale), interpret=_backend() != "tpu"
                         ).reshape(r, hkv, h // hkv, -1)
    elif form == "own_pages":
        table = page_table.astype(jnp.int32)
        own_k, sub_k = _own_pages(pool_k, table, page, hkv)
        own_v, sub_v = (own_k, sub_k) if shared \
            else _own_pages(pool_v, table, page, hkv)
        live = jnp.arange(table.shape[1] * page, dtype=jnp.int32) \
            < _context_slots(pos_idx, write_slot)[:, None]
        s = jnp.einsum("rkgd,%s->rkgu" % sub_k, q, own_k,
                       preferred_element_type=jnp.float32)
        out = _pool_softmax_context(
            s, scale, jnp.where(live, jnp.float32(0), _NEG)[:, None, None, :],
            own_v, "rkgu,%s->rkgd" % sub_v)
    else:
        all_k, sub_k = _whole_pool(pool_k, hkv)
        all_v, sub_v = (all_k, sub_k) if shared else _whole_pool(pool_v, hkv)
        s = jnp.einsum("rkgd,%s->rkgs" % sub_k, q, all_k,
                       preferred_element_type=jnp.float32)
        out = _pool_softmax_context(
            s, scale, mask.astype(jnp.float32)[:, None, None, :], all_v,
            "rkgs,%s->rkgd" % sub_v)
    if attrs.get("value_dim", 0) > 0:
        out = out[..., :attrs["value_dim"]]
    return out.reshape(r, h, out.shape[-1]).astype(query.dtype)


@register(
    "_contrib_KVPageMask",
    attrs={"page_size": AttrSpec("int", required=True),
           "num_slots": AttrSpec("int", required=True)},
    input_names=("page_table", "pos_idx", "write_slot"),
    aliases=("KVPageMask",),
)
def _kv_page_mask(attrs, page_table, pos_idx, write_slot):
    """``KVPoolAttention``'s additive mask from each row's page table:
    ``page_table`` (R, max_pages) names the frames of the row's pages in
    order, ``pos_idx`` (R, 1) its position and ``write_slot`` (R, 1) whether
    it writes there, so its context is ``n = pos + 1`` slots, or none where
    the slot is negative. The result is (R, num_slots) float32: 0.0 on the
    ``page_size`` slots of each of the first ``ceil(n / page_size)`` frames
    but, in the last of them, only on its first ``n - (pages - 1) *
    page_size``; ``-1e9`` everywhere else. Table entries past those pages
    are never looked at, and a frame two rows share shows in both.

    Every page is compared with every frame of the pool (R x max_pages x
    frames integer compares, reduced over the pages): a few microseconds
    against the reads of the pool that follow."""
    page, slots = attrs["page_size"], attrs["num_slots"]
    if page < 1 or slots % page:
        raise MXNetError("KVPageMask: page_size %d must divide num_slots %d"
                         % (page, slots))
    rows, max_pages = page_table.shape
    n = _context_slots(pos_idx, write_slot)
    # context slots in each of the row's pages: a full page, the last one's
    # remainder, none past it
    held = jnp.clip(
        n[:, None] - page * jnp.arange(max_pages, dtype=jnp.int32)[None, :],
        0, page)
    frames = jnp.arange(slots // page, dtype=jnp.int32)
    at_frame = page_table.astype(jnp.int32)[:, :, None] == frames
    held_in_frame = jnp.max(jnp.where(at_frame, held[:, :, None], 0), axis=1)
    live = jnp.arange(page, dtype=jnp.int32) < held_in_frame[:, :, None]
    return jnp.where(live, jnp.float32(0), _NEG).reshape(rows, slots)


def _ring_write_inputs(attrs):
    return [name % i for i in range(attrs.get("num_rings", 1))
            for name in ("ring_%d", "rows_%d")] + ["pos_idx", "write_slot"]


@register(
    "_contrib_KVRingWrite",
    attrs={"num_rings": AttrSpec("int", default=1)},
    input_names=_ring_write_inputs,
    num_outputs=lambda attrs: attrs.get("num_rings", 1),
    aliases=("KVRingWrite",),
)
def _kv_ring_write(attrs, *inputs):
    """A decode step's write into the per-lane rings of a WINDOW layer, into
    ``num_rings`` rings at once (the layer's K and V): ``ring_i``
    (R, Hkv, W, d) and ``rows_i`` (R, Hkv, d), pair after pair, then
    ``pos_idx`` (R, 1), row r's position, and ``write_slot`` (R, 1), negative
    for a row that writes nothing (a lane that rides along; its value is
    otherwise the pools' business). What comes back is every ring with
    ``ring[r, :, pos_r mod W, :] = rows[r]`` in the ring's dtype: after the
    token at position t the ring holds exactly the positions t - W + 1 .. t,
    each at its position mod W, and a stored row is the row bit for bit.

    A ring is a lane's own: it takes no frame of the page allocator and no
    entry of a page table, and its bytes do not depend on the lane's length.
    The write is one ``where`` over the ring, W slots a lane: a program that
    takes the rings donated updates them in place."""
    rings, rows = inputs[0:-2:2], inputs[1:-2:2]
    window = rings[0].shape[2]
    pos = inputs[-2].reshape(-1).astype(jnp.int32)
    at = (jnp.arange(window, dtype=jnp.int32)[None, :]
          == (pos % window)[:, None]) & (inputs[-1].reshape(-1) >= 0)[:, None]
    return tuple(jnp.where(at[:, None, :, None],
                           new.astype(ring.dtype)[:, :, None, :], ring)
                 for ring, new in zip(rings, rows))


@register(
    "_contrib_KVRingAttention",
    attrs={"scale": AttrSpec("float", default=-1.0),
           "sink": AttrSpec("bool", default=False),
           "value_dim": AttrSpec("int", default=0)},
    input_names=lambda attrs: ("query", "ring_k", "ring_v", "pos_idx",
                               "write_slot") + (
        ("sink",) if attrs.get("sink") else ()),
    aliases=("KVRingAttention",),
)
def _kv_ring_attention(attrs, query, ring_k, ring_v, pos_idx, write_slot,
                       sink=None):
    """The read of a window layer's rings in a decode step: row r of
    ``query`` (R, H, dk) attends ITS OWN ``ring_k`` (R, Hkv, W, dk) /
    ``ring_v`` (R, Hkv, W, dv), written by ``KVRingWrite`` in the same step,
    so the ring holds the row's last W positions, itself included. Keys are
    cached rotated and attention over slots is order-agnostic, so the read
    needs no positions, only which slots hold a position of THIS sequence:
    the first ``pos + 1`` while the ring is filling (what a lane's last
    occupant left past them is masked), all W from then on, none where
    ``write_slot`` is negative. ``sink=True`` takes ``sink`` (H,), one logit
    a query head in the softmax's denominator (``MultiHeadAttention`` says
    how). Contractions, accumulator, softmax and grouped heads as
    ``KVPoolAttention``'s; the output is (R, H, dv). ``value_dim`` > 0 takes
    the value from the first ``value_dim`` columns of ``ring_v``, which may
    then BE ``ring_k`` (a window over LATENTS: one ring a layer whose row
    [c | k_r] is the key whole and the value in its first columns), cut
    after the contraction as ``KVPoolAttention`` cuts it."""
    scale = attrs["scale"] if attrs["scale"] > 0 \
        else 1.0 / np.sqrt(query.shape[-1])
    r, h, dk = query.shape
    hkv, window = ring_k.shape[1], ring_k.shape[2]
    g = _kv_groups(h, hkv, "KVRingAttention")
    live = jnp.arange(window, dtype=jnp.int32)[None, :] \
        < _context_slots(pos_idx, write_slot)[:, None]
    s = jnp.einsum("rkgd,rkwd->rkgw", query.reshape(r, hkv, g, dk), ring_k,
                   preferred_element_type=jnp.float32) * scale \
        + jnp.where(live, jnp.float32(0), _NEG)[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1) if sink is None else _sink_softmax(
        s, sink.astype(jnp.float32).reshape(1, hkv, g, 1))
    out = jnp.einsum("rkgw,rkwd->rkgd", p, ring_v,
                     preferred_element_type=jnp.float32)
    if attrs.get("value_dim", 0) > 0:
        out = out[..., :attrs["value_dim"]]
    return out.reshape(r, h, out.shape[-1]).astype(query.dtype)


@register(
    "_contrib_SparseIndexSelect",
    attrs={"topk": AttrSpec("int", required=True),
           "page_size": AttrSpec("int", default=0)},
    input_names=lambda attrs: ("index_query", "index_weight") + (
        ("pool", "page_table", "pos_idx", "write_slot", "kept")
        if attrs.get("page_size", 0) > 0 else ("index_key", "length")),
    aliases=("SparseIndexSelect",),
)
def _sparse_index_select(attrs, index_query, index_weight, keys, *more):
    """The selection of learned sparse attention for ONE query a row: row
    r's ``index_query`` (R, Hi, di) and ``index_weight`` (R, Hi) score the
    index keys of the row's own context (``index_scores``), and the
    positions of the ``topk`` largest come back, (R, topk) float32 in no
    order, -1 where the context holds fewer: all of it, then.

    ``page_size`` > 0, a decode step: ``pool`` is the layer's pool of index
    keys (one head, either layout), ``page_table`` (R, max_pages),
    ``pos_idx`` and ``write_slot`` (R, 1) as ``KVPoolAttention`` takes
    them; a row's keys are the slots of its own pages in order (slot u of
    them IS position u), the first ``pos + 1`` live; a row whose write slot
    is negative (a lane that rides along) selects nothing and gets back
    ``kept`` (R, topk), what it was handed. Otherwise, a prefill's last row:
    ``index_key`` (R, T, di) and ``length`` (R, 1), the first ``length``
    keys live."""
    topk, page = attrs["topk"], attrs.get("page_size", 0)
    kept = None
    if page > 0:
        table, pos_idx, write_slot, kept = more
        keys = _own_pages(keys, table.astype(jnp.int32), page, 1)[0]
        keys = keys.reshape(table.shape[0], table.shape[1] * page, -1)
        count = _context_slots(pos_idx, write_slot)
    else:
        count = more[0].reshape(-1).astype(jnp.int32)
    live = jnp.arange(keys.shape[1], dtype=jnp.int32)[None, :] \
        < count[:, None]
    score = jnp.where(live, index_scores(
        index_query[:, None], index_weight[:, None], keys)[:, 0], -jnp.inf)
    k = min(topk, keys.shape[1])
    best, at = jax.lax.top_k(score, k)
    at = jnp.pad(jnp.where(best > -jnp.inf, at, -1).astype(jnp.float32),
                 ((0, 0), (0, topk - k)), constant_values=-1.0)
    return at if kept is None else jnp.where(count[:, None] > 0, at, kept)
