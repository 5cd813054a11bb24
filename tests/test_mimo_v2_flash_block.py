"""The MiMo-V2-Flash block (``arch="mimo_v2_flash"`` of models/transformer.py
and serving.PagedKVDecoder: window layers whose K and V ride in per-lane
rings beside the paged pools of the full layers, a sink in the window
softmax, keys wider than values, partial rotary with a base a kind, expert
layers that hold a share of the experts they route over) against the
benchmark's plain reference, benchmark/reference/mimo_v2_flash_decoder.py, on
seeded weights at small sizes: the published pattern's first seven layers
(full, 4 x window, full, window; the first dense, six of experts), 4 query
heads over 1 (full) and 2 (window) key/value heads, keys of 12 over values of
8, a window of 8, 32 experts of which experts 8..15 are held, 4 a token.
Every tolerance says where it comes from.
"""
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import transformer as tf
from mxnet_tpu.ops import attention, moe
from mxnet_tpu.serving import PagedKVDecoder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reference():
    """A fresh copy of the reference module: a test may bend one of its
    functions without any other test seeing it."""
    path = os.path.join(ROOT, "benchmark", "reference",
                        "mimo_v2_flash_decoder.py")
    spec = importlib.util.spec_from_file_location("mimo_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = reference()

W = 8
# vocabulary above 256 on purpose: bfloat16 holds whole numbers to 256 only
CFG = dict(arch="mimo_v2_flash", vocab_size=600, num_layers=7, num_heads=4,
           num_kv_heads=1, swa_num_kv_heads=2, head_dim=12, v_head_dim=8,
           model_dim=48, ffn_dim=64, moe_ffn_dim=16, num_experts=32,
           num_experts_per_tok=4, num_local_experts=8, local_expert_offset=8,
           hybrid_layer_pattern=[0, 1, 1, 1, 1, 0, 1],
           moe_layer_freq=[0, 1, 1, 1, 1, 1, 1], sliding_window=W,
           rotary_dim=4, rope_theta=5e6, swa_rope_theta=1e4,
           attention_value_scale=0.707, rms_eps=1e-5,
           routed_scaling_factor=1.0, norm_topk_prob=True)
# a bucket of four windows: the prefill's window layers score a band
SERVE = dict(max_len=64, prefill_len=32, page_size=8, lanes=4)

# float32 on both sides on the CPU: what is left is the order of the sums
# (grouped matmul against a loop over experts, the ring's and the pool's
# contraction and the band's blocks against the full softmax with the sink as
# a column); the runs read 2e-7 to 6e-7
F32_TOL = 1e-4
# bfloat16 weights, activations, pools and rings against the float32
# reference over the same (bfloat16-valued) weights: every stored activation
# is rounded to 8 bits of mantissa, some dozen roundings a layer; seven
# layers read 6e-3 to 2e-2 on a row whose experts are the reference's. It
# holds a sample's LOWER-QUARTILE row, in the manner of the benchmark's check:
# where a token's fourth and fifth biased score lie within the rounding the
# program and the reference choose another expert and that row reads 0.05 to
# 0.2
BF16_TOL = 5e-2
# a ring's keys: one bfloat16 rounding of the key itself, the bfloat16
# residual stream of the dense layer before it, the rotation in float32
BF16_RING_TOL = 3e-2


def _lower_quartile(err):
    return np.sort(err)[-(-len(err) // 4) - 1]


def _weights(dtype="float32", seed=0, cfg=CFG):
    """N(0, 0.1) matrices but q, k and v, N(0, 0.4) (scores of order one, so
    that where a key sits matters), a unit-variance embedding, sinks
    N(0.5, 1) and a selection bias N(0, 0.5): large enough beside those
    scores and sigmoid scores near 0.5 that a dropped sink, and selecting by
    s + b against weighing by s + b, are told apart."""
    rs = np.random.RandomState(seed)
    out = {}
    for name, shape in sorted(tf.param_shapes(**cfg).items()):
        if name.endswith("gamma"):
            v = np.ones(shape, "f")
        elif name.endswith("sink_bias"):
            v = (0.5 + rs.randn(*shape)).astype("f")
        else:
            v = rs.randn(*shape).astype("f") * (
                1.0 if name == "embed_weight"
                else 0.5 if name.endswith("router_bias")
                else 0.4 if name.endswith("qkv_weight") else 0.1)
        out[name] = jnp.asarray(v).astype(dtype)
    return out


def _decoder(params, dtype="float32", cfg=CFG, **kw):
    return PagedKVDecoder({k: mx.nd.NDArray(v) for k, v in params.items()},
                          dtype=dtype, **dict(SERVE, **kw), **cfg)


def _rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def _ring(dec, seq, name="ring_k_1"):
    return np.array(dec.lane_state(seq, (name,))[name]).astype(np.float32)


def _ring_error(ring, keys, upto):
    """A ring (Hkv, W, d) against the reference's keys (Hkv, T, d) at the
    positions it holds once position ``upto`` is written: relative L2 over
    the slots that hold one."""
    held = np.arange(max(0, upto - W + 1), upto + 1)
    got, want = ring[:, held % W], np.asarray(keys)[:, held]
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _admit_and_step(dec, toks, length):
    """Admit ``toks[:length]``, then feed the rest one step each: (the 1 +
    steps logits rows, layer 1's key ring after the admission, the same
    after the last step)."""
    seq, logits = dec.admit(np.asarray(toks[:length], np.float32))
    admitted = _ring(dec, seq)
    got = [np.asarray(logits)]
    for tok in toks[length:]:
        got.append(np.asarray(dec.step({seq: int(tok)})[seq]))
    last = _ring(dec, seq)
    dec.retire(seq)
    return np.stack(got), admitted, last


@pytest.fixture
def tm():
    telemetry.reset()
    saved = telemetry.current_override()
    telemetry.set_mode("trace")
    yield telemetry
    telemetry.set_mode(saved)
    telemetry.reset()


# ------------------------------------------------------------- (a) operators
def _qkv(t, seed=0, hq=4, hkv=2, dk=12, dv=8):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(1, hq, t, dk), jnp.float32),
            jnp.asarray(rs.randn(1, hkv, t, dk), jnp.float32),
            jnp.asarray(rs.randn(1, hkv, t, dv), jnp.float32),
            jnp.asarray(rs.randn(hq), jnp.float32))


def _masked_reference(q, k, v, sink, window):
    """The reference's way: full T x T scores, the window a mask, the sink
    an extra column dropped after the softmax."""
    t, hq = q.shape[2], q.shape[1]
    kk, vv = (jnp.repeat(a[0], hq // a.shape[1], axis=0) for a in (k, v))
    s = jnp.einsum("htd,hsd->hts", q[0], kk) * q.shape[-1] ** -0.5
    seen = jnp.tril(jnp.ones((t, t), bool)) \
        & ~jnp.tril(jnp.ones((t, t), bool), k=-window)
    s = jnp.concatenate([jnp.where(seen, s, -jnp.inf), jnp.broadcast_to(
        sink[:, None, None], (hq, t, 1))], axis=-1)
    return jnp.einsum("hts,hsd->htd", jax.nn.softmax(s, axis=-1)[..., :-1],
                      vv)[None]


@pytest.mark.parametrize("t", [8, 16, 32, 31, 5])
def test_the_blocked_band_prefill_equals_the_masked_one(t):
    """``MultiHeadAttention(window=8, sink=True)`` over buckets of one, two
    and four windows (a band from two on), over a ragged 31 and a short 5
    (the masked full scores): all the reference's masked T x T softmax with
    the sink as a column; the values' width is the output's."""
    q, k, v, sink = _qkv(t)
    got = attention._multi_head_attention(
        dict(causal=True, scale=-1.0, window=W, sink=True), q, k, v, sink)
    assert got.shape == (1, 4, t, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        _masked_reference(q, k, v, sink, W)), rtol=2e-5, atol=2e-6)


def test_the_band_scores_two_windows_a_block_and_never_t_by_t():
    """The banded form is chosen from the shapes: at 32 positions its scores
    are (4 blocks, 8, 16), and no (32, 32) array is made; the dropped sink
    and a window one slot longer both move the result."""
    q, k, v, sink = _qkv(32, seed=1)
    attrs = dict(causal=True, scale=-1.0, window=W, sink=True)
    text = jax.jit(lambda *a: attention._multi_head_attention(
        attrs, *a)).lower(q, k, v, sink).as_text()
    assert "4x8x16" in text and "32x32" not in text
    want = np.asarray(_masked_reference(q, k, v, sink, W))
    no_sink = attention._multi_head_attention(
        dict(attrs, sink=False), q, k, v)
    longer = attention._multi_head_attention(dict(attrs, window=9), q, k, v,
                                             sink)
    for other in (no_sink, longer):
        assert _rel_l2(np.asarray(other).reshape(-1, 8),
                       want.reshape(-1, 8)).max() > 0.05
    with pytest.raises(MXNetError, match="a window needs causal"):
        attention._multi_head_attention(dict(attrs, causal=False), q, k, v,
                                        sink)


def test_rotary_over_the_first_features_of_a_head():
    """``rotary_dim=4`` rotates features 0..3 of 12 as a head of four (pairs
    (0, 2), (1, 3), its own frequencies) and passes the other eight through;
    0 and the head's own width are the whole head; an odd count is refused."""
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(2, 3, 5, 12), jnp.float32)
    pos = jnp.asarray(rs.randint(0, 50, (2, 5)), jnp.float32)
    attrs = dict(base=1e4, interleaved=False)
    got = attention._rotary_embedding(dict(attrs, rotary_dim=4), x, pos)
    assert np.array_equal(np.asarray(got[..., 4:]), np.asarray(x[..., 4:]))
    want = jnp.stack([ref.rope(x[b], pos[b], 1e4, 4) for b in range(2)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6)
    whole = attention._rotary_embedding(dict(attrs, rotary_dim=0), x, pos)
    assert np.array_equal(np.asarray(whole), np.asarray(
        attention._rotary_embedding(dict(attrs, rotary_dim=12), x, pos)))
    assert not np.allclose(np.asarray(whole[..., 4:]), np.asarray(x[..., 4:]))
    with pytest.raises(MXNetError, match="rotary_dim 5 must be even"):
        attention._rotary_embedding(dict(attrs, rotary_dim=5), x, pos)


def test_a_ring_holds_the_last_window_and_masks_what_it_does_not_own():
    """``KVRingWrite`` puts row r at slot ``pos mod W`` of ring r bit for bit
    and leaves a lane that rides along alone; ``KVRingAttention`` reads the
    first ``pos + 1`` slots while the ring fills (garbage past them weighs
    nothing), all of them after, with the sink in the denominator."""
    rs = np.random.RandomState(3)
    ring_k = jnp.asarray(rs.randn(3, 2, W, 12), jnp.float32) * 100  # garbage
    ring_v = jnp.asarray(rs.randn(3, 2, W, 8), jnp.float32) * 100
    k_new = jnp.asarray(rs.randn(3, 2, 12), jnp.float32)
    v_new = jnp.asarray(rs.randn(3, 2, 8), jnp.float32)
    pos = jnp.asarray([[2.0], [13.0], [5.0]])       # 13 mod 8 = 5
    slot = jnp.asarray([[17.0], [40.0], [-1.0]])    # lane 2 rides along
    new_k, new_v = attention._kv_ring_write(
        {"num_rings": 2}, ring_k, k_new, ring_v, v_new, pos, slot)
    assert np.array_equal(np.asarray(new_k[0, :, 2]), np.asarray(k_new[0]))
    assert np.array_equal(np.asarray(new_v[1, :, 5]), np.asarray(v_new[1]))
    assert np.array_equal(np.asarray(new_k[2]), np.asarray(ring_k[2]))
    changed = np.asarray(new_k != ring_k).any(axis=(1, 3))
    assert changed.sum() == 2 and changed[0, 2] and changed[1, 5]
    q = jnp.asarray(rs.randn(3, 4, 12), jnp.float32)
    sink = jnp.asarray(rs.randn(4), jnp.float32)
    got = attention._kv_ring_attention({"scale": -1.0, "sink": True}, q,
                                       new_k, new_v, pos, slot, sink)
    assert got.shape == (3, 4, 8)
    for r, live in ((0, 3), (1, W)):
        kk = jnp.repeat(new_k[r, :, :live], 2, axis=0)
        vv = jnp.repeat(new_v[r, :, :live], 2, axis=0)
        s = jnp.einsum("hd,hwd->hw", q[r], kk) * 12 ** -0.5
        p = jax.nn.softmax(jnp.concatenate([s, sink[:, None]], -1), -1)
        np.testing.assert_allclose(
            np.asarray(got[r]), np.asarray(jnp.einsum(
                "hw,hwd->hd", p[:, :-1], vv)), rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(got[2])).all()    # fully masked: finite


def test_operators_infer_their_shapes_from_the_data():
    """Shape rules: a sink is one logit a query head, a ring's positions and
    slots one a row, a value pool keeps its own width beside a wider key
    pool, and an expert layer's stacks have the held experts' rows."""
    q, k, v, s = (mx.sym.Variable(n) for n in "qkvs")
    att = mx.sym.MultiHeadAttention(q, k, v, s, causal=True, window=8,
                                    sink=True)
    args, outs, _ = att.infer_shape(q=(1, 4, 32, 12), k=(1, 2, 32, 12),
                                    v=(1, 2, 32, 8))
    assert dict(zip(att.list_arguments(), args))["s"] == (4,)
    assert outs == [(1, 4, 32, 8)]
    pos, slot = mx.sym.Variable("pos"), mx.sym.Variable("slot")
    rings = mx.sym.KVRingWrite(mx.sym.Variable("rk"), mx.sym.Variable("nk"),
                               mx.sym.Variable("rv"), mx.sym.Variable("nv"),
                               pos, slot, num_rings=2)
    read = mx.sym.KVRingAttention(q, rings[0], rings[1], pos, slot, s,
                                  sink=True)
    args, outs, _ = read.infer_shape(q=(3, 4, 12), rk=(3, 2, 8, 12),
                                     nk=(3, 2, 12), rv=(3, 2, 8, 8),
                                     nv=(3, 2, 8))
    got = dict(zip(read.list_arguments(), args))
    assert got["pos"] == got["slot"] == (3, 1) and got["s"] == (4,)
    assert outs == [(3, 4, 8)]
    pool = mx.sym.KVPoolAttention(q, mx.sym.Variable("pk"),
                                  mx.sym.Variable("pv"), mx.sym.Variable("m"))
    args, outs, _ = pool.infer_shape(q=(3, 4, 12), pk=(1, 64, 12),
                                     pv=(1, 64, 8))
    assert dict(zip(pool.list_arguments(), args))["m"] == (3, 64)
    assert outs == [(3, 4, 8)]
    names = ("x", "r", "g", "u", "d", "b")
    ffn = mx.sym.MoEFeedForward(
        *(mx.sym.Variable(n) for n in names), num_experts=32, num_hidden=16,
        num_experts_per_tok=4, scoring="sigmoid", router_bias=True,
        num_local_experts=8, local_expert_offset=8)
    args, outs, _ = ffn.infer_shape(x=(5, 48))
    assert dict(zip(names, args)) == {
        "x": (5, 48), "r": (32, 48), "g": (8, 48, 16), "u": (8, 48, 16),
        "d": (8, 16, 48), "b": (32,)}
    assert outs == [(5, 48), (32,)]


# ------------------------------------------------------ (b) the expert share
def _expert_layer(seed=0, n=40, d=48, f=16, e=32):
    rs = np.random.RandomState(seed)
    g = lambda *shape: jnp.asarray(rs.randn(*shape).astype("f") * 0.2)
    return (g(n, d) * 5, g(e, d), g(e, d, f), g(e, d, f), g(e, f, d),
            jnp.asarray(rs.randn(e).astype("f") * 0.5))


def test_the_shares_add_up():
    """32 experts in 4 shares of 8: each share routes over all 32, picks and
    renormalises over the 4 chosen wherever they live, and sums its own
    experts' products; the four partial results add up to the uncut layer's
    (the order of a float32 sum apart) and every share counts the same load
    over all 32. No share's output is the whole's."""
    x, router, gate, up, down, bias = _expert_layer()
    attrs = dict(num_experts=32, num_hidden=16, num_experts_per_tok=4,
                 scoring="sigmoid", router_bias=True, norm_topk_prob=True,
                 routed_scaling_factor=1.0)
    whole, load = moe._moe_feed_forward(attrs, x, router, gate, up, down,
                                        bias)
    assert float(load.sum()) == 40 * 4
    parts = []
    for first in range(0, 32, 8):
        held = slice(first, first + 8)
        part, part_load = moe._moe_feed_forward(
            dict(attrs, num_local_experts=8, local_expert_offset=first), x,
            router, gate[held], up[held], down[held], bias)
        assert np.array_equal(np.asarray(part_load), np.asarray(load))
        assert np.isfinite(np.asarray(part)).all()
        parts.append(np.asarray(part, np.float64))
    whole = np.asarray(whole, np.float64)
    assert np.abs(sum(parts) - whole).max() < 1e-5 * np.abs(whole).max()
    assert all(_rel_l2(p, whole).max() > 0.1 for p in parts)
    # a share is the reference's held part, expert by expert
    want = ref.moe(x, router, bias, gate[8:16], up[8:16], down[8:16], 4, 1.0,
                   8)
    np.testing.assert_allclose(parts[1], np.asarray(want), rtol=1e-4,
                               atol=1e-5)


def test_a_token_none_of_whose_experts_is_held_gets_nothing():
    """The selection bias sends every token to experts 0..3: a share that
    holds experts 8..15 adds exactly zero (rows past the grouped matmul's
    groups are dropped, whatever they read), the share of 0..7 the whole."""
    x, router, gate, up, down, bias = _expert_layer(seed=1)
    bias = bias.at[:4].set(50.0)
    attrs = dict(num_experts=32, num_hidden=16, num_experts_per_tok=4,
                 scoring="sigmoid", router_bias=True, norm_topk_prob=True)
    share = lambda first: moe._moe_feed_forward(
        dict(attrs, num_local_experts=8, local_expert_offset=first), x,
        router, gate[first:first + 8], up[first:first + 8],
        down[first:first + 8], bias)[0]
    whole = moe._moe_feed_forward(attrs, x, router, gate, up, down, bias)[0]
    assert not np.asarray(share(8)).any()
    np.testing.assert_allclose(np.asarray(share(0)), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)


def test_the_default_holds_every_expert_and_a_wrong_share_is_refused():
    """``num_local_experts`` 0, and a share that is all of them, are the old
    layer bit for bit; stacks that do not match the share are refused."""
    x, router, gate, up, down, bias = _expert_layer(seed=2)
    attrs = dict(num_experts=32, num_hidden=16, num_experts_per_tok=4,
                 scoring="sigmoid", router_bias=True, norm_topk_prob=True)
    whole = moe._moe_feed_forward(attrs, x, router, gate, up, down, bias)
    for same in (dict(attrs, num_local_experts=0),
                 dict(attrs, num_local_experts=32)):
        again = moe._moe_feed_forward(same, x, router, gate, up, down, bias)
        assert all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(whole, again))
    for bad in (dict(num_local_experts=8),                  # stacks of 32
                dict(num_local_experts=32, local_expert_offset=8)):
        with pytest.raises(MXNetError, match="of 32 held, stacks of 32"):
            moe._moe_feed_forward(dict(attrs, **bad), x, router, gate, up,
                                  down, bias)


# ------------------------------------------ (c) prefill, then decode: the cache
@pytest.mark.parametrize("length", [5, 8, 20, 32])
def test_admit_then_steps_agree_with_the_full_forward(length):
    """The logits ``admit`` returns and those of 30 single decode steps through
    the cache (pages of the two full layers, rings of the five window layers)
    against the reference's full forward over the whole sequence, row by row:
    prompts shorter than, as long as and longer than the window of 8 and the
    whole bucket, the steps fed DRAWN tokens and running across the ring's
    wrap more than three times. The first window layer's key ring after the
    admission and after the last step holds the reference's rotated keys of
    the last 8 positions (fewer while it fills), each at its position mod 8."""
    params = _weights()
    dec = _decoder(params)
    toks = np.random.RandomState(length).randint(1, CFG["vocab_size"],
                                                 length + 30)
    got, admitted, last = _admit_and_step(dec, toks, length)
    want = np.asarray(ref.logits(params, jnp.asarray(toks), CFG, last=31))
    assert got.dtype == np.float32 and got.shape == (31, 600)
    assert _rel_l2(got, want).max() < F32_TOL
    keys = ref.first_window_keys(params, jnp.asarray(toks), CFG)
    assert admitted.shape == last.shape == (2, W, 12) == keys.shape[:1] + (
        W, 12)
    assert _ring_error(admitted, keys, length - 1) < 1e-5
    assert _ring_error(last, keys, len(toks) - 1) < 1e-5


def _faulty(fault):
    """(reference module, its configuration, what to do to the weights) with
    one part of the layer equations wrong."""
    bad, cfg, bend = reference(), dict(CFG), lambda p: p
    if fault == "sink_dropped":
        bend = lambda p: {k: jnp.full_like(v, -1e9)
                          if k.endswith("sink_bias") else v
                          for k, v in p.items()}
    elif fault == "window_one_slot_too_long":
        cfg["sliding_window"] = W + 1
    elif fault == "values_not_scaled":
        cfg["attention_value_scale"] = 1.0
    elif fault == "whole_head_rotated":
        cfg["rotary_dim"] = 12
    elif fault == "full_base_in_window_layers":
        cfg["swa_rope_theta"] = cfg["rope_theta"]
    elif fault == "window_base_in_full_layers":
        cfg["rope_theta"] = cfg["swa_rope_theta"]
    elif fault == "weights_from_biased_scores":
        def route(h, router, bias, top_k, scaling):
            s = jax.nn.sigmoid(h @ router.astype(jnp.float32).T) \
                + bias.astype(jnp.float32)
            w, chosen = jax.lax.top_k(s, top_k)
            return scaling * w / (jnp.sum(w, axis=-1, keepdims=True)
                                  + 1e-20), chosen
        bad.route = route
    elif fault == "another_share":
        cfg["local_expert_offset"] = 0
    else:
        raise AssertionError(fault)
    return bad, cfg, bend


@pytest.mark.parametrize("fault", [
    "sink_dropped", "window_one_slot_too_long", "values_not_scaled",
    "whole_head_rotated", "full_base_in_window_layers",
    "window_base_in_full_layers", "weights_from_biased_scores",
    "another_share"])
def test_a_reference_with_one_part_wrong_disagrees(fault):
    """Each mechanism the block adds is seen by the comparison: against a
    reference without the sink, with a window of 9, with unscaled values,
    with the whole head rotated, with one rotary base for both kinds of
    layer, with weights taken from score + bias, or with the share of experts
    0..7, EVERY row of the sample reads above 30 times the sound limit (the
    weights from score + bias the least, 8e-3: a quarter of a token's
    experts is held here)."""
    params = _weights()
    dec = _decoder(params)
    toks = np.random.RandomState(11).randint(1, CFG["vocab_size"], 20 + 12)
    got, _, _ = _admit_and_step(dec, toks, 20)
    bad, cfg, bend = _faulty(fault)
    want = np.asarray(bad.logits(bend(params), jnp.asarray(toks), cfg,
                                 last=13))
    assert _rel_l2(got, want).min() > 30 * F32_TOL


def test_bfloat16_weights_pools_and_rings():
    """The chip's types on the CPU: bfloat16 weights, pools and rings,
    float32 ids and positions. A sample's lower-quartile row and the ring's
    keys stay within storage rounding of the float32 reference."""
    params = _weights("bfloat16")
    dec = _decoder(params, "bfloat16")
    toks = np.random.RandomState(5).randint(1, CFG["vocab_size"], 20 + 12)
    got, admitted, last = _admit_and_step(dec, toks, 20)
    want = np.asarray(ref.logits(params, jnp.asarray(toks), CFG, last=13))
    assert got.dtype == np.float32
    assert _lower_quartile(_rel_l2(got, want)) < BF16_TOL
    keys = ref.first_window_keys(params, jnp.asarray(toks), CFG)
    assert _ring_error(admitted, keys, 19) < BF16_RING_TOL
    assert _ring_error(last, keys, len(toks) - 1) < BF16_RING_TOL
    types = {name: str(dec._dec_exe.arg_dict[name].dtype)
             for name, _, _ in dec._cache}
    assert set(types.values()) == {"bfloat16"}


def test_two_kinds_of_cache_side_by_side():
    """``decode_cache`` names pools for the full layers (one key/value head,
    a key of 12 over a value of 8) and rings for the window layers (two
    heads), in layer order; a ring is 8 slots a lane WHATEVER ``max_len`` is,
    and an admission takes page frames for the pools alone."""
    cache = tf.decode_cache(**CFG)
    assert [(n, k) for n, k, _ in cache[:6]] == [
        ("kv_k_0", "pool"), ("kv_v_0", "pool"), ("ring_k_1", "ring"),
        ("ring_v_1", "ring"), ("ring_k_2", "ring"), ("ring_v_2", "ring")]
    shapes = {n: s for n, _, s in cache}
    assert shapes["kv_k_5"] == (1, 12) and shapes["kv_v_5"] == (1, 8)
    assert shapes["ring_k_6"] == (2, W, 12) and shapes["ring_v_6"] == (2, W, 8)
    assert len(cache) == 14
    params = _weights()
    for max_len in (64, 256):
        dec = _decoder(params, max_len=max_len).warmup()
        bufs = {n: dec._dec_exe.arg_dict[n].shape for n, _, _ in cache}
        assert bufs["ring_k_1"] == (4, 2, W, 12)
        assert bufs["ring_v_4"] == (4, 2, W, 8)
        assert bufs["kv_k_0"] == (1, 4 * max_len, 12)
        assert bufs["kv_v_5"] == (1, 4 * max_len, 8)
        assert dec._decode_shapes()["step_in"] == (4, 3 + max_len // 8)
        assert dec._ring_names == [n for n, k, _ in cache if k == "ring"]
        assert dec._pool_names == ["kv_k_0", "kv_v_0", "kv_k_5", "kv_v_5"]
        seq, _ = dec.admit(np.arange(1, 21, dtype=np.float32))
        assert dec.pool.in_use == 3        # 20 tokens in pages of 8
        for tok in range(5):
            dec.step({seq: tok + 1})
        assert dec.pool.in_use == 4        # position 24 opened a page
        dec.retire(seq)
        assert dec.pool.in_use == 0


def test_multiplexed_lanes_equal_sequential_decoding():
    """Three sequences of different lengths stepped together, one of them
    admitted while the others are mid-way, give row for row what each gives
    alone in a fresh decoder: a lane's ring and pages are its own."""
    params = _weights()
    rs = np.random.RandomState(7)
    seqs = [rs.randint(1, 600, n + 14) for n in (3, 11, 26)]
    lens = (3, 11, 26)
    alone = [_admit_and_step(_decoder(params), toks, n)[0]
             for toks, n in zip(seqs, lens)]
    dec = _decoder(params)
    ids, got = {}, {i: [] for i in range(3)}
    for i in (0, 1):
        ids[i], row = dec.admit(seqs[i][:lens[i]].astype(np.float32))
        got[i].append(np.asarray(row))
    for step in range(14):
        if step == 4:       # a late arrival, into the third lane
            ids[2], row = dec.admit(seqs[2][:lens[2]].astype(np.float32))
            got[2].append(np.asarray(row))
        feed = {ids[i]: int(seqs[i][lens[i] + len(got[i]) - 1])
                for i in ids if len(got[i]) <= 14}
        rows = dec.step(feed)
        for i in ids:
            if ids[i] in rows:
                got[i].append(np.asarray(rows[ids[i]]))
    for _ in range(4):      # the late one catches up alone
        rows = dec.step({ids[2]: int(seqs[2][lens[2] + len(got[2]) - 1])})
        got[2].append(np.asarray(rows[ids[2]]))
    for i in range(3):
        assert len(got[i]) == 15
        np.testing.assert_allclose(np.stack(got[i]), alone[i], rtol=1e-5,
                                   atol=1e-5)


def test_a_readmitted_lane_never_reads_its_predecessors_ring():
    """Lane 0 serves a long sequence that fills every ring, retires, and is
    given a prompt of 3 tokens: slots 3..7 of its rings still hold the
    predecessor's keys (asserted), and every row of the newcomer is the
    reference's and a fresh decoder's, while its ring fills and after."""
    params = _weights()
    dec = _decoder(params)
    rs = np.random.RandomState(9)
    first = rs.randint(1, 600, 30)
    seq, _ = dec.admit(first[:25].astype(np.float32))
    for tok in first[25:]:
        dec.step({seq: int(tok)})
    old = _ring(dec, seq)
    dec.retire(seq)
    toks = rs.randint(1, 600, 3 + 9)
    seq, logits = dec.admit(toks[:3].astype(np.float32))
    assert dec._seq_lane[seq] == 0
    ring = _ring(dec, seq)
    keys = ref.first_window_keys(params, jnp.asarray(toks), CFG)
    assert _ring_error(ring, keys, 2) < 1e-5
    assert np.abs(ring[:, 3:]).max() > 0    # what a careless read would see
    got = [np.asarray(logits)]
    for tok in toks[3:]:
        got.append(np.asarray(dec.step({seq: int(tok)})[seq]))
    want = np.asarray(ref.logits(params, jnp.asarray(toks), CFG, last=10))
    assert _rel_l2(np.stack(got), want).max() < F32_TOL
    fresh = _admit_and_step(_decoder(params), toks, 3)[0]
    np.testing.assert_allclose(np.stack(got), fresh, rtol=1e-5, atol=1e-5)
    assert not np.array_equal(old, _ring(dec, seq))


def test_what_a_ring_cannot_do_is_refused():
    """``fork``, ``rollback``, the prefix cache, the chunk, verify and
    megastep programs refuse the arch as they refuse rows; admit, step and
    retire are the same entry points as every other block's."""
    params = _weights()
    with pytest.raises(MXNetError, match="not built for arch "
                                         "'mimo_v2_flash' yet"):
        _decoder(params, prefix_cache=True)
    dec = _decoder(params)
    seq, logits = dec.admit(np.asarray([5, 6, 7], np.float32))
    for call in (lambda: dec.fork(seq), lambda: dec.rollback(seq, 1)):
        with pytest.raises(MXNetError, match="a window's ring cannot be "
                                             "shared or rolled back"):
            call()
    for call in (lambda: dec.verify_chunk(seq, [1, 2]),
                 lambda: dec.step_megastep({seq: 1}, k=2),
                 lambda: dec._chunk_for(4)):
        with pytest.raises(MXNetError, match="not built for arch "
                                             "'mimo_v2_flash' yet"):
            call()
    row = dec.step({seq: int(np.argmax(logits))})[seq]
    assert row.shape == (600,) and dec.position(seq) == 4
    dec.retire(seq)
    assert dec.stats()["active"] == 0 and dec.stats()["pages_in_use"] == 0
    assert dec._pf_cache._model_key.endswith("-mimo_v2_flash-prefill")
    with pytest.raises(MXNetError, match="hybrid_layer_pattern must give 7"):
        tf.param_shapes(**dict(CFG, hybrid_layer_pattern=[0, 1]))


def test_spans_gauges_and_counters(tm):
    """What the tracing sees of the two kinds of cache and of the share: the
    gauges set at warm-up, the ring's hand-over under ``serving.admit.state``
    inside ``serving.admit.scatter``, and a step's counters: the live window
    slots beside the context, the assignments that reached a held expert
    beside all of them, the held experts touched."""
    params = _weights()
    dec = _decoder(params).warmup()
    pools = 2 * 4 * 64 * (12 + 8) * 4       # two layers x slots x (k + v)
    rings = 5 * 4 * 2 * W * (12 + 8) * 4    # five layers x lanes x heads
    assert tm.gauge("serving.full_pool_bytes").value == pools
    assert tm.gauge("serving.window_ring_bytes").value == rings
    assert tm.gauge("serving.cache_bytes").value == pools + rings \
        == tm.gauge("serving.decode_aliased_bytes").value
    tm.clear_events()
    before = tm.counters()
    a, _ = dec.admit(np.arange(1, 21, dtype=np.float32))     # 20 > window
    b, _ = dec.admit(np.asarray([7, 8, 9], np.float32))      # 3 < window
    dec.step({a: 4, b: 5})
    after = tm.counters()
    moved = {k: v - before.get(k, 0) for k, v in after.items()}
    assert moved["serving.step_context_tokens"] == 21 + 4
    assert moved["serving.step_window_slots"] == W + 4
    # every lane passes through the experts, those that ride along too
    assert moved["serving.moe.step_assignments"] == 6 * 4 * 4
    local = moved["serving.moe.step_local_assignments"]
    assert 0 < local < moved["serving.moe.step_assignments"]
    assert 0 < moved["serving.moe.step_experts_touched"] <= min(local, 6 * 8)
    assert moved["serving.step_slot_writes"] == 2 * 4
    spans = {attrs["id"]: (name, attrs.get("parent"), attrs)
             for name, _t0, _dur, _tid, attrs in tm.drain_events()
             if "id" in attrs}
    states = [v for v in spans.values() if v[0] == "serving.admit.state"]
    assert len(states) == 2 and states[0][2]["buffers"] == 10
    for _, parent, _ in states:
        chain = []
        while parent in spans:
            chain.append(spans[parent][0])
            parent = spans[parent][1]
        assert chain == ["serving.admit.scatter", "serving.paged_admit"]


def test_an_admission_that_moves_its_held_rows_alone_counts_them(
        tm, monkeypatch):
    """The rule held to these sizes (8 of 32 experts held, chunks of whole
    16-row tiles): the prefill's six expert layers gather, multiply and
    combine their held rows alone, the admission's logits are the all-rows
    decoder's to a float32 sum's order, and the counters say what ran: the
    assignments that reached a held expert (the prefill's own ``moe_load``
    over experts 8..15), the six layers, and those among them whose held
    rows outgrew one chunk."""
    monkeypatch.setattr(moe, "_ROW_TILE", 16)
    monkeypatch.setattr(moe, "_ROWS_WORTH_A_CHUNK", 16)
    params = _weights()
    prompt = np.arange(1, 21, dtype=np.float32)
    with mx.name.NameManager():
        dec = _decoder(params)
    bucket = dec.prefill_len
    chunk = moe.held_rows_chunk(bucket, 4, 8, 32)
    assert dec._admit_chunk == chunk > 0 and chunk % 16 == 0
    before = tm.counters()
    _, logits = dec.admit(prompt)
    moved = {k: v - before.get(k, 0) for k, v in tm.counters().items()}
    pf = dec._pf_cache.executable(dec._prefill_shapes())
    load = np.asarray(pf.outputs[dec._pf_moe_load]._jax())
    assert load.shape == (6, 32) and load.sum() == 6 * bucket * 4
    held = load[:, 8:16].sum(axis=1)
    assert moved["serving.moe.assignments"] == load.sum()
    assert moved["serving.moe.admit_local_assignments"] == held.sum() > 0
    assert moved["serving.moe.admit_compact_layers"] == 6
    assert moved.get("serving.moe.admit_overflow_layers", 0) \
        == np.count_nonzero(held > chunk)
    # the same weights through a decoder whose layers keep every row
    monkeypatch.setattr(moe, "_ROWS_WORTH_A_CHUNK", 1 << 40)
    with mx.name.NameManager():
        plain = _decoder(params)
    assert plain._admit_chunk == 0
    before = tm.counters()
    _, want = plain.admit(prompt)
    moved = {k: v - before.get(k, 0) for k, v in tm.counters().items()}
    assert moved["serving.moe.admit_local_assignments"] == held.sum()
    assert "serving.moe.admit_compact_layers" not in moved \
        or moved["serving.moe.admit_compact_layers"] == 0
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
