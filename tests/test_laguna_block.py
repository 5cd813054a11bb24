"""The Laguna block (``arch="laguna"`` of models/transformer.py and
serving.PagedKVDecoder: window layers of more query heads than the full
layers beside them, all over the same key/value heads; a q/k norm and a gate
a head; YaRN on the full layers' partial rotary alone; routed experts of which
a share is held beside a shared one) against the benchmark's plain reference,
benchmark/reference/laguna_decoder.py, on seeded weights at small sizes that
keep what is odd about the model: the published pattern's first six layers
(full, 3 x window, full, window; the first dense, five of experts), 4 (full)
and 6 (window) query heads over 2 key/value heads of 16 (groups of 2 and 3),
the first 8 features of a full layer's head rotated under a YaRN whose
``low`` = 1 and ``high`` = 3 fall INSIDE the four frequencies (two plain, one
blended, one interpolated), a window of 8 under a bucket of 32, 32 experts of
which experts 8..15 are held, 4 a token, x 2.5. Every tolerance says where it
comes from.
"""
import functools
import hashlib
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
    path = os.path.join(ROOT, "benchmark", "reference", "laguna_decoder.py")
    spec = importlib.util.spec_from_file_location("laguna_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = reference()

W = 8
FULL, WINDOW = "full_attention", "sliding_attention"
# vocabulary above 256 on purpose: bfloat16 holds whole numbers to 256 only
CFG = dict(arch="laguna", vocab_size=600, num_layers=6, num_heads=4,
           swa_num_heads=6, num_kv_heads=2, head_dim=16, model_dim=48,
           ffn_dim=64, moe_ffn_dim=16, num_experts=32, num_experts_per_tok=4,
           num_local_experts=8, local_expert_offset=8, num_shared_experts=1,
           first_dense_layers=1,
           layer_types=[FULL, WINDOW, WINDOW, WINDOW, FULL, WINDOW],
           sliding_window=W, rotary_dim=8, rope_theta=100.0,
           swa_rope_theta=1e4, yarn_factor=8.0,
           yarn_original_max_position=128, yarn_beta_fast=4.0,
           yarn_beta_slow=1.0, attention_factor=1.2079441541679836,
           rms_eps=1e-6, routed_scaling_factor=2.5, norm_topk_prob=True)
# a bucket of four windows: the prefill's window layers score a band
SERVE = dict(max_len=64, prefill_len=32, page_size=8, lanes=4)

# float32 on both sides on the CPU: what is left is the order of the sums
# (grouped matmul against a loop over experts, the ring's and the pool's
# contraction and the band's blocks against the full softmax); the runs read
# 4e-7 to 6e-7
F32_TOL = 1e-4
# bfloat16 weights, activations, pools and rings against the float32
# reference over the same (bfloat16-valued) weights: every stored activation
# is rounded to 8 bits of mantissa, some dozen roundings a layer. It holds a
# sample's LOWER-QUARTILE row, in the manner of the benchmark's check: where a
# token's fourth and fifth biased score lie within the rounding the program
# and the reference choose another expert and that row reads 0.05 to 0.2
BF16_TOL = 5e-2
# a ring's keys: one bfloat16 rounding of the key itself, the bfloat16
# residual stream of the dense layer before it, norm and rotation in float32
BF16_RING_TOL = 3e-2


def _lower_quartile(err):
    return np.sort(err)[-(-len(err) // 4) - 1]


def _weights(dtype="float32", seed=0, cfg=CFG):
    """N(0, 0.1) matrices but q, k and v, N(0, 0.4), a unit-variance
    embedding, gammas of the q/k norms drawn around 1 (so that a norm that
    forgets its gamma is seen) and a selection bias N(0, 0.5): large enough
    beside sigmoid scores near 0.5 that selecting by s + b and weighing by
    s + b are told apart."""
    rs = np.random.RandomState(seed)
    out = {}
    for name, shape in sorted(tf.param_shapes(**cfg).items()):
        if name.endswith(("qnorm_gamma", "knorm_gamma")):
            v = (1.0 + 0.3 * rs.randn(*shape)).astype("f")
        elif name.endswith("gamma"):
            v = np.ones(shape, "f")
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


@functools.lru_cache(maxsize=None)
def _shared_decoder():
    """ONE float32 decoder for the tests that only admit, step and retire
    (a build compiles two programs: 8 s here): a retired lane is clean for
    its next occupant, which ``test_a_readmitted_lane_...`` holds."""
    return _decoder(_weights())


@functools.lru_cache(maxsize=None)
def _sample():
    """The program's side of the fault tests, made once: (tokens, the logits
    of admit at 20 tokens and 12 steps)."""
    toks = np.random.RandomState(11).randint(1, CFG["vocab_size"], 20 + 12)
    return toks, _admit_and_step(_shared_decoder(), toks, 20)[0]


@pytest.fixture
def tm():
    telemetry.reset()
    saved = telemetry.current_override()
    telemetry.set_mode("trace")
    yield telemetry
    telemetry.set_mode(saved)
    telemetry.reset()


# ------------------------------------------------------------- (a) operators
PUBLISHED = dict(rotary_dim=64, rope_theta=500000.0, yarn_factor=128.0,
                 yarn_original_max_position=8192, yarn_beta_fast=32.0,
                 yarn_beta_slow=1.0)


def test_yarn_at_the_published_numbers():
    """Laguna-S-2.1's full layers: over 64 rotated features, base 500,000,
    factor 128, original 8,192, beta 32 and 1, the correction indices are
    9.04 and 17.49, so ``low`` 9 and ``high`` 18: frequency 0 and 9 are the
    plain ones, 13 a blend (ramp 4/9), 18 and 31 divided by 128. The values
    are written out (Python floats, by the formula of the config's
    ``rope_type: yarn``); the operator's table and the reference's agree with
    them, and ``attention_factor`` is 0.1 ln 128 + 1."""
    want = {0: 1.0, 9: 0.024955408670558694, 13: 0.0027053709606281347,
            17: 0.000110792054139413, 18: 4.865409546207781e-06,
            31: 2.3545766813587275e-08}
    got = attention.yarn_inv_freq(64, 500000.0, 128.0, 8192, 32.0, 1.0)
    theirs = ref.yarn_inv_freq(PUBLISHED)
    assert got.shape == (32,) and len(theirs) == 32
    for i, value in want.items():
        assert abs(got[i] / value - 1) < 1e-12
        assert abs(theirs[i] / value - 1) < 1e-12
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    assert np.allclose(got[:10], plain[:10], rtol=1e-13)
    assert np.allclose(got[18:], plain[18:] / 128, rtol=1e-13)
    assert ((got[10:18] < plain[10:18]) & (got[10:18] > plain[10:18] / 128)
            ).all()
    assert abs(0.1 * np.log(128.0) + 1 - 1.4852030263919618) < 1e-15


def test_the_tiny_yarn_has_all_three_regimes():
    """The tiny sizes' four frequencies: two plain, one blended by half, one
    divided by the factor."""
    got = attention.yarn_inv_freq(8, 100.0, 8.0, 128, 4.0, 1.0)
    plain = 100.0 ** (-np.arange(0, 8, 2) / 8)
    np.testing.assert_allclose(got, plain * [1, 1, (1 + 1 / 8) / 2, 1 / 8],
                               rtol=1e-12)
    np.testing.assert_allclose(got, ref.yarn_inv_freq(CFG), rtol=1e-12)


def _rotary_before_yarn(attrs, data, positions):
    """``RotaryEmbedding`` as it stood before it learned YaRN, copied from
    the parent commit."""
    part = attrs.get("rotary_dim", 0)
    if part and part != data.shape[-1]:
        turned = _rotary_before_yarn(dict(attrs, rotary_dim=0),
                                     data[..., :part], positions)
        return jnp.concatenate([turned, data[..., part:]], axis=-1)
    dh = data.shape[-1]
    inv_freq = attrs["base"] ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = positions.astype(jnp.float32)[:, None, :, None] * inv_freq
    if attrs.get("interleaved"):
        x = data.astype(jnp.float32).reshape(data.shape[:-1] + (dh // 2, 2))
        x1, x2 = x[..., 0], x[..., 1]
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        y = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return y.reshape(data.shape).astype(data.dtype)
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], axis=-1)
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], axis=-1)
    x = data.astype(jnp.float32)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    y = x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
    return y.astype(data.dtype)


@pytest.mark.parametrize("attrs", [
    dict(base=1e4), dict(base=5e6, rotary_dim=8),
    dict(base=1e4, interleaved=True)], ids=["plain", "partial", "interleaved"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rotary_without_yarn_is_what_it_was(attrs, dtype):
    """Without the YaRN attributes the operator's output is the parent's bit
    for bit and its lowered text the parent's letter for letter (the text is
    part of every prefill program's compile-cache key)."""
    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.randn(2, 3, 5, 16), dtype)
    pos = jnp.asarray(rs.randint(0, 9000, (2, 5)), jnp.float32)
    attrs = dict(dict(interleaved=False, rotary_dim=0), **attrs)
    now = jax.jit(lambda a, b: attention._rotary_embedding(attrs, a, b))
    then = jax.jit(lambda a, b: _rotary_before_yarn(attrs, a, b))
    assert np.array_equal(np.asarray(now(x, pos), np.float32),
                          np.asarray(then(x, pos), np.float32))
    strip = lambda text: "\n".join(
        line.split(" loc(")[0] for line in text.splitlines()
        if not line.startswith("#loc"))
    assert strip(now.lower(x, pos).as_text()) \
        == strip(then.lower(x, pos).as_text())


def test_rotary_under_yarn_is_the_references():
    """``RotaryEmbedding`` with the YaRN attributes over the first 8 of 16
    features: the reference's ``rotary`` of a full layer; the other 8 pass
    through UNSCALED, and dropping ``attention_factor`` or the YaRN numbers
    moves the rotated ones."""
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(2, 3, 5, 16), jnp.float32)
    pos = jnp.asarray(rs.randint(0, 500, (2, 5)), jnp.float32)
    attrs = dict(tf._laguna_sizes(**{k: v for k, v in CFG.items()
                                     if k not in ("arch", "vocab_size")}
                                  )["rotary"], interleaved=False)
    assert attrs["yarn_factor"] == 8.0 and attrs["rotary_dim"] == 8
    got = attention._rotary_embedding(attrs, x, pos)
    want = jnp.stack([ref.rotary(x[b], pos[b], CFG, False) for b in range(2)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    assert np.array_equal(np.asarray(got[..., 8:]), np.asarray(x[..., 8:]))
    for other in (dict(attrs, attention_factor=1.0),
                  dict(attrs, yarn_factor=0.0)):
        bent = attention._rotary_embedding(other, x, pos)
        assert _rel_l2(np.asarray(bent[..., :8]).reshape(-1, 8),
                       np.asarray(want[..., :8]).reshape(-1, 8)).max() > 0.1


def _qkv(t, seed=0, hq=6, hkv=2, d=16):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(1, hq, t, d), jnp.float32),
            jnp.asarray(rs.randn(1, hkv, t, d), jnp.float32),
            jnp.asarray(rs.randn(1, hkv, t, d), jnp.float32))


def _masked_reference(q, k, v, window):
    """The reference's way: full T x T scores, the window a mask."""
    t, hq = q.shape[2], q.shape[1]
    kk, vv = (jnp.repeat(a[0], hq // a.shape[1], axis=0) for a in (k, v))
    s = jnp.einsum("htd,hsd->hts", q[0], kk) * q.shape[-1] ** -0.5
    seen = jnp.tril(jnp.ones((t, t), bool)) \
        & ~jnp.tril(jnp.ones((t, t), bool), k=-window)
    return jnp.einsum("hts,hsd->htd", jax.nn.softmax(
        jnp.where(seen, s, -jnp.inf), axis=-1), vv)[None]


@pytest.mark.parametrize("t", [8, 16, 32, 31, 5])
def test_a_band_of_groups_of_three_equals_the_masked_scores(t, monkeypatch):
    """``MultiHeadAttention(window=8)`` WITHOUT a sink at 6 query heads over
    2 key/value heads, over buckets of one, two and four windows (a band
    from two on), a ragged 31 and a short 5: the reference's masked T x T
    softmax; and the same when the band is made a run of blocks at a time
    (``_SCORE_BYTES`` held to one block's scores, as 72 heads x 8,192 x
    1,024 pass it on the chip)."""
    q, k, v = _qkv(t)
    attrs = dict(causal=True, scale=-1.0, window=W)
    want = np.asarray(_masked_reference(q, k, v, W))
    got = attention._multi_head_attention(attrs, q, k, v)
    assert got.shape == (1, 6, t, 16)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)
    monkeypatch.setattr(attention, "_SCORE_BYTES", 4 * 6 * W * 2 * W)
    runs = attention._multi_head_attention(attrs, q, k, v)
    np.testing.assert_allclose(np.asarray(runs), want, rtol=2e-5, atol=2e-6)


def test_a_ring_read_by_groups_of_three_without_a_sink():
    """``KVRingAttention`` without a sink at 6 query heads over 2 key/value
    heads: the first ``pos + 1`` slots while the ring fills, all of them
    after; query head h reads key/value head h // 3."""
    rs = np.random.RandomState(3)
    ring_k = jnp.asarray(rs.randn(2, 2, W, 16), jnp.float32)
    ring_v = jnp.asarray(rs.randn(2, 2, W, 16), jnp.float32)
    q = jnp.asarray(rs.randn(2, 6, 16), jnp.float32)
    pos = jnp.asarray([[2.0], [13.0]])
    slot = jnp.asarray([[17.0], [40.0]])
    got = attention._kv_ring_attention({"scale": -1.0}, q, ring_k, ring_v,
                                       pos, slot)
    assert got.shape == (2, 6, 16)
    for r, live in ((0, 3), (1, W)):
        kk = jnp.repeat(ring_k[r, :, :live], 3, axis=0)
        vv = jnp.repeat(ring_v[r, :, :live], 3, axis=0)
        p = jax.nn.softmax(jnp.einsum("hd,hwd->hw", q[r], kk) * 0.25, -1)
        np.testing.assert_allclose(
            np.asarray(got[r]), np.asarray(jnp.einsum("hw,hwd->hd", p, vv)),
            rtol=2e-5, atol=2e-5)


# ------------------------------------------------------ (b) the expert share
def test_four_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """32 experts in 4 shares of 8 (offsets 0, 8, 16, 24), as the deployment's
    four chips hold 64 of 256 each: every share routes over all 32, picks
    and renormalises over the 4 chosen wherever they live, scales by 2.5 and
    sums its own experts' products. The four partial results and the shared
    expert COUNTED ONCE are the reference's uncut layer (its loop over all 32
    experts beside its shared MLP), the order of a float32 sum apart; no
    share alone is."""
    rs = np.random.RandomState(0)
    g = lambda *shape: jnp.asarray(rs.randn(*shape).astype("f") * 0.2)
    n, d, f, e = 40, 48, 16, 32
    x, router, gate, up, down = g(n, d) * 5, g(e, d), g(e, d, f), \
        g(e, d, f), g(e, f, d)
    bias = jnp.asarray(rs.randn(e).astype("f") * 0.5)
    shared_in, shared_out = g(2 * f, d), g(d, f)
    attrs = dict(num_experts=e, num_hidden=f, num_experts_per_tok=4,
                 scoring="sigmoid", router_bias=True, norm_topk_prob=True,
                 routed_scaling_factor=2.5)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(
            ref.moe(x, router, bias, gate, up, down, 4, 2.5, 0)
            + ref.gated_mlp(x, shared_in, shared_out), np.float64)
        shared = np.asarray(ref.gated_mlp(x, shared_in, shared_out),
                            np.float64)
    parts = []
    for first in range(0, e, 8):
        held = slice(first, first + 8)
        part, load = moe._moe_feed_forward(
            dict(attrs, num_local_experts=8, local_expert_offset=first), x,
            router, gate[held], up[held], down[held], bias)
        assert float(load.sum()) == n * 4
        parts.append(np.asarray(part, np.float64))
    assert np.abs(sum(parts) + shared - want).max() \
        < 1e-5 * np.abs(want).max()
    assert all(_rel_l2(p + shared, want).max() > 0.1 for p in parts)
    # the shared expert counted with every share would be counted four times
    assert _rel_l2(sum(p + shared for p in parts), want).max() > 0.1


# ------------------------------------------ (c) prefill, then decode: the cache
@pytest.mark.parametrize("length", [5, 8, 20, 32])
def test_admit_then_steps_agree_with_the_full_forward(length):
    """The logits ``admit`` returns and those of 30 single decode steps through
    the cache (pages of the two full layers, rings of the four window layers)
    against the reference's full forward over the whole sequence, row by row:
    prompts shorter than the window of 8, as long as it, no multiple of it
    (20) and the whole bucket (32), the steps fed DRAWN tokens and running
    across the ring's wrap more than three times. The first window layer's
    key ring after the admission and after the last step holds the
    reference's normed and rotated keys of the last 8 positions (fewer while
    it fills), each at its position mod 8."""
    params = _weights()
    toks = np.random.RandomState(length).randint(1, CFG["vocab_size"],
                                                 length + 30)
    got, admitted, last = _admit_and_step(_shared_decoder(), toks, length)
    want = np.asarray(ref.logits(params, jnp.asarray(toks), CFG, last=31))
    assert got.dtype == np.float32 and got.shape == (31, 600)
    assert _rel_l2(got, want).max() < F32_TOL
    keys = ref.first_window_keys(params, jnp.asarray(toks), CFG)
    assert admitted.shape == last.shape == (2, W, 16) == keys.shape[:1] + (
        W, 16)
    assert _ring_error(admitted, keys, length - 1) < 1e-5
    assert _ring_error(last, keys, len(toks) - 1) < 1e-5


FAULTS = ("window_one_slot_too_long", "window_rotary_in_full_layers",
          "attention_factor_dropped", "whole_head_rotated_in_full_layers",
          "gate_dropped", "qk_norm_dropped", "weights_from_biased_scores",
          "scaling_dropped", "shared_expert_dropped", "another_share")


def faulty(fault, cfg=CFG):
    """(reference module, its configuration, what to do to the weights) with
    one part of the layer equations wrong. (The benchmark's chip runs use it
    too, at the published sizes.)"""
    bad, cfg, bend = reference(), dict(cfg), lambda p: p
    if fault == "window_one_slot_too_long":
        cfg["sliding_window"] = cfg["sliding_window"] + 1
    elif fault == "window_rotary_in_full_layers":
        rotary = bad.rotary
        bad.rotary = lambda x, pos, c, windowed: rotary(x, pos, c, True)
    elif fault == "attention_factor_dropped":
        cfg["attention_factor"] = 1.0
    elif fault == "whole_head_rotated_in_full_layers":
        cfg["rotary_dim"] = cfg["head_dim"]
    elif fault == "gate_dropped":
        bad.head_gate = lambda a, p, n: jnp.ones(
            (a.shape[0], p[n + "gate_weight"].shape[0]), jnp.float32)
    elif fault == "qk_norm_dropped":
        bad.qk_norm = lambda x, gamma, eps: x
    elif fault == "weights_from_biased_scores":
        def route(h, router, bias, top_k, scaling):
            s = jax.nn.sigmoid(h @ router.astype(jnp.float32).T) \
                + bias.astype(jnp.float32)
            w, chosen = jax.lax.top_k(s, top_k)
            return scaling * w / (jnp.sum(w, axis=-1, keepdims=True)
                                  + 1e-20), chosen
        bad.route = route
    elif fault == "scaling_dropped":
        cfg["routed_scaling_factor"] = 1.0
    elif fault == "shared_expert_dropped":
        bend = lambda p: {k: jnp.zeros_like(v)
                          if k.endswith("shared_out_weight") else v
                          for k, v in p.items()}
    elif fault == "another_share":
        cfg["local_expert_offset"] = 0 if cfg["local_expert_offset"] else \
            cfg["num_local_experts"]
    else:
        raise AssertionError(fault)
    return bad, cfg, bend


@pytest.mark.parametrize("fault", FAULTS)
def test_a_reference_with_one_part_wrong_disagrees(fault):
    """Each mechanism the block adds is seen by the comparison: against a
    reference with a window of 9, with the window layers' plain rotary in a
    full layer, without ``attention_factor``, with the whole head rotated in
    a full layer, without the gate, without the q/k norm, with weights taken
    from score + bias, without the x 2.5, without the shared expert, or with
    the share of experts 0..7, EVERY row of the sample reads above 30 times
    the sound limit."""
    toks, got = _sample()
    bad, cfg, bend = faulty(fault)
    want = np.asarray(bad.logits(bend(_weights()), jnp.asarray(toks), cfg,
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


def test_two_head_counts_over_one_cache_layout():
    """``param_shapes`` gives a window layer's projections 6 query heads and
    a full layer's 4, both over 2 key/value heads; ``decode_cache`` names
    pools for the full layers and rings for the window layers, the SAME two
    heads of 16 in both, in layer order; a ring is 8 slots a lane WHATEVER
    ``max_len`` is, and an admission takes page frames for the pools alone."""
    shapes = tf.param_shapes(**CFG)
    assert shapes["layer0_qkv_weight"] == ((4 + 4) * 16, 48)
    assert shapes["layer1_qkv_weight"] == ((6 + 4) * 16, 48)
    assert shapes["layer0_proj_weight"] == (48, 64)
    assert shapes["layer1_proj_weight"] == (48, 96)
    assert shapes["layer4_gate_weight"] == (4, 48)
    assert shapes["layer5_gate_weight"] == (6, 48)
    assert shapes["layer3_qnorm_gamma"] == shapes["layer3_knorm_gamma"] \
        == (16,)
    assert "layer0_router_weight" not in shapes \
        and shapes["layer0_mlp_in_weight"] == (128, 48)
    assert shapes["layer1_experts_gate_weight"] == (8, 48, 16)
    assert shapes["layer1_shared_in_weight"] == (32, 48)
    cache = tf.decode_cache(**CFG)
    assert [(n, k) for n, k, _ in cache] == [
        ("kv_k_0", "pool"), ("kv_v_0", "pool"), ("ring_k_1", "ring"),
        ("ring_v_1", "ring"), ("ring_k_2", "ring"), ("ring_v_2", "ring"),
        ("ring_k_3", "ring"), ("ring_v_3", "ring"), ("kv_k_4", "pool"),
        ("kv_v_4", "pool"), ("ring_k_5", "ring"), ("ring_v_5", "ring")]
    by_name = {n: s for n, _, s in cache}
    assert by_name["kv_k_4"] == by_name["kv_v_4"] == (2, 16)
    assert by_name["ring_k_5"] == by_name["ring_v_5"] == (2, W, 16)
    params = _weights()
    for max_len in (64, 256):
        dec = _decoder(params, max_len=max_len).warmup()
        bufs = {n: dec._dec_exe.arg_dict[n].shape for n, _, _ in cache}
        assert bufs["ring_k_1"] == bufs["ring_v_3"] == (4, 2, W, 16)
        assert bufs["kv_k_0"] == bufs["kv_v_4"] == (2, 4 * max_len, 16)
        assert dec._ring_names == [n for n, k, _ in cache if k == "ring"]
        assert dec._pool_names == ["kv_k_0", "kv_v_0", "kv_k_4", "kv_v_4"]
        seq, _ = dec.admit(np.arange(1, 21, dtype=np.float32))
        assert dec.pool.in_use == 3        # 20 tokens in pages of 8
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
    megastep programs refuse the arch as they refuse its nine siblings;
    admit, step and retire are the same entry points as every other
    block's; a configuration that does not say every layer's kind is
    refused."""
    params = _weights()
    with pytest.raises(MXNetError, match="not built for arch 'laguna' yet"):
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
        with pytest.raises(MXNetError, match="not built for arch 'laguna' "
                                             "yet"):
            call()
    row = dec.step({seq: int(np.argmax(logits))})[seq]
    assert row.shape == (600,) and dec.position(seq) == 4
    dec.retire(seq)
    assert dec.stats()["active"] == 0 and dec.stats()["pages_in_use"] == 0
    assert dec._pf_cache._model_key.endswith("-laguna-prefill")
    with pytest.raises(MXNetError, match="layer_types must name 6 layers"):
        tf.param_shapes(**dict(CFG, layer_types=[FULL, WINDOW]))
    with pytest.raises(MXNetError, match="rotary_dim 7 must be even"):
        tf.param_shapes(**dict(CFG, rotary_dim=7))


def test_spans_gauges_and_counters(tm):
    """What the tracing sees: the gauges set at warm-up (pools and rings),
    the ring's hand-over under ``serving.admit.state``, a step's window
    slots and local assignments, and the two admission counters: what ONE
    window layer's band scored over the bucket (32 x 2 x 8, whatever the
    prompt) and what of it a real position attends (a prompt of 20: 36 pairs
    while the window fills, then 12 x 8; a prompt of 3: 6)."""
    params = _weights()
    dec = _decoder(params).warmup()
    pools = 2 * 4 * 64 * 2 * 32 * 4         # two layers x slots x (k + v)
    rings = 4 * 4 * 2 * W * 32 * 4          # four layers x lanes x heads
    assert tm.gauge("serving.full_pool_bytes").value == pools
    assert tm.gauge("serving.window_ring_bytes").value == rings
    assert tm.gauge("serving.cache_bytes").value == pools + rings \
        == tm.gauge("serving.decode_aliased_bytes").value
    tm.clear_events()
    before = tm.counters()
    a, _ = dec.admit(np.arange(1, 21, dtype=np.float32))     # 20 > window
    b, _ = dec.admit(np.asarray([7, 8, 9], np.float32))      # 3 < window
    dec.step({a: 4, b: 5})
    moved = {k: v - before.get(k, 0) for k, v in tm.counters().items()}
    assert moved["serving.admit_window_pairs_scored"] == 2 * 32 * 2 * W
    assert moved["serving.admit_window_pairs_live"] \
        == (W * (W + 1) // 2 + 12 * W) + 6
    assert moved["serving.step_context_tokens"] == 21 + 4
    assert moved["serving.step_window_slots"] == W + 4
    # every lane passes through the experts, those that ride along too
    assert moved["serving.moe.step_assignments"] == 5 * 4 * 4
    local = moved["serving.moe.step_local_assignments"]
    assert 0 < local < moved["serving.moe.step_assignments"]
    assert 0 < moved["serving.moe.step_experts_touched"] <= min(local, 5 * 8)
    spans = [name for name, _t0, _dur, _tid, _attrs in tm.drain_events()]
    assert spans.count("serving.admit.state") == 2


@pytest.mark.parametrize("length", [5, 20, 32])
def test_a_prefill_held_to_the_windowed_kernel_is_the_references(
        monkeypatch, tm, length):
    """The rule held to ``"window_kernel"`` for the four window layers (the
    chip's answer at the cell's sizes), the kernel interpreted in (8, 16)
    blocks at groups of three: the admission's logits and the first window
    layer's ring are the reference's inside the float32 limit the band holds,
    the warm-up's gauges name four kernel layers under a window and no band,
    and the admission counts the pairs THAT form scores: of the four
    query blocks of 8 the third alone reaches back into a second key block
    of 16, 8 x 16 x 5, where the band scored 32 x 2 x 8."""
    from mxnet_tpu.ops import pallas_attention as pa

    rule = attention.attention_form
    monkeypatch.setattr(
        attention, "attention_form", lambda *a: "window_kernel"
        if a[4] > 0 and not a[5] else rule(*a))
    monkeypatch.setattr(pa, "blocks", lambda *a, **kw: (8, 16))
    params = _weights()
    before = dict(attention.DISPATCH_COUNTS)
    dec = _decoder(params).warmup()
    assert tm.gauge("serving.prefill_attention.window_kernel_layers").value \
        == 4
    assert tm.gauge("serving.prefill_attention.band_layers").value == 0
    assert attention.DISPATCH_COUNTS["window_kernel"] \
        == before["window_kernel"] + 4
    assert attention.DISPATCH_COUNTS["band"] == before["band"]
    toks = np.random.RandomState(length).randint(1, CFG["vocab_size"],
                                                 length + 3)
    counted = tm.counters()
    got, admitted, _ = _admit_and_step(dec, toks, length)
    moved = {k: v - counted.get(k, 0) for k, v in tm.counters().items()}
    assert pa.window_key_blocks(32, 32, 8, 16, W) == 2
    assert moved["serving.admit_window_pairs_scored"] == 8 * 16 * 5 \
        == pa.window_pairs_scored(32, 32, 8, 16, W)
    want = np.asarray(ref.logits(params, jnp.asarray(toks), CFG, last=4))
    assert _rel_l2(got, want).max() < F32_TOL
    keys = ref.first_window_keys(params, jnp.asarray(toks), CFG)
    assert _ring_error(admitted, keys, length - 1) < 1e-5


def test_a_dense_window_scores_the_whole_bucket(tm):
    """Where the bucket is no multiple of the window (or of the window less
    one) the window layers mask full T x T scores, and the counter says so."""
    dec = _decoder(_weights(), prefill_len=20, max_len=40).warmup()
    before = tm.counters()
    dec.admit(np.arange(1, 13, dtype=np.float32))
    moved = {k: v - before.get(k, 0) for k, v in tm.counters().items()}
    assert moved["serving.admit_window_pairs_scored"] == 20 * 20
    assert moved["serving.admit_window_pairs_live"] == 36 + 4 * W


# ------------------------------- (d) what this PR shared, and what it left be
_MIMO = dict(arch="mimo_v2_flash", vocab_size=96, num_layers=7, num_heads=4,
             num_kv_heads=1, swa_num_kv_heads=2, head_dim=12, v_head_dim=8,
             model_dim=48, ffn_dim=64, moe_ffn_dim=16, num_experts=32,
             num_local_experts=8, local_expert_offset=0, num_experts_per_tok=4,
             hybrid_layer_pattern=[0, 1, 1, 1, 1, 0, 1],
             moe_layer_freq=[0, 1, 1, 1, 1, 1, 1], sliding_window=8,
             rotary_dim=4, rope_theta=5e6, swa_rope_theta=1e4,
             attention_value_scale=0.707, rms_eps=1e-5,
             routed_scaling_factor=1.0, norm_topk_prob=True)


def test_mimos_graphs_are_the_parents():
    """The two ``attend`` closures this PR took out of ``_mimo_prefill_symbol``
    and ``_mimo_decode_symbol`` (``_window_prefill_attend``,
    ``_window_step_attend``: ONE copy, which ``laguna`` builds from too)
    leave mimo's graphs the parent commit's letter for letter: the JSON is
    part of the program store's key. (sha1 of ``tojson()`` at the benchmark's
    tiny sizes, taken from the parent commit's tree.)"""
    sha = lambda sym: hashlib.sha1(sym.tojson().encode()).hexdigest()[:12]
    with mx.name.NameManager():
        prefill = tf.get_prefill_symbol(prefill_len=16, **_MIMO)
    with mx.name.NameManager():
        decode = tf.get_decode_symbol(max_len=4 * 64, page_size=8, **_MIMO)
    assert (sha(prefill), sha(decode)) == ("d28e8d5cb69b", "3d46c6616254")
    for builder, shared in ((tf._mimo_prefill_symbol, "_window_prefill_symbol"),
                            (tf._laguna_prefill_symbol,
                             "_window_prefill_symbol"),
                            (tf._mimo_decode_symbol, "_window_decode_symbol"),
                            (tf._laguna_decode_symbol,
                             "_window_decode_symbol")):
        assert shared in builder.__code__.co_names
