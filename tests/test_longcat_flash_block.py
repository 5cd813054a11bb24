"""The LongCat-Flash block (``arch="longcat_flash"`` of models/transformer.py
and serving.PagedKVDecoder: a layer of TWO latent attentions with a low-rank
query, two dense MLPs and ONE shortcut-connected expert layer whose router is
WIDER than its experts, the outputs past them zero-compute experts, the
identity) against the benchmark's plain reference,
benchmark/reference/longcat_flash_decoder.py, on seeded weights at small
sizes that keep what is odd about the model: two layers = four latent pools,
4 heads of [8 | 4] over a latent of 16 behind a query rank of 24 (rho_q =
sqrt 2, rho_kv = sqrt 3), 32 experts of which experts 8..15 are held beside
16 zero-compute ones, 6 a token by softmax scores with a selection bias, not
renormalised, x 6. Every tolerance says where it comes from.
"""
import functools
import hashlib
import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import transformer as tf
from mxnet_tpu.ops import moe
from mxnet_tpu.serving import PagedKVDecoder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reference():
    """A fresh copy of the reference module: a test may bend one of its
    functions without any other test seeing it."""
    path = os.path.join(ROOT, "benchmark", "reference",
                        "longcat_flash_decoder.py")
    spec = importlib.util.spec_from_file_location("longcat_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = reference()

E, Z, K = 32, 16, 6
# vocabulary above 256 on purpose: bfloat16 holds whole numbers to 256 only
CFG = dict(arch="longcat_flash", vocab_size=600, num_layers=2, num_heads=4,
           model_dim=48, ffn_dim=64, moe_ffn_dim=16, num_experts=E,
           num_zero_experts=Z, num_experts_per_tok=K, num_local_experts=8,
           local_expert_offset=8, q_lora_rank=24, kv_lora_rank=16,
           qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
           rope_theta=1e4, rms_eps=1e-5, routed_scaling_factor=6.0)
SERVE = dict(max_len=64, prefill_len=32, page_size=8, lanes=4)

# float32 on both sides on the CPU: what is left is the order of the sums
# (grouped matmul against a loop over experts, the pool's absorbed
# contraction against materialised keys and values); the runs read 4e-7 to
# 9e-7
F32_TOL = 1e-4
# bfloat16 weights, activations and pools against the float32 reference over
# the same (bfloat16-valued) weights: every stored activation is rounded to 8
# bits of mantissa, some dozen roundings a sublayer. It holds a sample's
# LOWER-QUARTILE row, in the manner of the benchmark's check: where a token's
# sixth and seventh biased score lie within the rounding the program and the
# reference choose another expert and that row reads higher
BF16_TOL = 5e-2
# a pool's rows: one bfloat16 rounding of the row itself, the bfloat16
# residual stream of one sublayer before it, norm and rotation in float32
BF16_POOL_TOL = 3e-2


def _lower_quartile(err):
    return np.sort(err)[-(-len(err) // 4) - 1]


def _weights(dtype="float32", seed=0, cfg=CFG):
    """N(0, 0.1) matrices, a unit-variance embedding, gammas of the two
    latent norms drawn around 1 (so that a norm that forgets its gamma is
    seen), a router N(0, 0.3) (softmax scores that differ) and a selection
    bias N(0, 0.05): twice the scores' own level of 1 / 48, so that
    selecting by p + b and weighing by p are told apart in every row."""
    rs = np.random.RandomState(seed)
    out = {}
    for name, shape in sorted(tf.param_shapes(**cfg).items()):
        if name.endswith(("qnorm_gamma", "kvnorm_gamma")):
            v = (1.0 + 0.3 * rs.randn(*shape)).astype("f")
        elif name.endswith("gamma"):
            v = np.ones(shape, "f")
        else:
            v = rs.randn(*shape).astype("f") * (
                1.0 if name == "embed_weight"
                else 0.05 if name.endswith("router_bias")
                else 0.3 if name.endswith("router_weight") else 0.1)
        out[name] = jnp.asarray(v).astype(dtype)
    return out


def _decoder(params, dtype="float32", cfg=CFG, **kw):
    return PagedKVDecoder({k: mx.nd.NDArray(v) for k, v in params.items()},
                          dtype=dtype, **dict(SERVE, **kw), **cfg)


def _rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def _pool(dec, seq, name="kv_c_1"):
    return np.array(dec.lane_state(seq, (name,))[name]).astype(np.float32)


def _admit_and_step(dec, toks, length):
    """Admit ``toks[:length]``, then feed the rest one step each: (the 1 +
    steps logits rows, the lane's rows of the first layer's two pools after
    the last step)."""
    seq, logits = dec.admit(np.asarray(toks[:length], np.float32))
    got = [np.asarray(logits)]
    for tok in toks[length:]:
        got.append(np.asarray(dec.step({seq: int(tok)})[seq]))
    pools = _pool(dec, seq, "kv_c_0"), _pool(dec, seq, "kv_c_1")
    dec.retire(seq)
    return np.stack(got), pools


@functools.lru_cache(maxsize=None)
def _shared_decoder():
    """ONE float32 decoder for the tests that only admit, step and retire."""
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


# ----------------------------------------- (a) the operator's zero experts
def _written_layer(rs, n=8, d=48, f=16):
    """A layer whose routing is WRITTEN: feature 0 large sends a token to the
    zero-compute experts alone, feature 1 large to experts with weights
    alone (router rows +-1 on the two features, small noise elsewhere)."""
    g = lambda *shape: jnp.asarray(rs.randn(*shape).astype("f") * 0.2)
    router = rs.randn(E + Z, d).astype("f") * 0.01
    router[:E, :2], router[E:, :2] = (-1.0, 1.0), (1.0, -1.0)
    x = rs.randn(n, d).astype("f")
    x[:, :2] = 0.0
    return x, jnp.asarray(router), g(E, d, f), g(E, d, f), g(E, f, d)


ATTRS = dict(num_experts=E, num_zero_experts=Z, num_hidden=16,
             num_experts_per_tok=K, scoring="softmax",
             routed_scaling_factor=6.0)


def test_all_zero_compute_all_real_and_the_load_by_hand():
    """A token whose six are all zero-compute experts comes back as
    ``6 x sum(p_chosen) x`` and multiplies nothing; one whose six are all experts with weights has no identity part
    and equals the layer WITHOUT zero experts over the same scores; ``load``
    is (E + Z,) and its tail counts the zero-compute assignments."""
    rs = np.random.RandomState(3)
    x, router, gate, up, down = _written_layer(rs)
    x[0, 0], x[1, 1] = 9.0, 9.0            # token 0: zero only; 1: real only
    x = jnp.asarray(x)
    y, load = moe._moe_feed_forward(ATTRS, x, router, gate, up, down)
    p = jax.nn.softmax(jnp.dot(x, router.T, precision="highest"), axis=-1)
    w, chosen = jax.lax.top_k(p, K)
    chosen = np.asarray(chosen)
    assert (chosen[0] >= E).all() and (chosen[1] < E).all()
    np.testing.assert_allclose(
        np.asarray(y[0]), 6.0 * float(w[0].sum()) * np.asarray(x[0]),
        rtol=1e-5, atol=1e-6)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.moe(x, router, jnp.zeros(E + Z), gate, up,
                                  down, K, 6.0, 0, E))
    assert _rel_l2(np.asarray(y), want).max() < 1e-5
    part = np.asarray(ref.identity_part(x, 6.0 * w, jnp.asarray(chosen), E))
    assert np.abs(part[1]).max() == 0.0 and np.abs(part[0]).max() > 1.0
    load = np.asarray(load)
    assert load.shape == (E + Z,) and load.sum() == x.shape[0] * K
    assert np.array_equal(load, np.bincount(chosen.ravel(),
                                            minlength=E + Z))
    assert load[E:].sum() == (chosen >= E).sum() >= K
    sym_out = mx.sym.MoEFeedForward(
        *(mx.sym.Variable(n) for n in ("data", "router_weight", "gate_weight",
                                       "up_weight", "down_weight")), **ATTRS)
    _, out_shapes, _ = sym_out.infer_shape(
        data=x.shape, router_weight=router.shape, gate_weight=gate.shape,
        up_weight=up.shape, down_weight=down.shape)
    assert out_shapes == [x.shape, (E + Z,)]


@pytest.mark.parametrize("compact", [False, True])
def test_four_shares_and_the_identity_part_once_add_up_to_the_uncut_layer(
        compact, monkeypatch):
    """32 experts in 4 shares of 8, as the deployment's 32 chips hold 16 of
    512 each: every share routes over all 48 outputs, picks six wherever
    they live and sums its own experts' products; every share also adds the
    identity part for every row (a token's own chip does in the deployment).
    The four expert parts and the identity part COUNTED ONCE are the
    reference's uncut layer; counted with every share it would be counted
    four times. Both forms: every assignment a row (``_all_rows``) and the
    held rows alone in chunks (``_held_rows``, the rule held to a test's
    sizes); a zero-compute assignment takes a row in neither group."""
    if compact:
        monkeypatch.setattr(moe, "_ROW_TILE", 16)
        monkeypatch.setattr(moe, "_ROWS_WORTH_A_CHUNK", 64)
    rs = np.random.RandomState(0)
    g = lambda *shape: jnp.asarray(rs.randn(*shape).astype("f") * 0.2)
    n, d, f = 40, 48, 16
    x, router, gate, up, down = g(n, d) * 5, g(E + Z, d), g(E, d, f), \
        g(E, d, f), g(E, f, d)
    bias = jnp.asarray(rs.randn(E + Z).astype("f") * 0.02)
    assert bool(moe.held_rows_chunk(n, K, 8, E + Z)) == compact
    attrs = dict(ATTRS, router_bias=True)
    with jax.default_matmul_precision("highest"):
        weights, chosen = ref.route(x, router, bias, K, 6.0)
        identity = np.asarray(ref.identity_part(x, weights, chosen, E),
                              np.float64)
        want = np.asarray(ref.moe(x, router, bias, gate, up, down, K, 6.0, 0,
                                  E), np.float64)
    assert np.abs(identity).max() > 0.1 * np.abs(want).max()
    parts = []
    for first in range(0, E, 8):
        held = slice(first, first + 8)
        part, load = moe._moe_feed_forward(
            dict(attrs, num_local_experts=8, local_expert_offset=first), x,
            router, gate[held], up[held], down[held], bias)
        assert float(load.sum()) == n * K and load.shape == (E + Z,)
        parts.append(np.asarray(part, np.float64) - identity)
    assert np.abs(sum(parts) + identity - want).max() \
        < 1e-5 * np.abs(want).max()
    assert all(_rel_l2(p + identity, want).max() > 0.1 for p in parts)
    assert _rel_l2(sum(p + identity for p in parts), want).max() > 0.1


def test_without_zero_experts_the_operator_is_what_it_was():
    """``num_zero_experts`` 0, given or left out, lowers to the same text:
    every existing graph's program, and with it its compile-cache entry,
    stays the parent's (mimo's, kanana's and dots3's graph JSON are pinned
    below and in ``test_laguna_block``)."""
    rs = np.random.RandomState(1)
    g = lambda *shape: jnp.asarray(rs.randn(*shape).astype("f") * 0.2)
    args = g(16, 48), g(E, 48), g(E, 48, 16), g(E, 48, 16), g(E, 16, 48)
    plain = dict(num_experts=E, num_hidden=16, num_experts_per_tok=4)
    text = lambda attrs: jax.jit(
        lambda *a: moe._moe_feed_forward(attrs, *a)).lower(*args).as_text()
    assert text(plain) == text(dict(plain, num_zero_experts=0))
    assert text(plain) != text(dict(plain, num_zero_experts=4))


# ------------------------------------------ (b) prefill, then decode: the cache
@pytest.mark.parametrize("length", [3, 8, 20, 32])
def test_admit_then_steps_agree_with_the_full_forward(length):
    """The logits ``admit`` returns and those of 24 single decode steps
    through the four pools against the reference's full forward over the
    whole sequence, row by row: a prompt shorter than a page, one page, no
    multiple of it and the whole bucket, the steps fed DRAWN tokens. The
    first layer's SECOND pool holds the reference's [rho_kv c | k_r] of every
    position, and its FIRST pool does not: a pool index off by one (the
    second pool read or written for the first sublayer) is seen."""
    params = _weights()
    toks = np.random.RandomState(length).randint(1, CFG["vocab_size"],
                                                 length + 24)
    got, (first, second) = _admit_and_step(_shared_decoder(), toks, length)
    want = np.asarray(ref.logits(params, jnp.asarray(toks), CFG, last=25))
    assert got.dtype == np.float32 and got.shape == (25, 600)
    assert _rel_l2(got, want).max() < F32_TOL
    rows = np.asarray(ref.second_pool_rows(params, jnp.asarray(toks), CFG))
    assert second.shape == rows.shape == (1, len(toks), 16 + 4)
    assert _rel_l2(second[0], rows[0]).max() < 1e-5
    assert _rel_l2(first[0], rows[0]).min() > 0.3


FAULTS = ("values_not_scaled", "rotary_key_scaled", "query_not_scaled",
          "experts_join_after_the_first_mlp", "weights_renormalised",
          "identity_part_dropped", "weights_from_biased_scores",
          "scaling_dropped", "another_share")


def faulty(fault, cfg=CFG):
    """(reference module, its configuration) with one part of the layer
    equations wrong. (The benchmark's chip runs use it too, at the published
    sizes.)"""
    bad, cfg = reference(), dict(cfg)
    if fault == "values_not_scaled":
        sound = bad.keys_and_values

        def keys_and_values(c, p, n, c_):
            keys, _ = sound(c, p, n, c_)
            return keys, sound(c / bad.rho(c_, "kv_lora_rank"), p, n, c_)[1]
        bad.keys_and_values = keys_and_values
    elif fault == "rotary_key_scaled":
        sound_row = bad.latent_row

        def latent_row(a, p, n, positions, c_):
            row, lat = sound_row(a, p, n, positions, c_), c_["kv_lora_rank"]
            return jnp.concatenate(
                [row[:, :lat], row[:, lat:] * bad.rho(c_, "kv_lora_rank")],
                axis=-1)
        bad.latent_row = latent_row
    elif fault == "query_not_scaled":
        sound_q = bad.queries
        bad.queries = lambda a, p, n, positions, c_: sound_q(
            a, p, n, positions, c_) / bad.rho(c_, "q_lora_rank")
    elif fault == "experts_join_after_the_first_mlp":
        bad.JOINS_AFTER = 0
    elif fault == "weights_renormalised":
        sound_route = bad.route

        def route(h, router, bias, top_k, scaling):
            w, chosen = sound_route(h, router, bias, top_k, scaling)
            return scaling * w / jnp.sum(w, axis=-1, keepdims=True), chosen
        bad.route = route
    elif fault == "identity_part_dropped":
        bad.identity_part = lambda h, weights, chosen, n: jnp.zeros_like(h)
    elif fault == "weights_from_biased_scores":
        def route(h, router, bias, top_k, scaling):
            s = jax.nn.softmax(h @ router.astype(jnp.float32).T, axis=-1) \
                + bias.astype(jnp.float32)
            w, chosen = jax.lax.top_k(s, top_k)
            return scaling * w, chosen
        bad.route = route
    elif fault == "scaling_dropped":
        cfg["routed_scaling_factor"] = 1.0
    elif fault == "another_share":
        cfg["local_expert_offset"] = 0 if cfg["local_expert_offset"] else \
            cfg["num_local_experts"]
    else:
        raise AssertionError(fault)
    return bad, cfg


@pytest.mark.parametrize("fault", FAULTS)
def test_a_reference_with_one_part_wrong_disagrees(fault):
    """Each mechanism the block adds is seen by the comparison: against a
    reference whose values miss rho_kv, whose shared rotary key has it, whose
    query misses rho_q, whose expert sum joins after the FIRST MLP, whose
    weights are renormalised over the six, without the identity part, with
    weights taken from score + bias or without the x 6, EVERY row of the
    sample reads above 30 times the sound limit; with the share of experts
    0..7 the sample's MEDIAN row does (a token none of whose six is held by
    either share reads the same under both, until a token before it differs)."""
    toks, got = _sample()
    bad, cfg = faulty(fault)
    want = np.asarray(bad.logits(_weights(), jnp.asarray(toks), cfg,
                                 last=13))
    seen = np.median if fault == "another_share" else np.min
    assert seen(_rel_l2(got, want)) > 30 * F32_TOL


def test_bfloat16_weights_and_pools():
    """The chip's types on the CPU: bfloat16 weights and pools, float32 ids
    and positions. A sample's lower-quartile row and the second pool's rows
    stay within storage rounding of the float32 reference."""
    params = _weights("bfloat16")
    dec = _decoder(params, "bfloat16")
    toks = np.random.RandomState(5).randint(1, CFG["vocab_size"], 20 + 12)
    got, (_, second) = _admit_and_step(dec, toks, 20)
    want = np.asarray(ref.logits(params, jnp.asarray(toks), CFG, last=13))
    assert got.dtype == np.float32
    assert _lower_quartile(_rel_l2(got, want)) < BF16_TOL
    rows = np.asarray(ref.second_pool_rows(params, jnp.asarray(toks), CFG))
    assert np.linalg.norm(second - rows) / np.linalg.norm(rows) \
        < BF16_POOL_TOL
    types = {name: str(dec._dec_exe.arg_dict[name].dtype)
             for name, _, _ in dec._cache}
    assert set(types.values()) == {"bfloat16"}


def test_two_pools_a_layer_and_a_router_wider_than_the_experts():
    """``param_shapes`` names a sublayer's weights ``layer<2l + s>_*``, the
    expert layer's with its first sublayer's, the router E + Z wide over
    stacks of the held experts; ``decode_cache`` names a pool a SUBLAYER, in
    order; an admission takes page frames once for all of them."""
    shapes = tf.param_shapes(**CFG)
    assert shapes["layer0_router_weight"] == shapes["layer2_router_weight"] \
        == (E + Z, 48)
    assert shapes["layer2_router_bias"] == (E + Z,)
    assert shapes["layer0_experts_gate_weight"] == (8, 48, 16)
    assert "layer1_router_weight" not in shapes \
        and "layer3_experts_up_weight" not in shapes
    for j in range(4):
        n = "layer%d_" % j
        assert shapes[n + "qa_weight"] == (24, 48)
        assert shapes[n + "qb_weight"] == (4 * 12, 24)
        assert shapes[n + "kva_weight"] == (16 + 4, 48)
        assert shapes[n + "kvb_weight"] == (4 * 16, 16)
        assert shapes[n + "mlp_in_weight"] == (128, 48)
    assert tf.decode_cache(**CFG) == [
        ("kv_c_%d" % j, "pool", (1, 20)) for j in range(4)]
    dec = _shared_decoder().warmup()
    assert dec._pool_names == ["kv_c_0", "kv_c_1", "kv_c_2", "kv_c_3"]
    assert all(dec._dec_exe.arg_dict[n].shape == (1, 4 * 64, 20)
               for n in dec._pool_names)
    seq, _ = dec.admit(np.arange(1, 21, dtype=np.float32))
    assert dec.pool.in_use == 3        # 20 tokens in pages of 8
    dec.retire(seq)
    assert dec.pool.in_use == 0


def test_multiplexed_lanes_equal_sequential_decoding():
    """Three sequences of different lengths stepped together, one of them
    joining late, produce each the logits it produces alone."""
    dec = _shared_decoder()
    rs = np.random.RandomState(7)
    prompts = [rs.randint(1, 600, n) for n in (5, 17, 9)]
    feeds = rs.randint(1, 600, (3, 6))
    alone = []
    for prompt, feed in zip(prompts, feeds):
        alone.append(_admit_and_step(dec, np.concatenate([prompt, feed]),
                                     len(prompt))[0])
    seqs, rows = [], [[], [], []]
    for j in (0, 1):
        seq, logits = dec.admit(prompts[j].astype(np.float32))
        seqs.append(seq)
        rows[j].append(np.asarray(logits))
    for step in range(6):
        if step == 2:
            seq, logits = dec.admit(prompts[2].astype(np.float32))
            seqs.append(seq)
            rows[2].append(np.asarray(logits))
        feed = {seq: int(feeds[j][len(rows[j]) - 1])
                for j, seq in enumerate(seqs)}
        out = dec.step(feed)
        for j, seq in enumerate(seqs):
            rows[j].append(np.asarray(out[seq]))
    for seq in seqs:
        dec.retire(seq)
    for j in range(3):
        n = len(rows[j])
        assert _rel_l2(np.stack(rows[j]), alone[j][:n]).max() < 1e-5


def test_what_the_arch_cannot_do_is_refused():
    """The prefix cache, the chunk, verify and megastep programs refuse the
    arch as they refuse its ten siblings; admit, step, retire, ``fork`` and
    ``rollback`` are the same entry points as every other block's of pools
    alone; an odd rotary width is refused."""
    params = _weights()
    with pytest.raises(MXNetError,
                       match="not built for arch 'longcat_flash' yet"):
        _decoder(params, prefix_cache=True)
    dec = _shared_decoder()
    seq, logits = dec.admit(np.asarray([5, 6, 7], np.float32))
    for call in (lambda: dec.verify_chunk(seq, [1, 2]),
                 lambda: dec.step_megastep({seq: 1}, k=2),
                 lambda: dec._chunk_for(4)):
        with pytest.raises(MXNetError,
                           match="not built for arch 'longcat_flash' yet"):
            call()
    row = dec.step({seq: int(np.argmax(logits))})[seq]
    assert row.shape == (600,) and dec.position(seq) == 4
    # pools alone fork and roll back, all four on the one page table
    twin = dec.fork(seq)
    both = dec.step({seq: 9, twin: 9})
    assert _rel_l2(both[twin], both[seq]) < 1e-6
    dec.rollback(twin, dec.position(twin) - 1)
    assert _rel_l2(dec.step({twin: 9})[twin], both[seq]) < 1e-6
    dec.retire(seq)
    dec.retire(twin)
    assert dec.stats()["active"] == 0 and dec.stats()["pages_in_use"] == 0
    assert dec._pf_cache._model_key.endswith("-longcat_flash-prefill")
    with pytest.raises(MXNetError, match="qk_rope_head_dim 3 is odd"):
        tf.param_shapes(**dict(CFG, qk_rope_head_dim=3))
    with pytest.raises(MXNetError, match="get_symbol is not built for arch"):
        tf.get_symbol(**{k: v for k, v in CFG.items()
                         if k in ("arch", "vocab_size", "num_layers",
                                  "num_heads", "model_dim", "ffn_dim")})


def test_counters_of_the_zero_compute_assignments(tm):
    """What the tracing sees of the router: an admission's and a step's
    assignments, those among them that went to a zero-compute expert (read
    from the ``load`` the programs return: the tail past the experts with
    weights) and the held experts a step touched."""
    dec = _decoder(_weights()).warmup()
    before = tm.counters()
    a, _ = dec.admit(np.arange(1, 21, dtype=np.float32))
    moved = {k: v - before.get(k, 0) for k, v in tm.counters().items()}
    # every position of the bucket passes through both expert layers
    assert moved["serving.moe.assignments"] == 2 * 32 * K
    load = np.asarray(dec._pf_cache.executable(
        dec._prefill_shapes()).outputs[dec._pf_moe_load]._jax())
    assert load.shape == (2, E + Z)
    assert moved["serving.moe.zero_assignments"] == load[:, E:].sum() > 0
    assert moved["serving.moe.admit_local_assignments"] \
        == load[:, 8:16].sum()
    before = tm.counters()
    dec.step({a: 4})
    moved = {k: v - before.get(k, 0) for k, v in tm.counters().items()}
    # every lane passes through the experts, those that ride along too
    assert moved["serving.moe.step_assignments"] == 2 * 4 * K
    zero = moved["serving.moe.step_zero_assignments"]
    local = moved["serving.moe.step_local_assignments"]
    assert 0 < zero and zero + local <= 2 * 4 * K
    assert moved["serving.moe.step_experts_touched"] <= min(local, 2 * 8)


# ------------------------------------- (c) one latent attention, three archs
def test_kananas_and_dots3s_graphs_are_the_parents():
    """The one ``_latent_operands`` that ``deepseek_v3``, ``dots3_note`` and
    this arch's two sublayers build their latent attention from, and the one
    pair of prefill and decode builders that ``deepseek_v3`` and this arch
    share, leave kanana's and dots3's graphs the parent commit's letter for
    letter: the JSON is part of the program store's key. (sha1 of
    ``tojson()`` at the benchmark's tiny sizes, taken from the parent
    commit's tree BEFORE the refactor.)"""
    sha = lambda sym: hashlib.sha1(sym.tojson().encode()).hexdigest()[:12]
    pins = {"kanana-2-30b-a3b": ("698537781264", "5b4a93a609b1"),
            "dots3-note-prev": ("6c815009a085", "9ae380af48ef")}
    for name, want in pins.items():
        with open(os.path.join(ROOT, "benchmark", "configs", "_tiny",
                               name + ".json")) as f:
            cfg = json.load(f)
        model, serve = cfg["model"], cfg["serving"]
        with mx.name.NameManager():
            prefill = tf.get_prefill_symbol(
                prefill_len=serve["prefill_len"], **model)
        with mx.name.NameManager():
            decode = tf.get_decode_symbol(
                max_len=serve["lanes"] * serve["max_len"],
                page_size=serve["page_size"], **model)
        assert (sha(prefill), sha(decode)) == want, name
    for layer in (tf._deepseek_v3_layer, tf._dots3_layer, tf._longcat_layer):
        assert "_latent_operands" in layer.__code__.co_names
    for builder, shared in (
            (tf._deepseek_v3_prefill_symbol, "_latent_prefill_symbol"),
            (tf._longcat_prefill_symbol, "_latent_prefill_symbol"),
            (tf._deepseek_v3_decode_symbol, "_latent_decode_symbol"),
            (tf._longcat_decode_symbol, "_latent_decode_symbol")):
        assert shared in builder.__code__.co_names
