"""The OLMoE block (RMSNorm, RotaryEmbedding, MoEFeedForward;
``arch="olmoe"`` of models/transformer.py and serving.PagedKVDecoder)
against the benchmark's plain reference, benchmark/reference/olmoe_decoder.py,
on seeded weights at small sizes. Every tolerance says where it comes from.
"""
import importlib.util
import os

import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import transformer as tf
from mxnet_tpu.serving import PagedKVDecoder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(ROOT, "benchmark", "reference", "olmoe_decoder.py")
    spec = importlib.util.spec_from_file_location("olmoe_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()

# vocabulary above 256 on purpose: bfloat16 holds whole numbers to 256 only
CFG = dict(arch="olmoe", vocab_size=600, num_layers=2, num_heads=4,
           head_dim=16, model_dim=64, ffn_dim=32, num_experts=8,
           num_experts_per_tok=2, rope_theta=10000.0, rms_eps=1e-5)
SERVE = dict(max_len=64, page_size=8, lanes=4)

# float32 on both sides on the CPU: what is left is the order of the sums
# (grouped matmul against a loop over experts, fused attention against the
# pool read), a few ulp on values of order 1
F32_TOL = 1e-4
# bfloat16 weights, activations and pool against the float32 reference over
# the same (bfloat16-valued) weights: every stored activation is rounded to
# 8 bits of mantissa (2^-9 relative), some ten roundings a layer; two layers
# read 6e-3 to 8e-3 worst row, and a float32 run of the same code 5e-7, so
# 3e-2 is storage rounding and nothing coarser (one int8 step would be 2^-4)
BF16_TOL = 3e-2


def _weights(dtype, seed=0, scale=0.1):
    rs = np.random.RandomState(seed)
    out = {}
    for name, shape in sorted(tf.param_shapes(**CFG).items()):
        v = np.ones(shape, "f") if name.endswith("gamma") \
            else rs.randn(*shape).astype("f") * scale
        out[name] = jnp.asarray(v).astype(dtype)
    return out


def _decoder(params, dtype, **kw):
    return PagedKVDecoder({k: mx.nd.NDArray(v) for k, v in params.items()},
                          dtype=dtype, **SERVE, **CFG, **kw)


def _rel_l2(got, want):
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


@pytest.fixture
def tm():
    telemetry.reset()
    saved = telemetry.current_override()
    telemetry.set_mode("counters")
    yield telemetry
    telemetry.set_mode(saved)
    telemetry.reset()


# ------------------------------------------------------------- (a) operators
def test_rms_norm_matches_the_reference():
    rs = np.random.RandomState(1)
    x, g = rs.randn(3, 5, 32).astype("f"), rs.rand(32).astype("f") + 0.5
    got = mx.nd.RMSNorm(mx.nd.array(x), mx.nd.array(g), eps=1e-5).asnumpy()
    # the same float32 formula on both sides: rounding of one division
    np.testing.assert_allclose(got, np.asarray(ref.rms_norm(x, g, 1e-5)),
                               rtol=1e-6, atol=1e-6)
    s = mx.sym.RMSNorm(mx.sym.Variable("data"), eps=1e-5, name="n")
    assert s.list_arguments() == ["data", "n_gamma"]
    assert s.infer_shape(data=(3, 5, 32))[0] == [(3, 5, 32), (32,)]


def test_rotary_embedding_takes_positions_as_data():
    rs = np.random.RandomState(2)
    x = rs.randn(2, 4, 6, 16).astype("f")
    pos = np.array([[0, 1, 2, 3, 4, 5], [40, 41, 7, 3, 2, 63]], "f")
    got = mx.nd.RotaryEmbedding(mx.nd.array(x), mx.nd.array(pos),
                                base=10000.0).asnumpy()
    for b in range(2):  # the reference rotates one sequence at a time
        want = np.asarray(ref.rope(jnp.asarray(x[b]), jnp.asarray(pos[b]),
                                   10000.0))
        # sine and cosine of the same float32 angles: a few ulp
        np.testing.assert_allclose(got[b], want, rtol=1e-5, atol=1e-6)
    # position 0 rotates by nothing
    np.testing.assert_array_equal(got[0, :, 0], x[0, :, 0])
    s = mx.sym.RotaryEmbedding(mx.sym.Variable("q"), mx.sym.Variable("p"))
    assert s.infer_shape(q=(2, 4, 6, 16))[0] == [(2, 4, 6, 16), (2, 6)]


def _moe_case(seed, n=24, d=16, e=8, f=8):
    rs = np.random.RandomState(seed)
    return (rs.randn(n, d).astype("f"), rs.randn(e, d).astype("f") * 0.5,
            rs.randn(e, d, f).astype("f") * 0.3,
            rs.randn(e, d, f).astype("f") * 0.3,
            rs.randn(e, f, d).astype("f") * 0.3)


def _moe(x, router, gate, up, down, k):
    out = mx.nd.MoEFeedForward(
        *(mx.nd.array(a) for a in (x, router, gate, up, down)),
        num_experts=router.shape[0], num_hidden=gate.shape[2],
        num_experts_per_tok=k)
    return out[0].asnumpy(), out[1].asnumpy()


def _moe_token_loop(x, router, gate, up, down, k):
    """The expert sum spelled token by token in numpy: softmax over all
    experts, the k largest with ties to the lower index, no renormalising."""
    y = np.zeros_like(x)
    for t, h in enumerate(x):
        z = h @ router.T
        p = np.exp(z - z.max())
        p /= p.sum()
        for e in sorted(range(len(p)), key=lambda i: (-p[i], i))[:k]:
            a = h @ gate[e]
            y[t] += p[e] * (((a / (1 + np.exp(-a))) * (h @ up[e])) @ down[e])
    return y


@pytest.mark.parametrize("case", ["random", "tie", "idle_expert"])
def test_moe_feed_forward_matches_the_reference(case):
    x, router, gate, up, down = _moe_case(3)
    k = 2
    if case == "tie":
        # three experts with the SAME router row tie for every token; two of
        # them fit into the top 2 wherever they lead: the lower indices win
        router[4] = router[6] = router[1]
    if case == "idle_expert":
        # one expert every token scores far below the rest receives no row:
        # an empty group in the grouped matmul
        x = np.abs(x)
        router[5] = -4.0
    y, load = _moe(x, router, gate, up, down, k)
    want = np.asarray(ref.moe(jnp.asarray(x), router, gate, up, down, k))
    # float32 both sides; the program sums a token's k experts, the
    # reference all 8 with zeros: order of summation only
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(y, _moe_token_loop(x, router, gate, up, down,
                                                  k), rtol=1e-4, atol=1e-5)
    assert load.shape == (8,) and load.sum() == x.shape[0] * k
    if case == "tie":
        assert load[6] <= load[4] <= load[1] and load[1] > 0
        assert load[6] == 0     # never among the first two of three equals
    if case == "idle_expert":
        assert load[5] == 0


# ------------------------------------- (b) prefill, then decode through the pool
def _admit_and_step(dec, prompts, steps):
    """Admit the prompts, then ``steps`` greedy steps with both lanes in
    one dispatch. Returns per prompt (all tokens, the 1 + steps logits)."""
    seqs, rows, toks = [], [], [list(p) for p in prompts]
    for p in prompts:
        sid, lg = dec.admit(np.asarray(p, np.float32))
        seqs.append(sid)
        rows.append([lg])
    for _ in range(steps):
        feed = {}
        for j, sid in enumerate(seqs):
            toks[j].append(int(np.argmax(rows[j][-1])))
            feed[sid] = toks[j][-1]
        out = dec.step(feed)
        for j, sid in enumerate(seqs):
            rows[j].append(out[sid])
    for sid in seqs:
        dec.retire(sid)
    return [(np.asarray(t), np.stack(r)) for t, r in zip(toks, rows)]


@pytest.mark.parametrize("dtype,tol", [("float32", F32_TOL),
                                       ("bfloat16", BF16_TOL)])
def test_admit_then_steps_match_the_reference_forward(dtype, tol):
    params = _weights(dtype)
    dec = _decoder(params, dtype)
    rs = np.random.RandomState(5)
    prompts = [rs.randint(257, 600, size=n) for n in (20, 33)]
    for toks, got in _admit_and_step(dec, prompts, steps=6):
        assert got.dtype == np.float32
        want = np.asarray(ref.logits(params, jnp.asarray(toks), CFG))[-7:]
        err = _rel_l2(got, want)
        assert err.max() <= tol, (dtype, err)
    # the pool keeps the weights' type across steps; everything else the
    # host writes stays float32
    args = dec._dec_exe.arg_dict
    assert str(args["kv_k_0"].dtype) == str(args["kv_v_1"].dtype) == dtype
    assert str(args["step_in"].dtype) == "float32"    # the ONE host input
    assert dec.stats()["pages_in_use"] == 0


def test_a_lane_is_bounded_by_max_len_alone():
    dec = _decoder(_weights("float32"), "float32")
    assert dec.pos_len is None
    sid, lg = dec.admit(np.arange(1, 64, dtype=np.float32))    # 63 of 64
    dec.step({sid: int(np.argmax(lg))})                        # position 63
    with pytest.raises(MXNetError, match="max_len 64"):
        dec.step({sid: 1})


# ----------------------------------------------------- (c) routing load, counted
def test_moe_load_counts_every_position_and_the_counters_follow(tm):
    dec = _decoder(_weights("float32"), "float32")
    dec.warmup()
    before = dict(tm.counters())
    sid, _ = dec.admit(np.arange(300, 330, dtype=np.float32))
    pf = dec._pf_cache.executable(dec._prefill_shapes())
    load = pf.outputs[1 + 2 * CFG["num_layers"]].asnumpy()
    assert load.shape == (CFG["num_layers"], CFG["num_experts"])
    # padding included: the prefill computes the whole bucket
    per_layer = dec.prefill_len * CFG["num_experts_per_tok"]
    assert (load.sum(axis=1) == per_layer).all()
    now = tm.counters()
    grew = lambda n: now.get(n, 0) - before.get(n, 0)
    assert grew("serving.moe.assignments") == CFG["num_layers"] * per_layer
    assert grew("serving.moe.max_expert_assignments") \
        == int(load.max(axis=1).sum())
    dec.retire(sid)


def test_moe_counters_are_absent_with_telemetry_off():
    telemetry.reset()
    assert not telemetry.enabled()
    dec = _decoder(_weights("float32"), "float32")
    sid, _ = dec.admit(np.arange(300, 330, dtype=np.float32))
    dec.retire(sid)
    assert not [n for n in telemetry.counters() if "moe" in n]


# ------------------------------------------------- (d) what is not built yet
def test_unported_entry_points_refuse_the_architecture():
    params = _weights("float32")
    nd = {k: mx.nd.NDArray(v) for k, v in params.items()}
    refusal = "not built for arch 'olmoe' yet"
    for build in (tf.get_symbol, tf.get_symbol_mt, tf.get_chunk_symbol):
        with pytest.raises(MXNetError, match=refusal):
            build(**CFG)
    with pytest.raises(MXNetError, match=refusal):
        PagedKVDecoder(nd, prefix_cache=True, **SERVE, **CFG)
    dec = _decoder(params, "float32")
    sid, lg = dec.admit(np.arange(1, 9, dtype=np.float32))
    for call in (lambda: dec.verify_chunk(sid, [1, 2]),
                 lambda: dec.step_megastep({sid: 1}, k=2),
                 lambda: dec._chunk_for(4)):
        with pytest.raises(MXNetError, match=refusal):
            call()
    dec.step({sid: int(np.argmax(lg))})       # the lane is still usable
    with pytest.raises(MXNetError, match="unknown arch"):
        PagedKVDecoder(nd, **SERVE, **dict(CFG, arch="llama"))


def test_default_model_key_names_the_architecture():
    olmoe = _decoder(_weights("float32"), "float32")
    assert olmoe._pf_cache._model_key.endswith("-olmoe-prefill")
    assert olmoe._dec_cache._model_key.endswith("-olmoe-decode")


# ------------------------------ (e) token ids are float32 whatever the weights
def test_token_ids_above_256_survive_bfloat16_weights():
    params = _weights("bfloat16")
    dec = _decoder(params, "bfloat16")
    rows = {}
    for tok in (256, 257, 599):   # 257 and 599 are no bfloat16 numbers
        sid, rows[tok] = dec.admit(np.asarray([300, tok], np.float32))
        dec.retire(sid)
        want = np.asarray(ref.logits(params, jnp.asarray([300, tok]),
                                     CFG))[-1]
        assert _rel_l2(rows[tok], want) <= BF16_TOL
    # an id rounded to bfloat16 would have read row 256 for 257
    assert _rel_l2(rows[257], rows[256]) > 10 * BF16_TOL


# ------------------------------------ (f) the weights are held once, not thrice
def test_both_executables_hold_the_callers_weight_buffers():
    params = _weights("bfloat16")
    dec = _decoder(params, "bfloat16")
    dec.warmup()
    pf = dec._pf_cache.executable(dec._prefill_shapes())
    for exe in (pf, dec._dec_exe):
        for name, arr in params.items():
            held = exe.arg_dict[name]._jax()
            assert held.dtype == jnp.bfloat16, name
            assert held.unsafe_buffer_pointer() \
                == arr.unsafe_buffer_pointer(), name
