"""The Granite 4.0-H hybrid block (Mamba2Scan, Mamba2Step, grouped-query
attention; ``arch="granite_hybrid"`` of models/transformer.py and
serving.PagedKVDecoder) against the benchmark's plain reference,
benchmark/reference/granite_hybrid_decoder.py, whose recurrence runs one
position after the other, on seeded weights at small sizes. Every tolerance
says where it comes from.
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
from mxnet_tpu.ops import attention as att_ops
from mxnet_tpu.ops import ssm
from mxnet_tpu.serving import PagedKVDecoder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(ROOT, "benchmark", "reference",
                        "granite_hybrid_decoder.py")
    spec = importlib.util.spec_from_file_location("granite_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()

# vocabulary above 256 on purpose: bfloat16 holds whole numbers to 256 only
CFG = dict(arch="granite_hybrid", vocab_size=600, num_layers=4, num_heads=4,
           num_kv_heads=2, head_dim=16, model_dim=64, ffn_dim=96,
           layer_types=["mamba", "attention", "mamba", "mamba"],
           mamba_heads=8, mamba_head_dim=16, mamba_state=16, mamba_conv=4,
           mamba_chunk=8, embedding_multiplier=12.0,
           attention_multiplier=0.0625, residual_multiplier=0.22,
           logits_scaling=8.0, rms_eps=1e-5)
SERVE = dict(max_len=64, prefill_len=32, page_size=8, lanes=4)
CORE = dict(num_heads=4, head_dim=8, state_size=16, conv_kernel=4)

# float32 on both sides on the CPU: what is left is the order of the sums
# (a chunk's masked matrix product against the recurrence, the pool read
# against fused attention), a few ulp on values of order 1; the runs read
# 2e-7 to 3e-7
F32_TOL = 1e-5
# bfloat16 weights, residual stream and pool against the float32 reference
# over the same (bfloat16-valued) weights: every stored activation is rounded
# to 8 bits of mantissa (2^-9 relative), some ten roundings a layer; four
# layers read 5e-3 to 8e-3 worst row and a float32 run of the same code 3e-7,
# so 3e-2 is storage rounding and nothing coarser (one int8 step is 2^-4)
BF16_TOL = 3e-2


def _weights(dtype, seed=0, scale=0.1):
    """The configuration's kinds of draw at a small size: normal matrices,
    unit gammas and D, A in [1, 16], dt in [1e-3, 1e-1] through the inverse
    softplus, convolution weights and bias in (-0.5, 0.5)."""
    rs = np.random.RandomState(seed)
    out = {}
    for name, shape in sorted(tf.param_shapes(**CFG).items()):
        if name.endswith(("gamma", "_D")):
            v = np.ones(shape, "f")
        elif name.endswith("A_log"):
            v = np.log(rs.uniform(1, 16, shape))
        elif name.endswith("dt_bias"):
            v = np.log(np.expm1(np.exp(rs.uniform(np.log(1e-3), np.log(1e-1),
                                                  shape))))
        elif "_conv_" in name:
            v = rs.uniform(-0.5, 0.5, shape)
        else:
            v = rs.randn(*shape) * scale
        out[name] = jnp.asarray(v, jnp.float32).astype(dtype)
    return out


def _decoder(params, dtype="float32", **kw):
    return PagedKVDecoder({k: mx.nd.NDArray(v) for k, v in params.items()},
                          dtype=dtype, **{**SERVE, **kw}, **CFG)


def _rel_l2(got, want):
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def _state(dec):
    """The decoder's per-lane state buffers, copied to the host, by name (a
    view would follow the device's buffer into its next use)."""
    return {name: np.array(dec._dec_exe.arg_dict[name]._jax())
            for name, kind, _ in dec._cache if kind == "row"}


@pytest.fixture
def tm():
    telemetry.reset()
    saved = telemetry.current_override()
    telemetry.set_mode("trace")
    yield telemetry
    telemetry.set_mode(saved)
    telemetry.reset()


# ------------------------------------------------------------- (a) operators
def _core_inputs(t, seed=0):
    h, p, n, k = (CORE[x] for x in ("num_heads", "head_dim", "state_size",
                                    "conv_kernel"))
    c = h * p + 2 * n
    rs = np.random.RandomState(seed)
    dt_bias = np.log(np.expm1(np.exp(rs.uniform(np.log(1e-3), np.log(1e-1),
                                                h))))
    return dict(xbc=rs.randn(1, t, c).astype("f"),
                dt=rs.randn(1, t, h).astype("f"),
                w=rs.uniform(-.5, .5, (c, k)).astype("f"),
                b=rs.uniform(-.5, .5, (c,)).astype("f"),
                dt_bias=dt_bias.astype("f"),
                a_log=np.log(rs.uniform(1, 16, h)).astype("f"),
                d=rs.randn(h).astype("f"))


def _sequential(v, length):
    """The reference's convolution and recurrence over the first ``length``
    positions: (y (length, H*P), state (H, P, N))."""
    h, p, n = CORE["num_heads"], CORE["head_dim"], CORE["state_size"]
    conv = ref.causal_conv(jnp.asarray(v["xbc"][0, :length]), v["w"], v["b"])
    x, b, c = jnp.split(conv, [h * p, h * p + n], axis=-1)
    dt = jax.nn.softplus(v["dt"][0, :length] + v["dt_bias"])
    y, state = ref.recurrence(x.reshape(length, h, p), dt,
                              -jnp.exp(v["a_log"]), b, c, v["d"])
    return np.asarray(y).reshape(length, -1), np.asarray(state)


@pytest.mark.parametrize("length", [1, 3, 7, 8, 9, 16, 17, 20])
def test_chunked_scan_is_the_sequential_recurrence(length):
    """``Mamba2Scan`` over a 20-position bucket in chunks of 8, the length as
    data: below a chunk, at its edge, across one and two edges, the whole
    bucket. Outputs before the length, the state at the length and the last
    three pre-activation columns are the reference's; float32 both sides."""
    v = _core_inputs(20)
    y, state, conv = ssm._mamba2_scan(
        dict(CORE, chunk_size=8), jnp.asarray(v["xbc"]), jnp.asarray(v["dt"]),
        v["w"], v["b"], v["dt_bias"], v["a_log"], v["d"],
        jnp.asarray([[float(length)]]))
    want_y, want_state = _sequential(v, length)
    np.testing.assert_allclose(np.asarray(y[0, :length]), want_y,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(state[0]), want_state,
                               rtol=1e-5, atol=1e-7)
    k = CORE["conv_kernel"]
    padded = np.concatenate([np.zeros((k - 1, v["xbc"].shape[-1]), "f"),
                             v["xbc"][0]])
    assert np.array_equal(np.asarray(conv[0]), padded[length:length + k - 1])
    assert state.dtype == conv.dtype == jnp.float32


@pytest.mark.parametrize("length", [2, 8, 13])
def test_one_token_update_continues_the_scan(length):
    """``Mamba2Step`` from the state ``Mamba2Scan`` left at ``length`` is
    position ``length`` of the sequential recurrence; a row that rides along
    (negative ``stepped``) gets its state back bit for bit."""
    v = _core_inputs(20, seed=1)
    _, state, conv = ssm._mamba2_scan(
        dict(CORE, chunk_size=8), jnp.asarray(v["xbc"]), jnp.asarray(v["dt"]),
        v["w"], v["b"], v["dt_bias"], v["a_log"], v["d"],
        jnp.asarray([[float(length)]]))
    # two rows with the same state: row 0 steps, row 1 rides along
    two = lambda a: jnp.concatenate([a, a])
    y, new_state, new_conv = ssm._mamba2_step(
        CORE, two(jnp.asarray(v["xbc"][:, length])),
        two(jnp.asarray(v["dt"][:, length])), v["w"], v["b"], v["dt_bias"],
        v["a_log"], v["d"], two(state), two(conv),
        jnp.asarray([[5.0], [-1.0]]))
    want_y, want_state = _sequential(v, length + 1)
    np.testing.assert_allclose(np.asarray(y[0]), want_y[-1],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(new_state[0]), want_state,
                               rtol=1e-5, atol=1e-7)
    assert np.array_equal(np.asarray(new_conv[0, -1]), v["xbc"][0, length])
    assert np.array_equal(np.asarray(new_conv[0, :-1]), np.asarray(conv[0, 1:]))
    assert np.array_equal(np.asarray(new_state[1]), np.asarray(state[0]))
    assert np.array_equal(np.asarray(new_conv[1]), np.asarray(conv[0]))


def test_mamba_operators_infer_their_weights_and_state_from_the_data():
    """Shape rules: the weights of both operators, the scan's ``length`` and
    the step's state follow from the data's shape and the sizes."""
    sizes = dict(num_heads=4, head_dim=8, state_size=16)
    weights = [mx.sym.Variable(n) for n in ("w", "b", "dtb", "alog", "d")]
    scan = mx.sym.Mamba2Scan(mx.sym.Variable("x"), mx.sym.Variable("dt"),
                             *weights, mx.sym.Variable("length"),
                             chunk_size=8, **sizes)
    args, outs, _ = scan.infer_shape(x=(2, 20, 64))
    assert dict(zip(scan.list_arguments(), args)) == {
        "x": (2, 20, 64), "dt": (2, 20, 4), "w": (64, 4), "b": (64,),
        "dtb": (4,), "alog": (4,), "d": (4,), "length": (2, 1)}
    assert outs == [(2, 20, 32), (2, 4, 8, 16), (2, 3, 64)]
    step = mx.sym.Mamba2Step(mx.sym.Variable("x"), mx.sym.Variable("dt"),
                             *weights, mx.sym.Variable("s"),
                             mx.sym.Variable("c"), mx.sym.Variable("go"),
                             **sizes)
    args, outs, _ = step.infer_shape(x=(5, 64))
    got = dict(zip(step.list_arguments(), args))
    assert (got["dt"], got["s"], got["c"], got["go"]) == (
        (5, 4), (5, 4, 8, 16), (5, 3, 64), (5, 1))
    assert outs == [(5, 32), (5, 4, 8, 16), (5, 3, 64)]


@pytest.mark.parametrize("op", ["MultiHeadAttention", "KVPoolAttention"])
def test_grouped_heads_equal_repeated_heads(op):
    """Fewer key/value heads than query heads, grouped inside the
    contraction, against the same operator over the keys and values repeated
    to one a query head (``repeat_kv``): key/value head j serves query heads
    j*g .. (j+1)*g - 1. Float32 on the CPU, the same sums in another order."""
    rs = np.random.RandomState(3)
    h, hkv, t, s, d = 8, 2, 5, 12, 16
    if op == "MultiHeadAttention":
        q = jnp.asarray(rs.randn(2, h, t, d), jnp.float32)
        k, v = (jnp.asarray(rs.randn(2, hkv, t, d), jnp.float32)
                for _ in range(2))
        attrs = {"causal": True, "scale": 0.0625}
        got = att_ops._multi_head_attention(attrs, q, k, v)
        want = att_ops._multi_head_attention(
            attrs, q, jnp.repeat(k, h // hkv, axis=1),
            jnp.repeat(v, h // hkv, axis=1))
    else:
        q = jnp.asarray(rs.randn(t, h, d), jnp.float32)
        k, v = (jnp.asarray(rs.randn(hkv, s, d), jnp.float32)
                for _ in range(2))
        mask = jnp.where(jnp.asarray(rs.rand(t, s) < 0.6), 0.0, att_ops._NEG)
        attrs = {"scale": 0.0625}
        got = att_ops._kv_pool_attention(attrs, q, k, v, mask)
        want = att_ops._kv_pool_attention(
            attrs, q, jnp.repeat(k, h // hkv, axis=0),
            jnp.repeat(v, h // hkv, axis=0), mask)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    with pytest.raises(MXNetError, match="do not divide"):
        (att_ops._multi_head_attention(attrs, q, k[:, :1].repeat(3, 1),
                                       v[:, :1].repeat(3, 1))
         if op == "MultiHeadAttention" else
         att_ops._kv_pool_attention(attrs, q, k[:1].repeat(3, 0),
                                    v[:1].repeat(3, 0), mask))


def test_equal_heads_are_the_group_of_one():
    """One body serves both head counts: with as many key/value heads as
    query heads the group axis has size 1 and the contractions are the ones
    spelled without it, bit for bit."""
    rs = np.random.RandomState(5)
    h, t, s, d = 4, 6, 10, 16
    q, k, v = (jnp.asarray(rs.randn(2, h, t, d), jnp.float32)
               for _ in range(3))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.25
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
    got = att_ops._multi_head_attention({"causal": True, "scale": -1.0},
                                        q, k, v)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    rows = q[0].transpose(1, 0, 2)
    pool_k, pool_v = (jnp.asarray(rs.randn(h, s, d), jnp.float32)
                      for _ in range(2))
    mask = jnp.where(jnp.asarray(rs.rand(t, s) < 0.6), 0.0, att_ops._NEG)
    p = jax.nn.softmax(jnp.einsum("rhd,hsd->rhs", rows, pool_k) * 0.25
                       + mask[:, None, :], axis=-1)
    want = jnp.einsum("rhs,hsd->rhd", p, pool_v)
    got = att_ops._kv_pool_attention({"scale": -1.0}, rows, pool_k, pool_v,
                                     mask)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------ (b) prefill, then decode: the cache
@pytest.mark.parametrize("dtype,tol", [("float32", F32_TOL),
                                       ("bfloat16", BF16_TOL)])
@pytest.mark.parametrize("length", [3, 9, 32])
def test_admit_then_steps_agree_with_the_full_forward(dtype, tol, length):
    """The logits ``admit`` returns and those of 12 single decode steps
    through the cache (KV pages of the attention layer, recurrent state and
    convolution columns of the three Mamba layers) against the reference's
    full forward over the whole sequence, row by row: a prompt inside the
    first chunk, one across a chunk edge, and the whole bucket."""
    params = _weights(dtype)
    dec = _decoder(params, dtype)
    prompt = np.random.RandomState(length).randint(1, CFG["vocab_size"],
                                                   length)
    seq, logits = dec.admit(prompt.astype(np.float32))
    toks, got = list(prompt), [logits]
    for _ in range(12):
        toks.append(int(np.argmax(got[-1])))
        got.append(dec.step({seq: toks[-1]})[seq])
    state = dec.lane_state(seq)
    assert list(dec.lane_state(seq, ("conv_state_0",))) == ["conv_state_0"]
    dec.retire(seq)
    with pytest.raises(MXNetError, match="unknown seq_id"):
        dec.lane_state(seq)
    want = np.asarray(ref.logits(params, jnp.asarray(toks, jnp.int32),
                                 CFG))[-13:]
    assert np.stack(got).dtype == np.float32
    assert _rel_l2(np.stack(got), want).max() < tol
    # what the lane carries for the first layer is the reference's state
    # after the last token fed; the rows of the other lanes are not in it
    assert sorted(state) == sorted(n for n, kind, _ in dec._cache
                                   if kind == "row")
    for name, ours in zip(("ssm_state_0", "conv_state_0"),
                          ref.first_mixer_state(
                              params, jnp.asarray(toks, jnp.int32), CFG)):
        assert state[name].shape == ours.shape
        assert _rel_l2(np.asarray(state[name]).reshape(1, -1),
                       np.asarray(ours).reshape(1, -1)).max() < tol
    # the state is float32 whatever the weights are; the pool is the weights'
    types = {name: str(dec._dec_exe.arg_dict[name].dtype)
             for name, _, _ in dec._cache}
    assert types["ssm_state_0"] == types["conv_state_3"] == "float32"
    assert types["kv_k_1"] == types["kv_v_1"] == dtype


def test_padding_of_the_bucket_never_reaches_the_state():
    """The same prompt, the bucket's padding filled with two different
    things: the prefill's one row of logits (the prompt's last real row),
    the state at the length and the convolution columns are the same bit for
    bit (positions past the length get dt = 0, the columns a slice that
    starts at the length); the padding's K and V are not."""
    params = _weights("float32")
    sym = tf.get_prefill_symbol(prefill_len=32, **CFG)
    names = sym.list_arguments()
    length = 11
    prompt = np.random.RandomState(5).randint(1, CFG["vocab_size"], length)

    def run(fill):
        data = np.full((1, 32), fill, np.float32)
        data[0, :length] = prompt
        exe = sym.bind(mx.cpu(), {
            n: mx.nd.array(data) if n == "data"
            else mx.nd.array(np.full((1, 1), length, np.float32))
            if n == "length" else mx.nd.NDArray(params[n]) for n in names},
            grad_req="null")
        exe.forward(is_train=False)
        return [o.asnumpy() for o in exe.outputs]

    a, b = run(0), run(417)
    assert a[0].shape == (1, CFG["vocab_size"]) and np.array_equal(a[0], b[0])
    cache = tf.decode_cache(**CFG)
    for (name, kind, _), x, y in zip(cache, a[1:], b[1:]):
        if kind == "row":
            assert np.array_equal(x, y), name
        else:   # K and V of the real positions
            assert np.array_equal(x[:, :, :length], y[:, :, :length]), name
            assert not np.array_equal(x[:, :, length:], y[:, :, length:]), name


def test_lanes_stepped_alternately_leave_each_others_state_alone():
    """Two sequences stepped in turn: the lane that rides along keeps every
    state buffer bit for bit, and each sequence's logits are those of the
    same sequence decoded alone. The tokens fed are drawn, not sampled: a
    tiny model's greedy choice repeats, and the first layer's convolution
    columns, which see the token alone, would then stand still."""
    params = _weights("float32")
    rs = np.random.RandomState(9)
    prompts = [rs.randint(1, CFG["vocab_size"], n) for n in (5, 12)]
    fed = rs.randint(1, CFG["vocab_size"], (2, 4))

    def alone(prompt, tokens):
        dec = _decoder(params)
        seq, logits = dec.admit(prompt.astype(np.float32))
        return [logits] + [dec.step({seq: int(t)})[seq] for t in tokens]

    want = [alone(p, t) for p, t in zip(prompts, fed)]
    dec = _decoder(params)
    seqs, got = [], []
    for p in prompts:
        seq, logits = dec.admit(p.astype(np.float32))
        seqs.append(seq)
        got.append([logits])
    lanes = [dec._seq_lane[s] for s in seqs]
    for step in range(4):
        for me, other in ((0, 1), (1, 0)):
            before = _state(dec)
            got[me].append(dec.step(
                {seqs[me]: int(fed[me, step])})[seqs[me]])
            after = _state(dec)
            for name in before:
                assert np.array_equal(before[name][lanes[other]],
                                      after[name][lanes[other]]), name
                assert not np.array_equal(before[name][lanes[me]],
                                          after[name][lanes[me]]), name
    for mine, theirs in zip(got, want):
        # the same program on the same rows: another lane's rows change
        # nothing in this one's arithmetic
        assert np.array_equal(np.stack(mine), np.stack(theirs))


def test_warmup_can_release_the_warm_dispatchs_outputs():
    """The warm dispatch leaves a copy of the cache in the executable's
    outputs; ``warmup(release_outputs=True)`` drops it and changes nothing
    a caller sees."""
    params, prompt = _weights("float32"), np.arange(1, 8, dtype=np.float32)
    got = []
    for release in (False, True):
        dec = _decoder(params)
        dec.warmup(release_outputs=release)
        held = dec._dec_exe.outputs
        assert (held == []) is release and (dec._dec_exe.output_dict == {}) \
            is release
        seq, logits = dec.admit(prompt)
        got.append((logits, dec.step({seq: 3})[seq]))
        assert len(dec._dec_exe.outputs) > len(dec._cache)  # refilled
    for kept, released in zip(*got):
        assert np.array_equal(kept, released)


def test_admission_and_steps_are_counted_and_spanned(tm):
    """The hand-off of the state rows is a span inside the admission, the
    resident state a gauge, and a step adds its lanes' contexts."""
    dec = _decoder(_weights("float32"))
    dec.warmup()
    state_bytes = 4 * SERVE["lanes"] * 3 * (8 * 16 * 16 + 3 * (128 + 32))
    assert tm.gauge("serving.state_bytes").value == state_bytes
    tm.clear_events()
    c0 = tm.counters()
    a, la = dec.admit(np.arange(1, 6, dtype=np.float32))
    b, lb = dec.admit(np.arange(1, 10, dtype=np.float32))
    dec.step({a: int(np.argmax(la)), b: int(np.argmax(lb))})
    dec.step({a: 7})
    moved = {k: v - c0.get(k, 0) for k, v in tm.counters().items()}
    assert moved["serving.admit_scatter_dispatches"] == 2
    assert moved["serving.paged_steps"] == 2
    assert moved["serving.decode_tokens"] == 3
    # position + 1 of every stepped lane: (5 + 1) + (9 + 1), then 6 + 1
    assert moved["serving.step_context_tokens"] == 6 + 10 + 7
    spans = {attrs["id"]: (name, attrs.get("parent"), attrs)
             for name, _t0, _dur, _tid, attrs in tm.drain_events()
             if "id" in attrs}
    states = [v for v in spans.values() if v[0] == "serving.admit.state"]
    assert len(states) == 2 and states[0][2]["buffers"] == 6
    for _, parent, _ in states:
        chain = []
        while parent in spans:
            chain.append(spans[parent][0])
            parent = spans[parent][1]
        assert chain == ["serving.admit.scatter", "serving.paged_admit"]


# ------------------------------------------------------- (c) what is not ported
@pytest.mark.parametrize("entry", ["fork", "rollback", "verify_chunk",
                                   "step_megastep", "prefix_cache",
                                   "get_symbol", "get_symbol_mt",
                                   "get_chunk_symbol"])
def test_unported_entry_points_refuse(entry):
    """A recurrent state is one row, overwritten at every token: sharing
    pages says nothing of it and going back needs a snapshot nobody keeps
    yet; the chunk and megastep programs know the Vaswani block only."""
    if entry.startswith("get_"):
        with pytest.raises(MXNetError, match="not built for arch "
                           "'granite_hybrid' yet"):
            getattr(tf, entry)(arch="granite_hybrid")
        return
    params = {k: mx.nd.NDArray(v) for k, v in _weights("float32").items()}
    if entry == "prefix_cache":
        with pytest.raises(MXNetError, match="prefix_cache=True is not built "
                           "for arch 'granite_hybrid' yet"):
            PagedKVDecoder(params, prefix_cache=True, **SERVE, **CFG)
        return
    dec = PagedKVDecoder(params, **SERVE, **CFG)
    seq, _ = dec.admit(np.arange(1, 6, dtype=np.float32))
    call = {"fork": lambda: dec.fork(seq),
            "rollback": lambda: dec.rollback(seq, 2),
            "verify_chunk": lambda: dec.verify_chunk(seq, [1, 2]),
            "step_megastep": lambda: dec.step_megastep({seq: 1}, k=2)}[entry]
    with pytest.raises(MXNetError, match="not built for arch "
                       "'granite_hybrid' yet"):
        call()
    # nothing moved: the sequence still steps
    assert dec.position(seq) == 5 and dec.step({seq: 1})[seq].shape == (600,)
