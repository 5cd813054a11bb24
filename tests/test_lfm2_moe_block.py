"""The LFM2-MoE block (``arch="lfm2_moe"`` of models/transformer.py and
serving.PagedKVDecoder: ``GatedShortConv`` / ``GatedShortConvStep``,
``_lfm2_moe_layer`` with per-head q/k norms under grouped queries, sigmoid
experts beside per-lane rows) against the benchmark's plain reference,
benchmark/reference/lfm2_moe_decoder.py, on seeded weights at small sizes:
2 dense + 4 expert layers, 4 conv mixers and 2 attention mixers (4 query heads
over 2 key/value heads), 8 experts, 3 a token. Every tolerance says where it
comes from.
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
from mxnet_tpu.ops import shortconv
from mxnet_tpu.serving import PagedKVDecoder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reference():
    """A fresh copy of the reference module: a test may bend one of its
    functions (``FAULTS``) without any other test seeing it."""
    path = os.path.join(ROOT, "benchmark", "reference", "lfm2_moe_decoder.py")
    spec = importlib.util.spec_from_file_location("lfm2_moe_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = reference()

# vocabulary above 256 on purpose: bfloat16 holds whole numbers to 256 only
CFG = dict(arch="lfm2_moe", vocab_size=600, num_layers=6, num_heads=4,
           num_kv_heads=2, head_dim=16, model_dim=64, ffn_dim=96,
           moe_ffn_dim=32, num_experts=8, num_experts_per_tok=3,
           first_dense_layers=2,
           layer_types=["conv", "conv", "full_attention", "conv", "conv",
                        "full_attention"],
           conv_kernel=3, rope_theta=1e6, rms_eps=1e-5,
           routed_scaling_factor=1.0, norm_topk_prob=True)
SERVE = dict(max_len=64, prefill_len=32, page_size=8, lanes=4)

# float32 on both sides on the CPU: what is left is the order of the sums
# (grouped matmul against a loop over experts, the pool's contraction against
# the full softmax) and the renormalisation's 1e-20 against the published
# 1e-6 (5e-7 relative on a sum of three scores); the runs read 2e-7 to 4e-7
F32_TOL = 1e-4
# bfloat16 weights, activations and pools against the float32 reference over
# the same (bfloat16-valued) weights: every stored activation is rounded to
# 8 bits of mantissa, some dozen roundings a layer; six layers read 5e-3 to
# 1.5e-2 on a row whose experts are the reference's and a float32 run of the
# same code 3e-7, so 4e-2 is storage rounding and nothing coarser. It holds a
# prompt's LOWER-QUARTILE row (of 7: the second smallest), in the manner of
# the benchmark's check (drivers/paged_closed_loop_lfm2.py holds the third
# smallest of 17): where a token's third and fourth biased
# score lie within the rounding, the program and the reference choose another
# expert and that row reads 0.07 to 0.15 (three rows in 35 at seed 0)
BF16_TOL = 4e-2
# the first layer's row: its input is the embedding, so one bfloat16 rounding
# of the normed input and the projection's float32 accumulator are all that
# stand between the two; worst feature over the columns' rms reads 4e-3 to
# 9e-3, and 0 in float32 (elementwise float32 on both sides)
BF16_ROW_TOL = 3e-2


def _lower_quartile(err):
    return np.sort(err)[-(-len(err) // 4) - 1]


def _weights(dtype="float32", seed=0, cfg=CFG):
    """N(0, 0.1) matrices, a unit-variance embedding, taps U(-0.5, 0.5), a
    selection bias N(0, 0.5): large enough beside sigmoid scores near 0.5
    that selecting by s + b and weighing by s + b are told apart."""
    rs = np.random.RandomState(seed)
    out = {}
    for name, shape in sorted(tf.param_shapes(**cfg).items()):
        if name.endswith("gamma"):
            v = np.ones(shape, "f")
        elif name.endswith("conv_weight"):
            v = rs.uniform(-.5, .5, shape).astype("f")
        else:
            v = rs.randn(*shape).astype("f") * (
                1.0 if name == "embed_weight"
                else 0.5 if name.endswith("router_bias") else 0.1)
        out[name] = jnp.asarray(v).astype(dtype)
    return out


def _decoder(params, dtype="float32", cfg=CFG, **kw):
    return PagedKVDecoder({k: mx.nd.NDArray(v) for k, v in params.items()},
                          dtype=dtype, **dict(SERVE, **kw), **cfg)


def _rel_l2(got, want):
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def _every_row(exe, rows):
    """The bound prefill's one row of logits at every length up to ``rows``:
    what its head gave over the whole bucket before it narrowed."""
    out = []
    for length in range(1, rows + 1):
        exe.arg_dict["length"][:] = np.full((1, 1), length, "f")
        exe.forward(is_train=False)
        out.append(exe.outputs[0].asnumpy())
    return np.concatenate(out)


def _row_error(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.max(np.abs(got - want)) / np.sqrt(np.mean(np.square(want)))


def _rows(dec):
    """Every per-lane buffer of the cache, copied (a view would follow the
    device's buffer into its next use)."""
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


def _admit_and_step(dec, prompt, steps):
    """Admit, then ``steps`` greedy steps: (all tokens, the 1 + steps logits,
    layer 0's row after the admission, the same after the last step)."""
    seq, logits = dec.admit(np.asarray(prompt, np.float32))
    admitted = np.array(dec.lane_state(seq, ("conv_state_0",))["conv_state_0"])
    toks, got = list(prompt), [np.asarray(logits)]
    for _ in range(steps):
        toks.append(int(np.argmax(got[-1])))
        got.append(np.asarray(dec.step({seq: toks[-1]})[seq]))
    last = np.array(dec.lane_state(seq, ("conv_state_0",))["conv_state_0"])
    dec.retire(seq)
    return np.asarray(toks), np.stack(got), admitted, last


# ------------------------------------------------------------- (a) operators
def _conv_inputs(t, d=16, seed=0):
    rs = np.random.RandomState(seed)
    return (rs.randn(1, t, 3 * d).astype("f"),
            rs.uniform(-.5, .5, (3, d)).astype("f"))


def _conv_reference(bcu, taps, length):
    """The reference's mixer core over the first ``length`` positions:
    (y (length, d), z (length, d))."""
    b, c, u = jnp.split(jnp.asarray(bcu[0, :length]), 3, axis=-1)
    z = b * u
    return np.asarray(c * ref.causal_conv(z, jnp.asarray(taps))), \
        np.asarray(z)


@pytest.mark.parametrize("length", [1, 2, 3, 7, 20])
def test_prefill_convolution_takes_its_state_at_the_real_end(length):
    """``GatedShortConv`` over a 20-position bucket, the length as data: one,
    two and three positions (fewer than, as many as and more than the kept
    columns), inside the bucket and the whole of it. Outputs before the
    length are the reference's, the state is the last two gated columns
    before the LENGTH, zeros where the prompt is shorter; float32 both
    sides, the same products in the same order."""
    bcu, taps = _conv_inputs(20)
    y, state = shortconv._gated_short_conv(
        {"kernel": 3}, jnp.asarray(bcu), jnp.asarray(taps),
        jnp.asarray([[float(length)]]))
    want_y, z = _conv_reference(bcu, taps, length)
    np.testing.assert_allclose(np.asarray(y[0, :length]), want_y,
                               rtol=1e-6, atol=1e-7)
    padded = np.concatenate([np.zeros((2, z.shape[-1]), "f"), z])
    assert np.array_equal(np.asarray(state[0]), padded[length:length + 2])
    assert state.dtype == jnp.float32 and state.shape == (1, 2, 16)


@pytest.mark.parametrize("length", [1, 2, 5, 20])
def test_steps_from_a_zero_row_equal_the_prefill(length):
    """``length`` calls of ``GatedShortConvStep`` from a zero row give the
    prefill's outputs position by position and end in its state; a row that
    rides along (negative ``stepped``) gets its state back bit for bit."""
    bcu, taps = _conv_inputs(20, seed=1)
    y, state = shortconv._gated_short_conv(
        {"kernel": 3}, jnp.asarray(bcu), jnp.asarray(taps),
        jnp.asarray([[float(length)]]))
    row = jnp.zeros((2, 2, 16), jnp.float32)    # row 0 steps, row 1 rides
    for t in range(length):
        out, new = shortconv._gated_short_conv_step(
            {"kernel": 3}, jnp.asarray(np.repeat(bcu[:, t], 2, axis=0)),
            jnp.asarray(taps), row, jnp.asarray([[3.0], [-1.0]]))
        assert np.array_equal(np.asarray(new[1]), np.asarray(row[1]))
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(y[0, t]),
                                   rtol=1e-6, atol=1e-7)
        row = new
    assert np.array_equal(np.asarray(row[0]), np.asarray(state[0]))
    assert not np.asarray(row[1]).any()


def test_conv_operators_infer_their_weights_and_state_from_the_data():
    """Shape rules: the taps, the prefill's ``length`` and the step's row
    follow from the data's shape and the kernel; a width that is not three
    blocks is refused."""
    scan = mx.sym.GatedShortConv(mx.sym.Variable("x"), mx.sym.Variable("w"),
                                 mx.sym.Variable("length"), kernel=3)
    args, outs, _ = scan.infer_shape(x=(2, 20, 48))
    assert dict(zip(scan.list_arguments(), args)) == {
        "x": (2, 20, 48), "w": (3, 16), "length": (2, 1)}
    assert outs == [(2, 20, 16), (2, 2, 16)]
    step = mx.sym.GatedShortConvStep(
        mx.sym.Variable("x"), mx.sym.Variable("w"), mx.sym.Variable("c"),
        mx.sym.Variable("go"), kernel=4)
    args, outs, _ = step.infer_shape(x=(5, 48))
    assert dict(zip(step.list_arguments(), args)) == {
        "x": (5, 48), "w": (4, 16), "c": (5, 3, 16), "go": (5, 1)}
    assert outs == [(5, 16), (5, 3, 16)]
    with pytest.raises(MXNetError, match="not three blocks"):
        shortconv._gated_short_conv(
            {"kernel": 3}, jnp.zeros((1, 4, 47)), jnp.zeros((3, 16)),
            jnp.ones((1, 1)))


def test_heads_are_normed_one_by_one_before_the_rotation():
    """One attention layer of the graph against the reference's mixer with
    4 query heads over 2 key/value heads and head norms that are NOT ones:
    the norm is over a head's 16 features (a norm over the whole projected
    vector, as OLMoE's, or after the rotation with these gammas, reads 0.3
    and more), key/value head j serves query heads 2j and 2j + 1."""
    cfg = dict(CFG, num_layers=1, layer_types=["full_attention"],
               first_dense_layers=1)
    params = _weights(cfg=cfg, seed=4)
    rs = np.random.RandomState(5)
    for n in ("layer0_qnorm_gamma", "layer0_knorm_gamma"):
        params[n] = jnp.asarray(rs.uniform(0.5, 2.0, 16).astype("f"))
    toks = rs.randint(1, 600, 12)
    want = np.asarray(ref.logits(params, jnp.asarray(toks), cfg))
    sym = tf.get_prefill_symbol(prefill_len=12, **cfg)
    exe = sym.bind(mx.cpu(), {
        n: mx.nd.array(toks[None].astype("f")) if n == "data"
        else mx.nd.array(np.full((1, 1), 12, "f")) if n == "length"
        else mx.nd.NDArray(params[n]) for n in sym.list_arguments()},
        grad_req="null")
    got = _every_row(exe, 12)
    assert _rel_l2(got, want).max() < F32_TOL
    # the pool's key is the normed, rotated one
    h = ref.rms_norm(params["embed_weight"][toks], params["layer0_ln1_gamma"],
                     1e-5)
    k = (h @ params["layer0_qkv_weight"].T)[:, 64:96].reshape(12, 2, 16)
    k = ref.rope(ref.rms_norm(k.transpose(1, 0, 2),
                              params["layer0_knorm_gamma"], 1e-5),
                 jnp.arange(12), 1e6)
    np.testing.assert_allclose(exe.outputs[1].asnumpy()[0], np.asarray(k),
                               rtol=1e-4, atol=1e-5)

    whole = reference()
    whole.rms_norm = lambda x, gamma, eps: ref.rms_norm(
        x, gamma, eps) if x.ndim != 3 else ref.rms_norm(
        x.transpose(1, 0, 2).reshape(12, -1),
        jnp.tile(gamma, x.shape[0]), eps).reshape(12, -1, 16).transpose(
        1, 0, 2)
    assert _rel_l2(got, np.asarray(
        whole.logits(params, jnp.asarray(toks), cfg))).max() > 0.05


# ------------------------------------------ (b) prefill, then decode: the cache
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("length", [1, 2, 3, 9, 32])
def test_admit_then_steps_agree_with_the_full_forward(dtype, length):
    """The logits ``admit`` returns and those of 6 single decode steps through
    the cache (KV pages of the two attention layers, convolution rows of the
    four conv layers) against the reference's full forward over the whole
    sequence, row by row, and the first layer's row after the admission and
    after the last step against the reference's gated columns at the
    prompt's real end and at the last position: prompts shorter than, as long
    as and longer than the kept columns, and the whole bucket."""
    params = _weights(dtype)
    dec = _decoder(params, dtype)
    prompt = np.random.RandomState(length).randint(1, CFG["vocab_size"],
                                                   length)
    toks, got, admitted, last = _admit_and_step(dec, prompt, 6)
    want = np.asarray(ref.logits(params, jnp.asarray(toks), CFG, last=7))
    assert got.dtype == np.float32
    err = _rel_l2(got, want)
    if dtype == "float32":
        assert err.max() < F32_TOL
    else:
        assert _lower_quartile(err) < BF16_TOL
    row_tol = 1e-6 if dtype == "float32" else BF16_ROW_TOL
    for row, at in ((admitted, length - 1), (last, len(toks) - 1)):
        want_row = ref.first_conv_columns(params, jnp.asarray(toks), CFG, at)
        assert row.shape == want_row.shape == (2, 64)
        assert _row_error(row, want_row) < row_tol
    # the rows are float32 whatever the weights are; the pools the weights'
    types = {name: str(dec._dec_exe.arg_dict[name].dtype)
             for name, _, _ in dec._cache}
    assert types["conv_state_0"] == types["conv_state_4"] == "float32"
    assert types["kv_k_2"] == types["kv_v_5"] == dtype


def _faulty(fault):
    """(reference module with one part wrong, what to do to the weights)."""
    bad, bend = reference(), lambda p: p
    if fault == "weights_in_float8":
        bend = lambda p: {k: v.astype(jnp.float8_e4m3fn).astype(jnp.float32)
                          if k.endswith("_weight") else v
                          for k, v in p.items()}
    elif fault == "break_reference":
        bend = lambda p: dict(p, layer0_conv_in_weight=p[
            "layer0_conv_in_weight"] * 1.25)
    elif fault == "taps_reversed_in_time":
        bad.causal_conv = lambda z, taps: ref.causal_conv(z, taps[::-1])
    elif fault == "input_gate_dropped":
        def gated_columns(h, w_in):
            _, c, u = jnp.split(h @ w_in.astype(jnp.float32).T, 3, axis=-1)
            return u, c
        bad.gated_columns = gated_columns
    elif fault == "output_gate_dropped":
        def gated_columns(h, w_in):
            z, c = ref.gated_columns(h, w_in)
            return z, jnp.ones_like(c)
        bad.gated_columns = gated_columns
    elif fault == "weights_from_biased_scores":
        def route(h, router, bias, top_k, scaling):
            s = jax.nn.sigmoid(h @ router.astype(jnp.float32).T) \
                + bias.astype(jnp.float32)
            w, chosen = jax.lax.top_k(s, top_k)
            return scaling * w / (jnp.sum(w, axis=-1, keepdims=True)
                                  + 1e-6), chosen
        bad.route = route
    elif fault == "state_at_the_buckets_end":
        def first_conv_columns(p, tokens, cfg, at, bucket=SERVE["prefill_len"]):
            # what a prefill that ignored ``length`` would keep of a prompt:
            # the columns of the bucket's padding (token 0)
            t = tokens.shape[0]
            padded = jnp.where(jnp.arange(bucket) <= at,
                               jnp.resize(tokens, (bucket,)), 0)
            return jnp.where(
                at == t - 1, ref.first_conv_columns(p, tokens, cfg, at),
                ref.first_conv_columns(p, padded, cfg, bucket - 1))
        bad.first_conv_columns = first_conv_columns
    elif fault == "columns_swapped":
        bad.first_conv_columns = lambda p, tokens, cfg, at: \
            ref.first_conv_columns(p, tokens, cfg, at)[::-1]
    else:
        raise ValueError(fault)
    return bad, bend


FAULTS = ["weights_in_float8", "break_reference", "taps_reversed_in_time",
          "input_gate_dropped", "output_gate_dropped",
          "weights_from_biased_scores", "state_at_the_buckets_end",
          "columns_swapped"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_reference_with_one_part_wrong_fails_the_same_tolerance(fault):
    """Each way the block can be wrong that the benchmark's check must see,
    put into the REFERENCE, against the sound bfloat16 program at the
    tolerances of the test above: the logits' lower quartile or the first
    layer's row fails, by at least one of the two. A row comparison alone
    cannot see the taps, the output gate or the experts' weights (the row is
    made before them); the logits alone cannot see a state taken at the
    bucket's end or two columns swapped in the reference (neither reaches
    them)."""
    params = _weights("bfloat16")
    dec = _decoder(params, "bfloat16")
    prompt = np.random.RandomState(9).randint(1, CFG["vocab_size"], 9)
    toks, got, admitted, last = _admit_and_step(dec, prompt, 6)
    bad, bend = _faulty(fault)
    p = bend(params)
    logits = _lower_quartile(_rel_l2(got, np.asarray(
        bad.logits(p, jnp.asarray(toks), CFG, last=7))))
    rows = max(_row_error(row, bad.first_conv_columns(
        p, jnp.asarray(toks), CFG, at))
        for row, at in ((admitted, 8), (last, len(toks) - 1)))
    sound_logits = _lower_quartile(_rel_l2(got, np.asarray(
        ref.logits(params, jnp.asarray(toks), CFG, last=7))))
    assert sound_logits < BF16_TOL
    assert logits > BF16_TOL or rows > BF16_ROW_TOL, (logits, rows)
    by_rows = fault in ("weights_in_float8", "break_reference",
                        "input_gate_dropped", "state_at_the_buckets_end",
                        "columns_swapped")
    assert bool(rows > BF16_ROW_TOL) is by_rows, (fault, rows)
    if fault not in ("state_at_the_buckets_end", "columns_swapped"):
        assert logits > BF16_TOL, (fault, logits)


def test_the_router_selects_on_the_biased_score_and_weighs_by_the_unbiased():
    """One expert layer of the graph with a bias large enough to change the
    choice: the program's routed sum is the reference's (selection on s + b,
    weights from s over their sum), and not that of weights from s + b."""
    cfg = dict(CFG, num_layers=1, layer_types=["conv"], first_dense_layers=0)
    params = _weights(cfg=cfg, seed=6)
    params["layer0_router_bias"] = jnp.asarray(
        np.random.RandomState(7).randn(8).astype("f") * 0.5)
    toks = np.random.RandomState(8).randint(1, 600, 10)
    sym = tf.get_prefill_symbol(prefill_len=10, **cfg)
    exe = sym.bind(mx.cpu(), {
        n: mx.nd.array(toks[None].astype("f")) if n == "data"
        else mx.nd.array(np.full((1, 1), 10, "f")) if n == "length"
        else mx.nd.NDArray(params[n]) for n in sym.list_arguments()},
        grad_req="null")
    got = _every_row(exe, 10)
    assert got.shape == (10, 600)
    assert _rel_l2(got, np.asarray(
        ref.logits(params, jnp.asarray(toks), cfg))).max() < F32_TOL
    biased, _ = _faulty("weights_from_biased_scores")
    assert _rel_l2(got, np.asarray(
        biased.logits(params, jnp.asarray(toks), cfg))).max() > 0.05
    # moe_load comes last, one row an expert layer: 10 tokens x 3 experts
    load = exe.outputs[-1].asnumpy()
    assert load.shape == (1, 8) and load.sum() == 30


def test_padding_of_the_bucket_never_reaches_the_rows():
    """The same prompt, the bucket's padding filled with two different
    things: the prefill's one row of logits (the prompt's last real row) and
    every row are the same bit for bit (the rows are a slice that starts at
    the length); the padding's K and V are not."""
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
    for (name, kind, _), x, y in zip(tf.decode_cache(**CFG), a[1:], b[1:]):
        if kind == "row":
            assert np.array_equal(x, y), name
        else:   # K and V of the real positions
            assert np.array_equal(x[:, :, :length], y[:, :, :length]), name
            assert not np.array_equal(x[:, :, length:], y[:, :, length:]), name


def test_a_lane_that_rides_along_keeps_its_rows():
    """Two sequences stepped in turn: the lane that rides along keeps every
    row bit for bit, and each sequence's logits are those of the same
    sequence decoded alone. The tokens fed are drawn, not sampled: a tiny
    model's greedy choice repeats, and the first layer's columns, which see
    the token alone, would then stand still."""
    params = _weights("float32")
    rs = np.random.RandomState(9)
    prompts = [rs.randint(1, CFG["vocab_size"], n) for n in (5, 12)]
    fed = rs.randint(1, CFG["vocab_size"], (2, 4))

    def alone(prompt, tokens):
        dec = _decoder(params)
        seq, logits = dec.admit(prompt.astype(np.float32))
        return [np.asarray(logits)] + [np.asarray(dec.step({seq: int(t)})[seq])
                                       for t in tokens]

    want = [alone(p, t) for p, t in zip(prompts, fed)]
    dec = _decoder(params)
    seqs, got = [], []
    for p in prompts:
        seq, logits = dec.admit(p.astype(np.float32))
        seqs.append(seq)
        got.append([np.asarray(logits)])
    lanes = [dec._seq_lane[s] for s in seqs]
    for step in range(4):
        for me, other in ((0, 1), (1, 0)):
            before = _rows(dec)
            got[me].append(np.asarray(dec.step(
                {seqs[me]: int(fed[me, step])})[seqs[me]]))
            after = _rows(dec)
            for name in before:
                assert np.array_equal(before[name][lanes[other]],
                                      after[name][lanes[other]]), name
                assert not np.array_equal(before[name][lanes[me]],
                                          after[name][lanes[me]]), name
    for mine, theirs in zip(got, want):
        assert np.array_equal(np.stack(mine), np.stack(theirs))


def test_a_readmitted_lane_forgets_its_last_occupant():
    """A lane that held a long sequence, retired and re-admitted with a
    one-token prompt: its rows are the new prompt's (one column, the other
    zero) and its logits those of a fresh decoder, bit for bit."""
    params = _weights("float32")
    rs = np.random.RandomState(11)
    dec = _decoder(params, lanes=1)
    seq, logits = dec.admit(rs.randint(1, 600, 20).astype(np.float32))
    for _ in range(5):
        logits = dec.step({seq: int(np.argmax(logits))})[seq]
    assert all(rows[0].any(axis=-1).all() for rows in _rows(dec).values())
    dec.retire(seq)
    prompt = rs.randint(1, 600, 1).astype(np.float32)
    seq, logits = dec.admit(prompt)
    assert dec._seq_lane[seq] == 0
    rows = _rows(dec)
    for name, row in rows.items():
        assert not row[0, 0].any() and row[0, 1].any(), name
    fresh = _decoder(params, lanes=1)
    seq2, logits2 = fresh.admit(prompt)
    assert np.array_equal(np.asarray(logits), np.asarray(logits2))
    for name, row in _rows(fresh).items():
        assert np.array_equal(row, rows[name]), name
    assert np.array_equal(np.asarray(dec.step({seq: 3})[seq]),
                          np.asarray(fresh.step({seq2: 3})[seq2]))


def test_the_cache_lists_pools_and_rows_in_layer_order():
    """``decode_cache``: two pools of (8 heads of 64) an attention layer, one
    row of (2, 2,048) a conv layer, in the order of the published pattern's
    first ten layers; the decode graph's outputs follow it between the
    logits and the token head, the experts' load comes last, and the
    parameters are the cut's 5,267,090,176."""
    kinds = ["conv", "conv", "full_attention", "conv", "conv", "conv",
             "full_attention", "conv", "conv", "conv"]
    cfg = dict(arch="lfm2_moe", vocab_size=65536, num_layers=10, num_heads=32,
               num_kv_heads=8, head_dim=64, model_dim=2048, ffn_dim=11776,
               moe_ffn_dim=1536, num_experts=64, num_experts_per_tok=4,
               first_dense_layers=2, layer_types=kinds)
    want = []
    for i, kind in enumerate(kinds):
        want += [("conv_state_%d" % i, "row", (2, 2048))] if kind == "conv" \
            else [("kv_k_%d" % i, "pool", (8, 64)),
                  ("kv_v_%d" % i, "pool", (8, 64))]
    assert tf.decode_cache(**cfg) == want
    outs = tf.get_decode_symbol(max_len=64, page_size=16,
                                **cfg).list_outputs()
    assert outs[0] == "lm_head_output" and len(outs) == 1 + 12 + 2
    assert outs[-2:] == ["greedy_token_output", "moe_load_output"]
    assert [o.split("_")[0] for o in outs[1:13]] == [
        "layer%d" % i for i, kind in enumerate(kinds)
        for _ in range(1 if kind == "conv" else 2)]
    assert sum(int(np.prod(s)) for s in tf.param_shapes(**cfg).values()) \
        == 5_267_090_176
    with pytest.raises(MXNetError, match="layer_types must name 10 layers"):
        tf.decode_cache(**dict(cfg, layer_types=kinds[:9]))
    with pytest.raises(MXNetError, match="'conv' or 'full_attention'"):
        tf.decode_cache(**dict(cfg, layer_types=["mamba"] * 10))


def test_a_step_names_its_convolution_in_the_program():
    """The operators' nodes are ``layer<i>_conv_core``: what a device trace's
    ``op_name`` finds them by."""
    sym = tf.get_decode_symbol(max_len=SERVE["lanes"] * SERVE["max_len"],
                               page_size=8, **CFG)
    cores = [n.name for n in sym._topo()
             if n.op == "_contrib_GatedShortConvStep"]
    assert cores == ["layer%d_conv_core" % i for i in (0, 1, 3, 4)]
    cores = [n.name for n in tf.get_prefill_symbol(
        prefill_len=32, **CFG)._topo() if n.op == "_contrib_GatedShortConv"]
    assert cores == ["layer%d_conv_core" % i for i in (0, 1, 3, 4)]


# ------------------------------------------------------- (c) spans and counters
def test_admission_and_steps_are_counted_and_spanned(tm):
    """The rows count in the gauge ``serving.state_bytes`` and their hand-off
    is a span inside the admission; the prefill's and the decode graph's
    ``moe_load`` feed the expert counters; the gauges say which form the two
    attention layers' reads took."""
    dec = _decoder(_weights("float32"))
    dec.warmup()
    assert tm.gauge("serving.state_bytes").value \
        == 4 * SERVE["lanes"] * 4 * 2 * 64
    assert tm.gauge("serving.pool_read.own_pages_layers").value \
        + tm.gauge("serving.pool_read.whole_pool_layers").value == 2
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
    assert moved["serving.step_context_tokens"] == 6 + 10 + 7
    # two admissions of a 32 bucket: 32 rows x 3 experts x 4 expert layers
    assert moved["serving.moe.assignments"] == 2 * 32 * 3 * 4
    # every lane passes through the experts, those that ride along too
    assert moved["serving.moe.step_assignments"] == 2 * 4 * 3 * 4
    assert 2 * 4 * 1 <= moved["serving.moe.step_experts_touched"] <= 2 * 4 * 8
    assert moved["serving.step_gathered_slots"] > 0
    spans = {attrs["id"]: (name, attrs.get("parent"), attrs)
             for name, _t0, _dur, _tid, attrs in tm.drain_events()
             if "id" in attrs}
    states = [v for v in spans.values() if v[0] == "serving.admit.state"]
    assert len(states) == 2 and states[0][2]["buffers"] == 4
    for _, parent, _ in states:
        chain = []
        while parent in spans:
            chain.append(spans[parent][0])
            parent = spans[parent][1]
        assert chain == ["serving.admit.scatter", "serving.paged_admit"]
    accounts = [v for v in spans.values() if v[0] == "serving.step.account"]
    assert len(accounts) == 2


def test_counters_and_the_gauge_are_absent_with_telemetry_off():
    telemetry.reset()
    saved = telemetry.current_override()
    telemetry.set_mode(None)
    try:
        dec = _decoder(_weights("float32"))
        seq, logits = dec.admit(np.arange(1, 6, dtype=np.float32))
        dec.step({seq: int(np.argmax(logits))})
        assert not [k for k in telemetry.counters()
                    if k.startswith("serving.")]
        assert telemetry.gauge("serving.state_bytes").value in (None, 0)
    finally:
        telemetry.set_mode(saved)
        telemetry.reset()


# ------------------------------------------------------- (d) what is not ported
@pytest.mark.parametrize("entry", ["fork", "rollback", "verify_chunk",
                                   "step_megastep", "prefix_cache",
                                   "get_symbol", "get_symbol_mt",
                                   "get_chunk_symbol"])
def test_unported_entry_points_refuse_the_architecture_by_name(entry):
    """A lane's convolution row is overwritten at every token: sharing pages
    says nothing of it and going back needs a snapshot nobody keeps yet; the
    chunk and megastep programs know the Vaswani block only."""
    if entry.startswith("get_"):
        with pytest.raises(MXNetError, match="not built for arch "
                           "'lfm2_moe' yet"):
            getattr(tf, entry)(arch="lfm2_moe")
        return
    params = {k: mx.nd.NDArray(v) for k, v in _weights("float32").items()}
    if entry == "prefix_cache":
        with pytest.raises(MXNetError, match="prefix_cache=True is not built "
                           "for arch 'lfm2_moe' yet"):
            PagedKVDecoder(params, prefix_cache=True, **SERVE, **CFG)
        return
    dec = PagedKVDecoder(params, **SERVE, **CFG)
    seq, _ = dec.admit(np.arange(1, 6, dtype=np.float32))
    call = {"fork": lambda: dec.fork(seq),
            "rollback": lambda: dec.rollback(seq, 2),
            "verify_chunk": lambda: dec.verify_chunk(seq, [1, 2]),
            "step_megastep": lambda: dec.step_megastep({seq: 1}, k=2)}[entry]
    with pytest.raises(MXNetError, match="not built for arch "
                       "'lfm2_moe' yet"):
        call()
    # nothing moved: the sequence still steps
    assert dec.position(seq) == 5 and dec.step({seq: 1})[seq].shape == (600,)
    assert "lfm2_moe" in tf.ARCHS
    with pytest.raises(MXNetError, match="'lfm2_moe'"):
        tf.param_shapes("mamba3", 10, 1, 1, 8, 8)
