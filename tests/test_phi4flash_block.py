"""The Phi-4-mini-flash block (``arch="phi4flash"`` of models/transformer.py
and serving.PagedKVDecoder: a self-decoder of Mamba-1 rows and window rings,
ONE full-attention pool that one layer writes and it and every cross layer
read, gated memory units on a tensor the step carries, differential attention
in its padded-query form, an admission whose cross-decoder runs one row)
against the benchmark's plain reference,
benchmark/reference/phi4_flash_decoder.py, on seeded weights at small sizes:
twelve layers (Mamba-1 at 0, 2, 4, 6; window attention at 1, 3, 5; full
attention at 7; gated memory units at 8, 10; cross attention at 9, 11),
8 query heads over 4 key/value heads of 8, a window of 8, state 4, rank 5.
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
from mxnet_tpu.ops import attention
from mxnet_tpu.ops.registry import get_op
from mxnet_tpu.serving import PagedKVDecoder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reference():
    """A fresh copy of the reference module: a test may bend one of its
    functions without any other test seeing it."""
    path = os.path.join(ROOT, "benchmark", "reference",
                        "phi4_flash_decoder.py")
    spec = importlib.util.spec_from_file_location("phi4flash_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = reference()

W = 8
# vocabulary above 256 on purpose: bfloat16 holds whole numbers to 256 only
CFG = dict(arch="phi4flash", vocab_size=600, num_layers=12, num_heads=8,
           num_kv_heads=4, head_dim=8, model_dim=64, ffn_dim=96,
           sliding_window=W, mamba_state=4, mamba_conv=4, mamba_expand=2,
           mamba_dt_rank=5)
# the same block with a pool row of 4 x 32 = 128: bound PAGE-MAJOR
# (``ops.attention.pool_shape``), as the published widths' 10 x 128 is
PAGED = dict(CFG, num_heads=16, num_kv_heads=8, head_dim=16, model_dim=256)
# a bucket of four windows: the prefill's window layers score a band
SERVE = dict(max_len=64, prefill_len=32, page_size=8, lanes=4)
KINDS = ["mamba", "window"] * 3 + ["mamba", "full"] + ["gmu", "cross"] * 2

# float32 on both sides on the CPU: what is left is the order of the sums
# (the padded query's zeros against four softmaxes of half the width, the
# ring's and the pool's contraction and the band's blocks against the full
# T x T scores, the scan's loop against the reference's); the runs read
# 1e-6 to 3e-6
F32_TOL = 1e-4
# bfloat16 weights, activations, pool and rings (the recurrent state and the
# scan stay float32) against the float32 reference over the same
# (bfloat16-valued) weights: every stored activation is rounded to 8 bits of
# mantissa, some dozen roundings a layer; twelve layers read 1e-2 to 3e-2
BF16_TOL = 6e-2


def _weights(dtype="float32", seed=0, cfg=CFG):
    """N(0, 0.15) matrices, a unit-variance embedding, biases and LayerNorm
    offsets N(0, 0.1) (a dropped bias is seen), lambda vectors N(0, 0.3)
    (lam is then 0.3 to 1.7, not lam0 alone), the sub-norm's weight
    N(1, 0.2); the Mamba initialiser of the benchmark's configuration: A
    uniform in [1, 16], dt's bias N(-2, 0.5) (steps of 0.05 to 0.3, decays
    between 0.01 and 0.95 a token), convolution U(-0.5, 0.5), D one."""
    rs = np.random.RandomState(seed)
    out = {}
    for name, shape in sorted(tf.param_shapes(**cfg).items()):
        if name.endswith("subln_gamma"):
            v = 1.0 + 0.2 * rs.randn(*shape)
        elif name.endswith(("gamma", "_D")):
            v = np.ones(shape)
        elif name.endswith("A_log"):
            v = np.log(rs.uniform(1, 16, shape))
        elif name.endswith("dt_bias"):
            v = -2.0 + 0.5 * rs.randn(*shape)
        elif "_conv_" in name:
            v = rs.uniform(-0.5, 0.5, shape)
        else:
            v = rs.randn(*shape) * (
                1.0 if name == "embed_weight"
                else 0.3 if "_lambda_" in name
                else 0.1 if name.endswith(("_beta", "_bias")) else 0.15)
        out[name] = jnp.asarray(v.astype("f")).astype(dtype)
    return out


def _decoder(params, dtype="float32", cfg=CFG, **kw):
    return PagedKVDecoder({k: mx.nd.NDArray(v) for k, v in params.items()},
                          dtype=dtype, **dict(SERVE, **kw), **cfg)


def _rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def _admit_and_step(dec, toks, length, keep=()):
    """Admit ``toks[:length]``, then feed the rest one step each: (the 1 +
    steps logits rows, ``lane_state(keep)`` after the last step)."""
    seq, logits = dec.admit(np.asarray(toks[:length], np.float32))
    got = [np.asarray(logits)]
    for tok in toks[length:]:
        got.append(np.asarray(dec.step({seq: int(tok)})[seq]))
    state = {k: np.array(v, dtype=np.float32)
             for k, v in dec.lane_state(seq, keep).items()} if keep else {}
    dec.retire(seq)
    return np.stack(got), state


@pytest.fixture
def tm():
    telemetry.reset()
    saved = telemetry.current_override()
    telemetry.set_mode("trace")
    yield telemetry
    telemetry.set_mode(saved)
    telemetry.reset()


# ------------------------------------------------------ (a) the Mamba-1 pair
M1 = ("conv_weight", "conv_bias", "x_weight", "dt_weight", "dt_bias",
      "A_log", "D")


def _mixer(seed=0, t=21):
    """(layer 0's Mamba-1 weights as the operators take them, u (T, E), the
    reference's y (T, E) and what it carries after each prefix)."""
    p = _weights(seed=seed)
    rs = np.random.RandomState(seed + 1)
    h = jnp.asarray(rs.randn(t, CFG["model_dim"]).astype("f"))
    with jax.default_matmul_precision("highest"):
        u = (h @ p["layer0_mamba1_in_weight"].T)[:, :2 * CFG["model_dim"]]
        y = ref.mamba_mixer(h, p, "layer0_")[1]
        carried = lambda n: ref.mamba_mixer(h[:n], p, "layer0_",
                                            with_state=True)
    return [p["layer0_mamba1_" + w] for w in M1], u, np.asarray(y), carried


@pytest.mark.parametrize("length", [21, 13, 8, 3, 1])
def test_the_scan_equals_the_sequential_recurrence(length):
    """``Mamba1Scan`` over a bucket of 24 with the LENGTH as data against the
    reference's position-by-position recurrence over the real tokens alone:
    the outputs before the gate up to the length, the state (N, E) and the
    last three columns of u at the length, padding and all. Float32 on both
    sides: 1e-6 is the order of a sum."""
    weights, u, y, carried = _mixer()
    padded = jnp.zeros((1, 24, u.shape[1])).at[0, :21].set(u)
    # the padding is not zeros: a recurrence that read it would show
    padded = padded.at[0, length:].set(7.0)
    out, state, conv = get_op("Mamba1Scan").fn(
        {}, padded, *weights, jnp.full((1, 1), length, jnp.float32))
    want_state, want_conv = carried(length)
    assert out.shape == (1, 24, 128) and state.shape == (1, 4, 128)
    np.testing.assert_allclose(out[0, :length], y[:length], atol=1e-6)
    np.testing.assert_allclose(state[0].T, want_state, atol=1e-6)
    np.testing.assert_allclose(conv[0], want_conv, atol=0)


@pytest.mark.parametrize("length", [1, 2, 9])
def test_steps_continue_a_scan(length):
    """``Mamba1Step`` from a scan's two rows, token after token, equals the
    scan over the longer sequence: outputs, state and columns; a row that
    rides along comes back bit for bit."""
    weights, u, y, carried = _mixer(seed=3)
    scan, step = get_op("Mamba1Scan").fn, get_op("Mamba1Step").fn
    _, state, conv = scan({}, u[None], *weights,
                          jnp.full((1, 1), length, jnp.float32))
    # row 0 steps, row 1 rides along with rubbish for data
    state = jnp.concatenate([state, state + 1.0])
    conv = jnp.concatenate([conv, conv - 1.0])
    idle = (np.array(state[1]), np.array(conv[1]))
    for t in range(length, 21):
        out, state, conv = step(
            {}, jnp.stack([u[t], u[0] * 9]), *weights, state, conv,
            jnp.asarray([[5.0], [-1.0]]))
        np.testing.assert_allclose(out[0], y[t], atol=1e-6)
    want_state, want_conv = carried(21)
    np.testing.assert_allclose(state[0].T, want_state, atol=1e-6)
    np.testing.assert_allclose(conv[0], want_conv, atol=0)
    np.testing.assert_array_equal(state[1], idle[0])
    np.testing.assert_array_equal(conv[1], idle[1])


def test_the_mamba1_pair_infers_its_shapes_from_the_data():
    u, names = mx.sym.Variable("u"), ("cw", "cb", "xw", "dw", "db", "a", "d")
    scan = mx.sym.Mamba1Scan(
        u, *(mx.sym.Variable(n) for n in names), mx.sym.Variable("len"))
    args, outs, _ = scan.infer_shape(u=(2, 24, 128), cw=(128, 4),
                                     xw=(13, 128), dw=(128, 5), a=(128, 4))
    got = dict(zip(scan.list_arguments(), args))
    assert got["cb"] == got["db"] == got["d"] == (128,)
    assert got["len"] == (2, 1)
    assert outs == [(2, 24, 128), (2, 4, 128), (2, 3, 128)]
    step = mx.sym.Mamba1Step(
        u, *(mx.sym.Variable(n) for n in names), mx.sym.Variable("s"),
        mx.sym.Variable("c"), mx.sym.Variable("go"))
    args, outs, _ = step.infer_shape(u=(3, 128), cw=(128, 4), xw=(13, 128),
                                     dw=(128, 5), a=(128, 4), s=(3, 4, 128),
                                     c=(3, 3, 128))
    assert dict(zip(step.list_arguments(), args))["go"] == (3, 1)
    assert outs == [(3, 128), (3, 4, 128), (3, 3, 128)]
    with pytest.raises(MXNetError, match="does not project 128 channels"):
        get_op("Mamba1Step").fn(
            {}, jnp.zeros((1, 128)), jnp.zeros((128, 4)), jnp.zeros(128),
            jnp.zeros((12, 128)), jnp.zeros((128, 5)), jnp.zeros(128),
            jnp.zeros((128, 4)), jnp.zeros(128), jnp.zeros((1, 4, 128)),
            jnp.zeros((1, 3, 128)), jnp.ones((1, 1)))


# ------------------------- (b) differential attention in its padded-query form
def _four_softmaxes(q, k, v, seen, cfg=CFG, depth=1, seed=0):
    """The reference's differential attention on q (T, Hq dh) over k, v
    (Hkv, S, dh) without the output projection (identity, no bias): what
    ``_diff_combine`` must give, (T, Hq dh)."""
    d = cfg["num_heads"] * cfg["head_dim"]
    p = {"proj_weight": jnp.eye(d), "proj_bias": jnp.zeros(d)}
    p.update(_lambdas(cfg, seed))
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.differential_attention(
            q, k, v, p, "", depth, cfg, seen))


def _lambdas(cfg, seed):
    rs = np.random.RandomState(100 + seed)
    dh = cfg["head_dim"]
    out = {"lambda_" + v: jnp.asarray(0.4 * rs.randn(dh).astype("f"))
           for v in ("q1", "k1", "q2", "k2")}
    out["subln_gamma"] = jnp.asarray(1 + 0.3 * rs.randn(2 * dh).astype("f"))
    return out


def _pairs(a, cfg=CFG):
    """Keys or values (Hkv, S, dh) as the cache keeps them: (1, Hkv/2, S,
    2 dh), a pair of heads side by side."""
    hkv, dh = cfg["num_kv_heads"], cfg["head_dim"]
    return jnp.asarray(a).reshape(hkv // 2, 2, -1, dh).transpose(
        0, 2, 1, 3).reshape(1, hkv // 2, -1, 2 * dh)


def _padded_form(read, t, feed, cfg=CFG, depth=1, seed=0):
    """The block's own graph pieces (``_diff_queries`` -> ``read`` ->
    ``_diff_combine``) bound and run: ``read(query (B, Hq, T, 2 dh), keys,
    values)`` builds one of the repo's three attention reads over the
    symbols ``q``, ``k``, ``v`` and whatever else it names; ``feed`` holds
    them all, ``q`` as (B, T, Hq dh). Returns (B, T, Hq dh)."""
    hq, dh = cfg["num_heads"], cfg["head_dim"]
    qs = tf._diff_queries(mx.sym.Variable("q"), t, hq, dh)
    out = tf._diff_combine(read(qs, mx.sym.Variable("k"),
                                mx.sym.Variable("v")), "x", depth, t, hq, dh,
                           "float32")
    feed = dict(feed, **{"x_" + n: a
                         for n, a in _lambdas(cfg, seed).items()})
    exe = out.bind(mx.cpu(), {n: mx.nd.NDArray(jnp.asarray(a))
                              for n, a in feed.items()})
    return np.asarray(exe.forward()[0]._jax())


def _qkv(t, s=None, seed=0, cfg=CFG):
    rs = np.random.RandomState(seed)
    hq, hkv, dh = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    return (jnp.asarray(rs.randn(t, hq * dh).astype("f")),
            jnp.asarray(rs.randn(hkv, s or t, dh).astype("f")),
            jnp.asarray(rs.randn(hkv, s or t, dh).astype("f")))


@pytest.mark.parametrize("t", [32, 16, 5])
def test_the_padded_query_form_under_a_window_is_the_four_softmaxes(t):
    """The banded prefill (``MultiHeadAttention(window=)``): zeros beside a
    query leave ``q1 k1`` and ``q2 k2`` alone, and each softmax applies to
    ``[v1 | v2]``: 8 padded heads of 16 over 2 key/value heads of 16 give the
    reference's 4 pairs of two softmaxes each, the subtraction and the
    sub-norm included."""
    q, k, v = _qkv(t)
    seen = jnp.tril(jnp.ones((t, t), bool)) \
        & ~jnp.tril(jnp.ones((t, t), bool), k=-W)
    got = _padded_form(
        lambda qs, ks, vs: mx.sym.MultiHeadAttention(
            query=qs, key=ks, value=vs, causal=True, window=W,
            scale=CFG["head_dim"] ** -0.5), t,
        {"q": q[None], "k": _pairs(k), "v": _pairs(v)})[0]
    np.testing.assert_allclose(got, _four_softmaxes(q, k, v, seen),
                               atol=2e-6)


@pytest.mark.parametrize("pos", [2, 7, 8, 19])
def test_the_padded_query_form_over_a_ring_is_the_four_softmaxes(pos):
    """``KVRingAttention`` over a ring written position after position: one
    row at ``pos``, its last 8 keys (fewer while the ring fills), each at its
    position mod 8."""
    q, k, v = _qkv(1, pos + 1, seed=pos)
    held = np.arange(max(0, pos - W + 1), pos + 1)
    seen = jnp.zeros((1, pos + 1), bool).at[0, held].set(True)

    def read(qs, ks, vs):
        rows = lambda a, n: mx.sym.Reshape(a, shape=(-1, n, 16))
        return mx.sym.Reshape(mx.sym.KVRingAttention(
            rows(qs, 8), ks, vs, mx.sym.Variable("pos"),
            mx.sym.Variable("slot"), scale=CFG["head_dim"] ** -0.5),
            shape=(-1, 8, 1, 16))

    # the ring as the steps leave it: position p at slot p mod 8, and
    # rubbish where the sequence has not written yet
    def ring(a):
        out = np.full((CFG["num_kv_heads"], W, 8), 50.0, "f")
        out[:, held % W] = np.asarray(a)[:, held]
        return jnp.asarray(out)

    got = _padded_form(read, 1, {
        "q": q[None], "k": _pairs(ring(k)), "v": _pairs(ring(v)),
        "pos": jnp.full((1, 1), float(pos)), "slot": jnp.ones((1, 1))})[0]
    np.testing.assert_allclose(got, _four_softmaxes(q, k, v, seen),
                               atol=2e-6)


@pytest.mark.parametrize("form", ["whole_pool", "own_pages", "kernel"])
@pytest.mark.parametrize("cfg", [CFG, PAGED], ids=["head_major",
                                                   "page_major"])
def test_the_padded_query_form_over_the_pool_is_the_four_softmaxes(
        monkeypatch, form, cfg):
    """``KVPoolAttention`` in each of its forms (the kernel interpreted, over
    the page-major pool alone) for three rows at contexts of 5, 16 and 23
    slots in pages of 8 scattered over a pool of 64 slots: a cross layer's
    read, whose keys another layer wrote."""
    if form == "kernel" and cfg is CFG:
        pytest.skip("the kernel walks page-major pools only")
    hq, hkv, dh = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    monkeypatch.setattr(attention, "pool_read_form", lambda *a: form)
    contexts, page, slots = (5, 16, 23), 8, 64
    tables = np.asarray([[7, 0, 0], [2, 5, 0], [1, 6, 3]], np.float32)
    rs = np.random.RandomState(5)
    pool = {t: rs.randn(slots, hkv // 2, 2 * dh).astype("f") for t in "kv"}
    q = jnp.asarray(rs.randn(3, hq * dh).astype("f"))
    want = []
    for r, n in enumerate(contexts):
        at = (tables[r].astype(int)[:, None] * page
              + np.arange(page)).reshape(-1)[:n]
        heads = lambda a: jnp.asarray(a[at]).reshape(n, hkv, dh).transpose(
            1, 0, 2)
        want.append(_four_softmaxes(q[r:r + 1], heads(pool["k"]),
                                    heads(pool["v"]),
                                    jnp.ones((1, n), bool), cfg)[0])
    bound = {t: pool[t].reshape(attention.pool_shape(
        hkv // 2, 2 * dh, slots, page)) if attention.pool_paged(
            hkv // 2, 2 * dh) else pool[t].transpose(1, 0, 2) for t in "kv"}
    pages = dict(page_table=mx.sym.Variable("table"),
                 pos_idx=mx.sym.Variable("pos"),
                 write_slot=mx.sym.Variable("slot"))
    read = lambda qs, ks, vs: mx.sym.Reshape(mx.sym.KVPoolAttention(
        mx.sym.Reshape(qs, shape=(-1, hq, 2 * dh)), ks, vs,
        scale=dh ** -0.5, page_size=page,
        mask=mx.sym.KVPageMask(page_size=page, num_slots=slots, **pages),
        **pages), shape=(-1, hq, 1, 2 * dh))
    got = _padded_form(read, 1, {
        "q": q[:, None], "k": bound["k"], "v": bound["v"], "table": tables,
        "pos": np.asarray(contexts, "f")[:, None] - 1,
        "slot": np.ones((3, 1), "f")}, cfg)[:, 0]
    np.testing.assert_allclose(got, np.stack(want), atol=3e-6)


# --------------------------------------------------- (c) through the decoder
def test_the_mixer_follows_depth_and_parity():
    assert list(tf._phi4flash_sizes(
        12, **{k: v for k, v in CFG.items()
               if k not in ("arch", "vocab_size", "num_layers")})["kinds"]) \
        == KINDS == ref.kinds(CFG)
    cache = tf.decode_cache(**CFG)
    assert [(n, k) for n, k, _ in cache] == [
        (name % i, kind) for i, layer in enumerate(KINDS)
        for name, kind in {
            "mamba": [("ssm_state_%d", "row"), ("conv_state_%d", "row")],
            "window": [("ring_k_%d", "ring"), ("ring_v_%d", "ring")],
            "full": [("kv_k_%d", "pool"), ("kv_v_%d", "pool")]}.get(layer, ())]
    shapes = {n: s for n, _, s in cache}
    assert shapes["ssm_state_0"] == (4, 128)        # state-major
    assert shapes["conv_state_6"] == (3, 128)
    assert shapes["ring_k_1"] == (2, W, 16) == shapes["ring_v_5"]
    assert shapes["kv_k_7"] == (2, 16) == shapes["kv_v_7"]
    for bad in (dict(num_layers=10), dict(mb_per_layer=1),
                dict(num_kv_heads=3)):
        with pytest.raises(MXNetError, match="phi4flash: "):
            tf.param_shapes(**dict(CFG, **bad))


def test_the_published_widths_count_3_852_562_944_parameters():
    shapes = tf.param_shapes(
        arch="phi4flash", vocab_size=200064, num_layers=32, num_heads=40,
        num_kv_heads=20, head_dim=64, model_dim=2560, ffn_dim=10240,
        sliding_window=512)
    count = lambda pick: sum(int(np.prod(s)) for n, s in shapes.items()
                             if pick(n))
    assert count(lambda n: True) == 3_852_562_944
    assert count(lambda n: n == "embed_weight") == 512_163_840
    assert count(lambda n: "_mlp_" in n) == 2_516_582_400
    assert count(lambda n: "_mamba1_" in n) == 9 * 41_241_600
    assert count(lambda n: "_self_" in n) == 9 * 19_668_864
    assert count(lambda n: "_cross_" in n) == 7 * 13_112_704
    assert count(lambda n: "_gmu_" in n) == 7 * 26_214_400
    assert count(lambda n: "_ln" in n) == 332_800
    assert shapes["layer0_mamba1_dt_weight"] == (5120, 160)
    cache = tf.decode_cache(
        arch="phi4flash", num_layers=32, num_heads=40, num_kv_heads=20,
        head_dim=64, model_dim=2560, ffn_dim=10240, sliding_window=512)
    kinds = [k for _, k, _ in cache]
    assert (kinds.count("row"), kinds.count("ring"), kinds.count("pool")) \
        == (18, 16, 2)
    assert dict((n, s) for n, _, s in cache)["kv_k_17"] == (10, 128)


@pytest.mark.parametrize("cfg", [CFG, PAGED], ids=["head_major",
                                                   "page_major"])
@pytest.mark.parametrize("length", [5, 8, 20, 32])
def test_admit_then_steps_agree_with_the_full_forward(length, cfg):
    """The logits ``admit`` returns and those of 30 single decode steps
    through the cache against the reference's full forward over the whole
    sequence, row by row: prompts shorter than, as long as and longer than
    the window of 8 and the whole bucket, the steps fed DRAWN tokens, across
    the ring's wrap more than three times and three page boundaries. The
    admission ran the cross-decoder on one row, the reference on every row.
    The first layer's state and columns after the last step are the
    reference's sequential recurrence's."""
    params = _weights(cfg=cfg)
    dec = _decoder(params, cfg=cfg)
    toks = np.random.RandomState(length).randint(1, 600, length + 30)
    got, state = _admit_and_step(dec, toks, length,
                                 ("ssm_state_0", "conv_state_0"))
    want = np.asarray(ref.logits(params, jnp.asarray(toks), cfg, last=31))
    assert got.dtype == np.float32 and got.shape == (31, 600)
    assert _rel_l2(got, want).max() < F32_TOL
    want_state, want_conv = ref.first_mixer_state(params, jnp.asarray(toks),
                                                  cfg)
    np.testing.assert_allclose(state["ssm_state_0"].T, want_state, atol=1e-5)
    np.testing.assert_allclose(state["conv_state_0"], want_conv, atol=1e-5)


def _faulty(fault):
    """(reference module, its configuration, what to do to the weights) with
    one part of the layer equations wrong."""
    bad, cfg, bend = reference(), dict(CFG), lambda p: p
    zero = lambda tail: lambda p: {
        k: jnp.zeros_like(v) if k.endswith(tail) else v for k, v in p.items()}
    if fault == "window_one_slot_too_long":
        cfg["sliding_window"] = W + 1
    elif fault == "lambda_is_lambda_init_alone":
        bend = zero(("_lambda_q1", "_lambda_q2"))
    elif fault == "no_subtraction":
        bad.lambda_init = lambda depth: 0.0     # lam = 1 - 1 + 0
        bend = zero(("_lambda_q1", "_lambda_q2"))
    elif fault == "lambda_init_of_another_depth":
        first = bad.lambda_init
        bad.lambda_init = lambda depth: first(depth + 1)
    elif fault == "sub_norm_without_its_weight":
        bend = lambda p: {k: jnp.ones_like(v) if k.endswith("subln_gamma")
                          else v for k, v in p.items()}
    elif fault == "attention_bias_dropped":
        bend = zero(("qkv_bias", "q_bias", "proj_bias"))
    elif fault == "layer_norm_bias_dropped":
        bend = zero("_beta")
    elif fault == "gmu_gate_dropped":
        bend = zero("gmu_in_weight")
    elif fault == "m_after_the_gate":
        mixer = bad.mamba_mixer

        def gated(h, p, n, with_state=False):
            out = mixer(h, p, n, with_state)
            if with_state:
                return out
            z = (h @ p[n + "mamba1_in_weight"].astype(jnp.float32).T)[
                :, out[1].shape[1]:]
            return out[0], out[1] * jax.nn.silu(z)
        bad.mamba_mixer = gated
    elif fault == "m_without_d_times_u":
        bend = lambda p: {k: jnp.zeros_like(v) if k == "layer6_mamba1_D"
                          else v for k, v in p.items()}
    elif fault == "cross_layers_with_the_window":
        attend = bad.differential_attention

        def windowed(q, k, v, p, n, i, cfg, seen):
            if n.endswith("cross_"):
                t = seen.shape[0]
                seen = seen & ~jnp.tril(jnp.ones((t, t), bool), k=-W)
            return attend(q, k, v, p, n, i, cfg, seen)
        bad.differential_attention = windowed
    elif fault == "dt_bias_dropped":
        bend = zero("dt_bias")
    else:
        raise AssertionError(fault)
    return bad, cfg, bend


@pytest.fixture(scope="module")
def sample():
    """(weights, 32 tokens, the program's 13 rows: a prompt of 20 admitted
    and 12 drawn tokens stepped), shared by the faults below."""
    params = _weights()
    toks = np.random.RandomState(11).randint(1, 600, 20 + 12)
    return params, toks, _admit_and_step(_decoder(params), toks, 20)[0]


@pytest.mark.parametrize("fault", [
    "window_one_slot_too_long", "lambda_is_lambda_init_alone",
    "no_subtraction", "lambda_init_of_another_depth",
    "sub_norm_without_its_weight", "attention_bias_dropped",
    "layer_norm_bias_dropped", "gmu_gate_dropped", "m_after_the_gate",
    "m_without_d_times_u", "cross_layers_with_the_window",
    "dt_bias_dropped"])
def test_a_reference_with_one_part_wrong_disagrees(fault, sample):
    """Each mechanism the block adds is seen by the comparison: against a
    reference with a window of 9, with lam = lam0, with no second softmax,
    with lam0 of the next depth, with the sub-norm's weight 1, without the
    attention biases, without LayerNorm's, without the memory unit's gate,
    with m taken after the gate or without D * u', with the cross layers
    windowed, or without dt's bias, EVERY row of the sample reads above 30
    times the sound limit."""
    params, toks, got = sample
    bad, cfg, bend = _faulty(fault)
    want = np.asarray(bad.logits(bend(params), jnp.asarray(toks), cfg,
                                 last=13))
    assert _rel_l2(got, want).min() > 30 * F32_TOL


def test_bfloat16_weights_pool_and_rings():
    """The chip's types on the CPU: bfloat16 weights, pool and rings, float32
    rows, ids and positions. Every row stays within storage rounding of the
    float32 reference (no experts here: no row may flip), and the state the
    first layer keeps is float32 arithmetic on bfloat16-rounded inputs."""
    params = _weights("bfloat16")
    dec = _decoder(params, "bfloat16")
    exe = dec.warmup()._dec_exe
    types = {n: str(exe.arg_dict[n].dtype) for n in dec._cache_names}
    assert {types[n] for n, k, _ in dec._cache if k == "row"} == {"float32"}
    assert {types[n] for n, k, _ in dec._cache if k != "row"} == {"bfloat16"}
    toks = np.random.RandomState(2).randint(1, 600, 20 + 16)
    got, state = _admit_and_step(dec, toks, 20, ("ssm_state_0",))
    want = np.asarray(ref.logits(params, jnp.asarray(toks), CFG, last=17))
    assert _rel_l2(got, want).max() < BF16_TOL
    want_state = ref.first_mixer_state(params, jnp.asarray(toks), CFG)[0]
    assert _rel_l2(state["ssm_state_0"].T.reshape(1, -1),
                   np.asarray(want_state).reshape(1, -1))[0] < 2e-2


def test_one_pool_eight_readers_in_spirit(tm):
    """ONE pool pair in the cache, written by one node and read by three
    here (layer 7 and the cross layers 9 and 11; eight at the published
    depth): its bytes are counted once, a read of it once a reading layer,
    and the cross layers add nothing to what a step swaps back."""
    params = _weights()
    dec = _decoder(params).warmup()
    sym = dec._dec_cache._sym
    ops = [n.op for n in sym._topo()]
    assert ops.count("_contrib_KVPoolAttention") == 3
    assert ops.count("_contrib_KVPoolSlotWrite") == 1
    assert ops.count("_contrib_KVRingAttention") == 3
    assert ops.count("_contrib_Mamba1Step") == 4
    assert dec._pool_names == ["kv_k_7", "kv_v_7"]
    # logits, 8 rows, 6 rings, 2 pools, the token: nothing for layers 8-11
    assert len(sym.list_outputs()) == 1 + 16 + 1
    from mxnet_tpu.serving import kv_decode
    reads = kv_decode._pool_reads(dec._dec_cache, dec._decode_shapes())
    assert [(node, pool) for node, pool, _, _ in reads] == [
        ("layer7_self_att", "kv_k_7"), ("layer9_cross_att", "kv_k_7"),
        ("layer11_cross_att", "kv_k_7")]
    assert {form for _, _, form, _ in reads} == {"whole_pool"}  # rows of 32
    assert tm.gauge("serving.shared_pool_readers").value == 2
    assert tm.gauge("serving.pool_read.whole_pool_layers").value == 3
    pool = 2 * 4 * 64 * 2 * 16 * 4           # k and v x slots x heads x d
    rings = 3 * 2 * 4 * 2 * W * 16 * 4       # layers x k, v x lanes x ...
    rows = 4 * 4 * (4 + 3) * 128 * 4         # layers x lanes x (N + K-1) E
    assert tm.gauge("serving.full_pool_bytes").value == pool
    assert tm.gauge("serving.window_ring_bytes").value == rings
    assert tm.gauge("serving.state_bytes").value == rows
    assert tm.gauge("serving.cache_bytes").value == pool + rings + rows \
        == tm.gauge("serving.decode_aliased_bytes").value
    before = tm.counters()
    a, _ = dec.admit(np.arange(1, 21, dtype=np.float32))
    b, _ = dec.admit(np.asarray([7, 8, 9], np.float32))
    dec.step({a: 4, b: 5})
    moved = {k: v - before.get(k, 0) for k, v in tm.counters().items()}
    assert moved["serving.admit_self_rows"] == 2 * 32
    assert moved["serving.admit_cross_rows"] == 2 == moved[
        "serving.paged_admits"]
    assert moved["serving.step_context_tokens"] == 21 + 4
    assert moved["serving.step_window_slots"] == W + 4
    assert moved["serving.step_slot_writes"] == 2 * 2   # one layer's k and v
    # every buffer of the cache is handed over by reference, once
    assert moved["executor.rebind_copy"] == 0


def test_the_published_shape_reads_page_major_pools_through_the_kernel_rule():
    """At a pool row of whole lane tiles the pool is bound page-major and
    every one of the reading layers gathers a lane's own pages on the CPU
    (the kernel on the chip: ``tests/test_tpu_aot_compile.py``)."""
    dec = _decoder(_weights(cfg=PAGED), cfg=PAGED).warmup()
    assert dec._dec_exe.arg_dict["kv_k_7"].shape == (4 * 64 // 8, 8, 128)
    from mxnet_tpu.serving import kv_decode
    reads = kv_decode._pool_reads(dec._dec_cache, dec._decode_shapes())
    assert [form for _, _, form, _ in reads] == ["own_pages"] * 3


def test_the_admission_skips_the_cross_decoder_and_loses_nothing():
    """The prefill hands back ONE row of logits, and that row is what a step
    computes for the same token with every layer run on it: a prompt
    admitted whole against the same prompt admitted one token short and
    stepped. (The reference, which runs every layer over every row, agrees
    with both: ``test_admit_then_steps_agree_with_the_full_forward``.)"""
    params = _weights()
    dec = _decoder(params).warmup()
    pf = dec._pf_cache.executable(dec._prefill_shapes())
    assert pf.outputs[0].shape == (1, 600) and dec._head_rows == 1
    toks = np.random.RandomState(4).randint(1, 600, 19)
    whole, logits = dec.admit(toks.astype(np.float32))
    short, _ = dec.admit(toks[:-1].astype(np.float32))
    stepped = np.asarray(dec.step({short: int(toks[-1])})[short])
    np.testing.assert_allclose(np.asarray(logits), stepped, rtol=1e-5,
                               atol=2e-5)
    # and the two lanes hold the same cache from here on
    for name in ("ssm_state_6", "ring_k_5", "kv_k_7", "kv_v_7"):
        np.testing.assert_allclose(
            *(np.asarray(dec.lane_state(s, (name,))[name])
              for s in (whole, short)), atol=2e-5)
    assert dec.lane_state(whole, ("kv_k_7",))["kv_k_7"].shape == (2, 19, 16)


def test_multiplexed_lanes_equal_sequential_decoding():
    """Four sequences of different lengths stepped together, some lanes
    riding along some steps, equal the same sequences decoded alone."""
    params = _weights()
    rs = np.random.RandomState(6)
    seqs = [rs.randint(1, 600, n) for n in (26, 9, 17, 33)]
    starts = (20, 3, 8, 32)
    dec = _decoder(params)      # one after the other in an empty decoder
    alone = [_admit_and_step(dec, t, n)[0] for t, n in zip(seqs, starts)]
    ids, got = [], [[] for _ in seqs]
    for j, (t, n) in enumerate(zip(seqs, starts)):
        seq, logits = dec.admit(t[:n].astype(np.float32))
        ids.append(seq)
        got[j].append(np.asarray(logits))
    at = list(starts)
    while any(a < len(t) for a, t in zip(at, seqs)):
        feed = {ids[j]: int(seqs[j][at[j]]) for j in range(4)
                if at[j] < len(seqs[j])}
        for j, row in ((ids.index(s), r) for s, r in dec.step(feed).items()):
            got[j].append(np.asarray(row))
            at[j] += 1
    for mine, theirs in zip(got, alone):
        np.testing.assert_allclose(np.stack(mine), theirs, rtol=1e-5,
                                   atol=1e-5)


def test_a_readmitted_lane_inherits_nothing():
    """Lane 0 serves a long sequence that fills every ring, three pages and
    its rows, retires, and is given a prompt of 3 tokens: the rings' slots
    past it and the frames it is handed still hold the predecessor's keys
    (asserted), its state rows are overwritten, and every row of the
    newcomer is the reference's and a fresh decoder's."""
    params = _weights()
    dec = _decoder(params)
    rs = np.random.RandomState(9)
    first = rs.randint(1, 600, 30)
    seq, _ = dec.admit(first[:25].astype(np.float32))
    for tok in first[25:]:
        dec.step({seq: int(tok)})
    frames = list(dec._lanes[0].frames)
    dec.retire(seq)
    toks = rs.randint(1, 600, 3 + 9)
    seq, logits = dec.admit(toks[:3].astype(np.float32))
    assert dec._seq_lane[seq] == 0 and dec._lanes[0].frames[0] in frames
    ring = np.asarray(dec.lane_state(seq, ("ring_k_1",))["ring_k_1"])
    assert np.abs(ring[:, 3:]).max() > 0    # what a careless read would see
    page = np.asarray(dec._dec_exe.arg_dict["kv_k_7"]._jax())[
        :, dec._lanes[0].frames[0] * 8 + 3:dec._lanes[0].frames[0] * 8 + 8]
    assert np.abs(page).max() > 0           # and a careless walk of a page
    got = [np.asarray(logits)]
    for tok in toks[3:]:
        got.append(np.asarray(dec.step({seq: int(tok)})[seq]))
    want = np.asarray(ref.logits(params, jnp.asarray(toks), CFG, last=10))
    assert _rel_l2(np.stack(got), want).max() < F32_TOL
    fresh = _admit_and_step(_decoder(params), toks, 3)[0]
    np.testing.assert_allclose(np.stack(got), fresh, rtol=1e-5, atol=1e-5)


def test_what_rows_and_rings_cannot_do_is_refused():
    """``fork``, ``rollback``, the prefix cache, the chunk, verify and
    megastep programs refuse the arch as they refuse every row-keeping one,
    with the same messages; admit, step and retire are the same entry points
    as every other block's."""
    params = _weights()
    refusal = "not built for arch 'phi4flash' yet"
    with pytest.raises(MXNetError, match=refusal):
        _decoder(params, prefix_cache=True)
    dec = _decoder(params)
    seq, logits = dec.admit(np.asarray([5, 6, 7], np.float32))
    for call in (lambda: dec.fork(seq), lambda: dec.rollback(seq, 1)):
        with pytest.raises(MXNetError, match=refusal + ": a recurrent state "
                           "or a window's ring cannot be shared or rolled "
                           "back"):
            call()
    for call in (lambda: dec.verify_chunk(seq, [1, 2]),
                 lambda: dec.step_megastep({seq: 1}, k=2),
                 lambda: dec._chunk_for(4)):
        with pytest.raises(MXNetError, match=refusal):
            call()
    for entry in ("get_symbol", "get_symbol_mt", "get_chunk_symbol"):
        with pytest.raises(MXNetError, match=refusal + r" \(built for: "
                                             "vaswani"):
            getattr(tf, entry)(arch="phi4flash")
    with pytest.raises(MXNetError, match="unknown arch 'phi5' .*phi4flash"):
        tf.get_decode_symbol(arch="phi5")
    row = dec.step({seq: int(np.argmax(logits))})[seq]
    assert row.shape == (600,) and dec.position(seq) == 4
    dec.retire(seq)
    assert dec.stats()["active"] == 0 and dec.stats()["pages_in_use"] == 0
    assert dec._pf_cache._model_key.endswith("-phi4flash-prefill")


def test_the_graphs_name_their_nodes_for_the_trace():
    """``self_``, ``cross_``, ``gmu_`` and ``mamba1_`` in the node names: the
    executor's ``jax.named_scope(node.name)`` puts them on the device trace,
    and the checkpoint's names follow the nodes'."""
    dec = _decoder(_weights())
    for sym in (dec._dec_cache._sym, dec._pf_cache._sym):
        names = [n.name for n in sym._topo() if not n.is_variable]
        for i, kind in enumerate(KINDS):
            tag = {"mamba": "mamba1_core", "window": "self_att",
                   "full": "self_att", "gmu": "gmu_out",
                   "cross": "cross_att"}[kind]
            assert "layer%d_%s" % (i, tag) in names
    prefill = [n.name for n in dec._pf_cache._sym._topo()]
    assert "layer7_self_kv" in prefill and "layer7_self_q" in prefill
    assert "layer7_self_qkv" not in prefill
