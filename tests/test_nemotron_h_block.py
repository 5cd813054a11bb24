"""The Nemotron-H block (``arch="nemotron_h"`` of models/transformer.py and
serving.PagedKVDecoder): ONE mixer a block, Mamba-2 with GROUPS of B and C
and a gated norm a group, position-free grouped-query attention, UNGATED
relu^2 experts (a share of them held) beside a shared one, against the
benchmark's plain reference, benchmark/reference/nemotron_h_decoder.py, whose
recurrence runs one position after the other and whose experts are computed
densely and masked, on seeded weights at small sizes. Every tolerance says
where it comes from.
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
from mxnet_tpu.ops import moe, ssm
from mxnet_tpu.ops import pallas_grouped_matmul as kernel
from mxnet_tpu.serving import PagedKVDecoder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(ROOT, "benchmark", "reference",
                        "nemotron_h_decoder.py")
    spec = importlib.util.spec_from_file_location("nemotron_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()

# vocabulary above 256 on purpose: bfloat16 holds whole numbers to 256 only.
# The pattern is the published one's head, MEMEM*E: every kind, an expert
# block first behind a Mamba block and last behind attention.
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}
CFG = dict(arch="nemotron_h", vocab_size=600, num_layers=7, num_heads=4,
           num_kv_heads=2, head_dim=16, model_dim=64, ffn_dim=24,
           layer_types=[KINDS[c] for c in "MEMEM*E"],
           mamba_heads=8, mamba_head_dim=16, mamba_state=16, mamba_groups=4,
           mamba_conv=4, mamba_chunk=8, moe_ffn_dim=24, shared_ffn_dim=48,
           num_experts=8, num_experts_per_tok=2, num_local_experts=4,
           local_expert_offset=0, routed_scaling_factor=2.5,
           norm_topk_prob=True, rms_eps=1e-5)
SERVE = dict(max_len=64, prefill_len=32, page_size=8, lanes=4)
CORE = dict(num_heads=8, head_dim=8, state_size=16, conv_kernel=4)

# float32 on both sides on the CPU: what is left is the order of the sums (a
# chunk's masked matrix product against the recurrence, the pool read against
# fused attention, a grouped matmul against a dense one), a few ulp on values
# of order 1
F32_TOL = 1e-5
# bfloat16 weights, residual stream and pool against the float32 reference
# over the same (bfloat16-valued) weights: every stored activation is rounded
# to 8 bits of mantissa; seven blocks read 5e-3 to 1e-2 where no expert
# flips. A near-tied expert does flip under that rounding and its row then
# reads 1e-1, as high as a fault and none (paged_closed_loop_lfm2's
# kth_smallest says so at length): the rows' LOWER QUARTILE is held
BF16_TOL = 3e-2


def _weights(dtype, cfg=CFG, seed=0, scale=0.1):
    """The configuration's kinds of draw at a small size: normal matrices (the
    embedding of unit variance and the router's bias N(0, 0.1), as kanana's
    file draws them), unit gammas and D, A in [1, 16], dt in [1e-3, 1e-1]
    through the inverse softplus, convolution weights and bias in
    (-0.5, 0.5); the experts' stacks ZERO past the published width."""
    rs = np.random.RandomState(seed)
    f = cfg["moe_ffn_dim"]
    out = {}
    for name, shape in sorted(tf.param_shapes(**cfg).items()):
        if name.endswith(("gamma", "_D")):
            v = np.ones(shape, "f")
        elif name.endswith("A_log"):
            v = np.log(rs.uniform(1, 16, shape))
        elif name.endswith("dt_bias"):
            v = np.log(np.expm1(np.exp(rs.uniform(np.log(1e-3), np.log(1e-1),
                                                  shape))))
        elif "_conv_" in name:
            v = rs.uniform(-0.5, 0.5, shape)
        elif name == "embed_weight":
            v = rs.randn(*shape)
        else:
            v = rs.randn(*shape) * scale
        if name.endswith("experts_up_weight"):
            v[:, :, f:] = 0
        elif name.endswith("experts_down_weight"):
            v[:, f:, :] = 0
        out[name] = jnp.asarray(v, jnp.float32).astype(dtype)
    return out


def _decoder(params, dtype="float32", cfg=CFG, **kw):
    return PagedKVDecoder({k: mx.nd.NDArray(v) for k, v in params.items()},
                          dtype=dtype, **{**SERVE, **kw}, **cfg)


def _rel_l2(got, want):
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


@pytest.fixture
def tm():
    telemetry.reset()
    saved = telemetry.current_override()
    telemetry.set_mode("trace")
    yield telemetry
    telemetry.set_mode(saved)
    telemetry.reset()


# ------------------------------------------------------------- (a) the scan
def _core_inputs(t, groups, seed=0, rows=1):
    h, p, n, k = (CORE[x] for x in ("num_heads", "head_dim", "state_size",
                                    "conv_kernel"))
    c = h * p + 2 * groups * n
    rs = np.random.RandomState(seed)
    dt_bias = np.log(np.expm1(np.exp(rs.uniform(np.log(1e-3), np.log(1e-1),
                                                h))))
    return dict(xbc=rs.randn(rows, t, c).astype("f"),
                dt=rs.randn(rows, t, h).astype("f"),
                w=rs.uniform(-.5, .5, (c, k)).astype("f"),
                b=rs.uniform(-.5, .5, (c,)).astype("f"),
                dt_bias=dt_bias.astype("f"),
                a_log=np.log(rs.uniform(1, 16, h)).astype("f"),
                d=rs.randn(h).astype("f"))


def _weights_of(v):
    return v["w"], v["b"], v["dt_bias"], v["a_log"], v["d"]


def _sequential(v, length, groups, row=0):
    """The reference's convolution and recurrence over the first ``length``
    positions: (y (length, H*P), state (H, P, N))."""
    h, p, n = CORE["num_heads"], CORE["head_dim"], CORE["state_size"]
    conv = ref.causal_conv(jnp.asarray(v["xbc"][row, :length]), v["w"],
                           v["b"])
    x, b, c = jnp.split(conv, [h * p, h * p + groups * n], axis=-1)
    dt = jax.nn.softplus(v["dt"][row, :length] + v["dt_bias"])
    y, state = ref.recurrence(
        x.reshape(length, h, p), dt, -jnp.exp(v["a_log"]),
        b.reshape(length, groups, n), c.reshape(length, groups, n), v["d"])
    return np.asarray(y).reshape(length, -1), np.asarray(state)


@pytest.mark.parametrize("groups", [2, 8])
@pytest.mark.parametrize("length", [1, 7, 8, 9, 17, 20])
def test_grouped_scan_is_the_sequential_recurrence(groups, length):
    """``Mamba2Scan`` with ``num_groups`` groups of B and C over a
    20-position bucket in chunks of 8, the length as data, two rows of
    different lengths: outputs before the length, the state at the length and
    the last three pre-activation columns are the reference's, head h reading
    group h // (H / G); float32 both sides."""
    v = _core_inputs(20, groups, rows=2)
    lengths = (length, 20 - length + 1)
    y, state, conv = ssm._mamba2_scan(
        dict(CORE, chunk_size=8, num_groups=groups), jnp.asarray(v["xbc"]),
        jnp.asarray(v["dt"]), *_weights_of(v),
        jnp.asarray([[float(n)] for n in lengths]))
    k = CORE["conv_kernel"]
    for row, n_real in enumerate(lengths):
        want_y, want_state = _sequential(v, n_real, groups, row)
        np.testing.assert_allclose(np.asarray(y[row, :n_real]), want_y,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(state[row]), want_state,
                                   rtol=1e-5, atol=1e-7)
        padded = np.concatenate([np.zeros((k - 1, v["xbc"].shape[-1]), "f"),
                                 v["xbc"][row]])
        assert np.array_equal(np.asarray(conv[row]),
                              padded[n_real:n_real + k - 1])
    assert state.dtype == conv.dtype == jnp.float32
    assert state.shape == (2, 8, 8, 16)


@pytest.mark.parametrize("groups", [2, 8])
@pytest.mark.parametrize("length", [2, 8, 13])
def test_grouped_step_continues_the_scan(groups, length):
    """``Mamba2Step`` from the state ``Mamba2Scan`` left at ``length`` is
    position ``length`` of the sequential recurrence; a row that rides along
    (negative ``stepped``) gets its state back bit for bit."""
    v = _core_inputs(20, groups, seed=1)
    attrs = dict(CORE, num_groups=groups)
    _, state, conv = ssm._mamba2_scan(
        dict(attrs, chunk_size=8), jnp.asarray(v["xbc"]),
        jnp.asarray(v["dt"]), *_weights_of(v), jnp.asarray([[float(length)]]))
    two = lambda a: jnp.concatenate([a, a])
    y, new_state, new_conv = ssm._mamba2_step(
        attrs, two(jnp.asarray(v["xbc"][:, length])),
        two(jnp.asarray(v["dt"][:, length])), *_weights_of(v), two(state),
        two(conv), jnp.asarray([[5.0], [-1.0]]))
    want_y, want_state = _sequential(v, length + 1, groups)
    np.testing.assert_allclose(np.asarray(y[0]), want_y[-1],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(new_state[0]), want_state,
                               rtol=1e-5, atol=1e-7)
    assert np.array_equal(np.asarray(new_conv[0, -1]), v["xbc"][0, length])
    assert np.array_equal(np.asarray(new_state[1]), np.asarray(state[0]))
    assert np.array_equal(np.asarray(new_conv[1]), np.asarray(conv[0]))


def _ungrouped_scan_as_it_was(attrs, xbc, dt, w, bias, dt_bias, a_log, d,
                              length):
    """``Mamba2Scan`` as PR 45 left it, before any group axis: the frozen
    arithmetic ``num_groups = 1`` is held to."""
    h, p, n = attrs["num_heads"], attrs["head_dim"], attrs["state_size"]
    k, q = attrs["conv_kernel"], attrs["chunk_size"]
    hi = jax.lax.Precision.HIGHEST
    bsz, t, _ = xbc.shape
    n_real = length.reshape(bsz).astype(jnp.int32)
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = jax.nn.silu(bias + sum(padded[:, j:j + t] * w[:, j]
                                  for j in range(k)))
    x = conv[..., :h * p].reshape(bsz, t, h, p)
    b, c = conv[..., h * p:h * p + n], conv[..., h * p + n:]
    live = jnp.arange(t)[None, :] < n_real[:, None]
    dt = jnp.where(live[..., None], jax.nn.softplus(dt + dt_bias), 0.0)
    a = -jnp.exp(a_log)
    pad = -t % q
    x_, dt_, b_, c_ = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),)
                               * (v.ndim - 2)) for v in (x, dt, b, c))
    nc = (t + pad) // q
    blocks = lambda v: jnp.moveaxis(v.reshape((bsz, nc, q) + v.shape[2:]),
                                    1, 0)
    xs = blocks(x_ * dt_[..., None])
    cum = jnp.cumsum(blocks(dt_ * a), axis=2)
    earlier = jnp.tril(jnp.ones((q, q), bool))[None, :, :, None]

    def one(state, blk):
        xs_c, cum_c, b_c, c_c = blk
        decay = jnp.exp(jnp.where(
            earlier, cum_c[:, :, None, :] - cum_c[:, None, :, :], -jnp.inf))
        cb = jnp.einsum("bln,bsn->bls", c_c, b_c, precision=hi)
        y = jnp.einsum("blsh,bshp->blhp", cb[..., None] * decay, xs_c,
                       precision=hi)
        y = y + jnp.einsum("bln,bhpn->blhp", c_c, state, precision=hi) \
            * jnp.exp(cum_c)[..., None]
        last = cum_c[:, -1]
        tail = jnp.exp(last[:, None, :] - cum_c)
        state = state * jnp.exp(last)[:, :, None, None] + jnp.einsum(
            "bsn,bshp->bhpn", b_c, xs_c * tail[..., None], precision=hi)
        return state, y

    state, y = jax.lax.scan(one, jnp.zeros((bsz, h, p, n), jnp.float32),
                            (xs, cum, blocks(b_), blocks(c_)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, nc * q, h, p)[:, :t]
    return (y + d[:, None] * x).reshape(bsz, t, -1), state


@pytest.mark.parametrize("said", [False, True], ids=["default", "num_groups=1"])
def test_one_group_is_the_operator_as_it_was_bit_for_bit(said):
    """``num_groups`` 1, said or left out, is the scan and the step
    ``granite_hybrid`` had before the operators knew groups: the same
    outputs and state bit for bit (the scan against its frozen arithmetic,
    the step against the update spelled without a group axis)."""
    v = _core_inputs(20, 1, seed=2)
    attrs = dict(CORE, **({"num_groups": 1} if said else {}))
    args = (jnp.asarray(v["xbc"]), jnp.asarray(v["dt"]), *_weights_of(v))
    length = jnp.asarray([[13.0]])
    y, state, conv = ssm._mamba2_scan(dict(attrs, chunk_size=8), *args,
                                      length)
    want_y, want_state = _ungrouped_scan_as_it_was(
        dict(CORE, chunk_size=8), *args, length)
    assert np.array_equal(np.asarray(y), np.asarray(want_y))
    assert np.array_equal(np.asarray(state), np.asarray(want_state))
    h, p, n = CORE["num_heads"], CORE["head_dim"], CORE["state_size"]
    xbc, dt = jnp.asarray(v["xbc"][:, 13]), jnp.asarray(v["dt"][:, 13])
    got = ssm._mamba2_step(attrs, xbc, dt, *_weights_of(v), state, conv,
                           jnp.asarray([[1.0]]))
    window = jnp.concatenate([conv, xbc[:, None, :]], axis=1)
    act = jax.nn.silu(v["b"] + sum(window[:, j] * v["w"][:, j]
                                   for j in range(4)))
    x = act[:, :h * p].reshape(1, h, p)
    b, c = act[:, h * p:h * p + n], act[:, h * p + n:]
    step = jax.nn.softplus(dt + v["dt_bias"])
    new = jnp.exp(step * -jnp.exp(v["a_log"]))[:, :, None, None] * state \
        + (step[..., None] * x)[..., None] * b[:, None, None, :]
    want = jnp.sum(new * c[:, None, None, :], axis=-1) + v["d"][:, None] * x
    assert np.array_equal(np.asarray(got[0]), np.asarray(want.reshape(1, -1)))
    assert np.array_equal(np.asarray(got[1]), np.asarray(new))


def test_mamba_operators_infer_grouped_weights_from_the_sizes():
    """Shape rules: with ``num_groups`` the convolution's channels, the
    columns a row keeps and the data are H*P + 2*G*N wide; heads that do not
    divide over the groups are refused."""
    sizes = dict(num_heads=4, head_dim=8, state_size=16, num_groups=2)
    weights = [mx.sym.Variable(n) for n in ("w", "b", "dtb", "alog", "d")]
    step = mx.sym.Mamba2Step(mx.sym.Variable("x"), mx.sym.Variable("dt"),
                             *weights, mx.sym.Variable("s"),
                             mx.sym.Variable("c"), mx.sym.Variable("go"),
                             **sizes)
    args, outs, _ = step.infer_shape(x=(5, 96))
    got = dict(zip(step.list_arguments(), args))
    assert (got["w"], got["s"], got["c"]) == ((96, 4), (5, 4, 8, 16),
                                              (5, 3, 96))
    assert outs == [(5, 32), (5, 4, 8, 16), (5, 3, 96)]
    v = _core_inputs(4, 3)
    with pytest.raises(MXNetError, match="do not divide"):
        ssm._mamba2_scan(dict(CORE, chunk_size=8, num_groups=3),
                         jnp.asarray(v["xbc"]), jnp.asarray(v["dt"]),
                         *_weights_of(v), jnp.asarray([[4.0]]))


def test_the_gated_norm_takes_its_statistics_a_group():
    """``_mamba2_mixer``'s norm with several groups: ``y * silu(z)`` divided
    by the root mean square of ITS group's H*P / G features, times a learned
    scale a feature (``MambaRMSNormGated`` with ``group_size``), and NOT by
    that of all H*P, which one group gives."""
    block = tf._nemotron_h_sizes(
        **{k: v for k, v in CFG.items() if k not in ("arch", "vocab_size")})
    inner, g = 128, block["mamba_groups"]
    fc = lambda data, width, tag, **kw: data     # the projections left out
    width = inner + tf._mamba_conv_dim(block) + block["mamba_heads"]
    mixed = tf._mamba2_mixer(
        fc, mx.sym.Variable("zxbcdt"), 0,
        lambda i, xbc, dt: mx.sym.slice_axis(xbc, axis=2, begin=0, end=inner),
        block)
    rs = np.random.RandomState(4)
    data, gamma = rs.randn(2, 3, width).astype("f"), rs.rand(inner).astype("f")
    exe = mixed.bind(mx.cpu(), {"zxbcdt": mx.nd.array(data),
                                "layer0_mamba_norm_gamma": mx.nd.array(gamma)},
                     grad_req="null")
    exe.forward(is_train=False)
    z, y = data[..., :inner], data[..., inner:2 * inner]
    gated = (y * (z / (1 + np.exp(-z)))).reshape(2, 3, g, inner // g)
    want = gated / np.sqrt(np.mean(gated ** 2, axis=-1, keepdims=True) + 1e-5)
    want = want.reshape(2, 3, inner) * gamma
    got = exe.outputs[0].asnumpy()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    flat = gated.reshape(2, 3, inner)
    one_group = flat / np.sqrt(np.mean(flat ** 2, axis=-1, keepdims=True)
                               + 1e-5) * gamma
    assert np.abs(got - one_group).max() > 1e-2


# ----------------------------------------------------------- (b) the experts
E, K_TOP, D, F = 8, 2, 32, 24


def _expert_layer(seed=0, dtype="float32", tokens=20):
    """(h (tokens, D), {the reference's names: arrays}) of one expert block,
    all E experts held, the stacks stored ``_lane_tiles(F)`` wide."""
    rs = np.random.RandomState(seed)
    stored = tf._lane_tiles(F)
    up, down = np.zeros((E, D, stored), "f"), np.zeros((E, stored, D), "f")
    up[:, :, :F] = rs.randn(E, D, F) * 0.3
    down[:, :F, :] = rs.randn(E, F, D) * 0.3
    p = {"router_weight": rs.randn(E, D), "router_bias": rs.randn(E) * 0.1,
         "experts_up_weight": up, "experts_down_weight": down,
         "shared_up_weight": rs.randn(48, D) * 0.3,
         "shared_down_weight": rs.randn(D, 48) * 0.3}
    return jnp.asarray(rs.randn(tokens, D), jnp.float32).astype(dtype), \
        {"l_" + k: jnp.asarray(v, jnp.float32).astype(dtype)
         for k, v in p.items()}


_MOE = dict(num_experts=E, num_hidden=tf._lane_tiles(F),
            num_experts_per_tok=K_TOP, scoring="sigmoid", router_bias=True,
            norm_topk_prob=True, routed_scaling_factor=2.5, gated=False,
            activation="relu2")
_REF_MOE = dict(num_experts_per_tok=K_TOP, routed_scaling_factor=2.5)


def _routed(h, p, **share):
    """``MoEFeedForward`` ungated over the layer's stacks, or a share."""
    first = share.get("local_expert_offset", 0)
    held = slice(first, first + (share.get("num_local_experts") or E))
    return moe._moe_feed_forward(
        dict(_MOE, **share), h, p["l_router_weight"],
        p["l_experts_up_weight"][held], p["l_experts_down_weight"][held],
        p["l_router_bias"])


def _shared(h, p):
    return ref.relu2(h @ p["l_shared_up_weight"].T) @ p["l_shared_down_weight"].T


def test_ungated_experts_are_the_dense_masked_sum():
    """``MoEFeedForward(gated=False, activation="relu2")`` (sorted rows, two
    ``ragged_dot``s, the un-sort) against the reference's loop over EVERY
    expert applied to every token and masked by the routing: the same
    function; ``load`` counts each expert's rows."""
    h, p = _expert_layer()
    y, load = _routed(h, p)
    with jax.default_matmul_precision("highest"):
        want = ref.moe_mixer(h, p, "l_", _REF_MOE) - _shared(h, p)
        _, chosen = ref.route(h, p["l_router_weight"], p["l_router_bias"],
                              K_TOP, 2.5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    assert np.array_equal(np.asarray(load),
                          np.bincount(np.asarray(chosen).ravel(), minlength=E))
    # the square is part of the function: relu alone is another one
    relu_only = sum(
        np.where((np.asarray(chosen) == e).any(-1, keepdims=True), 1, 0)
        * np.maximum(np.asarray(h) @ np.asarray(p["l_experts_up_weight"][e]),
                     0) @ np.asarray(p["l_experts_down_weight"][e])
        for e in range(E))
    assert np.abs(np.asarray(y)).max() > 0 and not np.allclose(
        np.asarray(y), relu_only, atol=1e-2)


@pytest.mark.parametrize("bad", [dict(gated=True, activation="relu2"),
                                 dict(gated=False, activation="silu"),
                                 dict(gated=False, activation="gelu")])
def test_a_pair_that_is_no_expert_here_is_refused(bad):
    h, p = _expert_layer()
    with pytest.raises(MXNetError, match="is not one of"):
        moe._moe_feed_forward(
            dict(_MOE, **bad), h, p["l_router_weight"],
            p["l_experts_up_weight"], p["l_experts_down_weight"],
            p["l_router_bias"])


def test_the_router_is_float32_whatever_the_storage_type():
    """bfloat16 rows and a bfloat16 router: the scores are the float32
    product of the stored values at the highest matmul precision, so the
    experts chosen are the float32 reference's over the same values, token
    for token; one bfloat16 pass of the same product chooses otherwise for
    some of 4,000 tokens. On the chip the cell's check cannot see this (the
    bfloat16 residual stream flips as many near-tied experts as a bfloat16
    router would: configs/nemotron-3-nano-30b-a3b.json ``check.why``), so it
    is held here, where nothing else rounds."""
    rs = np.random.RandomState(11)
    h = jnp.asarray(rs.randn(4000, D), jnp.float32).astype(jnp.bfloat16)
    _, p = _expert_layer(seed=11, dtype="bfloat16")
    _, load = _routed(h, p)
    with jax.default_matmul_precision("highest"):
        _, chosen = ref.route(h.astype(jnp.float32), p["l_router_weight"],
                              p["l_router_bias"], K_TOP, 2.5)
    assert np.array_equal(np.asarray(load), np.bincount(
        np.asarray(chosen).ravel(), minlength=E))
    low = jax.nn.sigmoid(jnp.dot(h, p["l_router_weight"].T)
                         .astype(jnp.float32))      # ONE bfloat16 pass
    _, coarse = jax.lax.top_k(low + p["l_router_bias"].astype(jnp.float32),
                              K_TOP)
    assert (np.sort(np.asarray(coarse)) != np.sort(np.asarray(chosen))).any()


def test_the_shares_add_up_to_the_uncut_layer():
    """Two chips share a layer: experts 0-3 on one, 4-7 on the other, each
    routing over all 8 and computing its own experts' part. The two parts and
    the shared expert, which every chip computes alike, counted ONCE, are the
    uncut reference layer; and each share is the reference's under the same
    share."""
    h, p = _expert_layer(seed=3)
    shares = [dict(num_local_experts=4, local_expert_offset=o) for o in (0, 4)]
    parts = [_routed(h, p, **share) for share in shares]
    with jax.default_matmul_precision("highest"):
        whole = ref.moe_mixer(h, p, "l_", _REF_MOE)
        shared = _shared(h, p)
        for (y, load), share in zip(parts, shares):
            first = share["local_expert_offset"]
            cut = dict(p, **{
                "l_experts_%s_weight" % w: p["l_experts_%s_weight" % w][
                    first:first + 4] for w in ("up", "down")})
            want = ref.moe_mixer(h, cut, "l_", dict(_REF_MOE, **share))
            np.testing.assert_allclose(np.asarray(y + shared),
                                       np.asarray(want), rtol=2e-5, atol=2e-5)
    total = parts[0][0] + parts[1][0] + shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-5, atol=2e-5)
    # both shares count ALL the experts' rows, and neither part is nothing
    assert np.array_equal(np.asarray(parts[0][1]), np.asarray(parts[1][1]))
    assert all(float(jnp.abs(y).max()) > 1e-3 for y, _ in parts)


# a width that is NO whole lane tiles, 192 = 64 (mod 128) as 1,856 is, stored
# padded to 256; the rows an expert gets straddle tiles, one expert has none
_GROUPS = (64, [5, 0, 40, 3, 16])


@pytest.mark.parametrize("depth", [0, 1, 2, 3])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_padded_kernel_is_the_published_width_bit_for_bit(depth, dtype):
    """The kernel's two calls of an ungated expert (relu^2 fused into the
    first, written once in the storage type), interpreted, over stacks stored
    ``_lane_tiles(192)`` = 256 wide with zero columns and rows, at every
    depth of the fetch ring: bit for bit the kernel over the UNPADDED 192
    (one whole-width column tile through the pipeline's blocks, which the
    interpreter takes and Mosaic ran at half the rate), so relu(0)^2 = 0 and
    a zero row of ``down`` add exactly nothing; and ``ragged_dot`` over the
    unpadded stacks to the order of a float32 sum."""
    m, sizes = _GROUPS
    width, stored = 192, tf._lane_tiles(192)
    assert width % 128 == 64 and stored == 256
    rs = np.random.RandomState(7)
    draw = lambda *shape: jnp.asarray(rs.randn(*shape).astype("f")) * 0.3
    up, down = draw(len(sizes), 32, width), draw(len(sizes), width, 128)
    rows = draw(m, 32).astype(dtype)
    up, down = up.astype(dtype), down.astype(dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    dot = lambda a, b: jax.lax.ragged_dot(
        a, b, sizes, preferred_element_type=jnp.float32)
    act = jnp.square(jnp.maximum(dot(rows, up), 0.0)).astype(dtype)
    held = (np.arange(m) < int(sizes.sum()))[:, None]
    want = jnp.where(held, dot(act, down), 0)
    pad = stored - width
    up_p = jnp.pad(up, ((0, 0), (0, 0), (0, pad)))
    down_p = jnp.pad(down, ((0, 0), (0, pad), (0, 0)))
    meta = kernel.visits(sizes, m, 16)
    first = kernel.grouped_matmul(rows, (up_p,), meta, tm=16, tn=128,
                                  depth=depth, activation="relu2",
                                  interpret=True)
    assert first.dtype == jnp.dtype(dtype) and first.shape == (m, stored)
    assert not np.asarray(first[:, width:], np.float32).any()
    got = kernel.grouped_matmul(first, (down_p,), meta, tm=16, tn=128,
                                depth=depth, interpret=True)
    assert got.dtype == jnp.float32
    narrow = kernel.grouped_matmul(rows, (up,), meta, tm=16, tn=width,
                                   depth=0, activation="relu2",
                                   interpret=True)
    assert np.array_equal(np.asarray(first[:, :width], np.float32),
                          np.asarray(narrow, np.float32))
    assert np.array_equal(np.asarray(got), np.asarray(kernel.grouped_matmul(
        narrow, (down,), meta, tm=16, tn=128, depth=0, interpret=True)))
    # a float32 sum's order; in bfloat16 one rounding of the activation,
    # which the second product carries on
    tol = 2.0 ** -6 if dtype == "bfloat16" else 1e-5
    np.testing.assert_allclose(np.asarray(first[:, :width], np.float32),
                               np.asarray(jnp.where(held, act, 0), np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=8 * tol)
    with pytest.raises(ValueError, match="activation"):
        kernel.grouped_matmul(rows, (up_p, up_p), meta, tm=16, tn=128,
                              depth=depth, activation="relu2", interpret=True)


def test_the_operator_runs_the_kernel_where_its_rule_says(monkeypatch):
    """``MoEFeedForward`` ungated with its rule held to the kernel (the
    kernel interpreted: the CPU) gives what the operator gives through
    ``ragged_dot``, a share held and rows past the last group among them;
    ``expert_ffn`` takes the ungated tiles (ONE stack in the first ring)."""
    h, p = _expert_layer(seed=5, tokens=24)
    share = dict(num_local_experts=4, local_expert_offset=4)
    want, load = _routed(h, p, **share)
    monkeypatch.setattr(kernel, "moe_form", lambda *a: "kernel")
    got, load_k = _routed(h, p, **share)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    assert np.array_equal(np.asarray(load), np.asarray(load_k))
    # the ring of an ungated first call holds one matrix a slot, not two:
    # at the published widths three whole matrices fit where two pairs do not
    assert kernel.tiles(192, 64, 2688, 1920, jnp.bfloat16, gated=False) \
        == (32, 1920, 2688, 3)
    assert kernel.tiles(192, 64, 2688, 1920, jnp.bfloat16)[1] < 1920


# ------------------------------------------ (c) prefill, then decode: the cache
def _drive(dec, lengths, steps, seed=0):
    """Admit prompts of ``lengths`` into lanes side by side, then step them
    ALL ``steps`` times with drawn tokens: [(tokens fed, logits rows, the
    lane's rows of state after its last step)]."""
    rs = np.random.RandomState(seed)
    fed = [list(rs.randint(1, CFG["vocab_size"], n)) for n in lengths]
    seqs, got = [], []
    for toks in fed:
        seq, logits = dec.admit(np.asarray(toks, np.float32))
        seqs.append(seq)
        got.append([logits])
    for _ in range(steps):
        nxt = rs.randint(1, CFG["vocab_size"], len(seqs))
        out = dec.step({seq: int(t) for seq, t in zip(seqs, nxt)})
        for i, (seq, t) in enumerate(zip(seqs, nxt)):
            fed[i].append(int(t))
            got[i].append(out[seq])
    states = [dec.lane_state(seq) for seq in seqs]
    for seq in seqs:
        dec.retire(seq)
    return [(np.asarray(t, np.int32), np.stack(g), s)
            for t, g, s in zip(fed, got, states)]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_admit_then_steps_agree_with_the_full_forward(dtype):
    """Three lanes admitted at DIFFERENT lengths (inside the first chunk and
    page, across a chunk edge, the whole bucket) and stepped together 12
    times, every lane across a page boundary of 8 slots: the logits ``admit``
    returns and those of every step through the cache (rows of the three
    Mamba blocks, pages of the attention block, nothing for the experts)
    against the reference's full forward over each lane's whole sequence
    under the same share (experts 0-3 of 8 held), row by row; the first
    block's state and columns after the last step against the sequential
    recurrence."""
    params = _weights(dtype)
    dec = _decoder(params, dtype)
    lanes = _drive(dec, (3, 9, 32), 12)
    for toks, got, state in lanes:
        want = np.asarray(ref.logits(params, jnp.asarray(toks), CFG,
                                     last=got.shape[0]))
        assert got.dtype == np.float32 and got.shape == want.shape
        errors = _rel_l2(got, want)
        if dtype == "float32":
            assert errors.max() < F32_TOL, errors
        else:
            assert np.sort(errors)[len(errors) // 4] < BF16_TOL, errors
        assert sorted(state) == sorted(n for n, kind, _ in dec._cache
                                       if kind == "row")
        for name, ours in zip(("ssm_state_0", "conv_state_0"),
                              ref.first_mixer_state(params,
                                                    jnp.asarray(toks), CFG)):
            assert state[name].shape == ours.shape
            assert _rel_l2(np.asarray(state[name]).reshape(1, -1),
                           np.asarray(ours).reshape(1, -1)).max() \
                < (F32_TOL if dtype == "float32" else BF16_TOL)
    types = {name: str(dec._dec_exe.arg_dict[name].dtype)
             for name, _, _ in dec._cache}
    assert types["ssm_state_0"] == types["conv_state_4"] == "float32"
    assert types["kv_k_5"] == types["kv_v_5"] == dtype


def test_the_cache_is_rows_and_pools_and_nothing_for_the_experts():
    """``decode_cache``: a Mamba block's two rows, an attention block's two
    pools, in block order; an expert block keeps nothing. The prefill exports
    exactly those after the logits and ``moe_load`` (expert blocks, experts)
    last; the decode graph takes them back."""
    cache = tf.decode_cache(**CFG)
    assert [(n, k) for n, k, _ in cache] == [
        ("ssm_state_0", "row"), ("conv_state_0", "row"),
        ("ssm_state_2", "row"), ("conv_state_2", "row"),
        ("ssm_state_4", "row"), ("conv_state_4", "row"),
        ("kv_k_5", "pool"), ("kv_v_5", "pool")]
    assert cache[0][2] == (8, 16, 16) and cache[1][2] == (3, 128 + 2 * 4 * 16)
    assert cache[6][2] == (2, 16)
    prefill = tf.get_prefill_symbol(prefill_len=32, **CFG)
    _, outs, _ = prefill.infer_shape(data=(1, 32))
    assert outs[0] == (1, CFG["vocab_size"]) and outs[-1] == (3, 8)
    assert len(outs) == 1 + len(cache) + 1
    decode = tf.get_decode_symbol(max_len=256, page_size=8, **CFG)
    assert {n for n, _, _ in cache} <= set(decode.list_arguments())
    assert decode.list_outputs()[-1] == "moe_load_output"
    with pytest.raises(MXNetError, match="layer_types must name"):
        tf.get_decode_symbol(max_len=256, page_size=8,
                             **dict(CFG, layer_types=["mamba"] * 6 + ["mlp"]))


def test_the_expert_counters_count_this_archs_expert_blocks(tm):
    """A step's ``serving.moe.*`` counters read ``moe_load`` of the THREE
    expert blocks (blocks 1, 3 and 6 of seven): every stepped lane's 2
    assignments a block, those that reached a held expert, the held experts
    touched; ``serving.step_context_tokens`` counts the one pool layer's
    contexts; the admission hands the rows over under ``serving.admit.state``."""
    dec = _decoder(_weights("float32"))
    dec.warmup()
    # six rows of (8 x 16 x 16 + 3 x 256) float32 a lane
    assert tm.gauge("serving.state_bytes").value \
        == 4 * SERVE["lanes"] * 3 * (8 * 16 * 16 + 3 * (128 + 2 * 4 * 16))
    assert tm.gauge("serving.moe.xla_layers.decode").value == 3
    tm.clear_events()
    c0 = tm.counters()
    seq, _ = dec.admit(np.arange(1, 10, dtype=np.float32))
    dec.step({seq: 5})
    c = {k: v - c0.get(k, 0) for k, v in tm.counters().items()}
    # lanes that ride along pass through the experts too: 4 lanes x 2 x 3
    assert c["serving.moe.step_assignments"] == SERVE["lanes"] * 2 * 3
    assert 0 < c["serving.moe.step_local_assignments"] \
        <= c["serving.moe.step_assignments"]
    assert 0 < c["serving.moe.step_experts_touched"] <= 3 * 4
    assert c["serving.step_context_tokens"] == 10
    assert c["serving.moe.assignments"] == 32 * 2 * 3   # the bucket's rows
    assert c["serving.step_slot_writes"] == 2           # ONE pool pair
    states = [attrs for name, _t0, _dur, _tid, attrs in tm.drain_events()
              if name == "serving.admit.state"]
    assert len(states) == 1 and states[0]["buffers"] == 6


# ------------------------------------------------------- what is not ported
@pytest.mark.parametrize("entry", ["fork", "rollback", "verify_chunk",
                                   "step_megastep", "prefix_cache",
                                   "get_symbol", "get_chunk_symbol"])
def test_unported_entry_points_refuse(entry):
    """As ``granite_hybrid``: a recurrent state is one row, overwritten at
    every token, and the chunk, verify and megastep programs know the
    Vaswani block only."""
    if entry.startswith("get_"):
        with pytest.raises(MXNetError, match="not built for arch "
                           "'nemotron_h' yet"):
            getattr(tf, entry)(arch="nemotron_h")
        return
    params = {k: mx.nd.NDArray(v) for k, v in _weights("float32").items()}
    if entry == "prefix_cache":
        with pytest.raises(MXNetError, match="prefix_cache=True is not built "
                           "for arch 'nemotron_h' yet"):
            PagedKVDecoder(params, prefix_cache=True, **SERVE, **CFG)
        return
    dec = PagedKVDecoder(params, **SERVE, **CFG)
    seq, _ = dec.admit(np.arange(1, 6, dtype=np.float32))
    call = {"fork": lambda: dec.fork(seq),
            "rollback": lambda: dec.rollback(seq, 2),
            "verify_chunk": lambda: dec.verify_chunk(seq, [1, 2]),
            "step_megastep": lambda: dec.step_megastep({seq: 1}, k=2)}[entry]
    with pytest.raises(MXNetError, match="not built for arch 'nemotron_h'"):
        call()


# ------------------------------------------------- (d) the published widths
PUBLISHED = dict(
    arch="nemotron_h", vocab_size=65536, num_layers=13, num_heads=32,
    num_kv_heads=2, head_dim=128, model_dim=2688, ffn_dim=1856,
    layer_types=[KINDS[c] for c in "MEMEM*EMEMEM*"], mamba_heads=64,
    mamba_head_dim=64, mamba_state=128, mamba_groups=8, mamba_conv=4,
    mamba_chunk=128, moe_ffn_dim=1856, shared_ffn_dim=3712, num_experts=128,
    num_experts_per_tok=6, num_local_experts=64, local_expert_offset=0,
    routed_scaling_factor=2.5, norm_topk_prob=True, rms_eps=1e-5)


def test_parameters_and_cache_at_the_published_widths():
    """``param_shapes`` and ``decode_cache`` of the benchmark's cut (blocks
    0-12, 64 of 128 experts, half the vocabulary) are ISSUE 48's table: a
    Mamba block 38.74 M parameters, an attention block 23.40 M, an expert
    block of 64 held experts 658.9 M AT THE PUBLISHED 1,856 (the stacks are
    stored 1,920 wide: 64 zero columns and rows an expert beside them),
    embedding and head 352.3 M: 3,926 M; a lane's rows (64 x 64 x 128 +
    3 x 6,144) float32 a Mamba block, a token's K and V 2 x 128 a pool."""
    shapes = tf.param_shapes(**PUBLISHED)
    count = lambda prefix: sum(int(np.prod(s)) for n, s in shapes.items()
                               if n.startswith(prefix))
    assert count("layer0_") == 2688 + 2688 * 10304 + 6144 * 5 + 3 * 64 \
        + 4096 + 4096 * 2688 == 38_744_896
    assert count("layer5_") == 2688 + 2688 * 4608 + 4096 * 2688 == 23_399_040
    assert shapes["layer1_experts_up_weight"] == (64, 2688, 1920)
    assert shapes["layer1_experts_down_weight"] == (64, 1920, 2688)
    padding = 64 * 2 * 2688 * (1920 - 1856)
    assert count("layer1_") - padding == 2688 + 128 * 2688 + 128 \
        + 2 * 2688 * 3712 + 64 * 2 * 2688 * 1856 == 658_885_376
    assert count("embed_") + count("lm_head_") + count("final_") \
        == 2 * 65536 * 2688 + 2688
    total = sum(int(np.prod(s)) for s in shapes.values()) - 5 * padding
    assert total == 6 * 38_744_896 + 2 * 23_399_040 + 5 * 658_885_376 \
        + 2 * 65536 * 2688 + 2688 == 3_926_018_560
    cache = tf.decode_cache(**PUBLISHED)
    rows = [s for _, kind, s in cache if kind == "row"]
    pools = [s for _, kind, s in cache if kind == "pool"]
    assert rows == [(64, 64, 128), (3, 6144)] * 6 and pools == [(2, 128)] * 4
    # 64 lanes: 0.83 GB of rows; 524,288 slots: 1.07 GB of pools in bfloat16
    assert 64 * sum(int(np.prod(s)) for s in rows) * 4 == 833_617_920
    assert 524288 * sum(int(np.prod(s)) for s in pools) * 2 == 1_073_741_824
