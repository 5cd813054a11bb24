"""The dots3-note block (``arch="dots3_note"`` of models/transformer.py and
serving.PagedKVDecoder: latent attention with a query-side low rank in two
geometries, a full layer's read a learned selection over TWO pools on one page
table, a window layer's a ring of latents, a head-wise gate, expert layers
that hold a share of the experts they route over beside a shared one) against
the benchmark's plain reference, benchmark/reference/dots3_note_decoder.py,
on seeded weights at small sizes: the published pattern's first six layers
(full, full, 3 x window, full; the first dense, five of experts), 4 full
heads over a latent of 16 and 2 window heads over a latent of 32, a window of
9, an indexer of 8 heads of 8 that selects 16 of 48-96 tokens (so the
selection BITES), 32 experts of which experts 8..15 are held, 4 a token.
Every tolerance says where it comes from.
"""
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
from mxnet_tpu.ops import attention
from mxnet_tpu.ops.registry import get_op, parse_attrs
from mxnet_tpu.serving import PagedKVDecoder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reference():
    """A fresh copy of the reference module: a test may bend one of its
    functions without any other test seeing it."""
    path = os.path.join(ROOT, "benchmark", "reference",
                        "dots3_note_decoder.py")
    spec = importlib.util.spec_from_file_location("dots3_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = reference()

W, TOPK = 9, 16
FULL, WINDOW = "full_attention", "sliding_attention"
# vocabulary above 256 on purpose: bfloat16 holds whole numbers to 256 only
CFG = dict(arch="dots3_note", vocab_size=600, num_layers=6, num_heads=4,
           model_dim=48, ffn_dim=64, moe_ffn_dim=16, num_experts=32,
           num_experts_per_tok=4, num_local_experts=8, local_expert_offset=8,
           num_shared_experts=1, first_dense_layers=1,
           layer_types=[FULL, FULL, WINDOW, WINDOW, WINDOW, FULL],
           q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
           qk_rope_head_dim=4, v_head_dim=8, rope_theta=8e7, swa_num_heads=2,
           swa_q_lora_rank=24, swa_kv_lora_rank=32, swa_qk_nope_head_dim=12,
           swa_qk_rope_head_dim=4, swa_v_head_dim=8, swa_rope_theta=5e4,
           sliding_window=W, index_n_heads=8, index_head_dim=8,
           index_topk=TOPK, lora_rescale=True, rms_eps=1e-5,
           routed_scaling_factor=1.0, norm_topk_prob=True)
# a bucket of eight blocks of W - 1: the window layers score a ragged band
SERVE = dict(max_len=96, prefill_len=64, page_size=8, lanes=4)

# float32 on both sides on the CPU: what is left is the order of the sums
# (grouped matmul against a loop over experts, absorbed against materialised,
# query blocks and a band's blocks against the full softmax); runs read 2e-7
# to 8e-7
F32_TOL = 1e-4
# bfloat16 weights, activations, pools and rings against the float32 reference
# over the same (bfloat16-valued) weights: a LOWER-QUARTILE row, in the manner
# of the benchmark's check: near-tied experts and near-tied index scores at
# the 16th place flip under bfloat16 and such a row reads 0.05 to 0.3; rows
# whose choices are the reference's read 1e-2 to 3e-2 over six layers
BF16_TOL = 6e-2
BF16_RING_TOL = 3e-2


def _lower_quartile(err):
    return np.sort(err)[-(-len(err) // 4) - 1]


def _weights(dtype="float32", seed=0, cfg=CFG):
    """N(0, 0.1) matrices but the ones scores are made of (q_b, kv_a, the
    indexer's three), N(0, 0.3): scores of order one, so that WHICH keys a
    query may attend matters; a unit-variance embedding, a selection bias
    N(0, 0.5), an index-key bias N(0, 0.1)."""
    rs = np.random.RandomState(seed)
    out = {}
    for name, shape in sorted(tf.param_shapes(**cfg).items()):
        if name.endswith("gamma"):
            v = np.ones(shape, "f")
        else:
            sharp = any(name.endswith(t + "_weight")
                        for t in ("qb", "kva", "iq", "ik", "iw"))
            v = rs.randn(*shape).astype("f") * (
                1.0 if name == "embed_weight"
                else 0.5 if name.endswith("router_bias")
                else 0.3 if sharp else 0.1)
        out[name] = jnp.asarray(v).astype(dtype)
    return out


def _decoder(params, dtype="float32", cfg=CFG, **kw):
    return PagedKVDecoder({k: mx.nd.NDArray(v) for k, v in params.items()},
                          dtype=dtype, **dict(SERVE, **kw), **cfg)


def _rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def _kept(dec, seq, *names):
    rows = dec.lane_state(seq, names)
    return [np.array(rows[n]).astype(np.float32) for n in names]


def _ring_error(ring, rows, upto):
    """A ring (1, W, d) against the reference's rows (1, T, d) at the
    positions it holds once position ``upto`` is written."""
    held = np.arange(max(0, upto - W + 1), upto + 1)
    got, want = ring[:, held % W], np.asarray(rows)[:, held]
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _chosen(row):
    return sorted(int(p) for p in row if p >= 0)


def _admit_and_step(dec, toks, length):
    """Admit ``toks[:length]``, then feed the rest one step each: (the 1 +
    steps logits rows, [layer 2's ring, layer 0's selection] after the
    admission, the same after the last step)."""
    seq, logits = dec.admit(np.asarray(toks[:length], np.float32))
    admitted = _kept(dec, seq, "ring_c_2", "sparse_sel_0")
    got = [np.asarray(logits)]
    for tok in toks[length:]:
        got.append(np.asarray(dec.step({seq: int(tok)})[seq]))
    last = _kept(dec, seq, "ring_c_2", "sparse_sel_0")
    dec.retire(seq)
    return np.stack(got), admitted, last


def _tokens(n, seed=1):
    return np.random.RandomState(seed).randint(1, CFG["vocab_size"], size=n)


T = 72   # every sequence scored here: one compile of each reference function
GAUGES = {}     # what the shared decoder's warm-up set (``served``)


@pytest.fixture(scope="module")
def served():
    """(float32 weights, ONE warmed decoder over them): the block's two
    programs compile once for the tests that only admit, step and retire."""
    params = _weights()
    telemetry.reset()
    saved = telemetry.current_override()
    telemetry.set_mode("trace")     # warm-up sets the gauges where it is on
    try:
        dec = _decoder(params).warmup()
        GAUGES.update({name: telemetry.gauge(name).value for name in (
            "serving.pool_read.selected_layers",
            "serving.prefill_attention.sparse_layers",
            "serving.prefill_attention.band_layers",
            "serving.latent_pool_bytes", "serving.index_pool_bytes",
            "serving.window_ring_bytes")})
    finally:
        telemetry.set_mode(saved)
        telemetry.reset()
    return params, dec


def _jitted(mod, cfg=None):
    """``mod``'s three functions over ``cfg``, jitted: a reference traced
    once a (module, configuration) and run at the one length ``T``."""
    cfg = cfg or CFG
    return (jax.jit(lambda p, toks: mod.logits(p, toks, cfg)),
            jax.jit(lambda p, toks: mod.first_window_rows(p, toks, cfg)),
            jax.jit(lambda p, toks, at: mod.first_selected(p, toks, cfg, at)))


REF_LOGITS, REF_ROWS, REF_SELECTED = _jitted(ref)


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
            jnp.asarray(rs.randn(1, hkv, t, dv), jnp.float32))


def _masked(q, k, v, allowed):
    """The reference's way: full T x T scores under a mask (T, T)."""
    hq = q.shape[1]
    kk, vv = (jnp.repeat(a[0], hq // a.shape[1], axis=0) for a in (k, v))
    s = jnp.einsum("htd,hsd->hts", q[0], kk) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(allowed[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hts,hsd->htd", p, vv)[None]


def _mha(*inputs, **attrs):
    op = get_op("MultiHeadAttention")
    return op.fn(parse_attrs(op, dict(causal=True, **attrs)), *inputs)


def _window_mask(t, window):
    seen = jnp.tril(jnp.ones((t, t), bool))
    return seen & ~jnp.tril(jnp.ones((t, t), bool), k=-window)


@pytest.mark.parametrize("t,window,runs", [
    (64, 9, 1),     # blocks of W - 1 = 8: T is no multiple of the window
    (64, 9, 4),     # the same band a run of two blocks at a time
    (32, 8, 2),     # blocks of W, two runs: the chunked form of mimo's band
    (30, 9, 1),     # neither W nor W - 1 divides T: the masked full scores
])
def test_a_ragged_window_is_a_band_of_blocks_one_narrower(monkeypatch, t,
                                                          window, runs):
    """``MultiHeadAttention(window=W)`` over T positions, T a multiple of
    W - 1 and not of W (the model's 513 under a bucket of 8,192), scores a
    band of blocks W - 1 wide; a band past ``_SCORE_BYTES`` runs a few blocks
    at a time; both equal the masked full scores (float32: the order of a
    sum)."""
    q, k, v = _qkv(t)
    blk = attention._band_block(t, window)
    assert blk == {64: 8, 32: 8, 30: 0}[t]
    form = attention.attention_form(q, k, v, True, window)
    assert form == ("band" if blk else "dense")
    if runs > 1:    # the whole band's scores are 4 * H * T * 2 * blk bytes
        monkeypatch.setattr(attention, "_SCORE_BYTES",
                            4 * 4 * t * 2 * blk // runs)
    got = _mha(q, k, v, window=window)
    want = _masked(q, k, v, _window_mask(t, window))
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-6


def _parents_band(q, k, v, w, scale):
    """``_band_attention`` as the parent commit spelled it (PR 38), for the
    programs that were there: blocks of the window, float32 operands."""
    b, hkv, g, t, d = q.shape
    nb = t // w

    def banded(a):
        blocks = a.reshape(b, hkv, nb, w, a.shape[-1])
        before = jnp.concatenate(
            [jnp.zeros_like(blocks[:, :, :1]), blocks[:, :, :-1]], axis=2)
        return jnp.concatenate([before, blocks], axis=3)

    s = jnp.einsum("bkgnqd,bknud->bkgnqu", q.reshape(b, hkv, g, nb, w, d),
                   banded(k)) * scale
    ahead = w + jnp.arange(w)[:, None] - jnp.arange(2 * w)[None, :]
    live = (ahead >= 0) & (ahead < w)
    first = live & (jnp.arange(2 * w)[None, :] >= w)
    mask = jnp.where(jnp.arange(nb)[:, None, None] == 0, first, live)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bkgnqu,bknud->bkgnqd", p, banded(v)).reshape(
        b, hkv, g, t, v.shape[-1])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_band_of_whole_windows_is_the_parents_bit_for_bit(dtype):
    """Where the window divides the bucket and the band's scores fit (every
    cell the benchmark had: mimo's 128 under 2,048, phi4flash's 512), the
    band is the parent's expression: the same output bit for bit, keys and
    values cast whole before they are cut into blocks."""
    q, k, v = (a.astype(dtype) for a in _qkv(32, seed=3))
    qf = q.astype(jnp.float32).reshape(1, 2, 2, 32, 12)
    got = attention._band_attention(qf, k, v, 8, 0.25, None)
    want = _parents_band(qf, k.astype(jnp.float32), v.astype(jnp.float32),
                         8, 0.25)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# graph JSON of the tiny blocks below at the parent commit (1e8a73d), each
# built under a fresh ``NameManager`` (auto-names count from 0): where
# an operator gained a keyword, the graphs that do not name it are unchanged
_PARENTS_GRAPHS = {
    ("mimo_v2_flash", "prefill"): "90e5c0a1158ac13fc6f69a6187813ff75a233e7e",
    ("mimo_v2_flash", "decode"): "a57c0a28996713f751638eb3a44660137f1f2841",
    ("deepseek_v3", "prefill"): "0ae408bc7fa5d85cc2b1400afd5cafdd1db343a7",
    ("deepseek_v3", "decode"): "d3c25a73558f7cc28d820db6513ed01aaba324c0",
    ("vaswani", "prefill"): "343d10df9e381a706210d18bdf29c9fd11411cb7",
    ("vaswani", "decode"): "2f653b34eeb2bf385a19b70de0c02f7d0c93a3bd",
    ("olmoe", "prefill"): "6fd8a1e71f02d52f3601af7d1bde2fd992664913",
    ("olmoe", "decode"): "efef3f04c863226e6de5254c1cf507a6d9702e31",
    ("granite_hybrid", "prefill"): "48e644a734a506565e02699611bd27038befd91b",
    ("granite_hybrid", "decode"): "b718b88ed74668d92c9a84e9ee5536751f79f3e3",
    ("phi4flash", "prefill"): "f4906feca6aa6ed7210b71249ddbe79384421e62",
    ("phi4flash", "decode"): "104274a22cc03645f3bb639a327324cbe7afe90f"}
_OLDER = {
    "vaswani": dict(vocab_size=50, num_layers=2, num_heads=2, model_dim=32,
                    ffn_dim=64),
    "olmoe": dict(vocab_size=60, num_layers=2, num_heads=4, head_dim=8,
                  model_dim=32, ffn_dim=16, num_experts=4,
                  num_experts_per_tok=2, rope_theta=10000.0, rms_eps=1e-5),
    "granite_hybrid": dict(
        vocab_size=60, num_layers=3, num_heads=4, num_kv_heads=2, head_dim=8,
        model_dim=32, ffn_dim=48, layer_types=["mamba", "attention", "mamba"],
        mamba_heads=4, mamba_head_dim=8, mamba_state=8, mamba_conv=4,
        mamba_chunk=8, embedding_multiplier=12.0, attention_multiplier=0.125,
        residual_multiplier=0.22, logits_scaling=8.0, rms_eps=1e-5),
    "phi4flash": dict(vocab_size=600, num_layers=12, num_heads=8,
                      num_kv_heads=4, head_dim=8, model_dim=64, ffn_dim=96,
                      sliding_window=8, mamba_state=4, mamba_conv=4,
                      mamba_expand=2, mamba_dt_rank=5),
    "mimo_v2_flash": dict(
        vocab_size=600, num_layers=7, num_heads=4, num_kv_heads=1,
        swa_num_kv_heads=2, head_dim=12, v_head_dim=8, model_dim=48,
        ffn_dim=64, moe_ffn_dim=16, num_experts=32, num_experts_per_tok=4,
        num_local_experts=8, local_expert_offset=8,
        hybrid_layer_pattern=[0, 1, 1, 1, 1, 0, 1],
        moe_layer_freq=[0, 1, 1, 1, 1, 1, 1], sliding_window=8, rotary_dim=4,
        rope_theta=5e6, swa_rope_theta=1e4, attention_value_scale=0.707,
        rms_eps=1e-5, routed_scaling_factor=1.0, norm_topk_prob=True),
    "deepseek_v3": dict(
        vocab_size=600, num_layers=3, num_heads=4, model_dim=48, ffn_dim=64,
        moe_ffn_dim=16, num_experts=8, num_experts_per_tok=2,
        num_shared_experts=1, first_dense_layers=1, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, kv_lora_rank=16, rope_theta=1e6,
        rms_eps=1e-6, routed_scaling_factor=2.0, norm_topk_prob=True)}


@pytest.mark.parametrize("arch,program", list(_PARENTS_GRAPHS))
def test_the_older_blocks_graphs_are_the_parents(arch, program):
    """``KVPoolAttention(selected=)``, ``KVRingAttention(value_dim=)`` and
    ``MultiHeadAttention(topk=)`` default to what was there: the graphs of
    the two blocks that share those operators, of the three that run
    ``MultiHeadAttention`` plainly (``vaswani``, ``olmoe``,
    ``granite_hybrid``) and of the other ring block (``phi4flash``) are the
    parent's text, so their programs are the parent's (the operators' default
    paths are untouched; the band's is held bit for bit above)."""
    from mxnet_tpu.name import NameManager

    with NameManager():     # auto-names count from 0, as in a fresh process
        sym = tf.get_prefill_symbol(
            prefill_len=32, arch=arch, **_OLDER[arch]) \
            if program == "prefill" else tf.get_decode_symbol(
                max_len=256, page_size=8, arch=arch, **_OLDER[arch])
    assert hashlib.sha1(sym.tojson().encode()).hexdigest() \
        == _PARENTS_GRAPHS[arch, program]


def _indexer(t, hi=4, di=8, seed=5):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(1, hi, t, di), jnp.float32),
            jnp.asarray(rs.randn(1, 1, t, di), jnp.float32),
            jnp.asarray(rs.randn(1, t, hi), jnp.float32))


def _topk_mask(iq, ik, iw, topk):
    """The reference's way: ``top_k`` indices of the causal index scores,
    scattered into a mask."""
    t = iq.shape[2]
    score = jnp.einsum("hqs,qh->qs", jax.nn.relu(
        jnp.einsum("hqd,sd->hqs", iq[0], ik[0, 0])), iw[0])
    causal = jnp.tril(jnp.ones((t, t), bool))
    _, at = jax.lax.top_k(jnp.where(causal, score, -jnp.inf), min(topk, t))
    chosen = jnp.zeros((t, t), bool).at[jnp.arange(t)[:, None], at].set(True)
    return chosen & causal


@pytest.mark.parametrize("t,topk,blocks", [
    (64, 16, 1),    # one block of queries, one group of keys
    (64, 16, 8),    # eight blocks in four groups of growing key extents
    (48, 16, 3),    # three blocks: one group
    (16, 16, 2),    # no context passes the selection: every causal key
    (64, 5, 4),     # ties: two index heads, many scores exactly 0
])
def test_the_sparse_prefill_is_the_topk_mask_in_query_blocks(monkeypatch, t,
                                                             topk, blocks):
    """``MultiHeadAttention(topk=K)`` equals full scores under the mask of
    ``jax.lax.top_k``'s set, whatever the blocks (a block's scores are sized
    from ``_SCORE_BYTES``) and the groups; ties at the K-th place go to the
    lower position in both (with two index heads a quarter of the scores are
    exactly 0)."""
    q, k, v = _qkv(t, seed=2)
    iq, ik, iw = _indexer(t, hi=2 if topk == 5 else 4)
    monkeypatch.setattr(attention, "_SCORE_BYTES", 4 * 4 * t * t // blocks)
    before = dict(attention.DISPATCH_COUNTS)
    got = _mha(q, k, v, iq, ik, iw, topk=topk)
    assert attention.DISPATCH_COUNTS["sparse"] == before["sparse"] + 1
    want = _masked(q, k, v, _topk_mask(iq, ik, iw, topk))
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-6


def test_the_block_with_the_rule_held_to_the_kernel_is_the_references(
        monkeypatch):
    """The block's prefill with ``attention_form`` held to
    ``"sparse_kernel"`` for its three full layers (what the chip's rule
    answers at the cell's shapes; here the kernel is interpreted): the mask
    of every layer reaches the blockwise kernel, the admission's logits are
    the reference's inside the float32 limit the XLA form holds, the kept
    selection is the reference's ``top_k``, and the warm-up's gauges name
    three kernel layers under a selection and no XLA one."""
    rule = attention.attention_form
    monkeypatch.setattr(
        attention, "attention_form", lambda *a: "sparse_kernel"
        if a[7] > 0 else rule(*a))
    params = _weights()
    before = dict(attention.DISPATCH_COUNTS)
    telemetry.reset()
    saved = telemetry.current_override()
    telemetry.set_mode("counters")
    try:
        dec = _decoder(params).warmup()
        forms = {form: telemetry.gauge(
            "serving.prefill_attention.%s_layers" % form).value
            for form in ("sparse_kernel", "sparse", "band",
                         "window_kernel")}
    finally:
        telemetry.set_mode(saved)
        telemetry.reset()
    # (off the chip a window layer's prefill stays XLA's band)
    assert forms == {"sparse_kernel": 3, "sparse": 0, "band": 3,
                     "window_kernel": 0}
    assert attention.DISPATCH_COUNTS["sparse_kernel"] \
        == before["sparse_kernel"] + 3
    assert attention.DISPATCH_COUNTS["sparse"] == before["sparse"]
    for length in (56, 64):
        toks = _tokens(T, seed=length)
        seq, logits = dec.admit(np.asarray(toks[:length], np.float32))
        chosen, = _kept(dec, seq, "sparse_sel_0")
        dec.retire(seq)
        want = np.asarray(REF_LOGITS(params, jnp.asarray(toks)))[length - 1]
        assert _rel_l2(np.asarray(logits), want).max() < F32_TOL
        allowed = np.asarray(REF_SELECTED(params, jnp.asarray(toks),
                                          jnp.asarray((length - 1,))))[0]
        assert _chosen(chosen) == np.nonzero(allowed)[0].tolist()


def test_topk_refuses_a_window_a_sink_and_cross_attention():
    q, k, v = _qkv(16)
    iq, ik, iw = _indexer(16)
    with pytest.raises(MXNetError, match="topk needs plain causal"):
        _mha(q, k, v, iq, ik, iw, topk=4, window=8)
    with pytest.raises(MXNetError, match="topk needs plain causal"):
        _mha(q, k[:, :, :8], v[:, :, :8], iq, ik, iw, topk=4)


def _pool_ops(rows=3, pages=4, page=8, hi=4, di=8, heads=4, width=12, lat=8,
              seed=7, paged_index=False):
    """A latent pool and an index pool over ``rows`` lanes' shuffled frames,
    and each row's context."""
    rs = np.random.RandomState(seed)
    di = 128 if paged_index else di
    frames = rows * pages
    slots = frames * page
    table = rs.permutation(frames).reshape(rows, pages).astype(np.float32)
    pos = np.array([[5], [30], [17]], np.float32)[:rows]
    pool = jnp.asarray(rs.randn(1, slots, width), jnp.float32)
    index = jnp.asarray(rs.randn(1, slots, di), jnp.float32)
    if paged_index:
        index = index.reshape(frames, page, di)
    return dict(
        table=jnp.asarray(table), pos=jnp.asarray(pos), pool=pool,
        index=index, page=page,
        query=jnp.asarray(rs.randn(rows, heads, width), jnp.float32),
        iq=jnp.asarray(rs.randn(rows, hi, di), jnp.float32),
        iw=jnp.asarray(rs.randn(rows, hi), jnp.float32),
        slot=jnp.asarray(np.array([[1.0], [1.0], [-1.0]], "f")[:rows]),
        kept=jnp.full((rows, 6), 7.0, jnp.float32), lat=lat)


@pytest.mark.parametrize("paged_index", [False, True])
def test_a_steps_selection_scores_a_lanes_own_pages_and_reads_only_those(
        paged_index):
    """``SparseIndexSelect`` scores the index keys of a row's own pages in
    order (slot u of them IS position u), in either layout of the index pool,
    and ``KVPoolAttention(selected=True)`` reads the chosen rows of the
    latent pool through the same table: together they equal attention over
    the row's own context under the reference's ``top_k`` mask. A context
    shorter than the selection keeps all of it (-1 past it); a row that rides
    along keeps what it was handed."""
    o = _pool_ops(paged_index=paged_index)
    sel = get_op("SparseIndexSelect")
    chosen = np.asarray(sel.fn(
        parse_attrs(sel, dict(topk=6, page_size=o["page"])), o["iq"], o["iw"],
        o["index"], o["table"], o["pos"], o["slot"], o["kept"]))
    att = get_op("KVPoolAttention")
    got = np.asarray(att.fn(
        parse_attrs(att, dict(value_dim=o["lat"], page_size=o["page"],
                             selected=True)),
        o["query"], o["pool"], o["pool"], None, o["table"], o["pos"],
        o["slot"], jnp.asarray(chosen)))
    assert got.shape == (3, 4, o["lat"])
    flat = np.asarray(o["index"]).reshape(-1, o["index"].shape[-1])
    for r, n in ((0, 6), (1, 31)):
        frames = np.asarray(o["table"][r], np.int64)
        own = (frames[:, None] * o["page"] + np.arange(o["page"])).reshape(-1)
        keys = flat[own][:n]
        score = np.einsum("hs,h->s", np.maximum(
            np.einsum("hd,sd->hs", np.asarray(o["iq"][r]), keys), 0),
            np.asarray(o["iw"][r]))
        want = sorted(np.argsort(-score, kind="stable")[:6].tolist())
        assert _chosen(chosen[r]) == want
        assert (chosen[r] >= 0).sum() == min(n, 6)
        rows = np.asarray(o["pool"])[0][own][want]
        s = np.einsum("hd,sd->hs", np.asarray(o["query"][r]), rows) \
            * rows.shape[-1] ** -0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        assert np.abs(got[r] - (p @ rows)[:, :o["lat"]]).max() < 2e-6
    assert (chosen[2] == 7.0).all()     # rode along: what it was handed


def test_a_prefills_last_row_selects_over_the_prompt_alone():
    """Without a page size the keys are given and ``length`` says how many
    are the prompt's: what an admission keeps for the prompt's last row."""
    rs = np.random.RandomState(3)
    iq = jnp.asarray(rs.randn(1, 4, 8), jnp.float32)
    iw = jnp.asarray(rs.randn(1, 4), jnp.float32)
    keys = jnp.asarray(rs.randn(1, 32, 8), jnp.float32)
    sel = get_op("SparseIndexSelect")
    got = np.asarray(sel.fn(parse_attrs(sel, dict(topk=6)), iq, iw, keys,
                            jnp.asarray([[20.0]])))
    score = np.einsum("hs,h->s", np.maximum(np.einsum(
        "hd,sd->hs", np.asarray(iq[0]), np.asarray(keys[0, :20])), 0),
        np.asarray(iw[0]))
    assert _chosen(got[0]) == sorted(np.argsort(-score)[:6].tolist())


def test_a_ring_of_latents_is_key_whole_and_value_in_its_first_columns():
    """``KVRingAttention(value_dim=)`` over ONE ring that is key and value:
    the context is cut to the value's width after the contraction."""
    rs = np.random.RandomState(4)
    ring = jnp.asarray(rs.randn(2, 1, 9, 20), jnp.float32)
    q = jnp.asarray(rs.randn(2, 3, 20), jnp.float32)
    pos = jnp.asarray([[4.0], [30.0]])
    slot = jnp.asarray([[3.0], [9.0]])
    op = get_op("KVRingAttention")
    got = np.asarray(op.fn(parse_attrs(op, dict(value_dim=16, scale=0.3)), q,
                           ring, ring, pos, slot))
    assert got.shape == (2, 3, 16)
    for r, live in ((0, 5), (1, 9)):
        rows = np.asarray(ring[r, 0, :live])
        s = np.asarray(q[r]) @ rows.T * 0.3
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        assert np.abs(got[r] - (p @ rows)[:, :16]).max() < 2e-6


# ---------------------------------------------------------------- (b) block
def test_what_the_cache_keeps_and_what_the_checkpoint_holds():
    cache = tf.decode_cache(**CFG)
    assert cache == [
        ("kv_c_0", "pool", (1, 20)), ("kv_i_0", "pool", (1, 8)),
        ("sparse_sel_0", "row", (TOPK,)),
        ("kv_c_1", "pool", (1, 20)), ("kv_i_1", "pool", (1, 8)),
        ("sparse_sel_1", "row", (TOPK,)),
        ("ring_c_2", "ring", (1, W, 36)), ("ring_c_3", "ring", (1, W, 36)),
        ("ring_c_4", "ring", (1, W, 36)),
        ("kv_c_5", "pool", (1, 20)), ("kv_i_5", "pool", (1, 8)),
        ("sparse_sel_5", "row", (TOPK,))]
    shapes = tf.param_shapes(**CFG)
    assert shapes["layer0_qb_weight"] == (4 * 12, 24)
    assert shapes["layer2_qb_weight"] == (2 * 16, 24)
    assert shapes["layer2_kva_weight"] == (36, 48)
    assert shapes["layer0_iq_weight"] == (64, 24)
    assert "layer2_iq_weight" not in shapes and "layer0_mlp_in_weight" in shapes
    assert shapes["layer1_experts_up_weight"] == (8, 48, 16)
    assert shapes["layer1_shared_in_weight"] == (32, 48)
    with pytest.raises(MXNetError, match="layer_types must name"):
        tf.param_shapes(**dict(CFG, layer_types=[FULL] * 5))


@pytest.mark.parametrize("length", [56, 37, 9, 64])
def test_prefill_then_steps_match_the_references_full_forward(served, length):
    """Admission and 72 - length single steps through the caches against the
    reference's full forward over the whole sequence, LOGITS not tokens, in
    float32: a prompt whose selection bites (56, 37 of a 64 bucket, and the
    bucket's own end), and one shorter than the selection and as long as the
    window (9). The kept selection equals the reference's ``top_k`` for the
    same query after the admission and after the last step, and the first
    window layer's ring its rotated [c | k_r]."""
    params, dec = served
    toks = _tokens(T, seed=length)
    got, admitted, last = _admit_and_step(dec, toks, length)
    want = np.asarray(REF_LOGITS(params, jnp.asarray(toks)))[length - 1:]
    assert _rel_l2(got, want).max() < F32_TOL
    ends = (length - 1, T - 1)
    allowed = np.asarray(REF_SELECTED(params, jnp.asarray(toks),
                                      jnp.asarray(ends)))
    rows = REF_ROWS(params, jnp.asarray(toks))
    for (ring, chosen), mask, at in zip((admitted, last), allowed, ends):
        assert _chosen(chosen) == np.nonzero(mask)[0].tolist()
        assert len(_chosen(chosen)) == min(at + 1, TOPK)
        assert _ring_error(ring, rows, at) < 1e-5


def test_bfloat16_holds_a_lower_quartile_row_and_the_ring():
    """The configuration's type: bfloat16 weights, pools, index keys and
    rings against the float32 reference over the same weights."""
    params = _weights("bfloat16")
    toks = _tokens(T, seed=3)
    got, admitted, last = _admit_and_step(_decoder(params, "bfloat16"), toks,
                                          56)
    want = np.asarray(REF_LOGITS(params, jnp.asarray(toks)))[55:]
    assert _lower_quartile(_rel_l2(got, want)) < BF16_TOL
    rows = REF_ROWS(params, jnp.asarray(toks))
    assert _ring_error(admitted[0], rows, 55) < BF16_RING_TOL
    assert _ring_error(last[0], rows, 71) < BF16_RING_TOL
    allowed = np.asarray(REF_SELECTED(params, jnp.asarray(toks),
                                      jnp.asarray((55, 71))))
    for (_, chosen), mask in zip((admitted, last), allowed):
        # near-tied index scores at the 16th place may flip: most agree
        both = len(set(_chosen(chosen)) & set(np.nonzero(mask)[0].tolist()))
        assert both >= TOPK - 3


def test_the_absorbed_step_equals_the_materialised_prefill(served):
    """The same position through the step (absorbed, selected rows of the
    pool, the ring) and through an admission one token longer
    (materialised, a mask over query blocks, the band): the same logits."""
    _, dec = served
    toks = _tokens(41)
    seq, _ = dec.admit(np.asarray(toks[:40], np.float32))
    stepped = np.asarray(dec.step({seq: int(toks[40])})[seq])
    other, admitted = dec.admit(np.asarray(toks, np.float32))
    dec.retire(seq)
    dec.retire(other)
    assert _rel_l2(stepped[None], np.asarray(admitted)[None]).max() < F32_TOL


def test_a_readmitted_lane_sees_nothing_of_its_predecessor(served):
    """A lane's pools, index keys, rings and kept selection are handed over
    at the PROMPT's real end: a long sequence that fills the bucket and steps
    on, retired, then a short prompt in the same lane and frames (frames come
    off a LIFO list) reads as the reference says: nothing of the lane's last
    occupant shows, neither in its ring nor in its selection."""
    params, dec = served
    seq, _ = dec.admit(np.asarray(_tokens(64, seed=5), np.float32))
    lane = dec._seq_lane[seq]
    for tok in _tokens(20, seed=6):
        dec.step({seq: int(tok)})
    dec.retire(seq)
    toks = _tokens(T, seed=7)
    seq, first = dec.admit(np.asarray(toks[:21], np.float32))
    assert dec._seq_lane[seq] == lane
    ring, chosen = _kept(dec, seq, "ring_c_2", "sparse_sel_0")
    got = [np.asarray(first)] + [np.asarray(dec.step({seq: int(t)})[seq])
                                 for t in toks[21:27]]
    dec.retire(seq)
    want = np.asarray(REF_LOGITS(params, jnp.asarray(toks)))[20:27]
    # the reference scored 72 tokens: causal, so rows 20..26 are the prompt's
    assert _rel_l2(np.stack(got), want).max() < F32_TOL
    assert _ring_error(ring, REF_ROWS(params, jnp.asarray(toks)), 20) < 1e-5
    mask = np.asarray(REF_SELECTED(params, jnp.asarray(toks),
                                   jnp.asarray((20,))))[0]
    assert _chosen(chosen) == np.nonzero(mask)[0].tolist()


def test_a_row_written_at_a_wrong_slot_is_seen(served):
    """The latent row and the index key of a token land in the slot the host
    names, the prompt's at the positions of its own pages: with a step's
    write moved one slot on, the read finds a stale row where the token's
    should be and the logits leave the reference."""
    params, dec = served
    toks = _tokens(T, seed=11)
    want = np.asarray(REF_LOGITS(params, jnp.asarray(toks)))
    seq, _ = dec.admit(np.asarray(toks[:40], np.float32))
    pools = dec.lane_state(seq, ("kv_c_0", "kv_i_0"))
    # positions 0..39 of the latent pool hold the reference's rows for them
    rows = np.asarray(ref.latents(
        ref.rms_norm(jnp.asarray(params["embed_weight"])[toks[:40]],
                     params["layer0_ln1_gamma"], 1e-5), params, "layer0_",
        jnp.arange(40), CFG, ref.geometry(CFG, FULL))[1])
    assert np.abs(np.asarray(pools["kv_c_0"])[0] - rows).max() < 1e-5
    assert np.asarray(pools["kv_i_0"]).shape == (1, 40, 8)
    slot_of = dec._phys_slot
    dec._phys_slot = lambda lane, pos: slot_of(lane, pos) + 1
    try:
        got = [np.asarray(dec.step({seq: int(t)})[seq]) for t in toks[40:44]]
    finally:
        del dec._phys_slot
        dec.retire(seq)
    assert _rel_l2(np.stack(got), want[40:44]).max() > 1e-3


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """The share test (model-configs guide, section 4): one expert layer's
    routed sum, computed by each of the four shares of eight experts through
    the PROGRAM's ``MoEFeedForward`` with what every chip computes alike, the
    shared expert, counted once, adds up to the uncut reference's layer."""
    from mxnet_tpu.ops import moe as moe_ops  # noqa: F401  (registers the op)

    params = _weights(cfg=dict(CFG, num_local_experts=0))
    n, rs = "layer1_", np.random.RandomState(9)
    h = jnp.asarray(rs.randn(24, 48), jnp.float32)
    whole = ref.moe(h, params[n + "router_weight"], params[n + "router_bias"],
                    params[n + "experts_gate_weight"],
                    params[n + "experts_up_weight"],
                    params[n + "experts_down_weight"], 4, 1.0, 0) \
        + ref.gated_mlp(h, params[n + "shared_in_weight"],
                        params[n + "shared_out_weight"])
    op = get_op("MoEFeedForward")
    total = ref.gated_mlp(h, params[n + "shared_in_weight"],
                          params[n + "shared_out_weight"])
    for first in range(0, 32, 8):
        attrs = parse_attrs(op, dict(
            num_experts=32, num_hidden=16, num_experts_per_tok=4,
            scoring="sigmoid", router_bias=True, norm_topk_prob=True,
            routed_scaling_factor=1.0, num_local_experts=8,
            local_expert_offset=first))
        part = op.fn(attrs, h, params[n + "router_weight"], *(
            params[n + "experts_%s_weight" % t][first:first + 8]
            for t in ("gate", "up", "down")), params[n + "router_bias"])[0]
        total = total + part
    assert _rel_l2(np.asarray(total), np.asarray(whole)).max() < 1e-5


def _recent(h, c_q, p, n, positions, cfg, g, rows):
    """A fault: the indexer's scores replaced by recency."""
    return -(positions[rows][:, None] - positions[None, :]).astype(
        jnp.float32) ** 2


_FAULTS = {
    "the selection dropped": dict(cfg=dict(index_topk=10 ** 6)),
    "the most RECENT keys instead of the top": dict(bend=("index_scores",
                                                          _recent)),
    "the window one key long": dict(cfg=dict(sliding_window=W + 1)),
    "the full layers' theta in a window layer": dict(
        cfg=dict(swa_rope_theta=8e7)),
    "the gate dropped": dict(bend=("head_gate", lambda h, p, n: jnp.ones(
        (h.shape[0], 1)))),
    "rho dropped": dict(cfg=dict(lora_rescale=False)),
}


@pytest.fixture(scope="module")
def sound(served):
    """The program's logits for ONE prompt whose selection bites and that is
    five windows long, and a few steps behind it."""
    params, dec = served
    toks = _tokens(T, seed=13)
    return params, toks, _admit_and_step(dec, toks[:60], 56)


@pytest.mark.parametrize("fault", list(_FAULTS))
def test_each_named_fault_fails(sound, fault):
    """The reference bent by one named fault leaves the program's logits (or
    its first window ring) by far more than the float32 tolerance: the
    comparison sees each of them."""
    params, toks, (got, (ring, _), _) = sound
    bent, how = reference(), _FAULTS[fault]
    if "bend" in how:
        setattr(bent, *how["bend"])
    logits, rows, _ = _jitted(bent, dict(CFG, **how.get("cfg", {})))
    want = np.asarray(logits(params, jnp.asarray(toks)))[55:60]
    # a rotary base moves the logits little over a window of 9 (four rotary
    # features, angles under 0.04): the RING's rotated keys see it, at the
    # absolute positions they were written at, as the benchmark's third hold
    assert np.median(_rel_l2(got, want)) > 20 * F32_TOL or _ring_error(
        ring, rows(params, jnp.asarray(toks)), 55) > 1e-2


# -------------------------------------------------------------- (c) serving
def test_the_decoder_refuses_what_the_sealed_archs_refuse(served):
    params, dec = served
    with pytest.raises(MXNetError, match="not built for arch 'dots3_note'"):
        _decoder(params, prefix_cache=True)
    seq, _ = dec.admit(np.asarray(_tokens(12), np.float32))
    try:
        for call in (lambda: dec.step_megastep({seq: 1}, k=2),
                     lambda: dec.verify_chunk(seq, [1, 2]),
                     lambda: dec.fork(seq), lambda: dec.rollback(seq, 4)):
            with pytest.raises(MXNetError, match="not built for arch"):
                call()
    finally:
        dec.retire(seq)
    for what in ("get_chunk_symbol", "get_symbol"):
        with pytest.raises(MXNetError, match="not built for arch"):
            tf._refuse_arch("dots3_note", what)


def test_the_counters_of_a_step_and_an_admission(served, tm):
    """``serving.sparse.*``: index keys a step scored (position + 1 a
    stepped lane and full layer), rows its read took (at most the
    selection), causal pairs an admission's indexers scored; the two pools'
    bytes as gauges; the forms the rules named."""
    _, dec = served
    assert GAUGES["serving.pool_read.selected_layers"] == 3
    assert GAUGES["serving.prefill_attention.sparse_layers"] == 3
    assert GAUGES["serving.prefill_attention.band_layers"] == 3
    slots = SERVE["lanes"] * SERVE["max_len"]
    assert GAUGES["serving.latent_pool_bytes"] == 3 * slots * 20 * 4
    assert GAUGES["serving.index_pool_bytes"] == 3 * slots * 8 * 4
    assert GAUGES["serving.window_ring_bytes"] \
        == 3 * SERVE["lanes"] * W * 36 * 4
    a, _ = dec.admit(np.asarray(_tokens(40), np.float32))
    b, _ = dec.admit(np.asarray(_tokens(10, seed=2), np.float32))
    c = lambda name: tm.counter(name).value
    assert c("serving.sparse.admit_scored_pairs") \
        == 3 * (40 * 41 + 10 * 11) // 2
    dec.step({a: 1, b: 2})
    dec.step({a: 3})
    dec.retire(a)
    dec.retire(b)
    assert c("serving.sparse.step_scored_slots") == 3 * (41 + 11 + 42)
    assert c("serving.sparse.step_selected_slots") == 3 * (16 + 11 + 16)
    assert c("serving.step_window_slots") == 9 + 9 + 9
    assert c("serving.step_context_tokens") == 41 + 11 + 42
    assert c("serving.moe.step_assignments") > 0
    spans = {name for name, _t0, _dur, _tid, _attrs in tm.drain_events()}
    for name in ("serving.admit.prefill", "serving.admit.scatter",
                 "serving.admit.state", "serving.step.stage",
                 "serving.step.dispatch", "serving.step.commit"):
        assert name in spans, name
