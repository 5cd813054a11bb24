"""Attention op + ring attention (sequence parallel) + Transformer model."""
import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import parallel
from mxnet_tpu import symbol as sym
from mxnet_tpu.parallel.ring_attention import ring_attention


def _ref_attention(q, k, v, causal):
    # numpy oracle over (B,H,T,D); causal mask bottom-right aligned for S>=T
    d = q.shape[-1]
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    if causal:
        T, S = s.shape[-2], s.shape[-1]
        mask = np.tril(np.ones((T, S), bool), k=S - T)
        s = np.where(mask, s, -np.inf)
    s = s - s.max(-1, keepdims=True)
    p = np.exp(s)
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_mha_op_matches_numpy(causal):
    rs = np.random.RandomState(0)
    q, k, v = (rs.randn(2, 3, 8, 4).astype("float32") for _ in range(3))
    out = mx.nd.MultiHeadAttention(mx.nd.array(q), mx.nd.array(k), mx.nd.array(v),
                                   causal=causal).asnumpy()
    np.testing.assert_allclose(out, _ref_attention(q, k, v, causal),
                               rtol=1e-4, atol=1e-5)


def test_mha_causal_rectangular_decode():
    # single-token decode: 1 query over 16 cached keys must see ALL of them
    rs = np.random.RandomState(1)
    q = rs.randn(1, 2, 1, 4).astype("float32")
    k, v = (rs.randn(1, 2, 16, 4).astype("float32") for _ in range(2))
    out = mx.nd.MultiHeadAttention(mx.nd.array(q), mx.nd.array(k), mx.nd.array(v),
                                   causal=True).asnumpy()
    np.testing.assert_allclose(out, _ref_attention(q, k, v, True),
                               rtol=1e-4, atol=1e-5)
    assert np.isfinite(out).all()


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_exact(causal):
    import jax

    rs = np.random.RandomState(1)
    B, T, H, D = 2, 16, 2, 4
    q, k, v = (rs.randn(B, T, H, D).astype("float32") for _ in range(3))
    mesh = parallel.make_mesh({"seq": 4}, devices=jax.devices()[:4])
    out = np.asarray(ring_attention(q, k, v, mesh, seq_axis="seq", causal=causal))
    # oracle in (B,H,T,D) layout
    ref = _ref_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                         v.transpose(0, 2, 1, 3), causal).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_ring_attention_grad_flows():
    import jax
    import jax.numpy as jnp

    rs = np.random.RandomState(2)
    B, T, H, D = 1, 8, 1, 4
    q, k, v = (jnp.asarray(rs.randn(B, T, H, D).astype("float32")) for _ in range(3))
    mesh = parallel.make_mesh({"seq": 4}, devices=jax.devices()[:4])

    def loss(q, k, v):
        return ring_attention(q, k, v, mesh, causal=True).sum()

    g = jax.grad(loss)(q, k, v)
    assert np.isfinite(np.asarray(g)).all()


def test_transformer_builds_and_steps():
    from mxnet_tpu.models import transformer

    net = transformer.get_symbol(vocab_size=100, num_layers=2, num_heads=2,
                                 model_dim=16, ffn_dim=32, seq_len=8)
    exe = net.simple_bind(ctx=mx.cpu(), data=(2, 8), softmax_label=(2, 8),
                          type_dict={"data": "int32"})
    rs = np.random.RandomState(3)
    exe.arg_dict["data"][:] = rs.randint(0, 100, (2, 8)).astype("int32")
    exe.arg_dict["softmax_label"][:] = rs.randint(0, 100, (2, 8)).astype("float32")
    for name, arr in exe.arg_dict.items():
        if name in ("data", "softmax_label"):
            continue
        arr[:] = rs.uniform(-0.05, 0.05, arr.shape).astype("float32")
    out = exe.forward_backward()
    assert out[0].shape == (16, 100)
    g = exe.grad_dict["lm_head_weight"].asnumpy()
    assert np.isfinite(g).all() and np.abs(g).sum() > 0


def test_transformer_spmd_trains():
    import jax

    from mxnet_tpu.models import transformer

    mesh = parallel.make_mesh({"data": 2, "model": 2},
                              devices=jax.devices()[:4])
    net = transformer.get_symbol(vocab_size=64, num_layers=1, num_heads=2,
                                 model_dim=16, ffn_dim=32, seq_len=8)
    tr = parallel.SPMDTrainer(net, mesh, optimizer="adam",
                              optimizer_params={"learning_rate": 1e-3})
    tr.init_params({"data": (4, 8)}, {"softmax_label": (4, 8)})
    rs = np.random.RandomState(4)
    x = rs.randint(0, 64, (4, 8)).astype("int32")
    y = rs.randint(0, 64, (4, 8)).astype("float32")
    import jax.numpy as jnp

    for _ in range(2):
        outs = tr.step({"data": jnp.asarray(x)}, {"softmax_label": y})
    assert np.isfinite(np.asarray(outs[0])).all()


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_flash_attention_matches_oracle(causal):
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_attention as pa

    rs = np.random.RandomState(2)
    q = rs.randn(2, 2, 16, 8).astype("float32")
    k, v = (rs.randn(2, 2, 32, 8).astype("float32") for _ in range(2))
    out = np.asarray(pa.flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal,
        block_q=8, block_k=8, interpret=True))
    np.testing.assert_allclose(out, _ref_attention(q, k, v, causal),
                               rtol=1e-4, atol=1e-5)


def _mha(q, k, v, *more, **attrs):
    from mxnet_tpu.ops.registry import get_op

    return get_op("_contrib_MultiHeadAttention").fn(
        {"causal": True, "scale": -1.0, "window": 0, **attrs}, q, k, v, *more)


def _operands(h, hkv, t, s, dk, dv, dtype, seed=3):
    import jax.numpy as jnp

    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(*shape).astype("float32"), dtype)
                 for shape in ((1, h, t, dk), (1, hkv, s, dk),
                               (1, hkv, s, dv)))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("group", [1, 4, 16])
@pytest.mark.parametrize("widths", [(64, 64), (128, 128), (192, 128)])
@pytest.mark.parametrize("t,s", [(64, 64), (32, 64)])
def test_the_kernel_interpreted_is_the_dense_path(dtype, group, widths, t, s):
    """The blockwise kernel against ``MultiHeadAttention``'s dense path over
    one grid: both types, the groups and head widths of the cells (a key of
    192 over a value of 128 among them), a bucket and S > T (bottom-right
    aligned), in blocks that make the grid 2 x 4 or more with the diagonal
    crossing some and passing over others. The same types in the same
    places: float32 to a float32 sum's order, bfloat16 to one rounding of
    the output."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_attention as pa

    q, k, v = _operands(2 * group, 2, t, s, *widths, dtype)
    got = pa.flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                             interpret=True)
    want = _mha(q, k, v)
    assert got.shape == want.shape == (1, 2 * group, t, widths[1])
    assert got.dtype == want.dtype == jnp.dtype(dtype)
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got, "float32"),
                               np.asarray(want, "float32"), rtol=tol, atol=tol)


@pytest.mark.parametrize("group", [1, 4])
def test_a_block_above_the_diagonal_is_never_read(group):
    """Keys and values past the first block of queries' diagonal are NaN: a
    masked product would carry them into every row (0 x NaN), a skipped block
    leaves the first block's rows what the clean operands give, bit for
    bit. The later rows see the poison, as they must."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_attention as pa

    q, k, v = _operands(2 * group, 2, 64, 64, 128, 128, "float32")
    run = lambda k, v: np.asarray(pa.flash_attention(
        q, k, v, causal=True, block_q=16, block_k=16, interpret=True))
    poison = lambda a: a.at[:, :, 16:].set(jnp.nan)
    clean, dirty = run(k, v), run(poison(k), poison(v))
    np.testing.assert_array_equal(dirty[:, :, :16], clean[:, :, :16])
    assert np.isnan(dirty[:, :, 16:]).all()


# what the rule answers, by shape: (H, Hkv, T, S, dk, dv, dtype) and the
# attributes -> the form on the chip (off the chip every one is dense or a
# band)
_RULE_CASES = {
    "a bucket, equal heads": ((16, 16, 2048, 2048, 128, 128, "bfloat16"),
                              {}, "kernel"),
    "grouped heads": ((32, 2, 2048, 2048, 128, 128, "bfloat16"), {},
                      "kernel"),
    "a value narrower than the key": (
        (32, 32, 1024, 1024, 192, 128, "bfloat16"), {}, "kernel"),
    "float32 operands": ((16, 16, 2048, 2048, 64, 64, "float32"), {},
                         "kernel"),
    "more keys than queries": ((16, 16, 1024, 2048, 64, 64, "bfloat16"), {},
                               "kernel"),
    "fewer keys than queries": ((16, 16, 2048, 1024, 64, 64, "bfloat16"), {},
                                "dense"),
    "T no multiple of a block": ((16, 16, 2000, 2000, 64, 64, "bfloat16"),
                                 {}, "dense"),
    "scores the chip keeps in its vector memory": (
        (8, 8, 1024, 1024, 64, 64, "bfloat16"), {}, "dense"),
    "a toy model": ((2, 2, 16, 16, 8, 8, "float32"), {}, "dense"),
    "bidirectional": ((16, 16, 2048, 2048, 64, 64, "bfloat16"),
                      {"causal": False}, "dense"),
    "a sink": ((16, 16, 2048, 2048, 64, 64, "bfloat16"), {"sink": True},
               "dense"),
    "a window that tiles": ((16, 16, 2048, 2048, 64, 64, "bfloat16"),
                            {"window": 128}, "band"),
    "a ragged window": ((16, 16, 2048, 2048, 64, 64, "bfloat16"),
                        {"window": 100}, "dense"),
    # a window whose band is large: the kernel under the window, at the
    # cells' window layers (T no multiple of the window asked of the kernel:
    # dots3's 513 is masked, not tiled)
    "laguna's window layer": ((72, 8, 8192, 8192, 128, 128, "bfloat16"),
                              {"window": 512}, "window_kernel"),
    "dots3's window layer": ((64, 64, 8192, 8192, 256, 128, "bfloat16"),
                             {"window": 513}, "window_kernel"),
    "phi4flash's window layer": (
        (40, 10, 2048, 2048, 128, 128, "bfloat16"), {"window": 512},
        "window_kernel"),
    "mimo's window layer, a sink": (
        (64, 8, 2048, 2048, 192, 128, "bfloat16"),
        {"window": 128, "sink": True}, "band"),
    "a large ragged window": ((72, 8, 8192, 8192, 128, 128, "bfloat16"),
                              {"window": 500}, "window_kernel"),
    "a large window, float32": ((72, 8, 8192, 8192, 128, 128, "float32"),
                                {"window": 512}, "window_kernel"),
    "a large window, mixed types": (
        (72, 8, 8192, 8192, 128, 128, ("bfloat16", "float32")),
        {"window": 512}, "band"),
    "a large window, T no whole lane tiles": (
        (72, 8, 8200, 8200, 128, 128, "bfloat16"), {"window": 512}, "dense"),
    "a window as long as the bucket": (
        (72, 8, 8192, 8192, 128, 128, "bfloat16"), {"window": 8192},
        "dense"),
    "mixed types": ((16, 16, 2048, 2048, 64, 64, ("bfloat16", "float32")),
                    {}, "dense"),
    # a learned selection (``topk``): the kernel under a mask where it takes
    # the operands, XLA's query blocks everywhere else
    "a selection at dots3's admission": (
        (128, 128, 8192, 8192, 192, 128, "bfloat16"), {"topk": 2048},
        "sparse_kernel"),
    "a selection over grouped heads": (
        (32, 2, 2048, 2048, 128, 128, "bfloat16"), {"topk": 512},
        "sparse_kernel"),
    "a selection, float32": ((16, 16, 2048, 2048, 64, 64, "float32"),
                             {"topk": 512}, "sparse_kernel"),
    "a selection over scores the chip keeps": (
        (8, 8, 1024, 1024, 64, 64, "bfloat16"), {"topk": 256}, "sparse"),
    "a selection, T no multiple of a block": (
        (16, 16, 2000, 2000, 64, 64, "bfloat16"), {"topk": 512}, "sparse"),
    "a selection, mixed types": (
        (16, 16, 2048, 2048, 64, 64, ("bfloat16", "float32")),
        {"topk": 512}, "sparse"),
    "a selection in a toy model": ((2, 2, 16, 16, 8, 8, "float32"),
                                   {"topk": 4}, "sparse"),
}


@pytest.mark.parametrize("case", list(_RULE_CASES))
def test_the_rule_names_the_form_from_shapes_attributes_and_backend(
        case, monkeypatch):
    """``attention_form`` (plain Python over shapes: nothing is traced): off
    the chip no case is the kernel; held to the chip, each is what the table
    says. No environment variable is read: ``MXNET_USE_PALLAS_ATTENTION`` set
    moves nothing."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as attn_op

    (h, hkv, t, s, dk, dv, dtype), attrs, on_chip = _RULE_CASES[case]
    qt, kt = dtype if isinstance(dtype, tuple) else (dtype, dtype)
    struct = lambda dt, *shape: jax.ShapeDtypeStruct(shape, jnp.dtype(dt))
    ops = (struct(qt, 1, h, t, dk), struct(kt, 1, hkv, s, dk),
           struct(kt, 1, hkv, s, dv))
    args = (attrs.get("causal", True), attrs.get("window", 0),
            attrs.get("sink", False), None, attrs.get("topk", 0))
    monkeypatch.setenv("MXNET_USE_PALLAS_ATTENTION", "1")
    off_chip = "sparse" if "topk" in attrs else "band" if attn_op._band_block(
        t, attrs.get("window", 0)) else "dense"
    assert attn_op.attention_form(*ops, *args) == off_chip
    monkeypatch.setattr(attn_op, "_backend", lambda: "tpu")
    assert attn_op.attention_form(*ops, *args) == on_chip


def test_the_rule_keeps_a_step_over_several_devices_dense(monkeypatch):
    """Under a mesh of several devices the compiler partitions the dense path
    and could only replicate a kernel: the rule names the kernel for one
    device (no mesh, or a mesh of one) and never for more; a ``seq`` axis is
    the ring's."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as attn_op

    monkeypatch.setattr(attn_op, "_backend", lambda: "tpu")
    struct = lambda b: jax.ShapeDtypeStruct((b, 16, 2048, 128), jnp.bfloat16)
    form = lambda b, mesh, topk=0: attn_op.attention_form(
        struct(b), struct(b), struct(b), True, 0, False, mesh, topk)
    one = parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    data = parallel.make_mesh({"data": 4}, devices=jax.devices()[:4])
    seq = parallel.make_mesh({"data": 2, "seq": 2}, devices=jax.devices()[:4])
    assert form(1, None) == form(1, one) == form(4, None) == "kernel"
    assert form(4, data) == "dense"
    assert form(4, seq) == "ring"
    # a learned selection: the masked kernel on one device, XLA's query
    # blocks under any mesh of several
    assert form(1, None, 512) == form(1, one, 512) == "sparse_kernel"
    assert form(4, data, 512) == form(4, seq, 512) == "sparse"
    # a window: the kernel on one device, XLA's band under any mesh of
    # several
    window = lambda b, mesh: attn_op.attention_form(
        struct(b), struct(b), struct(b), True, 512, False, mesh, 0)
    assert window(4, None) == window(4, one) == "window_kernel"
    assert window(4, data) == window(4, seq) == "band"


def test_the_op_runs_the_form_the_rule_names_and_counts_it(monkeypatch):
    """``MultiHeadAttention`` off the chip is the dense path and counts it;
    with the rule held to the kernel the same call runs it interpreted,
    grouped heads and a narrower value through the operator, and counts
    that."""
    from mxnet_tpu.ops import attention as attn_op

    q, k, v = _operands(8, 2, 32, 32, 16, 8, "float32")
    before = dict(attn_op.DISPATCH_COUNTS)
    dense = _mha(q, k, v)
    assert attn_op.DISPATCH_COUNTS["dense"] == before["dense"] + 1
    _mha(q, q, q, window=8)
    assert attn_op.DISPATCH_COUNTS["band"] == before["band"] + 1
    monkeypatch.setattr(attn_op, "attention_form", lambda *a: "kernel")
    kernel = _mha(q, k, v)
    assert attn_op.DISPATCH_COUNTS["kernel"] == before["kernel"] + 1
    assert attn_op.DISPATCH_COUNTS["dense"] == before["dense"] + 1
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------- the kernel under a selection
def _index_operands(hi, t, di, dtype, seed=5, whole=False):
    """An indexer's (index_query (1, Hi, T, di), index_key (1, 1, T, di),
    index_weight (1, T, Hi)); ``whole``: small whole numbers, so that index
    scores tie in droves (and are exactly 0 under the ReLU)."""
    import jax.numpy as jnp

    rs = np.random.RandomState(seed)
    draw = (lambda *shape: rs.randint(-1, 2, size=shape)) if whole \
        else rs.randn
    return tuple(jnp.asarray(draw(*shape).astype("float32"), dtype)
                 for shape in ((1, hi, t, di), (1, 1, t, di), (1, t, hi)))


def _masked_softmax(q, k, v, allowed):
    """Full float64 scores under a mask (T, S): the plain reference."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    k, v = (np.repeat(a, q.shape[1] // a.shape[1], axis=1) for a in (k, v))
    s = np.einsum("bhtd,bhsd->bhts", q, k) / np.sqrt(q.shape[-1])
    s = np.where(allowed, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhts,bhsd->bhtd", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("widths", [(128, 128), (192, 128)])
@pytest.mark.parametrize("t", [256, 512])
@pytest.mark.parametrize("group", [1, 2])
def test_the_masked_kernel_interpreted_is_the_sparse_form(monkeypatch, dtype,
                                                          widths, t, group):
    """The blockwise kernel under ``_selection``'s mask against
    ``_sparse_attention`` (XLA's query blocks) over one grid: both types,
    the cell's widths (a key of 192 over a value of 128), a selection of 64
    keys over 256 and 512 positions in kernel blocks of 128 and query blocks
    of 32 in eight groups, so that rows below and above ``topk``, several
    groups and several key blocks all occur; the mask shared by the heads of
    a group and across groups. The same types in the same places: float32
    to a float32 sum's order, bfloat16 to one rounding of the output."""
    from mxnet_tpu.ops import attention as attn_op
    from mxnet_tpu.ops import pallas_attention as pa

    heads, topk = 2 * group, 64
    q, k, v = _operands(heads, 2, t, t, *widths, dtype)
    index = _index_operands(4, t, 16, dtype)
    monkeypatch.setattr(attn_op, "_SCORE_BYTES", 4 * heads * t * 32)
    scale = widths[0] ** -0.5
    want = attn_op._sparse_attention(q, k, v, *index, topk, scale)
    selected = attn_op._selection(heads, *index, topk)
    assert selected.shape == (1, t, t) and selected.dtype == np.int8
    chosen = np.asarray(selected[0]).sum(axis=1)
    assert (chosen == np.minimum(np.arange(t) + 1, topk)).all()
    got = pa.flash_attention(q, k, v, causal=True, scale=scale, block_q=128,
                             block_k=128, interpret=True, selected=selected)
    assert got.shape == want.shape == (1, heads, t, widths[1])
    assert got.dtype == want.dtype == q.dtype
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got, "float32"),
                               np.asarray(want, "float32"), rtol=tol, atol=tol)


def test_a_row_with_no_selected_key_in_its_first_blocks_forgets_them():
    """The masked value is finite: a row whose first key blocks hold no
    selected key accumulates ``exp(0)`` of every masked score there, and the
    first real key's maximum must wipe that to exactly nothing (``alpha =
    0``). Rows that select keys of their LAST block alone, of a middle
    block alone and one key only, in blocks of 16 over 64 positions, against
    the plain float64 softmax under the same mask."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_attention as pa

    t = 64
    q, k, v = _operands(4, 2, t, t, 32, 16, "float32")
    allowed = np.tril(np.ones((t, t), bool))
    allowed[40, :32] = False            # its last two key blocks alone
    allowed[50] = False
    allowed[50, 20:30] = True           # a middle block (two of them) alone
    allowed[63] = False
    allowed[63, 63] = True              # one key, the diagonal's
    allowed[33, :32] = False            # the crossed block alone
    # the kernel applies the causal mask itself: ones above the diagonal
    # select nothing
    selected = jnp.asarray(allowed | ~np.tril(np.ones((t, t), bool)),
                           jnp.int8)[None]
    got = pa.flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                             interpret=True, selected=selected)
    want = _masked_softmax(q, k, v, allowed)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)
    # and two batch rows each take their own mask, whatever the heads
    both = pa.flash_attention(
        *(jnp.concatenate([a, a]) for a in (q, k, v)), causal=True,
        block_q=16, block_k=16, interpret=True, selected=jnp.concatenate(
            [selected, jnp.ones_like(selected)]))
    np.testing.assert_array_equal(np.asarray(both[0]), np.asarray(got[0]))
    np.testing.assert_allclose(
        np.asarray(both[1]), _masked_softmax(q, k, v, np.tril(allowed | True))
        [0], rtol=2e-5, atol=2e-6)


def test_tied_index_scores_go_to_the_lower_positions_in_the_kernels_mask():
    """Index scores that tie at the ``topk``-th place (whole numbers: a
    third of them exactly 0 under the ReLU): the mask the kernel is handed
    holds ``jax.lax.top_k``'s set, the lower positions of a tie, whatever
    the query blocks; and the kernel under it equals full scores under the
    scattered ``top_k`` indices."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as attn_op
    from mxnet_tpu.ops import pallas_attention as pa

    t, topk = 128, 24
    iq, ik, iw = _index_operands(2, t, 8, "float32", whole=True)
    score = np.asarray(attn_op.index_scores(iq[0].transpose(1, 0, 2), iw[0],
                                            ik[0, 0]))
    causal = np.tril(np.ones((t, t), bool))
    _, at = jax.lax.top_k(jnp.where(causal, score, -jnp.inf), topk)
    want = np.zeros((t, t), bool)
    want[np.arange(t)[:, None], np.asarray(at)] = True
    want &= causal
    kth = np.sort(np.where(causal, score, -np.inf), axis=1)[:, -topk]
    tied = ((np.where(causal, score, -np.inf) == kth[:, None]).sum(1) > 1)
    assert tied[topk:].sum() > t // 2       # the rule is exercised
    for heads in (1, 16):                   # one block of queries, sixteen
        saved, attn_op._SCORE_BYTES = attn_op._SCORE_BYTES, 4 * t * 8 * 16
        try:
            got = attn_op._selection(heads, iq, ik, iw, topk)
        finally:
            attn_op._SCORE_BYTES = saved
        np.testing.assert_array_equal(np.asarray(got[0], bool), want)
    q, k, v = _operands(2, 2, t, t, 16, 16, "float32")
    out = pa.flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                             interpret=True, selected=got)
    np.testing.assert_allclose(np.asarray(out),
                               _masked_softmax(q, k, v, want),
                               rtol=2e-5, atol=2e-6)


def test_under_a_selection_a_block_above_the_diagonal_is_never_read():
    """As the plain kernel's: keys and values past the first block of
    queries' diagonal are NaN and the MASK says "selected" everywhere above
    the diagonal; the first block's rows are what the clean operands give,
    bit for bit (a block above the diagonal is not run, and inside the
    crossed block the causal mask holds whatever the selection says)."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_attention as pa

    t = 64
    q, k, v = _operands(4, 2, t, t, 128, 128, "float32")
    rs = np.random.RandomState(11)
    below = (rs.rand(t, t) < 0.5) | np.eye(t, dtype=bool)
    above = ~np.tril(np.ones((t, t), bool))
    run = lambda k, v, mask: np.asarray(pa.flash_attention(
        q, k, v, causal=True, block_q=16, block_k=16, interpret=True,
        selected=jnp.asarray(mask, jnp.int8)[None]))
    poison = lambda a: a.at[:, :, 16:].set(jnp.nan)
    clean = run(k, v, below & ~above)
    dirty = run(poison(k), poison(v), below | above)
    np.testing.assert_array_equal(dirty[:, :, :16], clean[:, :, :16])
    assert np.isnan(dirty[:, :, 16:]).all()


def test_the_op_runs_a_selection_through_the_kernel_and_counts_it(
        monkeypatch):
    """``MultiHeadAttention(topk=)`` off the chip is XLA's query blocks and
    counts ``"sparse"``; with the rule held to ``"sparse_kernel"`` the same
    call makes the mask, runs the kernel interpreted and counts that. Its
    gradient is the XLA form's (the kernel has no backward under a
    selection, and says so where it is differentiated alone)."""
    from mxnet_tpu.ops import attention as attn_op
    from mxnet_tpu.ops import pallas_attention as pa

    t = 64
    q, k, v = _operands(4, 2, t, t, 16, 8, "float32")
    index = _index_operands(4, t, 8, "float32")
    before = dict(attn_op.DISPATCH_COUNTS)
    sparse = _mha(q, k, v, *index, topk=16)
    assert attn_op.DISPATCH_COUNTS["sparse"] == before["sparse"] + 1
    loss = lambda q, k, v: (_mha(q, k, v, *index, topk=16) ** 2).sum()
    want = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setattr(attn_op, "attention_form",
                        lambda *a: "sparse_kernel")
    kernel = _mha(q, k, v, *index, topk=16)
    assert attn_op.DISPATCH_COUNTS["sparse_kernel"] \
        == before["sparse_kernel"] + 1
    # (the one call and the gradient's trace)
    assert attn_op.DISPATCH_COUNTS["sparse"] == before["sparse"] + 2
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(sparse),
                               rtol=2e-5, atol=2e-5)
    got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5)
    selected = attn_op._selection(4, *index, 16)
    with pytest.raises(NotImplementedError, match="not differentiable"):
        jax.grad(lambda q: pa.flash_attention(
            q, k, v, causal=True, interpret=True,
            selected=selected).sum())(q)


# --------------------------------------------------- the kernel under a window
def _windowed_softmax(q, k, v, window, scale):
    """The dense form's masked softmax in numpy float64: query r attends keys
    ``r + (S - T) - window < j <= r + (S - T)``; grouped heads, any widths."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    (b, h, t, d), (_, hkv, s, _) = q.shape, k.shape
    sc = np.einsum("bkgqd,bkud->bkgqu", q.reshape(b, hkv, h // hkv, t, d),
                   k) * scale
    at = np.arange(t)[:, None] + (s - t) - np.arange(s)[None, :]
    sc = np.where((at >= 0) & (at < window), sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bkgqu,bkud->bkgqd", p, v).reshape(b, h, t, -1)


@pytest.mark.parametrize("widths", [(128, 128), (192, 128)])
@pytest.mark.parametrize("group", [1, 2, 9])
@pytest.mark.parametrize("t,block", [(1024, (128, 128)), (704, (32, 64))])
@pytest.mark.parametrize("window", [128, 512, 513])
def test_the_windowed_kernel_interpreted_is_the_masked_softmax(
        window, t, block, group, widths):
    """``flash_attention(window=W)`` against the dense form's masked softmax:
    the three configurations' windows, T a multiple of W (1,024 of 128 and
    512) and of neither W nor W - 1 (704), the groups of the cells (laguna's
    9 among them), a value narrower than the key. The first W - 1 rows see
    fewer than W keys; the grid's key axis is shorter than the keys'."""
    from mxnet_tpu.ops import pallas_attention as pa

    q, k, v = _operands(group, 1, t, t, *widths, "float32")
    scale = widths[0] ** -0.5
    got = pa.flash_attention(q, k, v, causal=True, scale=scale,
                             block_q=block[0], block_k=block[1],
                             interpret=True, window=window)
    assert got.shape == (1, group, t, widths[1])
    assert pa.window_key_blocks(t, t, *block, window) < t // block[1]
    np.testing.assert_allclose(np.asarray(got),
                               _windowed_softmax(q, k, v, window, scale),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("window", [24, 128, 200])
def test_the_windowed_kernel_over_more_keys_than_queries(window, dtype):
    """S > T: the window hangs from the bottom-right diagonal, query r's
    newest key ``r + (S - T)``; and the operands' types: bfloat16 to one
    rounding of the output."""
    from mxnet_tpu.ops import pallas_attention as pa

    q, k, v = _operands(4, 2, 128, 384, 64, 32, dtype)
    got = pa.flash_attention(q, k, v, causal=True, scale=0.125, block_q=32,
                             block_k=64, interpret=True, window=window)
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, "float32"),
        _windowed_softmax(q, k, v, window, 0.125), rtol=tol, atol=tol)


def test_the_first_rows_of_a_window_see_the_keys_they_have():
    """Row 0 attends itself alone and row r < W its r + 1 keys: the output's
    first row IS the value's, and a window as long as the bucket is plain
    causal attention."""
    from mxnet_tpu.ops import pallas_attention as pa

    q, k, v = _operands(2, 2, 64, 64, 16, 16, "float32")
    run = lambda w: np.asarray(pa.flash_attention(
        q, k, v, causal=True, block_q=16, block_k=16, interpret=True,
        window=w))
    np.testing.assert_allclose(run(8)[:, :, 0], np.asarray(v)[:, :, 0],
                               rtol=1e-6)
    np.testing.assert_allclose(run(8)[:, :, :8], run(64)[:, :, :8],
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(run(64), np.asarray(pa.flash_attention(
        q, k, v, causal=True, block_q=16, block_k=16, interpret=True)),
        rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("group", [1, 4])
def test_a_block_outside_the_window_is_never_read(group):
    """Keys and values OUTSIDE the blocks query block 4 visits (before the
    block of its first row's oldest key, past its diagonal's) are NaN: a
    masked product would carry them into every row (0 x NaN), a block that
    is neither run nor fetched leaves the block's rows what the clean
    operands give, bit for bit."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_attention as pa

    w, blk = 24, 16
    q, k, v = _operands(2 * group, 2, 128, 128, 128, 128, "float32")
    run = lambda k, v: np.asarray(pa.flash_attention(
        q, k, v, causal=True, block_q=blk, block_k=blk, interpret=True,
        window=w))
    # rows 64..79 reach back to key 64 - 23 = 41 (block 2) and up to 79
    rows = slice(4 * blk, 5 * blk)
    poison = lambda a: a.at[:, :, :2 * blk].set(jnp.nan).at[
        :, :, 5 * blk:].set(jnp.nan)
    clean, dirty = run(k, v), run(poison(k), poison(v))
    np.testing.assert_array_equal(dirty[:, :, rows], clean[:, :, rows])
    assert np.isnan(dirty[:, :, :2 * blk]).all()
    assert pa.window_key_blocks(128, 128, blk, blk, w) == 3


# a window layer of each configuration with one: (H, Hkv, dk, dv, window)
_WINDOW_LAYERS = {
    "laguna-s-2.1": (72, 8, 128, 128, 512),
    "dots3-note-prev": (64, 64, 256, 128, 513),
    "phi-4-mini-flash-reasoning": (40, 10, 128, 128, 512),
    "mimo-v2-flash": (64, 8, 192, 128, 128),
}


@pytest.mark.parametrize("layer", list(_WINDOW_LAYERS))
def test_every_tiling_the_rule_names_under_a_window_holds_the_output(layer):
    """``blocks(window=)`` at a cell's window layer (one key/value head of
    it, bfloat16 sizes, the bucket cut to 2,048: the rule's answer is the
    cell's own 8,192's) tiles the call, and the kernel at that tiling is the
    masked softmax."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_attention as pa

    h, hkv, dk, dv, w = _WINDOW_LAYERS[layer]
    g, t = h // hkv, 2048
    tiling = pa.blocks(t, t, g, dk, dv, jnp.bfloat16, window=w)
    assert tiling == pa.blocks(8192, 8192, g, dk, dv, jnp.bfloat16, window=w)
    assert t % tiling[0] == 0 and t % tiling[1] == 0
    assert pa.block_bytes(*tiling, g, dk, dv, jnp.bfloat16) \
        <= pa._VMEM_BUDGET
    q, k, v = _operands(g, 1, t, t, dk, dv, "float32")
    got = pa.flash_attention(q, k, v, causal=True, scale=dk ** -0.5,
                             block_q=tiling[0], block_k=tiling[1],
                             interpret=True, window=w)
    np.testing.assert_allclose(np.asarray(got),
                               _windowed_softmax(q, k, v, w, dk ** -0.5),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("t,window", [(64, 16), (60, 16), (64, 13)])
def test_the_op_runs_a_window_through_the_kernel_and_its_gradient_is_the_bands(
        monkeypatch, t, window):
    """``MultiHeadAttention(window=)`` off the chip is a band (the dense form
    where no block tiles T) and counts it; with the rule held to
    ``"window_kernel"`` the same call runs the kernel interpreted and counts
    that. Its gradient is XLA's form's, to the last bit (the kernel has no
    backward under a window, and says so where it is differentiated
    alone)."""
    from mxnet_tpu.ops import attention as attn_op
    from mxnet_tpu.ops import pallas_attention as pa

    q, _, v = _operands(4, 2, t, t, 16, 8, "float32")
    k = _operands(4, 2, t, t, 16, 8, "float32", seed=9)[1]
    xla = "band" if attn_op._band_block(t, window) else "dense"
    before = dict(attn_op.DISPATCH_COUNTS)
    want = _mha(q, k, v, window=window)
    assert attn_op.DISPATCH_COUNTS[xla] == before[xla] + 1
    loss = lambda q, k, v: (_mha(q, k, v, window=window) ** 2).sum()
    want_grad = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setattr(attn_op, "attention_form",
                        lambda *a: "window_kernel")
    blocks = pa.blocks
    monkeypatch.setattr(pa, "blocks", lambda *a, **kw: (
        4 if t % 8 else 8, 4 if t % 8 else 16))
    got = _mha(q, k, v, window=window)
    assert attn_op.DISPATCH_COUNTS["window_kernel"] \
        == before["window_kernel"] + 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # the gradient's cotangent comes from the KERNEL's output, its pull-back
    # is XLA's form's
    got_grad = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got_grad, want_grad):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5)
    monkeypatch.setattr(pa, "blocks", blocks)
    with pytest.raises(NotImplementedError, match="not differentiable"):
        jax.grad(lambda q: pa.flash_attention(
            q, k, v, causal=True, block_q=4, block_k=4, interpret=True,
            window=window).sum())(q)
    with pytest.raises(ValueError, match="causal calls without a selection"):
        pa.flash_attention(q, k, v, causal=False, block_q=4, block_k=4,
                           interpret=True, window=window)


# ------------------------------------------------------- flash attention grads
def _xla_attention_jax(q, k, v, causal, scale=None):
    import jax
    import jax.numpy as jnp

    d = q.shape[-1]
    scale = scale or 1.0 / np.sqrt(d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        T, S = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((T, S), bool), k=S - T)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [
    # (B, H, T, S, D): square, rectangular decode (S > T), multi-block
    (2, 2, 16, 16, 8),
    (1, 2, 8, 32, 8),
    (1, 1, 32, 32, 16),
])
def test_pallas_flash_attention_grad_matches_xla(causal, shape):
    """VERDICT r2 item 5: jax.grad through flash_attention must match the
    XLA path (it used to fail with a bare AssertionError)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_attention as pa

    B, H, T, S, D = shape
    rs = np.random.RandomState(5)
    q = jnp.asarray(rs.randn(B, H, T, D).astype("float32"))
    k = jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
    v = jnp.asarray(rs.randn(B, H, S, D).astype("float32"))
    w = jnp.asarray(rs.randn(B, H, T, D).astype("float32"))  # cotangent mix

    def loss_flash(q, k, v):
        out = pa.flash_attention(q, k, v, causal=causal, block_q=8,
                                 block_k=8, interpret=True)
        return (out * w).sum()

    def loss_xla(q, k, v):
        return (_xla_attention_jax(q, k, v, causal) * w).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gx):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4,
            err_msg="d%s mismatch (causal=%s shape=%s)" % (name, causal, shape))


def test_pallas_flash_attention_grad_bf16_long_seq():
    """bf16 grads over a longer sequence (S=512, streamed in 128-blocks)
    track the XLA path within bf16 tolerance."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_attention as pa

    rs = np.random.RandomState(6)
    B, H, T, S, D = 1, 1, 256, 512, 8
    q = jnp.asarray(rs.randn(B, H, T, D).astype("float32"), dtype=jnp.bfloat16)
    k = jnp.asarray(rs.randn(B, H, S, D).astype("float32"), dtype=jnp.bfloat16)
    v = jnp.asarray(rs.randn(B, H, S, D).astype("float32"), dtype=jnp.bfloat16)

    def loss_flash(q, k, v):
        return pa.flash_attention(q, k, v, causal=True,
                                  interpret=True).astype(jnp.float32).sum()

    def loss_xla(q, k, v):
        return _xla_attention_jax(q.astype(jnp.float32), k.astype(jnp.float32),
                                  v.astype(jnp.float32), True).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gx):
        np.testing.assert_allclose(
            np.asarray(a, dtype=np.float32), np.asarray(b), rtol=5e-2,
            atol=2e-2, err_msg="d%s bf16 mismatch" % name)


def test_pallas_training_through_module_op(monkeypatch):
    """Training through the op where the rule names the kernel must not
    crash and must produce the dense path's grads — the round-2 failure
    mode. Grouped heads: the backward repeats the keys and sums the group."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as attn_op

    q, k, v = _operands(4, 2, 16, 16, 8, 8, "float32", seed=7)
    loss = lambda q, k, v: jnp.square(_mha(q, k, v)).sum()
    want = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setattr(attn_op, "attention_form", lambda *a: "kernel")
    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for got, ref in zip(grads, want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


def test_pallas_supported_rejects_causal_decode_underflow():
    """ADVICE r2: causal with S < T has fully-masked rows — must be
    rejected so the XLA path handles it."""
    from mxnet_tpu.ops import pallas_attention as pa

    assert not pa.supported((1, 1, 32, 8), (1, 1, 16, 8), causal=True)
    assert pa.supported((1, 1, 32, 8), (1, 1, 16, 8), causal=False)
    assert pa.supported((1, 1, 16, 8), (1, 1, 32, 8), causal=True)


@pytest.mark.skipif("jax.default_backend() != 'tpu'")
def test_pallas_flash_attention_grad_8k_tpu():
    """Long-context check on real hardware: S=T=8192 streams through VMEM in
    128-blocks (fwd + bwd), grads finite and close to XLA (bf16)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_attention as pa

    rs = np.random.RandomState(8)
    B, H, T, D = 1, 1, 8192, 64
    q, k, v = (jnp.asarray(rs.randn(B, H, T, D).astype("float32") * 0.1,
                           dtype=jnp.bfloat16) for _ in range(3))

    def loss(q, k, v):
        return pa.flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert all(np.isfinite(np.asarray(g, dtype=np.float32)).all() for g in grads)

    def loss_xla(q, k, v):
        return _xla_attention_jax(q.astype(jnp.float32), k.astype(jnp.float32),
                                  v.astype(jnp.float32), True).sum()

    gx = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", grads, gx):
        np.testing.assert_allclose(np.asarray(a, dtype=np.float32),
                                   np.asarray(b), rtol=5e-2, atol=5e-2,
                                   err_msg="d%s 8k mismatch" % name)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_dispatch_in_op(causal):
    """Under a trace mesh with a 'seq' axis, the MultiHeadAttention op must
    dispatch to ring attention (dp x sp) and match the dense path exactly."""
    import jax

    from mxnet_tpu.ops import attention as attn_op
    from mxnet_tpu.ops.registry import get_op
    from mxnet_tpu.parallel.mesh import trace_mesh

    rs = np.random.RandomState(3)
    B, H, T, D = 4, 2, 16, 4
    q, k, v = (rs.randn(B, H, T, D).astype("float32") for _ in range(3))
    opdef = get_op("_contrib_MultiHeadAttention")
    attrs = {"causal": causal, "scale": -1.0}
    (dense,), _ = opdef.apply(attrs, [q, k, v])

    mesh = parallel.make_mesh({"data": 2, "seq": 4}, devices=jax.devices()[:8])
    before = attn_op.DISPATCH_COUNTS["ring"]
    with trace_mesh(mesh):
        (ring,), _ = opdef.apply(attrs, [q, k, v])
    assert attn_op.DISPATCH_COUNTS["ring"] == before + 1
    np.testing.assert_allclose(np.asarray(ring), np.asarray(dense),
                               rtol=1e-4, atol=1e-5)


def test_ring_dispatch_respects_kill_switch(monkeypatch):
    import jax

    from mxnet_tpu.ops import attention as attn_op
    from mxnet_tpu.ops.registry import get_op
    from mxnet_tpu.parallel.mesh import trace_mesh

    monkeypatch.setenv("MXNET_RING_ATTENTION", "0")
    rs = np.random.RandomState(4)
    q, k, v = (rs.randn(2, 2, 16, 4).astype("float32") for _ in range(3))
    mesh = parallel.make_mesh({"data": 2, "seq": 4}, devices=jax.devices()[:8])
    before = attn_op.DISPATCH_COUNTS["ring"]
    with trace_mesh(mesh):
        get_op("_contrib_MultiHeadAttention").apply(
            {"causal": True, "scale": -1.0}, [q, k, v])
    assert attn_op.DISPATCH_COUNTS["ring"] == before
