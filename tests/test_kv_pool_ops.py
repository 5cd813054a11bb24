"""The two operators of the shared KV pool (``KVPoolWrite``,
``KVPoolAttention``; ops/attention.py) against the spelling they replaced in
``models/transformer.py``: broadcast products summed over an axis, written
out here as the decode, the OLMoE and the chunk builders had them.

The write must agree bit for bit (it multiplies by exactly 0 and 1); the
read sums the same float32 products in another order.

And a decode step's write by slot index (``KVPoolSlotWrite``: the page that
holds a row's slot read, the row put in, the page written back) against that
blend with the one-hots ``PagedKVDecoder.step`` used to build on the host,
bit for bit; and the operator that makes a step's masks on the device
(``KVPageMask``) against the host's arrays, element for element.

And the read's second form, a row's own pages gathered by its table, against
the whole-pool read under the mask made of the same table.

Every operator in BOTH layouts of a pool (``pool_shape``): head-major
(H, S, dh) where a token's row of all its heads is no whole tile of the
chip's lanes, page-major (frames, page, H * dh) where it is. ``_by_slot``
reads either as (H, S, dh), so a case says one thing of both. (The kernel that
reads page-major pools on the chip: tests/test_paged_read_kernel.py.)
"""
import functools
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import attention
from mxnet_tpu.ops.attention import (_kv_page_mask, _kv_pool_attention,
                                     _kv_pool_slot_write, _kv_pool_write,
                                     pool_read_bytes, pool_read_form,
                                     pool_shape)

H, S, DH = 4, 96, 16
SCALE = 1.0 / np.sqrt(DH)
PAGE = 8
# a head's width -> the layout ``pool_shape`` gives H heads of it: 4 x 16 is
# no whole tile of lanes, 4 x 32 is one
LAYOUTS = pytest.mark.parametrize("dh", [DH, 32],
                                  ids=["head_major", "page_major"])


def _parent_write(pool, rows, onehot, chunk):
    """``kv·keep + Σ_r new_r·onehot_r`` in the pool's type: the one-hots and
    the keep mask are cast to it (the OLMoE builder did; in float32 the cast
    is nothing), the sum runs over lanes (decode) or chunk rows."""
    dt = pool.dtype
    keep3 = (1.0 - jnp.sum(onehot, axis=0).reshape(1, S, 1)).astype(dt)
    if chunk:   # (H, T, 1, dh) · (1, T, S, 1), summed over the rows
        wr = jnp.sum(rows.transpose(1, 0, 2)[:, :, None, :]
                     * onehot[None, :, :, None].astype(dt), axis=1)
    else:       # (B, H, 1, dh) · (B, 1, S, 1), summed over the lanes
        wr = jnp.sum(rows[:, :, None, :]
                     * onehot[:, None, :, None].astype(dt), axis=0)
    return pool * keep3 + wr


def _parent_read(q, pool_k, pool_v, mask):
    """scores = Σ_d q·k, softmax, Σ_s p·v as float32 broadcast products."""
    q, k, v = (a.astype(jnp.float32) for a in (q, pool_k, pool_v))
    scores = jnp.sum(q[:, :, None, :] * k[None], axis=3) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(scores + mask[:, None, :], axis=-1)
    return jnp.sum(p[..., None] * v[None], axis=2)


def _case(dtype, rows, seed=0, dh=DH):
    """A pool, R new rows and their inputs. Decode: 5 lanes at scattered
    slots, lane 3 idle (no write, nothing visible). Chunk: 4 positions of one
    lane in a row of slots, each seeing the lane's past and the chunk up to
    itself, the last a pad row (no write, fully masked). The pools come
    head-major (H, S, dh) whatever ``dh``: what the parent's spelling takes;
    ``_bound`` lays them out as a decoder binds them."""
    rs = np.random.RandomState(seed)
    R = 4 if rows == "chunk" else 5
    pool_k, pool_v = (jnp.asarray(rs.randn(H, S, dh), dtype) for _ in "kv")
    q, k_new, v_new = (jnp.asarray(rs.randn(R, H, dh), dtype) for _ in "qkv")
    onehot = np.zeros((R, S), "f")
    mask = np.full((R, S), -1e9, "f")
    if rows == "chunk":
        for j in range(R - 1):
            onehot[j, 40 + j] = 1.0
            mask[j, 8:24] = 0.0
            mask[j, 40:41 + j] = 0.0
    else:
        for lane, slot in ((0, 7), (1, 64), (2, 95), (4, 0)):
            onehot[lane, slot] = 1.0
            mask[lane, rs.choice(S, 20, replace=False)] = 0.0
            mask[lane, slot] = 0.0
    return (pool_k, pool_v, q, k_new, v_new, jnp.asarray(onehot),
            jnp.asarray(mask))


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


def _by_slot(pool, heads):
    """A pool of ``heads`` heads in either layout as (heads, slots, width):
    a page-major pool's frames are runs of ``page`` slots and its row a
    token's heads side by side."""
    pool = np.asarray(pool)
    if pool.shape[-1] % 128:
        return pool
    return pool.reshape(-1, heads, pool.shape[-1] // heads).transpose(1, 0, 2)


def _bound(pool, page=PAGE):
    """A head-major array (heads, slots, width) in the layout ``pool_shape``
    binds a pool of its heads and width in."""
    heads, slots, width = pool.shape
    shape = pool_shape(heads, width, slots, page)
    if shape == pool.shape:
        return pool
    return jnp.transpose(pool, (1, 0, 2)).reshape(shape)


CASES = pytest.mark.parametrize("rows", ["decode", "chunk"])
DTYPES = pytest.mark.parametrize("dtype", ["float32", "bfloat16"])


@LAYOUTS
@DTYPES
@CASES
def test_pool_write_is_the_parents_blend_bit_for_bit(dtype, rows, dh):
    pool, _, _, k_new, _, onehot, _ = _case(dtype, rows, dh=dh)
    bound = _bound(pool)
    assert (bound.shape == pool.shape) == (dh == DH)
    got = _kv_pool_write({}, bound, k_new, onehot)
    assert got.dtype == pool.dtype and got.shape == bound.shape
    got = _by_slot(got, H)
    want = _parent_write(pool, k_new, onehot, chunk=(rows == "chunk"))
    np.testing.assert_array_equal(_bits(got), _bits(want))
    # a written slot holds the row itself, every other slot what it held
    written = np.asarray(onehot).sum(0) > 0
    np.testing.assert_array_equal(_bits(got)[:, ~written],
                                  _bits(pool)[:, ~written])
    for r, s in zip(*np.nonzero(np.asarray(onehot))):
        np.testing.assert_array_equal(_bits(got)[:, s], _bits(k_new)[r])
    assert written.sum() == len(onehot) - 1     # the idle lane, the pad row


@LAYOUTS
@DTYPES
def test_pool_write_with_no_onehot_leaves_the_pool_bitwise(dtype, dh):
    """The replay of a fully cached prompt: every row's one-hot is zero."""
    pool, _, _, k_new, _, onehot, _ = _case(dtype, "chunk", dh=dh)
    got = _kv_pool_write({}, _bound(pool), k_new, jnp.zeros_like(onehot))
    np.testing.assert_array_equal(_bits(got), _bits(_bound(pool)))


@LAYOUTS
@DTYPES
@CASES
def test_pool_attention_is_the_parents_masked_weighted_sum(dtype, rows, dh):
    pool_k, pool_v, q, _, _, _, mask = _case(dtype, rows, dh=dh)
    bound_k, bound_v = _bound(pool_k), _bound(pool_v)
    got = _kv_pool_attention({"scale": -1.0}, q, bound_k, bound_v, mask)
    assert got.dtype == q.dtype and got.shape == q.shape
    want = _parent_read(q, pool_k, pool_v, mask)
    err = (np.linalg.norm(np.asarray(got, "f") - np.asarray(want), axis=-1)
           / np.linalg.norm(np.asarray(want), axis=-1))
    # float32: the same products (exact ones, of bfloat16 values) summed in
    # another order; a bfloat16 query takes the context back in bfloat16,
    # one rounding of 2^-9 an element
    assert err.max() < (1e-6 if dtype == "float32" else 2.0 ** -8)
    explicit = _kv_pool_attention({"scale": 1.0 / np.sqrt(dh)}, q, bound_k,
                                  bound_v, mask)
    np.testing.assert_array_equal(_bits(explicit), _bits(got))


@LAYOUTS
@DTYPES
@CASES
def test_a_fully_masked_row_reads_finite_and_moves_no_other(dtype, rows, dh):
    """The idle lane of a decode step and the pad row of a chunk see no slot:
    the softmax subtracts the row's maximum, so they come out finite (and are
    discarded), and the other rows read what they read without them."""
    pool_k, pool_v, q, _, _, _, mask = _case(dtype, rows, dh=dh)
    pool_k, pool_v = _bound(pool_k), _bound(pool_v)
    dead = 3
    assert float(mask[dead].max()) == -1e9
    got = np.asarray(_kv_pool_attention({"scale": -1.0}, q, pool_k, pool_v,
                                        mask), "f")
    assert np.isfinite(got).all()
    live = [r for r in range(len(q)) if r != dead]
    alone = np.asarray(_kv_pool_attention(
        {"scale": -1.0}, q[jnp.asarray(live)], pool_k, pool_v,
        mask[jnp.asarray(live)]), "f")
    np.testing.assert_allclose(got[live], alone, rtol=1e-6, atol=1e-6)


def test_the_write_asks_for_exact_products_and_the_read_for_the_default():
    """What reaches the compiler: the one-hot matmul at HIGHEST (on the chip
    a default-precision float32 matmul rounds the stored row to bfloat16),
    the two contractions of the read at the default precision, as the
    prefill's attention has them."""
    pool_k, pool_v, q, k_new, _, onehot, mask = _case("float32", "decode")
    write = jax.jit(lambda *a: _kv_pool_write({}, *a)).lower(
        pool_k, k_new, onehot).as_text()
    assert write.count("dot_general") == 1
    assert "precision = [HIGHEST, HIGHEST]" in write
    read = jax.jit(lambda *a: _kv_pool_attention({"scale": -1.0}, *a)).lower(
        q, pool_k, pool_v, mask).as_text()
    assert read.count("dot_general") == 2 and "HIGHEST" not in read


def test_symbols_infer_the_pool_and_the_row_inputs():
    v = mx.sym.Variable
    w = mx.sym.KVPoolWrite(v("pool"), v("rows"), v("onehot"), name="w")
    assert w.list_arguments() == ["pool", "rows", "onehot"]
    shapes = [(H, S, DH), (5, H, DH), (5, S)]
    assert w.infer_shape(rows=shapes[1], onehot=shapes[2]) \
        == (shapes, [shapes[0]], [])
    assert w.infer_shape(pool=shapes[0], rows=shapes[1])[0] == shapes
    a = mx.sym.KVPoolAttention(v("q"), w, v("pool_v"), v("mask"), name="a")
    arg_shapes, out_shapes, _ = a.infer_shape(
        q=(5, H, DH), pool=shapes[0], rows=shapes[1])
    assert dict(zip(a.list_arguments(), arg_shapes)) == {
        "q": (5, H, DH), "pool": shapes[0], "rows": shapes[1],
        "onehot": (5, S), "pool_v": shapes[0], "mask": (5, S)}
    assert out_shapes == [(5, H, DH)]
    # a page-major pool's slots are its frames x its page
    paged = pool_shape(H, 32, S, PAGE)
    assert paged == (S // PAGE, PAGE, H * 32)
    assert w.infer_shape(pool=paged, rows=(5, H, 32))[0] \
        == [paged, (5, H, 32), (5, S)]
    arg_shapes, out_shapes, _ = a.infer_shape(
        q=(5, H, 32), pool=paged, rows=(5, H, 32))
    assert dict(zip(a.list_arguments(), arg_shapes))["mask"] == (5, S)
    assert out_shapes == [(5, H, 32)]


@LAYOUTS
@DTYPES
def test_one_step_through_the_executor_is_the_two_operators(dtype, dh):
    """Bound as a graph (what the decode builders do): the written pool and
    the context of one step, against the operators called directly."""
    pool_k, pool_v, q, k_new, v_new, onehot, mask = _case(dtype, "decode", 5,
                                                          dh=dh)
    v = mx.sym.Variable
    k_upd = mx.sym.KVPoolWrite(v("kv_k"), v("k_new"), v("oh"), name="kupd")
    v_upd = mx.sym.KVPoolWrite(v("kv_v"), v("v_new"), v("oh"), name="vupd")
    ctx = mx.sym.KVPoolAttention(v("q"), k_upd, v_upd, v("msk"), name="att")
    exe = mx.sym.Group([ctx, k_upd, v_upd]).bind(mx.cpu(), {
        name: mx.nd.NDArray(a) for name, a in dict(
            kv_k=_bound(pool_k), kv_v=_bound(pool_v), k_new=k_new,
            v_new=v_new, oh=onehot, q=q, msk=mask).items()})
    exe.forward()
    got_ctx, got_k, got_v = (o._jax() for o in exe.outputs)
    want_k = _parent_write(pool_k, k_new, onehot, chunk=False)
    want_v = _parent_write(pool_v, v_new, onehot, chunk=False)
    np.testing.assert_array_equal(_bits(_by_slot(got_k, H)), _bits(want_k))
    np.testing.assert_array_equal(_bits(_by_slot(got_v, H)), _bits(want_v))
    want = _kv_pool_attention({"scale": -1.0}, q, _bound(want_k),
                              _bound(want_v), mask)
    np.testing.assert_allclose(np.asarray(got_ctx, "f"), np.asarray(want, "f"),
                               rtol=1e-6, atol=1e-6)


# ------------------------------------------------- the write by slot index
# case -> the slots of its rows in a pool of SLOT_S slots (pages of 8)
SLOT_S = 80
SLOT_WRITES = {
    "scattered": [7, 64, 33, 0],
    "negative_slot": [5, -1, 40, -1],
    "last_slot_of_a_page": [15, 47, 79],        # 79: the pool's last, too
    "two_rows_in_one_page": [18, 29, 50],
    "every_row_rides_along": [-1, -1],
    "empty_step": [],
    "two_rows_on_one_slot": [9, 30, 9],         # the later one stays
}


@pytest.mark.parametrize("case", list(SLOT_WRITES))
@pytest.mark.parametrize("heads,width", [(8, 64), (1, 576), (2, 24)])
@DTYPES
def test_slot_write_is_the_onehot_blend_bit_for_bit(dtype, heads, width,
                                                    case):
    """``KVPoolSlotWrite`` against ``KVPoolWrite`` fed the host's one-hots of
    the same slots, at the pool shapes the serving cells have (8 heads of
    64: a row of 512, PAGE-MAJOR; one latent row of 576, head-major) and a
    toy model's (2 heads of 24, head-major): the same pool bit for bit, a
    written slot the row itself and every other slot what it held, a
    negative slot nothing, the later of two rows on one slot; a head-major
    pool shorter than a run and one whose length no run divides; one pool
    and two in the one loop."""
    slots = SLOT_WRITES[case]
    rs = np.random.RandomState(len(case) + heads)
    for pool_slots in (SLOT_S, 2 * attention._WRITE_RUN + 8):  # a cut run
        pool = jnp.asarray(rs.randn(heads, pool_slots, width), dtype)
        rows = jnp.asarray(rs.randn(len(slots), heads, width), dtype)
        bound = _bound(pool)
        assert (bound.shape != pool.shape) == (heads * width == 512)
        write_slot = jnp.asarray(slots, jnp.float32).reshape(-1, 1)
        got, = _kv_pool_slot_write({}, bound, rows, write_slot)
        assert got.dtype == pool.dtype and got.shape == bound.shape
        # row after row, as numpy says it
        want = np.asarray(pool).copy()
        for r, slot in enumerate(slots):
            if slot >= 0:
                want[:, slot] = np.asarray(rows)[r]
        np.testing.assert_array_equal(_bits(_by_slot(got, heads)),
                                      _bits(want))
        onehot = np.zeros((len(slots), pool_slots), "f")
        for r, slot in enumerate(slots):
            if slot >= 0:
                onehot[r, slot] = 1.0
        blend = _kv_pool_write({}, bound, rows, jnp.asarray(onehot))
        if onehot.sum(0).max() <= 1:    # the blend SUMS two rows on a slot
            np.testing.assert_array_equal(_bits(got), _bits(blend))
        # a layer's two pools in the one loop: each as it is alone
        both = _kv_pool_slot_write({"num_pools": 2}, bound, rows, blend,
                                   rows[::-1], write_slot)
        np.testing.assert_array_equal(_bits(both[0]), _bits(got))
        np.testing.assert_array_equal(_bits(both[1]), _bits(
            _kv_pool_slot_write({}, blend, rows[::-1], write_slot)[0]))
        untouched = np.setdiff1d(np.arange(pool_slots),
                                 [slot for slot in slots if slot >= 0])
        np.testing.assert_array_equal(
            _bits(_by_slot(got, heads))[:, untouched],
            _bits(pool)[:, untouched])


def test_slot_write_takes_two_pools_each_in_its_own_layout():
    """A layer whose key row is whole tiles and whose value row is not (2
    heads of 64 beside 2 of 48): one loop, the key pool page-major and the
    value pool head-major, each the one-hot blend of its own."""
    rs = np.random.RandomState(3)
    slots = [5, -1, 33, 79]
    onehot = np.zeros((len(slots), SLOT_S), "f")
    for r, slot in enumerate(slots):
        if slot >= 0:
            onehot[r, slot] = 1.0
    write_slot = jnp.asarray(slots, jnp.float32).reshape(-1, 1)
    pools = [_bound(jnp.asarray(rs.randn(2, SLOT_S, d), "float32"))
             for d in (64, 48)]
    rows = [jnp.asarray(rs.randn(len(slots), 2, d), "float32")
            for d in (64, 48)]
    assert [p.shape for p in pools] == [(10, 8, 128), (2, SLOT_S, 48)]
    got = _kv_pool_slot_write({"num_pools": 2}, pools[0], rows[0], pools[1],
                              rows[1], write_slot)
    for pool, new, out in zip(pools, rows, got):
        np.testing.assert_array_equal(
            _bits(out), _bits(_kv_pool_write({}, pool, new,
                                             jnp.asarray(onehot))))


# a looped stack's step (``ouro-2.6b``: 16 lanes, a layer's two pools of 16
# heads of 128 in bfloat16, 1,280 frames of 16): case -> the slots of its rows
LOOPED_FRAMES, LOOPED_WIDTH = 1280, 2048
LOOPED_WRITES = {
    "a_page_a_lane": [16 * (7 + 79 * lane) + (5 * lane) % 16
                      for lane in range(16)],
    "negative_slots_among_them": [-1 if lane % 4 == 1 else 16 * (3 + 80 * lane)
                                  + lane for lane in range(16)],
    "two_rows_on_one_slot": [16 * (9 + 70 * lane) for lane in range(15)]
    + [16 * 9],                                         # the later one stays
    "a_chunks_rows_in_one_page": [16 * 611 + i for i in range(16)],
    "one_row": [16 * LOOPED_FRAMES - 1],
}


@pytest.fixture(scope="module")
def looped_pools():
    rs = np.random.RandomState(55)
    # seeded BITS: every pattern of a bfloat16 but the NaNs' and infinities'
    bits = rs.randint(0, 1 << 16, (2, LOOPED_FRAMES, 16, LOOPED_WIDTH),
                      np.uint16)
    bits[(bits & 0x7F80) == 0x7F80] = 0
    return tuple(jnp.asarray(b).view(jnp.bfloat16) for b in bits)


def _rows_by_slot(pools, rows, slots):
    """What numpy says a write leaves: the bits of each pool's (slots, row)
    view with ``rows[r]`` at ``slots[r]``, row after row; a slot that is
    negative or past the pool's end takes nothing."""
    out = []
    for pool, new in zip(pools, rows):
        want = _bits(pool).reshape(-1, pool.shape[-1]).copy()
        for r, slot in enumerate(slots):
            if 0 <= slot < len(want):
                want[slot] = _bits(new)[r].reshape(-1)
        out.append(want)
    return out


def _slot_write_is_numpys(pools, rows, slots):
    inputs = [a for pair in zip(pools, rows) for a in pair]
    got = _kv_pool_slot_write(
        {"num_pools": len(pools)}, *inputs,
        jnp.asarray(slots, jnp.float32).reshape(-1, 1))
    for pool, out, want in zip(pools, got, _rows_by_slot(pools, rows, slots)):
        assert out.dtype == pool.dtype and out.shape == pool.shape
        np.testing.assert_array_equal(
            _bits(out).reshape(-1, pool.shape[-1]), want)


@pytest.mark.parametrize("case", list(LOOPED_WRITES))
def test_slot_write_at_a_looped_stacks_operands(looped_pools, case):
    """A page-major write at ``ouro-2.6b.generate``'s operands: the pools
    with the rows' bits at their slots, row after row as numpy says it, and
    every other slot's bits what they were; a negative slot nothing, the
    later of two rows on one slot, a chunk's 16 rows in ONE page, one row."""
    slots = LOOPED_WRITES[case]
    rs = np.random.RandomState(len(case))
    rows = [jnp.asarray(rs.randn(len(slots), 16, 128), "bfloat16")
            for _ in looped_pools]
    _slot_write_is_numpys(looped_pools, rows, slots)


# the other ``generate`` cells' page-major writes at few frames: cell ->
# (dtype, the row widths of a layer's key and value pools, rows a step)
CELL_FRAMES = 64
CELL_WRITES = {
    "transformer-base": ("float32", (512, 512), 64),
    "mimo-v2-flash": ("bfloat16", (768, 512), 32),      # a narrower value
    "phi-4-mini-flash-reasoning": ("bfloat16", (1280, 1280), 48),
}
# case -> the slots of a call's rows, from the rows a step and a generator
CELL_SLOTS = {
    "a_page_a_lane": lambda n, rs: rs.permutation(CELL_FRAMES)[:n] * 16
    + rs.randint(0, 16, n),
    "lanes_that_ride_along": lambda n, rs: np.where(
        np.arange(n) % 3 == 1, -1, rs.permutation(CELL_FRAMES)[:n] * 16 + 5),
    "nobody_writes": lambda n, rs: np.full(n, -1),
    "an_odd_count": lambda n, rs: np.r_[0, 17, 34, -1, 68, 85, 15],
    # a chunk's rows: one lane's consecutive slots
    "a_chunk_over_three_pages": lambda n, rs: 2 * 16 + 9 + np.arange(2 * 16),
    "a_page_shared_out_of_order": lambda n, rs: np.r_[48, 100, 50, 101, 49,
                                                      48],
    # what no caller sends: past the pool's end nothing is written
    "a_slot_past_the_end": lambda n, rs: np.r_[3, CELL_FRAMES * 16,
                                               CELL_FRAMES * 16 + 40, 19],
}


@pytest.mark.parametrize("case", list(CELL_SLOTS))
@pytest.mark.parametrize("cell", list(CELL_WRITES))
def test_slot_write_at_the_cells_operands(cell, case):
    """The scatter at each cell's type and widths (float32; a value pool
    narrower than its key pool) over the rows a step, a chunk or a verify
    program hands: numpy's row after row, bit for bit."""
    dtype, widths, lanes = CELL_WRITES[cell]
    rs = np.random.RandomState(len(cell) + len(case))
    slots = np.asarray(CELL_SLOTS[case](lanes, rs), np.int64)
    pools = [jnp.asarray(rs.randn(CELL_FRAMES, 16, w), dtype) for w in widths]
    rows = [jnp.asarray(rs.randn(len(slots), 4, w // 4), dtype)
            for w in widths]
    _slot_write_is_numpys(pools, rows, slots)


@pytest.mark.parametrize("pools,form", [
    ([(1280, 16, 2048)] * 2, "scatter"),            # ouro-2.6b
    ([(4096, 16, 768), (4096, 16, 512)], "scatter"),  # mimo-v2-flash
    ([(1, 4096, 576)], "loop"),                     # kanana's latent row
    ([(2, 64, 16)] * 2, "loop"),                    # toy heads
    # both layouts in one call: named by its page-major pool
    ([(64, 16, 512), (1, 1024, 576)], "scatter"),
])
def test_the_write_rule_reads_the_layout_off_the_pools(monkeypatch, pools,
                                                       form):
    """``pool_write_form`` from the pools' shapes alone, and the same on
    every backend: one form writes a page-major pool."""
    specs = [jax.ShapeDtypeStruct(shape, "bfloat16") for shape in pools]
    assert attention.pool_write_form(specs) == form
    monkeypatch.setattr(attention, "_backend", lambda: "tpu")
    assert attention.pool_write_form(specs) == form


@pytest.mark.parametrize("layout,pool,piece", [
    ("page_major", (256, 16, 512), "5x512"),
    ("head_major", (1, 4096, 576), "1x%dx576" % attention._WRITE_RUN)])
def test_slot_write_moves_a_row_or_a_run_and_nothing_of_the_pools_size(
        layout, pool, piece):
    """What reaches the compiler: no contraction, no one-hot, nothing
    (rows, slots) or pool-sized made. A call of PAGE-MAJOR pools alone is no
    loop: ONE scatter a pool of the step's rows, (rows, H * dh), into the
    pool's (slots, H * dh) view, the slot the one scattered index. A
    HEAD-MAJOR pool: a loop whose body slices the run of slots that holds a
    row's slot and updates it."""
    heads = 8 if layout == "page_major" else 1
    rows = jnp.zeros((5, heads, pool[2] // heads), jnp.float32)
    text = jax.jit(lambda *a: _kv_pool_slot_write({}, *a)[0]).lower(
        jnp.zeros(pool, jnp.float32), rows,
        jnp.zeros((5, 1), jnp.float32)).as_text()
    assert "dot_general" not in text and "5x4096" not in text
    assert piece in text
    made = {tuple(int(d) for d in dims.split("x")) for dims in
            re.findall(r"tensor<((?:\d+x)*\d+)x[a-z]\w*>", text)}
    if layout == "page_major":
        assert "stablehlo.while" not in text
        assert "dynamic_slice" not in text
        assert text.count("\"stablehlo.scatter\"(") == 1
        assert "x%dx" % attention._WRITE_RUN not in text
        # the pool, its (slots, row) view and nothing else of its size
        assert [shape for shape in made
                if math.prod(shape) >= math.prod(pool)] \
            == [shape for shape in ((256, 16, 512), (4096, 512))
                if shape in made]
    else:
        assert "dynamic_slice" in text and "dynamic_update_slice" in text
        assert "stablehlo.while" in text and "stablehlo.scatter" not in text


@pytest.mark.parametrize("arch", ["vaswani", "olmoe", "granite_hybrid",
                                  "deepseek_v3", "lfm2_moe"])
def test_decode_symbols_write_by_slot_and_build_no_onehot(arch):
    """Every decode graph writes its pools through ``KVPoolSlotWrite``, one
    node a layer that has pools; none names ``KVSlotOneHot`` (the operator is gone) or
    ``KVPoolWrite`` (the chunk graph's, whose one-hots the host makes)."""
    import json

    from mxnet_tpu.models import transformer as tf

    sizes = {
        "vaswani": dict(pos_len=16),
        "olmoe": dict(arch="olmoe", head_dim=8, num_experts=4,
                      num_experts_per_tok=2),
        "granite_hybrid": dict(
            arch="granite_hybrid", num_kv_heads=2, head_dim=8,
            layer_types=["mamba", "attention"], mamba_heads=2,
            mamba_head_dim=8, mamba_state=4),
        "deepseek_v3": dict(
            arch="deepseek_v3", moe_ffn_dim=8, num_experts=4,
            num_experts_per_tok=2, num_shared_experts=1, first_dense_layers=1,
            qk_nope_head_dim=4, qk_rope_head_dim=4, v_head_dim=4,
            kv_lora_rank=12),
        "lfm2_moe": dict(
            arch="lfm2_moe", num_kv_heads=2, head_dim=8,
            layer_types=["conv", "full_attention"], moe_ffn_dim=8,
            num_experts=4, num_experts_per_tok=2, first_dense_layers=1),
    }[arch]
    symbol = tf.get_decode_symbol(vocab_size=32, num_layers=2, num_heads=4,
                                  model_dim=16, ffn_dim=32, max_len=64,
                                  page_size=8, **sizes)
    ops = [n["op"] for n in json.loads(symbol.tojson())["nodes"]]
    assert "KVSlotOneHot" not in symbol.tojson()
    assert "_contrib_KVPoolWrite" not in ops
    pools = [n for n in symbol.list_arguments() if n.startswith("kv_")]
    written = [n for n in json.loads(symbol.tojson())["nodes"]
               if n["op"] == "_contrib_KVPoolSlotWrite"]
    assert sum(int(n["attr"].get("num_pools", 1)) for n in written) \
        == len(pools) > 0
    assert len(written) == len({name.rsplit("_", 1)[1] for name in pools})
    with pytest.raises(AttributeError):
        mx.sym.KVSlotOneHot


# ------------------------------------------------ a step's inputs, on device
# lanes x slots a lane, page size: a small pool, olmoe-1b-7b.score's and
# transformer-base.generate's
GEOMETRIES = {"small": (7, 32, 8), "olmoe": (8, 2048, 16),
              "generate": (64, 1024, 16)}
# lane -> (pages held, position of the token it writes; None: it rides along)
LANES = {
    "one_slot": lambda P: (1, 0),               # its whole context is one slot
    "mid_page": lambda P: (3, 2 * P + 3),
    "page_boundary": lambda P: (2, 2 * P - 1),  # the context ends a page
    "new_page": lambda P: (3, 2 * P),           # the token opens a page
    "idle": lambda P: (2, None),
    "shared_a": lambda P: (2, P + 1),           # first frame: shared_b's too
    "shared_b": lambda P: (3, 2 * P + P // 2),
}


def _host_row(frames, pos, page, slots):
    """What the parent's ``PagedKVDecoder.step`` wrote into a lane's rows of
    ``slot_onehot`` and ``kv_mask``: ``_lane_slots`` plus the current slot."""
    onehot = np.zeros((slots,), np.float32)
    mask = np.full((slots,), np.float32(-1e9), np.float32)
    if pos is None:
        return -1, onehot, mask
    phys = frames[pos // page] * page + pos % page
    held = np.asarray(frames[:(pos + page - 1) // page], np.int64)
    seen = (held[:, None] * page + np.arange(page)[None, :]).reshape(-1)[:pos]
    onehot[phys] = 1.0
    mask[seen] = 0.0
    mask[phys] = 0.0
    return phys, onehot, mask


# the pools a step of ``_step_inputs`` writes into: layout -> heads, width
STEP_POOLS = {"head_major": (2, 8), "page_major": (2, 64)}
STEP_LAYOUTS = pytest.mark.parametrize("layout", list(STEP_POOLS))


@functools.lru_cache(maxsize=None)
def _step_inputs(geometry, layout="head_major"):
    """One step of a seeded pool: every lane of ``LANES`` at frames drawn
    without order from the whole pool (never frame 0, which the table's
    padding names), the rest of the lanes mid-context; then what the
    operators make of the step's few numbers a lane (the pool written by
    slot index, the masks) and what the host's arrays give (the one-hot
    blend of the same pool, the masks)."""
    lanes, per_lane, page = GEOMETRIES[geometry]
    slots, max_pages = lanes * per_lane, per_lane // page
    rs = np.random.RandomState(len(geometry))
    free = list(1 + rs.permutation(slots // page - 1))
    kinds = list(LANES) + ["mid_page"] * (lanes - len(LANES))
    table = np.zeros((lanes, max_pages), np.float32)
    pos_idx = np.zeros((lanes, 1), np.float32)
    write_slot = np.full((lanes, 1), -1, np.float32)
    want_oh, want_mask, shared = [], [], None
    for r, kind in enumerate(kinds):
        n_pages, pos = LANES[kind](page)
        frames = [int(free.pop()) for _ in range(n_pages)]
        if kind == "shared_a":
            shared = frames[0]
        elif kind == "shared_b":
            frames[0] = shared
        table[r, :n_pages] = frames
        phys, onehot, mask = _host_row(frames, pos, page, slots)
        write_slot[r, 0] = phys
        pos_idx[r, 0] = 0 if pos is None else pos
        want_oh.append(onehot)
        want_mask.append(mask)
    heads, width = STEP_POOLS[layout]
    pool = jnp.asarray(rs.randn(heads, slots, width), jnp.float32)
    rows = jnp.asarray(rs.randn(lanes, heads, width), jnp.float32)
    bound = _bound(pool, page)
    assert (bound.shape != pool.shape) == (layout == "page_major")
    got_pool, = _kv_pool_slot_write({}, bound, rows, jnp.asarray(write_slot))
    want_pool = _kv_pool_write({}, bound, rows,
                               jnp.asarray(np.stack(want_oh)))
    got_pool, want_pool = (_by_slot(a, heads) for a in (got_pool, want_pool))
    got_mask = _kv_page_mask({"page_size": page, "num_slots": slots},
                             jnp.asarray(table), jnp.asarray(pos_idx),
                             jnp.asarray(write_slot))
    return (kinds, np.asarray(got_pool), np.asarray(got_mask),
            np.asarray(want_pool), np.stack(want_mask), table, write_slot,
            np.asarray(pool), np.asarray(rows))


@STEP_LAYOUTS
@pytest.mark.parametrize("lane", list(LANES))
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_a_steps_onehot_and_mask_are_the_hosts_element_for_element(
        geometry, lane, layout):
    """A lane's half of the write (its slot of the pool written by index
    against the host's one-hot blend) and its row of the mask."""
    (kinds, got_pool, got_mask, want_pool, want_mask, _, write_slot, pool,
     rows) = _step_inputs(geometry, layout)
    lanes, per_lane, page = GEOMETRIES[geometry]
    assert got_pool.shape == want_pool.shape == STEP_POOLS[layout][:1] + (
        lanes * per_lane,) + STEP_POOLS[layout][1:]
    assert got_mask.shape == (lanes, lanes * per_lane)
    assert got_pool.dtype == got_mask.dtype == np.float32
    r = kinds.index(lane)
    n_pages, pos = LANES[lane](page)
    phys = int(write_slot[r, 0])
    assert (phys >= 0) == (pos is not None)
    if pos is not None:
        # the slot holds the lane's row itself, as the blend leaves it, and
        # the rest of its page what the pool held
        np.testing.assert_array_equal(_bits(got_pool[:, phys]),
                                      _bits(want_pool[:, phys]))
        np.testing.assert_array_equal(_bits(got_pool[:, phys]),
                                      _bits(rows[r]))
        start = phys // page * page
        rest = [s for s in range(start, start + page) if s != phys]
        np.testing.assert_array_equal(_bits(got_pool[:, rest]),
                                      _bits(pool[:, rest]))
    np.testing.assert_array_equal(_bits(got_mask[r]), _bits(want_mask[r]))
    seen = 0 if pos is None else pos + 1
    assert (got_mask[r] == 0).sum() == seen
    assert set(np.unique(got_mask[r])) <= {np.float32(0), np.float32(-1e9)}


@STEP_LAYOUTS
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_every_lane_of_a_step_and_the_frame_two_lanes_share(geometry, layout):
    (kinds, got_pool, got_mask, want_pool, want_mask, table, write_slot, pool,
     _) = _step_inputs(geometry, layout)
    np.testing.assert_array_equal(_bits(got_pool), _bits(want_pool))
    np.testing.assert_array_equal(_bits(got_mask), _bits(want_mask))
    page = GEOMETRIES[geometry][2]
    a, b = kinds.index("shared_a"), kinds.index("shared_b")
    first = int(table[a, 0]) * page
    assert table[a, 0] == table[b, 0]
    assert (got_mask[[a, b], first:first + page] == 0).all()
    # nobody else sees that frame, and nobody sees the frame the padding names
    others = [r for r in range(len(kinds)) if r not in (a, b)]
    assert (got_mask[others, first:first + page] == -1e9).all()
    assert (got_mask[:, :page] == -1e9).all()
    # the written slots are disjoint, one a writing lane, and nothing else
    # of the pool moved: the lane that rides along wrote back what it read
    moved = np.flatnonzero((_bits(got_pool) != _bits(pool)).any(axis=(0, 2)))
    wrote = write_slot[write_slot >= 0].astype(np.int64)
    assert len(set(wrote)) == len(wrote) == len(kinds) - 1
    np.testing.assert_array_equal(moved, np.sort(wrote))


def test_page_mask_refuses_a_page_that_does_not_tile_the_pool():
    args = (jnp.zeros((2, 3)), jnp.zeros((2, 1)), jnp.zeros((2, 1)))
    with pytest.raises(MXNetError, match="must divide"):
        _kv_page_mask({"page_size": 5, "num_slots": 24}, *args)
    assert _kv_page_mask({"page_size": 4, "num_slots": 24},
                         *args).shape == (2, 24)


def test_symbols_infer_a_steps_inputs_from_the_row_count():
    v = mx.sym.Variable
    wr = mx.sym.KVPoolSlotWrite(v("pool"), v("rows"), v("write_slot"),
                                name="wr")
    assert wr.list_arguments() == ["pool", "rows", "write_slot"]
    assert wr.infer_shape(pool=(H, S, DH), rows=(5, H, DH)) == (
        [(H, S, DH), (5, H, DH), (5, 1)], [(H, S, DH)], [])
    kv = mx.sym.KVPoolSlotWrite(v("k"), v("k_new"), v("v"), v("v_new"),
                                v("write_slot"), num_pools=2, name="kv")
    assert kv.list_outputs() == ["kv_output0", "kv_output1"]
    assert kv.infer_shape(k=(H, S, DH), k_new=(5, H, DH), v=(2, S, 8),
                          v_new=(5, 2, 8)) == (
        [(H, S, DH), (5, H, DH), (2, S, 8), (5, 2, 8), (5, 1)],
        [(H, S, DH), (2, S, 8)], [])
    msk = mx.sym.KVPageMask(v("page_table"), v("pos_idx"), v("write_slot"),
                            page_size=8, num_slots=S, name="msk")
    assert msk.list_arguments() == ["page_table", "pos_idx", "write_slot"]
    assert msk.infer_shape(page_table=(5, 4), pos_idx=(5, 1),
                           write_slot=(5, 1))[1] == [(5, S)]
    _, types, _ = msk.infer_type(page_table="float32", pos_idx="float32",
                                 write_slot="float32")
    assert [np.dtype(t) for t in types] == [np.dtype("float32")]


# ------------------------- a value of another width than the key (PR 32)
def _plain_attention(q, k, v, scale, mask):
    """softmax(q k^T * scale + mask) v in float32 ``jax.numpy``; q (..., T,
    dk), k (..., S, dk), v (..., S, dv), mask broadcast over the scores."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    scores = jnp.einsum("...td,...sd->...ts", q, k) * scale + mask
    return jnp.einsum("...ts,...sd->...td", jax.nn.softmax(scores, axis=-1),
                      v)


@pytest.mark.parametrize("dk,dv,hkv", [(24, 16, 4), (24, 16, 1), (16, 24, 2),
                                       (16, 16, 4)])
def test_dense_attention_takes_its_output_width_from_the_value(dk, dv, hkv):
    """``MultiHeadAttention`` with a value narrower (latent attention: 128
    under a 192-wide key) or wider than the key, equal and grouped heads,
    against plain ``jax.numpy``; float32 both sides, order of the sums only."""
    rs = np.random.RandomState(dk + dv + hkv)
    b, h, t = 2, 4, 7
    q = jnp.asarray(rs.randn(b, h, t, dk), jnp.float32)
    k = jnp.asarray(rs.randn(b, hkv, t, dk), jnp.float32)
    v = jnp.asarray(rs.randn(b, hkv, t, dv), jnp.float32)
    got = mx.nd.MultiHeadAttention(*(mx.nd.NDArray(a) for a in (q, k, v)),
                                   causal=True, scale=0.3).asnumpy()
    assert got.shape == (b, h, t, dv)
    causal = jnp.where(jnp.tril(jnp.ones((t, t), bool)), 0.0, -jnp.inf)
    rep = lambda a: jnp.repeat(a, h // hkv, axis=1)
    want = _plain_attention(q, rep(k), rep(v), 0.3, causal)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("heads", [1, 4])
def test_pool_attention_reads_a_value_that_is_a_prefix_of_the_key(
        heads, dtype, tol):
    """``KVPoolAttention(q, pool, pool, mask, value_dim=)``: ONE pool of one
    head whose row is the key whole and the value in its first columns (a
    latent cache's [c | k_r]), ``heads`` query heads on it, ``scale`` given;
    against plain ``jax.numpy`` on the sliced pool. bfloat16: the pool and
    the query are bfloat16-valued on both sides, what differs is the
    probabilities' one bfloat16 rounding in the second contraction (2^-9)."""
    rs = np.random.RandomState(heads)
    r, s, dk, dv = 5, 48, 24, 16
    q = jnp.asarray(rs.randn(r, heads, dk), dtype)
    pool = jnp.asarray(rs.randn(1, s, dk), dtype)
    mask = jnp.asarray(np.where(rs.rand(r, s) < 0.5, 0.0, -1e9), jnp.float32)
    mask = mask.at[:, 0].set(0.0)       # no row is masked whole
    got = _kv_pool_attention({"scale": 0.25, "value_dim": dv}, q, pool, pool,
                             mask)
    assert got.shape == (r, heads, dv) and got.dtype == q.dtype
    want = _plain_attention(q.transpose(1, 0, 2), pool, pool[..., :dv], 0.25,
                            mask[None]).transpose(1, 0, 2)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=tol, atol=tol)
    # without value_dim the pool's whole row is the value, as it always was
    whole = _kv_pool_attention({"scale": 0.25, "value_dim": 0}, q, pool,
                               pool, mask)
    assert whole.shape == (r, heads, dk)
    np.testing.assert_allclose(np.asarray(whole, np.float32)[..., :dv],
                               np.asarray(got, np.float32), rtol=tol,
                               atol=tol)


# ------------------------------------------------- a row reads its own pages
# case -> (query heads, pool heads, row width, value_dim)
OWN_PAGES = {
    "partial_last_page": (4, 4, 16, 0),
    "one_token": (4, 4, 16, 0),
    "rides_along": (4, 4, 16, 0),
    "shared_frame": (4, 4, 16, 0),
    "grouped_32_over_8": (32, 8, 16, 0),
    "value_dim_576_to_512": (4, 1, 576, 512),
    "unused_entries_zero": (4, 4, 16, 0),
    # rows of whole tiles: the pools are page-major
    "page_major_one_token": (4, 4, 32, 0),
    "page_major_rides_along": (4, 4, 32, 0),
    "page_major_grouped_32_over_8": (32, 8, 64, 0),
    "page_major_value_dim_128_to_96": (4, 1, 128, 96),
    "page_major_unused_entries_zero": (8, 2, 64, 0),
}


def _both_reads(monkeypatch, attrs, q, pool_k, pool_v, table, pos_idx,
                write_slot, page):
    """(own pages, whole pool): the operator with the rule held to each
    answer, on one step's operands."""
    slots = attention.pool_slots(pool_k.shape)
    operands = [jnp.asarray(a) for a in (table, pos_idx, write_slot)]
    mask = _kv_page_mask({"page_size": page, "num_slots": slots}, *operands)
    got = []
    for form in ("own_pages", "whole_pool"):
        monkeypatch.setattr(attention, "pool_read_form",
                            lambda *a, form=form: form)
        got.append(np.asarray(_kv_pool_attention(
            dict(attrs, page_size=page), q, pool_k, pool_v, mask, *operands),
            np.float32))
    return got


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-6), ("bfloat16", 1e-2)])
@pytest.mark.parametrize("case", list(OWN_PAGES))
def test_own_pages_read_is_the_whole_pool_read(monkeypatch, case, dtype, tol):
    """One step of the seeded ``small`` pool (7 lanes x 32 slots, pages of
    8; ``_step_inputs``: a context of one token, a last page held in part, a
    lane that rides along, two lanes on one frame, table entries past a
    lane's pages 0) read both ways. Each row agrees to ``tol`` of its norm:
    float32 differs by the order of a sum, a bfloat16 pool by the
    probabilities' one rounding in the second contraction."""
    hq, hkv, d, value_dim = OWN_PAGES[case]
    lanes, per_lane, page = GEOMETRIES["small"]
    slots = lanes * per_lane
    kinds, *_, table, write_slot, _, _ = _step_inputs("small")
    rs = np.random.RandomState(len(case))
    pool_k = _bound(jnp.asarray(rs.randn(hkv, slots, d), dtype), page)
    pool_v = pool_k if value_dim else _bound(
        jnp.asarray(rs.randn(hkv, slots, d), dtype), page)
    # 8 heads of 16 are a row of 128 too: that grouped case is page-major
    assert (pool_k.shape[0] != hkv) == ((hkv * d) % 128 == 0) \
        == (case.startswith("page_major") or case == "grouped_32_over_8")
    q = jnp.asarray(rs.randn(lanes, hq, d) * (4.0 / np.sqrt(d)), dtype)
    pos_idx = np.asarray([[0 if LANES[k](page)[1] is None
                           else LANES[k](page)[1]] for k in kinds], "f")
    attrs = {"scale": 0.25, "value_dim": value_dim}
    step = (q, pool_k, pool_v, table, pos_idx, write_slot, page)
    own, whole = _both_reads(monkeypatch, attrs, *step)
    assert own.shape == whole.shape == (lanes, hq, value_dim or d)
    assert np.isfinite(own).all()
    live = [r for r, k in enumerate(kinds) if k != "idle"]
    case = case.replace("page_major_", "")
    rows = {"partial_last_page": [kinds.index("mid_page")],
            "one_token": [kinds.index("one_slot")],
            "shared_frame": [kinds.index("shared_a"),
                             kinds.index("shared_b")]}.get(case, live)
    for r in rows:
        norm = np.linalg.norm(whole[r], axis=-1, keepdims=True)
        assert np.abs(own[r] - whole[r]).max() <= tol * norm.max(), r
    idle = kinds.index("idle")
    if case == "one_token":
        # the softmax of one slot is 1: the context IS that slot's value
        r = rows[0]
        want = _by_slot(np.asarray(pool_v, np.float32), hkv)[
            :, int(write_slot[r, 0])]
        np.testing.assert_allclose(own[r].reshape(hkv, -1, d),
                                   np.broadcast_to(want[:, None], (hkv, 1, d)),
                                   rtol=tol, atol=tol)
    if case == "rides_along":
        # whatever the riding lane's table and position say, it comes out
        # finite and no other row moves by a bit
        moved_table, moved_pos = table.copy(), pos_idx.copy()
        moved_table[idle] = rs.permutation(slots // page)[:table.shape[1]]
        moved_pos[idle] = per_lane - 1
        moved, _ = _both_reads(monkeypatch, attrs, q, pool_k, pool_v,
                               moved_table, moved_pos, write_slot, page)
        assert np.isfinite(moved[idle]).all()
        np.testing.assert_array_equal(_bits(moved[live]), _bits(own[live]))
    if case == "unused_entries_zero":
        # entries past a lane's pages are gathered and weigh exactly 0:
        # naming other frames there changes no bit
        n_pages = {r: LANES[k](page)[0] for r, k in enumerate(kinds)}
        other = table.copy()
        for r in live:
            assert (table[r, n_pages[r]:] == 0).all()
            other[r, n_pages[r]:] = 1 + rs.randint(
                slots // page - 1, size=table.shape[1] - n_pages[r])
        filled, _ = _both_reads(monkeypatch, attrs, q, pool_k, pool_v, other,
                                pos_idx, write_slot, page)
        np.testing.assert_array_equal(_bits(filled[live]), _bits(own[live]))


# cell -> (lanes, query heads, pool heads, key width, value width, slots a
# lane, type, pools, the form on the CPU, the form on the chip) at the
# benchmark's serving sizes, pages of 16
POOL_READS = {
    "kanana-2-30b-a3b": (32, 32, 1, 576, 576, 2048, "bfloat16", 1,
                         "own_pages", "own_pages"),
    "granite-4.0-h-micro": (32, 32, 8, 64, 64, 2048, "bfloat16", 2,
                            "own_pages", "kernel"),
    "transformer-base": (64, 8, 8, 64, 64, 1024, "float32", 2,
                         "own_pages", "kernel"),
    "olmoe-1b-7b": (8, 16, 16, 128, 128, 2048, "bfloat16", 2,
                    "own_pages", "kernel"),
    "lfm2-24b-a2b": (64, 32, 8, 64, 64, 2048, "bfloat16", 2,
                     "own_pages", "kernel"),
    "mimo-v2-flash": (32, 64, 4, 192, 128, 8192, "bfloat16", 2,
                      "own_pages", "kernel"),
}


@pytest.mark.parametrize("cell", list(POOL_READS))
def test_the_rule_names_the_form_of_each_serving_configuration(monkeypatch,
                                                               cell):
    """The rule's answer at the shapes of the six serving configurations as
    a decoder binds them (``pool_shape``). One 576-wide latent row is no
    whole tile of lanes: its pool stays head-major, small beside its scores,
    and XLA gathers a lane's pages, on the chip as on the CPU. Every other
    configuration's row is whole tiles (512, 768, 2,048 wide): page-major,
    the kernel's on the chip, XLA's gather where Mosaic does not run. And a
    read that was handed no table scores the whole pool."""
    (lanes, hq, hkv, dk, dv, per_lane, dtype, pools, on_cpu,
     on_chip) = POOL_READS[cell]
    spec = jax.ShapeDtypeStruct
    slots = lanes * per_lane
    query = spec((lanes, hq, dk), dtype)
    pool_k = spec(pool_shape(hkv, dk, slots, 16), dtype)
    pool_v = spec(pool_shape(hkv, dv, slots, 16), dtype) if pools == 2 \
        else None
    assert (pool_k.shape[0] == hkv) == (cell == "kanana-2-30b-a3b")
    table = spec((lanes, per_lane // 16), "float32")
    assert pool_read_form(query, pool_k, pool_v, table, 16) == on_cpu
    assert pool_read_form(query, pool_k, pool_v, None, 0) == "whole_pool"
    monkeypatch.setattr(attention, "_backend", lambda: "tpu")
    assert pool_read_form(query, pool_k, pool_v, table, 16) == on_chip
    assert pool_read_form(query, pool_k, pool_v, None, 0) == "whole_pool"


@pytest.mark.parametrize("cell", list(POOL_READS))
def test_the_output_rule_is_what_evaluating_the_read_gives(cell):
    """Shape inference asks ``KVPoolAttention`` for its output's shape and
    type (``OpDef.infer``) and traces no read, which on the chip would import
    Pallas: at every serving configuration's operands, with and without a
    step's table, the rule says what abstractly evaluating the operator
    says; kanana's value is the first 512 columns of its one pool."""
    from mxnet_tpu.ops.registry import get_op

    (lanes, hq, hkv, dk, dv, per_lane, dtype, pools, _, _) = POOL_READS[cell]
    spec = jax.ShapeDtypeStruct
    slots = lanes * per_lane
    query = spec((lanes, hq, dk), dtype)
    pool_k = spec(pool_shape(hkv, dk, slots, 16), dtype)
    pool_v = spec(pool_shape(hkv, dv, slots, 16), dtype)
    mask = spec((lanes, slots), "float32")
    step = [spec((lanes, per_lane // 16), "float32"),
            spec((lanes, 1), "float32"), spec((lanes, 1), "float32")]
    op = get_op("KVPoolAttention")
    for page, value_dim in ((0, 0), (16, 0)) + (
            ((16, 512),) if pools == 1 else ()):
        attrs = {"scale": -1.0, "value_dim": value_dim, "page_size": page}
        operands = [query, pool_k, pool_v, mask] + (step if page else [])
        (want,) = jax.eval_shape(lambda *a: op.fn(attrs, *a), *operands),
        assert op.infer(attrs, operands) == [(want.shape, want.dtype)]
        assert want.shape == (lanes, hq, value_dim or dv)


@pytest.mark.parametrize("hkv,d,own", [(8, 24, False), (1, 576, True)])
def test_a_head_major_read_takes_the_form_that_moves_fewer_bytes(hkv, d, own):
    """Head-major pools (a row that is no whole tile: 8 heads of 24; one
    latent row of 576) at 32 lanes x 2,048 slots, 32 query heads: narrow
    heads pad to the chip's 128 lanes in a gathered copy and the gather
    moves more than the pool, so the whole pool is scored; one wide row read
    by every head is small beside its scores and is gathered."""
    spec = jax.ShapeDtypeStruct
    lanes, slots = 32, 32 * 2048
    query = spec((lanes, 32, d), "bfloat16")
    pool = spec(pool_shape(hkv, d, slots, 16), "bfloat16")
    assert pool.shape == (hkv, slots, d)
    table = spec((lanes, 128), "float32")
    pool_v = pool if hkv > 1 else None
    whole, gathered = pool_read_bytes(query, pool, pool_v, table, 16)
    assert (gathered < whole) == own
    assert pool_read_form(query, pool, pool_v, table, 16) \
        == ("own_pages" if own else "whole_pool")


def test_the_byte_count_takes_a_key_and_a_value_pool_each_at_its_own_width():
    """``pool_read_bytes`` of head-major pools of different width: 64 query
    heads over a key pool (4, 262,144, 192) and a value pool (4, 262,144,
    128), 32 lanes of 8,192 slots (``mimo-v2-flash.generate``'s full layers
    as PR 38 bound them; they are page-major now and the rule no longer asks
    this count of them). Each pool's bytes and each copy's padding (192 to
    256 lanes, 128 to 128) are its own: the whole-pool read is the pools'
    0.67 GB and 6.4 GB of scores, the lanes' own pages 2 x 0.67 + 4 x 0.81
    GB of copies and 0.2 GB of scores. Counting the value at the key's width
    would add 4 x 0.27 GB."""
    spec = jax.ShapeDtypeStruct
    lanes, slots = 32, 32 * 8192
    query = spec((lanes, 64, 192), "bfloat16")
    pool_k = spec((4, slots, 192), "bfloat16")
    pool_v = spec((4, slots, 128), "bfloat16")
    table = spec((lanes, 8192 // 16), "float32")
    pools = 4 * slots * (192 + 128) * 2
    copies = lanes * 4 * 8192 * (256 + 128) * 2
    whole, own = pool_read_bytes(query, pool_k, pool_v, table, 16)
    assert pools == 671_088_640 and copies == 805_306_368
    assert whole == pools + 12 * lanes * 64 * slots == 7_113_539_584
    assert own == 2 * pools + 4 * copies + 12 * lanes * 64 * 8192 \
        == 4_764_729_344
    alike = pool_read_bytes(query, pool_k, pool_k, table, 16)
    assert alike[1] - own == 4 * lanes * 4 * 8192 * 128 * 2 \
        + 2 * 4 * slots * 64 * 2
    # one pool that is key and value both is counted once
    assert pool_read_bytes(query, pool_k, None, table, 16)[0] \
        == 4 * slots * 192 * 2 + 12 * lanes * 64 * slots


# ------------------------------------------- the two forms through the decoder
def _latent_decoder(monkeypatch, own, latent):
    """A ``deepseek_v3`` decoder small enough for the CPU whose read the rule
    sends to own pages (64 heads on one row of ``latent`` + 8, 8 lanes), or,
    with the rule held to the whole pool, the same decoder over that."""
    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.serving import PagedKVDecoder

    if not own:
        monkeypatch.setattr(attention, "pool_read_form",
                            lambda *a: "whole_pool")
    cfg = dict(vocab_size=64, num_layers=3, num_heads=64, model_dim=32,
               ffn_dim=64, moe_ffn_dim=16, num_experts=8,
               num_experts_per_tok=2, num_shared_experts=1,
               first_dense_layers=1, qk_nope_head_dim=4, qk_rope_head_dim=8,
               v_head_dim=4, kv_lora_rank=latent)
    rs = np.random.RandomState(5)
    params = {n: mx.nd.array(rs.randn(*shape).astype("f") * 0.2)
              for n, shape in tf.param_shapes(arch="deepseek_v3",
                                              **cfg).items()}
    return PagedKVDecoder(params, arch="deepseek_v3", max_len=64, page_size=8,
                          lanes=8, prefill_len=16, **cfg)


@pytest.mark.parametrize("latent,paged", [(112, False), (120, True)],
                         ids=["head_major_120", "page_major_128"])
def test_a_decoder_steps_alike_in_both_forms_and_says_which(monkeypatch,
                                                            latent, paged):
    """Admissions of unequal lengths, a lane that joins late and one that
    retires, stepped side by side: the logits of the decoder whose lanes
    read their own pages are the whole-pool decoder's to float32's sum
    order, and its telemetry says what a step read and how it wrote. A latent
    row of 120 is kept head-major, one of 128 page-major (one pool that is
    key and value both: XLA's gather in either)."""
    from mxnet_tpu import telemetry as tm

    saved = tm.current_override()
    tm.set_mode("counters")
    try:
        logits = []
        for own in (True, False):
            tm.reset()
            dec = _latent_decoder(monkeypatch, own, latent).warmup()
            pool = dec._dec_exe.arg_dict["kv_c_0"].shape
            assert pool == ((64, 8, 128) if paged else (1, 512, 120))
            snap = tm.snapshot()
            assert snap["serving.pool_read.own_pages_layers"] == 3 * own
            assert snap["serving.pool_read.whole_pool_layers"] == 3 * (not own)
            assert snap["serving.pool_read.kernel_layers"] == 0
            # the write: a loop a layer into a head-major latent pool, one
            # scatter into a page-major one; 3 layers x 8 lanes
            assert snap["serving.pool_write.loop_nodes"] == 3 * (not paged)
            assert snap["serving.pool_write.scatter_nodes"] == 3 * paged
            assert snap["serving.pool_write.rows_a_step"] == 24
            seqs = [dec.admit(np.arange(n) % 61)[0] for n in (3, 9, 16)]
            rows = []
            for t in range(12):
                if t == 4:
                    seqs.append(dec.admit(np.arange(5) % 59)[0])
                if t == 8:
                    dec.retire(seqs.pop(0))
                out = dec.step({s: (7 * t + s) % 64 for s in seqs})
                rows += [out[s] for s in seqs]
            logits.append(np.stack(rows))
            moved = tm.snapshot()
            # own pages: 8 lanes x 8 pages of 8; whole: 8 lanes x 512 slots
            assert moved["serving.step_gathered_slots"] \
                == moved["serving.paged_steps"] * (512 if own else 8 * 512)
            assert "serving.step_kernel_slots" not in moved
        np.testing.assert_allclose(logits[0], logits[1], rtol=2e-5, atol=2e-5)
    finally:
        tm.set_mode(saved)
        tm.reset()
