"""A decode step's read of a paged KV pool as a Pallas TPU kernel.

``KVPoolAttention``'s third form (ops/attention.py names the others: the
whole pool under a mask, a row's own pages gathered by XLA). One query token a
row attends the pages its table names, up to its own context and no further,
out of pools stored a page at a time: ``(frames, page_size, Hkv * d)``, a
token's row all its key/value heads side by side, so a page is ONE contiguous
piece and the pool's last dimension a multiple of the chip's 128 lanes (the
chip keeps such a pool row-major and a Pallas operand needs no re-layout).

The grid walks the rows. The page table and the contexts are scalar-prefetched;
per row a loop over ``ceil(context / block)`` blocks of ``pages_per_block``
pages, each LIVE page one ``make_async_copy`` a pool into a double-buffered
VMEM scratch (a row's last block fetches the pages its context reaches and no
further: what is fetched is the context rounded up to a page, whatever the
block), the next block in flight while this one is scored (the next ROW's
first block too: the work items of all rows are one pipeline). A block is
sized by the BYTES a loop turn keeps in flight, not by the table's length: a
turn has a cost of its own (0.47 us at one page of ouro's rows, 1.15 us at
eight: PERF.md section 6, PR 57), so under small blocks the core paces the
read and not the memory. Scores, online softmax and accumulator are float32;
the matrix unit takes the pool's dtype.

Grouped and unequal head widths without a lane slice: the query comes in
BLOCK-DIAGONAL, ``(H, Hkv * dk)`` with head (k, g)'s numbers in columns
``k * dk ..`` and zeros elsewhere, so ``scores = Q_bd . block^T`` and ``acc +=
p . V_block`` are two plain matmuls over whole rows of the pool (the zeros add
exactly 0.0), and the caller keeps each head's own ``dv`` columns of the
``(H, Hkv * dv)`` result.

The XLA forms stay the reference (tests/test_paged_read_kernel.py runs this
kernel interpreted against the own-pages form on the same pools).

``jax.experimental.pallas`` costs a process 1.5 to 2 s to import, so it is
imported where the kernel is TRACED (``paged_read``) and nowhere else: the
rules over shapes here (``supported``, ``block_slots``) are plain Python, and a
process that loads its decode program from the program store
(serving/cache.py) never imports it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .attention import _LANES   # a pool row is whole tiles of the chip's lanes

__all__ = ["paged_read", "pages_per_block", "block_slots", "supported"]

_NEG = -1e30
# the most VMEM the two double-buffered blocks may take
_SCRATCH_BYTES = 4 << 20
# the most slots a block holds
_BLOCK_SLOTS = 256
# the key and value bytes a block brings, where the slots allow: what a loop
# turn keeps in flight (PERF.md section 6, PR 57, has the sweep that named it)
_BLOCK_BYTES = 1 << 20


def supported(query, pool_k, pool_v):
    """Whether Mosaic takes these operands: pools (frames, page, Hkv * d) of
    the query's type whose rows are whole tiles of 128 lanes and whose pages
    are whole sublane tiles (8 rows of float32, 16 of bfloat16), the key's
    row Hkv query-widths wide. Shapes and types alone."""
    if pool_k.ndim != 3 or pool_v.ndim != 3 \
            or pool_k.shape[:2] != pool_v.shape[:2]:
        return False
    if not (query.dtype == pool_k.dtype == pool_v.dtype) \
            or jnp.dtype(query.dtype).itemsize not in (2, 4):
        return False
    page, wk, wv = pool_k.shape[1], pool_k.shape[2], pool_v.shape[2]
    heads, dk = query.shape[1], query.shape[2]
    if wk % _LANES or wv % _LANES or wk % dk:
        return False
    hkv = wk // dk
    if heads % hkv or wv % hkv:
        return False
    return page % (32 // jnp.dtype(query.dtype).itemsize) == 0


def pages_per_block(max_pages, page, row_bytes):
    """Pages a block of the kernel fetches, from the shapes alone: as many as
    bring a block's ``row_bytes`` a slot (key and value) to ``_BLOCK_BYTES``,
    at most ``_BLOCK_SLOTS`` slots, what two buffers fit in
    ``_SCRATCH_BYTES`` and the table's ``max_pages``, at least a page. A
    row's last block fetches its live pages only, so a large block costs a
    short context nothing and need not divide the table."""
    most = min(_BLOCK_SLOTS, _SCRATCH_BYTES // (2 * row_bytes)) // page
    want = -(-_BLOCK_BYTES // (page * row_bytes))
    return max(min(want, most, max_pages), 1)


def block_slots(pool_k, pool_v, max_pages):
    """The slots of the kernel's block over these pools (shape and dtype
    alone) under a table of ``max_pages``: what ``paged_read`` fetches at a
    time, and what a row's context is rounded up to."""
    page = pool_k.shape[1]
    return page * pages_per_block(
        max_pages, page, (pool_k.shape[2] + pool_v.shape[2])
        * jnp.dtype(pool_k.dtype).itemsize)


def _kernel(table_ref, ctx_ref, start_ref, next_ref, q_ref, k_hbm, v_hbm,
            o_ref, k_buf, v_buf, sem, *, scale, page, pages):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    row, rows = pl.program_id(0), pl.num_programs(0)
    block = page * pages
    context = ctx_ref[row]
    blocks = (context + block - 1) // block

    def live(lane, blk):
        """The pages of ``lane``'s block ``blk`` that its context reaches."""
        return jnp.minimum(
            pages, (ctx_ref[lane] - blk * block + page - 1) // page)

    def copies(slot, i, frame=0):
        """The two copies of a block's page ``i`` into buffer ``slot``; a
        wait needs the shapes and the semaphore alone, so it names no
        frame."""
        at = pl.ds(i * page if isinstance(i, int)
                   else pl.multiple_of(i * page, page), page)
        return (pltpu.make_async_copy(
                    k_hbm.at[frame], k_buf.at[slot, at], sem.at[0, slot]),
                pltpu.make_async_copy(
                    v_hbm.at[frame], v_buf.at[slot, at], sem.at[1, slot]))

    def each_live_page(n, do):
        """``do(i)`` for the ``n`` live pages of a block: a whole block's
        unrolled (the scheduler interleaves the copies' address arithmetic
        and bounds checks, which a loop runs a page after a page), a last
        block's in a loop."""
        @pl.when(n == pages)
        def _():
            for i in range(pages):
                do(i)

        @pl.when(n < pages)
        def _():
            jax.lax.fori_loop(0, n, lambda i, _: do(i), None)

    def fetch(lane, blk, slot):
        def start(i):
            for copy in copies(slot, i, table_ref[lane, blk * pages + i]):
                copy.start()

        each_live_page(live(lane, blk), start)

    def arrive(blk, slot):
        """Every copy ``fetch`` started for this row's block ``blk``, copy
        for copy: a semaphore counts what was started and no more."""
        def wait(i):
            for copy in copies(slot, i):
                copy.wait()

        each_live_page(live(row, blk), wait)

    # the pages a last block leaves unfetched are scored with p = 0.0, and
    # 0 x what a buffer held before its first use must be 0: zeros, once
    # (later, a dead row holds an earlier block's, pool data and finite)
    @pl.when(row == 0)
    def _():
        v_buf[...] = jnp.zeros_like(v_buf)

    first = start_ref[row] % 2      # the buffer this row's first block is in

    # the first block of all: nobody has asked for it yet
    @pl.when((blocks > 0) & (start_ref[row] == 0))
    def _():
        fetch(row, 0, first)

    q = q_ref[0]
    heads = q.shape[0]

    def body(i, carry):
        m, l, acc = carry
        slot = (first + i) % 2

        @pl.when(i + 1 < blocks)
        def _():
            fetch(row, i + 1, 1 - slot)

        # the next row that has any context, while this row's last is scored
        @pl.when((i + 1 == blocks) & (next_ref[row] < rows))
        def _():
            fetch(next_ref[row], 0, 1 - slot)

        arrive(i, slot)
        s = jax.lax.dot_general(
            q, k_buf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        at = i * block + jax.lax.broadcasted_iota(
            jnp.int32, (heads, block), 1)
        s = jnp.where(at < context, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(v_buf.dtype), v_buf[slot],
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    _, l, acc = jax.lax.fori_loop(0, blocks, body, (
        jnp.full((heads, 1), _NEG, jnp.float32),
        jnp.zeros((heads, 1), jnp.float32),
        jnp.zeros((heads, v_buf.shape[2]), jnp.float32)))
    # a row with no context ran no block: zeros, finite
    o_ref[0] = acc / jnp.where(l > 0, l, 1.0)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_read(query, pool_k, pool_v, page_table, context, *, scale,
               interpret=False):
    """``softmax(q . K^T * scale) . V`` of each row over its own context.

    ``query`` (R, H, dk); ``pool_k`` (frames, page, Hkv * dk) and ``pool_v``
    (frames, page, Hkv * dv) in the query's type (``supported``);
    ``page_table`` (R, max_pages) int32, the frames of a row's pages in
    order, every entry a frame of the pool (zeros past a row's pages);
    ``context`` (R,) int32, the slots a row attends, the first ``context``
    of its pages. Query head h reads key/value head ``h // (H / Hkv)``.
    Returns (R, H, dv) float32; a row whose context is 0 comes out zeros.
    ``interpret``: run the kernel interpreted (the CPU)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, heads, dk = query.shape
    _, page, wk = pool_k.shape
    wv = pool_v.shape[2]
    hkv = wk // dk
    dv, group = wv // hkv, heads // hkv
    block = block_slots(pool_k, pool_v, page_table.shape[1])
    pages = block // page
    # head (k, g)'s query in columns k * dk ..: zeros everywhere else
    eye = jnp.eye(hkv, dtype=query.dtype)
    q_bd = (query.reshape(rows, hkv, group, 1, dk)
            * eye[None, :, None, :, None]).reshape(rows, heads, wk)
    context = context.astype(jnp.int32)
    blocks = (context + block - 1) // block
    # the rows' blocks are one pipeline: a row's first buffer is the parity
    # of the blocks before it, and its last block prefetches the next row
    # that has any
    start = jnp.cumsum(blocks) - blocks
    lane = jnp.arange(rows, dtype=jnp.int32)
    later = jnp.where((blocks > 0)[None, :] & (lane[None, :] > lane[:, None]),
                      lane[None, :], rows)
    after = jnp.min(later, axis=1).astype(jnp.int32)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, page=page, pages=pages),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(rows,),
            in_specs=[
                pl.BlockSpec((1, heads, wk), lambda r, *_: (r, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, heads, wv), lambda r, *_: (r, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, block, wk), pool_k.dtype),
                pltpu.VMEM((2, block, wv), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=jax.ShapeDtypeStruct((rows, heads, wv), jnp.float32),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_read",
    )(page_table.astype(jnp.int32), context, start.astype(jnp.int32), after,
      q_bd, pool_k, pool_v)
    # head (k, g) keeps its own key/value head's columns
    own = jnp.diagonal(out.reshape(rows, hkv, group, hkv, dv), axis1=1,
                       axis2=3)
    return jnp.moveaxis(own, -1, 1).reshape(rows, heads, dv)
