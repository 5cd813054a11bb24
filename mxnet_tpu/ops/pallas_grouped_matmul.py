"""The experts' grouped matmul as a Pallas TPU kernel.

``MoEFeedForward``'s second form (ops/moe.py names the first: three calls of
``jax.lax.ragged_dot``, XLA's own). Rows arrive sorted by expert with the
group sizes as data; an expert layer is TWO calls of the one kernel here:
``silu(rows . gate_e) * (rows . up_e)`` written once in the storage type (the
two float32 products never reach HBM), then ``. down_e`` in float32.

The row tiles are cut at every group's first row: a VISIT is one (row tile,
expert) pair that shares a row, at most ``row tiles + experts - 1`` of them,
a static bound. The group offsets and the visits are made in the graph
(``visits``) and scalar-prefetched. The grid runs over output-column tiles
and, inside, the visits in order: a visit multiplies its tile of rows by the
column tile of ITS expert's matrix, the whole contraction in one block,
float32 sums, and keeps the rows of its own group (the others are a
neighbouring visit's). Consecutive visits of one expert name the same block
of its matrix, which the pipeline then leaves in VMEM; an expert that
received no row is in no visit and its weights are never fetched, so a
decode step reads the experts TOUCHED. A tile past the last group (rows
assigned to an expert the layer does not hold) gets one visit that writes
zeros and multiplies nothing.

The shape of such a kernel is known:
``jax.experimental.pallas.ops.tpu.megablox.gmm`` of the installed JAX has the
same grid and the same prefetched group metadata. It was read and LEARNED
FROM, not called and not copied: this kernel takes the whole contraction a
block (no k axis, no accumulator scratch), fuses two matrices and the
activation in one call, makes its visits by a sort of the cut points, zeroes
what no group owns, and takes its tiles from ``tiles`` below.

``jax.experimental.pallas`` costs a process 1.5 to 2 s to import, so it is
imported where the kernel is TRACED (``grouped_matmul``) and nowhere else:
``tiles``, ``supported`` and ``moe_form`` are plain Python, and a process
that loads its programs from the program store never imports it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import attention as _attention   # ``_backend``: where the trace runs
from .attention import _LANES           # a width is whole tiles of the lanes

__all__ = ["grouped_matmul", "expert_ffn", "visits", "tiles", "supported",
           "moe_form"]

# the most VMEM the double-buffered blocks of a call's matrices may take (the
# chip has 128 MiB and the compiler's own default limit is 16; the rows, the
# output and the float32 sums of a 128-row tile take up to 14 more)
_MATRIX_BYTES = 36 << 20


def supported(rows, gate_weight, down_weight):
    """Whether Mosaic takes an expert layer of these operands (shapes and
    types alone): bfloat16 rows and stacks (float32 experts stay XLA's:
    no cell runs them and a block of theirs is twice the VMEM), both widths
    whole tiles of 128 lanes, the rows a whole number of bfloat16's sublane
    tiles of 16."""
    if not (rows.dtype == gate_weight.dtype == down_weight.dtype
            == jnp.bfloat16):
        return False
    d, f = gate_weight.shape[1], gate_weight.shape[2]
    return d % _LANES == 0 and f % _LANES == 0 \
        and rows.shape[0] % 16 == 0


def _column_tile(k, n, mats, itemsize):
    """The widest column tile of an (k, n) matrix, a divisor of n in whole
    lane tiles (n itself where n is no multiple of 128), whose ``mats``
    double-buffered blocks fit ``_MATRIX_BYTES``."""
    if n % _LANES:
        return n
    units = n // _LANES
    for parts in range(1, units + 1):
        if units % parts == 0 \
                and 2 * mats * k * (n // parts) * itemsize <= _MATRIX_BYTES:
            return n // parts
    return _LANES


def tiles(rows, experts, d, f, dtype):
    """``(row tile, column tile of gate and up, column tile of down)`` of an
    expert layer whose held ``experts`` of width ``f`` under a model width
    ``d`` get ``rows`` assignment rows between them: THE rule, from the rows
    an expert gets on average and nothing else (no option, no model's name),
    written from paired chip runs (``PERF.md`` section 6, PR 41).

    The row tile is about two groups long, the power of two at or above
    twice the rows an expert gets, between 32 and 128. The matrix unit loads
    a 128 x 128 tile of a matrix in about the time 128 rows pass through it,
    so a visit costs the same for 16 rows as for 128 and a shorter tile only
    makes more of them; past 128 a tile that straddles two groups multiplies
    rows it then discards (512 was a third slower at 256 rows an expert).
    Few rows an expert (a decode step: 1.5 to 16) is a weight-streaming
    problem whatever the tile, 32 there. The column tile is the widest that
    fits the budget: a matrix arrives in few large pieces and the rows are
    read once a column tile (half as wide was 4 to 12% slower in every
    regime)."""
    itemsize = jnp.dtype(dtype).itemsize
    per = rows / experts
    tm = 32
    while tm < min(2 * per, 128):
        tm *= 2
    return tm, _column_tile(d, f, 2, itemsize), \
        _column_tile(f, d, 1, itemsize)


def moe_form(rows, gate_weight, down_weight):
    """THE rule that names the form of ``MoEFeedForward``'s expert products,
    ``"kernel"`` or ``"ragged_dot"``, from the operands' shapes and types
    and the backend; no caller, option or environment variable does. Each
    operand carries ``.shape`` and ``.dtype``: ``rows`` (N * k, D) sorted by
    expert, ``gate_weight`` (held experts, D, F), ``down_weight`` (held
    experts, F, D).

    ``"ragged_dot"``: three calls of XLA's grouped matmul. The CPU (a test
    that wants the kernel holds this rule and runs it interpreted), and on
    the chip whatever ``supported`` refuses.

    ``"kernel"``: ``expert_ffn``, two calls of the kernel here, on the chip:
    in every regime a cell has, from 1.5 rows an expert (a step) to 256 (an
    admission), paired chip runs read it 1.3 to 2.4 times as fast as XLA's
    form (``PERF.md`` section 6, PR 41), so the rows an expert gets choose
    the tiles and not the form."""
    if _attention._backend() == "tpu" \
            and supported(rows, gate_weight, down_weight):
        return "kernel"
    return "ragged_dot"


def visits(sizes, rows, tm):
    """``(offsets (E + 1,), tile (V,), expert (V,), count (1,))``, int32: the
    (row tile, expert) pairs that share a row, in order, of ``rows`` rows
    sorted by expert with ``sizes`` (E,) rows a group, under row tiles of
    ``tm``. ``V`` = row tiles + E - 1 is the static bound; entries past
    ``count`` repeat the last visit's blocks (nothing is fetched for them).

    A visit starts at a CUT: a tile's first row, or the first row of a
    non-empty group inside a tile. The cuts sorted are the visits; a cut's
    tile is its row over ``tm`` and its expert the group that holds the row.
    Rows past the last group are held by no group: their cuts take the last
    non-empty expert (whose block is in VMEM already) and own no row."""
    experts = sizes.shape[0]
    n_tiles = -(-rows // tm)
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    inside = (sizes > 0) & (starts % tm != 0)
    past = n_tiles * tm
    cuts = jnp.sort(jnp.concatenate([
        jnp.arange(n_tiles, dtype=jnp.int32) * tm,
        jnp.where(inside, starts, past)]))[:n_tiles + experts - 1]
    count = n_tiles + jnp.sum(inside.astype(jnp.int32))
    tile = jnp.minimum(cuts // tm, n_tiles - 1)
    last = jnp.max(jnp.where(sizes > 0, jnp.arange(experts, dtype=jnp.int32),
                             0))
    expert = jnp.minimum(
        jnp.sum((ends[None, :] <= cuts[:, None]).astype(jnp.int32), axis=1),
        last)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, tile, expert, count.reshape(1)


def _kernel(off_ref, tile_ref, expert_ref, count_ref, x_ref, *refs, tm):
    from jax.experimental import pallas as pl

    *w_refs, o_ref = refs
    v = pl.program_id(1)
    tile, expert = tile_ref[v], expert_ref[v]
    lo, hi = off_ref[expert], off_ref[expert + 1]
    row0 = tile * tm

    # a tile's first visit: what no group owns stays zero
    @pl.when((v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != tile))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when((v < count_ref[0]) & (lo < row0 + tm) & (hi > row0))
    def _():
        x = x_ref[...]
        dot = lambda w_ref: jnp.dot(x, w_ref[...],
                                    preferred_element_type=jnp.float32)
        if len(w_refs) == 2:    # gate and up: the activation on the sums
            out = jax.nn.silu(dot(w_refs[0])) * dot(w_refs[1])
        else:
            out = dot(w_refs[0])
        row = row0 + jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
        o_ref[...] = jnp.where((row >= lo) & (row < hi),
                               out.astype(o_ref.dtype), o_ref[...])


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def grouped_matmul(rows, weights, meta, *, tm, tn, interpret=False):
    """Row r of ``rows`` (M, K), in group e by ``meta`` = ``visits(sizes, M,
    tm)``, times ``weights[i][e]`` (E, K, N). One stack: the products, (M, N)
    float32. Two stacks (gate, up): ``silu(rows . gate_e) * (rows . up_e)``,
    the activation on the float32 sums, cast once to the rows' type. A row
    past the last group comes out zero. ``tn`` divides N. ``interpret``: run
    the kernel interpreted (the CPU)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = rows.shape
    n = weights[0].shape[2]
    off, tile, expert, count = meta
    out_dtype = rows.dtype if len(weights) == 2 else jnp.float32
    itemsize = jnp.dtype(rows.dtype).itemsize
    blocks = 2 * (tm * k * itemsize
                  + tm * tn * jnp.dtype(out_dtype).itemsize) \
        + 2 * len(weights) * k * tn * itemsize + 4 * tm * tn * 4
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, tile.shape[0]),
            in_specs=[pl.BlockSpec((tm, k),
                                   lambda j, v, off, t, e, c: (t[v], 0))]
            + [pl.BlockSpec((None, k, tn),
                            lambda j, v, off, t, e, c: (e[v], 0, j))
               for _ in weights],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, off, t, e, c: (t[v], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(min(blocks + (8 << 20), 100 << 20))),
        # what XLA's own grouped matmul is counted as: every row once, and
        # (the most a call can fetch) every expert's matrix once
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n * len(weights),
            transcendentals=m * n * (len(weights) - 1),
            bytes_accessed=(m * k + sum(w.size for w in weights)) * itemsize
            + m * n * jnp.dtype(out_dtype).itemsize),
        interpret=interpret,
        name="grouped_matmul_gated" if len(weights) == 2
        else "grouped_matmul",
    )(off, tile, expert, count, rows, *weights)


def expert_ffn(rows, gate_weight, up_weight, down_weight, sizes,
               routed_experts=None, interpret=False):
    """``(silu(rows . gate_e) * (rows . up_e)) . down_e`` for the rows of
    every group e: ``rows`` (M, D) sorted by expert, ``sizes`` (E,) rows a
    group, the stacks (E, D, F), (E, D, F), (E, F, D). Returns (M, D)
    float32, zero for a row past the last group. The tiles are ``tiles``'
    at the rows that reach a held expert under even routing: all M, or
    E of ``routed_experts``' share of them."""
    m, d = rows.shape
    experts, _, f = gate_weight.shape
    tm, tn_up, tn_down = tiles(
        m * experts // (routed_experts or experts), experts, d, f,
        rows.dtype)
    tm = min(tm, m)     # fewer rows than a tile: one tile of them all
    meta = visits(sizes, m, tm)
    act = grouped_matmul(rows, (gate_weight, up_weight), meta, tm=tm,
                         tn=tn_up, interpret=interpret)
    return grouped_matmul(act, (down_weight,), meta, tm=tm, tn=tn_down,
                          interpret=interpret)
