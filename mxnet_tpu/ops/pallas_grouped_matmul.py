"""The experts' grouped matmul as a Pallas TPU kernel.

``MoEFeedForward``'s second form (ops/moe.py names the first: three calls of
``jax.lax.ragged_dot``, XLA's own). Rows arrive sorted by expert with the
group sizes as data; an expert layer is TWO calls of the one kernel here:
``silu(rows . gate_e) * (rows . up_e)`` written once in the storage type (the
two float32 products never reach HBM), then ``. down_e`` in float32. An
UNGATED expert (``down_e(relu(rows . up_e)^2)``) is the same two calls, the
first over ONE stack with the squared ReLU on its float32 sums.

The row tiles are cut at every group's first row: a VISIT is one (row tile,
expert) pair that shares a row, at most ``row tiles + experts - 1`` of them,
a static bound. The group offsets and the visits are made in the graph
(``visits``) and scalar-prefetched. The grid runs over output-column tiles
and, inside, the visits in order: a visit multiplies its tile of rows by the
column tile of ITS expert's matrix, the whole contraction in one block,
float32 sums, and keeps the rows of its own group (the others are a
neighbouring visit's). A tile past the last group (rows assigned to an
expert the layer does not hold) gets one visit that writes zeros and
multiplies nothing.

The rows' and the output's blocks come and go through Pallas's pipeline. The
expert matrices do NOT: the pipeline looks one VISIT ahead, and at 256 rows
an expert a visit's matrix work covers half of the next expert's fetch, so
the matrix unit waited at every change of expert. The stacks stay in HBM and
the kernel fetches them BY HAND, a RUN ahead: a run is the consecutive visits
of one expert (``visits`` numbers them), a matrix has a ring of ``depth``
VMEM buffers and a DMA semaphore a slot, and run r's column tile lives in
slot r % depth. At a run's first visit the fetch of run r + depth - 1 starts
(its slot was run r - 1's, whose visits are over) and the run's own is waited
for; the first visit of a column tile primes depth - 1 runs; every later
visit of the run finds the block in VMEM. An expert that received no row is
in no run and its weights are never fetched, so a decode step reads the
experts TOUCHED. ``depth`` says when a matrix arrives and nothing about what
is computed: the output is the same bit for bit at every depth.

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

__all__ = ["grouped_matmul", "expert_ffn", "visits", "tiles", "layer_tiles",
           "supported", "moe_form"]

# the most VMEM the rings of a call's matrices may take (the chip has 128 MiB
# and the compiler's own default limit is 16; the rows, the output and the
# float32 sums of a 128-row tile take up to 14 more)
_MATRIX_BYTES = 36 << 20


def supported(rows, gate_weight, down_weight):
    """Whether Mosaic takes an expert layer of these operands (shapes and
    types alone; ``gate_weight`` a stack of the first call, the one ``up`` of
    an ungated expert): bfloat16 rows and stacks (float32 experts stay XLA's:
    no cell runs them and a block of theirs is twice the VMEM), both widths
    whole tiles of 128 lanes, the rows a whole number of bfloat16's sublane
    tiles of 16. A width that is NO whole lane tiles (1,856 = 14.5) is stored
    padded to the next one by the model that has it (zero columns of up, zero
    rows of down: ``models/transformer.py:_lane_tiles``): Mosaic slices no
    such width out of HBM ("Slice shape along dimension 2 must be aligned to
    tiling (128), but is 1856"), and through the pipeline's own blocks, which
    it does take, a layer ran at HALF the padded one's rate (3.73 against
    1.72 ms a step's layer, 4.73 against 2.10 an admission's: ``PERF.md``
    section 6, PR 48)."""
    if not (rows.dtype == gate_weight.dtype == down_weight.dtype
            == jnp.bfloat16):
        return False
    d, f = gate_weight.shape[1], gate_weight.shape[2]
    return d % _LANES == 0 and f % _LANES == 0 \
        and rows.shape[0] % 16 == 0


def _column_tile(k, n, buffers, itemsize):
    """The widest column tile of an (k, n) matrix, a divisor of n in whole
    lane tiles (n itself where n is no multiple of 128), ``buffers`` blocks
    of which fit ``_MATRIX_BYTES``."""
    if n % _LANES:
        return n
    units = n // _LANES
    for parts in range(1, units + 1):
        if units % parts == 0 \
                and buffers * k * (n // parts) * itemsize <= _MATRIX_BYTES:
            return n // parts
    return _LANES


def tiles(rows, experts, d, f, dtype, gated=True):
    """``(row tile, column tile of gate and up, column tile of down, depth
    of the fetch ring)`` of an expert layer whose held ``experts`` of width
    ``f`` under a model width ``d`` get ``rows`` assignment rows between
    them (``gated``: the first call has two stacks, gate and up; else one):
    THE rule, from the rows an expert gets on average, the matrices'
    bytes and the budget and nothing else (no option, no model's name),
    written from chip runs (``PERF.md`` section 6, PRs 41 and 47).

    The row tile is about two groups long, the power of two at or above
    twice the rows an expert gets, between 32 and 128. The matrix unit loads
    a 128 x 128 tile of a matrix in about the time 128 rows pass through it,
    so a visit costs the same for 16 rows as for 128 and a shorter tile only
    makes more of them; past 128 a tile that straddles two groups multiplies
    rows it then discards (512 was a third slower at 256 rows an expert).
    Few rows an expert (a decode step: 1.5 to 16) is a weight-streaming
    problem whatever the tile, 32 there. The column tile is the widest whose
    ring fits the budget: a matrix arrives in few large pieces and the rows
    are read once a column tile (half as wide was 4 to 12% slower in every
    regime).

    The ring is TWO buffers a matrix, one run ahead, where a run is several
    visits (more than 64 rows an expert: at 256 the next expert's 8 MB land
    behind two visits' matrix work, 2.34 ms a layer for the pipeline's 2.74
    to 2.89; a third buffer puts two fetches in flight that share the
    memory's rate and the nearer one lands later: 2.40), and THREE, two runs
    ahead, where a row tile holds two groups or more, so that a run is one
    visit and one run ahead is what the pipeline gave (48 and 64 rows an
    expert: 1.91 and 1.89 ms for 1.96 and 1.99 at two and 2.07 and 2.17
    through the pipeline), if three buffers of the WIDEST column tile fit,
    else two (a narrower tile costs more than the third buffer gives: 3.32
    against 3.00 ms at 16 stacks of 4,096 x 2,048). A fourth gave 2% more in
    two admissions and lost 2% in a step. In a step a run is one visit
    whatever the depth, and the ring reads the pipeline's time to 1% (2.6%
    under it at best)."""
    itemsize = jnp.dtype(dtype).itemsize
    per = rows / experts
    tm = 32
    while tm < min(2 * per, 128):
        tm *= 2
    first = 2 if gated else 1
    wide = lambda depth: (_column_tile(d, f, first * depth, itemsize),
                          _column_tile(f, d, depth, itemsize))
    depth = 3 if 2 * per <= 128 and wide(3) == wide(2) else 2
    return (tm, *wide(depth), depth)


def layer_tiles(rows, up_weight, routed_experts=None, gated=True):
    """``tiles`` of an expert layer from its operands (each carries
    ``.shape`` and ``.dtype``: ``rows`` (M, D) sorted by expert,
    ``up_weight`` (held experts, D, F) one of the first call's stacks), at
    the rows that reach a held expert under even routing: all M, or the held
    experts' share of them where the layer holds some of
    ``routed_experts``."""
    experts, d, f = up_weight.shape
    return tiles(rows.shape[0] * experts // (routed_experts or experts),
                 experts, d, f, rows.dtype, gated)


def moe_form(rows, gate_weight, down_weight):
    """THE rule that names the form of ``MoEFeedForward``'s expert products,
    ``"kernel"`` or ``"ragged_dot"``, from the operands' shapes and types
    and the backend; no caller, option or environment variable does. Each
    operand carries ``.shape`` and ``.dtype``: ``rows`` (N * k, D) sorted by
    expert, ``gate_weight`` (held experts, D, F) a stack of the first call
    (the one ``up`` of an ungated expert), ``down_weight`` (held experts,
    F, D).

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
    """``(offsets (E + 1,), tile (V,), expert (V,), count (1,), run (V,),
    run_expert (R,), runs (1,))``, int32: the (row tile, expert) pairs that
    share a row, in order, of ``rows`` rows sorted by expert with ``sizes``
    (E,) rows a group, under row tiles of ``tm``. ``V`` = row tiles + E - 1
    is the static bound; entries past ``count`` repeat the last visit's
    blocks (nothing is fetched for them).

    A visit starts at a CUT: a tile's first row, or the first row of a
    non-empty group inside a tile. The cuts sorted are the visits; a cut's
    tile is its row over ``tm`` and its expert the group that holds the row.
    Rows past the last group are held by no group: their cuts take the last
    non-empty expert (whose block is in VMEM already) and own no row.

    A RUN is the consecutive visits of one expert, what the kernel fetches a
    matrix for: ``run`` is each visit's, ``run_expert`` each run's expert
    (the non-empty groups in order; ``R`` = min(E, V), entries past ``runs``
    repeat the last) and ``runs`` how many there are, one at least."""
    experts = sizes.shape[0]
    n_tiles = -(-rows // tm)
    bound = n_tiles + experts - 1
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    inside = (sizes > 0) & (starts % tm != 0)
    past = n_tiles * tm
    cuts = jnp.sort(jnp.concatenate([
        jnp.arange(n_tiles, dtype=jnp.int32) * tm,
        jnp.where(inside, starts, past)]))[:bound]
    count = n_tiles + jnp.sum(inside.astype(jnp.int32))
    tile = jnp.minimum(cuts // tm, n_tiles - 1)
    last = jnp.max(jnp.where(sizes > 0, jnp.arange(experts, dtype=jnp.int32),
                             0))
    expert = jnp.minimum(
        jnp.sum((ends[None, :] <= cuts[:, None]).astype(jnp.int32), axis=1),
        last)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    # the experts never go back, so a run ends where the expert changes; the
    # r-th run's expert is the first to bring the non-empty groups to r + 1
    run = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(
        (expert[1:] != expert[:-1]).astype(jnp.int32))])
    live = jnp.cumsum((sizes > 0).astype(jnp.int32))
    run_expert = jnp.minimum(jnp.sum(
        (live[None, :] <= jnp.arange(min(experts, bound),
                                     dtype=jnp.int32)[:, None])
        .astype(jnp.int32), axis=1), last)
    return offsets, tile, expert, count.reshape(1), run, run_expert, \
        jnp.maximum(live[-1:], 1)


def _kernel(off_ref, tile_ref, expert_ref, count_ref, run_ref, run_expert_ref,
            runs_ref, x_ref, *refs, tm, mats, depth, activation):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w_refs, o_ref = refs[:mats], refs[mats]
    j, v = pl.program_id(0), pl.program_id(1)
    tile, expert = tile_ref[v], expert_ref[v]
    lo, hi = off_ref[expert], off_ref[expert + 1]
    row0 = tile * tm

    if depth:   # the matrices by hand: run r's column tile in slot r % depth
        w_bufs, sem = refs[mats + 1:-1], refs[-1]
        tn = o_ref.shape[1]
        run = run_ref[v]

        def copies(r):
            columns = pl.ds(pl.multiple_of(j * tn, _LANES), tn)
            return [pltpu.make_async_copy(
                w.at[run_expert_ref[r], :, columns], buf.at[r % depth],
                sem.at[i, r % depth])
                for i, (w, buf) in enumerate(zip(w_refs, w_bufs))]

        def fetch(r):
            @pl.when(r < runs_ref[0])
            def _():
                for copy in copies(r):
                    copy.start()

        # a column tile's first visit primes the ring
        @pl.when(v == 0)
        def _():
            for r in range(depth - 1):
                fetch(r)

        # a run's first visit: ``depth - 1`` runs ahead leaves, its own lands
        @pl.when((v == 0) | (run_ref[jnp.maximum(v - 1, 0)] != run))
        def _():
            fetch(run + depth - 1)
            for copy in copies(run):
                copy.wait()

        matrix = lambda i: w_bufs[i][run % depth]
    else:       # the pipeline's own blocks, one visit ahead
        matrix = lambda i: w_refs[i][...]

    # a tile's first visit: what no group owns stays zero
    @pl.when((v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != tile))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when((v < count_ref[0]) & (lo < row0 + tm) & (hi > row0))
    def _():
        x = x_ref[...]
        dot = lambda i: jnp.dot(x, matrix(i),
                                preferred_element_type=jnp.float32)
        if mats == 2:   # gate and up: the activation on the sums
            out = jax.nn.silu(dot(0)) * dot(1)
        elif activation == "relu2":
            out = jnp.square(jnp.maximum(dot(0), 0.0))
        else:
            out = dot(0)
        row = row0 + jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
        o_ref[...] = jnp.where((row >= lo) & (row < hi),
                               out.astype(o_ref.dtype), o_ref[...])


@functools.partial(jax.jit, static_argnames=("tm", "tn", "depth",
                                             "activation", "interpret"))
def grouped_matmul(rows, weights, meta, *, tm, tn, depth, activation=None,
                   interpret=False):
    """Row r of ``rows`` (M, K), in group e by ``meta`` = ``visits(sizes, M,
    tm)``, times ``weights[i][e]`` (E, K, N). One stack: the products, (M, N)
    float32; with ``activation="relu2"`` ``relu(rows . up_e)^2``, the
    activation on the float32 sums, cast once to the rows' type. Two stacks
    (gate, up): ``silu(rows . gate_e) * (rows . up_e)``, likewise. A row
    past the last group comes out zero. ``tn`` divides N. ``depth``: the
    buffers a matrix of the ring the kernel fetches into by hand, a run's
    matrices ``depth - 1`` runs ahead; when a matrix arrives, never what is
    computed. 0: the matrices through the pipeline's own two blocks, one
    VISIT ahead, as PR 41 shipped the kernel: ``tiles`` never names it, the
    tests hold every depth to it bit for bit. ``interpret``: run the kernel
    interpreted (the CPU)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = rows.shape
    n = weights[0].shape[2]
    if activation not in (None, "relu2") or (activation
                                             and len(weights) != 1):
        raise ValueError("grouped_matmul: activation %r over %d stack(s)"
                         % (activation, len(weights)))
    fused = len(weights) == 2 or activation is not None
    out_dtype = rows.dtype if fused else jnp.float32
    itemsize = jnp.dtype(rows.dtype).itemsize
    blocks = 2 * (tm * k * itemsize
                  + tm * tn * jnp.dtype(out_dtype).itemsize) \
        + (depth or 2) * len(weights) * k * tn * itemsize + 4 * tm * tn * 4
    at = lambda index: lambda j, v, off, t, e, c, r, re, rs: index(j, v, t, e)
    if depth:
        matrices = [pl.BlockSpec(memory_space=pl.ANY) for _ in weights]
        scratch = [pltpu.VMEM((depth, k, tn), w.dtype) for w in weights] \
            + [pltpu.SemaphoreType.DMA((len(weights), depth))]
    else:
        matrices = [pl.BlockSpec((None, k, tn),
                                 at(lambda j, v, t, e: (e[v], 0, j)))
                    for _ in weights]
        scratch = []
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, mats=len(weights), depth=depth,
                          activation=activation),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(meta),
            grid=(n // tn, meta[1].shape[0]),
            in_specs=[pl.BlockSpec((tm, k),
                                   at(lambda j, v, t, e: (t[v], 0)))]
            + matrices,
            out_specs=pl.BlockSpec((tm, tn),
                                   at(lambda j, v, t, e: (t[v], j))),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(min(blocks + (8 << 20), 100 << 20))),
        # what XLA's own grouped matmul is counted as: every row once, and
        # (the most a call can fetch) every expert's matrix once
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n * len(weights),
            transcendentals=m * n * (len(weights) - 1),  # the SiLU
            bytes_accessed=(m * k + sum(w.size for w in weights)) * itemsize
            + m * n * jnp.dtype(out_dtype).itemsize),
        interpret=interpret,
        name="grouped_matmul_gated" if len(weights) == 2
        else "grouped_matmul_relu2" if activation else "grouped_matmul",
    )(*meta, rows, *weights)


def expert_ffn(rows, gate_weight, up_weight, down_weight, sizes,
               routed_experts=None, interpret=False):
    """``(silu(rows . gate_e) * (rows . up_e)) . down_e`` for the rows of
    every group e, or, where ``gate_weight`` is None (an ungated expert),
    ``relu(rows . up_e)^2 . down_e``: ``rows`` (M, D) sorted by expert,
    ``sizes`` (E,) rows a group, the stacks (E, D, F), (E, D, F), (E, F, D).
    Returns (M, D) float32, zero for a row past the last group. The tiles
    are ``layer_tiles``' at these operands."""
    m = rows.shape[0]
    gated = gate_weight is not None
    tm, tn_up, tn_down, depth = layer_tiles(rows, up_weight, routed_experts,
                                            gated)
    tm = min(tm, m)     # fewer rows than a tile: one tile of them all
    meta = visits(sizes, m, tm)
    act = grouped_matmul(
        rows, (gate_weight, up_weight) if gated else (up_weight,), meta,
        tm=tm, tn=tn_up, depth=depth,
        activation=None if gated else "relu2", interpret=interpret)
    return grouped_matmul(act, (down_weight,), meta, tm=tm, tn=tn_down,
                          depth=depth, interpret=interpret)
