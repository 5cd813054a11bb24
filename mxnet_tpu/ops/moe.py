"""Sparse-expert feed-forward (the registry's first data-dependent operator).

Router -> top-k -> sort the (token, expert) assignments by expert -> the
experts' grouped matmuls -> weighted un-sort. The grouped matmuls have two
forms, named from the operands by ``pallas_grouped_matmul.moe_form``: one
``jax.lax.ragged_dot`` per expert matrix (XLA's own: the CPU, and whatever
the kernel does not take), or two calls of this repo's Pallas kernel, gate
and up fused with the activation, then down (the chip). An expert is gated
SiLU over three stacks, or UNGATED over two (``gated=False``:
``down_e(relu(up_e x)^2)``). Every shape is
static: N tokens
always make N*k assignment rows, nothing is dropped and there is no capacity
factor, so how evenly the router spreads its tokens changes the rows an
expert gets and never a shape. A layer that holds a small share of its
experts moves the HELD rows alone, a static chunk of them a loop turn
(``held_rows_chunk``, ``_held_rows``): the routing then changes how many
turns run, and still no shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..base import MXNetError
from . import attention as _attention
from . import pallas_grouped_matmul as _kernel   # plain Python until traced
from .registry import AttrSpec, register

_SCORES = {"softmax": lambda z: jax.nn.softmax(z, axis=-1),
           "sigmoid": jax.nn.sigmoid}


def _moe_names(attrs):
    names = ["data", "router_weight", "gate_weight", "up_weight",
             "down_weight"]
    if not attrs.get("gated", True):
        names.remove("gate_weight")
    return names + ["router_bias"] if attrs.get("router_bias") else names


# an expert's function of its first product(s), on the float32 sums:
# (gated, activation) -> f(gate, up) or f(up)
_EXPERTS = {(True, "silu"): lambda gate, up: jax.nn.silu(gate) * up,
            (False, "relu2"): lambda up: jnp.square(jnp.maximum(up, 0.0))}


def _moe_out(attrs, inputs):
    """``MoEFeedForward``'s outputs from its operands' shapes: ``y`` as the
    data, ``load`` (num_experts,) float32. Shape inference asks this and
    traces no expert product: the kernel's form would import Pallas to say
    the same."""
    data = inputs[0]
    routed = attrs["num_experts"] + attrs.get("num_zero_experts", 0)
    return [(data.shape, data.dtype), ((routed,), jnp.float32)]


@register(
    "_contrib_MoEFeedForward",
    attrs={
        "num_experts": AttrSpec("int", required=True),
        "num_hidden": AttrSpec("int", required=True),
        "num_experts_per_tok": AttrSpec("int", required=True),
        "scoring": AttrSpec("str", default="softmax"),
        "router_bias": AttrSpec("bool", default=False),
        "norm_topk_prob": AttrSpec("bool", default=False),
        "routed_scaling_factor": AttrSpec("float", default=1.0),
        "num_local_experts": AttrSpec("int", default=0),
        "local_expert_offset": AttrSpec("int", default=0),
        "gated": AttrSpec("bool", default=True),
        "activation": AttrSpec("str", default="silu"),
        "num_zero_experts": AttrSpec("int", default=0),
    },
    input_names=_moe_names,
    num_outputs=2,
    output_names=("output", "load"),
    aliases=("MoEFeedForward",),
    infer=_moe_out,
)
def _moe_feed_forward(attrs, data, router_weight, *weights):
    """``y = sum_{e in top-k} p_e * down_e(silu(gate_e x) * (up_e x))`` for
    every row x of ``data`` (N, D), with ``p = softmax(x router^T)`` over ALL
    experts and NOT renormalised over the chosen k (OLMoE's
    ``norm_topk_prob: false``): the defaults. ``scoring="sigmoid"`` scores
    every expert on its own; ``router_bias=True`` takes a sixth input
    ``router_bias`` (E,) that is added to the scores for the SELECTION alone
    (``e_score_correction_bias`` of ``topk_method: noaux_tc``, one group):
    the weights stay the unbiased scores of the chosen; ``norm_topk_prob``
    divides them by their sum over the chosen (+ 1e-20) and
    ``routed_scaling_factor`` multiplies them. ``router_weight`` is (E, D);
    the experts'
    matrices are stored (in, out) — ``gate_weight``/``up_weight`` (E, D, F),
    ``down_weight`` (E, F, D) — which is what ``ragged_dot``'s
    (group, k, n) operand takes without a transpose. Returns ``(y (N, D),
    load (E,))``, ``load`` the number of rows each expert received, float32.

    ``num_local_experts`` = L > 0 with ``local_expert_offset`` = o says which
    experts this layer HOLDS, experts o .. o + L - 1 of the E it routes over
    (one chip's share under expert parallelism): the stacks then have
    L leading rows, the router still E. Scores, selection and
    renormalisation run over all E, wherever the chosen live; only the held
    experts' products are computed and summed, an assignment to an absent
    expert adds nothing, and ``load`` still counts all E. The shares of one
    layer therefore add up to the uncut layer's output. Where the layer
    holds a quarter of its experts or fewer and there are rows enough to
    leave out (``held_rows_chunk``: an admission, never a step) only the
    HELD assignments are gathered, multiplied and combined, a static chunk
    of them a loop turn and as many turns as the routing asks
    (``_held_rows``); everywhere else every assignment keeps its row in the
    grouped matmul, those of absent experts sorted past the last group
    (``_all_rows``). Either way no shape depends on the routing and no
    assignment to a held expert is dropped. The default, 0, holds every
    expert.

    ``gated=False, activation="relu2"`` is the UNGATED expert,
    ``down_e(relu(up_e x)^2)``: there is no ``gate_weight`` among the inputs
    (``data, router_weight, up_weight, down_weight[, router_bias]``), the
    stacks are ``up_weight`` (E, D, F) and ``down_weight`` (E, F, D), and
    routing, the share, ``load``, the row order and the un-sort are the same
    code. The two pairs are all there is: another is refused. The stacks may
    be stored WIDER than the expert (zero columns of ``up``, zero rows of
    ``down``, so that F is whole lane tiles; ``num_hidden`` is then the
    stored width): relu(0)^2 = 0, the padding adds exactly nothing.

    ``num_zero_experts`` = Z > 0 makes the router WIDER than the experts:
    ``router_weight`` is (E + Z, D), ``router_bias`` and ``load`` (E + Z,),
    and ids E .. E + Z - 1 are ZERO-COMPUTE experts, the identity: an
    assignment to one adds ``weight * x`` and multiplies nothing. Scores,
    selection, renormalisation and scaling run over all E + Z, so how many
    of a token's k are real experts (0 to k) is the routing's; such an
    assignment belongs to no group of the grouped matmul, sorted past the
    last one with the absent experts' (``_all_rows``) or left out with them
    (``_held_rows``, whose chunk is the held share of E + Z). A layer that
    holds a share computes the identity part for EVERY row all the same (in
    a deployment a token's own chip adds it): like a shared expert it is
    counted ONCE when the shares of a layer are summed.

    The router's product and softmax run in float32 at the highest matmul
    precision whatever the storage type: one bfloat16 pass flips near-tied
    experts. Ties go to the lower expert index (``jax.lax.top_k``). The
    expert products multiply in the storage type and accumulate in float32."""
    k, n_exp = attrs["num_experts_per_tok"], attrs["num_experts"]
    n_routed = n_exp + attrs.get("num_zero_experts", 0)
    n_local = attrs.get("num_local_experts", 0) or n_exp
    first = attrs.get("local_expert_offset", 0)
    gated = bool(attrs.get("gated", True))
    expert_fn = _EXPERTS.get((gated, attrs.get("activation", "silu")))
    if expert_fn is None:
        raise MXNetError(
            "MoEFeedForward: gated=%r with activation %r is not one of %s"
            % (gated, attrs.get("activation", "silu"), sorted(_EXPERTS)))
    stacks = 3 if gated else 2
    if len(weights) not in (stacks, stacks + 1):
        raise MXNetError("MoEFeedForward: %d stacks and a router_bias at "
                         "most, got %d inputs" % (stacks, len(weights)))
    *first_stacks, down_weight = weights[:stacks]
    up_weight = first_stacks[-1]
    router_bias = weights[stacks] if len(weights) > stacks else None
    if not 0 <= first <= n_exp - n_local or up_weight.shape[0] != n_local:
        raise MXNetError(
            "MoEFeedForward: experts %d..%d of %d held, stacks of %d"
            % (first, first + n_local - 1, n_exp, up_weight.shape[0]))
    n = data.shape[0]
    scoring = attrs.get("scoring", "softmax")
    if scoring not in _SCORES:
        raise MXNetError("MoEFeedForward: scoring %r is not one of %s"
                         % (scoring, sorted(_SCORES)))
    probs = _SCORES[scoring](
        jnp.dot(data.astype(jnp.float32), router_weight.astype(jnp.float32).T,
                precision=jax.lax.Precision.HIGHEST))
    if router_bias is None:
        weight, expert = jax.lax.top_k(probs, k)            # (N, k) each
    else:
        _, expert = jax.lax.top_k(probs + router_bias.astype(jnp.float32), k)
        weight = jnp.take_along_axis(probs, expert, axis=-1)
    if attrs.get("norm_topk_prob"):
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    if attrs.get("routed_scaling_factor", 1.0) != 1.0:
        weight = weight * attrs["routed_scaling_factor"]
    identity = None
    if n_routed > n_exp:
        # a zero-compute expert is the identity: its weights' sum times x
        identity = jnp.sum(jnp.where(expert >= n_exp, weight, 0), axis=-1,
                           keepdims=True) * data.astype(jnp.float32)
    expert = expert.reshape(-1)
    by, held = expert, None
    if n_local < n_routed:
        # an assignment to an absent expert (or to a zero-compute one) keeps
        # its row, sorted past the last group, and weighs nothing
        held = (expert >= first) & (expert < first + n_local)
        by = jnp.where(held, expert - first, n_local)
        weight = jnp.where(held.reshape(n, k), weight, 0)
    order = jnp.argsort(by, stable=True)                    # rows by expert
    load = jnp.bincount(expert, length=n_routed).astype(jnp.int32)
    groups = load if held is None else load[first:first + n_local]
    chunk = held_rows_chunk(n, k, n_local, n_routed)
    if chunk:
        y = _held_rows((k, n_routed, expert_fn), chunk, data, weight, order,
                       groups, *first_stacks, down_weight)
    else:
        y = _all_rows((k, n_routed, expert_fn), data, weight, order, groups,
                      held, *first_stacks, down_weight)
    if identity is not None:
        y = y + identity
    return y.astype(data.dtype), load.astype(jnp.float32)


# a layer moves its held rows alone where that leaves this many rows out
_ROWS_WORTH_A_CHUNK = 4096
_ROW_TILE = 128     # the widest row tile ``pallas_grouped_matmul.tiles`` names


def held_rows_chunk(tokens, k, held, routed):
    """THE rule that says whether a layer of ``held`` of the ``routed``
    experts moves its HELD assignment rows alone, and in chunks of how many:
    the chunk's rows (whole row tiles of the kernel), or 0 where every one of
    the ``tokens * k`` assignments keeps its row. From the operands' shapes
    and the attributes alone; no caller, option or environment variable
    does.

    A chunk where the layer holds a quarter of its experts or fewer AND the
    rows a chunk leaves out are worth a loop around the products
    (``_ROWS_WORTH_A_CHUNK``): the admissions of mimo, dots3 (16 of 256
    held) and laguna (64 of 256). 0 in every decode step (256 to 320 rows:
    its time follows the held experts TOUCHED, not the rows), where half the
    experts are held (half the rows are computed) and where all are.

    The chunk is the held experts' share of the rows under even routing:
    1,024 rows of mimo's 16,384, 4,096 of dots3's 65,536, 20,480 of
    laguna's 81,920. The cells' routing is far from even (a layer's median
    is 0.25 to 1.7 times its share; a padded bucket's positions all choose
    the same experts), so a third to a half of the layers run a second
    turn, and a routing that sends more runs more: nothing is dropped. A
    larger chunk was tried and lost: a turn's cost follows its rows, so on
    the same seeds a chunk of twice the share read 1.1 and 0.2 ms more an
    admission in mimo and 7.8 in dots3 than the share itself (``PERF.md``
    section 6, PR 59, has the readings)."""
    # (a token's held rows lie side by side in a turn: two row tiles at most)
    if not 0 < 4 * held <= routed or min(k, held) - 1 > _ROW_TILE:
        return 0
    rows = tokens * k
    chunk = -(-(rows * held) // (routed * _ROW_TILE)) * _ROW_TILE
    return chunk if rows - chunk >= _ROWS_WORTH_A_CHUNK else 0


def _products(rows, stacks, groups, expert_fn, routed):
    """The experts' products of ``rows`` (M, D) sorted by expert, ``groups``
    rows an expert, over ``stacks`` (gate,) up, down, in the form
    ``moe_form`` names: ``(out (M, D) float32, zeroed)``, ``zeroed`` whether
    a row past the groups comes out zero (the kernel's) or undefined
    (``ragged_dot``'s)."""
    *first_stacks, down_weight = stacks
    if _kernel.moe_form(rows, first_stacks[-1], down_weight) == "kernel":
        # off the chip a test that holds the rule runs the kernel interpreted
        return _kernel.expert_ffn(
            rows, first_stacks[0] if len(first_stacks) == 2 else None,
            first_stacks[-1], down_weight, groups, routed,
            interpret=_attention._backend() != "tpu"), True
    dot = lambda a, b: jax.lax.ragged_dot(
        a, b, groups, preferred_element_type=jnp.float32)
    act = expert_fn(*(dot(rows, w) for w in first_stacks))
    return dot(act.astype(rows.dtype), down_weight), False


def _all_rows(static, data, weight, order, groups, held, *stacks):
    """``y (N, D)`` float32 with a row for EVERY assignment: ``data[order //
    k]`` (N * k, D) through the products, the rows of absent experts
    (``held`` (N * k,) False; None: every expert is held) sorted past the
    last group and weighing nothing, then the weighted un-sort. ``static``:
    experts a token, experts routed over, the expert's function."""
    k, n_exp, expert_fn = static
    n = data.shape[0]
    rows = data[order // k]                                 # (N*k, D)
    out, zeroed = _products(rows, stacks, groups, expert_fn, n_exp)
    if held is not None and not zeroed:
        out = jnp.where(held[order][:, None], out, 0)
    # un-sort: row j of the sorted order is assignment order[j]; its inverse
    # permutation brings every token's k rows back side by side
    back = jnp.argsort(order).reshape(n, k)
    return jnp.sum(out[back] * weight[..., None], axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_rows(static, chunk, data, weight, order, groups, *stacks):
    """``_all_rows``' sum over the HELD assignments alone, ``chunk`` rows a
    loop turn: the held are the first ``h = sum(groups)`` entries of
    ``order``, grouped by expert already, and turn t takes
    ``order[t * chunk:(t + 1) * chunk]``: gathers those rows of ``data``,
    multiplies them with the groups clipped to the turn (an expert that
    straddles two turns is fetched in both), and adds every token's weighted
    rows to ``y``. ``ceil(h / chunk)`` turns (one at least), so one under
    even routing and ``N * k / chunk`` where every token chose held experts
    alone: nothing is dropped, and nothing of N * k rows is built.

    A turn's combine scatters nothing (XLA's row scatter costs the chip
    more than the rows saved) and shifts nothing by less than a tile (a
    slice of rows that starts inside a tile is a copy of the whole chunk):
    the turn's rows in (token, slot) order, a sort of ``chunk`` keys; each
    token's at most ``min(k, held experts)`` adjacent rows summed ON THE
    MATRIX UNIT, a row tile at a time, by a 0/1 matrix that says which later
    rows of the tile (and which of the next tile's first rows: a run is
    shorter than a tile, so it straddles one boundary at most) belong to a
    row's token, float32 at the highest precision (1.0 x a row is the row);
    then a token reads its total at the first row of its run. The products
    are ``_all_rows``', so the two differ by the order of a token's float32
    sum alone.

    Differentiates as ``_all_rows`` (the same function of the same operands):
    a data-dependent number of turns has no transpose of its own."""
    k, _, expert_fn = static
    n, d = data.shape
    tile, tiles = _ROW_TILE, chunk // _ROW_TILE
    halo = min(k, groups.shape[0]) - 1      # a run's rows past its first
    ends = jnp.cumsum(groups)
    starts = ends - groups
    slots = jnp.pad(order, (0, -(n * k) % chunk))
    weight = weight.reshape(-1)
    at = jnp.arange(chunk, dtype=jnp.int32)
    token = jnp.arange(n, dtype=jnp.int32)
    later = at[None, :tile] >= at[:tile, None]
    ones = lambda mask, rows: jnp.einsum(
        "tij,tjd->tid", mask.astype(jnp.float32), rows,
        precision=jax.lax.Precision.HIGHEST)

    def turn(t):
        lo = t * chunk
        mine = jax.lax.dynamic_slice(slots, (lo,), (chunk,))
        sizes = jnp.clip(ends, lo, lo + chunk) - jnp.clip(starts, lo,
                                                          lo + chunk)
        out, zeroed = _products(data[mine // k], stacks, sizes, expert_fn,
                                None)
        live = lo + at < ends[-1]
        if not zeroed:
            out = jnp.where(live[:, None], out, 0)
        # (token, slot) order; what is past the held rows sorts last and
        # belongs to token n, which nobody asks for
        key = jnp.where(live, mine, n * k)
        by_token = jnp.argsort(key)
        owner = (key[by_token] // k).reshape(tiles, tile)
        row = (out * weight[mine][:, None])[by_token].reshape(tiles, tile, d)
        total = ones((owner[:, :, None] == owner[:, None, :]) & later, row)
        if halo:
            beyond = jnp.pad(owner[1:, :halo], ((0, 1), (0, 0)),
                             constant_values=-1)
            total = total + ones(
                owner[:, :, None] == beyond[:, None, :],
                jnp.pad(row[1:, :halo], ((0, 1), (0, 0), (0, 0))))
        owner, total = owner.reshape(chunk), total.reshape(chunk, d)
        run = jnp.minimum(jnp.searchsorted(owner, token,
                                           method="compare_all"), chunk - 1)
        return jnp.where((owner[run] == token)[:, None], total[run], 0)

    # the first turn stands in front of the loop: it is every turn there is
    # unless the routing is uneven, and a loop's first turn would read and
    # write a zero ``y`` (N, D) beside it (0.3 to 1.5 ms a layer on the chip)
    return jax.lax.fori_loop(1, -(-ends[-1] // chunk),
                             lambda t, y: y + turn(t), turn(0))


def _held_rows_fwd(static, chunk, *operands):
    return _held_rows(static, chunk, *operands), operands


def _held_rows_bwd(static, chunk, operands, dy):
    data, weight, order, groups, *stacks = operands
    at = jnp.arange(order.shape[0])
    held = jnp.zeros(at.shape, bool).at[order].set(at < jnp.sum(groups))
    _, pull = jax.vjp(
        lambda data, weight, *stacks: _all_rows(
            static, data, weight, order, groups, held, *stacks),
        data, weight, *stacks)
    d_data, d_weight, *d_stacks = pull(dy)
    return (d_data, d_weight, None, None, *d_stacks)


_held_rows.defvjp(_held_rows_fwd, _held_rows_bwd)
