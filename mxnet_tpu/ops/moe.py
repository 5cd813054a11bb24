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
expert gets and never a shape.
"""
from __future__ import annotations

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
    return [(data.shape, data.dtype),
            ((attrs["num_experts"],), jnp.float32)]


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
    layer therefore add up to the uncut layer's output. Every assignment
    keeps its row in the grouped matmul (rows of absent experts sort last,
    past the last group), so no shape depends on the routing. The default,
    0, holds every expert.

    ``gated=False, activation="relu2"`` is the UNGATED expert,
    ``down_e(relu(up_e x)^2)``: there is no ``gate_weight`` among the inputs
    (``data, router_weight, up_weight, down_weight[, router_bias]``), the
    stacks are ``up_weight`` (E, D, F) and ``down_weight`` (E, F, D), and
    routing, the share, ``load``, the row order and the un-sort are the same
    code. The two pairs are all there is: another is refused. The stacks may
    be stored WIDER than the expert (zero columns of ``up``, zero rows of
    ``down``, so that F is whole lane tiles; ``num_hidden`` is then the
    stored width): relu(0)^2 = 0, the padding adds exactly nothing.

    The router's product and softmax run in float32 at the highest matmul
    precision whatever the storage type: one bfloat16 pass flips near-tied
    experts. Ties go to the lower expert index (``jax.lax.top_k``). The
    expert products multiply in the storage type and accumulate in float32."""
    k, n_exp = attrs["num_experts_per_tok"], attrs["num_experts"]
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
    expert = expert.reshape(-1)
    by, held = expert, None
    if n_local < n_exp:
        # an assignment to an absent expert keeps its row, sorted past the
        # last group, and weighs nothing
        held = (expert >= first) & (expert < first + n_local)
        by = jnp.where(held, expert - first, n_local)
        weight = jnp.where(held.reshape(n, k), weight, 0)
    order = jnp.argsort(by, stable=True)                    # rows by expert
    load = jnp.bincount(expert, length=n_exp).astype(jnp.int32)
    groups = load if held is None else load[first:first + n_local]
    rows = data[order // k]                                 # (N*k, D)
    if _kernel.moe_form(rows, up_weight, down_weight) == "kernel":
        # a row past the groups comes out zero; off the chip a test that
        # holds the rule runs the kernel interpreted
        out = _kernel.expert_ffn(
            rows, first_stacks[0] if gated else None, up_weight, down_weight,
            groups, n_exp,
            interpret=_attention._backend() != "tpu")       # (N*k, D) f32
    else:
        dot = lambda a, b: jax.lax.ragged_dot(
            a, b, groups, preferred_element_type=jnp.float32)
        act = expert_fn(*(dot(rows, w) for w in first_stacks))
        out = dot(act.astype(data.dtype), down_weight)      # (N*k, D) f32
        if held is not None:    # what a row past the groups reads is not
            out = jnp.where(held[order][:, None], out, 0)   # defined
    # un-sort: row j of the sorted order is assignment order[j]; its inverse
    # permutation brings every token's k rows back side by side
    back = jnp.argsort(order).reshape(n, k)
    y = jnp.sum(out[back] * weight[..., None], axis=1)
    return y.astype(data.dtype), load.astype(jnp.float32)
