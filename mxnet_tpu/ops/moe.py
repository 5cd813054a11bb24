"""Sparse-expert feed-forward (the registry's first data-dependent operator).

Router -> top-k -> sort the (token, expert) assignments by expert -> one
grouped matmul per expert matrix (``jax.lax.ragged_dot``: XLA's own, no
kernel of this repo) -> weighted un-sort. Every shape is static: N tokens
always make N*k assignment rows, nothing is dropped and there is no capacity
factor, so how evenly the router spreads its tokens changes the rows an
expert gets and never a shape.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import AttrSpec, register


@register(
    "_contrib_MoEFeedForward",
    attrs={
        "num_experts": AttrSpec("int", required=True),
        "num_hidden": AttrSpec("int", required=True),
        "num_experts_per_tok": AttrSpec("int", required=True),
    },
    input_names=("data", "router_weight", "gate_weight", "up_weight",
                 "down_weight"),
    num_outputs=2,
    output_names=("output", "load"),
    aliases=("MoEFeedForward",),
)
def _moe_feed_forward(attrs, data, router_weight, gate_weight, up_weight,
                      down_weight):
    """``y = sum_{e in top-k} p_e * down_e(silu(gate_e x) * (up_e x))`` for
    every row x of ``data`` (N, D), with ``p = softmax(x router^T)`` over ALL
    experts and NOT renormalised over the chosen k (OLMoE's
    ``norm_topk_prob: false``). ``router_weight`` is (E, D); the experts'
    matrices are stored (in, out) — ``gate_weight``/``up_weight`` (E, D, F),
    ``down_weight`` (E, F, D) — which is what ``ragged_dot``'s
    (group, k, n) operand takes without a transpose. Returns ``(y (N, D),
    load (E,))``, ``load`` the number of rows each expert received, float32.

    The router's product and softmax run in float32 at the highest matmul
    precision whatever the storage type: one bfloat16 pass flips near-tied
    experts. Ties go to the lower expert index (``jax.lax.top_k``). The
    expert products multiply in the storage type and accumulate in float32."""
    k, n_exp = attrs["num_experts_per_tok"], attrs["num_experts"]
    n = data.shape[0]
    probs = jax.nn.softmax(
        jnp.dot(data.astype(jnp.float32), router_weight.astype(jnp.float32).T,
                precision=jax.lax.Precision.HIGHEST), axis=-1)
    weight, expert = jax.lax.top_k(probs, k)                # (N, k) each
    expert = expert.reshape(-1)
    order = jnp.argsort(expert, stable=True)                # rows by expert
    load = jnp.bincount(expert, length=n_exp).astype(jnp.int32)
    rows = data[order // k]                                 # (N*k, D)
    dot = lambda a, b: jax.lax.ragged_dot(
        a, b, load, preferred_element_type=jnp.float32)
    act = jax.nn.silu(dot(rows, gate_weight)) * dot(rows, up_weight)
    out = dot(act.astype(data.dtype), down_weight)          # (N*k, D) f32
    # un-sort: row j of the sorted order is assignment order[j]; its inverse
    # permutation brings every token's k rows back side by side
    back = jnp.argsort(order).reshape(n, k)
    y = jnp.sum(out[back] * weight[..., None], axis=1)
    return y.astype(data.dtype), load.astype(jnp.float32)
