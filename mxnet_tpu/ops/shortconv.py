"""The gated short convolution of the LFM2 family (``model_type: lfm2`` /
``lfm2_moe``, ``conv_L_cache`` taps, ``conv_bias: false``): a depthwise
causal convolution over a handful of positions, gated on both sides, as two
registry operators in plain ``jax.numpy``.

``GatedShortConv`` runs T positions of a right-padded sequence whose LENGTH
is data and hands back the outputs and what a decoder keeps at that length;
``GatedShortConvStep`` advances that by one token a row. Both compute in
float32 whatever they are fed, and the state is float32. The projections
around the mixer stay in the graph (models/transformer.py
``_lfm2_moe_layer``).

With [B | C | u] the three d-wide blocks of the projected input, K taps:
    z_t = B_t * u_t
    c_t = sum_{j<K} w_j * z_{t-K+1+j}            zeros left of t = 0, no bias
    y_t = C_t * c_t
What a row keeps between steps: z_{t-K+2} .. z_t, (K-1, d).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..base import MXNetError
from .registry import AttrSpec, register
from .ssm import columns_before

_KERNEL = {"kernel": AttrSpec("int", default=3)}


def _gates(data, weight, kernel):
    """(z, C, taps) in float32 of the projected ``data`` (..., 3d) and
    ``weight`` (K, d)."""
    width = data.shape[-1]
    if width % 3:
        raise MXNetError("GatedShortConv: %d features are not three blocks "
                         "[B | C | u]" % width)
    d = width // 3
    if tuple(weight.shape) != (kernel, d):
        raise MXNetError("GatedShortConv: weight %s, %d taps over %d "
                         "features need %s"
                         % (tuple(weight.shape), kernel, d, (kernel, d)))
    x = data.astype(jnp.float32)
    return (x[..., :d] * x[..., 2 * d:], x[..., d:2 * d],
            weight.astype(jnp.float32))


@register(
    "_contrib_GatedShortConv",
    attrs=dict(_KERNEL),
    input_names=("data", "weight", "length"),
    num_outputs=2,
    output_names=("output", "conv_state"),
    aliases=("GatedShortConv",),
)
def _gated_short_conv(attrs, data, weight, length):
    """The mixer over a right-padded sequence: ``data`` (B, T, 3d) is the
    projected [B | C | u], ``weight`` (K, d) the taps, oldest first,
    ``length`` (B, 1) the number of real positions a row (data, so one
    program serves every length). Returns ``(y (B, T, d), conv_state
    (B, K-1, d))``: the outputs in ``data``'s type (those past the length
    are meaningless), and in float32 the last K-1 gated columns ``z`` before
    ``length``, the PROMPT's end and not the bucket's (zeros where the
    sequence is shorter), from a slice whose start is the length."""
    k = attrs["kernel"]
    z, gate, w = _gates(data, weight, k)
    bsz, t, _ = z.shape
    padded = jnp.pad(z, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + t] * w[j] for j in range(k))
    state = columns_before(
        padded, length.reshape(bsz).astype(jnp.int32), k - 1)
    return (gate * conv).astype(data.dtype), state


@register(
    "_contrib_GatedShortConvStep",
    attrs=dict(_KERNEL),
    input_names=("data", "weight", "conv_state", "stepped"),
    num_outputs=2,
    output_names=("output", "conv_state"),
    aliases=("GatedShortConvStep",),
)
def _gated_short_conv_step(attrs, data, weight, conv_state, stepped):
    """One token a row: ``data`` (R, 3d) and ``weight`` as in
    ``GatedShortConv``, ``conv_state`` (R, K-1, d) the row's last gated
    columns, ``stepped`` (R, 1) negative for a row that rides along (a decode
    step's ``write_slot``). Returns ``(y (R, d), conv_state')``; the state of
    a row that rides along comes back bit for bit and its ``y`` is
    meaningless. Elementwise float32: nothing here rounds through the matrix
    unit."""
    k = attrs["kernel"]
    z, gate, w = _gates(data, weight, k)
    window = jnp.concatenate([conv_state.astype(jnp.float32), z[:, None, :]],
                             axis=1)
    conv = sum(window[:, j] * w[j] for j in range(k))
    moved = stepped.reshape(-1) >= 0
    return ((gate * conv).astype(data.dtype),
            jnp.where(moved[:, None, None], window[:, 1:], conv_state))
