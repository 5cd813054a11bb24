"""The cores of two state-space mixers, each a pair of registry operators in
plain ``jax.numpy``: Mamba-2 (Dao & Gu 2024, "Transformers are SSMs") and,
at the end of the file, Mamba-1 (Gu & Dao 2023, "Mamba"). A core is the
depthwise causal convolution, its activation and the selective state-space
recurrence.

``Mamba2Scan`` runs T positions of a right-padded sequence whose LENGTH is
data, in the chunked (state-space-dual) form, and hands back the outputs and
the two pieces of state a decoder keeps at that length. ``Mamba2Step``
advances that state by one token a row. Both compute in float32 whatever
they are fed, and the state is float32 (the published ``mamba_ssm`` kernels
keep it so). The projections around the core, the gate and its norm stay in
the graph (models/transformer.py ``_granite_layer``).

Per head h of P features, state N, G groups of B and C, kernel K (head h
reads group g = h // (H / G); ``num_groups`` 1, the default: every head reads
the one B and C):
    xBC_t = silu(sum_{j<K} w[:, j] * xBC_{t-K+1+j} + b)     zeros left of t = 0
    [x (H x P) | B (G x N) | C (G x N)] = xBC_t;  dt_t = softplus(dt_t + dt_bias)
    S_t = exp(dt_t A_h) S_{t-1} + dt_t * x_t (outer) B_{t,g}, A_h = -exp(A_log_h)
    y_t = S_t C_{t,g} + D_h x_t
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..base import MXNetError
from .registry import AttrSpec, register

_HI = jax.lax.Precision.HIGHEST  # float32 arithmetic on the matrix unit too

_SIZES = {
    "num_heads": AttrSpec("int", required=True),
    "head_dim": AttrSpec("int", required=True),
    "state_size": AttrSpec("int", required=True),
    "conv_kernel": AttrSpec("int", default=4),
    "num_groups": AttrSpec("int", default=1),
}
_WEIGHTS = ("conv_weight", "conv_bias", "dt_bias", "A_log", "D")


def _split(attrs, xbc):
    """[x (..., H, P) | B | C] of the activated xBC; B and C (..., N) of one
    group, (..., G, N) of several."""
    h, p, n = attrs["num_heads"], attrs["head_dim"], attrs["state_size"]
    g = attrs.get("num_groups", 1)
    if g < 1 or h % g:
        raise MXNetError("Mamba2: %d heads do not divide over %d groups"
                         % (h, g))
    if xbc.shape[-1] != h * p + 2 * g * n:
        raise MXNetError("Mamba2: xBC has %d features, %d heads of %d and "
                         "two states of %d in %d group(s) need %d"
                         % (xbc.shape[-1], h, p, n, g, h * p + 2 * g * n))
    x = xbc[..., :h * p].reshape(xbc.shape[:-1] + (h, p))
    b, c = xbc[..., h * p:h * p + g * n], xbc[..., h * p + g * n:]
    if g > 1:
        b, c = (v.reshape(v.shape[:-1] + (g, n)) for v in (b, c))
    return x, b, c


def _f32(*arrays):
    return tuple(a.astype(jnp.float32) for a in arrays)


def _chunked_scan(x, dt, a, b, c, chunk):
    """The recurrence over T positions, ``chunk`` at a time: inside a chunk
    every position reads every earlier one through the product of the decays
    between them (a masked matrix product: the "dual" form), and a chunk
    reads the ones before it through ONE state (B, H, P, N) that a
    ``lax.scan`` carries from chunk to chunk. x (B, T, H, P); dt (B, T, H)
    after its softplus, 0 where a position is padding (then the state
    passes through it unchanged); a (H,); b, c (B, T, N). Returns
    (y (B, T, H, P), the state after position T - 1)."""
    bsz, t, h, p = x.shape
    n = b.shape[-1]
    q = min(chunk, t)
    pad = -t % q
    if pad:  # dt = 0: padding changes no state
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc = (t + pad) // q
    # chunk-major for the scan: (nc, B, q, ...)
    blocks = lambda v: jnp.moveaxis(v.reshape((bsz, nc, q) + v.shape[2:]), 1, 0)
    xs = blocks(x * dt[..., None])
    cum = jnp.cumsum(blocks(dt * a), axis=2)        # log decay, inclusive
    earlier = jnp.tril(jnp.ones((q, q), bool))[None, :, :, None]

    def one(state, blk):
        xs_c, cum_c, b_c, c_c = blk
        # position l reads s <= l through exp(cum_l - cum_s)
        decay = jnp.exp(jnp.where(
            earlier, cum_c[:, :, None, :] - cum_c[:, None, :, :], -jnp.inf))
        cb = jnp.einsum("bln,bsn->bls", c_c, b_c, precision=_HI)
        y = jnp.einsum("blsh,bshp->blhp", cb[..., None] * decay, xs_c,
                       precision=_HI)
        # what the chunk inherits, decayed to each of its positions
        y = y + jnp.einsum("bln,bhpn->blhp", c_c, state, precision=_HI) \
            * jnp.exp(cum_c)[..., None]
        # the state at the chunk's end
        last = cum_c[:, -1]
        tail = jnp.exp(last[:, None, :] - cum_c)
        state = state * jnp.exp(last)[:, :, None, None] + jnp.einsum(
            "bsn,bshp->bhpn", b_c, xs_c * tail[..., None], precision=_HI)
        return state, y

    state, y = jax.lax.scan(one, jnp.zeros((bsz, h, p, n), jnp.float32),
                            (xs, cum, blocks(b), blocks(c)))
    return jnp.moveaxis(y, 0, 1).reshape(bsz, nc * q, h, p)[:, :t], state


def _grouped_scan(x, dt, a, b, c, chunk):
    """``_chunked_scan`` where b and c (B, T, G, N) are one a GROUP of heads:
    the heads (H = G x H/G, group-major) of each group scanned with the
    group's b and c, the groups side by side (``vmap``: every contraction
    gains a batch axis, nothing is repeated per head)."""
    g = b.shape[2]
    split = lambda v: v.reshape(v.shape[:2] + (g, -1) + v.shape[3:])
    y, state = jax.vmap(
        lambda *group: _chunked_scan(*group, chunk),
        in_axes=(2, 2, 0, 2, 2), out_axes=(2, 1))(
            split(x), split(dt), a.reshape(g, -1), b, c)
    return y.reshape(x.shape), state.reshape(state.shape[:1] + x.shape[2:]
                                             + state.shape[-1:])


def _of_head(v, heads):
    """A row's B or C as each of its ``heads`` reads it, against a state
    (R, H, P, N): (R, N) of one group -> (R, 1, 1, N); (R, G, N) ->
    (R, H, 1, N), head h taking group h // (H / G)."""
    if v.ndim == 2:
        return v[:, None, None, :]
    return jnp.repeat(v, heads // v.shape[1], axis=1)[:, :, None, :]


def columns_before(padded, length, count):
    """The ``count`` rows of each ``padded`` (B, count + T, C) that precede
    position ``length`` (B,) of its sequence, (B, count, C): position p sits
    at padded index p + count, so they start at padded index ``length``."""
    return jax.vmap(lambda row, at: jax.lax.dynamic_slice_in_dim(
        row, at, count, axis=0))(padded, length)


@register(
    "_contrib_Mamba2Scan",
    attrs=dict(_SIZES, chunk_size=AttrSpec("int", default=256)),
    input_names=("data", "dt") + _WEIGHTS + ("length",),
    num_outputs=3,
    output_names=("output", "ssm_state", "conv_state"),
    aliases=("Mamba2Scan",),
)
def _mamba2_scan(attrs, data, dt, conv_weight, conv_bias, dt_bias, A_log, D,
                 length):
    """The core over a right-padded sequence: ``data`` (B, T, H*P + 2GN) is
    the projected xBC before its convolution, ``dt`` (B, T, H) the raw step
    sizes, ``length`` (B, 1) the number of real positions a row (data, so one
    program serves every length). Returns ``(y (B, T, H*P), ssm_state
    (B, H, P, N), conv_state (B, K-1, H*P + 2GN))``: the outputs in ``data``'s
    type (those past the length are meaningless), and in float32 the
    recurrent state after position ``length - 1`` and the last K-1
    PRE-activation xBC columns before ``length`` (zeros where the sequence is
    shorter). Positions at and past the length get ``dt = 0``, so the state
    passes through them unchanged; the columns come from a slice whose start
    is the length. ``chunk_size`` is the chunk of the chunked form and
    changes no function."""
    k = attrs["conv_kernel"]
    xbc, dt, w, bias, dt_bias, a_log, d = _f32(
        data, dt, conv_weight, conv_bias, dt_bias, A_log, D)
    bsz, t, _ = xbc.shape
    n_real = length.reshape(bsz).astype(jnp.int32)
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = jax.nn.silu(bias + sum(padded[:, j:j + t] * w[:, j]
                                  for j in range(k)))
    conv_state = columns_before(padded, n_real, k - 1)
    x, b, c = _split(attrs, conv)
    live = jnp.arange(t)[None, :] < n_real[:, None]
    dt = jnp.where(live[..., None], jax.nn.softplus(dt + dt_bias), 0.0)
    scan = _chunked_scan if b.ndim == 3 else _grouped_scan
    y, state = scan(x, dt, -jnp.exp(a_log), b, c, attrs["chunk_size"])
    y = y + d[:, None] * x
    return y.reshape(bsz, t, -1).astype(data.dtype), state, conv_state


@register(
    "_contrib_Mamba2Step",
    attrs=dict(_SIZES),
    input_names=("data", "dt") + _WEIGHTS + ("ssm_state", "conv_state",
                                             "stepped"),
    num_outputs=3,
    output_names=("output", "ssm_state", "conv_state"),
    aliases=("Mamba2Step",),
)
def _mamba2_step(attrs, data, dt, conv_weight, conv_bias, dt_bias, A_log, D,
                 ssm_state, conv_state, stepped):
    """One token a row: ``data`` (R, H*P + 2GN) and ``dt`` (R, H) as in
    ``Mamba2Scan``, ``ssm_state`` (R, H, P, N) and ``conv_state``
    (R, K-1, H*P + 2GN) the row's state, ``stepped`` (R, 1) negative for a row
    that rides along (a decode step's ``write_slot``). Returns ``(y (R, H*P),
    ssm_state', conv_state')``; the state of a row that rides along comes
    back bit for bit and its ``y`` is meaningless. Elementwise float32 and a
    sum over the state's last axis: nothing here rounds through the matrix
    unit."""
    k = attrs["conv_kernel"]
    xbc, dt, w, bias, dt_bias, a_log, d = _f32(
        data, dt, conv_weight, conv_bias, dt_bias, A_log, D)
    window = jnp.concatenate([conv_state, xbc[:, None, :]], axis=1)
    conv = jax.nn.silu(bias + sum(window[:, j] * w[:, j] for j in range(k)))
    x, b, c = _split(attrs, conv)
    dt = jax.nn.softplus(dt + dt_bias)
    new = jnp.exp(dt * -jnp.exp(a_log))[:, :, None, None] * ssm_state \
        + (dt[..., None] * x)[..., None] * _of_head(b, x.shape[1])
    y = jnp.sum(new * _of_head(c, x.shape[1]), axis=-1) + d[:, None] * x
    moved = stepped.reshape(-1) >= 0
    return (y.reshape(y.shape[0], -1).astype(data.dtype),
            jnp.where(moved[:, None, None, None], new, ssm_state),
            jnp.where(moved[:, None, None], window[:, 1:], conv_state))


# ------------------------------------------------------------------- Mamba-1
# Per channel e of E, state N, rank R, kernel K (no heads, no groups):
#     u'_t = silu(sum_{j<K} w[:, j] * u_{t-K+1+j} + b)       zeros left of t = 0
#     [r (R) | B_t (N) | C_t (N)] = Wx u'_t;  dt_t = softplus(Wdt r + dt_bias)
#     S_t = exp(dt_t (outer) A) * S_{t-1} + (dt_t * u'_t) (outer) B_t
#     A = -exp(A_log) (E, N);  y_t = S_t C_t + D * u'_t
# ``A`` is per channel AND state, so there is no scalar decay a head and the
# chunked dual form above does not compute it: the scan is the recurrence.
# The state is kept STATE-MAJOR, (N, E): its minor dimension is then whole
# tiles of the chip's 128 lanes (E = 5,120), where (E, 16) would be padded
# eight times over in memory and in every step's read and write.
_M1_WEIGHTS = ("conv_weight", "conv_bias", "x_weight", "dt_weight", "dt_bias",
               "A_log", "D")
_M1_BLOCK = 8       # positions a trip of the scan's loop: changes no function


def _mamba1_selective(conv, x_weight, dt_weight, dt_bias):
    """(dt (..., E) after its softplus, B (..., N), C (..., N)) of the
    activated convolution ``conv`` (..., E), float32 on the matrix unit
    too."""
    rank = dt_weight.shape[1]
    n = (x_weight.shape[0] - rank) // 2
    if x_weight.shape != (rank + 2 * n, conv.shape[-1]):
        raise MXNetError("Mamba1: x_weight %r does not project %d channels "
                         "to a rank of %d and two states"
                         % (x_weight.shape, conv.shape[-1], rank))
    rbc = jnp.einsum("...e,oe->...o", conv, x_weight, precision=_HI)
    dt = jnp.einsum("...r,er->...e", rbc[..., :rank], dt_weight,
                    precision=_HI)
    return jax.nn.softplus(dt + dt_bias), rbc[..., rank:rank + n], \
        rbc[..., rank + n:]


@register(
    "_contrib_Mamba1Scan",
    attrs={},
    input_names=("data",) + _M1_WEIGHTS + ("length",),
    num_outputs=3,
    output_names=("output", "ssm_state", "conv_state"),
    aliases=("Mamba1Scan",),
)
def _mamba1_scan(attrs, data, conv_weight, conv_bias, x_weight, dt_weight,
                 dt_bias, A_log, D, length):
    """The Mamba-1 core over a right-padded sequence: ``data`` (B, T, E) is
    the projected u before its convolution, ``length`` (B, 1) the number of
    real positions a row (data, so one program serves every length). Returns
    ``(y (B, T, E), ssm_state (B, N, E), conv_state (B, K-1, E))``: the
    scan's outputs BEFORE any gate, in ``data``'s type (those past the
    length are meaningless), and in float32 the recurrent state after
    position ``length - 1``, state-major, and the last K-1 PRE-activation
    columns of u before ``length`` (zeros where the sequence is shorter).
    Positions at and past the length get ``dt = 0``, so the state passes
    through them unchanged. The recurrence runs one position after the
    other, ``_M1_BLOCK`` of them a trip of a ``lax.scan``: the (T, N, E)
    history of the state is never made, a block's (8, N, E) is (on the chip,
    standing alone at T 2,048: 2.0 ms a layer against 2.7 for a trip a
    position unrolled by 8 and 5.8 not unrolled; PERF.md section 6,
    PR 45)."""
    k = conv_weight.shape[1]
    u, w, bias, x_weight, dt_weight, dt_bias, a_log, d = _f32(
        data, conv_weight, conv_bias, x_weight, dt_weight, dt_bias, A_log, D)
    bsz, t, _ = u.shape
    n_real = length.reshape(bsz).astype(jnp.int32)
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    conv = jax.nn.silu(bias + sum(padded[:, j:j + t] * w[:, j]
                                  for j in range(k)))
    dt, b, c = _mamba1_selective(conv, x_weight, dt_weight, dt_bias)
    live = jnp.arange(t)[None, :] < n_real[:, None]
    dt = jnp.where(live[..., None], dt, 0.0)
    a = -jnp.exp(a_log).T                               # (N, E)
    q = min(_M1_BLOCK, t)
    pad = -t % q
    # (trips, B, q, .): padding has dt = 0 and changes no state
    blocks = lambda v: jnp.moveaxis(jnp.pad(
        v, ((0, 0), (0, pad), (0, 0))).reshape(bsz, -1, q, v.shape[-1]), 1, 0)

    def one(state, blk):
        dt_q, du_q, b_q, c_q = blk      # (B, q, E) twice, (B, q, N) twice
        # what needs no state is made for the q positions at once; the q
        # updates follow one another; the read-out is one sum over them
        decay = jnp.exp(dt_q[:, :, None, :] * a)
        fed = du_q[:, :, None, :] * b_q[:, :, :, None]
        states = []
        for j in range(q):
            state = decay[:, j] * state + fed[:, j]
            states.append(state)
        return state, jnp.sum(jnp.stack(states, axis=1)
                              * c_q[:, :, :, None], axis=2)

    state, y = jax.lax.scan(
        one, jnp.zeros((bsz,) + a.shape, jnp.float32),
        tuple(blocks(v) for v in (dt, dt * conv, b, c)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, -1, y.shape[-1])[:, :t] + d * conv
    return y.astype(data.dtype), state, columns_before(padded, n_real, k - 1)


@register(
    "_contrib_Mamba1Step",
    attrs={},
    input_names=("data",) + _M1_WEIGHTS + ("ssm_state", "conv_state",
                                           "stepped"),
    num_outputs=3,
    output_names=("output", "ssm_state", "conv_state"),
    aliases=("Mamba1Step",),
)
def _mamba1_step(attrs, data, conv_weight, conv_bias, x_weight, dt_weight,
                 dt_bias, A_log, D, ssm_state, conv_state, stepped):
    """One token a row: ``data`` (R, E) as in ``Mamba1Scan``, ``ssm_state``
    (R, N, E) and ``conv_state`` (R, K-1, E) the row's state, ``stepped``
    (R, 1) negative for a row that rides along (a decode step's
    ``write_slot``). Returns ``(y (R, E), ssm_state', conv_state')``; the
    state of a row that rides along comes back bit for bit and its ``y`` is
    meaningless."""
    k = conv_weight.shape[1]
    u, w, bias, x_weight, dt_weight, dt_bias, a_log, d = _f32(
        data, conv_weight, conv_bias, x_weight, dt_weight, dt_bias, A_log, D)
    window = jnp.concatenate([conv_state, u[:, None, :]], axis=1)
    conv = jax.nn.silu(bias + sum(window[:, j] * w[:, j] for j in range(k)))
    dt, b, c = _mamba1_selective(conv, x_weight, dt_weight, dt_bias)
    new = jnp.exp(dt[:, None, :] * -jnp.exp(a_log).T) * ssm_state \
        + (dt * conv)[:, None, :] * b[:, :, None]
    y = jnp.sum(new * c[:, :, None], axis=1) + d * conv
    moved = stepped.reshape(-1) >= 0
    return (y.astype(data.dtype),
            jnp.where(moved[:, None, None], new, ssm_state),
            jnp.where(moved[:, None, None], window[:, 1:], conv_state))
