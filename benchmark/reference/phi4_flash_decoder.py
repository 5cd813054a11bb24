"""Plain reference: Phi-4-mini-flash-reasoning (``model_type: phi4flash``),
full forward, the recurrence run SEQUENTIALLY and differential attention as
its FOUR softmaxes a pair of key/value pairs.

The SambaY decoder-hybrid-decoder (arXiv:2507.06607) with Differential
Attention (arXiv:2410.05258) over Mamba-1 (arXiv:2312.00752), at the sizes of
https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json
and, where that file is silent, the family's ``configuration_phi4flash.py``
defaults and ``modeling_phi4flash.py``, written from knowledge of them because
there is no network here. Straightforward ``jax.numpy``: float32,
``default_matmul_precision("highest")``, a Python loop over the layers, a full
causal forward with T x T scores: no cache, no page, no ring, no band, no
chunk of the recurrence, no padded query, nothing from ``mxnet_tpu``. EVERY
layer runs over EVERY position (the program's admission runs the
cross-decoder on one row; this does not). One call scores one whole sequence;
prefill and decode through the program's cache (rows, rings AND the one
shared pool) must agree with it position by position.

For tokens t_0..t_{T-1}: x = E[t] (no position table, no rotation, no
multiplier). Every layer i of N, ``half`` = N / 2:
    h = x + mixer_i(ln(x; g1_i, b1_i));   x' = h + mlp_i(ln(h; g2_i, b2_i))
    mlp(x) = Wd (silu(g) * u),  [g | u] = Wgu x        (gate rows first)
    ln(x; g, b) = (x - mean) / sqrt(var + 1e-5) * g + b
logits = ln(x; gf, bf) E^T                             (tied head)

The mixer by depth (``mb_per_layer: 2``):
    i <= half, even      Mamba-1; layer ``half`` also hands on m = y (below)
    i <  half, odd       differential attention over the last W keys, the
                         token itself among them (W = ``sliding_window``)
    i == half + 1        differential attention, causal, full; its keys and
                         values (k, v) are what the cross layers attend
    i >= half + 2, even  gated memory unit: Wo_i (m * silu(Wi_i x))
    i >= half + 2, odd   differential CROSS attention: own Wq, Wo, lambdas,
                         sub-norm; keys and values are layer half + 1's

``Mamba-1`` on h (T, d): E = expand * d channels, state S, rank R, kernel K:
    [u | z] = Win h
    u'_t = silu(sum_{j<K} w[:, j] u_{t-K+1+j} + b)        zeros left of t = 0
    [r (R) | B_t (S) | C_t (S)] = Wx u'_t;  dt_t = softplus(Wdt r + b_dt)
    S_t = exp(dt_t (outer) A) * S_{t-1} + (dt_t * u'_t) (outer) B_t   (E x S)
    A = -exp(A_log), S_{-1} = 0;  y_t = S_t C_t + D * u'_t
    out = Wout (y_t * silu(z_t))
  run here as a ``lax.scan`` over the T positions.

``differential attention`` on h (T, d), Hq query heads and Hkv key/value
heads of dh: [q | k | v] = Wqkv h + b (a cross layer: q = Wq h + b alone).
Query heads pair as (2j, 2j + 1) = (q1_j, q2_j), j < Hq / 2; key heads as
(2g, 2g + 1) = (k1_g, k2_g) and value heads likewise, g < Hkv / 2; pair j
attends pair g = j // (Hq / Hkv):
    a1_j = softmax(q1_j k1_g^T / sqrt(dh) + mask) [v1_g | v2_g]
    a2_j = softmax(q2_j k2_g^T / sqrt(dh) + mask) [v1_g | v2_g]     (2 dh wide)
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(i)
    lam0(i) = 0.8 - 0.6 exp(-0.3 i)
    o_j = (1 - lam0(i)) * rms(a1_j - lam a2_j; gamma_i, 1e-5)     over 2 dh
    out = Wo [o_0 | ... ] + b_o

Points I could not check against the source, each a possible departure:
- which depth holds which mixer (the table above), layer ``half`` handing on
  the scan's output BEFORE its gate, D * u' included;
- lam0's depth is the layer's index, counted from 0;
- the window holds W keys WITH the token itself;
- the convolution and ``dt_proj`` carry a bias, the other Mamba projections
  none; the attention projections carry one;
- ``dt`` is not clamped after its softplus;
- the sub-norm is an RMS norm with a weight, over the 2 dh of a pair;
- grouped attention pairs key/value pair g with query pairs
  g * Hq/Hkv .. (g + 1) * Hq/Hkv - 1 (``repeat_kv``).
Layout choices that change no function: q, k and v live in ONE fused matrix
(rows q, then k, then v, each head-major); the MLP's gate rows before its up
rows; u's rows before z's in ``Win``.

Checkpoint layout (the only thing shared with the program): ``embed_weight``
(vocab, d), also the head; ``final_ln_gamma`` / ``_beta`` (d,); per layer
``layer<i>_`` ``ln1_gamma`` / ``_beta``, ``ln2_gamma`` / ``_beta`` (d,),
``mlp_in_weight`` (2F, d), ``mlp_out_weight`` (d, F); a Mamba layer
``mamba1_in_weight`` (2E, d), ``mamba1_conv_weight`` (E, K),
``mamba1_conv_bias`` (E,), ``mamba1_x_weight`` (R + 2S, E),
``mamba1_dt_weight`` (E, R), ``mamba1_dt_bias`` (E,), ``mamba1_A_log``
(E, S), ``mamba1_D`` (E,), ``mamba1_out_weight`` (d, E); a gated memory unit
``gmu_in_weight`` (E, d), ``gmu_out_weight`` (d, E); a self-attention layer
``self_qkv_weight`` ((Hq + 2 Hkv) dh, d), ``self_qkv_bias``,
``self_proj_weight`` (d, Hq dh), ``self_proj_bias``, ``self_lambda_q1`` /
``_k1`` / ``_q2`` / ``_k2`` (dh,), ``self_subln_gamma`` (2 dh,); a cross layer
the same under ``cross_`` with ``cross_q_weight`` (Hq dh, d) and
``cross_q_bias`` in place of the fused matrix. Linear weights are (out, in).
Weights may be stored in a narrower type: each matrix is upcast to float32
where it is used (the head's in blocks of rows), so the float32 copies never
exist side by side.
"""
import math

import jax
import jax.numpy as jnp

LN_EPS = SUBLN_EPS = 1e-5


def layer_norm(x, p, name):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) \
        * p[name + "_gamma"].astype(jnp.float32) \
        + p[name + "_beta"].astype(jnp.float32)


def kinds(cfg):
    """The mixer of every layer, by depth and parity."""
    n = int(cfg["num_layers"])
    half = n // 2
    if n % 4 or int(cfg.get("mb_per_layer", 2)) != 2:
        raise ValueError("phi4flash: a multiple of 4 layers and "
                         "mb_per_layer 2, got %d and %r"
                         % (n, cfg.get("mb_per_layer")))
    return ["mamba" if i <= half and i % 2 == 0
            else "window" if i < half
            else "full" if i == half + 1
            else "gmu" if i % 2 == 0 else "cross" for i in range(n)]


def lambda_init(depth):
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def mamba_mixer(h, p, n, with_state=False):
    """The Mamba-1 mixer on h (T, d) -> (out (T, d), y (T, E): the scan's
    output before its gate). With ``with_state`` instead what the layer
    carries past position T - 1: the recurrent state (E, S) and the last
    K - 1 columns of u BEFORE the convolution (K - 1, E), zeros where the
    sequence is shorter."""
    f32 = lambda name: p[n + "mamba1_" + name].astype(jnp.float32)
    t = h.shape[0]
    u, z = jnp.split(h @ f32("in_weight").T, 2, axis=-1)
    w, k = f32("conv_weight"), p[n + "mamba1_conv_weight"].shape[1]
    padded = jnp.pad(u, ((k - 1, 0), (0, 0)))
    conv = jax.nn.silu(f32("conv_bias")
                       + sum(padded[j:j + t] * w[:, j] for j in range(k)))
    rank = p[n + "mamba1_dt_weight"].shape[1]
    states = p[n + "mamba1_A_log"].shape[1]
    r, b, c = jnp.split(conv @ f32("x_weight").T, [rank, rank + states],
                        axis=-1)
    dt = jax.nn.softplus(r @ f32("dt_weight").T + f32("dt_bias"))
    a = -jnp.exp(f32("A_log"))

    def one(s, step):
        dt_t, u_t, b_t, c_t = step
        s = jnp.exp(dt_t[:, None] * a) * s \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        return s, s @ c_t

    state, y = jax.lax.scan(one, jnp.zeros(a.shape, jnp.float32),
                            (dt, conv, b, c))
    if with_state:
        return state, padded[t:]
    y = y + f32("D") * conv
    return (y * jax.nn.silu(z)) @ f32("out_weight").T, y


def keys_and_values(h, p, n, cfg):
    """(k, v) of a self-attention layer on h (T, d), each (Hkv, T, dh)."""
    hq, hkv, dh = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    rows = slice(hq * dh, (hq + 2 * hkv) * dh)
    kv = h @ p[n + "self_qkv_weight"][rows].astype(jnp.float32).T \
        + p[n + "self_qkv_bias"][rows].astype(jnp.float32)
    k, v = jnp.split(kv, 2, axis=-1)
    heads = lambda a: a.reshape(-1, hkv, dh).transpose(1, 0, 2)
    return heads(k), heads(v)


def differential_attention(q, k, v, p, n, i, cfg, seen):
    """q (T, Hq * dh) against k, v (Hkv, S, dh) under ``seen`` (T, S) bool:
    the four softmaxes of every pair of key/value pairs, one pair of pairs
    after the other, the subtraction, the sub-norm and the output
    projection. ``n`` ends in ``self_`` or ``cross_``."""
    hq, hkv, dh = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    f32 = lambda name: p[n + name].astype(jnp.float32)
    t = q.shape[0]
    lam0 = lambda_init(i)
    lam = jnp.exp(jnp.sum(f32("lambda_q1") * f32("lambda_k1"))) \
        - jnp.exp(jnp.sum(f32("lambda_q2") * f32("lambda_k2"))) + lam0
    group = hq // hkv                   # query pairs a key/value pair serves
    # (pairs g, query pairs of g, 1 | 2, T, dh)
    q = q.reshape(t, hkv // 2, group, 2, dh).transpose(1, 2, 3, 0, 4)
    k = k.reshape(hkv // 2, 2, -1, dh)
    v = v.reshape(hkv // 2, 2, -1, dh)
    gamma = f32("subln_gamma")

    def one_pair(operands):
        q_g, k_g, v_g = operands        # (group, 2, T, dh), (2, S, dh) twice
        both = jnp.concatenate([v_g[0], v_g[1]], axis=-1)       # (S, 2 dh)
        scores = jnp.einsum("jctd,csd->jcts", q_g, k_g) / math.sqrt(dh)
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        a = jnp.einsum("jcts,sd->jctd", weights, both)
        diff = a[:, 0] - lam * a[:, 1]                          # (group, T, 2 dh)
        rms = jnp.sqrt(jnp.mean(jnp.square(diff), axis=-1, keepdims=True)
                       + SUBLN_EPS)
        return (1.0 - lam0) * diff / rms * gamma

    o = jax.lax.map(one_pair, (q, k, v))            # (Hkv/2, group, T, 2 dh)
    o = o.reshape(hq // 2, t, 2 * dh).transpose(1, 0, 2).reshape(t, hq * dh)
    return o @ f32("proj_weight").T + f32("proj_bias")


def _layer(x, p, i, kind, cfg, carried):
    """One layer on x (T, d). ``carried`` holds what the self-decoder hands
    the cross-decoder: ``m`` and layer half + 1's ``k`` and ``v``."""
    n = "layer%d_" % i
    hq, dh, t = cfg["num_heads"], cfg["head_dim"], x.shape[0]
    f32 = lambda name: p[n + name].astype(jnp.float32)
    h = layer_norm(x, p, n + "ln1")
    causal = jnp.tril(jnp.ones((t, t), bool))
    if kind == "mamba":
        mixed, y = mamba_mixer(h, p, n)
        if i == len(kinds(cfg)) // 2:
            carried["m"] = y
    elif kind == "gmu":
        mixed = (carried["m"] * jax.nn.silu(h @ f32("gmu_in_weight").T)) \
            @ f32("gmu_out_weight").T
    elif kind == "cross":
        q = h @ f32("cross_q_weight").T + f32("cross_q_bias")
        mixed = differential_attention(q, carried["k"], carried["v"], p,
                                       n + "cross_", i, cfg, causal)
    else:
        q = h @ p[n + "self_qkv_weight"][:hq * dh].astype(jnp.float32).T \
            + p[n + "self_qkv_bias"][:hq * dh].astype(jnp.float32)
        k, v = keys_and_values(h, p, n, cfg)
        seen = causal
        if kind == "window":
            seen &= ~jnp.tril(jnp.ones((t, t), bool),
                              k=-int(cfg["sliding_window"]))
        else:
            carried["k"], carried["v"] = k, v
        mixed = differential_attention(q, k, v, p, n + "self_", i, cfg, seen)
    x = x + mixed
    gate, up = jnp.split(layer_norm(x, p, n + "ln2") @ f32("mlp_in_weight").T,
                         2, axis=-1)
    return x + (jax.nn.silu(gate) * up) @ f32("mlp_out_weight").T


def head(x, table):
    """x (R, d) float32 against the tied ``table`` (vocab, d), upcast a block
    of rows at a time: (R, vocab)."""
    vocab, d = table.shape
    block = math.gcd(vocab, 384)
    parts = jax.lax.map(lambda rows: x @ rows.astype(jnp.float32).T,
                        table.reshape(vocab // block, block, d))
    return parts.transpose(1, 0, 2).reshape(x.shape[0], vocab)


def logits(p, tokens, cfg, last=None):
    """(T, vocab) next-token logits at every position of ``tokens`` (T,);
    with ``last`` only the last ``last`` positions go through the final norm
    and the head, (last, vocab)."""
    with jax.default_matmul_precision("highest"):
        x = p["embed_weight"][tokens.astype(jnp.int32)].astype(jnp.float32)
        carried = {}
        for i, kind in enumerate(kinds(cfg)):
            x = _layer(x, p, i, kind, cfg, carried)
        if last is not None:
            x = x[-last:]
        return head(layer_norm(x, p, "final_ln"), p["embed_weight"])


def first_mixer_state(p, tokens, cfg):
    """(recurrent state (E, S), convolution columns (K - 1, E)) of the FIRST
    layer's Mamba mixer after the last of ``tokens``: what a decoder must
    hold for that layer once it has been fed them all. Only the first layer,
    whose input is the embedding itself: between the program's value and
    this one stand the mixer's own arithmetic and the type the state is kept
    in, not the rounding of the layers before."""
    with jax.default_matmul_precision("highest"):
        x = p["embed_weight"][tokens.astype(jnp.int32)].astype(jnp.float32)
        return mamba_mixer(layer_norm(x, p, "layer0_ln1"), p, "layer0_",
                           with_state=True)
