"""Plain reference: Granite 4.0-H (``model_type: granitemoehybrid``), full
forward, the recurrence run SEQUENTIALLY.

The layer equations of the public ``transformers`` implementation
(``GraniteMoeHybridMambaLayer``, the Mamba-2 mixer of ``mamba_ssm``;
``GraniteMoeHybridAttention``; ``GraniteMoeHybridMLP`` as the shared expert
of a model with ``num_local_experts: 0``), written from knowledge of it
because there is no network here; the sizes are those of
https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json.
Straightforward ``jax.numpy``: float32, ``default_matmul_precision("highest")``,
no cache, no chunks, no kernels, no batching, nothing from ``mxnet_tpu``. One
call scores every position of one whole sequence; prefill and decode through
the program's cache (KV pages AND recurrent state) must agree with it
position by position.

For tokens t_0..t_{T-1}: x = E[t] * embedding_multiplier (no position table,
no rotation: ``position_embedding_type: nope``). Every layer i, its mixer
chosen by ``layer_types[i]``:
    x = x + residual_multiplier * mixer(rms(x; g1))
    [a | b] = rms(x; g2) Wi^T;  x = x + residual_multiplier * Wd(silu(a) * b)
logits = rms(x; gf) E^T / logits_scaling;  rms(x; g) = x / sqrt(mean(x^2) + eps) * g.

``mamba`` mixer on u (T, d), H heads of P, state N, one group, kernel K:
    [z (H*P) | xBC (H*P + 2N) | dt (H)] = u Win^T
    xBC_t = silu(sum_{j<K} w[:, j] * xBC_{t-K+1+j} + b)   depthwise, causal,
                                                          zeros left of t = 0
    [x (H x P) | B (N) | C (N)] = xBC;  dt_t = softplus(dt_t + dt_bias)
    per head h:  S_t = exp(dt_t A_h) S_{t-1} + dt_t * x_t (outer) B_t   (P x N),
                 A_h = -exp(A_log_h), S_{-1} = 0
    y_t = S_t C_t + D_h x_t
    y = rms(y * silu(z); gn)  over all H*P features at once (one group)
    out = y Wout^T
  run here as a ``lax.scan`` over the T positions: the recurrence itself,
  which the chunked form and the one-token update of the program must both
  reproduce.

``attention`` mixer: q = h Wq^T (Hq heads of dh), k, v = h Wk^T, h Wv^T
(Hkv heads), no rotation, causal softmax(q k^T * attention_multiplier) v, each
key/value head serving Hq / Hkv consecutive query heads, then Wo.

Points I could not check against the source, each a possible departure:
- the gated norm is taken as ``rms(y * silu(z))`` (``norm_before_gate``
  false), over ONE group of all H*P features (``mamba_n_groups`` 1);
- ``dt`` is not clamped after the softplus (``time_step_limit`` (0, inf));
- the convolution and its activation apply to x, B and C together;
- ``residual_multiplier`` scales both branches of every layer, and the
  embedding is scaled BEFORE the first layer's norm;
- the MLP's fused input matrix holds the gate rows first, then the up rows;
- grouped attention pairs key/value head j with query heads
  j * Hq/Hkv .. (j + 1) * Hq/Hkv - 1 (``repeat_kv``).
Depth is the configuration's (``layer_types``). Layout choices that change
no function: q, k and v live in ONE fused matrix (rows q, then k, then v,
each head-major).

Checkpoint layout (the only thing shared with the program): ``embed_weight``
(vocab, d), also the head; ``final_ln_gamma`` (d,); per layer ``layer<i>_``
``ln1_gamma``, ``ln2_gamma`` (d,), ``mlp_in_weight`` (2F, d),
``mlp_out_weight`` (d, F); a mamba layer ``mamba_in_weight``
(2*H*P + 2N + H, d), ``mamba_conv_weight`` (H*P + 2N, K), ``mamba_conv_bias``
(H*P + 2N,), ``mamba_dt_bias``, ``mamba_A_log``, ``mamba_D`` (H,),
``mamba_norm_gamma`` (H*P,), ``mamba_out_weight`` (d, H*P); an attention
layer ``qkv_weight`` ((Hq + 2 Hkv) * dh, d), ``proj_weight`` (d, Hq * dh).
Linear weights are (out, in). Weights may be stored in a narrower type: each
matrix is upcast to float32 where it is used, so the float32 copies never
exist side by side.
"""
import jax
import jax.numpy as jnp


def rms_norm(x, gamma, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gamma.astype(jnp.float32)


def causal_conv(xbc, weight, bias):
    """Depthwise causal convolution over time and its SiLU: xbc (T, C),
    weight (C, K), bias (C,); positions before 0 hold zeros."""
    k = weight.shape[1]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    out = bias + sum(padded[j:j + xbc.shape[0]] * weight[:, j]
                     for j in range(k))
    return jax.nn.silu(out)


def recurrence(x, dt, a, b, c, d, state=None):
    """The Mamba-2 recurrence, one position after the other. x (T, H, P),
    dt (T, H) after its softplus, a (H,) negative, b and c (T, N), d (H,).
    Returns (y (T, H, P), the state after the last position (H, P, N))."""
    if state is None:
        state = jnp.zeros(x.shape[1:] + b.shape[1:], jnp.float32)

    def one(s, step):
        x_t, dt_t, b_t, c_t = step
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return s, jnp.einsum("hpn,n->hp", s, c_t) + d[:, None] * x_t

    state, y = jax.lax.scan(one, state, (x, dt, b, c))
    return y, state


def mamba_mixer(u, p, n, cfg, with_state=False):
    """The Mamba-2 mixer on u (T, d); ``n`` is the layer's name prefix. With
    ``with_state`` also what the layer carries past position T - 1: the
    recurrent state (H, P, N) and the last K-1 xBC columns BEFORE the
    convolution (K-1, H*P + 2N), zeros where the sequence is shorter."""
    heads, hp, ns = cfg["mamba_heads"], cfg["mamba_head_dim"], \
        cfg["mamba_state"]
    f32 = lambda name: p[n + name].astype(jnp.float32)
    inner = heads * hp
    t = u.shape[0]
    zxbcdt = u @ f32("mamba_in_weight").T
    z, raw, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * ns], axis=-1)
    xbc = causal_conv(raw, f32("mamba_conv_weight"), f32("mamba_conv_bias"))
    x, b, c = jnp.split(xbc, [inner, inner + ns], axis=-1)
    dt = jax.nn.softplus(dt + f32("mamba_dt_bias"))
    y, state = recurrence(x.reshape(t, heads, hp), dt,
                          -jnp.exp(f32("mamba_A_log")), b, c, f32("mamba_D"))
    y = rms_norm(y.reshape(t, inner) * jax.nn.silu(z),
                 p[n + "mamba_norm_gamma"], cfg["rms_eps"])
    out = y @ f32("mamba_out_weight").T
    if not with_state:
        return out
    k = p[n + "mamba_conv_weight"].shape[1]
    return out, state, jnp.pad(raw, ((k - 1, 0), (0, 0)))[t:]


def attention_mixer(h, p, n, cfg):
    """Grouped-query causal attention without positions on h (T, d)."""
    hq, hkv, dh = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    t = h.shape[0]
    qkv = h @ p[n + "qkv_weight"].astype(jnp.float32).T
    q, k, v = jnp.split(qkv, [hq * dh, (hq + hkv) * dh], axis=-1)
    q = q.reshape(t, hq, dh).transpose(1, 0, 2)
    # key/value head j serves query heads j*g .. (j+1)*g - 1
    k, v = (jnp.repeat(a.reshape(t, hkv, dh).transpose(1, 0, 2),
                       hq // hkv, axis=0) for a in (k, v))
    scores = jnp.einsum("htd,hsd->hts", q, k) * cfg["attention_multiplier"]
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    att = jnp.einsum("hts,hsd->htd", jax.nn.softmax(scores, axis=-1), v)
    att = att.transpose(1, 0, 2).reshape(t, hq * dh)
    return att @ p[n + "proj_weight"].astype(jnp.float32).T


def logits(p, tokens, cfg):
    """(T, vocab) next-token logits at every position of ``tokens`` (T,)."""
    eps, res = cfg["rms_eps"], cfg["residual_multiplier"]
    f32 = lambda name: p[name].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = f32("embed_weight")[tokens.astype(jnp.int32)] \
            * cfg["embedding_multiplier"]
        for i, kind in enumerate(cfg["layer_types"]):
            n = "layer%d_" % i
            h = rms_norm(x, p[n + "ln1_gamma"], eps)
            mixer = mamba_mixer if kind == "mamba" else attention_mixer
            x = x + res * mixer(h, p, n, cfg)
            h = rms_norm(x, p[n + "ln2_gamma"], eps)
            a, b = jnp.split(h @ f32(n + "mlp_in_weight").T, 2, axis=-1)
            x = x + res * ((jax.nn.silu(a) * b) @ f32(n + "mlp_out_weight").T)
        x = rms_norm(x, p["final_ln_gamma"], eps)
        return x @ f32("embed_weight").T / cfg["logits_scaling"]


def first_mixer_state(p, tokens, cfg):
    """(recurrent state (H, P, N), convolution columns (K-1, H*P + 2N)) of the
    FIRST layer's Mamba mixer after the last of ``tokens``: what a decoder
    must hold for that layer once it has been fed them all. Only the first
    layer, whose input is the embedding itself: between the program's value
    and this one stand the mixer's own arithmetic and the type the state is
    kept in, not the rounding of the layers before."""
    if cfg["layer_types"][0] != "mamba":
        raise ValueError("the first layer is not a Mamba mixer")
    with jax.default_matmul_precision("highest"):
        x = p["embed_weight"].astype(jnp.float32)[tokens.astype(jnp.int32)] \
            * cfg["embedding_multiplier"]
        h = rms_norm(x, p["layer0_ln1_gamma"], cfg["rms_eps"])
        return mamba_mixer(h, p, "layer0_", cfg, with_state=True)[1:]
