"""Plain reference: LFM2-MoE (``model_type: lfm2_moe``), full forward.

The layer equations of the public ``transformers`` implementation of
``model_type: lfm2_moe`` (``Lfm2MoeShortConv``, ``Lfm2MoeAttention`` with its
``q_layernorm`` / ``k_layernorm``, ``Lfm2MoeSparseMoeBlock`` with
``use_expert_bias``, ``Lfm2MoeMLP``), written from knowledge of it because
there is no network here; the sizes are those of
https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json.
Straightforward ``jax.numpy``: float32, ``default_matmul_precision("highest")``,
a Python loop over the layers, a full causal forward with no cache, no page,
no bucket, no grouped matmul, no batching, nothing from ``mxnet_tpu``. One
call scores one whole sequence; prefill and decode through the program's
cache (KV pages AND convolution rows) must agree with it position by
position.

For tokens t_0..t_{T-1} at positions 0..T-1: x = E[t]. Every layer i, its
mixer chosen by ``layer_types[i]``, its feed-forward by depth:
    x = x + mixer_i(rms(x; g_operator));   x = x + ffn_i(rms(x; g_ffn))
logits = rms(x; g_final) E^T;   rms(x; g) = x / sqrt(mean(x^2) + eps) * g.

``conv`` mixer on h (T, d), K = conv_L_cache taps, no bias:
    [B | C | u] = h Win^T            three blocks of d, in this order
    z_t = B_t * u_t
    c_t = sum_{j<K} w_j * z_{t-K+1+j}     depthwise, causal, z_s = 0 for s < 0
    out = (C_t * c_t) Wout^T
  What a decoder keeps between steps is z_{t-K+2} .. z_t (``first_conv_columns``).

``full_attention`` mixer: q = h Wq^T (Hq heads of dh), k, v = h Wk^T, h Wv^T
(Hkv heads); q and k normed PER HEAD, rms(.; g_q), rms(.; g_k) with g in R^dh,
BEFORE the rotation; rotary positions on q and k (half-split pairs
(i, i + dh/2), inv_freq_i = theta^(-2i/dh)); causal softmax(q k^T / sqrt(dh)) v,
each key/value head serving Hq / Hkv consecutive query heads; then Wo.

``ffn``, the first ``first_dense_layers`` layers: W2(silu(W1 h) * (W3 h)). The
others, no shared expert:
    s = sigmoid(h Wr^T) over ALL experts, float32
    S = the top-k of s + b          b = expert_bias; ties: the lower expert
                                    index, as jax.lax.top_k
    p_e = scaling * s_e / (sum_{e in S} s_e + 1e-6)      from s, NOT s + b
    ffn = sum_{e in S} p_e W2_e(silu(W1_e h) * (W3_e h))

Departures from the published model, and points I could not check against the
source, each a possible departure:
- depth only is cut (the configuration's ``layer_types``: layers 0..9 of the
  published 40);
- the head is tied to the embedding and ``head_dim`` = hidden / heads (the
  family's convention; the config gives neither);
- the blocks of ``Win`` are taken in the order B, C, u (``in_proj`` chunked in
  three; B gates the input, C the output);
- the rotation pairs feature i with i + dh/2 (``rotate_half``);
- ``expert_bias`` is added to the sigmoid scores for the SELECTION alone, and
  the chosen weights are renormalised with + 1e-6 (the published block). The
  PROGRAM's ``MoEFeedForward`` renormalises with + 1e-20 instead: four sigmoid
  scores sum to 2 to 3, so the two differ by under 5e-7 relative, below
  float32's own rounding of the sum; this reference keeps the published 1e-6;
- grouped attention pairs key/value head j with query heads
  j * Hq/Hkv .. (j + 1) * Hq/Hkv - 1 (``repeat_kv``).
Layout choices that change no function: q, k and v live in ONE fused matrix
(rows q, then k, then v, each head-major); an MLP's gate (W1) and up (W3) rows
live in ONE matrix (gate rows first); an expert's matrices are stored
(in, out), stacked over experts; the taps are stored oldest first, (K, d).

Checkpoint layout (the only thing shared with the program): ``embed_weight``
(vocab, d), also the head; ``final_ln_gamma`` (d,); per layer ``layer<i>_``
``ln1_gamma``, ``ln2_gamma`` (d,); a conv layer ``conv_in_weight`` (3d, d),
``conv_weight`` (K, d), ``conv_out_weight`` (d, d); an attention layer
``qkv_weight`` ((Hq + 2 Hkv) * dh, d), ``qnorm_gamma``, ``knorm_gamma`` (dh,),
``proj_weight`` (d, Hq * dh); a dense layer ``mlp_in_weight`` (2F, d),
``mlp_out_weight`` (d, F); an expert layer ``router_weight`` (E, d),
``router_bias`` (E,), ``experts_gate_weight`` / ``experts_up_weight``
(E, d, Fe), ``experts_down_weight`` (E, Fe, d). Linear weights are (out, in)
except the experts'. Weights may be stored in a narrower type: each matrix is
upcast to float32 where it is used (an expert's as the loop reaches that
expert), so the float32 copies never exist side by side.
"""
import jax
import jax.numpy as jnp


def rms_norm(x, gamma, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gamma.astype(jnp.float32)


def gated_columns(h, w_in):
    """(z (T, d), C (T, d)) of the conv mixer's input projection."""
    b, c, u = jnp.split(h @ w_in.astype(jnp.float32).T, 3, axis=-1)
    return b * u, c


def causal_conv(z, taps):
    """Depthwise causal convolution over time: z (T, d), taps (K, d), oldest
    first; positions before 0 hold zeros."""
    k, t = taps.shape[0], z.shape[0]
    padded = jnp.pad(z, ((k - 1, 0), (0, 0)))
    return sum(padded[j:j + t] * taps[j].astype(jnp.float32)
               for j in range(k))


def conv_mixer(h, p, n):
    z, gate = gated_columns(h, p[n + "conv_in_weight"])
    return (gate * causal_conv(z, p[n + "conv_weight"])) \
        @ p[n + "conv_out_weight"].astype(jnp.float32).T


def rope(x, positions, theta):
    """Rotary positions over half-split pairs on x (heads, T, dh) at
    ``positions`` (T,)."""
    dh = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention_mixer(h, p, n, positions, cfg):
    hq, hkv, dh = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_eps"], float(cfg["rope_theta"])
    t = h.shape[0]
    qkv = h @ p[n + "qkv_weight"].astype(jnp.float32).T
    heads = lambda a, count: a.reshape(t, count, dh).transpose(1, 0, 2)
    q = heads(qkv[:, :hq * dh], hq)
    k = heads(qkv[:, hq * dh:(hq + hkv) * dh], hkv)
    v = heads(qkv[:, (hq + hkv) * dh:], hkv)
    q = rope(rms_norm(q, p[n + "qnorm_gamma"], eps), positions, theta)
    k = rope(rms_norm(k, p[n + "knorm_gamma"], eps), positions, theta)
    k, v = (jnp.repeat(a, hq // hkv, axis=0) for a in (k, v))
    scores = jnp.einsum("htd,hsd->hts", q, k) * dh ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = jnp.einsum("hts,hsd->htd", jax.nn.softmax(scores, axis=-1), v)
    return att.transpose(1, 0, 2).reshape(t, hq * dh) \
        @ p[n + "proj_weight"].astype(jnp.float32).T


def gated_mlp(h, w_in, w_out):
    gate, up = jnp.split(h @ w_in.astype(jnp.float32).T, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_out.astype(jnp.float32).T


def route(h, router, bias, top_k, scaling):
    """(weights (T, k), expert indices (T, k)) of every token: chosen on
    the biased score, weighted by the unbiased one."""
    s = jax.nn.sigmoid(h @ router.astype(jnp.float32).T)
    _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    return scaling * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6), chosen


def moe(h, router, bias, gate, up, down, top_k, scaling):
    """The routed experts' sum for h (T, d): a loop over the experts
    (``fori_loop``, so the program stays small at 64 of them), each applied
    to EVERY token and weighted by that token's weight for it, 0 where the
    expert is not among the token's top-k."""
    weights, chosen = route(h, router, bias, top_k, scaling)
    gate, up, down = jnp.asarray(gate), jnp.asarray(up), jnp.asarray(down)

    def add_expert(e, y):
        w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        a = jax.nn.silu(h @ gate[e].astype(jnp.float32)) \
            * (h @ up[e].astype(jnp.float32))
        return y + w_e[:, None] * (a @ down[e].astype(jnp.float32))

    return jax.lax.fori_loop(0, router.shape[0], add_expert,
                             jnp.zeros_like(h))


def logits(p, tokens, cfg, last=None):
    """(T, vocab) next-token logits at every position of ``tokens`` (T,);
    with ``last`` only the last ``last`` positions go through the final norm
    and the head, (last, vocab)."""
    eps = cfg["rms_eps"]
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0])
        x = p["embed_weight"][tokens.astype(jnp.int32)].astype(jnp.float32)
        for i, kind in enumerate(cfg["layer_types"]):
            n = "layer%d_" % i
            h = rms_norm(x, p[n + "ln1_gamma"], eps)
            x = x + (conv_mixer(h, p, n) if kind == "conv"
                     else attention_mixer(h, p, n, pos, cfg))
            h = rms_norm(x, p[n + "ln2_gamma"], eps)
            if i < cfg["first_dense_layers"]:
                x = x + gated_mlp(h, p[n + "mlp_in_weight"],
                                  p[n + "mlp_out_weight"])
                continue
            x = x + moe(h, p[n + "router_weight"], p[n + "router_bias"],
                        p[n + "experts_gate_weight"],
                        p[n + "experts_up_weight"],
                        p[n + "experts_down_weight"],
                        cfg["num_experts_per_tok"],
                        float(cfg["routed_scaling_factor"]))
        if last is not None:
            x = x[-last:]
        x = rms_norm(x, p["final_ln_gamma"], eps)
        return x @ p["embed_weight"].astype(jnp.float32).T


def first_conv_columns(p, tokens, cfg, at):
    """Layer 0's gated columns z at positions ``at - K + 2 .. at`` of
    ``tokens`` (T,), (K-1, d), zeros left of position 0: what a decoder's row
    for the first layer holds once the token at position ``at`` has gone
    through it. Layer 0 is a conv layer in every published ``layer_types``."""
    k = int(cfg["conv_kernel"])
    with jax.default_matmul_precision("highest"):
        x = p["embed_weight"][tokens.astype(jnp.int32)].astype(jnp.float32)
        z, _ = gated_columns(rms_norm(x, p["layer0_ln1_gamma"],
                                      cfg["rms_eps"]),
                             p["layer0_conv_in_weight"])
        padded = jnp.pad(z, ((k - 1, 0), (0, 0)))
        return jax.lax.dynamic_slice_in_dim(padded, at + 1, k - 1, axis=0)
