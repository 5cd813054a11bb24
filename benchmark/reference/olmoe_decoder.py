"""Plain reference: OLMoE (``model_type: olmoe``), full forward.

The layer equations of the public ``transformers`` implementation of
``model_type: olmoe`` (``OlmoeAttention`` with ``q_norm``/``k_norm`` over the
whole projected vector, ``rotate_half`` rotary positions,
``OlmoeSparseMoeBlock`` with a float32 softmax over all experts before the
top-k, ``norm_topk_prob: false``), written from knowledge of it because there
is no network here; the sizes are those of
https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct/blob/main/config.json.
Straightforward ``jax.numpy``: float32, ``default_matmul_precision("highest")``,
no cache, no kernels, no batching, nothing from ``mxnet_tpu``. One call scores
every position of one whole sequence; prefill and decode through the
program's cache must agree with it position by position.

For tokens t_0..t_{T-1}: x = E[t] (no scaling, no position table). Per layer
    h = rms(x; g1);  [q|k|v] = h Wqkv^T
    q = rms(q; gq), k = rms(k; gk)      over all heads*head_dim features
    split into heads; q, k = rope(q, pos), rope(k, pos)
        half-split rotation (pairs (i, i + head_dim/2)),
        inv_freq_i = theta^(-2i/head_dim)
    causal softmax attention, scale 1/sqrt(head_dim);  x = x + att Wo^T
    h = rms(x; g2);  p = softmax(h Wr^T) over ALL experts
    S = the top-k of p (ties: the lower expert index, as jax.lax.top_k)
    y = sum_{e in S} p_e * Wd_e(silu(Wg_e h) * (Wu_e h))   p NOT renormalised
    x = x + y
logits = rms(x; gf) Wout^T;  rms(x; g) = x / sqrt(mean(x^2) + eps) * g.

Departures from the published model: depth only (the configuration's
``num_layers``; every layer is of the one kind). Layout choices that change
no function: q, k and v live in ONE fused matrix (rows ordered q, k, v, each
head-major), and an expert's three matrices are stored (in, out), stacked
over experts.

Checkpoint layout (the only thing shared with the program): ``embed_weight``
(vocab, d); per layer ``layer<i>_`` ``ln1_gamma`` (d,), ``qkv_weight``
(3*H*dh, d), ``qnorm_gamma`` / ``knorm_gamma`` (H*dh,), ``proj_weight``
(d, H*dh), ``ln2_gamma`` (d,), ``router_weight`` (E, d),
``experts_gate_weight`` / ``experts_up_weight`` (E, d, F),
``experts_down_weight`` (E, F, d); ``final_ln_gamma`` (d,);
``lm_head_weight`` (vocab, d). Linear weights are (out, in) except the
experts'. Weights may be stored in a narrower type: each matrix is upcast
to float32 where it is used (an expert's as the loop reaches that expert),
so the float32 copies never exist side by side.
"""
import math

import jax
import jax.numpy as jnp


def rms_norm(x, gamma, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gamma.astype(jnp.float32)


def rope(x, positions, theta):
    """Rotary positions on x (heads, T, dh) at ``positions`` (T,)."""
    dh = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], axis=-1)
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], axis=-1)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def attention(q, k, v):
    """Causal scaled dot-product attention; q, k, v are (heads, T, dh)."""
    t = q.shape[1]
    scores = jnp.einsum("htd,hsd->hts", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    return jnp.einsum("hts,hsd->htd", jax.nn.softmax(scores, axis=-1), v)


def route(h, router, top_k):
    """(probabilities (T, k), expert indices (T, k)) of every token."""
    p = jax.nn.softmax(h @ router.astype(jnp.float32).T, axis=-1)
    return jax.lax.top_k(p, top_k)


def moe(h, router, gate, up, down, top_k):
    """The expert sum for h (T, d): a loop over the experts (``fori_loop``,
    so the program stays small at 64 of them), each applied to EVERY token
    and weighted by that token's probability for it, 0 where the expert is
    not among the token's top-k."""
    weights, chosen = route(h, router, top_k)
    gate, up, down = jnp.asarray(gate), jnp.asarray(up), jnp.asarray(down)

    def add_expert(e, y):
        w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        a = jax.nn.silu(h @ gate[e].astype(jnp.float32)) \
            * (h @ up[e].astype(jnp.float32))
        return y + w_e[:, None] * (a @ down[e].astype(jnp.float32))

    return jax.lax.fori_loop(0, router.shape[0], add_expert,
                             jnp.zeros_like(h))


def logits(p, tokens, cfg):
    """(T, vocab) next-token logits at every position of ``tokens`` (T,)."""
    heads, dh = cfg["num_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_eps"], float(cfg["rope_theta"])
    f32 = lambda name: p[name].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        t = tokens.shape[0]
        pos = jnp.arange(t)
        x = f32("embed_weight")[tokens.astype(jnp.int32)]
        for i in range(cfg["num_layers"]):
            n = "layer%d_" % i
            h = rms_norm(x, p[n + "ln1_gamma"], eps)
            q, k, v = jnp.split(h @ f32(n + "qkv_weight").T, 3, axis=-1)
            q = rms_norm(q, p[n + "qnorm_gamma"], eps)
            k = rms_norm(k, p[n + "knorm_gamma"], eps)
            q, k, v = (a.reshape(t, heads, dh).transpose(1, 0, 2)
                       for a in (q, k, v))
            att = attention(rope(q, pos, theta), rope(k, pos, theta), v)
            att = att.transpose(1, 0, 2).reshape(t, heads * dh)
            x = x + att @ f32(n + "proj_weight").T
            h = rms_norm(x, p[n + "ln2_gamma"], eps)
            x = x + moe(h, p[n + "router_weight"],
                        p[n + "experts_gate_weight"],
                        p[n + "experts_up_weight"],
                        p[n + "experts_down_weight"],
                        cfg["num_experts_per_tok"])
        x = rms_norm(x, p["final_ln_gamma"], eps)
        return x @ f32("lm_head_weight").T
