"""Plain reference: ``model_type: deepseek_v3`` without a query-side low-rank
projection (``q_lora_rank: null``), full forward.

The layer equations of the public ``transformers`` implementation of
``model_type: deepseek_v3`` (``DeepseekV3Attention`` with
``kv_a_proj_with_mqa``, ``kv_a_layernorm``, ``kv_b_proj`` and
``rope_interleave``; ``DeepseekV3TopkRouter`` with ``scoring_func: sigmoid``
and ``topk_method: noaux_tc``; ``DeepseekV3MoE`` with its shared experts),
written from knowledge of it because there is no network here; the sizes are
those of
https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json.
Straightforward ``jax.numpy``: float32, ``default_matmul_precision("highest")``,
NON-absorbed (every head's key and value are made of the latent), no cache, no
kernels, no batching, nothing from ``mxnet_tpu``. One call scores one whole
sequence; prefill and decode through the program's latent cache must agree
with it position by position.

For tokens t_0..t_{T-1} at positions pos = 0..T-1: x = E[t]. Per layer
    h = rms(x; g1)
    q = h Wq^T                      H heads of [q_nope (nope) | q_rope (rope)]
    [c | k_r] = h Wkva^T            latent (kv_lora_rank) + ONE rotary key;
    c = rms(c; g_kv)
    q_rope, k_r = rope(q_rope, pos), rope(k_r, pos)
        interleaved pairs (2i, 2i + 1), inv_freq_i = theta^(-2i/rope)
    [k_nope_h | v_h] = c Wkvb_h^T   nope + v_dim a head
    p_h = causal softmax((q_nope_h . k_nope_h + q_rope_h . k_r)
                         / sqrt(nope + rope));   o_h = p_h v_h
    x = x + concat_h(o_h) Wo^T
    h = rms(x; g2)
    the first ``first_dense_layers`` layers:
        x = x + Wd(silu(Wg h) * (Wu h))
    the others:
        s = sigmoid(h Wr^T) over ALL experts, float32
        S = the top-k of s + b      b = e_score_correction_bias; ties: the
                                    lower expert index, as jax.lax.top_k
        w_e = scaling * s_e / (sum_{e in S} s_e + 1e-20)    from s, NOT s + b
        x = x + sum_{e in S} w_e Wd_e(silu(Wg_e h) * (Wu_e h)) + shared(h)
        shared: one gated SiLU MLP of num_shared_experts * moe_ffn_dim
logits = rms(x; gf) Wout^T;  rms(x; g) = x / sqrt(mean(x^2) + eps) * g.

Departures from the published model: depth only (the configuration's
``num_layers``: the leading dense layer and the expert layers after it, all
of the one kind). ``n_group = topk_group = 1``, so the grouped selection of
``noaux_tc`` is the plain top-k written here. Layout choices that change no
function: an MLP's gate and up rows live in ONE matrix (gate rows first); an
expert's matrices are stored (in, out), stacked over experts; the rotated
pairs stay where they are (``transformers`` moves the even features in front
of the odd ones before it rotates halves, in q_rope and k_r alike, which
leaves every q_rope . k_r as it is).

Checkpoint layout (the only thing shared with the program): ``embed_weight``
(vocab, d); per layer ``layer<i>_`` ``ln1_gamma`` (d,), ``q_weight``
(H*(nope+rope), d) head-major, ``kva_weight`` (latent+rope, d),
``kvnorm_gamma`` (latent,), ``kvb_weight`` (H*(nope+v_dim), latent)
head-major, ``proj_weight`` (d, H*v_dim), ``ln2_gamma`` (d,); a dense layer
``mlp_in_weight`` (2*ffn, d), ``mlp_out_weight`` (d, ffn); an expert layer
``router_weight`` (E, d), ``router_bias`` (E,), ``experts_gate_weight`` /
``experts_up_weight`` (E, d, F), ``experts_down_weight`` (E, F, d),
``shared_in_weight`` (2*S*F, d), ``shared_out_weight`` (d, S*F);
``final_ln_gamma`` (d,); ``lm_head_weight`` (vocab, d). Linear weights are
(out, in) except the experts'. Weights may be stored in a narrower type: each
matrix is upcast to float32 where it is used (an expert's as the loop reaches
that expert, the embedding's rows after they are looked up), so the float32
copies never exist side by side.
"""
import jax
import jax.numpy as jnp


def rms_norm(x, gamma, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gamma.astype(jnp.float32)


def rope(x, positions, theta):
    """Rotary positions over interleaved pairs on x (heads, T, dh) at
    ``positions`` (T,)."""
    dh = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def attention(q, k, v, scale):
    """Causal scaled dot-product attention; q, k are (heads, T, nope + rope),
    v is (heads, T, v_dim)."""
    t = q.shape[1]
    scores = jnp.einsum("htd,hsd->hts", q, k) * scale
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    return jnp.einsum("hts,hsd->htd", jax.nn.softmax(scores, axis=-1), v)


def latent_attention(h, p, n, positions, cfg):
    """The attention sub-layer's output before ``proj``: (T, H * v_dim)."""
    heads, nope, rope_dim = (cfg[k] for k in (
        "num_heads", "qk_nope_head_dim", "qk_rope_head_dim"))
    v_dim, latent = cfg["v_head_dim"], cfg["kv_lora_rank"]
    theta = float(cfg["rope_theta"])
    f32 = lambda name: p[n + name].astype(jnp.float32)
    t = h.shape[0]
    q = (h @ f32("q_weight").T).reshape(t, heads, nope + rope_dim)
    q = q.transpose(1, 0, 2)
    kva = h @ f32("kva_weight").T
    c = rms_norm(kva[:, :latent], p[n + "kvnorm_gamma"], cfg["rms_eps"])
    k_r = rope(kva[None, :, latent:], positions, theta)     # ONE head
    kv = (c @ f32("kvb_weight").T).reshape(t, heads, nope + v_dim)
    kv = kv.transpose(1, 0, 2)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], positions,
                                             theta)], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, (heads, t, rope_dim))],
                        axis=-1)
    att = attention(q, k, kv[..., nope:], (nope + rope_dim) ** -0.5)
    return att.transpose(1, 0, 2).reshape(t, heads * v_dim)


def gated_mlp(h, w_in, w_out):
    gate, up = jnp.split(h @ w_in.astype(jnp.float32).T, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_out.astype(jnp.float32).T


def route(h, router, bias, top_k, scaling):
    """(weights (T, k), expert indices (T, k)) of every token: chosen on
    the biased score, weighted by the unbiased one."""
    s = jax.nn.sigmoid(h @ router.astype(jnp.float32).T)
    _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    return scaling * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20), chosen


def moe(h, router, bias, gate, up, down, top_k, scaling):
    """The routed experts' sum for h (T, d): a loop over the experts
    (``fori_loop``, so the program stays small at 128 of them), each applied
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
        for i in range(cfg["num_layers"]):
            n = "layer%d_" % i
            att = latent_attention(rms_norm(x, p[n + "ln1_gamma"], eps), p, n,
                                   pos, cfg)
            x = x + att @ p[n + "proj_weight"].astype(jnp.float32).T
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
                        float(cfg["routed_scaling_factor"])) \
                + gated_mlp(h, p[n + "shared_in_weight"],
                            p[n + "shared_out_weight"])
        if last is not None:
            x = x[-last:]
        x = rms_norm(x, p["final_ln_gamma"], eps)
        return x @ p["lm_head_weight"].astype(jnp.float32).T
