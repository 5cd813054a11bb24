"""Plain reference: LongCat-Flash-Omni's language model (``model_type:
longcat_flash``), full forward.

The layer equations of the public ``transformers`` implementation of
``model_type: longcat_flash`` (``LongcatFlashMLA`` with ``mla_scale_q_lora``
and ``mla_scale_kv_lora``; ``LongcatFlashTopkRouter`` with its
``e_score_correction_bias`` over ``n_routed_experts + zero_expert_num``
outputs; ``LongcatFlashMoE`` whose experts past the routed ones are
``nn.Identity``; ``LongcatFlashDecoderLayer`` with two attentions, two MLPs
and the shortcut-connected expert layer), written from knowledge of it because
there is no network here; the sizes are those of
https://huggingface.co/meituan-longcat/LongCat-Flash-Omni/blob/main/config.json
(the language model's keys; the audio and vision encoders and the codec decoder
are not language-model layers and are not here). Straightforward
``jax.numpy``: float32, ``default_matmul_precision("highest")``, NON-absorbed
(every head's key and value are made of the latent), a Python loop over the
layers and a loop over the held experts, no cache, no page, no kernel, nothing
from ``mxnet_tpu``. One call scores one whole sequence; prefill and decode
through the program's two latent pools a layer must agree with it position by
position. The ONE concession to size: attention runs over blocks of ``_BLOCK``
queries, one after another (a block's scores against ALL T keys are whole; the
(64, T, T) tensor of a 4,112-token check would be 4.3 GB beside a chip that is
full), which changes no value.

For tokens t_0..t_{T-1} at positions 0..T-1: x = E[t]. Every layer l:

    for s in (0, 1):                               sublayer 2l + s
        a = rms(x; g_in)
        c_q = rms(a Wqa^T; g_q)                    q_lora_rank
        q   = (c_q Wqb^T) * rho_q                  H heads of [q_nope | q_rope];
                                                   rho_q = sqrt(d / q_lora_rank)
                                                   reaches BOTH parts
        [c | k_r] = a Wkva^T                       latent + ONE rotary key
        c   = rms(c; g_kv) * rho_kv                rho_kv = sqrt(d / kv_lora_rank)
        [k_nope_j | v_j] = c Wkvb_j^T              so rho_kv reaches keys AND
                                                   values; k_r is NOT scaled
        q_rope, k_r = rope(q_rope, pos), rope(k_r, pos)
            interleaved pairs (2i, 2i + 1), inv_freq_i = theta^(-2i/rope);
            no rope_scaling key: plain rotary
        p_j = causal softmax((q_nope_j . k_nope_j + q_rope_j . k_r)
                             / sqrt(nope + rope));     o_j = p_j v_j
        x = x + concat_j(o_j) Wo^T
        h = rms(x; g_post)
        if s == 0:  m = MoE(h)                     computed here ...
        x = x + Wd(silu(Wg h) * (Wu h))            the dense MLP, no bias
        if s == 1:  x = x + m                      ... added here

    MoE(h): p = softmax(h Wr^T) over ALL E + Z router outputs, float32
        S = the top-k of p + b      b = e_score_correction_bias (E + Z,);
                                    ties: the lower index, as jax.lax.top_k
        w_e = scaling * p_e for e in S             NOT renormalised
        m = sum_{e in S, e < E, e HELD} w_e Wd_e(silu(Wg_e h) * (Wu_e h))
            + (sum_{e in S, e >= E} w_e) * h       a zero-compute expert is
                                                   the identity
      HELD are experts ``local_expert_offset`` .. + ``num_local_experts`` - 1:
      the share of one chip of an expert-parallel deployment (the stacks have
      that many rows). What the absent experts would have added is left out,
      here as in the program; the identity part is added for every token
      (a token's own chip adds it in the deployment), so when the shares of a
      layer are summed it counts ONCE. No shared expert.
logits = rms(x; g_f) Whead^T;  rms(x; g) = x / sqrt(mean(x^2) + eps) * g.

Departures from the published model, and what its config leaves to the
implementation's defaults (the configuration's file lists them under
``assumed``): depth, the experts held and the vocabulary are cut (the
configuration's file says how); ``norm_topk_prob`` false; no bias on the
router's product; ``hidden_act`` silu; an untied head; no
multi-token-prediction module. Layout choices that change no function: an
MLP's gate and up rows live in ONE matrix (gate rows first); an expert's
matrices are stored (in, out), stacked over the held experts; the rotated
pairs stay interleaved where they are, in q_rope and k_r alike.

Checkpoint layout (the only thing shared with the program). Names count
SUBLAYERS: sublayer s of layer l is ``layer<2l + s>_``: ``ln1_gamma`` (d,),
``qa_weight`` (q_rank, d), ``qnorm_gamma`` (q_rank,), ``qb_weight``
(H*(nope+rope), q_rank) head-major, ``kva_weight`` (latent+rope, d),
``kvnorm_gamma`` (latent,), ``kvb_weight`` (H*(nope+v_dim), latent)
head-major, ``proj_weight`` (d, H*v_dim), ``ln2_gamma`` (d,),
``mlp_in_weight`` (2*ffn, d), ``mlp_out_weight`` (d, ffn); the expert layer's
are its FIRST sublayer's: ``layer<2l>_router_weight`` (E+Z, d),
``router_bias`` (E+Z,), ``experts_gate_weight`` / ``experts_up_weight``
(held, d, F), ``experts_down_weight`` (held, F, d). ``embed_weight``
(vocab, d); ``final_ln_gamma`` (d,); ``lm_head_weight`` (vocab, d). Linear
weights are (out, in) except the experts'. Weights may be stored in a narrower
type: each matrix is upcast to float32 where it is used (an expert's as the
loop reaches that expert, the embedding's rows after they are looked up), so
the float32 copies (20.7 GB at the benchmark's cut) never exist side by side.
"""
import jax
import jax.numpy as jnp

_BLOCK = 128    # queries scored at once (module docstring)
JOINS_AFTER = 1  # the sublayer after whose MLP the expert sum joins x


def rms_norm(x, gamma, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gamma.astype(jnp.float32)


def rope(x, positions, theta):
    """Rotary positions over interleaved pairs on x (..., T, dh)."""
    dh = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def rho(cfg, rank):
    """``mla_scale_q_lora`` / ``mla_scale_kv_lora``: sqrt(d / rank)."""
    return (cfg["model_dim"] / cfg[rank]) ** 0.5


def latent_row(a, p, n, positions, cfg):
    """[c | k_r] (T, latent + rope) of the normed input ``a``: the normed
    latent times rho_kv beside the ONE rotated key, unscaled. What the
    sublayer's pool keeps of a token."""
    lat = cfg["kv_lora_rank"]
    kva = a @ p[n + "kva_weight"].astype(jnp.float32).T
    c = rms_norm(kva[:, :lat], p[n + "kvnorm_gamma"], cfg["rms_eps"]) \
        * rho(cfg, "kv_lora_rank")
    return jnp.concatenate(
        [c, rope(kva[:, lat:], positions, float(cfg["rope_theta"]))], axis=-1)


def keys_and_values(c, p, n, cfg):
    """Every head's (k_nope (H, T, nope), v (H, T, v_dim)) of the scaled
    latent ``c`` (T, latent)."""
    heads, nope = cfg["num_heads"], cfg["qk_nope_head_dim"]
    kv = (c @ p[n + "kvb_weight"].astype(jnp.float32).T).reshape(
        c.shape[0], heads, nope + cfg["v_head_dim"]).transpose(1, 0, 2)
    return kv[..., :nope], kv[..., nope:]


def queries(a, p, n, positions, cfg):
    """(H, T, nope + rope): the low-rank query, scaled, its rotary part
    rotated."""
    heads, nope = cfg["num_heads"], cfg["qk_nope_head_dim"]
    f32 = lambda name: p[n + name].astype(jnp.float32)
    c_q = rms_norm(a @ f32("qa_weight").T, p[n + "qnorm_gamma"],
                   cfg["rms_eps"])
    q = (c_q @ f32("qb_weight").T) * rho(cfg, "q_lora_rank")
    q = q.reshape(a.shape[0], heads, -1).transpose(1, 0, 2)
    return jnp.concatenate([q[..., :nope], rope(
        q[..., nope:], positions, float(cfg["rope_theta"]))], axis=-1)


def attention(a, p, n, positions, cfg):
    """The attention sub-layer's output, (T, d), of the normed input."""
    heads, nope, rope_dim, v_dim, lat = (cfg[k] for k in (
        "num_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "kv_lora_rank"))
    t = a.shape[0]
    q = queries(a, p, n, positions, cfg)
    row = latent_row(a, p, n, positions, cfg)
    k_nope, v = keys_and_values(row[:, :lat], p, n, cfg)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        row[None, :, lat:], (heads, t, rope_dim))], axis=-1)

    def block(start):
        rows = jnp.minimum(start + jnp.arange(_BLOCK), t - 1)
        s = jnp.einsum("hqd,hsd->hqs", q[:, rows], k) \
            * (nope + rope_dim) ** -0.5
        allowed = positions[None, :] <= positions[rows][:, None]
        weights = jax.nn.softmax(jnp.where(allowed[None], s, -jnp.inf),
                                 axis=-1)
        return jnp.einsum("hqs,hsd->qhd", weights, v)

    n_blocks = -(-t // _BLOCK)
    out = jax.lax.map(block, jnp.arange(n_blocks) * _BLOCK)
    out = out.reshape(n_blocks * _BLOCK, heads * v_dim)[:t]
    return out @ p[n + "proj_weight"].astype(jnp.float32).T


def gated_mlp(h, w_in, w_out):
    gate, up = jnp.split(h @ w_in.astype(jnp.float32).T, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_out.astype(jnp.float32).T


def route(h, router, bias, top_k, scaling):
    """(weights (T, k), indices (T, k)) of every token over ALL the router's
    outputs, the zero-compute experts' among them: chosen on the biased
    softmax score, weighted by the unbiased one, not renormalised."""
    s = jax.nn.softmax(h @ router.astype(jnp.float32).T, axis=-1)
    _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    return scaling * jnp.take_along_axis(s, chosen, axis=-1), chosen


def identity_part(h, weights, chosen, n_experts):
    """What the zero-compute experts add: their weights' sum times h."""
    return jnp.sum(jnp.where(chosen >= n_experts, weights, 0.0), axis=-1,
                   keepdims=True) * h


def moe(h, router, bias, gate, up, down, top_k, scaling, first, n_experts):
    """The HELD experts' part of the routed sum for h (T, d) and the
    identity part: a loop over the stacks' rows (``fori_loop``), row j being
    expert ``first + j``, applied to EVERY token and weighted by that token's
    weight for it, 0 where the expert is not among the token's top-k."""
    weights, chosen = route(h, router, bias, top_k, scaling)
    gate, up, down = jnp.asarray(gate), jnp.asarray(up), jnp.asarray(down)

    def add_expert(j, y):
        w_e = jnp.sum(jnp.where(chosen == first + j, weights, 0.0), axis=-1)
        a = jax.nn.silu(h @ gate[j].astype(jnp.float32)) \
            * (h @ up[j].astype(jnp.float32))
        return y + w_e[:, None] * (a @ down[j].astype(jnp.float32))

    return jax.lax.fori_loop(0, gate.shape[0], add_expert, jnp.zeros_like(h)) \
        + identity_part(h, weights, chosen, n_experts)


def _layer(x, p, layer, pos, cfg):
    eps, m = cfg["rms_eps"], None
    for s in (0, 1):
        n = "layer%d_" % (2 * layer + s)
        x = x + attention(rms_norm(x, p[n + "ln1_gamma"], eps), p, n, pos,
                          cfg)
        h = rms_norm(x, p[n + "ln2_gamma"], eps)
        if s == 0:
            m = moe(h, p[n + "router_weight"], p[n + "router_bias"],
                    p[n + "experts_gate_weight"], p[n + "experts_up_weight"],
                    p[n + "experts_down_weight"], cfg["num_experts_per_tok"],
                    float(cfg["routed_scaling_factor"]),
                    int(cfg.get("local_expert_offset", 0)),
                    cfg["num_experts"])
        x = x + gated_mlp(h, p[n + "mlp_in_weight"], p[n + "mlp_out_weight"])
        if s == JOINS_AFTER:
            x = x + m
    return x


def logits(p, tokens, cfg, last=None):
    """(T, vocab) next-token logits at every position of ``tokens`` (T,);
    with ``last`` only the last ``last`` positions go through the final norm
    and the head, (last, vocab)."""
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0])
        x = p["embed_weight"][tokens.astype(jnp.int32)].astype(jnp.float32)
        for layer in range(cfg["num_layers"]):
            x = _layer(x, p, layer, pos, cfg)
        if last is not None:
            x = x[-last:]
        x = rms_norm(x, p["final_ln_gamma"], cfg["rms_eps"])
        return x @ p["lm_head_weight"].astype(jnp.float32).T


def second_pool_rows(p, tokens, cfg):
    """The first layer's SECOND sublayer's [c | k_r] at every position of
    ``tokens`` (T,), (1, T, latent + rope): what a decoder's pool ``kv_c_1``
    holds of a lane. It reads the first sublayer's attention and MLP and not
    the experts, whose sum joins the stream behind it."""
    eps = cfg["rms_eps"]
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0])
        x = p["embed_weight"][tokens.astype(jnp.int32)].astype(jnp.float32)
        x = x + attention(rms_norm(x, p["layer0_ln1_gamma"], eps), p,
                          "layer0_", pos, cfg)
        x = x + gated_mlp(rms_norm(x, p["layer0_ln2_gamma"], eps),
                          p["layer0_mlp_in_weight"],
                          p["layer0_mlp_out_weight"])
        return latent_row(rms_norm(x, p["layer1_ln1_gamma"], eps), p,
                          "layer1_", pos, cfg)[None]
