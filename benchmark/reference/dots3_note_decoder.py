"""Plain reference: dots3-note-prev (``model_type: dots3_note``), the language
model, full forward.

The layer equations as the keys of
https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json give
them, read by the conventions of the two public families whose keys they are
(DeepSeek-V3.2: latent attention with a query-side low rank and a lightning
indexer that picks ``index_topk`` keys a query; LongCat-Flash:
``mla_scale_q_lora`` / ``mla_scale_kv_lora``), written from knowledge of
those because there is no network here. Straightforward ``jax.numpy``:
float32, ``default_matmul_precision("highest")``, NON-absorbed (every head's
key and value are made of the latent), a Python loop over the layers, no
cache, no page, no ring, no kernel, nothing from ``mxnet_tpu``. One call
scores one whole sequence; prefill and decode through the program's caches
(two pools a full layer, a ring a window layer) must agree with it position
by position. The ONE concession to size: attention runs over blocks of
``_BLOCK`` queries, one after another (a block's scores against ALL T keys are
whole; the (heads, T, T) tensor of a 8,208-token check would be 34 GB), which
changes no value.

For tokens t_0..t_{T-1} at positions 0..T-1: x = E[t]. Every layer i, with
h = rms(x; g_1) and the sizes of its KIND (``layer_types[i]``; a window layer
reads the ``swa_`` keys):
    c_q = rho_q * rms(h Wqa^T; g_q)                   q_lora_rank
    q   = c_q Wqb^T              H heads of [q_nope (nope) | q_rope (rope)]
    [c | k_r] = h Wkva^T         latent (kv_lora_rank) + ONE rotary key
    c   = rho_kv * rms(c; g_kv)
    q_rope, k_r = rope(q_rope, pos), rope(k_r, pos)   interleaved pairs
        (2i, 2i + 1), inv_freq_i = theta^(-2i/rope), theta the KIND's
    [k_nope_j | v_j] = c Wkvb_j^T                     nope + v_dim a head
    s_j[t, u] = (q_nope_j[t] . k_nope_j[u] + q_rope_j[t] . k_r[u])
                / sqrt(nope + rope)
  full layer: the indexer, fed by the SAME c_q:
    qI = c_q Wiq^T               Hi heads of di
    kI = layernorm(h Wik^T; g_I, b_I)                 ONE head of di, eps 1e-5
    the first ``rope`` features of qI and kI rotated at theta, half-split
        pairs (i, i + rope/2); the other di - rope pass through
    w  = h Wiw^T / sqrt(Hi * di)                      one weight a head
    I[t, u] = sum_j w[t, j] * relu(qI_j[t] . kI[u])
    S_t = the ``index_topk`` positions u <= t of largest I[t, u]
          (``jax.lax.top_k``: ties go to the lower position), all of them
          while t < index_topk
    o_j[t] = sum_{u in S_t} softmax_{u in S_t}(s_j[t, u]) v_j[u]
  window layer: no indexer; S_t = {u : t - W < u <= t}, W =
    ``sliding_window`` keys WITH the token itself.
    g = sigmoid(h Wg^T)          one gate a head
    x = x + concat_j(g_j o_j) Wo^T
    h = rms(x; g_2)
    the first ``first_dense_layers`` layers: x = x + Wd(silu(Wg h) * (Wu h))
    the others (``model_type: deepseek_v3``'s router, one group):
        s = sigmoid(h Wr^T) over ALL E experts, float32
        S = the top-k of s + b       b = e_score_correction_bias; ties: the
                                     lower expert index
        p_e = scaling * s_e / (sum_{e in S} s_e + 1e-20)    from s, NOT s + b
        x = x + sum_{e in S, e HELD} p_e Wd_e(silu(Wg_e h) * (Wu_e h))
              + shared(h)            one gated SiLU MLP of
                                     num_shared_experts * moe_ffn_dim
      HELD are experts ``local_expert_offset`` .. + ``num_local_experts`` - 1:
      the share of one chip of an expert-parallel deployment (the stacks
      have that many rows). What the absent experts would have added is left
      out, here as in the program, and that partial result goes on.
logits = rms(x; g_f) Whead^T;  rms(x; g) = x / sqrt(mean(x^2) + eps) * g.

Departures from the published model, and points the config's keys leave
open, each a possible departure (the configuration's file lists them under
``assumed``):
- depth, the experts held and the vocabulary are cut (the configuration's
  file says how); the vision and audio towers and the multi-token-prediction
  layers are left out (the catalog's config gives them no sizes);
- ``apply_mla_qkv_lora_rescale``: rho_q = sqrt(d / q_lora_rank), rho_kv =
  sqrt(d / kv_lora_rank), each kind's own ranks, on the NORMED latents
  (LongCat-Flash's definition);
- ``attention_gate_type: headwise``: the gate reads the layer's normed input
  h and multiplies a head's context before Wo;
- the indexer: no Hadamard rotation of qI and kI (an orthogonal map on both
  leaves every qI . kI as it is), no float8 keys (a storage precision the
  config does not state), its rotary pairs half-split and FIRST in a head,
  its head weights scaled by Hi^-1/2 * di^-1/2;
- ``sliding_window_size`` 513 = 513 keys with the token itself;
- ``n_group = topk_group = 1`` (the config has neither key).
Layout choices that change no function: an MLP's gate and up rows live in ONE
matrix (gate rows first); an expert's matrices are stored (in, out), stacked
over the HELD experts; the rotated pairs of q_rope and k_r stay where they
are (``_deepseek_v3_decoder`` says why that is the same function).

Checkpoint layout (the only thing shared with the program): ``embed_weight``,
``lm_head_weight`` (vocab, d); ``final_ln_gamma`` (d,); per layer ``layer<i>_``
``ln1_gamma``, ``ln2_gamma`` (d,), ``qa_weight`` (q_rank, d), ``qnorm_gamma``
(q_rank,), ``qb_weight`` (H*(nope+rope), q_rank) head-major, ``kva_weight``
(latent+rope, d), ``kvnorm_gamma`` (latent,), ``kvb_weight`` (H*(nope+v_dim),
latent) head-major, ``gate_weight`` (H, d), ``proj_weight`` (d, H*v_dim); a
full layer ``iq_weight`` (Hi*di, q_rank) head-major, ``ik_weight`` (di, d),
``iknorm_gamma``, ``iknorm_beta`` (di,), ``iw_weight`` (Hi, d); a dense layer
``mlp_in_weight`` (2F, d), ``mlp_out_weight`` (d, F); an expert layer
``router_weight`` (E, d), ``router_bias`` (E,), ``experts_gate_weight`` /
``experts_up_weight`` (held, d, Fe), ``experts_down_weight`` (held, Fe, d),
``shared_in_weight`` (2*S*Fe, d), ``shared_out_weight`` (d, S*Fe). Linear
weights are (out, in) except the experts'. Weights may be stored in a
narrower type: each matrix is upcast to float32 where it is used.
"""
import jax
import jax.numpy as jnp

_BLOCK = 128    # queries scored at once (module docstring)


def rms_norm(x, gamma, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gamma.astype(jnp.float32)


def layer_norm(x, gamma, beta, eps=1e-5):
    cent = x - jnp.mean(x, axis=-1, keepdims=True)
    return cent / jnp.sqrt(jnp.mean(jnp.square(cent), axis=-1, keepdims=True)
                           + eps) * gamma.astype(jnp.float32) \
        + beta.astype(jnp.float32)


def rope_interleaved(x, positions, theta):
    """Rotary positions over interleaved pairs on x (..., T, dh)."""
    dh = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def rope_half(x, positions, theta, r):
    """Rotary positions on the first ``r`` features of x (..., T, d),
    half-split pairs inside those; the rest untouched."""
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., r:]], axis=-1)


def geometry(cfg, kind):
    """The latent geometry of a layer of ``kind``: a window layer reads the
    ``swa_`` keys (one left out is the full layers')."""
    own = lambda key: cfg.get("swa_" + key) or cfg[key] \
        if kind == "sliding_attention" else cfg[key]
    g = dict(heads=own("num_heads"), q_rank=own("q_lora_rank"),
             latent=own("kv_lora_rank"), nope=own("qk_nope_head_dim"),
             rope=own("qk_rope_head_dim"), v_dim=own("v_head_dim"),
             theta=float(own("rope_theta")))
    rescale = cfg.get("lora_rescale", True)
    g["rho_q"] = (cfg["model_dim"] / g["q_rank"]) ** 0.5 if rescale else 1.0
    g["rho_kv"] = (cfg["model_dim"] / g["latent"]) ** 0.5 if rescale else 1.0
    return g


def latents(h, p, n, positions, cfg, g):
    """(c_q (T, q_rank), the row a cache keeps [c | k_r] (T, latent + rope):
    the scaled normed latent beside the rotated shared key)."""
    f32 = lambda name: p[n + name].astype(jnp.float32)
    eps, lat = cfg["rms_eps"], g["latent"]
    c_q = g["rho_q"] * rms_norm(h @ f32("qa_weight").T, p[n + "qnorm_gamma"],
                                eps)
    kva = h @ f32("kva_weight").T
    c = g["rho_kv"] * rms_norm(kva[:, :lat], p[n + "kvnorm_gamma"], eps)
    return c_q, jnp.concatenate(
        [c, rope_interleaved(kva[:, lat:], positions, g["theta"])], axis=-1)


def index_scores(h, c_q, p, n, positions, cfg, g, rows):
    """The indexer's I[t, u] (rows, T) float32 for queries ``rows`` (a slice
    of positions) against every key."""
    f32 = lambda name: p[n + name].astype(jnp.float32)
    hi, di, t = cfg["index_n_heads"], cfg["index_head_dim"], h.shape[0]
    k_i = rope_half(layer_norm(h @ f32("ik_weight").T, p[n + "iknorm_gamma"],
                               p[n + "iknorm_beta"]),
                    positions, g["theta"], g["rope"])
    q_i = (c_q[rows] @ f32("iq_weight").T).reshape(-1, hi, di)
    q_i = rope_half(q_i.transpose(1, 0, 2), positions[rows], g["theta"],
                    g["rope"])                              # (Hi, rows, di)
    w = h[rows] @ f32("iw_weight").T * (hi * di) ** -0.5    # (rows, Hi)
    return jnp.einsum("hqs,qh->qs", jax.nn.relu(
        jnp.einsum("hqd,sd->hqs", q_i, k_i)), w)


def selected(scores, positions, rows, topk):
    """The keys each query of ``rows`` may attend under a learned selection,
    (rows, T) bool: the ``topk`` causal keys of largest ``scores`` (rows, T),
    every causal key while there are no more."""
    causal = positions[None, :] <= positions[rows][:, None]
    k = min(topk, scores.shape[1])
    _, at = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), k)
    chosen = jnp.zeros(causal.shape, bool).at[
        jnp.arange(at.shape[0])[:, None], at].set(True)
    return chosen & causal


def head_gate(h, p, n):
    """(T, H): one gate a head, from the layer's normed input."""
    return jax.nn.sigmoid(h @ p[n + "gate_weight"].astype(jnp.float32).T)


def attention(h, p, n, positions, cfg, kind):
    """The attention sub-layer's output, (T, d)."""
    g = geometry(cfg, kind)
    heads, nope, rope, v_dim = (g[k] for k in ("heads", "nope", "rope",
                                               "v_dim"))
    f32 = lambda name: p[n + name].astype(jnp.float32)
    t = h.shape[0]
    c_q, row = latents(h, p, n, positions, cfg, g)
    q = (c_q @ f32("qb_weight").T).reshape(t, heads, nope + rope)
    q = q.transpose(1, 0, 2)
    q = jnp.concatenate([q[..., :nope], rope_interleaved(
        q[..., nope:], positions, g["theta"])], axis=-1)
    kv = (row[:, :g["latent"]] @ f32("kvb_weight").T).reshape(
        t, heads, nope + v_dim).transpose(1, 0, 2)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        row[None, :, g["latent"]:], (heads, t, rope))], axis=-1)
    v = kv[..., nope:]
    window = int(cfg["sliding_window"])

    def block(start):
        rows = start + jnp.arange(_BLOCK)
        rows = jnp.minimum(rows, t - 1)     # the last block repeats a row
        if kind == "sliding_attention":
            ahead = positions[rows][:, None] - positions[None, :]
            allowed = (ahead >= 0) & (ahead < window)
        else:
            allowed = selected(
                index_scores(h, c_q, p, n, positions, cfg, g, rows),
                positions, rows, int(cfg["index_topk"]))
        s = jnp.einsum("hqd,hsd->hqs", q[:, rows], k) \
            * (nope + rope) ** -0.5
        weights = jax.nn.softmax(jnp.where(allowed[None], s, -jnp.inf),
                                 axis=-1)
        return jnp.einsum("hqs,hsd->qhd", weights, v)

    n_blocks = -(-t // _BLOCK)
    out = jax.lax.map(block, jnp.arange(n_blocks) * _BLOCK)
    out = out.reshape(n_blocks * _BLOCK, heads, v_dim)[:t]
    out = out * head_gate(h, p, n)[:, :, None]
    return out.reshape(t, heads * v_dim) @ f32("proj_weight").T


def gated_mlp(h, w_in, w_out):
    gate, up = jnp.split(h @ w_in.astype(jnp.float32).T, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_out.astype(jnp.float32).T


def route(h, router, bias, top_k, scaling):
    """(weights (T, k), expert indices (T, k)) of every token over ALL the
    experts: chosen on the biased score, weighted by the unbiased one."""
    s = jax.nn.sigmoid(h @ router.astype(jnp.float32).T)
    _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    return scaling * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20), chosen


def moe(h, router, bias, gate, up, down, top_k, scaling, first):
    """The HELD experts' part of the routed sum for h (T, d): a loop over
    the stacks' rows (``fori_loop``), row j being expert ``first + j``,
    applied to EVERY token and weighted by that token's weight for it, 0
    where the expert is not among the token's top-k."""
    weights, chosen = route(h, router, bias, top_k, scaling)
    gate, up, down = jnp.asarray(gate), jnp.asarray(up), jnp.asarray(down)

    def add_expert(j, y):
        w_e = jnp.sum(jnp.where(chosen == first + j, weights, 0.0), axis=-1)
        a = jax.nn.silu(h @ gate[j].astype(jnp.float32)) \
            * (h @ up[j].astype(jnp.float32))
        return y + w_e[:, None] * (a @ down[j].astype(jnp.float32))

    return jax.lax.fori_loop(0, gate.shape[0], add_expert, jnp.zeros_like(h))


def _layer(x, p, i, pos, cfg):
    n, eps = "layer%d_" % i, cfg["rms_eps"]
    x = x + attention(rms_norm(x, p[n + "ln1_gamma"], eps), p, n, pos, cfg,
                      cfg["layer_types"][i])
    h = rms_norm(x, p[n + "ln2_gamma"], eps)
    if i < cfg["first_dense_layers"]:
        return x + gated_mlp(h, p[n + "mlp_in_weight"],
                             p[n + "mlp_out_weight"])
    return x + moe(h, p[n + "router_weight"], p[n + "router_bias"],
                   p[n + "experts_gate_weight"], p[n + "experts_up_weight"],
                   p[n + "experts_down_weight"], cfg["num_experts_per_tok"],
                   float(cfg.get("routed_scaling_factor", 1.0)),
                   int(cfg.get("local_expert_offset", 0))) \
        + gated_mlp(h, p[n + "shared_in_weight"], p[n + "shared_out_weight"])


def _upto(p, tokens, cfg, layer):
    """(positions, the residual stream entering ``layer``)."""
    pos = jnp.arange(tokens.shape[0])
    x = p["embed_weight"][tokens.astype(jnp.int32)].astype(jnp.float32)
    for i in range(layer):
        x = _layer(x, p, i, pos, cfg)
    return pos, x


def logits(p, tokens, cfg, last=None):
    """(T, vocab) next-token logits at every position of ``tokens`` (T,);
    with ``last`` only the last ``last`` positions go through the final norm
    and the head, (last, vocab)."""
    with jax.default_matmul_precision("highest"):
        _, x = _upto(p, tokens, cfg, len(cfg["layer_types"]))
        if last is not None:
            x = x[-last:]
        x = rms_norm(x, p["final_ln_gamma"], cfg["rms_eps"])
        return x @ p["lm_head_weight"].astype(jnp.float32).T


def first_window_rows(p, tokens, cfg):
    """The FIRST window layer's [c | k_r] at every position of ``tokens``
    (T,), (1, T, latent + rope): what a decoder's ring for that layer holds
    of the positions it keeps, position p at slot p mod W."""
    first = list(cfg["layer_types"]).index("sliding_attention")
    with jax.default_matmul_precision("highest"):
        pos, x = _upto(p, tokens, cfg, first)
        n = "layer%d_" % first
        return latents(rms_norm(x, p[n + "ln1_gamma"], cfg["rms_eps"]), p, n,
                       pos, cfg, geometry(cfg, "sliding_attention"))[1][None]


def first_selected(p, tokens, cfg, rows):
    """The FIRST full layer's selection for the queries at positions
    ``rows`` (R,) of ``tokens`` (T,): (R, T) bool, the keys each attends."""
    first = list(cfg["layer_types"]).index("full_attention")
    with jax.default_matmul_precision("highest"):
        pos, x = _upto(p, tokens, cfg, first)
        n = "layer%d_" % first
        h = rms_norm(x, p[n + "ln1_gamma"], cfg["rms_eps"])
        g = geometry(cfg, "full_attention")
        c_q, _ = latents(h, p, n, pos, cfg, g)
        rows = jnp.asarray(rows)
        return selected(index_scores(h, c_q, p, n, pos, cfg, g, rows), pos,
                        rows, int(cfg["index_topk"]))
