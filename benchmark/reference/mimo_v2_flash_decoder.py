"""Plain reference: MiMo-V2-Flash (``model_type: mimo_v2_flash``), full forward.

The layer equations as https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/config.json
and the model's public description give them (window attention of 128 with a
learnable sink bias beside global grouped-query attention, five to one; keys
of 192 over values of 128; 256 sigmoid-routed experts, eight a token, none
shared), written from those because there is no network here.
Straightforward ``jax.numpy``: float32, ``default_matmul_precision("highest")``,
a Python loop over the layers, a full causal forward with the FULL T x T
scores in every layer: no cache, no page, no ring, no band of blocks, no
grouped matmul, no batching, nothing from ``mxnet_tpu``. One call scores one
whole sequence; prefill and decode through the program's cache (pages AND
rings) must agree with it position by position.

For tokens t_0..t_{T-1} at positions 0..T-1: x = E[t]. Every layer i:
    x = x + attention_i(rms(x; g_1));   x = x + ffn_i(rms(x; g_2))
logits = rms(x; g_final) Whead^T;   rms(x; g) = x / sqrt(mean(x^2) + eps) * g.

``attention``, both kinds: q = h Wq^T (Hq heads of dk), k = h Wk^T (Hkv heads
of dk), v = a * h Wv^T (Hkv heads of dv < dk; a = ``attention_value_scale``),
no bias, no q/k norm. Rotary positions on the FIRST r = ``rotary_dim``
features of each q and k head: pairs (i, i + r/2), inv_freq_i =
theta^(-2i/r); the other dk - r features pass through. Scores q k^T / sqrt(dk),
each key/value head serving Hq / Hkv consecutive query heads; then Wo
(Hq * dv -> d).
  full layer (``hybrid_layer_pattern[i]`` 0): Hkv = ``num_kv_heads``, theta =
  ``rope_theta``, position t attends every j <= t, plain softmax.
  window layer (1): Hkv = ``swa_num_kv_heads``, theta = ``swa_rope_theta``,
  position t attends t - W < j <= t (W = ``sliding_window``: itself and the
  W - 1 before), and a learnable sink b_h, one logit a query head, is one
  more COLUMN of the scores: p = softmax([s | b_h]); the column is dropped
  after the softmax, so the sink takes weight and gives no value.

``ffn``, where ``moe_layer_freq[i]`` is 0: W2(silu(W1 h) * (W3 h)). Else, no
shared expert:
    s = sigmoid(h Wr^T) over ALL E experts, float32
    S = the top-k of s + b          b = e_score_correction_bias (noaux_tc,
                                    one group); ties: the lower expert index
    p_e = scaling * s_e / (sum_{e in S} s_e + 1e-20)      from s, NOT s + b
    ffn = sum_{e in S, e HELD} p_e W2_e(silu(W1_e h) * (W3_e h))
  HELD are experts ``local_expert_offset`` .. + ``num_local_experts`` - 1:
  the share of one chip of an expert-parallel deployment (the stacks have
  that many rows). What the absent experts would have added is left out,
  here as in the program, and that partial result goes on to the next layer.

Departures from the published model, and points I could not check against the
source, each a possible departure:
- depth, the experts held and the vocabulary are cut (the configuration's
  file says how); the 3 multi-token-prediction layers and the V2.5 towers are
  left out (the config gives them no sizes);
- the 0.707 scales the VALUES before they are attended (and cached), which
  equals scaling the context before Wo;
- the window holds W keys WITH the token itself;
- r = 64: ``partial_rotary_factor`` 0.334 x 192 = 64.1, rounded down to an
  even count; the rotated features are the FIRST r, paired half-split
  (``rotate_half``);
- the sink joins the softmax's denominator only (an extra logit, no value);
- the chosen weights are renormalised with + 1e-20 (``norm_topk_prob``), and
  ``routed_scaling_factor: null`` is 1;
- grouped attention pairs key/value head j with query heads
  j * Hq/Hkv .. (j + 1) * Hq/Hkv - 1 (``repeat_kv``).
Layout choices that change no function: q, k and v live in ONE fused matrix
(rows q, then k, then v, each head-major); an MLP's gate (W1) and up (W3) rows
live in ONE matrix (gate rows first); an expert's matrices are stored
(in, out), stacked over the HELD experts.

Checkpoint layout (the only thing shared with the program): ``embed_weight``,
``lm_head_weight`` (vocab, d); ``final_ln_gamma`` (d,); per layer ``layer<i>_``
``ln1_gamma``, ``ln2_gamma`` (d,), ``qkv_weight`` ((Hq + Hkv) * dk + Hkv * dv,
d), ``proj_weight`` (d, Hq * dv), a window layer ``sink_bias`` (Hq,); a dense
layer ``mlp_in_weight`` (2F, d), ``mlp_out_weight`` (d, F); an expert layer
``router_weight`` (E, d), ``router_bias`` (E,), ``experts_gate_weight`` /
``experts_up_weight`` (held, d, Fe), ``experts_down_weight`` (held, Fe, d).
Linear weights are (out, in) except the experts'. Weights may be stored in a
narrower type: each matrix is upcast to float32 where it is used (an expert's
as the loop reaches that expert), so the float32 copies never exist side by
side.
"""
import jax
import jax.numpy as jnp


def rms_norm(x, gamma, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gamma.astype(jnp.float32)


def rope(x, positions, theta, r):
    """Rotary positions on the first ``r`` features of x (heads, T, dk) at
    ``positions`` (T,), half-split pairs inside those; the rest untouched."""
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., r:]], axis=-1)


def keys_and_values(h, p, n, positions, cfg, windowed):
    """(q (Hq, T, dk) rotated, k (Hkv, T, dk) rotated, v (Hkv, T, dv)
    scaled) of one layer: what the scores are made of, and what a cache
    keeps of k and v."""
    hq, dk, dv = cfg["num_heads"], cfg["head_dim"], cfg["v_head_dim"]
    hkv = cfg["swa_num_kv_heads" if windowed else "num_kv_heads"]
    theta = float(cfg["swa_rope_theta" if windowed else "rope_theta"])
    t = h.shape[0]
    qkv = h @ p[n + "qkv_weight"].astype(jnp.float32).T
    heads = lambda a, count, d: a.reshape(t, count, d).transpose(1, 0, 2)
    q = heads(qkv[:, :hq * dk], hq, dk)
    k = heads(qkv[:, hq * dk:(hq + hkv) * dk], hkv, dk)
    v = heads(qkv[:, (hq + hkv) * dk:], hkv, dv)
    r = int(cfg["rotary_dim"])
    return rope(q, positions, theta, r), rope(k, positions, theta, r), \
        v * float(cfg["attention_value_scale"])


def attention(h, p, n, positions, cfg, windowed):
    hq, dk, dv = cfg["num_heads"], cfg["head_dim"], cfg["v_head_dim"]
    t = h.shape[0]
    q, k, v = keys_and_values(h, p, n, positions, cfg, windowed)
    k, v = (jnp.repeat(a, hq // a.shape[0], axis=0) for a in (k, v))
    scores = jnp.einsum("htd,hsd->hts", q, k) * dk ** -0.5
    seen = jnp.tril(jnp.ones((t, t), bool))
    if windowed:
        seen &= ~jnp.tril(jnp.ones((t, t), bool),
                          k=-int(cfg["sliding_window"]))
        sink = jnp.broadcast_to(
            p[n + "sink_bias"].astype(jnp.float32)[:, None, None], (hq, t, 1))
        weights = jax.nn.softmax(jnp.concatenate(
            [jnp.where(seen, scores, -jnp.inf), sink], axis=-1),
            axis=-1)[..., :-1]
    else:
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("hts,hsd->htd", weights, v)
    return att.transpose(1, 0, 2).reshape(t, hq * dv) \
        @ p[n + "proj_weight"].astype(jnp.float32).T


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
                      bool(cfg["hybrid_layer_pattern"][i]))
    h = rms_norm(x, p[n + "ln2_gamma"], eps)
    if not cfg["moe_layer_freq"][i]:
        return x + gated_mlp(h, p[n + "mlp_in_weight"],
                             p[n + "mlp_out_weight"])
    return x + moe(h, p[n + "router_weight"], p[n + "router_bias"],
                   p[n + "experts_gate_weight"], p[n + "experts_up_weight"],
                   p[n + "experts_down_weight"], cfg["num_experts_per_tok"],
                   float(cfg.get("routed_scaling_factor", 1.0)),
                   int(cfg.get("local_expert_offset", 0)))


def logits(p, tokens, cfg, last=None):
    """(T, vocab) next-token logits at every position of ``tokens`` (T,);
    with ``last`` only the last ``last`` positions go through the final norm
    and the head, (last, vocab)."""
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0])
        x = p["embed_weight"][tokens.astype(jnp.int32)].astype(jnp.float32)
        for i in range(len(cfg["hybrid_layer_pattern"])):
            x = _layer(x, p, i, pos, cfg)
        if last is not None:
            x = x[-last:]
        x = rms_norm(x, p["final_ln_gamma"], cfg["rms_eps"])
        return x @ p["lm_head_weight"].astype(jnp.float32).T


def first_window_keys(p, tokens, cfg):
    """The FIRST window layer's rotated keys at every position of ``tokens``
    (T,), (Hkv, T, dk): what a decoder's ring for that layer holds of the
    positions it keeps, position p at slot p mod W."""
    first = list(cfg["hybrid_layer_pattern"]).index(1)
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0])
        x = p["embed_weight"][tokens.astype(jnp.int32)].astype(jnp.float32)
        for i in range(first):
            x = _layer(x, p, i, pos, cfg)
        n = "layer%d_" % first
        return keys_and_values(
            rms_norm(x, p[n + "ln1_gamma"], cfg["rms_eps"]), p, n, pos, cfg,
            True)[1]
