"""Plain reference: Laguna (``model_type: laguna``), full forward.

The layer equations as https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json
gives them (window attention of 512 over 72 query heads beside full attention
over 48, three to one, both over the same 8 key/value heads of 128; a gate a
head; YaRN on the full layers' partial rotary alone; 256 routed experts, ten a
token, beside one shared), written from that config because there is no
network here. Straightforward ``jax.numpy``: float32,
``default_matmul_precision("highest")``, a Python loop over the layers, a full
causal forward: no cache, no page, no ring, no band, no kernel, no grouped
matmul, no batching, nothing from the program under test. One call scores one
whole sequence;
prefill and decode through the program's cache (pages AND rings) must agree
with it position by position. The ONE concession to size: attention runs over
blocks of ``_BLOCK`` queries, one after another (a block's scores against ALL
T keys are whole; the (72, T, T) tensor of a 8,208-token check would be
19 GB), which changes no value.

For tokens t_0..t_{T-1} at positions 0..T-1: x = E[t]. Every layer i:
    x = x + attention_i(rms(x; g_1));   x = x + ffn_i(rms(x; g_2))
logits = rms(x; g_final) Whead^T;   rms(x; g) = x / sqrt(mean(x^2) + eps) * g.

``attention`` of layer i on a = rms(x; g_1), H = ``num_heads`` where
``layer_types[i]`` is ``full_attention`` and ``swa_num_heads`` where it is
``sliding_attention``, Hkv = ``num_kv_heads`` in both:
    q = a Wq^T (H heads of dh), k = a Wk^T, v = a Wv^T (Hkv heads of dh)
    q_h = rms(q_h; g_q), k_h = rms(k_h; g_k)     over a head's dh features,
                                                 one gamma the heads share
    q, k = rope_kind(q), rope_kind(k)
    s = q k^T / sqrt(dh), key/value head j serving query heads
        j * H/Hkv .. (j + 1) * H/Hkv - 1; position t attends j <= t, and in a
        window layer t - W < j <= t (W = ``sliding_window``: itself and the
        W - 1 before); plain softmax, no sink
    c_h = softmax(s) v;   g = softplus(a Wg^T)   (T, H), one gate a head
    attention = [g_h c_h]_h Wo^T
  window layer's rope: every feature of a head, half-split pairs
  (i, i + dh/2), inv_freq_i = theta_w^(-2i/dh), theta_w = ``swa_rope_theta``.
  full layer's rope: the FIRST r = ``rotary_dim`` features as a head of r
  (pairs (i, i + r/2)), the other dh - r untouched; YaRN over dim r:
      e_i = theta^(-2i/r), theta = ``rope_theta``;  i = 0 .. r/2 - 1
      corr(n) = r ln(L0 / (2 pi n)) / (2 ln theta),  L0 =
          ``yarn_original_max_position``
      low = max(floor(corr(beta_fast)), 0), high = min(ceil(corr(beta_slow)),
          r - 1)
      ramp_i = clip((i - low) / (high - low), 0, 1)
      inv_freq_i = e_i / factor * ramp_i + e_i * (1 - ramp_i)
  the same at every position; cos and sin are multiplied by
  ``attention_factor``, on q and on k. Keys are cached rotated (and scaled).

``ffn``, in the first ``first_dense_layers`` layers: W2(silu(W1 m) * (W3 m)).
After them, with m = rms(x; g_2):
    s = sigmoid(m Wr^T) over ALL E experts, float32
    S = the top-k of s + b         b = a selection bias (E,); ties: the lower
                                   expert index
    p_e = scaling * s_e / (sum_{e in S} s_e + 1e-20)     from s, NOT s + b
    ffn = sum_{e in S, e HELD} p_e W2_e(silu(W1_e m) * (W3_e m)) + shared(m)
  ``shared`` is one more gated SiLU MLP every token takes, ungated. HELD are
  experts ``local_expert_offset`` .. + ``num_local_experts`` - 1: the share
  of one chip of an expert-parallel deployment (the stacks have that many
  rows). What the absent experts would have added is left out, here as in the
  program, and that partial result goes on to the next layer.

Departures from the published model, and points the config leaves open, each
a possible departure (the configuration's ``assumed`` says why each):
- depth, the experts held and the vocabulary are cut;
- the gate's nonlinearity is softplus and its input the layer's normed input
  (``gating: per-head`` gives neither);
- a q/k norm a head, gamma of dh shared by the heads (no key in the config;
  the convention of the family its key names come from);
- sigmoid scoring with a selection bias, weights from the unbiased score
  (``moe_routed_scaling_factor`` 2.5 over renormalised weights beside a
  shared expert is that recipe); the shared expert ungated;
- the window holds W keys WITH the token itself;
- the rotated features are the FIRST r, paired half-split; YaRN is static
  with ``truncate`` on;
- grouped attention pairs key/value head j with query heads
  j * H/Hkv .. (j + 1) * H/Hkv - 1 (``repeat_kv``).
Layout choices that change no function: q, k and v live in ONE fused matrix
(rows q, then k, then v, each head-major); an MLP's gate (W1) and up (W3) rows
live in ONE matrix (gate rows first); an expert's matrices are stored
(in, out), stacked over the HELD experts.

Checkpoint layout (the only thing shared with the program): ``embed_weight``,
``lm_head_weight`` (vocab, d); ``final_ln_gamma`` (d,); per layer ``layer<i>_``
``ln1_gamma``, ``ln2_gamma`` (d,), ``qkv_weight`` ((H + 2 Hkv) * dh, d),
``qnorm_gamma``, ``knorm_gamma`` (dh,), ``gate_weight`` (H, d),
``proj_weight`` (d, H * dh); a dense layer ``mlp_in_weight`` (2F, d),
``mlp_out_weight`` (d, F); an expert layer ``router_weight`` (E, d),
``router_bias`` (E,), ``experts_gate_weight`` / ``experts_up_weight`` (held,
d, Fe), ``experts_down_weight`` (held, Fe, d), ``shared_in_weight`` (2 Fs,
d), ``shared_out_weight`` (d, Fs). Linear weights are (out, in) except the
experts'. Weights may be stored in a narrower type: each matrix is upcast to
float32 where it is used (an expert's as the loop reaches that expert), so the
float32 copies never exist side by side.
"""
import math

import jax
import jax.numpy as jnp

_BLOCK = 128    # queries whose scores against all T keys are made at once


def rms_norm(x, gamma, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gamma.astype(jnp.float32)


def yarn_inv_freq(cfg):
    """The full layers' r / 2 inverse frequencies, a Python list."""
    r, theta = int(cfg["rotary_dim"]), float(cfg["rope_theta"])
    plain = [theta ** (-2.0 * i / r) for i in range(r // 2)]
    factor = float(cfg.get("yarn_factor", 0.0))
    if not factor:
        return plain
    corr = lambda turns: r * math.log(
        float(cfg["yarn_original_max_position"]) / (turns * 2 * math.pi)) \
        / (2 * math.log(theta))
    low = max(math.floor(corr(float(cfg.get("yarn_beta_fast", 32.0)))), 0)
    high = min(math.ceil(corr(float(cfg.get("yarn_beta_slow", 1.0)))), r - 1)
    ramp = [min(max((i - low) / (high - low), 0.0), 1.0)
            for i in range(r // 2)]
    return [e / factor * t + e * (1 - t) for e, t in zip(plain, ramp)]


def rope(x, positions, inv_freq, factor=1.0):
    """Rotary positions on the first ``2 * len(inv_freq)`` features of x
    (heads, T, dh) at ``positions`` (T,), half-split pairs inside those,
    cosine and sine times ``factor``; the rest untouched."""
    r = 2 * len(inv_freq)
    angle = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    # (the two halves as an axis of their own, not two pieces side by side in
    # the minor dimension: the chip's compiler aborts on the unaligned
    # update a concatenation at feature 64 of 128 becomes, PERF.md section 6)
    halves = x[..., :r].reshape(x.shape[:-1] + (2, r // 2))
    x1, x2 = halves[..., 0, :], halves[..., 1, :]
    turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                       axis=-2).reshape(x.shape[:-1] + (r,))
    if r == x.shape[-1]:
        return turned
    return jnp.concatenate([turned, x[..., r:]], axis=-1)


def heads_of(cfg, windowed):
    return int(cfg["swa_num_heads" if windowed else "num_heads"])


def qk_norm(x, gamma, eps):
    """The norm of each q or k head (heads, T, dh) over its dh features."""
    return rms_norm(x, gamma, eps)


def rotary(x, positions, cfg, windowed):
    """The rotation of q or k (heads, T, dh) by the layer's kind: all dh
    features plainly at ``swa_rope_theta`` in a window layer; the first
    ``rotary_dim`` under YaRN, scaled by ``attention_factor``, in a full
    one."""
    if windowed:
        dh, theta = x.shape[-1], float(cfg["swa_rope_theta"])
        return rope(x, positions, [theta ** (-2.0 * i / dh)
                                   for i in range(dh // 2)])
    factor = float(cfg.get("attention_factor", 1.0)) \
        if cfg.get("yarn_factor") else 1.0
    return rope(x, positions, yarn_inv_freq(cfg), factor)


def head_gate(a, p, n):
    """(T, H): one gate a head, from the layer's normed input."""
    return jax.nn.softplus(a @ p[n + "gate_weight"].astype(jnp.float32).T)


def keys_and_values(a, p, n, positions, cfg, windowed):
    """(q (H, T, dh) normed and rotated, k (Hkv, T, dh) normed and rotated,
    v (Hkv, T, dh)) of one layer: what the scores are made of, and what a
    cache keeps of k and v."""
    hq, hkv, dh = heads_of(cfg, windowed), int(cfg["num_kv_heads"]), \
        int(cfg["head_dim"])
    t, eps = a.shape[0], cfg["rms_eps"]
    qkv = a @ p[n + "qkv_weight"].astype(jnp.float32).T
    heads = lambda x, count: x.reshape(t, count, dh).transpose(1, 0, 2)
    q = qk_norm(heads(qkv[:, :hq * dh], hq), p[n + "qnorm_gamma"], eps)
    k = qk_norm(heads(qkv[:, hq * dh:(hq + hkv) * dh], hkv),
                p[n + "knorm_gamma"], eps)
    v = heads(qkv[:, (hq + hkv) * dh:], hkv)
    return rotary(q, positions, cfg, windowed), \
        rotary(k, positions, cfg, windowed), v


def attention(a, p, n, positions, cfg, windowed):
    """The attention sub-layer's output, (T, d)."""
    hq, dh = heads_of(cfg, windowed), int(cfg["head_dim"])
    t = a.shape[0]
    q, k, v = keys_and_values(a, p, n, positions, cfg, windowed)
    k, v = (jnp.repeat(x, hq // x.shape[0], axis=0) for x in (k, v))
    window = int(cfg["sliding_window"])

    def block(start):
        rows = jnp.minimum(start + jnp.arange(_BLOCK), t - 1)   # the last
        ahead = positions[rows][:, None] - positions[None, :]   # repeats one
        seen = ahead >= 0
        if windowed:
            seen &= ahead < window
        s = jnp.einsum("hqd,hsd->hqs", q[:, rows], k) * dh ** -0.5
        weights = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,hsd->qhd", weights, v)

    n_blocks = -(-t // _BLOCK)
    out = jax.lax.map(block, jnp.arange(n_blocks) * _BLOCK)
    out = out.reshape(n_blocks * _BLOCK, hq, dh)[:t]
    return (out * head_gate(a, p, n)[:, :, None]).reshape(t, hq * dh) \
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


def _windowed(cfg, i):
    return cfg["layer_types"][i] == "sliding_attention"


def _layer(x, p, i, pos, cfg):
    n, eps = "layer%d_" % i, cfg["rms_eps"]
    x = x + attention(rms_norm(x, p[n + "ln1_gamma"], eps), p, n, pos, cfg,
                      _windowed(cfg, i))
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


def first_window_keys(p, tokens, cfg):
    """The FIRST window layer's normed and rotated keys at every position of
    ``tokens`` (T,), (Hkv, T, dh): what a decoder's ring for that layer holds
    of the positions it keeps, position p at slot p mod W."""
    first = list(cfg["layer_types"]).index("sliding_attention")
    with jax.default_matmul_precision("highest"):
        pos, x = _upto(p, tokens, cfg, first)
        n = "layer%d_" % first
        return keys_and_values(
            rms_norm(x, p[n + "ln1_gamma"], cfg["rms_eps"]), p, n, pos, cfg,
            True)[1]
