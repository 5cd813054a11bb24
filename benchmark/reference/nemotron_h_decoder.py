"""Plain reference: NVIDIA Nemotron-3-Nano (``model_type: nemotron_h``), full
forward, the recurrence run SEQUENTIALLY.

The layer equations of the public ``transformers`` implementation
(``NemotronHBlock``: ``NemotronHMamba2Mixer`` with ``MambaRMSNormGated``,
``NemotronHAttention``, ``NemotronHMOE`` with its ``NemotronHTopkRouter``),
written from knowledge of it because there is no network here; the sizes are
those of
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json.
Straightforward ``jax.numpy``: float32, ``default_matmul_precision("highest")``,
a Python loop over the blocks, the recurrence one position after the other
(no chunks), every HELD expert computed densely for every token and masked
(no sort, no grouped product), the full T x T scores, no cache, no batching,
nothing from ``mxnet_tpu``. One call scores every position of one whole
sequence; prefill and decode through the program's cache (KV pages AND
recurrent state) must agree with it position by position.

For tokens t_0..t_{T-1}: x = E[t] (no position table, no rotation). Every
block i is ONE mixer, named by ``layer_types[i]`` (the letters of
``hybrid_override_pattern``: M "mamba", E "moe", * "attention"):
    x = x + mixer_i(rms(x; g_i))
logits = rms(x; g_final) Whead^T;   rms(x; g) = x / sqrt(mean(x^2) + eps) * g.

``mamba`` on u (T, d), H heads of P, state N, G groups of B and C, kernel K:
    [z (H*P) | xBC (H*P + 2*G*N) | dt (H)] = u Win^T       (the inner width is
                        mamba_num_heads x mamba_head_dim, NOT expand x d)
    xBC_t = silu(sum_{j<K} w[:, j] * xBC_{t-K+1+j} + b)   depthwise, causal,
                                                          zeros left of t = 0
    [x (H x P) | B (G x N) | C (G x N)] = xBC;  dt_t = softplus(dt_t + dt_bias)
    per head h, its group g = h // (H / G):
        S_t = exp(dt_t A_h) S_{t-1} + dt_t * x_t (outer) B_{t,g}    (P x N),
        A_h = -exp(A_log_h), S_{-1} = 0;   y_t = S_t C_{t,g} + D_h x_t
    y = rms_per_group(y * silu(z)) * gn    the statistics over each group's
                        H*P / G features (``MambaRMSNormGated``, gate FIRST)
    out = y Wout^T
``attention``: q = h Wq^T (Hq heads of dh), k, v = h Wk^T, h Wv^T (Hkv heads),
no bias, NO positions, causal softmax(q k^T / sqrt(dh)) v, each key/value head
serving Hq / Hkv consecutive query heads, then Wo.
``moe``: s = sigmoid(h Wr^T) over ALL E experts;  S = the top-k of s + b
(b = ``e_score_correction_bias``; one group; ties: the lower index);
    p_e = scaling * s_e / (sum_{e in S} s_e + 1e-20)        from s, NOT s + b
    out = sum_{e in S, e HELD} p_e Wd_e relu(Wu_e h)^2 + Wd_s relu(Wu_s h)^2
an expert is UNGATED, two matrices and a squared ReLU; the shared expert
(index s) likewise, taken by every token.

Departures from the published model, and points I could not check against the
source, each a possible departure:
- the SHARE: HELD are experts ``local_expert_offset`` .. +
  ``num_local_experts`` - 1 of the E the router scores (one chip's share of
  two that divide every layer); what the absent experts would have added is
  left out, here as in the program, and that partial result goes on;
- the SLICE: the vocabulary is the first ``vocab_size`` rows of the
  published 131,072 (embedding and head);
- the DEPTH: the configuration's ``layer_types`` (blocks 0-12 of 52);
- NO positions: ``transformers``' ``NemotronHAttention`` applies no rotary
  embedding (the Mamba layers carry order); ``rope_theta`` and
  ``partial_rotary_factor`` of the config are read by nothing there;
- ``dt`` is not clamped after the softplus (``time_step_limit`` (0, inf);
  ``time_step_min/max/floor`` are the initialiser's);
- the gated norm multiplies by silu(z) BEFORE the statistics
  (``norm_before_gate`` false);
- the chosen weights are renormalised with + 1e-20 and THEN scaled by
  ``routed_scaling_factor``;
- ``residual_in_fp32`` false: nothing here depends on it (all float32);
  ``rescale_prenorm_residual`` and ``expand`` are read by nothing here.
Layout choices that change no function: q, k and v live in ONE fused matrix
(rows q, then k, then v, each head-major); an expert's matrices are stored
(in, out), stacked over the HELD experts, and may be stored WIDER than the
expert (zero columns of up, zero rows of down: relu(0)^2 = 0, they add
nothing); the shared expert's are (out, in) like every other linear weight.

Checkpoint layout (the only thing shared with the program): ``embed_weight``,
``lm_head_weight`` (vocab, d); ``final_ln_gamma`` (d,); per block ``layer<i>_``
``ln1_gamma`` (d,); a mamba block ``mamba_in_weight`` (2*H*P + 2*G*N + H, d),
``mamba_conv_weight`` (H*P + 2*G*N, K), ``mamba_conv_bias``, ``mamba_dt_bias``,
``mamba_A_log``, ``mamba_D`` (H,), ``mamba_norm_gamma`` (H*P,),
``mamba_out_weight`` (d, H*P); an attention block ``qkv_weight``
((Hq + 2 Hkv) * dh, d), ``proj_weight`` (d, Hq * dh); an expert block
``router_weight`` (E, d), ``router_bias`` (E,), ``experts_up_weight`` (held, d,
F'), ``experts_down_weight`` (held, F', d) with F' >= F, ``shared_up_weight``
(Fs, d), ``shared_down_weight`` (d, Fs). Weights may be stored in a narrower
type: each matrix is upcast to float32 where it is used (an expert's as the
loop reaches that expert), so the float32 copies never exist side by side.
"""
import jax
import jax.numpy as jnp


def rms_norm(x, gamma, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gamma.astype(jnp.float32)


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def causal_conv(xbc, weight, bias):
    """Depthwise causal convolution over time and its SiLU: xbc (T, C),
    weight (C, K), bias (C,); positions before 0 hold zeros."""
    k = weight.shape[1]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    out = bias + sum(padded[j:j + xbc.shape[0]] * weight[:, j]
                     for j in range(k))
    return jax.nn.silu(out)


def recurrence(x, dt, a, b, c, d):
    """The Mamba-2 recurrence, one position after the other. x (T, H, P),
    dt (T, H) after its softplus, a (H,) negative, b and c (T, G, N), d (H,);
    head h reads group h // (H / G). Returns (y (T, H, P), the state after
    the last position (H, P, N))."""
    per = x.shape[1] // b.shape[1]

    def one(s, step):
        x_t, dt_t, b_t, c_t = step
        b_h, c_h = (jnp.repeat(v, per, axis=0) for v in (b_t, c_t))  # (H, N)
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_h) + d[:, None] * x_t

    state = jnp.zeros(x.shape[1:] + b.shape[2:], jnp.float32)
    state, y = jax.lax.scan(one, state, (x, dt, b, c))
    return y, state


def mamba_mixer(u, p, n, cfg, with_state=False):
    """The Mamba-2 mixer on u (T, d); ``n`` is the block's name prefix. With
    ``with_state`` also what the block carries past position T - 1: the
    recurrent state (H, P, N) and the last K-1 xBC columns BEFORE the
    convolution (K-1, H*P + 2*G*N), zeros where the sequence is shorter."""
    heads, hp, ns, g = (cfg[k] for k in ("mamba_heads", "mamba_head_dim",
                                         "mamba_state", "mamba_groups"))
    f32 = lambda name: p[n + name].astype(jnp.float32)
    inner, t = heads * hp, u.shape[0]
    zxbcdt = u @ f32("mamba_in_weight").T
    z, raw, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * g * ns], axis=-1)
    xbc = causal_conv(raw, f32("mamba_conv_weight"), f32("mamba_conv_bias"))
    x, b, c = jnp.split(xbc, [inner, inner + g * ns], axis=-1)
    dt = jax.nn.softplus(dt + f32("mamba_dt_bias"))
    y, state = recurrence(x.reshape(t, heads, hp), dt,
                          -jnp.exp(f32("mamba_A_log")), b.reshape(t, g, ns),
                          c.reshape(t, g, ns), f32("mamba_D"))
    gated = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, g, inner // g)
    gated = gated / jnp.sqrt(jnp.mean(jnp.square(gated), axis=-1,
                                      keepdims=True) + cfg["rms_eps"])
    out = (gated.reshape(t, inner) * f32("mamba_norm_gamma")) \
        @ f32("mamba_out_weight").T
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
    scores = jnp.einsum("htd,hsd->hts", q, k) * dh ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    att = jnp.einsum("hts,hsd->htd", jax.nn.softmax(scores, axis=-1), v)
    att = att.transpose(1, 0, 2).reshape(t, hq * dh)
    return att @ p[n + "proj_weight"].astype(jnp.float32).T


def route(h, router, bias, top_k, scaling):
    """(weights (T, k), expert indices (T, k)) of every token over ALL the
    experts: chosen on the biased score, weighted by the unbiased one."""
    s = jax.nn.sigmoid(h @ router.astype(jnp.float32).T)
    _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    return scaling * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20), chosen


def moe_mixer(h, p, n, cfg):
    """The HELD experts' part of the routed sum for h (T, d), beside the
    shared expert: a loop over the stacks' rows (``fori_loop``), row j being
    expert ``local_expert_offset + j``, applied to EVERY token and weighted
    by that token's weight for it, 0 where the expert is not among the
    token's top-k."""
    weights, chosen = route(
        h, p[n + "router_weight"], p[n + "router_bias"],
        cfg["num_experts_per_tok"],
        float(cfg.get("routed_scaling_factor", 1.0)))
    up, down = (jnp.asarray(p[n + "experts_%s_weight" % w])
                for w in ("up", "down"))
    first = int(cfg.get("local_expert_offset", 0))

    def add_expert(j, y):
        w_e = jnp.sum(jnp.where(chosen == first + j, weights, 0.0), axis=-1)
        a = relu2(h @ up[j].astype(jnp.float32))
        return y + w_e[:, None] * (a @ down[j].astype(jnp.float32))

    routed = jax.lax.fori_loop(0, up.shape[0], add_expert, jnp.zeros_like(h))
    shared = relu2(h @ p[n + "shared_up_weight"].astype(jnp.float32).T) \
        @ p[n + "shared_down_weight"].astype(jnp.float32).T
    return routed + shared


_MIXERS = {"mamba": mamba_mixer, "attention": attention_mixer,
           "moe": moe_mixer}


def logits(p, tokens, cfg, last=None):
    """(T, vocab) next-token logits at every position of ``tokens`` (T,);
    with ``last`` only the last ``last`` positions go through the final norm
    and the head, (last, vocab)."""
    with jax.default_matmul_precision("highest"):
        x = p["embed_weight"][tokens.astype(jnp.int32)].astype(jnp.float32)
        for i, kind in enumerate(cfg["layer_types"]):
            n = "layer%d_" % i
            x = x + _MIXERS[kind](
                rms_norm(x, p[n + "ln1_gamma"], cfg["rms_eps"]), p, n, cfg)
        if last is not None:
            x = x[-last:]
        x = rms_norm(x, p["final_ln_gamma"], cfg["rms_eps"])
        return x @ p["lm_head_weight"].astype(jnp.float32).T


def first_mixer_state(p, tokens, cfg):
    """(recurrent state (H, P, N), convolution columns (K-1, H*P + 2*G*N)) of
    the FIRST block's Mamba mixer after the last of ``tokens``: what a
    decoder must hold for that block once it has been fed them all. Only the
    first block, whose input is the embedding itself: between the program's
    value and this one stand the mixer's own arithmetic and the type the
    state is kept in, not the rounding of the blocks before."""
    if cfg["layer_types"][0] != "mamba":
        raise ValueError("the first block is not a Mamba mixer")
    with jax.default_matmul_precision("highest"):
        x = p["embed_weight"][tokens.astype(jnp.int32)].astype(jnp.float32)
        h = rms_norm(x, p["layer0_ln1_gamma"], cfg["rms_eps"])
        return mamba_mixer(h, p, "layer0_", cfg, with_state=True)[1:]
