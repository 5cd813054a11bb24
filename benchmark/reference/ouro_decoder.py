"""Plain reference: Ouro (``model_type: ouro``), a looped language model: ONE
stack of layers run ``total_ut_steps`` times over the same weights, full
forward.

The layer equations of the checkpoint's own ``modeling_ouro.py`` and of
arXiv:2510.25741 (Ouro / LoopLM: "sandwich normalization", "exit gate"),
written from knowledge of them because there is no network here; the sizes
are those of https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json.
Straightforward ``jax.numpy``: float32, ``default_matmul_precision("highest")``,
the loop written as two ``for``s, a full causal forward with T x T scores: no
cache, no page, no offset, no kernels, no batching, nothing from
``mxnet_tpu``. One call scores one whole sequence; prefill and decode through
the program's cache must agree with it position by position.

For tokens t_0..t_{T-1}: x = E[t] (no scaling, no position table). For pass
u = 1..U and, inside it, layer i = 0..N-1, the SAME weights in every pass:
    a = rms(x; g1_i);  [q|k|v] = a Wqkv_i^T           (H heads of dh each)
    q, k = rope(q, pos), rope(k, pos)
        half-split rotation (pairs (j, j + dh/2)), inv_freq_j = theta^(-2j/dh)
    o = causal softmax attention of q over THIS pass's k and v of layer i,
        scale 1/sqrt(dh): position t of pass u never sees another pass's keys
    x = x + rms(o Wo_i^T; g2_i)                       (sandwich: a norm on the
    m = rms(x; g3_i)                                   branch's OUTPUT too)
    x = x + rms(Wd_i (silu(Wg_i m) * Wu_i m); g4_i)
After layer N-1 of every pass: x = rms(x; gf), ONE gamma for all passes;
h_u = x is what the pass hands on (pass u + 1 starts from it) and
g_u = w_g . h_u + b_g, a scalar a token.
Which pass feeds the head: lambda_u = sigmoid(g_u);
p_u = lambda_u prod_{j<u}(1 - lambda_j) for u < U, p_U = prod_{j<U}(1 - lambda_j);
u* = the first u with sum_{j<=u} p_j >= early_exit_threshold, else U;
logits = h_{u*} Wout^T.  rms(x; g) = x / sqrt(mean(x^2) + eps) * g.
All U passes run for every token whatever u* is: later tokens attend this
token's keys of every pass.

Points I could not check against the source, each a possible departure
(the configuration's ``assumed`` has them): where the four norms of a layer
sit, the final norm INSIDE the loop, the gate's bias, the exit rule's
``>=``, a cache of its own for every pass.

Layout choices that change no function: q, k and v live in ONE fused matrix
(rows ordered q, k, v, each head-major); the MLP's gate rows come before its
up rows in ONE matrix.

Checkpoint layout (the only thing shared with the program): ``embed_weight``
(vocab, d); per LAYER (not per pass) ``layer<i>_`` ``ln1_gamma`` ..
``ln4_gamma`` (d,), ``qkv_weight`` (3*H*dh, d), ``proj_weight`` (d, H*dh),
``mlp_in_weight`` (2*F, d), ``mlp_out_weight`` (d, F); ``final_ln_gamma``
(d,); ``exit_gate_weight`` (1, d), ``exit_gate_bias`` (1,);
``lm_head_weight`` (vocab, d). Linear weights are (out, in). Weights may be
stored in a narrower type: a layer's are upcast to float32 where that layer
is applied (``layer`` is one jitted function, called N x U times), so the
float32 copies never exist side by side and the reference fits beside a
program that fills the chip.

``fault`` names a departure ON PURPOSE, for the readings that must fail:
``"previous_pass_keys"`` (pass u > 1 attends the keys and values pass u - 1
made, the loop's own fault: one offset wrong) and
``"no_norm_between_passes"`` (the final norm applied after the last pass
alone).
"""
import functools
import math

import jax
import jax.numpy as jnp

FAULTS = (None, "previous_pass_keys", "no_norm_between_passes")


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


@functools.partial(jax.jit, static_argnames=("heads", "dh", "eps", "theta"))
def layer(x, w, others, *, heads, dh, eps, theta):
    """One layer on x (T, d) with its weights ``w`` (the checkpoint's names
    without the ``layer<i>_``): (x', k, v), the rotated keys and the values
    (heads, T, dh) it made. ``others`` is None, or the (k, v) to attend
    INSTEAD of its own (``fault="previous_pass_keys"``)."""
    f32 = lambda name: w[name].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        pos = jnp.arange(t)
        a = rms_norm(x, w["ln1_gamma"], eps)
        q, k, v = (m.reshape(t, heads, dh).transpose(1, 0, 2) for m in
                   jnp.split(a @ f32("qkv_weight").T, 3, axis=-1))
        q, k = rope(q, pos, theta), rope(k, pos, theta)
        o = attention(q, *(others if others is not None else (k, v)))
        o = o.transpose(1, 0, 2).reshape(t, heads * dh)
        x = x + rms_norm(o @ f32("proj_weight").T, w["ln2_gamma"], eps)
        m = rms_norm(x, w["ln3_gamma"], eps)
        gate, up = jnp.split(m @ f32("mlp_in_weight").T, 2, axis=-1)
        y = (jax.nn.silu(gate) * up) @ f32("mlp_out_weight").T
        return x + rms_norm(y, w["ln4_gamma"], eps), k, v


_LAYER_WEIGHTS = ("ln1_gamma", "qkv_weight", "proj_weight", "ln2_gamma",
                  "ln3_gamma", "mlp_in_weight", "mlp_out_weight", "ln4_gamma")


def _run(p, tokens, cfg, fault=None, keep=None):
    """The two ``for``s: (h (U, T, d), g (U, T), the rotated keys layer
    ``keep`` made in every pass (U, heads, T, dh), or None)."""
    if fault not in FAULTS:
        raise ValueError("unknown fault %r (have: %r)" % (fault, FAULTS))
    sizes = dict(heads=cfg["num_heads"], dh=cfg["head_dim"],
                 eps=float(cfg["rms_eps"]), theta=float(cfg["rope_theta"]))
    n_passes = int(cfg.get("total_ut_steps", 4))
    x = p["embed_weight"][tokens.astype(jnp.int32)].astype(jnp.float32)
    handed, scalars, kept, made = [], [], [], {}
    for u in range(n_passes):
        for i in range(cfg["num_layers"]):
            w = {name: p["layer%d_%s" % (i, name)] for name in _LAYER_WEIGHTS}
            others = made.get((u - 1, i)) \
                if fault == "previous_pass_keys" else None
            x, k, v = layer(x, w, others, **sizes)
            if fault == "previous_pass_keys":
                made[u, i] = (k, v)
                made.pop((u - 2, i), None)
            if i == keep:
                kept.append(k)
        h = rms_norm(x, p["final_ln_gamma"], sizes["eps"])
        if fault != "no_norm_between_passes" or u == n_passes - 1:
            x = h
        handed.append(h)
        with jax.default_matmul_precision("highest"):
            scalars.append(
                h @ p["exit_gate_weight"].astype(jnp.float32)[0]
                + p["exit_gate_bias"].astype(jnp.float32)[0])
    return jnp.stack(handed), jnp.stack(scalars), \
        jnp.stack(kept) if kept else None


def passes(p, tokens, cfg, fault=None):
    """(h (U, T, d), g (U, T)): what every pass hands on, after the final
    norm, and the exit gate's scalar read off it."""
    return _run(p, tokens, cfg, fault)[:2]


def exit_pass(g, threshold):
    """u* (T,) int32, counted from 1, of the gates' scalars ``g`` (U, T)."""
    lam = jax.nn.sigmoid(g.astype(jnp.float32))
    n_passes = g.shape[0]
    at = jnp.full(g.shape[1:], n_passes, jnp.int32)
    survive, cum, reached = 1.0, 0.0, []
    for u in range(n_passes - 1):
        cum = cum + lam[u] * survive
        survive = survive * (1.0 - lam[u])
        reached.append(cum >= threshold)
    for u in reversed(range(n_passes - 1)):
        at = jnp.where(reached[u], u + 1, at)
    return at


def logits_and_keys(p, tokens, cfg, last=None, fault=None, keep=None):
    """``logits`` and, beside them, the rotated keys layer ``keep`` made in
    every pass, (U, heads, T, dh): what a cache must hold of that layer, a
    pass apart."""
    h, g, kept = _run(p, tokens, cfg, fault, keep)
    at = exit_pass(g, float(cfg.get("early_exit_threshold", 1.0)))
    chosen = jnp.take_along_axis(h, (at - 1)[None, :, None], axis=0)[0]
    if last is not None:
        chosen = chosen[-last:]
    with jax.default_matmul_precision("highest"):
        return chosen @ p["lm_head_weight"].astype(jnp.float32).T, kept


def logits(p, tokens, cfg, last=None, fault=None):
    """(T, vocab) next-token logits at every position of ``tokens`` (T,);
    with ``last`` only the last ``last`` positions go through the head,
    (last, vocab)."""
    return logits_and_keys(p, tokens, cfg, last, fault)[0]


def first_pass_hidden(p, tokens, cfg):
    """h_1 (T, d): what the first pass hands on."""
    return passes(p, tokens, cfg)[0][0]


def gates(p, tokens, cfg):
    """g (U, T): the exit gate's scalar after every pass."""
    return passes(p, tokens, cfg)[1]
