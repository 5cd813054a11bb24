"""Plain reference: a decoder-only Transformer language model, full forward.

Written from Vaswani et al., "Attention Is All You Need" (2017), sections
3.1-3.4 (scaled dot-product attention over ``h`` heads, position-wise ReLU
feed-forward, learned position embeddings of Table 3 row (E)), in
straightforward ``jax.numpy``: float32, ``default_matmul_precision("highest")``,
no cache, no kernels, no batching, nothing from ``mxnet_tpu``. One call scores
every position of one whole sequence; prefill and decode through the
program's cache must agree with it position by position.

Departures from the paper, because the program under test makes them and a
reference has to compute the same function: the stack is the decoder without
cross-attention (a language model); each sub-layer is pre-norm,
``x + Sublayer(LayerNorm(x))``, with one LayerNorm after the last block
(the paper normalises after the residual); LayerNorm epsilon is 1e-5; input
and output embeddings are not tied and the embedding is not scaled by
sqrt(d_model).

Checkpoint layout (the only thing shared with the program): ``embed_weight``
(vocab, d), ``pos_embed_weight`` (positions, d), per layer ``layer<i>_``
``ln1_{gamma,beta}``, ``qkv_{weight,bias}`` with the 3d output rows ordered
q, k, v and each head-major (head, d_head), ``proj_``, ``ln2_``, ``ffn1_``,
``ffn2_``; ``final_ln_``; ``lm_head_``. Linear weights are (out, in).
"""
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def layer_norm(x, gamma, beta):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * gamma + beta


def linear(p, name, x):
    return x @ p[name + "_weight"].T + p[name + "_bias"]


def attention(q, k, v):
    """Causal scaled dot-product attention; q, k, v are (heads, T, d_head)."""
    t = q.shape[1]
    scores = jnp.einsum("htd,hsd->hts", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    return jnp.einsum("hts,hsd->htd", jax.nn.softmax(scores, axis=-1), v)


def logits(p, tokens, cfg):
    """(T, vocab) next-token logits at every position of ``tokens`` (T,)."""
    heads = cfg["num_heads"]
    with jax.default_matmul_precision("highest"):
        t = tokens.shape[0]
        x = p["embed_weight"][tokens.astype(jnp.int32)] \
            + p["pos_embed_weight"][:t]
        d = x.shape[-1]
        for i in range(cfg["num_layers"]):
            name = "layer%d" % i
            h = layer_norm(x, p[name + "_ln1_gamma"], p[name + "_ln1_beta"])
            qkv = linear(p, name + "_qkv", h).reshape(t, 3, heads, d // heads)
            q, k, v = (qkv[:, j].transpose(1, 0, 2) for j in range(3))
            att = attention(q, k, v).transpose(1, 0, 2).reshape(t, d)
            x = x + linear(p, name + "_proj", att)
            h = layer_norm(x, p[name + "_ln2_gamma"], p[name + "_ln2_beta"])
            x = x + linear(p, name + "_ffn2",
                           jax.nn.relu(linear(p, name + "_ffn1", h)))
        x = layer_norm(x, p["final_ln_gamma"], p["final_ln_beta"])
        return linear(p, "lm_head", x)
