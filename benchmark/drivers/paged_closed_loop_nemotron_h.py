"""Driver ``paged_closed_loop_nemotron_h``: the closed loop of
``paged_closed_loop_hybrid`` (its ``run``: the decoder built with the
configuration's ``dtype`` and ``serving.prefill_len`` and warmed without the
warm dispatch's outputs, the warm-up grid, the staggered ramp, the window, the
result's keys; its initialiser's kinds for a state-space mixer; its
``_KeepsState``, which keeps the first block's recurrent state and
convolution columns of each sampled lane as its last step left them) around a
``serving.PagedKVDecoder`` of the Nemotron-H block (``arch="nemotron_h"``):
ONE mixer a block, Mamba-2 rows (8 groups of B and C) beside the paged pools
of two position-free attention blocks beside expert blocks that keep nothing
and hold 64 of the 128 ungated relu^2 experts they route over. The hybrid's
``run`` is taken as it is, in a copy of its module private to this one
(``load_module`` makes a new module each time it is called), in which five
names it looks up are this file's:

- ``make_weights`` draws as the hybrid's does and then ZEROES what the routed
  experts' stacks hold past the published width (they are stored whole lane
  tiles wide, 1,920 for 1,856: ``models/transformer.py:_lane_tiles``), so the
  program and the reference compute the published expert exactly;
- ``sample_program`` is ``paged_closed_loop_lfm2``'s: it samples the prompt
  lengths the traffic's ``check_prompt_lens`` names and FEEDS DRAWN tokens;
- ``check_against_reference`` is TWO comparisons, both must hold: the logits
  (each sampled row against the reference's full forward at the same
  position, the head computed for the compared rows only), held to the fifth
  smallest of all the sample's rows and to each prompt's second smallest
  (``paged_closed_loop_lfm2.kth_smallest``: near-tied experts flip under
  bfloat16, a prompt's rows together); and the first block's recurrent state
  (the worst HEAD's relative L2) and convolution columns of each sampled lane
  after its last step against the reference's sequential recurrence
  (``reference.first_mixer_state``), which no flipped expert reaches: block 0
  is a Mamba mixer. ``--break-reference``: ``layer0_mamba_out_weight`` x 1.25
  in a shallow copy of the dict;
- ``model_flops``, which the hybrid's ``Loop`` calls for a step at the lanes'
  own contexts and for an admission over the prompt's real tokens with one
  row of logits, counts this block's own equations at the PUBLISHED widths;
- ``Loop`` is the hybrid's around a decoder whose stepped lanes are fed
  DRAWN tokens (``_DrawsTokens``): the loop still pulls every lane's row of
  logits and takes its arg-max (the host's work stays what it is), but what a
  lane feeds next is drawn from the seed. Under random weights the arg-max is
  the same few tokens in every lane (``PERF.md`` section 7: "the timed loop's
  lanes repeat one token each"), and how far a seed's lanes collapse into one
  another differs from seed to seed: twelve seeds fed the arg-max spread
  ``gen_tokens_per_s`` by 5.0% (5.4% and 5.1% in sets of six, where a new
  cell is admitted under 5%), twelve fed drawn tokens by 1.9% (``PERF.md``
  section 6, PR 48). Users do not type one another's tokens. It does NOT even
  the routing: a step touches 36 of a block's 64 held experts either way (34
  to 35 fed the arg-max), because over random weights the router's choice
  follows the common part of the residual stream and not the token.

``sizes`` and ``step_bytes``, the bytes a decode step NEEDS by the layer
equations of ``reference/nemotron_h_decoder.py``, live here too:
``kernels.hbm_share.ssm_moe`` reads them. So do ``expert_layer_bytes`` and
``expert_layer_flops``, by which the grouped-matmul kernel's share of its
roofline is computed where it is measured standing alone (``PERF.md``
section 6): the harness keeps the ten longest device operations of a trace
and a step here has ten kernel calls, so no reader of ``device_ops`` could
divide the right bytes by the right time.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness.spec import load_module

_hybrid = load_module("drivers", "paged_closed_loop_hybrid")
_lfm2 = load_module("drivers", "paged_closed_loop_lfm2")
sample_program, kth_smallest = _lfm2.sample_program, _lfm2.kth_smallest
POOLED, A_PROMPT = _lfm2.POOLED, _lfm2.A_PROMPT

BROKEN = _hybrid.BROKEN         # layer0_mamba_out_weight, x 1.25
_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
_PADDED = ("experts_up_weight", "experts_down_weight")


def _widths(m):
    """(d, Mamba heads, inner width, xBC's features, state, kernel, query
    width, key/value width) of the model."""
    d, h, p, n, g, k = (m[x] for x in (
        "model_dim", "mamba_heads", "mamba_head_dim", "mamba_state",
        "mamba_groups", "mamba_conv"))
    q, kv = (m[x] * m["head_dim"] for x in ("num_heads", "num_kv_heads"))
    return d, h, h * p, h * p + 2 * g * n, n, k, q, kv


def _counts(m):
    """The block's sizes by the layer equations, at the PUBLISHED widths:
    (parameters of a Mamba block: norm, [z | xBC | dt] projection, K taps and
    bias, dt_bias, A_log, D, the gated norm's scale, the output projection;
    of an attention block: norm, q, k, v and output projection; of an expert
    block OUTSIDE its routed experts: norm, router and its bias, the shared
    expert's two matrices; of ONE routed expert's two matrices; of embedding
    or head, each; float32 state elements a lane a Mamba block; K and V
    elements a token an attention block)."""
    d, h, inner, conv_dim, n, k, q, kv = _widths(m)
    mamba = d + (inner + conv_dim + h) * d + conv_dim * (k + 1) + 3 * h \
        + inner + d * inner
    attention = d + (q + 2 * kv) * d + d * q
    around = d + m["num_experts"] * (d + 1) + 2 * d * m["shared_ffn_dim"]
    return mamba, attention, around, 2 * d * m["moe_ffn_dim"], \
        m["vocab_size"] * d, inner * n + (k - 1) * conv_dim, 2 * kv


def _blocks(m):
    kinds = list(m["layer_types"])
    return tuple(kinds.count(k) for k in ("mamba", "attention", "moe"))


def sizes(m):
    """(parameters a step reads whatever the router does: every block outside
    its routed experts, the head and the final norm, NOT the embedding, of
    which a step gathers one row a lane; parameters of ONE routed expert;
    float32 state elements a lane keeps over all Mamba blocks; K and V
    elements a token keeps over all attention blocks)."""
    mamba, attention, around, expert, table, state, kv = _counts(m)
    n_m, n_a, n_e = _blocks(m)
    return n_m * mamba + n_a * attention + n_e * around + table \
        + m["model_dim"], expert, n_m * state, n_a * kv


def parameters(m):
    """Every parameter this chip holds at the published widths."""
    always, expert, _, _ = sizes(m)
    held = m.get("num_local_experts") or m["num_experts"]
    return always + _counts(m)[4] + _blocks(m)[2] * held * expert


def model_flops(m, tokens, context_tokens, head_rows):
    """FLOP (2 x MACs) the layer equations need HERE for ``tokens`` tokens
    through every block: a Mamba mixer's two projections, its K taps and its
    recurrence (a state element takes a decay multiply-add, the outer
    product's multiply and the read-out's multiply-add: 5 a token, however
    the program groups them into chunks); an attention mixer's qkv and output
    projections; an expert block's router over all its experts, the shared
    expert's two matrices and the HELD experts' two at the share even routing
    sends them (``num_experts_per_tok`` x held / routed-over experts a token:
    3 of 6 here; ``moe.local_rows_share`` says how far the routing is from
    even); attention's scores and apply over ``context_tokens`` (each token's
    context summed, the causal half of a prompt not discounted, as
    ``flops.py`` counts it); and the vocabulary head for ``head_rows``
    positions."""
    d, h, inner, conv_dim, n, k, q, kv = _widths(m)
    held = m.get("num_local_experts") or m["num_experts"]
    mamba = 2 * ((inner + conv_dim + h) * d + inner * d + k * conv_dim) \
        + 5 * inner * n
    attention = 2 * ((q + 2 * kv) * d + q * d)
    experts = 2 * (m["num_experts"] * d + 2 * d * m["shared_ffn_dim"]
                   + 2 * d * m["moe_ffn_dim"] * m["num_experts_per_tok"]
                   * held / m["num_experts"])
    n_m, n_a, n_e = _blocks(m)
    return float(tokens * (n_m * mamba + n_a * attention + n_e * experts)
                 + context_tokens * n_a * 4 * q
                 + head_rows * 2 * d * m["vocab_size"])


def step_bytes(model, dtype, steps, tokens, context_tokens, experts_touched):
    """Bytes ``steps`` decode steps NEED that stepped ``tokens`` lanes in all
    over ``context_tokens`` tokens of context and touched ``experts_touched``
    held experts (summed over blocks and steps), weights and pools in
    ``dtype``: in every step every weight outside the routed experts once
    (the head among them; of the embedding one row a stepped lane); ONE
    expert's two matrices AT THE PUBLISHED 1,856 for every held expert that
    received at least one row (the 64 columns and rows of zero padding the
    stacks carry are moved and NOT counted: they read as lost share); every
    Mamba block's float32 state and convolution columns read and written for
    every stepped lane; an attention block's key and value rows read for
    every token of a stepped lane's own context and written once a stepped
    lane. Not counted: activations, logits, the page table."""
    always, expert, state, kv = sizes(model)
    return _BYTES[dtype] * (
        steps * always + tokens * model["model_dim"]
        + experts_touched * expert + (context_tokens + tokens) * kv) \
        + 4 * 2 * state * tokens


def expert_layer_bytes(m, rows, held_rows, experts_touched, dtype="bfloat16"):
    """Bytes ONE expert block's two grouped matmuls need: the touched
    experts' two matrices at the published width; the ``rows`` sorted rows
    read by the first call (a row past the groups is fetched with its tile);
    relu(up)^2 of the ``held_rows`` written once and read once in ``dtype``;
    the float32 products of the held rows written."""
    d, f, w = m["model_dim"], m["moe_ffn_dim"], _BYTES[dtype]
    return experts_touched * 2 * d * f * w + rows * d * w \
        + 2 * held_rows * f * w + held_rows * d * 4


def expert_layer_flops(m, held_rows):
    """FLOP of the same two calls: two matrices a held row."""
    return 2 * held_rows * 2 * m["model_dim"] * m["moe_ffn_dim"]


def make_weights(shapes, rules, seed, dtype, width):
    """The hybrid's draw; then the routed experts' stacks zero past the
    published ``width`` (the last axis of ``up``, the middle one of
    ``down``)."""
    out = _hybrid_make_weights(shapes, rules, seed, dtype)
    for name in out:
        if name.endswith(_PADDED):
            axis = 2 if name.endswith("up_weight") else 1
            real = jnp.arange(out[name].shape[axis]) < width
            out[name] = jnp.where(
                real[:, None] if axis == 1 else real, out[name], 0)
    return out


def check_against_reference(run, params, sampled, states):
    """Two comparisons with the reference, both must hold. The logits: each
    sampled row against the full forward over the whole sequence at the same
    position, held to the fifth smallest of all the rows and to each prompt's
    second smallest (``paged_closed_loop_lfm2.kth_smallest`` says why not to
    the worst). The first block's recurrent state of each sampled lane after
    its last step against the reference's sequential recurrence over the same
    tokens (the worst HEAD's relative L2: a slow head compounds a rounding at
    every token, and over the whole tensor the fast heads' norm would hide
    it), and its convolution columns: what thirteen blocks of bfloat16
    rounding and flipped experts hide from the logits is the precision the
    state is KEPT in."""
    model, chk = run.config["model"], run.config["check"]
    ref = run.reference()
    keep = jax.jit(lambda p, tokens: ref.first_mixer_state(p, tokens, model))
    kept = []
    for (toks, _), lane in zip(sampled, states):
        want_ssm, want_conv = (np.asarray(a, np.float64) for a in keep(
            params, jnp.asarray(toks)))
        ssm = np.asarray(lane["ssm_state_0"], np.float64)
        heads = np.linalg.norm((ssm - want_ssm).reshape(ssm.shape[0], -1),
                               axis=-1) / (np.linalg.norm(
                                   want_ssm.reshape(ssm.shape[0], -1),
                                   axis=-1) + 1e-30)
        conv = np.asarray(lane["conv_state_0"], np.float64)
        kept.append([float(heads.max()),
                     float(np.linalg.norm(conv - want_conv)
                           / (np.linalg.norm(want_conv) + 1e-30))])
    kept = np.asarray(kept)
    sound = bool(np.isfinite(kept).all()) \
        and kept.max() <= chk["state_rel_l2"]
    if run.break_reference:
        params = dict(params, **{BROKEN: params[BROKEN] * 1.25})

    @jax.jit
    def errors(p, tokens, got):
        want = ref.logits(p, tokens, model, last=got.shape[0])
        return jnp.linalg.norm(got - want, axis=-1) / (
            jnp.linalg.norm(want, axis=-1) + 1e-30)

    rows = [np.asarray(errors(params, jnp.asarray(toks), jnp.asarray(got)))
            for toks, got in sampled]
    pooled = kth_smallest(np.concatenate(rows), POOLED)
    held = [kth_smallest(e, A_PROMPT) for e in rows]
    run.notes["check_rows_sorted"] = [[float("%.3g" % x) for x in np.sort(e)]
                                      for e in rows]
    good = bool(np.isfinite(np.concatenate(rows)).all()) \
        and pooled <= chk["logits_rel_l2"] \
        and max(held) <= chk["logits_rel_l2_a_prompt"]
    return good and sound, [
        "logits of admit and %d decode steps at prompt lengths %s vs the "
        "reference's full forward, relative L2: the fifth smallest of all %d "
        "rows %.3e (limit %.1e), a prompt's second smallest %s (limit %.1e; "
        "a prompt's median %s, worst %s; %d of %d rows above the limit: "
        "experts flipped) %s"
        % (len(sampled[0][1]) - 1,
           [len(t) - len(g) + 1 for t, g in sampled],
           sum(len(e) for e in rows), pooled, chk["logits_rel_l2"],
           ", ".join("%.3e" % e for e in held),
           chk["logits_rel_l2_a_prompt"],
           ", ".join("%.3e" % np.median(e) for e in rows),
           ", ".join("%.3e" % e.max() for e in rows),
           int(sum((e > chk["logits_rel_l2"]).sum() for e in rows)),
           sum(len(e) for e in rows), "ok" if good else "FAIL"),
        "the first block's state of each sampled lane after its last step "
        "vs the reference's sequential recurrence: worst head's relative L2 "
        "%s, convolution columns %s (limit %.1e) %s"
        % (", ".join("%.3e" % e for e in kept[:, 0]),
           ", ".join("%.3e" % e for e in kept[:, 1]), chk["state_rel_l2"],
           "ok" if sound else "FAIL")]


class _DrawsTokens:
    """The decoder as the timed loop drives it: ``step`` feeds every stepped
    lane a token drawn from the seed in place of the one the loop hands it
    (the arg-max of the lane's last row). Everything else is the decoder's."""

    def __init__(self, dec, seed, vocab_size):
        self._dec, self._vocab = dec, int(vocab_size)
        self._rng = np.random.default_rng([int(seed), 99])

    def __getattr__(self, name):
        return getattr(self._dec, name)

    def step(self, tokens):
        drawn = self._rng.integers(1, self._vocab, size=len(tokens))
        return self._dec.step({seq: int(tok)
                               for seq, tok in zip(tokens, drawn)})


class Loop(_hybrid.Loop):
    """The hybrid's loop, its lanes fed drawn tokens."""

    def __init__(self, run, dec, callers, k):
        super().__init__(run, _DrawsTokens(
            dec, run.seed, run.config["model"]["vocab_size"]), callers, k)


_hybrid_make_weights = _hybrid.make_weights
_hybrid.sample_program = sample_program
_hybrid.check_against_reference = check_against_reference
_hybrid.model_flops = model_flops
_hybrid.Loop = Loop


def expert_forms(run):
    """What ``moe_form`` names at the cell's shapes, asked as the operator
    asks it: {"decode": form, "prefill": form}."""
    from mxnet_tpu.models.transformer import param_shapes
    from mxnet_tpu.ops.pallas_grouped_matmul import moe_form

    m, serving = run.config["model"], run.config["serving"]
    shapes = param_shapes(**m)
    first = list(m["layer_types"]).index("moe")
    spec = lambda shape: jax.ShapeDtypeStruct(shape, run.config["dtype"])
    up, down = (spec(shapes["layer%d_experts_%s_weight" % (first, w)])
                for w in ("up", "down"))
    rows = lambda tokens: spec((tokens * m["num_experts_per_tok"],
                                m["model_dim"]))
    return {"decode": moe_form(rows(int(serving["lanes"])), up, down),
            "prefill": moe_form(rows(serving["prefill_len"]), up, down)}


def run(run):
    """The hybrid's ``run`` with the five names above; the notes say which
    form the expert blocks were bound in and, of a traced run, how many HELD
    experts a step touched, a block (the program's counter; what
    ``kernels.hbm_share.ssm_moe`` counts an expert's bytes by)."""
    m = run.config["model"]
    _hybrid.make_weights = functools.partial(make_weights,
                                             width=m["moe_ffn_dim"])
    obs = _hybrid.run(run)
    run.notes["expert_form"] = expert_forms(run)
    c = run.counters_window or {}
    steps = c.get("serving.paged_steps")
    if steps and "serving.moe.step_experts_touched" in c:
        run.notes["held_experts_touched_a_step_and_layer"] = \
            c["serving.moe.step_experts_touched"] / (steps * _blocks(m)[2])
    return obs
