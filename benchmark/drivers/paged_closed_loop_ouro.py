"""Driver ``paged_closed_loop_ouro``: the closed loop of
``paged_closed_loop_hybrid`` (its ``run``: weights drawn on the device one
shape at a time as ``paged_closed_loop_arch`` draws them, the decoder built
with the configuration's ``dtype`` and ``serving.prefill_len`` and warmed
without the warm dispatch's outputs, the warm-up grid, the staggered ramp,
the window, the result's keys) around a ``serving.PagedKVDecoder`` of the
looped stack (``arch="ouro"``): ONE set of layers applied ``total_ut_steps``
times, every pass with keys and values of its own. The hybrid's ``run`` is
taken as it is, in a copy of its module private to this one (``load_module``
makes a new module each time it is called), in which three names it looks up
are this file's (and a fourth, ``_KeepsState``):

- ``sample_program`` is ``paged_closed_loop_lfm2``'s: it samples the prompt
  lengths the traffic's ``check_prompt_lens`` names and FEEDS DRAWN tokens in
  its steps;
- ``check_against_reference`` is TWO comparisons, both must hold: the logits,
  each sampled row against the reference's full forward at the same
  position, held to their WORST row (no experts, so no row may flip), and
  the last layer's keys of every pass as the pool keeps them
  (``_KeepsKeys``, in place of the hybrid's ``_KeepsState``), pass 1's held
  tightly. The reference is not jitted whole: it applies ONE jitted layer
  48 x 4 times, upcasting that layer's weights as it goes, so it compiles in
  seconds and fits beside a program that fills the chip. ``--break-reference``
  perturbs ONE matrix, ``layer0_qkv_weight`` x 1.25 (attention's scores x
  1.5625 in all four passes), in a shallow copy of the dict. NOT the output
  projection the other drivers perturb: under sandwich norms the branch's
  output is normed before it is added, so the SCALE of ``proj_weight`` (and
  of the MLP's ``mlp_out_weight``) cancels but for eps, and a reference
  broken there reads as sound. The configuration's ``check.fault``
  (``--set config.check.fault='"<name>"'``) gives the other readings that
  must fail: the reference's own ``previous_pass_keys`` and
  ``no_norm_between_passes``, and ``float8_weights`` (every matrix rounded to
  float8_e4m3 where the reference reads it: the next storage precision below
  bfloat16);
- ``Loop`` counts this block's own FLOP (``model_flops``): FOUR passes of
  every matrix and of attention's scores and apply, the head and the gate
  once.

``step_bytes``, the bytes a decode step NEEDS by the layer equations, lives
here too: ``kernels.hbm_share.loop`` reads it.
"""
import jax.numpy as jnp
import numpy as np

from harness.spec import load_module

_hybrid = load_module("drivers", "paged_closed_loop_hybrid")
sample_program = load_module("drivers",
                             "paged_closed_loop_lfm2").sample_program

BROKEN = "layer0_qkv_weight"  # what --break-reference perturbs, x 1.25
_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _sizes(m):
    """(passes, layers, d, attention width, FFN width, vocabulary)."""
    return (int(m.get("total_ut_steps", 4)), m["num_layers"], m["model_dim"],
            m["num_heads"] * m["head_dim"], m["ffn_dim"], m["vocab_size"])


def layer_parameters(m):
    """The parameters of ONE layer: q, k, v and output projections, the
    MLP's three matrices, four norms."""
    _, _, d, width, f, _ = _sizes(m)
    return 4 * width * d + 3 * d * f + 4 * d


def model_flops(m, tokens, context_tokens, head_rows):
    """FLOP (2 x MACs) the layer equations
    (``reference/ouro_decoder.py``) need for ``tokens`` tokens: in EVERY
    pass every layer's q, k, v and output projections and the MLP's three
    matrices, and attention's scores and apply over ``context_tokens`` (each
    token's context summed, the causal half of a prompt not discounted, as
    ``flops.py`` counts it): a pass attends its own keys, so four passes
    read four contexts; the vocabulary head ONCE for ``head_rows`` positions,
    and the exit gate, a row of ``d``, after every pass but the last."""
    passes, layers, d, width, f, vocab = _sizes(m)
    return float(
        passes * layers * (tokens * 2 * (4 * width * d + 3 * d * f)
                           + context_tokens * 4 * width)
        + head_rows * 2 * d * (vocab + passes - 1))


def step_bytes(model, dtype, steps, decode_tokens, context_tokens):
    """Bytes ``steps`` decode steps NEED that stepped ``decode_tokens`` lanes
    in all over ``context_tokens`` tokens of context, weights and pool in
    ``dtype``. In every step every LAYER weight once a pass: the layers'
    4.9 GB do not stay on the chip between passes (its on-chip memory holds a
    few dozen MB), so pass u + 1 reads them again; the final norm, the gate
    and the head once. A stepped lane reads its embedding row and writes a key
    row and a value row (heads x head_dim) in every layer of every pass; a
    token of a stepped lane's own context (``serving.step_context_tokens``:
    position + 1 a lane and step) has as many rows READ. Not counted:
    activations, logits, the page table, and whatever the program moves
    beyond the need (a page read whole for one row)."""
    passes, layers, d, width, _, vocab = _sizes(model)
    row = 2 * passes * layers * width           # a token's keys and values
    return _BYTES[dtype] * (
        steps * (passes * layers * layer_parameters(model)
                 + vocab * d + d + d + 1)
        + decode_tokens * (d + row) + context_tokens * row)


_HybridLoop = _hybrid.Loop


class Loop(_HybridLoop):
    """The hybrid's loop with this block's FLOP: a step's for the lanes it
    steps at their own contexts, an admission's for the prompt's REAL tokens
    (padding to the bucket is not credited) and the one row of logits a
    generating admission needs (past the hybrid's own count, to the old
    loop's ``_token``)."""

    def _token(self, req, now, first):
        if first and self.recording:
            n = len(req.prompt)
            self.model_flops += model_flops(self.run.config["model"],
                                            n, n * n, 1)
        super(_HybridLoop, self)._token(req, now, first)

    def _flops(self, feed):
        # the token fed now attends itself and everything before it
        contexts = sum(len(self.active[seq].prompt) + self.active[seq].got
                       for seq in feed)
        return model_flops(self.run.config["model"], len(feed), contexts,
                           len(feed))


class _Float8Weights(dict):
    """The checkpoint as the reference reads it under
    ``check.fault = "float8_weights"``: every matrix rounded to float8_e4m3
    WHERE IT IS READ (a second copy of 5.3 GB would not fit beside the
    program)."""

    def __getitem__(self, name):
        value = super().__getitem__(name)
        if not name.endswith("_weight"):
            return value
        return value.astype(jnp.float8_e4m3fn).astype(value.dtype)


class _KeepsKeys:
    """The decoder as ``sample_program`` drives it, which also keeps the LAST
    layer's keys of each sampled lane, every pass's, as the pool holds them
    when the lane retires: (passes, heads, positions, head_dim)."""

    def __init__(self, dec):
        self._dec, self.states = dec, []
        self._name = "kv_k_%d" % (dec.num_layers - 1)

    def __getattr__(self, name):
        return getattr(self._dec, name)

    def retire(self, seq):
        kept = self._dec.lane_state(seq, (self._name,))[self._name]
        self.states.append(np.asarray(kept, np.float32))
        self._dec.retire(seq)


def check_against_reference(run, params, sampled, states):
    """Two comparisons with the reference, both must hold. The logits: each
    sampled row against the full forward over the whole sequence at the same
    position, the WORST row. Rounding in the weights' type is amplified from
    pass to pass (seeded weights: about 2.2 x a pass, PERF.md section 6,
    PR 54), so the logits' limit is wide; what it cannot see is held by the
    second: the LAST layer's rotated keys of each sampled lane as the pool
    keeps them after its last step (``states``), pass by pass, against the
    keys the reference's layer made at the same positions. Pass 1's have run
    through every layer once and no amplification: they are held to a limit
    ten times tighter (``first_pass_keys_rel_l2``); the later passes' are
    reported."""
    model, chk = run.config["model"], run.config["check"]
    ref, fault = run.reference(), chk.get("fault")
    if run.break_reference:
        params = dict(params, **{BROKEN: params[BROKEN] * 1.25})
    if fault == "float8_weights":
        params, fault = _Float8Weights(params), None
    rows, keys = [], []
    rel = lambda got, want, axes: np.linalg.norm(
        (got - want).reshape(axes), axis=-1) / (np.linalg.norm(
            want.reshape(axes), axis=-1) + 1e-30)
    for (toks, got), kept in zip(sampled, states):
        want, made = ref.logits_and_keys(
            params, jnp.asarray(toks), model, last=got.shape[0], fault=fault,
            keep=model["num_layers"] - 1)
        rows.append(rel(got, np.asarray(want), got.shape))
        # the last token fed was never stepped past: its key is not kept
        made = np.asarray(made)[:, :, :kept.shape[2]]
        keys.append(rel(kept, made, (kept.shape[0], -1)))
    worst = float(max(e.max() for e in rows))
    keys = np.asarray(keys)                     # (prompts, passes)
    run.notes["check_rows_sorted"] = [[float("%.3g" % x) for x in np.sort(e)]
                                      for e in rows]
    run.notes["check_keys_by_pass"] = [[float("%.3g" % x) for x in e]
                                       for e in keys]
    good = bool(np.isfinite(np.concatenate(rows)).all()) \
        and worst <= chk["logits_rel_l2"]
    sound = bool(np.isfinite(keys).all()) \
        and keys[:, 0].max() <= chk["first_pass_keys_rel_l2"]
    under = " under fault %r" % chk["fault"] if chk.get("fault") else ""
    return good and sound, [
        "logits of admit and %d decode steps at prompt lengths %s vs the "
        "reference's full forward%s, relative L2: the worst of %d rows %.3e "
        "(limit %.1e; a prompt's median %s, worst %s) %s"
        % (len(sampled[0][1]) - 1,
           [len(t) - len(g) + 1 for t, g in sampled], under,
           sum(len(e) for e in rows), worst, chk["logits_rel_l2"],
           ", ".join("%.3e" % np.median(e) for e in rows),
           ", ".join("%.3e" % e.max() for e in rows),
           "ok" if good else "FAIL"),
        "the last layer's keys of each sampled lane as the pool keeps them "
        "vs the reference's%s, relative L2 a pass: pass 1 %s (limit %.1e); "
        "the later passes, a prompt each: %s %s"
        % (under, ", ".join("%.3e" % e for e in keys[:, 0]),
           chk["first_pass_keys_rel_l2"],
           "; ".join(", ".join("%.3e" % x for x in e[1:]) for e in keys),
           "ok" if sound else "FAIL")]


_hybrid.sample_program = sample_program
_hybrid._KeepsState = _KeepsKeys
_hybrid.check_against_reference = check_against_reference
_hybrid.Loop = Loop

run = _hybrid.run
