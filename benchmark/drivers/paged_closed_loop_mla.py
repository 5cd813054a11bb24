"""Driver ``paged_closed_loop_mla``: the closed loop of
``paged_closed_loop_hybrid`` (its ``run``: the decoder built with the
configuration's ``dtype`` and ``serving.prefill_len`` and warmed without the
warm dispatch's outputs, the warm-up grid, the staggered ramp, the window, the
result's keys) around a ``serving.PagedKVDecoder`` of a latent-attention
model with sparse experts (``arch="deepseek_v3"``). The hybrid's ``run`` is
taken as it is, in a copy of its module private to this one
(``load_module`` makes a new module each time it is called), in which three
names it looks up are this file's:

- ``sample_program`` samples the prompt lengths the traffic's
  ``check_prompt_lens`` names (the old one takes the shortest, the middle and
  the longest of the grid);
- ``check_against_reference`` is ONE comparison, the old driver's (each
  sampled row against the reference's full forward at the same position:
  logits, not tokens), held to a prompt's lower-quartile row and not to its
  worst (``lower_quartile``: a near-tied expert flips under bfloat16), with
  the head computed for the compared rows only
  (``reference.logits(..., last=)``: the float32 logits of 1,040 positions
  over 128,256 words would be 0.53 GB beside a chip that is full), and its
  own ``--break-reference``: ``layer0_kvb_weight`` x 1.25, the matrix both
  attention paths read (materialised in the prefill, absorbed in the step),
  in a shallow copy of the dict;
- ``Loop`` counts ``model_flops_in_window`` by this block's own equations
  (``model_flops``): a step in the ABSORBED form at the lanes' own contexts,
  an admission in the MATERIALISED form over the prompt's real tokens with
  one row of logits; the chosen experts and the shared one only.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np

from harness.spec import load_module

_old = load_module("drivers", "paged_closed_loop")
_hybrid = load_module("drivers", "paged_closed_loop_hybrid")

BROKEN = "layer0_kvb_weight"    # what --break-reference perturbs, x 1.25


def model_flops(m, tokens, context_tokens, head_rows, absorbed):
    """FLOP (2 x MACs) the layer equations
    (``reference/deepseek_v3_decoder.py``) need for ``tokens`` tokens through
    every layer: the query and the latent projections; the up-projection of
    the latent, which the materialised form applies to every token's latent
    (H x (nope + v_dim) x latent) and the absorbed form to the query and the
    context (H x nope x latent + H x latent x v_dim: the same count); the
    output projection; the feed-forward (the dense width in the leading
    layers, else the router, ``num_experts_per_tok`` experts and the shared
    one, three matrices each); attention's scores and apply over
    ``context_tokens`` (each token's context summed, the causal half of a
    prompt not discounted, as ``flops.py`` counts it), which cost H x (nope +
    rope + v_dim) a context token materialised and H x (2 x latent + rope)
    absorbed: the price of never making a key; and the vocabulary head for
    ``head_rows`` positions."""
    d, h = m["model_dim"], m["num_heads"]
    nope, rope = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    v_dim, lat = m["v_head_dim"], m["kv_lora_rank"]
    attention = h * (nope + rope) * d + (lat + rope) * d \
        + h * (nope + v_dim) * lat + h * v_dim * d
    dense = 3 * d * m["ffn_dim"]
    sparse = m["num_experts"] * d + 3 * d * m["moe_ffn_dim"] * (
        m["num_experts_per_tok"] + m["num_shared_experts"])
    n_dense = m["first_dense_layers"]
    n_sparse = m["num_layers"] - n_dense
    per_context = h * (2 * lat + rope) if absorbed \
        else h * (nope + rope + v_dim)
    return 2.0 * (
        tokens * (m["num_layers"] * attention + n_dense * dense
                  + n_sparse * sparse)
        + context_tokens * m["num_layers"] * per_context
        + head_rows * d * m["vocab_size"])


class Loop(_old.Loop):
    """The old loop, decode dispatches and all, with this block's FLOP."""

    def _token(self, req, now, first):
        if first and self.recording:
            n = len(req.prompt)
            self.model_flops += model_flops(self.run.config["model"],
                                            n, n * n, 1, absorbed=False)
        super()._token(req, now, first)

    def _flops(self, feed):
        # the token fed now attends itself and everything before it
        contexts = sum(len(self.active[seq].prompt) + self.active[seq].got
                       for seq in feed)
        return model_flops(self.run.config["model"], len(feed), contexts,
                           len(feed), absorbed=True)


def sample_program(run, dec):
    """The old driver's sample (admit, then ``check_decode_steps`` single
    steps through the cache) at the traffic's ``check_prompt_lens``."""
    lens = [int(n) for n in run.traffic["check_prompt_lens"]]
    if len(lens) != 3:
        raise ValueError("check_prompt_lens names three lengths (the old "
                         "sample's shortest, middle and longest), got %r"
                         % (lens,))
    picked = dict(run.traffic, fields=dict(
        run.traffic["fields"], prompt_len={"dist": "choice",
                                           "values": lens}))
    return _old.sample_program(types.SimpleNamespace(
        traffic=picked, seed=run.seed, config=run.config), dec)


def row_errors(got, want):
    """Relative L2 of each row of ``got`` against ``want``."""
    return jnp.linalg.norm(got - want, axis=-1) / (
        jnp.linalg.norm(want, axis=-1) + 1e-30)


def lower_quartile(errors):
    """The statistic a prompt is held to: the ``ceil(n / 4)``-th smallest of
    its rows' errors. Sigmoid scores renormalised over six chosen experts
    make every choice count (a chosen expert weighs about 0.4 of a layer's
    routed sum), and with random weights the sixth and the seventh biased
    score of a token lie 9e-3 apart in the median: where they lie within the
    1e-3 that bfloat16 storage moves a score, the program and the float32
    reference choose another expert, that row reads 1e-1 to 3e-1 (the later
    layers then choose differently too), and it is no fault: which of two
    tied experts serves a token is not the model's function. A sixth of the
    rows do so (PERF.md section 6, PR 32). Every fault that must fail moves
    EVERY row, so the quartile of the rows that agree best still reads it,
    while three rows in four would have to flip to move it."""
    ordered = np.sort(np.asarray(errors, np.float64))
    return float(ordered[-(-len(ordered) // 4) - 1])


def check_against_reference(run, params, sampled, states=None):
    """Each sampled row against the reference's full forward over the whole
    sequence at the same position; a prompt's rows (its admission and the
    steps after it) are held to their lower quartile (``lower_quartile``
    says why not to the worst), the worst prompt decides. ``states`` (what
    the hybrid's loop keeps of a lane's recurrent rows) is empty here."""
    model, limit = run.config["model"], run.config["check"]["logits_rel_l2"]
    ref = run.reference()
    if run.break_reference:
        params = dict(params, **{BROKEN: params[BROKEN] * 1.25})

    @jax.jit
    def errors(p, tokens, got):
        return row_errors(got, ref.logits(p, tokens, model,
                                          last=got.shape[0]))

    rows = [np.asarray(errors(params, jnp.asarray(toks), jnp.asarray(got)))
            for toks, got in sampled]
    held = [lower_quartile(e) for e in rows]
    good = bool(np.isfinite(np.concatenate(rows)).all()) \
        and max(held) <= limit
    return good, [
        "logits of admit and %d decode steps at prompt lengths %s vs the "
        "reference's full forward: a prompt's lower-quartile row, relative "
        "L2, worst prompt %.3e (limit %.1e; a prompt: quartile %s, median "
        "%s, worst %s; %d of %d rows above the limit: experts flipped) %s"
        % (len(sampled[0][1]) - 1,
           [len(t) - len(g) + 1 for t, g in sampled], max(held), limit,
           ", ".join("%.3e" % e for e in held),
           ", ".join("%.3e" % np.median(e) for e in rows),
           ", ".join("%.3e" % e.max() for e in rows),
           int(sum((e > limit).sum() for e in rows)),
           sum(len(e) for e in rows), "ok" if good else "FAIL")]


_hybrid.sample_program = sample_program
_hybrid.check_against_reference = check_against_reference
_hybrid.Loop = Loop


def run(run):
    """The hybrid's ``run`` with the three names above; a traced run's notes
    also say how many experts a step touched, a layer (the program's
    counter; what ``kernels.hbm_share.mla`` counts an expert's bytes by)."""
    obs = _hybrid.run(run)
    c, m = run.counters_window or {}, run.config["model"]
    steps = c.get("serving.paged_steps")
    if steps and "serving.moe.step_experts_touched" in c:
        run.notes["experts_touched_a_step_and_layer"] = \
            c["serving.moe.step_experts_touched"] / (
                steps * (m["num_layers"] - m["first_dense_layers"]))
    return obs
