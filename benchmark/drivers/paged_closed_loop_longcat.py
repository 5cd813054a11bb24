"""Driver ``paged_closed_loop_longcat``: the closed loop of
``paged_closed_loop_hybrid`` (its ``run``: the decoder built with the
configuration's ``dtype`` and ``serving.prefill_len`` and warmed without the
warm dispatch's outputs, the warm-up grid, the staggered ramp, the window, the
result's keys) around a ``serving.PagedKVDecoder`` of the LongCat-Flash block
(``arch="longcat_flash"``): a layer of TWO latent attentions with a low-rank
query over two latent pools, two dense MLPs and ONE shortcut-connected expert
layer that holds 16 of the 512 experts its router scores beside 256
zero-compute ones. What a latent block's check needs is taken from the
drivers that have it, not copied: ``sample_program`` (DRAWN tokens in the
steps: lfm2's, which dots3's copy of the hybrid already holds),
``kth_smallest`` and its two ranks (lfm2's), the loop that hands a request's
OUTPUT length out by the order of issue (``paged_closed_loop_dots3.Loop``: a
20 s window holds one and a half rounds of 32 requests here, as in laguna's
cell, where the seed's luck moved tokens/s by 8.4% between the quartiles and
the fixed order by 2.8%, PERF.md section 6, PR 58). What is this file's:

- ``_KeepsState`` keeps the first layer's SECOND latent pool of each sampled
  lane (``kv_c_1``, the lane's own positions) as its last step left it: the
  rows the admission scattered and the rows the steps wrote, in order;
- ``check_against_reference`` is TWO comparisons, both must hold: the logits
  (each sampled row against the reference's full forward at the same
  position, ``reference.logits(..., last=)``), held to the fifth smallest of
  all the sample's rows and to each prompt's second smallest (a flipped
  near-tie among twelve unrenormalised softmax weights times 6 shows in a
  row, and which of two tied experts serves a token is not the model's
  function) and, as in ``paged_closed_loop_dots3``, to the MEDIAN of all the
  rows: the rows lie close together here, so a hold that binds half of them
  stands well under the smallest fault, and a fault that spoils only the
  later steps cannot pass under two low ranks; and the kept pool against the reference's [rho_kv c | k_r] of
  every position (``reference.second_pool_rows``), the prompt's rows and the
  steps' apart: what a pool index off by one, a row scattered into the wrong
  pool or a factor on the wrong part moves, and no expert reaches (the
  expert sum joins the stream behind this sublayer). Its own
  ``--break-reference``: ``layer0_kvb_weight`` x 1.25, the matrix both of the
  first sublayer's attention paths read (materialised in the prefill,
  absorbed in the step), in a shallow copy of the dict;
- ``model_flops``, which the hybrid's ``Loop`` calls for a step at the lanes'
  own contexts and for an admission over the prompt's real tokens with one
  row of logits, counts this block's own equations;
- ``sizes`` and ``step_bytes``, the bytes a decode step NEEDS by the layer
  equations: ``kernels.hbm_share.scmoe`` reads them.
"""
import jax
import jax.numpy as jnp
import numpy as np

from harness.spec import load_module

# dots3's copy of the hybrid's module: its ``Loop`` hands the output lengths
# out by the order of issue, its ``sample_program`` feeds drawn tokens
_dots3 = load_module("drivers", "paged_closed_loop_dots3")
_hybrid = _dots3._hybrid
kth_smallest, POOLED, A_PROMPT = _dots3.kth_smallest, _dots3.POOLED, \
    _dots3.A_PROMPT

BROKEN = "layer0_kvb_weight"    # what --break-reference perturbs, x 1.25
POOL = "kv_c_1"                 # the first layer's SECOND latent pool
_BYTES = _dots3._BYTES


def _counts(m):
    """The block's sizes by the layer equations
    (``reference/longcat_flash_decoder.py``): (the matrices every token
    passes outside the routed experts and the head: two latent attentions
    and two dense MLPs a layer, the router over experts and zero-compute
    experts; the vectors beside them: four norms a sublayer, the selection
    bias, the final norm; the head's slice; ONE expert; the numbers a token
    keeps in the latent pools of all sublayers)."""
    d, h, layers = m["model_dim"], m["num_heads"], m["num_layers"]
    nope, rope = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    v, lat, rank = m["v_head_dim"], m["kv_lora_rank"], m["q_lora_rank"]
    routed = m["num_experts"] + m["num_zero_experts"]
    attention = rank * d + h * (nope + rope) * rank + (lat + rope) * d \
        + h * (nope + v) * lat + h * v * d
    matrices = layers * (2 * (attention + 3 * d * m["ffn_dim"]) + routed * d)
    vectors = layers * (2 * (2 * d + rank + lat) + routed) + d
    return matrices, vectors, m["vocab_size"] * d, \
        3 * d * m["moe_ffn_dim"], 2 * layers * (lat + rope)


def sizes(m):
    """(parameters a step reads whatever the router does, the head's slice
    among them; parameters of ONE expert; numbers a token keeps in the latent
    pools of all sublayers)."""
    matrices, vectors, head, expert, latent = _counts(m)
    return matrices + vectors + head, expert, latent


def model_flops(m, tokens, context_tokens, head_rows):
    """FLOP (2 x MACs) the layer equations need HERE for ``tokens`` tokens
    through every layer: both attentions' projections (every head's key and
    value made of the latent, the materialised count), both dense MLPs, the
    router over all its outputs and the HELD experts' three matrices at the
    share even routing sends them (``num_experts_per_tok`` x held / router
    outputs a token: a quarter of an expert here; a zero-compute expert
    multiplies nothing); both attentions' scores and apply over
    ``context_tokens`` (each token's context summed, the causal half of a
    prompt not discounted, as ``flops.py`` counts it); and the vocabulary
    head for ``head_rows`` positions."""
    matrices, _, head, expert, _ = _counts(m)
    routed = m["num_experts"] + m["num_zero_experts"]
    held = m.get("num_local_experts") or m["num_experts"]
    experts = m["num_layers"] * expert * m["num_experts_per_tok"] * held \
        / routed
    pair = 2 * m["num_heads"] * (m["qk_nope_head_dim"]
                                 + m["qk_rope_head_dim"] + m["v_head_dim"])
    return float(tokens * 2 * (matrices + experts)
                 + context_tokens * 2 * m["num_layers"] * pair
                 + head_rows * 2 * head)


def step_bytes(model, dtype, steps, tokens, context_tokens, experts_touched):
    """Bytes ``steps`` decode steps need that stepped ``tokens`` lanes in all
    over ``context_tokens`` tokens of context and touched ``experts_touched``
    held experts (summed over layers and steps), everything in ``dtype``: in
    every step the weights outside the routed experts once, the head's slice
    among them (the embedding is looked up, not read); three matrices for
    every held expert that received at least one row, so the count is the
    routing's and not the implementation's; a latent row a sublayer read for
    every token of a stepped lane's own context and written for every
    stepped lane."""
    always, expert, latent = sizes(model)
    return _BYTES[dtype] * (steps * always + experts_touched * expert
                            + (context_tokens + tokens) * latent)


class _KeepsState:
    """The decoder as ``sample_program`` drives it, which also keeps each
    sampled lane's own positions of ``POOL`` when it retires: ``states`` is
    [(positions, latent + rope)], float32 copies (a view would follow the
    device's buffer into its next use)."""

    def __init__(self, dec):
        self._dec, self.states = dec, []

    def __getattr__(self, name):
        return getattr(self._dec, name)

    def retire(self, seq):
        rows = self._dec.lane_state(seq, (POOL,))[POOL]
        self.states.append(np.array(rows, dtype=np.float32)[0])
        self._dec.retire(seq)


def pool_error(got, want):
    """Relative L2 of the kept rows ``got`` against the reference's."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def check_against_reference(run, params, sampled, states):
    """Two comparisons with the reference, both must hold (the
    configuration's ``check.why`` has every reading). The logits: each
    sampled row against the full forward over the whole sequence at the same
    position, held to the ``POOLED``-th smallest of all the rows, to their
    median and to each prompt's ``A_PROMPT``-th smallest. The first layer's
    SECOND latent pool
    of each sampled lane against the reference's [rho_kv c | k_r], the rows
    its admission scattered and the rows its steps wrote apart."""
    model, chk = run.config["model"], run.config["check"]
    ref = run.reference()
    if run.break_reference:
        params = dict(params, **{BROKEN: params[BROKEN] * 1.25})

    @jax.jit
    def errors(p, tokens, got):
        want = ref.logits(p, tokens, model, last=got.shape[0])
        return jnp.linalg.norm(got - want, axis=-1) / (
            jnp.linalg.norm(want, axis=-1) + 1e-30)

    rows_of = jax.jit(lambda p, tokens: ref.second_pool_rows(p, tokens,
                                                             model))
    rows = [np.asarray(errors(params, jnp.asarray(toks), jnp.asarray(got)))
            for toks, got in sampled]
    pooled = kth_smallest(np.concatenate(rows), POOLED)
    median = float(np.median(np.concatenate(rows)))
    held = [kth_smallest(e, A_PROMPT) for e in rows]
    run.notes["check_rows_sorted"] = [[float("%.3g" % x) for x in np.sort(e)]
                                      for e in rows]
    good = bool(np.isfinite(np.concatenate(rows)).all()) \
        and pooled <= chk["logits_rel_l2"] \
        and median <= chk["logits_rel_l2_median"] \
        and max(held) <= chk["logits_rel_l2_a_prompt"]
    pools = []
    for (toks, got), kept in zip(sampled, states):
        # every token of ``toks`` was fed: the prompt, then a step each, the
        # last one's row written too
        admitted = len(toks) - len(got) + 1
        want = np.asarray(rows_of(params, jnp.asarray(toks)))[0]
        pools.append([pool_error(kept[:admitted], want[:admitted]),
                      pool_error(kept[admitted:len(toks)], want[admitted:])])
    pools = np.asarray(pools)
    sound = bool(np.isfinite(pools).all()) \
        and pools.max() <= chk["pool_rows_rel_l2"]
    return good and sound, [
        "logits of admit and %d decode steps at prompt lengths %s vs the "
        "reference's full forward, relative L2: the %d-th smallest of all %d "
        "rows %.3e (limit %.1e), their median %.3e (limit %.1e), a prompt's "
        "%d-th smallest %s (limit %.1e; a prompt's median %s, worst %s; %d "
        "of %d rows above the limit) %s"
        % (len(sampled[0][1]) - 1,
           [len(t) - len(g) + 1 for t, g in sampled], POOLED,
           sum(len(e) for e in rows), pooled, chk["logits_rel_l2"], median,
           chk["logits_rel_l2_median"], A_PROMPT,
           ", ".join("%.3e" % e for e in held),
           chk["logits_rel_l2_a_prompt"],
           ", ".join("%.3e" % np.median(e) for e in rows),
           ", ".join("%.3e" % e.max() for e in rows),
           int(sum((e > chk["logits_rel_l2"]).sum() for e in rows)),
           sum(len(e) for e in rows), "ok" if good else "FAIL"),
        "the first layer's second latent pool (%s) of each sampled lane vs "
        "the reference's [rho_kv c | k_r], relative L2: the rows the "
        "admission scattered %s, the rows the steps wrote %s (limit %.1e) %s"
        % (POOL, ", ".join("%.3e" % e for e in pools[:, 0]),
           ", ".join("%.3e" % e for e in pools[:, 1]),
           chk["pool_rows_rel_l2"], "ok" if sound else "FAIL")]


_hybrid.check_against_reference = check_against_reference
_hybrid.model_flops = model_flops
_hybrid._KeepsState = _KeepsState


def run(run):
    """The hybrid's ``run`` with the names above; a traced run's notes also
    say what this cell's line leaves out because a passing test pins the
    accepted list's members or the accepted reader misreads this router:
    how many HELD experts a step touched, a layer; what share of a step's
    assignments reached one (16 / 768 of them under even routing:
    ``moe.local_rows_share``'s quantity); the busiest of the router's
    outputs over the even share BY THE ROUTER'S WIDTH, experts, zero-compute
    experts and absent experts alike (``moe.load_max_over_mean``'s reader
    multiplies by ``num_experts``, two thirds of this router, and its
    maximum no longer bounds the grouped matmul's longest group); and the
    medians of a step's ``dispatch`` and ``wait`` spans, by the accepted
    readers."""
    obs = _hybrid.run(run)
    c, m = run.counters_window or {}, run.config["model"]
    steps = c.get("serving.paged_steps")
    if steps and "serving.moe.step_experts_touched" in c:
        run.notes["held_experts_touched_a_step_and_layer"] = \
            c["serving.moe.step_experts_touched"] / (steps * m["num_layers"])
        run.notes["step_local_rows_share"] = \
            c.get("serving.moe.step_local_assignments", 0) \
            / max(c.get("serving.moe.step_assignments", 0), 1)
    if c.get("serving.moe.assignments"):
        run.notes["router_load_max_over_mean"] = \
            c.get("serving.moe.max_expert_assignments", 0) \
            * (m["num_experts"] + m["num_zero_experts"]) \
            / c["serving.moe.assignments"]
    for phase in ("dispatch", "wait"):
        name = "serving.step_%s_ms_p50" % phase
        p50 = load_module("layer_metrics", name).read(run)
        if p50 is not None:
            run.notes[name] = p50
    return obs
