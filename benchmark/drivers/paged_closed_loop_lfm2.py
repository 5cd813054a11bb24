"""Driver ``paged_closed_loop_lfm2``: the closed loop of
``paged_closed_loop_hybrid`` (its ``run``: the decoder built with the
configuration's ``dtype`` and ``serving.prefill_len`` and warmed without the
warm dispatch's outputs, the warm-up grid, the staggered ramp, the window, the
result's keys) around a ``serving.PagedKVDecoder`` of the LFM2-MoE block
(``arch="lfm2_moe"``): gated short convolutions whose columns ride in
per-lane rows beside the KV pages of the attention layers, sigmoid-routed
experts in the same block. The hybrid's ``run`` is taken as it is, in a copy
of its module private to this one (``load_module`` makes a new module each
time it is called), in which five names it looks up are this file's:

- ``sample_program`` samples the prompt lengths the traffic's
  ``check_prompt_lens`` names and FEEDS DRAWN tokens in its steps (the old
  one feeds the arg-max, which a tied head makes the token just fed);
- ``_KeepsState`` keeps the FIRST layer's convolution row of each sampled
  lane twice: as the admission left it and as the last step did;
- ``check_against_reference`` is TWO comparisons, both must hold: the logits
  (each sampled row against the reference's full forward at the same
  position, the head computed for the compared rows only:
  ``reference.logits(..., last=)``), held to the fifth smallest of all the
  sample's rows and to each prompt's second smallest (``kth_smallest``:
  near-tied experts flip under bfloat16, a prompt's rows together); and the
  two kept rows against the
  reference's gated columns at the PROMPT's real end and at the last
  position (``reference.first_conv_columns``), worst feature. Its own
  ``--break-reference``: ``layer0_conv_in_weight`` x 1.25, the first mixer's
  input projection, in a shallow copy of the dict;
- ``model_flops``, which the hybrid's ``Loop`` calls for a step at the lanes'
  own contexts and for an admission over the prompt's real tokens with one
  row of logits, counts this block's own equations: the chosen experts only.
"""
import jax
import jax.numpy as jnp
import numpy as np

from harness.spec import load_module

_hybrid = load_module("drivers", "paged_closed_loop_hybrid")

BROKEN = "layer0_conv_in_weight"    # what --break-reference perturbs, x 1.25
ROW = "conv_state_0"                # the first layer's row of a lane


def model_flops(m, tokens, context_tokens, head_rows):
    """FLOP (2 x MACs) the layer equations (``reference/lfm2_moe_decoder.py``)
    need for ``tokens`` tokens through every layer: a conv mixer's two
    projections (3d + d outputs), its K taps and its two gates; an attention
    mixer's qkv and output projections; the gated MLP's three matrices in the
    leading dense layers, else the router and ``num_experts_per_tok`` experts
    of three matrices each (no shared one); attention's scores and apply over
    ``context_tokens`` (each token's context summed, the causal half of a
    prompt not discounted, as ``flops.py`` counts it); and the vocabulary
    head for ``head_rows`` positions."""
    d, kinds = m["model_dim"], m["layer_types"]
    q, kv = (m[k] * m["head_dim"] for k in ("num_heads", "num_kv_heads"))
    conv = 2 * (4 * d * d + m["conv_kernel"] * d) + 2 * d
    attention = 2 * ((q + 2 * kv) * d + q * d)
    n_att, n_dense = kinds.count("full_attention"), m["first_dense_layers"]
    sparse = 2 * (m["num_experts"] * d
                  + 3 * d * m["moe_ffn_dim"] * m["num_experts_per_tok"])
    return float(
        tokens * (kinds.count("conv") * conv + n_att * attention
                  + n_dense * 2 * 3 * d * m["ffn_dim"]
                  + (len(kinds) - n_dense) * sparse)
        + context_tokens * n_att * 4 * q
        + head_rows * 2 * d * m["vocab_size"])


def sample_program(run, dec):
    """The program's side of the check: at each of the traffic's
    ``check_prompt_lens``, the logits ``admit`` returns and those of
    ``check_decode_steps`` single decode steps through the cache. [(tokens,
    logits rows)]. The tokens FED are drawn from the seed with the prompt,
    not taken as the arg-max of the last row as the old sample does: under a
    tied head over a unit-variance embedding the arg-max IS the token just
    fed, every step would feed the prompt's last token, a prompt's rows would
    be one sample sixteen times over (their experts flip together: on the
    chip up to 14 of a prompt's 17 rows read alike, PERF.md section 6, PR 36)
    and the first layer's two kept columns would be equal, a swapped order
    invisible. Logits are compared, not tokens, so nothing needs the
    arg-max."""
    steps = int(run.traffic["check_decode_steps"])
    rng = np.random.default_rng([run.seed, 77])
    sampled = []
    for length in (int(n) for n in run.traffic["check_prompt_lens"]):
        toks = rng.integers(1, run.config["model"]["vocab_size"],
                            size=length + steps)
        seq, logits = dec.admit(toks[:length].astype(np.float32))
        got = [np.asarray(logits)]
        for tok in toks[length:]:
            got.append(np.asarray(dec.step({seq: int(tok)})[seq]))
        dec.retire(seq)
        sampled.append((toks.astype(np.int32), np.stack(got)))
    return sampled


POOLED, A_PROMPT = 5, 2     # the ranks the two limits hold


def kth_smallest(errors, k):
    """The ``k``-th smallest of ``errors`` (the largest where there are
    fewer): what the logits' rows are held to, never their worst. With four
    of 64 experts chosen on sigmoid scores, a token's fourth and fifth biased
    score lie within the 1e-3 that ten layers of bfloat16 move a score in a
    quarter of the rows; there the program and the float32 reference choose
    another expert, the later layers then choose differently too, and the row
    reads 5e-2 to 1.6e-1, as high as a fault, and is none: which of two tied
    experts serves a token is not the model's function
    (``paged_closed_loop_mla.lower_quartile``, PR 32, found the same at a
    sixth of the rows). A flipped token of the PROMPT also moves the keys and
    values every later row of that prompt attends, by less (1.2e-2 to 3.8e-2),
    so a prompt's rows flip together: of 42 prompts on the chip one kept a
    single row of 17 at the floor (PERF.md section 6, PR 36). Hence two
    holds: the FIFTH smallest of all the sample's rows under the tight limit
    (three prompts flip independently; a fault that spares only the three
    admissions' rows still moves it), and each prompt's SECOND smallest under
    a loose one that no number of flips reaches and a gross fault at one
    prompt length does."""
    ordered = np.sort(np.ravel(np.asarray(errors, np.float64)))
    return float(ordered[min(k, len(ordered)) - 1])


class _KeepsState:
    """The decoder as ``sample_program`` drives it, which also keeps the
    first layer's convolution row of each sampled lane as its admission left
    it and as its last decode step did: ``states`` is [(after admit, after
    the last step)], copies (a view would follow the device's buffer into
    its next use)."""

    def __init__(self, dec):
        self._dec, self.states, self._admitted = dec, [], {}

    def __getattr__(self, name):
        return getattr(self._dec, name)

    def _row(self, seq):
        return np.array(self._dec.lane_state(seq, (ROW,))[ROW])

    def admit(self, prompt):
        seq, logits = self._dec.admit(prompt)
        self._admitted[seq] = self._row(seq)
        return seq, logits

    def retire(self, seq):
        self.states.append((self._admitted.pop(seq), self._row(seq)))
        self._dec.retire(seq)


def column_error(got, want):
    """The worst feature of a kept row (K-1, d) against the reference's
    columns: the largest absolute difference over the columns' root mean
    square (a feature's own value may be 0)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want))
                 / (np.sqrt(np.mean(np.square(want))) + 1e-30))


def check_against_reference(run, params, sampled, states):
    """Two comparisons with the reference, both must hold. The logits: each
    sampled row against the full forward over the whole sequence at the same
    position, held to the fifth smallest of all the rows and to each prompt's
    second smallest (``kth_smallest`` says why not to the worst). The first
    layer's convolution row of each sampled lane after its admission, against
    the reference's gated columns at the prompt's REAL end, and after its
    last step, against those at the last position fed: what a state taken at
    the bucket's end, a row not reset at re-admission or a swapped column
    order moves, and ten layers of bfloat16 rounding and flipped experts
    would hide."""
    model, chk = run.config["model"], run.config["check"]
    ref = run.reference()
    if run.break_reference:
        params = dict(params, **{BROKEN: params[BROKEN] * 1.25})

    @jax.jit
    def errors(p, tokens, got):
        want = ref.logits(p, tokens, model, last=got.shape[0])
        return jnp.linalg.norm(got - want, axis=-1) / (
            jnp.linalg.norm(want, axis=-1) + 1e-30)

    columns = jax.jit(lambda p, tokens, at: ref.first_conv_columns(
        p, tokens, model, at))

    rows = [np.asarray(errors(params, jnp.asarray(toks), jnp.asarray(got)))
            for toks, got in sampled]
    pooled = kth_smallest(np.concatenate(rows), POOLED)
    held = [kth_smallest(e, A_PROMPT) for e in rows]
    run.notes["check_rows_sorted"] = [[float("%.3g" % x) for x in np.sort(e)]
                                      for e in rows]
    good = bool(np.isfinite(np.concatenate(rows)).all()) \
        and pooled <= chk["logits_rel_l2"] \
        and max(held) <= chk["logits_rel_l2_a_prompt"]
    cols = []
    for (toks, got), (admitted, last) in zip(sampled, states):
        # every token of ``toks`` was fed: the prompt, then a step each
        ends = (len(toks) - len(got), len(toks) - 1)
        cols.append([column_error(row, columns(params, jnp.asarray(toks), at))
                     for row, at in zip((admitted, last), ends)])
    cols = np.asarray(cols)
    sound = bool(np.isfinite(cols).all()) \
        and cols.max() <= chk["conv_state_max_err"]
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
        "the first layer's convolution row of each sampled lane vs the "
        "reference's gated columns, worst feature over the columns' rms: "
        "after the admission (the prompt's real end) %s, after the last "
        "step %s (limit %.1e) %s"
        % (", ".join("%.3e" % e for e in cols[:, 0]),
           ", ".join("%.3e" % e for e in cols[:, 1]),
           chk["conv_state_max_err"], "ok" if sound else "FAIL")]


_hybrid.sample_program = sample_program
_hybrid.check_against_reference = check_against_reference
_hybrid.model_flops = model_flops
_hybrid._KeepsState = _KeepsState


def run(run):
    """The hybrid's ``run`` with the five names above; a traced run's notes
    also say how many experts a step touched, a layer (the program's
    counter; what ``kernels.hbm_share.shortconv`` counts an expert's bytes
    by)."""
    obs = _hybrid.run(run)
    c, m = run.counters_window or {}, run.config["model"]
    steps = c.get("serving.paged_steps")
    if steps and "serving.moe.step_experts_touched" in c:
        run.notes["experts_touched_a_step_and_layer"] = \
            c["serving.moe.step_experts_touched"] / (
                steps * (m["num_layers"] - m["first_dense_layers"]))
    return obs
