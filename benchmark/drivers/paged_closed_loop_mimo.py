"""Driver ``paged_closed_loop_mimo``: the closed loop of
``paged_closed_loop_hybrid`` (its ``run``: the decoder built with the
configuration's ``dtype`` and ``serving.prefill_len`` and warmed without the
warm dispatch's outputs, the warm-up grid, the staggered ramp, the window, the
result's keys) around a ``serving.PagedKVDecoder`` of the MiMo-V2-Flash block
(``arch="mimo_v2_flash"``): five window layers whose last 128 keys and values
a lane ride in per-lane rings beside the paged pools of two full-attention
layers, a sink in the window softmax, and expert layers that hold 16 of the
256 experts they route over. The hybrid's ``run`` is taken as it is, in a
copy of its module private to this one (``load_module`` makes a new module
each time it is called), in which five names it looks up are this file's:

- ``sample_program`` samples the prompt lengths the traffic's
  ``check_prompt_lens`` names and FEEDS DRAWN tokens in its steps, as
  ``paged_closed_loop_lfm2``'s does (a row then depends on the tokens of its
  own window, not on sixteen copies of one);
- ``_KeepsState`` keeps the FIRST window layer's key ring of each sampled
  lane twice: as the admission left it and as the last step did;
- ``check_against_reference`` is TWO comparisons, both must hold: the logits
  (each sampled row against the reference's full forward at the same
  position, the head computed for the compared rows only:
  ``reference.logits(..., last=)``), held to the fifth smallest of all the
  sample's rows and to each prompt's second smallest (``kth_smallest``:
  near-tied experts flip under bfloat16, a prompt's rows together); and the
  two kept rings against the reference's rotated keys at the positions a
  ring holds (``reference.first_window_keys``; ``ring_error``), which no
  flipped expert reaches: layer 0 is dense. Its own ``--break-reference``:
  ``layer0_qkv_weight`` x 1.25, the first layer's scores and values, in a
  shallow copy of the dict;
- ``model_flops``, which the hybrid's ``Loop`` calls for a step at the lanes'
  own contexts and for an admission over the prompt's real tokens with one
  row of logits, counts this block's own equations.

``step_bytes``, the bytes a decode step NEEDS by the layer equations, lives
here too: ``kernels.hbm_share.swa`` reads it.
"""
import jax
import jax.numpy as jnp
import numpy as np

from harness.spec import load_module

_hybrid = load_module("drivers", "paged_closed_loop_hybrid")
_lfm2 = load_module("drivers", "paged_closed_loop_lfm2")
sample_program, kth_smallest = _lfm2.sample_program, _lfm2.kth_smallest
POOLED, A_PROMPT = _lfm2.POOLED, _lfm2.A_PROMPT

BROKEN = "layer0_qkv_weight"    # what --break-reference perturbs, x 1.25
_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _counts(m):
    """The block's sizes by the layer equations
    (``reference/mimo_v2_flash_decoder.py``): (the matrices every token
    passes outside the routed experts and the head: q, k, v and the output
    projection of both kinds of attention, the dense MLPs, the routers; the
    vectors beside them: layer norms, sinks, router biases, the final norm;
    the head's slice; ONE expert; key and value numbers a token keeps over
    all FULL layers; the same over all WINDOW layers)."""
    d, hq, dk, dv = (m[k] for k in ("model_dim", "num_heads", "head_dim",
                                    "v_head_dim"))
    windowed, sparse = m["hybrid_layer_pattern"], m["moe_layer_freq"]
    n_win, n_layers, n_sparse = sum(windowed), len(windowed), sum(sparse)
    full_kv = (n_layers - n_win) * m["num_kv_heads"] * (dk + dv)
    win_kv = n_win * m["swa_num_kv_heads"] * (dk + dv)
    matrices = n_layers * hq * (dk + dv) * d + (full_kv + win_kv) * d \
        + (n_layers - n_sparse) * 3 * d * m["ffn_dim"] \
        + n_sparse * m["num_experts"] * d
    vectors = n_layers * 2 * d + n_win * hq + n_sparse * m["num_experts"] + d
    return matrices, vectors, m["vocab_size"] * d, \
        3 * d * m["moe_ffn_dim"], full_kv, win_kv


def sizes(m):
    """(parameters a step reads whatever the router does, the head's slice
    among them; parameters of ONE expert; key and value numbers a token keeps
    over all FULL layers; the same over all WINDOW layers)."""
    matrices, vectors, head, expert, full_kv, win_kv = _counts(m)
    return matrices + vectors + head, expert, full_kv, win_kv


def model_flops(m, tokens, context_tokens, head_rows):
    """FLOP (2 x MACs) the layer equations need HERE for ``tokens`` tokens
    through every layer: the qkv and output projections of both kinds of
    attention, the dense layers' three matrices, an expert layer's router
    over all its experts and the HELD experts' three matrices at the share
    even routing sends them (``num_experts_per_tok`` x held / routed-over
    experts a token: 0.5 here; ``moe.local_rows_share`` says how far the
    routing is from even); a full layer's scores and apply over
    ``context_tokens`` (each token's context summed, the causal half of a
    prompt not discounted, as ``flops.py`` counts it) and a window layer's
    over ``sliding_window`` keys a token (the first window of a prompt
    over-counted by half); and the vocabulary head for ``head_rows``
    positions."""
    matrices, _, head, expert, full_kv, win_kv = _counts(m)
    groups = lambda kv, heads: kv // heads   # layers x (dk + dv)
    held = m.get("num_local_experts") or m["num_experts"]
    experts = sum(m["moe_layer_freq"]) * expert * m["num_experts_per_tok"] \
        * held / m["num_experts"]
    hq = m["num_heads"]
    return float(
        tokens * (2 * (matrices + experts) + m["sliding_window"] * 2 * hq
                  * groups(win_kv, m["swa_num_kv_heads"]))
        + context_tokens * 2 * hq * groups(full_kv, m["num_kv_heads"])
        + head_rows * 2 * head)


def step_bytes(model, dtype, steps, tokens, context_tokens, window_slots,
               experts_touched):
    """Bytes ``steps`` decode steps need that stepped ``tokens`` lanes in all
    over ``context_tokens`` tokens of context, found ``window_slots`` live
    slots in a window layer's rings and touched ``experts_touched`` held
    experts (summed over layers and steps), everything in ``dtype``: in
    every step the weights outside the routed experts once, the head's slice
    among them; three matrices for every held expert that received at least
    one row; a full layer's key and value rows read for every token of a
    stepped lane's own context and written for every stepped lane; a window
    layer's read for every live slot of a stepped lane's ring and written
    for every stepped lane."""
    always, expert, full_kv, win_kv = sizes(model)
    return _BYTES[dtype] * (
        steps * always + experts_touched * expert
        + (context_tokens + tokens) * full_kv
        + (window_slots + tokens) * win_kv)


class _KeepsState:
    """The decoder as ``sample_program`` drives it, which also keeps the
    first window layer's key ring of each sampled lane as its admission left
    it and as its last decode step did: ``states`` is [(after admit, after
    the last step)], float32 copies (a view would follow the device's buffer
    into its next use)."""

    def __init__(self, dec):
        self._dec, self.states, self._admitted = dec, [], {}
        self._name = "ring_k_%d" % next(
            int(n.rsplit("_", 1)[1]) for n, kind, _ in dec._cache
            if kind == "ring")

    def __getattr__(self, name):
        return getattr(self._dec, name)

    def _ring(self, seq):
        return np.array(self._dec.lane_state(seq, (self._name,))[self._name],
                        dtype=np.float32)

    def admit(self, prompt):
        seq, logits = self._dec.admit(prompt)
        self._admitted[seq] = self._ring(seq)
        return seq, logits

    def retire(self, seq):
        self.states.append((self._admitted.pop(seq), self._ring(seq)))
        self._dec.retire(seq)


def ring_error(ring, keys, upto):
    """A kept ring (Hkv, W, dk) against the reference's rotated keys
    (Hkv, T, dk) once position ``upto`` is written: the ring holds positions
    ``upto - W + 1 .. upto`` (from 0 while it fills), position p at slot
    p mod W; relative L2 over those slots. What a slot past them holds is
    not compared: the read masks it."""
    window = ring.shape[1]
    held = np.arange(max(0, upto - window + 1), upto + 1)
    got = np.asarray(ring, np.float64)[:, held % window]
    want = np.asarray(keys, np.float64)[:, held]
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def check_against_reference(run, params, sampled, states):
    """Two comparisons with the reference, both must hold. The logits: each
    sampled row against the full forward over the whole sequence at the same
    position, held to the fifth smallest of all the rows and to each prompt's
    second smallest (``paged_closed_loop_lfm2.kth_smallest`` says why not to
    the worst). The first window layer's key ring of each sampled lane after
    its admission, against the reference's rotated keys of the prompt's last
    128 positions, and after its last step, against those of the last 128
    fed: what a ring handed over at the bucket's end, written at a wrong
    slot, rotated at the other kind's base or left from the lane's last
    occupant moves, and seven layers of bfloat16 rounding and flipped
    experts would blur."""
    model, chk = run.config["model"], run.config["check"]
    ref = run.reference()
    if run.break_reference:
        params = dict(params, **{BROKEN: params[BROKEN] * 1.25})

    @jax.jit
    def errors(p, tokens, got):
        want = ref.logits(p, tokens, model, last=got.shape[0])
        return jnp.linalg.norm(got - want, axis=-1) / (
            jnp.linalg.norm(want, axis=-1) + 1e-30)

    keys = jax.jit(lambda p, tokens: ref.first_window_keys(p, tokens, model))

    rows = [np.asarray(errors(params, jnp.asarray(toks), jnp.asarray(got)))
            for toks, got in sampled]
    pooled = kth_smallest(np.concatenate(rows), POOLED)
    held = [kth_smallest(e, A_PROMPT) for e in rows]
    run.notes["check_rows_sorted"] = [[float("%.3g" % x) for x in np.sort(e)]
                                      for e in rows]
    good = bool(np.isfinite(np.concatenate(rows)).all()) \
        and pooled <= chk["logits_rel_l2"] \
        and max(held) <= chk["logits_rel_l2_a_prompt"]
    rings = []
    for (toks, got), (admitted, last) in zip(sampled, states):
        want = np.asarray(keys(params, jnp.asarray(toks)))
        # every token of ``toks`` was fed: the prompt, then a step each
        ends = (len(toks) - len(got), len(toks) - 1)
        rings.append([ring_error(ring, want, at)
                      for ring, at in zip((admitted, last), ends)])
    rings = np.asarray(rings)
    sound = bool(np.isfinite(rings).all()) \
        and rings.max() <= chk["ring_keys_rel_l2"]
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
        "the first window layer's key ring of each sampled lane vs the "
        "reference's rotated keys at the positions it holds, relative L2: "
        "after the admission (the prompt's last window) %s, after the last "
        "step %s (limit %.1e) %s"
        % (", ".join("%.3e" % e for e in rings[:, 0]),
           ", ".join("%.3e" % e for e in rings[:, 1]),
           chk["ring_keys_rel_l2"], "ok" if sound else "FAIL")]


_hybrid.sample_program = sample_program
_hybrid.check_against_reference = check_against_reference
_hybrid.model_flops = model_flops
_hybrid._KeepsState = _KeepsState


def run(run):
    """The hybrid's ``run`` with the five names above; a traced run's notes
    also say how many HELD experts a step touched, a layer (the program's
    counter; what ``kernels.hbm_share.swa`` counts an expert's bytes by)."""
    obs = _hybrid.run(run)
    c, m = run.counters_window or {}, run.config["model"]
    steps = c.get("serving.paged_steps")
    if steps and "serving.moe.step_experts_touched" in c:
        run.notes["held_experts_touched_a_step_and_layer"] = \
            c["serving.moe.step_experts_touched"] / (
                steps * sum(m["moe_layer_freq"]))
    return obs
