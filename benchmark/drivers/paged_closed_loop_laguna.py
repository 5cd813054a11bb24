"""Driver ``paged_closed_loop_laguna``: the closed loop of
``paged_closed_loop_hybrid`` (its ``run``: the decoder built with the
configuration's ``dtype`` and ``serving.prefill_len`` and warmed without the
warm dispatch's outputs, the warm-up grid, the staggered ramp, the window, the
result's keys) around a ``serving.PagedKVDecoder`` of the Laguna block
(``arch="laguna"``): four window layers of 72 query heads whose last 512 keys
and values a lane ride in per-lane rings beside the paged pools of two full
layers of 48, all over 8 key/value heads; a q/k norm and a gate a head; YaRN
on the full layers' partial rotary alone; expert layers that hold 64 of the
256 experts they route over beside a shared one. Everything a window-and-full
block's check needs is ``paged_closed_loop_mimo``'s and is taken from there,
not copied: ``sample_program`` (DRAWN tokens in the steps; lfm2's, which
dots3's copy of the hybrid already holds), ``_KeepsState``
(the first window layer's key ring after the admission and after the last
step), ``check_against_reference`` (the logits held to the fifth smallest of
all the sample's rows and to each prompt's second smallest; the two kept
rings against ``reference.first_window_keys``) and ``ring_error``. What is
this file's:

- ``--break-reference`` perturbs ``layer0_mlp_out_weight`` x 1.25, the dense
  layer's output, in a shallow copy of the dict: mimo's ``layer0_qkv_weight``
  would move the VALUES of one full layer alone here (a q/k norm takes the
  scale off q and k), a few hundredths of the residual stream at a long
  context;

- the loop is ``paged_closed_loop_dots3``'s: a request's OUTPUT length goes
  by the order of issue and not by the caller (its ``Loop`` says how). A 20 s
  window holds about one and a half rounds of 32 requests here and three
  quarters of the device's time are admissions of 0.24 s, so which lengths
  fell inside it was the seed's luck: four seeds read 821-918 tokens/s
  (8.4% between the quartiles, PERF.md section 6, PR 58) where six read
  827-862 (2.8%) with the order fixed;
- ``model_flops``, which the hybrid's ``Loop`` calls for a step at the lanes'
  own contexts and for an admission over the prompt's real tokens with one
  row of logits, counts this block's own equations (two head counts, the
  gate, the shared expert);
- ``sizes`` and ``step_bytes``, the bytes a decode step NEEDS by the layer
  equations: ``kernels.hbm_share.swa_heads`` reads them.
"""
from harness.spec import load_module

# dots3's copy of the hybrid's module, whose ``Loop`` is already the one that
# hands the output lengths out by the order of issue; the rest of a
# window-and-full block's check is mimo's, from dots3's copy of THAT module
_dots3 = load_module("drivers", "paged_closed_loop_dots3")
_mimo, _hybrid = _dots3._mimo, _dots3._hybrid
ring_error, check_against_reference = _mimo.ring_error, \
    _mimo.check_against_reference
_hybrid.check_against_reference = check_against_reference
_hybrid._KeepsState = _mimo._KeepsState

_mimo.BROKEN = BROKEN = "layer0_mlp_out_weight"     # x 1.25
_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
FULL, WINDOW = "full_attention", "sliding_attention"


def _counts(m):
    """The block's sizes by the layer equations
    (``reference/laguna_decoder.py``): (the matrices every token passes
    outside the routed experts and the head: q, k, v, the gate and the output
    projection of both kinds of attention, the dense MLP, the routers, the
    shared experts; the vectors beside them: layer norms, q/k norms, router
    biases, the final norm; the head's slice; ONE expert; key and value
    numbers a token keeps over all FULL layers; the same over all WINDOW
    layers)."""
    d, dh, hkv = m["model_dim"], m["head_dim"], m["num_kv_heads"]
    kinds = list(m["layer_types"])
    n_full, n_win = kinds.count(FULL), kinds.count(WINDOW)
    n_dense = m["first_dense_layers"]
    n_sparse = len(kinds) - n_dense
    # q, k, v rows, a gate row a head, the output projection's columns
    attention = lambda heads: ((heads + 2 * hkv) * dh + heads + heads * dh) * d
    matrices = n_full * attention(m["num_heads"]) \
        + n_win * attention(m["swa_num_heads"]) \
        + n_dense * 3 * d * m["ffn_dim"] \
        + n_sparse * (m["num_experts"] * d
                      + 3 * d * m["num_shared_experts"] * m["moe_ffn_dim"])
    vectors = len(kinds) * (2 * d + 2 * dh) + n_sparse * m["num_experts"] + d
    return matrices, vectors, m["vocab_size"] * d, \
        3 * d * m["moe_ffn_dim"], n_full * hkv * 2 * dh, n_win * hkv * 2 * dh


def sizes(m):
    """(parameters a step reads whatever the router does, the head's slice
    among them; parameters of ONE expert; key and value numbers a token keeps
    over all FULL layers; the same over all WINDOW layers)."""
    matrices, vectors, head, expert, full_kv, win_kv = _counts(m)
    return matrices + vectors + head, expert, full_kv, win_kv


def model_flops(m, tokens, context_tokens, head_rows):
    """FLOP (2 x MACs) the layer equations need HERE for ``tokens`` tokens
    through every layer: the qkv, gate and output projections of both kinds
    of attention, the dense layer's three matrices, an expert layer's router
    over all its experts, its shared expert and the HELD experts' three
    matrices at the share even routing sends them (``num_experts_per_tok`` x
    held / routed-over experts a token: 2.5 here; ``moe.local_rows_share``
    says how far the routing is from even); a full layer's scores and apply
    over ``context_tokens`` (each token's context summed, the causal half of
    a prompt not discounted, as ``flops.py`` counts it) at ``num_heads``
    query heads and a window layer's over ``sliding_window`` keys a token at
    ``swa_num_heads`` (the first window of a prompt over-counted by half);
    and the vocabulary head for ``head_rows`` positions."""
    matrices, _, head, expert, _, _ = _counts(m)
    kinds = list(m["layer_types"])
    held = m.get("num_local_experts") or m["num_experts"]
    experts = (len(kinds) - m["first_dense_layers"]) * expert \
        * m["num_experts_per_tok"] * held / m["num_experts"]
    pair = lambda heads: 2 * heads * 2 * m["head_dim"]  # a score and an apply
    return float(
        tokens * (2 * (matrices + experts) + kinds.count(WINDOW)
                  * m["sliding_window"] * pair(m["swa_num_heads"]))
        + context_tokens * kinds.count(FULL) * pair(m["num_heads"])
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


_hybrid.model_flops = model_flops


def run(run):
    """The hybrid's ``run`` with mimo's check and this block's FLOP; a traced
    run's notes also say how many HELD experts a step touched, a layer (the
    program's counter; what ``kernels.hbm_share.swa_heads`` counts an
    expert's bytes by)."""
    obs = _hybrid.run(run)
    c, m = run.counters_window or {}, run.config["model"]
    steps = c.get("serving.paged_steps")
    if steps and "serving.moe.step_experts_touched" in c:
        run.notes["held_experts_touched_a_step_and_layer"] = \
            c["serving.moe.step_experts_touched"] / (
                steps * (len(m["layer_types"]) - m["first_dense_layers"]))
    return obs
