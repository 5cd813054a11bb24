"""Driver ``paged_closed_loop_phi4flash``: the closed loop of
``paged_closed_loop_hybrid`` (its ``run``: the decoder built with the
configuration's ``dtype`` and ``serving.prefill_len`` and warmed without the
warm dispatch's outputs, the warm-up grid, the staggered ramp, the window, the
result's keys; its ``_KeepsState``, which keeps the first layer's recurrent
state and convolution columns of each sampled lane as its last step left
them) around a ``serving.PagedKVDecoder`` of the Phi-4-mini-flash block
(``arch="phi4flash"``): a self-decoder of Mamba-1 rows and window rings, ONE
full-attention pool that layer 17 writes and it and the seven cross layers
behind it read, gated memory units on a tensor the step carries, differential
attention throughout. The hybrid's ``run`` is taken as it is, in a copy of
its module private to this one (``load_module`` makes a new module each time
it is called), in which three names it looks up are this file's:

- ``sample_program`` is ``paged_closed_loop_lfm2``'s: it samples the prompt
  lengths the traffic's ``check_prompt_lens`` names and FEEDS DRAWN tokens in
  its steps (a row then depends on the tokens of its own window and state,
  not on thirty-two copies of one);
- ``check_against_reference`` is TWO comparisons, both must hold: the logits
  (each sampled row against the reference's full forward at the same
  position, the head computed for the compared rows only:
  ``reference.logits(..., last=)``), held to their WORST row (no experts
  here, so no row may flip); and the first layer's recurrent state and
  convolution columns of each sampled lane after its last step against the
  reference's sequential recurrence (``reference.first_mixer_state``), the
  worst group of 128 channels. Its own ``--break-reference``:
  ``layer0_mamba1_out_weight`` x 1.25 in a shallow copy of the dict;
- ``Loop`` counts this block's own FLOP (``step_flops``,
  ``admission_flops``): an admission runs the cross-decoder on ONE row.

``step_bytes``, the bytes a decode step NEEDS by the layer equations, lives
here too: ``kernels.hbm_share.yoco`` reads it.
"""
import jax
import jax.numpy as jnp
import numpy as np

from harness.spec import load_module

_hybrid = load_module("drivers", "paged_closed_loop_hybrid")
sample_program = load_module("drivers",
                             "paged_closed_loop_lfm2").sample_program

BROKEN = "layer0_mamba1_out_weight"  # what --break-reference perturbs, x 1.25
GROUP = 128     # channels a group of the state's comparison holds
_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _widths(m):
    """(d, F, Hq, Hkv, dh, E, state, kernel, rank) of the model."""
    d = m["model_dim"]
    return (d, m["ffn_dim"], m["num_heads"], m["num_kv_heads"],
            m["head_dim"], m["mamba_expand"] * d, m["mamba_state"],
            m["mamba_conv"], m["mamba_dt_rank"])


def _counts(m):
    """The block's sizes by the layer equations
    (``reference/phi4_flash_decoder.py``), each a dict by kind of layer
    (``mamba``, ``window``, ``full``, ``gmu``, ``cross``): how many layers
    there are of it, and the parameters of one such layer's mixer. Beside
    them: the parameters of a layer outside its mixer (the MLP and two
    LayerNorms), of the embedding (also the head), and of the final norm."""
    d, f, hq, hkv, dh, e, s, k, r = _widths(m)
    n = m["num_layers"]
    half = n // 2
    layers = {"mamba": half // 2 + 1, "window": half // 2, "full": 1,
              "gmu": (n - half - 2) // 2, "cross": (n - half - 2) // 2}
    around = d * hq * dh + d + 4 * dh + 2 * dh   # out + bias, lambdas, sub-norm
    mixer = {
        "mamba": 2 * e * d + e * k + e + (r + 2 * s) * e + e * r + e
        + e * s + e + d * e,
        "window": (hq + 2 * hkv) * dh * (d + 1) + around,
        "gmu": 2 * e * d,
        "cross": hq * dh * (d + 1) + around}
    mixer["full"] = mixer["window"]
    return layers, mixer, 3 * d * f + 4 * d, m["vocab_size"] * d, 2 * d


def parameters(m):
    """Every parameter of the model: what a decode step reads of the weights,
    once."""
    layers, mixer, outside, table, final = _counts(m)
    return sum(layers[k] * mixer[k] for k in layers) \
        + m["num_layers"] * outside + table + final


def _layer_flops(m):
    """FLOP (2 x MACs) ONE token needs in one layer of each kind, outside
    attention's scores and apply: a Mamba-1 mixer's two projections, its K
    taps, x_proj and dt_proj and its recurrence (a state element takes a
    decay multiply-add, the outer product's multiply and the read-out's
    multiply-add: 5 a token); a memory unit's two matrices; an attention
    mixer's projections; and the MLP every layer has. (per kind, the MLP)."""
    d, f, hq, hkv, dh, e, s, k, r = _widths(m)
    return {"mamba": 2 * (3 * e * d + k * e + (r + 2 * s) * e + e * r)
            + 5 * e * s,
            "gmu": 4 * e * d,
            "self": 2 * ((hq + 2 * hkv) * dh * d + hq * dh * d),
            "cross": 4 * hq * dh * d}, 6 * d * f


def _read_flops(m):
    """FLOP one query row needs for one key of its context in one layer of
    differential attention: a pair of query heads takes two dot products of
    dh (q1 k1, q2 k2) and applies two softmaxes to BOTH value heads of its
    pair (2 dh wide): 2 x 2 dh + 2 x 2 x 2 dh = 12 dh a pair, 6 dh a query
    head, where ordinary attention takes 4."""
    return 6 * m["num_heads"] * m["head_dim"]


def step_flops(m, lanes, context_tokens):
    """FLOP the layer equations need for one decode step of ``lanes`` lanes
    whose contexts add up to ``context_tokens``: every layer's mixer and MLP
    a lane; the pool read by layer 17 and each cross layer over a lane's
    whole context; a window layer's ring over ``sliding_window`` keys a lane
    (a lane shorter than the window over-counted); the head a lane."""
    layers, _, _, table, _ = _counts(m)
    kind, mlp = _layer_flops(m)
    per_lane = layers["mamba"] * kind["mamba"] + layers["gmu"] * kind["gmu"] \
        + (layers["window"] + 1) * kind["self"] \
        + layers["cross"] * kind["cross"] + m["num_layers"] * mlp \
        + layers["window"] * m["sliding_window"] * _read_flops(m) + 2 * table
    return float(lanes * per_lane + context_tokens
                 * (1 + layers["cross"]) * _read_flops(m))


def admission_flops(m, tokens):
    """FLOP the layer equations need to admit a prompt of ``tokens`` real
    tokens (padding to the bucket is not credited) as THIS program does: the
    self-decoder's Mamba-1 and window layers and their MLPs, and layer 17's
    key and value projection, over every token (a window layer's scores over
    ``sliding_window`` keys a token, the first window over-counted by half);
    layer 17's query, read and output, its MLP, every layer behind it and
    the head over the LAST token alone, its reads over the prompt."""
    layers, _, _, table, _ = _counts(m)
    kind, mlp = _layer_flops(m)
    hkv, dh, d = m["num_kv_heads"], m["head_dim"], m["model_dim"]
    over_all = layers["mamba"] * kind["mamba"] + layers["window"] * (
        kind["self"] + m["sliding_window"] * _read_flops(m)) \
        + (layers["mamba"] + layers["window"]) * mlp + 2 * 2 * hkv * dh * d
    last_row = kind["self"] - 2 * 2 * hkv * dh * d \
        + layers["gmu"] * kind["gmu"] + layers["cross"] * kind["cross"] \
        + (1 + layers["gmu"] + layers["cross"]) * mlp \
        + tokens * (1 + layers["cross"]) * _read_flops(m) + 2 * table
    return float(tokens * over_all + last_row)


def step_bytes(model, dtype, steps, tokens, context_tokens, window_slots):
    """Bytes ``steps`` decode steps NEED that stepped ``tokens`` lanes in all
    over ``context_tokens`` tokens of context and found ``window_slots``
    live slots in a window layer's rings, weights, pool and rings in
    ``dtype``: in every step every weight once (the embedding's table as the
    head); the ONE pool's key and value rows read for every token of a
    stepped lane's own context once for EACH of the layers that read it
    (layer 17 and every cross layer: eight at the published depth) and
    written once a stepped lane; a window layer's rings read for every live
    slot of a stepped lane and written for every stepped lane; a Mamba-1
    layer's float32 state and convolution columns read and written for every
    stepped lane. Not counted: activations, ``m``, logits, the page table."""
    layers = _counts(model)[0]
    _, _, _, hkv, dh, e, s, k, _ = _widths(model)
    row = 2 * hkv * dh                      # a token's keys and values
    state = (s + k - 1) * e
    return _BYTES[dtype] * (
        steps * parameters(model)
        + row * ((1 + layers["cross"]) * context_tokens + tokens)
        + row * layers["window"] * (window_slots + tokens)) \
        + 4 * 2 * layers["mamba"] * state * tokens


_HybridLoop = _hybrid.Loop


class Loop(_HybridLoop):
    """The hybrid's loop with this block's FLOP: a step's for the lanes it
    steps at their own contexts, an admission's as the program runs it
    (past the hybrid's own count, to the old loop's ``_token``)."""

    def _token(self, req, now, first):
        if first and self.recording:
            self.model_flops += admission_flops(self.run.config["model"],
                                                len(req.prompt))
        super(_HybridLoop, self)._token(req, now, first)

    def _flops(self, feed):
        # the token fed now attends itself and everything before it
        contexts = sum(len(self.active[seq].prompt) + self.active[seq].got
                       for seq in feed)
        return step_flops(self.run.config["model"], len(feed), contexts)


def state_error(got, want):
    """The worst GROUP of 128 channels of a kept state ``got`` (N, E),
    state-major as the program keeps it, against the reference's ``want``
    (E, N): relative L2 a group. A channel whose decays sit near 1 compounds
    a rounding at every token, and over the whole tensor the fast channels'
    norm would hide it (granite-4.0-h-micro's worst head)."""
    got = np.asarray(got, np.float64).T.reshape(-1, GROUP * want.shape[1])
    want = np.asarray(want, np.float64).reshape(got.shape)
    return float(np.max(np.linalg.norm(got - want, axis=1)
                        / (np.linalg.norm(want, axis=1) + 1e-30)))


def check_against_reference(run, params, sampled, states):
    """Two comparisons with the reference, both must hold. The logits: each
    sampled row against the full forward over the whole sequence at the same
    position, the WORST row. The first layer's recurrent state of each
    sampled lane after its last step against the reference's sequential
    recurrence over the same tokens (``state_error``), and its convolution
    columns: what thirty-two layers of bfloat16 rounding hide from the
    logits is the precision the state is KEPT in."""
    model, chk = run.config["model"], run.config["check"]
    ref = run.reference()
    keep = jax.jit(lambda p, tokens: ref.first_mixer_state(p, tokens, model))
    kept = []
    for (toks, _), lane in zip(sampled, states):
        want_ssm, want_conv = (np.asarray(a) for a in keep(
            params, jnp.asarray(toks)))
        conv = np.asarray(lane["conv_state_0"], np.float64)
        kept.append([state_error(lane["ssm_state_0"], want_ssm),
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
    worst = float(max(e.max() for e in rows))
    run.notes["check_rows_sorted"] = [[float("%.3g" % x) for x in np.sort(e)]
                                      for e in rows]
    good = bool(np.isfinite(np.concatenate(rows)).all()) \
        and worst <= chk["logits_rel_l2"]
    return good and sound, [
        "logits of admit and %d decode steps at prompt lengths %s vs the "
        "reference's full forward, relative L2: the worst of %d rows %.3e "
        "(limit %.1e; a prompt's median %s, worst %s) %s"
        % (len(sampled[0][1]) - 1,
           [len(t) - len(g) + 1 for t, g in sampled],
           sum(len(e) for e in rows), worst, chk["logits_rel_l2"],
           ", ".join("%.3e" % np.median(e) for e in rows),
           ", ".join("%.3e" % e.max() for e in rows),
           "ok" if good else "FAIL"),
        "the first layer's state of each sampled lane after its last step "
        "vs the reference's sequential recurrence: worst group of %d "
        "channels, relative L2 %s, convolution columns %s (limit %.1e) %s"
        % (GROUP, ", ".join("%.3e" % e for e in kept[:, 0]),
           ", ".join("%.3e" % e for e in kept[:, 1]), chk["state_rel_l2"],
           "ok" if sound else "FAIL")]


_hybrid.sample_program = sample_program
_hybrid.check_against_reference = check_against_reference
_hybrid.Loop = Loop

run = _hybrid.run
