"""Driver ``paged_closed_loop_dots3``: the closed loop of
``paged_closed_loop_hybrid`` (its ``run``: the decoder built with the
configuration's ``dtype`` and ``serving.prefill_len`` and warmed without the
warm dispatch's outputs, the warm-up grid, the staggered ramp, the window, the
result's keys) around a ``serving.PagedKVDecoder`` of the dots3-note block
(``arch="dots3_note"``): latent attention with a query-side low rank in two
geometries, a full layer's read a learned selection (an indexer's keys in a
second pool on the same page table, the ``index_topk`` best rows of the
latent pool read and no others), a window layer's a ring of latents, and
expert layers that hold 16 of the 256 experts they route over beside a shared
one. The hybrid's ``run`` is taken as it is, in a copy of its module private
to this one (``load_module`` makes a new module each time it is called), in
which five names it looks up are this file's:

- ``sample_program`` samples the prompt lengths the traffic's
  ``check_prompt_lens`` names and FEEDS DRAWN tokens in its steps, as
  ``paged_closed_loop_lfm2``'s does;
- ``_KeepsState`` keeps, of each sampled lane, the FIRST window layer's ring
  and the FIRST full layer's selected positions (``sparse_sel_<i>``), each
  twice: as the admission left it and as the last step did;
- ``check_against_reference`` is THREE comparisons, all must hold: the
  logits (each sampled row against the reference's full forward at the same
  position, ``reference.logits(..., last=)``), held to a stated k-th
  smallest of all the sample's rows and of each prompt's
  (``paged_closed_loop_lfm2.kth_smallest``: near-tied experts AND near-tied
  index scores at the last selected place flip under bfloat16, and neither
  is the model's function) and, so that most rows are bounded, to the
  MEDIAN of all the rows; the kept selections against the reference's
  ``top_k`` (``reference.first_selected``), as the share of the reference's
  positions the program chose too; the kept rings against the reference's
  rotated [c | k_r] at the positions a ring holds
  (``reference.first_window_rows``; ``paged_closed_loop_mimo.ring_error``).
  Its own ``--break-reference``: ``layer0_kvb_weight`` x 1.25, the matrix
  both of layer 0's attention paths read, in a shallow copy of the dict;
- ``model_flops``, which the hybrid's ``Loop`` calls for a step at the lanes'
  own contexts and for an admission over the prompt's real tokens with one
  row of logits, counts this block's own equations;
- ``Loop`` hands out the OUTPUT lengths in one order by issue, whatever the
  seed (its docstring says why: this window is shorter than one round of
  requests). The generator is left as the harness has it: callers, prompt
  lengths, tokens and the stagger are ``harness.traffic``'s and the seed's.

``step_bytes``, the bytes a decode step NEEDS by the layer equations, lives
here too: ``kernels.hbm_share.dsa`` reads it.
"""
import jax
import jax.numpy as jnp
import numpy as np

from harness import traffic
from harness.spec import load_module

_hybrid = load_module("drivers", "paged_closed_loop_hybrid")
_lfm2 = load_module("drivers", "paged_closed_loop_lfm2")
_mimo = load_module("drivers", "paged_closed_loop_mimo")
sample_program, kth_smallest = _lfm2.sample_program, _lfm2.kth_smallest
POOLED, A_PROMPT = _lfm2.POOLED, _lfm2.A_PROMPT
ring_error = _mimo.ring_error

BROKEN = "layer0_kvb_weight"    # what --break-reference perturbs, x 1.25
_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
FULL, WINDOW = "full_attention", "sliding_attention"


def _geometry(m, kind):
    """(heads, q rank, latent, nope, rope, value width) of a layer of
    ``kind``; a window layer reads the ``swa_`` keys."""
    own = lambda key: (m.get("swa_" + key) or m[key]) if kind == WINDOW \
        else m[key]
    return tuple(own(k) for k in (
        "num_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim"))


def _counts(m):
    """The block's sizes by the layer equations
    (``reference/dots3_note_decoder.py``): (the matrices every token passes
    outside the routed experts and the head: both kinds of attention with
    their gates, the indexers, the dense MLP, the routers, the shared
    experts; the vectors beside them; the head's slice; ONE expert; the
    numbers a token keeps in a full layer's latent pool, summed over the full
    layers; the same for their index keys; the same for the window layers'
    rings)."""
    d, kinds = m["model_dim"], list(m["layer_types"])
    n_full, n_win = kinds.count(FULL), kinds.count(WINDOW)
    n_dense = m["first_dense_layers"]
    n_sparse = len(kinds) - n_dense
    matrices = vectors = 0
    row = {}
    for kind, n in ((FULL, n_full), (WINDOW, n_win)):
        h, rank, lat, nope, rope, v = _geometry(m, kind)
        matrices += n * (rank * d + h * (nope + rope) * rank
                         + (lat + rope) * d + h * (nope + v) * lat + h * d
                         + h * v * d)
        vectors += n * (2 * d + rank + lat)
        row[kind] = lat + rope
    hi, di = m["index_n_heads"], m["index_head_dim"]
    matrices += n_full * (hi * di * m["q_lora_rank"] + di * d + hi * d)
    vectors += n_full * 2 * di + d
    shared = m["num_shared_experts"] * m["moe_ffn_dim"]
    matrices += n_dense * 3 * d * m["ffn_dim"] \
        + n_sparse * (m["num_experts"] * d + 3 * d * shared)
    vectors += n_sparse * m["num_experts"]
    return matrices, vectors, m["vocab_size"] * d, 3 * d * m["moe_ffn_dim"], \
        n_full * row[FULL], n_full * di, n_win * row[WINDOW]


def sizes(m):
    """(parameters a step reads whatever the router does, the head's slice
    among them; parameters of ONE expert; numbers a token keeps in the latent
    pools of all FULL layers; in their index pools; in the rings of all
    WINDOW layers)."""
    matrices, vectors, head, expert, latent, index, ring = _counts(m)
    return matrices + vectors + head, expert, latent, index, ring


def model_flops(m, tokens, context_tokens, head_rows):
    """FLOP (2 x MACs) the layer equations need HERE for ``tokens`` tokens
    through every layer: the matrices outside the routed experts (every
    head's key and value made of the latent, the materialised count), the
    HELD experts' three matrices at the share even routing sends them
    (``num_experts_per_tok`` x held / routed-over experts a token); a full
    layer's index logits over ``context_tokens`` (each token's context
    summed, the causal half of a prompt not discounted, as ``flops.py``
    counts it) and its scores and apply over the SELECTED keys, at most
    ``index_topk`` a token; a window layer's over ``sliding_window`` keys a
    token; and the vocabulary head for ``head_rows`` positions."""
    matrices, _, head, expert, _, _, _ = _counts(m)
    kinds = list(m["layer_types"])
    held = m.get("num_local_experts") or m["num_experts"]
    experts = (len(kinds) - m["first_dense_layers"]) * expert \
        * m["num_experts_per_tok"] * held / m["num_experts"]
    def pair(kind):     # a (query, key) pair's score and apply, all heads
        heads, _, _, nope, rope, v = _geometry(m, kind)
        return 2 * heads * (nope + rope + v)

    selected = min(context_tokens, tokens * m["index_topk"])
    return float(
        tokens * (2 * (matrices + experts) + kinds.count(WINDOW)
                  * m["sliding_window"] * pair(WINDOW))
        + kinds.count(FULL) * (
            context_tokens * 2 * m["index_n_heads"] * m["index_head_dim"]
            + selected * pair(FULL))
        + head_rows * 2 * head)


def step_bytes(model, dtype, steps, tokens, scored_slots, selected_slots,
               window_slots, experts_touched):
    """Bytes ``steps`` decode steps need that stepped ``tokens`` lanes in
    all, scored ``scored_slots`` index keys and read ``selected_slots``
    latent rows (both summed over the full layers, the program's
    ``serving.sparse.*`` counters), found ``window_slots`` live slots in A
    window layer's ring and touched ``experts_touched`` held experts (summed
    over layers and steps), everything in ``dtype``: in every step the
    weights outside the routed experts once, the head's slice among them;
    three matrices for every held expert that received at least one row; an
    index-key row for every token of a stepped lane's own context and a
    latent row for every SELECTED token only, whatever implements the read;
    a ring row for every live slot, every window layer; and the rows a
    stepped lane writes: a latent and an index key a full layer, a ring row
    a window layer."""
    always, expert, latent, index, ring = sizes(model)
    row = _geometry(model, FULL)
    return _BYTES[dtype] * (
        steps * always + experts_touched * expert
        + scored_slots * model["index_head_dim"]
        + selected_slots * (row[2] + row[4])
        + window_slots * ring + tokens * (latent + index + ring))


class _KeepsState:
    """The decoder as ``sample_program`` drives it, which also keeps the
    first window layer's ring and the first full layer's selected positions
    of each sampled lane as its admission left them and as its last decode
    step did: ``states`` is [((ring, selected) after admit, (ring, selected)
    after the last step)], float32 copies (a view would follow the device's
    buffer into its next use)."""

    def __init__(self, dec):
        self._dec, self.states, self._admitted = dec, [], {}
        first = lambda prefix: next(n for n, _, _ in dec._cache
                                    if n.startswith(prefix))
        self._names = (first("ring_c_"), first("sparse_sel_"))

    def __getattr__(self, name):
        return getattr(self._dec, name)

    def _kept(self, seq):
        rows = self._dec.lane_state(seq, self._names)
        return tuple(np.array(rows[n], dtype=np.float32)
                     for n in self._names)

    def admit(self, prompt):
        seq, logits = self._dec.admit(prompt)
        self._admitted[seq] = self._kept(seq)
        return seq, logits

    def retire(self, seq):
        self.states.append((self._admitted.pop(seq), self._kept(seq)))
        self._dec.retire(seq)


def selection_agreement(chosen, allowed):
    """The share of the reference's selected positions (``allowed`` (T,)
    bool) that the program's kept row ``chosen`` (K,) names too (-1: none);
    a position the program names and the reference does not is one the
    reference names and the program does not, both hold ``index_topk``."""
    got = np.zeros(allowed.shape, bool)
    got[np.asarray(chosen[chosen >= 0], np.int64)] = True
    return float((got & allowed).sum() / max(int(allowed.sum()), 1))


def check_against_reference(run, params, sampled, states):
    """Three comparisons with the reference, all must hold (the
    configuration's ``check.why`` has every reading). The logits: each
    sampled row against the full forward over the whole sequence at the same
    position, held to the ``POOLED``-th smallest of all the rows, to each
    prompt's ``A_PROMPT``-th smallest and to the median of all the rows. The
    first full layer's selection of each sampled lane after its admission
    (the prompt's last real row) and after its last step, against the
    reference's ``top_k`` for the same query, as the share of positions both
    chose: layer 0 is dense and full, so no flipped expert reaches it. The first window layer's ring after the
    admission and after the last step against the reference's rotated
    [c | k_r] at the positions the ring holds."""
    model, chk = run.config["model"], run.config["check"]
    ref = run.reference()
    if run.break_reference:
        params = dict(params, **{BROKEN: params[BROKEN] * 1.25})

    @jax.jit
    def errors(p, tokens, got):
        want = ref.logits(p, tokens, model, last=got.shape[0])
        return jnp.linalg.norm(got - want, axis=-1) / (
            jnp.linalg.norm(want, axis=-1) + 1e-30)

    rows_of = jax.jit(lambda p, tokens: ref.first_window_rows(p, tokens,
                                                              model))
    chosen_of = jax.jit(lambda p, tokens, at: ref.first_selected(
        p, tokens, model, at))

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
    rings, agreed = [], []
    for (toks, got), kept in zip(sampled, states):
        # every token of ``toks`` was fed: the prompt, then a step each
        ends = (len(toks) - len(got), len(toks) - 1)
        want = np.asarray(rows_of(params, jnp.asarray(toks)))
        allowed = np.asarray(chosen_of(params, jnp.asarray(toks),
                                       jnp.asarray(ends, jnp.int32)))
        rings.append([ring_error(ring, want, at)
                      for (ring, _), at in zip(kept, ends)])
        agreed.append([selection_agreement(chosen, mask)
                       for (_, chosen), mask in zip(kept, allowed)])
    rings, agreed = np.asarray(rings), np.asarray(agreed)
    sound = bool(np.isfinite(rings).all()) \
        and rings.max() <= chk["ring_rows_rel_l2"]
    chosen_ok = bool(agreed.min() >= chk["selected_agreement"])
    return good and sound and chosen_ok, [
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
        "the first full layer's selected positions of each sampled lane vs "
        "the reference's top_k for the same query, share both chose: after "
        "the admission (the prompt's last row) %s, after the last step %s "
        "(limit %.3f) %s"
        % (", ".join("%.4f" % e for e in agreed[:, 0]),
           ", ".join("%.4f" % e for e in agreed[:, 1]),
           chk["selected_agreement"], "ok" if chosen_ok else "FAIL"),
        "the first window layer's ring of each sampled lane vs the "
        "reference's rotated [c | k_r] at the positions it holds, relative "
        "L2: after the admission (the prompt's last window) %s, after the "
        "last step %s (limit %.1e) %s"
        % (", ".join("%.3e" % e for e in rings[:, 0]),
           ", ".join("%.3e" % e for e in rings[:, 1]),
           chk["ring_rows_rel_l2"], "ok" if sound else "FAIL")]


def issue_order(n):
    """The place each of ``n`` consecutive issues takes among the ``n``
    midpoint quantiles of a round: the rank of the base-2 radical inverse
    (van der Corput's sequence; bit reversal where ``n`` is a power of two:
    0, 16, 8, 24, 4, ... of 32), so any aligned run of 2^k issues holds one
    from each 2^k-th of the distribution."""
    def inverse(j):
        x, half = 0.0, 0.5
        while j:
            x, j, half = x + half * (j & 1), j >> 1, half / 2
        return x
    return np.argsort(np.argsort([inverse(j) for j in range(n)]))


class Loop(_hybrid.Loop):
    """The hybrid's loop, in which a request's OUTPUT length goes by the
    order of issue and not by the caller. ``harness.traffic`` gives the k-th
    request of each of the n callers one of the n midpoint quantiles, which
    caller takes which drawn from the seed: whole rounds are the same work
    whatever the seed. Here an admission holds every lane for 35 steps'
    time and a window holds two thirds of ONE round (21 or 22 admissions of
    32), so WHICH lengths fall inside it was the seed's luck: six seeds read
    287-376 tokens/s, and the driver's check refused the cell for it
    (PERF.md section 6, PR 52). So the round's set of lengths is the
    generator's still (``traffic.quantile`` at the same midpoints) and its
    order is stratified too: the j-th issue of a round takes place
    ``issue_order(n)[j]``, whoever the caller. In the FIRST round, which the
    hybrid's ``run`` cuts short by its seed-drawn stagger, j is the rank of
    the caller's stagger share, so the (length, share) pairs the window
    opens on are the Hammersley set {(order[j], j)} whatever the seed. The
    seed still decides which caller holds which pair, every prompt's length
    and tokens, and the weights; it no longer decides how much work a
    window holds."""

    def __init__(self, run, dec, callers, k):
        super().__init__(run, dec, callers, k)
        n = len(callers)
        self._order, self._issues = issue_order(n), 0
        self._lengths = run.traffic["fields"]["output_len"]
        # the hybrid's ``run`` draws the same shares, by the same call
        self._share = traffic.strata(run.seed, "stagger", 0, n)

    def issue(self, caller, now):
        super().issue(caller, now)
        n, j = len(self.callers), self._issues
        self._issues += 1
        place = int(self._share[caller.index] * n) if j < n else j % n
        self.waiting[-1].want = traffic.quantile(
            self._lengths, (self._order[place] + 0.5) / n)


_hybrid.sample_program = sample_program
_hybrid.check_against_reference = check_against_reference
_hybrid.model_flops = model_flops
_hybrid._KeepsState = _KeepsState
_hybrid.Loop = Loop


def run(run):
    """The hybrid's ``run`` with the five names above; a traced run's notes
    also say how many HELD experts a step touched, a layer, and what share
    of a lane's context a step's read kept."""
    obs = _hybrid.run(run)
    c, m = run.counters_window or {}, run.config["model"]
    steps = c.get("serving.paged_steps")
    if steps and "serving.moe.step_experts_touched" in c:
        run.notes["held_experts_touched_a_step_and_layer"] = \
            c["serving.moe.step_experts_touched"] / (
                steps * (len(m["layer_types"]) - m["first_dense_layers"]))
    if c.get("serving.sparse.step_scored_slots"):
        run.notes["selected_share_of_scored"] = \
            c["serving.sparse.step_selected_slots"] \
            / c["serving.sparse.step_scored_slots"]
    return obs
