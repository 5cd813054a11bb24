"""Driver ``paged_closed_loop``: callers that each wait for their reply, one
per lane at most, through ``serving.PagedKVDecoder``.

The loop is the one ``PagedKVDecoder.greedy`` runs (``k =
decode_megastep_k()``: ``step`` and an arg-max on the host when 1,
``step_megastep`` otherwise), with admission and retirement per sequence:
admit every waiting caller that finds a free lane, one dispatch for all
active lanes, retire the finished, and a caller issues its next request the
moment its last one retires. A request of one output token is complete when
``admit`` returns, so such a mix never dispatches a decode step.

No scheduler joins the decoders to the serving engine yet (ROADMAP R1), so
there is no queue in the program for an open loop to load: this in-process
loop is what the decoder's callers are today.

Configuration keys it reads: ``model`` (the decoder's sizes), ``serving``
{max_len, page_size, lanes}, ``init``, ``reference``, ``check``
{logits_rel_l2}. Traffic keys: ``callers`` (a number, or
"lanes"), ``fields`` {prompt_len, output_len} (harness/traffic.py),
``ramp_dispatches``, ``check_decode_steps``.

The program's defaults are what is measured: no MXNET_* variable is set, no
``k``, ``prefix_cache`` or dtype is passed.
"""
import collections
import time

import jax
import jax.numpy as jnp
import numpy as np

import flops
import mxnet_tpu as mx
from harness import traffic as traffic_mod
from harness import weights
from mxnet_tpu.serving import PagedKVDecoder
from mxnet_tpu.serving.kv_decode import decode_megastep_k


def param_shapes(model, positions):
    """The checkpoint's parameter shapes, from the sizes alone (the layout
    is documented in reference/transformer_decoder.py)."""
    d, ffn, vocab = model["model_dim"], model["ffn_dim"], model["vocab_size"]
    shapes = {"embed_weight": (vocab, d), "pos_embed_weight": (positions, d),
              "final_ln_gamma": (d,), "final_ln_beta": (d,),
              "lm_head_weight": (vocab, d), "lm_head_bias": (vocab,)}
    for i in range(model["num_layers"]):
        n = "layer%d_" % i
        shapes.update({
            n + "ln1_gamma": (d,), n + "ln1_beta": (d,),
            n + "qkv_weight": (3 * d, d), n + "qkv_bias": (3 * d,),
            n + "proj_weight": (d, d), n + "proj_bias": (d,),
            n + "ln2_gamma": (d,), n + "ln2_beta": (d,),
            n + "ffn1_weight": (ffn, d), n + "ffn1_bias": (ffn,),
            n + "ffn2_weight": (d, ffn), n + "ffn2_bias": (d,)})
    return shapes


def grid_lengths(field):
    """Every prompt length a field can draw: what set-up has to warm."""
    if field["dist"] == "const":
        return [int(field["value"])]
    return sorted(int(v) for v in field.get("grid") or field["values"])


def largest(field):
    """The largest value a field can draw."""
    if field["dist"] == "lognormal":
        return max(field.get("grid") or field["clip"])
    return max(grid_lengths(field))


def decode(dec, feed, k):
    """One dispatch for the sequences of ``feed`` ({seq_id: token}), by the
    path ``PagedKVDecoder.greedy`` takes for this ``k``."""
    return dec.step(feed) if k == 1 else dec.step_megastep(feed, k=k)


class Request:
    __slots__ = ("caller", "prompt", "want", "issued", "in_window", "seq",
                 "last", "got", "t_last")

    def __init__(self, caller, spec, issued, in_window):
        self.caller = caller
        self.prompt = spec["tokens"]
        self.want = int(spec["output_len"])
        self.issued = issued
        self.in_window = in_window
        self.seq = self.last = self.t_last = None
        self.got = 0


class Loop:
    """The closed loop. One round is ``admit_waiting`` then ``dispatch``
    (which retires the finished). Token arrivals are recorded while
    ``recording``."""

    def __init__(self, run, dec, callers, k):
        self.run, self.dec, self.k = run, dec, k
        self.callers = callers
        self.waiting = collections.deque()
        self.active = {}            # seq_id -> Request
        self.recording = False
        self.t_open = None
        self.tokens = 0             # arrived while recording
        self.ttft, self.itl = [], []
        self.issued = self.completed = self.failed = 0
        self.dispatch_s, self.busy_lanes = [], []
        self.model_flops = 0.0

    def issue(self, caller, now):
        req = Request(caller, caller.next_request(), now, self.recording)
        if self.recording:
            self.issued += 1
        self.waiting.append(req)

    def _token(self, req, now, first):
        """One token of ``req`` reached its caller at ``now``."""
        if self.recording:
            self.tokens += 1
            if first:
                if req.in_window:
                    self.ttft.append(now - req.issued)
            elif req.t_last is not None and req.t_last >= self.t_open:
                self.itl.append(now - req.t_last)
        req.t_last = now
        req.got += 1

    def _retire(self, req, now):
        self.dec.retire(req.seq)
        del self.active[req.seq]
        if req.in_window:
            self.completed += 1
        self.issue(req.caller, now)

    def admit_waiting(self, deadline=None):
        """Admit the callers waiting now, while lanes are free."""
        for _ in range(len(self.waiting)):
            if len(self.active) >= self.dec.lanes:
                break
            if deadline is not None and time.perf_counter() >= deadline:
                break
            req = self.waiting.popleft()
            try:
                with self.run.annotate("bench.admit"):
                    req.seq, logits = self.dec.admit(req.prompt)
                with self.run.annotate("bench.sample"):
                    req.last = int(np.argmax(logits))
            except mx.MXNetError as exc:  # PagedKVExhausted included
                self.run.say("admit failed: %s" % exc)
                if req.in_window:
                    self.failed += 1
                self.issue(req.caller, time.perf_counter())
                continue
            now = time.perf_counter()
            self.active[req.seq] = req
            self._token(req, now, first=True)
            if req.got >= req.want:
                with self.run.annotate("bench.retire"):
                    self._retire(req, now)

    def dispatch(self):
        """One decode dispatch for every active lane."""
        if not self.active:
            return
        feed = {seq: req.last for seq, req in self.active.items()}
        if self.recording:
            self.model_flops += self._flops(feed)
        t0 = time.perf_counter()
        with self.run.annotate("bench.step"):
            out = decode(self.dec, feed, self.k)
        with self.run.annotate("bench.sample"):
            if self.k == 1:
                new = {s: [int(np.argmax(lg))] for s, lg in out.items()}
            else:
                new = {s: [int(t) for t in ids] for s, ids in out.items()}
        now = time.perf_counter()
        if self.recording:
            self.dispatch_s.append(now - t0)
            self.busy_lanes.append(len(feed))
        with self.run.annotate("bench.retire"):
            for seq, toks in new.items():
                req = self.active[seq]
                # a megastep may run past the request's end: the surplus
                # tokens were computed but nobody asked for them
                for tok in toks[:req.want - req.got]:
                    self._token(req, now, first=False)
                req.last = toks[-1]
                if req.got >= req.want:
                    self._retire(req, now)

    def _flops(self, feed):
        m = self.run.config["model"]
        # the token fed now sits at position prompt + got - 1 and attends
        # over itself and everything before it
        contexts = [len(self.active[seq].prompt) + self.active[seq].got
                    for seq in feed]
        return self.k * flops.decode_step_flops(
            contexts, m["model_dim"], m["num_layers"], m["ffn_dim"],
            m["vocab_size"])

    def drain(self):
        for seq in list(self.active):
            self.dec.retire(seq)
        self.active.clear()
        self.waiting.clear()


def sample_program(run, dec):
    """The program's side of the check: for a seeded sample of prompts (the
    shortest, a middle and the longest length of the grid), the logits
    ``admit`` returns and those of the following single decode steps
    through the cache. [(tokens, logits rows)]."""
    steps = int(run.traffic["check_decode_steps"])
    lengths = grid_lengths(run.traffic["fields"]["prompt_len"])
    picks = sorted({lengths[0], lengths[len(lengths) // 2], lengths[-1]})
    rng = np.random.default_rng([run.seed, 77])
    sampled = []
    for length in picks:
        prompt = rng.integers(1, run.config["model"]["vocab_size"],
                              size=length)
        seq, logits = dec.admit(prompt.astype(np.float32))
        got, toks = [np.asarray(logits)], list(prompt)
        for _ in range(steps):
            toks.append(int(np.argmax(got[-1])))
            got.append(np.asarray(dec.step({seq: toks[-1]})[seq]))
        dec.retire(seq)
        sampled.append((np.asarray(toks, np.int32), np.stack(got)))
    return sampled


def check_against_reference(run, params, sampled):
    """Each sampled row against the reference's full forward over the whole
    sequence at the same position: logits, not tokens."""
    cfg, chk = run.config, run.config["check"]
    ref = run.reference()
    if run.break_reference:
        params = {k: v * 1.25 if k.endswith("_weight") else v
                  for k, v in params.items()}

    @jax.jit
    def errors(p, tokens, got):
        want = ref.logits(p, tokens, cfg["model"])[-got.shape[0]:]
        diff = jnp.linalg.norm(got - want, axis=-1)
        return diff / (jnp.linalg.norm(want, axis=-1) + 1e-30)

    worst = max(float(np.max(np.asarray(errors(
        params, jnp.asarray(toks), jnp.asarray(got)))))
        for toks, got in sampled)
    rows = sum(len(got) for _, got in sampled)
    good = bool(np.isfinite(worst)) and worst <= chk["logits_rel_l2"]
    return good, ["logits of admit and %d decode steps at prompt lengths %s "
                  "vs the reference's full forward: worst row relative L2 "
                  "%.3e over %d rows (limit %.1e) %s"
                  % (len(sampled[0][1]) - 1,
                     [len(t) - len(g) + 1 for t, g in sampled], worst, rows,
                     chk["logits_rel_l2"], "ok" if good else "FAIL")]


def run(run):
    cfg, traffic = run.config, run.traffic
    model, serving = cfg["model"], cfg["serving"]
    lanes = int(serving["lanes"])
    n_callers = lanes if traffic["callers"] == "lanes" \
        else int(traffic["callers"])
    if n_callers > lanes:
        raise ValueError("%d callers on %d lanes: a closed loop keeps at "
                         "most one request per lane" % (n_callers, lanes))
    traffic = dict(traffic, callers=n_callers)

    ctx = mx.current_context()
    params = weights.make(param_shapes(model, serving["max_len"]),
                          cfg["init"], run.seed)
    dec = PagedKVDecoder(
        {k: mx.nd.NDArray(v, ctx=ctx) for k, v in params.items()},
        max_len=serving["max_len"], page_size=serving["page_size"],
        lanes=lanes, ctx=ctx, **model)
    run.mark("weights on the device, decoder built")
    dec.warmup()
    run.mark("compile or load prefill and decode")
    k = decode_megastep_k()

    # warm exactly the shapes this traffic uses: admit compiles small
    # programs per prompt length (PERF.md), the decode dispatch once
    lengths = grid_lengths(traffic["fields"]["prompt_len"])
    decodes = largest(traffic["fields"]["output_len"]) > 1
    for length in lengths:
        seq, logits = dec.admit(np.ones((length,), np.float32))
        if decodes:
            decode(dec, {seq: int(np.argmax(logits))}, k)
        dec.retire(seq)
    run.mark("warm %d prompt lengths" % len(lengths))
    sampled = sample_program(run, dec)
    peak = run.memory_peak()  # the program's own, before the reference
    ok, checks = check_against_reference(run, params, sampled)
    run.mark("reference check")

    # reach the steady state before the window opens: every caller's first
    # request is taken part-way through its output, as if it had been
    # running when we arrived, so retirements are staggered from the start
    loop = Loop(run, dec, traffic_mod.callers(traffic, run.seed,
                                              model["vocab_size"]), k)
    part = traffic_mod.strata(run.seed, "stagger", 0, n_callers)
    now = time.perf_counter()
    for caller in loop.callers:
        loop.issue(caller, now)
        req = loop.waiting[-1]
        req.want = max(1, int(np.ceil(req.want * part[caller.index])))
    for _ in range(int(traffic["ramp_dispatches"])):
        loop.admit_waiting()
        loop.dispatch()

    run.mark("ramp to the steady state")
    t0 = run.open_window()
    deadline = t0 + run.seconds
    loop.recording, loop.t_open = True, t0
    while time.perf_counter() < deadline:
        loop.admit_waiting(deadline)
        if time.perf_counter() >= deadline:
            break
        loop.dispatch()
    t1 = time.perf_counter()
    loop.recording = False
    run.close_window()
    in_flight = loop.issued - loop.completed - loop.failed
    loop.drain()

    stats = dec.stats()
    clean = stats["active"] == 0 and stats["pages_in_use"] == 0
    checks.append("every lane retired and every page returned: %s"
                  % ("ok" if clean else "FAIL %r" % (stats,)))
    quiet = run.compiles_window["requests"] == 0
    checks.append("compile requests inside the window: %d %s"
                  % (run.compiles_window["requests"],
                     "ok" if quiet else "FAIL"))
    obs = {
        "correct": bool(ok and clean and quiet and loop.failed == 0),
        "checks": checks, "attempted": loop.issued, "failed": loop.failed,
        "elapsed_s": t1 - t0, "tokens_in_window": loop.tokens,
        "ttft_s": loop.ttft, "itl_s": loop.itl,
        "dispatch_s": loop.dispatch_s,
        "model_flops_in_window": loop.model_flops,
        "memory_peak_bytes": peak,
    }
    run.notes.update(
        k=k, lanes=lanes, callers=n_callers, completed=loop.completed,
        in_flight_at_close=in_flight, dispatches=len(loop.dispatch_s),
        ttft_samples=len(loop.ttft), itl_samples=len(loop.itl),
        dispatch_ms_p50=1e3 * float(np.median(loop.dispatch_s))
        if loop.dispatch_s else None,
        busy_lanes_mean=float(np.mean(loop.busy_lanes))
        if loop.busy_lanes else None,
        itl_ms_percentiles={q: 1e3 * float(np.percentile(loop.itl, q))
                            for q in (50, 90, 95, 99)} if loop.itl else None,
        ttft_ms_percentiles={q: 1e3 * float(np.percentile(loop.ttft, q))
                             for q in (25, 50, 75, 95)} if loop.ttft else None)
    return obs
