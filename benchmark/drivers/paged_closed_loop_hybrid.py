"""Driver ``paged_closed_loop_hybrid``: the closed loop of
``paged_closed_loop`` (its ``Loop`` with decode dispatches, its warm-up grid,
its sampled check, its staggered ramp) around a ``serving.PagedKVDecoder`` of
the architecture the configuration's ``model`` names, built as
``paged_closed_loop_arch`` builds one (the program's own
``param_shapes(**model)``, the configuration's ``dtype``, weights drawn on
the device one shape at a time). What neither of the two gives:

- the decoder takes the configuration's ``serving.prefill_len``: the prompt
  bucket is smaller than a lane's ``max_len``;
- the initialiser knows three more kinds beside ``ones`` and ``normal``, for
  the parameters of a state-space mixer: ``uniform`` {lo, hi},
  ``log_of_uniform`` {lo, hi} (the log of a uniform draw: ``A_log``) and
  ``inv_softplus_of_log_uniform`` {lo, hi} (x with softplus(x) log-uniform:
  ``dt_bias``, the ``mamba_ssm`` initialiser). They are drawn here, in the
  configuration's ``dtype``, each from its own key
  ``fold_in(seed, index of its name among the sorted names)``;
- ``--break-reference`` perturbs ``layer0_mamba_out_weight`` x 1.25 (the first
  layer here has no ``proj_weight``) in a shallow copy of the dict;
- the check has a second comparison, of the recurrent state itself
  (``check_against_reference``), and the decoder is warmed without the warm
  dispatch's outputs (``build``);
- ``model_flops_in_window`` is this block's own count (``model_flops``), for
  decode steps AND admissions: the old loop's reads ``ffn_dim`` as a Vaswani
  width and counts no admission, the arch driver's refuses decode.
"""
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

import mxnet_tpu as mx
from harness import traffic as traffic_mod
from harness import weights as weights_mod
from harness.spec import load_module
from mxnet_tpu.models.transformer import param_shapes
from mxnet_tpu.serving import PagedKVDecoder

_old = load_module("drivers", "paged_closed_loop")
_arch = load_module("drivers", "paged_closed_loop_arch")
grid_lengths, largest = _old.grid_lengths, _old.largest
sample_program = _old.sample_program

BROKEN = "layer0_mamba_out_weight"  # what --break-reference perturbs, x 1.25


def _uniform_kinds(key, shape, rule, dtype):
    """One parameter of a kind ``paged_closed_loop_arch.make_weights`` does
    not draw; ``lo`` and ``hi`` bound the UNIFORM draw (of the value, or of
    its logarithm)."""
    lo, hi = float(rule["lo"]), float(rule["hi"])
    kind = rule["kind"]
    if kind == "uniform":
        x = jax.random.uniform(key, shape, jnp.float32, lo, hi)
    elif kind == "log_of_uniform":
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, lo, hi))
    elif kind == "inv_softplus_of_log_uniform":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        np.log(lo), np.log(hi)))
        x = dt + jnp.log(-jnp.expm1(-dt))   # softplus(x) == dt
    else:
        raise ValueError("unknown init kind %r" % kind)
    return x.astype(dtype)


def make_weights(shapes, rules, seed, dtype):
    """{name: array} on the default device, in ``dtype``: the ``ones`` and
    ``normal`` parameters as ``paged_closed_loop_arch.make_weights`` makes
    them, the three uniform kinds here."""
    plain = {n: s for n, s in shapes.items()
             if weights_mod._rule_for(n, rules)["kind"] in ("ones", "normal")}
    out = _arch.make_weights(plain, rules, seed, dtype)
    base = jax.random.PRNGKey(int(seed))
    for index, name in enumerate(sorted(shapes)):
        if name not in plain:
            out[name] = _uniform_kinds(
                jax.random.fold_in(base, index), tuple(shapes[name]),
                weights_mod._rule_for(name, rules), dtype)
    return out


def model_flops(m, tokens, context_tokens, head_rows):
    """FLOP (2 x MACs) the layer equations
    (``reference/granite_hybrid_decoder.py``) need for ``tokens`` tokens
    through every layer: a Mamba mixer's two projections, its K taps and its
    recurrence (a state element takes a decay multiply-add, the outer
    product's multiply and the read-out's multiply-add: 5 a token, however
    the program groups them into chunks), an attention mixer's qkv and
    output projections, the gated MLP's three matrices; attention's scores
    and apply over ``context_tokens`` (each token's context summed, the
    causal half of a prompt not discounted, as ``flops.py`` counts it); and
    the vocabulary head for ``head_rows`` positions."""
    d, n = m["model_dim"], m["mamba_state"]
    inner = m["mamba_heads"] * m["mamba_head_dim"]
    q, kv = (m[k] * m["head_dim"] for k in ("num_heads", "num_kv_heads"))
    mamba = 2 * ((2 * inner + 2 * n + m["mamba_heads"]) * d + inner * d
                 + m["mamba_conv"] * (inner + 2 * n)) + 5 * inner * n
    attention = 2 * ((q + 2 * kv) * d + q * d)
    kinds = m["layer_types"]
    n_att = kinds.count("attention")
    return float(
        tokens * (kinds.count("mamba") * mamba + n_att * attention
                  + len(kinds) * 2 * 3 * d * m["ffn_dim"])
        + context_tokens * n_att * 4 * q
        + head_rows * 2 * d * m["vocab_size"])


class Loop(_old.Loop):
    """The old loop, decode dispatches and all, with this block's FLOP: a
    step's for the lanes it steps at their own contexts, an admission's for
    the prompt's REAL tokens (padding to the bucket is not credited) and the
    one row of logits a generating admission needs."""

    def _token(self, req, now, first):
        if first and self.recording:
            n = len(req.prompt)
            self.model_flops += model_flops(self.run.config["model"],
                                            n, n * n, 1)
        super()._token(req, now, first)

    def _flops(self, feed):
        # the token fed now attends itself and everything before it
        contexts = sum(len(self.active[seq].prompt) + self.active[seq].got
                       for seq in feed)
        return model_flops(self.run.config["model"], len(feed), contexts,
                           len(feed))


class _KeepsState:
    """The decoder as ``sample_program`` drives it, which also keeps what
    each sampled lane carries for the FIRST layer's mixer when it retires:
    the state after the prompt and every decode step of the sample."""

    def __init__(self, dec):
        self._dec, self.states = dec, []

    def __getattr__(self, name):
        return getattr(self._dec, name)

    def retire(self, seq):
        rows = self._dec.lane_state(seq, ("ssm_state_0", "conv_state_0"))
        self.states.append({k: np.asarray(v) for k, v in rows.items()})
        self._dec.retire(seq)


def check_against_reference(run, params, sampled, states):
    """Two comparisons with the reference. The old driver's (each sampled
    row against the full forward at the same position: logits, not tokens),
    with this driver's ``--break-reference``: ONE matrix perturbed in a
    shallow copy. And what the logits cannot see through forty layers of
    bfloat16 rounding, the precision the recurrent state is kept in: the
    first layer's state of each sampled lane after its last step against
    the reference's sequential recurrence over the same tokens (the worst
    HEAD's relative L2: a slow head, whose decay sits near 1, compounds a
    rounding at every token, and the fast heads' norm would hide it), and
    its convolution columns."""
    limit = run.config["check"]["state_rel_l2"]
    ref, model = run.reference(), run.config["model"]

    @jax.jit
    def errors(p, tokens, ssm, conv):
        want_ssm, want_conv = ref.first_mixer_state(p, tokens, model)
        heads = jnp.linalg.norm((ssm - want_ssm).reshape(ssm.shape[0], -1),
                                axis=-1) / (jnp.linalg.norm(
                                    want_ssm.reshape(ssm.shape[0], -1),
                                    axis=-1) + 1e-30)
        return jnp.max(heads), jnp.linalg.norm(conv - want_conv) / (
            jnp.linalg.norm(want_conv) + 1e-30)

    got = np.asarray([errors(params, jnp.asarray(toks), lane["ssm_state_0"],
                             lane["conv_state_0"])
                      for (toks, _), lane in zip(sampled, states)])
    head, conv = (float(v) for v in got.max(axis=0))
    sound = bool(np.isfinite(got).all()) and max(head, conv) <= limit
    if run.break_reference:
        params = dict(params, **{BROKEN: params[BROKEN] * 1.25})
    ok, checks = _old.check_against_reference(
        types.SimpleNamespace(config=run.config, reference=run.reference,
                              break_reference=False), params, sampled)
    return ok and sound, checks + [
        "the first layer's state of each sampled lane after its last step "
        "vs the reference's sequential recurrence: worst head's relative L2 "
        "%.3e, convolution columns %.3e (limit %.1e) %s"
        % (head, conv, limit, "ok" if sound else "FAIL")]


def build(run):
    """(params, warmed decoder) of the run's configuration and seed."""
    cfg = run.config
    model, serving = cfg["model"], cfg["serving"]
    ctx = mx.current_context()
    params = make_weights(param_shapes(**model), cfg["init"], run.seed,
                          cfg["dtype"])
    dec = PagedKVDecoder(
        {k: mx.nd.NDArray(v, ctx=ctx) for k, v in params.items()},
        max_len=serving["max_len"], prefill_len=serving["prefill_len"],
        page_size=serving["page_size"], lanes=int(serving["lanes"]), ctx=ctx,
        dtype=cfg["dtype"], **model)
    run.mark("weights on the device, decoder built")
    # without the warm dispatch's outputs: the first step would hold a third
    # copy of the cache beside the two every step holds (PERF.md section 6)
    dec.warmup(release_outputs=True)
    run.mark("compile or load prefill and decode")
    return params, dec


def run(run):
    cfg, traffic = run.config, run.traffic
    model, lanes = cfg["model"], int(cfg["serving"]["lanes"])
    n_callers = lanes if traffic["callers"] == "lanes" \
        else int(traffic["callers"])
    if n_callers > lanes:
        raise ValueError("%d callers on %d lanes: a closed loop keeps at "
                         "most one request per lane" % (n_callers, lanes))
    traffic = dict(traffic, callers=n_callers)
    params, dec = build(run)

    # warm exactly the shapes this traffic uses: admit compiles small
    # programs per prompt length (PERF.md), the decode dispatch once
    lengths = grid_lengths(traffic["fields"]["prompt_len"])
    for length in lengths:
        seq, logits = dec.admit(np.ones((length,), np.float32))
        dec.step({seq: int(np.argmax(logits))})
        dec.retire(seq)
    run.mark("warm %d prompt lengths" % len(lengths))
    keeper = _KeepsState(dec)
    sampled = sample_program(run, keeper)
    peak = run.memory_peak()  # the program's own, before the reference
    ok, checks = check_against_reference(run, params, sampled, keeper.states)
    run.mark("reference check")

    # reach the steady state before the window opens: every caller's first
    # request is taken part-way through its output, as if it had been
    # running when we arrived, so retirements are staggered from the start
    loop = Loop(run, dec, traffic_mod.callers(traffic, run.seed,
                                              model["vocab_size"]), 1)
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
        k=1, lanes=lanes, callers=n_callers, completed=loop.completed,
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
