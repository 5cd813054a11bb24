"""Driver ``paged_closed_loop_arch``: the closed loop of
``paged_closed_loop`` (its ``Loop``, its warm-up grid, its sampled check)
around a ``serving.PagedKVDecoder`` of whatever architecture the
configuration's ``model`` names, at a size where three things of the old
driver no longer hold:

- parameter shapes come from the program's own
  ``models.transformer.param_shapes(**model)``, not from a list spelled here;
- the decoder is built with the configuration's ``dtype`` (weights and KV
  pool) beside the serving keys;
- ``model_flops_in_window`` is counted for ADMISSIONS, the only dispatches of
  a mix whose requests want one token: 2 x the MACs of the request's REAL
  prompt tokens (padding to the prefill bucket is not credited) through the
  qkv and output projections, the router, ``num_experts_per_tok`` active
  experts of three matrices each, dense causal attention counted as
  ``flops.py`` counts it (scores and apply over the prompt, the causal half
  not discounted), and the vocabulary head once for every REAL prompt
  position (what scoring a document needs; the program computes it for all
  positions of the bucket and ``admit`` hands back the last).

Two more cannot be reused at this size, and both fail only on the chip.
``harness/weights.make`` cuts every parameter from ONE float32 normal draw
(3.57 B parameters would be 14.3 GB of noise), so the weights are made here,
on the device, in the configuration's ``dtype``, one jitted draw per distinct
shape, keyed by ``fold_in(seed, index of the sorted name)``. And the old
check's ``--break-reference`` multiplies EVERY ``_weight`` by 1.25 into a
second full copy; here it perturbs one matrix (``layer0_proj_weight`` x
1.25) in a shallow copy of the dict and otherwise compares as the old one
does: the worst row's relative L2 over the last ``1 + check_decode_steps``
rows. The decoder is given the driver's arrays and holds no second copy of
them; the peak is read before the reference runs.

A mix with ``output_len`` above 1 is refused: the old loop's decode FLOP
count reads ``ffn_dim`` as a dense width, which is wrong here, and no decode
cell of this driver has been asked for yet.
"""
import functools
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
grid_lengths, largest = _old.grid_lengths, _old.largest
sample_program = _old.sample_program

BROKEN = "layer0_proj_weight"  # what --break-reference perturbs, x 1.25


def make_weights(shapes, rules, seed, dtype):
    """{name: array} on the default device, in ``dtype``: one jitted draw per
    distinct shape (each compiles once), every parameter from its own key
    ``fold_in(seed, index of its name among the sorted names)``."""
    @functools.partial(jax.jit, static_argnums=(1,))
    def normal(key, shape, std):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    base, out = jax.random.PRNGKey(int(seed)), {}
    for index, name in enumerate(sorted(shapes)):
        shape, rule = tuple(shapes[name]), weights_mod._rule_for(name, rules)
        if rule["kind"] == "ones":
            out[name] = jnp.ones(shape, dtype)
        elif rule["kind"] == "normal":
            out[name] = normal(jax.random.fold_in(base, index), shape,
                               jnp.float32(rule.get("scale", 1.0)))
        else:
            raise ValueError("init kind %r of %r is not made here"
                             % (rule["kind"], name))
    return out


def admit_flops(length, m):
    """FLOP (2 x MACs) an admission of ``length`` real tokens needs; what is
    counted is in the module docstring."""
    d, width = m["model_dim"], m["num_heads"] * m["head_dim"]
    per_token = m["num_layers"] * (
        3 * width * d + d * width + m["num_experts"] * d
        + m["num_experts_per_tok"] * 3 * d * m["ffn_dim"]
        + 2 * length * width) + d * m["vocab_size"]
    return 2.0 * length * per_token


class Loop(_old.Loop):
    """The old loop; the model's FLOP are counted per admission."""

    def _token(self, req, now, first):
        if first and self.recording:
            self.model_flops += admit_flops(len(req.prompt),
                                            self.run.config["model"])
        super()._token(req, now, first)

    def _flops(self, feed):
        raise NotImplementedError("no decode dispatch is counted by the "
                                  "paged_closed_loop_arch driver")


def check_against_reference(run, params, sampled):
    """The old driver's comparison (each sampled row against the reference's
    full forward at the same position: logits, not tokens), with its own
    ``--break-reference``: ONE matrix perturbed in a shallow copy."""
    if run.break_reference:
        params = dict(params, **{BROKEN: params[BROKEN] * 1.25})
    return _old.check_against_reference(
        types.SimpleNamespace(config=run.config, reference=run.reference,
                              break_reference=False), params, sampled)


def run(run):
    cfg, traffic = run.config, run.traffic
    model, serving = cfg["model"], cfg["serving"]
    lanes = int(serving["lanes"])
    n_callers = lanes if traffic["callers"] == "lanes" \
        else int(traffic["callers"])
    if n_callers > lanes:
        raise ValueError("%d callers on %d lanes: a closed loop keeps at "
                         "most one request per lane" % (n_callers, lanes))
    if largest(traffic["fields"]["output_len"]) > 1:
        raise ValueError("driver paged_closed_loop_arch counts admissions "
                         "only: a mix with output_len > 1 needs a decode "
                         "FLOP count for arch %r first" % model.get("arch"))
    traffic = dict(traffic, callers=n_callers)

    ctx = mx.current_context()
    params = make_weights(param_shapes(**model), cfg["init"], run.seed,
                          cfg["dtype"])
    dec = PagedKVDecoder(
        {k: mx.nd.NDArray(v, ctx=ctx) for k, v in params.items()},
        max_len=serving["max_len"], page_size=serving["page_size"],
        lanes=lanes, ctx=ctx, dtype=cfg["dtype"], **model)
    run.mark("weights on the device, decoder built")
    dec.warmup()
    run.mark("compile or load prefill and decode")

    # warm exactly the shapes this traffic uses: admit compiles small
    # programs per prompt length (PERF.md)
    lengths = grid_lengths(traffic["fields"]["prompt_len"])
    for length in lengths:
        seq, _ = dec.admit(np.ones((length,), np.float32))
        dec.retire(seq)
    run.mark("warm %d prompt lengths" % len(lengths))
    sampled = sample_program(run, dec)
    peak = run.memory_peak()  # the program's own, before the reference
    ok, checks = check_against_reference(run, params, sampled)
    run.mark("reference check")

    loop = Loop(run, dec, traffic_mod.callers(traffic, run.seed,
                                              model["vocab_size"]), 1)
    now = time.perf_counter()
    for caller in loop.callers:
        loop.issue(caller, now)
    for _ in range(int(traffic["ramp_dispatches"])):
        loop.admit_waiting()

    run.mark("ramp to the steady state")
    t0 = run.open_window()
    deadline = t0 + run.seconds
    loop.recording, loop.t_open = True, t0
    while time.perf_counter() < deadline:
        loop.admit_waiting(deadline)
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
        in_flight_at_close=in_flight, dispatches=0,
        ttft_samples=len(loop.ttft),
        ttft_ms_percentiles={q: 1e3 * float(np.percentile(loop.ttft, q))
                             for q in (25, 50, 75, 95)} if loop.ttft else None)
    return obs
