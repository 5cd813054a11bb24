"""Pattern-based subgraph fusion over the Symbol DAG: the pattern engine.

``ops/fusion_patterns.py`` declares matchers and fused lowerings for
matmul+bias+act, attention, norm+residual and elementwise chains. ``plan()``
roots each match in the directive map (a matched root gets a ``pattern``
directive, its interior nodes elide behind ``Lazy`` markers); the plan is
structural, built once per program from the Symbol DAG. The
per-(pattern, shape, dtype, device-kind) engage decision is made at trace
time by the persistent measure-and-cache autotuner (``fusion_tune.py``):
with ``MXNET_FUSION_TUNE_DIR`` unset no site has a verdict and every gate
declines. ``MXNET_FUSED_PATTERNS`` selects and forces patterns
(docs/ENV_VARS.md). Every fallback path (gate declined, tuner rejected,
lowering unavailable) is the registered operator over resolved inputs: the
bit-identical unfused graph.

Every node no pattern claims (a Convolution, a BatchNorm, an Activation, an
elementwise add among them) is computed by ``get_op(node.op).apply`` in
``executor.interpret``: this module holds no second lowering of any operator.
"""
from __future__ import annotations

import os

from . import telemetry as _tm

__all__ = ["plan", "plan_sites", "execute", "resolve", "enabled_patterns",
           "gate_pattern_explain", "losers_note", "attention_trains_flash"]


# --------------------------------------------------------------------- values
class Lazy:
    """A pattern-interior node's not-yet-computed output. Carries the node
    and its raw input values (possibly markers themselves); ``materialize()``
    runs the ordinary opdef — the bit-identical unfused semantics — and
    caches, so a marker consumed by both its pattern root (which fell back)
    and nothing else still computes at most once."""

    __slots__ = ("node", "ins", "_mat")

    def __init__(self, node, ins):
        self.node, self.ins = node, list(ins)
        self._mat = None

    def materialize(self):
        if self._mat is None:
            from .ops.registry import get_op

            vals = [resolve(v) for v in self.ins]
            outs, _ = get_op(self.node.op).apply(
                self.node.parsed_attrs(), vals, aux=[], is_train=False,
                rng=None)
            self._mat = outs[0]
        return self._mat


def resolve(v):
    """Any op that is not fusion-aware sees a plain tensor."""
    return v.materialize() if isinstance(v, Lazy) else v


# ----------------------------------------------------------------------- plan
def enabled_patterns(infer=False):
    """Per-pattern mode map from ``MXNET_FUSED_PATTERNS``: name ->
    ``"auto"`` (engage per measured verdict), ``"1"`` (force the first
    candidate lowering), ``"0"`` (off), or a LOWERING NAME (force that
    specific candidate — ``attention=pallas_flash`` — where it exists for
    the site; prefix-matched, so a forced name also selects its schedule
    variants). Grammar: ``auto``/``all`` (every pattern in auto, the
    default), ``0``/``off``/``none``, or a comma list of names with
    optional forces (``attention,matmul_bias_act=1``) — listed patterns
    get their mode, unlisted ones are off.

    ``infer=True`` is the serving/grad-less gate: when
    ``MXNET_FUSED_PATTERNS_INFER`` is set it overrides the training map on
    inference executions only (same grammar), so a serving fleet can pin
    its own pattern set — e.g. disable a pattern whose inference shapes
    were never tuned — without touching training behavior.

    The parse is memoized on the raw env string (the faultinject idiom):
    the per-site gate consults this map on every pattern execution during
    trace, so re-splitting the grammar there would be pure overhead.
    Callers get a fresh copy — ``plan()`` mutates its map."""
    from .ops.fusion_patterns import pattern_names

    names = pattern_names()
    env = os.environ.get("MXNET_FUSED_PATTERNS", "auto").strip().lower()
    if infer:
        env = os.environ.get("MXNET_FUSED_PATTERNS_INFER",
                             env).strip().lower() or env
    cached = _patterns_env_memo.get(env)
    if cached is not None:
        return dict(cached)
    modes = _parse_patterns_env(env, names)
    _patterns_env_memo[env] = modes
    return dict(modes)


def _parse_patterns_env(env, names):
    if env in ("", "auto", "all", "1"):
        return {n: "auto" for n in names}
    if env in ("0", "off", "none"):
        return {n: "0" for n in names}
    modes = {n: "0" for n in names}
    for item in env.split(","):
        item = item.strip()
        if not item:
            continue
        if item in ("auto", "all"):
            modes = {n: "auto" for n in names}
            continue
        name, _, val = item.partition("=")
        if name in modes:
            if val in ("0", "1"):
                modes[name] = val
            elif val in ("", "auto"):
                modes[name] = "auto"
            else:
                # a forced lowering NAME (e.g. pallas_flash). A value
                # matching no known lowering family warns once — a typo'd
                # value here used to read as "auto", and as a
                # never-matching name it would silently unfuse every site
                modes[name] = val
                if (val not in _warned_forced_vals
                        and not val.startswith(_LOWERING_FAMILIES)):
                    _warned_forced_vals.add(val)
                    import logging

                    logging.getLogger("mxnet_tpu").warning(
                        "MXNET_FUSED_PATTERNS treats %s=%r as a FORCED "
                        "lowering name, and it matches no known lowering "
                        "family %s: every site will run unfused (use "
                        "auto/0/1 for the mode grammar)",
                        name, val, list(_LOWERING_FAMILIES))
        else:
            global _warned_patterns_env
            if not _warned_patterns_env:
                _warned_patterns_env = True
                import logging

                logging.getLogger("mxnet_tpu").warning(
                    "MXNET_FUSED_PATTERNS names unknown pattern %r "
                    "(known: %s)", name, ", ".join(names))
    return modes


_warned_patterns_env = False
_warned_forced_vals = set()
_patterns_env_memo = {}
#: candidate-name families the patterns emit (forced-name validation)
_LOWERING_FAMILIES = ("pallas", "block_causal", "chunked_kv", "fused",
                      "onepass", "xla")


def plan_sites(directives):
    """Static per-pattern site inventory of one fusion plan: pattern name ->
    sites. Computed ONCE per bound program (``_GraphProgram.pattern_sites``):
    consumers (serving cache, health probes, the graphlint --rewrite dump)
    read the cached inventory instead of re-walking the directive map."""
    sites = {}
    for d in directives.values():
        if d["kind"] == "pattern":
            name = d["pat"].name
            sites[name] = sites.get(name, 0) + 1
    return sites


class _PlanCtx:
    """What pattern matchers may see of the graph: the consumer map, the
    program-output ids, and the directives built so far (``claimed``)."""

    __slots__ = ("consumers", "output_ids", "claimed")

    def __init__(self, consumers, output_ids, claimed):
        self.consumers, self.output_ids = consumers, output_ids
        self.claimed = claimed


def plan(topo, output_ids=()):
    """Build the fusion plan: id(node) -> directive dict. Structural only.

    Each enabled pattern (``enabled_patterns()``) is matched in priority
    order over the still-unclaimed nodes: a matched root gets a ``pattern``
    directive, its interior nodes ``lazy`` markers.

    ``output_ids`` are the ids of nodes whose outputs are PROGRAM outputs
    (executor passes them from the bound symbol). A graph-output node has an
    implicit extra consumer the ``consumers`` map cannot see: its value must
    materialize, so it is excluded from every pattern interior."""
    output_ids = frozenset(output_ids)
    consumers = {}
    for node in topo:
        for inp, oi in node.inputs:
            consumers.setdefault(id(inp), []).append((node, oi))

    directives = {}
    # a pattern is PLANNED when either the training or the inference map
    # enables it (the per-execution gate re-reads the right map); the plan
    # is shared by both execution modes of a program
    modes = enabled_patterns()
    for name, mode in enabled_patterns(infer=True).items():
        if modes.get(name, "0") == "0" and mode != "0":
            modes[name] = mode
    if any(v != "0" for v in modes.values()):
        from .ops.fusion_patterns import get_patterns

        ctx = _PlanCtx(consumers, output_ids, directives)
        for pat in get_patterns():
            if modes.get(pat.name, "0") == "0":
                continue
            for node in topo:
                if node.is_variable or id(node) in directives:
                    continue
                m = pat.match(node, ctx)
                if m is None:
                    continue
                directives[id(node)] = {"kind": "pattern", "pat": pat,
                                        "meta": m.meta}
                for n in m.interior:
                    directives[id(n)] = {"kind": "lazy"}
    return directives


# ----------------------------------------------------- generic pattern gate
def _tune_key(pat, meta, args):
    from .ops.fusion_patterns import sig_of

    variant = pat.key_variant(meta)
    return "%s|%s|%s" % (pat.name, variant, sig_of(args))


def _rec_best_times(rec):
    """(fused_us, baseline_us) fwd+bwd totals from a tune record — the
    engaged lowering's when one won, else the best measured candidate's —
    for the explain strings GL303 quotes. None when nothing timed."""
    base = rec.get("base_fwd_us")
    if base is None:
        return None
    base += rec.get("base_bwd_us") or 0.0
    if rec.get("fused_fwd_us") is not None:
        return (rec["fused_fwd_us"] + (rec.get("fused_bwd_us") or 0.0), base)
    best = None
    for row in (rec.get("measured") or {}).values():
        if row.get("fwd_us") is None:
            continue
        t = row["fwd_us"] + (row.get("bwd_us") or 0.0)
        best = t if best is None or t < best else best
    return None if best is None else (best, base)


def losers_note(rec, winner):
    """The measured-losers clause of a schedule-search win: up to three
    runner-up candidates with their fwd(+bwd) totals, fastest first —
    ``gate_pattern_explain`` reasons quote it so the
    schedule decision is auditable without opening the cache file."""
    rows = []
    for name, row in (rec.get("measured") or {}).items():
        if name == winner or row.get("fwd_us") is None:
            continue
        if "rejected" in row or "error" in row:
            continue  # failed parity / failed to run: not beaten on TIME
        rows.append((row["fwd_us"] + (row.get("bwd_us") or 0.0), name))
    if not rows:
        return ""
    rows.sort()
    note = ", ".join("%s %.0fµs" % (n, t) for t, n in rows[:3])
    extra = "" if len(rows) <= 3 else " +%d more" % (len(rows) - 3)
    return "; beat %s%s" % (note, extra)


def tuned_reject_note(rec):
    """The measured-timings clause for a tuned-and-rejected site (feeds the
    GL303 explainer and ``gate_pattern_explain`` reasons)."""
    if "error" in rec:
        return "tuned and failed to measure (%s)" % rec["error"]
    times = _rec_best_times(rec)
    if times is None:
        return "tuned and rejected (no candidate lowering could be timed)"
    return ("tuned and rejected (best fused %.0fµs vs baseline %.0fµs "
            "fwd+bwd)" % times)


def gate_pattern_explain(pat, meta, args, train=True):
    """The per-site engage decision for a generic pattern WITH its
    predicate: ``(engaged, (lowering_name, fn) | None, reason)``.

    Predicate order: env mode (``MXNET_FUSED_PATTERNS``) → inference
    eligibility → mesh (patterns engage single-device only; SPMD traces
    keep the op's own dispatch, e.g. ring attention) → candidate lowerings
    exist for these shapes → forced, else the measure-and-cache verdict
    (``fusion_tune``): cache hit engages/rejects with the measured µs;
    a miss MEASURES when tuning is enabled, else stays unfused."""
    from . import fusion_tune as _tune

    mode = enabled_patterns(infer=not train).get(pat.name, "0")
    if mode == "0":
        return False, None, ("pattern disabled (MXNET_FUSED_PATTERNS%s)"
                             % ("" if train else "[_INFER]"))
    if not train and not pat.inference:
        return False, None, "pattern does not engage on inference executions"
    from .parallel.mesh import current_trace_mesh

    mesh = current_trace_mesh()
    if mesh is not None and mesh.size > 1:
        return False, None, ("multi-device mesh: generic patterns engage "
                             "single-device only (the op's own SPMD "
                             "dispatch applies)")
    baseline, cands = pat.build(meta, args)
    if not cands:
        return False, None, ("no fused lowering for this site (shape does "
                             "not tile / variant unsupported)")
    if mode == "1":
        return True, cands[0], "forced (MXNET_FUSED_PATTERNS)"
    if mode != "auto":
        # a forced lowering NAME (prefix-matched so a bare family name
        # also selects its schedule variants): engage where it exists
        match = next((c for c in cands if c[0] == mode),
                     next((c for c in cands if c[0].startswith(mode)),
                          None))
        if match is not None:
            return True, match, ("forced (MXNET_FUSED_PATTERNS %s=%s)"
                                 % (pat.name, mode))
        return False, None, ("forced lowering %r has no candidate at "
                             "this site" % mode)
    if not getattr(pat, "tunable", True):
        return False, None, ("no lowering distinct from the baseline to "
                             "measure (engage via MXNET_FUSED_PATTERNS="
                             "%s=1)" % pat.name)
    key = _tune_key(pat, meta, args)

    def _measure():
        # synthetic concrete inputs: the real args are tracers mid-trace.
        # tuner_build() keeps force-gated interpret candidates (an
        # inference-map pin) out of the measured set off-TPU.
        from .ops.fusion_patterns import tuner_build

        sargs = _tune.synth_like(args)
        with tuner_build():
            sbase, scands = pat.build(meta, sargs)
        return _tune.measure_candidates(sbase, scands, sargs, train=True)

    rec = _tune.verdict(key, _measure)
    if rec is None:
        return False, None, ("no measured verdict for this site (tuning "
                             "disabled: set MXNET_FUSION_TUNE_DIR)")
    want = "engage" if train else "engage_fwd"
    low = rec.get("lowering") if train else (rec.get("lowering_fwd")
                                             or rec.get("lowering"))
    if rec.get(want) and low:
        fn = dict(cands).get(low)
        if fn is None:
            return False, None, ("cached lowering %r is unavailable for "
                                 "this site" % low)
        times = _rec_best_times(rec)
        reason = "measured win (%s)" % low if times is None else (
            "measured win (%s: fused %.0fµs vs baseline %.0fµs fwd+bwd%s)"
            % ((low,) + times + (losers_note(rec, low),)))
        return True, (low, fn), reason
    return False, None, tuned_reject_note(rec)


def attention_trains_flash(q_shape, k_shape, dtype, causal, scale=-1.0):
    """Whether TRAINING through an attention site with these shapes will
    statically engage the flash (``pallas_flash``) lowering — whose
    ``custom_vjp`` online-softmax recompute backward never stashes the
    (B, H, T, S) probability tensor. Decidable without tracing: the
    pattern mode force-names a flash lowering, or the tune cache records
    an engaged ``pallas_flash`` winner for this exact site. The GL5xx
    memory planner uses it to elide the score-stash charge."""
    try:
        from .ops import pallas_attention as pa

        if not pa.supported(tuple(q_shape), tuple(k_shape),
                            causal=bool(causal)):
            return False
        mode = enabled_patterns().get("attention", "0")
        if mode in ("0", "1"):
            return False  # "1" engages the FIRST candidate (XLA family)
        if mode != "auto":
            return mode.startswith("pallas_flash")
        from . import fusion_tune as _tune
        from .ops.fusion_patterns import get_patterns

        class _Arg:  # shape/dtype carrier for the tune-key signature
            def __init__(self, shape, dtype):
                self.shape, self.dtype = tuple(shape), dtype

        pat = next(p for p in get_patterns() if p.name == "attention")
        meta = {"causal": bool(causal), "scale": float(scale)}
        args = (_Arg(q_shape, dtype), _Arg(k_shape, dtype),
                _Arg(k_shape, dtype))
        rec = _tune.peek(_tune_key(pat, meta, args))
        return bool(rec and rec.get("engage")
                    and str(rec.get("lowering") or "").startswith(
                        "pallas_flash"))
    except Exception:  # a planner refinement must never sink an analysis
        return False


def _exec_pattern(directive, node, ins, is_train):
    """Run one pattern-rooted node: engage the gated lowering, or fall back
    to the bit-identical unfused root op over resolved inputs."""
    pat, meta = directive["pat"], directive["meta"]
    engaged, chosen, reason = False, None, None
    try:
        args = pat.externals(meta, ins, resolve)
    except Exception:  # matcher/exec mismatch: unfused fallback
        args, reason = None, "externals recovery failed (marker mismatch)"
    if args is not None:
        engaged, chosen, reason = gate_pattern_explain(
            pat, meta, args, train=is_train)
    if _tm.enabled():
        _tm.counter("fusion.pattern_engaged.%s" % pat.name if engaged
                    else "fusion.pattern_fallback.%s" % pat.name).inc()
    if _tm.tracing():
        _tm.event("fusion.pattern", op=node.name, pattern=pat.name,
                  engaged=engaged, reason=reason,
                  **({"lowering": chosen[0]} if chosen else {}))
    if engaged:
        return (chosen[1](*args),), ()
    from .ops.registry import get_op

    rins = [resolve(v) for v in ins]
    outs, aux_out = get_op(node.op).apply(
        node.parsed_attrs(), rins, aux=[], is_train=is_train, rng=None)
    return tuple(outs), tuple(aux_out)


def execute(directive, node, ins, is_train):
    """Run one planned node during interpret(). ``ins`` are the raw values
    (possibly ``Lazy`` markers); returns (outs_tuple, new_aux)."""
    kind = directive["kind"]
    if kind == "lazy":
        return (Lazy(node, ins),), ()
    if kind == "pattern":
        return _exec_pattern(directive, node, ins, is_train)
    raise AssertionError(kind)
