"""mxnet_tpu.analysis: static graph-lint & engine-race analysis.

The home for every static pass over the Symbol DAG, executor bind metadata,
and recorded engine schedules (ISSUE 1 tentpole; Relay/PyGraph lineage in
PAPERS.md). Three entry points:

* ``lint(symbol, shapes=..., types=...)`` — run the graph passes, get a
  ``Report`` of structured ``Diagnostic``s (stable ``GLxxx`` codes). Pass
  ``mesh="dp=8,model=2"`` (and optionally ``rules``/``budget_gb``/``bwd``)
  to add the GL4xx sharding-plan lint and the GL5xx per-device peak-HBM
  planner; the planner's table lands on ``Report.memory_plan``.
* ``MXNET_GRAPHLINT=warn|error`` — ``executor.bind``/``simple_bind`` run the
  same passes on every bind; ``warn`` logs, ``error`` raises ``MXNetError``
  with the formatted report instead of a JAX traceback. The fused-step
  path (``module.spmd_adapter``) feeds the passes the REAL mesh + rules.
* ``tools/graphlint`` — the CLI: lints bundled models or a serialized
  Symbol JSON (``python tools/graphlint --all-models``); ``--mesh`` /
  ``--budget-gb`` / ``--bwd`` drive the distributed-plan passes.

Engine schedules are analyzed separately (they are runtime traces, not
graphs): wrap any engine in ``RecordingEngine``, run the workload, then
``analyze_trace(engine.trace)``. See ``docs/static_analysis.md`` for every
diagnostic code.
"""
from __future__ import annotations

import logging
import os

from ..base import MXNetError
from .diagnostics import CODES, Diagnostic, Report, Severity, describe_code
from .dispatch_lint import (dispatch_gap_pct, lint_dispatch_gaps,
                            lint_dispatch_paths, lint_dispatch_source)
from .engine_race import RecordingEngine, ScheduleTrace, analyze_trace
from .manager import GraphContext, graph_pass, list_passes, run_graph_passes
from .rewrite import (RewritePass, RewriteResult, graphrewrite_mode,
                      rewrite, rewrite_pass_names, verify_rewrite)

__all__ = [
    "CODES", "Diagnostic", "Report", "Severity", "describe_code",
    "GraphContext", "graph_pass", "list_passes", "run_graph_passes",
    "RecordingEngine", "ScheduleTrace", "analyze_trace",
    "lint", "lint_bind", "graphlint_mode",
    "rewrite", "verify_rewrite", "graphrewrite_mode", "RewritePass",
    "RewriteResult", "rewrite_pass_names",
    "lint_dispatch_paths", "lint_dispatch_source", "lint_dispatch_gaps",
    "dispatch_gap_pct",
]

_LOG = logging.getLogger("mxnet_tpu.graphlint")


def lint(symbol, shapes=None, types=None, strict_shapes=None, passes=None,
         target="", mesh=None, rules=None, budget_gb=None, bwd="stash",
         train=True) -> Report:
    """Run the registered graph passes over ``symbol``.

    ``shapes``/``types`` are name->shape / name->dtype hints (same contract
    as ``Symbol.infer_shape``/``infer_type`` kwargs). ``strict_shapes``
    defaults to True when shape hints are given: underdetermined arguments
    are then GL002 errors rather than expected polymorphism (GL203).

    Distributed-plan knobs (docs/static_analysis.md §GL4xx/GL5xx):
    ``mesh`` is a ``parallel.MeshSpec``/jax Mesh/axis dict/``"dp=8,model=2"``
    string enabling the sharding-plan lint; ``rules`` overrides the
    ``ShardingRules`` derived from it. ``budget_gb`` (binary GiB — the unit
    every report line prints; default: the ``MXNET_MEMLINT_BUDGET_GB`` env)
    arms GL501; ``bwd`` is the planner's stash/recompute policy and
    ``train`` toggles grad/optimizer accounting.
    """
    if mesh is not None:
        from ..parallel.mesh import parse_mesh_spec

        mesh = parse_mesh_spec(mesh)
    return run_graph_passes(symbol, shape_hints=shapes, type_hints=types,
                            strict_shapes=strict_shapes, passes=passes,
                            target=target, mesh=mesh, rules=rules,
                            budget_bytes=(None if budget_gb is None
                                          else float(budget_gb) * 2 ** 30),
                            bwd_policy=bwd, train=train)


_warned_modes = set()


def graphlint_mode():
    """The MXNET_GRAPHLINT env knob: None (off, the default), 'warn', or
    'error'. Boolean-style truthy values ('1', 'true', 'on') mean 'warn'
    (every other knob in docs/ENV_VARS.md is 0/1, so honor the idiom);
    anything else logs a one-time warning and stays off rather than letting
    the user believe a gate is active that never runs."""
    raw = os.environ.get("MXNET_GRAPHLINT", "0").strip().lower()
    if raw in ("warn", "error"):
        return raw
    if raw in ("1", "true", "on"):
        return "warn"
    if raw not in ("", "0", "false", "off") and raw not in _warned_modes:
        _warned_modes.add(raw)
        _LOG.warning("MXNET_GRAPHLINT=%r is not a recognized mode "
                     "(0|warn|error); graphlint stays OFF", raw)
    return None


def lint_bind(symbol, shapes, types, mode, target="bind", mesh=None,
              rules=None, train=True):
    """Bind-time hook used by ``executor.bind`` (single device: memory plan
    only) and ``SPMDStepAdapter`` (real mesh + rules: the full GL4xx/GL5xx
    suite): lint with the concrete bind shapes/dtypes, log findings, and
    under ``error`` raise MXNetError when any error-severity diagnostic
    fires."""
    report = lint(symbol, shapes=shapes, types=types, strict_shapes=True,
                  target=target, mesh=mesh, rules=rules, train=train)
    for d in report:
        if d.severity == Severity.ERROR:
            _LOG.error(d.format())
        elif d.severity == Severity.WARNING:
            _LOG.warning(d.format())
        else:
            _LOG.debug(d.format())
    if mode == "error" and report.errors:
        raise MXNetError(
            "graphlint found %d error(s) at bind (MXNET_GRAPHLINT=error):\n%s"
            % (len(report.errors), report.format(min_severity=Severity.WARNING)))
    return report
