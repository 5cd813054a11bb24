"""Fusion-eligibility explainer (GL303).

``fusion.plan`` silently skips every subgraph no pattern roots — correct,
but invisible: a model author who expected the fused path has no way to
learn *which* predicate failed short of reading the planner. This pass
re-runs the plan and reports, for every node a pattern of the engine
(ops/fusion_patterns.py) ALMOST rooted (a FullyConnected whose consumer is
not a fusable Activation, a broadcast_add whose LayerNorm chain broke one
link deep, ...), the pattern's ``reject_reason``. The engage itself is a
per-shape trace-time decision (the fusion_tune measured verdict, whose
tuned-and-rejected reasons carry the measured fused-vs-baseline µs).

All findings are INFO severity: an unfused graph is slower, not wrong.
"""
from __future__ import annotations

from .diagnostics import Diagnostic
from .manager import GraphContext, graph_pass

__all__ = ["fusion_explain"]


@graph_pass("fusion_explain")
def fusion_explain(ctx: GraphContext):
    from .. import fusion

    # same output_ids the executor passes: the explained plan must be the
    # plan that actually runs
    directives = fusion.plan(
        ctx.topo, output_ids={id(n) for n, _ in ctx.symbol._outputs})
    return _explain_patterns(ctx, directives)


def _explain_patterns(ctx: GraphContext, directives):
    """GL303: NEAR-MISS rejections of the generic pattern engine — a node
    that almost rooted a pattern (e.g. a FullyConnected whose fusable
    Activation consumer is not its sole consumer) with the failed
    predicate. Deliberately quiet: a node that simply isn't a pattern's
    shape is not a finding (a clean model must lint clean), and the
    planned-site inventory lives on ``Report.memory_plan["fusion"]`` and
    the serving cache's ``fusion_sites()``, not here."""
    from .. import fusion
    from ..ops.fusion_patterns import get_patterns

    diags = []
    modes = fusion.enabled_patterns()
    pctx = fusion._PlanCtx(
        ctx.consumers, {id(n) for n, _ in ctx.symbol._outputs}, directives)
    for node in ctx.topo:
        if node.is_variable or directives.get(id(node)) is not None:
            continue
        for pat in get_patterns():
            if modes.get(pat.name, "0") == "0":
                continue
            reason = pat.reject_reason(node, pctx)
            if reason is not None:
                diags.append(Diagnostic(
                    "GL303",
                    "not rooted by the %r pattern: %s" % (pat.name, reason),
                    node=node.name, op=node.op,
                    fix_hint="pattern matchers are structural; see "
                             "ops/fusion_patterns.py for the contract",
                ))
                break
    return diags
