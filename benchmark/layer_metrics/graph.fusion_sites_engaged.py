"""Fusion sites the planner engaged while the step was bound and traced
(telemetry counters ``fusion.fwd_engaged`` + ``fusion.bwd_engaged`` +
``fusion.pattern_engaged.*`` at the window's opening). A count: 0 today,
because every gate defaults to off."""


def read(run):
    return sum(v for k, v in run.counters_setup.items()
               if k in ("fusion.fwd_engaged", "fusion.bwd_engaged")
               or k.startswith("fusion.pattern_engaged."))
