"""Median duration of ``serving.step.dispatch`` inside ``serving.paged_step``:
``exe.forward`` of the decode executable, from collecting its arguments to
the jitted call's return (the program is enqueued, not finished). Since PR 34
the traced call costs what the untraced one does plus a span and a counter;
on a program from before it the reading carries the rebuilt call signature
(1.2-1.5 ms at 542 arguments)."""
from harness.spec import load_module

p50_ms = load_module("layer_metrics", "serving.admit_stage_ms_p50").p50_ms


def read(run):
    return p50_ms(run, "serving.step.dispatch", "serving.paged_step")
