"""Median duration of ``serving.step.stage.slots`` inside
``serving.paged_step``: the pass over the step's tokens (lane lookup, the
position check, the write slot, which may take a new page or a private copy of
a shared one, and the three ``(lanes, 1)`` fills). One of the three parts of
``serving.step.stage``; a program from before the split records none: nothing
to read."""
from harness.spec import load_module

p50_ms = load_module("layer_metrics", "serving.admit_stage_ms_p50").p50_ms


def read(run):
    return p50_ms(run, "serving.step.stage.slots", "serving.paged_step")
