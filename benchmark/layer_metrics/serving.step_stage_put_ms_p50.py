"""Median duration of ``serving.step.stage.put`` inside
``serving.paged_step``: the one batched host-to-device transfer of the four
staged arrays and their hand-off to the executable. One of the three parts of
``serving.step.stage``; a program from before the split records none: nothing
to read."""
from harness.spec import load_module

p50_ms = load_module("layer_metrics", "serving.admit_stage_ms_p50").p50_ms


def read(run):
    return p50_ms(run, "serving.step.stage.put", "serving.paged_step")
