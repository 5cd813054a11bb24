"""Median duration of ``serving.step.commit`` inside ``serving.paged_step``:
handing the 2 x layers updated pool buffers back as the next step's inputs
and advancing the lanes."""
from harness.spec import load_module

p50_ms = load_module("layer_metrics", "serving.admit_stage_ms_p50").p50_ms


def read(run):
    return p50_ms(run, "serving.step.commit", "serving.paged_step")
