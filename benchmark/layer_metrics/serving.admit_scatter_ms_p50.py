"""Median duration of ``serving.admit.scatter`` inside ``serving.paged_admit``:
enqueueing the 2 x layers updates of the pool with the prompt's keys and
values (op-by-op programs; the device runs the copies after the span ends)."""
from harness.spec import load_module

p50_ms = load_module("layer_metrics", "serving.admit_stage_ms_p50").p50_ms


def read(run):
    return p50_ms(run, "serving.admit.scatter", "serving.paged_admit")
