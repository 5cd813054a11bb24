"""Median duration of ``serving.step.read`` inside ``serving.paged_step``: the
blocking read of the step's logits, so the host's wait for the device's
decode program plus the copy of (lanes, vocabulary) floats."""
from harness.spec import load_module

p50_ms = load_module("layer_metrics", "serving.admit_stage_ms_p50").p50_ms


def read(run):
    return p50_ms(run, "serving.step.read", "serving.paged_step")
