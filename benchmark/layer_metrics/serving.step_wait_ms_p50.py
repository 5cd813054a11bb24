"""Median duration of ``serving.step.wait`` inside ``serving.paged_step``: the
first half of ``serving.step.read``, the host blocked until the decode
program's logits are ready, so the device BUSY. A program from before the
read was split records no such span: nothing to read."""
from harness.spec import load_module

p50_ms = load_module("layer_metrics", "serving.admit_stage_ms_p50").p50_ms


def read(run):
    return p50_ms(run, "serving.step.wait", "serving.paged_step")
