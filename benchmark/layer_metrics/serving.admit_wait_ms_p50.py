"""Median duration of ``serving.admit.wait`` inside ``serving.paged_admit``: the
part of ``serving.admit.logits`` in which the host is blocked until the last
row of the prefill's logits is ready on the device. ``logits`` less ``wait`` is
enqueueing the reshape and the slice op by op, and reading one row. A program
from before the split records no such span: nothing to read."""
from harness.spec import load_module

p50_ms = load_module("layer_metrics", "serving.admit_stage_ms_p50").p50_ms


def read(run):
    return p50_ms(run, "serving.admit.wait", "serving.paged_admit")
