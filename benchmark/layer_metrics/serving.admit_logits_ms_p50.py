"""Median duration of ``serving.admit.logits`` inside ``serving.paged_admit``:
reshape and slice of the last real position's row on the device and the
blocking read of it, so the wait for the prefill itself lands here."""
from harness.spec import load_module

p50_ms = load_module("layer_metrics", "serving.admit_stage_ms_p50").p50_ms


def read(run):
    return p50_ms(run, "serving.admit.logits", "serving.paged_admit")
