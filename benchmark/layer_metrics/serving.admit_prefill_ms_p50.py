"""Median duration of ``serving.admit.prefill`` inside ``serving.paged_admit``:
enqueueing the padded prefill (``executor.forward`` nests in it); the device
runs it while the next phase waits."""
from harness.spec import load_module

p50_ms = load_module("layer_metrics", "serving.admit_stage_ms_p50").p50_ms


def read(run):
    return p50_ms(run, "serving.admit.prefill", "serving.paged_admit")
