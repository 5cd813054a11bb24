"""Median duration of ``serving.admit.state`` inside ``serving.paged_admit``:
handing the lane's recurrent-state rows (two buffers a Mamba layer, written by
the one donated cache-update program) back to the decode executable's inputs.
A program whose cache is pools only records no such span, and neither does one
from before the span existed: nothing to read."""
from harness.spec import load_module

p50_ms = load_module("layer_metrics", "serving.admit_stage_ms_p50").p50_ms


def read(run):
    return p50_ms(run, "serving.admit.state", "serving.paged_admit")
