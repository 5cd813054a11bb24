"""Median ``serving.device_gap`` that an admission's prefill closed
(``before="prefill"``): from the last result seen ready, a step's or another
admission's row, to the prefill's enqueue returning. What the device waits
for around an admission; all of a score cell's idle time."""
from harness.spec import load_module

p50_ms = load_module("layer_metrics", "serving.device_gap_share").p50_ms


def read(run):
    return p50_ms(run, "prefill")
