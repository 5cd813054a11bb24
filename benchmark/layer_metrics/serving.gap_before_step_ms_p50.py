"""Median ``serving.device_gap`` that a decode step's enqueue closed
(``before="decode"``), whatever was seen ready before it: another step's
result or an admission's row. ``serving.step_gap_ms_p50`` reads the same
interval from pairs of step spans and drops the pairs with an admission
between; this one keeps them, and needs no pairing."""
from harness.spec import load_module

p50_ms = load_module("layer_metrics", "serving.device_gap_share").p50_ms


def read(run):
    return p50_ms(run, "decode")
