"""Median duration of ``serving.step.stage.table`` inside
``serving.paged_step``: building the ``(lanes, pages a lane)`` page table from
the stepped lanes' frames, from nothing, every step. One of the three parts of
``serving.step.stage``; a program from before the split records none: nothing
to read."""
from harness.spec import load_module

p50_ms = load_module("layer_metrics", "serving.admit_stage_ms_p50").p50_ms


def read(run):
    return p50_ms(run, "serving.step.stage.table", "serving.paged_step")
