"""Median duration of ``serving.step.copy`` inside ``serving.paged_step``: the
second half of ``serving.step.read``, (lanes, vocabulary) floats that are
ready going from the device to the host, so the device IDLE. The program
queues the copy behind the decode program, so the span opens one host wake-up
into it and reads the copy less that. A program from before the read was
split records no such span: nothing to read."""
from harness.spec import load_module

p50_ms = load_module("layer_metrics", "serving.admit_stage_ms_p50").p50_ms


def read(run):
    return p50_ms(run, "serving.step.copy", "serving.paged_step")
