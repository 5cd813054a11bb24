"""Median duration of ``serving.step.stage`` inside ``serving.paged_step``:
building the step's tokens, positions, write one-hot and mask on the host
and the four writes of them into the decode executable's inputs."""
from harness.spec import load_module

p50_ms = load_module("layer_metrics", "serving.admit_stage_ms_p50").p50_ms


def read(run):
    return p50_ms(run, "serving.step.stage", "serving.paged_step")
