"""Median duration of the program's ``serving.decode_step`` or
``serving.decode_megastep`` span: one decode dispatch, from staging its
inputs to the tokens or logits on the host."""
from harness import stats

SPANS = ("serving.decode_step", "serving.decode_megastep")


def read(run):
    p50 = stats.median([dur for name, _t0, dur, _a in run.spans
                        if name in SPANS])
    return None if p50 is None else 1e3 * p50
