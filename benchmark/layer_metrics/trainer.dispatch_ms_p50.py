"""Median duration of the program's ``trainer.step`` span: placing the batch
and enqueueing the jitted step, on the host. The device runs the step after
the span ends, so against ``trainer.step_ms_p50`` this is the host's headroom
seen from inside the program."""
from harness import stats


def read(run):
    p50 = stats.median([dur for name, _t0, dur, _a in run.spans
                        if name == "trainer.step"])
    return None if p50 is None else 1e3 * p50
