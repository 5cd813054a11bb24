"""Median duration of the program's ``serving.paged_admit`` span."""
from harness import stats


def read(run):
    p50 = stats.median([dur for name, _t0, dur, _a in run.spans
                        if name == "serving.paged_admit"])
    return None if p50 is None else 1e3 * p50
