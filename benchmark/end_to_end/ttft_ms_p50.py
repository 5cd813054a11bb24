"""Median, over requests issued inside the window, of issue -> first token
on the host."""
from harness import stats


def read(run):
    p50 = stats.median(run.obs["ttft_s"])
    return None if p50 is None else 1e3 * p50
