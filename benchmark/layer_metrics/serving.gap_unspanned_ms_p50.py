"""Median, over the ``serving.device_gap`` records, of the part of the gap
that no program span covers: the gap less the union of every other interval in
``run.spans``. What is left is the CALLER's code between two calls into the
decoder (sampling, retirement, bookkeeping), which no span of the program can
name."""
import bisect

from harness import stats
from harness.spec import load_module
from harness.trace import subtract, union

_share = load_module("layer_metrics", "serving.device_gap_share")


def unspanned(run):
    """Seconds of each gap outside every program span, oldest gap first."""
    found = _share.gaps(run)
    covered = union((t0, t0 + dur) for name, t0, dur, _a in run.spans
                    if name != _share.GAP)
    # the records follow one another (a gap opens at a ready and closes at
    # the next enqueue), so a piece left over lies in exactly one of them
    starts = [start for start, _end, _a in found]
    out = [0.0] * len(found)
    for lo, hi in subtract(union((s, e) for s, e, _a in found), covered):
        out[bisect.bisect_right(starts, lo) - 1] += hi - lo
    return out


def read(run):
    p50 = stats.median(unspanned(run))
    return None if p50 is None else 1e3 * p50
