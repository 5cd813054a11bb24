"""Median, over consecutive decode steps with no admission between them, of the
host time from the first step's ``serving.step.wait`` closing (its logits are
ready: the device has just finished) to the second step's
``serving.step.dispatch`` closing (the next decode program is enqueued): the
host's estimate of how long the chip had no decode program. Between the two
lie the first step's copy, commit and accounting, the caller's share
(``serving.step_between_ms_p50``) and the second step's stage and dispatch, so
the gap is their sum; a dispatch period is the gap plus the wait.

This file also holds what the two pair readers share: which steps follow one
another. ``run.spans`` carries no thread, and needs none: a decoder is stepped
by one thread."""
import bisect

from harness import stats

STEP, ADMIT = "serving.paged_step", "serving.paged_admit"
WAIT, DISPATCH = "serving.step.wait", "serving.step.dispatch"


def step_pairs(spans):
    """[(first, second)]: the ``serving.paged_step`` spans of ``run.spans``
    rows (name, start_s, duration_s, attrs) that follow one another with no
    ``serving.paged_admit`` opening between the first's close and the
    second's open. A step is {"open", "close", <name of a wait or dispatch
    span under it>: (open, close)}, by the ``id`` / ``parent`` of the
    program's spans; a program that draws no ids gives no steps."""
    up = {attrs["id"]: attrs.get("parent")
          for _n, _t0, _dur, attrs in spans if "id" in attrs}
    steps = {attrs["id"]: {"open": t0, "close": t0 + dur}
             for name, t0, dur, attrs in spans
             if name == STEP and "id" in attrs}
    for name, t0, dur, attrs in spans:
        if name not in (WAIT, DISPATCH):
            continue
        parent = attrs.get("parent")
        while parent in up and parent not in steps:
            parent = up[parent]
        if parent in steps:
            steps[parent][name] = (t0, t0 + dur)
    ordered = sorted(steps.values(), key=lambda s: s["open"])
    admits = sorted(t0 for name, t0, _dur, _a in spans if name == ADMIT)
    return [(first, second) for first, second in zip(ordered, ordered[1:])
            if bisect.bisect_left(admits, first["close"]) ==
            bisect.bisect_right(admits, second["open"])]


def read(run):
    p50 = stats.median([second[DISPATCH][1] - first[WAIT][1]
                        for first, second in step_pairs(run.spans)
                        if WAIT in first and DISPATCH in second])
    return None if p50 is None else 1e3 * p50
