"""Share of the window in which, by the program's own record, the device had
no NEW program: the sum of the ``serving.device_gap`` records' durations
inside the window over the window. The decoder writes one record from every
observed-ready (``serving.step.wait`` / ``serving.admit.wait`` closing) to the
next enqueue's close, whatever the two programs are, so admissions count as
steps do. The host's estimate of ``device.idle_share.serving``, from inside:
it leaves out the two seams no host span sees (the program's start inside the
enqueue, the host's wake-up after the end), and is an upper bound where the
record says a program was still in flight (``behind``).

This file also holds what the gap readers share: the records of a run. A
program from before the record writes none: nothing to read."""
from harness import stats

GAP = "serving.device_gap"


def gaps(run):
    """[(start_s, end_s, attrs)] of the run's ``serving.device_gap`` records,
    oldest first, from ``run.spans`` rows (name, start_s, duration_s,
    attrs)."""
    return sorted((t0, t0 + dur, attrs)
                  for name, t0, dur, attrs in run.spans if name == GAP)


def p50_ms(run, before):
    """Median of the records whose ``before`` (the kind of program whose
    enqueue closed the gap) is ``before``, in milliseconds."""
    p50 = stats.median([end - start for start, end, attrs in gaps(run)
                        if attrs.get("before") == before])
    return None if p50 is None else 1e3 * p50


def read(run):
    found = gaps(run)
    if not found:
        return None
    lo, hi = run.window
    inside = sum(max(0.0, min(end, hi) - max(start, lo))
                 for start, end, _attrs in found)
    return 100.0 * inside / (hi - lo)
