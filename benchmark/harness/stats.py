"""Order statistics on host-clock samples."""
import math


def percentile(samples, q):
    """The ``q``-th percentile (0..100) by linear interpolation between the
    two nearest order statistics (numpy's default). None when empty."""
    xs = sorted(samples)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(samples):
    return percentile(samples, 50)
