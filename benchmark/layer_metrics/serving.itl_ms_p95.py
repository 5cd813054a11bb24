"""95th percentile over all gaps between consecutive tokens of one sequence,
as its caller sees them: a K-token dispatch delivers K tokens at once, K-1
gaps of 0 and one long one, which is what a streaming user gets. In a
closed loop the gaps sit on plateaus (one decode step plus 0, 1, 2...
admissions that ran between two steps) and a percentile lies on one of them:
steady while it stays there (187-189 ms on ten seeds), a whole admission
away (28%) once a change moves the admission rate across the edge. So it is
recorded here and not held to a bound (PERF.md section 2)."""
from harness import stats


def read(run):
    p95 = stats.percentile(run.obs["itl_s"], 95)
    return None if p95 is None else 1e3 * p95
