"""The one traffic generator. A traffic mix is a data file; what varies
between requests is a set of named fields, each drawn from a distribution the
file describes. The same seed gives the same requests.

A field is one of
    {"dist": "const", "value": v}
    {"dist": "choice", "values": [...]}                  uniform over the list
    {"dist": "lognormal", "median": m, "sigma": s,       exp(N(ln m, s)), then
     "clip": [lo, hi], "grid": [...]}                    clipped, then rounded
                                                         UP to the grid
``clip`` and ``grid`` are optional; a draw above the grid's top takes the top.

Draws are stratified across the callers: the k-th request of each of the n
callers takes one of the n quantiles (i + 1/2) / n of the field's
distribution, which caller takes which being a permutation drawn from the
seed, the field and k. Every round of requests is therefore the same set of
lengths whatever the seed, and only their order and the tokens differ: the
work in a window is fixed by the mix and not by the luck of a seed, which is
what lets two runs on two seeds agree within a per cent (PERF.md section 6,
PR 22: plain random draws moved tokens/s by 10% between seeds).
"""
import bisect
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def quantile(field, q):
    """The ``q``-quantile (0 < q < 1) of ``field``, as an integer."""
    dist = field["dist"]
    if dist == "const":
        return int(field["value"])
    if dist == "choice":
        values = field["values"]
        return int(values[min(int(q * len(values)), len(values) - 1)])
    if dist == "lognormal":
        x = math.exp(math.log(field["median"])
                     + field["sigma"] * _NORMAL.inv_cdf(q))
        if "clip" in field:
            x = min(max(x, field["clip"][0]), field["clip"][1])
        if "grid" in field:
            grid = sorted(field["grid"])
            return int(grid[min(bisect.bisect_left(grid, x), len(grid) - 1)])
        return int(round(x))
    raise ValueError("unknown distribution %r" % dist)


def strata(seed, label, round_index, n):
    """The quantile each of ``n`` callers takes in one round: the midpoints
    (i + 1/2) / n in an order drawn from (seed, label, round)."""
    key = [int(seed), sum(label.encode()), int(round_index)]
    return (np.random.default_rng(key).permutation(n) + 0.5) / n


class Caller:
    """One closed-loop caller's endless stream of requests."""

    def __init__(self, fields, seed, index, n_callers, vocab):
        self.index = index
        self._fields = fields
        self._seed, self._n = seed, n_callers
        self._vocab = vocab
        self._rng = np.random.default_rng([int(seed), int(index)])
        self.issued = 0

    def next_request(self):
        """{field: value, ..., "tokens": prompt ids} for the next request."""
        req = {name: quantile(f, strata(self._seed, name, self.issued,
                                        self._n)[self.index])
               for name, f in sorted(self._fields.items())}
        req["tokens"] = self._rng.integers(
            1, self._vocab, size=req["prompt_len"]).astype(np.float32)
        req["caller"] = self.index
        req["serial"] = self.issued
        self.issued += 1
        return req


def callers(traffic, seed, vocab):
    n = int(traffic["callers"])
    return [Caller(traffic["fields"], seed, i, n, vocab) for i in range(n)]
