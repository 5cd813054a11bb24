"""From the profiler's ``.xplane.pb`` to numbers: device-busy union, idle
gaps by what the host was doing, the operations that took most time, and
collective time that nothing hid.

What a TPU trace looks like (read by hand on a v5e, PR 22): one plane
``/device:TPU:<n>`` per chip whose line ``XLA Ops`` holds one event per
executed HLO instruction, named by the instruction's text
(``%fusion.3 = bf16[...] fusion(...)``), container instructions (``while``)
spanning their bodies; one plane ``/host:CPU`` with a line per thread, where
``jax.profiler.TraceAnnotation`` spans appear under their own names. Device
and host times share one axis to within about a millisecond (the device
clock is mapped onto the host's, not read from it): a gap shorter than that
can land on the neighbouring span.

The arithmetic works on plain ``Event`` tuples so that it can be checked on
hand-made events; ``read`` is the only function that touches the file format.
"""
import bisect
import collections
import glob
import os
import re

Event = collections.namedtuple("Event", "name start end")  # ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
UNATTRIBUTED = "(no bench span)"
# HLO instructions are named after their opcode unless someone renames them
COLLECTIVE_OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
                      "all-to-all", "collective-permute",
                      "collective-broadcast", "ragged-all-to-all",
                      "send", "recv")


# ------------------------------------------------------------ the file format
def find(trace_dir):
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def op_name(text):
    """``%fusion.3 = bf16[..] fusion(..)`` -> ``fusion.3``."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def read(path):
    """{"ops": {chip: [Event]}, "spans": [Event]} of one ``.xplane.pb``.

    ``ops`` are the device's executed instructions by chip ordinal;
    ``spans`` are the host's ``bench.*`` annotations. On a backend without
    device planes (the CPU, in a rehearsal) the host events that carry an
    ``hlo_op`` stat stand in for device operations, by ``device_ordinal``."""
    import warnings

    from jax.profiler import ProfileData

    def event(e, name=None):
        return Event(name or e.name, e.start_ns, e.start_ns + e.duration_ns)

    data = ProfileData.from_file(path)
    ops = collections.defaultdict(list)
    host_planes = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops[int(m.group(1))].extend(
                        event(e, op_name(e.name)) for e in line.events)
        elif plane.name.startswith("/host:"):
            host_planes.append(plane)
    stand_in = not ops
    spans = []
    for plane in host_planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append(event(e))
                elif stand_in and e.duration_ns > 0:
                    with warnings.catch_warnings():   # jax's own, on .stats
                        warnings.simplefilter("ignore", DeprecationWarning)
                        stats = dict(e.stats)
                    if "hlo_op" in stats:
                        ops[int(stats.get("device_ordinal", 0))].append(
                            event(e))
    order = lambda evs: sorted(evs, key=lambda e: (e.start, -e.end))
    return {"ops": {k: order(v) for k, v in ops.items()},
            "spans": order(spans)}


# --------------------------------------------------------- interval arithmetic
def union(intervals):
    """Disjoint, sorted (start, end) pairs covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """The parts of the disjoint sorted intervals ``a`` that ``b`` (also
    disjoint and sorted) does not cover."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def nesting(events):
    """For events of one line sorted by (start, -end): each event's self
    time (its duration less its direct children's) and whether it is a
    leaf. A container instruction such as ``while`` spans its body."""
    self_ns = [e.end - e.start for e in events]
    leaf = [True] * len(events)
    stack = []
    for i, e in enumerate(events):
        while stack and events[stack[-1]].end <= e.start:
            stack.pop()
        if stack and e.end <= events[stack[-1]].end:
            self_ns[stack[-1]] -= e.end - e.start
            leaf[stack[-1]] = False
        stack.append(i)
    return self_ns, leaf


def is_collective(name):
    return name.startswith(COLLECTIVE_OPCODES)


# ------------------------------------------------------------------ reduction
def window_of(trace):
    """(start, end) of the traced window: the ``bench.window`` span when the
    benchmark wrote one, else the extent of everything recorded."""
    for s in trace["spans"]:
        if s.name == WINDOW_SPAN:
            return s.start, s.end
    points = [(e.start, e.end) for evs in trace["ops"].values() for e in evs]
    points += [(s.start, s.end) for s in trace["spans"]]
    if not points:
        return 0.0, 0.0
    return min(p[0] for p in points), max(p[1] for p in points)


def attribute(gaps, spans):
    """{span name: ns} of idle time, each instant of a gap going to the
    innermost ``bench.*`` span that covers it, or to UNATTRIBUTED."""
    out = collections.defaultdict(float)
    spans = sorted((s for s in spans if s.name != WINDOW_SPAN),
                   key=lambda s: s.start)
    starts = [s.start for s in spans]
    longest = max((s.end - s.start for s in spans), default=0)
    for gs, ge in gaps:
        # only spans that start before the gap ends and could still reach it
        near = spans[bisect.bisect_left(starts, gs - longest):
                     bisect.bisect_left(starts, ge)]
        covering = [s for s in near if s.end > gs]
        # innermost first: the shortest covering span wins each instant
        covering.sort(key=lambda s: s.end - s.start)
        left = [(gs, ge)]
        for s in covering:
            piece = clip(left, s.start, s.end)
            out[s.name] += total(piece)
            left = subtract(left, union(piece))
            if not left:
                break
        out[UNATTRIBUTED] += total(left)
    return {k: v for k, v in out.items() if v > 0}


def reduce(trace, top=10):
    """The summary every trace-reading metric starts from. Times in seconds.

    busy_s        union of operation intervals, averaged over the chips
    idle_share    1 - busy_s / window_s
    device_ops    [[name, s], ...] self time by instruction on the lowest
                  chip, most first
    idle_gaps     [[span, s], ...] that chip's idle time by the bench.* span
                  the host was in, most first
    collective_s, collective_exposed_s   on the lowest chip: time in
                  collective instructions, and the part of it during which
                  no other instruction ran there
    """
    lo, hi = window_of(trace)
    chips = sorted(trace["ops"])
    if hi <= lo or not chips:
        return None
    merged = {chip: union(clip([(e.start, e.end)
                                for e in trace["ops"][chip]], lo, hi))
              for chip in chips}
    busy_by_chip = {chip: total(m) for chip, m in merged.items()}
    first = chips[0]
    events = [e for e in trace["ops"][first] if e.end > lo and e.start < hi]
    self_ns, leaf = nesting(events)
    by_name = collections.defaultdict(float)
    for e, s in zip(events, self_ns):
        by_name[e.name] += s
    idle = subtract([(lo, hi)], merged[first])
    by_span = attribute(idle, trace["spans"])
    coll = union(clip([(e.start, e.end) for e, l in zip(events, leaf)
                       if l and is_collective(e.name)], lo, hi))
    other = union(clip([(e.start, e.end) for e, l in zip(events, leaf)
                        if l and not is_collective(e.name)], lo, hi))
    rank = lambda d: [[k, v / 1e9] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    busy_s = sum(busy_by_chip.values()) / len(chips) / 1e9
    window_s = (hi - lo) / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "busy_s_by_chip": {str(c): v / 1e9 for c, v in busy_by_chip.items()},
        "idle_share": 1.0 - busy_s / window_s,
        "device_ops": rank(by_name),
        "idle_gaps": rank(by_span),
        "collective_s": total(coll) / 1e9,
        "collective_exposed_s": total(subtract(coll, other)) / 1e9,
        "executions": len(events),
    }
