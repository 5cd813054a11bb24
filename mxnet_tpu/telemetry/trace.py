"""Chrome-trace export + live summary for the telemetry subsystem.

The exporter honors the reference ``MXDumpProfile`` contract
(src/engine/profiler.cc wrote ``traceEvents`` JSON the chrome://tracing
viewer loads directly): complete ``"ph": "X"`` events with microsecond
``ts``/``dur``, process/thread metadata events, plus an ``otherData``
block carrying the counter snapshot and per-step rows — the part the
reference never had and ``tools/mxtrace`` tables are built from. When a
JAX/XLA capture ran alongside (profiler.py), the dump records the XLA
trace directory so viewers and ``profiler.trace_files()`` can merge both.
"""
from __future__ import annotations

import json
import os

from . import histogram, lockwitness, registry, spans

__all__ = ["export_chrome_trace", "summarize", "span_summary",
           "gap_summary", "merge_traces", "SCHEMA_VERSION"]

SCHEMA_VERSION = 1

_PID = 1  # single framework process lane (merge_traces re-pids by os pid)


def _category(name):
    """Span taxonomy: the dotted prefix is the category lane
    (``engine.push`` → ``engine``; docs/OBSERVABILITY.md)."""
    return name.split(".", 1)[0] if "." in name else name


def build_trace(xla_trace_dir=None, extra=None):
    """The chrome-trace dict for the events recorded so far."""
    perf0, wall0 = spans.epoch()
    raw = spans.drain_events()
    tids = {}
    events = [{"ph": "M", "pid": _PID, "name": "process_name",
               "args": {"name": "mxnet_tpu framework"}}]
    for name, t0, dur, ident, attrs in raw:
        tid = tids.get(ident)
        if tid is None:
            tid = tids[ident] = len(tids) + 1
            events.append({"ph": "M", "pid": _PID, "tid": tid,
                           "name": "thread_name",
                           "args": {"name": "py-thread-%d" % tid}})
        ev = {"ph": "X", "pid": _PID, "tid": tid,
              "cat": _category(name), "name": name,
              "ts": round((wall0 + (t0 - perf0)) * 1e6, 1),
              "dur": round(dur * 1e6, 1)}
        if attrs:
            ev["args"] = {k: _jsonable(v) for k, v in attrs.items()}
        events.append(ev)
    other = {"mxnet_telemetry": SCHEMA_VERSION,
             "counters": registry.snapshot(),
             "steps": registry.step_rows(),
             "pid": os.getpid(),
             "dropped": spans.dropped_events()}
    if xla_trace_dir:
        other["xla_trace_dir"] = os.path.abspath(xla_trace_dir)
    if lockwitness.witnessing():
        # MXNET_CONCLINT=witness: ship the lock-contention/inversion record
        # with the trace so mxtrace renders the table and
        # `graphlint --concurrency --witness dump.json` can judge it (GL805)
        other["lock_witness"] = lockwitness.witness_report()
    if extra:
        other.update(extra)
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": other}


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, (tuple, list)):
        return [_jsonable(x) for x in v]
    return str(v)


def export_chrome_trace(path, xla_trace_dir=None, extra=None):
    """Write the chrome-trace JSON to ``path``; returns the trace dict."""
    trace = build_trace(xla_trace_dir=xla_trace_dir, extra=extra)
    d = os.path.dirname(os.path.abspath(path))
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(trace, f)
    return trace


def span_summary(trace=None, top=25):
    """Aggregate span wall time by name, heaviest first — the per-op stat
    table of the reference engine profiler, over framework spans. Accepts a
    loaded trace dict (mxtrace) or None for the live buffer.

    Each row carries p50/p95/p99 milliseconds from a log-bucketed
    histogram of the span's durations (bounded ~10% relative error) —
    ``total/count`` means hide tail behavior."""
    acc = {}          # name -> [ms, count, Histogram]
    def _add(name, dur_s):
        row = acc.get(name)
        if row is None:
            row = acc[name] = [0.0, 0, histogram.Histogram()]
        row[0] += dur_s * 1000.0
        row[1] += 1
        row[2].record(dur_s)

    if trace is None:
        for name, _t0, dur, _ident, _attrs in spans.drain_events():
            _add(name, dur)
    else:
        for ev in trace.get("traceEvents", []):
            if ev.get("ph") != "X":
                continue
            _add(ev.get("name", "?"), ev.get("dur", 0) / 1e6)
    rows = []
    for n, (ms, cnt, h) in acc.items():
        q = h.quantiles_ms()
        rows.append({"name": n, "ms": round(ms, 3), "count": cnt,
                     "p50_ms": round(q.get("p50", 0.0), 3),
                     "p95_ms": round(q.get("p95", 0.0), 3),
                     "p99_ms": round(q.get("p99", 0.0), 3)})
    rows.sort(key=lambda r: -r["ms"])
    return rows[:top]


def gap_summary(trace=None, prefix=None, top=25):
    """Inter-span host-gap attribution per span name: the time between one
    span's END and the NEXT same-name span's START on the same thread —
    for dispatch-shaped spans (``serving.decode_step``,
    ``serving.dispatch``) that is exactly the host time between an
    executable's return and the next enqueue, the seam the GL7xx
    dispatch lint prices (docs/static_analysis.md).

    Threaded spans interleave non-monotonically: a batcher's span can
    overlap the step span that contains it, so a successor may START
    before its predecessor ENDED and the raw gap goes negative. Negative
    gaps CLAMP TO ZERO per interval — they must not cancel real gaps
    elsewhere in the chain (the mxtrace gap-math fix).

    Accepts a loaded chrome-trace dict (mxtrace) or None for the live
    buffer (drains it, like ``span_summary``). ``prefix`` filters span
    names (``prefix="serving."``). Rows: ``{"name", "count", "intervals",
    "busy_ms", "gap_ms", "max_gap_ms", "clamped"}``, largest gap first.
    """
    per_site = {}  # (name, tid) -> list[(start_ms, dur_ms)]
    if trace is None:
        for name, t0, dur, ident, _attrs in spans.drain_events():
            if prefix and not name.startswith(prefix):
                continue
            per_site.setdefault((name, ident), []).append(
                (t0 * 1000.0, dur * 1000.0))
    else:
        for ev in trace.get("traceEvents", []):
            if ev.get("ph") != "X":
                continue
            name = ev.get("name", "?")
            if prefix and not name.startswith(prefix):
                continue
            per_site.setdefault((name, ev.get("tid", 0)), []).append(
                (ev.get("ts", 0) / 1000.0, ev.get("dur", 0) / 1000.0))
    acc = {}  # name -> [count, intervals, busy, gap, max_gap, clamped]
    for (name, _tid), evs in per_site.items():
        evs.sort(key=lambda e: e[0])
        row = acc.setdefault(name, [0, 0, 0.0, 0.0, 0.0, 0])
        prev_end = None
        for start, dur in evs:
            row[0] += 1
            row[2] += dur
            if prev_end is not None:
                raw = start - prev_end
                row[1] += 1
                if raw < 0.0:
                    row[5] += 1  # clamped interval, not a negative credit
                else:
                    row[3] += raw
                    row[4] = max(row[4], raw)
            prev_end = max(prev_end, start + dur) if prev_end is not None \
                else start + dur
    rows = [{"name": n, "count": c, "intervals": it,
             "busy_ms": round(busy, 3), "gap_ms": round(gap, 3),
             "max_gap_ms": round(mx, 3), "clamped": cl}
            for n, (c, it, busy, gap, mx, cl) in acc.items()]
    rows.sort(key=lambda r: -r["gap_ms"])
    return rows[:top]


def _fold_counters(dst, src):
    """Fold one process's counter snapshot into a fleet rollup: counters
    and gauges add, timer rows add total_ms/count (quantile fields are
    per-process — rebuilt fleet-wide from merged buckets, not summed)."""
    for k, v in (src or {}).items():
        if isinstance(v, dict):
            d = dst.setdefault(k, {"total_ms": 0.0, "count": 0})
            d["total_ms"] = round(d.get("total_ms", 0.0)
                                  + (v.get("total_ms") or 0.0), 3)
            d["count"] = d.get("count", 0) + (v.get("count") or 0)
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            dst[k] = (dst.get(k) or 0) + v
    return dst


def merge_traces(dumps, offsets_s=None, labels=None):
    """Align per-process chrome dumps into ONE fleet timeline.

    ``dumps`` are ``build_trace()`` dicts (live or JSON-loaded), each
    self-identified by ``otherData.pid``. ``offsets_s`` maps pid → clock
    correction in SECONDS, ADDED to that process's timestamps — the
    router's per-connection midpoint handshake (rpc.py) measures these,
    so replica spans land on the router's wall clock and a request's
    router→rpc→replica→dispatch chain reads monotonically. ``labels``
    maps pid → display name (``router``, ``replica-0``).

    The merged dump keeps the single-process schema (mxtrace --check
    passes on it) plus ``otherData.merged`` and a per-process block:
    ``processes[pid] = {label, counters, dropped, clock_offset_ms}``.
    Top-level counters/dropped are fleet-folded; steps come from the
    first dump (the router's lane)."""
    offsets_s = offsets_s or {}
    labels = labels or {}
    events, processes, counters = [], {}, {}
    dropped_total, steps, used_pids = 0, None, set()
    fleet = None
    for i, dump in enumerate(dumps):
        if not isinstance(dump, dict):
            continue
        other = dump.get("otherData") or {}
        pid = other.get("pid")
        if not isinstance(pid, int) or pid in used_pids:
            pid = 100000 + i
            while pid in used_pids:
                pid += 1
        used_pids.add(pid)
        off = offsets_s.get(pid, offsets_s.get(str(pid), 0.0)) or 0.0
        label = labels.get(pid, labels.get(str(pid))) \
            or "pid-%d" % pid
        events.append({"ph": "M", "pid": pid, "name": "process_name",
                       "args": {"name": label}})
        for ev in dump.get("traceEvents", []):
            if not isinstance(ev, dict):
                continue
            if ev.get("ph") == "M" and ev.get("name") == "process_name":
                continue      # replaced by the labeled one above
            ev = dict(ev)
            ev["pid"] = pid
            if off and isinstance(ev.get("ts"), (int, float)):
                ev["ts"] = round(ev["ts"] + off * 1e6, 1)
            events.append(ev)
        dropped = other.get("dropped") or 0
        dropped_total += dropped
        _fold_counters(counters, other.get("counters"))
        processes[str(pid)] = {
            "label": label, "dropped": dropped,
            "clock_offset_ms": round(off * 1000.0, 3),
            "counters": other.get("counters") or {}}
        if steps is None:
            steps = other.get("steps") or []
        if fleet is None and other.get("fleet"):
            fleet = other["fleet"]   # router's metrics() rollup survives
    merged_other = {"mxnet_telemetry": SCHEMA_VERSION,
                    "merged": True, "counters": counters,
                    "steps": steps or [], "dropped": dropped_total,
                    "processes": processes}
    if fleet is not None:
        merged_other["fleet"] = fleet
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": merged_other}


# counters the scoreboard cares about, reported per step when steps exist
_KEY_COUNTERS = ("executor.retrace", "executor.compile", "executor.cache_hit",
                 "kvstore.push_bytes", "kvstore.pull_bytes",
                 "engine.push")


def summarize():
    """Live summary for bench.py: the full counter snapshot, per-step rates
    of the scoreboard counters, and the heaviest spans (trace mode only).

    ``{"mode", "counters", "num_steps", "per_step", "spans"}`` — all
    JSON-safe, cheap to build (no device work)."""
    snap = registry.snapshot()
    rows = registry.step_rows()
    out = {"mode": {0: "off", 1: "counters", 2: "trace"}[spans.mode()],
           "counters": snap, "num_steps": len(rows)}
    if rows:
        per_step = {}
        for key in _KEY_COUNTERS:
            total = sum(r["counters"].get(key, 0) for r in rows)
            if total:
                per_step[key] = round(total / float(len(rows)), 3)
        timed = [r["wall_ms"] for r in rows if r["wall_ms"] is not None]
        if timed:
            per_step["wall_ms"] = round(sum(timed) / len(timed), 3)
        out["per_step"] = per_step
    if spans.tracing():
        out["spans"] = span_summary(top=10)
    return out
