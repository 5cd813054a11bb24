"""Profiler (reference: python/mxnet/profiler.py + src/engine/profiler.cc).

The reference hand-stamped per-op start/end times in the engine and emitted
Chrome trace-event JSON (SURVEY.md §5.1). Here a capture is TWO coordinated
recorders:

  * the XLA trace — ``jax.profiler`` capture into ``<filename dir>/jax_trace``
    (viewable in TensorBoard/Perfetto, a superset of the chrome-trace
    contract), and
  * the framework telemetry spans (mxnet_tpu.telemetry) — engine/executor/
    kvstore/io/serving/trainer seams, forced to ``trace`` mode for
    the window even when ``MXNET_TELEMETRY`` is off.

The two share one clock: in ``trace`` mode every telemetry span is also a
``jax.profiler.TraceAnnotation`` of the same name, so the XLA capture's
``.xplane.pb`` holds the framework's spans on the ``/host:CPU`` thread
lines beside ``/device:TPU:n``'s ``XLA Ops`` (whose programs are named
``jit_mx_decode``, ``jit_mx_train_step``...): one capture shows what the
device ran and what the framework was doing meanwhile
(docs/OBSERVABILITY.md).

``dump_profile()`` finalizes both and honors the reference ``MXDumpProfile``
contract: it writes the framework spans as chrome-trace JSON to the
configured ``filename`` (with the XLA trace directory recorded in
``otherData.xla_trace_dir`` so viewers can merge), and returns that path.
State transitions are idempotent: ``profiler_set_state('run')`` while
running, ``'stop'`` while stopped, and ``dump_profile()`` with no capture
are all clean no-ops that never leave ``_state``/``_trace_dir`` torn.
"""
from __future__ import annotations

import logging
import os

from .base import MXNetError
from . import telemetry as _tm

__all__ = ["profiler_set_config", "profiler_set_state", "dump_profile",
           "trace_files", "summarize", "State"]

_LOG = logging.getLogger("mxnet_tpu")

_config = {"mode": "symbolic", "filename": "profile.json"}
_state = "stop"
_trace_dir = None     # XLA capture dir of the current/last capture
_dump_path = None     # framework chrome-trace written by the last dump
_xla_active = False   # jax.profiler capture actually started
_captured = False     # at least one capture window ran (dump has content)
_saved_override = None  # telemetry mode override to restore at stop


class State:
    stop = "stop"
    run = "run"


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """(reference: profiler.py profiler_set_config; modes kOnlySymbolic/
    kAllOperator — with one fused XLA program the distinction collapses)."""
    if mode not in ("symbolic", "all"):
        raise MXNetError("profiler mode must be 'symbolic' or 'all'")
    _config["mode"] = mode
    _config["filename"] = filename


def profiler_set_state(state="stop"):
    """(reference: profiler.py profiler_set_state). Idempotent in both
    directions: re-entering the current state is a no-op."""
    global _state, _trace_dir, _xla_active, _captured, _saved_override
    if state not in ("stop", "run"):
        raise MXNetError("profiler state must be 'stop' or 'run'")
    if state == _state:
        return  # already there — never tear _trace_dir/telemetry mode

    if state == "run":
        # frame the capture window: force span recording on, remember what
        # to restore (an explicit set_mode override, or the env default)
        _saved_override = _tm.current_override()
        _tm.set_mode("trace")
        _tm.clear_events()
        _trace_dir = os.path.join(
            os.path.dirname(os.path.abspath(_config["filename"])) or ".",
            "jax_trace")
        _xla_active = False
        try:
            import jax

            jax.profiler.start_trace(_trace_dir)
            _xla_active = True
        except Exception as exc:
            # framework spans still record; the dump just has no XLA half
            _LOG.warning("profiler: XLA trace capture failed to start (%s); "
                         "capturing framework spans only", exc)
        _state = "run"
        _captured = True
        return

    # state == "stop"
    if _xla_active:
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as exc:
            _LOG.warning("profiler: XLA trace capture failed to stop: %s",
                         exc)
        _xla_active = False
    _tm.set_mode(_saved_override)
    _state = "stop"


def dump_profile():
    """Finalize the capture and write the framework chrome-trace JSON to the
    configured ``filename`` (reference: MXDumpProfile). Returns the written
    path — or ``None``, cleanly, when no capture ever ran."""
    global _dump_path
    if _state == "run":
        profiler_set_state("stop")
    if not _captured:
        return None  # nothing recorded; stay consistent instead of raising
    _dump_path = os.path.abspath(_config["filename"])
    _tm.export_chrome_trace(
        _dump_path, xla_trace_dir=_trace_dir,
        extra={"profiler_mode": _config["mode"]})
    return _dump_path


def trace_files(trace_dir=None):
    """Every artifact the capture produced, framework AND XLA: the
    chrome-trace JSON ``dump_profile`` wrote (if any) plus the
    perfetto/xplane files under ``<dir>/plugins/profile/<ts>/``. Empty
    list = no capture (or the capture failed)."""
    import glob

    d = trace_dir or _trace_dir
    out = []
    if (trace_dir is None or trace_dir == _trace_dir) \
            and _dump_path and os.path.exists(_dump_path):
        out.append(_dump_path)
    if d:
        out.extend(sorted(glob.glob(
            os.path.join(d, "plugins", "profile", "*", "*"))))
    return out


def _framework_rows(trace_dir):
    """Aggregate framework spans for the CURRENT capture: from the dumped
    chrome-trace when one exists, else the live telemetry buffer. An
    explicit ``trace_dir`` naming a DIFFERENT capture gets no framework
    rows — this process's buffer/dump says nothing about an archived
    trace, and attributing it there would misreport where that capture's
    time went."""
    if trace_dir is not None and trace_dir != _trace_dir:
        return []
    trace = None
    if _dump_path and os.path.exists(_dump_path):
        import json

        try:
            with open(_dump_path) as f:
                trace = json.load(f)
        except (OSError, ValueError):
            trace = None
    rows = _tm.span_summary(trace=trace, top=None if trace else 10**6)
    return [{"process": "mxnet_tpu framework", "name": r["name"],
             "ms": r["ms"], "count": r["count"]} for r in rows]


def summarize(trace_dir=None, top=25, device_only=True):
    """Aggregate per-kernel wall time from a captured trace — the per-op
    stat table of the reference's engine profiler (src/engine/profiler.cc
    chrome-trace events), recovered from the XLA trace and MERGED with the
    framework telemetry spans.

    Returns a list of {"name", "ms", "count", "process"} dicts, heaviest
    first. ``device_only=False`` includes host-side python/runtime spans
    and the framework spans (framework seams are host work by definition).
    """
    import collections
    import glob
    import gzip
    import json
    import re

    d = trace_dir or _trace_dir
    out = []
    files = sorted(glob.glob(
        os.path.join(d or ".", "plugins", "profile", "*",
                     "*.trace.json.gz")))
    if files:
        raw = json.loads(gzip.open(files[-1]).read().decode())
        events = raw.get("traceEvents", [])
        pids = {e["pid"]: e["args"].get("name", "")
                for e in events if e.get("ph") == "M"
                and e.get("name") == "process_name"}
        acc = collections.Counter()
        cnt = collections.Counter()
        for e in events:
            if e.get("ph") != "X":
                continue
            proc = pids.get(e["pid"], str(e["pid"]))
            if device_only and "TPU" not in proc and "GPU" not in proc \
                    and "device" not in proc.lower():
                continue
            name = e.get("name", "?")
            # drop the whole-program umbrella spans and bare step-number
            # marks
            if name.startswith("jit_") or re.fullmatch(r"\d+", name):
                continue
            key = (proc, name)
            acc[key] += e.get("dur", 0)
            cnt[key] += 1
        out = [{"process": proc, "name": name,
                "ms": round(us / 1000.0, 3), "count": cnt[(proc, name)]}
               for (proc, name), us in acc.items()]
    if not device_only:
        out.extend(_framework_rows(trace_dir))
    out.sort(key=lambda r: -r["ms"])
    return out[:top]
