"""Telemetry subsystem (mxnet_tpu/telemetry/, docs/OBSERVABILITY.md):
registry correctness under threads, zero-overhead off path, chrome-trace
schema, executor retrace counting, profiler state idempotency, and the
end-to-end fit trace."""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tm():
    """Fresh registry + explicit mode control, restored afterwards."""
    telemetry.reset()
    telemetry.clear_events()
    saved = telemetry.current_override()
    yield telemetry
    telemetry.set_mode(saved)
    telemetry.reset()
    telemetry.clear_events()


def _conv_bn_net():
    sym = mx.sym.Variable("data")
    sym = mx.sym.Convolution(sym, kernel=(3, 3), pad=(1, 1), num_filter=8,
                             no_bias=True, name="conv1")
    sym = mx.sym.BatchNorm(sym, name="bn1")
    sym = mx.sym.Activation(sym, act_type="relu")
    sym = mx.sym.Flatten(sym)
    sym = mx.sym.FullyConnected(sym, num_hidden=16, name="fc1")
    sym = mx.sym.Activation(sym, act_type="relu")
    sym = mx.sym.FullyConnected(sym, num_hidden=4, name="fc")
    return mx.sym.SoftmaxOutput(sym, name="softmax")


# --------------------------------------------------------------- registry
def test_counters_exact_under_threads(tm):
    tm.set_mode("counters")
    c = tm.counter("t.threads")
    timer = tm.timer("t.timer")
    N, T = 2000, 8

    def work():
        for _ in range(N):
            c.inc()
            timer.add(0.001)

    threads = [threading.Thread(target=work) for _ in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == N * T
    assert timer.count == N * T
    assert abs(timer.total_ms - N * T) < 1e-6 * N * T + 1e-3


def test_span_buffer_under_threads(tm):
    tm.set_mode("trace")
    N, T = 200, 6

    def work(k):
        for i in range(N):
            with tm.span("t.span", worker=k):
                pass

    threads = [threading.Thread(target=work, args=(k,)) for k in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    events = tm.drain_events()
    mine = [e for e in events if e[0] == "t.span"]
    assert len(mine) == N * T
    # every worker's spans landed, attrs intact (thread IDENTS can be
    # reused once a thread exits, so count workers, not idents)
    assert {e[4]["worker"] for e in mine} == set(range(T))


def test_step_stats_deltas(tm):
    tm.set_mode("counters")
    c = tm.counter("t.step")
    c.inc(3)
    tm.mark_step()
    c.inc(2)
    row = tm.mark_step()
    assert row["counters"] == {"t.step": 2}
    rows = tm.step_rows()
    assert [r["step"] for r in rows] == [0, 1]
    assert rows[0]["counters"] == {"t.step": 3}
    assert rows[1]["wall_ms"] is not None  # second mark has a delta


# ------------------------------------------------------------- off = free
def test_off_by_default_allocates_no_spans(tm):
    tm.set_mode(None)
    env = os.environ.get("MXNET_TELEMETRY")
    try:
        os.environ.pop("MXNET_TELEMETRY", None)
        assert not telemetry.enabled() and not telemetry.tracing()
        # the off path returns ONE shared no-op object — no allocation
        s1 = telemetry.span("engine.push")
        s2 = telemetry.span("kvstore.pull", nkeys=3)
        assert s1 is s2 is telemetry.NULL_SPAN
        with s1 as s:
            s.set(anything=1)  # all methods are no-ops
        telemetry.event("x")  # swallowed
        assert telemetry.drain_events() == []
    finally:
        if env is not None:
            os.environ["MXNET_TELEMETRY"] = env


def test_env_gating_modes(tm):
    tm.set_mode(None)
    env = os.environ.get("MXNET_TELEMETRY")
    try:
        os.environ["MXNET_TELEMETRY"] = "counters"
        assert telemetry.enabled() and not telemetry.tracing()
        os.environ["MXNET_TELEMETRY"] = "trace"
        assert telemetry.enabled() and telemetry.tracing()
        os.environ["MXNET_TELEMETRY"] = "bogus"  # warns once, stays off
        assert not telemetry.enabled()
    finally:
        if env is None:
            os.environ.pop("MXNET_TELEMETRY", None)
        else:
            os.environ["MXNET_TELEMETRY"] = env


# ----------------------------------------------------------- chrome trace
def test_chrome_trace_schema(tm, tmp_path):
    from mxnet_tpu.telemetry import cli

    tm.set_mode("trace")
    tm.counter("executor.compile").inc()
    with tm.span("executor.forward", cache="compile"):
        with tm.span("engine.wait_for_all"):
            pass
    tm.mark_step()
    path = str(tmp_path / "trace.json")
    tm.export_chrome_trace(path, xla_trace_dir=str(tmp_path / "jax_trace"))
    trace = json.load(open(path))
    assert cli.check(trace) == []
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert {e["cat"] for e in xs} == {"executor", "engine"}
    assert all(e["dur"] >= 0 and e["ts"] > 0 for e in xs)
    other = trace["otherData"]
    assert other["mxnet_telemetry"] == telemetry.SCHEMA_VERSION
    assert other["counters"]["executor.compile"] == 1
    assert len(other["steps"]) == 1
    # the CLI agrees, end to end
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxtrace"), path,
         "--check"], capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    # and a corrupted dump fails the gate
    bad = dict(trace)
    bad["traceEvents"] = [{"no_ph": True}]
    assert cli.check(bad)


# ------------------------------------------------- host-gap attribution
def _gap_trace(spans_us, name="serving.decode_step", tid=1):
    """Minimal chrome-trace dict: one thread, one span name."""
    events = [{"ph": "M", "pid": 1, "name": "process_name",
               "args": {"name": "t"}}]
    events += [{"ph": "X", "pid": 1, "tid": tid, "name": name,
                "cat": "serving", "ts": ts, "dur": dur}
               for ts, dur in spans_us]
    return {"traceEvents": events, "otherData": {}}


def test_gap_summary_clamps_negative_interleaved_gaps(tm):
    """The mxtrace gap-math regression: threaded spans interleave
    non-monotonically, so a successor can START before its predecessor
    ENDED. The negative raw gap must clamp to zero (counted in
    ``clamped``) — NOT subtract from the real gaps in the chain."""
    # end 10ms; +5ms gap; span ending 25ms; OVERLAP (starts 20 < 25, raw
    # gap -5ms); then a +10ms gap after the running max end (30ms)
    rows = telemetry.gap_summary(trace=_gap_trace(
        [(0, 10000), (15000, 10000), (20000, 10000), (40000, 5000)]))
    assert len(rows) == 1
    r = rows[0]
    assert r["name"] == "serving.decode_step"
    assert r["count"] == 4 and r["intervals"] == 3
    assert r["clamped"] == 1
    # 5 + 10 — a buggy negative credit would report 10 (or less)
    assert r["gap_ms"] == pytest.approx(15.0)
    assert r["max_gap_ms"] == pytest.approx(10.0)
    assert r["busy_ms"] == pytest.approx(35.0)


def test_gap_summary_separates_threads_and_live_buffer(tm):
    # same name on two tids: gaps attribute per thread, never across
    tr = _gap_trace([(0, 1000), (5000, 1000)])
    tr["traceEvents"] += _gap_trace([(2000, 1000), (9000, 1000)],
                                    tid=2)["traceEvents"][1:]
    r = telemetry.gap_summary(trace=tr)[0]
    assert r["count"] == 4 and r["intervals"] == 2
    assert r["gap_ms"] == pytest.approx(4.0 + 6.0)
    # live-buffer form drains real spans, like span_summary
    tm.set_mode("trace")
    for _ in range(3):
        with tm.span("t.gap"):
            pass
    rows = telemetry.gap_summary()
    mine = [x for x in rows if x["name"] == "t.gap"]
    assert mine and mine[0]["intervals"] == 2
    assert mine[0]["gap_ms"] >= 0.0


def test_mxtrace_reports_gap_attribution(tm, tmp_path):
    from mxnet_tpu.telemetry import cli

    path = str(tmp_path / "gap_trace.json")
    with open(path, "w") as f:
        json.dump(_gap_trace([(0, 10000), (15000, 10000)]), f)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxtrace"), path,
         "--json"], capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    payload = json.loads(out.stdout)
    assert payload["gaps"][0]["name"] == "serving.decode_step"
    assert payload["gaps"][0]["gap_ms"] == pytest.approx(5.0)
    # the human table renders the same attribution section
    assert "host-gap attribution" in subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxtrace"), path],
        capture_output=True, text=True).stdout
    # cli-level: the GL705 lint consumes these rows directly
    from mxnet_tpu.analysis import dispatch_lint
    diags = dispatch_lint.lint_dispatch_gaps(
        [{"name": "serving.decode_step", "intervals": 9, "busy_ms": 10.0,
          "gap_ms": 9.0}], pct=0.5)
    assert [d.code for d in diags] == ["GL705"]


# ------------------------------------------------------ executor counters
def test_retrace_counter_on_cache_busting_rebind(tm):
    tm.set_mode("counters")
    sym = _conv_bn_net()
    exe = mx.executor.simple_bind(sym, mx.cpu(), data=(2, 3, 8, 8),
                                  softmax_label=(2,))
    exe.forward_backward()
    assert tm.counter("executor.compile").value == 1
    assert tm.counter("executor.retrace").value == 0
    exe.forward_backward()
    assert tm.counter("executor.cache_hit").value == 1
    # deliberate cache bust: reshape shares the program, so the new batch
    # size is a NEW abstract signature on the same jit entry — a retrace
    exe2 = exe.reshape(allow_up_sizing=True, data=(4, 3, 8, 8),
                       softmax_label=(4,))
    exe2.forward_backward()
    assert tm.counter("executor.retrace").value == 1
    reason = tm.gauge("executor.last_retrace_reason").value
    assert reason  # GL201-203 diagnosis (or the explicit none-found text)
    exe2.forward_backward()
    assert tm.counter("executor.cache_hit").value == 2


def _sum_of(n_args):
    """A graph of ``n_args`` arguments, all of one shape."""
    net = mx.sym.Variable("a0")
    for i in range(1, n_args):
        net = net + mx.sym.Variable("a%d" % i)
    return net


def test_a_second_executor_of_another_shape_still_counts_one_retrace(tm):
    """An executor asks the shared program once a jit entry; the program
    still tells a new signature from one it has seen, whoever brought it."""
    tm.set_mode("trace")
    sym = _sum_of(3)
    exe = mx.executor.simple_bind(sym, mx.cpu(), grad_req="null",
                                  a0=(2, 4), a1=(2, 4), a2=(2, 4))
    other = exe.reshape(allow_up_sizing=True, a0=(3, 4), a1=(3, 4),
                        a2=(3, 4))
    back = other.reshape(a0=(2, 4), a1=(2, 4), a2=(2, 4))
    assert other._prog is exe._prog is back._prog

    def counts():
        c = tm.counters()
        return [c.get("executor." + k, 0)
                for k in ("compile", "cache_hit", "retrace")]

    exe.forward()
    assert counts() == [1, 0, 0]
    other.forward()
    assert counts() == [1, 0, 1]
    assert tm.gauge("executor.last_retrace_reason").value
    other.forward()
    exe.forward()
    assert counts() == [1, 2, 1]
    back.forward()                      # its first call: a shape seen before
    assert counts() == [1, 3, 1]
    spans = [e[4] for e in sorted(tm.drain_events(), key=lambda e: e[1])
             if e[0] == "executor.forward"]
    assert [a["cache"] for a in spans] == ["compile", "retrace", "cache_hit",
                                           "cache_hit", "cache_hit"]
    assert "retrace_reason" in spans[1] and "retrace_reason" not in spans[2]
    # train and inference are two jit entries of one program
    exe.forward(is_train=True)
    assert counts() == [2, 3, 1]


@pytest.mark.parametrize("n_args", [2, 48])
def test_a_steady_forward_is_classified_without_looking_at_an_argument(
        tm, monkeypatch, n_args):
    """A thousand ``forward``s of one executor: one compile, 999 cache hits,
    and after the first call neither the signature is rebuilt nor the
    program asked, however many arguments the executable takes."""
    from mxnet_tpu import executor as ex

    tm.set_mode("counters")
    shapes = {"a%d" % i: (2, 2) for i in range(n_args)}
    exe = mx.executor.simple_bind(_sum_of(n_args), mx.cpu(), grad_req="null",
                                  **shapes)
    calls = {"signature": 0, "note_call": 0}
    signature, note_call = ex._signature, ex._GraphProgram._note_call

    def counted_signature(arrays):
        calls["signature"] += 1
        return signature(arrays)

    def counted_note_call(self, *args, **kwargs):
        calls["note_call"] += 1
        return note_call(self, *args, **kwargs)

    monkeypatch.setattr(ex, "_signature", counted_signature)
    monkeypatch.setattr(ex._GraphProgram, "_note_call", counted_note_call)
    exe.forward()
    first = dict(calls)
    assert first == {"signature": 2, "note_call": 1}    # args and aux, once
    for _ in range(999):
        exe.forward()
    assert calls == first
    c = tm.counters()
    assert c["executor.compile"] == 1 and c["executor.cache_hit"] == 999
    assert c.get("executor.retrace", 0) == 0


def test_a_bound_argument_keeps_its_shape_and_type_through_every_write(tm):
    """What lets an executor classify a call without its arguments: nothing
    written into a bound array changes the shape or the type it was bound
    with, whether the value changes hands by reference, is cast, is
    broadcast, or lands in a view."""
    import jax.numpy as jnp

    tm.set_mode("counters")
    exe = mx.executor.simple_bind(_sum_of(2), mx.cpu(), grad_req="null",
                                  a0=(4, 3), a1=(4, 3))
    arr = exe.arg_dict["a0"]
    bound = (arr._jax().shape, arr._jax().dtype)
    exe.rebind(["a0"], [jnp.ones((4, 3), jnp.float32)])        # by reference
    exe.rebind(["a0"], [jnp.ones((4, 3), jnp.bfloat16)])       # cast
    exe.rebind(["a0"], [np.ones((4, 3), np.float64)])          # host, cast
    arr[:] = 2.0                                               # broadcast
    arr[1:3] = np.ones((2, 3), np.float16)                     # a view
    arr[:] = mx.nd.ones((4, 3), dtype="int32")
    assert (arr._jax().shape, arr._jax().dtype) == bound
    c = tm.counters()
    assert c["executor.rebind"] == 1 and c["executor.rebind_copy"] == 2
    exe.forward()
    exe.forward()
    assert tm.counters()["executor.cache_hit"] == 1


# ------------------------------------------------------------- profiler
def test_profiler_state_idempotent(tm, tmp_path):
    from mxnet_tpu import profiler

    profiler.profiler_set_config(filename=str(tmp_path / "p.json"))
    profiler.profiler_set_state("run")
    st = profiler._state
    td = profiler._trace_dir
    profiler.profiler_set_state("run")  # no-op, no torn state
    assert profiler._state == st and profiler._trace_dir == td
    # the capture window forces span recording even though MXNET_TELEMETRY
    # is unset in this process
    assert telemetry.tracing()
    with telemetry.span("test.captured"):
        x = mx.nd.ones((8, 8))
        (x + 1).wait_to_read()
    profiler.profiler_set_state("stop")
    profiler.profiler_set_state("stop")  # no-op
    assert profiler._state == "stop"
    path = profiler.dump_profile()
    assert path and os.path.exists(path)
    trace = json.load(open(path))
    assert trace["otherData"]["mxnet_telemetry"] == telemetry.SCHEMA_VERSION
    # merged artifact listing: the framework dump + the XLA capture files
    files = profiler.trace_files()
    assert path in files
    assert any(f.endswith((".trace.json.gz", ".xplane.pb")) for f in files)
    # merged summary carries both process lanes
    rows = profiler.summarize(device_only=False, top=100)
    assert any(r["process"] == "mxnet_tpu framework" for r in rows)


def test_dump_profile_without_capture_is_clean(tmp_path):
    # fresh subprocess: no capture must ever have run in-process
    code = (
        "import os; os.environ['MXNET_DEFAULT_CONTEXT']='cpu'\n"
        "from mxnet_tpu import profiler\n"
        "assert profiler.dump_profile() is None\n"
        "assert profiler.trace_files() == []\n"
        "profiler.profiler_set_state('stop')\n"  # stop-while-stopped: no-op
        "assert profiler.dump_profile() is None\n"
        "print('CLEAN')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=ROOT,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr
    assert "CLEAN" in out.stdout


# ------------------------------------------------- observers (satellites)
def test_monitor_stat_helper_guards_non_numeric(tm):
    mon = mx.monitor.Monitor(1)
    mon.tic()
    mon.stat_helper("ok", mx.nd.ones((2, 2)))

    class Boom:
        def asnumpy(self):
            raise TypeError("not numeric")

    mon.stat_helper("bad", Boom())  # must not raise mid-fit
    res = mon.toc()
    stats = {k: v for _, k, v in res}
    assert stats["ok"] == "1.0"
    assert "stat failed" in stats["bad"]


def test_monitor_toc_reads_telemetry_registry(tm):
    tm.set_mode("counters")
    mon = mx.monitor.Monitor(1)
    mon.tic()
    tm.counter("kvstore.push_bytes").inc(128)
    tm.mark_step()
    res = mon.toc()
    stats = {k: v for _, k, v in res}
    assert stats["telemetry.kvstore.push_bytes"] == "128"


def test_speedometer_reads_step_registry(tm, caplog):
    import logging

    tm.set_mode("counters")
    sp = mx.callback.Speedometer(batch_size=10, frequent=2)

    class P:
        epoch, eval_metric = 0, None

    # steps of known duration via explicit wall_ms
    for n in range(1, 5):
        telemetry.mark_step(wall_ms=100.0)
        P.nbatch = n
        with caplog.at_level(logging.INFO):
            sp(P)
    msgs = [r.message for r in caplog.records if "samples/sec" in r.message]
    assert msgs, "Speedometer never logged"
    # 2 batches x 10 samples over 2 x 100ms = 100 samples/sec
    assert any("Speed: 100.00 samples/sec" in m for m in msgs), msgs

    # staleness guard: a loop that does NOT mark steps (score/predict after
    # a fit) must not recycle the fit's rows as its own speed — it falls
    # back to the local wall clock (fast here, so >> 100 samples/sec)
    caplog.clear()
    for n in range(5, 9):
        P.nbatch = n
        with caplog.at_level(logging.INFO):
            sp(P)
    stale = [r.message for r in caplog.records if "samples/sec" in r.message]
    assert stale and not any("Speed: 100.00 samples/sec" in m
                             for m in stale), stale


# ------------------------------------------------------------ end to end
@pytest.mark.slow
def test_fit_trace_end_to_end(tm, tmp_path):
    """The acceptance path: a 3-step fit with MXNET_TELEMETRY=trace dumps a
    chrome trace holding engine/executor/kvstore/io spans, >=1
    compile and >=1 cache-hit step, and mxtrace --check passes."""
    tm.set_mode("trace")
    from mxnet_tpu import profiler

    sym = _conv_bn_net()
    rs = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rs.rand(12, 3, 8, 8).astype("float32"),
                           rs.randint(0, 4, (12,)).astype("float32"),
                           batch_size=4)
    mod = mx.mod.Module(sym, context=mx.cpu())
    profiler.profiler_set_config(filename=str(tmp_path / "profile.json"))
    profiler.profiler_set_state("run")
    mod.fit(it, num_epoch=1, kvstore=mx.kv.create("local"),
            epoch_end_callback=mx.callback.do_checkpoint(
                str(tmp_path / "ck")))
    mx.nd.waitall()
    path = profiler.dump_profile()
    trace = json.load(open(path))
    cats = {e.get("cat") for e in trace["traceEvents"] if e["ph"] == "X"}
    assert {"engine", "executor", "kvstore", "io"} <= cats, cats
    counters = trace["otherData"]["counters"]
    assert counters.get("executor.compile", 0) >= 1
    assert counters.get("executor.cache_hit", 0) >= 1
    assert counters.get("kvstore.push_bytes", 0) > 0
    assert len(trace["otherData"]["steps"]) == 3
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxtrace"), path,
         "--check"], capture_output=True, text=True)
    assert out.returncode == 0, out.stderr


# ----------------------------------------------- dropped-span accounting
def test_dropped_events_are_accounted(tm, monkeypatch):
    """Ring-buffer overflow must be VISIBLE: the evicted-span count ticks
    a counter, lands in the dump metadata, and survives until clear."""
    import collections

    from mxnet_tpu.telemetry import spans as spans_mod

    tm.set_mode("trace")
    monkeypatch.setattr(spans_mod, "_events",
                        collections.deque(maxlen=50))
    for i in range(60):
        tm.record_span("t.flood", float(i), 0.001)
    assert tm.dropped_events() == 10
    assert tm.counters()["telemetry.dropped_events"] == 10
    trace = tm.build_trace()
    assert trace["otherData"]["dropped"] == 10
    assert len([e for e in trace["traceEvents"]
                if e.get("ph") == "X"]) == 50
    tm.clear_events()
    assert tm.dropped_events() == 0


def test_mxtrace_check_warns_on_truncated_dump(tm, tmp_path, capsys):
    from mxnet_tpu.telemetry import cli

    tm.set_mode("trace")
    trace = tm.build_trace()
    trace["otherData"]["dropped"] = 7
    p = tmp_path / "trunc.json"
    p.write_text(json.dumps(trace))
    assert cli.main([str(p), "--check"]) == 0  # truncated, not invalid
    out = capsys.readouterr().out
    assert "TRUNCATED" in out and "7" in out


# ------------------------------------------------- trace context (fleet)
def test_trace_scope_stamps_and_restores(tm):
    tm.set_mode("trace")
    assert tm.trace_context() is None
    with tm.trace_scope("aaaa000011112222"):
        assert tm.trace_context() == "aaaa000011112222"
        with tm.span("t.inner"):
            pass
        tm.event("t.mark")
        with tm.trace_scope("bbbb000011112222"):
            assert tm.trace_context() == "bbbb000011112222"
        assert tm.trace_context() == "aaaa000011112222"  # restored
        # an explicit trace_id attr wins over the ambient context
        with tm.span("t.explicit", trace_id="cccc000011112222"):
            pass
    assert tm.trace_context() is None
    by_name = {e[0]: e[4] for e in tm.drain_events()}
    assert by_name["t.inner"]["trace_id"] == "aaaa000011112222"
    assert by_name["t.mark"]["trace_id"] == "aaaa000011112222"
    assert by_name["t.explicit"]["trace_id"] == "cccc000011112222"


def test_record_span_out_of_band(tm):
    """record_span appends an interval measured across threads (replica
    queue-wait) — no-op below trace mode, inherits the trace context."""
    tm.set_mode("counters")
    tm.record_span("t.oob", 1.0, 0.5)
    tm.set_mode("trace")
    assert tm.drain_events() == []
    with tm.trace_scope("dddd000011112222"):
        tm.record_span("t.oob", 2.0, 0.25, replica="r1")
    (name, t0, dur, _ident, attrs), = tm.drain_events()
    assert (name, t0, dur) == ("t.oob", 2.0, 0.25)
    assert attrs == {"replica": "r1", "trace_id": "dddd000011112222"}


# -------------------------------------------- span summary tail latency
def test_span_summary_rows_carry_quantiles(tm):
    """The mxtrace top-N table reads p50/p95/p99 per span name — a
    90/10 bimodal span whose mean (~11ms) describes NEITHER mode."""
    import time

    tm.set_mode("trace")
    t0 = time.perf_counter()
    for i in range(90):
        tm.record_span("t.bimodal", t0 + i, 0.001)
    for i in range(10):
        tm.record_span("t.bimodal", t0 + 90 + i, 0.100)
    row, = [r for r in telemetry.span_summary(top=5)
            if r["name"] == "t.bimodal"]
    assert row["count"] == 100
    from mxnet_tpu.telemetry import histogram as hg
    assert row["p50_ms"] == pytest.approx(1.0, rel=hg.REL_ERROR + 0.01)
    assert row["p95_ms"] == pytest.approx(100.0, rel=hg.REL_ERROR + 0.01)
    assert row["p99_ms"] == pytest.approx(100.0, rel=hg.REL_ERROR + 0.01)


def test_timer_snapshot_quantiles(tm):
    tm.set_mode("counters")
    t = tm.timer("t.lat")
    for _ in range(95):
        t.add(0.002)
    for _ in range(5):
        t.add(0.900)    # 5% tail so the nearest-rank p99 lands in it
    snap = tm.snapshot()["t.lat"]
    assert snap["count"] == 100
    from mxnet_tpu.telemetry import histogram as hg
    assert snap["p50_ms"] == pytest.approx(2.0, rel=hg.REL_ERROR + 0.01)
    assert snap["p99_ms"] == pytest.approx(900.0, rel=hg.REL_ERROR + 0.01)
    # per-step rows diff the BUCKETS, so a quiet step shows its own tail
    tm.mark_step()
    for _ in range(10):
        t.add(0.004)
    row = tm.mark_step()
    assert row["timers"]["t.lat"]["count"] == 10
    assert row["timers"]["t.lat"]["p99_ms"] == pytest.approx(
        4.0, rel=hg.REL_ERROR + 0.01)


# --------------------------------------------------- fleet trace merging
def test_merge_traces_builds_one_fleet_timeline(tm):
    """Two per-process dumps sharing a trace_id merge into one dump:
    re-pidded, clock-offset applied, labels installed, counters folded,
    and the request chain spans both processes."""
    import time

    from mxnet_tpu.telemetry import cli

    tm.set_mode("trace")
    t0 = time.perf_counter()
    with tm.trace_scope("deadbeefcafe0123"):
        with tm.span("fleet.dispatch", replica="r0"):
            pass
    d1 = tm.build_trace()
    d1["otherData"]["pid"] = 111
    d1["otherData"]["counters"] = {
        "fleet.requests": 3, "t.req": {"total_ms": 6.0, "count": 3}}
    tm.clear_events()
    with tm.trace_scope("deadbeefcafe0123"):
        tm.record_span("serving.dispatch", t0, 0.002, rows=4)
    d2 = tm.build_trace()
    d2["otherData"]["pid"] = 222
    d2["otherData"]["counters"] = {
        "fleet.requests": 2, "t.req": {"total_ms": 4.0, "count": 2}}
    ts_before = [e["ts"] for e in d2["traceEvents"] if e.get("ph") == "X"]

    merged = telemetry.merge_traces(
        [d1, d2], offsets_s={222: 1.5},
        labels={111: "router", 222: "replica-0"})
    assert cli.check(merged) == []
    other = merged["otherData"]
    assert other["merged"] is True
    assert other["counters"]["fleet.requests"] == 5
    assert other["counters"]["t.req"] == {"total_ms": 10.0, "count": 5}
    assert other["processes"]["111"]["label"] == "router"
    assert other["processes"]["222"]["clock_offset_ms"] == 1500.0
    metas = {e["pid"]: e["args"]["name"] for e in merged["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert metas == {111: "router", 222: "replica-0"}
    # replica timestamps moved onto the router's wall clock
    ts_after = [e["ts"] for e in merged["traceEvents"]
                if e.get("ph") == "X" and e["pid"] == 222]
    assert len(ts_after) == len(ts_before)
    for got, was in zip(ts_after, ts_before):
        assert got == pytest.approx(was + 1.5e6, abs=0.2)
    # ONE trace_id joins spans from both processes
    chains = cli.request_chains(merged)
    assert set(chains) == {"deadbeefcafe0123"}
    assert {s["pid"] for s in chains["deadbeefcafe0123"]} == {111, 222}


def test_mxtrace_fleet_and_fleet_trace_views(tm, tmp_path, capsys):
    """mxtrace merges multiple dump arguments (honoring stamped
    clock_offset_s / label), keeps the router's fleet rollup block, and
    renders --fleet + --fleet-trace."""
    import time

    from mxnet_tpu.telemetry import cli

    tm.set_mode("trace")
    t0 = time.perf_counter()
    with tm.trace_scope("feedfacefeedface"):
        tm.record_span("fleet.dispatch", t0, 0.004, replica="r0")
    d1 = tm.build_trace()
    d1["otherData"].update(pid=111, label="router")
    d1["otherData"]["fleet"] = {
        "qps": 12.5, "requests": 100, "errors": 1, "shed": 0,
        "latency_ms": {"fleet.request": {
            "count": 100, "p50": 4.0, "p95": 9.0, "p99": 12.0}},
        "replicas": {"r0": {"state": "up", "qps": 12.5, "requests": 100,
                            "clock_offset_ms": 250.0}},
        "slo": {"ok": False, "burn_rate": 2.5, "burn_threshold": 1.0,
                "window_s": 4.0, "short_window_s": 1.0,
                "objectives": {"err_pct": {
                    "threshold": 1.0, "burn_rate": 2.5, "value": 2.0,
                    "firing": True}}},
        "violations": [{"kind": "slo.violation", "objective": "err_pct"}],
    }
    tm.clear_events()
    with tm.trace_scope("feedfacefeedface"):
        tm.record_span("serving.dispatch", t0, 0.002)
    d2 = tm.build_trace()
    d2["otherData"].update(pid=222, label="replica-0", clock_offset_s=0.25)

    p1, p2 = tmp_path / "router.json", tmp_path / "r0.json"
    p1.write_text(json.dumps(d1))
    p2.write_text(json.dumps(d2))
    out = tmp_path / "fleet.json"
    assert cli.main([str(p1), str(p2), "--out", str(out),
                     "--check"]) == 0, capsys.readouterr().err
    capsys.readouterr()
    merged = json.loads(out.read_text())
    assert merged["otherData"]["merged"]
    assert merged["otherData"]["processes"]["222"]["clock_offset_ms"] \
        == 250.0
    assert merged["otherData"]["fleet"]["requests"] == 100

    assert cli.main([str(out), "--fleet", "--fleet-trace"]) == 0
    text = capsys.readouterr().out
    assert "fleet:" in text and "qps=12.5" in text
    assert "fleet.request" in text
    assert "slo: ok=False" in text and "FIRING" in text
    assert "request feedfacefeedface" in text
    assert "router" in text and "replica-0" in text


# --------------------------------------------------------- SLO burn rate
def test_slo_spec_parse_forms(tmp_path):
    from mxnet_tpu.telemetry.slo import SloSpec

    s = SloSpec.parse("p99_ms:250, err_pct:1 ,avail_pct:99")
    assert s.objectives == {"p99_ms": 250.0, "err_pct": 1.0,
                            "avail_pct": 99.0}
    assert SloSpec.parse('{"p99_ms": 100}').objectives == {"p99_ms": 100.0}
    f = tmp_path / "slo.json"
    f.write_text('{"err_pct": 2}')
    assert SloSpec.parse(str(f)).objectives == {"err_pct": 2.0}
    # a trailing comma is tolerated (k:v lists paste from shells)
    assert SloSpec.parse("p99_ms:250,").objectives == {"p99_ms": 250.0}
    with pytest.raises(ValueError):
        SloSpec.parse("bogus_key:1")
    with pytest.raises(ValueError):
        SloSpec.parse("p99_ms")       # no value
    with pytest.raises(ValueError):
        SloSpec({"err_pct": 0})       # out of range
    with pytest.raises(ValueError):
        SloSpec({"avail_pct": 120})


def test_slo_monitor_fire_and_clear_cycle(tm):
    """Error burst trips the multi-window burn gate; clean traffic rolls
    it out of both windows and the matching clear event is emitted."""
    from mxnet_tpu.telemetry.slo import SloMonitor, SloSpec

    tm.set_mode("trace")
    mon = SloMonitor(SloSpec.parse("err_pct:10"), window_s=4.0,
                     short_window_s=1.0, burn_threshold=1.0)
    mon.observe(total=100, errors=0, t=100.0)
    mon.observe(total=100, errors=0, t=101.0)
    r = mon.evaluate(t=101.5)
    assert r["ok"] and r["burn_rate"] == 0.0
    # burst: 80% errors = 8x the 10% budget in the short window, and
    # enough to push the long window over too (multi-window AND)
    mon.observe(total=100, errors=80, t=102.0)
    r = mon.evaluate(t=102.2)
    assert not r["ok"]
    obj = r["objectives"]["err_pct"]
    assert obj["firing"] and obj["short"] > obj["long"] >= 1.0
    assert r["burn_rate"] >= 1.0
    assert mon.firing() == ["err_pct"]
    assert tm.snapshot()["slo.burn_rate"] >= 1.0  # gauge published
    # recovery: clean ticks age the burst past the 4s window
    for i in range(4):
        mon.observe(total=100, errors=0, t=103.0 + i)
    r = mon.evaluate(t=106.5)
    assert r["ok"] and mon.firing() == []
    kinds = [v["kind"] for v in mon.violations()]
    assert kinds == ["slo.violation", "slo.clear"]
    viol = mon.violations()[0]
    assert viol["objective"] == "err_pct" and viol["burn_rate"] >= 1.0
    # structured span events rode along for the trace timeline
    names = [e[0] for e in tm.drain_events()]
    assert "slo.violation" in names and "slo.clear" in names


def test_slo_latency_objective_over_buckets(tm):
    """p99 objective burns by the fraction of bucketed samples over the
    ceiling — fed the same sparse buckets the fleet wire ships."""
    from mxnet_tpu.telemetry.histogram import Histogram
    from mxnet_tpu.telemetry.slo import SloMonitor, SloSpec

    tm.set_mode("counters")
    mon = SloMonitor(SloSpec.parse("p99_ms:50"), window_s=4.0,
                     short_window_s=1.0, burn_threshold=1.0)
    good = Histogram()
    for _ in range(995):
        good.record(0.010)
    for _ in range(5):
        good.record(0.200)   # 0.5% tail: half the 1% budget
    mon.observe(total=1000, latency_buckets=good.to_dict()["buckets"],
                t=100.0)
    r = mon.evaluate(t=100.5)
    assert r["ok"]
    assert r["objectives"]["p99_ms"]["value"] == pytest.approx(10.0,
                                                               rel=0.15)
    bad = Histogram()
    for _ in range(950):
        bad.record(0.010)
    for _ in range(50):
        bad.record(0.200)    # 5% tail: 5x the budget
    mon.observe(total=1000, latency_buckets=bad.to_dict()["buckets"],
                t=101.0)
    r = mon.evaluate(t=101.2)
    assert not r["ok"] and r["objectives"]["p99_ms"]["firing"]
    assert r["objectives"]["p99_ms"]["value"] > 50.0


def test_slo_availability_objective(tm):
    from mxnet_tpu.telemetry.slo import SloMonitor, SloSpec

    tm.set_mode("counters")
    mon = SloMonitor(SloSpec.parse("avail_pct:99"), window_s=4.0,
                     short_window_s=1.0, burn_threshold=1.0)
    mon.observe(available=True, t=10.0)
    assert mon.evaluate(t=10.5)["ok"]
    mon.observe(available=False, t=11.0)   # replica-less tick
    r = mon.evaluate(t=11.2)
    assert not r["ok"] and r["objectives"]["avail_pct"]["firing"]


# ------------------------------ span ids, parents, and the profiler's clock
def _by_name(events):
    return {e[0]: e for e in events}


def test_span_ids_and_parents_nest_per_thread(tm):
    """Every trace-mode span carries a process-unique ``id`` and the
    ``parent`` id of the span open on ITS thread; roots carry no parent."""
    tm.set_mode("trace")
    ready = threading.Barrier(3)

    def work(k):
        with tm.span("t%d.outer" % k):
            ready.wait(timeout=30)  # all three outers open at once
            with tm.span("t%d.mid" % k):
                with tm.span("t%d.leaf" % k):
                    pass
            with tm.span("t%d.second" % k):
                pass

    threads = [threading.Thread(target=work, args=(k,)) for k in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    ev = _by_name(tm.drain_events())
    ids = [e[4]["id"] for e in ev.values()]
    assert len(ev) == 12 and len(set(ids)) == 12
    assert all(isinstance(i, int) for i in ids)
    for k in range(3):
        outer = ev["t%d.outer" % k][4]
        assert "parent" not in outer
        assert ev["t%d.mid" % k][4]["parent"] == outer["id"]
        assert ev["t%d.second" % k][4]["parent"] == outer["id"]
        assert ev["t%d.leaf" % k][4]["parent"] == ev["t%d.mid" % k][4]["id"]
        # one thread's spans never adopt another thread's open span
        assert len({ev["t%d.%s" % (k, n)][3]
                    for n in ("outer", "mid", "leaf", "second")}) == 1


def test_span_parent_stack_is_popped_when_the_body_raises(tm):
    tm.set_mode("trace")
    with tm.span("t.root"):
        with pytest.raises(ValueError):
            with tm.span("t.raises"):
                with tm.span("t.inside"):
                    raise ValueError("boom")
        with tm.span("t.after"):
            pass
    with tm.span("t.next_root"):
        pass
    ev = _by_name(tm.drain_events())
    root = ev["t.root"][4]["id"]
    assert ev["t.raises"][4]["parent"] == root
    assert ev["t.raises"][4]["error"] == "ValueError"
    assert ev["t.inside"][4]["parent"] == ev["t.raises"][4]["id"]
    # the raise left nothing on the stack: siblings and later roots are right
    assert ev["t.after"][4]["parent"] == root
    assert "parent" not in ev["t.next_root"][4]


def test_ids_and_parents_reach_the_chrome_trace_unchanged(tm):
    tm.set_mode("trace")
    with tm.span("t.root", seq=7):
        with tm.span("t.child"):
            pass
    rows = {e["name"]: e for e in telemetry.build_trace()["traceEvents"]
            if e.get("ph") == "X"}
    assert rows["t.root"]["args"]["seq"] == 7
    assert rows["t.child"]["args"]["parent"] == rows["t.root"]["args"]["id"]


@pytest.mark.parametrize("mode", ["0", "counters"])
def test_off_and_counters_build_no_span_draw_no_id_no_annotation(
        tm, mode, monkeypatch):
    from mxnet_tpu.telemetry import spans

    def built(*_a, **_k):
        raise AssertionError("the %s path must not get here" % mode)

    monkeypatch.setattr(spans, "_Span", built)
    monkeypatch.setattr(spans, "_annotation", built)
    monkeypatch.setattr(spans, "_span_ids", iter(()))  # next() would raise
    tm.set_mode(mode)
    with tm.span("t.off", a=1) as s:
        s.set(b=2)
    assert s is telemetry.NULL_SPAN
    tm.event("t.event")
    tm.record_span("t.rec", 0.0, 1.0)
    assert tm.drain_events() == []


_ALONE = r"""
import importlib.util, os, sys
pkg = os.path.join(sys.argv[1], "mxnet_tpu", "telemetry")
spec = importlib.util.spec_from_file_location(
    "telemetry_alone", os.path.join(pkg, "__init__.py"),
    submodule_search_locations=[pkg])
tm = importlib.util.module_from_spec(spec)
sys.modules["telemetry_alone"] = tm
spec.loader.exec_module(tm)
for mode in ("0", "counters", "trace"):
    tm.set_mode(mode)
    with tm.span("alone.outer"):
        with tm.span("alone.inner"):
            tm.counter("alone.count").inc()
names = [e[0] for e in tm.drain_events()]
assert names == ["alone.inner", "alone.outer"], names
loaded = sorted(m for m in sys.modules if m == "jax" or m.startswith("jax."))
assert not loaded, loaded
print("ok")
"""


def test_telemetry_alone_imports_no_jax_in_any_mode():
    """The package by itself, outside mxnet_tpu: spans in all three modes,
    and neither jax nor jax.profiler was imported on its account (trace
    mode annotates only when the process has jax loaded already)."""
    out = subprocess.run([sys.executable, "-c", _ALONE, ROOT],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def _host_events(trace_dir):
    """{name: [(thread line's index, start ns, end ns)]} of the /host:CPU
    plane (thread lines share names, so a line is known by its place)."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (i, e.start_ns, e.start_ns + e.duration_ns))
    return out


def test_spans_land_in_the_profiler_trace_with_the_same_nesting(tm, tmp_path):
    """One span, two sinks: under a jax.profiler capture every ring-buffer
    span has exactly one event of its name on a /host:CPU thread line of the
    .xplane.pb, enclosing its children there as in the ring buffer, with a
    duration within 0.2 ms of the perf_counter one."""
    import time

    import jax
    import jax.numpy as jnp

    tm.set_mode("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        def side():
            with tm.span("p.side", worker=1):
                time.sleep(0.001)

        with tm.span("p.root", seq=3, label="x", ratio=0.5, flag=True,
                     skipped=(1, 2)):       # a non-scalar attr is left out
            with tm.span("p.sleep"):
                time.sleep(0.002)
            with tm.span("p.device"):
                with tm.span("p.device.inner"):
                    (jnp.ones((8, 8)) @ jnp.ones((8, 8))).block_until_ready()
            t = threading.Thread(target=side)
            t.start()
            t.join(timeout=30)
            assert not t.is_alive()
    finally:
        jax.profiler.stop_trace()
    ring = _by_name(tm.drain_events())
    assert set(ring) == {"p.root", "p.sleep", "p.device", "p.device.inner",
                         "p.side"}
    host = _host_events(str(tmp_path))
    for name, (_n, _t0, dur, _tid, _attrs) in ring.items():
        assert len(host.get(name, [])) == 1, (name, host.get(name))
        _line, start, end = host[name][0]
        assert abs((end - start) / 1e9 - dur) < 2e-4, name
    by_id = {e[4]["id"]: e[0] for e in ring.values()}
    for name, e in ring.items():
        parent = by_id.get(e[4].get("parent"))
        if parent is None:
            continue
        line, start, end = host[name][0]
        pline, pstart, pend = host[parent][0]
        assert line == pline and pstart <= start and end <= pend, name
    assert host["p.side"][0][0] != host["p.root"][0][0]  # its own thread line
    assert "parent" not in ring["p.side"][4]
