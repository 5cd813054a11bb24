"""The decoder's record of the device (``kv_decode._DeviceRecord``): one
``serving.device_gap`` from every observed-ready to the next enqueue's close,
admissions included, through every path that enqueues; nothing of it with
telemetry off; the ``dispatch.host_gap`` timers fed from the same stamps; and
``serving.step.stage`` in three. Order, names and counts on the CPU: no time
is compared with anything but another stamp of the same run."""
import time

import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.serving import kv_decode
from mxnet_tpu.telemetry import spans

import test_rebind

GAP = "serving.device_gap"
# the arches the cells run that change the admission: pools only, per-lane
# rows beside them, rows and a window's rings and one pool two layers read
ARCHS = ["vaswani", "granite_hybrid", "phi4flash"]


@pytest.fixture
def tm():
    telemetry.reset()
    telemetry.clear_events()
    saved = telemetry.current_override()
    yield telemetry
    telemetry.set_mode(saved)
    telemetry.reset()
    telemetry.clear_events()


def _close(e):
    return e[1] + e[2]


def _events(tm):
    return sorted(tm.drain_events(), key=lambda e: (e[1], -e[2]))


def _gaps(events):
    return [e for e in events if e[0] == GAP]


def _argmax(rows):
    return {s: int(np.argmax(r)) for s, r in rows.items()}


def _drive(dec):
    """admit -> step -> step -> admit -> step -> retire: what is handed
    back, as arrays, in order."""
    out = []
    a, logits = dec.admit(np.asarray([3, 1, 4, 1, 5], np.float32))
    out.append(np.asarray(logits))
    nxt = {a: int(np.argmax(logits))}
    for _ in range(2):
        rows = dec.step(nxt)
        out.extend(np.asarray(rows[s]) for s in sorted(rows))
        nxt = _argmax(rows)
    b, logits = dec.admit(np.asarray([2, 7, 1], np.float32))
    out.append(np.asarray(logits))
    nxt[b] = int(np.argmax(logits))
    rows = dec.step(nxt)
    out.extend(np.asarray(rows[s]) for s in sorted(rows))
    for s in (a, b):
        dec.retire(s)
    return out


# ----------------------------------------------------------- the record alone
def test_the_record_keeps_what_was_enqueued_behind_the_waited_program(tm):
    tm.set_mode("trace")
    rec = kv_decode._DeviceRecord()
    rec.enqueued("cow")
    rec.enqueued("prefill")
    rec.enqueued("admit_scatter")
    assert tm.drain_events() == []          # no ready stands: no gap
    rec.ready("prefill")
    assert rec.in_flight == ["admit_scatter"]
    assert (rec.after, rec.behind) == ("prefill", "admit_scatter")
    rec.enqueued("decode")
    rec.enqueued("decode")                  # one gap a ready, not one an enqueue
    (gap,) = tm.drain_events()
    assert gap[0] == GAP and gap[1] == rec.ready_t and gap[2] >= 0
    assert gap[4] == {"after": "prefill", "before": "decode",
                      "behind": "admit_scatter"}
    rec.ready("decode")                     # the newest of its kind
    assert rec.in_flight == [] and rec.behind == ""
    # a program enqueued while the record was off: everything leaves
    rec.enqueued("chunk")
    rec.ready("megastep")
    assert rec.in_flight == [] and rec.after == "megastep"


# ------------------------------------------------------- through the decoder
@pytest.mark.parametrize("arch", ARCHS)
def test_one_gap_from_every_ready_to_the_next_enqueues_close(tm, arch):
    dec = test_rebind._decoder(arch).warmup()
    tm.set_mode("trace")
    tm.clear_events()
    _drive(dec)
    events = _events(tm)
    by_name = {}
    for e in events:
        by_name.setdefault(e[0], []).append(e)
    gaps = _gaps(events)
    # five programs were waited for; the last has no enqueue behind it
    assert [(g[4]["after"], g[4]["before"], g[4]["behind"]) for g in gaps] \
        == [("prefill", "decode", "admit_scatter"), ("decode", "decode", ""),
            ("decode", "prefill", ""), ("prefill", "decode", "admit_scatter")]
    assert all(set(g[4]) == {"after", "before", "behind"} for g in gaps)
    # each gap starts where its wait closed, before anything else opens,
    # and ends where the next enqueue's span closed, before the next opens
    waits = sorted(by_name["serving.step.wait"] + by_name["serving.admit.wait"],
                   key=lambda e: e[1])
    enqueues = sorted(by_name["serving.step.dispatch"] +
                      by_name["serving.admit.prefill"], key=lambda e: e[1])
    assert len(waits) == 5 and len(enqueues) == 5
    for gap, wait, enqueue in zip(gaps, waits, enqueues[1:]):
        after_wait = min(e[1] for e in events
                         if e[0] != GAP and e[1] >= _close(wait))
        assert _close(wait) <= gap[1] <= after_wait
        after_enqueue = min(e[1] for e in events
                            if e[0] != GAP and e[1] >= _close(enqueue))
        assert _close(enqueue) <= _close(gap) <= after_enqueue
    # the device is busy while the host waits: no gap overlaps a wait
    for gap in gaps:
        for wait in waits:
            assert _close(gap) <= wait[1] or _close(wait) <= gap[1]
    # the last step's result stands ready, nothing behind it
    assert dec._device.in_flight == [] and dec._device.after == "decode"
    assert dec._last_return_t >= _close(gaps[-1])


def test_megastep_chunk_and_copied_page_record_through_the_same_record(tm):
    dec = test_rebind._decoder("vaswani", prefix_cache=True,
                               prefix_chunk=4).warmup()
    prompt = np.asarray([3, 1, 4, 1, 5, 9], np.float32)   # a chunk and a tail
    tm.set_mode("trace")
    seq, logits = dec.admit(prompt)
    nxt = {seq: int(np.argmax(logits))}
    dec.step_megastep(nxt, k=2)             # compiles; its result stands ready
    tm.clear_events()
    other, logits = dec.admit(prompt)       # its chunk cached, the tail run
    nxt[other] = int(np.argmax(logits))
    toks = dec.step_megastep(nxt, k=2)
    nxt = {s: int(t[-1]) for s, t in toks.items()}
    twin = dec.fork(seq)                    # mid-page: the next write copies
    nxt[twin] = nxt[seq]
    dec.step(nxt)
    kinds = [(g[4]["after"], g[4]["before"], g[4]["behind"])
             for g in _gaps(_events(tm))]
    assert kinds == [("megastep", "chunk", ""), ("chunk", "megastep", ""),
                     ("megastep", "cow", "")]
    assert tm.counters()["serving.cow_copies"] >= 1
    # the copied page went in front of the step that wrote into it
    assert dec._device.after == "decode" and dec._device.in_flight == []


def test_a_cold_chunked_admission_gaps_chunk_to_chunk(tm):
    dec = test_rebind._decoder("vaswani", prefix_cache=True,
                               prefix_chunk=4).warmup()
    tm.set_mode("trace")
    tm.clear_events()
    dec.admit(np.asarray([3, 1, 4, 1, 5, 9, 2, 6, 5], np.float32))
    assert [(g[4]["after"], g[4]["before"]) for g in _gaps(_events(tm))] \
        == [("chunk", "chunk")] * 2


# ------------------------------------------------------------ telemetry off
@pytest.mark.parametrize("arch", ARCHS)
def test_telemetry_off_reads_no_clock_and_changes_no_output(tm, monkeypatch,
                                                            arch):
    tm.set_mode("trace")
    traced = _drive(test_rebind._decoder(arch).warmup())
    assert _gaps(tm.drain_events())
    tm.clear_events()
    tm.set_mode("0")
    dec = test_rebind._decoder(arch).warmup()   # a twin: the same frames

    reads = []

    class Clock:
        """``kv_decode``'s ``time``: counts the reads of ``perf_counter``."""

        def __getattr__(self, name):
            return getattr(time, name)

        @staticmethod
        def perf_counter():
            reads.append(1)
            return time.perf_counter()

    monkeypatch.setattr(kv_decode, "time", Clock())
    plain = _drive(dec)
    assert not reads and tm.drain_events() == []
    record = dec._device
    assert (record.in_flight, record.ready_t, record.after) == ([], None, None)
    assert len(plain) == len(traced)
    for got, want in zip(plain, traced):
        np.testing.assert_array_equal(got, want)
    tm.set_mode("counters")                 # the clock is read again when on
    _drive(dec)
    assert reads and tm.drain_events() == []


@pytest.mark.parametrize("what", ["step", "admit"])
def test_mode_checks_of_a_step_and_an_admission_with_telemetry_off(
        tm, monkeypatch, what):
    """The parent commit read the mode 14 times in a warm step and 14 in a
    warm admission of this decoder; the record and the stage's parts may add
    one to each."""
    dec = test_rebind._decoder("vaswani").warmup()
    seq, logits = dec.admit(np.asarray([3, 1, 4], np.float32))
    dec.step({seq: int(np.argmax(logits))})
    tm.set_mode("0")
    checks = []
    mode = spans.mode

    def counting():
        checks.append(1)
        return mode()

    monkeypatch.setattr(spans, "mode", counting)
    if what == "step":
        dec.step({seq: 1})
    else:
        dec.admit(np.asarray([2, 7], np.float32))
    assert 0 < len(checks) <= 15


# ----------------------------------------------------- the timers, the stage
def test_host_gap_timers_tick_from_the_records_stamps(tm):
    """``dispatch.host_gap`` in ``counters`` mode: the record's ready to where
    the next decode-side dispatch is about to be enqueued, along a steady
    chain; an admission's time never counts, a copied page does not hide the
    stamp."""
    dec = test_rebind._decoder("vaswani").warmup()
    tm.set_mode("counters")
    seq, logits = dec.admit(np.asarray([3, 1, 4, 1, 5], np.float32))
    nxt = {seq: int(np.argmax(logits))}
    agg = tm.timer("dispatch.host_gap")
    site = tm.timer("dispatch.host_gap.serving.paged_step")
    nxt = _argmax(dec.step(nxt))
    assert agg.count == 0                   # the step after an admission
    nxt = _argmax(dec.step(nxt))
    assert agg.count == site.count == 1
    twin = dec.fork(seq)                    # position 7, mid-page: the next
    nxt[twin] = nxt[seq]                    # write copies it for one side
    nxt = _argmax(dec.step(nxt))
    assert tm.counters()["serving.cow_copies"] == 1
    nxt = _argmax(dec.step(nxt))
    assert agg.count == site.count == 3 and agg.total_ms > 0
    dec.admit(np.asarray([2, 7], np.float32))
    dec.step(nxt)
    assert agg.count == 3                   # the chain restarts
    toks = dec.step_megastep(nxt, k=2)
    assert agg.count == 4 and \
        tm.timer("dispatch.host_gap.serving.paged_megastep").count == 1
    assert set(toks) == set(nxt) and tm.drain_events() == []


def test_the_stage_is_three_parts_inside_the_span_that_stays(tm):
    dec = test_rebind._decoder("vaswani").warmup()
    seq, logits = dec.admit(np.asarray([3, 1, 4], np.float32))
    tm.set_mode("trace")
    tm.clear_events()
    dec.step({seq: int(np.argmax(logits))})
    events = _events(tm)
    (stage,) = [e for e in events if e[0] == "serving.step.stage"]
    parts = [e for e in events if e[4].get("parent") == stage[4]["id"]]
    assert [p[0] for p in parts] == ["serving.step.stage.slots",
                                     "serving.step.stage.table",
                                     "serving.step.stage.put"]
    for earlier, later in zip(parts, parts[1:]):
        assert _close(earlier) <= later[1]
    assert stage[1] <= parts[0][1] and _close(parts[-1]) <= _close(stage)
    assert sum(p[2] for p in parts) <= stage[2]
