"""The per-layer metrics of a decode dispatch's idle budget (PR 34): the wait
and the copy inside the blocking read, the dispatch read directly, the
caller's share between two steps, the gap from one decode program's end to the
next one's enqueue, and an admission's wait for its prefill. Each reader on
hand-made ``run.spans``, and the traced rehearsal that must report them."""
import json
import os
import shutil
import types

import pytest

from harness import main as harness_main, spec as spec_mod

GENERATE = ["transformer-base.generate", "granite-4.0-h-micro.generate",
            "kanana-2-30b-a3b.generate"]
SERVING = ["transformer-base.generate", "transformer-base.score",
           "olmoe-1b-7b.score", "granite-4.0-h-micro.generate",
           "kanana-2-30b-a3b.generate"]
# metric -> (span it reads, the ancestors it must sit under, its cells, the
# end-to-end metric it moves)
PHASES = {
    "serving.step_wait_ms_p50": (
        "serving.step.wait", ("serving.paged_step", "serving.decode_step",
                              "serving.step.read"),
        GENERATE, "gen_tokens_per_s"),
    "serving.step_copy_ms_p50": (
        "serving.step.copy", ("serving.paged_step", "serving.decode_step",
                              "serving.step.read"),
        GENERATE, "gen_tokens_per_s"),
    "serving.step_dispatch_ms_p50": (
        "serving.step.dispatch", ("serving.paged_step",
                                  "serving.decode_step"),
        GENERATE, "gen_tokens_per_s"),
    "serving.admit_wait_ms_p50": (
        "serving.admit.wait", ("serving.paged_admit",
                               "serving.admit.logits"),
        SERVING, "ttft_ms_p50"),
}
PAIRS = {"serving.step_between_ms_p50": (GENERATE, "gen_tokens_per_s"),
         "serving.step_gap_ms_p50": (GENERATE, "gen_tokens_per_s")}
NEW = ["serving.step_wait_ms_p50", "serving.step_copy_ms_p50",
       "serving.step_dispatch_ms_p50", "serving.step_between_ms_p50",
       "serving.step_gap_ms_p50", "serving.admit_wait_ms_p50"]


def reader(name):
    return spec_mod.load_module("layer_metrics", name).read


def run_with(spans=()):
    return types.SimpleNamespace(spans=list(spans))


class Timeline:
    """Hand-made ``run.spans`` rows on one clock (seconds), with the ids and
    parents the program would draw."""

    def __init__(self):
        self.rows, self._next = [], 1

    def add(self, name, t0, dur, parent=None):
        attrs = {"id": self._next}
        if parent is not None:
            attrs["parent"] = parent
        self.rows.append((name, t0, dur, attrs))
        self._next += 1
        return attrs["id"]

    def chain(self, names, t0, dur):
        """``names[0]`` ⊃ ``names[1]`` ⊃ ... at ``t0``; the innermost lasts
        ``dur``, the others a little longer."""
        parent = None
        for name in names[:-1]:
            parent = self.add(name, t0, dur + 1.0, parent)
        return self.add(names[-1], t0, dur, parent)

    def step(self, t0, stage=1e-3, dispatch=2e-3, wait=10e-3, copy=3e-3,
             commit=0.5e-3, account=0.25e-3):
        """One ``serving.paged_step`` as the decoder lays it out, opening at
        ``t0``. Returns when it closes."""
        total = stage + dispatch + wait + copy + commit + account
        step = self.add("serving.paged_step", t0, total)
        self.add("serving.step.stage", t0, stage, step)
        t = t0 + stage
        dec = self.add("serving.decode_step", t, dispatch + wait + copy, step)
        self.add("serving.step.dispatch", t, dispatch, dec)
        t += dispatch
        read = self.add("serving.step.read", t, wait + copy, dec)
        self.add("serving.step.wait", t, wait, read)
        self.add("serving.step.copy", t + wait, copy, read)
        t += wait + copy
        self.add("serving.step.commit", t, commit, step)
        self.add("serving.step.account", t + commit, account, step)
        return t0 + total

    def admit(self, t0, dur=5e-3):
        self.add("serving.paged_admit", t0, dur)
        return t0 + dur


@pytest.mark.parametrize("metric", sorted(PHASES))
def test_phase_reader_takes_the_median_under_its_own_ancestor(metric):
    span, ancestors, _cells, _moves = PHASES[metric]
    t = Timeline()
    for i, ms in enumerate((2.0, 4.0, 9.0)):
        t.chain(ancestors + (span,), float(i), ms / 1e3)
    # the same name under another parent (a chunked admission, a megastep)
    # is another layer's time, and so is one with no parent at all
    t.chain(("serving.chunk_prefill", "serving.step.read", span), 5.0, 0.5)
    t.chain(("serving.decode_megastep", span), 6.0, 0.5)
    t.add(span, 7.0, 0.7)
    assert reader(metric)(run_with(t.rows)) == pytest.approx(4.0)


@pytest.mark.parametrize("metric", NEW)
def test_reader_returns_none_without_its_spans(metric):
    assert reader(metric)(run_with()) is None
    # a program that draws no ids has nothing to read
    old = [("serving.paged_admit", 1.0, 0.05, {"seq": 0}),
           ("serving.paged_step", 2.0, 0.08, {"rows": 64, "paged": True}),
           ("serving.paged_step", 2.1, 0.08, {"rows": 64, "paged": True})]
    assert reader(metric)(run_with(old)) is None


def test_the_parent_s_spans_give_dispatch_and_between_and_nothing_else():
    """The program before PR 34 has ``serving.step.read`` whole and no
    ``serving.admit.wait``: the readers of what it lacks return None and do
    not raise."""
    t = Timeline()
    for t0 in (0.0, 0.020):
        step = t.add("serving.paged_step", t0, 0.018)
        dec = t.add("serving.decode_step", t0 + 1e-3, 0.016, step)
        t.add("serving.step.dispatch", t0 + 1e-3, 2e-3, dec)
        t.add("serving.step.read", t0 + 3e-3, 0.014, dec)
    admit = t.add("serving.paged_admit", 0.05, 5e-3)
    t.add("serving.admit.logits", 0.051, 3e-3, admit)
    got = {m: reader(m)(run_with(t.rows)) for m in NEW}
    assert got["serving.step_dispatch_ms_p50"] == pytest.approx(2.0)
    assert got["serving.step_between_ms_p50"] == pytest.approx(2.0)
    for metric in ("serving.step_wait_ms_p50", "serving.step_copy_ms_p50",
                   "serving.step_gap_ms_p50", "serving.admit_wait_ms_p50"):
        assert got[metric] is None


def test_gap_and_between_skip_a_pair_with_an_admission_between():
    t = Timeline()
    close = t.step(0.0)
    close = t.step(close + 1e-3)                  # 1 ms of caller between
    close = t.admit(close + 0.5e-3)               # an admission: no pair
    close = t.step(close + 0.5e-3)
    close = t.step(close + 3e-3)                  # 3 ms between
    t.step(close + 2e-3)                          # 2 ms between
    run = run_with(t.rows)
    assert reader("serving.step_between_ms_p50")(run) == pytest.approx(2.0)
    # copy 3 + commit 0.5 + account 0.25 + between + stage 1 + dispatch 2
    assert reader("serving.step_gap_ms_p50")(run) == pytest.approx(6.75 + 2.0)
    # without the admission the pair across it would count: 1, 6, 3, 2
    rows = [r for r in t.rows if r[0] != "serving.paged_admit"]
    assert reader("serving.step_between_ms_p50")(run_with(rows)) == \
        pytest.approx(2.5)


def test_an_admission_opening_inside_neither_step_only_breaks_its_own_pair():
    """An admission BEFORE the first step or AFTER the second breaks
    nothing."""
    t = Timeline()
    close = t.admit(0.0)
    close = t.step(close + 1e-3)
    close = t.step(close + 4e-3)
    t.admit(close + 1e-3)
    assert reader("serving.step_between_ms_p50")(run_with(t.rows)) == \
        pytest.approx(4.0)


def test_gap_is_the_sum_of_its_parts_on_one_pair():
    parts = dict(stage=1.25e-3, dispatch=4.75e-3, wait=22e-3, copy=2.5e-3,
                 commit=0.5e-3, account=0.125e-3)
    between = 1.5e-3
    t = Timeline()
    close = t.step(0.0, **parts)
    t.step(close + between, **parts)
    run = run_with(t.rows)
    got = {m: reader(m)(run) for m in NEW if m.startswith("serving.step")}
    assert got["serving.step_wait_ms_p50"] == pytest.approx(22.0)
    assert got["serving.step_copy_ms_p50"] == pytest.approx(2.5)
    assert got["serving.step_dispatch_ms_p50"] == pytest.approx(4.75)
    assert got["serving.step_between_ms_p50"] == pytest.approx(1.5)
    named = sum(got["serving.step_%s_ms_p50" % p]
                for p in ("copy", "between", "dispatch"))
    assert got["serving.step_gap_ms_p50"] == pytest.approx(
        named + 1e3 * (parts["commit"] + parts["account"] + parts["stage"]))
    # and a dispatch period is the gap plus the wait
    period = 1e3 * (close + between)
    assert got["serving.step_gap_ms_p50"] + got["serving.step_wait_ms_p50"] \
        == pytest.approx(period)


def test_the_pair_readers_take_steps_in_order_of_start_not_of_arrival():
    """The ring buffer holds a span when it CLOSES, children before parents:
    the readers sort."""
    t = Timeline()
    close = t.step(0.0)
    t.step(close + 2e-3)
    rows = list(reversed(t.rows))
    assert reader("serving.step_between_ms_p50")(run_with(rows)) == \
        pytest.approx(2.0)
    assert reader("serving.step_gap_ms_p50")(run_with(rows)) == \
        pytest.approx(8.75)


@pytest.mark.parametrize("metric", NEW)
def test_each_metric_is_declared_once_for_its_cells_with_a_reader(metric):
    spec = spec_mod.Spec()
    (entry,) = [m for m in spec.doc["per_layer"] if m["name"] == metric]
    cells, moves = PHASES[metric][2:] if metric in PHASES else PAIRS[metric]
    assert entry == {"name": metric, "unit": "ms", "better": "lower",
                     "source": "program_span", "layer": "serving",
                     "moves": moves, "workloads": cells}
    assert callable(reader(metric))
    # every cell on the list reports the end-to-end metric it moves
    (e2e,) = [m for m in spec.doc["end_to_end"] if m["name"] == moves]
    assert set(cells) <= set(e2e["workloads"])


def _rehearse(cell, capsys, tmp_path):
    """A traced rehearsal in a copy of its own: the harness keeps a cell's
    trace under ``<root>/.bench_trace/<cell>``, and another test file may be
    tracing the same cell in another worker."""
    root = str(tmp_path)
    shutil.copytree(spec_mod.BENCH_DIR, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(spec_mod.ROOT, "BENCHMARK.json"), root)
    try:
        rc = harness_main.main(["--workload", cell, "--seed", "2600000011",
                                "--seconds", "0.5", "--trace", "1",
                                "--rehearse-cpu"], root=root)
    finally:
        from harness import program

        program.telemetry().set_mode(None)
    out = capsys.readouterr().out.strip().splitlines()
    assert out[-1] == "*** REHEARSAL passed -- no result line ***" and rc == 0
    line = json.loads(out[-2].partition("REHEARSAL (not a result): ")[2])
    return {n: m["value"] for n, m in line["metrics"].items()}


def test_the_traced_rehearsal_of_generate_reports_the_whole_budget(capsys,
                                                                   tmp_path):
    got = _rehearse("transformer-base.generate", capsys, tmp_path)
    assert set(NEW) <= set(got)
    assert all(got[n] > 0 for n in NEW)
    # two halves of one span, medians each: about the whole, never far above
    halves = got["serving.step_wait_ms_p50"] + got["serving.step_copy_ms_p50"]
    assert halves <= 1.25 * got["serving.step_read_ms_p50"] + 0.5
    assert got["serving.admit_wait_ms_p50"] <= \
        got["serving.admit_logits_ms_p50"]
    named = sum(got["serving.step_%s_ms_p50" % p]
                for p in ("copy", "commit", "between", "stage", "dispatch"))
    assert named <= 1.5 * got["serving.step_gap_ms_p50"] + 1.0


def test_the_traced_rehearsal_of_score_reports_the_admission_s_wait(capsys,
                                                                    tmp_path):
    """A cell that never steps is on the admission metric's list alone."""
    got = _rehearse("transformer-base.score", capsys, tmp_path)
    assert 0 < got["serving.admit_wait_ms_p50"] <= \
        got["serving.admit_logits_ms_p50"]
    assert not {n for n in got if n.startswith("serving.step_")}
