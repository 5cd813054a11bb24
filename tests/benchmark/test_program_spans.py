"""The per-layer metrics that read the program's own phase spans and its
decode-bytes counter: each reader on hand-made spans, and two rehearsals that
must report them."""
import json
import types

import pytest

from harness import main as harness_main, spec as spec_mod

ADMIT = ("stage", "prefill", "logits", "scatter")
STEP = ("stage", "read", "commit")
PHASE_READERS = [("serving.admit_%s_ms_p50" % p, "serving.admit." + p,
                  ("serving.paged_admit",)) for p in ADMIT]
PHASE_READERS += [("serving.step_stage_ms_p50", "serving.step.stage",
                   ("serving.paged_step",)),
                  ("serving.step_read_ms_p50", "serving.step.read",
                   ("serving.paged_step", "serving.decode_step")),
                  ("serving.step_commit_ms_p50", "serving.step.commit",
                   ("serving.paged_step",))]
NEW = [r[0] for r in PHASE_READERS] + ["trainer.dispatch_ms_p50",
                                        "kernels.hbm_share.serving"]


def reader(name):
    return spec_mod.load_module("layer_metrics", name).read


def run_with(spans=(), counters=None, summary=None, peaks=None):
    return types.SimpleNamespace(spans=list(spans), counters_window=counters,
                                 trace_summary=summary, peaks=peaks)


class Spans:
    """Hand-made ``run.spans`` rows with the ids the program would draw."""

    def __init__(self):
        self.rows, self._next = [], 1

    def add(self, name, dur_s, parent=None):
        attrs = {"id": self._next}
        if parent is not None:
            attrs["parent"] = parent
        self.rows.append((name, float(self._next), dur_s, attrs))
        self._next += 1
        return attrs["id"]

    def chain(self, names, dur_s):
        """``names[0]`` ⊃ ``names[1]`` ⊃ ...; the innermost lasts ``dur_s``."""
        parent = None
        for name in names[:-1]:
            parent = self.add(name, 1.0, parent)
        return self.add(names[-1], dur_s, parent)


@pytest.mark.parametrize("metric,span,ancestors", PHASE_READERS)
def test_phase_reader_takes_the_median_under_its_own_parent(metric, span,
                                                            ancestors):
    s = Spans()
    for ms in (2.0, 4.0, 9.0):
        s.chain(ancestors + (span,), ms / 1e3)
    # the same phase name under another parent is another layer's time
    s.chain(("serving.chunk_prefill", span), 0.5)
    s.add(span, 0.7)                      # and one with no parent at all
    assert reader(metric)(run_with(s.rows)) == pytest.approx(4.0)


@pytest.mark.parametrize("metric", [r[0] for r in PHASE_READERS] +
                         ["trainer.dispatch_ms_p50"])
def test_span_reader_returns_none_without_its_span(metric):
    assert reader(metric)(run_with()) is None
    # a program that draws no ids (the parent commit) has nothing to read
    old = [("serving.paged_admit", 1.0, 0.05, {"seq": 0}),
           ("serving.decode_step", 2.0, 0.08, {"rows": 64, "paged": True})]
    assert reader(metric)(run_with(old)) is None


def test_trainer_dispatch_reads_the_whole_trainer_step_span():
    s = Spans()
    for ms in (1.0, 3.0, 2.0):
        step = s.add("trainer.step", ms / 1e3)
        s.add("trainer.place", 0.1e-3, step)
        s.add("trainer.dispatch", 0.5e-3, step)
    assert reader("trainer.dispatch_ms_p50")(run_with(s.rows)) == \
        pytest.approx(2.0)


def test_serving_hbm_share_is_counted_bytes_over_busy_time_over_peak():
    read = reader("kernels.hbm_share.serving")
    peaks = {"hbm_bytes_per_s": 800e9}
    summary = {"busy_s": 2.0}
    full = run_with(counters={"serving.decode_xla_bytes": 400e9},
                    summary=summary, peaks=peaks)
    assert read(full) == pytest.approx(25.0)
    for missing in (run_with(counters={}, summary=summary, peaks=peaks),
                    run_with(counters=None, summary=summary, peaks=peaks),
                    run_with(counters={"serving.decode_xla_bytes": 1},
                             summary=None, peaks=peaks),
                    run_with(counters={"serving.decode_xla_bytes": 1},
                             summary=summary, peaks=None)):
        assert read(missing) is None


def test_every_new_metric_is_declared_once_with_a_reader_of_its_name():
    spec = spec_mod.Spec()
    tail = [m["name"] for m in spec.doc["per_layer"][-len(NEW):]]
    assert tail == NEW                      # appended, in the issue's order
    for m in spec.doc["per_layer"][-len(NEW):]:
        want = "device_trace" if m["name"].startswith("kernels.") \
            else "program_span"
        assert m["source"] == want and callable(reader(m["name"]))


# (cell, what the phases must add up to: the parent span's own metric)
REHEARSALS = [("transformer-base.generate", "serving.admit_ms_p50"),
              ("resnet50.train", None)]


@pytest.mark.parametrize("cell,whole", REHEARSALS)
def test_traced_rehearsal_reports_the_new_metrics_of_its_cell(cell, whole,
                                                              capsys):
    try:
        rc = harness_main.main(["--workload", cell, "--seed", "5",
                                "--seconds", "0.5", "--trace", "1",
                                "--rehearse-cpu"])
    finally:
        from harness import program

        program.telemetry().set_mode(None)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0 and out[-1] == "*** REHEARSAL passed -- no result line ***"
    line = json.loads(out[-2].partition("REHEARSAL (not a result): ")[2])
    declared = {m["name"] for m in spec_mod.Spec().metrics("per_layer", cell)}
    # no peaks in a rehearsal, so no share of a peak
    want = (declared & set(NEW)) - {"kernels.hbm_share.serving"}
    assert want and want <= set(line["metrics"])
    values = {n: line["metrics"][n]["value"] for n in want}
    assert all(v > 0 for v in values.values())
    if whole:
        parts = sum(values["serving.admit_%s_ms_p50" % p] for p in ADMIT)
        # medians of parts of one interval: about the whole, never far above
        assert parts <= 1.5 * line["metrics"][whole]["value"] + 1.0
