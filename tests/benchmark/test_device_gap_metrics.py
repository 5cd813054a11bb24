"""The seven per-layer metrics that read the decoder's ``serving.device_gap``
records and the three parts of ``serving.step.stage``: each reader on
hand-made ``run.spans`` rows, the entries by membership, and two rehearsals
whose lines must carry them."""
import json
import os
import types

import pytest

from harness import contract, main as harness_main, spec as spec_mod

GAP = "serving.device_gap"
SERVING = ["transformer-base.generate", "transformer-base.score",
           "olmoe-1b-7b.score", "granite-4.0-h-micro.generate",
           "kanana-2-30b-a3b.generate", "lfm2-24b-a2b.generate",
           "mimo-v2-flash.generate", "phi-4-mini-flash-reasoning.generate",
           "nemotron-3-nano-30b-a3b.generate"]
GENERATE = [c for c in SERVING if c.endswith(".generate")]
# name: (unit, the end-to-end metric it moves, the cells that report it)
ENTRIES = {
    "serving.device_gap_share": ("%", "gen_tokens_per_s", SERVING),
    "serving.gap_before_step_ms_p50": ("ms", "gen_tokens_per_s", GENERATE),
    "serving.gap_before_admit_ms_p50": ("ms", "ttft_ms_p50", SERVING),
    "serving.gap_unspanned_ms_p50": ("ms", "gen_tokens_per_s", SERVING),
    "serving.step_stage_slots_ms_p50": ("ms", "gen_tokens_per_s", GENERATE),
    "serving.step_stage_table_ms_p50": ("ms", "gen_tokens_per_s", GENERATE),
    "serving.step_stage_put_ms_p50": ("ms", "gen_tokens_per_s", GENERATE),
}
STAGE_PARTS = ("slots", "table", "put")


def reader(name):
    return spec_mod.load_module("layer_metrics", name).read


def run_with(spans=(), window=(0.0, 10.0)):
    return types.SimpleNamespace(spans=list(spans), window=window)


def gap(start, dur, before="decode", after="decode", behind=""):
    return (GAP, start, dur, {"after": after, "before": before,
                              "behind": behind})


# ------------------------------------------------------------- the gap readers
@pytest.mark.parametrize("metric", sorted(ENTRIES))
def test_a_program_that_records_none_of_it_gives_nothing_to_read(metric):
    assert reader(metric)(run_with()) is None
    # the parent commit: spans with ids, no gap record and one stage span
    old = [("serving.paged_step", 1.0, 0.02, {"id": 1}),
           ("serving.step.stage", 1.0, 0.001, {"id": 2, "parent": 1}),
           ("serving.step.wait", 1.002, 0.01, {"id": 3, "parent": 1}),
           ("serving.paged_admit", 2.0, 0.05, {"id": 4, "seq": 0})]
    assert reader(metric)(run_with(old)) is None


def test_the_share_is_the_gaps_inside_the_window_over_the_window():
    read = reader("serving.device_gap_share")
    rows = [gap(1.0, 0.5), gap(4.0, 1.5, before="prefill"),
            gap(9.5, 1.0),              # half of it lies past the close
            gap(12.0, 3.0)]             # outside the window: not in the share
    assert read(run_with(rows)) == pytest.approx(100 * (0.5 + 1.5 + 0.5) / 10)
    assert read(run_with(rows[3:])) == pytest.approx(0.0)
    # other spans are not gaps, however long
    rows.append(("serving.paged_step", 2.0, 5.0, {"id": 1}))
    assert read(run_with(rows, window=(0.0, 5.0))) == \
        pytest.approx(100 * (0.5 + 1.0) / 5)


def test_a_gap_is_read_by_the_program_that_closed_it():
    rows = [gap(1.0, 0.002, before="decode"),
            gap(2.0, 0.004, before="decode", after="prefill",
                behind="admit_scatter"),    # a step after an admission counts
            gap(3.0, 0.009, before="decode"),
            gap(4.0, 0.003, before="prefill"),
            gap(5.0, 0.005, before="prefill", after="prefill",
                behind="admit_scatter"),
            gap(6.0, 0.050, before="cow"), gap(7.0, 0.070, before="chunk")]
    run = run_with(rows)
    assert reader("serving.gap_before_step_ms_p50")(run) == pytest.approx(4.0)
    assert reader("serving.gap_before_admit_ms_p50")(run) == pytest.approx(4.0)
    only_admits = run_with(rows[3:5])
    assert reader("serving.gap_before_step_ms_p50")(only_admits) is None
    assert reader("serving.gap_before_admit_ms_p50")(only_admits) \
        == pytest.approx(4.0)


def test_unspanned_is_the_part_of_a_gap_no_program_span_covers():
    read = reader("serving.gap_unspanned_ms_p50")
    rows = [
        # half covered: a copy and a commit inside the gap, nested spans once
        gap(1.0, 0.004),
        ("serving.paged_step", 0.9, 0.102, {"id": 1}),
        ("serving.step.copy", 1.0, 0.001, {"id": 2, "parent": 1}),
        ("serving.step.commit", 1.001, 0.001, {"id": 3, "parent": 1}),
        # wholly covered by the span it lies in
        gap(2.0, 0.003),
        ("serving.paged_admit", 1.99, 0.02, {"id": 4}),
        # covered by nothing: the caller's
        gap(3.0, 0.006, before="prefill"),
        # a zero-length event covers nothing
        ("serving.retire", 3.001, 0.0, {"seq": 1}),
    ]
    unspanned = spec_mod.load_module(
        "layer_metrics", "serving.gap_unspanned_ms_p50").unspanned
    assert unspanned(run_with(rows)) == pytest.approx([0.002, 0.0, 0.006])
    assert read(run_with(rows)) == pytest.approx(2.0)
    # a span that straddles the gap's start and another its end
    rows = [gap(5.0, 0.010), ("a", 4.99, 0.012, {}), ("b", 5.008, 0.1, {})]
    assert read(run_with(rows)) == pytest.approx(6.0)


@pytest.mark.parametrize("part", STAGE_PARTS)
def test_a_stage_part_is_the_median_under_the_step(part):
    read = reader("serving.step_stage_%s_ms_p50" % part)
    rows, ids = [], iter(range(1, 100))
    for ms in (2.0, 4.0, 9.0):
        step, stage = next(ids), next(ids)
        rows.append(("serving.paged_step", 1.0, 1.0, {"id": step}))
        rows.append(("serving.step.stage", 1.0, 0.5,
                     {"id": stage, "parent": step}))
        for other in STAGE_PARTS:
            rows.append(("serving.step.stage." + other, 1.0,
                         ms / 1e3 if other == part else 0.5,
                         {"id": next(ids), "parent": stage}))
    # the same name with no step above it is not the step's time
    rows.append(("serving.step.stage." + part, 1.0, 0.0001, {"id": next(ids)}))
    assert read(run_with(rows)) == pytest.approx(4.0)


# ------------------------------------------------------------------ the entries
def test_the_seven_entries_are_declared_by_membership():
    spec = spec_mod.Spec()
    declared = {}
    for m in spec.doc["per_layer"]:
        assert m["name"] not in declared, m["name"]     # once
        declared[m["name"]] = m
    cells = {w["name"] for w in spec.doc["workloads"]}
    for name, (unit, moves, where) in ENTRIES.items():
        m = declared[name]
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) \
            == (unit, "lower", "program_span", "serving", moves), name
        assert set(where) <= set(m["workloads"]) <= cells, name
        moved = next(e for e in spec.doc["end_to_end"] if e["name"] == moves)
        assert set(m["workloads"]) <= set(moved["workloads"]), name
        assert os.path.isfile(os.path.join(
            spec.bench_dir, "layer_metrics", name + ".py")), name
        for cell in where:
            assert m in spec.metrics("per_layer", cell)


# --------------------------------------------------------------- the rehearsals
@pytest.mark.parametrize("cell", ["transformer-base.generate",
                                  "transformer-base.score"])
def test_a_traced_rehearsal_carries_every_new_metric_of_its_cell(cell, capsys):
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
    spec = spec_mod.Spec()
    declared = {m["name"]: m["unit"] for m in spec.metrics("per_layer", cell)}
    assert contract.problems(line, declared, True) == []
    want = {name for name, (_u, _m, where) in ENTRIES.items() if cell in where}
    assert len(want) == (7 if cell.endswith(".generate") else 3)
    assert want <= set(line["metrics"])
    value = lambda name: line["metrics"][name]["value"]
    assert all(value(name) >= 0 for name in want)
    assert 0 < value("serving.device_gap_share") <= 100
    assert value("serving.gap_before_admit_ms_p50") > 0
    assert value("serving.gap_unspanned_ms_p50") <= max(
        value(n) for n in want if n.startswith("serving.gap_before"))
    if cell.endswith(".generate"):
        # the three parts lie inside the span that stays
        parts = sum(value("serving.step_stage_%s_ms_p50" % p)
                    for p in STAGE_PARTS)
        assert 0 < parts <= 1.5 * value("serving.step_stage_ms_p50")
