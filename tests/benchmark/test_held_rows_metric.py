"""``moe.admit_held_rows_share`` (PR 59): the admission-side sibling of
``moe.local_rows_share``, declared once for the three cells whose admissions
move their held rows alone, with a reader of its name that needs the
program's two counters and gives nothing (never 0) without them."""
import os
from types import SimpleNamespace

import pytest

from harness import spec as spec_mod

NAME = "moe.admit_held_rows_share"
CELLS = ["mimo-v2-flash.generate", "dots3-note-prev.generate",
         "laguna-s-2.1.generate"]


def test_the_metric_is_declared_once_for_the_three_cells():
    """MEMBERSHIP only: no assertion reads a position, so a later metric may
    follow this one."""
    spec = spec_mod.Spec()
    found = [m for m in spec.doc["per_layer"] if m["name"] == NAME]
    assert found == [{
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "serving",
        "moves": "ttft_ms_p50", "workloads": CELLS}]
    sibling = next(m for m in spec.doc["per_layer"]
                   if m["name"] == "moe.local_rows_share")
    # the sibling's cells but the one that holds half and keeps every row
    assert set(sibling["workloads"]) - set(CELLS) == {
        "nemotron-3-nano-30b-a3b.generate"}
    for cell in CELLS:
        assert NAME in {m["name"] for m in spec.metrics("per_layer", cell)}
        assert "ttft_ms_p50" in {m["name"] for m in spec.metrics(
            "end_to_end", cell)}
    assert os.path.isfile(os.path.join(spec.bench_dir, "layer_metrics",
                                       NAME + ".py"))


@pytest.mark.parametrize("held,every,want", [
    (6 * 1024, 6 * 16384, 6.25),          # 16 of 256, even routing
    (5 * 20480, 5 * 81920, 25.0),         # 64 of 256
    (0, 5 * 65536, 0.0),                  # nobody chose a held expert
    (5 * 65536, 5 * 65536, 100.0)])
def test_the_reader_is_the_quotient_of_the_programs_two_counters(held, every,
                                                                 want):
    read = spec_mod.Spec().module("layer_metrics", NAME).read
    run = lambda c: SimpleNamespace(counters_window=c)
    assert read(run({"serving.moe.admit_local_assignments": held,
                     "serving.moe.assignments": every})) \
        == pytest.approx(want)


@pytest.mark.parametrize("counters", [
    None, {}, {"serving.moe.assignments": 98304},       # the parent commit
    {"serving.moe.admit_local_assignments": 6144},
    {"serving.moe.admit_local_assignments": 0,
     "serving.moe.assignments": 0}])                    # no admission
def test_the_reader_gives_nothing_without_its_counters(counters):
    read = spec_mod.Spec().module("layer_metrics", NAME).read
    assert read(SimpleNamespace(counters_window=counters)) is None
