"""The command itself: no chip, no result; and each driver rehearsed on the
CPU at the tiny sizes, its line held to the contract and never printed as a
result."""
import json
import os
import subprocess
import sys

import pytest

from harness import contract, main as harness_main, spec as spec_mod

RUN = os.path.join(spec_mod.BENCH_DIR, "run.py")


def test_without_a_tpu_the_command_exits_2_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", "resnet50.train", "--seed", "0",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "runs on the chip only" in proc.stderr


# (cell, --trace): both drivers, both kinds of line, the mesh path once
REHEARSALS = [("resnet50.train-dp4", 0), ("transformer-base.generate", 1)]


@pytest.mark.parametrize("cell,trace", REHEARSALS)
def test_rehearsal_runs_to_its_end_and_meets_the_contract(cell, trace,
                                                          capsys):
    try:
        rc = harness_main.main(["--workload", cell, "--seed", "3",
                                "--seconds", "0.5", "--trace", str(trace),
                                "--rehearse-cpu"])
    finally:
        from harness import program

        program.telemetry().set_mode(None)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    assert out[0].startswith("*** REHEARSAL on the CPU")
    assert out[-1] == "*** REHEARSAL passed -- no result line ***"
    # nothing on stdout parses as a result line
    for text in out:
        assert not text.startswith("{")
    line = json.loads(out[-2].partition("REHEARSAL (not a result): ")[2])
    spec = spec_mod.Spec()
    kind = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in spec.metrics(kind, cell)}
    assert contract.problems(line, declared, bool(trace)) == []
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["compiles"]["window"]["requests"] == 0
    assert line["device"]["platform"] == "cpu"
    if trace:
        assert "breakdown" in line and line["device"]["busy_s"] > 0
    else:
        assert set(line["metrics"]) == set(declared)


def test_a_broken_reference_is_reported_as_incorrect(capsys):
    """The third cell's rehearsal, with the reference's weights perturbed:
    the line still meets the contract and says ``correct: false``."""
    cell = "transformer-base.score"
    rc = harness_main.main(["--workload", cell, "--seconds", "0.2",
                            "--rehearse-cpu", "--break-reference"])
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-2].partition("REHEARSAL (not a result): ")[2])
    declared = {m["name"]: m["unit"]
                for m in spec_mod.Spec().metrics("end_to_end", cell)}
    assert rc == 0 and contract.problems(line, declared, False) == []
    assert line["correct"] is False and line["failed"] == 0
    assert any("FAIL" in c for c in line["checks"])
    assert line["notes"]["dispatches"] == 0   # admit alone served it
