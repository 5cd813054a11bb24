"""BENCHMARK.json against the contract it is written to, and the promise
that a later PR adds cells by adding files and entries only."""
import json
import os
import re
import shutil

import pytest

from harness import contract, main as harness_main, spec as spec_mod

ROOT = spec_mod.ROOT
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_benchmark_json_meets_the_contract():
    spec = spec_mod.Spec()
    doc = spec.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= doc["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in doc[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for c in doc["configs"]:
        assert any(c["file"].startswith(p + "/") for p in doc["paths"])
        cfg = spec_mod.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["reduced"] == c["reduced"] and "assumed" in cfg
        assert any(w["config"] == c["name"] for w in doc["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [w for w in doc["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(doc["workloads"]) // 4)
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in doc["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for w in doc["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        spec.config(w), spec.traffic(w)             # the files are there
        spec.config(w, tiny=True), spec.traffic(w, tiny=True)
        mine = {m["name"] for m in spec.metrics("end_to_end", w["name"])}
        assert "setup_s" in mine and len(mine) >= 2
        layer = spec.metrics("per_layer", w["name"])
        assert layer
        for m in layer:
            assert m["source"] in SOURCES
            assert m["moves"] in mine, (w["name"], m["name"])
    for kind, folder in (("end_to_end", "end_to_end"),
                         ("per_layer", "layer_metrics")):
        for m in doc[kind]:
            assert callable(spec.module(folder, m["name"]).read)
    for w in doc["workloads"]:
        spec.module("drivers", spec.traffic(w)["driver"])
        spec.module("reference", spec.config(w)["reference"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_no_file_of_the_harness_names_a_model_a_cell_or_a_metric():
    doc = spec_mod.Spec().doc
    words = {x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in doc[k]}
    words |= {"resnet", "transformer"}
    words -= {"setup_s"}  # named by the contract itself, read from a file
    files = [os.path.join(spec_mod.BENCH_DIR, "run.py")]
    hdir = os.path.join(spec_mod.BENCH_DIR, "harness")
    files += [os.path.join(hdir, f) for f in os.listdir(hdir)
              if f.endswith(".py")]
    for path in files:
        text = open(path).read()
        for word in words:
            assert word not in text, (path, word)


def test_peaks_are_keyed_by_the_exact_device_kind():
    row = spec_mod.peaks("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9 and row["hbm_bytes"] == 16e9
    assert row["interconnect_bits_per_s"] == 1600e9 and row["source"]
    for kind in ("TPU v5", "TPU v5 lite ", "TPU v5p", "cpu"):
        with pytest.raises(spec_mod.SpecError):
            spec_mod.peaks(kind)


NOOP_DRIVER = '''
"""A driver that was not there before: it counts loop turns."""
import time
import jax.numpy as jnp

def run(run):
    x = jnp.ones((8, 8)) * run.config["scale"]
    t0 = run.open_window()
    turns = 0
    while time.perf_counter() < t0 + run.seconds:
        with run.annotate("bench.step"):
            (x @ x).block_until_ready()
        turns += 1
    run.close_window()
    return {"correct": True, "checks": [], "attempted": turns, "failed": 0,
            "turns": turns, "elapsed_s": time.perf_counter() - t0,
            "pause": run.traffic["pause"]}
'''


def test_new_cell_is_files_and_entries_only(tmp_path, capsys):
    """A configuration, a traffic mix, a driver, an end-to-end metric, a
    layer metric and a cell, added to a temporary copy as new files and new
    entries, run through the unchanged harness."""
    root = str(tmp_path)
    shutil.copytree(spec_mod.BENCH_DIR, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    doc = spec_mod.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench = os.path.join(root, "benchmark")
    for sub in ("configs", "configs/_tiny"):
        with open(os.path.join(bench, sub, "toy.json"), "w") as f:
            json.dump({"scale": 0.5, "reduced": [], "assumed": {}}, f)
    for sub in ("traffic", "traffic/_tiny"):
        with open(os.path.join(bench, sub, "spin.json"), "w") as f:
            json.dump({"driver": "spin", "pause": 0}, f)
    with open(os.path.join(bench, "drivers", "spin.py"), "w") as f:
        f.write(NOOP_DRIVER)
    with open(os.path.join(bench, "end_to_end", "turns_per_s.py"), "w") as f:
        f.write("def read(run):\n"
                "    return run.obs['turns'] / run.obs['elapsed_s']\n")
    with open(os.path.join(bench, "layer_metrics", "spin.turns.py"),
              "w") as f:
        f.write("def read(run):\n    return run.obs['turns']\n")
    with open(os.path.join(bench, "layer_metrics", "spin.absent.py"),
              "w") as f:
        f.write("def read(run):\n    return None  # nothing to read\n")
    doc["configs"].append({"name": "toy", "source": "none", "reduced": [],
                           "file": "benchmark/configs/toy.json", "why": "x"})
    doc["workloads"].append({"name": "toy.spin", "config": "toy",
                             "traffic": "spin", "chips": 1, "why": "x"})
    doc["end_to_end"].append({"name": "turns_per_s", "unit": "1/s",
                              "better": "higher", "bound": 0.05,
                              "source": "host_clock",
                              "workloads": ["toy.spin"]})
    for name in ("spin.turns", "spin.absent"):
        doc["per_layer"].append({"name": name, "unit": "count",
                                 "better": "higher", "source": "host_clock",
                                 "layer": "spin", "moves": "turns_per_s",
                                 "workloads": ["toy.spin"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)

    for trace in (0, 1):
        rc = harness_main.main(["--workload", "toy.spin", "--seconds", "0.2",
                                "--trace", str(trace), "--rehearse-cpu"],
                               root=root)
        out = capsys.readouterr().out.strip().splitlines()
        assert rc == 0 and out[-1] == \
            "*** REHEARSAL passed -- no result line ***"
        line = json.loads(out[-2].partition("REHEARSAL (not a result): ")[2])
        if trace:
            assert set(line["metrics"]) == {"process.compile_s",
                                            "device.peak_hbm_gb",
                                            "spin.turns"}
            assert line["metrics"]["spin.turns"]["value"] > 0
            assert line["device"]["busy_s"] > 0
            assert line["breakdown"]["idle_gaps"][0][0] == "bench.step"
        else:
            assert set(line["metrics"]) == {"setup_s", "turns_per_s"}
    from harness import program

    program.telemetry().set_mode(None)


def test_contract_check_refuses_a_line_the_driver_could_not_read():
    good = {"correct": True, "attempted": 3, "failed": 0,
            "metrics": {"setup_s": {"value": 2.0, "unit": "s"}},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 5}}
    declared = {"setup_s": "s"}
    assert contract.problems(good, declared, trace=False) == []
    assert contract.problems(dict(good, failed=4), declared, False)
    assert contract.problems({k: v for k, v in good.items()
                              if k != "device"}, declared, False)
    zero = dict(good, metrics={"setup_s": {"value": 0.0, "unit": "s"}})
    assert contract.problems(zero, declared, False)
    other = dict(good, metrics={"x": {"value": 1.0, "unit": "s"}})
    assert contract.problems(other, declared, False)
    assert contract.problems(good, declared, trace=True)  # no busy_s
    traced = dict(good, device=dict(good["device"], busy_s=0.5, window_s=1.0),
                  breakdown={"device_ops": [["fusion.1", 0.4]],
                             "idle_gaps": [["bench.step", 0.5]]})
    assert contract.problems(traced, declared, trace=True) == []
    idle = dict(traced, device=dict(traced["device"], busy_s=0.0))
    assert contract.problems(idle, declared, trace=True)
