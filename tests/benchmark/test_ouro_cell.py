"""The cell ``ouro-2.6b.generate`` rehearsed on the CPU at its tiny size: it
runs to its end and meets the contract untraced and traced and its program
reports the counters ``kernels.hbm_share.loop`` reads, a broken reference and
each of the reference's faults are reported, the configuration holds every
published number of the catalog's row with no cut, the driver's ``step_bytes``
and FLOP are the layer equations' arithmetic written out, the new metric gives
nothing where there is nothing to read, the traffic stays inside ``max_len``,
and the reference imports nothing of the program. The cell's place in
``BENCHMARK.json`` is held by MEMBERSHIP, never by position: the next cell
appended behind it breaks nothing here."""
import ast
import json
import os
from types import SimpleNamespace

import pytest

from harness import contract, main as harness_main, spec as spec_mod

CELL = "ouro-2.6b.generate"
CONFIG = "ouro-2.6b"
SOURCE = "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
DOTS3 = "dots3-note-prev.generate"
NOT_MINE = ("moe.load_max_over_mean", "moe.local_rows_share",
            "serving.admit_state_ms_p50", "kernels.hbm_share.dsa",
            "serving.sparse_selected_share")
OTHERS = ("transformer-base.generate", "olmoe-1b-7b.score",
          "granite-4.0-h-micro.generate", "kanana-2-30b-a3b.generate",
          "lfm2-24b-a2b.generate", "mimo-v2-flash.generate",
          "phi-4-mini-flash-reasoning.generate",
          "nemotron-3-nano-30b-a3b.generate", DOTS3, "resnet50.train")
LAYER = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048    # 51,388,416


def _rehearse(capsys, *flags):
    try:
        rc = harness_main.main(["--workload", CELL, "--seconds", "0.5",
                                "--rehearse-cpu", *flags])
    finally:
        from harness import program

        program.telemetry().set_mode(None)
    out = capsys.readouterr().out.strip().splitlines()
    assert out[0].startswith("*** REHEARSAL on the CPU")
    for text in out:    # nothing on stdout parses as a result line
        assert not text.startswith("{")
    return rc, out, json.loads(
        out[-2].partition("REHEARSAL (not a result): ")[2])


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_to_its_end_and_meets_the_contract(trace, capsys,
                                                              monkeypatch):
    seen = {}
    if trace:
        # what the program counted inside the window, as the reader of
        # ``kernels.hbm_share.loop`` is handed it (a rehearsal has no peaks,
        # so the reader itself gives nothing here)
        spec = spec_mod.Spec()
        reader = spec.module("layer_metrics", "kernels.hbm_share.loop")
        module = spec_mod.Spec.module

        def spy(self, kind, name):
            if name != "kernels.hbm_share.loop":
                return module(self, kind, name)
            return SimpleNamespace(read=lambda run: seen.update(
                run.counters_window) or reader.read(run))

        monkeypatch.setattr(spec_mod.Spec, "module", spy)
    rc, out, line = _rehearse(capsys, "--seed", "3000000031",
                              "--trace", str(trace))
    assert rc == 0
    assert out[-1] == "*** REHEARSAL passed -- no result line ***"
    spec = spec_mod.Spec()
    kind = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in spec.metrics(kind, CELL)}
    assert contract.problems(line, declared, bool(trace)) == []
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["notes"]["dispatches"] > 0
    assert line["compiles"]["window"]["requests"] == 0
    assert "admit and 5 decode steps at prompt lengths [4, 8, 16]" \
        in line["checks"][0]
    assert "the worst of 18 rows" in line["checks"][0]
    assert line["checks"][1].startswith("the last layer's keys of each "
                                        "sampled lane as the pool keeps them")
    for check in line["checks"][:2]:
        assert check.endswith("ok")
    assert "every lane retired and every page returned: ok" in line["checks"]
    assert [len(r) for r in line["notes"]["check_rows_sorted"]] == [6, 6, 6]
    # three prompts, the tiny twin's two passes each
    assert [len(r) for r in line["notes"]["check_keys_by_pass"]] == [2, 2, 2]
    if not trace:
        assert set(line["metrics"]) == set(declared)
        return
    got = line["metrics"]
    assert "kernels.hbm_share.loop" in declared
    for absent in ("kernels.hbm_share.dsa", "kernels.hbm_share.yoco",
                   "moe.local_rows_share", "serving.admit_state_ms_p50"):
        assert absent not in declared
    for phase in ("stage", "prefill", "logits", "scatter"):
        assert got["serving.admit_%s_ms_p50" % phase]["value"] > 0
    for name in ("serving.admit_ms_p50", "serving.step_ms_p50",
                 "serving.step_stage_ms_p50", "serving.step_read_ms_p50",
                 "serving.step_commit_ms_p50", "serving.itl_ms_p95",
                 "serving.device_gap_share"):
        assert got[name]["value"] > 0
    assert got["process.compiles_in_window.serving"]["value"] == 0
    assert got["graph.retraces_in_window.serving"]["value"] == 0
    # the tiny twin runs 2 passes: a step and an admission count 2 each, and
    # with the published threshold every token is headed by the last pass
    steps, admits = seen["serving.paged_steps"], seen["serving.paged_admits"]
    assert steps > 0 and admits > 0
    assert seen["serving.loop.passes"] == 2 * (steps + admits)
    assert seen["serving.loop.exit_tokens"] \
        == seen["serving.decode_tokens"] + admits
    assert seen["serving.loop.exit_pass_sum"] \
        == 2 * seen["serving.loop.exit_tokens"]
    assert seen["serving.step_context_tokens"] > seen["serving.decode_tokens"]
    # 3 layers x 2 passes x K and V, a stepped lane
    assert seen["serving.step_slot_writes"] \
        == 12 * seen["serving.decode_tokens"]


@pytest.mark.parametrize("flags,first_pass", [
    (("--break-reference",), "FAIL"),
    (("--set", 'config.check.fault="previous_pass_keys"'), "ok"),
    (("--set", 'config.check.fault="no_norm_between_passes"'), "ok"),
    (("--set", 'config.check.fault="float8_weights"'), "FAIL"),
], ids=["break_reference", "previous_pass_keys", "no_norm_between_passes",
        "float8_weights"])
def test_a_reading_that_must_fail_is_reported_as_incorrect(flags, first_pass,
                                                           capsys):
    """``layer0_qkv_weight`` x 1.25 (all passes feel it; the output
    projection's scale would cancel in the norm behind it), a pass reading
    the pass before's keys, the final norm left out between passes, and the
    weights a storage precision down: each moves the logits far past the
    limit. The two faults of the LOOP leave the first pass as it was, and the
    first pass's keys say so: one of the cell's limits fails, not each."""
    rc, _, line = _rehearse(capsys, *flags)
    declared = {m["name"]: m["unit"]
                for m in spec_mod.Spec().metrics("end_to_end", CELL)}
    assert rc == 0 and contract.problems(line, declared, False) == []
    assert line["correct"] is False and line["failed"] == 0
    assert line["checks"][0].endswith("FAIL")
    assert line["checks"][1].endswith(first_pass)
    worst = max(max(rows) for rows in line["notes"]["check_rows_sorted"])
    assert worst > 100 * 1e-4
    later = min(r[1] for r in line["notes"]["check_keys_by_pass"])
    assert later > 50 * 1e-4    # the second pass's keys see every fault


def test_the_configuration_holds_the_published_numbers_and_no_cut():
    """Every key of the catalog's ``config`` under the same key with the same
    value, ``reduced`` empty in the file and in ``BENCHMARK.json``, every
    width as published, the deployment and its bytes stated, each assumption
    written down, the decoder's sizes the same numbers."""
    spec = spec_mod.Spec()
    cfg = spec.config(spec.cell(CELL))
    published = dict(
        head_dim=128, hidden_act="silu", hidden_size=2048,
        intermediate_size=5632, layer_types=["full_attention"] * 48,
        max_position_embeddings=65536, max_window_layers=48,
        model_type="ouro", num_attention_heads=16, num_hidden_layers=48,
        num_key_value_heads=16, rms_norm_eps=1e-6, rope_scaling=None,
        rope_theta=1000000, sliding_window=None, tie_word_embeddings=False,
        total_ut_steps=4, early_exit_threshold=1, use_sliding_window=False,
        vocab_size=49152)
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == []
    (entry,) = [c for c in spec.doc["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == [] and entry["source"] == SOURCE
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert cfg["source"].startswith(SOURCE)
    assert entry["file"] == "benchmark/configs/ouro-2.6b.json"
    assert cfg["model"] == dict(
        arch="ouro", vocab_size=49152, num_layers=48, num_heads=16,
        head_dim=128, model_dim=2048, ffn_dim=5632, total_ut_steps=4,
        early_exit_threshold=1.0, rope_theta=1000000.0, rms_eps=1e-6)
    assert cfg["dtype"] == "bfloat16"
    assert cfg["serving"] == dict(max_len=320, prefill_len=128, page_size=16,
                                  lanes=16)
    for said in ("2,667,974,657", "5.34 GB", "1,572,864 B", "8.05 GB",
                 "13.4 GB", "donated"):
        assert said in cfg["deployment"], said
    assert set(cfg["assumed"]) >= {"norms", "loop", "exit_gate", "bias",
                                   "cache", "attention", "dtype", "layout",
                                   "serving", "init"}
    assert "input_layernorm_2" in cfg["assumed"]["norms"]
    assert "NOT built" in cfg["assumed"]["cache"]
    assert cfg["reference"] == "ouro_decoder"
    assert cfg["check"]["fault"] is None and cfg["check"]["why"]
    assert 0 < cfg["check"]["first_pass_keys_rel_l2"] \
        < cfg["check"]["logits_rel_l2"] / 5
    tiny = spec.config(spec.cell(CELL), tiny=True)
    assert tiny["model"]["arch"] == "ouro"
    assert (tiny["model"]["num_layers"], tiny["model"]["total_ut_steps"],
            tiny["model"]["model_dim"], tiny["model"]["num_heads"],
            tiny["model"]["head_dim"], tiny["model"]["ffn_dim"],
            tiny["model"]["vocab_size"]) == (3, 2, 64, 4, 16, 128, 256)


def test_param_shapes_counts_a_layer_once_and_sums_to_the_published_count():
    from mxnet_tpu.models.transformer import param_shapes

    spec = spec_mod.Spec()
    cfg = spec.config(spec.cell(CELL))
    shapes = param_shapes(**cfg["model"])
    count = lambda names: sum(
        int(__import__("math").prod(shapes[n])) for n in names)
    assert count(n for n in shapes if n.startswith("layer7_")) == LAYER
    assert len([n for n in shapes if n.startswith("layer")]) == 48 * 8
    assert count(shapes) == 48 * LAYER + 2 * 49152 * 2048 + 2048 + 2049 \
        == 2_667_974_657


def test_the_traffic_is_the_issues_and_stays_inside_a_lane():
    """Prompts log-normal, median 64, sigma 0.5, on the grid 32 to 128 in the
    128 bucket; new tokens log-normal, median 128, sigma 0.4, clipped to
    64-192; the largest prompt and the longest answer fit a lane's
    ``max_len``; 16 callers, one a lane."""
    spec = spec_mod.Spec()
    cell = spec.cell(CELL)
    assert cell == {"name": CELL, "config": CONFIG, "chips": 1,
                    "traffic": "generate-128-16-closed", "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "4 x over 4.9 GB" in cell["why"]
    traffic, serving = spec.traffic(cell), spec.config(cell)["serving"]
    assert traffic["driver"] == "paged_closed_loop_ouro"
    assert traffic["callers"] == "lanes" and serving["lanes"] == 16
    assert traffic["fields"] == {
        "prompt_len": {"dist": "lognormal", "median": 64, "sigma": 0.5,
                       "grid": [32, 48, 64, 96, 128]},
        "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.4,
                       "clip": [64, 192]}}
    assert traffic["ramp_dispatches"] == 8
    assert traffic["check_decode_steps"] == 16
    assert traffic["check_prompt_lens"] == [32, 64, 128]
    for t, s in ((traffic, serving),
                 (spec.traffic(cell, tiny=True),
                  spec.config(cell, tiny=True)["serving"])):
        longest = max(t["fields"]["prompt_len"]["grid"])
        assert longest <= s["prefill_len"]
        assert longest + max(t["fields"]["output_len"]["clip"]) \
            <= s["max_len"]
        assert max(t["check_prompt_lens"]) + t["check_decode_steps"] \
            <= s["max_len"]


def test_the_cell_is_a_member_of_the_lists_it_reports_and_of_no_other():
    """The cell reports what ``dots3-note-prev.generate`` reports but the
    experts' two, the per-lane rows' one and that cell's own two, plus its
    own one. MEMBERSHIP only: no assertion here reads a position, so a later
    cell may follow this one."""
    doc = spec_mod.Spec().doc
    assert [c["name"] for c in doc["workloads"]].count(CELL) == 1
    assert [c["name"] for c in doc["configs"]].count(CONFIG) == 1
    assert sum(c["chips"] == 4 for c in doc["workloads"]) == 1
    metrics = {m["name"]: m for m in doc["end_to_end"] + doc["per_layer"]}
    assert metrics["kernels.hbm_share.loop"] == {
        "name": "kernels.hbm_share.loop", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "gen_tokens_per_s", "workloads": [CELL]}
    for name, m in metrics.items():
        lists = m.get("workloads", [])
        assert lists.count(CELL) <= 1
        if name == "kernels.hbm_share.loop":
            continue
        if name in NOT_MINE:
            assert CELL not in lists, name
        else:
            assert (CELL in lists) == (DOTS3 in lists), name
    spec = spec_mod.Spec()
    reported = spec.metrics("per_layer", CELL) + spec.metrics("end_to_end",
                                                              CELL)
    assert {"setup_s", "gen_tokens_per_s", "ttft_ms_p50",
            "kernels.flops_share.serving", "device.peak_hbm_gb",
            "serving.device_gap_share"} <= {m["name"] for m in reported}
    for m in reported:
        kind = "end_to_end" if m in doc["end_to_end"] else "layer_metrics"
        assert os.path.isfile(os.path.join(
            spec.bench_dir, kind, m["name"] + ".py")), m["name"]


def _driver_and_config():
    spec = spec_mod.Spec()
    return spec, spec.module("drivers", "paged_closed_loop_ouro"), \
        spec.config(spec.cell(CELL))


def test_the_step_byte_count_is_the_layer_equations():
    """At the published widths, the sums written out: a step's weights are
    every layer FOUR times, the head, the final norm and the gate once; a
    stepped lane reads an embedding row and writes 192 key rows and 192 value
    rows of 4,096 B; a context token has as many read."""
    _, driver, cfg = _driver_and_config()
    model = cfg["model"]
    assert driver.layer_parameters(model) == LAYER
    assert 48 * LAYER * 2 == 4_933_287_936
    a_step = 4 * 4_933_287_936 + 201_326_592 + 4_096 + 4_098
    assert a_step == 19_934_486_530
    assert driver.step_bytes(model, "bfloat16", 1, 0, 0) == a_step
    row = 192 * 2 * 4_096
    assert row == 1_572_864
    assert driver.step_bytes(model, "bfloat16", 0, 1, 0) == 4_096 + row
    assert driver.step_bytes(model, "bfloat16", 0, 0, 1) == row
    assert driver.step_bytes(model, "bfloat16", 7, 7 * 16, 7 * 16 * 200) \
        == 7 * a_step + 7 * 16 * (4_096 + row) + 7 * 16 * 200 * row
    # a pool in float32 doubles every term
    assert driver.step_bytes(model, "float32", 1, 1, 1) \
        == 2 * driver.step_bytes(model, "bfloat16", 1, 1, 1)


def test_the_flop_count_is_the_layer_equations():
    """Four passes of every matrix and of attention's scores and apply; the
    head once, the gate after every pass but the last."""
    _, driver, cfg = _driver_and_config()
    model = cfg["model"]
    matrices = 2 * (4 * 2048 * 2048 + 3 * 2048 * 5632)
    assert driver.model_flops(model, 1, 0, 0) == 4 * 48 * matrices
    assert driver.model_flops(model, 0, 1, 0) == 4 * 48 * 4 * 2048
    assert driver.model_flops(model, 0, 0, 1) == 2 * 2048 * (49152 + 3)
    assert driver.model_flops(model, 16, 3000, 16) == pytest.approx(
        16 * 4 * 48 * matrices + 3000 * 4 * 48 * 4 * 2048
        + 16 * 2 * 2048 * 49155)
    # a step of 16 lanes: some 0.32 T FLOP over 20 GB and more
    assert 3.1e11 < driver.model_flops(model, 16, 3000, 16) < 3.3e11


def test_the_new_metric_needs_the_programs_counters_and_the_architecture():
    """Nothing to read, and no error, from a program without the counters
    (the parent commit) or a configuration of another architecture."""
    spec, driver, cfg = _driver_and_config()
    share = spec.module("layer_metrics", "kernels.hbm_share.loop")
    full = {"serving.paged_steps": 100, "serving.decode_tokens": 1600,
            "serving.step_context_tokens": 1600 * 190,
            "serving.loop.passes": 400}
    run = lambda **kw: SimpleNamespace(**{
        "trace_summary": {"busy_s": 4.0}, "counters_window": full,
        "peaks": {"hbm_bytes_per_s": 819e9}, "config": cfg, **kw})
    got = share.read(run())
    assert got == pytest.approx(100.0 * driver.step_bytes(
        cfg["model"], "bfloat16", 100, 1600, 1600 * 190) / (4.0 * 819e9))
    assert 60 < got < 100
    for gone in full:
        if gone == "serving.decode_tokens":
            continue
        old = {k: v for k, v in full.items() if k != gone}
        assert share.read(run(counters_window=old)) is None, gone
    assert share.read(run(counters_window=None)) is None
    assert share.read(run(counters_window={})) is None
    assert share.read(run(peaks=None)) is None
    assert share.read(run(trace_summary=None)) is None
    for other in OTHERS:
        config = spec.config(spec.cell(other))
        assert share.read(run(config=config)) is None, other


def test_the_reference_imports_nothing_of_the_program():
    """``reference/ouro_decoder.py`` is plain ``jax.numpy``: its only imports
    are jax's and the standard library's, it sets the highest matmul
    precision, writes the loop as two ``for``s, and its notes name each
    point it could not check."""
    spec = spec_mod.Spec()
    path = os.path.join(spec.bench_dir, "reference", "ouro_decoder.py")
    source = open(path).read()
    tree = ast.parse(source)
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported == {"jax", "functools", "math"}
    assert "mxnet_tpu" not in source.replace("nothing from\n``mxnet_tpu``", "")
    assert 'default_matmul_precision("highest")' in source
    assert "for u in range(n_passes):\n        for i in range(" in source
    assert "pallas" not in source and "fori_loop" not in source \
        and "scan(" not in source
    doc = ast.get_docstring(tree)
    for said in ("sandwich", "final norm INSIDE the loop", "gate's bias",
                 "a cache of its own for every pass", "previous_pass_keys",
                 "no_norm_between_passes"):
        assert said in doc, said
