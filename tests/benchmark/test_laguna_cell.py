"""The cell ``laguna-s-2.1.generate`` rehearsed on the CPU at its tiny size: it
runs to its end and meets the contract untraced and traced and its program
reports the counters its two metrics read, a broken reference is reported, the
configuration holds every published number of the catalog's row and exactly
the three cuts, ``param_shapes`` sums to the held and to the whole model, the
driver's ``sizes``, ``step_bytes`` and FLOP are the layer equations'
arithmetic written out, each new metric gives nothing where there is nothing
to read, the traffic stays inside ``max_len``, and the reference imports
nothing of the program. The cell's place in ``BENCHMARK.json`` is held by
MEMBERSHIP, never by position: the next cell appended behind it breaks
nothing here."""
import ast
import json
import math
import os
from types import SimpleNamespace

import pytest

from harness import contract, main as harness_main, spec as spec_mod

CELL = "laguna-s-2.1.generate"
CONFIG = "laguna-s-2.1"
SOURCE = "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json"
MIMO = "mimo-v2-flash.generate"
MINE = ("kernels.hbm_share.swa_heads", "serving.admit_window_live_share")
OTHERS = ("transformer-base.generate", "olmoe-1b-7b.score",
          "granite-4.0-h-micro.generate", "kanana-2-30b-a3b.generate",
          "lfm2-24b-a2b.generate", MIMO,
          "phi-4-mini-flash-reasoning.generate",
          "nemotron-3-nano-30b-a3b.generate", "dots3-note-prev.generate",
          "ouro-2.6b.generate", "resnet50.train")
FULL, WINDOW = "full_attention", "sliding_attention"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


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
        # ``kernels.hbm_share.swa_heads`` is handed it (a rehearsal has no
        # peaks, so the reader itself gives nothing here)
        spec = spec_mod.Spec()
        reader = spec.module("layer_metrics", MINE[0])
        module = spec_mod.Spec.module

        def spy(self, kind, name):
            if name != MINE[0]:
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
    assert "admit and 12 decode steps at prompt lengths [4, 12, 16]" \
        in line["checks"][0]
    assert "the fifth smallest of all 39 rows" in line["checks"][0]
    assert line["checks"][1].startswith("the first window layer's key ring")
    for check in line["checks"][:2]:
        assert check.endswith("ok")
    assert "every lane retired and every page returned: ok" in line["checks"]
    assert [len(r) for r in line["notes"]["check_rows_sorted"]] == [13] * 3
    if not trace:
        assert set(line["metrics"]) == set(declared)
        return
    got = line["metrics"]
    assert set(MINE) <= set(declared)
    for absent in ("kernels.hbm_share.swa", "kernels.hbm_share.dsa",
                   "serving.step_wait_ms_p50", "serving.admit_wait_ms_p50"):
        assert absent not in declared
    for phase in ("stage", "prefill", "logits", "scatter", "state"):
        assert got["serving.admit_%s_ms_p50" % phase]["value"] > 0
    for name in ("serving.admit_ms_p50", "serving.step_ms_p50",
                 "serving.step_stage_ms_p50", "serving.step_read_ms_p50",
                 "serving.step_commit_ms_p50", "serving.itl_ms_p95",
                 "serving.device_gap_share", "moe.load_max_over_mean"):
        assert got[name]["value"] > 0
    assert got["process.compiles_in_window.serving"]["value"] == 0
    assert got["graph.retraces_in_window.serving"]["value"] == 0
    # 8 of 32 experts held: 25% under even routing, as the cell's 64 of 256
    assert 5 < got["moe.local_rows_share"]["value"] < 60
    # the tiny bucket of 16 is two windows of 8: a band of 16 x 16 pairs an
    # admission whatever the prompt; prompts of 4 to 16 attend 10 to 100
    admits = seen["serving.paged_admits"]
    assert seen["serving.admit_window_pairs_scored"] == admits * 16 * 2 * 8
    assert 10 * admits <= seen["serving.admit_window_pairs_live"] \
        <= 100 * admits
    assert got[MINE[1]]["value"] == pytest.approx(
        100.0 * seen["serving.admit_window_pairs_live"]
        / seen["serving.admit_window_pairs_scored"])
    assert 3 < got[MINE[1]]["value"] < 40
    assert seen["serving.step_context_tokens"] > seen["serving.decode_tokens"]
    # a ring of 8 slots: at most 8 live a stepped lane
    assert 0 < seen["serving.step_window_slots"] \
        <= 8 * seen["serving.decode_tokens"]
    assert seen["serving.moe.step_experts_touched"] > 0
    assert line["notes"]["held_experts_touched_a_step_and_layer"] > 0


def test_a_broken_reference_is_reported_as_incorrect(capsys):
    """``layer0_mlp_out_weight`` x 1.25, the dense layer's output (mimo's
    ``layer0_qkv_weight`` would reach the values of one full layer alone
    here: the q/k norm takes the scale off q and k): the logits' holds fail,
    and so does the first window layer's key ring, whose layer reads the
    stream behind the dense layer."""
    rc, _, line = _rehearse(capsys, "--break-reference")
    declared = {m["name"]: m["unit"]
                for m in spec_mod.Spec().metrics("end_to_end", CELL)}
    assert rc == 0 and contract.problems(line, declared, False) == []
    assert line["correct"] is False and line["failed"] == 0
    assert line["checks"][0].endswith("FAIL")
    assert line["checks"][1].endswith("FAIL")
    worst = max(max(rows) for rows in line["notes"]["check_rows_sorted"])
    assert worst > 100 * 1e-4
    spec = spec_mod.Spec()
    driver = spec.module("drivers", "paged_closed_loop_laguna")
    assert driver.BROKEN == driver._mimo.BROKEN == "layer0_mlp_out_weight"
    # mimo's own copy of its driver keeps its own
    assert spec.module("drivers", "paged_closed_loop_mimo").BROKEN \
        == "layer0_qkv_weight"


def test_the_configuration_holds_the_published_numbers_and_three_cuts():
    """Every key of the catalog's ``config`` under the same key with the same
    value but the three of ``reduced``, those with their published values
    beside them; every width as published; the deployment stated; each
    assumption the issue names written down; the decoder's sizes the same
    numbers."""
    spec = spec_mod.Spec()
    cfg = spec.config(spec.cell(CELL))
    (row,) = [r for r in map(json.loads, open(CATALOG))
              if r["name"] == "Laguna-S-2.1"] if os.path.isfile(CATALOG) \
        else [None]
    cuts = {"num_hidden_layers": (48, 6), "num_experts": (256, 64),
            "vocab_size": (100352, 25088)}
    if row is not None:
        assert row["source_url"] == SOURCE
        for key, value in row["config"].items():
            assert cfg[key] == (cuts[key][1] if key in cuts else value), key
    assert cfg["reduced"] == list(cuts)
    for key, (published, run) in cuts.items():
        assert cfg["published"][key] == published and cfg[key] == run
    assert "117,561,977,600" in cfg["published"]["parameters"]
    for key, value in dict(
            hidden_size=3072, intermediate_size=12288, head_dim=128,
            num_attention_heads=48, num_key_value_heads=8,
            moe_intermediate_size=1024, num_experts_per_tok=10,
            shared_expert_intermediate_size=1024, sliding_window=512,
            moe_routed_scaling_factor=2.5, rms_norm_eps=1e-6).items():
        assert cfg[key] == value, key
    assert cfg["num_attention_heads_per_layer"][:6] == [48, 72, 72, 72, 48,
                                                        72]
    assert cfg["rope_parameters"]["full_attention"] == dict(
        rope_theta=500000, rope_type="yarn", factor=128,
        original_max_position_embeddings=8192, beta_slow=1, beta_fast=32,
        attention_factor=1.4852030263919618, partial_rotary_factor=0.5)
    (entry,) = [c for c in spec.doc["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == list(cuts) and entry["source"] == SOURCE
    assert len(entry["why"]) <= 200
    assert cfg["source"].startswith(SOURCE) and len(cfg["source"]) <= 200
    assert entry["file"] == "benchmark/configs/laguna-s-2.1.json"
    assert cfg["model"] == dict(
        arch="laguna", vocab_size=25088, num_layers=6, num_heads=48,
        swa_num_heads=72, num_kv_heads=8, head_dim=128, model_dim=3072,
        ffn_dim=12288, moe_ffn_dim=1024, num_experts=256,
        num_local_experts=64, local_expert_offset=0, num_experts_per_tok=10,
        num_shared_experts=1, first_dense_layers=1,
        layer_types=[FULL, WINDOW, WINDOW, WINDOW, FULL, WINDOW],
        sliding_window=512, rotary_dim=64, rope_theta=500000.0,
        swa_rope_theta=10000.0, yarn_factor=128.0,
        yarn_original_max_position=8192, yarn_beta_fast=32.0,
        yarn_beta_slow=1.0, attention_factor=1.4852030263919618,
        rms_eps=1e-6, routed_scaling_factor=2.5, norm_topk_prob=True)
    assert cfg["dtype"] == "bfloat16"
    assert cfg["serving"] == dict(max_len=9216, prefill_len=8192,
                                  page_size=16, lanes=32)
    for said in ("v5e-32", "four chips share each layer", "eight pipeline",
                 "3,679,364,864", "7.36 GB", "2.42 GB", "0.27 GB",
                 "1.25 rows", "6 of 48 layers"):
        assert said in cfg["deployment"], said
    assert set(cfg["assumed"]) >= {
        "gate", "qk_norm", "router", "shared_expert", "window", "rotation",
        "yarn", "cache", "dtype", "serving", "init", "layout"}
    assert "softplus" in cfg["assumed"]["gate"]
    assert "truncate" in cfg["assumed"]["yarn"]
    assert "N(0, 0.2)" in cfg["assumed"]["router"]
    assert cfg["reference"] == "laguna_decoder"
    assert cfg["check"]["why"]
    assert 0 < cfg["check"]["logits_rel_l2"] \
        < cfg["check"]["logits_rel_l2_a_prompt"]
    tiny = spec.config(spec.cell(CELL), tiny=True)["model"]
    # what is odd about the model survives the cut to a toy: groups of 2 and
    # 3, half the head rotated, a window that is not the bucket
    assert tiny["arch"] == "laguna"
    assert (tiny["num_heads"], tiny["swa_num_heads"], tiny["num_kv_heads"],
            tiny["rotary_dim"], tiny["head_dim"], tiny["sliding_window"]) \
        == (4, 6, 2, 8, 16, 8)


def test_param_shapes_sums_to_the_held_and_to_the_whole_model():
    from mxnet_tpu.models.transformer import param_shapes

    spec = spec_mod.Spec()
    cfg = spec.config(spec.cell(CELL))
    count = lambda shapes, names=None: sum(
        math.prod(shapes[n]) for n in (shapes if names is None else names))
    held = param_shapes(**cfg["model"])
    layer = lambda i: [n for n in held if n.startswith("layer%d_" % i)]
    attention = ("qkv_weight", "qnorm_gamma", "knorm_gamma", "gate_weight",
                 "proj_weight")
    assert count(held, ["layer4_" + n for n in attention]) == 44_187_904
    assert count(held, ["layer1_" + n for n in attention]) == 63_136_000
    assert count(held, layer(0)) == 157_440_256
    assert count(held, layer(1)) == count(held, layer(5)) == 677_345_792
    assert count(held, layer(4)) == 658_397_696
    assert count(held) == 157_440_256 + 4 * 677_345_792 + 658_397_696 \
        + 2 * 77_070_336 + 3_072 == 3_679_364_864
    whole = param_shapes(**dict(
        cfg["model"], num_layers=48, vocab_size=100352, num_local_experts=0,
        layer_types=cfg["layer_types"]))
    assert count(whole) == 157_440_256 + 11 * 2_470_337_024 \
        + 36 * 2_489_285_120 + 616_562_688 + 3_072 == 117_561_977_600


def test_the_traffic_is_the_issues_and_stays_inside_a_lane():
    """Prompts log-normal, median 4,096, sigma 0.6, on the grid 1,024 to
    8,192 in the 8,192 bucket; new tokens log-normal, median 256, sigma 0.8,
    clipped to 64-1,024; the largest prompt and the longest answer fit a
    lane's ``max_len``; 32 callers, one a lane."""
    spec = spec_mod.Spec()
    cell = spec.cell(CELL)
    assert cell == {"name": CELL, "config": CONFIG, "chips": 1,
                    "traffic": "generate-8k-1k-closed", "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "4 bands of 72 heads" in cell["why"]
    traffic, serving = spec.traffic(cell), spec.config(cell)["serving"]
    assert traffic["driver"] == "paged_closed_loop_laguna"
    assert traffic["callers"] == "lanes" and serving["lanes"] == 32
    assert traffic["fields"] == {
        "prompt_len": {"dist": "lognormal", "median": 4096, "sigma": 0.6,
                       "grid": [1024, 2048, 3072, 4096, 6144, 8192]},
        "output_len": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                       "clip": [64, 1024]}}
    assert traffic["ramp_dispatches"] == 8
    assert traffic["check_decode_steps"] == 16
    assert traffic["check_prompt_lens"] == [1024, 4096, 8192]
    for t, s in ((traffic, serving),
                 (spec.traffic(cell, tiny=True),
                  spec.config(cell, tiny=True)["serving"])):
        longest = max(t["fields"]["prompt_len"]["grid"])
        assert longest == s["prefill_len"]
        assert longest + max(t["fields"]["output_len"]["clip"]) \
            <= s["max_len"]
        assert max(t["check_prompt_lens"]) + t["check_decode_steps"] \
            <= s["max_len"]


def test_the_cell_is_a_member_of_the_lists_it_reports_and_of_no_other():
    """The cell reports what ``mimo-v2-flash.generate`` reports but that
    cell's own ``kernels.hbm_share.swa``, plus its own two. MEMBERSHIP only:
    no assertion here reads a position, so a later cell may follow this
    one."""
    doc = spec_mod.Spec().doc
    assert [c["name"] for c in doc["workloads"]].count(CELL) == 1
    assert [c["name"] for c in doc["configs"]].count(CONFIG) == 1
    assert sum(c["chips"] == 4 for c in doc["workloads"]) == 1
    metrics = {m["name"]: m for m in doc["end_to_end"] + doc["per_layer"]}
    assert metrics[MINE[0]] == {
        "name": MINE[0], "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "gen_tokens_per_s", "workloads": [CELL]}
    assert metrics[MINE[1]] == {
        "name": MINE[1], "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "serving",
        "moves": "ttft_ms_p50", "workloads": [CELL]}
    for name, m in metrics.items():
        lists = m.get("workloads", [])
        assert lists.count(CELL) <= 1
        if name in MINE:
            continue
        if name == "kernels.hbm_share.swa":
            assert CELL not in lists
        else:
            assert (CELL in lists) == (MIMO in lists), name
    spec = spec_mod.Spec()
    reported = spec.metrics("per_layer", CELL) + spec.metrics("end_to_end",
                                                              CELL)
    assert {"setup_s", "gen_tokens_per_s", "ttft_ms_p50",
            "kernels.flops_share.serving", "device.peak_hbm_gb",
            "moe.load_max_over_mean", "moe.local_rows_share",
            "serving.admit_state_ms_p50", "serving.device_gap_share"} \
        <= {m["name"] for m in reported}
    for m in reported:
        kind = "end_to_end" if m in doc["end_to_end"] else "layer_metrics"
        assert os.path.isfile(os.path.join(
            spec.bench_dir, kind, m["name"] + ".py")), m["name"]


def _driver_and_config():
    spec = spec_mod.Spec()
    return spec, spec.module("drivers", "paged_closed_loop_laguna"), \
        spec.config(spec.cell(CELL))


# the published widths, written out
D, DH, HKV = 3072, 128, 8
ATT_FULL = (48 + 2 * HKV) * DH * D + 48 * D + 48 * DH * D       # 44,187,648
ATT_WINDOW = (72 + 2 * HKV) * DH * D + 72 * D + 72 * DH * D     # 63,135,744
EXPERT = 3 * D * 1024                                           # 9,437,184
MATRICES = 2 * ATT_FULL + 4 * ATT_WINDOW + 3 * D * 12288 \
    + 5 * (256 * D + EXPERT)
VECTORS = 6 * (2 * D + 2 * DH) + 5 * 256 + D
HEAD = 25088 * D


def test_the_sizes_and_the_step_byte_count_are_the_layer_equations():
    """At the published widths, the sums written out: what a step reads
    whatever the router does (both kinds of attention with their gates, the
    dense MLP, the routers and shared experts, the vectors, the head's
    slice: 582 M parameters, 1.16 GB), one expert's 18.9 MB, a token's 8 KiB
    in the two pools and 16 KiB in the four rings."""
    _, driver, cfg = _driver_and_config()
    model = cfg["model"]
    assert (ATT_FULL, ATT_WINDOW) == (44_187_648, 63_135_744)
    assert MATRICES == 505_282_560 and VECTORS == 42_752
    assert driver.sizes(model) == (MATRICES + VECTORS + HEAD, EXPERT,
                                   2 * HKV * 2 * DH, 4 * HKV * 2 * DH)
    assert driver.sizes(model) == (582_395_648, 9_437_184, 4_096, 8_192)
    a_step = 2 * 582_395_648
    assert driver.step_bytes(model, "bfloat16", 1, 0, 0, 0, 0) == a_step
    assert driver.step_bytes(model, "bfloat16", 0, 1, 0, 0, 0) \
        == 2 * (4_096 + 8_192)      # a stepped lane writes every layer's row
    assert driver.step_bytes(model, "bfloat16", 0, 0, 1, 0, 0) == 2 * 4_096
    assert driver.step_bytes(model, "bfloat16", 0, 0, 0, 1, 0) == 2 * 8_192
    assert driver.step_bytes(model, "bfloat16", 0, 0, 0, 0, 1) == 2 * EXPERT
    # 100 steps of 32 lanes at 5,000 tokens of context, rings full, 46 of
    # the 64 held experts touched a layer: 7.2 GB a step
    steps, lanes = 100, 32
    moved = driver.step_bytes(model, "bfloat16", steps, steps * lanes,
                              steps * lanes * 5000, steps * lanes * 512,
                              steps * 5 * 46)
    assert moved == steps * a_step + steps * 5 * 46 * 2 * EXPERT \
        + steps * lanes * (5001 * 8_192 + 513 * 16_384)
    assert 7.0e9 < moved / steps < 7.4e9
    assert driver.step_bytes(model, "float32", 1, 1, 1, 1, 1) \
        == 2 * driver.step_bytes(model, "bfloat16", 1, 1, 1, 1, 1)


def test_the_flop_count_is_the_layer_equations():
    """A token passes the matrices outside the routed experts and 10 x 64 /
    256 = 2.5 held experts a sparse layer (623 M active parameters here); a
    window layer scores and applies 512 keys at 72 heads, a full layer its
    context at 48; the head a row."""
    _, driver, cfg = _driver_and_config()
    model = cfg["model"]
    active = MATRICES + 5 * EXPERT * 10 * 64 // 256
    assert active == 623_247_360
    window = 4 * 512 * 2 * 72 * 2 * DH
    assert driver.model_flops(model, 1, 0, 0) == 2 * active + window \
        == 1_321_992_192
    assert driver.model_flops(model, 0, 1, 0) == 2 * 2 * 48 * 2 * DH
    assert driver.model_flops(model, 0, 0, 1) == 2 * HEAD
    # an admission of a prompt that fills the bucket: 13.8 T FLOP, of which
    # 10.2 the matrices, 0.6 the bands and 3.3 the two causal layers (the
    # causal half not discounted)
    t = 8192
    assert driver.model_flops(model, t, t * t, 1) == pytest.approx(
        t * (2 * active + window) + t * t * 49_152 + 2 * HEAD)
    assert 1.35e13 < driver.model_flops(model, t, t * t, 1) < 1.45e13
    # every expert held: the whole 10 a token
    whole = dict(model, num_local_experts=0)
    assert driver.model_flops(whole, 1, 0, 0) \
        == 2 * (MATRICES + 5 * EXPERT * 10) + window


def test_each_new_metric_needs_the_programs_counters_and_the_architecture():
    """Nothing to read, and no error, from a program without the counters
    (the parent commit) or a configuration of another architecture."""
    spec, driver, cfg = _driver_and_config()
    share = spec.module("layer_metrics", MINE[0])
    live = spec.module("layer_metrics", MINE[1])
    full = {"serving.paged_steps": 100, "serving.decode_tokens": 3200,
            "serving.step_context_tokens": 3200 * 5000,
            "serving.step_window_slots": 3200 * 512,
            "serving.moe.step_experts_touched": 100 * 5 * 46,
            "serving.admit_window_pairs_scored": 30 * 8192 * 1024,
            "serving.admit_window_pairs_live": 30 * (
                512 * 513 // 2 + (4096 - 512) * 512)}
    run = lambda **kw: SimpleNamespace(**{
        "trace_summary": {"busy_s": 2.0}, "counters_window": full,
        "peaks": {"hbm_bytes_per_s": 819e9}, "config": cfg, **kw})
    got = share.read(run())
    assert got == pytest.approx(100.0 * driver.step_bytes(
        cfg["model"], "bfloat16", 100, 3200, 3200 * 5000, 3200 * 512,
        100 * 5 * 46) / (2.0 * 819e9))
    assert 30 < got < 60
    # the median prompt of 4,096 in the 8,192 bucket: about a quarter
    assert live.read(run()) == pytest.approx(100.0 * (
        512 * 513 // 2 + 3584 * 512) / (8192 * 1024))
    assert 23 < live.read(run()) < 25
    for reader, needs in ((share, ("serving.paged_steps",
                                   "serving.step_context_tokens",
                                   "serving.step_window_slots",
                                   "serving.moe.step_experts_touched")),
                          (live, ("serving.admit_window_pairs_scored",
                                  "serving.admit_window_pairs_live"))):
        for gone in needs:
            old = {k: v for k, v in full.items() if k != gone}
            assert reader.read(run(counters_window=old)) is None, gone
        assert reader.read(run(counters_window=None)) is None
        assert reader.read(run(counters_window={})) is None
        for other in OTHERS:
            config = spec.config(spec.cell(other))
            assert reader.read(run(config=config)) is None, other
    assert share.read(run(peaks=None)) is None
    assert share.read(run(trace_summary=None)) is None


def test_the_reference_imports_nothing_of_the_program():
    """``reference/laguna_decoder.py`` is plain ``jax.numpy``: its only
    imports are jax's and the standard library's, it sets the highest matmul
    precision, loops over the layers in Python, and its notes name each
    point the config leaves open."""
    spec = spec_mod.Spec()
    path = os.path.join(spec.bench_dir, "reference", "laguna_decoder.py")
    source = open(path).read()
    tree = ast.parse(source)
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported == {"jax", "math"}
    assert "mxnet_tpu" not in source and "pallas" not in source
    assert 'default_matmul_precision("highest")' in source
    assert "for i in range(layer):" in source
    doc = ast.get_docstring(tree)
    for said in ("softplus", "q/k norm", "selection bias", "ungated",
                 "WITH the token itself", "truncate", "repeat_kv",
                 "attention_factor", "HELD"):
        assert said in doc, said
