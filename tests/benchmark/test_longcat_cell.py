"""The cell ``longcat-flash-omni.generate`` rehearsed on the CPU at its tiny
size: it runs to its end and meets the contract untraced and traced and its
program reports the counters its two metrics read, a broken reference is
reported, the configuration holds every published number of the catalog's row
and exactly the three cuts, ``param_shapes`` sums to the held and to the whole
model, the driver's ``sizes``, ``step_bytes`` and FLOP are the layer
equations' arithmetic written out, each new metric gives nothing where there
is nothing to read, the traffic stays inside ``max_len``, and the reference
imports nothing of the program. The cell's place in ``BENCHMARK.json`` is held
by MEMBERSHIP, never by position or by equality on a shared list: the next
cell appended behind it breaks nothing here."""
import ast
import json
import math
import os
from types import SimpleNamespace

import pytest

from harness import contract, main as harness_main, spec as spec_mod

CELL = "longcat-flash-omni.generate"
CONFIG = "longcat-flash-omni"
SOURCE = ("https://huggingface.co/meituan-longcat/LongCat-Flash-Omni/blob/"
          "main/config.json")
KANANA, LAGUNA = "kanana-2-30b-a3b.generate", "laguna-s-2.1.generate"
MINE = ("kernels.hbm_share.scmoe", "moe.zero_expert_share")
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
        # what the program counted and spanned inside the window, as the
        # reader of ``kernels.hbm_share.scmoe`` is handed it (a rehearsal has
        # no peaks and no device trace, so the reader itself gives nothing
        # here)
        spec = spec_mod.Spec()
        reader = spec.module("layer_metrics", MINE[0])
        module = spec_mod.Spec.module

        def spy(self, kind, name):
            if name != MINE[0]:
                return module(self, kind, name)
            return SimpleNamespace(read=lambda run: seen.update(
                run.counters_window, spans={s[0] for s in run.spans})
                or reader.read(run))

        monkeypatch.setattr(spec_mod.Spec, "module", spy)
    rc, out, line = _rehearse(capsys, "--seed", "3000000061",
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
    assert "the 5-th smallest of all 39 rows" in line["checks"][0]
    assert "their median" in line["checks"][0]
    assert line["checks"][1].startswith(
        "the first layer's second latent pool (kv_c_1)")
    for check in line["checks"][:2]:
        assert check.endswith("ok")
    assert "every lane retired and every page returned: ok" in line["checks"]
    assert [len(r) for r in line["notes"]["check_rows_sorted"]] == [13] * 3
    if not trace:
        assert set(line["metrics"]) == set(declared)
        return
    got = line["metrics"]
    assert set(MINE) <= set(declared)
    for absent in ("kernels.hbm_share.mla", "kernels.hbm_share.swa_heads",
                   "moe.local_rows_share", "moe.admit_held_rows_share",
                   "moe.load_max_over_mean", "serving.step_wait_ms_p50",
                   "serving.step_dispatch_ms_p50",
                   "serving.admit_state_ms_p50"):
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
    # 16 of the tiny router's 48 outputs are zero-compute: a third under even
    # routing, and the counters the share is made of
    zero = seen["serving.moe.zero_assignments"] \
        + seen["serving.moe.step_zero_assignments"]
    total = seen["serving.moe.assignments"] \
        + seen["serving.moe.step_assignments"]
    assert got[MINE[1]]["value"] == pytest.approx(100.0 * zero / total)
    assert 15 < got[MINE[1]]["value"] < 60
    # every position of a bucket of 16 through two expert layers, six each
    assert seen["serving.moe.assignments"] \
        == seen["serving.paged_admits"] * 16 * 2 * 6
    assert seen["serving.step_context_tokens"] > seen["serving.decode_tokens"]
    assert 0 < seen["serving.moe.step_experts_touched"] \
        <= seen["serving.moe.step_local_assignments"]
    assert {"serving.step.dispatch", "serving.step.wait"} <= seen["spans"]
    # what the line leaves out (a pinned list, a reader that takes the
    # router for 32 wide where it is 48) is in the notes
    notes = line["notes"]
    assert notes["held_experts_touched_a_step_and_layer"] > 0
    assert 0 < notes["step_local_rows_share"] < 0.5
    assert notes["router_load_max_over_mean"] == pytest.approx(
        seen["serving.moe.max_expert_assignments"] * 48
        / seen["serving.moe.assignments"])
    assert 1 <= notes["router_load_max_over_mean"] <= 48
    assert notes["serving.step_dispatch_ms_p50"] > 0
    assert notes["serving.step_wait_ms_p50"] > 0


def test_a_broken_reference_is_reported_as_incorrect(capsys):
    """``layer0_kvb_weight`` x 1.25, the matrix both of the first sublayer's
    attention paths read: the logits' holds fail, and so does the second
    pool, whose sublayer reads the stream behind that attention."""
    rc, _, line = _rehearse(capsys, "--break-reference")
    declared = {m["name"]: m["unit"]
                for m in spec_mod.Spec().metrics("end_to_end", CELL)}
    assert rc == 0 and contract.problems(line, declared, False) == []
    assert line["correct"] is False and line["failed"] == 0
    assert line["checks"][0].endswith("FAIL")
    assert line["checks"][1].endswith("FAIL")
    worst = max(max(rows) for rows in line["notes"]["check_rows_sorted"])
    assert worst > 100 * 1e-4
    driver = spec_mod.Spec().module("drivers", "paged_closed_loop_longcat")
    assert driver.BROKEN == "layer0_kvb_weight" and driver.POOL == "kv_c_1"


def test_the_configuration_holds_the_published_numbers_and_three_cuts():
    """Every key of the catalog's ``config`` under the same key with the same
    value but the three of ``reduced``, those with their published values
    beside them; every width, the 768 router outputs, 12 a token and the 256
    zero-compute experts as published; the deployment stated; each assumption
    the issue names written down; the decoder's sizes the same numbers."""
    spec = spec_mod.Spec()
    cfg = spec.config(spec.cell(CELL))
    (row,) = [r for r in map(json.loads, open(CATALOG))
              if r["name"] == "LongCat-Flash-Omni"] \
        if os.path.isfile(CATALOG) else [None]
    cuts = {"num_layers": (28, 4), "n_routed_experts": (512, 16),
            "vocab_size": (131072, 16384)}
    if row is not None:
        assert row["source_url"] == SOURCE
        for key, value in row["config"].items():
            assert cfg[key] == (cuts[key][1] if key in cuts else value), key
    assert cfg["reduced"] == list(cuts)
    for key, (published, run) in cuts.items():
        assert cfg["published"][key] == published and cfg[key] == run
    assert "560,664,980,480" in cfg["published"]["parameters"]
    for key, value in dict(
            hidden_size=6144, ffn_hidden_size=12288,
            expert_ffn_hidden_size=2048, num_attention_heads=64,
            kv_lora_rank=512, q_lora_rank=1536, qk_rope_head_dim=64,
            qk_nope_head_dim=128, v_head_dim=128, zero_expert_num=256,
            zero_expert_type="identity", moe_topk=12,
            routed_scaling_factor=6, rms_norm_eps=1e-5,
            rope_theta=10000000).items():
        assert cfg[key] == value, key
    (entry,) = [c for c in spec.doc["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == list(cuts) and entry["source"] == SOURCE
    assert len(entry["why"]) <= 200
    assert cfg["source"].startswith(SOURCE)
    assert entry["file"] == "benchmark/configs/longcat-flash-omni.json"
    assert cfg["model"] == dict(
        arch="longcat_flash", vocab_size=16384, num_layers=4, num_heads=64,
        model_dim=6144, ffn_dim=12288, moe_ffn_dim=2048, num_experts=512,
        num_zero_experts=256, num_local_experts=16, local_expert_offset=0,
        num_experts_per_tok=12, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_theta=1e7, rms_eps=1e-5, routed_scaling_factor=6.0)
    assert cfg["dtype"] == "bfloat16"
    assert cfg["serving"] == dict(max_len=5120, prefill_len=4096,
                                  page_size=16, lanes=32)
    for said in ("224", "v5e-256", "32 chips share each layer",
                 "7 pipeline stages of 4 layers", "data-parallel",
                 "5,172,749,312", "10.35 GB", "1.51 GB", "64 rows",
                 "half a row"):
        assert said in cfg["deployment"], said
    assert set(cfg["assumed"]) >= {
        "norm_topk_prob", "router", "hidden_act", "tie_word_embeddings",
        "e_score_correction_bias", "mtp", "rope", "dtype", "serving", "init",
        "layout"}
    assert "N(0, 0.002)" in cfg["assumed"]["e_score_correction_bias"]
    assert cfg["reference"] == "longcat_flash_decoder"
    assert cfg["check"]["why"]
    assert set(cfg["check"]) == {
        "logits_rel_l2", "logits_rel_l2_a_prompt", "logits_rel_l2_median",
        "pool_rows_rel_l2", "why"}
    assert 0 < cfg["check"]["logits_rel_l2"] \
        < cfg["check"]["logits_rel_l2_median"] \
        < cfg["check"]["logits_rel_l2_a_prompt"]
    for said in ("MEDIAN of all 51 rows", "p + b", "by the choice of the "
                 "bias", "raised to 2.8e-2 and 3.7e-2"):
        assert said in cfg["check"]["why"], said
    assert 0 < cfg["check"]["pool_rows_rel_l2"]
    tiny = spec.config(spec.cell(CELL), tiny=True)["model"]
    # what is odd about the model survives the cut to a toy: two pools a
    # layer, a router wider than the experts, a share held
    assert tiny["arch"] == "longcat_flash"
    assert (tiny["num_experts"], tiny["num_zero_experts"],
            tiny["num_local_experts"], tiny["num_experts_per_tok"]) \
        == (32, 16, 8, 6)


def test_param_shapes_sums_to_the_held_and_to_the_whole_model():
    from mxnet_tpu.models.transformer import decode_cache, param_shapes

    spec = spec_mod.Spec()
    cfg = spec.config(spec.cell(CELL))
    count = lambda shapes, names=None: sum(
        math.prod(shapes[n]) for n in (shapes if names is None else names))
    held = param_shapes(**cfg["model"])
    sub = lambda j: [n for n in held if n.startswith("layer%d_" % j)]
    attention = ("qa_weight", "qnorm_gamma", "qb_weight", "kva_weight",
                 "kvnorm_gamma", "kvb_weight", "proj_weight")
    for j in range(8):
        assert count(held, ["layer%d_%s" % (j, n) for n in attention]) \
            == 90_572_800
        assert count(held, ["layer%d_mlp_%s_weight" % (j, n)
                            for n in ("in", "out")]) == 226_492_416
    assert count(held, sub(0)) + count(held, sub(1)) == 1_242_854_144 \
        == 638_874_368 + 16 * 37_748_736
    assert not [n for n in sub(1) if "router" in n or "experts" in n]
    assert count(held) == 4 * 1_242_854_144 + 201_332_736 == 5_172_749_312
    whole = param_shapes(**dict(cfg["model"], num_layers=28,
                                vocab_size=131072, num_local_experts=0))
    assert count(whole) == 28 * 19_966_227_200 + 1_610_618_880 \
        == 560_664_980_480
    cache = decode_cache(**cfg["model"])
    assert cache == [("kv_c_%d" % j, "pool", (1, 576)) for j in range(8)]
    serving = cfg["serving"]
    assert 8 * serving["lanes"] * serving["max_len"] * 576 * 2 \
        == 1_509_949_440


def test_the_traffic_is_the_issues_and_stays_inside_a_lane():
    """Prompts log-normal, median 2,048, sigma 0.6, on the grid 512 to 4,096
    in the 4,096 bucket; new tokens log-normal, median 256, sigma 0.7,
    clipped to 64-1,024; the largest prompt and the longest answer fit a
    lane's ``max_len``; 32 callers, one a lane."""
    spec = spec_mod.Spec()
    cell = spec.cell(CELL)
    assert cell == {"name": CELL, "config": CONFIG, "chips": 1,
                    "traffic": "generate-4k-1k-closed", "why": cell["why"]}
    assert len(cell["why"]) <= 200
    for said in ("64 rows", "0.5 a step", "deployment: 16"):
        assert said in cell["why"], said
    traffic, serving = spec.traffic(cell), spec.config(cell)["serving"]
    assert traffic["driver"] == "paged_closed_loop_longcat"
    assert traffic["callers"] == "lanes" and serving["lanes"] == 32
    assert traffic["fields"] == {
        "prompt_len": {"dist": "lognormal", "median": 2048, "sigma": 0.6,
                       "grid": [512, 1024, 1536, 2048, 3072, 4096]},
        "output_len": {"dist": "lognormal", "median": 256, "sigma": 0.7,
                       "clip": [64, 1024]}}
    assert traffic["ramp_dispatches"] == 8
    assert traffic["check_decode_steps"] == 16
    assert traffic["check_prompt_lens"] == [512, 2048, 4096]
    for t, s in ((traffic, serving),
                 (spec.traffic(cell, tiny=True),
                  spec.config(cell, tiny=True)["serving"])):
        longest = max(t["fields"]["prompt_len"]["grid"])
        assert longest == s["prefill_len"]
        assert longest + max(t["fields"]["output_len"]["clip"]) \
            <= s["max_len"]
        assert max(t["check_prompt_lens"]) + t["check_decode_steps"] \
            <= s["max_len"]


def test_the_cell_is_a_member_of_the_lists_it_reports():
    """The cell reports the end-to-end metrics of a generating cell, every
    per-layer list that BOTH ``kanana-2-30b-a3b.generate`` and
    ``laguna-s-2.1.generate`` are in but ``moe.load_max_over_mean`` (its
    reader multiplies by ``num_experts``, two thirds of this router's
    outputs: the driver's notes have the ratio by the router's width), and
    its own two; it joins no one-cell list of another cell's, neither of the
    two held-share lists (``tests/benchmark/test_held_rows_metric.py`` pins
    their members) and neither ``serving.step_dispatch_ms_p50`` nor
    ``serving.step_wait_ms_p50`` (``tests/benchmark/test_dispatch_budget.py``
    pins theirs; the notes have both medians).
    MEMBERSHIP only: no assertion here reads a position or the whole of a
    shared list, so a later cell may follow this one."""
    doc = spec_mod.Spec().doc
    assert [c["name"] for c in doc["workloads"]].count(CELL) == 1
    assert [c["name"] for c in doc["configs"]].count(CONFIG) == 1
    metrics = {m["name"]: m for m in doc["end_to_end"] + doc["per_layer"]}
    assert metrics[MINE[0]] == {
        "name": MINE[0], "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "gen_tokens_per_s", "workloads": [CELL]}
    assert metrics[MINE[1]] == {
        "name": MINE[1], "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "serving",
        "moves": "gen_tokens_per_s", "workloads": [CELL]}
    for name, m in metrics.items():
        lists = m.get("workloads", [])
        assert lists.count(CELL) <= 1
        if name in MINE or name == "moe.load_max_over_mean" or not lists:
            continue
        assert (CELL in lists) == (KANANA in lists and LAGUNA in lists), name
    for apart in ("moe.local_rows_share", "moe.admit_held_rows_share",
                  "moe.load_max_over_mean", "serving.step_dispatch_ms_p50",
                  "serving.step_wait_ms_p50", "kernels.hbm_share.mla",
                  "kernels.hbm_share.swa_heads"):
        assert CELL not in metrics[apart]["workloads"]
    spec = spec_mod.Spec()
    reported = spec.metrics("per_layer", CELL) + spec.metrics("end_to_end",
                                                              CELL)
    assert {"setup_s", "gen_tokens_per_s", "ttft_ms_p50",
            "kernels.flops_share.serving", "device.idle_share.serving",
            "device.peak_hbm_gb", "serving.device_gap_share", "serving.admit_ms_p50",
            "serving.step_ms_p50"} <= {m["name"] for m in reported}
    for m in reported:
        kind = "end_to_end" if m in doc["end_to_end"] else "layer_metrics"
        assert os.path.isfile(os.path.join(
            spec.bench_dir, kind, m["name"] + ".py")), m["name"]


def _driver_and_config():
    spec = spec_mod.Spec()
    return spec, spec.module("drivers", "paged_closed_loop_longcat"), \
        spec.config(spec.cell(CELL))


# the published widths, written out
D, H, RANK, LAT = 6144, 64, 1536, 512
ATTENTION = RANK * D + H * 192 * RANK + (LAT + 64) * D + H * 256 * LAT \
    + H * 128 * D                                               # 90,570,752
MLP = 3 * D * 12288                                             # 226,492,416
EXPERT = 3 * D * 2048                                           # 37,748,736
MATRICES = 4 * (2 * (ATTENTION + MLP) + 768 * D)
VECTORS = 4 * (2 * (2 * D + RANK + LAT) + 768) + D
HEAD = 16384 * D


def test_the_sizes_and_the_step_byte_count_are_the_layer_equations():
    """At the published widths, the sums written out: what a step reads
    whatever the router does (eight latent attentions, eight dense MLPs, four
    routers, the vectors, the head's slice: 2.66 B parameters, 5.31 GB), one
    expert's 75.5 MB, a token's 9 KiB in the eight pools."""
    _, driver, cfg = _driver_and_config()
    model = cfg["model"]
    assert (ATTENTION, MLP, EXPERT) == (90_570_752, 226_492_416, 37_748_736)
    assert MATRICES == 2_555_379_712 and VECTORS == 123_904
    # a layer outside its experts, as the configuration's file counts it
    assert (MATRICES + VECTORS - D) // 4 == 638_874_368
    assert driver.sizes(model) == (MATRICES + VECTORS + HEAD, EXPERT,
                                   8 * 576)
    assert driver.sizes(model) == (2_656_166_912, 37_748_736, 4_608)
    a_step = 2 * 2_656_166_912
    assert driver.step_bytes(model, "bfloat16", 1, 0, 0, 0) == a_step
    assert driver.step_bytes(model, "bfloat16", 0, 1, 0, 0) == 2 * 4_608
    assert driver.step_bytes(model, "bfloat16", 0, 0, 1, 0) == 2 * 4_608
    assert driver.step_bytes(model, "bfloat16", 0, 0, 0, 1) == 2 * EXPERT
    # 100 steps of 32 lanes at 2,500 tokens of context, 6 of the 16 held
    # experts touched a layer: 7.9 GB a step
    steps, lanes = 100, 32
    moved = driver.step_bytes(model, "bfloat16", steps, steps * lanes,
                              steps * lanes * 2500, steps * 4 * 6)
    assert moved == steps * a_step + steps * 4 * 6 * 2 * EXPERT \
        + steps * lanes * 2501 * 9_216
    assert 7.7e9 < moved / steps < 8.0e9
    assert driver.step_bytes(model, "float32", 1, 1, 1, 1) \
        == 2 * driver.step_bytes(model, "bfloat16", 1, 1, 1, 1)


def test_the_flop_count_is_the_layer_equations():
    """A token passes the matrices outside the routed experts and 12 x 16 /
    768 = a quarter of a held expert a layer (2.59 B active parameters here);
    each of the eight attentions scores and applies its context at 64 heads
    of 192 | 128; the head a row."""
    _, driver, cfg = _driver_and_config()
    model = cfg["model"]
    active = MATRICES + 4 * EXPERT * 12 * 16 // 768
    assert active == 2_593_128_448
    assert driver.model_flops(model, 1, 0, 0) == 2 * active
    assert driver.model_flops(model, 0, 1, 0) == 8 * 2 * H * (192 + 128)
    assert driver.model_flops(model, 0, 0, 1) == 2 * HEAD
    # an admission of a prompt that fills the bucket: 26.7 T FLOP, of which
    # 21.2 the matrices and 5.5 the eight causal attentions (the causal half
    # not discounted)
    t = 4096
    assert driver.model_flops(model, t, t * t, 1) == pytest.approx(
        t * 2 * active + t * t * 327_680 + 2 * HEAD)
    assert 2.6e13 < driver.model_flops(model, t, t * t, 1) < 2.8e13
    # every expert held: eight of a token's twelve under even routing
    whole = dict(model, num_local_experts=0)
    assert driver.model_flops(whole, 1, 0, 0) \
        == 2 * (MATRICES + 4 * EXPERT * 12 * 512 // 768)


def _run(config, counters, busy_s=None, peaks=True):
    return SimpleNamespace(
        config=config, counters_window=counters, spans=[],
        peaks={"hbm_bytes_per_s": 819e9} if peaks else None,
        trace_summary=busy_s and {"busy_s": busy_s})


def test_each_new_metric_reads_its_counters_or_nothing():
    spec, driver, cfg = _driver_and_config()
    share = spec.module("layer_metrics", MINE[1]).read
    counted = {"serving.moe.assignments": 900, "serving.moe.zero_assignments":
               280, "serving.moe.step_assignments": 100,
               "serving.moe.step_zero_assignments": 53}
    assert share(_run(cfg, counted)) == pytest.approx(33.3)
    assert share(_run(cfg, {})) is None             # the parent: no counter
    assert share(_run(cfg, {"serving.moe.assignments": 900})) is None
    kanana = spec.config(spec.cell(KANANA))
    assert share(_run(kanana, counted)) is None     # no zero-compute experts
    roofline = spec.module("layer_metrics", MINE[0]).read
    steps = {"serving.paged_steps": 10, "serving.decode_tokens": 320,
             "serving.step_context_tokens": 800_000,
             "serving.moe.step_experts_touched": 240}
    # over ALL the device's busy seconds in the window, the admissions' too,
    # from the device's trace: no span of the host's enters
    moved = driver.step_bytes(cfg["model"], "bfloat16", 10, 320, 800_000, 240)
    assert roofline(_run(cfg, steps, 0.5)) == pytest.approx(
        100.0 * moved / (0.5 * 819e9))
    assert roofline(_run(cfg, steps, 0.5, peaks=False)) is None
    assert roofline(_run(cfg, steps)) is None       # untraced: no device time
    assert roofline(_run(cfg, {}, 0.5)) is None     # the parent: no counter
    assert roofline(_run(kanana, steps, 0.5)) is None


def test_the_reference_imports_nothing_of_the_program():
    spec = spec_mod.Spec()
    path = os.path.join(spec.bench_dir, "reference",
                        "longcat_flash_decoder.py")
    tree = ast.parse(open(path).read())
    imported = {a.name.split(".")[0] for n in ast.walk(tree)
                if isinstance(n, ast.Import) for a in n.names} \
        | {n.module.split(".")[0] for n in ast.walk(tree)
           if isinstance(n, ast.ImportFrom) and n.module}
    assert imported == {"jax"}
    doc = ast.get_docstring(tree)
    for said in ("no network", "longcat_flash", "NOT renormalised",
                 "identity", "added here", "rho_kv reaches keys AND"):
        assert said in doc, said
