"""The cell ``nemotron-3-nano-30b-a3b.generate`` rehearsed on the CPU at its
tiny size: it runs to its end and meets the contract untraced and traced, a
broken reference is reported, the configuration holds every published number
of the catalog's row with the three cuts it states, the driver's ``sizes``,
``step_bytes`` and FLOP are the layer equations' arithmetic at the PUBLISHED
widths (the stacks' zero padding is not counted), the new metric gives nothing
where there is nothing to read, and the reference imports nothing of the
program. The cell's place in ``BENCHMARK.json`` is held by MEMBERSHIP, never
by position: the next cell appended behind it breaks nothing here."""
import ast
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from harness import contract, main as harness_main, spec as spec_mod

CELL = "nemotron-3-nano-30b-a3b.generate"
CONFIG = "nemotron-3-nano-30b-a3b"
SOURCE = "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/" \
         "blob/main/config.json"
PHI = "phi-4-mini-flash-reasoning.generate"
OTHERS = ("transformer-base.generate", "olmoe-1b-7b.score",
          "granite-4.0-h-micro.generate", "kanana-2-30b-a3b.generate",
          "lfm2-24b-a2b.generate", "mimo-v2-flash.generate", PHI,
          "resnet50.train")
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


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
def test_the_cell_rehearses_to_its_end_and_meets_the_contract(trace, capsys):
    rc, out, line = _rehearse(capsys, "--seed", "3000000029",
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
    assert "admit and 12 decode steps at prompt lengths [4, 8, 16]" \
        in line["checks"][0]
    assert "the fifth smallest of all 39 rows" in line["checks"][0]
    assert line["checks"][0].endswith("ok")
    assert line["checks"][1].startswith("the first block's state of each "
                                        "sampled lane after its last step")
    assert "worst head's relative L2" in line["checks"][1]
    assert line["checks"][1].endswith("ok")
    assert "every lane retired and every page returned: ok" in line["checks"]
    assert [len(r) for r in line["notes"]["check_rows_sorted"]] \
        == [13, 13, 13]
    # the CPU's expert blocks are XLA's form; the chip's are the kernel's
    assert line["notes"]["expert_form"] == {"decode": "ragged_dot",
                                            "prefill": "ragged_dot"}
    if not trace:
        assert set(line["metrics"]) == set(declared)
        return
    got = line["metrics"]
    # the share of the HBM peak needs a chip's peaks
    assert "kernels.hbm_share.ssm_moe" in declared
    for absent in ("kernels.hbm_share.swa", "kernels.hbm_share.yoco",
                   "kernels.hbm_share.ssm", "serving.admit_cross_rows_share"):
        assert absent not in declared
    # 8 of 16 experts held: about half of a step's assignments are local
    assert 25 < got["moe.local_rows_share"]["value"] < 75
    assert got["moe.load_max_over_mean"]["value"] >= 1
    assert got["serving.admit_state_ms_p50"]["value"] > 0
    for phase in ("stage", "prefill", "logits", "scatter"):
        assert got["serving.admit_%s_ms_p50" % phase]["value"] > 0
    for name in ("serving.admit_ms_p50", "serving.step_ms_p50",
                 "serving.step_stage_ms_p50", "serving.step_read_ms_p50",
                 "serving.step_commit_ms_p50", "serving.itl_ms_p95"):
        assert got[name]["value"] > 0
    assert got["process.compiles_in_window.serving"]["value"] == 0
    assert got["graph.retraces_in_window.serving"]["value"] == 0
    # three expert blocks of eight held experts: a step touches some of each
    assert 0 < line["notes"]["held_experts_touched_a_step_and_layer"] <= 8


def test_a_broken_reference_is_reported_as_incorrect(capsys):
    """``layer0_mamba_out_weight`` x 1.25 moves the first mixer's output and
    so every row of the logits; the state that mixer KEEPS does not pass
    through its output projection and still agrees: the first comparison
    fails alone, and that is enough."""
    rc, _, line = _rehearse(capsys, "--break-reference")
    declared = {m["name"]: m["unit"]
                for m in spec_mod.Spec().metrics("end_to_end", CELL)}
    assert rc == 0 and contract.problems(line, declared, False) == []
    assert line["correct"] is False and line["failed"] == 0
    assert line["checks"][0].endswith("FAIL")
    assert line["checks"][1].endswith("ok")


def test_the_configuration_holds_the_published_numbers_and_states_its_cut():
    """Every key of the catalog's ``config`` under the same key with the same
    value, but the three the file lists as ``reduced``, whose published
    values stand beside them; every width as published; the deployment (two
    chips a layer, four stages of 13) stated; the decoder's sizes the same
    numbers."""
    spec = spec_mod.Spec()
    cfg = spec.config(spec.cell(CELL))
    published = dict(
        attention_bias=False, chunk_size=128, conv_kernel=4, expand=2,
        head_dim=128, hidden_size=2688, hybrid_override_pattern=PATTERN,
        intermediate_size=1856, layer_norm_epsilon=1e-05, mamba_head_dim=64,
        mamba_hidden_act="silu", mamba_num_heads=64, mamba_proj_bias=False,
        max_position_embeddings=262144, mlp_bias=False,
        mlp_hidden_act="relu2", model_type="nemotron_h",
        moe_intermediate_size=1856, moe_shared_expert_intermediate_size=3712,
        n_group=1, n_groups=8, n_routed_experts=128, n_shared_experts=1,
        norm_eps=1e-05, norm_topk_prob=True, num_attention_heads=32,
        num_experts_per_tok=6, num_hidden_layers=52, num_key_value_heads=2,
        num_logits_to_keep=1, partial_rotary_factor=1,
        rescale_prenorm_residual=True, residual_in_fp32=False,
        rope_theta=10000, routed_scaling_factor=2.5, sliding_window=None,
        ssm_state_size=128, tie_word_embeddings=False, time_step_floor=0.0001,
        time_step_max=0.1, time_step_min=0.001, topk_group=1, use_bias=False,
        use_conv_bias=True, use_mamba_kernels=True, vocab_size=131072)
    cut = dict(num_hidden_layers=13, n_routed_experts=64, vocab_size=65536)
    for key, value in published.items():
        want = cut.get(key, value)
        assert cfg[key] == want and type(cfg[key]) is type(want), key
    assert cfg["reduced"] == sorted(cut, key=list(cut).index) \
        == spec.configs[CONFIG]["reduced"]
    assert cfg["published"] == {k: published[k] for k in cut}
    # floors: a period and four more layers, 8 experts, 1/8 of the vocabulary
    assert not any("dim" in k or "size" in k and k != "vocab_size"
                   for k in cfg["reduced"])
    assert cfg["source"].startswith(SOURCE)
    assert spec.configs[CONFIG]["source"].startswith(SOURCE + " nemotron_h")
    for text in (spec.configs[CONFIG]["source"], spec.configs[CONFIG]["why"]):
        assert len(text) <= 200
    for said in ("v5e-8", "TWO chips share each layer", "FOUR pipeline stages",
                 "52 -> 13", "128 -> 64", "131,072 -> 65,536",
                 "3,926,018,560"):
        assert said in cfg["deployment"], said
    for key in ("no_positions", "read_by_nothing", "dtype", "dt", "chunk",
                "e_score_correction_bias", "serving", "lanes", "init",
                "layout"):
        assert cfg["assumed"][key], key
    assert "rope_theta" in cfg["assumed"]["no_positions"]
    for name in ("rescale_prenorm_residual", "expand"):
        assert name in cfg["assumed"]["read_by_nothing"]
    m = cfg["model"]
    same = dict(vocab_size="vocab_size", num_layers="num_hidden_layers",
                num_heads="num_attention_heads",
                num_kv_heads="num_key_value_heads", head_dim="head_dim",
                model_dim="hidden_size", ffn_dim="intermediate_size",
                mamba_heads="mamba_num_heads", mamba_head_dim="mamba_head_dim",
                mamba_state="ssm_state_size", mamba_groups="n_groups",
                mamba_conv="conv_kernel", mamba_chunk="chunk_size",
                moe_ffn_dim="moe_intermediate_size",
                shared_ffn_dim="moe_shared_expert_intermediate_size",
                num_experts_per_tok="num_experts_per_tok",
                num_local_experts="n_routed_experts",
                routed_scaling_factor="routed_scaling_factor",
                norm_topk_prob="norm_topk_prob", rms_eps="norm_eps")
    assert set(same) | {"arch", "layer_types", "num_experts",
                        "local_expert_offset"} == set(m)
    for ours, theirs in same.items():
        assert m[ours] == cfg[theirs], ours
    assert m["arch"] == "nemotron_h" and m["local_expert_offset"] == 0
    assert m["num_experts"] == 128 == cfg["published"]["n_routed_experts"]
    assert m["layer_types"] == [KINDS[c] for c in PATTERN[:13]]
    assert [m["layer_types"].count(k) for k in ("mamba", "moe", "attention")] \
        == [6, 5, 2]
    assert cfg["serving"] == {"max_len": 8192, "prefill_len": 2048,
                              "page_size": 16, "lanes": 64}
    assert cfg["dtype"] == "bfloat16"
    assert cfg["reference"] == "nemotron_h_decoder"
    assert set(cfg["check"]) == {"logits_rel_l2", "logits_rel_l2_a_prompt",
                                 "state_rel_l2", "why"}
    assert "TO BE SET" not in cfg["check"]["why"]
    assert cfg["check"]["logits_rel_l2"] < cfg["check"][
        "logits_rel_l2_a_prompt"]


def test_the_traffic_is_the_issues_letter_for_letter():
    spec = spec_mod.Spec()
    cell = spec.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "generate-2k-8k-64-moe-closed", 1)
    assert len(cell["why"]) <= 200
    for said in ("64 lanes", "3 rows a held expert", "6", "13/52"):
        assert said in cell["why"]
    traffic = spec.traffic(cell)
    assert traffic["driver"] == "paged_closed_loop_nemotron_h"
    assert traffic["callers"] == "lanes"
    # the length distributions are generate-2k-8k-64-closed's, letter for
    # letter; only the driver and the words differ
    other = spec.traffic(spec.cell(PHI))
    for key in ("fields", "callers", "ramp_dispatches", "check_decode_steps",
                "check_prompt_lens", "why_grid"):
        assert traffic[key] == other[key], key
    assert traffic["fields"]["prompt_len"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.7,
        "grid": [256, 512, 768, 1024, 1536, 2048]}
    assert traffic["fields"]["output_len"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.8,
        "clip": [128, 6144]}
    assert traffic["ramp_dispatches"] == 8
    serving = spec.config(cell)["serving"]
    assert 2048 + 6144 == serving["max_len"]
    assert max(traffic["fields"]["prompt_len"]["grid"]) \
        == serving["prefill_len"]
    tiny = spec.traffic(cell, tiny=True)
    assert tiny["driver"] == traffic["driver"]
    assert {k: v for k, v in tiny.items() if k != "driver"} \
        == {k: v for k, v in spec.traffic(spec.cell(PHI), tiny=True).items()
            if k != "driver"}


def test_the_cell_is_a_member_of_the_lists_it_reports_and_of_no_other():
    """The cell reports what ``phi-4-mini-flash-reasoning.generate`` reports
    but that cell's own two metrics, plus the experts' two, the state
    hand-off's span and its own share; it joins none of PR 34's six lists,
    which a test pins. MEMBERSHIP only: no assertion here reads a position,
    so a later cell may follow this one."""
    doc = spec_mod.Spec().doc
    assert [c["name"] for c in doc["workloads"]].count(CELL) == 1
    assert [c["name"] for c in doc["configs"]].count(CONFIG) == 1
    assert sum(c["chips"] == 4 for c in doc["workloads"]) == 1
    metrics = {m["name"]: m for m in doc["end_to_end"] + doc["per_layer"]}
    mine = metrics["kernels.hbm_share.ssm_moe"]
    assert mine == {"name": "kernels.hbm_share.ssm_moe", "unit": "%",
                    "better": "higher", "source": "device_trace",
                    "layer": "kernels", "moves": "gen_tokens_per_s",
                    "workloads": [CELL]}
    for name in ("gen_tokens_per_s", "ttft_ms_p50", "moe.load_max_over_mean",
                 "moe.local_rows_share", "serving.admit_state_ms_p50"):
        assert CELL in metrics[name]["workloads"], name
    phis_own = {"kernels.hbm_share.yoco", "serving.admit_cross_rows_share"}
    added = {"moe.load_max_over_mean", "moe.local_rows_share",
             "kernels.hbm_share.ssm_moe"}
    pinned = {"serving.step_wait_ms_p50", "serving.step_copy_ms_p50",
              "serving.step_dispatch_ms_p50", "serving.step_between_ms_p50",
              "serving.step_gap_ms_p50", "serving.admit_wait_ms_p50"}
    for name, m in metrics.items():
        lists = m.get("workloads", [])
        assert lists.count(CELL) <= 1
        if name in added:
            continue
        if name in phis_own | pinned:
            assert CELL not in lists, name
        else:
            assert (CELL in lists) == (PHI in lists), name
    # every reader the cell's line needs is a file beside the others
    spec = spec_mod.Spec()
    for m in spec.metrics("per_layer", CELL) + spec.metrics("end_to_end",
                                                            CELL):
        kind = "end_to_end" if m in doc["end_to_end"] else "layer_metrics"
        assert os.path.isfile(os.path.join(
            spec.bench_dir, kind, m["name"] + ".py")), m["name"]


def _driver_and_config():
    spec = spec_mod.Spec()
    return spec, spec.module("drivers", "paged_closed_loop_nemotron_h"), \
        spec.config(spec.cell(CELL))


def test_the_sizes_are_the_layer_equations_at_the_published_widths():
    """The driver's ``sizes`` and ``parameters`` against the sums written
    out, and against ``param_shapes`` less the stacks' zero padding (the
    stacks are stored 1,920 wide; the benchmark counts the published 1,856)."""
    from mxnet_tpu.models.transformer import decode_cache, param_shapes

    spec, driver, cfg = _driver_and_config()
    model = cfg["model"]
    mamba = 2688 + 2688 * 10304 + 6144 * 5 + 3 * 64 + 4096 + 4096 * 2688
    attention = 2688 + 2688 * 4608 + 4096 * 2688
    around = 2688 + 128 * 2688 + 128 + 2 * 2688 * 3712
    expert, table = 2 * 2688 * 1856, 65536 * 2688
    assert (mamba, attention, around, expert) \
        == (38_744_896, 23_399_040, 20_302_592, 9_977_856)
    always, one, state, kv = driver.sizes(model)
    assert always == 6 * mamba + 2 * attention + 5 * around + table + 2688
    assert one == expert
    assert state == 6 * (64 * 64 * 128 + 3 * 6144) and kv == 2 * 2 * 2 * 128
    assert driver.parameters(model) == always + table + 5 * 64 * expert \
        == 3_926_018_560
    stored = sum(int(np.prod(s)) for s in param_shapes(**model).values())
    assert stored - driver.parameters(model) \
        == 5 * 64 * 2 * 2688 * (1920 - 1856)
    # the same state and K/V by the program's own cache
    rows = sum(int(np.prod(s)) for _, kind, s in decode_cache(**model)
               if kind == "row")
    pools = sum(int(np.prod(s)) for _, kind, s in decode_cache(**model)
                if kind == "pool")
    assert (rows, pools) == (state, kv)
    # the memory arithmetic of the deployment: 7.85 + 0.83 + 1.07 GB
    assert 2 * driver.parameters(model) == 7_852_037_120
    assert 64 * state * 4 == 833_617_920 and 64 * 8192 * kv * 2 \
        == 1_073_741_824
    small = spec.config(spec.cell(CELL), tiny=True)["model"]
    width = 128 - small["moe_ffn_dim"]
    assert sum(int(np.prod(s)) for s in param_shapes(**small).values()) \
        - driver.parameters(small) \
        == 3 * 8 * 2 * small["model_dim"] * width


def test_the_step_byte_count_is_the_layer_equations():
    """``step_bytes``: every weight outside the routed experts once a step
    and a row of the embedding a stepped lane, ONE expert's two matrices at
    1,856 for every held expert touched, six blocks' float32 rows read and
    written a stepped lane, a token's 2,048 B of K and V read a context token
    and written a stepped lane."""
    _, driver, cfg = _driver_and_config()
    model = cfg["model"]
    always, expert, state, kv = driver.sizes(model)
    # 100 steps of 64 lanes over 1,500 tokens of context each, 61 of 64 held
    # experts touched in each of 5 blocks
    steps, tokens = 100, 6400
    touched = steps * 5 * 61
    want = 2 * (steps * always + tokens * 2688 + touched * expert
                + (tokens * 1500 + tokens) * 1024) + 4 * 2 * state * tokens
    assert driver.step_bytes(model, cfg["dtype"], steps, tokens,
                             tokens * 1500, touched) == want
    # ISSUE 48's arithmetic: 6.1 GB of expert matrices, 0.2 of shared
    # experts, 0.47 Mamba, 0.09 attention, 0.35 head, 1.67 of state, 0.2 of
    # own pages at 1,500 tokens a lane: 9.1 GB a step
    assert 9.0e9 < want / steps < 9.2e9
    assert 2 * 5 * 61 * expert == pytest.approx(6.09e9, rel=1e-3)
    assert driver.step_bytes(model, "bfloat16", 0, 0, 1, 0) == 2048
    assert driver.step_bytes(model, "bfloat16", 0, 0, 0, 1) \
        == 2 * 2 * 2688 * 1856
    assert driver.step_bytes(model, "bfloat16", 0, 1, 0, 0) \
        == 2 * 2688 + 2048 + 8 * state
    assert driver.step_bytes(model, "bfloat16", 1, 0, 0, 0) == 2 * always
    # the kernel's own two calls, standing alone (PERF.md section 6)
    assert driver.expert_layer_bytes(model, 384, 204, 62) \
        == 62 * 2 * 2688 * 1856 * 2 + 384 * 2688 * 2 + 2 * 204 * 1856 * 2 \
        + 204 * 2688 * 4
    assert driver.expert_layer_flops(model, 204) == 204 * 4 * 2688 * 1856


def test_the_flop_count_is_the_layer_equations():
    """``model_flops``: a token is 2 x its matrices as ``param_shapes`` lists
    them outside the routed experts (at the published widths), 5 x 4,096 x
    128 a Mamba block's recurrence, the K taps, three of its six routed
    experts (the held half under even routing); a context token 4 x 4,096 a
    block of attention; a row of logits 2 x 2,688 x 65,536."""
    from mxnet_tpu.models.transformer import param_shapes

    _, driver, cfg = _driver_and_config()
    model = cfg["model"]
    shapes = param_shapes(**model)
    matrices = sum(int(np.prod(s)) for n, s in shapes.items()
                   if n.endswith("_weight") and "experts_" not in n
                   and "_conv_" not in n
                   and n not in ("embed_weight", "lm_head_weight"))
    token = 2 * matrices + 6 * (2 * 4 * 6144 + 5 * 4096 * 128) \
        + 5 * 3 * 2 * 2 * 2688 * 1856
    assert driver.model_flops(model, 1, 0, 0) == token
    assert driver.model_flops(model, 0, 1, 0) == 2 * 4 * 4096
    assert driver.model_flops(model, 0, 0, 1) == 2 * 2688 * 65536
    # a step of 64 lanes at 1,500 tokens: ISSUE 48's "about 90 GFLOP"
    step = driver.model_flops(model, 64, 64 * 1500, 64)
    assert 85e9 < step < 100e9
    # an admission of 2,048 tokens: about 2.3 TFLOP
    admit = driver.model_flops(model, 2048, 2048 * 2048, 1)
    assert 2.2e12 < admit < 2.6e12


def test_the_new_metric_needs_the_programs_counters_and_the_architecture():
    """Nothing to read, and no error, from a program without the counters
    (the parent commit) or a configuration of another architecture."""
    spec, driver, cfg = _driver_and_config()
    share = spec.module("layer_metrics", "kernels.hbm_share.ssm_moe")
    full = {"serving.paged_steps": 100, "serving.decode_tokens": 6400,
            "serving.step_context_tokens": 6400 * 1500,
            "serving.moe.step_experts_touched": 100 * 5 * 61}
    run = lambda **kw: SimpleNamespace(**{
        "trace_summary": {"busy_s": 1.5}, "counters_window": full,
        "peaks": {"hbm_bytes_per_s": 819e9}, "config": cfg, **kw})
    got = share.read(run())
    assert got == pytest.approx(100.0 * driver.step_bytes(
        cfg["model"], "bfloat16", 100, 6400, 6400 * 1500, 100 * 5 * 61)
        / (1.5 * 819e9))
    assert 73 < got < 75
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


def test_the_drivers_draw_leaves_the_padding_zero():
    """``make_weights``: the routed experts' stacks are the hybrid's draw at
    the published width and ZERO behind it; every other parameter is the
    hybrid's draw untouched."""
    from mxnet_tpu.models.transformer import param_shapes

    spec, driver, _ = _driver_and_config()
    cfg = spec.config(spec.cell(CELL), tiny=True)
    shapes = param_shapes(**cfg["model"])
    width = cfg["model"]["moe_ffn_dim"]
    got = driver.make_weights(shapes, cfg["init"], 7, cfg["dtype"], width)
    plain = driver._hybrid_make_weights(shapes, cfg["init"], 7, cfg["dtype"])
    assert set(got) == set(shapes)
    for name, value in got.items():
        value, drawn = np.asarray(value), np.asarray(plain[name])
        assert value.shape == tuple(shapes[name])
        if name.endswith("experts_up_weight"):
            assert value.shape[2] == 128 and not value[:, :, width:].any()
            assert np.array_equal(value[:, :, :width], drawn[:, :, :width])
            assert drawn[:, :, width:].any()
        elif name.endswith("experts_down_weight"):
            assert value.shape[1] == 128 and not value[:, width:, :].any()
            assert np.array_equal(value[:, :width, :], drawn[:, :width, :])
        else:
            assert np.array_equal(value, drawn), name


def test_the_reference_imports_nothing_of_the_program():
    """``reference/nemotron_h_decoder.py`` is plain ``jax.numpy``: its only
    imports are jax's, it sets the highest matmul precision, and its notes
    name each departure (the share, the slice, the depth, no positions)."""
    spec = spec_mod.Spec()
    path = os.path.join(spec.bench_dir, "reference", "nemotron_h_decoder.py")
    source = open(path).read()
    tree = ast.parse(source)
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported == {"jax"}
    assert "mxnet_tpu" not in source.replace("nothing from ``mxnet_tpu``", "")
    assert 'default_matmul_precision("highest")' in source
    doc = ast.get_docstring(tree)
    for said in ("the SHARE", "the SLICE", "the DEPTH", "NO positions",
                 "one position after the other", "densely"):
        assert said in doc, said
    assert "ragged_dot" not in source and "argsort" not in source


def test_the_timed_loops_lanes_are_fed_drawn_tokens():
    """``_DrawsTokens``: whatever the loop hands ``step`` (the arg-max of a
    lane's last row, the same few tokens in every lane under random weights),
    each stepped lane is fed a token drawn from the seed, inside the
    vocabulary's slice and never the padding id 0; the same seed draws the
    same tokens; everything else is the decoder's own."""
    _, driver, cfg = _driver_and_config()
    vocab = cfg["model"]["vocab_size"]

    class Decoder:
        lanes = 64

        def __init__(self):
            self.fed = []

        def step(self, tokens):
            self.fed.append(dict(tokens))
            return {seq: np.zeros(3) for seq in tokens}

    runs = []
    for seed in (7, 7, 2147483999):
        dec = Decoder()
        loop_dec = driver._DrawsTokens(dec, seed, vocab)
        assert loop_dec.lanes == 64
        for _ in range(50):
            out = loop_dec.step({seq: 11 for seq in range(64)})
            assert sorted(out) == list(range(64))
        runs.append(dec.fed)
    fed = np.array([[step[seq] for seq in range(64)] for step in runs[0]])
    assert fed.min() >= 1 and fed.max() < vocab
    assert len(np.unique(fed)) > 0.97 * fed.size     # 64 distinct tokens a step
    assert runs[0] == runs[1] and runs[0] != runs[2]
    assert issubclass(driver.Loop, driver._hybrid.Loop.__mro__[1])
    assert driver._hybrid.Loop is driver.Loop
