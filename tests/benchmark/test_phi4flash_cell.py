"""The cell ``phi-4-mini-flash-reasoning.generate`` rehearsed on the CPU at its
tiny size: it runs to its end and meets the contract untraced and traced, a
broken reference is reported, the configuration holds the published sizes
whole (``reduced: []``) with every assumed size beside them, its stated
parameter count is ``param_shapes``' at the published widths, the bytes
``kernels.hbm_share.yoco`` counts and the driver's FLOP are the layer
equations' arithmetic, the two new metrics give nothing where there is
nothing to read, and the state's comparison is by groups of channels. The
cell's place in ``BENCHMARK.json`` is held by MEMBERSHIP, never by position:
the next cell appended behind it breaks nothing here."""
import json
from types import SimpleNamespace

import numpy as np
import pytest

from harness import contract, main as harness_main, spec as spec_mod

CELL = "phi-4-mini-flash-reasoning.generate"
CONFIG = "phi-4-mini-flash-reasoning"
SOURCE = "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/" \
         "blob/main/config.json"
OTHERS = ("transformer-base.generate", "transformer-base.score",
          "olmoe-1b-7b.score", "granite-4.0-h-micro.generate",
          "kanana-2-30b-a3b.generate", "lfm2-24b-a2b.generate",
          "mimo-v2-flash.generate", "resnet50.train")


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
    rc, out, line = _rehearse(capsys, "--seed", "3000000019",
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
    # prompts below, at and above the window of 8; 12 steps cross its wrap
    assert "admit and 12 decode steps at prompt lengths [4, 8, 16]" \
        in line["checks"][0]
    assert "the worst of 39 rows" in line["checks"][0]
    assert line["checks"][0].endswith("ok")
    assert line["checks"][1].startswith("the first layer's state of each "
                                        "sampled lane after its last step")
    assert "worst group of 128 channels" in line["checks"][1]
    assert line["checks"][1].endswith("ok")
    assert "every lane retired and every page returned: ok" in line["checks"]
    assert [len(r) for r in line["notes"]["check_rows_sorted"]] \
        == [13, 13, 13]
    if not trace:
        assert set(line["metrics"]) == set(declared)
        return
    got = line["metrics"]
    # the shares of the HBM and matrix-unit peaks need a chip's peaks
    assert "kernels.hbm_share.yoco" in declared
    assert "kernels.flops_share.serving" in declared
    for absent in ("kernels.hbm_share.swa", "moe.load_max_over_mean",
                   "moe.local_rows_share", "kernels.hbm_share.ssm"):
        assert absent not in declared
    # the tiny bucket is 16 rows: one row of sixteen
    assert got["serving.admit_cross_rows_share"]["value"] == 100.0 / 16
    assert got["serving.admit_state_ms_p50"]["value"] > 0
    for phase in ("stage", "prefill", "logits", "scatter"):
        assert got["serving.admit_%s_ms_p50" % phase]["value"] > 0
    for name in ("serving.admit_ms_p50", "serving.step_ms_p50",
                 "serving.step_stage_ms_p50", "serving.step_read_ms_p50",
                 "serving.step_commit_ms_p50", "serving.itl_ms_p95"):
        assert got[name]["value"] > 0
    assert got["process.compiles_in_window.serving"]["value"] == 0
    assert got["graph.retraces_in_window.serving"]["value"] == 0


def test_a_broken_reference_is_reported_as_incorrect(capsys):
    """``layer0_mamba1_out_weight`` x 1.25 moves the first mixer's output and
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


def test_the_configuration_holds_the_published_sizes_whole():
    """Every number of the catalog's ``config`` under the same key, nothing
    reduced, every size the source does not give under ``assumed`` with its
    origin, and the decoder's sizes the same numbers."""
    spec = spec_mod.Spec()
    cfg = spec.config(spec.cell(CELL))
    published = dict(
        embd_pdrop=0, hidden_act="silu", hidden_size=2560,
        intermediate_size=10240, layer_norm_eps=1e-05,
        max_position_embeddings=262144, mb_per_layer=2,
        model_type="phi4flash", num_attention_heads=40, num_hidden_layers=32,
        num_key_value_heads=20, resid_pdrop=0, sliding_window=512,
        tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False,
        vocab_size=200064)
    for key, value in published.items():
        assert cfg[key] == value and type(cfg[key]) is type(value), key
    assert cfg["reduced"] == [] == spec.configs[CONFIG]["reduced"]
    assert cfg["source"].startswith(SOURCE)
    assert spec.configs[CONFIG]["source"] == SOURCE
    assert len(spec.configs[CONFIG]["why"]) <= 200
    for key in ("deployment", "parameters"):
        assert cfg[key] and "PLACEHOLDER" not in cfg[key]
    assert "3,852,562,944" in cfg["parameters"]
    assert "11.95 GB" in cfg["deployment"]
    for key in ("mixer_by_depth", "mamba", "attention", "window",
                "positions", "dtype", "layout", "serving", "lanes", "init"):
        assert cfg["assumed"][key], key
    m = cfg["model"]
    same = dict(vocab_size="vocab_size", num_layers="num_hidden_layers",
                num_heads="num_attention_heads",
                num_kv_heads="num_key_value_heads", model_dim="hidden_size",
                ffn_dim="intermediate_size", sliding_window="sliding_window",
                mb_per_layer="mb_per_layer")
    assert set(same) | {"arch", "head_dim", "mamba_state", "mamba_conv",
                        "mamba_expand", "mamba_dt_rank"} == set(m)
    for ours, theirs in same.items():
        assert m[ours] == cfg[theirs], ours
    assert m["arch"] == "phi4flash"
    assert m["head_dim"] == 64 == cfg["hidden_size"] \
        // cfg["num_attention_heads"]
    assert (m["mamba_state"], m["mamba_conv"], m["mamba_expand"]) \
        == (16, 4, 2)
    assert m["mamba_dt_rank"] == 160 == -(-cfg["hidden_size"] // 16)
    assert cfg["serving"] == {"max_len": 8192, "prefill_len": 2048,
                              "page_size": 16, "lanes": 64}
    assert cfg["dtype"] == "bfloat16"
    assert cfg["reference"] == "phi4_flash_decoder"
    assert set(cfg["check"]) == {"logits_rel_l2", "state_rel_l2", "why"}
    assert "PLACEHOLDER" not in cfg["check"]["why"]
    from mxnet_tpu.models.transformer import decode_cache, param_shapes

    count = sum(int(np.prod(s)) for s in param_shapes(**m).values())
    assert count == 3_852_562_944 == 512_163_840 + 2_516_582_400 \
        + 9 * 41_241_600 + 9 * 19_668_864 + 7 * 13_112_704 \
        + 7 * 26_214_400 + 332_800
    # the memory arithmetic of the deployment, by the program's own cache
    lanes, slots = 64, 64 * 8192
    held = {"pool": 0, "ring": 0, "row": 0}
    for _, kind, shape in decode_cache(**m):
        held[kind] += int(np.prod(shape)) * (
            2 * slots if kind == "pool" else 2 * lanes if kind == "ring"
            else 4 * lanes)
    assert held == {"pool": slots * 5120, "ring": 1_342_177_280,
                    "row": 64 * 3_502_080}
    assert 11.94e9 < 2 * count + sum(held.values()) < 11.96e9


def test_the_traffic_is_the_issues_letter_for_letter():
    spec = spec_mod.Spec()
    cell = spec.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "generate-2k-8k-64-closed", 1)
    assert len(cell["why"]) <= 200
    for said in ("64 lanes", "eight times"):
        assert said in cell["why"]
    traffic = spec.traffic(cell)
    assert traffic["driver"] == "paged_closed_loop_phi4flash"
    assert traffic["callers"] == "lanes"
    # the length distributions are generate-2k-8k-closed's, letter for letter
    other = spec.traffic(spec.cell("mimo-v2-flash.generate"))
    assert traffic["fields"] == other["fields"]
    assert traffic["fields"]["prompt_len"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.7,
        "grid": [256, 512, 768, 1024, 1536, 2048]}
    assert traffic["fields"]["output_len"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.8,
        "clip": [128, 6144]}
    assert traffic["ramp_dispatches"] == 8
    assert traffic["check_prompt_lens"] == [256, 512, 2048]
    assert traffic["check_decode_steps"] == 32
    assert set(traffic["check_prompt_lens"]) <= set(
        traffic["fields"]["prompt_len"]["grid"])
    serving = spec.config(cell)["serving"]
    # the longest prompt and the longest output fill a lane exactly
    assert 2048 + 6144 == serving["max_len"]
    assert max(traffic["fields"]["prompt_len"]["grid"]) \
        == serving["prefill_len"]
    # a prompt of one window and 32 steps: a ring overwrites its slot 0
    assert 512 == spec.config(cell)["model"]["sliding_window"]


def test_the_cell_is_a_member_of_the_lists_it_reports_and_of_no_other():
    """The cell reports what ``mimo-v2-flash.generate`` reports but that
    cell's own share and the two metrics of experts, plus its own two; it
    joins none of PR 34's six lists, which a test pins. MEMBERSHIP only: no
    assertion here reads a position, so a later cell may follow this one."""
    doc = spec_mod.Spec().doc
    assert [c["name"] for c in doc["workloads"]].count(CELL) == 1
    assert [c["name"] for c in doc["configs"]].count(CONFIG) == 1
    metrics = {m["name"]: m for m in doc["end_to_end"] + doc["per_layer"]}
    for name, moves, better, source in (
            ("kernels.hbm_share.yoco", "gen_tokens_per_s", "higher",
             "device_trace"),
            ("serving.admit_cross_rows_share", "ttft_ms_p50", "lower",
             "program_counter")):
        m = metrics[name]
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert (m["moves"], m["better"], m["source"]) \
            == (moves, better, source)
        assert m["layer"] == name.split(".")[0]
    excluded = {"kernels.hbm_share.swa", "moe.load_max_over_mean",
                "moe.local_rows_share"}
    pinned = {"serving.step_wait_ms_p50", "serving.step_copy_ms_p50",
              "serving.step_dispatch_ms_p50", "serving.step_between_ms_p50",
              "serving.step_gap_ms_p50", "serving.admit_wait_ms_p50"}
    for name, m in metrics.items():
        lists = m.get("workloads", [])
        if name in ("kernels.hbm_share.yoco",
                    "serving.admit_cross_rows_share"):
            continue
        if name in excluded | pinned:
            assert CELL not in lists, name
        else:
            assert (CELL in lists) == ("mimo-v2-flash.generate" in lists), \
                name
        assert lists.count(CELL) <= 1


def test_the_step_byte_count_is_the_layer_equations():
    """The driver's ``parameters`` and ``step_bytes`` at the published
    widths, against the sums written out: every weight once a step, a
    token's K and V 10 pairs of heads x 128 x 2 = 5,120 B in the ONE pool
    read by EIGHT layers and written by one, the same row in each of eight
    rings, and a lane's nine float32 states and column sets read and
    written."""
    from mxnet_tpu.models.transformer import param_shapes

    spec = spec_mod.Spec()
    driver = spec.module("drivers", "paged_closed_loop_phi4flash")
    cfg = spec.config(spec.cell(CELL))
    model = cfg["model"]
    assert driver.parameters(model) == 3_852_562_944 == sum(
        int(np.prod(s)) for s in param_shapes(**model).values())
    small = spec.config(spec.cell(CELL), tiny=True)["model"]
    assert driver.parameters(small) == sum(
        int(np.prod(s)) for s in param_shapes(**small).values())
    # 100 steps of 64 lanes over 1,500 tokens of context each, rings full
    steps, tokens = 100, 6400
    state = 9 * (16 + 3) * 5120
    want = 2 * (steps * 3_852_562_944
                + 2560 * (8 * tokens * 1500 + tokens)
                + 2560 * 8 * (tokens * 512 + tokens)) \
        + 4 * 2 * state * tokens
    assert driver.step_bytes(model, cfg["dtype"], steps, tokens,
                             tokens * 1500, tokens * 512) == want
    # a step with every lane busy: 7.7 GB of weights, 3.9 GB of the one
    # pool read eight times, 1.3 GB of rings, 0.45 GB of rows: ISSUE 45's
    # 13.4 GB
    assert 13.3e9 < want / steps < 13.5e9
    # a token of context costs 5,120 B eight times; a ring's slot 5,120 B
    # in each of eight layers; a stepped lane writes both and moves 7 MB of
    # state
    assert driver.step_bytes(model, "bfloat16", 0, 0, 1, 0) == 8 * 5120
    assert driver.step_bytes(model, "bfloat16", 0, 0, 0, 1) == 8 * 5120
    assert driver.step_bytes(model, "bfloat16", 0, 1, 0, 0) \
        == 9 * 5120 + 8 * state


def test_the_flop_count_is_the_layer_equations():
    """The driver's ``step_flops`` and ``admission_flops`` at the published
    widths: a token is 2 x its matrices (as ``param_shapes`` lists them),
    5 x 5,120 x 16 a Mamba layer's recurrence and a window of 512 keys in
    each of the eight window layers; a read of differential attention takes
    6 x 40 x 64 a key, in each of the eight layers that read the pool; an
    admission runs the cross-decoder on one row."""
    from mxnet_tpu.models.transformer import param_shapes

    spec = spec_mod.Spec()
    driver = spec.module("drivers", "paged_closed_loop_phi4flash")
    model = spec.config(spec.cell(CELL))["model"]
    shapes = param_shapes(**model)
    size = lambda pick: sum(int(np.prod(s)) for n, s in shapes.items()
                            if n.endswith("_weight") and pick(n))
    self_layer = lambda n: int(n.split("_")[0][5:]) <= 16
    every = size(lambda n: n != "embed_weight")
    head = 2 * 2560 * 200064
    recurrence, window, read = 5 * 5120 * 16, 512 * 6 * 40 * 64, 6 * 40 * 64
    assert driver.step_flops(model, 1, 0) \
        == 2 * every + 9 * recurrence + 8 * window + head
    assert driver.step_flops(model, 0, 1) == 8 * read
    # a step of 64 lanes at 1,500 tokens of context: 6.7 G a token in the
    # matrices, 1.0 G in the head, 0.06 in the rings, 0.18 in the pool
    step = driver.step_flops(model, 64, 64 * 1500)
    assert 7.9e9 < step / 64 < 8.1e9
    # an admission of 2,048 tokens: the self-decoder's 17 layers and layer
    # 17's keys and values at every token, everything else at one
    below = size(lambda n: n != "embed_weight" and self_layer(n))
    kv17 = 2 * 20 * 64 * 2560
    admit = driver.admission_flops(model, 2048)
    assert admit == 2048 * (2 * (below + kv17) + 9 * recurrence + 8 * window) \
        + 2 * (every - below - kv17) + 2048 * 8 * read + head
    # 7.7 T by the matrices (ISSUE 45), 0.2 T of window scores and scans
    assert 7.8e12 < admit < 8.0e12
    assert 2 * 2048 * (below + kv17) == pytest.approx(7.67e12, rel=5e-3)
    # all 32 layers over the bucket would be 13.7 T: 44% skipped
    assert 2 * 2048 * every == pytest.approx(13.68e12, rel=5e-3)


def test_the_new_metrics_need_the_programs_counters_and_the_architecture():
    """Nothing to read, and no error, from a program without the counters
    (the parent commit) or a configuration of another architecture."""
    spec = spec_mod.Spec()
    share = spec.module("layer_metrics", "kernels.hbm_share.yoco")
    rows = spec.module("layer_metrics", "serving.admit_cross_rows_share")
    driver = spec.module("drivers", "paged_closed_loop_phi4flash")
    cfg = spec.config(spec.cell(CELL))
    full = {"serving.paged_steps": 100, "serving.decode_tokens": 6400,
            "serving.step_context_tokens": 6400 * 1500,
            "serving.step_window_slots": 6400 * 512,
            "serving.admit_self_rows": 40 * 2048,
            "serving.admit_cross_rows": 40}
    run = lambda **kw: SimpleNamespace(**{
        "trace_summary": {"busy_s": 2.0}, "counters_window": full,
        "peaks": {"hbm_bytes_per_s": 819e9}, "config": cfg, **kw})
    got = share.read(run())
    assert got == pytest.approx(100.0 * driver.step_bytes(
        cfg["model"], "bfloat16", 100, 6400, 6400 * 1500, 6400 * 512)
        / (2.0 * 819e9))
    assert 80 < got < 83
    assert rows.read(run()) == pytest.approx(100.0 / 2048)
    assert round(rows.read(run()), 3) == 0.049
    # a later change that stops skipping the cross-decoder reads 100%
    assert rows.read(run(counters_window=dict(
        full, **{"serving.admit_cross_rows": 40 * 2048}))) == 100.0
    for gone in ("serving.step_context_tokens", "serving.step_window_slots",
                 "serving.paged_steps"):
        old = {k: v for k, v in full.items() if k != gone}
        assert share.read(run(counters_window=old)) is None
    for gone in ("serving.admit_self_rows", "serving.admit_cross_rows"):
        old = {k: v for k, v in full.items() if k != gone}
        assert rows.read(run(counters_window=old)) is None
    for reader in (share, rows):
        assert reader.read(run(counters_window=None)) is None
        assert reader.read(run(counters_window={})) is None
    assert share.read(run(peaks=None)) is None
    assert share.read(run(trace_summary=None)) is None
    # every other configuration: its program counts no admission rows, and
    # its architecture is not this one's
    theirs = {k: v for k, v in full.items() if "admit_" not in k}
    for other in OTHERS:
        config = spec.config(spec.cell(other))
        assert share.read(run(config=config)) is None, other
        assert rows.read(run(config=config, counters_window=theirs)) \
            is None, other


def test_the_state_is_compared_by_groups_of_channels():
    """``state_error``: the program keeps (N, E), the reference (E, N); a
    fault in ONE group of 128 channels reads as that group's own relative
    error, however large the other groups' norm."""
    driver = spec_mod.Spec().module("drivers", "paged_closed_loop_phi4flash")
    rs = np.random.RandomState(0)
    want = rs.randn(512, 16)
    want[:128] *= 1e-3          # a group of fast channels: a small state
    assert driver.state_error(want.T, want) == 0.0
    got = want.copy()
    got[:128] *= 1.5
    assert driver.state_error(got.T, want) == pytest.approx(0.5)
    whole = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert whole < 1e-3         # what the whole tensor's norm would say
    got = want.copy()
    got[300, 7] += 1.0
    assert 0 < driver.state_error(got.T, want) < 0.1
    assert driver.state_error(np.swapaxes(want.reshape(512, 16), 0, 1)[::-1],
                              want) > 0.5   # a state read state-minor
