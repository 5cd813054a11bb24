"""The cell ``lfm2-24b-a2b.generate`` rehearsed on the CPU at its tiny size:
it runs to its end and meets the contract untraced and traced, a broken
reference is reported by BOTH comparisons, the configuration holds the
published sizes and the cut's parameter count, the bytes
``kernels.hbm_share.shortconv`` counts and the driver's FLOP are the layer
equations' arithmetic, the metric gives nothing where there is nothing to
read, and the check's sample and statistic are what they say."""
import json
from types import SimpleNamespace

import numpy as np
import pytest

from harness import contract, main as harness_main, spec as spec_mod

CELL = "lfm2-24b-a2b.generate"
KINDS = ["conv", "conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv", "conv", "conv"]


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
    assert "admit and 5 decode steps at prompt lengths [4, 8, 16]" \
        in line["checks"][0]
    assert "0 of 18 rows above the limit" in line["checks"][0]
    assert line["checks"][0].endswith("ok")
    assert line["checks"][1].startswith("the first layer's convolution row")
    assert line["checks"][1].endswith("ok")
    assert [len(r) for r in line["notes"]["check_rows_sorted"]] == [6, 6, 6]
    if not trace:
        assert set(line["metrics"]) == set(declared)
        return
    got = line["metrics"]
    # the shares of the HBM and matrix-unit peaks need a chip's peaks
    assert "kernels.hbm_share.shortconv" in declared
    assert "kernels.flops_share.serving" in declared
    assert "kernels.hbm_share.mla" not in declared
    assert got["moe.load_max_over_mean"]["value"] >= 1.0
    assert got["serving.admit_state_ms_p50"]["value"] > 0
    for phase in ("stage", "prefill", "logits", "scatter"):
        assert got["serving.admit_%s_ms_p50" % phase]["value"] > 0
    for name in ("serving.admit_ms_p50", "serving.step_ms_p50",
                 "serving.step_stage_ms_p50", "serving.step_read_ms_p50",
                 "serving.step_commit_ms_p50", "serving.itl_ms_p95"):
        assert got[name]["value"] > 0
    assert got["process.compiles_in_window.serving"]["value"] == 0
    assert got["graph.retraces_in_window.serving"]["value"] == 0
    assert 1 <= line["notes"]["experts_touched_a_step_and_layer"] <= 8


def test_a_broken_reference_is_reported_as_incorrect(capsys):
    """``layer0_conv_in_weight`` x 1.25 moves every row of the logits and,
    squared, the first layer's columns: both comparisons fail."""
    rc, _, line = _rehearse(capsys, "--break-reference")
    declared = {m["name"]: m["unit"]
                for m in spec_mod.Spec().metrics("end_to_end", CELL)}
    assert rc == 0 and contract.problems(line, declared, False) == []
    assert line["correct"] is False and line["failed"] == 0
    assert line["checks"][0].endswith("FAIL")
    assert "18 of 18 rows above the limit" in line["checks"][0]
    assert line["checks"][1].endswith("FAIL")


def test_the_configuration_holds_the_published_sizes():
    """Every number of the catalog's ``config`` under the same key, the
    nested groups whole, the depth alone cut to the published list's first
    ten layers, and the decoder's sizes the same numbers."""
    spec = spec_mod.Spec()
    cfg = spec.config(spec.cell(CELL))
    published = dict(
        conv_L_cache=3, hidden_size=2048, intermediate_size=11776,
        max_position_embeddings=128000, moe_intermediate_size=1536,
        norm_eps=1e-05, num_attention_heads=32, num_dense_layers=2,
        num_experts=64, num_experts_per_tok=4, num_hidden_layers=40,
        num_key_value_heads=8, routed_scaling_factor=1, vocab_size=65536)
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key, value in published.items():
        assert cfg[key] == (10 if key == "num_hidden_layers" else value), key
    assert cfg["model_type"] == "lfm2_moe"
    assert cfg["conv_bias"] is False and cfg["use_expert_bias"] is True
    assert cfg["norm_topk_prob"] is True
    assert cfg["rope_parameters"] == {"rope_theta": 1000000,
                                      "rope_type": "default"}
    # the published list of 40, whole: conv, conv, attention, then conv x 3,
    # attention repeating, the last layer conv
    kinds = cfg["layer_types"]
    assert len(kinds) == 40 and kinds.count("full_attention") == 10
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] \
        == list(range(2, 40, 4))
    assert len(cfg["source"]) <= 200 and "lfm2_moe" in cfg["source"]
    for key in ("deployment", "reduced_why", "assumed"):
        assert cfg[key]
    assert "5,267,090,176" in cfg["reduced_why"]
    m = cfg["model"]
    assert m["layer_types"] == kinds[:10] == KINDS
    same = dict(vocab_size="vocab_size", num_layers="num_hidden_layers",
                num_heads="num_attention_heads",
                num_kv_heads="num_key_value_heads", model_dim="hidden_size",
                ffn_dim="intermediate_size",
                moe_ffn_dim="moe_intermediate_size",
                num_experts="num_experts",
                num_experts_per_tok="num_experts_per_tok",
                first_dense_layers="num_dense_layers",
                conv_kernel="conv_L_cache", rms_eps="norm_eps",
                routed_scaling_factor="routed_scaling_factor",
                norm_topk_prob="norm_topk_prob")
    assert set(same) | {"arch", "head_dim", "layer_types", "rope_theta"} \
        == set(m)
    for ours, theirs in same.items():
        assert m[ours] == cfg[theirs], ours
    assert m["head_dim"] * m["num_heads"] == m["model_dim"]
    assert m["rope_theta"] == cfg["rope_parameters"]["rope_theta"]
    assert cfg["serving"] == {"max_len": 2048, "prefill_len": 1024,
                              "page_size": 16, "lanes": 64}
    assert cfg["dtype"] == "bfloat16"
    assert set(cfg["check"]) == {"logits_rel_l2", "logits_rel_l2_a_prompt",
                                 "conv_state_max_err", "why"}
    # the cut's arithmetic, by the program's own parameter shapes
    from mxnet_tpu.models.transformer import param_shapes

    count = sum(int(np.prod(s)) for s in param_shapes(**m).values())
    assert count == 178_278_400 + 2 * 10_489_984 + 6 * 16_787_456 \
        + 8 * 604_110_912 + 134_219_776 == 5_267_090_176


def test_the_traffic_is_the_issues_letter_for_letter():
    spec = spec_mod.Spec()
    cell = spec.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("lfm2-24b-a2b", "generate-1k-64-closed", 1)
    traffic = spec.traffic(cell)
    assert traffic["driver"] == "paged_closed_loop_lfm2"
    assert traffic["callers"] == "lanes"
    # the prompts of the other sparse-expert decode cell, letter for letter
    kanana = spec.traffic(spec.cell("kanana-2-30b-a3b.generate"))
    assert traffic["fields"]["prompt_len"] == kanana["fields"]["prompt_len"] \
        == {"dist": "lognormal", "median": 384, "sigma": 0.7,
            "grid": [128, 192, 256, 384, 512, 768, 1024]}
    assert traffic["fields"]["output_len"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.6, "clip": [64, 768]}
    assert traffic["ramp_dispatches"] == 8
    assert traffic["check_prompt_lens"] == [128, 512, 1024]
    assert traffic["check_decode_steps"] == 16
    assert set(traffic["check_prompt_lens"]) <= set(
        traffic["fields"]["prompt_len"]["grid"])


def test_the_step_byte_count_is_the_layer_equations():
    """``kernels.hbm_share.shortconv``'s byte function at the published
    widths, against the sums written out: a conv mixer 16,783,360
    parameters, an attention mixer 10,485,888 with its two head norms, two
    layer norms 4,096 a layer, a dense MLP 72,351,744, a router 131,136 with
    its bias, the tied table 134,217,728 once with the final norm's 2,048; an
    expert 9,437,184; a token's K and V 2 layers x 2 x 512; a lane's rows
    8 layers x 2 x 2,048."""
    spec = spec_mod.Spec()
    reader = spec.module("layer_metrics", "kernels.hbm_share.shortconv")
    cfg = spec.config(spec.cell(CELL))
    always = 8 * 16_783_360 + 2 * 10_485_888 + 10 * 4_096 \
        + 2 * 72_351_744 + 8 * 131_136 + 2_048 + 134_217_728
    expert = 3 * 2048 * 1536
    assert reader.sizes(cfg["model"]) == (always, expert, 2 * 2 * 512,
                                          8 * 2 * 2048)
    assert always == 435_251_968 and expert == 9_437_184
    # what a step reads whatever the router does and all 64 experts of 8
    # layers are the model: the table is read once (the head), not twice
    assert always + 8 * 64 * expert == 5_267_090_176
    # 100 steps of 64 lanes over 600 tokens of context each, 48 experts
    # touched a layer
    steps, tokens, touched = 100, 6400, 100 * 8 * 48
    want = 2 * (steps * always + touched * expert
                + (tokens * 600 + tokens) * 2048) \
        + 4 * 2 * tokens * 8 * 2 * 2048
    assert reader.step_bytes(cfg["model"], cfg["dtype"], steps, tokens,
                             tokens * 600, touched) == want
    # a step with every lane busy: 0.87 GB outside the experts, 7.2 GB of
    # experts, 0.16 GB of K and V, 17 MB of rows
    assert 8.2e9 < want / steps < 8.4e9
    # a token of context: K and V of 2 layers x 8 heads x 64 in bfloat16 =
    # 4,096 B (ISSUE 36 wrote 8,192: its own 0.54 GB of pools is 131,072
    # slots x 4,096 B)
    assert reader.step_bytes(cfg["model"], "bfloat16", 0, 0, 1, 0) == 4096


def test_the_flop_count_is_the_layer_equations():
    """The driver's ``model_flops`` at the published widths: a token is 2 x
    its matrices (as ``param_shapes`` lists them, 4 of the 64 experts, the
    taps among them) and a gate's multiply for each of the two gates of the
    8 conv mixers; attention 2 x 2 x 2,048 a context token and attention
    layer; the head 2 x 2,048 x 65,536 a row."""
    from mxnet_tpu.models.transformer import param_shapes

    spec = spec_mod.Spec()
    driver = spec.module("drivers", "paged_closed_loop_lfm2")
    model = spec.config(spec.cell(CELL))["model"]
    shapes = param_shapes(**model)
    size = lambda pick: sum(int(np.prod(s)) for n, s in shapes.items()
                            if n.endswith("_weight") and pick(n))
    experts = size(lambda n: "_experts_" in n)
    token = size(lambda n: "_experts_" not in n and n != "embed_weight") \
        + experts * 4 // 64
    assert experts == 8 * 64 * 9_437_184
    head = 2 * 2048 * 65536
    assert driver.model_flops(model, 1, 0, 0) == 2 * token + 8 * 2 * 2048
    assert driver.model_flops(model, 0, 0, 1) == head
    assert driver.model_flops(model, 0, 1, 0) == 2 * 2 * 2 * 2048
    # a step of 64 lanes at 600 tokens of context: 1.22 G a token outside
    # attention's reads and the head
    step = driver.model_flops(model, 64, 64 * 600, 64)
    assert step == 64 * (2 * token + 8 * 2 * 2048 + head) \
        + 64 * 600 * 2 * 2 * 2 * 2048
    assert 1.4e9 < step / 64 < 1.5e9
    # an admission of 384 real tokens: its own rows, one row of logits
    assert driver.model_flops(model, 384, 384 * 384, 1) == \
        384 * (2 * token + 8 * 2 * 2048) + 384 * 384 * 8 * 2048 + head


def test_the_share_needs_the_programs_counters_and_the_architecture():
    """Nothing to read, and no error, from a program without the counters
    (the parent commit) or a configuration of another architecture."""
    spec = spec_mod.Spec()
    reader = spec.module("layer_metrics", "kernels.hbm_share.shortconv")
    cfg = spec.config(spec.cell(CELL))
    full = {"serving.paged_steps": 100, "serving.decode_tokens": 6400,
            "serving.step_context_tokens": 6400 * 600,
            "serving.moe.step_experts_touched": 100 * 8 * 48}
    run = lambda **kw: SimpleNamespace(**{
        "trace_summary": {"busy_s": 3.4}, "counters_window": full,
        "peaks": {"hbm_bytes_per_s": 819e9}, "config": cfg, **kw})
    share = reader.read(run())
    assert share == pytest.approx(100.0 * reader.step_bytes(
        cfg["model"], "bfloat16", 100, 6400, 6400 * 600, 100 * 8 * 48)
        / (3.4 * 819e9))
    assert 25 < share < 35
    for gone in ("serving.step_context_tokens",
                 "serving.moe.step_experts_touched", "serving.paged_steps"):
        old = {k: v for k, v in full.items() if k != gone}
        assert reader.read(run(counters_window=old)) is None
    assert reader.read(run(counters_window=None)) is None
    assert reader.read(run(peaks=None)) is None
    assert reader.read(run(trace_summary=None)) is None
    for other in ("transformer-base.generate", "olmoe-1b-7b.score",
                  "granite-4.0-h-micro.generate",
                  "kanana-2-30b-a3b.generate"):
        assert reader.read(run(config=spec.config(spec.cell(other)))) is None


def test_the_rows_are_held_by_rank_and_never_by_their_worst():
    """``kth_smallest``: of all 51 rows the fifth smallest, so forty-six rows
    whose experts flipped leave it where it was, a fault in every row moves
    it and so does one that spares only the three admissions' rows; of a
    prompt's 17 the second smallest; the worst feature of a kept row is read
    over the columns' rms."""
    driver = spec_mod.Spec().module("drivers", "paged_closed_loop_lfm2")
    assert (driver.POOLED, driver.A_PROMPT) == (5, 2)
    sound = [1.0e-2 + 1e-5 * i for i in range(51)]
    assert driver.kth_smallest(sound, 5) == pytest.approx(1.004e-2)
    flipped = np.asarray(sound[:5] + [0.1] * 46).reshape(3, 17)
    assert driver.kth_smallest(flipped[::-1], 5) == pytest.approx(1.004e-2)
    assert driver.kth_smallest(sound[:3] + [0.1] * 48, 5) == 0.1
    assert driver.kth_smallest([0.02 + x for x in sound], 5) > 0.03
    assert driver.kth_smallest([0.5], 5) == 0.5
    assert driver.kth_smallest([0.3, 0.2, 0.1], 2) == 0.2
    want = np.asarray([[3.0, -4.0], [0.0, 5.0]])
    got = want + np.asarray([[0.0, 0.1], [-0.5, 0.0]])
    assert driver.column_error(got, want) == pytest.approx(
        0.5 / np.sqrt(50.0 / 4))
    assert driver.column_error(want, want) == 0.0
    check = spec_mod.Spec().config(spec_mod.Spec().cell(CELL))["check"]
    assert check["logits_rel_l2"] < check["logits_rel_l2_a_prompt"] \
        < check["conv_state_max_err"]


def test_the_sample_feeds_drawn_tokens_and_keeps_the_row_twice():
    """``sample_program`` admits each of ``check_prompt_lens`` and feeds the
    tokens it drew with the prompt, whatever the rows' arg-max; the keeper
    hands the first layer's row as the admission left it and as the last
    step did."""
    driver = spec_mod.Spec().module("drivers", "paged_closed_loop_lfm2")

    class Dec:
        def __init__(self):
            self.fed, self.row, self.retired = {}, {}, []

        def admit(self, prompt):
            seq = len(self.fed)
            self.fed[seq] = [float(t) for t in prompt]
            self.row[seq] = np.full((2, 4), len(prompt), "f")
            return seq, np.zeros(50, "f")      # arg-max 0, never fed

        def step(self, feed):
            (seq, tok), = feed.items()
            self.fed[seq].append(tok)
            self.row[seq] = self.row[seq] + 1
            return {seq: np.zeros(50, "f")}

        def lane_state(self, seq, names):
            assert names == ("conv_state_0",)
            return {"conv_state_0": self.row[seq]}

        def retire(self, seq):
            self.retired.append(seq)

    run = SimpleNamespace(seed=7, config={"model": {"vocab_size": 50}},
                          traffic={"check_decode_steps": 3,
                                   "check_prompt_lens": [2, 5]})
    keeper = driver._KeepsState(Dec())
    sampled = driver.sample_program(run, keeper)
    assert [len(t) for t, _ in sampled] == [5, 8]
    assert [g.shape for _, g in sampled] == [(4, 50), (4, 50)]
    for seq, (toks, _) in enumerate(sampled):
        assert keeper._dec.fed[seq] == [float(t) for t in toks]
        assert toks.min() >= 1 and len(set(toks[-3:])) > 1
    assert keeper._dec.retired == [0, 1]
    assert [(a[0, 0], b[0, 0]) for a, b in keeper.states] \
        == [(2.0, 5.0), (5.0, 8.0)]
    again = driver.sample_program(run, driver._KeepsState(Dec()))
    assert all(np.array_equal(a[0], b[0]) for a, b in zip(sampled, again))
