"""The cell ``mimo-v2-flash.generate`` rehearsed on the CPU at its tiny size:
it runs to its end and meets the contract untraced and traced, a broken
reference is reported by BOTH comparisons, the configuration holds the
published sizes, the cut and the deployment, its stated parameter count is
``param_shapes``' at the published widths, the bytes
``kernels.hbm_share.swa`` counts and the driver's FLOP are the layer
equations' arithmetic, the two new metrics give nothing where there is
nothing to read, and the check's sample and statistic are what they say."""
import json
from types import SimpleNamespace

import numpy as np
import pytest

from harness import contract, main as harness_main, spec as spec_mod

CELL = "mimo-v2-flash.generate"
PATTERN = [0, 1, 1, 1, 1, 0, 1]


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
    assert "0 of 39 rows above the limit" in line["checks"][0]
    assert line["checks"][0].endswith("ok")
    assert line["checks"][1].startswith("the first window layer's key ring")
    assert line["checks"][1].endswith("ok")
    assert "every lane retired and every page returned: ok" in line["checks"]
    assert [len(r) for r in line["notes"]["check_rows_sorted"]] \
        == [13, 13, 13]
    if not trace:
        assert set(line["metrics"]) == set(declared)
        return
    got = line["metrics"]
    # the shares of the HBM and matrix-unit peaks need a chip's peaks
    assert "kernels.hbm_share.swa" in declared
    assert "kernels.flops_share.serving" in declared
    assert "kernels.hbm_share.shortconv" not in declared
    # 8 of 32 experts held: a quarter of the assignments under even routing
    assert 0 < got["moe.local_rows_share"]["value"] < 100
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
    assert 0 < line["notes"]["held_experts_touched_a_step_and_layer"] <= 8


def test_a_broken_reference_is_reported_as_incorrect(capsys):
    """``layer0_qkv_weight`` x 1.25 moves the first layer's scores and
    values, so every row of the logits and the keys of the layer after it:
    both comparisons fail."""
    rc, _, line = _rehearse(capsys, "--break-reference")
    declared = {m["name"]: m["unit"]
                for m in spec_mod.Spec().metrics("end_to_end", CELL)}
    assert rc == 0 and contract.problems(line, declared, False) == []
    assert line["correct"] is False and line["failed"] == 0
    assert line["checks"][0].endswith("FAIL")
    assert "39 of 39 rows above the limit" in line["checks"][0]
    assert line["checks"][1].endswith("FAIL")


def test_the_configuration_holds_the_published_sizes_and_the_cut():
    """Every number of the catalog's ``config`` under the same key, the two
    published lists of 48 whole, three keys cut and listed (depth, the
    experts HELD, the vocabulary's slice) with the published counts and the
    16-chip deployment beside them, and the decoder's sizes the same
    numbers."""
    spec = spec_mod.Spec()
    cfg = spec.config(spec.cell(CELL))
    published = dict(
        attention_value_scale=0.707, hidden_size=4096,
        intermediate_size=16384, max_position_embeddings=262144,
        num_attention_heads=64, head_dim=192, num_hidden_layers=48,
        num_key_value_heads=4, layernorm_epsilon=1e-05, rope_theta=5000000,
        vocab_size=152576, partial_rotary_factor=0.334, sliding_window=128,
        swa_rope_theta=10000, v_head_dim=128, sliding_window_size=128,
        attention_chunk_size=128, moe_intermediate_size=2048,
        n_routed_experts=256, num_experts_per_tok=8, n_group=1, topk_group=1,
        swa_num_attention_heads=64, swa_num_key_value_heads=8,
        swa_head_dim=192, swa_v_head_dim=128)
    held = dict(num_hidden_layers=7, n_routed_experts=16, vocab_size=19072)
    assert cfg["reduced"] == list(held)
    for key, value in published.items():
        assert cfg[key] == held.get(key, value), key
    assert cfg["published"]["num_hidden_layers"] == 48
    assert cfg["published"]["n_routed_experts"] == 256
    assert cfg["published"]["vocab_size"] == 152576 == 8 * 19072
    assert cfg["model_type"] == "mimo_v2_flash"
    assert cfg["hidden_act"] == "silu" and cfg["scoring_func"] == "sigmoid"
    assert cfg["topk_method"] == "noaux_tc" and cfg["norm_topk_prob"] is True
    assert cfg["tie_word_embeddings"] is False
    assert cfg["attention_bias"] is False
    assert cfg["add_swa_attention_sink_bias"] is True
    assert cfg["add_full_attention_sink_bias"] is False
    assert cfg["n_shared_experts"] is None
    assert cfg["routed_scaling_factor"] is None
    # the published lists of 48, whole: a full layer first, then five window
    # layers and a full one repeating; every layer but the first of experts
    kinds, sparse = cfg["hybrid_layer_pattern"], cfg["moe_layer_freq"]
    assert len(kinds) == len(sparse) == 48
    assert [i for i, k in enumerate(kinds) if k == 0] \
        == [0] + list(range(5, 48, 6))
    assert sparse == [0] + [1] * 47
    assert len(cfg["source"]) <= 200 and cfg["source"].startswith(
        "https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/config.json")
    assert spec.configs["mimo-v2-flash"]["source"] \
        == "https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/" \
           "config.json"
    assert spec.configs["mimo-v2-flash"]["reduced"] == cfg["reduced"]
    for key in ("deployment", "reduced_why"):
        assert cfg[key] and "PLACEHOLDER" not in cfg[key]
    assert "16 chips" in cfg["deployment"]
    assert "3,429,955,392" in cfg["reduced_why"]
    for key in ("sink_bias", "expert_bias", "value_scale", "window",
                "rotation", "dtype", "serving", "init"):
        assert cfg["assumed"][key], key
    m = cfg["model"]
    assert m["hybrid_layer_pattern"] == kinds[:7] == PATTERN
    assert m["moe_layer_freq"] == sparse[:7] == [0, 1, 1, 1, 1, 1, 1]
    same = dict(vocab_size="vocab_size", num_layers="num_hidden_layers",
                num_heads="num_attention_heads",
                num_kv_heads="num_key_value_heads",
                swa_num_kv_heads="swa_num_key_value_heads",
                head_dim="head_dim", v_head_dim="v_head_dim",
                model_dim="hidden_size", ffn_dim="intermediate_size",
                moe_ffn_dim="moe_intermediate_size",
                num_local_experts="n_routed_experts",
                num_experts_per_tok="num_experts_per_tok",
                sliding_window="sliding_window", rope_theta="rope_theta",
                swa_rope_theta="swa_rope_theta",
                attention_value_scale="attention_value_scale",
                rms_eps="layernorm_epsilon", norm_topk_prob="norm_topk_prob")
    assert set(same) | {
        "arch", "num_experts", "local_expert_offset", "rotary_dim",
        "hybrid_layer_pattern", "moe_layer_freq", "routed_scaling_factor"} \
        == set(m)
    for ours, theirs in same.items():
        assert m[ours] == cfg[theirs], ours
    # the router keeps its published width; the share is experts 0..15
    assert m["num_experts"] == cfg["published"]["n_routed_experts"] == 256
    assert m["local_expert_offset"] == 0
    # 0.334 x 192 = 64.1, rounded down to an even count
    assert m["rotary_dim"] == 64 == int(
        cfg["partial_rotary_factor"] * cfg["head_dim"]) // 2 * 2
    assert cfg["serving"] == {"max_len": 8192, "prefill_len": 2048,
                              "page_size": 16, "lanes": 32}
    assert cfg["dtype"] == "bfloat16"
    assert set(cfg["check"]) == {"logits_rel_l2", "logits_rel_l2_a_prompt",
                                 "ring_keys_rel_l2", "why"}
    assert "PLACEHOLDER" not in cfg["check"]["why"]
    # the cut's arithmetic, by the program's own parameter shapes
    from mxnet_tpu.models.transformer import decode_cache, param_shapes

    count = sum(int(np.prod(s)) for s in param_shapes(**m).values())
    assert count == 290_463_744 + 5 * 498_082_112 + 492_839_168 \
        + 2 * 78_118_912 + 4_096 == 3_429_955_392
    # and the whole model's, at the published depth, experts and vocabulary:
    # the published 309B
    whole = dict(m, vocab_size=152576, num_layers=48, num_local_experts=0,
                 hybrid_layer_pattern=kinds, moe_layer_freq=sparse)
    total = sum(int(np.prod(s)) for s in param_shapes(**whole).values())
    routed = 8_192 + 1_048_832 + 256 * 25_165_824   # norms, router, experts
    assert total == 290_463_744 + 8 * (89_128_960 + routed) \
        + 39 * (94_371_904 + routed) + 2 * 624_951_296 + 4_096
    assert 308.5e9 < total < 309.5e9
    # a window layer's cache is 128 slots a lane, whatever max_len is
    rings = [s for _, kind, s in decode_cache(**m) if kind == "ring"]
    assert rings == [(8, 128, 192), (8, 128, 128)] * 5


def test_the_traffic_is_the_issues_letter_for_letter():
    spec = spec_mod.Spec()
    cell = spec.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("mimo-v2-flash", "generate-2k-8k-closed", 1)
    assert len(cell["why"]) <= 200
    traffic = spec.traffic(cell)
    assert traffic["driver"] == "paged_closed_loop_mimo"
    assert traffic["callers"] == "lanes"
    assert traffic["fields"]["prompt_len"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.7,
        "grid": [256, 512, 768, 1024, 1536, 2048]}
    assert traffic["fields"]["output_len"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.8,
        "clip": [128, 6144]}
    assert traffic["ramp_dispatches"] == 8
    assert traffic["check_prompt_lens"] == [256, 1024, 2048]
    assert traffic["check_decode_steps"] == 16
    assert set(traffic["check_prompt_lens"]) <= set(
        traffic["fields"]["prompt_len"]["grid"])
    serving = spec.config(cell)["serving"]
    # the longest prompt and the longest output fill a lane exactly
    assert 2048 + 6144 == serving["max_len"]
    assert max(traffic["fields"]["prompt_len"]["grid"]) \
        == serving["prefill_len"]


def test_the_cell_is_appended_where_lfm2s_is_and_nowhere_else():
    """The cell reports what ``lfm2-24b-a2b.generate`` reports but that
    cell's own share and PR 34's six, whose lists a test pins, plus its own
    two metrics; every list it joined has it last."""
    doc = spec_mod.Spec().doc
    assert doc["workloads"][-1]["name"] == CELL
    assert doc["configs"][-1]["name"] == "mimo-v2-flash"
    assert [m["name"] for m in doc["per_layer"][-2:]] \
        == ["kernels.hbm_share.swa", "moe.local_rows_share"]
    for m in doc["per_layer"][-2:]:
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["moves"] == "gen_tokens_per_s"
    pinned = {"serving.step_wait_ms_p50", "serving.step_copy_ms_p50",
              "serving.step_dispatch_ms_p50", "serving.step_between_ms_p50",
              "serving.step_gap_ms_p50", "serving.admit_wait_ms_p50"}
    for m in doc["end_to_end"] + doc["per_layer"][:-2]:
        lists = m.get("workloads", [])
        if m["name"] in pinned or m["name"] == "kernels.hbm_share.shortconv":
            assert CELL not in lists, m["name"]
        elif "lfm2-24b-a2b.generate" in lists:
            assert lists[-1] == CELL, m["name"]
        else:
            assert CELL not in lists, m["name"]


def test_the_step_byte_count_is_the_layer_equations():
    """The driver's ``sizes`` and ``step_bytes`` at the published widths,
    against the sums written out: attention 89,128,960 a full layer and
    94,371,904 a window layer with its 64 sinks, two layer norms 8,192, the
    dense MLP 201,326,592, a router 1,048,832 with its bias, the final norm
    and the head's slice 78,118,912; an expert 25,165,824; a token's K and V
    2 full layers x 4 heads x 320 and 5 window layers x 8 x 320."""
    spec = spec_mod.Spec()
    driver = spec.module("drivers", "paged_closed_loop_mimo")
    cfg = spec.config(spec.cell(CELL))
    always = 2 * 89_128_960 + 5 * 94_371_904 + 7 * 8_192 + 201_326_592 \
        + 6 * 1_048_832 + 4_096 + 78_118_912
    expert = 3 * 4096 * 2048
    assert driver.sizes(cfg["model"]) == (always, expert, 2 * 4 * 320,
                                          5 * 8 * 320)
    assert expert == 25_165_824
    # what a step reads whatever the router does, the 16 held experts of 6
    # layers and the embedding (a lookup, not read whole) are the model
    assert always + 6 * 16 * expert + 78_118_912 == 3_429_955_392
    # 100 steps of 32 lanes over 3,000 tokens of context each, every ring
    # full, 10 of the 16 held experts touched a layer
    steps, tokens, touched = 100, 3200, 100 * 6 * 10
    want = 2 * (steps * always + touched * expert
                + (tokens * 3000 + tokens) * 2560
                + (tokens * 128 + tokens) * 12800)
    assert driver.step_bytes(cfg["model"], cfg["dtype"], steps, tokens,
                             tokens * 3000, tokens * 128, touched) == want
    # a step with every lane busy: 1.9 GB outside the experts, 3.0 GB of
    # experts, 0.49 GB of the full layers' K and V, 0.1 GB of rings
    assert 5.4e9 < want / steps < 5.6e9
    # a token of context: K and V of 2 layers x 4 heads x 320 in bfloat16 =
    # 5,120 B; a ring's slot, 5 layers x 8 heads x 320 = 25,600 B
    assert driver.step_bytes(cfg["model"], "bfloat16", 0, 0, 1, 0, 0) == 5120
    assert driver.step_bytes(cfg["model"], "bfloat16", 0, 0, 0, 1, 0) \
        == 25600


def test_the_flop_count_is_the_layer_equations():
    """The driver's ``model_flops`` at the published widths: a token is 2 x
    its matrices (as ``param_shapes`` lists them, the held experts at the
    share even routing sends them: 8 x 16 / 256 = half an expert a layer)
    and a window of 128 keys in each of the five window layers; a full
    layer's scores and apply 2 x 64 x 320 a context token; the head 2 x
    4,096 x 19,072 a row."""
    from mxnet_tpu.models.transformer import param_shapes

    spec = spec_mod.Spec()
    driver = spec.module("drivers", "paged_closed_loop_mimo")
    model = spec.config(spec.cell(CELL))["model"]
    shapes = param_shapes(**model)
    size = lambda pick: sum(int(np.prod(s)) for n, s in shapes.items()
                            if n.endswith("_weight") and pick(n))
    experts = size(lambda n: "_experts_" in n)
    assert experts == 6 * 16 * 25_165_824
    token = size(lambda n: "_experts_" not in n and n not in (
        "embed_weight", "lm_head_weight")) + experts * 8 // 256
    head = 2 * 4096 * 19072
    window = 5 * 128 * 2 * 64 * 320
    assert driver.model_flops(model, 1, 0, 0) == 2 * token + window
    assert driver.model_flops(model, 0, 0, 1) == head
    assert driver.model_flops(model, 0, 1, 0) == 2 * 2 * 64 * 320
    # a step of 32 lanes at 3,000 tokens of context: 1.9 G a token in the
    # matrices, 0.25 G in the full layers' reads, 0.16 G in the head
    step = driver.model_flops(model, 32, 32 * 3000, 32)
    assert step == 32 * (2 * token + window + head) \
        + 32 * 3000 * 2 * 2 * 64 * 320
    assert 2.2e9 < step / 32 < 2.4e9


def test_the_new_metrics_need_the_programs_counters_and_the_architecture():
    """Nothing to read, and no error, from a program without the counters
    (the parent commit) or a configuration of another architecture."""
    spec = spec_mod.Spec()
    share = spec.module("layer_metrics", "kernels.hbm_share.swa")
    local = spec.module("layer_metrics", "moe.local_rows_share")
    driver = spec.module("drivers", "paged_closed_loop_mimo")
    cfg = spec.config(spec.cell(CELL))
    full = {"serving.paged_steps": 100, "serving.decode_tokens": 3200,
            "serving.step_context_tokens": 3200 * 3000,
            "serving.step_window_slots": 3200 * 128,
            "serving.moe.step_experts_touched": 100 * 6 * 10,
            "serving.moe.step_assignments": 100 * 6 * 256,
            "serving.moe.step_local_assignments": 100 * 6 * 16}
    run = lambda **kw: SimpleNamespace(**{
        "trace_summary": {"busy_s": 2.0}, "counters_window": full,
        "peaks": {"hbm_bytes_per_s": 819e9}, "config": cfg, **kw})
    got = share.read(run())
    assert got == pytest.approx(100.0 * driver.step_bytes(
        cfg["model"], "bfloat16", 100, 3200, 3200 * 3000, 3200 * 128,
        100 * 6 * 10) / (2.0 * 819e9))
    assert 30 < got < 40
    assert local.read(run()) == pytest.approx(6.25)
    for gone in ("serving.step_context_tokens", "serving.step_window_slots",
                 "serving.moe.step_experts_touched", "serving.paged_steps"):
        old = {k: v for k, v in full.items() if k != gone}
        assert share.read(run(counters_window=old)) is None
    for gone in ("serving.moe.step_assignments",
                 "serving.moe.step_local_assignments"):
        old = {k: v for k, v in full.items() if k != gone}
        assert local.read(run(counters_window=old)) is None
    for reader in (share, local):
        assert reader.read(run(counters_window=None)) is None
    assert share.read(run(peaks=None)) is None
    assert share.read(run(trace_summary=None)) is None
    for other in ("transformer-base.generate", "olmoe-1b-7b.score",
                  "granite-4.0-h-micro.generate", "lfm2-24b-a2b.generate",
                  "kanana-2-30b-a3b.generate"):
        assert share.read(run(config=spec.config(spec.cell(other)))) is None


def test_a_ring_is_compared_at_the_positions_it_holds():
    """``ring_error``: position p at slot p mod W, the last W positions once
    the ring is full and the first ``upto + 1`` while it fills; what a slot
    past them holds is not compared, a key at a wrong slot is."""
    driver = spec_mod.Spec().module("drivers", "paged_closed_loop_mimo")
    rs = np.random.RandomState(0)
    keys = rs.randn(2, 30, 6)
    ring = np.zeros((2, 8, 6))
    for p in range(22 - 7, 22 + 1):
        ring[:, p % 8] = keys[:, p]
    assert driver.ring_error(ring, keys, 22) == 0.0
    assert driver.ring_error(ring, keys, 23) > 0.3      # one slot stale
    young = np.full((2, 8, 6), 99.0)
    young[:, :3] = keys[:, :3]
    assert driver.ring_error(young, keys, 2) == 0.0
    assert driver.ring_error(np.roll(ring, 1, axis=1), keys, 22) > 1.0
    check = spec_mod.Spec().config(spec_mod.Spec().cell(CELL))["check"]
    assert check["logits_rel_l2"] < check["logits_rel_l2_a_prompt"]


def test_the_sample_feeds_drawn_tokens_and_keeps_the_ring_twice():
    """``sample_program`` admits each of ``check_prompt_lens`` and feeds the
    tokens it drew with the prompt; the keeper hands the first window
    layer's key ring as the admission left it and as the last step did."""
    driver = spec_mod.Spec().module("drivers", "paged_closed_loop_mimo")

    class Dec:
        _cache = [("kv_k_0", "pool", (1, 4)), ("ring_k_1", "ring", (1, 2, 4)),
                  ("ring_v_1", "ring", (1, 2, 4))]

        def __init__(self):
            self.fed, self.ring, self.retired = {}, {}, []

        def admit(self, prompt):
            seq = len(self.fed)
            self.fed[seq] = [float(t) for t in prompt]
            self.ring[seq] = np.full((1, 2, 4), len(prompt), "f")
            return seq, np.zeros(50, "f")      # arg-max 0, never fed

        def step(self, feed):
            (seq, tok), = feed.items()
            self.fed[seq].append(tok)
            self.ring[seq] = self.ring[seq] + 1
            return {seq: np.zeros(50, "f")}

        def lane_state(self, seq, names):
            assert names == ("ring_k_1",)
            return {"ring_k_1": self.ring[seq]}

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
    assert [(a[0, 0, 0], b[0, 0, 0]) for a, b in keeper.states] \
        == [(2.0, 5.0), (5.0, 8.0)]
    assert all(a.dtype == np.float32 for a, _ in keeper.states)
