"""The cell ``dots3-note-prev.generate`` rehearsed on the CPU at its tiny
size: it runs to its end and meets the contract untraced and traced, a broken
reference is reported, the configuration holds every published number of the
catalog's row with the three cuts it states, ``param_shapes`` sums to the held
count the file states, the driver's ``sizes``, ``step_bytes`` and FLOP are the
layer equations' arithmetic, the two new metrics give nothing where there is
nothing to read, and the reference imports nothing of the program. The cell's
place in ``BENCHMARK.json`` is held by MEMBERSHIP, never by position: the next
cell appended behind it breaks nothing here."""
import ast
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from harness import contract, main as harness_main, spec as spec_mod

CELL = "dots3-note-prev.generate"
CONFIG = "dots3-note-prev"
SOURCE = "https://huggingface.co/dots-studio/dots3-note-prev/blob/main/" \
         "config.json"
NEMOTRON = "nemotron-3-nano-30b-a3b.generate"
OTHERS = ("transformer-base.generate", "olmoe-1b-7b.score",
          "granite-4.0-h-micro.generate", "kanana-2-30b-a3b.generate",
          "lfm2-24b-a2b.generate", "mimo-v2-flash.generate",
          "phi-4-mini-flash-reasoning.generate", NEMOTRON, "resnet50.train")
FULL, WINDOW = "full_attention", "sliding_attention"
LAYER_TYPES = [FULL, FULL] + ([WINDOW] * 3 + [FULL]) * 11


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
    assert "admit and 12 decode steps at prompt lengths [16, 24, 32]" \
        in line["checks"][0]
    assert "5-th smallest of all 39 rows" in line["checks"][0]
    assert line["checks"][1].startswith("the first full layer's selected "
                                        "positions of each sampled lane")
    assert "1.0000, 1.0000, 1.0000" in line["checks"][1]
    assert line["checks"][2].startswith("the first window layer's ring of "
                                        "each sampled lane")
    for check in line["checks"][:3]:
        assert check.endswith("ok")
    assert "every lane retired and every page returned: ok" in line["checks"]
    assert [len(r) for r in line["notes"]["check_rows_sorted"]] \
        == [13, 13, 13]
    if not trace:
        assert set(line["metrics"]) == set(declared)
        return
    got = line["metrics"]
    # the share of the HBM peak needs a chip's peaks
    assert "kernels.hbm_share.dsa" in declared
    for absent in ("kernels.hbm_share.swa", "kernels.hbm_share.mla",
                   "kernels.hbm_share.ssm_moe",
                   "serving.admit_cross_rows_share"):
        assert absent not in declared
    # a selection of 8 over contexts of 16 to 52: the read keeps a part
    assert 10 < got["serving.sparse_selected_share"]["value"] < 60
    assert line["notes"]["selected_share_of_scored"] == pytest.approx(
        got["serving.sparse_selected_share"]["value"] / 100)
    # 8 of 32 experts held: about a quarter of a step's assignments local
    assert 10 < got["moe.local_rows_share"]["value"] < 50
    assert got["moe.load_max_over_mean"]["value"] >= 1
    assert got["serving.admit_state_ms_p50"]["value"] > 0
    for phase in ("stage", "prefill", "logits", "scatter"):
        assert got["serving.admit_%s_ms_p50" % phase]["value"] > 0
    for name in ("serving.admit_ms_p50", "serving.step_ms_p50",
                 "serving.step_stage_ms_p50", "serving.step_read_ms_p50",
                 "serving.step_commit_ms_p50", "serving.itl_ms_p95",
                 "serving.device_gap_share"):
        assert got[name]["value"] > 0
    assert got["process.compiles_in_window.serving"]["value"] == 0
    assert got["graph.retraces_in_window.serving"]["value"] == 0
    # five expert layers of eight held experts: a step touches some of each
    assert 0 < line["notes"]["held_experts_touched_a_step_and_layer"] <= 8


def test_a_broken_reference_is_reported_as_incorrect(capsys):
    """``layer0_kvb_weight`` x 1.25 moves layer 0's keys and values and so
    every row of the logits and the ring two layers on; layer 0's SELECTION
    reads neither and still agrees: two holds fail, one is enough."""
    rc, _, line = _rehearse(capsys, "--break-reference")
    declared = {m["name"]: m["unit"]
                for m in spec_mod.Spec().metrics("end_to_end", CELL)}
    assert rc == 0 and contract.problems(line, declared, False) == []
    assert line["correct"] is False and line["failed"] == 0
    assert line["checks"][0].endswith("FAIL")
    assert line["checks"][1].endswith("ok")
    assert line["checks"][2].endswith("FAIL")


def test_the_configuration_holds_the_published_numbers_and_states_its_cut():
    """Every key of the catalog's ``config`` under the same key with the same
    value, but the three the file lists as ``reduced``, whose published
    values stand beside them; every width as published; the deployment
    (16 chips a layer, 8 stages of 6) stated; the decoder's sizes the same
    numbers."""
    spec = spec_mod.Spec()
    cfg = spec.config(spec.cell(CELL))
    published = dict(
        apply_mla_qkv_lora_rescale=True, attention_bias=False,
        attention_gate_type="headwise", first_k_dense_replace=1,
        hidden_act="silu", hidden_size=5120, index_head_dim=128,
        index_n_heads=64, index_topk=2048, intermediate_size=13824,
        kv_lora_rank=512, layer_types=LAYER_TYPES,
        max_position_embeddings=524288, model_type="dots3_note",
        moe_intermediate_size=1536, moe_layer_freq=1, n_routed_experts=256,
        n_shared_experts=1, norm_topk_prob=True, num_attention_heads=128,
        num_experts_per_tok=8, num_hidden_layers=46, num_key_value_heads=128,
        q_lora_rank=1024, qk_nope_head_dim=128, qk_rope_head_dim=64,
        rms_norm_eps=1e-05, rope_scaling=None, rope_theta=80000000,
        routed_scaling_factor=1, scoring_func="sigmoid",
        sliding_window_size=513, swa_attention_gate_type="headwise",
        swa_kv_lora_rank=1024, swa_num_attention_heads=64,
        swa_num_key_value_heads=64, swa_q_lora_rank=1024,
        swa_qk_nope_head_dim=192, swa_qk_rope_head_dim=64,
        swa_rope_theta=50000, swa_v_head_dim=128, tie_word_embeddings=False,
        topk_method="noaux_tc", v_head_dim=128, vocab_size=152064)
    cut = dict(num_hidden_layers=6, n_routed_experts=16, vocab_size=19008)
    for key, value in published.items():
        want = cut.get(key, value)
        assert cfg[key] == want and type(cfg[key]) is type(want), key
    assert cfg["reduced"] == list(cut) == spec.configs[CONFIG]["reduced"]
    assert {k: cfg["published"][k] for k in cut} \
        == {k: published[k] for k in cut}
    assert not any("dim" in k or "rank" in k or "size" in k
                   and k != "vocab_size" for k in cfg["reduced"])
    assert cfg["source"].startswith(SOURCE + " model_type dots3_note")
    assert spec.configs[CONFIG]["source"] == cfg["source"]
    for text in (cfg["source"], spec.configs[CONFIG]["why"]):
        assert len(text) <= 200
    for said in ("v5e-128", "16 chips share each layer", "8 pipeline stages",
                 "stage 0", "3,123,656,192", "6.25 GB"):
        assert said in cfg["deployment"], said
    for said in ("46 -> 6", "256 -> 16", "152,064 -> 19,008",
                 "144,055,040", "90,840,064", "279,551,726,592",
                 "3,123,656,192"):
        assert said in cfg["reduced_why"], said
    for key in ("lora_rescale", "gate", "indexer", "window", "rotation",
                "router", "expert_bias", "dtype", "serving", "init",
                "layout"):
        assert cfg["assumed"][key], key
    m = cfg["model"]
    same = dict(model_dim="hidden_size", ffn_dim="intermediate_size",
                moe_ffn_dim="moe_intermediate_size",
                num_heads="num_attention_heads",
                num_experts_per_tok="num_experts_per_tok",
                num_shared_experts="n_shared_experts",
                first_dense_layers="first_k_dense_replace",
                q_lora_rank="q_lora_rank", kv_lora_rank="kv_lora_rank",
                qk_nope_head_dim="qk_nope_head_dim",
                qk_rope_head_dim="qk_rope_head_dim",
                v_head_dim="v_head_dim", rope_theta="rope_theta",
                swa_num_heads="swa_num_attention_heads",
                swa_q_lora_rank="swa_q_lora_rank",
                swa_kv_lora_rank="swa_kv_lora_rank",
                swa_qk_nope_head_dim="swa_qk_nope_head_dim",
                swa_qk_rope_head_dim="swa_qk_rope_head_dim",
                swa_v_head_dim="swa_v_head_dim",
                swa_rope_theta="swa_rope_theta",
                sliding_window="sliding_window_size",
                index_n_heads="index_n_heads",
                index_head_dim="index_head_dim", index_topk="index_topk",
                lora_rescale="apply_mla_qkv_lora_rescale",
                rms_eps="rms_norm_eps",
                routed_scaling_factor="routed_scaling_factor",
                norm_topk_prob="norm_topk_prob")
    assert set(same) | {"arch", "vocab_size", "num_layers", "layer_types",
                        "num_experts", "num_local_experts",
                        "local_expert_offset"} == set(m)
    for ours, theirs in same.items():
        assert m[ours] == cfg[theirs], ours
    assert (m["arch"], m["num_layers"], m["num_local_experts"],
            m["local_expert_offset"], m["vocab_size"]) \
        == ("dots3_note", cfg["num_hidden_layers"], cfg["n_routed_experts"],
            0, cfg["vocab_size"])
    assert m["num_experts"] == 256 == cfg["published"]["n_routed_experts"]
    assert m["layer_types"] == LAYER_TYPES[:6]
    assert m["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["serving"] == {"max_len": 10240, "prefill_len": 8192,
                              "page_size": 16, "lanes": 32}
    assert cfg["dtype"] == "bfloat16"
    assert cfg["reference"] == "dots3_note_decoder"
    assert set(cfg["check"]) == {"logits_rel_l2", "logits_rel_l2_median",
                                 "logits_rel_l2_a_prompt",
                                 "selected_agreement", "ring_rows_rel_l2",
                                 "why"}
    assert "PLACEHOLDER" not in cfg["check"]["why"]
    assert cfg["check"]["logits_rel_l2"] < cfg["check"][
        "logits_rel_l2_a_prompt"] < cfg["check"]["logits_rel_l2_median"]
    assert 0.5 < cfg["check"]["selected_agreement"] < 1


def test_the_traffic_is_the_issues_letter_for_letter():
    spec = spec_mod.Spec()
    cell = spec.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "generate-8k-512-closed", 1)
    assert len(cell["why"]) <= 200
    for said in ("32 lanes", "1 row a held expert", "16", "6/46"):
        assert said in cell["why"]
    traffic = spec.traffic(cell)
    assert traffic["driver"] == "paged_closed_loop_dots3"
    assert traffic["callers"] == "lanes"
    assert traffic["fields"]["prompt_len"] == {
        "dist": "lognormal", "median": 6144, "sigma": 0.3,
        "grid": [4096, 5120, 6144, 7168, 8192]}
    assert traffic["fields"]["output_len"] == {
        "dist": "lognormal", "median": 384, "sigma": 0.8, "clip": [64, 2048]}
    assert traffic["ramp_dispatches"] == 8
    assert traffic["check_decode_steps"] == 16
    assert traffic["check_prompt_lens"] == [4096, 6144, 8192]
    serving = spec.config(cell)["serving"]
    assert 8192 + 2048 == serving["max_len"]
    assert max(traffic["fields"]["prompt_len"]["grid"]) \
        == serving["prefill_len"]
    tiny, small = spec.traffic(cell, tiny=True), spec.config(cell, tiny=True)
    assert tiny["driver"] == traffic["driver"]
    assert max(tiny["fields"]["prompt_len"]["grid"]) \
        == small["serving"]["prefill_len"]
    # the tiny selection BITES: every sampled prompt is longer than it
    assert min(tiny["check_prompt_lens"]) > small["model"]["index_topk"]


def _issued(driver, traffic, seed, issues):
    """(the first round's (output length, stagger share) by caller, the
    output lengths of the next ``issues`` issues by callers taken in an order
    of the seed's, the prompt lengths of the first round) as the driver's
    ``Loop`` hands them out."""
    from harness import traffic as traffic_mod

    n = traffic["callers"]
    run = SimpleNamespace(seed=seed, traffic=traffic, config={"model": {}})
    loop = driver.Loop(run, None, traffic_mod.callers(traffic, seed, 19008),
                       1)
    share = traffic_mod.strata(seed, "stagger", 0, n)
    for caller in loop.callers:
        loop.issue(caller, 0.0)
    first = [(r.want, share[r.caller.index]) for r in loop.waiting]
    prompts = [len(r.prompt) for r in loop.waiting]
    loop.waiting.clear()
    for who in np.random.default_rng(seed).integers(0, n, size=issues):
        loop.issue(loop.callers[who], 0.0)
    return first, [r.want for r in loop.waiting], prompts


def test_every_seed_is_offered_the_same_work_in_the_same_order():
    """The generator is the harness's, untouched (callers, prompt lengths,
    tokens, stagger); the driver's ``Loop`` hands the round's OUTPUT lengths
    out by the order of issue, so what a window shorter than a round holds
    is not the seed's luck: the pairs of (length, stagger share) the window
    opens on and the lengths issued after them are the same whatever the
    seed and whichever caller asks; a round is the generator's set; any
    aligned run of 2^k issues holds one length from each 2^k-th of it."""
    from harness import traffic as traffic_mod

    spec = spec_mod.Spec()
    driver = spec.module("drivers", "paged_closed_loop_dots3")
    assert driver._hybrid.traffic_mod is traffic_mod
    assert driver.traffic is traffic_mod
    assert driver._hybrid.Loop is driver.Loop
    source = open(driver.__file__).read()
    assert "traffic_mod" not in source and "_rng" not in source
    assert list(driver.issue_order(8)) == [0, 4, 2, 6, 1, 5, 3, 7]
    assert sorted(driver.issue_order(12)) == list(range(12))
    traffic = dict(spec.traffic(spec.cell(CELL)), callers=32)
    field = traffic["fields"]["output_len"]
    whole = sorted(traffic_mod.quantile(field, (i + 0.5) / 32)
                   for i in range(32))
    assert whole == sorted(c.next_request()["output_len"] for c in
                           traffic_mod.callers(traffic, 2147483777, 19008))
    a, b = (_issued(driver, traffic, seed, 64)
            for seed in (2147483777, 2468013579))
    assert sorted(a[0]) == sorted(b[0]) and a[0] != b[0]
    assert sorted(w for w, _ in a[0]) == whole
    assert a[1] == b[1] and sorted(a[1][:32]) == sorted(a[1][32:]) == whole
    for width in (2, 4, 8, 16):
        part = 32 // width
        for start in range(0, 32, width):
            ranks = sorted(whole.index(w) // part
                           for w in a[1][start:start + width])
            assert ranks == list(range(width))
    # the prompts stay the seed's: one set, another order
    assert sorted(a[2]) == sorted(b[2]) and a[2] != b[2]


def test_the_cell_is_a_member_of_the_lists_it_reports_and_of_no_other():
    """The cell reports what ``nemotron-3-nano-30b-a3b.generate`` reports but
    that cell's own share, plus its own two; it joins none of the lists a
    test pins. MEMBERSHIP only: no assertion here reads a position, so a
    later cell may follow this one."""
    doc = spec_mod.Spec().doc
    assert [c["name"] for c in doc["workloads"]].count(CELL) == 1
    assert [c["name"] for c in doc["configs"]].count(CONFIG) == 1
    assert sum(c["chips"] == 4 for c in doc["workloads"]) == 1
    metrics = {m["name"]: m for m in doc["end_to_end"] + doc["per_layer"]}
    assert metrics["kernels.hbm_share.dsa"] == {
        "name": "kernels.hbm_share.dsa", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "gen_tokens_per_s", "workloads": [CELL]}
    assert metrics["serving.sparse_selected_share"] == {
        "name": "serving.sparse_selected_share", "unit": "%",
        "better": "lower", "source": "program_counter", "layer": "serving",
        "moves": "gen_tokens_per_s", "workloads": [CELL]}
    mine = {"kernels.hbm_share.dsa", "serving.sparse_selected_share"}
    for name, m in metrics.items():
        lists = m.get("workloads", [])
        assert lists.count(CELL) <= 1
        if name in mine:
            continue
        if name == "kernels.hbm_share.ssm_moe":
            assert CELL not in lists
        else:
            assert (CELL in lists) == (NEMOTRON in lists), name
    spec = spec_mod.Spec()
    for m in spec.metrics("per_layer", CELL) + spec.metrics("end_to_end",
                                                            CELL):
        kind = "end_to_end" if m in doc["end_to_end"] else "layer_metrics"
        assert os.path.isfile(os.path.join(
            spec.bench_dir, kind, m["name"] + ".py")), m["name"]


def _driver_and_config():
    spec = spec_mod.Spec()
    return spec, spec.module("drivers", "paged_closed_loop_dots3"), \
        spec.config(spec.cell(CELL))


def test_param_shapes_sums_to_the_held_count_the_file_states():
    """ISSUE 52's table by ``param_shapes``: a full layer's attention
    144.06 M, a window layer's 90.85 M, one expert 23.59 M, the dense MLP
    212.34 M, the embedding at 1/8 97.32 M; held 3,123.7 M = 6.25 GB; whole
    279.55 B; and the driver's ``sizes`` is the same arithmetic."""
    from mxnet_tpu.models.transformer import decode_cache, param_shapes

    _, driver, cfg = _driver_and_config()
    model = cfg["model"]
    count = lambda shapes, keep: sum(
        int(np.prod(s)) for n, s in shapes.items() if keep(n))
    held = param_shapes(**model)
    outside = ("mlp_", "router", "experts", "shared", "ln2")
    attention = lambda i: count(held, lambda n: n.startswith(
        "layer%d_" % i) and not any(t in n for t in outside))
    assert attention(0) == attention(1) == attention(5) \
        == 144_055_040              # with the layer norm in front of it
    assert attention(2) == attention(3) == 90_840_064
    assert count(held, lambda n: "layer0_mlp_" in n) == 212_336_640
    assert count(held, lambda n: n.startswith("layer1_experts")) \
        == 16 * 23_592_960
    assert count(held, lambda n: n == "embed_weight") == 97_320_960
    layers = [count(held, lambda n: n.startswith("layer%d_" % i))
              for i in range(6)]
    assert layers == [356_396_800, 546_451_456, 493_236_480, 493_236_480,
                      493_236_480, 546_451_456]
    total = count(held, lambda n: True)
    assert total == 3_123_656_192 and 2 * total == 6_247_312_384
    whole = param_shapes(**dict(
        model, vocab_size=152064, num_layers=46, layer_types=LAYER_TYPES,
        num_local_experts=0))
    assert count(whole, lambda n: True) == 279_551_726_592
    # the driver counts the same: everything outside the held experts' stacks
    always, expert, latent, index, ring = driver.sizes(model)
    assert expert == 23_592_960
    assert always == total - 5 * 16 * expert - 97_320_960   # the embedding
    assert (latent, index, ring) == (3 * 576, 3 * 128, 3 * 1088)
    cache = decode_cache(**model)
    assert [k for _, k, _ in cache] == ["pool", "pool", "row"] * 2 \
        + ["ring"] * 3 + ["pool", "pool", "row"]
    assert sum(int(np.prod(s)) for _, k, s in cache if k == "pool") \
        == latent + index
    # the cache's bytes at 32 lanes x 10,240 slots: 1.38 + 0.11 GB
    assert 32 * 10240 * (latent + index) * 2 == 1_384_120_320
    assert 32 * 513 * ring * 2 == 107_163_648


def test_the_step_byte_count_is_the_layer_equations():
    """``step_bytes`` against a hand count: every weight outside the routed
    experts once a step, ONE expert's three matrices a held expert touched,
    an index-key row of 128 a SCORED slot, a latent row of 576 a SELECTED
    slot only, a ring row of 1,088 a live slot of each of three window
    layers, and the rows a stepped lane writes."""
    _, driver, cfg = _driver_and_config()
    model = cfg["model"]
    always, expert, latent, index, ring = driver.sizes(model)
    # 100 steps of 32 lanes at 7,000 tokens of context each: 3 full layers
    # score 7,000 and select 2,048 a lane, 3 window layers find 513 live,
    # 10 of 16 held experts touched in each of 5 expert layers
    steps, tokens = 100, 3200
    scored, chosen = 3 * tokens * 7000, 3 * tokens * 2048
    window, touched = tokens * 513, steps * 5 * 10
    want = 2 * (steps * always + touched * expert + scored * 128
                + chosen * 576 + window * 3 * 1088
                + tokens * (3 * 576 + 3 * 128 + 3 * 1088))
    assert driver.step_bytes(model, cfg["dtype"], steps, tokens, scored,
                             chosen, window, touched) == want
    # ISSUE 52's arithmetic: about 4.6 GB of weights a step (10 of 16 held
    # experts a layer), 57 MB of index keys and 75 MB of selected rows a
    # full layer, 36 MB a ring
    assert 4.4e9 < 2 * (always + 50 * expert) < 4.8e9
    assert 2 * 32 * 7000 * 128 == pytest.approx(57e6, rel=0.02)
    assert 2 * 32 * 2048 * 576 == pytest.approx(75e6, rel=0.02)
    assert 2 * 32 * 513 * 1088 == pytest.approx(36e6, rel=0.02)
    assert driver.step_bytes(model, "bfloat16", 1, 0, 0, 0, 0, 0) \
        == 2 * always
    assert driver.step_bytes(model, "bfloat16", 0, 0, 1, 0, 0, 0) == 2 * 128
    assert driver.step_bytes(model, "bfloat16", 0, 0, 0, 1, 0, 0) == 2 * 576
    assert driver.step_bytes(model, "bfloat16", 0, 0, 0, 0, 1, 0) \
        == 2 * 3 * 1088
    assert driver.step_bytes(model, "bfloat16", 0, 0, 0, 0, 0, 1) \
        == 2 * 3 * 5120 * 1536
    assert driver.step_bytes(model, "bfloat16", 0, 1, 0, 0, 0, 0) \
        == 2 * (3 * 704 + 3 * 1088)


def test_the_flop_count_is_the_layer_equations():
    """``model_flops``: a token is 2 x its matrices as ``param_shapes`` lists
    them outside the routed experts, half an expert a layer (8 x 16 / 256
    under even routing) and a band of 513 keys a window layer; a context
    token an index logit of 64 heads of 128 a full layer and, up to 2,048 a
    token, a score and an apply of 128 heads; a row of logits 2 x 5,120 x
    19,008."""
    from mxnet_tpu.models.transformer import param_shapes

    _, driver, cfg = _driver_and_config()
    model = cfg["model"]
    shapes = param_shapes(**model)
    matrices = sum(int(np.prod(s)) for n, s in shapes.items()
                   if n.endswith("_weight") and "experts_" not in n
                   and n not in ("embed_weight", "lm_head_weight"))
    token = 2 * matrices + 5 * 2 * 3 * 5120 * 1536 * 8 * 16 // 256 \
        + 3 * 513 * 2 * 64 * (192 + 64 + 128)
    assert driver.model_flops(model, 1, 0, 0) == token
    assert driver.model_flops(model, 0, 1, 0) == 3 * 2 * 64 * 128
    assert driver.model_flops(model, 1, 1, 0) - token \
        == 3 * (2 * 64 * 128 + 2 * 128 * (128 + 64 + 128))
    assert driver.model_flops(model, 1, 10 ** 6, 0) - token \
        == 3 * (10 ** 6 * 2 * 64 * 128 + 2048 * 2 * 128 * 320)
    assert driver.model_flops(model, 0, 0, 1) == 2 * 5120 * 19008
    # an admission of 8,192 tokens: ISSUE 52's "about 30 TFLOP"
    admit = driver.model_flops(model, 8192, 8192 * 8192, 1)
    assert 25e12 < admit < 35e12


def test_the_new_metrics_need_the_programs_counters_and_the_architecture():
    """Nothing to read, and no error, from a program without the counters
    (the parent commit) or a configuration of another architecture."""
    spec, driver, cfg = _driver_and_config()
    share = spec.module("layer_metrics", "kernels.hbm_share.dsa")
    kept = spec.module("layer_metrics", "serving.sparse_selected_share")
    full = {"serving.paged_steps": 100, "serving.decode_tokens": 3200,
            "serving.sparse.step_scored_slots": 3 * 3200 * 7000,
            "serving.sparse.step_selected_slots": 3 * 3200 * 2048,
            "serving.step_window_slots": 3200 * 513,
            "serving.moe.step_experts_touched": 100 * 5 * 10}
    run = lambda **kw: SimpleNamespace(**{
        "trace_summary": {"busy_s": 2.5}, "counters_window": full,
        "peaks": {"hbm_bytes_per_s": 819e9}, "config": cfg, **kw})
    got = share.read(run())
    assert got == pytest.approx(100.0 * driver.step_bytes(
        cfg["model"], "bfloat16", 100, 3200, 3 * 3200 * 7000,
        3 * 3200 * 2048, 3200 * 513, 100 * 5 * 10) / (2.5 * 819e9))
    assert 20 < got < 30
    assert kept.read(run()) == pytest.approx(100 * 2048 / 7000)
    for gone in full:
        if gone == "serving.decode_tokens":
            continue
        old = {k: v for k, v in full.items() if k != gone}
        assert share.read(run(counters_window=old)) is None, gone
    for reader in (share, kept):
        assert reader.read(run(counters_window=None)) is None
        assert reader.read(run(counters_window={})) is None
    assert share.read(run(peaks=None)) is None
    assert share.read(run(trace_summary=None)) is None
    for other in OTHERS:
        config = spec.config(spec.cell(other))
        assert share.read(run(config=config)) is None, other


def test_the_selection_agreement_counts_what_both_chose():
    _, driver, _ = _driver_and_config()
    allowed = np.zeros(20, bool)
    allowed[[1, 3, 5, 7]] = True
    assert driver.selection_agreement(np.array([1., 3., 5., 7.]), allowed) == 1
    assert driver.selection_agreement(np.array([1., 3., 5., 8.]), allowed) \
        == 0.75
    assert driver.selection_agreement(np.array([1., 3., -1., -1.]), allowed) \
        == 0.5


def test_the_reference_imports_nothing_of_the_program():
    """``reference/dots3_note_decoder.py`` is plain ``jax.numpy``: its only
    imports are jax's, it sets the highest matmul precision, takes its
    selection from ``jax.lax.top_k``, and its notes name each departure."""
    spec = spec_mod.Spec()
    path = os.path.join(spec.bench_dir, "reference", "dots3_note_decoder.py")
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
    assert "jax.lax.top_k" in source
    doc = ast.get_docstring(tree)
    for said in ("apply_mla_qkv_lora_rescale", "headwise", "no Hadamard",
                 "no float8", "513 keys with the token itself",
                 "n_group = topk_group = 1", "towers", "HELD"):
        assert said in doc, said
    assert "ragged_dot" not in source and "pallas" not in source
