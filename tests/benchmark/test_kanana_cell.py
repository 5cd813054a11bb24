"""The cell ``kanana-2-30b-a3b.generate`` rehearsed on the CPU at its tiny
size: it runs to its end and meets the contract untraced and traced, a broken
reference is reported, the bytes ``kernels.hbm_share.mla`` counts and the
driver's FLOP are the layer equations' arithmetic, the metric gives nothing
where there is nothing to read, and the check's statistic is what it says."""
import json

import numpy as np
import pytest

from harness import contract, main as harness_main, spec as spec_mod

CELL = "kanana-2-30b-a3b.generate"


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
    if not trace:
        assert set(line["metrics"]) == set(declared)
        return
    got = line["metrics"]
    # the shares of the HBM and matrix-unit peaks need a chip's peaks; the
    # compiler's byte count is not this cell's (it counts the whole pool)
    assert "kernels.hbm_share.mla" in declared
    assert "kernels.flops_share.serving" in declared
    assert "kernels.hbm_share.serving" not in declared
    assert got["moe.load_max_over_mean"]["value"] >= 1.0
    for phase in ("stage", "prefill", "logits", "scatter"):
        assert got["serving.admit_%s_ms_p50" % phase]["value"] > 0
    for name in ("serving.admit_ms_p50", "serving.step_ms_p50",
                 "serving.step_stage_ms_p50", "serving.step_read_ms_p50",
                 "serving.step_commit_ms_p50", "serving.itl_ms_p95"):
        assert got[name]["value"] > 0
    assert got["process.compiles_in_window.serving"]["value"] == 0
    assert got["graph.retraces_in_window.serving"]["value"] == 0


def test_a_broken_reference_is_reported_as_incorrect(capsys):
    rc, _, line = _rehearse(capsys, "--break-reference")
    declared = {m["name"]: m["unit"]
                for m in spec_mod.Spec().metrics("end_to_end", CELL)}
    assert rc == 0 and contract.problems(line, declared, False) == []
    assert line["correct"] is False and line["failed"] == 0
    assert line["checks"][0].endswith("FAIL")
    assert "18 of 18 rows above the limit" in line["checks"][0]


def test_the_configuration_holds_the_published_sizes():
    """Every number of the catalog's ``config`` under the same key, the depth
    alone cut, and the decoder's sizes the same numbers."""
    spec = spec_mod.Spec()
    cfg = spec.config(spec.cell(CELL))
    published = dict(
        first_k_dense_replace=1, head_dim=64, hidden_size=2048,
        intermediate_size=6144, kv_lora_rank=512,
        max_position_embeddings=32768, moe_intermediate_size=768,
        moe_layer_freq=1, n_group=1, n_routed_experts=128, n_shared_experts=2,
        num_attention_heads=32, num_experts_per_tok=6, num_hidden_layers=48,
        num_key_value_heads=32, qk_head_dim=192, qk_nope_head_dim=128,
        qk_rope_head_dim=64, rms_norm_eps=1e-06, rope_theta=1000000,
        routed_scaling_factor=2.448, topk_group=1, v_head_dim=128,
        vocab_size=128256)
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key, value in published.items():
        assert cfg[key] == (8 if key == "num_hidden_layers" else value), key
    assert (cfg["model_type"], cfg["scoring_func"], cfg["topk_method"]) \
        == ("deepseek_v3", "sigmoid", "noaux_tc")
    assert cfg["q_lora_rank"] is None and cfg["rope_interleave"] is True
    assert cfg["norm_topk_prob"] is True
    m = cfg["model"]
    same = dict(vocab_size="vocab_size", num_layers="num_hidden_layers",
                num_heads="num_attention_heads", model_dim="hidden_size",
                ffn_dim="intermediate_size",
                moe_ffn_dim="moe_intermediate_size",
                num_experts="n_routed_experts",
                num_experts_per_tok="num_experts_per_tok",
                num_shared_experts="n_shared_experts",
                first_dense_layers="first_k_dense_replace",
                qk_nope_head_dim="qk_nope_head_dim",
                qk_rope_head_dim="qk_rope_head_dim",
                v_head_dim="v_head_dim", kv_lora_rank="kv_lora_rank",
                rope_theta="rope_theta", rms_eps="rms_norm_eps",
                routed_scaling_factor="routed_scaling_factor",
                norm_topk_prob="norm_topk_prob")
    assert set(same) | {"arch"} == set(m)
    for ours, theirs in same.items():
        assert m[ours] == cfg[theirs], ours
    # the cut's arithmetic, by the program's own parameter shapes
    from mxnet_tpu.models.transformer import param_shapes

    count = sum(int(np.prod(s)) for s in param_shapes(**m).values())
    assert count == 525_338_624 + 64_098_816 + 7 * 640_029_312 \
        == 5_069_642_624


def test_the_step_byte_count_is_the_layer_equations():
    """``kernels.hbm_share.mla``'s byte function at the published widths,
    against the sums written out: attention 26,350,080 parameters a layer
    with its three norms, the shared expert 9,437,184, the router 262,272
    with its bias, the dense MLP 37,748,736, the head 262,668,288 with the
    final norm; an expert 4,718,592; a token's latent 8 x 576."""
    spec = spec_mod.Spec()
    reader = spec.module("layer_metrics", "kernels.hbm_share.mla")
    cfg = spec.config(spec.cell(CELL))
    attention = 2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 \
        + 4096 * 2048 + 2048 + 512 + 2048
    assert attention == 26_350_080
    always = 8 * attention + 3 * 2048 * 6144 \
        + 7 * (128 * 2048 + 128 + 3 * 2048 * 1536) + 2048 + 128256 * 2048
    expert = 3 * 2048 * 768
    assert reader.sizes(cfg["model"]) == (always, expert, 8 * 576)
    assert always == 579_115_904 and expert == 4_718_592
    # what a step reads whatever the router does, and all 128 experts of 7
    # layers, and the embedding it only looks up, are the model
    assert always + 7 * 128 * expert + 128256 * 2048 == 5_069_642_624
    # 100 steps of 32 lanes over 500 tokens of context each, 99 experts
    # touched a layer
    steps, tokens, touched = 100, 3200, 100 * 7 * 99
    want = 2 * (steps * always + touched * expert
                + (tokens * 500 + tokens) * 8 * 576)
    assert reader.step_bytes(cfg["model"], cfg["dtype"], steps, tokens,
                             tokens * 500, touched) == want
    # a step with every lane busy: 1.16 GB outside the experts, 6.5 GB of
    # experts, 0.15 GB of latents
    assert 7.8e9 < want / steps < 7.9e9


def test_the_flop_count_is_the_layer_equations():
    """The driver's ``model_flops`` at the published widths: a token is 2 x
    its matrices (as ``param_shapes`` lists them, 6 of the 128 experts),
    attention 2 x 32 x (192 + 128) a context token and layer materialised
    and 2 x 32 x (512 + 64 + 512) absorbed, the head 2 x 2,048 x 128,256 a
    row."""
    from mxnet_tpu.models.transformer import param_shapes

    spec = spec_mod.Spec()
    driver = spec.module("drivers", "paged_closed_loop_mla")
    model = spec.config(spec.cell(CELL))["model"]
    shapes = param_shapes(**model)
    size = lambda pick: sum(int(np.prod(s)) for n, s in shapes.items()
                            if n.endswith("_weight") and pick(n))
    experts = size(lambda n: "_experts_" in n)
    token = size(lambda n: "_experts_" not in n
                 and n not in ("embed_weight", "lm_head_weight")) \
        + experts * 6 // 128
    assert experts == 7 * 128 * 4_718_592
    head = 2 * 2048 * 128256
    for absorbed in (False, True):
        assert driver.model_flops(model, 1, 0, 0, absorbed) == 2 * token
        assert driver.model_flops(model, 0, 0, 1, absorbed) == head
    assert driver.model_flops(model, 0, 1, 0, False) == 2 * 8 * 32 * 320
    assert driver.model_flops(model, 0, 1, 0, True) == 2 * 8 * 32 * 1088
    # a step of 32 lanes at 500 tokens of context: 1.55 G a token outside
    # attention, 0.28 G of absorbed attention
    step = driver.model_flops(model, 32, 32 * 500, 32, True)
    assert step == 32 * (2 * token + head) + 32 * 500 * 2 * 8 * 32 * 1088
    assert 1.8e9 < step / 32 < 1.9e9
    # an admission of 384 real tokens: its own rows, one row of logits
    assert driver.model_flops(model, 384, 384 * 384, 1, False) == \
        384 * 2 * token + 384 * 384 * 2 * 8 * 32 * 320 + head


def test_the_share_needs_the_programs_counters_and_a_latent():
    """Nothing to read, and no error, from a program without the counters
    (the parent commit) or a configuration without a latent."""
    from types import SimpleNamespace

    spec = spec_mod.Spec()
    reader = spec.module("layer_metrics", "kernels.hbm_share.mla")
    cfg = spec.config(spec.cell(CELL))
    full = {"serving.paged_steps": 100, "serving.decode_tokens": 3200,
            "serving.step_context_tokens": 3200 * 500,
            "serving.moe.step_experts_touched": 100 * 7 * 99}
    run = lambda **kw: SimpleNamespace(**{
        "trace_summary": {"busy_s": 3.0}, "counters_window": full,
        "peaks": {"hbm_bytes_per_s": 819e9}, "config": cfg, **kw})
    share = reader.read(run())
    assert share == pytest.approx(100.0 * reader.step_bytes(
        cfg["model"], "bfloat16", 100, 3200, 3200 * 500, 100 * 7 * 99)
        / (3.0 * 819e9))
    assert 30 < share < 35
    for gone in ("serving.step_context_tokens",
                 "serving.moe.step_experts_touched", "serving.paged_steps"):
        old = {k: v for k, v in full.items() if k != gone}
        assert reader.read(run(counters_window=old)) is None
    assert reader.read(run(counters_window=None)) is None
    assert reader.read(run(peaks=None)) is None
    assert reader.read(run(trace_summary=None)) is None
    for other in ("transformer-base.generate", "olmoe-1b-7b.score",
                  "granite-4.0-h-micro.generate"):
        assert reader.read(run(config=spec.config(spec.cell(other)))) is None


def test_a_prompt_is_held_to_its_lower_quartile_row():
    """The ``ceil(n / 4)``-th smallest: of 17 rows the fifth, so twelve rows
    whose experts flipped leave it where it was and a fault in every row
    moves it; the sampled prompts are the traffic's ``check_prompt_lens``."""
    driver = spec_mod.Spec().module("drivers", "paged_closed_loop_mla")
    sound = [1.0e-2 + 1e-4 * i for i in range(17)]
    assert driver.lower_quartile(sound) == pytest.approx(1.04e-2)
    flipped = sound[:5] + [0.3] * 12
    assert driver.lower_quartile(flipped[::-1]) == pytest.approx(1.04e-2)
    assert driver.lower_quartile(sound[:4] + [0.3] * 13) == 0.3
    assert driver.lower_quartile([0.06 + x for x in sound]) > 0.06
    assert driver.lower_quartile([0.5]) == 0.5
    assert driver.lower_quartile([0.1, 0.2, 0.3, 0.4, 0.5, 0.6]) == 0.2
    traffic = spec_mod.Spec().traffic(spec_mod.Spec().cell(CELL))
    assert traffic["check_prompt_lens"] == [128, 512, 1024]
    assert set(traffic["check_prompt_lens"]) <= set(
        traffic["fields"]["prompt_len"]["grid"])
    assert traffic["check_decode_steps"] == 16
