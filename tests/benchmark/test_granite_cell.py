"""The cell ``granite-4.0-h-micro.generate`` rehearsed on the CPU at its tiny
size: it runs to its end and meets the contract untraced and traced, a broken
reference is reported, the bytes ``kernels.hbm_share.ssm`` counts are the
layer equations' arithmetic, and the driver's extra kinds of draw are what
they say."""
import json

import numpy as np
import pytest

from harness import contract, main as harness_main, spec as spec_mod

CELL = "granite-4.0-h-micro.generate"


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
    assert "admit and 5 decode steps" in line["checks"][0]
    assert "first layer's state" in line["checks"][1]
    assert line["checks"][0].endswith("ok") and line["checks"][1].endswith("ok")
    if not trace:
        assert set(line["metrics"]) == set(declared)
        return
    got = line["metrics"]
    # the new span reader reports; the shares of the HBM and matrix-unit
    # peaks need a chip's peaks; the compiler's byte count is not this
    # cell's (it counts every state slice twice: 136% on the chip)
    assert got["serving.admit_state_ms_p50"]["value"] > 0
    assert "kernels.hbm_share.ssm" in declared
    assert "kernels.flops_share.serving" in declared
    assert "kernels.hbm_share.serving" not in declared
    assert line["notes"]["dispatches"] > 0
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
    assert any("FAIL" in c for c in line["checks"])


def test_the_step_byte_count_is_the_layer_equations():
    """``kernels.hbm_share.ssm``'s byte function at the published widths,
    against the sums written out: a Mamba mixer 25.85 M parameters, an
    attention mixer 10.49 M, an MLP 50.33 M, the tied embedding 205.5 M; a
    lane's state 36 x (64 x 64 x 128 + 3 x 4,352) float32; a context token
    4 layers x 2 x 8 x 64 bfloat16."""
    spec = spec_mod.Spec()
    reader = spec.module("layer_metrics", "kernels.hbm_share.ssm")
    cfg = spec.config(spec.cell(CELL))
    mamba = (4096 + 4096 + 2 * 128 + 64) * 2048 + (4096 + 256) * 5 + 3 * 64 \
        + 4096 + 2048 * 4096
    attention = (2048 + 2 * 512) * 2048 + 2048 * 2048
    mlp = 2 * 8192 * 2048 + 2048 * 8192
    assert (mamba, attention, mlp) == (25_847_232, 10_485_760, 50_331_648)
    params = 100352 * 2048 + 2048 + 40 * (mlp + 2 * 2048) \
        + 36 * mamba + 4 * attention
    state = 36 * (64 * 64 * 128 + 3 * 4352)
    assert reader.sizes(cfg["model"]) == (params, state, 4 * 2 * 8 * 64)
    assert 3.18e9 < params < 3.20e9 and state * 4 == 77_377_536
    # 600 steps of 32 lanes, each over 300 tokens of context on average
    steps, tokens = 600, 600 * 32
    want = steps * params * 2 + tokens * 2 * 4 * state \
        + tokens * 300 * 4096 * 2
    assert reader.step_bytes(cfg["model"], cfg["dtype"], steps, tokens,
                             tokens * 300) == want
    # a step with every lane busy: 6.4 GB of weights, 5.0 GB of state
    assert 11.3e9 < want / steps < 11.5e9


def test_the_flop_count_is_the_layer_equations():
    """The driver's ``model_flops`` at the published widths: a token is 2 x
    its matrices' parameters (as ``param_shapes`` lists them, the tied head
    apart) and its convolution taps, 5 a state element and token, attention
    4 x 32 heads x 64 a context token and layer, the head 2 x 2,048 x
    100,352 a row; and a traced run's observation carries it."""
    from mxnet_tpu.models.transformer import param_shapes

    spec = spec_mod.Spec()
    driver = spec.module("drivers", "paged_closed_loop_hybrid")
    model = spec.config(spec.cell(CELL))["model"]
    matrices = sum(int(np.prod(shape))
                   for name, shape in param_shapes(**model).items()
                   if name.endswith("_weight") and name != "embed_weight")
    recurrences = 36 * 5 * 64 * 64 * 128
    head = 2 * 2048 * 100352
    assert driver.model_flops(model, 1, 0, 0) == 2 * matrices + recurrences
    assert driver.model_flops(model, 0, 1, 0) == 4 * 4 * 32 * 64
    assert driver.model_flops(model, 0, 0, 1) == head
    # a step of 32 lanes at 300 tokens of context: 2 x 3.19 G a token
    step = driver.model_flops(model, 32, 32 * 300, 32)
    assert step == 32 * (2 * matrices + recurrences + head) \
        + 32 * 300 * 4 * 4 * 32 * 64
    assert 6.3e9 < step / 32 < 6.6e9
    # an admission of 128 real tokens: its own rows, one row of logits
    assert driver.model_flops(model, 128, 128 * 128, 1) == \
        128 * (2 * matrices + recurrences) + 128 * 128 * 4 * 4 * 32 * 64 \
        + head


def test_the_share_needs_the_programs_counters_and_a_recurrent_state():
    """Nothing to read, and no error, from a program without the counters
    (the parent commit) or a configuration without Mamba layers."""
    from types import SimpleNamespace

    spec = spec_mod.Spec()
    reader = spec.module("layer_metrics", "kernels.hbm_share.ssm")
    cfg = spec.config(spec.cell(CELL))
    peaks = {"hbm_bytes_per_s": 819e9}
    trace = {"busy_s": 2.0}
    full = {"serving.paged_steps": 100, "serving.decode_tokens": 3200,
            "serving.step_context_tokens": 3200 * 250}
    run = lambda **kw: SimpleNamespace(**{
        "trace_summary": trace, "counters_window": full, "peaks": peaks,
        "config": cfg, **kw})
    share = reader.read(run())
    assert share == pytest.approx(
        100.0 * reader.step_bytes(cfg["model"], "bfloat16", 100, 3200,
                                  3200 * 250) / (2.0 * 819e9))
    assert 60 < share < 80
    old = {k: v for k, v in full.items()
           if k != "serving.step_context_tokens"}
    assert reader.read(run(counters_window=old)) is None
    assert reader.read(run(counters_window=None)) is None
    assert reader.read(run(peaks=None)) is None
    other = spec.config(spec.cell("transformer-base.generate"))
    assert reader.read(run(config=other)) is None


def test_the_drivers_uniform_kinds_are_what_they_say():
    """``log_of_uniform`` is the log of a draw in [lo, hi];
    ``inv_softplus_of_log_uniform`` is x with softplus(x) in [lo, hi],
    evenly in its logarithm; ``uniform`` stays in its bounds; one seed, one
    draw; a rule of the old kinds is the old driver's."""
    driver = spec_mod.Spec().module("drivers", "paged_closed_loop_hybrid")
    cfg = spec_mod.Spec().config(spec_mod.Spec().cell(CELL))
    shapes = {"layer0_mamba_A_log": (4096,), "layer0_mamba_dt_bias": (4096,),
              "layer0_mamba_conv_weight": (64, 4), "layer0_mamba_D": (8,),
              "layer0_mamba_out_weight": (16, 32)}
    got = {k: np.asarray(v, np.float64) for k, v in driver.make_weights(
        shapes, cfg["init"], 7, "float32").items()}
    a = np.exp(got["layer0_mamba_A_log"])
    assert 1.0 <= a.min() < 1.1 and 15.9 < a.max() <= 16.0
    assert abs(a.mean() - 8.5) < 0.3                    # uniform in A
    dt = np.log1p(np.exp(got["layer0_mamba_dt_bias"]))
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001
    assert abs(np.log(dt).mean() - np.log(1e-2)) < 0.1  # uniform in log dt
    w = got["layer0_mamba_conv_weight"]
    assert -0.5 <= w.min() < -0.3 and 0.3 < w.max() <= 0.5
    assert np.array_equal(got["layer0_mamba_D"], np.ones(8))
    assert 0.01 < got["layer0_mamba_out_weight"].std() < 0.03
    again = driver.make_weights(shapes, cfg["init"], 7, "float32")
    other = driver.make_weights(shapes, cfg["init"], 8, "float32")
    for k in shapes:
        assert np.array_equal(np.asarray(again[k], np.float64), got[k])
    assert not np.array_equal(np.asarray(other["layer0_mamba_A_log"]),
                              got["layer0_mamba_A_log"])
