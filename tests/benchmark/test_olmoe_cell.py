"""The cell ``olmoe-1b-7b.score`` rehearsed on the CPU at its tiny size: it
runs to its end and meets the contract untraced and traced, a broken
reference is reported, and the reference's expert sum agrees with a
token-by-token spelling of it."""
import json

import numpy as np
import pytest

from harness import contract, main as harness_main, spec as spec_mod

CELL = "olmoe-1b-7b.score"


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
    assert line["attempted"] > 0 and line["notes"]["dispatches"] == 0
    assert line["compiles"]["window"]["requests"] == 0
    if not trace:
        assert set(line["metrics"]) == set(declared)
        return
    got = line["metrics"]
    # the new reader reports; the share of the peak needs a chip's peaks
    assert got["moe.load_max_over_mean"]["value"] >= 1.0
    assert "kernels.flops_share.moe" in declared
    for phase in ("stage", "prefill", "logits", "scatter"):
        assert got["serving.admit_%s_ms_p50" % phase]["value"] > 0
    assert got["serving.admit_ms_p50"]["value"] > 0
    assert got["process.compiles_in_window.serving"]["value"] == 0
    assert got["graph.retraces_in_window.serving"]["value"] == 0


def test_a_broken_reference_is_reported_as_incorrect(capsys):
    rc, _, line = _rehearse(capsys, "--break-reference")
    declared = {m["name"]: m["unit"]
                for m in spec_mod.Spec().metrics("end_to_end", CELL)}
    assert rc == 0 and contract.problems(line, declared, False) == []
    assert line["correct"] is False and line["failed"] == 0
    assert any("FAIL" in c for c in line["checks"])


def test_the_admission_flop_count_is_the_layer_equations():
    """The driver's count for one admission, against the sum written out
    for the published widths: 75.6 M MACs a token and layer plus
    attention, 103 M for the head."""
    driver = spec_mod.Spec().module("drivers", "paged_closed_loop_arch")
    model = spec_mod.Spec().config(spec_mod.Spec().cell(CELL))["model"]
    per_layer = 3 * 2048 * 2048 + 2048 * 2048 + 64 * 2048 \
        + 8 * 3 * 2048 * 1024
    assert per_layer == 67_239_936
    length = 1536
    want = 2 * length * (8 * (per_layer + 2 * length * 2048)
                         + 2048 * 50304)
    assert driver.admit_flops(length, model) == want


def test_the_reference_expert_sum_is_the_token_loop():
    """``reference.moe`` (a masked loop over experts) against the sum
    spelled token by token, at the tiny configuration's sizes."""
    spec = spec_mod.Spec()
    model = spec.config(spec.cell(CELL), tiny=True)["model"]
    ref = spec.module("reference", "olmoe_decoder")
    d, f = model["model_dim"], model["ffn_dim"]
    e, k = model["num_experts"], model["num_experts_per_tok"]
    rs = np.random.default_rng(7)
    h = rs.standard_normal((10, d)).astype("f")
    router = rs.standard_normal((e, d)).astype("f") * 0.3
    gate, up = (rs.standard_normal((e, d, f)).astype("f") * 0.2
                for _ in range(2))
    down = rs.standard_normal((e, f, d)).astype("f") * 0.2
    want = np.zeros_like(h)
    for t, x in enumerate(h):
        z = x @ router.T
        p = np.exp(z - z.max()) / np.exp(z - z.max()).sum()
        for j in np.argsort(-p, kind="stable")[:k]:
            a = x @ gate[j]
            want[t] += p[j] * ((a / (1 + np.exp(-a)) * (x @ up[j])) @ down[j])
    got = np.asarray(ref.moe(h, router, gate, up, down, k))
    # float32 both sides: the order of eight against two additions
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
