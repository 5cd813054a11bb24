"""Schedule-search autotuning (docs/PERF.md §15): the v2 cache schema with
both-direction version handling (v1 binary verdicts load and serve with
zero re-tunes; unknown future versions are cleanly invalidated with one
warning — never a crash, never a silent stale winner), schedule-annotated
records, and the bounded per-kernel schedule spaces."""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import fusion, fusion_tune, telemetry


@pytest.fixture(autouse=True)
def _fresh(monkeypatch, tmp_path):
    saved = telemetry.current_override()
    monkeypatch.setenv("MXNET_FUSION_TUNE_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_FUSION_TUNE_ITERS", "2")
    monkeypatch.setenv("MXNET_TELEMETRY", "counters")
    telemetry.set_mode("counters")
    fusion_tune.reset()
    telemetry.reset()
    yield
    fusion_tune.reset()
    telemetry.reset()
    telemetry.set_mode(saved)


def _write_cache(version, entries):
    path = fusion_tune.cache_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"version": version,
                   "device_kind": fusion_tune.device_kind(),
                   "digest": fusion_tune.entries_digest(entries),
                   "entries": entries}, f)
    return path


# ------------------------------------------------------- schema both ways
def test_v1_binary_verdict_cache_loads_with_zero_retunes(caplog):
    """Direction 1: a PR 9 (version-1) cache file LOADS under the v2
    schema — its records serve as default-schedule verdicts, the warm run
    never re-tunes, and nothing crashes or warns."""
    rec = {"engage": False, "engage_fwd": False, "lowering": None,
           "base_fwd_us": 10.0, "base_bwd_us": 20.0, "measured": {}}
    _write_cache(1, {"k1": rec})
    with caplog.at_level("WARNING", logger="mxnet_tpu"):
        got = fusion_tune.peek("k1")
    assert got == rec
    assert not any("ignoring cache file" in r.message
                   for r in caplog.records)

    def boom():
        raise AssertionError("a loaded v1 verdict must never re-tune")

    assert fusion_tune.verdict("k1", boom) == rec
    assert telemetry.counter("fusion.tune").value == 0
    # a v1 record is never misread as a searched winner
    assert "schedule" not in got


def test_future_version_cache_invalidated_with_one_warning(caplog):
    """Direction 2: an UNKNOWN (future) schema version is cleanly
    invalidated — one warning, no crash, and the next tune rewrites the
    file at the current version."""
    _write_cache(99, {"k2": {"engage": True, "lowering": "pallas"}})
    with caplog.at_level("WARNING", logger="mxnet_tpu"):
        assert fusion_tune.peek("k2") is None
        assert fusion_tune.peek("k2") is None  # warned ONCE, not per read
    warns = [r for r in caplog.records
             if "unknown schema version" in r.message]
    assert len(warns) == 1
    # the miss re-tunes and persists at the CURRENT version
    rec = fusion_tune.verdict("k2", lambda: {"engage": False,
                                             "lowering": None})
    assert rec["engage"] is False
    payload = json.load(open(fusion_tune.cache_path()))
    assert payload["version"] == 2


def test_v1_record_never_a_silent_stale_winner():
    """A v1 engaged record whose lowering no longer exists at the site
    falls back with a reason, not a crash or a phantom engage."""
    from mxnet_tpu.ops.fusion_patterns import MatmulBiasAct

    pat = MatmulBiasAct()
    meta = {"act": "relu", "flatten": True, "no_bias": False}
    rs = np.random.RandomState(0)
    import jax.numpy as jnp

    args = (jnp.asarray(rs.randn(8, 32).astype("f")),
            jnp.asarray(rs.randn(128, 32).astype("f")),
            jnp.asarray(rs.randn(128).astype("f")))
    key = fusion._tune_key(pat, meta, args)
    _write_cache(1, {key: {"engage": True, "lowering": "gone-lowering"}})
    engaged, chosen, reason = fusion.gate_pattern_explain(pat, meta, args)
    assert engaged is False
    assert "unavailable" in reason


# ------------------------------------------------------ schedule records
def test_verdict_annotates_schedule_and_search_width():
    rec = fusion_tune.verdict("s1", lambda: {
        "engage": True, "lowering": "pallas@bm=256,bn=128",
        "measured": {"pallas": {"fwd_us": 9.0},
                     "pallas@bm=256,bn=128": {"fwd_us": 5.0}}})
    assert rec["schedule"] == {"bm": 256, "bn": 128}
    assert rec["schedules_searched"] == 1


def test_default_winner_schedule_is_default():
    rec = fusion_tune.verdict("s2", lambda: {
        "engage": True, "lowering": "pallas",
        "measured": {"pallas": {"fwd_us": 5.0}}})
    assert rec["schedule"] == "default"
    assert rec["schedules_searched"] == 0


def test_sched_name_parse_roundtrip():
    name = fusion_tune.sched_name("block_causal", bq=64)
    assert name == "block_causal@bq=64"
    assert fusion_tune.parse_schedule(name) == {"bq": 64}
    assert fusion_tune.parse_schedule("pallas") == "default"
    assert fusion_tune.parse_schedule(None) is None


def test_schedule_budget_knob(monkeypatch):
    monkeypatch.setenv("MXNET_FUSION_TUNE_SCHEDULES", "0")
    assert fusion_tune.schedule_budget() == 0
    monkeypatch.setenv("MXNET_FUSION_TUNE_SCHEDULES", "7")
    assert fusion_tune.schedule_budget() == 7
    monkeypatch.setenv("MXNET_FUSION_TUNE_SCHEDULES", "junk")
    assert fusion_tune.schedule_budget() == 4
    monkeypatch.delenv("MXNET_FUSION_TUNE_SCHEDULES")
    assert fusion_tune.schedule_budget() == 4


def test_losers_note_quotes_runners_up():
    rec = {"measured": {
        "pallas": {"fwd_us": 5.0, "bwd_us": 5.0},
        "pallas@bm=256,bn=128": {"fwd_us": 20.0, "bwd_us": 20.0},
        "pallas@bm=128,bn=256": {"fwd_us": 12.0, "bwd_us": 10.0}}}
    note = fusion.losers_note(rec, "pallas")
    assert "beat" in note
    # fastest loser first
    assert note.index("bm=128") < note.index("bm=256")


# --------------------------------------------------- bounded spaces per kernel
def test_matmul_block_candidates_bounded_and_supported():
    from mxnet_tpu.ops import pallas_matmul_bias_act as pk

    cands = pk.block_candidates(1024, 128, 2048, "relu", itemsize=4)
    assert cands and cands[0] == (512, 256)  # planner default first
    assert len(cands) == len(set(cands))
    for bm, bn in cands:
        assert pk.supported(1024, 128, 2048, "relu", bm, bn, itemsize=4)


def test_attention_block_schedules_distinct_effective():
    from mxnet_tpu.ops import pallas_attention as pa

    q = (2, 4, 512, 32)
    scheds = pa.block_schedules(q, q, causal=True)
    assert scheds and scheds[0] == (128, 128)
    assert len(scheds) == len(set(scheds))
    # a tiny T collapses every block_q to T: exactly one effective tiling
    # per distinct block_k survives
    small = pa.block_schedules((2, 2, 8, 16), (2, 2, 64, 16), causal=False)
    assert len({s for s in small}) == len(small)


def test_norm_residual_block_candidates():
    from mxnet_tpu.ops import pallas_norm_residual as pn

    cands = pn.block_candidates((4, 64, 128), itemsize=4)
    assert cands and cands[0] == max(cands)  # largest = planner default
    assert all(256 % br == 0 or 256 // br for br in cands)
    assert pn.block_candidates((4, 64, 100)) == []  # D not lane-aligned


# -------------------------------------------------- cold-tune integration
def _mba_fit(monkeypatch, env_patterns="matmul_bias_act"):
    # (256, 32) @ (256, 32)ᵀ: large enough that the (bm, bn) fan-out has
    # >1 DISTINCT effective tiling (a tiny site collapses every variant
    # onto the clamped default and legitimately searches nothing)
    monkeypatch.setenv("MXNET_FUSED_PATTERNS", env_patterns)
    rs = np.random.RandomState(0)
    sym = mx.sym
    x = sym.Variable("data")
    h = sym.FullyConnected(x, num_hidden=256, name="fc1")
    h = sym.Activation(h, act_type="relu", name="act1")
    net = sym.SoftmaxOutput(
        sym.FullyConnected(h, num_hidden=4, name="fc2"), name="softmax")
    ex = net.simple_bind(mx.cpu(), data=(256, 32), softmax_label=(256,),
                         grad_req="write")
    for name, arr in zip(net.list_arguments(), ex.arg_arrays):
        arr[:] = (rs.randint(0, 4, arr.shape) if "label" in name
                  else rs.uniform(-0.5, 0.5, arr.shape)).astype("f")
    ex.forward(is_train=True)
    ex.backward()


def test_cold_tune_searches_and_persists_schedules(monkeypatch):
    """The CI schedule-cache contract: a cold tune under the default
    schedule budget measures ≥1 schedule variant and persists the
    annotated record; the warm read re-tunes zero times."""
    _mba_fit(monkeypatch)
    assert telemetry.counter("fusion.tune").value == 1
    payload = json.load(open(fusion_tune.cache_path()))
    assert payload["version"] == 2
    [rec] = list(payload["entries"].values())
    assert rec["schedules_searched"] >= 1
    assert any("@" in n for n in rec["measured"])
    fusion_tune.reset()
    telemetry.reset()
    _mba_fit(monkeypatch)
    assert telemetry.counter("fusion.tune").value == 0


def test_schedules_zero_restores_binary_verdicts(monkeypatch):
    """MXNET_FUSION_TUNE_SCHEDULES=0 is the PR 9 engine: only the
    planner-default candidate is measured."""
    monkeypatch.setenv("MXNET_FUSION_TUNE_SCHEDULES", "0")
    _mba_fit(monkeypatch)
    payload = json.load(open(fusion_tune.cache_path()))
    [rec] = list(payload["entries"].values())
    assert rec["schedules_searched"] == 0
    assert not any("@" in n for n in rec["measured"])
