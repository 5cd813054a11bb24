"""The persistent measure-and-cache autotuner (fusion_tune.py) end to end:
cold tune → persist → warm hits with zero re-tunes; corrupt or
digest-mismatched cache files are ignored with a warning, never a crash;
tuned-and-rejected verdicts surface their measured timings through the
gate reasons (the GL303 explain contract)."""
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


def _mba_net():
    sym = mx.sym
    x = sym.Variable("data")
    h = sym.FullyConnected(x, num_hidden=128, name="fc1")
    h = sym.Activation(h, act_type="relu", name="act1")
    fc = sym.FullyConnected(h, num_hidden=4, name="fc2")
    return sym.SoftmaxOutput(fc, name="softmax")


def _fit_once(monkeypatch, seed=0):
    monkeypatch.setenv("MXNET_FUSED_PATTERNS", "matmul_bias_act")
    rs = np.random.RandomState(seed)
    net = _mba_net()
    ex = net.simple_bind(mx.cpu(), data=(8, 32), softmax_label=(8,),
                        grad_req="write")
    for name, arr in zip(net.list_arguments(), ex.arg_arrays):
        if "label" in name:
            arr[:] = rs.randint(0, 4, arr.shape).astype("f")
        else:
            arr[:] = rs.uniform(-0.5, 0.5, arr.shape).astype("f")
    ex.forward(is_train=True)
    ex.backward()
    return ex


def test_cold_tune_persists_and_warm_process_never_retunes(monkeypatch,
                                                           tmp_path):
    _fit_once(monkeypatch)
    tunes = telemetry.counter("fusion.tune").value
    assert tunes == 1  # one site, one measurement
    path = fusion_tune.cache_path()
    assert path is not None and os.path.exists(path)
    payload = json.load(open(path))
    assert payload["device_kind"] == fusion_tune.device_kind()
    assert payload["digest"] == fusion_tune.entries_digest(
        payload["entries"])
    [key] = list(payload["entries"])
    assert key.startswith("matmul_bias_act|relu|")

    # "fresh process": drop the in-memory memo, rebind, re-run — the
    # verdict must come from disk with ZERO re-tunes
    fusion_tune.reset()
    telemetry.reset()
    _fit_once(monkeypatch)
    assert telemetry.counter("fusion.tune").value == 0
    assert telemetry.counter("fusion.tune_cache_hit").value >= 1


def test_corrupt_cache_file_is_ignored_not_fatal(monkeypatch, tmp_path,
                                                 caplog):
    path = fusion_tune.cache_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write("{ this is not json")
    with caplog.at_level("WARNING", logger="mxnet_tpu"):
        _fit_once(monkeypatch)  # must tune fresh, not crash
    assert telemetry.counter("fusion.tune").value == 1
    assert any("ignoring cache file" in r.message for r in caplog.records)
    # and the re-tune REWROTE the file valid
    payload = json.load(open(path))
    assert payload["digest"] == fusion_tune.entries_digest(
        payload["entries"])


def test_digest_mismatch_is_ignored_with_warning(monkeypatch, tmp_path,
                                                 caplog):
    _fit_once(monkeypatch)
    path = fusion_tune.cache_path()
    payload = json.load(open(path))
    # hand-edit an entry without updating the digest (a value no real
    # measurement can produce, so the edit is never a no-op)
    for k in payload["entries"]:
        payload["entries"][k]["engage"] = True
        payload["entries"][k]["lowering"] = "hand-edited"
    with open(path, "w") as f:
        json.dump(payload, f)
    fusion_tune.reset()
    with caplog.at_level("WARNING", logger="mxnet_tpu"):
        assert fusion_tune.peek(list(payload["entries"])[0]) is None
    assert any("digest mismatch" in r.message for r in caplog.records)


def test_device_kind_mismatch_is_ignored(monkeypatch, tmp_path, caplog):
    path = fusion_tune.cache_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    entries = {"k": {"engage": True}}
    with open(path, "w") as f:
        json.dump({"version": 1, "device_kind": "TPU v99",
                   "digest": fusion_tune.entries_digest(entries),
                   "entries": entries}, f)
    with caplog.at_level("WARNING", logger="mxnet_tpu"):
        assert fusion_tune.peek("k") is None
    assert any("device_kind" in r.message for r in caplog.records)


def test_tuned_and_rejected_reason_reports_measured_timings(monkeypatch):
    """satellite contract: a tuned-and-rejected site's gate reason carries
    the measured fused-vs-baseline µs from the cache, not a bare 'no
    verdict'."""
    # seed a rejection record directly through the verdict path
    key = "matmul_bias_act|relu|float32(256, 32);(256, 32);(256,)"
    rec = {"engage": False, "engage_fwd": False, "lowering": None,
           "base_fwd_us": 100.0, "base_bwd_us": 200.0,
           "measured": {"pallas": {"fwd_us": 400.0, "bwd_us": 500.0,
                                   "rel_err": 0.0}}}
    got = fusion_tune.verdict(key, lambda: rec)
    assert got["engage"] is False
    note = fusion.tuned_reject_note(got)
    assert "tuned and rejected" in note
    assert "900" in note and "300" in note  # fused vs baseline fwd+bwd µs


def test_measure_candidates_rejects_parity_violations():
    import jax.numpy as jnp

    def baseline(x):
        return x * 2.0

    def wrong(x):
        return x * 2.5  # fast but WRONG: must never engage

    rec = fusion_tune.measure_candidates(
        baseline, [("wrong", wrong)],
        (np.random.RandomState(0).randn(64).astype("f"),), train=True,
        iters=2)
    assert rec["engage"] is False
    assert "rejected" in rec["measured"]["wrong"]
