"""Serving subsystem (mxnet_tpu/serving/, docs/SERVING.md): executable
cache warmup/seal/persistence, continuous batching over shape buckets
(pad-to-bucket correctness, deadline partials, oversize rejection,
cross-thread ordering) and the predictor's zero-recompile contract."""
import json
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import (InferenceEngine, PersistentExecutableCache)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tm():
    telemetry.reset()
    telemetry.clear_events()
    saved = telemetry.current_override()
    yield telemetry
    telemetry.set_mode(saved)
    telemetry.reset()
    telemetry.clear_events()


def _mlp_net():
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=5,
                                name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _mlp_params(seed=0):
    rs = np.random.RandomState(seed)
    return {"fc_weight": rs.randn(5, 8).astype("float32"),
            "fc_bias": rs.randn(5).astype("float32")}


def _direct_forward(net, params, x_padded):
    exe = net.simple_bind(mx.cpu(), grad_req="null",
                          data=x_padded.shape)
    for k, v in params.items():
        exe.arg_dict[k][:] = v
    exe.arg_dict["data"][:] = x_padded
    exe.forward(is_train=False)
    return exe.outputs[0].asnumpy()


# ---------------------------------------------------------------- cache
def test_cache_warmup_seal_and_miss_raises():
    cache = PersistentExecutableCache(_mlp_net(), _mlp_params(), {},
                                      cache_dir=None)
    n = cache.warmup([{"data": (1, 8)}, {"data": (4, 8)}])
    assert n == 2 and cache.sealed
    # warmed bucket: runs
    out = cache.run({"data": np.zeros((4, 8), "float32")})
    assert out[0].shape == (4, 5)
    # unwarmed bucket: the call that would retrace raises with diagnosis
    with pytest.raises(MXNetError, match="post-warmup executable-cache "
                                         "miss"):
        cache.run({"data": np.zeros((3, 8), "float32")})


def test_cache_hit_vs_compile_counters(tm):
    tm.set_mode("counters")
    cache = PersistentExecutableCache(_mlp_net(), _mlp_params(), {},
                                      cache_dir=None)
    cache.warmup([{"data": (2, 8)}])
    c0 = tm.counters()
    for _ in range(5):
        cache.run({"data": np.zeros((2, 8), "float32")})
    c1 = tm.counters()
    assert c1["serving.executable_hit"] - c0.get("serving.executable_hit",
                                                 0) == 5
    assert c1.get("serving.executable_compile", 0) == \
        c0.get("serving.executable_compile", 0)
    # the executor underneath replays its jit entry: no new compiles
    assert c1.get("executor.compile", 0) == c0.get("executor.compile", 0)
    assert c1.get("executor.retrace", 0) == c0.get("executor.retrace", 0)


def test_cache_manifest_persistence(tmp_path):
    params = _mlp_params()
    c1 = PersistentExecutableCache(_mlp_net(), params, {},
                                   cache_dir=str(tmp_path), model_key="m")
    c1.warmup([{"data": (1, 8)}, {"data": (2, 8)}])
    manifest = c1._manifest_path()
    assert os.path.exists(manifest)
    rec = json.load(open(manifest))
    assert len(rec["buckets"]) == 2 and rec["dtype"] == "float32"
    # a fresh process-equivalent: warmup(None) replays the manifest
    c2 = PersistentExecutableCache(_mlp_net(), params, {},
                                   cache_dir=str(tmp_path), model_key="m")
    assert c2.warmup(None) == 2
    assert sorted(c2.keys()) == sorted(c1.keys())
    # a DIFFERENT model under the same key must not inherit the buckets
    other = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=7,
                              name="fc"), name="softmax")
    rs = np.random.RandomState(1)
    c3 = PersistentExecutableCache(
        other, {"fc_weight": rs.randn(7, 8).astype("float32"),
                "fc_bias": np.zeros(7, "float32")}, {},
        cache_dir=str(tmp_path), model_key="m")
    assert c3.warmup(None) == 0
    # zero warmed buckets must NOT seal (an empty sealed cache would
    # reject every request with no way back) nor clobber the manifest
    assert not c3.sealed
    c3.executable({"data": (1, 8)})  # still bindable
    assert json.load(open(manifest))["digest"] == rec["digest"]


def test_cache_shares_params_across_buckets():
    """Bucket executors share ONE set of param/aux device arrays — a
    4-rung ladder must not hold 4 full weight copies, and a param write
    through one executor is visible to every bucket."""
    cache = PersistentExecutableCache(_mlp_net(), _mlp_params(), {},
                                      cache_dir=None)
    cache.warmup([{"data": (1, 8)}, {"data": (4, 8)}])
    e1, e4 = (cache._exes[k] for k in sorted(cache._exes))
    for p in ("fc_weight", "fc_bias"):
        assert e1.arg_dict[p] is e4.arg_dict[p], \
            "param %r duplicated across bucket executors" % p
    # inputs stay per-bucket (their shape IS the cache key)
    assert e1.arg_dict["data"] is not e4.arg_dict["data"]
    before = cache.run({"data": np.ones((1, 8), "float32")})[0]
    e4.arg_dict["fc_weight"][:] = 0.0
    e4.arg_dict["fc_bias"][:] = 0.0
    after = cache.run({"data": np.ones((1, 8), "float32")})[0]
    assert not np.array_equal(before, after), \
        "bucket-1 executor did not see the shared param update"


# --------------------------------------------------------------- engine
def test_pad_to_bucket_bitwise():
    """A request padded into a bucket returns exactly the rows the padded
    direct forward produces — bitwise for fp32 (same executable, same
    batch layout, slicing only)."""
    net, params = _mlp_net(), _mlp_params()
    cache = PersistentExecutableCache(net, params, {}, cache_dir=None)
    rs = np.random.RandomState(3)
    x = rs.rand(3, 8).astype("float32")
    with InferenceEngine(cache, {"data": (8,)}, buckets=(4, 8),
                         max_delay_ms=1) as eng:
        got = eng.infer({"data": x})[0]
    pad = np.zeros((4, 8), "float32")
    pad[:3] = x
    want = _direct_forward(net, params, pad)[:3]
    np.testing.assert_array_equal(got, want)


def test_bucket_selection_smallest_covering(tm):
    tm.set_mode("counters")
    cache = PersistentExecutableCache(_mlp_net(), _mlp_params(), {},
                                      cache_dir=None)
    eng = InferenceEngine(cache, {"data": (8,)}, buckets=(1, 2, 4, 8),
                          max_delay_ms=0)
    eng.start()
    try:
        for rows, want_bucket in ((1, 1), (2, 2), (3, 4), (5, 8)):
            c0 = tm.counters()
            out = eng.infer({"data": np.zeros((rows, 8), "float32")})
            assert out[0].shape == (rows, 5)
            c1 = tm.counters()
            got = c1["serving.batch_capacity"] - \
                c0.get("serving.batch_capacity", 0)
            assert got == want_bucket, (rows, got, want_bucket)
    finally:
        eng.close()


def test_deadline_triggered_partial_batch(tm):
    """Requests smaller than the largest bucket dispatch when the batching
    deadline expires, not when the bucket fills."""
    tm.set_mode("counters")
    cache = PersistentExecutableCache(_mlp_net(), _mlp_params(), {},
                                      cache_dir=None)
    eng = InferenceEngine(cache, {"data": (8,)}, buckets=(8,),
                          max_delay_ms=50)
    eng.start()
    try:
        t0 = time.perf_counter()
        f1 = eng.submit({"data": np.zeros((1, 8), "float32")})
        f2 = eng.submit({"data": np.zeros((2, 8), "float32")})
        r = f1.result(timeout=10.0)
        waited = time.perf_counter() - t0
        f2.result(timeout=10.0)
        assert r[0].shape == (1, 5)
        # dispatched as ONE partial batch of 3/8 after the deadline
        snap = tm.counters()
        assert snap["serving.batches"] == 1
        assert snap["serving.batch_items"] == 3
        assert snap["serving.batch_capacity"] == 8
        assert waited >= 0.045, "dispatched before the deadline"
        assert telemetry.gauge("serving.batch_occupancy").value == \
            pytest.approx(3 / 8)
    finally:
        eng.close()


def test_full_bucket_dispatches_before_deadline():
    cache = PersistentExecutableCache(_mlp_net(), _mlp_params(), {},
                                      cache_dir=None)
    eng = InferenceEngine(cache, {"data": (8,)}, buckets=(2,),
                          max_delay_ms=10_000)
    eng.start()
    try:
        t0 = time.perf_counter()
        f1 = eng.submit({"data": np.zeros((1, 8), "float32")})
        f2 = eng.submit({"data": np.zeros((1, 8), "float32")})
        f1.result(timeout=10.0)
        f2.result(timeout=10.0)
        assert time.perf_counter() - t0 < 5.0, \
            "a full bucket waited for the deadline"
    finally:
        eng.close()


def test_oversize_request_rejected():
    cache = PersistentExecutableCache(_mlp_net(), _mlp_params(), {},
                                      cache_dir=None)
    with InferenceEngine(cache, {"data": (8,)}, buckets=(1, 4),
                         max_delay_ms=1) as eng:
        with pytest.raises(MXNetError, match="exceed the largest bucket"):
            eng.submit({"data": np.zeros((5, 8), "float32")})
        # wrong item shape is rejected too (it would silently mis-pad)
        with pytest.raises(MXNetError, match="item shape"):
            eng.submit({"data": np.zeros((2, 9), "float32")})


def test_rejected_counter_counts_oversize(tm):
    """serving.rejected is the load-shedding row: oversize/malformed
    submits count, not just queue-full backpressure."""
    tm.set_mode("counters")
    cache = PersistentExecutableCache(_mlp_net(), _mlp_params(), {},
                                      cache_dir=None)
    with InferenceEngine(cache, {"data": (8,)}, buckets=(1, 4),
                         max_delay_ms=1) as eng:
        c0 = tm.counters().get("serving.rejected", 0)
        with pytest.raises(MXNetError):
            eng.submit({"data": np.zeros((5, 8), "float32")})  # oversize
        with pytest.raises(MXNetError):
            eng.submit({"data": np.zeros((2, 9), "float32")})  # bad shape
        assert tm.counters().get("serving.rejected", 0) == c0 + 2


def test_non_batch_major_output_replicated_whole():
    """An output whose leading dim does NOT scale with the bucket (here a
    per-unit weight reduction of constant shape (8,)) is delivered whole
    to every request — even when that dim coincidentally divides the
    dispatched bucket, which a runtime divisibility test would mis-slice."""
    rs = np.random.RandomState(2)
    params = {"fc_weight": rs.randn(8, 8).astype("float32"),
              "fc_bias": rs.randn(8).astype("float32")}
    net = mx.sym.Group([
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=8,
                              name="fc"),
        mx.sym.sum(mx.sym.Variable("fc_weight"), axis=1, name="wsum")])
    # the classification is pure shape inference, so it must hold even on
    # a SINGLE-bucket ladder where no cross-bucket comparison exists and
    # wsum's dim 8 coincidentally divides the lone bucket
    for buckets in ((1, 8), (8,)):
        cache = PersistentExecutableCache(net, params, {}, cache_dir=None)
        with InferenceEngine(cache, {"data": (8,)}, buckets=buckets,
                             max_delay_ms=1) as eng:
            out = eng.infer({"data": rs.rand(5, 8).astype("float32")})
        assert out[0].shape == (5, 8), buckets  # batch-major: sliced
        assert out[1].shape == (8,), buckets  # constant: replicated whole
        np.testing.assert_allclose(out[1], params["fc_weight"].sum(axis=1),
                                   rtol=1e-6)


def test_cross_thread_queue_ordering_and_correctness():
    """Concurrent submitters each get THEIR outputs back, and a request is
    never overtaken by one submitted after it (per-thread submit order is
    preserved in completion timestamps)."""
    net, params = _mlp_net(), _mlp_params()
    cache = PersistentExecutableCache(net, params, {}, cache_dir=None)
    results = {}
    errs = []

    def worker(tid):
        try:
            futs = []
            for j in range(6):
                x = np.full((1, 8), tid * 10 + j, "float32")
                futs.append((j, x, eng.submit({"data": x})))
            for j, x, f in futs:
                results[(tid, j)] = (x, f.result(timeout=30.0)[0], f.done_at)
        except Exception as exc:  # pragma: no cover - surfaced by assert
            errs.append(exc)

    with InferenceEngine(cache, {"data": (8,)}, buckets=(1, 2, 4),
                         max_delay_ms=2) as eng:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errs, errs
    assert len(results) == 30
    for (tid, j), (x, got, _) in results.items():
        want = _direct_forward(net, params, np.tile(x, (1, 1)))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for tid in range(5):
        stamps = [results[(tid, j)][2] for j in range(6)]
        assert stamps == sorted(stamps), \
            "completions overtook submit order within a thread"


def test_engine_unknown_input_name_rejected():
    cache = PersistentExecutableCache(_mlp_net(), _mlp_params(), {},
                                      cache_dir=None)
    with pytest.raises(MXNetError, match="not model inputs"):
        InferenceEngine(cache, {"nope": (8,)}, buckets=(1,))


# ------------------------------------------------------------ predictor
def test_predictor_zero_recompiles_across_100_calls(tm, tmp_path):
    """The satellite regression: repeated forward() at an identical shape
    is a guaranteed executable-cache hit — 0 recompiles across 100 calls."""
    from mxnet_tpu.predictor import Predictor

    tm.set_mode("counters")
    rs = np.random.RandomState(0)
    net = _mlp_net()
    p = str(tmp_path / "m.params")
    mx.nd.save(p, {"arg:fc_weight": mx.nd.array(rs.randn(5, 8)
                                                .astype("float32")),
                   "arg:fc_bias": mx.nd.array(rs.randn(5)
                                              .astype("float32"))})
    pred = Predictor(net.tojson(), open(p, "rb").read(), {"data": (4, 8)})
    x = rs.rand(4, 8).astype("float32")
    pred.forward(data=x)
    first = pred.get_output(0).copy()
    base = tm.counters().get("executor.compile", 0)
    for _ in range(100):
        pred.forward(data=x)
    snap = tm.counters()
    assert snap.get("executor.compile", 0) == base, \
        "forward() recompiled on a repeated identical shape"
    assert snap.get("executor.retrace", 0) == 0
    np.testing.assert_array_equal(pred.get_output(0), first)


def test_predictor_reshape_roundtrip_reuses_executable(tm, tmp_path):
    from mxnet_tpu.predictor import Predictor

    tm.set_mode("counters")
    rs = np.random.RandomState(0)
    p = str(tmp_path / "m.params")
    mx.nd.save(p, {"arg:fc_weight": mx.nd.array(rs.randn(5, 8)
                                                .astype("float32")),
                   "arg:fc_bias": mx.nd.zeros((5,))})
    pred = Predictor(_mlp_net().tojson(), open(p, "rb").read(),
                     {"data": (4, 8)})
    x = rs.rand(4, 8).astype("float32")
    pred.forward(data=x)
    want = pred.get_output(0).copy()
    pred.reshape({"data": (2, 8)})
    pred.forward(data=x[:2])
    compiles = tm.counters().get("executor.compile", 0)
    pred.reshape({"data": (4, 8)})  # back to a seen shape: cache hit
    pred.forward(data=x)
    assert tm.counters().get("executor.compile", 0) == compiles, \
        "reshape back to a known shape recompiled"
    np.testing.assert_array_equal(pred.get_output(0), want)


def test_predictor_reshape_lru_bounded(tm, tmp_path, monkeypatch):
    """An unsealed (predict-API) cache is LRU-bounded: reshaping through
    more distinct shapes than MXNET_SERVE_MAX_EXECUTABLES retains at most
    the cap, recent shapes stay zero-recompile, and an evicted shape
    recompiles once instead of growing memory forever."""
    from mxnet_tpu.predictor import Predictor

    tm.set_mode("counters")
    monkeypatch.setenv("MXNET_SERVE_MAX_EXECUTABLES", "3")
    rs = np.random.RandomState(0)
    p = str(tmp_path / "m.params")
    mx.nd.save(p, {"arg:fc_weight": mx.nd.array(rs.randn(5, 8)
                                                .astype("float32")),
                   "arg:fc_bias": mx.nd.zeros((5,))})
    pred = Predictor(_mlp_net().tojson(), open(p, "rb").read(),
                     {"data": (1, 8)})
    for b in (2, 3, 4, 5, 6):
        pred.reshape({"data": (b, 8)})
        pred.forward(data=rs.rand(b, 8).astype("float32"))
    assert len(pred._cache.keys()) == 3
    assert tm.counters().get("serving.executable_evict", 0) == 3
    c = tm.counters().get("executor.compile", 0)
    pred.reshape({"data": (6, 8)})  # most recent: still cached
    pred.forward(data=rs.rand(6, 8).astype("float32"))
    assert tm.counters().get("executor.compile", 0) == c
    pred.reshape({"data": (1, 8)})  # evicted long ago: recompiles once
    pred.forward(data=rs.rand(1, 8).astype("float32"))
    assert tm.counters().get("executor.compile", 0) > c


# ------------------------------------------------------------ serve_bench
@pytest.mark.slow
def test_serve_bench_check_smoke():
    import subprocess
    import sys

    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "MXNET_DEFAULT_CONTEXT": "cpu"})
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "serve_bench.py"),
         "--model", "mlp", "--qps", "60", "--duration", "1", "--check"],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert r.returncode == 0, (r.stdout + r.stderr)[-800:]
    rec = json.loads([l for l in r.stdout.splitlines()
                      if l.startswith("{")][-1])
    assert rec["qps"] > 0 and rec["retraces_post_warmup"] == 0


# ------------------------------------------------- batcher failure latch
def test_batcher_death_latches_and_fails_fast(tm):
    """A dead batcher thread must not strand its callers: the in-flight
    batch's futures fail, the engine latches, and later ``submit()`` /
    ``start()`` raise promptly instead of hanging forever (the
    PrefetchingIter._shutdown latch pattern)."""
    tm.set_mode("counters")
    cache = PersistentExecutableCache(_mlp_net(), _mlp_params(), {},
                                      cache_dir=None)
    eng = InferenceEngine(cache, {"data": (8,)}, buckets=(2,),
                          max_delay_ms=1)
    eng.start()
    try:
        def boom(batch):
            # non-Exception: escapes the per-batch handler and kills the
            # thread — exactly the case that used to hang every future
            raise KeyboardInterrupt("simulated batcher death")

        eng._dispatch = boom
        f1 = eng.submit({"data": np.zeros((2, 8), "float32")})
        with pytest.raises((MXNetError, KeyboardInterrupt)):
            f1.result(timeout=10)
        eng._thread.join(timeout=10)
        t0 = time.time()
        with pytest.raises(MXNetError, match="latched|died"):
            eng.submit({"data": np.zeros((1, 8), "float32")})
        assert time.time() - t0 < 5, "submit after batcher death must " \
            "fail promptly, not hang"
        with pytest.raises(MXNetError, match="latched|died"):
            eng.start()
        assert telemetry.counter("serving.batcher_deaths").value == 1
    finally:
        eng._started = False  # thread already dead; skip close()'s join


def test_latch_fails_pending_queued_futures():
    """Requests still sitting in the queue when the batcher dies get their
    futures failed immediately — no waiter left behind."""
    cache = PersistentExecutableCache(_mlp_net(), _mlp_params(), {},
                                      cache_dir=None)
    eng = InferenceEngine(cache, {"data": (8,)}, buckets=(8,),
                          max_delay_ms=5000)
    eng.start()
    try:
        # 1 row into an 8-bucket: the batcher holds it in the queue while
        # waiting out the 5s admission deadline
        fut = eng.submit({"data": np.zeros((1, 8), "float32")})
        deadline = time.time() + 5
        while not eng._queue and time.time() < deadline:
            time.sleep(0.005)
        eng._latch_failure(RuntimeError("simulated death"))
        with pytest.raises(MXNetError, match="died"):
            fut.result(timeout=5)
        with pytest.raises(MXNetError, match="died"):
            eng.submit({"data": np.zeros((1, 8), "float32")})
    finally:
        eng._started = False
