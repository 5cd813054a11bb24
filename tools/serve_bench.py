#!/usr/bin/env python
"""Serving benchmark: synthetic open-loop load against the inference engine.

The headline perf artifact for the serving subsystem (docs/SERVING.md): a
load generator submits requests on a fixed open-loop schedule (arrivals do
NOT wait for completions — the honest serving-latency regime) against an
``InferenceEngine`` over a warmed ``PersistentExecutableCache``, then
reports

  - sustained QPS (completed requests / wall time),
  - p50 / p99 request latency (submit -> delivery),
  - batch occupancy (dispatched rows / dispatched bucket capacity),
  - post-warmup retrace/compile counts (MUST be zero — the engine's whole
    point; the sealed cache raises on the miss that would retrace, and the
    executor's compile/cache-hit telemetry proves the replay),
  - with ``--compare-batch1``: closed-loop saturation throughput of the
    bucket ladder vs a batch-size-1 engine — continuous batching's
    amortization of per-dispatch overhead, the PR's >=2x acceptance
    number.

``--model transformer-decode`` measures the KV-cache autoregressive path
instead: per-token decode-step latency and tokens/s over ``--rows`` lanes
of a ``PagedKVDecoder`` (prefill bucket + single-token decode executable,
zero retraces across positions). ``--megastep-k K`` (default 8) adds the decode-megastep
comparison leg: the same streams decoded K tokens per dispatch through
the ``lax.scan`` megastep program (docs/SERVING.md §Megasteps), gated
under ``--check`` on token-identical parity with single-step greedy AND
``host_gap_per_token`` at K ≤ 0.5× the K=1 baseline.

``--workload zipf-prefix`` is the shared-prefix serving smoke
(docs/SERVING.md §Prefix cache & speculative decoding): requests draw
their prompt head from a small Zipf-distributed set of shared prefixes,
measured once against a prefix-cache-off PagedKVDecoder baseline and
once with the copy-on-write prefix cache on — reporting the chunk hit
rate, prefill tokens/FLOPs saved, a bitwise cached-vs-cold admit
subcheck, and the speculative-decoding leg (draft-verify megasteps,
``--spec-gamma`` / ``--spec-draft-layers``): accepted-draft rate plus
per-token p50/p99 against plain greedy, gated under ``--check`` on
token-identical parity, hit rate > 0.5, accepted rate > 0, spec p50 <=
baseline, and zero post-warmup retraces/compiles.

``--chaos`` is the serving resilience smoke (docs/RESILIENCE.md): the same
open-loop load, but with deterministic fault injection live on the
dispatch path (``serving.dispatch`` raise + delay plans,
mxnet_tpu/faultinject.py) and one hitless ``reload()`` fired mid-run. The
gate (with ``--check``) asserts ZERO hung futures (every request resolves
with a terminal state: completed | shed | deadline-failed |
injected-fault-after-retry), zero post-warmup retraces/compiles, the
reload applied, p99 of *completed* requests within ``--p99-bound-ms``, and
the engine back to ``healthy`` once injection stops.

    python tools/serve_bench.py --model mlp --qps 200 --duration 3 --json
    python tools/serve_bench.py --model lenet --compare-batch1 --check
    python tools/serve_bench.py --model mlp --chaos --qps 150 --duration 2 --check
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

ITEM_SHAPES = {
    "mlp": (784,),
    "lenet": (1, 28, 28),
    "resnet-18": (3, 32, 32),
}


def _model_kwargs(name):
    """get_symbol kwargs for an ITEM_SHAPES model — shared between the
    in-process builders and the fleet replica spec, so replicas build
    EXACTLY the model the baseline measures."""
    kwargs = {"num_classes": 10}
    if name.startswith("resnet"):
        kwargs["image_shape"] = ",".join(str(d)
                                         for d in ITEM_SHAPES[name])
    return kwargs


def _build_model(name):
    """Symbol + random host-side params. Shapes come from ``infer_shape``,
    not a bind: the fleet leg's parent must not touch a device its replica
    children need (a chip belongs to one process)."""
    from mxnet_tpu import models

    item = ITEM_SHAPES[name]
    net = models.get_symbol(name, **_model_kwargs(name))
    arg_shapes, _, aux_shapes = net.infer_shape(data=(1,) + item)
    rs = np.random.RandomState(0)
    arg_params = {k: (rs.randn(*shape) * 0.1).astype("float32")
                  for k, shape in zip(net.list_arguments(), arg_shapes)
                  if k not in ("data", "softmax_label")}
    aux_params = {k: np.abs(rs.randn(*shape)).astype("float32") + 0.5
                  for k, shape in zip(net.list_auxiliary_states(),
                                      aux_shapes)}
    return net, arg_params, aux_params, item


def _percentiles(lat_ms):
    if not lat_ms:
        return None, None
    return (float(np.percentile(lat_ms, 50)),
            float(np.percentile(lat_ms, 99)))


def _hist_delta_quantiles(name, warm_buckets):
    """Engine-side histogram quantiles for timer ``name`` over the
    measured window only: sparse-bucket delta against the pre-window
    snapshot, read back as {"p50": ms, "p95": ms, "p99": ms}."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import histogram as _hg

    end = telemetry.hist_buckets().get(name, {})
    warm = warm_buckets.get(name, {})
    db = {k: v - warm.get(k, 0) for k, v in end.items()
          if v - warm.get(k, 0) > 0}
    q = _hg.quantiles_from_buckets(db)
    q["count"] = sum(db.values())
    return q


def _quantile_agreement(hist_q, client_p50, client_p99,
                        abs_ms=(15.0, 50.0), rel=(0.5, 0.75)):
    """Cross-check histogram p50/p99 against client-side request-list
    percentiles: each must agree within max(abs floor, rel fraction) —
    loose enough for the ~10% bucket error + scheduling noise, tight
    enough to catch unit errors and a histogram measuring the wrong
    thing. Returns (ok, detail)."""
    detail = {"hist_p50_ms": round(hist_q.get("p50", 0.0), 3),
              "hist_p99_ms": round(hist_q.get("p99", 0.0), 3),
              "client_p50_ms": None if client_p50 is None
              else round(client_p50, 3),
              "client_p99_ms": None if client_p99 is None
              else round(client_p99, 3),
              "samples": hist_q.get("count", 0)}
    if not hist_q.get("count") or client_p50 is None:
        return False, detail
    ok = True
    for key, cli, a, r in (("p50", client_p50, abs_ms[0], rel[0]),
                           ("p99", client_p99, abs_ms[1], rel[1])):
        h = hist_q.get(key, 0.0)
        tol = max(a, r * max(cli, h))
        if abs(h - cli) > tol:
            ok = False
    detail["agree"] = ok
    return ok, detail


def _mk_engine(net, arg_params, aux_params, item, buckets, max_delay_ms,
               cache_dir, tag):
    from mxnet_tpu.serving import InferenceEngine, PersistentExecutableCache

    cache = PersistentExecutableCache(net, arg_params, aux_params,
                                      cache_dir=cache_dir,
                                      model_key=tag)
    return InferenceEngine(cache, {"data": item}, buckets=buckets,
                           max_delay_ms=max_delay_ms, name=tag)


def _counters():
    from mxnet_tpu import telemetry

    return dict(telemetry.counters())


def _open_loop(eng, item, qps, duration, rows):
    """Submit at the target rate for ``duration`` seconds; returns
    (latencies_ms, completed, elapsed, offered)."""
    rs = np.random.RandomState(1)
    payloads = [rs.rand(rows, *item).astype("float32") for _ in range(8)]
    futs = []
    start = time.perf_counter()
    n = 0
    interval = 1.0 / qps
    while True:
        now = time.perf_counter()
        if now - start >= duration:
            break
        target = start + n * interval
        if target > now:
            time.sleep(target - now)
        t0 = time.perf_counter()
        try:
            futs.append((t0, eng.submit({"data": payloads[n % 8]})))
        except Exception:
            futs.append((t0, None))  # backpressure drop counts as offered
        n += 1
    lat = []
    dropped = 0
    for t0, f in futs:
        if f is None:
            dropped += 1
            continue
        f.result(timeout=60.0)
        lat.append((f.done_at - t0) * 1000.0)
    elapsed = time.perf_counter() - start
    return lat, len(lat), elapsed, n, dropped


def _closed_loop(eng, item, n_requests, rows):
    """Saturation: all requests in flight at once; returns QPS."""
    rs = np.random.RandomState(2)
    x = rs.rand(rows, *item).astype("float32")
    t0 = time.perf_counter()
    futs = [eng.submit({"data": x}) for _ in range(n_requests)]
    for f in futs:
        f.result(timeout=120.0)
    return n_requests / (time.perf_counter() - t0)


def bench_engine(args):
    from mxnet_tpu import telemetry

    net, arg_params, aux_params, item = _build_model(args.model)
    buckets = tuple(int(b) for b in args.buckets.split(","))
    eng = _mk_engine(net, arg_params, aux_params, item, buckets,
                     args.max_delay_ms, args.cache_dir, args.model)
    eng.start()  # warmup compiles + seals here
    # burn-in: first post-warmup dispatch pays one-time jax dispatch-path
    # setup; keep it out of the measured window
    eng.infer({"data": np.zeros((args.rows,) + item, "float32")})
    c_warm = _counters()
    hb_warm = telemetry.hist_buckets()
    lat, completed, elapsed, offered, dropped = _open_loop(
        eng, item, args.qps, args.duration, args.rows)
    c_end = _counters()
    p50, p99 = _percentiles(lat)
    # engine-vs-client agreement: the serving.request timer histogram
    # (submit -> delivery, measured in _dispatch) must tell the same
    # latency story the client request list does
    _, hist_detail = _quantile_agreement(
        _hist_delta_quantiles("serving.request", hb_warm), p50, p99,
        abs_ms=(10.0, 25.0), rel=(0.4, 0.6))
    items = c_end.get("serving.batch_items", 0) - \
        c_warm.get("serving.batch_items", 0)
    capacity = c_end.get("serving.batch_capacity", 0) - \
        c_warm.get("serving.batch_capacity", 0)
    res = {
        "mode": "engine",
        "model": args.model,
        "buckets": list(buckets),
        "max_delay_ms": args.max_delay_ms,
        "offered_qps": args.qps,
        "qps": round(completed / elapsed, 2) if elapsed else 0.0,
        "requests": offered,
        "completed": completed,
        "dropped": dropped,
        "p50_ms": None if p50 is None else round(p50, 3),
        "p99_ms": None if p99 is None else round(p99, 3),
        "engine_hist": hist_detail,
        "batches": c_end.get("serving.batches", 0)
        - c_warm.get("serving.batches", 0),
        "batch_occupancy": round(items / capacity, 4) if capacity else None,
        "retraces_post_warmup": c_end.get("executor.retrace", 0)
        - c_warm.get("executor.retrace", 0),
        "compiles_post_warmup": c_end.get("executor.compile", 0)
        - c_warm.get("executor.compile", 0),
    }
    if args.compare_batch1:
        n_req = max(64, int(args.qps * min(args.duration, 2)))
        qps_b = _closed_loop(eng, item, n_req, args.rows)
        eng.close()
        eng1 = _mk_engine(net, arg_params, aux_params, item, (args.rows,),
                          0.0, None, args.model + "-b1")
        eng1.start()
        qps_1 = _closed_loop(eng1, item, n_req, args.rows)
        eng1.close()
        res["qps_batched_saturated"] = round(qps_b, 2)
        res["qps_batch1_saturated"] = round(qps_1, 2)
        res["batching_speedup"] = round(qps_b / qps_1, 2) if qps_1 else None
    else:
        eng.close()
    return res


def bench_decode(args):
    from mxnet_tpu import telemetry
    from mxnet_tpu.serving import PagedKVDecoder

    cfg = dict(vocab_size=256, num_layers=2, num_heads=2, model_dim=64,
               ffn_dim=128)
    S = 64
    params = _decode_params(cfg, S)
    B = args.rows
    dec = PagedKVDecoder(params, max_len=S, page_size=8, lanes=B,
                         prefill_len=16, pos_len=S, cache_dir=args.cache_dir,
                         **cfg)
    dec.warmup()
    rs = np.random.RandomState(1)
    prompts = list(rs.randint(1, 256, (B, 8)).astype("float32"))

    def admit_all():
        """One lane per prompt: {seq id: its first token}."""
        toks = {}
        for p in prompts:
            sid, logits = dec.admit(p)
            toks[sid] = int(np.argmax(logits))
        return toks

    def retire_all(toks):
        for sid in toks:
            dec.retire(sid)

    K = max(0, int(args.megastep_k))
    if K > 1:
        # compile + seal the K-step megastep program BEFORE the counter
        # snapshot, exactly like warmup() does for the per-step executables
        # — the measured window must replay it with zero compiles
        toks = admit_all()
        dec.step_megastep(toks, k=K)
        retire_all(toks)
    def arg_max(logits):
        return {sid: int(np.argmax(row)) for sid, row in logits.items()}

    c_warm = _counters()
    # one burn-in step: the first post-warmup dispatch pays one-time jax
    # dispatch-path setup that would otherwise read as a fake p99 outlier
    toks = arg_max(dec.step(admit_all()))
    steps = min(int(args.qps * args.duration), S - 8 - 2) or 1
    gap_t = telemetry.timer("dispatch.host_gap")
    lat = []
    gap0_ms = gap_t.total_ms
    t0 = time.perf_counter()
    for _ in range(steps):
        t1 = time.perf_counter()
        # graphlint: waive GL702 -- measuring the per-token loop IS the bench
        toks = arg_max(dec.step(toks))
        lat.append((time.perf_counter() - t1) * 1000.0)
    elapsed = time.perf_counter() - t0
    gap_ms = gap_t.total_ms - gap0_ms
    retire_all(toks)
    p50, p99 = _percentiles(lat)
    res = {
        "mode": "kv_decode",
        "model": "transformer-decode",
        "streams": B,
        "decode_steps": steps,
        "qps": round(B * steps / elapsed, 2),  # tokens/s across streams
        "p50_ms": round(p50, 3),
        "p99_ms": round(p99, 3),
        "batch_occupancy": 1.0,
        # host time between one executable's return and the next enqueue
        # (the dispatch.host_gap timer), amortized per generated token
        "host_gap_ms": round(gap_ms, 3),
        "host_gap_per_token": round(gap_ms / (B * steps), 6),
    }
    if K > 1:
        # megastep leg: parity first (K-chunked greedy must be
        # token-identical to single-step greedy), then a timed window of
        # K-token dispatches for the ≥2x host-gap-per-token gate
        n_par = 2 * K + 1
        seq = dec.greedy(prompts, n_par, k=1)
        mega = dec.greedy(prompts, n_par, k=K)
        parity = all(np.array_equal(a, b) for a, b in zip(seq, mega))
        def last(chunk):
            return {sid: int(ids[-1]) for sid, ids in chunk.items()}

        # burn-in megastep, then as many full-K chunks as positions allow
        toks = last(dec.step_megastep(admit_all(), k=K))
        m_chunks = max(1, (S - len(prompts[0]) - K) // K)
        mgap0_ms = gap_t.total_ms
        t0m = time.perf_counter()
        for _ in range(m_chunks):
            # graphlint: waive GL702 -- measuring the megastep loop IS the bench
            toks = last(dec.step_megastep(toks, k=K))
        m_elapsed = time.perf_counter() - t0m
        m_gap_ms = gap_t.total_ms - mgap0_ms
        retire_all(toks)
        m_tokens = B * m_chunks * K
        m_gap_per_tok = round(m_gap_ms / m_tokens, 6)
        res["megastep"] = {
            "k": K,
            "chunks": m_chunks,
            "tokens_per_s": round(m_tokens / m_elapsed, 2),
            "host_gap_per_token": m_gap_per_tok,
            "parity_token_identical": parity,
            "k_sweep": [
                {"k": 1, "tokens_per_s": res["qps"],
                 "host_gap_per_token": res["host_gap_per_token"]},
                {"k": K, "tokens_per_s": round(m_tokens / m_elapsed, 2),
                 "host_gap_per_token": m_gap_per_tok},
            ],
        }
    c_end = _counters()
    res["retraces_post_warmup"] = c_end.get("executor.retrace", 0) \
        - c_warm.get("executor.retrace", 0)
    res["compiles_post_warmup"] = c_end.get("executor.compile", 0) \
        - c_warm.get("executor.compile", 0)
    return res


def _decode_params(cfg, S, seed=0):
    """Random transformer weights straight from the training graph's own
    shapes (the decode/prefill/chunk programs bind the same names)."""
    from mxnet_tpu.models import transformer as _tf
    from mxnet_tpu import context as _ctx

    probe = _tf.get_symbol(seq_len=S, **cfg).simple_bind(
        _ctx.current_context(), grad_req="null", data=(1, S),
        softmax_label=(1, S))
    rs = np.random.RandomState(seed)
    return {k: (rs.randn(*a.shape) * 0.1).astype("float32")
            for k, a in probe.arg_dict.items()
            if k not in ("data", "softmax_label")}


def _zipf_prompts(rs, n_requests, vocab, prefixes, suffix_len, alpha):
    """Shared-prefix workload: each request draws its prompt head from
    ``prefixes`` with Zipf(alpha) popularity and appends a unique random
    suffix — the distribution real multi-tenant serving sees (few hot
    system prompts, long unique tails)."""
    ranks = np.arange(1, len(prefixes) + 1, dtype=np.float64)
    pz = ranks ** -float(alpha)
    pz /= pz.sum()
    picks = rs.choice(len(prefixes), size=n_requests, p=pz)
    out = []
    for i in picks:
        sfx = rs.randint(1, vocab, (suffix_len,))
        out.append(np.concatenate([prefixes[int(i)],
                                   sfx]).astype("float32"))
    return out


def bench_prefix_spec(args):
    """--workload zipf-prefix: the shared-prefix cache + speculative
    decoding leg. One decoder with the prefix cache OFF is the latency
    baseline; the same workload then replays against the COW prefix
    cache, and a draft-verify SpeculativeDecoder races plain greedy."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.serving import PagedKVDecoder, SpeculativeDecoder

    cfg = dict(vocab_size=256, num_layers=2, num_heads=2, model_dim=64,
               ffn_dim=128)
    S = 64
    params = _decode_params(cfg, S)
    n_params = int(sum(int(np.prod(v.shape)) for v in params.values()))

    C = 8                       # prefix chunk == page size
    plen = 3 * C                # shared head: 3 cacheable chunks
    suffix_len = C              # unique tail: 1 chunk per request
    n_decode = 4                # decode tail per request
    n_req = max(24, min(200, int(args.qps * args.duration)))
    rs = np.random.RandomState(0)
    prefixes = [rs.randint(1, cfg["vocab_size"], (plen,))
                for _ in range(4)]
    prompts = _zipf_prompts(rs, n_req, cfg["vocab_size"], prefixes,
                            suffix_len, alpha=1.1)
    serve = dict(max_len=S, page_size=C, lanes=4,
                 prefill_len=plen + suffix_len, pos_len=S,
                 cache_dir=args.cache_dir)

    def _run_requests(dec, plist):
        lat = []
        for p in plist:
            t0 = time.perf_counter()
            sid, logits = dec.admit(p)
            # graphlint: waive GL703 -- one argmax per admitted request
            tok = int(np.argmax(logits))
            for _ in range(n_decode):
                # graphlint: waive GL702 -- the per-request decode tail IS the workload
                out = dec.step({sid: tok})
                # graphlint: waive GL703 -- bench workload loop, one id per step
                tok = int(np.argmax(out[sid]))
            dec.retire(sid)
            lat.append((time.perf_counter() - t0) * 1000.0)
        return lat

    base = PagedKVDecoder(params, prefix_cache=False, **cfg,
                          **serve).warmup()
    cached = PagedKVDecoder(params, prefix_cache=True, prefix_chunk=C,
                            **cfg, **serve).warmup()
    # burn-in (one-time jax dispatch-path setup) with prompts ALIEN to
    # the workload's prefixes, so the measured hit rate is untouched
    alien = [np.concatenate([rs.randint(1, 256, (plen,)),
                             rs.randint(1, 256, (suffix_len,))
                             ]).astype("float32") for _ in range(2)]
    _run_requests(base, alien[:1])
    _run_requests(cached, alien[:1])
    # bitwise cached-vs-cold: the SAME prompt admitted cold, retired,
    # then admitted again off the cache must produce identical logits
    sid, cold = cached.admit(alien[1])
    cached.retire(sid)
    sid, warm2 = cached.admit(alien[1])
    cached.retire(sid)
    bitwise = bool(np.array_equal(cold, warm2))

    # build + warm the speculative pair BEFORE the compile snapshot:
    # the zero-post-warmup gate below covers BOTH measured legs
    g = max(1, int(args.spec_gamma))
    dl = int(args.spec_draft_layers) or cfg["num_layers"]
    sserve = dict(max_len=S, page_size=C, lanes=1, prefill_len=16,
                  pos_len=S, prefix_cache=False,
                  cache_dir=args.cache_dir)
    spec = SpeculativeDecoder.build(params, draft_layers=dl, gamma=g,
                                    **cfg, **sserve).warmup()
    sbase = PagedKVDecoder(params, **cfg, **sserve).warmup()
    n_tok = 24
    sprompts = [rs.randint(1, cfg["vocab_size"], (8,)).astype("float32")
                for _ in range(6)]
    # parity subcheck doubles as the burn-in for both timed paths
    parity = bool(np.array_equal(
        spec.greedy(sprompts[0], n_tok),
        sbase.greedy([sprompts[0]], n_tok, k=1)[0]))

    c_warm = _counters()
    t0 = time.perf_counter()
    lat_base = _run_requests(base, prompts)
    lat_cache = _run_requests(cached, prompts)
    elapsed = time.perf_counter() - t0
    c_mid = _counters()

    hits = c_mid.get("serving.prefix_hits", 0) \
        - c_warm.get("serving.prefix_hits", 0)
    misses = c_mid.get("serving.prefix_misses", 0) \
        - c_warm.get("serving.prefix_misses", 0)
    saved = c_mid.get("serving.prefill_tokens_saved", 0) \
        - c_warm.get("serving.prefill_tokens_saved", 0)
    p50b, p99b = _percentiles(lat_base)
    p50c, p99c = _percentiles(lat_cache)
    prefix = {
        "chunk_hits": hits,
        "chunk_misses": misses,
        "hit_rate": round(hits / (hits + misses), 4)
        if hits + misses else 0.0,
        "tokens_saved": saved,
        # ~2 FLOPs per weight per token (matmul-dominated forward): the
        # standard estimate, reported as such
        "param_count": n_params,
        "prefill_flops_saved": int(saved * 2 * n_params),
        "pages_shared": c_mid.get("serving.pages_shared", 0)
        - c_warm.get("serving.pages_shared", 0),
        "cow_copies": c_mid.get("serving.cow_copies", 0)
        - c_warm.get("serving.cow_copies", 0),
        "evictions": c_mid.get("serving.prefix_evictions", 0)
        - c_warm.get("serving.prefix_evictions", 0),
        "p50_ms": round(p50c, 3), "p99_ms": round(p99c, 3),
        "baseline_p50_ms": round(p50b, 3),
        "baseline_p99_ms": round(p99b, 3),
        "bitwise_cached_vs_cold": bitwise,
        "cache": cached.stats().get("prefix_cache"),
    }

    # ---- speculative leg: draft proposes gamma tokens per round, the
    # target scores all gamma+1 in one rectangular verify dispatch
    c_sp0 = c_mid
    sl_base, sl_spec = [], []
    for p in sprompts:
        t1 = time.perf_counter()
        sbase.greedy([p], n_tok, k=1)
        sl_base.append((time.perf_counter() - t1) * 1000.0 / n_tok)
    for p in sprompts:
        t1 = time.perf_counter()
        spec.greedy(p, n_tok)
        sl_spec.append((time.perf_counter() - t1) * 1000.0 / n_tok)
    c_end = _counters()
    proposed = c_end.get("spec.proposed_tokens", 0) \
        - c_sp0.get("spec.proposed_tokens", 0)
    accepted = c_end.get("spec.accepted_tokens", 0) \
        - c_sp0.get("spec.accepted_tokens", 0)
    sp50b, sp99b = _percentiles(sl_base)
    sp50s, sp99s = _percentiles(sl_spec)
    spec_res = {
        "gamma": g, "draft_layers": dl,
        "proposed_tokens": proposed, "accepted_tokens": accepted,
        "accepted_rate": round(accepted / proposed, 4)
        if proposed else 0.0,
        "rollbacks": c_end.get("spec.rollbacks", 0)
        - c_sp0.get("spec.rollbacks", 0),
        "p50_ms_per_token": round(sp50s, 4),
        "p99_ms_per_token": round(sp99s, 4),
        "baseline_p50_ms_per_token": round(sp50b, 4),
        "baseline_p99_ms_per_token": round(sp99b, 4),
        "parity_token_identical": parity,
    }
    return {
        "mode": "prefix_spec",
        "model": "transformer-decode",
        "workload": "zipf-prefix",
        "requests": n_req,
        "prefixes": len(prefixes),
        "zipf_alpha": 1.1,
        "prompt_len": plen + suffix_len,
        "prefix_chunk": C,
        "qps": round(n_req / elapsed, 2) if elapsed else 0.0,
        "p50_ms": prefix["p50_ms"], "p99_ms": prefix["p99_ms"],
        "prefix": prefix,
        "spec": spec_res,
        "retraces_post_warmup": c_end.get("executor.retrace", 0)
        - c_warm.get("executor.retrace", 0),
        "compiles_post_warmup": c_end.get("executor.compile", 0)
        - c_warm.get("executor.compile", 0),
    }


def bench_chaos(args):
    """Open-loop load under injected dispatch faults + one mid-run hitless
    reload; classifies every request's terminal state."""
    import mxnet_tpu  # noqa: F401  (package import before submodules)
    from mxnet_tpu import faultinject as fi
    from mxnet_tpu.serving import (InferenceEngine,
                                   PersistentExecutableCache,
                                   ServeDeadlineError, ServeOverloadError)

    net, arg_params, aux_params, item = _build_model(args.model)
    buckets = tuple(int(b) for b in args.buckets.split(","))
    cache = PersistentExecutableCache(net, arg_params, aux_params,
                                      cache_dir=args.cache_dir,
                                      model_key=args.model + "-chaos")
    eng = InferenceEngine(cache, {"data": item}, buckets=buckets,
                          max_delay_ms=args.max_delay_ms,
                          name=args.model + "-chaos",
                          deadline_ms=args.chaos_deadline_ms,
                          health_window_s=1.0)
    eng.start()
    eng.infer({"data": np.zeros((args.rows,) + item, "float32")})  # burn-in
    c_warm = _counters()
    fi.reset_stats()
    # the weights the mid-run reload swaps in (same shapes — zero retraces)
    new_params = {k: (v * 1.05 + 0.01).astype("float32")
                  for k, v in arg_params.items()}

    rs = np.random.RandomState(1)
    payloads = [rs.rand(args.rows, *item).astype("float32")
                for _ in range(8)]
    futs = []          # (t_submit, future or terminal-class string)
    reload_fut = None
    start = time.perf_counter()
    interval = 1.0 / args.qps
    n = 0
    half = args.duration / 2.0
    with fi.inject("serving.dispatch", "raise", prob=args.chaos_fail_prob,
                   seed=7), \
         fi.inject("serving.dispatch", "delay_ms",
                   prob=args.chaos_delay_prob, seed=11,
                   arg=args.chaos_delay_ms):
        while True:
            now = time.perf_counter()
            if now - start >= args.duration:
                break
            if reload_fut is None and now - start >= half:
                reload_fut = eng.reload(new_params)
            target = start + n * interval
            if target > now:
                time.sleep(target - now)
            t0 = time.perf_counter()
            try:
                futs.append((t0, eng.submit({"data": payloads[n % 8]})))
            except ServeOverloadError:
                futs.append((t0, "shed"))
            except Exception:
                futs.append((t0, "rejected"))  # backpressure etc.
            n += 1
    elapsed = time.perf_counter() - start

    counts = {"completed": 0, "shed": 0, "deadline": 0, "fault": 0,
              "rejected": 0, "hung": 0}
    lat = []
    for t0, f in futs:
        if isinstance(f, str):
            counts[f] += 1
            continue
        try:
            f.result(timeout=60.0)
            counts["completed"] += 1
            lat.append((f.done_at - t0) * 1000.0)
        except ServeDeadlineError:
            counts["deadline"] += 1
        except ServeOverloadError:
            counts["shed"] += 1
        except Exception:
            # terminal only if the future actually resolved; an unresolved
            # future after 60s is a HUNG request — the one chaos outcome
            # that must never happen
            counts["fault" if f.done() else "hung"] += 1
    reload_ok = False
    if reload_fut is not None:
        try:
            reload_ok = bool(reload_fut.result(timeout=30.0))
        except Exception:
            reload_ok = False

    # injection is over (context exited): a short clean run, then let the
    # recent-fault window drain — the engine must report healthy again
    for _ in range(10):
        eng.infer({"data": payloads[0]}, timeout=30.0)
    time.sleep(eng.health_window_s + 0.2)
    health = eng.health()
    c_end = _counters()
    fired = fi.stats()
    p50, p99 = _percentiles(lat)
    eng.close()
    return {
        "mode": "chaos",
        "model": args.model,
        "buckets": list(buckets),
        "offered_qps": args.qps,
        "duration_s": args.duration,
        "requests": n,
        "elapsed_s": round(elapsed, 3),
        "resolved": counts,
        "qps": round(counts["completed"] / elapsed, 2) if elapsed else 0.0,
        "p50_ms": None if p50 is None else round(p50, 3),
        "p99_ms": None if p99 is None else round(p99, 3),
        "reload_applied": reload_ok,
        "health_after": health,
        "injected": fired,
        "dispatch_retries": c_end.get("serving.dispatch_retries", 0)
        - c_warm.get("serving.dispatch_retries", 0),
        "deadline_expired": c_end.get("serving.deadline_expired", 0)
        - c_warm.get("serving.deadline_expired", 0),
        "retraces_post_warmup": c_end.get("executor.retrace", 0)
        - c_warm.get("executor.retrace", 0),
        "compiles_post_warmup": c_end.get("executor.compile", 0)
        - c_warm.get("executor.compile", 0),
        "p99_bound_ms": args.p99_bound_ms,
    }


def bench_fleet(args):
    """The fleet smoke (docs/SERVING.md §Fleet): N replica PROCESSES
    behind the router under open-loop load with a seeded chaos plan —
    injected router-dispatch faults, one replica SIGKILLed mid-run (the
    supervisor restarts it), and one fleet-wide hitless rollout. Reports aggregate
    QPS/p99, redispatches, restarts, and the single-replica closed-loop
    baseline the aggregate must beat."""
    import shutil
    import tempfile
    import threading

    import mxnet_tpu  # noqa: F401
    from mxnet_tpu import faultinject as fi
    from mxnet_tpu import telemetry
    from mxnet_tpu.serving import ServeOverloadError, ServeDeadlineError
    from mxnet_tpu.serving.fleet import (Fleet, RpcClient, save_params_npz,
                                         FleetRolloutError)

    net, arg_params, aux_params, item = _build_model(args.model)
    buckets = [int(b) for b in args.buckets.split(",")]
    workdir = tempfile.mkdtemp(prefix="mxtpu_fleet_bench_")
    params_path = os.path.join(workdir, "params.npz")
    save_params_npz(params_path, arg_params, aux_params)
    spec = {"model": args.model,
            "model_kwargs": _model_kwargs(args.model),
            "item_shapes": {"data": list(item)},
            "buckets": buckets,
            "params": params_path,
            "engine": {"max_delay_ms": args.max_delay_ms},
            # replica subprocesses don't inherit the bench's in-process
            # set_mode(): ship the mode so their spans/timers exist for
            # the merged trace and the health() telemetry snapshots
            "telemetry": telemetry.mode(),
            "heartbeat_ms": 300}
    n = args.fleet_replicas
    rs = np.random.RandomState(1)
    payloads = [rs.rand(args.rows, *item).astype("float32")
                for _ in range(8)]
    new_params = {k: (v * 1.02 + 0.01).astype("float32")
                  for k, v in arg_params.items()}
    res = {"mode": "fleet", "model": args.model, "replicas": n,
           "buckets": buckets}
    fi.reset_stats()
    # latency discipline under oversubscription: per-request deadlines
    # purge stuck work, the router's absolute shed cap bounds the queueing
    # a completed request can have suffered — both scale off the p99 bound
    deadline_ms = args.p99_bound_ms / 2.0
    # SLO gate, windows scaled to bench length (env wins if already set):
    # err_pct is what the seeded 100% fault burst below must trip, and
    # what the recovery traffic must clear once the window rolls past
    os.environ.setdefault("MXNET_SLO_WINDOW_S", "4")
    os.environ.setdefault("MXNET_SLO_SHORT_WINDOW_S", "1")
    slo_spec = ("p99_ms:%g,err_pct:2,avail_pct:50"
                % args.p99_bound_ms)
    fleet = Fleet(spec, n_replicas=n, workdir=workdir,
                  router_kwargs=dict(
                      workers=max(8, 2 * n), health_interval_ms=100,
                      stale_ms=1500, shed_ms=args.p99_bound_ms / 4.0,
                      dispatch_wait_ms=30000, slo=slo_spec))
    try:
        t_up = time.perf_counter()
        fleet.start()
        res["startup_s"] = round(time.perf_counter() - t_up, 1)
        router = fleet.router

        # ---- single-replica closed-loop baseline through the SAME RPC
        # path. The GATE baseline is the textbook closed loop — ONE
        # client, next arrival waits for the completion — which is what a
        # single replica gives a synchronous upstream; the fleet's win
        # over it comes from replication hiding the per-request
        # batching/dispatch latency (on a multi-core host, from real
        # parallelism too). The 4-way saturated number is reported
        # alongside for the multi-core reading.
        addr = fleet.supervisor.addresses()[0]
        n_base = max(64, int(args.qps))

        def _closed(worker_idx, counts):
            cli = RpcClient(addr, timeout_s=60.0)
            for i in range(max(1, n_base // max(1, len(counts)))):
                cli.call("infer",
                         inputs={"data": payloads[(worker_idx + i) % 8]})
                counts[worker_idx] += 1
            cli.close()

        def _run_closed(conc):
            counts = [0] * conc
            t0 = time.perf_counter()
            ts = [threading.Thread(target=_closed, args=(i, counts))
                  for i in range(conc)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            return sum(counts) / (time.perf_counter() - t0)

        base_qps = _run_closed(1)
        sat_qps = _run_closed(4)
        res["qps_single_replica_closed"] = round(base_qps, 2)
        res["qps_single_replica_saturated"] = round(sat_qps, 2)
        res["host_cores"] = os.cpu_count()

        # ---- open-loop fleet load with the chaos plan: offered rate
        # oversubscribes a single replica's saturated capacity, so the
        # completed aggregate reflects what the REPLICATION carried
        offered_qps = max(args.qps, 1.6 * sat_qps)
        duration = args.duration
        interval = 1.0 / offered_qps
        futs = []
        rollout_result = {}
        victim_pid = None
        rollout_thread = None

        def _do_rollout():
            try:
                rollout_result["res"] = fleet.rollout(
                    new_params, drain_timeout_s=60.0)
            except FleetRolloutError as exc:
                rollout_result["error"] = str(exc)

        with fi.inject("fleet.dispatch", "raise",
                       prob=args.chaos_fail_prob, seed=7):
            start = time.perf_counter()
            k = 0
            while True:
                now = time.perf_counter()
                if now - start >= duration:
                    break
                if victim_pid is None and now - start >= duration / 3.0:
                    victim_pid = fleet.supervisor.kill_replica(0)
                if rollout_thread is None and \
                        now - start >= duration / 2.0:
                    rollout_thread = threading.Thread(target=_do_rollout)
                    rollout_thread.start()
                target = start + k * interval
                if target > now:
                    time.sleep(target - now)
                t0 = time.perf_counter()
                try:
                    futs.append((t0, router.submit(
                        {"data": payloads[k % 8]},
                        deadline_ms=deadline_ms)))
                except ServeOverloadError:
                    futs.append((t0, "shed"))
                except Exception:
                    futs.append((t0, "rejected"))
                k += 1
            elapsed = time.perf_counter() - start
            counts = {"completed": 0, "shed": 0, "deadline": 0,
                      "fault": 0, "rejected": 0, "hung": 0}
            lat = []
            last_done = start
            for t0, f in futs:
                if isinstance(f, str):
                    counts[f] += 1
                    continue
                try:
                    f.result(timeout=60.0)
                    counts["completed"] += 1
                    lat.append((f.done_at - t0) * 1000.0)
                    last_done = max(last_done, f.done_at)
                except ServeDeadlineError:
                    counts["deadline"] += 1
                except ServeOverloadError:
                    counts["shed"] += 1
                except Exception:
                    counts["fault" if f.done() else "hung"] += 1
            # honest aggregate-QPS denominator: completions draining
            # AFTER the submission window count only if the window is
            # stretched to cover them — the closed-loop baseline divides
            # by time-to-last-completion, so this must too
            span = max(elapsed, last_done - start)
        if rollout_thread is not None:
            rollout_thread.join(timeout=120.0)

        # chaos over: the fleet must return to full strength
        fleet.supervisor.wait_ready(n, timeout_s=120.0)
        deadline = time.perf_counter() + 30.0
        while time.perf_counter() < deadline and \
                router.health()["state"] != "healthy":
            time.sleep(0.2)
        p50, p99 = _percentiles(lat)
        states = fleet.supervisor.states()
        res.update({
            "offered_qps": round(offered_qps, 1),
            "duration_s": duration,
            "requests": k,
            "elapsed_s": round(elapsed, 3),
            "drain_tail_s": round(span - elapsed, 3),
            "resolved": counts,
            "qps": round(counts["completed"] / span, 2)
            if span else 0.0,
            "p50_ms": None if p50 is None else round(p50, 3),
            "p99_ms": None if p99 is None else round(p99, 3),
            "victim_killed": victim_pid is not None,
            "replica_restarts": sum(d["restarts"]
                                    for d in states.values()),
            "rollout": rollout_result.get(
                "res", {"error": rollout_result.get("error",
                                                    "never ran")}),
            "router_counts": router.health()["counts"],
            "redispatches": router.health()["counts"]["redispatched"],
            "injected": fi.stats(),
            "fleet_health_after": router.health()["state"],
            "p99_bound_ms": args.p99_bound_ms,
        })

        # ---- observability plane (docs/OBSERVABILITY.md §Fleet) ----
        # fleet rollup + fleet-vs-client latency agreement: the router's
        # fleet.request histogram brackets exactly what clients timed
        # over the load window (metrics read BEFORE the SLO burst below
        # adds traffic), so its p50/p99 must tell the same story
        m = router.metrics()
        fq = (m.get("latency_ms") or {}).get("fleet.request", {})
        _, agree = _quantile_agreement(
            {"p50": fq.get("p50", 0.0), "p99": fq.get("p99", 0.0),
             "count": fq.get("count", 0)}, p50, p99,
            abs_ms=(25.0, 75.0), rel=(0.6, 0.8))
        res["fleet_metrics"] = m
        res["fleet_hist_vs_client"] = agree

        # merged fleet trace: one clock-aligned timeline whose request
        # chains must join >=2 processes (router + replica) on a single
        # router-minted trace_id
        if telemetry.tracing():
            merged = fleet.collect_fleet_trace()
            res["fleet_trace"] = _fleet_trace_stats(merged)
            if args.trace_out:
                with open(args.trace_out, "w") as f:
                    json.dump(merged, f)
                res["fleet_trace"]["written"] = args.trace_out

        # seeded fault burst: 100% fleet.dispatch raises exhaust the
        # redispatch budget -> router errors -> slo.burn_rate trips; then
        # clean recovery traffic must CLEAR it once the window rolls
        slo_burst = {"fired": False, "cleared": False, "peak_burn": 0.0}
        with fi.inject("fleet.dispatch", "raise", prob=1.0, seed=13):
            t_burst = time.perf_counter()
            while time.perf_counter() - t_burst < 12.0:
                try:
                    router.infer({"data": payloads[0]}, timeout=20.0)
                except Exception:
                    pass
                s = router.metrics().get("slo")
                if s:
                    slo_burst["peak_burn"] = max(slo_burst["peak_burn"],
                                                 s.get("burn_rate", 0.0))
                    if not s.get("ok", True):
                        slo_burst["fired"] = True
                        break
                time.sleep(0.05)
        t_rec = time.perf_counter()
        while time.perf_counter() - t_rec < 20.0:
            try:
                router.infer({"data": payloads[0]}, timeout=20.0)
            except Exception:
                pass
            s = router.metrics().get("slo")
            if slo_burst["fired"] and s and s.get("ok"):
                slo_burst["cleared"] = True
                break
            time.sleep(0.1)
        res["slo_burst"] = slo_burst
        res["slo_violations"] = router.slo_violations()
    finally:
        fleet.close()
        shutil.rmtree(workdir, ignore_errors=True)
    return res


def _fleet_trace_stats(merged):
    """Summary of a merged fleet trace: how many request chains cross
    process boundaries (>=2 pids joined by one trace_id) — the number the
    --check gate asserts is at least 1."""
    by_tid = {}
    span_pids = set()
    events = merged.get("traceEvents", [])
    for ev in events:
        if ev.get("ph") == "X":
            span_pids.add(ev.get("pid"))
        a = ev.get("args") or {}
        tids = []
        if a.get("trace_id"):
            tids.append(a["trace_id"])
        tids.extend(a.get("trace_ids") or [])
        for tid in tids:
            by_tid.setdefault(tid, set()).add(ev.get("pid"))
    cross = sum(1 for pids in by_tid.values() if len(pids) >= 2)
    other = merged.get("otherData") or {}
    return {"events": len(events), "span_pids": len(span_pids),
            "traced_requests": len(by_tid),
            "cross_process_traces": cross,
            "dropped": other.get("dropped", 0)}


def _check_fleet(res):
    ok = True

    def _fail(msg):
        nonlocal ok
        ok = False
        sys.stderr.write("serve_bench --fleet --check FAILED: %s\n" % msg)

    counts = res["resolved"]
    # zero-lost has two teeth: (a) every issued future resolved within the
    # 60s wait — an unresolved one lands in "hung", the catch-all bucket,
    # so hung==0 IS the lost-request gate; (b) the router's own books must
    # agree with what the clients observed delivered — a router that
    # dropped (or double-delivered) a request can't balance both sides
    if counts["hung"]:
        _fail("%d request(s) HUNG past the 60s resolution wait — lost "
              "to the fleet" % counts["hung"])
    rc = res["router_counts"]
    if rc["completed"] != counts["completed"]:
        _fail("router books claim %d completed but clients observed %d "
              "deliveries — requests lost or double-counted"
              % (rc["completed"], counts["completed"]))
    if not counts["completed"]:
        _fail("no request completed under fleet chaos")
    if not res["victim_killed"]:
        _fail("the chaos plan never killed a replica")
    if res["replica_restarts"] < 1:
        _fail("the supervisor never restarted the killed replica")
    if res["rollout"].get("error") or not res["rollout"].get("applied"):
        _fail("mid-run fleet rollout did not apply: %s" % res["rollout"])
    if not any(k.startswith("fleet.dispatch:")
               for k in res["injected"]):
        _fail("no fleet.dispatch faults were injected: %s"
              % res["injected"])
    if res["fleet_health_after"] != "healthy":
        _fail("fleet did not return to healthy: %r"
              % res["fleet_health_after"])
    base = res["qps_single_replica_closed"]
    if not res["qps"] or res["qps"] <= base:
        _fail("aggregate fleet QPS %.1f did not beat the single-replica "
              "closed-loop baseline %.1f" % (res["qps"] or 0.0, base))
    p99 = res.get("p99_ms")
    if p99 is None or not math.isfinite(p99) or p99 > res["p99_bound_ms"]:
        _fail("p99 of completed requests %r ms outside bound %r ms"
              % (p99, res["p99_bound_ms"]))
    # ---- observability-plane gates (docs/OBSERVABILITY.md §Fleet)
    agree = res.get("fleet_hist_vs_client") or {}
    if not agree.get("agree"):
        _fail("fleet.request histogram p50/p99 disagree with client-side "
              "request percentiles: %s" % agree)
    ft = res.get("fleet_trace")
    if ft is None:
        _fail("no merged fleet trace was collected")
    elif not ft.get("cross_process_traces"):
        _fail("merged fleet trace has no request chain spanning >=2 "
              "processes on one trace_id: %s" % ft)
    burst = res.get("slo_burst") or {}
    if not burst.get("fired"):
        _fail("the seeded fault burst never tripped the SLO burn-rate "
              "gate: %s" % burst)
    if not burst.get("cleared"):
        _fail("the SLO violation did not clear after recovery: %s"
              % burst)
    viol = res.get("slo_violations") or []
    if not any(v.get("kind") == "slo.violation" for v in viol):
        _fail("no structured slo.violation event was recorded: %s" % viol)
    if not any(v.get("kind") == "slo.clear" for v in viol):
        _fail("no structured slo.clear event was recorded: %s" % viol)
    return ok


def _check_chaos(res):
    ok = True

    def _fail(msg):
        nonlocal ok
        ok = False
        sys.stderr.write("serve_bench --chaos --check FAILED: %s\n" % msg)

    counts = res["resolved"]
    if counts["hung"]:
        _fail("%d request(s) HUNG (future unresolved after 60s)"
              % counts["hung"])
    terminal = sum(counts.values())
    if terminal != res["requests"]:
        _fail("resolved %d of %d offered requests" % (terminal,
                                                      res["requests"]))
    if not counts["completed"]:
        _fail("no request completed under chaos")
    if res["retraces_post_warmup"]:
        _fail("post-warmup retraces: %d" % res["retraces_post_warmup"])
    if res["compiles_post_warmup"]:
        _fail("post-warmup compiles: %d" % res["compiles_post_warmup"])
    if not res["reload_applied"]:
        _fail("mid-run reload() did not apply")
    if res["health_after"].get("state") != "healthy":
        _fail("engine did not return to healthy after injection stopped: "
              "%s" % res["health_after"])
    if not any(k.startswith("serving.dispatch:") for k in res["injected"]):
        _fail("no faults were actually injected: %s" % res["injected"])
    if not res["dispatch_retries"]:
        _fail("the dispatch retry path never fired under injected faults")
    p99 = res.get("p99_ms")
    if p99 is None or not math.isfinite(p99) or p99 > res["p99_bound_ms"]:
        _fail("p99 of completed requests %r ms outside bound %r ms"
              % (p99, res["p99_bound_ms"]))
    return ok


def _check_prefix_spec(res):
    ok = True

    def _fail(msg):
        nonlocal ok
        ok = False
        sys.stderr.write("serve_bench --workload zipf-prefix --check "
                         "FAILED: %s\n" % msg)

    pre = res["prefix"]
    if pre["hit_rate"] <= 0.5:
        _fail("prefix chunk hit rate %.3f not > 0.5 under the zipf "
              "workload (%d hits / %d misses)"
              % (pre["hit_rate"], pre["chunk_hits"],
                 pre["chunk_misses"]))
    if pre["tokens_saved"] <= 0 or not pre["prefill_flops_saved"]:
        _fail("no prefill work saved: tokens_saved=%r flops_saved=%r"
              % (pre["tokens_saved"], pre["prefill_flops_saved"]))
    if not pre["bitwise_cached_vs_cold"]:
        _fail("cached admit logits are NOT bitwise identical to the "
              "cold admit of the same prompt")
    sp = res["spec"]
    if not sp["parity_token_identical"]:
        _fail("speculative greedy diverged from non-speculative greedy "
              "(gamma=%d draft_layers=%d)" % (sp["gamma"],
                                              sp["draft_layers"]))
    if sp["accepted_rate"] <= 0.0:
        _fail("accepted-draft rate %.3f not > 0 (%d proposed)"
              % (sp["accepted_rate"], sp["proposed_tokens"]))
    if sp["p50_ms_per_token"] > sp["baseline_p50_ms_per_token"]:
        _fail("speculative p50 %.4f ms/token not <= plain-greedy "
              "baseline %.4f ms/token"
              % (sp["p50_ms_per_token"],
                 sp["baseline_p50_ms_per_token"]))
    if res["retraces_post_warmup"]:
        _fail("post-warmup retraces: %d" % res["retraces_post_warmup"])
    if res["compiles_post_warmup"]:
        _fail("post-warmup compiles: %d" % res["compiles_post_warmup"])
    return ok


def _check(res, trace_families):
    ok = True

    def _fail(msg):
        nonlocal ok
        ok = False
        sys.stderr.write("serve_bench --check FAILED: %s\n" % msg)

    if not res.get("qps"):
        _fail("qps not > 0: %r" % res.get("qps"))
    p99 = res.get("p99_ms")
    if p99 is None or not math.isfinite(p99):
        _fail("p99 not finite: %r" % p99)
    if res.get("retraces_post_warmup"):
        _fail("post-warmup retraces: %d" % res["retraces_post_warmup"])
    if res.get("compiles_post_warmup"):
        _fail("post-warmup compiles: %d" % res["compiles_post_warmup"])
    need = {"serving.dispatch"} if res["mode"] == "engine" \
        else {"serving.decode_step", "serving.paged_admit"}
    ms = res.get("megastep")
    if ms is not None:
        need.add("serving.decode_megastep")
    missing = need - trace_families
    if missing:
        _fail("missing serving.* trace families: %s" % sorted(missing))
    if res["mode"] == "kv_decode" and not res.get("host_gap_per_token"):
        _fail("host_gap_per_token missing or zero — the dispatch.host_gap "
              "timer never ticked on the decode path")
    if ms is not None:
        if not ms.get("parity_token_identical"):
            _fail("megastep K=%d greedy diverged from single-step decode"
                  % ms["k"])
        base = res.get("host_gap_per_token") or 0.0
        if not base or ms["host_gap_per_token"] > 0.5 * base:
            _fail("megastep host_gap_per_token %.6f ms not <= 0.5x the "
                  "K=1 baseline %.6f ms"
                  % (ms["host_gap_per_token"], base))
    if res.get("batching_speedup") is not None \
            and res["batching_speedup"] < 2.0:
        _fail("continuous batching speedup %.2fx < 2x over batch-size-1"
              % res["batching_speedup"])
    if res["mode"] == "engine":
        eh = res.get("engine_hist") or {}
        if not eh.get("agree"):
            _fail("engine-side serving.request histogram p50/p99 disagree "
                  "with client-side request percentiles: %s" % eh)
    return ok


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--model", default="mlp",
                    choices=sorted(ITEM_SHAPES) + ["transformer-decode"])
    ap.add_argument("--qps", type=float, default=200.0,
                    help="offered open-loop rate (decode: steps*duration)")
    ap.add_argument("--duration", type=float, default=3.0)
    ap.add_argument("--rows", type=int, default=1,
                    help="rows per request (decode: streams)")
    ap.add_argument("--buckets", default="1,2,4,8")
    ap.add_argument("--max-delay-ms", type=float, default=5.0)
    ap.add_argument("--cache-dir", default=None,
                    help="persist executables/manifests here "
                         "(default: MXNET_SERVE_CACHE_DIR)")
    ap.add_argument("--compare-batch1", action="store_true",
                    help="also measure saturation QPS vs a batch-1 engine")
    ap.add_argument("--megastep-k", type=int, default=8,
                    help="transformer-decode: K tokens per dispatch for "
                         "the megastep comparison leg (MXNET_DECODE_"
                         "MEGASTEP_K); 0 or 1 disables the leg")
    ap.add_argument("--workload", default="uniform",
                    choices=["uniform", "zipf-prefix"],
                    help="zipf-prefix: shared-prefix KV-cache + "
                         "speculative-decoding leg (transformer decode; "
                         "docs/SERVING.md §Prefix cache & speculative "
                         "decoding)")
    ap.add_argument("--spec-gamma", type=int, default=4,
                    help="zipf-prefix: draft tokens per speculative "
                         "round (MXNET_SPEC_GAMMA)")
    ap.add_argument("--spec-draft-layers", type=int, default=0,
                    help="zipf-prefix: layers truncated from the target "
                         "checkpoint for the draft model; 0 = self-draft "
                         "(draft == target, acceptance 1.0 — the "
                         "amortization smoke)")
    ap.add_argument("--fleet", action="store_true",
                    help="fleet smoke (docs/SERVING.md §Fleet): N replica "
                         "processes behind the router under open-loop "
                         "load + chaos (kill-one-replica, injected "
                         "dispatch faults, one mid-run fleet rollout)")
    ap.add_argument("--fleet-replicas", type=int, default=4)
    ap.add_argument("--chaos", action="store_true",
                    help="serving resilience smoke: open-loop load with "
                         "injected dispatch raises/delays + one mid-run "
                         "hitless reload (docs/RESILIENCE.md)")
    ap.add_argument("--chaos-fail-prob", type=float, default=0.1,
                    help="per-dispatch injected-raise probability")
    ap.add_argument("--chaos-delay-prob", type=float, default=0.2,
                    help="per-dispatch injected-delay probability")
    ap.add_argument("--chaos-delay-ms", type=float, default=15.0)
    ap.add_argument("--chaos-deadline-ms", type=float, default=300.0,
                    help="per-request deadline under chaos")
    ap.add_argument("--p99-bound-ms", type=float, default=None,
                    help="chaos/fleet gate: p99 of COMPLETED requests "
                         "must stay under this (default 1500; fleet mode "
                         "4000 — its deadline/shed knobs derive from it)")
    ap.add_argument("--trace-out", default=None,
                    help="--fleet: write the merged, clock-aligned fleet "
                         "chrome trace here (forces trace mode; view "
                         "with mxtrace or chrome://tracing)")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--check", action="store_true",
                    help="CI gate: assert qps>0, finite p99, zero "
                         "post-warmup retraces/compiles, serving.* spans "
                         "(with --chaos: the resilience gate)")
    args = ap.parse_args(argv)

    from mxnet_tpu import telemetry

    telemetry.set_mode("trace" if (args.check or args.trace_out)
                       else "counters")
    if args.p99_bound_ms is None:
        args.p99_bound_ms = 4000.0 if args.fleet else 1500.0
    if args.fleet:
        if args.model == "transformer-decode":
            ap.error("--fleet drives the bucketed engine; pick an "
                     "ITEM_SHAPES model")
        res = bench_fleet(args)
    elif args.chaos:
        if args.model == "transformer-decode":
            ap.error("--chaos drives the bucketed engine; pick an "
                     "ITEM_SHAPES model")
        res = bench_chaos(args)
    elif args.workload == "zipf-prefix":
        res = bench_prefix_spec(args)
    elif args.model == "transformer-decode":
        res = bench_decode(args)
    else:
        res = bench_engine(args)

    ok = True
    if args.check:
        if args.fleet:
            ok = _check_fleet(res)
        elif args.chaos:
            ok = _check_chaos(res)
        elif args.workload == "zipf-prefix":
            ok = _check_prefix_spec(res)
        else:
            families = {e[0] for e in telemetry.drain_events()}
            ok = _check(res, families)
        res["check"] = "ok" if ok else "FAILED"
    if telemetry.witnessing():
        # MXNET_CONCLINT=witness run: the bench doubles as the GL805 race
        # gate — any witnessed lock-order inversion or dispatch-seam hold
        # fails the run (tools/ci_check.sh chaos smoke)
        from mxnet_tpu.analysis.concurrency_lint import lint_lock_witness

        witness_diags = lint_lock_witness(telemetry.witness_report())
        res["gl805"] = [d.message for d in witness_diags]
        if witness_diags:
            ok = False
            for d in witness_diags:
                sys.stderr.write("serve_bench witness GL805: %s\n"
                                 % d.message)
    if args.json or args.check:
        print(json.dumps(res))
    else:
        for k, v in res.items():
            print("%-26s %s" % (k, v))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
