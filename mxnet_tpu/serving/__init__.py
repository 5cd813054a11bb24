"""mxnet_tpu.serving: the production inference engine (docs/SERVING.md).

The "millions of users" leg of the roadmap: the training side compiles one
XLA executable per step and replays it; serving gets the same discipline.
PyGraph's thesis (PAPERS.md) — per-call dispatch overhead disappears when
the compiled graph is captured once and replayed — maps here onto a
``PersistentExecutableCache``: one pre-compiled executable per
(model, shape bucket, dtype), kept hot across requests, persisted per
device kind, with any post-warmup recompile a HARD error diagnosed by the
GL201-203 retrace guard. ``InferenceEngine`` feeds those executables from a
thread-safe request queue with continuous batching over the buckets
(pad-to-bucket, admit mid-flight until ``MXNET_SERVE_MAX_DELAY_MS``).
``PagedKVDecoder`` is the autoregressive variant: a prefill-bucket
executable plus a single-token decode executable over a preallocated, paged
KV pool its lanes share (models/transformer.py serving symbols).

    cache = serving.PersistentExecutableCache(sym, arg_params, aux_params)
    eng = serving.InferenceEngine(cache, buckets=(1, 2, 4, 8),
                                  item_shapes={"data": (3, 28, 28)})
    eng.start()
    probs = eng.infer({"data": batch})          # blocking convenience
    fut = eng.submit({"data": batch})           # or async
    probs = fut.result(timeout=5.0)
"""
from __future__ import annotations

from .cache import PersistentExecutableCache
from .engine import (InferenceEngine, ServeFuture, ServeDeadlineError,
                     ServeOverloadError, ServeClosedError)
from .kv_decode import PagedKVDecoder, PagedKVExhausted
from .prefix_cache import PrefixCache
from .speculative import SpeculativeDecoder, spec_decode_enabled, spec_gamma
from . import fleet

__all__ = ["PersistentExecutableCache", "InferenceEngine", "ServeFuture",
           "ServeDeadlineError", "ServeOverloadError", "ServeClosedError",
           "PagedKVDecoder", "PagedKVExhausted",
           "PrefixCache", "SpeculativeDecoder", "spec_decode_enabled",
           "spec_gamma", "fleet"]
