#!/usr/bin/env python
"""All-reduce bandwidth microbenchmark.

Counterpart of the reference's tools/bandwidth/measure.py (KVStore push/pull
bandwidth over ps-lite). Here the reduction IS an XLA psum over the device
mesh (ICI on a pod, host shared-memory on the virtual CPU mesh), so the
measured quantity is collective bandwidth per chip:

    algo_bw   = 2 * (n-1)/n * bytes / time   (ring all-reduce wire traffic)

Run on N virtual CPU devices:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 MXNET_DEFAULT_CONTEXT=cpu \
        python tools/bandwidth/measure.py --sizes 1,16,64
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..")))
import mxnet_tpu  # noqa: E402,F401  (honors MXNET_DEFAULT_CONTEXT=cpu platform forcing)


def measure(size_mb, n_iter=10):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.local_devices()
    n = len(devs)
    mesh = Mesh(np.array(devs), ("x",))
    elems = int(size_mb * 1e6 / 4)
    elems -= elems % max(n, 1)
    x = jnp.ones((elems,), jnp.float32)
    x = jax.device_put(x, NamedSharding(mesh, P("x")))

    @jax.jit
    def allreduce(v):
        return jax.shard_map(lambda s: jax.lax.psum(s, "x"), mesh=mesh,
                             in_specs=P("x"), out_specs=P(None))(v)

    out = allreduce(x)  # compile + warmup
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n_iter):
        out = allreduce(x)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / n_iter
    nbytes = elems * 4
    algo_bw = 2 * (n - 1) / max(n, 1) * nbytes / dt / 1e9
    return dt, algo_bw, n


def measure_kvstore(size_mb, n_iter=10, legacy=False, n_keys=1,
                    bucket_mb=None):
    """Measure the *KVStore* dist allreduce path (push+pull round-trip), the
    quantity BASELINE.md tracks. Run under tools/launch.py so multiple
    processes join the collective:

        python tools/launch.py -n 8 --launcher local --cpu-devices 1 \\
            python tools/bandwidth/measure.py --kvstore --sizes 16

    ``n_keys`` splits the payload into that many keys pushed per-key with
    reverse-topo priorities — the bucketed overlap path ``Module.fit``
    drives (docs/PERF.md §11); ``bucket_mb`` pins MXNET_KVSTORE_BUCKET_MB
    for this store (the bench's bucket-size sweep). ``legacy=True``
    measures the round-2 per-key host allgather+sum instead of the compiled
    collective, for comparison. Returns (dt, busbw, n, overlap_ratio)."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.ndarray import NDArray

    if bucket_mb is not None:
        os.environ["MXNET_KVSTORE_BUCKET_MB"] = str(bucket_mb)
    kv = mx.kv.create("dist_tpu_sync")
    n = kv.num_workers
    elems = int(size_mb * 1e6 / 4 / n_keys)
    keys = ["bw%d" % i for i in range(n_keys)]
    vals = [mx.nd.ones((elems,)) for _ in keys]
    outs = [mx.nd.zeros((elems,)) for _ in keys]
    for k in keys:
        kv.init(k, mx.nd.zeros((elems,)))

    if legacy:
        def allgather_sum(arr):
            import jax.numpy as jnp
            from jax.experimental.multihost_utils import process_allgather

            gathered = process_allgather(arr._jax())
            return NDArray(jnp.sum(gathered, axis=0), ctx=arr.context)

        def round_trip():
            for k, v in zip(keys, vals):
                kv._store[k] = allgather_sum(v)
            for k, o in zip(keys, outs):
                kv.pull(k, out=o)
    else:
        def round_trip():
            # reverse-topo push order + priorities: deepest first, the
            # schedule update_params_on_kvstore emits
            for j in range(n_keys - 1, -1, -1):
                kv.push(keys[j], vals[j], priority=-j)
            for j in range(n_keys):
                kv.pull(keys[j], out=outs[j], priority=-j)

    # warm past compile AND the engine's first-N-rounds key-hash verify
    # (MXNET_KVSTORE_CHECK_STEPS), so the timed loop is steady state
    for _ in range(4):
        round_trip()
    outs[0].wait_to_read()
    kv._barrier()
    t0 = time.perf_counter()
    for _ in range(n_iter):
        round_trip()
    for o in outs:
        o.wait_to_read()
    dt = (time.perf_counter() - t0) / n_iter
    nbytes = elems * 4 * n_keys
    algo_bw = 2 * (n - 1) / max(n, 1) * nbytes / dt / 1e9
    overlap = telemetry.gauge("kvstore.overlap_ratio").value \
        if telemetry.enabled() else None
    return dt, algo_bw, n, overlap


def main():
    parser = argparse.ArgumentParser(description="all-reduce bandwidth")
    parser.add_argument("--sizes", type=str, default="1,4,16,64",
                        help="comma-separated MB sizes")
    parser.add_argument("--iters", type=int, default=10)
    parser.add_argument("--kvstore", action="store_true",
                        help="measure the dist KVStore push/pull path "
                             "(run under tools/launch.py)")
    parser.add_argument("--legacy-allgather", action="store_true",
                        help="with --kvstore: measure the host allgather "
                             "path instead of the compiled collective")
    parser.add_argument("--keys", type=int, default=1,
                        help="with --kvstore: split the payload into N keys "
                             "pushed per-key with priorities (exercises the "
                             "bucket plan + overlap)")
    parser.add_argument("--bucket-mb-sweep", type=str, default="",
                        help="with --kvstore: comma-separated "
                             "MXNET_KVSTORE_BUCKET_MB values; one "
                             "measurement per value")
    parser.add_argument("--json", action="store_true",
                        help="emit one JSON line per size (for bench.py)")
    args = parser.parse_args()

    import json

    if not args.json:
        print("%8s %12s %12s" % ("size_MB", "time_ms", "busbw_GB/s"))
    sweep = ([float(b) for b in args.bucket_mb_sweep.split(",")]
             if args.bucket_mb_sweep else [None])
    for size in (float(s) for s in args.sizes.split(",")):
        for bucket_mb in sweep:
            overlap = None
            if args.kvstore:
                dt, bw, n, overlap = measure_kvstore(
                    size, args.iters, legacy=args.legacy_allgather,
                    n_keys=args.keys, bucket_mb=bucket_mb)
                # under launch.py every worker shares one stdout —
                # interleaved prints corrupt the JSON stream, so only rank 0
                # reports
                if args.json and int(os.environ.get("MXNET_TPU_WORKER_ID",
                                                    "0")):
                    continue
            else:
                dt, bw, n = measure(size, args.iters)
            if args.json:
                rec = {"size_mb": size, "time_ms": round(dt * 1e3, 3),
                       "busbw_gbps": round(bw, 3), "devices": n}
                if bucket_mb is not None:
                    rec["bucket_mb"] = bucket_mb
                if args.keys > 1:
                    rec["keys"] = args.keys
                if overlap is not None:
                    rec["overlap_ratio"] = overlap
                print(json.dumps(rec))
            else:
                extra = "" if bucket_mb is None else \
                    "  bucket=%gMB" % bucket_mb
                print("%8g %12.3f %12.2f   (%d devices)%s"
                      % (size, dt * 1e3, bw, n, extra))


if __name__ == "__main__":
    main()
