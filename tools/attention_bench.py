"""A/B the blockwise Pallas attention kernel against the dense path at
transformer-base TRAINING shapes (batches of sequences, forward and
backward), which no serving cell runs.

Times the MultiHeadAttention op's two forms — fwd-only and fwd+bwd — at
(B, H, T, D) transformer-base shapes, seq 512/1024/2048, bf16, amortized
inside one jitted scan with host-fetch sync (docs/PERF.md §0), and prints
beside each what ``ops.attention.attention_form`` names there on this
backend: the table is what that rule's threshold (``pallas_attention.takes``)
is held against (PERF.md §6, PR 49).

    python tools/attention_bench.py
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# transformer-base: model_dim 512, 8 heads x 64
SHAPES = [
    # (B, H, T, D, causal)
    (16, 8, 512, 64, False),
    (16, 8, 512, 64, True),
    (8, 8, 1024, 64, False),
    (8, 8, 1024, 64, True),
    (4, 8, 2048, 64, True),
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--quick", action="store_true",
                    help="one tiny shape (CPU plumbing smoke)")
    args = ap.parse_args()
    shapes = [(2, 2, 128, 64, True)] if args.quick else SHAPES

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import attention as attn_op
    from mxnet_tpu.ops import pallas_attention as pa
    from mxnet_tpu.ops.attention import _multi_head_attention

    # the dense arm: the operator with its rule held to the dense path
    attn_op.attention_form = lambda *a: "dense"
    rule = lambda q, k, v, causal: (
        "kernel" if causal and on_tpu and pa.takes(q, k, v) else "dense")

    dt = jnp.dtype(args.dtype)
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"

    sync = jax.block_until_ready  # a real barrier on the chip (docs/PERF.md §0)

    def timeit(fn, *arrs):
        @jax.jit
        def many(*arrs):
            def body(c, _):
                o = fn(*arrs)
                return c + o.reshape(-1)[:1].astype(jnp.float32), None

            out, _ = jax.lax.scan(body, jnp.zeros((1,), jnp.float32),
                                  None, length=args.iters)
            return out

        sync(many(*arrs))
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            out = many(*arrs)
            sync(out)
            best = min(best, (time.perf_counter() - t0) / args.iters)
        return best

    rs = np.random.RandomState(0)
    rows = []
    for B, H, T, D, causal in shapes:
        q, k, v = (jnp.asarray(rs.randn(B, H, T, D) * 0.3, dt)
                   for _ in range(3))
        attrs = {"causal": causal, "scale": -1.0}
        rec = {"B": B, "H": H, "T": T, "D": D, "causal": causal,
               "rule": rule(q, k, v, causal)}
        if not pa.supported(q.shape, k.shape, causal=causal):
            rec["skipped"] = "pallas unsupported"
            rows.append(rec)
            print(json.dumps(rec))
            continue

        def xla_fwd(q, k, v):
            return _multi_head_attention(attrs, q, k, v)

        def pal_fwd(q, k, v):
            return pa.flash_attention(q, k, v, causal=causal, scale=0.0,
                                      interpret=not on_tpu)

        cot = jnp.asarray(rs.randn(B, H, T, D) * 0.1, dt)

        def grad_of(fn):
            def f(q, k, v):
                out = fn(q, k, v)
                return jnp.sum((out * cot).astype(jnp.float32))

            return jax.grad(f, argnums=(0, 1, 2))

        try:
            t_x = timeit(xla_fwd, q, k, v)
            t_p = timeit(pal_fwd, q, k, v)
            gx = grad_of(xla_fwd)
            gp = grad_of(pal_fwd)

            def run_gx(q, k, v):
                a, b, c = gx(q, k, v)
                return a + b + c

            def run_gp(q, k, v):
                a, b, c = gp(q, k, v)
                return a + b + c

            t_xb = timeit(run_gx, q, k, v)
            t_pb = timeit(run_gp, q, k, v)
            o0 = jax.jit(xla_fwd)(q, k, v)
            o1 = jax.jit(pal_fwd)(q, k, v)
            rel = float(jnp.max(jnp.abs(o0.astype(jnp.float32)
                                        - o1.astype(jnp.float32))))
            rec.update({
                "xla_fwd_ms": round(t_x * 1e3, 3),
                "pallas_fwd_ms": round(t_p * 1e3, 3),
                "fwd_speedup": round(t_x / t_p, 3),
                "xla_bwd_ms": round(t_xb * 1e3, 3),
                "pallas_bwd_ms": round(t_pb * 1e3, 3),
                "bwd_speedup": round(t_xb / t_pb, 3),
                "max_abs_err": round(rel, 5),
            })
        except Exception as exc:
            rec["error"] = "%s: %s" % (type(exc).__name__, exc)
        rows.append(rec)
        print(json.dumps(rec))

    measured = [r for r in rows if "fwd_speedup" in r]
    if measured:
        wins = sum(1 for r in measured
                   if r["fwd_speedup"] >= 1.0 and r["bwd_speedup"] >= 1.0)
        print(json.dumps({"summary": {
            "device": dev.device_kind, "dtype": str(dt),
            "shapes_measured": len(measured),
            "pallas_wins_both_directions": wins,
        }}))


if __name__ == "__main__":
    main()
