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

``--topk`` instead splits ONE full-attention layer of a long admission under
a learned selection, standing alone at ``dots3-note-prev.generate``'s shapes
(B = 1, 128 heads of 192 over a value of 128, T = S = 8,192, an indexer of
64 heads of 128, the 2,048 highest index scores a query, bfloat16), into
what ``ops/attention.py`` makes it of: the index scores, the ``top_k`` mask
on top of them, and the masked attention, in XLA's query blocks
(``_sparse_attention``) and in the blockwise kernel under the mask
(``attention_form``'s ``"sparse_kernel"``), beside the plain causal kernel
over the same operands (``PERF.md`` section 6, PR 53).

    python tools/attention_bench.py --topk

``--window`` instead times ONE window layer of an admission standing alone,
XLA's band (``_band_attention``) against the blockwise kernel under the
window (``attention_form``'s ``"window_kernel"``), at the cells' shapes
(``WINDOW_LAYERS``): the kernel at the blocks ``pallas_attention.blocks``
names and, with ``--sweep``, at every tiling beside them. A form is timed as
a CHAIN of four layers in one program, each layer's output the next one's
query (or value, or the query's first columns, where the widths differ), so
that nothing is hoisted or overlapped (``PERF.md`` section 6, PR 60).

    python tools/attention_bench.py --window [--sweep]
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


# dots3-note-prev's full layer at the cell's bucket: (heads, T, key width,
# value width, index heads, index width, topk)
SPARSE_LAYER = (128, 8192, 192, 128, 64, 128, 2048)


# a window layer at its cell's bucket: (heads, key/value heads, T, key width,
# value width, window). mimo's carries a sink in its cell and stays XLA's
# there; it stands here without one, for the rule's threshold
WINDOW_LAYERS = {
    "laguna-s-2.1": (72, 8, 8192, 128, 128, 512),
    "dots3-note-prev": (64, 64, 8192, 256, 128, 513),
    "phi-4-mini-flash-reasoning": (40, 10, 2048, 128, 128, 512),
    "mimo-v2-flash (no sink)": (64, 8, 2048, 192, 128, 128),
    # smaller bands, for where the rule's threshold falls (no cell's)
    "probe 32 MiB": (16, 16, 2048, 64, 64, 128),
    "probe 64 MiB": (32, 8, 2048, 128, 128, 128),
    "probe 256 MiB": (32, 8, 4096, 128, 128, 256),
}
_CHAIN = 4


def window_layers(args):
    """One JSON line a shape: a layer's milliseconds as XLA's band and as the
    kernel (medians of ``--iters`` runs of a chain of ``_CHAIN`` layers, timed
    to ``block_until_ready``, over ``_CHAIN``), the kernel's worst difference
    from the band over the output's largest magnitude and, with ``--sweep``,
    every other tiling's milliseconds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import attention as attn_op
    from mxnet_tpu.ops import pallas_attention as pa

    dt = jnp.dtype(args.dtype)
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    layers = {"quick": (4, 2, 128, 16, 8, 32)} if args.quick \
        else WINDOW_LAYERS
    rs = np.random.RandomState(0)

    def chain_ms(layer, q, k, v, n):
        def chain(q, k, v):
            for _ in range(n):
                out = layer(q, k, v)
                if out.shape == q.shape:
                    q = out
                elif out.shape == v.shape:
                    v = out
                else:   # a value narrower than the key over grouped heads
                    q = jnp.concatenate(
                        [out, q[..., out.shape[-1]:]], axis=-1)
            return out

        fn = jax.jit(chain)
        jax.block_until_ready(fn(q, k, v))
        times = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(q, k, v))
            times.append((time.perf_counter() - t0) * 1e3)
        return round(float(np.median(times)) / n, 3)

    for name, (h, hkv, t, dk, dv, w) in layers.items():
        if args.only and args.only not in name:
            continue
        draw = lambda *shape: jnp.asarray(
            rs.randn(*shape).astype("float32"), dt)
        q, k, v = draw(1, h, t, dk), draw(1, hkv, t, dk), draw(1, hkv, t, dv)
        scale = dk ** -0.5
        band = lambda q, k, v: attn_op._xla_attention(
            q, k, v, None, True, w, scale)
        kernel = lambda bq, bk: lambda q, k, v: pa.flash_attention(
            q, k, v, causal=True, scale=scale, window=w, block_q=bq,
            block_k=bk, interpret=not on_tpu)
        ruled = pa.blocks(t, t, h // hkv, dk, dv, dt, False, w)
        rec = {"layer": name, "device": dev.device_kind, "dtype": str(dt),
               "heads": [h, hkv], "T": t, "widths": [dk, dv], "window": w,
               "iters": args.iters, "blocks": ruled,
               "band_block": attn_op._band_block(t, w),
               "takes": bool(pa.takes(q, k, v, window=w))}
        want = np.asarray(jax.jit(band)(q, k, v), np.float32)
        got = np.asarray(jax.jit(kernel(*ruled))(q, k, v), np.float32)
        rec["max_err_over_max"] = float(np.abs(got - want).max()
                                        / np.abs(want).max())
        n = 1 if args.quick else _CHAIN
        rec["band_ms"] = chain_ms(band, q, k, v, n)
        rec["kernel_ms"] = chain_ms(kernel(*ruled), q, k, v, n)
        if args.sweep:
            g, unit = h // hkv, 32 // dt.itemsize
            rec["sweep"] = {}
            for bq in (16, 32, 64, 128, 256, 512, 1024):
                for bk in (128, 256, 512, 1024):
                    if (bq, bk) == tuple(ruled) or bq % unit or t % bq \
                            or t % bk or not 256 <= g * bq <= 2048 \
                            or pa.block_bytes(bq, bk, g, dk, dv, dt) \
                            > pa._VMEM_BUDGET:
                        continue
                    try:
                        rec["sweep"]["%dx%d" % (bq, bk)] = [
                            chain_ms(kernel(bq, bk), q, k, v, n),
                            pa.window_key_blocks(t, t, bq, bk, w)]
                    except Exception as exc:   # a tiling Mosaic refuses
                        rec["sweep"]["%dx%d" % (bq, bk)] = "%s: %s" % (
                            type(exc).__name__, str(exc)[:120])
        print(json.dumps(rec), flush=True)


def sparse_split(args):
    """One JSON line: a layer's milliseconds by part (medians of
    ``--iters`` runs, each a program of its own, timed to
    ``block_until_ready``), and the kernel's worst difference from XLA's
    form over the output's largest magnitude."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import attention as attn_op
    from mxnet_tpu.ops import pallas_attention as pa

    h, t, dk, dv, hi, di, topk = (4, 256, 24, 16, 4, 16, 64) if args.quick \
        else SPARSE_LAYER
    dt = jnp.dtype(args.dtype)
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    rs = np.random.RandomState(0)
    draw = lambda *shape: jnp.asarray(rs.randn(*shape).astype("float32"), dt)
    q, k, v = draw(1, h, t, dk), draw(1, h, t, dk), draw(1, h, t, dv)
    index = draw(1, hi, t, di), draw(1, 1, t, di), draw(1, t, hi)
    scale = dk ** -0.5

    def ms(fn, *arrs):
        fn = jax.jit(fn)
        out = jax.block_until_ready(fn(*arrs))
        times = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*arrs))
            times.append((time.perf_counter() - t0) * 1e3)
        return out, round(float(np.median(times)), 3)

    def unranked(*index):
        # the query blocks with the index scores alone: the mask is a
        # threshold over them and no ``top_k`` runs
        ranked, attn_op._topk_mask = attn_op._topk_mask, \
            lambda score, topk: score > 0
        try:
            return attn_op._selection(h, *index, topk)
        finally:
            attn_op._topk_mask = ranked

    rec = {"device": dev.device_kind, "dtype": str(dt), "heads": h, "T": t,
           "widths": [dk, dv], "indexer": [hi, di], "topk": topk,
           "iters": args.iters,
           "rule": attn_op.attention_form(q, k, v, True, topk=topk)}
    _, rec["index_scores_ms"] = ms(unranked, *index)
    selected, rec["selection_ms"] = ms(
        lambda *index: attn_op._selection(h, *index, topk), *index)
    want, rec["layer_xla_ms"] = ms(
        lambda *a: attn_op._sparse_attention(*a, topk, scale), q, k, v,
        *index)
    got, rec["layer_kernel_ms"] = ms(
        lambda *a: attn_op._sparse_kernel(*a, topk, scale, not on_tpu),
        q, k, v, *index)
    _, rec["kernel_masked_ms"] = ms(
        lambda q, k, v, selected: pa.flash_attention(
            q, k, v, causal=True, scale=scale, interpret=not on_tpu,
            selected=selected), q, k, v, selected)
    _, rec["kernel_causal_ms"] = ms(
        lambda q, k, v: pa.flash_attention(
            q, k, v, causal=True, scale=scale, interpret=not on_tpu), q, k, v)
    rec["topk_mask_ms"] = round(rec["selection_ms"]
                                - rec["index_scores_ms"], 3)
    rec["attention_xla_ms"] = round(rec["layer_xla_ms"]
                                    - rec["selection_ms"], 3)
    rec["selected_share"] = round(float(jnp.mean(
        selected.astype(jnp.float32))), 4)
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    rec["max_err_over_max"] = float(np.abs(got - want).max()
                                    / np.abs(want).max())
    print(json.dumps(rec))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--quick", action="store_true",
                    help="one tiny shape (CPU plumbing smoke)")
    ap.add_argument("--topk", action="store_true",
                    help="split one full layer under a learned selection "
                         "(dots3-note-prev's shapes) instead")
    ap.add_argument("--window", action="store_true",
                    help="time one window layer, XLA's band against the "
                         "kernel under the window (the cells' shapes) instead")
    ap.add_argument("--sweep", action="store_true",
                    help="with --window: every tiling beside the rule's")
    ap.add_argument("--only", default="",
                    help="with --window: the layers whose name holds this")
    args = ap.parse_args()
    if args.topk:
        return sparse_split(args)
    if args.window:
        return window_layers(args)
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
