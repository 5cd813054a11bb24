"""Autotune harness for the fused conv+BN Pallas stack: measure fused vs
XLA per (shape, variant, direction) and emit the WINS table that gates graph
integration (mxnet_tpu/ops/fused_conv_bn_table.py).

Contracts under test (fusion.py):

  forward   unfused:  xn = relu(x*scale + shift)  [materialized]
                      c  = conv(xn);  s = sum(c32);  q = sum(c32^2)
            fused:    conv_block(...) — prologue in VMEM, stats from the f32
                      MXU accumulator, one HBM write for c.
  backward  unfused:  jax.vjp of the composition above (cotangent fold,
                      dgrad, wgrad, prologue backward each cross HBM).
            fused:    the Pallas dgrad/wgrad kernel, per residual policy —
                      'recompute' (xn re-derived in VMEM) and 'stash' (xn
                      written by the forward, streamed back).

Variants: 'p' = prologue-only, 'pr' = prologue+residual. Each direction is
timed separately; backward wins are recorded per winning POLICY — the WINS
value for a ``variant + ":bwd"`` key is the policy string, which
``fusion.bwd_mode`` rides into ``conv_block(bwd=...)`` under
``MXNET_FUSED_CONV_BN=auto``.

Each timing amortizes ``--iters`` executions inside one jitted scan (a
dispatch costs ~0.2 ms on the chip, more than several of these kernels) and
ends in ``jax.block_until_ready`` (docs/PERF.md §0). A contract "wins" when fused time <= unfused time AND
gradient/output parity holds; wins are written with ``--emit-table``.

``--interpret`` forces Pallas interpret mode so the whole harness — timing
scaffolding, parity checks, table emission, loadability — runs on CPU
without a chip (the CI smoke in tools/ci_check.sh). Interpret timings are
NOT predictive (the emulator is orders of magnitude slower than compiled
XLA), so --interpret defaults ``--min-speedup`` to 0: the emitted table
records every parity-validated contract, proving the machinery end to end.

    python tools/fused_stats_bench.py --batch 256 --emit-table      # on-chip
    python tools/fused_stats_bench.py --interpret --emit-table \\
        --table-out /tmp/table.py                                   # CPU CI
"""
import argparse
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_TABLE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "mxnet_tpu", "ops", "fused_conv_bn_table.py")

_BWD_POLICIES = ("recompute", "stash")


def resnet50_sites():
    """Canonical @224 site list (kept as the historical entry point; the
    shared implementation lives in mxnet_tpu.ops.conv_bn_bytes)."""
    from mxnet_tpu.ops.conv_bn_bytes import resnet50_sites as _sites

    return _sites()


def tiny_sites():
    """Small shapes covering every kernel family / stride / ceil-div path —
    the interpret-mode (CPU) site list, where @224 shapes would take hours
    in the Pallas emulator."""
    return [
        ((1, 1), (1, 1), 8, 16, 8, 1, 0),
        ((1, 1), (2, 2), 8, 16, 9, 1, 0),   # odd H: ceil-div strided dims
        ((3, 3), (1, 1), 8, 8, 8, 1, 1),
        ((1, 1), (1, 1), 16, 8, 8, 1, 1),
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=None,
                    help="default 256 (2 with --interpret)")
    ap.add_argument("--iters", type=int, default=None,
                    help="default 10 (2 with --interpret)")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--emit-table", action="store_true")
    ap.add_argument("--table-out", default=_TABLE,
                    help="where --emit-table writes (default: the committed "
                         "mxnet_tpu/ops/fused_conv_bn_table.py)")
    ap.add_argument("--sites", choices=["resnet50", "tiny"], default=None,
                    help="default resnet50 (tiny with --interpret)")
    ap.add_argument("--directions", default="fwd,bwd",
                    help="comma list of fwd,bwd")
    ap.add_argument("--interpret", action="store_true",
                    help="run the Pallas kernels in interpret mode (CPU CI "
                         "smoke; timings not predictive)")
    ap.add_argument("--min-speedup", type=float, default=None,
                    help="fused engages where t_xla/t_fused >= this "
                         "(default 1.0; 0.0 with --interpret)")
    args = ap.parse_args()
    if args.interpret:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault("MXNET_DEFAULT_CONTEXT", "cpu")
        if (args.emit_table
                and os.path.abspath(args.table_out) == os.path.abspath(_TABLE)):
            # the committed table is an ON-CHIP measurement; an interpret
            # run would clobber it with a cpu-stamped table whose min_speedup=0
            # wins are artifacts of the emulator — and auto mode would then
            # engage the interpret-slow Pallas path in every CPU test run
            ap.error("--interpret --emit-table refuses to overwrite the "
                     "committed table; pass --table-out <path>")
    batch = args.batch if args.batch is not None else (2 if args.interpret
                                                       else 256)
    iters = args.iters if args.iters is not None else (2 if args.interpret
                                                       else 10)
    min_speedup = args.min_speedup if args.min_speedup is not None else (
        0.0 if args.interpret else 1.0)
    directions = tuple(d for d in args.directions.split(",") if d)

    import jax
    import jax.numpy as jnp

    if args.interpret:
        jax.config.update("jax_platforms", "cpu")

    from mxnet_tpu.ops.pallas_conv_bn import (_stats_of, _xla_conv,
                                              conv_block, plan_bwd_blocks,
                                              strided_dims, supported)

    dt = jnp.dtype(args.dtype)
    dev = jax.devices()[0]
    site_list = (tiny_sites()
                 if (args.sites or ("tiny" if args.interpret else "resnet50"))
                 == "tiny" else resnet50_sites())

    sync = jax.block_until_ready  # a real barrier on the chip (docs/PERF.md §0)

    def timeit_many(many):
        sync(many())  # compile + warmup
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            out = many()
            sync(out)
            best = min(best, (time.perf_counter() - t0) / iters)
        return best

    def timeit_fwd(fn, *arrs):
        # operands are jit ARGUMENTS (not closure constants) so XLA cannot
        # constant-fold the measured computation out of the scan
        @jax.jit
        def many(*arrs):
            def body(carry, _):
                c, s, q = fn(*arrs)
                return (carry + s[:1] + q[:1]
                        + c.reshape(-1)[:1].astype(jnp.float32)), None

            out, _ = jax.lax.scan(body, jnp.zeros((1,), jnp.float32),
                                  None, length=iters)
            return out

        return timeit_many(lambda: many(*arrs))

    def timeit_bwd(fn, cts, *arrs):
        """Time ONLY the backward: the vjp closure (residuals resident, like
        a training step's) applied ``iters`` times in one jitted scan.
        vjp_fn is a Partial pytree, so passing it as a jit argument keeps
        the residuals traced arguments rather than baked-in constants."""
        _, vjp_fn = jax.vjp(fn, *arrs)

        @jax.jit
        def many(vjp_fn, cts):
            def body(carry, _):
                grads = vjp_fn(cts)
                leaf = grads[0].reshape(-1)[:1].astype(jnp.float32)
                return carry + leaf, None

            out, _ = jax.lax.scan(body, jnp.zeros((1,), jnp.float32),
                                  None, length=iters)
            return out

        return timeit_many(lambda: many(vjp_fn, cts))

    def grads_of(fn, cts, *arrs):
        _, vjp_fn = jax.vjp(fn, *arrs)
        return jax.jit(lambda: vjp_fn(cts))()

    rel = lambda a, b: float(
        jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
        / (float(jnp.max(jnp.abs(b.astype(jnp.float32)))) + 1e-9))

    rs = np.random.RandomState(0)
    wins, rows = {}, []
    for kernel, stride, K, N, H, count, _res_count in site_list:
        B = batch
        x_shape = (B, K, H, H)
        w_shape = (N, K) + kernel
        rec = {"kernel": kernel[0], "stride": stride[0], "K": K, "N": N,
               "H": H, "count": count}
        if not supported(x_shape, w_shape, stride, itemsize=dt.itemsize,
                         prologue=True):
            rec["skipped"] = "unsupported"
            rows.append(rec)
            print(json.dumps(rec))
            continue
        x = jnp.asarray(rs.randn(*x_shape), dt)
        w = jnp.asarray(rs.randn(*w_shape) * 0.1, dt)
        scale = jnp.asarray(rs.uniform(0.5, 1.5, (K,)), jnp.float32)
        shift = jnp.asarray(rs.uniform(-0.2, 0.2, (K,)), jnp.float32)
        Ho, Wo = strided_dims(H, H, stride)
        r = jnp.asarray(rs.randn(B, N, Ho, Wo) * 0.1, dt)
        cts = (jnp.asarray(rs.randn(B, N, Ho, Wo), dt),
               jnp.asarray(rs.randn(N), jnp.float32),
               jnp.asarray(rs.randn(N) * 0.1, jnp.float32))

        # two measured contracts: 'p' = prologue-only (every in-graph conv
        # with a folded BN), 'pr' = prologue + residual epilogue (convs
        # deferred into the block's skip add). gate()/bwd_mode() engage
        # exactly the (variant, direction) that was measured.
        for variant, res in (("p", None), ("pr", r)):
            if res is not None and not supported(
                    x_shape, w_shape, stride, itemsize=dt.itemsize,
                    prologue=True, res=True):
                continue
            key = (kernel[0], K, N, Ho * Wo, stride[0], variant)

            def unfused(x, w, scale, shift, res=res):
                c = _xla_conv(x, w, scale, shift, res, kernel, stride, True)
                s, q = _stats_of(c)
                return c, s, q

            def fused(x, w, scale, shift, res=res, bwd="xla"):
                return conv_block(x, w, scale, shift, res, kernel, stride,
                                  True, True, bwd)

            if "fwd" in directions:
                try:
                    t_x = timeit_fwd(unfused, x, w, scale, shift)
                    t_p = timeit_fwd(fused, x, w, scale, shift)
                    c0, s0, q0 = jax.jit(unfused)(x, w, scale, shift)
                    c1, s1, q1 = jax.jit(fused)(x, w, scale, shift)
                    rec.update({
                        "xla_ms_%s" % variant: round(t_x * 1e3, 3),
                        "pallas_ms_%s" % variant: round(t_p * 1e3, 3),
                        "speedup_%s" % variant: round(t_x / t_p, 3),
                        "c_rel_err_%s" % variant: round(rel(c1, c0), 5),
                        "stats_rel_err_%s" % variant:
                            round(max(rel(s1, s0), rel(q1, q0)), 5),
                    })
                    if (t_x / t_p >= min_speedup
                            and rec["c_rel_err_%s" % variant] < 2e-2):
                        wins[key] = True
                except Exception as exc:
                    rec["error_%s" % variant] = \
                        "%s: %s" % (type(exc).__name__, exc)

            if "bwd" in directions:
                n_args = (x, w, scale, shift)
                try:
                    t_bx = timeit_bwd(unfused, cts, *n_args)
                    g_ref = grads_of(unfused, cts, *n_args)
                    rec["bwd_xla_ms_%s" % variant] = round(t_bx * 1e3, 3)
                    best = None
                    for policy in _BWD_POLICIES:
                        if plan_bwd_blocks(
                                x_shape, w_shape, stride,
                                itemsize=dt.itemsize, prologue=True,
                                res=res is not None,
                                stash=(policy == "stash")) is None:
                            continue
                        fn = functools.partial(fused, bwd=policy)
                        t_bp = timeit_bwd(fn, cts, *n_args)
                        g_pol = grads_of(fn, cts, *n_args)
                        err = max(rel(a, b) for a, b in zip(g_pol, g_ref))
                        rec["bwd_%s_ms_%s" % (policy, variant)] = \
                            round(t_bp * 1e3, 3)
                        rec["bwd_%s_grad_rel_err_%s" % (policy, variant)] = \
                            round(err, 5)
                        if (t_bx / t_bp >= min_speedup and err < 2e-2
                                and (best is None or t_bp < best[1])):
                            best = (policy, t_bp)
                    if best is not None:
                        rec["bwd_policy_%s" % variant] = best[0]
                        rec["bwd_speedup_%s" % variant] = \
                            round(t_bx / best[1], 3)
                        wins[key[:5] + (variant + ":bwd",)] = best[0]
                except Exception as exc:
                    rec["bwd_error_%s" % variant] = \
                        "%s: %s" % (type(exc).__name__, exc)
        rows.append(rec)
        print(json.dumps(rec))

    def _key(r, variant):
        hw = ((r["H"] + r["stride"] - 1) // r["stride"]) ** 2
        return (r["kernel"], r["K"], r["N"], hw, r["stride"], variant)

    measured = [r for r in rows
                if any(k.startswith(("speedup_", "bwd_")) and "error" not in k
                       for k in r)]
    won_p = [r for r in measured if _key(r, "p") in wins]
    won_pr = [r for r in measured if _key(r, "pr") in wins]
    won_bwd = [r for r in measured
               if _key(r, "p:bwd") in wins or _key(r, "pr:bwd") in wins]
    summary = {
        "device": dev.device_kind, "batch": batch, "dtype": str(dt),
        "interpret": bool(args.interpret),
        "directions": list(directions),
        "sites_total": sum(r["count"] for r in rows),
        "sites_measured": sum(r["count"] for r in measured),
        "sites_won_p": sum(r["count"] for r in won_p),
        "sites_won_pr": sum(r["count"] for r in won_pr),
        "sites_won_bwd": sum(r["count"] for r in won_bwd),
        "unique_measured": len(measured),
        "unique_won_p": len(won_p), "unique_won_pr": len(won_pr),
        "unique_won_bwd": len(won_bwd),
    }
    print(json.dumps({"summary": summary}))

    if args.emit_table:
        with open(args.table_out, "w") as f:
            f.write('"""Per-shape engage table for the fused conv+BN Pallas '
                    'path - GENERATED by\n``tools/fused_stats_bench.py '
                    '--emit-table`` from on-chip measurements; do not\n'
                    'hand-edit. Key: ``(kernel_size, C_in, C_out, '
                    'H_out*W_out, stride, variant)`` with\nvariant "p" = '
                    'prologue-only, "pr" = prologue+residual, and '
                    '"p:bwd"/"pr:bwd"\nthe backward direction. A forward '
                    'value of True means the Pallas kernel beat\nthe '
                    'unfused XLA lowering for that measured contract on the '
                    'measured device\n(fusion.gate engages it under '
                    'MXNET_FUSED_CONV_BN=auto); a backward value is\nthe '
                    'winning residual policy string ("recompute" or '
                    '"stash") that\nfusion.bwd_mode rides into '
                    'conv_block(bwd=...).\n\nMeasurement: %s\n"""\n\n'
                    % json.dumps(summary))
            f.write("DEVICE = %r\n\nWINS = {\n" % dev.device_kind)
            for key in sorted(wins):
                f.write("    %r: %r,\n" % (key, wins[key]))
            f.write("}\n")
        print(json.dumps({"table_written": args.table_out,
                          "entries": len(wins)}))


if __name__ == "__main__":
    main()
