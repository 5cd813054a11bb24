"""LSTM perf lab: the PTB-config bucketed-LSTM step under the roofline
(docs/PERF.md §6; VERDICT r4 weak #5 — "LSTM 329k tokens/s is reported
without a roofline").

Measures the BASELINE config-3 step (2x200 LSTM, embed 200, vocab 10k,
batch 32, seq 60) and prints the measured tokens/s against the analytic
ceiling decomposition:

- projection GEMM: (B*T, H) x (H, V) fwd + 2x bwd — large, MXU-efficient;
- hoisted input-gate GEMM: (T*B, I) x (I, 4H) per layer (out-of-scan after
  the round-5 hoist);
- sequential recurrence: T steps of (B, H) x (H, 4H) per layer — small
  matmuls, latency-bound, the irreducible serial chain;
- scan/loop overhead: T iterations of XLA while-loop bookkeeping.

    python tools/lstm_perf.py [--profile DIR] [--cost] [--seq 60] ...
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=60)
    ap.add_argument("--hidden", type=int, default=200)
    ap.add_argument("--embed", type=int, default=200)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--vocab", type=int, default=10000)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--compute-dtype", default="bfloat16")
    ap.add_argument("--profile", default=None, help="capture jax trace to DIR")
    ap.add_argument("--cost", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu import models, parallel
    from mxnet_tpu.device_info import bf16_peak_flops

    dev = jax.devices()[0]
    mesh = parallel.make_mesh((1,), axis_names=("data",), devices=[dev])
    B, T, H, E, L, V = (args.batch, args.seq, args.hidden, args.embed,
                        args.layers, args.vocab)
    net = models.get_symbol("lstm", num_classes=V, num_embed=E, num_hidden=H,
                            num_layers=L, seq_len=T, batch_size=B)
    trainer = parallel.SPMDTrainer(
        net, mesh, optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        data_names=("data", "lstm_init_h", "lstm_init_c"),
        label_names=("softmax_label",),
        compute_dtype=args.compute_dtype or None)
    shapes = {"data": (B, T), "lstm_init_h": (L, B, H),
              "lstm_init_c": (L, B, H)}
    trainer.init_params(shapes, {"softmax_label": (B, T)}, seed=0)
    rs = np.random.RandomState(0)
    place = lambda name, arr: jax.device_put(
        arr, trainer.rules.named(trainer.rules.batch_spec(arr.shape)))
    data = {"data": place("data", rs.randint(1, V, (B, T)).astype("float32")),
            "lstm_init_h": place("h", np.zeros((L, B, H), "float32")),
            "lstm_init_c": place("c", np.zeros((L, B, H), "float32"))}
    y = place("y", rs.randint(1, V, (B, T)).astype("float32"))

    sync = jax.block_until_ready  # a real barrier on the chip (docs/PERF.md §0)

    for _ in range(3):
        outs = trainer.step(data, {"softmax_label": y})
    sync(outs)
    if args.profile:
        jax.profiler.start_trace(args.profile)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        outs = trainer.step(data, {"softmax_label": y})
    sync(outs)
    dt = time.perf_counter() - t0
    if args.profile:
        jax.profiler.stop_trace()
    step_s = dt / args.steps
    tokens_s = B * T / step_s

    # ---- analytic decomposition (FLOPs; fwd x3 for training) -------------
    gate_w = 4 * H
    proj_flops = 3 * 2.0 * B * T * H * V               # lm head
    in_gemm_flops = 3 * 2.0 * T * B * E * gate_w * L   # hoisted, batched
    rec_flops = 3 * 2.0 * T * B * H * gate_w * L       # sequential chain
    embed_bytes = B * T * E * 2                         # gather, bf16
    peak = bf16_peak_flops(dev.device_kind)
    # efficiency assumptions: the projection runs near matmul peak (74%
    # measured for big GEMMs, docs/PERF.md §0); the recurrence's (32,200)
    # matmuls fill 32/128 MXU rows -> <=25% ceiling; while-loop overhead
    # ~2us/iteration measured on v5e (fused step dispatch)
    t_proj = proj_flops / (0.74 * peak)
    t_in = in_gemm_flops / (0.5 * peak)
    t_rec = rec_flops / (0.25 * peak * (B / 128 if B < 128 else 1.0))
    t_loop = T * (2 * L + 2) * 2e-6
    ceiling_s = t_proj + t_in + t_rec + t_loop
    out = {
        "config": "b%d_seq%d_%dx%d_v%d" % (B, T, L, H, V),
        "device": dev.device_kind,
        "step_ms": round(step_s * 1e3, 3),
        "tokens_per_s": round(tokens_s, 1),
        "ceiling_tokens_per_s": round(B * T / ceiling_s, 1),
        "pct_of_ceiling": round(100 * (B * T / ceiling_s and
                                       tokens_s / (B * T / ceiling_s)), 1),
        "ceiling_ms_breakdown": {
            "projection_gemm": round(t_proj * 1e3, 3),
            "input_gate_gemm": round(t_in * 1e3, 3),
            "sequential_recurrence": round(t_rec * 1e3, 3),
            "loop_overhead": round(t_loop * 1e3, 3),
        },
    }
    if args.cost:
        cost = trainer.cost_analysis(data, {"softmax_label": y})
        gb = cost.get("bytes accessed", 0.0) / 1e9
        out["xla_gb_accessed"] = round(gb, 3)
        out["xla_tflops"] = round(cost.get("flops", 0.0) / 1e12, 4)
        out["hbm_gbps_achieved"] = round(gb / step_s, 1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
