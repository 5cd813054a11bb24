"""Perf lab: measure ResNet-50 step time on the chip under different knobs.

Usage: python tools/perf_lab.py [--batch N] [--net NAME] [--profile DIR]

Not part of the public API — the experimental harness behind docs/PERF.md.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--net", default="resnet-50")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--profile", default=None, help="capture jax trace to DIR")
    ap.add_argument("--compute-dtype", default="bfloat16")
    ap.add_argument("--remat", default="false",
                    choices=["false", "true", "dots", "nothing"])
    ap.add_argument("--cost", action="store_true",
                    help="also print XLA cost analysis (flops, bytes)")
    ap.add_argument("--input-dtype", default="float32",
                    help="dtype the input batch is placed on device in")
    args = ap.parse_args()

    import jax
    import numpy as np

    from mxnet_tpu import models, parallel

    dev = jax.devices()[0]
    mesh = parallel.make_mesh((1,), axis_names=("data",), devices=[dev])
    net = models.get_symbol(args.net, num_classes=1000,
                            image_shape="3,%d,%d" % (args.image, args.image))
    remat = {"false": False, "true": True}.get(args.remat, args.remat)
    trainer = parallel.SPMDTrainer(
        net, mesh, optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        remat=remat,
        compute_dtype=args.compute_dtype or None)
    b = args.batch
    trainer.init_params({"data": (b, 3, args.image, args.image)},
                        {"softmax_label": (b,)}, seed=0)
    rs = np.random.RandomState(0)
    import jax.numpy as _jnp

    x_host = rs.rand(b, 3, args.image, args.image).astype("float32")
    if args.input_dtype != "float32":
        x_host = x_host.astype(_jnp.dtype(args.input_dtype))
    x = jax.device_put(x_host,
                       trainer.rules.named(trainer.rules.batch_spec((b, 3, args.image, args.image))))
    y = jax.device_put(rs.randint(0, 1000, (b,)).astype("float32"),
                       trainer.rules.named(trainer.rules.batch_spec((b,))))
    import jax.numpy as jnp

    sync = jax.block_until_ready  # a real barrier on the chip (docs/PERF.md §0)

    for _ in range(3):
        outs = trainer.step({"data": x}, {"softmax_label": y})
    sync(outs)

    if args.profile:
        jax.profiler.start_trace(args.profile)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        outs = trainer.step({"data": x}, {"softmax_label": y})
    sync(outs)
    dt = time.perf_counter() - t0
    if args.profile:
        jax.profiler.stop_trace()

    img_s = b * args.steps / dt
    # FLOPs model is ResNet-50-specific — MFU only claims meaning there
    flops = 3 * 4.09e9 * (args.image / 224.0) ** 2
    peak = 197e12 if "v5 lite" in dev.device_kind else None
    mfu_ok = peak and args.net == "resnet-50"
    out = {"batch": b, "step_ms": round(1000 * dt / args.steps, 2),
           "img_s": round(img_s, 1), "device": dev.device_kind,
           "net": args.net, "remat": args.remat,
           "input_dtype": args.input_dtype,
           "mfu": round(img_s * flops / peak, 4) if mfu_ok else None}
    if args.cost:
        cost = trainer.cost_analysis({"data": x}, {"softmax_label": y})
        gb = cost.get("bytes accessed", 0.0) / 1e9
        out["xla_gb_accessed"] = round(gb, 2)
        out["xla_tflops"] = round(cost.get("flops", 0.0) / 1e12, 3)
        out["hbm_gbps_achieved"] = round(gb / (dt / args.steps), 1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
