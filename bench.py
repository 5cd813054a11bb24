"""Benchmark: the three BASELINE.md scoreboard metrics in ONE JSON line.

- ``resnet50_train_throughput`` (img/s, + MFU): synthetic fwd+bwd+SGD,
  counterpart of the reference's ``train_imagenet.py --benchmark 1``
  (example/image-classification/README.md:255-261). Baseline: 109 img/s on
  1x K80, batch 32 (README.md:149-156).
- ``lstm_tokens_per_s``: bucketed-LSTM training step at the PTB config
  (example/rnn/lstm_bucketing.py defaults: 2x200 LSTM, embed 200, batch 32,
  bucket 60).
- ``allreduce_gbps``: collective bus bandwidth via tools/bandwidth/measure
  (the reference's tools/bandwidth/measure.py KVStore metric). With one
  local chip this runs on the 8-process virtual CPU mesh (fabric field says
  so); on a pod slice the same path measures ICI.

One process per chip: this script is an orchestrator that never imports
jax. The legs that run in-process on the chip (ResNet-50, LSTM, recommender
step, input pipeline) run in ONE child (``bench.py --inproc``), which checks
first thing that JAX's default device is a TPU and exits 2 otherwise — there
is no CPU fallback, a bench without a chip is a failure. Children that need
the chip (serve_bench legs) then run one after another;
children that measure host-side paths (kvstore bandwidth, checkpoint,
2-process wire smokes) carry ``JAX_PLATFORMS=cpu``. A leg that raises is
reported under its name AND makes the exit code non-zero.

Timing: ``jax.block_until_ready`` is a real barrier on the chip (checked by
chip_smoke.py phase 0: it agrees with a host scalar fetch to within 1 ms on
a 48 ms matmul chain); ``_sync`` is the one helper every timing here uses.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...extras}.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np

BASELINE_IMG_S = 109.0  # reference README.md:149-156, resnet-50, 1x K80, b32

# ResNet-50 @224: ~4.09 GFLOP forward per image (2*MACs); training ≈ 3x fwd
_TRAIN_FLOPS_PER_IMG = 3 * 4.09e9


def _mfu_fields(flops_per_step, step_s, peak):
    """Analytic-FLOPs MFU for one bench leg (docs/PERF.md §4/§15): the
    model's training FLOPs per step (2×MACs fwd, ×3 for fwd+bwd+update)
    over wall step time, as a fraction of the device's bf16 ``peak``
    (device_info.bf16_peak_flops of the exact device kind — an unknown kind
    raises there, it never borrows a neighbour's peak)."""
    return {"model_flops_per_step": int(flops_per_step),
            "model_tflops_per_s": round(flops_per_step / step_s / 1e12, 5),
            "mfu": round(flops_per_step / step_s / peak, 4)}


def _lstm_train_flops(batch, seq, hidden, embed, layers, vocab):
    """PTB-config LSTM: per token, the 4-gate matmuls per layer (input dim
    = embed for layer 0, hidden above) plus the vocab head; ×3 train."""
    per_tok = 2 * 4 * hidden * (hidden + embed)
    per_tok += (layers - 1) * 2 * 4 * hidden * (2 * hidden)
    per_tok += 2 * hidden * vocab
    return 3 * batch * seq * per_tok


def _recommender_train_flops(batch, embed_dim=64, dense_dim=16,
                             bottom=(128,), top=(512, 256)):
    """DLRM-style two-tower click model (models/recommender.py defaults):
    bottom MLP + top MLP matmuls per sample (embedding lookups move bytes,
    not FLOPs); ×3 train."""
    dims = (dense_dim,) + tuple(bottom) + (embed_dim,)
    mac = sum(a * b for a, b in zip(dims, dims[1:]))
    tdims = (3 * embed_dim + 1,) + tuple(top) + (1,)
    mac += sum(a * b for a, b in zip(tdims, tdims[1:]))
    return 3 * batch * 2 * mac


def _sync(x):
    """The one device barrier (see module docstring)."""
    import jax

    return jax.block_until_ready(x)


def _make_trainer(net, dev, batch_shapes, compute_dtype, parallel,
                  data_names=None):
    mesh = parallel.make_mesh((1,), axis_names=("data",), devices=[dev])
    trainer = parallel.SPMDTrainer(
        net, mesh, optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        compute_dtype=compute_dtype,
        data_names=data_names or tuple(n for n in batch_shapes
                                       if "label" not in n),
        label_names=tuple(n for n in batch_shapes if "label" in n))
    data_shapes = {n: s for n, s in batch_shapes.items() if "label" not in n}
    label_shapes = {n: s for n, s in batch_shapes.items() if "label" in n}
    trainer.init_params(data_shapes, label_shapes, seed=0)
    return trainer


def _place(trainer, name, arr):
    import jax

    return jax.device_put(arr, trainer.rules.named(
        trainer.rules.batch_spec(arr.shape)))


_RESNET_BATCH, _RESNET_IMAGE, _RESNET_STEPS = 256, 224, 10


def _resnet50_img_s(models, parallel, dev):
    """img/s of the bf16 ResNet-50 step at the ONE stated batch (a batch
    that does not fit is a failure of the leg, not a reason to try a
    smaller one)."""
    import jax.numpy as jnp

    from mxnet_tpu import telemetry

    batch, image = _RESNET_BATCH, _RESNET_IMAGE
    net = models.get_symbol("resnet-50", num_classes=1000,
                            image_shape="3,%d,%d" % (image, image))
    rs = np.random.RandomState(0)
    trainer = _make_trainer(
        net, dev, {"data": (batch, 3, image, image),
                   "softmax_label": (batch,)}, "bfloat16", parallel)
    # feed the batch in the compute dtype (saves the on-chip fp32
    # materialization + cast; measured ~1.6% step time, docs/PERF.md)
    x = _place(trainer, "data",
               rs.rand(batch, 3, image, image).astype("float32")
               .astype(jnp.bfloat16))
    y = _place(trainer, "softmax_label",
               rs.randint(0, 1000, (batch,)).astype("float32"))
    for _ in range(3):
        outs = trainer.step({"data": x}, {"softmax_label": y})
    _sync(outs)
    mark = telemetry.enabled()  # off by default: zero touch on the clock
    t0 = time.perf_counter()
    for _ in range(_RESNET_STEPS):
        outs = trainer.step({"data": x}, {"softmax_label": y})
        if mark:
            telemetry.mark_step()
    _sync(outs)
    return batch * _RESNET_STEPS / (time.perf_counter() - t0)


def _bench_resnet50(models, parallel, dev, peak):
    batch, image = _RESNET_BATCH, _RESNET_IMAGE
    img_s = _resnet50_img_s(models, parallel, dev)
    res = {"img_s": img_s, "batch": batch, "image": image,
           "step_ms": 1000 * batch / img_s,
           "flops_per_img": _TRAIN_FLOPS_PER_IMG}
    res.update(_mfu_fields(_TRAIN_FLOPS_PER_IMG * batch, batch / img_s, peak))
    return res


def _bench_lstm(models, parallel, dev, peak):
    """PTB-shape bucketed-LSTM training step (BASELINE config 3)."""
    batch, seq = 32, 60
    vocab, hidden, embed, layers = 10000, 200, 200, 2
    net = models.get_symbol("lstm", num_classes=vocab, num_embed=embed,
                            num_hidden=hidden, num_layers=layers,
                            seq_len=seq, batch_size=batch)
    rs = np.random.RandomState(0)
    # initial states are DATA (the reference feeds init_states per batch,
    # example/rnn/lstm.py provide_data), not trainable params. NOTE: their
    # leading dim is num_layers, not batch — fine on this 1-device mesh,
    # but a multi-device data mesh must not batch_spec-shard them
    shapes = {"data": (batch, seq),
              "lstm_init_h": (layers, batch, hidden),
              "lstm_init_c": (layers, batch, hidden),
              "softmax_label": (batch, seq)}
    trainer = _make_trainer(net, dev, shapes, "bfloat16", parallel,
                            data_names=("data", "lstm_init_h", "lstm_init_c"))
    data = {"data": _place(trainer, "data",
                           rs.randint(1, vocab, (batch, seq)).astype("float32")),
            "lstm_init_h": _place(trainer, "lstm_init_h",
                                  np.zeros((layers, batch, hidden), "float32")),
            "lstm_init_c": _place(trainer, "lstm_init_c",
                                  np.zeros((layers, batch, hidden), "float32"))}
    y = _place(trainer, "softmax_label",
               rs.randint(1, vocab, (batch, seq)).astype("float32"))
    for _ in range(3):
        outs = trainer.step(data, {"softmax_label": y})
    _sync(outs)
    n_steps = 20
    t0 = time.perf_counter()
    for _ in range(n_steps):
        outs = trainer.step(data, {"softmax_label": y})
    _sync(outs)
    dt = time.perf_counter() - t0
    res = {"tokens_per_s": batch * seq * n_steps / dt, "batch": batch,
           "seq_len": seq, "step_ms": 1000 * dt / n_steps}
    res.update(_mfu_fields(
        _lstm_train_flops(batch, seq, hidden, embed, layers, vocab),
        dt / n_steps, peak))
    return res


def _bench_allreduce(device):
    """KVStore allreduce bandwidth (the BASELINE.md metric): push+pull
    round-trip through the dist KVStore's bucketed collective path
    (docs/PERF.md §11), 8 worker processes under tools/launch.py
    (measure.py --kvstore). The payload rides 16 keys pushed per-key with
    priorities — the schedule a real training round emits — swept over
    MXNET_KVSTORE_BUCKET_MB values; the headline is the best point and the
    report carries the whole sweep plus the engine's overlap gauge. The 8
    workers always run on the CPU (``JAX_PLATFORMS=cpu``: eight processes
    cannot share a chip), so this measures the kvstore code path, not an
    interconnect. ``device`` is the in-process child's device report."""
    root = os.path.dirname(os.path.abspath(__file__))
    fabric = "cpu-8proc"
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "MXNET_DEFAULT_CONTEXT": "cpu",
                "MXNET_TELEMETRY": "counters"})
    out = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "launch.py"), "-n", "8",
         "--launcher", "local", sys.executable,
         os.path.join(root, "tools", "bandwidth", "measure.py"),
         "--kvstore", "--sizes", "64", "--keys", "16", "--iters", "5",
         "--bucket-mb-sweep", "4,16,25", "--json"],
        capture_output=True, text=True, timeout=600, env=env, cwd=root)
    recs = []
    dec = json.JSONDecoder()
    for l in out.stdout.splitlines():
        l = l.strip()
        # workers share one stdout: tolerate interleaved/concatenated lines
        while l.startswith("{"):
            try:
                rec, end = dec.raw_decode(l)
            except ValueError:
                break
            if "busbw_gbps" in rec:
                recs.append(rec)
            l = l[end:].lstrip()
    if not recs:
        raise RuntimeError(
            "kvstore bandwidth run produced no JSON (rc=%d): %s"
            % (out.returncode, (out.stderr or out.stdout).strip()[-400:]))
    rec = max(recs, key=lambda r: r["busbw_gbps"])
    res = {"gbps": rec["busbw_gbps"], "devices": rec["devices"],
           "fabric": fabric}
    if "bucket_mb" in rec:
        res["bucket_mb"] = rec["bucket_mb"]
    if rec.get("overlap_ratio") is not None:
        res["overlap_ratio"] = rec["overlap_ratio"]
    sweep = {str(r["bucket_mb"]): r["busbw_gbps"] for r in recs
             if "bucket_mb" in r}
    if sweep:
        res["bucket_sweep"] = sweep
    # second datapoint: the XLA device-mesh allreduce (shard_map psum over a
    # single-process mesh). With several chips the one child drives them all
    # and this rides ICI; with one chip it runs on an 8-device virtual CPU
    # mesh and is labeled as such.
    env2 = dict(os.environ)
    if device["count"] > 1:
        mesh_fabric = "%s-%ddev" % (device["platform"], device["count"])
    else:
        mesh_fabric = "cpu-shmem-8dev"
        env2.update({"JAX_PLATFORMS": "cpu",
                     "MXNET_DEFAULT_CONTEXT": "cpu",
                     "XLA_FLAGS": (env2.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=8")})
    out2 = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "bandwidth",
                                      "measure.py"), "--sizes", "64",
         "--json"],
        capture_output=True, text=True, timeout=600, env=env2, cwd=root)
    for l in out2.stdout.splitlines():
        if l.startswith("{"):
            res["device_mesh_gbps"] = json.loads(l)["busbw_gbps"]
            res["device_mesh_fabric"] = mesh_fabric
    if "device_mesh_gbps" not in res:
        raise RuntimeError(
            "no JSON from measure.py (rc=%d): %s"
            % (out2.returncode, (out2.stderr or out2.stdout).strip()[-300:]))
    return res


_CKPT_BENCH_WORKER = r"""
import json, os, sys, threading, time
import numpy as np
sys.path.insert(0, sys.argv[1])
os.environ.setdefault("MXNET_KVSTORE_BUCKET_MB", "1")
os.environ["MXNET_KVSTORE_UPDATE"] = "sharded"
os.environ.setdefault("MXNET_TELEMETRY", "counters")
import mxnet_tpu as mx
from mxnet_tpu import telemetry

mx.kv.create("dist_tpu_sync")  # dist.init before any JAX computation
workdir = sys.argv[2]
# a realistically-sized step (~100 ms on the CI host): the leg measures the
# checkpoint overhead a real training run would see, not the degenerate
# ratio against a sub-10ms toy step where any fixed cost looks enormous
BATCH, BATCHES, EPOCHS, DIM = 64, 15, 3, 256


def _mlp():
    s = mx.sym.Variable("data")
    s = mx.sym.FullyConnected(s, num_hidden=1024, name="fc1")
    s = mx.sym.Activation(s, act_type="relu")
    s = mx.sym.FullyConnected(s, num_hidden=512, name="fc2")
    s = mx.sym.Activation(s, act_type="relu")
    s = mx.sym.FullyConnected(s, num_hidden=10, name="fc3")
    return mx.sym.SoftmaxOutput(s, name="softmax")


def _data():
    rs = np.random.RandomState(7)
    x = rs.rand(BATCHES * BATCH, DIM).astype("float32")
    y = rs.randint(0, 10, (BATCHES * BATCH,)).astype("float32")
    return mx.io.NDArrayIter(x, y, batch_size=BATCH)


# per-epoch checkpoint cadence: epoch 0 warms the compile caches, then a
# balanced ABBA/BAAB interleave of plain (0) and checkpointing (5) epochs —
# the host's speed drifts on a timescale comparable to one PHASE, so the
# mode must alternate faster than the drift, inside ONE fit
PERIOD = 5
SCHED = [0, 0, PERIOD, PERIOD, 0, PERIOD, 0, 0, PERIOD]


def run(ckpt_dir):
    stamps = []
    g = telemetry.gauge("checkpoint.inflight")

    def cb(param):
        v = g.value  # a save submitted last round may still be in flight
        if v:
            peak["inflight"] = max(peak["inflight"], v)
        ctl = param.locals["self"]  # the ElasticFit controller
        ctl.checkpoint_period = SCHED[min(param.epoch, len(SCHED) - 1)]
        if param.epoch >= 1:  # epoch 0 is the compile warmup
            stamps.append((param.epoch, time.time()))

    mod = mx.mod.Module(_mlp(), context=mx.cpu(), fused_step=False)
    mod.fit(_data(), num_epoch=len(SCHED), kvstore="dist_tpu_sync",
            optimizer="sgd",
            optimizer_params=(("learning_rate", 0.05), ("momentum", 0.9)),
            batch_end_callback=cb,
            elastic={"checkpoint_dir": ckpt_dir,
                     "checkpoint_period": 0, "resume": False})
    per_epoch = {}
    for (e0, t0), (e1, t1) in zip(stamps, stamps[1:]):
        if e0 == e1:
            per_epoch.setdefault(e0, []).append(t1 - t0)
    med = {}
    for e, steps in per_epoch.items():
        steps.sort()
        med[e] = steps[len(steps) // 2]
    plain = [med[e] for e in med if SCHED[e] == 0]
    ckpt = [med[e] for e in med if SCHED[e] != 0]
    return (sum(plain) / len(plain), sum(ckpt) / len(ckpt))


peak = {"inflight": 0.0}
stop = threading.Event()


def _sample():
    # gentle poll (5 ms): on a small host a hot sampler would perturb the
    # very step time this leg measures; the batch callback above reads the
    # gauge at every round boundary as the deterministic backstop
    g = telemetry.gauge("checkpoint.inflight")
    while not stop.is_set():
        v = g.value
        if v:
            peak["inflight"] = max(peak["inflight"], v)
        time.sleep(0.005)


threading.Thread(target=_sample, daemon=True).start()
plain, ckpt = run(os.path.join(workdir, "ckpt"))
stop.set()
rank = int(os.environ.get("MXNET_TPU_WORKER_ID", "0"))
if rank == 0:
    print(json.dumps({
        "ckpt_bench": 1,
        "step_ms_plain": round(plain * 1000, 3),
        "step_ms_ckpt": round(ckpt * 1000, 3),
        "regression": round(ckpt / plain - 1, 4),
        "peak_inflight": peak["inflight"],
        "saves": telemetry.counter("checkpoint.saves").value,
    }), flush=True)
"""


def _bench_checkpoint():
    """Async-checkpoint overhead leg (docs/FAULT_TOLERANCE.md): ONE
    2-process sharded-update fit whose epochs alternate checkpointing off
    and every-5th-round sharded async checkpoints in a balanced ABBA/BAAB
    interleave (epoch 0 = compile warmup; host-speed drift cancels because
    the mode alternates faster than the drift). Reports the mean of the
    per-epoch median step times per mode and their regression (acceptance:
    < 10%; the snapshot is device refs + a writer thread, so the
    device→host transfer and disk I/O overlap the next steps) and the peak
    ``checkpoint.inflight`` gauge (must be > 0: the write really was in
    flight while training ran)."""
    import tempfile

    root = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "MXNET_DEFAULT_CONTEXT": "cpu"})
    with tempfile.TemporaryDirectory(prefix="mxtpu_ckpt_bench") as workdir:
        script = os.path.join(workdir, "worker.py")
        with open(script, "w") as f:
            f.write(_CKPT_BENCH_WORKER)
        out = subprocess.run(
            [sys.executable, os.path.join(root, "tools", "launch.py"),
             "-n", "2", "--launcher", "local", "--cpu-devices", "1",
             sys.executable, script, root, workdir],
            capture_output=True, text=True, timeout=600, env=env, cwd=root)
    rec = None
    for l in out.stdout.splitlines():
        if l.startswith("{") and "ckpt_bench" in l:
            rec = json.loads(l)
    if rec is None:
        raise RuntimeError("no JSON from checkpoint bench (rc=%d): %s"
                           % (out.returncode,
                              (out.stderr or out.stdout).strip()[-400:]))
    rec.pop("ckpt_bench", None)
    return rec


def _bench_serving():
    """Serving leg (docs/SERVING.md): QPS + p99 under a fixed open-loop
    load for lenet/mlp, continuous-batching-vs-batch-1 saturation speedup
    on mlp, the transformer KV-cache decode rate, the shared-prefix
    cache + speculative-decoding leg (zipf workload: hit rate, prefill
    FLOPs saved, accepted-draft rate, p50/p99 vs the prefix-off
    baseline), and the FLEET leg — a
    4-replica router run under the seeded chaos plan (kill-one + mid-run
    rollout) recording aggregate QPS / p99 / redispatches / restarts next
    to its single-replica closed-loop baseline (docs/SERVING.md §Fleet).
    Each leg runs tools/serve_bench.py in a fresh subprocess, one after
    another (each needs the chip to itself). The fleet leg is the
    exception: four replica processes cannot share one chip, so it runs
    wholly on the CPU and says so (``platform: cpu``) until replicas get a
    chip each (ROADMAP R7)."""
    root = os.path.dirname(os.path.abspath(__file__))
    legs = {
        "mlp": ["--model", "mlp", "--qps", "120", "--duration", "2",
                "--compare-batch1"],
        "lenet": ["--model", "lenet", "--qps", "40", "--duration", "2"],
        "transformer_decode": ["--model", "transformer-decode", "--qps",
                               "30", "--duration", "2", "--rows", "4",
                               "--megastep-k", "8"],
        "prefix_spec": ["--model", "transformer-decode", "--workload",
                        "zipf-prefix", "--qps", "20", "--duration", "2"],
        "fleet": ["--model", "mlp", "--fleet", "--fleet-replicas", "4",
                  "--qps", "80", "--duration", "3"],
    }
    out = {}
    for name, extra in legs.items():
        try:
            env = dict(os.environ)
            if name == "fleet":
                env["JAX_PLATFORMS"] = "cpu"
            r = subprocess.run(
                [sys.executable, os.path.join(root, "tools",
                                              "serve_bench.py"),
                 "--json"] + extra,
                capture_output=True, text=True, timeout=420,
                cwd=root, env=env)
            rec = None
            for l in r.stdout.splitlines():
                if l.startswith("{"):
                    rec = json.loads(l)
            if rec is None:
                raise RuntimeError("no JSON (rc=%d): %s"
                                   % (r.returncode,
                                      (r.stderr or r.stdout).strip()[-300:]))
            keep = {k: rec.get(k) for k in
                    ("qps", "p50_ms", "p99_ms", "batch_occupancy",
                     "retraces_post_warmup", "batching_speedup",
                     "qps_single_replica_closed", "replicas",
                     "redispatches", "replica_restarts",
                     "host_gap_ms", "host_gap_per_token",
                     "megastep", "workload", "prefix", "spec")
                    if rec.get(k) is not None}
            if name == "fleet":
                keep["platform"] = "cpu"
                keep["resolved"] = rec.get("resolved")
                keep["rollout_applied"] = bool(
                    (rec.get("rollout") or {}).get("applied"))
            out[name] = keep
        except Exception as exc:
            out[name] = {"error": "%s: %s" % (type(exc).__name__, exc)}
    return out


def _bench_recommender(models, parallel, dev, peak):
    """Recommender leg (docs/SPARSE.md): the embedding-dominated workload
    the row-sparse subsystem opens. Three numbers:

    - ``samples_per_s`` — single-device DLRM-style train step (embedding
      lookups + MLP) through the SPMD trainer;
    - ``embedding_bytes_moved`` / ``sparse_vs_dense_wire_ratio`` — from the
      2-process sparse-vs-dense smoke (tests/nightly/dist_sparse_kvstore):
      the wire bytes the sparse KVStore round actually moved for the
      tables vs the dense-push control, weight-parity enforced inside;
    - ``autoplan`` — the 8-device plan under a budget that makes
      replicated tables infeasible: the mesh and how many tables the
      per-param search sharded over the model axis.
    """
    batch = 512
    net = models.get_symbol("recommender")
    rs = np.random.RandomState(0)
    shapes = {"user": (batch,), "item": (batch,), "dense": (batch, 16),
              "label": (batch,)}
    trainer = _make_trainer(net, dev, shapes, "bfloat16", parallel,
                            data_names=("user", "item", "dense"))
    data = {"user": _place(trainer, "user",
                           rs.randint(0, 65536, (batch,)).astype("float32")),
            "item": _place(trainer, "item",
                           rs.randint(0, 32768, (batch,)).astype("float32")),
            "dense": _place(trainer, "dense",
                            rs.rand(batch, 16).astype("float32"))}
    y = _place(trainer, "label",
               rs.randint(0, 2, (batch,)).astype("float32"))
    for _ in range(3):
        outs = trainer.step(data, {"label": y})
    _sync(outs)
    n_steps = 20
    t0 = time.perf_counter()
    for _ in range(n_steps):
        outs = trainer.step(data, {"label": y})
    _sync(outs)
    dt = time.perf_counter() - t0
    res = {"samples_per_s": round(batch * n_steps / dt, 1), "batch": batch,
           "step_ms": round(1000 * dt / n_steps, 2)}
    res.update(_mfu_fields(_recommender_train_flops(batch), dt / n_steps,
                           peak))

    # 2-proc sparse-vs-dense wire measurement (parity gated inside)
    root = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "MXNET_DEFAULT_CONTEXT": "cpu"})
    r = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "launch.py"),
         "-n", "2", "--launcher", "local", "--cpu-devices", "1",
         sys.executable,
         os.path.join(root, "tests", "nightly", "dist_sparse_kvstore.py")],
        capture_output=True, text=True, timeout=420, env=env, cwd=root)
    rec = None
    for line in r.stdout.splitlines():
        if line.startswith("DIST_SPARSE {"):
            rec = json.loads(line[len("DIST_SPARSE "):])
    if rec is None:
        raise RuntimeError("2-proc sparse smoke produced no row (rc=%d): %s"
                           % (r.returncode,
                              (r.stderr or r.stdout).strip()[-300:]))
    res["embedding_bytes_moved"] = rec["embedding_bytes_moved"]
    res["sparse_vs_dense_wire_ratio"] = rec["sparse_vs_dense_wire_ratio"]
    res["wire_parity_max_abs_diff"] = rec["parity_max_abs_diff"]
    res["rows_pushed_2proc"] = rec["rows_pushed"]

    # the 8-device plan when replicated tables do not fit (the regime the
    # subsystem targets): the search must shard the tables, not pipeline
    from mxnet_tpu.parallel import autoplan

    plan = autoplan.plan_parallel(
        net, {"user": (64,), "item": (64,), "dense": (64, 16),
              "label": (64,)},
        types={"user": "int32", "item": "int32"}, devices=8,
        budget_gb=0.0625, label="recommender")
    res["autoplan"] = {
        "mesh": dict(plan.mesh), "feasible": plan.feasible,
        "sharded_tables": sum(
            1 for n in ("user_embed_weight", "item_embed_weight")
            if any(plan.param_specs.get(n, []))),
        "comm_vs_naive": round(
            plan.predicted["comm_bytes"] / max(1, plan.naive["comm_bytes"]),
            6),
    }
    return res


def _bench_input_pipeline(peak):
    """Double-buffered input pipeline A/B (docs/PERF.md §15): the SAME
    small-MLP ``Module.fit`` twice from identical initial weights — plain
    ``NDArrayIter`` (host slicing + transfer inline with the step) vs the
    iterator wrapped in ``io.DevicePrefetchIter`` (batch N+1 sliced,
    ``device_put`` and parked by the pump thread while step N runs).
    Records the ``io.input_bound_pct`` gauge per arm (the fraction of
    epoch wall time the fit loop spent waiting on input — it must drop
    with prefetch on) and asserts the final weights are BITWISE identical
    (device transfer preserves bits; no augment hook here)."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    def mlp():
        s = mx.sym.Variable("data")
        s = mx.sym.FullyConnected(s, num_hidden=256, name="ip_fc1")
        s = mx.sym.Activation(s, act_type="relu")
        s = mx.sym.FullyConnected(s, num_hidden=64, name="ip_fc2")
        s = mx.sym.Activation(s, act_type="relu")
        s = mx.sym.FullyConnected(s, num_hidden=10, name="ip_fc3")
        return mx.sym.SoftmaxOutput(s, name="softmax")

    rs = np.random.RandomState(11)
    batch, batches, dim = 128, 24, 128
    x = rs.rand(batches * batch, dim).astype("float32")
    y = rs.randint(0, 10, (batches * batch,)).astype("float32")
    init = {
        "ip_fc1_weight": mx.nd.array(rs.rand(256, dim).astype("f") * 0.05),
        "ip_fc1_bias": mx.nd.array(np.zeros(256, "f")),
        "ip_fc2_weight": mx.nd.array(rs.rand(64, 256).astype("f") * 0.05),
        "ip_fc2_bias": mx.nd.array(np.zeros(64, "f")),
        "ip_fc3_weight": mx.nd.array(rs.rand(10, 64).astype("f") * 0.05),
        "ip_fc3_bias": mx.nd.array(np.zeros(10, "f")),
    }

    saved = telemetry.current_override()
    telemetry.set_mode("counters")
    try:
        def run(prefetch):
            it = mx.io.NDArrayIter(x, y, batch_size=batch)
            if prefetch:
                it = mx.io.DevicePrefetchIter(it)
            stamps = []  # epoch-1 batch boundaries: epoch 0 is the
            # compile warmup, so the median inter-batch gap here is the
            # STEADY-STATE step time (the other legs' timing contract)

            def cb(param):
                if param.epoch >= 1:
                    stamps.append(time.perf_counter())

            t0 = time.perf_counter()
            mod = mx.mod.Module(mlp(), context=mx.context.current_context())
            mod.fit(it, num_epoch=2, kvstore="local",
                    arg_params=dict(init), initializer=None,
                    batch_end_callback=cb)
            wall = time.perf_counter() - t0
            args, _ = mod.get_params()
            pct = telemetry.gauge("io.input_bound_pct").value
            gaps = sorted(b - a for a, b in zip(stamps, stamps[1:]))
            step_s = gaps[len(gaps) // 2] if gaps else wall
            return pct, wall, step_s, {k: v.asnumpy()
                                       for k, v in args.items()}

        # warmup pass for BOTH arms: the two fits share this process's
        # JAX trace/compile caches, so without it the second arm would
        # inherit the first's compile warmth and the wall/step numbers
        # would measure run ORDER, not the pipeline
        run(False)
        run(True)
        pct_off, wall_off, step_off, params_off = run(False)
        pct_on, wall_on, step_on, params_on = run(True)
    finally:
        telemetry.set_mode(saved)
    res = {
        "input_bound_pct_off": pct_off,
        "input_bound_pct_on": pct_on,
        "input_bound_dropped": bool(pct_on < pct_off),
        "fit_wall_s_off": round(wall_off, 3),
        "fit_wall_s_on": round(wall_on, 3),
        "step_ms_off": round(step_off * 1000, 3),
        "step_ms_on": round(step_on * 1000, 3),
        "bitwise_identical": bool(all(
            np.array_equal(params_off[k], params_on[k])
            for k in params_off)),
        "batch": batch, "batches_per_epoch": batches,
    }
    flops = 3 * batch * 2 * (dim * 256 + 256 * 64 + 64 * 10)
    res.update(_mfu_fields(flops, step_on, peak))
    return res


def _bench_autoplan():
    """Auto-parallel planner leg (docs/PARALLEL_PLANNER.md): the plan the
    cost model picks for the transformer at 8 abstract devices (predicted
    comm bytes, chosen vs naive all-dp), plus a REAL 2-process CPU fit
    (tests/nightly/autoplan_measure.py) comparing the predicted grad-sync
    bytes against the measured ``kvstore.bytes.*`` counters — the planner's
    claim to a scoreboard number is only as good as that ratio."""
    root = os.path.dirname(os.path.abspath(__file__))
    from mxnet_tpu import models
    from mxnet_tpu.parallel import autoplan

    plan = autoplan.plan_parallel(
        models.get_symbol("transformer"),
        {"data": (2, 64), "softmax_label": (2, 64)},
        types={"data": "int32"}, devices=8, label="transformer")
    rec = {
        "transformer_mesh": dict(plan.mesh),
        "transformer_pipeline_stages": plan.pipeline_stages,
        "predicted_comm_bytes": plan.predicted["comm_bytes"],
        "naive_comm_bytes": plan.naive["comm_bytes"],
        "comm_vs_naive": round(
            plan.predicted["comm_bytes"] / max(1, plan.naive["comm_bytes"]),
            4),
        "predicted_peak_bytes": plan.predicted["peak_bytes"],
        "sharded_params": sum(1 for v in plan.param_specs.values() if any(v)),
    }
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # children of a process that holds the chip
    r = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "launch.py"),
         "-n", "2", "--launcher", "local", "--cpu-devices", "1",
         sys.executable,
         os.path.join(root, "tests", "nightly", "autoplan_measure.py")],
        capture_output=True, text=True, timeout=420, env=env, cwd=root)
    measured = None
    for line in r.stdout.splitlines():
        if line.startswith("AUTOPLAN_MEASURE {"):
            measured = json.loads(line[len("AUTOPLAN_MEASURE "):])
    if measured is None:
        raise RuntimeError("2-proc measure produced no row (rc=%d): %s"
                           % (r.returncode,
                              (r.stderr or r.stdout).strip()[-300:]))
    rec["measured_2proc"] = measured
    rec["within_2x"] = bool(0.5 <= measured["ratio"] <= 2.0)
    return rec


def _leg(legs, name, fn):
    """Run one leg; a leg that raises is REPORTED under its name (with the
    traceback on stderr) — ``_failed_legs`` then makes the exit code say so."""
    import traceback

    try:
        legs[name] = fn()
    except Exception as exc:  # noqa: BLE001 — reported and counted, see above
        traceback.print_exc()
        legs[name] = {"error": "%s: %s" % (type(exc).__name__, exc)}


def _failed_legs(tree, path=""):
    """Paths of every ``{"error": ...}`` in the result tree."""
    out = []
    if isinstance(tree, dict):
        if "error" in tree:
            out.append(path or "bench")
        for k, v in tree.items():
            out += _failed_legs(v, "%s.%s" % (path, k) if path else k)
    return out


def main_inproc():
    """The ONE child that holds the chip for the in-process legs. Its last
    stdout line is ``{"inproc": 1, "device": {...}, "legs": {...}}``."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.stderr.write("bench: JAX found no TPU (platform=%s); there is no "
                         "CPU fallback.\n" % dev.platform)
        return 2
    from mxnet_tpu import models, parallel, telemetry
    from mxnet_tpu.device_info import bf16_peak_flops

    peak = bf16_peak_flops(dev.device_kind)  # unknown kind: an error
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "bf16_peak_flops": peak}
    legs = {}
    _leg(legs, "resnet50", lambda: _bench_resnet50(models, parallel, dev,
                                                   peak))
    _leg(legs, "lstm", lambda: _bench_lstm(models, parallel, dev, peak))
    _leg(legs, "input_pipeline", lambda: _bench_input_pipeline(peak))
    _leg(legs, "autoplan", _bench_autoplan)
    _leg(legs, "recommender",
         lambda: _bench_recommender(models, parallel, dev, peak))
    # MXNET_TELEMETRY=counters|trace: the registry's view of the same run —
    # retraces, kv bytes/step — next to the wall time
    # (docs/OBSERVABILITY.md). Off by default.
    if telemetry.enabled():
        _leg(legs, "telemetry", telemetry.summarize)
    print(json.dumps({"inproc": 1, "device": device, "legs": legs}),
          flush=True)
    return 0


def _run_inproc():
    """Start the in-process child and return its report. No chip: exit 2
    right here, before any other leg runs a model."""
    r = subprocess.run([sys.executable, os.path.abspath(__file__),
                        "--inproc"], stdout=subprocess.PIPE, text=True,
                       timeout=3000)
    if r.returncode == 2:
        raise SystemExit(2)
    for l in reversed(r.stdout.splitlines()):
        if l.startswith("{") and '"inproc"' in l:
            return json.loads(l)
    raise RuntimeError("in-process child produced no report (rc=%d): %s"
                       % (r.returncode, r.stdout.strip()[-400:]))


def main():
    inproc = _run_inproc()          # holds the chip, then exits
    device, legs = inproc["device"], inproc["legs"]
    # chip children, one at a time
    _leg(legs, "serving", _bench_serving)
    # host-side children (JAX_PLATFORMS=cpu inside each)
    _leg(legs, "allreduce", lambda: _bench_allreduce(device))
    _leg(legs, "checkpoint", _bench_checkpoint)

    rn = legs["resnet50"]
    result = {"metric": "resnet50_train_throughput", "unit": "img/s",
              "device": device["kind"], "platform": device["platform"],
              "device_count": device["count"]}
    if "error" not in rn:
        result.update({
            "value": round(rn["img_s"], 2),
            "vs_baseline": round(rn["img_s"] / BASELINE_IMG_S, 3),
            "batch": rn["batch"], "image_size": rn["image"],
            "step_ms": round(rn["step_ms"], 2), "mfu": rn["mfu"],
        })
    else:
        result.update({"value": None, "vs_baseline": None,
                       "error": rn["error"]})
    lstm = legs["lstm"]
    if "error" not in lstm:
        result["lstm_tokens_per_s"] = round(lstm["tokens_per_s"], 1)
        result["lstm_config"] = "b%d_seq%d_2x200" % (lstm["batch"],
                                                     lstm["seq_len"])
    ar = legs["allreduce"]
    if "error" not in ar:
        result["allreduce_gbps"] = round(ar["gbps"], 3)
        # interpretive guard: host shared-memory loopback through 8 local
        # CPU processes — it measures the kvstore code path, NOT an
        # interconnect (v5e ICI spec ~186 GB/s/link; tools/bandwidth)
        result["allreduce_note"] = (
            "host-loopback (8 CPU processes); measures the kvstore path, "
            "not interconnect bandwidth")
    result["legs"] = legs
    failed = _failed_legs(legs)
    if failed:
        result["failed_legs"] = failed
    print(json.dumps(result))
    return 1 if failed else 0


if __name__ == "__main__":
    if "--inproc" in sys.argv[1:]:
        raise SystemExit(main_inproc())
    try:
        raise SystemExit(main())
    except Exception as exc:  # always leave ONE JSON line for the driver
        import traceback

        traceback.print_exc()
        print(json.dumps({
            "metric": "resnet50_train_throughput",
            "value": None,
            "unit": "img/s",
            "vs_baseline": None,
            "error": "%s: %s" % (type(exc).__name__, exc),
        }))
        raise SystemExit(1)
