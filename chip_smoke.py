#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the two main paths once, through the entry points a user
calls, at the full width of the models the bench uses (weights random, from
a seed), and checks what comes out by the repo's own means:

  0 device   the chip is there and is the default context; block_until_ready
             is a real barrier; what one tiny dispatch costs
  1 train    ResNet-50, 224², batch 256, bf16, parallel.SPMDTrainer on a
             one-device mesh — the bench's path
  2 fit      the same network through mx.mod.Module(context=mx.tpu(0)).fit
             on a synthetic iterator, f32, batch 32 — the user's path
  3 serve    Transformer-base through serving.PagedKVDecoder (8 lanes x 1024
             slots): greedy tokens against a full re-forward on the device;
             then prefix cache + K-token megasteps; a SECOND run of the phase
             in the same call (another process, on the program store the
             first left warm) loads its programs, imports nothing of Pallas
             and steps the first's tokens; then the shared pool's write and
             read operators at the benchmark's 64 lanes x 65,536 slots (the
             written pool bit for bit, the kernel's read against the whole
             pool's at the highest precision)
  5 4 chips  (when >= 4 devices) phase 1 on a {"data": 4} mesh, Module on
             four contexts, ring attention on {"data": 2, "seq": 2}
  6 prefill  a prefill's causal attention at OLMoE's (1, 16, 2048, 128) and
             nemotron's (1, 32 over 2, 2048, 128) in bfloat16: the blockwise
             kernel the rule names against the dense path, the worst
             relative difference and a layer's time in each; then a full
             layer of dots3-note-prev under its learned selection (128 heads
             of 192 over 128, 8,192 positions, the 2,048 highest of a
             64 x 128 indexer): the kernel under the layer's mask against
             XLA's query blocks
  7 write    a decode step's write of its K/V rows into a layer's two
             page-major pools at ouro-2.6b.generate's operands (16 rows,
             bfloat16 (1280, 16, 2048)) and transformer-base.generate's (64
             rows, float32 (4096, 16, 512)): the kernel the rule names
             against XLA's scatter, the pools bit for bit

(There is no phase 4: it checked the pattern engine's kernels and went with
them; the later phases keep the numbers the records cite them by.)

Every phase prints PASS, FAIL or SKIP <reason>; a skip is never the result
of an exception. Any FAIL makes the exit code 1. With no TPU the script
exits 2 before running anything and prints no result line. The last line of
stdout on a run of all phases is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--phases 0,3`` runs a subset (the JSON then also carries "phases").
``--rehearse-cpu`` is for debugging this script in a sandbox without a chip:
tiny sizes, Pallas in interpret mode, loudly labelled, no result line.
"""
import argparse
import gc
import json
import os
import shutil
import sys
import time
import traceback

REHEARSE = "--rehearse-cpu" in sys.argv
if REHEARSE:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")

import jax  # noqa: E402

DEV = jax.devices()[0]
if DEV.platform != "tpu" and not REHEARSE:
    sys.stderr.write(
        "chip_smoke: JAX found no TPU (platform=%s); this script runs on the "
        "chip only — no result.\n" % DEV.platform)
    sys.exit(2)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402  (sets the compile-cache directory)
from mxnet_tpu import models, parallel, telemetry  # noqa: E402

# full sizes are the contract; the rehearsal sizes only debug the script
if not REHEARSE:
    SZ = dict(
        image=224, classes=1000, train_batch=256, train_dtype="bfloat16",
        fit_batch=32, fit_batches=8,
        tf=dict(vocab_size=32000, num_layers=6, num_heads=8, model_dim=512,
                ffn_dim=2048),
        lanes=8, slots=1024, page=16, prompts=(5, 17, 40, 64, 100),
        new_tokens=33, mega_k=4, shared_prefix=48,
        tf_train_batch=8, tf_train_seq=512,
        pool=(64, 8, 64 * 1024, 64),
        matmul_n=8192,
        # a prefill's attention layer: (query heads, key/value heads, bucket,
        # head width) of olmoe-1b-7b.score and nemotron-3-nano-30b-a3b.generate
        prefill_attn={"olmoe": (16, 16, 2048, 128),
                      "nemotron": (32, 2, 2048, 128)},
        # dots3-note-prev's full layer at its bucket: (heads, bucket, key
        # width, value width, index heads, index width, topk)
        sparse_attn=(128, 8192, 192, 128, 64, 128, 2048),
        # a layer's two page-major pools and the lanes of a step:
        # ouro-2.6b.generate's and transformer-base.generate's
        pool_write={"ouro-2.6b": ((1280, 16, 2048), "bfloat16", 16),
                    "transformer-base": ((4096, 16, 512), "float32", 64)},
        # ouro-2.6b.generate's read: 16 lanes of 16 heads of 128 over a pass's
        # 16 x 320 slots (tables of 20 pages), and the contexts to hold
        paged_read_ouro=(16, 16, 16 * 320, 128, (1, 16, 17, 195, 320)),
    )
else:
    SZ = dict(
        image=32, classes=16, train_batch=8, train_dtype=None,
        fit_batch=4, fit_batches=6,
        tf=dict(vocab_size=64, num_layers=2, num_heads=2, model_dim=128,
                ffn_dim=256),
        lanes=4, slots=64, page=8, prompts=(3, 9, 14), new_tokens=9,
        mega_k=4, shared_prefix=16,
        tf_train_batch=2, tf_train_seq=16,
        pool=(4, 2, 64, 64),
        matmul_n=256,
        prefill_attn={"olmoe": (2, 2, 32, 16), "nemotron": (8, 2, 32, 16)},
        sparse_attn=(4, 64, 16, 8, 4, 8, 16),
        pool_write={"ouro-2.6b": ((24, 16, 256), "bfloat16", 4),
                    "transformer-base": ((24, 16, 128), "float32", 8)},
        paged_read_ouro=(4, 2, 4 * 40, 64, (1, 8, 9)),
    )


# ------------------------------------------------------------ measuring aids
class Compiles:
    """Every XLA compile request this process makes (a persistent-cache hit
    still counts as a request; its seconds are then the load time)."""

    def __init__(self):
        self.n = self.hits = self.misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snap(self):
        return (self.n, self.seconds, self.hits, self.misses)


COMPILES = Compiles()


def say(msg=""):
    print(msg, flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError(what)
    say("    ok: %s" % what)


def on_tpu(arr):
    """Whether a jax array (or NDArray) lives on the accelerator."""
    data = arr._jax() if hasattr(arr, "_jax") else arr
    want = "cpu" if REHEARSE else "tpu"
    return all(d.platform == want for d in data.devices())


def peak_gb():
    stats = DEV.memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 2 ** 30


def rel_l2(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-12))


def counters_since(before):
    now = telemetry.counters()
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v != before.get(k, 0)}


# ------------------------------------------------------------------ phase 0
def phase_device():
    import jaxlib

    say("    platform=%s device_kind=\"%s\" count=%d"
        % (DEV.platform, DEV.device_kind, len(jax.devices())))
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "absent"
    say("    jax %s  jaxlib %s  libtpu %s" % (jax.__version__,
                                             jaxlib.__version__,
                                             libtpu_version))
    from mxnet_tpu import compile_cache

    say("    compile cache: %s (JAX_COMPILATION_CACHE_DIR %s)"
        % (compile_cache.directory(),
           "set" if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "unset"))
    check(compile_cache.directory(), "a persistent compile cache is placed")

    from mxnet_tpu import engine, image_native, io_native

    native = {"engine": engine.get().native,
              "io": io_native.available(),
              "image": image_native.available()}
    say("    native libraries: " + "  ".join(
        "%s: %s" % (k, "native" if v else "python") for k, v in native.items()))
    if shutil.which("g++"):
        check(all(native.values()),
              "with a toolchain present every native library built and loaded")

    if not REHEARSE:
        check(mx.current_context() == mx.tpu(0), "default context is tpu(0)")
    ones = mx.nd.ones((2, 3))
    check(on_tpu(ones) and (ones.asnumpy() == 1).all(),
          "mx.nd.ones((2,3)) lives on the device and reads back")
    try:
        mx.tpu(len(jax.devices())).jax_device
    except mx.MXNetError as exc:
        say("    ok: a chip id past the visible chips raises (%s)" % exc)
    else:
        if not REHEARSE:
            raise AssertionError("tpu(n) past the visible chips resolved")

    # is block_until_ready a real barrier? A chain of large matmuls timed to
    # block_until_ready and timed to a host scalar fetch must agree, and
    # both must dwarf the enqueue.
    n = SZ["matmul_n"]
    x = jnp.full((n, n), 0.001, jnp.bfloat16)

    @jax.jit
    def chain(a):
        for _ in range(8):
            a = (a @ a) * 0.001
        return a

    @jax.jit
    def corner(a):
        return jnp.sum(a[0, :8].astype(jnp.float32))

    float(corner(chain(x)))  # compile both
    rows = []
    for _ in range(3):
        t0 = time.perf_counter()
        y = chain(x)
        t_enq = time.perf_counter() - t0
        y.block_until_ready()
        t_bur = time.perf_counter() - t0
        t0 = time.perf_counter()
        float(corner(chain(x)))
        rows.append((t_enq, t_bur, time.perf_counter() - t0))
    t_enq, t_bur, t_fetch = min(rows, key=lambda r: r[1])
    say("    8 chained %d² bf16 matmuls: enqueue %.2f ms, to block_until_ready "
        "%.2f ms (%.1f TFLOP/s), to host scalar fetch %.2f ms"
        % (n, t_enq * 1e3, t_bur * 1e3, 16 * n ** 3 / t_bur / 1e12,
           t_fetch * 1e3))
    if not REHEARSE:
        check(abs(t_bur - t_fetch) <= 0.1 * t_fetch + 2e-3 and
              t_enq < 0.2 * t_bur,
              "block_until_ready waits for the device (agrees with a host "
              "fetch; dispatch itself is asynchronous)")
        # the NDArray barriers rest on it: wait_to_read may not return
        # before the chip could possibly have finished, and must leave the
        # array ready
        from mxnet_tpu.device_info import bf16_peak_flops

        a = mx.nd.array(np.full((n, n), 0.001, "float32"))
        mx.nd.dot(a, a).wait_to_read()  # compile
        t0 = time.perf_counter()
        b = a
        for _ in range(4):
            b = mx.nd.dot(b, a)
        b.wait_to_read()
        t_wait = time.perf_counter() - t0
        floor = 4 * 2 * n ** 3 / bf16_peak_flops(DEV.device_kind)
        check(b._jax().is_ready() and t_wait >= floor,
              "NDArray.wait_to_read is a barrier (4 chained %d² f32 "
              "mx.nd.dot: returned after %.1f ms with the result ready; the "
              "chip's peak allows no less than %.1f ms)"
              % (n, t_wait * 1e3, floor * 1e3))
        mx.nd.waitall()
        del a, b

    # what a trivially small jitted dispatch costs
    @jax.jit
    def tiny(a):
        return a + 1.0

    a = tiny(jnp.zeros((8,), jnp.float32))
    a.block_until_ready()
    reps = 1000
    t0 = time.perf_counter()
    for _ in range(reps):
        a = tiny(a)
    a.block_until_ready()
    chained = (time.perf_counter() - t0) / reps
    t0 = time.perf_counter()
    for _ in range(200):
        a = tiny(a)
        a.block_until_ready()
    synced = (time.perf_counter() - t0) / 200
    t0 = time.perf_counter()
    for _ in range(200):
        a = tiny(a)
        float(a[0])
    fetched = (time.perf_counter() - t0) / 200
    say("    tiny jitted dispatch: %.0f us chained, %.0f us with "
        "block_until_ready each, %.0f us with a host read of one element each"
        % (chained * 1e6, synced * 1e6, fetched * 1e6))


# ------------------------------------------------------------------ phase 1
def resnet50():
    return models.get_symbol(
        "resnet-50", num_classes=SZ["classes"],
        image_shape="3,%d,%d" % (SZ["image"], SZ["image"]))


def make_trainer(net, mesh, batch):
    """bench.py:_make_trainer, on the given mesh."""
    trainer = parallel.SPMDTrainer(
        net, mesh, optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        compute_dtype=SZ["train_dtype"], data_names=("data",),
        label_names=("softmax_label",))
    image = SZ["image"]
    trainer.init_params({"data": (batch, 3, image, image)},
                        {"softmax_label": (batch,)}, seed=0)
    return trainer


def train_batch(trainer, batch):
    rs = np.random.RandomState(0)
    image = SZ["image"]
    x = rs.rand(batch, 3, image, image).astype("float32")
    if SZ["train_dtype"]:
        x = x.astype(jnp.dtype(SZ["train_dtype"]))
    y = rs.randint(0, SZ["classes"], (batch,)).astype("float32")
    place = lambda a: jax.device_put(a, trainer.rules.named(
        trainer.rules.batch_spec(a.shape)))
    return place(x), place(y)


def check_probs(out, batch):
    out = np.asarray(out, np.float32)
    check(out.shape == (batch, SZ["classes"]) and np.isfinite(out).all(),
          "outputs are finite with shape %s" % (out.shape,))
    check(np.allclose(out.sum(axis=1), 1.0, atol=2e-2),
          "softmax rows sum to 1 (max deviation %.1e)"
          % np.abs(out.sum(axis=1) - 1).max())


def run_trainer_steps(trainer, x, y, batch, timed=5):
    """Warm up (compile), then ``timed`` steps that must compile nothing.
    Returns (first-step outputs on host, seconds per step)."""
    watch = ("fc1_weight", "bn_data_beta", "stage1_unit1_conv1_weight")
    names = [n for n in watch if n in trainer.params] or \
        sorted(trainer.params)[:3]
    before = {n: np.asarray(trainer.params[n], np.float32) for n in names}
    outs = trainer.step({"data": x}, {"softmax_label": y})
    first = np.asarray(outs[0], np.float32)
    outs = trainer.step({"data": x}, {"softmax_label": y})
    jax.block_until_ready(outs)
    n0 = COMPILES.n
    t0 = time.perf_counter()
    for _ in range(timed):
        outs = trainer.step({"data": x}, {"softmax_label": y})
    jax.block_until_ready(outs)
    step_s = (time.perf_counter() - t0) / timed
    check(COMPILES.n == n0, "zero compiles in %d steps after warm-up" % timed)
    check_probs(first, batch)
    check_probs(outs[0], batch)
    for n in names:
        check(not np.array_equal(before[n],
                                 np.asarray(trainer.params[n], np.float32)),
              "parameter %s changed" % n)
    check(all(on_tpu(v) for v in trainer.params.values()),
          "all %d parameters live on the device" % len(trainer.params))
    return first, step_s


FIRST_STEP = {}  # phase 1's first-step outputs, for phase 5


def phase_train():
    batch = SZ["train_batch"]
    net = resnet50()
    mesh = parallel.make_mesh((1,), axis_names=("data",), devices=[DEV])
    trainer = make_trainer(net, mesh, batch)
    x, y = train_batch(trainer, batch)
    first, step_s = run_trainer_steps(trainer, x, y, batch)
    FIRST_STEP["probs"] = first
    say("    info: batch %d (stated, not laddered), %s compute, %.1f ms/step, "
        "%.0f img/s, peak device memory so far %.2f GiB"
        % (batch, SZ["train_dtype"] or "float32", step_s * 1e3,
           batch / step_s, peak_gb()))


# ------------------------------------------------------------------ phase 2
def phase_fit():
    batch, n_batches = SZ["fit_batch"], SZ["fit_batches"]
    image = SZ["image"]
    rs = np.random.RandomState(1)
    data = rs.rand(batch * n_batches, 3, image, image).astype("float32")
    label = rs.randint(0, SZ["classes"],
                       (batch * n_batches,)).astype("float32")
    train = mx.io.NDArrayIter(data, label, batch_size=batch)
    ctx = mx.cpu(0) if REHEARSE else mx.tpu(0)
    mod = mx.mod.Module(resnet50(), context=ctx)
    mod.bind(train.provide_data, train.provide_label)
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2))
    before = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    seen = [COMPILES.n]  # compile requests before fit, then after each batch

    def record(param):
        seen.append(COMPILES.n)

    metric = mx.metric.create("acc")
    t0 = time.perf_counter()
    mod.fit(train, num_epoch=1, eval_metric=metric, kvstore="local",
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9,
                              "wd": 1e-4},
            batch_end_callback=[mx.callback.Speedometer(batch, 2), record])
    wall = time.perf_counter() - t0
    check(len(seen) == n_batches + 1, "fit ran %d batches" % n_batches)
    check(seen[-1] == seen[3],
          "zero compiles after the third batch (requests by batch: %s)"
          % [b - a for a, b in zip(seen, seen[1:])])
    name, value = metric.get()
    check(np.isfinite(value), "metric %s is finite" % name)
    after, aux = mod.get_params()
    changed = [k for k in before
               if not np.array_equal(before[k], after[k].asnumpy())]
    check(len(changed) == len(before),
          "get_params(): all %d parameters changed" % len(before))
    check(all(np.isfinite(v.asnumpy()).all()
              for v in list(after.values()) + list(aux.values())),
          "parameters and BatchNorm moving statistics are finite")
    exe = mod._exec_group.execs[0]
    check(all(on_tpu(a) for a in exe.arg_arrays) and
          all(a.context == ctx for a in exe.arg_arrays),
          "executor arrays live on %r" % ctx)
    say("    info: batch %d f32 (this path has no bf16 switch), fit of %d "
        "batches took %.1f s including compilation"
        % (batch, n_batches, wall))


# ------------------------------------------------------------------ phase 3
def transformer_params(seq_len, seed=0):
    """Random Transformer-base weights at the training graph's own shapes
    (the prefill / decode / chunk programs bind the same names)."""
    from mxnet_tpu.models import transformer as tfm

    net = tfm.get_symbol(seq_len=seq_len, **SZ["tf"])
    shapes, _, _ = net.infer_shape(data=(1, seq_len),
                                   softmax_label=(1, seq_len))
    rs = np.random.RandomState(seed)
    params = {}
    for name, shape in zip(net.list_arguments(), shapes):
        if name in ("data", "softmax_label"):
            continue
        if name.endswith(("_gamma",)):
            params[name] = np.ones(shape, "float32")
        elif name.endswith(("_beta", "_bias")):
            params[name] = np.zeros(shape, "float32")
        else:
            params[name] = (rs.randn(*shape) * 0.05).astype("float32")
    return net, params


class Reference:
    """The training graph's full forward over a whole sequence, on the same
    device — the oracle tests/test_kv_decode.py uses. One teacher-forced
    forward per sequence checks every generated token at once: token t must
    be the arg-max of the reference at position prompt+t-1."""

    def __init__(self, net, params, seq_len, ctx):
        self.seq_len = seq_len
        self.exe = net.simple_bind(ctx, grad_req="null", data=(1, seq_len),
                                   softmax_label=(1, seq_len))
        for k, v in params.items():
            self.exe.arg_dict[k][:] = v

    def check(self, prompt, tokens, label):
        L, n = len(prompt), len(tokens)
        seq = np.zeros((1, self.seq_len), np.float32)
        seq[0, :L] = prompt
        seq[0, L:L + n - 1] = tokens[:-1]
        self.exe.arg_dict["data"][:] = seq
        self.exe.forward(is_train=False)
        probs = self.exe.outputs[0].asnumpy().reshape(self.seq_len, -1)
        rows = probs[L - 1:L - 1 + n]
        want = rows.argmax(axis=-1)
        same = int((want == tokens).sum())
        # a token that is not the arg-max may only be a near-tie: both paths
        # run f32 matmuls at the chip's default precision, in different
        # orders, so two candidates closer than that rounding can swap
        logp = np.log(np.maximum(rows, 1e-30))
        deficit = logp.max(axis=-1) - logp[np.arange(n), tokens]
        spread = float((logp.max(axis=-1) - logp.mean(axis=-1)).mean())
        tol = 0.01 * spread
        check((deficit <= tol).all(),
              "%s: %d/%d tokens are the re-forward's arg-max; worst "
              "log-prob deficit %.2e (near-tie bound %.2e)"
              % (label, same, n, deficit.max(), tol))
        return same, n


def check_step_inputs(onehot, slots):
    """What a decode step makes on the device of a write slot and a page
    table a lane against the host's arrays: the write by slot index
    (``KVPoolSlotWrite``, the pool DONATED as the decode program takes it)
    against the host's one-hot blend (``KVPoolWrite`` fed ``onehot``, whose
    last lane is idle), bit for bit in both pool types, and ``KVPageMask``
    against the mask of lanes at random positions in frames scattered over
    the pool; float32, exactly."""
    from mxnet_tpu.ops.attention import (_kv_page_mask, _kv_pool_slot_write,
                                         _kv_pool_write, pool_shape)

    (R, S), page = onehot.shape, SZ["page"]
    H, D = SZ["pool"][1], SZ["pool"][3]
    bound = pool_shape(H, D, S, page)   # page-major: a row of H x D is tiles
    write_slot = np.append(slots, -1).astype("float32")[:, None]
    for dt in ("float32", "bfloat16"):
        k1, k2 = jax.random.split(jax.random.PRNGKey(7 + len(dt)))
        pool = jax.random.normal(k1, bound, jnp.float32).astype(dt)
        rows = jax.random.normal(k2, (R, H, D), jnp.float32).astype(dt)
        want = jax.jit(lambda *a: _kv_pool_write({}, *a))(pool, rows, onehot)
        held = pool.unsafe_buffer_pointer()
        got, = jax.jit(lambda *a: _kv_pool_slot_write({}, *a),
                       donate_argnums=(0,))(pool, rows, write_slot)
        bits = jnp.uint16 if dt == "bfloat16" else jnp.uint32
        same = jax.jit(lambda a, b: jnp.all(
            jax.lax.bitcast_convert_type(a, bits)
            == jax.lax.bitcast_convert_type(b, bits)))
        check(bool(same(got, want)) and pool.is_deleted()
              and got.unsafe_buffer_pointer() == held,
              "KVPoolSlotWrite %s %s: the host's one-hot blend, bit for "
              "bit, written into the donated pool's own buffer"
              % (dt, bound))
    rs = np.random.RandomState(13)
    max_pages = S // R // page
    table = rs.permutation(S // page)[:R * max_pages].reshape(R, max_pages)
    pos = rs.randint(0, max_pages * page, (R, 1))
    pos[0], pos[1] = 0, page - 1        # one slot; a context that ends a page
    phys = np.take_along_axis(table, pos // page, 1) * page + pos % page
    phys[-1] = -1
    mask = np.full((R, S), -1e9, "float32")
    for r in range(R - 1):
        seen = (table[r, :, None] * page + np.arange(page)).reshape(-1)
        mask[r, seen[:pos[r, 0] + 1]] = 0.0
    got_mask = jax.jit(lambda *a: _kv_page_mask(
        {"page_size": page, "num_slots": S}, *a))(
            table.astype("float32"), pos.astype("float32"),
            phys.astype("float32"))
    check(got_mask.dtype == jnp.float32 and bool(jnp.all(got_mask == mask)),
          "KVPageMask %s, pages of %d: the host's masks, element for element"
          % ((R, S), page))


def check_pool_operators():
    """The chunk's write into the shared pool, a decode step's write and
    its read of it (ops/attention.py), at the benchmark's lanes x heads x
    slots x dh. The one-hot write against the blend of broadcast products it replaced, which
    multiplies by exactly 0 and 1: bit for bit, float32 (on the chip only
    ``Precision.HIGHEST`` keeps a row's 24 bits through the one-hot matmul)
    and bfloat16. The read, two default-precision contractions, against the
    same sums at the highest precision."""
    from mxnet_tpu.ops.attention import (_kv_pool_attention, _kv_pool_write,
                                         pool_shape)

    R, H, S, D = SZ["pool"]
    bound = pool_shape(H, D, S, SZ["page"])
    assert bound == (S // SZ["page"], SZ["page"], H * D)

    def as_bound(pool):     # (H, S, D) by slot -> the layout a decoder binds
        return pool.transpose(1, 0, 2).reshape(bound)

    rs = np.random.RandomState(11)
    slots = rs.choice(S, R - 1, replace=False)   # the last lane is idle
    onehot = np.zeros((R, S), "float32")
    onehot[np.arange(R - 1), slots] = 1.0
    mask = np.full((R, S), -1e9, "float32")
    for r in range(R - 1):
        mask[r, rs.choice(S, S // 64, replace=False)] = 0.0
        mask[r, slots[r]] = 0.0
    check_step_inputs(onehot, slots)
    onehot, mask = jnp.asarray(onehot), jnp.asarray(mask)

    def blend(pool, rows, onehot):
        dt = pool.dtype
        keep = (1.0 - jnp.sum(onehot, axis=0).reshape(1, S, 1)).astype(dt)
        return pool * keep + jnp.sum(
            rows[:, :, None, :] * onehot[:, None, :, None].astype(dt), axis=0)

    for dt in ("float32", "bfloat16"):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(len(dt)), 3)
        pool = jax.random.normal(k1, (H, S, D), jnp.float32).astype(dt)
        rows = jax.random.normal(k2, (R, H, D), jnp.float32).astype(dt)
        paged = jax.jit(as_bound)(pool)
        got = jax.jit(lambda *a: _kv_pool_write({}, *a))(paged, rows, onehot)
        bits = jnp.uint16 if dt == "bfloat16" else jnp.uint32
        same = jax.jit(lambda a, b: jnp.all(
            jax.lax.bitcast_convert_type(a, bits)
            == jax.lax.bitcast_convert_type(b, bits)))
        written = got.reshape(S, H, D)[slots]
        check(bool(same(got, jax.jit(lambda *a: as_bound(blend(*a)))(
                  pool, rows, onehot)))
              and bool(same(written, rows[:R - 1])),
              "KVPoolWrite %s %s: %d written slots hold their rows and the "
              "pool is the broadcast blend's, bit for bit"
              % (dt, bound, R - 1))
        q = jax.random.normal(k3, (R, H, D), jnp.float32).astype(dt)
        ctx = jax.jit(lambda *a: _kv_pool_attention({"scale": -1.0}, *a))(
            q, got, paged, mask)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda *a: _kv_pool_attention(
                {"scale": -1.0}, *(t.astype(jnp.float32) for t in a)))(
                    q, got, paged, mask)
        compare("KVPoolAttention %s, the whole pool (one bfloat16 pass)" % dt,
                [ctx], [want], 1e-2)
    check_paged_read()


def check_paged_read():
    """A decode step's read as the chip runs it: ``KVPoolAttention`` handed a
    page table over page-major pools, which is the kernel that walks the
    table (``ops/pallas_paged_read.py``; off the chip, XLA's gather), against
    the whole-pool read under the mask made of the same table at the highest
    precision. The benchmark's lanes at random contexts, one that rides
    along, two that share their first frame, both pool types; then
    ``ouro-2.6b.generate``'s operands (the widest rows under the shortest
    tables: a block of the kernel is eight of a table's twenty pages), where
    a row's last block fetches its live pages only: contexts of one slot, a
    page, a page and one, the traffic's mean and the whole table."""
    R, H, S, D = SZ["pool"]
    page = SZ["page"]
    max_pages = S // R // page
    rs = np.random.RandomState(17)
    pos = rs.randint(0, max_pages * page, (R, 1))
    pos[0], pos[1], pos[2] = 0, page - 1, max_pages * page - 1
    for dt in ("float32", "bfloat16"):
        paged_read_against_the_whole_pool(dt, R, H, S, D, page, pos)
    R, H, S, D, contexts = SZ["paged_read_ouro"]
    pos = rs.randint(0, S // R, (R, 1))
    pos[:len(contexts), 0] = np.asarray(contexts) - 1
    paged_read_against_the_whole_pool("bfloat16", R, H, S, D, page, pos)


def paged_read_against_the_whole_pool(dt, R, H, S, D, page, pos):
    """``R`` lanes of ``H`` heads of ``D`` over ``S`` slots, lane r attending
    its first ``pos[r] + 1`` slots; the last lane rides along."""
    from mxnet_tpu.ops.attention import (_kv_page_mask, _kv_pool_attention,
                                         pool_read_form, pool_shape)

    bound = pool_shape(H, D, S, page)
    max_pages = S // R // page
    rs = np.random.RandomState(17)
    table = rs.permutation(S // page)[:R * max_pages].reshape(R, max_pages)
    table[1, 0] = table[0, 0]
    write_slot = np.take_along_axis(table, pos // page, 1) * page + pos % page
    write_slot[-1] = -1
    step = [jnp.asarray(a, jnp.float32) for a in (table, pos, write_slot)]
    mask = jax.jit(lambda *a: _kv_page_mask(
        {"page_size": page, "num_slots": S}, *a))(*step)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3 + len(dt)), 3)
    pool_k = jax.random.normal(k1, bound, jnp.float32).astype(dt)
    pool_v = jax.random.normal(k2, bound, jnp.float32).astype(dt)
    q = jax.random.normal(k3, (R, H, D), jnp.float32).astype(dt)
    form = pool_read_form(q, pool_k, pool_v, step[0], page)
    check(form == ("own_pages" if REHEARSE else "kernel"),
          "the rule names the read of %s pools %s: %s" % (dt, bound, form))
    ctx = jax.jit(lambda *a: _kv_pool_attention(
        {"scale": -1.0, "page_size": page}, *a))(
            q, pool_k, pool_v, mask, *step)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: _kv_pool_attention(
            {"scale": -1.0}, *(t.astype(jnp.float32) for t in a)))(
                q, pool_k, pool_v, mask)
    check(bool(jnp.all(jnp.isfinite(ctx.astype(jnp.float32)))),
          "the lane that rides along reads finite")
    compare("KVPoolAttention %s, %d lanes' live pages of a table of %d (%s)"
            % (dt, R, max_pages, form), [ctx[:-1]], [want[:-1]], 1e-2)
    # a lane at a time: a short context's error does not hide in the norm of
    # the long ones'
    worst = max(rel_l2(ctx[r], want[r]) for r in range(R - 1))
    check(worst <= 3e-2, "every lane alone within 3e-2 (worst %.1e)" % worst)


def phase_serve():
    from mxnet_tpu.serving import PagedKVDecoder

    telemetry.set_mode("counters")
    ctx = mx.current_context()
    slots, lanes, page = SZ["slots"], SZ["lanes"], SZ["page"]
    net, params = transformer_params(slots)
    ref = Reference(net, params, slots, ctx)
    rs = np.random.RandomState(7)
    vocab = SZ["tf"]["vocab_size"]
    n_new, k = SZ["new_tokens"], SZ["mega_k"]

    def prompts_of(lengths, shared=0):
        head = rs.randint(1, vocab, (shared,))
        return [np.concatenate([head, rs.randint(1, vocab, (n,))])
                .astype(np.float32) for n in lengths]

    tokens = {}   # label -> what each prompt generated

    def run(dec, prompts, label, k):
        toks = dec.greedy(prompts, n_new, k=k)
        tokens[label] = [[int(t) for t in row] for row in toks]
        same = total = 0
        for i, (p, t) in enumerate(zip(prompts, toks)):
            s, n = ref.check(p.astype(np.int64), np.asarray(t, np.int64),
                             "%s prompt %d (len %d)" % (label, i, len(p)))
            same, total = same + s, total + n
        return same, total

    say("  -- paged decode, one token per dispatch")
    pallas = lambda: [m for m in sys.modules if m.startswith(
        ("jax._src.pallas", "jax.experimental.pallas"))]
    traced_before, c0 = pallas(), telemetry.counters()
    dec = PagedKVDecoder(params, max_len=slots, page_size=page, lanes=lanes,
                         ctx=ctx, **SZ["tf"])
    dec.warmup()
    store = {what: counters_since(c0).get("serving.program_store." + what, 0)
             for what in ("hit", "miss", "stale")}
    say("    info: the program store: %s; Pallas %s"
        % (store, "imported" if pallas() else "not imported"))
    if store["hit"] == 2 and not traced_before:
        check(not pallas(), "decode and prefill came out of the program "
              "store: nothing was traced, and Pallas is not imported")
    same, total = run(dec, prompts_of(SZ["prompts"]), "k=1", 1)
    c0, n0 = telemetry.counters(), COMPILES.n
    s2, t2 = run(dec, prompts_of(SZ["prompts"][::-1]), "k=1 again", 1)
    ran = counters_since(c0)
    check(COMPILES.n == n0 and not ran.get("executor.retrace")
          and not ran.get("executor.compile"),
          "second pass: zero compiles, zero retraces")
    check(dec.stats()["active"] == 0 and dec.stats()["pages_in_use"] == 0,
          "every lane retired and every page returned")
    say("    info: %d/%d tokens identical to the re-forward arg-max"
        % (same + s2, total + t2))
    del dec
    gc.collect()

    say("  -- prefix cache + %d-token megasteps" % k)
    dec = PagedKVDecoder(params, max_len=slots, page_size=page, lanes=lanes,
                         prefix_cache=True, ctx=ctx, **SZ["tf"])
    dec.warmup()
    lengths = [n for n in SZ["prompts"] if n < slots - n_new][:lanes]
    shared = SZ["shared_prefix"]
    same, total = run(dec, prompts_of(lengths, shared), "prefix k=%d" % k, k)
    stats = dec.stats()
    check(stats["prefix_hit_rate"] > 0,
          "prompts sharing a %d-token head hit the prefix cache (chunk hit "
          "rate %.2f)" % (shared, stats["prefix_hit_rate"]))
    c0, n0 = telemetry.counters(), COMPILES.n
    s2, t2 = run(dec, prompts_of(lengths[::-1], shared),
                 "prefix k=%d again" % k, k)
    ran = counters_since(c0)
    check(COMPILES.n == n0 and not ran.get("executor.retrace")
          and not ran.get("executor.compile"),
          "second pass: zero compiles, zero retraces")
    say("    info: %d/%d tokens identical to the re-forward arg-max; peak "
        "device memory so far %.2f GiB"
        % (same + s2, total + t2, peak_gb()))
    # a process before this one in the same call left what it generated: this
    # one, on the store that one warmed, steps the same tokens
    left = os.path.join("chiprun_out", "smoke_serve_tokens.json")
    if os.path.exists(left):
        with open(left) as f:
            check(json.load(f) == tokens, "the tokens of the process before "
                  "this one (%s), token for token" % left)
    else:
        os.makedirs("chiprun_out", exist_ok=True)
        with open(left, "w") as f:
            json.dump(tokens, f)
    say("  -- the shared pool's write and read operators, %s" % (SZ["pool"],))
    check_pool_operators()


# ---------------------------------------------------------- comparing aids
def is_mosaic(fn, *args):
    """The lowering carries a Mosaic custom call: compiled, not interpreted."""
    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def compare(label, got, want, tol):
    errs = [rel_l2(g, w) for g, w in zip(jax.tree_util.tree_leaves(got),
                                         jax.tree_util.tree_leaves(want))]
    check(max(errs) <= tol and all(np.isfinite(np.asarray(g, np.float32))
                                   .all() for g in
                                   jax.tree_util.tree_leaves(got)),
          "%s: relative L2 error %s <= %.0e"
          % (label, ", ".join("%.1e" % e for e in errs), tol))


# ------------------------------------------------------------------ phase 5
def phase_four_chips():
    devices = jax.devices()[:4]
    batch = SZ["train_batch"]

    say("  -- SPMDTrainer on a {\"data\": 4} mesh, global batch %d" % batch)
    mesh = parallel.make_mesh({"data": 4}, devices=devices)
    trainer = make_trainer(resnet50(), mesh, batch)
    x, y = train_batch(trainer, batch)
    first, step_s = run_trainer_steps(trainer, x, y, batch)
    shards = {s.device for s in x.addressable_shards}
    check(len(shards) == 4, "the batch has shards on 4 distinct devices")
    w = next(iter(trainer.params.values()))
    check(len({s.device for s in w.addressable_shards}) == 4,
          "parameters are laid out on 4 devices")
    if not REHEARSE:
        used = [d.memory_stats()["bytes_in_use"] for d in devices]
        check(all(u > 0 for u in used),
              "every chip holds memory (%s MiB)"
              % [u >> 20 for u in used])
    lr = jnp.asarray(0.1, "float32")
    hlo = trainer._step_fn.lower(
        trainer.params, trainer.aux, trainer.opt_state,
        {"data": x, "softmax_label": y}, trainer._base_key,
        lr).compile().as_text()
    check("all-reduce" in hlo,
          "the compiled step contains a cross-device all-reduce")
    if "probs" in FIRST_STEP:
        compare("first-step outputs vs the one-chip run at the same global "
                "batch", first, FIRST_STEP["probs"], 5e-2)
    say("    info: %.1f ms/step on 4 chips (information, not a claim)"
        % (step_s * 1e3))
    del trainer, x, y, hlo
    gc.collect()

    say("  -- Module(context=[tpu(0..3)]).fit")
    fit_batch = 4 * SZ["fit_batch"]
    image = SZ["image"]
    rs = np.random.RandomState(2)
    n_batches = 4
    data = rs.rand(fit_batch * n_batches, 3, image, image).astype("float32")
    label = rs.randint(0, SZ["classes"],
                       (fit_batch * n_batches,)).astype("float32")
    train = mx.io.NDArrayIter(data, label, batch_size=fit_batch)
    ctxs = [mx.cpu(i) if REHEARSE else mx.tpu(i) for i in range(4)]
    mod = mx.mod.Module(resnet50(), context=ctxs)
    metric = mx.metric.create("acc")
    mod.fit(train, num_epoch=1, eval_metric=metric, kvstore="local",
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            initializer=mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2))
    check(mod._spmd is not None,
          "spmd_adapter accepted the fused step (4 distinct devices)")
    check(mod._spmd.trainer.mesh.devices.size == 4,
          "the fused step's mesh spans 4 devices")
    check(np.isfinite(metric.get()[1]), "metric is finite")
    args, _ = mod.get_params()
    check(all(np.isfinite(v.asnumpy()).all() for v in args.values()),
          "parameters are finite after fit")
    del mod
    gc.collect()

    say("  -- ring attention on a {\"data\": 2, \"seq\": 2} mesh")
    from mxnet_tpu.models import transformer as tfm
    from mxnet_tpu.ops import attention as attn_op

    mesh = parallel.make_mesh({"data": 2, "seq": 2}, devices=devices)
    B, T = SZ["tf_train_batch"], SZ["tf_train_seq"]
    net = tfm.get_symbol(seq_len=T, **SZ["tf"])
    trainer = parallel.SPMDTrainer(
        net, mesh, optimizer="sgd", optimizer_params={"learning_rate": 0.05},
        rules=parallel.ShardingRules(mesh, seq_axis="seq"))
    trainer.init_params({"data": (B, T)}, {"softmax_label": (B, T)}, seed=0)
    vocab = SZ["tf"]["vocab_size"]
    tokens = rs.randint(1, vocab, (B, T)).astype("float32")
    labels = rs.randint(1, vocab, (B, T)).astype("float32")
    before = attn_op.DISPATCH_COUNTS["ring"]
    outs = trainer.step({"data": tokens}, {"softmax_label": labels})
    out = np.asarray(outs[0], np.float32)
    moved = attn_op.DISPATCH_COUNTS["ring"] - before
    check(moved >= SZ["tf"]["num_layers"],
          "attention.DISPATCH_COUNTS[\"ring\"] moved by %d" % moved)
    check(out.shape == (B * T, vocab) and np.isfinite(out).all() and
          np.allclose(out.sum(axis=1), 1.0, atol=1e-3),
          "outputs are finite softmax rows")


def phase_prefill_attention():
    """``MultiHeadAttention`` as a prefill calls it (one sequence, causal,
    bfloat16) at two cells' shapes: the form the rule names on this backend
    (the blockwise kernel) against the dense path (the rule held to it), the
    worst difference over the output's largest magnitude and a layer's time in
    each, eight layers inside one program. Then ONE full layer under a
    learned selection at dots3-note-prev's shapes: the form the rule names
    (the kernel under the layer's mask) against XLA's query blocks."""
    from mxnet_tpu.ops import attention as attn_op
    from mxnet_tpu.ops.registry import get_op

    op = get_op("_contrib_MultiHeadAttention").fn
    attrs = {"causal": True, "scale": -1.0, "window": 0}
    layers, rs = 8, np.random.RandomState(6)
    rule = attn_op.attention_form

    def layer_ms(fn, args, n, layers):
        out = fn(*args)
        out.block_until_ready()
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n):
                out = fn(*args)
            out.block_until_ready()
            times.append((time.perf_counter() - t0) / n / layers * 1e3)
        return out, float(np.median(times))

    def both_forms(kernel, other, body, args, n, layers):
        """``body`` jitted and timed with the rule held to the ``kernel``'s
        form and to the ``other`` (it is read at trace time): (a layer's
        milliseconds in each, their worst difference over the largest
        output)."""
        outs = {}
        for form in (kernel, other):
            attn_op.attention_form = lambda *a, _f=form: _f
            try:
                # a function of its own: jit keeps one trace a function
                fn = jax.jit(lambda *a: body(*a))
                if form == kernel and not REHEARSE:
                    check(is_mosaic(fn, *args),
                          "the operator lowers to a Mosaic custom call")
                outs[form] = layer_ms(fn, args, 1 if REHEARSE else n, layers)
            finally:
                attn_op.attention_form = rule
        got, want = (np.asarray(outs[f][0], np.float32)
                     for f in (kernel, other))
        return (outs[kernel][1], outs[other][1],
                float(np.abs(got - want).max() / np.abs(want).max()))

    def verdict(fast, slow, diff, what):
        check(diff < 2e-2, "%s, to a bfloat16 rounding of the output (%.2e)"
              % (what, diff))
        if not REHEARSE:
            check(fast < slow,
                  "the kernel is the faster form where the rule names it")

    for name, (h, hkv, t, d) in SZ["prefill_attn"].items():
        say("  -- %s: %d query heads over %d key/value heads, %d positions, "
            "width %d, bfloat16" % (name, h, hkv, t, d))
        qs, k, v = (jnp.asarray(rs.randn(*shape).astype("float32"),
                                jnp.bfloat16)
                    for shape in ((layers, 1, h, t, d), (1, hkv, t, d),
                                  (1, hkv, t, d)))
        ruled = rule(qs[0], k, v, True)
        if not REHEARSE:
            check(ruled == "kernel", "the rule names the kernel (%s)" % ruled)
        fast, slow, diff = both_forms(
            "kernel", "dense", lambda qs, k, v: jax.lax.map(
                lambda q: op(attrs, q, k, v), qs), (qs, k, v), 20, layers)
        say("    a layer: kernel %.3f ms, dense %.3f ms (x%.2f); worst "
            "difference over the largest output %.2e"
            % (fast, slow, slow / fast, diff))
        verdict(fast, slow, diff, "kernel and dense path agree")

    h, t, dk, dv, hi, di, topk = SZ["sparse_attn"]
    say("  -- dots3-note-prev, a full layer: %d heads of %d over %d, %d "
        "positions, the %d highest of a %d x %d indexer, bfloat16"
        % (h, dk, dv, t, topk, hi, di))
    operands = tuple(
        jnp.asarray(rs.randn(*shape).astype("float32"), jnp.bfloat16)
        for shape in ((1, h, t, dk), (1, h, t, dk), (1, h, t, dv),
                      (1, hi, t, di), (1, 1, t, di), (1, t, hi)))
    ruled = rule(*operands[:3], True, 0, False, None, topk)
    if not REHEARSE:
        check(ruled == "sparse_kernel",
              "the rule names the kernel under a selection (%s)" % ruled)
    fast, slow, diff = both_forms(
        "sparse_kernel", "sparse",
        lambda *a: op(dict(attrs, topk=topk), *a), operands, 5, 1)
    say("    a layer: the kernel under its mask %.2f ms, XLA's query blocks "
        "%.2f ms (x%.2f); worst difference over the largest output %.2e"
        % (fast, slow, slow / fast, diff))
    verdict(fast, slow, diff, "both forms attend the same keys")


# ---------------------------------------------------- phase 7: a step's write
def phase_pool_write():
    """A decode step's write of its new K/V rows as the chip runs it:
    ``KVPoolSlotWrite`` over a layer's two page-major pools, one XLA scatter a
    pool, against what the operator promises, bit for bit, at
    ``ouro-2.6b.generate``'s operands and ``transformer-base.generate``'s: a
    step's lanes (a page each, some riding along, two on one slot), a chunk's
    rows in consecutive slots, one row. Every written slot holds its row (the
    later of two), every other slot what it held. And what a call takes in a
    chain."""
    from mxnet_tpu.ops.attention import _kv_pool_slot_write, pool_write_form

    def bits(a):
        return jax.lax.bitcast_convert_type(
            a, jnp.uint16 if a.dtype.itemsize == 2 else jnp.uint32)

    @jax.jit
    def kept(pool, new, out, at, row):
        """``out`` is ``pool`` but for slots ``at``, which hold ``new[row]``."""
        flat, was = (bits(a).reshape(-1, a.shape[-1]) for a in (out, pool))
        rows = bits(new).reshape(new.shape[0], -1)
        written = jnp.zeros(flat.shape[0], bool).at[at].set(True)
        return (jnp.all(flat[at] == rows[row])
                & jnp.all((flat == was) | written[:, None]))

    write = jax.jit(lambda pk, rk, pv, rv, s: _kv_pool_slot_write(
        {"num_pools": 2}, pk, rk, pv, rv, s))
    for name, (shape, dt, lanes) in SZ["pool_write"].items():
        frames, page, width = shape
        rs = np.random.RandomState(len(name))
        step = rs.permutation(frames)[:lanes] * page + rs.randint(0, page, lanes)
        step[1::5] = -1
        step[-1] = step[0]
        chunk = (frames // 2) * page + 3 + np.arange(2 * page)
        k1, k2 = jax.random.split(jax.random.PRNGKey(len(name)))
        pools = [jax.random.normal(k, shape, jnp.float32).astype(dt)
                 for k in (k1, k2)]
        form = pool_write_form(pools)
        check(form == "scatter",
              "the rule names the write of %s pools %s: %s" % (dt, shape, form))
        for what, slots in (("a step's lanes", step), ("a chunk's rows", chunk),
                            ("one row", step[:1])):
            rows = [jax.random.normal(k, (len(slots), 4, width // 4),
                                      jnp.float32).astype(dt)
                    for k in jax.random.split(jax.random.PRNGKey(len(slots)))]
            got = write(pools[0], rows[0], pools[1], rows[1],
                        jnp.asarray(slots, jnp.float32).reshape(-1, 1))
            # slot -> the LAST row that names it
            live = {int(s): r for r, s in enumerate(slots) if s >= 0}
            at, row = (jnp.asarray(list(v)) for v in (live, live.values()))
            check(all(bool(kept(pool, new, out, at, row))
                      for pool, new, out in zip(pools, rows, got)),
                  "KVPoolSlotWrite %s %s x 2, %s (%d rows, %d written): the "
                  "rows at their slots and every other slot as it was, bit "
                  "for bit" % (dt, shape, what, len(slots), len(live)))
        # a call in a chain of 48 on the same two pools, donated
        rows = [jax.random.normal(k1, (lanes, 4, width // 4),
                                  jnp.float32).astype(dt)] * 2
        slot = jnp.asarray(step, jnp.float32).reshape(-1, 1)

        def chain(pk, pv):
            for i in range(48):
                pk, pv = _kv_pool_slot_write(
                    {"num_pools": 2}, pk, rows[0], pv, rows[1],
                    jnp.where(slot >= 0, (slot + page * i) % (frames * page),
                              slot))
            return pk, pv

        run = jax.jit(chain, donate_argnums=(0, 1))
        pools = jax.block_until_ready(run(*pools))
        t0 = time.perf_counter()
        pools = jax.block_until_ready(run(*pools))
        say("    %s: %.1f us a call of %d rows x 2 pools (%s)"
            % (name, 1e6 * (time.perf_counter() - t0) / 48, lanes, form))


# --------------------------------------------------------------------- main
PHASES = {
    0: ("device", phase_device),
    1: ("train: SPMDTrainer, ResNet-50", phase_train),
    2: ("fit: Module.fit, ResNet-50", phase_fit),
    3: ("serve: PagedKVDecoder, Transformer-base", phase_serve),
    5: ("four chips", phase_four_chips),
    6: ("prefill attention: the kernel against the dense path",
        phase_prefill_attention),
    7: ("pool write: a step's rows into page-major pools", phase_pool_write),
}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--phases", default=None,
                    help="comma list of phase numbers (default: all)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="debug run without a chip: tiny sizes, no result")
    args = ap.parse_args()
    want = (sorted({int(p) for p in args.phases.split(",")})
            if args.phases else sorted(PHASES))
    unknown = [p for p in want if p not in PHASES]
    if unknown:
        ap.error("no phase %s (phases: %s)"
                 % (unknown, ", ".join(map(str, sorted(PHASES)))))
    import logging

    # Speedometer and the fused-step notices log at INFO
    logging.basicConfig(level=logging.INFO, stream=sys.stdout,
                        format="    log: %(message)s")
    if REHEARSE:
        say("*** REHEARSAL on the CPU at tiny sizes: this debugs the script "
            "and is NOT a chip result ***")
    failed, t_start = [], time.perf_counter()
    for i in want:
        name, fn = PHASES[i]
        say("PHASE %d %s" % (i, name))
        if i == 5 and len(jax.devices()) < 4:
            say("PHASE 5 SKIP %d device(s) visible, the phase needs 4"
                % len(jax.devices()))
            continue
        n0, s0, h0, m0 = COMPILES.snap()
        t0 = time.perf_counter()
        try:
            fn()
            verdict = "PASS"
        except Exception:  # noqa: BLE001 — reported, counted, exit code 1
            traceback.print_exc(file=sys.stdout)
            verdict = "FAIL"
            failed.append(i)
        n1, s1, h1, m1 = COMPILES.snap()
        say("PHASE %d %s  %.1f s wall; set-up: %d compile requests, %.1f s "
            "(persistent cache: %d hits, %d misses)"
            % (i, verdict, time.perf_counter() - t0, n1 - n0, s1 - s0,
               h1 - h0, m1 - m0))
        gc.collect()
    n, s, h, m = COMPILES.snap()
    say("TOTAL %.1f s wall; set-up: %d compile requests, %.1f s (persistent "
        "cache: %d hits, %d misses; programs under JAX's 1 s threshold are "
        "never persisted); peak device memory %.2f GiB"
        % (time.perf_counter() - t_start, n, s, h, m, peak_gb()))
    if failed:
        say("FAILED phases: %s" % failed)
    if REHEARSE:
        say("*** REHEARSAL %s — no result line ***"
            % ("FAILED" if failed else "passed"))
        return 1 if failed else 0
    result = {"ok": not failed,
              "device": {"platform": DEV.platform, "kind": DEV.device_kind,
                         "count": len(jax.devices())}}
    if args.phases:
        result["phases"] = want
    say(json.dumps(result))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
