"""Driver ``train_steps``: training steps on one resident batch until the
window ends, through ``parallel.SPMDTrainer`` on the traffic mix's mesh.

Configuration keys it reads: ``symbol`` {name, args}, ``input`` {shape, dtype,
classes}, ``batch_per_chip``, ``compute_dtype``, ``optimizer`` {name, params,
rescale_grad_by_batch}, ``init`` (weights.py), ``reference``,
``reference_args``, ``check`` {watch, probs_rel_l2, update_rel_l2}.
Traffic keys: ``mesh`` {axis: size}, ``sync_every``.

The program's defaults are what is measured: no MXNET_* variable is set and
no path selector is passed beyond the configuration's own compute dtype.
"""
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

import flops
from harness import weights
from mxnet_tpu import models, parallel


def rel_l2(got, want):
    """Relative L2 distance, inside a jitted check."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return jnp.linalg.norm(got - want) / (jnp.linalg.norm(want) + 1e-30)


def build(run):
    """The trainer with seeded weights on the mesh, the resident batch, and
    the shapes that built them."""
    cfg, mesh_shape = run.config, run.traffic["mesh"]
    n_chips = math.prod(mesh_shape.values())
    if n_chips != run.chips:
        raise ValueError("traffic mesh %r needs %d chips, the cell has %d"
                         % (mesh_shape, n_chips, run.chips))
    batch = cfg["batch_per_chip"] * n_chips
    net = models.get_symbol(cfg["symbol"]["name"], **cfg["symbol"]["args"])
    mesh = parallel.make_mesh(dict(mesh_shape), devices=run.devices)
    opt = dict(cfg["optimizer"]["params"])
    if cfg["optimizer"].get("rescale_grad_by_batch"):
        # what Module.init_optimizer sets for train_imagenet.py: the
        # SoftmaxOutput gradient is summed over the batch, this makes it a mean
        opt["rescale_grad"] = 1.0 / batch
    trainer = parallel.SPMDTrainer(
        net, mesh, optimizer=cfg["optimizer"]["name"], optimizer_params=opt,
        compute_dtype=cfg.get("compute_dtype"), data_names=("data",),
        label_names=("softmax_label",))
    in_shapes = {"data": (batch,) + tuple(cfg["input"]["shape"]),
                 "softmax_label": (batch,)}
    arg_shapes, _, aux_shapes = net.infer_shape(**in_shapes)
    shapes = dict(zip(net.list_arguments(), arg_shapes))
    shapes.update(zip(net.list_auxiliary_states(), aux_shapes))
    for name in in_shapes:
        shapes.pop(name)
    rules = trainer.rules
    replicated = rules.named(jax.sharding.PartitionSpec())
    shard = {n: rules.named(rules.param_spec(n, s))
             if n in trainer.param_names else replicated
             for n, s in shapes.items()}
    arrays = weights.make(shapes, cfg["init"], run.seed, shardings=shard)
    trainer.params = {n: arrays[n] for n in trainer.param_names}
    trainer.aux = {n: arrays[n] for n in trainer.aux_names}
    trainer.set_params({}, {})  # optimizer state for the weights just placed

    classes = cfg["input"]["classes"]

    def make_batch(key):
        kx, ky = jax.random.split(key)
        x = jax.random.uniform(kx, in_shapes["data"], jnp.float32)
        y = jax.random.randint(ky, (batch,), 0, classes)
        return x.astype(cfg["input"]["dtype"]), y.astype(jnp.float32)

    place = lambda s: rules.named(rules.batch_spec(s))
    x, y = jax.jit(make_batch, out_shardings=(
        place(in_shapes["data"]), place((batch,))))(
            jax.random.fold_in(jax.random.PRNGKey(run.seed), 1 << 20))
    return net, trainer, x, y, in_shapes, batch


def check_first_step(run, x, y, before, probs, deltas):
    """The program's first step against the plain reference on the same
    weights and batch: output probabilities, and the first SGD update of the
    watched parameters (``deltas``) against -lr x the reference's gradient.
    Each comparison is one jitted program that returns relative L2 errors."""
    cfg = run.config
    ref, ref_args, chk = run.reference(), cfg["reference_args"], cfg["check"]
    lr = cfg["optimizer"]["params"]["learning_rate"]
    if run.break_reference:
        # a reference that is deliberately wrong: the run must say so
        before = {k: v * 1.25 if k.endswith("_weight") and v.ndim == 2
                  else v + 0.5 if k.endswith("_beta") else v
                  for k, v in before.items()}
    watch = sorted(deltas)
    rest = {k: v for k, v in before.items() if k not in watch}

    @jax.jit
    def forward_error(p, x, probs):
        return rel_l2(probs, ref.probabilities(p, x, ref_args))

    @jax.jit
    def update_errors(watched, rest, x, y, deltas):
        grads = jax.grad(lambda w: ref.loss({**rest, **w}, x, y, ref_args,
                                            remat=True))(watched)
        return {n: rel_l2(deltas[n] / -lr, grads[n]) for n in watched}

    errors = {"first-step probabilities vs the reference's":
              (float(forward_error(before, x, probs)), chk["probs_rel_l2"])}
    upd = update_errors({n: before[n] for n in watch}, rest, x, y, deltas)
    for n in watch:
        errors["first update of %s vs -lr x the reference's gradient" % n] = \
            (float(upd[n]), chk["update_rel_l2"])
    checks, ok = [], True
    for what, (err, limit) in errors.items():
        good = math.isfinite(err) and err <= limit
        ok &= good
        checks.append("%s: relative L2 %.3e (limit %.1e) %s"
                      % (what, err, limit, "ok" if good else "FAIL"))
    return ok, checks


def run(run):
    cfg, every = run.config, int(run.traffic["sync_every"])
    net, trainer, x, y, in_shapes, batch = build(run)
    data, label = {"data": x}, {"softmax_label": y}
    jax.block_until_ready((x, y))
    run.mark("trainer, weights and batch on the device")

    @jax.jit
    def cross_entropy(probs, y):
        p = jnp.take_along_axis(probs.astype(jnp.float32),
                                y.astype(jnp.int32)[:, None], axis=1)
        return -jnp.mean(jnp.log(jnp.maximum(p, 1e-30)))

    # the step donates its state, so what the reference needs is copied
    # first (one program: outputs of a jit never alias undonated inputs)
    watch = list(cfg["check"]["watch"])
    before = jax.jit(lambda p: jax.tree_util.tree_map(jnp.copy, p))(
        trainer.params)
    outs = trainer.step(data, label)
    probs = outs[0]
    deltas = jax.jit(lambda new, old: {n: new[n] - old[n] for n in new})(
        {n: trainer.params[n] for n in watch}, {n: before[n] for n in watch})
    for _ in range(2):
        outs = trainer.step(data, label)
    first_loss = float(cross_entropy(outs[0], y))
    jax.block_until_ready(outs)
    run.mark("compile or load the step, three steps")
    # the program's own peak: taken before the reference runs
    peak = run.memory_peak()
    # XLA's count for the compiled step (the lowering and the executable are
    # both cached by now: this costs no compile)
    step_bytes = float(trainer.cost_analysis(data, label)["bytes accessed"])
    run.mark("XLA's cost analysis of the step")
    ok, checks = check_first_step(run, x, y, before, probs, deltas)
    del before, deltas, probs
    run.mark("reference check")

    t0 = run.open_window()
    deadline = t0 + run.seconds
    steps, group_s, losses, t_prev = 0, [], [], t0
    while True:
        for _ in range(every):
            with run.annotate("bench.train_step"):
                outs = trainer.step(data, label)
        steps += every
        with run.annotate("bench.sync"):
            jax.block_until_ready(outs)
        now = time.perf_counter()
        group_s.append(now - t_prev)
        t_prev = now
        losses.append(cross_entropy(outs[0], y))
        if now >= deadline:
            break
    run.close_window()
    losses = [float(v) for v in losses]

    bad_groups = sum(1 for v in losses if not math.isfinite(v))
    failed = min(steps, bad_groups * every + int(trainer.skipped_steps))
    tail = losses[-max(1, len(losses) // 5):]
    fell = bad_groups == 0 and sum(tail) / len(tail) < first_loss
    checks.append("loss on the repeated batch: %.4f after 3 steps, %.4f at "
                  "the window's first sync, %.4f at its end; finite and "
                  "below where it started: %s"
                  % (first_loss, losses[0], tail[-1],
                     "ok" if fell else "FAIL"))
    quiet = run.compiles_window["requests"] == 0
    checks.append("compile requests inside the window: %d %s"
                  % (run.compiles_window["requests"],
                     "ok" if quiet else "FAIL"))

    macs = flops.graph_macs(net, **in_shapes)[0]
    obs = {
        "correct": bool(ok and fell and quiet and failed == 0),
        "checks": checks, "attempted": steps, "failed": failed,
        "steps": steps, "samples": steps * batch, "global_batch": batch,
        "sample_unit": cfg["sample_unit"],
        "elapsed_s": t_prev - t0, "group_s": group_s, "sync_every": every,
        "train_flops_per_sample": 6.0 * macs / batch,
        "model_flops_in_window": 6.0 * macs * steps,
        "xla_bytes_per_step_per_chip": step_bytes,
        "memory_peak_bytes": peak,
    }
    run.notes.update(step_ms_p50=1e3 * float(np.median(group_s)) / every,
                     first_loss=first_loss, last_loss=tail[-1],
                     graph_macs_per_sample=macs // batch)
    return obs
