"""A pre-activation residual unit, trained and evaluated through the operator
registry's Convolution, BatchNorm, Activation and elemwise_add, against a
plain float32 ``jax.numpy`` reference written here.

The unit is the chain models/resnet.py builds: BN -> relu -> conv -> BN ->
relu -> conv (+ shortcut) -> BN. Its first convolution takes each contract
the ResNets use (1x1 and 3x3 at strides 1 and 2, the 7x7 stem); the shortcut
is none, the identity over the second half, or a strided 1x1 projection of
the first activation. Every case is a tiny shape bound with
``Symbol.simple_bind``; the trainer cases go through ``SPMDTrainer``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import parallel

MOMENTUM = 0.9
CONTRACTS = {  # kernel, stride, pad of the unit's first convolution
    "k1s1": ((1, 1), (1, 1), (0, 0)),
    "k1s2": ((1, 1), (2, 2), (0, 0)),
    "k3s1": ((3, 3), (1, 1), (1, 1)),
    "k3s2": ((3, 3), (2, 2), (1, 1)),
    "k7s2": ((7, 7), (2, 2), (3, 3)),
}
SHORTCUTS = ("none", "identity", "projection")
B, C, F, HW = 2, 8, 16, 8


# ----------------------------------------------------------------- reference
def _axes(x):
    return (0,) + tuple(range(2, x.ndim)), (1, -1) + (1,) * (x.ndim - 2)


def ref_bn(x, gamma, beta, fix_gamma, eps):
    """Training BatchNorm over axis 1: two-pass biased batch moments."""
    axes, b = _axes(x)
    mean = jnp.mean(x, axis=axes)
    var = jnp.mean(jnp.square(x - mean.reshape(b)), axis=axes)
    xhat = (x - mean.reshape(b)) / jnp.sqrt(var.reshape(b) + eps)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    return xhat * g.reshape(b) + beta.reshape(b), mean, var


def ref_bn_eval(x, gamma, beta, mean, var, fix_gamma, eps):
    _, b = _axes(x)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    return (x - mean.reshape(b)) / jnp.sqrt(var.reshape(b) + eps) \
        * g.reshape(b) + beta.reshape(b)


def ref_conv(x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=stride, padding=[(p, p) for p in pad],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=jax.lax.Precision.HIGHEST)


def ref_unit(p, x, contract, shortcut, fix_gamma, eps, moving=None):
    """The unit's output and each BatchNorm's batch (mean, var); with
    ``moving`` ({name: array}) it normalises by those, as at inference."""
    _, stride, pad = CONTRACTS[contract]
    stats = {}

    def bn(name, v):
        if moving is not None:
            return ref_bn_eval(v, p[name + "_gamma"], p[name + "_beta"],
                               moving[name + "_moving_mean"],
                               moving[name + "_moving_var"], fix_gamma, eps)
        out, mean, var = ref_bn(v, p[name + "_gamma"], p[name + "_beta"],
                                fix_gamma, eps)
        stats[name] = (mean, var)
        return out

    a1 = jnp.maximum(bn("bn1", x), 0)
    c1 = ref_conv(a1, p["conv1_weight"], stride, pad)
    a2 = jnp.maximum(bn("bn2", c1), 0)
    s = ref_conv(a2, p["conv2_weight"], (1, 1), (1, 1))
    if shortcut == "identity":
        s = s + c1
    elif shortcut == "projection":
        s = s + ref_conv(a1, p["sc_weight"], stride, (0, 0))
    return bn("bn3", s), stats


# -------------------------------------------------------------------- symbol
def unit_symbol(contract, shortcut, fix_gamma, eps=2e-5):
    kernel, stride, pad = CONTRACTS[contract]
    sym = mx.sym
    bn = lambda d, name: sym.BatchNorm(data=d, fix_gamma=fix_gamma, eps=eps,
                                       momentum=MOMENTUM, name=name)
    relu = lambda d: sym.Activation(data=d, act_type="relu")
    a1 = relu(bn(sym.Variable("data"), "bn1"))
    c1 = sym.Convolution(data=a1, num_filter=F, kernel=kernel, stride=stride,
                         pad=pad, no_bias=True, name="conv1")
    a2 = relu(bn(c1, "bn2"))
    s = sym.Convolution(data=a2, num_filter=F, kernel=(3, 3), stride=(1, 1),
                        pad=(1, 1), no_bias=True, name="conv2")
    if shortcut == "identity":
        s = s + c1
    elif shortcut == "projection":
        s = s + sym.Convolution(data=a1, num_filter=F, kernel=(1, 1),
                                stride=stride, pad=(0, 0), no_bias=True,
                                name="sc")
    return bn(s, "bn3")


def classifier(unit):
    """The unit under a loss, as a trainer needs it."""
    pool = mx.sym.Pooling(data=unit, kernel=(1, 1), global_pool=True,
                          pool_type="avg", name="pool")
    fc = mx.sym.FullyConnected(data=mx.sym.Flatten(pool), num_hidden=4,
                               name="fc")
    return mx.sym.SoftmaxOutput(data=fc, name="softmax")


def draw(shapes, seed):
    """Values for every argument and aux state, by the name's suffix."""
    rs = np.random.RandomState(seed)
    out = {}
    for name, shape in shapes.items():
        if name.endswith("_gamma") or name.endswith("_moving_var"):
            v = rs.uniform(0.5, 1.5, shape)
        elif name.endswith("_beta") or name.endswith("_moving_mean"):
            v = rs.uniform(-0.3, 0.3, shape)
        elif name.endswith("_weight"):
            v = rs.randn(*shape) * np.sqrt(2.0 / np.prod(shape[1:]))
        elif name.endswith("_label"):
            v = rs.randint(0, 4, shape)
        else:
            v = rs.uniform(-1, 1, shape)
        out[name] = v.astype("float32")
    return out


def draw_for(net, shapes, seed):
    """``draw`` for every argument and aux state of ``net`` at ``shapes``."""
    arg_shapes, _, aux_shapes = net.infer_shape(**shapes)
    named = dict(zip(net.list_arguments(), arg_shapes))
    named.update(zip(net.list_auxiliary_states(), aux_shapes))
    return draw(named, seed)


def bind(net, grad_req="write", seed=0, **inputs):
    exe = net.simple_bind(mx.cpu(), grad_req=grad_req, **inputs)
    vals = draw({n: a.shape for n, a in list(exe.arg_dict.items())
                 + list(exe.aux_dict.items())}, seed)
    for store in (exe.arg_dict, exe.aux_dict):
        for n, a in store.items():
            a[:] = vals[n]
    return exe, {n: jnp.asarray(v) for n, v in vals.items()}


def close(got, want, tol, what):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol,
                               atol=tol, err_msg=what)


# --------------------------------------------------------------------- tests
@pytest.mark.parametrize("eps", [2e-5, 1e-3], ids=["eps2e-5", "eps1e-3"])
@pytest.mark.parametrize("fix_gamma", [True, False],
                         ids=["fix_gamma", "gamma"])
@pytest.mark.parametrize("shortcut", SHORTCUTS)
@pytest.mark.parametrize("contract", list(CONTRACTS))
def test_preact_unit_matches_reference(contract, shortcut, fix_gamma, eps):
    """Outputs, the gradient of the input and of every parameter, and both
    moving statistics of every BatchNorm after one training step, at the
    ResNets' ``eps`` and at the operator's default."""
    exe, v = bind(unit_symbol(contract, shortcut, fix_gamma, eps),
                  data=(B, C, HW, HW))
    out = exe.forward(is_train=True)[0].asnumpy()
    cot = np.random.RandomState(1).randn(*out.shape).astype("float32")
    exe.backward(out_grads=[mx.nd.array(cot)])

    args = {n: v[n] for n in exe.arg_dict}
    want, stats = ref_unit(args, v["data"], contract, shortcut, fix_gamma,
                           eps)
    close(out, want, 2e-4, "output")
    grads = jax.grad(lambda p: jnp.sum(ref_unit(
        p, p["data"], contract, shortcut, fix_gamma, eps)[0] * cot))(args)
    for name, g in grads.items():
        close(exe.grad_dict[name].asnumpy(), g, 1e-3, "gradient of " + name)
    for bn, (mean, var) in stats.items():
        for stat, batch in (("_moving_mean", mean), ("_moving_var", var)):
            close(exe.aux_dict[bn + stat].asnumpy(),
                  v[bn + stat] * MOMENTUM + batch * (1 - MOMENTUM), 1e-5,
                  bn + stat)


@pytest.mark.parametrize("contract", list(CONTRACTS))
def test_preact_unit_eval_uses_moving_stats(contract, tmp_path):
    """``is_train=False`` normalises by the moving statistics and leaves
    them alone, and ``Predictor`` returns the same bits."""
    from mxnet_tpu.predictor import Predictor

    net = unit_symbol(contract, "projection", False)
    exe, v = bind(net, grad_req="null", data=(B, C, HW, HW))
    out = exe.forward(is_train=False)[0].asnumpy()
    want, _ = ref_unit(v, v["data"], contract, "projection", False, 2e-5,
                       moving=v)
    close(out, want, 2e-4, "output")
    for name, a in exe.aux_dict.items():
        assert np.array_equal(a.asnumpy(), np.asarray(v[name])), name

    path = str(tmp_path / "unit.params")
    mx.nd.save(path, {**{"arg:" + n: a for n, a in exe.arg_dict.items()
                         if n != "data"},
                      **{"aux:" + n: a for n, a in exe.aux_dict.items()}})
    pred = Predictor(net.tojson(), open(path, "rb").read(),
                     {"data": (B, C, HW, HW)})
    pred.forward(data=np.asarray(v["data"]))
    assert np.array_equal(pred.get_output(0), out)


def _ref_train(p, x, y, contract, lr, steps):
    """``steps`` of plain SGD on the summed cross-entropy / batch, float32;
    returns the weights and each step's loss."""
    def loss(p):
        feat, _ = ref_unit(p, x, contract, "projection", False, 2e-5)
        logits = feat.mean(axis=(2, 3)) @ p["fc_weight"].T + p["fc_bias"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(logp[jnp.arange(x.shape[0]), y.astype(jnp.int32)])

    losses, step = [], jax.jit(jax.value_and_grad(loss))
    for _ in range(steps):
        value, grads = step(p)
        losses.append(float(value))
        p = {k: p[k] - lr * grads[k] for k in p}
    return p, losses


def _trainer(net, mesh, shapes, vals, compute_dtype=None, lr=0.1):
    batch = shapes["data"][0]
    tr = parallel.SPMDTrainer(
        net, mesh, optimizer="sgd",
        optimizer_params={"learning_rate": lr, "momentum": 0.0,
                          "rescale_grad": 1.0 / batch},
        compute_dtype=compute_dtype)
    tr.init_params({"data": shapes["data"]},
                   {"softmax_label": shapes["softmax_label"]}, seed=0)
    # copies: the step donates its state, and on the CPU a device array may
    # share the numpy array's memory
    tr.set_params({n: np.array(vals[n]) for n in tr.param_names},
                  {n: np.array(vals[n]) for n in tr.aux_names})
    return tr


def _loss(probs, y):
    p = np.asarray(probs, np.float32)[np.arange(len(y)), y.astype(int)]
    return float(-np.mean(np.log(p)))


@pytest.mark.parametrize("contract", list(CONTRACTS))
def test_unit_bf16_compute_close_to_f32(contract):
    """Two steps through ``SPMDTrainer(compute_dtype="bfloat16")`` on one
    device: each step's loss and what the two updates moved each weight by
    stay within bfloat16's rounding of the float32 reference's."""
    net = classifier(unit_symbol(contract, "projection", False))
    shapes = {"data": (4, C, HW, HW), "softmax_label": (4,)}
    vals = draw_for(net, shapes, seed=3)
    x, y = vals["data"], vals["softmax_label"]
    mesh = parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    tr = _trainer(net, mesh, shapes, vals, compute_dtype="bfloat16")
    losses = [_loss(tr.step({"data": x}, {"softmax_label": y})[0], y)
              for _ in range(2)]
    got, _ = tr.get_params()

    params = {n: jnp.asarray(vals[n]) for n in tr.param_names}
    want, want_losses = _ref_train(params, jnp.asarray(x), y, contract, 0.1, 2)
    np.testing.assert_allclose(losses, want_losses, rtol=1e-2)
    for name in want:
        moved = np.asarray(want[name]) - vals[name]
        err = np.linalg.norm(got[name] - vals[name] - moved) \
            / np.linalg.norm(moved)
        assert err < 8e-2, "two updates of %s: relative L2 %.3f" % (name, err)


@pytest.mark.parametrize("devices", ["data2", "data4", "data2_model2"])
def test_dp_mesh_step_equals_single_device(devices):
    """A step on a mesh is the step on one device: every BatchNorm sees the
    GLOBAL batch's moments whether the batch is split over ``data`` alone or
    the weights over ``model`` as well."""
    axes = {"data2": {"data": 2}, "data4": {"data": 4},
            "data2_model2": {"data": 2, "model": 2}}[devices]
    n = int(np.prod(list(axes.values())))
    if len(jax.devices()) < n:
        pytest.skip("needs %d devices" % n)
    net = classifier(unit_symbol("k3s1", "projection", False))
    shapes = {"data": (8, C, HW, HW), "softmax_label": (8,)}
    vals = draw_for(net, shapes, seed=5)
    x, y = vals["data"], vals["softmax_label"]

    def run(mesh):
        tr = _trainer(net, mesh, shapes, vals)
        probs = [np.asarray(tr.step({"data": x}, {"softmax_label": y})[0])
                 for _ in range(2)]
        return probs, tr.get_params()

    one = run(parallel.make_mesh({"data": 1}, devices=jax.devices()[:1]))
    many = run(parallel.make_mesh(axes, devices=jax.devices()[:n]))
    for a, b in zip(one[0], many[0]):
        close(b, a, 1e-5, "probabilities")
    for held_one, held_many in zip(one[1], many[1]):
        for name in held_one:
            close(held_many[name], held_one[name], 1e-5, name)
    # and those moments are the reference's over all 8 images: bn1 sees the
    # batch itself, so after two steps its moving mean holds it twice
    _, stats = ref_unit({k: jnp.asarray(a) for k, a in vals.items()},
                        jnp.asarray(x), "k3s1", "projection", False, 2e-5)
    keep = MOMENTUM ** 2
    close(many[1][1]["bn1_moving_mean"],
          vals["bn1_moving_mean"] * keep + stats["bn1"][0] * (1 - keep),
          1e-5, "bn1_moving_mean")


HEAD_CASES = ["mean_var_heads", "global_stats_in_training", "ndim2", "ndim3",
              "ndim4", "ndim5", "momentum0", "momentum1"]


@pytest.mark.parametrize("case", HEAD_CASES)
def test_batchnorm_heads_and_modes(case):
    """One BatchNorm node: the ``output_mean_var`` heads with cotangents of
    their own, ``use_global_stats`` while training, inputs of 2 to 5
    dimensions, and the two ends of ``momentum``."""
    shape = {"ndim2": (6, 5), "ndim3": (4, 5, 7), "ndim5": (2, 5, 3, 4, 2)
             }.get(case, (3, 5, 4, 4))
    heads = case == "mean_var_heads"
    frozen = case == "global_stats_in_training"
    momentum = {"momentum0": 0.0, "momentum1": 1.0}.get(case, MOMENTUM)
    eps = 1e-3
    net = mx.sym.BatchNorm(data=mx.sym.Variable("data"), fix_gamma=False,
                           eps=eps, momentum=momentum, output_mean_var=heads,
                           use_global_stats=frozen, name="bn")
    exe, v = bind(net, seed=11, data=shape)
    outs = [o.asnumpy() for o in exe.forward(is_train=True)]
    rs = np.random.RandomState(2)
    cots = [rs.randn(*o.shape).astype("float32") for o in outs]
    exe.backward(out_grads=[mx.nd.array(c) for c in cots])

    def ref(p):
        if frozen:
            return (ref_bn_eval(p["data"], p["bn_gamma"], p["bn_beta"],
                                v["bn_moving_mean"], v["bn_moving_var"],
                                False, eps),)
        out, mean, var = ref_bn(p["data"], p["bn_gamma"], p["bn_beta"],
                                False, eps)
        return (out, mean, var) if heads else (out,)

    args = {n: v[n] for n in exe.arg_dict}
    for got, want in zip(outs, ref(args)):
        close(got, want, 1e-4, "output")
    grads = jax.jit(jax.grad(lambda p: sum(
        jnp.sum(o * c) for o, c in zip(ref(p), cots))))(args)
    for name, g in grads.items():
        close(exe.grad_dict[name].asnumpy(), g, 1e-3, "gradient of " + name)
    _, mean, var = ref_bn(v["data"], v["bn_gamma"], v["bn_beta"], False, eps)
    for stat, batch in (("bn_moving_mean", mean), ("bn_moving_var", var)):
        want = v[stat] if frozen else \
            v[stat] * momentum + batch * (1 - momentum)
        close(exe.aux_dict[stat].asnumpy(), want, 1e-5, stat)
