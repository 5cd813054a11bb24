"""Plain reference: pre-activation bottleneck ResNet, training mode.

Written from He et al., "Identity Mappings in Deep Residual Networks" (2016),
Fig. 4(e), and the depth table of "Deep Residual Learning" (2015), Table 1, in
straightforward ``jax.numpy``: float32, ``default_matmul_precision("highest")``,
no kernels, no fusion, nothing from ``mxnet_tpu``. It shares with the program
only the checkpoint's parameter names, which are upstream MXNet's
(``example/image-classification/symbols/resnet.py``: conv0, bn0,
stage<s>_unit<u>_{bn1,conv1,bn2,conv2,bn3,conv3,sc}, bn1, fc1), and NCHW/OIHW.

Departures from upstream, because the program under test makes them: no
``bn_data`` normalisation of the input; BatchNorm epsilon 2e-5 as upstream.
Batch statistics are taken over the whole (global) batch, variance biased.
The loss is the mean cross-entropy over the batch, whose gradient is what
SoftmaxOutput with ``rescale_grad = 1/batch`` hands the optimizer.
"""
import jax
import jax.numpy as jnp
from jax import lax

# depth -> (units per stage, bottleneck?), the 2015 paper's Table 1
DEPTHS = {18: ((2, 2, 2, 2), False), 34: ((3, 4, 6, 3), False),
          50: ((3, 4, 6, 3), True), 101: ((3, 4, 23, 3), True),
          152: ((3, 8, 36, 3), True)}
BN_EPS = 2e-5


def conv(x, w, stride=1, pad=0):
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def batch_norm(x, gamma, beta):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    shape = (1, -1, 1, 1)
    return (x - mean) * lax.rsqrt(var + BN_EPS) * gamma.reshape(shape) \
        + beta.reshape(shape)


def bn_relu(p, name, x):
    return jax.nn.relu(batch_norm(x, p[name + "_gamma"], p[name + "_beta"]))


def unit(p, name, x, stride, dim_match, bottleneck):
    """Bottleneck: BN-ReLU-1x1, BN-ReLU-3x3 (strided), BN-ReLU-1x1. Basic
    (depths 18, 34): BN-ReLU-3x3 (strided), BN-ReLU-3x3. Plus the shortcut:
    identity, or a strided 1x1 projection of the first activation."""
    act1 = bn_relu(p, name + "_bn1", x)
    if bottleneck:
        y = conv(act1, p[name + "_conv1_weight"])
        y = conv(bn_relu(p, name + "_bn2", y), p[name + "_conv2_weight"],
                 stride=stride, pad=1)
        y = conv(bn_relu(p, name + "_bn3", y), p[name + "_conv3_weight"])
    else:
        y = conv(act1, p[name + "_conv1_weight"], stride=stride, pad=1)
        y = conv(bn_relu(p, name + "_bn2", y), p[name + "_conv2_weight"],
                 pad=1)
    shortcut = x if dim_match else conv(act1, p[name + "_sc_weight"],
                                        stride=stride)
    return y + shortcut


def logits(p, x, cfg, remat=False):
    """(batch, classes) logits of images ``x`` (batch, 3, H, W), float32.
    ``remat`` recomputes each unit in the backward pass (the float32
    backward of a full batch then fits beside the program under test)."""
    x = x.astype(p["conv0_weight"].dtype)   # float32 weights: float32 math
    units, bottleneck = DEPTHS[cfg["num_layers"]]
    block = jax.checkpoint(unit, static_argnums=(1, 3, 4, 5)) if remat \
        else unit
    y = conv(x, p["conv0_weight"], stride=2, pad=3)
    y = bn_relu(p, "bn0", y)
    y = lax.reduce_window(y, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    for s, n_units in enumerate(units):
        for u in range(n_units):
            first = u == 0
            y = block(p, "stage%d_unit%d" % (s + 1, u + 1), y,
                      2 if (first and s > 0) else 1, not first, bottleneck)
    y = bn_relu(p, "bn1", y)
    y = jnp.mean(y, axis=(2, 3))
    return y @ p["fc1_weight"].T + p["fc1_bias"]


def probabilities(p, x, cfg):
    with jax.default_matmul_precision("highest"):
        return jax.nn.softmax(logits(p, x, cfg), axis=-1)


def loss(p, x, labels, cfg, remat=False):
    """Mean cross-entropy over the batch."""
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(logits(p, x, cfg, remat), axis=-1)
    picked = jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[:, None], axis=1)
    return -jnp.mean(picked)
