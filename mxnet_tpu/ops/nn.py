"""Neural-network layer ops.

Covers the reference's legacy OperatorProperty layers (src/operator/
{fully_connected,convolution,deconvolution,batch_norm,pooling,activation,
dropout,softmax_output,leaky_relu,lrn,instance_norm,l2_normalization,
upsampling,make_loss,regression_output,svm_output}.*). There are no cuDNN
wrappers to reproduce (src/operator/cudnn_*): conv/pool/BN lower to
lax.conv_general_dilated / lax.reduce_window and XLA fuses the rest — the
TPU-native answer to vendor kernels (SURVEY.md §7 translation table).

Loss layers reproduce the reference's backward contract — they IGNORE the
incoming head gradient and emit their own (softmax_output-inl.h Backward) —
via jax.custom_vjp.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import functools

from ..base import MXNetError
from .registry import AttrSpec, register


# --- FullyConnected (reference: fully_connected.cc:60, -inl.h) ----------------
def _fc_names(attrs):
    return ["data", "weight"] if attrs.get("no_bias") else ["data", "weight", "bias"]


@register(
    "FullyConnected",
    attrs={
        "num_hidden": AttrSpec("int", required=True),
        "no_bias": AttrSpec("bool", default=False),
        "flatten": AttrSpec("bool", default=True),
        "out_dtype": AttrSpec("dtype", default=None),
    },
    input_names=_fc_names,
)
def _fully_connected(attrs, data, weight, bias=None):
    """y = x · Wᵀ + b. Batched 2D matmul → single MXU op. With flatten=False
    the matmul applies over the last axis, keeping leading axes (the later
    reference semantics the attr advertises). ``out_dtype`` asks the matmul
    for its accumulator's type (bfloat16 operands, float32 logits) instead
    of rounding the result to the operands' type."""
    out = attrs.get("out_dtype")
    if attrs.get("flatten", True):
        x = data.reshape((data.shape[0], -1)) if data.ndim != 2 else data
        y = jnp.dot(x, weight.T, preferred_element_type=out)
    else:
        y = jnp.einsum("...i,oi->...o", data, weight,
                       preferred_element_type=out)
    if bias is not None:
        y = y + bias
    return y


# --- Convolution (reference: convolution.cc:81, -inl.h) -----------------------
_CONV_ATTRS = lambda: {
    "kernel": AttrSpec("shape", required=True),
    "stride": AttrSpec("shape", default=()),
    "dilate": AttrSpec("shape", default=()),
    "pad": AttrSpec("shape", default=()),
    "num_filter": AttrSpec("int", required=True),
    "num_group": AttrSpec("int", default=1),
    "workspace": AttrSpec("int", default=1024),
    "no_bias": AttrSpec("bool", default=False),
    "cudnn_tune": AttrSpec("str", default=None),
    "cudnn_off": AttrSpec("bool", default=False),
    "layout": AttrSpec("str", default=None),
    "target_shape": AttrSpec("shape", default=()),
    "adj": AttrSpec("shape", default=()),
}


def _conv_dnums(nd):
    # NC + spatial, OI + spatial — the reference's NCHW/NCDHW layouts.
    sp = "DHW"[3 - nd :]
    return ("NC" + sp, "OI" + sp, "NC" + sp)


def _spatial(attrs, key, nd, fill):
    v = attrs.get(key) or ()
    return tuple(v) if len(v) == nd else (fill,) * nd


@register("Convolution", attrs=_CONV_ATTRS(), input_names=_fc_names, aliases=("Convolution_v1",))
def _convolution(attrs, data, weight, bias=None):
    nd = len(attrs["kernel"])
    stride = _spatial(attrs, "stride", nd, 1)
    dilate = _spatial(attrs, "dilate", nd, 1)
    pad = _spatial(attrs, "pad", nd, 0)
    out = jax.lax.conv_general_dilated(
        data,
        weight,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=_conv_dnums(nd),
        feature_group_count=attrs["num_group"],
    )
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


@register("Deconvolution", attrs=_CONV_ATTRS(), input_names=_fc_names)
def _deconvolution(attrs, data, weight, bias=None):
    """Transposed convolution = conv with lhs dilation (reference:
    deconvolution-inl.h). Weight layout (C_in, num_filter/g, *kernel)."""
    nd = len(attrs["kernel"])
    stride = _spatial(attrs, "stride", nd, 1)
    pad = _spatial(attrs, "pad", nd, 0)
    adj = _spatial(attrs, "adj", nd, 0)
    kernel = attrs["kernel"]
    # flip spatial dims and swap I/O to express deconv as a dilated conv
    w = jnp.flip(weight, axis=tuple(range(2, 2 + nd)))
    g = attrs["num_group"]
    if g > 1:
        cin = w.shape[0]
        w = w.reshape((g, cin // g) + w.shape[1:])
        w = jnp.swapaxes(w, 1, 2).reshape((w.shape[2] * g, cin // g) + w.shape[3:])
    else:
        w = jnp.swapaxes(w, 0, 1)
    pads = [
        (kernel[i] - 1 - pad[i], kernel[i] - 1 - pad[i] + adj[i]) for i in range(nd)
    ]
    out = jax.lax.conv_general_dilated(
        data,
        w,
        window_strides=(1,) * nd,
        padding=pads,
        lhs_dilation=stride,
        dimension_numbers=_conv_dnums(nd),
        feature_group_count=g,
    )
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


# --- Pooling (reference: pooling.cc, pool.h) ----------------------------------
@register(
    "Pooling",
    attrs={
        "kernel": AttrSpec("shape", required=True),
        "pool_type": AttrSpec("str", default="max"),
        "global_pool": AttrSpec("bool", default=False),
        "stride": AttrSpec("shape", default=()),
        "pad": AttrSpec("shape", default=()),
        "pooling_convention": AttrSpec("str", default="valid"),
        "cudnn_off": AttrSpec("bool", default=False),
    },
    aliases=("Pooling_v1",),
)
def _pooling(attrs, data):
    nd = data.ndim - 2
    if attrs["global_pool"]:
        kernel = data.shape[2:]
        stride = (1,) * nd
        pad = (0,) * nd
    else:
        kernel = tuple(attrs["kernel"])
        stride = _spatial(attrs, "stride", nd, 1)
        pad = _spatial(attrs, "pad", nd, 0)
    window = (1, 1) + kernel
    strides = (1, 1) + stride
    if attrs["pooling_convention"] == "full":
        # ceil-mode output: pad high edge enough to cover the last window
        pads = [(0, 0), (0, 0)]
        for i in range(nd):
            in_sz = data.shape[2 + i] + 2 * pad[i]
            out_sz = -(-(in_sz - kernel[i]) // stride[i]) + 1
            needed = (out_sz - 1) * stride[i] + kernel[i] - in_sz
            pads.append((pad[i], pad[i] + max(needed, 0)))
    else:
        pads = [(0, 0), (0, 0)] + [(p, p) for p in pad]
    pt = attrs["pool_type"]
    if pt == "max":
        init = -jnp.inf
        out = jax.lax.reduce_window(data, init, jax.lax.max, window, strides, pads)
    elif pt in ("avg", "sum"):
        out = jax.lax.reduce_window(data, 0.0, jax.lax.add, window, strides, pads)
        if pt == "avg":
            out = out / np.prod(kernel)  # count-include-pad, as mshadow pool does
    else:
        raise MXNetError("unknown pool_type %r" % pt)
    return out


# --- Activations --------------------------------------------------------------
@register("Activation", attrs={"act_type": AttrSpec("str", required=True)})
def _activation(attrs, data):
    """(reference: activation.cc) act_type ∈ relu|sigmoid|tanh|softrelu, and
    silu (x * sigmoid(x)), which the reference's era did not have."""
    t = attrs["act_type"]
    if t == "silu":
        return jax.nn.silu(data)
    if t == "relu":
        return jnp.maximum(data, 0)
    if t == "sigmoid":
        return jax.nn.sigmoid(data)
    if t == "tanh":
        return jnp.tanh(data)
    if t == "softrelu":
        return jnp.logaddexp(data, 0.0)
    raise MXNetError("unknown act_type %r" % t)


def _lrelu_names(attrs):
    return ["data", "gamma"] if attrs.get("act_type") == "prelu" else ["data"]


@register(
    "LeakyReLU",
    attrs={
        "act_type": AttrSpec("str", default="leaky"),
        "slope": AttrSpec("float", default=0.25),
        "lower_bound": AttrSpec("float", default=0.125),
        "upper_bound": AttrSpec("float", default=0.334),
    },
    input_names=_lrelu_names,
    needs_rng=True,
    needs_train_flag=True,
)
def _leaky_relu(attrs, data, gamma=None, is_train=False, rng=None):
    """(reference: leaky_relu.cc) leaky|prelu|elu|rrelu."""
    t = attrs["act_type"]
    if t == "leaky":
        return jnp.where(data >= 0, data, attrs["slope"] * data)
    if t == "elu":
        return jnp.where(data >= 0, data, attrs["slope"] * jnp.expm1(data))
    if t == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) if data.ndim > 2 else gamma
        return jnp.where(data >= 0, data, g * data)
    if t == "rrelu":
        if is_train and rng is not None:
            slope = jax.random.uniform(
                rng, data.shape, minval=attrs["lower_bound"], maxval=attrs["upper_bound"], dtype=data.dtype
            )
        else:
            slope = (attrs["lower_bound"] + attrs["upper_bound"]) / 2.0
        return jnp.where(data >= 0, data, slope * data)
    raise MXNetError("unknown act_type %r" % t)


@register(
    "Dropout",
    attrs={"p": AttrSpec("float", default=0.5)},
    needs_rng=True,
    needs_train_flag=True,
)
def _dropout(attrs, data, is_train=False, rng=None):
    """Inverted dropout (reference: dropout-inl.h); identity at inference."""
    p = attrs["p"]
    if not is_train or p <= 0.0 or rng is None:
        return data
    keep = 1.0 - p
    mask = jax.random.bernoulli(rng, keep, data.shape)
    return jnp.where(mask, data / keep, 0.0).astype(data.dtype)


@register(
    "softmax",
    attrs={"axis": AttrSpec("int", default=-1), "temperature": AttrSpec("any", default=None)},
)
def _softmax(attrs, data):
    t = attrs.get("temperature")
    if t not in (None, "None"):
        data = data / float(t)
    return jax.nn.softmax(data, axis=attrs["axis"])


@register("log_softmax", attrs={"axis": AttrSpec("int", default=-1)})
def _log_softmax(attrs, data):
    return jax.nn.log_softmax(data, axis=attrs["axis"])


@register(
    "SoftmaxActivation",
    attrs={"mode": AttrSpec("str", default="instance")},
)
def _softmax_activation(attrs, data):
    """(reference: softmax_activation.cc) instance → over trailing dims of each
    sample; channel → over axis 1."""
    if attrs["mode"] == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


# --- BatchNorm (reference: batch_norm.cc:38, -inl.h) --------------------------
def _bn_outputs(attrs):
    return 3 if attrs.get("output_mean_var") else 1


@jax.custom_vjp
def _normalize(x, scale32, shift32):
    """``x * scale + shift`` per channel (axis 1), in the activation's dtype:
    the one elementwise pass of a training BatchNorm."""
    b = (1, -1) + (1,) * (x.ndim - 2)
    return x * scale32.astype(x.dtype).reshape(b) \
        + shift32.astype(x.dtype).reshape(b)


def _normalize_fwd(x, scale32, shift32):
    return _normalize(x, scale32, shift32), (x, scale32)


def _normalize_bwd(saved, dout):
    # explicit f32 accumulators for the per-channel reductions (plain
    # autodiff would reduce in the activation dtype: bf16 over B*H*W)
    x, scale32 = saved
    b = (1, -1) + (1,) * (x.ndim - 2)
    axes = (0,) + tuple(range(2, x.ndim))
    dx = dout * scale32.astype(dout.dtype).reshape(b)
    dout32 = dout.astype(jnp.float32)
    dscale = jnp.sum(dout32 * x.astype(jnp.float32), axis=axes)
    dshift = jnp.sum(dout32, axis=axes)
    return dx, dscale, dshift


_normalize.defvjp(_normalize_fwd, _normalize_bwd)


@register(
    "BatchNorm",
    attrs={
        "eps": AttrSpec("float", default=1e-3),
        "momentum": AttrSpec("float", default=0.9),
        "fix_gamma": AttrSpec("bool", default=True),
        "use_global_stats": AttrSpec("bool", default=False),
        "output_mean_var": AttrSpec("bool", default=False),
    },
    input_names=("data", "gamma", "beta"),
    aux_names=("moving_mean", "moving_var"),
    num_outputs=_bn_outputs,
    output_names=lambda a: ["output", "mean", "var"][: _bn_outputs(a)],
    needs_train_flag=True,
)
def _batch_norm(attrs, inputs, aux, is_train=False):
    """Channel-axis-1 batch norm with moving-stat aux state. The reference
    mutates aux in-place via FMutateInputs; here new aux values are returned
    as functional carries and threaded by the executor (SURVEY.md §7 hard
    parts: "Mutable aux states")."""
    data, gamma, beta = inputs
    moving_mean, moving_var = aux
    eps, momentum = attrs["eps"], attrs["momentum"]
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    if is_train and not attrs["use_global_stats"]:
        # batch moments from one pass of f32 sums; the per-channel scale and
        # shift stay f32 and differentiate by plain autodiff (which is what
        # gives the mean and var heads their cotangents); only _normalize,
        # the pass over the activation, has a hand-written backward
        axes = (0,) + tuple(range(2, data.ndim))
        x32 = data.astype(jnp.float32)
        ssum = jnp.sum(x32, axis=axes)
        ssq = jnp.sum(x32 * x32, axis=axes)
        cnt = data.size // data.shape[1]
        mean = ssum / cnt
        var = ssq / cnt - mean * mean
        scale32 = jax.lax.rsqrt(var + eps)
        if not attrs["fix_gamma"]:
            scale32 = gamma.astype(jnp.float32) * scale32
        shift32 = beta.astype(jnp.float32) - mean * scale32
        sg = jax.lax.stop_gradient
        new_mean = moving_mean * momentum \
            + sg(mean).astype(moving_mean.dtype) * (1 - momentum)
        new_var = moving_var * momentum \
            + sg(var).astype(moving_var.dtype) * (1 - momentum)
        out = _normalize(data, scale32, shift32)
        m, v = mean.astype(data.dtype), var.astype(data.dtype)
        outs = (out, m, v) if attrs["output_mean_var"] else (out,)
        return outs, (new_mean, new_var)
    if attrs["fix_gamma"]:
        gamma = jax.lax.stop_gradient(jnp.ones_like(gamma))
    m, v = moving_mean, moving_var
    out = (data - m.reshape(bshape)) * jax.lax.rsqrt(v.reshape(bshape) + eps)
    out = out * gamma.reshape(bshape) + beta.reshape(bshape)
    outs = (out, m, v) if attrs["output_mean_var"] else (out,)
    return outs, (moving_mean, moving_var)


# --- Loss/output layers (custom-vjp: ignore head gradient) --------------------
_SM_ATTRS = lambda: {
    "grad_scale": AttrSpec("float", default=1.0),
    "ignore_label": AttrSpec("float", default=-1.0),
    "multi_output": AttrSpec("bool", default=False),
    "use_ignore": AttrSpec("bool", default=False),
    "preserve_shape": AttrSpec("bool", default=False),
    "normalization": AttrSpec("str", default="null"),
    "out_grad": AttrSpec("bool", default=False),
}


def _softmax_output_grad(prob, label, attrs):
    """(p - onehot(y)) · scale, with 'null'|'batch'|'valid' normalization
    (reference: softmax_output-inl.h Backward)."""
    if prob.ndim > 2 and attrs["multi_output"]:
        # (N, C, ...) with label (N, ...)
        nclass = prob.shape[1]
        onehot = jax.nn.one_hot(label.astype(jnp.int32), nclass, axis=1, dtype=prob.dtype)
    else:
        nclass = prob.shape[-1]
        onehot = jax.nn.one_hot(label.astype(jnp.int32), nclass, dtype=prob.dtype)
    grad = prob - onehot
    valid = jnp.ones(label.shape, dtype=prob.dtype)
    if attrs["use_ignore"]:
        keep = (label != attrs["ignore_label"]).astype(prob.dtype)
        if attrs["multi_output"] and prob.ndim > 2:
            grad = grad * jnp.expand_dims(keep, 1)
        else:
            grad = grad * keep.reshape(keep.shape + (1,))
        valid = keep
    norm = attrs["normalization"]
    scale = attrs["grad_scale"]
    if norm == "batch":
        grad = grad / label.shape[0]
    elif norm == "valid":
        grad = grad / jnp.maximum(jnp.sum(valid), 1.0)
    return grad * scale


@functools.lru_cache(maxsize=None)
def _softmax_output_core(attrs_key):
    """Build a custom-vjp softmax-output closure for one attr signature.
    Attrs are static (compile-time) config, matching the reference where
    SoftmaxOutputParam is baked into the bound operator."""
    attrs = dict(attrs_key)

    @jax.custom_vjp
    def core(data, label):
        axis = 1 if (attrs["multi_output"] and data.ndim > 2) else -1
        return jax.nn.softmax(data, axis=axis)

    def fwd(data, label):
        out = core(data, label)
        return out, (out, label)

    def bwd(res, g):
        prob, label = res
        dgrad = _softmax_output_grad(prob, label, attrs).astype(prob.dtype)
        return (dgrad, jnp.zeros_like(label))

    core.defvjp(fwd, bwd)
    return core


@register(
    "SoftmaxOutput",
    attrs=_SM_ATTRS(),
    input_names=("data", "label"),
    aliases=("Softmax",),
)
def _softmax_output(attrs, data, label):
    """Softmax forward + cross-entropy gradient on backward, ignoring the head
    gradient exactly like the reference (softmax_output-inl.h)."""
    key = tuple(
        (k, attrs[k])
        for k in ("grad_scale", "ignore_label", "multi_output", "use_ignore", "normalization")
    )
    return _softmax_output_core(key)(data, label)


def _make_output_op(name, fwd, grad):
    """Regression-output family: forward transform + own backward (reference:
    regression_output-inl.h). grad_scale is compile-time config baked into the
    cached closure so the vjp's cotangent pytree matches the primal args
    exactly (custom_vjp rejects None cotangents for array args)."""

    @functools.lru_cache(maxsize=None)
    def core_for(grad_scale):
        @jax.custom_vjp
        def core(data, label):
            return fwd(data)

        def core_fwd(data, label):
            out = fwd(data)
            return out, (out, label)

        def core_bwd(res, g):
            out, label = res
            num_output = max(int(np.prod(out.shape[1:])), 1)
            d = grad(out, label.reshape(out.shape)) * (grad_scale / num_output)
            return (d.astype(out.dtype), jnp.zeros_like(label))

        core.defvjp(core_fwd, core_bwd)
        return core

    @register(name, attrs={"grad_scale": AttrSpec("float", default=1.0)}, input_names=("data", "label"))
    def op(attrs, data, label):
        return core_for(float(attrs["grad_scale"]))(data, label)

    return op


_make_output_op("LinearRegressionOutput", lambda x: x, lambda o, y: o - y)
_make_output_op("LogisticRegressionOutput", jax.nn.sigmoid, lambda o, y: o - y)
_make_output_op("MAERegressionOutput", lambda x: x, lambda o, y: jnp.sign(o - y))


@functools.lru_cache(maxsize=None)
def _make_loss_core(grad_scale, norm_div):
    """grad_scale/norm_div are static config (like the bound MakeLossParam in
    the reference), so the vjp returns exactly one cotangent for `data`."""

    @jax.custom_vjp
    def core(data):
        return data

    def ml_fwd(data):
        return data, None

    def ml_bwd(res, g):
        # output aliases data, so g's shape/dtype are data's
        return (jnp.full(jnp.shape(g), grad_scale / norm_div, dtype=g.dtype),)

    core.defvjp(ml_fwd, ml_bwd)
    return core


@register(
    "MakeLoss",
    attrs={
        "grad_scale": AttrSpec("float", default=1.0),
        "valid_thresh": AttrSpec("float", default=0.0),
        "normalization": AttrSpec("str", default="null"),
    },
)
def _make_loss(attrs, data):
    """Treat data as a loss: backward emits grad_scale (reference: make_loss.cc)."""
    norm_div = float(data.shape[0]) if attrs["normalization"] == "batch" else 1.0
    return _make_loss_core(float(attrs["grad_scale"]), norm_div)(data)


@functools.lru_cache(maxsize=None)
def _svm_core(margin, coef, use_linear):
    @jax.custom_vjp
    def core(data, label):
        return data

    def fwd(data, label):
        return data, (data, label)

    def bwd(res, g):
        data, label = res
        onehot = jax.nn.one_hot(label.astype(jnp.int32), data.shape[-1], dtype=data.dtype)
        ty = 2.0 * onehot - 1.0  # +1 for target class, -1 otherwise
        viol = (margin - ty * data) > 0
        if use_linear:
            d = jnp.where(viol, -ty * coef, 0.0)
        else:
            d = jnp.where(viol, -2.0 * coef * (margin - ty * data) * ty, 0.0)
        return (d.astype(data.dtype), jnp.zeros_like(label))

    core.defvjp(fwd, bwd)
    return core


@register(
    "SVMOutput",
    attrs={
        "margin": AttrSpec("float", default=1.0),
        "regularization_coefficient": AttrSpec("float", default=1.0),
        "use_linear": AttrSpec("bool", default=False),
    },
    input_names=("data", "label"),
)
def _svm_output(attrs, data, label):
    """Hinge-loss output layer (reference: svm_output.cc)."""
    return _svm_core(
        attrs["margin"], attrs["regularization_coefficient"], bool(attrs["use_linear"])
    )(data, label)


@register(
    "IdentityAttachKLSparseReg",
    attrs={
        "sparseness_target": AttrSpec("float", default=0.1),
        "penalty": AttrSpec("float", default=0.001),
        "momentum": AttrSpec("float", default=0.9),
    },
    aux_names=("moving_avg",),
)
def _identity_kl(attrs, inputs, aux):
    """Identity forward with KL sparseness penalty added to the gradient
    (reference: identity_attach_KL_sparse_reg.cc)."""
    (data,) = inputs
    (moving,) = aux
    rho_hat = jnp.mean(jax.nn.sigmoid(data))
    new_moving = moving * attrs["momentum"] + rho_hat * (1 - attrs["momentum"])
    rho = attrs["sparseness_target"]
    penalty = attrs["penalty"] * (-rho / (rho_hat + 1e-8) + (1 - rho) / (1 - rho_hat + 1e-8))
    # forward identity; penalty enters via a zero-valued term with gradient
    out = data + jax.lax.stop_gradient(penalty) * (data - jax.lax.stop_gradient(data))
    return (out,), (new_moving,)


# --- Norm layers --------------------------------------------------------------
@register(
    "LRN",
    attrs={
        "alpha": AttrSpec("float", default=1e-4),
        "beta": AttrSpec("float", default=0.75),
        "knorm": AttrSpec("float", default=2.0),
        "nsize": AttrSpec("int", required=True),
    },
)
def _lrn(attrs, data):
    """Local response norm across channels (reference: lrn.cc)."""
    n = attrs["nsize"]
    sq = jnp.square(data)
    half = n // 2
    pad = [(0, 0), (half, half)] + [(0, 0)] * (data.ndim - 2)
    sq = jnp.pad(sq, pad)
    window = (1, n) + (1,) * (data.ndim - 2)
    ssum = jax.lax.reduce_window(sq, 0.0, jax.lax.add, window, (1,) * data.ndim, [(0, 0)] * data.ndim)
    norm = attrs["knorm"] + (attrs["alpha"] / n) * ssum
    return data * jnp.power(norm, -attrs["beta"])


@register(
    "InstanceNorm",
    attrs={"eps": AttrSpec("float", default=1e-3)},
    input_names=("data", "gamma", "beta"),
)
def _instance_norm(attrs, data, gamma, beta):
    """Per-sample per-channel normalization (reference: instance_norm.cc)."""
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    out = (data - mean) * jax.lax.rsqrt(var + attrs["eps"])
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


@register(
    "L2Normalization",
    attrs={"eps": AttrSpec("float", default=1e-10), "mode": AttrSpec("str", default="instance")},
)
def _l2_normalization(attrs, data):
    """(reference: l2_normalization.cc) instance|channel|spatial."""
    mode = attrs["mode"]
    if mode == "instance":
        axes = tuple(range(1, data.ndim))
    elif mode == "channel":
        axes = (1,)
    else:  # spatial
        axes = tuple(range(2, data.ndim))
    norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=axes, keepdims=True) + attrs["eps"])
    return data / norm


@register(
    "UpSampling",
    attrs={
        "scale": AttrSpec("int", required=True),
        "num_filter": AttrSpec("int", default=0),
        "sample_type": AttrSpec("str", default="nearest"),
        "multi_input_mode": AttrSpec("str", default="concat"),
        "num_args": AttrSpec("int", default=1),
        "workspace": AttrSpec("int", default=512),
    },
    input_names=lambda a: ["arg%d" % i for i in range(int(a.get("num_args", 1)))],
)
def _upsampling(attrs, *args):
    """Nearest/bilinear upsampling (reference: upsampling.cc)."""
    s = attrs["scale"]
    outs = []
    for data in args:
        if attrs["sample_type"] == "nearest":
            out = jnp.repeat(jnp.repeat(data, s, axis=2), s, axis=3)
        else:
            n, c, h, w = data.shape
            out = jax.image.resize(data, (n, c, h * s, w * s), method="bilinear")
        outs.append(out)
    if len(outs) == 1:
        return outs[0]
    if attrs["multi_input_mode"] == "sum":
        total = outs[0]
        for o in outs[1:]:
            total = total + o
        return total
    return jnp.concatenate(outs, axis=1)


# --- Correlation-style vision ops are in vision.py (round scope) --------------
