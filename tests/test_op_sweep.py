"""Registry-driven operator sweep (round-5, VERDICT #3).

One declarative spec per registered op: forward against a numpy/scipy
oracle, a finite-difference gradient check where the op is smooth, moment
tests for the samplers. The meta-test at the bottom walks
``registry.list_ops()`` and FAILS if any registered op has neither a spec
here nor an explicit EXEMPT pointer to the dedicated suite that covers it —
silent breakage of an op can no longer pass CI. Depth model:
/root/reference/tests/python/unittest/test_operator.py + test_random.py.
"""
import math

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import test_utils
from mxnet_tpu.ops import registry

RS = lambda seed=0: np.random.RandomState(seed)


def _u(lo, hi, shape=(3, 4), seed=0):
    return RS(seed).uniform(lo, hi, shape).astype("float32")


class Spec:
    """One op's sweep entry. ``build(rs)`` returns (symbol, location,
    expected outputs); ``grad`` enables the finite-difference check."""

    def __init__(self, build, grad=False, rtol=1e-4, atol=1e-5,
                 grad_eps=1e-2):
        self.build, self.grad = build, grad
        self.rtol, self.atol, self.grad_eps = rtol, atol, grad_eps


def UNARY(fn, lo=-1.0, hi=1.0, grad=True, name=None, **kw):
    def build(op):
        x = _u(lo, hi)
        s = getattr(mx.sym, op)(mx.sym.Variable("x"))
        return s, {"x": x}, [fn(x)]

    return Spec(build, grad=grad, **kw)


def BINARY(fn, lo=-1.0, hi=1.0, bcast=False, grad=True, **kw):
    def build(op):
        a = _u(lo, hi, (3, 4), 1)
        b = _u(lo, hi, (3, 1) if bcast else (3, 4), 2)
        s = getattr(mx.sym, op)(mx.sym.Variable("a"), mx.sym.Variable("b"))
        return s, {"a": a, "b": b}, [fn(a, b)]

    return Spec(build, grad=grad, **kw)


def SCALAR(fn, scalar=1.7, lo=-1.0, hi=1.0, grad=True, **kw):
    def build(op):
        x = _u(lo, hi, seed=3)
        s = getattr(mx.sym, op)(mx.sym.Variable("x"), scalar=scalar)
        return s, {"x": x}, [fn(x, np.float32(scalar))]

    return Spec(build, grad=grad, **kw)


def REDUCE(fn, lo=0.5, hi=1.5, grad=True, attrs=None, **kw):
    attrs = attrs if attrs is not None else {"axis": (1,), "keepdims": True}

    def build(op):
        x = _u(lo, hi, (2, 3, 4), 4)
        s = getattr(mx.sym, op)(mx.sym.Variable("x"), **attrs)
        ax = attrs.get("axis")
        np_kw = {}
        if ax is not None and ax != ():
            np_kw["axis"] = ax if not isinstance(ax, tuple) or len(ax) > 1 else ax[0]
        if attrs.get("keepdims"):
            np_kw["keepdims"] = True
        return s, {"x": x}, [fn(x, **np_kw)]

    return Spec(build, grad=grad, **kw)


def CUSTOM(build, **kw):
    return Spec(build, **kw)


def _sp():
    return pytest.importorskip("scipy.special")


# ---------------------------------------------------------------------- specs
SPECS = {
    # ---- unary elementwise (elemwise_unary_op.cc families)
    "abs": UNARY(np.abs),
    "negative": UNARY(np.negative),
    "reciprocal": UNARY(np.reciprocal, 0.5, 2.0),
    "sign": UNARY(np.sign, grad=False),
    "square": UNARY(np.square),
    "sqrt": UNARY(np.sqrt, 0.5, 2.0),
    "rsqrt": UNARY(lambda x: 1.0 / np.sqrt(x), 0.5, 2.0),
    "cbrt": UNARY(np.cbrt, 0.5, 2.0),
    "rcbrt": UNARY(lambda x: 1.0 / np.cbrt(x), 0.5, 2.0),
    "exp": UNARY(np.exp),
    "expm1": UNARY(np.expm1),
    "log": UNARY(np.log, 0.5, 3.0),
    "log10": UNARY(np.log10, 0.5, 3.0),
    "log2": UNARY(np.log2, 0.5, 3.0),
    "log1p": UNARY(np.log1p, -0.4, 2.0),
    "sin": UNARY(np.sin),
    "cos": UNARY(np.cos),
    "tan": UNARY(np.tan, -1.0, 1.0),
    "arcsin": UNARY(np.arcsin, -0.8, 0.8),
    "arccos": UNARY(np.arccos, -0.8, 0.8),
    "arctan": UNARY(np.arctan, -2.0, 2.0),
    "sinh": UNARY(np.sinh),
    "cosh": UNARY(np.cosh),
    "tanh": UNARY(np.tanh),
    "arcsinh": UNARY(np.arcsinh),
    "arccosh": UNARY(np.arccosh, 1.2, 3.0),
    "arctanh": UNARY(np.arctanh, -0.8, 0.8),
    "degrees": UNARY(np.degrees),
    "radians": UNARY(np.radians),
    # rounding family: no ties in (lo,hi) randoms; zero/undefined gradient
    "ceil": UNARY(np.ceil, -2.3, 2.3, grad=False),
    "floor": UNARY(np.floor, -2.3, 2.3, grad=False),
    "trunc": UNARY(np.trunc, -2.3, 2.3, grad=False),
    "fix": UNARY(np.fix, -2.3, 2.3, grad=False),
    "rint": UNARY(np.rint, -2.3, 2.3, grad=False),
    "round": UNARY(lambda x: np.sign(x) * np.floor(np.abs(x) + 0.5),
                   -2.3, 2.3, grad=False),
    "erf": CUSTOM(lambda op: (mx.sym.erf(mx.sym.Variable("x")),
                              {"x": _u(-2, 2)},
                              [_sp().erf(_u(-2, 2)).astype("float32")]),
                  grad=True),
    "gamma": CUSTOM(lambda op: (mx.sym.gamma(mx.sym.Variable("x")),
                                {"x": _u(1.2, 3.0)},
                                [_sp().gamma(_u(1.2, 3.0)).astype("float32")]),
                    grad=True),
    "gammaln": CUSTOM(lambda op: (mx.sym.gammaln(mx.sym.Variable("x")),
                                  {"x": _u(1.2, 3.0)},
                                  [_sp().gammaln(_u(1.2, 3.0)).astype("float32")]),
                      grad=True),
    "relu": UNARY(lambda x: np.maximum(x, 0), grad=False),  # kink at 0
    "sigmoid": UNARY(lambda x: 1 / (1 + np.exp(-x))),
    "softsign": UNARY(lambda x: x / (1 + np.abs(x))),
    "logical_not": UNARY(lambda x: (x == 0).astype("float32"), -1, 1,
                         grad=False),
    "_copy": UNARY(lambda x: x),
    "ones_like": UNARY(np.ones_like, grad=False),
    "zeros_like": UNARY(np.zeros_like, grad=False),
    "BlockGrad": UNARY(lambda x: x, grad=False),
    "smooth_l1": SCALAR(
        lambda x, s: np.where(np.abs(x) < 1 / s ** 2,
                              0.5 * (s * x) ** 2, np.abs(x) - 0.5 / s ** 2),
        scalar=1.0, lo=-2, hi=2, grad=False),
    "clip": CUSTOM(lambda op: (
        mx.sym.clip(mx.sym.Variable("x"), a_min=-0.5, a_max=0.5),
        {"x": _u(-1, 1)}, [np.clip(_u(-1, 1), -0.5, 0.5)]), grad=False),
    "Cast": CUSTOM(lambda op: (
        mx.sym.Cast(mx.sym.Variable("x"), dtype="float64"),
        {"x": _u(-1, 1)}, [_u(-1, 1).astype("float64")])),
    # ---- binary elementwise
    "elemwise_add": BINARY(np.add),
    "elemwise_sub": BINARY(np.subtract),
    "elemwise_mul": BINARY(np.multiply),
    "elemwise_div": BINARY(np.divide, 0.5, 2.0),
    "_grad_add": BINARY(np.add),
    "_maximum": BINARY(np.maximum, grad=False),
    "_minimum": BINARY(np.minimum, grad=False),
    "_hypot": BINARY(np.hypot, 0.5, 2.0),
    "_mod": BINARY(np.mod, 1.0, 3.0, grad=False),
    "_power": BINARY(np.power, 0.5, 2.0),
    "_equal": BINARY(lambda a, b: (a == b).astype("f"), grad=False),
    "_not_equal": BINARY(lambda a, b: (a != b).astype("f"), grad=False),
    "_greater": BINARY(lambda a, b: (a > b).astype("f"), grad=False),
    "_greater_equal": BINARY(lambda a, b: (a >= b).astype("f"), grad=False),
    "_lesser": BINARY(lambda a, b: (a < b).astype("f"), grad=False),
    "_lesser_equal": BINARY(lambda a, b: (a <= b).astype("f"), grad=False),
    # ---- broadcast binary
    "broadcast_add": BINARY(np.add, bcast=True),
    "broadcast_sub": BINARY(np.subtract, bcast=True),
    "broadcast_mul": BINARY(np.multiply, bcast=True),
    "broadcast_div": BINARY(np.divide, 0.5, 2.0, bcast=True),
    "broadcast_mod": BINARY(np.mod, 1.0, 3.0, bcast=True, grad=False),
    "broadcast_power": BINARY(np.power, 0.5, 2.0, bcast=True),
    "broadcast_maximum": BINARY(np.maximum, bcast=True, grad=False),
    "broadcast_minimum": BINARY(np.minimum, bcast=True, grad=False),
    "broadcast_hypot": BINARY(np.hypot, 0.5, 2.0, bcast=True),
    "broadcast_equal": BINARY(lambda a, b: (a == b).astype("f"),
                              bcast=True, grad=False),
    "broadcast_not_equal": BINARY(lambda a, b: (a != b).astype("f"),
                                  bcast=True, grad=False),
    "broadcast_greater": BINARY(lambda a, b: (a > b).astype("f"),
                                bcast=True, grad=False),
    "broadcast_greater_equal": BINARY(lambda a, b: (a >= b).astype("f"),
                                      bcast=True, grad=False),
    "broadcast_lesser": BINARY(lambda a, b: (a < b).astype("f"),
                               bcast=True, grad=False),
    "broadcast_lesser_equal": BINARY(lambda a, b: (a <= b).astype("f"),
                                     bcast=True, grad=False),
    # ---- scalar ops
    "_plus_scalar": SCALAR(lambda x, s: x + s),
    "_minus_scalar": SCALAR(lambda x, s: x - s),
    "_rminus_scalar": SCALAR(lambda x, s: s - x),
    "_mul_scalar": SCALAR(lambda x, s: x * s),
    "_div_scalar": SCALAR(lambda x, s: x / s),
    "_rdiv_scalar": SCALAR(lambda x, s: s / x, lo=0.5, hi=2.0),
    "_mod_scalar": SCALAR(lambda x, s: np.mod(x, s), lo=1, hi=3, grad=False),
    "_rmod_scalar": SCALAR(lambda x, s: np.mod(s, x), lo=1, hi=3, grad=False),
    "_power_scalar": SCALAR(lambda x, s: np.power(x, s), lo=0.5, hi=2.0),
    "_rpower_scalar": SCALAR(lambda x, s: np.power(s, x)),
    "_maximum_scalar": SCALAR(np.maximum, scalar=0.1, grad=False),
    "_minimum_scalar": SCALAR(np.minimum, scalar=0.1, grad=False),
    "_hypot_scalar": SCALAR(np.hypot, lo=0.5, hi=2.0),
    "_equal_scalar": SCALAR(lambda x, s: (x == s).astype("f"), grad=False),
    "_not_equal_scalar": SCALAR(lambda x, s: (x != s).astype("f"), grad=False),
    "_greater_scalar": SCALAR(lambda x, s: (x > s).astype("f"), scalar=0.0,
                              grad=False),
    "_greater_equal_scalar": SCALAR(lambda x, s: (x >= s).astype("f"),
                                    scalar=0.0, grad=False),
    "_lesser_scalar": SCALAR(lambda x, s: (x < s).astype("f"), scalar=0.0,
                             grad=False),
    "_lesser_equal_scalar": SCALAR(lambda x, s: (x <= s).astype("f"),
                                   scalar=0.0, grad=False),
    # ---- reductions
    "sum": REDUCE(np.sum),
    "mean": REDUCE(np.mean),
    "prod": REDUCE(np.prod),
    "max": REDUCE(np.max, grad=False),
    "min": REDUCE(np.min, grad=False),
    "nansum": CUSTOM(lambda op: _nan_reduce(mx.sym.nansum, np.nansum),
                     grad=False),
    "nanprod": CUSTOM(lambda op: _nan_reduce(mx.sym.nanprod, np.nanprod),
                      grad=False),
    "norm": CUSTOM(lambda op: (
        mx.sym.norm(mx.sym.Variable("x")), {"x": _u(-1, 1, (3, 4), 6)},
        [np.sqrt(np.sum(np.square(_u(-1, 1, (3, 4), 6))))]), grad=False),
    "argmax": REDUCE(lambda x, axis, keepdims: np.argmax(x, axis=axis)
                     .astype("f")[:, None],
                     attrs={"axis": 1, "keepdims": True}, grad=False),
    "argmin": REDUCE(lambda x, axis, keepdims: np.argmin(x, axis=axis)
                     .astype("f")[:, None],
                     attrs={"axis": 1, "keepdims": True}, grad=False),
    "argmax_channel": CUSTOM(lambda op: (
        mx.sym.argmax_channel(mx.sym.Variable("x")),
        {"x": _u(-1, 1, (3, 4), 7)},
        [np.argmax(_u(-1, 1, (3, 4), 7), axis=1).astype("f")]), grad=False),
    # ---- shape / layout
    "Reshape": CUSTOM(lambda op: (
        mx.sym.Reshape(mx.sym.Variable("x"), shape=(4, 3)),
        {"x": _u(-1, 1)}, [_u(-1, 1).reshape(4, 3)]), grad=True),
    "Flatten": CUSTOM(lambda op: (
        mx.sym.Flatten(mx.sym.Variable("x")),
        {"x": _u(-1, 1, (2, 3, 4))}, [_u(-1, 1, (2, 3, 4)).reshape(2, 12)]),
        grad=True),
    "expand_dims": CUSTOM(lambda op: (
        mx.sym.expand_dims(mx.sym.Variable("x"), axis=1),
        {"x": _u(-1, 1)}, [_u(-1, 1)[:, None, :]]), grad=True),
    "transpose": CUSTOM(lambda op: (
        mx.sym.transpose(mx.sym.Variable("x"), axes=(1, 0)),
        {"x": _u(-1, 1)}, [_u(-1, 1).T]), grad=True),
    "SwapAxis": CUSTOM(lambda op: (
        mx.sym.SwapAxis(mx.sym.Variable("x"), dim1=0, dim2=2),
        {"x": _u(-1, 1, (2, 3, 4))}, [_u(-1, 1, (2, 3, 4)).swapaxes(0, 2)]),
        grad=True),
    "tile": CUSTOM(lambda op: (
        mx.sym.tile(mx.sym.Variable("x"), reps=(2, 3)),
        {"x": _u(-1, 1)}, [np.tile(_u(-1, 1), (2, 3))]), grad=True),
    "repeat": CUSTOM(lambda op: (
        mx.sym.repeat(mx.sym.Variable("x"), repeats=2, axis=1),
        {"x": _u(-1, 1)}, [np.repeat(_u(-1, 1), 2, axis=1)]), grad=True),
    "reverse": CUSTOM(lambda op: (
        mx.sym.reverse(mx.sym.Variable("x"), axis=(1,)),
        {"x": _u(-1, 1)}, [_u(-1, 1)[:, ::-1]]), grad=True),
    "broadcast_to": CUSTOM(lambda op: (
        mx.sym.broadcast_to(mx.sym.Variable("x"), shape=(3, 4)),
        {"x": _u(-1, 1, (3, 1), 8)},
        [np.broadcast_to(_u(-1, 1, (3, 1), 8), (3, 4))]), grad=True),
    "broadcast_axis": CUSTOM(lambda op: (
        mx.sym.broadcast_axis(mx.sym.Variable("x"), axis=(1,), size=(4,)),
        {"x": _u(-1, 1, (3, 1), 8)},
        [np.broadcast_to(_u(-1, 1, (3, 1), 8), (3, 4))]), grad=True),
    "slice": CUSTOM(lambda op: (
        mx.sym.slice(mx.sym.Variable("x"), begin=(1, 0), end=(3, 2)),
        {"x": _u(-1, 1, (4, 4), 9)}, [_u(-1, 1, (4, 4), 9)[1:3, 0:2]]),
        grad=True),
    "slice_axis": CUSTOM(lambda op: (
        mx.sym.slice_axis(mx.sym.Variable("x"), axis=1, begin=1, end=3),
        {"x": _u(-1, 1, (4, 4), 9)}, [_u(-1, 1, (4, 4), 9)[:, 1:3]]),
        grad=True),
    "Concat": CUSTOM(lambda op: (
        mx.sym.Concat(mx.sym.Variable("a"), mx.sym.Variable("b"), dim=1),
        {"a": _u(-1, 1, (3, 2), 1), "b": _u(-1, 1, (3, 3), 2)},
        [np.concatenate([_u(-1, 1, (3, 2), 1), _u(-1, 1, (3, 3), 2)], 1)]),
        grad=True),
    "SliceChannel": CUSTOM(lambda op: (
        mx.sym.SliceChannel(mx.sym.Variable("x"), num_outputs=2, axis=1),
        {"x": _u(-1, 1, (3, 4), 10)},
        [_u(-1, 1, (3, 4), 10)[:, :2], _u(-1, 1, (3, 4), 10)[:, 2:]]),
        grad=True),
    "add_n": CUSTOM(lambda op: (
        mx.sym.add_n(mx.sym.Variable("a"), mx.sym.Variable("b"),
                     mx.sym.Variable("c")),
        {"a": _u(-1, 1, (3, 4), 1), "b": _u(-1, 1, (3, 4), 2),
         "c": _u(-1, 1, (3, 4), 3)},
        [_u(-1, 1, (3, 4), 1) + _u(-1, 1, (3, 4), 2) + _u(-1, 1, (3, 4), 3)]),
        grad=True),
    "where": CUSTOM(lambda op: (
        mx.sym.where(mx.sym.Variable("c"), mx.sym.Variable("a"),
                     mx.sym.Variable("b")),
        {"c": (RS(11).rand(3, 4) > 0.5).astype("f"),
         "a": _u(-1, 1, (3, 4), 1), "b": _u(-1, 1, (3, 4), 2)},
        [np.where(RS(11).rand(3, 4) > 0.5, _u(-1, 1, (3, 4), 1),
                  _u(-1, 1, (3, 4), 2))]), grad=False),
    "Pad": CUSTOM(lambda op: (
        mx.sym.Pad(mx.sym.Variable("x"), mode="constant",
                   pad_width=(0, 0, 0, 0, 1, 1, 2, 2), constant_value=0.5),
        {"x": _u(-1, 1, (2, 3, 4, 4), 12)},
        [np.pad(_u(-1, 1, (2, 3, 4, 4), 12),
                ((0, 0), (0, 0), (1, 1), (2, 2)), mode="constant",
                constant_values=0.5)]), grad=True),
    # ---- indexing / gather
    "take": CUSTOM(lambda op: (
        mx.sym.take(mx.sym.Variable("a"), mx.sym.Variable("i")),
        {"a": _u(-1, 1, (5, 3), 13), "i": np.array([0., 2., 4.], "f")},
        [_u(-1, 1, (5, 3), 13)[[0, 2, 4]]]), grad=False),
    "batch_take": CUSTOM(lambda op: (
        mx.sym.batch_take(mx.sym.Variable("a"), mx.sym.Variable("i")),
        {"a": _u(-1, 1, (3, 4), 13), "i": np.array([0., 3., 1.], "f")},
        [_u(-1, 1, (3, 4), 13)[np.arange(3), [0, 3, 1]]]), grad=False),
    "pick": CUSTOM(lambda op: (
        mx.sym.pick(mx.sym.Variable("a"), mx.sym.Variable("i"), axis=1),
        {"a": _u(-1, 1, (3, 4), 14), "i": np.array([1., 0., 3.], "f")},
        [_u(-1, 1, (3, 4), 14)[np.arange(3), [1, 0, 3]]]), grad=False),
    "one_hot": CUSTOM(lambda op: (
        mx.sym.one_hot(mx.sym.Variable("i"), depth=4),
        {"i": np.array([0., 2., 3.], "f")},
        [np.eye(4, dtype="f")[[0, 2, 3]]]), grad=False),
    "Embedding": CUSTOM(lambda op: (
        mx.sym.Embedding(mx.sym.Variable("i"), mx.sym.Variable("w"),
                         input_dim=5, output_dim=3),
        {"i": np.array([1., 4., 0.], "f"), "w": _u(-1, 1, (5, 3), 15)},
        [_u(-1, 1, (5, 3), 15)[[1, 4, 0]]]), grad=False),
    # same lookup; the row-sparse-gradient contract lives in the sparse
    # subsystem (tests/test_sparse.py), the op itself is the plain gather
    "SparseEmbedding": CUSTOM(lambda op: (
        mx.sym.SparseEmbedding(mx.sym.Variable("i"), mx.sym.Variable("w"),
                               input_dim=5, output_dim=3),
        {"i": np.array([1., 4., 0.], "f"), "w": _u(-1, 1, (5, 3), 15)},
        [_u(-1, 1, (5, 3), 15)[[1, 4, 0]]]), grad=False),
    # ---- linalg
    "dot": CUSTOM(lambda op: (
        mx.sym.dot(mx.sym.Variable("a"), mx.sym.Variable("b")),
        {"a": _u(-1, 1, (3, 4), 16), "b": _u(-1, 1, (4, 2), 17)},
        [_u(-1, 1, (3, 4), 16) @ _u(-1, 1, (4, 2), 17)]), grad=True,
        rtol=1e-3, atol=1e-4),
    "batch_dot": CUSTOM(lambda op: (
        mx.sym.batch_dot(mx.sym.Variable("a"), mx.sym.Variable("b")),
        {"a": _u(-1, 1, (2, 3, 4), 16), "b": _u(-1, 1, (2, 4, 2), 17)},
        [np.einsum("bij,bjk->bik", _u(-1, 1, (2, 3, 4), 16),
                   _u(-1, 1, (2, 4, 2), 17))]), grad=True,
        rtol=1e-3, atol=1e-4),
    # ---- softmax family. The gradient check weights the output by a second
    # input: with the checker's all-ones head gradient, d(sum softmax)/dx
    # is identically zero (softmax rows sum to 1) and the check degenerates.
    "softmax": CUSTOM(lambda op: _weighted(
        mx.sym.softmax(mx.sym.Variable("x"), axis=-1),
        _np_softmax(_u(-2, 2, (3, 4), 18))), grad=True),
    "log_softmax": CUSTOM(lambda op: _weighted(
        mx.sym.log_softmax(mx.sym.Variable("x"), axis=-1),
        np.log(_np_softmax(_u(-2, 2, (3, 4), 18)))), grad=True),
    "SoftmaxActivation": CUSTOM(lambda op: _weighted(
        mx.sym.SoftmaxActivation(mx.sym.Variable("x")),
        _np_softmax(_u(-2, 2, (3, 4), 18))), grad=True),
    # ---- sorting
    "sort": CUSTOM(lambda op: (
        mx.sym.sort(mx.sym.Variable("x"), axis=1),
        {"x": _u(-1, 1, (3, 4), 19)}, [np.sort(_u(-1, 1, (3, 4), 19), 1)]),
        grad=False),
    "argsort": CUSTOM(lambda op: (
        mx.sym.argsort(mx.sym.Variable("x"), axis=1),
        {"x": _u(-1, 1, (3, 4), 19)},
        [np.argsort(_u(-1, 1, (3, 4), 19), 1).astype("f")]), grad=False),
    "topk": CUSTOM(lambda op: (
        mx.sym.topk(mx.sym.Variable("x"), axis=1, k=2),
        {"x": _u(-1, 1, (3, 4), 19)},
        [np.argsort(-_u(-1, 1, (3, 4), 19), 1)[:, :2].astype("f")]),
        grad=False),
    # ---- creation (no-input; imperative path)
    "_zeros": CUSTOM(lambda op: (None, {"shape": (2, 3)},
                                 [np.zeros((2, 3), "f")])),
    "_ones": CUSTOM(lambda op: (None, {"shape": (2, 3)},
                                [np.ones((2, 3), "f")])),
    "_full": CUSTOM(lambda op: (None, {"shape": (2, 3), "value": 2.5},
                                [np.full((2, 3), 2.5, "f")])),
    "_arange": CUSTOM(lambda op: (None, {"start": 2.0, "stop": 8.0,
                                         "step": 1.5},
                                  [np.arange(2.0, 8.0, 1.5, "f")])),
    # ---- layers with no dedicated suite (VERDICT r4 weak #3 names these)
    "InstanceNorm": CUSTOM(lambda op: _instance_norm_spec(), grad=True,
                           rtol=1e-3, atol=1e-4),
    "UpSampling": CUSTOM(lambda op: (
        mx.sym.UpSampling(mx.sym.Variable("x"), scale=2,
                          sample_type="nearest"),
        {"x": _u(-1, 1, (2, 3, 4, 4), 29)},
        [_u(-1, 1, (2, 3, 4, 4), 29).repeat(2, 2).repeat(2, 3)]), grad=True),
    "IdentityAttachKLSparseReg": CUSTOM(lambda op: (
        mx.sym.IdentityAttachKLSparseReg(mx.sym.Variable("x")),
        {"x": _u(0.05, 0.95, (3, 4), 30)}, [_u(0.05, 0.95, (3, 4), 30)],
        {"identityattachklsparsereg0_moving_avg": np.full((4,), 0.2, "f")})),
    "_CrossDeviceCopy": CUSTOM(lambda op: (
        getattr(mx.sym, "_CrossDeviceCopy")(mx.sym.Variable("x")),
        {"x": _u(-1, 1, (3, 4), 31)}, [_u(-1, 1, (3, 4), 31)]), grad=True),
    # ---- optimizer updates (closed-form oracles; reference
    # src/operator/optimizer_op.cc:18-85)
    "sgd_update": CUSTOM(lambda op: _opt_sgd()),
    "sgd_mom_update": CUSTOM(lambda op: _opt_sgd_mom()),
    "adam_update": CUSTOM(lambda op: _opt_adam()),
    "rmsprop_update": CUSTOM(lambda op: _opt_rmsprop()),
    "rmspropalex_update": CUSTOM(lambda op: _opt_rmspropalex()),
}


def _nan_reduce(symf, npf):
    x = _u(0.5, 1.5, (3, 4), 5)
    x[0, 1] = np.nan
    x[2, 2] = np.nan
    return symf(mx.sym.Variable("x"), axis=(1,)), {"x": x}, [npf(x, axis=1)]


def _np_softmax(x):
    e = np.exp(x - x.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def _weighted(sym_out, expect_raw):
    """Multiply an op's output by a second variable so the sum objective of
    the gradient checker is non-degenerate, keeping the forward checkable."""
    w = _u(0.5, 1.5, (3, 4), 99)
    s = mx.sym.elemwise_mul(sym_out, mx.sym.Variable("wgt"))
    return s, {"x": _u(-2, 2, (3, 4), 18), "wgt": w}, [expect_raw * w]


def _instance_norm_spec(eps=1e-3):
    x = _u(-1, 1, (2, 3, 4, 4), 32)
    g = _u(0.5, 1.5, (3,), 33)
    b = _u(-0.2, 0.2, (3,), 34)
    m = x.mean(axis=(2, 3), keepdims=True)
    v = x.var(axis=(2, 3), keepdims=True)
    want = ((x - m) / np.sqrt(v + eps) * g[None, :, None, None]
            + b[None, :, None, None])
    # weight the output (as in _weighted): the plain sum objective is
    # degenerate for a normalizer (sum of out == sum of beta, grad wrt x ~ 0)
    w = _u(0.5, 1.5, (2, 3, 4, 4), 35)
    s = mx.sym.InstanceNorm(mx.sym.Variable("x"), mx.sym.Variable("g"),
                            mx.sym.Variable("b"), eps=eps)
    s = mx.sym.elemwise_mul(s, mx.sym.Variable("wgt"))
    return s, {"x": x, "g": g, "b": b, "wgt": w}, [want * w]


# -------------------------------------------------- optimizer-update oracles
def _opt_arrays():
    w = _u(-1, 1, (3, 4), 20)
    g = _u(-1, 1, (3, 4), 21)
    return w, g


def _clip(g, c):
    return np.clip(g, -c, c) if c > 0 else g


def _opt_sgd(lr=0.1, wd=0.01, rescale=2.0, clip=0.5):
    w, g = _opt_arrays()
    gp = _clip(g * rescale, clip)
    want = w - lr * (gp + wd * w)
    s = mx.sym.sgd_update(mx.sym.Variable("w"), mx.sym.Variable("g"),
                          lr=lr, wd=wd, rescale_grad=rescale,
                          clip_gradient=clip)
    return s, {"w": w, "g": g}, [want]


def _opt_sgd_mom(lr=0.1, wd=0.01, mom=0.9):
    w, g = _opt_arrays()
    m = _u(-0.1, 0.1, (3, 4), 22)
    new_m = mom * m - lr * (g + wd * w)
    s = mx.sym.sgd_mom_update(mx.sym.Variable("w"), mx.sym.Variable("g"),
                              mx.sym.Variable("m"), lr=lr, wd=wd,
                              momentum=mom)
    return s, {"w": w, "g": g, "m": m}, [w + new_m, new_m]


def _opt_adam(lr=0.01, wd=0.01, b1=0.9, b2=0.999, eps=1e-8):
    w, g = _opt_arrays()
    m = _u(-0.1, 0.1, (3, 4), 23)
    v = _u(0.0, 0.1, (3, 4), 24)
    gp = g + wd * w
    nm = b1 * m + (1 - b1) * gp
    nv = b2 * v + (1 - b2) * gp ** 2
    want_w = w - lr * nm / (np.sqrt(nv) + eps)
    s = mx.sym.adam_update(mx.sym.Variable("w"), mx.sym.Variable("g"),
                           mx.sym.Variable("m"), mx.sym.Variable("v"),
                           lr=lr, wd=wd, beta1=b1, beta2=b2, epsilon=eps)
    return s, {"w": w, "g": g, "m": m, "v": v}, [want_w, nm, nv]


def _opt_rmsprop(lr=0.01, wd=0.0, g1=0.95, eps=1e-8):
    w, g = _opt_arrays()
    n = _u(0.0, 0.1, (3, 4), 25)
    gp = g + wd * w
    nn = g1 * n + (1 - g1) * gp ** 2
    want_w = w - lr * gp / np.sqrt(nn + eps)
    s = mx.sym.rmsprop_update(mx.sym.Variable("w"), mx.sym.Variable("g"),
                              mx.sym.Variable("n"), lr=lr, wd=wd, gamma1=g1,
                              epsilon=eps)
    return s, {"w": w, "g": g, "n": n}, [want_w, nn]


def _opt_rmspropalex(lr=0.01, g1=0.95, g2=0.9, eps=1e-8):
    w, g = _opt_arrays()
    n = _u(0.5, 1.0, (3, 4), 26)
    gs = _u(-0.1, 0.1, (3, 4), 27)
    d = _u(-0.1, 0.1, (3, 4), 28)
    nn = g1 * n + (1 - g1) * g ** 2
    ng = g1 * gs + (1 - g1) * g
    nd = g2 * d - lr * g / np.sqrt(nn - ng ** 2 + eps)
    s = mx.sym.rmspropalex_update(
        mx.sym.Variable("w"), mx.sym.Variable("g"), mx.sym.Variable("n"),
        mx.sym.Variable("gs"), mx.sym.Variable("d"), lr=lr, gamma1=g1,
        gamma2=g2, epsilon=eps)
    return s, {"w": w, "g": g, "n": n, "gs": gs, "d": d}, [w + nd, nn, ng, nd]


# ------------------------------------------------------------ forward + grad
def _built(spec, opname):
    out = spec.build(opname)
    sym, loc, expect = out[:3]
    aux = out[3] if len(out) > 3 else None
    return sym, loc, expect, aux


@pytest.mark.parametrize("opname", sorted(SPECS))
def test_forward(opname):
    spec = SPECS[opname]
    sym, loc, expect, aux = _built(spec, opname)
    if sym is None:  # creation op: imperative call with attrs
        out = getattr(mx.nd, opname)(**loc)
        np.testing.assert_allclose(out.asnumpy(), expect[0],
                                   rtol=spec.rtol, atol=spec.atol)
        return
    test_utils.check_symbolic_forward(sym, loc, expect, aux_states=aux,
                                      check_eps=max(spec.rtol, 1e-4))


@pytest.mark.parametrize(
    "opname", sorted(n for n, s in SPECS.items() if s.grad))
def test_gradient(opname):
    spec = SPECS[opname]
    sym, loc, _, aux = _built(spec, opname)
    test_utils.check_numeric_gradient(sym, loc, aux_states=aux,
                                      check_eps=spec.grad_eps)


# ------------------------------------------------------------ sampler moments
_MOMENTS = {
    # op -> (attrs, mean, var)
    "random_uniform": ({"low": -1.0, "high": 3.0}, 1.0, 16.0 / 12),
    "random_normal": ({"loc": 2.0, "scale": 1.5}, 2.0, 2.25),
    "random_exponential": ({"lam": 2.0}, 0.5, 0.25),
    "random_gamma": ({"alpha": 3.0, "beta": 2.0}, 6.0, 12.0),
    "random_poisson": ({"lam": 4.0}, 4.0, 4.0),
    "random_negative_binomial": ({"k": 3, "p": 0.4}, 4.5, 11.25),
    # GNB(mu, alpha): mean mu, var mu + alpha mu^2
    "random_generalized_negative_binomial":
        ({"mu": 2.0, "alpha": 0.5}, 2.0, 4.0),
}


@pytest.mark.parametrize("opname", sorted(_MOMENTS))
def test_sampler_moments(opname):
    attrs, want_mean, want_var = _MOMENTS[opname]
    mx.random.seed(42)
    x = getattr(mx.nd, opname)(shape=(200000,), **attrs).asnumpy()
    assert abs(x.mean() - want_mean) < 0.05 * max(1.0, abs(want_mean)), (
        x.mean(), want_mean)
    assert abs(x.var() - want_var) < 0.08 * max(1.0, want_var), (
        x.var(), want_var)


_MULTI = {
    # sample_* take per-row parameter ARRAYS -> (n, shape) draws per row
    "sample_uniform": ({"low": [0.0, 2.0], "high": [1.0, 6.0]},
                       [0.5, 4.0], [1.0 / 12, 16.0 / 12]),
    "sample_normal": ({"mu": [0.0, 3.0], "sigma": [1.0, 2.0]},
                      [0.0, 3.0], [1.0, 4.0]),
    "sample_exponential": ({"lam": [1.0, 4.0]}, [1.0, 0.25], [1.0, 1.0 / 16]),
    "sample_gamma": ({"alpha": [2.0, 5.0], "beta": [1.0, 0.5]},
                     [2.0, 2.5], [2.0, 1.25]),
    "sample_poisson": ({"lam": [2.0, 6.0]}, [2.0, 6.0], [2.0, 6.0]),
    "sample_negative_binomial": ({"k": [2.0, 5.0], "p": [0.5, 0.4]},
                                 [2.0, 7.5], [4.0, 18.75]),
    "sample_generalized_negative_binomial":
        ({"mu": [2.0, 3.0], "alpha": [0.25, 0.5]},
         [2.0, 3.0], [3.0, 7.5]),
}


@pytest.mark.parametrize("opname", sorted(_MULTI))
def test_multisample_moments(opname):
    attrs, want_mean, want_var = _MULTI[opname]
    mx.random.seed(7)
    ins = {k: mx.nd.array(np.asarray(v, "f")) for k, v in attrs.items()}
    x = getattr(mx.nd, opname)(shape=(100000,), **ins).asnumpy()
    assert x.shape == (2, 100000)
    for row in range(2):
        m, v = x[row].mean(), x[row].var()
        assert abs(m - want_mean[row]) < 0.08 * max(1.0, abs(want_mean[row])), (
            opname, row, m, want_mean[row])
        assert abs(v - want_var[row]) < 0.12 * max(1.0, want_var[row]), (
            opname, row, v, want_var[row])


# ------------------------------------------------------------- coverage meta
# Every registered op must be swept above OR carry an explicit pointer to
# the dedicated suite that exercises it. Pointers are validated: the file
# must exist and mention the op.
EXEMPT = {
    "_graph_const": "tests/test_graph_rewrite.py",
    "Activation": "tests/test_operator.py",
    "BatchNorm": "tests/test_operator.py",
    "BilinearSampler": "tests/test_vision.py",
    "Convolution": "tests/test_operator.py",
    "Correlation": "tests/test_vision.py",
    "Crop": "tests/test_vision.py",
    "Custom": "tests/test_custom_op.py",
    "Deconvolution": "tests/test_operator.py",
    "Dropout": "tests/test_operator.py",
    "FullyConnected": "tests/test_operator.py",
    "GridGenerator": "tests/test_vision.py",
    "L2Normalization": "tests/test_operator.py",
    "LRN": "tests/test_operator.py",
    "LeakyReLU": "tests/test_operator.py",
    "LinearRegressionOutput": "tests/test_gradients.py",
    "LogisticRegressionOutput": "tests/test_gradients.py",
    "MAERegressionOutput": "tests/test_gradients.py",
    "MakeLoss": "tests/test_gradients.py",
    "Pooling": "tests/test_operator.py",
    "RNN": "tests/test_rnn.py",
    "ROIPooling": "tests/test_vision.py",
    "SVMOutput": "tests/test_gradients.py",
    "SequenceLast": "tests/test_operator.py",
    "SequenceMask": "tests/test_operator.py",
    "SequenceReverse": "tests/test_operator.py",
    "SoftmaxOutput": "tests/test_operator.py",
    "SpatialTransformer": "tests/test_vision.py",
    "WarpCTC": "tests/test_ctc.py",
    "_contrib_MultiBoxDetection": "tests/test_vision.py",
    "_contrib_MultiBoxPrior": "tests/test_vision.py",
    "_contrib_GatedShortConv": "tests/test_lfm2_moe_block.py",
    "_contrib_GatedShortConvStep": "tests/test_lfm2_moe_block.py",
    "_contrib_KVPageMask": "tests/test_kv_pool_ops.py",
    "_contrib_KVPoolAttention": "tests/test_kv_pool_ops.py",
    "_contrib_KVRingAttention": "tests/test_mimo_v2_flash_block.py",
    "_contrib_KVRingWrite": "tests/test_mimo_v2_flash_block.py",
    "_contrib_SparseIndexSelect": "tests/test_dots3_note_block.py",
    "_contrib_KVPoolSlotWrite": "tests/test_kv_pool_ops.py",
    "_contrib_KVPoolWrite": "tests/test_kv_pool_ops.py",
    "_contrib_Mamba1Scan": "tests/test_phi4flash_block.py",
    "_contrib_Mamba1Step": "tests/test_phi4flash_block.py",
    "_contrib_Mamba2Scan": "tests/test_granite_hybrid_block.py",
    "_contrib_Mamba2Step": "tests/test_granite_hybrid_block.py",
    "_contrib_MoEFeedForward": "tests/test_olmoe_block.py",
    "_contrib_MultiBoxTarget": "tests/test_vision.py",
    "_contrib_MultiHeadAttention": "tests/test_attention.py",
    "_contrib_Proposal": "tests/test_vision.py",
    "_contrib_RMSNorm": "tests/test_olmoe_block.py",
    "_contrib_RotaryEmbedding": "tests/test_olmoe_block.py",
    "_contrib_count_sketch": "tests/test_vision.py",
    "_contrib_fft": "tests/test_vision.py",
    "_contrib_ifft": "tests/test_vision.py",
}

_ROOT = __file__.rsplit("/", 2)[0]


def test_every_registered_op_is_covered():
    import os

    missing, stale = [], []
    for op in registry.list_ops():
        if op in SPECS or op in _MOMENTS or op in _MULTI:
            continue
        ref = EXEMPT.get(op)
        if ref is None:
            missing.append(op)
            continue
        path = os.path.join(_ROOT, ref)
        with open(path) as f:
            src = f.read()
        variants = {op, op.lstrip("_"), op.replace("_contrib_", "")}
        if not any(v in src for v in variants):
            stale.append((op, ref))
    assert not missing, (
        "registered ops with no sweep spec and no EXEMPT pointer: %s"
        % missing)
    assert not stale, "EXEMPT pointers that do not mention the op: %s" % stale


# gather-family gradients: differentiable w.r.t. the DATA argument only
# (indices have no tangent space) — check_numeric_gradient restricted via
# grad_nodes so finite differences never perturb the integer inputs.
_GATHER_GRADS = {
    "take": (lambda: (mx.sym.take(mx.sym.Variable("a"), mx.sym.Variable("i")),
                      {"a": _u(-1, 1, (5, 3), 13),
                       "i": np.array([0., 2., 4.], "f")}), ["a"]),
    "batch_take": (lambda: (mx.sym.batch_take(mx.sym.Variable("a"),
                                              mx.sym.Variable("i")),
                            {"a": _u(-1, 1, (3, 4), 13),
                             "i": np.array([0., 3., 1.], "f")}), ["a"]),
    "pick": (lambda: (mx.sym.pick(mx.sym.Variable("a"),
                                  mx.sym.Variable("i"), axis=1),
                      {"a": _u(-1, 1, (3, 4), 14),
                       "i": np.array([1., 0., 3.], "f")}), ["a"]),
    "Embedding": (lambda: (mx.sym.Embedding(mx.sym.Variable("i"),
                                            mx.sym.Variable("w"),
                                            input_dim=5, output_dim=3),
                           {"i": np.array([1., 4., 0.], "f"),
                            "w": _u(-1, 1, (5, 3), 15)}), ["w"]),
}


@pytest.mark.parametrize("opname", sorted(_GATHER_GRADS))
def test_gather_gradients(opname):
    build, grad_nodes = _GATHER_GRADS[opname]
    sym, loc = build()
    test_utils.check_numeric_gradient(sym, loc, grad_nodes=grad_nodes)
