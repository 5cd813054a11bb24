"""Matrix / shape-manipulation / indexing / ordering / init ops.

Covers the reference's src/operator/tensor/{matrix_op,indexing_op,init_op,
ordering_op,control_flow_op}.* plus the legacy Concat/SliceChannel/SwapAxis/Pad
layers. ``dot`` maps straight to jnp.dot/einsum — i.e. the MXU — and is the
single most performance-critical lowering in the framework.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError
from .registry import AttrSpec, register

_B2 = ("lhs", "rhs")


@register(
    "dot",
    attrs={
        "transpose_a": AttrSpec("bool", default=False),
        "transpose_b": AttrSpec("bool", default=False),
    },
    input_names=_B2,
)
def _dot(attrs, lhs, rhs):
    """Matrix/tensor product (reference: matrix_op.cc dot). 2D×2D → MXU matmul;
    higher-rank follows the reference's "last axis of lhs, first of rhs" rule."""
    if attrs["transpose_a"]:
        lhs = jnp.moveaxis(lhs, 0, -1) if lhs.ndim > 2 else lhs.T
    if attrs["transpose_b"]:
        rhs = jnp.moveaxis(rhs, -1, 0) if rhs.ndim > 2 else rhs.T
    if lhs.ndim == 1 and rhs.ndim == 1:
        return jnp.dot(lhs, rhs)
    return jnp.tensordot(lhs, rhs, axes=([lhs.ndim - 1], [0]))


@register(
    "batch_dot",
    attrs={
        "transpose_a": AttrSpec("bool", default=False),
        "transpose_b": AttrSpec("bool", default=False),
    },
    input_names=_B2,
)
def _batch_dot(attrs, lhs, rhs):
    """Batched matmul (reference: matrix_op.cc batch_dot)."""
    if attrs["transpose_a"]:
        lhs = jnp.swapaxes(lhs, -1, -2)
    if attrs["transpose_b"]:
        rhs = jnp.swapaxes(rhs, -1, -2)
    return jnp.matmul(lhs, rhs)


@register("transpose", attrs={"axes": AttrSpec("shape", default=())})
def _transpose(attrs, data):
    axes = attrs["axes"] or None
    return jnp.transpose(data, axes)


def _reshape_target(shape_spec, in_shape):
    """MXNet Reshape shape-code semantics: 0 copy, -1 infer, -2 copy rest,
    -3 merge two, -4 split (reference: matrix_op-inl.h ReshapeParam)."""
    out = []
    i = 0  # index into in_shape
    j = 0
    spec = list(shape_spec)
    while j < len(spec):
        s = spec[j]
        if s == 0:
            out.append(in_shape[i])
            i += 1
        elif s == -1:
            out.append(-1)
            i += 1
        elif s == -2:
            out.extend(in_shape[i:])
            i = len(in_shape)
        elif s == -3:
            out.append(in_shape[i] * in_shape[i + 1])
            i += 2
        elif s == -4:
            a, b = spec[j + 1], spec[j + 2]
            if a == -1:
                a = in_shape[i] // b
            if b == -1:
                b = in_shape[i] // a
            out.extend([a, b])
            i += 1
            j += 2
        else:
            out.append(s)
            i += 1
        j += 1
    if out.count(-1) > 1:
        raise MXNetError("Reshape: at most one -1 allowed")
    return tuple(out)


@register(
    "Reshape",
    attrs={
        "shape": AttrSpec("shape", default=()),
        "target_shape": AttrSpec("shape", default=()),
        "keep_highest": AttrSpec("bool", default=False),
        "reverse": AttrSpec("bool", default=False),
    },
    aliases=("reshape",),
)
def _reshape(attrs, data):
    spec = attrs["shape"] or attrs["target_shape"]
    if attrs.get("reverse"):
        tgt = _reshape_target(tuple(reversed(spec)), tuple(reversed(data.shape)))
        tgt = tuple(reversed(tgt))
    else:
        tgt = _reshape_target(spec, data.shape)
    return jnp.reshape(data, tgt)


@register("Flatten", aliases=("flatten",))
def _flatten(attrs, data):
    return jnp.reshape(data, (data.shape[0], -1))


@register("expand_dims", attrs={"axis": AttrSpec("int", required=True)})
def _expand_dims(attrs, data):
    return jnp.expand_dims(data, attrs["axis"])


@register(
    "slice",
    attrs={
        "begin": AttrSpec("shape", required=True),
        "end": AttrSpec("shape", required=True),
    },
    aliases=("crop",),
)
def _slice(attrs, data):
    idx = tuple(slice(b, e) for b, e in zip(attrs["begin"], attrs["end"]))
    return data[idx]


@register(
    "slice_axis",
    attrs={
        "axis": AttrSpec("int", required=True),
        "begin": AttrSpec("int", default=0),
        "end": AttrSpec("any", default=None),
    },
)
def _slice_axis(attrs, data):
    ax = attrs["axis"] % data.ndim
    end = attrs["end"]
    end = None if end in (None, "None") else int(end)
    idx = [slice(None)] * data.ndim
    idx[ax] = slice(attrs["begin"], end)
    return data[tuple(idx)]


@register(
    "repeat",
    attrs={"repeats": AttrSpec("int", required=True), "axis": AttrSpec("any", default=None)},
)
def _repeat(attrs, data):
    ax = attrs["axis"]
    ax = None if ax in (None, "None") else int(ax)
    return jnp.repeat(data, attrs["repeats"], axis=ax)


@register("tile", attrs={"reps": AttrSpec("shape", required=True)})
def _tile(attrs, data):
    return jnp.tile(data, attrs["reps"])


@register("reverse", attrs={"axis": AttrSpec("shape", required=True)}, aliases=("flip",))
def _reverse(attrs, data):
    return jnp.flip(data, axis=attrs["axis"])


@register(
    "SwapAxis",
    attrs={"dim1": AttrSpec("int", default=0), "dim2": AttrSpec("int", default=0)},
    aliases=("swapaxes",),
)
def _swapaxis(attrs, data):
    return jnp.swapaxes(data, attrs["dim1"], attrs["dim2"])


def _n_args_names(attrs):
    n = int(attrs.get("num_args", 1))
    return ["arg%d" % i for i in range(n)]


@register(
    "Concat",
    attrs={"num_args": AttrSpec("int", required=True), "dim": AttrSpec("int", default=1)},
    input_names=_n_args_names,
    aliases=("concat",),
)
def _concat(attrs, *args):
    """Concatenate along dim (reference: src/operator/concat.cc)."""
    return jnp.concatenate(args, axis=attrs["dim"])


@register(
    "SliceChannel",
    attrs={
        "num_outputs": AttrSpec("int", required=True),
        "axis": AttrSpec("int", default=1),
        "squeeze_axis": AttrSpec("bool", default=False),
    },
    num_outputs=lambda attrs: int(attrs["num_outputs"]),
    aliases=("split",),
)
def _slice_channel(attrs, data):
    """Split into equal parts along axis (reference: src/operator/slice_channel.cc)."""
    parts = jnp.split(data, attrs["num_outputs"], axis=attrs["axis"])
    if attrs["squeeze_axis"]:
        parts = [jnp.squeeze(p, axis=attrs["axis"]) for p in parts]
    return tuple(parts)


@register(
    "Embedding",
    attrs={
        "input_dim": AttrSpec("int", required=True),
        "output_dim": AttrSpec("int", required=True),
        "dtype": AttrSpec("dtype", default=np.float32),
        # reference: Embedding(..., sparse_grad=True) marks the weight for a
        # row-sparse gradient (docs/SPARSE.md). The forward is identical;
        # the flag is metadata the sparse KVStore glue and the GL4xx
        # sharding lint read (sparse.sparse_param_names).
        "sparse_grad": AttrSpec("bool", default=False),
    },
    input_names=("data", "weight"),
)
def _embedding(attrs, data, weight):
    """Lookup-table embedding (reference: indexing_op.cc Embedding). XLA lowers
    this gather to a one-hot matmul on the MXU for small vocabularies."""
    return jnp.take(weight, data.astype(jnp.int32), axis=0)


@register(
    "SparseEmbedding",
    attrs={
        "input_dim": AttrSpec("int", required=True),
        "output_dim": AttrSpec("int", required=True),
        "dtype": AttrSpec("dtype", default=np.float32),
    },
    input_names=("data", "weight"),
    aliases=("row_sparse_embedding",),
)
def _sparse_embedding(attrs, data, weight):
    """Embedding whose weight gradient is row-sparse by contract
    (reference: contrib.SparseEmbedding over kRowSparseStorage): the
    backward is a segment-sum over the batch's unique ids
    (``sparse.embedding_backward``) — the (vocab, dim) dense gradient is
    never materialized, and only touched rows reach the optimizer/wire.
    Forward is the same gather; the distinct op name carries the
    ``row_sparse_embedding`` shard-rule category (ops/infer_meta.py) so the
    sharding lint and autoplan price its vocab-sharded placement."""
    return jnp.take(weight, data.astype(jnp.int32), axis=0)


@register(
    "take",
    attrs={
        "axis": AttrSpec("int", default=0),
        "mode": AttrSpec("str", default="clip"),
    },
    input_names=("a", "indices"),
)
def _take(attrs, a, indices):
    mode = attrs["mode"]
    idx = indices.astype(jnp.int32)
    return jnp.take(a, idx, axis=attrs["axis"], mode="wrap" if mode == "wrap" else "clip")


@register("batch_take", input_names=("a", "indices"))
def _batch_take(attrs, a, indices):
    """out[i] = a[i, indices[i]], the index clipped into a's second axis
    (reference: indexing_op.cc batch_take, which clamps it). Rows of a matrix
    there; here a (B, T, ...) takes out[i] = a[i, indices[i], ...] too."""
    idx = indices.astype(jnp.int32).reshape((-1,) + (1,) * (a.ndim - 1))
    return jnp.take_along_axis(a, idx, axis=1, mode="clip")[:, 0]


@register(
    "one_hot",
    attrs={
        "depth": AttrSpec("int", required=True),
        "on_value": AttrSpec("float", default=1.0),
        "off_value": AttrSpec("float", default=0.0),
        "dtype": AttrSpec("dtype", default=np.float32),
    },
    input_names=("indices",),
)
def _one_hot(attrs, indices):
    hot = jax.nn.one_hot(indices.astype(jnp.int32), attrs["depth"], dtype=attrs["dtype"])
    return hot * (attrs["on_value"] - attrs["off_value"]) + attrs["off_value"]


@register("where", input_names=("condition", "x", "y"))
def _where(attrs, condition, x, y):
    """Elementwise/row select (reference: control_flow_op.cc where)."""
    if condition.ndim == 1 and x.ndim > 1:
        condition = condition.reshape((-1,) + (1,) * (x.ndim - 1))
    return jnp.where(condition != 0, x, y)


@register("pick", attrs={"axis": AttrSpec("int", default=1), "keepdims": AttrSpec("bool", default=False)}, input_names=("data", "index"))
def _pick(attrs, data, index):
    ax = attrs["axis"] % data.ndim
    idx = jnp.expand_dims(index.astype(jnp.int32), ax)
    out = jnp.take_along_axis(data, idx, axis=ax)
    if not attrs["keepdims"]:
        out = jnp.squeeze(out, axis=ax)
    return out


# --- ordering (reference: tensor/ordering_op*.cc; cub/thrust → XLA sort) ------
_TOPK_ATTRS = lambda: {
    "axis": AttrSpec("any", default=-1),
    "k": AttrSpec("int", default=1),
    "ret_typ": AttrSpec("str", default="indices"),
    "is_ascend": AttrSpec("bool", default=False),
}


@register("topk", attrs=_TOPK_ATTRS(), num_outputs=lambda a: 2 if a.get("ret_typ") == "both" else 1)
def _topk(attrs, data):
    ax = attrs["axis"]
    ax = data.ndim - 1 if ax in (None, "None") else int(ax) % data.ndim
    k = attrs["k"]
    vals = data if not attrs["is_ascend"] else -data
    moved = jnp.moveaxis(vals, ax, -1)
    top_vals, raw_idx = jax.lax.top_k(moved, k)
    if attrs["is_ascend"]:
        top_vals = -top_vals
    top_vals = jnp.moveaxis(top_vals, -1, ax)
    top_idx = jnp.moveaxis(raw_idx, -1, ax).astype(jnp.float32)
    rt = attrs["ret_typ"]
    if rt == "value":
        return top_vals
    if rt == "both":
        return top_vals, top_idx
    if rt == "mask":
        # 0/1 mask with ones at top-k positions (reference: ordering_op kRetMask)
        onehot = jax.nn.one_hot(raw_idx, moved.shape[-1], dtype=data.dtype)
        mask = jnp.clip(jnp.sum(onehot, axis=-2), 0, 1)
        return jnp.moveaxis(mask, -1, ax)
    if rt != "indices":
        raise MXNetError("topk: unsupported ret_typ %r" % rt)
    return top_idx


@register("sort", attrs={"axis": AttrSpec("any", default=-1), "is_ascend": AttrSpec("bool", default=True)})
def _sort(attrs, data):
    ax = attrs["axis"]
    if ax in (None, "None"):
        data, ax = data.reshape(-1), 0
    out = jnp.sort(data, axis=int(ax))
    return out if attrs["is_ascend"] else jnp.flip(out, axis=int(ax))


@register("argsort", attrs={"axis": AttrSpec("any", default=-1), "is_ascend": AttrSpec("bool", default=True)})
def _argsort(attrs, data):
    ax = attrs["axis"]
    if ax in (None, "None"):
        data, ax = data.reshape(-1), 0
    out = jnp.argsort(data, axis=int(ax))
    if not attrs["is_ascend"]:
        out = jnp.flip(out, axis=int(ax))
    return out.astype(jnp.float32)


# --- init ops (reference: tensor/init_op.cc) ----------------------------------
@register(
    "_zeros",
    attrs={"shape": AttrSpec("shape", default=()), "dtype": AttrSpec("dtype", default=np.float32)},
    input_names=(),
)
def _zeros(attrs):
    return jnp.zeros(attrs["shape"], dtype=attrs["dtype"])


@register(
    "_ones",
    attrs={"shape": AttrSpec("shape", default=()), "dtype": AttrSpec("dtype", default=np.float32)},
    input_names=(),
)
def _ones(attrs):
    return jnp.ones(attrs["shape"], dtype=attrs["dtype"])


@register(
    "_full",
    attrs={
        "shape": AttrSpec("shape", default=()),
        "dtype": AttrSpec("dtype", default=np.float32),
        "value": AttrSpec("float", default=0.0),
    },
    input_names=(),
)
def _full(attrs):
    return jnp.full(attrs["shape"], attrs["value"], dtype=attrs["dtype"])


@register(
    "_arange",
    attrs={
        "start": AttrSpec("float", default=0.0),
        "stop": AttrSpec("any", default=None),
        "step": AttrSpec("float", default=1.0),
        "repeat": AttrSpec("int", default=1),
        "dtype": AttrSpec("dtype", default=np.float32),
    },
    input_names=(),
)
def _arange(attrs):
    stop = attrs["stop"]
    stop = None if stop in (None, "None") else float(stop)
    out = jnp.arange(attrs["start"], stop, attrs["step"], dtype=attrs["dtype"])
    if attrs["repeat"] > 1:
        out = jnp.repeat(out, attrs["repeat"])
    return out


@register(
    "_graph_const",
    attrs={
        # raw little-endian bytes of the folded value — bytes are hashable,
        # so the node freezes cleanly into symbol._eval_node_shape's cache
        # key (an ndarray attr would not)
        "data": AttrSpec("any", required=True),
        "shape": AttrSpec("shape", default=()),
        "dtype": AttrSpec("dtype", default=np.float32),
    },
    input_names=(),
)
def _graph_const(attrs):
    """A constant materialized by the graph-rewrite constant-folding pass
    (analysis/rewrite.py): the one-time host-side evaluation of a subgraph
    whose leaves were all init ops. Never written by frontends directly."""
    arr = np.frombuffer(attrs["data"], dtype=attrs["dtype"])
    return jnp.asarray(arr.reshape(attrs["shape"]))


@register("zeros_like")
def _zeros_like(attrs, data):
    return jnp.zeros_like(data)


@register("ones_like")
def _ones_like(attrs, data):
    return jnp.ones_like(data)


@register(
    "Pad",
    attrs={
        "mode": AttrSpec("str", default="constant"),
        "pad_width": AttrSpec("shape", required=True),
        "constant_value": AttrSpec("float", default=0.0),
    },
    aliases=("pad",),
)
def _pad(attrs, data):
    """N-D padding (reference: src/operator/pad.cc)."""
    pw = attrs["pad_width"]
    pads = [(pw[2 * i], pw[2 * i + 1]) for i in range(data.ndim)]
    mode = attrs["mode"]
    if mode == "constant":
        return jnp.pad(data, pads, mode="constant", constant_values=attrs["constant_value"])
    return jnp.pad(data, pads, mode="edge" if mode == "edge" else "reflect")
