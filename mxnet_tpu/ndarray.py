"""NDArray: the imperative tensor API.

TPU-native re-design of the reference's NDArray stack (include/mxnet/ndarray.h:58-445,
src/ndarray/ndarray.cc, python/mxnet/ndarray.py). The reference schedules every
mutation through the ThreadedEngine with read/write var sets; here JAX's async
dispatch plays that role — ops return immediately with futures-backed
jax.Arrays, and ``wait_to_read`` maps to ``block_until_ready`` (SURVEY.md §3.4
TPU mapping). Views (Slice/At/Reshape, ndarray.h:284-352) share a ``_Chunk``
and write through it, reproducing the reference's offset-view semantics in a
functional world.

Every registered operator is exposed as a module-level function (the
reference's autogenerated ``_init_ndarray_module``); eager calls run through a
cached ``jax.jit`` per (op, attrs) so imperative chains still fuse.
"""
from __future__ import annotations

import builtins
import functools
import struct
import sys
from typing import List, Optional

import numpy as np

from .base import MXNetError, dtype_code, dtype_from_code, np_dtype
from .context import Context, current_context
from .ops import registry as _registry
from .ops.registry import get_op, parse_attrs

__all__ = [
    "NDArray",
    "array",
    "zeros",
    "ones",
    "full",
    "empty",
    "arange",
    "concatenate",
    "save",
    "load",
    "waitall",
    "imperative_invoke",
]

_MAGIC_LIST = 0x112  # reference: kMXAPINDArrayListMagic (src/c_api/c_api.cc)


class _Chunk:
    """Backing buffer shared by views (reference: NDArray::Chunk, ndarray.h:374)."""

    __slots__ = ("data", "ctx", "__weakref__")

    def __init__(self, data, ctx: Context):
        self.data = data
        self.ctx = ctx
        _LIVE_CHUNKS.add(self)


import weakref

_LIVE_CHUNKS: "weakref.WeakSet[_Chunk]" = weakref.WeakSet()


def _to_jax(value, ctx: Context, dtype=None):
    import jax

    if isinstance(value, NDArray):
        arr = value._jax()
        if dtype is not None and arr.dtype != np_dtype(dtype):
            arr = arr.astype(np_dtype(dtype))
        return jax.device_put(arr, ctx.jax_device)
    if isinstance(value, jax.Array):
        # keep device-resident values on device — no host round-trip
        if dtype is not None and value.dtype != np_dtype(dtype):
            value = value.astype(np_dtype(dtype))
        if value.dtype == np.float64 and dtype is None:
            value = value.astype(np.float32)
        return jax.device_put(value, ctx.jax_device)
    arr = np.asarray(value, dtype=np_dtype(dtype) if dtype is not None else None)
    if arr.dtype == np.float64 and dtype is None:
        arr = arr.astype(np.float32)  # framework default, like the reference
    return jax.device_put(arr, ctx.jax_device)


class NDArray:
    __slots__ = ("_chunk", "_begin", "_end", "_shape", "_writable")

    def __init__(self, data=None, ctx=None, *, chunk=None, begin=None, end=None, shape=None, writable=True):
        if chunk is not None:
            self._chunk = chunk
            self._begin = begin
            self._end = end
            self._shape = tuple(shape)
        else:
            ctx = ctx or current_context()
            jarr = _to_jax(data, ctx)
            self._chunk = _Chunk(jarr, ctx)
            self._begin = None
            self._end = None
            self._shape = tuple(jarr.shape)
        self._writable = writable

    # ------------------------------------------------------------------ core
    def _jax(self):
        d = self._chunk.data
        if self._begin is not None:
            d = d[self._begin : self._end]
        if tuple(d.shape) != self._shape:
            d = d.reshape(self._shape)
        return d

    def _set_jax(self, value):
        """Write through the chunk (view-aware). A ``jax.Array`` that is
        already the whole chunk's shape and (strong) type changes hands by
        reference (``jax.Array``s are immutable, so two chunks may name
        one): returns True then, False when the value was shaped, cast or
        blended in."""
        import jax
        import jax.numpy as jnp

        if not self._writable:
            raise MXNetError("trying to write to a read-only NDArray")
        d = self._chunk.data
        if self._begin is None:
            if (isinstance(value, jax.Array)
                    and value.shape == d.shape == self._shape
                    and value.dtype == d.dtype
                    and not value.aval.weak_type):
                self._chunk.data = value
                return True
            region_shape = d.shape
            new = jnp.broadcast_to(value.reshape(self._shape) if hasattr(value, "reshape") and tuple(getattr(value, "shape", ())) == self._shape else value, self._shape)
            self._chunk.data = jnp.asarray(new).reshape(region_shape).astype(d.dtype)
        else:
            region = d[self._begin : self._end]
            new = jnp.broadcast_to(value, self._shape).reshape(region.shape).astype(d.dtype)
            self._chunk.data = d.at[self._begin : self._end].set(new)
        return False

    # ------------------------------------------------------------- properties
    @property
    def shape(self):
        return self._shape

    @property
    def ndim(self):
        return len(self._shape)

    @property
    def size(self):
        return int(np.prod(self._shape)) if self._shape else 1

    @property
    def dtype(self):
        return np.dtype(self._chunk.data.dtype)

    @property
    def context(self) -> Context:
        return self._chunk.ctx

    ctx = context

    @property
    def writable(self):
        return self._writable

    def __repr__(self):
        return "<NDArray %s @%s>" % ("x".join(str(s) for s in self._shape), self.context)

    def __len__(self):
        if not self._shape:
            raise TypeError("len() of 0-d NDArray")
        return self._shape[0]

    # ------------------------------------------------------------- conversion
    def asnumpy(self) -> np.ndarray:
        return np.asarray(self._jax())

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("the array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def astype(self, dtype) -> "NDArray":
        return imperative_invoke("Cast", [self], {"dtype": np_dtype(dtype)})[0]

    def wait_to_read(self):
        self._chunk.data.block_until_ready()

    def wait_to_write(self):
        self._chunk.data.block_until_ready()

    # -------------------------------------------------------------- views
    def reshape(self, shape) -> "NDArray":
        if isinstance(shape, int):
            shape = (shape,)
        shape = tuple(shape)
        if -1 in shape:
            known = int(np.prod([s for s in shape if s != -1]))
            shape = tuple(self.size // builtins.max(known, 1) if s == -1 else s for s in shape)
        if int(np.prod(shape)) != self.size:
            raise MXNetError("reshape size mismatch %s -> %s" % (self._shape, shape))
        return NDArray(chunk=self._chunk, begin=self._begin, end=self._end, shape=shape, writable=self._writable)

    def slice(self, begin, end) -> "NDArray":
        """Axis-0 view sharing memory (reference: ndarray.h:284 Slice)."""
        n = self._shape[0]
        begin = int(begin) % n if begin < 0 else int(begin)
        end = n if end is None else (int(end) + n if end < 0 else int(end))
        if not (0 <= begin <= end <= n):
            raise MXNetError("invalid slice [%s, %s) for axis size %d" % (begin, end, n))
        if self._begin is None and self._shape == tuple(self._chunk.data.shape):
            b0 = begin
            e0 = end
        else:
            # view of a view: only valid when self spans whole rows of the chunk
            per_row = self.size // builtins.max(n, 1)
            base = self._begin or 0
            chunk_row = int(np.prod(self._chunk.data.shape[1:])) or 1
            if per_row % chunk_row != 0:
                raise MXNetError("unsupported nested view slicing")
            rows_per = per_row // chunk_row
            b0 = base + begin * rows_per
            e0 = base + end * rows_per
        return NDArray(
            chunk=self._chunk,
            begin=b0,
            end=e0,
            shape=(end - begin,) + self._shape[1:],
            writable=self._writable,
        )

    def at(self, idx) -> "NDArray":
        orig = idx = int(idx)
        n0 = self._shape[0] if self._shape else 0
        if idx < 0:
            idx += n0
        if not 0 <= idx < n0:
            # IndexError, not MXNetError: `for row in arr` probes increasing
            # indices and stops on IndexError (sequence protocol)
            raise IndexError("index %d out of bounds for axis of size %d"
                             % (orig, n0))
        v = self.slice(idx, idx + 1)
        return NDArray(chunk=v._chunk, begin=v._begin, end=v._end, shape=self._shape[1:], writable=self._writable)

    def __getitem__(self, key):
        if isinstance(key, int):
            return self.at(key)
        if isinstance(key, builtins.slice):
            if key.step is not None and key.step != 1:
                raise MXNetError("NDArray only supports step=1 slicing")
            start = 0 if key.start is None else key.start
            return self.slice(start, key.stop)
        # advanced indexing returns a copy (TPU-native extension)
        return NDArray(np.asarray(self._jax())[key], ctx=self.context)

    def __setitem__(self, key, value):
        import jax.numpy as jnp

        target = self
        if isinstance(key, int):
            target = self.at(key)
        elif isinstance(key, builtins.slice):
            if not (key.start is None and key.stop is None and key.step is None):
                start = 0 if key.start is None else key.start
                target = self.slice(start, key.stop)
        elif isinstance(key, tuple):
            # general multi-axis assignment via functional scatter on the view
            if isinstance(value, NDArray):
                val = value._jax()
            else:
                val = jnp.asarray(np.asarray(value), dtype=self.dtype)
            self._set_jax(self._jax().at[key].set(val))
            return
        if isinstance(value, NDArray):
            val = value._jax()
        elif isinstance(value, (int, float, np.generic)):
            val = jnp.full(target._shape, value, dtype=target.dtype)
        else:
            val = jnp.asarray(np.asarray(value), dtype=target.dtype)
        target._set_jax(val)

    # ------------------------------------------------------------- transfers
    def copyto(self, other):
        if isinstance(other, NDArray):
            if other is self or other._chunk is self._chunk:
                raise MXNetError("copyto: source and target are the same")
            other._set_jax(_to_jax(self._jax(), other.context, dtype=other.dtype))
            return other
        if isinstance(other, Context):
            return NDArray(self._jax(), ctx=other)
        raise TypeError("copyto: unsupported target %r" % (other,))

    def copy(self) -> "NDArray":
        return NDArray(self._jax(), ctx=self.context)

    def as_in_context(self, ctx: Context) -> "NDArray":
        if ctx == self.context:
            return self
        return self.copyto(ctx)

    # ------------------------------------------------------------- arithmetic
    def _binary(self, other, op, scalar_op, reverse=False):
        if isinstance(other, NDArray):
            a, b = (other, self) if reverse else (self, other)
            return imperative_invoke(op, [a, b], {})[0]
        return imperative_invoke(scalar_op, [self], {"scalar": float(other)})[0]

    def __add__(self, other):
        return self._binary(other, "broadcast_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, other):
        return self._binary(other, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, other):
        return self._binary(other, "broadcast_sub", "_rminus_scalar", reverse=True)

    def __mul__(self, other):
        return self._binary(other, "broadcast_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binary(other, "broadcast_div", "_div_scalar")

    def __rtruediv__(self, other):
        return self._binary(other, "broadcast_div", "_rdiv_scalar", reverse=True)

    __div__ = __truediv__
    __rdiv__ = __rtruediv__

    def __pow__(self, other):
        return self._binary(other, "broadcast_power", "_power_scalar")

    def __rpow__(self, other):
        return self._binary(other, "broadcast_power", "_rpower_scalar", reverse=True)

    def __mod__(self, other):
        return self._binary(other, "broadcast_mod", "_mod_scalar")

    def __neg__(self):
        return imperative_invoke("negative", [self], {})[0]

    def __eq__(self, other):
        return self._binary(other, "broadcast_equal", "_equal_scalar")

    def __ne__(self, other):
        return self._binary(other, "broadcast_not_equal", "_not_equal_scalar")

    def __gt__(self, other):
        return self._binary(other, "broadcast_greater", "_greater_scalar")

    def __ge__(self, other):
        return self._binary(other, "broadcast_greater_equal", "_greater_equal_scalar")

    def __lt__(self, other):
        return self._binary(other, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, other):
        return self._binary(other, "broadcast_lesser_equal", "_lesser_equal_scalar")

    __hash__ = object.__hash__

    def __iadd__(self, other):
        self._set_jax((self + other)._jax())
        return self

    def __isub__(self, other):
        self._set_jax((self - other)._jax())
        return self

    def __imul__(self, other):
        self._set_jax((self * other)._jax())
        return self

    def __itruediv__(self, other):
        self._set_jax((self / other)._jax())
        return self

    @property
    def T(self):
        return imperative_invoke("transpose", [self], {})[0]

    # numpy-style conveniences mapped onto registered ops
    def sum(self, axis=(), keepdims=False):
        return imperative_invoke("sum", [self], {"axis": axis, "keepdims": keepdims})[0]

    def mean(self, axis=(), keepdims=False):
        return imperative_invoke("mean", [self], {"axis": axis, "keepdims": keepdims})[0]

    def max(self, axis=(), keepdims=False):
        return imperative_invoke("max", [self], {"axis": axis, "keepdims": keepdims})[0]

    def min(self, axis=(), keepdims=False):
        return imperative_invoke("min", [self], {"axis": axis, "keepdims": keepdims})[0]


# ---------------------------------------------------------------- dispatch
@functools.lru_cache(maxsize=4096)
def _jitted_apply(op_name, attrs_key, is_train, with_rng, n_inputs, n_aux):
    """Build a jitted executor for one (op, attrs) signature. Imperative calls
    reuse it, so eager op chains still run as compiled XLA programs (the
    engine-bypass analogue of the reference's PushFCompute, c_api_ndarray.cc:233)."""
    import jax

    opdef = get_op(op_name)
    attrs = dict(attrs_key)

    def run(arrays, rng):
        inputs = arrays[:n_inputs]
        aux = arrays[n_inputs:]
        outs, new_aux = opdef.apply(attrs, inputs, aux=aux, is_train=is_train, rng=rng)
        return tuple(outs) + tuple(new_aux)

    return jax.jit(run)


def _freeze_attrs(attrs: dict):
    def freeze(v):
        if isinstance(v, (list, tuple)):
            return tuple(freeze(x) for x in v)
        if isinstance(v, np.dtype):
            return v.name if v.name != "bfloat16" else "bfloat16"
        return v

    return tuple(sorted((k, freeze(v)) for k, v in attrs.items()))


def imperative_invoke(op_name, inputs, attrs, out=None, ctx=None, is_train=True, rng=None) -> List[NDArray]:
    """Run an operator eagerly on NDArrays (reference: MXImperativeInvoke,
    src/c_api/c_api_ndarray.cc:322). Aux-state ops (BatchNorm) mutate their aux
    inputs in place like the reference's FMutateInputs contract."""
    opdef = get_op(op_name)
    attrs = parse_attrs(opdef, attrs)
    n_named = len(opdef.input_names(attrs))
    n_aux = len(opdef.aux_names(attrs))
    if ctx is None:
        ctx = inputs[0].context if inputs else (Context(attrs["ctx"]) if attrs.get("ctx") else current_context())

    jarrays = tuple(x._jax() for x in inputs)
    if opdef.needs_rng and rng is None:
        from . import random as _random

        rng = _random._next_key()
    fn = _jitted_apply(op_name, _freeze_attrs(attrs), bool(is_train), rng is not None, len(jarrays) - n_aux, n_aux)
    results = fn(jarrays, rng)
    if not jarrays:
        # 0-input ops (creation/sampling) land on the default device; pin to ctx
        import jax

        results = tuple(jax.device_put(r, ctx.jax_device) for r in results)
    n_out = opdef.num_outputs(attrs)
    outs = results[:n_out]
    new_aux = results[n_out:]
    # write aux updates back in place (FMutateInputs semantics)
    for arr, new in zip(inputs[len(inputs) - n_aux :], new_aux):
        arr._set_jax(new)
    out_nd = []
    if out is not None:
        targets = out if isinstance(out, (list, tuple)) else [out]
        for t, o in zip(targets, outs):
            t._set_jax(o)
            out_nd.append(t)
    else:
        for o in outs:
            out_nd.append(NDArray(chunk=_Chunk(o, ctx), shape=o.shape))
    from . import autograd as _ag

    if _ag.is_recording():
        # in_vals are the pre-call jax values, so out=/aux aliasing of inputs
        # can't corrupt the tape (reference: RecordImperativeFCompute,
        # src/ndarray/autograd.cc:73)
        _ag._record_op(op_name, attrs, list(inputs), list(jarrays), n_aux,
                       out_nd, rng, bool(is_train))
    return out_nd


def _make_op_function(op_name):
    opdef = get_op(op_name)

    def fn(*args, out=None, name=None, ctx=None, **kwargs):
        inputs = [a for a in args if isinstance(a, NDArray)]
        extra = [a for a in args if not isinstance(a, NDArray)]
        if extra:
            raise MXNetError("%s: positional args must be NDArrays; use kwargs for attrs" % op_name)
        # named tensor inputs may come via kwargs (data=..., weight=...)
        attrs = {}
        tmp_attrs = {k: v for k, v in kwargs.items() if not isinstance(v, NDArray)}
        probe = parse_attrs(opdef, tmp_attrs)
        names = opdef.input_names(probe) + opdef.aux_names(probe)
        named_inputs = {}
        for k, v in kwargs.items():
            if isinstance(v, NDArray):
                named_inputs[k] = v
            else:
                attrs[k] = v
        if named_inputs:
            for n in names:
                if n in named_inputs:
                    inputs.append(named_inputs.pop(n))
            if named_inputs:
                raise MXNetError("%s: unknown tensor inputs %s" % (op_name, list(named_inputs)))
        return _maybe_scalar_out(imperative_invoke(op_name, inputs, attrs, out=out, ctx=ctx))

    fn.__name__ = op_name
    fn.__doc__ = opdef.doc
    return fn


def _maybe_scalar_out(outs):
    return outs[0] if len(outs) == 1 else outs


def _init_ndarray_module():
    """Expose every registered op as a module-level function (reference:
    python/mxnet/ndarray.py _init_ndarray_module)."""
    mod = sys.modules[__name__]
    for name in list(_registry._REGISTRY.keys()):
        if not hasattr(mod, name):
            setattr(mod, name, _make_op_function(name))


# ---------------------------------------------------------------- creation
def array(source_array, ctx=None, dtype=None) -> NDArray:
    ctx = ctx or current_context()
    if isinstance(source_array, NDArray):
        source_array = source_array.asnumpy()
    if dtype is None:
        # match the reference: keep numpy array dtypes, default lists to float32
        dtype = source_array.dtype if isinstance(source_array, np.ndarray) else np.float32
        if np.dtype(dtype) == np.float64:
            dtype = np.float32
    arr = np.asarray(source_array)
    return NDArray(arr.astype(np_dtype(dtype)), ctx=ctx)


def empty(shape, ctx=None, dtype=np.float32) -> NDArray:
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=np.float32) -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    return imperative_invoke("_zeros", [], {"shape": shape, "dtype": np_dtype(dtype)}, ctx=ctx or current_context())[0]


def ones(shape, ctx=None, dtype=np.float32) -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    return imperative_invoke("_ones", [], {"shape": shape, "dtype": np_dtype(dtype)}, ctx=ctx or current_context())[0]


def full(shape, val, ctx=None, dtype=np.float32) -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    return imperative_invoke(
        "_full", [], {"shape": shape, "value": float(val), "dtype": np_dtype(dtype)}, ctx=ctx or current_context()
    )[0]


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=np.float32) -> NDArray:
    if stop is None:
        start, stop = 0.0, start
    return imperative_invoke(
        "_arange",
        [],
        {"start": float(start), "stop": float(stop), "step": float(step), "repeat": int(repeat), "dtype": np_dtype(dtype)},
        ctx=ctx or current_context(),
    )[0]


def concatenate(arrays, axis=0, always_copy=True) -> NDArray:
    return imperative_invoke("Concat", list(arrays), {"num_args": len(arrays), "dim": axis})[0]


def onehot_encode(indices, out):
    """(reference: ndarray.py onehot_encode) one-hot fill of ``out``."""
    depth = out.shape[1]
    res = imperative_invoke("one_hot", [indices], {"depth": depth})[0]
    out._set_jax(res._jax().astype(out.dtype))
    return out


def waitall():
    """Block until all pending computation is done (reference: MXNDArrayWaitAll).

    JAX has no global "all work done" barrier, so live chunks are tracked in a
    weak set and each pending buffer is blocked on — the semantics benchmarks
    rely on (``jax.effects_barrier`` would NOT wait on async array computation).
    """
    for chunk in list(_LIVE_CHUNKS):
        data = chunk.data
        if hasattr(data, "block_until_ready"):
            try:
                data.block_until_ready()
            except RuntimeError as e:
                # deleted/donated buffers don't gate the barrier; real async
                # execution failures must surface like the reference's WaitAll
                if "deleted" in str(e).lower() or "donated" in str(e).lower():
                    continue
                raise
    # drain host-side engine work too (IO stages, checkpoint writes pushed
    # through mx.engine) — the reference's WaitForAll covers both
    from . import engine as _engine_mod

    if _engine_mod._engine is not None:
        _engine_mod._engine.wait_for_all()


# ---------------------------------------------------------------- serialization
def _write_ndarray(f, arr: NDArray):
    """Reference-exact layout (src/ndarray/ndarray.cc:623-645 NDArray::Save):
    TShape (uint32 ndim + uint32 dims), then — unless the array is_none
    (ndim==0) — Context (int32 dev_type, int32 dev_id), int32 type_flag, and
    the raw contiguous bytes with NO length prefix (size is implied by
    shape × dtype)."""
    if arr is None:
        f.write(struct.pack("<I", 0))  # is_none: shape only, like the reference
        return
    np_arr = arr.asnumpy()
    shape = np_arr.shape
    if len(shape) == 0:
        # the reference format has no 0-d representation (ndim==0 means
        # is_none, ndarray.cc:650-652) — refuse rather than silently drop data
        raise MXNetError("cannot save 0-d NDArray in the .params format; reshape to (1,)")
    f.write(struct.pack("<I", len(shape)))
    f.write(struct.pack("<%dI" % len(shape), *shape))
    ctx = arr.context
    f.write(struct.pack("<ii", ctx.device_typeid, ctx.device_id))
    f.write(struct.pack("<i", dtype_code(np_arr.dtype)))
    f.write(np.ascontiguousarray(np_arr).tobytes())


def _read_ndarray(f) -> Optional[NDArray]:
    (ndim,) = struct.unpack("<I", f.read(4))
    if ndim == 0:
        return None  # reference: is_none NDArray (ndarray.cc:650-652)
    shape = struct.unpack("<%dI" % ndim, f.read(4 * ndim))
    dev_type, dev_id = struct.unpack("<ii", f.read(8))
    (dt_code,) = struct.unpack("<i", f.read(4))
    dtype = dtype_from_code(dt_code)
    nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
    arr = np.frombuffer(f.read(nbytes), dtype=dtype).reshape(shape)
    return NDArray(arr, ctx=current_context())


def save(fname: str, data):
    """Save list/dict of NDArrays (reference: mx.nd.save; format modeled on the
    kMXAPINDArrayListMagic layout, ndarray.h:358-369)."""
    if isinstance(data, dict):
        names, arrays = list(data.keys()), list(data.values())
    elif isinstance(data, (list, tuple)):
        names, arrays = [], list(data)
    elif isinstance(data, NDArray):
        names, arrays = [], [data]
    else:
        raise TypeError("save: need dict/list/NDArray")
    with open(fname, "wb") as f:
        f.write(struct.pack("<QQ", _MAGIC_LIST, 0))
        f.write(struct.pack("<Q", len(arrays)))
        for a in arrays:
            _write_ndarray(f, a)
        f.write(struct.pack("<Q", len(names)))
        for n in names:
            b = n.encode("utf-8")
            f.write(struct.pack("<Q", len(b)))
            f.write(b)


def load(fname: str):
    with open(fname, "rb") as f:
        magic, _ = struct.unpack("<QQ", f.read(16))
        if magic != _MAGIC_LIST:
            raise MXNetError("invalid NDArray file %s" % fname)
        (count,) = struct.unpack("<Q", f.read(8))
        arrays = [_read_ndarray(f) for _ in range(count)]
        (n_names,) = struct.unpack("<Q", f.read(8))
        names = []
        for _ in range(n_names):
            (ln,) = struct.unpack("<Q", f.read(8))
            names.append(f.read(ln).decode("utf-8"))
    if names:
        return dict(zip(names, arrays))
    return arrays


_init_ndarray_module()
