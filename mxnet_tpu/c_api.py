"""Training-side C ABI: build helper + the Python glue the embedded
interpreter calls (src/c_api.cc; reference: include/mxnet/c_api.h's
imperative slice, src/c_api/c_api_ndarray.cc:322 MXImperativeInvoke).

The C library addresses everything through this module so the C side stays
a thin GIL/refcount shim: op invocation (by registry name, string attrs
parsed exactly like symbol JSON), simple_bind over a symbol JSON, KVStore
verbs, and host copies."""
from __future__ import annotations

import os
import sys
import sysconfig
import threading

import numpy as np

from ._native_build import build_lib, source_path

__all__ = ["build", "lib_path"]

_SRC = source_path("c_api.cc")
_lock = threading.Lock()


def lib_path():
    from ._native_build import _BUILD_DIR

    return os.path.join(_BUILD_DIR, "libmxtpu_c.so")


def build():
    """Compile (if stale) and return the .so path; None if no toolchain."""
    with _lock:
        inc = sysconfig.get_paths()["include"]
        libdir = sysconfig.get_config_var("LIBDIR")
        pyver = "python%d.%d" % sys.version_info[:2]
        return build_lib(_SRC, "libmxtpu_c.so",
                         extra_flags=["-I", inc, "-L", libdir, "-l", pyver])


# ---------------------------------------------------------------- C-side glue
def zeros(shape):
    from . import ndarray as nd

    return nd.zeros(tuple(int(d) for d in shape))


def copy_from_host(arr, mem):
    # .copy() is load-bearing: jax's CPU backend zero-copy-aliases numpy
    # arrays, and the C caller frees its buffer right after this returns
    # (same reason predict_api.cc's make_array copies)
    data = np.frombuffer(mem, dtype=np.float32).reshape(arr.shape).copy()
    arr[:] = data
    return True


def waitall():
    from . import ndarray as nd

    nd.waitall()
    return True


def invoke(op_name, inputs, keys, vals, outs):
    """MXImperativeInvokeByName glue: string attr values, optional in-place
    ``out=`` targets. Returns the output list (possibly the out targets)."""
    from . import ndarray as nd
    from .ops.registry import get_op, parse_attrs

    attrs = dict(zip(keys, vals))
    if outs is not None:
        # imperative_invoke zip-truncates; an undersized out list would
        # silently drop outputs (e.g. sgd_mom_update's momentum) — refuse
        opdef = get_op(op_name)
        n_out = opdef.num_outputs(parse_attrs(opdef, dict(attrs)))
        if len(outs) != n_out:
            raise ValueError(
                "%s produces %d outputs but %d out targets were supplied"
                % (op_name, n_out, len(outs)))
    res = nd.imperative_invoke(op_name, list(inputs), attrs,
                               out=list(outs) if outs is not None else None)
    return list(res)


def bind_from_json(symbol_json, shapes):
    from . import symbol as sym
    from .context import current_context

    net = sym.load_json(symbol_json)
    # the named inputs (data/labels — the keys the C caller gave shapes
    # for) get grad_req null so MXExecutorGetGrad returns NULL for them,
    # per the header's parameter-vs-input idiom; everything else is a
    # trainable parameter with grad_req write
    grad_req = {n: ("null" if n in shapes else "write")
                for n in net.list_arguments()}
    ex = net.simple_bind(current_context(), grad_req=grad_req,
                         **{k: tuple(v) for k, v in shapes.items()})
    return ex


def arg_names(ex):
    return list(ex.arg_dict.keys())


def get_arg(ex, name):
    if name not in ex.arg_dict:
        raise KeyError("unknown argument %r" % name)
    return ex.arg_dict[name]


def get_grad(ex, name):
    if name not in ex.grad_dict:
        raise KeyError("unknown argument %r" % name)
    return ex.grad_dict[name]


def kv_create(type_str):
    from . import kvstore

    return kvstore.create(type_str)


def kv_init(kv, keys, vals):
    kv.init(list(keys), list(vals))
    return True


def kv_push(kv, keys, vals):
    kv.push(list(keys), list(vals))
    return True


def kv_pull(kv, keys, outs):
    kv.pull(list(keys), out=list(outs))
    return True
