"""mxnet_tpu: a TPU-native deep learning framework.

A brand-new framework with the capabilities of pre-Gluon MXNet 0.9 (the
reference described in SURVEY.md), designed TPU-first on JAX/XLA: imperative
NDArray + symbolic Symbol/Executor over one operator registry, a Module
training layer, KVStore-style data parallelism lowered to XLA collectives over
a device mesh, and lax.scan RNNs. Importable as ``mx`` for script parity:

    import mxnet_tpu as mx
    x = mx.nd.ones((2, 3), ctx=mx.tpu())
"""
__version__ = "0.1.0"

import os as _os

if _os.environ.get("MXNET_DEFAULT_CONTEXT", "").startswith("cpu"):
    # host-only run: pin the JAX platform before any backend initialises, so
    # a process whose default context is the CPU never opens (and holds) a chip
    import jax as _jax

    _jax.config.update("jax_platforms", "cpu")

from . import compile_cache as _compile_cache

_compile_cache.configure()

from . import base
from .base import MXNetError
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus
from .attribute import AttrScope
from . import name
from . import ndarray
from . import ndarray as nd
from . import random
from . import random as rnd
from . import ops

__all__ = [
    "MXNetError",
    "Context",
    "AttrScope",
    "cpu",
    "gpu",
    "tpu",
    "current_context",
    "name",
    "nd",
    "ndarray",
    "random",
    "ops",
]


def __getattr__(name):
    # lazy subsystem imports keep `import mxnet_tpu` light and avoid cycles
    import importlib

    lazy = {
        "analysis": ".analysis",
        "sym": ".symbol",
        "symbol": ".symbol",
        "executor": ".executor",
        "mod": ".module",
        "module": ".module",
        "io": ".io",
        "optimizer": ".optimizer",
        "lr_scheduler": ".lr_scheduler",
        "metric": ".metric",
        "initializer": ".initializer",
        "init": ".initializer",
        "kvstore": ".kvstore",
        "kv": ".kvstore",
        "dist": ".dist",
        "engine": ".engine",
        "predictor": ".predictor",
        "rtc": ".rtc",
        "callback": ".callback",
        "monitor": ".monitor",
        "mon": ".monitor",
        "rnn": ".rnn",
        "model": ".model",
        "autograd": ".autograd",
        "operator": ".operator",
        "parallel": ".parallel",
        "test_utils": ".test_utils",
        "visualization": ".visualization",
        "viz": ".visualization",
        "profiler": ".profiler",
        "telemetry": ".telemetry",
        "faultinject": ".faultinject",
        "serving": ".serving",
        "sparse": ".sparse",
        "checkpoint": ".checkpoint",
        "recordio": ".recordio",
        "image": ".image",
        "img": ".image",
        "models": ".models",
    }
    if name in lazy:
        return importlib.import_module(lazy[name], __name__)
    raise AttributeError("module %r has no attribute %r" % (__name__, name))
