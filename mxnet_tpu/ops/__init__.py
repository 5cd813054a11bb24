"""Operator library: pure-JAX implementations behind a single registry.

The TPU-native replacement for the reference's src/operator/ (45.7k LoC of
C++/CUDA, SURVEY.md §2.3): kernels become jnp/lax expressions XLA fuses and
tiles onto the MXU/VPU, so each op is a few lines. The registry (registry.py)
is the single source of truth for both the imperative NDArray frontend and the
symbolic Symbol frontend, like the NNVM registry was for the reference.
"""
from . import registry
from .registry import AttrSpec, OpDef, get_op, has_op, list_ops, parse_attrs, register
from . import infer_meta  # per-op shape/dtype metadata for analysis passes

# importing these modules populates the registry
from . import elemwise  # noqa: F401
from . import broadcast_reduce  # noqa: F401
from . import matrix  # noqa: F401
from . import nn  # noqa: F401
from . import sample  # noqa: F401
from . import sequence  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import ctc  # noqa: F401
from . import rnn  # noqa: F401
from . import vision  # noqa: F401
from . import attention  # noqa: F401
from . import moe  # noqa: F401
from . import ssm  # noqa: F401
from . import shortconv  # noqa: F401
from . import custom  # noqa: F401

__all__ = [
    "AttrSpec",
    "OpDef",
    "get_op",
    "has_op",
    "list_ops",
    "parse_attrs",
    "register",
]
