"""Symbol: declarative graph composition.

TPU-native redesign of the reference's Symbol layer (nnvm ``Symbol`` +
python/mxnet/symbol.py). The reference builds an nnvm::Graph and runs C++
passes (InferShape/InferType, reference src/executor/graph_executor.cc:423-424);
here a Symbol is a lightweight Python DAG over the single op registry, and
shape/type inference *is* ``jax.eval_shape`` over each op's JAX function —
the op implementation is the one source of truth, exactly how XLA wants
tracing to work. Backward-flowing parameter shapes (FC weights etc.) come
from declarative rules in ``ops/shape_rules.py``.

Graph JSON save/load keeps the reference's ``*-symbol.json`` nnvm format
(nodes / arg_nodes / heads / node_row_ptr; python/mxnet/symbol.py:745-769)
so checkpoints interoperate.
"""
from __future__ import annotations

import builtins
import functools
import json
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

from .attribute import AttrScope
from .base import MXNetError, np_dtype
from .context import current_context
from .name import NameManager
from .ops import registry as _registry
from .ops.registry import get_op, parse_attrs
from .ops.shape_rules import RULES as _SHAPE_RULES

__all__ = ["Symbol", "Variable", "var", "Group", "load", "load_json", "pow", "maximum", "minimum"]


class _Node:
    """One graph node: an operator application or a variable (op=None)."""

    __slots__ = ("op", "name", "attrs", "inputs", "_parsed")

    def __init__(self, op: Optional[str], name: str, attrs: dict, inputs):
        self.op = op  # canonical registry name, or None for variables
        self.name = name
        self.attrs = dict(attrs or {})
        self.inputs = list(inputs)  # list[(node, out_index)]
        self._parsed = None

    @property
    def is_variable(self):
        return self.op is None

    def parsed_attrs(self) -> dict:
        if self._parsed is None:
            self._parsed = parse_attrs(get_op(self.op), self.attrs) if self.op else {}
        return self._parsed

    def opdef(self):
        return get_op(self.op)

    def num_outputs(self) -> int:
        if self.op is None:
            return 1
        return self.opdef().num_outputs(self.parsed_attrs())


def _topo_order(head_nodes) -> List[_Node]:
    """Iterative post-order DFS preserving input order (nnvm DFSVisit)."""
    order: List[_Node] = []
    visited = set()
    stack = [(n, False) for n in reversed(head_nodes)]
    while stack:
        node, expanded = stack.pop()
        if id(node) in visited:
            continue
        if expanded:
            visited.add(id(node))
            order.append(node)
        else:
            stack.append((node, True))
            for inp, _ in reversed(node.inputs):
                if id(inp) not in visited:
                    stack.append((inp, False))
    return order


def _aux_positions(node: _Node) -> int:
    """Number of trailing inputs of ``node`` that are aux states."""
    if node.op is None:
        return 0
    return len(node.opdef().aux_names(node.parsed_attrs()))


class Symbol:
    """A list of output entries over the graph (reference: nnvm Symbol)."""

    __slots__ = ("_outputs",)

    def __init__(self, outputs):
        self._outputs = list(outputs)  # list[(node, out_index)]

    # ------------------------------------------------------------- structure
    @property
    def name(self):
        if len(self._outputs) != 1:
            return None
        return self._outputs[0][0].name

    def __repr__(self):
        name = self.name
        return "<Symbol %s>" % (name if name else "Grouped")

    def __iter__(self):
        return (Symbol([o]) for o in self._outputs)

    def __len__(self):
        return len(self._outputs)

    def _head_nodes(self):
        seen, heads = set(), []
        for node, _ in self._outputs:
            if id(node) not in seen:
                seen.add(id(node))
                heads.append(node)
        return heads

    def _topo(self) -> List[_Node]:
        return _topo_order(self._head_nodes())

    def _classified_variables(self):
        """Topo-ordered (args, auxs) variable name lists. A variable feeding an
        aux slot of any consumer is an auxiliary state (the reference derives
        this from FMutateInputs, src/nnvm/legacy_op_util.cc)."""
        topo = self._topo()
        aux_vars = set()
        for node in topo:
            n_aux = _aux_positions(node)
            if n_aux:
                for inp, _ in node.inputs[len(node.inputs) - n_aux :]:
                    if inp.is_variable:
                        aux_vars.add(id(inp))
        args, auxs = [], []
        for node in topo:
            if node.is_variable:
                (auxs if id(node) in aux_vars else args).append(node)
        return args, auxs

    def list_arguments(self) -> List[str]:
        args, _ = self._classified_variables()
        return [n.name for n in args]

    def list_auxiliary_states(self) -> List[str]:
        _, auxs = self._classified_variables()
        return [n.name for n in auxs]

    def list_inputs(self) -> List[str]:
        return [n.name for n in self._topo() if n.is_variable]

    def list_outputs(self) -> List[str]:
        out = []
        for node, idx in self._outputs:
            if node.is_variable:
                out.append(node.name)
            else:
                out.append("%s_%s" % (node.name, node.opdef().output_names(node.parsed_attrs())[idx]))
        return out

    def get_internals(self) -> "Symbol":
        """All intermediate outputs as a grouped symbol (reference:
        symbol.py get_internals)."""
        outs = []
        for node in self._topo():
            for i in range(node.num_outputs()):
                outs.append((node, i))
        return Symbol(outs)

    def get_children(self) -> Optional["Symbol"]:
        outs = []
        for node in self._head_nodes():
            outs.extend(node.inputs)
        return Symbol(outs) if outs else None

    def __getitem__(self, index):
        if isinstance(index, str):
            names = self.list_outputs()
            if index not in names:
                raise MXNetError("cannot find output %r in %s" % (index, names))
            index = names.index(index)
        # NB: builtins — module-level op functions shadow names like `slice`
        if isinstance(index, builtins.slice):
            return Symbol(self._outputs[index])
        return Symbol([self._outputs[index]])

    # ------------------------------------------------------------------ attrs
    def attr(self, key):
        if len(self._outputs) != 1:
            raise MXNetError("attr() requires a single-output symbol")
        v = self._outputs[0][0].attrs.get(key)
        return None if v is None else str(v)

    def list_attr(self):
        if len(self._outputs) != 1:
            raise MXNetError("list_attr() requires a single-output symbol")
        return {k: str(v) for k, v in self._outputs[0][0].attrs.items()}

    def attr_dict(self):
        return {n.name: {k: str(v) for k, v in n.attrs.items()} for n in self._topo() if n.attrs}

    def _set_attr(self, **kwargs):
        for node, _ in self._outputs:
            node.attrs.update({k: str(v) for k, v in kwargs.items()})
            node._parsed = None

    # -------------------------------------------------------------- arithmetic
    def _binary(self, other, op, scalar_op, reverse=False):
        if isinstance(other, Symbol):
            a, b = (other, self) if reverse else (self, other)
            return _create(op, [a, b], {})
        return _create(scalar_op, [self], {"scalar": float(other)})

    def __add__(self, other):
        return self._binary(other, "elemwise_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, other):
        return self._binary(other, "elemwise_sub", "_minus_scalar")

    def __rsub__(self, other):
        return self._binary(other, "elemwise_sub", "_rminus_scalar", reverse=True)

    def __mul__(self, other):
        return self._binary(other, "elemwise_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binary(other, "elemwise_div", "_div_scalar")

    def __rtruediv__(self, other):
        return self._binary(other, "elemwise_div", "_rdiv_scalar", reverse=True)

    __div__ = __truediv__
    __rdiv__ = __rtruediv__

    def __pow__(self, other):
        if isinstance(other, Symbol):
            return _create("_power", [self, other], {})
        return _create("_power_scalar", [self], {"scalar": float(other)})

    def __neg__(self):
        return _create("negative", [self], {})

    def __copy__(self):
        return Symbol(list(self._outputs))

    def __eq__(self, other):
        if isinstance(other, (Symbol, int, float)):
            return self._binary(other, "_equal", "_equal_scalar")
        return NotImplemented

    def __ne__(self, other):
        if isinstance(other, (Symbol, int, float)):
            return self._binary(other, "_not_equal", "_not_equal_scalar")
        return NotImplemented

    def __gt__(self, other):
        return self._binary(other, "_greater", "_greater_scalar")

    def __ge__(self, other):
        return self._binary(other, "_greater_equal", "_greater_equal_scalar")

    def __lt__(self, other):
        return self._binary(other, "_lesser", "_lesser_scalar")

    def __le__(self, other):
        return self._binary(other, "_lesser_equal", "_lesser_equal_scalar")

    __hash__ = object.__hash__

    # -------------------------------------------------------------- inference
    def _resolve_kwargs_shapes(self, args, kwargs):
        known = {}
        if args:
            arg_names = self.list_arguments()
            for name, sh in zip(arg_names, args):
                if sh is not None:
                    known[name] = tuple(sh)
        for k, v in kwargs.items():
            if v is not None:
                known[k] = tuple(v)
        return known

    def infer_shape(self, *args, **kwargs):
        """Infer shapes of arguments/outputs/aux states. Returns
        (arg_shapes, out_shapes, aux_shapes); (None, None, None) when
        underdetermined (reference: symbol.py:597 infer_shape)."""
        try:
            arg_s, out_s, aux_s = self._infer_impl(self._resolve_kwargs_shapes(args, kwargs), {}, partial=False)[:3]
            return arg_s, out_s, aux_s
        except _IncompleteInference:
            return None, None, None

    def infer_shape_partial(self, *args, **kwargs):
        arg_s, out_s, aux_s = self._infer_impl(self._resolve_kwargs_shapes(args, kwargs), {}, partial=True)[:3]
        return arg_s, out_s, aux_s

    def infer_type(self, *args, **kwargs):
        known = {}
        if args:
            for name, t in zip(self.list_arguments(), args):
                if t is not None:
                    known[name] = np_dtype(t)
        for k, v in kwargs.items():
            if v is not None:
                known[k] = np_dtype(v)
        # dtype inference must work without shapes (reference: infer_type is
        # independent of infer_shape) — partial mode falls back to dtype
        # promotion rules where eval_shape can't run
        res = self._infer_impl({}, known, partial=True)
        return res[3], res[4], res[5]

    def _infer_impl(self, shape_hints: dict, type_hints: dict, partial: bool):
        """Single pass computing shapes+dtypes for every graph entry."""
        topo = self._topo()
        args, auxs = self._classified_variables()
        entries_shape: Dict[Tuple[int, int], Optional[tuple]] = {}
        entries_dtype: Dict[Tuple[int, int], Optional[np.dtype]] = {}
        var_shape: Dict[str, Optional[tuple]] = {}
        var_dtype: Dict[str, Optional[np.dtype]] = {}

        for node in topo:
            if node.is_variable:
                sh = shape_hints.get(node.name)
                if sh is None and "__shape__" in node.attrs:
                    sh = _parse_shape_attr(node.attrs["__shape__"])
                dt = type_hints.get(node.name)
                if dt is None and "__dtype__" in node.attrs:
                    dt = np_dtype(node.attrs["__dtype__"])
                var_shape[node.name] = tuple(sh) if sh is not None else None
                var_dtype[node.name] = dt

        for node in topo:
            if node.is_variable:
                entries_shape[(id(node), 0)] = var_shape[node.name]
                entries_dtype[(id(node), 0)] = var_dtype[node.name]
                continue
            parsed = node.parsed_attrs()
            in_entries = [(id(n), i) for n, i in node.inputs]
            in_shapes = [entries_shape.get(e) for e in in_entries]
            rule = _SHAPE_RULES.get(node.op)
            if rule is not None and any(s is None for s in in_shapes):
                filled = rule(parsed, list(in_shapes))
                for (inp, out_i), old, new in zip(node.inputs, in_shapes, filled):
                    if old is None and new is not None:
                        new = tuple(int(x) for x in new)
                        entries_shape[(id(inp), out_i)] = new
                        if inp.is_variable:
                            if var_shape.get(inp.name) is not None and var_shape[inp.name] != new:
                                raise MXNetError(
                                    "inferred shape %s for %r conflicts with %s"
                                    % (new, inp.name, var_shape[inp.name])
                                )
                            var_shape[inp.name] = new
                in_shapes = [entries_shape.get(e) for e in in_entries]
            in_dtypes = [entries_dtype.get(e) for e in in_entries]
            if any(s is None for s in in_shapes):
                if partial:
                    # shapes unknown: still propagate dtypes by promotion so
                    # infer_type works standalone (Cast/creation ops override)
                    dt = _fallback_dtype(node, parsed, in_dtypes)
                    # inputs take the promotion of the KNOWN inputs — never the
                    # output dtype, which dtype-forcing ops (Cast) decouple
                    known_in = [d for d in in_dtypes if d is not None]
                    in_promo = np.dtype(np.result_type(*known_in)) if known_in else None
                    for (inp, _), d in zip(node.inputs, in_dtypes):
                        if inp.is_variable and var_dtype.get(inp.name) is None and in_promo is not None:
                            var_dtype[inp.name] = in_promo
                            entries_dtype[(id(inp), 0)] = in_promo
                    for i in range(node.num_outputs()):
                        entries_shape[(id(node), i)] = None
                        entries_dtype[(id(node), i)] = dt
                    continue
                missing = [
                    node.inputs[i][0].name
                    for i, s in enumerate(in_shapes)
                    if s is None and node.inputs[i][0].is_variable
                ]
                raise _IncompleteInference(
                    "cannot infer shapes at node %r (op %s): unknown inputs %s"
                    % (node.name, node.op, missing)
                )
            # unknown dtypes default to float32 (the reference's default_dtype)
            in_dtypes = [np.dtype(np.float32) if d is None else d for d in in_dtypes]
            for (inp, out_i), d in zip(node.inputs, in_dtypes):
                if inp.is_variable and var_dtype.get(inp.name) is None:
                    var_dtype[inp.name] = d
                    entries_dtype[(id(inp), 0)] = d
            out_structs = _eval_node_shape(
                node.op,
                _freeze(parsed),
                tuple(in_shapes),
                tuple(str(d) for d in in_dtypes),
                _aux_positions(node),
            )
            for i, st in enumerate(out_structs[: node.num_outputs()]):
                entries_shape[(id(node), i)] = tuple(st[0])
                entries_dtype[(id(node), i)] = np.dtype(st[1])

        def _var_results(var_nodes):
            return (
                [var_shape.get(n.name) for n in var_nodes],
                [var_dtype.get(n.name) or np.dtype(np.float32) for n in var_nodes],
            )

        arg_shapes, arg_types = _var_results(args)
        aux_shapes, aux_types = _var_results(auxs)
        out_shapes = [entries_shape.get((id(n), i)) for n, i in self._outputs]
        out_types = [entries_dtype.get((id(n), i)) for n, i in self._outputs]
        if not partial and any(s is None for s in arg_shapes + out_shapes + aux_shapes):
            missing = [n.name for n, s in zip(args, arg_shapes) if s is None]
            raise _IncompleteInference("underdetermined shapes for arguments %s" % missing)
        return arg_shapes, out_shapes, aux_shapes, arg_types, out_types, aux_types

    # --------------------------------------------------------------- binding
    def simple_bind(self, ctx=None, grad_req="write", type_dict=None, group2ctx=None, **kwargs):
        from .executor import simple_bind as _sb

        return _sb(self, ctx or current_context(), grad_req=grad_req, type_dict=type_dict, group2ctx=group2ctx, **kwargs)

    def bind(self, ctx, args, args_grad=None, grad_req="write", aux_states=None, group2ctx=None, shared_exec=None):
        from .executor import bind as _bind

        return _bind(self, ctx, args, args_grad=args_grad, grad_req=grad_req, aux_states=aux_states, shared_exec=shared_exec, group2ctx=group2ctx)

    def eval(self, ctx=None, **kwargs):
        """One-shot forward on NDArray kwargs (reference: symbol.py eval)."""
        ex = self.bind(ctx or current_context(), kwargs)
        return ex.forward(is_train=False)

    # ------------------------------------------------------------------ JSON
    def tojson(self) -> str:
        topo = self._topo()
        ids = {id(n): i for i, n in enumerate(topo)}
        nodes = []
        arg_nodes = []
        row_ptr = [0]
        for n in topo:
            entry = {
                "op": n.op if n.op else "null",
                "name": n.name,
                "inputs": [[ids[id(inp)], oi, 0] for inp, oi in n.inputs],
            }
            if n.attrs:
                entry["attr"] = {k: str(v) for k, v in n.attrs.items()}
            nodes.append(entry)
            if n.op is None:
                arg_nodes.append(ids[id(n)])
            row_ptr.append(row_ptr[-1] + n.num_outputs())
        graph = {
            "nodes": nodes,
            "arg_nodes": arg_nodes,
            "node_row_ptr": row_ptr,
            "heads": [[ids[id(n)], i, 0] for n, i in self._outputs],
            "attrs": {"mxnet_version": ["int", 905]},
        }
        return json.dumps(graph, indent=2)

    def save(self, fname: str):
        with open(fname, "w") as f:
            f.write(self.tojson())

    # ------------------------------------------------------------- debug info
    def debug_str(self) -> str:
        lines = []
        for n in self._topo():
            if n.is_variable:
                lines.append("Variable:%s" % n.name)
            else:
                ins = ", ".join("%s[%d]" % (inp.name, oi) for inp, oi in n.inputs)
                lines.append("Op:%s, Name=%s\nInputs:\n\t%s" % (n.op, n.name, ins))
        return "\n".join(lines)


class _IncompleteInference(MXNetError):
    pass


def _fallback_dtype(node, parsed, in_dtypes):
    """Dtype of a node's outputs when shapes are unknown: attr-declared dtype
    (Cast, creation ops) or numpy promotion of the known input dtypes."""
    if isinstance(parsed.get("dtype"), (np.dtype, type, str)):
        try:
            return np.dtype(np_dtype(parsed["dtype"]))
        except TypeError:
            pass
    known = [d for d in in_dtypes if d is not None]
    if not known:
        return np.dtype(np.float32)
    return np.dtype(np.result_type(*known))


def _parse_shape_attr(v):
    if isinstance(v, (tuple, list)):
        return tuple(int(x) for x in v)
    s = str(v).strip().lstrip("([").rstrip(")]")
    if not s:
        return ()
    return tuple(int(float(x)) for x in s.split(",") if x.strip())


def _freeze(attrs: dict):
    def fr(v):
        if isinstance(v, (list, tuple)):
            return tuple(fr(x) for x in v)
        if isinstance(v, np.dtype):
            return v.name
        return v

    return tuple(sorted((k, fr(v)) for k, v in attrs.items()))


@functools.lru_cache(maxsize=16384)
def _eval_node_shape(op_name, attrs_key, in_shapes, in_dtypes, n_aux):
    """Abstract-evaluate one node via jax.eval_shape — the FInferShape/FInferType
    pass collapsed into the op function itself."""
    import jax

    opdef = get_op(op_name)
    attrs = dict(attrs_key)
    n_in = len(in_shapes) - n_aux
    structs = [
        jax.ShapeDtypeStruct(tuple(s), np_dtype(d)) for s, d in zip(in_shapes, in_dtypes)
    ]
    if opdef.infer is not None:
        return tuple((tuple(shape), np.dtype(dtype).name)
                     for shape, dtype in opdef.infer(attrs, structs))
    key = jax.random.PRNGKey(0) if opdef.needs_rng else None

    def run(*arrays):
        outs, new_aux = opdef.apply(attrs, arrays[:n_in], aux=arrays[n_in:], is_train=True, rng=key)
        return tuple(outs)

    out = jax.eval_shape(run, *structs)
    return tuple((tuple(o.shape), np.dtype(o.dtype).name) for o in out)


# ----------------------------------------------------------------- creation
def Variable(name, attr=None, shape=None, lr_mult=None, wd_mult=None, dtype=None, init=None, **kwargs) -> Symbol:
    """Create a named variable placeholder (reference: symbol.py Variable)."""
    if not isinstance(name, str):
        raise TypeError("Expect a string for variable name")
    attr = AttrScope.current().get(attr)
    attr = dict(attr or {})
    if shape is not None:
        attr["__shape__"] = str(tuple(shape))
    if lr_mult is not None:
        attr["__lr_mult__"] = str(lr_mult)
    if wd_mult is not None:
        attr["__wd_mult__"] = str(wd_mult)
    if dtype is not None:
        attr["__dtype__"] = np.dtype(np_dtype(dtype)).name
    if init is not None:
        if not isinstance(init, str):
            init = init.dumps()
        attr["__init__"] = init
    for k, v in kwargs.items():
        if k.startswith("__") and k.endswith("__"):
            attr[k] = str(v)
        else:
            raise ValueError("Attribute name=%s is not supported." % k)
    return Symbol([(_Node(None, name, attr, []), 0)])


var = Variable


def Group(symbols) -> Symbol:
    """Group symbols into one multi-output symbol (reference: symbol.py Group)."""
    outputs = []
    for s in symbols:
        if not isinstance(s, Symbol):
            raise TypeError("Group: expected Symbol, got %r" % (s,))
        outputs.extend(s._outputs)
    return Symbol(outputs)


def _create(op_name, input_syms, attrs, name=None, attr=None) -> Symbol:
    """Create an op node over single-output input symbols."""
    opdef = get_op(op_name)
    canonical = opdef.name
    parsed = parse_attrs(opdef, attrs)
    hint = canonical.lower().lstrip("_")
    name = NameManager.current().get(name, hint if hint else canonical.lower())
    node_attrs = dict(attrs)
    scope_attrs = AttrScope.current().get(attr)
    if scope_attrs:
        node_attrs.update(scope_attrs)
    inputs = []
    for s in input_syms:
        if len(s._outputs) != 1:
            raise MXNetError("op %s: input symbols must have a single output" % op_name)
        inputs.append(s._outputs[0])
    node = _Node(canonical, name, node_attrs, inputs)
    return Symbol([(node, i) for i in range(opdef.num_outputs(parsed))])


def _make_symbol_function(op_name):
    opdef = get_op(op_name)

    def creator(*args, **kwargs):
        name = kwargs.pop("name", None)
        attr = kwargs.pop("attr", None)
        sym_args = []
        for a in args:
            if isinstance(a, Symbol):
                sym_args.append(a)
            else:
                raise TypeError("%s: positional args must be Symbols; use kwargs for attrs" % op_name)
        sym_kwargs = {}
        attrs = {}
        for k, v in kwargs.items():
            if isinstance(v, Symbol):
                sym_kwargs[k] = v
            else:
                attrs[k] = v
        if "num_args" in opdef.attr_specs and "num_args" not in attrs:
            attrs["num_args"] = len(sym_args) + len(sym_kwargs)
        parsed = parse_attrs(opdef, attrs)
        slots = opdef.input_names(parsed) + opdef.aux_names(parsed)
        hint = opdef.name.lower().lstrip("_") or opdef.name.lower()
        name = NameManager.current().get(name, hint)
        if len(sym_args) > len(slots):
            raise MXNetError(
                "%s: too many positional inputs (%d given, expects %s)"
                % (op_name, len(sym_args), slots)
            )
        filled: Dict[str, Symbol] = {}
        for slot, s in zip(slots, sym_args):
            filled[slot] = s
        for k, v in sym_kwargs.items():
            if k not in slots:
                raise MXNetError("%s: unknown tensor input %r (expects %s)" % (op_name, k, slots))
            if k in filled:
                raise MXNetError("%s: input %r given twice" % (op_name, k))
            filled[k] = v
        input_syms = []
        for slot in slots:
            if slot in filled:
                input_syms.append(filled[slot])
            else:
                # auto-create the parameter variable (reference behavior:
                # omitted named inputs become new variables "<name>_<slot>")
                input_syms.append(Variable("%s_%s" % (name, slot)))
        node_attrs = dict(attrs)
        scope_attrs = AttrScope.current().get(attr)
        if scope_attrs:
            node_attrs.update(scope_attrs)
        inputs = []
        for s in input_syms:
            if len(s._outputs) != 1:
                raise MXNetError("op %s: input symbols must have a single output" % op_name)
            inputs.append(s._outputs[0])
        node = _Node(opdef.name, name, node_attrs, inputs)
        return Symbol([(node, i) for i in range(opdef.num_outputs(parsed))])

    creator.__name__ = op_name
    creator.__doc__ = opdef.doc
    return creator


def pow(base, exp):
    if isinstance(base, Symbol) and isinstance(exp, Symbol):
        return _create("_power", [base, exp], {})
    if isinstance(base, Symbol):
        return base.__pow__(exp)
    if isinstance(exp, Symbol):
        return exp.__rpow__(base) if hasattr(exp, "__rpow__") else _create("_rpower_scalar", [exp], {"scalar": float(base)})
    raise TypeError("pow: need at least one Symbol")


def maximum(left, right):
    if isinstance(left, Symbol) and isinstance(right, Symbol):
        return _create("_maximum", [left, right], {})
    if isinstance(left, Symbol):
        return _create("_maximum_scalar", [left], {"scalar": float(right)})
    return _create("_maximum_scalar", [right], {"scalar": float(left)})


def minimum(left, right):
    if isinstance(left, Symbol) and isinstance(right, Symbol):
        return _create("_minimum", [left, right], {})
    if isinstance(left, Symbol):
        return _create("_minimum_scalar", [left], {"scalar": float(right)})
    return _create("_minimum_scalar", [right], {"scalar": float(left)})


# -------------------------------------------------------------------- JSON load
def load_json(json_str: str) -> Symbol:
    """Rebuild a Symbol from nnvm graph JSON (reference format,
    src/nnvm/legacy_json_util.cc handles the same keys)."""
    graph = json.loads(json_str)
    nodes_json = graph["nodes"]
    built: List[_Node] = []
    for nj in nodes_json:
        op = nj["op"]
        attrs = nj.get("attr") or nj.get("attrs") or nj.get("param") or {}
        inputs = [(built[e[0]], e[1]) for e in nj.get("inputs", [])]
        built.append(_Node(None if op == "null" else get_op(op).name, nj["name"], attrs, inputs))
    heads = graph.get("heads")
    if not heads:
        heads = [[len(built) - 1, 0, 0]]
    return Symbol([(built[h[0]], h[1]) for h in heads])


def load(fname: str) -> Symbol:
    with open(fname) as f:
        return load_json(f.read())


def fromjson(json_str: str) -> Symbol:
    return load_json(json_str)


def _init_symbol_module():
    mod = sys.modules[__name__]
    for name in list(_registry._REGISTRY.keys()):
        if not hasattr(mod, name):
            setattr(mod, name, _make_symbol_function(name))


_init_symbol_module()
