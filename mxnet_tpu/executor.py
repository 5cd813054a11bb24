"""Executor: bound, compiled symbol graphs.

TPU-native redesign of the reference's GraphExecutor
(src/executor/graph_executor.cc:333 Init, :178 InitFullGraph,
python/mxnet/executor.py). The reference builds an explicit fwd+bwd nnvm
graph, plans memory, and pushes per-node engine ops; here the whole graph is
*traced once* into a single jitted XLA computation — forward via topological
interpretation of the op registry, backward via ``jax.vjp`` over that same
trace (SURVEY.md §3.2 TPU mapping: "InitGraph down collapses into trace →
XLA compile"). Memory planning, fusion, scheduling, and the reference's
inplace/bulk-exec optimizations are XLA's job.

Semantics kept from the reference:
  * ``grad_req`` ∈ {write, add, null} per argument (kWriteTo/kAddTo/kNullOp).
  * aux states (BN moving stats) are threaded functionally through the trace
    and written back after ``forward`` — never by ``backward`` — matching the
    FMutateInputs contract.
  * ``backward`` reuses the forward's PRNG key so stochastic ops (Dropout)
    see identical masks in both passes, like the reference's cached masks.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional

import numpy as np

from .base import MXNetError, np_dtype
from .context import Context
from .ndarray import NDArray, _Chunk, zeros
from .ops.registry import get_op
from . import telemetry as _tm

__all__ = ["Executor", "bind", "simple_bind"]


def _named(fn, name):
    """``fn`` under the name a trace shows it by: jax.jit calls the XLA
    module ``jit_<name>`` and the host's dispatch event
    ``PjitFunction(<name>)``. The name is part of the persistent compile
    cache's key, so it holds nothing that differs between two runs of one
    program (no counter, address or time)."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def _cost_of(compiled):
    """XLA's cost analysis of a jit call compiled ahead of time
    (``lower(...).compile()``): a dict with ``flops`` and ``bytes
    accessed``. The AOT compile does not share jit's executable cache, so
    it costs one compile (or one load from the persistent compile
    cache)."""
    cost = compiled.cost_analysis()
    return cost[0] if isinstance(cost, (list, tuple)) else cost


def _signature(arrays):
    """What ``jax.jit`` keys a call's arrays on, as far as the telemetry
    tells calls apart: each one's shape and type."""
    return tuple((tuple(a.shape), str(a.dtype)) for a in arrays)


class _DonatedForward:
    """A forward program jitted with some of its arguments DONATED, called
    and lowered as the plain one is, ``(args, aux, rng)``. ``jax.jit``
    donates whole parameters, so the jitted function takes the donated
    arguments (``donated``: their indices among ``n_args``) as one tuple and
    the rest as another, and puts them back in order before ``run``. With a
    ``store`` the jitted function is the store's (``_GraphProgram.store``)."""

    def __init__(self, run, name, donated, n_args, store=None):
        import jax

        given = set(donated)
        kept = [i for i in range(n_args) if i not in given]

        def merged(held, rest, aux, rng):
            args = [None] * n_args
            for i, a in zip(donated, held):
                args[i] = a
            for i, a in zip(kept, rest):
                args[i] = a
            return run(tuple(args), aux, rng)

        self._fn = jax.jit(_named(merged, name), donate_argnums=(0,))
        self._donated, self._kept = tuple(donated), tuple(kept)
        if store is not None:
            args, aux, rng = store.specs
            self._fn = store.program(
                self._fn, name, self._split(args) + (aux, rng),
                donate_argnums=(0,))

    def _split(self, args):
        return (tuple(args[i] for i in self._donated),
                tuple(args[i] for i in self._kept))

    def __call__(self, args, aux, rng):
        return self._fn(*self._split(args), aux, rng)

    def lower(self, args, aux, rng):
        return self._fn.lower(*self._split(args), aux, rng)


class _GraphProgram:
    """The traced interpretation of a Symbol: pure functions over arg/aux
    tuples, compiled lazily per (is_train, shapes) by jax.jit. ``label``
    names the forward program for whoever owns it (``mx_decode``); without
    one the programs are ``mx_<head name>_fwd`` / ``_fwd_bwd``."""

    def __init__(self, symbol, group2ctx=None):
        self.symbol = symbol
        self.label = None  # set by the owner before the first call
        # arguments the forward program takes DONATED, by name (likewise):
        # it may update them in place, and after a call the arrays that went
        # in are dead; whoever owns the executor hands the outputs that took
        # their place back (``Executor.rebind``). None donated: a plain jit
        self.donated = ()
        # where the inference program is kept EXPORTED (likewise; serving/
        # cache.py ``_StoredProgram``): ``store.program(jitted, name)`` gives
        # what is run in place of the traced function, called, lowered and
        # donated alike, which a process that finds it in the store neither
        # traces nor lowers. None: traced, as ever
        self.store = None
        self.topo = symbol._topo()
        self.group2ctx = dict(group2ctx or {})
        # PlaceDevice-pass analogue (reference: graph_executor.cc:242
        # AssignContext → nnvm PlaceDevice inserting _CrossDeviceCopy): map
        # each node carrying a __ctx_group__ attr to its concrete device;
        # interpret() transfers that node's inputs there, so under jit XLA
        # compiles a multi-device program with real transfers at the group
        # boundaries (example: example/model-parallel-lstm in the reference).
        self._node_devices = {}
        if self.group2ctx:
            from .context import Context as _Ctx

            for node in self.topo:
                group = node.attrs.get("__ctx_group__") if node.op else None
                if group and group in self.group2ctx:
                    ctx = self.group2ctx[group]
                    ctx = ctx if isinstance(ctx, _Ctx) else _Ctx(ctx)
                    self._node_devices[id(node)] = ctx.jax_device
        args, auxs = symbol._classified_variables()
        self.arg_names = [n.name for n in args]
        self.aux_names = [n.name for n in auxs]
        self._arg_index = {n: i for i, n in enumerate(self.arg_names)}
        self._aux_index = {n: i for i, n in enumerate(self.aux_names)}
        self.outputs = list(symbol._outputs)
        self.output_names = symbol.list_outputs()
        # one stable int per rng-consuming node for fold_in
        self._rng_ids = {}
        for node in self.topo:
            if node.op is not None and get_op(node.op).needs_rng:
                self._rng_ids[id(node)] = len(self._rng_ids)
        # per-instance jit cache (an lru_cache on the methods would key a
        # class-level cache on self and leak every program + XLA executable)
        self._jit_cache = {}
        # telemetry: abstract-value signatures seen per jit entry, mirroring
        # jax.jit's own cache key so compile/cache-hit/retrace is observable
        # without reaching into jax internals (maintained only when
        # MXNET_TELEMETRY is on)
        self._seen_sigs = {}
        self._retrace_reason = None  # lazy GL201-203 diagnosis, cached

    # -------------------------------------------------------------- telemetry
    def _note_call(self, key, args, aux, extra=()):
        """Classify an executor's FIRST noted call of a compiled entry:
        ``compile`` (first signature for this jit key), ``cache_hit``
        (another executor of these shapes and types got there before), or
        ``retrace`` (a NEW signature after the first — jax.jit compiles a
        fresh XLA program). Returns ``(kind, reason)``; ``reason`` is the
        cached GL201-203 retrace-guard diagnosis on retraces. Building the
        signature walks every argument, so an executor asks once a jit key
        and remembers (``Executor._note_telemetry``)."""
        sig = (_signature(args), _signature(aux), extra)
        seen = self._seen_sigs.setdefault(key, set())
        if sig in seen:
            return "cache_hit", None
        first = not seen
        seen.add(sig)
        if first:
            return "compile", None
        return "retrace", self._retrace_reasons()

    def _retrace_reasons(self):
        """Why this program retraces, per the static retrace guard
        (analysis/retrace_guard.py GL201-203) — run once per program, on
        the first observed retrace, and cached."""
        if self._retrace_reason is None:
            try:
                from .analysis import lint

                rep = lint(self.symbol, passes=["retrace_guard"])
                self._retrace_reason = "; ".join(
                    "%s: %s" % (d.code, d.message) for d in rep) \
                    or "no GL201-203 pattern found (shape/dtype change " \
                       "came from the caller)"
            except Exception as exc:  # diagnosis must never sink a step
                self._retrace_reason = "retrace-guard diagnosis failed: %s" \
                    % exc
        return self._retrace_reason

    def program_name(self, kind):
        """The jitted program's name: ``kind`` is ``fwd`` or ``fwd_bwd``."""
        if self.label and kind == "fwd":
            return self.label
        head = re.sub(r"[^A-Za-z0-9_]", "_", self.outputs[0][0].name)
        return "mx_%s_%s" % (head, kind)

    # ---------------------------------------------------------------- tracing
    def interpret(self, arg_vals, aux_vals, is_train, rng):
        """Run the graph on jax values. Returns (outputs, new_aux_tuple)."""
        import jax

        vals = {}
        new_aux = list(aux_vals)
        for node in self.topo:
            if node.is_variable:
                if node.name in self._arg_index:
                    vals[(id(node), 0)] = arg_vals[self._arg_index[node.name]]
                else:
                    vals[(id(node), 0)] = aux_vals[self._aux_index[node.name]]
                continue
            opdef = get_op(node.op)
            parsed = node.parsed_attrs()
            n_aux = len(opdef.aux_names(parsed))
            ins = [vals[(id(inp), oi)] for inp, oi in node.inputs]
            # trace-time only: every HLO instruction this node lowers to
            # carries the node's name in its op_name metadata, so a trace
            # viewer shows which layers a fusion.N holds
            with jax.named_scope(node.name):
                dev = self._node_devices.get(id(node))
                if dev is not None:
                    # cross-device copy at a ctx-group boundary
                    ins = [jax.device_put(x, dev) for x in ins]
                node_rng = None
                if opdef.needs_rng:
                    node_rng = jax.random.fold_in(rng, self._rng_ids[id(node)])
                outs, aux_out = opdef.apply(
                    parsed,
                    ins[: len(ins) - n_aux] if n_aux else ins,
                    aux=ins[len(ins) - n_aux :] if n_aux else [],
                    is_train=is_train,
                    rng=node_rng,
                )
            for i, o in enumerate(outs):
                vals[(id(node), i)] = o
            if n_aux:
                for (inp, _), new in zip(node.inputs[len(node.inputs) - n_aux :], aux_out):
                    if not inp.is_variable:
                        raise MXNetError(
                            "aux input of %s must be a variable" % node.name
                        )
                    new_aux[self._aux_index[inp.name]] = new
        outputs = tuple(vals[(id(n), i)] for n, i in self.outputs)
        return outputs, tuple(new_aux)

    # --------------------------------------------------------------- compiled
    def _fwd(self, is_train):
        key = ("fwd", is_train)
        if key in self._jit_cache:
            return self._jit_cache[key]
        import jax

        def run(args, aux, rng):
            return self.interpret(args, aux, is_train, rng)

        name = self.program_name("fwd")
        store = None if is_train else self.store
        if self.donated:
            fn = _DonatedForward(
                run, name, [self._arg_index[n] for n in self.donated],
                len(self.arg_names), store)
        else:
            fn = jax.jit(_named(run, name))
            if store is not None:
                fn = store.program(fn, name)
        self._jit_cache[key] = fn
        return fn

    def _fwd_bwd_cached(self, with_head_grads):
        key = ("fwd_bwd", with_head_grads)
        if key not in self._jit_cache:
            self._jit_cache[key] = self._fwd_bwd(with_head_grads)
        return self._jit_cache[key]

    def _fwd_bwd(self, with_head_grads):
        """One XLA computation: forward + full backward (the reference's
        InitFullGraph fwd+bwd graph, graph_executor.cc:178)."""
        import jax
        import jax.numpy as jnp

        def run(args, aux, head_grads, rng):
            def f(a):
                outs, new_aux = self.interpret(a, aux, True, rng)
                return outs, new_aux

            outs, vjp_fn, new_aux = jax.vjp(f, args, has_aux=True)
            if with_head_grads:
                cot = tuple(h.astype(o.dtype) for h, o in zip(head_grads, outs))
            else:
                # loss-style outputs: custom-vjp loss ops ignore the incoming
                # cotangent, so ones is the identity head gradient
                cot = tuple(jnp.ones_like(o) for o in outs)
            (grads,) = vjp_fn(cot)
            return outs, grads, new_aux

        return jax.jit(_named(run, self.program_name("fwd_bwd")))


class Executor:
    """A bound computation (reference: python/mxnet/executor.py)."""

    def __init__(self, symbol, ctx: Context, arg_arrays, grad_arrays, grad_req, aux_arrays, program=None):
        self._symbol = symbol
        self._ctx = ctx
        self._prog = program or _GraphProgram(symbol)
        self.arg_arrays: List[NDArray] = list(arg_arrays)
        self.grad_arrays: List[Optional[NDArray]] = list(grad_arrays)
        self.aux_arrays: List[NDArray] = list(aux_arrays)
        self._grad_req: List[str] = list(grad_req)
        self.outputs: List[NDArray] = []
        self.arg_dict: Dict[str, NDArray] = dict(zip(self._prog.arg_names, self.arg_arrays))
        self.grad_dict: Dict[str, Optional[NDArray]] = dict(zip(self._prog.arg_names, self.grad_arrays))
        self.aux_dict: Dict[str, NDArray] = dict(zip(self._prog.aux_names, self.aux_arrays))
        self.output_dict: Dict[str, NDArray] = {}
        self._last_rng = None
        self._monitor_callback = None
        self._cached_vjp = None
        self._noted = set()  # (jit key, head signature) already classified

    # ----------------------------------------------------------------- running
    def _collect(self):
        args = tuple(a._jax() for a in self.arg_arrays)
        aux = tuple(a._jax() for a in self.aux_arrays)
        return args, aux

    def _next_rng(self):
        """This call's key: drawn from the global stream where the graph has
        a random node, the process's constant key where it has none (the
        program ignores it, and a draw is a device dispatch)."""
        from . import random as _random

        self._last_rng = _random._next_key() if self._prog._rng_ids \
            else _random._constant_key()
        return self._last_rng

    def rebind(self, names, arrays):
        """Hand ``arrays`` on as the arguments ``names``: a buffer that is
        already the argument's shape and type changes hands by reference
        (``executor.rebind``), any other value is written as ``a[:] = v``
        writes it (``executor.rebind_copy``)."""
        args = self.arg_dict
        taken = [args[n]._set_jax(a) for n, a in zip(names, arrays)]
        if _tm.enabled():
            _tm.counter("executor.rebind").inc(sum(taken))
            _tm.counter("executor.rebind_copy").inc(len(taken) - sum(taken))

    def _set_outputs(self, outs):
        self.outputs = [NDArray(chunk=_Chunk(o, self._ctx), shape=o.shape) for o in outs]
        self.output_dict = dict(zip(self._prog.output_names, self.outputs))
        if self._monitor_callback is not None:
            for name, arr in self.output_dict.items():
                self._monitor_callback(name, arr)
        return self.outputs

    def release_outputs(self):
        """Forget the last run's outputs, so the device may free them: after
        a run whose results nobody reads (a warm-up dispatch), they would
        otherwise live until the next ``forward`` has made its own."""
        self.outputs, self.output_dict = [], {}

    def _write_aux(self, new_aux):
        for arr, new in zip(self.aux_arrays, new_aux):
            arr._set_jax(new)

    def _apply_grads(self, grads):
        import jax
        import jax.numpy as jnp

        for garr, g, req in zip(self.grad_arrays, grads, self._grad_req):
            if req == "null" or garr is None:
                continue
            if g.dtype == jax.dtypes.float0:
                # integer-typed argument (e.g. token ids): no tangent space
                continue
            if req == "add":
                garr._set_jax(garr._jax() + g.astype(garr.dtype))
            else:  # write
                garr._set_jax(g.astype(garr.dtype))

    def forward(self, is_train=False, **kwargs):
        """Run forward; optional kwargs copy new values into bound args
        (reference: executor.py forward).

        Cost note: every train-mode forward re-runs the jax.vjp
        linearization (a Python retrace, unlike the cached fused
        forward_backward program) and pins the residual set on device until
        ``backward()`` or the next forward — callers that never backward
        should pass ``is_train=False`` (or use Module's fused path) to skip
        both costs.

        With ``is_train=True`` the forward is run under ``jax.vjp`` and the
        vjp closure (holding the forward-time residuals on device, like the
        reference's retained activations) is cached so a later
        ``backward()`` executes ONLY the backward computation — the manual
        forward/backward idiom costs 1x fwd + 1x bwd, same as
        ``forward_backward``'s single fused program."""
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("unknown argument %r" % k)
            self.arg_dict[k][:] = v
        args, aux = self._collect()
        rng = self._next_rng()
        sp = _tm.NULL_SPAN
        if _tm.enabled():
            sp = _tm.span("executor.forward", train=bool(is_train))
            self._note_telemetry(sp, ("fwd", bool(is_train)), args, aux)
        # release the previous step's residuals BEFORE tracing the new vjp —
        # otherwise two full activation sets coexist on device
        self._cached_vjp = None
        with sp:
            if is_train and any(r != "null" for r in self._grad_req):
                import jax

                fn = self._prog._fwd(True)

                def f(a):
                    return fn(a, aux, rng)

                outs, vjp_fn, new_aux = jax.vjp(f, args, has_aux=True)
                self._cached_vjp = (vjp_fn, tuple(o.dtype for o in outs))
            else:
                outs, new_aux = self._prog._fwd(bool(is_train))(args, aux, rng)
        if is_train:
            self._write_aux(new_aux)
        return self._set_outputs(outs)

    def compiled(self, is_train=False):
        """The bound forward program as it is dispatched (donated arguments
        donated; the stored program where one is run, so a process that
        loaded it traces nothing here either), compiled ahead of time at the
        bound shapes: what
        ``cost_analysis()`` and ``memory_analysis()`` are asked of. The AOT
        compile does not share jit's executable cache, so this costs one
        compile (or one load from the persistent compile cache). Executes
        nothing, donates nothing and draws no random key."""
        import jax

        args, aux = self._collect()
        return self._prog._fwd(bool(is_train)).lower(
            args, aux, jax.random.PRNGKey(0)).compile()

    def cost_analysis(self, is_train=False):
        """XLA's cost analysis of the bound forward program at the bound
        shapes — a dict with ``flops`` and ``bytes accessed`` (the
        compiler's count for its own program, not the least the algorithm
        needs)."""
        return _cost_of(self.compiled(is_train))

    def _note_telemetry(self, sp, key, args, aux, extra=()):
        """Count compile/cache_hit/retrace for this call and attach the
        classification (plus the GL201-203 diagnosis on retraces) to the
        span. Caller guards with ``_tm.enabled()``.

        An executor's arguments keep the shapes and types it was bound
        with (``NDArray._set_jax`` shapes and casts every value to its
        chunk's; ``reshape`` makes a new executor), so a jit entry it has
        noted once is a cache hit ever after, without looking at an
        argument. Only its first call of an entry asks the program, which
        may be shared (``Executor(program=...)``) and so may have seen
        another signature, or this one, before."""
        if (key, extra) in self._noted:
            kind, reason = "cache_hit", None
        else:
            kind, reason = self._prog._note_call(key, args, aux, extra)
            self._noted.add((key, extra))
        _tm.counter("executor." + kind).inc()
        sp.set(cache=kind)
        if reason is not None:
            sp.set(retrace_reason=reason)
            _tm.gauge("executor.last_retrace_reason").set(reason)

    def backward(self, out_grads=None):
        """Run backward, accumulating into grad arrays per grad_req.

        After ``forward(is_train=True)`` this applies the cached vjp —
        gradients come from the forward-time activations (reference
        semantics) with no forward recompute. Without a cached vjp (e.g.
        ``backward()`` cold) it falls back to the fused fwd+bwd program."""
        if out_grads is not None:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            if len(out_grads) != len(self._prog.outputs):
                raise MXNetError(
                    "backward: expected %d head gradients, got %d"
                    % (len(self._prog.outputs), len(out_grads))
                )
        cached = getattr(self, "_cached_vjp", None)
        if cached is not None:
            import jax.numpy as jnp

            vjp_fn, out_dtypes = cached
            if out_grads is None:
                # loss-style outputs: custom-vjp loss ops ignore the incoming
                # cotangent, so ones is the identity head gradient
                cot = tuple(jnp.ones(o.shape, dt)
                            for o, dt in zip(self.outputs, out_dtypes))
            else:
                cot = tuple(g._jax().astype(dt)
                            for g, dt in zip(out_grads, out_dtypes))
            with _tm.span("executor.backward", path="cached_vjp"):
                (grads,) = vjp_fn(cot)
            self._cached_vjp = None  # residuals consumed — free the activations
            self._apply_grads(grads)
            return
        args, aux = self._collect()
        rng = self._last_rng if self._last_rng is not None else self._next_rng()
        with_head = out_grads is not None
        head = tuple(g._jax() for g in out_grads) if with_head else ()
        sp = _tm.NULL_SPAN
        if _tm.enabled():
            sp = _tm.span("executor.backward", path="fused_fwd_bwd")
            self._note_telemetry(
                sp, ("fwd_bwd", with_head), args, aux,
                extra=_signature(head))
        with sp:
            fn = self._prog._fwd_bwd_cached(with_head)
            outs, grads, _ = fn(args, aux, head, rng)
        self._apply_grads(grads)

    def forward_backward(self, out_grads=None, is_train=True):
        """Fused fwd+bwd: ONE compiled XLA computation per training step —
        the TPU-native analogue of the reference's cached-op bulk segments
        (graph_executor.cc:690 InitOpSegs)."""
        args, aux = self._collect()
        rng = self._next_rng()
        self._cached_vjp = None  # this step supersedes any cached forward
        with_head = out_grads is not None
        head = tuple(g._jax() for g in out_grads) if with_head else ()
        sp = _tm.NULL_SPAN
        if _tm.enabled():
            sp = _tm.span("executor.forward_backward", train=bool(is_train))
            self._note_telemetry(
                sp, ("fwd_bwd", with_head), args, aux,
                extra=_signature(head))
        with sp:
            fn = self._prog._fwd_bwd_cached(with_head)
            outs, grads, new_aux = fn(args, aux, head, rng)
        self._write_aux(new_aux)
        self._apply_grads(grads)
        return self._set_outputs(outs)

    # ------------------------------------------------------------------ misc
    def copy_params_from(self, arg_params, aux_params=None, allow_extra_params=False):
        for name, arr in (arg_params or {}).items():
            if name in self.arg_dict:
                self.arg_dict[name][:] = arr
            elif not allow_extra_params:
                raise MXNetError("Found name %r not in executor arguments" % name)
        for name, arr in (aux_params or {}).items():
            if name in self.aux_dict:
                self.aux_dict[name][:] = arr
            elif not allow_extra_params:
                raise MXNetError("Found name %r not in executor aux states" % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return a new executor bound to new shapes (reference:
        executor.py reshape). XLA recompiles per shape — same economics as the
        reference's executor-per-bucket. ``partial_shaping`` keeps old shapes
        for arguments the new hints leave undetermined; without
        ``allow_up_sizing`` an argument may not grow."""
        if partial_shaping:
            arg_shapes, _, aux_shapes = self._symbol.infer_shape_partial(**kwargs)
        else:
            arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
            if arg_shapes is None:
                raise MXNetError(
                    "reshape: insufficient shape info (pass partial_shaping=True "
                    "to keep old shapes for undetermined arguments)"
                )

        def _renew(arr, shape, name):
            if shape is None:
                if not partial_shaping:
                    raise MXNetError("reshape: shape of %r undetermined" % name)
                return arr, False
            if tuple(arr.shape) == tuple(shape):
                return arr, False
            new_size = int(np.prod(shape))
            if new_size > arr.size and not allow_up_sizing:
                raise MXNetError(
                    "reshape: new shape %s of %r is larger than original %s; pass "
                    "allow_up_sizing=True to permit reallocation" % (shape, name, arr.shape)
                )
            return zeros(shape, ctx=self._ctx, dtype=arr.dtype), True

        new_args, new_grads, new_aux = [], [], []
        for name, arr, garr, shape in zip(
            self._prog.arg_names, self.arg_arrays, self.grad_arrays, arg_shapes
        ):
            na, changed = _renew(arr, shape, name)
            new_args.append(na)
            if garr is None:
                new_grads.append(None)
            else:
                new_grads.append(zeros(na.shape, ctx=self._ctx, dtype=garr.dtype) if changed else garr)
        for name, arr, shape in zip(self._prog.aux_names, self.aux_arrays, aux_shapes):
            new_aux.append(_renew(arr, shape, name)[0])
        exe = Executor(self._symbol, self._ctx, new_args, new_grads, self._grad_req, new_aux, program=self._prog)
        # keep the pre-rewrite symbol identity: a reshaped executor handed
        # to bind(shared_exec=...) must still match the user's symbol
        exe._orig_symbol = getattr(self, "_orig_symbol", self._symbol)
        return exe

    def set_monitor_callback(self, callback):
        self._monitor_callback = callback

    def debug_str(self):
        return self._symbol.debug_str()


# -------------------------------------------------------------------- binding
def _normalize_grad_req(grad_req, arg_names):
    if isinstance(grad_req, str):
        return [grad_req] * len(arg_names)
    if isinstance(grad_req, (list, tuple)):
        if len(grad_req) != len(arg_names):
            raise MXNetError("grad_req list length mismatch")
        return list(grad_req)
    if isinstance(grad_req, dict):
        return [grad_req.get(n, "null") for n in arg_names]
    raise TypeError("grad_req must be str/list/dict")


def _lint_at_bind(symbol, arg_arrays, arg_names, aux_arrays, aux_names,
                  train=True):
    """MXNET_GRAPHLINT=warn|error hook: run the static passes with the
    concrete bind shapes/dtypes (analysis/: the nnvm-attribute-pass
    analogue). ``warn`` logs findings; ``error`` raises MXNetError with the
    structured report instead of letting a broken graph reach jit tracing.
    ``train`` steers the GL5xx memory planner: a grad-less bind plans
    forward-only liveness, a training bind adds grads + optimizer state."""
    from .analysis import graphlint_mode, lint_bind

    mode = graphlint_mode()
    if mode is None:
        return
    shapes = {n: tuple(a.shape) for n, a in zip(arg_names, arg_arrays)
              if a is not None}
    types = {n: np.dtype(a.dtype) for n, a in zip(arg_names, arg_arrays)
             if a is not None}
    shapes.update({n: tuple(a.shape) for n, a in zip(aux_names, aux_arrays)})
    types.update({n: np.dtype(a.dtype) for n, a in zip(aux_names, aux_arrays)})
    lint_bind(symbol, shapes, types, mode, target="bind", train=train)


def _rewrite_at_bind(symbol, args, grad_req, aux_states):
    """MXNET_GRAPHREWRITE=on|verify hook: run the Symbol→Symbol rewrite
    pipeline (analysis/rewrite.py — const fold, CSE, canonicalize, DCE,
    optional bf16 legalization) with the concrete bind shapes/dtypes and
    bind the REWRITTEN graph. Under ``verify`` the GL6xx provenance
    verifier gates the result (GL601/602/604 raise). Any failure falls
    back to the original symbol — a rewrite must never sink a bind."""
    from .analysis.rewrite import graphrewrite_mode, rewrite_for_bind

    if graphrewrite_mode() is None:
        return symbol
    shapes, types = {}, {}
    named = (dict(args) if isinstance(args, dict)
             else dict(zip(symbol.list_arguments(), args or [])))
    if isinstance(aux_states, dict):
        named.update(aux_states)
    elif aux_states:
        named.update(zip(symbol.list_auxiliary_states(), aux_states))
    for n, a in named.items():
        if a is not None:
            shapes[n] = tuple(a.shape)
            types[n] = np.dtype(a.dtype)
    return rewrite_for_bind(symbol, shapes, types, grad_req=grad_req,
                            target="bind")[0]


def bind(symbol, ctx, args, args_grad=None, grad_req="write", aux_states=None, shared_exec=None, group2ctx=None):
    """Bind NDArrays to a symbol's arguments (reference: symbol.py:917 bind →
    Executor::Bind, graph_executor.cc:936)."""
    if _tm.enabled():
        _tm.counter("executor.bind").inc()
    orig_symbol = symbol
    if shared_exec is not None and (
            shared_exec._symbol is symbol
            or getattr(shared_exec, "_orig_symbol", None) is symbol):
        # reuse the shared program's (possibly rewritten) symbol so the
        # jit cache carries over (reshape/bucketing path)
        symbol = shared_exec._symbol
    else:
        symbol = _rewrite_at_bind(symbol, args, grad_req, aux_states)
    with _tm.span("executor.bind", symbol=symbol.name,
                  shared=shared_exec is not None):
        if shared_exec is not None and shared_exec._symbol is symbol \
                and shared_exec._prog.group2ctx == dict(group2ctx or {}):
            prog = shared_exec._prog
        else:
            prog = _GraphProgram(symbol, group2ctx=group2ctx)
    arg_names = prog.arg_names
    aux_names = prog.aux_names
    ctx = Context(ctx) if not isinstance(ctx, Context) else ctx

    if isinstance(args, dict):
        missing = [n for n in arg_names if n not in args]
        if missing:
            raise MXNetError("bind: missing arguments %s" % missing)
        arg_arrays = [args[n] for n in arg_names]
    else:
        if len(args) != len(arg_names):
            raise MXNetError("bind: expected %d args, got %d" % (len(arg_names), len(args)))
        arg_arrays = list(args)

    reqs = _normalize_grad_req(grad_req, arg_names)
    if args_grad is None:
        grad_arrays = [None] * len(arg_names)
        reqs = ["null"] * len(arg_names)
    elif isinstance(args_grad, dict):
        grad_arrays = [args_grad.get(n) for n in arg_names]
        reqs = [r if g is not None else "null" for r, g in zip(reqs, grad_arrays)]
    else:
        grad_arrays = list(args_grad)

    if aux_states is None:
        aux_arrays = []
        for n in aux_names:
            raise MXNetError("bind: missing aux state %r" % n)
    elif isinstance(aux_states, dict):
        missing = [n for n in aux_names if n not in aux_states]
        if missing:
            raise MXNetError("bind: missing aux states %s" % missing)
        aux_arrays = [aux_states[n] for n in aux_names]
    else:
        aux_arrays = list(aux_states)
        if len(aux_arrays) != len(aux_names):
            raise MXNetError("bind: expected %d aux states, got %d" % (len(aux_names), len(aux_arrays)))

    _lint_at_bind(symbol, arg_arrays, arg_names, aux_arrays, aux_names,
                  train=any(r != "null" and g is not None
                            for r, g in zip(reqs, grad_arrays)))
    exe = Executor(symbol, ctx, arg_arrays, grad_arrays, reqs, aux_arrays, program=prog)
    # the caller's symbol, pre-rewrite: reshape()/shared_exec identity
    # checks and debugging compare against what the user actually built
    exe._orig_symbol = orig_symbol
    return exe


def simple_bind(symbol, ctx, grad_req="write", type_dict=None, group2ctx=None, shared_exec=None, **kwargs):
    """Infer shapes/types from kwarg shapes, allocate all arrays, bind
    (reference: symbol.py:836 simple_bind)."""
    shape_hints = {k: tuple(v) for k, v in kwargs.items() if v is not None}
    type_hints = {k: np_dtype(v) for k, v in (type_dict or {}).items()}
    try:
        res = symbol._infer_impl(shape_hints, type_hints, partial=False)
    except Exception as e:
        from .analysis import graphlint_mode

        if graphlint_mode() is not None:
            # diagnose the failure with the full pass suite: structured
            # per-node findings with provenance instead of a jit traceback
            from .analysis import lint

            report = lint(symbol, shapes=shape_hints, types=type_hints,
                          strict_shapes=True, target="simple_bind")
            if report.errors:
                raise MXNetError(
                    "simple_bind failed: %s\ngraphlint diagnosis:\n%s"
                    % (e, report.format(min_severity="warning")))
        if isinstance(e, MXNetError):
            raise MXNetError("simple_bind failed: %s" % e)
        raise
    arg_shapes, out_shapes, aux_shapes, arg_types, out_types, aux_types = res
    ctx = Context(ctx) if not isinstance(ctx, Context) else ctx

    arg_names = symbol.list_arguments()
    reqs = _normalize_grad_req(grad_req, arg_names)
    arg_arrays = [zeros(s, ctx=ctx, dtype=t) for s, t in zip(arg_shapes, arg_types)]
    grad_arrays = [
        zeros(s, ctx=ctx, dtype=t) if r != "null" else None
        for s, t, r in zip(arg_shapes, arg_types, reqs)
    ]
    aux_arrays = [zeros(s, ctx=ctx, dtype=t) for s, t in zip(aux_shapes, aux_types)]
    return bind(
        symbol,
        ctx,
        arg_arrays,
        args_grad=grad_arrays,
        grad_req=reqs,
        aux_states=aux_arrays,
        shared_exec=shared_exec,
        group2ctx=group2ctx,
    )
