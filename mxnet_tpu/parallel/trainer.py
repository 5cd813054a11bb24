"""SPMDTrainer: the whole training step as one sharded XLA computation.

Replaces the reference's hot path end to end (SURVEY.md §3.1): where
``Module.fit`` drove DataParallelExecutorGroup.forward/backward per device and
then KVStore push/pull per key (executor_group.py:355/481, model.py:88-116),
here forward + backward + gradient all-reduce + optimizer update compile into
a single ``jax.jit`` over a device mesh. The gradient psum never appears in
user code — params are laid out replicated (or model-axis-sharded) while the
batch is data-axis-sharded, so XLA's sharding propagation inserts the
all-reduce, batching all keys of the step into fused collectives riding ICI
(the hand-tuned priority queues of model.py:95-110 become the compiler's
latency hiding).
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from ..base import MXNetError
from .optim import make_functional_optimizer
from .sharding import ShardingRules

__all__ = ["SPMDTrainer"]


class _TrainState:
    """The mutable training state (params / aux / optimizer state) in one
    cell, so several trainers can SHARE it: bucketing compiles one step per
    bucket shape while every bucket trains the same weights — the
    executor-per-bucket economics of the reference's shared memory pool
    (graph_executor.cc:348-351) with state sharing instead of buffer sharing.

    ``dirty`` flags device state newer than any host copy (checkpointing and
    exec-group refresh read it through SPMDStepAdapter.params_dirty)."""

    __slots__ = ("params", "aux", "opt_state", "dirty")

    def __init__(self):
        self.params = {}
        self.aux = {}
        self.opt_state = None
        self.dirty = False


class SPMDTrainer:
    """Train a Symbol over a mesh.

    Parameters
    ----------
    symbol : the network (loss heads as outputs, e.g. SoftmaxOutput).
    mesh : jax.sharding.Mesh (see parallel.make_mesh).
    data_names / label_names : input argument names.
    optimizer / optimizer_params : functional optimizer spec (optim.py).
    rules : ShardingRules (defaults to batch-on-'data', params replicated or
        tensor-sharded on 'model' when present).
    remat : rematerialise the forward during backward (jax.checkpoint) — the
        MXNET_BACKWARD_DO_MIRROR memory/compute trade. May also be a policy
        name: 'dots' (save matmul/conv outputs, recompute elementwise/BN —
        the bytes-for-FLOPs trade docs/PERF.md recommends on HBM-bound
        chips), 'nothing' (recompute everything), or True (save-nothing
        default checkpoint).
    compute_dtype : e.g. 'bfloat16' — cast inputs+params for compute, keep
        fp32 master weights and fp32 grads (MXU fast path).
    """

    def __init__(self, symbol, mesh, data_names=("data",),
                 label_names=("softmax_label",), optimizer="sgd",
                 optimizer_params=None, rules: Optional[ShardingRules] = None,
                 remat=False, compute_dtype=None):
        # remat accepts False | True | 'dots' | 'nothing'
        from ..executor import _GraphProgram

        self.symbol = symbol
        self.mesh = mesh
        self.rules = rules or ShardingRules(mesh)
        self._prog = _GraphProgram(symbol)
        self._remat = remat
        self._compute_dtype = np.dtype(compute_dtype) if compute_dtype else None

        arg_names = self._prog.arg_names
        self.input_names = [n for n in list(data_names) + list(label_names) if n in arg_names]
        self.param_names = [n for n in arg_names if n not in self.input_names]
        self.aux_names = self._prog.aux_names

        opt_kwargs = dict(optimizer_params or {})
        if isinstance(optimizer, str):
            # mirror make_functional_optimizer's default lr
            self._opt_static_lr = float(opt_kwargs.get("learning_rate", 0.01))
            self._opt_init, self._opt_apply = make_functional_optimizer(
                optimizer, **opt_kwargs)
        else:
            # pre-built (init, apply) pair, e.g. from functional_from_optimizer;
            # its learning rate is baked into the closure — pass lr=None
            # through so apply() uses it, unless the caller overrides per step
            self._opt_static_lr = None
            self._opt_init, self._opt_apply = optimizer

        self._state = _TrainState()
        self._step_fn = None
        self._megastep_fns = {}  # (n, with_lr) -> jitted N-step scan
        self._step_count = 0
        self._seed = 0
        self._base_key = None
        self._spans_cache = None
        # NaN/Inf anomaly guard (MXNET_ANOMALY_GUARD, docs/RESILIENCE.md):
        # mode is read when the step compiles; skipped_steps counts dropped
        # updates in skip mode
        self._anomaly_mode = None
        self.skipped_steps = 0

    # ----------------------------------------------------------- shared state
    @property
    def params(self) -> Dict:
        return self._state.params

    @params.setter
    def params(self, v):
        self._state.params = v

    @property
    def aux(self) -> Dict:
        return self._state.aux

    @aux.setter
    def aux(self, v):
        self._state.aux = v

    @property
    def opt_state(self):
        return self._state.opt_state

    @opt_state.setter
    def opt_state(self, v):
        self._state.opt_state = v

    def adopt_state(self, other: "SPMDTrainer"):
        """Share another trainer's state cell — the bucketing contract: same
        weights, a differently-shaped compiled step per bucket."""
        if set(self.param_names) != set(other.param_names) or \
                set(self.aux_names) != set(other.aux_names):
            raise MXNetError(
                "cannot share training state: bucket symbols disagree on "
                "parameter names")
        self._state = other._state

    # ------------------------------------------------------------------ init
    def init_params(self, data_shapes, label_shapes=None, initializer=None,
                    dtype="float32", seed=0):
        """Infer all shapes, initialize params on host, lay them out on the
        mesh per the sharding rules (committed arrays — jit respects them)."""
        import jax
        import jax.numpy as jnp

        from ..initializer import InitDesc, Xavier

        initializer = initializer or Xavier(factor_type="in", magnitude=2.0)
        hints = dict(data_shapes)
        hints.update(label_shapes or {})
        arg_shapes, _, aux_shapes = self.symbol.infer_shape(**hints)
        arg_map = dict(zip(self._prog.arg_names, arg_shapes))
        aux_map = dict(zip(self.aux_names, aux_shapes))
        attrs = self.symbol.attr_dict()
        from .. import random as _rnd

        _rnd.seed(seed)  # deterministic init regardless of prior RNG use

        def host_init(name, shape):
            arr = np.zeros(shape, dtype=dtype)
            desc = InitDesc(name, attrs.get(name, {}))
            # initializer mutates NDArray-likes; adapt via a tiny shim
            from ..ndarray import array as nd_array

            tmp = nd_array(arr)
            initializer(desc, tmp)
            return tmp.asnumpy()

        self.params = {}
        for name in self.param_names:
            spec = self.rules.param_spec(name, arg_map[name])
            self.params[name] = self._put_global(host_init(name, arg_map[name]), spec)
        self.aux = {}
        for name in self.aux_names:
            self.aux[name] = self._put_global(
                host_init(name, aux_map[name]), _replicated(self.rules))
        self.opt_state = self._opt_init(self.params)
        return self

    def _put_global(self, host, spec):
        """Place a full host copy of an array onto the mesh. Works across
        processes because every process holds the complete value and serves
        just its addressable shards."""
        import jax
        import jax.numpy as jnp

        host = np.asarray(host)
        sharding = self.rules.named(spec)
        if self._spans_processes:
            return jax.make_array_from_callback(
                host.shape, sharding, lambda idx: host[idx])
        return jax.device_put(jnp.asarray(host), sharding)

    # ------------------------------------------------------------------ step
    def _make_step_fn(self):
        """The pure one-step function ``step(params, aux, opt_state,
        inputs, base_key, lr)`` — traced by ``_build_step`` as the
        single-dispatch jit AND by ``_build_megastep`` as the scan body,
        so the N-step megastep is bitwise the same math as N separate
        steps (the per-step PRNG key folds the optimizer counter, which a
        guard-skipped step does not advance — seeded dropout etc. stays
        reproducible across any N partitioning)."""
        import jax
        import jax.numpy as jnp

        prog = self._prog
        input_names = self.input_names
        param_names = self.param_names
        aux_names = self.aux_names
        cdt = self._compute_dtype
        opt_apply = self._opt_apply

        def assemble(params, inputs):
            vals = []
            for n in prog.arg_names:
                v = inputs[n] if n in input_names else params[n]
                if cdt is not None and jnp.issubdtype(v.dtype, jnp.floating):
                    v = v.astype(cdt)
                vals.append(v)
            return tuple(vals)

        mesh = self.mesh

        def fwd(params, aux_tuple, inputs, rng):
            from .mesh import trace_mesh

            with trace_mesh(mesh):  # mesh-aware ops (ring attention) dispatch
                outs, new_aux = prog.interpret(assemble(params, inputs), aux_tuple, True, rng)
            if cdt is not None:
                new_aux = tuple(a.astype(o.dtype) if hasattr(o, "dtype") else a
                                for a, o in zip(new_aux, aux_tuple))
            return outs, new_aux

        if self._remat:
            if self._remat == "dots":
                # keep MXU results, re-derive cheap elementwise/norm chains
                # in backward instead of round-tripping them through HBM
                pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                fwd = jax.checkpoint(fwd, policy=pol)
            elif self._remat == "nothing":
                fwd = jax.checkpoint(
                    fwd, policy=jax.checkpoint_policies.nothing_saveable)
            else:
                fwd = jax.checkpoint(fwd, static_argnums=())

        from ..base import anomaly_guard_mode

        guard = anomaly_guard_mode() if param_names else None
        self._anomaly_mode = guard

        def step(params, aux, opt_state, inputs, base_key, lr):
            # derive the per-step key on device from the optimizer counter —
            # no host→device key transfer inside the training loop
            rng = jax.random.fold_in(base_key, opt_state["t"])
            aux_tuple = tuple(aux[n] for n in aux_names)

            def f(p):
                return fwd(p, aux_tuple, inputs, rng)

            outs, vjp_fn, new_aux = jax.vjp(f, params, has_aux=True)
            # loss heads (SoftmaxOutput & friends) ignore the incoming
            # cotangent, so ones is the identity head gradient
            cot = tuple(jnp.ones_like(o) for o in outs)
            (grads,) = vjp_fn(cot)
            grads = {k: g.astype(params[k].dtype) for k, g in grads.items()
                     if hasattr(g, "dtype") and g.dtype != jax.dtypes.float0}
            for k in params:
                if k not in grads:
                    grads[k] = jnp.zeros_like(params[k])
            new_params, new_opt = opt_apply(params, grads, opt_state, lr=lr)
            new_aux_d = dict(zip(aux_names, new_aux))
            if guard is None:
                return new_params, new_aux_d, new_opt, outs
            # anomaly guard: one all-finite bit per gradient, fused into
            # the step — if ANY is false the whole update (params, aux,
            # optimizer state incl. its counter) selects the OLD values,
            # so a dropped step is a true no-op on device. The per-key
            # vector goes back to the host so step() can name the first
            # offending key (key order: sorted, matching step()).
            finite_vec = jnp.stack(
                [jnp.all(jnp.isfinite(grads[k])) for k in sorted(grads)])
            ok = jnp.all(finite_vec)

            def _sel(new, old):
                return jax.tree_util.tree_map(
                    lambda a, b: jnp.where(ok, a, b), new, old)

            return (_sel(new_params, params),
                    _sel(new_aux_d, dict(zip(aux_names, aux_tuple))),
                    _sel(new_opt, opt_state), outs, finite_vec)

        return step

    def _build_step(self):
        import jax

        from ..executor import _named

        return jax.jit(_named(self._make_step_fn(), "mx_train_step"),
                       donate_argnums=(0, 1, 2))

    def _build_megastep(self, n, with_lr):
        """N fused steps in ONE dispatch: a ``lax.scan`` of the SAME step
        body over batch-stacked inputs (leading axis N) and per-step lrs.
        The carry is (params, aux, opt_state); head outputs (and the
        anomaly guard's per-step finite vectors) stack along the scan
        axis. Dispatch-side state mutation stays identical to ``step`` —
        one jitted call, donated state."""
        import jax

        step = self._make_step_fn()
        guard = self._anomaly_mode

        def megastep(params, aux, opt_state, inputs, base_key, lrs):
            def body(carry, xs):
                p, a, o = carry
                inp, lr = xs if with_lr else (xs, None)
                res = step(p, a, o, inp, base_key, lr)
                if guard is None:
                    p2, a2, o2, outs = res
                    return (p2, a2, o2), (outs, ())
                p2, a2, o2, outs, fv = res
                return (p2, a2, o2), (outs, fv)

            xs = (inputs, lrs) if with_lr else inputs
            (p, a, o), (outs, fvs) = jax.lax.scan(
                body, (params, aux, opt_state), xs, length=n)
            if guard is None:
                return p, a, o, outs
            return p, a, o, outs, fvs

        from ..executor import _named

        return jax.jit(_named(megastep, "mx_train_megastep%d" % n),
                       donate_argnums=(0, 1, 2))

    @property
    def _spans_processes(self):
        """True when the mesh covers devices of more than one process —
        inputs must then be assembled from per-process local shards."""
        if self._spans_cache is None:
            import jax

            self._spans_cache = any(d.process_index != jax.process_index()
                                    for d in self.mesh.devices.flat)
        return self._spans_cache

    def _place_input(self, v, spec):
        """Lay a host batch out on the mesh. Multi-host: each process holds
        its local rows — ``make_array_from_process_local_data`` glues them
        into one global array along the data axis (SPMD analogue of the
        per-worker batches the reference feeds through kvstore ranks)."""
        import jax

        if self._spans_processes:
            return jax.make_array_from_process_local_data(
                self.rules.named(spec), np.asarray(v))
        return jax.device_put(v, self.rules.named(spec))

    def step(self, data: Dict, label: Optional[Dict] = None, lr=None):
        """Run one training step; returns the head outputs (jax arrays).

        ``lr`` optionally overrides the optimizer's static learning rate for
        this step (drives lr schedules without retracing)."""
        import jax
        import jax.numpy as jnp

        if not self.params and self.param_names:
            raise MXNetError("call init_params first")
        if self._step_fn is None:
            self._step_fn = self._build_step()
        from .. import telemetry as _tm

        sp = _tm.NULL_SPAN
        if _tm.enabled():
            _tm.counter("trainer.step").inc()
            _tm.counter("trainer.dispatches").inc()
            _tm.gauge("train.steps_per_dispatch").set(1)
            # host-side dispatch time only: the XLA step itself is async
            sp = _tm.span("trainer.step", n=self._step_count)
        with sp:
            with _tm.span("trainer.place"):
                placed = self._place_batch(data, label)
            if lr is None:
                lr = self._opt_static_lr  # may stay None → apply() uses its own lr
            self._step_count += 1
            with _tm.span("trainer.dispatch"):
                res = self._step_fn(
                    self.params, self.aux, self.opt_state, placed,
                    self._base_key,
                    None if lr is None else jnp.asarray(lr, "float32"))
                if self._anomaly_mode is None:
                    self.params, self.aux, self.opt_state, outs = res
                else:
                    self.params, self.aux, self.opt_state, outs, finite = res
                    self._check_anomaly(finite)
        return outs

    def step_many(self, data_list, label_list=None, lrs=None):
        """Run N training steps in ONE dispatch (the training megastep,
        docs/PERF.md §megasteps): the N batches are stacked on a leading
        axis and scanned through the same step body ``step`` traces, so
        the resulting weights are bitwise what N ``step`` calls produce —
        including NaN-guard skipped steps, which where-select the old
        state inside the scan exactly as they do outside it.

        ``lrs`` is an optional per-step learning-rate list (None entries
        fall back to the optimizer's static lr). Returns a list of N
        per-step head-output tuples (device arrays, sliced from the
        stacked scan outputs). Multi-process meshes are rejected:
        process-local shard assembly has no stacked equivalent."""
        import jax.numpy as jnp

        n = len(data_list)
        if n == 0:
            return []
        if not self.params and self.param_names:
            raise MXNetError("call init_params first")
        if n == 1:
            lr = lrs[0] if lrs else None
            outs = self.step(data_list[0],
                             (label_list or [None])[0], lr=lr)
            return [outs]
        if self._spans_processes:
            raise MXNetError(
                "step_many: multi-process meshes are not supported (the "
                "stacked batch cannot be assembled from process-local "
                "shards) — set MXNET_TRAIN_MEGASTEP_N=1")
        with_lr = False
        lr_vals = None
        if lrs is not None or self._opt_static_lr is not None:
            vals = [(None if lrs is None else lrs[i]) for i in range(n)]
            vals = [self._opt_static_lr if v is None else v for v in vals]
            if any(v is None for v in vals):
                raise MXNetError(
                    "step_many: per-step lr required when the optimizer "
                    "has no static learning rate")
            with_lr = True
            lr_vals = jnp.asarray(np.asarray(vals, np.float32))
        key = (n, with_lr)
        fn = self._megastep_fns.get(key)
        if fn is None:
            if self._step_fn is None:
                # step() and step_many() share _anomaly_mode; build the
                # single-step jit first so both read the same guard mode
                self._step_fn = self._build_step()
            fn = self._megastep_fns[key] = self._build_megastep(n, with_lr)
        from .. import telemetry as _tm

        sp = _tm.NULL_SPAN
        if _tm.enabled():
            _tm.counter("trainer.step").inc(n)
            _tm.counter("trainer.megastep").inc()
            _tm.counter("trainer.dispatches").inc()
            _tm.gauge("train.steps_per_dispatch").set(n)
            sp = _tm.span("trainer.megastep", n=self._step_count, steps=n)
        with sp:
            placed = self._place_batch_stacked(data_list, label_list)
            self._step_count += n
            res = fn(self.params, self.aux, self.opt_state, placed,
                     self._base_key, lr_vals)
            if self._anomaly_mode is None:
                self.params, self.aux, self.opt_state, outs = res
            else:
                self.params, self.aux, self.opt_state, outs, fvs = res
                self._check_anomaly(fvs)
        return [tuple(o[i] for o in outs) for i in range(n)]

    def _place_batch_stacked(self, data_list, label_list=None):
        """Stack N host batches on a leading scan axis and lay them out on
        the mesh: per-step sharding is the usual batch spec, the scan axis
        is unsharded (``P(None, *batch_spec)``)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        n = len(data_list)
        labels = label_list or [None] * n
        placed = {}
        for name in self.input_names:
            rows = []
            for i in range(n):
                inputs = dict(data_list[i])
                inputs.update(labels[i] or {})
                if name not in inputs:
                    raise MXNetError("missing input %r" % name)
                rows.append(np.asarray(inputs[name]))
            stacked = np.stack(rows, axis=0)
            spec = self.rules.batch_spec(rows[0].shape)
            sspec = P(*((None,) + tuple(spec)))
            placed[name] = jax.device_put(jnp.asarray(stacked),
                                          self.rules.named(sspec))
        if getattr(self, "_base_key", None) is None:
            self._base_key = jax.device_put(
                jax.random.PRNGKey(self._seed),
                self.rules.named(_replicated(self.rules)))
        return placed

    def _check_anomaly(self, finite_vec):
        """Host half of the anomaly guard: the device side already
        where-selected the old state if any gradient was non-finite; here
        the per-key vector is read back (this synchronizes the step — the
        guard trades async dispatch for the check, docs/RESILIENCE.md) to
        count the skip or raise naming the first offending key.

        A megastep hands a (N, keys) stack — one row per scanned step,
        checked in step order. The device side already skip-selected each
        offending step individually; in raise mode the error surfaces
        after the whole dispatch (the scan cannot stop mid-flight)."""
        from .. import telemetry as _tm

        fv = np.asarray(finite_vec)
        if fv.all():
            return
        if fv.ndim == 2:
            for row in fv:
                self._check_anomaly(row)
            return
        bad = sorted(self.params)[int(np.argmin(fv))]
        if self._anomaly_mode == "raise":
            raise MXNetError(
                "anomaly guard: non-finite (NaN/Inf) gradient for "
                "parameter %r at step %d — the fused step left params/"
                "optimizer state UN-updated (MXNET_ANOMALY_GUARD=raise)"
                % (bad, self._step_count))
        self.skipped_steps += 1
        if _tm.enabled():
            _tm.counter("trainer.skipped_steps").inc()
        import logging

        logging.getLogger("mxnet_tpu").warning(
            "anomaly guard: dropped step %d — non-finite gradient, first "
            "offending key %r (%d step(s) skipped so far)",
            self._step_count, bad, self.skipped_steps)

    def _place_batch(self, data, label=None):
        """Lay one batch out on the mesh per the sharding rules (shared by
        ``step`` and ``cost_analysis``)."""
        import jax
        import jax.numpy as jnp

        inputs = dict(data)
        inputs.update(label or {})
        placed = {}
        for n in self.input_names:
            if n not in inputs:
                raise MXNetError("missing input %r" % n)
            v = inputs[n]
            v = v if hasattr(v, "dtype") and not isinstance(v, np.ndarray) else jnp.asarray(np.asarray(v))
            placed[n] = self._place_input(v, self.rules.batch_spec(v.shape))
        if getattr(self, "_base_key", None) is None:
            self._base_key = jax.device_put(
                jax.random.PRNGKey(self._seed), self.rules.named(_replicated(self.rules)))
        return placed

    def cost_analysis(self, data, label=None):
        """XLA's cost analysis of the compiled training step — a dict with
        ``flops`` and ``bytes accessed`` (the quantities docs/PERF.md's
        roofline argument rests on). Lowers, does NOT execute the step.
        Note: the AOT lower/compile here does not share jit's executable
        cache, so this pays one extra compile — a perf-lab cost, not a
        training-loop one."""
        import jax.numpy as jnp

        from ..executor import _cost_of

        if not self.params and self.param_names:
            raise MXNetError("call init_params first")
        if self._step_fn is None:
            self._step_fn = self._build_step()
        placed = self._place_batch(data, label)
        lr = self._opt_static_lr
        return _cost_of(self._step_fn.lower(
            self.params, self.aux, self.opt_state, placed, self._base_key,
            None if lr is None else jnp.asarray(lr, "float32")).compile())

    # ------------------------------------------------------------------ misc
    def get_params(self):
        """Gather params/aux to host numpy (for checkpointing / Module interop)."""
        import jax

        if self._spans_processes:
            from jax.experimental.multihost_utils import process_allgather

            fetch = lambda v: np.asarray(process_allgather(v, tiled=True))
        else:
            fetch = lambda v: np.asarray(jax.device_get(v))
        gather = lambda d: {k: fetch(v) for k, v in d.items()}
        return gather(self.params), gather(self.aux)

    def set_params(self, arg_params, aux_params=None):
        for name, v in (arg_params or {}).items():
            if name in self.param_names:
                spec = self.rules.param_spec(name, np.shape(v))
                self.params[name] = self._put_global(np.asarray(v), spec)
        for name, v in (aux_params or {}).items():
            if name in self.aux_names:
                self.aux[name] = self._put_global(np.asarray(v), _replicated(self.rules))
        if self.opt_state is None and self.params:
            self.opt_state = self._opt_init(self.params)


def _replicated(rules):
    from jax.sharding import PartitionSpec as P

    return P()
