"""Pattern-based subgraph fusion over the Symbol DAG.

Two generations of machinery live here, one engine:

**Conv+BN (the first migrated pattern, PR 2/round-5 perf work).** The
reference reached vendor-kernel conv+BN throughput via cuDNN
(/root/reference/src/operator/cudnn_convolution-inl.h with the CUDNN BN /
fused-add epilogues of batch_norm.cu); the TPU translation is a graph pass
that rewrites eligible subgraphs onto the Pallas kernel in
``ops/pallas_conv_bn.py``. Three rewrites compose along the pre-activation
ResNet chain (BN -> relu -> Conv -> [+res] -> BN ...; models/resnet.py):

- **prologue fold**: a BatchNorm whose (relu) output feeds only eligible
  convolutions never materializes — its per-channel ``scale``/``shift`` ride
  into each consumer kernel's VMEM prologue (saves one activation write +
  one read per edge).
- **stats reuse**: a BatchNorm whose input carries kernel-emitted
  ``(sum, sum_sq)`` skips its statistics pass entirely (saves one activation
  read) whether or not it folds.
- **residual defer**: a convolution whose only consumer is an elementwise
  add runs *at the add site* with the other operand streamed into its
  epilogue (saves the separate read-read-write add pass), and the sum's
  statistics feed the next block's BatchNorm.

The plan is structural (built once per program from the Symbol DAG); the
per-shape engage/fallback decision is made at trace time against the
committed on-chip WINS table (``ops/fused_conv_bn_table.py``), overridable
with ``MXNET_FUSED_CONV_BN=0|1|auto``. Every fallback path degrades to the
ordinary XLA lowering, including mid-chain (a Deferred input materializes
its normalized activation once, cached, shared by all fallback consumers).

Autodiff: only the Pallas kernel is a custom_vjp; the per-channel BN math
here (mean/var from sums, scale/shift, moving-stat updates) is plain traced
JAX, so gradients for gamma/beta flow through ``scale32``/``shift32`` into
the kernel's hand-written f32-accumulated prologue cotangents.

**The generic pattern engine (this round).** ``ops/fusion_patterns.py``
declares matchers + fused lowerings for matmul+bias+act, attention,
norm+residual and elementwise chains; ``plan()`` roots each match in the
directive map (interior nodes elide behind ``Lazy`` markers), and the
per-(pattern, shape, dtype, device-kind) engage decision comes from the
persistent measure-and-cache autotuner (``fusion_tune.py``) — TVM's
measured-schedule discipline replacing the committed WINS table, which
remains the conv+BN seed/fallback when tuning is disabled
(``MXNET_FUSION_TUNE_DIR`` unset). ``MXNET_FUSED_PATTERNS`` selects and
forces patterns (docs/ENV_VARS.md); every fallback path — gate declined,
tuner rejected, lowering unavailable — is the bit-identical unfused graph.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from .ops.pallas_conv_bn import (_xla_conv, conv_block, conv_block_infer,
                                 plan_blocks, plan_bwd_blocks, strided_dims,
                                 supported)
from . import telemetry as _tm

__all__ = ["plan", "plan_sites", "execute", "resolve", "gate",
           "gate_explain", "bwd_mode", "conv_reject_reason",
           "bn_reject_reason", "infer_default", "quant_mode",
           "enabled_patterns", "gate_pattern_explain", "conv_schedule",
           "losers_note", "attention_trains_flash", "CONV_BN_KINDS"]

#: directive kinds owned by the conv+BN machinery — the executor masks these
#: (only) on inference executions where ``infer_default()`` declined, keeping
#: CPU eval numerics byte-identical to the unfused op-by-op lowering
CONV_BN_KINDS = frozenset({"conv", "bn", "relu_fold", "resadd"})


# --------------------------------------------------------------------- values
class Deferred:
    """A folded BN(+relu) output: ``relu(raw * scale + shift)``, not yet
    materialized. ``materialize()`` builds (and caches) the XLA elementwise
    form for consumers that fall back."""

    __slots__ = ("raw", "scale", "shift", "relu", "_mat")

    def __init__(self, raw, scale, shift, relu=False):
        self.raw, self.scale, self.shift, self.relu = raw, scale, shift, relu
        self._mat = None

    def with_relu(self):
        return Deferred(self.raw, self.scale, self.shift, relu=True)

    def materialize(self):
        if self._mat is None:
            out = _normalize(self.raw, self.scale, self.shift)
            if self.relu:
                out = jnp.maximum(out, 0)
            self._mat = out
        return self._mat


class WithStats:
    """A conv/add output plus the kernel's per-channel f32 (sum, sum_sq)."""

    __slots__ = ("c", "ssum", "ssq")

    def __init__(self, c, ssum, ssq):
        self.c, self.ssum, self.ssq = c, ssum, ssq


class PendingConv:
    """A conv deferred to its consuming residual add."""

    __slots__ = ("x", "w", "scale", "shift", "relu", "kernel", "stride",
                 "bwd", "bn")

    def __init__(self, x, w, scale, shift, relu, kernel, stride, bwd="xla",
                 bn=None):
        self.x, self.w = x, w
        self.scale, self.shift, self.relu = scale, shift, relu
        self.kernel, self.stride = kernel, stride
        self.bwd = bwd
        self.bn = bn

    def run(self, res):
        kind, mesh, _ = _mesh_kind()
        if kind == _MESH_DP:
            return _conv_block_sharded(
                mesh, self.x, self.w, self.scale, self.shift, res,
                self.kernel, self.stride, self.relu, self.bwd, self.bn)
        return conv_block(self.x, self.w, self.scale, self.shift, res,
                          self.kernel, self.stride, self.relu, True,
                          self.bwd, self.bn)


class Lazy:
    """A pattern-interior node's not-yet-computed output. Carries the node
    and its raw input values (possibly markers themselves); ``materialize()``
    runs the ordinary opdef — the bit-identical unfused semantics — and
    caches, so a marker consumed by both its pattern root (which fell back)
    and nothing else still computes at most once."""

    __slots__ = ("node", "ins", "_mat")

    def __init__(self, node, ins):
        self.node, self.ins = node, list(ins)
        self._mat = None

    def materialize(self):
        if self._mat is None:
            from .ops.registry import get_op

            vals = [resolve(v) for v in self.ins]
            outs, _ = get_op(self.node.op).apply(
                self.node.parsed_attrs(), vals, aux=[], is_train=False,
                rng=None)
            self._mat = outs[0]
        return self._mat


def resolve(v):
    """Any op that is not fusion-aware sees a plain tensor."""
    if isinstance(v, WithStats):
        return v.c
    if isinstance(v, (Deferred, Lazy)):
        return v.materialize()
    if isinstance(v, PendingConv):
        # defensive: plan() keeps graph-output convs out of the defer
        # rewrite, so a marker should never escape to a consumer that is
        # not the planned resadd — but if one does, its standalone value
        # (no residual) is exactly the conv output
        return v.run(None)[0]
    return v


# ------------------------------------------------------- normalize (custom_vjp)
@jax.custom_vjp
def _normalize(x, scale32, shift32):
    b = (1, -1) + (1,) * (x.ndim - 2)
    return x * scale32.astype(x.dtype).reshape(b) \
        + shift32.astype(x.dtype).reshape(b)


def _normalize_fwd(x, scale32, shift32):
    return _normalize(x, scale32, shift32), (x, scale32)


def _normalize_bwd(saved, dout):
    # explicit f32 accumulators for the per-channel reductions (plain
    # autodiff would reduce in the activation dtype — bf16 over B*H*W)
    x, scale32 = saved
    b = (1, -1) + (1,) * (x.ndim - 2)
    axes = (0,) + tuple(range(2, x.ndim))
    dx = dout * scale32.astype(dout.dtype).reshape(b)
    dout32 = dout.astype(jnp.float32)
    dscale = jnp.sum(dout32 * x.astype(jnp.float32), axis=axes)
    dshift = jnp.sum(dout32, axis=axes)
    return dx, dscale, dshift


_normalize.defvjp(_normalize_fwd, _normalize_bwd)


# ----------------------------------------------------------------------- plan
def _pair(v, fill):
    v = tuple(v or ())
    return v if len(v) == 2 else (fill, fill)


def conv_reject_reason(node):
    """The exact predicate that bars this Convolution from the Pallas path,
    or None when it is structurally eligible (shape gating still happens at
    trace time). The analysis subsystem (analysis/fusion_explain.py) reports
    these verbatim, so keep each reason a precise, single predicate."""
    if node.op != "Convolution":
        return "not a Convolution"
    if len(node.inputs) != 2:
        return "bias input present (no_bias=False): the kernel has no bias epilogue"
    a = node.parsed_attrs()
    kernel = tuple(a.get("kernel") or ())
    stride = _pair(a.get("stride"), 1)
    pad = _pair(a.get("pad"), 0)
    dilate = _pair(a.get("dilate"), 1)
    if a.get("num_group", 1) != 1:
        return "grouped convolution (num_group=%s != 1)" % a.get("num_group")
    if dilate != (1, 1):
        return "dilated convolution (dilate=%s)" % (dilate,)
    if kernel == (1, 1):
        if pad != (0, 0):
            return "1x1 kernel needs pad=(0, 0), got pad=%s" % (pad,)
        if stride not in ((1, 1), (2, 2)):
            return "1x1 kernel needs stride (1, 1) or (2, 2), got %s" % (stride,)
        return None
    if kernel == (3, 3):
        if pad != (1, 1):
            return "3x3 kernel needs pad=(1, 1), got pad=%s" % (pad,)
        if stride != (1, 1):
            return "3x3 kernel needs stride=(1, 1), got %s" % (stride,)
        return None
    return ("kernel %s has no Pallas variant (supported: 1x1 pad 0 stride "
            "1 or 2; 3x3 pad 1 stride 1)" % (kernel,))


def _conv_cfg(node):
    """(kernel, stride) if this Convolution can run on the Pallas path
    (structurally — shape gating happens at trace time), else None."""
    if conv_reject_reason(node) is not None:
        return None
    a = node.parsed_attrs()
    return tuple(a.get("kernel") or ()), _pair(a.get("stride"), 1)


def bn_reject_reason(node):
    """The exact predicate that bars this BatchNorm from the fusion plan,
    or None when eligible."""
    if node.op != "BatchNorm":
        return "not a BatchNorm"
    a = node.parsed_attrs()
    if a.get("use_global_stats"):
        return "use_global_stats=True: inference-style BN never runs the batch statistics pass the fusion reuses"
    if a.get("output_mean_var"):
        return "output_mean_var=True: the mean/var outputs must materialize, so the BN cannot stay folded"
    return None


def _bn_ok(node):
    return bn_reject_reason(node) is None


def enabled_patterns(infer=False):
    """Per-pattern mode map from ``MXNET_FUSED_PATTERNS``: name ->
    ``"auto"`` (engage per measured verdict), ``"1"`` (force the first
    candidate lowering), ``"0"`` (off), or a LOWERING NAME (force that
    specific candidate — ``attention=pallas_flash`` — where it exists for
    the site; prefix-matched, so a forced name also selects its schedule
    variants). Grammar: ``auto``/``all`` (every pattern in auto, the
    default), ``0``/``off``/``none``, or a comma list of names with
    optional forces (``attention,matmul_bias_act=1``) — listed patterns
    get their mode, unlisted ones are off. The conv+BN pattern is governed
    by its own ``MXNET_FUSED_CONV_BN[_BWD]`` knobs.

    ``infer=True`` is the serving/grad-less gate: when
    ``MXNET_FUSED_PATTERNS_INFER`` is set it overrides the training map on
    inference executions only (same grammar), so a serving fleet can pin
    its own pattern set — e.g. disable a pattern whose inference shapes
    were never tuned — without touching training behavior.

    The parse is memoized on the raw env string (the faultinject idiom):
    the per-site gate consults this map on every pattern execution during
    trace, so re-splitting the grammar there would be pure overhead.
    Callers get a fresh copy — ``plan()`` mutates its map."""
    from .ops.fusion_patterns import pattern_names

    names = pattern_names()
    env = os.environ.get("MXNET_FUSED_PATTERNS", "auto").strip().lower()
    if infer:
        env = os.environ.get("MXNET_FUSED_PATTERNS_INFER",
                             env).strip().lower() or env
    cached = _patterns_env_memo.get(env)
    if cached is not None:
        return dict(cached)
    modes = _parse_patterns_env(env, names)
    _patterns_env_memo[env] = modes
    return dict(modes)


def _parse_patterns_env(env, names):
    if env in ("", "auto", "all", "1"):
        return {n: "auto" for n in names}
    if env in ("0", "off", "none"):
        return {n: "0" for n in names}
    modes = {n: "0" for n in names}
    for item in env.split(","):
        item = item.strip()
        if not item:
            continue
        if item in ("auto", "all"):
            modes = {n: "auto" for n in names}
            continue
        name, _, val = item.partition("=")
        if name in modes:
            if val in ("0", "1"):
                modes[name] = val
            elif val in ("", "auto"):
                modes[name] = "auto"
            else:
                # a forced lowering NAME (e.g. pallas_flash). A value
                # matching no known lowering family warns once — a typo'd
                # value here used to read as "auto", and as a
                # never-matching name it would silently unfuse every site
                modes[name] = val
                if (val not in _warned_forced_vals
                        and not val.startswith(_LOWERING_FAMILIES)):
                    _warned_forced_vals.add(val)
                    import logging

                    logging.getLogger("mxnet_tpu").warning(
                        "MXNET_FUSED_PATTERNS treats %s=%r as a FORCED "
                        "lowering name, and it matches no known lowering "
                        "family %s: every site will run unfused (use "
                        "auto/0/1 for the mode grammar)",
                        name, val, list(_LOWERING_FAMILIES))
        else:
            global _warned_patterns_env
            if not _warned_patterns_env:
                _warned_patterns_env = True
                import logging

                logging.getLogger("mxnet_tpu").warning(
                    "MXNET_FUSED_PATTERNS names unknown pattern %r "
                    "(known: %s)", name, ", ".join(names))
    return modes


_warned_patterns_env = False
_warned_forced_vals = set()
_patterns_env_memo = {}
#: candidate-name families the patterns emit (forced-name validation)
_LOWERING_FAMILIES = ("pallas", "block_causal", "chunked_kv", "fused",
                      "onepass", "xla")


def plan_sites(directives):
    """Static per-pattern site inventory of one fusion plan:
    ``(pattern_sites, conv_bn_directive_count)``. Computed ONCE per bound
    program (``_GraphProgram.pattern_sites``) — consumers (serving cache,
    health probes, the graphlint --rewrite dump) read the cached inventory
    instead of re-walking the directive map."""
    sites, conv_bn = {}, 0
    for d in directives.values():
        if d["kind"] == "pattern":
            name = d["pat"].name
            sites[name] = sites.get(name, 0) + 1
        elif d["kind"] != "lazy":
            conv_bn += 1
    return sites, conv_bn


class _PlanCtx:
    """What pattern matchers may see of the graph: the consumer map, the
    program-output ids, and the directives built so far (``claimed``)."""

    __slots__ = ("consumers", "output_ids", "claimed")

    def __init__(self, consumers, output_ids, claimed):
        self.consumers, self.output_ids = consumers, output_ids
        self.claimed = claimed


def plan(topo, output_ids=()):
    """Build the fusion plan: id(node) -> directive dict. Structural only.

    Two passes: the conv+BN rewrites (unless ``MXNET_FUSED_CONV_BN=0``),
    then each enabled generic pattern (``enabled_patterns()``) in priority
    order over the still-unclaimed nodes — a matched root gets a
    ``pattern`` directive, its interior nodes ``lazy`` markers.

    ``output_ids`` are the ids of nodes whose outputs are PROGRAM outputs
    (executor passes them from the bound symbol). A graph-output node has an
    implicit extra consumer the ``consumers`` map cannot see: its value must
    materialize, so it is excluded from the prologue-fold rewrite (the fold
    would save nothing), from the residual-defer rewrite (a deferred
    conv's ``PendingConv`` marker would otherwise escape ``interpret()`` as
    a program output and fail at jit trace time under
    ``MXNET_FUSED_CONV_BN=1``), and from every pattern interior."""
    output_ids = frozenset(output_ids)
    consumers = {}
    for node in topo:
        for inp, oi in node.inputs:
            consumers.setdefault(id(inp), []).append((node, oi))
    order = {id(n): i for i, n in enumerate(topo)}

    directives = {}
    if os.environ.get("MXNET_FUSED_CONV_BN", "auto") != "0":
        _plan_conv_bn(topo, output_ids, consumers, order, directives)

    # a pattern is PLANNED when either the training or the inference map
    # enables it (the per-execution gate re-reads the right map); the plan
    # is shared by both execution modes of a program
    modes = enabled_patterns()
    for name, mode in enabled_patterns(infer=True).items():
        if modes.get(name, "0") == "0" and mode != "0":
            modes[name] = mode
    if any(v != "0" for v in modes.values()):
        from .ops.fusion_patterns import get_patterns

        ctx = _PlanCtx(consumers, output_ids, directives)
        for pat in get_patterns():
            if modes.get(pat.name, "0") == "0":
                continue
            for node in topo:
                if node.is_variable or id(node) in directives:
                    continue
                m = pat.match(node, ctx)
                if m is None:
                    continue
                directives[id(node)] = {"kind": "pattern", "pat": pat,
                                        "meta": m.meta}
                for n in m.interior:
                    directives[id(n)] = {"kind": "lazy"}
    return directives


def _plan_conv_bn(topo, output_ids, consumers, order, directives):
    """The conv+BN rewrite pass (prologue fold, stats reuse, residual
    defer) — fills ``directives`` in place."""
    conv_nodes = {}
    for node in topo:
        if node.is_variable:
            continue
        cfg = _conv_cfg(node)
        if cfg is not None:
            directives[id(node)] = {"kind": "conv", "kernel": cfg[0],
                                    "stride": cfg[1], "defer": False}
            conv_nodes[id(node)] = node
        elif _bn_ok(node):
            directives[id(node)] = {"kind": "bn", "fold": False}

    def _is_fusable_conv_data_edge(cons_node, producer):
        d = directives.get(id(cons_node))
        return (d is not None and d["kind"] == "conv"
                and cons_node.inputs[0][0] is producer)

    # prologue folds: BN (-> relu) whose every consumer is a fusable conv's
    # data input
    for node in topo:
        d = directives.get(id(node))
        if not d or d["kind"] != "bn":
            continue
        cons = consumers.get(id(node), [])
        if not cons:
            continue
        relu_node = None
        targets = [c for c, oi in cons if oi == 0]
        if len(cons) == 1 and len(targets) == 1:
            c0 = targets[0]
            if (c0.op == "Activation"
                    and c0.parsed_attrs().get("act_type") == "relu"):
                relu_node = c0
                targets = [c for c, oi in consumers.get(id(c0), []) if oi == 0]
                if len(targets) != len(consumers.get(id(c0), [])):
                    continue
        src = relu_node if relu_node is not None else node
        if id(node) in output_ids or id(src) in output_ids:
            continue  # the BN (or its relu) value materializes regardless
        if targets and all(_is_fusable_conv_data_edge(c, src)
                           for c in targets):
            d["fold"] = True
            if relu_node is not None:
                directives[id(relu_node)] = {"kind": "relu_fold"}

    # residual defers: elemwise_add with an operand whose only consumer is
    # the add and whose producer is a fusable conv
    for node in topo:
        if node.op != "elemwise_add" or len(node.inputs) != 2:
            continue
        best = None
        for slot, (inp, oi) in enumerate(node.inputs):
            if oi != 0 or id(inp) not in conv_nodes:
                continue
            if id(inp) in output_ids:
                continue  # program output: the conv must materialize
            if len(consumers.get(id(inp), [])) != 1:
                continue
            if best is None or order[id(inp)] > order[id(best[1])]:
                best = (slot, inp)
        if best is not None:
            slot, conv = best
            directives[id(conv)]["defer"] = True
            directives[id(node)] = {"kind": "resadd", "pending_slot": slot}
    return directives


# ----------------------------------------------------------------------- gate
def _table_device_matches():
    """The WINS table is an on-chip measurement: it only applies on the
    device generation it was taken on (interpret-mode Pallas on CPU would be
    orders of magnitude slower than the XLA path the table says it beats)."""
    from .ops.fused_conv_bn_table import DEVICE

    if DEVICE is None:
        return False
    import jax

    try:
        return jax.devices()[0].device_kind == DEVICE
    except Exception:
        return False


def _conv_bn_key(kernel, stride, x_shape, w_shape, dtype, res):
    import numpy as np

    return "conv_bn|k%ds%d%s|%s%s;%s" % (
        kernel[0], stride[0], "pr" if res else "p",
        np.dtype(dtype).name, tuple(x_shape), tuple(w_shape))


def _conv_bn_measure(kernel, stride, x_shape, w_shape, dtype, res):
    """The PR 2 fwd+bwd autotune contract for one conv+BN site, as a
    fusion_tune measurement: unfused (XLA conv + stats re-read) vs the
    Pallas ``conv_block`` under each tileable backward policy. The winning
    candidate name (``pallas:<policy>``) carries the backward mode
    ``bwd_mode`` rides into ``conv_block(bwd=...)``."""
    import functools

    import numpy as np

    from .fusion_tune import measure_candidates
    from .ops.pallas_conv_bn import _stats_of

    rs = np.random.RandomState(0)
    dt = jnp.dtype(dtype)
    itemsize = dt.itemsize
    x = jnp.asarray(rs.randn(*x_shape), dt)
    w = jnp.asarray(rs.randn(*w_shape) * 0.1, dt)
    K = x_shape[1]
    scale = jnp.asarray(rs.uniform(0.5, 1.5, (K,)), jnp.float32)
    shift = jnp.asarray(rs.uniform(-0.2, 0.2, (K,)), jnp.float32)
    args = [x, w, scale, shift]
    if res:
        Ho, Wo = strided_dims(x_shape[2], x_shape[3], stride)
        args.append(jnp.asarray(
            rs.randn(x_shape[0], w_shape[0], Ho, Wo) * 0.1, dt))

    def baseline(x, w, scale, shift, r=None):
        c = _xla_conv(x, w, scale, shift, r, kernel, stride, True)
        s, q = _stats_of(c)
        return (c, s, q)

    def fused(x, w, scale, shift, r=None, bwd="xla", bn=None):
        return conv_block(x, w, scale, shift, r, kernel, stride, True,
                          True, bwd, bn)

    from . import fusion_tune as _tune
    from .ops.pallas_conv_bn import _conv_geometry, bn_candidates

    geo = _conv_geometry(tuple(x_shape), tuple(w_shape), stride, itemsize)
    budget = _tune.schedule_budget()
    cands = []
    for policy in ("xla", "recompute", "stash"):
        if policy != "xla":
            if (policy == "stash" and plan_blocks(
                    x_shape, w_shape, stride, itemsize=itemsize,
                    prologue=True, res=res, emit_xn=True) is None):
                continue
            if plan_bwd_blocks(x_shape, w_shape, stride, itemsize=itemsize,
                               prologue=True, res=res,
                               stash=(policy == "stash")) is None:
                continue
        cands.append(("pallas:" + policy,
                      functools.partial(fused, bwd=policy)))
        if geo is not None and budget:
            # the forward stripe's schedule axis (choose_blocks seeds the
            # bare-name default; the variants carry their measured stripe)
            B_, K_, N_, HW_, taps_ = geo
            bns = bn_candidates(B_, K_, N_, HW_, itemsize, taps=taps_,
                                prologue=True, res=res,
                                emit_xn=(policy == "stash"))
            cands.extend(
                (_tune.sched_name("pallas:" + policy, bn=bn),
                 functools.partial(fused, bwd=policy, bn=bn))
                for bn in bns[1:1 + budget])
    return measure_candidates(baseline, cands, tuple(args), train=True)


def _conv_bn_verdict(kernel, stride, x_shape, w_shape, dtype, res):
    """The measured verdict for this conv+BN site — cache hit, measure on
    miss (tuning enabled), else None (committed WINS table decides)."""
    from . import fusion_tune as _tune

    if _tune.cache_dir() is None:
        return None
    key = _conv_bn_key(kernel, stride, x_shape, w_shape, dtype, res)
    return _tune.verdict(key, lambda: _conv_bn_measure(
        kernel, stride, x_shape, w_shape, dtype, res))


def _conv_bn_peek(kernel, stride, x_shape, w_shape, dtype, res):
    """Cache-only read of the conv+BN verdict (never measures) — the
    ``bwd_mode`` consult, which must not tune from inside a policy query."""
    from . import fusion_tune as _tune

    return _tune.peek(_conv_bn_key(kernel, stride, x_shape, w_shape, dtype,
                                   res))


def conv_schedule(kernel, stride, x_shape, w_shape, dtype, res):
    """The tuned forward channel-stripe override (``@bn=…``) for an
    ENGAGED conv+BN site, or None (planner default / no searched winner /
    v1 binary-verdict record). Cache-only read."""
    rec = _conv_bn_peek(kernel, stride, x_shape, w_shape, dtype, res)
    if not rec or not rec.get("engage"):
        return None
    sched = rec.get("schedule")
    if isinstance(sched, dict) and isinstance(sched.get("bn"), int):
        return sched["bn"]
    return None


def gate_explain(kernel, stride, x_shape, w_shape, dtype, prologue,
                 res=False, train=True):
    """The per-shape engage decision WITH the predicate that made it:
    ``(engaged, reason)``. Same predicate order as the reference planner's
    gate; ``gate`` is this plus telemetry counting. Keep each reason a
    single precise predicate — telemetry spans and fusion_explain (GL301)
    report them verbatim.

    ``train=False`` is the inference predicate (grad-less bind): the same
    shape/VMEM and WINS checks apply, but no backward budget exists — the
    stash/bwd-policy machinery (``bwd_mode``) is never consulted, so a
    shape only needs the FORWARD win to engage."""
    env = os.environ.get("MXNET_FUSED_CONV_BN", "auto")
    if env == "0":
        return False, "MXNET_FUSED_CONV_BN=0 (fusion disabled)"
    if not supported(x_shape, w_shape, stride,
                     itemsize=jnp.dtype(dtype).itemsize,
                     prologue=prologue, res=res):
        return False, ("shape %sx%s does not tile within the VMEM budget "
                       "(supported() declined)" % (x_shape, w_shape))
    if env == "1":
        return True, "forced (MXNET_FUSED_CONV_BN=1)"
    if not prologue:
        return False, ("bare conv (no folded BN prologue): no measured "
                       "WINS contract, never engages in auto mode")
    rec = _conv_bn_verdict(kernel, stride, x_shape, w_shape, dtype, res)
    if rec is not None:
        want = "engage" if train else "engage_fwd"
        if rec.get(want):
            times = _rec_best_times(rec)
            return True, ("measured win (tuned: fused %.0fµs vs xla "
                          "%.0fµs fwd+bwd%s)"
                          % (times + (losers_note(rec,
                                                  rec.get("lowering")),))
                          if times else "measured win (tuned)")
        return False, tuned_reject_note(rec)
    # seed/fallback when tuning is disabled: the committed on-chip table
    if not _table_device_matches():
        return False, ("WINS table absent or measured on a different "
                       "device generation")
    from .ops.fused_conv_bn_table import WINS

    if bool(WINS.get(_wins_key(kernel, stride, x_shape, w_shape, res),
                     False)):
        return True, ("WINS-table win for this shape"
                      if train else
                      "WINS-table forward win for this shape (inference: "
                      "no backward budget to clear)")
    return False, "no WINS-table win for this shape"


def gate(kernel, stride, x_shape, w_shape, dtype, prologue, res=False,
         train=True):
    """Per-shape engage decision: env override, else the committed on-chip
    WINS table (device-matched, per measured VARIANT — 'p' prologue-only,
    'pr' prologue+residual; bare convs have no measured contract and never
    engage in auto mode), else off. Untileable calls never engage.
    ``train=False`` counts into the ``fusion.infer_*`` telemetry family
    instead of ``fusion.fwd_*``."""
    engaged, _ = gate_explain(kernel, stride, x_shape, w_shape, dtype,
                              prologue, res=res, train=train)
    if _tm.enabled():
        if train:
            _tm.counter("fusion.fwd_engaged" if engaged
                        else "fusion.fwd_fallback").inc()
        else:
            _tm.counter("fusion.infer_engaged" if engaged
                        else "fusion.infer_fallback").inc()
    return engaged


def infer_default():
    """Whether the fusion plan is ACTIVE on inference (grad-less /
    ``is_train=False``) executions of a program. Distinct from the
    per-shape ``gate`` decision: an active plan applies the structural
    rewrites (BN prologue fold, moving-stat constant fold, quantized
    weights) with the per-shape Pallas engage still decided by
    ``gate(train=False)``; an inactive plan leaves inference on the plain
    op-by-op lowering, byte-identical to the pre-serving behavior.

    Active when fusion is forced (``MXNET_FUSED_CONV_BN=1``), when the
    committed WINS table matches this device generation (on-chip serving),
    or when a quantized inference variant is requested
    (``MXNET_SERVE_QUANT`` — quantization is applied by the fused execute
    path, so it needs the plan live even where Pallas declines)."""
    env = os.environ.get("MXNET_FUSED_CONV_BN", "auto")
    if env == "0":
        return False
    if env == "1":
        return True
    if quant_mode() != "off":
        return True
    return _table_device_matches()


def _wins_key(kernel, stride, x_shape, w_shape, res):
    """The per-shape WINS-table key. The spatial term uses the kernel's own
    post-stride arithmetic (ceil for odd dims) so the key always matches
    what tools/fused_stats_bench.py measured and emitted."""
    Ho, Wo = strided_dims(x_shape[2], x_shape[3], stride)
    return (kernel[0], x_shape[1], w_shape[0], Ho * Wo, stride[0],
            "pr" if res else "p")


_warned_bwd_env = False


def bwd_mode(kernel, stride, x_shape, w_shape, dtype, prologue, res=False):
    """The stash-vs-recompute policy for the fused backward, decided per
    shape (see ``_bwd_mode_impl``); counts ``fusion.bwd_engaged`` /
    ``fusion.bwd_xla`` into the telemetry registry when enabled."""
    mode = _bwd_mode_impl(kernel, stride, x_shape, w_shape, dtype, prologue,
                          res=res)
    if _tm.enabled():
        _tm.counter("fusion.bwd_xla" if mode == "xla"
                    else "fusion.bwd_engaged").inc()
    return mode


def _bwd_mode_impl(kernel, stride, x_shape, w_shape, dtype, prologue,
                   res=False):
    """The stash-vs-recompute policy for the fused backward, decided per
    shape like ``choose_blocks`` (docs/PERF.md §6b):

    - ``MXNET_FUSED_CONV_BN_BWD=0|xla`` pins the jax.vjp-of-XLA backward;
      ``recompute``/``stash`` force a policy (measurement) where the shape
      tiles;
    - ``auto`` (default) consults the committed WINS table's backward
      entries — key ``(..., variant + ":bwd")``, value the measured winning
      policy string — device-matched like the forward gate.

    Only meaningful when the forward engages (``gate`` returned True for
    the same call); the returned mode rides into ``conv_block(bwd=...)``.
    """
    env = os.environ.get("MXNET_FUSED_CONV_BN_BWD", "auto")
    if env in ("0", "xla"):
        return "xla"
    if env == "1":
        env = "recompute"  # mirror MXNET_FUSED_CONV_BN=1 force semantics
    elif env not in ("auto", "recompute", "stash"):
        global _warned_bwd_env
        if not _warned_bwd_env:
            _warned_bwd_env = True
            import logging

            logging.getLogger("mxnet_tpu").warning(
                "MXNET_FUSED_CONV_BN_BWD=%r not recognized "
                "(0|xla|1|recompute|stash|auto); backward stays on the XLA "
                "lowering", env)
        return "xla"
    itemsize = jnp.dtype(dtype).itemsize

    def _tiles(policy):
        if policy == "stash" and plan_blocks(
                x_shape, w_shape, stride, itemsize=itemsize,
                prologue=prologue, res=res, emit_xn=True) is None:
            return False  # forward cannot afford the xn output stream
        return plan_bwd_blocks(x_shape, w_shape, stride, itemsize=itemsize,
                               prologue=prologue, res=res,
                               stash=(policy == "stash")) is not None

    if env in ("recompute", "stash"):
        return env if _tiles(env) else "xla"
    if not prologue:
        return "xla"
    # measured verdict first (the forward gate already tuned this site —
    # cache-only read here, a policy query must never trigger a measurement)
    rec = _conv_bn_peek(kernel, stride, x_shape, w_shape, dtype, res)
    if rec is not None and rec.get("engage"):
        low = rec.get("lowering") or ""
        # "pallas:<policy>[@bn=…]" — the @-suffix is the forward stripe
        # schedule (conv_schedule reads it), not part of the policy
        policy = low.partition(":")[2].partition("@")[0]
        if policy in ("recompute", "stash") and _tiles(policy):
            return policy
        return "xla"
    if not _table_device_matches():
        return "xla"
    from .ops.fused_conv_bn_table import WINS

    k, K, N, hw, s, variant = _wins_key(kernel, stride, x_shape, w_shape,
                                        res)
    policy = WINS.get((k, K, N, hw, s, variant + ":bwd"))
    if policy in ("recompute", "stash") and _tiles(policy):
        return policy
    return "xla"


# ----------------------------------------------------- generic pattern gate
def _tune_key(pat, meta, args):
    from .ops.fusion_patterns import sig_of

    variant = pat.key_variant(meta)
    return "%s|%s|%s" % (pat.name, variant, sig_of(args))


def _rec_best_times(rec):
    """(fused_us, baseline_us) fwd+bwd totals from a tune record — the
    engaged lowering's when one won, else the best measured candidate's —
    for the explain strings GL302/GL303 quote. None when nothing timed."""
    base = rec.get("base_fwd_us")
    if base is None:
        return None
    base += rec.get("base_bwd_us") or 0.0
    if rec.get("fused_fwd_us") is not None:
        return (rec["fused_fwd_us"] + (rec.get("fused_bwd_us") or 0.0), base)
    best = None
    for row in (rec.get("measured") or {}).values():
        if row.get("fwd_us") is None:
            continue
        t = row["fwd_us"] + (row.get("bwd_us") or 0.0)
        best = t if best is None or t < best else best
    return None if best is None else (best, base)


def losers_note(rec, winner):
    """The measured-losers clause of a schedule-search win: up to three
    runner-up candidates with their fwd(+bwd) totals, fastest first —
    ``gate_explain``/``gate_pattern_explain`` reasons quote it so the
    schedule decision is auditable without opening the cache file."""
    rows = []
    for name, row in (rec.get("measured") or {}).items():
        if name == winner or row.get("fwd_us") is None:
            continue
        if "rejected" in row or "error" in row:
            continue  # failed parity / failed to run: not beaten on TIME
        rows.append((row["fwd_us"] + (row.get("bwd_us") or 0.0), name))
    if not rows:
        return ""
    rows.sort()
    note = ", ".join("%s %.0fµs" % (n, t) for t, n in rows[:3])
    extra = "" if len(rows) <= 3 else " +%d more" % (len(rows) - 3)
    return "; beat %s%s" % (note, extra)


def tuned_reject_note(rec):
    """The measured-timings clause for a tuned-and-rejected site (feeds the
    GL302 explainer and ``gate_pattern_explain`` reasons)."""
    if "error" in rec:
        return "tuned and failed to measure (%s)" % rec["error"]
    times = _rec_best_times(rec)
    if times is None:
        return "tuned and rejected (no candidate lowering could be timed)"
    return ("tuned and rejected (best fused %.0fµs vs baseline %.0fµs "
            "fwd+bwd)" % times)


def gate_pattern_explain(pat, meta, args, train=True):
    """The per-site engage decision for a generic pattern WITH its
    predicate: ``(engaged, (lowering_name, fn) | None, reason)``.

    Predicate order: env mode (``MXNET_FUSED_PATTERNS``) → inference
    eligibility → mesh (patterns engage single-device only; SPMD traces
    keep the op's own dispatch, e.g. ring attention) → candidate lowerings
    exist for these shapes → forced, else the measure-and-cache verdict
    (``fusion_tune``): cache hit engages/rejects with the measured µs;
    a miss MEASURES when tuning is enabled, else stays unfused."""
    from . import fusion_tune as _tune

    mode = enabled_patterns(infer=not train).get(pat.name, "0")
    if mode == "0":
        return False, None, ("pattern disabled (MXNET_FUSED_PATTERNS%s)"
                             % ("" if train else "[_INFER]"))
    if not train and not pat.inference:
        return False, None, "pattern does not engage on inference executions"
    if _mesh_kind()[0] != _MESH_NONE:
        return False, None, ("multi-device mesh: generic patterns engage "
                             "single-device only (the op's own SPMD "
                             "dispatch applies)")
    baseline, cands = pat.build(meta, args)
    if not cands:
        return False, None, ("no fused lowering for this site (shape does "
                             "not tile / variant unsupported)")
    if mode == "1":
        return True, cands[0], "forced (MXNET_FUSED_PATTERNS)"
    if mode != "auto":
        # a forced lowering NAME (prefix-matched so a bare family name
        # also selects its schedule variants): engage where it exists
        match = next((c for c in cands if c[0] == mode),
                     next((c for c in cands if c[0].startswith(mode)),
                          None))
        if match is not None:
            return True, match, ("forced (MXNET_FUSED_PATTERNS %s=%s)"
                                 % (pat.name, mode))
        return False, None, ("forced lowering %r has no candidate at "
                             "this site" % mode)
    if not getattr(pat, "tunable", True):
        return False, None, ("no lowering distinct from the baseline to "
                             "measure (engage via MXNET_FUSED_PATTERNS="
                             "%s=1)" % pat.name)
    key = _tune_key(pat, meta, args)

    def _measure():
        # synthetic concrete inputs: the real args are tracers mid-trace.
        # tuner_build() keeps force-gated interpret candidates (an
        # inference-map pin) out of the measured set off-TPU.
        from .ops.fusion_patterns import tuner_build

        sargs = _tune.synth_like(args)
        with tuner_build():
            sbase, scands = pat.build(meta, sargs)
        return _tune.measure_candidates(sbase, scands, sargs, train=True)

    rec = _tune.verdict(key, _measure)
    if rec is None:
        return False, None, ("no measured verdict for this site (tuning "
                             "disabled: set MXNET_FUSION_TUNE_DIR)")
    want = "engage" if train else "engage_fwd"
    low = rec.get("lowering") if train else (rec.get("lowering_fwd")
                                             or rec.get("lowering"))
    if rec.get(want) and low:
        fn = dict(cands).get(low)
        if fn is None:
            return False, None, ("cached lowering %r is unavailable for "
                                 "this site" % low)
        times = _rec_best_times(rec)
        reason = "measured win (%s)" % low if times is None else (
            "measured win (%s: fused %.0fµs vs baseline %.0fµs fwd+bwd%s)"
            % ((low,) + times + (losers_note(rec, low),)))
        return True, (low, fn), reason
    return False, None, tuned_reject_note(rec)


def attention_trains_flash(q_shape, k_shape, dtype, causal, scale=-1.0):
    """Whether TRAINING through an attention site with these shapes will
    statically engage the flash (``pallas_flash``) lowering — whose
    ``custom_vjp`` online-softmax recompute backward never stashes the
    (B, H, T, S) probability tensor. Decidable without tracing: the
    pattern mode force-names a flash lowering, or the tune cache records
    an engaged ``pallas_flash`` winner for this exact site. The GL5xx
    memory planner uses it to elide the score-stash charge."""
    try:
        from .ops import pallas_attention as pa

        if not pa.supported(tuple(q_shape), tuple(k_shape),
                            causal=bool(causal)):
            return False
        mode = enabled_patterns().get("attention", "0")
        if mode in ("0", "1"):
            return False  # "1" engages the FIRST candidate (XLA family)
        if mode != "auto":
            return mode.startswith("pallas_flash")
        from . import fusion_tune as _tune
        from .ops.fusion_patterns import get_patterns

        class _Arg:  # shape/dtype carrier for the tune-key signature
            def __init__(self, shape, dtype):
                self.shape, self.dtype = tuple(shape), dtype

        pat = next(p for p in get_patterns() if p.name == "attention")
        meta = {"causal": bool(causal), "scale": float(scale)}
        args = (_Arg(q_shape, dtype), _Arg(k_shape, dtype),
                _Arg(k_shape, dtype))
        rec = _tune.peek(_tune_key(pat, meta, args))
        return bool(rec and rec.get("engage")
                    and str(rec.get("lowering") or "").startswith(
                        "pallas_flash"))
    except Exception:  # a planner refinement must never sink an analysis
        return False


def _exec_pattern(directive, node, ins, is_train):
    """Run one pattern-rooted node: engage the gated lowering, or fall back
    to the bit-identical unfused root op over resolved inputs."""
    pat, meta = directive["pat"], directive["meta"]
    engaged, chosen, reason = False, None, None
    try:
        args = pat.externals(meta, ins, resolve)
    except Exception:  # matcher/exec mismatch: unfused fallback
        args, reason = None, "externals recovery failed (marker mismatch)"
    if args is not None:
        engaged, chosen, reason = gate_pattern_explain(
            pat, meta, args, train=is_train)
    if _tm.enabled():
        _tm.counter("fusion.pattern_engaged.%s" % pat.name if engaged
                    else "fusion.pattern_fallback.%s" % pat.name).inc()
    if _tm.tracing():
        _tm.event("fusion.pattern", op=node.name, pattern=pat.name,
                  engaged=engaged, reason=reason,
                  **({"lowering": chosen[0]} if chosen else {}))
    if engaged:
        return (chosen[1](*args),), ()
    from .ops.registry import get_op

    rins = [resolve(v) for v in ins]
    outs, aux_out = get_op(node.op).apply(
        node.parsed_attrs(), rins, aux=[], is_train=is_train, rng=None)
    return tuple(outs), tuple(aux_out)


# -------------------------------------------------------------------- execute
def execute(directive, node, ins, aux, is_train):
    """Run one planned node during interpret(). ``ins`` are the raw values
    (possibly fusion markers); returns (outs_tuple_or_marker, new_aux)."""
    kind = directive["kind"]
    if kind == "bn":
        return _exec_bn(directive, node, ins, aux, is_train)
    if kind == "relu_fold":
        v = ins[0]
        if isinstance(v, Deferred):
            return (v.with_relu(),), ()
        return (jnp.maximum(resolve(v), 0),), ()
    if kind == "conv":
        if not is_train:
            return (_exec_conv_infer(directive, node, ins),), ()
        return (_exec_conv(directive, node, ins),), ()
    if kind == "resadd":
        return (_exec_resadd(directive, ins),), ()
    if kind == "lazy":
        return (Lazy(node, ins),), ()
    if kind == "pattern":
        return _exec_pattern(directive, node, ins, is_train)
    raise AssertionError(kind)


def _exec_bn(directive, node, ins, aux, is_train=True):
    data_v, gamma, beta = ins
    moving_mean, moving_var = aux
    a = node.parsed_attrs()
    eps, momentum = float(a["eps"]), float(a["momentum"])
    fix_gamma = bool(a["fix_gamma"])

    if not is_train:
        # inference: normalize with the MOVING stats — per-channel scale and
        # shift are constants, so the fold costs nothing even mid-chain
        x = data_v.c if isinstance(data_v, WithStats) else resolve(data_v)
        istd = jax.lax.rsqrt(moving_var.astype(jnp.float32) + eps)
        scale32 = istd if fix_gamma else gamma.astype(jnp.float32) * istd
        shift32 = beta.astype(jnp.float32) \
            - moving_mean.astype(jnp.float32) * scale32
        if directive["fold"]:
            out = Deferred(x, scale32, shift32, relu=False)
        else:
            out = _normalize(x, scale32, shift32)
        return (out,), (moving_mean, moving_var)

    if isinstance(data_v, WithStats):
        x, ssum, ssq = data_v.c, data_v.ssum, data_v.ssq
    else:
        x = resolve(data_v)
        x32 = x.astype(jnp.float32)
        axes = (0,) + tuple(range(2, x.ndim))
        ssum = jnp.sum(x32, axis=axes)
        ssq = jnp.sum(x32 * x32, axis=axes)
    cnt = x.shape[0]
    for dim in x.shape[2:]:
        cnt *= dim
    mean = ssum / cnt
    var = ssq / cnt - mean * mean
    istd = jax.lax.rsqrt(var + eps)
    g32 = istd if fix_gamma else gamma.astype(jnp.float32) * istd
    scale32 = g32
    shift32 = beta.astype(jnp.float32) - mean * scale32

    sg = jax.lax.stop_gradient
    new_mean = moving_mean * momentum + sg(mean).astype(moving_mean.dtype) * (1 - momentum)
    new_var = moving_var * momentum + sg(var).astype(moving_var.dtype) * (1 - momentum)

    if directive["fold"]:
        out = Deferred(x, scale32, shift32, relu=False)
    else:
        out = _normalize(x, scale32, shift32)
    return (out,), (new_mean, new_var)


_MESH_NONE, _MESH_DP, _MESH_OTHER = 0, 1, 2


def _mesh_kind():
    """Tri-state: (_MESH_NONE, None, 0) outside any SPMD trace or on a
    1-device mesh (run the kernel directly); (_MESH_DP, mesh, dp) on a
    pure data-parallel mesh over a 'data' axis (run per-shard under
    shard_map with psum'd statistics); (_MESH_OTHER, None, 0) on any other
    multi-device mesh — tensor/seq-sharded, or a dp axis not named 'data' —
    where a raw pallas_call would make GSPMD gather its operands: those
    take the XLA fallback unconditionally."""
    from .parallel.mesh import current_trace_mesh

    mesh = current_trace_mesh()
    if mesh is None or mesh.size <= 1:
        return _MESH_NONE, None, 0
    dp = mesh.shape.get("data", 0) if "data" in mesh.axis_names else 0
    if dp == mesh.size:
        return _MESH_DP, mesh, dp
    return _MESH_OTHER, None, 0


def _conv_block_sharded(mesh, x, w, scale, shift, res, kernel, stride, relu,
                        bwd="xla", bn=None):
    """Run the kernel per data-shard (pallas_call has no SPMD partitioning
    rule, so GSPMD would gather its operands); the per-shard statistics
    psum over 'data' so the downstream BN sees GLOBAL-batch moments —
    identical semantics to the unfused dp path, where XLA turns the stats
    reduction over a sharded batch into the same collective."""
    import jax
    from jax.sharding import PartitionSpec as P

    args = [x, w]
    specs = [P("data", *([None] * (x.ndim - 1))), P(*([None] * w.ndim))]
    has_p, has_r = scale is not None, res is not None
    if has_p:
        args += [scale, shift]
        specs += [P(None), P(None)]
    if has_r:
        args.append(res)
        specs.append(P("data", *([None] * (res.ndim - 1))))

    def local(*a):
        it = iter(a)
        x_, w_ = next(it), next(it)
        sc = next(it) if has_p else None
        sh = next(it) if has_p else None
        r_ = next(it) if has_r else None
        c, s, q = conv_block(x_, w_, sc, sh, r_, kernel, stride, relu,
                             True, bwd, bn)
        return (c, jax.lax.psum(s, "data"), jax.lax.psum(q, "data"))

    # check_vma off: pallas_call out_shapes carry no vma annotation for the
    # replication checker to verify
    fn = jax.shard_map(
        local, mesh=mesh, in_specs=tuple(specs),
        out_specs=(P("data", *([None] * (x.ndim - 1))), P(None), P(None)),
        check_vma=False)
    return fn(*args)


def _note_conv(node, x_shape, engaged, reason, bwd=None):
    """Trace-time telemetry: one event per planned conv recording the
    per-shape engage-or-fallback decision with its predicate. Fires during
    jit tracing (once per compile, not per step) — the observable record of
    whether the Pallas path actually ran in this program."""
    if not _tm.tracing():
        return
    _tm.event("fusion.conv", op=node.name, shape=tuple(x_shape),
              engaged=engaged, reason=reason,
              **({} if bwd is None else {"bwd": bwd}))


def _exec_conv(directive, node, ins):
    v, w = ins
    kernel, stride = directive["kernel"], directive["stride"]
    if isinstance(v, Deferred):
        x, scale, shift, relu = v.raw, v.scale, v.shift, v.relu
    else:
        x, scale, shift, relu = resolve(v), None, None, False
    kind, mesh, dp = _mesh_kind()
    if kind == _MESH_DP:
        local_shape = (x.shape[0] // dp,) + x.shape[1:]
        if (x.shape[0] % dp == 0
                and gate(kernel, stride, local_shape, w.shape, x.dtype,
                         scale is not None, res=directive["defer"])):
            bwd = bwd_mode(kernel, stride, local_shape, w.shape, x.dtype,
                           scale is not None, res=directive["defer"])
            bn = conv_schedule(kernel, stride, local_shape, w.shape,
                               x.dtype, directive["defer"])
            _note_conv(node, local_shape, True, "engaged (dp mesh)", bwd)
            if directive["defer"]:
                return PendingConv(x, w, scale, shift, relu, kernel, stride,
                                   bwd, bn)
            c, s, q = _conv_block_sharded(mesh, x, w, scale, shift, None,
                                          kernel, stride, relu, bwd, bn)
            return WithStats(c, s, q)
    elif kind == _MESH_NONE and gate(kernel, stride, x.shape, w.shape,
                                     x.dtype, scale is not None,
                                     res=directive["defer"]):
        bwd = bwd_mode(kernel, stride, x.shape, w.shape, x.dtype,
                       scale is not None, res=directive["defer"])
        bn = conv_schedule(kernel, stride, x.shape, w.shape, x.dtype,
                           directive["defer"])
        _note_conv(node, x.shape, True, "engaged", bwd)
        if directive["defer"]:
            return PendingConv(x, w, scale, shift, relu, kernel, stride,
                               bwd, bn)
        c, s, q = conv_block(x, w, scale, shift, None, kernel, stride, relu,
                             True, bwd, bn)
        return WithStats(c, s, q)
    # kind == _MESH_OTHER (tensor/seq-sharded) always lands here: XLA path
    # fallback: materialize the normalized input (cached on the marker) and
    # run the ordinary XLA conv (shared lowering from pallas_conv_bn)
    if _tm.enabled():
        # the mesh-shape branches above never reach gate(), so their
        # fallback must be counted here or these configs would read as
        # "zero fallbacks" in exactly the runs where fusion disengaged
        mesh_barred = (kind == _MESH_OTHER
                       or (kind == _MESH_DP and x.shape[0] % dp != 0))
        if mesh_barred:
            _tm.counter("fusion.fwd_fallback").inc()
        if _tm.tracing():
            if kind == _MESH_OTHER:
                reason = ("multi-device mesh without a pure 'data' axis: a "
                          "raw pallas_call would make GSPMD gather its "
                          "operands")
            elif mesh_barred:
                reason = ("batch %d not divisible by data-parallel degree %d"
                          % (x.shape[0], dp))
            else:
                shape = ((x.shape[0] // dp,) + x.shape[1:]
                         if kind == _MESH_DP else x.shape)
                _, reason = gate_explain(kernel, stride, shape, w.shape,
                                         x.dtype, scale is not None,
                                         res=directive["defer"])
            _note_conv(node, x.shape, False, reason)
    xn = v.materialize() if isinstance(v, Deferred) else x
    return _xla_conv(xn, w, None, None, None, kernel, stride, False)


# --------------------------------------------- inference (grad-less) variants
_warned_quant_env = False


def quant_mode():
    """The requested quantized-inference variant: ``off`` | ``bf16`` |
    ``int8`` (``MXNET_SERVE_QUANT``, docs/SERVING.md). Unrecognized values
    warn once and stay off."""
    env = os.environ.get("MXNET_SERVE_QUANT", "off").strip().lower()
    if env in ("", "0", "off", "none", "fp32", "float32"):
        return "off"
    if env in ("bf16", "bfloat16"):
        return "bf16"
    if env == "int8":
        return "int8"
    global _warned_quant_env
    if not _warned_quant_env:
        _warned_quant_env = True
        import logging

        logging.getLogger("mxnet_tpu").warning(
            "MXNET_SERVE_QUANT=%r not recognized (off|bf16|int8); "
            "quantized inference stays off", env)
    return "off"


def _quant_conv_inputs(x, w, mode):
    """The quantized-inference input transform for one conv site.

    Deliberately traced INTO the compiled program: weights are executor
    inputs (arg_dict), so hoisting the transform would mean freezing them
    into the executable — a different ownership model the predict API's
    param-update path contradicts. The steady-state cost is O(|w|)
    (abs-max reduce + round) against the conv's O(|w|·B·H·W): under 1% at
    serving batch shapes, and XLA fuses the bf16 casts into the conv's
    operand reads.

    - ``bf16``: activations AND weights compute in bfloat16 (the MXU fast
      path; f32 accumulate comes from the conv's preferred_element_type).
    - ``int8``: weight-only symmetric per-output-channel quantization —
      weights snap to the 255-point int8 grid and dequantize through their
      per-channel scale. Compute stays in the activation dtype, so this
      measures the ACCURACY of int8 weights with fp32 math; an int8-MAC
      kernel can adopt the same grid later without changing results
      further.
    """
    if mode == "bf16":
        return x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    if mode == "int8":
        w32 = w.astype(jnp.float32)
        s = jnp.max(jnp.abs(w32), axis=tuple(range(1, w.ndim)),
                    keepdims=True) / 127.0
        s = jnp.where(s > 0, s, 1.0)
        wq = jnp.clip(jnp.round(w32 / s), -127, 127)
        return x, (wq * s).astype(w.dtype)
    return x, w


def _exec_conv_infer(directive, node, ins):
    """The grad-less execute path for a planned conv: moving-stat BN
    prologue stays folded (``_exec_bn`` inference branch), weights ride the
    quantized variant when requested, and ``gate(train=False)`` decides the
    Pallas-vs-XLA lowering with no backward budget in the predicate.
    Residual defers never engage here (the add runs as a plain elementwise
    — at inference the deferral saves no statistics pass), so no
    ``PendingConv`` marker is created."""
    v, w = ins
    kernel, stride = directive["kernel"], directive["stride"]
    if isinstance(v, Deferred):
        x, scale, shift, relu = v.raw, v.scale, v.shift, v.relu
    else:
        x, scale, shift, relu = resolve(v), None, None, False
    quant = quant_mode()
    x_c, w_c = _quant_conv_inputs(x, resolve(w), quant)
    kind, _, _ = _mesh_kind()
    if kind == _MESH_NONE:
        engaged = gate(kernel, stride, x_c.shape, w_c.shape, x_c.dtype,
                       scale is not None, res=False, train=False)
        reason = None
    else:
        engaged, reason = False, ("multi-device mesh: inference fusion "
                                  "runs single-device only")
        if _tm.enabled():
            _tm.counter("fusion.infer_fallback").inc()
    if engaged:
        _note_conv(node, x.shape, True,
                   "engaged (inference%s)"
                   % ("" if quant == "off" else ", quant=" + quant))
        # stats-free kernel variant: at is_train=False every downstream BN
        # folds its MOVING stats, so the training kernel's ssum/ssq
        # epilogue would be dead outputs the opaque pallas_call still
        # computes — return a plain tensor, not WithStats
        c = conv_block_infer(x_c, w_c, scale, shift, kernel, stride, relu)
        return c.astype(x.dtype)
    if _tm.tracing():
        if reason is None:
            _, reason = gate_explain(kernel, stride, x_c.shape, w_c.shape,
                                     x_c.dtype, scale is not None,
                                     res=False, train=False)
        _note_conv(node, x.shape, False, reason)
    # XLA fallback keeps the prologue folded into the conv's elementwise
    # preamble (no separate BN materialization) and the quantized weights
    c = _xla_conv(x_c, w_c, scale, shift, None, kernel, stride, relu)
    return c.astype(x.dtype)


def _exec_resadd(directive, ins):
    slot = directive["pending_slot"]
    pending, other = ins[slot], ins[1 - slot]
    if isinstance(pending, PendingConv):
        c, s, q = pending.run(resolve(other))
        return WithStats(c, s, q)
    return resolve(pending) + resolve(other)
