"""Declarative pattern registry for the subgraph fusion engine.

Each pattern of the fusion engine (fusion.py) is a matcher over the Symbol
DAG plus one-or-more fused lowerings, gated per (shape, dtype) by the
persistent measure-and-cache autotuner (``fusion_tune.py``). The patterns here
cover exactly the chains "Operator Fusion in XLA" (PAPERS.md) names as the
ones XLA leaves on the table over our Symbol DAG:

- ``matmul_bias_act``   — FullyConnected(+bias) → Activation, onto the
  Pallas epilogue kernel (``ops/pallas_matmul_bias_act.py``).
- ``attention``         — the fused MultiHeadAttention op, onto block-causal
  XLA (skips the masked upper-triangle key blocks: ~2× fewer score FLOPs on
  causal sites, exact parity) or the Pallas flash kernel on TPU.
- ``norm_residual``     — the LayerNorm composition the transformer zoo
  emits (mean/center/var/rsqrt/affine over broadcast ops), as one traced
  function.
- ``elemwise_chain``    — runs of single-consumer unary elementwise ops,
  composed into one lowering unit.

Contract per pattern:

- ``match(node, ctx)``       — try to root a match at ``node``; returns a
  ``Match`` (root, interior nodes, meta) or None. Interior nodes must be
  single-output, aux-free, rng-free, unclaimed, and not program outputs —
  the executor elides them behind lazy markers.
- ``externals(meta, ins, resolve)`` — recover the subgraph's EXTERNAL
  input values from the root's (possibly lazy) ``ins`` at trace time.
- ``build(meta, args)``      — ``(baseline_fn, [(name, fused_fn), ...])``:
  the unfused composition (the measurement reference AND the semantic
  spec) and the candidate fused lowerings for these concrete shapes. An
  empty candidate list means "nothing to measure here" and the site runs
  unfused.
- ``reject_reason(node, ctx)`` — for the GL303 explainer: why a
  near-miss node did not root a match (or None when it did / is not this
  pattern's root op).

The matchers deliberately refuse anything stateful: no aux (BN moving
stats), no rng (Dropout), no multi-output interiors — the fallback path
must be bit-identical to the unfused graph.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from .registry import get_op

__all__ = ["Match", "Pattern", "get_patterns", "pattern_names", "sig_of",
           "tuner_build"]


class Match:
    __slots__ = ("root", "interior", "meta")

    def __init__(self, root, interior, meta):
        self.root, self.interior, self.meta = root, list(interior), dict(meta)


class Pattern:
    name = None
    inference = True  # may engage on grad-less (is_train=False) executions

    def key_variant(self, meta):
        """The meta component of the tune-cache key (shape-independent)."""
        return ""

    def match(self, node, ctx):
        raise NotImplementedError

    def externals(self, meta, ins, resolve):
        raise NotImplementedError

    def build(self, meta, args):
        raise NotImplementedError

    def reject_reason(self, node, ctx):
        return None


def sig_of(args):
    """Canonical shape/dtype signature of the external inputs — the tune
    cache key's site component."""
    return ";".join("%s%s" % (str(np.dtype(a.dtype).name),
                              tuple(a.shape)) for a in args)


# ------------------------------------------------------------ schedule helpers
def _sched_budget():
    """How many schedule variants (beyond the planner default) a pattern
    may emit per candidate family (``MXNET_FUSION_TUNE_SCHEDULES``)."""
    from .. import fusion_tune

    return fusion_tune.schedule_budget()


def _sname(base, **kv):
    from .. import fusion_tune

    return fusion_tune.sched_name(base, **kv)


import contextlib
import threading

_tuner_scope = threading.local()


@contextlib.contextmanager
def tuner_build():
    """Marks a ``build()`` call made to CONSTRUCT MEASUREMENT candidates
    (the auto-mode tuner): force-gated interpret candidates are excluded
    inside this scope, so an inference-map force (e.g. a serving pin of
    ``attention=pallas_flash``) can never leak emulated off-TPU Pallas
    into a training-side measurement."""
    _tuner_scope.active = True
    try:
        yield
    finally:
        _tuner_scope.active = False


def _forced_lowering_requested(pattern_name, prefix):
    """Whether MXNET_FUSED_PATTERNS[_INFER] forces a lowering whose name
    starts with ``prefix`` for this pattern — the opt-in that makes
    ``build`` include interpret-mode Pallas candidates off-TPU (auto-mode
    tuning never measures interpret kernels at real shapes: the emulation
    is orders of magnitude off the question being asked, which is also
    why the ``tuner_build`` scope suppresses this check entirely)."""
    if getattr(_tuner_scope, "active", False):
        return False
    from .. import fusion

    for infer in (False, True):
        m = fusion.enabled_patterns(infer=infer).get(pattern_name, "0")
        if m not in ("0", "1", "auto") and m.startswith(prefix):
            return True
    return False


# --------------------------------------------------------------- match helpers
def _sole_consumer(ctx, node):
    """The single consumer of ``node``'s output 0, or None."""
    cons = ctx.consumers.get(id(node), [])
    if len(cons) == 1 and cons[0][1] == 0:
        return cons[0][0]
    return None


def _interior_ok(ctx, node):
    """Whether ``node`` may be elided behind a lazy marker."""
    if node.is_variable or id(node) in ctx.claimed:
        return False
    if id(node) in ctx.output_ids:
        return False  # its value is a program output: must materialize
    op = get_op(node.op)
    return (node.num_outputs() == 1 and not op.needs_rng
            and not op.needs_train_flag
            and not op.aux_names(node.parsed_attrs()))


def _apply1(node, *ins):
    """Run a single-output, stateless node on concrete values — the exact
    unfused semantics (same opdef the interpreter would call)."""
    outs, _ = get_op(node.op).apply(node.parsed_attrs(), list(ins),
                                    aux=[], is_train=False, rng=None)
    return outs[0]


# ------------------------------------------------------------ matmul_bias_act
class MatmulBiasAct(Pattern):
    """FullyConnected(+bias) → Activation(relu|sigmoid|tanh|softrelu)."""

    name = "matmul_bias_act"

    def key_variant(self, meta):
        return "%s%s%s" % (meta["act"],
                           "" if meta["flatten"] else ",noflat",
                           ",nobias" if meta["no_bias"] else "")

    _ACTS = ("relu", "sigmoid", "tanh", "softrelu")

    def match(self, node, ctx):
        if node.op != "Activation" or id(node) in ctx.claimed:
            return None
        act = node.parsed_attrs().get("act_type")
        if act not in self._ACTS:
            return None
        if not node.inputs or node.inputs[0][1] != 0:
            return None
        fc = node.inputs[0][0]
        if fc.is_variable or fc.op != "FullyConnected":
            return None
        if not _interior_ok(ctx, fc) or _sole_consumer(ctx, fc) is not node:
            return None
        a = fc.parsed_attrs()
        return Match(node, [fc], {"act": act,
                                  "flatten": bool(a.get("flatten", True)),
                                  "no_bias": bool(a.get("no_bias", False))})

    def reject_reason(self, node, ctx):
        # a NEAR miss only: some consumer IS a fusable Activation, yet the
        # match failed. A FullyConnected that simply isn't followed by an
        # activation (every classifier head) is not this pattern's business.
        if node.op != "FullyConnected":
            return None
        cons = ctx.consumers.get(id(node), [])
        acts = [c for c, oi in cons if oi == 0 and c.op == "Activation"
                and c.parsed_attrs().get("act_type") in self._ACTS]
        if not acts:
            return None
        if len(cons) != 1:
            return ("its output has %d consumers; the activation epilogue "
                    "needs the FullyConnected consumed exactly once"
                    % len(cons))
        if id(node) in ctx.output_ids:
            return "its output is a program output and must materialize"
        return None

    def externals(self, meta, ins, resolve):
        lazy = ins[0]
        fc_ins = [resolve(v) for v in lazy.ins]
        return tuple(fc_ins)  # (x, w) or (x, w, b)

    def build(self, meta, args):
        act = meta["act"]
        flatten = meta["flatten"]
        act_fn = {"relu": lambda y: jnp.maximum(y, 0),
                  "sigmoid": jax.nn.sigmoid,
                  "tanh": jnp.tanh,
                  "softrelu": lambda y: jnp.logaddexp(y, 0.0)}[act]

        def baseline(x, w, b=None):
            if flatten:
                x2 = x.reshape((x.shape[0], -1)) if x.ndim != 2 else x
                y = jnp.dot(x2, w.T)
            else:
                y = jnp.einsum("...i,oi->...o", x, w)
            if b is not None:
                y = y + b
            return act_fn(y)

        from . import pallas_matmul_bias_act as pk

        x, w = args[0], args[1]
        if meta["flatten"]:
            m = int(x.shape[0])
            k = int(np.prod(x.shape[1:]))
        else:
            m = int(np.prod(x.shape[:-1]))
            k = int(x.shape[-1])
        n = int(w.shape[0])
        cands = []
        if k == int(w.shape[1]):
            blocks = pk.block_candidates(
                m, k, n, act, itemsize=jnp.dtype(x.dtype).itemsize)

            def make(bm, bn):
                def fused(x, w, b=None, _m=m, _k=k, _n=n, _bm=bm, _bn=bn):
                    x2 = x.reshape((_m, _k))
                    bb = b if b is not None else jnp.zeros((_n,), x.dtype)
                    y = pk.matmul_bias_act(x2, w, bb, meta["act"], _bm, _bn)
                    if meta["flatten"]:
                        return y
                    return y.reshape(x.shape[:-1] + (_n,))

                return fused

            if blocks:
                # planner default keeps the bare name (v1 cache records
                # resolve to it); the schedule variants carry their blocks
                cands.append(("pallas", make(*blocks[0])))
                for bm, bn in blocks[1:1 + _sched_budget()]:
                    cands.append((_sname("pallas", bm=bm, bn=bn),
                                  make(bm, bn)))
        return baseline, cands


# ------------------------------------------------------------------ attention
class Attention(Pattern):
    """The fused MultiHeadAttention op. Candidate lowerings per site shape:

    - ``block_causal`` (causal, T == S): never computes the masked
      upper-triangle key blocks — ~half the score FLOPs, exact parity.
    - ``chunked_kv`` (decode/cross-attention: T_q != T_kv and/or no causal
      mask): streaming online-softmax over key chunks, so the (T, S) score
      matrix never materializes whole — the serving-side decode lowering.
    - ``pallas_flash`` (TPU; off-TPU only when force-named — interpret
      mode): the hand-tiled flash kernel, fwd AND bwd (``custom_vjp``
      online-softmax recompute backward), so TRAINING through this site
      stops stashing the (B, H, T, S) probability tensor.

    Each family fans out over the autotuner's bounded schedule space
    (block/chunk sizes), measured against the op's own dense lowering."""

    name = "attention"

    def key_variant(self, meta):
        return ("causal" if meta["causal"] else "full") + (
            ",s%g" % meta["scale"] if meta["scale"] > 0 else "")

    _OPS = ("_contrib_MultiHeadAttention", "MultiHeadAttention")
    _BLOCKS = (128, 64, 32)

    def match(self, node, ctx):
        if node.op not in self._OPS or id(node) in ctx.claimed:
            return None
        a = node.parsed_attrs()
        if a.get("window") or a.get("sink") or a.get("topk"):
            return None     # the candidates know none: the op's own path
        return Match(node, [], {"causal": bool(a.get("causal")),
                                "scale": float(a.get("scale", -1.0))})

    def reject_reason(self, node, ctx):
        # every attention node roots a match but a windowed, sunk or
        # sparse one
        if node.op in self._OPS:
            a = node.parsed_attrs()
            if a.get("window") or a.get("sink"):
                return ("a window or a sink in the softmax: no candidate "
                        "lowering computes either, the operator's band does")
            if a.get("topk"):
                return ("a learned selection: no candidate lowering takes an "
                        "indexer, the operator's query blocks do")
        return None

    def externals(self, meta, ins, resolve):
        return tuple(resolve(v) for v in ins)  # (q, k, v)

    _CHUNKS = (128, 256, 64, 32)

    def build(self, meta, args):
        q, k, _ = args
        causal = meta["causal"]
        scale = meta["scale"] if meta["scale"] > 0 else (
            1.0 / float(np.sqrt(q.shape[-1])))
        T, S = q.shape[2], k.shape[2]

        def baseline(q, k, v):
            # the registered op's dense XLA path, verbatim semantics
            q32, k32, v32 = (t.astype("float32") for t in (q, k, v))
            s = jnp.einsum("bhqd,bhkd->bhqk", q32, k32) * scale
            if causal:
                Tq, Sk = s.shape[-2], s.shape[-1]
                mask = jnp.tril(jnp.ones((Tq, Sk), bool), k=Sk - Tq)
                s = jnp.where(mask, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhqk,bhkd->bhqd", p, v32).astype(q.dtype)

        def make_block_causal(bq):
            def block_causal(q, k, v, _bq=bq):
                # query block i attends keys [0, (i+1)*bq): the masked
                # upper-triangle key blocks are never computed at all
                q32, k32, v32 = (t.astype("float32") for t in (q, k, v))
                outs = []
                for i in range(T // _bq):
                    qi = q32[:, :, i * _bq:(i + 1) * _bq]
                    end = (i + 1) * _bq
                    s = jnp.einsum("bhqd,bhkd->bhqk", qi,
                                   k32[:, :, :end]) * scale
                    mask = (jnp.arange(end)[None, :]
                            <= (jnp.arange(_bq) + i * _bq)[:, None])
                    s = jnp.where(mask, s, -jnp.inf)
                    p = jax.nn.softmax(s, axis=-1)
                    outs.append(jnp.einsum("bhqk,bhkd->bhqd", p,
                                           v32[:, :, :end]))
                return jnp.concatenate(outs, axis=2).astype(q.dtype)

            return block_causal

        def make_chunked(ck):
            def chunked(q, k, v, _ck=ck):
                # streaming online softmax over key chunks: the (T, S)
                # score matrix exists only one (T, ck) slab at a time.
                # Bottom-right causal alignment (row r sees cols <= r+S-T)
                # matches the op; with S >= T the first chunk's lowest
                # cols are visible to every row, so the running max is
                # real before any fully-masked tail entry (whose
                # exp(-1e30 - m) underflows to exactly 0).
                q32 = q.astype(jnp.float32) * scale
                k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
                B, H, Tq, D = q.shape
                Sk = k.shape[2]
                off = Sk - Tq
                rows = jnp.arange(Tq)
                neg = jnp.float32(-1e30)

                def body(carry, i):
                    m, l, acc = carry
                    kc = jax.lax.dynamic_slice_in_dim(k32, i * _ck, _ck,
                                                      axis=2)
                    vc = jax.lax.dynamic_slice_in_dim(v32, i * _ck, _ck,
                                                      axis=2)
                    s = jnp.einsum("bhqd,bhkd->bhqk", q32, kc)
                    if causal:
                        cols = i * _ck + jnp.arange(_ck)
                        s = jnp.where(cols[None, :] <= rows[:, None] + off,
                                      s, neg)
                    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
                    alpha = jnp.exp(m - m_new)
                    p = jnp.exp(s - m_new)
                    l_new = l * alpha + p.sum(axis=-1, keepdims=True)
                    acc_new = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd",
                                                       p, vc)
                    return (m_new, l_new, acc_new), None

                init = (jnp.full((B, H, Tq, 1), neg),
                        jnp.zeros((B, H, Tq, 1), jnp.float32),
                        jnp.zeros((B, H, Tq, D), jnp.float32))
                (_, l, acc), _ = jax.lax.scan(body, init,
                                              jnp.arange(Sk // _ck))
                return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)

            return chunked

        budget = _sched_budget()
        cands = []
        if causal and T == S:
            bqs = [b for b in self._BLOCKS if T % b == 0 and T > b]
            if bqs:
                cands.append(("block_causal", make_block_causal(bqs[0])))
                cands.extend((_sname("block_causal", bq=b),
                              make_block_causal(b))
                             for b in bqs[1:1 + budget])
        elif not causal or S >= T:
            # decode/cross-attention shapes: T_q != T_kv and/or no mask
            cks = [c for c in self._CHUNKS if S % c == 0 and S > c]
            if cks:
                cands.append(("chunked_kv", make_chunked(cks[0])))
                cands.extend((_sname("chunked_kv", ck=c), make_chunked(c))
                             for c in cks[1:1 + budget])
        on_tpu = jax.default_backend() == "tpu"
        if on_tpu or _forced_lowering_requested(self.name, "pallas_flash"):
            from . import pallas_attention as pa

            interp = not on_tpu

            def make_flash(bq, bk):
                def flash(q, k, v, _bq=bq, _bk=bk):
                    return pa.flash_attention(
                        q, k, v, causal=causal,
                        scale=max(meta["scale"], 0.0),
                        block_q=_bq, block_k=_bk, interpret=interp)

                return flash

            scheds = pa.block_schedules(q.shape, k.shape, causal=causal)
            if scheds:
                cands.append(("pallas_flash", make_flash(*scheds[0])))
                cands.extend((_sname("pallas_flash", q=bq, k=bk),
                              make_flash(bq, bk))
                             for bq, bk in scheds[1:1 + budget])
        return baseline, cands


# -------------------------------------------------------------- norm_residual
def _is_mean_last(node):
    if node.op != "mean":
        return False
    a = node.parsed_attrs()
    return (tuple(a.get("axis") or ()) == (-1,) and a.get("keepdims")
            and not a.get("exclude"))


class NormResidual(Pattern):
    """The LayerNorm composition the transformer zoo emits:

        mean → broadcast_sub → square → mean → +eps → rsqrt
             → broadcast_mul → broadcast_mul(gamma) → broadcast_add(beta)

    rooted at the final broadcast_add (the normalized, affine output the
    residual stream consumes)."""

    name = "norm_residual"

    def key_variant(self, meta):
        return "eps%g" % meta["eps"]

    def _chain(self, node, ctx):
        """The matched interior chain + slots, or (None, reason)."""
        if node.op != "broadcast_add" or len(node.inputs) != 2:
            return None, "not a 2-input broadcast_add"
        mul1 = mul1_slot = None
        for slot, (inp, oi) in enumerate(node.inputs):
            if (oi == 0 and not inp.is_variable and inp.op == "broadcast_mul"
                    and _interior_ok(ctx, inp)
                    and _sole_consumer(ctx, inp) is node):
                mul1, mul1_slot = inp, slot
                break
        if mul1 is None:
            return None, "no sole-consumer broadcast_mul feeds the add"
        mul0 = mul0_slot = None
        for slot, (inp, oi) in enumerate(mul1.inputs):
            if (oi == 0 and not inp.is_variable and inp.op == "broadcast_mul"
                    and _interior_ok(ctx, inp)
                    and _sole_consumer(ctx, inp) is mul1):
                mul0, mul0_slot = inp, slot
                break
        if mul0 is None or len(mul1.inputs) != 2:
            return None, "no gamma-scale broadcast_mul under the affine add"
        if len(mul0.inputs) != 2:
            return None, "normalize mul is not 2-input"
        cent = rs = cent_slot = None
        for slot, (inp, oi) in enumerate(mul0.inputs):
            if oi != 0 or inp.is_variable:
                return None, "normalize mul has a variable operand"
            if inp.op == "broadcast_sub":
                cent, cent_slot = inp, slot
            elif inp.op == "rsqrt":
                rs = inp
        if cent is None or rs is None:
            return None, "normalize mul is not centered*rsqrt"
        if not _interior_ok(ctx, rs) or _sole_consumer(ctx, rs) is not mul0:
            return None, "rsqrt output is consumed outside the chain"
        ps = rs.inputs[0][0] if rs.inputs else None
        if (ps is None or ps.is_variable or ps.op != "_plus_scalar"
                or not _interior_ok(ctx, ps)
                or _sole_consumer(ctx, ps) is not rs):
            return None, "no epsilon _plus_scalar under the rsqrt"
        m2 = ps.inputs[0][0]
        if (m2.is_variable or not _is_mean_last(m2)
                or not _interior_ok(ctx, m2)
                or _sole_consumer(ctx, m2) is not ps):
            return None, "variance is not a keepdims mean over the last axis"
        sq = m2.inputs[0][0]
        if (sq.is_variable or sq.op != "square" or not _interior_ok(ctx, sq)
                or _sole_consumer(ctx, sq) is not m2):
            return None, "variance operand is not square(centered)"
        if sq.inputs[0][0] is not cent:
            return None, "square input is not the centered activation"
        if not _interior_ok(ctx, cent):
            return None, "centered activation cannot be elided"
        cent_cons = {id(c) for c, _ in ctx.consumers.get(id(cent), [])}
        if cent_cons != {id(mul0), id(sq)}:
            return None, ("centered activation is consumed outside the "
                          "chain")
        if len(cent.inputs) != 2 or cent.inputs[0][1] != 0:
            return None, "center sub has unexpected inputs"
        m1 = cent.inputs[1][0]
        if (m1.is_variable or not _is_mean_last(m1)
                or not _interior_ok(ctx, m1)
                or _sole_consumer(ctx, m1) is not cent):
            return None, "center subtrahend is not a keepdims mean"
        if (m1.inputs[0][0] is not cent.inputs[0][0]
                or m1.inputs[0][1] != cent.inputs[0][1]):
            return None, "mean and center read different inputs"
        meta = {"eps": float(ps.parsed_attrs()["scalar"]),
                "mul1_slot": mul1_slot, "mul0_slot": mul0_slot,
                "cent_slot": cent_slot}
        return ([mul1, mul0, cent, rs, ps, m2, sq, m1], meta)

    def match(self, node, ctx):
        if node.op != "broadcast_add" or id(node) in ctx.claimed:
            return None
        interior, meta = self._chain(node, ctx)
        if interior is None:
            return None
        if any(id(n) in ctx.claimed for n in interior):
            return None
        return Match(node, interior, meta)

    def externals(self, meta, ins, resolve):
        l_mul1 = ins[meta["mul1_slot"]]
        beta = resolve(ins[1 - meta["mul1_slot"]])
        l_mul0 = l_mul1.ins[meta["mul0_slot"]]
        gamma = resolve(l_mul1.ins[1 - meta["mul0_slot"]])
        l_cent = l_mul0.ins[meta["cent_slot"]]
        x = resolve(l_cent.ins[0])
        return (x, gamma, beta)

    def build(self, meta, args):
        eps = meta["eps"]

        def baseline(x, gamma, beta):
            mean = jnp.mean(x, axis=-1, keepdims=True)
            cent = x - mean
            var = jnp.mean(jnp.square(cent), axis=-1, keepdims=True)
            inv = jax.lax.rsqrt(var + eps)
            return (cent * inv) * gamma + beta

        def onepass(x, gamma, beta):
            # E[x²]−E[x]² halves the reduction passes over x; numerics
            # differ at ~1e-6 rel (the tuner's parity check is the contract)
            x32 = x.astype(jnp.float32)
            mean = jnp.mean(x32, axis=-1, keepdims=True)
            msq = jnp.mean(x32 * x32, axis=-1, keepdims=True)
            inv = jax.lax.rsqrt(jnp.maximum(msq - mean * mean, 0.0) + eps)
            out = (x32 - mean) * inv
            return (out * gamma + beta).astype(x.dtype)

        # "fused" (the identical recomposition, bit-safe under force) is
        # first so =1 engages it; the tuner measures all and only a real
        # winner clears the margin
        cands = [("fused", baseline), ("onepass", onepass)]

        # the Pallas kernel lowering (ops/pallas_norm_residual.py): one
        # VMEM-resident tile per row block, fwd AND bwd. TPU always;
        # off-TPU only when force-named (interpret mode, parity tests)
        from . import pallas_norm_residual as pn

        x = args[0]
        on_tpu = jax.default_backend() == "tpu"
        if on_tpu or _forced_lowering_requested(self.name, "pallas"):
            itemsize = jnp.dtype(x.dtype).itemsize
            brs = pn.block_candidates(x.shape, itemsize)
            interp = not on_tpu

            def make_pallas(br):
                def fused_pallas(x, gamma, beta, _br=br):
                    # gamma/beta may carry broadcast shapes ((1,1,D)); the
                    # reshape is traced, so its transpose restores the
                    # cotangent shape
                    D = x.shape[-1]
                    return pn.layer_norm_affine(
                        x, gamma.reshape(D), beta.reshape(D), eps,
                        block_rows=_br, interpret=interp)

                return fused_pallas

            if brs:
                cands.append(("pallas", make_pallas(brs[0])))
                cands.extend((_sname("pallas", br=b), make_pallas(b))
                             for b in brs[1:1 + _sched_budget()])
        return baseline, cands


# ------------------------------------------------------------- elemwise_chain
class ElemwiseChain(Pattern):
    """Runs of ≥2 single-consumer unary elementwise ops, composed into one
    lowering unit (one fusion decision instead of N).

    ``tunable = False``: the composed lowering is computation-identical to
    the unfused chain (XLA fuses both the same way), so auto mode never
    measures it — a guaranteed-rejection tune would only add cold-start
    latency. The pattern exists as a grouping/observability unit and as
    the seam future kernel lowerings slot into; ``=1`` force-engages."""

    name = "elemwise_chain"
    tunable = False

    def key_variant(self, meta):
        parts = []
        for n in meta["nodes"]:
            if n.op == "Activation":
                parts.append(n.parsed_attrs().get("act_type"))
            elif n.op.endswith("_scalar"):
                parts.append("%s(%g)" % (n.op, n.parsed_attrs()["scalar"]))
            else:
                parts.append(n.op)
        return "-".join(parts)

    _UNARY = frozenset({
        "abs", "square", "sqrt", "rsqrt", "exp", "log", "log1p", "expm1",
        "negative", "reciprocal", "relu", "sigmoid", "tanh", "softsign",
        "_plus_scalar", "_minus_scalar", "_mul_scalar", "_div_scalar",
    })

    def _link_ok(self, node):
        if node.is_variable:
            return False
        if node.op == "Activation":
            return node.parsed_attrs().get("act_type") in (
                "relu", "sigmoid", "tanh", "softrelu")
        return node.op in self._UNARY

    def match(self, node, ctx):
        if id(node) in ctx.claimed or node.is_variable:
            return None
        if not self._link_ok(node):
            return None
        # only root at the END of a chain: a sole whitelisted consumer
        # would extend it, so let that consumer root instead
        nxt = _sole_consumer(ctx, node)
        if (nxt is not None and self._link_ok(nxt)
                and id(nxt) not in ctx.claimed
                and id(node) not in ctx.output_ids):
            return None
        chain = []
        cur = node
        while True:
            if not cur.inputs or cur.inputs[0][1] != 0:
                break
            prev = cur.inputs[0][0]
            if (not self._link_ok(prev) or not _interior_ok(ctx, prev)
                    or _sole_consumer(ctx, prev) is not cur):
                break
            chain.append(prev)
            cur = prev
        if not chain:
            return None
        nodes = list(reversed(chain)) + [node]  # innermost-first, root last
        return Match(node, chain, {"nodes": nodes})

    def externals(self, meta, ins, resolve):
        from .. import fusion

        v = ins[0]
        while isinstance(v, fusion.Lazy):
            v = v.ins[0]
        return (resolve(v),)

    def build(self, meta, args):
        # chain ops captured at plan time ride in via meta["nodes"]
        nodes = meta["nodes"]  # innermost-first list incl. root last

        def baseline(x):
            for n in nodes:
                x = _apply1(n, x)
            return x

        return baseline, [("fused", baseline)]


_PATTERNS = (Attention(), MatmulBiasAct(), NormResidual(), ElemwiseChain())


def get_patterns():
    """All registered patterns, in matching-priority order."""
    return _PATTERNS


def pattern_names():
    return tuple(p.name for p in _PATTERNS)
