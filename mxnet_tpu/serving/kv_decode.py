"""KV-cache incremental decode for the transformer LM (docs/SERVING.md).

Autoregressive serving without per-step recompilation: ONE prefill
executable (prompt bucket, exports every layer's K/V) plus ONE
single-token decode executable over a preallocated KV pool. Both come from
a sealed ``PersistentExecutableCache``, so after warmup a decode of any
length replays exactly two XLA programs — the full-sequence re-forward it
replaces costs O(T) work per token and a recompile per prompt length.

One layout (``PagedKVDecoder``): the decode batch's rows are ``lanes``, one
per sequence, and the lanes share ONE slot axis (``lanes * max_len`` slots
per layer) carved into refcounted page frames; a K or V pool of H heads of
dh is bound ``(frames, page_size, H * dh)``, a page one contiguous piece,
where that row is whole tiles of the chip's 128 lanes, and ``(H, slots,
dh)`` where it is not (``ops.attention.pool_shape``: THE layout rule; the
operators, ``_AdmitScatter`` and ``_cow_page`` follow it, and nothing else
looks inside a pool). What
the decode graph keeps between steps is a list of named buffers the model
gives (``models.transformer.decode_cache``), of three kinds: those pools,
addressed by slot; where a layer keeps a recurrent state or its last
convolution columns instead (``arch="granite_hybrid"``, ``"lfm2_moe"``,
``"nemotron_h"``, whose expert blocks keep NOTHING and are in no list),
per-lane rows ``(lanes, ...)`` addressed by lane; and, where a layer attends
a WINDOW (``arch="mimo_v2_flash"``), per-lane rings ``(lanes, heads, window,
d)`` addressed by lane and position mod the window, which take no frame and
no page-table entry whatever a lane's length. A pool may be READ by more
layers than write it (``arch="phi4flash"``: ONE pool pair that one layer
writes and eight read, the seven behind it keeping nothing): it is one
buffer, donated, written and swapped back once a step, and the accounting
counts its bytes once and a read of it once for every layer that reads. A
token's write happens IN-GRAPH, into the page that holds each lane's
``write_slot`` (models/transformer.py ``get_decode_symbol``), as the program
makes each lane's attention mask of its ``page_table``: a step hands the
device a few numbers a lane, as ONE array the decoder keeps between steps
and patches (``_step_in_symbol``: the program cuts the graph's four inputs
out of it). The decode program OWNS the cache: it takes
every buffer donated and updates it in place, so the cache exists once, and
the updated buffers are program outputs the decoder swaps back in as the
next step's inputs — a device-side pointer swap, no copy, no host
round-trip; the arrays that went in are dead from the enqueue on. Attention over
slots is order-agnostic (position information lives in the embeddings), so
a sequence's tokens may sit in any frames. Physical sharing is then free —
the prefix cache (serving/prefix_cache.py) parks whole prompt chunks at a
refcount and cached admits adopt them without recompute; ``fork`` clones a
sequence by increfing its frames; a write into a shared page
copy-on-writes; and ``rollback``/``verify_chunk`` give speculative decoding
(serving/speculative.py) its accept/reject primitives.

Megasteps (``MXNET_DECODE_MEGASTEP_K``, docs/SERVING.md §megasteps): the
per-token loop above still pays one host round-trip per token.
``step_megastep`` folds K decode steps into ONE compiled program — a
``lax.scan`` over the same decode graph with on-device sampling (greedy
argmax head, or temperature/top-k via the PRNG machinery) — so only (K, B)
token ids cross the host per dispatch. Per-lane early exit reuses the
negative ``write_slot`` idle-lane idiom: once a lane emits ``eos_id`` its
remaining scan steps write NOTHING to its KV slots. K=1 keeps the
single-step path byte-for-byte.
"""
from __future__ import annotations

import os
import time
from typing import Dict, Optional

import numpy as np

from ..base import MXNetError
from .. import telemetry as _tm
from ..executor import _cost_of
from ..ops.attention import _NEG, _band_block, pool_paged, pool_shape
from .cache import PersistentExecutableCache

__all__ = ["PagedKVDecoder", "PagedKVExhausted", "decode_megastep_k"]


def decode_megastep_k(default=1):
    """Decode tokens per dispatch (``MXNET_DECODE_MEGASTEP_K``). K=1 is
    the classic single-step path; K>1 routes the greedy loops through the
    scan megastep. Junk values fall back to ``default``."""
    raw = os.environ.get("MXNET_DECODE_MEGASTEP_K", "").strip()
    if not raw:
        return int(default)
    try:
        k = int(raw)
    except ValueError:
        return int(default)
    return k if k >= 1 else int(default)


def _no_span(name, **attrs):
    """``_tm.span`` for a caller that has read the mode already."""
    return _tm.NULL_SPAN


class _DeviceRecord:
    """What the decoder has sent to the device and not yet seen finish, on
    the host's ``perf_counter``: the one record every program a
    ``PagedKVDecoder`` enqueues passes through, kept only while telemetry is
    on (the callers hold every touch behind one ``_tm.enabled()``).

    Two facts, updated at the two seams the spans already mark. At an
    ENQUEUE's close (``serving.step.dispatch``, ``serving.admit.prefill``,
    ``serving.admit.scatter`` close; ``_cow_page``'s copies are queued) the
    program's kind joins ``in_flight``: ``decode``, ``megastep``, ``chunk``,
    ``prefill``, ``admit_scatter``, ``cow``. At an OBSERVED-READY
    (``serving.step.wait``, ``serving.admit.wait`` close) the program waited
    for and everything enqueued before it leave the list (programs run in the
    order they were enqueued) and ``ready_t`` is stamped; what was enqueued
    behind the waited program, an admission's scatter always, stays.

    The next enqueue's close after a ready records one ``serving.device_gap``
    span from ``ready_t`` to that close (``after``: the kind seen ready,
    ``before``: the kind just enqueued, ``behind``: the kinds still in flight
    at ``ready_t``, comma-joined, ``""`` if none): the host's estimate of an
    interval in which the device had no NEW program, exact where ``behind``
    is empty and an upper bound where it is not. The ``dispatch.host_gap``
    timers read the same stamp (``mark``): from ``ready_t`` to where a
    decode-side dispatch is about to be enqueued, only along a steady chain
    (``admit`` clears ``steady`` when it is done, so the step after an
    admission never counts the admission's time)."""

    __slots__ = ("in_flight", "ready_t", "after", "behind", "steady")

    def __init__(self):
        self.in_flight = []     # kinds, oldest first
        self.ready_t = None     # the last observed-ready
        self.after = None       # its kind, until the gap behind it is written
        self.behind = ""
        self.steady = False     # a ready, and no admission finished since

    def mark(self, site):
        """A decode-side dispatch is about to be enqueued at ``site``: the
        ``dispatch.host_gap`` timers take ready-to-here, the seam the GL7xx
        analyzer prices (docs/OBSERVABILITY.md)."""
        if self.steady:
            dt = time.perf_counter() - self.ready_t
            _tm.timer("dispatch.host_gap").add(dt)
            _tm.timer("dispatch.host_gap." + site).add(dt)

    def enqueued(self, kind):
        """An enqueue of ``kind`` has just closed."""
        if self.after is not None:
            _tm.record_span(
                "serving.device_gap", self.ready_t,
                time.perf_counter() - self.ready_t, after=self.after,
                before=kind, behind=self.behind)
            self.after = None
        self.in_flight.append(kind)

    def ready(self, kind):
        """The newest program of ``kind`` in flight has just been seen
        finished (a program enqueued while the record was off is not in the
        list: everything before the wait then leaves)."""
        self.ready_t = time.perf_counter()
        flying = self.in_flight
        behind = flying[::-1].index(kind) if kind in flying else 0
        del flying[:len(flying) - behind]
        self.after, self.behind, self.steady = kind, ",".join(flying), True


def _dispatch_and_pull(dec, site, kind, span, enqueue, **span_args):
    """One decode-side dispatch and the blocking read of its result, the
    same with telemetry on and off. ``enqueue()`` enqueues the program (of
    ``kind``, as ``_DeviceRecord`` names them) and returns ``(pulled,
    kept)``: the device arrays the host reads now, and what stays on the
    device for the caller. Returns ``(host arrays, kept)``.

    Inside ``span``: ``serving.step.dispatch`` around the enqueue, then
    ``serving.step.read`` around its two halves, ``serving.step.wait`` (the
    host blocked while the device runs the program) and
    ``serving.step.copy`` (device to host of arrays that are ready: the
    device idle; the copy itself is queued behind the program, so the span
    holds what is left of it once the host has woken). The decoder's record
    of the device is told of the enqueue where ``dispatch`` closes and of
    the result where ``wait`` closes, under one mode check."""
    import jax

    record = dec._device if _tm.enabled() else None
    if record is not None:
        record.mark(site)
    with _tm.span(span, **span_args):
        with _tm.span("serving.step.dispatch"):
            pulled, kept = enqueue()
        if record is not None:
            record.enqueued(kind)
        with _tm.span("serving.step.read"):
            # queued behind the program: the copy starts the moment the
            # device finishes, not a host wake-up later (0.12 ms a read)
            for a in pulled:
                a.copy_to_host_async()
            with _tm.span("serving.step.wait"):
                jax.block_until_ready(pulled)
            if record is not None:
                record.ready(kind)
            with _tm.span("serving.step.copy",
                          bytes=sum(a.nbytes for a in pulled)):
                host = [np.asarray(a) for a in pulled]
    return host, kept


class _LogitsRow(np.lib.mixins.NDArrayOperatorsMixin):
    """One lane's ``(vocab,)`` logits of a decode step, as ``step`` returns
    them: an array whose bytes cross to the host when somebody reads them.

    The rows of a step share ``block``, a one-element list that holds the
    step's ``(lanes, vocab)`` device array until the first read of any row
    and the host's copy of it from then on: one transfer a step, inside
    ``serving.step.logits_pull``. ``np.asarray(row)``, indexing, arithmetic
    and every other ``ndarray`` method read; ``shape``, ``dtype`` and
    ``len`` do not, and neither does ``argmax()`` with no axis (what
    ``np.argmax(row)`` calls), which answers with the token the program's
    own ``greedy_token`` head chose for the lane."""

    __slots__ = ("_block", "_lane", "_token")

    def __init__(self, block, lane, token):
        self._block, self._lane, self._token = block, lane, token

    def _host(self):
        block = self._block
        if not isinstance(block[0], np.ndarray):
            with _tm.span("serving.step.logits_pull",
                          bytes=block[0].nbytes):
                block[0] = np.asarray(block[0])
            if _tm.enabled():
                _tm.counter("serving.step_logits_pulls").inc()
                _tm.counter("serving.step_logits_pull_bytes").inc(
                    block[0].nbytes)
        return block[0][self._lane]

    @property
    def shape(self):
        return self._block[0].shape[1:]

    @property
    def dtype(self):
        return np.dtype(self._block[0].dtype)

    def __len__(self):
        return self.shape[0]

    def __array__(self, dtype=None, copy=None):
        row = self._host()
        if dtype is not None and row.dtype != dtype:
            return row.astype(dtype)
        return row.copy() if copy else row

    def __array_ufunc__(self, ufunc, method, *inputs, **kwargs):
        def host(x):
            return x._host() if isinstance(x, _LogitsRow) else x

        if any(isinstance(x, _LogitsRow) for x in kwargs.get("out", ())):
            return NotImplemented  # a row is what the program gave: read-only
        return getattr(ufunc, method)(*(host(x) for x in inputs), **kwargs)

    def __getitem__(self, item):
        return self._host()[item]

    def __iter__(self):
        return iter(self._host())

    def __getattr__(self, name):  # the rest of ndarray: of the host's copy
        if name.startswith("_"):
            raise AttributeError(name)
        return getattr(self._host(), name)

    def argmax(self, axis=None, out=None, **kwargs):
        if axis is None and out is None and not kwargs:
            return self._token
        return self._host().argmax(axis, out, **kwargs)

    def __repr__(self):
        return "_LogitsRow(lane=%d, token=%d, %s)" % (
            self._lane, self._token,
            "on the host" if isinstance(self._block[0], np.ndarray)
            else "on the device")


def _output_at(symbol, name):
    """The index of a graph's output ``name``, or None for a graph that has
    none: ``moe_load (layers, experts)``, where a graph of sparse experts
    reports the rows each expert received; ``greedy_token (lanes,)``."""
    outs = symbol.list_outputs()
    return outs.index(name + "_output") if name + "_output" in outs else None


def _operands_of(cache, input_shapes, op):
    """``[(node, {input name: ShapeDtypeStruct})]`` of every ``op`` node of a
    program's graph, in the graph's order, its operands as inferred at these
    input shapes: what an operator's own rule over shapes and types is asked
    of when the program is traced."""
    import jax

    from ..symbol import Symbol

    nodes = [n for n in cache._sym._topo() if n.op == op]
    if not nodes:
        return []
    res = cache._infer_full(
        input_shapes, Symbol([e for n in nodes for e in n.inputs]))
    structs = (jax.ShapeDtypeStruct(s, t) for s, t in zip(res[1], res[4]))
    return [(n, dict(zip(n.opdef().input_names(n.parsed_attrs()), structs)))
            for n in nodes]


def _pool_readers(symbol):
    """``[(reading node, the pool its keys come from)]`` over a decode
    graph's ``KVPoolAttention`` nodes, in the graph's order: the key operand
    is a cache buffer itself or output j of the step's write, whose operands
    are (pool, rows) pair after pair. A pool may be read by more nodes than
    write it (``phi4flash``: eight layers, one write)."""
    out = []
    for n in symbol._topo():
        if n.op == "_contrib_KVPoolAttention":
            src, j = n.inputs[1]
            out.append((n, src if src.is_variable else src.inputs[2 * j][0]))
    return out


def _pool_reads(cache, input_shapes):
    """What one dispatch of a decode program reads of its pools, a READING
    node: ``[(node, pool, form, slots)]`` over the graph's
    ``KVPoolAttention`` nodes at these input shapes, by name; ``pool`` is the
    cache buffer its keys come from, through the step's write. A pool may be
    read by more nodes than write it (``phi4flash``: eight layers, one
    write), and is then listed once a reader: its bytes are moved once a
    read. The form is the operator's own rule (``pool_read_form``).
    ``slots``: what an XLA form scores a dispatch (the rows' tables whole, the
    pool for every row, or the rows an indexer selected); for the kernel, whose fetch follows the rows'
    contexts, the slots of ONE block (``serving.step_kernel_blocks`` counts
    the blocks of each stepped lane's context, ``serving.step_kernel_slots``
    the pages they fetch)."""
    from ..ops.attention import pool_read_form, pool_slots

    pools = {id(n): pool.name for n, pool in _pool_readers(cache._sym)}
    out = []
    for n, ops in _operands_of(cache, input_shapes,
                               "_contrib_KVPoolAttention"):
        page = n.parsed_attrs().get("page_size", 0)
        table, k, v = ops.get("page_table"), ops["pool_k"], ops["pool_v"]
        form = pool_read_form(
            ops["query"], k, None if n.inputs[1] == n.inputs[2] else v,
            table, page, ops.get("selected"))
        rows = ops["query"].shape[0]
        if form == "selected":
            slots = rows * ops["selected"].shape[1]
        elif form == "kernel":
            from ..ops.pallas_paged_read import block_slots

            slots = block_slots(k, v, table.shape[1])
        elif form == "own_pages":
            slots = rows * table.shape[1] * page
        else:
            slots = rows * pool_slots(k.shape)
        out.append((n.name, pools[id(n)], form, slots))
    return out


def _pool_writes(cache, input_shapes):
    """What one dispatch of a decode program writes into its pools, a
    ``KVPoolSlotWrite`` node: ``[(form, rows)]`` at these input shapes, the
    form the operator's own rule names for the node's pools
    (``pool_write_form``) and the rows it writes, one a row and pool."""
    from ..ops.attention import pool_write_form

    out = []
    for n, ops in _operands_of(cache, input_shapes,
                               "_contrib_KVPoolSlotWrite"):
        pools = n.parsed_attrs().get("num_pools", 1)
        out.append((pool_write_form(
            [ops["pool_%d" % i] for i in range(pools)]),
            pools * ops["rows_0"].shape[0]))
    return out


def _moe_forms(cache, input_shapes):
    """``(forms, depth)``: the form of every ``MoEFeedForward`` node of a
    program's graph at these input shapes, ``["kernel" | "ragged_dot"]`` in
    the graph's order, and the deepest fetch ring among its kernel layers (0
    where none is), by the operator's own rules
    (``pallas_grouped_matmul.moe_form``, ``layer_tiles``) at the rows its
    products take: every assignment's, or a chunk of the held ones
    (``moe.held_rows_chunk``)."""
    import jax

    from ..ops.moe import held_rows_chunk
    from ..ops.pallas_grouped_matmul import layer_tiles, moe_form

    forms, depth = [], 0
    for n, ops in _operands_of(cache, input_shapes,
                               "_contrib_MoEFeedForward"):
        data, up = ops["data"], ops["up_weight"]
        attrs = n.parsed_attrs()
        gated = attrs.get("gated", True)
        tokens, k = data.shape[0], attrs["num_experts_per_tok"]
        chunk = held_rows_chunk(
            tokens, k, up.shape[0],
            attrs["num_experts"] + attrs.get("num_zero_experts", 0))
        rows = jax.ShapeDtypeStruct((chunk or tokens * k, data.shape[1]),
                                    data.dtype)
        forms.append(moe_form(rows, up, ops["down_weight"]))
        if forms[-1] == "kernel":
            depth = max(depth, layer_tiles(
                rows, up, None if chunk else attrs["num_experts"],
                gated)[3])
    return forms, depth


def _attention_sites(cache, input_shapes):
    """``[(form, window, operands)]`` of every ``MultiHeadAttention`` node of
    a program's graph at these input shapes, in the graph's order: the form
    by the operator's own rule (``ops.attention.attention_form``; no program
    here traces under a mesh)."""
    from ..ops.attention import attention_form

    sites = []
    for n, ops in _operands_of(cache, input_shapes,
                               "_contrib_MultiHeadAttention"):
        attrs = n.parsed_attrs()
        window = attrs.get("window", 0)
        sites.append((attention_form(
            ops["query"], ops["key"], ops["value"], attrs["causal"], window,
            bool(attrs.get("sink")), None, attrs.get("topk", 0)), window,
            ops))
    return sites


def _window_pairs_scored(cache, input_shapes):
    """The (query, key) pairs ONE window layer of a prefill program scores
    over its bucket, in the form the operator's own rule names for the
    program's first ``MultiHeadAttention(window=)`` node: every block a
    query block visits, whole, under the blockwise kernel
    (``"window_kernel"``: ``pallas_attention.window_pairs_scored`` at the
    rule's blocks), ``T x 2 x block`` as a band, ``T x T`` dense; 0 where the
    program has no such node."""
    from ..ops import pallas_attention as pa

    for form, w, ops in _attention_sites(cache, input_shapes):
        if w <= 0:
            continue
        q, k, v = ops["query"], ops["key"], ops["value"]
        t = q.shape[2]
        if form == "window_kernel":
            return pa.window_pairs_scored(t, t, *pa.blocks(
                t, t, q.shape[1] // k.shape[1], q.shape[3], v.shape[3],
                q.dtype, window=w), w)
        return t * 2 * _band_block(t, w) if form == "band" else t * t
    return 0


def _swap_cache(exe, names):
    """Hand the updated cache buffers (program outputs, in the cache's order
    after the logits) back as the next dispatch's inputs — device-side
    pointer swaps, no copy. The decode program took what ``arg_dict`` held
    donated, so those arrays died at its enqueue and these stand in their
    place. ``arg_dict`` owns the cache from here on: ``exe.outputs[1..]``
    still names the same arrays, and they die with the next donated update
    (the next step, ``_AdmitScatter``), so every reader takes a buffer from
    ``arg_dict`` at the time of use."""
    exe.rebind(names, [o._jax() for o in exe.outputs[1:1 + len(names)]])


# a lane's row of a step's ONE host-fed array: its token, its position, the
# slot the token lands in, then the frames of its pages in order
_STEP_COLUMNS = ("data", "pos_idx", "write_slot", "page_table")
_SLOT_AT, _TABLE_AT = 2, 3


def _step_in_symbol(decode, pages):
    """``decode`` (``get_decode_symbol``'s graph, whatever the arch) fed by
    ONE array: its four host-fed Variables (``_STEP_COLUMNS``: ``data``,
    ``pos_idx``, ``write_slot`` (lanes, 1) and ``page_table`` (lanes,
    ``pages``)) become static slices of ``step_in`` (lanes, 3 + ``pages``)
    float32, cut inside the same program, so a step transfers one array and
    every operator receives the values it received before. The graph is
    rewired in place (the builders make a fresh one a call) and returned;
    the model graphs, the megastep's feed and whoever binds a decode graph
    directly keep the four names."""
    from .. import symbol as _sym

    step_in, cuts, at = _sym.Variable("step_in"), {}, 0
    for name in _STEP_COLUMNS:
        width = pages if name == "page_table" else 1
        cuts[name] = _sym.slice_axis(
            step_in, axis=1, begin=at, end=at + width,
            name="step_" + name)._outputs[0]
        at += width
    for node in decode._topo():
        node.inputs = [cuts.get(src.name, (src, j)) if src.is_variable
                       else (src, j) for src, j in node.inputs]
    return decode


# ------------------------------------------------------------------ megastep
class _Sampler:
    """On-device sampling config for megasteps: ``greedy`` takes the
    graph's argmax head; ``topk`` divides logits by ``temperature``,
    masks everything below the ``top_k``-th logit (0 = no truncation) and
    draws with ``jax.random.categorical``."""

    __slots__ = ("mode", "temperature", "top_k")

    def __init__(self, mode="greedy", temperature=1.0, top_k=0):
        if mode not in ("greedy", "topk"):
            raise MXNetError("decode sampler: mode must be 'greedy' or "
                             "'topk', got %r" % (mode,))
        self.mode = mode
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        if self.temperature <= 0:
            raise MXNetError("decode sampler: temperature must be > 0")
        if self.top_k < 0:
            raise MXNetError("decode sampler: top_k must be >= 0")

    def key(self):
        return (self.mode, self.temperature, self.top_k)


def _sampler_from(sample=None, temperature=None, top_k=None):
    """Resolve sampler knobs: explicit arguments win over the
    MXNET_DECODE_SAMPLE / _TEMP / _TOPK environment defaults."""
    mode = sample or os.environ.get("MXNET_DECODE_SAMPLE", "greedy")
    if temperature is None:
        temperature = float(os.environ.get("MXNET_DECODE_SAMPLE_TEMP",
                                           "1.0"))
    if top_k is None:
        top_k = int(os.environ.get("MXNET_DECODE_SAMPLE_TOPK", "0"))
    return _Sampler(mode, temperature, top_k)


def _sampling_key(dec):
    """Per-decoder PRNG base key for on-device sampling. Seeded from the
    decoder's ``sample_seed`` ctor arg, else MXNET_DECODE_SAMPLE_SEED,
    else split off the global PRNG stream. The base key is FIXED for the
    decoder's life — the megastep folds the absolute position and lane
    index into it per draw, so a seeded decode emits the same tokens no
    matter how the steps are partitioned into megasteps."""
    if dec._sample_key is None:
        import jax

        seed = dec._sample_seed
        if seed is None:
            raw = os.environ.get("MXNET_DECODE_SAMPLE_SEED", "").strip()
            seed = int(raw) if raw else None
        if seed is not None:
            dec._sample_key = jax.random.PRNGKey(int(seed))
        else:
            from .. import random as _rnd

            dec._sample_key = _rnd._next_key()
    return dec._sample_key


class _SealedProgram:
    """One jitted program of the decoder, compiled ONCE at warm time and
    sealed like the ``PersistentExecutableCache``: every later dispatch is a
    jit cache hit (``executor.cache_hit``), and inputs whose shapes or types
    drift from the warmed ones are a hard retrace error
    (``executor.retrace``), never a silent recompile.

    A subclass hands its function to ``_jit`` and gives two things:
    ``_dummy(dec)``, the ``(sealed, rest)`` inputs of a dispatch that changes
    nothing, and ``_dispatch(dec, sealed, *rest)``, which enqueues the
    program; its ``run`` goes through ``_run``. ``sealed`` is the tuple of
    arrays whose signature is held; weights and pool are read from the live
    decode executable at dispatch time (``_live``), so a hitless
    ``swap_params`` or a donated pool lands in the very next one."""

    def __init__(self, what, rule, span, **span_args):
        self._what, self._rule = what, rule     # of the drift error
        self._span, self._span_args = span, span_args   # of the warm compile
        self._fn = self._sig = None

    def _jit(self, fn, label, donate_argnums=()):
        import jax

        from ..executor import _named

        self._fn = jax.jit(_named(fn, label), donate_argnums=donate_argnums)

    @staticmethod
    def _sig_of(arrays):
        return tuple((tuple(a.shape), str(a.dtype)) for a in arrays)

    @staticmethod
    def _live(dec, names):
        """The decode executable's arguments ``names`` as they are NOW."""
        args = dec._dec_exe.arg_dict
        return tuple(args[n]._jax() for n in names)

    def _graph(self, symbol, inputs, cache_names):
        """Bind a serving graph: ``interpret(weights, kvs, feed, key)`` runs
        it on ``feed`` (its ``inputs`` by name) and the pool
        (``cache_names``) and returns its outputs. Every other argument is a
        weight, shared by name across the serving graphs
        (``weight_names``)."""
        from ..executor import _GraphProgram

        prog = _GraphProgram(symbol)
        if prog.aux_names:
            raise MXNetError("%s: the graph must carry no aux state, got %r"
                             % (self._what, prog.aux_names))
        self.kv_names = list(cache_names)
        fed = set(inputs).union(self.kv_names)
        self.weight_names = [n for n in prog.arg_names if n not in fed]

        def interpret(weights, kvs, feed, key):
            feed = dict(feed, **dict(zip(self.kv_names, kvs)))
            args = [feed[n] if n in feed else weights[n]
                    for n in prog.arg_names]
            return prog.interpret(args, (), False, key)[0]

        return interpret

    def _weights_and_pool(self, dec):
        """A bound graph's first two arguments, read at dispatch time."""
        return (dict(zip(self.weight_names,
                         self._live(dec, self.weight_names))),
                self._live(dec, self.kv_names))

    def warm(self, dec):
        """Compile NOW with the subclass's do-nothing dispatch and seal its
        signature, counted as the one ``executor.compile`` this program
        ever charges — bench warmup snapshots see it, the steady state
        never does."""
        import jax

        sealed, rest = self._dummy(dec)
        with _tm.span(self._span, **self._span_args):
            out = self._dispatch(dec, sealed, *rest)
            # graphlint: waive GL7xx -- warm-time compile barrier, not the dispatch path
            jax.block_until_ready(out)
        self._sig = self._sig_of(sealed)
        if _tm.enabled():
            _tm.counter("executor.compile").inc()

    def _run(self, dec, sealed, *rest):
        """One dispatch; a drifted signature is refused before anything is
        enqueued."""
        sig = self._sig_of(sealed)
        if sig != self._sig:
            if _tm.enabled():
                _tm.counter("executor.retrace").inc()
            raise MXNetError(
                "%s: input signature drifted from the warmed shapes "
                "(%r != %r) — %s sealed like the executable cache"
                % (self._what, sig, self._sig, self._rule))
        if _tm.enabled():
            _tm.counter("executor.cache_hit").inc()
        return self._dispatch(dec, sealed, *rest)


class _DecodeMegastep(_SealedProgram):
    """K decode steps folded into ONE compiled program.

    A ``jax.jit``-ted ``lax.scan`` over the decode graph
    (``_GraphProgram.interpret`` is pure and jit-safe): the scan carries
    (next token, done mask, KV pool), each step hands the graph its lanes'
    write slots and the page table of all K positions (constant over the
    scan: the graph reads ``pos + 1`` slots of it), samples the next token
    ON DEVICE, and only the stacked (K, B) ids + activity mask ever cross
    to the host. EOS'd / idle lanes carry a negative ``write_slot`` — their
    KV passes through bitwise-unchanged (the idle-lane idiom ``step``
    already relies on)."""

    def __init__(self, dec, k, sampler):
        import jax
        import jax.numpy as jnp

        from ..models import transformer as _tf

        self.k = int(k)
        B, L, S = dec.lanes, dec.num_layers, dec.total_slots
        pos_len = dec.pos_len
        super().__init__("decode megastep (K=%d)" % self.k,
                         "megastep programs are", "serving.megastep_compile",
                         k=self.k, rows=B, sampler=sampler.mode)
        interpret = self._graph(
            _tf.get_decode_symbol(
                vocab_size=dec.vocab_size, num_layers=L,
                num_heads=dec.num_heads, model_dim=dec.model_dim,
                ffn_dim=dec.ffn_dim, max_len=S, pos_len=pos_len,
                page_size=dec.page_size),
            ("data", "pos_idx", "write_slot", "page_table"),
            dec._cache_names)
        mode, temp, top_k = sampler.mode, sampler.temperature, sampler.top_k
        lane_ids = jnp.arange(B)

        def _sample(logits, pos_abs, base_key):
            lg = logits.astype(jnp.float32) / jnp.float32(temp)
            if top_k > 0:
                kth = jax.lax.top_k(lg, top_k)[0][:, -1:]
                lg = jnp.where(lg < kth, -jnp.inf, lg)

            def draw(p, lane, row):
                # fold ABSOLUTE position then lane: reproducible across
                # any K partitioning of the same decode
                return jax.random.categorical(
                    jax.random.fold_in(
                        jax.random.fold_in(base_key, p), lane), row)

            return jax.vmap(draw)(pos_abs, lane_ids, lg)

        def run(weights, kvs, tok0, pos, slots, table, done0, key, eos):
            def body(carry, xs):
                tok, done, kv = carry
                t, slot_col = xs
                act = jnp.logical_not(done)
                # idle/done lanes clamp their position into the trained
                # table; their write slot is negative so the value is
                # never written anywhere
                pos_t = jnp.clip(pos + t, 0, pos_len - 1)
                outs = interpret(
                    weights, kv,
                    {"data": tok.astype(jnp.float32)[:, None],
                     "pos_idx": pos_t.astype(jnp.float32)[:, None],
                     "write_slot": jnp.where(act, slot_col, -1).astype(
                         jnp.float32)[:, None],
                     "page_table": table}, key)
                new_kv = tuple(outs[1 + j] for j in range(2 * L))
                if mode == "greedy":
                    nxt = outs[-1].astype(jnp.int32)  # on-device argmax head
                else:
                    nxt = _sample(outs[0], pos + t, key).astype(jnp.int32)
                nxt = jnp.where(act, nxt, jnp.maximum(eos, 0))
                done = jnp.logical_or(
                    done, jnp.logical_and(act, (eos >= 0) & (nxt == eos)))
                return (nxt, done, new_kv), (nxt, act)

            xs = (jnp.arange(self.k), jnp.transpose(slots))
            (_tok, done_f, kv_f), (toks, acts) = jax.lax.scan(
                body, (tok0, done0, kvs), xs)
            return toks, acts, kv_f, done_f

        self._jit(run, "mx_megastep%d" % self.k)

    def _dummy(self, dec):
        B = dec.lanes
        return (np.zeros((B,), np.int32), np.zeros((B,), np.int32),
                np.zeros((B, self.k), np.int32), dec._page_table(),
                # every lane idle: compiles, writes nothing
                np.ones((B,), bool)), (np.int32(-1),)

    def _dispatch(self, dec, sealed, eos):
        return self._fn(*self._weights_and_pool(dec), *sealed,
                        _sampling_key(dec), eos)

    def run(self, dec, tok0, pos, slots, table, done0, eos):
        """One megastep dispatch. Returns device-resident
        ``(toks (K,B) i32, acts (K,B) bool, new_kvs, done)`` — the caller
        pulls the ids (the only host transfer) and pointer-swaps the KV."""
        return self._run(dec, (tok0, pos, slots, table, done0), eos)


def _megastep_for(dec, k, sampler):
    """The decoder's cached megastep program for ``(K, sampler)`` —
    built + warm-compiled once, a jit cache hit forever after."""
    cache_key = (int(k), sampler.key())
    ms = dec._megasteps.get(cache_key)
    if ms is None:
        ms = _DecodeMegastep(dec, k, sampler)
        ms.warm(dec)
        dec._megasteps[cache_key] = ms
    return ms


class _ChunkProgram(_SealedProgram):
    """T tokens of ONE lane scored (and optionally written) in a single
    rectangular dispatch over the pool (models/transformer.py
    ``get_chunk_symbol``). Chunked prefill — admit computes only a
    prompt's un-cached tail, C tokens per dispatch — and the speculative
    draft-verify pass (γ+1 candidate positions at once) are the SAME
    program at different T."""

    def __init__(self, dec, t):
        from ..models import transformer as _tf

        self.t = int(t)
        S, L = dec.total_slots, dec.num_layers
        super().__init__("chunk program (T=%d)" % self.t,
                         "chunk programs are", "serving.chunk_compile",
                         t=self.t)
        interpret = self._graph(
            _tf.get_chunk_symbol(
                vocab_size=dec.vocab_size, num_layers=L,
                num_heads=dec.num_heads, model_dim=dec.model_dim,
                ffn_dim=dec.ffn_dim, chunk_len=self.t, total_slots=S,
                pos_len=dec.pos_len),
            ("data", "pos_idx", "write_onehot", "att_mask"),
            dec._cache_names)

        def run(weights, kvs, data, pos_idx, w_oh, mask, key):
            outs = interpret(weights, kvs,
                             {"data": data, "pos_idx": pos_idx,
                              "write_onehot": w_oh, "att_mask": mask}, key)
            new_kv = tuple(outs[1 + j] for j in range(2 * L))
            return outs[0], new_kv, outs[-1]

        self._jit(run, "mx_chunk%d" % self.t)

    def _dummy(self, dec):
        """An all-pad chunk: zero writes, fully masked."""
        T, S = self.t, dec.total_slots
        return (np.zeros((1, T), np.float32), np.zeros((1, T), np.float32),
                np.zeros((T, S), np.float32),
                np.full((T, S), _NEG, np.float32)), ()

    def _dispatch(self, dec, sealed):
        return self._fn(*self._weights_and_pool(dec), *sealed,
                        _sampling_key(dec))

    def run(self, dec, data, pos_idx, w_oh, mask):
        """One chunk dispatch. Returns device-resident
        ``(logits (T, vocab), new_kvs, tokens (T,))`` — the caller
        pointer-swaps the KV and pulls only what it needs."""
        return self._run(dec, (data, pos_idx, w_oh, mask))


class _AdmitScatter(_SealedProgram):
    """The cache update of a classic admission as ONE program: every cache
    buffer goes in DONATED and comes back updated in place — a pool with the
    prefill's K/V at positions ``0..length-1`` of the lane's page frames, a
    per-lane buffer with the prefill's state in the lane's row, a window
    layer's ring with the prompt's last ``window`` keys or values in the
    lane's ring, each at its position mod the window.

    ``run(dec, new, frames, length, lane)``: ``new`` is the prefill
    executable's cache outputs, ``(1, H, prefill_len, dh)`` for a pool
    (``(passes, H, prefill_len, dh)`` where the stack is looped: pass u's
    rows land a whole pass's frames behind pass u - 1's, same table) and
    ``(1,) + row`` for a per-lane buffer, ``(1, H, prefill_len, d)`` again
    for a ring (the sealed inputs), ``frames`` the
    lane's page-frame table, ``length`` the prompt length, ``lane`` the
    lane's index. Every shape is the decoder's, none the prompt's, so one
    compile serves every prompt length. The pool update walks
    the prompt's pages with ``dynamic_update_slice`` — a page is a
    contiguous slot run, and in a page-major pool
    (``ops.attention.pool_shape``) ONE ``(page, H * d)`` piece at its frame —
    and blends the last, partial page with what the pool holds there, so
    exactly the slots of positions ``< length`` change. A scatter over the
    slot axis would say the same, but the TPU keeps a narrow head-major pool
    with slots minor-most and re-lays the WHOLE buffer out around a scatter,
    twice per buffer; the page walk leaves either layout in place.
    A row is one ``dynamic_update_slice`` at the lane's index, and so is a
    ring, after a gather of ``window`` positions of the prompt: slot j takes
    the last position before ``length`` that is j mod the window (a slot
    past a prompt shorter than the window takes anything: the read masks
    it), so the ring is what the decode steps would have left."""

    def __init__(self, dec):
        import jax
        import jax.numpy as jnp

        super().__init__("admit scatter", "the cache-update program is",
                         "serving.admit_scatter_compile")
        self.kv_names = [name for name, _, _ in dec._cache]
        self.pools = pools = [j for j, (_, kind, _) in enumerate(dec._cache)
                              if kind == "pool"]
        self.rows = rows_at = [j for j in range(len(dec._cache))
                               if j not in pools]
        rings = {j: shape[1] for j, (_, kind, shape) in enumerate(dec._cache)
                 if kind == "ring"}
        ps = dec.page_size
        self.n_pages = -(-dec.prefill_len // ps)
        tail = self.n_pages * ps - dec.prefill_len

        # a page-major pool (``pool_shape``) takes a page as one piece
        paged = [pool_paged(*dec._cache[j][2]) for j in pools]

        def run(bufs, new, frames, at):
            length, lane = at[0], at[1]
            # a pool's rows, an array a PASS (one pass for every arch but a
            # looped one, whose prefill hands a layer's passes over
            # together), cut apart HERE, outside the page walk: (H, T, d);
            # for a page-major pool (T, H * d), a token's heads side by side
            # as its pool keeps them
            rows = [tuple(new[j][u].transpose(1, 0, 2).reshape(
                              new[j].shape[2], -1) if pm else new[j][u]
                          for u in range(new[j].shape[0]))
                    for j, pm in zip(pools, paged)]
            if tail:  # so a page-sized slice never runs off the end
                rows = [tuple(jnp.pad(one, ((0, tail), (0, 0)) if pm
                                      else ((0, 0), (0, tail), (0, 0)))
                              for one in r) for r, pm in zip(rows, paged)]
            in_page = jnp.arange(ps, dtype=jnp.int32)[None, :, None]

            def page(j, kvs):
                live = in_page < length - j * ps
                out = []
                for kv, r, pm in zip(kvs, rows, paged):
                    # pass u of a pool sits a whole pass's frames further on
                    for u, one in enumerate(r):
                        if pm:  # (1, page, H * d) at frame frames[j]
                            at = (frames[j] + u * (kv.shape[0] // len(r)),
                                  0, 0)
                            blk = jax.lax.dynamic_slice(
                                one, (j * ps, 0), (ps, one.shape[1]))[None]
                        else:   # (H, page, d) at slot frames[j] * page
                            at = (0, frames[j] * ps
                                  + u * (kv.shape[1] // len(r)), 0)
                            blk = jax.lax.dynamic_slice(
                                one, (0, j * ps, 0),
                                (kv.shape[0], ps, kv.shape[2]))
                        old = jax.lax.dynamic_slice(kv, at, blk.shape)
                        kv = jax.lax.dynamic_update_slice(
                            kv, jnp.where(live, blk, old), at)
                    out.append(kv)
                return tuple(out)

            out = list(bufs)
            for j, kv in zip(pools, jax.lax.fori_loop(
                    0, (length + ps - 1) // ps, page,
                    tuple(bufs[j] for j in pools))):
                out[j] = kv
            for j in rows_at:
                at = (lane,) + (0,) * (bufs[j].ndim - 1)
                value = new[j]
                if j in rings:
                    slot = jnp.arange(rings[j], dtype=jnp.int32)
                    held = length - 1 - (length - 1 - slot) % rings[j]
                    value = jnp.take(value, jnp.clip(
                        held, 0, value.shape[2] - 1), axis=2)
                # a zero-length prompt (the warm dispatch) changes nothing
                row = jnp.where(length > 0, value, jax.lax.dynamic_slice(
                    bufs[j], at, value.shape))
                out[j] = jax.lax.dynamic_update_slice(bufs[j], row, at)
            return tuple(out)

        self._jit(run, "mx_admit_scatter", donate_argnums=(0,))

    def _dummy(self, dec):
        """A zero-length prompt on the live cache: no page is walked, every
        buffer comes back bitwise as it went in. The new values come from a
        prefill staged the way an admission stages it, so the arrays are of
        the kind a real call passes and jit never compiles again."""
        pf = dec._stage_prefill(np.zeros((1, 0), np.float32))
        pf.forward(is_train=False)
        return dec._prefill_cache(pf), ((), 0, 0)

    def _dispatch(self, dec, new, frames, length, lane):
        """Donate the cache, run, hand the updated buffers back: from the
        enqueue on, the arrays ``arg_dict`` held before are dead."""
        table = np.zeros((self.n_pages,), np.int32)
        table[:len(frames)] = frames
        # length and lane travel as ONE small array: a host-to-device copy
        # costs the same however small
        out = self._fn(self._live(dec, self.kv_names), new, table,
                       np.array([length, lane], np.int32))
        exe = dec._dec_exe
        exe.rebind([self.kv_names[j] for j in self.pools],
                   [out[j] for j in self.pools])
        if self.rows:
            with _tm.span("serving.admit.state", buffers=len(self.rows)):
                exe.rebind([self.kv_names[j] for j in self.rows],
                           [out[j] for j in self.rows])
        return out

    def run(self, dec, new, frames, length, lane):
        """One cache update, enqueued. ``frames`` is the lane's frame table
        (any length up to ``n_pages``), ``length`` the prompt length,
        ``lane`` the lane's index."""
        self._run(dec, new, frames, length, lane)
        if _tm.enabled():
            _tm.counter("serving.admit_scatter_dispatches").inc()


# --------------------------------------------------------------- paged decode
class PagedKVExhausted(MXNetError):
    """The paged KV pool cannot satisfy an allocation: no free lane for a
    new sequence, or no free page for a growing one. Retire a sequence (or
    size the pool larger) and retry — this is admission backpressure, not
    corruption."""


class _PagePool:
    """REFCOUNTED block allocator over ONE global slot axis
    (docs/SERVING.md §Prefix cache).

    The pool's ``lanes * slots`` KV slots form a single physical space
    carved into fixed-size page frames; any lane (and the prefix index)
    may reference any frame, which is what lets N concurrent sequences —
    and the cache — point at ONE physical copy of a shared prompt
    prefix. Every holder owns a reference: ``acquire`` hands out a frame
    at refcount 1, ``incref`` adds a holder, ``release`` drops one and
    returns the frame to the free list only when the LAST holder lets
    go — so eviction/retire can never free a page some other lane still
    attends (refcount > 1 just decrements).

    Frames come off a LIFO free list, and ``release`` pushes them back
    REVERSED so a retire-then-readmit (or rollback-then-regrow) replays
    the original placement order — physical placement is routinely
    non-contiguous (attention is slot-order-agnostic) but DETERMINISTIC,
    which the bitwise cached-admit parity gate leans on. A ``budget``
    below the physical frame count models admission control against a
    smaller HBM reservation: a shared frame counts ONCE no matter how
    many holders it has."""

    def __init__(self, lanes, slots, page_size, budget=None):
        if slots % page_size:
            raise MXNetError("paged_kv: page_size %d must divide the %d "
                             "slots per lane" % (page_size, slots))
        self.lanes = int(lanes)
        self.page_size = int(page_size)
        self.frames_per_lane = slots // page_size
        self.total_frames = self.lanes * self.frames_per_lane
        self.budget = int(budget) if budget else self.total_frames
        # LIFO: pop() serves the highest-numbered frame first; release()
        # re-stacks reversed so re-acquisition replays acquisition order
        self._free = list(range(self.total_frames))
        self._ref: Dict[int, int] = {}  # frame -> holder count

    @property
    def in_use(self):
        """Frames with at least one holder (each counts once — sharing
        is free under the budget)."""
        return len(self._ref)

    def can_acquire(self, n=1):
        return len(self._free) >= n and self.in_use + n <= self.budget

    def acquire(self):
        """One free frame at refcount 1, or raise ``PagedKVExhausted``."""
        if self.in_use >= self.budget:
            raise PagedKVExhausted(
                "paged_kv: page budget exhausted (%d/%d frames in use); "
                "retire a sequence and retry" % (self.in_use, self.budget))
        if not self._free:
            raise PagedKVExhausted(
                "paged_kv: no free page frame (%d frames all referenced) "
                "— retire a sequence or evict cached prefixes and retry"
                % self.total_frames)
        f = self._free.pop()
        self._ref[f] = 1
        return f

    def incref(self, frame):
        """Add a holder to an allocated frame (page sharing)."""
        self._ref[frame] += 1

    def refcount(self, frame):
        return self._ref.get(frame, 0)

    def release(self, frames):
        """Drop ONE reference per listed frame; frames whose last holder
        left go back on the free list (reversed — see class docstring)."""
        freed = []
        for f in frames:
            n = self._ref[f] - 1
            if n:
                self._ref[f] = n
            else:
                del self._ref[f]
                freed.append(f)
        self._free.extend(reversed(freed))


class _Lane:
    __slots__ = ("seq_id", "pos", "_frames", "stale")

    def __init__(self, seq_id):
        self.seq_id = seq_id
        self.pos = 0            # next position to be written
        self.frames = ()

    @property
    def frames(self):
        """Logical page -> physical frame index: a tuple, so a page map
        changes nowhere but in the setter."""
        return self._frames

    @frames.setter
    def frames(self, frames):
        """THE place a lane's page map changes (a page appended, copied,
        adopted, shared or dropped): the lane's row of the decoder's staged
        step input no longer holds it (``PagedKVDecoder.step`` rewrites a
        stale row and no other)."""
        self._frames = tuple(frames)
        self.stale = True


class PagedKVDecoder:
    """Multiplexed KV-cache decode: ONE decode batch serves many
    concurrent, independently-positioned sequences (docs/SERVING.md).

    The decode executable's batch rows are ``lanes``: sequences are
    admitted one at a time (a batch-1 prefill seeds that lane's slots),
    advance at their own positions, and retire independently — the
    continuous-batching idea applied to autoregressive decode; ``lanes``
    prompts admitted together and stepped together are a lockstep batch.
    Slot storage is paged: a lane's ``max_len`` slots are carved into
    ``page_size``-slot frames allocated on demand from a ``_PagePool``
    (and freed at retire), so short sequences don't reserve ``max_len``
    slots of KV for their whole life and admission fails with a structured
    ``PagedKVExhausted`` instead of an OOM.

    Per-lane math does not depend on what the other lanes hold (each lane
    carries its own write slot and page-table row), so multiplexed decode is
    token-identical to sequential per-request decode — the acceptance
    test pins exactly that.

    KV storage is ONE slot pool (``get_decode_symbol``): per layer the
    buffers are (H, lanes·max_len, dh) and every lane's write slot and page
    table index the shared axis, so a page frame is just a slot range ANY
    lane can reference. That is the substrate for cross-request prefix reuse
    (serving/prefix_cache.py): with ``prefix_cache=True`` (or
    ``MXNET_SERVE_PREFIX_CACHE=1``) admit hashes the prompt in
    ``prefix_chunk``-token chunks, adopts the cached pages of the longest
    matched chunk chain at a refcount (no copy, no recompute), and
    chunk-prefills ONLY the unmatched tail through the rectangular chunk
    program. A lane's first write into a page some other holder still
    references triggers a copy-on-write private copy (``fork`` shares
    all pages this way). ``rollback`` truncates a sequence by releasing
    whole rejected pages — the speculative-decoding accept/reject
    primitive (serving/speculative.py).

    ``arch="olmoe"`` serves the sparse-expert block of
    ``models/transformer.py`` (``head_dim``, ``num_experts``,
    ``num_experts_per_tok``, ``rope_theta``, ``rms_eps``; ``ffn_dim`` is one
    expert's width) through the same admission, pool and single-step decode.
    It has no position table, so a lane is bounded by ``max_len`` alone;
    ``dtype`` is then the type of the weights AND of the pool, while token
    ids, positions, slots and frames stay float32 (a token id of 50,303
    does not survive bfloat16). The prefix cache, the chunk and verify
    programs and the megastep are not built for it yet and raise.

    ``arch="granite_hybrid"`` serves the Mamba-2 / attention hybrid block
    (further sizes of ``models.transformer``'s builder as keywords:
    ``layer_types``, ``num_kv_heads``, ``mamba_heads``, ``mamba_state``...).
    Its attention layers alone have K/V pools; every Mamba layer keeps a
    lane's recurrent state and last convolution columns in per-lane row
    buffers, float32 whatever ``dtype`` is, written at admission by the same
    donated program as the pages and advanced by every step that steps the
    lane. The prefill is told the prompt's length. ``fork`` and ``rollback``
    raise too: a state that is one row cannot be shared or taken back
    without a snapshot.

    ``arch="lfm2_moe"`` serves the gated-short-convolution / attention block
    with sparse experts (``layer_types``, ``num_kv_heads``, ``moe_ffn_dim``,
    ``first_dense_layers``, ``conv_kernel``...): K/V pools for its attention
    layers, a per-lane float32 row of the last ``conv_kernel - 1`` gated
    columns for every conv layer, handed over and advanced as
    ``granite_hybrid``'s rows are, and the experts' load read from both
    graphs as for ``deepseek_v3``. It refuses what both of those refuse.

    ``arch="mimo_v2_flash"`` serves the window / full attention block with
    sparse experts (``hybrid_layer_pattern``, ``moe_layer_freq``,
    ``num_kv_heads`` and ``swa_num_kv_heads``, ``head_dim`` above
    ``v_head_dim``, ``sliding_window``, ``rotary_dim``, two rotary bases,
    ``attention_value_scale``, ``num_local_experts`` of ``num_experts`` from
    ``local_expert_offset`` on). A full layer keeps paged K and V pools, the
    key's wider than the value's. A window layer keeps a lane's last
    ``sliding_window`` keys and values in per-lane RINGS of the pools' type:
    they take no frame of the page pool and no entry of the page table, so a
    lane's window bytes do not depend on its length; an admission writes the
    prompt's last positions there in the same donated program as the pages,
    a step writes its token at ``pos mod window`` and reads the slots that
    hold a position of THIS sequence (a re-admitted lane never sees its
    predecessor's). The expert layers hold a SHARE of the experts they route
    over; the load read from both graphs counts all of them. It refuses what
    ``lfm2_moe`` refuses: a ring cannot be shared, and taking a token back
    would need the one it overwrote.

    ``arch="phi4flash"`` serves the SambaY decoder-hybrid-decoder with
    differential attention (``num_kv_heads``, ``head_dim``,
    ``sliding_window``, ``mamba_state``, ``mamba_conv``, ``mamba_expand``,
    ``mamba_dt_rank``; the mixer of a layer follows from its depth). The
    self-decoder, layers 0 to N/2, keeps Mamba-1 rows (state (N, E) and
    convolution columns, float32) and window rings as the two archs above
    do; layer N/2 + 1 keeps THE pool pair, keys and values in pairs of heads
    side by side; the layers behind it keep nothing: a gated memory unit
    reads a tensor the step carries from layer N/2's scan, a cross layer
    reads layer N/2 + 1's pool, in the same step after that layer's write.
    An admission's prefill runs the self-decoder and layer N/2 + 1's keys
    and values over the bucket and everything behind them over the prompt's
    LAST row alone, so it hands back one row of logits
    (``serving.admit_self_rows`` / ``serving.admit_cross_rows``). It refuses
    what ``mimo_v2_flash`` refuses.

    ``arch="nemotron_h"`` serves blocks of ONE mixer each
    (``models.transformer._nemotron_h_layer``; extra sizes: ``layer_types``
    of ``"mamba" | "moe" | "attention"``, ``num_kv_heads``, ``head_dim``,
    ``mamba_heads``, ``mamba_head_dim``, ``mamba_state``, ``mamba_groups``,
    ``mamba_conv``, ``mamba_chunk``, ``moe_ffn_dim``, ``shared_ffn_dim``,
    ``num_experts``, ``num_experts_per_tok``, ``num_local_experts`` /
    ``local_expert_offset``, ``routed_scaling_factor``): a Mamba-2 block
    keeps ``granite_hybrid``'s two rows (its B and C in groups), an
    attention block its two pools, an expert block nothing, so the cache is
    rows and pools in block order with gaps, and ``moe_load`` has a row an
    EXPERT block: the ``serving.moe.*`` counters and the held experts'
    slice read it as they read every other arch's. It refuses what
    ``granite_hybrid`` refuses.

    ``arch="dots3_note"`` serves latent attention with a query-side low rank
    in two geometries, chosen by ``layer_types`` (``"full_attention"`` |
    ``"sliding_attention"``; sizes ``q_lora_rank``, ``kv_lora_rank``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim`` and their
    ``swa_`` twins with ``swa_num_heads``, two rotary bases,
    ``sliding_window``, ``index_n_heads``, ``index_head_dim``,
    ``index_topk``, ``lora_rescale``, and ``deepseek_v3``'s experts with a
    held share). THREE kinds of cache side by side. A full layer keeps TWO
    pools on the ONE page table, written by the same slot: ``kv_c_<i>``, a
    token's [c | k_r], and ``kv_i_<i>``, its rotated index key; a step
    scores the index keys of a lane's own pages, takes the ``index_topk``
    best (``SparseIndexSelect``) and reads THOSE rows of the latent pool and
    no others (``KVPoolAttention(selected=True)``), absorbed. A window layer
    keeps ONE ring a lane, ``ring_c_<i>`` (1, ``sliding_window``, latent +
    rope): the row is key (whole) and value (its first columns), read
    absorbed (``KVRingAttention(value_dim=)``). And a full layer keeps a
    per-lane float32 row ``sparse_sel_<i>`` (``index_topk``,): the positions
    the lane's LAST token selected (-1 past a shorter context), written by
    the admission for the prompt's last real row and by every step, never
    read by the model: an operator-facing debug output that ``lane_state``
    shows (which tokens of a long context a lane's answer is reading). The
    admission's prefill is
    materialised; a full layer's selection is a mask over query blocks
    (``MultiHeadAttention(topk=)``; on the chip the blockwise kernel applies
    it, gauge ``serving.prefill_attention.sparse_kernel_layers``), a window
    layer's scores a band. Counters
    ``serving.sparse.*`` (docs/OBSERVABILITY.md). It refuses what
    ``mimo_v2_flash`` refuses.

    ``arch="laguna"`` serves window and full layers whose QUERY-head counts
    differ (``layer_types``, ``num_heads`` a full layer's and
    ``swa_num_heads`` a window layer's over the same ``num_kv_heads``,
    ``head_dim``, ``sliding_window``, ``rotary_dim`` and ``rope_theta`` with
    the ``yarn_*`` numbers and ``attention_factor`` for the full layers,
    ``swa_rope_theta``, and ``deepseek_v3``'s experts with a held share
    beside a shared one): a q/k norm a head, a gate a head on the context.
    The cache is ``mimo_v2_flash``'s, pools for a full layer and rings for a
    window layer, both of ``num_kv_heads`` heads; an ``admit`` counts what
    ONE window layer's prefill scored over the bucket and what of it a real
    position attends (``serving.admit_window_pairs_scored`` / ``_live``, for
    every arch with rings). It refuses what ``mimo_v2_flash`` refuses.

    ``arch="longcat_flash"`` serves layers of TWO latent attentions with a
    low-rank query, two dense MLPs and ONE shortcut-connected expert layer
    (``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``,
    ``qk_rope_head_dim``, ``v_head_dim``, ``ffn_dim`` a dense MLP's and
    ``moe_ffn_dim`` an expert's width, ``num_experts`` with weights and
    ``num_zero_experts`` more router outputs that are the identity,
    ``num_local_experts`` held from ``local_expert_offset``): the cache is
    ``deepseek_v3``'s latent pool, TWO a layer (``kv_c_<2l>``,
    ``kv_c_<2l + 1>``), all on the lane's one page table, an admission's rows
    scattered into each. ``serving.moe.zero_assignments`` and
    ``serving.moe.step_zero_assignments`` count the assignments that
    multiplied nothing, from the tail of the ``load`` the programs return. It
    refuses what ``deepseek_v3`` is refused.

    ``arch="ouro"`` serves a LOOPED stack: ``num_layers`` layers of sandwich
    norms, rotary attention and a gated MLP applied ``total_ut_steps`` times
    over the same weights (``early_exit_threshold``, ``head_dim``,
    ``rope_theta``, ``rms_eps``), the checkpoint one entry a LAYER. Every
    pass keeps keys and values of its own, so a token costs ``passes`` times
    a plain stack's cache, and the pool, not the weights, fills the chip. A
    layer's passes share ONE pool pair of ``passes x lanes x max_len`` slots,
    pass u a whole pass's frames behind pass u - 1: a lane's page table and
    its one allocation address all of them, ``stats()`` counts a page once,
    a retire returns every pass's slots at once, and the admission's scatter
    writes a prompt's rows of every pass in the same donated program. All
    passes run for every token; an exit gate read after each chooses which
    pass's output feeds the head, and the chosen pass rides the token's read
    (counters ``serving.loop.*``, docs/OBSERVABILITY.md). ``fork`` and
    ``rollback`` work (a page is copied or dropped for every pass); the
    prefix cache, the chunk and verify programs and the megastep raise.
    """

    def __init__(self, arg_params: Dict[str, object], vocab_size,
                 num_layers=2, num_heads=2, model_dim=32, ffn_dim=64,
                 max_len=64, page_size=8, lanes=4, page_budget=None,
                 prefill_len: Optional[int] = None,
                 pos_len: Optional[int] = None, prefix_cache=None,
                 prefix_chunk=None, ctx=None,
                 dtype="float32", cache_dir=None, model_key=None,
                 sample_seed=None, arch="vaswani", head_dim=None,
                 num_experts=None, num_experts_per_tok=None,
                 rope_theta=None, rms_eps=None, **arch_sizes):
        from ..models import transformer as _tf

        self.arch = arch  # an unknown one is refused by the graph builders
        self.vocab_size = int(vocab_size)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.model_dim = int(model_dim)
        self.ffn_dim = int(ffn_dim)
        self.max_len = int(max_len)
        self.lanes = int(lanes)
        self.prefill_len = int(prefill_len or max_len)
        # rows of the trained position table; None where the architecture
        # has none and max_len alone bounds a lane
        self.pos_len = int(pos_len or max_len) if arch == "vaswani" else None
        self.dh = int(head_dim or self.model_dim // self.num_heads)
        if self.prefill_len > self.max_len:
            raise MXNetError("paged_kv: prefill_len %d > max_len %d"
                             % (self.prefill_len, self.max_len))
        self.pool = _PagePool(self.lanes, self.max_len, page_size,
                              budget=page_budget)
        self.page_size = self.pool.page_size
        self.total_slots = self.lanes * self.max_len
        if prefix_cache is None:
            prefix_cache = os.environ.get(
                "MXNET_SERVE_PREFIX_CACHE", "").strip().lower() \
                in ("1", "on", "true", "yes")
        if prefix_cache:
            from .prefix_cache import PrefixCache

            self._refuse_arch("prefix_cache=True")
            if prefix_chunk is None:
                raw = os.environ.get("MXNET_SERVE_PREFIX_CHUNK",
                                     "").strip()
                prefix_chunk = int(raw) if raw else self.page_size
            self.prefix_chunk = int(prefix_chunk)
            self._prefix = PrefixCache(self.pool, self.prefix_chunk)
        else:
            self.prefix_chunk = None
            self._prefix = None
        self._prefix_hits = 0
        self._prefix_misses = 0
        cfg = dict(vocab_size=self.vocab_size, num_layers=self.num_layers,
                   num_heads=self.num_heads, model_dim=self.model_dim,
                   ffn_dim=int(ffn_dim), pos_len=self.pos_len)
        # NOTE the default key differs from the pre-global-pool layout on
        # purpose: the decode graph's KV shapes changed, and a stale
        # on-disk cache under the old key must not satisfy this one
        key = model_key or "transformer_paged_global_decode"
        binding = dict(ctx=ctx, dtype=dtype, cache_dir=cache_dir)
        if arch == "vaswani":
            if arch_sizes:
                raise TypeError("PagedKVDecoder: unexpected keywords %s"
                                % sorted(arch_sizes))
        else:
            given = dict(head_dim=head_dim, num_experts=num_experts,
                         num_experts_per_tok=num_experts_per_tok,
                         rope_theta=rope_theta, rms_eps=rms_eps, **arch_sizes)
            cfg.update(arch=arch, dtype=dtype, **{
                k: v for k, v in given.items() if v is not None})
            del cfg["pos_len"]
            if model_key is None:  # one architecture never answers for another
                key += "-" + arch
        # what the decode graph keeps between steps, in program order:
        # (name, "pool" | "row", shape) — pools of (heads, dh) addressed by
        # slot, per-lane rows (lanes,) + shape addressed by lane
        self._cache = _tf.decode_cache(**dict(cfg, arch=arch))
        # a looped stack keeps every pass's keys and values: a layer's ONE
        # pool pair holds ``passes`` times the slots, pass u a whole pass's
        # frames behind pass u - 1, so a lane's page stands for all of them
        self._passes = _tf.loop_passes(**dict(cfg, arch=arch))
        if self.total_slots * self._passes > 1 << 24:
            # a step's slots and frames reach the program as float32
            raise MXNetError("paged_kv: %d lanes x %d slots x %d passes is "
                             "past the 2^24 slots a float32 index counts "
                             "exactly" % (self.lanes, self.max_len,
                                          self._passes))
        self._cache_names = [name for name, _, _ in self._cache]
        self._pool_names = [name for name, kind, _ in self._cache
                            if kind == "pool"]
        self._has_rows = len(self._pool_names) < len(self._cache)
        # window layers: (rings, slots a ring); the experts a layer holds of
        # those it routes over (all of them unless the model names a share)
        self._ring_names = [name for name, kind, _ in self._cache
                            if kind == "ring"]
        self._window = max((shape[1] for _, kind, shape in self._cache
                            if kind == "ring"), default=0)
        # pairs one window layer's prefill scores: read off the program at
        # the first admission that counts them (telemetry on)
        self._window_scored = None
        # layers whose read is a learned selection keep what a lane's last
        # token selected (``sparse_sel_<i>``, a row of ``index_topk``
        # positions): how many, and how many positions each selects
        chosen = [shape[0] for name, _, shape in self._cache
                  if name.startswith("sparse_sel_")]
        self._sparse_layers, self._sparse_topk = len(chosen), max(
            chosen, default=0)
        first = int(arch_sizes.get("local_expert_offset") or 0)
        held = int(arch_sizes.get("num_local_experts") or 0)
        # zero-compute experts (ids past the experts with weights, the
        # identity) are routed over, held by nobody and counted apart
        experts = int(cfg.get("num_experts") or 0)
        zero = int(arch_sizes.get("num_zero_experts") or 0)
        self._zero_experts = slice(experts, experts + zero)
        end = first + held if held else experts if zero else None
        self._held_experts = slice(first, end)
        # the rows of a chunk where an admission's expert layers move their
        # HELD rows alone, by the operator's own rule; 0: every row
        from ..ops.moe import held_rows_chunk
        self._admit_chunk = held_rows_chunk(
            self.prefill_len, int(cfg.get("num_experts_per_tok") or 0),
            held, experts + zero)
        if arch != "vaswani":
            # inputs are float32 whatever the weights are, a lane's recurrent
            # state among them; only pools and rings take the weights' type
            binding.update(dtype="float32", input_dtypes={
                n: dtype for n in self._pool_names + self._ring_names})
        prefill = _tf.get_prefill_symbol(prefill_len=self.prefill_len, **cfg)
        decode = _step_in_symbol(
            _tf.get_decode_symbol(max_len=self.total_slots,
                                  page_size=self.page_size, **cfg),
            self.pool.frames_per_lane)
        self._pf_moe_load = _output_at(prefill, "moe_load")
        self._pf_exit_pass = _output_at(prefill, "exit_pass")
        self._dec_moe_load = _output_at(decode, "moe_load")
        self._dec_token = _output_at(decode, "greedy_token")
        self._pf_cache = PersistentExecutableCache(
            prefill, arg_params, {}, model_key=key + "-prefill",
            program_label="mx_prefill", **binding)
        self._dec_cache = PersistentExecutableCache(
            decode, arg_params, {}, model_key=key + "-decode",
            program_label="mx_decode", donated=self._cache_names, **binding)
        # readers of a pool past its first: layers that read what another
        # layer writes; and the rows of logits an admission's head computes,
        # read off the bound prefill at warmup: 1 while the graph narrows to
        # the prompt's last real row (where a pool is shared, the rows the
        # cross layers ran over too)
        readers = _pool_readers(decode)
        self._shared_readers = len(readers) - len(
            {id(pool) for _, pool in readers})
        self._head_rows = 0
        self._lengths = {}          # prompt length -> its (1, 1) device array
        self._dec_exe = None
        self._decode_xla_bytes = None  # read at warmup when telemetry is on
        self._step_gathered_slots = 0  # likewise: slots a dispatch scores
        self._kernel_block = 0         # and the slots of the kernel's block
        self._lanes: Dict[int, _Lane] = {}   # lane index -> _Lane
        # a step's ONE host-fed array, kept between steps (``step``): a row a
        # lane in ``_STEP_COLUMNS``' order, idle (token 0, position 0, write
        # slot -1, no page) unless the lane was stepped last; and the lanes
        # that were, by row
        self._idle_row = np.zeros((_TABLE_AT + self.pool.frames_per_lane,),
                                  np.float32)
        self._idle_row[_SLOT_AT] = -1
        self._step_in = np.tile(self._idle_row, (self.lanes, 1))
        self._stepped: Dict[int, _Lane] = {}
        self._seq_lane: Dict[int, int] = {}  # seq_id -> lane index
        self._next_seq = 0
        self._warm = False
        self._device = _DeviceRecord()  # in flight and last seen ready
        self._megasteps = {}        # (K, sampler) -> _DecodeMegastep
        self._chunks = {}           # T -> _ChunkProgram
        self._admit_scatter = None  # _AdmitScatter, built in warmup
        self._sample_seed = sample_seed
        self._sample_key = None

    @property
    def _last_return_t(self):
        """Where the last wait for the device ended: the start of the
        ``dispatch.host_gap`` and ``serving.device_gap`` intervals."""
        return self._device.ready_t

    def _refuse_arch(self, what):
        """The chunk, verify and megastep programs, and with them the
        prefix cache and speculation, know the Vaswani block only
        (ROADMAP D2)."""
        from ..models.transformer import _refuse_arch

        _refuse_arch(self.arch, "paged_kv: " + what)

    def _refuse_rows(self, what):
        """Sharing or dropping pages says nothing of what a lane keeps
        beside them, a recurrent state or a window's ring: it is one row,
        overwritten at every token, and going back needs a snapshot nobody
        keeps yet (ROADMAP R6)."""
        if self._has_rows:
            raise MXNetError(
                "paged_kv: %s is not built for arch %r yet: a recurrent "
                "state or a window's ring cannot be shared or rolled back "
                "without a snapshot" % (what, self.arch))

    # ------------------------------------------------------------ lifecycle
    def _decode_shapes(self):
        B, S = self.lanes, self.total_slots
        shapes = {"step_in": self._step_in.shape}
        for name, kind, shape in self._cache:
            # a pool in the layout its row's width gives it: page-major
            # (frames, page, heads * d) or head-major (heads, slots, d)
            shapes[name] = pool_shape(*shape, S * self._passes,
                                      self.page_size) \
                if kind == "pool" else (B,) + tuple(shape)
        return shapes

    def _prefill_shapes(self):
        """The prefill bucket's inputs: the padded prompt and its length (the
        head runs over the prompt's last real row alone; a recurrence reads
        its padding unless told where the prompt ends)."""
        return {"data": (1, self.prefill_len), "length": (1, 1)}

    def warmup(self, release_outputs=False):
        """Compile the multiplexed decode executable plus the admit-side
        programs — classically the batch-1 prefill bucket and the donated
        pool update (``_AdmitScatter``), the C-token chunk program when
        the prefix cache is on (chunked admit never touches the prefill
        bucket: cold and cached admits must replay the SAME program for
        the bitwise parity gate to hold).

        The decode program takes the cache donated, the warm dispatch too:
        its outputs are swapped back in here as a step's are, and the cache
        exists once. ``release_outputs=True`` drops what else the warm
        dispatch left (its logits): nothing of the cache."""
        if self._warm:
            return self
        self._dec_cache.warmup([self._decode_shapes()])
        self._dec_exe = exe = self._dec_cache.executable(self._decode_shapes())
        _swap_cache(exe, self._cache_names)
        if release_outputs:
            exe.release_outputs()
        self._warm = True
        if _tm.enabled():
            # the program as it is DISPATCHED: XLA's own byte count for one
            # decode dispatch, read once here so step() can add it to
            # serving.decode_xla_bytes for free, and the bytes of its
            # arguments it updates in place, beside the cache's
            program = exe.compiled()
            self._decode_xla_bytes = int(
                _cost_of(program)["bytes accessed"])
            _tm.gauge("serving.decode_aliased_bytes").set(
                int(program.memory_analysis().alias_size_in_bytes))
            _tm.gauge("serving.cache_bytes").set(sum(
                exe.arg_dict[name]._jax().nbytes
                for name in self._cache_names))
            reads = _pool_reads(self._dec_cache, self._decode_shapes())
            by_form = lambda form: [n for _, _, f, n in reads if f == form]
            _tm.gauge("serving.shared_pool_readers").set(
                self._shared_readers)
            for form in ("kernel", "own_pages", "whole_pool", "selected"):
                _tm.gauge("serving.pool_read.%s_layers" % form).set(
                    len(by_form(form)))
            # the step's writes, a node, by the form the operator's rule
            # names, and the rows they put into a pool each
            writes = _pool_writes(self._dec_cache, self._decode_shapes())
            for form in ("scatter", "loop"):
                _tm.gauge("serving.pool_write.%s_nodes" % form).set(
                    sum(f == form for f, _ in writes))
            _tm.gauge("serving.pool_write.rows_a_step").set(
                sum(rows for _, rows in writes))
            # a layer's: the mean over the program's reads of a kind, which
            # are alike. What XLA scores a dispatch; the kernel's block
            scored = by_form("own_pages") + by_form("whole_pool")
            self._step_gathered_slots = sum(scored) // max(len(scored), 1)
            self._kernel_block = max(by_form("kernel"), default=0)
            # the expert layers of each bound program by the form of their
            # grouped matmuls
            programs = {"decode": (self._dec_cache, self._decode_shapes())}
            if self._prefix is None:
                programs["prefill"] = (self._pf_cache, self._prefill_shapes())
            for program, bound in programs.items():
                forms, depth = _moe_forms(*bound)
                _tm.gauge("serving.moe.kernel_layers." + program).set(
                    forms.count("kernel"))
                _tm.gauge("serving.moe.xla_layers." + program).set(
                    forms.count("ragged_dot"))
                _tm.gauge("serving.moe.fetch_depth." + program).set(depth)
            if "prefill" in programs:
                # the prefill's attention layers by the form the rule names
                forms = [form for form, _, _ in _attention_sites(
                    *programs["prefill"])]
                for form in ("kernel", "dense", "band", "sparse",
                             "sparse_kernel", "window_kernel"):
                    _tm.gauge("serving.prefill_attention.%s_layers"
                              % form).set(forms.count(form))
            _tm.gauge("serving.state_bytes").set(sum(
                4 * self.lanes * int(np.prod(shape))
                for _, kind, shape in self._cache if kind == "row"))
            held = lambda names: sum(exe.arg_dict[name]._jax().nbytes
                                     for name in names)
            latent = [n for n in self._pool_names if n.startswith("kv_c_")]
            if latent:  # one pool a layer: a token's latent, not its heads
                _tm.gauge("serving.latent_pool_bytes").set(held(latent))
            index = [n for n in self._pool_names if n.startswith("kv_i_")]
            if index:   # beside it, on the same page table: its index keys
                _tm.gauge("serving.index_pool_bytes").set(held(index))
            if self._ring_names:  # two kinds of attention cache, side by side
                _tm.gauge("serving.full_pool_bytes").set(
                    held(self._pool_names))
                _tm.gauge("serving.window_ring_bytes").set(
                    held(self._ring_names))
        if self._prefix is None:
            self._pf_cache.warmup([self._prefill_shapes()])
            self._admit_scatter = _AdmitScatter(self)
            self._admit_scatter.warm(self)
            # the warm prefill's rows of logits
            self._head_rows = self._pf_cache.executable(
                self._prefill_shapes()).outputs[0].shape[0]
        else:
            self._chunk_for(self.prefix_chunk)
        return self

    def _stage_prefill(self, prompt):
        """The prefill executable with ``prompt`` (1, L) staged: the bucket,
        right-padded with zeros, in ONE transfer, and the length by
        reference: a host-to-device copy costs the host a quarter of a
        millisecond however small, so a length's (1, 1) array is put on the
        device once and kept (at most ``prefill_len + 1`` of four bytes)."""
        import jax

        pf = self._pf_cache.executable(self._prefill_shapes())
        L = prompt.shape[1]
        padded = np.zeros((1, self.prefill_len), np.float32)
        padded[:, :L] = prompt
        length = self._lengths.get(L)
        if length is None:
            length = self._lengths[L] = jax.device_put(
                np.full((1, 1), L, np.float32))
        pf.rebind(("data", "length"), (jax.device_put(padded), length))
        return pf

    def _count_passes(self, exits):
        """One dispatch of a looped stack, which headed ``len(exits)`` tokens
        each from the pass ``exits`` names (from 1): every pass ran."""
        _tm.counter("serving.loop.passes").inc(self._passes)
        _tm.counter("serving.loop.exit_tokens").inc(len(exits))
        _tm.counter("serving.loop.exit_pass_sum").inc(int(exits.sum()))

    def _prefill_cache(self, pf):
        """The prefill executable's cache outputs, in the cache's order."""
        return tuple(o._jax() for o in pf.outputs[1:1 + len(self._cache)])

    def stats(self):
        """Lanes and pages now. A page counts ONCE whatever it holds: where
        the stack is looped (``arch="ouro"``) it stands for the slots of
        every pass, ``passes`` pieces of each pool."""
        out = {"lanes": self.lanes,
               "active": len(self._lanes),
               "pages_in_use": self.pool.in_use,
               "page_budget": self.pool.budget,
               "page_size": self.page_size}
        if self._prefix is not None:
            out["prefix_cache"] = self._prefix.stats()
            tot = self._prefix_hits + self._prefix_misses
            out["prefix_hit_rate"] = \
                (self._prefix_hits / tot) if tot else 0.0
        return out

    # ------------------------------------------------------------ admission
    def _acquire_frame(self):
        """One page frame from the pool, evicting cached prefixes (LRU,
        leaf-first) to make room before giving up."""
        try:
            return self.pool.acquire()
        except PagedKVExhausted:
            if self._prefix is not None and self._prefix.evict_for(1):
                return self.pool.acquire()
            raise

    def _cow_page(self, lane: _Lane, page):
        """Copy-on-write: give ``lane`` a private copy of logical page
        ``page`` when some other holder (another lane, or the prefix
        index) still references its frame. Device-side slot-range copy in
        every layer's K/V buffer; the shared frame just loses one ref."""
        frame = lane.frames[page]
        if self.pool.refcount(frame) <= 1:
            return frame
        fresh = self._acquire_frame()
        P = self.page_size
        src = frame * P + np.arange(P)
        dst = fresh * P + np.arange(P)
        exe = self._dec_exe
        # a buffer at a time: its old copy may die before the next is made;
        # the page of EVERY pass a looped stack keeps there, a pass's frames
        # (or slots) apart
        for tag, kind, shape in self._cache:
            if kind != "pool":
                continue
            buf = exe.arg_dict[tag]._jax()
            for u in range(self._passes):
                by = u * self.pool.total_frames
                buf = buf.at[fresh + by].set(buf[frame + by]) \
                    if pool_paged(*shape) else buf.at[
                        :, dst + by * P, :].set(buf[:, src + by * P, :])
            exe.rebind([tag], [buf])
        self.pool.release([frame])
        lane.frames = lane.frames[:page] + (fresh,) + lane.frames[page + 1:]
        if _tm.enabled():
            self._device.enqueued("cow")
            _tm.counter("serving.cow_copies").inc()
        return fresh

    def _phys_slot(self, lane: _Lane, pos):
        """Physical slot of logical position ``pos`` FOR WRITING: acquires
        a new page frame when the position crosses into an unallocated
        page, and resolves copy-on-write when the page it lands in is
        still shared (the caller is about to write into it)."""
        if pos >= self.max_len:
            raise MXNetError(
                "paged_kv: position %d exceeds the per-sequence slot "
                "quota (max_len %d)" % (pos, self.max_len))
        page, off = divmod(pos, self.page_size)
        while len(lane.frames) <= page:
            lane.frames += (self._acquire_frame(),)
        frame = self._cow_page(lane, page)
        return frame * self.page_size + off

    def _page_table(self, lanes=()):
        """The decode graph's ``page_table`` (lanes, pages a lane) float32:
        row ``idx`` holds the frames of ``lane``'s pages in order for each
        ``(idx, lane)`` given, zeros past them and in every other row."""
        table = np.zeros((self.lanes, self.pool.frames_per_lane), np.float32)
        for idx, lane in lanes:
            table[idx, :len(lane.frames)] = lane.frames
        return table

    def _lane_slots(self, lane: _Lane, upto=None):
        """Physical slots of positions 0..n-1 (n = ``lane.pos`` unless
        ``upto`` given) — derived from the frame table, never stored:
        positions are always contiguous, so the slot list IS the page
        map."""
        n = lane.pos if upto is None else int(upto)
        if n <= 0:
            return np.zeros((0,), np.int64)
        P = self.page_size
        pages = np.asarray(lane.frames[:(n + P - 1) // P], np.int64)
        slots = pages[:, None] * P + np.arange(P, dtype=np.int64)[None, :]
        return slots.reshape(-1)[:n]

    def admit(self, prompt):
        """Admit one sequence. ``prompt`` is a (L,) or (1, L) token
        array, 0 < L <= prefill_len. Returns ``(seq_id, logits)`` with
        logits the (vocab,) distribution for the sequence's next token.
        Raises ``PagedKVExhausted`` when no lane or not enough page
        frames are free.

        Without the prefix cache a batch-1 prefill seeds the lane's
        pages (classic path). With it, admit is CHUNKED: the prompt's
        chunk-hash chain is matched against the prefix index, matched
        chunks are adopted at a refcount (zero recompute, zero copy) and
        only the unmatched tail runs through the C-token chunk program —
        cold and cached admits replay the same program over the same
        physical slots, so their logits are bitwise identical."""
        self.warmup()
        prompt = np.asarray(prompt, dtype=np.float32).reshape(1, -1)
        L = prompt.shape[1]
        if not 0 < L <= self.prefill_len:
            raise MXNetError("paged_kv: prompt length %d not in (0, %d]"
                             % (L, self.prefill_len))
        free_lanes = [i for i in range(self.lanes) if i not in self._lanes]
        if not free_lanes:
            raise PagedKVExhausted(
                "paged_kv: all %d lanes occupied; retire a sequence first"
                % self.lanes)
        idx = free_lanes[0]
        seq_id = self._next_seq
        self._next_seq += 1
        lane = _Lane(seq_id)
        self._lanes[idx] = lane
        self._seq_lane[seq_id] = idx
        try:
            if self._prefix is not None:
                logits = self._admit_chunked(prompt, lane)
            else:
                logits = self._admit_prefill(prompt, lane, idx)
        except BaseException:
            # ANY admit failure (pool exhaustion, a prefill/scatter
            # error) must release the lane and its frames — the caller
            # has no seq_id to retire, so a leak here would bleed the
            # pool dry one failed admit at a time
            self._evict(idx)
            raise
        lane.pos = L
        self._device.steady = False  # admit breaks the steady decode chain
        if _tm.enabled():
            _tm.counter("serving.paged_admits").inc()
            _tm.counter("serving.prefill_tokens").inc(L)
            _tm.gauge("serving.paged_pages_in_use").set(self.pool.in_use)
        return seq_id, logits

    def _admit_prefill(self, prompt, lane, idx):
        """Classic admit: one batch-1 prefill dispatch, then ONE donated
        program that writes the prompt's K/V into the lane's page frames
        and its recurrent state, where it has one, into the lane's row, in
        place (``_AdmitScatter``)."""
        L = prompt.shape[1]
        # a frame per page of the prompt, acquired before any device work
        for p in range(0, L, self.page_size):
            self._phys_slot(lane, p)
        # the record of the device, told of both enqueues and of the row
        record = self._device if _tm.enabled() else None
        with _tm.span("serving.paged_admit", seq=lane.seq_id,
                      prompt_len=L, lane=idx):
            with _tm.span("serving.admit.stage"):
                pf = self._stage_prefill(prompt)
            with _tm.span("serving.admit.prefill"):
                pf.forward(is_train=False)
                # the graph narrowed to the prompt's last real row itself:
                # (1, vocab) sets out for the host as it is, behind the
                # prefill alone, with no program between them
                row = pf.outputs[0]._jax()
                row.copy_to_host_async()
            if record is not None:
                record.enqueued("prefill")
            # the pool update stays on the device, and is enqueued BEFORE
            # the row is waited for: the device runs the prefill meanwhile
            with _tm.span("serving.admit.scatter"):
                self._admit_scatter.run(self, self._prefill_cache(pf),
                                        lane.frames, L, idx)
            if record is not None:
                record.enqueued("admit_scatter")
            with _tm.span("serving.admit.logits"):
                # the host blocked while the device runs what is left of
                # the prefill; then one row is read and indexed on the host
                with _tm.span("serving.admit.wait"):
                    row.block_until_ready()
                if record is not None:
                    record.ready("prefill")
                logits = np.asarray(row)[0]
        if record is not None:
            _tm.counter("serving.admit_head_rows").inc(self._head_rows)
            if self._sparse_layers:
                # causal (query, key) pairs of the prompt's real tokens the
                # indexers scored, a sparse layer
                _tm.counter("serving.sparse.admit_scored_pairs").inc(
                    self._sparse_layers * L * (L + 1) // 2)
            if self._window:
                # (query, key) pairs ONE window layer's prefill scored over
                # the bucket in the form it runs in (the kernel's blocks, a
                # band of two blocks a query, else all of them), and those
                # among them a real position attends
                w = self._window
                if self._window_scored is None:
                    self._window_scored = _window_pairs_scored(
                        self._pf_cache, self._prefill_shapes())
                _tm.counter("serving.admit_window_pairs_scored").inc(
                    self._window_scored)
                _tm.counter("serving.admit_window_pairs_live").inc(
                    min(L, w) * (min(L, w) + 1) // 2 + max(L - w, 0) * w)
            if self._shared_readers:
                # the bucket's rows either half of the depth computed
                _tm.counter("serving.admit_self_rows").inc(self.prefill_len)
                _tm.counter("serving.admit_cross_rows").inc(self._head_rows)
        if self._pf_exit_pass is not None and record is not None:
            # graphlint: waive GL701 -- the instrument's own read, telemetry on only
            self._count_passes(pf.outputs[self._pf_exit_pass].asnumpy())
        if self._pf_moe_load is not None and record is not None:
            # rows each expert received, per layer, over every position the
            # prefill computed (padding included: the grouped matmul's work)
            load = np.asarray(pf.outputs[self._pf_moe_load]._jax())
            _tm.counter("serving.moe.assignments").inc(int(load.sum()))
            _tm.counter("serving.moe.zero_assignments").inc(
                int(load[:, self._zero_experts].sum()))
            _tm.counter("serving.moe.max_expert_assignments").inc(
                int(load.max(axis=1).sum()))
            # of those, the ones that reached an expert held here: the rows
            # a layer that compacts them moves, and the layers whose held
            # rows outgrew one chunk (they ran a second turn, nothing lost)
            local = load[:, self._held_experts].sum(axis=1)
            _tm.counter("serving.moe.admit_local_assignments").inc(
                int(local.sum()))
            if self._admit_chunk:
                _tm.counter("serving.moe.admit_compact_layers").inc(
                    len(local))
                _tm.counter("serving.moe.admit_overflow_layers").inc(
                    int(np.count_nonzero(local > self._admit_chunk)))
        return logits

    def _chunk_for(self, t):
        """The sealed T-token chunk program, compiled on first use."""
        self._refuse_arch("the chunk program (prefix cache, verify_chunk, "
                          "speculation)")
        prog = self._chunks.get(t)
        if prog is None:
            prog = _ChunkProgram(self, t)
            prog.warm(self)
            self._chunks[t] = prog
        return prog

    def _run_chunk(self, lane: _Lane, tokens, base, write, prog=None):
        """Dispatch ``tokens`` (length <= T) of ``lane`` at positions
        ``base..base+len-1`` through the chunk program, writing K/V when
        ``write`` (rows past ``len`` are pad: zero write-onehot, fully
        masked — they soak up a uniform softmax and touch nothing).
        Returns host logits rows (len, vocab)."""
        prog = prog or self._chunk_for(self.prefix_chunk)
        T, S = prog.t, self.total_slots
        n = len(tokens)
        data = np.zeros((1, T), np.float32)
        pos_idx = np.zeros((1, T), np.float32)
        w_oh = np.zeros((T, S), np.float32)
        mask = np.full((T, S), _NEG, np.float32)
        data[0, :n] = tokens
        pos_idx[0, :n] = np.arange(base, base + n)
        if write:
            phys = [self._phys_slot(lane, base + j) for j in range(n)]
        else:
            phys = self._lane_slots(lane, base + n)[base:]
        seen = self._lane_slots(lane, base)
        for j in range(n):
            if write:
                w_oh[j, phys[j]] = 1.0
            mask[j, seen] = 0.0
            mask[j, phys[: j + 1]] = 0.0

        def enqueue():
            logits, new_kvs, _tok = prog.run(self, data, pos_idx, w_oh, mask)
            return (logits,), new_kvs

        (out,), new_kvs = _dispatch_and_pull(
            self, "serving.chunk_prefill", "chunk", "serving.chunk_prefill",
            enqueue, t=T, rows=n, write=bool(write))
        out = out[:n]
        if write:
            self._dec_exe.rebind(prog.kv_names, new_kvs)
        return out

    def _admit_chunked(self, prompt, lane):
        """Prefix-cache admit: match the prompt's chunk-hash chain,
        adopt matched pages at a refcount, chunk-prefill only the tail.
        A fully-matched prompt replays its last chunk with a ZERO
        write-onehot — ``kv*1 + new*0`` leaves every buffer bitwise
        untouched while producing the exact logits a cold admit did."""
        C = self.prefix_chunk
        toks = np.asarray(prompt, np.int64).reshape(-1)
        L = toks.shape[0]
        n_full = L // C
        hashes = self._prefix.chain_hashes(toks[:n_full * C])
        matched, frames = self._prefix.match(hashes)
        for f in frames:
            self.pool.incref(f)
        lane.frames = frames
        if _tm.enabled() and frames:
            _tm.counter("serving.pages_shared").inc(len(frames))
        if matched:
            self._prefix_hits += 1
            if _tm.enabled():
                _tm.counter("serving.prefix_hits").inc(matched)
                _tm.counter("serving.prefill_tokens_saved").inc(
                    matched * C)
        else:
            self._prefix_misses += 1
        if _tm.enabled():
            _tm.counter("serving.prefix_misses").inc(n_full - matched)
        logits = None
        with _tm.span("serving.paged_admit", seq=lane.seq_id,
                      prompt_len=L, cached_tokens=matched * C):
            for c in range(matched, n_full):
                base = c * C
                rows = self._run_chunk(lane, toks[base:base + C], base,
                                       write=True)
                logits = rows[-1]
                # whole chunks become cache currency the moment they
                # are computed — the index increfs the frames itself
                self._prefix.insert(
                    hashes[c],
                    lane.frames[base // self.page_size:
                                (base + C) // self.page_size],
                    parent=hashes[c - 1] if c else None)
            tail = L - n_full * C
            if tail:
                rows = self._run_chunk(lane, toks[L - tail:], L - tail,
                                       write=True)
                logits = rows[-1]
            elif logits is None:
                # full match: zero-write replay of the last chunk
                base = (n_full - 1) * C
                rows = self._run_chunk(lane, toks[base:base + C], base,
                                       write=False)
                logits = rows[-1]
        if _tm.enabled():
            tot = self._prefix_hits + self._prefix_misses
            _tm.gauge("serving.prefix_hit_rate").set(
                self._prefix_hits / tot if tot else 0.0)
        return logits

    def _evict(self, idx):
        lane = self._lanes.pop(idx)
        self._seq_lane.pop(lane.seq_id, None)
        self.pool.release(lane.frames)

    def retire(self, seq_id):
        """Free a finished sequence's lane and page frames (the slots are
        masked out for every other lane already; no zeroing needed)."""
        idx = self._seq_lane.get(seq_id)
        if idx is None:
            raise MXNetError("paged_kv: unknown seq_id %r" % (seq_id,))
        _tm.event("serving.retire", seq=seq_id, pos=self._lanes[idx].pos)
        self._evict(idx)
        if _tm.enabled():
            _tm.counter("serving.paged_retires").inc()
            _tm.gauge("serving.paged_pages_in_use").set(self.pool.in_use)

    @property
    def active(self):
        return sorted(self._seq_lane)

    def position(self, seq_id):
        return self._lanes[self._seq_lane[seq_id]].pos

    def lane_state(self, seq_id, names=None):
        """{name: array} of what the sequence's lane carries, by the cache's
        own names, as the last ``admit`` or ``step`` left it. Without
        ``names``: its row of every per-lane buffer. A recurrent model's
        state; a window layer's rings (heads, window, d), position p at slot
        p mod the window; empty where the cache is pools only. A POOL is
        named like the rest, whoever reads it (``phi4flash``'s one pair is
        eight layers'): ``names`` that hold one get the lane's own
        positions of it, (heads, position, d), gathered from its pages in
        order, whichever layout the pool is bound in; where the stack is
        looped, every pass's: (passes, heads, position, d)."""
        idx = self._seq_lane.get(seq_id)
        if idx is None:
            raise MXNetError("paged_kv: unknown seq_id %r" % (seq_id,))
        self.warmup()
        out = {}
        for name, kind, shape in self._cache:
            wanted = kind != "pool" if names is None else name in names
            if not wanted:
                continue
            buf = self._dec_exe.arg_dict[name]._jax()
            if kind != "pool":
                out[name] = buf[idx]
                continue
            slots = self._lane_slots(self._lanes[idx])
            if self._passes > 1:    # (passes, position): a pass's slots apart
                slots = slots + self.total_slots * np.arange(
                    self._passes)[:, None]
            if pool_paged(*shape):  # (..., position, heads, d) as gathered
                out[name] = buf.reshape((-1,) + tuple(shape))[slots].swapaxes(
                    -3, -2)
            else:
                own = buf[:, slots, :]
                out[name] = own.swapaxes(0, 1) if self._passes > 1 else own
        return out

    # ----------------------------------------------------- fork / rollback
    def fork(self, seq_id):
        """Clone a sequence into a free lane by SHARING every page frame
        at a refcount — zero copy, zero recompute (the parallel-sampling
        idiom). Either side's next write into a shared page triggers its
        private copy-on-write. Returns the clone's seq_id."""
        self._refuse_rows("fork")
        idx = self._seq_lane.get(seq_id)
        if idx is None:
            raise MXNetError("paged_kv: unknown seq_id %r" % (seq_id,))
        src = self._lanes[idx]
        free_lanes = [i for i in range(self.lanes) if i not in self._lanes]
        if not free_lanes:
            raise PagedKVExhausted(
                "paged_kv: all %d lanes occupied; retire a sequence first"
                % self.lanes)
        new_idx = free_lanes[0]
        new_id = self._next_seq
        self._next_seq += 1
        lane = _Lane(new_id)
        lane.pos = src.pos
        lane.frames = src.frames
        for f in lane.frames:
            self.pool.incref(f)
        self._lanes[new_idx] = lane
        self._seq_lane[new_id] = new_idx
        if _tm.enabled():
            _tm.counter("serving.pages_shared").inc(len(lane.frames))
            _tm.gauge("serving.paged_pages_in_use").set(self.pool.in_use)
        return new_id

    def rollback(self, seq_id, pos):
        """Truncate a sequence back to ``pos`` written positions: whole
        pages past the boundary are RELEASED (decref — a frame another
        holder shares just loses this lane's ref), the partial boundary
        page is kept with its stale tail slots simply excluded from the
        derived valid-slot set. No copy, no device work — this is the
        speculative-decoding reject primitive."""
        self._refuse_rows("rollback")
        idx = self._seq_lane.get(seq_id)
        if idx is None:
            raise MXNetError("paged_kv: unknown seq_id %r" % (seq_id,))
        lane = self._lanes[idx]
        pos = int(pos)
        if not 0 <= pos <= lane.pos:
            raise MXNetError(
                "paged_kv: rollback target %d outside [0, %d]"
                % (pos, lane.pos))
        keep = (pos + self.page_size - 1) // self.page_size
        dropped = lane.frames[keep:]
        lane.frames = lane.frames[:keep]
        self.pool.release(dropped)
        lane.pos = pos
        if _tm.enabled():
            _tm.counter("spec.rollbacks").inc()
            _tm.gauge("serving.paged_pages_in_use").set(self.pool.in_use)

    def verify_chunk(self, seq_id, tokens):
        """Score ``tokens`` (length T) at the sequence's next T positions
        in ONE rectangular dispatch, writing their K/V (row j attends to
        everything before it plus rows 0..j — exactly T successive
        ``step`` calls fused). Advances the position by T; the caller
        accepts a prefix and ``rollback``s the rest. Returns (T, vocab)
        logits. This is the speculative-decoding verify pass."""
        self._refuse_arch("verify_chunk")
        self.warmup()
        idx = self._seq_lane.get(seq_id)
        if idx is None:
            raise MXNetError("paged_kv: unknown seq_id %r" % (seq_id,))
        lane = self._lanes[idx]
        toks = np.asarray(tokens, np.int64).reshape(-1)
        t = toks.shape[0]
        if t < 1:
            raise MXNetError("verify_chunk: need at least one token")
        if lane.pos + t > self.pos_len:
            raise MXNetError(
                "paged_kv: seq %d verify positions %d..%d exceed the "
                "trained position table (%d rows)"
                % (seq_id, lane.pos, lane.pos + t - 1, self.pos_len))
        prog = self._chunk_for(t)
        rows = self._run_chunk(lane, toks, lane.pos, write=True,
                               prog=prog)
        lane.pos += t
        if _tm.enabled():
            _tm.gauge("serving.paged_pages_in_use").set(self.pool.in_use)
        return rows

    # --------------------------------------------------------------- decode
    def _patch_step_in(self, tokens, part):
        """``step``'s host half: ``tokens``' lanes written into the array the
        decoder keeps between steps (``_step_in``), under the stage's spans
        ``slots`` and ``table`` (``part``). Returns ``([(seq_id, lane index,
        lane)], table rows written)``. The array is written only here, and a
        step returns only after it has waited for the program that consumed
        the transfer (``_dispatch_and_pull``): ``jax.device_put`` may read a
        host array after it returns, and on the CPU may alias its memory, so
        the patch must come after that wait. Keep this order if the wait
        ever moves."""
        staged = self._step_in
        stepped, now, stale = [], {}, []
        try:
            with part("serving.step.stage.slots"):
                for seq_id, tok in tokens.items():
                    idx = self._seq_lane.get(seq_id)
                    if idx is None:
                        raise MXNetError("paged_kv: unknown seq_id %r"
                                         % (seq_id,))
                    lane = self._lanes[idx]
                    if self.pos_len is not None and lane.pos >= self.pos_len:
                        raise MXNetError(
                            "paged_kv: seq %d at position %d exceeds the "
                            "trained position table (%d rows)"
                            % (seq_id, lane.pos, self.pos_len))
                    # resolves the frame first: a new page, or a private copy
                    # of a shared one
                    slot = self._phys_slot(lane, lane.pos)
                    staged[idx, 0] = float(np.asarray(tok).reshape(()))
                    staged[idx, 1] = lane.pos
                    staged[idx, _SLOT_AT] = slot
                    if lane.stale:
                        stale.append((idx, lane))
                    now[idx] = lane
                    stepped.append((seq_id, idx, lane))
            with part("serving.step.stage.table"):
                # most steps nothing: a lane that crossed a page, was
                # admitted, copied or rolled back; one left out
                for idx, lane in stale:
                    upto = _TABLE_AT + len(lane.frames)
                    staged[idx, _TABLE_AT:upto] = lane.frames
                    staged[idx, upto:] = 0
                    lane.stale = False
                left = [idx for idx in self._stepped if idx not in now]
                for idx in left:
                    staged[idx] = self._idle_row
                    self._stepped[idx].stale = True
                self._stepped = now
        except BaseException:
            # a refused step leaves no half-written row behind
            staged[:] = self._idle_row
            for lane in (*self._stepped.values(), *now.values()):
                lane.stale = True
            self._stepped = {}
            raise
        return stepped, len(stale) + len(left)

    def step(self, tokens: Dict[int, object]):
        """One multiplexed decode dispatch: ``tokens`` maps seq_id -> next
        token id for any subset of active sequences; every stepped
        sequence advances at ITS OWN position in the one batch. Returns
        {seq_id: (vocab,) logits}: what crosses to the host in the step is
        the program's ``greedy_token``, one id a lane, which
        ``np.argmax(row)`` answers with; the ``(lanes, vocab)`` block
        follows when a row is read as an array, once for the step's rows
        (``_LogitsRow``). What the host hands the program is, a
        lane, its token, its position, the slot the token lands in and the
        frames of its pages, ONE array in ONE transfer (``step_in``:
        ``lanes * (3 + max_len / page_size)`` float32, the graph's ``data``,
        ``pos_idx``, ``write_slot`` and ``page_table`` side by side, cut
        apart inside the program); the masks over the pool's slots are made
        of them on the device, and the token's K/V goes into the page that
        holds its slot. The array LIVES between steps: a stepped lane's
        three numbers are written in place, its table row only when its
        page map changed since it was last written (``_Lane.frames``), and
        a lane stepped last time and not now gets the idle row back, so the
        array is at every step what building it from nothing would give.
        The program takes the cache DONATED: from its enqueue to the swap in
        ``serving.step.commit`` what ``arg_dict`` holds of it is dead. Lanes
        not stepped (or unoccupied) ride along with a negative write slot —
        their KV is untouched and their logits discarded."""
        import jax

        self.warmup()
        if not tokens:
            return {}
        # the stage's three parts hang on ONE mode read, not on one each
        part = _tm.span if _tm.tracing() else _no_span
        with _tm.span("serving.paged_step", rows=len(tokens), paged=True):
            exe = self._dec_exe
            with _tm.span("serving.step.stage"):
                stepped, rows_written = self._patch_step_in(tokens, part)
                # ONE transfer: a host-to-device copy is paid by the array
                # (0.24 ms the first, 0.17 each further), not by the byte
                with part("serving.step.stage.put"):
                    exe.rebind(("step_in",),
                               (jax.device_put(self._step_in),))

            def enqueue():
                exe.forward(is_train=False)
                # the logits stay where they are, in the one-element list
                # the step's rows share (``_LogitsRow``)
                return ((exe.outputs[self._dec_token]._jax(),),
                        [exe.outputs[0]._jax()])

            # graphlint: waive GL701 -- single-step tail of the megastep loop; the K-amortized body is the lax.scan in step_megastep
            (chosen,), block = _dispatch_and_pull(
                self, "serving.paged_step", "decode", "serving.decode_step",
                enqueue, rows=len(stepped), paged=True)
            out = {}
            with _tm.span("serving.step.commit"):
                _swap_cache(exe, self._cache_names)
                # a lane's row of the small read: its token and, where the
                # stack is looped, the pass that fed the token's head
                chosen = chosen.reshape(self.lanes, -1)
                for seq_id, idx, lane in stepped:
                    lane.pos += 1
                    out[seq_id] = _LogitsRow(block, idx, int(chosen[idx, 0]))
            if _tm.enabled():
                # the instrument's own work in a step, under its own name
                with _tm.span("serving.step.account"):
                    _tm.counter("serving.decode_tokens").inc(len(stepped))
                    # what the stepped lanes attended: position + 1 each
                    _tm.counter("serving.step_context_tokens").inc(
                        sum(lane.pos for _, _, lane in stepped))
                    _tm.counter("serving.paged_steps").inc()
                    _tm.counter("serving.step_gathered_slots").inc(
                        self._step_gathered_slots)
                    if self._kernel_block:
                        # what one layer's kernel fetches, each stepped
                        # lane's context rounded up to a page, and the loop
                        # turns it takes, a block each
                        _tm.counter("serving.step_kernel_slots").inc(sum(
                            -(-lane.pos // self.page_size) * self.page_size
                            for _, _, lane in stepped))
                        _tm.counter("serving.step_kernel_blocks").inc(sum(
                            -(-lane.pos // self._kernel_block)
                            for _, _, lane in stepped))
                    if self._window:
                        # what a window layer's read finds live, a layer
                        _tm.counter("serving.step_window_slots").inc(sum(
                            min(lane.pos, self._window)
                            for _, _, lane in stepped))
                    if self._sparse_layers:
                        # index keys the step scored, a lane's own context a
                        # sparse layer, and the rows its read then took
                        _tm.counter("serving.sparse.step_scored_slots").inc(
                            self._sparse_layers
                            * sum(lane.pos for _, _, lane in stepped))
                        _tm.counter("serving.sparse.step_selected_slots").inc(
                            self._sparse_layers * sum(
                                min(lane.pos, self._sparse_topk)
                                for _, _, lane in stepped))
                    _tm.counter("serving.step_slot_writes").inc(
                        len(stepped) * len(self._pool_names) * self._passes)
                    if self._passes > 1:
                        self._count_passes(
                            chosen[[idx for _, idx, _ in stepped], 1])
                    _tm.counter("serving.step_input_bytes").inc(
                        self._step_in.nbytes)
                    _tm.counter("serving.step_staged_arrays").inc()
                    _tm.counter("serving.step_table_rows_written").inc(
                        rows_written)
                    if self._decode_xla_bytes:
                        _tm.counter("serving.decode_xla_bytes").inc(
                            self._decode_xla_bytes)
                    if self._dec_moe_load is not None:
                        # rows each expert received from ALL the step's
                        # lanes (those that ride along pass through the
                        # experts too)
                        # graphlint: waive GL701 -- the instrument's own read, telemetry on only (serving.step.account)
                        load = exe.outputs[self._dec_moe_load].asnumpy()
                        _tm.counter("serving.moe.step_assignments").inc(
                            int(load.sum()))
                        _tm.counter(
                            "serving.moe.step_zero_assignments").inc(
                                int(load[:, self._zero_experts].sum()))
                        # of those, the ones that reached an expert held
                        # here, and how many of the held received any
                        local = load[:, self._held_experts]
                        _tm.counter(
                            "serving.moe.step_local_assignments").inc(
                                int(local.sum()))
                        _tm.counter("serving.moe.step_experts_touched").inc(
                            int(np.count_nonzero(local)))
                    _tm.gauge("decode.tokens_per_dispatch").set(len(stepped))
                    _tm.gauge("serving.paged_pages_in_use").set(
                        self.pool.in_use)
            return out

    def step_megastep(self, tokens: Dict[int, object], k=None, eos_id=None,
                      sample=None, temperature=None, top_k=None):
        """K multiplexed decode steps in ONE dispatch: every stepped
        sequence advances K positions at ITS OWN offsets through the
        ``lax.scan`` megastep, sampling on device (greedy argmax default,
        temperature/top-k via ``sample='topk'``). Page frames for ALL K
        positions are acquired UP FRONT, so pool exhaustion
        (``PagedKVExhausted``) surfaces BEFORE any device work — megastep
        backpressure is admission backpressure: already-acquired frames
        stay with their lanes (a retry after ``retire`` reuses them) and
        the KV state is untouched. Unstepped lanes ride along idle
        (a negative write slot); with ``eos_id`` a lane that emits eos
        mid-megastep writes nothing for its remaining steps and only its
        pre-eos slots become valid. Returns {seq_id: (K,) int64 ids}."""
        self._refuse_arch("step_megastep")
        self.warmup()
        k = int(k) if k is not None else decode_megastep_k()
        if k < 1:
            raise MXNetError("step_megastep: K must be >= 1, got %d" % k)
        if not tokens:
            return {}
        B = self.lanes
        stepped = []
        for seq_id, tok in tokens.items():
            idx = self._seq_lane.get(seq_id)
            if idx is None:
                raise MXNetError("paged_kv: unknown seq_id %r" % (seq_id,))
            lane = self._lanes[idx]
            if lane.pos + k > self.pos_len:
                raise MXNetError(
                    "paged_kv: seq %d megastep positions %d..%d exceed the "
                    "trained position table (%d rows)"
                    % (seq_id, lane.pos, lane.pos + k - 1, self.pos_len))
            stepped.append((seq_id, idx, lane, tok))
        phys = {}
        for seq_id, idx, lane, tok in stepped:
            phys[seq_id] = [self._phys_slot(lane, lane.pos + i)
                            for i in range(k)]
        ms = _megastep_for(self, k,
                           _sampler_from(sample, temperature, top_k))
        tok0 = np.zeros((B,), np.int32)
        posv = np.zeros((B,), np.int32)
        slots = np.zeros((B, k), np.int32)
        # frames of all K positions: a step of the scan reads pos + 1 slots
        table = self._page_table((idx, lane) for _, idx, lane, _ in stepped)
        done0 = np.ones((B,), bool)  # idle unless stepped
        for seq_id, idx, lane, tok in stepped:
            tok0[idx] = int(np.asarray(tok).reshape(()))
            posv[idx] = lane.pos
            slots[idx] = phys[seq_id]
            done0[idx] = False
        eos = np.int32(-1 if eos_id is None else int(eos_id))
        def enqueue():
            toks, acts, new_kvs, _done = ms.run(
                self, tok0, posv, slots, table, done0, eos)
            return (toks, acts), new_kvs

        # (K, B) ids and the active mask: the only host pull
        # graphlint: waive GL701 -- one round-trip a K tokens: the amortized shape the single-step tail is measured against
        (ids, acts_h), new_kvs = _dispatch_and_pull(
            self, "serving.paged_megastep", "megastep",
            "serving.decode_megastep", enqueue, rows=len(stepped), paged=True,
            k=k)
        self._dec_exe.rebind(ms.kv_names, new_kvs)
        out = {}
        written = 0
        for seq_id, idx, lane, tok in stepped:
            # active steps form a prefix (done latches): exactly the
            # steps whose KV write landed — only THOSE positions advance
            n_w = int(acts_h[:, idx].sum())
            lane.pos += n_w
            written += n_w
            out[seq_id] = ids[:, idx].astype(np.int64)
        if _tm.enabled():
            _tm.counter("serving.decode_tokens").inc(written)
            _tm.counter("serving.megasteps").inc()
            _tm.gauge("decode.tokens_per_dispatch").set(k * len(stepped))
            _tm.gauge("serving.paged_pages_in_use").set(self.pool.in_use)
        return out

    def greedy(self, prompts, n_tokens, k=None):
        """Greedy-decode ``n_tokens`` continuations for several prompts AT
        ONCE through the multiplexed batch (admitted together, stepped
        together). With ``k`` > 1 (default ``MXNET_DECODE_MEGASTEP_K``)
        the loop advances K tokens per dispatch via ``step_megastep``;
        K=1 reproduces the classic one-dispatch-per-token loop call for
        call. ``prompts`` is a list of (L_i,) token arrays (lengths may
        differ). Returns a list of (n_tokens,) int64 arrays. Convenience
        for tests/bench."""
        k = int(k) if k is not None else decode_megastep_k()
        seqs = []
        logits = {}
        try:
            for p in prompts:
                sid, lg = self.admit(p)
                seqs.append(sid)
                logits[sid] = lg
            out = {sid: np.zeros((n_tokens,), np.int64) for sid in seqs}
            nxt = {sid: int(np.argmax(logits[sid])) for sid in seqs}
            for sid in seqs:
                if n_tokens:
                    out[sid][0] = nxt[sid]
            t = 1
            while t < n_tokens:
                if k > 1 and n_tokens - t >= k:
                    # graphlint: waive GL702 -- K steps already folded into one lax.scan dispatch; the carried token is K-amortized
                    chunk = self.step_megastep(nxt, k=k)
                    for sid in seqs:
                        out[sid][t:t + k] = chunk[sid]
                        nxt[sid] = int(chunk[sid][-1])
                    t += k
                else:
                    # graphlint: waive GL702 -- sub-K tail: fewer than K tokens left, single-step program is already warm
                    lg = self.step(nxt)
                    # graphlint: waive GL703 -- np.argmax of a step's row answers with the program's greedy_token, the one id a lane the step pulled; no logits cross
                    nxt = {sid: int(np.argmax(lg[sid])) for sid in seqs}
                    for sid in seqs:
                        out[sid][t] = nxt[sid]
                    t += 1
            return [out[sid] for sid in seqs]
        finally:
            # retire on EVERY exit: a partial admit/step failure must not
            # strand the already-admitted lanes (the caller has no
            # seq_ids to clean up)
            for sid in seqs:
                if sid in self._seq_lane:
                    self.retire(sid)
