"""Bucketed gradient comm for the dist KVStore (docs/PERF.md §11).

The reference KVStore's whole point at L5 was *overlap*: ``push(priority=)``
let layer N's gradient ride ps-lite while layer N-1's backward was still
running (kvstore_dist.h:275-313 sharded big arrays across servers by hand).
The first SPMD port dropped that — every push round re-concatenated every key
into a fresh flat buffer and ran one end-of-backward collective. This module
restores the overlap design TPU-natively:

* **Static bucket plan** — built ONCE from the first dist push round: keys
  are packed, in arrival (reverse-topo) order, into per-dtype buckets of
  ``MXNET_KVSTORE_BUCKET_MB`` (default 25 MB). Offsets are fixed forever, so
  the per-step variable-length ``jnp.concatenate`` + fresh ``device_put`` +
  retrace-prone shape wobble disappear: each bucket owns ONE compiled pack
  executable (concat+cast+pad fused by XLA) and ONE compiled collective.
* **Asynchronous flush** — a push writes its slot (functionally: the grad
  array is referenced, copy happens inside the compiled pack) and the bucket
  *flushes* — dispatches its collective via JAX async dispatch, non-blocking
  — the moment its last slot fills. Push order is reverse-topo (last layer
  first, ``kvstore_helper.update_params_on_kvstore``), so the deepest
  buckets' collectives are in flight while the host is still issuing the
  shallow layers' pushes; ``pull`` finalizes only its own key's bucket.
* **Sharded weight update** (``MXNET_KVSTORE_UPDATE=sharded``) — following
  "Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
  Training" (PAPERS.md): reduce-scatter + per-shard optimizer update +
  all-gather replaces all-reduce + W-way replicated optimizer math. The
  jitted flat updater (``optimizer.flat_update_spec``) runs on this worker's
  1/W shard INSIDE the same compiled program as both collectives, cutting
  replicated update FLOPs/bytes W-fold and fusing update into the comm
  executable. Wire bytes drop from 2(W-1)/W·N (all-reduce) to the same
  2(W-1)/W·N but the optimizer reads/writes N/W instead of N.
* **Wire compression** (``MXNET_KVSTORE_COMM_DTYPE=bf16``) — fp32 buckets
  cast to bf16 at the pack, halving comm-buffer bytes; the compiled
  collective upcasts to fp32 before accumulating (sum never runs in bf16).

Telemetry (docs/OBSERVABILITY.md): ``kvstore.bucket_flushes`` /
``kvstore.bucket_flush_bytes`` counters, per-transport byte counters
(``kvstore.bytes.allreduce|reduce_scatter|all_gather``), the
``kvstore.overlap_ratio`` gauge (fraction of the push→pull round a
dispatched collective was in flight while the host did other work) and
``kvstore.bucket_flush`` spans.
"""
from __future__ import annotations

import hashlib
import logging
import os
import time
from collections import namedtuple
from typing import Dict, List, Optional

import numpy as np

from .base import MXNetError
from . import telemetry as _tm
from .ndarray import NDArray

__all__ = ["BucketPlan", "BucketSpec", "Slot", "BucketEngine",
           "bucket_bytes", "update_mode", "comm_dtype_for",
           "verify_digest_across_workers"]

log = logging.getLogger("mxnet_tpu.kvstore")

DEFAULT_BUCKET_MB = 25.0
# cross-worker key-set/order verification runs for the first N push rounds
DEFAULT_CHECK_ROUNDS = 3

# one contiguous piece of one key inside one bucket. Keys larger than the
# bucket cap split into parts across consecutive buckets — the reference's
# big-array sharding across servers (kvstore_dist.h:275-313) made literal:
# each part's collective dispatches independently, so a huge key's comm
# pipelines instead of serializing through one giant transfer.
#   offset   — element offset inside the bucket's flat buffer
#   src_off  — element offset inside the key's own flat data
#   part/n_parts — this piece's index / the key's total piece count
Slot = namedtuple("Slot", ["key", "offset", "size", "shape", "dtype",
                           "src_off", "part", "n_parts"])


def bucket_bytes() -> int:
    """Bucket capacity in bytes from MXNET_KVSTORE_BUCKET_MB (docs/ENV_VARS.md)."""
    raw = os.environ.get("MXNET_KVSTORE_BUCKET_MB", "")
    try:
        mb = float(raw) if raw else DEFAULT_BUCKET_MB
        if mb <= 0:
            raise ValueError(mb)
    except ValueError:
        log.warning("MXNET_KVSTORE_BUCKET_MB=%r is not a positive number; "
                    "using %g", raw, DEFAULT_BUCKET_MB)
        mb = DEFAULT_BUCKET_MB
    return max(1, int(mb * 1e6))


def update_mode() -> str:
    """MXNET_KVSTORE_UPDATE=replicated|sharded (docs/ENV_VARS.md)."""
    raw = os.environ.get("MXNET_KVSTORE_UPDATE", "replicated").lower()
    if raw in ("replicated", "sharded"):
        return raw
    log.warning("MXNET_KVSTORE_UPDATE=%r unknown (replicated|sharded); "
                "using replicated", raw)
    return "replicated"


def comm_dtype_for(dtype) -> str:
    """Wire dtype for a bucket of ``dtype`` under MXNET_KVSTORE_COMM_DTYPE.
    Only fp32 buckets compress (bf16 wire, fp32 accumulate); everything else
    ships as-is."""
    raw = os.environ.get("MXNET_KVSTORE_COMM_DTYPE", "").lower()
    if raw in ("", "0", "none", "off"):
        return str(dtype)
    if raw in ("bf16", "bfloat16"):
        return "bfloat16" if str(dtype) == "float32" else str(dtype)
    log.warning("MXNET_KVSTORE_COMM_DTYPE=%r unknown (bf16); ignoring", raw)
    return str(dtype)


class BucketSpec:
    """One bucket: a fixed window of keys at fixed offsets in a flat comm
    buffer. ``total`` is padded to a multiple of ``n_workers`` so the sharded
    update's reduce-scatter splits evenly."""

    def __init__(self, index, dtype, comm_dtype, slots, n_workers, priority):
        self.index = index
        self.dtype = str(dtype)           # parameter/accumulate dtype
        self.comm_dtype = str(comm_dtype)  # wire/pack dtype
        self.slots = list(slots)
        self.priority = priority           # max key priority (dispatch order)
        used = self.slots[-1].offset + self.slots[-1].size if self.slots else 0
        self.total = -(-used // n_workers) * n_workers  # ceil to W multiple
        self.pad = self.total - used

    @property
    def keys(self):
        return [s.key for s in self.slots]

    def describe(self):
        return {"index": self.index, "dtype": self.dtype,
                "comm_dtype": self.comm_dtype, "total": self.total,
                "pad": self.pad, "priority": self.priority,
                "slots": [tuple(s) for s in self.slots]}


class BucketPlan:
    """Deterministic one-time packing of a push round's keys into buckets.

    Built from the FIRST dist push round's arrival sequence (which
    ``update_params_on_kvstore`` emits in reverse-topo order with
    ``priority=-index``), then frozen: every process derives the identical
    plan from the identical sequence — verified by the cross-worker hash
    check in the engine."""

    def __init__(self, buckets, bucket_cap, n_workers):
        self.buckets: List[BucketSpec] = buckets
        self.bucket_cap = bucket_cap
        self.n_workers = n_workers
        # key -> [(bucket, slot), ...] in part order (len > 1: split key)
        self.key_to_slots: Dict = {}
        for b in buckets:
            for s in b.slots:
                self.key_to_slots.setdefault(s.key, []).append((b, s))
        for parts in self.key_to_slots.values():
            parts.sort(key=lambda bs: bs[1].part)
        self.hash = hashlib.sha1(
            repr([(b.dtype, b.comm_dtype, b.total,
                   [tuple(s) for s in b.slots]) for b in buckets]).encode()
        ).hexdigest()

    @staticmethod
    def build(records, n_workers, bucket_cap=None) -> "BucketPlan":
        """``records``: [(key, shape, dtype_str, priority)] in arrival order.
        Keys pack greedily per dtype in arrival order; a bucket closes when
        the next key would overflow ``bucket_cap`` bytes. A key LARGER than
        the cap splits into cap-sized parts across consecutive buckets (see
        ``Slot``): measured on the 8-process CPU fabric, chunked collectives
        pipeline where one monolithic transfer falls off gloo's throughput
        cliff (docs/PERF.md §11), and on ICI the same chunking bounds each
        executable's comm-buffer footprint."""
        if bucket_cap is None:
            bucket_cap = bucket_bytes()
        by_dtype: Dict[str, list] = {}
        order: List[str] = []
        for key, shape, dtype, priority in records:
            dt = str(dtype)
            if dt not in by_dtype:
                by_dtype[dt] = []
                order.append(dt)
            by_dtype[dt].append((key, tuple(shape), priority))
        buckets = []
        for dt in order:
            comm_dt = comm_dtype_for(dt)
            itemsize = np.dtype(comm_dt).itemsize
            cap_elems = max(n_workers, bucket_cap // itemsize)
            cur, cur_elems, cur_prio = [], 0, None

            def close():
                nonlocal cur, cur_elems, cur_prio
                if cur:
                    buckets.append(BucketSpec(len(buckets), dt, comm_dt, cur,
                                              n_workers, cur_prio))
                    cur, cur_elems, cur_prio = [], 0, None

            for key, shape, priority in by_dtype[dt]:
                size = int(np.prod(shape)) if shape else 1
                n_parts = -(-size // cap_elems)
                if n_parts == 1:
                    if cur_elems + size > cap_elems:
                        close()
                    offset = cur[-1].offset + cur[-1].size if cur else 0
                    cur.append(Slot(key, offset, size, shape, dt, 0, 0, 1))
                    cur_elems += size
                else:
                    # oversize key: split into cap-sized parts, each opening
                    # a fresh bucket; the tail part's bucket stays open for
                    # the following keys
                    close()
                    for part in range(n_parts):
                        src_off = part * cap_elems
                        psize = min(cap_elems, size - src_off)
                        cur.append(Slot(key, 0, psize, shape, dt,
                                        src_off, part, n_parts))
                        cur_elems = psize
                        cur_prio = priority
                        if part != n_parts - 1:
                            close()
                cur_prio = priority if cur_prio is None else max(cur_prio,
                                                                 priority)
            close()
        return BucketPlan(buckets, bucket_cap, n_workers)

    def describe(self):
        return {"hash": self.hash, "bucket_cap": self.bucket_cap,
                "n_workers": self.n_workers,
                "buckets": [b.describe() for b in self.buckets]}

    def describe_portable(self):
        """JSON-round-trippable describe() (tuples -> lists) — the slot map
        a checkpoint manifest records so any-world loaders can re-flatten
        the shard set (mxnet_tpu.checkpoint.per_key_states)."""
        d = self.describe()
        for b in d["buckets"]:
            b["slots"] = [[s[0], s[1], s[2], list(s[3])] + list(s[4:])
                          for s in b["slots"]]
        return d


# --------------------------------------------------------------------- flat
# The jittable flat optimizer kernels moved to ``optimizer.FLAT_KERNELS``
# so the row-sparse lazy update (optimizer.update_row_sparse,
# docs/SPARSE.md) and this engine's fused sharded update share ONE
# expression tree — sharded, replicated and lazy-sparse land within
# reassociation drift of each other. Re-exported under the old name for
# existing imports/tests.
from .optimizer import FLAT_KERNELS as _FLAT_KERNELS  # noqa: E402


class _BucketState:
    """Runtime state of one bucket within the current push round."""

    def __init__(self, spec):
        self.spec = spec
        self.slots: Dict = {}        # key -> flat jax array (this round)
        self.result = None            # dispatched collective output(s)
        self.t_dispatch = None
        self.partial = False          # flushed with missing slots

    def reset(self):
        self.slots.clear()
        self.result = None
        self.t_dispatch = None
        self.partial = False


class BucketEngine:
    """Per-KVStore comm engine: records the first push round, commits the
    plan, then runs every later round through compiled per-bucket
    collectives with async flush + per-bucket finalize."""

    def __init__(self, kv):
        self._kv = kv
        self._collective = None
        self.plan: Optional[BucketPlan] = None
        self._recording: List = []    # (key, merged NDArray, priority)
        self._states: Dict[int, _BucketState] = {}
        self._packs: Dict[int, object] = {}      # bucket idx -> jitted pack
        self._sharded_step: Dict[int, object] = {}
        self._sharded_state: Dict[int, dict] = {}
        self._mode = update_mode()
        self._mode_reason = None
        self._plan_records = None     # committed plan's records (for replan)
        self._preloaded_shards = {}   # bucket idx -> [np local state shards]
        self._pending_parts: Dict = {}  # split-key segments awaiting assembly
        self._ticked = set()          # keys whose update count ticked (round)
        self._round_seq: List = []    # (key, shape, dtype) arrival this round
        self._round_t0 = None
        self._round_flushes = []      # (t_dispatch, t_finalize) closed windows
        self._rounds_done = 0
        self._check_rounds = self._env_check_rounds()
        self._legacy_warned = False

    @staticmethod
    def _env_check_rounds():
        raw = os.environ.get("MXNET_KVSTORE_CHECK_STEPS", "")
        try:
            return int(raw) if raw else DEFAULT_CHECK_ROUNDS
        except ValueError:
            log.warning("MXNET_KVSTORE_CHECK_STEPS=%r not an int; using %d",
                        raw, DEFAULT_CHECK_ROUNDS)
            return DEFAULT_CHECK_ROUNDS

    # ------------------------------------------------------------------ util
    def _coll(self):
        if self._collective is None:
            from .kvstore import _Collective

            self._collective = _Collective.get()
        return self._collective

    @property
    def mode(self) -> str:
        """Effective update mode AFTER capability resolution ('sharded' only
        when the optimizer has a flat lowering and the store updates)."""
        return self._resolve_mode()

    def _resolve_mode(self):
        if self._mode != "sharded":
            return "replicated"
        if self._mode_reason is not None:
            return "replicated"
        opt = getattr(self._kv, "_optimizer", None)
        upd = getattr(self._kv, "_updater", None)
        if upd is None or opt is None:
            self._mode_reason = ("no kvstore optimizer (update_on_kvstore "
                                 "is off) — sharded update needs the "
                                 "updater to run inside the collective")
        elif opt.flat_update_spec() is None:
            self._mode_reason = ("optimizer %s has no flat_update_spec()"
                                 % type(opt).__name__)
        else:
            # per-key lr/wd mults DO work: they fold into the lr/wd segment
            # vectors gathered inside the compiled program
            return "sharded"
        log.warning("MXNET_KVSTORE_UPDATE=sharded unavailable: %s; "
                    "falling back to replicated", self._mode_reason)
        return "replicated"

    # ------------------------------------------------------------------ push
    def push(self, keys, merged_list, priority):
        """One push call's keys (already locally reduced), in order."""
        now = time.perf_counter()
        if self._round_t0 is None:
            self._round_t0 = now
        if self._rounds_done <= self._check_rounds:
            # consumed only inside the verify window — not worth per-step
            # host allocations for the rest of the job
            for k, m in zip(keys, merged_list):
                self._round_seq.append((k, tuple(m.shape), str(m.dtype)))
        if self.plan is None:
            recorded = {r[0] for r in self._recording}
            if not any(k in recorded for k in keys):
                for k, m in zip(keys, merged_list):
                    # snapshot the (immutable) jax buffer NOW: the caller may
                    # legally overwrite its NDArray between push and the
                    # plan-committing pull, and recording defers the read
                    self._recording.append(
                        (k, NDArray(m._jax(), ctx=m.context), priority))
                return
            # a key repeated before any pull: the round ended without a
            # read — commit what we have and continue bucketed below
            self._commit_plan()
        self._push_bucketed(keys, merged_list, priority)

    def _push_bucketed(self, keys, merged_list, priority):
        legacy_k, legacy_m = [], []
        for k, m in zip(keys, merged_list):
            parts = self.plan.key_to_slots.get(k)
            if parts is None:
                legacy_k.append(k)
                legacy_m.append(m)
                continue
            flat = None
            # a new push of this key opens a new round FOR THIS KEY: its
            # update count must tick again even if the previous round never
            # fully closed (subset pulls leave buckets in flight)
            self._ticked.discard(k)
            for bucket, slot in parts:
                st = self._states[bucket.index]
                sid = (k, slot.part)
                if sid in st.slots or st.result is not None:
                    # round restart for this bucket: drain it first — a
                    # not-yet-dispatched bucket must flush (partial) so the
                    # earlier push's gradient reduces+applies rather than
                    # being silently overwritten (reference: one updater
                    # application per push)
                    if st.result is None:
                        self._flush(st)
                    self._finalize(st)
                if flat is None:
                    flat = m._jax().reshape(-1)
                st.slots[sid] = (flat if slot.n_parts == 1 else
                                 flat[slot.src_off:slot.src_off + slot.size])
                if len(st.slots) == len(bucket.slots):
                    self._flush(st)
        if legacy_k:
            self._legacy_round(legacy_k, legacy_m)

    def before_read(self, keys):
        """Pull-side sync: commit the plan if still recording, then finalize
        ONLY the buckets the requested keys live in (plus flush any of their
        partially-filled buckets) — other buckets' collectives stay in
        flight."""
        if self.plan is None and self._recording:
            self._commit_plan()
        if self.plan is None:
            return
        touched = []
        for k in keys:
            for b, _slot in self.plan.key_to_slots.get(k, ()):
                if b.index not in touched:
                    touched.append(b.index)
        # deterministic flush order for not-yet-dispatched partial buckets:
        # priority desc, then plan order — identical on every worker
        pending = [self._states[i] for i in touched]
        for st in sorted((s for s in pending if s.result is None and s.slots),
                         key=lambda s: (-s.spec.priority, s.spec.index)):
            self._flush(st)
        for i in touched:
            self._finalize(self._states[i])
        if not any(s.result is not None or s.slots
                   for s in self._states.values()):
            self._close_round()

    def finalize_all(self):
        """Drain every in-flight/partial bucket (barrier, checkpoint...)."""
        if self.plan is None:
            if self._recording:
                self._commit_plan()
            else:
                return
        for st in sorted((s for s in self._states.values()
                          if s.result is None and s.slots),
                         key=lambda s: (-s.spec.priority, s.spec.index)):
            self._flush(st)
        for st in self._states.values():
            self._finalize(st)
        self._close_round()

    # ---------------------------------------------------------- resume/reform
    def preload_flat_shards(self, shards):
        """Seed the NEXT flat-state build from checkpoint shards: ``shards``
        maps bucket index -> [np local 1/W state slices] (this worker's).
        The live sharded state (if any) is dropped so the next flush
        rebuilds from the preload — the same-W shard-direct resume path of
        mxnet_tpu.checkpoint (momentum bit-parity: the exact bytes the
        checkpoint captured device_put straight back)."""
        self._preloaded_shards = dict(shards)
        self._sharded_state.clear()
        self._sharded_step.clear()
        # a load clears any prior capability veto: the caller proved the
        # optimizer/world alignment by matching the manifest digest
        if self._mode_reason and "partial push round" not in self._mode_reason:
            self._mode_reason = None

    def reseed_updater_states(self):
        """Drop flat sharded state so the next flush re-seeds from the
        per-key Updater states (the different-W / re-flattened resume path;
        also used after load_optimizer_states mid-run)."""
        self._preloaded_shards.clear()
        self._sharded_state.clear()
        self._sharded_step.clear()

    def reform(self, records=None):
        """Rebuild this engine for the CURRENT world (after an elastic
        re-form changed the process set, docs/FAULT_TOLERANCE.md): drop
        every compiled executable, collective handle and in-flight bucket,
        then re-plan the committed key sequence for the new worker count.
        The cross-worker plan-digest allgather re-verifies agreement, and
        the first-N round checks re-arm — a re-formed job gets the same
        validation a fresh one does."""
        records = records if records is not None else self._plan_records
        self._collective = None     # _Collective.get() re-keys on the backend
        self._states = {}
        self._packs = {}
        self._sharded_step = {}
        self._sharded_state = {}
        self._preloaded_shards = {}
        self._pending_parts = {}
        self._ticked = set()
        self._round_seq = []
        self._round_t0 = None
        self._round_flushes = []
        self.rearm_verify()
        self._mode = update_mode()
        self._mode_reason = None
        self.plan = None
        self._recording = []
        if records is not None:
            self._plan_records = list(records)
            self.plan = BucketPlan.build(records, self._coll().n_workers)
            self._states = {b.index: _BucketState(b)
                            for b in self.plan.buckets}
            log.info("KVStore bucket plan re-formed: %d keys -> %d "
                     "bucket(s) over %d worker(s), hash %s",
                     len(records), len(self.plan.buckets),
                     self._coll().n_workers, self.plan.hash[:12])
            self._verify_across_workers("plan:" + self.plan.hash)

    # ------------------------------------------------------------------ plan
    def _commit_plan(self):
        records = [(k, tuple(m.shape), str(m.dtype), p)
                   for k, m, p in self._recording]
        self._plan_records = records
        self.plan = BucketPlan.build(records, self._coll().n_workers)
        self._states = {b.index: _BucketState(b) for b in self.plan.buckets}
        log.info("KVStore bucket plan: %d keys -> %d bucket(s), cap %.1f MB, "
                 "update=%s, hash %s",
                 len(records), len(self.plan.buckets),
                 self.plan.bucket_cap / 1e6, self.mode, self.plan.hash[:12])
        self._verify_across_workers("plan:" + self.plan.hash)
        # a committed plan changes every subsequent round's wire layout:
        # re-open the first-N digest window over the new plan
        self.rearm_verify()
        # replay the recorded round through the fresh buckets (bypassing
        # push(): the round sequence already logged these keys)
        recorded, self._recording = self._recording, []
        for k, m, p in recorded:
            self._push_bucketed([k], [m], p)

    # ----------------------------------------------------------------- flush
    def _pack(self, st):
        """Compiled concat+cast+pad for one bucket (traced once: slot count,
        shapes, dtypes are all static)."""
        import jax
        import jax.numpy as jnp

        spec = st.spec
        fn = self._packs.get(spec.index)
        if fn is None:
            comm_dt = jnp.dtype(spec.comm_dtype)
            pad = spec.pad
            if (len(spec.slots) == 1 and not pad
                    and spec.comm_dtype == spec.slots[0].dtype):
                # single whole-bucket key, nothing to cast or pad: the row is
                # a metadata-only reshape, no executable needed
                fn = lambda f: f.reshape(1, -1)  # noqa: E731
            else:
                def pack(*flats):
                    parts = [f.astype(comm_dt) for f in flats]
                    if pad:
                        parts.append(jnp.zeros((pad,), comm_dt))
                    out = (jnp.concatenate(parts) if len(parts) > 1
                           else parts[0])
                    return out.reshape(1, -1)

                fn = jax.jit(pack)
            self._packs[spec.index] = fn
        flats = []
        for s in spec.slots:
            got = st.slots.get((s.key, s.part))
            if got is None:
                got = jnp.zeros((s.size,), jnp.dtype(s.dtype))
                st.partial = True
            flats.append(got)
        return fn(*flats)

    def _flush(self, st):
        """Dispatch this bucket's collective — non-blocking (JAX async
        dispatch): the call returns as soon as the executable is enqueued,
        and the host goes back to issuing the remaining pushes."""
        spec = st.spec
        coll = self._coll()
        wire = int(2 * (coll.n_workers - 1) / coll.n_workers * spec.total
                   * np.dtype(spec.comm_dtype).itemsize)
        row = self._pack(st)  # sets st.partial; span attrs must see it
        if self.mode == "sharded" and st.partial:
            # a missing slot means that key was not pushed this round; the
            # fused update would still apply wd/momentum to it — semantics
            # the replicated path does not have. Downgrade the ENGINE to
            # replicated FOR GOOD (a split key's state spans buckets, so a
            # per-bucket downgrade could leave a key half-sharded), seeding
            # the per-key updater states from the flat shards so momentum
            # history survives. Deterministic: 'partial' is SPMD-symmetric,
            # every worker downgrades together.
            self._downgrade_sharded()
        mode = self.mode
        sp = _tm.NULL_SPAN
        if _tm.enabled():
            _tm.counter("kvstore.bucket_flushes").inc()
            _tm.counter("kvstore.bucket_flush_bytes").inc(wire)
            sp = _tm.span("kvstore.bucket_flush", bucket=spec.index,
                          nkeys=len(spec.slots), bytes=wire,
                          priority=spec.priority, mode=mode,
                          comm_dtype=spec.comm_dtype,
                          partial=st.partial)
        with sp:
            if mode == "sharded":
                st.result = ("sharded", self._dispatch_sharded(st, row))
                if _tm.enabled():
                    _tm.counter("kvstore.bytes.reduce_scatter").inc(wire // 2)
                    _tm.counter("kvstore.bytes.all_gather").inc(wire // 2)
            else:
                st.result = ("replicated", coll.allreduce_rows(
                    row, acc_dtype=spec.dtype))
                if _tm.enabled():
                    _tm.counter("kvstore.bytes.allreduce").inc(wire)
        st.t_dispatch = time.perf_counter()

    def _gather_per_key_states(self):
        """All-gather every bucket's 1/W flat state shards and stitch them
        into per-key HOST arrays: ``(n_states, {key: [np, ...]})``. Split
        keys stitch their per-bucket segments; parts whose bucket never
        dispatched shardedly contribute zeros (the state a fresh Updater
        would lazily create). The all-gather is a COLLECTIVE — every
        current member must call this together. Read-only: the live
        sharded state is untouched."""
        if not self._sharded_state:
            return 0, {}
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        coll = self._coll()
        gather = jax.jit(lambda x: x,
                         out_shardings=NamedSharding(coll.mesh, P()))
        n_states = 0
        pending: Dict = {}  # key -> {part: [np state segments]}
        for spec in (s.spec for s in self._states.values()):
            sstate = self._sharded_state.get(spec.index)
            if sstate is None or not sstate["states"]:
                continue
            n_states = len(sstate["states"])
            full = [np.asarray(gather(s).addressable_data(0))
                    for s in sstate["states"]]
            for s in spec.slots:
                pending.setdefault(s.key, {})[s.part] = [
                    fs[s.offset:s.offset + s.size] for fs in full]
        if not n_states:
            return 0, {}
        out = {}
        for key, parts in pending.items():
            slots = [sl for _, sl in self.plan.key_to_slots[key]]
            segs = []
            for sl in slots:  # zeros for parts whose bucket never dispatched
                segs.append(parts.get(sl.part,
                                      [np.zeros((sl.size,),
                                                np.dtype(sl.dtype))
                                       for _ in range(n_states)]))
            shape = slots[0].shape
            out[key] = [np.concatenate([p[i] for p in segs]).reshape(shape)
                        if len(segs) > 1 else segs[0][i].reshape(shape)
                        for i in range(n_states)]
        return n_states, out

    def export_per_key_states(self):
        """Per-key optimizer states from the live flat shards, on host —
        the pause-time snapshot elastic recovery seeds from when no
        complete checkpoint exists (the all-gather path,
        docs/FAULT_TOLERANCE.md). Collective: requires the full CURRENT
        membership still participating (i.e. a DRAINING departure, not a
        crash). Finalizes in-flight buckets first. ``{}`` when the engine
        holds no flat state (replicated mode)."""
        self.finalize_all()
        _, states = self._gather_per_key_states()
        return states

    def _downgrade_sharded(self):
        """Move the WHOLE engine from the fused sharded update back to
        replicated, without losing optimizer history: drain any in-flight
        sharded buckets, all-gather every bucket's 1/W flat state shards,
        and seed the per-key Updater states the replicated path reads from
        now on."""
        if self._mode_reason is not None:
            return
        self._mode_reason = ("partial push round — bucket keys were not all "
                             "pushed; replicated from here on")
        # in-flight sharded results still need their sstate to finalize
        for st in self._states.values():
            if st.result is not None and st.result[0] == "sharded":
                self._finalize(st)
        if not self._sharded_state:
            return
        import jax.numpy as jnp

        log.warning(
            "KVStore: partial push round under MXNET_KVSTORE_UPDATE=sharded "
            "— downgrading to the replicated update (per-key optimizer "
            "states seeded from the flat shards; momentum history preserved)")
        n_states, per_key = self._gather_per_key_states()
        self._sharded_state.clear()
        self._sharded_step.clear()
        if not n_states:
            return
        upd = self._kv._updater
        for key, arrs in per_key.items():
            ctx = self._kv._store[key].context
            nds = [NDArray(jnp.asarray(a), ctx=ctx) for a in arrs]
            upd.states[key] = nds[0] if n_states == 1 else tuple(nds)

    # -------------------------------------------------------------- finalize
    def _finalize(self, st):
        if st.result is None:
            return
        kind, payload = st.result
        t_fin = time.perf_counter()
        self._round_flushes.append((st.t_dispatch, t_fin))
        spec = st.spec
        if kind == "sharded":
            w_full = payload[0]
            loc = w_full.addressable_data(0)
            sstate = self._sharded_state[spec.index]
            sstate["w_full"] = w_full
            sstate["states"] = payload[1:]
            for s in spec.slots:
                if s.offset == 0 and s.size == spec.total:
                    seg = loc
                else:
                    seg = loc[s.offset:s.offset + s.size]
                self._deliver(s, seg, is_weight=True)
        else:
            loc = payload.addressable_data(0)
            import jax.numpy as jnp

            dt = jnp.dtype(spec.dtype)
            for s in spec.slots:
                if (s.key, s.part) not in st.slots:
                    continue  # not pushed this round (partial flush)
                if s.offset == 0 and s.size == spec.total:
                    seg = loc  # whole-bucket slot: no slice dispatch
                else:
                    seg = loc[s.offset:s.offset + s.size]
                if seg.dtype != dt:
                    seg = seg.astype(dt)
                self._deliver(s, seg, is_weight=False)
        st.reset()

    def _deliver(self, slot, seg, is_weight):
        """Land one finalized slot. Whole keys apply immediately; a split
        key waits until every part's bucket finalized, then assembles."""
        kv = self._kv
        if slot.n_parts > 1:
            parts = self._pending_parts.setdefault(slot.key, {})
            parts[slot.part] = seg
            if len(parts) < slot.n_parts:
                return
            import jax.numpy as jnp

            seg = jnp.concatenate([parts[p] for p in range(slot.n_parts)])
            del self._pending_parts[slot.key]
        value = NDArray(seg.reshape(slot.shape),
                        ctx=kv._store[slot.key].context)
        if is_weight or kv._updater is None:
            kv._store[slot.key] = value
        else:
            kv._updater(slot.key, value, kv._store[slot.key])

    def _close_round(self):
        """End-of-round bookkeeping: overlap telemetry + first-N verify."""
        if self._round_t0 is None:
            return
        if self._round_flushes and _tm.enabled():
            t_end = max(f[1] for f in self._round_flushes)
            span = t_end - self._round_t0
            inflight = sum(f[1] - f[0] for f in self._round_flushes)
            ratio = min(1.0, inflight / span) if span > 0 else 0.0
            _tm.gauge("kvstore.overlap_ratio").set(round(ratio, 4))
            _tm.timer("kvstore.comm_inflight").add(inflight)
        seq, self._round_seq = self._round_seq, []
        self._round_t0 = None
        self._round_flushes = []
        self._ticked.clear()
        self._rounds_done += 1
        if self._rounds_done <= self._check_rounds:
            self._verify_across_workers(repr(seq))

    # ------------------------------------------------------------ validation
    def rearm_verify(self):
        """Re-open the first-N digest window: the next
        MXNET_KVSTORE_CHECK_STEPS rounds allgather-verify the key sequence
        again. Called after anything that can desynchronize the workers'
        push streams — an elastic ``reform``, a bucket re-plan — so a
        divergence the change introduced fails loudly instead of
        deadlocking inside a later collective."""
        self._rounds_done = 0

    def _verify_across_workers(self, payload: str):
        """Cheap cross-worker agreement check: allgather a 4-byte digest of
        this round's key sequence (or the plan hash) and compare. Catches
        mismatched key sets/orders that would otherwise deadlock or silently
        misreduce inside the collective. Gated to the first
        MXNET_KVSTORE_CHECK_STEPS rounds — steady state costs nothing."""
        verify_digest_across_workers(payload, self._check_rounds,
                                     self._allgather_digest)

    @staticmethod
    def _allgather_digest(arr):
        from jax.experimental.multihost_utils import process_allgather

        return np.asarray(process_allgather(arr)).reshape(-1)

    # ---------------------------------------------------------------- legacy
    def _legacy_round(self, keys, merged_list):
        """Keys outside the committed plan (pushed for the first time after
        round 1): immediate batched collective, the pre-bucket path."""
        kv = self._kv
        if not self._legacy_warned:
            log.info("KVStore: %d key(s) outside the bucket plan (first seen "
                     "after the planning round) ride the unbucketed "
                     "collective: %s", len(keys), keys[:4])
            self._legacy_warned = True
        reduced = kv._allreduce_batch(merged_list)
        for k, merged in zip(keys, reduced):
            if kv._updater is not None:
                kv._updater(k, merged, kv._store[k])
            else:
                kv._store[k] = merged

    # --------------------------------------------------------------- sharded
    def _dispatch_sharded(self, st, row):
        """Fused reduce-scatter + 1/W-shard optimizer update + all-gather,
        ONE compiled program per bucket."""
        spec = st.spec
        step = self._sharded_step.get(spec.index)
        if step is None:
            step = self._build_sharded(spec)
            self._sharded_step[spec.index] = step
        sstate = self._sharded_state[spec.index]
        lr_seg, wd_seg = self._lr_wd_segments(spec)
        coll = self._coll()
        g_rows = coll.make_global_rows(row)
        return step["fn"](g_rows, sstate["w_full"], *sstate["states"],
                          lr_seg, wd_seg, sstate["idx"])

    def _lr_wd_segments(self, spec):
        """Per-unique-(lr,wd) segment values for this flush. The bucket's
        static uint8 index map gathers them to per-element vectors inside
        the compiled program; only these tiny arrays cross host->device per
        step, and the host also ticks the per-key update counts so lr
        schedules stay bit-identical with the replicated path."""
        opt = self._kv._optimizer
        kind, hyper, _ = opt.flat_update_spec()
        per_key = []
        for s in spec.slots:
            if s.key not in self._ticked:
                # once per key per ROUND (a split key's other parts flush
                # from other buckets and must see the same count)
                opt._update_count(s.key)
                self._ticked.add(s.key)
            lr, wd = opt._get_lr(s.key), opt._get_wd(s.key)
            if kind == "adam":
                # keyed on the SPEC kind, not the class name: Adam
                # subclasses inheriting the adam flat kernel need the same
                # host-side bias-correction fold Adam.update applies
                import math

                t = opt._index_update_count[s.key]
                lr *= (math.sqrt(1.0 - hyper["beta2"] ** t)
                       / (1.0 - hyper["beta1"] ** t))
            per_key.append((lr, wd))
        uniq = {}
        for lw in per_key:
            uniq.setdefault(lw, len(uniq))
        lr_seg = np.zeros((len(uniq),), np.float32)
        wd_seg = np.zeros((len(uniq),), np.float32)
        for (lr, wd), i in uniq.items():
            lr_seg[i], wd_seg[i] = lr, wd
        sstate = self._sharded_state[spec.index]
        ordinals = tuple(uniq[lw] for lw in per_key)
        if sstate.get("idx_ordinals") != ordinals:
            sstate["idx"] = self._build_idx(spec, ordinals)
            sstate["idx_ordinals"] = ordinals
        return lr_seg, wd_seg

    def _build_idx(self, spec, ordinals):
        """Static per-element key-segment map, sharded over workers (uint8:
        ≤256 distinct (lr,wd) segments per bucket — 1/4 the footprint of a
        per-element fp32 lr vector)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if len(set(ordinals)) > 256:
            raise MXNetError("bucket %d has >256 distinct (lr,wd) segments"
                             % spec.index)
        coll = self._coll()
        idx = np.zeros((spec.total,), np.uint8)
        for s, o in zip(spec.slots, ordinals):
            idx[s.offset:s.offset + s.size] = o
        shard = spec.total // coll.n_workers
        r = coll.rank
        local = jax.device_put(idx[r * shard:(r + 1) * shard],
                               coll.my_device)
        return jax.make_array_from_single_device_arrays(
            (spec.total,), NamedSharding(coll.mesh, P("worker")), [local])

    def _build_sharded(self, spec):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P


        coll = self._coll()
        opt = self._kv._optimizer
        kind, hyper, n_states = opt.flat_update_spec()
        kernel = _FLAT_KERNELS[kind](hyper)
        mesh = coll.mesh
        W = coll.n_workers
        shard = spec.total // W
        acc_dt = jnp.dtype(spec.dtype)

        def body(g_rows, w_full, *rest):
            states = rest[:n_states]
            lr_seg, wd_seg, idx = rest[n_states:]
            g = g_rows.reshape(-1).astype(acc_dt)
            g = jax.lax.psum_scatter(g, "worker", scatter_dimension=0,
                                     tiled=True)
            r = jax.lax.axis_index("worker")
            w = jax.lax.dynamic_slice(w_full, (r * shard,), (shard,))
            lr = lr_seg[idx]
            wd = wd_seg[idx]
            w_new, new_states = kernel(w, g, states, lr, wd)
            w_gathered = jax.lax.all_gather(w_new, "worker", tiled=True)
            return (w_gathered,) + tuple(new_states)

        in_specs = ((P("worker", None), P(None))
                    + (P("worker"),) * n_states
                    + (P(None), P(None), P("worker")))
        out_specs = (P(None),) + (P("worker"),) * n_states
        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                   out_specs=out_specs, check_vma=False))
        # persistent flat weight (replicated) + optimizer state (sharded).
        # States seed, in priority order, from (1) a preloaded checkpoint
        # shard (same-W shard-direct resume, mxnet_tpu.checkpoint — this
        # worker's 1/W slice device_puts straight in, bit-parity by
        # construction), (2) the per-key Updater states when present (a
        # resume via load_optimizer_states must not silently restart
        # momentum at zero), else (3) zeros — what a fresh Updater would
        # lazily create.
        preloaded = self._preloaded_shards.pop(spec.index, None)
        states = []
        for i in range(n_states):
            if preloaded is not None:
                loc = np.asarray(preloaded[i]).reshape(-1)
                if loc.shape[0] != shard:
                    raise MXNetError(
                        "preloaded checkpoint shard for bucket %d has %d "
                        "elements, expected %d — plan/world mismatch "
                        "(the manifest digest guard should have caught this)"
                        % (spec.index, loc.shape[0], shard))
                host_local = loc
            else:
                host = np.zeros((spec.total,), spec.dtype)
                for s in spec.slots:
                    loaded = self._kv._updater.states.get(s.key)
                    if loaded is None:
                        continue
                    if n_states > 1 and not isinstance(loaded, (tuple, list)):
                        continue  # foreign-optimizer state layout: start fresh
                    part = loaded if n_states == 1 else loaded[i]
                    flat_part = np.asarray(part._jax()).reshape(-1)
                    host[s.offset:s.offset + s.size] = \
                        flat_part[s.src_off:s.src_off + s.size]
                host_local = host[coll.rank * shard:(coll.rank + 1) * shard]
            s_local = jax.device_put(
                jnp.asarray(host_local, dtype=acc_dt), coll.my_device)
            states.append(jax.make_array_from_single_device_arrays(
                (spec.total,), NamedSharding(mesh, P("worker")), [s_local]))
        self._sharded_state[spec.index] = {
            "w_full": self._weights_from_store(spec),
            "states": tuple(states)}
        return {"fn": fn, "n_states": n_states}

    def _weights_from_store(self, spec):
        """Assemble the bucket's persistent flat weight buffer (replicated
        global array) from the current store values."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        coll = self._coll()
        kv = self._kv
        w_parts = [np.asarray(kv._store[s.key]._jax()).reshape(-1)
                   [s.src_off:s.src_off + s.size].astype(spec.dtype)
                   for s in spec.slots]
        if spec.pad:
            w_parts.append(np.zeros((spec.pad,), spec.dtype))
        w_host = np.concatenate(w_parts) if len(w_parts) > 1 else w_parts[0]
        w_local = jax.device_put(jnp.asarray(w_host), coll.my_device)
        return jax.make_array_from_single_device_arrays(
            (spec.total,), NamedSharding(coll.mesh, P()), [w_local])


def verify_digest_across_workers(payload: str, check_rounds: int,
                                 allgather) -> None:
    """Allgather a 4-byte sha1 of ``payload`` and require every rank to
    agree — the shared core of the BucketEngine round/plan checks and the
    monolithic ``KVStore._verify_push_round`` window."""
    import jax

    if jax.process_count() == 1:
        return
    # uint32: jax's 32-bit default would silently truncate a wider
    # digest inside the allgather and fail the compare on matching keys
    digest = hashlib.sha1(payload.encode()).digest()[:4]
    mine = np.frombuffer(digest, dtype=np.uint32)
    theirs = allgather(mine)
    if not (theirs == mine[0]).all():
        bad = {int(r): hex(int(v)) for r, v in enumerate(theirs)}
        raise MXNetError(
            "dist KVStore workers disagree on the pushed key "
            "set/order this round (digest by rank: %s). Every worker "
            "must push the same keys in the same order — check for "
            "rank-dependent branches around kv.push. (Verified for the "
            "first %d rounds; set MXNET_KVSTORE_CHECK_STEPS to tune.)"
            % (bad, check_rounds))
