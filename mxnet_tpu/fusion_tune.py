"""Persistent measure-and-cache autotuning for the fusion pattern engine.

TVM's thesis (PAPERS.md) applied to the pattern fuser: instead of a
hand-curated, committed table of wins per kernel family, every (pattern,
shape, dtype) site is MEASURED against its unfused baseline on first
encounter — fused and baseline run as standalone jitted computations on
synthetic inputs, forward and backward — and the verdict (engage or not, winning lowering, measured µs,
backward policy) is persisted to a per-device-kind JSON cache so every later
run, in this process or any other, reuses it with zero re-tunes.

Cache layout: ``$MXNET_FUSION_TUNE_DIR/<device_kind>.json`` holding

    {"version": 2, "device_kind": ..., "digest": sha256(entries-json),
     "entries": {"<pattern>|<variant>|<sig>": {record}, ...}}

Schema v2 (this round) upgrades records from a binary engage/fallback
VERDICT to a measured SCHEDULE: candidate lowerings carry block-size/grid
variants (``name@k=v,...``), and the winning record stores the parsed
``schedule`` dict plus ``schedules_searched``. Version-1 files (PR 9's
binary verdicts) still LOAD — their records are valid verdicts for the
planner-default schedule, never re-tuned, never misread as a searched
winner (``schedule`` absent marks them). Files from an UNKNOWN (future)
version are invalidated with one warning, never a crash.

Writes are atomic (temp + ``os.replace``, the checkpoint.py discipline) and
merge-on-write, so concurrent processes tuning disjoint sites compose. A
corrupt or digest-mismatched file is IGNORED with a one-time warning —
never a crash, never a poisoned verdict; the next tune rewrites it whole.

Verdicts are device-generation-scoped by construction (one file per
``device_kind``): a cache tuned on v5e never gates a v4 run.

Gating env (docs/ENV_VARS.md):

- ``MXNET_FUSION_TUNE_DIR``  — cache directory; setting it ENABLES tuning.
- ``MXNET_FUSION_TUNE=0``    — kill-switch: never measure, never read.
- ``MXNET_FUSION_TUNE_ITERS``— timing iterations per measurement (default 10).
- ``MXNET_FUSION_TUNE_SCHEDULES`` — schedule-search width: how many
  block-size/grid variants each pattern may enumerate per candidate family
  beyond the planner-default (default 4); ``0`` restores the PR 9
  binary-verdict behavior (default candidate only).

Telemetry (docs/OBSERVABILITY.md): ``fusion.tune`` counts actual
measurements (a warm cache keeps this at zero), ``fusion.tune_cache_hit``
counts verdicts served from the cache, ``fusion.tune_error`` counts sites
and candidates discarded because they failed to build, compile or run.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import re
import threading
import time

from . import telemetry as _tm

__all__ = ["enabled", "cache_dir", "device_kind", "lookup", "peek",
           "verdict", "measure_candidates", "synth_like", "reset",
           "cache_path", "entries_digest", "schedule_budget",
           "parse_schedule", "sched_name"]

log = logging.getLogger("mxnet_tpu")

_VERSION = 2
#: prior schema whose entries remain readable: PR 9's binary verdicts are
#: valid records for the planner-default schedule (no ``schedule`` field)
_COMPAT_VERSIONS = (1,)

_lock = threading.Lock()
# device_kind -> {key: record}; None means "not loaded yet"
_mem = {}
_warned_paths = set()
_warned_errors = set()


# ------------------------------------------------------------------- gating
def cache_dir():
    """The configured cache directory (``MXNET_FUSION_TUNE_DIR``), or None
    when persistence/tuning is off (the default)."""
    d = os.environ.get("MXNET_FUSION_TUNE_DIR", "").strip()
    return d or None


def enabled():
    """Whether the autotuner may MEASURE: a cache dir is configured and the
    kill-switch (``MXNET_FUSION_TUNE=0``) is not set."""
    if os.environ.get("MXNET_FUSION_TUNE", "auto").strip() == "0":
        return False
    return cache_dir() is not None


def tune_iters():
    try:
        return max(1, int(os.environ.get("MXNET_FUSION_TUNE_ITERS", "10")))
    except ValueError:
        return 10


def schedule_budget():
    """How many block-size/grid-shape variants each pattern may enumerate
    per candidate family beyond the planner-default candidate
    (``MXNET_FUSION_TUNE_SCHEDULES``, default 4). ``0`` = binary-verdict
    mode: only the planner-default schedule is measured (the PR 9
    contract)."""
    try:
        return max(0, int(os.environ.get("MXNET_FUSION_TUNE_SCHEDULES",
                                         "4")))
    except ValueError:
        return 4


def sched_name(base, **kv):
    """The canonical schedule-variant candidate name: ``base@k=v,...``
    (sorted keys, so the name is deterministic and round-trips through
    ``parse_schedule``)."""
    return "%s@%s" % (base, ",".join(
        "%s=%d" % (k, v) for k, v in sorted(kv.items())))


def parse_schedule(name):
    """The schedule dict a candidate name encodes (``base@k=v,...``), or
    ``"default"`` for a bare (planner-default) candidate name, or None for
    no lowering at all."""
    if not name:
        return None
    _, sep, tail = str(name).partition("@")
    if not sep:
        return "default"
    out = {}
    for item in tail.split(","):
        k, _, v = item.partition("=")
        try:
            out[k] = int(v)
        except ValueError:
            out[k] = v
    return out


def device_kind():
    """The current device generation (the cache scope)."""
    import jax

    try:
        return jax.devices()[0].device_kind
    except Exception:
        return "unknown"


def cache_path(kind=None):
    d = cache_dir()
    if d is None:
        return None
    kind = kind if kind is not None else device_kind()
    safe = re.sub(r"[^A-Za-z0-9._-]+", "_", str(kind)) or "unknown"
    return os.path.join(d, safe + ".json")


def entries_digest(entries):
    """The integrity digest over the canonical entries JSON. A hand-edited
    (or torn) cache file fails this check and is ignored — measured verdicts
    are trusted precisely because nothing else can masquerade as one."""
    blob = json.dumps(entries, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


def reset():
    """Drop the in-process memo (tests). The on-disk cache is untouched."""
    with _lock:
        _mem.clear()
        _warned_paths.clear()
        _warned_errors.clear()


def _note_error(what, exc):
    """A site or candidate that failed to build, compile or run is discarded
    — but never silently: counted (``fusion.tune_error``) and its message
    logged once. On the chip this is where a kernel the compiler refuses
    shows up instead of quietly taking the XLA lowering."""
    msg = "%s: %s" % (type(exc).__name__, exc)
    if _tm.enabled():
        _tm.counter("fusion.tune_error").inc()
    if msg not in _warned_errors:
        _warned_errors.add(msg)
        log.warning("fusion_tune: %s failed and is discarded: %s", what, msg)
    return msg


# ------------------------------------------------------------------ storage
def _warn_once(path, msg):
    if path not in _warned_paths:
        _warned_paths.add(path)
        log.warning("fusion_tune: ignoring cache file %s: %s", path, msg)


def _load_file(path, kind):
    """Entries from one cache file, or {} when absent/corrupt/mismatched."""
    try:
        with open(path) as f:
            payload = json.load(f)
    except FileNotFoundError:
        return {}
    except (OSError, ValueError) as exc:
        _warn_once(path, "unreadable or not JSON (%s)" % exc)
        return {}
    version = payload.get("version") if isinstance(payload, dict) else None
    if version != _VERSION and version not in _COMPAT_VERSIONS:
        # a FUTURE (or garbage) schema: cleanly invalidate with one warning
        # — never a crash, and never a silently-misread winner
        _warn_once(path, "unknown schema version %r (this build reads "
                   "v%d and the compatible v%s)"
                   % (version if isinstance(payload, dict)
                      else type(payload).__name__, _VERSION,
                      "/v".join(str(v) for v in _COMPAT_VERSIONS)))
        return {}
    if payload.get("device_kind") != kind:
        _warn_once(path, "stamped for device_kind %r, this process runs %r"
                   % (payload.get("device_kind"), kind))
        return {}
    entries = payload.get("entries")
    if not isinstance(entries, dict):
        _warn_once(path, "entries missing or not a dict")
        return {}
    if payload.get("digest") != entries_digest(entries):
        _warn_once(path, "digest mismatch (torn write or hand edit)")
        return {}
    if version in _COMPAT_VERSIONS:
        # v1 (binary-verdict) records load as-is: engage/lowering/timings
        # keep their meaning, and the ABSENT ``schedule`` field marks them
        # as default-schedule verdicts — a warm run still does zero
        # re-tunes, and nothing misreports them as a searched winner
        log.info("fusion_tune: cache file %s is schema v%s (binary "
                 "verdicts); records load as default-schedule entries",
                 path, version)
    return entries


def _entries(kind):
    """The in-memory entry map for this device kind, loading the file once
    per process (warm-process verdicts never re-read the disk)."""
    ent = _mem.get(kind)
    if ent is None:
        path = cache_path(kind)
        ent = _load_file(path, kind) if path is not None else {}
        _mem[kind] = ent
    return ent


def _persist(kind, new_entries):
    """Merge ``new_entries`` into the on-disk file atomically. The
    read-merge-replace runs under an advisory flock on a sidecar lock file
    so concurrent PROCESSES tuning disjoint sites compose (without it, two
    simultaneous writers would each replace the other's fresh verdicts —
    a lost update the zero-retune contract cannot absorb); our fresh
    measurements win ties. In-process serialization comes from ``_lock``."""
    path = cache_path(kind)
    if path is None:
        return
    from .checkpoint import atomic_write_bytes

    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        lock_fd = None
        try:
            import fcntl

            lock_fd = os.open(path + ".lock", os.O_CREAT | os.O_RDWR, 0o644)
            fcntl.flock(lock_fd, fcntl.LOCK_EX)
        except (ImportError, OSError):
            pass  # best effort: no flock on this platform/filesystem
        try:
            merged = _load_file(path, kind)
            merged.update(new_entries)
            payload = {"version": _VERSION, "device_kind": kind,
                       "digest": entries_digest(merged), "entries": merged}
            atomic_write_bytes(path, json.dumps(
                payload, sort_keys=True, indent=1).encode())
            _mem[kind] = merged
        finally:
            if lock_fd is not None:
                os.close(lock_fd)  # closing releases the flock
    except OSError as exc:  # a read-only dir must not sink the step
        log.warning("fusion_tune: could not persist cache %s: %s", path, exc)


# ------------------------------------------------------------------ lookups
def peek(key):
    """The cached record for ``key`` (no telemetry, no measurement) — the
    explain path (``gate_pattern_explain``/GL303) reads rejected verdicts here."""
    if cache_dir() is None:
        return None
    kind = device_kind()
    with _lock:
        return _entries(kind).get(key)


def lookup(key):
    """The cached record for ``key``, counting ``fusion.tune_cache_hit``."""
    rec = peek(key)
    if rec is not None and _tm.enabled():
        _tm.counter("fusion.tune_cache_hit").inc()
    return rec


def verdict(key, measure):
    """The record for ``key``: cache hit, else (when tuning is enabled)
    measure NOW via ``measure()`` → record, persist, return. Returns None
    when no verdict exists and tuning is disabled.

    ``measure()`` returns the record dict (see ``measure_candidates``); a
    measurement failure is itself cached (``engage: False`` with the error)
    so a broken site costs one attempt per device kind, not one per trace.
    """
    rec = lookup(key)
    if rec is not None:
        return rec
    if not enabled():
        return None
    if _tm.enabled():
        _tm.counter("fusion.tune").inc()
    t0 = time.perf_counter()
    try:
        rec = measure()
    except Exception as exc:  # noqa: BLE001 — a tune failure must not sink a trace
        rec = {"engage": False, "lowering": None,
               "error": _note_error("site %s" % key, exc)}
    rec.setdefault("engage", False)
    rec["tune_s"] = round(time.perf_counter() - t0, 4)
    # schedule-search annotations (schema v2): the winner's parsed schedule
    # and how many schedule variants were actually timed at this site
    sched = parse_schedule(rec.get("lowering"))
    if sched is not None:
        rec["schedule"] = sched
    rec["schedules_searched"] = sum(
        1 for n in (rec.get("measured") or {}) if "@" in n)
    kind = device_kind()
    with _lock:
        _entries(kind)[key] = rec
        _persist(kind, {key: rec})
    return rec


# -------------------------------------------------------------- measurement
_ROUNDS = 3


def _prepare(fn, operands, iters):
    """A timed runner for ``iters`` executions of ``fn(*operands)`` inside
    one jitted scan (the scan amortizes dispatch, the scalar fetch is the
    device barrier). ``operands`` are jit
    ARGUMENTS, never closure constants — XLA would constant-fold (or
    loop-hoist) the entire measured computation otherwise. The scan carry
    feeds the first element of every output back into the next iteration's
    probe so the body is loop-VARIANT: invariant code motion cannot lift
    the measured computation out of the loop. Compiles + warms up now; each
    call returns one amortized wall time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def many(*ops):
        def probe_of(out):
            leaves = [l for l in jax.tree_util.tree_leaves(out)
                      if hasattr(l, "ravel") and l.size]
            return sum(l.ravel()[0].astype(jnp.float32) for l in leaves)

        def body(carry, _):
            # fold the carry into the first floating leaf (one scalar add —
            # noise next to the measured op) so every iteration's inputs
            # depend on the previous iteration's outputs
            jitter = carry * jnp.float32(1e-30)
            leaves, treedef = jax.tree_util.tree_flatten(ops)
            salted, out = False, []
            for l in leaves:
                if (not salted and hasattr(l, "dtype") and hasattr(l, "size")
                        and l.size
                        and jnp.issubdtype(l.dtype, jnp.floating)):
                    out.append(l + jitter.astype(l.dtype))
                    salted = True
                else:
                    out.append(l)
            return probe_of(fn(*jax.tree_util.tree_unflatten(treedef, out))), None

        out, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), None,
                              length=iters)
        return out

    np.asarray(many(*operands))  # compile + warmup

    def run():
        t0 = time.perf_counter()
        np.asarray(many(*operands))
        return (time.perf_counter() - t0) / iters

    return run


def synth_like(args, seed=0):
    """Concrete standard-normal arrays matching ``args``' shapes/dtypes.

    A gate invoked MID jit-trace holds TRACERS for the site's real inputs —
    those cannot be timed (and must not leak into the eager measurement),
    so the measurement runs on synthetic data of the same contract."""
    import numpy as np

    rs = np.random.RandomState(seed)
    return tuple(rs.randn(*[int(d) for d in a.shape]).astype(
        np.dtype(a.dtype)) for a in args)


def _rel_err(a, b):
    """Max relative error over corresponding pytree leaves (an output may
    be a tuple)."""
    import jax
    import jax.numpy as jnp

    worst = 0.0
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        a32 = la.astype(jnp.float32)
        b32 = lb.astype(jnp.float32)
        denom = float(jnp.max(jnp.abs(b32))) + 1e-9
        worst = max(worst, float(jnp.max(jnp.abs(a32 - b32))) / denom)
    return worst


def min_speedup():
    """The fused-vs-baseline margin a candidate must clear to engage
    (``MXNET_FUSION_TUNE_MIN_SPEEDUP``, default 1.05): a 5% guard band so
    timer noise cannot flip a neutral site into a phantom win."""
    try:
        return float(os.environ.get("MXNET_FUSION_TUNE_MIN_SPEEDUP", "1.05"))
    except ValueError:
        return 1.05


def measure_candidates(baseline, candidates, args, train=True, iters=None,
                       rel_tol=2e-2, margin=None):
    """Measure ``candidates`` ([(name, fn)]) against ``baseline`` on the
    concrete ``args``, forward and (``train``) backward, and return the
    verdict record.

    Every fn maps ``*args -> array`` (or pytree). The backward times the
    jax.vjp closure with ones-cotangents — residuals resident, exactly a
    training step's backward. All timers run in INTERLEAVED rounds
    (baseline, cand1, cand2, baseline, ...; min per fn) so host-speed drift
    hits every contestant equally. A candidate is eligible when its outputs
    AND grads stay within ``rel_tol`` of baseline; it wins when its fwd+bwd
    time beats baseline by the ``margin`` (default ``min_speedup()``).
    Record fields: ``engage``, ``lowering``, ``base_fwd_us``/
    ``fused_fwd_us``, ``base_bwd_us``/``fused_bwd_us``, ``engage_fwd`` (the
    inference gate: forward-only win), per-candidate ``measured`` rows.

    Runs in a FRESH THREAD: JAX trace state is thread-local, so a gate
    invoked MID jit-trace (the usual case — gates fire while the training
    step is being traced) still measures at top level, with real compiled
    executions; neither ``ensure_compile_time_eval`` (which cannot nest
    vjp-inside-jit) nor the ambient trace is involved.
    """
    box = {}

    def work():
        try:
            box["rec"] = _measure_impl(baseline, candidates, args, train,
                                       iters, rel_tol, margin)
        except BaseException as exc:  # noqa: BLE001 — re-raised on the caller thread
            box["exc"] = exc

    t = threading.Thread(target=work, name="fusion-tune-measure")
    t.start()
    t.join()
    if "exc" in box:
        raise box["exc"]
    return box["rec"]


def _measure_impl(baseline, candidates, args, train, iters, rel_tol,
                  margin):
    import jax
    import jax.numpy as jnp

    iters = iters if iters is not None else tune_iters()
    margin = margin if margin is not None else min_speedup()

    args = tuple(jnp.asarray(a) for a in args)

    def prepare(fn):
        """(fwd_runner, fwdbwd_runner_or_None) for one contestant. The
        backward is timed as a self-contained fwd+bwd program (vjp
        taken INSIDE the jitted runner over argument-passed operands —
        a pre-built vjp closure would ride in as foldable constants),
        so the reported bwd time is (fwd+bwd) − fwd."""
        runners = [_prepare(fn, args, iters)]
        if train:
            out = fn(*args)
            cts = jax.tree_util.tree_map(jnp.ones_like, out)

            def fwdbwd(*ops):
                a, c = ops[:-1], ops[-1]
                _, vjp_fn = jax.vjp(fn, *a)
                return vjp_fn(c)

            runners.append(_prepare(fwdbwd, args + (cts,), iters))
        else:
            runners.append(None)
        return runners

    def grads(fn):
        out, vjp_fn = jax.vjp(fn, *args)
        cts = jax.tree_util.tree_map(jnp.ones_like, out)
        return out, vjp_fn(cts)

    out_ref, g_ref = grads(baseline) if train else (baseline(*args), ())
    rec = {"engage": False, "engage_fwd": False, "lowering": None,
           "iters": iters, "train": bool(train), "measured": {}}
    table = [("__baseline__", prepare(baseline))]
    errs = {}
    for name, fn in candidates:
        try:
            runners = prepare(fn)
            if train:
                out, g = grads(fn)
                err = max([_rel_err(out, out_ref)]
                          + [_rel_err(a, b) for a, b in zip(g, g_ref)])
            else:
                err = _rel_err(fn(*args), out_ref)
            errs[name] = err
            table.append((name, runners))
        except Exception as exc:  # noqa: BLE001 — one bad candidate ≠ no verdict
            rec["measured"][name] = {
                "error": _note_error("candidate %s" % name, exc)}
    times = {name: [float("inf"), float("inf")] for name, _ in table}
    for _ in range(_ROUNDS):
        for name, runners in table:
            times[name][0] = min(times[name][0], runners[0]())
            if runners[1] is not None:
                times[name][1] = min(times[name][1], runners[1]())
    b_fwd, b_tot = times["__baseline__"]
    b_bwd = max(b_tot - b_fwd, 0.0) if train else 0.0
    rec["base_fwd_us"] = round(b_fwd * 1e6, 2)
    if train:
        rec["base_bwd_us"] = round(b_bwd * 1e6, 2)
    best = best_fwd = None
    for name, _ in table[1:]:
        f_fwd, f_tot = times[name]
        f_bwd = max(f_tot - f_fwd, 0.0) if train else 0.0
        err = errs[name]
        row = {"fwd_us": round(f_fwd * 1e6, 2),
               "rel_err": round(err, 6)}
        if train:
            row["bwd_us"] = round(f_bwd * 1e6, 2)
        if err <= rel_tol:
            total = f_tot if train else f_fwd
            base_total = b_tot if train else b_fwd
            if (base_total / total >= margin
                    and (best is None or total < best[0])):
                best = (total, name, f_fwd, f_bwd, err)
            if (b_fwd / f_fwd >= margin
                    and (best_fwd is None or f_fwd < best_fwd[0])):
                best_fwd = (f_fwd, name)
        else:
            row["rejected"] = "parity (rel_err %.2g > %.2g)" % (
                err, rel_tol)
        rec["measured"][name] = row
    if best is not None:
        _, name, f_fwd, f_bwd, err = best
        rec.update({"engage": True, "lowering": name,
                    "fused_fwd_us": round(f_fwd * 1e6, 2),
                    "rel_err": round(err, 6)})
        if train:
            rec["fused_bwd_us"] = round(f_bwd * 1e6, 2)
    if best_fwd is not None:
        rec["engage_fwd"] = True
        rec.setdefault("lowering_fwd", best_fwd[1])
    return rec
