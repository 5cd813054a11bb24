"""Multi-process runtime bootstrap.

Counterpart of the reference's cluster env plumbing (`KVStore::InitPSEnv`,
include/mxnet/kvstore.h:158-164, consuming DMLC_ROLE/DMLC_PS_ROOT_URI/... set
by tools/launch.py). The ps-lite scheduler/server roles are gone — in the
SPMD design every process runs the same program — so the only bootstrap
needed is the JAX coordination service: ``tools/launch.py`` sets the three
``MXNET_TPU_*`` env vars below and ``init()`` wires them into
``jax.distributed.initialize``, after which ``jax.process_index()`` /
``jax.process_count()`` back KVStore ``rank``/``num_workers`` and XLA
collectives ride ICI/DCN across all hosts.
"""
from __future__ import annotations

import logging
import os

__all__ = ["init", "is_initialized", "rank", "num_workers", "shutdown",
           "num_dead_nodes", "elastic_enabled", "members", "generation",
           "orig_rank", "dead_members", "dead_timeout_seconds",
           "plan_reform", "plan_from_pause",
           "reform", "coordination_client", "propose_pause", "poll_pause",
           "stop_heartbeat", "is_heartbeating"]

# env contract with tools/launch.py (the DMLC_* vars of the reference)
ENV_COORDINATOR = "MXNET_TPU_COORDINATOR"  # host:port of process 0
ENV_NUM_WORKERS = "MXNET_TPU_NUM_WORKERS"
ENV_WORKER_ID = "MXNET_TPU_WORKER_ID"
# failure detection (reference: ps-lite heartbeats scanned by
# kvstore_dist.h:158-167 behind KVStore::get_num_dead_node,
# include/mxnet/kvstore.h:234-244): each worker touches
# $MXNET_TPU_HEARTBEAT_DIR/worker-<rank> on a timer; the launcher (and
# num_dead_nodes below) treat a stale file as a dead/hung worker
ENV_HEARTBEAT_DIR = "MXNET_TPU_HEARTBEAT_DIR"
ENV_HEARTBEAT_INTERVAL = "MXNET_TPU_HEARTBEAT_INTERVAL"
# elastic membership (docs/FAULT_TOLERANCE.md): worker death becomes a
# survivable event instead of a job-killing one
ENV_ELASTIC = "MXNET_ELASTIC"
ENV_REFORM_TIMEOUT = "MXNET_ELASTIC_REFORM_TIMEOUT"
ENV_MIN_WORKERS = "MXNET_ELASTIC_MIN_WORKERS"
ENV_DEAD_TIMEOUT = "MXNET_ELASTIC_DEAD_TIMEOUT"
ENV_PAUSE_MARGIN = "MXNET_ELASTIC_PAUSE_MARGIN"

_initialized = False
_heartbeat_thread = None
_heartbeat_stop = None  # threading.Event; set by stop_heartbeat()
_start_time = None  # job-start anchor for num_dead_nodes' startup grace
# ---- elastic state (meaningful only under MXNET_ELASTIC=1) ----
_elastic = False      # this job runs the survivable coordination layer
_generation = 0       # bumped by every successful reform()
_members = None       # ORIGINAL ranks of the current generation, sorted
_orig_rank = None     # this process's launcher rank (stable across reforms)
_orig_world = None    # the launch-time worker count


def _job_start_time():
    """When this job started, as far as this process can tell: pinned at
    ``init()`` (workers) or lazily at the first liveness query (monitors).
    Anchors the startup grace below."""
    global _start_time
    if _start_time is None:
        import time

        _start_time = time.time()
    return _start_time


def is_initialized() -> bool:
    return _initialized


def elastic_enabled() -> bool:
    """MXNET_ELASTIC=1 (docs/FAULT_TOLERANCE.md): run the survivable
    coordination layer — worker death pauses and re-forms the job instead of
    killing it. Death propagation through the JAX coordination service is
    disabled (its heartbeat tolerance is set effectively infinite) and
    failure detection moves to the launcher's heartbeat files, exactly the
    reference's ps-lite node-heartbeat semantics."""
    return os.environ.get(ENV_ELASTIC, "").lower() in ("1", "on", "true",
                                                       "yes")


def init(coordinator_address=None, num_processes=None, process_id=None):
    """Connect this process to the job's coordination service.

    Arguments default to the ``MXNET_TPU_*`` env vars; no-op when neither is
    present (single-process job) or when already initialized. Safe to call
    multiple times. Under ``MXNET_ELASTIC=1`` the coordination client is
    built directly (not via ``jax.distributed.initialize``) so its
    missed-heartbeat tolerance can be made effectively infinite — a dead
    peer must NOT abort the survivors; they detect it themselves
    (``num_dead_nodes``) and re-form (``reform``)."""
    global _initialized
    if _initialized:
        return
    coordinator_address = coordinator_address or os.environ.get(ENV_COORDINATOR)
    if coordinator_address is None:
        return  # single-process
    num_processes = int(num_processes if num_processes is not None
                        else os.environ.get(ENV_NUM_WORKERS, "1"))
    process_id = int(process_id if process_id is not None
                     else os.environ.get(ENV_WORKER_ID, "0"))
    import jax

    try:
        if elastic_enabled():
            _init_elastic(coordinator_address, num_processes, process_id)
        else:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
            )
    except RuntimeError as e:
        from .base import MXNetError

        raise MXNetError(
            "mxnet_tpu.dist.init() must run before any JAX computation. "
            "Create the dist kvstore (mx.kv.create('dist_tpu_sync')) or call "
            "mx.dist.init() at the top of the worker script, before building "
            "NDArrays or binding modules. Original error: %s" % e
        ) from e
    _initialized = True
    _job_start_time()
    _start_heartbeat(process_id)
    logging.info("mxnet_tpu.dist: worker %d/%d connected to %s%s",
                 process_id, num_processes, coordinator_address,
                 " [elastic]" if _elastic else "")


def _init_elastic(coordinator_address, num_processes, process_id):
    """Elastic bootstrap: the same coordination service/client pair
    ``jax.distributed.initialize`` would build, but with death propagation
    disabled — a heartbeat timeout that never expires on both ends and
    ``shutdown_on_destruction=False`` (a survivor tearing down its old
    backend must not shut the service down for its peers). The client and
    service OUTLIVE backend re-forms: ``reform()`` rebuilds the XLA backend
    over the survivor set while this client keeps its original node id for
    barriers and the membership KV protocol."""
    global _elastic, _members, _orig_rank, _orig_world, _generation
    from jax._src import distributed as jdist
    from jax._src.lib import _jax

    gs = jdist.global_state
    if gs.client is not None:
        raise RuntimeError("jax.distributed already initialized")
    # seconds (~3 years): the coordination service never declares a node
    # dead on its own
    never = 10 ** 8
    if process_id == 0:
        bind = "[::]:" + coordinator_address.rsplit(":", 1)[1]
        gs.service = _jax.get_distributed_runtime_service(
            bind, num_processes, heartbeat_timeout=never)
    gs.client = _jax.get_distributed_runtime_client(
        coordinator_address, process_id, init_timeout=300,
        heartbeat_timeout=never, shutdown_on_destruction=False,
        use_compression=True)
    gs.client.connect()
    gs.process_id = process_id
    gs.num_processes = num_processes
    gs.coordinator_address = coordinator_address
    _elastic = True
    _generation = 0
    _members = list(range(num_processes))
    _orig_rank = process_id
    _orig_world = num_processes


def _start_heartbeat(process_id):
    """Touch the per-worker heartbeat file on a timer (daemon thread). A
    killed/frozen/OOM-thrashed worker stops beating and the launcher's
    watchdog (tools/launch.py) sees the stale file. Note the limit: a worker
    whose MAIN thread is deadlocked in a collective keeps beating (the
    daemon thread is alive) — liveness here means 'process running', the
    same contract as the reference's ps-lite node heartbeats."""
    global _heartbeat_thread, _heartbeat_stop
    hb_dir = os.environ.get(ENV_HEARTBEAT_DIR)
    if not hb_dir or _heartbeat_thread is not None:
        return
    import threading
    import time

    interval = float(os.environ.get(ENV_HEARTBEAT_INTERVAL, "5"))
    path = os.path.join(hb_dir, "worker-%d" % process_id)
    stop = threading.Event()

    def beat():
        from . import faultinject as _fi

        while _initialized and not stop.is_set():
            try:
                # injection site dist.heartbeat (docs/RESILIENCE.md): a
                # `raise` skips this beat (one missed heartbeat), a
                # delay/hang stalls the thread so the file goes stale —
                # the exact signal the launcher watchdog and the elastic
                # dead-node scan act on
                _fi.fire("dist.heartbeat")
                os.makedirs(hb_dir, exist_ok=True)
                with open(path, "a"):
                    os.utime(path, None)
            except (OSError, _fi.FaultInjected):
                pass
            stop.wait(interval)

    _heartbeat_stop = stop
    _heartbeat_thread = threading.Thread(target=beat, daemon=True,
                                         name="mxtpu-heartbeat")
    _heartbeat_thread.start()


def is_heartbeating() -> bool:
    """Whether this worker's heartbeat thread is live (it stops at
    ``stop_heartbeat`` or ``shutdown``)."""
    return _heartbeat_thread is not None and _heartbeat_thread.is_alive()


def stop_heartbeat(remove=False):
    """Stop this worker's heartbeat — the first step of the DRAIN protocol
    (docs/FAULT_TOLERANCE.md): a SIGTERM'd worker stops beating, and with
    ``remove=True`` deletes its file outright, so the others' next scan
    classes it dead immediately instead of after the staleness timeout.
    The draining worker keeps participating in collectives until the agreed
    pause round; only then does it exit."""
    global _heartbeat_thread, _heartbeat_stop
    if _heartbeat_stop is not None:
        _heartbeat_stop.set()
    if _heartbeat_thread is not None:
        _heartbeat_thread.join(timeout=2.0)
        _heartbeat_thread = None
        _heartbeat_stop = None
    if remove:
        hb_dir = os.environ.get(ENV_HEARTBEAT_DIR)
        wid = _orig_rank if _orig_rank is not None \
            else os.environ.get(ENV_WORKER_ID)
        if hb_dir and wid is not None:
            try:
                os.unlink(os.path.join(hb_dir, "worker-%s" % wid))
            except OSError:
                pass


def num_dead_nodes(timeout=60.0, startup_grace=None):
    """Count workers whose heartbeat file is older than ``timeout`` seconds
    (reference: KVStore::get_num_dead_node,
    include/mxnet/kvstore.h:234-244). Returns 0 when heartbeating is not
    configured (single-process, or launcher without a heartbeat dir).

    A MISSING heartbeat file is treated as alive until ``startup_grace``
    seconds (default: ``timeout``) after the job start — workers come up
    staggered (backend init, first compile) and a peer that simply has not
    beaten YET is not dead. This matches the launcher's ``_stale_worker``
    semantics, where a not-yet-written file is startup, covered by process
    polling; after the grace a still-missing file counts as dead (it never
    came up). Job start is the EARLIEST evidence available: this process's
    anchor (``init()`` in workers, first query in monitors) or the
    heartbeat directory's mtime (set when the first worker file appeared) —
    so a monitor process started long after launch does not grant a dead
    worker a fresh grace window.

    In an elastic job the scan covers the CURRENT membership only: a worker
    already re-formed away stays dead forever (its file never refreshes)
    and must not be re-counted against the new generation."""
    dead, max_age = _scan_heartbeats(timeout, startup_grace)
    _note_liveness(len(dead), max_age)
    return len(dead)


def dead_timeout_seconds() -> float:
    """MXNET_ELASTIC_DEAD_TIMEOUT (default 60 s) — the heartbeat staleness
    past which a member counts dead."""
    try:
        return float(os.environ.get(ENV_DEAD_TIMEOUT, "60"))
    except ValueError:
        return 60.0


def dead_members(timeout=None, startup_grace=None):
    """ORIGINAL ranks of current members whose heartbeat is stale — the
    input to ``plan_reform``. Default timeout: MXNET_ELASTIC_DEAD_TIMEOUT
    (60 s)."""
    if timeout is None:
        timeout = dead_timeout_seconds()
    dead, _ = _scan_heartbeats(timeout, startup_grace)
    return dead


def _scan_heartbeats(timeout, startup_grace):
    """``(dead original-rank list, max heartbeat age)`` over the ranks this
    process currently considers members."""
    import time

    hb_dir = os.environ.get(ENV_HEARTBEAT_DIR)
    if not hb_dir or not os.path.isdir(hb_dir):
        return [], 0.0
    if startup_grace is None:
        startup_grace = timeout
    if _elastic and _members is not None:
        ranks = list(_members)
    else:
        ranks = list(range(int(os.environ.get(ENV_NUM_WORKERS, "1"))))
    now = time.time()
    start = _job_start_time()
    try:
        start = min(start, os.path.getmtime(hb_dir))
    except OSError:
        pass
    in_grace = now - start <= startup_grace
    dead = []
    max_age = 0.0
    for r in ranks:
        path = os.path.join(hb_dir, "worker-%d" % r)
        try:
            age = now - os.path.getmtime(path)
            max_age = max(max_age, age)
            if age > timeout:
                dead.append(r)
        except OSError:
            if not in_grace:
                dead.append(r)  # never heartbeated, grace period over
                # its effective staleness is the whole job lifetime — the
                # age gauge must not read 0 when every worker is missing
                max_age = max(max_age, now - start)
    return dead, max_age


_last_dead = 0  # previous num_dead_nodes result, for transition counting


def _note_liveness(dead, max_age):
    """Telemetry: current dead-worker count and oldest heartbeat age as
    gauges, plus a counter that ticks on every dead-count CHANGE — the
    'node died / node came back' transitions a dashboard alerts on."""
    global _last_dead
    from . import telemetry as _tm

    if not _tm.enabled():
        _last_dead = dead
        return
    _tm.gauge("dist.dead_nodes").set(dead)
    _tm.gauge("dist.heartbeat_age_s").set(round(max_age, 3))
    if dead != _last_dead:
        _tm.counter("dist.dead_node_transitions").inc()
        _tm.event("dist.dead_node_transition", dead=dead,
                  previous=_last_dead)
        _last_dead = dead


def rank() -> int:
    """This process's rank in the CURRENT generation (dense 0..W-1). Elastic
    jobs track it here — ``jax.process_index`` is lru_cached and a re-form
    must not depend on cache-poking order."""
    if _elastic and _members is not None:
        return _members.index(_orig_rank)
    import jax

    return jax.process_index()


def num_workers() -> int:
    if _elastic and _members is not None:
        return len(_members)
    import jax

    return jax.process_count()


# ----------------------------------------------------------------- elastic
def members():
    """ORIGINAL launcher ranks of the current generation, sorted; None when
    not an elastic job. Original ranks are the stable identity — heartbeat
    files and coordination-service node ids keep them across re-forms while
    the dense backend rank (``rank()``) is re-assigned per generation."""
    return list(_members) if _members is not None else None


def generation() -> int:
    """0 at launch; +1 per successful ``reform()``."""
    return _generation


def orig_rank():
    """This process's launch-time rank (stable across re-forms); None when
    not elastic."""
    return _orig_rank


def coordination_client():
    """The job's coordination-service client (elastic jobs only) — the
    barrier/KV substrate the re-form protocol runs on. It outlives backend
    re-forms; its node id is this process's ORIGINAL rank."""
    from .base import MXNetError

    if not _elastic:
        raise MXNetError(
            "coordination_client() needs an elastic job (MXNET_ELASTIC=1 "
            "before dist.init())")
    from jax._src import distributed as jdist

    return jdist.global_state.client


def _reform_timeout_ms() -> int:
    try:
        return int(1000 * float(os.environ.get(ENV_REFORM_TIMEOUT, "120")))
    except ValueError:
        return 120_000


def plan_reform(timeout=None, dead=None):
    """Decide the next generation's membership from the heartbeat files.

    Returns ``{"generation", "members", "dead", "rank", "world"}`` — the
    survivor set and this process's dense rank in it. Raises a structured
    ``MXNetError`` for the unrecoverable cases (docs/FAULT_TOLERANCE.md):

    * the coordinator (original rank 0 — its process HOSTS the coordination
      service; there is no job without it) is among the dead;
    * fewer than ``MXNET_ELASTIC_MIN_WORKERS`` (default 1) survivors;
    * this process itself is classed dead (its own heartbeat went stale —
      clock skew or an overloaded host; re-joining a generation that has
      already written us off would corrupt the collective).
    """
    from .base import MXNetError

    if not _elastic or _members is None:
        raise MXNetError("plan_reform() needs an elastic job "
                         "(MXNET_ELASTIC=1 before dist.init())")
    if dead is None:
        dead = dead_members(timeout=timeout)
    dead = sorted(set(dead) & set(_members))
    if not dead:
        raise MXNetError("plan_reform(): no dead members — nothing to "
                         "re-form (membership: %s)" % (_members,))
    survivors = [m for m in _members if m not in dead]
    if 0 in dead:
        raise MXNetError(
            "elastic re-form impossible: the coordinator (original rank 0) "
            "is dead — its process hosts the coordination service every "
            "barrier and KV exchange rides. Unrecoverable; restart the job "
            "from the last checkpoint (dead: %s)" % dead)
    try:
        min_workers = int(os.environ.get(ENV_MIN_WORKERS, "1"))
    except ValueError:
        min_workers = 1
    if len(survivors) < max(1, min_workers):
        raise MXNetError(
            "elastic re-form impossible: %d survivor(s) %s is below "
            "MXNET_ELASTIC_MIN_WORKERS=%d (dead: %s). Unrecoverable; "
            "restart the job from the last checkpoint"
            % (len(survivors), survivors, min_workers, dead))
    if _orig_rank in dead:
        raise MXNetError(
            "elastic re-form: THIS worker (original rank %d) is classed "
            "dead by its own heartbeat scan — clock skew or a stalled "
            "host. The survivors are re-forming without us; exiting is the "
            "only safe move" % _orig_rank)
    return {"generation": _generation + 1, "members": survivors,
            "dead": dead, "rank": survivors.index(_orig_rank),
            "world": len(survivors)}


def _pause_key(gen):
    return "mxtpu-elastic/gen-%d/pause" % gen


def _pause_margin() -> int:
    try:
        return max(1, int(os.environ.get(ENV_PAUSE_MARGIN, "3")))
    except ValueError:
        return 3


def propose_pause(dead, round_no, margin=None):
    """Publish the pause decision for the NEXT generation in the
    coordination KV (first-write-wins: a second proposal is a no-op and the
    FIRST payload stays in force — every worker acts on one decision even
    when two detect trouble in the same window). Two proposers exist:

    * the coordinator's per-round heartbeat scan (crashed/stalled peers);
    * a SIGTERM'd worker draining itself (``dead=[orig_rank()]``) — no
      staleness wait, the cleanest departure.

    ``pause_at = round_no + margin`` (MXNET_ELASTIC_PAUSE_MARGIN, default
    3): every worker — the proposer included — keeps training through round
    ``pause_at`` so the collective count stays identical across workers
    (hosts drift under async dispatch; the metric read in ``Module.fit``
    bounds the drift well under the default margin). Returns the payload in
    force."""
    import json

    from .base import MXNetError

    client = coordination_client()
    gen = _generation + 1
    payload = {"generation": gen, "dead": sorted(set(int(d) for d in dead)),
               "pause_at": int(round_no) + (_pause_margin() if margin is None
                                            else max(1, int(margin))),
               "proposer": _orig_rank}
    key = _pause_key(gen)
    try:
        client.key_value_set(key, json.dumps(payload))
        from . import telemetry as _tm

        if _tm.enabled():
            _tm.event("dist.pause_proposed", generation=gen,
                      pause_at=payload["pause_at"],
                      dead=",".join(map(str, payload["dead"])))
        return payload
    except Exception:
        # first writer won — adopt its decision
        try:
            return json.loads(client.blocking_key_value_get(key, 10_000))
        except Exception as e:
            raise MXNetError(
                "elastic pause: could not publish OR read the gen-%d pause "
                "payload (%s) — coordination service unreachable; the "
                "coordinator likely died. Unrecoverable; restart from the "
                "last checkpoint" % (gen, e)) from e


def poll_pause():
    """Non-blocking check for a published pause decision for the NEXT
    generation: the payload dict, or None. Cheap enough to call every
    round (one KV directory poll against the coordination service)."""
    import json

    client = coordination_client()
    prefix = "mxtpu-elastic/gen-%d/" % (_generation + 1)
    try:
        entries = client.key_value_dir_get(prefix)
    except Exception:
        return None
    for key, value in entries:
        if key.endswith("/pause"):
            try:
                return json.loads(value)
            except ValueError:
                return None
    return None


def plan_from_pause(payload):
    """Membership plan from an AGREED pause payload — every worker re-forms
    from the same dead set even when local heartbeat scans disagree at the
    staleness boundary. Raises ``EvictedError`` when the payload names THIS
    worker dead (drain after SIGTERM: expected, exit clean; stale heartbeat:
    the survivors have written us off and rejoining would corrupt the
    collective), and the same structured ``MXNetError``s as ``plan_reform``
    for the unrecoverable shapes (coordinator death, too few survivors)."""
    from .base import EvictedError, MXNetError

    if not _elastic or _members is None:
        raise MXNetError("plan_from_pause() needs an elastic job "
                         "(MXNET_ELASTIC=1 before dist.init())")
    gen = int(payload.get("generation", -1))
    if gen != _generation + 1:
        raise MXNetError(
            "elastic pause payload is for generation %d but this worker is "
            "at generation %d — membership drifted (a re-form happened "
            "without us?); unrecoverable" % (gen, _generation))
    dead = sorted(set(payload["dead"]) & set(_members))
    if _orig_rank in dead:
        raise EvictedError(
            "this worker (original rank %d) is in generation %d's dead set "
            "%s — draining (expected after SIGTERM) or written off by the "
            "survivors; stopping training" % (_orig_rank, gen, dead))
    return plan_reform(dead=dead)


def reform(plan=None):
    """Re-form the job over the survivor set: rebuild the XLA backend (and
    its gloo/ICI collective fabric) over ``plan["members"]``, keeping the
    coordination client. The protocol (docs/FAULT_TOLERANCE.md):

    1. the coordinator PUBLISHES the membership plan in the coordination KV
       (every worker scans heartbeats independently; borderline staleness
       must not let two workers re-form different worlds);
    2. survivors rendezvous at a generation-named barrier — a survivor
       wedged in a dead collective has ``MXNET_ELASTIC_REFORM_TIMEOUT`` to
       error out of it and arrive;
    3. the coordinator deletes the PREVIOUS generation's backend topology
       keys (the new backend re-exchanges topology under the same names);
    4. every survivor drops its local backend + compiled caches and
       re-initializes over ``world`` processes at its new dense rank.

    Callers must re-create device arrays afterwards (kvstore.elastic_reform
    snapshots + reseeds); anything built on the old backend is invalid.
    Raises ``MXNetError`` when the plan cannot be agreed or the barrier
    times out."""
    global _members, _generation
    import json
    import time as _time

    from . import telemetry as _tm
    from .base import MXNetError

    if plan is None:
        plan = plan_reform()
    client = coordination_client()
    gen = plan["generation"]
    timeout_ms = _reform_timeout_ms()
    t0 = _time.time()
    with _tm.span("dist.reform", generation=gen, world=plan["world"]):
        key = "mxtpu-elastic/gen-%d/members" % gen
        if _orig_rank == 0:
            client.key_value_set(key, json.dumps(plan["members"]))
            agreed = plan["members"]
        else:
            try:
                agreed = json.loads(
                    client.blocking_key_value_get(key, timeout_ms))
            except Exception as e:
                raise MXNetError(
                    "elastic re-form gen %d: coordinator never published "
                    "the membership plan within %.0fs — it likely died "
                    "mid-re-form. Unrecoverable; restart from the last "
                    "checkpoint (%s)" % (gen, timeout_ms / 1000, e)) from e
        if _orig_rank not in agreed:
            raise MXNetError(
                "elastic re-form gen %d: the coordinator's membership %s "
                "excludes this worker (original rank %d) — our heartbeat "
                "went stale from its point of view. Exiting is the only "
                "safe move" % (gen, agreed, _orig_rank))
        try:
            client.wait_at_barrier("mxtpu-reform-gen-%d" % gen, timeout_ms,
                                   list(agreed))
        except Exception as e:
            raise MXNetError(
                "elastic re-form gen %d: survivor barrier over %s did not "
                "complete within %.0fs — a survivor is wedged or died "
                "during the re-form. Unrecoverable; restart from the last "
                "checkpoint (%s)" % (gen, agreed, timeout_ms / 1000, e)
            ) from e
        prev_world = len(_members)
        _teardown_backend(agreed, prev_world, gen, client, timeout_ms)
        _members = list(agreed)
        _generation = gen
        # rebuild the backend NOW (lazily would hide failures until the
        # first collective) and check the new world actually formed
        import jax

        procs = {d.process_index for d in jax.devices()}
        # validate against the AGREED membership, not the local plan: a
        # borderline-staleness scan can class one extra member dead
        # locally, and the coordinator's publication exists precisely to
        # absorb that divergence — a successful re-form over `agreed`
        # must not be aborted because the local guess was wider
        if len(procs) != len(agreed):
            raise MXNetError(
                "elastic re-form gen %d: re-initialized backend spans %d "
                "process(es), expected %d — the survivor set disagrees "
                "with the backend topology" % (gen, len(procs),
                                               len(agreed)))
    # dead/world derive from what was AGREED, not the local scan
    dead = sorted(set(plan["dead"]) | (set(plan["members"]) - set(agreed)))
    dead = [d for d in dead if d not in agreed]
    if _tm.enabled():
        _tm.counter("dist.reforms").inc()
        _tm.gauge("dist.generation").set(gen)
        _tm.gauge("dist.world").set(len(agreed))
        _tm.event("dist.reform", generation=gen, world=len(agreed),
                  dead=",".join(map(str, dead)),
                  seconds=round(_time.time() - t0, 3))
    logging.info(
        "mxnet_tpu.dist: re-formed generation %d over %d worker(s) "
        "(original ranks %s, dead %s) in %.2fs", gen, len(agreed),
        agreed, dead, _time.time() - t0)
    return {"generation": gen, "members": list(agreed),
            "rank": agreed.index(_orig_rank), "world": len(agreed),
            "dead": dead}


def _teardown_backend(agreed, prev_world, gen, client, timeout_ms):
    """Drop the old backend and re-point the distributed globals at the new
    world. The old gloo sockets/executables die with the backend; the
    topology KV keys of the previous generation are deleted (coordinator)
    so the new backend's exchange starts clean under the same names."""
    import jax
    from jax._src import distributed as jdist
    from jax._src import xla_bridge as xb

    if _orig_rank == 0:
        # every platform the old backend exchanged topology for — the key
        # names are platform-qualified (jax has used both spellings across
        # versions), so a TPU job must delete tpu:* keys, not cpu:*
        plats = {"cpu"}
        try:
            plats.add(jax.default_backend())
        except Exception:
            pass
        for plat in sorted(plats):
            for r in range(prev_world):
                for prefix in ("%s:local_topology/%s/%d" % (plat, plat, r),
                               "local_topology:%s:%d" % (plat, r)):
                    try:
                        client.key_value_delete(prefix)
                    except Exception:
                        pass
            for prefix in ("%s:global_topology/%s" % (plat, plat),
                           "global_topology:%s" % plat):
                try:
                    client.key_value_delete(prefix)
                except Exception:
                    pass
    client.wait_at_barrier("mxtpu-reform-keys-gen-%d" % gen, timeout_ms,
                           list(agreed))
    jax.clear_caches()
    xb._clear_backends()
    # rank/world/DEVICE queries are lru_cached on top of the backend
    # caches — local_devices especially: it caches device OBJECTS, and a
    # stale hit hands old-client devices to the first post-re-form
    # collective ("Buffer ... is on device X, but replica is assigned to
    # device X" — same name, dead client)
    for fn in (xb.process_count, xb.process_index,
               getattr(xb, "device_count", None),
               getattr(xb, "local_device_count", None),
               getattr(xb, "local_devices", None),
               getattr(xb, "devices", None),
               getattr(xb, "process_indices", None)):
        if fn is not None and hasattr(fn, "cache_clear"):
            fn.cache_clear()
    gs = jdist.global_state
    gs.num_processes = len(agreed)
    gs.process_id = list(agreed).index(_orig_rank)
    # module-level arrays that survive the teardown must be re-materialized
    # on the new backend — the global PRNG key especially: dropout draws
    # split it every forward, and a poisoned old-backend key buffer would
    # fail the FIRST post-re-form step with the old generation's error
    from . import random as _random

    _random.refresh_backend()


def shutdown():
    global _initialized, _heartbeat_thread, _heartbeat_stop
    global _elastic, _members, _orig_rank, _orig_world, _generation
    if _initialized:
        import jax

        jax.distributed.shutdown()
        _initialized = False
        if _heartbeat_stop is not None:
            _heartbeat_stop.set()
        _heartbeat_thread = None  # a later init() must restart the beat
        _heartbeat_stop = None
        _elastic = False
        _members = None
        _orig_rank = None
        _orig_world = None
        _generation = 0
