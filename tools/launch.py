#!/usr/bin/env python
"""Launch a multi-worker training job.

Counterpart of the reference's tools/launch.py (dmlc-core tracker submitting
scheduler+server+worker processes over ssh/mpi/sge/yarn). The TPU-native job
has no scheduler or server roles — every worker runs the same SPMD program —
so launching means: start N copies of the command with the ``MXNET_TPU_*``
coordination env (see mxnet_tpu/dist.py), worker 0 hosting the coordination
service.

Launchers:
  * ``local`` — N processes on this host (the reference's ``--launcher local``
    used by tests/nightly/dist_sync_kvstore.py). With ``--cpu-devices K`` each
    worker gets K virtual CPU devices and ``JAX_PLATFORMS=cpu`` (testing
    without TPU hardware). Without it, on a TPU host, each worker is pinned
    to its own chip (mxnet_tpu/chips.py) and more workers than chips is an
    error: a chip belongs to one process.
  * ``ssh``  — one worker per host from --hostfile via ssh (reference's ssh
    tracker); workers see the coordinator via this host's address.

On real TPU pods the platform's own job scheduler (GKE/ICI runtime) starts
one process per host and this launcher is unnecessary — pass the coordinator
env directly.

Example:
  python tools/launch.py -n 4 --launcher local --cpu-devices 2 \
      python tests/nightly/dist_sync_kvstore.py
"""
import argparse
import os
import signal
import socket
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _worker_env(base, args, coordinator, rank, hb_dir=None):
    env = dict(base)
    env["MXNET_TPU_COORDINATOR"] = coordinator
    env["MXNET_TPU_NUM_WORKERS"] = str(args.num_workers)
    env["MXNET_TPU_WORKER_ID"] = str(rank)
    if getattr(args, "elastic", False):
        env["MXNET_ELASTIC"] = "1"
    if hb_dir:
        env["MXNET_TPU_HEARTBEAT_DIR"] = hb_dir
        if args.heartbeat_interval is not None:
            env["MXNET_TPU_HEARTBEAT_INTERVAL"] = str(args.heartbeat_interval)
        else:
            env.setdefault("MXNET_TPU_HEARTBEAT_INTERVAL", "5")
    if args.cpu_devices:
        flags = env.get("XLA_FLAGS", "")
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%d" % args.cpu_devices
        ).strip()
        env["MXNET_DEFAULT_CONTEXT"] = "cpu"
        # a CPU worker must never open the TPU backend: a chip belongs to
        # one process, and N workers on one chip fail or hang
        env["JAX_PLATFORMS"] = "cpu"
    return env


def _stale_worker(hb_dir, ranks, timeout):
    """Rank (among the still-LIVE ranks) whose heartbeat went stale, else
    None. Exited workers are excluded — a finished worker's frozen file is
    not a failure."""
    import time

    now = time.time()
    for r in ranks:
        path = os.path.join(hb_dir, "worker-%d" % r)
        try:
            if now - os.path.getmtime(path) > timeout:
                return r
        except OSError:
            pass  # not written yet: startup, covered by process polling
    return None


def _terminate(procs, grace=10):
    """SIGTERM, wait up to ``grace`` seconds, then SIGKILL — a worker
    blocked in a dead collective cannot run a SIGTERM handler."""
    import time

    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
    deadline = time.time() + grace
    while any(p.poll() is None for p in procs) and time.time() < deadline:
        time.sleep(0.2)
    for p in procs:
        if p.poll() is None:
            p.kill()
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass


def _wait_all(procs, hb_dir=None, hb_timeout=0, elastic=False):
    """Wait for every worker. Failure detection (reference: ps-lite
    heartbeats behind KVStore::get_num_dead_node, kvstore.h:234-244 /
    kvstore_dist.h:158-167): a nonzero exit, OR a stale heartbeat from a
    live worker process (catches frozen/SIGSTOPped/OOM-thrashed workers
    whose runtime stopped beating — NOT a live-but-deadlocked collective,
    whose heartbeat thread keeps running; that case needs job-level
    timeouts), terminates the whole job with SIGTERM-then-SIGKILL — the
    caller decides whether to restart from the last checkpoint.

    ``elastic=True`` (docs/FAULT_TOLERANCE.md) inverts the policy for
    non-coordinator workers: their death or stale heartbeat is the
    SURVIVORS' business (pause → re-form → resume), so the launcher keeps
    waiting instead of tearing the job down. Only worker 0's failure —
    its process hosts the coordination service, nothing survives it — or
    every worker failing still kills the job."""
    import time

    code = 0
    live = dict(enumerate(procs))  # rank -> proc (Popen order is rank order)
    failed = False
    any_ok = False
    while live:
        for r, p in list(live.items()):
            rc = p.poll()
            if rc is None:
                continue
            del live[r]
            if rc == 0:
                any_ok = True
            else:
                if elastic and r != 0:
                    sys.stderr.write(
                        "launch: worker %d exited rc=%d — elastic job, "
                        "survivors re-form without it\n" % (r, rc))
                    # forgiven below iff anyone succeeds AND the job never
                    # hit a terminal failure (coordinator death)
                    code = code or rc
                    continue
                code = code or rc
                failed = True
        if not failed and hb_dir and hb_timeout > 0 and live:
            stale = _stale_worker(hb_dir, sorted(live), hb_timeout)
            if stale is not None and (not elastic or stale == 0):
                sys.stderr.write(
                    "launch: worker %d heartbeat stale > %gs — declaring the "
                    "job dead\n" % (stale, hb_timeout))
                code = 124
                failed = True
            elif stale is not None:
                # elastic: the survivors already class this worker dead
                # (same staleness signal) and re-form without it — but its
                # frozen PROCESS must still be reaped or `live` never
                # empties and the launcher hangs after the job finishes
                sys.stderr.write(
                    "launch: worker %d heartbeat stale > %gs — elastic "
                    "job, reaping the frozen process; survivors re-form "
                    "without it\n" % (stale, hb_timeout))
                _terminate([live[stale]])
        if failed and live:
            _terminate(list(live.values()))
        time.sleep(0.2)
    if elastic and any_ok and not failed:
        # the job succeeded if the final generation finished, even though
        # evicted workers exited nonzero along the way — but a TERMINAL
        # failure (coordinator death, stale-coordinator watchdog) stays a
        # failure no matter how many workers exited clean before it
        return 0
    return code


def launch_local(args, command):
    """Run the job; on worker death/freeze, tear down and relaunch up to
    ``--auto-restart`` times. Training scripts resume from their last
    checkpoint (model.find_last_checkpoint / fit(begin_epoch=...))."""
    import shutil
    import tempfile

    attempts = 0
    while True:
        coordinator = "127.0.0.1:%d" % _free_port()
        # elastic jobs need the heartbeat dir unconditionally: it is the
        # workers' OWN failure detector, not just the launcher's
        hb_dir = tempfile.mkdtemp(prefix="mxtpu-hb-") \
            if (args.heartbeat_timeout > 0 or args.elastic) else None
        procs = []
        try:
            # workers that open the TPU get one chip each and form one job
            # over the host's chips; more workers than chips raises here
            envs = [_worker_env(os.environ, args, coordinator, rank, hb_dir)
                    for rank in range(args.num_workers)]
            if envs[0].get("JAX_PLATFORMS") != "cpu":
                if _ROOT not in sys.path:
                    sys.path.insert(0, _ROOT)
                from mxnet_tpu import chips  # opens no jax backend

                envs = chips.pin_children(
                    envs, job_ports=[_free_port() for _ in envs])
            for env in envs:
                procs.append(subprocess.Popen(command, env=env))
            code = _wait_all(procs, hb_dir, args.heartbeat_timeout,
                             elastic=args.elastic)
        finally:
            # every old worker must be DEAD before cleanup/relaunch: a
            # straggler could race the next attempt's checkpoint resume (and
            # its beat thread would recreate hb_dir after rmtree)
            _terminate(procs)
            if hb_dir:
                shutil.rmtree(hb_dir, ignore_errors=True)
        if code == 0 or attempts >= args.auto_restart:
            return code
        attempts += 1
        sys.stderr.write(
            "launch: job failed (rc=%d) — restart %d/%d from last "
            "checkpoint\n" % (code, attempts, args.auto_restart))


def launch_ssh(args, command):
    with open(args.hostfile) as f:
        hosts = [h.strip() for h in f if h.strip()]
    assert len(hosts) >= args.num_workers, "hostfile has fewer hosts than -n"
    # worker 0 hosts the coordination service, so advertise ITS address; the
    # port cannot be probed remotely — use a fixed high port (reference's
    # tracker likewise picks a port for the root role)
    coordinator = "%s:%d" % (hosts[0], args.port)
    procs = []
    try:
        for rank in range(args.num_workers):
            import shlex

            env = _worker_env({}, args, coordinator, rank)
            envstr = " ".join("%s=%s" % (k, shlex.quote(v)) for k, v in env.items())
            remote = "cd %s && env %s %s" % (
                shlex.quote(os.getcwd()), envstr,
                " ".join(shlex.quote(w) for w in command))
            procs.append(subprocess.Popen(["ssh", "-o",
                                           "StrictHostKeyChecking=no",
                                           hosts[rank], remote]))
        return _wait_all(procs)
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)


def main():
    parser = argparse.ArgumentParser(
        description="Launch a multi-worker mxnet_tpu job",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    parser.add_argument("-n", "--num-workers", type=int, required=True,
                        help="number of worker processes")
    parser.add_argument("--launcher", type=str, default="local",
                        choices=["local", "ssh"])
    parser.add_argument("--hostfile", type=str, default=None,
                        help="(ssh) file with one host per line")
    parser.add_argument("--port", type=int, default=29400,
                        help="(ssh) coordination-service port on the first host")
    parser.add_argument("--cpu-devices", type=int, default=0,
                        help="give each worker this many virtual CPU devices "
                             "(multi-host testing without TPU hardware)")
    parser.add_argument("--elastic", action="store_true",
                        help="(local) run the job elastically "
                             "(MXNET_ELASTIC=1): a non-coordinator worker's "
                             "death pauses and re-forms the job over the "
                             "survivors instead of killing it "
                             "(docs/FAULT_TOLERANCE.md)")
    parser.add_argument("--auto-restart", type=int, default=0,
                        help="(local) relaunch the whole job up to this many "
                             "times after a worker dies or hangs; workers "
                             "resume from their last checkpoint")
    parser.add_argument("--heartbeat-timeout", type=float, default=60.0,
                        help="(local) declare the job dead when a LIVE "
                             "worker's heartbeat file is older than this "
                             "many seconds — catches frozen/stopped worker "
                             "processes (0 disables)")
    parser.add_argument("--heartbeat-interval", type=float, default=None,
                        help="how often workers touch their heartbeat file "
                             "(default: inherit MXNET_TPU_HEARTBEAT_INTERVAL "
                             "from the environment, else 5)")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="the training command to run on every worker")
    args = parser.parse_args()
    if not args.command:
        parser.error("no command given")

    if args.launcher == "local":
        sys.exit(launch_local(args, args.command))
    sys.exit(launch_ssh(args, args.command))


if __name__ == "__main__":
    main()
