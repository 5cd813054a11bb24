"""One process per chip: how a parent hands each child its own device.

A TPU chip belongs to one process at a time. A second process that opens
the same chip fails or hangs, so whoever starts children (``tools/launch.py``,
``serving.fleet.ReplicaSupervisor``, ``bench.py``) must either keep them off
the chip (``JAX_PLATFORMS=cpu`` in the child's environment) or give each
child a chip of its own — and must not itself hold the chip the child needs.

This module is the one place that knows how. It never imports jax: counting
chips goes through the device files libtpu opens, and pinning goes through
the environment variables the installed libtpu (0.0.34) honours, established
on a four-chip v5e host:

- ``TPU_VISIBLE_CHIPS=i`` + ``TPU_CHIPS_PER_PROCESS_BOUNDS=1,1,1`` +
  ``TPU_PROCESS_BOUNDS=1,1,1``: the child sees chip ``i`` alone, as a
  one-device world (independent children: serving replicas).
- the same with ``TPU_PROCESS_BOUNDS=<host bounds>``, ``TPU_PROCESS_ADDRESSES``,
  ``TPU_PROCESS_PORT`` and ``CLOUD_TPU_TASK_ID``: the children form ONE job
  over all the host's chips, one local device each (a launched training
  job; ``jax.process_index()`` follows the chips' coordinates, not the
  task id).
"""
import glob
import sys

from .base import MXNetError

__all__ = ["local_chip_count", "wants_chip", "holds_chip", "pin_children"]

# process grid of a whole host, by chip count (Cloud TPU v5e/v6e host shapes);
# the machine's own TPU_CHIPS_PER_HOST_BOUNDS wins when it is set
_HOST_BOUNDS = {1: "1,1,1", 4: "2,2,1", 8: "2,4,1"}


def local_chip_count():
    """TPU chips on this host, counted without opening the backend."""
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


def wants_chip(env):
    """Whether a process started with ``env`` would open the TPU backend."""
    platforms = env.get("JAX_PLATFORMS", "")
    if platforms:
        return "tpu" in platforms.split(",")
    return local_chip_count() > 0


def holds_chip():
    """Whether THIS process has initialised a JAX backend on the TPU."""
    if "jax" not in sys.modules:
        return False
    import jax
    from jax._src import xla_bridge

    return (xla_bridge.backends_are_initialized()
            and jax.default_backend() == "tpu")


def pin_children(envs, job_ports=None):
    """Give each child (one environment dict per child) a chip of its own.

    Children that stay on the CPU are returned unchanged. Children that
    would open the TPU are pinned to chips ``0..n-1``; more children than
    chips, or a parent that already holds the chip, is an ``MXNetError`` —
    never a hang. ``job_ports`` (one free port per child) makes the children
    one cooperative job over ALL the host's chips; without it each child is
    an independent one-chip process."""
    count = len(envs)
    if not wants_chip(envs[0]):
        return envs
    chips = local_chip_count()
    if count > chips:
        raise MXNetError(
            "%d child process(es) would each open the TPU, but this host "
            "has %d chip(s) and a chip belongs to one process. Start at most "
            "%d, or keep the children off the chip with JAX_PLATFORMS=cpu."
            % (count, chips, chips))
    if holds_chip():
        raise MXNetError(
            "this process has initialised JAX on the TPU and so holds the "
            "chip its children need. Start chip children before touching "
            "JAX, or keep them off the chip with JAX_PLATFORMS=cpu.")
    if count == 1:
        return envs  # the only child may drive every chip
    if job_ports is not None:
        if count != chips:
            raise MXNetError(
                "a cooperative job spans one process or all %d chips of "
                "this host (the process grid of a partial host is not "
                "known here); got %d workers" % (chips, count))
        grid = (envs[0].get("TPU_CHIPS_PER_HOST_BOUNDS")
                or _HOST_BOUNDS.get(chips))
        if grid is None:
            raise MXNetError("no process grid known for a %d-chip host "
                             "(set TPU_CHIPS_PER_HOST_BOUNDS)" % chips)
    pinned = []
    for i, env in enumerate(envs):
        env = dict(env)
        env["TPU_VISIBLE_CHIPS"] = str(i)
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"
        if job_ports is not None:
            # the configuration verified on the four-chip host, legacy
            # spellings included (the machine presets them for ONE process
            # over the whole host)
            env["TPU_PROCESS_BOUNDS"] = env["TPU_HOST_BOUNDS"] = grid
            env["TPU_CHIPS_PER_HOST_BOUNDS"] = "1,1,1"
            env["TPU_PROCESS_ADDRESSES"] = ",".join(
                "localhost:%d" % p for p in job_ports)
            env["TPU_PROCESS_PORT"] = str(job_ports[i])
            env["CLOUD_TPU_TASK_ID"] = str(i)
            env.pop("TPU_WORKER_HOSTNAMES", None)
            env.pop("TPU_WORKER_ID", None)
        pinned.append(env)
    return pinned
