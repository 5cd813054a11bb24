"""Nothing on the measuring paths may hide the device (PR 21).

Each test pins one removed fallback: a chip id past the visible chips, an
unknown device kind's peak, a compile cache re-pointed in code, a native
library picked up by mtime, a bench that carries on without a chip, CPU
workers that open the TPU, more chip children than chips.
"""
import argparse
import os
import re
import shutil
import subprocess
import sys
import time

import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import chips, compile_cache, context
from mxnet_tpu.base import MXNetError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ context
def test_accelerator_id_past_the_visible_chips_raises(monkeypatch):
    # no accelerator in this process: every tpu/gpu id is out of range
    for ctx in (mx.tpu(0), mx.tpu(1), mx.gpu(0)):
        with pytest.raises(MXNetError, match="0 accelerator"):
            ctx.jax_device
    # one chip: id 0 is it, id 1 is an error — never chip 0 by modulo
    chip = object()
    monkeypatch.setattr(context, "_accelerator_devices", lambda: [chip])
    assert mx.tpu(0).jax_device is chip
    with pytest.raises(MXNetError, match="1 accelerator"):
        mx.tpu(1).jax_device
    # the cpu id stays a hint (the reference's semantics)
    n_cpu = len(jax.local_devices(backend="cpu"))
    assert mx.cpu(n_cpu + 3).jax_device is mx.cpu(3).jax_device


def test_unknown_device_kind_has_no_peak():
    from mxnet_tpu.device_info import bf16_peak_flops

    assert bf16_peak_flops("TPU v5 lite") == 197e12
    for kind in ("TPU v5x", "TPU v5 lite pod", "cpu"):
        with pytest.raises(MXNetError, match="no bf16 peak"):
            bf16_peak_flops(kind)


# ------------------------------------------------------------ compile cache
def _tiny_symbol():
    data = mx.symbol.Variable("data")
    return mx.symbol.FullyConnected(data, num_hidden=4, name="fc")


def test_compile_cache_is_placed_once(monkeypatch, tmp_path):
    from mxnet_tpu.serving import PersistentExecutableCache

    saved = jax.config.jax_compilation_cache_dir
    try:
        # unset: the fixed in-checkout directory, also after a serving cache
        # with its own cache_dir was built (it keeps manifests only)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        compile_cache.configure()
        assert compile_cache.DEFAULT_DIR == os.path.join(ROOT, ".jax_cache")
        assert compile_cache.directory() == compile_cache.DEFAULT_DIR
        PersistentExecutableCache(_tiny_symbol(), cache_dir=str(tmp_path))
        assert compile_cache.directory() == compile_cache.DEFAULT_DIR
        # set: JAX reads the variable itself; no code path touches the setting
        outside = str(tmp_path / "placed-from-outside")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        jax.config.update("jax_compilation_cache_dir", outside)  # as jax does
        compile_cache.configure()
        PersistentExecutableCache(_tiny_symbol(), cache_dir=str(tmp_path))
        assert compile_cache.directory() == outside
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)


def test_exactly_one_setter_of_the_cache_dir():
    setters = []
    targets = [os.path.join(ROOT, "bench.py"),
               os.path.join(ROOT, "chip_smoke.py")]
    for top in ("mxnet_tpu", "tools"):
        for dirpath, _, files in os.walk(os.path.join(ROOT, top)):
            targets += [os.path.join(dirpath, f) for f in files
                        if f.endswith(".py")]
    for path in targets:
        with open(path) as f:
            if re.search(r"update\(\s*[\"']jax_compilation_cache_dir",
                         f.read()):
                setters.append(os.path.relpath(path, ROOT))
    assert setters == [os.path.join("mxnet_tpu", "compile_cache.py")]


# ------------------------------------------------------------- native build
@pytest.mark.skipif(shutil.which("g++") is None, reason="no toolchain")
def test_native_build_keys_on_source_hash_not_mtime(monkeypatch, tmp_path):
    from mxnet_tpu import _native_build as nb

    monkeypatch.setattr(nb, "_BUILD_DIR", str(tmp_path / "build"))
    src = tmp_path / "probe.cc"
    src.write_text('extern "C" int probe() { return 1; }\n')
    old = time.time() - 3600
    os.utime(src, (old, old))
    lib = nb.build_lib(str(src), "libprobe.so")
    assert lib and os.path.isfile(lib)
    key1 = open(lib + ".key").read()
    built1 = os.stat(lib).st_mtime_ns
    assert nb.build_lib(str(src), "libprobe.so") == lib
    assert os.stat(lib).st_mtime_ns == built1  # fresh: not rebuilt
    # new source under the SAME (old) mtime: an mtime check would keep the
    # stale library; the hash must rebuild
    src.write_text('extern "C" int probe() { return 2; }\n')
    os.utime(src, (old, old))
    assert nb.build_lib(str(src), "libprobe.so") == lib
    assert open(lib + ".key").read() != key1
    assert os.stat(lib).st_mtime_ns != built1
    # a library copied in without its key never stands in for the source
    os.unlink(lib + ".key")
    built2 = os.stat(lib).st_mtime_ns
    nb.build_lib(str(src), "libprobe.so")
    assert os.stat(lib).st_mtime_ns != built2
    # a failed compile says so and returns None
    src.write_text("this is not C++\n")
    assert nb.build_lib(str(src), "libprobe.so") is None


# -------------------------------------------------------- no chip, no result
@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_measuring_scripts_fail_without_a_chip(script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, script)],
                       capture_output=True, text=True, timeout=120, env=env,
                       cwd=ROOT)
    assert r.returncode not in (0, None), r.stdout + r.stderr
    assert "no TPU" in r.stderr
    assert "degraded" not in r.stdout + r.stderr
    assert '"ok"' not in r.stdout and '"value"' not in r.stdout


# ------------------------------------------------------ one process per chip
def _launch_module():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "launch_under_test", os.path.join(ROOT, "tools", "launch.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_launch_cpu_workers_carry_jax_platforms_cpu():
    launch = _launch_module()
    args = argparse.Namespace(num_workers=2, elastic=False,
                              heartbeat_interval=None, cpu_devices=2)
    env = launch._worker_env({"JAX_PLATFORMS": "tpu,cpu"}, args,
                             "127.0.0.1:1", 1)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["MXNET_DEFAULT_CONTEXT"] == "cpu"
    assert "--xla_force_host_platform_device_count=2" in env["XLA_FLAGS"]
    # without --cpu-devices the platform is the caller's business
    args.cpu_devices = 0
    env = launch._worker_env({"JAX_PLATFORMS": "tpu,cpu"}, args,
                             "127.0.0.1:1", 1)
    assert env["JAX_PLATFORMS"] == "tpu,cpu"


def test_chip_children_get_one_chip_each_or_an_error(monkeypatch):
    tpu_env = {"JAX_PLATFORMS": "tpu,cpu", "TPU_CHIPS_PER_HOST_BOUNDS":
               "2,2,1"}
    # CPU children are nobody's business
    cpu = [{"JAX_PLATFORMS": "cpu"}] * 8
    assert chips.pin_children(cpu) is cpu
    assert not chips.holds_chip()
    monkeypatch.setattr(chips, "local_chip_count", lambda: 4)
    # independent children (serving replicas): a one-device world each
    pinned = chips.pin_children([tpu_env] * 3)
    assert [e["TPU_VISIBLE_CHIPS"] for e in pinned] == ["0", "1", "2"]
    assert {e["TPU_PROCESS_BOUNDS"] for e in pinned} == {"1,1,1"}
    # a cooperative job spans the whole host
    job = chips.pin_children([tpu_env] * 4, job_ports=[9001, 9002, 9003,
                                                       9004])
    assert {e["TPU_PROCESS_BOUNDS"] for e in job} == {"2,2,1"}
    assert [e["CLOUD_TPU_TASK_ID"] for e in job] == ["0", "1", "2", "3"]
    assert job[2]["TPU_PROCESS_PORT"] == "9003"
    with pytest.raises(MXNetError, match="all 4 chips"):
        chips.pin_children([tpu_env] * 2, job_ports=[9001, 9002])
    # more children than chips is an error, not a hang
    with pytest.raises(MXNetError, match="has 4 chip"):
        chips.pin_children([tpu_env] * 5)
    # and so is a parent that already holds the chip
    monkeypatch.setattr(chips, "holds_chip", lambda: True)
    with pytest.raises(MXNetError, match="holds the chip"):
        chips.pin_children([tpu_env] * 2)


def test_replica_supervisor_refuses_more_replicas_than_chips(monkeypatch,
                                                             tmp_path):
    from mxnet_tpu.serving.fleet import ReplicaSupervisor

    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setattr(chips, "local_chip_count", lambda: 1)
    with pytest.raises(MXNetError, match="has 1 chip"):
        ReplicaSupervisor({"model": "mlp"}, n_replicas=4,
                          workdir=str(tmp_path))
    # on the CPU the same fleet is fine (nothing is spawned until start())
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    ReplicaSupervisor({"model": "mlp"}, n_replicas=4, workdir=str(tmp_path))
