"""The program store (``serving/cache.py``): a bucket's forward program kept
EXPORTED in a directory of the compile cache's, so that a process which finds
it there binds, and neither traces nor lowers nor imports what tracing needs.

The pytest process is held out of the compile cache (``conftest.py``), and so
out of the store; these tests turn both on over a ``tmp_path``."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import compile_cache
from mxnet_tpu import telemetry as tm
from mxnet_tpu.serving import PersistentExecutableCache
from mxnet_tpu.serving import cache as cache_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def store(tmp_path):
    """The compile cache, and so the store, on over ``tmp_path``; counters
    on. Gives the store's directory."""
    from jax._src import compilation_cache

    saved_dir = jax.config.jax_compilation_cache_dir
    saved_mode = tm.current_override()
    compilation_cache.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    tm.set_mode("counters")
    tm.reset()
    try:
        yield compile_cache.program_store()
    finally:
        tm.set_mode(saved_mode)
        tm.reset()
        jax.config.update("jax_enable_compilation_cache", False)
        jax.config.update("jax_compilation_cache_dir", saved_dir)
        compilation_cache.reset_cache()


def _counts():
    snap = tm.snapshot()
    return tuple(snap.get("serving.program_store." + what, 0)
                 for what in ("hit", "miss", "stale"))


def _net(hidden=8):
    data = mx.sym.Variable("data")
    state = mx.sym.Variable("state")
    fc = mx.sym.FullyConnected(data=data, num_hidden=hidden, name="fc")
    return mx.sym.Group([mx.sym.tanh(fc, name="out"),
                         mx.sym.elemwise_add(state, fc, name="state_next")])


def _cache(shape=(2, 4), hidden=8, **kw):
    """A cache of the two-output net, warmed at one bucket: its executor."""
    params = {"fc_weight": np.arange(hidden * 4, dtype="f").reshape(
        hidden, 4) / 10, "fc_bias": np.ones(hidden, "f")}
    cache = PersistentExecutableCache(_net(hidden), params, **kw)
    shapes = {"data": shape, "state": (shape[0], hidden)}
    cache.warmup([shapes])
    return cache.executable(shapes)


def _run(exe):
    exe.arg_dict["data"][:] = np.arange(8, dtype="f").reshape(2, 4)
    exe.arg_dict["state"][:] = np.ones((2, 8), "f")
    exe.forward(is_train=False)
    return [np.array(o.asnumpy()) for o in exe.outputs]


def test_the_store_is_on_where_the_compile_cache_is():
    """``compile_cache.program_store()`` names a directory of the compile
    cache's, and nothing where that cache is off (this process, by
    ``conftest.py``): a cache bound here keeps no store and writes
    nothing."""
    assert not jax.config.jax_enable_compilation_cache
    assert compile_cache.program_store() is None
    exe = _cache()
    assert exe._prog.store is None
    jax.config.update("jax_enable_compilation_cache", True)
    try:
        assert compile_cache.program_store() == os.path.join(
            compile_cache.directory(), "mx_programs")
    finally:
        jax.config.update("jax_enable_compilation_cache", False)


def test_a_stored_program_is_the_traced_one_bit_for_bit(store):
    """The first executor of a bucket misses, exports and writes one blob;
    the next finds it, and both give what the traced program gives, bit for
    bit. What is run is lowered under the traced program's name."""
    jax.config.update("jax_enable_compilation_cache", False)
    traced = _run(_cache(program_label="mx_try"))
    jax.config.update("jax_enable_compilation_cache", True)
    assert _counts() == (0, 0, 0)
    first = _cache(program_label="mx_try")
    assert _counts() == (0, 1, 0)
    blobs = os.listdir(store)
    assert len(blobs) == 1 and blobs[0].endswith(".mxprog")
    second = _cache(program_label="mx_try")
    assert _counts() == (1, 1, 0) and os.listdir(store) == blobs
    for exe in (first, second):
        for got, want in zip(_run(exe), traced):
            assert got.tobytes() == want.tobytes()
        assert "module @jit_mx_try" in exe._prog._fwd(False).lower(
            *exe._prog.store.specs).as_text()


def _other_source(monkeypatch):
    monkeypatch.setattr(cache_mod, "_SOURCE_DIGEST", "another checkout")


def _other_jax(monkeypatch):
    monkeypatch.setattr(jax, "__version__", jax.__version__ + ".1")


def _other_env(monkeypatch):
    monkeypatch.setenv("MXNET_RING_ATTENTION", "0")


# what changes -> how: keywords of ``_cache``, or a patch
_KEY_PARTS = {
    "a_shape": dict(shape=(3, 4)),
    "a_width_of_the_symbol": dict(hidden=16),
    "a_dtype": dict(input_dtypes={"state": "bfloat16"}),
    "the_donated_names": dict(donated=("state",)),
    "the_label": dict(program_label="mx_other"),
    "a_byte_of_the_packages_source": _other_source,
    "the_version_of_jax": _other_jax,
    "an_environment_variable_of_ours": _other_env,
}


@pytest.mark.parametrize("part", list(_KEY_PARTS))
def test_each_part_of_the_key_alone_makes_a_miss(store, monkeypatch, part):
    """After the program is stored, a bind that differs in one part of the
    key misses and writes a second blob; the unchanged bind still hits."""
    base = dict(program_label="mx_try")
    _cache(**base)
    assert _counts() == (0, 1, 0)
    change = _KEY_PARTS[part]
    if callable(change):
        change(monkeypatch)
        _cache(**base)
    else:
        _cache(**dict(base, **change))
    assert _counts() == (0, 2, 0), part
    assert len(os.listdir(store)) == 2
    monkeypatch.undo()
    _cache(**base)
    assert _counts() == (1, 2, 0)


@pytest.mark.parametrize("damage", ["truncated", "another_key", "not_a_blob"])
def test_a_blob_that_cannot_be_used_is_a_counted_miss(store, damage):
    """A stored file cut short, holding another key, or whose blob
    ``deserialize`` refuses is ``stale``: logged, counted, traced again and
    written over, and the run goes on with the right answer."""
    want = _run(_cache())
    (name,) = os.listdir(store)
    path = os.path.join(store, name)
    with open(path, "rb") as f:
        blob = f.read()
    head = len(cache_mod._StoredProgram._MAGIC) + 32
    with open(path, "wb") as f:
        f.write({"truncated": blob[:head + 100],
                 "another_key": blob[:head - 1] + b"\0" + blob[head:],
                 "not_a_blob": blob[:head] + b"no flatbuffer"}[damage])
    got = _run(_cache())
    assert _counts() == (0, 1, 1)
    assert all(g.tobytes() == w.tobytes() for g, w in zip(got, want))
    with open(path, "rb") as f:
        assert f.read() == blob     # whole again
    _cache()
    assert _counts() == (1, 1, 1)


def _decoder():
    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.serving import PagedKVDecoder

    cfg = dict(vocab_size=48, num_layers=2, num_heads=2, model_dim=128,
               ffn_dim=64)
    net = tf.get_symbol(seq_len=128, **cfg)
    shapes, _, _ = net.infer_shape(data=(1, 128), softmax_label=(1, 128))
    rs = np.random.RandomState(2)
    params = {n: mx.nd.array((rs.randn(*s) * 0.1).astype("f"))
              for n, s in zip(net.list_arguments(), shapes)
              if n not in ("data", "softmax_label")}
    # a process names its symbols' nodes by count: a second decoder of one
    # process is the first of another only from a fresh count
    with mx.name.NameManager():
        return PagedKVDecoder(params, max_len=128, page_size=16, lanes=4,
                              prefill_len=32, pos_len=128, **cfg)


def _generate(dec):
    """Admissions and steps; every row's logits, and whether each step's
    donated cache died with it."""
    seqs = [dec.admit(np.arange(n) % 47)[0] for n in (3, 15, 32)]
    rows, died = [], []
    for t in range(5):
        held = [dec._dec_exe.arg_dict[n]._jax() for n in dec._cache_names]
        out = dec.step({s: (7 * t + s) % 48 for s in seqs})
        died.append(all(a.is_deleted() for a in held))
        rows += [np.asarray(out[s]) for s in seqs]
    return np.stack(rows), died


def test_a_stored_decode_step_donates_as_a_traced_one_does(store):
    """A decoder that loads its decode and prefill programs from the store
    steps as the one that traced them: the same logits bit for bit, and
    after each step the arrays its cache held are deleted."""
    jax.config.update("jax_enable_compilation_cache", False)
    traced, died = _generate(_decoder().warmup())
    assert all(died)
    jax.config.update("jax_enable_compilation_cache", True)
    _decoder().warmup()
    assert _counts() == (0, 2, 0)
    dec = _decoder().warmup()
    assert _counts() == (2, 2, 0)
    stored, died = _generate(dec)
    assert all(died)
    assert stored.tobytes() == traced.tobytes()
    # the cost analysis a traced run's warm-up reads lowers what is run
    assert dec._dec_exe.compiled().cost_analysis()["flops"] > 0


_PROCESS = """
import json, sys
import numpy as np
import jax
sys.path.insert(0, %(tests)r)
from mxnet_tpu import telemetry as tm
from mxnet_tpu.ops import attention
import test_program_store as t

# the kernel, interpreted: the process that traces it imports Pallas
attention.pool_read_form = lambda *a: (
    "kernel" if a[3] is not None and a[2] is not None else "whole_pool")
jax.config.update("jax_enable_compilation_cache", True)
tm.set_mode("counters")
dec = t._decoder().warmup()
pallas = sorted(m for m in sys.modules if m.startswith(
    ("jax._src.pallas", "jax.experimental.pallas")))
logits, died = t._generate(dec)
print(json.dumps({
    "counts": t._counts(), "pallas": len(pallas), "died": all(died),
    "tokens": logits.argmax(-1).tolist(),
    "logits": __import__("hashlib").sha1(logits.tobytes()).hexdigest()}))
"""


def test_a_second_process_loads_what_the_first_exported(tmp_path):
    """Two FRESH processes build the same decoder on one store: the first
    misses twice (decode, prefill), traces the kernel (so it imports Pallas)
    and writes two blobs; the second hits twice, imports nothing of Pallas
    through ``warmup()``, and generates the first's logits bit for bit."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.pop("MXNET_TELEMETRY", None)
    runs = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c",
             _PROCESS % {"tests": os.path.join(REPO, "tests")}],
            env=env, capture_output=True, text=True, timeout=600, cwd=REPO)
        assert out.returncode == 0, out.stderr[-4000:]
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    first, second = runs
    assert first["counts"] == [0, 2, 0] and first["pallas"] > 0
    assert second["counts"] == [2, 0, 0] and second["pallas"] == 0
    assert len(os.listdir(tmp_path / "mx_programs")) == 2
    assert first["died"] and second["died"]
    assert second["logits"] == first["logits"]
    assert second["tokens"] == first["tokens"]


def test_the_exported_decode_program_is_the_chips(tmp_path, monkeypatch):
    """``transformer-base``'s decode program at the benchmark's sizes,
    exported for the v5e from here and read back from the store: the blob
    carries the ``tpu_custom_call``, and what a warm process would run,
    ``jax.jit(exported.call)`` with the pools donated, compiles for the chip
    with its six kernel calls (one a layer), since PR 55 one scatter a pool
    in place of a loop a layer, and the whole cache aliased."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from mxnet_tpu.executor import _GraphProgram
    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.ops import attention
    from mxnet_tpu.ops.attention import pool_shape

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no libtpu, or one that cannot
        pytest.skip("libtpu cannot build the v5e topology: %s" % exc)
    chip = SingleDeviceSharding(topo.devices[0])
    monkeypatch.setattr(attention, "_backend", lambda: "tpu")
    layers, lanes, slots, heads, dh, page = 6, 64, 64 * 1024, 8, 64, 16
    sym = tf.get_decode_symbol(
        vocab_size=32000, num_layers=layers, num_heads=heads, model_dim=512,
        ffn_dim=2048, max_len=slots, pos_len=1024, page_size=page)
    pools = ["kv_%s_%d" % (t, i) for i in range(layers) for t in "kv"]
    arg_shapes, _, _ = sym.infer_shape(
        data=(lanes, 1), pos_idx=(lanes, 1), write_slot=(lanes, 1),
        page_table=(lanes, slots // lanes // page),
        **{n: pool_shape(heads, dh, slots, page) for n in pools})
    prog = _GraphProgram(sym)
    prog.label, prog.donated = "mx_decode", tuple(pools)
    spec = lambda shape, dtype, **kw: jax.ShapeDtypeStruct(
        shape, jnp.dtype(dtype), **kw)
    specs = (tuple(spec(s, "float32") for s in arg_shapes), (),
             spec((2,), "uint32"))
    prog.store = cache_mod._StoredProgram(
        str(tmp_path), "the key", specs, "tpu")
    tm_mode = tm.current_override()
    tm.set_mode("counters")
    tm.reset()
    try:
        prog._fwd(False)                      # a miss: exports and writes
        again = _GraphProgram(sym)
        again.label, again.donated = prog.label, prog.donated
        again.store = cache_mod._StoredProgram(
            str(tmp_path), "the key", specs, "tpu")
        run = again._fwd(False)               # a hit: deserialises
        assert _counts() == (1, 1, 0)
    finally:
        tm.set_mode(tm_mode)
        tm.reset()
    (name,) = os.listdir(tmp_path)
    with open(tmp_path / name, "rb") as f:
        blob = f.read()
    assert b"tpu_custom_call" in blob     # the bytecode names it once
    on_chip = (tuple(spec(s, "float32", sharding=chip) for s in arg_shapes),
               (), spec((2,), "uint32", sharding=chip))
    compiled = run.lower(*on_chip).compile()
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == layers
    assert all("/paged_read/" in line for line in kernels)
    assert text.count(" scatter(") == 2 * layers and " while(" not in text
    assert compiled.memory_analysis().alias_size_in_bytes \
        == 2 * layers * heads * slots * dh * 4
