"""A dispatch hands its buffers on by reference (``NDArray._set_jax``'s
whole-chunk case, ``Executor.rebind``) and a program with no random node
draws no key (``Executor._next_rng``). Counts that repeat exactly and values
bit for bit; no timing.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import random as mx_random
from mxnet_tpu import telemetry
from mxnet_tpu.models import transformer as tf
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.serving import PagedKVDecoder


@pytest.fixture
def tm():
    telemetry.reset()
    saved = telemetry.current_override()
    telemetry.set_mode("counters")
    yield telemetry
    telemetry.set_mode(saved)
    telemetry.reset()


def _parents_set_jax(self, value):
    """``NDArray._set_jax`` as the parent commit spelled it: every write
    goes through ``reshape``, ``broadcast_to``, ``asarray`` and ``astype``.
    Kept here, not in the package, to hold the new spelling to it."""
    d = self._chunk.data
    if self._begin is None:
        region_shape = d.shape
        new = jnp.broadcast_to(
            value.reshape(self._shape) if hasattr(value, "reshape")
            and tuple(getattr(value, "shape", ())) == self._shape else value,
            self._shape)
        self._chunk.data = \
            jnp.asarray(new).reshape(region_shape).astype(d.dtype)
    else:
        region = d[self._begin: self._end]
        new = jnp.broadcast_to(value, self._shape) \
            .reshape(region.shape).astype(d.dtype)
        self._chunk.data = d.at[self._begin: self._end].set(new)
    return False


def _counts(tm):
    c = tm.counters()
    return c.get("executor.rebind", 0), c.get("executor.rebind_copy", 0)


# ------------------------------------------------------- the operator's side
def _target(case):
    """The argument a hand-off writes, and the NDArray that owns its chunk."""
    base = mx.nd.array(np.arange(12, dtype="f").reshape(4, 3))
    if case == "view":
        return base.slice(1, 3), base
    if case == "reshaped_whole_chunk":
        return base.reshape((3, 4)), base
    return base, base


HAND_OFFS = {
    # case: (the value handed on, taken by reference)
    "whole_chunk": (lambda: jnp.full((4, 3), 7, jnp.float32), True),
    "view": (lambda: jnp.full((2, 3), 7, jnp.float32), False),
    "reshaped_whole_chunk": (lambda: jnp.full((3, 4), 7, jnp.float32), False),
    "numpy_value": (lambda: np.full((4, 3), 7, np.float32), False),
    "other_dtype": (lambda: jnp.full((4, 3), 7, jnp.bfloat16), False),
    "broadcastable_shape": (lambda: jnp.full((1, 3), 7, jnp.float32), False),
    "weak_type": (lambda: jnp.broadcast_to(jnp.asarray(7.0), (4, 3)), False),
}


@pytest.mark.parametrize("case", sorted(HAND_OFFS))
def test_a_matching_whole_chunk_changes_hands_by_reference(tm, case):
    make, by_reference = HAND_OFFS[case]
    value = make()
    target, owner = _target(case)
    exe = mx.sym.Variable("x").bind(mx.cpu(), args={"x": target})
    exe.rebind(["x"], [value])
    assert _counts(tm) == ((1, 0) if by_reference else (0, 1))
    if by_reference:
        assert target._jax() is value
    # the parent's spelling on a twin: the same values in the same type, in
    # the argument and in the chunk it is a view of
    twin, twin_owner = _target(case)
    _parents_set_jax(twin, value)
    for got, want in ((target, twin), (owner, twin_owner)):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(got.asnumpy(), want.asnumpy())
        assert not got._jax().aval.weak_type
    # and the next forward reads what was handed on
    np.testing.assert_array_equal(exe.forward()[0].asnumpy(),
                                  target.asnumpy())


def test_the_hand_off_counts_nothing_with_telemetry_off(tm):
    tm.set_mode("off")
    x = mx.nd.zeros((2, 2))
    exe = mx.sym.Variable("x").bind(mx.cpu(), args={"x": x})
    value = jnp.ones((2, 2), jnp.float32)
    exe.rebind(["x"], [value])
    assert x._jax() is value
    assert _counts(tm) == (0, 0)


def test_a_read_only_array_still_refuses_the_hand_off():
    x = NDArray(chunk=mx.nd.zeros((2, 2))._chunk, shape=(2, 2),
                writable=False)
    with pytest.raises(mx.base.MXNetError, match="read-only"):
        x._set_jax(jnp.ones((2, 2), jnp.float32))


def _splits(monkeypatch):
    """Count the calls of ``jax.random.split`` (a draw from the stream)."""
    calls = []
    split = jax.random.split

    def counting(*args, **kwargs):
        calls.append(1)
        return split(*args, **kwargs)

    monkeypatch.setattr(jax.random, "split", counting)
    return calls


@pytest.mark.parametrize("entry", ["forward", "forward_train",
                                   "forward_backward"])
def test_a_graph_with_no_random_node_draws_no_key(monkeypatch, entry):
    mx.random.seed(11)
    x = mx.sym.Variable("x")
    net = mx.sym.FullyConnected(x, num_hidden=3, name="fc")
    exe = net.simple_bind(mx.cpu(), x=(2, 5))
    for name, arr in exe.arg_dict.items():
        arr[:] = np.random.RandomState(0).randn(*arr.shape).astype("f")
    before = np.array(mx_random._KEY)
    calls = _splits(monkeypatch)
    outs = []
    for _ in range(2):
        if entry == "forward_backward":
            outs.append(exe.forward_backward(
                out_grads=[mx.nd.ones((2, 3))])[0].asnumpy())
        else:
            outs.append(exe.forward(
                is_train=entry == "forward_train")[0].asnumpy())
    assert not calls
    np.testing.assert_array_equal(np.array(mx_random._KEY), before)
    np.testing.assert_array_equal(outs[0], outs[1])
    # the key it was given has a drawn key's shape and type: no retrace
    drawn = mx_random._next_key()
    assert (exe._last_rng.shape, exe._last_rng.dtype) == \
        (drawn.shape, drawn.dtype)
    assert exe._last_rng is mx_random._constant_key()


def test_a_graph_with_dropout_draws_as_before(monkeypatch):
    x = mx.sym.Variable("x")
    net = mx.sym.Dropout(x, p=0.5, name="drop")
    exe = net.simple_bind(mx.cpu(), grad_req="null", x=(8, 16))
    exe.arg_dict["x"][:] = np.ones((8, 16), "f")
    calls = _splits(monkeypatch)

    def two_forwards():
        mx.random.seed(5)
        return [exe.forward(is_train=True)[0].asnumpy() for _ in range(2)]

    first, second = two_forwards()
    assert len(calls) == 2                       # one draw a forward
    assert not np.array_equal(first, second)
    # the parent's draw: the stream split once a forward, the node's key
    # folded in at its index
    key = jax.random.PRNGKey(5)
    for got in (first, second):
        key, sub = jax.random.split(key)
        assert exe._last_rng is not mx_random._constant_key()
        want = exe._prog._fwd(True)(
            (jnp.ones((8, 16), jnp.float32),), (), sub)[0][0]
        np.testing.assert_array_equal(got, np.asarray(want))
    again = two_forwards()
    np.testing.assert_array_equal(first, again[0])
    np.testing.assert_array_equal(second, again[1])


# -------------------------------------------------------- the decoder's side
ARCHS = {
    "vaswani": dict(vocab_size=50, num_layers=2, num_heads=2, model_dim=32,
                    ffn_dim=64),
    "olmoe": dict(arch="olmoe", vocab_size=60, num_layers=2, num_heads=4,
                  head_dim=8, model_dim=32, ffn_dim=16, num_experts=4,
                  num_experts_per_tok=2, rope_theta=10000.0, rms_eps=1e-5),
    "granite_hybrid": dict(
        arch="granite_hybrid", vocab_size=60, num_layers=3, num_heads=4,
        num_kv_heads=2, head_dim=8, model_dim=32, ffn_dim=48,
        layer_types=["mamba", "attention", "mamba"], mamba_heads=4,
        mamba_head_dim=8, mamba_state=8, mamba_conv=4, mamba_chunk=8,
        embedding_multiplier=12.0, attention_multiplier=0.125,
        residual_multiplier=0.22, logits_scaling=8.0, rms_eps=1e-5),
    # rows, rings AND one pool that two layers read (layers 5 and 7 of 8)
    "phi4flash": dict(
        arch="phi4flash", vocab_size=60, num_layers=8, num_heads=4,
        num_kv_heads=2, head_dim=8, model_dim=32, ffn_dim=48,
        sliding_window=4, mamba_state=4, mamba_dt_rank=3),
}
S, PAGE, LANES, PREFILL = 32, 4, 3, 16


def _params(arch):
    cfg = ARCHS[arch]
    rs = np.random.RandomState(0)
    if arch == "vaswani":
        exe = tf.get_symbol(seq_len=S, **cfg).simple_bind(
            mx.cpu(), grad_req="null", data=(1, S), softmax_label=(1, S))
        shapes = {n: a.shape for n, a in exe.arg_dict.items()
                  if n not in ("data", "softmax_label")}
    else:
        shapes = tf.param_shapes(**cfg)
    out = {}
    for name, shape in sorted(shapes.items()):
        if name.endswith(("gamma", "_D")):
            v = np.ones(shape)
        elif name.endswith("A_log"):
            v = np.log(rs.uniform(1, 16, shape))
        elif name.endswith("dt_bias"):
            v = np.log(np.expm1(rs.uniform(1e-3, 1e-1, shape)))
        elif "_conv_" in name:
            v = rs.uniform(-0.5, 0.5, shape)
        else:
            v = rs.randn(*shape) * 0.1
        out[name] = v.astype("float32")
    return out


def _decoder(arch, **kw):
    serve = dict(max_len=S, page_size=PAGE, lanes=LANES, prefill_len=PREFILL)
    if arch == "vaswani":
        serve["pos_len"] = S
    else:
        serve["dtype"] = "float32"
    return PagedKVDecoder(_params(arch), **{**serve, **kw}, **ARCHS[arch])


PROMPTS = ([3, 1, 4, 1, 5, 9], [2, 7, 1])


def _admit_two(dec):
    nxt = {}
    for prompt in PROMPTS:
        seq, logits = dec.admit(np.asarray(prompt, np.float32))
        nxt[seq] = int(np.argmax(logits))
    return nxt


@pytest.mark.parametrize("arch,what", [
    ("vaswani", "step"), ("vaswani", "admit"), ("vaswani", "step_megastep"),
    ("vaswani", "chunked_admit"), ("vaswani", "copy_on_write"),
    ("olmoe", "step"), ("olmoe", "admit"),
    ("granite_hybrid", "step"), ("granite_hybrid", "admit"),
    ("phi4flash", "step"), ("phi4flash", "admit")])
def test_the_decoder_hands_every_buffer_on_by_reference(tm, arch, what):
    """A steady step: the cache's buffers and the ONE staged input; an
    admission: the cache's buffers and the prefill's two (the bucket and the
    length, one transfer); a megastep and a chunk: the cache's buffers (their
    inputs are the program's own arguments, not the executable's); a copied
    page: the pools. Never a copy."""
    chunked = what == "chunked_admit"
    dec = _decoder(arch, **(dict(prefix_cache=True, prefix_chunk=4)
                            if chunked else {})).warmup()
    cache = len(dec._cache_names)
    assert cache == {"vaswani": 4, "olmoe": 4, "granite_hybrid": 6,
                     "phi4flash": 12}[arch]
    if what in ("admit", "chunked_admit"):
        per_call = [cache * -(-len(p) // 4) if chunked else cache + 2
                    for p in PROMPTS]
        calls = [lambda p=p: dec.admit(np.asarray(p, np.float32))
                 for p in PROMPTS]
    elif what == "copy_on_write":
        seq, logits = dec.admit(np.asarray(PROMPTS[0], np.float32))
        twin = dec.fork(seq)                    # position 6: mid-page
        per_call = [len(dec._pool_names) + cache + 1]
        calls = [lambda: dec.step({twin: int(np.argmax(logits))})]
    else:
        nxt = _admit_two(dec)
        if what == "step":
            per_call = [cache + 1] * 3
            calls = [lambda: nxt.update(
                {s: int(np.argmax(l)) for s, l in dec.step(nxt).items()})] * 3
        else:
            per_call = [cache] * 2
            calls = [lambda: nxt.update(
                {s: int(t[-1]) for s, t in
                 dec.step_megastep(nxt, k=2).items()})] * 2
    for call, want in zip(calls, per_call):
        before = _counts(tm)
        call()
        after = _counts(tm)
        assert (after[0] - before[0], after[1] - before[1]) == (want, 0)
    if what == "copy_on_write":
        assert tm.counters()["serving.cow_copies"] == 1
    assert tm.counters().get("executor.retrace", 0) == 0


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_logits_are_bitwise_the_parents_spelling(monkeypatch, arch):
    """Admit + 8 steps (and, where the arch has it, two megasteps) with the
    hand-offs by reference against the same run with every hand-off through
    the parent's ``_set_jax``: the same logits, the same cache."""
    def run():
        dec = _decoder(arch).warmup()
        rows, nxt = [], {}
        for prompt in PROMPTS:
            seq, logits = dec.admit(np.asarray(prompt, np.float32))
            rows.append(logits)
            nxt[seq] = int(np.argmax(logits))
        for _ in range(8):
            out = dec.step(nxt)
            rows.extend(out[s] for s in sorted(out))
            nxt = {s: int(np.argmax(l)) for s, l in out.items()}
        if arch == "vaswani":
            for _ in range(2):
                toks = dec.step_megastep(nxt, k=2)
                rows.extend(toks[s] for s in sorted(toks))
                nxt = {s: int(t[-1]) for s, t in toks.items()}
        cache = [np.array(dec._dec_exe.arg_dict[n]._jax())
                 for n in dec._cache_names]
        return rows, cache

    rows, cache = run()
    monkeypatch.setattr(NDArray, "_set_jax", _parents_set_jax)
    want_rows, want_cache = run()
    assert len(rows) == len(want_rows) == 2 + 16 + (4 if arch == "vaswani"
                                                    else 0)
    for got, want in zip(rows + cache, want_rows + want_cache):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)
