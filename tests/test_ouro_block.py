"""``arch="ouro"``: ONE stack of layers applied ``total_ut_steps`` times over
the same weights, every pass with keys and values of its own, served through
``PagedKVDecoder`` (CPU, tiny sizes, float32, seeded weights):

(a) admit + decode equals the plain reference's full forward, logits to 1e-4,
    at three prompt lengths, lanes multiplexed equal to sequential;
(b) ``total_ut_steps=1`` equals a plain one-pass stack of the same weights;
(c) ``param_shapes`` has one entry a LAYER and the published widths give
    2,667,974,657;
(d) pass separation: the reference with pass u reading pass u - 1's keys
    differs from the program by more than 100 x (a)'s tolerance;
(e) the exit rule: a gate that saturates at once under a threshold of 0.5
    heads pass 1, the published threshold 1 heads the last pass, program and
    reference agree and the ``serving.loop.*`` counters say so;
(f) a retire returns the pages of every pass, an admission past the pool
    raises ``PagedKVExhausted`` with nothing leaked; a fork copies the page
    of every pass.
"""
import math
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import transformer as tf
from mxnet_tpu.serving import PagedKVDecoder
from mxnet_tpu.serving.kv_decode import PagedKVExhausted

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from harness.spec import load_module  # noqa: E402

ref = load_module("reference", "ouro_decoder")

TOL = 1e-4
SIZES = dict(arch="ouro", vocab_size=256, num_layers=3, num_heads=4,
             head_dim=16, model_dim=64, ffn_dim=128, total_ut_steps=3,
             early_exit_threshold=1.0, rope_theta=1e6, rms_eps=1e-6)
SERVING = dict(max_len=32, prefill_len=16, page_size=8, lanes=4)


def _params(cfg, seed=0, gate_bias=0.0):
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape in sorted(tf.param_shapes(**cfg).items()):
        if name.endswith("_gamma"):     # not 1: a norm left out is then seen
            out[name] = rng.uniform(0.5, 1.5, shape).astype(np.float32)
        elif name == "exit_gate_bias":
            out[name] = np.full(shape, gate_bias, np.float32)
        else:
            out[name] = rng.normal(0, 0.08, shape).astype(np.float32)
    return out


def _decoder(params, cfg, **serving):
    return PagedKVDecoder({k: mx.nd.array(v) for k, v in params.items()},
                          **dict(SERVING, **serving), **cfg)


def _generate(dec, prompt, steps, fed):
    """(seq, [logits row] of the admission and ``steps`` steps fed ``fed``)."""
    seq, logits = dec.admit(np.asarray(prompt, np.float32))
    rows = [np.asarray(logits)]
    for tok in fed[:steps]:
        rows.append(np.asarray(dec.step({seq: int(tok)})[seq]))
    return seq, np.stack(rows)


def _reference(params, tokens, cfg, **kw):
    return np.asarray(ref.logits({k: jnp.asarray(v) for k, v in
                                  params.items()},
                                 jnp.asarray(tokens), cfg, **kw))


@pytest.fixture(scope="module")
def served():
    """One decoder, three prompts of 5, 8 and 16 tokens with 6 steps each,
    sequentially: {length: (tokens, rows)}."""
    params = _params(SIZES)
    dec = _decoder(params, SIZES)
    rng = np.random.default_rng(7)
    out = {}
    for length in (5, 8, 16):
        toks = rng.integers(1, 256, length + 6)
        seq, rows = _generate(dec, toks[:length], 6, toks[length:])
        dec.retire(seq)
        out[length] = (toks, rows)
    return params, dec, out


@pytest.mark.parametrize("length", [5, 8, 16])
def test_admit_and_decode_equal_the_references_full_forward(served, length):
    """(a): the admission's row and six steps through the cache, each against
    the reference's row at the same position. 16 tokens fill the bucket and
    the steps cross into a third page of every pass's piece of a pool."""
    params, _, runs = served
    toks, rows = runs[length]
    want = _reference(params, toks, SIZES)[length - 1:]
    assert rows.shape == want.shape == (7, 256)
    assert np.abs(rows - want).max() < TOL
    assert np.abs(want).max() > 0.1


def test_lanes_multiplexed_equal_sequential(served):
    """(a): the three sequences admitted together and stepped in one batch,
    at their own positions, give what each gave alone."""
    _, dec, runs = served
    seqs, rows = {}, {}
    for length, (toks, _) in runs.items():
        seqs[length], logits = dec.admit(toks[:length].astype(np.float32))
        rows[length] = [np.asarray(logits)]
    for step in range(6):
        out = dec.step({seqs[n]: int(runs[n][0][n + step]) for n in runs})
        for n in runs:
            rows[n].append(np.asarray(out[seqs[n]]))
    for n in runs:
        assert np.abs(np.stack(rows[n]) - runs[n][1]).max() < 1e-5, n
        dec.retire(seqs[n])
    assert dec.stats()["pages_in_use"] == 0


def test_one_pass_is_a_plain_stack_of_the_same_weights():
    """(b): ``total_ut_steps=1`` against a stack written here with no loop:
    the layers once, the final norm, the head; no gate is read."""
    cfg = dict(SIZES, total_ut_steps=1)
    params = _params(cfg, seed=3)
    toks = np.random.default_rng(5).integers(1, 256, 14)
    dec = _decoder(params, cfg)
    seq, rows = _generate(dec, toks[:8], 6, toks[8:])
    assert dec._passes == 1
    p = {k: jnp.asarray(v) for k, v in params.items()}
    x = p["embed_weight"][toks]
    for i in range(3):
        w = {n: p["layer%d_%s" % (i, n)] for n in ref._LAYER_WEIGHTS}
        x, _, _ = ref.layer(x, w, None, heads=4, dh=16, eps=1e-6, theta=1e6)
    want = np.asarray(ref.rms_norm(x, p["final_ln_gamma"], 1e-6)
                      @ p["lm_head_weight"].T)[7:]
    assert np.abs(rows - want).max() < TOL
    # and the looped model at three passes is another function
    assert np.abs(_reference(params, toks, SIZES)[7:] - want).max() > 100 * TOL


def test_param_shapes_has_one_entry_a_layer():
    """(c): 192 layer applications over 48 layers of weights."""
    few = tf.param_shapes(**SIZES)
    assert sorted(n for n in few if not n.startswith("layer")) == [
        "embed_weight", "exit_gate_bias", "exit_gate_weight",
        "final_ln_gamma", "lm_head_weight"]
    assert len([n for n in few if n.startswith("layer")]) == 3 * 8
    assert few == tf.param_shapes(**dict(SIZES, total_ut_steps=1))
    full = tf.param_shapes("ouro", 49152, 48, 16, 2048, 5632, head_dim=128,
                           total_ut_steps=4)
    assert len(full) == 48 * 8 + 5
    assert sum(math.prod(s) for s in full.values()) == 2_667_974_657
    # both serving graphs take exactly these arguments beside their inputs
    for sym, fed in (
            (tf.get_prefill_symbol(prefill_len=16, **SIZES),
             {"data", "length"}),
            (tf.get_decode_symbol(max_len=128, page_size=8, **SIZES),
             {"data", "pos_idx", "write_slot", "page_table"}
             | {"kv_%s_%d" % (t, i) for t in "kv" for i in range(3)})):
        args = sym.list_arguments()
        assert len(args) == len(set(args))
        assert set(args) - fed == set(few)
    assert tf.loop_passes(**SIZES) == 3
    assert tf.loop_passes("olmoe", num_heads=4, model_dim=64, ffn_dim=8) == 1
    assert [(n, k) for n, k, _ in tf.decode_cache(**SIZES)] == [
        ("kv_%s_%d" % (t, i), "pool") for i in range(3) for t in "kv"]
    with pytest.raises(MXNetError, match="total_ut_steps"):
        tf.param_shapes(**dict(SIZES, total_ut_steps=0))


def test_a_pass_reads_its_own_keys_and_no_other_passes(served):
    """(d): the reference with pass u attending pass u - 1's keys and values
    (one offset wrong) is far from what the program computes; so is the
    reference without the final norm between passes. And the pool holds the
    passes apart: a lane's keys of pass 0 and pass 1 differ."""
    params, dec, runs = served
    toks, rows = runs[8]
    for fault in ("previous_pass_keys", "no_norm_between_passes"):
        wrong = _reference(params, toks, SIZES, fault=fault)[7:]
        assert np.abs(rows - wrong).max() > 100 * TOL, fault
    with pytest.raises(ValueError, match="unknown fault"):
        _reference(params, toks, SIZES, fault="nothing")
    seq, _ = _generate(dec, toks[:8], 3, toks[8:])
    keys = np.asarray(dec.lane_state(seq, ("kv_k_1",))["kv_k_1"])
    assert keys.shape == (3, 4, 11, 16)     # passes, heads, positions, d
    assert np.abs(keys[0] - keys[1]).max() > 0.01
    assert np.abs(keys[1] - keys[2]).max() > 0.01
    dec.retire(seq)


@pytest.mark.parametrize("threshold,bias,want", [(0.5, 30.0, 1),
                                                 (1.0, 0.0, 3),
                                                 (0.5, -30.0, 3)])
def test_the_exit_rule_chooses_the_pass_that_feeds_the_head(threshold, bias,
                                                            want):
    """(e): a bias of +30 saturates the first gate, lambda_1 = 1 >= 0.5, and
    the logits are ``W_head h_1``; the published threshold 1 is never reached
    and the last pass feeds the head, as under a gate that never opens. The
    program and the reference agree, and the counters carry the pass."""
    cfg = dict(SIZES, early_exit_threshold=threshold)
    params = _params(cfg, seed=11, gate_bias=bias)
    toks = np.random.default_rng(13).integers(1, 256, 12)
    telemetry.reset()
    saved = telemetry.current_override()
    telemetry.set_mode("counters")
    try:
        dec = _decoder(params, cfg).warmup()
        warm = telemetry.counter("serving.loop.passes").value
        _, rows = _generate(dec, toks[:8], 4, toks[8:])
        counted = {name: telemetry.counter(name).value for name in (
            "serving.loop.passes", "serving.loop.exit_tokens",
            "serving.loop.exit_pass_sum")}
    finally:
        telemetry.set_mode(saved)
        telemetry.reset()
    assert warm == 0    # the warm dispatches head nobody's token
    p = {k: jnp.asarray(v) for k, v in params.items()}
    hidden, gates = ref.passes(p, jnp.asarray(toks), cfg)
    assert np.asarray(ref.exit_pass(gates, threshold)).tolist() == [want] * 12
    head = np.asarray(hidden[want - 1] @ p["lm_head_weight"].T)[7:]
    assert np.abs(rows - head).max() < TOL
    assert np.abs(_reference(params, toks, cfg)[7:] - head).max() < 1e-6
    other = np.asarray(hidden[want % 3] @ p["lm_head_weight"].T)[7:]
    assert np.abs(rows - other).max() > 100 * TOL
    # an admission and four steps: five tokens headed, three passes each run
    assert counted == {"serving.loop.passes": 15,
                       "serving.loop.exit_tokens": 5,
                       "serving.loop.exit_pass_sum": 5 * want}
    np.testing.assert_allclose(ref.first_pass_hidden(p, jnp.asarray(toks),
                                                     cfg), hidden[0])
    np.testing.assert_allclose(ref.gates(p, jnp.asarray(toks), cfg), gates)


def test_a_page_stands_for_every_pass_and_the_pool_runs_out_cleanly():
    """(f): 2 lanes x 32 slots are 8 pages; a prompt of 16 takes two, whatever
    the passes. Under a budget of 3 pages a second admission is refused with
    ``PagedKVExhausted`` and leaves nothing behind; a retire returns the
    lane's pages of every pass at once, and the next admission reuses them
    and computes what the first did."""
    params = _params(SIZES, seed=17)
    dec = _decoder(params, SIZES, lanes=2, page_budget=3)
    assert dec._decode_shapes()["kv_k_0"] == (4, 3 * 2 * 32, 16)
    toks = np.random.default_rng(19).integers(1, 256, 32)
    seq, rows = _generate(dec, toks[:16], 1, toks[16:])
    assert dec.stats()["pages_in_use"] == 3 and "once" in \
        PagedKVDecoder.stats.__doc__.lower()
    with pytest.raises(PagedKVExhausted):
        dec.admit(toks[:9].astype(np.float32))
    assert dec.stats() == {"lanes": 2, "active": 1, "pages_in_use": 3,
                           "page_budget": 3, "page_size": 8}
    with pytest.raises(PagedKVExhausted):   # a step that needs a fourth page
        for tok in toks[17:]:
            dec.step({seq: int(tok)})
    dec.retire(seq)
    assert dec.stats()["pages_in_use"] == 0 and dec.active == []
    seq, again = _generate(dec, toks[:16], 1, toks[16:])
    assert np.array_equal(again, rows)
    dec.retire(seq)
    for what, call in (("chunk", lambda: dec.verify_chunk(0, [1, 2])),
                       ("megastep", lambda: dec.step_megastep({0: 1}, k=2))):
        with pytest.raises(MXNetError, match="not built for arch 'ouro'"):
            call()
    with pytest.raises(MXNetError, match="not built for arch 'ouro'"):
        _decoder(params, SIZES, prefix_cache=True)


def test_a_fork_copies_the_page_of_every_pass():
    """(f): a forked lane shares its pages until it writes; the private copy
    it then takes holds every pass's slots, so both lanes go on as one
    sequence would."""
    params = _params(SIZES, seed=23)
    dec = _decoder(params, SIZES)
    toks = np.random.default_rng(29).integers(1, 256, 14)
    seq, rows = _generate(dec, toks[:10], 0, [])
    twin = dec.fork(seq)
    got = {seq: [], twin: []}
    for tok in toks[10:]:
        out = dec.step({seq: int(tok), twin: int(tok)})
        for s in got:
            got[s].append(np.asarray(out[s]))
    want = _reference(params, toks, SIZES)[10:]
    for s in got:
        assert np.abs(np.stack(got[s]) - want).max() < TOL
    dec.retire(seq)
    dec.retire(twin)
    assert dec.stats()["pages_in_use"] == 0
