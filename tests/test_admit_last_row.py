"""An admission's head runs over the prompt's last real row alone: every
prefill graph gathers row ``length - 1`` of its last layer's output by a
``length`` it takes as data, so one row of logits leaves the program and
``admit`` reads it with no program behind the prefill. Each architecture is
held to its own block test's reference, weights, sizes and tolerance."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import telemetry

import test_deepseek_v3_block
import test_granite_hybrid_block
import test_kv_decode
import test_lfm2_moe_block
import test_mimo_v2_flash_block
import test_olmoe_block

BLOCKS = {"olmoe": test_olmoe_block,
          "granite_hybrid": test_granite_hybrid_block,
          "deepseek_v3": test_deepseek_v3_block,
          "lfm2_moe": test_lfm2_moe_block,
          "mimo_v2_flash": test_mimo_v2_flash_block}
ARCHS = ["vaswani"] + list(BLOCKS)
VASWANI_LEN = 32    # the trained position table, and the training symbol's


def _vaswani_case():
    """(decoder, the all-positions forward, vocabulary): ``get_symbol``'s
    training graph over the same weights, whose head is a SoftmaxOutput."""
    cfg = test_kv_decode.CFG
    _, exe, params = test_kv_decode._trained_params(VASWANI_LEN)
    dec = test_kv_decode._paged(params, VASWANI_LEN, lanes=2, prefill_len=16)

    def rows(tokens):
        pad = np.zeros((1, VASWANI_LEN), np.float32)
        pad[0, :len(tokens)] = tokens
        exe.arg_dict["data"][:] = pad
        exe.forward(is_train=False)
        return exe.outputs[0].asnumpy()[:len(tokens)]

    def agree(got, want):
        # the training head is a SoftmaxOutput: compare post-softmax, at
        # test_kv_decode's tolerance for the same comparison
        p = np.exp(got - got.max())
        np.testing.assert_allclose(p / p.sum(), want, rtol=1e-4, atol=1e-5)

    return dec, rows, agree, cfg["vocab_size"]


def _block_case(block):
    params = block._weights("float32")
    dec = block._decoder(params, "float32")

    def rows(tokens):
        return np.asarray(block.ref.logits(
            params, jnp.asarray(tokens, jnp.int32), block.CFG))

    def agree(got, want):
        assert block._rel_l2(got, want) < block.F32_TOL

    return dec, rows, agree, block.CFG["vocab_size"]


@pytest.fixture(scope="module")
def case():
    """One warmed decoder an architecture for the whole module."""
    built = {}

    def get(arch):
        if arch not in built:
            made = _vaswani_case() if arch == "vaswani" \
                else _block_case(BLOCKS[arch])
            made[0].warmup()
            built[arch] = made
        return built[arch]

    return get


@pytest.fixture
def compiles():
    """Every XLA compile request of this process, op-by-op programs among
    them (``jax.monitoring``, as the benchmark counts them)."""
    seen = []

    def note(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            seen.append(secs)

    jax.monitoring.register_event_duration_secs_listener(note)
    yield seen
    jax.monitoring.unregister_event_duration_listener(note)


@pytest.mark.parametrize("length", ["1", "middle", "prefill_len"])
@pytest.mark.parametrize("arch", ARCHS)
def test_admit_hands_out_the_prompts_last_real_row(case, arch, length):
    """The bound prefill's first output is ``(1, vocab)``; the row ``admit``
    returns is row ``L - 1`` of the all-positions forward; and what stands
    in the bucket behind the prompt does not reach it, bit for bit."""
    dec, rows, agree, vocab = case(arch)
    P = dec.prefill_len
    L = {"1": 1, "middle": P // 2 - 1, "prefill_len": P}[length]
    prompt = np.random.RandomState(L).randint(1, vocab, L)
    assert "length" in dec._pf_cache.input_names
    seq, got = dec.admit(prompt.astype(np.float32))
    dec.retire(seq)
    pf = dec._pf_cache.executable(dec._prefill_shapes())
    assert pf.outputs[0].shape == (1, vocab) == (dec._head_rows, vocab)
    assert got.shape == (vocab,) and got.dtype == np.float32
    agree(got, rows(prompt)[L - 1])
    # the same prompt, the rest of the bucket filled with other token ids
    other = np.full((1, P), 41, np.float32)
    other[0, :L] = prompt
    pf.rebind(["data", "length"], jax.device_put(
        [other, np.full((1, 1), L, np.float32)]))
    pf.forward(is_train=False)
    assert np.array_equal(pf.outputs[0].asnumpy()[0], got)


def test_admissions_of_new_lengths_build_no_program(compiles):
    """After ``warmup()`` five admissions of prompt lengths never seen
    before compile nothing, op by op or otherwise: the length is data to the
    prefill and to the pool update, and the one row is read as it is. The
    head's rows are counted beside the admissions: one each."""
    saved = telemetry.current_override()
    telemetry.set_mode("counters")
    try:
        dec, _, _, vocab = _vaswani_case()
        dec.warmup()
        before, requests = dict(telemetry.counters()), len(compiles)
        for L in (2, 5, 7, 11, 13):
            seq, row = dec.admit(np.arange(1, L + 1, dtype=np.float32))
            assert row.shape == (vocab,)
            dec.retire(seq)
        now = telemetry.counters()
        moved = lambda name: now.get(name, 0) - before.get(name, 0)
        assert len(compiles) == requests
        assert moved("executor.compile") == moved("executor.retrace") == 0
        assert moved("serving.paged_admits") == 5
        assert moved("serving.admit_head_rows") == 5
    finally:
        telemetry.set_mode(saved)
