"""Decode megasteps (mxnet_tpu/serving/kv_decode.py step_megastep,
docs/SERVING.md §Megasteps): K tokens per dispatch through one lax.scan
program. Gates: token-identical parity with single-step greedy, seeded
top-k reproducibility across K partitionings, EOS early-exit lanes write
NOTHING (KV bitwise-unchanged past eos), and pre-acquire backpressure."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.serving import PagedKVDecoder, PagedKVExhausted
from mxnet_tpu.serving.kv_decode import decode_megastep_k

CFG = dict(vocab_size=50, num_layers=2, num_heads=2, model_dim=32,
           ffn_dim=64)


@pytest.fixture
def tm():
    telemetry.reset()
    telemetry.clear_events()
    saved = telemetry.current_override()
    yield telemetry
    telemetry.set_mode(saved)
    telemetry.reset()
    telemetry.clear_events()


def _params(S, seed=0):
    net = tfm.get_symbol(seq_len=S, **CFG)
    exe = net.simple_bind(mx.cpu(), grad_req="null", data=(1, S),
                          softmax_label=(1, S))
    rs = np.random.RandomState(seed)
    params = {}
    for name, arr in exe.arg_dict.items():
        if name in ("data", "softmax_label"):
            continue
        w = (rs.randn(*arr.shape) * 0.1).astype("float32")
        arr[:] = w
        params[name] = w
    return params


def _decoder(params, S, B, page_size=4, **kw):
    return PagedKVDecoder(params, max_len=S, page_size=page_size, lanes=B,
                          prefill_len=8, pos_len=S, **CFG, **kw)


def _prompt(B, seed=3, L=4):
    rs = np.random.RandomState(seed)
    return rs.randint(1, CFG["vocab_size"], (B, L)).astype(np.float32)


def _admit(dec, prompt):
    """One lane per row of ``prompt``: (seq ids, each one's first token)."""
    sids, tok = [], []
    for row in prompt:
        sid, logits = dec.admit(row)
        sids.append(sid)
        tok.append(int(np.argmax(logits)))
    return sids, np.asarray(tok)


def _megastep(dec, sids, tok, **kw):
    """``step_megastep`` of every lane as one (B, K) array."""
    out = dec.step_megastep(dict(zip(sids, tok)), **kw)
    return np.stack([out[sid] for sid in sids])


# ------------------------------------------------------------------ knobs
def test_megastep_k_env(monkeypatch):
    monkeypatch.delenv("MXNET_DECODE_MEGASTEP_K", raising=False)
    assert decode_megastep_k() == 1
    monkeypatch.setenv("MXNET_DECODE_MEGASTEP_K", "8")
    assert decode_megastep_k() == 8
    monkeypatch.setenv("MXNET_DECODE_MEGASTEP_K", "junk")
    assert decode_megastep_k() == 1
    monkeypatch.setenv("MXNET_DECODE_MEGASTEP_K", "0")
    assert decode_megastep_k() == 1


# ----------------------------------------------------------------- parity
def test_megastep_env_default_drives_greedy(tm, monkeypatch):
    tm.set_mode("counters")
    S, B, n = 32, 2, 9
    params = _params(S)
    prompt = _prompt(B)
    base = _decoder(params, S, B).greedy(list(prompt), n, k=1)
    monkeypatch.setenv("MXNET_DECODE_MEGASTEP_K", "4")
    got = _decoder(params, S, B).greedy(list(prompt), n)
    np.testing.assert_array_equal(base, got)


def test_megastep_zero_retrace_and_sealed(tm):
    """Repeated megasteps replay ONE compiled program (cache-hit path);
    a K change is a different sealed program, and a shape drift raises
    instead of retracing."""
    tm.set_mode("counters")
    S, B, K = 32, 2, 4
    params = _params(S)
    dec = _decoder(params, S, B)
    sids, tok = _admit(dec, _prompt(B))
    chunk = _megastep(dec, sids, tok, k=K)  # compiles + seals here
    c0 = tm.counters()
    for _ in range(3):
        chunk = _megastep(dec, sids, chunk[:, -1], k=K)
    c1 = tm.counters()
    assert c1.get("executor.retrace", 0) == c0.get("executor.retrace", 0)
    assert c1.get("executor.compile", 0) == c0.get("executor.compile", 0)
    assert c1.get("executor.cache_hit", 0) >= c0.get("executor.cache_hit", 0) + 3


def test_megastep_counters_and_gauge(tm):
    tm.set_mode("counters")
    S, B, K = 32, 2, 4
    dec = _decoder(_params(S), S, B)
    sids, tok = _admit(dec, _prompt(B))
    _megastep(dec, sids, tok, k=K)
    c = tm.counters()
    assert c.get("serving.megasteps", 0) == 1
    assert c.get("serving.decode_tokens", 0) >= B * K
    assert tm.gauge("decode.tokens_per_dispatch").value == B * K


def test_megastep_position_budget_raises():
    S, B = 16, 1
    dec = _decoder(_params(S), S, B)
    sids, tok = _admit(dec, _prompt(B, L=4))
    with pytest.raises(MXNetError):
        _megastep(dec, sids, tok, k=S)  # pos 4 + 16 > pos_len 16


# --------------------------------------------------------------- sampling
def test_topk_sampling_reproducible_across_k(tm):
    """Seeded top-k draws key off (seed, absolute position, lane), so one
    K=4 megastep must emit the exact tokens of two K=2 megasteps."""
    tm.set_mode("counters")
    S, B = 32, 2
    params = _params(S)
    prompt = _prompt(B)
    kw = dict(sample="topk", temperature=0.8, top_k=5)

    d4 = _decoder(params, S, B, sample_seed=11)
    sids, tok = _admit(d4, prompt)
    full = _megastep(d4, sids, tok, k=4, **kw)

    d2 = _decoder(params, S, B, sample_seed=11)
    sids, tok = _admit(d2, prompt)
    a = _megastep(d2, sids, tok, k=2, **kw)
    b = _megastep(d2, sids, a[:, -1], k=2, **kw)
    np.testing.assert_array_equal(full, np.concatenate([a, b], axis=1))


# ------------------------------------------------------------- early exit
def test_eos_early_exit_writes_nothing(tm):
    """Once a lane emits eos mid-megastep its later scan steps must write
    NOTHING: the KV slots past the eos step stay bitwise what they were
    before the dispatch, and the lane's remaining outputs are eos filler.
    The other lane keeps decoding normally."""
    tm.set_mode("counters")
    S, B, K = 32, 2, 6
    params = _params(S)
    prompt = _prompt(B)
    # seeded top-k: deterministic like greedy but token-diverse (random
    # weights make greedy collapse to one repeated id, which would leave
    # no usable eos candidate); the eos/done latch is sampler-independent
    kw = dict(sample="topk", temperature=1.5, top_k=10)

    probe_dec = _decoder(params, S, B, sample_seed=23)
    sids, tok0 = _admit(probe_dec, prompt)
    probe = _megastep(probe_dec, sids, tok0, k=K, **kw)  # (B, K) eos-free

    # an eos candidate lane 0 emits mid-megastep, not emitted earlier by
    # lane 0 and never emitted by lane 1 (keeps lane 1 assertions exact)
    j = eos = None
    for cand_j in range(1, K - 1):
        cand = int(probe[0, cand_j])
        if cand not in probe[0, :cand_j] and cand not in probe[1]:
            j, eos = cand_j, cand
            break
    assert eos is not None, "no usable eos candidate in %r" % probe

    dec = _decoder(params, S, B, sample_seed=23)
    sids, tok0 = _admit(dec, prompt)
    p = dec.position(sids[0])
    kv_names = [n for n in dec._dec_exe.arg_dict
                if n.startswith(("kv_k_", "kv_v_"))]
    before = {n: np.asarray(dec._dec_exe.arg_dict[n]._jax()).copy()
              for n in kv_names}
    out = _megastep(dec, sids, tok0, k=K, eos_id=eos, **kw)

    # lane 0: tokens up to and including eos match the eos-free run, the
    # rest is eos filler
    np.testing.assert_array_equal(out[0, :j + 1], probe[0, :j + 1])
    assert (out[0, j + 1:] == eos).all()
    # lane 1 never hit eos: identical to the eos-free run
    np.testing.assert_array_equal(out[1], probe[1])

    after = {n: np.asarray(dec._dec_exe.arg_dict[n]._jax())
             for n in kv_names}
    # step t writes the slot of position p+t for its INPUT token; the eos
    # EMITTED at step j latches done, so steps j+1.. write nothing for
    # lane 0, which stays at the position of its last write
    assert dec.position(sids[0]) == p + j + 1
    assert dec.position(sids[1]) == p + K
    # the slots of all K positions were acquired up front, for both lanes
    slots = [dec._lane_slots(dec._lanes[dec._seq_lane[sid]], upto=p + K)
             for sid in sids]
    dead = [slots[0][p + t] for t in range(j + 1, K)]
    live = [slots[0][p + t] for t in range(0, j + 1)]
    other = [slots[1][p + t] for t in range(j + 1, K)]
    for n in kv_names:
        np.testing.assert_array_equal(
            after[n][:, dead, :], before[n][:, dead, :],
            err_msg="%s: EOS'd lane wrote past its eos step" % n)
        # sanity: the pre-eos slots DID get written
        assert not np.array_equal(after[n][:, live, :],
                                  before[n][:, live, :])
        # lane 1 wrote all K slots
        assert not np.array_equal(after[n][:, other, :],
                                  before[n][:, other, :])


# ------------------------------------------------------------------ paged
@pytest.mark.parametrize("case", ["lockstep", "staggered"])
def test_paged_megastep_parity_with_page_crossing(tm, case):
    """The acceptance gate: K-chunked greedy == single-step greedy, token
    for token (the scan body IS the single-step math), with page_size 4
    and enough tokens that every lane crosses a page boundary mid-run.
    ``lockstep``: 2 prompts of one length, 17 tokens; ``staggered``: 3
    prompts of 2, 3 and 4 tokens, so three positions in every dispatch."""
    tm.set_mode("counters")
    S = 32
    params = _params(S)
    if case == "lockstep":
        n_streams, n, prompts = 2, 17, list(_prompt(2))
    else:
        n_streams, n = 3, 13
        rs = np.random.RandomState(9)
        prompts = [rs.randint(1, CFG["vocab_size"],
                              (2 + i,)).astype(np.float32)
                   for i in range(n_streams)]
    seq = _decoder(params, S, n_streams).greedy(prompts, n, k=1)
    mega = _decoder(params, S, n_streams).greedy(prompts, n, k=4)
    for a, b in zip(seq, mega):
        np.testing.assert_array_equal(a, b)


def test_paged_megastep_backpressure_before_dispatch(tm):
    """Pool exhaustion mid-pre-acquire raises PagedKVExhausted BEFORE any
    device work: lane positions and KV are untouched, and after a retire
    frees frames the same megastep succeeds."""
    tm.set_mode("counters")
    S = 16
    params = _params(S)
    dec = _decoder(params, S, 2, page_size=2, page_budget=5)
    rs = np.random.RandomState(1)
    pa = rs.randint(1, CFG["vocab_size"], (3,)).astype(np.float32)
    pb = rs.randint(1, CFG["vocab_size"], (3,)).astype(np.float32)
    sa, la = dec.admit(pa)   # positions 0..2 -> 2 frames
    sb, lb = dec.admit(pb)   # 2 more frames; 1 of 5 left
    tok_a = int(np.argmax(la))
    tok_b = int(np.argmax(lb))
    pos_before = (dec.position(sa), dec.position(sb))
    with pytest.raises(PagedKVExhausted):
        # each lane needs pages for positions 3..6 -> 2 new frames apiece,
        # only 1 in the pool
        dec.step_megastep({sa: tok_a, sb: tok_b}, k=4)
    assert (dec.position(sa), dec.position(sb)) == pos_before, \
        "failed pre-acquire moved a lane position"
    dec.retire(sb)
    out = dec.step_megastep({sa: tok_a}, k=4)
    assert out[sa].shape == (4,)
    assert dec.position(sa) == pos_before[0] + 4


def test_paged_megastep_matches_single_steps(tm):
    """Direct step_megastep parity against the per-step loop (argmax fed
    back host-side) for lanes at DIFFERENT positions."""
    tm.set_mode("counters")
    S, K = 32, 4
    params = _params(S)
    rs = np.random.RandomState(5)
    prompts = [rs.randint(1, CFG["vocab_size"], (L,)).astype(np.float32)
               for L in (2, 5)]

    def admit_all(d):
        toks = {}
        for p in prompts:
            sid, logits = d.admit(p)
            toks[sid] = int(np.argmax(logits))
        return toks

    d1 = _decoder(params, S, 2)
    toks = admit_all(d1)
    want = {sid: [] for sid in toks}
    cur = dict(toks)
    for _ in range(K):
        lg = d1.step(cur)
        cur = {sid: int(np.argmax(lg[sid])) for sid in lg}
        for sid in cur:
            want[sid].append(cur[sid])

    d2 = _decoder(params, S, 2)
    toks2 = admit_all(d2)
    assert toks2 == toks
    got = d2.step_megastep(toks2, k=K)
    for sid in toks:
        np.testing.assert_array_equal(got[sid], np.asarray(want[sid]))
