"""Shared-prefix KV cache (mxnet_tpu/serving/prefix_cache.py + the
refcounted COW _PagePool in kv_decode.py, docs/SERVING.md §Prefix cache
& speculative decoding): refcount/COW edge contracts on the pool, and
the serving-level guarantees — cached-prefix admits are BITWISE
identical to cold admits, hit accounting is truthful, eviction never
frees a shared page, and fork/COW isolates writers."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.serving import PagedKVDecoder, PagedKVExhausted, PrefixCache
from mxnet_tpu.serving.kv_decode import _PagePool

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


@pytest.fixture(params=["head_major", "page_major"])
def layout(request, monkeypatch):
    """The KV pools' layout (``ops.attention.pool_shape``): a row of 2 heads
    of 16 is kept head-major, one of 2 heads of 64 (128: whole tiles of the
    chip's lanes) page-major; the gates below hold in both."""
    if request.param == "page_major":
        monkeypatch.setitem(CFG, "model_dim", 128)
    return request.param


def _trained_params(S, seed=0):
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


def _decoder(params, S=16, lanes=3, **kw):
    kw.setdefault("prefix_cache", True)
    kw.setdefault("prefix_chunk", 4)
    return PagedKVDecoder(params, max_len=S, page_size=4, lanes=lanes,
                          prefill_len=12, pos_len=S, **CFG, **kw)


# --------------------------------------------------------- index contract
def test_chain_hashes_are_prefix_addressed():
    """h[i] names the ENTIRE prefix through chunk i: change any earlier
    token and every later hash moves; append-only growth keeps the
    shared stem's hashes stable."""
    pool = _PagePool(lanes=1, slots=16, page_size=4)
    pc = PrefixCache(pool, chunk=4)
    a = pc.chain_hashes(np.arange(12))
    b = pc.chain_hashes(np.arange(12))
    assert a == b and len(a) == 3
    mut = np.arange(12)
    mut[1] += 1
    c = pc.chain_hashes(mut)
    assert c[0] != a[0] and c[1] != a[1] and c[2] != a[2]
    tail = np.concatenate([np.arange(12), [99, 98, 97, 96]])
    d = pc.chain_hashes(tail)
    assert d[:3] == a and len(d) == 4
    with pytest.raises(ValueError, match="multiple"):
        PrefixCache(pool, chunk=6)  # page_size 4 does not divide 6


def test_eviction_never_frees_shared_pages_and_is_leaf_first():
    """The satellite edge: evicting a cache entry whose frames a lane
    still references must NOT return them to the free list (the lane
    holds a ref); interior chain entries outlive their children."""
    pool = _PagePool(lanes=1, slots=16, page_size=4)  # 4 frames
    pc = PrefixCache(pool, chunk=4)
    h = pc.chain_hashes(np.arange(8))
    f0, f1 = pool.acquire(), pool.acquire()
    pc.insert(h[0], [f0])
    pc.insert(h[1], [f1], parent=h[0])
    # the admitting lane retires; a second lane still shares f0
    pool.incref(f0)
    pool.release([f0, f1])
    assert pool.refcount(f0) == 2 and pool.refcount(f1) == 1
    # 4 frames can never come free while the lane pins f0: eviction
    # walks child-then-parent, drops both entries, REPORTS failure —
    # and the shared frame stays allocated under the lane's reference
    assert not pc.evict_for(4)
    assert pc.stats()["entries"] == 0 and pc.stats()["evictions"] == 2
    assert pool.refcount(f1) == 0
    assert pool.refcount(f0) == 1 and pool.in_use == 1
    assert pool.can_acquire(3)


def test_evict_for_reports_failure_when_nothing_evictable():
    pool = _PagePool(lanes=1, slots=16, page_size=4)
    pc = PrefixCache(pool, chunk=4)
    held = [pool.acquire() for _ in range(4)]  # lanes hold everything
    assert not pc.evict_for(1)
    pool.release(held)


# --------------------------------------------------- serving-level parity
def test_cached_admit_bitwise_identical_and_hit_accounting(tm, layout):
    """The acceptance gate: admit a prompt cold, admit it again cached —
    the second admit adopts the cached pages (hit counters move, prefill
    work is saved) and returns BITWISE-identical logits; a retire +
    re-admit replays the same physical placement. Zero post-warmup
    compiles or retraces."""
    tm.set_mode("counters")
    params = _trained_params(16)
    dec = _decoder(params)
    rs = np.random.RandomState(3)
    prompt = rs.randint(1, CFG["vocab_size"], (8,)).astype(np.float32)

    s0, cold = dec.admit(prompt)  # cold: 2 chunks computed + registered
    c0 = telemetry.counters()
    assert c0.get("serving.prefix_misses", 0) == 2
    s1, hit = dec.admit(prompt)   # full match: zero-write replay
    c1 = telemetry.counters()
    np.testing.assert_array_equal(np.asarray(cold), np.asarray(hit))
    assert c1.get("serving.prefix_hits", 0) == 2
    assert c1.get("serving.prefill_tokens_saved", 0) == 8
    assert c1.get("serving.pages_shared", 0) >= 2
    # shared pages: both lanes + the cache reference the same frames
    lane0 = dec._lanes[dec._seq_lane[s0]]
    lane1 = dec._lanes[dec._seq_lane[s1]]
    assert lane0.frames == lane1.frames
    for f in lane0.frames:
        assert dec.pool.refcount(f) == 3
    # retire + re-admit: deterministic placement => still bitwise
    dec.retire(s1)
    s2, again = dec.admit(prompt)
    np.testing.assert_array_equal(np.asarray(cold), np.asarray(again))
    # the two admits after warmup replayed sealed programs only
    assert c1.get("executor.retrace", 0) == 0
    c2 = telemetry.counters()
    assert c2.get("executor.compile", 0) == c0.get("executor.compile", 0)
    # hit-rate gauge is live
    assert dec.stats()["prefix_hit_rate"] > 0.5
    dec.retire(s0)
    dec.retire(s2)


def test_partial_prefix_match_decodes_token_identical(tm, layout):
    """Two prompts sharing a 4-token stem: the second admit reuses the
    stem chunk and computes only its tail, then decodes token-identical
    to a prefix-cache-OFF decoder over the same checkpoint."""
    tm.set_mode("counters")
    params = _trained_params(16)
    rs = np.random.RandomState(5)
    stem = rs.randint(1, CFG["vocab_size"], (4,)).astype(np.float32)
    p0 = np.concatenate([stem, [7.0, 9.0, 11.0, 13.0]])
    p1 = np.concatenate([stem, [8.0, 10.0, 12.0, 14.0]])

    base = PagedKVDecoder(params, max_len=16, page_size=4, lanes=2,
                          prefill_len=12, pos_len=16,
                          prefix_cache=False, **CFG)
    want = base.greedy([p0, p1], 5, k=1)

    dec = _decoder(params)
    dec.admit(p0)
    c0 = telemetry.counters()
    s1, _ = dec.admit(p1)
    c1 = telemetry.counters()
    assert c1.get("serving.prefix_hits", 0) - \
        c0.get("serving.prefix_hits", 0) == 1   # the stem chunk
    dec.retire(s1)
    for sid in list(dec.active):
        dec.retire(sid)
    got = dec.greedy([p0, p1], 5, k=1)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


# ------------------------------------------------------------- COW / fork
def test_fork_shares_pages_then_cow_isolates_writers(tm, layout):
    """The mid-megastep COW satellite: fork a sequence (every page
    shared at a refcount), megastep BOTH forks down different token
    paths — the first write into the shared boundary page triggers a
    private copy, the divergent continuations never corrupt each other,
    and cow_copies counts the copy."""
    tm.set_mode("counters")
    params = _trained_params(16)
    dec = _decoder(params, lanes=3)
    rs = np.random.RandomState(9)
    prompt = rs.randint(1, CFG["vocab_size"], (6,)).astype(np.float32)

    s0, lg = dec.admit(prompt)
    fk = dec.fork(s0)
    l0 = dec._lanes[dec._seq_lane[s0]]
    l1 = dec._lanes[dec._seq_lane[fk]]
    assert l0.frames == l1.frames and l1.pos == l0.pos
    shared = list(l0.frames)
    for f in shared:
        assert dec.pool.refcount(f) >= 2

    # oracle: each continuation decoded alone, no sharing anywhere
    solo = PagedKVDecoder(params, max_len=16, page_size=4, lanes=1,
                          prefill_len=12, pos_len=16,
                          prefix_cache=False, **CFG)
    t0 = int(np.argmax(lg))
    t1 = int(t0 == 0)  # any different token
    want = {}
    for tok in (t0, t1):
        sid, _ = solo.admit(prompt)
        want[tok] = solo.step_megastep({sid: tok}, k=4)[sid]
        solo.retire(sid)

    # both forks advance in ONE multiplexed megastep; position 6 lands
    # mid-page, so each lane's first write COWs the shared boundary page
    got = dec.step_megastep({s0: t0, fk: t1}, k=4)
    c = telemetry.counters()
    np.testing.assert_array_equal(got[s0], want[t0])
    np.testing.assert_array_equal(got[fk], want[t1])
    assert c.get("serving.cow_copies", 0) >= 1
    assert dec._lanes[dec._seq_lane[s0]].frames[1] != \
        dec._lanes[dec._seq_lane[fk]].frames[1]
    dec.retire(s0)
    dec.retire(fk)
    assert dec.stats()["pages_in_use"] == 1  # cache still holds the stem


def test_retire_while_shared_and_exhaustion_with_shared_pages(tm):
    """Two satellite edges: (1) retiring a lane whose pages are shared
    leaves the survivors' KV intact (frames stay allocated under their
    refs); (2) pool exhaustion with shared pages held raises the
    structured backpressure error instead of stealing shared frames."""
    tm.set_mode("counters")
    params = _trained_params(16)
    # 3 lanes x 4 frames = 12 frames, budget capped to 4
    dec = _decoder(params, lanes=3, page_budget=4)
    rs = np.random.RandomState(13)
    prompt = rs.randint(1, CFG["vocab_size"], (8,)).astype(np.float32)

    s0, lg0 = dec.admit(prompt)   # 2 frames (cache shares them)
    s1, lg1 = dec.admit(prompt)   # same 2 frames adopted
    assert dec.pool.in_use == 2
    np.testing.assert_array_equal(np.asarray(lg0), np.asarray(lg1))

    # (1) retire the ORIGINAL writer while its pages are shared
    dec.retire(s0)
    lane1 = dec._lanes[dec._seq_lane[s1]]
    for f in lane1.frames:
        assert dec.pool.refcount(f) == 2  # survivor + cache
    ref = PagedKVDecoder(params, max_len=16, page_size=4, lanes=1,
                         prefill_len=12, pos_len=16,
                         prefix_cache=False, **CFG)
    rsid, rlg = ref.admit(prompt)
    t = int(np.argmax(rlg))
    assert t == int(np.argmax(lg1))
    np.testing.assert_array_equal(
        dec.step_megastep({s1: t}, k=2)[s1],
        ref.step_megastep({rsid: t}, k=2)[rsid])

    # (2) exhaustion with shared pages held: the megastep grew s1 to 3
    # distinct frames (budget 4); an unrelated 12-token admit needs 3
    # fresh frames, so it must raise structured backpressure — the
    # shared frames survive under s1's references (the cache's own
    # entries are legal eviction fodder, their pages are not)
    held_before = dec.pool.in_use
    assert held_before == 3
    alien = np.arange(30, 42).astype(np.float32)
    with pytest.raises(PagedKVExhausted, match="budget exhausted"):
        dec.admit(alien)
    assert dec.pool.in_use == held_before
    for f in lane1.frames:
        assert dec.pool.refcount(f) >= 1


def test_rollback_releases_whole_pages_only(tm, layout):
    """Rollback (the speculative reject primitive): whole pages past the
    boundary are released, the partial boundary page is kept, and the
    re-decoded continuation is token-identical to never having rolled
    back."""
    tm.set_mode("counters")
    params = _trained_params(16)
    dec = _decoder(params, lanes=2, prefix_cache=False)
    rs = np.random.RandomState(17)
    prompt = rs.randint(1, CFG["vocab_size"], (4,)).astype(np.float32)
    sid, lg = dec.admit(prompt)
    t0 = int(np.argmax(lg))
    want = dec.step_megastep({sid: t0}, k=6)[sid]  # positions 4..9
    assert len(dec._lanes[dec._seq_lane[sid]].frames) == 3
    before = telemetry.counters().get("spec.rollbacks", 0)
    dec.rollback(sid, 6)   # keep pages 0..1, drop page 2
    lane = dec._lanes[dec._seq_lane[sid]]
    assert lane.pos == 6 and len(lane.frames) == 2
    assert telemetry.counters().get("spec.rollbacks", 0) == before + 1
    # re-decode from the rollback point: identical tokens
    redo = dec.step_megastep({sid: int(want[1])}, k=4)[sid]
    np.testing.assert_array_equal(redo, want[2:6])
    with pytest.raises(MXNetError, match="rollback target"):
        dec.rollback(sid, 99)
