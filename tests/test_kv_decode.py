"""KV-cache incremental decode (mxnet_tpu/serving/kv_decode.py +
models/transformer.py serving symbols, docs/SERVING.md): token-identical
greedy parity against full-sequence re-forward, prefill-length
independence, the paged pool, the sealed programs, and the zero-retrace
contract."""
import os
import types

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.serving import PagedKVDecoder, PagedKVExhausted

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
    """The KV pools' layout (``ops.attention.pool_shape``): 2 heads of 16
    are a row of 32, kept head-major (heads, slots, 16); 2 heads of 64 a row
    of 128, whole tiles of the chip's lanes, kept page-major (frames, page,
    128). The model is otherwise the same."""
    if request.param == "page_major":
        monkeypatch.setitem(CFG, "model_dim", 128)
    return request.param


def _by_slot(pool):
    """A pool buffer in either layout as (heads, slots, dh)."""
    pool = np.asarray(pool)
    if pool.shape[-1] % 128:
        return pool
    heads = CFG["num_heads"]
    return pool.reshape(-1, heads, pool.shape[-1] // heads).transpose(1, 0, 2)


def _trained_params(S, seed=0):
    """Random 'trained' weights harvested through the TRAINING symbol's
    bind shapes — the serving graphs must accept them by name."""
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
    return net, exe, params


def _paged(params, S, lanes, prefill_len=8, **kw):
    return PagedKVDecoder(params, max_len=S, page_size=4, lanes=lanes,
                          prefill_len=prefill_len, pos_len=S, **CFG, **kw)


def _ref_greedy(exe, prompt, n_tokens, S, vocab):
    """Oracle: full-sequence re-forward per step (pad to S; causality
    keeps pad tokens from influencing earlier positions)."""
    B = prompt.shape[0]
    seq = prompt.astype(np.float32)
    out = np.zeros((B, n_tokens), np.int64)
    for t in range(n_tokens):
        L = seq.shape[1]
        pad = np.zeros((B, S), np.float32)
        pad[:, :L] = seq
        exe.arg_dict["data"][:] = pad
        exe.forward(is_train=False)
        probs = exe.outputs[0].asnumpy().reshape(B, S, vocab)
        nxt = np.argmax(probs[:, L - 1, :], axis=-1)
        out[:, t] = nxt
        seq = np.concatenate([seq, nxt[:, None].astype(np.float32)], axis=1)
    return out


def test_greedy_decode_token_identical_32(tm, layout):
    """The PR acceptance bar: 32-token greedy decode through the KV-cache
    path produces token-identical output to full-sequence re-forward."""
    tm.set_mode("counters")
    S, B = 48, 2
    _, exe, params = _trained_params(S)
    # oracle executor is bound at batch 1; rebuild at B for the reference
    net = tfm.get_symbol(seq_len=S, **CFG)
    rexe = net.simple_bind(mx.cpu(), grad_req="null", data=(B, S),
                           softmax_label=(B, S))
    for k, v in params.items():
        rexe.arg_dict[k][:] = v
    rs = np.random.RandomState(3)
    prompt = rs.randint(1, CFG["vocab_size"], (B, 4))
    dec = _paged(params, S, lanes=B)
    c0 = tm.counters()
    got = np.stack(dec.greedy(list(prompt.astype(np.float32)), 32))
    c1 = tm.counters()
    want = _ref_greedy(rexe, prompt, 32, S, CFG["vocab_size"])
    np.testing.assert_array_equal(got, want)
    # zero retraces across 32 positions: ONE decode executable replayed
    assert c1.get("executor.retrace", 0) == c0.get("executor.retrace", 0)
    # (snapshot after the oracle ran: its own first forward compiles too)
    warm_compiles = tm.counters().get("executor.compile", 0)
    dec.greedy(list(prompt.astype(np.float32)), 8)
    assert tm.counters().get("executor.compile", 0) == warm_compiles, \
        "a second decode recompiled something"


def test_prefill_logits_match_full_forward():
    S = 32
    _, exe, params = _trained_params(S)
    rs = np.random.RandomState(5)
    L = 6
    prompt = rs.randint(1, CFG["vocab_size"], (1, L)).astype(np.float32)
    dec = _paged(params, S, lanes=1, prefill_len=16)
    logits = dec.admit(prompt)[1][None]
    pad = np.zeros((1, S), np.float32)
    pad[:, :L] = prompt
    exe.arg_dict["data"][:] = pad
    exe.forward(is_train=False)
    probs = exe.outputs[0].asnumpy().reshape(1, S, CFG["vocab_size"])
    # the training head is a SoftmaxOutput: compare post-softmax
    p = np.exp(logits - logits.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    np.testing.assert_allclose(p, probs[:, L - 1, :], rtol=1e-4, atol=1e-5)


def test_decoder_input_validation():
    S = 16
    _, _, params = _trained_params(S)
    with pytest.raises(MXNetError, match="prefill_len"):
        _paged(params, 8, lanes=1, prefill_len=16)
    dec = _paged(params, S, lanes=2)
    with pytest.raises(MXNetError, match="length"):
        dec.admit(np.ones((9,), np.float32))
    with pytest.raises(MXNetError, match="unknown seq_id"):
        dec.step({7: 1})
    # a lane is bounded by the trained position table and by max_len:
    # structured errors, not an out-of-bounds gather or a wrapped write
    short = PagedKVDecoder(params, max_len=S, page_size=4, lanes=1,
                           prefill_len=8, pos_len=6, **CFG)
    sid, _ = short.admit(np.ones((6,), np.float32))
    with pytest.raises(MXNetError, match="position table"):
        short.step({sid: 1})
    sid, logits = dec.admit(np.ones((8,), np.float32))
    for _ in range(S - 8):
        logits = dec.step({sid: int(np.argmax(logits))})[sid]
    assert dec.position(sid) == S and np.isfinite(logits).all()
    with pytest.raises(MXNetError, match="position table|slot quota"):
        dec.step({sid: 1})


def test_serving_symbols_share_training_weight_names():
    S = 16
    train_args = set(tfm.get_symbol(seq_len=S, **CFG).list_arguments())
    pf_args = set(tfm.get_prefill_symbol(prefill_len=8, pos_len=S,
                                         **CFG).list_arguments())
    dec_args = set(tfm.get_decode_symbol(max_len=S, pos_len=S,
                                         **CFG).list_arguments())
    # every serving weight exists in the training graph (data/kv/mask
    # inputs are serving-only by construction)
    serving_only = {"data", "pos_idx", "write_slot", "page_table"} | \
        {"kv_%s_%d" % (t, i) for t in ("k", "v")
         for i in range(CFG["num_layers"])}
    assert (pf_args - {"data", "length"}) <= train_args
    assert (dec_args - serving_only) <= train_args


# ----------------------------------------------------------- paged decode
def test_page_pool_accounting_and_reuse():
    """Allocator unit contract (no device work): frames hand out LIFO
    over ONE global frame space (non-contiguous physical placement is
    routine), refcounts gate the free list, release re-stacks reversed
    so re-acquisition replays placement, the budget caps distinct frames
    in use, and page_size must divide the slot count."""
    from mxnet_tpu.serving.kv_decode import _PagePool, PagedKVExhausted

    pool = _PagePool(lanes=2, slots=16, page_size=4)
    assert pool.frames_per_lane == 4 and pool.budget == 8
    a = [pool.acquire() for _ in range(8)]
    assert sorted(a) == list(range(8)) and pool.in_use == 8
    with pytest.raises(PagedKVExhausted, match="budget exhausted"):
        pool.acquire()
    # refcounted sharing: only the LAST holder frees the frame
    f = a[0]
    pool.incref(f)
    assert pool.refcount(f) == 2
    pool.release([f])
    assert pool.refcount(f) == 1 and pool.in_use == 8
    pool.release([f])
    assert pool.refcount(f) == 0 and pool.in_use == 7
    # deterministic placement: release re-stacks reversed, so a
    # re-acquisition sequence replays the original frame order
    x = a[3:6]
    pool.release(x)
    assert [pool.acquire() for _ in range(3)] == x
    # a budget above the physical frame count exposes the free-list wall
    wide = _PagePool(lanes=1, slots=16, page_size=4, budget=10)
    for _ in range(4):
        wide.acquire()
    with pytest.raises(PagedKVExhausted, match="no free page frame"):
        wide.acquire()
    # global budget below the physical frame count gates admission
    tight = _PagePool(lanes=2, slots=16, page_size=4, budget=1)
    tight.acquire()
    with pytest.raises(PagedKVExhausted, match="budget"):
        tight.acquire()
    with pytest.raises(MXNetError, match="divide"):
        _PagePool(lanes=1, slots=10, page_size=4)


def test_paged_multiplexed_token_identical(layout):
    """The acceptance bar: >=2 concurrent sequences served from ONE
    decode batch, admitted at different times and advancing at different
    positions, produce token-identical output to sequential per-request
    decode — and the multiplexed path never retraces."""
    telemetry.reset()
    telemetry.set_mode("counters")
    try:
        S = 16
        _, _, params = _trained_params(S)
        rs = np.random.RandomState(7)
        prompts = [rs.randint(1, CFG["vocab_size"], (n,)).astype(np.float32)
                   for n in (3, 5, 2)]

        # oracle: each prompt decoded alone through a one-lane decoder
        def solo(prompt, n_tok):
            return _paged(params, S, lanes=1).greedy([prompt], n_tok)[0]

        want = [solo(p, 6) for p in prompts]

        paged = PagedKVDecoder(params, max_len=S, page_size=4, lanes=3,
                               prefill_len=8, pos_len=S, **CFG)
        # staggered admission: two sequences run for 2 steps before the
        # third joins — three lanes at three different positions in every
        # later dispatch
        sids, logits, toks = [], {}, {}
        for p in prompts[:2]:
            sid, lg = paged.admit(p)
            sids.append(sid)
            logits[sid] = lg
            toks[sid] = []
        c0 = telemetry.counters()
        for _ in range(2):
            nxt = {s: int(np.argmax(logits[s])) for s in sids}
            for s in sids:
                toks[s].append(nxt[s])
            logits = paged.step(nxt)
        sid3, lg3 = paged.admit(prompts[2])
        sids.append(sid3)
        logits[sid3] = lg3
        toks[sid3] = []
        for _ in range(6):
            need = [s for s in sids if len(toks[s]) < 6]
            if not need:
                break
            nxt = {s: int(np.argmax(logits[s])) for s in need}
            for s in need:
                toks[s].append(nxt[s])
            step_ids = {s: nxt[s] for s in need if len(toks[s]) < 6}
            if step_ids:
                logits.update(paged.step(step_ids))
        for sid, w in zip(sids, want):
            np.testing.assert_array_equal(np.asarray(toks[sid]), w)
        # one decode executable, replayed for every multiplexed step
        c1 = telemetry.counters()
        assert c1.get("executor.retrace", 0) == c0.get("executor.retrace", 0)
        assert c1.get("executor.compile", 0) == c0.get("executor.compile", 0)
        assert paged.stats()["active"] == 3
        for sid in sids:
            paged.retire(sid)
        assert paged.stats()["pages_in_use"] == 0
    finally:
        telemetry.set_mode(None)
        telemetry.reset()


def test_paged_admission_backpressure_and_reuse():
    """Lane exhaustion and page-budget exhaustion raise the structured
    PagedKVExhausted (admission backpressure); retiring frees the lane
    and its pages for the next sequence, which lands on recycled
    (non-contiguous) frames and still decodes identically."""
    S = 16
    _, _, params = _trained_params(S)
    rs = np.random.RandomState(11)
    prompt = rs.randint(1, CFG["vocab_size"], (4,)).astype(np.float32)

    paged = PagedKVDecoder(params, max_len=S, page_size=4, lanes=2,
                           prefill_len=8, pos_len=S, **CFG)
    s0, _ = paged.admit(prompt)
    s1, _ = paged.admit(prompt)
    with pytest.raises(PagedKVExhausted, match="lanes occupied"):
        paged.admit(prompt)
    paged.retire(s0)
    s2, lg = paged.admit(prompt)  # recycled lane + frames
    want = _paged(params, S, lanes=1).greedy([prompt], 4)[0]
    toks = []
    for _ in range(4):
        t = int(np.argmax(lg))
        toks.append(t)
        lg = paged.step({s2: t})[s2]
    np.testing.assert_array_equal(np.asarray(toks), want)

    # a page budget below the physical capacity sheds admissions
    tight = PagedKVDecoder(params, max_len=S, page_size=4, lanes=2,
                           page_budget=1, prefill_len=8, pos_len=S, **CFG)
    tight.admit(prompt)  # 4 tokens -> exactly 1 page
    with pytest.raises(PagedKVExhausted, match="budget"):
        tight.admit(prompt)


# ---------------------------------------------- on-device greedy head (GL703)
ARCHS = {
    "vaswani": CFG,
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
    "deepseek_v3": dict(
        arch="deepseek_v3", vocab_size=60, num_layers=2, num_heads=4,
        model_dim=32, ffn_dim=48, moe_ffn_dim=16, num_experts=4,
        num_experts_per_tok=2, num_shared_experts=1, first_dense_layers=1,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        kv_lora_rank=16, rope_theta=10000.0, rms_eps=1e-6,
        routed_scaling_factor=2.448, norm_topk_prob=True),
}


def _arch_decoder(arch, lanes=3, twin_rows=False):
    """A decoder of ``arch`` at a test's size. ``twin_rows``: every row of
    the output head (and of the embedding, which a tied head IS) equals its
    even neighbour's, so each logit has a twin and every maximum is a tie."""
    cfg, S = ARCHS[arch], 32
    if arch == "vaswani":
        params = _trained_params(S)[2]
    else:
        rs = np.random.RandomState(0)
        params = {}
        for name, shape in sorted(tfm.param_shapes(**cfg).items()):
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
            params[name] = v.astype("float32")
    if twin_rows:
        for name in ("embed_weight", "lm_head_weight", "lm_head_bias"):
            if name in params:
                params[name] = np.repeat(params[name][0::2], 2, axis=0)
    serve = dict(max_len=S, page_size=4, lanes=lanes, prefill_len=16)
    serve.update(dict(pos_len=S) if arch == "vaswani"
                 else dict(dtype="float32"))
    return PagedKVDecoder(params, **serve, **cfg)


def _admit_prompts(dec, prompts=([3, 1, 4, 1, 5, 9], [2, 7, 1])):
    nxt = {}
    for prompt in prompts:
        sid, logits = dec.admit(np.asarray(prompt, np.float32))
        nxt[sid] = int(np.argmax(logits))
    return nxt


def _pulls(tm):
    c = tm.counters()
    return (c.get("serving.step_logits_pulls", 0),
            c.get("serving.step_logits_pull_bytes", 0))


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_greedy_step_on_device_argmax_token_parity(tm, arch):
    """The GL703 fix gate: the token ``np.argmax(row)`` answers with, the
    decode program's own ``greedy_token`` and no pull, is the arg-max of the
    row's pulled logits, step for step. Every logit has a twin, so each
    maximum is a tie that both sides settle for the first; the third lane
    rides along, and so does the second sequence in one step of three."""
    tm.set_mode("counters")
    dec = _arch_decoder(arch, twin_rows=True)
    nxt = _admit_prompts(dec)
    first = next(iter(nxt))
    for t in range(12):
        out = dec.step(nxt if t % 3 else {first: nxt[first]})
        assert len(out) == (2 if t % 3 else 1)
        for sid, row in out.items():
            before = _pulls(tm)
            tok_d = int(np.argmax(row))
            assert _pulls(tm) == before
            logits = np.asarray(row)
            assert isinstance(logits, np.ndarray)
            tok_h = int(np.argmax(logits))
            assert tok_d == tok_h
            assert tok_h % 2 == 0 and logits[tok_h] == logits[tok_h + 1]
            nxt[sid] = tok_h
    # the compiled decode program really carries the token head, under the
    # name the decoder finds it by
    exe = dec._dec_exe
    assert list(exe.output_dict)[dec._dec_token].startswith("greedy_token")
    assert exe.outputs[dec._dec_token].shape == (dec.lanes,)


def test_rows_that_are_only_argmaxed_leave_the_logits_on_the_device(tm):
    """A greedy caller's step: the host copies one id a lane
    (``serving.step.copy``'s ``bytes``) and the ``(lanes, vocab)`` block
    never crosses: no pull counted, no ``serving.step.logits_pull`` span."""
    tm.set_mode("trace")
    dec = _tiny_paged().warmup()
    nxt = _admit_prompts(dec)
    tm.clear_events()
    for _ in range(3):
        out = dec.step(nxt)
        nxt = {sid: int(np.argmax(row)) for sid, row in out.items()}
        assert all(row.shape == (CFG["vocab_size"],)
                   and row.dtype == np.float32
                   and len(row) == CFG["vocab_size"] for row in out.values())
    events = tm.drain_events()
    copies = [e for e in events if e[0] == "serving.step.copy"]
    assert [e[4]["bytes"] for e in copies] == [dec.lanes * 4] * 3
    assert not [e for e in events if e[0] == "serving.step.logits_pull"]
    assert _pulls(tm) == (0, 0)
    assert tm.counters()["serving.paged_steps"] == 3


def test_one_read_pulls_the_block_once_for_the_whole_step(tm):
    """``np.asarray`` of any row moves the step's block to the host, inside
    ``serving.step.logits_pull``, and every row of the step is a slice of
    that one copy: the logits output's own lanes, bit for bit."""
    tm.set_mode("trace")
    dec = _tiny_paged().warmup()
    nxt = _admit_prompts(dec)
    block = dec.lanes * CFG["vocab_size"] * 4
    for n in (1, 2):
        out = dec.step(nxt)
        want = dec._dec_exe.outputs[0].asnumpy()  # what the parent pulled
        tm.clear_events()
        for sid in reversed(sorted(out)):
            got = np.asarray(out[sid])
            assert got.dtype == np.float32
            np.testing.assert_array_equal(got, want[dec._seq_lane[sid]])
            assert _pulls(tm) == (n, n * block)
        pulled = [e for e in tm.drain_events()
                  if e[0] == "serving.step.logits_pull"]
        assert [e[4]["bytes"] for e in pulled] == [block]
        nxt = {sid: int(np.argmax(row)) for sid, row in out.items()}
    assert tm.counters()["serving.paged_steps"] == 2


def test_a_row_read_later_still_holds_its_own_steps_logits(tm):
    """A row keeps ITS step's block, on the device, however many steps
    follow before somebody reads it."""
    tm.set_mode("counters")
    dec = _tiny_paged().warmup()
    nxt = _admit_prompts(dec)
    kept, want = [], []
    for _ in range(3):
        out = dec.step(nxt)
        kept.append(out)
        want.append(dec._dec_exe.outputs[0].asnumpy().copy())
        nxt = {sid: int(np.argmax(row)) for sid, row in out.items()}
    assert _pulls(tm)[0] == 0
    assert not np.array_equal(want[0], want[2])
    for out, block in zip(kept, want):
        for sid, row in out.items():
            np.testing.assert_array_equal(row, block[dec._seq_lane[sid]])
    assert _pulls(tm)[0] == 3


def test_a_row_is_an_array_to_numpy(tm):
    """What callers do with a row: index it, do arithmetic on it, stack it,
    compare it, reduce it along an axis. All of it reads the same copy."""
    tm.set_mode("counters")
    dec = _tiny_paged().warmup()
    out = dec.step(_admit_prompts(dec))
    rows = [out[sid] for sid in sorted(out)]
    want = dec._dec_exe.outputs[0].asnumpy()[
        [dec._seq_lane[sid] for sid in sorted(out)]]
    np.testing.assert_array_equal(np.stack(rows), want)
    np.testing.assert_allclose(rows[0], want[0], rtol=0, atol=0)
    assert rows[0][3] == want[0][3] and list(rows[1][:2]) == list(want[1][:2])
    np.testing.assert_array_equal(rows[0] - rows[1], want[0] - want[1])
    np.testing.assert_array_equal(2.0 * rows[0], 2.0 * want[0])
    np.testing.assert_array_equal(np.exp(rows[1]), np.exp(want[1]))
    np.testing.assert_array_equal(rows[0] > 0, want[0] > 0)
    assert rows[0].max() == want[0].max() == np.max(rows[0])
    assert np.argmax(rows[0], axis=-1) == rows[0].argmax() == want[0].argmax()
    assert np.argsort(rows[1])[-1] == want[1].argmax()
    assert rows[0].astype(np.float64).dtype == np.float64
    assert _pulls(tm) == (1, dec.lanes * CFG["vocab_size"] * 4)
    # with telemetry off the read touches no counter
    tm.reset()
    tm.set_mode(None)
    env = os.environ.pop("MXNET_TELEMETRY", None)
    try:
        row = next(iter(dec.step({s: int(np.argmax(r))
                                  for s, r in out.items()}).values()))
        assert np.asarray(row).shape == (CFG["vocab_size"],)
        assert _pulls(tm) == (0, 0)
    finally:
        if env is not None:
            os.environ["MXNET_TELEMETRY"] = env


def test_dispatch_host_gap_timer_ticks_only_when_enabled(tm):
    """dispatch.host_gap attribution: ticks per steady-state decode step
    when telemetry is on; with MXNET_TELEMETRY off the instrumented path
    never touches the registry (the zero-overhead contract)."""
    S, B = 16, 1
    _, _, params = _trained_params(S)
    prompt = list(np.ones((B, 3), np.float32))
    dec = _paged(params, S, lanes=B, prefill_len=4)

    tm.set_mode(None)
    env = os.environ.pop("MXNET_TELEMETRY", None)
    try:
        dec.greedy(prompt, 4)
        assert tm.timer("dispatch.host_gap").count == 0
    finally:
        if env is not None:
            os.environ["MXNET_TELEMETRY"] = env

    tm.set_mode("counters")
    dec.greedy(prompt, 4)
    agg = tm.timer("dispatch.host_gap")
    # 3 steps; the first after the admission has no prior return to gap
    # against (admit resets the chain), so 2 steady-state intervals
    assert agg.count == 2
    assert agg.total_ms > 0.0
    site = tm.timer("dispatch.host_gap.serving.paged_step")
    assert site.count == agg.count

    # the interval starts where the device has just finished: the stamp
    # sits between ``serving.step.wait``'s close and ``serving.step.copy``'s
    # open, so the copy to the host is inside the gap that follows
    tm.set_mode("trace")
    tm.reset()
    tm.clear_events()
    sid, logits = dec.admit(np.ones((3,), np.float32))
    for _ in range(2):
        logits = dec.step({sid: int(np.argmax(logits))})[sid]
    by_name = {}
    for e in sorted(tm.drain_events(), key=lambda e: e[1]):
        by_name.setdefault(e[0], []).append(e)
    wait, copy = by_name["serving.step.wait"], by_name["serving.step.copy"]
    assert wait[1][1] + wait[1][2] <= dec._last_return_t <= copy[1][1]
    gap = tm.timer("dispatch.host_gap")
    assert gap.count == 1
    # wait[0]'s close -> dispatch[1]'s open holds copy[0] and commit[0]
    commit = by_name["serving.step.commit"][0]
    assert gap.total_ms >= 1e3 * (copy[0][2] + commit[2])
    dispatch = by_name["serving.step.dispatch"][1]
    assert gap.total_ms <= 1e3 * (dispatch[1] - wait[0][1] - wait[0][2])


# ------------------------------------------------ phase spans and XLA bytes
ADMIT_PHASES = ("stage", "prefill", "scatter", "logits")
STEP_PHASES = ("stage", "dispatch", "read", "commit")


def _tiny_paged(S=16):
    _, _, params = _trained_params(S)
    return _paged(params, S, lanes=2)


@pytest.fixture
def traced_request(tm):
    """One admit, two steps and a retire of a tiny paged decoder in trace
    mode: (events oldest-first by start, counter readings around the steps,
    the seq id)."""
    tm.set_mode("trace")
    dec = _tiny_paged().warmup()
    tm.clear_events()
    sid, logits = dec.admit(np.array([3, 1, 4, 1, 5], np.float32))
    bytes_seen = [tm.counters().get("serving.decode_xla_bytes", 0)]
    for _ in range(2):
        logits = dec.step({sid: int(np.argmax(logits))})[sid]
        bytes_seen.append(tm.counters()["serving.decode_xla_bytes"])
    dec.retire(sid)
    events = sorted(tm.drain_events(), key=lambda e: e[1])
    return events, bytes_seen, sid


def _children(events, parent):
    pid = parent[4]["id"]
    return [e for e in events if e[4].get("parent") == pid]


def _inside_and_disjoint(parent, kids):
    t = parent[1]
    for k in sorted(kids, key=lambda e: e[1]):
        assert t <= k[1], k[0]          # starts after the previous one ended
        t = k[1] + k[2]
    assert t <= parent[1] + parent[2]   # the last one ends inside the parent


def test_admit_yields_one_span_per_phase_inside_paged_admit(traced_request):
    events, _bytes, sid = traced_request
    (admit,) = [e for e in events if e[0] == "serving.paged_admit"]
    assert admit[4]["seq"] == sid and admit[4]["prompt_len"] == 5
    kids = _children(events, admit)
    assert [k[0] for k in kids] == ["serving.admit." + p
                                    for p in ADMIT_PHASES]
    _inside_and_disjoint(admit, kids)
    # the executor's own span nests in the prefill phase, not beside it
    (prefill,) = [k for k in kids if k[0] == "serving.admit.prefill"]
    assert [e[0] for e in _children(events, prefill)] == ["executor.forward"]


def test_step_yields_one_span_per_phase_inside_paged_step(traced_request):
    events, _bytes, _sid = traced_request
    steps = [e for e in events if e[0] == "serving.paged_step"]
    assert len(steps) == 2
    for step in steps:
        assert step[4]["rows"] == 1 and step[4]["paged"] is True
        kids = _children(events, step)
        assert [k[0] for k in kids] == ["serving.step.stage",
                                        "serving.decode_step",
                                        "serving.step.commit",
                                        "serving.step.account"]
        _inside_and_disjoint(step, kids)
        inner = _children(events, kids[1])
        assert [k[0] for k in inner] == ["serving.step.dispatch",
                                         "serving.step.read"]
        _inside_and_disjoint(kids[1], inner)
    names = [e[0] for e in events]
    for phase in STEP_PHASES + ("wait", "copy", "account"):
        assert names.count("serving.step." + phase) == 2


def _read_halves(events, under):
    """Every ``serving.step.read`` of ``events`` whose parent is a span
    called ``under``, with its children in order of start."""
    by_id = {e[4]["id"]: e for e in events if "id" in e[4]}
    reads = [e for e in events if e[0] == "serving.step.read"
             and by_id[e[4]["parent"]][0] == under]
    return [(r, _children(events, r)) for r in reads]


@pytest.mark.parametrize("path", ["step", "step_megastep", "chunk"])
def test_the_blocking_read_is_a_wait_and_then_a_copy(tm, path):
    """One helper, three sites: inside ``serving.step.read`` the host first
    waits for the device (``serving.step.wait``), then copies arrays that are
    ready (``serving.step.copy``, ``bytes``: what crossed)."""
    tm.set_mode("trace")
    dec = _tiny_paged().warmup()
    sid, logits = dec.admit(np.array([3, 1, 4, 1, 5], np.float32))
    tok = int(np.argmax(logits))
    lanes, vocab = dec.lanes, CFG["vocab_size"]
    if path == "chunk":
        dec._chunk_for(3)               # its warm compile is not a dispatch
    elif path == "step_megastep":
        dec.step_megastep({sid: tok}, k=2)
    tm.clear_events()
    if path == "step":
        dec.step({sid: tok})
        # one id a lane: the logits stay on the device (``_LogitsRow``)
        under, crossed = "serving.decode_step", lanes * 4
    elif path == "step_megastep":
        dec.step_megastep({sid: tok}, k=2)
        # (K, lanes) int32 ids and the (K, lanes) active mask
        under, crossed = "serving.decode_megastep", 2 * lanes * (4 + 1)
    else:
        dec.verify_chunk(sid, [tok, 7, 9])
        under, crossed = "serving.chunk_prefill", 3 * vocab * 4
    events = sorted(tm.drain_events(), key=lambda e: e[1])
    ((read, halves),) = _read_halves(events, under)
    assert [h[0] for h in halves] == ["serving.step.wait",
                                      "serving.step.copy"]
    _inside_and_disjoint(read, halves)
    assert halves[1][4]["bytes"] == crossed
    assert "bytes" not in halves[0][4]
    # the dispatch is the read's sibling, before it
    siblings = _children(events, next(
        e for e in events if e[4].get("id") == read[4]["parent"]))
    assert [k[0] for k in siblings] == ["serving.step.dispatch",
                                        "serving.step.read"]


def test_the_pull_queues_its_copy_before_it_waits(tm):
    """The copy to the host is queued behind the program before the host
    blocks, as a bare ``np.asarray`` of a pending array would queue it: the
    wait then costs a read no second trip to the device."""
    from mxnet_tpu.serving import kv_decode as kd

    calls = []

    class Pending:
        nbytes = 12

        def copy_to_host_async(self):
            calls.append("copy_to_host_async")

        def block_until_ready(self):
            calls.append("block_until_ready")
            return self

        def __array__(self, dtype=None, copy=None):
            calls.append("__array__")
            return np.arange(3, dtype=np.float32)

    def enqueue():
        calls.append("enqueue")
        return (Pending(),), "kept"

    dec = types.SimpleNamespace(_device=kd._DeviceRecord())
    tm.set_mode("0")
    (host,), kept = kd._dispatch_and_pull(dec, "site", "decode", "span",
                                          enqueue)
    assert calls == ["enqueue", "copy_to_host_async", "block_until_ready",
                     "__array__"]
    assert kept == "kept" and host.tolist() == [0.0, 1.0, 2.0]
    assert dec._device.ready_t is None and tm.drain_events() == []
    tm.set_mode("trace")
    kd._dispatch_and_pull(dec, "site", "decode", "span", enqueue, rows=1)
    names = [e[0] for e in sorted(tm.drain_events(), key=lambda e: e[1])]
    assert names == ["span", "serving.step.dispatch", "serving.step.read",
                     "serving.step.wait", "serving.step.copy"]
    assert dec._device.ready_t is not None and dec._device.in_flight == []


def test_admit_waits_for_the_prefill_inside_the_logits_phase(traced_request):
    events, _bytes, _sid = traced_request
    (logits,) = [e for e in events if e[0] == "serving.admit.logits"]
    kids = _children(events, logits)
    assert [k[0] for k in kids] == ["serving.admit.wait"]
    _inside_and_disjoint(logits, kids)


def test_a_step_builds_no_span_object_with_telemetry_off(tm, monkeypatch):
    """The split costs an untraced step one ``block_until_ready`` on a
    buffer about to be read, and nothing of the instrument: no span, no id,
    no annotation, no clock read for the gap, no registry object."""
    from mxnet_tpu.telemetry import spans

    dec = _tiny_paged().warmup()
    sid, logits = dec.admit(np.array([3, 1, 4], np.float32))

    def built(*_a, **_k):
        raise AssertionError("telemetry is off: nothing may be built")

    monkeypatch.setattr(spans, "_Span", built)
    monkeypatch.setattr(spans, "_annotation", built)
    monkeypatch.setattr(spans, "_span_ids", iter(()))  # next() would raise
    tm.set_mode("0")
    for _ in range(2):
        logits = dec.step({sid: int(np.argmax(logits))})[sid]
    assert logits.shape == (CFG["vocab_size"],)
    assert dec._last_return_t is None
    assert tm.drain_events() == [] and tm.counters() == {}


def test_retire_event_closes_the_request(traced_request):
    events, _bytes, sid = traced_request
    (retire,) = [e for e in events if e[0] == "serving.retire"]
    # 5 prompt tokens + 2 decoded: the position the lane reached
    assert retire[4] == {"seq": sid, "pos": 7} and retire[2] == 0.0
    assert retire[1] >= max(e[1] for e in events
                            if e[0] == "serving.paged_step")


def test_decode_xla_bytes_rises_by_the_program_s_count_per_step(
        traced_request):
    _events, seen, _sid = traced_request
    assert seen[0] == 0                       # an admission adds none
    assert seen[1] > 0 and seen[2] == 2 * seen[1]


def test_a_step_hands_the_program_a_few_numbers_a_lane(tm):
    """``serving.step_input_bytes`` over ``serving.paged_steps``: a token, a
    position, a write slot and a page table a lane, whatever the lanes hold
    and however many of them step, in ONE array (``serving.step_staged_arrays``
    a step); no input of the decode program has the pool's length."""
    tm.set_mode("trace")
    S, lanes, page = 16, 3, 4
    _, _, params = _trained_params(S)
    dec = _paged(params, S, lanes=lanes).warmup()
    assert tm.counters().get("serving.step_input_bytes", 0) == 0
    a, la = dec.admit(np.array([3, 1, 4, 1, 5], np.float32))
    b, lb = dec.admit(np.array([2, 7, 1], np.float32))
    assert tm.counters().get("serving.step_input_bytes", 0) == 0
    nxt = {a: int(np.argmax(la)), b: int(np.argmax(lb))}
    for seqs in ((a, b), (a,), (b, a), (a, b)):
        out = dec.step({s: nxt[s] for s in seqs})
        nxt.update({s: int(np.argmax(out[s])) for s in seqs})
    c = tm.counters()
    assert c["serving.paged_steps"] == 4
    assert c["serving.step_input_bytes"] == 4 * lanes * (3 + S // page) * 4
    assert c["serving.step_staged_arrays"] == 4
    inputs = {n: a.shape for n, a in dec._dec_exe.arg_dict.items()
              if n in dec._decode_shapes() and not n.startswith("kv_")}
    assert inputs == {"step_in": (lanes, 3 + S // page)}


def _cold_probs(exe, tokens, S):
    """The training graph's next-token distribution after ``tokens``."""
    pad = np.zeros((1, S), np.float32)
    pad[0, :len(tokens)] = tokens
    exe.arg_dict["data"][:] = pad
    exe.forward(is_train=False)
    return exe.outputs[0].asnumpy().reshape(S, -1)[len(tokens) - 1]


def _softmax(logits):
    p = np.exp(logits - logits.max())
    return p / p.sum()


@pytest.mark.parametrize("case", ["fork", "copy_on_write", "rollback"])
def test_a_step_reads_a_cold_re_forward_after(tm, case, layout):
    """The mask is made on the device from the lane's frame table, so what a
    lane sees after its table changed under it — a fork's shared frames, the
    private copy a write into one makes, the pages and the stale tail a
    rollback drops — is what a re-forward of its tokens from nothing sees."""
    tm.set_mode("counters")
    S = 16
    _, exe, params = _trained_params(S)
    dec = _paged(params, S, lanes=3).warmup()
    prompt = [3, 1, 4, 1, 5, 9]          # position 6: mid-page, pages of 4
    sid, logits = dec.admit(np.asarray(prompt, np.float32))
    t0 = int(np.argmax(logits))

    def check(got, tokens):
        np.testing.assert_allclose(_softmax(got), _cold_probs(exe, tokens, S),
                                   rtol=1e-4, atol=1e-5)

    if case == "fork":
        twin = dec.fork(sid)
        t1 = (t0 + 1) % CFG["vocab_size"]
        out = dec.step({sid: t0, twin: t1})     # both write the shared page
        check(out[sid], prompt + [t0])
        check(out[twin], prompt + [t1])
        nxt = dec.step({twin: 7})[twin]         # and the other rides along
        check(nxt, prompt + [t1, 7])
    elif case == "copy_on_write":
        twin = dec.fork(sid)
        shared = list(dec._lanes[dec._seq_lane[sid]].frames)
        t1 = (t0 + 1) % CFG["vocab_size"]
        check(dec.step({twin: t1})[twin], prompt + [t1])
        assert tm.counters()["serving.cow_copies"] == 1
        mine = dec._lanes[dec._seq_lane[twin]].frames
        assert mine[0] == shared[0] and mine[1] != shared[1]
        # the first writer's page was copied, not written: the other lane
        # still reads its own tokens there, then writes it in place
        check(dec.step({sid: t0})[sid], prompt + [t0])
        assert tm.counters()["serving.cow_copies"] == 1
        assert list(dec._lanes[dec._seq_lane[sid]].frames) == shared
    else:
        toks = [t0]
        for _ in range(4):                      # positions 6..10: three pages
            toks.append(int(np.argmax(dec.step({sid: toks[-1]})[sid])))
        assert len(dec._lanes[dec._seq_lane[sid]].frames) == 3
        dec.rollback(sid, 7)                    # page 2 goes, page 1 keeps 3
        assert len(dec._lanes[dec._seq_lane[sid]].frames) == 2
        other = (toks[1] + 1) % CFG["vocab_size"]
        check(dec.step({sid: other})[sid], prompt + [t0, other])
        check(dec.step({sid: 2})[sid], prompt + [t0, other, 2])


def test_executor_cost_analysis_counts_the_bound_program(tm):
    dec = _tiny_paged().warmup()
    assert dec._decode_xla_bytes is None      # telemetry off: never read
    cost = dec._dec_exe.cost_analysis()
    assert cost["flops"] > 0 and cost["bytes accessed"] > 0
    tm.set_mode("counters")
    assert _tiny_paged().warmup()._decode_xla_bytes == \
        int(cost["bytes accessed"])


def test_admit_and_step_logits_are_bitwise_the_same_with_telemetry_off(tm):
    prompt = np.array([3, 1, 4, 1, 5], np.float32)

    def serve():
        dec = _tiny_paged().warmup()
        sid, first = dec.admit(prompt)
        rows = [np.asarray(first)]
        for _ in range(3):
            rows.append(np.asarray(
                dec.step({sid: int(np.argmax(rows[-1]))})[sid]))
        dec.retire(sid)
        return np.stack(rows)

    tm.set_mode("0")
    off = serve()
    assert tm.drain_events() == [] and tm.counters() == {}
    tm.set_mode("trace")
    on = serve()
    assert tm.counters()["serving.paged_steps"] == 3
    assert off.dtype == on.dtype and np.array_equal(off, on)


# ---- the pool update of an admission: one sealed, donated program ---------
def _paged_for_pool(lanes):
    """Pages of 4 under a prefill window that is no multiple of a page, so
    the program's last page-sized slice needs its pad."""
    S = 16
    _, _, params = _trained_params(S)
    return _paged(params, S, lanes=lanes, prefill_len=10)


def _pool(dec):
    """The pool's arrays through their owner, ``arg_dict``."""
    return [dec._dec_exe.arg_dict[n]._jax()
            for n in dec._admit_scatter.kv_names]


@pytest.mark.parametrize("case", ["L=1", "L=page-1", "L=page", "L=page+1",
                                  "L=prefill_len", "frames-not-contiguous"])
def test_admit_pool_update_is_bitwise_the_op_by_op_scatter(case, layout):
    """After ``admit`` every pool buffer is what the parent's
    ``ring.at[:, phys, :].set(new[0, :, :L, :])`` gave: the slots of
    positions 0..L-1 hold the prefill's K/V, every other slot its old bits
    (the rest of the last page included); in either layout, read by slot."""
    dec = _paged_for_pool(lanes=3).warmup()
    assert (_pool(dec)[0].shape[-1] == 128) == (layout == "page_major")
    page, P = dec.page_size, dec.prefill_len
    rs = np.random.RandomState(7)
    for name in dec._admit_scatter.kv_names:      # nothing to hide behind
        arr = dec._dec_exe.arg_dict[name]
        arr[:] = rs.randn(*arr.shape).astype("float32")
    if case == "frames-not-contiguous":
        sids = [dec.admit(np.full((page,), 1 + i, np.float32))[0]
                for i in range(3)]
        dec.retire(sids[1])
        L = P
    else:
        L = {"L=1": 1, "L=page-1": page - 1, "L=page": page,
             "L=page+1": page + 1, "L=prefill_len": P}[case]
    before = [_by_slot(a).copy() for a in _pool(dec)]
    sid, _ = dec.admit(rs.randint(1, CFG["vocab_size"], size=L)
                       .astype(np.float32))
    lane = dec._lanes[dec._seq_lane[sid]]
    if case == "frames-not-contiguous":
        assert np.any(np.abs(np.diff(lane.frames)) != 1)
    phys = [lane.frames[p // page] * page + p % page for p in range(L)]
    pf = dec._pf_cache.executable(dec._prefill_shapes())
    for old, got, new in zip(before, _pool(dec), pf.outputs[1:]):
        want = old.copy()
        want[:, phys, :] = np.asarray(new._jax())[0, :, :L, :]
        got = _by_slot(got)
        assert got.dtype == want.dtype and np.array_equal(got, want)
        rest = np.setdiff1d(np.arange(dec.total_slots), phys)
        assert np.array_equal(got[:, rest, :], old[:, rest, :])


def test_admit_scatter_is_one_sealed_donated_program(tm):
    """One compile serves every prompt length; each admission enqueues one
    pool-update program, which takes the pool donated: the arrays held
    before are dead afterwards and no second copy of a buffer lives."""
    import gc

    import jax

    tm.set_mode("counters")
    dec = _paged_for_pool(lanes=5).warmup()   # a pool shape no other test has
    prog = dec._admit_scatter
    shape = tuple(_pool(dec)[0].shape)

    def live():
        gc.collect()
        return sum(1 for a in jax.live_arrays() if tuple(a.shape) == shape)

    def moved(since):
        now = tm.counters()
        return {k: now.get(k, 0) - since.get(k, 0)
                for k in ("serving.admit_scatter_dispatches",
                          "serving.paged_admits", "executor.compile",
                          "executor.retrace")}

    # a step first, so the decode executable's outputs ARE the pool: the
    # state every admission after the first dispatch finds
    sid, logits = dec.admit(np.array([3, 1, 4], np.float32))
    dec.step({sid: int(np.argmax(logits))})
    assert live() == 2 * dec.num_layers
    for L in (1, 6, 10):
        held, c0 = _pool(dec), tm.counters()
        dec.admit(np.arange(1, L + 1, dtype=np.float32))
        assert moved(c0) == {"serving.admit_scatter_dispatches": 1,
                             "serving.paged_admits": 1,
                             "executor.compile": 0, "executor.retrace": 0}
        assert all(a.is_deleted() for a in held)
        assert not any(a.is_deleted() for a in _pool(dec))
        assert live() == 2 * dec.num_layers
    assert prog._fn._cache_size() == 1        # jit's own count: one compile

    # a drifted signature is the sealed-program error, before any donation
    held, c0 = _pool(dec), tm.counters()
    pf = dec._pf_cache.executable(dec._prefill_shapes())
    new = dec._prefill_cache(pf)
    with pytest.raises(MXNetError, match="sealed"):
        prog.run(dec, tuple(a[:, :, :-1] for a in new), [0], 1, 0)
    assert moved(c0)["executor.retrace"] == 1
    assert moved(c0)["serving.admit_scatter_dispatches"] == 0
    assert not any(a.is_deleted() for a in held)


def _sealed_program_case(dec, program):
    """(the program, a call with its warmed inputs, one with a drifted
    input, whether the good call donates the pool)."""
    from mxnet_tpu.serving import kv_decode as kd

    if program == "megastep":
        prog = kd._megastep_for(dec, 2, kd._sampler_from("greedy"))
    elif program == "chunk":
        prog = dec._chunk_for(4)
    else:
        prog = dec._admit_scatter
    sealed, rest = prog._dummy(dec)
    if program == "admit_scatter":
        drifted = tuple(a[:, :, :-1] for a in sealed)
        return (prog, lambda: prog.run(dec, sealed, *rest),
                lambda: prog.run(dec, drifted, *rest), True)
    drifted = (sealed[0][..., :-1],) + tuple(sealed[1:])
    return (prog, lambda: prog.run(dec, *sealed, *rest),
            lambda: prog.run(dec, *drifted, *rest), False)


@pytest.mark.parametrize("program", ["megastep", "chunk", "admit_scatter"])
def test_sealed_program_compiles_once_and_refuses_a_drifted_signature(
        tm, program):
    """The contract the three programs share through ``_SealedProgram``: one
    ``executor.compile`` at warm time, a cache hit and no compile for every
    dispatch with the warmed signature, and for a drifted one the sealed
    error under the program's own name and ``executor.retrace`` + 1 before
    anything is enqueued (the pool is not donated, jit holds one program)."""
    tm.set_mode("counters")
    dec = _paged_for_pool(lanes=2).warmup()
    c0 = tm.counters()
    prog, good, bad, donates = _sealed_program_case(dec, program)

    def moved(since):
        now = tm.counters()
        return [now.get(k, 0) - since.get(k, 0)
                for k in ("executor.compile", "executor.cache_hit",
                          "executor.retrace")]

    # built here: its one compile; built by warmup(): none since (the hit
    # is the prefill that ``_AdmitScatter._dummy`` stages its K/V with)
    assert moved(c0) == ([0, 1, 0] if program == "admit_scatter"
                         else [1, 0, 0])
    c0 = tm.counters()
    for _ in range(2):
        good()
    assert moved(c0) == [0, 2, 0]
    held, c0 = _pool(dec), tm.counters()
    name = {"megastep": r"decode megastep \(K=2\)",
            "chunk": r"chunk program \(T=4\)",
            "admit_scatter": "admit scatter"}[program]
    with pytest.raises(MXNetError, match=name + ": input signature drifted "
                       "from the warmed shapes .* sealed like the executable "
                       "cache"):
        bad()
    assert moved(c0) == [0, 0, 1]
    assert not any(a.is_deleted() for a in held)
    assert all(a is b for a, b in zip(held, _pool(dec)))
    assert prog._fn._cache_size() == 1
    good()                                  # and the seal still holds
    assert moved(c0) == [0, 1, 1]
    assert all(a.is_deleted() for a in held) == donates


def test_pool_readers_after_a_donated_admit_stay_token_identical(layout):
    """``step``, ``fork``, ``rollback``, a megastep and a chunk dispatch
    after an admission that donated the pool (the decode executable's
    outputs name dead arrays by then) read the pool through ``arg_dict``
    and reproduce ``greedy`` token for token."""
    prompt = np.array([3, 1, 4, 1, 5], np.float32)
    ref = _paged_for_pool(lanes=3).greedy([prompt], 6, k=1)[0]

    dec = _paged_for_pool(lanes=3).warmup()
    sid, logits = dec.admit(prompt)
    assert int(np.argmax(logits)) == ref[0]
    assert int(np.argmax(dec.step({sid: ref[0]})[sid])) == ref[1]
    other, _ = dec.admit(np.array([2, 7, 1, 8, 2, 8], np.float32))
    assert all(o._jax().is_deleted() for o in dec._dec_exe.outputs[1:-1])
    twin = dec.fork(sid)
    out = dec.step({sid: ref[1], twin: ref[1]})
    assert np.array_equal(out[sid], out[twin])
    assert int(np.argmax(out[sid])) == ref[2]
    dec.retire(other)
    dec.admit(np.array([9, 9, 9], np.float32))    # donates again, mid-flight
    assert list(dec.step_megastep({sid: ref[2]}, k=2)[sid]) == list(ref[3:5])
    rows = dec.verify_chunk(twin, ref[2:4])
    assert [int(np.argmax(r)) for r in rows] == list(ref[3:5])
    dec.rollback(twin, dec.position(twin) - 1)
    assert int(np.argmax(dec.step({twin: ref[3]})[twin])) == ref[4]
    assert int(np.argmax(dec.step({sid: ref[4]})[sid])) == ref[5]


# ------------------------------------------- a decode step owns its cache
def _fresh_outputs(dec):
    """``dec`` dispatching the SAME decode graph with nothing donated: every
    output a fresh buffer, the arrays a step read alive after it. What the
    donated program is held to, bit for bit."""
    dec._dec_cache._donated = ()
    return dec


def _cache(dec):
    return [dec._dec_exe.arg_dict[n]._jax() for n in dec._cache_names]


def _bitwise(a):
    return np.array(a, np.float32).tobytes()


def _owned_scenario(case, dec):
    """One use of the cache a step owns; returns every logits row, token and
    cache row the scenario read, in order, as bytes."""
    seen = []
    note = lambda a: seen.append(_bitwise(a))
    release = case == "warmup_release_outputs"
    dec.warmup(release_outputs=release)
    if release:
        assert dec._dec_exe.outputs == []
    prompt = np.array([3, 1, 4, 1, 5], np.float32)
    sid, logits = dec.admit(prompt)
    note(logits)
    tok = int(np.argmax(logits))
    for _ in range(2):
        row = dec.step({sid: tok})[sid]
        note(row)
        tok = int(np.argmax(row))
    if case == "lane_state":
        for name, value in sorted(dec.lane_state(sid).items()):
            note(value)
        note(dec.step({sid: tok})[sid])
        for name, value in sorted(dec.lane_state(sid).items()):
            note(value)
    elif case == "fork_copy_on_write":
        twin = dec.fork(sid)
        for a, b in ((7, 11), (2, 2)):      # both branches write the page
            out = dec.step({sid: a, twin: b})
            note(out[sid])
            note(out[twin])
        note(dec.step({twin: 5})[twin])
    elif case == "rollback":
        note(dec.step({sid: 9})[sid])
        dec.rollback(sid, dec.position(sid) - 2)
        note(dec.step({sid: tok})[sid])
    elif case == "verify_chunk":
        for row in dec.verify_chunk(sid, [tok, 7, 9]):
            note(row)
        dec.rollback(sid, dec.position(sid) - 1)
        note(dec.step({sid: 4})[sid])
    elif case == "megastep_after_steps":
        ids = dec.step_megastep({sid: tok}, k=3)[sid]
        note(ids)
        note(dec.step({sid: int(ids[-1])})[sid])
    elif case == "admission_between_steps":
        other, logits = dec.admit(np.array([2, 7, 1, 8, 2, 8], np.float32))
        note(logits)
        out = dec.step({sid: tok, other: int(np.argmax(logits))})
        note(out[sid])
        note(out[other])
    for buf in _cache(dec):     # what the scenario left in the cache
        note(buf)
    return seen


OWNED = ["steps", "lane_state", "fork_copy_on_write", "rollback",
         "verify_chunk", "megastep_after_steps", "admission_between_steps",
         "warmup_release_outputs"]


@pytest.mark.parametrize("case", OWNED)
def test_a_donated_step_gives_what_fresh_outputs_give(case, layout):
    """Every reader of the cache, after steps that took it donated, against
    the same decoder dispatching its decode graph un-donated (each output a
    fresh buffer, as before the step owned its cache): the same logits,
    tokens, state rows and cache, bit for bit."""
    arch = "granite_hybrid" if case == "lane_state" else "vaswani"
    got = _owned_scenario(case, _arch_decoder(arch))
    want = _owned_scenario(case, _fresh_outputs(_arch_decoder(arch)))
    assert len(got) == len(want) > 3
    assert got == want


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_a_step_leaves_dead_what_it_read_and_live_what_it_wrote(tm, arch):
    """The decode program takes every buffer of the cache, pools and rows,
    donated: after ``step`` the arrays ``arg_dict`` held are deleted and
    the ones it holds now are the program's outputs, alive; the weights and
    the logits are not donated; the warm dispatch hands its outputs back
    the same way, and the program's aliased bytes are the cache's."""
    tm.set_mode("counters")
    dec = _arch_decoder(arch).warmup()
    gauges = tm.snapshot()
    assert gauges["serving.decode_aliased_bytes"] \
        == gauges["serving.cache_bytes"] \
        == sum(a.nbytes for a in _cache(dec)) > 0
    assert not any(a.is_deleted() for a in _cache(dec))
    nxt = _admit_prompts(dec)
    exe = dec._dec_exe
    weights = [a._jax() for n, a in exe.arg_dict.items()
               if n not in dec._cache_names]
    before = _cache(dec)
    rows = dec.step(nxt)
    assert all(a.is_deleted() for a in before)
    after = _cache(dec)
    assert not any(a.is_deleted() for a in after)
    assert [id(a) for a in after] == [
        id(o._jax()) for o in exe.outputs[1:1 + len(after)]]
    assert not any(w.is_deleted() for w in weights)
    first = {sid: np.array(row) for sid, row in rows.items()}
    dec.step({sid: int(np.argmax(row)) for sid, row in rows.items()})
    assert all(a.is_deleted() for a in after)
    # a step's logits are its own: the next step's donation leaves them
    for sid, row in rows.items():
        np.testing.assert_array_equal(np.asarray(row), first[sid])
    writes = tm.counters()["serving.step_slot_writes"]
    assert writes == 2 * len(nxt) * len(dec._pool_names)


# ---------------- a step's ONE host input, kept between steps and patched
def _from_nothing(dec, tokens):
    """The four arrays the parent's ``step`` built FROM NOTHING at every
    step (its code, kept here to hold the patched array to it), side by
    side. Read after the step: a stepped lane's position is one behind
    ``lane.pos`` and its frames are what ``_phys_slot`` left."""
    B = dec.lanes
    data = np.zeros((B, 1), np.float32)
    pos_idx = np.zeros((B, 1), np.float32)
    write_slot = np.full((B, 1), -1, np.float32)
    table = np.zeros((B, dec.pool.frames_per_lane), np.float32)
    for seq_id, tok in tokens.items():
        idx = dec._seq_lane[seq_id]
        lane = dec._lanes[idx]
        page, off = divmod(lane.pos - 1, dec.page_size)
        write_slot[idx, 0] = lane.frames[page] * dec.page_size + off
        data[idx, 0] = float(np.asarray(tok).reshape(()))
        pos_idx[idx, 0] = lane.pos - 1
        table[idx, :len(lane.frames)] = lane.frames
    return np.concatenate([data, pos_idx, write_slot, table], axis=1)


def _aligned(array, offset):
    """A copy of ``array`` whose memory starts ``offset`` bytes past a
    64-byte boundary: at 0 the CPU backend's ``jax.device_put`` ALIASES it
    (the device array is the host's memory, whatever is written there
    later), at 4 it copies."""
    raw = np.zeros(array.nbytes + 128, np.uint8)
    start = (-raw.ctypes.data) % 64 + offset
    out = raw[start:start + array.nbytes].view(array.dtype).reshape(
        array.shape)
    out[...] = array
    assert out.ctypes.data % 64 == offset
    return out


class _Pair:
    """A decoder whose staged array the CPU backend aliases, beside a twin
    driven alike whose array is made idle and whose every row is marked stale
    before each step (built from nothing, and copied by the transfer). Every
    step is held to three things: ONE ``jax.device_put``, of the staged
    array; that array, as it was handed over, equal to the parent's four
    element for element; and the rows' logits equal to the twin's bit for
    bit (a patch that came before the program's read would show here)."""

    def __init__(self, make, monkeypatch):
        import jax

        self.dec, self.twin = make().warmup(), make().warmup()
        self.dec._step_in = _aligned(self.dec._step_in, 0)
        self.twin._step_in = _aligned(self.twin._step_in, 4)
        self.puts = puts = []
        put = jax.device_put

        def recording(x, *args, **kwargs):
            puts.append((x, np.array(x) if isinstance(x, np.ndarray) else x))
            return put(x, *args, **kwargs)

        monkeypatch.setattr(jax, "device_put", recording)
        self.steps = 0

    def both(self, call):
        """``call(decoder)`` on the twin, then on the decoder, whose answer
        is returned: ``PagedKVExhausted`` where the pool refused, which it
        does to both or to neither."""
        outs = []
        for dec in (self.twin, self.dec):
            try:
                outs.append(call(dec))
            except PagedKVExhausted:
                outs.append(PagedKVExhausted)
        assert (outs[0] is PagedKVExhausted) == (outs[1] is PagedKVExhausted)
        return outs

    def step(self, tokens):
        """The step's rows, or None where the pool refused it (both)."""
        dec, twin = self.dec, self.twin
        twin._step_in[:] = twin._idle_row
        for lane in twin._lanes.values():
            lane.stale = True
        twin._stepped = {}
        del self.puts[:]
        want, got = self.both(lambda d: d.step(tokens))
        if got is PagedKVExhausted:
            # a refused step leaves every row idle behind it
            np.testing.assert_array_equal(
                dec._step_in, np.tile(dec._idle_row, (dec.lanes, 1)))
            return None
        (_, _), (given, seen) = self.puts       # the twin's, then this one
        assert given is dec._step_in
        # the premise: what the program was handed IS the host's memory
        assert dec._dec_exe.arg_dict["step_in"]._jax() \
            .unsafe_buffer_pointer() == given.ctypes.data
        np.testing.assert_array_equal(seen, _from_nothing(self.dec, tokens))
        np.testing.assert_array_equal(self.dec._step_in, seen)
        assert sorted(got) == sorted(want) == sorted(tokens)
        for seq in tokens:
            np.testing.assert_array_equal(np.asarray(got[seq]),
                                          np.asarray(want[seq]))
        self.steps += 1
        return got

    def caches_agree(self):
        for name in self.dec._cache_names:
            np.testing.assert_array_equal(
                np.array(self.dec._dec_exe.arg_dict[name]._jax()),
                np.array(self.twin._dec_exe.arg_dict[name]._jax()))


WALKS = {
    # what the arch's cache holds beside (or in place of) pools, and whether
    # pages can be shared: pools only; pools with the prefix cache's adopted
    # pages; per-lane rows; rows, rings and one pool
    "pool_only": ("vaswani", {}),
    "prefix_cache": ("vaswani", dict(prefix_cache=True, prefix_chunk=4)),
    "hybrid_rows": ("granite_hybrid", {}),
    "window_rings": ("phi4flash", {}),
}


@pytest.mark.parametrize("walk", sorted(WALKS))
def test_the_staged_array_is_what_the_parent_built_from_nothing(
        tm, monkeypatch, walk):
    """A seeded random walk over admit, step of random subsets, retire and,
    where pages can be shared, fork, rollback, a shared page's copy and a
    prefix-cache hit, in pages of 4 so that lanes cross page boundaries all
    the time: at EVERY step the one array the decoder keeps and patches is
    the concatenation of the four the parent built from nothing."""
    import test_rebind

    arch, kw = WALKS[walk]
    shares = arch == "vaswani"
    pair = _Pair(lambda: test_rebind._decoder(arch, lanes=4, **kw),
                 monkeypatch)
    dec = pair.dec
    rs = np.random.RandomState(51)
    vocab = test_rebind.ARCHS[arch]["vocab_size"]
    prompts = [rs.randint(1, vocab, n).astype(np.float32)
               for n in (3, 4, 7, 8, 9, 13)]
    seen = dict(admit=0, retire=0, fork=0, rollback=0, subset=0, crossed=0,
                hit=0, cow=0)
    tm.set_mode("counters")
    for _ in range(90):
        active = dec.active
        for seq in active:          # a lane at its quota's end leaves
            if dec.position(seq) >= test_rebind.S - 1:
                pair.both(lambda d: d.retire(seq))
        active = dec.active
        op = rs.choice(["admit", "step", "step", "step", "retire",
                        "fork", "rollback"])
        if op == "admit" and len(active) < dec.lanes:
            prompt = prompts[rs.randint(len(prompts))]
            seen["admit"] += pair.both(
                lambda d: d.admit(prompt)[0])[1] is not PagedKVExhausted
        elif op == "retire" and active:
            seq = active[rs.randint(len(active))]
            pair.both(lambda d: d.retire(seq))
            seen["retire"] += 1
        elif op == "fork" and shares and active \
                and len(active) < dec.lanes:
            seq = active[rs.randint(len(active))]
            seen["fork"] += pair.both(
                lambda d: d.fork(seq))[1] is not PagedKVExhausted
        elif op == "rollback" and shares and active:
            seq = active[rs.randint(len(active))]
            to = rs.randint(1, dec.position(seq) + 1)
            pair.both(lambda d: d.rollback(seq, to))
            seen["rollback"] += 1
        elif op == "step" and active:
            some = [s for s in active if rs.rand() < 0.7] or active[:1]
            seen["subset"] += len(some) < len(active)
            seen["crossed"] += sum(
                dec.position(s) % test_rebind.PAGE == 0 for s in some)
            pair.step({s: int(rs.randint(1, vocab)) for s in some})
    c = tm.counters()
    seen["hit"], seen["cow"] = (c.get("serving.prefix_hits", 0),
                                c.get("serving.cow_copies", 0))
    pair.caches_agree()
    assert pair.steps >= 25 and seen["subset"] and seen["crossed"] >= 5
    assert seen["admit"] >= 4 and seen["retire"] >= 2
    if shares:
        assert seen["fork"] and seen["rollback"] and seen["cow"]
    if walk == "prefix_cache":
        assert seen["hit"]


def test_a_lane_left_out_of_a_step_rides_along_and_comes_back(
        monkeypatch, layout):
    """Stepped, left out of ``tokens``, stepped again: while it is left out
    its row is the idle row (token 0, position 0, write slot -1, no page),
    as a ride-along row always was, and when it comes back its three numbers
    AND its table row are written again, a page crossed meanwhile by the
    other lane and by itself included."""
    S = 24
    _, _, params = _trained_params(S)
    pair = _Pair(lambda: _paged(params, S, lanes=3), monkeypatch)
    dec = pair.dec
    (_, a), (_, b) = [pair.both(lambda d: d.admit(np.asarray(p, np.float32)))
                      for p in ([3, 1, 4, 1, 5, 9, 2], [2, 7, 1])]
    a, b = a[0], b[0]
    row = lambda seq: dec._step_in[dec._seq_lane[seq]]
    pair.step({a: 5, b: 6})                      # a writes 7, b writes 3
    assert row(a)[2] >= 0 and row(b)[2] >= 0
    for tok in (7, 8):                           # b crosses into its page 1
        pair.step({b: tok})
        np.testing.assert_array_equal(row(a), dec._idle_row)
    pair.step({a: 9, b: 1})                      # a comes back, at page 2
    assert list(row(a)[:2]) == [9, 8] and row(a)[2] == \
        dec._lanes[dec._seq_lane[a]].frames[2] * 4
    assert list(row(a)[3:6]) == list(dec._lanes[dec._seq_lane[a]].frames)
    pair.step({a: 2})                            # and b is the one left out
    np.testing.assert_array_equal(row(b), dec._idle_row)
    pair.both(lambda d: d.retire(b))
    pair.step({a: 3})
    pair.caches_agree()
    assert pair.steps == 6


def test_the_stage_counts_its_one_array_and_the_table_rows_it_wrote(tm):
    """``serving.step_staged_arrays`` = ``serving.paged_steps`` (four arrays a
    step on the parent); ``serving.step_table_rows_written``: nothing over
    steps in which no lane crossed a page and none was admitted, left out or
    retired, else those lanes (every stepped lane every step on the
    parent)."""
    tm.set_mode("counters")
    S = 32
    _, _, params = _trained_params(S)
    dec = _paged(params, S, lanes=3).warmup()
    a, _ = dec.admit(np.asarray([3, 1, 4, 1, 5], np.float32))   # at 5
    b, _ = dec.admit(np.asarray([2, 7, 1], np.float32))         # at 3
    wrote = []
    for tokens in ({a: 1, b: 1},    # both admitted since the last step: 2
                   {a: 1, b: 1},    # b writes position 4, a new page: 1
                   {a: 1, b: 1},    # 7 and 5: none
                   {a: 1, b: 1},    # a writes position 8, a new page: 1
                   {a: 1},          # b left out, its row idle again: 1
                   {a: 1},          # 10: none
                   {a: 1, b: 1},    # b back (7): its row again: 1
                   {a: 1, b: 1}):   # a at 12 and b at 8 both cross: 2
        before = tm.counters().get("serving.step_table_rows_written", 0)
        dec.step(tokens)
        wrote.append(tm.counters()["serving.step_table_rows_written"]
                     - before)
    assert wrote == [2, 1, 0, 1, 1, 0, 1, 2]
    dec.retire(b)
    dec.step({a: 1})                # the retired lane's row goes idle: 1
    dec.step({a: 1})                # 14: none
    c = tm.counters()
    assert c["serving.step_table_rows_written"] == sum(wrote) + 1
    assert c["serving.step_staged_arrays"] == c["serving.paged_steps"] == 10
    assert c["serving.step_input_bytes"] == 10 * 3 * (3 + S // 4) * 4
