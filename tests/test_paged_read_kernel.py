"""The kernel that walks the page table (``ops/pallas_paged_read.py``),
interpreted on the CPU, against XLA's own-pages form of the same read on the
same page-major pools: ``KVPoolAttention`` with its rule held to each form.

The cases are the five ``generate`` cells' (dtype, key/value heads, group,
key width, value width), the widths as published where the CPU affords
them, at few lanes and a short table that the block does not divide."""
import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu.ops import attention
from mxnet_tpu.ops.attention import _kv_pool_attention, pool_read_form
from mxnet_tpu.ops.pallas_paged_read import (paged_read, pages_per_block,
                                             supported)

# 40 pages under a block of 16: two blocks and a half
PAGE, MAX_PAGES, LANES = 16, 40, 4
# cell -> (dtype, Hkv, G, dk, dv, scale, tolerance of a row's norm)
CELLS = {
    # 64 heads over 4 of 192 / 128 there; the same 3 : 2 at half the width
    "mimo-v2-flash": ("bfloat16", 4, 16, 96, 64, -1.0, 1e-2),
    "transformer-base": ("float32", 8, 1, 64, 64, -1.0, 1e-5),
    "granite-4.0-h-micro": ("bfloat16", 8, 4, 64, 64, 0.015625, 1e-2),
    "lfm2-24b-a2b": ("bfloat16", 8, 4, 64, 64, -1.0, 1e-2),
    # 16 heads of 128, a row of 2,048 a pool: the widest any cell runs
    "ouro-2.6b": ("bfloat16", 16, 1, 128, 128, -1.0, 1e-2),
}


def _block(cell):
    """The slots of the kernel's block at this cell's rows and this table
    (its edge is a case)."""
    dtype, hkv, _, dk, dv, _, _ = CELLS[cell]
    return PAGE * pages_per_block(
        MAX_PAGES, PAGE, hkv * (dk + dv) * jnp.dtype(dtype).itemsize)


# case -> the contexts of the four lanes; lane 0 is the one the case names
CONTEXTS = {
    "none": lambda b: [0, 5, 40, 3],
    "one_slot": lambda b: [1, 2 * PAGE, 7, 0],
    "page_less_one": lambda b: [PAGE - 1, 1, b + 1, 90],
    "a_page": lambda b: [PAGE, 0, 33, 2],
    "page_and_one": lambda b: [PAGE + 1, PAGE, 5, 60],
    "a_blocks_edge": lambda b: [b, b + 1, 2 * b, 2 * b - 1],
    "the_whole_table": lambda b: [PAGE * MAX_PAGES, 17, PAGE * MAX_PAGES, 1],
    "ragged": lambda b: [2 * b + 5, 7, 0, 5 * PAGE + 9],
    # the partial tail: a last block fetches its live pages and no more
    "a_pages_edge_in_a_block": lambda b: [3 * PAGE, b + 2 * PAGE, 5 * PAGE,
                                          PAGE],
    "one_slot_into_a_second_page": lambda b: [b + PAGE + 1, PAGE + 1,
                                              2 * b + PAGE + 1, 1],
    # the prefetch of the NEXT row's first block takes that row's page count
    "none_between_two": lambda b: [b + 3, 0, 2 * PAGE + 1, 5],
    "a_tail_after_a_whole_block": lambda b: [1, b, 0, b + 1],
}


def _step(cell, contexts, shared):
    """One step's operands over seeded page-major pools: a table of frames
    drawn without order (never frame 0, which the padding names), zeros past
    a lane's pages; ``shared``: the first two lanes that have a context hold
    the same first frame."""
    dtype, hkv, group, dk, dv, _, _ = CELLS[cell]
    rs = np.random.RandomState(len(cell) + sum(contexts))
    frames = LANES * MAX_PAGES
    pool_k = jnp.asarray(rs.randn(frames, PAGE, hkv * dk), dtype)
    pool_v = jnp.asarray(rs.randn(frames, PAGE, hkv * dv), dtype)
    query = jnp.asarray(rs.randn(LANES, hkv * group, dk) * (3.0 / np.sqrt(dk)),
                        dtype)
    free = list(1 + rs.permutation(frames - 1))
    table = np.zeros((LANES, MAX_PAGES), np.float32)
    for lane, n in enumerate(contexts):
        pages = -(-n // PAGE)
        table[lane, :pages] = [free.pop() for _ in range(pages)]
    if shared:
        first, second = [lane for lane, n in enumerate(contexts) if n][:2]
        table[second, 0] = table[first, 0]
    pos_idx = np.asarray([[max(n - 1, 0)] for n in contexts], np.float32)
    write_slot = np.asarray([[5.0 if n else -1.0] for n in contexts],
                            np.float32)
    return query, pool_k, pool_v, table, pos_idx, write_slot


def _read(monkeypatch, form, cell, step):
    query, pool_k, pool_v, table, pos_idx, write_slot = step
    monkeypatch.setattr(attention, "pool_read_form", lambda *a: form)
    # no form reads the mask: a step's program builds none
    return np.asarray(_kv_pool_attention(
        {"scale": CELLS[cell][5], "value_dim": 0, "page_size": PAGE},
        query, pool_k, pool_v, None, jnp.asarray(table), jnp.asarray(pos_idx),
        jnp.asarray(write_slot)), np.float32)


@pytest.mark.parametrize("shared", [False, True],
                         ids=["own_frames", "shared_frame"])
@pytest.mark.parametrize("case", list(CONTEXTS))
@pytest.mark.parametrize("cell", list(CELLS))
def test_the_kernel_reads_what_the_own_pages_form_reads(monkeypatch, cell,
                                                        case, shared):
    """Every lane with a context agrees with XLA's gathered read to the
    cell's tolerance of its norm (float32: the order of a sum; bfloat16: the
    probabilities' one rounding); a lane with none comes out finite (zeros
    from the kernel) and moves no other lane."""
    dtype, hkv, group, dk, dv, _, tol = CELLS[cell]
    contexts = CONTEXTS[case](_block(cell))
    step = _step(cell, contexts, shared)
    assert supported(*step[:3])
    kernel = _read(monkeypatch, "kernel", cell, step)
    gathered = _read(monkeypatch, "own_pages", cell, step)
    assert kernel.shape == gathered.shape == (LANES, hkv * group, dv)
    assert np.isfinite(kernel).all()
    for lane, n in enumerate(contexts):
        if n == 0:
            assert not kernel[lane].any()
            continue
        norm = np.linalg.norm(gathered[lane], axis=-1).max()
        assert np.abs(kernel[lane] - gathered[lane]).max() <= tol * norm, \
            (lane, n)
    if contexts[0] == 1:
        # the softmax of one slot is 1: the context IS that slot's value
        frame = int(step[3][0, 0])
        want = np.asarray(step[2], np.float32)[frame, 0].reshape(hkv, 1, dv)
        np.testing.assert_allclose(
            kernel[0].reshape(hkv, group, dv),
            np.broadcast_to(want, (hkv, group, dv)), rtol=tol, atol=tol)


def test_the_rule_names_the_kernel_on_the_chip_alone(monkeypatch):
    """``pool_read_form`` at a cell's operands: own pages on the CPU, the
    kernel where the backend is the chip's; a pool that is key and value
    both, a page that is no whole tile and a read with no table are not the
    kernel's anywhere."""
    import jax

    spec = jax.ShapeDtypeStruct
    query = spec((32, 32, 64), "bfloat16")
    pool = spec((4096, 16, 512), "bfloat16")
    table = spec((32, 128), "float32")
    assert pool_read_form(query, pool, pool, table, 16) == "own_pages"
    monkeypatch.setattr(attention, "_backend", lambda: "tpu")
    assert pool_read_form(query, pool, pool, table, 16) == "kernel"
    assert pool_read_form(query, pool, None, table, 16) == "own_pages"
    assert pool_read_form(query, pool, pool, None, 0) == "whole_pool"
    small_page = spec((8192, 8, 512), "bfloat16")
    assert not supported(query, small_page, small_page)
    assert pool_read_form(query, small_page, small_page, table, 8) \
        == "own_pages"
    assert supported(spec((32, 32, 64), "float32"),
                     spec((8192, 8, 512), "float32"),
                     spec((8192, 8, 512), "float32"))
    # a query of another type than the pools is XLA's
    assert not supported(spec((32, 32, 64), "float32"), pool, pool)


@pytest.mark.parametrize("max_pages,page,row_bytes,pages", [
    (20, 16, 8192, 8),      # ouro-2.6b.generate: 128 slots, 1 MB (a page was)
    (64, 16, 4096, 16),     # transformer-base.generate: 256 slots, 1 MB
    (128, 16, 2048, 16),    # granite, lfm2: 256 slots, 512 KB
    (512, 16, 2560, 16),    # mimo-v2-flash.generate: 256 slots, 640 KB
    (512, 16, 5120, 13),    # phi-4-mini-flash-reasoning: 208 slots, 1 MB
    (512, 16, 1024, 16),    # nemotron: 256 slots of a row of 256 lanes
    (3, 16, 2048, 3),       # a table of three pages: all three
    (7, 16, 8192, 7),       # a block need not divide the table, nor it a block
    (96, 32, 2048, 8),      # pages of 32: 256 slots are eight
    (512, 16, 1 << 20, 1),  # a row so wide that two buffers fit a page alone
])
def test_the_block_follows_the_bytes_in_flight(max_pages, page, row_bytes,
                                               pages):
    """A block brings ``_BLOCK_BYTES`` of keys and values where 256 slots,
    the scratch and the table allow; the table's length sizes nothing
    else."""
    from mxnet_tpu.ops import pallas_paged_read as kernel

    assert pages_per_block(max_pages, page, row_bytes) == pages
    assert pages * page <= kernel._BLOCK_SLOTS or pages == 1
    assert 2 * pages * page * row_bytes <= kernel._SCRATCH_BYTES or pages == 1
    assert pages == pages_per_block(max_pages * 16, page, row_bytes) \
        or pages == max_pages


def test_a_call_outside_the_operator_takes_contexts_as_given():
    """``paged_read`` itself: int32 table and contexts, float32 out."""
    step = _step("transformer-base", [40, 0, 3, 100], False)
    query, pool_k, pool_v, table, _, _ = step
    out = paged_read(query, pool_k, pool_v, jnp.asarray(table, jnp.int32),
                     jnp.asarray([40, 0, 3, 100], jnp.int32), scale=0.125,
                     interpret=True)
    assert out.shape == (LANES, 8, 64) and out.dtype == jnp.float32
    assert not np.asarray(out[1]).any() and np.isfinite(np.asarray(out)).all()


# --------------------------------------------- the kernel through the decoder
def _decoder(monkeypatch, form):
    """A Vaswani decoder whose pools are page-major (2 heads of 64: a row of
    128; pages of 16, a table of 8 pages) with the rule held to ``form``."""
    import mxnet_tpu as mx
    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.serving import PagedKVDecoder

    monkeypatch.setattr(attention, "pool_read_form", lambda *a: form)
    cfg = dict(vocab_size=48, num_layers=2, num_heads=2, model_dim=128,
               ffn_dim=64)
    net = tf.get_symbol(seq_len=128, **cfg)
    shapes, _, _ = net.infer_shape(data=(1, 128), softmax_label=(1, 128))
    rs = np.random.RandomState(2)
    params = {n: mx.nd.array((rs.randn(*s) * 0.1).astype("f"))
              for n, s in zip(net.list_arguments(), shapes)
              if n not in ("data", "softmax_label")}
    return PagedKVDecoder(params, max_len=128, page_size=16, lanes=4,
                          prefill_len=32, pos_len=128, **cfg)


def test_a_decoder_steps_through_the_kernel_and_says_what_it_fetched(
        monkeypatch):
    """Admissions of unequal lengths, a lane that joins late and one that
    retires, stepped side by side through the (interpreted) kernel and
    through XLA's gather: the same logits to float32's sum order, the same
    greedy tokens; and the telemetry says how many of the program's reads
    are the kernel's, what one layer's kernel fetched (each stepped lane's
    context rounded up to a page) and in how many loop turns (a block
    each)."""
    from mxnet_tpu import telemetry as tm
    from mxnet_tpu.ops import pallas_paged_read as kernel

    saved = tm.current_override()
    tm.set_mode("counters")
    try:
        # a block of 32 slots under this table of 128, so that contexts cross
        # blocks here as they do at a cell's widths
        monkeypatch.setattr(kernel, "_BLOCK_BYTES", 2 * PAGE * 2 * 128 * 4)
        logits, block = [], PAGE * pages_per_block(8, PAGE, 2 * 128 * 4)
        assert block == 2 * PAGE
        for form in ("kernel", "own_pages"):
            tm.reset()
            dec = _decoder(monkeypatch, form).warmup()
            assert dec._dec_exe.arg_dict["kv_k_0"].shape == (32, 16, 128)
            snap = tm.snapshot()
            for name in ("kernel", "own_pages", "whole_pool"):
                assert snap["serving.pool_read.%s_layers" % name] \
                    == 2 * (name == form)
            seqs = [dec.admit(np.arange(n) % 47)[0] for n in (3, 15, 32)]
            rows, fetched, blocks = [], 0, 0
            for t in range(20):
                if t == 4:
                    seqs.append(dec.admit(np.arange(5) % 43)[0])
                if t == 8:
                    dec.retire(seqs.pop(0))
                contexts = [dec.position(s) + 1 for s in seqs]
                fetched += sum(-(-n // PAGE) * PAGE for n in contexts)
                blocks += sum(-(-n // block) for n in contexts)
                out = dec.step({s: (7 * t + s) % 48 for s in seqs})
                rows += [np.asarray(out[s]) for s in seqs]
            logits.append(np.stack(rows))
            moved = tm.snapshot()
            if form == "kernel":
                assert moved["serving.step_kernel_slots"] == fetched
                assert moved["serving.step_gathered_slots"] == 0
                # what it fetched is the contexts, rounded up to a PAGE
                # whatever the block; its loop turns, a block each
                assert 1.0 <= fetched / moved["serving.step_context_tokens"] \
                    < 2.0
                assert moved["serving.step_kernel_blocks"] == blocks
                assert len(rows) < blocks < fetched // PAGE
            else:
                assert "serving.step_kernel_slots" not in moved
                assert "serving.step_kernel_blocks" not in moved
                assert moved["serving.step_gathered_slots"] \
                    == moved["serving.paged_steps"] * 4 * 128
        np.testing.assert_allclose(logits[0], logits[1], rtol=2e-5, atol=2e-5)
        assert (logits[0].argmax(-1) == logits[1].argmax(-1)).all()
    finally:
        tm.set_mode(saved)
        tm.reset()


def test_the_rules_over_shapes_import_no_pallas():
    """``jax.experimental.pallas`` costs a process 1.5 to 2 s, and a process
    that loads its decode program from the program store traces no kernel:
    importing the kernel's module, asking its rules over shapes
    (``supported``, ``block_slots``) and the form rule at a cell's operands
    with the backend held to the chip's imports nothing of Pallas; tracing
    the kernel does."""
    import os
    import subprocess
    import sys

    code = """
import sys
import jax
from mxnet_tpu.ops import attention, pallas_paged_read as kernel
spec = jax.ShapeDtypeStruct
query, pool = spec((32, 32, 64), "bfloat16"), spec((4096, 16, 512), "bfloat16")
attention._backend = lambda: "tpu"
assert attention.pool_read_form(
    query, pool, pool, spec((32, 128), "float32"), 16) == "kernel"
assert kernel.supported(query, pool, pool)
assert kernel.block_slots(pool, pool, 128) == 256
pallas = lambda: sorted(m for m in sys.modules if "pallas" in m
                        and not m.startswith("mxnet_tpu"))
assert pallas() == [], pallas()
jax.eval_shape(lambda *a: kernel.paged_read(*a, scale=1.0, interpret=True),
               query, pool, pool, spec((32, 128), "int32"),
               spec((32,), "int32"))
assert "jax.experimental.pallas" in pallas()
print("OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0 and out.stdout.strip() == "OK", out.stderr
