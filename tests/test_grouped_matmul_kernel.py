"""The experts' grouped matmul (``ops/pallas_grouped_matmul.py``),
interpreted on the CPU, against ``jax.lax.ragged_dot`` on the same operands:
the kernel's two calls alone at small shapes, then ``MoEFeedForward`` with
its rule held to the kernel against the operator as it stands, then a
decoder whose gauges say which form its programs run."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import moe
from mxnet_tpu.ops import pallas_grouped_matmul as kernel

TM = 16     # the row tile of the cases below
# case -> (rows M, the groups' sizes): what the rows of a tile can belong to
GROUPS = {
    "even": (64, [16, 16, 16, 16]),
    "one_expert_has_every_row": (48, [0, 0, 48, 0]),
    "empty_groups": (48, [0, 20, 0, 0, 28, 0]),
    "a_group_straddles_tiles": (64, [5, 40, 3, 16]),
    "many_groups_in_one_tile": (32, [1, 2, 0, 3, 1, 4, 5, 16]),
    # an assignment to an expert the layer does not hold sorts past the
    # last group: 23 rows of 64 held, two whole tiles of nothing
    "rows_past_the_last_group": (64, [10, 0, 13]),
    "no_row_is_held": (32, [0, 0, 0]),
    "rows_no_multiple_of_the_tile": (40, [7, 21, 12]),
    "past_the_groups_and_no_multiple": (56, [9, 0, 17, 4]),
    # what a ring of buffers can get wrong
    "fewer_runs_than_the_ring": (32, [20, 12]),
    "a_run_longer_than_the_ring": (112, [4, 0, 92, 16]),
    "full_experts_between_empty_ones": (80, [0, 16, 0, 0, 33, 0, 31, 0, 0]),
}
K, N = 32, 256      # two column tiles of 128: the ring is primed twice
# the buffers a matrix of the ring the kernel fetches into by hand; 0: the
# pipeline's own blocks, the form as PR 41 shipped it
DEPTHS = [0, 1, 2, 3]


def _operands(case, dtype, seed=0):
    m, sizes = GROUPS[case]
    rs = np.random.RandomState(seed + m + len(sizes))
    draw = lambda *shape: jnp.asarray(rs.randn(*shape).astype("f"))
    stacks = [draw(len(sizes), K, N) * 0.3 for _ in range(2)]
    # an expert that received no row is never fetched: were it, the output
    # would say so
    stacks = [jnp.where((np.asarray(sizes) > 0)[:, None, None], w, np.nan)
              .astype(dtype) for w in stacks]
    return draw(m, K).astype(dtype), stacks, jnp.asarray(sizes, jnp.int32)


def _ragged(rows, weight, sizes):
    """XLA's form; a row past the last group is not defined there: zero."""
    out = jax.lax.ragged_dot(rows, jnp.nan_to_num(weight), sizes,
                             preferred_element_type=jnp.float32)
    held = np.arange(rows.shape[0]) < int(sizes.sum())
    return jnp.where(held[:, None], out, 0)


@pytest.mark.parametrize("depth", DEPTHS)
@pytest.mark.parametrize("fused", [False, True], ids=["down", "gate_up"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(GROUPS))
def test_the_kernel_multiplies_what_ragged_dot_multiplies(case, dtype, fused,
                                                          depth):
    """One stack: the float32 products of every row with its own expert's
    matrix, zeros past the last group. Two stacks: ``silu(dot) * dot`` on the
    float32 sums, cast ONCE to the storage type. Products in the storage
    type and sums in float32 on both sides: what differs is the order of a
    float32 sum and, fused, one rounding of the result. Whatever the ring's
    ``depth``: it says when a matrix arrives, and the output is the
    pipeline-fed form's bit for bit."""
    rows, (gate, up), sizes = _operands(case, dtype)
    meta = kernel.visits(sizes, rows.shape[0], TM)
    call = lambda buffers: kernel.grouped_matmul(
        rows, (gate, up) if fused else (gate,), meta, tm=TM, tn=128,
        depth=buffers, interpret=True)
    got = call(depth)
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(call(0), np.float32))
    want = _ragged(rows, gate, sizes)
    if fused:
        want = (jax.nn.silu(want) * _ragged(rows, up, sizes)).astype(dtype)
        assert got.dtype == jnp.dtype(dtype)
    else:
        assert got.dtype == jnp.float32
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    assert not got[int(sizes.sum()):].any()
    # a float32 sum's order; fused in bfloat16, one unit of its last place
    tol = 2.0 ** -7 if fused and dtype == "bfloat16" else 1e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("tm", [8, 16, 64])
def test_the_visits_are_the_pairs_that_share_a_row(seed, tm):
    """Drawn group sizes, some empty, some rows past the last group: the
    first ``count`` visits are exactly the (tile, expert) pairs with a row in
    common, in order, plus one visit for every tile past the groups (which
    owns no row of its expert's); never more than tiles + experts - 1; and
    what follows repeats the last visit's blocks. The RUNS are a plain walk
    over those visits: a new run wherever the expert changes, each run's
    expert the one its visits name, and their count."""
    rs = np.random.RandomState(seed)
    experts = int(rs.randint(1, 12))
    sizes = rs.randint(0, 40, experts) * (rs.rand(experts) < 0.6)
    rows = int(sizes.sum()) + int(rs.randint(0, 3 * tm))
    rows += -rows % 8 or 8
    off, tile, expert, count, run, run_expert, runs = (
        np.asarray(a) for a in kernel.visits(jnp.asarray(sizes, jnp.int32),
                                             rows, tm))
    n_tiles = -(-rows // tm)
    assert len(tile) == len(expert) == n_tiles + experts - 1
    assert list(off) == [0] + list(np.cumsum(sizes))
    owner = np.repeat(np.arange(experts), sizes)         # a held row's expert
    want = sorted({(r // tm, e) for r, e in enumerate(owner)})
    count = int(count[0])
    got = list(zip(tile[:count], expert[:count]))
    shares = lambda t, e: off[e] < (t + 1) * tm and off[e + 1] > t * tm
    assert [v for v in got if shares(*v)] == want
    assert sorted(got) == got and len(set(got)) == count
    # every tile is visited (a tile nobody owns is written as zeros)
    assert sorted({t for t, _ in got}) == list(range(n_tiles))
    assert all(v == got[-1] for v in zip(tile[count:], expert[count:]))
    walk, experts_of = [], []
    for e in expert:
        if not experts_of or e != experts_of[-1]:
            experts_of.append(e)
        walk.append(len(experts_of) - 1)
    assert list(run) == walk and int(runs[0]) == len(experts_of)
    assert len(run_expert) == min(experts, len(tile))
    assert list(run_expert[:len(experts_of)]) == experts_of
    assert all(e == experts_of[-1] for e in run_expert[len(experts_of):])
    # a run is one non-empty group's visits (one run of nothing if none is)
    assert experts_of == list(np.flatnonzero(sizes)) or not sizes.any()


@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("fused", [False, True], ids=["down", "gate_up"])
@pytest.mark.parametrize("case", [
    "even", "one_expert_has_every_row", "many_groups_in_one_tile",
    "rows_past_the_last_group", "no_row_is_held", "fewer_runs_than_the_ring",
    "a_run_longer_than_the_ring", "full_experts_between_empty_ones"])
def test_the_ring_is_read_after_it_lands_and_written_after_it_is_read(
        case, fused, depth):
    """The by-hand fetch under Pallas's TPU interpreter, which runs a copy
    only when it is WAITED for and watches every buffer for a race: a slot
    read before its copy was waited for holds what the last run left there,
    a slot written while a run still reads it is reported, and a copy
    started and never waited for (or waited for and never started) hangs or
    leaves its semaphore non-zero. The output is the pipeline-fed form's bit
    for bit."""
    from jax.experimental.pallas import tpu as pltpu
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call

    rows, stacks, sizes = _operands(case, "bfloat16")
    meta = kernel.visits(sizes, rows.shape[0], TM)
    call = lambda buffers, interpret: kernel.grouped_matmul(
        rows, tuple(stacks) if fused else (stacks[0],), meta, tm=TM, tn=128,
        depth=buffers, interpret=interpret)
    got = call(depth, pltpu.InterpretParams(detect_races=True,
                                            dma_execution_mode="on_wait"))
    assert not interpret_pallas_call.races.races_found
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(call(0, True), np.float32))


@pytest.mark.parametrize("layer", ["olmoe", "kanana", "a_held_share"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_operator_through_the_kernel_is_the_operator(monkeypatch, layer,
                                                         dtype):
    """``MoEFeedForward`` end to end with its rule held to the kernel
    (interpreted) against the operator as it stands: softmax scores over all
    the experts; sigmoid scores with a selection bias, renormalised and
    scaled; and a layer that holds 8 of 32 experts, whose absent
    assignments' rows ride along and add nothing. The router, the sort and
    the un-sort are shared, so the loads are equal and the outputs differ by
    the one rounding of the fused activation."""
    rs = np.random.RandomState(3)
    n, d, f, e = 24, 48, 32, 32
    draw = lambda *shape: jnp.asarray(rs.randn(*shape).astype("f") * 0.2)
    x, router, bias = draw(n, d) * 5, draw(e, d), draw(e) * 2
    attrs = dict(num_experts=e, num_hidden=f, num_experts_per_tok=4)
    first, held = 0, e
    if layer != "olmoe":
        attrs.update(scoring="sigmoid", router_bias=True, norm_topk_prob=True,
                     routed_scaling_factor=2.448)
    if layer == "a_held_share":
        first, held = 8, 8
        attrs.update(num_local_experts=held, local_expert_offset=first)
    stacks = [draw(e, d, f), draw(e, d, f), draw(e, f, d)]
    args = [a.astype(dtype) for a in [x, router]
            + [w[first:first + held] for w in stacks]]
    if layer != "olmoe":
        args.append(bias)
    want, want_load = moe._moe_feed_forward(attrs, *args)
    monkeypatch.setattr(kernel, "moe_form", lambda *a: "kernel")
    got, load = moe._moe_feed_forward(attrs, *args)
    assert np.array_equal(np.asarray(load), np.asarray(want_load))
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    tol = 2.0 ** -6 if dtype == "bfloat16" else 1e-5
    assert np.abs(got - want).max() <= tol * np.abs(want).max()
    assert np.abs(want).max() > 0.01


@pytest.mark.parametrize("rows,experts,d,f", [
    (16384, 64, 2048, 1024), (6144, 128, 2048, 768), (1024, 16, 4096, 2048),
    (192, 128, 2048, 768), (16, 16, 4096, 2048), (8, 4, 8192, 8192)])
def test_the_tiles_are_whole_and_fit(rows, experts, d, f):
    """Whatever the rule answers, at a cell's admission or step or at widths
    no cell has: a row tile of whole bfloat16 sublane tiles, column tiles
    that divide the widths in whole lane tiles, a ring of two buffers a
    matrix or three, and matrices whose ``depth`` buffers fit the budget the
    kernel's file states."""
    tm, tn_up, tn_down, depth = kernel.tiles(rows, experts, d, f,
                                             jnp.bfloat16)
    assert tm % 16 == 0 and 32 <= tm <= 128
    assert f % tn_up == 0 and tn_up % 128 == 0
    assert d % tn_down == 0 and tn_down % 128 == 0
    assert depth in (2, 3)
    assert 2 * depth * max(2 * d * tn_up, f * tn_down) \
        <= kernel._MATRIX_BYTES


def test_the_rule_names_the_kernel_on_the_chip_alone(monkeypatch):
    """On the CPU every expert layer is ``ragged_dot``'s; with the backend
    held to the chip's, bfloat16 stacks of whole lane tiles are the kernel's
    and float32 experts, a width that is no whole tile, or rows that are no
    whole sublane tile stay XLA's."""
    from mxnet_tpu.ops import attention

    spec = jax.ShapeDtypeStruct
    cell = lambda m=6144, d=2048, f=768, t="bfloat16": (
        spec((m, d), t), spec((128, d, f), t), spec((128, f, d), t))
    assert kernel.moe_form(*cell()) == "ragged_dot"
    monkeypatch.setattr(attention, "_backend", lambda: "tpu")
    assert kernel.moe_form(*cell()) == "kernel"
    assert kernel.moe_form(*cell(m=192)) == "kernel"
    assert kernel.moe_form(*cell(t="float32")) == "ragged_dot"
    assert kernel.moe_form(*cell(f=96)) == "ragged_dot"
    assert kernel.moe_form(*cell(m=24)) == "ragged_dot"


def test_a_decoder_says_which_form_its_programs_run(monkeypatch):
    """An OLMoE decoder with the rule held to the kernel (interpreted):
    ``warmup`` sets the gauges of both bound programs, every expert layer
    the kernel's and none XLA's, and the depth of the fetch ring ``tiles``
    names at each program's rows; the decoder as it stands on the CPU says
    the opposite, and a depth of 0; and an admission and steps through
    either give the same greedy tokens and logits to the fused activation's
    rounding."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry as tm
    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.serving.kv_decode import PagedKVDecoder

    cfg = dict(arch="olmoe", vocab_size=300, num_layers=2, num_heads=2,
               head_dim=16, model_dim=32, ffn_dim=32, num_experts=8,
               num_experts_per_tok=2, rope_theta=10000.0, rms_eps=1e-5)
    rs = np.random.RandomState(0)
    params = {
        name: mx.nd.NDArray(jnp.asarray(
            np.ones(shape, "f") if name.endswith("gamma")
            else rs.randn(*shape).astype("f") * 0.1))
        for name, shape in sorted(tf.param_shapes(**cfg).items())}
    saved = tm.current_override()
    tm.set_mode("counters")
    try:
        logits = []
        for form in ("ragged_dot", "kernel"):
            tm.reset()
            if form == "kernel":
                monkeypatch.setattr(kernel, "moe_form", lambda *a: "kernel")
            with mx.name.NameManager():
                dec = PagedKVDecoder(params, max_len=32, page_size=8, lanes=2,
                                     prefill_len=16, **cfg).warmup()
            snap = tm.snapshot()
            for program in ("prefill", "decode"):
                assert snap["serving.moe.kernel_layers." + program] \
                    == 2 * (form == "kernel")
                assert snap["serving.moe.xla_layers." + program] \
                    == 2 * (form == "ragged_dot")
                rows = 2 * (16 if program == "prefill" else 2)
                assert snap["serving.moe.fetch_depth." + program] == (
                    kernel.tiles(rows, 8, 32, 32, jnp.float32)[3]
                    if form == "kernel" else 0)
            seq, first = dec.admit(np.arange(11) % 29)
            rows = [np.asarray(first)]
            for t in range(3):
                rows.append(np.asarray(dec.step({seq: 5 + t})[seq]))
            logits.append(np.stack(rows))
        np.testing.assert_allclose(logits[0], logits[1], rtol=2e-4, atol=2e-4)
        assert (logits[0].argmax(-1) == logits[1].argmax(-1)).all()
    finally:
        tm.set_mode(saved)
        tm.reset()


def test_the_rules_over_shapes_import_no_pallas():
    """A warm process that loads its programs from the program store traces
    no kernel and pays for no Pallas import: importing the operator and the
    kernel's module and asking ``moe_form``, ``tiles`` and ``supported`` at a
    cell's operands with the backend held to the chip's imports nothing of
    Pallas; tracing the kernel does."""
    import os
    import subprocess
    import sys

    code = """
import sys
import jax
from mxnet_tpu.ops import attention, moe, pallas_grouped_matmul as kernel
spec = jax.ShapeDtypeStruct
rows = spec((6144, 2048), "bfloat16")
gate = spec((128, 2048, 768), "bfloat16")
down = spec((128, 768, 2048), "bfloat16")
attention._backend = lambda: "tpu"
assert kernel.moe_form(rows, gate, down) == "kernel"
assert kernel.supported(rows, gate, down)
assert kernel.tiles(6144, 128, 2048, 768, "bfloat16")[0] % 16 == 0
pallas = lambda: sorted(m for m in sys.modules if "pallas" in m
                        and not m.startswith("mxnet_tpu"))
assert pallas() == [], pallas()
jax.eval_shape(lambda *a: kernel.expert_ffn(*a, interpret=True), rows, gate,
               gate, down, spec((128,), "int32"))
assert "jax.experimental.pallas" in pallas()
print("OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0 and out.stdout.strip() == "OK", out.stderr
