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
}
K, N = 32, 256      # two column tiles of 128


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


@pytest.mark.parametrize("fused", [False, True], ids=["down", "gate_up"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(GROUPS))
def test_the_kernel_multiplies_what_ragged_dot_multiplies(case, dtype, fused):
    """One stack: the float32 products of every row with its own expert's
    matrix, zeros past the last group. Two stacks: ``silu(dot) * dot`` on the
    float32 sums, cast ONCE to the storage type. Products in the storage
    type and sums in float32 on both sides: what differs is the order of a
    float32 sum and, fused, one rounding of the result."""
    rows, (gate, up), sizes = _operands(case, dtype)
    meta = kernel.visits(sizes, rows.shape[0], TM)
    got = kernel.grouped_matmul(rows, (gate, up) if fused else (gate,), meta,
                                tm=TM, tn=128, interpret=True)
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
    what follows repeats the last visit's blocks."""
    rs = np.random.RandomState(seed)
    experts = int(rs.randint(1, 12))
    sizes = rs.randint(0, 40, experts) * (rs.rand(experts) < 0.6)
    rows = int(sizes.sum()) + int(rs.randint(0, 3 * tm))
    rows += -rows % 8 or 8
    off, tile, expert, count = (np.asarray(a) for a in kernel.visits(
        jnp.asarray(sizes, jnp.int32), rows, tm))
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
    that divide the widths in whole lane tiles, and matrices whose
    double-buffered blocks fit the budget the kernel's file states."""
    tm, tn_up, tn_down = kernel.tiles(rows, experts, d, f, jnp.bfloat16)
    assert tm % 16 == 0 and 32 <= tm <= 128
    assert f % tn_up == 0 and tn_up % 128 == 0
    assert d % tn_down == 0 and tn_down % 128 == 0
    assert 2 * 2 * max(2 * d * tn_up, f * tn_down) <= kernel._MATRIX_BYTES


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
    the kernel's and none XLA's; the decoder as it stands on the CPU says
    the opposite; and an admission and steps through either give the same
    greedy tokens and logits to the fused activation's rounding."""
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
