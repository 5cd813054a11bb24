"""A layer that holds a share of its experts moves the rows it holds and no
others (``ops/moe.py``: ``held_rows_chunk`` the rule, ``_held_rows`` the
loop of chunks), held here to the all-rows form it replaces and to a plain
spelling of the layer, whatever the routing: every assignment to a held
expert is computed, in one turn or in many."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import moe
from mxnet_tpu.ops import pallas_grouped_matmul as kernel

N, K, E, L = 64, 4, 32, 8          # tokens, experts a token, routed, held


@pytest.fixture
def small_chunks(monkeypatch):
    """The rule at a test's sizes: a chunk of whole 16-row tiles (what the
    kernel's rows must be) wherever it leaves 64 rows out."""
    monkeypatch.setattr(moe, "_ROW_TILE", 16)
    monkeypatch.setattr(moe, "_ROWS_WORTH_A_CHUNK", 64)
    return moe.held_rows_chunk(N, K, L, E)


def _never(monkeypatch):
    monkeypatch.setattr(moe, "_ROWS_WORTH_A_CHUNK", 1 << 40)


def _layer(held_rows, first, gated, bias, dtype="float32", d=48, f=16,
           seed=0):
    """A layer's attrs and operands whose routing is WRITTEN: the router is
    the identity over the first E features and a token's chosen experts are
    its large ones, so exactly ``held_rows`` of the N * K assignments reach
    experts ``first .. first + L - 1``, spread over the tokens at random (at
    most K a token) and over the held experts unevenly."""
    rs = np.random.RandomState(seed)
    per = np.zeros(N, int)
    for _ in range(held_rows):
        per[rs.choice(np.flatnonzero(per < K))] += 1
    here = np.arange(first, first + L)
    away = np.setdiff1d(np.arange(E), here)
    x = rs.randn(N, d).astype("f") * 0.1
    uneven = np.arange(1.0, L + 1) / np.arange(1.0, L + 1).sum()
    for t in range(N):
        chosen = np.concatenate([
            rs.choice(here, per[t], replace=False, p=uneven),
            rs.choice(away, K - per[t], replace=False)])
        x[t, chosen] = 3.0 + rs.rand(K)
    g = lambda *shape: jnp.asarray(rs.randn(*shape).astype("f") * 0.2, dtype)
    router = np.zeros((E, d), "f")
    router[np.arange(E), np.arange(E)] = 1.0
    stacks = ([g(L, d, f)] if gated else []) + [g(L, d, f), g(L, f, d)]
    attrs = dict(num_experts=E, num_hidden=f, num_experts_per_tok=K,
                 num_local_experts=L, local_expert_offset=first,
                 gated=gated, activation="silu" if gated else "relu2")
    operands = [jnp.asarray(x, dtype), jnp.asarray(router, dtype)] + stacks
    if bias:
        attrs.update(scoring="sigmoid", router_bias=True,
                     norm_topk_prob=True, routed_scaling_factor=2.5)
        operands.append(jnp.asarray(rs.randn(E).astype("f") * 0.02))
    return attrs, operands


def _plain(attrs, x, router, *rest):
    """The layer spelled out a slot at a time, every token against the
    matrices of the expert its slot chose: no sort, no grouped matmul."""
    gated = attrs["gated"]
    *first_stacks, down = rest[:3 if gated else 2]
    bias = rest[-1] if attrs.get("router_bias") else None
    first = attrs["local_expert_offset"]
    x32 = x.astype(jnp.float32)
    scores = jnp.dot(x32, router.astype(jnp.float32).T,
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.sigmoid(scores) if bias is not None \
        else jax.nn.softmax(scores, axis=-1)
    _, expert = jax.lax.top_k(probs if bias is None else probs + bias, K)
    weight = jnp.take_along_axis(probs, expert, axis=-1)
    if attrs.get("norm_topk_prob"):
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    weight = weight * attrs.get("routed_scaling_factor", 1.0)
    y = jnp.zeros(x.shape, jnp.float32)
    for j in range(K):
        local = expert[:, j] - first
        held = (local >= 0) & (local < L)
        at = jnp.clip(local, 0, L - 1)
        through = lambda w: jnp.einsum(
            "nd,ndf->nf", x32, w[at].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)
        if gated:
            act = jax.nn.silu(through(first_stacks[0])) \
                * through(first_stacks[1])
        else:
            act = jnp.square(jnp.maximum(through(first_stacks[0]), 0.0))
        out = jnp.einsum("nf,nfd->nd", act.astype(x.dtype)
                         .astype(jnp.float32), down[at].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        y = y + jnp.where(held[:, None], out * weight[:, j:j + 1], 0)
    load = jnp.bincount(expert.reshape(-1), length=E).astype(jnp.float32)
    return y, load


# how many of the 256 assignments reach a held expert, by the chunk C: none,
# under one chunk, exactly one, one more, two and a bit (every held expert's
# group straddles a turn somewhere), every one
_HELD = {"none": lambda c: 0, "under": lambda c: c // 2,
         "a_chunk": lambda c: c, "a_chunk_and_one": lambda c: c + 1,
         "straddled": lambda c: 2 * c + 5, "every": lambda c: N * K}


@pytest.mark.parametrize("bias", [False, True], ids=["softmax", "biased"])
@pytest.mark.parametrize("gated", [True, False], ids=["silu", "relu2"])
@pytest.mark.parametrize("first", [0, 16])
@pytest.mark.parametrize("routing", list(_HELD))
def test_the_held_rows_alone_are_the_layer(small_chunks, monkeypatch,
                                           routing, first, gated, bias):
    """XLA's form, float32: the same products row for row, so the chunks'
    sum is the all-rows form's to the ORDER of a token's float32 sum (XLA
    adds a token's k rows as a tree, the chunks add its held rows in slot
    order and a turn at a time): 2e-6 of the largest output (measured
    2.4e-7), and the plain spelling's to 1e-5; ``load`` is the same array;
    a token none of whose experts is held gets exactly zero."""
    chunk = small_chunks
    assert chunk == 64
    held_rows = _HELD[routing](chunk)
    attrs, operands = _layer(held_rows, first, gated, bias)
    got, load = moe._moe_feed_forward(attrs, *operands)
    assert float(load[first:first + L].sum()) == held_rows
    assert float(load.sum()) == N * K
    _never(monkeypatch)
    want, want_load = moe._moe_feed_forward(attrs, *operands)
    plain, plain_load = _plain(attrs, *operands)
    assert np.array_equal(np.asarray(load), np.asarray(want_load))
    assert np.array_equal(np.asarray(load), np.asarray(plain_load))
    got, want = np.asarray(got), np.asarray(want)
    scale = max(np.abs(want).max(), 1e-30)
    assert np.abs(got - want).max() <= 2e-6 * scale
    assert np.abs(got - np.asarray(plain)).max() <= 1e-5 * scale
    assert np.array_equal(got.any(axis=1), want.any(axis=1))
    assert (held_rows > 0) == bool(got.any())


@pytest.mark.parametrize("gated", [True, False], ids=["silu", "relu2"])
@pytest.mark.parametrize("routing", ["none", "a_chunk", "a_chunk_and_one",
                                     "straddled", "every"])
def test_the_held_rows_through_the_kernel(small_chunks, monkeypatch, routing,
                                          gated):
    """The kernel's form (interpreted), bfloat16 at whole lane tiles: a
    turn's groups are the layer's clipped to the turn, rows past them come
    out zero, and the sum is the all-rows kernel form's to one bfloat16
    rounding of the largest output (the float32 sums differ by their
    order alone)."""
    monkeypatch.setattr(kernel, "moe_form", lambda *a: "kernel")
    held_rows = _HELD[routing](small_chunks)
    attrs, operands = _layer(held_rows, 16, gated, True, "bfloat16", d=128,
                             f=128, seed=1)
    got, load = moe._moe_feed_forward(attrs, *operands)
    _never(monkeypatch)
    want, want_load = moe._moe_feed_forward(attrs, *operands)
    assert np.array_equal(np.asarray(load), np.asarray(want_load))
    assert float(load[16:16 + L].sum()) == held_rows
    got = np.asarray(got.astype(jnp.float32))
    want = np.asarray(want.astype(jnp.float32))
    assert np.abs(got - want).max() <= 2.0 ** -7 * np.abs(want).max()
    assert np.array_equal(got.any(axis=1), want.any(axis=1))
    plain, _ = _plain(attrs, *operands)
    assert np.abs(got - np.asarray(plain)).max() \
        <= 3e-2 * max(np.abs(want).max(), 1e-30)


def test_nothing_of_every_assignments_rows_is_built(small_chunks,
                                                    monkeypatch):
    """The compacted layer's jaxpr holds nothing of N * k rows by D, and
    nothing (N, k, D); the all-rows form's does."""
    attrs, operands = _layer(40, 0, True, True)
    d = operands[0].shape[1]

    def wide(jaxpr, found):
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                shape = getattr(v.aval, "shape", ())
                if shape in ((N * K, d), (N, K, d)):
                    found.append((eqn.primitive.name, shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                wide(sub, found)
        return found

    trace = lambda: jax.make_jaxpr(
        lambda *a: moe._moe_feed_forward(attrs, *a))(*operands).jaxpr
    assert not wide(trace(), [])
    _never(monkeypatch)
    assert wide(trace(), [])


def test_the_shares_of_a_prefill_add_up():
    """``tests/test_mimo_v2_flash_block.py``'s rule at a prefill's row count
    and the rule's own numbers: 2,048 tokens x 4 over 32 experts in four
    shares of 8 (chunks of 2,048 rows of the 8,192); the four partial sums
    add up to the uncut layer's and every share counts the same load."""
    rs = np.random.RandomState(0)
    n, d, f = 2048, 48, 16
    g = lambda *shape: jnp.asarray(rs.randn(*shape).astype("f") * 0.2)
    x, router, gate, up, down = g(n, d) * 5, g(E, d), g(E, d, f), \
        g(E, d, f), g(E, f, d)
    bias = jnp.asarray(rs.randn(E).astype("f") * 0.5)
    attrs = dict(num_experts=E, num_hidden=f, num_experts_per_tok=K,
                 scoring="sigmoid", router_bias=True, norm_topk_prob=True)
    assert moe.held_rows_chunk(n, K, 8, E) == 2048
    assert moe.held_rows_chunk(n, K, E, E) == 0
    whole, load = moe._moe_feed_forward(attrs, x, router, gate, up, down,
                                        bias)
    parts = []
    for first in range(0, E, 8):
        held = slice(first, first + 8)
        part, part_load = moe._moe_feed_forward(
            dict(attrs, num_local_experts=8, local_expert_offset=first), x,
            router, gate[held], up[held], down[held], bias)
        assert np.array_equal(np.asarray(part_load), np.asarray(load))
        parts.append(np.asarray(part, np.float64))
    # the bias sends more than a chunk's rows to some share: several turns
    assert max(float(load[i:i + 8].sum()) for i in range(0, E, 8)) > 2048
    whole = np.asarray(whole, np.float64)
    assert np.abs(sum(parts) - whole).max() < 1e-5 * np.abs(whole).max()
    assert all(np.abs(p - whole).max() > 0.1 * np.abs(whole).max()
               for p in parts)


def test_a_backward_pass_through_a_share_is_the_all_rows_forms(small_chunks,
                                                               monkeypatch):
    """``jax.grad`` through the compacted layer runs (a data-dependent number
    of turns has no transpose: the layer differentiates as the all-rows
    form, the same function) and gives that form's gradients."""
    attrs, operands = _layer(2 * small_chunks + 5, 16, True, True)
    loss = lambda x, gate, up, down: jnp.sum(jnp.square(
        moe._moe_feed_forward(attrs, x, operands[1], gate, up, down,
                              operands[5])[0]))
    args = (operands[0],) + tuple(operands[2:5])
    got = jax.grad(loss, argnums=(0, 1, 2, 3))(*args)
    _never(monkeypatch)
    want = jax.grad(loss, argnums=(0, 1, 2, 3))(*args)
    for a, b in zip(got, want):
        assert np.abs(np.asarray(b)).max() > 0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6 * np.abs(np.asarray(b)).max())


# tokens, experts a token, held, routed -> the chunk: the cells' admissions
# and steps
@pytest.mark.parametrize("tokens,k,held,routed,chunk", [
    (2048, 8, 16, 256, 1024),       # mimo's admission: the even share
    (8192, 8, 16, 256, 4096),       # dots3's
    (8192, 10, 64, 256, 20480),     # laguna's: a quarter held
    (2048, 8, 3, 256, 256),         # 192 rows: up to whole row tiles
    (32, 8, 16, 256, 0),            # their steps
    (32, 10, 64, 256, 0),
    (2048, 6, 64, 128, 0),          # nemotron: half held
    (64, 6, 64, 128, 0),
    (2048, 8, 64, 64, 0),           # olmoe, kanana, lfm2: every expert held
    (1024, 6, 128, 128, 0),
    (1024, 4, 0, 64, 0),
    (512, 8, 16, 256, 0),           # 3,840 rows left out: under the worth
    (1024, 8, 16, 256, 512),        # 7,680: over it
    (4096, 200, 256, 1024, 0),      # a token's held rows: two tiles at most
])
def test_the_rule_at_the_cells_shapes(tokens, k, held, routed, chunk):
    assert moe.held_rows_chunk(tokens, k, held, routed) == chunk
