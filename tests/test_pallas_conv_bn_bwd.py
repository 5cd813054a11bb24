"""Gradient-parity sweep for the fused Pallas dgrad/wgrad kernels
(interpret mode on CPU; docs/PERF.md §6b). The oracle is ``jax.vjp`` of the
unfused XLA lowering of the same fused contract — exactly what
``MXNET_FUSED_CONV_BN_BWD=0`` computes — across kernel sizes, strides
(including the ceil-div odd-dim path), prologue-only / prologue+residual
variants, both stash and recompute policies, bf16 and f32.

The non-slow subset (one case per load-bearing axis) is wired into
tools/ci_check.sh; the full matrix runs under ``-m slow``.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import pallas_conv_bn as pcb

slow = pytest.mark.slow


def _mk(shape, seed, dtype=np.float32):
    rs = np.random.RandomState(seed)
    return jnp.asarray(rs.randn(*shape).astype(np.float32), dtype)


def _ref(x, w, scale, shift, res, kernel, stride, relu):
    c = pcb._xla_conv(x, w, scale, shift, res, kernel, stride, relu)
    s, q = pcb._stats_of(c)
    return c, s, q


def _grads(fn, kernel, stride, relu, x, w, scale, shift, r, cos, cs, cq):
    """Gradients of a loss exercising all three outputs (c, ssum, ssq) with
    FIXED cotangents (linear in s and q). A nonlinear term like sin(s)
    would make ds depend on the statistics' VALUE — and the kernel's
    f32-accumulator stats differ from XLA's rounded-activation sums at the
    documented bf16-epsilon level, which cos(s) at |s|~1e2 amplifies into
    O(1) cotangent differences that are a property of the probe, not the
    kernels."""

    def loss(*a):
        c, s, q = fn(*a)
        return (jnp.sum(c.astype(jnp.float32) * cos)
                + jnp.sum(s * cs) + jnp.sum(q * cq))

    argnums = tuple(i for i, a in enumerate((x, w, scale, shift, r))
                    if a is not None)
    return jax.grad(loss, argnums=argnums)(x, w, scale, shift, r)


def _case(kernel, stride, variant, policy, dtype, seed=10):
    B, K, H, W, N = 2, 8, 8, 8, 16
    if stride != (1, 1):
        H = W = 9  # odd spatial dims: the ceil-div strided path
    prologue = variant in ("p", "pr")
    res = variant == "pr"
    x = _mk((B, K, H, W), seed, dtype)
    w = _mk((N, K) + kernel, seed + 1, dtype) * 0.1
    scale = _mk((K,), seed + 2) if prologue else None
    shift = _mk((K,), seed + 3) if prologue else None
    if prologue:
        # keep relu ties out of the sweep: exact bf16 cancellation
        # (x*scale == -shift) makes the affine exactly 0 at ~1/1000
        # elements, where jnp.maximum's vjp (the oracle) splits the
        # cotangent g/2 while the kernels use the xn>0 subgradient — both
        # valid; the comparison should not hinge on the convention
        bsh = (1, -1, 1, 1)
        for _ in range(64):
            xn = (x * scale.astype(dtype).reshape(bsh)
                  + shift.astype(dtype).reshape(bsh))
            if not bool(jnp.any(xn == 0)):
                break
            shift = shift + np.float32(0.0031)
    Ho, Wo = pcb.strided_dims(H, W, stride)
    r = _mk((B, N, Ho, Wo), seed + 4, dtype) if res else None
    cos = _mk((B, N, Ho, Wo), seed + 5)
    cs = _mk((N,), seed + 6) * 0.1
    cq = _mk((N,), seed + 7) * 0.01
    relu = prologue
    g_ref = _grads(
        lambda *a: _ref(*a, kernel, stride, relu),
        kernel, stride, relu, x, w, scale, shift, r, cos, cs, cq)
    g_pal = _grads(
        lambda *a: pcb.conv_block(*a, kernel, stride, relu, True, policy),
        kernel, stride, relu, x, w, scale, shift, r, cos, cs, cq)
    return g_pal, g_ref


# one pytest.param per sweep cell; the non-slow subset covers every axis
# (kernel family, strided ceil-div, both variants, both policies, both
# dtypes) at least once
SWEEP = []
_FAST = {
    ((1, 1), (1, 1), "p", "recompute", "float32"),
    ((1, 1), (1, 1), "pr", "stash", "float32"),
    ((3, 3), (1, 1), "pr", "recompute", "float32"),
    ((3, 3), (1, 1), "p", "stash", "bfloat16"),
    ((1, 1), (2, 2), "p", "recompute", "bfloat16"),
    ((1, 1), (1, 1), "pr", "recompute", "bfloat16"),
}
for kernel, stride in (((1, 1), (1, 1)), ((1, 1), (2, 2)), ((3, 3), (1, 1))):
    for variant in ("p", "pr"):
        for policy in ("recompute", "stash"):
            for dtype in ("float32", "bfloat16"):
                cell = (kernel, stride, variant, policy, dtype)
                SWEEP.append(pytest.param(
                    *cell,
                    marks=() if cell in _FAST else (slow,),
                    id="%dx%d-s%d-%s-%s-%s" % (kernel[0], kernel[1],
                                               stride[0], variant, policy,
                                               dtype)))


@pytest.mark.parametrize("kernel,stride,variant,policy,dtype", SWEEP)
def test_bwd_gradient_parity(kernel, stride, variant, policy, dtype):
    g_pal, g_ref = _case(kernel, stride, variant, policy, jnp.dtype(dtype))
    for i, (ga, gb) in enumerate(zip(g_pal, g_ref)):
        ga32 = np.asarray(ga, np.float32)
        gb32 = np.asarray(gb, np.float32)
        if dtype == "float32":
            rtol, atol = 2e-3, 3e-3
        else:
            # bf16: BOTH paths round the effective cotangent to the
            # activation dtype before the transposed contractions (by
            # design — the kernel matches the XLA path's bf16 cotangent),
            # so each reduced grad carries ~eps*sqrt(n) noise from 1-ulp
            # input differences, proportional to the REDUCTION's magnitude
            # (a near-zero dscale channel after cancellation still wobbles
            # by eps of its summands). Hence atol scaled by the oracle's
            # own magnitude; the f32 sweep above pins the math at 2e-3.
            rtol = 1e-1
            atol = 3e-2 * max(1.0, float(np.abs(gb32).max()))
        np.testing.assert_allclose(ga32, gb32, rtol=rtol, atol=atol,
                                   err_msg="grad argnum %d" % i)


def test_bare_conv_bwd_parity():
    """No prologue: the backward kernel's xn == x path (dscale/dshift
    outputs absent)."""
    g_pal, g_ref = _case((1, 1), (1, 1), "bare", "recompute", jnp.float32)
    for ga, gb in zip(g_pal, g_ref):
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                   rtol=2e-3, atol=3e-3)


def test_policies_agree():
    """stash and recompute are the same mathematical function — their
    gradients must agree to much tighter tolerance than either vs XLA."""
    g_r, _ = _case((3, 3), (1, 1), "pr", "recompute", jnp.float32)
    g_s, _ = _case((3, 3), (1, 1), "pr", "stash", jnp.float32)
    for ga, gb in zip(g_s, g_r):
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                   rtol=1e-5, atol=1e-5)


def test_stash_forward_value_unchanged():
    """The stash policy's extra xn output must not perturb (c, s, q)."""
    B, K, H, W, N = 2, 8, 8, 8, 16
    x = _mk((B, K, H, W), 40)
    w = _mk((N, K, 1, 1), 41) * 0.1
    scale, shift = _mk((K,), 42), _mk((K,), 43)
    base = pcb.conv_block(x, w, scale, shift, None, (1, 1), (1, 1), True)
    st = pcb.conv_block(x, w, scale, shift, None, (1, 1), (1, 1), True,
                        True, "stash")
    for a, b in zip(st, base):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_untileable_bwd_demotes_to_xla(monkeypatch):
    """A shape the backward planner rejects must silently take the XLA vjp
    (never an in-jit assert), even with a policy forced — the full demotion
    chain stash -> recompute -> xla."""
    monkeypatch.setattr(pcb, "_SCOPED_VMEM_LIMIT", 0)
    assert pcb.plan_bwd_blocks((2, 8, 8, 8), (16, 8, 1, 1)) is None
    g_pal, g_ref = _case((1, 1), (1, 1), "p", "stash", jnp.float32)
    for ga, gb in zip(g_pal, g_ref):
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                   rtol=1e-5, atol=1e-5)


def test_stash_demotes_when_xn_output_does_not_fit(monkeypatch):
    """Review regression: the stash decision must budget the FORWARD
    kernel's extra xn output stream too. With a budget where the plain
    forward fits but forward+xn does not, bwd='stash' must silently demote
    (recompute) instead of compiling an over-budget kernel."""
    B, K, H, W, N = 2, 8, 8, 8, 16
    shape, wshape = (B, K, H, W), (N, K, 1, 1)
    base = pcb.plan_blocks(shape, wshape, itemsize=4)
    assert base is not None
    # find a budget admitting the plain forward but not the xn stream
    for budget in range(pcb._VMEM_BUDGET, 0, -1024):
        monkeypatch.setattr(pcb, "_VMEM_BUDGET", budget)
        if pcb.plan_blocks(shape, wshape, itemsize=4) is not None and \
                pcb.plan_blocks(shape, wshape, itemsize=4,
                                emit_xn=True) is None:
            break
    else:
        pytest.fail("no discriminating budget found")
    x = _mk(shape, 70)
    w = _mk(wshape, 71) * 0.1
    scale, shift = _mk((K,), 72), _mk((K,), 73)
    from mxnet_tpu import fusion
    monkeypatch.setenv("MXNET_FUSED_CONV_BN_BWD", "stash")
    assert fusion.bwd_mode((1, 1), (1, 1), shape, wshape, "float32",
                           True) == "xla"  # stash does not fit -> honest
    g = jax.grad(lambda x, w: jnp.sum(pcb.conv_block(
        x, w, scale, shift, None, (1, 1), (1, 1), True, True,
        "stash")[0]))(x, w)
    assert np.isfinite(np.asarray(g)).all()


def test_bwd_planner_mirrors_fwd_structural_gate():
    """plan_bwd_blocks shares plan_blocks' structural predicate (kernel,
    stride, K%8) and uses ceil-div strided dims in its working set."""
    assert pcb.plan_bwd_blocks((2, 6, 8, 8), (16, 6, 1, 1)) is None  # K%8
    assert pcb.plan_bwd_blocks((2, 8, 8, 8), (16, 8, 5, 5)) is None  # 5x5
    assert pcb.plan_bwd_blocks((2, 8, 9, 9), (16, 8, 1, 1),
                               stride=(2, 2)) is not None  # odd-H ceil
