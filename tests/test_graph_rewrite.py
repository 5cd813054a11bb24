"""Graph-rewrite pass framework tests (analysis/rewrite.py, ISSUE 14).

Covers each builtin pass (const fold, CSE, canonicalize, bf16 legalize,
DCE) with its bit-parity contract, pipeline idempotence (running twice is a
no-op with zero provenance records on pass 2), the bind-time
MXNET_GRAPHREWRITE integration on both executor paths, and the
``graphlint --rewrite`` dump.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import analysis


@pytest.fixture(autouse=True)
def _pin_rewrite_env(monkeypatch):
    # the bitwise-parity assertions assume the default pass set: an
    # ambient MXNET_GRAPHREWRITE[_BF16] would change what rewrite() does
    monkeypatch.delenv("MXNET_GRAPHREWRITE", raising=False)
    monkeypatch.delenv("MXNET_GRAPHREWRITE_BF16", raising=False)


def _tiny_transformer():
    return mx.models.get_symbol("transformer", vocab_size=50, model_dim=32,
                                num_heads=2, num_layers=1, ffn_dim=64,
                                seq_len=8)


_TF_SHAPES = {"data": (2, 8), "softmax_label": (2, 8)}
_TF_TYPES = {"data": "int32"}


def _fill(ex, seed=1):
    rs = np.random.RandomState(seed)
    for n, a in zip(ex._prog.arg_names, ex.arg_arrays):
        if np.issubdtype(np.dtype(a.dtype), np.integer):
            a[:] = rs.randint(0, 50, a.shape).astype(a.dtype)
        elif "label" in n:
            a[:] = rs.randint(0, 10, a.shape).astype(a.dtype)
        else:
            a[:] = rs.uniform(-0.1, 0.1, a.shape).astype(a.dtype)


def _fwd_bwd(sym, shapes, types=None, seed=1, grad_req="write"):
    mx.random.seed(7)
    ex = sym.simple_bind(mx.cpu(), type_dict=types, grad_req=grad_req,
                         **shapes)
    _fill(ex, seed)
    ex.forward(is_train=True)
    ex.backward()
    grads = {n: (g.asnumpy() if g is not None else None)
             for n, g in zip(ex._prog.arg_names, ex.grad_arrays)}
    return [o.asnumpy() for o in ex.outputs], grads


# --------------------------------------------------------------- const fold
def test_const_fold_evaluates_init_subgraph_once():
    x = mx.sym.Variable("x")
    scale = mx.sym._ones(shape=(4,)) * 3.0  # init-op subgraph: foldable
    net = mx.sym.broadcast_mul(x, scale, name="out")
    res = analysis.rewrite(net, shapes={"x": (2, 4)})
    assert res.counts["folded"] == 1
    ops = [n.op for n in res.symbol._topo() if n.op]
    assert "_graph_const" in ops and "_ones" not in ops
    # the fold is bitwise: same forward as the unfolded graph
    a, _ = _fwd_bwd(net, {"x": (2, 4)})
    b, _ = _fwd_bwd(res.symbol, {"x": (2, 4)})
    assert np.array_equal(a[0], b[0])


def test_const_fold_never_touches_variables_or_aux():
    # a parameter-fed subgraph must NOT fold (weights are runtime values)
    x = mx.sym.Variable("x")
    w = mx.sym.Variable("w", shape=(4,))
    net = mx.sym.broadcast_mul(x, w * 2.0)
    res = analysis.rewrite(net, shapes={"x": (2, 4)})
    assert res.counts["folded"] == 0
    assert res.symbol.list_arguments() == net.list_arguments()


# -------------------------------------------------------------------- cse
def test_cse_merges_duplicate_subexpressions_bitwise():
    a, b = mx.sym.Variable("a"), mx.sym.Variable("b")
    net = (a + b) * (a + b)
    res = analysis.rewrite(net, shapes={"a": (3,), "b": (3,)})
    assert res.counts["merged"] == 1
    o1, g1 = _fwd_bwd(net, {"a": (3,), "b": (3,)})
    o2, g2 = _fwd_bwd(res.symbol, {"a": (3,), "b": (3,)})
    assert np.array_equal(o1[0], o2[0])
    for k in g1:
        assert np.array_equal(g1[k], g2[k]), k


def test_cse_never_merges_stateful_ops():
    # two Dropouts over the same input are two masks; two BatchNorms are
    # two moving-stat updates — neither may merge
    x = mx.sym.Variable("x")
    net = mx.sym.Dropout(x, p=0.5, name="d1") + mx.sym.Dropout(
        x, p=0.5, name="d2")
    res = analysis.rewrite(net, shapes={"x": (4, 4)})
    assert res.counts["merged"] == 0
    x2 = mx.sym.Variable("y")
    bn = mx.sym.BatchNorm(x2, name="bn1") + mx.sym.BatchNorm(x2, name="bn2")
    res2 = analysis.rewrite(bn, shapes={"y": (4, 4)})
    merged = [r for r in res2.records if r["action"] == "merge"
              and "bn" in (r["node"] or "")]
    assert not merged


# ------------------------------------------------------------ canonicalize
@pytest.mark.parametrize("build,rule", [
    (lambda x: x * x, "mul_self_to_square"),
    (lambda x: mx.sym.relu(x), "relu_to_activation"),
    (lambda x: 1.0 / mx.sym.sqrt(x + 2.0), "rsqrt_compose"),
    (lambda x: mx.sym.reciprocal(mx.sym.sqrt(x + 2.0)), "rsqrt_compose"),
    (lambda x: (x * 1.0) + 0.5, "identity_elide"),
], ids=["square", "relu", "rdiv_sqrt", "recip_sqrt", "mul_one"])
def test_canonicalize_rules_fire_and_stay_bitwise(build, rule):
    x = mx.sym.Variable("x")
    net = build(x)
    res = analysis.rewrite(net, shapes={"x": (16,)})
    assert "canonicalize." + rule in res.rule_table(), res.rule_table()
    o1, g1 = _fwd_bwd(net, {"x": (16,)})
    o2, g2 = _fwd_bwd(res.symbol, {"x": (16,)})
    if rule == "rsqrt_compose":
        # a division replaced by a reciprocal square root, which XLA's CPU
        # backend no longer evaluates to the same last bit: one ulp. And
        # rsqrt's vjp is a different (mathematically equal) expression than
        # the composed div∘sqrt chain rule: same documented backward
        # tolerance as CSE
        np.testing.assert_array_max_ulp(o1[0], o2[0], maxulp=1)
        np.testing.assert_allclose(g1["x"], g2["x"], atol=1e-6, rtol=0)
    else:
        assert np.array_equal(o1[0], o2[0])  # forward: bitwise
        assert np.array_equal(g1["x"], g2["x"])


def test_canonicalize_negative_axis_normalization():
    x = mx.sym.Variable("x")
    net = mx.sym.broadcast_sub(x, mx.sym.mean(x, axis=2, keepdims=True))
    res = analysis.rewrite(net, shapes={"x": (2, 3, 8)})
    assert "canonicalize.negative_axis" in res.rule_table()
    mean_node = [n for n in res.symbol._topo() if n.op == "mean"][0]
    assert tuple(mean_node.parsed_attrs()["axis"]) == (-1,)
    o1, _ = _fwd_bwd(net, {"x": (2, 3, 8)})
    o2, _ = _fwd_bwd(res.symbol, {"x": (2, 3, 8)})
    assert np.array_equal(o1[0], o2[0])


def test_canonicalize_keeps_output_identity_nodes():
    # an identity op that IS a program output must not be elided (its name
    # is the output name)
    x = mx.sym.Variable("x")
    net = x * 1.0
    res = analysis.rewrite(net, shapes={"x": (4,)})
    assert res.symbol.list_outputs() == net.list_outputs()


# ------------------------------------------------------ transformer parity
def test_transformer_rewrite_parity_and_node_reduction():
    """The zoo transformer's sloppy-frontend LN: CSE+canonicalize+DCE must
    shrink the graph, keep the forward BITWISE, and keep the backward
    within documented single-ulp cotangent-reassociation drift."""
    net = _tiny_transformer()
    res = analysis.rewrite(net, shapes=_TF_SHAPES, types=_TF_TYPES)
    assert res.counts["merged"] > 0 and res.counts["removed"] > 0
    assert res.nodes_after < res.nodes_before
    o1, g1 = _fwd_bwd(net, _TF_SHAPES, _TF_TYPES)
    o2, g2 = _fwd_bwd(res.symbol, _TF_SHAPES, _TF_TYPES)
    assert np.array_equal(o1[0], o2[0])  # forward: bitwise
    for k in g1:
        if g1[k] is None:
            continue
        # backward: the merged graph sums cotangents in a different order
        # than the duplicated one — ≤1e-6 absolute (measured ~3e-8)
        np.testing.assert_allclose(g1[k], g2[k], atol=1e-6, rtol=0,
                                   err_msg=k)


def test_rewrite_idempotent_second_run_is_noop():
    """Running the pipeline twice is a no-op: pass 2 fires zero rules and
    emits zero provenance records (the satellite contract)."""
    net = _tiny_transformer()
    r1 = analysis.rewrite(net, shapes=_TF_SHAPES, types=_TF_TYPES)
    assert r1.changed
    r2 = analysis.rewrite(r1.symbol, shapes=_TF_SHAPES, types=_TF_TYPES)
    assert r2.records == []
    assert not r2.changed
    assert r2.nodes_before == r2.nodes_after == r1.nodes_after
    assert r2.rounds == 1 and r2.fixpoint


# ------------------------------------------------------------------- bf16
def test_bf16_legalization_cast_sandwich():
    net = mx.models.get_symbol("mlp", num_classes=10)
    shapes = {"data": (4, 784), "softmax_label": (4,)}
    res = analysis.rewrite(net, shapes=shapes, bf16=True)
    assert res.counts["casts"] > 0
    rep = analysis.verify_rewrite(res, grad_req="write")
    assert not rep.errors, rep.format()  # GL601-clean: dtypes sandwiched
    casts = [n for n in res.symbol._topo() if n.op == "Cast"]
    assert any(str(n.parsed_attrs()["dtype"]) == "bfloat16" for n in casts)
    # bf16 compute, f32 interface: documented-tolerance parity, not bitwise
    o1, _ = _fwd_bwd(net, shapes)
    o2, _ = _fwd_bwd(res.symbol, shapes)
    assert o1[0].dtype == o2[0].dtype == np.float32
    np.testing.assert_allclose(o1[0], o2[0], atol=5e-2, rtol=0)
    # idempotent: a second run inserts nothing
    r2 = analysis.rewrite(res.symbol, shapes=shapes, bf16=True)
    assert r2.counts["casts"] == 0


def test_bf16_off_by_default():
    net = mx.models.get_symbol("mlp", num_classes=10)
    res = analysis.rewrite(net, shapes={"data": (4, 784)})
    assert res.counts["casts"] == 0


# -------------------------------------------------------- bind integration
def test_bind_rewrites_under_env_and_stays_bitwise(monkeypatch):
    net = _tiny_transformer()
    o1, _ = _fwd_bwd(net, _TF_SHAPES, _TF_TYPES)
    n_raw = len(net._topo())
    monkeypatch.setenv("MXNET_GRAPHREWRITE", "on")
    mx.random.seed(7)
    ex = net.simple_bind(mx.cpu(), type_dict=_TF_TYPES, grad_req="write",
                         **_TF_SHAPES)
    assert len(ex._prog.topo) < n_raw  # bound program IS the rewritten one
    assert ex._orig_symbol is net
    _fill(ex)
    ex.forward(is_train=True)
    assert np.array_equal(o1[0], ex.outputs[0].asnumpy())


def test_bind_verify_mode_clean_zoo(monkeypatch):
    monkeypatch.setenv("MXNET_GRAPHREWRITE", "verify")
    net = mx.models.get_symbol("mlp", num_classes=10)
    ex = net.simple_bind(mx.cpu(), data=(4, 784), softmax_label=(4,))
    assert ex.forward(is_train=False)[0].shape == (4, 10)


def test_bind_rewrite_off_by_default(monkeypatch):
    monkeypatch.delenv("MXNET_GRAPHREWRITE", raising=False)
    assert analysis.graphrewrite_mode() is None
    net = _tiny_transformer()
    ex = net.simple_bind(mx.cpu(), type_dict=_TF_TYPES, grad_req="write",
                         **_TF_SHAPES)
    assert len(ex._prog.topo) == len(net._topo())


def test_graphrewrite_mode_aliases_and_unknown(monkeypatch, caplog):
    monkeypatch.setenv("MXNET_GRAPHREWRITE", "1")
    assert analysis.graphrewrite_mode() == "on"
    monkeypatch.setenv("MXNET_GRAPHREWRITE", "verify")
    assert analysis.graphrewrite_mode() == "verify"
    monkeypatch.setenv("MXNET_GRAPHREWRITE", "bogus")
    with caplog.at_level("WARNING", logger="mxnet_tpu.graphrewrite"):
        assert analysis.graphrewrite_mode() is None


def test_spmd_adapter_binds_rewritten_symbol(monkeypatch):
    """The fused-SPMD path compiles the rewritten graph too."""
    monkeypatch.setenv("MXNET_MODULE_FUSED_STEP", "1")
    monkeypatch.setenv("MXNET_GRAPHREWRITE", "verify")
    net = _tiny_transformer()
    rs = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rs.randint(0, 50, (8, 8)).astype("int32"),
                           rs.randint(0, 50, (8, 8)).astype("float32"),
                           batch_size=4)
    mod = mx.mod.Module(net, context=mx.cpu(),
                        label_names=("softmax_label",))
    mod.fit(it, num_epoch=1, optimizer="sgd", eval_metric=mx.metric.Loss())
    assert mod._spmd is not None, "fused SPMD step did not engage"
    assert len(mod._spmd.trainer._prog.topo) < len(net._topo())


# ------------------------------------------------------------ observability
def test_rewrite_telemetry_counters(monkeypatch):
    from mxnet_tpu import telemetry

    monkeypatch.setenv("MXNET_TELEMETRY", "counters")
    telemetry.reset()
    analysis.rewrite(_tiny_transformer(), shapes=_TF_SHAPES,
                     types=_TF_TYPES)
    assert telemetry.counter("rewrite.runs").value == 1
    assert telemetry.counter("rewrite.nodes_merged").value > 0
    assert telemetry.counter("rewrite.nodes_removed").value > 0


def test_cli_rewrite_dump_and_json(capsys):
    from mxnet_tpu.analysis.cli import main

    rc = main(["transformer", "--rewrite"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "graphrewrite: transformer" in out
    assert "cse.merge" in out and "mul_self_to_square" in out
    rc = main(["transformer", "--rewrite", "--rewrite-json"])
    import json as _json

    payload = _json.loads(capsys.readouterr().out)
    assert rc == 0
    entry = payload[0]
    assert entry["rewrite"]["nodes_after"] < entry["rewrite"]["nodes_before"]
    assert sorted(entry) == ["records", "rewrite", "target", "verify"]
    assert entry["records"], "provenance records missing from the dump"
    assert not [d for d in entry["verify"]["diagnostics"]
                if d["code"] in ("GL601", "GL602", "GL604")]
