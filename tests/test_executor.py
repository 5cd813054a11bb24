"""Executor tests: bind/simple_bind, fwd/bwd numerics, grad_req, aux states.

Modeled on the reference's tests/python/unittest/test_executor.py
(bind correctness against numpy, grad accumulation, reshape)."""
import numpy as np
import pytest

import mxnet_tpu as mx


def _rand(*shape):
    return np.random.RandomState(0).uniform(-1, 1, shape).astype(np.float32)


def test_bind_add_mul_backward():
    rng = np.random.RandomState(3)
    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    c = a * b + a
    an, bn = rng.uniform(-1, 1, (4, 5)).astype("f"), rng.uniform(-1, 1, (4, 5)).astype("f")
    ga = mx.nd.zeros((4, 5))
    gb = mx.nd.zeros((4, 5))
    ex = c.bind(
        mx.cpu(),
        {"a": mx.nd.array(an), "b": mx.nd.array(bn)},
        args_grad={"a": ga, "b": gb},
    )
    out = ex.forward(is_train=True)
    assert np.allclose(out[0].asnumpy(), an * bn + an, atol=1e-6)
    head = np.ones((4, 5), dtype="f") * 2.0
    ex.backward(mx.nd.array(head))
    assert np.allclose(ga.asnumpy(), head * (bn + 1), atol=1e-6)
    assert np.allclose(gb.asnumpy(), head * an, atol=1e-6)


def test_grad_req_add_accumulates():
    a = mx.sym.Variable("a")
    out = a * 3.0
    ga = mx.nd.zeros((2, 2))
    ex = out.bind(mx.cpu(), {"a": mx.nd.ones((2, 2))}, args_grad={"a": ga}, grad_req="add")
    ex.forward(is_train=True)
    ex.backward(mx.nd.ones((2, 2)))
    ex.backward(mx.nd.ones((2, 2)))
    assert np.allclose(ga.asnumpy(), 6.0 * np.ones((2, 2)))


def test_grad_req_null_skips():
    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    out = a * b
    ga = mx.nd.zeros((2,))
    gb = mx.nd.zeros((2,))
    ex = out.bind(
        mx.cpu(),
        {"a": mx.nd.ones((2,)), "b": mx.nd.ones((2,))},
        args_grad={"a": ga, "b": gb},
        grad_req={"a": "write", "b": "null"},
    )
    ex.forward(is_train=True)
    ex.backward(mx.nd.ones((2,)))
    assert np.allclose(ga.asnumpy(), 1.0)
    assert np.allclose(gb.asnumpy(), 0.0)


def test_simple_bind_allocates_and_infers():
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data=data, num_hidden=8, name="fc")
    sm = mx.sym.SoftmaxOutput(data=fc, name="softmax")
    ex = sm.simple_bind(ctx=mx.cpu(), data=(16, 30))
    assert ex.arg_dict["fc_weight"].shape == (8, 30)
    assert ex.arg_dict["softmax_label"].shape == (16,)
    assert ex.grad_dict["fc_weight"].shape == (8, 30)
    out = ex.forward(is_train=False)
    assert out[0].shape == (16, 8)
    assert np.allclose(out[0].asnumpy().sum(axis=1), 1.0, atol=1e-5)


def test_softmax_output_backward_semantics():
    """SoftmaxOutput backward = (p - onehot) regardless of head gradient."""
    data = mx.sym.Variable("data")
    sm = mx.sym.SoftmaxOutput(data=data, name="softmax")
    x = _rand(3, 4)
    label = np.array([0, 1, 3], dtype="f")
    gd = mx.nd.zeros((3, 4))
    ex = sm.bind(
        mx.cpu(),
        {"data": mx.nd.array(x), "softmax_label": mx.nd.array(label)},
        args_grad={"data": gd},
    )
    out = ex.forward(is_train=True)
    ex.backward()
    p = np.exp(x) / np.exp(x).sum(axis=1, keepdims=True)
    onehot = np.eye(4, dtype="f")[label.astype(int)]
    assert np.allclose(out[0].asnumpy(), p, atol=1e-5)
    assert np.allclose(gd.asnumpy(), p - onehot, atol=1e-5)


def test_batchnorm_aux_updated_only_in_forward_train():
    data = mx.sym.Variable("data")
    bn = mx.sym.BatchNorm(data=data, momentum=0.5, name="bn")
    ex = bn.simple_bind(ctx=mx.cpu(), data=(8, 3))
    ex.arg_dict["data"][:] = _rand(8, 3) + 2.0
    ex.arg_dict["bn_gamma"][:] = 1.0
    mm0 = ex.aux_dict["bn_moving_mean"].asnumpy().copy()
    ex.forward(is_train=False)
    assert np.allclose(ex.aux_dict["bn_moving_mean"].asnumpy(), mm0)
    ex.forward(is_train=True)
    mm1 = ex.aux_dict["bn_moving_mean"].asnumpy().copy()
    assert not np.allclose(mm1, mm0)
    batch_mean = ex.arg_dict["data"].asnumpy().mean(axis=0)
    assert np.allclose(mm1, 0.5 * mm0 + 0.5 * batch_mean, atol=1e-5)
    # backward must not touch aux again
    ex.backward()
    assert np.allclose(ex.aux_dict["bn_moving_mean"].asnumpy(), mm1)


def test_forward_backward_fused_matches_separate():
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data=data, num_hidden=4, name="fc")
    sm = mx.sym.SoftmaxOutput(data=fc, name="softmax")
    x = _rand(6, 5)
    lab = np.array([0, 1, 2, 3, 0, 1], dtype="f")

    def build():
        ex = sm.simple_bind(ctx=mx.cpu(), data=(6, 5))
        ex.arg_dict["data"][:] = x
        ex.arg_dict["fc_weight"][:] = _rand(4, 5)
        ex.arg_dict["softmax_label"][:] = lab
        return ex

    e1, e2 = build(), build()
    e1.forward(is_train=True)
    e1.backward()
    e2.forward_backward()
    assert np.allclose(e1.outputs[0].asnumpy(), e2.outputs[0].asnumpy(), atol=1e-6)
    assert np.allclose(
        e1.grad_dict["fc_weight"].asnumpy(), e2.grad_dict["fc_weight"].asnumpy(), atol=1e-6
    )


def test_executor_forward_kwargs_update():
    a = mx.sym.Variable("a")
    out = a * 2.0
    ex = out.bind(mx.cpu(), {"a": mx.nd.zeros((2, 2))})
    res = ex.forward(a=np.full((2, 2), 3.0, dtype="f"))
    assert np.allclose(res[0].asnumpy(), 6.0)


def test_executor_reshape():
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data=data, num_hidden=4, name="fc")
    ex = fc.simple_bind(ctx=mx.cpu(), data=(8, 10))
    w = ex.arg_dict["fc_weight"]
    w[:] = _rand(4, 10)
    ex2 = ex.reshape(data=(2, 10))
    assert ex2.arg_dict["data"].shape == (2, 10)
    # weight shape unchanged → same array shared
    assert ex2.arg_dict["fc_weight"].shape == (4, 10)
    x = _rand(2, 10)
    ex2.arg_dict["data"][:] = x
    out = ex2.forward()
    assert np.allclose(out[0].asnumpy(), x @ w.asnumpy().T, atol=1e-5)


def test_executor_copy_params_from():
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data=data, num_hidden=3, no_bias=True, name="fc")
    ex = fc.simple_bind(ctx=mx.cpu(), data=(2, 4))
    w = _rand(3, 4)
    ex.copy_params_from({"fc_weight": mx.nd.array(w)})
    assert np.allclose(ex.arg_dict["fc_weight"].asnumpy(), w)
    with pytest.raises(mx.MXNetError):
        ex.copy_params_from({"bogus": mx.nd.zeros((1,))})


def test_dropout_rng_consistent_between_fwd_bwd():
    data = mx.sym.Variable("data")
    d = mx.sym.Dropout(data=data, p=0.5, name="drop")
    x = np.ones((100,), dtype="f")
    gd = mx.nd.zeros((100,))
    ex = d.bind(mx.cpu(), {"data": mx.nd.array(x)}, args_grad={"data": gd})
    out = ex.forward(is_train=True)
    mask_fwd = out[0].asnumpy() != 0
    ex.backward(mx.nd.ones((100,)))
    mask_bwd = gd.asnumpy() != 0
    assert (mask_fwd == mask_bwd).all()


def test_multi_output_executor():
    data = mx.sym.Variable("data")
    parts = mx.sym.SliceChannel(data=data, num_outputs=2, axis=1, name="sl")
    g = mx.sym.Group([parts[0] * 2.0, parts[1] + 1.0])
    x = _rand(3, 4)
    ex = g.bind(mx.cpu(), {"data": mx.nd.array(x)})
    outs = ex.forward()
    assert len(outs) == 2
    assert np.allclose(outs[0].asnumpy(), x[:, :2] * 2.0, atol=1e-6)
    assert np.allclose(outs[1].asnumpy(), x[:, 2:] + 1.0, atol=1e-6)


def test_rnn_symbol_bind():
    data = mx.sym.Variable("data")
    rnn = mx.sym.RNN(
        data=data, state_size=6, num_layers=1, mode="lstm", name="lstm", state_outputs=True
    )
    arg_shapes, out_shapes, _ = rnn.infer_shape(data=(7, 2, 5))
    d = dict(zip(rnn.list_arguments(), arg_shapes))
    assert d["lstm_state"] == (1, 2, 6)
    assert out_shapes[0] == (7, 2, 6)
    ex = rnn.simple_bind(ctx=mx.cpu(), data=(7, 2, 5))
    ex.arg_dict["data"][:] = _rand(7, 2, 5)
    ex.arg_dict["lstm_parameters"][:] = _rand(*d["lstm_parameters"]) * 0.1
    outs = ex.forward(is_train=True)
    assert outs[0].shape == (7, 2, 6)
    assert outs[1].shape == (1, 2, 6)


def test_backward_does_not_recompute_forward():
    """forward(is_train=True) + backward() must run the forward host-visible
    computation exactly once (the cached-vjp path; previously backward
    re-ran the fused fwd+bwd, silently doubling forward cost). Observed via
    a CustomOp whose forward increments a host counter."""
    from mxnet_tpu import operator as op

    counters = {"fwd": 0}

    @op.register("count_fwd_sigmoid")
    class CountProp(op.CustomOpProp):
        def __init__(self):
            super().__init__(need_top_grad=True)

        def create_operator(self, ctx, in_shapes, in_dtypes):
            class CountOp(op.CustomOp):
                def forward(self, is_train, req, in_data, out_data, aux):
                    counters["fwd"] += 1
                    x = in_data[0].asnumpy()
                    self.assign(out_data[0], req[0], 1.0 / (1.0 + np.exp(-x)))

                def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
                    y = out_data[0].asnumpy()
                    self.assign(in_grad[0], req[0],
                                out_grad[0].asnumpy() * y * (1.0 - y))

            return CountOp()

    data = mx.sym.Variable("data")
    net = mx.sym.Custom(data=mx.sym.FullyConnected(data, num_hidden=4,
                                                   name="fc"),
                        op_type="count_fwd_sigmoid", name="sig")
    exe = net.simple_bind(ctx=mx.cpu(), data=(2, 3))
    rs = np.random.RandomState(0)
    for name, arr in exe.arg_dict.items():
        arr[:] = rs.rand(*arr.shape).astype("float32")
    counters["fwd"] = 0
    exe.forward(is_train=True)
    assert counters["fwd"] == 1
    exe.backward(out_grads=[mx.nd.ones((2, 4))])
    assert counters["fwd"] == 1, (
        "backward re-ran the forward %d extra time(s)" % (counters["fwd"] - 1))
    g = exe.grad_dict["fc_weight"].asnumpy()
    assert np.isfinite(g).all() and np.abs(g).sum() > 0


# ------------------------------------------------- names in the lowered text
_NAMED_NET = r"""
import re, sys
import jax
import mxnet_tpu as mx
for _ in range(int(sys.argv[1])):      # shift every auto-name counter
    mx.sym.FullyConnected(mx.sym.Variable("x"), num_hidden=2)
net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                            name="fc_head")
net = mx.sym.SoftmaxOutput(net, name="softmax")
exe = net.simple_bind(mx.cpu(), data=(2, 3), softmax_label=(2,))
fwd = exe._prog._fwd(False).lower(*exe._collect(), jax.random.PRNGKey(0))
both = exe._prog._fwd_bwd_cached(False).lower(
    *exe._collect(), (), jax.random.PRNGKey(0))
text = fwd.as_text(debug_info=True)
print(re.search(r"module @(\S+)", text).group(1),
      re.search(r"module @(\S+)", both.as_text()).group(1),
      int("/fc_head/dot_general" in text))
"""


def _lowered_names(shift):
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _NAMED_NET, str(shift)],
                         capture_output=True, text=True, timeout=300,
                         env=env, cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


def test_jitted_programs_are_named_for_what_they_are_in_every_process():
    """The lowered module of a bound symbol is ``jit_mx_<head>_<kind>``,
    not ``jit_run``; a node's name is in its instructions' ``op_name``
    metadata; and a second process, whose auto-name counters stand
    elsewhere, gives the same names (the compile cache keys on them)."""
    first, second = _lowered_names(0), _lowered_names(5)
    assert first == ["jit_mx_softmax_fwd", "jit_mx_softmax_fwd_bwd", "1"]
    assert second == first


def test_program_label_names_the_forward_program():
    from mxnet_tpu.executor import _GraphProgram

    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                                name="odd-name.1")
    prog = _GraphProgram(net)
    assert prog.program_name("fwd") == "mx_odd_name_1_fwd"
    assert prog.program_name("fwd_bwd") == "mx_odd_name_1_fwd_bwd"
    prog.label = "mx_decode"
    assert prog.program_name("fwd") == "mx_decode"
    assert prog._fwd(False).__name__ == "mx_decode"


# ------------------------------------------------ one way to compute a node
def _block_sizes(arch):
    """An architecture's sizes and serving sizes as its own block test has
    them (``vaswani``'s are the paged decoder's test's)."""
    import importlib

    if arch == "vaswani":
        cfg = importlib.import_module("test_kv_decode").CFG
        return (dict(cfg, pos_len=32),
                dict(max_len=32, prefill_len=16, page_size=8, lanes=4))
    block = importlib.import_module("test_%s_block" % arch)
    if arch == "ouro":
        return block.SIZES, block.SERVING
    return block.CFG, dict(block.SERVE, prefill_len=block.SERVE.get(
        "prefill_len", block.SERVE["max_len"]))


def _bound_graph(graph):
    """(symbol, the shapes of its data, training or not) of one graph the
    system binds: ResNet-50's and ``vaswani``'s training graphs (decoder
    alone, and encoder with decoder), ``vaswani``'s chunk graph, and every
    architecture's prefill and single-step decode graph."""
    from mxnet_tpu import models
    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.ops.attention import pool_shape

    if graph == "resnet50_train":
        return (models.get_symbol("resnet-50", num_classes=1000,
                                  image_shape="3,224,224"),
                dict(data=(2, 3, 224, 224), softmax_label=(2,)), True)
    if graph == "vaswani_train":
        cfg, _ = _block_sizes("vaswani")
        return (tf.get_symbol(seq_len=16, **dict(cfg, pos_len=None)),
                dict(data=(2, 16), softmax_label=(2, 16)), True)
    if graph == "vaswani_mt_train":
        cfg, _ = _block_sizes("vaswani")
        return (tf.get_symbol_mt(src_len=8, tgt_len=16,
                                 **dict(cfg, pos_len=None)),
                dict(data=(2, 8), dec_data=(2, 16), softmax_label=(2, 16)),
                True)
    arch, program = graph.rsplit("_", 1)
    cfg, serve = _block_sizes(arch)
    if program == "chunk":
        slots, chunk = serve["lanes"] * serve["max_len"], serve["page_size"]
        shapes = dict(data=(1, chunk), pos_idx=(1, chunk),
                      write_onehot=(chunk, slots), att_mask=(chunk, slots))
        for name, _, shape in tf.decode_cache(arch=arch, **cfg):
            shapes[name] = pool_shape(*shape, slots, serve["page_size"])
        return (tf.get_chunk_symbol(chunk_len=chunk, total_slots=slots,
                                    **cfg), shapes, False)
    sizes = dict({"arch": arch}, **cfg)
    sizes.pop("pos_len", None)
    # the checkpoint's shapes, as a decoder binds them (``vaswani``'s follow
    # from the data's)
    weights = {} if arch == "vaswani" else tf.param_shapes(**sizes)
    if program == "prefill":
        bucket = serve["prefill_len"]
        return (tf.get_prefill_symbol(prefill_len=bucket, **cfg),
                dict(weights, data=(1, bucket), length=(1, 1)), False)
    lanes, page = serve["lanes"], serve["page_size"]
    slots = lanes * serve["max_len"]
    shapes = dict(weights, data=(lanes, 1), pos_idx=(lanes, 1),
                  write_slot=(lanes, 1),
                  page_table=(lanes, serve["max_len"] // page))
    for name, kind, shape in tf.decode_cache(**sizes):
        shapes[name] = pool_shape(
            *shape, slots * tf.loop_passes(**sizes), page) \
            if kind == "pool" else (lanes,) + tuple(shape)
    return (tf.get_decode_symbol(max_len=slots, page_size=page, **cfg),
            shapes, False)


def _graph_names():
    from mxnet_tpu.models.transformer import ARCHS

    return ["resnet50_train", "vaswani_train", "vaswani_mt_train",
            "vaswani_chunk"] + ["%s_%s" % (arch, program) for arch in ARCHS
                                for program in ("prefill", "decode")]


@pytest.fixture(scope="module")
def interpreted():
    """``interpreted(graph)``: ONE abstract interpretation of a bound graph
    (``jax.make_jaxpr`` of ``_GraphProgram.interpret``: no compile, no bind)
    with ``OpDef.apply`` wrapped, kept for the module. Gives the program,
    how often each operator was applied, every name on the name stack of an
    equation, and for each node a function that interprets that node ALONE
    over the same abstract operands and counts the equations it emits."""
    import collections

    import jax

    from mxnet_tpu.executor import _GraphProgram
    from mxnet_tpu.ops.registry import OpDef

    kept = {}
    abstract = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)

    def interpret(graph):
        if graph in kept:
            return kept[graph]
        net, shapes, is_train = _bound_graph(graph)
        prog = _GraphProgram(net)
        arg_shapes, _, aux_shapes = net.infer_shape(**shapes)
        # an operator is handed its node's parsed attributes, the same dict
        # every time: that names the node an application is for
        node_of = {id(n.parsed_attrs()): n for n in prog.topo
                   if not n.is_variable}
        applied, alone = collections.Counter(), {}
        apply = OpDef.apply

        def watching(self, attrs, inputs, aux=None, is_train=False, rng=None):
            applied[self.name] += 1
            operands = ([abstract(x) for x in inputs],
                        [abstract(x) for x in aux or []],
                        None if rng is None else abstract(rng))

            def equations():
                return len(jax.make_jaxpr(
                    lambda ins, ax, key: apply(
                        self, attrs, ins, aux=ax, is_train=is_train,
                        rng=key))(*operands).jaxpr.eqns)

            alone[node_of[id(attrs)].name] = equations
            return apply(self, attrs, inputs, aux=aux, is_train=is_train,
                         rng=rng)

        spec = lambda found: tuple(jax.ShapeDtypeStruct(s, "float32")
                                   for s in found)
        OpDef.apply = watching
        try:
            closed = jax.make_jaxpr(
                lambda a, x, k: prog.interpret(a, x, is_train, k))(
                    spec(arg_shapes), spec(aux_shapes), jax.random.PRNGKey(0))
        finally:
            OpDef.apply = apply
        scopes = {part for eqn in closed.jaxpr.eqns
                  for part in str(eqn.source_info.name_stack).split("/")}
        kept[graph] = (prog, applied, alone, scopes)
        return kept[graph]

    return interpret


@pytest.mark.parametrize("graph", _graph_names())
def test_every_node_is_computed_by_its_registered_operator(interpreted,
                                                           graph):
    """Interpreting a graph the system binds applies the registry's operator
    once at every node and nothing else: the graph layer holds no second way
    to compute a node and no module that could plan one."""
    import collections
    import importlib.util

    prog, applied, _, _ = interpreted(graph)
    ops = collections.Counter(n.opdef().name for n in prog.topo
                              if not n.is_variable)
    assert applied == ops
    if graph == "resnet50_train":
        assert (ops["Convolution"], ops["BatchNorm"], ops["Activation"],
                ops["elemwise_add"]) == (53, 50, 50, 16)
    assert importlib.util.find_spec("mxnet_tpu.fusion") is None


@pytest.mark.parametrize("graph", _graph_names())
def test_every_node_leaves_its_own_name(interpreted, graph):
    """Every operator node that emits an equation at all has its own name on
    the name stack of at least one: an instruction's ``op_name`` carries its
    symbol node's name, not its consumer's (``PERF.md`` section 3). A node
    whose name is on none is interpreted alone and must emit nothing (an
    identity, a constant): exempt by that rule, not by a list of names."""
    prog, _, alone, scopes = interpreted(graph)
    nodes = {n.name for n in prog.topo if not n.is_variable}
    assert set(alone) == nodes
    assert len(nodes & scopes) > len(nodes) // 2, sorted(nodes - scopes)
    under_another = {name: alone[name]() for name in nodes - scopes}
    assert not any(under_another.values()), under_another
