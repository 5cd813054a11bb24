"""graphlint test suite (analysis/ subsystem).

Every diagnostic code ships with BOTH a trigger (a deliberately-broken
graph or schedule that fires it) and a clean case (a healthy graph or
schedule that does not) — parametrized from one table so the completeness
meta-test can prove no code is untested. Plus: bind-time integration
(MXNET_GRAPHLINT=warn|error), the engine wait_for_var satellite fix, the
infer_meta registry, the CLI, and the models/resnet.py lint-clean
regression.
"""
import json
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import analysis
from mxnet_tpu import engine as eng
from mxnet_tpu.analysis import CODES, RecordingEngine, analyze_trace


def _codes(sym, **kw):
    return set(analysis.lint(sym, **kw).codes())


# --------------------------------------------------------------------------
# graph-code table: code -> (broken_builder, clean_builder), each returning
# (symbol, lint_kwargs)
# --------------------------------------------------------------------------
def _gl001_broken():
    a = mx.sym.Variable("a", shape=(2, 3))
    b = mx.sym.Variable("b", shape=(4, 5))
    return mx.sym.dot(a, b, name="baddot"), {}


def _gl001_clean():
    a = mx.sym.Variable("a", shape=(2, 3))
    b = mx.sym.Variable("b", shape=(3, 5))
    return mx.sym.dot(a, b, name="okdot"), {}


def _gl002_broken():
    d = mx.sym.Variable("data")
    e = mx.sym.Variable("extra")
    s = mx.sym.FullyConnected(data=d, num_hidden=4, name="fcA") \
        + mx.sym.FullyConnected(data=e, num_hidden=4, name="fcB")
    return s, {"shapes": {"data": (2, 8)}}  # 'extra' stays unknown


def _gl002_clean():
    d = mx.sym.Variable("data")
    s = mx.sym.FullyConnected(data=d, num_hidden=4, name="fcC")
    return s, {"shapes": {"data": (2, 8)}}


def _gl003_broken():
    d = mx.sym.Variable("data")
    w = mx.sym.Variable("fc_weight", shape=(7, 99))
    return (mx.sym.FullyConnected(data=d, weight=w, num_hidden=7, name="fc"),
            {"shapes": {"data": (2, 10)}})


def _gl003_clean():
    d = mx.sym.Variable("data")
    w = mx.sym.Variable("fc_weight", shape=(7, 10))
    return (mx.sym.FullyConnected(data=d, weight=w, num_hidden=7, name="fc"),
            {"shapes": {"data": (2, 10)}})


def _gl004_broken():
    x = mx.sym.Variable("x", dtype="float16")
    y = mx.sym.Variable("y", dtype="float32")
    return x + y, {"shapes": {"x": (2,), "y": (2,)}}


def _gl004_clean():
    x = mx.sym.Variable("x", dtype="float16")
    y = mx.sym.Variable("y", dtype="float16")
    return x + y, {"shapes": {"x": (2,), "y": (2,)}}


def _gl005_broken():
    return mx.sym.Variable("dup") + mx.sym.Variable("dup"), \
        {"shapes": {"dup": (2,)}}


def _gl005_clean():
    return mx.sym.Variable("p") + mx.sym.Variable("q"), \
        {"shapes": {"p": (2,), "q": (2,)}}


def _gl006_broken():
    d = mx.sym.Variable("data")
    flat = mx.sym.Flatten(data=d)
    return (mx.sym.Convolution(data=flat, num_filter=8, kernel=(3, 3),
                               name="badconv"),
            {"shapes": {"data": (2, 3, 8, 8)}})


def _gl006_clean():
    d = mx.sym.Variable("data")
    return (mx.sym.Convolution(data=d, num_filter=8, kernel=(3, 3),
                               pad=(1, 1), name="okconv"),
            {"shapes": {"data": (2, 3, 8, 8)}})


def _gl201_broken():
    return mx.sym.Variable("x") * 0.125, {}


def _gl201_clean():
    return mx.sym.Variable("x") + mx.sym.Variable("y"), {}


def _gl202_broken():
    h = mx.sym.Variable("h", dtype="float16")
    x = mx.sym.Variable("x")  # weak: defaults to f32 at trace time
    return x + h, {}


def _gl202_clean():
    h = mx.sym.Variable("h", dtype="float16")
    x = mx.sym.Variable("x", dtype="float16")
    return x + h, {}


def _gl203_broken():
    # no shape hints at all: data inputs are shape-polymorphic
    return mx.sym.FullyConnected(data=mx.sym.Variable("data"),
                                 num_hidden=4, name="fcP"), {}


def _gl203_clean():
    return mx.sym.FullyConnected(data=mx.sym.Variable("data"),
                                 num_hidden=4, name="fcP"), \
        {"shapes": {"data": (2, 8)}}


# --- GL4xx: sharding-plan lint (mesh/rules kwargs ride through lint()) -----
def _gl401_broken():
    # weight (999, 783): both dims odd, prod >= min_shard_elems -> the rule
    # silently falls back to full replication
    d = mx.sym.Variable("data")
    return (mx.sym.FullyConnected(data=d, num_hidden=999, name="oddfc"),
            {"shapes": {"data": (4, 783)}, "mesh": "dp=2,model=2"})


def _gl401_clean():
    d = mx.sym.Variable("data")
    return (mx.sym.FullyConnected(data=d, num_hidden=1000, name="evenfc"),
            {"shapes": {"data": (4, 784)}, "mesh": "dp=2,model=2"})


def _gl402_broken():
    # fc1's weight is sharded (out dim model-split), so its activation is
    # model-sharded on dim 1; fc2's weight is too small to shard, so the
    # contraction is sharded on the data side only -> implicit all-gather
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data=d, num_hidden=256, name="fcbig")
    return (mx.sym.FullyConnected(data=h, num_hidden=8, name="fcsmall"),
            {"shapes": {"data": (8, 512)}, "mesh": "dp=2,model=2"})


def _gl402_clean():
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data=d, num_hidden=16, name="fc_a")
    return (mx.sym.FullyConnected(data=h, num_hidden=8, name="fc_b"),
            {"shapes": {"data": (8, 64)}, "mesh": "dp=2,model=2"})


def _gl403_broken():
    # sum collapses the data-sharded batch dim MID-graph (the scalar then
    # feeds another op) -> everything downstream runs un-sharded
    d = mx.sym.Variable("data")
    s = mx.sym.sum(d, name="collapse")
    return s * 2.0, {"shapes": {"data": (8, 16)}, "mesh": "dp=2"}


def _gl403_clean():
    # the same reduction as the graph HEAD is a loss-style scalar: fine
    d = mx.sym.Variable("data")
    return (mx.sym.sum(d, name="lossval"),
            {"shapes": {"data": (8, 16)}, "mesh": "dp=2"})


def _gl404_broken():
    d = mx.sym.Variable("data")
    return (mx.sym.FullyConnected(data=d, num_hidden=8, name="fc"),
            {"shapes": {"data": (3, 16)}, "mesh": "dp=2"})


def _gl404_clean():
    d = mx.sym.Variable("data")
    return (mx.sym.FullyConnected(data=d, num_hidden=8, name="fc"),
            {"shapes": {"data": (4, 16)}, "mesh": "dp=2"})


def _gl405_rules(param_rule):
    from mxnet_tpu.parallel import ShardingRules, parse_mesh_spec

    mesh = parse_mesh_spec("dp=2,model=2")
    return mesh, ShardingRules.infer_axes(mesh, param_rule=param_rule)


def _gl405_broken():
    from jax.sharding import PartitionSpec as P

    mesh, rules = _gl405_rules(lambda name, shape: P())  # replicate all
    d = mx.sym.Variable("data")
    return (mx.sym.FullyConnected(data=d, num_hidden=256, name="fc"),
            {"shapes": {"data": (8, 512)}, "mesh": mesh, "rules": rules})


def _gl405_clean():
    mesh, rules = _gl405_rules(None)  # the default rule shards it
    d = mx.sym.Variable("data")
    return (mx.sym.FullyConnected(data=d, num_hidden=256, name="fc"),
            {"shapes": {"data": (8, 512)}, "mesh": mesh, "rules": rules})


# --- GL5xx: memory planner (no mesh needed: plans replicated) --------------
def _gl501_broken():
    d = mx.sym.Variable("data")
    return (mx.sym.FullyConnected(data=d, num_hidden=8, name="fc"),
            {"shapes": {"data": (8, 16)}, "budget_gb": 1e-6})


def _gl501_clean():
    d = mx.sym.Variable("data")
    return (mx.sym.FullyConnected(data=d, num_hidden=8, name="fc"),
            {"shapes": {"data": (8, 16)}, "budget_gb": 1000.0})


def _gl502_broken():
    # one 1-GiB activation (4096 x 65536 f32) IS the stash: it dominates
    # the fwd->bwd watermark and the fix is a recompute policy
    d = mx.sym.Variable("data")
    return (mx.sym.FullyConnected(data=d, num_hidden=65536, name="bigfc"),
            {"shapes": {"data": (4096, 64)}})


def _gl502_clean():
    d = mx.sym.Variable("data")
    return (mx.sym.FullyConnected(data=d, num_hidden=1024, name="smallfc"),
            {"shapes": {"data": (64, 64)}})


GRAPH_CODE_CASES = {
    "GL001": (_gl001_broken, _gl001_clean),
    "GL002": (_gl002_broken, _gl002_clean),
    "GL003": (_gl003_broken, _gl003_clean),
    "GL004": (_gl004_broken, _gl004_clean),
    "GL005": (_gl005_broken, _gl005_clean),
    "GL006": (_gl006_broken, _gl006_clean),
    "GL201": (_gl201_broken, _gl201_clean),
    "GL202": (_gl202_broken, _gl202_clean),
    "GL203": (_gl203_broken, _gl203_clean),
    "GL401": (_gl401_broken, _gl401_clean),
    "GL402": (_gl402_broken, _gl402_clean),
    "GL403": (_gl403_broken, _gl403_clean),
    "GL404": (_gl404_broken, _gl404_clean),
    "GL405": (_gl405_broken, _gl405_clean),
    "GL501": (_gl501_broken, _gl501_clean),
    "GL502": (_gl502_broken, _gl502_clean),
}


@pytest.mark.parametrize("code", sorted(GRAPH_CODE_CASES))
def test_graph_code_triggers_on_broken_graph(code):
    sym, kw = GRAPH_CODE_CASES[code][0]()
    assert code in _codes(sym, **kw)


@pytest.mark.parametrize("code", sorted(GRAPH_CODE_CASES))
def test_graph_code_silent_on_clean_graph(code):
    sym, kw = GRAPH_CODE_CASES[code][1]()
    assert code not in _codes(sym, **kw)


# --------------------------------------------------------------------------
# engine-schedule codes: trace builders over a RecordingEngine
# --------------------------------------------------------------------------
def _trace_gl101_broken(e):
    v = e.new_variable()
    e.push(lambda: None, const_vars=[v], mutable_vars=[v])


def _trace_gl102_broken(e):
    v = e.new_variable()
    e.push(lambda: None, const_vars=[v])
    e.wait_for_var(v)


def _trace_gl103_broken(e):
    v = e.new_variable()
    e.push(lambda: None, mutable_vars=[v, v])


def _trace_gl104_broken(e):
    v = e.new_variable()
    e.push(lambda: None, const_vars=[v])   # read before any write
    e.push(lambda: None, mutable_vars=[v])


def _trace_clean(e):
    v, w = e.new_variable(), e.new_variable()
    e.push(lambda: None, mutable_vars=[v])
    e.push(lambda: None, const_vars=[v], mutable_vars=[w])
    e.push(lambda: None, const_vars=[v, w])
    e.wait_for_var(w)


ENGINE_CODE_CASES = {
    "GL101": _trace_gl101_broken,
    "GL102": _trace_gl102_broken,
    "GL103": _trace_gl103_broken,
    "GL104": _trace_gl104_broken,
}


@pytest.mark.parametrize("code", sorted(ENGINE_CODE_CASES))
def test_engine_code_triggers_on_broken_schedule(code):
    e = RecordingEngine(eng.NaiveEngine())
    ENGINE_CODE_CASES[code](e)
    assert code in analyze_trace(e.trace).codes()


@pytest.mark.parametrize("code", sorted(ENGINE_CODE_CASES) + ["GL105"])
def test_engine_code_silent_on_clean_schedule(code):
    e = RecordingEngine(eng._PythonThreadedEngine(2), assert_discipline=True)
    _trace_clean(e)
    e.wait_for_all()
    assert code not in analyze_trace(e.trace).codes()


class _NoDisciplineEngine(eng.Engine):
    """Deliberately broken: runs every op on its own thread, ignoring the
    declared var sets entirely — what the shim exists to catch."""

    def __init__(self):
        self._n = 0
        self._threads = []

    def new_variable(self):
        self._n += 1
        return self._n

    def push(self, fn, const_vars=(), mutable_vars=()):
        def quiet():
            try:
                fn()
            except Exception:
                pass  # the shim raises; the trace records it

        t = threading.Thread(target=quiet)
        t.start()
        self._threads.append(t)

    def wait_for_var(self, var):
        self.wait_for_all()

    def wait_for_all(self):
        for t in self._threads:
            t.join()


def test_gl105_runtime_shim_catches_broken_engine():
    e = RecordingEngine(_NoDisciplineEngine(), assert_discipline=True)
    v = e.new_variable()
    gate = threading.Event()
    started = threading.Event()

    def first():
        started.set()
        gate.wait(5)

    e.push(first, mutable_vars=[v])
    assert started.wait(5)
    e.push(lambda: None, mutable_vars=[v])  # overlapping writer
    time.sleep(0.05)
    gate.set()
    e.wait_for_all()
    report = analyze_trace(e.trace)
    assert "GL105" in report.codes()
    assert any("write-write" in d.message for d in report.by_code("GL105"))


def test_shipped_python_engine_passes_discipline_shim():
    """The pure-Python fallback engine, under a real concurrent workload,
    never violates the var discipline the shim asserts."""
    e = RecordingEngine(eng._PythonThreadedEngine(4), assert_discipline=True)
    vars_ = [e.new_variable() for _ in range(4)]
    for i in range(80):
        e.push(lambda: time.sleep(0.0005), mutable_vars=[vars_[i % 4]])
        e.push(lambda: None, const_vars=[vars_[i % 4]],
               mutable_vars=[vars_[(i + 1) % 4]])
    e.wait_for_all()
    assert not e.trace.violations
    assert "GL105" not in analyze_trace(e.trace).codes()


# --------------------------------------------------------------------------
# GL6xx: graph-rewrite verifier codes (analysis/rewrite.py). These come from
# verify_rewrite over a RewriteResult, not from lint() — each case returns
# the code set the verifier produced. Deliberately-buggy custom passes
# exercise the contract a correct pass must uphold.
# --------------------------------------------------------------------------
def _rw_codes(sym, passes=None, grad_req=None, max_rounds=None, shapes=None,
              types=None):
    res = analysis.rewrite(sym, shapes=shapes, types=types, passes=passes,
                           max_rounds=max_rounds)
    return set(analysis.verify_rewrite(res, grad_req=grad_req).codes())


class _OncePass(analysis.RewritePass):
    """Base for the buggy test passes: fires exactly once."""

    def __init__(self):
        self._done = False

    def run(self, g):
        if self._done:
            return 0
        self._done = True
        return self._fire(g)


class _ShapeBreakingPass(_OncePass):
    """Replaces the output with its whole-array sum — shape drift."""

    name = "badshape"

    def _fire(self, g):
        node, oi = g.outputs[0]
        new = g.new_node("sum", node.name + "_collapsed", {}, [(node, oi)])
        g.outputs[0] = (new, 0)
        g.note(self.name, "collapse", "replace", node=new.name,
               origins=[node.name])
        g.invalidate()
        return 1


class _NoProvenancePass(_OncePass):
    """Inserts an identity node but never notes it — a provenance gap."""

    name = "noprov"

    def _fire(self, g):
        node, oi = g.outputs[0]
        new = g.new_node("_copy", node.name + "_id", {}, [(node, oi)])
        g.outputs[0] = (new, 0)
        g.invalidate()
        return 1


class _NeverConvergesPass(analysis.RewritePass):
    """Claims a firing every round without changing the graph."""

    name = "pingpong"

    def run(self, g):
        return 1


class _ArgDroppingPass(_OncePass):
    """Replaces the output with a literal of the same shape/dtype — every
    argument becomes unreachable while shapes/dtypes stay intact."""

    name = "argdrop"

    def _fire(self, g):
        import numpy as _np

        arr = _np.zeros((2,), "float32")
        lit = g.new_node("_graph_const", "lit",
                         {"data": arr.tobytes(), "shape": (2,),
                          "dtype": "float32"}, [])
        g.outputs[0] = (lit, 0)
        g.note(self.name, "drop", "replace", node=lit.name,
               origins=[g.topo()[0].name])
        g.invalidate()
        return 1


def _scalar_chain():
    return mx.sym.Variable("x") * 2.0, {"shapes": {"x": (2,)}}


def _gl601_broken_rw():
    sym, kw = _scalar_chain()
    return _rw_codes(sym, passes=[_ShapeBreakingPass()], **kw)


def _gl601_clean_rw():
    sym, kw = _scalar_chain()
    return _rw_codes(sym, **kw)


def _gl602_broken_rw():
    sym, kw = _scalar_chain()
    return _rw_codes(sym, passes=[_NoProvenancePass()], **kw)


def _gl602_clean_rw():
    # the builtin pipeline notes every node it creates
    d = mx.sym.Variable("data")
    net = mx.sym.Activation(d * d, act_type="relu")  # fires canonicalize
    return _rw_codes(net, shapes={"data": (2, 3)})


def _gl603_broken_rw():
    sym, kw = _scalar_chain()
    return _rw_codes(sym, passes=[_NeverConvergesPass()], max_rounds=2,
                     **kw)


def _gl603_clean_rw():
    net = mx.models.get_symbol("transformer", vocab_size=20, model_dim=16,
                               num_heads=2, num_layers=1, ffn_dim=16,
                               seq_len=4)
    return _rw_codes(net)  # real multi-pass run converges in budget


def _gl604_broken_rw():
    sym, kw = _scalar_chain()
    return _rw_codes(sym, passes=[_ArgDroppingPass()], grad_req="write",
                     **kw)


def _gl604_clean_rw():
    sym, kw = _scalar_chain()
    return _rw_codes(sym, passes=[_ArgDroppingPass()], grad_req="null",
                     **kw)


def _gl605_broken_rw():
    # "broken" here = the summary fires whenever the pipeline changed
    # anything: a graph with a common subexpression
    a, b = mx.sym.Variable("a"), mx.sym.Variable("b")
    net = (a + b) * (a + b)
    return _rw_codes(net, shapes={"a": (2,), "b": (2,)})


def _gl605_clean_rw():
    # an already-canonical graph: zero records, no summary
    return _rw_codes(mx.models.get_symbol("mlp", num_classes=10),
                     shapes={"data": (2, 784)})


REWRITE_CODE_CASES = {
    "GL601": (_gl601_broken_rw, _gl601_clean_rw),
    "GL602": (_gl602_broken_rw, _gl602_clean_rw),
    "GL603": (_gl603_broken_rw, _gl603_clean_rw),
    "GL604": (_gl604_broken_rw, _gl604_clean_rw),
    "GL605": (_gl605_broken_rw, _gl605_clean_rw),
}


@pytest.mark.parametrize("code", sorted(REWRITE_CODE_CASES))
def test_rewrite_code_triggers_on_broken_rewrite(code):
    assert code in REWRITE_CODE_CASES[code][0]()


@pytest.mark.parametrize("code", sorted(REWRITE_CODE_CASES))
def test_rewrite_code_silent_on_clean_rewrite(code):
    assert code not in REWRITE_CODE_CASES[code][1]()


# --------------------------------------------------------------------------
# dispatch-discipline codes (GL7xx): source snippets through the AST lint
# (GL701-GL704), synthetic gap rows through the measured lint (GL705)
# --------------------------------------------------------------------------
from mxnet_tpu.analysis import dispatch_lint  # noqa: E402

_GL701_BROKEN = """
def greedy(dec, tok, n):
    for _ in range(n):
        logits = dec.decode_step(tok)
        tok = logits.asnumpy()
    return tok
"""

_GL701_CLEAN = """
def drain(dec, toks):
    outs = []
    for t in toks:
        outs.append(dec.decode_step(t))
    return outs[-1].asnumpy()
"""

_GL702_BROKEN = """
def decode(dec, tok, n):
    for _ in range(n):
        tok = dec.decode_step(tok)
    return tok
"""

# the lax.scan rewrite of _GL702_BROKEN: the loop state rides as the scan
# carry and the host dispatches ONE megastep — exactly the fix GL702 asks for
_GL702_CLEAN = """
def decode(dec, tok, n):
    def megastep(carry, _):
        return dec.scan_body(carry), None
    final, _ = lax.scan(megastep, tok, None, length=n)
    return final
"""

_GL703_BROKEN = """
def pick(dec, x):
    logits = dec.decode_step(x)
    return np.argmax(logits, axis=-1)
"""

_GL703_CLEAN = """
def pick(dec, x):
    ids = dec.greedy_step(x)
    return ids
"""

_GL703_WAIVED = """
def pick(dec, x):
    logits = dec.decode_step(x)
    return np.argmax(logits, axis=-1)  # graphlint: waive GL703 -- acknowledged
"""

_GL704_BROKEN = """
def run2(a, b, x):
    ya = a.forward(x)
    out = ya.asnumpy()
    yb = b.forward(x)
    return out, yb.asnumpy()
"""

_GL704_CLEAN = """
def run2(a, b, x):
    ya = a.forward(x)
    yb = b.forward(x)
    return ya.asnumpy(), yb.asnumpy()
"""


def _dl_codes(src):
    return {f.code
            for f in dispatch_lint.lint_dispatch_source("<case>", text=src)}


def _gl705_rows(gap_ms):
    return [{"name": "serving.decode_step", "count": 10, "intervals": 9,
             "busy_ms": 10.0, "gap_ms": gap_ms, "max_gap_ms": gap_ms / 2.0,
             "clamped": 0}]


def _gl705_codes(gap_ms):
    return {d.code
            for d in dispatch_lint.lint_dispatch_gaps(_gl705_rows(gap_ms),
                                                      pct=0.25)}


DISPATCH_CODE_CASES = {
    "GL701": (lambda: _dl_codes(_GL701_BROKEN),
              lambda: _dl_codes(_GL701_CLEAN)),
    "GL702": (lambda: _dl_codes(_GL702_BROKEN),
              lambda: _dl_codes(_GL702_CLEAN)),
    "GL703": (lambda: _dl_codes(_GL703_BROKEN),
              lambda: _dl_codes(_GL703_CLEAN)),
    "GL704": (lambda: _dl_codes(_GL704_BROKEN),
              lambda: _dl_codes(_GL704_CLEAN)),
    # 8 ms host gap against 10 ms busy = 80% >> the 25% threshold; the
    # clean side's 1 ms = 10% stays under it
    "GL705": (lambda: _gl705_codes(8.0), lambda: _gl705_codes(1.0)),
}


@pytest.mark.parametrize("code", sorted(DISPATCH_CODE_CASES))
def test_dispatch_code_triggers_on_broken_source(code):
    assert code in DISPATCH_CODE_CASES[code][0]()


@pytest.mark.parametrize("code", sorted(DISPATCH_CODE_CASES))
def test_dispatch_code_silent_on_clean_source(code):
    assert code not in DISPATCH_CODE_CASES[code][1]()


def test_dispatch_waived_site_reported_but_not_failing():
    """A '# graphlint: waive GL703 -- reason' comment keeps the finding in
    the site table (waived=True, severity info, '[waived]' marker) instead
    of failing the run."""
    findings = dispatch_lint.lint_dispatch_source("<case>",
                                                  text=_GL703_WAIVED)
    f = next(f for f in findings if f.code == "GL703")
    assert f.waived
    d = f.to_diagnostic()
    assert d.severity == "info"
    assert d.message.endswith("[waived]")
    # the same site without the waiver is a warning
    g = next(f for f in dispatch_lint.lint_dispatch_source(
        "<case>", text=_GL703_BROKEN) if f.code == "GL703")
    assert not g.waived
    assert g.to_diagnostic().severity == "warning"


def test_dispatch_family_waiver_covers_every_gl7xx_code():
    src = _GL701_BROKEN.replace(
        "tok = logits.asnumpy()",
        "tok = logits.asnumpy()  # graphlint: waive GL7xx -- family waiver")
    findings = dispatch_lint.lint_dispatch_source("<case>", text=src)
    waived_lines = {f.line for f in findings if f.waived}
    assert waived_lines, [f.to_dict() for f in findings]


def test_gl705_needs_two_intervals():
    rows = _gl705_rows(8.0)
    rows[0]["intervals"] = 1
    assert not dispatch_lint.lint_dispatch_gaps(rows, pct=0.25)


def test_repo_dispatch_scan_flags_kv_decode_host_sync_sites():
    """Acceptance: the default-surface scan flags the known kv_decode
    host-sync sites (GL701 in the decoder's greedy loop) with file:line
    provenance."""
    report, sites = dispatch_lint.lint_dispatch_paths()
    kv = [s for s in sites if s["file"].endswith("serving/kv_decode.py")
          and s["code"] == "GL701"]
    assert len(kv) >= 1, sites
    assert {s["function"] for s in kv} >= {"PagedKVDecoder.greedy"}
    assert all(s["line"] > 0 and s["provenance"] for s in kv)


def test_graph_gl703_fires_on_tokenless_decode_symbol_only():
    """Graph-side GL703: the decode-signature symbol WITHOUT the on-device
    greedy head triggers; token_out=True (the default) is clean."""
    from mxnet_tpu.models import transformer as tf

    cfg = dict(vocab_size=64, num_layers=2, num_heads=2, model_dim=32,
               ffn_dim=64)
    B, S, H, dh = 2, 8, 2, 16
    sh = {"data": (B, 1), "pos_idx": (B, 1), "write_slot": (B, 1),
          "page_table": (B, 1)}
    for i in range(cfg["num_layers"]):
        sh["kv_k_%d" % i] = (H, S, dh)
        sh["kv_v_%d" % i] = (H, S, dh)
    bare = tf.get_decode_symbol(max_len=S, pos_len=S, token_out=False, **cfg)
    assert "GL703" in _codes(bare, shapes=sh)
    headed = tf.get_decode_symbol(max_len=S, pos_len=S, **cfg)
    assert "GL703" not in _codes(headed, shapes=sh)


# --------------------------------------------------------------------------
# concurrency codes (GL8xx): source snippets through the AST lint
# (GL801-GL804), witness dumps through the measured lint (GL805)
# --------------------------------------------------------------------------
from mxnet_tpu.analysis import concurrency_lint  # noqa: E402

_GL801_BROKEN = """
import jax

def step(kv):
    if jax.process_index() == 0:
        kv.allreduce([1])
"""

# guarding on world SIZE is rank-uniform — the correct idiom, not divergence
_GL801_CLEAN = """
import jax

def step(kv):
    if jax.process_count() > 1:
        kv.allreduce([1])
"""

_GL802_BROKEN = """
import threading

class Srv:
    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0
        self._t = threading.Thread(target=self._loop)

    def _loop(self):
        self.count += 1

    def bump(self):
        with self._lock:
            self.count += 1
"""

_GL802_CLEAN = """
import threading

class Srv:
    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0
        self._t = threading.Thread(target=self._loop)

    def _loop(self):
        with self._lock:
            self.count += 1

    def bump(self):
        with self._lock:
            self.count += 1
"""

_GL803_BROKEN = """
import threading

class Srv:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def one(self):
        with self._a:
            with self._b:
                pass

    def two(self):
        with self._b:
            with self._a:
                pass
"""

_GL803_CLEAN = """
import threading

class Srv:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def one(self):
        with self._a:
            with self._b:
                pass

    def two(self):
        with self._a:
            with self._b:
                pass
"""

_GL804_BROKEN = """
import threading

class Srv:
    def __init__(self):
        self._lock = threading.Lock()
        self._q = None

    def drain(self):
        with self._lock:
            return self._q.get()
"""

# cond.wait() on a condition backed by the held lock RELEASES it — exempt
_GL804_CLEAN = """
import threading

class Srv:
    def __init__(self):
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._q = None

    def drain(self):
        with self._lock:
            self._cv.wait()
        return self._q.get()
"""

_GL804_WAIVED = """
import threading

class Srv:
    def __init__(self):
        self._lock = threading.Lock()
        self._q = None

    def drain(self):
        with self._lock:
            return self._q.get()  # graphlint: waive GL804 -- bounded producer
"""


def _cl_codes(src):
    return {f.code for f in
            concurrency_lint.lint_concurrency_source("<case>", text=src)}


def _gl805_witness(seam):
    return {"enabled": True, "threshold_ms": 50.0,
            "events": [{"kind": "long_hold", "lock": "serving.engine",
                        "hold_ms": 80.0, "threshold_ms": 50.0,
                        "thread": "T", "dispatch_seam": seam}]}


def _gl805_codes(seam):
    return {d.code for d in concurrency_lint.lint_lock_witness(
        _gl805_witness(seam))}


CONCURRENCY_CODE_CASES = {
    "GL801": (lambda: _cl_codes(_GL801_BROKEN),
              lambda: _cl_codes(_GL801_CLEAN)),
    "GL802": (lambda: _cl_codes(_GL802_BROKEN),
              lambda: _cl_codes(_GL802_CLEAN)),
    "GL803": (lambda: _cl_codes(_GL803_BROKEN),
              lambda: _cl_codes(_GL803_CLEAN)),
    "GL804": (lambda: _cl_codes(_GL804_BROKEN),
              lambda: _cl_codes(_GL804_CLEAN)),
    # measured: a >threshold hold ACROSS a dispatch seam fires; the same
    # hold with no seam stays in the contention table only
    "GL805": (lambda: _gl805_codes(True), lambda: _gl805_codes(False)),
}


@pytest.mark.parametrize("code", sorted(CONCURRENCY_CODE_CASES))
def test_concurrency_code_triggers_on_broken_source(code):
    assert code in CONCURRENCY_CODE_CASES[code][0]()


@pytest.mark.parametrize("code", sorted(CONCURRENCY_CODE_CASES))
def test_concurrency_code_silent_on_clean_source(code):
    assert code not in CONCURRENCY_CODE_CASES[code][1]()


def test_concurrency_waived_site_reported_but_not_failing():
    findings = concurrency_lint.lint_concurrency_source(
        "<case>", text=_GL804_WAIVED)
    f = next(f for f in findings if f.code == "GL804")
    assert f.waived
    d = f.to_diagnostic()
    assert d.severity == "info"
    assert d.message.endswith("[waived]")
    g = next(f for f in concurrency_lint.lint_concurrency_source(
        "<case>", text=_GL804_BROKEN) if f.code == "GL804")
    assert not g.waived
    assert g.to_diagnostic().severity == "warning"


def test_concurrency_family_waiver_covers_every_gl8xx_code():
    src = _GL801_BROKEN.replace(
        "kv.allreduce([1])",
        "kv.allreduce([1])  # graphlint: waive GL8xx -- family waiver")
    findings = concurrency_lint.lint_concurrency_source("<case>", text=src)
    waived_lines = {f.line for f in findings if f.waived}
    assert waived_lines, [f.to_dict() for f in findings]


def test_gl801_except_handler_is_rank_varying():
    """A collective inside a caught-exception branch diverges: which rank
    raises (and what) is runtime-local."""
    src = """
def step(kv):
    try:
        risky()
    except Exception:
        kv._barrier()
"""
    assert "GL801" in _cl_codes(src)


def test_gl801_provenance_names_the_divergent_read():
    findings = concurrency_lint.lint_concurrency_source(
        "<case>", text=_GL801_BROKEN)
    f = next(f for f in findings if f.code == "GL801")
    assert any("process_index" in p for p in f.provenance), f.provenance


def test_repo_concurrency_scan_is_clean_or_waived():
    """Acceptance: the default-surface scan exits clean — every finding on
    the real tree fixed or carrying a waive reason (the CI repo gate)."""
    report, sites = concurrency_lint.lint_concurrency_paths()
    unwaived = [s for s in sites if not s["waived"]]
    assert not unwaived, unwaived
    # the known protocol-level GL801 in the elastic pause path stays
    # visible as a waived site (the docs worked example)
    assert any(s["code"] == "GL801"
               and s["file"].endswith("module/elastic.py")
               for s in sites), sites


def test_every_diagnostic_code_is_tested():
    covered = (set(GRAPH_CODE_CASES) | set(ENGINE_CODE_CASES) | {"GL105"}
               | set(REWRITE_CODE_CASES) | set(DISPATCH_CODE_CASES)
               | set(CONCURRENCY_CODE_CASES))
    assert covered == set(CODES), (
        "codes missing a trigger/clean test pair: %s; stale test entries: %s"
        % (sorted(set(CODES) - covered), sorted(covered - set(CODES))))


# --------------------------------------------------------------------------
# sharding-plan lint + memory planner (GL4xx/GL5xx) acceptance
# --------------------------------------------------------------------------
def test_missharded_symbol_fires_three_distinct_gl4xx_codes():
    """Acceptance: a deliberately mis-sharded symbol triggers >= 3 distinct
    GL4xx codes — uneven batch (GL404), indivisible weight (GL401), and a
    sharded-contraction all-gather (GL402)."""
    d = mx.sym.Variable("data")        # batch 3 over dp=2 -> GL404
    h = mx.sym.FullyConnected(data=d, num_hidden=256, name="fc1")
    h = mx.sym.Activation(data=h, act_type="relu", name="relu1")
    h = mx.sym.FullyConnected(data=h, num_hidden=8, name="fc2")  # GL402
    d2 = mx.sym.Variable("aux_data")
    odd = mx.sym.FullyConnected(data=d2, num_hidden=999, name="oddfc")
    sym = mx.sym.Group([h, odd])       # oddfc weight (999, 783) -> GL401
    report = analysis.lint(
        sym, shapes={"data": (3, 512), "aux_data": (4, 783)},
        mesh="dp=2,model=2", target="missharded")
    fired = {c for c in report.codes() if c.startswith("GL4")}
    assert len(fired) >= 3, report.format()
    assert {"GL401", "GL402", "GL404"} <= fired, report.format()


def test_clean_model_lints_clean_under_mesh_and_budget():
    """Acceptance: an under-budget, well-sharded model has zero findings."""
    net = mx.models.get_symbol("mlp", num_classes=10)
    report = analysis.lint(net, shapes={"data": (8, 784)},
                           mesh="dp=8", budget_gb=16.0, target="mlp")
    assert report.codes() == [], report.format()
    assert report.memory_plan is not None
    assert report.memory_plan["per_device"]["peak"] > 0


def test_memory_plan_structure_and_policies():
    """The plan's accounting identities: peak = params+grads+opt+inputs+act;
    recompute never stashes more than stash; inference drops grads/opt."""
    net = mx.models.get_symbol("mlp", num_classes=10)
    sh = {"data": (32, 784)}
    stash = analysis.lint(net, shapes=sh).memory_plan
    rec = analysis.lint(net, shapes=sh, bwd="recompute").memory_plan
    inf = analysis.lint(net, shapes=sh, train=False).memory_plan
    pd = stash["per_device"]
    assert pd["peak"] == (pd["params"] + pd["grads"] + pd["opt_state"]
                          + pd["inputs"] + pd["act_peak"])
    assert pd["grads"] == pd["opt_state"] > 0
    assert rec["per_device"]["act_peak"] <= pd["act_peak"]
    assert inf["per_device"]["grads"] == inf["per_device"]["opt_state"] == 0
    assert inf["per_device"]["peak"] < pd["peak"]
    assert stash["peak_node"] and stash["peak_live"]
    # sharding divides per-device bytes: dp=8 cuts the batch-sharded
    # activation watermark vs the single-device plan
    dp = analysis.lint(net, shapes=sh, mesh="dp=8").memory_plan
    assert dp["per_device"]["act_peak"] < pd["act_peak"]
    assert dp["per_device"]["params"] == pd["params"]  # replicated


@pytest.mark.parametrize("backend,attrs,mesh,form,charged", [
    ("cpu", {}, None, "dense", 1.0),
    ("tpu", {}, None, "kernel", 0.0),
    ("tpu", {"window": 256}, None, "band", 1.0),
    ("tpu", {}, "dp=2", "dense", 0.5),
], ids=["dense", "kernel", "band", "several_devices"])
def test_attention_score_stash_follows_the_operators_rule(
        monkeypatch, backend, attrs, mesh, form, charged):
    """An attention site's scores are charged from its forward to its
    backward unless ``ops.attention.attention_form``, asked as the operator
    asks it, names a form whose backward recomputes them: the kernel on one
    chip is elided; the dense path, a band (the same dense bound) and a step
    over several devices (the rule keeps it dense; the scores are a device's
    share) are charged B x H x T x S x 4 bytes."""
    from mxnet_tpu.ops import attention as attn_op

    B, H, T, D = 2, 16, 1024, 64
    q, k, v = (mx.sym.Variable(n) for n in "qkv")
    net = mx.sym.MakeLoss(mx.sym.sum(mx.sym.MultiHeadAttention(
        q, k, v, causal=True, name="attn", **attrs)), name="loss")
    shapes = {n: (B, H, T, D) for n in "qkv"}
    named, rule = [], attn_op.attention_form

    def spy(*operands_and_attrs):
        named.append(rule(*operands_and_attrs))
        return named[-1]

    monkeypatch.setattr(attn_op, "attention_form", spy)
    dense = analysis.lint(net, shapes=shapes, mesh=mesh).memory_plan
    assert named == ["band" if "window" in attrs else "dense"]  # the CPU's
    monkeypatch.setattr(attn_op, "_backend", lambda: backend)
    plan = analysis.lint(net, shapes=shapes, mesh=mesh).memory_plan
    assert named[1:] == [form]
    scores = B * H * T * T * 4
    assert plan["attention"] == {
        "sites": 1, "score_bytes": int(scores * charged),
        "flash_elided_sites": 0 if charged else 1}
    # what the CPU says of the same graph is the dense bound, and the
    # elided site's peak is that much lower
    assert dense["per_device"]["peak"] - plan["per_device"]["peak"] == (
        0 if charged else scores)


def test_predicted_peak_within_2x_of_measured_live_buffers():
    """Acceptance: the GL5xx prediction for a zoo model is within 2x of the
    bytes actually held live by a bound executor on the CPU backend (args +
    grads + aux + outputs — the buffers that survive a fwd/bwd step)."""
    net = mx.models.get_symbol("mlp", num_classes=10)
    shapes = {"data": (32, 784), "softmax_label": (32,)}
    report = analysis.lint(net, shapes=shapes, target="mlp")
    pred = report.memory_plan["per_device"]["peak"]
    exe = net.simple_bind(ctx=mx.cpu(), **shapes)
    exe.forward(is_train=True)
    exe.backward()

    def nbytes(a):
        return int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize

    measured = sum(nbytes(a) for a in exe.arg_arrays)
    measured += sum(nbytes(g) for g in exe.grad_arrays if g is not None)
    measured += sum(nbytes(a) for a in exe.aux_arrays)
    measured += sum(nbytes(o) for o in exe.outputs)
    assert measured / 2 <= pred <= measured * 2, (pred, measured)


def test_batch_one_keeps_batch_sharding_no_false_gl403():
    """Regression: an extent-1 batch dim that STAYS extent 1 through an
    elementwise op must keep its data-axis sharding — batch=1 shapes (the
    CLI's zoo defaults) used to lose the axis at the first Activation and
    emit a false GL403 'collapses the batch dim'."""
    net = mx.models.get_symbol("mlp", num_classes=10)
    report = analysis.lint(net, shapes={"data": (1, 784)},
                           mesh="dp=8,model=2", target="mlp-b1")
    assert "GL403" not in report.codes(), report.format()


def test_null_grad_req_bind_plans_inference(monkeypatch):
    """Regression: bind with grad arrays but grad_req='null' never runs a
    backward — the GL5xx planner must account it as inference (no grads,
    no optimizer state), not as a training bind."""
    from mxnet_tpu import telemetry

    monkeypatch.setenv("MXNET_GRAPHLINT", "warn")
    monkeypatch.setenv("MXNET_TELEMETRY", "counters")
    net = mx.models.get_symbol("mlp", num_classes=10)
    arg_shapes, _, _ = net.infer_shape(data=(8, 784))
    args = {n: mx.nd.zeros(s) for n, s in zip(net.list_arguments(),
                                              arg_shapes)}
    grads = {n: mx.nd.zeros(s) for n, s in zip(net.list_arguments(),
                                               arg_shapes)}
    telemetry.reset()
    net.bind(ctx=mx.cpu(), args=args, args_grad=grads, grad_req="write")
    train_peak = telemetry.gauge("memlint.predicted_peak_bytes").value
    telemetry.reset()
    net.bind(ctx=mx.cpu(), args=args, args_grad=grads, grad_req="null")
    inf_peak = telemetry.gauge("memlint.predicted_peak_bytes").value
    assert inf_peak < train_peak, (inf_peak, train_peak)


def test_memory_plan_exports_telemetry_gauge(monkeypatch):
    from mxnet_tpu import telemetry

    monkeypatch.setenv("MXNET_TELEMETRY", "counters")
    telemetry.reset()
    net = mx.models.get_symbol("mlp", num_classes=10)
    report = analysis.lint(net, shapes={"data": (8, 784)})
    g = telemetry.gauge("memlint.predicted_peak_bytes")
    assert g.value == report.memory_plan["per_device"]["peak"]


def test_memlint_budget_env_var(monkeypatch):
    """MXNET_MEMLINT_BUDGET_GB arms GL501 without any caller kwarg."""
    monkeypatch.setenv("MXNET_MEMLINT_BUDGET_GB", "0.000001")
    sym, kw = _gl501_clean()  # generous-kwarg variant; env drives it now
    report = analysis.lint(sym, shapes=kw["shapes"])
    assert "GL501" in report.codes()
    monkeypatch.setenv("MXNET_MEMLINT_BUDGET_GB", "1000")
    assert "GL501" not in _codes(sym, shapes=kw["shapes"])


def test_cli_mesh_resnet50_reshard_and_peak_table(capsys):
    """Acceptance: graphlint resnet-50 --mesh dp=8,model=2 prints per-edge
    reshard-bytes diagnostics and the per-device peak-HBM table."""
    from mxnet_tpu.analysis.cli import main

    rc = main(["resnet-50", "--shape", "data=32,3,224,224",
               "--mesh", "dp=8,model=2"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "implicit reshard" in out and "moved per device" in out
    assert "predicted peak HBM per device" in out
    assert "params" in out and "activations" in out


def test_cli_mesh_summary_table_and_json_plan(tmp_path, capsys):
    from mxnet_tpu.analysis.cli import main

    rc = main(["mlp", "lenet", "--mesh", "dp=2"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "peak-HBM summary" in out  # multi-target text mode summarizes
    rc = main(["mlp", "--mesh", "dp=2", "--format", "json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 0
    plan = payload[0]["memory_plan"]
    assert plan["mesh"] == {"dp": 2}
    assert plan["per_device"]["peak"] > 0


def test_cli_bad_mesh_is_usage_error(capsys):
    from mxnet_tpu.analysis.cli import main

    assert main(["mlp", "--mesh", "dp8"]) == 2


def test_spmd_adapter_feeds_mesh_to_lint(monkeypatch):
    """SPMDStepAdapter's bind path lints with the REAL mesh + rules: the
    predicted peak lands on the telemetry gauge and reflects dp sharding."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    from mxnet_tpu import telemetry

    monkeypatch.setenv("MXNET_GRAPHLINT", "warn")
    monkeypatch.setenv("MXNET_TELEMETRY", "counters")
    telemetry.reset()
    net = mx.models.get_symbol("mlp", num_classes=10)
    it = mx.io.NDArrayIter(np.zeros((16, 784), "float32"),
                           np.zeros((16,), "float32"), batch_size=16)
    mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(8)])
    mod.fit(it, num_epoch=1)
    assert mod._spmd is not None, "fused SPMD step did not engage"
    spmd_peak = telemetry.gauge("memlint.predicted_peak_bytes").value
    assert spmd_peak and spmd_peak > 0
    # the same symbol planned single-device predicts MORE per device than
    # the dp=8 plan (batch-sharded activations divide by 8)
    single = analysis.lint(net, shapes={"data": (16, 784),
                                        "softmax_label": (16,)}).memory_plan
    assert single["per_device"]["act_peak"] > 0
    assert spmd_peak < single["per_device"]["peak"]


# --------------------------------------------------------------------------
# satellite: engine wait_for_var on an unknown var raises (all engine types)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("maker", [
    eng.NaiveEngine,
    lambda: eng.ThreadedEngine(num_workers=2),
    lambda: eng._PythonThreadedEngine(2),
], ids=["naive", "threaded", "python"])
def test_wait_for_unknown_var_raises(maker):
    e = maker()
    with pytest.raises(mx.MXNetError, match="unknown engine variable"):
        e.wait_for_var(987654321)
    # known vars still work
    v = e.new_variable()
    done = []
    e.push(lambda: done.append(1), mutable_vars=[v])
    e.wait_for_var(v)
    assert done == [1]


# --------------------------------------------------------------------------
# satellite: infer_meta registry is the shared source of truth
# --------------------------------------------------------------------------
def test_infer_meta_registry():
    from mxnet_tpu.ops import infer_meta, shape_rules

    conv = infer_meta.get_meta("Convolution")
    assert conv.input_ranks["data"] == (4, 4)
    assert "weight" in conv.param_slots
    # backward rules are re-exported, not duplicated
    assert infer_meta.backward_shape_rule("FullyConnected") \
        is shape_rules.RULES["FullyConnected"]
    # unregistered ops get the permissive default
    default = infer_meta.get_meta("no_such_op")
    assert default.input_ranks == {} and default.param_slots == ()


# --------------------------------------------------------------------------
# bind integration: MXNET_GRAPHLINT=0|warn|error
# --------------------------------------------------------------------------
def test_bind_lint_error_mode_raises(monkeypatch):
    monkeypatch.setenv("MXNET_GRAPHLINT", "error")
    sym, kw = _gl006_broken()
    with pytest.raises(mx.MXNetError, match="GL006"):
        sym.simple_bind(ctx=mx.cpu(), **{k: v for k, v in kw["shapes"].items()})


def test_bind_lint_error_mode_passes_clean_graph(monkeypatch):
    monkeypatch.setenv("MXNET_GRAPHLINT", "error")
    net = mx.models.get_symbol("mlp", num_classes=10)
    exe = net.simple_bind(ctx=mx.cpu(), data=(4, 784), softmax_label=(4,))
    assert exe.forward(is_train=False)[0].shape == (4, 10)


def test_bind_lint_warn_mode_logs_but_binds(monkeypatch, caplog):
    monkeypatch.setenv("MXNET_GRAPHLINT", "warn")
    x = mx.sym.Variable("x", dtype="float16")
    y = mx.sym.Variable("y", dtype="float32")
    s = x + y
    with caplog.at_level("WARNING", logger="mxnet_tpu.graphlint"):
        exe = s.simple_bind(ctx=mx.cpu(), x=(2,), y=(2,),
                            type_dict={"x": "float16", "y": "float32"})
    assert exe is not None
    assert any("GL004" in r.message for r in caplog.records)


def test_bind_lint_off_by_default(monkeypatch):
    monkeypatch.delenv("MXNET_GRAPHLINT", raising=False)
    assert analysis.graphlint_mode() is None


def test_graphlint_mode_aliases_and_unknown(monkeypatch, caplog):
    monkeypatch.setenv("MXNET_GRAPHLINT", "1")
    assert analysis.graphlint_mode() == "warn"  # boolean idiom honored
    monkeypatch.setenv("MXNET_GRAPHLINT", "bogus")
    with caplog.at_level("WARNING", logger="mxnet_tpu.graphlint"):
        assert analysis.graphlint_mode() is None
    assert any("not a recognized mode" in r.message for r in caplog.records)


# --------------------------------------------------------------------------
# regression: models/resnet.py lints clean under MXNET_GRAPHLINT=error
# --------------------------------------------------------------------------
def test_resnet_lints_clean_under_error_mode(monkeypatch):
    monkeypatch.setenv("MXNET_GRAPHLINT", "error")
    net = mx.models.get_symbol("resnet-18", num_classes=10,
                               image_shape="3,32,32")
    exe = net.simple_bind(ctx=mx.cpu(), data=(2, 3, 32, 32),
                          softmax_label=(2,))
    assert exe is not None
    report = analysis.lint(net, shapes={"data": (2, 3, 32, 32)},
                           target="resnet-18")
    assert report.errors == [] and report.warnings == []


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------
def test_cli_single_model_clean():
    from mxnet_tpu.analysis.cli import main

    assert main(["mlp"]) == 0


def test_cli_list_codes(capsys):
    from mxnet_tpu.analysis.cli import main

    assert main(["--list-codes"]) == 0
    out = capsys.readouterr().out
    for code in CODES:
        assert code in out


def test_cli_json_format_and_broken_symbol_file(tmp_path, capsys):
    from mxnet_tpu.analysis.cli import main

    sym, kw = _gl006_broken()
    path = str(tmp_path / "broken-symbol.json")
    sym.save(path)
    rc = main([path, "--shape", "data=2,3,8,8", "--format", "json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert any(d["code"] == "GL006"
               for entry in payload for d in entry["diagnostics"])


def test_cli_unknown_target_is_usage_error(capsys):
    from mxnet_tpu.analysis.cli import main

    assert main(["no-such-model"]) == 2


def test_cli_default_shapes_are_case_insensitive(capsys):
    """'MLP' must get the same default shape hints as 'mlp' (get_symbol
    lowercases the zoo key, so the shape table must too)."""
    from mxnet_tpu.analysis.cli import main

    assert main(["MLP"]) == 0
    out = capsys.readouterr().out
    # with default shapes applied the graph is fully determined: no GL203,
    # zero findings — a structural-only lint would report 1 finding
    assert "0 total finding(s)" in out


def test_unknown_pass_subset_raises():
    """A typo'd --passes selection must not lint nothing and exit 'clean'."""
    sym, _ = _gl001_clean()
    with pytest.raises(ValueError, match="unknown analysis pass"):
        analysis.lint(sym, passes=["shapelint"])  # typo of shape_lint


def test_cli_strict_fails_on_warnings():
    from mxnet_tpu.analysis.cli import main

    sym, _ = _gl202_broken()
    import tempfile, os as _os

    with tempfile.TemporaryDirectory() as td:
        path = _os.path.join(td, "warn-symbol.json")
        sym.save(path)
        assert main([path]) == 0            # warnings alone pass
        assert main([path, "--strict"]) == 1  # ... unless strict


@pytest.mark.slow
def test_cli_all_models_sweep_exits_zero():
    """Acceptance: tools/graphlint runs on every bundled model and exits 0."""
    from mxnet_tpu.analysis.cli import main

    assert main(["--all-models"]) == 0


# --------------------------------------------------------------------------
# CI dogfood: the subsystem lints itself on every PR (tools/ci_check.sh runs
# the same steps standalone)
# --------------------------------------------------------------------------
def test_package_sources_compile():
    """Every mxnet_tpu source parses/compiles — the dependency-free floor of
    the ruff/pyflakes step (those run in ci_check.sh when installed)."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(mx.__file__)))
    pkg = os.path.join(root, "mxnet_tpu")
    bad = []
    for dirpath, _, files in os.walk(pkg):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(dirpath, f)
            with open(path) as fh:
                try:
                    compile(fh.read(), path, "exec")
                except SyntaxError as exc:
                    bad.append("%s: %s" % (path, exc))
    assert not bad, "\n".join(bad)


def test_pyflakes_clean_when_available():
    pytest.importorskip("pyflakes")
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(mx.__file__)))
    proc = subprocess.run(
        [sys.executable, "-m", "pyflakes", os.path.join(root, "mxnet_tpu")],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
