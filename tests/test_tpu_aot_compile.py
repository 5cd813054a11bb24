"""Every kept Pallas kernel must pass Mosaic for the chip, checked here
without one.

The CPU tests run the kernels in interpret mode, which accepts block shapes
and vector ops the TPU compiler refuses. libtpu can compile for a "TPU v5
lite" from this sandbox through the topology API, so each kernel is lowered
with ``interpret=False`` and compiled, forward and backward, at one
chip_smoke.py shape. This is the guard that a kernel edited on the CPU still
compiles on the chip (the first on-chip run found two that did not).
"""
import math
import re

import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops import pallas_conv_bn as pc
from mxnet_tpu.ops import pallas_matmul_bias_act as pm
from mxnet_tpu.ops import pallas_norm_residual as pn


@pytest.fixture(scope="module")
def v5e():
    """A sharding on one device of an abstract v5e 2x2 host."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no libtpu, or one that cannot
        pytest.skip("libtpu cannot build the v5e topology: %s" % exc)
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def mosaic_not_interpret(monkeypatch):
    # the kernels pick interpret mode from the default backend (CPU here)
    for mod in (pc, pm, pn):
        monkeypatch.setattr(mod, "_interpret_mode", lambda: False)


def _compile(sharding, fn, *shapes_dtypes):
    specs = [None if sd is None else
             jax.ShapeDtypeStruct(sd[0], jnp.dtype(sd[1]), sharding=sharding)
             for sd in shapes_dtypes]
    lowered = jax.jit(fn).lower(*specs)
    assert "tpu_custom_call" in lowered.as_text(), "kernel was interpreted"
    lowered.compile()  # raises with Mosaic's message on a refusal


def _with_grads(fn, n_args):
    def fwd_bwd(*args):
        return jax.grad(lambda *a: sum(
            jnp.sum(o.astype(jnp.float32))
            for o in jax.tree_util.tree_leaves(fn(*a))),
            argnums=tuple(range(n_args)))(*args)
    return fwd_bwd


def test_flash_attention_fwd_bwd(v5e):
    qkv = ((8, 8, 512, 64), "bfloat16")
    fn = lambda q, k, v: pa.flash_attention(q, k, v, causal=True,
                                            interpret=False)
    _compile(v5e, _with_grads(fn, 3), qkv, qkv, qkv)


def test_matmul_bias_act_fwd_bwd(v5e):
    fn = lambda a, w, b: pm.matmul_bias_act(a, w, b, "relu")
    _compile(v5e, _with_grads(fn, 3), ((4096, 512), "bfloat16"),
             ((2048, 512), "bfloat16"), ((2048,), "bfloat16"))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_layer_norm_affine_fwd_bwd(v5e, dtype):
    fn = lambda x, g, b: pn.layer_norm_affine(x, g, b, interpret=False)
    _compile(v5e, _with_grads(fn, 3), ((4096, 512), dtype), ((512,), dtype),
             ((512,), dtype))


# ResNet-50 sites at the bench batch: a 1x1 with the skip add, the 3x3, and
# the 7x7-spatial tail whose 49-wide rows pad to 128 lanes (the VMEM case)
_CONV_SITES = [
    ((1, 1), (1, 1), 128, 512, 28, True),
    ((3, 3), (1, 1), 128, 128, 28, False),
    ((1, 1), (2, 2), 1024, 2048, 14, False),
]


@pytest.mark.parametrize("bwd", ["xla", "recompute", "stash"])
@pytest.mark.parametrize("site", _CONV_SITES,
                         ids=lambda s: "k%ds%d_K%d_N%d_H%d%s" % (
                             s[0][0], s[1][0], s[2], s[3], s[4],
                             "_res" if s[5] else ""))
def test_conv_bn_fwd_bwd(v5e, site, bwd):
    kernel, stride, K, N, H, res = site
    B, dt = 256, "bfloat16"
    x, w = (B, K, H, H), (N, K) + kernel
    assert pc.supported(x, w, stride, 2, True, res)
    if bwd != "xla":
        # what the planner passes, the compiler must accept
        assert pc.plan_bwd_blocks(x, w, stride, 2, True, res,
                                  stash=(bwd == "stash")) is not None
    Ho, Wo = pc.strided_dims(H, H, stride)
    r = ((B, N, Ho, Wo), dt) if res else None

    def fn(x, w, scale, shift, r=None):
        return pc.conv_block(x, w, scale, shift, r, kernel, stride, True,
                             True, bwd, None)

    _compile(v5e, _with_grads(fn, 5 if res else 4), (x, dt), (w, dt),
             ((K,), "float32"), ((K,), "float32"), r)


def test_conv_bn_infer(v5e):
    fn = lambda x, w, scale, shift: pc.conv_block_infer(
        x, w, scale, shift, (3, 3), (1, 1), True)
    _compile(v5e, fn, ((256, 128, 28, 28), "bfloat16"),
             ((128, 128, 3, 3), "bfloat16"), ((128,), "float32"),
             ((128,), "float32"))


def test_backward_planner_declines_what_the_compiler_refuses():
    """k1 K64→N256 at 56² with the skip add: no lane-aligned K stripe fits
    the scoped VMEM limit, so the planner — not a caught compile error —
    sends the backward to XLA."""
    x, w = (256, 64, 56, 56), (256, 64, 1, 1)
    assert pc.supported(x, w, (1, 1), 2, True, True)
    assert pc.plan_bwd_blocks(x, w, (1, 1), 2, True, True) is None
    # a K stripe is the lane dim of the weight block: whole K or 128-multiples
    assert pc.choose_bwd_blocks(256, 256, 64, 3136, 2, prologue=True) in (
        128, 256)


def test_admit_pool_update_stays_in_place_on_the_chip(v5e):
    """The pool update of an admission (serving/kv_decode.py
    ``_AdmitScatter``) at the benchmark's sizes: all 12 pool buffers are
    aliased to their outputs and the program holds no whole-buffer
    temporary. The chip keeps the pool with slots minor-most, so a scatter
    over the slot axis — the form this program replaced — re-lays every
    134 MB buffer out before and after (269 MB of temporaries, aliased or
    not); the page walk must not."""
    from types import SimpleNamespace

    from mxnet_tpu.serving.kv_decode import _AdmitScatter

    layers, heads, dh, slots, prefill, page = 6, 8, 64, 64 * 1024, 1024, 16
    prog = _AdmitScatter(SimpleNamespace(
        _cache=[("kv_%s_%d" % (t, i), "pool", (heads, dh))
                for i in range(layers) for t in "kv"],
        page_size=page, prefill_len=prefill))

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=v5e)

    mem = prog._fn.lower(
        tuple(spec((heads, slots, dh), "float32") for _ in range(2 * layers)),
        tuple(spec((1, heads, prefill, dh), "float32")
              for _ in range(2 * layers)),
        spec((prefill // page,), "int32"), spec((2,), "int32"),
    ).compile().memory_analysis()
    assert mem.alias_size_in_bytes == 2 * layers * heads * slots * dh * 4
    assert mem.temp_size_in_bytes < 1 << 20


def _compile_program(sharding, sym, args, donated=()):
    """``sym`` as the executor jits its forward, compiled for the chip from
    ``args``, {argument: (shape, dtype)}; ``donated``: the arguments the
    program takes donated, in its outputs' order (a decode step's cache, as
    ``PagedKVDecoder`` dispatches it)."""
    from mxnet_tpu.executor import _GraphProgram

    prog = _GraphProgram(sym)
    prog.donated = tuple(donated)
    specs = tuple(jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                       sharding=sharding)
                  for shape, dtype in (args[n] for n in prog.arg_names))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sharding)
    return prog._fwd(False).lower(specs, (), key).compile()


_INSTRUCTION = re.compile(
    r"%?([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(%?([\w.\-]+)")


def _assert_pool_step_contracts(compiled, layers, rows, heads, slots, dh,
                                temp_bytes, cache_bytes):
    """A shared-pool decode program as the chip runs it, its pools donated:
    each layer's two reads of the pool are ``convolution``s or ``dot``s (the
    matrix unit) and its write of both pools is none: ONE loop a layer that
    updates a run of slots a row in each, in place, so the program aliases
    ``cache_bytes``, all of its cache; nothing multiplies rows x heads x slots x dh elements
    out to ``reduce`` them (the vector unit: 2.1 ms a product at the
    benchmark's sizes), no buffer the size of a pool is copied or transposed
    (the chip keeps a dh = 64 pool slots-minor and a dh = 128 one dh-minor;
    a contraction spelled against that, or an update one slot wide, re-lays
    134 MB out, 8 ms a step), the temporaries stay under ``temp_bytes``, and
    no parameter of the program is rows x slots: the masks are made on the
    device and the write takes a slot index a row."""
    hlo = compiled.as_text()
    entry = hlo[hlo.index("\nENTRY "):]
    params = [tuple(int(d) for d in dims.split(",") if d) for dims in
              re.findall(r" = \w+\[([\d,]*)\]\S* parameter\(",
                         entry[:entry.index("\n}")])]
    assert (heads, slots, dh) in params and (rows, 1) in params
    assert (rows, slots) not in params
    assert not [p for p in params if len(p) == 2 and math.prod(p) >= slots
                and slots in p]
    size, found = {}, []
    for name, dims, op, arg in _INSTRUCTION.findall(hlo):
        size[name] = math.prod(int(d) for d in dims.split(",") if d)
        found.append((op, name, arg))
    contractions = [line for line in hlo.splitlines()
                    if re.search(r" (convolution|dot)\(", line)]
    updates = [line for line in hlo.splitlines()
               if " dynamic-update-slice(" in line]
    loops = [line for line in hlo.splitlines() if " while(" in line]
    for i in range(layers):
        for node, count in (("kvupd", 0), ("att", 2)):
            tag = "layer%d_%s/" % (i, node)
            assert sum(tag in line for line in contractions) == count, tag
        assert sum("layer%d_kvupd/" % i in line for line in updates) == 2
        assert sum("layer%d_kvupd/" % i in line for line in loops) == 1
    assert "slot_onehot" not in hlo
    pool, product = heads * slots * dh, rows * heads * slots * dh
    assert not [(op, name) for op, name, arg in found
                if op == "reduce" and size.get(arg, 0) >= product]
    assert max(size.values()) < product
    assert not [(op, name) for op, name, _ in found
                if op in ("copy", "transpose") and size[name] >= pool]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < temp_bytes
    assert mem.alias_size_in_bytes == cache_bytes


def test_transformer_base_decode_step_contracts_on_the_chip(v5e):
    """``transformer-base.generate``'s decode program (two of its six layers,
    64 lanes x 65,536 slots, float32) lowered for the v5e as it is
    dispatched: the four pools donated. The temporaries are one layer's
    float32 scores, 64 x 8 x 65,536 x 4 = 134 MB, and small change; all 512
    MB of pool are updated in place."""
    from mxnet_tpu.models import transformer as tf

    layers, lanes, slots, heads, dh, page = 2, 64, 64 * 1024, 8, 64, 16
    sym = tf.get_decode_symbol(
        vocab_size=32000, num_layers=layers, num_heads=heads, model_dim=512,
        ffn_dim=2048, max_len=slots, pos_len=1024, page_size=page)
    arg_shapes, _, _ = sym.infer_shape(
        data=(lanes, 1), pos_idx=(lanes, 1), write_slot=(lanes, 1),
        page_table=(lanes, slots // lanes // page),
        **{"kv_%s_%d" % (t, i): (heads, slots, dh)
           for t in "kv" for i in range(layers)})
    pools = ["kv_%s_%d" % (t, i) for i in range(layers) for t in "kv"]
    compiled = _compile_program(v5e, sym, {
        n: (shape, "float32")
        for n, shape in zip(sym.list_arguments(), arg_shapes)}, donated=pools)
    _assert_pool_step_contracts(
        compiled, layers, lanes, heads, slots, dh, temp_bytes=160 << 20,
        cache_bytes=2 * layers * heads * slots * dh * 4)
    # XLA's count: 0.84 GB a layer of pool read twice, scores and masks, and
    # the feed-forward and head; the one-hot blend of every pool read and
    # rewrote each whole, 1.38 GB a layer and 3.10 GB in all
    assert compiled.cost_analysis()["bytes accessed"] < 2.2e9


# OLMoE-1B-7B's published widths with one layer, granite-4.0-h-micro's with
# its first six (five Mamba-2, one attention)
_OLMOE = dict(arch="olmoe", vocab_size=50304, num_layers=1, num_heads=16,
              head_dim=128, model_dim=2048, ffn_dim=1024, num_experts=64,
              num_experts_per_tok=8, rope_theta=10000.0, rms_eps=1e-5,
              dtype="bfloat16")
_GRANITE = dict(arch="granite_hybrid", vocab_size=100352, num_layers=6,
                num_heads=32, num_kv_heads=8, head_dim=64, model_dim=2048,
                ffn_dim=8192, layer_types=["mamba"] * 5 + ["attention"],
                mamba_heads=64, mamba_head_dim=64, mamba_state=128,
                mamba_conv=4, mamba_chunk=256, embedding_multiplier=12.0,
                attention_multiplier=0.015625, residual_multiplier=0.22,
                logits_scaling=8.0, rms_eps=1e-5, dtype="bfloat16")


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_olmoe_serving_programs_compile_for_the_chip(v5e, program):
    """The two graphs ``PagedKVDecoder(arch="olmoe")`` runs, lowered for the
    v5e at OLMoE-1B-7B's published widths with one layer and the benchmark's
    serving sizes (8 lanes x 2,048 slots, bfloat16 weights and pool, float32
    ids, positions, write slots and page tables): a shape or layout XLA:TPU refuses
    is found here, without a chip. The experts stay XLA's grouped matmul
    (no per-expert dense expansion: the compiler's FLOP count is the sparse
    one), and the decode step hands the pool back in the type it came in."""
    from mxnet_tpu.models import transformer as tf

    lanes, max_len = 8, 2048
    slots = lanes * max_len
    cfg = _OLMOE
    weights = {n: (s, "bfloat16") for n, s in tf.param_shapes(**cfg).items()}
    if program == "prefill":
        sym = tf.get_prefill_symbol(prefill_len=max_len, **cfg)
        inputs = {"data": ((1, max_len), "float32")}
    else:
        sym = tf.get_decode_symbol(max_len=slots, page_size=16, **cfg)
        inputs = {"data": ((lanes, 1), "float32"),
                  "pos_idx": ((lanes, 1), "float32"),
                  "write_slot": ((lanes, 1), "float32"),
                  "page_table": ((lanes, max_len // 16), "float32"),
                  "kv_k_0": ((16, slots, 128), "bfloat16"),
                  "kv_v_0": ((16, slots, 128), "bfloat16")}
    compiled = _compile_program(
        v5e, sym, {**weights, **inputs},
        donated=[n for n in inputs if n.startswith("kv_")])
    assert "ragged" in compiled.as_text().lower()
    flops = compiled.cost_analysis()["flops"]
    if program == "prefill":
        # 2 x 2,048 tokens x (67.2 M projections, router and 8 experts +
        # 8.4 M dense attention + 103 M head) MACs; 64 dense experts a
        # token would be 2.4 TFLOP
        assert 0.70e12 < flops < 0.80e12
        assert [str(s.dtype) for s in compiled.out_info[0]] \
            == ["float32", "bfloat16", "bfloat16", "float32"]
    else:
        assert [str(s.dtype) for s in compiled.out_info[0]] \
            == ["float32", "bfloat16", "bfloat16", "float32"]
        assert compiled.out_info[0][1].shape == (16, slots, 128)
        # no temporary of a pool's size (16 x 16,384 x 128 bfloat16 = 67 MB)
        _assert_pool_step_contracts(compiled, 1, lanes, 16, slots, 128,
                                    temp_bytes=48 << 20,
                                    cache_bytes=2 * 16 * slots * 128 * 2)


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_granite_hybrid_serving_programs_compile_for_the_chip(v5e, program):
    """The two graphs ``PagedKVDecoder(arch="granite_hybrid")`` runs, lowered
    for the v5e at granite-4.0-h-micro's published widths with the first six
    layers (five Mamba-2, one attention) and the benchmark's serving sizes
    (32 lanes x 2,048 slots, a 512 bucket, bfloat16 weights and pool,
    float32 state). The chunked scan and the one-token update are plain
    ``jax.numpy``: what has to hold on the chip is that neither program
    copies or transposes a buffer the size of a lane's recurrent state
    (32 x 64 x 64 x 128 float32 = 67 MB a layer) or of a pool (8 x 65,536 x
    64 bfloat16 = 67 MB), that the state comes back float32 and the pool in
    the type it went in, and that a step's temporaries stay small beside the
    3 GB of cache it rewrites."""
    from mxnet_tpu.models import transformer as tf

    lanes, max_len, bucket, page, layers = 32, 2048, 512, 16, 6
    slots = lanes * max_len
    cfg = _GRANITE
    assert cfg["num_layers"] == layers
    weights = {n: (s, "bfloat16") for n, s in tf.param_shapes(**cfg).items()}
    cache = tf.decode_cache(**cfg)
    assert [kind for _, kind, _ in cache] == ["row"] * 10 + ["pool"] * 2
    state, pool = lanes * 64 * 64 * 128, 8 * slots * 64
    if program == "prefill":
        sym = tf.get_prefill_symbol(prefill_len=bucket, **cfg)
        inputs = {"data": ((1, bucket), "float32"),
                  "length": ((1, 1), "float32")}
        want_types = ["float32"] * 11 + ["bfloat16"] * 2
    else:
        sym = tf.get_decode_symbol(max_len=slots, page_size=page, **cfg)
        inputs = {"data": ((lanes, 1), "float32"),
                  "pos_idx": ((lanes, 1), "float32"),
                  "write_slot": ((lanes, 1), "float32"),
                  "page_table": ((lanes, max_len // page), "float32")}
        for name, kind, shape in cache:
            inputs[name] = ((shape[0], slots, shape[1]), "bfloat16") \
                if kind == "pool" else ((lanes,) + tuple(shape), "float32")
        want_types = ["float32"] * 11 + ["bfloat16"] * 2 + ["float32"]
    compiled = _compile_program(
        v5e, sym, {**weights, **inputs},
        donated=[name for name, _, _ in cache] if program == "decode" else ())
    assert [str(s.dtype) for s in compiled.out_info[0]] == want_types
    hlo = compiled.as_text()
    size, moved = {}, []
    for name, dims, op, _arg in _INSTRUCTION.findall(hlo):
        size[name] = math.prod(int(d) for d in dims.split(",") if d)
        if op in ("copy", "transpose"):
            moved.append(name)
    assert not [n for n in moved if size[n] >= min(state, pool)]
    mem = compiled.memory_analysis()
    if program == "decode":
        assert compiled.out_info[0][1].shape == (lanes, 64, 64, 128)
        assert compiled.out_info[0][11].shape == (8, slots, 64)
        # the scores of one attention layer, 32 x 32 x 65,536 float32 =
        # 268 MB, and small change; no second copy of any cache buffer:
        # the five layers' states and columns and the two pools are all
        # updated in place
        assert mem.temp_size_in_bytes < 400 << 20
        assert mem.alias_size_in_bytes == 2 * pool * 2 + sum(
            lanes * math.prod(shape) * 4
            for _, kind, shape in cache if kind == "row")
        assert "slot_onehot" not in hlo
    else:
        # 2 x 512 tokens x (5 x 76.2 M + 60.8 M + 205.5 M) MACs of matrices
        # and the head, and the chunked scan's products beside them
        assert 0.66e12 < compiled.cost_analysis()["flops"] < 0.80e12
        assert mem.temp_size_in_bytes < 400 << 20


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_latent_attention_serving_programs_compile_for_the_chip(v5e, program):
    """The two graphs ``PagedKVDecoder(arch="deepseek_v3")`` runs, lowered for
    the v5e at kanana-2-30b-a3b's published widths with the dense layer and
    the first two expert layers and the benchmark's serving sizes (32 lanes
    x 2,048 slots, a 1,024 bucket, bfloat16 weights and latent pool). What
    has to hold on the chip: the cache is ONE (1, 65,536, 576) pool a layer
    that comes back in the type it went in, the step makes no key or value
    of a head (32 heads x 65,536 slots x 128 would be 268 MB in bfloat16, a
    layer and kind) and scores no lane against the whole pool (32 x 32 x
    65,536 float32 would be 268 MB and 155 GFLOP a layer): it gathers each
    lane's 128 frames (an 84 MB copy, in bounds by promise, so no ``select``
    passes over it) and scores 2,048 slots a lane, and builds no (32,
    65,536) mask, which none of its reads looks at. Both programs keep the
    grouped matmul and report the experts' load last."""
    from mxnet_tpu.models import transformer as tf

    lanes, max_len, bucket, page, layers = 32, 2048, 1024, 16, 3
    slots = lanes * max_len
    cfg = dict(arch="deepseek_v3", vocab_size=128256, num_layers=layers,
               num_heads=32, model_dim=2048, ffn_dim=6144, moe_ffn_dim=768,
               num_experts=128, num_experts_per_tok=6, num_shared_experts=2,
               first_dense_layers=1, qk_nope_head_dim=128,
               qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
               rope_theta=1e6, rms_eps=1e-6, routed_scaling_factor=2.448,
               norm_topk_prob=True, dtype="bfloat16")
    weights = {n: (s, "bfloat16") for n, s in tf.param_shapes(**cfg).items()}
    cache = tf.decode_cache(**cfg)
    assert cache == [("kv_c_%d" % i, "pool", (1, 576)) for i in range(layers)]
    if program == "prefill":
        sym = tf.get_prefill_symbol(prefill_len=bucket, **cfg)
        inputs = {"data": ((1, bucket), "float32")}
        want = [((bucket, 128256), "float32")] \
            + [((1, 1, bucket, 576), "bfloat16")] * layers \
            + [((layers - 1, 128), "float32")]
    else:
        sym = tf.get_decode_symbol(max_len=slots, page_size=page, **cfg)
        inputs = {"data": ((lanes, 1), "float32"),
                  "pos_idx": ((lanes, 1), "float32"),
                  "write_slot": ((lanes, 1), "float32"),
                  "page_table": ((lanes, max_len // page), "float32")}
        inputs.update({name: ((1, slots, 576), "bfloat16")
                       for name, _, _ in cache})
        want = [((lanes, 128256), "float32")] \
            + [((1, slots, 576), "bfloat16")] * layers \
            + [((lanes,), "float32"), ((layers - 1, 128), "float32")]
    compiled = _compile_program(
        v5e, sym, {**weights, **inputs},
        donated=[name for name, _, _ in cache] if program == "decode" else ())
    assert [(s.shape, str(s.dtype)) for s in compiled.out_info[0]] == want
    hlo = compiled.as_text()
    assert "ragged" in hlo.lower()
    mem = compiled.memory_analysis()
    if program == "decode":
        # every latent pool is updated in place
        assert mem.alias_size_in_bytes == layers * slots * 576 * 2
        found = [(math.prod(int(d) for d in dims.split(",") if d), op)
                 for _n, dims, op, _a in _INSTRUCTION.findall(hlo)
                 if op != "parameter"]
        # nothing made is as large as the lanes' scores over the pool (67 M
        # elements), let alone the heads' keys (a bitcast of the head's
        # weights is), and no read's mask is built
        assert not [n for n, _ in found
                    if n >= lanes * 32 * slots and n != 128256 * 2048]
        assert "kv_mask" not in hlo and "slot_onehot" not in hlo
        assert not [n for n, op in found
                    if op == "select" and n >= lanes * max_len * 576]
        gathers = [line for line in hlo.splitlines() if " gather(" in line]
        for i in range(layers):     # ONE gather a layer: key and value
            assert sum("layer%d_att/" % i in g for g in gathers) == 1
        # the re-layout in front of it: the pool arrives slots-minor and the
        # gather wants rows (a pool whose row is a page would need none)
        assert sum(" copy(" in line and "[1,%d,576]" % slots in line
                   for line in hlo.splitlines()) == layers
        assert mem.temp_size_in_bytes < 200 << 20
        # 2 x (32 x 32 x 2,048 x (576 + 576)) the latent read, a layer, the
        # head 8.4 G, the matrices of 32 rows (the write is no matmul); the
        # whole-pool read was 0.51e12
        assert compiled.cost_analysis()["flops"] < 0.1e12
    else:
        assert mem.temp_size_in_bytes < 400 << 20


_LFM2 = dict(arch="lfm2_moe", vocab_size=65536, num_layers=10, num_heads=32,
             num_kv_heads=8, head_dim=64, model_dim=2048, ffn_dim=11776,
             moe_ffn_dim=1536, num_experts=64, num_experts_per_tok=4,
             first_dense_layers=2,
             layer_types=["conv", "conv", "full_attention", "conv", "conv",
                          "conv", "full_attention", "conv", "conv", "conv"],
             conv_kernel=3, rope_theta=1e6, rms_eps=1e-5,
             routed_scaling_factor=1.0, norm_topk_prob=True, dtype="bfloat16")


@pytest.mark.parametrize("program", ["prefill", "decode", "admit_scatter"])
def test_lfm2_moe_serving_programs_compile_for_the_chip(v5e, program):
    """The three programs ``PagedKVDecoder(arch="lfm2_moe")`` runs, lowered
    for the v5e at LFM2-24B-A2B's published widths, all ten layers of the
    benchmark's cut (5,267,090,176 parameters in bfloat16) and its serving
    sizes (64 lanes x 2,048 slots, a 1,024 bucket). What has to hold on the
    chip: the cache is a (64, 2, 2,048) float32 row a conv layer and two
    (8, 131,072, 64) pools an attention layer, in layer order, each back in
    the type it went in; the step's two attention layers read a lane's OWN
    PAGES, as ``pool_read_own_pages`` says of (64, 32, 64) queries over two
    pools of 8 x 131,072 x 64 (2.7 GB a layer against 3.5 for the whole
    pool), so no (64, 131,072) mask is built and nothing as large as the
    lanes' scores over the pool is made; the conv operators are in the
    program under their nodes' names; both graphs keep the grouped matmul
    and report the experts' load last; and everything fits beside the
    weights: the step's temporaries under 0.7 GB, the admission's under 0.1."""
    from types import SimpleNamespace

    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.ops.attention import pool_read_own_pages
    from mxnet_tpu.serving.kv_decode import _AdmitScatter

    lanes, max_len, bucket, page = 64, 2048, 1024, 16
    slots, cfg = lanes * max_len, _LFM2
    weights = {n: (s, "bfloat16") for n, s in tf.param_shapes(**cfg).items()}
    cache = tf.decode_cache(**cfg)
    assert [kind for _, kind, _ in cache] == \
        ["row", "row", "pool", "pool", "row", "row", "row", "pool", "pool",
         "row", "row", "row"]
    buffers = [((8, slots, 64), "bfloat16") if kind == "pool"
               else ((lanes, 2, 2048), "float32") for _, kind, _ in cache]
    if program == "admit_scatter":
        prog = _AdmitScatter(SimpleNamespace(
            _cache=cache, page_size=page, prefill_len=bucket))
        spec = lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, jnp.dtype(dtype), sharding=v5e)
        new = [((1, 8, bucket, 64), "bfloat16") if kind == "pool"
               else ((1, 2, 2048), "float32") for _, kind, _ in cache]
        mem = prog._fn.lower(
            tuple(spec(*b) for b in buffers), tuple(spec(*n) for n in new),
            spec((bucket // page,), "int32"), spec((2,), "int32"),
        ).compile().memory_analysis()
        # every buffer of the cache is updated in place, pools and rows
        assert mem.alias_size_in_bytes == 4 * 8 * slots * 64 * 2 \
            + 8 * lanes * 2 * 2048 * 4
        assert mem.temp_size_in_bytes < 1 << 20
        return
    if program == "prefill":
        sym = tf.get_prefill_symbol(prefill_len=bucket, **cfg)
        inputs = {"data": ((1, bucket), "float32"),
                  "length": ((1, 1), "float32")}
        want = [((bucket, 65536), "float32")] \
            + [((1, 8, bucket, 64), "bfloat16") if kind == "pool"
               else ((1, 2, 2048), "float32") for _, kind, _ in cache] \
            + [((8, 64), "float32")]
    else:
        sym = tf.get_decode_symbol(max_len=slots, page_size=page, **cfg)
        inputs = {"data": ((lanes, 1), "float32"),
                  "pos_idx": ((lanes, 1), "float32"),
                  "write_slot": ((lanes, 1), "float32"),
                  "page_table": ((lanes, max_len // page), "float32")}
        inputs.update({name: b for (name, _, _), b in zip(cache, buffers)})
        want = [((lanes, 65536), "float32")] + buffers \
            + [((lanes,), "float32"), ((8, 64), "float32")]
    compiled = _compile_program(
        v5e, sym, {**weights, **inputs},
        donated=[name for name, _, _ in cache] if program == "decode" else ())
    assert [(s.shape, str(s.dtype)) for s in compiled.out_info[0]] == want
    hlo = compiled.as_text()
    assert "ragged" in hlo.lower()
    for i in (0, 1, 3, 4, 5, 7, 8, 9):
        assert "layer%d_conv_core/" % i in hlo
    mem = compiled.memory_analysis()
    if program == "prefill":
        assert mem.temp_size_in_bytes < 100 << 20
        return
    # the rule, asked as the operator asks it
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    pool = struct((8, slots, 64), "bfloat16")
    assert pool_read_own_pages(
        struct((lanes, 32, 64), "bfloat16"), pool, pool,
        struct((lanes, max_len // page), "float32"), page)
    found = [(math.prod(int(d) for d in dims.split(",") if d), op)
             for _n, dims, op, _a in _INSTRUCTION.findall(hlo)
             if op != "parameter"]
    # nothing made is as large as the lanes' scores over the pool (268 M
    # elements), and no read's mask is built
    assert not [n for n, _ in found if n >= lanes * 32 * slots]
    assert "kv_mask" not in hlo and "slot_onehot" not in hlo
    # the step updates every row and pool in place
    assert mem.alias_size_in_bytes == 4 * 8 * slots * 64 * 2 \
        + 8 * lanes * 2 * 2048 * 4
    gathers = [line for line in hlo.splitlines() if " gather(" in line]
    for i in (2, 6):    # a gather for the keys, one for the values
        assert sum("layer%d_att/" % i in g for g in gathers) == 2
    assert mem.temp_size_in_bytes < 700 << 20


_MIMO = dict(arch="mimo_v2_flash", vocab_size=19072, num_layers=7,
             num_heads=64, num_kv_heads=4, swa_num_kv_heads=8, head_dim=192,
             v_head_dim=128, model_dim=4096, ffn_dim=16384, moe_ffn_dim=2048,
             num_experts=256, num_local_experts=16, local_expert_offset=0,
             num_experts_per_tok=8, hybrid_layer_pattern=[0, 1, 1, 1, 1, 0, 1],
             moe_layer_freq=[0, 1, 1, 1, 1, 1, 1], sliding_window=128,
             rotary_dim=64, rope_theta=5e6, swa_rope_theta=1e4,
             attention_value_scale=0.707, rms_eps=1e-5,
             routed_scaling_factor=1.0, norm_topk_prob=True, dtype="bfloat16")


@pytest.mark.parametrize("program", ["prefill", "decode", "admit_scatter"])
def test_mimo_v2_flash_serving_programs_compile_for_the_chip(v5e, program):
    """The three programs ``PagedKVDecoder(arch="mimo_v2_flash")`` runs,
    lowered for the v5e at MiMo-V2-Flash's published widths, the benchmark's
    cut (layers 0-6, 16 of 256 experts, 19,072 rows of the vocabulary:
    3,429,955,392 parameters in bfloat16) and its serving sizes (32 lanes x
    8,192 slots, a 2,048 bucket). What has to hold on the chip: the cache is
    a key pool (4, 262,144, 192) beside a value pool (4, 262,144, 128) for a
    full layer and two rings (32, 8, 128, .) for a window layer, in layer
    order, each back in the type it went in and updated in place; a window
    layer's prefill scores a BAND (nothing of 2,048 x 2,048 a window head);
    the step's two full layers read a lane's OWN PAGES, as
    ``pool_read_own_pages`` says of a key and a value pool of different
    width; both graphs keep the grouped matmul over the 16 held experts and
    report the load of all 256 last; and everything fits beside 6.9 GB of
    weights."""
    from types import SimpleNamespace

    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.ops.attention import pool_read_own_pages
    from mxnet_tpu.serving.kv_decode import _AdmitScatter

    lanes, max_len, bucket, page = 32, 8192, 2048, 16
    slots, cfg = lanes * max_len, _MIMO
    shapes = tf.param_shapes(**cfg)
    assert sum(math.prod(s) for s in shapes.values()) == 3_429_955_392
    weights = {n: (s, "bfloat16") for n, s in shapes.items()}
    cache = tf.decode_cache(**cfg)
    assert [kind for _, kind, _ in cache] == \
        ["pool"] * 2 + ["ring"] * 8 + ["pool"] * 2 + ["ring"] * 2
    buffers = [((shape[0], slots, shape[1]) if kind == "pool"
                else (lanes,) + shape, "bfloat16")
               for _, kind, shape in cache]
    cache_bytes = sum(2 * math.prod(shape) for shape, _ in buffers)
    # the two full layers' pools 1.34 GB, the five window layers' rings 0.1
    assert cache_bytes == 2 * slots * 4 * 320 * 2 + 5 * lanes * 128 * 8 * 640
    exported = [((1, shape[0], bucket, shape[-1]), "bfloat16")
                for _, _, shape in cache]
    if program == "admit_scatter":
        prog = _AdmitScatter(SimpleNamespace(
            _cache=cache, page_size=page, prefill_len=bucket))
        spec = lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, jnp.dtype(dtype), sharding=v5e)
        mem = prog._fn.lower(
            tuple(spec(*b) for b in buffers),
            tuple(spec(*n) for n in exported),
            spec((bucket // page,), "int32"), spec((2,), "int32"),
        ).compile().memory_analysis()
        # every buffer of the cache is updated in place, pools and rings
        assert mem.alias_size_in_bytes == cache_bytes
        assert mem.temp_size_in_bytes < 8 << 20
        return
    if program == "prefill":
        sym = tf.get_prefill_symbol(prefill_len=bucket, **cfg)
        inputs = {"data": ((1, bucket), "float32")}
        want = [((bucket, 19072), "float32")] + exported \
            + [((6, 256), "float32")]
    else:
        sym = tf.get_decode_symbol(max_len=slots, page_size=page, **cfg)
        inputs = {"data": ((lanes, 1), "float32"),
                  "pos_idx": ((lanes, 1), "float32"),
                  "write_slot": ((lanes, 1), "float32"),
                  "page_table": ((lanes, max_len // page), "float32")}
        inputs.update({name: b for (name, _, _), b in zip(cache, buffers)})
        want = [((lanes, 19072), "float32")] + buffers \
            + [((lanes,), "float32"), ((6, 256), "float32")]
    compiled = _compile_program(
        v5e, sym, {**weights, **inputs},
        donated=[name for name, _, _ in cache] if program == "decode" else ())
    assert [(s.shape, str(s.dtype)) for s in compiled.out_info[0]] == want
    hlo = compiled.as_text()
    assert "ragged" in hlo.lower()
    mem = compiled.memory_analysis()
    found = [(math.prod(int(d) for d in dims.split(",") if d), op)
             for _n, dims, op, _a in _INSTRUCTION.findall(hlo)
             if op != "parameter"]
    if program == "prefill":
        # the full layers' float32 scores, 64 x 2,048 x 2,048, are the
        # largest thing made; a window layer's are an eighth of that
        assert max(n for n, _ in found) <= 64 * bucket * bucket
        assert mem.temp_size_in_bytes < 3 << 30
        return
    # the rule, asked as the operator asks it: pools of different width
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    assert pool_read_own_pages(
        struct((lanes, 64, 192), "bfloat16"),
        struct((4, slots, 192), "bfloat16"),
        struct((4, slots, 128), "bfloat16"),
        struct((lanes, max_len // page), "float32"), page)
    # nothing made is as large as the lanes' scores over the pool (537 M
    # elements), and no read's mask is built
    assert not [n for n, _ in found if n >= lanes * 64 * slots]
    assert "kv_mask" not in hlo and "slot_onehot" not in hlo
    # the step updates every pool and ring in place
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < 3 << 30


def _program_alone(hlo):
    """A compiled program's text without what names the source it was
    traced from: the module's name (the graph's last node, numbered as it
    was built), the tables of files, functions and locations in front of
    the computations and every instruction's ``metadata={...}``."""
    blocks = [b for b in hlo.split("\n\n") if b.split("\n", 1)[0] not in (
        "FileNames", "FunctionNames", "FileLocations", "StackFrames")]
    return re.sub(r",? metadata=\{[^{}]*\}|^HloModule [^,]+", "",
                  "\n\n".join(blocks))


# cell -> (the builder's sizes, lanes, slots a lane, weights' type)
_WHOLE_POOL_CELLS = {
    "transformer-base": (dict(vocab_size=32000, num_layers=2, num_heads=8,
                              model_dim=512, ffn_dim=2048, pos_len=1024),
                         64, 1024, "float32"),
    "olmoe-1b-7b": (_OLMOE, 8, 2048, "bfloat16"),
    "granite-4.0-h-micro": (_GRANITE, 32, 2048, "bfloat16"),
}


@pytest.mark.parametrize("cell", list(_WHOLE_POOL_CELLS))
def test_a_pool_of_narrow_heads_keeps_its_decode_program(v5e, monkeypatch,
                                                         cell):
    """The decode programs of the three configurations whose pools hold 64-
    and 128-wide heads, at their cells' serving sizes: handing the read its
    page table changes nothing the chip runs. The rule says whole pool, so
    the program is, instruction for instruction, the one compiled from the
    graph that hands ``KVPoolAttention`` a mask and nothing else, which is
    the graph of before the read could take a table."""
    from mxnet_tpu.models import transformer as tf

    cfg, lanes, max_len, dtype = _WHOLE_POOL_CELLS[cell]
    slots, page = lanes * max_len, 16
    def mask_only(*a):
        write, read = step_inputs(*a)
        return write, {"mask": read["mask"]}

    step_inputs = tf._pool_step_inputs

    def build(mask_alone):
        with monkeypatch.context() as patch:
            if mask_alone:
                patch.setattr(tf, "_pool_step_inputs", mask_only)
            return tf.get_decode_symbol(max_len=slots, page_size=page, **cfg)

    args = {n: ((lanes, 1), "float32")
            for n in ("data", "pos_idx", "write_slot")}
    args["page_table"] = ((lanes, max_len // page), "float32")
    if "arch" in cfg:
        args.update({n: (shape, dtype)
                     for n, shape in tf.param_shapes(**cfg).items()})
        for name, kind, shape in tf.decode_cache(**cfg):
            args[name] = ((shape[0], slots, shape[1]), dtype) \
                if kind == "pool" else ((lanes,) + tuple(shape), "float32")
    else:
        heads, dh = cfg["num_heads"], cfg["model_dim"] // cfg["num_heads"]
        pools = {"kv_%s_%d" % (t, i): (heads, slots, dh)
                 for t in "kv" for i in range(cfg["num_layers"])}
        sym = build(False)
        shapes, _, _ = sym.infer_shape(**pools, **{
            n: s for n, (s, _) in args.items()})
        args = {n: (s, dtype) for n, s in zip(sym.list_arguments(), shapes)}
    with_table, mask_alone = build(False), build(True)
    operands = [[len(n.inputs) for n in sym._topo()
                 if n.op == "_contrib_KVPoolAttention"]
                for sym in (with_table, mask_alone)]
    assert operands[0] and set(operands[0]) == {7}
    assert len(operands[1]) == len(operands[0]) and set(operands[1]) == {4}
    programs = [_program_alone(_compile_program(v5e, sym, args).as_text())
                for sym in (with_table, mask_alone)]
    assert programs[0] == programs[1]
