"""Every kept Pallas kernel must pass Mosaic for the chip, checked here
without one.

The CPU tests run the kernels in interpret mode, which accepts block shapes
and vector ops the TPU compiler refuses. libtpu can compile for a "TPU v5
lite" from this sandbox through the topology API, so each kernel is lowered
with ``interpret=False`` and compiled at the cells' shapes (the attention
kernel forward and backward). This is the guard that a kernel edited on the CPU still
compiles on the chip (the first on-chip run found two that did not).
"""
import math
import re

import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import pallas_attention as pa


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
    # the rules pick a kernel or XLA's form from the default backend (the
    # CPU here)
    from mxnet_tpu.ops import attention

    monkeypatch.setattr(attention, "_backend", lambda: "tpu")


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


@pytest.mark.parametrize("heads,kv_heads,t,dk,dv", [
    (8, 8, 512, 64, 64),          # a training step's, a batch of 8
    (32, 2, 2048, 128, 128),      # grouped heads folded into the rows
    (32, 32, 1024, 192, 128),     # a value narrower than the key
])
def test_flash_attention_fwd_bwd(v5e, heads, kv_heads, t, dk, dv):
    """The blockwise kernel at the rule's blocks, forward and both backward
    kernels, through Mosaic for the v5e."""
    spec = lambda h, d: ((8 if heads == 8 else 1, h, t, d), "bfloat16")
    fn = lambda q, k, v: pa.flash_attention(q, k, v, causal=True,
                                            interpret=False)
    _compile(v5e, _with_grads(fn, 3), spec(heads, dk), spec(kv_heads, dk),
             spec(kv_heads, dv))


@pytest.mark.parametrize("heads,kv_heads,t,dk,dv,window,blocks", [
    (72, 8, 8192, 128, 128, 512, (64, 1024)),     # laguna: a group of 9
    (64, 64, 8192, 256, 128, 513, (512, 1024)),   # dots3: T no multiple
    (40, 10, 2048, 128, 128, 512, (256, 1024)),   # phi4flash's padded pairs
])
def test_flash_attention_under_a_window(v5e, heads, kv_heads, t, dk, dv,
                                        window, blocks):
    """The forward kernel under a window at the three cells' window layers
    and the rule's blocks, through Mosaic for the v5e; and the operator
    differentiated there (``"window_kernel"``'s backward is the band's:
    XLA's alone, the kernel forward)."""
    from mxnet_tpu.ops import attention

    assert pa.blocks(t, t, heads // kv_heads, dk, dv, jnp.bfloat16,
                     window=window) == blocks
    spec = lambda h, d: ((1, h, t, d), "bfloat16")
    specs = spec(heads, dk), spec(kv_heads, dk), spec(kv_heads, dv)
    _compile(v5e, lambda q, k, v: pa.flash_attention(
        q, k, v, causal=True, interpret=False, window=window), *specs)
    if heads == 40:     # the smallest: the op's forward and backward
        op = lambda q, k, v: attention._multi_head_attention(
            {"causal": True, "scale": -1.0, "window": window}, q, k, v)
        # (a loss that reads the output, or the forward is dead code)
        _compile(v5e, jax.grad(lambda *a: jnp.sum(
            op(*a).astype(jnp.float32) ** 2), argnums=(0, 1, 2)), *specs)


def test_resnet50_training_step_is_xla_alone(v5e):
    """The training cells' step (``SPMDTrainer``, ResNet-50, 224x224, 256
    images, bfloat16) lowered for the chip: every node is its registered
    operator, so the program holds each of the 53 convolutions forward and
    twice backward (less the stem's data gradient) and no Mosaic custom
    call."""
    from mxnet_tpu import models, parallel

    net = models.get_symbol("resnet-50", num_classes=1000,
                            image_shape="3,224,224")
    shapes = {"data": (256, 3, 224, 224), "softmax_label": (256,)}
    mesh = parallel.make_mesh({"data": 1}, devices=list(v5e.device_set))
    trainer = parallel.SPMDTrainer(
        net, mesh, optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        compute_dtype="bfloat16")
    arg_shapes, _, aux_shapes = net.infer_shape(**shapes)
    struct = lambda shape, dtype="float32": jax.ShapeDtypeStruct(
        shape, jnp.dtype(dtype), sharding=v5e)
    named = dict(zip(net.list_arguments(), arg_shapes))
    params = {n: struct(named[n]) for n in trainer.param_names}
    aux = dict(zip(trainer.aux_names, map(struct, aux_shapes)))
    opt_state = jax.tree_util.tree_map(
        lambda s: struct(s.shape, s.dtype),
        jax.eval_shape(trainer._opt_init, params))
    inputs = {"data": struct(shapes["data"], "bfloat16"),
              "softmax_label": struct(shapes["softmax_label"])}
    hlo = trainer._build_step().lower(
        params, aux, opt_state, inputs, struct((2,), "uint32"),
        struct(())).as_text()
    assert "tpu_custom_call" not in hlo
    assert hlo.count("stablehlo.convolution") == 3 * 53 - 1


def test_admit_pool_update_stays_in_place_on_the_chip(v5e):
    """The pool update of an admission (serving/kv_decode.py
    ``_AdmitScatter``) at the benchmark's sizes: all 12 pool buffers are
    aliased to their outputs and the program holds no whole-buffer
    temporary. The pools are PAGE-MAJOR (4,096 frames of (16, 8 x 64)): the
    chip keeps them row-major as bound, a page is one 32 KB piece, and the
    page walk writes one piece a page and pool. (A head-major pool of 64-wide
    heads sat slots-minor, and a scatter over its slot axis re-laid every
    134 MB buffer out before and after.)"""
    from types import SimpleNamespace

    from mxnet_tpu.ops.attention import pool_shape
    from mxnet_tpu.serving.kv_decode import _AdmitScatter

    layers, heads, dh, slots, prefill, page = 6, 8, 64, 64 * 1024, 1024, 16
    prog = _AdmitScatter(SimpleNamespace(
        _cache=[("kv_%s_%d" % (t, i), "pool", (heads, dh))
                for i in range(layers) for t in "kv"],
        page_size=page, prefill_len=prefill))
    bound = pool_shape(heads, dh, slots, page)
    assert bound == (slots // page, page, heads * dh)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=v5e)

    compiled = prog._fn.lower(
        tuple(spec(bound, "float32") for _ in range(2 * layers)),
        tuple(spec((1, heads, prefill, dh), "float32")
              for _ in range(2 * layers)),
        spec((prefill // page,), "int32"), spec((2,), "int32"),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * layers * heads * slots * dh * 4
    # the prompt's rows turned a token's heads side by side: 12 x 2 MB
    assert mem.temp_size_in_bytes < 32 << 20
    _assert_no_pool_sized_copy(compiled.as_text(), heads * slots * dh)


@pytest.mark.parametrize("heads,kv_heads,t,dk,dv", [
    (128, 128, 8192, 192, 128),   # dots3-note-prev's full layer, the cell's
    (32, 2, 2048, 128, 128),      # a group's rows share the mask's block
])
def test_flash_attention_under_a_selection(v5e, heads, kv_heads, t, dk, dv):
    """The forward kernel with a selection's int8 mask (B, T, S) beside its
    operands, at the rule's blocks (``blocks(..., selected=True)``: the
    mask's two buffers counted), through Mosaic for the v5e."""
    assert pa.blocks(t, t, heads // kv_heads, dk, dv, jnp.bfloat16, True) \
        == (1024 * kv_heads // heads or 64, 1024)
    spec = lambda h, d: ((1, h, t, d), "bfloat16")
    fn = lambda q, k, v, selected: pa.flash_attention(
        q, k, v, causal=True, interpret=False, selected=selected)
    _compile(v5e, fn, spec(heads, dk), spec(kv_heads, dk), spec(kv_heads, dv),
             ((1, t, t), "int8"))


def _paged_read_calls(hlo):
    """The program's calls of the kernel that walks the page table
    (``ops/pallas_paged_read.py``), a line each."""
    return [line for line in hlo.splitlines() if " custom-call(" in line
            and 'custom_call_target="tpu_custom_call"' in line
            and "/paged_read/" in line]


def _kernel_assembly(body):
    """A Mosaic kernel's serialized body (the base64 of a custom call's
    ``"body"``) as assembly WITHOUT the file and line of each operation."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True   # the serialized dialect's
    with ctx:
        return ir.Module.parse(base64.b64decode(body)).operation.get_asm(
            enable_debug_info=False)


def _paged_read_blocks(hlo):
    """What the program's calls of the page-walk kernel keep in VMEM: the set
    of ``(slots, lanes, type)`` of their double-buffered scratch blocks, read
    off each call's serialized body (key and value buffers alike, two
    buffers each: ``memref<2 x slots x lanes>``)."""
    found = set()
    for line in _paged_read_calls(hlo):
        asm = _kernel_assembly(re.search(
            r'"body":"([A-Za-z0-9+/=]+)"', line).group(1))
        head = asm[asm.index("^bb0("):].split("\n", 1)[0]
        scratch = re.findall(
            r"memref<2x(\d+)x(\d+)x(bf16|f32), #tpu.memory_space<vmem>>",
            head)
        assert len(scratch) == 2, head
        found |= {(int(slots), int(lanes), dt) for slots, lanes, dt in scratch}
    return found


def _assert_the_block_the_rule_names(hlo, max_pages, page, lanes, dtype):
    """Every page-walk call of the program holds the block ``pages_per_block``
    names for pools of ``lanes`` a row under a table of ``max_pages``, both
    buffers of both pools inside ``_SCRATCH_BYTES``."""
    from mxnet_tpu.ops import pallas_paged_read as kernel

    width = jnp.dtype(dtype).itemsize
    pages = kernel.pages_per_block(max_pages, page, 2 * lanes * width)
    assert _paged_read_blocks(hlo) == {
        (pages * page, lanes, {"bfloat16": "bf16", "float32": "f32"}[dtype])}
    assert 2 * pages * page * 2 * lanes * width <= kernel._SCRATCH_BYTES
    return pages * page


def _assert_one_write_a_node(hlo, nodes, pools=2):
    """Each ``KVPoolSlotWrite`` node named in ``nodes`` (its scope's tag)
    writes each of its ``pools`` page-major pools in ONE device operation, a
    scatter of all the node's rows (on the chip a fusion of its own, the pool
    aliased), and nothing of the loop it replaced: no ``while``, no
    ``dynamic-update-slice`` and no one-row slice under the node's name."""
    lines = hlo.splitlines()
    scatters = [line for line in lines
                if " scatter(" in line and "kvupd/" in line]
    assert len(scatters) == pools * len(nodes)
    for tag in nodes:
        assert sum(tag in line for line in scatters) == pools, tag
        assert not [line for line in lines if tag in line and re.search(
            r" (while|dynamic-update-slice|dynamic-slice|custom-call)\(",
            line)]


def _assert_attention_is_blockwise(hlo, layers, bucket):
    """A prefill's ``layers`` plain causal attention layers each run as ONE
    call of the blockwise kernel (``ops/pallas_attention.py``), and nothing
    in the program is a layer's scores: no float32 buffer of heads x
    ``bucket`` x ``bucket`` (``f32[..., 16, 2048, 2048]``, whatever
    dimensions of 1 stand among them)."""
    assert len(_flash_attention_calls(hlo)) == layers
    scores = [dims for dims in re.findall(r"f32\[([\d,]+)\]", hlo)
              if [int(d) for d in dims.split(",") if int(d) != 1][1:]
              [-2:] == [bucket, bucket]]
    assert not scores, scores[:4]


def _flash_attention_calls(hlo):
    """The program's calls of the blockwise attention kernel
    (``ops/pallas_attention.py``), a line each."""
    return [line for line in hlo.splitlines() if " custom-call(" in line
            and 'custom_call_target="tpu_custom_call"' in line
            and "/flash_attention" in line]


def _window_attention_calls(hlo):
    """The program's calls of the blockwise attention kernel under a WINDOW
    (``flash_attention(window=)``: ``attention_form``'s ``"window_kernel"``),
    a line each."""
    return [line for line in hlo.splitlines() if " custom-call(" in line
            and 'custom_call_target="tpu_custom_call"' in line
            and "/window_attention" in line]


def _assert_expert_layers(hlo, layers, tokens, k, experts, d, f,
                          routed=None):
    """The program's ``layers`` expert layers of ``tokens`` rows, ``k`` experts
    a token, ``experts`` held stacks (D, F) of ``routed`` run the form the
    operator's rule (``pallas_grouped_matmul.moe_form``) names at those
    operands, asked as the operator asks it: TWO calls of the kernel a kernel
    layer (gate and up fused with the activation, then down) and no
    ``ragged-dot`` there; XLA's grouped matmul and no call of the kernel
    otherwise. A kernel call holds the fetch ring ``tiles`` names there,
    ``depth`` buffers a matrix of its column tile, and what Mosaic gave it of
    VMEM is that ring and the rows', the output's and the float32 sums' 16 MB
    at most beside it, under the limit the call states. Where the layer
    moves its HELD rows alone (``moe.held_rows_chunk`` names a chunk: the
    admissions of mimo, dots3 and laguna) a call's rows are the CHUNK's and
    the program holds nothing of ``tokens * k`` rows by ``d``, nor
    (tokens, k, d), in any type. Returns the form."""
    from mxnet_tpu.ops.moe import held_rows_chunk
    from mxnet_tpu.ops.pallas_grouped_matmul import layer_tiles, moe_form

    struct = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    chunk = held_rows_chunk(tokens, k, experts, routed or experts)
    rows = chunk or tokens * k
    form = moe_form(struct(rows, d), struct(experts, d, f),
                    struct(experts, f, d))
    if chunk:
        every = re.findall(r"\w+\[(?:%d,%d|%d,%d,%d)\]"
                           % (tokens * k, d, tokens, k, d), hlo)
        assert not every, every[:4]
    calls = [line for line in hlo.splitlines() if " custom-call(" in line
             and 'custom_call_target="tpu_custom_call"' in line
             and "/grouped_matmul" in line]
    # XLA's form, by its name among the instructions: the tables of source
    # names in front of them hold whatever test first traced a cached helper
    ragged = "ragged" in _program_alone(hlo).lower()
    # a layer that moves its held rows alone holds a turn twice: the first
    # in front of the loop, every further one inside it
    turns = 2 if chunk else 1
    if form == "kernel":
        assert len(calls) == 2 * turns * layers and not ragged
        assert sum("/grouped_matmul_gated/" in line for line in calls) \
            == turns * layers
        _, tn_up, tn_down, depth = layer_tiles(
            struct(rows, d), struct(experts, d, f),
            None if chunk else routed)
        assert depth >= 2
        for line in calls:
            assert int(re.search(r"= \w+\[(\d+),", line).group(1)) == rows
            ring = 2 * depth * (2 * d * tn_up if "_gated/" in line
                                else f * tn_down)
            # where XLA put the call's VMEM, and how far into it Mosaic went
            (at, limit), (_, end) = (map(int, re.search(
                r'"%s":\[\{"memory_space":"1","offset":"(\d+)","size":"(\d+)"'
                % key, line).groups()) for key in (
                    "scoped_memory_configs", "used_scoped_memory_configs"))
            assert ring <= end - at <= min(ring + (16 << 20), limit), \
                (ring, at, end, limit)
    else:
        assert not calls and ragged
    return form


# cell, program -> (tokens, experts a token, routed experts, held experts, D,
# F) and what the rules answer there: the form, and the tiles (row tile,
# column tile of gate and up, column tile of down, depth of the fetch ring)
_EXPERT_LAYERS = {
    ("olmoe-1b-7b.score", "prefill"): ((2048, 8, 64, 64, 2048, 1024),
                                       "kernel", (128, 1024, 2048, 2)),
    ("olmoe-1b-7b.score", "decode"): ((8, 8, 64, 64, 2048, 1024),
                                      "kernel", (32, 1024, 2048, 3)),
    ("kanana-2-30b-a3b.generate", "prefill"): (
        (1024, 6, 128, 128, 2048, 768), "kernel", (128, 768, 2048, 3)),
    ("kanana-2-30b-a3b.generate", "decode"): (
        (32, 6, 128, 128, 2048, 768), "kernel", (32, 768, 2048, 3)),
    ("lfm2-24b-a2b.generate", "prefill"): (
        (1024, 4, 64, 64, 2048, 1536), "kernel", (128, 1536, 2048, 3)),
    ("lfm2-24b-a2b.generate", "decode"): (
        (64, 4, 64, 64, 2048, 1536), "kernel", (32, 1536, 2048, 3)),
    ("mimo-v2-flash.generate", "prefill"): (
        (2048, 8, 256, 16, 4096, 2048), "kernel", (128, 1024, 4096, 2)),
    ("mimo-v2-flash.generate", "decode"): (
        (32, 8, 256, 16, 4096, 2048), "kernel", (32, 1024, 4096, 2)),
    ("laguna-s-2.1.generate", "prefill"): (
        (8192, 10, 256, 64, 3072, 1024), "kernel", (128, 1024, 3072, 2)),
    ("laguna-s-2.1.generate", "decode"): (
        (32, 10, 256, 64, 3072, 1024), "kernel", (32, 1024, 3072, 3)),
    # routed over 768 router outputs, 256 of them zero-compute experts
    ("longcat-flash-omni.generate", "prefill"): (
        (4096, 12, 768, 16, 6144, 2048), "kernel", (128, 512, 3072, 3)),
    ("longcat-flash-omni.generate", "decode"): (
        (32, 12, 768, 16, 6144, 2048), "kernel", (32, 512, 3072, 3)),
}


@pytest.mark.parametrize("cell,program", list(_EXPERT_LAYERS))
def test_the_expert_rules_at_the_cells_shapes(cell, program):
    """What ``moe_form`` and ``tiles`` answer at the four cells' admissions
    and steps (the widths as published, the benchmark's lanes and buckets),
    pinned: a change of either rule changes eight programs of the benchmark,
    and says so here first. Plain Python: no chip, no compile."""
    from mxnet_tpu.ops import pallas_grouped_matmul as kernel

    (tokens, k, routed, held, d, f), form, tiles = _EXPERT_LAYERS[cell,
                                                                  program]
    struct = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert kernel.moe_form(struct(tokens * k, d), struct(held, d, f),
                           struct(held, f, d)) == form
    assert kernel.tiles(tokens * k * held // routed, held, d, f,
                        jnp.bfloat16) == tiles


# cell -> a full-attention layer of its prefill (query heads, key/value
# heads, the bucket, key width, value width) and what the rules answer there:
# the form, and the kernel's blocks (block_q a group's share of a step's
# rows, block_k)
_ATTENTION_LAYERS = {
    "olmoe-1b-7b.score": ((16, 16, 2048, 128, 128), "kernel", (1024, 1024)),
    "nemotron-3-nano-30b-a3b.generate": ((32, 2, 2048, 128, 128), "kernel",
                                         (64, 1024)),
    "mimo-v2-flash.generate": ((64, 4, 2048, 192, 128), "kernel",
                               (64, 1024)),
    "kanana-2-30b-a3b.generate": ((32, 32, 1024, 192, 128), "kernel",
                                  (1024, 1024)),
    "lfm2-24b-a2b.generate": ((32, 8, 1024, 64, 64), "kernel", (256, 1024)),
    # 32 MiB of scores: the chip keeps them in its vector memory
    "granite-4.0-h-micro.generate": ((32, 8, 512, 64, 64), "dense", None),
    "transformer-base.generate": ((8, 8, 1024, 64, 64), "dense", None),
    "transformer-base.score": ((8, 8, 1024, 64, 64), "dense", None),
    # its full layers, under the selection of 2,048 (the mask's two buffers
    # fit beside the blocks of a plain call)
    "dots3-note-prev.generate": ((128, 128, 8192, 192, 128), "sparse_kernel",
                                 (1024, 1024)),
    # its full layers: a group of 6, the first that is no power of two, so a
    # step's rows are 6 x 128 = 768 where every other cell folds to 1,024
    "laguna-s-2.1.generate": ((48, 8, 8192, 128, 128), "kernel", (128, 1024)),
    # both sublayers' materialised latent attention, a key/value head a head
    "longcat-flash-omni.generate": ((64, 64, 4096, 192, 128), "kernel",
                                    (1024, 1024)),
}


@pytest.mark.parametrize("cell", list(_ATTENTION_LAYERS))
def test_the_attention_rules_at_the_cells_shapes(cell):
    """What ``attention_form`` and ``blocks`` answer at every cell's
    admission (the widths as published, the benchmark's buckets, bfloat16
    but for ``transformer-base``'s float32), pinned: a change of either rule
    changes the prefill programs of the benchmark, and says so here first.
    (``phi-4-mini-flash-reasoning.generate`` has no such layer: windows, and
    a one-row read.) Plain Python: no chip, no compile."""
    from mxnet_tpu.ops import attention

    (h, hkv, t, dk, dv), form, blocks = _ATTENTION_LAYERS[cell]
    dtype = jnp.float32 if cell.startswith("transformer-base") \
        else jnp.bfloat16
    struct = lambda heads, d: jax.ShapeDtypeStruct((1, heads, t, d), dtype)
    ops = struct(h, dk), struct(hkv, dk), struct(hkv, dv)
    selected = form == "sparse_kernel"
    assert attention.attention_form(*ops, True, 0, False, None,
                                    2048 if selected else 0) == form
    # a window of 128: the kernel under the window where it takes the
    # operands and the band's float32 scores pass its threshold (dots3's and
    # laguna's wide full layers alone), XLA's band below
    assert attention.attention_form(*ops, True, 128) == (
        "window_kernel" if blocks and 4 * h * t * 256 > pa._BAND_SCORES
        else "band")
    assert attention.attention_form(*ops, True, 0, True) == "dense"
    assert attention.attention_form(*ops, False) == "dense"
    if blocks:
        assert pa.blocks(t, t, h // hkv, dk, dv, dtype, selected) == blocks


def _assert_no_pool_sized_copy(hlo, pool):
    """No ``copy`` or ``transpose`` in the program makes ``pool`` elements or
    more: no buffer of a pool's size is re-laid."""
    moved = [(op, name, dims) for name, dims, op, _ in _INSTRUCTION.findall(hlo)
             if op in ("copy", "transpose")
             and math.prod(int(d) for d in dims.split(",") if d) >= pool]
    assert not moved, moved


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


def _assert_one_row_of_logits(compiled, bucket, vocab):
    """A prefill heads the prompt's last real row alone: its first output is
    that one row, and nothing in the program is a bucket's rows of logits
    (``f32[bucket, vocab]``, whatever dimensions of 1 stand around them)."""
    assert compiled.out_info[0][0].shape == (1, vocab)
    assert str(compiled.out_info[0][0].dtype) == "float32"
    rows = [(name, op) for name, dims, op, _ in
            _INSTRUCTION.findall(compiled.as_text())
            if [int(d) for d in dims.split(",") if d and int(d) != 1]
            == [bucket, vocab]]
    assert not rows, rows


def _entry_parameters(hlo):
    """The shapes of a program's parameters, in its entry's order."""
    entry = hlo[hlo.index("\nENTRY "):]
    return [tuple(int(d) for d in dims.split(",") if d) for dims in
            re.findall(r" = \w+\[([\d,]*)\]\S* parameter\(",
                       entry[:entry.index("\n}")])]


def _assert_pool_step_contracts(compiled, layers, rows, heads, slots, dh,
                                temp_bytes, cache_bytes, page=16):
    """A shared-pool decode program as the chip runs it, its PAGE-MAJOR pools
    ``(slots / page, page, heads x dh)`` donated: each layer's read of its
    two pools is ONE ``tpu_custom_call`` (the kernel that walks the page
    table; no contraction of XLA's touches a pool) and its write of both
    pools is ONE scatter a pool that puts every lane's row in (no loop over
    the rows), in place, so the program aliases ``cache_bytes``, all of its cache; no
    buffer the size of a pool is copied or transposed, the temporaries stay
    under ``temp_bytes`` (the whole-pool scores, rows x heads x slots, would
    not), no mask over the pool's slots is built, and no parameter
    of the program is rows x slots: the write takes a slot index a row."""
    hlo = compiled.as_text()
    params = _entry_parameters(hlo)
    assert (slots // page, page, heads * dh) in params and (rows, 1) in params
    assert (rows, slots) not in params
    assert not [p for p in params if len(p) == 2 and math.prod(p) >= slots
                and slots in p]
    lines = hlo.splitlines()
    kernels = _paged_read_calls(hlo)
    contractions = [line for line in lines
                    if re.search(r" (convolution|dot)\(", line)]
    assert len(kernels) == layers
    for i in range(layers):
        assert sum("layer%d_att/" % i in line for line in kernels) == 1
        for node in ("kvupd", "att"):
            tag = "layer%d_%s/" % (i, node)
            assert not [line for line in contractions if tag in line], tag
    _assert_one_write_a_node(hlo, ["layer%d_kvupd/" % i
                                   for i in range(layers)])
    assert " while(" not in hlo
    assert "slot_onehot" not in hlo and "kv_mask" not in hlo
    _assert_no_pool_sized_copy(hlo, heads * slots * dh)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < temp_bytes
    assert mem.alias_size_in_bytes == cache_bytes


def test_transformer_base_decode_step_contracts_on_the_chip(v5e):
    """``transformer-base.generate``'s decode program (two of its six layers,
    64 lanes x 65,536 slots, float32) lowered for the v5e as it is
    dispatched: the four pools donated, page-major (4,096, 16, 512). A
    layer's read is the kernel, so no scores over the pool are made (64 x 8
    x 65,536 x 4 = 134 MB a layer, before) and the temporaries are the
    feed-forward's; all 512 MB of pool are updated in place."""
    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.ops.attention import pool_shape

    layers, lanes, slots, heads, dh, page = 2, 64, 64 * 1024, 8, 64, 16
    sym = tf.get_decode_symbol(
        vocab_size=32000, num_layers=layers, num_heads=heads, model_dim=512,
        ffn_dim=2048, max_len=slots, pos_len=1024, page_size=page)
    arg_shapes, _, _ = sym.infer_shape(
        data=(lanes, 1), pos_idx=(lanes, 1), write_slot=(lanes, 1),
        page_table=(lanes, slots // lanes // page),
        **{"kv_%s_%d" % (t, i): pool_shape(heads, dh, slots, page)
           for t in "kv" for i in range(layers)})
    pools = ["kv_%s_%d" % (t, i) for i in range(layers) for t in "kv"]
    compiled = _compile_program(v5e, sym, {
        n: (shape, "float32")
        for n, shape in zip(sym.list_arguments(), arg_shapes)}, donated=pools)
    _assert_pool_step_contracts(
        compiled, layers, lanes, heads, slots, dh, temp_bytes=32 << 20,
        cache_bytes=2 * layers * heads * slots * dh * 4)
    # XLA's count: the feed-forward and the head, and next to nothing for a
    # custom call (its operands' small blocks, not the pages it copies): the
    # whole-pool read counted 0.84 GB a layer and 2.2 GB in all
    assert compiled.cost_analysis()["bytes accessed"] < 0.6e9
    # the kernel's block by the bytes a turn keeps in flight: 256 slots of a
    # 4,096-byte row (a sixteenth of the table, 64, until PR 57), 2 MB of
    # scratch
    assert _assert_the_block_the_rule_names(
        compiled.as_text(), slots // lanes // page, page, heads * dh,
        "float32") == 256


def test_transformer_base_prefill_heads_one_row_on_the_chip(v5e):
    """``transformer-base``'s prefill (two of its six layers, the 1,024
    bucket, float32) lowered for the v5e: the head runs over the prompt's
    last real row, gathered by ``length`` inside the program, so the first
    output is ``(1, vocab)`` and no ``f32[1024, 32000]`` (131 MB) is made;
    the K and V it exports are over the bucket as before."""
    from mxnet_tpu.models import transformer as tf

    layers, bucket, vocab = 2, 1024, 32000
    sym = tf.get_prefill_symbol(
        vocab_size=vocab, num_layers=layers, num_heads=8, model_dim=512,
        ffn_dim=2048, prefill_len=bucket, pos_len=bucket)
    arg_shapes, _, _ = sym.infer_shape(data=(1, bucket), length=(1, 1))
    compiled = _compile_program(v5e, sym, {
        n: (shape, "float32")
        for n, shape in zip(sym.list_arguments(), arg_shapes)})
    _assert_one_row_of_logits(compiled, bucket, vocab)
    assert [s.shape for s in compiled.out_info[0][1:]] \
        == [(1, 8, bucket, 64)] * 2 * layers
    # 2 x 1,024 x 3.1 M MACs a layer and the dense scores; the head over the
    # bucket alone was 2 x 1,024 x 16.4 M = 33.6 GFLOP
    assert compiled.cost_analysis()["flops"] < 20e9
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


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
    is found here, without a chip. The expert layer runs the form its rule
    names (the kernel's two calls since PR 41; no per-expert dense expansion:
    the FLOP count, the kernel's cost estimate in it, is the sparse one),
    and the decode step hands the pool back in the type it came in."""
    from mxnet_tpu.models import transformer as tf

    lanes, max_len = 8, 2048
    slots = lanes * max_len
    cfg = _OLMOE
    weights = {n: (s, "bfloat16") for n, s in tf.param_shapes(**cfg).items()}
    if program == "prefill":
        sym = tf.get_prefill_symbol(prefill_len=max_len, **cfg)
        inputs = {"data": ((1, max_len), "float32"),
                  "length": ((1, 1), "float32")}
    else:
        sym = tf.get_decode_symbol(max_len=slots, page_size=16, **cfg)
        inputs = {"data": ((lanes, 1), "float32"),
                  "pos_idx": ((lanes, 1), "float32"),
                  "write_slot": ((lanes, 1), "float32"),
                  "page_table": ((lanes, max_len // 16), "float32"),
                  "kv_k_0": ((slots // 16, 16, 16 * 128), "bfloat16"),
                  "kv_v_0": ((slots // 16, 16, 16 * 128), "bfloat16")}
    compiled = _compile_program(
        v5e, sym, {**weights, **inputs},
        donated=[n for n in inputs if n.startswith("kv_")])
    _assert_expert_layers(compiled.as_text(), 1,
                          max_len if program == "prefill" else lanes, 8, 64,
                          2048, 1024)
    flops = compiled.cost_analysis()["flops"]
    if program == "prefill":
        # 2 x 2,048 tokens x (67.2 M projections, router and 8 experts +
        # 8.4 M dense attention) MACs and the head over ONE row; the head
        # over the bucket was 0.42 TFLOP more, 64 dense experts a token
        # would be 2.4 TFLOP
        assert 0.29e12 < flops < 0.35e12
        _assert_one_row_of_logits(compiled, max_len, 50304)
        _assert_attention_is_blockwise(compiled.as_text(), 1, max_len)
        assert _program_sha1(compiled.as_text()) \
            == _PLAIN_KERNEL_PREFILLS["olmoe"]
        assert [str(s.dtype) for s in compiled.out_info[0]] \
            == ["float32", "bfloat16", "bfloat16", "float32"]
    else:
        assert [str(s.dtype) for s in compiled.out_info[0]] \
            == ["float32", "bfloat16", "bfloat16", "float32"]
        assert compiled.out_info[0][1].shape == (slots // 16, 16, 16 * 128)
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
    from mxnet_tpu.ops.attention import pool_shape

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
            inputs[name] = (pool_shape(*shape, slots, page), "bfloat16") \
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
        assert compiled.out_info[0][11].shape == (slots // page, page, 512)
        # the attention layer's read is the kernel (no scores over the pool:
        # 32 x 32 x 65,536 float32 were 268 MB); no second copy of any cache
        # buffer: the five layers' states and columns and the two pools are
        # all updated in place
        assert len(_paged_read_calls(hlo)) == 1 and "kv_mask" not in hlo
        assert mem.temp_size_in_bytes < 150 << 20
        assert mem.alias_size_in_bytes == 2 * pool * 2 + sum(
            lanes * math.prod(shape) * 4
            for _, kind, shape in cache if kind == "row")
        assert "slot_onehot" not in hlo
    else:
        # 2 x 512 tokens x (5 x 76.2 M + 60.8 M) MACs of matrices and the
        # chunked scan's products beside them; the head is over ONE row (over
        # the bucket it was 2 x 512 x 205.5 M = 0.21 TFLOP more)
        assert 0.44e12 < compiled.cost_analysis()["flops"] < 0.50e12
        _assert_one_row_of_logits(compiled, bucket, 100352)
        assert mem.temp_size_in_bytes < 400 << 20


_KANANA = dict(arch="deepseek_v3", vocab_size=128256, num_layers=3,
               num_heads=32, model_dim=2048, ffn_dim=6144, moe_ffn_dim=768,
               num_experts=128, num_experts_per_tok=6, num_shared_experts=2,
               first_dense_layers=1, qk_nope_head_dim=128,
               qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
               rope_theta=1e6, rms_eps=1e-6, routed_scaling_factor=2.448,
               norm_topk_prob=True, dtype="bfloat16")


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
    65,536) mask, which none of its reads looks at. Both programs run their
    expert layers in the form the operator's rule names and report the
    experts' load last."""
    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.ops.attention import pool_shape

    lanes, max_len, bucket, page, layers = 32, 2048, 1024, 16, 3
    slots = lanes * max_len
    cfg = _KANANA
    assert cfg["num_layers"] == layers
    weights = {n: (s, "bfloat16") for n, s in tf.param_shapes(**cfg).items()}
    cache = tf.decode_cache(**cfg)
    assert cache == [("kv_c_%d" % i, "pool", (1, 576)) for i in range(layers)]
    if program == "prefill":
        sym = tf.get_prefill_symbol(prefill_len=bucket, **cfg)
        inputs = {"data": ((1, bucket), "float32"),
                  "length": ((1, 1), "float32")}
        want = [((1, 128256), "float32")] \
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
    _assert_expert_layers(hlo, layers - 1,
                          bucket if program == "prefill" else lanes, 6, 128,
                          2048, 768)
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
        # a row of 576 is no whole tile of lanes: the pool stays head-major
        # and its read XLA's gather, no kernel
        assert pool_shape(1, 576, slots, page) == (1, slots, 576)
        assert not _paged_read_calls(hlo)
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
        _assert_one_row_of_logits(compiled, bucket, 128256)
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
    page-major (8,192, 16, 512) pools an attention layer, in layer order,
    each back in the type it went in; the step's two attention layers read
    through the KERNEL that walks the page table, as ``pool_read_form`` says
    of (64, 32, 64) queries over two such pools, so no (64, 131,072) mask is
    built, nothing is gathered and no pool is re-laid; the conv operators
    are in the program under their nodes' names; both graphs keep the
    grouped matmul and report the experts' load last; and everything fits
    beside the weights: the step's temporaries under 0.3 GB (0.7 with the
    gathered copies), the admission's under 0.1."""
    from types import SimpleNamespace

    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.ops.attention import pool_read_form, pool_shape
    from mxnet_tpu.serving.kv_decode import _AdmitScatter

    lanes, max_len, bucket, page = 64, 2048, 1024, 16
    slots, cfg = lanes * max_len, _LFM2
    weights = {n: (s, "bfloat16") for n, s in tf.param_shapes(**cfg).items()}
    cache = tf.decode_cache(**cfg)
    assert [kind for _, kind, _ in cache] == \
        ["row", "row", "pool", "pool", "row", "row", "row", "pool", "pool",
         "row", "row", "row"]
    bound = pool_shape(8, 64, slots, page)
    assert bound == (slots // page, page, 512)
    buffers = [(bound, "bfloat16") if kind == "pool"
               else ((lanes, 2, 2048), "float32") for _, kind, _ in cache]
    if program == "admit_scatter":
        prog = _AdmitScatter(SimpleNamespace(
            _cache=cache, page_size=page, prefill_len=bucket))
        spec = lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, jnp.dtype(dtype), sharding=v5e)
        new = [((1, 8, bucket, 64), "bfloat16") if kind == "pool"
               else ((1, 2, 2048), "float32") for _, kind, _ in cache]
        compiled = prog._fn.lower(
            tuple(spec(*b) for b in buffers), tuple(spec(*n) for n in new),
            spec((bucket // page,), "int32"), spec((2,), "int32"),
        ).compile()
        mem = compiled.memory_analysis()
        # every buffer of the cache is updated in place, pools and rows
        assert mem.alias_size_in_bytes == 4 * 8 * slots * 64 * 2 \
            + 8 * lanes * 2 * 2048 * 4
        # the prompt's rows turned a token's heads side by side: 4 x 1 MB
        assert mem.temp_size_in_bytes < 8 << 20
        _assert_no_pool_sized_copy(compiled.as_text(), 8 * slots * 64)
        return
    if program == "prefill":
        sym = tf.get_prefill_symbol(prefill_len=bucket, **cfg)
        inputs = {"data": ((1, bucket), "float32"),
                  "length": ((1, 1), "float32")}
        want = [((1, 65536), "float32")] \
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
    _assert_expert_layers(hlo, 8, bucket if program == "prefill" else lanes,
                          4, 64, 2048, 1536)
    for i in (0, 1, 3, 4, 5, 7, 8, 9):
        assert "layer%d_conv_core/" % i in hlo
    mem = compiled.memory_analysis()
    if program == "prefill":
        # an attention layer's float32 scores, 32 x 1,024 x 1,024 = 134 MB,
        # and the experts' rows: temporaries of their own since no 268 MB
        # block of logits is there for them to be laid into
        _assert_one_row_of_logits(compiled, bucket, 65536)
        assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 256 << 20
        return
    # the rule, asked as the operator asks it
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    pool = struct(bound, "bfloat16")
    assert pool_read_form(
        struct((lanes, 32, 64), "bfloat16"), pool, pool,
        struct((lanes, max_len // page), "float32"), page) == "kernel"
    # one call of the kernel an attention layer; no read's mask is built,
    # nothing is gathered for a read and no pool is re-laid
    calls = _paged_read_calls(hlo)
    assert len(calls) == 2
    for i in (2, 6):
        assert sum("layer%d_att/" % i in line for line in calls) == 1
    assert "kv_mask" not in hlo and "slot_onehot" not in hlo
    _assert_no_pool_sized_copy(hlo, 8 * slots * 64)
    # the step updates every row and pool in place
    assert mem.alias_size_in_bytes == 4 * 8 * slots * 64 * 2 \
        + 8 * lanes * 2 * 2048 * 4
    assert mem.temp_size_in_bytes < 300 << 20


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
    a page-major key pool (16,384, 16, 768) beside a value pool (16,384, 16,
    512) for a full layer and two rings (32, 8, 128, .) for a window layer,
    in layer order, each back in the type it went in and updated in place; a
    window layer's prefill scores a BAND (nothing of 2,048 x 2,048 a window
    head); the step's two full layers read through the KERNEL that walks the
    page table, as ``pool_read_form`` says of a key and a value pool of
    different width, and re-lay no pool; both graphs keep the grouped matmul
    over the 16 held experts and report the load of all 256 last; and
    everything fits beside 6.9 GB of weights."""
    from types import SimpleNamespace

    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.ops.attention import pool_read_form, pool_shape
    from mxnet_tpu.serving.kv_decode import _AdmitScatter

    lanes, max_len, bucket, page = 32, 8192, 2048, 16
    slots, cfg = lanes * max_len, _MIMO
    shapes = tf.param_shapes(**cfg)
    assert sum(math.prod(s) for s in shapes.values()) == 3_429_955_392
    weights = {n: (s, "bfloat16") for n, s in shapes.items()}
    cache = tf.decode_cache(**cfg)
    assert [kind for _, kind, _ in cache] == \
        ["pool"] * 2 + ["ring"] * 8 + ["pool"] * 2 + ["ring"] * 2
    buffers = [(pool_shape(*shape, slots, page) if kind == "pool"
                else (lanes,) + shape, "bfloat16")
               for _, kind, shape in cache]
    assert [b for b, _ in buffers[:2]] == [(slots // page, page, 768),
                                           (slots // page, page, 512)]
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
        compiled = prog._fn.lower(
            tuple(spec(*b) for b in buffers),
            tuple(spec(*n) for n in exported),
            spec((bucket // page,), "int32"), spec((2,), "int32"),
        ).compile()
        mem = compiled.memory_analysis()
        # every buffer of the cache is updated in place, pools and rings
        assert mem.alias_size_in_bytes == cache_bytes
        # the prompt's rows turned a token's heads side by side: 10.5 MB
        assert mem.temp_size_in_bytes < 24 << 20
        _assert_no_pool_sized_copy(compiled.as_text(), 4 * slots * 128)
        return
    if program == "prefill":
        sym = tf.get_prefill_symbol(prefill_len=bucket, **cfg)
        inputs = {"data": ((1, bucket), "float32"),
                  "length": ((1, 1), "float32")}
        want = [((1, 19072), "float32")] + exported \
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
    _assert_expert_layers(hlo, 6, bucket if program == "prefill" else lanes,
                          8, 16, 4096, 2048, routed=256)
    mem = compiled.memory_analysis()
    found = [(math.prod(int(d) for d in dims.split(",") if d), op)
             for _n, dims, op, _a in _INSTRUCTION.findall(hlo)
             if op != "parameter"]
    if program == "prefill":
        # the full layers' float32 scores, 64 x 2,048 x 2,048, are the
        # largest thing made; a window layer's are an eighth of that
        assert max(n for n, _ in found) <= 64 * bucket * bucket
        _assert_one_row_of_logits(compiled, bucket, 19072)
        # PR 58's program, every assignment's rows in it: 710,681,088
        assert mem.temp_size_in_bytes <= 710_681_088
        # its window layers carry a sink: XLA's band, the parent's program
        assert not _window_attention_calls(hlo)
        assert _step_sha1(hlo) == _WINDOW_BYPASSED["mimo_v2_flash prefill"]
        return
    assert _step_sha1(hlo) == _HELD_SHARE_STEPS["mimo_v2_flash"]
    # the rule, asked as the operator asks it: pools of different width
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    assert pool_read_form(
        struct((lanes, 64, 192), "bfloat16"),
        struct(buffers[0][0], "bfloat16"), struct(buffers[1][0], "bfloat16"),
        struct((lanes, max_len // page), "float32"), page) == "kernel"
    calls = _paged_read_calls(hlo)
    assert len(calls) == 2
    for i in (0, 5):
        assert sum("layer%d_att/" % i in line for line in calls) == 1
    # no read's mask is built and no pool is re-laid (the value pool is the
    # smaller: 134 M elements)
    assert "kv_mask" not in hlo and "slot_onehot" not in hlo
    _assert_no_pool_sized_copy(hlo, 4 * slots * 128)
    # the step updates every pool and ring in place
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < 1 << 30


_LAGUNA = dict(arch="laguna", vocab_size=25088, num_layers=6, num_heads=48,
               swa_num_heads=72, num_kv_heads=8, head_dim=128, model_dim=3072,
               ffn_dim=12288, moe_ffn_dim=1024, num_experts=256,
               num_local_experts=64, local_expert_offset=0,
               num_experts_per_tok=10, num_shared_experts=1,
               first_dense_layers=1,
               layer_types=["full_attention"] + ["sliding_attention"] * 3
               + ["full_attention", "sliding_attention"],
               sliding_window=512, rotary_dim=64, rope_theta=5e5,
               swa_rope_theta=1e4, yarn_factor=128.0,
               yarn_original_max_position=8192, yarn_beta_fast=32.0,
               yarn_beta_slow=1.0, attention_factor=1.4852030263919618,
               rms_eps=1e-6, routed_scaling_factor=2.5, norm_topk_prob=True,
               dtype="bfloat16")


@pytest.mark.parametrize("program", ["prefill", "decode", "admit_scatter"])
def test_laguna_serving_programs_compile_for_the_chip(v5e, program):
    """The three programs ``PagedKVDecoder(arch="laguna")`` runs, lowered for
    the v5e at Laguna-S-2.1's published widths, the benchmark's cut (layers
    0-5, 64 of 256 experts, 25,088 rows of the vocabulary: 3,679,364,864
    parameters in bfloat16) and its serving sizes (32 lanes x 9,216 slots,
    an 8,192 bucket). What has to hold on the chip: the cache is a page-major
    pool pair (18,432, 16, 1,024) for a full layer and two rings (32, 8, 512,
    128) for a window layer, in layer order, each updated in place; a full
    layer's admission is ONE call of the blockwise kernel at a group of 6
    (no float32 buffer of 48 x 8,192 x 8,192), a window layer's ONE call of
    the same kernel under the window at a group of 9 (no band: no float32
    copy of its operands, no score written); the step's two full layers read
    through the
    kernel that walks the page table at a (48, 1,024) block-diagonal query
    and re-lay no pool; both graphs keep the grouped matmul over the 64 held
    experts and report the load of all 256 last; and everything fits beside
    7.4 GB of weights."""
    from types import SimpleNamespace

    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.ops.attention import (attention_form, pool_read_form,
                                         pool_shape)
    from mxnet_tpu.serving.kv_decode import _AdmitScatter

    lanes, max_len, bucket, page = 32, 9216, 8192, 16
    slots, cfg = lanes * max_len, _LAGUNA
    shapes = tf.param_shapes(**cfg)
    assert sum(math.prod(s) for s in shapes.values()) == 3_679_364_864
    weights = {n: (s, "bfloat16") for n, s in shapes.items()}
    cache = tf.decode_cache(**cfg)
    assert [kind for _, kind, _ in cache] == \
        ["pool"] * 2 + ["ring"] * 6 + ["pool"] * 2 + ["ring"] * 2
    buffers = [(pool_shape(*shape, slots, page) if kind == "pool"
                else (lanes,) + shape, "bfloat16")
               for _, kind, shape in cache]
    assert buffers[0] == buffers[1] == ((slots // page, page, 1024),
                                        "bfloat16")
    assert buffers[2][0] == (lanes, 8, 512, 128)
    cache_bytes = sum(2 * math.prod(shape) for shape, _ in buffers)
    # the two full layers' pools 2.42 GB, the four window layers' rings 0.27
    assert cache_bytes == 2 * slots * 4096 + 4 * lanes * (2 << 20)
    exported = [((1, shape[0], bucket, shape[-1]), "bfloat16")
                for _, _, shape in cache]
    if program == "admit_scatter":
        prog = _AdmitScatter(SimpleNamespace(
            _cache=cache, page_size=page, prefill_len=bucket))
        spec = lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, jnp.dtype(dtype), sharding=v5e)
        compiled = prog._fn.lower(
            tuple(spec(*b) for b in buffers),
            tuple(spec(*n) for n in exported),
            spec((bucket // page,), "int32"), spec((2,), "int32"),
        ).compile()
        mem = compiled.memory_analysis()
        # every buffer of the cache is updated in place, pools and rings
        assert mem.alias_size_in_bytes == cache_bytes
        # the prompt's rows turned a token's heads side by side: 16 MB
        assert mem.temp_size_in_bytes < 32 << 20
        _assert_no_pool_sized_copy(compiled.as_text(), slots * 1024)
        return
    if program == "prefill":
        sym = tf.get_prefill_symbol(prefill_len=bucket, **cfg)
        inputs = {"data": ((1, bucket), "float32"),
                  "length": ((1, 1), "float32")}
        want = [((1, 25088), "float32")] + exported \
            + [((5, 256), "float32")]
    else:
        sym = tf.get_decode_symbol(max_len=slots, page_size=page, **cfg)
        inputs = {"data": ((lanes, 1), "float32"),
                  "pos_idx": ((lanes, 1), "float32"),
                  "write_slot": ((lanes, 1), "float32"),
                  "page_table": ((lanes, max_len // page), "float32")}
        inputs.update({name: b for (name, _, _), b in zip(cache, buffers)})
        want = [((lanes, 25088), "float32")] + buffers \
            + [((lanes,), "float32"), ((5, 256), "float32")]
    compiled = _compile_program(
        v5e, sym, {**weights, **inputs},
        donated=[name for name, _, _ in cache] if program == "decode" else ())
    assert [(s.shape, str(s.dtype)) for s in compiled.out_info[0]] == want
    hlo = compiled.as_text()
    _assert_expert_layers(hlo, 5, bucket if program == "prefill" else lanes,
                          10, 64, 3072, 1024, routed=256)
    mem = compiled.memory_analysis()
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    if program == "prefill":
        # the rules, asked as the operator asks them
        heads = lambda n: struct((1, n, bucket, 128), "bfloat16")
        assert attention_form(heads(48), heads(8), heads(8), True) == "kernel"
        assert attention_form(heads(72), heads(8), heads(8), True,
                              512) == "window_kernel"
        assert pa.blocks(bucket, bucket, 9, 128, 128, jnp.bfloat16,
                         window=512) == (64, 1024)
        _assert_attention_is_blockwise(hlo, 2, bucket)
        # a window layer's admission is ONE call of the same kernel under
        # the window, 72 heads folded nine to a key/value head: no band, so
        # no loop but an expert layer's (the turns past the first over the
        # held rows' chunks), and no float32 copy of a window layer's
        # keys and values in the band's blocks, let alone its 72 x 8,192 x
        # 1,024 scores
        calls = _window_attention_calls(hlo)
        assert len(calls) == 4
        for i in (1, 2, 3, 5):
            assert sum("layer%d_att/" % i in line for line in calls) == 1
        assert hlo.count(" while(") == 5
        made = [[int(d) for d in dims.split(",") if d]
                for dims in re.findall(r"f32\[([\d,]+)\]", hlo)]
        assert max(math.prod(d) for d in made) < 72 * bucket * 1024
        # (the band's blocks of 512: float32 operands (.., 512, 128) and
        # scores (.., 512, 1,024))
        assert not [d for d in made if d[-2:] in ([512, 128], [512, 1024])]
        _assert_one_row_of_logits(compiled, bucket, 25088)
        # PR 58's program, every assignment's rows in it: 3,344,669,696;
        # with the weights' 7.36 GB and the cache's 2.68, 13.4 of 16
        assert mem.temp_size_in_bytes <= 3_344_669_696
        return
    assert _step_sha1(hlo) == _HELD_SHARE_STEPS["laguna"]
    assert pool_read_form(
        struct((lanes, 48, 128), "bfloat16"),
        struct(buffers[0][0], "bfloat16"), struct(buffers[1][0], "bfloat16"),
        struct((lanes, max_len // page), "float32"), page) == "kernel"
    calls = _paged_read_calls(hlo)
    assert len(calls) == 2
    for i in (0, 4):
        assert sum("layer%d_att/" % i in line for line in calls) == 1
    _assert_the_block_the_rule_names(hlo, max_len // page, page, 1024,
                                     "bfloat16")
    _assert_one_write_a_node(hlo, ["layer0_kvupd/", "layer4_kvupd/"])
    assert "kv_mask" not in hlo and "slot_onehot" not in hlo
    _assert_no_pool_sized_copy(hlo, slots * 1024)
    # the step updates every pool and ring in place
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < 64 << 20


def _program_alone(hlo):
    """A compiled program's text without what names the source it was
    traced from: the module's name (the graph's last node, numbered as it
    was built), the tables of files, functions and locations in front of
    the computations and every instruction's ``metadata={...}``."""
    blocks = [b for b in hlo.split("\n\n") if b.split("\n", 1)[0] not in (
        "FileNames", "FunctionNames", "FileLocations", "StackFrames")]
    return re.sub(r",? metadata=\{[^{}]*\}|^HloModule [^,]+", "",
                  "\n\n".join(blocks))


def _program_sha1(hlo):
    """sha1 of a compiled program's text as ``_program_alone`` leaves it,
    with every Mosaic kernel's serialized body (MLIR bytecode, which carries
    the file and line of each operation it was traced from) replaced by its
    assembly WITHOUT those locations: two trees whose programs are equal
    instruction for instruction answer the same, wherever their lines sit."""
    import hashlib

    def assembly(found):
        return '"body":%r' % _kernel_assembly(found.group(1))

    text = re.sub(r'"body":"([A-Za-z0-9+/=]+)"', assembly,
                  _program_alone(hlo))
    return hashlib.sha1(text.encode()).hexdigest()


# the prefills of two cells whose attention is the PLAIN causal kernel, as
# the tests above compile them, at PR 52's tree (6ce9d69): what a change to
# ``ops/pallas_attention.py`` that teaches the kernel something a plain call
# does not use (PR 53: a selection's mask) must leave them. A PR that moves
# these programs on purpose writes its own two lines here.
_PLAIN_KERNEL_PREFILLS = {
    "olmoe": "6d9ffceb605f56f469f17e0bf2cd4633dc979432",
    "nemotron_h": "84307b1188cbe3ab689ddf67c8a18f67865b82b5",
}


def _step_sha1(hlo):
    """``_program_sha1`` with the kernels' calls named without their number:
    a Pallas call's instruction is numbered by how many calls of its name
    the PROCESS lowered before it (``%paged_read.6`` alone, ``.8`` behind
    two other cells' steps), which says nothing of the program."""
    return _program_sha1(re.sub(
        r"%(paged_read|grouped_matmul\w*?|flash_attention)\.\d+", r"%\1",
        hlo))


# the DECODE programs of the four cells whose expert layers hold a share, as
# the tests above compile them, at PR 58's tree (21789b6): a step's 256 to
# 384 assignment rows keep a row each (``moe.held_rows_chunk`` names no
# chunk there), so a change to how an ADMISSION moves its held rows (PR 59)
# must leave them instruction for instruction. A PR that moves these
# programs on purpose writes its own lines here.
_HELD_SHARE_STEPS = {
    "mimo_v2_flash": "353027a38d6939d394960d77a5963f8399020b56",
    "dots3_note": "e4bd38401d088c717ca0415e0f412a51f74542f1",
    "laguna": "f90cac47c167857d4fd32a1bccc3a1451b23ce69",
    "nemotron_h": "823ea1fae773a557dc5e121d4abea8eb73defcf7",
}


# what the window inside the blockwise kernel (PR 60) must leave
# instruction for instruction, as the tests above compile them, at PR 59's
# tree (e2c981b): mimo's PREFILL, whose window layers carry a sink and stay
# XLA's band, and phi4flash's DECODE program (a step reads rings, never
# ``MultiHeadAttention`` under a window; mimo's, dots3's and laguna's steps
# are ``_HELD_SHARE_STEPS``'). A PR that moves these programs on purpose
# writes its own lines here.
_WINDOW_BYPASSED = {
    "mimo_v2_flash prefill": "8cced02a9c8063ce8ae67d5ccff6670190335341",
    "phi4flash decode": "158c0fee5e91eddd7ed8c558214114c106b6a33c",
}


# cell -> (the builder's sizes, lanes, slots a lane, weights' type, attention
# layers, (key/value heads, width))
_NARROW_POOL_CELLS = {
    "transformer-base": (dict(vocab_size=32000, num_layers=2, num_heads=8,
                              model_dim=512, ffn_dim=2048, pos_len=1024),
                         64, 1024, "float32", 2, (8, 64)),
    "olmoe-1b-7b": (_OLMOE, 8, 2048, "bfloat16", 1, (16, 128)),
    "granite-4.0-h-micro": (_GRANITE, 32, 2048, "bfloat16", 1, (8, 64)),
}


@pytest.mark.parametrize("cell", list(_NARROW_POOL_CELLS))
def test_a_pool_of_narrow_heads_reads_through_the_kernel(v5e, monkeypatch,
                                                         cell):
    """The decode programs of the three configurations whose pools hold 64-
    and 128-wide heads, at their cells' serving sizes. (Until the pools were
    page-major these kept the whole-pool read: the table changed nothing the
    chip ran.) Handing the read its page table now IS the program: one call
    of the kernel an attention layer, no mask over the pool's slots, no
    pool-sized copy, the whole cache updated in place; the graph that hands
    ``KVPoolAttention`` a mask and nothing else (a chunk's) scores the whole
    pool over the same page-major buffers, and XLA counts more bytes for
    it than for the kernel's program."""
    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.ops.attention import pool_shape

    cfg, lanes, max_len, dtype, att_layers, (hkv, d) = \
        _NARROW_POOL_CELLS[cell]
    slots, page = lanes * max_len, 16
    bound = pool_shape(hkv, d, slots, page)
    assert bound == (slots // page, page, hkv * d)

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
        cache = tf.decode_cache(**cfg)
        for name, kind, shape in cache:
            args[name] = (pool_shape(*shape, slots, page), dtype) \
                if kind == "pool" else ((lanes,) + tuple(shape), "float32")
        donated = [name for name, _, _ in cache]
    else:
        donated = ["kv_%s_%d" % (t, i) for i in range(cfg["num_layers"])
                   for t in "kv"]
        sym = build(False)
        shapes, _, _ = sym.infer_shape(**{n: bound for n in donated}, **{
            n: s for n, (s, _) in args.items()})
        args = {n: (s, dtype) for n, s in zip(sym.list_arguments(), shapes)}
    with_table, mask_alone = build(False), build(True)
    operands = [[len(n.inputs) for n in sym._topo()
                 if n.op == "_contrib_KVPoolAttention"]
                for sym in (with_table, mask_alone)]
    assert len(operands[0]) == att_layers and set(operands[0]) == {7}
    assert len(operands[1]) == att_layers and set(operands[1]) == {4}
    kernel, whole = (_compile_program(v5e, sym, args, donated=donated)
                     for sym in (with_table, mask_alone))
    hlo = kernel.as_text()
    assert len(_paged_read_calls(hlo)) == att_layers
    assert "kv_mask" not in hlo
    _assert_no_pool_sized_copy(hlo, hkv * slots * d)
    itemsize = jnp.dtype(dtype).itemsize
    cache_bytes = sum(
        math.prod(args[n][0]) * jnp.dtype(args[n][1]).itemsize
        for n in donated)
    assert cache_bytes >= 2 * att_layers * hkv * slots * d * itemsize
    assert kernel.memory_analysis().alias_size_in_bytes == cache_bytes
    assert not _paged_read_calls(whole.as_text())
    assert "kv_mask" in whole.as_text()
    assert kernel.cost_analysis()["bytes accessed"] \
        < whole.cost_analysis()["bytes accessed"]


_PHI4 = dict(arch="phi4flash", vocab_size=200064, num_layers=32, num_heads=40,
             num_kv_heads=20, head_dim=64, model_dim=2560, ffn_dim=10240,
             sliding_window=512, mb_per_layer=2, mamba_state=16, mamba_conv=4,
             mamba_expand=2, mamba_dt_rank=160, dtype="bfloat16")


@pytest.mark.parametrize("program", ["prefill", "decode", "admit_scatter"])
def test_phi4flash_serving_programs_compile_for_the_chip(v5e, program):
    """The three programs ``PagedKVDecoder(arch="phi4flash")`` runs, lowered
    for the v5e at Phi-4-mini-flash-reasoning's published sizes, whole
    (3,852,562,944 parameters in bfloat16) and the cell's serving sizes (64
    lanes x 8,192 slots, a 2,048 bucket). What has to hold on the chip: the
    cache is 18 float32 rows, 16 rings (64, 10, 512, 128) and ONE page-major
    pool pair (32,768, 16, 1,280), in layer order, each back in the type it
    went in and updated in place; the step reads that one pair through the
    KERNEL that walks the page table EIGHT times (layer 17 and the seven
    cross layers, 40 query heads over 10 key/value heads of 128) and writes
    it once; the prefill's logits are ONE row, its nine scans are loops that
    never make a state history, its window layers run the blockwise kernel
    under the window (no band of scores); and
    everything fits beside 7.7 GB of weights: 11.96 GB of arguments to a
    step, as the configuration's arithmetic says."""
    from types import SimpleNamespace

    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.ops.attention import pool_read_form, pool_shape
    from mxnet_tpu.serving.kv_decode import _AdmitScatter

    lanes, max_len, bucket, page = 64, 8192, 2048, 16
    slots, cfg = lanes * max_len, _PHI4
    shapes = tf.param_shapes(**cfg)
    assert sum(math.prod(s) for s in shapes.values()) == 3_852_562_944
    weights = {n: (s, "bfloat16") for n, s in shapes.items()}
    cache = tf.decode_cache(**cfg)
    kinds = [kind for _, kind, _ in cache]
    assert kinds == (["row"] * 2 + ["ring"] * 2) * 8 + ["row"] * 2 \
        + ["pool"] * 2
    buffers = [(pool_shape(*shape, slots, page) if kind == "pool"
                else (lanes,) + shape,
                "float32" if kind == "row" else "bfloat16")
               for _, kind, shape in cache]
    assert buffers[-1] == ((slots // page, page, 1280), "bfloat16")
    cache_bytes = sum(math.prod(shape) * (4 if t == "float32" else 2)
                      for shape, t in buffers)
    # the pool 2.68 GB, the rings 1.34, the rows 0.22
    assert cache_bytes == slots * 5120 + 16 * lanes * 10 * 512 * 128 * 2 \
        + 9 * lanes * (16 + 3) * 5120 * 4
    exported = [((1,) + shape, "float32") if kind == "row"
                else ((1, shape[0], bucket, shape[-1]), "bfloat16")
                for _, kind, shape in cache]
    if program == "admit_scatter":
        prog = _AdmitScatter(SimpleNamespace(
            _cache=cache, page_size=page, prefill_len=bucket))
        spec = lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, jnp.dtype(dtype), sharding=v5e)
        compiled = prog._fn.lower(
            tuple(spec(*b) for b in buffers),
            tuple(spec(*n) for n in exported),
            spec((bucket // page,), "int32"), spec((2,), "int32"),
        ).compile()
        mem = compiled.memory_analysis()
        # every buffer of the cache is updated in place: rows, rings, pool
        assert mem.alias_size_in_bytes == cache_bytes
        assert mem.temp_size_in_bytes < 24 << 20
        _assert_no_pool_sized_copy(compiled.as_text(), 10 * slots * 128)
        return
    if program == "prefill":
        sym = tf.get_prefill_symbol(prefill_len=bucket, **cfg)
        inputs = {"data": ((1, bucket), "float32"),
                  "length": ((1, 1), "float32")}
        want = [((1, 200064), "float32")] + exported
    else:
        sym = tf.get_decode_symbol(max_len=slots, page_size=page, **cfg)
        inputs = {"data": ((lanes, 1), "float32"),
                  "pos_idx": ((lanes, 1), "float32"),
                  "write_slot": ((lanes, 1), "float32"),
                  "page_table": ((lanes, max_len // page), "float32")}
        inputs.update({name: b for (name, _, _), b in zip(cache, buffers)})
        want = [((lanes, 200064), "float32")] + buffers \
            + [((lanes,), "float32")]
    compiled = _compile_program(
        v5e, sym, {**weights, **inputs},
        donated=[name for name, _, _ in cache] if program == "decode" else ())
    assert [(s.shape, str(s.dtype)) for s in compiled.out_info[0]] == want
    hlo = compiled.as_text()
    mem = compiled.memory_analysis()
    found = [(math.prod(int(d) for d in dims.split(",") if d), op)
             for _n, dims, op, _a in _INSTRUCTION.findall(hlo)
             if op != "parameter"]
    if program == "prefill":
        # nine scans, each a loop; a window layer is ONE call of the
        # blockwise kernel under the window (40 query heads over 10 of 128),
        # so nothing made is as large as a band of float32 scores, 40 x
        # 2,048 x 1,024, was: an eighth of what a state history (2,048 x 16
        # x 5,120) or full scores would be
        assert hlo.count(" while(") == 9
        calls = _window_attention_calls(hlo)
        assert len(calls) == 8 and not _flash_attention_calls(hlo)
        for i in range(1, 16, 2):
            assert sum("layer%d_self_att/" % i in line for line in calls) == 1
        # (the tied head over ONE row is a multiply and a sum inside a
        # fusion: the table's own size is listed and never made)
        assert max(n for n, _ in found if n != 200064 * 2560) \
            < 40 * bucket * 1024
        assert not _paged_read_calls(hlo)
        assert mem.temp_size_in_bytes < 1 << 30
        return
    # the rule, asked as the operator asks it
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    assert pool_read_form(
        struct((lanes, 40, 128), "bfloat16"),
        struct(buffers[-2][0], "bfloat16"), struct(buffers[-1][0], "bfloat16"),
        struct((lanes, max_len // page), "float32"), page) == "kernel"
    calls = _paged_read_calls(hlo)
    assert len(calls) == 8
    for i, tag in [(17, "self")] + [(i, "cross") for i in range(19, 32, 2)]:
        assert sum("layer%d_%s_att/" % (i, tag) in line for line in calls) == 1
    # ONE write of the pool pair: one scatter a pool, every lane's row in,
    # and no loop in the program
    _assert_one_write_a_node(hlo, ["layer17_kvupd/"])
    assert " while(" not in hlo
    # no read's mask is built and the pool is not re-laid
    assert "kv_mask" not in hlo and "slot_onehot" not in hlo
    _assert_no_pool_sized_copy(hlo, 10 * slots * 128)
    # the step updates every row, ring and the pool in place, and holds
    # weights and cache once: 11.96 GB
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.argument_size_in_bytes < 11_970_000_000
    assert mem.temp_size_in_bytes < 128 << 20
    assert _step_sha1(hlo) == _WINDOW_BYPASSED["phi4flash decode"]


def test_the_page_walk_at_forty_query_heads_over_ten_of_128(v5e):
    """The kernel alone at the shape the padded-query form of differential
    attention gives it: 64 rows of 40 query heads of 128 over page-major
    pools of 10 key/value heads of 128 (a row of 1,280: ten whole tiles),
    four query heads a key/value head, ``scale`` 1/8 of the 64-wide heads
    the zeros pad."""
    from mxnet_tpu.ops import pallas_paged_read as kernel
    from mxnet_tpu.ops.attention import pool_shape

    lanes, max_len, page = 64, 8192, 16
    pool = (pool_shape(10, 128, lanes * max_len, page), "bfloat16")
    struct = lambda sd: jax.ShapeDtypeStruct(sd[0], jnp.dtype(sd[1]))
    query = ((lanes, 40, 128), "bfloat16")
    assert kernel.supported(struct(query), struct(pool), struct(pool))
    fn = lambda q, k, v, table, context: kernel.paged_read(
        q, k, v, table, context, scale=0.125, interpret=False)
    _compile(v5e, fn, query, pool, pool,
             ((lanes, max_len // page), "int32"), ((lanes,), "int32"))


_NEMOTRON = dict(
    arch="nemotron_h", vocab_size=65536, num_layers=13, num_heads=32,
    num_kv_heads=2, head_dim=128, model_dim=2688, ffn_dim=1856,
    layer_types=[{"M": "mamba", "E": "moe", "*": "attention"}[c]
                 for c in "MEMEM*EMEMEM*"],
    mamba_heads=64, mamba_head_dim=64, mamba_state=128, mamba_groups=8,
    mamba_conv=4, mamba_chunk=128, moe_ffn_dim=1856, shared_ffn_dim=3712,
    num_experts=128, num_experts_per_tok=6, num_local_experts=64,
    local_expert_offset=0, routed_scaling_factor=2.5, norm_topk_prob=True,
    dtype="bfloat16")


@pytest.mark.parametrize("program", ["prefill", "decode", "kernel"])
def test_nemotron_h_serving_programs_compile_for_the_chip(v5e, program):
    """The two graphs ``PagedKVDecoder(arch="nemotron_h")`` runs, lowered for
    the v5e at NVIDIA-Nemotron-3-Nano-30B-A3B's published widths, the cell's
    cut (blocks 0-12, 64 of 128 experts held, half the vocabulary) and its
    serving sizes (64 lanes x 8,192 slots, a 2,048 bucket), and the
    1,856-wide UNGATED expert layer's kernel alone at the step's rows. What
    has to hold on the chip: the five expert blocks run the Pallas kernel,
    TWO calls a block (``grouped_matmul_relu2`` over the ONE ``up`` stack
    stored 1,920 wide, then ``grouped_matmul``), with the ring ``tiles``
    names for an ungated first call, and no ``ragged-dot``; the two
    attention blocks read their page-major pools (2 heads of 128: a row of
    256) through the kernel that walks the page table at 16 queries a
    key/value head; the cache is twelve float32 rows and two pool pairs,
    updated in place; an expert block keeps nothing; and a step's arguments
    are the 7.96 GB of stored weights and the 1.91 GB of cache once."""
    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.ops import pallas_grouped_matmul as kernel
    from mxnet_tpu.ops.attention import pool_shape

    lanes, max_len, bucket, page = 64, 8192, 2048, 16
    slots, cfg = lanes * max_len, _NEMOTRON
    struct = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    stacks = struct(64, 2688, 1920), struct(64, 1920, 2688)
    for tokens, tiles in ((lanes, (32, 1920, 2688, 3)),
                          (bucket, (128, 1920, 2688, 2))):
        rows = struct(tokens * 6, 2688)
        assert kernel.moe_form(rows, *stacks) == "kernel"
        assert kernel.layer_tiles(rows, stacks[0], 128, gated=False) == tiles
    # the published width itself is no whole lane tiles: XLA's form
    assert kernel.moe_form(struct(384, 2688), struct(64, 2688, 1856),
                           struct(64, 1856, 2688)) == "ragged_dot"
    if program == "kernel":
        _compile(v5e, lambda rows, up, down, sizes: kernel.expert_ffn(
            rows, None, up, down, sizes, 128),
            ((384, 2688), "bfloat16"), ((64, 2688, 1920), "bfloat16"),
            ((64, 1920, 2688), "bfloat16"), ((64,), "int32"))
        return
    weights = {n: (s, "bfloat16") for n, s in tf.param_shapes(**cfg).items()}
    cache = tf.decode_cache(**cfg)
    assert [kind for _, kind, _ in cache] == \
        ["row"] * 6 + ["pool"] * 2 + ["row"] * 6 + ["pool"] * 2
    if program == "prefill":
        sym = tf.get_prefill_symbol(prefill_len=bucket, **cfg)
        inputs = {"data": ((1, bucket), "float32"),
                  "length": ((1, 1), "float32")}
    else:
        sym = tf.get_decode_symbol(max_len=slots, page_size=page, **cfg)
        inputs = {"data": ((lanes, 1), "float32"),
                  "pos_idx": ((lanes, 1), "float32"),
                  "write_slot": ((lanes, 1), "float32"),
                  "page_table": ((lanes, max_len // page), "float32")}
        for name, kind, shape in cache:
            inputs[name] = (pool_shape(*shape, slots, page), "bfloat16") \
                if kind == "pool" else ((lanes,) + tuple(shape), "float32")
    compiled = _compile_program(
        v5e, sym, {**weights, **inputs},
        donated=[name for name, _, _ in cache] if program == "decode" else ())
    types = ["float32" if kind == "row" else "bfloat16"
             for _, kind, _ in cache]
    assert [str(s.dtype) for s in compiled.out_info[0]] == \
        ["float32"] + types + ["float32"] * (1 if program == "prefill"
                                             else 2)    # (token,) moe_load
    assert compiled.out_info[0][-1].shape == (5, 128)       # moe_load
    hlo = compiled.as_text()
    calls = [line for line in hlo.splitlines() if " custom-call(" in line
             and 'custom_call_target="tpu_custom_call"' in line
             and "/grouped_matmul" in line]
    assert len(calls) == 10 and "ragged" not in _program_alone(hlo).lower()
    assert sum("/grouped_matmul_relu2/" in line for line in calls) == 5
    depth = 3 if program == "decode" else 2
    for line in calls:
        ring = depth * 2 * 2688 * 1920      # ONE matrix a slot, both calls
        (at, limit), (_, end) = (map(int, re.search(
            r'"%s":\[\{"memory_space":"1","offset":"(\d+)","size":"(\d+)"'
            % key, line).groups()) for key in (
                "scoped_memory_configs", "used_scoped_memory_configs"))
        assert ring <= end - at <= min(ring + (16 << 20), limit), \
            (ring, at, end, limit)
    mem = compiled.memory_analysis()
    if program == "prefill":
        _assert_one_row_of_logits(compiled, bucket, 65536)
        # 2 x 2,048 tokens x (6 x 38.7 M + 2 x 23.4 M + 5 x (20.3 M + 3
        # experts of 10.0 M)) MACs = 2.2 TFLOP, the chunked scans and the
        # attention beside them; 128 dense experts a token would be 27
        assert 2.5e12 < compiled.cost_analysis()["flops"] < 3.4e12
        assert mem.temp_size_in_bytes < 1 << 30
        _assert_attention_is_blockwise(hlo, 2, bucket)
        assert _program_sha1(hlo) == _PLAIN_KERNEL_PREFILLS["nemotron_h"]
        return
    assert _step_sha1(hlo) == _HELD_SHARE_STEPS["nemotron_h"]
    assert compiled.out_info[0][1].shape == (lanes, 64, 64, 128)
    assert compiled.out_info[0][7].shape == (slots // page, page, 256)
    assert len(_paged_read_calls(hlo)) == 2 and "kv_mask" not in hlo
    assert "slot_onehot" not in hlo
    _assert_no_pool_sized_copy(hlo, min(lanes * 64 * 64 * 128,
                                        2 * slots * 128))
    cache_bytes = 2 * 2 * slots * 256 * 2 + 6 * lanes * (
        64 * 64 * 128 + 3 * 6144) * 4
    assert mem.alias_size_in_bytes == cache_bytes == 1_907_359_744
    assert mem.argument_size_in_bytes < 9_990_000_000
    assert mem.temp_size_in_bytes < 128 << 20


_VASWANI = dict(vocab_size=32000, num_layers=2, num_heads=8, model_dim=512,
                ffn_dim=2048, pos_len=1024)
_DOTS3 = dict(
    arch="dots3_note", vocab_size=19008, num_layers=6, num_heads=128,
    model_dim=5120, ffn_dim=13824, moe_ffn_dim=1536, num_experts=256,
    num_local_experts=16, local_expert_offset=0, num_experts_per_tok=8,
    num_shared_experts=1, first_dense_layers=1,
    layer_types=["full_attention"] * 2 + ["sliding_attention"] * 3
    + ["full_attention"],
    q_lora_rank=1024, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, rope_theta=8e7, swa_num_heads=64,
    swa_q_lora_rank=1024, swa_kv_lora_rank=1024, swa_qk_nope_head_dim=192,
    swa_qk_rope_head_dim=64, swa_v_head_dim=128, swa_rope_theta=5e4,
    sliding_window=513, index_n_heads=64, index_head_dim=128,
    index_topk=2048, lora_rescale=True, rms_eps=1e-5,
    routed_scaling_factor=1.0, norm_topk_prob=True, dtype="bfloat16")


@pytest.mark.parametrize("program", ["prefill", "decode", "admit_scatter"])
def test_dots3_note_serving_programs_compile_for_the_chip(v5e, program):
    """The three programs ``PagedKVDecoder(arch="dots3_note")`` runs, lowered
    for the v5e at dots3-note-prev's published widths, the cell's cut (layers
    0-5, 16 of 256 experts, 19,008 rows of the vocabulary: 3,123,656,192
    parameters in bfloat16) and its serving sizes (32 lanes x 10,240 slots,
    an 8,192 bucket). What has to hold on the chip: a full layer keeps a
    head-major latent pool (1, 327,680, 576) beside a page-major index-key
    pool (20,480, 16, 128) and a float32 row of 2,048 kept positions, a
    window layer ONE ring (32, 1, 513, 1,088), in layer order, each back in
    the type it went in and updated in place; the prefill makes nothing of a
    head's 8,192 x 8,192 pairs (neither the index logits' 17 GB nor the
    scores'): a full layer's masked attention is one call of the blockwise
    kernel under the layer's int8 mask, and it fits beside the weights and
    the cache with room;
    both graphs run the five expert layers' grouped matmuls as the kernel
    over the 16 held experts; the step scores no mask over the pool."""
    from types import SimpleNamespace

    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.ops import attention
    from mxnet_tpu.ops.attention import pool_shape
    from mxnet_tpu.serving.kv_decode import _AdmitScatter

    lanes, max_len, bucket, page = 32, 10240, 8192, 16
    slots, cfg = lanes * max_len, _DOTS3
    shapes = tf.param_shapes(**cfg)
    assert sum(math.prod(s) for s in shapes.values()) == 3_123_656_192
    weights = {n: (s, "bfloat16") for n, s in shapes.items()}
    cache = tf.decode_cache(**cfg)
    assert [kind for _, kind, _ in cache] == \
        ["pool", "pool", "row"] * 2 + ["ring"] * 3 + ["pool", "pool", "row"]
    buffers = [(pool_shape(*shape, slots, page), "bfloat16") if kind == "pool"
               else ((lanes,) + shape, "float32" if kind == "row"
                     else "bfloat16") for _, kind, shape in cache]
    assert [b for b, _ in buffers[:3]] == [
        (1, slots, 576), (slots // page, page, 128), (lanes, 2048)]
    assert buffers[6] == ((lanes, 1, 513, 1088), "bfloat16")
    cache_bytes = sum(jnp.dtype(t).itemsize * math.prod(shape)
                      for shape, t in buffers)
    # latent pools 1.13 GB, index keys 0.25, rings 0.11, the kept rows 0.8 MB
    assert cache_bytes == 3 * slots * 704 * 2 + 3 * lanes * 513 * 1088 * 2 \
        + 3 * lanes * 2048 * 4
    # the chip keeps a ring's row of 1,088 as nine tiles of lanes, 1,152
    padding = 3 * lanes * 513 * 64 * 2
    exported = [((1, 2048), "float32") if kind == "row"
                else ((1, shape[0], bucket, shape[-1]), "bfloat16")
                for _, kind, shape in cache]
    if program == "admit_scatter":
        prog = _AdmitScatter(SimpleNamespace(
            _cache=cache, page_size=page, prefill_len=bucket))
        spec = lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, jnp.dtype(dtype), sharding=v5e)
        compiled = prog._fn.lower(
            tuple(spec(*b) for b in buffers),
            tuple(spec(*n) for n in exported),
            spec((bucket // page,), "int32"), spec((2,), "int32"),
        ).compile()
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == cache_bytes + padding
        assert mem.temp_size_in_bytes < 64 << 20
        return
    if program == "prefill":
        sym = tf.get_prefill_symbol(prefill_len=bucket, **cfg)
        inputs = {"data": ((1, bucket), "float32"),
                  "length": ((1, 1), "float32")}
        want = [((1, 19008), "float32")] + exported \
            + [((5, 256), "float32")]
    else:
        sym = tf.get_decode_symbol(max_len=slots, page_size=page, **cfg)
        inputs = {"data": ((lanes, 1), "float32"),
                  "pos_idx": ((lanes, 1), "float32"),
                  "write_slot": ((lanes, 1), "float32"),
                  "page_table": ((lanes, max_len // page), "float32")}
        inputs.update({name: b for (name, _, _), b in zip(cache, buffers)})
        want = [((lanes, 19008), "float32")] + buffers \
            + [((lanes,), "float32"), ((5, 256), "float32")]
    compiled = _compile_program(
        v5e, sym, {**weights, **inputs},
        donated=[name for name, _, _ in cache] if program == "decode" else ())
    assert [(s.shape, str(s.dtype)) for s in compiled.out_info[0]] == want
    hlo = compiled.as_text()
    _assert_expert_layers(hlo, 5, bucket if program == "prefill" else lanes,
                          8, 16, 5120, 1536, routed=256)
    mem = compiled.memory_analysis()
    if program == "prefill":
        found = [math.prod(int(d) for d in dims.split(",") if d)
                 for _n, dims, op, _a in _INSTRUCTION.findall(hlo)
                 if op != "parameter"]
        # nothing made is larger than the experts' 65,536 assignment rows of
        # 5,120 were until PR 59 (the held rows' chunk is 4,096 of them); a
        # block's float32 scores stay inside the operator's budget
        assert max(found) <= 8 * bucket * 5120
        assert attention._SCORE_BYTES // 4 < 8 * bucket * 5120
        # a full layer's masked attention is ONE call of the blockwise
        # kernel under the layer's mask, (1, 8192, 8192) int8 made a block of
        # 128 queries at a time, and nothing is a query block's float32
        # scores of 128 heads (``f32[1,128,1,128,<keys>]``)
        assert len(_flash_attention_calls(hlo)) == 3
        # a window layer's (64 heads, a key of 256 over a value of 128, a
        # window of 513 the bucket is no multiple of) ONE call of the same
        # kernel under the window
        calls = _window_attention_calls(hlo)
        assert len(calls) == 3
        dims = lambda kind: [[int(d) for d in found.split(",") if int(d) != 1]
                             for found in re.findall(kind + r"\[([\d,]+)\]",
                                                     hlo)]
        assert [bucket, bucket] in dims("s8")
        scores = [d for d in dims("f32") if d[:2] == [128, 128] and len(d) == 3]
        assert not scores, scores[:4]
        _assert_one_row_of_logits(compiled, bucket, 19008)
        # PR 58's program, every assignment's rows in it: 3,945,426,432;
        # under 15 GB beside 6.25 of weights and 1.50 of cache
        assert mem.temp_size_in_bytes <= 3_945_426_432
        return
    assert _step_sha1(hlo) == _HELD_SHARE_STEPS["dots3_note"]
    assert "kv_mask" not in hlo and "slot_onehot" not in hlo
    assert mem.alias_size_in_bytes == cache_bytes + padding
    assert mem.temp_size_in_bytes < 1 << 30


_ONE_INPUT_STEPS = {
    # arch: (sizes, lanes, slots a lane, reading nodes that are the kernel's,
    # copies and transposes of a pool's size: the head-major latent pool's
    # re-layout and its gathered frames', all 65,536 slots, one each a layer,
    # as in the program of four inputs)
    "vaswani": (_VASWANI, 64, 1024, 2, 0),
    "olmoe": (_OLMOE, 8, 2048, 1, 0),
    "granite_hybrid": (_GRANITE, 32, 2048, 1, 0),
    "deepseek_v3": (_KANANA, 32, 2048, 0, 6),
    "lfm2_moe": (_LFM2, 64, 2048, 2, 0),
    "mimo_v2_flash": (_MIMO, 32, 8192, 2, 0),
    "phi4flash": (_PHI4, 64, 8192, 8, 0),
    "nemotron_h": (_NEMOTRON, 64, 8192, 2, 0),
    # the three latent pools' re-layouts (kanana's, at five times the slots)
    # and the own pages of the three index pools, gathered and turned
    "dots3_note": (_DOTS3, 32, 10240, 0, 6),
}
# what the chip's layout adds to a cache's bytes: a ring row of 1,088 is kept
# 1,152 wide (nine tiles of lanes), three rings of 32 x 513 rows
_LAYOUT_PADDING = {"dots3_note": 3 * 32 * 513 * 64 * 2}


@pytest.mark.parametrize("arch", list(_ONE_INPUT_STEPS))
def test_a_decode_step_takes_one_host_fed_array_on_the_chip(v5e, arch):
    """Each arch's decode program AS ``PagedKVDecoder`` BINDS IT
    (``kv_decode._step_in_symbol`` over ``get_decode_symbol``), at the sizes
    the cases above compile, lowered for the v5e with its cache donated:
    beside the weights and the cache it has exactly ONE parameter, ``step_in``
    (lanes, 3 + pages a lane) float32, and none of a token's, a slot's or a
    table's shape; the four operands are cut out of it inside the program,
    and what held before still holds: the whole cache is updated in place,
    no buffer of a pool's size is copied or transposed (the latent pool's
    re-layouts apart), and every reading node of a page-major
    pool is one call of the kernel that walks the page table."""
    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.ops.attention import pool_shape
    from mxnet_tpu.serving.kv_decode import _step_in_symbol

    cfg, lanes, max_len, reads, relaid = _ONE_INPUT_STEPS[arch]
    page, slots, dtype = 16, lanes * max_len, cfg.get("dtype", "float32")
    sym = _step_in_symbol(
        tf.get_decode_symbol(max_len=slots, page_size=page, **cfg),
        max_len // page)
    cache = tf.decode_cache(**dict(cfg, arch=arch))
    args = {"step_in": ((lanes, 3 + max_len // page), "float32")}
    for name, kind, shape in cache:
        args[name] = (pool_shape(*shape, slots, page), dtype) \
            if kind == "pool" else ((lanes,) + tuple(shape),
                                    "float32" if kind == "row" else dtype)
    fed = set(args)
    assert not {"data", "pos_idx", "write_slot", "page_table"} \
        & set(sym.list_arguments())
    if arch == "vaswani":
        shapes, _, _ = sym.infer_shape(**{n: s for n, (s, _) in args.items()})
        weights = dict(zip(sym.list_arguments(), shapes))
    else:
        weights = tf.param_shapes(**cfg)
    args.update({n: (s, dtype) for n, s in weights.items() if n not in fed})
    assert set(args) == set(sym.list_arguments())
    compiled = _compile_program(v5e, sym, args,
                                donated=[name for name, _, _ in cache])
    hlo = compiled.as_text()
    params = _entry_parameters(hlo)
    assert len(params) == len(args)         # weights, cache, step_in: no key
    assert params.count((lanes, 3 + max_len // page)) == 1
    assert (lanes, 1) not in params and (lanes, max_len // page) not in params
    cache_bytes = sum(math.prod(shape) * jnp.dtype(t).itemsize
                      for shape, t in (args[name] for name, _, _ in cache))
    assert compiled.memory_analysis().alias_size_in_bytes \
        == cache_bytes + _LAYOUT_PADDING.get(arch, 0)
    assert len(_paged_read_calls(hlo)) == reads
    pool = min(math.prod(args[name][0]) for name, kind, _ in cache
               if kind == "pool")
    moved = [name for name, dims, op, _ in _INSTRUCTION.findall(hlo)
             if op in ("copy", "transpose")
             and math.prod(int(d) for d in dims.split(",") if d) >= pool]
    assert len(moved) == relaid, moved
    assert "kv_mask" not in hlo or not reads


def test_a_looped_stacks_decode_step_updates_every_pass_in_place(v5e):
    """``PagedKVDecoder(arch="ouro")``'s decode program lowered for the v5e at
    Ouro-2.6B's published widths and the cell's serving sizes (16 lanes x 320
    slots, pages of 16), FOUR of its 48 layers and all four passes (the 192
    bodies of the whole depth compile in 101 s and say the same of each
    buffer). A layer's ONE pool pair holds the four passes' slots,
    (4 x 320, 16, 2,048) page-major; every pass writes its token's row into
    its own piece and reads it back through the paged-read kernel, sixteen
    read calls and thirty-two scatters over eight buffers, and the program
    aliases ALL of the cache: no pass's write makes a second copy of a pool
    (at the whole depth the pools are 8.05 GB of the chip's 16). The moved
    page table reaches the kernel as data; no mask over the slots is built.
    The small read is (lanes, 2): the token beside the pass that fed it."""
    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.ops.attention import pool_shape
    from mxnet_tpu.serving.kv_decode import _step_in_symbol

    lanes, max_len, page, passes = 16, 320, 16, 4
    slots = lanes * max_len
    cfg = dict(arch="ouro", vocab_size=49152, num_layers=4, num_heads=16,
               head_dim=128, model_dim=2048, ffn_dim=5632,
               total_ut_steps=passes, early_exit_threshold=1.0,
               rope_theta=1e6, rms_eps=1e-6, dtype="bfloat16")
    assert tf.loop_passes(**cfg) == passes
    weights = {n: (s, "bfloat16") for n, s in tf.param_shapes(**cfg).items()}
    cache = tf.decode_cache(**cfg)
    pool = pool_shape(16, 128, passes * slots, page)
    assert pool == (passes * slots // page, page, 2048) and len(cache) == 8
    # as the decoder binds it: ONE host-fed array, cut apart in the program
    inputs = {"step_in": ((lanes, 3 + max_len // page), "float32")}
    inputs.update({name: (pool, "bfloat16") for name, _, _ in cache})
    sym = _step_in_symbol(
        tf.get_decode_symbol(max_len=slots, page_size=page, **cfg),
        max_len // page)
    assert set(sym.list_arguments()) == set(weights) | set(inputs)
    compiled = _compile_program(v5e, sym, {**weights, **inputs},
                                donated=[name for name, _, _ in cache])
    assert [(s.shape, str(s.dtype)) for s in compiled.out_info[0]] == \
        [((lanes, 49152), "float32")] + [(pool, "bfloat16")] * 8 \
        + [((lanes, 2), "float32")]
    hlo = compiled.as_text()
    kernels = _paged_read_calls(hlo)
    assert len(kernels) == 4 * passes
    for u in range(passes):
        for i in range(4):
            tag = "pass%d_layer%d_" % (u, i)
            assert sum(tag + "att/" in line for line in kernels) == 1
    # ONE write operation a (pass, layer) and pool: all 16 lanes' rows in
    # one scatter, and no loop anywhere in the step
    _assert_one_write_a_node(hlo, ["pass%d_layer%d_kvupd/" % (u, i)
                                   for u in range(passes) for i in range(4)])
    assert " while(" not in hlo and "kv_mask" not in hlo
    # every read keeps the block its rule names: 128 slots of an 8,192-byte
    # row, 1 MB a turn in flight and 2 MB of scratch (ONE page of the table's
    # twenty until PR 57, 128 KB a turn)
    assert _assert_the_block_the_rule_names(
        hlo, max_len // page, page, 2048, "bfloat16") == 128
    _assert_no_pool_sized_copy(hlo, math.prod(pool))
    mem = compiled.memory_analysis()
    cache_bytes = 8 * 2 * math.prod(pool)
    assert 12 * cache_bytes == 1_572_864 * slots     # 48 layers: 1.5 MiB a token
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < 256 << 20


def test_the_admission_scatter_cuts_nothing_inside_its_page_walk(v5e):
    """``_AdmitScatter`` at ``olmoe-1b-7b.score``'s sizes (32 page-major pools
    of 8 lanes x 2,048 slots, a 2,048 bucket): the prefill's rows are turned
    to the pool's layout ONCE, outside the walk over the prompt's pages, and
    the walk moves a page a pool and step. The compiler's own byte count is
    the guard: 0.946 GB when this was written; a pass cut out of its array
    inside the walk's body (PR 54's first form, for a looped stack's several
    passes) reads 1.5 GB here and ran the cell's admission 1.6 x as long on
    the chip. A looped stack's passes are cut apart before the walk too."""
    from types import SimpleNamespace

    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.ops.attention import pool_shape
    from mxnet_tpu.serving.kv_decode import _AdmitScatter

    spec = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, jnp.dtype(dtype), sharding=v5e)

    def scatter(cache, passes, lanes, max_len, bucket, page=16):
        prog = _AdmitScatter(SimpleNamespace(
            _cache=cache, page_size=page, prefill_len=bucket))
        pools = tuple(spec(pool_shape(*shape, passes * lanes * max_len, page),
                           "bfloat16") for _, _, shape in cache)
        new = tuple(spec((passes, shape[0], bucket, shape[1]), "bfloat16")
                    for _, _, shape in cache)
        compiled = prog._fn.lower(pools, new, spec((bucket // page,), "int32"),
                                  spec((2,), "int32")).compile()
        cache_bytes = sum(2 * math.prod(p.shape) for p in pools)
        assert compiled.memory_analysis().alias_size_in_bytes == cache_bytes
        return compiled.cost_analysis()["bytes accessed"], \
            sum(2 * math.prod(n.shape) for n in new)

    moved, handed = scatter(tf.decode_cache("olmoe", 16, 16, 2048,
                                            head_dim=128), 1, 8, 2048, 2048)
    assert handed == 32 * 2048 * 2048 * 2
    assert moved < 4 * handed          # 3.5 x: turned once, walked once
    looped = tf.decode_cache("ouro", 4, 16, 2048, head_dim=128)
    moved, handed = scatter(looped, 4, 16, 320, 128)
    assert handed == 8 * 4 * 128 * 2048 * 2
    assert moved < 5 * handed          # 4.4 x at a bucket of eight pages


_LONGCAT = dict(arch="longcat_flash", vocab_size=16384, num_layers=4,
                num_heads=64, model_dim=6144, ffn_dim=12288, moe_ffn_dim=2048,
                num_experts=512, num_zero_experts=256, num_local_experts=16,
                local_expert_offset=0, num_experts_per_tok=12,
                q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                qk_rope_head_dim=64, v_head_dim=128, rope_theta=1e7,
                rms_eps=1e-5, routed_scaling_factor=6.0, dtype="bfloat16")


@pytest.mark.parametrize("program", ["prefill", "decode", "admit_scatter"])
def test_longcat_flash_serving_programs_compile_for_the_chip(v5e, program):
    """The three programs ``PagedKVDecoder(arch="longcat_flash")`` runs,
    lowered for the v5e at LongCat-Flash-Omni's published widths, the
    benchmark's cut (layers 0-3, 16 of 512 experts held beside 256
    zero-compute ones, 16,384 rows of the vocabulary: 5,172,749,312
    parameters in bfloat16) and its serving sizes (32 lanes x 5,120 slots, a
    4,096 bucket). What has to hold on the chip: the cache is EIGHT
    head-major latent pools (1, 163,840, 576), two a layer, each updated in
    place; an admission's eight materialised attentions are one call of the
    blockwise kernel each (no float32 buffer of 64 x 4,096 x 4,096); both
    graphs keep the grouped matmul over the 16 held experts, an admission
    over a CHUNK of the held rows and nothing of 49,152 rows by 6,144, and
    report the load of all 768 router outputs last; and arguments, outputs
    and temporaries fit the chip's 16 GB beside each other."""
    from types import SimpleNamespace

    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.ops.attention import pool_shape
    from mxnet_tpu.serving.kv_decode import _AdmitScatter

    lanes, max_len, bucket, page = 32, 5120, 4096, 16
    slots, cfg = lanes * max_len, _LONGCAT
    shapes = tf.param_shapes(**cfg)
    assert sum(math.prod(s) for s in shapes.values()) == 5_172_749_312
    weights = {n: (s, "bfloat16") for n, s in shapes.items()}
    cache = tf.decode_cache(**cfg)
    assert [(n, k) for n, k, _ in cache] == [("kv_c_%d" % j, "pool")
                                             for j in range(8)]
    buffers = [(pool_shape(*shape, slots, page), "bfloat16")
               for _, _, shape in cache]
    assert buffers[0] == ((1, slots, 576), "bfloat16")
    cache_bytes = sum(2 * math.prod(shape) for shape, _ in buffers)
    assert cache_bytes == 8 * slots * 576 * 2 == 1_509_949_440
    exported = [((1, 1, bucket, 576), "bfloat16")] * 8
    if program == "admit_scatter":
        prog = _AdmitScatter(SimpleNamespace(
            _cache=cache, page_size=page, prefill_len=bucket))
        spec = lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, jnp.dtype(dtype), sharding=v5e)
        compiled = prog._fn.lower(
            tuple(spec(*b) for b in buffers),
            tuple(spec(*n) for n in exported),
            spec((bucket // page,), "int32"), spec((2,), "int32"),
        ).compile()
        mem = compiled.memory_analysis()
        # every pool is updated in place, the second of a layer as the first
        assert mem.alias_size_in_bytes == cache_bytes
        assert mem.temp_size_in_bytes < 64 << 20
        return
    if program == "prefill":
        sym = tf.get_prefill_symbol(prefill_len=bucket, **cfg)
        inputs = {"data": ((1, bucket), "float32"),
                  "length": ((1, 1), "float32")}
        want = [((1, 16384), "float32")] + exported \
            + [((4, 768), "float32")]
    else:
        sym = tf.get_decode_symbol(max_len=slots, page_size=page, **cfg)
        inputs = {"data": ((lanes, 1), "float32"),
                  "pos_idx": ((lanes, 1), "float32"),
                  "write_slot": ((lanes, 1), "float32"),
                  "page_table": ((lanes, max_len // page), "float32")}
        inputs.update({name: b for (name, _, _), b in zip(cache, buffers)})
        want = [((lanes, 16384), "float32")] + buffers \
            + [((lanes,), "float32"), ((4, 768), "float32")]
    compiled = _compile_program(
        v5e, sym, {**weights, **inputs},
        donated=[name for name, _, _ in cache] if program == "decode" else ())
    assert [(s.shape, str(s.dtype)) for s in compiled.out_info[0]] == want
    hlo = compiled.as_text()
    assert _assert_expert_layers(
        hlo, 4, bucket if program == "prefill" else lanes, 12, 16, 6144,
        2048, routed=768) == "kernel"
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    if program == "prefill":
        calls = _flash_attention_calls(hlo)
        assert len(calls) == 8
        for j in range(8):
            assert sum("layer%d_att/" % j in line for line in calls) == 1
        _assert_attention_is_blockwise(hlo, 8, bucket)
        # a loop an expert layer: the turns past the first over the held
        # rows' chunks
        assert hlo.count(" while(") == 4
        _assert_one_row_of_logits(compiled, bucket, 16384)
        # weights 10.35 GB, the bucket's temporaries 1.36; with the decoder's
        # pools beside them 13.3 of 16 GB
        assert mem.argument_size_in_bytes < 10.4e9
        assert mem.temp_size_in_bytes < 1.5e9
        assert held + cache_bytes < 14e9
        return
    # a head-major pool's write is the loop over the lanes, one a pool; its
    # read gathers the lanes' own pages (no kernel takes a row of 576)
    assert not _paged_read_calls(hlo)
    assert hlo.count(" while(") == 8
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.argument_size_in_bytes < 11.9e9
    assert mem.temp_size_in_bytes < 0.6e9
    assert held < 13e9
