"""Parameter-shape inference rules.

The reference's ``InferShape`` pass runs bidirectionally so ``simple_bind``
can deduce every weight shape from just the data shape
(/root/reference/src/executor/graph_executor.cc:423, per-op InferShape
functions e.g. fully_connected-inl.h). In the TPU-native design, forward
shape inference comes free from ``jax.eval_shape`` over the op function; the
only genuinely backward-flowing facts are *parameter* shapes (weights, biases,
norm stats, labels), captured here as per-op rules.

Each rule receives the parsed attrs and the list of currently-known input
shapes (``None`` = unknown), ordered ``input_names + aux_names``, and returns
the list with any deducible entries filled in.
"""
from __future__ import annotations


from .rnn import rnn_param_size

RULES = {}


def rule(name):
    def _r(fn):
        RULES[name] = fn
        return fn

    return _r


def _prod(xs):
    p = 1
    for x in xs:
        p *= int(x)
    return p


@rule("FullyConnected")
def _fc(attrs, shapes):
    data = shapes[0]
    if data is not None:
        nh = attrs["num_hidden"]
        d = _prod(data[1:]) if attrs.get("flatten", True) else data[-1]
        if shapes[1] is None:
            shapes[1] = (nh, d)
        if len(shapes) > 2 and shapes[2] is None:
            shapes[2] = (nh,)
    return shapes


@rule("Convolution")
def _conv(attrs, shapes):
    data = shapes[0]
    if data is not None:
        nf, g = attrs["num_filter"], attrs.get("num_group", 1)
        if shapes[1] is None:
            shapes[1] = (nf, data[1] // g) + tuple(attrs["kernel"])
        if len(shapes) > 2 and shapes[2] is None:
            shapes[2] = (nf,)
    return shapes


@rule("Deconvolution")
def _deconv(attrs, shapes):
    data = shapes[0]
    if data is not None:
        nf, g = attrs["num_filter"], attrs.get("num_group", 1)
        if shapes[1] is None:
            shapes[1] = (data[1], nf // g) + tuple(attrs["kernel"])
        if len(shapes) > 2 and shapes[2] is None:
            shapes[2] = (nf,)
    return shapes


@rule("BatchNorm")
def _bn(attrs, shapes):
    data = shapes[0]
    if data is not None:
        c = (data[1],)
        for i in range(1, 5):  # gamma, beta, moving_mean, moving_var
            if shapes[i] is None:
                shapes[i] = c
    return shapes


@rule("InstanceNorm")
def _in(attrs, shapes):
    data = shapes[0]
    if data is not None:
        for i in (1, 2):
            if shapes[i] is None:
                shapes[i] = (data[1],)
    return shapes


@rule("LeakyReLU")
def _lrelu(attrs, shapes):
    data = shapes[0]
    if data is not None and len(shapes) > 1 and shapes[1] is None:
        shapes[1] = (data[1],)
    return shapes


@rule("Embedding")
@rule("SparseEmbedding")
def _embedding(attrs, shapes):
    if shapes[1] is None:
        shapes[1] = (attrs["input_dim"], attrs["output_dim"])
    return shapes


@rule("_contrib_RMSNorm")
@rule("RMSNorm")
def _rms_norm(attrs, shapes):
    if shapes[0] is not None and shapes[1] is None:
        shapes[1] = (shapes[0][-1],)
    return shapes


@rule("_contrib_RotaryEmbedding")
@rule("RotaryEmbedding")
def _rotary(attrs, shapes):
    if shapes[0] is not None and shapes[1] is None:
        shapes[1] = (shapes[0][0], shapes[0][2])    # (B, H, T, dh) -> (B, T)
    return shapes


@rule("_contrib_KVPoolWrite")
@rule("KVPoolWrite")
def _kv_pool_write(attrs, shapes):
    # (H, S, dh) or page-major (frames, page, H * dh); (R, H, dh), (R, S)
    from .attention import pool_slots

    pool, rows, onehot = shapes
    if pool is None and rows is not None and onehot is not None:
        shapes[0] = (rows[1], onehot[1], rows[2])   # head-major: no page here
    elif onehot is None and pool is not None and rows is not None:
        shapes[2] = (rows[0], pool_slots(pool))
    return shapes


@rule("_contrib_KVPoolSlotWrite")
@rule("KVPoolSlotWrite")
def _kv_pool_slot_write(attrs, shapes):
    # (H, S, dh), (R, H, dh) a pool, then (R, 1)
    rows = next((s for s in shapes[1:-1:2] if s is not None), None)
    if shapes[-1] is None and rows is not None:
        shapes[-1] = (rows[0], 1)
    return shapes


@rule("_contrib_KVPoolAttention")
@rule("KVPoolAttention")
def _kv_pool_attention(attrs, shapes):
    # (R, H, dh), 2 x (H, S, dh) or page-major (frames, page, H * dh),
    # (R, S); a step's table and rows are bound. A value pool may be narrower
    # than the key's: one that is known stays
    from .attention import pool_slots

    query, pool_k, pool_v, mask = shapes[:4]
    pool = pool_k or pool_v
    if pool is not None:
        shapes[1], shapes[2] = pool_k or pool, pool_v or pool
        if mask is None and query is not None:
            shapes[3] = (query[0], pool_slots(pool))
    return shapes


@rule("_contrib_MultiHeadAttention")
@rule("MultiHeadAttention")
def _multi_head_attention(attrs, shapes):
    # (B, H, T, d) x 3, then with sink=True one logit a query head (an
    # indexer's three operands behind it are computed, never bound)
    if attrs.get("sink") and shapes[3] is None and shapes[0] is not None:
        shapes[3] = (shapes[0][1],)
    return shapes


@rule("_contrib_KVRingWrite")
@rule("KVRingWrite")
def _kv_ring_write(attrs, shapes):
    # (R, Hkv, W, d), (R, Hkv, d) a ring, then pos_idx and write_slot (R, 1)
    rows = next((s for s in shapes[1:-2:2] if s is not None), None)
    if rows is not None:
        for i in (-2, -1):
            if shapes[i] is None:
                shapes[i] = (rows[0], 1)
    return shapes


@rule("_contrib_KVRingAttention")
@rule("KVRingAttention")
def _kv_ring_attention(attrs, shapes):
    # (R, H, dk), (R, Hkv, W, dk), (R, Hkv, W, dv), 2 x (R, 1)[, (H,)]
    query = shapes[0]
    if query is not None:
        for i in (3, 4):
            if shapes[i] is None:
                shapes[i] = (query[0], 1)
        if len(shapes) > 5 and shapes[5] is None:
            shapes[5] = (query[1],)
    return shapes


@rule("_contrib_MoEFeedForward")
@rule("MoEFeedForward")
def _moe(attrs, shapes):
    data = shapes[0]
    if data is not None:
        e, f, d = attrs["num_experts"], attrs["num_hidden"], data[-1]
        held = attrs.get("num_local_experts", 0) or e   # the stacks' rows
        first = 2 if attrs.get("gated", True) else 1    # gate and up, or up
        slots = ((e, d),) + ((held, d, f),) * first + ((held, f, d), (e,))
        for i, s in enumerate(slots[:len(shapes) - 1], 1):   # router_bias last
            if shapes[i] is None:
                shapes[i] = s
    return shapes


def _mamba2_weights(attrs, shapes):
    """conv_weight, conv_bias, dt_bias, A_log, D (slots 2..6) from the
    operator's sizes; returns (heads, head_dim, state, kernel, channels)."""
    h, p, n = attrs["num_heads"], attrs["head_dim"], attrs["state_size"]
    k = attrs.get("conv_kernel", 4)
    c = h * p + 2 * attrs.get("num_groups", 1) * n
    for i, s in enumerate(((c, k), (c,), (h,), (h,), (h,)), 2):
        if shapes[i] is None:
            shapes[i] = s
    return h, p, n, k, c


@rule("_contrib_Mamba2Scan")
@rule("Mamba2Scan")
def _mamba2_scan(attrs, shapes):
    h = _mamba2_weights(attrs, shapes)[0]
    data = shapes[0]
    if data is not None:            # (B, T, C): dt (B, T, H), length (B, 1)
        if shapes[1] is None:
            shapes[1] = (data[0], data[1], h)
        if shapes[7] is None:
            shapes[7] = (data[0], 1)
    return shapes


@rule("_contrib_Mamba2Step")
@rule("Mamba2Step")
def _mamba2_step(attrs, shapes):
    h, p, n, k, c = _mamba2_weights(attrs, shapes)
    data = shapes[0]
    if data is not None:            # (R, C): one token a row
        r = data[0]
        for i, s in ((1, (r, h)), (7, (r, h, p, n)), (8, (r, k - 1, c)),
                     (9, (r, 1))):
            if shapes[i] is None:
                shapes[i] = s
    return shapes


def _mamba1_weights(shapes):
    """conv_bias, dt_bias and D (slots 2, 5, 7) from the channels of
    ``data``; the matrices carry sizes ``data`` does not (kernel, rank,
    state), so the graph names their shapes (``_phi4flash_mamba``)."""
    data = shapes[0]
    if data is not None:
        for i in (2, 5, 7):
            if shapes[i] is None:
                shapes[i] = (data[-1],)
    return data


@rule("_contrib_Mamba1Scan")
@rule("Mamba1Scan")
def _mamba1_scan(attrs, shapes):
    data = _mamba1_weights(shapes)
    if data is not None and shapes[8] is None:      # length (B, 1)
        shapes[8] = (data[0], 1)
    return shapes


@rule("_contrib_Mamba1Step")
@rule("Mamba1Step")
def _mamba1_step(attrs, shapes):
    data = _mamba1_weights(shapes)
    if data is not None and shapes[10] is None:     # stepped (R, 1)
        shapes[10] = (data[0], 1)
    return shapes


@rule("_contrib_GatedShortConv")
@rule("GatedShortConv")
def _gated_short_conv(attrs, shapes):
    data = shapes[0]
    if data is not None:            # (B, T, 3d): taps (K, d), length (B, 1)
        if shapes[1] is None:
            shapes[1] = (attrs.get("kernel", 3), data[-1] // 3)
        if shapes[2] is None:
            shapes[2] = (data[0], 1)
    return shapes


@rule("_contrib_GatedShortConvStep")
@rule("GatedShortConvStep")
def _gated_short_conv_step(attrs, shapes):
    data = shapes[0]
    if data is not None:            # (R, 3d): one token a row
        k, d = attrs.get("kernel", 3), data[-1] // 3
        for i, s in ((1, (k, d)), (2, (data[0], k - 1, d)), (3, (data[0], 1))):
            if shapes[i] is None:
                shapes[i] = s
    return shapes


@rule("RNN")
def _rnn_shapes(attrs, shapes):
    data = shapes[0]
    if data is not None:
        T, N, I = data
        H, L = attrs["state_size"], attrs["num_layers"]
        d = 2 if attrs.get("bidirectional") else 1
        if shapes[1] is None:
            shapes[1] = (rnn_param_size(L, I, H, attrs.get("bidirectional", False), attrs["mode"]),)
        if shapes[2] is None:
            shapes[2] = (L * d, N, H)
        if len(shapes) > 3 and shapes[3] is None:
            shapes[3] = (L * d, N, H)
    return shapes


@rule("SoftmaxOutput")
def _softmax_out(attrs, shapes):
    data = shapes[0]
    if data is not None and shapes[1] is None:
        if attrs.get("multi_output") and len(data) > 2:
            shapes[1] = (data[0],) + tuple(data[2:])
        elif attrs.get("preserve_shape"):
            shapes[1] = tuple(data[:-1])
        else:
            shapes[1] = (data[0],)
    return shapes


def _label_like_data(attrs, shapes):
    if shapes[0] is not None and shapes[1] is None:
        shapes[1] = tuple(shapes[0])
    return shapes


for _n in ("LinearRegressionOutput", "LogisticRegressionOutput", "MAERegressionOutput"):
    RULES[_n] = _label_like_data


@rule("SVMOutput")
def _svm_out(attrs, shapes):
    data = shapes[0]
    if data is not None and shapes[1] is None:
        shapes[1] = (data[0],)
    return shapes


@rule("IdentityAttachKLSparseReg")
def _klreg(attrs, shapes):
    return shapes
