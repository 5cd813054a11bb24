"""Decoder-only Transformer language model (BASELINE.md stretch config:
Transformer-base MT; built entirely from Symbol ops with the fused
MultiHeadAttention op from ops/attention.py).

Pre-norm blocks: x + MHA(LN(x)), x + FFN(LN(x)); LN via the registry's
LayerNorm-equivalent composition (InstanceNorm is channel-first, so LN here
is mean/var composed from broadcast ops to stay faithful to the op set)."""
import math

from .. import symbol as sym
from ..base import MXNetError
from ..ops.attention import _LANES, _NEG, pool_paged

ARCHS = ("vaswani", "olmoe", "granite_hybrid", "deepseek_v3", "lfm2_moe",
         "mimo_v2_flash", "phi4flash", "nemotron_h", "dots3_note", "ouro",
         "laguna", "longcat_flash")


# the block every graph is derived from (ROADMAP D2); the others have the
# serving prefill and the single-step decode graph alone
EVERY_GRAPH = ARCHS[:1]


def _refuse_arch(arch, what):
    """Everything but the serving prefill and the shared-pool decode graph
    knows the block of ``EVERY_GRAPH`` only (ROADMAP D2: one block, every
    graph derived from it). Both refusals name the archs from the tuples
    above: nobody else lists them."""
    if arch not in ARCHS:
        raise MXNetError("unknown arch %r (have: %s)" % (arch, ", ".join(ARCHS)))
    if arch not in EVERY_GRAPH:
        raise MXNetError("%s is not built for arch %r yet (built for: %s)"
                         % (what, arch, ", ".join(EVERY_GRAPH)))


def _layer_norm(x, name, dim):
    # Deliberately the naive frontend composition: the variance branch
    # recomputes its own mean/centering, and the square is spelled as a
    # self-multiply. Bit-identical to the single-chain form (XLA CSEs the
    # duplicates; x*x IS jnp.square). It is spelled so for the rewrite
    # pipeline's tests (MXNET_GRAPHREWRITE, default off: cse merges the
    # duplicate mean/center, canonicalize turns the self-multiply into
    # square; docs/static_analysis.md §GL6xx), and nothing else reads the
    # difference (ROADMAP D5). The arithmetic is ``vaswani``'s and
    # ``phi4flash``'s graph, so respelling it changes two cells' programs.
    mean = sym.mean(x, axis=-1, keepdims=True)
    cent = sym.broadcast_sub(x, mean, name="%s_cent" % name)
    cent_v = sym.broadcast_sub(x, sym.mean(x, axis=-1, keepdims=True))
    var = sym.mean(cent_v * cent_v, axis=-1, keepdims=True)
    inv = sym.rsqrt(var + 1e-5)
    normed = sym.broadcast_mul(cent, inv)
    gamma = sym.Variable("%s_gamma" % name, shape=(dim,))
    beta = sym.Variable("%s_beta" % name, shape=(dim,))
    return sym.broadcast_add(sym.broadcast_mul(normed, gamma), beta, name=name)


def _split_fused(fused, n_parts, seq_len, num_heads, dh):
    """Split one fused (B, T, n_parts·M) projection into n_parts head-major
    (B, H, T, dh) tensors — the single owner of the fused-weight layout."""
    fused = sym.Reshape(fused, shape=(-1, seq_len, n_parts, num_heads, dh))
    outs = []
    for i in range(n_parts):
        p = sym.Reshape(sym.slice_axis(fused, axis=2, begin=i, end=i + 1),
                        shape=(-1, seq_len, num_heads, dh))
        outs.append(sym.SwapAxis(p, dim1=1, dim2=2))  # (B,T,H,D)→(B,H,T,D)
    return outs


def _split_rows(fused, n_parts, num_heads, dh):
    """Split one fused (B, T, n_parts·M) projection into n_parts row-major
    (B·T, H, dh) tensors: the rows the shared-pool operators take (the
    lanes of a decode step, T = 1, or the positions of a chunk, B = 1)."""
    fused = sym.Reshape(fused, shape=(-1, n_parts, num_heads, dh))
    return [sym.Reshape(sym.slice_axis(fused, axis=1, begin=i, end=i + 1),
                        shape=(-1, num_heads, dh)) for i in range(n_parts)]


def _self_attend(qkv, name, num_heads, model_dim, seq_len, causal=True,
                 kvs=None):
    """Full-sequence self-attention on ONE fused (B, T, 3·M) qkv projection
    (better MXU shape than three M-wide ones) -> (B, T, M). ``kvs``, when a
    list, collects the head-major (B, H, T, dh) key and value: what the
    serving prefill exports to seed the decode path's KV pool."""
    q, k, v = _split_fused(qkv, 3, seq_len, num_heads, model_dim // num_heads)
    if kvs is not None:
        kvs += [k, v]
    att = sym.MultiHeadAttention(query=q, key=k, value=v, causal=causal,
                                 name="%s_att" % name)
    return _merge_heads(att, seq_len, model_dim)


def _attention_block(x, name, num_heads, model_dim, seq_len, causal=True):
    """The translation model's self-attention sub-layer: fused qkv,
    ``_self_attend``, output projection."""
    qkv = sym.FullyConnected(data=x, num_hidden=3 * model_dim, flatten=False,
                             name="%s_qkv" % name)
    att = _self_attend(qkv, name, num_heads, model_dim, seq_len, causal)
    return sym.FullyConnected(data=att, num_hidden=model_dim, flatten=False,
                              name="%s_proj" % name)


def _split_heads(x, seq_len, num_heads, dh):
    """(B, T, M) → (B, H, T, dh) for the fused attention op."""
    x = sym.Reshape(x, shape=(-1, seq_len, num_heads, dh))
    return sym.SwapAxis(x, dim1=1, dim2=2)


def _merge_heads(att, seq_len, model_dim):
    att = sym.SwapAxis(att, dim1=1, dim2=2)
    return sym.Reshape(att, shape=(-1, seq_len, model_dim))


def _cross_attention(q_in, kv_in, name, num_heads, model_dim, q_len, kv_len):
    """Attention with separate query/key-value sources (the MT decoder's
    encoder-attention). Only the q projection is separate; k and v share
    one fused 2·M-wide GEMM on kv_in (same MXU-shape rationale as
    _attention_block's fused qkv; self-attention sites use that block)."""
    dh = model_dim // num_heads
    q = sym.FullyConnected(data=q_in, num_hidden=model_dim, flatten=False,
                           name="%s_q" % name)
    kv = sym.FullyConnected(data=kv_in, num_hidden=2 * model_dim,
                            flatten=False, name="%s_kv" % name)
    k, v = _split_fused(kv, 2, kv_len, num_heads, dh)
    att = sym.MultiHeadAttention(
        query=_split_heads(q, q_len, num_heads, dh),
        key=k, value=v,
        causal=False, name="%s_att" % name)
    att = _merge_heads(att, q_len, model_dim)
    return sym.FullyConnected(data=att, num_hidden=model_dim, flatten=False,
                              name="%s_proj" % name)


def _ffn(x, name, model_dim, ffn_dim):
    h = sym.FullyConnected(data=x, num_hidden=ffn_dim, flatten=False,
                           name="%s_ffn1" % name)
    h = sym.Activation(h, act_type="relu")
    return sym.FullyConnected(data=h, num_hidden=model_dim, flatten=False,
                              name="%s_ffn2" % name)


def _embed_with_pos(tokens, vocab_size, model_dim, seq_len, name):
    embed = sym.Embedding(data=tokens, input_dim=vocab_size,
                          output_dim=model_dim, name="%s_embed" % name)
    pos = sym.Variable("%s_pos_weight" % name, shape=(seq_len, model_dim))
    return sym.broadcast_add(
        embed, sym.Reshape(pos, shape=(1, seq_len, model_dim)),
        name="%s_pos_add" % name)


def get_symbol_mt(vocab_size=32000, num_layers=6, num_heads=8, model_dim=512,
                  ffn_dim=2048, src_len=64, tgt_len=64, **kwargs):
    """Encoder-decoder Transformer-base for MT (BASELINE.md stretch config:
    "Transformer-base MT"; the reference era predates Transformers — the
    closest ancestor is its seq2seq RNN stack — so the architecture here is
    the standard pre-norm Transformer built from this repo's Symbol ops and
    the fused MultiHeadAttention, not a translation of reference code).

    Inputs: ``data`` (B, src_len) source tokens, ``dec_data`` (B, tgt_len)
    shifted-right target tokens, ``softmax_label`` (B, tgt_len). Fixed
    lengths (pad to bucket shapes; BucketingModule handles the rest) —
    padding attends as ordinary tokens, the toy/bucketed regime this model
    targets."""
    _refuse_arch(kwargs.get("arch", "vaswani"), "get_symbol_mt")
    src = sym.Variable("data")
    tgt = sym.Variable("dec_data")
    label = sym.Variable("softmax_label")

    # ---- encoder: pre-norm self-attention stack, non-causal
    x = _embed_with_pos(src, vocab_size, model_dim, src_len, "enc")
    for i in range(num_layers):
        n = "enc%d" % i
        ln = _layer_norm(x, "%s_ln1" % n, model_dim)
        x = x + _attention_block(ln, n + "_self", num_heads, model_dim,
                                 src_len, causal=False)
        x = x + _ffn(_layer_norm(x, "%s_ln2" % n, model_dim), n,
                     model_dim, ffn_dim)
    memory = _layer_norm(x, "enc_final_ln", model_dim)

    # ---- decoder: causal self-attention + cross-attention on the memory
    y = _embed_with_pos(tgt, vocab_size, model_dim, tgt_len, "dec")
    for i in range(num_layers):
        n = "dec%d" % i
        ln = _layer_norm(y, "%s_ln1" % n, model_dim)
        y = y + _attention_block(ln, n + "_self", num_heads, model_dim,
                                 tgt_len, causal=True)
        y = y + _cross_attention(_layer_norm(y, "%s_ln2" % n, model_dim),
                                 memory, n + "_cross", num_heads, model_dim,
                                 tgt_len, src_len)
        y = y + _ffn(_layer_norm(y, "%s_ln3" % n, model_dim), n,
                     model_dim, ffn_dim)
    y = _layer_norm(y, "dec_final_ln", model_dim)
    y = sym.Reshape(y, shape=(-1, model_dim))
    logits = sym.FullyConnected(data=y, num_hidden=vocab_size, name="mt_head")
    label_flat = sym.Reshape(label, shape=(-1,))
    return sym.SoftmaxOutput(data=logits, label=label_flat, name="softmax")


# ------------------------------------------------------------ the Vaswani block
def _vaswani_layer(x, i, attend, model_dim, ffn_dim):
    """One pre-norm Vaswani block on x (B, T, M): x + proj(attend(LN(x)·Wqkv)),
    then x + FFN(LN(x)). ``attend(i, qkv)`` is the one thing the graphs do
    differently: it takes the fused (B, T, 3·M) projection and returns
    attention's (B, T, M) output, over the sequence itself (``_self_attend``:
    training and the prefill) or over the shared KV pool (``_pool_rows_symbol``:
    decode and chunk). It takes the projection unsplit because the two split it
    differently, head-major against row-major."""
    name = "layer%d" % i
    qkv = sym.FullyConnected(
        data=_layer_norm(x, "%s_ln1" % name, model_dim),
        num_hidden=3 * model_dim, flatten=False, name="%s_qkv" % name)
    x = x + sym.FullyConnected(data=attend(i, qkv), num_hidden=model_dim,
                               flatten=False, name="%s_proj" % name)
    return x + _ffn(_layer_norm(x, "%s_ln2" % name, model_dim), name,
                    model_dim, ffn_dim)


def _vaswani_head(x, vocab_size, model_dim):
    """Final norm and the head: (B, T, M) -> logits (B·T, vocab)."""
    x = _layer_norm(x, "final_ln", model_dim)
    return sym.FullyConnected(
        data=sym.Reshape(x, shape=(-1, model_dim)), num_hidden=vocab_size,
        name="lm_head")


def _last_real_row(x, length):
    """(B, T, ...) -> (B, 1, ...): of each prompt its last real row, row
    ``length - 1`` of its own bucket (``length`` (B, 1); ``batch_take`` clips
    the index into the bucket, so a length of 0 reads row 0). A prefill's
    final norm and head run over this row alone: it is the one row of logits
    an admission hands out, and a gather changes no value and no type."""
    return sym.expand_dims(
        sym.batch_take(x, sym.Reshape(length, shape=(-1,)) - 1.0), axis=1)


def _vaswani_sequence(vocab_size, num_layers, num_heads, model_dim, ffn_dim,
                      seq_len, pos_len=None, kvs=None, length=None):
    """The decoder-only stack over ``data`` (B, seq_len) -> logits
    (B·seq_len, vocab): training (``get_symbol``) and the serving prefill,
    which reads the first ``seq_len`` rows of a ``pos_len``-row position table,
    collects every layer's K/V in ``kvs`` and, given each prompt's ``length``
    (B, 1), heads its last real row alone: logits (B, vocab)."""
    pos_len = pos_len or seq_len
    data = sym.Variable("data")  # (B, T) int tokens
    embed = sym.Embedding(data=data, input_dim=vocab_size,
                          output_dim=model_dim, name="embed")
    pos = sym.Variable("pos_embed_weight", shape=(pos_len, model_dim))
    if seq_len != pos_len:
        pos = sym.slice_axis(pos, axis=0, begin=0, end=seq_len)
    x = sym.broadcast_add(
        embed, sym.Reshape(pos, shape=(1, seq_len, model_dim)),
        name="pos_add")

    def attend(i, qkv):
        return _self_attend(qkv, "layer%d" % i, num_heads, model_dim,
                            seq_len, kvs=kvs)

    for i in range(num_layers):
        x = _vaswani_layer(x, i, attend, model_dim, ffn_dim)
    if length is not None:
        x = _last_real_row(x, length)
    return _vaswani_head(x, vocab_size, model_dim)


def get_symbol(vocab_size=32000, num_layers=6, num_heads=8, model_dim=512,
               ffn_dim=2048, seq_len=64, **kwargs):
    _refuse_arch(kwargs.get("arch", "vaswani"), "get_symbol")
    label = sym.Variable("softmax_label")
    logits = _vaswani_sequence(vocab_size, num_layers, num_heads, model_dim,
                               ffn_dim, seq_len)
    label_flat = sym.Reshape(label, shape=(-1,))
    return sym.SoftmaxOutput(data=logits, label=label_flat, name="softmax")


# --------------------------------------------------------------------- serving
def get_prefill_symbol(vocab_size=32000, num_layers=6, num_heads=8,
                       model_dim=512, ffn_dim=2048, prefill_len=64,
                       pos_len=None, arch="vaswani", **kwargs):
    """Serving prefill graph (docs/SERVING.md): the decoder-only LM of
    ``get_symbol`` over a fixed ``prefill_len`` bucket, additionally
    exporting every layer's head-major key/value tensors so the serving
    path can seed the decode executable's KV pool.

    Weight names are IDENTICAL to ``get_symbol`` — a trained checkpoint
    loads into either. ``pos_len`` is the trained position table's length
    (defaults to ``prefill_len``); prompts are right-padded to
    ``prefill_len`` by the caller, and causality guarantees pad tokens
    cannot influence earlier positions.

    Inputs, for every ``arch``: ``data`` (B, P) and ``length`` (B, 1), the
    real tokens of each prompt. The layers run over the bucket; the final
    norm and the head run over each prompt's LAST REAL ROW alone, row
    ``length - 1`` gathered inside the graph (``_last_real_row``; the index
    is clipped into the bucket), because that row's logits are all an
    admission hands out. What the cache keeps is exported over the bucket.

    Outputs: ``[logits (B, vocab), k_0, v_0, ..., k_{L-1}, v_{L-1}]``
    with each k/v of shape (B, H, P, dh).

    ``arch="olmoe"`` builds the sparse-expert block instead (``_olmoe_layer``;
    keywords ``head_dim``, ``num_experts``, ``num_experts_per_tok``,
    ``rope_theta``, ``rms_eps``, ``dtype``; ``ffn_dim`` is one expert's
    width): no position table, K exported AFTER its norm and rotation, and
    one more output after the K/V, ``moe_load (layers, experts)``: the rows
    each expert received, padding positions included.

    ``arch="granite_hybrid"`` builds the Mamba-2 / attention hybrid block
    (``_granite_layer``, its mixer chosen by ``layer_types``). Its scans
    read ``length`` too: a recurrence, unlike causal attention, reads its
    padding unless told where the prompt ends. After the
    logits come the cache's values in ``decode_cache`` order: a Mamba layer's
    recurrent state at ``length`` and its last convolution columns, float32,
    an attention layer's K and V (B, Hkv, P, dh).

    ``arch="deepseek_v3"`` builds the latent-attention block
    (``_deepseek_v3_layer``) in its MATERIALISED form: every head's key and
    value are made of the latent and attended densely. After the logits
    comes ONE tensor a layer, the normed latent beside the rotated shared
    key, (B, 1, P, kv_lora_rank + qk_rope_head_dim): all the cache keeps;
    then ``moe_load (expert layers, experts)`` as for ``olmoe``.

    ``arch="lfm2_moe"`` builds the gated-short-convolution / attention block
    with sparse experts (``_lfm2_moe_layer``, its mixer chosen by
    ``layer_types``, its feed-forward by depth). Its convolutions read
    ``length`` as ``granite_hybrid``'s scans do: a convolution's state is
    the last columns of the PROMPT, not of the bucket. After the logits come
    the cache's values in ``decode_cache`` order: a conv layer's last
    ``conv_kernel - 1`` gated columns before ``length``, float32, an
    attention layer's K (normed per head and rotated) and V
    (B, Hkv, P, dh); then ``moe_load``.

    ``arch="mimo_v2_flash"`` builds the window / full attention block with
    sparse experts (``_mimo_layer``, its attention chosen by
    ``hybrid_layer_pattern``, its feed-forward by ``moe_layer_freq``). After
    the logits come EVERY layer's K (rotated) and V (scaled), (B, Hkv, P,
    head_dim) and (B, Hkv, P, v_head_dim), a window layer's with its own
    head count: the admission takes the prompt's last ``sliding_window``
    positions of those into the lane's rings; then ``moe_load``. A window
    layer scores a band of the bucket (``MultiHeadAttention(window=)``).

    ``arch="phi4flash"`` builds the SambaY block with differential attention
    (``_phi4flash_layer``, its mixer chosen by depth and parity), for ONE
    prompt a call (``length`` (1, 1)). It narrows EARLIER than the others:
    layers 0 to N/2 and layer N/2 + 1's keys and values run over the bucket;
    that layer's query, its MLP and every layer behind it run over the
    prompt's last real row alone, with the row of ``m`` (layer N/2's scan
    before its gate) that belongs to it.
    After them come the cache's values in ``decode_cache`` order: a Mamba-1
    layer's state at ``length`` (1, N, E) and its last convolution columns,
    float32; a window layer's and layer N/2 + 1's K and V
    (1, Hkv / 2, P, 2 * head_dim), pairs of heads side by side. The cross
    layers export nothing.

    ``arch="nemotron_h"`` builds the one-mixer blocks of
    ``_nemotron_h_layer`` (Mamba-2 with groups of B and C | ungated relu^2
    experts beside a shared one | position-free grouped-query attention).
    After the logits come the cache's values in ``decode_cache`` order, a
    Mamba layer's state and columns as ``granite_hybrid``'s, an attention
    layer's K and V; an expert block exports nothing; ``moe_load`` last.

    ``arch="ouro"`` builds the LOOPED stack (``_ouro_layer``: sandwich norms,
    rotary attention, a gated MLP), the ``num_layers`` layers applied
    ``total_ut_steps`` times over the same weights, for ONE prompt a call:
    operators named ``pass<u>_layer<i>_*``, weights ``layer<i>_*``. After
    every pass the one final norm; the exit gate and the rule of
    ``_ouro_head`` choose which pass's output, at the prompt's last real row,
    feeds the head. After the logits come a layer's K (rotated) and V of
    EVERY pass, pass-major, (passes, H, P, dh), in ``decode_cache`` order;
    then ``exit_pass (1,)``, the pass that fed the head, counted from 1.

    ``arch="laguna"`` builds window and full layers whose QUERY-head counts
    differ (``_laguna_layer``: ``swa_num_heads`` and ``num_heads`` over the
    same ``num_kv_heads``, a q/k norm a head, a gate a head, YaRN on the
    full layers' partial rotary alone, routed experts beside a shared one),
    its attention ``_window_prefill_attend``'s as ``mimo_v2_flash``'s is.
    After the logits come every layer's K (rotated) and V in
    ``decode_cache`` order, then ``moe_load``.

    ``arch="longcat_flash"`` builds layers of TWO latent attentions, two
    dense MLPs and one shortcut-connected expert layer whose router also
    chooses among zero-compute experts (``_longcat_layer``), the attentions
    MATERIALISED as ``deepseek_v3``'s. After the logits come TWO tensors a
    layer, sublayer 2i's and 2i + 1's [c | k_r] (B, 1, P, kv_lora_rank +
    qk_rope_head_dim); then ``moe_load (layers, experts + zero experts)``.
    """
    builders = {"olmoe": _olmoe_prefill_symbol,
                "granite_hybrid": _granite_prefill_symbol,
                "deepseek_v3": _deepseek_v3_prefill_symbol,
                "lfm2_moe": _lfm2_moe_prefill_symbol,
                "mimo_v2_flash": _mimo_prefill_symbol,
                "phi4flash": _phi4flash_prefill_symbol,
                "nemotron_h": _nemotron_h_prefill_symbol,
                "dots3_note": _dots3_prefill_symbol,
                "ouro": _ouro_prefill_symbol,
                "laguna": _laguna_prefill_symbol,
                "longcat_flash": _longcat_prefill_symbol}
    if arch in builders:
        return builders[arch](
            vocab_size=vocab_size, num_layers=num_layers,
            num_heads=num_heads, model_dim=model_dim, ffn_dim=ffn_dim,
            prefill_len=prefill_len, **kwargs)
    _refuse_arch(arch, "get_prefill_symbol")
    kvs = []
    logits = _vaswani_sequence(vocab_size, num_layers, num_heads, model_dim,
                               ffn_dim, prefill_len, pos_len, kvs,
                               length=sym.Variable("length"))
    return sym.Group([logits] + kvs)


def _pool_attend(i, q, k_new, v_new, write, read, kv_outs, **attrs):
    """Layer ``i``'s write into and read of the ONE shared KV pool, on rows
    (N, H, dh): ``write(i, {"k": k_new, "v": v_new})`` puts each row's new
    K/V into its slot of ``kv_k_i`` / ``kv_v_i`` (H, slots, dh), which come
    back in the type they went in and are collected in ``kv_outs``; then
    each row reads the updated pool as ``read`` says, ``KVPoolAttention``'s
    operands after the pools by name: its additive float32 ``mask``
    (N, slots) and, in a decode step, what the mask was made of
    (``_pool_step_inputs``). ``attrs``: a ``scale`` other than 1/sqrt(dh).
    Returns the context (N, H, dh)."""
    upd = write(i, {"k": k_new, "v": v_new})
    kv_outs += upd
    return sym.KVPoolAttention(q, upd[0], upd[1], name="layer%d_att" % i,
                               **read, **attrs)


def _pool_step_inputs(pos_idx, num_slots, page_size, write_slot=None):
    """``_pool_attend``'s write and read for a decode step, from what the
    host knows of a lane: ``write_slot`` (B, 1), the pool slot its token
    lands in (negative: the lane rides along, writes nothing and sees
    nothing), and ``page_table`` (B, pages a lane), the frames of its pages
    in order. ``write(i, {tag: rows})`` is slot-indexed, ONE
    ``KVPoolSlotWrite`` over layer ``i``'s pools ``kv_<tag>_i`` (a program
    that takes them donated updates them in place; all the lanes' rows reach
    a page-major pool in one scatter, head-major ones in one loop over the
    lanes: ``ops.attention.pool_write_form``) and gives them back in
    the tags' order. With ``pos_idx`` the two inputs are the lane's whole
    context: the read is its mask over the pool (``KVPageMask``, made ON
    THE DEVICE, once in front of the layers) beside the three it is made
    of, so ``KVPoolAttention`` may read a lane's own pages instead. A graph
    that reads ``write_slot`` elsewhere too hands its Variable in."""
    if write_slot is None:
        write_slot = sym.Variable("write_slot")
    pages = dict(page_table=sym.Variable("page_table"), pos_idx=pos_idx,
                 write_slot=write_slot)

    def write(i, news):
        pairs = [x for tag, rows in news.items()
                 for x in (sym.Variable("kv_%s_%d" % (tag, i)), rows)]
        out = sym.KVPoolSlotWrite(
            *pairs, write_slot, num_pools=len(news),
            name="layer%d_%supd" % (i, "".join(news)))
        return [out[j] for j in range(len(news))]

    return write, dict(pages, page_size=page_size, mask=sym.KVPageMask(
        page_size=page_size, num_slots=num_slots, name="kv_mask", **pages))


def _token_head(logits, kv_outs, token_name):
    """``[logits, k'_0, v'_0, ...]`` plus, where named, the on-device arg-max
    head: a greedy driver then pulls one id a row, not a row of logits."""
    outs = [logits] + kv_outs
    if token_name:
        outs.append(sym.argmax(logits, axis=-1, name=token_name))
    return sym.Group(outs)


def _pool_rows_symbol(vocab_size, num_layers, num_heads, model_dim, ffn_dim,
                      pos_len, seq_len, pool_inputs, token_name):
    """The Vaswani stack over N rows against the shared pool. The rows are
    the lanes of a decode step (``data`` (B, 1), ``seq_len`` 1) or the
    positions of one lane's chunk (``data`` (1, T), ``seq_len`` T); either
    way ``pos_idx`` has ``data``'s shape and ``pool_inputs(pos_idx)`` gives
    ``_pool_attend``'s ``write`` and ``read`` for the rows."""
    dh = model_dim // num_heads
    data = sym.Variable("data")
    pos_idx = sym.Variable("pos_idx")
    write, read = pool_inputs(pos_idx)
    emb = sym.Embedding(data=data, input_dim=vocab_size,
                        output_dim=model_dim, name="embed")
    posrow = sym.Embedding(data=pos_idx, input_dim=pos_len,
                           output_dim=model_dim, name="pos_embed")
    x = emb + posrow
    kv_outs = []

    def attend(i, qkv):
        q, k_new, v_new = _split_rows(qkv, 3, num_heads, dh)
        ctx = _pool_attend(i, q, k_new, v_new, write, read, kv_outs)
        return sym.Reshape(ctx, shape=(-1, seq_len, model_dim))

    for i in range(num_layers):
        x = _vaswani_layer(x, i, attend, model_dim, ffn_dim)
    return _token_head(_vaswani_head(x, vocab_size, model_dim), kv_outs,
                       token_name)


def get_decode_symbol(vocab_size=32000, num_layers=6, num_heads=8,
                      model_dim=512, ffn_dim=2048, max_len=64, pos_len=None,
                      token_out=True, arch="vaswani", page_size=8, **kwargs):
    """Serving single-token decode graph (docs/SERVING.md): ONE token per
    lane through the ``get_symbol`` stack, every lane writing into and
    attending over ONE shared KV pool of ``max_len`` slots per layer (for
    ``PagedKVDecoder``, lanes x slots a lane). Compiles ONCE — every decode
    step replays the same executable whatever the lanes' positions.

    Inputs beyond the weights:
      - ``data`` (B, 1): each lane's current token id.
      - ``pos_idx`` (B, 1): absolute positions (rows of the trained
        position table, so ``pos < pos_len``).
      - ``write_slot`` (B, 1): the pool slot each lane's token writes, as an
        index; negative for a lane that rides along (it writes nothing and
        attends nothing). The KV update is in-graph and by that index
        (``KVPoolSlotWrite``): all the lanes' rows go to their slots of a
        layer's page-major pools in ONE device operation a pool (an XLA
        scatter over the pool's rows), and into a head-major pool a lane at
        a time, the run of slots around its own read, the row put in, the
        run written back — no per-step
        host scatter, no per-slot recompile, nothing of the pool's size
        made, and in place where the program takes the pool donated
        (``PagedKVDecoder`` does). Lane slots are disjoint by construction
        (the page allocator hands a frame to one writer at a time), and a
        negative slot writes nothing, which is how idle lanes ride along
        for free.
      - ``page_table`` (B, pages a lane): the frames of each lane's pages,
        in order, ``page_size`` slots each (entries past the lane's last
        page are never read). From it, ``pos_idx`` and ``write_slot`` the
        graph makes the additive (B, max_len) score mask per lane
        (``KVPageMask``) — 0 on the slots holding that lane's context
        (INCLUDING the current slot), a large negative elsewhere — so what
        the host ships a step is a few numbers a lane, not two arrays of
        the pool's length. Masked slots contribute exp(-1e9) = 0 exactly,
        so N lanes can read the SAME physical page: that is what makes a
        shared prefix page a refcount instead of a copy (docs/SERVING.md
        §Prefix cache). Attention over slots is order-agnostic (positions
        live in the embeddings), so a lane's tokens may occupy ANY frames —
        what the allocator's non-contiguous placement relies on. The same
        three inputs reach every layer's ``KVPoolAttention`` beside the
        mask, which may then read a lane's own frames (on the chip, over
        page-major pools, with the kernel that walks the table and stops at
        the lane's context) and never read the mask: the operator's choice,
        from its operands' shapes and the backend
        (``ops.attention.pool_read_form``).
      - ``kv_k_i`` / ``kv_v_i`` per layer: the pool, H heads of dh over
        ``max_len`` slots, bound in the layout its row gives it
        (``ops.attention.pool_shape``: page-major (max_len / page_size,
        page_size, H * dh) where H * dh is a multiple of 128, head-major
        (H, max_len, dh) elsewhere; the operators read the layout off the
        buffer). The updated buffers are program OUTPUTS; the caller swaps
        them back in as the next step's inputs (``PagedKVDecoder`` does).

    One token a row collapses attention to a masked weighted sum, spelled,
    with the write before it, as two registry operators of
    ops/attention.py: ``KVPoolSlotWrite`` (the stored row is the row bit for
    bit: it is moved, not multiplied) and ``KVPoolAttention`` (scores and
    context as contractions at the default matmul precision with a float32
    accumulator and softmax). On the CPU that is float32 arithmetic, a few
    ulp from the full-sequence forward at matching positions; on the chip
    the two reads are one bfloat16 pass each, as ``MultiHeadAttention``
    gives the same tokens in the prefill, on the matrix unit.

    Outputs: ``[logits (B, vocab), k'_0, v'_0, ..., k'_{L-1}, v'_{L-1}]``,
    plus — with ``token_out=True`` (the default) — a trailing
    ``greedy_token (B,)`` head: ``argmax(logits, axis=-1)`` lowered ON
    DEVICE, so a greedy driver pulls one id per lane instead of the full
    (B, vocab) logits row (GL703; the KV outputs keep their ``1 + 2*i``
    positions either way).

    This graph is also the megastep building block (serving/kv_decode.py
    ``_DecodeMegastep``): it is pure in its (data, pos_idx, write_slot,
    page_table, kv_*) inputs, so K decode steps compose as a ``lax.scan`` over
    ONE compiled body — the scan carries the KV outputs back into the KV
    inputs and feeds each step's sampled token to the next, keeping the
    whole K-token loop device-resident (docs/SERVING.md §megasteps).

    ``arch="olmoe"`` runs the sparse-expert block of ``_olmoe_layer`` over
    the same pool: positions reach the rotary operator as data
    (``pos_idx``), there is no position table, and the pool keeps the
    weights' ``dtype`` while ids, positions, slots and frames stay float32
    inputs (and the masks made of them float32).

    ``arch="granite_hybrid"`` runs ``_granite_layer``: only its attention
    layers have ``kv_k_i`` / ``kv_v_i`` (Hkv, max_len, dh); a Mamba layer
    takes and returns ``ssm_state_i`` (B, H, P, N) and ``conv_state_i``
    (B, K-1, H*P + 2N), float32, one row a lane, and ``write_slot`` also
    tells it which lanes ride along (their rows come back bit for bit). The
    cache outputs follow the logits in ``decode_cache`` order.

    ``arch="deepseek_v3"`` runs ``_deepseek_v3_layer`` in its ABSORBED form
    over ONE pool a layer, ``kv_c_i`` (1, max_len, kv_lora_rank +
    qk_rope_head_dim): a lane's row is its normed latent beside its rotated
    shared key, no key or value of a head is ever made, and after the cache
    outputs and the token head comes ``moe_load (expert layers, experts)``,
    the rows each expert received from ALL the lanes of the step.

    ``arch="lfm2_moe"`` runs ``_lfm2_moe_layer``: an attention layer has
    ``kv_k_i`` / ``kv_v_i`` (Hkv, max_len, dh), a conv layer takes and
    returns ``conv_state_i`` (B, conv_kernel - 1, M), float32, one row a
    lane, and ``write_slot`` also tells it which lanes ride along (their
    rows come back bit for bit). The cache outputs follow the logits in
    ``decode_cache`` order, then the token head, then ``moe_load``.

    ``arch="mimo_v2_flash"`` runs ``_mimo_layer``: a full layer has
    ``kv_k_i`` (Hkv, max_len, head_dim) and ``kv_v_i`` (Hkv, max_len,
    v_head_dim); a window layer takes and returns ``ring_k_i`` /
    ``ring_v_i`` (B, swa Hkv, sliding_window, ·), one ring a lane, written
    at ``pos_idx`` mod the window (``KVRingWrite``) and read whole
    (``KVRingAttention``, the layer's sink in its softmax): no page and no
    mask of the pool. The cache outputs follow the logits in
    ``decode_cache`` order, then the token head, then ``moe_load`` (the
    rows each of ALL the experts received, held here or not).

    ``arch="phi4flash"`` runs ``_phi4flash_layer``: a Mamba-1 layer takes
    and returns ``ssm_state_i`` (B, N, E) and ``conv_state_i`` (B, K-1, E),
    float32; a window layer its rings ``ring_k_i`` / ``ring_v_i``
    (B, Hkv / 2, sliding_window, 2 * head_dim); layer N/2 + 1 writes the ONE
    pool pair ``kv_k_i`` / ``kv_v_i`` (Hkv / 2 heads of 2 * head_dim) and
    reads it, and every cross layer behind it takes that layer's UPDATED
    pools as operands (the token's own key among them) and writes nothing:
    eight ``KVPoolAttention`` nodes, one ``KVPoolSlotWrite``, one pair of
    cache outputs. ``m``, layer N/2's scan before its gate, is carried to
    the gated memory units inside the step and never cached.

    ``arch="nemotron_h"`` runs ``_nemotron_h_layer``: a Mamba layer takes
    and returns ``ssm_state_i`` (B, H, P, N) and ``conv_state_i`` (B, K-1,
    H*P + 2GN), float32; an attention layer has ``kv_k_i`` / ``kv_v_i``
    (Hkv, max_len, dh); an expert block keeps nothing. The cache outputs
    follow the logits in ``decode_cache`` order, then the token head, then
    ``moe_load`` (the rows each of ALL the experts received, held here or
    not), one row an EXPERT layer.

    ``arch="ouro"`` runs ``_ouro_layer`` ``total_ut_steps`` times over: a
    layer's ONE pool pair ``kv_k_i`` / ``kv_v_i`` holds ``passes x max_len``
    slots, pass ``u``'s keys and values ``u * max_len`` slots in (whole
    frames), so pass ``u`` writes slot ``write_slot + u * max_len`` and reads
    the frames ``page_table + u * max_len / page_size``: one page table and
    one allocation a lane cover every pass, and a pass never reads another's
    keys. ``KVPoolSlotWrite`` / ``KVPoolAttention`` / ``KVPageMask`` are the
    operators every arch has, handed the moved slot and table. A buffer is
    written ``passes`` times a step, in place where it is donated. The
    trailing ``greedy_token`` is (B, 2): the token and, riding the same small
    read, the pass that fed its head (``_ouro_head``).

    ``arch="laguna"`` runs ``_laguna_layer`` over ``mimo_v2_flash``'s cache
    (``_window_step_attend``: ``kv_k_<i>`` / ``kv_v_<i>`` pools for a full
    layer, ``ring_k_<i>`` / ``ring_v_<i>`` rings for a window layer, the
    same ``num_kv_heads`` in both), a full layer's query ``num_heads`` wide
    and a window layer's ``swa_num_heads``; ``moe_load`` last.

    ``arch="longcat_flash"`` runs ``_longcat_layer`` ABSORBED over TWO pools
    a layer, ``kv_c_<2i>`` and ``kv_c_<2i + 1>`` (``deepseek_v3``'s row, one
    a sublayer); after the cache outputs and the token head comes
    ``moe_load (layers, experts + zero experts)``.

    ``page_size`` is the decoder's (``PagedKVDecoder``'s default here); it
    must divide ``max_len``.
    """
    builders = {"olmoe": _olmoe_decode_symbol,
                "granite_hybrid": _granite_decode_symbol,
                "deepseek_v3": _deepseek_v3_decode_symbol,
                "lfm2_moe": _lfm2_moe_decode_symbol,
                "mimo_v2_flash": _mimo_decode_symbol,
                "phi4flash": _phi4flash_decode_symbol,
                "nemotron_h": _nemotron_h_decode_symbol,
                "dots3_note": _dots3_decode_symbol,
                "ouro": _ouro_decode_symbol,
                "laguna": _laguna_decode_symbol,
                "longcat_flash": _longcat_decode_symbol}
    if arch in builders:
        return builders[arch](
            vocab_size=vocab_size, num_layers=num_layers,
            num_slots=max_len, page_size=page_size, num_heads=num_heads,
            model_dim=model_dim, ffn_dim=ffn_dim, token_out=token_out,
            **kwargs)
    _refuse_arch(arch, "get_decode_symbol")
    return _pool_rows_symbol(
        vocab_size, num_layers, num_heads, model_dim, ffn_dim,
        pos_len or max_len, seq_len=1,
        pool_inputs=lambda pos: _pool_step_inputs(pos, max_len, page_size),
        token_name="greedy_token" if token_out else None)


def get_chunk_symbol(vocab_size=32000, num_layers=6, num_heads=8,
                     model_dim=512, ffn_dim=2048, chunk_len=8,
                     total_slots=64, pos_len=64, token_out=True, **kwargs):
    """Rectangular T-token chunk graph over the shared KV pool
    (docs/SERVING.md §Prefix cache & speculative decoding): ONE lane's
    next ``chunk_len`` positions scored — and optionally written — in a
    single dispatch. This is both the chunked-prefill program (admit
    computes only the un-cached tail of a prompt, chunk by chunk) and the
    speculative VERIFY program (the target model scores all γ+1 draft
    positions at once) — same symbol, different T. It is the decode
    graph with positions for rows instead of lanes (``_pool_rows_symbol``).

    Inputs beyond the weights:
      - ``data`` (1, T): the chunk's token ids (pad rows = 0).
      - ``pos_idx`` (1, T): absolute positions per row (pad rows clamp
        to 0; their writes are zeroed so the value never lands).
      - ``write_onehot`` (T, total_slots): row j's write slot in the
        pool. An ALL-ZERO row writes nothing — that is both the
        pad-row idiom and the zero-write REPLAY mode (a fully-cached
        prompt re-scores its last chunk against the stored pages:
        ``kv·1 + Σ(0·new) = kv`` bitwise, so replay logits are
        bit-identical to the cold chunked prefill that wrote them).
      - ``att_mask`` (T, total_slots): additive score mask per row — 0 on
        the lane's earlier slots AND on in-chunk slots of positions
        <= row j (intra-chunk causality is enforced HERE: all T writes
        land in the pool before attention, the mask hides the future
        ones). A fully-masked pad row softmaxes uniformly over garbage
        and is discarded — finite, never NaN (max-subtraction zeroes the
        row first).
      - ``kv_k_i`` / ``kv_v_i``: the pool buffers, in either layout, as in
        ``get_decode_symbol``.

    Outputs: ``[logits (T, vocab), k'_0, v'_0, ...]`` plus — with
    ``token_out=True`` — a trailing on-device ``chunk_token (T,)`` argmax
    head so the speculative accept loop pulls T ids, not T·vocab floats.
    """
    _refuse_arch(kwargs.get("arch", "vaswani"), "get_chunk_symbol")
    onehot = sym.Variable("write_onehot")

    def write(i, news):
        return [sym.KVPoolWrite(sym.Variable("kv_%s_%d" % (tag, i)), rows,
                                onehot, name="layer%d_%supd" % (i, tag))
                for tag, rows in news.items()]

    return _pool_rows_symbol(
        vocab_size, num_layers, num_heads, model_dim, ffn_dim, pos_len,
        seq_len=int(chunk_len),
        pool_inputs=lambda pos: (write, dict(mask=sym.Variable("att_mask"))),
        token_name="chunk_token" if token_out else None)


# --------------------------------------------------------------------- OLMoE
def _olmoe_layer(x, i, positions, seq_len, attend, *, num_heads, head_dim,
                 model_dim, ffn_dim, num_experts, num_experts_per_tok,
                 rope_theta, rms_eps):
    """One OLMoE block on x (B, T, M) -> (x', load (E,)): pre-norm RMSNorm,
    one bias-free qkv projection, RMSNorm over the WHOLE projected q and k
    vectors before the split into heads, rotary positions on q and k, a
    bias-free output projection, then the sparse-expert feed-forward.
    ``attend(i, q, k, v)`` is the one thing the prefill and the decode
    graph do differently: it takes the normed, rotated head-major
    (B, H, T, dh) tensors and returns attention's (B, H, T, dh) output."""
    name = "layer%d" % i
    width = num_heads * head_dim
    h = sym.RMSNorm(x, eps=rms_eps, name="%s_ln1" % name)
    qkv = sym.FullyConnected(data=h, num_hidden=3 * width, no_bias=True,
                             flatten=False, name="%s_qkv" % name)
    q, k, v = (sym.slice_axis(qkv, axis=2, begin=j * width,
                              end=(j + 1) * width) for j in range(3))
    q = sym.RMSNorm(q, eps=rms_eps, name="%s_qnorm" % name)
    k = sym.RMSNorm(k, eps=rms_eps, name="%s_knorm" % name)
    q, k, v = (_split_heads(a, seq_len, num_heads, head_dim)
               for a in (q, k, v))
    q = sym.RotaryEmbedding(q, positions, base=rope_theta,
                            name="%s_qrope" % name)
    k = sym.RotaryEmbedding(k, positions, base=rope_theta,
                            name="%s_krope" % name)
    att = _merge_heads(attend(i, q, k, v), seq_len, width)
    x = x + sym.FullyConnected(data=att, num_hidden=model_dim, no_bias=True,
                               flatten=False, name="%s_proj" % name)
    h = sym.RMSNorm(x, eps=rms_eps, name="%s_ln2" % name)
    moe = sym.MoEFeedForward(
        sym.Reshape(h, shape=(-1, model_dim)),
        sym.Variable("%s_router_weight" % name),
        sym.Variable("%s_experts_gate_weight" % name),
        sym.Variable("%s_experts_up_weight" % name),
        sym.Variable("%s_experts_down_weight" % name),
        num_experts=num_experts, num_hidden=ffn_dim,
        num_experts_per_tok=num_experts_per_tok, name="%s_moe" % name)
    return x + sym.Reshape(moe[0], shape=(-1, seq_len, model_dim)), moe[1]


def _olmoe_head(x, vocab_size, model_dim, rms_eps):
    """Final norm and the untied, bias-free head; logits leave in float32
    (the matmul's accumulator) whatever the weights' type."""
    x = sym.RMSNorm(x, eps=rms_eps, name="final_ln")
    return sym.FullyConnected(
        data=sym.Reshape(x, shape=(-1, model_dim)), num_hidden=vocab_size,
        no_bias=True, out_dtype="float32", name="lm_head")


def _olmoe_sizes(num_heads, model_dim, ffn_dim, head_dim=None, num_experts=64,
                 num_experts_per_tok=8, rope_theta=10000.0, rms_eps=1e-5,
                 **kwargs):
    """``_olmoe_layer``'s keywords from a builder's (defaults: OLMoE-1B-7B's;
    keywords of the other architecture, such as ``pos_len``, are dropped)."""
    return dict(num_heads=num_heads, head_dim=head_dim or model_dim // num_heads,
                model_dim=model_dim, ffn_dim=ffn_dim, num_experts=num_experts,
                num_experts_per_tok=num_experts_per_tok,
                rope_theta=rope_theta, rms_eps=rms_eps)


def _olmoe_prefill_symbol(vocab_size, num_layers, prefill_len, **sizes):
    block = _olmoe_sizes(**sizes)
    data = sym.Variable("data")  # (B, P) token ids, right-padded
    x = sym.Embedding(data=data, input_dim=vocab_size,
                      output_dim=block["model_dim"], name="embed")
    positions = sym.Reshape(sym._arange(start=0, stop=prefill_len),
                            shape=(1, prefill_len))
    kvs, loads = [], []

    def attend(i, q, k, v):
        kvs.extend([k, v])
        return sym.MultiHeadAttention(query=q, key=k, value=v, causal=True,
                                      name="layer%d_att" % i)

    for i in range(num_layers):
        x, load = _olmoe_layer(x, i, positions, prefill_len, attend, **block)
        loads.append(sym.Reshape(load, shape=(1, -1)))
    logits = _olmoe_head(_last_real_row(x, sym.Variable("length")),
                         vocab_size, block["model_dim"], block["rms_eps"])
    return sym.Group([logits] + kvs
                     + [sym.Concat(*loads, dim=0, name="moe_load")])


def _olmoe_decode_symbol(vocab_size, num_layers, num_slots, page_size,
                         token_out=True, **sizes):
    block = _olmoe_sizes(**sizes)
    num_heads, dh, model_dim = (block[k] for k in ("num_heads", "head_dim",
                                                   "model_dim"))
    data = sym.Variable("data")
    pos_idx = sym.Variable("pos_idx")
    write, read = _pool_step_inputs(pos_idx, num_slots, page_size)
    kv_outs = []

    def attend(i, q, k_new, v_new):
        # one token a lane: the head-major (B, H, 1, dh) tensors are the
        # pool's rows (B, H, dh)
        k_new, v_new, q = (sym.Reshape(a, shape=(-1, num_heads, dh))
                           for a in (k_new, v_new, q))
        ctx = _pool_attend(i, q, k_new, v_new, write, read, kv_outs)
        return sym.Reshape(ctx, shape=(-1, num_heads, 1, dh))

    x = sym.Embedding(data=data, input_dim=vocab_size, output_dim=model_dim,
                      name="embed")  # (B, 1, M)
    for i in range(num_layers):
        x, _ = _olmoe_layer(x, i, pos_idx, 1, attend, **block)
    return _token_head(
        _olmoe_head(x, vocab_size, model_dim, block["rms_eps"]), kv_outs,
        "greedy_token" if token_out else None)


# ------------------------------------------------------------ Granite hybrid
def _granite_sizes(num_layers, num_heads, model_dim, ffn_dim, layer_types,
                   num_kv_heads=None, head_dim=None, mamba_heads=None,
                   mamba_head_dim=64, mamba_state=128, mamba_conv=4,
                   mamba_chunk=256, embedding_multiplier=1.0,
                   attention_multiplier=None, residual_multiplier=1.0,
                   logits_scaling=1.0, rms_eps=1e-5, dtype="float32",
                   **kwargs):
    """``_granite_layer``'s keywords from a builder's (defaults: a Mamba-2
    mixer of expansion 2 and attention scaled by 1/sqrt(head_dim); keywords of
    the other architectures are dropped)."""
    kinds = tuple(layer_types)
    if len(kinds) != num_layers or set(kinds) - {"mamba", "attention"}:
        raise MXNetError("granite_hybrid: layer_types must name %d layers, "
                         "each 'mamba' or 'attention', got %r"
                         % (num_layers, kinds))
    head_dim = head_dim or model_dim // num_heads
    return dict(
        layer_types=kinds, num_heads=num_heads,
        num_kv_heads=num_kv_heads or num_heads, head_dim=head_dim,
        model_dim=model_dim, ffn_dim=ffn_dim,
        mamba_heads=mamba_heads or 2 * model_dim // mamba_head_dim,
        mamba_head_dim=mamba_head_dim, mamba_state=mamba_state,
        mamba_conv=mamba_conv, mamba_chunk=mamba_chunk,
        embedding_multiplier=float(embedding_multiplier),
        attention_multiplier=float(attention_multiplier
                                   or head_dim ** -0.5),
        residual_multiplier=float(residual_multiplier),
        logits_scaling=float(logits_scaling), rms_eps=rms_eps, dtype=dtype)


def _mamba_core(op, i, xbc, dt, block, **inputs):
    """One of ops/ssm.py's two operators on Mamba layer ``i``'s weights; a
    block with several groups of B and C names them (``mamba_groups``)."""
    name = "layer%d" % i
    if block.get("mamba_groups", 1) > 1:
        inputs["num_groups"] = block["mamba_groups"]
    return op(xbc, dt, *(sym.Variable("%s_mamba_%s" % (name, w)) for w in
                         ("conv_weight", "conv_bias", "dt_bias", "A_log", "D")),
              num_heads=block["mamba_heads"], head_dim=block["mamba_head_dim"],
              state_size=block["mamba_state"],
              conv_kernel=block["mamba_conv"], name="%s_mamba_core" % name,
              **inputs)


def _mamba_conv_dim(block):
    """Features of a Mamba-2 layer's xBC: [x (H*P) | B (G*N) | C (G*N)]."""
    return block["mamba_heads"] * block["mamba_head_dim"] \
        + 2 * block.get("mamba_groups", 1) * block["mamba_state"]


def _mamba2_mixer(fc, h, i, scan, block):
    """The Mamba-2 mixer on the normed h (B, T, M): one bias-free projection
    to [z | xBC | dt], asked for in float32 (the core computes so);
    ``scan(i, xbc, dt)`` runs the core and returns y (B, T, H*P); the gated
    norm ``rms(y * silu(z))``, over all H*P features where B and C are one
    group and over each group's H*P / G where they are several
    (``MambaRMSNormGated``'s ``group_size``, the gate first), back to the
    weights' type; the output projection to ``model_dim``."""
    name = "layer%d" % i
    heads, groups = block["mamba_heads"], block.get("mamba_groups", 1)
    inner = heads * block["mamba_head_dim"]
    conv_dim = _mamba_conv_dim(block)
    zxbcdt = fc(h, inner + conv_dim + heads, "mamba_in", out_dtype="float32")
    ends = (0, inner, inner + conv_dim, inner + conv_dim + heads)
    z, xbc, dt = (sym.slice_axis(zxbcdt, axis=2, begin=a, end=b)
                  for a, b in zip(ends, ends[1:]))
    y = scan(i, xbc, dt) * sym.Activation(z, act_type="silu")
    if groups == 1:
        y = sym.RMSNorm(y, eps=block["rms_eps"], name="%s_mamba_norm" % name)
    else:   # the statistics a group, the learned scale a feature
        y = sym.Reshape(sym.RMSNorm(
            sym.Reshape(y, shape=(0, 0, groups, -1)),
            sym.Reshape(sym.Variable("%s_mamba_norm_gamma" % name,
                                     shape=(inner,)), shape=(groups, -1)),
            eps=block["rms_eps"], name="%s_mamba_norm" % name),
            shape=(0, 0, -1))
    return fc(sym.Cast(y, dtype=block["dtype"]), block["model_dim"],
              "mamba_out")


def _gqa_mixer(fc, h, i, seq_len, attend, block):
    """Grouped-query attention WITHOUT positions on the normed h (B, T, M):
    one bias-free projection to [q | k | v] with fewer key/value heads than
    query heads; ``attend(i, q, k, v)`` takes the head-major (B, H or Hkv,
    T, dh) tensors and returns (B, H, T, dh); the output projection."""
    hq, hkv, dh = block["num_heads"], block["num_kv_heads"], block["head_dim"]
    q, k, v = _grouped_qkv(fc, h, seq_len, hq, hkv, dh)
    return fc(_merge_heads(attend(i, q, k, v), seq_len, hq * dh),
              block["model_dim"], "proj")


def _gated_mlp(fc, h, width, out_width, tag):
    """The gated SiLU MLP on h (B, T, M): ``fc(h, width, tag)`` is the
    layer's bias-free projection; gate and up rows live in ONE matrix
    ``<tag>_in`` (gate rows first), ``silu(gate) * up`` goes through
    ``<tag>_out``."""
    ab = fc(h, 2 * width, tag + "_in")
    gated = sym.Activation(sym.slice_axis(ab, axis=2, begin=0, end=width),
                           act_type="silu") \
        * sym.slice_axis(ab, axis=2, begin=width, end=2 * width)
    return fc(gated, out_width, tag + "_out")


def _grouped_qkv(fc, h, seq_len, hq, hkv, dh, dv=None):
    """Grouped-query attention's three head-major tensors from ONE bias-free
    projection ``qkv`` of h (B, T, M), rows q, then k, then v, each
    head-major: q (B, hq, T, dh), k (B, hkv, T, dh) and v (B, hkv, T, dv),
    a value head as wide as a key head unless ``dv`` says otherwise."""
    dv = dv or dh
    ends = (0, hq * dh, (hq + hkv) * dh, (hq + hkv) * dh + hkv * dv)
    qkv = fc(h, ends[-1], "qkv")
    return (_split_heads(sym.slice_axis(qkv, axis=2, begin=a, end=b),
                         seq_len, n, width)
            for a, b, n, width in zip(ends, ends[1:], (hq, hkv, hkv),
                                      (dh, dh, dv)))


def _granite_layer(x, i, seq_len, attend, scan, block):
    """One Granite 4.0-H block on x (B, T, M): pre-norm RMSNorm, a mixer
    chosen by ``layer_types[i]``, then the gated SiLU MLP every layer has,
    both branches scaled by ``residual_multiplier``.

    ``mamba``: one bias-free projection to [z | xBC | dt], asked for in
    float32 (the core computes so); ``scan(i, xbc, dt)`` runs the core and
    returns y (B, T, H*P); ``rms(y * silu(z))`` over all H*P features, back to
    the weights' type, and the output projection. ``attention``: one
    bias-free projection to [q | k | v] with fewer key/value heads than query
    heads, no positions; ``attend(i, q, k, v)`` takes the head-major
    (B, H or Hkv, T, dh) tensors and returns (B, H, T, dh). ``scan`` and
    ``attend`` are the two things the prefill and the decode graph do
    differently."""
    name = "layer%d" % i
    d, eps, res = block["model_dim"], block["rms_eps"], \
        block["residual_multiplier"]
    fc = lambda data, width, tag, **kw: sym.FullyConnected(
        data=data, num_hidden=width, no_bias=True, flatten=False,
        name="%s_%s" % (name, tag), **kw)
    h = sym.RMSNorm(x, eps=eps, name="%s_ln1" % name)
    if block["layer_types"][i] == "mamba":
        mixed = _mamba2_mixer(fc, h, i, scan, block)
    else:
        mixed = _gqa_mixer(fc, h, i, seq_len, attend, block)
    x = x + mixed * res
    return x + _gated_mlp(fc, sym.RMSNorm(x, eps=eps, name="%s_ln2" % name),
                          block["ffn_dim"], d, "mlp") * res


def _granite_stack(data, vocab_size, num_layers, seq_len, attend, scan, block,
                   length=None):
    """Embedding (scaled, and tied to the head), the layers, the final norm
    and the head: ``data`` (B, T) -> float32 logits (B·T, vocab), or
    (B, vocab) where a prefill gives each prompt's ``length``
    (``_last_real_row``)."""
    table = sym.Variable("embed_weight")
    d = block["model_dim"]
    x = sym.Embedding(data=data, weight=table, input_dim=vocab_size,
                      output_dim=d, name="embed") \
        * block["embedding_multiplier"]
    for i in range(num_layers):
        x = _granite_layer(x, i, seq_len, attend, scan, block)
    if length is not None:
        x = _last_real_row(x, length)
    x = sym.RMSNorm(x, eps=block["rms_eps"], name="final_ln")
    return sym.FullyConnected(
        data=sym.Reshape(x, shape=(-1, d)), weight=table,
        num_hidden=vocab_size, no_bias=True, out_dtype="float32",
        name="lm_head") / block["logits_scaling"]


def _rows_and_pools_prefill(block, length, cache):
    """``(attend, scan)`` of a prefill whose mixers are position-free
    grouped-query attention and Mamba-2 (``granite_hybrid``,
    ``nemotron_h``): each appends what the cache keeps of its layer to
    ``cache``, K and V over the bucket or the state and the columns at
    ``length``; the layers are built in order, so is the list."""
    def attend(i, q, k, v):
        cache.extend([k, v])
        return sym.MultiHeadAttention(
            query=q, key=k, value=v, causal=True,
            scale=block["attention_multiplier"], name="layer%d_att" % i)

    def scan(i, xbc, dt):
        core = _mamba_core(sym.Mamba2Scan, i, xbc, dt, block, length=length,
                           chunk_size=block["mamba_chunk"])
        cache.extend([core[1], core[2]])
        return core[0]

    return attend, scan


def _granite_prefill_symbol(vocab_size, num_layers, prefill_len, **sizes):
    block = _granite_sizes(num_layers, **sizes)
    length = sym.Variable("length")     # (B, 1): real tokens of the bucket
    cache = []
    attend, scan = _rows_and_pools_prefill(block, length, cache)
    logits = _granite_stack(sym.Variable("data"), vocab_size, num_layers,
                            prefill_len, attend, scan, block, length=length)
    return sym.Group([logits] + cache)


def _rows_and_pools_step(block, num_slots, page_size, cache):
    """``(attend, scan)`` of a decode step over the same two mixers: pools
    addressed by slot for attention, a lane's row of state and columns for
    Mamba-2, ``write_slot`` telling both which lanes ride along. Each appends
    its layer's UPDATED cache to ``cache``, in layer order."""
    hq, hkv, dh = (block[k] for k in ("num_heads", "num_kv_heads", "head_dim"))
    pos_idx = sym.Variable("pos_idx")
    write_slot = sym.Variable("write_slot")
    write, read = _pool_step_inputs(pos_idx, num_slots, page_size, write_slot)

    def attend(i, q, k_new, v_new):
        # one token a lane: the head-major (B, H, 1, dh) tensors are the
        # pool's rows (B, H, dh)
        q, k_new, v_new = (sym.Reshape(a, shape=(-1, n, dh)) for a, n in
                           ((q, hq), (k_new, hkv), (v_new, hkv)))
        ctx = _pool_attend(i, q, k_new, v_new, write, read, cache,
                           scale=block["attention_multiplier"])
        return sym.Reshape(ctx, shape=(-1, hq, 1, dh))

    def scan(i, xbc, dt):
        core = _mamba_core(
            sym.Mamba2Step, i, sym.Reshape(xbc, shape=(0, -1)),
            sym.Reshape(dt, shape=(0, -1)), block,
            ssm_state=sym.Variable("ssm_state_%d" % i),
            conv_state=sym.Variable("conv_state_%d" % i), stepped=write_slot)
        cache.extend([core[1], core[2]])
        return sym.Reshape(core[0], shape=(0, 1, -1))

    return attend, scan


def _granite_decode_symbol(vocab_size, num_layers, num_slots, page_size,
                           token_out=True, **sizes):
    block = _granite_sizes(num_layers, **sizes)
    cache = []
    attend, scan = _rows_and_pools_step(block, num_slots, page_size, cache)
    logits = _granite_stack(sym.Variable("data"), vocab_size, num_layers, 1,
                            attend, scan, block)
    return _token_head(logits, cache, "greedy_token" if token_out else None)


# ------------------- Nemotron-H (ONE mixer a block: Mamba-2 | experts | attention)
def _lane_tiles(width):
    """``width`` rounded up to whole tiles of the chip's 128 lanes: what an
    expert stack of a width that is none (1,856 = 14.5) is STORED at, zero
    columns of ``up`` and zero rows of ``down`` behind the real ones
    (relu(0)^2 = 0: the padding adds exactly nothing). The grouped-matmul
    kernel takes whole lane tiles only, and the chip ruled for the padding
    (``ops/pallas_grouped_matmul.supported``)."""
    return -(-width // _LANES) * _LANES


def _nemotron_h_sizes(num_layers, num_heads, model_dim, layer_types,
                      ffn_dim=None, moe_ffn_dim=None, shared_ffn_dim=None,
                      num_kv_heads=None, head_dim=None, mamba_heads=None,
                      mamba_head_dim=64, mamba_state=128, mamba_groups=8,
                      mamba_conv=4, mamba_chunk=128, num_experts=128,
                      num_experts_per_tok=6, num_local_experts=0,
                      local_expert_offset=0, routed_scaling_factor=1.0,
                      norm_topk_prob=True, rms_eps=1e-5, dtype="float32",
                      **kwargs):
    """``_nemotron_h_layer``'s keywords from a builder's: ``layer_types``
    names each block's ONE mixer (``hybrid_override_pattern``'s letters:
    ``M`` "mamba", ``E`` "moe", ``*`` "attention"; a dense ``-`` MLP block
    is not built, and ``ffn_dim``, its width, is read by nothing);
    ``moe_ffn_dim`` is one routed expert's width as published and
    ``moe_ffn_stored`` what its stacks are stored at (``_lane_tiles``),
    ``shared_ffn_dim`` the shared expert's; ``num_local_experts`` = 0 holds
    every expert; the Mamba-2 inner width is ``mamba_heads x
    mamba_head_dim`` (NOT an expansion of ``model_dim``); keywords of the
    other architectures are dropped."""
    kinds = tuple(layer_types)
    if len(kinds) != num_layers \
            or set(kinds) - {"mamba", "moe", "attention"}:
        raise MXNetError("nemotron_h: layer_types must name %d layers, each "
                         "'mamba', 'moe' or 'attention', got %r"
                         % (num_layers, kinds))
    if "moe" in kinds and not (moe_ffn_dim and shared_ffn_dim):
        raise MXNetError("nemotron_h: an expert layer needs moe_ffn_dim and "
                         "shared_ffn_dim")
    head_dim = head_dim or model_dim // num_heads
    return dict(
        num_layers=num_layers, layer_types=kinds, num_heads=num_heads,
        num_kv_heads=num_kv_heads or num_heads, head_dim=head_dim,
        model_dim=model_dim,
        mamba_heads=mamba_heads or 2 * model_dim // mamba_head_dim,
        mamba_head_dim=mamba_head_dim, mamba_state=mamba_state,
        mamba_groups=int(mamba_groups), mamba_conv=mamba_conv,
        mamba_chunk=mamba_chunk, attention_multiplier=head_dim ** -0.5,
        moe_ffn_dim=moe_ffn_dim and _lane_tiles(moe_ffn_dim),
        shared_ffn_dim=shared_ffn_dim, experts_activation="relu2",
        num_experts=num_experts, num_experts_per_tok=num_experts_per_tok,
        num_local_experts=int(num_local_experts),
        local_expert_offset=int(local_expert_offset),
        routed_scaling_factor=float(routed_scaling_factor),
        norm_topk_prob=bool(norm_topk_prob), rms_eps=rms_eps, dtype=dtype)


def _nemotron_h_layer(x, i, seq_len, attend, scan, block):
    """One ``model_type: nemotron_h`` block on x (B, T, M) -> (x', load (E,)
    or None): ``x + mixer(RMSNorm(x))``, ONE mixer a block, named by
    ``layer_types[i]``; no MLP follows a mixer.

    ``mamba``: ``_mamba2_mixer`` with ``mamba_groups`` groups of B and C and
    the gated norm a group. ``attention``: ``_gqa_mixer``, no positions (the
    Mamba layers carry order), scores over sqrt(head_dim). ``scan`` and
    ``attend`` are what the prefill and the decode graph do differently, as
    in ``_granite_layer``. ``moe``: ``_sigmoid_experts``' router over UNGATED
    experts, ``down_e(relu(up_e h)^2)``, of which the layer may hold a
    share, beside ONE shared expert of the same form every token takes
    (``shared_up``, ``shared_down``)."""
    name = "layer%d" % i
    d, kind = block["model_dim"], block["layer_types"][i]
    fc = lambda data, width, tag, **kw: sym.FullyConnected(
        data=data, num_hidden=width, no_bias=True, flatten=False,
        name="%s_%s" % (name, tag), **kw)
    h = sym.RMSNorm(x, eps=block["rms_eps"], name="%s_ln1" % name)
    if kind == "mamba":
        return x + _mamba2_mixer(fc, h, i, scan, block), None
    if kind == "attention":
        return x + _gqa_mixer(fc, h, i, seq_len, attend, block), None
    moe = _sigmoid_experts(h, name, block)
    shared = sym.square(sym.Activation(
        fc(h, block["shared_ffn_dim"], "shared_up"), act_type="relu"))
    return x + sym.Reshape(moe[0], shape=(-1, seq_len, d)) \
        + fc(shared, d, "shared_down"), moe[1]


def _nemotron_h_stack(vocab_size, seq_len, attend, scan, block, length=None):
    """``_deepseek_v3_stack`` (embedding, the blocks, final norm and untied
    head; ``moe_load`` a row an expert block) over this block's layer, which
    takes no positions and a ``scan`` beside its ``attend``."""
    return _deepseek_v3_stack(
        vocab_size, seq_len, None, attend, block, length=length,
        layer=lambda x, i, _positions, seq, att, blk: _nemotron_h_layer(
            x, i, seq, att, scan, blk))


def _nemotron_h_prefill_symbol(vocab_size, num_layers, prefill_len, **sizes):
    block = _nemotron_h_sizes(num_layers, **sizes)
    length = sym.Variable("length")     # (B, 1): real tokens of the bucket
    cache = []
    attend, scan = _rows_and_pools_prefill(block, length, cache)
    logits, load = _nemotron_h_stack(vocab_size, prefill_len, attend, scan,
                                     block, length=length)
    return sym.Group([logits] + cache + load)


def _nemotron_h_decode_symbol(vocab_size, num_layers, num_slots, page_size,
                              token_out=True, **sizes):
    block = _nemotron_h_sizes(num_layers, **sizes)
    cache = []
    attend, scan = _rows_and_pools_step(block, num_slots, page_size, cache)
    logits, load = _nemotron_h_stack(vocab_size, 1, attend, scan, block)
    # moe_load LAST: the cache and the token head keep their places
    return sym.Group([_token_head(
        logits, cache, "greedy_token" if token_out else None)] + load)


def _mamba2_param_shapes(block, n):
    """{name: shape} of Mamba-2 layer ``n``'s mixer (``n`` its prefix)."""
    h, k = block["mamba_heads"], block["mamba_conv"]
    inner, conv_dim = h * block["mamba_head_dim"], _mamba_conv_dim(block)
    return {
        n + "mamba_in_weight": (inner + conv_dim + h, block["model_dim"]),
        n + "mamba_conv_weight": (conv_dim, k),
        n + "mamba_conv_bias": (conv_dim,), n + "mamba_dt_bias": (h,),
        n + "mamba_A_log": (h,), n + "mamba_D": (h,),
        n + "mamba_norm_gamma": (inner,),
        n + "mamba_out_weight": (block["model_dim"], inner)}


def _gqa_param_shapes(block, n):
    """{name: shape} of attention layer ``n``'s position-free mixer."""
    hq, hkv, dh = (block[k] for k in ("num_heads", "num_kv_heads", "head_dim"))
    return {n + "qkv_weight": ((hq + 2 * hkv) * dh, block["model_dim"]),
            n + "proj_weight": (block["model_dim"], hq * dh)}


def _nemotron_h_param_shapes(vocab_size, num_layers, **sizes):
    """The routed experts' two stacks are ``_lane_tiles(moe_ffn_dim)`` wide:
    whoever fills them leaves the columns of ``experts_up_weight`` and the
    rows of ``experts_down_weight`` past ``moe_ffn_dim`` ZERO."""
    block = _nemotron_h_sizes(num_layers, **sizes)
    d, e, f = block["model_dim"], block["num_experts"], block["moe_ffn_dim"]
    held, shared = block["num_local_experts"] or e, block["shared_ffn_dim"]
    shapes = {"embed_weight": (vocab_size, d), "final_ln_gamma": (d,),
              "lm_head_weight": (vocab_size, d)}
    for i, kind in enumerate(block["layer_types"]):
        n = "layer%d_" % i
        shapes[n + "ln1_gamma"] = (d,)
        if kind == "mamba":
            shapes.update(_mamba2_param_shapes(block, n))
        elif kind == "attention":
            shapes.update(_gqa_param_shapes(block, n))
        else:
            shapes.update({
                n + "router_weight": (e, d), n + "router_bias": (e,),
                n + "experts_up_weight": (held, d, f),
                n + "experts_down_weight": (held, f, d),
                n + "shared_up_weight": (shared, d),
                n + "shared_down_weight": (d, shared)})
    return shapes


# --------------------------------------------------- DeepSeek-V3 (latent attention)
def _deepseek_v3_sizes(num_layers, num_heads, model_dim, ffn_dim=None,
                       moe_ffn_dim=None, num_experts=64,
                       num_experts_per_tok=6, num_shared_experts=1,
                       first_dense_layers=1, qk_nope_head_dim=128,
                       qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
                       rope_theta=10000.0, rms_eps=1e-6,
                       routed_scaling_factor=1.0, norm_topk_prob=True,
                       **kwargs):
    """``_deepseek_v3_layer``'s keywords from a builder's (``ffn_dim`` is the
    leading dense layers' width, ``moe_ffn_dim`` one expert's; keywords of
    the other architectures are dropped)."""
    if not 0 <= first_dense_layers <= num_layers:
        raise MXNetError("deepseek_v3: first_dense_layers %d outside [0, %d]"
                         % (first_dense_layers, num_layers))
    if qk_rope_head_dim % 2:
        raise MXNetError("deepseek_v3: qk_rope_head_dim %d is odd"
                         % qk_rope_head_dim)
    return dict(
        num_layers=num_layers, num_heads=num_heads, model_dim=model_dim,
        ffn_dim=ffn_dim, moe_ffn_dim=moe_ffn_dim, num_experts=num_experts,
        num_experts_per_tok=num_experts_per_tok,
        num_shared_experts=num_shared_experts,
        first_dense_layers=first_dense_layers, nope=qk_nope_head_dim,
        rope=qk_rope_head_dim, v_dim=v_head_dim, latent=kv_lora_rank,
        rope_theta=float(rope_theta), rms_eps=rms_eps,
        routed_scaling_factor=float(routed_scaling_factor),
        norm_topk_prob=bool(norm_topk_prob),
        # the published softmax_scale: over the WHOLE query-key width
        scale=float(qk_nope_head_dim + qk_rope_head_dim) ** -0.5)


def _sigmoid_experts(h, name, block):
    """Layer ``name``'s routed experts on h (B, T, M) -> ``MoEFeedForward``'s
    (y (B·T, M), load (E,)): sigmoid scores, chosen on the score plus
    ``<name>_router_bias``, weighted by the unbiased score, renormalised and
    scaled as ``block`` says. Where ``block`` names a share
    (``num_local_experts`` of them from ``local_expert_offset`` on) the
    layer holds those experts alone and computes their part. Where
    ``block`` names ``experts_activation`` the experts are UNGATED, two
    stacks and that activation (``nemotron_h``'s ``"relu2"``)."""
    share = {k: block[k] for k in ("num_local_experts", "local_expert_offset")
             if block.get("num_local_experts")}
    stacks = ("experts_gate_weight", "experts_up_weight",
              "experts_down_weight")
    if block.get("experts_activation"):     # an UNGATED expert: up and down
        stacks = stacks[1:]
        share.update(gated=False, activation=block["experts_activation"])
    return sym.MoEFeedForward(
        sym.Reshape(h, shape=(-1, block["model_dim"])),
        *(sym.Variable("%s_%s" % (name, w)) for w in (
            "router_weight",) + stacks + ("router_bias",)),
        num_experts=block["num_experts"], num_hidden=block["moe_ffn_dim"],
        num_experts_per_tok=block["num_experts_per_tok"], scoring="sigmoid",
        router_bias=True, norm_topk_prob=block["norm_topk_prob"],
        routed_scaling_factor=block["routed_scaling_factor"],
        name="%s_moe" % name, **share)


def _latent_operands(h, fc, name, positions, seq_len, eps, *, heads, nope,
                     rope, latent, rope_theta, q_rank=None, rho_q=None,
                     rho_kv=None, beside=None):
    """Latent attention's operands of the normed h (B, T, M), the ONE copy
    ``deepseek_v3``, ``dots3_note`` (both kinds of layer) and
    ``longcat_flash`` (both sublayers) build from: ``((q_nope (B, H, T, nope),
    q_rope (B, H, T, rope), c (B, T, latent), k_r (B, 1, T, rope)),
    beside(c_q))``, the two rotary parts rotated over interleaved pairs at
    ``rope_theta``: what an ``attend`` closure takes, materialised in a
    prefill and absorbed in a step.

    ``fc(data, width, tag)`` is the layer's bias-free projection
    ``<name>_<tag>``. The query is ONE projection ``<name>_q`` to H heads of
    [q_nope | q_rope], or with ``q_rank`` a LOW RANK: ``c_q =
    RMSNorm(W_qa h)`` (``<name>_qa``, ``<name>_qnorm``) times ``rho_q`` where
    given, then ``<name>_qb`` (the factor reaches both parts of every head).
    ``[c | k_r] = W_kva h``; ``c = RMSNorm(c)`` (``<name>_kvnorm``) times
    ``rho_kv`` where given, so the factor reaches every head's key AND value
    through ``<name>_kvb`` and the cache keeps the scaled latent; the ONE
    ``k_r`` every head shares is not scaled. The defaults (no low rank, no
    factor) are ``deepseek_v3``'s and add no node. ``beside(c_q)`` builds
    what else reads the query latent (``dots3_note``'s indexer), after k_r
    and before the query is cut and rotated: the nodes' order is part of a
    graph's JSON."""
    rotate = lambda a, tag: sym.RotaryEmbedding(
        a, positions, base=rope_theta, name="%s_%s" % (name, tag),
        interleaved=True)
    c_q = None
    if q_rank:
        c_q = sym.RMSNorm(fc(h, q_rank, "qa"), eps=eps,
                          name="%s_qnorm" % name)
        if rho_q is not None:
            c_q = c_q * rho_q
    q = _split_heads(fc(h, heads * (nope + rope), "q") if c_q is None
                     else fc(c_q, heads * (nope + rope), "qb"),
                     seq_len, heads, nope + rope)
    kva = fc(h, latent + rope, "kva")
    c = sym.RMSNorm(sym.slice_axis(kva, axis=2, begin=0, end=latent),
                    eps=eps, name="%s_kvnorm" % name)
    if rho_kv is not None:
        c = c * rho_kv
    k_r = sym.Reshape(sym.slice_axis(kva, axis=2, begin=latent,
                                     end=latent + rope),
                      shape=(-1, 1, seq_len, rope))
    extra = beside(c_q) if beside else None
    return (sym.slice_axis(q, axis=3, begin=0, end=nope),
            rotate(sym.slice_axis(q, axis=3, begin=nope, end=nope + rope),
                   "qrope"),
            c, rotate(k_r, "krope")), extra


def _deepseek_v3_layer(x, i, positions, seq_len, attend, block):
    """One ``model_type: deepseek_v3`` block (no query-side low-rank
    projection) on x (B, T, M) -> (x', load (E,) or None for a dense layer).

    Latent attention (``_latent_operands`` at its defaults): a bias-free
    query projection to H heads of [q_nope | q_rope]; ONE bias-free
    projection to [c | k_r], the latent (normed, ``kvnorm``) and a single
    rotary key every head shares; rotary positions over interleaved pairs on
    q_rope and k_r. ``attend(i, q_nope, q_rope, c, k_r)`` is the one thing
    the prefill and the decode graph do differently: it takes (B, H, T,
    nope), (B, H, T, rope), (B, T, latent) and (B, 1, T, rope) and returns
    attention's (B, H, T, v_dim) output, through ``layer<i>_kvb_weight`` (H x
    [k_nope | v] rows over the latent) either materialised into keys and
    values or absorbed into the query and the output. Then the feed-forward:
    the gated SiLU MLP in the first ``first_dense_layers`` layers; after them
    sigmoid-routed experts (selected on the biased score, weighted by the
    unbiased one, renormalised, scaled) beside a shared gated MLP every
    token takes."""
    name = "layer%d" % i
    d, eps, hq = block["model_dim"], block["rms_eps"], block["num_heads"]
    fc = lambda data, width, tag: sym.FullyConnected(
        data=data, num_hidden=width, no_bias=True, flatten=False,
        name="%s_%s" % (name, tag))
    h = sym.RMSNorm(x, eps=eps, name="%s_ln1" % name)
    operands, _ = _latent_operands(
        h, fc, name, positions, seq_len, eps, heads=hq, nope=block["nope"],
        rope=block["rope"], latent=block["latent"],
        rope_theta=block["rope_theta"])
    att = attend(i, *operands)
    x = x + fc(_merge_heads(att, seq_len, hq * block["v_dim"]), d, "proj")
    return _deepseek_v3_ffn(x, i, fc, seq_len, block)


def _deepseek_v3_ffn(x, i, fc, seq_len, block):
    """The feed-forward half of a ``deepseek_v3`` block on x (B, T, M) ->
    (x', load (E,) or None): the gated SiLU MLP in the first
    ``first_dense_layers`` layers, after them ``_sigmoid_experts`` beside a
    shared gated MLP every token takes."""
    name, d = "layer%d" % i, block["model_dim"]
    h = sym.RMSNorm(x, eps=block["rms_eps"], name="%s_ln2" % name)
    if i < block["first_dense_layers"]:
        return x + _gated_mlp(fc, h, block["ffn_dim"], d, "mlp"), None
    moe = _sigmoid_experts(h, name, block)
    shared = _gated_mlp(
        fc, h, block["num_shared_experts"] * block["moe_ffn_dim"], d,
        "shared")
    return x + sym.Reshape(moe[0], shape=(-1, seq_len, d)) + shared, moe[1]


def _deepseek_v3_ffn_shapes(block, i):
    """{name: shape} of what ``_deepseek_v3_ffn`` loads for layer ``i``: the
    dense MLP's two matrices, or the router, the HELD experts' three stacks
    (all of them unless ``block`` names a share) and the shared MLP's two."""
    n, d, e = "layer%d_" % i, block["model_dim"], block["num_experts"]
    if i < block["first_dense_layers"]:
        return {n + "mlp_in_weight": (2 * block["ffn_dim"], d),
                n + "mlp_out_weight": (d, block["ffn_dim"])}
    f, held = block["moe_ffn_dim"], block.get("num_local_experts") or e
    shared = block["num_shared_experts"] * f
    return {n + "router_weight": (e, d), n + "router_bias": (e,),
            n + "experts_gate_weight": (held, d, f),
            n + "experts_up_weight": (held, d, f),
            n + "experts_down_weight": (held, f, d),
            n + "shared_in_weight": (2 * shared, d),
            n + "shared_out_weight": (d, shared)}


def _deepseek_v3_stack(vocab_size, seq_len, positions, attend, block,
                       layer=None, length=None):
    """Embedding, the layers, final norm and untied head: ``data`` (B, T) ->
    (float32 logits (B·T, vocab), moe_load (expert layers, experts)); logits
    (B, vocab) where a prefill gives each prompt's ``length``
    (``_last_real_row``). ``layer``: another block's layer of the same
    signature (``_mimo_layer``)."""
    layer = layer or _deepseek_v3_layer
    x = sym.Embedding(data=sym.Variable("data"), input_dim=vocab_size,
                      output_dim=block["model_dim"], name="embed")
    loads = []
    for i in range(block["num_layers"]):
        x, load = layer(x, i, positions, seq_len, attend, block)
        if load is not None:
            loads.append(sym.Reshape(load, shape=(1, -1)))
    if length is not None:
        x = _last_real_row(x, length)
    logits = _olmoe_head(x, vocab_size, block["model_dim"], block["rms_eps"])
    return logits, [sym.Concat(*loads, dim=0, name="moe_load")] if loads \
        else []


def _materialised(i, q_nope, q_rope, c, k_r, seq_len, hq, nope, v_dim, lat):
    """Latent attention MATERIALISED, a prefill's form: (what the cache
    keeps of the layer, one head of [c | k_r] (B, 1, T, lat + rope) as a
    pool's or a ring's rows; ``MultiHeadAttention``'s query, key and value,
    every head's key and value made of the latent through ``layer<i>_kvb``)."""
    row = sym.Concat(sym.Reshape(c, shape=(-1, 1, seq_len, lat)), k_r, dim=3)
    kv = _split_heads(sym.FullyConnected(
        data=c, num_hidden=hq * (nope + v_dim), no_bias=True,
        flatten=False, name="layer%d_kvb" % i), seq_len, hq, nope + v_dim)
    key = sym.Concat(sym.slice_axis(kv, axis=3, begin=0, end=nope),
                     sym.broadcast_axis(k_r, axis=1, size=hq), dim=3)
    return row, dict(
        query=sym.Concat(q_nope, q_rope, dim=3), key=key,
        value=sym.slice_axis(kv, axis=3, begin=nope, end=nope + v_dim))


def _absorbed(i, q_nope, q_rope, read, hq, nope, rope, v_dim, lat):
    """Latent attention ABSORBED, a step's form, on one token a lane: a
    head's rows of ``layer<i>_kvb_weight`` are [Wuk_h | Wuv_h] over the
    latent; Wuk goes into the query, ``read(query)`` gives the context over
    the cached [c | k_r] rows (R, H, lat), Wuv goes onto it, and no key or
    value of a head is ever made. Returns (B, H, 1, v_dim)."""
    w = sym.Reshape(sym.Variable("layer%d_kvb_weight" % i,
                                 shape=(hq * (nope + v_dim), lat)),
                    shape=(hq, nope + v_dim, lat))
    by_head = lambda a, width: sym.SwapAxis(
        sym.Reshape(a, shape=(-1, hq, width)), dim1=0, dim2=1)
    q_lat = sym.batch_dot(by_head(q_nope, nope),
                          sym.slice_axis(w, axis=1, begin=0, end=nope))
    query = sym.Concat(sym.SwapAxis(q_lat, dim1=0, dim2=1),
                       sym.Reshape(q_rope, shape=(-1, hq, rope)), dim=2)
    out = sym.batch_dot(
        by_head(read(query), lat),
        sym.slice_axis(w, axis=1, begin=nope, end=nope + v_dim),
        transpose_b=True)
    return sym.Reshape(sym.SwapAxis(out, dim1=0, dim2=1),
                       shape=(-1, hq, 1, v_dim))


def _latent_prefill_symbol(block, layer, vocab_size, prefill_len):
    """The prefill graph of a stack whose every attention is latent and
    keeps ONE pool (``deepseek_v3``; ``longcat_flash``, two a layer), its
    keys and values MATERIALISED: ``attend`` is called once an attention, in
    the cache's order, with that attention's index."""
    hq, nope, v_dim, lat = (block[k] for k in ("num_heads", "nope", "v_dim",
                                               "latent"))
    positions = sym.Reshape(sym._arange(start=0, stop=prefill_len),
                            shape=(1, prefill_len))
    cache = []

    def attend(i, q_nope, q_rope, c, k_r):
        row, operands = _materialised(i, q_nope, q_rope, c, k_r, prefill_len,
                                      hq, nope, v_dim, lat)
        cache.append(row)
        return sym.MultiHeadAttention(
            causal=True, scale=block["scale"], name="layer%d_att" % i,
            **operands)

    logits, load = _deepseek_v3_stack(vocab_size, prefill_len, positions,
                                      attend, block, layer=layer,
                                      length=sym.Variable("length"))
    return sym.Group([logits] + cache + load)


def _latent_decode_symbol(block, layer, vocab_size, num_slots, page_size,
                          token_out):
    """``_latent_prefill_symbol``'s stack one token a lane, its attentions
    ABSORBED over their pools ``kv_c_<i>``."""
    hq, nope, rope, v_dim, lat = (block[k] for k in (
        "num_heads", "nope", "rope", "v_dim", "latent"))
    pos_idx = sym.Variable("pos_idx")
    write, read = _pool_step_inputs(pos_idx, num_slots, page_size)
    cache = []

    def attend(i, q_nope, q_rope, c, k_r):
        # one token a lane: its row of the latent pool is [c | k_r]
        row = sym.Concat(sym.Reshape(c, shape=(-1, 1, lat)),
                         sym.Reshape(k_r, shape=(-1, 1, rope)), dim=2)
        pool, = write(i, {"c": row})
        cache.append(pool)
        # the pool is key (all its columns) and value (its first ``lat``)
        return _absorbed(
            i, q_nope, q_rope, lambda query: sym.KVPoolAttention(
                query, pool, pool, scale=block["scale"], value_dim=lat,
                name="layer%d_att" % i, **read),
            hq, nope, rope, v_dim, lat)

    logits, load = _deepseek_v3_stack(vocab_size, 1, pos_idx, attend, block,
                                      layer=layer)
    outs = [logits] + cache
    if token_out:
        outs.append(sym.argmax(logits, axis=-1, name="greedy_token"))
    return sym.Group(outs + load)


def _deepseek_v3_prefill_symbol(vocab_size, num_layers, prefill_len, **sizes):
    return _latent_prefill_symbol(_deepseek_v3_sizes(num_layers, **sizes),
                                  None, vocab_size, prefill_len)


def _deepseek_v3_decode_symbol(vocab_size, num_layers, num_slots, page_size,
                               token_out=True, **sizes):
    return _latent_decode_symbol(_deepseek_v3_sizes(num_layers, **sizes),
                                 None, vocab_size, num_slots, page_size,
                                 token_out)


def _latent_param_shapes(n, d, *, heads, nope, rope, v_dim, latent,
                         q_rank=None):
    """{name: shape} of what ``_latent_operands`` and the two forms of its
    ``attend`` load under the prefix ``n``, with the two norms around the
    attention and its output projection."""
    query = {n + "q_weight": (heads * (nope + rope), d)} if not q_rank else {
        n + "qa_weight": (q_rank, d), n + "qnorm_gamma": (q_rank,),
        n + "qb_weight": (heads * (nope + rope), q_rank)}
    return dict(query, **{
        n + "ln1_gamma": (d,), n + "kva_weight": (latent + rope, d),
        n + "kvnorm_gamma": (latent,),
        n + "kvb_weight": (heads * (nope + v_dim), latent),
        n + "proj_weight": (d, heads * v_dim), n + "ln2_gamma": (d,)})


def _deepseek_v3_param_shapes(vocab_size, num_layers, **sizes):
    block = _deepseek_v3_sizes(num_layers, **sizes)
    d = block["model_dim"]
    shapes = {"embed_weight": (vocab_size, d), "final_ln_gamma": (d,),
              "lm_head_weight": (vocab_size, d)}
    for i in range(num_layers):
        shapes.update(_latent_param_shapes(
            "layer%d_" % i, d, heads=block["num_heads"], nope=block["nope"],
            rope=block["rope"], v_dim=block["v_dim"],
            latent=block["latent"]))
        shapes.update(_deepseek_v3_ffn_shapes(block, i))
    return shapes


# ---- LongCat-Flash (two latent attentions, two MLPs, a shortcut expert layer)
def _longcat_sizes(num_layers, num_heads, model_dim, ffn_dim=None,
                   moe_ffn_dim=None, num_experts=512, num_zero_experts=256,
                   num_experts_per_tok=12, num_local_experts=0,
                   local_expert_offset=0, q_lora_rank=1536, kv_lora_rank=512,
                   qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                   rope_theta=1e7, rms_eps=1e-5, routed_scaling_factor=6.0,
                   **kwargs):
    """``_longcat_layer``'s keywords from a builder's (``ffn_dim`` is a dense
    MLP's width, ``moe_ffn_dim`` one expert's; ``num_experts`` experts have
    weights and ``num_zero_experts`` more router outputs are the identity;
    ``mla_scale_q_lora`` and ``mla_scale_kv_lora``: the factors
    ``sqrt(model_dim / rank)`` go on both normed latents). Keywords of the
    other architectures are dropped."""
    if qk_rope_head_dim % 2:
        raise MXNetError("longcat_flash: qk_rope_head_dim %d is odd"
                         % qk_rope_head_dim)
    rho = lambda rank: math.sqrt(model_dim / rank)
    return dict(
        num_layers=num_layers, num_heads=num_heads, model_dim=model_dim,
        ffn_dim=ffn_dim, moe_ffn_dim=moe_ffn_dim, num_experts=num_experts,
        num_zero_experts=int(num_zero_experts),
        num_experts_per_tok=num_experts_per_tok,
        num_local_experts=int(num_local_experts),
        local_expert_offset=int(local_expert_offset),
        q_rank=q_lora_rank, nope=qk_nope_head_dim, rope=qk_rope_head_dim,
        v_dim=v_head_dim, latent=kv_lora_rank, rope_theta=float(rope_theta),
        rho_q=rho(q_lora_rank), rho_kv=rho(kv_lora_rank), rms_eps=rms_eps,
        routed_scaling_factor=float(routed_scaling_factor),
        scale=float(qk_nope_head_dim + qk_rope_head_dim) ** -0.5)


def _longcat_layer(x, i, positions, seq_len, attend, block):
    """One ``model_type: longcat_flash`` layer on x (B, T, M) -> (x', load
    (E + Z,)): TWO sublayers of a latent attention and a dense gated SiLU
    MLP each, and ONE expert layer that reads the first sublayer's
    post-attention norm and joins the residual stream only after the second
    sublayer's MLP (the shortcut-connected expert layer: the second
    attention and both MLPs lie between the router and the sum):

        for s in (0, 1):
            x = x + MLA_s(RMSNorm(x));  h = RMSNorm(x)
            if s == 0: m = MoE(h)
            x = x + MLP_s(h)
        x = x + m

    Names count SUBLAYERS: sublayer s of layer i is ``layer<2i + s>_*``
    (``ln1``, ``qa``, ``qnorm``, ``qb``, ``kva``, ``kvnorm``, ``kvb``,
    ``proj``, ``ln2``, ``mlp_in``, ``mlp_out``), its pool ``kv_c_<2i + s>``,
    and ``attend`` is called with that index; the expert layer's weights are
    its first sublayer's, ``layer<2i>_router_*`` and ``layer<2i>_experts_*``.
    The attention is ``_latent_operands`` with a low-rank query and both
    factors. The experts: softmax scores over ``num_experts +
    num_zero_experts`` router outputs, chosen on the score plus the
    selection bias, weighted by the unbiased score NOT renormalised, times
    ``routed_scaling_factor``; a zero-compute expert is the identity
    (``MoEFeedForward(num_zero_experts=)``); no shared expert."""
    d, eps, hq = block["model_dim"], block["rms_eps"], block["num_heads"]
    moe = None
    for j in (2 * i, 2 * i + 1):
        name = "layer%d" % j
        fc = lambda data, width, tag, name=name: sym.FullyConnected(
            data=data, num_hidden=width, no_bias=True, flatten=False,
            name="%s_%s" % (name, tag))
        h = sym.RMSNorm(x, eps=eps, name="%s_ln1" % name)
        operands, _ = _latent_operands(
            h, fc, name, positions, seq_len, eps, heads=hq, nope=block["nope"],
            rope=block["rope"], latent=block["latent"],
            rope_theta=block["rope_theta"], q_rank=block["q_rank"],
            rho_q=block["rho_q"], rho_kv=block["rho_kv"])
        att = attend(j, *operands)
        x = x + fc(_merge_heads(att, seq_len, hq * block["v_dim"]), d, "proj")
        h = sym.RMSNorm(x, eps=eps, name="%s_ln2" % name)
        if moe is None:
            share = {k: block[k] for k in ("num_local_experts",
                                           "local_expert_offset")
                     if block["num_local_experts"]}
            moe = sym.MoEFeedForward(
                sym.Reshape(h, shape=(-1, d)),
                *(sym.Variable("%s_%s" % (name, w)) for w in (
                    "router_weight", "experts_gate_weight",
                    "experts_up_weight", "experts_down_weight",
                    "router_bias")),
                num_experts=block["num_experts"],
                num_zero_experts=block["num_zero_experts"],
                num_hidden=block["moe_ffn_dim"],
                num_experts_per_tok=block["num_experts_per_tok"],
                scoring="softmax", router_bias=True,
                routed_scaling_factor=block["routed_scaling_factor"],
                name="%s_moe" % name, **share)
        x = x + _gated_mlp(fc, h, block["ffn_dim"], d, "mlp")
    return x + sym.Reshape(moe[0], shape=(-1, seq_len, d)), moe[1]


def _longcat_prefill_symbol(vocab_size, num_layers, prefill_len, **sizes):
    return _latent_prefill_symbol(_longcat_sizes(num_layers, **sizes),
                                  _longcat_layer, vocab_size, prefill_len)


def _longcat_decode_symbol(vocab_size, num_layers, num_slots, page_size,
                           token_out=True, **sizes):
    return _latent_decode_symbol(_longcat_sizes(num_layers, **sizes),
                                 _longcat_layer, vocab_size, num_slots,
                                 page_size, token_out)


def _longcat_param_shapes(vocab_size, num_layers, **sizes):
    block = _longcat_sizes(num_layers, **sizes)
    d, f, ffn = block["model_dim"], block["moe_ffn_dim"], block["ffn_dim"]
    routed = block["num_experts"] + block["num_zero_experts"]
    held = block["num_local_experts"] or block["num_experts"]
    shapes = {"embed_weight": (vocab_size, d), "final_ln_gamma": (d,),
              "lm_head_weight": (vocab_size, d)}
    for j in range(2 * num_layers):
        n = "layer%d_" % j
        shapes.update(_latent_param_shapes(
            n, d, heads=block["num_heads"], nope=block["nope"],
            rope=block["rope"], v_dim=block["v_dim"],
            latent=block["latent"], q_rank=block["q_rank"]))
        shapes.update({n + "mlp_in_weight": (2 * ffn, d),
                       n + "mlp_out_weight": (d, ffn)})
        if j % 2 == 0:
            shapes.update({
                n + "router_weight": (routed, d), n + "router_bias": (routed,),
                n + "experts_gate_weight": (held, d, f),
                n + "experts_up_weight": (held, d, f),
                n + "experts_down_weight": (held, f, d)})
    return shapes


# ------ dots3-note (learned sparse attention beside windowed latent attention)
def _dots3_sizes(num_layers, num_heads, model_dim, layer_types, ffn_dim=None,
                 moe_ffn_dim=None, num_experts=256, num_experts_per_tok=8,
                 num_local_experts=0, local_expert_offset=0,
                 num_shared_experts=1, first_dense_layers=1,
                 q_lora_rank=1024, kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, rope_theta=8e7,
                 swa_num_heads=None, swa_q_lora_rank=None,
                 swa_kv_lora_rank=None, swa_qk_nope_head_dim=None,
                 swa_qk_rope_head_dim=None, swa_v_head_dim=None,
                 swa_rope_theta=5e4, sliding_window=513, index_n_heads=64,
                 index_head_dim=128, index_topk=2048, lora_rescale=True,
                 rms_eps=1e-5, routed_scaling_factor=1.0, norm_topk_prob=True,
                 dtype="float32", **kwargs):
    """``_dots3_layer``'s keywords from a builder's. The two kinds of layer
    have a latent geometry each, ``block["full_attention"]`` and
    ``block["sliding_attention"]`` (a ``swa_`` size left out is the full
    layers'): heads, the query's and the key/value's low rank, nope, rope
    and value widths, the rotary base, the softmax scale over the whole
    query-key width and, with ``lora_rescale``, the factors
    ``sqrt(model_dim / rank)`` on both normed latents. Keywords of the other
    architectures are dropped."""
    kinds = tuple(layer_types)
    if len(kinds) != num_layers \
            or set(kinds) - {"full_attention", "sliding_attention"}:
        raise MXNetError("dots3_note: layer_types must name %d layers "
                         "'full_attention' or 'sliding_attention', got %r"
                         % (num_layers, kinds))
    if not 0 <= first_dense_layers <= num_layers:
        raise MXNetError("dots3_note: first_dense_layers %d outside [0, %d]"
                         % (first_dense_layers, num_layers))

    def geometry(heads, q_rank, latent, nope, rope, v_dim, theta):
        if rope % 2:
            raise MXNetError("dots3_note: a rotary width of %d is odd" % rope)
        rho = lambda rank: math.sqrt(model_dim / rank) if lora_rescale else 1.0
        return dict(heads=heads, q_rank=q_rank, latent=latent, nope=nope,
                    rope=rope, v_dim=v_dim, rope_theta=float(theta),
                    scale=float(nope + rope) ** -0.5, rho_q=rho(q_rank),
                    rho_kv=rho(latent))

    full = (num_heads, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
            qk_rope_head_dim, v_head_dim)
    swa = (swa_num_heads, swa_q_lora_rank, swa_kv_lora_rank,
           swa_qk_nope_head_dim, swa_qk_rope_head_dim, swa_v_head_dim)
    if index_head_dim < qk_rope_head_dim:
        raise MXNetError("dots3_note: index_head_dim %d under the rotary "
                         "width %d" % (index_head_dim, qk_rope_head_dim))
    return dict(
        num_layers=num_layers, layer_types=kinds, model_dim=model_dim,
        full_attention=geometry(*full, rope_theta),
        sliding_attention=geometry(
            *(own or theirs for own, theirs in zip(swa, full)),
            swa_rope_theta),
        sliding_window=int(sliding_window), index_heads=int(index_n_heads),
        index_dim=int(index_head_dim), index_topk=int(index_topk),
        ffn_dim=ffn_dim, moe_ffn_dim=moe_ffn_dim, num_experts=num_experts,
        num_experts_per_tok=num_experts_per_tok,
        num_local_experts=int(num_local_experts),
        local_expert_offset=int(local_expert_offset),
        num_shared_experts=num_shared_experts,
        first_dense_layers=first_dense_layers, rms_eps=rms_eps,
        routed_scaling_factor=float(routed_scaling_factor),
        norm_topk_prob=bool(norm_topk_prob), dtype=dtype)


def _layer_norm_f32(x, name, dtype, eps=1e-5):
    """LayerNorm with ``<name>_gamma`` and ``<name>_beta`` over the last
    axis, its statistics float32 whatever the IO dtype (``RMSNorm``'s rule:
    ``_layer_norm`` above is the naive composition in the data's type)."""
    xf = sym.Cast(x, dtype="float32")
    cent = sym.broadcast_sub(xf, sym.mean(xf, axis=-1, keepdims=True))
    inv = sym.rsqrt(sym.mean(cent * cent, axis=-1, keepdims=True) + eps)
    normed = sym.broadcast_mul(
        sym.broadcast_mul(cent, inv),
        sym.Cast(sym.Variable("%s_gamma" % name), dtype="float32"))
    return sym.Cast(sym.broadcast_add(
        normed, sym.Cast(sym.Variable("%s_beta" % name), dtype="float32")),
        dtype=dtype, name=name)


def _dots3_layer(x, i, positions, seq_len, attend, block):
    """One ``model_type: dots3_note`` block on x (B, T, M) -> (x', load (E,)
    or None for a dense layer). ``layer_types[i]`` names the attention's
    kind, and with it the latent geometry (``_dots3_sizes``).

    Latent attention with a QUERY-side low rank, both kinds
    (``_latent_operands``): ``c_q = rho_q *
    RMSNorm(W_qa h)``, ``q = W_qb c_q`` -> H heads of [q_nope | q_rope];
    ``[c | k_r] = W_kva h``, ``c = rho_kv * RMSNorm(c)``; rotary positions
    over interleaved pairs on q_rope and the ONE k_r every head shares, at
    the kind's own base. A FULL layer carries an indexer beside it, fed by
    the SAME query latent: ``q_I = W_iq c_q`` -> Hi heads of di,
    ``k_I = LayerNorm(W_ik h)`` (one head, with bias), the first ``rope``
    features of both rotated (half-split pairs, the layer's base), and a
    weight a head ``w = W_iw h / sqrt(Hi * di)``; a query attends the
    ``index_topk`` keys of largest ``sum_j w_j relu(q_I,j . k_I)`` alone. A
    WINDOW layer attends the last ``sliding_window`` positions, itself
    among them, and has no indexer. ``attend(i, q_nope, q_rope, c, k_r,
    index)`` is the one thing the prefill and the decode graph do
    differently (``index``: (q_I (B, Hi, T, di), k_I (B, 1, T, di), w (B, T,
    Hi)) or None) and returns (B, H, T, v_dim), through
    ``layer<i>_kvb_weight`` materialised or absorbed as in
    ``_deepseek_v3_layer``. A head-wise gate ``sigmoid(W_g h)``, one a head,
    multiplies a head's context before the output projection. The
    feed-forward is ``_deepseek_v3_layer``'s."""
    name = "layer%d" % i
    kind = block["layer_types"][i]
    geo, d, eps = block[kind], block["model_dim"], block["rms_eps"]
    hq, nope, rope, lat = (geo[k] for k in ("heads", "nope", "rope",
                                            "latent"))
    fc = lambda data, width, tag, **kw: sym.FullyConnected(
        data=data, num_hidden=width, no_bias=True, flatten=False,
        name="%s_%s" % (name, tag), **kw)
    rotate = lambda a, tag, **kw: sym.RotaryEmbedding(
        a, positions, base=geo["rope_theta"], name="%s_%s" % (name, tag),
        **kw)
    h = sym.RMSNorm(x, eps=eps, name="%s_ln1" % name)

    def indexer(c_q):
        if kind != "full_attention":
            return None
        hi, di = block["index_heads"], block["index_dim"]
        k_i = _layer_norm_f32(fc(h, di, "ik"), "%s_iknorm" % name,
                              block["dtype"])
        return (rotate(_split_heads(fc(c_q, hi * di, "iq"), seq_len, hi,
                                    di), "iqrope", rotary_dim=rope),
                rotate(sym.Reshape(k_i, shape=(-1, 1, seq_len, di)),
                       "ikrope", rotary_dim=rope),
                fc(h, hi, "iw") * float(hi * di) ** -0.5)

    operands, index = _latent_operands(
        h, fc, name, positions, seq_len, eps, heads=hq, nope=nope, rope=rope,
        latent=lat, rope_theta=geo["rope_theta"], q_rank=geo["q_rank"],
        rho_q=geo["rho_q"], rho_kv=geo["rho_kv"], beside=indexer)
    att = attend(i, *operands, index)
    gate = sym.Reshape(sym.sigmoid(fc(h, hq, "gate")),
                       shape=(-1, seq_len, hq, 1))
    att = sym.Reshape(sym.broadcast_mul(
        sym.transpose(att, axes=(0, 2, 1, 3)), gate),
        shape=(-1, seq_len, hq * geo["v_dim"]))
    return _deepseek_v3_ffn(x + fc(att, d, "proj"), i, fc, seq_len, block)


def _dots3_prefill_symbol(vocab_size, num_layers, prefill_len, **sizes):
    block = _dots3_sizes(num_layers, **sizes)
    positions = sym.Reshape(sym._arange(start=0, stop=prefill_len),
                            shape=(1, prefill_len))
    length = sym.Variable("length")
    cache = []      # the layers are built in order, so is this

    def attend(i, q_nope, q_rope, c, k_r, index):
        geo = block[block["layer_types"][i]]
        hq, nope, v_dim, lat = (geo[k] for k in ("heads", "nope", "v_dim",
                                                 "latent"))
        row, operands = _materialised(i, q_nope, q_rope, c, k_r, prefill_len,
                                      hq, nope, v_dim, lat)
        cache.append(row)
        if index is None:   # a band of the bucket
            return sym.MultiHeadAttention(
                causal=True, window=block["sliding_window"],
                scale=geo["scale"], name="layer%d_att" % i, **operands)
        q_i, k_i, w_i = index
        hi, di = block["index_heads"], block["index_dim"]
        # the index keys as their pool keeps them, and what the prompt's
        # last real row selected (the lane's row of ``sparse_sel_<i>``)
        last = lambda a, width: sym.Reshape(_last_real_row(
            a, length), shape=(-1,) + width)
        cache.extend([k_i, sym.SparseIndexSelect(
            last(sym.Reshape(sym.transpose(q_i, axes=(0, 2, 1, 3)),
                             shape=(-1, prefill_len, hi * di)), (hi, di)),
            last(w_i, (hi,)), sym.Reshape(k_i, shape=(-1, prefill_len, di)),
            length, topk=block["index_topk"], name="layer%d_sel" % i)])
        return sym.MultiHeadAttention(
            operands["query"], operands["key"], operands["value"], q_i, k_i,
            w_i, causal=True, topk=block["index_topk"], scale=geo["scale"],
            name="layer%d_att" % i)

    logits, load = _deepseek_v3_stack(vocab_size, prefill_len, positions,
                                      attend, block, layer=_dots3_layer,
                                      length=length)
    return sym.Group([logits] + cache + load)


def _dots3_decode_symbol(vocab_size, num_layers, num_slots, page_size,
                         token_out=True, **sizes):
    block = _dots3_sizes(num_layers, **sizes)
    pos_idx = sym.Variable("pos_idx")
    write_slot = sym.Variable("write_slot")
    write, read = _pool_step_inputs(pos_idx, num_slots, page_size, write_slot)
    cache = []      # the layers are built in order, so is this

    def attend(i, q_nope, q_rope, c, k_r, index):
        geo = block[block["layer_types"][i]]
        hq, nope, rope, v_dim, lat = (geo[k] for k in (
            "heads", "nope", "rope", "v_dim", "latent"))
        # one token a lane: its row of the latent cache is [c | k_r]
        row = sym.Concat(sym.Reshape(c, shape=(-1, 1, lat)),
                         sym.Reshape(k_r, shape=(-1, 1, rope)), dim=2)
        if index is None:
            # a window layer: the lane's own ring of latents, key (all of a
            # row) and value (its first ``lat``), no frame and no table
            ring, = sym.KVRingWrite(
                sym.Variable("ring_c_%d" % i), row, pos_idx, write_slot,
                num_rings=1, name="layer%d_cupd" % i)
            cache.append(ring)
            read_rows = lambda query: sym.KVRingAttention(
                query, ring, ring, pos_idx, write_slot, scale=geo["scale"],
                value_dim=lat, name="layer%d_att" % i)
        else:
            # a full layer: two pools on one page table, written by the same
            # slot; the index keys of the lane's own context are scored, the
            # chosen rows of the latent pool read and no others
            q_i, k_i, w_i = index
            hi, di = block["index_heads"], block["index_dim"]
            pool, keys = write(i, {
                "c": row, "i": sym.Reshape(k_i, shape=(-1, 1, di))})
            chosen = sym.SparseIndexSelect(
                sym.Reshape(q_i, shape=(-1, hi, di)),
                sym.Reshape(w_i, shape=(-1, hi)), keys, read["page_table"],
                pos_idx, write_slot, sym.Variable("sparse_sel_%d" % i),
                topk=block["index_topk"], page_size=page_size,
                name="layer%d_sel" % i)
            cache.extend([pool, keys, chosen])
            read_rows = lambda query: sym.KVPoolAttention(
                query, pool, pool, read["mask"], read["page_table"], pos_idx,
                write_slot, chosen, scale=geo["scale"], value_dim=lat,
                page_size=page_size, selected=True, name="layer%d_att" % i)
        return _absorbed(i, q_nope, q_rope, read_rows, hq, nope, rope, v_dim,
                         lat)

    logits, load = _deepseek_v3_stack(vocab_size, 1, pos_idx, attend, block,
                                      layer=_dots3_layer)
    # moe_load LAST: the cache and the token head keep their places
    return sym.Group([_token_head(
        logits, cache, "greedy_token" if token_out else None)] + load)


def _dots3_param_shapes(vocab_size, num_layers, **sizes):
    block = _dots3_sizes(num_layers, **sizes)
    d, hi, di = block["model_dim"], block["index_heads"], block["index_dim"]
    shapes = {"embed_weight": (vocab_size, d), "final_ln_gamma": (d,),
              "lm_head_weight": (vocab_size, d)}
    for i, kind in enumerate(block["layer_types"]):
        n, geo = "layer%d_" % i, block[kind]
        rank = geo["q_rank"]
        shapes.update(_latent_param_shapes(n, d, **{k: geo[k] for k in (
            "heads", "nope", "rope", "v_dim", "latent", "q_rank")}))
        shapes[n + "gate_weight"] = (geo["heads"], d)
        if kind == "full_attention":
            shapes.update({
                n + "iq_weight": (hi * di, rank), n + "ik_weight": (di, d),
                n + "iknorm_gamma": (di,), n + "iknorm_beta": (di,),
                n + "iw_weight": (hi, d)})
        shapes.update(_deepseek_v3_ffn_shapes(block, i))
    return shapes


# ------------------------------------------- LFM2-MoE (gated short convolutions)
def _lfm2_moe_sizes(num_layers, num_heads, model_dim, ffn_dim, layer_types,
                    moe_ffn_dim=None, num_kv_heads=None, head_dim=None,
                    num_experts=64, num_experts_per_tok=4,
                    first_dense_layers=2, conv_kernel=3, rope_theta=1e6,
                    rms_eps=1e-5, routed_scaling_factor=1.0,
                    norm_topk_prob=True, dtype="float32", **kwargs):
    """``_lfm2_moe_layer``'s keywords from a builder's (``ffn_dim`` is the
    leading dense layers' width, ``moe_ffn_dim`` one expert's; defaults:
    LFM2-24B-A2B's; keywords of the other architectures are dropped)."""
    kinds = tuple(layer_types)
    if len(kinds) != num_layers or set(kinds) - {"conv", "full_attention"}:
        raise MXNetError("lfm2_moe: layer_types must name %d layers, each "
                         "'conv' or 'full_attention', got %r"
                         % (num_layers, kinds))
    if not 0 <= first_dense_layers <= num_layers:
        raise MXNetError("lfm2_moe: first_dense_layers %d outside [0, %d]"
                         % (first_dense_layers, num_layers))
    return dict(
        layer_types=kinds, num_heads=num_heads,
        num_kv_heads=num_kv_heads or num_heads,
        head_dim=head_dim or model_dim // num_heads, model_dim=model_dim,
        ffn_dim=ffn_dim, moe_ffn_dim=moe_ffn_dim, num_experts=num_experts,
        num_experts_per_tok=num_experts_per_tok,
        first_dense_layers=first_dense_layers, conv_kernel=conv_kernel,
        rope_theta=float(rope_theta), rms_eps=rms_eps,
        routed_scaling_factor=float(routed_scaling_factor),
        norm_topk_prob=bool(norm_topk_prob), dtype=dtype)


def _lfm2_moe_layer(x, i, positions, seq_len, attend, conv, block):
    """One ``model_type: lfm2_moe`` block on x (B, T, M) -> (x', load (E,)
    or None for a dense layer): pre-norm RMSNorm, a mixer chosen by
    ``layer_types[i]``, then a feed-forward chosen by depth.

    ``conv``: one bias-free projection to [B | C | u], asked for in float32
    (the mixer computes so); ``conv(i, bcu)`` runs the gated short
    convolution (ops/shortconv.py) and returns y (B, T, M), float32; back to
    the weights' type and through the output projection. ``full_attention``:
    one bias-free projection to [q | k | v] with fewer key/value heads than
    query heads; q and k are normed PER HEAD (over ``head_dim`` features, one
    gamma for all heads) BEFORE their rotation; ``attend(i, q, k, v)`` takes
    the head-major (B, H or Hkv, T, dh) tensors and returns (B, H, T, dh).
    ``conv`` and ``attend`` are the two things the prefill and the decode
    graph do differently. The feed-forward: the gated SiLU MLP in the first
    ``first_dense_layers`` layers; after them sigmoid-routed experts alone
    (``_sigmoid_experts``: no shared one)."""
    name = "layer%d" % i
    d, eps = block["model_dim"], block["rms_eps"]
    fc = lambda data, width, tag, **kw: sym.FullyConnected(
        data=data, num_hidden=width, no_bias=True, flatten=False,
        name="%s_%s" % (name, tag), **kw)
    h = sym.RMSNorm(x, eps=eps, name="%s_ln1" % name)
    if block["layer_types"][i] == "conv":
        y = conv(i, fc(h, 3 * d, "conv_in", out_dtype="float32"))
        mixed = fc(sym.Cast(y, dtype=block["dtype"]), d, "conv_out")
    else:
        hq, hkv, dh = block["num_heads"], block["num_kv_heads"], \
            block["head_dim"]
        q, k, v = _grouped_qkv(fc, h, seq_len, hq, hkv, dh)
        q, k = (sym.RotaryEmbedding(
            sym.RMSNorm(a, eps=eps, name="%s_%snorm" % (name, tag)),
            positions, base=block["rope_theta"],
            name="%s_%srope" % (name, tag)) for a, tag in ((q, "q"), (k, "k")))
        mixed = fc(_merge_heads(attend(i, q, k, v), seq_len, hq * dh), d,
                   "proj")
    x = x + mixed
    h = sym.RMSNorm(x, eps=eps, name="%s_ln2" % name)
    if i < block["first_dense_layers"]:
        return x + _gated_mlp(fc, h, block["ffn_dim"], d, "mlp"), None
    moe = _sigmoid_experts(h, name, block)
    return x + sym.Reshape(moe[0], shape=(-1, seq_len, d)), moe[1]


def _lfm2_moe_stack(vocab_size, seq_len, positions, attend, conv, block,
                    length=None):
    """Embedding (tied to the head), the layers, the final norm and the head:
    ``data`` (B, T) -> (float32 logits (B·T, vocab), moe_load (expert layers,
    experts)); logits (B, vocab) where a prefill gives each prompt's
    ``length`` (``_last_real_row``)."""
    table = sym.Variable("embed_weight")
    d = block["model_dim"]
    x = sym.Embedding(data=sym.Variable("data"), weight=table,
                      input_dim=vocab_size, output_dim=d, name="embed")
    loads = []
    for i in range(len(block["layer_types"])):
        x, load = _lfm2_moe_layer(x, i, positions, seq_len, attend, conv,
                                  block)
        if load is not None:
            loads.append(sym.Reshape(load, shape=(1, -1)))
    if length is not None:
        x = _last_real_row(x, length)
    x = sym.RMSNorm(x, eps=block["rms_eps"], name="final_ln")
    logits = sym.FullyConnected(
        data=sym.Reshape(x, shape=(-1, d)), weight=table,
        num_hidden=vocab_size, no_bias=True, out_dtype="float32",
        name="lm_head")
    return logits, [sym.Concat(*loads, dim=0, name="moe_load")] if loads \
        else []


def _lfm2_conv(op, i, bcu, block, **inputs):
    """One of ops/shortconv.py's two operators on conv layer ``i``'s taps."""
    return op(bcu, sym.Variable("layer%d_conv_weight" % i),
              kernel=block["conv_kernel"], name="layer%d_conv_core" % i,
              **inputs)


def _lfm2_moe_prefill_symbol(vocab_size, num_layers, prefill_len, **sizes):
    block = _lfm2_moe_sizes(num_layers, **sizes)
    length = sym.Variable("length")     # (B, 1): real tokens of the bucket
    positions = sym.Reshape(sym._arange(start=0, stop=prefill_len),
                            shape=(1, prefill_len))
    cache = []      # the layers are built in order, so is this

    def attend(i, q, k, v):
        cache.extend([k, v])    # the key as the pool keeps it: normed, rotated
        return sym.MultiHeadAttention(query=q, key=k, value=v, causal=True,
                                      name="layer%d_att" % i)

    def conv(i, bcu):
        core = _lfm2_conv(sym.GatedShortConv, i, bcu, block, length=length)
        cache.append(core[1])
        return core[0]

    logits, load = _lfm2_moe_stack(vocab_size, prefill_len, positions,
                                   attend, conv, block, length=length)
    return sym.Group([logits] + cache + load)


def _lfm2_moe_decode_symbol(vocab_size, num_layers, num_slots, page_size,
                            token_out=True, **sizes):
    block = _lfm2_moe_sizes(num_layers, **sizes)
    hq, hkv, dh = (block[k] for k in ("num_heads", "num_kv_heads", "head_dim"))
    pos_idx = sym.Variable("pos_idx")
    write_slot = sym.Variable("write_slot")
    write, read = _pool_step_inputs(pos_idx, num_slots, page_size, write_slot)
    cache = []      # the layers are built in order, so is this

    def attend(i, q, k_new, v_new):
        # one token a lane: the head-major (B, H, 1, dh) tensors are the
        # pool's rows (B, H, dh)
        q, k_new, v_new = (sym.Reshape(a, shape=(-1, n, dh)) for a, n in
                           ((q, hq), (k_new, hkv), (v_new, hkv)))
        ctx = _pool_attend(i, q, k_new, v_new, write, read, cache)
        return sym.Reshape(ctx, shape=(-1, hq, 1, dh))

    def conv(i, bcu):
        core = _lfm2_conv(
            sym.GatedShortConvStep, i, sym.Reshape(bcu, shape=(0, -1)), block,
            conv_state=sym.Variable("conv_state_%d" % i), stepped=write_slot)
        cache.append(core[1])
        return sym.Reshape(core[0], shape=(0, 1, -1))

    logits, load = _lfm2_moe_stack(vocab_size, 1, pos_idx, attend, conv,
                                   block)
    # moe_load LAST: the cache and the token head keep their places
    return sym.Group([_token_head(
        logits, cache, "greedy_token" if token_out else None)] + load)


def _lfm2_moe_param_shapes(vocab_size, num_layers, **sizes):
    block = _lfm2_moe_sizes(num_layers, **sizes)
    d, e, f = block["model_dim"], block["num_experts"], block["moe_ffn_dim"]
    hq, hkv, dh = (block[k] for k in ("num_heads", "num_kv_heads", "head_dim"))
    shapes = {"embed_weight": (vocab_size, d), "final_ln_gamma": (d,)}
    for i, kind in enumerate(block["layer_types"]):
        n = "layer%d_" % i
        shapes.update({n + "ln1_gamma": (d,), n + "ln2_gamma": (d,)})
        if kind == "conv":
            shapes.update({n + "conv_in_weight": (3 * d, d),
                           n + "conv_weight": (block["conv_kernel"], d),
                           n + "conv_out_weight": (d, d)})
        else:
            shapes.update({n + "qkv_weight": ((hq + 2 * hkv) * dh, d),
                           n + "qnorm_gamma": (dh,), n + "knorm_gamma": (dh,),
                           n + "proj_weight": (d, hq * dh)})
        if i < block["first_dense_layers"]:
            shapes.update({n + "mlp_in_weight": (2 * block["ffn_dim"], d),
                           n + "mlp_out_weight": (d, block["ffn_dim"])})
            continue
        shapes.update({
            n + "router_weight": (e, d), n + "router_bias": (e,),
            n + "experts_gate_weight": (e, d, f),
            n + "experts_up_weight": (e, d, f),
            n + "experts_down_weight": (e, f, d)})
    return shapes


# ---------------------------------- MiMo-V2-Flash (window and full attention)
def _mimo_sizes(num_layers, num_heads, model_dim, ffn_dim, hybrid_layer_pattern,
                moe_layer_freq, moe_ffn_dim=None, num_kv_heads=None,
                swa_num_kv_heads=None, head_dim=None, v_head_dim=None,
                sliding_window=128, rotary_dim=None, rope_theta=5e6,
                swa_rope_theta=1e4, attention_value_scale=1.0,
                num_experts=256, num_experts_per_tok=8, num_local_experts=0,
                local_expert_offset=0, rms_eps=1e-5,
                routed_scaling_factor=1.0, norm_topk_prob=True, **kwargs):
    """``_mimo_layer``'s keywords from a builder's (``ffn_dim`` is a dense
    layer's width, ``moe_ffn_dim`` one expert's; ``num_local_experts`` = 0
    holds every expert; keywords of the other architectures are dropped)."""
    window, sparse = tuple(hybrid_layer_pattern), tuple(moe_layer_freq)
    for what, flags in (("hybrid_layer_pattern", window),
                        ("moe_layer_freq", sparse)):
        if len(flags) != num_layers or set(flags) - {0, 1}:
            raise MXNetError("mimo_v2_flash: %s must give %d layers a 0 or a "
                             "1, got %r" % (what, num_layers, flags))
    head_dim = head_dim or model_dim // num_heads
    rotary_dim = rotary_dim or head_dim
    if rotary_dim % 2 or rotary_dim > head_dim:
        raise MXNetError("mimo_v2_flash: rotary_dim %d must be even and at "
                         "most head_dim %d" % (rotary_dim, head_dim))
    return dict(
        num_layers=num_layers, window_layers=window, expert_layers=sparse,
        num_heads=num_heads, swa_num_heads=num_heads,
        num_kv_heads=num_kv_heads or num_heads,
        swa_num_kv_heads=swa_num_kv_heads or num_kv_heads or num_heads,
        head_dim=head_dim, v_head_dim=v_head_dim or head_dim,
        model_dim=model_dim, ffn_dim=ffn_dim, moe_ffn_dim=moe_ffn_dim,
        sliding_window=int(sliding_window), rotary_dim=rotary_dim,
        rope_theta=float(rope_theta), swa_rope_theta=float(swa_rope_theta),
        attention_value_scale=float(attention_value_scale),
        num_experts=num_experts, num_experts_per_tok=num_experts_per_tok,
        num_local_experts=int(num_local_experts),
        local_expert_offset=int(local_expert_offset), rms_eps=rms_eps,
        routed_scaling_factor=float(routed_scaling_factor),
        norm_topk_prob=bool(norm_topk_prob))


def _window_heads(block, i):
    """(query heads, key/value heads) of layer ``i`` of a block of window
    and full layers: a window layer has counts of its own (``swa_``)."""
    kind = "swa_" if block["window_layers"][i] else ""
    return block[kind + "num_heads"], block[kind + "num_kv_heads"]


def _sink_input(sink):
    """(inputs, attributes) a window layer's sink adds to its attention
    operator: the sink is one more INPUT, ``sink=True`` the attribute that
    says so (as MoEFeedForward's router_bias); nothing where there is none."""
    return ((), {}) if sink is None else ((sink,), {"sink": True})


def _window_prefill_attend(block, cache):
    """``attend(i, q, k, v, sink)`` of a PREFILL over window and full layers
    side by side (``mimo_v2_flash``, ``laguna``): the rotated head-major
    tensors go to ``cache`` as the cache keeps them, then causal
    ``MultiHeadAttention``, under ``sliding_window`` where
    ``block["window_layers"][i]``; ``sink`` (None: no sink) is that
    operator's fourth input."""
    def attend(i, q, k, v, sink=None):
        cache.extend([k, v])    # as the cache keeps them: rotated, scaled
        if not block["window_layers"][i]:
            return sym.MultiHeadAttention(query=q, key=k, value=v,
                                          causal=True, name="layer%d_att" % i)
        return sym.MultiHeadAttention(
            q, k, v, *_sink_input(sink)[0], causal=True,
            window=block["sliding_window"], name="layer%d_att" % i,
            **_sink_input(sink)[1])
    return attend


def _window_step_attend(block, cache, pos_idx, write_slot, write, read):
    """``attend(i, q, k_new, v_new, sink)`` of a decode STEP over window and
    full layers side by side: a full layer writes and reads its pools
    (``_pool_attend``), a window layer the lane's own rings, no frame and no
    table; the written buffers go to ``cache`` in layer order."""
    dk, dv = block["head_dim"], block["v_head_dim"]

    def attend(i, q, k_new, v_new, sink=None):
        # one token a lane: the head-major (B, H, 1, d) tensors are rows
        hq, hkv = _window_heads(block, i)
        q, k_new, v_new = (sym.Reshape(a, shape=(-1, n, width))
                           for a, n, width in ((q, hq, dk), (k_new, hkv, dk),
                                               (v_new, hkv, dv)))
        if not block["window_layers"][i]:
            ctx = _pool_attend(i, q, k_new, v_new, write, read, cache)
        else:
            # a window layer: the lane's own rings, no frame and no table
            rings = sym.KVRingWrite(
                sym.Variable("ring_k_%d" % i), k_new,
                sym.Variable("ring_v_%d" % i), v_new, pos_idx, write_slot,
                num_rings=2, name="layer%d_kvupd" % i)
            cache.extend([rings[0], rings[1]])
            ctx = sym.KVRingAttention(
                q, rings[0], rings[1], pos_idx, write_slot,
                *_sink_input(sink)[0], name="layer%d_att" % i,
                **_sink_input(sink)[1])
        return sym.Reshape(ctx, shape=(-1, hq, 1, dv))
    return attend


def _window_prefill_symbol(block, layer, vocab_size, prefill_len):
    """The prefill graph of a block of window and full layers (``layer``:
    ``_mimo_layer`` or ``_laguna_layer``): logits, every layer's K and V as
    the cache keeps them, ``moe_load``."""
    positions = sym.Reshape(sym._arange(start=0, stop=prefill_len),
                            shape=(1, prefill_len))
    cache = []      # the layers are built in order, so is this
    logits, load = _deepseek_v3_stack(
        vocab_size, prefill_len, positions,
        _window_prefill_attend(block, cache), block, layer=layer,
        length=sym.Variable("length"))
    return sym.Group([logits] + cache + load)


def _window_decode_symbol(block, layer, vocab_size, num_slots, page_size,
                          token_out):
    """The decode graph of the same: logits, pools and rings in layer order,
    the token head, ``moe_load``."""
    pos_idx = sym.Variable("pos_idx")
    write_slot = sym.Variable("write_slot")
    write, read = _pool_step_inputs(pos_idx, num_slots, page_size, write_slot)
    cache = []      # the layers are built in order, so is this
    logits, load = _deepseek_v3_stack(
        vocab_size, 1, pos_idx,
        _window_step_attend(block, cache, pos_idx, write_slot, write, read),
        block, layer=layer)
    # moe_load LAST: the cache and the token head keep their places
    return sym.Group([_token_head(
        logits, cache, "greedy_token" if token_out else None)] + load)


def _window_cache(block):
    """``decode_cache``'s list for a block of window and full layers: a
    ring pair a window layer, a pool pair a full one, in layer order."""
    dk, dv, w = (block[k] for k in ("head_dim", "v_head_dim",
                                    "sliding_window"))
    out = []
    for i, windowed in enumerate(block["window_layers"]):
        hkv = _window_heads(block, i)[1]
        out += [("ring_k_%d" % i, "ring", (hkv, w, dk)),
                ("ring_v_%d" % i, "ring", (hkv, w, dv))] if windowed \
            else [("kv_k_%d" % i, "pool", (hkv, dk)),
                  ("kv_v_%d" % i, "pool", (hkv, dv))]
    return out


def _mimo_layer(x, i, positions, seq_len, attend, block):
    """One ``model_type: mimo_v2_flash`` block on x (B, T, M) -> (x', load
    (E,) or None for a dense layer): pre-norm RMSNorm, grouped-query
    attention whose KIND is ``hybrid_layer_pattern[i]``, then a feed-forward
    chosen by ``moe_layer_freq[i]``.

    Attention, both kinds: one bias-free projection to [q | k | v], keys as
    wide as queries (``head_dim``) and values narrower (``v_head_dim``), no
    q/k norm; rotary positions over the FIRST ``rotary_dim`` features of
    each q and k head; the values scaled by ``attention_value_scale`` before
    they are attended or cached. A full layer (0) has ``num_kv_heads``
    key/value heads and rotates at ``rope_theta``; a window layer (1) has
    ``swa_num_kv_heads``, rotates at ``swa_rope_theta``, attends the last
    ``sliding_window`` positions and carries ``layer<i>_sink_bias`` (H,),
    one logit a query head in its softmax's denominator.
    ``attend(i, q, k, v, sink)`` is the one thing the prefill and the decode
    graph do differently (``sink`` None in a full layer): it takes the
    rotated head-major (B, H or Hkv, T, d) tensors and returns
    (B, H, T, v_head_dim). The feed-forward: the gated SiLU MLP where
    ``moe_layer_freq[i]`` is 0, else sigmoid-routed experts alone
    (``_sigmoid_experts``), of which the layer may hold a share."""
    name = "layer%d" % i
    d, eps, hq = block["model_dim"], block["rms_eps"], block["num_heads"]
    dk, dv = block["head_dim"], block["v_head_dim"]
    windowed = block["window_layers"][i]
    fc = lambda data, width, tag: sym.FullyConnected(
        data=data, num_hidden=width, no_bias=True, flatten=False,
        name="%s_%s" % (name, tag))
    h = sym.RMSNorm(x, eps=eps, name="%s_ln1" % name)
    q, k, v = _grouped_qkv(fc, h, seq_len, hq, _window_heads(block, i)[1],
                           dk, dv)
    q, k = (sym.RotaryEmbedding(
        a, positions, rotary_dim=block["rotary_dim"],
        base=block["swa_rope_theta" if windowed else "rope_theta"],
        name="%s_%srope" % (name, tag)) for a, tag in ((q, "q"), (k, "k")))
    sink = sym.Variable("%s_sink_bias" % name, shape=(hq,)) if windowed \
        else None
    att = attend(i, q, k, v * block["attention_value_scale"], sink)
    x = x + fc(_merge_heads(att, seq_len, hq * dv), d, "proj")
    h = sym.RMSNorm(x, eps=eps, name="%s_ln2" % name)
    if not block["expert_layers"][i]:
        return x + _gated_mlp(fc, h, block["ffn_dim"], d, "mlp"), None
    moe = _sigmoid_experts(h, name, block)
    return x + sym.Reshape(moe[0], shape=(-1, seq_len, d)), moe[1]


def _mimo_prefill_symbol(vocab_size, num_layers, prefill_len, **sizes):
    return _window_prefill_symbol(_mimo_sizes(num_layers, **sizes), _mimo_layer,
                                  vocab_size, prefill_len)


def _mimo_decode_symbol(vocab_size, num_layers, num_slots, page_size,
                        token_out=True, **sizes):
    return _window_decode_symbol(_mimo_sizes(num_layers, **sizes), _mimo_layer,
                                 vocab_size, num_slots, page_size, token_out)


def _mimo_param_shapes(vocab_size, num_layers, **sizes):
    block = _mimo_sizes(num_layers, **sizes)
    d, e, f = block["model_dim"], block["num_experts"], block["moe_ffn_dim"]
    held = block["num_local_experts"] or e
    hq, dk, dv = (block[k] for k in ("num_heads", "head_dim", "v_head_dim"))
    shapes = {"embed_weight": (vocab_size, d), "final_ln_gamma": (d,),
              "lm_head_weight": (vocab_size, d)}
    for i, windowed in enumerate(block["window_layers"]):
        n = "layer%d_" % i
        hkv = _window_heads(block, i)[1]
        shapes.update({n + "ln1_gamma": (d,), n + "ln2_gamma": (d,),
                       n + "qkv_weight": ((hq + hkv) * dk + hkv * dv, d),
                       n + "proj_weight": (d, hq * dv)})
        if windowed:
            shapes[n + "sink_bias"] = (hq,)
        if not block["expert_layers"][i]:
            shapes.update({n + "mlp_in_weight": (2 * block["ffn_dim"], d),
                           n + "mlp_out_weight": (d, block["ffn_dim"])})
            continue
        shapes.update({
            n + "router_weight": (e, d), n + "router_bias": (e,),
            n + "experts_gate_weight": (held, d, f),
            n + "experts_up_weight": (held, d, f),
            n + "experts_down_weight": (held, f, d)})
    return shapes


# ------------- Laguna (window and full layers of different query-head counts)
def _laguna_sizes(num_layers, num_heads, model_dim, ffn_dim, layer_types,
                  swa_num_heads=None, num_kv_heads=None, head_dim=None,
                  sliding_window=512, rotary_dim=None, rope_theta=5e5,
                  swa_rope_theta=1e4, yarn_factor=0.0,
                  yarn_original_max_position=0, yarn_beta_fast=32.0,
                  yarn_beta_slow=1.0, attention_factor=1.0, moe_ffn_dim=None,
                  num_experts=256, num_experts_per_tok=10, num_local_experts=0,
                  local_expert_offset=0, num_shared_experts=1,
                  first_dense_layers=1, rms_eps=1e-6,
                  routed_scaling_factor=1.0, norm_topk_prob=True,
                  dtype="float32", **kwargs):
    """``_laguna_layer``'s keywords from a builder's (``num_heads`` is a
    full layer's query heads, ``swa_num_heads`` a window layer's, over the
    same ``num_kv_heads``; ``ffn_dim`` is the leading dense layers' width,
    ``moe_ffn_dim`` one expert's; ``num_local_experts`` = 0 holds every
    expert; ``yarn_factor`` = 0 leaves the full layers' rotary plain;
    keywords of the other architectures are dropped)."""
    kinds = tuple(layer_types)
    if len(kinds) != num_layers \
            or set(kinds) - {"full_attention", "sliding_attention"}:
        raise MXNetError("laguna: layer_types must name %d layers "
                         "'full_attention' or 'sliding_attention', got %r"
                         % (num_layers, kinds))
    if not 0 <= first_dense_layers <= num_layers:
        raise MXNetError("laguna: first_dense_layers %d outside [0, %d]"
                         % (first_dense_layers, num_layers))
    head_dim = head_dim or model_dim // num_heads
    rotary_dim = rotary_dim or head_dim
    if rotary_dim % 2 or rotary_dim > head_dim:
        raise MXNetError("laguna: rotary_dim %d must be even and at most "
                         "head_dim %d" % (rotary_dim, head_dim))
    num_kv_heads = num_kv_heads or num_heads
    return dict(
        num_layers=num_layers, layer_types=kinds,
        window_layers=tuple(k == "sliding_attention" for k in kinds),
        num_heads=num_heads, swa_num_heads=swa_num_heads or num_heads,
        num_kv_heads=num_kv_heads, swa_num_kv_heads=num_kv_heads,
        head_dim=head_dim, v_head_dim=head_dim, model_dim=model_dim,
        ffn_dim=ffn_dim, moe_ffn_dim=moe_ffn_dim,
        sliding_window=int(sliding_window),
        # a full layer's rotary, then a window layer's: ``RotaryEmbedding``'s
        # attributes
        rotary=dict(
            base=float(rope_theta), rotary_dim=rotary_dim,
            **(dict(yarn_factor=float(yarn_factor),
                    yarn_original_max_position=int(
                        yarn_original_max_position),
                    yarn_beta_fast=float(yarn_beta_fast),
                    yarn_beta_slow=float(yarn_beta_slow),
                    attention_factor=float(attention_factor))
               if yarn_factor else {})),
        swa_rotary=dict(base=float(swa_rope_theta)),
        num_experts=num_experts, num_experts_per_tok=num_experts_per_tok,
        num_local_experts=int(num_local_experts),
        local_expert_offset=int(local_expert_offset),
        num_shared_experts=num_shared_experts,
        first_dense_layers=first_dense_layers, rms_eps=rms_eps,
        routed_scaling_factor=float(routed_scaling_factor),
        norm_topk_prob=bool(norm_topk_prob), dtype=dtype)


def _laguna_layer(x, i, positions, seq_len, attend, block):
    """One ``model_type: laguna`` block on x (B, T, M) -> (x', load (E,) or
    None for a dense layer): pre-norm RMSNorm, grouped-query attention whose
    KIND is ``layer_types[i]``, then ``_deepseek_v3_ffn`` (a gated SiLU MLP
    in the first ``first_dense_layers`` layers, after them sigmoid-routed
    experts, of which the layer may hold a share, beside a shared gated MLP
    every token takes).

    Attention: one bias-free projection to [q | k | v] whose WIDTH follows
    the kind: a window layer has ``swa_num_heads`` query heads, a full layer
    ``num_heads``, both over ``num_kv_heads`` key/value heads of
    ``head_dim`` (``_window_heads``); an RMSNorm over each q and each k
    head's features (``qnorm`` / ``knorm``: one gamma of ``head_dim`` the
    heads share); then rotary positions by the kind: a window layer turns
    all its features plainly at ``swa_rope_theta``, a full layer the FIRST
    ``rotary_dim`` at ``rope_theta`` under YaRN (``RotaryEmbedding``'s
    ``yarn_*`` attributes and ``attention_factor``). ``attend(i, q, k, v)``
    is the one thing the prefill and the decode graph do differently
    (``_window_prefill_attend`` / ``_window_step_attend``: a band or a ring
    for a window layer, causal attention or the pools for a full one). A
    gate a head, ``softplus(W_g h)`` in float32 from the layer's normed
    input, multiplies a head's context before the output projection."""
    name = "layer%d" % i
    d, eps, dh = block["model_dim"], block["rms_eps"], block["head_dim"]
    windowed = block["window_layers"][i]
    hq, hkv = _window_heads(block, i)
    fc = lambda data, width, tag, **kw: sym.FullyConnected(
        data=data, num_hidden=width, no_bias=True, flatten=False,
        name="%s_%s" % (name, tag), **kw)
    h = sym.RMSNorm(x, eps=eps, name="%s_ln1" % name)
    q, k, v = _grouped_qkv(fc, h, seq_len, hq, hkv, dh)
    q, k = (sym.RotaryEmbedding(
        sym.RMSNorm(a, eps=eps, name="%s_%snorm" % (name, tag)), positions,
        name="%s_%srope" % (name, tag),
        **block["swa_rotary" if windowed else "rotary"])
        for a, tag in ((q, "q"), (k, "k")))
    att = attend(i, q, k, v)
    gate = sym.Reshape(sym.Activation(
        fc(h, hq, "gate", out_dtype="float32"), act_type="softrelu"),
        shape=(-1, seq_len, hq, 1))
    att = sym.Reshape(sym.Cast(sym.broadcast_mul(
        sym.Cast(sym.transpose(att, axes=(0, 2, 1, 3)), dtype="float32"),
        gate), dtype=block["dtype"]), shape=(-1, seq_len, hq * dh))
    return _deepseek_v3_ffn(x + fc(att, d, "proj"), i, fc, seq_len, block)


def _laguna_prefill_symbol(vocab_size, num_layers, prefill_len, **sizes):
    return _window_prefill_symbol(_laguna_sizes(num_layers, **sizes), _laguna_layer,
                                  vocab_size, prefill_len)


def _laguna_decode_symbol(vocab_size, num_layers, num_slots, page_size,
                          token_out=True, **sizes):
    return _window_decode_symbol(_laguna_sizes(num_layers, **sizes), _laguna_layer,
                                 vocab_size, num_slots, page_size, token_out)


def _laguna_param_shapes(vocab_size, num_layers, **sizes):
    block = _laguna_sizes(num_layers, **sizes)
    d, dh = block["model_dim"], block["head_dim"]
    shapes = {"embed_weight": (vocab_size, d), "final_ln_gamma": (d,),
              "lm_head_weight": (vocab_size, d)}
    for i in range(num_layers):
        n = "layer%d_" % i
        hq, hkv = _window_heads(block, i)
        shapes.update({
            n + "ln1_gamma": (d,), n + "ln2_gamma": (d,),
            n + "qkv_weight": ((hq + 2 * hkv) * dh, d),
            n + "qnorm_gamma": (dh,), n + "knorm_gamma": (dh,),
            n + "gate_weight": (hq, d), n + "proj_weight": (d, hq * dh)})
        shapes.update(_deepseek_v3_ffn_shapes(block, i))
    return shapes


# ----------------- Phi-4-mini-flash (SambaY: Mamba-1, differential attention)
def _phi4flash_sizes(num_layers, num_heads, model_dim, ffn_dim,
                     num_kv_heads=None, head_dim=None, sliding_window=512,
                     mb_per_layer=2, mamba_state=16, mamba_conv=4,
                     mamba_expand=2, mamba_dt_rank=None, dtype="float32",
                     **kwargs):
    """``_phi4flash_layer``'s keywords from a builder's (defaults: the
    family's ``configuration_phi4flash.py``; keywords of the other
    architectures are dropped). ``kinds`` names every layer's mixer, chosen
    by DEPTH as well as parity: up to and including layer ``half`` =
    N / 2 the self-decoder (even ``mamba``, odd ``window``), layer half + 1
    ``full`` attention, whose keys and values are the one pool, then the
    cross-decoder (even ``gmu``, odd ``cross``)."""
    hkv = num_kv_heads or num_heads
    if num_layers % 4 or int(mb_per_layer) != 2:
        raise MXNetError("phi4flash: a multiple of 4 layers and "
                         "mb_per_layer 2, got %d and %r"
                         % (num_layers, mb_per_layer))
    if num_heads % 2 or hkv % 2 or num_heads % hkv:
        raise MXNetError("phi4flash: differential attention pairs its heads: "
                         "%d query heads over %d key/value heads"
                         % (num_heads, hkv))
    half = num_layers // 2
    kinds = tuple("mamba" if i <= half and i % 2 == 0
                  else "window" if i < half
                  else "full" if i == half + 1
                  else "gmu" if i % 2 == 0 else "cross"
                  for i in range(num_layers))
    return dict(
        kinds=kinds, half=half, num_heads=num_heads, num_kv_heads=hkv,
        head_dim=head_dim or model_dim // num_heads, model_dim=model_dim,
        ffn_dim=ffn_dim, sliding_window=int(sliding_window),
        inner=int(mamba_expand) * model_dim, mamba_state=int(mamba_state),
        mamba_conv=int(mamba_conv),
        mamba_dt_rank=int(mamba_dt_rank or -(-model_dim // 16)), dtype=dtype)


def _phi4flash_mamba(op, i, u, block, **inputs):
    """One of ops/ssm.py's two Mamba-1 operators on layer ``i``'s weights,
    whose shapes the graph names (the operator's data carries neither the
    kernel, nor the rank, nor the state)."""
    e, n, r = (block[k] for k in ("inner", "mamba_state", "mamba_dt_rank"))
    shapes = (("conv_weight", (e, block["mamba_conv"])), ("conv_bias", (e,)),
              ("x_weight", (r + 2 * n, e)), ("dt_weight", (e, r)),
              ("dt_bias", (e,)), ("A_log", (e, n)), ("D", (e,)))
    return op(u, *(sym.Variable("layer%d_mamba1_%s" % (i, w), shape=shape)
                   for w, shape in shapes),
              name="layer%d_mamba1_core" % i, **inputs)


def _diff_queries(q, seq_len, hq, dh):
    """Differential attention's queries for a read that knows one softmax:
    q (B, T, hq * dh), head 2j the first and head 2j + 1 the second query of
    pair j, becomes (B, hq, T, 2 dh), head 2j ``[q1_j | 0]`` and head 2j + 1
    ``[0 | q2_j]``. Against keys kept ``[k1_g | k2_g]`` the zeros leave
    ``q1 k1`` and ``q2 k2`` alone, and against values kept ``[v1_g | v2_g]``
    each softmax applies to both halves: the two reads of a pair are two
    ordinary heads of 2 dh, and every attention operator serves them."""
    pairs = sym.Reshape(q, shape=(-1, seq_len, hq // 2, 2, dh))
    first, second = (sym.slice_axis(pairs, axis=3, begin=c, end=c + 1)
                     for c in (0, 1))
    nothing = sym.zeros_like(first)
    padded = sym.Concat(sym.Concat(first, nothing, dim=4),
                        sym.Concat(nothing, second, dim=4), dim=3)
    return _split_heads(padded, seq_len, hq, 2 * dh)


def _diff_combine(att, name, depth, seq_len, hq, dh, dtype):
    """What follows the two reads of every pair, element-wise and float32:
    att (B, hq, T, 2 dh), heads 2j and 2j + 1 the contexts a1_j and a2_j ->
    ``(1 - lam0) * rms(a1 - lam * a2; <name>_subln_gamma)`` (B, T, hq * dh),
    ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0`` of the layer's four
    vectors ``<name>_lambda_*`` and ``lam0 = 0.8 - 0.6 exp(-0.3 depth)``."""
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * depth)
    vec = lambda tag: sym.Cast(sym.Variable(
        "%s_lambda_%s" % (name, tag), shape=(dh,)), dtype="float32")
    lam = sym.exp(sym.sum(vec("q1") * vec("k1"))) \
        - sym.exp(sym.sum(vec("q2") * vec("k2"))) + lam0
    pairs = sym.Reshape(
        sym.Cast(sym.SwapAxis(att, dim1=1, dim2=2), dtype="float32"),
        shape=(-1, seq_len, hq // 2, 2, 2 * dh))
    a1, a2 = (sym.slice_axis(pairs, axis=3, begin=c, end=c + 1)
              for c in (0, 1))
    diff = a1 - sym.broadcast_mul(a2, sym.Reshape(lam, shape=(1, 1, 1, 1, 1)))
    out = sym.RMSNorm(diff, eps=1e-5, name="%s_subln" % name) * (1.0 - lam0)
    return sym.Cast(sym.Reshape(out, shape=(-1, seq_len, hq * dh)),
                    dtype=dtype)


def _phi4flash_layer(x, i, seq_len, mix, block):
    """One ``model_type: phi4flash`` block on x (B, T, M) -> (x', its rows):
    pre-norm LayerNorm (weight and bias, float32 statistics), the mixer
    ``kinds[i]`` names, then the gated SiLU MLP every layer has. No
    positions anywhere. ``mix`` holds what the prefill and the decode graph
    do differently:

    ``mamba``: one bias-free projection to [u | z], asked for in float32;
    ``mix["scan"](i, u)`` runs the Mamba-1 core and returns y (B, T, E), the
    scan's output BEFORE its gate; layer ``half`` leaves it in ``mix["m"]``
    for the gated memory units; ``y * silu(z)`` goes through the output
    projection. ``gmu``: ``W_out (m * silu(W_in h))``, no state of its own.
    ``window`` / ``full``: one projection with a bias to [q | k | v];
    differential attention in its padded-query form (``_diff_queries``):
    keys and values are kept as hkv / 2 heads of 2 dh, ``mix[kind](i, q, k,
    v)`` takes the head-major tensors and returns the context
    (B, hq, T, 2 dh); ``_diff_combine`` and the output projection with its
    bias follow. ``cross``: its own query projection alone,
    ``mix["cross"](i, q)`` reads what layer half + 1 kept. In a prefill
    ``mix["last_row"]`` narrows x to the prompt's last real row at layer
    half + 1, AFTER that layer's keys and values are made of the bucket:
    its query, its MLP and every layer behind it compute one row."""
    name = "layer%d" % i
    kind = block["kinds"][i]
    d, e, dtype = block["model_dim"], block["inner"], block["dtype"]
    hq, hkv, dh = (block[k] for k in ("num_heads", "num_kv_heads",
                                      "head_dim"))
    fc = lambda data, width, tag, **kw: sym.FullyConnected(
        data=data, num_hidden=width, flatten=False,
        name="%s_%s" % (name, tag), **kw)
    norm = lambda data, tag: sym.Cast(_layer_norm(
        sym.Cast(data, dtype="float32"), "%s_%s" % (name, tag), d),
        dtype=dtype)
    silu = lambda data: sym.Activation(data, act_type="silu")
    h = norm(x, "ln1")
    if kind == "mamba":
        uz = fc(h, 2 * e, "mamba1_in", no_bias=True, out_dtype="float32")
        y = mix["scan"](i, sym.slice_axis(uz, axis=2, begin=0, end=e))
        if i == block["half"]:
            mix["m"] = y
        gated = y * silu(sym.slice_axis(uz, axis=2, begin=e, end=2 * e))
        mixed = fc(sym.Cast(gated, dtype=dtype), d, "mamba1_out",
                   no_bias=True)
    elif kind == "gmu":
        gate = silu(fc(h, e, "gmu_in", no_bias=True, out_dtype="float32"))
        mixed = fc(sym.Cast(mix["m"] * gate, dtype=dtype), d, "gmu_out",
                   no_bias=True)
    else:
        tag = "cross" if kind == "cross" else "self"
        if kind == "cross":
            att = mix["cross"](i, _diff_queries(fc(h, hq * dh, "cross_q"),
                                                seq_len, hq, dh))
        else:
            kv_len, ends = seq_len, (0, hq * dh, (hq + hkv) * dh,
                                     (hq + 2 * hkv) * dh)
            if kind == "full" and mix.get("last_row"):
                # keys and values of the bucket, the query of one row: the
                # fused matrix's rows, cut where the fused output would be
                weight, bias = (sym.Variable(
                    "%s_self_qkv_%s" % (name, w), shape=shape)
                    for w, shape in (("weight", (ends[-1], d)),
                                     ("bias", (ends[-1],))))
                part = lambda data, a, b, what: sym.FullyConnected(
                    data=data, num_hidden=b - a, flatten=False,
                    weight=sym.slice_axis(weight, axis=0, begin=a, end=b),
                    bias=sym.slice_axis(bias, axis=0, begin=a, end=b),
                    name="%s_self_%s" % (name, what))
                kv = part(h, ends[1], ends[3], "kv")
                x, h, mix["m"] = (mix["last_row"](a)
                                  for a in (x, h, mix["m"]))
                seq_len = 1
                q = part(h, ends[0], ends[1], "q")
                k, v = (sym.slice_axis(kv, axis=2, begin=a - ends[1],
                                       end=b - ends[1])
                        for a, b in zip(ends[1:], ends[2:]))
            else:
                qkv = fc(h, ends[-1], "self_qkv")
                q, k, v = (sym.slice_axis(qkv, axis=2, begin=a, end=b)
                           for a, b in zip(ends, ends[1:]))
            k, v = (_split_heads(a, kv_len, hkv // 2, 2 * dh)
                    for a in (k, v))
            att = mix[kind](i, _diff_queries(q, seq_len, hq, dh), k, v)
        mixed = fc(_diff_combine(att, "%s_%s" % (name, tag), i, seq_len, hq,
                                 dh, dtype), d, tag + "_proj")
    x = x + mixed
    no_bias = lambda data, width, tag: fc(data, width, tag, no_bias=True)
    return x + _gated_mlp(no_bias, norm(x, "ln2"), block["ffn_dim"], d,
                          "mlp"), seq_len


def _phi4flash_stack(vocab_size, seq_len, mix, block):
    """Embedding (tied to the head), the layers, the final LayerNorm and the
    head: ``data`` (B, T) -> float32 logits (B x rows, vocab), ``rows`` what
    the layers left of T (``_phi4flash_layer``)."""
    table = sym.Variable("embed_weight")
    d = block["model_dim"]
    x = sym.Embedding(data=sym.Variable("data"), weight=table,
                      input_dim=vocab_size, output_dim=d, name="embed")
    for i in range(len(block["kinds"])):
        x, seq_len = _phi4flash_layer(x, i, seq_len, mix, block)
    x = sym.Cast(_layer_norm(sym.Cast(x, dtype="float32"), "final_ln", d),
                 dtype=block["dtype"])
    return sym.FullyConnected(
        data=sym.Reshape(x, shape=(-1, d)), weight=table,
        num_hidden=vocab_size, no_bias=True, out_dtype="float32",
        name="lm_head")


def _phi4flash_prefill_symbol(vocab_size, num_layers, prefill_len, **sizes):
    block = _phi4flash_sizes(num_layers, **sizes)
    hq, hkv, dh, w = (block[k] for k in ("num_heads", "num_kv_heads",
                                         "head_dim", "sliding_window"))
    length = sym.Variable("length")     # (1, 1): real tokens of the bucket
    cache = []      # the layers are built in order, so is this
    kept = {}       # layer half + 1's keys and values, as a read takes them
    # the bucket's slots a read of the one row may see: those of the prompt
    seen = (1.0 - sym.broadcast_lesser(
        sym.Reshape(sym._arange(start=0, stop=prefill_len),
                    shape=(1, prefill_len)), length)) * float(_NEG)

    def scan(i, u):
        core = _phi4flash_mamba(sym.Mamba1Scan, i, u, block, length=length)
        cache.extend([core[1], core[2]])
        return core[0]

    def window(i, q, k, v):
        cache.extend([k, v])
        return sym.MultiHeadAttention(
            query=q, key=k, value=v, causal=True, window=w, scale=dh ** -0.5,
            name="layer%d_self_att" % i)

    def read(i, q, tag):
        # one row a prompt against the bucket's keys and values as ONE page
        # of a pool, under the prompt's length: no row of the bucket but
        # the last real one is a query from here on
        ctx = sym.KVPoolAttention(
            sym.Reshape(q, shape=(-1, hq, 2 * dh)), kept["k"], kept["v"],
            seen, scale=dh ** -0.5, name="layer%d_%s_att" % (i, tag))
        return sym.Reshape(ctx, shape=(-1, hq, 1, 2 * dh))

    def full(i, q, k, v):
        cache.extend([k, v])
        for tag, a in (("k", k), ("v", v)):
            kept[tag] = sym.Reshape(sym.SwapAxis(a, dim1=1, dim2=2), shape=(
                -1, prefill_len, hkv * dh)) if pool_paged(hkv // 2, 2 * dh) \
                else sym.Reshape(a, shape=(-1, prefill_len, 2 * dh))
        return read(i, q, "self")

    mix = dict(scan=scan, window=window, full=full,
               cross=lambda i, q: read(i, q, "cross"),
               last_row=lambda a: _last_real_row(a, length))
    return sym.Group([_phi4flash_stack(vocab_size, prefill_len, mix, block)]
                     + cache)


def _phi4flash_decode_symbol(vocab_size, num_layers, num_slots, page_size,
                             token_out=True, **sizes):
    block = _phi4flash_sizes(num_layers, **sizes)
    hq, hkv, dh = (block[k] for k in ("num_heads", "num_kv_heads",
                                      "head_dim"))
    pos_idx = sym.Variable("pos_idx")
    write_slot = sym.Variable("write_slot")
    write, pages = _pool_step_inputs(pos_idx, num_slots, page_size,
                                     write_slot)
    cache = []      # the layers are built in order, so is this
    pool = []       # layer half + 1's UPDATED pools: what eight layers read
    # one token a lane: a head-major (B, H, 1, d) tensor is rows (B, H, d)
    rows = lambda a, n: sym.Reshape(a, shape=(-1, n, 2 * dh))

    def scan(i, u):
        core = _phi4flash_mamba(
            sym.Mamba1Step, i, sym.Reshape(u, shape=(0, -1)), block,
            ssm_state=sym.Variable("ssm_state_%d" % i),
            conv_state=sym.Variable("conv_state_%d" % i), stepped=write_slot)
        cache.extend([core[1], core[2]])
        return sym.Reshape(core[0], shape=(0, 1, -1))

    def window(i, q, k_new, v_new):
        # the lane's own rings, no frame and no table
        rings = sym.KVRingWrite(
            sym.Variable("ring_k_%d" % i), rows(k_new, hkv // 2),
            sym.Variable("ring_v_%d" % i), rows(v_new, hkv // 2), pos_idx,
            write_slot, num_rings=2, name="layer%d_self_kvupd" % i)
        cache.extend([rings[0], rings[1]])
        ctx = sym.KVRingAttention(
            rows(q, hq), rings[0], rings[1], pos_idx, write_slot,
            scale=dh ** -0.5, name="layer%d_self_att" % i)
        return sym.Reshape(ctx, shape=(-1, hq, 1, 2 * dh))

    def read(i, q, tag):
        ctx = sym.KVPoolAttention(
            rows(q, hq), pool[0], pool[1], scale=dh ** -0.5,
            name="layer%d_%s_att" % (i, tag), **pages)
        return sym.Reshape(ctx, shape=(-1, hq, 1, 2 * dh))

    def full(i, q, k_new, v_new):
        # the ONE write of the step's keys and values; the cross layers take
        # the updated pools as operands (the token's own key among them) and
        # write nothing, so the donated pools are swapped back once
        pool.extend(write(i, {"k": rows(k_new, hkv // 2),
                              "v": rows(v_new, hkv // 2)}))
        cache.extend(pool)
        return read(i, q, "self")

    mix = dict(scan=scan, window=window, full=full,
               cross=lambda i, q: read(i, q, "cross"))
    return _token_head(_phi4flash_stack(vocab_size, 1, mix, block), cache,
                       "greedy_token" if token_out else None)


def _phi4flash_param_shapes(vocab_size, num_layers, **sizes):
    block = _phi4flash_sizes(num_layers, **sizes)
    d, e, f = block["model_dim"], block["inner"], block["ffn_dim"]
    hq, hkv, dh = (block[k] for k in ("num_heads", "num_kv_heads",
                                      "head_dim"))
    n_state, rank = block["mamba_state"], block["mamba_dt_rank"]
    shapes = {"embed_weight": (vocab_size, d), "final_ln_gamma": (d,),
              "final_ln_beta": (d,)}
    for i, kind in enumerate(block["kinds"]):
        n = "layer%d_" % i
        shapes.update({n + "ln1_gamma": (d,), n + "ln1_beta": (d,),
                       n + "ln2_gamma": (d,), n + "ln2_beta": (d,),
                       n + "mlp_in_weight": (2 * f, d),
                       n + "mlp_out_weight": (d, f)})
        if kind == "mamba":
            m = n + "mamba1_"
            shapes.update({
                m + "in_weight": (2 * e, d),
                m + "conv_weight": (e, block["mamba_conv"]),
                m + "conv_bias": (e,), m + "x_weight": (rank + 2 * n_state, e),
                m + "dt_weight": (e, rank), m + "dt_bias": (e,),
                m + "A_log": (e, n_state), m + "D": (e,),
                m + "out_weight": (d, e)})
            continue
        if kind == "gmu":
            shapes.update({n + "gmu_in_weight": (e, d),
                           n + "gmu_out_weight": (d, e)})
            continue
        a = n + ("cross_" if kind == "cross" else "self_")
        shapes.update({a + "proj_weight": (d, hq * dh), a + "proj_bias": (d,),
                       a + "subln_gamma": (2 * dh,)})
        shapes.update({a + "lambda_" + v: (dh,)
                       for v in ("q1", "k1", "q2", "k2")})
        rows = hq * dh if kind == "cross" else (hq + 2 * hkv) * dh
        proj = a + ("q_" if kind == "cross" else "qkv_")
        shapes.update({proj + "weight": (rows, d), proj + "bias": (rows,)})
    return shapes


# ------------------------------- Ouro (ONE stack of layers, run several times)
def _ouro_sizes(num_heads, model_dim, ffn_dim, head_dim=None, total_ut_steps=4,
                early_exit_threshold=1.0, rope_theta=1e6, rms_eps=1e-6,
                dtype="float32", **kwargs):
    """``_ouro_layer``'s keywords from a builder's (defaults: Ouro-2.6B's;
    keywords of the other architectures are dropped)."""
    passes = int(total_ut_steps)
    if passes < 1:
        raise MXNetError("ouro: total_ut_steps must be at least 1, got %r"
                         % (total_ut_steps,))
    return dict(num_heads=num_heads,
                head_dim=head_dim or model_dim // num_heads,
                model_dim=model_dim, ffn_dim=ffn_dim, passes=passes,
                exit_threshold=float(early_exit_threshold),
                rope_theta=rope_theta, rms_eps=rms_eps, dtype=dtype)


def loop_passes(arch, **sizes):
    """How many times a graph of ``arch`` applies its stack of layers: every
    pass keeps keys and values of its own, so a pool of ``decode_cache`` is
    bound with that many times the decoder's slots, pass ``u`` in frames
    ``u * frames`` onward (1 for every arch that runs its layers once)."""
    return _ouro_sizes(**sizes)["passes"] if arch == "ouro" else 1


def _shared_variables():
    """``var(name)``: THE Variable of that name in the graph being built. A
    looped stack names an operator by (pass, layer) and its weight by layer
    alone, and two Variables of one name would be two arguments."""
    made = {}
    return lambda name: made.setdefault(name, sym.Variable(name))


def _ouro_layer(x, u, i, positions, seq_len, attend, block, var):
    """Layer ``i`` in pass ``u`` of the looped stack, on x (B, T, M): the
    SAME weights in every pass (``layer<i>_*``, through ``var``), operators
    named ``pass<u>_layer<i>_*``. Sandwich norms, four RMSNorms a layer:
    ``x + ln2(W_o attend(rope(q), rope(k), v))`` of ``ln1(x)``'s fused
    bias-free q, k, v (rotary half-split pairs over the whole head), then
    ``x + ln4(gated SiLU MLP(ln3(x)))``. ``attend(u, i, q, k, v)`` takes the
    head-major (B, H, T, dh) tensors and returns (B, H, T, dh): over the
    bucket in the prefill, over pass ``u``'s own keys and values of layer
    ``i`` in the pool in a decode step."""
    name = "pass%d_layer%d" % (u, i)
    h, dh, eps = block["num_heads"], block["head_dim"], block["rms_eps"]
    fc = lambda data, width, tag, **kw: sym.FullyConnected(
        data=data, weight=var("layer%d_%s_weight" % (i, tag)),
        num_hidden=width, no_bias=True, flatten=False,
        name="%s_%s" % (name, tag), **kw)
    norm = lambda data, tag: sym.RMSNorm(
        data, var("layer%d_%s_gamma" % (i, tag)), eps=eps,
        name="%s_%s" % (name, tag))
    q, k, v = _grouped_qkv(fc, norm(x, "ln1"), seq_len, h, h, dh)
    q = sym.RotaryEmbedding(q, positions, base=block["rope_theta"],
                            name="%s_qrope" % name)
    k = sym.RotaryEmbedding(k, positions, base=block["rope_theta"],
                            name="%s_krope" % name)
    att = _merge_heads(attend(u, i, q, k, v), seq_len, h * dh)
    x = x + norm(fc(att, block["model_dim"], "proj"), "ln2")
    return x + norm(_gated_mlp(fc, norm(x, "ln3"), block["ffn_dim"],
                               block["model_dim"], "mlp"), "ln4")


def _ouro_passes(data, vocab_size, num_layers, positions, seq_len, attend,
                 block, var, rows):
    """The embedding and the stack ``passes`` times over: after layer N - 1 of
    every pass the ONE final norm, whose output the next pass starts from.
    ``rows(x)`` takes what the head may need of a pass's output, (R, M) rows
    (the prompt's last real row; a step's lanes). Returns those rows, a
    pass each."""
    x = sym.Embedding(data=data, weight=var("embed_weight"),
                      input_dim=vocab_size, output_dim=block["model_dim"],
                      name="embed")
    handed = []
    for u in range(block["passes"]):
        for i in range(num_layers):
            x = _ouro_layer(x, u, i, positions, seq_len, attend, block, var)
        x = sym.RMSNorm(x, var("final_ln_gamma"), eps=block["rms_eps"],
                        name="pass%d_final_ln" % u)
        handed.append(rows(x))
    return handed


def _ouro_head(handed, vocab_size, block, var):
    """Which pass feeds the head, and the head: ``handed[u]`` (R, M) is
    pass u + 1's output h. The exit gate reads every pass but the last:
    ``lambda_u = sigmoid(w_g . h_u + b_g)`` in float32, ``p_u = lambda_u
    prod_{j<u}(1 - lambda_j)``, and the head takes the FIRST pass whose
    cumulated ``p`` reaches ``early_exit_threshold``, the last pass where
    none does (the last pass takes what probability is left, so its own
    gate is never read). Every pass has run by then, whatever is chosen:
    later tokens attend this token's keys of every pass. Returns (float32
    logits (R, vocab), the chosen pass (R,) float32, counted from 1)."""
    passes = len(handed)
    reached, survive, cum = [], None, None
    for u, h in enumerate(handed[:-1]):
        gate = sym.FullyConnected(
            data=h, weight=var("exit_gate_weight"),
            bias=var("exit_gate_bias"), num_hidden=1, out_dtype="float32",
            name="pass%d_exit_gate" % u)
        lam = sym.Reshape(sym.sigmoid(gate), shape=(-1,))
        p = lam if survive is None else lam * survive
        cum = p if cum is None else cum + p
        survive = 1.0 - lam if survive is None else survive * (1.0 - lam)
        reached.append(cum >= block["exit_threshold"])
    # from the last pass down, so the FIRST pass that reached it stays; the
    # cumulated p never falls, so a pass that reached it is followed by
    # passes that did: the chosen pass is the last less those that reached it
    chosen = handed[-1]
    at = sym.Cast(sym.ones_like(sym.sum(handed[-1], axis=-1)),
                  dtype="float32") * float(passes)
    for u in reversed(range(passes - 1)):
        chosen = sym.where(reached[u], handed[u], chosen)
        at = at - reached[u]
    logits = sym.FullyConnected(
        data=chosen, weight=var("lm_head_weight"), num_hidden=vocab_size,
        no_bias=True, out_dtype="float32", name="lm_head")
    return logits, at


def _ouro_prefill_symbol(vocab_size, num_layers, prefill_len, **sizes):
    block = _ouro_sizes(**sizes)
    var = _shared_variables()
    positions = sym.Reshape(sym._arange(start=0, stop=prefill_len),
                            shape=(1, prefill_len))
    length = sym.Variable("length")
    kept = [([], []) for _ in range(num_layers)]  # a layer's K and V, a pass

    def attend(u, i, q, k, v):
        kept[i][0].append(k)
        kept[i][1].append(v)
        return sym.MultiHeadAttention(query=q, key=k, value=v, causal=True,
                                      name="pass%d_layer%d_att" % (u, i))

    handed = _ouro_passes(
        sym.Variable("data"), vocab_size, num_layers, positions, prefill_len,
        attend, block, var, lambda x: sym.Reshape(
            _last_real_row(x, length), shape=(-1, block["model_dim"])))
    logits, at = _ouro_head(handed, vocab_size, block, var)
    # ONE prompt a call: a layer's keys of every pass, pass-major, as the
    # layer's one pool keeps them (``loop_passes``)
    cache = [sym.Concat(*one, dim=0, name="layer%d_%s_passes" % (i, t))
             if len(one) > 1 else one[0]
             for i, pair in enumerate(kept) for t, one in zip("kv", pair)]
    return sym.Group([logits] + cache
                     + [sym.identity(at, name="exit_pass")])


def _ouro_decode_symbol(vocab_size, num_layers, num_slots, page_size,
                        token_out=True, **sizes):
    block = _ouro_sizes(**sizes)
    var = _shared_variables()
    h, dh = block["num_heads"], block["head_dim"]
    pos_idx = sym.Variable("pos_idx")
    write_slot = sym.Variable("write_slot")
    page_table = sym.Variable("page_table")
    pools = [[sym.Variable("kv_%s_%d" % (t, i)) for t in "kv"]
             for i in range(num_layers)]
    # pass u's keys and values of a layer sit ``u * num_slots`` slots into the
    # layer's ONE pool pair: the lane's page table and write slot, moved by
    # whole frames, address them; a lane that rides along stays negative
    writes = write_slot >= 0.0
    at_pass = []
    for u in range(block["passes"]):
        slot = write_slot + writes * float(u * num_slots)
        pages = dict(page_table=page_table + float(u * num_slots // page_size),
                     pos_idx=pos_idx, write_slot=slot)
        at_pass.append((slot, dict(
            pages, page_size=page_size, mask=sym.KVPageMask(
                page_size=page_size, num_slots=block["passes"] * num_slots,
                name="pass%d_kv_mask" % u, **pages))))

    def attend(u, i, q, k_new, v_new):
        # one token a lane: the head-major (B, H, 1, dh) tensors are the
        # pool's rows (B, H, dh)
        q, k_new, v_new = (sym.Reshape(a, shape=(-1, h, dh))
                           for a in (q, k_new, v_new))
        slot, read = at_pass[u]
        upd = sym.KVPoolSlotWrite(
            pools[i][0], k_new, pools[i][1], v_new, slot, num_pools=2,
            name="pass%d_layer%d_kvupd" % (u, i))
        pools[i] = [upd[0], upd[1]]
        ctx = sym.KVPoolAttention(q, upd[0], upd[1],
                                  name="pass%d_layer%d_att" % (u, i), **read)
        return sym.Reshape(ctx, shape=(-1, h, 1, dh))

    handed = _ouro_passes(
        sym.Variable("data"), vocab_size, num_layers, pos_idx, 1, attend,
        block, var, lambda x: sym.Reshape(x, shape=(-1, block["model_dim"])))
    logits, at = _ouro_head(handed, vocab_size, block, var)
    outs = [logits] + [pool for pair in pools for pool in pair]
    if token_out:
        # the greedy token and, riding the same small read, the pass that
        # fed its head: (B, 2) float32
        outs.append(sym.Concat(
            sym.Reshape(sym.argmax(logits, axis=-1), shape=(-1, 1)),
            sym.Reshape(at, shape=(-1, 1)), dim=1, name="greedy_token"))
    return sym.Group(outs)


def _ouro_param_shapes(vocab_size, num_layers, **sizes):
    """One entry a LAYER: the passes share them."""
    block = _ouro_sizes(**sizes)
    d, f = block["model_dim"], block["ffn_dim"]
    width = block["num_heads"] * block["head_dim"]
    shapes = {"embed_weight": (vocab_size, d), "final_ln_gamma": (d,),
              "exit_gate_weight": (1, d), "exit_gate_bias": (1,),
              "lm_head_weight": (vocab_size, d)}
    for i in range(num_layers):
        n = "layer%d_" % i
        shapes.update({n + "ln%d_gamma" % j: (d,) for j in (1, 2, 3, 4)})
        shapes.update({n + "qkv_weight": (3 * width, d),
                       n + "proj_weight": (d, width),
                       n + "mlp_in_weight": (2 * f, d),
                       n + "mlp_out_weight": (d, f)})
    return shapes


def decode_cache(arch, num_layers, num_heads, model_dim, head_dim=None,
                 **sizes):
    """What a decode graph of ``arch`` keeps between steps, in the order its
    cache inputs' updates follow the logits (and the prefill's values do):
    ``[(name, kind, shape)]``. A ``"pool"`` is addressed by slot, ``shape``
    is (heads, dh) and the buffer what ``ops.attention.pool_shape`` makes of
    it: (slots / page, page, heads * dh), a page one contiguous piece, where
    a token's row of all its heads is whole tiles of the chip's 128 lanes,
    (heads, slots, dh) where it is not (a latent row of 576; a toy model);
    a ``"row"`` is
    addressed by lane, ``shape`` is one lane's and the buffer (lanes,) +
    shape, float32. Latent attention keeps ONE pool an attention, of one
    head: the old kind, no new one (``longcat_flash``: TWO a layer,
    ``kv_c_<2i>`` and ``kv_c_<2i + 1>``, one a sublayer). Where
    ``layer_types`` chooses the mixer (``granite_hybrid``, ``lfm2_moe``) the
    list mixes the two kinds, in layer order; ``nemotron_h``'s expert blocks keep nothing and are skipped. A
    ``"ring"`` is a WINDOW layer's K or V (``mimo_v2_flash``):
    addressed by lane and position mod the window, ``shape`` is one lane's
    (heads, window, d) and the buffer (lanes,) + shape in the pools' type;
    it takes no frame and no page-table entry, whatever the lane's length.
    A full layer beside it keeps its pools, the key's wider than the
    value's.

    A pool may be READ by more layers than write it: ``phi4flash`` keeps ONE
    pool pair, layer N/2 + 1's, which that layer writes and it and every
    cross layer behind it read; the cross layers keep nothing. Its keys and
    values are kept in PAIRS of heads side by side (hkv / 2 heads of
    2 * head_dim, ``_diff_queries``), in the pool and in the window layers'
    rings alike; a Mamba-1 layer keeps its state (N, E), STATE-major (the
    minor dimension whole tiles of the chip's lanes), and its last
    convolution columns (K - 1, E), float32 rows.

    A LOOPED stack (``ouro``) keeps every pass's keys and values and lists a
    layer's pools ONCE: the buffer has ``loop_passes`` times the decoder's
    slots, pass u a whole pass's frames behind pass u - 1 (96 buffers for 48
    layers x 4 passes, not 384; a lane's page stands for all four)."""
    if arch == "phi4flash":
        block = _phi4flash_sizes(num_layers, num_heads=num_heads,
                                 model_dim=model_dim, head_dim=head_dim,
                                 **sizes)
        pairs, wide = block["num_kv_heads"] // 2, 2 * block["head_dim"]
        per_kind = {
            "mamba": [("ssm_state_%d", "row", (block["mamba_state"],
                                               block["inner"])),
                      ("conv_state_%d", "row", (block["mamba_conv"] - 1,
                                                block["inner"]))],
            "window": [("ring_%s_%%d" % t, "ring",
                        (pairs, block["sliding_window"], wide)) for t in "kv"],
            "full": [("kv_%s_%%d" % t, "pool", (pairs, wide)) for t in "kv"]}
        return [(name % i, kind, shape)
                for i, layer in enumerate(block["kinds"])
                for name, kind, shape in per_kind.get(layer, ())]
    if arch == "mimo_v2_flash":
        return _window_cache(_mimo_sizes(
            num_layers, num_heads=num_heads, model_dim=model_dim,
            head_dim=head_dim, **sizes))
    if arch == "laguna":
        return _window_cache(_laguna_sizes(
            num_layers, num_heads=num_heads, model_dim=model_dim,
            head_dim=head_dim, **sizes))
    if arch == "deepseek_v3":
        block = _deepseek_v3_sizes(num_layers, num_heads=num_heads,
                                   model_dim=model_dim, **sizes)
        return [("kv_c_%d" % i, "pool", (1, block["latent"] + block["rope"]))
                for i in range(num_layers)]
    if arch == "longcat_flash":     # a pool a SUBLAYER, two a layer
        block = _longcat_sizes(num_layers, num_heads=num_heads,
                               model_dim=model_dim, **sizes)
        return [("kv_c_%d" % j, "pool", (1, block["latent"] + block["rope"]))
                for j in range(2 * num_layers)]
    if arch == "dots3_note":
        block = _dots3_sizes(num_layers, num_heads=num_heads,
                             model_dim=model_dim, **sizes)
        row = lambda kind: block[kind]["latent"] + block[kind]["rope"]
        per_kind = {
            "full_attention": [
                ("kv_c_%d", "pool", (1, row("full_attention"))),
                ("kv_i_%d", "pool", (1, block["index_dim"])),
                ("sparse_sel_%d", "row", (block["index_topk"],))],
            "sliding_attention": [
                ("ring_c_%d", "ring", (1, block["sliding_window"],
                                       row("sliding_attention")))]}
        return [(name % i, kind, shape)
                for i, layer in enumerate(block["layer_types"])
                for name, kind, shape in per_kind[layer]]
    if arch not in ("granite_hybrid", "lfm2_moe", "nemotron_h"):
        pool = (num_heads, head_dim or model_dim // num_heads)
        return [("kv_%s_%d" % (t, i), "pool", pool)
                for i in range(num_layers) for t in "kv"]
    # a mixer chosen by layer_types: pools where it attends, rows elsewhere
    sizes.update(num_heads=num_heads, model_dim=model_dim, head_dim=head_dim)
    if arch == "lfm2_moe":
        block = _lfm2_moe_sizes(num_layers, **sizes)
        attends = "full_attention"
        per_kind = {"conv": [("conv_state_%d", "row", (
            block["conv_kernel"] - 1, block["model_dim"]))]}
    else:
        block = (_granite_sizes if arch == "granite_hybrid"
                 else _nemotron_h_sizes)(num_layers, **sizes)
        h, p, n = (block[k] for k in ("mamba_heads", "mamba_head_dim",
                                      "mamba_state"))
        attends = "attention"
        per_kind = {"mamba": [("ssm_state_%d", "row", (h, p, n)),
                              ("conv_state_%d", "row",
                               (block["mamba_conv"] - 1,
                                _mamba_conv_dim(block)))]}
    pool = (block["num_kv_heads"], block["head_dim"])
    per_kind[attends] = [("kv_k_%d", "pool", pool), ("kv_v_%d", "pool", pool)]
    # a layer of another kind (``nemotron_h``'s experts) keeps nothing
    return [(name % i, kind, shape)
            for i, layer in enumerate(block["layer_types"])
            for name, kind, shape in per_kind.get(layer, ())]


def _granite_param_shapes(vocab_size, num_layers, **sizes):
    block = _granite_sizes(num_layers, **sizes)
    d, ffn = block["model_dim"], block["ffn_dim"]
    shapes = {"embed_weight": (vocab_size, d), "final_ln_gamma": (d,)}
    for i, kind in enumerate(block["layer_types"]):
        n = "layer%d_" % i
        shapes.update({n + "ln1_gamma": (d,), n + "ln2_gamma": (d,),
                       n + "mlp_in_weight": (2 * ffn, d),
                       n + "mlp_out_weight": (d, ffn)})
        shapes.update(_gqa_param_shapes(block, n) if kind == "attention"
                      else _mamba2_param_shapes(block, n))
    return shapes


def param_shapes(arch, vocab_size, num_layers, num_heads, model_dim, ffn_dim,
                 head_dim=None, num_experts=64, **kwargs):
    """{name: shape} of the checkpoint the serving graphs of ``arch`` load,
    from the sizes alone (every arch of ``ARCHS`` but ``vaswani``, whose
    checkpoint is spelled where it always was, by its drivers and tests)."""
    if arch == "granite_hybrid":
        return _granite_param_shapes(
            vocab_size, num_layers, num_heads=num_heads, model_dim=model_dim,
            ffn_dim=ffn_dim, head_dim=head_dim, **kwargs)
    if arch == "deepseek_v3":
        return _deepseek_v3_param_shapes(
            vocab_size, num_layers, num_heads=num_heads, model_dim=model_dim,
            ffn_dim=ffn_dim, num_experts=num_experts, **kwargs)
    if arch in ("lfm2_moe", "mimo_v2_flash", "phi4flash", "nemotron_h",
                "dots3_note", "ouro", "laguna", "longcat_flash"):
        shapes = {"lfm2_moe": _lfm2_moe_param_shapes,
                  "mimo_v2_flash": _mimo_param_shapes,
                  "phi4flash": _phi4flash_param_shapes,
                  "nemotron_h": _nemotron_h_param_shapes,
                  "dots3_note": _dots3_param_shapes,
                  "ouro": _ouro_param_shapes,
                  "laguna": _laguna_param_shapes,
                  "longcat_flash": _longcat_param_shapes}[arch]
        return shapes(
            vocab_size, num_layers, num_heads=num_heads, model_dim=model_dim,
            ffn_dim=ffn_dim, head_dim=head_dim, num_experts=num_experts,
            **kwargs)
    if arch != "olmoe":
        raise MXNetError("param_shapes knows archs %s, not %r" % (
            ", ".join(repr(a) for a in ARCHS if a != "vaswani"), arch))
    d = model_dim
    width = num_heads * (head_dim or d // num_heads)
    shapes = {"embed_weight": (vocab_size, d), "final_ln_gamma": (d,),
              "lm_head_weight": (vocab_size, d)}
    for i in range(num_layers):
        n = "layer%d_" % i
        shapes.update({
            n + "ln1_gamma": (d,), n + "qkv_weight": (3 * width, d),
            n + "qnorm_gamma": (width,), n + "knorm_gamma": (width,),
            n + "proj_weight": (d, width), n + "ln2_gamma": (d,),
            n + "router_weight": (num_experts, d),
            n + "experts_gate_weight": (num_experts, d, ffn_dim),
            n + "experts_up_weight": (num_experts, d, ffn_dim),
            n + "experts_down_weight": (num_experts, ffn_dim, d)})
    return shapes


def draft_config(cfg, num_layers=1):
    """Speculative-decoding draft config: the FIRST ``num_layers`` blocks
    of a target model's config. Weight names are positional
    (``layer0..layer{k-1}`` plus the shared ``embed``/``pos_embed``/
    ``final_ln``/``lm_head``), so a target checkpoint's arg_params dict
    feeds a draft decoder unchanged — the draft simply stops looking up
    the deeper layers. docs/SERVING.md §speculative decoding."""
    k = int(num_layers)
    if not 0 < k <= int(cfg.get("num_layers", k)):
        raise ValueError(
            "draft_config: draft num_layers %d not in (0, %d]"
            % (k, int(cfg.get("num_layers", k))))
    out = dict(cfg)
    out["num_layers"] = k
    return out
