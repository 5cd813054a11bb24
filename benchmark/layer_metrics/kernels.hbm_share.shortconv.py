"""The decode steps' share of the chip's HBM peak by the bytes the ALGORITHM
needs, for a model of gated short convolutions, grouped-query attention in
some layers and sparse experts (``arch="lfm2_moe"``): in every step the
weights outside the routed experts, the tied table among them, once (the head
reads it; the embedding's lookup beside it is not counted again), three
matrices for every expert that received at least one row (the program counts
them, ``serving.moe.step_experts_touched``: an expert no lane chose is not
read), an attention layer's key and value rows read for every token of a
stepped lane's own context (``serving.step_context_tokens``: position + 1 a
lane and step) and written for every stepped lane (``serving.decode_tokens``),
and every stepped lane's convolution rows, float32, read and written back.
The sizes come from the configuration's ``model``, by the layer equations
(``reference/lfm2_moe_decoder.py``), and live here.

Over ALL the seconds the device was busy in the traced window, admissions'
included (they add busy time and no bytes here), as in
``kernels.hbm_share.mla``. A step of 64 lanes does 2 x 64 x 2.3 G FLOP over
about 10 GB: 30 FLOP a byte against the chip's 240, so HBM is this step's
roofline. A program without the counters, or a configuration of another
architecture, gives nothing."""

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def sizes(model):
    """(parameters a step reads whatever the router does, parameters of one
    routed expert, key and value numbers a token over all attention layers,
    convolution-row numbers a lane over all conv layers) of an ``lfm2_moe``
    model."""
    d, kinds = model["model_dim"], model["layer_types"]
    q, kv = (model[k] * model["head_dim"]
             for k in ("num_heads", "num_kv_heads"))
    n_conv, n_att = kinds.count("conv"), kinds.count("full_attention")
    n_dense = model["first_dense_layers"]
    # [B | C | u], the taps, the output
    conv = 3 * d * d + model["conv_kernel"] * d + d * d
    # q, k, v, the output, the two head norms
    attention = (q + 2 * kv) * d + q * d + 2 * model["head_dim"]
    always = n_conv * conv + n_att * attention + len(kinds) * 2 * d \
        + n_dense * 3 * d * model["ffn_dim"] \
        + (len(kinds) - n_dense) * model["num_experts"] * (d + 1) \
        + d + model["vocab_size"] * d
    return always, 3 * d * model["moe_ffn_dim"], n_att * 2 * kv, \
        n_conv * (model["conv_kernel"] - 1) * d


def step_bytes(model, dtype, steps, tokens, context_tokens, experts_touched):
    """Bytes ``steps`` decode steps need that stepped ``tokens`` lanes in all
    over ``context_tokens`` tokens of context and touched
    ``experts_touched`` experts (summed over layers and steps): weights and
    pools in ``dtype``, the convolution rows float32, in and out."""
    always, expert, kv, rows = sizes(model)
    return _BYTES[dtype] * (steps * always + experts_touched * expert
                            + (context_tokens + tokens) * kv) \
        + 4 * 2 * tokens * rows


def read(run):
    t, c = run.trace_summary, run.counters_window or {}
    model = run.config.get("model", {})
    steps = c.get("serving.paged_steps")
    if run.peaks is None or not t or not steps \
            or "serving.step_context_tokens" not in c \
            or "serving.moe.step_experts_touched" not in c \
            or model.get("arch") != "lfm2_moe":
        return None
    moved = step_bytes(model, run.config["dtype"], steps,
                       c.get("serving.decode_tokens", 0),
                       c["serving.step_context_tokens"],
                       c["serving.moe.step_experts_touched"])
    return 100.0 * moved / (t["busy_s"] * run.peaks["hbm_bytes_per_s"])
