"""The decode steps' share of the chip's HBM peak by the bytes the ALGORITHM
needs, for a latent-attention model with sparse experts
(``arch="deepseek_v3"``): in every step the weights outside the routed
experts and the head once (the embedding is looked up, not read), three
matrices for every expert that received at least one row (the program counts
them, ``serving.moe.step_experts_touched``: an expert no lane chose is not
read), and a layer's latent row, ``kv_lora_rank + qk_rope_head_dim`` numbers,
read for every token of a stepped lane's own context
(``serving.step_context_tokens``: position + 1 a lane and step) and written
for every stepped lane (``serving.decode_tokens``). The sizes come from the
configuration's ``model``, by the layer equations
(``reference/deepseek_v3_decoder.py``), and live here.

Over ALL the seconds the device was busy in the traced window, admissions'
included (they add busy time and no bytes here), as in
``kernels.hbm_share.ssm``. At 32 heads the absorbed read does 2 x 32 x (2 x
512 + 64) FLOP over the 1,152 bytes of a latent row, 60 FLOP a byte against
the chip's 240: HBM is this step's roofline. The compiler's count
(``kernels.hbm_share.serving``) is not reported beside it: it counts the
whole pool every lane scores (S1), not a lane's own context. A program
without the counters, or a configuration without a latent, gives nothing."""

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def sizes(model):
    """(parameters a step reads whatever the router does, parameters of one
    routed expert, latent numbers a token and all layers) of a
    ``deepseek_v3`` model."""
    d, h, vocab = model["model_dim"], model["num_heads"], model["vocab_size"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    v_dim, lat = model["v_head_dim"], model["kv_lora_rank"]
    f, layers = model["moe_ffn_dim"], model["num_layers"]
    n_dense = model["first_dense_layers"]
    # projections q, [c | k_r], [k_nope | v] and the output; three norms
    attention = h * (nope + rope) * d + (lat + rope) * d \
        + h * (nope + v_dim) * lat + d * h * v_dim + 2 * d + lat
    dense = 3 * d * model["ffn_dim"]
    around_experts = model["num_experts"] * (d + 1) \
        + 3 * d * f * model["num_shared_experts"]
    always = layers * attention + n_dense * dense \
        + (layers - n_dense) * around_experts + d + vocab * d
    return always, 3 * d * f, layers * (lat + rope)


def step_bytes(model, dtype, steps, tokens, context_tokens, experts_touched):
    """Bytes ``steps`` decode steps need that stepped ``tokens`` lanes in all
    over ``context_tokens`` tokens of context and touched
    ``experts_touched`` experts (summed over layers and steps), all in
    ``dtype``."""
    always, expert, latent = sizes(model)
    return _BYTES[dtype] * (steps * always + experts_touched * expert
                            + (context_tokens + tokens) * latent)


def read(run):
    t, c = run.trace_summary, run.counters_window or {}
    model = run.config.get("model", {})
    steps = c.get("serving.paged_steps")
    if run.peaks is None or not t or not steps \
            or "serving.step_context_tokens" not in c \
            or "serving.moe.step_experts_touched" not in c \
            or "kv_lora_rank" not in model:
        return None
    moved = step_bytes(model, run.config["dtype"], steps,
                       c.get("serving.decode_tokens", 0),
                       c["serving.step_context_tokens"],
                       c["serving.moe.step_experts_touched"])
    return 100.0 * moved / (t["busy_s"] * run.peaks["hbm_bytes_per_s"])
