"""The decode steps' share of the chip's HBM peak by the bytes the ALGORITHM
needs, for a model that keeps a recurrent state beside its KV pages: in every
step the weights once, for every stepped lane its recurrent state read and
written once, and for every token of a stepped lane's own context its keys
and values read once. The program counts the steps (``serving.paged_steps``),
the stepped lanes (``serving.decode_tokens``) and their contexts
(``serving.step_context_tokens``: position + 1 a lane and step); the sizes
come from the configuration's ``model``, by the layer equations
(``reference/granite_hybrid_decoder.py``), and live here.

Over ALL the seconds the device was busy in the traced window, admissions'
included (they add busy time and no bytes here), as in
``kernels.hbm_share.serving``: a share of the window's device time, not of a
decode program's own. The compiler's count for the same program
(``kernels.hbm_share.serving``) is NOT reported beside it in a cell with a
recurrent state: XLA counts every state slice once as a prefetch copy and
again as the fusion's operand (33.3 GB a step against the 11.4 GB counted
here, 136% of the HBM peak on the chip: PERF.md section 6, PR 30), which is
a share of nothing. A program without the counters, or a configuration
without a recurrent state, gives nothing."""

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def sizes(model):
    """(parameters, float32 state elements a lane, K/V elements a context
    token) of a ``granite_hybrid`` model."""
    d, ffn, vocab = model["model_dim"], model["ffn_dim"], model["vocab_size"]
    h, p, n = model["mamba_heads"], model["mamba_head_dim"], \
        model["mamba_state"]
    k, inner = model["mamba_conv"], h * p
    conv_dim = inner + 2 * n
    mamba = (2 * inner + 2 * n + h) * d + conv_dim * (k + 1) + 3 * h \
        + inner + d * inner
    q_width = model["num_heads"] * model["head_dim"]
    kv_width = model["num_kv_heads"] * model["head_dim"]
    attention = (q_width + 2 * kv_width) * d + d * q_width
    kinds = model["layer_types"]
    n_mamba, n_att = kinds.count("mamba"), kinds.count("attention")
    params = vocab * d + d + len(kinds) * (2 * d + 3 * ffn * d) \
        + n_mamba * mamba + n_att * attention
    state = n_mamba * (h * p * n + (k - 1) * conv_dim)
    return params, state, n_att * 2 * kv_width


def step_bytes(model, dtype, steps, tokens, context_tokens):
    """Bytes ``steps`` decode steps need that stepped ``tokens`` lanes in all
    over ``context_tokens`` tokens of context: weights (the tied embedding
    once, as the head) in ``dtype`` a step, the float32 state in and out a
    stepped lane, the ``dtype`` keys and values a context token."""
    params, state, kv = sizes(model)
    width = _BYTES[dtype]
    return steps * params * width + tokens * 2 * 4 * state \
        + context_tokens * kv * width


def read(run):
    t, c = run.trace_summary, run.counters_window or {}
    model = run.config.get("model", {})
    steps = c.get("serving.paged_steps")
    if run.peaks is None or not t or not steps \
            or "serving.step_context_tokens" not in c \
            or "mamba" not in model.get("layer_types", ()):
        return None
    moved = step_bytes(model, run.config["dtype"], steps,
                       c.get("serving.decode_tokens", 0),
                       c["serving.step_context_tokens"])
    return 100.0 * moved / (t["busy_s"] * run.peaks["hbm_bytes_per_s"])
