"""The decode steps' share of the chip's HBM peak by the bytes the ALGORITHM
needs, for a model of window and full layers whose QUERY-head counts differ
over the same key/value heads and whose expert layers hold a share of their
experts beside a shared one (``arch="laguna"``): in every step the weights
outside the routed experts once (attention of both kinds with its gates, the
dense MLP, the routers, the shared experts, the head's slice), three matrices
for every HELD expert that received at least one row
(``serving.moe.step_experts_touched``), a full layer's key and value rows
read for every token of a stepped lane's own context
(``serving.step_context_tokens``) and a window layer's for every live slot of
its ring (``serving.step_window_slots``: at most 512 a lane and step), both
written for every stepped lane (``serving.decode_tokens``). The function that
counts them, ``step_bytes``, lives with the cell's driver
(``drivers/paged_closed_loop_laguna.py``), by the layer equations of
``reference/laguna_decoder.py``, as ``kernels.hbm_share.swa`` reads mimo's.

Over ALL the seconds the device was busy in the traced window, admissions'
included: they add busy time and no bytes here, and they are about HALF of
this cell's busy time (an 8,192-token admission a request of a few hundred
output tokens), so the share reads about half of what the steps alone would.
A program without the counters (the parent commit has no window to count), or
a configuration of another architecture, gives nothing."""
from harness.spec import load_module


def read(run):
    t, c = run.trace_summary, run.counters_window or {}
    model = run.config.get("model", {})
    steps = c.get("serving.paged_steps")
    if run.peaks is None or not t or not steps \
            or "serving.step_context_tokens" not in c \
            or "serving.step_window_slots" not in c \
            or "serving.moe.step_experts_touched" not in c \
            or model.get("arch") != "laguna":
        return None
    moved = load_module("drivers", "paged_closed_loop_laguna").step_bytes(
        model, run.config["dtype"], steps, c.get("serving.decode_tokens", 0),
        c["serving.step_context_tokens"], c["serving.step_window_slots"],
        c["serving.moe.step_experts_touched"])
    return 100.0 * moved / (t["busy_s"] * run.peaks["hbm_bytes_per_s"])
