"""The decode steps' share of the chip's HBM peak by the bytes the ALGORITHM
needs, for a model of window and full attention side by side whose expert
layers hold a share of their experts (``arch="mimo_v2_flash"``): in every step
the weights outside the routed experts once (attention of both kinds, the
sinks, the dense MLP, the routers, the head's slice), three matrices for every
HELD expert that received at least one row (the program counts them,
``serving.moe.step_experts_touched``: an expert no lane chose is not read, an
expert held elsewhere is nobody's here), a full layer's key and value rows
read for every token of a stepped lane's own context
(``serving.step_context_tokens``: position + 1 a lane and step) and a window
layer's for every live slot of its ring (``serving.step_window_slots``: at
most 128 a lane and step), both written for every stepped lane
(``serving.decode_tokens``). The function that counts them,
``step_bytes``, lives with the cell's driver
(``drivers/paged_closed_loop_mimo.py``), by the layer equations of
``reference/mimo_v2_flash_decoder.py``.

Over ALL the seconds the device was busy in the traced window, admissions'
included (they add busy time and no bytes here), as in
``kernels.hbm_share.mla`` and ``.shortconv``. A step of 32 lanes does about
2 x 32 x 1.2 G FLOP over 4 to 5 GB: 17 FLOP a byte against the chip's 240, so
HBM is this step's roofline. A program without the counters (the parent
commit has no window to count), or a configuration of another architecture,
gives nothing."""
from harness.spec import load_module


def read(run):
    t, c = run.trace_summary, run.counters_window or {}
    model = run.config.get("model", {})
    steps = c.get("serving.paged_steps")
    if run.peaks is None or not t or not steps \
            or "serving.step_context_tokens" not in c \
            or "serving.step_window_slots" not in c \
            or "serving.moe.step_experts_touched" not in c \
            or model.get("arch") != "mimo_v2_flash":
        return None
    moved = load_module("drivers", "paged_closed_loop_mimo").step_bytes(
        model, run.config["dtype"], steps, c.get("serving.decode_tokens", 0),
        c["serving.step_context_tokens"], c["serving.step_window_slots"],
        c["serving.moe.step_experts_touched"])
    return 100.0 * moved / (t["busy_s"] * run.peaks["hbm_bytes_per_s"])
