"""The decode steps' share of the chip's HBM peak by the bytes the ALGORITHM
needs, for a model whose blocks are ONE mixer each, Mamba-2 rows beside
attention pools beside routed experts (``arch="nemotron_h"``): in every step
every weight outside the routed experts once (the shared experts, the
routers, the Mamba and attention matrices, the head; of the embedding a row a
stepped lane), ONE expert's two matrices AT THE PUBLISHED WIDTH for every
held expert that received a row (``serving.moe.step_experts_touched``; the
zero padding the stacks are stored with is moved and not counted: it reads as
lost share), every Mamba block's float32 state and convolution columns read
and written for every stepped lane (``serving.decode_tokens``), and the
attention blocks' key and value rows read for every token of a stepped lane's
own context (``serving.step_context_tokens``: position + 1 a lane and step)
and written once a stepped lane. NOT counted: activations, logits, the page
table and whatever the program moves beyond the need. The function that
counts them, ``step_bytes``, lives with the cell's driver
(``drivers/paged_closed_loop_nemotron_h.py``), by the layer equations of
``reference/nemotron_h_decoder.py``.

Over ALL the seconds the device was busy in the traced window, admissions'
included (they add busy time and no bytes here), as in
``kernels.hbm_share.yoco``: a share of the window's device time, so it stays
under 100% by more than the admissions' share of it. A step of 64 lanes does
about 90 GFLOP over 9 GB: 10 FLOP a byte against the chip's 240, so HBM is
this step's roofline, and this is the share of the whole step that bounds a
later claim here. A program without the counters (the parent commit has no
such arch), or a configuration of another architecture, gives nothing."""
from harness.spec import load_module


def read(run):
    t, c = run.trace_summary, run.counters_window or {}
    model = run.config.get("model", {})
    steps = c.get("serving.paged_steps")
    if run.peaks is None or not t or not steps \
            or "serving.step_context_tokens" not in c \
            or "serving.moe.step_experts_touched" not in c \
            or model.get("arch") != "nemotron_h":
        return None
    moved = load_module("drivers", "paged_closed_loop_nemotron_h").step_bytes(
        model, run.config["dtype"], steps, c.get("serving.decode_tokens", 0),
        c["serving.step_context_tokens"],
        c["serving.moe.step_experts_touched"])
    return 100.0 * moved / (t["busy_s"] * run.peaks["hbm_bytes_per_s"])
