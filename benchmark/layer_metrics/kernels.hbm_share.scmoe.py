"""The decode steps' share of the chip's HBM peak by the bytes the ALGORITHM
needs, for a model of two latent attentions, two dense MLPs and a
shortcut-connected expert layer a layer that holds a share of its experts
(``arch="longcat_flash"``): in every step the weights outside the routed
experts once (both attentions, both MLPs, the router, the head's slice),
three matrices for every HELD expert that received at least one row
(``serving.moe.step_experts_touched``: the routing's count, not the
implementation's, so a program that reads all sixteen experts a layer reads
LOWER here), and a latent row a sublayer read for every token of a stepped
lane's own context (``serving.step_context_tokens``) and written for every
stepped lane (``serving.decode_tokens``). NOT counted: activations, logits,
the page table and whatever the program moves beyond the need (a pool
re-laid out before its read). The function that counts them, ``step_bytes``,
lives with the cell's driver (``drivers/paged_closed_loop_longcat.py``), by
the layer equations of ``reference/longcat_flash_decoder.py``.

Over ALL the seconds the device was busy in the traced window, admissions'
included (they add busy time and no bytes here), as in
``kernels.hbm_share.ssm_moe`` and ``.yoco``: a share of the window's device
time, from the device's own trace. Admissions are more than half of this
cell's busy time, so the share reads well under what the decode program
alone reaches and stays under 100% by more than their part; a change that
shortens an admission raises it as one that shortens a step does. The
harness's trace summary keeps the device's time by instruction and not by
program, so the decode program's own time is not to be had here. A program
without the counters (the parent commit has no such arch), or a
configuration of another architecture, gives nothing."""
from harness.spec import load_module


def read(run):
    t, c = run.trace_summary, run.counters_window or {}
    model = run.config.get("model", {})
    steps = c.get("serving.paged_steps")
    if run.peaks is None or not t or not steps \
            or "serving.step_context_tokens" not in c \
            or "serving.moe.step_experts_touched" not in c \
            or model.get("arch") != "longcat_flash":
        return None
    moved = load_module("drivers", "paged_closed_loop_longcat").step_bytes(
        model, run.config["dtype"], steps, c.get("serving.decode_tokens", 0),
        c["serving.step_context_tokens"],
        c["serving.moe.step_experts_touched"])
    return 100.0 * moved / (t["busy_s"] * run.peaks["hbm_bytes_per_s"])
