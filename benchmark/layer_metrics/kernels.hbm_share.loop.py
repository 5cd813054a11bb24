"""The decode steps' share of the chip's HBM peak by the bytes the ALGORITHM
needs, for a looped stack (``arch="ouro"``): ONE set of layers applied
``total_ut_steps`` times, every pass with keys and values of its own. In
every step every layer weight once a PASS (the layers' 4.9 GB do not stay on
the chip between passes: four reads), the final norm, the exit gate and the
head once; a stepped lane (``serving.decode_tokens``) reads its embedding row
and writes a key row and a value row in every layer of every pass (192 x 2
rows of 4,096 B at the published widths: 1.5 MiB); every token of a stepped
lane's own context (``serving.step_context_tokens``: position + 1 a lane and
step) has as many rows read. NOT counted: activations, logits, the page table
and whatever the program moves beyond the need (a page read whole for one
row). The function that counts them, ``step_bytes``, lives with the cell's
driver (``drivers/paged_closed_loop_ouro.py``), by the layer equations of
``reference/ouro_decoder.py``.

It is the step's share of its roofline whatever implements the read, over
ALL the seconds the device was busy in the traced window, admissions'
included (they add busy time and no bytes here), as in
``kernels.hbm_share.yoco``: so it stays under 100% by more than the
admissions' share of the busy time. A step of 16 lanes does 16 x 4 x 4.9 G
FLOP over 20 to 25 GB: some 13 FLOP a byte against the chip's 240, so HBM is
this step's roofline. A program without the counters (the parent commit has
no such arch), or a configuration of another architecture, gives nothing."""
from harness.spec import load_module


def read(run):
    t, c = run.trace_summary, run.counters_window or {}
    model = run.config.get("model", {})
    steps = c.get("serving.paged_steps")
    if run.peaks is None or not t or not steps \
            or "serving.step_context_tokens" not in c \
            or "serving.loop.passes" not in c \
            or model.get("arch") != "ouro":
        return None
    moved = load_module("drivers", "paged_closed_loop_ouro").step_bytes(
        model, run.config["dtype"], steps, c.get("serving.decode_tokens", 0),
        c["serving.step_context_tokens"])
    return 100.0 * moved / (t["busy_s"] * run.peaks["hbm_bytes_per_s"])
