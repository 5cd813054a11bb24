"""The decode steps' share of the chip's HBM peak by the bytes the ALGORITHM
needs, for a decoder-hybrid-decoder whose cross-decoder reads ONE layer's
keys and values (``arch="phi4flash"``): in every step every weight once (the
embedding's table as the tied head), the one pool's key and value rows read
for every token of a stepped lane's own context
(``serving.step_context_tokens``: position + 1 a lane and step) once for EACH
layer that reads the pool (layer 17 and the seven cross layers behind it:
the same pages walked eight times; what one walk feeding several layers'
queries would save is not credited, because each layer's query exists only
after the layer before it) and written once a stepped lane
(``serving.decode_tokens``), the eight window layers' rings read for every
live slot (``serving.step_window_slots``: at most 512 a lane and step) and
written for every stepped lane, and the nine Mamba-1 layers' float32 state
and convolution columns read and written for every stepped lane. NOT
counted: activations, the carried ``m``, logits, the page table and whatever
the program moves beyond the need (a ring rewritten whole by its write, a
page read whole for one row). The function that counts them, ``step_bytes``,
lives with the cell's driver (``drivers/paged_closed_loop_phi4flash.py``), by
the layer equations of ``reference/phi4_flash_decoder.py``.

Over ALL the seconds the device was busy in the traced window, admissions'
included (they add busy time and no bytes here), as in
``kernels.hbm_share.swa``: the share is of the window's busy time, so it
stays under 100% by more than the admissions' share of it. A step of 64
lanes does about 2 x 64 x 3.3 G FLOP over 12 to 14 GB: some 35 FLOP a byte
against the chip's 240, so HBM is this step's roofline. A program without
the counters (the parent commit has no such arch), or a configuration of
another architecture, gives nothing."""
from harness.spec import load_module


def read(run):
    t, c = run.trace_summary, run.counters_window or {}
    model = run.config.get("model", {})
    steps = c.get("serving.paged_steps")
    if run.peaks is None or not t or not steps \
            or "serving.step_context_tokens" not in c \
            or "serving.step_window_slots" not in c \
            or model.get("arch") != "phi4flash":
        return None
    moved = load_module("drivers", "paged_closed_loop_phi4flash").step_bytes(
        model, run.config["dtype"], steps, c.get("serving.decode_tokens", 0),
        c["serving.step_context_tokens"], c["serving.step_window_slots"])
    return 100.0 * moved / (t["busy_s"] * run.peaks["hbm_bytes_per_s"])
