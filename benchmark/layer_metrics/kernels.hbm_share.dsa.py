"""The decode steps' share of the chip's HBM peak by the bytes the ALGORITHM
needs, for a model whose full layers read a LEARNED SELECTION of a latent
pool and whose window layers read a ring of latents (``arch="dots3_note"``):
in every step the weights outside the routed experts once (both kinds of
latent attention with their gates, the indexers, the dense MLP, the routers,
the shared experts, the head's slice), three matrices for every HELD expert
that received at least one row (``serving.moe.step_experts_touched``), an
index-key row for every token of a stepped lane's own context
(``serving.sparse.step_scored_slots``: position + 1 a lane, full layer and
step), a latent row for every SELECTED token only
(``serving.sparse.step_selected_slots``: at most ``index_topk`` a lane, full
layer and step), a ring row for every live slot of a window layer's ring
(``serving.step_window_slots``: at most 513 a lane and step), and the rows
written for every stepped lane (``serving.decode_tokens``). The function that
counts them, ``step_bytes``, lives with the cell's driver
(``drivers/paged_closed_loop_dots3.py``), by the layer equations of
``reference/dots3_note_decoder.py``, whatever implements the read: a read
that gathered a lane's whole table and masked it would move more and read a
smaller share here.

Over ALL the seconds the device was busy in the traced window, admissions'
included (they add busy time and no bytes here), as in
``kernels.hbm_share.swa`` and ``.mla``: at 8,192-token admissions most of the
busy time is theirs, so this is the share that bounds a later claim, not a
step's own roofline. A program without the counters (the parent commit has no
selection to count), or a configuration of another architecture, gives
nothing."""
from harness.spec import load_module

_NEEDS = ("serving.sparse.step_scored_slots",
          "serving.sparse.step_selected_slots", "serving.step_window_slots",
          "serving.moe.step_experts_touched")


def read(run):
    t, c = run.trace_summary, run.counters_window or {}
    model = run.config.get("model", {})
    steps = c.get("serving.paged_steps")
    if run.peaks is None or not t or not steps \
            or any(name not in c for name in _NEEDS) \
            or model.get("arch") != "dots3_note":
        return None
    moved = load_module("drivers", "paged_closed_loop_dots3").step_bytes(
        model, run.config["dtype"], steps, c.get("serving.decode_tokens", 0),
        *(c[name] for name in _NEEDS))
    return 100.0 * moved / (t["busy_s"] * run.peaks["hbm_bytes_per_s"])
