"""The model's FLOP in the traced window (counted from the graph, or for
decode from the lanes' own context, ``benchmark/flops.py``) per chip, over
the seconds the device was busy, over the chip's bf16 peak: how much of the
matrix unit the time on the device bought."""


def read(run):
    t = run.trace_summary
    if run.peaks is None or not t or "model_flops_in_window" not in run.obs:
        return None
    return 100.0 * run.obs["model_flops_in_window"] / run.chips / (
        t["busy_s"] * run.peaks["bf16_flops_per_s"])
