"""Bytes XLA counts for the compiled step (``cost_analysis()["bytes
accessed"]``: the compiler's count for its own program, not the least the
algorithm needs) times the steps of the traced window, over the seconds the
device was busy, over the chip's HBM peak."""


def read(run):
    t = run.trace_summary
    if run.peaks is None or not t or \
            "xla_bytes_per_step_per_chip" not in run.obs:
        return None
    moved = run.obs["xla_bytes_per_step_per_chip"] * run.obs["steps"]
    return 100.0 * moved / (t["busy_s"] * run.peaks["hbm_bytes_per_s"])
