"""``kernels.hbm_share`` for the decode step: the bytes XLA counts for the
compiled decode program (``cost_analysis()["bytes accessed"]``, which the
program adds to its counter ``serving.decode_xla_bytes`` at every decode
dispatch) over the seconds the device was busy, over the chip's HBM peak.
The compiler's count for its own program, not the least the algorithm needs;
decode dispatches only (an admission's prefill and pool copies add busy time
and no bytes), over ALL busy time of the traced window."""


def read(run):
    t = run.trace_summary
    moved = (run.counters_window or {}).get("serving.decode_xla_bytes")
    if run.peaks is None or not t or not moved:
        return None
    return 100.0 * moved / (t["busy_s"] * run.peaks["hbm_bytes_per_s"])
