"""The rows of the prompt bucket an admission's CROSS-decoder computed over
those its self-decoder did: ``serving.admit_cross_rows`` over
``serving.admit_self_rows``, both added by the program at every ``admit`` of
a model whose later layers read an earlier layer's pool
(``arch="phi4flash"``). An admission whose prefill runs the cross-decoder on
the prompt's last real row alone reads 1 / 2,048 = 0.049%; one that runs
every layer over the whole bucket reads 100%, and pays for it in
``ttft_ms_p50`` (by the matrices alone, 13.7 TFLOP where 7.7 suffice). A
program without the counters (the parent commit; every other architecture,
which has no cross-decoder) gives nothing."""


def read(run):
    c = run.counters_window or {}
    rows = c.get("serving.admit_self_rows")
    if not rows or "serving.admit_cross_rows" not in c:
        return None
    return 100.0 * c["serving.admit_cross_rows"] / rows
