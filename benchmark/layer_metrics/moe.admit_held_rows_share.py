"""The share of an ADMISSION's expert assignments that reached an expert
HELD here: ``serving.moe.admit_local_assignments`` over
``serving.moe.assignments``, both summed by the program over the expert
layers and the window's admissions from the prefill's ``moe_load`` (every
position of the bucket, padding included). The admission-side sibling of
``moe.local_rows_share``: a layer that holds a share of its experts moves
these rows and no others, a chunk of the even share at a time, so the number
says how full a chunk runs: 6.25% where 16 of 256 are held and 25% where 64
are, under even routing; a layer whose held rows outgrow a chunk runs a
second turn
(``serving.moe.admit_overflow_layers`` counts them). A layer that holds
every expert reads 100; a program without the counter (the parent commit)
gives nothing."""


def read(run):
    c = run.counters_window or {}
    total = c.get("serving.moe.assignments")
    if not total or "serving.moe.admit_local_assignments" not in c:
        return None
    return 100.0 * c["serving.moe.admit_local_assignments"] / total
