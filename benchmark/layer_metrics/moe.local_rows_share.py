"""The share of a decode step's expert assignments that reached an expert
HELD here: ``serving.moe.step_local_assignments`` over
``serving.moe.step_assignments``, both summed by the program over the expert
layers and the window's steps (every lane of a step passes through the
experts, those that ride along too). An expert layer that holds 16 of the 256
experts it routes over reads 6.25% under even routing; the grouped matmul
keeps a row for every assignment, so the rest are rows it carries and does
not compute. A layer that holds every expert reads 100; a program without
the counter (the parent commit) gives nothing."""


def read(run):
    c = run.counters_window or {}
    total = c.get("serving.moe.step_assignments")
    if not total or "serving.moe.step_local_assignments" not in c:
        return None
    return 100.0 * c["serving.moe.step_local_assignments"] / total
