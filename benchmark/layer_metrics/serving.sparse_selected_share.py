"""How much of a lane's context a decode step's read KEEPS under a learned
selection: ``serving.sparse.step_selected_slots`` (the latent rows a step's
full layers read: min(position + 1, ``index_topk``) a stepped lane and layer)
over ``serving.sparse.step_scored_slots`` (the index keys they scored:
position + 1 a stepped lane and layer), both summed by the program over the
window's steps. 100 would mean no context passed ``index_topk`` and the cell
is too short to show the mechanism; contexts of 4-10 thousand under a
selection of 2,048 read about 30. A program without the counters (the parent
commit; an architecture with no selection) gives nothing."""


def read(run):
    c = run.counters_window or {}
    scored = c.get("serving.sparse.step_scored_slots")
    if not scored or "serving.sparse.step_selected_slots" not in c:
        return None
    return 100.0 * c["serving.sparse.step_selected_slots"] / scored
