"""Share of the traced window that the lowest chip spent in collective
instructions while no other instruction ran there: communication that
nothing hid."""


def read(run):
    t = run.trace_summary
    return None if not t else \
        100.0 * t["collective_exposed_s"] / t["window_s"]
