"""1 - (union of the device's operation intervals / traced window), averaged
over the cell's chips, from the ``.xplane.pb``."""


def read(run):
    t = run.trace_summary
    return None if not t else 100.0 * t["idle_share"]
