"""The program's own count of executor compiles and retraces inside the
window (telemetry counters ``executor.compile`` + ``executor.retrace``)."""


def read(run):
    c = run.counters_window
    return c.get("executor.compile", 0) + c.get("executor.retrace", 0)
