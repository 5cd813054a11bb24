"""What the result line must look like. The harness checks its own line
against this before printing it, and the tests check rehearsals with it."""
import math

KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACE_DEVICE_KEYS = ("busy_s", "window_s")


def _number(x):
    return isinstance(x, (int, float)) and not isinstance(x, bool) \
        and math.isfinite(x)


def problems(line, declared, trace):
    """Why ``line`` (the result object) is not a valid result for a cell
    whose metrics of this kind of run are ``declared`` ({name: unit}); an
    empty list when it is. ``trace`` says which kind of run it was."""
    bad = []
    for key in KEYS:
        if key not in line:
            bad.append("missing key %r" % key)
    if bad:
        return bad
    if not isinstance(line["correct"], bool):
        bad.append("correct is not a boolean")
    for key in ("attempted", "failed"):
        if not isinstance(line[key], int) or line[key] < 0:
            bad.append("%s is not a count" % key)
    if not bad and line["failed"] > line["attempted"]:
        bad.append("more failed than attempted")
    metrics = line["metrics"]
    if not metrics:
        bad.append("no metric reported")
    for name, m in metrics.items():
        if name not in declared:
            bad.append("metric %r is not declared for this cell" % name)
        elif m.get("unit") != declared[name]:
            bad.append("metric %r has unit %r, declared %r"
                       % (name, m.get("unit"), declared[name]))
        if not _number(m.get("value")):
            bad.append("metric %r has no finite value" % name)
    if not trace:
        for name in declared:
            if name not in metrics:
                bad.append("end-to-end metric %r is missing" % name)
            elif metrics[name]["value"] == 0:
                bad.append("end-to-end metric %r is 0" % name)
    device = line["device"]
    for key in DEVICE_KEYS + (TRACE_DEVICE_KEYS if trace else ()):
        if key not in device:
            bad.append("device lacks %r" % key)
    if trace and not bad:
        if not device["busy_s"] > 0:
            bad.append("no operation ran on the device in the traced window")
        if not device["window_s"] >= device["busy_s"]:
            bad.append("busy_s exceeds window_s")
    if "breakdown" in line:
        if not trace:
            bad.append("breakdown outside a traced run")
        for key, rows in line["breakdown"].items():
            if key not in ("device_ops", "idle_gaps"):
                bad.append("unknown breakdown list %r" % key)
            elif len(rows) > 10 or not all(
                    len(r) == 2 and isinstance(r[0], str) and _number(r[1])
                    for r in rows):
                bad.append("breakdown.%s is not <= 10 [name, seconds] rows"
                           % key)
    return bad
