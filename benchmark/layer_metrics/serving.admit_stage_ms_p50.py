"""Median duration of the program's ``serving.admit.stage`` span inside
``serving.paged_admit``: finding the prefill executable and writing the padded
prompt into its input.

This file also holds what the phase readers share (they load it as the
``.serving`` readers load theirs): a phase span counts only when a span of the
wanted name is among its ancestors, by the ``id`` / ``parent`` the program
puts into every span's attributes. The same phase name under another parent
(the ``serving.step.*`` of a chunked admission or a megastep) is another
layer's time and is left out. A program that records no such span (or no
``id``) gives nothing to read."""
from harness import stats


def durations(spans, name, under):
    """Seconds of each span called ``name`` with an ancestor called
    ``under``, from ``run.spans`` rows (name, start_s, duration_s, attrs)."""
    by_id = {attrs["id"]: (n, attrs.get("parent"))
             for n, _t0, _dur, attrs in spans if "id" in attrs}
    out = []
    for n, _t0, dur, attrs in spans:
        if n != name:
            continue
        parent = attrs.get("parent")
        while parent in by_id:
            parent_name, parent = by_id[parent]
            if parent_name == under:
                out.append(dur)
                break
    return out


def p50_ms(run, name, under):
    p50 = stats.median(durations(run.spans, name, under))
    return None if p50 is None else 1e3 * p50


def read(run):
    return p50_ms(run, "serving.admit.stage", "serving.paged_admit")
