"""The one place the harness touches the program under test: its telemetry
(spans, counters), which the traced run switches on and the readers read.
Everything else about the program is a driver's business."""


def telemetry():
    from mxnet_tpu import telemetry as tm

    return tm


def trace_on():
    """Spans and counters on (an API call, not an environment variable: the
    benchmark sets no MXNET_* variable). Only the traced run calls this."""
    telemetry().set_mode("trace")


def counters():
    return dict(telemetry().counters())


def clear_spans():
    telemetry().clear_events()


def spans():
    """[(name, start_s, duration_s, attrs)] recorded since ``clear_spans``,
    on the host's ``perf_counter`` clock."""
    return [(name, t0, dur, attrs)
            for name, t0, dur, _tid, attrs in telemetry().drain_events()]
