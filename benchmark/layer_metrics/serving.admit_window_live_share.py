"""The share of the (query, key) pairs a window layer's admission SCORED that
a real position attends: ``serving.admit_window_pairs_live`` over
``serving.admit_window_pairs_scored``, both added by the program at every
``admit`` of a model with window layers, for one window layer, from shapes on
the host. The band form scores the bucket's T x 2 x block pairs (8,192 x
1,024 here) whatever the prompt's length; a real position attends at most the
window's 512: a prompt that fills the bucket reads 49%, the median prompt of
4,096 about 25%, one of 1,024 tokens 5%. It is what a window inside the
blockwise kernel (no pair above the band computed) or an admission in buckets
(no padding scored) would move, and with it ``ttft_ms_p50``. A program
without the counters (the parent commit), or a configuration of another
architecture, gives nothing."""


def read(run):
    c = run.counters_window or {}
    scored = c.get("serving.admit_window_pairs_scored")
    if not scored or "serving.admit_window_pairs_live" not in c \
            or run.config.get("model", {}).get("arch") != "laguna":
        return None
    return 100.0 * c["serving.admit_window_pairs_live"] / scored
