"""Median host time from one ``serving.paged_step`` closing to the next one
opening, over consecutive steps with no admission between them: the CALLER's
share of a dispatch period (picking the tokens, retiring, the loop), during
which the device has no decode program. The part of
``serving.step_gap_ms_p50`` that is not the decoder's."""
from harness import stats
from harness.spec import load_module

step_pairs = load_module("layer_metrics", "serving.step_gap_ms_p50").step_pairs


def read(run):
    p50 = stats.median([second["open"] - first["close"]
                        for first, second in step_pairs(run.spans)])
    return None if p50 is None else 1e3 * p50
