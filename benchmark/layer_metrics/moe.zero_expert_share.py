"""The share of the window's expert assignments that went to a ZERO-COMPUTE
expert (a router output past the experts with weights, the identity: such an
assignment adds ``weight * x`` and multiplies nothing):
``serving.moe.zero_assignments`` + ``serving.moe.step_zero_assignments`` over
``serving.moe.assignments`` + ``serving.moe.step_assignments``, all four
summed by the program over the expert layers, from the ``load`` its
admissions (every position of the bucket, padding included) and its steps
(every lane, those that ride along too) return. Even routing over 512 experts
and 256 zero-compute ones reads 33.3: four of a token's twelve. It says what
compute a token cost: the model's own lever on its FLOP a token. A program
without the counters (the parent commit has no such experts), or a model
without zero-compute experts, gives nothing."""


def read(run):
    c = run.counters_window or {}
    total = c.get("serving.moe.assignments", 0) \
        + c.get("serving.moe.step_assignments", 0)
    if not total or "serving.moe.zero_assignments" not in c \
            or not run.config.get("model", {}).get("num_zero_experts"):
        return None
    return 100.0 * (c["serving.moe.zero_assignments"]
                    + c.get("serving.moe.step_zero_assignments", 0)) / total
