"""How uneven the router's load is: the busiest expert's rows over the even
share, averaged over layers and admissions with the rows as weights. The
program adds, at every admission and over its layers, the assignments it
made (``serving.moe.assignments``) and the largest count any one expert
received (``serving.moe.max_expert_assignments``); both are sums over the
same layers and admissions, so max x experts / assignments is that mean.
1.0 is perfectly even routing; the grouped matmul's longest group, which
bounds a layer's expert time on a device that walks groups in turn, grows
with it. A program without the counters reports nothing."""


def read(run):
    c = run.counters_window or {}
    total = c.get("serving.moe.assignments")
    if not total:
        return None
    return c.get("serving.moe.max_expert_assignments", 0) \
        * run.config["model"]["num_experts"] / total
