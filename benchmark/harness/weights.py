"""Random weights, made on the device from the seed in one jitted call.

A configuration's ``init`` is a list of rules ``{"match": regex, "kind": ...}``;
the first rule whose regex is found in a parameter's name decides it:
    ones | zeros
    normal      N(0, scale)
    he_normal   N(0, sqrt(2 / fan_in)), fan_in = product of all but the first
                dimension (He et al. 2015; what MXNet's
                Xavier(gaussian, in, 2) draws)
A name no rule matches is an error: nothing is initialised by accident.
"""
import math
import re


def _rule_for(name, rules):
    for rule in rules:
        if re.search(rule["match"], name):
            return rule
    raise ValueError("no init rule of the configuration matches parameter %r"
                     % name)


def make(shapes, rules, seed, dtype="float32", shardings=None):
    """{name: array} for ``shapes`` ({name: shape}), laid out by
    ``shardings`` ({name: Sharding}, or None for the default device). All
    random parameters are cut, in sorted name order, from ONE normal draw
    (one random operation to compile, not one per parameter), so one seed
    always gives the same weights."""
    import jax
    import jax.numpy as jnp

    names = sorted(shapes)
    plan, drawn = [], 0
    for name in names:
        shape, rule = tuple(shapes[name]), _rule_for(name, rules)
        kind = rule["kind"]
        if kind in ("ones", "zeros"):
            plan.append((name, shape, kind, None, None))
        elif kind in ("normal", "he_normal"):
            std = rule.get("scale", 1.0) if kind == "normal" else \
                math.sqrt(2.0 / max(1, math.prod(shape[1:])))
            plan.append((name, shape, "normal", std, drawn))
            drawn += math.prod(shape)
        else:
            raise ValueError("unknown init kind %r for %r" % (kind, name))

    def build(key):
        noise = jax.random.normal(key, (max(drawn, 1),), jnp.float32)
        out = {}
        for name, shape, kind, std, at in plan:
            if kind == "ones":
                out[name] = jnp.ones(shape, dtype)
            elif kind == "zeros":
                out[name] = jnp.zeros(shape, dtype)
            else:
                size = math.prod(shape)
                out[name] = (noise[at:at + size].reshape(shape)
                             * std).astype(dtype)
        return out

    kwargs = {}
    if shardings is not None:
        kwargs["out_shardings"] = {n: shardings[n] for n in names}
    return jax.jit(build, **kwargs)(jax.random.PRNGKey(int(seed)))
