"""Seconds in XLA compile requests during set-up (``jax.monitoring``); with
a warm persistent cache these are the seconds it takes to load programs. The
requests, hits and misses are in the line's ``compiles``."""


def read(run):
    return run.compiles_setup["seconds"]
