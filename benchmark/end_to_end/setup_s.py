"""Process start to the window's opening: import, ``jax.devices()``, weights,
compilation or cache load, warm-up, and the correctness check."""


def read(run):
    return run.setup_s
