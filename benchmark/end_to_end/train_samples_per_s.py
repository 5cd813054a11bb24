"""Global samples (the configuration's ``sample_unit``) completed in the
window over its length; the window starts and ends on ``block_until_ready``."""


def read(run):
    return run.obs["samples"] / run.obs["elapsed_s"]
