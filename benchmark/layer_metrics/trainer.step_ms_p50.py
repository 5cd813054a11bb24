"""Median host-clock time of a group of steps that ends in
``block_until_ready``, divided by the group's size."""
from harness import stats


def read(run):
    return 1e3 * stats.median(run.obs["group_s"]) / run.obs["sync_every"]
