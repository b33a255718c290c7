"""Median host time inside one un-synced ``run`` call, in the window."""

from chipbench import stats


def value(run):
    return 1e3 * stats.percentile(run["dispatch_s"], 50.0)
