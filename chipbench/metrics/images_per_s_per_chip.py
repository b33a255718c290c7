"""Images trained per second per chip, over the whole window."""

from chipbench import stats


def value(run):
    return stats.throughput(run["stamps"], run["units_per_step"]) / run["chips"]
