"""95th percentile of the interval between consecutive step completions."""

from chipbench import stats


def value(run):
    return stats.step_ms_p95(run["stamps"])
