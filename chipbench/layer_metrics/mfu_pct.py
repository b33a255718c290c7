"""Model FLOP/s utilization: FLOPs the forward and backward NEED per sample
(``chipbench/flops.py``) times the samples per second of the UNPROFILED
window of the traced run, over chips times the bf16 peak."""

from chipbench import stats
from chipbench.peaks import peaks_for


def value(run):
    if "flops_per_sample" not in run:
        return None
    steps_per_s = stats.throughput(run["stamps"], 1.0)
    samples_per_s = steps_per_s * run["samples_per_step"]
    peak = peaks_for(run["device_kind"])["bf16_flops_per_s"]
    return 100.0 * run["flops_per_sample"] * samples_per_s \
        / (run["chips"] * peak)
