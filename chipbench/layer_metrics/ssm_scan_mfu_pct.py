"""The state-space scan against the chip's peak: the FLOPs the RECURRENCE
states (``scan_flops`` of the cell's ``configs/<name>/flops.py``: the state
decayed, a rank-one write and a read along C a token and head), x 3 for the
backward, x the steps traced, over the seconds the device booked under the
op type ``ssd_scan`` (forward and backward) and the chip's bf16 peak.
Needed work over ALL the op's time: the chunked form's own products and the
backward's second forward are in the time and not in the FLOPs, so it
cannot pass 100.  None where the step has no such op, or the cell's
configuration states no ``scan_flops``."""

import json
import os

from chipbench import plugins
from chipbench.peaks import peaks_for

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def needed(workload):
    """3 x ``scan_flops`` of the cell's configuration as run, a sample."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    entry = next((c for c in bench["configs"]
                  if cell and c["name"] == cell["config"]), None)
    if entry is None:
        return None
    rel = os.path.relpath(os.path.dirname(os.path.join(ROOT, entry["file"])),
                          os.path.join(ROOT, "chipbench"))
    own = plugins.load(rel, "flops")
    if own is None or not hasattr(own, "scan_flops"):
        return None
    with open(os.path.join(ROOT, entry["file"])) as f:
        return 3 * own.scan_flops(json.load(f))


def value(run):
    by_label = run.get("time_by_label")
    if not by_label:
        return None
    seconds = sum(v for k, v in by_label.items()
                  if k.startswith("op:ssd_scan"))
    flops = needed(run.get("workload")) if seconds else None
    if not flops:
        return None
    peak = peaks_for(run["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops * run["samples_per_step"] * run["steps_traced"] \
        / (seconds * run["chips"] * peak)
