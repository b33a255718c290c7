"""The arithmetic of the end-to-end metrics, on completion stamps.

A stamp is the host clock (``time.perf_counter``) read right after a step's
loss was seen finished.  The window is the list of stamps from the one that
opens it to the first at or after ``--seconds``; ``len(stamps) - 1`` steps
completed inside it.
"""

from __future__ import annotations

from typing import Sequence


def throughput(stamps: Sequence[float], units_per_step: float) -> float:
    """Units per second over the WHOLE window: every step that completed in
    it over all of its time, stalls included."""
    if len(stamps) < 2 or stamps[-1] <= stamps[0]:
        raise ValueError("a window needs two stamps and positive length")
    return (len(stamps) - 1) * units_per_step / (stamps[-1] - stamps[0])


def intervals(stamps: Sequence[float]):
    return [b - a for a, b in zip(stamps, stamps[1:])]


def percentile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        raise ValueError("no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def step_ms_p95(stamps: Sequence[float]) -> float:
    return 1e3 * percentile(intervals(stamps), 95.0)


def step_ms_median(stamps: Sequence[float]) -> float:
    return 1e3 * percentile(intervals(stamps), 50.0)
