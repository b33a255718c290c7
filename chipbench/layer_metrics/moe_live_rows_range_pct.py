"""Largest less smallest share of walked rows that is work (see
``moe_live_rows_pct``) over the window's steps: how far a step time that
followed the router would wander inside one window.  Read from the program's
step gauges (``chipbench/step_gauges.py``)."""

from chipbench import step_gauges


def value(run):
    return step_gauges.live_rows_range_pct(run)
