"""Median over the window's steps of the sum over routed layers of
``ops.moe.live_rows`` over the sum of ``ops.moe.rows``: the share of the rows
the expert layer walks that is work (an assignment to an expert held here;
the rest ride along as zero rows, ``parallel/moe.py``).  Read from the
program's step gauges (``chipbench/step_gauges.py``)."""

from chipbench import step_gauges


def value(run):
    return step_gauges.live_rows_pct(run)
