"""Median self time of ``fluid.run.feed`` over the ``fluid.run`` roots that
begin inside the unprofiled window: the feed's coercion and placement (`_coerce_feed`, `_put_feed`; `np.asarray` and `step.place_feed` under ParallelExecutor).
Read from the program's ring (``chipbench/program_spans.py``)."""

from chipbench import program_spans


def value(run):
    return program_spans.run_child_ms(run, "feed")
