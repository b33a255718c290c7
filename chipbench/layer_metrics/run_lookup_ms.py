"""Median self time of ``fluid.run.lookup`` over the ``fluid.run`` roots that
begin inside the unprofiled window: the cache key and the executor's cache lookup (its `fluid.run.build` child, on a miss, is not in it).
Read from the program's ring (``chipbench/program_spans.py``)."""

from chipbench import program_spans


def value(run):
    return program_spans.run_child_ms(run, "lookup")
