"""Median self time of ``fluid.run.commit`` over the ``fluid.run`` roots that
begin inside the unprofiled window: writing the new state back to the scope and wrapping the fetches.
Read from the program's ring (``chipbench/program_spans.py``)."""

from chipbench import program_spans


def value(run):
    return program_spans.run_child_ms(run, "commit")
