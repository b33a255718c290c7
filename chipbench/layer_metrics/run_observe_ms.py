"""Median self time of ``fluid.run.observe`` over the ``fluid.run`` roots that
begin inside the unprofiled window: the always-on tail: counters, watchdog, `note_scope_live`, goodput, guardian `defer`.
Read from the program's ring (``chipbench/program_spans.py``)."""

from chipbench import program_spans


def value(run):
    return program_spans.run_child_ms(run, "observe")
