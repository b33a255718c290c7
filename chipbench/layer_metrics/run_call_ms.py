"""Median self time of ``fluid.run.call`` over the ``fluid.run`` roots that
begin inside the unprofiled window: the jitted step's call: the enqueue (jax's own compile phases, on a first call, are not in it).
Read from the program's ring (``chipbench/program_spans.py``)."""

from chipbench import program_spans


def value(run):
    return program_spans.run_child_ms(run, "call")
