"""Median self time of ``fluid.run.state`` over the ``fluid.run`` roots that
begin inside the unprofiled window: the step boundary, the guardian boundary and gathering the state from the scope (`_gather_state`; `place_state` under ParallelExecutor).
Read from the program's ring (``chipbench/program_spans.py``)."""

from chipbench import program_spans


def value(run):
    return program_spans.run_child_ms(run, "state")
