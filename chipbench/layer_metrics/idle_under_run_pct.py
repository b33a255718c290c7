"""Device idle time in the gaps whose midpoint lies inside a ``fluid.run``
root, over the traced window, worst device: what the host side of ``run()``
costs the chip.  Read from the trace the run just wrote; the run also prints
``idle gaps by program span:`` (``chipbench/program_spans.py``)."""

from chipbench import program_spans


def value(run):
    return program_spans.idle_under_run(run)
