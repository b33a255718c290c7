"""One op's share of the device's busy time, from the traced stretch.

``share(run, prefixes)`` = the time of the labels that start with one of
``prefixes`` (``op:<fluid op>``, ``kernel:<family>``; ``tracing.py`` gives
every device event one) over the busy time of the device that was
labelled.  The per-layer metric of a new op's share is then a file of three
lines under ``layer_metrics/``::

    def value(run):
        s = op_time.share(run, ("op:moe_dispatch", "kernel:grouped_mm"))
        return None if s is None else 100.0 * s

None where there is nothing to read: a run that was not traced, or a step
in which no label starts so.
"""

from __future__ import annotations


def share(run, prefixes):
    by_label = run.get("time_by_label")
    if not by_label:
        return None
    mine = [v for k, v in by_label.items() if k.startswith(tuple(prefixes))]
    if not mine:
        return None
    return sum(mine) / run["labelled_busy_s"]
