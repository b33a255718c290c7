"""How many distinct ``scope`` labels have an ``ops.moe.rows`` step gauge in
the window: one a routed layer the program builds (the multi-token module's
block under ``mtp.ffn`` among them where it is routed).  A count, so a
rehearsal prints it too.  Read from the program's step gauges
(``chipbench/step_gauges.py``)."""

from chipbench import step_gauges


def value(run):
    return step_gauges.gauged_layers(run)
