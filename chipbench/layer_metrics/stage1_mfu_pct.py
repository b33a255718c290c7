"""ResNet's stage 1: the FLOPs its convolutions NEED (3 x the forward count of
``chipbench/flops.py`` over the ops under ``stage1``) over the time the
device booked under that name, as a share of the chip's bf16 peak
(``chipbench/scope_time.py`` ``mfu``).  None where nothing carries the path."""

from chipbench import scope_time


def value(run):
    return scope_time.pct(scope_time.mfu(run, ("stage1",)))
