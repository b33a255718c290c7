"""The head's one product and its two gradients: the FLOPs they NEED (3 x the
forward count of ``chipbench/flops.py`` over the ops under ``head``) over
ALL the time booked under ``head`` (loss kernels and the update too), as a
share of the chip's bf16 peak (``chipbench/scope_time.py`` ``mfu``).  None
where nothing carries the path."""

from chipbench import scope_time


def value(run):
    return scope_time.pct(scope_time.mfu(run, ("head",)))
