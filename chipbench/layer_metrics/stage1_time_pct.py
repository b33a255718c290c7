"""ResNet's stage 1, forward, backward and update: the share of the device's
busy time under the name scopes ``stage1.block*``
(``chipbench/scope_time.py``).  None where no instruction carries the path."""

from chipbench import scope_time


def value(run):
    return scope_time.pct(scope_time.share(run, ("stage1",)))
