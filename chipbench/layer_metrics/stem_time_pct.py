"""ResNet's stem (the 7x7 convolution, its norm, the pool), forward, backward
and update: the share of the device's busy time under the name scope ``stem``
(``chipbench/scope_time.py``).  None where no instruction carries the path."""

from chipbench import scope_time


def value(run):
    return scope_time.pct(scope_time.share(run, ("stem",)))
