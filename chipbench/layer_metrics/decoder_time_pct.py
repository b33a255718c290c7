"""The Transformer's decoder stack
(``decoder.layer<i>.self_attention|cross_attention|ffn``), forward, backward
and update: share of the device's busy time under it
(``chipbench/scope_time.py``).  None where no instruction carries the path."""

from chipbench import scope_time


def value(run):
    return scope_time.pct(scope_time.share(run, ("decoder",)))
