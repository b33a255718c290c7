"""The Transformer's encoder stack (``encoder.layer<i>.attention|ffn``),
forward, backward and update: share of the device's busy time under it
(``chipbench/scope_time.py``).  None where no instruction carries the path."""

from chipbench import scope_time


def value(run):
    return scope_time.pct(scope_time.share(run, ("encoder",)))
