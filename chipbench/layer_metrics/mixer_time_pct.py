"""Every decoder layer's token mixer (attention of either kind with its
indexer, or the short convolution; projections, norms, gate, residual add),
forward, backward and update: share of the device's busy time under
``layer<i>.mixer`` (``chipbench/scope_time.py``).  None where nothing
carries the path."""

from chipbench import scope_time


def value(run):
    return scope_time.pct(scope_time.share(run, ("layer*.mixer",)))
