"""Every decoder layer's feed-forward (dense or shared, router and routed
experts, norms, residual add), forward, backward and update: share of the
device's busy time under ``layer<i>.ffn`` (``chipbench/scope_time.py``).
None where nothing carries the path."""

from chipbench import scope_time


def value(run):
    return scope_time.pct(scope_time.share(run, ("layer*.ffn",)))
