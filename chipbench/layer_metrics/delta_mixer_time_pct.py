"""What a delta mixer has that is not one of its two plain products,
forward, backward and update: the causal filter and its SiLU, both gates,
the l2 norms and the rule itself, the gated head norm: share of the
device's busy time under ``layer<i>.mixer.delta``
(``chipbench/scope_time.py``).  None where nothing carries such a path: a
model without delta mixers, or the parent of the PR that added them."""

from chipbench import scope_time


def value(run):
    return scope_time.pct(scope_time.share(run, ("layer*.mixer.delta",)))
