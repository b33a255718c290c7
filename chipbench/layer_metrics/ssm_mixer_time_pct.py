"""What a state-space mixer has that is not one of its two plain products,
forward, backward and update: the causal filter with its bias and SiLU, the
step and the decay, the scan itself, the gate and the group norm: share of
the device's busy time under ``layer<i>.mixer.ssm``
(``chipbench/scope_time.py``).  None where nothing carries such a path: a
model without such mixers, or the parent of the PR that added them."""

from chipbench import scope_time


def value(run):
    return scope_time.pct(scope_time.share(run, ("layer*.mixer.ssm",)))
