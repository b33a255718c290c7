"""What a delta mixer's decay and output gate cost where they come through
a rank (a pair of products each, hidden -> rank -> heads x width), forward,
backward and update: both pairs, the decay's bias, softplus and head
factor, the gate's sigmoid: share of the device's busy time under
``layer<i>.mixer.delta.gates`` (``chipbench/scope_time.py``; a part of
``delta_mixer_time_pct``).  None where nothing carries such a path: a model
whose delta mixers take their gates from the projection in, one without
delta mixers, or the parent of the PR that added the scope."""

from chipbench import scope_time


def value(run):
    return scope_time.pct(scope_time.share(
        run, ("layer*.mixer.delta.gates",)))
