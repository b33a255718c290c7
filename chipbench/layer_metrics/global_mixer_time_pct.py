"""A global attention layer that rotates by a table of its own
(``decoder_lm.Config.global_rotary``), forward, backward and update: its
norm, projections, head norms, both rotaries, the attention and the output
projection: share of the device's busy time under ``layer<i>.mixer.global``
(``chipbench/scope_time.py``).  Beside ``mixer_time_pct`` it splits the
mixers' time between the global layers and the window layers.  None where
nothing carries such a path: a model whose global layers share the window
layers' table, or the parent of the PR that added the record."""

from chipbench import scope_time


def value(run):
    return scope_time.pct(scope_time.share(run, ("layer*.mixer.global",)))
