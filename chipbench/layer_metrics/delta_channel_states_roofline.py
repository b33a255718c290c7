"""``delta_channel_states`` (the channel-decay delta rule's walk made again for its backward,
a few chunks of a pair of heads a grid step) against its roofline: the
least time of its events, each from its own shapes (the contractions every
chunked implementation does, ``chipbench/kernels/delta_channel_states.py``;
the diagonal tiles and the inverse's construction left out) and the bytes
that cross HBM, over the same events' durations
(``trace_reduce.kernel_roofline``); left out where the step calls no such
kernel or the family's events do not equal its calls."""

from chipbench import trace_reduce


def value(run):
    return trace_reduce.family_pct(run.get("roofline"), "delta_channel_states")
