"""``grouped_matmul`` (the expert layer's grouped matrix product, plain and with the
weights contracted over their last axis)
against its roofline: the least time of its events, each from its own
shapes and the bytes that cross HBM, over the same events' durations
(``trace_reduce.kernel_roofline``); left out where the step calls no such
kernel or the family's events do not equal its calls."""

from chipbench import trace_reduce


def value(run):
    return trace_reduce.family_pct(run.get("roofline"), "grouped_matmul")
