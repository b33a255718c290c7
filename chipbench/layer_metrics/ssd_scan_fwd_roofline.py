"""``ssd_scan_fwd`` (the selective state-space scan's forward walk, a few
chunks of a group of heads a grid step) against its roofline: the least time of
its events, each from its own shapes (the contractions every chunked form
does, ``chipbench/kernels/ssd_scan_fwd.py``; the exponentials, masks and
element-by-element tiles left out) and the bytes that cross HBM, over the
same events' durations (``trace_reduce.kernel_roofline``); left out where
the step calls no such kernel or the family's events do not equal its
calls."""

from chipbench import trace_reduce


def value(run):
    return trace_reduce.family_pct(run.get("roofline"), "ssd_scan_fwd")
