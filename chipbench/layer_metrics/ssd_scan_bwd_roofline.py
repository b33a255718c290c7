"""``ssd_scan_bwd`` (the scan's backward walk, every cotangent of a
chunk made in VMEM) against its roofline: the least time of
its events, each from its own shapes (the contractions every chunked form
does, ``chipbench/kernels/ssd_scan_bwd.py``; the exponentials, masks and
element-by-element tiles left out) and the bytes that cross HBM, over the
same events' durations (``trace_reduce.kernel_roofline``); left out where
the step calls no such kernel or the family's events do not equal its
calls."""

from chipbench import trace_reduce


def value(run):
    return trace_reduce.family_pct(run.get("roofline"), "ssd_scan_bwd")
