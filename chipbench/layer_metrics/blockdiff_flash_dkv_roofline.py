"""``blockdiff_flash_dkv`` (grouped-query flash attention under the block rule
of diffusion over blocks, backward, dK and dV) against its roofline: the least time
of its events, each from its own shapes and the bytes that cross HBM, over
the same events' durations (``trace_reduce.kernel_roofline``); left out
where the step calls no such kernel or the family's events do not equal its
calls."""

from chipbench import trace_reduce


def value(run):
    return trace_reduce.family_pct(run.get("roofline"), "blockdiff_flash_dkv")
