"""Attention under the block rule of diffusion over blocks, forward and
backward: the share of the device's busy time inside its three Pallas
families (``kernel:blockdiff_flash_fwd`` / ``_dq`` / ``_dkv``).  The XLA
glue of those calls (the backward's delta, the layout changes) is under
``op:sparse_attention*`` and is counted by ``sparse_attention_time_pct``.
None where the step calls no such kernel."""

from chipbench import op_time


def value(run):
    s = op_time.share(run, ("kernel:blockdiff_flash_",))
    return None if s is None else 100.0 * s
