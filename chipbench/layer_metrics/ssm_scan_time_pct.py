"""The selective state-space scan, forward and backward: the share of the
device's busy time under the op type ``ssd_scan`` (``op:ssd_scan``,
``op:ssd_scan_grad``): the chunks' decays, scores and writes, the walk over
the chunks with the carried state and, in the backward, all of that made
again and walked backwards.  The filter in front and the gated group norm
behind are other ops (``ssm_mixer_time_pct`` holds them too).  None where
the step has no such op."""

from chipbench import op_time


def value(run):
    s = op_time.share(run, ("op:ssd_scan",))
    return None if s is None else 100.0 * s
