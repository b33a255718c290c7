"""The gated delta rule, forward and backward: the share of the device's
busy time under the op type ``gated_delta_rule`` (``op:gated_delta_rule``,
``op:gated_delta_rule_grad``): the chunk's decays, scores, inverse and its
two products, the walk over the chunks with the carried state and, in the
backward, all of that made again and walked backwards.  The filter in front
and the gated norm behind are other ops (``delta_mixer_time_pct`` holds
them too).  None where the step has no such op."""

from chipbench import op_time


def value(run):
    s = op_time.share(run, ("op:gated_delta_rule",))
    return None if s is None else 100.0 * s
