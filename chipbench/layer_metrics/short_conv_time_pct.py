"""The gated short convolution, forward and backward: the share of the
device's busy time under the op type ``short_conv`` (``op:short_conv``,
``op:short_conv_grad``): both gates and the causal filter, every part of a
conv layer's mixer that is not one of its two plain products, which are
under ``op:mul*`` with every other projection.  None where the step has no
such op."""

from chipbench import op_time


def value(run):
    s = op_time.share(run, ("op:short_conv",))
    return None if s is None else 100.0 * s
