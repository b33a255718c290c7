"""Pallas streaming softmax cross-entropy, forward: one pass over the logits.
(``ops/pallas_fused._xent_partial_kernel``)

Elementwise: no contraction, so the least time is the bytes over the HBM
bandwidth.
"""

KERNEL = "_xent_partial_kernel"


def flops(operands, results):
    return 0.0
