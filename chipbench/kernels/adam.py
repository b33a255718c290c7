"""Pallas fused Adam sweep over one parameter: reads param, grad and both
moments, writes param and both moments.
(``ops/pallas_fused._adam_kernel``)

Elementwise: no contraction, so the least time is the bytes over the HBM
bandwidth.
"""

KERNEL = "_adam_kernel"


def flops(operands, results):
    return 0.0
