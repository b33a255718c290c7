"""Pallas fused momentum sweep over one parameter: reads param, grad and
velocity, writes param and velocity.
(``ops/pallas_fused._momentum_kernel``)

Elementwise: no contraction, so the least time is the bytes over the HBM
bandwidth.
"""

KERNEL = "_momentum_kernel"


def flops(operands, results):
    return 0.0
