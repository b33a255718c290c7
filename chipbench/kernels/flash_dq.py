"""Pallas flash attention backward, dQ (``ops/pallas_flash._dq_kernel``).

Operands q, k, v, dO, lse, delta (+ key bias).  Three contractions:
scores again, dP = dO v^T, dQ = dS k.  Causal guess as in ``flash_fwd``.
"""

KERNEL = "_dq_kernel"
_MATMULS, _PLAIN_OPERANDS = 3, 6


def flops(operands, results):
    (bh, tq, d), _ = operands[0]
    tk = operands[1][0][1]
    full = 2.0 * _MATMULS * bh * tq * tk * d
    return full / 2 if len(operands) == _PLAIN_OPERANDS and tq == tk else full
