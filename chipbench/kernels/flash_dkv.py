"""Pallas flash attention backward, dK and dV (``_dkv_kernel``).

Operands q, k, v, dO, lse, delta (+ key bias).  Four contractions: scores
again, dP, dV = P^T dO, dK = dS^T q.  Causal guess as in ``flash_fwd``.
"""

KERNEL = "_dkv_kernel"
_MATMULS, _PLAIN_OPERANDS = 4, 6


def flops(operands, results):
    (bh, tq, d), _ = operands[0]
    tk = operands[1][0][1]
    full = 2.0 * _MATMULS * bh * tq * tk * d
    return full / 2 if len(operands) == _PLAIN_OPERANDS and tq == tk else full
