"""Pallas flash attention forward (``ops/pallas_flash._flash_kernel``).

Operands q, k, v as [b*h, t, d] (+ an optional key bias); results the
output and the log-sum-exp.  Two contractions of b*h*tq*tk*d
multiply-accumulates (scores and values).  A call with no bias operand and
tq == tk is taken to be causal self-attention, which needs half: a guess
that can only undercount the operations, never overcount them.
"""

KERNEL = "_flash_kernel"
_MATMULS, _PLAIN_OPERANDS = 2, 3


def flops(operands, results):
    (bh, tq, d), _ = operands[0]
    tk = operands[1][0][1]
    full = 2.0 * _MATMULS * bh * tq * tk * d
    return full / 2 if len(operands) == _PLAIN_OPERANDS and tq == tk else full
