"""Grouped-query flash attention over a selection, forward
(``ops/pallas_sparse_flash``, kernel ``sparse_flash_fwd``).

Operands q, k, v (+ the selection); results the output and the log-sum-exp.
Two contractions (scores and values).
q is [b*hq, t, d], k and v [b*hkv, t, d].  What is counted is the
CAUSAL half, bhq * t * t * d / 2 multiply-accumulates a contraction: the
tiles these kernels walk, with the selection as a mask inside them.  The
selection needs fewer (min(t + 1, topk) keys a query: 44% of the causal
half at t = 8192, topk = 2048), which the shapes do not say.  So against the
selected pairs, the yardstick of the configuration's ``flops.py``, this
count is too HIGH by 2.3x there, and the family's share of its roofline
measures causal-tile work: a kernel that skips unselected tiles could read
over 100%, and has to bring a count of its own (the selection's width as
an operand or in its ``kernel_name``) with it.
"""

KERNEL = "sparse_flash_fwd"
_MATMULS = 2


def flops(operands, results):
    (bh, t, d), _ = operands[0]
    return 2.0 * _MATMULS * bh * t * t * d / 2
