"""The scalar gated delta rule's backward walk, from the last chunk to the
first, every cotangent of a chunk made in VMEM
(``ops/pallas_delta_rule``, kernel ``delta_rule_bwd``).

Operands q, k, v, the gates' columns and rows, the states every chunk
starts from ``[B, Hv / 2, n, 2, dk, dv]`` (whose last two widths are the
heads') and ``dO``; results ``dq``, ``dk``, ``dv`` and the gates'
cotangents by column and by row.  Counted, a value head and chunk (C = 64),
what every chunked backward that keeps the operands and the states alone
contracts: the system again (``K K^T``, ``Q K^T``, ``W``: 2 C^2 dk each;
``U``: 2 C^2 dv) and ``V' = U - W S`` (2 C dk dv); then ``P^T dO``, ``T^T
dU``, ``dU U^T`` and ``dO V'^T`` (2 C^2 dv each), ``T^T dW``, ``dW W^T``,
``dP K``, ``dP^T Q`` and ``(X + X^T) K`` (2 C^2 dk each), and against the
state or its cotangent ``K dS``, ``Q^T (gamma dO)``, ``W^T dV'``, ``dV'
S^T``, ``(gamma dO) S^T`` and ``(e V') dS^T`` (2 C dk dv each).  Left out as
in ``delta_rule_fwd``: how the inverse is made, the extra passes of the
highest precision, the exponentials, the off-diagonal half of the pair's
``[128, 128]`` arrays.  So the count is never more than the kernel does and
a reading over 100% is a fault.
"""

KERNEL = "delta_rule_bwd"
CHUNK = 64
#: (multiples of C^2 dk, of C^2 dv, of C dk dv) a value head and chunk
TERMS = (8, 5, 7)
_STATES = 5


def flops(operands, results):
    (b, pairs, n, two, dk, dv), _ = operands[_STATES]
    return 2.0 * b * pairs * n * two * (
        CHUNK * CHUNK * (TERMS[0] * dk + TERMS[1] * dv)
        + TERMS[2] * CHUNK * dk * dv)
