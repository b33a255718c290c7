"""The channel-decay delta rule's backward walk, from the last chunk to the
first, every cotangent of a chunk made in VMEM
(``ops/pallas_delta_rule``, kernel ``delta_channel_bwd``).

Operands q, k, v, g, ``beta``'s columns, the states every chunk starts from
``[B, H / 2, n, 2, dv, dk]`` (whose last two widths are the heads') and
``dO``; results ``dq``, ``dk``, ``dv``, ``dg`` and ``beta``'s cotangent by
column.  Counted, a head and chunk (C = 64), what every chunked backward
that keeps the operands and the states alone contracts: the system again
(the six off-diagonal tiles of ``K K^T`` and of ``Q K^T``: 2 x 3/8 C^2 dk
each; ``W``: 2 C^2 dk; ``U``: 2 C^2 dv) and ``V' = U - W S`` (2 C dk dv);
then ``P^T dO``, ``T^T dU``, ``dU U^T`` and ``dO V'^T`` (2 C^2 dv each),
``T^T dW`` and ``dW W^T`` (2 C^2 dk each), both score matrices' cotangents
back through the split (for ``kk`` and for ``P``, the six tiles' ``dM
right`` and ``dM^T (x to_edge)``: 4 x 3/8 C^2 dk in all, times 2), and
against the state or its cotangent ``(k left) dS``, ``(q gamma)^T dO``,
``W^T dV'``, ``dV' S^T``, ``dO S^T`` and ``V' dS^T`` (2 C dk dv each).  Left
out as in ``delta_channel_fwd``: the diagonal tiles and their cotangents
(element by element: most of this kernel's time), how the inverse is made,
the running sums, the extra passes of the highest precision, the
exponentials.  So the count is never more than the kernel does and a
reading over 100% is a fault.
"""

KERNEL = "delta_channel_bwd"
CHUNK = 64
SPLIT = 6 * 16 * 16 / (CHUNK * CHUNK)
#: (multiples of C^2 dk, of C^2 dv, of C dk dv) a head and chunk
TERMS = (2 * SPLIT + 1 + 2 + 4 * SPLIT, 5, 7)
_STATES = 5


def flops(operands, results):
    (b, pairs, n, two, dv, dk), _ = operands[_STATES]
    return 2.0 * b * pairs * n * two * (
        CHUNK * CHUNK * (TERMS[0] * dk + TERMS[1] * dv)
        + TERMS[2] * CHUNK * dk * dv)
