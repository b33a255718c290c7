"""Grouped matrix product, rows sorted by group times each group's weights
(``ops/pallas_grouped``, kernel ``grouped_matmul``).

Operands: three scalar-prefetch tables (the groups' first rows ``[G + 1]``,
and for each grid step its row tile and its group, ``[tiles + G - 1]``
int32 each), then the rows ``[M, K]`` and the weights ``[G, K, N]``; result
``[M, N]``.  The same kernel with the weights contracted over their last
axis takes rows ``[M, N]`` and gives ``[M, K]`` (the rows' cotangent).  One
contraction either way, and every row meets ONE group's weights: 2 * M * K
* N, with K and N the weights' own two widths.  That is the needed work
where the group sizes sum to M, as the expert layer's do; rows past the
last group are written as zeros and multiplied by nothing, so the count is
never less than what the kernel does and a reading over 100% is a fault.
"""

KERNEL = "grouped_matmul"
_SCALAR_PREFETCH = 3


def flops(operands, results):
    (m, _), _ = operands[_SCALAR_PREFETCH]
    (_, k, n), _ = operands[_SCALAR_PREFETCH + 1]
    return 2.0 * m * k * n
