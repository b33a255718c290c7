"""The weights' gradient of a grouped matrix product
(``ops/pallas_grouped``, kernel ``grouped_matmul_t``).

Operands: the three scalar-prefetch tables of ``grouped_matmul`` (first
rows ``[G + 1]``; row tile and group of each grid step), then the rows
``[M, K]`` and their cotangent ``[M, N]``; result ``[G, K, N]``, group g's
``rows[its rows].T @ cot[its rows]``.  Every row is in one group: 2 * M * K
* N, needed work where the group sizes sum to M; a row past the last group
adds nothing and is not multiplied, so a reading over 100% is a fault.
"""

KERNEL = "grouped_matmul_t"
_SCALAR_PREFETCH = 3


def flops(operands, results):
    (m, k), _ = operands[_SCALAR_PREFETCH]
    (_, n), _ = operands[_SCALAR_PREFETCH + 1]
    return 2.0 * m * k * n
