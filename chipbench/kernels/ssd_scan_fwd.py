"""The selective state-space scan's forward walk, a few chunks of one group
of heads a grid step (``ops/pallas_ssd``, kernel ``ssd_scan_fwd``).

Operands u ``[B, T, H * P]``, b and c ``[B, T, G * N]``, the rows (``cum``
and the step of a group's heads, tokens along the lanes) and what is as
wide as a group's states, ``[B, G, n, 3, R * P]``; result ``Y`` ``[B, T, H
* P]``.
Counted: the contractions EVERY chunked form of the scan does at chunks of
128, a head and chunk (c = 128): ``C B^T`` at an R-th (a group's heads
share it: 2 c^2 N / R), the scores' product ``M x`` (2 c^2 P), the read of
the state ``C S^T`` and the chunk's write ``(e x)^T B`` (2 c N P each).
Left out: the exponentials, the masks, ``L`` times the scores and every
other element-by-element tile, the running sums (XLA's), ``D u``, and what
the kernel multiplies beyond the count (heads of 64 go through the scores'
product and the write two to a tile of 128 lanes, each against the whole
tile).  So the count is never more than the kernel does and a reading over
100% is a fault; a low one says what the vector work costs.  Counted a
group and chunk, so that no shape need give ``R``: a group's heads share
the first kind and their widths add up to the states' ``R * P``.
"""

KERNEL = "ssd_scan_fwd"
CHUNK = 128
#: (multiples of c^2 N / R, of c^2 P, of c N P) a head and chunk
TERMS = (1, 1, 2)
_B, _LANES = 1, 4


def counted(terms, group_chunks, state, wide):
    """2 x the multiply-accumulates of ``terms`` over ``group_chunks``
    chunks of a group whose states are ``[state, wide]``."""
    shared, scores, against = terms
    return 2.0 * group_chunks * (CHUNK * CHUNK * (shared * state
                                                  + scores * wide)
                                 + against * CHUNK * state * wide)


def flops(operands, results):
    (_, _, shared), _ = operands[_B]
    (rows, groups, n, _, wide), _ = operands[_LANES]
    return counted(TERMS, rows * groups * n, shared // groups, wide)
