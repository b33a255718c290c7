"""The selective state-space scan's backward walk, from the last chunk to
the first, every cotangent of a chunk made in VMEM (``ops/pallas_ssd``,
kernel ``ssd_scan_bwd``).

Operands u, b, c, the rows, what is as wide as the states, the states every
chunk starts from ``[B, G, n, N, R * P]`` and ``dY``; results ``du``,
``db``, ``dc`` and what is one number a token or chunk, by row and as wide
as the states.  Counted, a head and chunk (c = 128),
what every chunked backward that keeps the operands and the states alone
contracts: ``C B^T`` again and the two products that take the scores'
cotangent to ``dB`` and ``dC`` (``dcb B``, ``dcb^T C``), each shared by a
group's R heads (3 x 2 c^2 N / R); the scores' cotangent ``dY x^T`` and
``M^T dY`` (2 c^2 P each); and against the state or its cotangent ``B
dS^T``, ``C^T (gamma dY)``, ``(gamma dY) S``, ``(e x) dS`` and ``C S^T``
again for ``dcum`` (2 c N P each).  Left out as in ``ssd_scan_fwd``: the
exponentials, the masks, every element-by-element tile and its row and
column sums, and what the kernel multiplies beyond the count.  So the
count is never more than the kernel does and a reading over 100% is a
fault.
"""

KERNEL = "ssd_scan_bwd"
CHUNK = 128
#: (multiples of c^2 N / R, of c^2 P, of c N P) a head and chunk
TERMS = (3, 2, 5)
_STATES = 5


def flops(operands, results):
    # a group and chunk: its R heads share the first kind, and their
    # widths add up to the states' (R * P)
    (rows, groups, n, state, wide), _ = operands[_STATES]
    shared, scores, against = TERMS
    return 2.0 * rows * groups * n * (
        CHUNK * CHUNK * (shared * state + scores * wide)
        + against * CHUNK * state * wide)
