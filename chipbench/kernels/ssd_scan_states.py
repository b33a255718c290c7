"""The selective state-space scan's walk made again for its backward,
emitting the states every chunk starts from (a group's, transposed and side
by side: ``[N, R * P]``) and no output (``ops/pallas_ssd``, kernel
``ssd_scan_states``).

Operands as ``ssd_scan_fwd``'s; result the states ``[B, G, n, N, R * P]``.
Counted, a head and chunk (c = 128): the chunk's write ``(e x)^T B`` alone
(2 c N P), the one contraction that carries the state; no ``C B^T``, no
scores and no read, which only the output needs.  Left out as in
``ssd_scan_fwd``: the exponentials, the state's decay, and what the kernel
multiplies beyond the count (heads of 64 two to a tile).  So the count is
never more than the kernel does and a reading over 100% is a fault.
"""

KERNEL = "ssd_scan_states"
CHUNK = 128


def flops(operands, results):
    # a group and chunk: its heads' widths add up to the states' (R * P)
    (rows, groups, n, state, wide), _ = results[0]
    return 2.0 * rows * groups * n * CHUNK * state * wide
