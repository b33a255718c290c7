"""The channel-decay delta rule's walk made again for its backward, emitting
the state every chunk starts from (transposed, ``[dv, dk]``) and no output
(``ops/pallas_delta_rule``, kernel ``delta_channel_states``).

Operands as ``delta_channel_fwd``'s; result the states ``[B, H / 2, n, 2,
dv, dk]``, whose last two widths are the heads'.  Counted, a head and chunk
(C = 64): ``K K^T``'s six off-diagonal sub-block tiles (2 x 3/8 C^2 dk),
``W`` (2 C^2 dk) and ``U`` (2 C^2 dv), and the two products against the
state that carry it, ``W S`` and ``(k left)^T V'`` (2 C dk dv each); no
``Q K^T``, ``P V'`` or ``(q gamma) S``, which only the output reads.  Left
out as in ``delta_channel_fwd``: the diagonal tiles, how the inverse is
made, the running sum, the extra passes of the highest precision, the
exponentials.  So the count is never more than the kernel does and a
reading over 100% is a fault.
"""

KERNEL = "delta_channel_states"
CHUNK = 64
SPLIT = 6 * 16 * 16 / (CHUNK * CHUNK)
#: (multiples of C^2 dk, of C^2 dv, of C dk dv) a head and chunk
TERMS = (SPLIT + 1, 1, 2)


def flops(operands, results):
    (b, pairs, n, two, dv, dk), _ = results[0]
    return 2.0 * b * pairs * n * two * (
        CHUNK * CHUNK * (TERMS[0] * dk + TERMS[1] * dv)
        + TERMS[2] * CHUNK * dk * dv)
