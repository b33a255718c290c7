"""The scalar gated delta rule's walk made again for its backward, emitting
the state every chunk starts from and no output
(``ops/pallas_delta_rule``, kernel ``delta_rule_states``).

Operands as ``delta_rule_fwd``'s; result the states ``[B, Hv / 2, n, 2, dk,
dv]``, whose last two widths are the heads'.  Counted, a value head and
chunk (C = 64): ``K K^T`` (2 C^2 dk), ``U`` (2 C^2 dv) and ``W`` (2 C^2
dk), and the two products against the state that carry it, ``W S`` and
``K^T (e V')`` (2 C dk dv each); no ``Q K^T``, ``P V'`` or ``Q S``, which
only the output reads.  Left out as in ``delta_rule_fwd``: how the inverse
is made, the extra passes of the highest precision, the exponentials.  So
the count is never more than the kernel does and a reading over 100% is a
fault.
"""

KERNEL = "delta_rule_states"
CHUNK = 64
#: (multiples of C^2 dk, of C^2 dv, of C dk dv) a value head and chunk
TERMS = (2, 1, 2)


def flops(operands, results):
    (b, pairs, n, two, dk, dv), _ = results[0]
    return 2.0 * b * pairs * n * two * (
        CHUNK * CHUNK * (TERMS[0] * dk + TERMS[1] * dv)
        + TERMS[2] * CHUNK * dk * dv)
