"""The channel-decay delta rule's forward walk (a decay a key channel: the
state's rows each by their own number), a few chunks of a pair of heads a
grid step (``ops/pallas_delta_rule``, kernel ``delta_channel_fwd``).

Operands q, k, g ``[B, T, H * dk]``, v ``[B, T, H * dv]`` and ``beta``'s
columns ``[B, H / 2, n * 128, 128]``; result ``O`` ``[B, T, H * dv]``.
Counted, a head and chunk (C = 64, four sub-blocks of 16): the contractions
EVERY chunked implementation of this rule does at chunks of 64, which are
the scalar rule's (``delta_rule_fwd``) with the two score matrices made by
the split: ``K K^T`` and ``Q K^T`` are the SIX off-diagonal sub-block tiles
each, products about a sub-block's edge (6 x 16^2 = 3/8 C^2, so 2 x 3/8 C^2
dk each), ``W = T (beta gamma k)`` (2 C^2 dk), ``U = T (beta v)`` and ``P
V'`` (2 C^2 dv each), and the three products against the state, ``W S``,
``(q gamma) S`` and ``(k left)^T V'`` (2 C dk dv each).  Left out: the four
diagonal tiles of either score matrix (element by element in float32: vector
work, most of this kernel's time), how the inverse ``T`` is made, the
running sum of ``g`` (a product here), every product's extra passes at the
highest precision, the exponentials, and what the kernel multiplies beyond
the six tiles (its one stacked product a pair streams 96 rows a head against
all 128 tokens).  So the count is never more than the kernel does and a
reading over 100% is a fault; a low one says what the tiles and the inverse
cost.  The shapes give ``H`` (twice the columns' pairs) and both widths.
"""

KERNEL = "delta_channel_fwd"
CHUNK = 64
#: the six off-diagonal tiles of a score matrix, as a share of C^2
SPLIT = 6 * 16 * 16 / (CHUNK * CHUNK)
#: (multiples of C^2 dk, of C^2 dv, of C dk dv) a head and chunk
TERMS = (2 * SPLIT + 1, 2, 3)


def widths(q, v, cols):
    """(chunk-heads, dk, dv) from the declared shapes of q, v and ``beta``'s
    columns."""
    (b, t, keys), _ = q
    (_, _, values), _ = v
    heads = 2 * cols[0][1]
    return b * (t // CHUNK) * heads, keys // heads, values // heads


def counted(terms, chunk_heads, dk, dv):
    by_dk, by_dv, by_state = terms
    return 2.0 * chunk_heads * (CHUNK * CHUNK * (by_dk * dk + by_dv * dv)
                                + by_state * CHUNK * dk * dv)


def flops(operands, results):
    return counted(TERMS, *widths(operands[0], operands[2], operands[4]))
