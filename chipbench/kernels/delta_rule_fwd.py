"""The scalar gated delta rule's forward walk, a chunk of a pair of value
heads a grid step (``ops/pallas_delta_rule``, kernel ``delta_rule_fwd``).

Operands q, k ``[B, T, Hk * dk]``, v ``[B, T, Hv * dv]``, the gates' columns
``[B, Hv / 2, n * 128, 128]`` and rows; result ``O`` ``[B, T, Hv * dv]``.
Counted: the contractions EVERY chunked implementation does at chunks of
64, a value head and chunk (C = 64): ``K K^T`` and ``Q K^T`` (2 C^2 dk
each), ``U = T (beta v)`` (2 C^2 dv) and ``W = T (beta gamma k)`` (2 C^2
dk), ``P V'`` (2 C^2 dv), and the three products against the state, ``W
S``, ``Q S`` and ``K^T (e V')`` (2 C dk dv each).  Left out: how the inverse
``T`` is MADE (this kernel's ladder is ten ``[128, 128]`` products a pair
at six passes each, most of its MXU time), every product's extra passes at
the highest precision, the off-diagonal half of the pair's ``[128, 128]``
scores, the exponentials.  So the count is never more than the kernel does
and a reading over 100% is a fault; a low one says what the inverse costs.
The shapes give ``Hv`` (twice the columns' pairs) and ``dv``, and ``Hk *
dk``; the kernel takes one or two value heads a key head and head widths
that are multiples of 128, so ``dk`` is ``Hk * dk / Hv`` where that is such
a multiple (one value head a key head) and twice it otherwise: of two
readings that both fit, the smaller count.
"""

KERNEL = "delta_rule_fwd"
CHUNK = 64
#: (multiples of C^2 dk, of C^2 dv, of C dk dv) a value head and chunk
TERMS = (3, 2, 3)


def widths(q, v, cols):
    """(chunk-heads, dk, dv) from the declared shapes of q, v and the
    gates' columns."""
    (b, t, keys), _ = q
    (_, _, values), _ = v
    heads = 2 * cols[0][1]
    dk = keys // heads
    if dk % 128:
        dk *= 2
    return b * (t // CHUNK) * heads, dk, values // heads


def counted(terms, chunk_heads, dk, dv):
    by_dk, by_dv, by_state = terms
    return 2.0 * chunk_heads * (CHUNK * CHUNK * (by_dk * dk + by_dv * dv)
                                + by_state * CHUNK * dk * dv)


def flops(operands, results):
    return counted(TERMS, *widths(operands[0], operands[2], operands[3]))
