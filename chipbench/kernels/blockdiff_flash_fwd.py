"""Grouped-query flash attention under the block rule of diffusion over
blocks, forward (``ops/pallas_sparse_flash``, kernel
``blockdiff_flash_fwd``).

Operands the walk's table, q, k, v; results the output and the
log-sum-exp.  Two contractions (scores and values).
The call's first operand is the walk's table [5, steps] int32 (scalar
prefetch): one column a live tile, so ``steps`` is the tiles a head walks
(80 at 4,096 tokens a copy in tiles of 512: 56 interior, 24 edge).  q is
[b*hq, 2L, d], k and v [b*hkv, 2L, d]: a clean and a noised copy of L
tokens side by side.  What is counted is the pairs the rule NEEDS as far as
the declared shapes state them: a query of block b counts ``(b + 1) *
block`` keys, which is never fewer than the ``i + 1`` that blocks of one
token would give, so ``2 * L (L + 1) / 2`` pairs a head (16,781,312 at L =
4,096; blocks of 4 need 16,793,600, 0.07% more, which the shapes do not
say), and never more than the tiles the kernel walks (``steps`` of them,
whole: 80 x 512 x 512 = 20,971,520 there), so a reading over 100% is a
fault.  The configuration's ``flops.py`` counts the rule's own pairs.
"""

KERNEL = "blockdiff_flash_fwd"
_MATMULS = 2


def rule_pairs(operands):
    """(b*hq, pairs, d) from the walk's table and q as declared."""
    (bh, positions, d), _ = operands[1]
    tokens = positions // 2
    return bh, tokens * (tokens + 1), d


def flops(operands, results):
    bh, pairs, d = rule_pairs(operands)
    return 2.0 * _MATMULS * bh * pairs * d
