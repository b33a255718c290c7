"""Grouped-query flash attention under a causal window, forward
(``ops/pallas_sparse_flash``, kernel ``window_flash_fwd``).

Operands the band's table, q, k, v; results the output and the
log-sum-exp.  Two contractions (scores and values).
The call's first operand is the band's table [tiles, band] int32
(scalar prefetch): ``tiles`` query tiles of ``t / tiles`` positions, each
walking at most ``band`` key tiles.  q is [b*hq, t, d], k and v
[b*hkv, t, d].  What is counted is the pairs inside the band as the
declared shapes state it, ``sum_t min(t + 1, w)`` with ``w = (band - 1) *
(t / tiles)``: the window itself where it is a multiple of the tile (2,048
in tiles of 512: 14.68M pairs of the causal 33.56M at t = 8192), never more
than it otherwise, and never more than the tiles the kernel walks
(``sum_j min(j + 1, band)`` of them, whole: 70 x 512 x 512 = 18.35M pairs
there), so a reading over 100% is a fault.  It is also the yardstick of the
configuration's ``flops.py``: needed work, with the masked part of the
band's edge tiles as the kernel's own loss.
"""

KERNEL = "window_flash_fwd"
_MATMULS = 2


def band_pairs(operands):
    """(b*hq, pairs, d) from the band's table and q as declared."""
    (tiles, band), _ = operands[0]
    (bh, t, d), _ = operands[1]
    w = min((band - 1) * (t // tiles), t) or 1   # band 1: the diagonal only
    return bh, w * (w + 1) // 2 + (t - w) * w, d


def flops(operands, results):
    bh, pairs, d = band_pairs(operands)
    return 2.0 * _MATMULS * bh * pairs * d
