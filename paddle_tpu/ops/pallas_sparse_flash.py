"""Grouped-query flash attention over the keys a static rule or a
per-query selection lets count, as Pallas TPU kernels: forward, dQ and
dK/dV, in three kinds: causal (under a selection where there is one), causal
under a window, and the block rule of diffusion over blocks, which is NOT
causal (the last section below).

A sibling of ``pallas_flash`` (equal head counts, key-padding bias) for the
decoder path: ``hq`` query heads read ``hkv = hq / group`` key-value heads
(query head h reads head ``h // group``), attention is causal in the first
two kinds, and an
optional selection ``sel`` ([B, T, T] int8, 1 where query t attends key s;
it already holds causality) masks the scores inside the kernels, so no
[H, T, T] tensor ever reaches HBM.  K/V are never repeated in HBM either:
the index maps point the ``group`` query heads of a key-value head at the
same tiles, and the dK/dV kernel walks the group's query tiles in its
sequential grid dimension and sums them in VMEM.

Contractions take their operands in the input dtype (bf16 under AMP) and
accumulate in float32; softmax statistics and accumulators are float32
VMEM scratch.  Tiles that hold no selected key are NOT skipped yet (a
selection learned by an indexer leaves few of them empty).

The grids.  A grid is (heads, then the steps of a WALK over the tiles),
and a step of a walk is one tile pair: the tile that STAYS (the query tile
of forward and dQ, the key tile of dK/dV; its accumulators start at the
first of its steps and are written out at the last), the tile that STREAMS
past it (under dK/dV the query tiles of every query head of the group, head
after head), and the step's kind, which chooses the mask.  There is one
kernel a family (``_fwd_kernel``, ``_dq_kernel``, ``_dkv_kernel``) over the
shared tile bodies (``_attend``, ``_dq_tile``, ``_dkv_tile``); a walk
(``_Walk``) brings the grid, the index maps and its half of the kernel
(``step``: first, last, and the body under the step's mask), and the three
kinds are three walks:

- causal (``_causal_walk``): the LIVE tiles alone, ``n (n + 1) / 2`` steps
  a query head of ``n`` tiles and none dead (136 at 8,192 tokens in tiles of
  512, where the rectangle ``n x n`` laid 256 until PR 66).  The triangle is
  folded: the rows of ``p`` and of ``n + 1 - p`` live tiles make ``n + 1``
  steps together, so the grid is (heads, pairs, steps a pair) and a step's
  tiles are a compare and two selects away from its grid position
  (``_fold``; under dK/dV the query head of the group costs a compare a
  binary digit more).  There is NO table here, although the other two walks
  have one: a scalar-prefetch operand stands before every other, and the
  benchmark's ``chipbench/kernels/sparse_flash_*.py`` count the causal half
  from the declared shape of the call's FIRST operand, q.  ``causal_walk``
  gives the same walk as a table, for tests and readers.  Each accumulator
  sums its tiles in the order the rectangle did, so results are the same
  bits.
- a band (``_band_walk``, below): (heads, tiles, band steps) under a table
  ``[tiles, band]``; only the first ``band - 1`` rows hold dead steps.
- the block rule (``_rule_walk``, the last section): (heads, steps) under a
  table ``[5, steps]``, the live tiles alone.

``ops.sparse_attention.grid_steps{kernel}`` counts the steps a query head
of every grid laid, beside ``tiles{kernel,kind}``: equal where no step is
dead.

A static causal ``window`` (0: none; else key s counts for query t iff
``0 <= t - s < window``) shrinks the grids' key (for dK/dV: query) dimension
to the band: ``ceil((window - 1) / block) + 1`` tiles a row, 70 of the 136
causal tiles at 8,192 tokens and window 2,048, so a tile wholly outside the
band is no grid step at all.  Step s of query tile j walks key tile
``j - (band - 1) + s``; a negative one is dead (the first rows of the
band; for dK/dV the query tiles past the last), runs no body and maps to
the nearest live tile, which is in VMEM already.  The tile of each step
comes from a scalar-prefetch table ``[tiles, band]`` int32: the call's
first operand, whose shape states the band to whoever counts the kernel's
work from its declared shapes.  With a window there is no selection.

Where a selection or a window makes the mask more than one compare, a live
tile is INTERIOR or EDGE, told apart from the step's tiles and the static
shapes (``interior_reach``), and the kernel holds one body for each under
``pl.when``.  Interior: every (query, key) pair of the tile
satisfies the causal rule and the band's: the key tile lies wholly before
the query tile and, under a window, its farthest pair is still inside it
(at window 2,048 in tiles of 512 the three tiles before the diagonal).  The
body there makes no positional mask: no iota, no compare; under a window no
select either, with a selection the mask is the selection alone.  Edge: the
diagonal tile and the band's far tile(s), which mask by position as well.
The diagonal tile keeps the causal compare under a selection too: ``Sel``
is an input of the op, nothing makes it causal, and ``blocked_attention``
ANDs it with the causal rule, so the kernels give causal-AND-selection for
any ``Sel``.  120 of the 136 live tiles are interior at 8,192 tokens under a
selection, 30 of 50 in a band of 2,048 over 6,144 (``tile_counts``; counted
for every call traced as ``ops.sparse_attention.tiles{kernel,kind}``).
With neither a selection nor a window a kernel keeps ONE body that masks
every live tile: on the chip (v5e, the kernels alone at the decoder cells'
shapes, one body -> two) the second body cost the forward 1.3% at head
width 128, moved dK/dV by +0.1 to +0.3% and dQ by -0.9 to +0.1%, where under
a selection it saves 1.4 / 4.7 / 1.9% of forward / dQ / dK/dV and in a band
0.6 / 4.6 / 0.4%: the lone causal compare rides in issue slots that are
free, and a second body's code is not (PERF.md, Findings PR 46).

The forward keeps a row's running maximum and sum in EVERY lane of a
``[rows, 128]`` scratch (the lane reductions hand them back so): a
``[rows, 1]`` column would cost a lane broadcast through the permute unit
for each row group and tile, and that round trip, not the mask, was what
bound the forward's schedule.  A cut score reads ``MASKED``, which is under
the running maximum's first value, so its exponential is an exact 0 and the
forward selects once.  The row statistics leave the kernel as the
``[.., T, 1]`` log-sum-exp the backward kernels read.

The block rule (``rule`` = (tokens a copy L, block length), PR 65): T = 2L
holds a clean copy of a sequence and then a noised one, and with ``B(i) = (i
mod L) // block`` a clean query counts the clean keys of blocks ``<= B(t)``
(its own block whole, the later tokens of it too), a noised query the clean
keys of blocks ``< B(t)`` and the noised keys of block ``B(t)``, in one
softmax; no clean query counts a noised key.  The copies are cut into tiles
alike (``rule_tiles``), and a block is a power of two that no tile
straddles, so every tile is dead, INTERIOR (a clean key tile wholly before
the query tile's place: no mask at all) or one of three EDGE tiles whose
rows and columns start at the same place of their copies: clean query tile
j against clean tile j (``B(s) <= B(t)``), noised query tile j against
clean tile j (``B(s) < B(t)``) and against its own noised tile (``B(s) =
B(t)``: ``block`` live keys a row).  The walk is a scalar-prefetch table
``[5, steps]`` int32 (``rule_walk``, ``_walk_table``), the call's first
operand: for each step the tile that stays, the tile that streams, the
query head of the group, the mask and whether the step is the first or the
last of its resident tile.  80 steps a head at 4,096 tokens
a copy in tiles of 512 (56 interior, 24 edge) where causal attention over
the 8,192 positions walks 136.  Two bodies: the edge body makes its compare
from the step's mask (two scalar bounds on how many blocks the key lies
before the query), so the three edge kinds share it.

The kernels carry names of their own (``sparse_flash_fwd``,
``sparse_flash_dq``, ``sparse_flash_dkv``; with a window
``window_flash_fwd``, ``window_flash_dq``, ``window_flash_dkv``; under the
block rule ``blockdiff_flash_fwd``, ``blockdiff_flash_dq``,
``blockdiff_flash_dkv``).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_flash import NEG_INF, block_index, resolve

BLOCK = 512
#: lanes of a vector register: the forward keeps a row's running maximum
#: and sum in every lane of a ``[rows, LANE]`` scratch
LANE = 128
#: what a cut score reads: under the running maximum's first value
#: (``NEG_INF``), so ``exp(MASKED - m)`` is an exact 0 for every m a row
#: can hold and the forward needs no second select
MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)


def _block(t):
    b = min(BLOCK, t)
    while t % b:
        b //= 2
    return b


def band_tiles(window, blk, n):
    """Tiles of ``blk`` keys that a query tile's band of ``window`` keys
    touches: the diagonal one and ``ceil((window - 1) / blk)`` before it,
    of the ``n`` there are."""
    return min(-(-(window - 1) // blk) + 1, n)


def supported(q, k, sel, window=0, v=None, rule=None) -> str:
    """'' when the kernels take these operands, else why not.  ``v`` (None:
    as ``k``) has k's batch, heads and length, and k's width too: one head
    width is what the kernels' tiles are cut to.  ``rule``: (tokens a copy,
    block length) of the block rule; a block is a power of two that no tile
    of a copy straddles."""
    b, hq, t, d = q.shape
    if window and sel is not None:
        return "window_selection"
    if k.shape[0] != b or k.shape[2] != t or k.shape[3] != d:
        return "shape"
    if v is not None and v.shape[:3] != k.shape[:3]:
        return "shape"
    if v is not None and v.shape[3] != d:
        return "value_width"
    if hq % k.shape[1]:
        return "heads"
    if sel is not None and tuple(sel.shape) != (b, t, t):
        return "selection"
    if rule:
        tokens, block = rule
        if window or sel is not None or t != 2 * tokens:
            return "rule"
        if block < 1 or block & (block - 1) or _block(tokens) % block:
            return "block"
        t = tokens
    if t % 8 or _block(t) % 8:
        return "ragged"
    return ""


def _scores(q, k, scale):
    return jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * jnp.float32(scale)


def _keep(sel_ref, shape, offsets, window=0):
    """[bq, bk] bool: the pairs of this tile that count; None where all do.
    ``offsets``: (first query, first key) of an edge tile, whose positions
    the causal rule and the band are asked about; None in an interior
    tile, where both hold for every pair and only the selection cuts."""
    keep = None
    if offsets is not None:
        qpos = offsets[0] + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        kpos = offsets[1] + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        keep = qpos >= kpos
        if window:
            keep = jnp.logical_and(keep, qpos - kpos < jnp.int32(window))
    if sel_ref is not None:
        chosen = sel_ref[0].astype(jnp.float32) > 0.5
        keep = chosen if keep is None else jnp.logical_and(keep, chosen)
    return keep


def interior_reach(window, blk, band, selected=False):
    """How many of the ``band - 1`` tiles before the diagonal one are
    INTERIOR: run by a body of their own that makes no positional mask.
    The pairs of a tile ``dist`` tiles back lie ``dist * blk - (blk - 1)``
    to ``dist * blk + blk - 1`` keys back: all before the query, and all
    inside a window iff the farthest is.  0 without a selection or a
    window: the mask is then one compare, a second body buys nothing (the
    module's docstring has the readings) and the kernel keeps one."""
    if window:
        return max(min((window - blk) // blk, band - 1), 0)
    return band - 1 if selected else 0


def tile_counts(t, window=0, selected=False):
    """(interior, edge): the live tiles of each kind that one query head
    walks in a kernel call over ``t`` positions."""
    blk = _block(t)
    n = t // blk
    band = band_tiles(window, blk, n) if window else n
    reach = interior_reach(window, blk, band, selected)
    live = sum(min(j + 1, band) for j in range(n))
    interior = sum(min(j, reach) for j in range(n))
    return interior, live - interior


def _lanes(x, n):
    """``x`` [rows, w], every lane of a row the same value, as [rows, n]."""
    from jax.experimental.pallas import tpu as pltpu

    w = x.shape[1]
    if n <= w:
        return x[:, :n]
    if n % w:               # a head width such as 192: one lane, broadcast
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return pltpu.repeat(x, n // w, axis=1)


def _probabilities(s, lse, keep):
    """exp(s - lse) of the backward kernels, 0 where ``keep`` cuts."""
    p = jnp.exp(s - lse)
    return p if keep is None else jnp.where(keep, p, jnp.float32(0.0))


def _attend(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, scale, keep_of):
    """One live tile of the forward: its scores into the running maximum,
    sum and accumulator.  ``keep_of(shape)``: the tile's mask, or None."""
    s = _scores(q_ref[0], k_ref[0], scale)
    keep = keep_of(s.shape)
    if keep is not None:
        s = jnp.where(keep, s, jnp.float32(MASKED))
    m = m_ref[:]
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    # a cut score is under every m_new: its exp is an exact 0
    p = jnp.exp(s - _lanes(m_new, s.shape[1]))
    corr = jnp.exp(m - m_new)
    m_ref[:] = m_new
    l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[:] = acc_ref[:] * _lanes(corr, acc_ref.shape[1]) \
        + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _fwd_init(m_ref, l_ref, acc_ref):
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)


def _fwd_flush(o_ref, lse_ref, m_ref, l_ref, acc_ref):
    l = jnp.maximum(l_ref[:], jnp.float32(1e-30))
    o_ref[0] = (acc_ref[:] / _lanes(l, acc_ref.shape[1])).astype(
        o_ref.dtype)
    lse_ref[0] = (m_ref[:] + jnp.log(l))[:, :1]


def _dq_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_acc, scale,
             keep_of):
    """One live tile of dQ: ``dq_acc += scale * dS k``."""
    k = k_ref[0]
    s = _scores(q_ref[0], k, scale)
    p = _probabilities(s, lse_ref[0], keep_of(s.shape))
    dp = jax.lax.dot_general(
        do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0])
    dq_acc[:] += jnp.float32(scale) * jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _dkv_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_acc, dv_acc,
              scale, keep_of):
    """One live tile of dK/dV: ``dv_acc += P^T dO``, ``dk_acc += scale *
    dS^T q``."""
    q, do = q_ref[0], do_ref[0]
    s = _scores(q, k_ref[0], scale)                      # [bq, bk]
    p = _probabilities(s, lse_ref[0], keep_of(s.shape))
    dv_acc[:] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # [bk, d]
    dp = jax.lax.dot_general(
        do, v_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0])
    dk_acc[:] += jnp.float32(scale) * jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


# -- the kernels: one a family, whatever walks the tiles -------------------
#
# A kernel's grid is laid by a WALK (``_Walk``: causal, band or block rule),
# and the walk's ``step(table_ref, sel_ref, tile)`` is its half of every
# kernel: called once in the body, it gives ``first()`` and ``last()``,
# whether the grid step at hand is the first or the last of the tile that
# stays (where the accumulators start and are written out), and
# ``run(body)``, which runs ``body(keep_of)`` under the mask of the step's
# kind.  All three are called where their scalars are wanted, in that order.

def _fwd_kernel(*refs, scale, walk, has_sel):
    table_ref, (q_ref, k_ref, v_ref, *rest) = _table_first(walk, refs)
    sel_ref = rest.pop(0) if has_sel else None
    o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
    first, last, run = walk.step(table_ref, sel_ref, q_ref.shape[1])

    pl.when(first())(lambda: _fwd_init(m_ref, l_ref, acc_ref))
    run(lambda keep_of: _attend(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                                scale, keep_of))
    pl.when(last())(
        lambda: _fwd_flush(o_ref, lse_ref, m_ref, l_ref, acc_ref))


def _dq_kernel(*refs, scale, walk, has_sel):
    table_ref, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest) = \
        _table_first(walk, refs)
    sel_ref = rest.pop(0) if has_sel else None
    dq_ref, dq_acc = rest
    first, last, run = walk.step(table_ref, sel_ref, q_ref.shape[1])

    @pl.when(first())
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run(lambda keep_of: _dq_tile(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                 delta_ref, dq_acc, scale, keep_of))

    @pl.when(last())
    def _flush():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, walk, has_sel):
    """The K/V tile stays, and the query tiles of every query head of the
    group that read it stream past, head after head."""
    table_ref, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest) = \
        _table_first(walk, refs)
    sel_ref = rest.pop(0) if has_sel else None
    dk_ref, dv_ref, dk_acc, dv_acc = rest
    first, last, run = walk.step(table_ref, sel_ref, q_ref.shape[1])

    @pl.when(first())
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run(lambda keep_of: _dkv_tile(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                  delta_ref, dk_acc, dv_acc, scale, keep_of))

    @pl.when(last())
    def _flush():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


class _Walk(NamedTuple):
    """How one kernel call walks its tiles."""
    table: object   # the scalar-prefetch operand the index maps read; None
    steps: tuple    # the grid's dimensions after the heads'
    step: object    # the kernels' half, above
    stays: object   # index map of the tile that stays, of the grid's head
    streams: object     # ... that streams past, of the other side's head
    sel: object = None  # ... of the selection


def _table_first(walk, refs):
    return (None, refs) if walk.table is None else (refs[0], refs[1:])


# -- causal: the triangle, folded (the module's docstring) -----------------

def _fold(n, reps, u, r):
    """Step ``r`` of pair ``u`` of the folded triangle whose rows hold 1..n
    tiles, each row walked ``reps`` times over before the next: (tiles of
    the row the step is in, its place in the row, which of the row's
    walks, whether it is the first, the last of the row's steps).  A pair
    is the row of ``u + 1`` tiles and then the row of ``n - u`` (``n`` odd:
    of ``u`` and of ``n - u``, the longest row alone); ints or arrays."""
    short = u + 1 - n % 2
    in_short = r < short * reps
    at = jnp.where(in_short, r, r - short * reps)
    length = jnp.where(in_short, short, n - u)
    first, last = at == 0, at == length * reps - 1
    walk = jnp.zeros_like(at)
    bit = 1 << max(reps - 1, 0).bit_length()
    while bit > 1:                  # at // length, a binary digit a compare
        bit //= 2
        over = at >= length * bit
        at = jnp.where(over, at - length * bit, at)
        walk = walk + jnp.where(over, jnp.int32(bit), jnp.int32(0))
    return length, at, walk, first, last


def causal_steps(n, reps=1):
    """The folded grid after its heads: (pairs, steps a pair)."""
    return (n + 1) // 2, (n + 1 - n % 2) * reps


def _causal_tiles(n, group, by_key, u, r):
    """(query tile, key tile, query head of the group, first, last) of
    step ``r`` of pair ``u``.  The rows are the query tiles' (tile j reads
    key tiles 0..j); ``by_key`` the key tiles' (tile j is read by query
    tiles j..n-1), each walked once for every query head of the group."""
    length, at, member, first, last = _fold(
        n, group if by_key else 1, u, r)
    if by_key:
        return n - length + at, n - length, member, first, last
    return length - 1, at, member, first, last


def _causal_walk(n, group, hq, by_key=False):
    """Forward and dQ: grid (b*hq, pairs, steps), the query tile stays.
    dK/dV (``by_key``): grid (b*hkv, pairs, group x steps), the key tile
    stays."""
    reps = group if by_key else 1
    tiles = functools.partial(_causal_tiles, n, group, by_key)

    def stays(i, u, r):
        qt, kt = tiles(u, r)[:2]
        return block_index(i, kt if by_key else qt, 0)

    def streams(i, u, r):
        qt, kt, member = tiles(u, r)[:3]
        if by_key:
            return block_index(i * jnp.int32(group) + member, qt, 0)
        return block_index(jax.lax.div(i, jnp.int32(group)), kt, 0)

    def sel(i, u, r):
        qt, kt = tiles(u, r)[:2]
        return block_index(jax.lax.div(i, jnp.int32(hq // reps)), qt, kt)

    def step(_, sel_ref, blk):
        qt, kt, _, first, last = tiles(pl.program_id(1), pl.program_id(2))
        offsets = qt * jnp.int32(blk), kt * jnp.int32(blk)

        def run(body):
            def edge(shape):
                return _keep(sel_ref, shape, offsets)

            if sel_ref is None:     # the mask is one compare: one body
                return body(edge)
            pl.when(qt != kt)(
                lambda: body(lambda shape: _keep(sel_ref, shape, None)))
            pl.when(qt == kt)(lambda: body(edge))

        return lambda: first, lambda: last, run

    return _Walk(None, causal_steps(n, reps), step, stays, streams, sel)


def causal_walk(n, group=1, by_key=False, selected=False):
    """The causal walk as a table [5, steps] of numpy, rows as a block
    rule's (``RESIDENT`` ..): what the folded grid's index maps and kernels
    compute for each step, for whoever wants to read it.  The diagonal tile
    is the edge one (its compare is ``EDGE_LE`` at blocks of one token);
    the others are interior where they have a body of their own, under a
    selection."""
    import numpy as np

    pairs, steps = causal_steps(n, group if by_key else 1)
    u, r = (x.ravel() for x in np.meshgrid(
        np.arange(pairs, dtype=np.int32), np.arange(steps, dtype=np.int32),
        indexing="ij"))
    qt, kt, member, first, last = (
        np.asarray(x) for x in _causal_tiles(n, group, by_key, u, r))
    interior = np.logical_and(selected, qt != kt)
    return np.stack([kt if by_key else qt, qt if by_key else kt, member,
                     np.where(interior, INTERIOR, EDGE_LE),
                     FIRST * first + LAST * last]).astype(np.int32)


# -- causal under a window: a band ----------------------------------------

def _by_kind(live, dist, reach, offsets, body):
    """Runs ``body`` in a live tile whose query tile lies ``dist`` tiles
    after its key tile: ``body(None)`` in an interior one (one of the
    ``reach`` before the diagonal), ``body(offsets)`` in an edge one (the
    diagonal tile, the band's far tiles), which masks by position.  One
    kernel, two bodies; one where nothing is interior."""
    if not reach:
        pl.when(live)(lambda: body(offsets))
        return
    interior = jnp.logical_and(dist >= 1, dist <= jnp.int32(reach))
    pl.when(jnp.logical_and(live, interior))(lambda: body(None))
    pl.when(jnp.logical_and(live, jnp.logical_not(interior)))(
        lambda: body(offsets))


def _band_maps(group, band):
    """Index maps of the window kernels' grids; each takes the band's
    scalar-prefetch table as its last argument.  Forward and dQ, grid
    (b*hq, q tile, band step): ``table[j, s]`` is the step's key tile.
    dK/dV, grid (b*hkv, k tile, group member x band step): ``table[j, s]``
    is the step's query tile."""
    def resident(i, j, s, table):
        return block_index(i, j, 0)

    def kv(i, j, s, table):
        return block_index(jax.lax.div(i, jnp.int32(group)), table[j, s], 0)

    def q_side(i, j, s, table):
        head = i * jnp.int32(group) + jax.lax.div(s, jnp.int32(band))
        return block_index(head, table[j, jax.lax.rem(s, jnp.int32(band))],
                           0)

    return resident, kv, q_side


def _band_tables(n, band):
    """(key tile of step s of query tile j, query tile of step s of key
    tile j), each [n, band] int32, a dead step at its nearest live tile."""
    import numpy as np

    j, s = np.arange(n)[:, None], np.arange(band)[None, :]
    return (jnp.asarray(np.maximum(j - (band - 1) + s, 0), jnp.int32),
            jnp.asarray(np.minimum(j + s, n - 1), jnp.int32))


def _band_walk(n, group, window, blk, by_key=False):
    """Forward and dQ: grid (b*hq, q tile, band step), step s of query tile
    j is key tile ``j - (band - 1) + s``, live from tile 0 on.  dK/dV
    (``by_key``): grid (b*hkv, k tile, group member x band step), step s
    of key tile j is query tile ``j + s``, live up to the last."""
    band = band_tiles(window, blk, n)
    resident, kv, q_side = _band_maps(group, band)
    reach = interior_reach(window, blk, band)
    steps = (group if by_key else 1) * band

    def step(_, sel_ref, blk):
        # scalars in the order these kernels have always made them: the
        # lowered bodies are PR 65's, byte for byte
        j, s = pl.program_id(1), pl.program_id(2)
        blk = jnp.int32(blk)
        if by_key:
            qt, kt = j + jax.lax.rem(s, jnp.int32(band)), j
            live = qt <= jnp.int32(n - 1)
            place = lambda: (qt - kt, (qt * blk, kt * blk))  # noqa: E731
        else:
            dist = jnp.int32(band - 1) - s
            qt, kt = j, j - dist
            offsets = qt * blk, kt * blk
            live = kt >= 0
            place = lambda: (dist, offsets)                  # noqa: E731

        def run(body):
            dist, offsets = place()
            _by_kind(live, dist, reach, offsets, lambda offsets: body(
                lambda shape: _keep(None, shape, offsets, window)))

        return lambda: s == 0, lambda: s == steps - 1, run

    return _Walk(_band_tables(n, band)[by_key], (n, steps), step, resident,
                 q_side if by_key else kv)


# -- block diffusion: two copies of a sequence under one rule ------------
#
# A step of the walk is one live tile.  Its mask (``STEP_MASK`` row of the
# walk's table): INTERIOR, none at all; or one of three compares between
# the BLOCK of the query and the block of the key.  In every edge tile the
# rows and the columns start at the same position of their copies, so the
# blocks are told from the tile's own row and column numbers.
INTERIOR, EDGE_LE, EDGE_LT, EDGE_EQ = 0, 1, 2, 3
#: rows of a walk's table [5, steps] int32, the scalar-prefetch operand:
#: the tile that stays (the query tile of forward and dQ, the key tile of
#: dK/dV), the tile that streams past it, the query head of the group
#: (dK/dV; else 0), the step's mask, and FIRST | LAST where the step is
#: the first or last of its resident tile
RESIDENT, STREAMED, MEMBER, STEP_MASK, FLAGS = range(5)
FIRST, LAST = 1, 2


def rule_tiles(tokens):
    """(tile, tiles a copy) under the block rule: the copies are cut alike,
    so no tile holds positions of both."""
    blk = _block(tokens)
    return blk, tokens // blk


def rule_walk(n):
    """[(query tile, key tile, mask)] of the live tiles over two copies of
    ``n`` tiles each, query tile by query tile: clean tile j reads the clean
    tiles up to its own (whole blocks of its own: ``B(s) <= B(t)``); noised
    tile ``n + j`` the clean tiles before ``j``, of clean tile ``j`` the
    blocks BEFORE the query's, and of its own tile the query's block."""
    steps = []
    for j in range(n):
        steps += [(j, s, INTERIOR) for s in range(j)] + [(j, j, EDGE_LE)]
    for j in range(n):
        steps += [(n + j, s, INTERIOR) for s in range(j)] \
            + [(n + j, j, EDGE_LT), (n + j, n + j, EDGE_EQ)]
    return steps


def rule_tile_counts(tokens):
    """(interior, edge) live tiles a head under the block rule: of ``n``
    tiles a copy ``j`` interior ones for clean and for noised query tile j,
    one edge tile for a clean and two for a noised one; 56 and 24 at 4,096
    tokens a copy in tiles of 512, where causal attention over the 8,192
    positions walks 136."""
    n = rule_tiles(tokens)[1]
    return n * (n - 1), 3 * n


def _walk_table(n, group=1, by_key=False):
    """The walk as a kernel's table: query-major for forward and dQ; for
    dK/dV key-major, and under each key tile every query head of the group
    in turn."""
    import numpy as np

    walk = rule_walk(n)
    if not by_key:
        rows = [(q, k, 0, mask) for q, k, mask in walk]
    else:
        rows = [(k, q, g, mask) for tile in range(2 * n)
                for g in range(group)
                for q, k, mask in walk if k == tile]
    table = np.zeros((5, len(rows)), np.int32)
    table[:4] = np.asarray(rows, np.int32).T
    stays = table[RESIDENT]
    table[FLAGS] = FIRST * np.r_[True, stays[1:] != stays[:-1]] \
        + LAST * np.r_[stays[1:] != stays[:-1], True]
    return jnp.asarray(table)


def _block_keep(shape, mask, shift):
    """[bq, bk] bool of an edge tile: how many blocks the key's lies before
    the query's (blocks of ``1 << shift`` positions) against the bounds of
    the step's ``mask``: 0 or more, 1 or more, exactly 0."""
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    back = jax.lax.shift_right_logical(rows, jnp.int32(shift)) \
        - jax.lax.shift_right_logical(cols, jnp.int32(shift))
    least = jnp.where(mask == EDGE_LT, jnp.int32(1), jnp.int32(0))
    most = jnp.where(mask == EDGE_EQ, jnp.int32(0), jnp.int32(shape[0]))
    return jnp.logical_and(back >= least, back <= most)


def _by_mask(mask, shift, body):
    """``body(keep_of)`` in one of two bodies: an interior step makes no
    mask, an edge step the compare its ``mask`` names."""
    pl.when(mask == INTERIOR)(lambda: body(lambda shape: None))
    pl.when(mask != INTERIOR)(
        lambda: body(lambda shape: _block_keep(shape, mask, shift)))


def _rule_maps(group):
    """Index maps of the walked grids (heads, step): the tile that stays
    and the tile that streams, each of the query's or of the key-value
    head, the walk's table as the last argument."""
    def own(row):
        return lambda i, s, walk: block_index(i, walk[row, s], 0)

    def of_kv_head(row):        # forward, dQ: a query head's key tiles
        return lambda i, s, walk: block_index(
            jax.lax.div(i, jnp.int32(group)), walk[row, s], 0)

    def of_member(row):         # dK/dV: the group's query heads in turn
        return lambda i, s, walk: block_index(
            i * jnp.int32(group) + walk[MEMBER, s], walk[row, s], 0)

    return own, of_kv_head, of_member


def _rule_walk(n, group, shift, by_key=False):
    """Grid (heads, steps) under the walk's table; dK/dV walks the
    transpose.  Blocks of ``1 << shift`` positions."""
    table = _walk_table(n, group, by_key)
    own, of_kv_head, of_member = _rule_maps(group)

    def step(walk_ref, sel_ref, blk):
        at = pl.program_id(1)
        flags = walk_ref[FLAGS, at]
        return lambda: (flags & FIRST) != 0, lambda: (flags & LAST) != 0, \
            lambda body: _by_mask(walk_ref[STEP_MASK, at], shift, body)

    return _Walk(table, (table.shape[1],), step, own(RESIDENT),
                 (of_member if by_key else of_kv_head)(STREAMED))


def _call(kernel, name, walk, args, *, heads, in_specs, out_specs,
          out_shape, scratch_shapes, interpret, tiles, group=1):
    """``pallas_call`` over ``heads`` times the walk's steps; a walk's
    table is the scalar-prefetch operand.  Counts, a query head (a grid
    head of dK/dV walks for the ``group`` of them), the steps of the grid
    it lays and the call's live ``tiles`` (interior, edge): equal where no
    step is dead."""
    from jax.experimental.pallas import tpu as pltpu
    from .decoder_ops import _count

    _count("ops.sparse_attention.grid_steps",
           math.prod(walk.steps) // group, kernel=name)
    for kind, count in zip(("interior", "edge"), tiles):
        _count("ops.sparse_attention.tiles", count, kernel=name, kind=kind)

    grid = (heads,) + walk.steps
    if walk.table is None:
        return pl.pallas_call(
            kernel, out_shape=out_shape, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes,
            interpret=interpret, name=name)(*args)
    return pl.pallas_call(
        kernel, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes),
        interpret=interpret, name=name)(walk.table, *args)


def _kind(q, k, sel, window, rule):
    """(the kernels' first name, tile, (interior, edge) tiles a head, the
    walk of ``by_key``) of a call: the block rule's, a band's, or causal."""
    hq, t = q.shape[1:3]
    group = hq // k.shape[1]
    if rule:
        tokens, block = rule
        blk, n = rule_tiles(tokens)
        return "blockdiff", blk, rule_tile_counts(tokens), functools.partial(
            _rule_walk, n, group, block.bit_length() - 1)
    blk = _block(t)
    tiles = tile_counts(t, window, sel is not None)
    if window:
        return "window", blk, tiles, functools.partial(
            _band_walk, t // blk, group, window, blk)
    return "sparse", blk, tiles, functools.partial(
        _causal_walk, t // blk, group, hq)


def _forward(q, k, v, sel, scale, interpret, window=0, rule=None):
    from jax.experimental.pallas import tpu as pltpu

    b, hq, t, d = q.shape
    hkv = k.shape[1]
    name, blk, tiles, walk_of = _kind(q, k, sel, window, rule)
    walk = walk_of()
    in_specs = [pl.BlockSpec((1, blk, d), walk.stays),
                pl.BlockSpec((1, blk, d), walk.streams),
                pl.BlockSpec((1, blk, d), walk.streams)]
    args = [q.reshape(b * hq, t, d), k.reshape(b * hkv, t, d),
            v.reshape(b * hkv, t, d)]
    if sel is not None:
        in_specs.append(pl.BlockSpec((1, blk, blk), walk.sel))
        args.append(sel)
    out, lse = _call(
        functools.partial(_fwd_kernel, scale=scale, walk=walk,
                          has_sel=sel is not None),
        name + "_flash_fwd", walk, args, heads=b * hq, in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, blk, d), walk.stays),
                   pl.BlockSpec((1, blk, 1), walk.stays)],
        out_shape=[jax.ShapeDtypeStruct((b * hq, t, d), q.dtype),
                   jax.ShapeDtypeStruct((b * hq, t, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((blk, LANE), jnp.float32),
                        pltpu.VMEM((blk, LANE), jnp.float32),
                        pltpu.VMEM((blk, d), jnp.float32)],
        interpret=interpret, tiles=tiles)
    return out.reshape(b, hq, t, d), lse.reshape(b, hq, t, 1)


def _backward(q, k, v, sel, out, lse, do, scale, interpret, window=0,
              rule=None):
    from jax.experimental.pallas import tpu as pltpu

    b, hq, t, d = q.shape
    hkv = k.shape[1]
    name, blk, tiles, walk_of = _kind(q, k, sel, window, rule)
    has_sel = sel is not None
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    args = (q.reshape(b * hq, t, d), k.reshape(b * hkv, t, d),
            v.reshape(b * hkv, t, d), do.reshape(b * hq, t, d),
            lse.reshape(b * hq, t, 1), delta.reshape(b * hq, t, 1)) \
        + ((sel,) if has_sel else ())

    def specs(q_side, kv_side, sel_map):
        return [pl.BlockSpec((1, blk, d), q_side),
                pl.BlockSpec((1, blk, d), kv_side),
                pl.BlockSpec((1, blk, d), kv_side),
                pl.BlockSpec((1, blk, d), q_side),
                pl.BlockSpec((1, blk, 1), q_side),
                pl.BlockSpec((1, blk, 1), q_side)] \
            + ([pl.BlockSpec((1, blk, blk), sel_map)] if has_sel else [])

    walk = walk_of()
    dq = _call(
        functools.partial(_dq_kernel, scale=scale, walk=walk,
                          has_sel=has_sel),
        name + "_flash_dq", walk, args, heads=b * hq,
        in_specs=specs(walk.stays, walk.streams, walk.sel),
        out_specs=pl.BlockSpec((1, blk, d), walk.stays),
        out_shape=jax.ShapeDtypeStruct((b * hq, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32)],
        interpret=interpret, tiles=tiles)
    # dK/dV: the key tile stays, the tiles of the group's query heads that
    # read it stream past: the walk's transpose
    walk = walk_of(by_key=True)
    dk, dv = _call(
        functools.partial(_dkv_kernel, scale=scale, walk=walk,
                          has_sel=has_sel),
        name + "_flash_dkv", walk, args, heads=b * hkv,
        in_specs=specs(walk.streams, walk.stays, walk.sel),
        out_specs=[pl.BlockSpec((1, blk, d), walk.stays),
                   pl.BlockSpec((1, blk, d), walk.stays)],
        out_shape=[jax.ShapeDtypeStruct((b * hkv, t, d), k.dtype),
                   jax.ShapeDtypeStruct((b * hkv, t, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32),
                        pltpu.VMEM((blk, d), jnp.float32)],
        interpret=interpret, tiles=tiles, group=hq // hkv)
    return (dq.reshape(b, hq, t, d), dk.reshape(b, hkv, t, d),
            dv.reshape(b, hkv, t, d))


def forward(q, k, v, sel=None, scale=None, interpret=None, window=0,
            rule=None):
    """(out, lse [B, Hq, T, 1] float32): what ``backward`` needs kept.
    ``rule``: None, or (tokens a copy, block length) of two copies of a
    sequence side by side along T (then neither ``sel`` nor ``window``)."""
    scale, interpret = resolve(q, scale, interpret)
    return _forward(q, k, v, sel, scale, interpret, window,
                    tuple(rule) if rule else None)


def backward(q, k, v, sel, out, lse, do, scale=None, interpret=None,
             window=0, rule=None):
    """(dq, dk, dv) from the forward's own ``out`` and ``lse``."""
    scale, interpret = resolve(q, scale, interpret)
    return _backward(q, k, v, sel, out, lse, do, scale, interpret, window,
                     tuple(rule) if rule else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def sparse_flash_attention(q, k, v, sel=None, scale=None, interpret=None,
                           window=0, rule=None):
    """softmax(scale q k^T) v over the keys that count.  q: [B, Hq, T, D];
    k, v: [B, Hkv, T, D], Hq a multiple of Hkv.  Causal: every key s <= t,
    of those the ones ``sel`` selects ([B, T, T] int8, non-trainable; None:
    all) or, under ``window``, the last ``window`` only (then no ``sel``).
    Under ``rule`` = (tokens a copy, block length) T holds a clean and a
    noised copy of a sequence and the keys that count are the block
    rule's (the module's docstring), which is not causal."""
    return forward(q, k, v, sel, scale, interpret, window, rule)[0]


def _vjp_fwd(q, k, v, sel, scale, interpret, window, rule):
    out, lse = forward(q, k, v, sel, scale, interpret, window, rule)
    return out, (q, k, v, sel, out, lse)


def _vjp_bwd(scale, interpret, window, rule, res, do):
    q, k, v, sel, out, lse = res
    dq, dk, dv = backward(q, k, v, sel, out, lse, do, scale, interpret,
                          window, rule)
    dsel = None if sel is None else \
        jnp.zeros(sel.shape, jax.dtypes.float0)
    return dq, dk, dv, dsel


sparse_flash_attention.defvjp(_vjp_fwd, _vjp_bwd)
