"""Grouped-query flash attention over a per-query selection of keys, as
Pallas TPU kernels: forward, dQ and dK/dV.

A sibling of ``pallas_flash`` (equal head counts, key-padding bias) for the
decoder path: ``hq`` query heads read ``hkv = hq / group`` key-value heads
(query head h reads head ``h // group``), attention is causal, and an
optional selection ``sel`` ([B, T, T] int8, 1 where query t attends key s;
it already holds causality) masks the scores inside the kernels, so no
[H, T, T] tensor ever reaches HBM.  K/V are never repeated in HBM either:
the index maps point the ``group`` query heads of a key-value head at the
same tiles, and the dK/dV kernel walks the group's query tiles in its
sequential grid dimension and sums them in VMEM.

Contractions take their operands in the input dtype (bf16 under AMP) and
accumulate in float32; softmax statistics and accumulators are float32
VMEM scratch.  Dead causal tiles are skipped, and their index maps point
at the nearest live tile so that no DMA is issued for them.  Tiles that
hold no selected key are NOT skipped yet (a selection learned by an
indexer leaves few of them empty).

A static causal ``window`` (0: none; else key s counts for query t iff
``0 <= t - s < window``) shrinks the grids' key (for dK/dV: query) dimension
to the band: ``ceil((window - 1) / block) + 1`` tiles a row, 70 of the 136
causal tiles at 8,192 tokens and window 2,048, so a tile wholly outside the
band is no grid step at all.  Step s of query tile j walks key tile
``j - (band - 1) + s``; a negative one is dead (the first rows of the
band; for dK/dV the query tiles past the last) and maps to the nearest live
tile, as dead causal tiles do.  The tile of each step comes from a
scalar-prefetch table ``[tiles, band]`` int32: the call's first operand,
whose shape states the band to whoever counts the kernel's work from its
declared shapes.  With a window there is no selection.

Where a selection or a window makes the mask more than one compare, a live
tile is INTERIOR or EDGE, told apart from the grid position and the static
shapes (``interior_reach``, ``_by_kind``), and the kernel holds one body
for each under ``pl.when``.  Interior: every (query, key) pair of the tile
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

The kernels carry names of their own (``sparse_flash_fwd``,
``sparse_flash_dq``, ``sparse_flash_dkv``; with a window
``window_flash_fwd``, ``window_flash_dq``, ``window_flash_dkv``).
"""

from __future__ import annotations

import functools

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


def supported(q, k, sel, window=0, v=None) -> str:
    """'' when the kernels take these operands, else why not.  ``v`` (None:
    as ``k``) has k's batch, heads and length, and k's width too: one head
    width is what the kernels' tiles are cut to."""
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


def _q_side_step(q_ref, n_k, window):
    """(k step, (first query, first key), live, dist) of a grid step of
    forward and dQ; ``dist``: how many tiles the key tile lies before the
    query tile.  Without a window step s is key tile s, live up to the
    diagonal; with one it is key tile ``qi - (n_k - 1) + s`` of the band's
    ``n_k``, live from tile 0 on."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    blk = jnp.int32(q_ref.shape[1])
    if window:
        dist = jnp.int32(n_k - 1) - ki
        kt = qi - dist
        return ki, (qi * blk, kt * blk), kt >= 0, dist
    return ki, (qi * blk, ki * blk), ki <= qi, qi - ki


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


def _fwd_kernel(*refs, scale, n_k, has_sel, window=0):
    if window:
        refs = refs[1:]                 # the band's table: the index maps'
    q_ref, k_ref, v_ref, *rest = refs
    if has_sel:
        sel_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
        sel_ref = None
    ki, offsets, live, dist = _q_side_step(q_ref, n_k, window)
    reach = interior_reach(window, q_ref.shape[1], n_k, has_sel)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def attend(offsets):
        s = _scores(q_ref[0], k_ref[0], scale)
        keep = _keep(sel_ref, s.shape, offsets, window)
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

    _by_kind(live, dist, reach, offsets, attend)

    @pl.when(ki == n_k - 1)
    def _flush():
        l = jnp.maximum(l_ref[:], jnp.float32(1e-30))
        o_ref[0] = (acc_ref[:] / _lanes(l, acc_ref.shape[1])).astype(
            o_ref.dtype)
        lse_ref[0] = (m_ref[:] + jnp.log(l))[:, :1]


def _dq_kernel(*refs, scale, n_k, has_sel, window=0):
    if window:
        refs = refs[1:]
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest = refs
    if has_sel:
        sel_ref, dq_ref, dq_acc = rest
    else:
        dq_ref, dq_acc = rest
        sel_ref = None
    ki, offsets, live, dist = _q_side_step(q_ref, n_k, window)
    reach = interior_reach(window, q_ref.shape[1], n_k, has_sel)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def accum(offsets):
        k = k_ref[0]
        s = _scores(q_ref[0], k, scale)
        p = _probabilities(s, lse_ref[0],
                           _keep(sel_ref, s.shape, offsets, window))
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        dq_acc[:] += jnp.float32(scale) * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _by_kind(live, dist, reach, offsets, accum)

    @pl.when(ki == n_k - 1)
    def _flush():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, n_q, n_inner, has_sel, window=0, n_tiles=0):
    """Grid (b*hkv, k tile, group member x q tile): the K/V tile stays, the
    query tiles of every query head of the group stream past it: all
    ``n_q`` of them, or with a window the band's ``n_q`` from the diagonal
    on (those past the last of the ``n_tiles`` are dead)."""
    if window:
        refs = refs[1:]
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest = refs
    if has_sel:
        sel_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
        sel_ref = None
    kj, inner = pl.program_id(1), pl.program_id(2)
    blk = q_ref.shape[1]
    qi = jax.lax.rem(inner, jnp.int32(n_q))
    if window:
        qi = kj + qi
        live = qi <= jnp.int32(n_tiles - 1)
    else:
        live = qi >= kj
    reach = interior_reach(window, blk, n_q, has_sel)

    @pl.when(inner == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def accum(offsets):
        q, do = q_ref[0], do_ref[0]
        s = _scores(q, k_ref[0], scale)                      # [bq, bk]
        p = _probabilities(s, lse_ref[0],
                           _keep(sel_ref, s.shape, offsets, window))
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

    _by_kind(live, qi - kj, reach,
             (qi * jnp.int32(blk), kj * jnp.int32(blk)), accum)

    @pl.when(inner == n_inner - 1)
    def _flush():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _q_side_maps(hq, group):
    """Index maps of the grids (b*hq, q tile, k tile) of forward and dQ.
    A dead causal tile (k tile beyond the q tile; the tiles are square)
    maps to the diagonal one, which is already in VMEM."""
    def resident(i, j, s):
        return block_index(i, j, 0)

    def kv(i, j, s):
        return block_index(jax.lax.div(i, jnp.int32(group)),
                           jnp.minimum(s, j), 0)

    def sel(i, j, s):
        return block_index(jax.lax.div(i, jnp.int32(hq)), j,
                           jnp.minimum(s, j))

    return resident, kv, sel


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


def _call(kernel, name, table, args, *, grid, in_specs, out_specs,
          out_shape, scratch_shapes, interpret, tiles):
    """``pallas_call``; with a band's table, as its scalar-prefetch
    operand.  Counts the call's ``tiles`` (interior, edge) a head."""
    from jax.experimental.pallas import tpu as pltpu
    from .decoder_ops import _count

    for kind, count in zip(("interior", "edge"), tiles):
        _count("ops.sparse_attention.tiles", count, kernel=name, kind=kind)

    if table is None:
        return pl.pallas_call(
            kernel, out_shape=out_shape, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes,
            interpret=interpret, name=name)(*args)
    return pl.pallas_call(
        kernel, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes),
        interpret=interpret, name=name)(table, *args)


def _forward(q, k, v, sel, scale, interpret, window=0):
    from jax.experimental.pallas import tpu as pltpu

    b, hq, t, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    blk = _block(t)
    n = t // blk
    n_k, table = n, None
    if window:
        n_k = band_tiles(window, blk, n)
        table = _band_tables(n, n_k)[0]
        resident, kv, _ = _band_maps(group, n_k)
    else:
        resident, kv, sel_map = _q_side_maps(hq, group)
    in_specs = [pl.BlockSpec((1, blk, d), resident),
                pl.BlockSpec((1, blk, d), kv),
                pl.BlockSpec((1, blk, d), kv)]
    args = [q.reshape(b * hq, t, d), k.reshape(b * hkv, t, d),
            v.reshape(b * hkv, t, d)]
    if sel is not None:
        in_specs.append(pl.BlockSpec((1, blk, blk), sel_map))
        args.append(sel)
    out, lse = _call(
        functools.partial(_fwd_kernel, scale=scale, n_k=n_k,
                          has_sel=sel is not None, window=window),
        "window_flash_fwd" if window else "sparse_flash_fwd", table, args,
        grid=(b * hq, n, n_k), in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, blk, d), resident),
                   pl.BlockSpec((1, blk, 1), resident)],
        out_shape=[jax.ShapeDtypeStruct((b * hq, t, d), q.dtype),
                   jax.ShapeDtypeStruct((b * hq, t, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((blk, LANE), jnp.float32),
                        pltpu.VMEM((blk, LANE), jnp.float32),
                        pltpu.VMEM((blk, d), jnp.float32)],
        interpret=interpret, tiles=tile_counts(t, window, sel is not None))
    return out.reshape(b, hq, t, d), lse.reshape(b, hq, t, 1)


def _backward(q, k, v, sel, out, lse, do, scale, interpret, window=0):
    from jax.experimental.pallas import tpu as pltpu

    b, hq, t, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    blk = _block(t)
    n = t // blk
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    qr, dor = q.reshape(b * hq, t, d), do.reshape(b * hq, t, d)
    kr, vr = k.reshape(b * hkv, t, d), v.reshape(b * hkv, t, d)
    lser, dr = lse.reshape(b * hq, t, 1), delta.reshape(b * hq, t, 1)
    has_sel = sel is not None
    sel_args = [sel] if has_sel else []
    tiles = tile_counts(t, window, has_sel)
    band, k_table, q_table = n, None, None
    if window:
        band = band_tiles(window, blk, n)
        k_table, q_table = _band_tables(n, band)
        resident, kv, q_side = _band_maps(group, band)
    else:
        resident, kv, sel_map = _q_side_maps(hq, group)

    specs = [pl.BlockSpec((1, blk, d), resident),
             pl.BlockSpec((1, blk, d), kv),
             pl.BlockSpec((1, blk, d), kv),
             pl.BlockSpec((1, blk, d), resident),
             pl.BlockSpec((1, blk, 1), resident),
             pl.BlockSpec((1, blk, 1), resident)]
    if has_sel:
        specs.append(pl.BlockSpec((1, blk, blk), sel_map))
    dq = _call(
        functools.partial(_dq_kernel, scale=scale, n_k=band,
                          has_sel=has_sel, window=window),
        "window_flash_dq" if window else "sparse_flash_dq", k_table,
        (qr, kr, vr, dor, lser, dr, *sel_args),
        grid=(b * hq, n, band), in_specs=specs,
        out_specs=pl.BlockSpec((1, blk, d), resident),
        out_shape=jax.ShapeDtypeStruct((b * hq, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32)],
        interpret=interpret, tiles=tiles)

    # dK/dV: grid (b*hkv, k tile, group member x q tile)
    def k_side(i, j, s, *table):
        return block_index(i, j, 0)

    if not window:          # with one: q_side of _band_maps, no selection
        def q_tile(s, j):
            # a dead tile (q tile before the k tile) maps to the diagonal
            return jnp.maximum(jax.lax.rem(s, jnp.int32(n)), j)

        def q_side(i, j, s):
            head = i * jnp.int32(group) + jax.lax.div(s, jnp.int32(n))
            return block_index(head, q_tile(s, j), 0)

        def sel_side(i, j, s):
            return block_index(jax.lax.div(i, jnp.int32(hkv)),
                               q_tile(s, j), j)

    specs = [pl.BlockSpec((1, blk, d), q_side),
             pl.BlockSpec((1, blk, d), k_side),
             pl.BlockSpec((1, blk, d), k_side),
             pl.BlockSpec((1, blk, d), q_side),
             pl.BlockSpec((1, blk, 1), q_side),
             pl.BlockSpec((1, blk, 1), q_side)]
    if has_sel:
        specs.append(pl.BlockSpec((1, blk, blk), sel_side))
    dk, dv = _call(
        functools.partial(_dkv_kernel, scale=scale, n_q=band,
                          n_inner=group * band, has_sel=has_sel,
                          window=window, n_tiles=n),
        "window_flash_dkv" if window else "sparse_flash_dkv", q_table,
        (qr, kr, vr, dor, lser, dr, *sel_args),
        grid=(b * hkv, n, group * band), in_specs=specs,
        out_specs=[pl.BlockSpec((1, blk, d), k_side),
                   pl.BlockSpec((1, blk, d), k_side)],
        out_shape=[jax.ShapeDtypeStruct((b * hkv, t, d), k.dtype),
                   jax.ShapeDtypeStruct((b * hkv, t, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32),
                        pltpu.VMEM((blk, d), jnp.float32)],
        interpret=interpret, tiles=tiles)
    return (dq.reshape(b, hq, t, d), dk.reshape(b, hkv, t, d),
            dv.reshape(b, hkv, t, d))


def forward(q, k, v, sel=None, scale=None, interpret=None, window=0):
    """(out, lse [B, Hq, T, 1] float32): what ``backward`` needs kept."""
    scale, interpret = resolve(q, scale, interpret)
    return _forward(q, k, v, sel, scale, interpret, window)


def backward(q, k, v, sel, out, lse, do, scale=None, interpret=None,
             window=0):
    """(dq, dk, dv) from the forward's own ``out`` and ``lse``."""
    scale, interpret = resolve(q, scale, interpret)
    return _backward(q, k, v, sel, out, lse, do, scale, interpret, window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def sparse_flash_attention(q, k, v, sel=None, scale=None, interpret=None,
                           window=0):
    """Causal softmax(scale q k^T) v over the keys ``sel`` selects.  q:
    [B, Hq, T, D]; k, v: [B, Hkv, T, D], Hq a multiple of Hkv; sel: None
    (every key s <= t) or [B, T, T] int8, non-trainable; ``window``: 0, or
    the last ``window`` keys ``s <= t`` only (then no ``sel``)."""
    return forward(q, k, v, sel, scale, interpret, window)[0]


def _vjp_fwd(q, k, v, sel, scale, interpret, window):
    out, lse = forward(q, k, v, sel, scale, interpret, window)
    return out, (q, k, v, sel, out, lse)


def _vjp_bwd(scale, interpret, window, res, do):
    q, k, v, sel, out, lse = res
    dq, dk, dv = backward(q, k, v, sel, out, lse, do, scale, interpret,
                          window)
    dsel = None if sel is None else \
        jnp.zeros(sel.shape, jax.dtypes.float0)
    return dq, dk, dv, dsel


sparse_flash_attention.defvjp(_vjp_fwd, _vjp_bwd)
