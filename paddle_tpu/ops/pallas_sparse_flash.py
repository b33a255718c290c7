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

The kernels carry names of their own (``sparse_flash_fwd``,
``sparse_flash_dq``, ``sparse_flash_dkv``; with a window
``window_flash_fwd``, ``window_flash_dq``, ``window_flash_dkv``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_flash import NEG_INF, block_index

BLOCK = 512


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


def supported(q, k, sel, window=0) -> str:
    """'' when the kernels take these operands, else why not."""
    b, hq, t, d = q.shape
    if window and sel is not None:
        return "window_selection"
    if k.shape[0] != b or k.shape[2] != t or k.shape[3] != d:
        return "shape"
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


def _keep(sel_ref, shape, q_off, k_off, window=0):
    """[bq, bk] bool: the pairs of this tile that count."""
    qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    kpos = k_off + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    keep = qpos >= kpos
    if window:
        keep = jnp.logical_and(keep, qpos - kpos < jnp.int32(window))
    if sel_ref is not None:
        keep = jnp.logical_and(keep, sel_ref[0].astype(jnp.float32) > 0.5)
    return keep


def _q_side_step(q_ref, k_ref, n_k, window):
    """(k step, q offset, k offset, live) of a grid step of forward and dQ;
    ``live()`` says whether the step's tile holds a pair that counts.
    Without a window step s is key tile s, live up to the diagonal; with
    one it is key tile ``qi - (n_k - 1) + s`` of the band's ``n_k``, live
    from tile 0 on.  ``live`` is a thunk so that its compare is traced where
    the caller's ``pl.when`` stands, as it was before there was a window."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    if window:
        kt = qi - jnp.int32(n_k - 1) + ki
        return ki, qi * jnp.int32(bq), kt * jnp.int32(bk), lambda: kt >= 0
    q_off, k_off = qi * jnp.int32(bq), ki * jnp.int32(bk)
    return ki, q_off, k_off, lambda: k_off <= q_off + jnp.int32(bq - 1)


def _fwd_kernel(*refs, scale, n_k, has_sel, window=0):
    if window:
        refs = refs[1:]                 # the band's table: the index maps'
    q_ref, k_ref, v_ref, *rest = refs
    if has_sel:
        sel_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
        sel_ref = None
    ki, q_off, k_off, live = _q_side_step(q_ref, k_ref, n_k, window)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(live())
    def _attend():
        s = _scores(q_ref[0], k_ref[0], scale)
        keep = _keep(sel_ref, s.shape, q_off, k_off, window)
        s = jnp.where(keep, s, jnp.float32(NEG_INF))
        m = m_ref[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        # a row may have no selected key in this tile: exp(-inf - -inf)
        p = jnp.where(keep, jnp.exp(s - m_new), jnp.float32(0.0))
        corr = jnp.exp(m - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _flush():
        l = jnp.maximum(l_ref[:], jnp.float32(1e-30))
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(l)


def _dq_kernel(*refs, scale, n_k, has_sel, window=0):
    if window:
        refs = refs[1:]
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest = refs
    if has_sel:
        sel_ref, dq_ref, dq_acc = rest
    else:
        dq_ref, dq_acc = rest
        sel_ref = None
    ki, q_off, k_off, live = _q_side_step(q_ref, k_ref, n_k, window)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(live())
    def _accum():
        k = k_ref[0]
        s = _scores(q_ref[0], k, scale)
        keep = _keep(sel_ref, s.shape, q_off, k_off, window)
        p = jnp.where(keep, jnp.exp(s - lse_ref[0]), jnp.float32(0.0))
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        dq_acc[:] += jnp.float32(scale) * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

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
    qi = jax.lax.rem(inner, jnp.int32(n_q))
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    if window:
        qi = kj + qi
    q_off, k_off = qi * jnp.int32(bq), kj * jnp.int32(bk)

    def live():
        if window:
            return qi <= jnp.int32(n_tiles - 1)
        return q_off + jnp.int32(bq - 1) >= k_off

    @pl.when(inner == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(live())
    def _accum():
        q, do = q_ref[0], do_ref[0]
        s = _scores(q, k_ref[0], scale)                      # [bq, bk]
        keep = _keep(sel_ref, s.shape, q_off, k_off, window)
        p = jnp.where(keep, jnp.exp(s - lse_ref[0]), jnp.float32(0.0))
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
          out_shape, scratch_shapes, interpret):
    """``pallas_call``; with a band's table, as its scalar-prefetch
    operand."""
    from jax.experimental.pallas import tpu as pltpu

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
        scratch_shapes=[pltpu.VMEM((blk, 1), jnp.float32),
                        pltpu.VMEM((blk, 1), jnp.float32),
                        pltpu.VMEM((blk, d), jnp.float32)],
        interpret=interpret)
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
        interpret=interpret)

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
        interpret=interpret)
    return (dq.reshape(b, hq, t, d), dk.reshape(b, hkv, t, d),
            dv.reshape(b, hkv, t, d))


def _resolve(q, scale, interpret):
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return scale, interpret


def forward(q, k, v, sel=None, scale=None, interpret=None, window=0):
    """(out, lse [B, Hq, T, 1] float32): what ``backward`` needs kept."""
    scale, interpret = _resolve(q, scale, interpret)
    return _forward(q, k, v, sel, scale, interpret, window)


def backward(q, k, v, sel, out, lse, do, scale=None, interpret=None,
             window=0):
    """(dq, dk, dv) from the forward's own ``out`` and ``lse``."""
    scale, interpret = _resolve(q, scale, interpret)
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
