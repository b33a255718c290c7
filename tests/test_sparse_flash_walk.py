"""The causal attention kernels' grid (``ops/pallas_sparse_flash.py``): the
live tiles alone, the triangle folded into (pairs, steps a pair).  The
walk as a table, the three kernels bit for bit against the rectangular grid
with dead steps that they replaced, and the counter of the steps laid."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from decoder_reference import counters
from paddle_tpu.ops import pallas_sparse_flash as psf
from paddle_tpu.ops.pallas_flash import block_index


@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("n", [1, 2, 3, 16])
def test_the_causal_walk_is_every_live_tile_once_query_major(n, group):
    """Forward and dQ: a query tile's steps lie together, its key tiles in
    turn from 0 to the diagonal one, FIRST and LAST at the ends, the
    diagonal tile the edge one; no step is dead, whatever the group."""
    table = psf.causal_walk(n, group)
    steps = n * (n + 1) // 2
    assert table.shape == (5, steps) == (5, np.prod(psf.causal_steps(n)))
    stays, streams = table[psf.RESIDENT], table[psf.STREAMED]
    assert sorted(zip(stays, streams)) == \
        [(j, s) for j in range(n) for s in range(j + 1)]
    starts = np.flatnonzero(np.r_[1, np.diff(stays)])
    assert len(starts) == n                     # one run a query tile
    for at, end in zip(starts, np.r_[starts[1:], steps]):
        assert list(streams[at:end]) == list(range(stays[at] + 1))
    assert list(np.flatnonzero(table[psf.FLAGS] & psf.FIRST)) == list(starts)
    assert list(np.flatnonzero(table[psf.FLAGS] & psf.LAST)) == \
        list(np.r_[starts[1:], steps] - 1)
    assert not table[psf.MEMBER].any()
    # one body masks every tile; under a selection the diagonal one alone
    assert np.all(table[psf.STEP_MASK] == psf.EDGE_LE)
    kinds = psf.causal_walk(n, group, selected=True)[psf.STEP_MASK]
    assert np.array_equal(kinds != psf.INTERIOR, stays == streams)
    assert ((kinds == psf.INTERIOR).sum(), (kinds != psf.INTERIOR).sum()) \
        == psf.tile_counts(n * psf.BLOCK, 0, True)


@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("n", [1, 2, 3, 16])
def test_the_causal_walk_by_key_is_key_major_the_groups_heads_in_turn(
        n, group):
    """dK/dV: a key tile's steps lie together, and in them the query heads
    of the group one after the other, each walking the query tiles from the
    diagonal one to the last: the order the rectangular grid summed in."""
    table = psf.causal_walk(n, group, by_key=True, selected=True)
    steps = group * n * (n + 1) // 2
    assert table.shape == (5, steps) == \
        (5, np.prod(psf.causal_steps(n, group)))
    stays = table[psf.RESIDENT]
    starts = np.flatnonzero(np.r_[1, np.diff(stays)])
    assert len(starts) == n and sorted(stays[starts]) == list(range(n))
    for at, end in zip(starts, np.r_[starts[1:], steps]):
        key = stays[at]
        assert list(zip(table[psf.MEMBER, at:end],
                        table[psf.STREAMED, at:end])) == \
            [(g, q) for g in range(group) for q in range(key, n)]
    assert list(np.flatnonzero(table[psf.FLAGS] & psf.FIRST)) == list(starts)
    assert list(np.flatnonzero(table[psf.FLAGS] & psf.LAST)) == \
        list(np.r_[starts[1:], steps] - 1)
    assert np.array_equal(table[psf.STEP_MASK] != psf.INTERIOR,
                          stays == table[psf.STREAMED])


# -- the grid these kernels had: (heads, n, n), dead steps and all ---------

def _rectangular(q, k, v, sel, do):
    """(out, lse, dq, dk, dv) over grids (b*hq, q tile, k tile) and (b*hkv,
    k tile, group member x q tile) with a ``live`` test in the body and a
    dead step's index maps at the diagonal tile, from the module's own tile
    bodies: what ``_forward`` and ``_backward`` laid before the fold."""
    b, hq, t, d = q.shape
    hkv = k.shape[1]
    group, blk = hq // hkv, psf._block(t)
    n = t // blk
    scale = d ** -0.5
    has_sel = sel is not None

    def by_kind(live, qt, kt, sel_ref, body):
        offsets = qt * jnp.int32(blk), kt * jnp.int32(blk)
        if not has_sel:
            pl.when(live)(lambda: body(
                lambda shape: psf._keep(None, shape, offsets)))
            return
        pl.when(jnp.logical_and(live, qt != kt))(lambda: body(
            lambda shape: psf._keep(sel_ref, shape, None)))
        pl.when(jnp.logical_and(live, qt == kt))(lambda: body(
            lambda shape: psf._keep(sel_ref, shape, offsets)))

    def fwd(q_ref, k_ref, v_ref, *rest):
        sel_ref = rest[0] if has_sel else None
        o_ref, lse_ref, m_ref, l_ref, acc_ref = rest[has_sel:]
        qi, ki = pl.program_id(1), pl.program_id(2)
        pl.when(ki == 0)(lambda: psf._fwd_init(m_ref, l_ref, acc_ref))
        by_kind(ki <= qi, qi, ki, sel_ref, lambda keep_of: psf._attend(
            q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, scale, keep_of))
        pl.when(ki == n - 1)(lambda: psf._fwd_flush(
            o_ref, lse_ref, m_ref, l_ref, acc_ref))

    def dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest):
        sel_ref = rest[0] if has_sel else None
        dq_ref, dq_acc = rest[has_sel:]
        qi, ki = pl.program_id(1), pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            dq_acc[:] = jnp.zeros_like(dq_acc)

        by_kind(ki <= qi, qi, ki, sel_ref, lambda keep_of: psf._dq_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_acc, scale,
            keep_of))

        @pl.when(ki == n - 1)
        def _flush():
            dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)

    def dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest):
        sel_ref = rest[0] if has_sel else None
        dk_ref, dv_ref, dk_acc, dv_acc = rest[has_sel:]
        kj, inner = pl.program_id(1), pl.program_id(2)
        qi = jax.lax.rem(inner, jnp.int32(n))

        @pl.when(inner == 0)
        def _init():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

        by_kind(qi >= kj, qi, kj, sel_ref, lambda keep_of: psf._dkv_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_acc, dv_acc,
            scale, keep_of))

        @pl.when(inner == group * n - 1)
        def _flush():
            dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    def resident(i, j, s):
        return block_index(i, j, 0)

    def kv(i, j, s):
        return block_index(i // group, jnp.minimum(s, j), 0)

    def sel_map(i, j, s):
        return block_index(i // hq, j, jnp.minimum(s, j))

    def q_tile(s, j):
        return jnp.maximum(s % n, j)

    def q_side(i, j, s):
        return block_index(i * group + s // n, q_tile(s, j), 0)

    def sel_side(i, j, s):
        return block_index(i // hkv, q_tile(s, j), j)

    def specs(q_map, kv_map, sel_map, backward=True):
        wide = functools.partial(pl.BlockSpec, (1, blk, d))
        column = functools.partial(pl.BlockSpec, (1, blk, 1))
        return [wide(q_map), wide(kv_map), wide(kv_map)] \
            + ([wide(q_map), column(q_map), column(q_map)] if backward
               else []) \
            + ([pl.BlockSpec((1, blk, blk), sel_map)] if has_sel else [])

    call = functools.partial(pl.pallas_call, interpret=True)
    qr, dor = q.reshape(b * hq, t, d), do.reshape(b * hq, t, d)
    kr, vr = k.reshape(b * hkv, t, d), v.reshape(b * hkv, t, d)
    sels = (sel,) if has_sel else ()
    acc = pltpu.VMEM((blk, d), jnp.float32)
    lanes = pltpu.VMEM((blk, psf.LANE), jnp.float32)
    out, lse = call(
        fwd, grid=(b * hq, n, n),
        in_specs=specs(resident, kv, sel_map, backward=False),
        out_specs=[pl.BlockSpec((1, blk, d), resident),
                   pl.BlockSpec((1, blk, 1), resident)],
        out_shape=[jax.ShapeDtypeStruct((b * hq, t, d), q.dtype),
                   jax.ShapeDtypeStruct((b * hq, t, 1), jnp.float32)],
        scratch_shapes=[lanes, lanes, acc])(qr, kr, vr, *sels)
    delta = jnp.sum(do.astype(jnp.float32)
                    * out.reshape(q.shape).astype(jnp.float32),
                    axis=-1, keepdims=True).reshape(b * hq, t, 1)
    args = (qr, kr, vr, dor, lse, delta, *sels)
    dq = call(
        dq_kernel, grid=(b * hq, n, n), in_specs=specs(resident, kv, sel_map),
        out_specs=pl.BlockSpec((1, blk, d), resident),
        out_shape=jax.ShapeDtypeStruct((b * hq, t, d), q.dtype),
        scratch_shapes=[acc])(*args)
    dk, dv = call(
        dkv_kernel, grid=(b * hkv, n, group * n),
        in_specs=specs(q_side, resident, sel_side),
        out_specs=[pl.BlockSpec((1, blk, d), resident)] * 2,
        out_shape=[jax.ShapeDtypeStruct((b * hkv, t, d), k.dtype)] * 2,
        scratch_shapes=[acc, acc])(*args)
    return (out.reshape(q.shape), lse.reshape(b, hq, t, 1),
            dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))


@pytest.mark.parametrize("selected", [False, True])
@pytest.mark.parametrize("d,group,tiles,dtype", [
    (64, 4, 4, jnp.bfloat16), (128, 8, 4, jnp.bfloat16),
    (256, 2, 4, jnp.bfloat16), (128, 1, 5, jnp.float32),
    (64, 3, 3, jnp.float32), (128, 4, 1, jnp.bfloat16)])
def test_the_folded_grid_gives_the_rectangular_grids_bits(
        monkeypatch, d, group, tiles, dtype, selected):
    """Output, log-sum-exp, dQ, dK and dV of the walked kernels equal, bit
    for bit, what the grid with dead steps gave: every live tile's
    arithmetic is the same and each accumulator sums in the same order.
    Head widths 64 / 128 / 256, an even and an odd number of tiles and one,
    groups of 1 to 8 and one that is no power of two."""
    monkeypatch.setattr(psf, "BLOCK", 16)
    hkv, t = 2, 16 * tiles
    rng = np.random.RandomState(d + group + tiles)
    q, do = (jnp.asarray(rng.randn(2, group * hkv, t, d), dtype)
             for _ in range(2))
    k, v = (jnp.asarray(rng.randn(2, hkv, t, d), dtype) for _ in range(2))
    sel = jnp.asarray(rng.rand(2, t, t) < 0.5, jnp.int8) if selected else None

    def walked(q, k, v, sel, do):
        out, lse = psf.forward(q, k, v, sel, None, True)
        return (out, lse) + psf.backward(q, k, v, sel, out, lse, do, None,
                                         True)

    want = jax.jit(_rectangular)(q, k, v, sel, do)
    got = jax.jit(walked)(q, k, v, sel, do)
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), got, want):
        assert a.dtype == b.dtype and jnp.array_equal(a, b), name
    assert float(jnp.abs(got[0].astype(jnp.float32)).max()) > 0.5


@pytest.mark.parametrize("t,window,rule,selected", [
    (64, 0, None, True), (64, 0, None, False), (80, 0, None, True),
    (16, 0, None, False), (192, 64, None, False), (128, 0, (64, 4), False)])
def test_the_steps_a_grid_lays_are_counted_beside_its_tiles(
        monkeypatch, t, window, rule, selected):
    """``ops.sparse_attention.grid_steps{kernel}``: the steps a query head
    of the grid each kernel call lays.  The causal kernels and the block
    rule's lay their live tiles alone, so steps equal the ``tiles`` of both
    kinds; a band still lays its first rows' dead steps."""
    monkeypatch.setattr(psf, "BLOCK", 16)
    q = jnp.ones((1, 4, t, 128), jnp.bfloat16)
    k = jnp.ones((1, 2, t, 128), jnp.bfloat16)
    sel = jnp.ones((1, t, t), jnp.int8) if selected else None
    jax.jit(jax.grad(lambda q: psf.sparse_flash_attention(
        q, k, k, sel, None, True, window, rule).astype(jnp.float32).sum())
        ).lower(q)
    family = "blockdiff" if rule else "window" if window else "sparse"
    steps = counters("ops.sparse_attention.grid_steps")
    tiles = counters("ops.sparse_attention.tiles")
    assert sorted(steps) == [
        f'ops.sparse_attention.grid_steps{{kernel="{family}_flash_{kernel}"}}'
        for kernel in ("dkv", "dq", "fwd")]
    for kernel in ("fwd", "dq", "dkv"):
        name = f'kernel="{family}_flash_{kernel}"'
        live = sum(v for key, v in tiles.items() if name in key)
        laid = steps[f"ops.sparse_attention.grid_steps{{{name}}}"]
        if window:
            n = t // 16
            assert laid == n * psf.band_tiles(window, 16, n) > live
        else:
            assert laid == live
