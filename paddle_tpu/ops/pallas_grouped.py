"""Grouped matrix products as Pallas TPU kernels: rows sorted by group, each
group multiplied by its own weights (``lax.ragged_dot``'s contract), and the
weights' gradient of that product.

Three forms, two kernels, each under a name of its own:

- ``grouped_matmul(rows [M, K], weights [G, K, N], sizes [G]) -> [M, N]``
  and, with ``transpose=True``, the same weights contracted over their LAST
  axis (``rows [M, N] x weights [G, K, N] -> [M, K]``: the rows' cotangent,
  read from the weights as they lie in HBM, no transposed copy): kernel
  ``grouped_matmul``.
- ``grouped_matmul_t(rows [M, K], cot [M, N], sizes [G]) -> [G, K, N]``:
  the weights' gradient, accumulated in float32 in VMEM over a group's row
  tiles and written once a group; a group of no rows gives zeros: kernel
  ``grouped_matmul_t``.

The point is the tile.  A grid step takes the WHOLE contraction (no
accumulator is revisited across steps of ``grouped_matmul``) and as much of
the result's width as the VMEM budget holds, so a row tile is read once (or
once a column tile) and a group's weights are fetched once: with the whole
of K in the tile the weights' block index changes only where the group
does.  ``tile(...)`` derives the tile from the shapes and ``VMEM_BUDGET``
alone, and the budget is what Mosaic allows a kernel by default: a kernel
that states a larger ``vmem_limit_bytes`` ran alone and hung inside a
training step (PERF.md, Findings PR 37).  Inside a step the tile's product
is made ``COLUMN_CHUNK`` result columns at a time in a loop: Mosaic unrolls
a product, and a body that held the whole tile's (three times over, once
for each way to store it) added 92 MB of code to a step of 48 calls.

The column tile covers the width in the FEWEST tiles that fit and need not
divide it: at 1,408 = 11 x 128 the only divisor that fits is one lane row,
and every product read its rows eleven times (PERF.md, Findings PR 42).
The grid has ``cdiv(n, tn)`` column tiles and the last is ragged.  Pallas
reads a block that runs past its array with unspecified values there and
drops what is written there.  That is safe here ONLY because no value
crosses columns: a result column is a function of its own weight (or
cotangent) column, the selects and the float32 accumulator are column-wise,
and nothing is reduced over a tile's width; a change that sums over a
tile's columns has to mask them.  The last tile's chunk loop stops at the
result's edge (``_chunks``), so nothing past it is multiplied either.
``ops.moe.column_tiles{kernel,width,tile,tiles,ragged}`` counts the tile of
every kernel call traced.

Group boundaries come from a table made in ``jnp`` (``visits``; ``plan``
makes it once for the calls that share their groups), the calls'
scalar-prefetch operands: for each step of the grid's last dimension its
row tile and its group, beside the groups' first rows.  A row
tile that holds rows of several groups is visited once for each of them, in
turn, with the other groups' rows masked; at most ``G - 1`` visits more than
there are row tiles, so the grid is static.  Rows past the last group are
multiplied by nothing and WRITTEN AS ZEROS (XLA's own grouped product on
the TPU leaves them unwritten).  Everything is int32: the package runs jax
in x64 mode and Mosaic refuses 64-bit index maps.

Operands are contracted in the type they arrive in (bf16 under AMP) with
float32 accumulation; results have the operands' type.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import kernel_choice
from .pallas_flash import block_index

#: what a kernel's blocks, double-buffered, and its float32 working set may
#: take of VMEM: under the 16 MiB that Mosaic allows a kernel by default.  No
#: ``vmem_limit_bytes`` is stated: XLA keeps values of its own in VMEM across
#: the calls of a step (index arrays, prefetched operands), laid out around
#: the default scoped region
VMEM_BUDGET = 14 << 20
ROW_TILE = 512
#: result columns of the one product a kernel's body holds
COLUMN_CHUNK = 512
LANE = 128


def supported(rows, weights) -> str:
    """'' when the kernels take a product of ``rows`` [M, .] with
    ``weights`` [G, K, N] in all three forms, else why not."""
    if rows.ndim != 2 or weights.ndim != 3:
        return "rank"
    if rows.dtype != weights.dtype or \
            rows.dtype not in (jnp.bfloat16, jnp.float32):
        return "dtype"
    if weights.shape[1] % LANE or weights.shape[2] % LANE:
        return "lanes"
    if rows.shape[0] % ROW_TILE:
        return "rows"
    if rows.shape[1] not in weights.shape[1:]:
        return "shape"
    return ""


def _chunk(tn, n):
    """Columns of one product in a kernel's body: a divisor of the tile's
    and of what the LAST tile holds of a width ``n`` (all of it where the
    tile divides ``n``), so that no chunk lies across the result's edge and
    nothing past it is multiplied."""
    return math.gcd(math.gcd(tn, n % tn), COLUMN_CHUNK)


def tile(m, k, n, itemsize, transposed_result=False):
    """(row tile, column tile) of a product that contracts ``k`` whole and
    gives a result ``n`` wide (``transposed_result``: ``[k, n]`` a group
    from ``[m, k]`` and ``[m, n]`` rows).  The column tile, a multiple of
    the lane width, that covers ``n`` in the FEWEST tiles that fit
    ``VMEM_BUDGET``, whether or not it divides ``n`` (the last tile is then
    ragged): two buffers of every block that moves, the float32 product of
    one column chunk, and for the transposed result its float32 accumulator
    and the masked copy of the rows.  The transposed result keeps the
    widest tile that DIVIDES ``n`` wherever one of two lane rows or more
    fits, and takes the ragged one only where the divisors leave a single
    lane row: its accumulator grows with the tile, and at 1,024 to 2,048
    columns three to six ragged tiles of 384 or 768 ran 8 to 10% slower on
    the chip than four to eight that divide (PERF.md, Findings PR 42)."""
    tm = min(ROW_TILE, m)
    lanes = n // LANE

    def fits(tn):
        width = _chunk(tn, n)
        blocks = 2 * itemsize * (tm * k + k * tn + tm * tn)
        if transposed_result:
            work = 4 * k * tn + itemsize * tm * k + 4 * k * width
        else:
            work = (4 + itemsize) * tm * width
        return blocks + work <= VMEM_BUDGET

    fitting = [tn for tn in (LANE * pl.cdiv(lanes, parts)
                             for parts in range(1, lanes + 1)) if fits(tn)]
    if transposed_result:
        dividing = next((tn for tn in fitting if n % tn == 0), LANE)
        if dividing > LANE:
            return tm, dividing
    return tm, fitting[0] if fitting else LANE


def visits(sizes, m, tm, empty_groups=False):
    """(first rows [G + 1], row tile [V], group [V]) int32, V = tiles + G -
    1: the row tile and the group of each grid step.  A group is visited
    once for every row tile it has rows in, groups in order; then every row
    tile that lies wholly past the last group, once, under the last group
    (which has no row there: the step writes zeros); what is left of V
    repeats the last visit and is skipped.  ``empty_groups``: a group
    without rows is visited once too (its gradient is written as zeros),
    and the tiles past the last group are not."""
    i32 = jnp.int32
    g = sizes.shape[0]
    n_tiles = m // tm
    sizes = sizes.astype(i32)
    ends = jnp.cumsum(sizes, dtype=i32)
    starts = ends - sizes
    first = starts // i32(tm)
    count = jnp.where(sizes > 0, (ends - 1) // i32(tm) - first + 1,
                      i32(1 if empty_groups else 0))
    covered = (ends[-1] + i32(tm - 1)) // i32(tm)
    past = jnp.maximum(i32(0 if empty_groups else n_tiles) - covered, 0)
    count = jnp.concatenate([count, past[None]])
    first = jnp.concatenate([first, covered[None]])
    upto = jnp.cumsum(count, dtype=i32)
    step = jnp.minimum(jnp.arange(n_tiles + g - 1, dtype=i32),
                       jnp.maximum(upto[-1] - 1, 0))
    which = jnp.sum((upto[None, :] <= step[:, None]).astype(i32), axis=1,
                    dtype=i32)
    tiles = first[which] + step - (upto - count)[which]
    return (jnp.concatenate([jnp.zeros((1,), i32), ends]),
            jnp.clip(tiles, 0, n_tiles - 1).astype(i32),
            jnp.minimum(which, i32(g - 1)).astype(i32))


def _step(offsets, tiles, groups, tm):
    """Of this grid step: (its group's rows as a [tm, 1] mask over the row
    tile, whether it multiplies anything, whether the step before was at
    the same tile)."""
    i32 = jnp.int32
    v = pl.program_id(1)
    before = jnp.maximum(v - 1, 0)
    t, g = tiles[v], groups[v]
    same_tile = jnp.logical_and(v > 0, tiles[before] == t)
    repeat = jnp.logical_and(same_tile, groups[before] == g)
    lo, hi = offsets[g], offsets[g + 1]
    row0 = t * i32(tm)
    live = jnp.logical_and(
        jnp.logical_and(hi > jnp.maximum(lo, row0), lo < row0 + i32(tm)),
        jnp.logical_not(repeat))
    row = row0 + jax.lax.broadcasted_iota(i32, (tm, 1), 0)
    mine = jnp.logical_and(row >= lo, row < hi)
    return mine, live, same_tile


def _columns(j, width):
    return pl.ds(pl.multiple_of(j * width, width), width)


def _chunks(tn, n):
    """(columns of one product, products) of this grid step's column tile:
    in the last tile of a width ``n`` that the tile does not divide, only
    the chunks that hold a column of the result."""
    width = _chunk(tn, n)
    if n % tn == 0:
        return width, tn // width
    return width, jnp.where(pl.program_id(0) == n // tn,
                            jnp.int32(n % tn // width),
                            jnp.int32(tn // width))


def _matmul_kernel(offsets, tiles, groups, rows_ref, w_ref, out_ref, *,
                   transpose, n):
    # ONE product of COLUMN_CHUNK result columns in the body, looped over
    # the tile's columns (Mosaic unrolls a product: its code is what a call
    # adds to the program in HBM), selected into place whatever the step:
    # the group's rows from the product, the others from what an earlier
    # group of the same tile left, or zeros where this is the tile's first
    mine, live, same_tile = _step(offsets, tiles, groups, rows_ref.shape[0])
    width, n_chunks = _chunks(out_ref.shape[1], n)

    @pl.when(live)
    def _groups_rows():
        def chunk(j, carry):
            cols = _columns(j, width)
            w = w_ref[0, cols, :] if transpose else w_ref[0, :, cols]
            product = jax.lax.dot_general(
                rows_ref[...], w,
                (((1,), (1 if transpose else 0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(out_ref.dtype)
            others = jnp.where(same_tile, out_ref[:, cols], 0)
            out_ref[:, cols] = jnp.where(mine, product, others)
            return carry

        jax.lax.fori_loop(jnp.int32(0), jnp.int32(n_chunks), chunk, 0)

    @pl.when(jnp.logical_and(jnp.logical_not(live),
                             jnp.logical_not(same_tile)))
    def _rows_of_no_group():
        out_ref[...] = jnp.zeros_like(out_ref)


def _matmul_t_kernel(offsets, tiles, groups, rows_ref, cot_ref, out_ref,
                     acc_ref, *, n_visits, n):
    i32 = jnp.int32
    v = pl.program_id(1)
    g = groups[v]
    mine, live, _ = _step(offsets, tiles, groups, rows_ref.shape[0])
    width, n_chunks = _chunks(out_ref.shape[2], n)

    @pl.when(jnp.logical_or(v == 0, groups[jnp.maximum(v - 1, 0)] != g))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _groups_rows():
        # both selected, never multiplied by zero: a row past the last
        # group may hold anything, in either operand
        rows = jnp.where(mine, rows_ref[...], 0)

        def chunk(j, carry):
            cols = _columns(j, width)
            acc_ref[:, cols] += jax.lax.dot_general(
                rows, jnp.where(mine, cot_ref[:, cols], 0),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(jnp.int32(0), jnp.int32(n_chunks), chunk, 0)

    @pl.when(jnp.logical_or(
        v == n_visits - 1,
        groups[jnp.minimum(v + 1, i32(n_visits - 1))] != g))
    def _flush():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def _count_tiles(kernel, m, k, n, itemsize):
    from .decoder_ops import _count

    tn = tile(m, k, n, itemsize, kernel == "grouped_matmul_t")[1]
    _count("ops.moe.column_tiles", kernel=kernel, width=n, tile=tn,
           tiles=pl.cdiv(n, tn), ragged=int(n % tn > 0))


def _row_block(j, v, offsets, tiles, groups):
    return block_index(tiles[v], 0)


def _params():
    # column tiles in any order; a tile's steps in the table's
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def plan(sizes, m):
    """The visit tables of every product over ``m`` rows in groups of
    ``sizes`` and of those products' weights' gradients (``visits``), as
    the kernels take them for ``plan``: made once where a dozen calls share
    the groups, as an expert layer's do."""
    tm = min(ROW_TILE, m)
    return visits(sizes, m, tm), visits(sizes, m, tm, empty_groups=True)


def grouped_matmul(rows, weights, sizes, transpose=False, interpret=None,
                   plan=None):
    """``rows[group g's rows] @ weights[g]`` (``transpose``: ``@
    weights[g].T``) for every group, rows past the last group zeros."""
    m = rows.shape[0]
    _count_tiles("grouped_matmul", *rows.shape,
                 weights.shape[1 if transpose else 2], rows.dtype.itemsize)
    table = visits(sizes, m, min(ROW_TILE, m)) if plan is None else plan[0]
    return _matmul(rows, weights, table, transpose,
                   kernel_choice.interpret(interpret))


def grouped_matmul_t(rows, cot, sizes, interpret=None, plan=None):
    """[G, K, N]: for every group ``rows[its rows].T @ cot[its rows]``,
    zeros for a group of no rows."""
    m = rows.shape[0]
    _count_tiles("grouped_matmul_t", *rows.shape, cot.shape[1],
                 rows.dtype.itemsize)
    table = visits(sizes, m, min(ROW_TILE, m), empty_groups=True) \
        if plan is None else plan[1]
    return _matmul_t(rows, cot, table, kernel_choice.interpret(interpret))


# jitted: a step calls each form a dozen times a layer with the same
# shapes, and under ``jit`` the kernel is traced once and lowered to Mosaic
# once a program, not once a call (15 s of a decoder cell's set-up)
@functools.partial(jax.jit, static_argnums=(3, 4), inline=True)
def _matmul(rows, weights, table, transpose, interpret):
    from jax.experimental.pallas import tpu as pltpu

    m, k = rows.shape
    n = weights.shape[1 if transpose else 2]
    tm, tn = tile(m, k, n, rows.dtype.itemsize)

    def weight_block(j, v, offsets, tiles, groups):
        return block_index(groups[v], j, 0) if transpose \
            else block_index(groups[v], 0, j)

    def out_block(j, v, offsets, tiles, groups):
        return block_index(tiles[v], j)

    return pl.pallas_call(
        functools.partial(_matmul_kernel, transpose=transpose, n=n),
        out_shape=jax.ShapeDtypeStruct((m, n), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(n, tn), table[1].shape[0]),
            in_specs=[pl.BlockSpec((tm, k), _row_block),
                      pl.BlockSpec((1, tn, k) if transpose else (1, k, tn),
                                   weight_block)],
            out_specs=pl.BlockSpec((tm, tn), out_block)),
        compiler_params=_params(), interpret=interpret,
        name="grouped_matmul",
    )(*table, rows, weights)


@functools.partial(jax.jit, static_argnums=(3,), inline=True)
def _matmul_t(rows, cot, table, interpret):
    from jax.experimental.pallas import tpu as pltpu

    m, k = rows.shape
    n = cot.shape[1]
    tm, tn = tile(m, k, n, rows.dtype.itemsize, transposed_result=True)
    n_visits = table[1].shape[0]

    def cot_block(j, v, offsets, tiles, groups):
        return block_index(tiles[v], j)

    def out_block(j, v, offsets, tiles, groups):
        return block_index(groups[v], 0, j)

    return pl.pallas_call(
        functools.partial(_matmul_t_kernel, n_visits=n_visits, n=n),
        out_shape=jax.ShapeDtypeStruct((table[0].shape[0] - 1, k, n),
                                       rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(pl.cdiv(n, tn), n_visits),
            in_specs=[pl.BlockSpec((tm, k), _row_block),
                      pl.BlockSpec((tm, tn), cot_block)],
            out_specs=pl.BlockSpec((1, k, tn), out_block),
            scratch_shapes=[pltpu.VMEM((k, tn), jnp.float32)]),
        compiler_params=_params(), interpret=interpret,
        name="grouped_matmul_t",
    )(*table, rows, cot)
