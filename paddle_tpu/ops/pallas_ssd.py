"""The selective state-space scan (``ops/ssd.py``) as Pallas TPU kernels,
forward and backward.  The equations are that module's docstring's, letter
for letter; what is here is where a chunk's arrays live.

One grid step is STEP chunks of 128 tokens of one GROUP (the ``R`` heads
that read one ``B`` and ``C``), and the steps of a group are the grid's
sequential axis, with the group's states in VMEM scratch, TRANSPOSED and
side by side: ``[N, R * P]`` float32, head r in lanes ``r * P`` and on, so
that a chunk's decay of the states is ONE row ``exp(cum_c)`` along the
lanes and the products against them take a tile of 128 lanes at once (the
read ``C S^T``, backward ``B dS^T``, ``R`` and the two sums over a group's
heads for ``dB`` and ``dC``).  ``C B^T`` is made once a chunk.  The
products with the scores (``M x``, ``M^T dY``, ``dY x^T``) and the write
``B^T (e x)`` are a head's own; heads of 64 columns go through them two to
a tile, each against the whole tile (as many rows through the MXU as a
half tile takes) and the result's own half kept.  Nothing ``[c, c]``
reaches HBM: ``L``, the scores, and backward ``dscores``, ``dcb``, ``pair``
are made and spent in VMEM, and so are ``x``, ``e x``, ``gamma dY``, ``C
S^T`` and ``B dS^T``.

What a number a token costs is its relayout from a column to the lanes
(the XLU's: the first bodies, which broadcast ``delta``, ``gamma``, ``e``
and ``cum`` a tile each, spent two thirds of their schedule there), so a
head's ``cum`` goes over the lanes ONCE (``_over_lanes``) and everything
else is made of that array or rides on ROWS, which broadcast over the
sublanes for nothing: ``L = exp(cum - cum_row)``, ``gamma = exp(cum)``, ``e
= exp(cum_c - cum)`` with ``cum_c`` a row as wide as the states; the step
``delta`` rides on the scores' columns (``M x = (M delta_row) u``, ``dY x^T
= (dY u^T) delta_row``) and on ``B^T``'s (the write: ``(B^T (delta e)_row)
u``), and only the backward, whose ``du`` and ``dB`` want ``delta`` a
token's row, sends it over the lanes as well.

Operands are read as the mixer writes them: ``u``, ``dY`` and the result
``[B, T, H * P]``, a block ``(1, STEP * 128, R * P)`` at ``(row, step,
group)``; ``b``, ``c`` ``[B, T, G * N]``.  What is one number a token and
head (``cum``, the running sum of ``delta A`` inside the chunk, and the
step ``delta``; float32 and made by XLA) comes as ROWS alone, ``[B, G, R8,
T]`` (a group's R ``cum``, then its R steps, filled up to whole tiles of 8
rows; tokens along the lanes), and a chunk's columns are ONE transposition
of them in VMEM (``_columns``): an array of columns would be filled up to
128 lanes in HBM, 33 MB a layer that XLA then keeps from the forward pass
to the backward.  What is one number a head and chunk comes as wide as the
states, ``[B, G, n, 3, R * P]``: ``exp(cum_c)``, ``D`` and ``cum_c``.  The
backward hands back the same way: what it makes by column (``dcum``'s row
sums and ``u . dx``) transposed to rows shaped as the rows' own, ``dcum``'s
column sums by row ``[B, G, R, T]``, and as wide as the states a chunk's
``sum_N dS * S``, ``sum_t dY * u`` and ``sum_t`` of the writes' decay's
cotangent; XLA adds them, runs ``da`` from the chunk's end and makes
``ddelta``, ``dA`` and ``dD``.

Three kernels, each with its name: ``ssd_scan_fwd`` (the op: ``Y``),
``ssd_scan_states`` (the grad op's first pass: the same walk, emitting the
states every chunk STARTS from, in the type the products against them take,
and no ``Y``) and ``ssd_scan_bwd`` (from the last chunk to the first with
``dS`` in scratch: every cotangent).  The backward keeps the six operands
and ``dY`` alone.

Precision is ``ssd._scan``'s: the step, ``cum``, every exponent and
exponential, the carried ``S`` and ``dS``, ``dcum`` float32; every
contraction takes its inputs in the AMP type where ``fluid.amp`` is on and
accumulates in float32.  Every mask comes before its exponential, no
exponent is positive and nothing is divided by a decay.

They run under the ``flash`` gate (``ops/kernel_choice.py``) where
``supported`` gives no reason against; ``ssd._scan`` is their twin, what
the CPU runs and the oracle of their tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import kernel_choice
from .pallas_delta_rule import _NN, _NT, _TN, _cast, _iota, _mm, _params
from .pallas_flash import block_index

#: tokens a chunk
CHUNK = 128
LANE = 128
#: the chunks of a grid step, and the tokens: what a sequence is padded to
STEP = 2
TOKENS = STEP * CHUNK
#: the rows of what is as wide as the states
KEPT, D_SKIP, CUM_END = range(3)
#: and of what the backward hands back so
D_KEPT, D_D, D_END = range(3)
#: what a backward step may hold in VMEM: Mosaic's default limit is 16 MiB
VMEM_BYTES = 12 << 20


def supported(u, delta, b, c, chunk, groups) -> str:
    """'' when the kernels take these operands (u [B, T, H, P]; delta [B,
    T, H]; b, c [B, T, groups * N]; any T: a ragged tail is padded before
    them), else why not."""
    h, p = u.shape[2:]
    rep, state = h // groups, b.shape[-1] // groups
    if chunk != CHUNK:
        return "chunk"
    if p not in (LANE // 2, LANE) or state % LANE:
        return "width"
    if (rep * p) % LANE:
        return "heads"
    # a backward step: its blocks twice (u, dY, du; b, c, db, dc; the rows
    # three times; the states), dS, and a dozen arrays of a tile
    wide, f32 = rep * p, 4
    blocks = TOKENS * (3 * wide * u.dtype.itemsize
                       + 4 * state * b.dtype.itemsize + 6 * rep * f32) \
        + STEP * state * wide * f32
    if 2 * blocks + state * wide * f32 + 12 * CHUNK * LANE * f32 \
            > VMEM_BYTES:
        return "width"
    return ""


def _tokens(k):
    """The rows of chunk ``k`` of a grid step in a block of tokens."""
    return slice(k * CHUNK, (k + 1) * CHUNK)


def _lanes(j):
    return slice(j * LANE, (j + 1) * LANE)


def _halves(first, parts):
    """A tile's array from its heads' own, each made over the whole tile:
    each keeps its half."""
    return parts[0] if first is None else jnp.where(first, *parts)


def _columns(rows):
    """The rows [R8, c] (a number a token along the lanes) as columns [c,
    128], row i in lane i: one transposition a chunk, of a tile the rows
    are filled up to."""
    return jnp.concatenate(
        [rows, jnp.zeros((CHUNK - rows.shape[0], CHUNK), rows.dtype)], 0).T


def _over_lanes(cols, lane):
    """A column [c, 1] of ``cols`` over 128 lanes: the ONE relayout a
    number a token costs, whatever is made of it afterwards."""
    return jnp.broadcast_to(cols[:, lane:lane + 1], (CHUNK, LANE))


def _decay(cum, cum_row, causal):
    """``L`` [c, c] from a head's ``cum`` over the lanes and as a row:
    masked before the exponential, nothing above the diagonal."""
    return jnp.exp(jnp.where(causal, cum - cum_row, -jnp.inf))


def _walk_kernel(u_ref, b_ref, c_ref, rows_ref, lanes_ref, out_ref, state, *,
                 low, per, emit):
    """A step of the walk.  ``emit`` 'out': ``Y`` [STEP * 128, R * P];
    'starts': the states each chunk starts from, [STEP, N, R * P]."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    f32 = jnp.float32
    causal = _iota((CHUNK, CHUNK), 0) >= _iota((CHUNK, CHUNK), 1)
    first = _iota((CHUNK, LANE), 1) < LANE // 2 if per == 2 else None
    rep = state.shape[1] // LANE * per
    for k in range(STEP):
        bm, cm = b_ref[0, _tokens(k)], c_ref[0, _tokens(k)]
        # B^T, each head's by its own row: what a token leaves at the
        # chunk's end of what it writes, delta exp(cum_c - cum)
        bt = bm.astype(f32).T
        rows = rows_ref[0, 0, :, _tokens(k)]
        cum_rows, delta_rows = rows[:rep], rows[rep:2 * rep]
        left_rows = delta_rows * jnp.exp(cum_rows[:, CHUNK - 1:CHUNK]
                                         - cum_rows)
        if emit == "out":
            cb = _mm(cm, bm, _NT, low)
            cols = _columns(rows)
        for j in range(state.shape[1] // LANE):
            heads = range(j * per, (j + 1) * per)
            u = u_ref[0, _tokens(k), _lanes(j)]
            held = state[:, _lanes(j)]
            start = _cast(low, held)
            if emit == "starts":
                out_ref[0, 0, k, :, _lanes(j)] = start
            else:
                cums = [_over_lanes(cols, h) for h in heads]
                # the step rides on the scores' columns: M x = (M delta) u
                out = _halves(first, [_mm(
                    cb * _decay(cum, cum_rows[h:h + 1], causal)
                    * delta_rows[h:h + 1], u, _NN, low)
                    for h, cum in zip(heads, cums)])
                out = out + jnp.exp(_halves(first, cums)) \
                    * _mm(cm, start, _NN, low) \
                    + lanes_ref[0, 0, k, D_SKIP:D_SKIP + 1, _lanes(j)] \
                    * u.astype(f32)
                out_ref[0, _tokens(k), _lanes(j)] = out.astype(out_ref.dtype)
            state[:, _lanes(j)] = \
                lanes_ref[0, 0, k, KEPT:KEPT + 1, _lanes(j)] * held \
                + _halves(first, [_mm(bt * left_rows[h:h + 1], u, _NN, low)
                                  for h in heads])


def _bwd_kernel(u_ref, b_ref, c_ref, rows_ref, lanes_ref, starts_ref, dy_ref,
                du_ref, db_ref, dc_ref, dcols_ref, drows_ref, dlanes_ref,
                dstate, *, low, per):
    """A step of the walk from the last chunk to the first: ``dS`` (in
    ``dstate``) back through the step's chunks and every cotangent of each
    out."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    f32 = jnp.float32
    causal = _iota((CHUNK, CHUNK), 0) >= _iota((CHUNK, CHUNK), 1)
    first = _iota((CHUNK, LANE), 1) < LANE // 2 if per == 2 else None
    halves = [None] if first is None else [first, ~first]
    rep = dstate.shape[1] // LANE * per
    lane = _iota((CHUNK, LANE), 1)
    for k in reversed(range(STEP)):
        bm, cm = b_ref[0, _tokens(k)], c_ref[0, _tokens(k)]
        cb = _mm(cm, bm, _NT, low)
        rows = rows_ref[0, 0, :, _tokens(k)]
        cum_rows, delta_rows = rows[:rep], rows[rep:2 * rep]
        cols = _columns(rows)
        dcb = jnp.zeros((CHUNK, CHUNK), f32)
        db = jnp.zeros(bm.shape, f32)
        dc = jnp.zeros(cm.shape, f32)
        dcols = jnp.zeros(lane.shape, f32)
        for j in range(dstate.shape[1] // LANE):
            heads = range(j * per, (j + 1) * per)
            u, dout = (ref[0, _tokens(k), _lanes(j)].astype(f32)
                       for ref in (u_ref, dy_ref))
            start = starts_ref[0, 0, k, :, _lanes(j)]
            dnext = dstate[:, _lanes(j)]
            cums = [_over_lanes(cols, h) for h in heads]
            cum = _halves(first, cums)
            # no exponent is positive: cum falls along the chunk
            to_end = jnp.exp(
                lanes_ref[0, 0, k, CUM_END:CUM_END + 1, _lanes(j)] - cum)
            delta = _halves(first, [_over_lanes(cols, rep + h)
                                    for h in heads])
            x = delta * u
            left = to_end * x
            read = jnp.exp(cum) * dout
            # what each token wrote, decayed to the chunk's end, meets dS
            met = _mm(bm, dnext, _NN, low)
            met_left = met * left
            # every exponential of cum but L's: the read of the state and
            # the write's decay to the chunk's end, a token and column
            own = read * _mm(cm, start, _NN, low) - met_left
            from_out = []
            for h, over, half in zip(heads, cums, halves):
                decay = _decay(over, cum_rows[h:h + 1], causal)
                # the step rides on the scores' columns: dY x^T = (dY u^T)
                # delta
                through = _mm(dout if half is None
                              else jnp.where(half, dout, 0.0), u, _NT, low) \
                    * delta_rows[h:h + 1] * decay
                dcb = dcb + through
                pair = through * cb
                drows_ref[0, 0, h:h + 1, _tokens(k)] = -jnp.sum(
                    pair, axis=0, keepdims=True)
                dcum = jnp.sum(pair + (own if half is None else jnp.where(
                    half, own, 0.0)), axis=1, keepdims=True)
                dcols = jnp.where(lane == h, dcum, dcols)
                from_out.append(_mm(cb * decay, dout, _TN, low))
            dx = _halves(first, from_out) + to_end * met
            du_ref[0, _tokens(k), _lanes(j)] = (
                delta * dx
                + lanes_ref[0, 0, k, D_SKIP:D_SKIP + 1, _lanes(j)] * dout
            ).astype(du_ref.dtype)
            udx = dx * u
            for h, half in zip(heads, halves):
                dcols = jnp.where(lane == rep + h, jnp.sum(
                    udx if half is None else jnp.where(half, udx, 0.0),
                    axis=1, keepdims=True), dcols)
            for row, summed in ((D_KEPT, dnext * start.astype(f32)),
                                (D_D, dout * u), (D_END, met_left)):
                dlanes_ref[0, 0, k, row:row + 1, _lanes(j)] = jnp.sum(
                    summed, axis=0, keepdims=True)
            dc = dc + _mm(read, start, _NT, low)
            db = db + _mm(left, dnext, _NT, low)
            dstate[:, _lanes(j)] = \
                lanes_ref[0, 0, k, KEPT:KEPT + 1, _lanes(j)] * dnext \
                + _mm(cm, read, _TN, low)
        # back as rows, as they came
        dcols_ref[0, 0, :, _tokens(k)] = dcols.T[:rows.shape[0]]
        dc_ref[0, _tokens(k)] = (dc + _mm(dcb, bm, _NN, low)).astype(
            dc_ref.dtype)
        db_ref[0, _tokens(k)] = (db + _mm(dcb, cm, _TN, low)).astype(
            db_ref.dtype)


def _gates(delta, a, d, groups, p):
    """(the rows [B, G, R8, T]: ``cum`` of a group's R heads, then their
    steps, filled up with zeros to whole tiles of 8 rows; what is as wide
    as the states [B, G, n, 3, R P]; ``exp(cum_c)`` [B, n, H]) of the step
    delta [B, n * 128, H] float32 and a, d [H]."""
    b, t, h = delta.shape
    n, rep = t // CHUNK, h // groups
    cum = jnp.cumsum((delta * a).reshape(b, n, CHUNK, h), 2)
    kept = jnp.exp(cum[:, :, -1])
    rows = jnp.concatenate([cum.reshape(b, t, groups, rep),
                            delta.reshape(b, t, groups, rep)], 3)
    rows = jnp.pad(jnp.transpose(rows, (0, 2, 3, 1)),
                   [(0, 0), (0, 0), (0, -2 * rep % 8), (0, 0)])

    def wide(x):            # [B, n, H] -> [B, G, n, R P]
        x = jnp.repeat(x.reshape(b, n, groups, rep), p, -1)
        return jnp.swapaxes(x, 1, 2)

    lanes = jnp.stack([wide(kept), wide(jnp.broadcast_to(d, kept.shape)),
                       wide(cum[:, :, -1])], 3)
    return rows, lanes, kept


def _specs(b, n, groups, wide, state, rep, backwards):
    """(the grid, the block of u, of b or c, of the rows, of what is as
    wide as the states, of the states, and of what the backward hands back
    by row of its own): the steps from the last to the first where
    ``backwards``."""
    steps = n // STEP

    def step(i):
        return steps - 1 - i if backwards else i

    def spec(block, at):
        return pl.BlockSpec(block, lambda b, g, i: block_index(
            *at(b, g, step(i))))

    return ((b, groups, steps),
            spec((1, TOKENS, wide), lambda b, g, i: (b, i, g)),
            spec((1, TOKENS, state), lambda b, g, i: (b, i, g)),
            spec((1, 1, 2 * rep + -2 * rep % 8, TOKENS),
                 lambda b, g, i: (b, g, 0, i)),
            spec((1, 1, STEP, 3, wide), lambda b, g, i: (b, g, i, 0, 0)),
            spec((1, 1, STEP, state, wide), lambda b, g, i: (b, g, i, 0, 0)),
            spec((1, 1, rep, TOKENS), lambda b, g, i: (b, g, 0, i)))


# jitted, as ``pallas_delta_rule._walk`` is: the layers of a step call each
# kernel with the same shapes, and under ``jit`` its body is traced once
# and lowered to Mosaic once a program, not once a layer
@functools.partial(jax.jit, static_argnums=(0, 1, 7, 8), inline=True)
def _walk(low, groups, u, b, c, rows, lanes, emit, interpret):
    """``Y`` [B, T, H * P] in u's type (``emit`` 'out') or the states every
    chunk starts from, [B, G, n, N, R * P] in ``low`` ('starts')."""
    from jax.experimental.pallas import tpu as pltpu

    bsz, t, h, p = u.shape
    wide, state = h * p // groups, b.shape[-1] // groups
    grid, u_spec, bc_spec, rows_spec, lanes_spec, state_spec = _specs(
        bsz, t // CHUNK, groups, wide, state, h // groups, False)[:6]
    if emit == "out":
        out_spec = u_spec
        out_shape = jax.ShapeDtypeStruct((bsz, t, h * p), u.dtype)
    else:
        out_spec = state_spec
        out_shape = jax.ShapeDtypeStruct(
            (bsz, groups, t // CHUNK, state, wide), low or jnp.float32)
    return pl.pallas_call(
        functools.partial(_walk_kernel, low=low, per=LANE // p, emit=emit),
        out_shape=out_shape, grid=grid,
        in_specs=[u_spec, bc_spec, bc_spec, rows_spec, lanes_spec],
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((state, wide), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name="ssd_scan_fwd" if emit == "out" else "ssd_scan_states")(
            u.reshape(bsz, t, h * p), b, c, rows, lanes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def scan(low, groups, u, delta, a, b, c, d):
    """The scan over u [B, T, H, P], delta [B, T, H] float32, a, d [H]
    float32, b, c [B, T, groups * N], T a multiple of TOKENS -> [B, T, H,
    P] in u's type; ``low``: the AMP type's name or None."""
    rows, lanes, _ = _gates(delta, a, d, groups, u.shape[3])
    return _walk(low, groups, u, b, c, rows, lanes, "out",
                 kernel_choice.interpret()).reshape(u.shape)


def _scan_fwd(low, groups, *operands):
    return scan(low, groups, *operands), operands


@functools.partial(jax.jit, static_argnums=(0, 1, 9), inline=True)
def _back(low, groups, u, b, c, rows, lanes, starts, dout, interpret):
    """(du [B, T, H * P] in u's type, db, dc in b's, the cotangents that
    the kernel makes by column, as rows shaped as the rows' own, those it
    makes by row [B, G, R, T] and as wide as the states [B, G, n, 3, R P],
    float32)."""
    from jax.experimental.pallas import tpu as pltpu

    bsz, t, h, p = u.shape
    wide, state = h * p // groups, b.shape[-1] // groups
    n = t // CHUNK
    (grid, u_spec, bc_spec, rows_spec, lanes_spec, state_spec,
     drows_spec) = _specs(bsz, n, groups, wide, state, h // groups, True)
    flat = u.reshape(bsz, t, h * p)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, low=low, per=LANE // p),
        out_shape=[jax.ShapeDtypeStruct(flat.shape, u.dtype),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype)]
        + [jax.ShapeDtypeStruct(shape, jnp.float32) for shape in (
            rows.shape, (bsz, groups, h // groups, t), lanes.shape)],
        grid=grid,
        in_specs=[u_spec, bc_spec, bc_spec, rows_spec, lanes_spec, state_spec,
                  u_spec],
        out_specs=[u_spec, bc_spec, bc_spec, rows_spec, drows_spec,
                   lanes_spec],
        scratch_shapes=[pltpu.VMEM((state, wide), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name="ssd_scan_bwd")(
            flat, b, c, rows, lanes, starts, dout.reshape(flat.shape))


def _scan_bwd(low, groups, operands, dout):
    """The six cotangents from the six operands and ``dout`` alone."""
    u, delta, a, b, c, d = operands
    bsz, t, h, p = u.shape
    n, rep = t // CHUNK, h // groups
    interpret = kernel_choice.interpret()
    rows, lanes, kept = _gates(delta, a, d, groups, p)
    starts = _walk(low, groups, u, b, c, rows, lanes, "starts", interpret)
    du, db, dc, dcols, drows, dlanes = _back(
        low, groups, u, b, c, rows, lanes, starts, dout, interpret)
    # [B, G, R, T] -> [B, T, H]: cum's cotangent, what came by column and
    # what came by row, and u . dx
    dcum, udx = (jnp.moveaxis(x, 3, 1).reshape(bsz, t, h) for x in (
        dcols[:, :, :rep] + drows, dcols[:, :, rep:2 * rep]))
    # [B, G, n, R P] -> [B, n, H]: the sums over a head's columns
    dkept, dd, dend = (jnp.sum(jnp.swapaxes(dlanes[:, :, :, i], 1, 2).reshape(
        bsz, n, groups, rep, p), -1).reshape(bsz, n, h)
        for i in (D_KEPT, D_D, D_END))
    # cum's: what came by column and by row, cum_c's own from the writes'
    # decay to the chunk's end and the state's, then the running sum from
    # the chunk's end
    dcum = dcum.reshape(bsz, n, CHUNK, h)
    dcum = dcum.at[:, :, -1].add(dend + kept * dkept)
    da = lax.cumsum(dcum, 2, reverse=True).reshape(bsz, t, h)
    return (du.reshape(u.shape), udx + da * a, jnp.sum(da * delta, (0, 1)),
            db, dc, jnp.sum(dd, (0, 1)))


scan.defvjp(_scan_fwd, _scan_bwd)
