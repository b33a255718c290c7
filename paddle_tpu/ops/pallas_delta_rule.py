"""The scalar gated delta rule (``ops/delta_rule.py`` ``_rule``: a decay a
value head) as Pallas TPU kernels, forward and backward.  The equations are
that module's docstring's, letter for letter; what is here is where a
chunk's arrays live.

One grid step is STEP chunks of 64 tokens of one PAIR of value heads (the
caller pads a sequence to whole steps with tokens that write and decay
nothing), and the steps
of a pair are the grid's sequential axis, with the two ``[dk, dv]`` float32
states of the pair (backward: their cotangents) in VMEM scratch.
Everything of a chunk is made in VMEM and nothing ``[C, C]`` reaches HBM:
the decays ``D``, ``K K^T``, ``Q K^T``, ``A``, the inverse ``T``, ``U``,
``W``, ``P``, ``V'``, and backward every cotangent of them.  The pair's two
chunk-heads are ONE system of 128 rows, head a's 64 tokens then head b's:
``K K^T`` and ``Q K^T`` are one ``[128, dk] x [dk, 128]`` product each,
``D`` is masked to the two diagonal blocks BEFORE its exponential, so
``A``, ``T``, ``P`` and their cotangents are block diagonal ``[128, 128]``
arrays that fill every lane of their registers, and the ladder that makes
the inverse (``delta_rule.unit_lower_inverse``'s, two products a level;
``_inverses`` has the form it runs in) serves both heads with the same ten
products.  With two value heads a key head the pair is a key head's (its
``K K^T`` shared, ``dQ`` and ``dK`` summed over the two in VMEM); with one,
two neighbouring key heads.  A step's chunks share nothing but the state
that walks through them in order: their systems are made side by side, the
ladders level by level, because a ladder alone is a chain of products that
each wait 130 cycles for the one before, and an MXU takes its work in the
order the program states it.

Operands are read as the projections write them: ``q``, ``k`` ``[B, T, Hk *
dk]``, ``v``, ``dO`` and the result ``[B, T, Hv * dv]``, a block ``(1,
STEP * 64, d)`` at ``(row, step, head)``: no transposition to a chunked
layout exists.  What is one number a token (the running sum ``G`` of ``g``
inside the chunk, ``beta``, ``gamma = exp(G)``, ``e = exp(G_C - G)``,
``beta * gamma``, ``exp(G_C)``) is made by XLA from the ``[B, T, Hv]``
gates and handed over twice: as COLUMNS, ``[B, pairs, n * 128, 128]`` with
one quantity a lane (a ``[128, 1]`` lane slice broadcasts along a row
without a relayout), and as ROWS, ``[B, pairs, n, 8, 128]``: ``G``, and
``exp(G_C)`` of either head in every lane, which scales a whole state.  The
backward hands ``dG`` and ``dbeta`` back the same way (the column sums of
``dG``'s pair terms as a row), and XLA adds the two, runs the sum from the
chunk's end and puts them back to ``[B, T, Hv]``.

Three kernels, each with its name: ``delta_rule_fwd`` (the op: ``O``),
``delta_rule_states`` (the grad op's first pass: the same walk, emitting
the state every chunk STARTS from, in the type the products against it
take, and no ``O``) and ``delta_rule_bwd`` (from the last chunk to the
first with ``dS`` in scratch: the system again, then every cotangent).  The
backward keeps the five operands and ``dO`` alone.

Precision is ``_rule``'s: gates, decays, their sums, the inverse and its
two products, ``T^T dU``, ``T^T dW`` and ``dA`` float32 at the highest
matmul precision, the carried ``S`` and ``dS`` float32; every other
contraction takes its inputs in the AMP type where ``fluid.amp`` is on and
accumulates in float32.  Every mask comes before its exponential, no
exponent is positive and nothing is divided by a decay.

The family runs under the ``flash`` gate (``ops/kernel_choice.py``) where
``supported`` gives no reason against; ``_rule`` is its twin, what the CPU
runs and the oracle of its tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import kernel_choice
from .pallas_flash import block_index

#: tokens a chunk; two chunk-heads fill the 128 rows of a system
CHUNK = 64
ROWS = 2 * CHUNK
#: the chunks of a grid step, and the tokens: what a sequence is padded to
STEP = 4
TOKENS = STEP * CHUNK
LANE = 128
#: the lanes of the columns' array: one quantity a token each
G, BETA, GAMMA, TO_END, BETA_GAMMA, KEPT = range(6)
#: the lanes of the backward's columns
D_G, D_BETA = range(2)

_NN, _NT, _TN = (1, 0), (1, 1), (0, 0)


def supported(q, k, v, g, chunk) -> str:
    """'' when the kernels take these operands (q, k [B, T, Hk, dk]; v
    [B, T, Hv, dv]; g [B, T, Hv]; any T: a ragged tail is padded before
    them), else why not."""
    hk, dk = q.shape[2:]
    hv, dv = v.shape[2:]
    if g.ndim != 3:
        return "channel_decay"
    if chunk != CHUNK:
        return "chunk"
    if dk % LANE or dv % LANE or max(dk, dv) > 2 * LANE:
        return "width"
    if hv % 2 or hv not in (hk, 2 * hk):
        return "heads"
    return ""


def _mm(a, b, dims, low=None):
    """``a`` contracted with ``b`` over ``dims`` = (of a, of b) into
    float32, the inputs in ``low`` (None: as they are)."""
    if low is not None:
        a, b = a.astype(low), b.astype(low)
    return lax.dot_general(a, b, (((dims[0],), (dims[1],)), ((), ())),
                           preferred_element_type=jnp.float32)


def _exact(a, b, dims=_NN):
    """The same of float32 inputs at the highest matmul precision."""
    return lax.dot_general(a, b, (((dims[0],), (dims[1],)), ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _rowsum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _halves(x):
    """[64, 2 d] (head a's columns, then head b's) -> [128, d]."""
    d = x.shape[1] // 2
    return jnp.concatenate([x[:, :d], x[:, d:]], axis=0)


def _side_by_side(x):
    """[128, d] -> [64, 2 d]."""
    return jnp.concatenate([x[:CHUNK], x[CHUNK:]], axis=1)


def _inverses(systems):
    """``(I + a)^{-1}`` for every ``a`` [128, 128] of ``systems``, each
    strictly lower triangular inside its two diagonal blocks of 64 and zero
    outside them: ``delta_rule.unit_lower_inverse``'s ladder, two products
    a level, in the COMPACT form of a block-diagonal matrix.  A matrix whose
    blocks of ``size`` on the diagonal are all it holds is kept as
    ``[size, 128]``, block b in lanes ``b * size`` and on; its product with
    another is that array times the other EXPANDED to ``[128, 128]``, so a
    level streams ``size`` rows through the MXU where the plain form
    streams 128 (a grid step's MXU pushes fall by a half).  Levels of
    blocks of 4 and 8 run in the form of 8, one register.  The systems go
    up the ladder in step, a level's products of one after those of the
    other: a ladder alone waits on each product's result, and the MXUs
    take their work in program order."""
    def lanes(size):        # of the compact form: (row ^ column, block)
        row = lax.broadcasted_iota(jnp.int32, (size, LANE), 0)
        lane = lax.broadcasted_iota(jnp.int32, (size, LANE), 1)
        return row ^ (lane & (size - 1)), lane >> (size.bit_length() - 1)

    def compact(a, size, block):
        rows = [jnp.where(block == b, a[b * size:(b + 1) * size, :], 0.0)
                for b in range(ROWS // size)]
        return sum(rows[1:], rows[0])

    at = lax.broadcasted_iota(jnp.int32, (ROWS, LANE), 0)
    to = lax.broadcasted_iota(jnp.int32, (ROWS, LANE), 1)

    def join(xs, within, apart, level, size):
        shift = size.bit_length() - 1
        own = (at >> shift) == (to >> shift)

        def expand(x):
            return jnp.where(own, jnp.concatenate([x] * (ROWS // size),
                                                  axis=0), 0.0)

        # the blocks that join an odd block of 2 ** level to the even one
        # before it: where row ^ column >> level is 1, given that ``a``
        # holds nothing on or above the diagonal
        joins = (apart >> level) == 1
        half = [_exact(x, expand(jnp.where(joins, w, 0.0)))
                for x, w in zip(xs, within)]
        return [x - _exact(h, expand(x)) for x, h in zip(xs, half)], expand

    size = 8
    apart, block = lanes(size)
    within = [compact(a, size, block) for a in systems]
    xs = [jnp.where(apart == 0, jnp.float32(1),
                    -jnp.where(apart == 1, w, 0.0)) for w in within]  # of 2
    for level in (1, 2):                                     # of 4, of 8
        xs, _ = join(xs, within, apart, level, size)
    for level in (3, 4, 5):                                  # 16, 32, 64
        # the form of blocks twice as wide: an even block's rows, then the
        # odd one's beside it
        xs = [jnp.concatenate([jnp.where((block & 1) == half, x, 0.0)
                               for half in (0, 1)], axis=0) for x in xs]
        size *= 2
        apart, block = lanes(size)
        within = [compact(a, size, block) for a in systems]
        xs, expand = join(xs, within, apart, level, size)
    return [expand(x) for x in xs]


def _tokens(c):
    """The rows of chunk ``c`` of a grid step in a block of tokens."""
    return slice(c * CHUNK, (c + 1) * CHUNK)


def _systems(low, q_ref, k_ref, v_ref, cols_ref, rows_ref, shared):
    """Everything of each of the step's chunks that no state
    enters, as ``[128, .]`` arrays (head a's tokens, then head b's).
    ``shared``: the two value heads read one key head."""
    f32 = jnp.float32
    at = lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 0)
    to = lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 1)
    own = ((at ^ to) >> 6) == 0             # a head's own block
    below = own & (at > to)
    out = []
    for c in range(STEP):
        if shared:
            q, k = q_ref[0, _tokens(c)], k_ref[0, _tokens(c)]
            q, k = (jnp.concatenate([x, x], axis=0) for x in (q, k))
        else:
            q, k = (_halves(x[0, _tokens(c)]) for x in (q_ref, k_ref))
        v = _halves(v_ref[0, _tokens(c)].astype(f32))
        cols = cols_ref[0, 0, c * ROWS:(c + 1) * ROWS]
        col = {name: cols[:, lane:lane + 1] for name, lane in (
            ("g", G), ("beta", BETA), ("gamma", GAMMA), ("to_end", TO_END),
            ("beta_gamma", BETA_GAMMA), ("kept", KEPT))}
        rows = rows_ref[0, 0, c]
        # exp(G_C) of either head, as wide as a state
        kept = [jnp.concatenate([rows[1 + r:2 + r, :]]
                                * (v.shape[1] // LANE), axis=1)
                for r in range(2)]
        # masked before the exponential: nothing of the other head, no
        # positive exponent
        decay = jnp.exp(jnp.where(own & (at >= to), col["g"] - rows[0:1, :],
                                  -jnp.inf))
        out.append(dict(q=q, k=k, v=v, col=col, kept=kept, decay=decay,
                        below=below, kk=_mm(k, k, _NT, low)))
    invs = _inverses([jnp.where(below, m["col"]["beta"] * m["kk"]
                                * m["decay"], 0.0) for m in out])
    for m, inv in zip(out, invs):
        col, dv = m["col"], m["v"].shape[1]
        uw = _exact(inv, jnp.concatenate(
            [col["beta"] * m["v"], col["beta_gamma"] * m["k"]], axis=1))
        m.update(inv=inv, u=uw[:, :dv], w=uw[:, dv:],
                 scores=_mm(m["q"], m["k"], _NT, low) * m["decay"])
    return out


def _head(x, r):
    return x[r * CHUNK:(r + 1) * CHUNK]


def _cast(low, x):
    return x if low is None else x.astype(low)


def _walk_kernel(q_ref, k_ref, v_ref, cols_ref, rows_ref, out_ref, state, *,
                 low, shared, emit):
    """A step of the walk: its chunks' systems first, side by side, then
    the state through them in order.  ``emit`` 'out': ``O`` [STEP * 64,
    2 dv]; 'starts': the two states each chunk starts from, [STEP, 2, dk,
    dv]."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    systems = _systems(low, q_ref, k_ref, v_ref, cols_ref, rows_ref, shared)
    for c, m in enumerate(systems):
        col = m["col"]
        wrote, read = [], []
        for r in range(2):
            start = _cast(low, state[r])
            if emit == "starts":
                out_ref[0, 0, c, r] = start
            # W S and, where O is wanted, Q S: one product against the state
            both = _head(m["w"], r)
            if emit == "out":
                both = jnp.concatenate([both, _head(m["q"], r)], axis=0)
            both = _mm(both, start, _NN, low)
            wrote.append(_head(m["u"], r) - both[:CHUNK])
            read.append(both[CHUNK:])           # nothing where no O is
            state[r] = m["kept"][r] * state[r] + _mm(
                _head(m["k"], r), _head(col["to_end"], r) * wrote[r], _TN,
                low)
        if emit == "out":
            out = col["gamma"] * jnp.concatenate(read, axis=0) + _mm(
                m["scores"], jnp.concatenate(wrote, axis=0), _NN, low)
            out_ref[0, _tokens(c)] = _side_by_side(out).astype(out_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, cols_ref, rows_ref, starts_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dcols_ref, drows_ref, dstate, *,
                low, shared):
    """A step of the walk from the last chunk to the first: its chunks'
    systems again first, then ``dS`` (in ``dstate``) back through them and
    every cotangent of each out."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    systems = _systems(low, q_ref, k_ref, v_ref, cols_ref, rows_ref, shared)
    for c in reversed(range(STEP)):
        _bwd_chunk(systems[c], c, starts_ref, do_ref, dq_ref, dk_ref, dv_ref,
                   dcols_ref, drows_ref, dstate, low, shared)


def _bwd_chunk(m, c, starts_ref, do_ref, dq_ref, dk_ref, dv_ref, dcols_ref,
               drows_ref, dstate, low, shared):
    """Chunk ``c`` of a backward step from its system ``m``."""
    f32 = jnp.float32
    col, q, k = m["col"], m["q"], m["k"]
    dout = _halves(do_ref[0, _tokens(c)].astype(f32))
    read = col["gamma"] * dout
    from_out = _mm(m["scores"], dout, _TN, low)
    wrote, dwrote, back, dk_state, dkept = [], [], [], [], []
    for r in range(2):
        start = starts_ref[0, 0, c, r]
        dnext = dstate[r]
        wrote.append(_head(m["u"], r) - _mm(_head(m["w"], r), start, _NN,
                                            low))
        dwrote.append(_head(from_out, r) + _head(col["to_end"], r) * _mm(
            _head(k, r), dnext, _NN, low))
        # against S^T: dQ's read of the state, then dW
        carried = jnp.concatenate([_head(read, r), -dwrote[r]], axis=0)
        back.append(_mm(carried, start, _NT, low))
        dstate[r] = m["kept"][r] * dnext + _mm(
            jnp.concatenate([_head(q, r), _head(m["w"], r)], axis=0),
            carried, _TN, low)
        dk_state.append(_mm(_head(col["to_end"], r) * wrote[r], dnext, _NT,
                            low))
        dkept.append(jnp.sum(_rowsum(dnext * start.astype(f32)), axis=0,
                             keepdims=True))
    wrote, dwrote = (jnp.concatenate(x, axis=0) for x in (wrote, dwrote))
    dq_read = jnp.concatenate([x[:CHUNK] for x in back], axis=0)
    dw = jnp.concatenate([x[CHUNK:] for x in back], axis=0)
    dk_state = jnp.concatenate(dk_state, axis=0)
    dv_width = dout.shape[1]

    # the inverse and its two products, float32 at the highest precision
    dx = _exact(m["inv"], jnp.concatenate([dwrote, dw], axis=1), _TN)
    dxu, dxw = dx[:, :dv_width], dx[:, dv_width:]
    da = jnp.where(m["below"], -_exact(
        dx, jnp.concatenate([m["u"], m["w"]], axis=1), _NT), 0.0)
    through = da * m["kk"] * m["decay"]
    dkk = col["beta"] * da * m["decay"]
    dstep = _rowsum(dxw * k)
    # the scores and the read of the state
    dscores = _mm(dout, wrote, _NT, low)
    dqk = dscores * m["decay"]
    dq = dq_read + _mm(dqk, k, _NN, low)
    dk = _mm(jnp.concatenate([dqk, dkk], axis=0),
             jnp.concatenate([q, k], axis=0), _TN, low) \
        + _mm(dkk, k, _NN, low) + dk_state + col["beta_gamma"] * dxw
    dbeta = _rowsum(dxu * m["v"]) + col["gamma"] * dstep + _rowsum(through)
    # every exponential of G
    pair = dscores * m["scores"] + col["beta"] * through
    dto_end = _rowsum((dwrote - from_out) * wrote)
    dg = _rowsum(dq_read * q) + col["beta_gamma"] * dstep + _rowsum(pair) \
        - dto_end
    # G_C's own: the writes' decay to the chunk's end and the state's
    at_end = jnp.concatenate(
        [jnp.sum(_head(dto_end, r), axis=0, keepdims=True)
         + _head(col["kept"], r) * dkept[r] for r in range(2)], axis=0)
    token = lax.broadcasted_iota(jnp.int32, (ROWS, 1), 0)
    dg = dg + jnp.where((token & (CHUNK - 1)) == CHUNK - 1, at_end, 0.0)

    if shared:
        dq_ref[0, _tokens(c)] = dq[:CHUNK] + dq[CHUNK:]
        dk_ref[0, _tokens(c)] = dk[:CHUNK] + dk[CHUNK:]
    else:
        dq_ref[0, _tokens(c)] = _side_by_side(dq)
        dk_ref[0, _tokens(c)] = _side_by_side(dk)
    dv_ref[0, _tokens(c)] = _side_by_side(col["beta"] * dxu).astype(
        dv_ref.dtype)
    lane = lax.broadcasted_iota(jnp.int32, (ROWS, LANE), 1)
    dcols_ref[0, 0, c * ROWS:(c + 1) * ROWS] = jnp.where(
        lane == D_G, dg, jnp.where(lane == D_BETA, dbeta, 0.0))
    drows_ref[0, 0, c] = jnp.where(
        lax.broadcasted_iota(jnp.int32, (8, ROWS), 0) == 0,
        -jnp.sum(pair, axis=0, keepdims=True), 0.0)


def _by_pair(x, n):
    """[B, n * 64, Hv] -> [B, Hv / 2, n * 128]: a chunk's tokens of a
    pair's head a, then of its head b."""
    b, _, hv = x.shape
    x = x.reshape(b, n, CHUNK, hv // 2, 2)
    return jnp.transpose(x, (0, 3, 1, 4, 2)).reshape(b, hv // 2, n * ROWS)


def _by_token(x, n):
    """``_by_pair`` back: [B, Hv / 2, n * 128] -> [B, n * 64, Hv]."""
    b, pairs, _ = x.shape
    x = x.reshape(b, pairs, n, 2, CHUNK)
    return jnp.transpose(x, (0, 2, 4, 1, 3)).reshape(b, n * CHUNK, 2 * pairs)


def _gates(g, beta, n):
    """(the columns [B, pairs, n * 128, 128], the row [B, pairs, n, 8,
    128]) of the gates g, beta [B, n * 64, Hv] float32."""
    b, t, hv = g.shape
    gsum = jnp.cumsum(g.reshape(b, n, CHUNK, hv), 2)
    last = gsum[:, :, -1:]
    gamma = jnp.exp(gsum)
    step = beta.reshape(gsum.shape)
    cols = [gsum, step, gamma, jnp.exp(last - gsum), step * gamma,
            jnp.broadcast_to(jnp.exp(last), gsum.shape)]
    cols = jnp.stack([_by_pair(x.reshape(b, t, hv), n) for x in cols], -1)
    cols = jnp.pad(cols, [(0, 0)] * 3 + [(0, LANE - cols.shape[-1])])
    rows = [_by_pair(gsum.reshape(b, t, hv), n).reshape(b, hv // 2, n, ROWS)]
    kept = jnp.exp(last).reshape(b, n, hv // 2, 2)
    rows += [jnp.broadcast_to(jnp.moveaxis(kept[..., r], 2, 1)[..., None],
                              rows[0].shape) for r in range(2)]
    rows = jnp.stack(rows, 3)
    return cols, jnp.pad(rows, [(0, 0)] * 3 + [(0, 8 - rows.shape[3]),
                                               (0, 0)])


def _specs(b, n, hk, hv, dk, dv, backwards):
    """(the grid, the block of q or k, of v, of the columns, of the rows,
    of the states): the steps from the last to the first where
    ``backwards``."""
    pairs = hv // 2
    width = dk if hv == 2 * hk else 2 * dk
    steps = n // STEP

    def step(i):
        return steps - 1 - i if backwards else i

    return ((b, pairs, steps),
            pl.BlockSpec((1, TOKENS, width),
                         lambda b, p, i: block_index(b, step(i), p)),
            pl.BlockSpec((1, TOKENS, 2 * dv),
                         lambda b, p, i: block_index(b, step(i), p)),
            pl.BlockSpec((1, 1, STEP * ROWS, LANE),
                         lambda b, p, i: block_index(b, p, step(i), 0)),
            pl.BlockSpec((1, 1, STEP, 8, ROWS),
                         lambda b, p, i: block_index(b, p, step(i), 0, 0)),
            pl.BlockSpec((1, 1, STEP, 2, dk, dv),
                         lambda b, p, i: block_index(b, p, step(i), 0, 0,
                                                     0)))


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


# jitted, as ``pallas_grouped._matmul`` is: the layers of a step call each
# kernel with the same shapes, and under ``jit`` its body is traced once
# and lowered to Mosaic once a program, not once a layer
@functools.partial(jax.jit, static_argnums=(0, 6, 7), inline=True)
def _walk(low, q, k, v, cols, row, emit, interpret):
    """``O`` [B, T, Hv * dv] in v's type (``emit`` 'out') or every chunk's
    two starting states [B, pairs, n, 2, dk, dv] in ``low`` ('starts')."""
    from jax.experimental.pallas import tpu as pltpu

    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    n = t // CHUNK
    grid, qk_spec, v_spec, cols_spec, row_spec, state_spec = _specs(
        b, n, hk, hv, dk, dv, False)
    if emit == "out":
        out_spec = v_spec
        out_shape = jax.ShapeDtypeStruct((b, t, hv * dv), v.dtype)
    else:
        out_spec = state_spec
        out_shape = jax.ShapeDtypeStruct(
            (b, hv // 2, n, 2, dk, dv), low or jnp.float32)
    return pl.pallas_call(
        functools.partial(_walk_kernel, low=low, shared=hv == 2 * hk,
                          emit=emit),
        out_shape=out_shape, grid=grid,
        in_specs=[qk_spec, qk_spec, v_spec, cols_spec, row_spec],
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((2, dk, dv), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name="delta_rule_fwd" if emit == "out" else "delta_rule_states")(
            q.reshape(b, t, hk * dk), k.reshape(b, t, hk * dk),
            v.reshape(b, t, hv * dv), cols, row)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def rule(low, q, k, v, g, beta):
    """The rule over q, k [B, T, Hk, dk] float32 (normed and scaled), v
    [B, T, Hv, dv], g, beta [B, T, Hv] float32, T a multiple of TOKENS ->
    [B, T, Hv, dv] in v's type; ``low``: the AMP type's name or None."""
    cols, row = _gates(g, beta, q.shape[1] // CHUNK)
    return _walk(low, q, k, v, cols, row, "out",
                 kernel_choice.interpret()).reshape(v.shape)


def _rule_fwd(low, *operands):
    return rule(low, *operands), operands


@functools.partial(jax.jit, static_argnums=(0, 8), inline=True)
def _back(low, q, k, v, cols, row, starts, dout, interpret):
    """(dq, dk [B, T, Hk * dk] float32, dv [B, T, Hv * dv] in v's type, the
    gates' cotangents by column and by row, shaped as the gates' own)."""
    from jax.experimental.pallas import tpu as pltpu

    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    grid, qk_spec, v_spec, cols_spec, row_spec, state_spec = _specs(
        b, t // CHUNK, hk, hv, dk, dv, True)
    flat = [q.reshape(b, t, hk * dk), k.reshape(b, t, hk * dk),
            v.reshape(b, t, hv * dv)]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, low=low, shared=hv == 2 * hk),
        out_shape=[jax.ShapeDtypeStruct(flat[0].shape, jnp.float32),
                   jax.ShapeDtypeStruct(flat[1].shape, jnp.float32),
                   jax.ShapeDtypeStruct(flat[2].shape, v.dtype),
                   jax.ShapeDtypeStruct(cols.shape, jnp.float32),
                   jax.ShapeDtypeStruct(row.shape, jnp.float32)],
        grid=grid,
        in_specs=[qk_spec, qk_spec, v_spec, cols_spec, row_spec, state_spec,
                  v_spec],
        out_specs=[qk_spec, qk_spec, v_spec, cols_spec, row_spec],
        scratch_shapes=[pltpu.VMEM((2, dk, dv), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name="delta_rule_bwd")(
            *flat, cols, row, starts, dout.reshape(b, t, hv * dv))


def _rule_bwd(low, operands, dout):
    """The five cotangents from the five operands and ``dout`` alone."""
    q, k, v, g, beta = operands
    b, t, hv = g.shape
    n = t // CHUNK
    interpret = kernel_choice.interpret()
    cols, row = _gates(g, beta, n)
    starts = _walk(low, q, k, v, cols, row, "starts", interpret)
    dq, dk, dv, dcols, drow = _back(low, q, k, v, cols, row, starts, dout,
                                    interpret)
    # dG: what came by row and what came by column, then the running sum
    # from the chunk's end
    dgsum = _by_token(dcols[..., D_G] + drow[:, :, :, 0].reshape(
        b, hv // 2, n * ROWS), n).reshape(b, n, CHUNK, hv)
    dg = lax.cumsum(dgsum, 2, reverse=True).reshape(b, t, hv)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg, _by_token(dcols[..., D_BETA], n))


rule.defvjp(_rule_fwd, _rule_bwd)
