"""The gated delta rule (``ops/delta_rule.py``) as Pallas TPU kernels,
forward and backward: the scalar rule (``_rule``: a decay a value head;
``rule`` here, described first) and, at the end of the module, the channel
rule (``_channel_rule``: a decay a key channel; ``channel_rule``), two
families of kernel bodies that share the inverse's ladder, the products,
the lock-step layout and the block specs, and no walk.  The equations are
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

Under a decay a key channel (``g`` [B, T, H, dk], one value head a key
head): ``delta_channel_fwd``, ``delta_channel_states`` and
``delta_channel_bwd``, the same grid, blocks and lock-step chunks.  ``g``
is read as q and k are and everything made of it is made in VMEM: its
running sum ``G`` [C, dk] (one exact product with a triangle of ones),
``gamma``, the decays to the sub-blocks' edges and to the chunk's end; the
off-diagonal tiles of ``K K^T`` and ``Q K^T`` are ONE product a pair about
the sub-blocks' edges in the AMP type, the four diagonal tiles go element
by element in float32, a sub-block a trip of a loop, and nothing ``[C, C]``,
``[C, C, dk]`` or ``[sub, sub, dk]`` reaches HBM or is ever whole.  The
states are kept transposed, ``[dv, dk]``: their rows' decay runs along the
lanes.  ``beta`` alone comes as a column.

Either family runs under the ``flash`` gate (``ops/kernel_choice.py``)
where ``supported`` gives no reason against; ``_rule`` and
``_channel_rule`` are their twins, what the CPU runs and the oracles of
their tests.
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
#: under a decay a key channel, the one lane of either way's columns
D_BETA_ALONE = 0

_NN, _NT, _TN = (1, 0), (1, 1), (0, 0)


def supported(q, k, v, g, chunk) -> str:
    """'' when the kernels take these operands (q, k [B, T, Hk, dk]; v
    [B, T, Hv, dv]; g [B, T, Hv], or [B, T, Hv, dk] for a decay a key
    channel, whose kernels take one value head a key head and keys of 128:
    a chunk's gates are arrays as wide as the key there, and with keys of
    256 the backward's do not fit VMEM; any T: a ragged tail is padded
    before them), else why not."""
    hk, dk = q.shape[2:]
    hv, dv = v.shape[2:]
    if chunk != CHUNK:
        return "chunk"
    if dk % LANE or dv % LANE or max(dk, dv) > 2 * LANE \
            or (g.ndim == 4 and dk > LANE):
        return "width"
    if hv % 2 or hv not in ((hk,) if g.ndim == 4 else (hk, 2 * hk)):
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


# -- a decay a key channel (``delta_rule._channel_rule``) --------------------
#
# The same grid, blocks and lock-step chunks; what differs is the system (a
# chunk's scores need the split about its sub-blocks' edges), the state
# (kept TRANSPOSED, ``[dv, dk]``, so that the decay of its rows is a
# multiple along the lanes) and the gates: ``g`` comes as q and k do, ``[B,
# T, H * dk]``, and everything made of it (its running sum ``G``, ``gamma``,
# the decays to the edges and to the chunk's end) is made in VMEM.  ``beta``
# alone comes as a column (``_by_pair``), and ``dbeta`` goes back as one.

#: tokens of a sub-block (``delta_rule.SUB_BLOCKS`` of them a chunk), and
#: the sub-blocks of a pair's 128 rows
SUB = CHUNK // 4
TILES = ROWS // SUB


def _iota(shape, dim):
    return lax.broadcasted_iota(jnp.int32, shape, dim)


def _over_sub_blocks(rows):
    """Rows ``[1, d]`` -> ``[16 * their number, d]``, each over the 16 rows
    of its sub-block."""
    return jnp.concatenate([jnp.broadcast_to(r, (SUB, r.shape[1]))
                            for r in rows], axis=0)


def _edges(gsum):
    """A head's decays about its sub-blocks' edges from ``gsum`` [64, dk]:
    ``to_edge`` (each token's since the last token before its sub-block),
    ``to_sub_end`` (what is left of it at its sub-block's last token),
    ``left`` (at the chunk's last), ``kept`` [1, dk] (of the state's rows
    over the chunk) and the three decays from one edge to a later one.
    Every exponent is a difference of ``G`` the right way round: none is
    positive."""
    ends = [gsum[(a + 1) * SUB - 1:(a + 1) * SUB] for a in range(4)]
    edge = _over_sub_blocks([jnp.zeros_like(ends[0])] + ends[:3])
    sub_end = _over_sub_blocks(ends)
    mids = (jnp.exp(ends[1] - ends[0]), jnp.exp(ends[2] - ends[0]),
            jnp.exp(ends[2] - ends[1]))
    return (jnp.exp(gsum - edge), jnp.exp(sub_end - gsum),
            jnp.exp(ends[3] - gsum), jnp.exp(ends[3]), mids)


# The off-diagonal tiles of a head's scores, ``sum_c x_ic k_jc exp(G_ic -
# G_jc)`` for i in sub-block a and j in an earlier one b, as ONE product a
# pair: ``G_i - G_j = (G_i - edge_a) + (edge_a - end_b) + (end_b - G_j)``,
# each <= 0, so the tile (a, b) is ``x * to_edge * mid_ab`` rows against ``k
# * to_sub_end`` rows, and the six tiles' left sides are stacked (96 rows a
# head) against the one right side of all 64 tokens.

def _stack(xe, mids):
    """``x * to_edge`` [64, dk] of a head -> the six tiles' rows [96, dk]:
    (1,0) (2,0) (3,0) (2,1) (3,1) (3,2)."""
    m20, m30, m31 = mids
    s1, s2, s3 = (xe[a * SUB:(a + 1) * SUB] for a in (1, 2, 3))
    return jnp.concatenate([s1, s2 * m20, s3 * m30, s2, s3 * m31, s3],
                           axis=0)


def _columns(r):
    """Where the lanes of sub-blocks 0, 1, 2 of head ``r`` are."""
    sub = _iota((SUB, ROWS), 1) >> 4
    return [sub == 4 * r + b for b in range(3)]


def _tiles_of(res, r):
    """``_stack``'s product against all 128 tokens [96, 128] -> the head's
    rows of the scores [64, 128], zero outside the six tiles."""
    b0, b1, b2 = _columns(r)
    t = [res[i * SUB:(i + 1) * SUB] for i in range(6)]
    return jnp.concatenate([
        jnp.zeros((SUB, ROWS), jnp.float32),
        jnp.where(b0, t[0], 0.0),
        jnp.where(b0, t[1], jnp.where(b1, t[3], 0.0)),
        jnp.where(b0, t[2], jnp.where(b1, t[4], jnp.where(b2, t[5], 0.0)))],
        axis=0)


def _tiles_cotangent(dm, r):
    """``_tiles_of`` back: a head's rows of a scores' cotangent [64, 128]
    -> that of the stacked product [96, 128]."""
    b0, b1, b2 = _columns(r)
    s1, s2, s3 = (dm[a * SUB:(a + 1) * SUB] for a in (1, 2, 3))
    return jnp.concatenate([
        jnp.where(b0, s1, 0.0), jnp.where(b0, s2, 0.0),
        jnp.where(b0, s3, 0.0), jnp.where(b1, s2, 0.0),
        jnp.where(b1, s3, 0.0), jnp.where(b2, s3, 0.0)], axis=0)


def _stack_cotangent(dl, mids):
    """``_stack`` back: [96, dk] -> the cotangent of ``x * to_edge``
    [64, dk]."""
    m20, m30, m31 = mids
    t = [dl[i * SUB:(i + 1) * SUB] for i in range(6)]
    return jnp.concatenate([
        jnp.zeros_like(t[0]), t[0], t[1] * m20 + t[3],
        t[2] * m30 + t[4] * m31 + t[5]], axis=0)


# The diagonal tiles, ``sum_c x_ic k_jc exp(G_ic - G_jc)`` for i >= j inside
# a sub-block, element by element in float32.  A trip of the loop is ONE
# sub-block: its 16 rows of ``G``, ``k`` and ``x`` are six registers that
# stay where they are while the sub-block's tokens j go by one after another
# (unrolled: a token's row of ``G`` and of ``k`` is read over the rows, the
# mask comes BEFORE the exponential, the sum over the channels is a lane
# reduction whose result goes to lane j of the tile).  A sub-block is two
# registers tall and its upper one holds no row >= j once j >= 8, so it is
# left out there: a quarter of the work.  Nothing ``[sub, sub, dk]`` is ever
# whole.  The tiles are kept COMPACT, ``[128, 128]`` with a row's tile in
# lanes 0..15 (``_spread`` puts them on the diagonal, ``_compact`` back).

HALF = SUB // 2
G_ROWS, K_ROWS, Q_ROWS = range(3)


def _shifted(x, by):
    """Sub-block s's rows of ``x`` [128, 128] rolled ``by * s`` sub-blocks
    along the lanes."""
    from jax.experimental.pallas import tpu as pltpu

    return jnp.concatenate(
        [x[:SUB]] + [pltpu.roll(x[s * SUB:(s + 1) * SUB],
                                jnp.int32(by * s * SUB % ROWS), 1)
                     for s in range(1, TILES)], axis=0)


def _spread(tiles):
    """Compact tiles [128, 128] (lanes 0..15, zero beyond) -> on the
    diagonal of a [128, 128] array."""
    return _shifted(tiles, 1)


def _compact(full):
    """A [128, 128] array's diagonal tiles -> compact, in lanes 0..15 (what
    the other lanes hold is never read)."""
    return _shifted(full, -1)


def _tile_columns(rows_ref, c, off):
    """A sub-block's tokens one after another: for each token ``jj`` and
    each of the sub-block's halves that holds a row >= jj, (jj, the half,
    ``k_j exp(G_i - G_j)`` [8, dk] masked before the exponential, the
    exponential alone).  ``off``: the sub-block's first row in chunk
    ``c``."""
    halves = [rows_ref[c, G_ROWS, pl.ds(off + h * HALF, HALF), :]
              for h in range(2)]
    at = _iota(halves[0].shape, 0)
    for jj in range(SUB):
        gj = rows_ref[c, G_ROWS, pl.ds(off + jj, 1), :]
        kj = rows_ref[c, K_ROWS, pl.ds(off + jj, 1), :]
        for h in range(jj // HALF, 2):
            apart = halves[h] - gj
            if jj > h * HALF:           # else every row of the half is >= jj
                apart = jnp.where(at >= jj - h * HALF, apart, -jnp.inf)
            e = jnp.exp(apart)
            yield jj, h, kj * e, e


def _diagonal_tiles(rows_ref, tiles_ref, wanted):
    """The compact diagonal tiles of ``k`` (and, with two ``wanted``, of
    ``q``) against ``k`` for every chunk of the step, from ``rows_ref`` [STEP,
    3, 128, dk] (``G``, ``k``, ``q``) into ``tiles_ref`` [STEP, 2, 128,
    128]."""
    lane = _iota((HALF, LANE), 1)

    def sub_block(i, carry):
        c, off = i >> 3, pl.multiple_of((i & (TILES - 1)) * SUB, SUB)
        xs = [[rows_ref[c, K_ROWS + x, pl.ds(off + h * HALF, HALF), :]
               for h in range(2)] for x in range(wanted)]
        tiles = [[jnp.zeros((HALF, LANE), jnp.float32) for _ in range(2)]
                 for _ in range(wanted)]
        for jj, h, kje, _ in _tile_columns(rows_ref, c, off):
            for x in range(wanted):
                tiles[x][h] = jnp.where(lane == jj, _rowsum(xs[x][h] * kje),
                                        tiles[x][h])
        for x in range(wanted):
            tiles_ref[c, x, pl.ds(off, SUB), :] = jnp.concatenate(
                tiles[x], axis=0)
        return carry

    lax.fori_loop(jnp.int32(0), jnp.int32(STEP * TILES), sub_block, 0)


def _diagonal_tiles_bwd(c, rows_ref, tiles_ref, drows_ref, dkcol_ref):
    """The diagonal tiles' cotangents of chunk ``c``, a sub-block a trip:
    from the scores' cotangents ``tiles_ref[c]`` (compact: of ``k``'s, of
    ``q``'s), the row side ``dx_i = sum_j dm_ij k_j E_ij`` into
    ``drows_ref[c]`` (of ``k``, of ``q``) and the column side ``dk_j =
    sum_i dm_ij x_i E_ij`` summed over both into ``dkcol_ref[c]``."""
    def sub_block(s, carry):
        off = pl.multiple_of(s * SUB, SUB)
        xs, dms = ([[ref[c, first + x, pl.ds(off + h * HALF, HALF), :]
                     for h in range(2)] for x in range(2)]
                   for ref, first in ((rows_ref, K_ROWS), (tiles_ref, 0)))
        width = xs[0][0].shape[1]
        dxs = [[jnp.zeros((HALF, width), jnp.float32) for _ in range(2)]
               for _ in range(2)]
        into = None
        for jj, h, kje, e in _tile_columns(rows_ref, c, off):
            cols = [dms[x][h][:, jj:jj + 1] for x in range(2)]
            for x in range(2):
                dxs[x][h] = dxs[x][h] + cols[x] * kje
            part = jnp.sum((cols[0] * xs[0][h] + cols[1] * xs[1][h]) * e,
                           axis=0, keepdims=True)
            into = part if h == jj // HALF else into + part
            if h == 1:
                dkcol_ref[c, pl.ds(off + jj, 1), :] = into
        for x in range(2):
            drows_ref[c, x, pl.ds(off, SUB), :] = jnp.concatenate(
                dxs[x], axis=0)
        return carry

    lax.fori_loop(jnp.int32(0), jnp.int32(TILES), sub_block, 0)


def _channel_systems(low, q_ref, k_ref, v_ref, g_ref, cols_ref, rows_ref,
                     tiles_ref, reads):
    """Everything of each of the step's chunks that no state enters, as
    ``[128, .]`` arrays (head a's tokens, then head b's).  ``reads``: the
    query's scores too (``P``), which only ``O`` and the backward read."""
    f32 = jnp.float32
    at = _iota((ROWS, ROWS), 0)
    to = _iota((ROWS, ROWS), 1)
    own = ((at ^ to) >> 6) == 0             # a head's own block
    below = own & (at > to)
    running = jnp.where(own & (at >= to), f32(1), f32(0))
    out = []
    for c in range(STEP):
        q, k, g = (_halves(x[0, _tokens(c)]) for x in (q_ref, k_ref, g_ref))
        v = _halves(v_ref[0, _tokens(c)].astype(f32))
        beta = cols_ref[0, 0, c * ROWS:(c + 1) * ROWS][:, 0:1]
        gsum = _exact(running, g)           # the running sum inside a chunk
        rows_ref[c, G_ROWS], rows_ref[c, K_ROWS] = gsum, k
        if reads:
            rows_ref[c, Q_ROWS] = q
        heads = [_edges(_head(gsum, r)) for r in range(2)]
        to_edge, to_sub_end, left = (
            jnp.concatenate([h[i] for h in heads], axis=0) for i in range(3))
        kept, mids = [h[3] for h in heads], [h[4] for h in heads]
        xs = (k, q) if reads else (k,)
        right = k * to_sub_end
        stack = jnp.concatenate([_stack(_head(x * to_edge, r), mids[r])
                                 for x in xs for r in range(2)], axis=0)
        res = _mm(stack, right, _NT, low)
        many = stack.shape[0] // (2 * len(xs))
        off = [jnp.concatenate(
            [_tiles_of(res[(2 * i + r) * many:(2 * i + r + 1) * many], r)
             for r in range(2)], axis=0) for i in range(len(xs))]
        gamma = jnp.exp(gsum)
        out.append(dict(
            q=q, k=k, v=v, beta=beta, gamma=gamma, left=left, off=off,
            to_edge=to_edge, to_sub_end=to_sub_end, kept=kept, mids=mids,
            right=right, stack=stack, below=below, to_end=k * left))
    _diagonal_tiles(rows_ref, tiles_ref, 2 if reads else 1)
    for c, m in enumerate(out):
        m["kk"] = m["off"][0] + _spread(tiles_ref[c, 0])
        if reads:
            m.update(reads=m["q"] * m["gamma"],
                     scores=m["off"][1] + _spread(tiles_ref[c, 1]))
    invs = _inverses([jnp.where(below, m["beta"] * m["kk"], 0.0)
                      for m in out])
    for m, inv in zip(out, invs):
        dv = m["v"].shape[1]
        uw = _exact(inv, jnp.concatenate(
            [m["beta"] * m["v"], m["beta"] * m["gamma"] * m["k"]], axis=1))
        m.update(inv=inv, u=uw[:, :dv], w=uw[:, dv:])
    return out


def _channel_walk_kernel(q_ref, k_ref, v_ref, g_ref, cols_ref, out_ref,
                         state, rows_ref, tiles_ref, *, low, emit):
    """A step of the walk under a decay a key channel: its chunks' systems
    first, side by side, then the two states (TRANSPOSED, ``[dv, dk]``:
    their rows' decay runs along the lanes) through them in order.
    ``emit`` 'out': ``O`` [STEP * 64, 2 dv]; 'starts': the two states each
    chunk starts from, [STEP, 2, dv, dk]."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    systems = _channel_systems(low, q_ref, k_ref, v_ref, g_ref, cols_ref,
                               rows_ref, tiles_ref, emit == "out")
    for c, m in enumerate(systems):
        wrote, read = [], []
        for r in range(2):
            start = _cast(low, state[r])
            if emit == "starts":
                out_ref[0, 0, c, r] = start
            # W S and, where O is wanted, (q gamma) S: one product
            both = _head(m["w"], r)
            if emit == "out":
                both = jnp.concatenate([both, _head(m["reads"], r)], axis=0)
            both = _mm(both, start, _NT, low)
            wrote.append(_head(m["u"], r) - both[:CHUNK])
            read.append(both[CHUNK:])           # nothing where no O is
            state[r] = m["kept"][r] * state[r] + _mm(
                wrote[r], _head(m["to_end"], r), _TN, low)
        if emit == "out":
            out = jnp.concatenate(read, axis=0) + _mm(
                m["scores"], jnp.concatenate(wrote, axis=0), _NN, low)
            out_ref[0, _tokens(c)] = _side_by_side(out).astype(out_ref.dtype)


def _channel_bwd_kernel(q_ref, k_ref, v_ref, g_ref, cols_ref, starts_ref,
                        do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dcols_ref,
                        dstate, rows_ref, tiles_ref, drows_ref, dkcol_ref, *,
                        low):
    """A step of the walk from the last chunk to the first: its chunks'
    systems again first, then ``dS`` (transposed, in ``dstate``) back
    through them and every cotangent of each out."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    systems = _channel_systems(low, q_ref, k_ref, v_ref, g_ref, cols_ref,
                               rows_ref, tiles_ref, True)
    for c in reversed(range(STEP)):
        _channel_bwd_chunk(systems[c], c, starts_ref, do_ref, dq_ref, dk_ref,
                           dv_ref, dg_ref, dcols_ref, dstate, rows_ref,
                           tiles_ref, drows_ref, dkcol_ref, low)


def _channel_bwd_chunk(m, c, starts_ref, do_ref, dq_ref, dk_ref, dv_ref,
                       dg_ref, dcols_ref, dstate, rows_ref, tiles_ref,
                       drows_ref, dkcol_ref, low):
    """Chunk ``c`` of a backward step from its system ``m``:
    ``delta_rule._channel_rule_bwd``'s equations, the states transposed."""
    f32 = jnp.float32
    q, k, beta, gamma = m["q"], m["k"], m["beta"], m["gamma"]
    dout = _halves(do_ref[0, _tokens(c)].astype(f32))
    from_out = _mm(m["scores"], dout, _TN, low)
    wrote, dwrote, back, dto_end, at_end = [], [], [], [], []
    for r in range(2):
        start = starts_ref[0, 0, c, r]
        dnext = dstate[r]
        w, to_end = _head(m["w"], r), _head(m["to_end"], r)
        wrote.append(_head(m["u"], r) - _mm(w, start, _NT, low))
        dwrote.append(_head(from_out, r) + _mm(to_end, dnext, _NT, low))
        # against S: dO's read of the state, then dW
        carried = jnp.concatenate([_head(dout, r), -dwrote[r]], axis=0)
        back.append(_mm(carried, start, _NN, low))
        dstate[r] = m["kept"][r] * dnext + _mm(
            carried, jnp.concatenate([_head(m["reads"], r), w], axis=0), _TN,
            low)
        dto_end.append(_mm(wrote[r], dnext, _NN, low))
        # G_C's own: the writes' decay to the chunk's end and the state's
        at_end.append(
            jnp.sum(to_end * dto_end[r], axis=0, keepdims=True)
            + m["kept"][r] * jnp.sum(dnext * start.astype(f32), axis=0,
                                     keepdims=True))
    wrote, dwrote, dto_end = (jnp.concatenate(x, axis=0)
                              for x in (wrote, dwrote, dto_end))
    dreads = jnp.concatenate([x[:CHUNK] for x in back], axis=0)
    dw = jnp.concatenate([x[CHUNK:] for x in back], axis=0)
    dv_width = dout.shape[1]

    # the inverse and its two products, float32 at the highest precision
    dx = _exact(m["inv"], jnp.concatenate([dwrote, dw], axis=1), _TN)
    dxu, dxw = dx[:, :dv_width], dx[:, dv_width:]
    da = jnp.where(m["below"], -_exact(
        dx, jnp.concatenate([m["u"], m["w"]], axis=1), _NT), 0.0)
    # both score matrices back through the split; what dP holds above the
    # diagonal or of the other head is never read
    dms = (beta * da, _mm(dout, wrote, _NT, low))
    dres = jnp.concatenate([_tiles_cotangent(_head(dm, r), r)
                            for dm in dms for r in range(2)], axis=0)
    dstack = _mm(dres, m["right"], _NN, low)
    many = dstack.shape[0] // 4
    dk_rows, dq = (m["to_edge"] * jnp.concatenate(
        [_stack_cotangent(dstack[(2 * i + r) * many:(2 * i + r + 1) * many],
                          m["mids"][r]) for r in range(2)], axis=0)
        for i in range(2))
    for x, dm in enumerate(dms):
        tiles_ref[c, x] = _compact(dm)
    _diagonal_tiles_bwd(c, rows_ref, tiles_ref, drows_ref, dkcol_ref)
    dk_rows, dq = dk_rows + drows_ref[c, 0], dq + drows_ref[c, 1]
    dk_cols = m["to_sub_end"] * _mm(dres, m["stack"], _TN, low) \
        + dkcol_ref[c]
    dgsum = k * dk_rows + q * dq - k * dk_cols
    # the read of the state, the write's decay to the chunk's end, W's
    # operand
    dstep = gamma * dxw                                 # of beta * k
    dq = dq + gamma * dreads
    dk = dk_cols + dk_rows + m["left"] * dto_end + beta * dstep
    dbeta = _rowsum(dxu * m["v"]) + _rowsum(k * dstep) \
        + _rowsum(da * m["kk"])
    dgsum = dgsum + m["reads"] * dreads + beta * k * dstep \
        - m["to_end"] * dto_end
    last = (_iota(dgsum.shape, 0) & (CHUNK - 1)) == CHUNK - 1
    dgsum = dgsum + jnp.where(last, jnp.concatenate(
        [jnp.broadcast_to(x, (CHUNK, x.shape[1])) for x in at_end], axis=0),
        0.0)
    # g's: the running sum of dG from the chunk's end
    running = jnp.where(m["below"] | (_iota((ROWS, ROWS), 0)
                                      == _iota((ROWS, ROWS), 1)),
                        f32(1), f32(0))
    dq_ref[0, _tokens(c)] = _side_by_side(dq)
    dk_ref[0, _tokens(c)] = _side_by_side(dk)
    dg_ref[0, _tokens(c)] = _side_by_side(_exact(running, dgsum, _TN))
    dv_ref[0, _tokens(c)] = _side_by_side(beta * dxu).astype(dv_ref.dtype)
    dcols_ref[0, 0, c * ROWS:(c + 1) * ROWS] = jnp.where(
        _iota((ROWS, LANE), 1) == D_BETA_ALONE, dbeta, 0.0)


def _beta_columns(beta, n):
    """beta [B, n * 64, H] float32 -> the columns [B, pairs, n * 128, 128],
    a pair's two heads in lane 0."""
    cols = _by_pair(beta, n)[..., None]
    return jnp.pad(cols, [(0, 0)] * 3 + [(0, LANE - 1)])


def _channel_specs(b, n, h, dk, dv, backwards):
    """(the grid, the block of q, k or g, of v, of the columns, of the
    transposed states): ``_specs``'s of one value head a key head."""
    return _specs(b, n, h, h, dk, dv, backwards)[:4] + (
        _specs(b, n, h, h, dv, dk, backwards)[5],)


@functools.partial(jax.jit, static_argnums=(0, 6, 7), inline=True)
def _channel_walk(low, q, k, v, g, cols, emit, interpret):
    """``O`` [B, T, H * dv] in v's type (``emit`` 'out') or every chunk's
    two starting states, transposed, [B, pairs, n, 2, dv, dk] in ``low``
    ('starts')."""
    from jax.experimental.pallas import tpu as pltpu

    b, t, h, dk = q.shape
    dv = v.shape[3]
    n = t // CHUNK
    grid, qk_spec, v_spec, cols_spec, state_spec = _channel_specs(
        b, n, h, dk, dv, False)
    if emit == "out":
        out_spec = v_spec
        out_shape = jax.ShapeDtypeStruct((b, t, h * dv), v.dtype)
    else:
        out_spec = state_spec
        out_shape = jax.ShapeDtypeStruct(
            (b, h // 2, n, 2, dv, dk), low or jnp.float32)
    return pl.pallas_call(
        functools.partial(_channel_walk_kernel, low=low, emit=emit),
        out_shape=out_shape, grid=grid,
        in_specs=[qk_spec, qk_spec, v_spec, qk_spec, cols_spec],
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((2, dv, dk), jnp.float32),
                        pltpu.VMEM((STEP, 3, ROWS, dk), jnp.float32),
                        pltpu.VMEM((STEP, 2, ROWS, ROWS), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name="delta_channel_fwd" if emit == "out"
        else "delta_channel_states")(
            q.reshape(b, t, h * dk), k.reshape(b, t, h * dk),
            v.reshape(b, t, h * dv), g.reshape(b, t, h * dk), cols)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def channel_rule(low, q, k, v, g, beta):
    """The rule under a decay a key channel over q, k, g [B, T, H, dk]
    float32 (q, k normed and scaled), v [B, T, H, dv], beta [B, T, H]
    float32, T a multiple of TOKENS -> [B, T, H, dv] in v's type; ``low``:
    the AMP type's name or None."""
    cols = _beta_columns(beta, q.shape[1] // CHUNK)
    return _channel_walk(low, q, k, v, g, cols, "out",
                         kernel_choice.interpret()).reshape(v.shape)


def _channel_rule_fwd(low, *operands):
    return channel_rule(low, *operands), operands


@functools.partial(jax.jit, static_argnums=(0, 8), inline=True)
def _channel_back(low, q, k, v, g, cols, starts, dout, interpret):
    """(dq, dk, dg [B, T, H * dk] float32, dv [B, T, H * dv] in v's type,
    beta's cotangent by column, shaped as the columns)."""
    from jax.experimental.pallas import tpu as pltpu

    b, t, h, dk = q.shape
    dv = v.shape[3]
    grid, qk_spec, v_spec, cols_spec, state_spec = _channel_specs(
        b, t // CHUNK, h, dk, dv, True)
    wide = jax.ShapeDtypeStruct((b, t, h * dk), jnp.float32)
    return pl.pallas_call(
        functools.partial(_channel_bwd_kernel, low=low),
        out_shape=[wide, wide, jax.ShapeDtypeStruct((b, t, h * dv), v.dtype),
                   wide, jax.ShapeDtypeStruct(cols.shape, jnp.float32)],
        grid=grid,
        in_specs=[qk_spec, qk_spec, v_spec, qk_spec, cols_spec, state_spec,
                  v_spec],
        out_specs=[qk_spec, qk_spec, v_spec, qk_spec, cols_spec],
        scratch_shapes=[pltpu.VMEM((2, dv, dk), jnp.float32),
                        pltpu.VMEM((STEP, 3, ROWS, dk), jnp.float32),
                        pltpu.VMEM((STEP, 2, ROWS, ROWS), jnp.float32),
                        pltpu.VMEM((STEP, 2, ROWS, dk), jnp.float32),
                        pltpu.VMEM((STEP, ROWS, dk), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name="delta_channel_bwd")(
            q.reshape(b, t, h * dk), k.reshape(b, t, h * dk),
            v.reshape(b, t, h * dv), g.reshape(b, t, h * dk), cols, starts,
            dout.reshape(b, t, h * dv))


def _channel_rule_bwd(low, operands, dout):
    """The five cotangents from the five operands and ``dout`` alone."""
    q, k, v, g, beta = operands
    n = q.shape[1] // CHUNK
    interpret = kernel_choice.interpret()
    cols = _beta_columns(beta, n)
    starts = _channel_walk(low, q, k, v, g, cols, "starts", interpret)
    dq, dk, dv, dg, dcols = _channel_back(low, q, k, v, g, cols, starts,
                                          dout, interpret)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape), _by_token(dcols[..., D_BETA_ALONE], n))


channel_rule.defvjp(_channel_rule_fwd, _channel_rule_bwd)
