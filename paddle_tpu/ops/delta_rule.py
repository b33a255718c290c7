"""The gated delta rule, a linear attention whose state is a matrix a head
that every token decays, corrects and reads, in the chunked form a training
step needs, with its backward written by hand.  The mathematics of the op
``gated_delta_rule`` and of its grad op (``ops/decoder_ops.py``), plain
``jax.numpy`` with one ``lax.scan`` over the chunks forward and two
backward: the XLA lowering.  Under either kind of decay (a number a value
head, ``_rule``; a vector along the key, ``_channel_rule``) it is the TWIN
of the Pallas kernels beside it (``ops/pallas_delta_rule.py``, the same
equations with a chunk's arrays in VMEM: ``rule`` and ``channel_rule``),
which ``chunked`` calls where the ``flash`` gate is open
(``ops/kernel_choice.py``: a TPU, or the switch) and
``pallas_delta_rule.supported`` gives no reason against
(``kernel_declines``); it is what the CPU runs, what operands the kernels
refuse run everywhere, and the oracle of the kernels' tests.

For one value head (its key head is ``h // (Hv // Hk)``: key head j serves
the value heads ``j * Hv / Hk`` and the ``Hv / Hk - 1`` after it), with
``S_0 = 0`` in ``R^{dk x dv}``, a decay ``g_t <= 0`` and a step ``beta_t``::

    S'  = exp(g_t) * S_{t-1}
    u_t = beta_t * (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

Chunked (``C`` tokens a chunk, ``G_i`` the running sum of g inside the
chunk, ``gamma = exp(G)``, ``e = exp(G_C - G)``, ``D_ij = exp(G_i - G_j)``
for ``i >= j`` else 0, ``S`` the state the chunk starts from)::

    A    = beta_i (k_i . k_j) D_ij                       for i > j, else 0
    T    = (I + A)^{-1}                                  unit lower triangular
    U    = T (beta * v),   W = T (beta * gamma * k)
    P    = (Q K^T) * D
    V'   = U - W S                                       what each token writes
    S   <- exp(G_C) S + K^T (e * V')                     (the walk: these two)
    O    = gamma * (Q S) + P V'

``_system`` makes everything down to ``P`` for all chunks at once.  ``_walk``
goes through the chunks one after another and carries the state and nothing
else: two products a step, which emits ``V'`` and the state the chunk
STARTED from.  ``O`` reads those for all chunks at once, outside the walk.

The backward (``_rule``'s ``jax.custom_vjp``; nothing differentiates through
a scan or through the inverse) keeps the five operands alone.  It makes the
system again and walks again for ``V'`` and every chunk's ``S`` (no ``O``),
makes ``P^T dO`` and ``Q^T (gamma * dO)`` for all chunks at once, then walks
from the last chunk to the first with ``dS`` (zero behind the last), again
two products a step, emitting ``dV'`` and the ``dS`` it came with::

    dV'  = P^T dO + e * (K dS)
    dS  <- Q^T (gamma * dO) + exp(G_C) dS - W^T dV'

and everything else is made for all chunks at once from ``dV'``, ``dS`` (of
the state the chunk hands ON), ``S``, ``V'`` and ``dO``::

    dU   = dV',   dW = -dV' S^T
    d(beta * v) = T^T dU,   d(beta * gamma * k) = T^T dW
    dT   = dU (beta * v)^T + dW (beta * gamma * k)^T     never made:
    dA   = -T^T dT T^T                                   kept where i > j
         = -d(beta * v) U^T - d(beta * gamma * k) W^T
    dP   = dO V'^T
    dQ   = (gamma * dO) S^T + (dP * D) K
    dK   = (dP * D)^T Q + (e * V') dS^T + beta * gamma * d(beta gamma k)
           + (X + X^T) K              with X = beta_i dA_ij D_ij, summed
                                      over the key head's value heads
    dv   = beta * d(beta * v)
    dbeta_i = d(beta v)_i . v_i + gamma_i d(beta gamma k)_i . k_i
              + sum_j dA_ij (k_i . k_j) D_ij
    dG_i = (gamma_i dO_i) . (Q S)_i + beta_i gamma_i d(beta gamma k)_i . k_i
           + sum_j Y_ij - sum_j Y_ji - e_i de_i,   Y = dP * P + dA * A,
           e * de = sum_v (dV' - P^T dO) * V'
    dG_C += sum_i e_i de_i + exp(G_C) <dS, S>
    dg   = the running sum of dG from the chunk's end

so every term that holds an ``exp`` of ``G`` comes back multiplied by that
same ``exp`` and nothing is ever divided by one.

Precision, forward and its mirror image backward.  Decays, their sums, the
inverse and its two products are float32, the products at the highest
matmul precision; so are ``dA`` and the cotangents through ``U`` and ``W``
(``T^T dU``, ``T^T dW``); the carried ``S`` and ``dS`` are float32.  Every
other contraction takes its inputs in the AMP type where ``fluid.amp`` is on
and accumulates in float32; the walks read ``W`` and ``K`` and emit the
states in that type, which is the one their products cast them to.  No
exponent is ever positive: ``exp`` sees ``G_i - G_j`` for ``i >= j`` only,
the rest is masked to ``-inf`` BEFORE the exponential, so a decay that
underflows inside a chunk gives zeros and never ``inf * 0``, in the
cotangents too.

A decay that is a VECTOR along the key (``g`` [B, T, Hv, dk]: one number a
key channel; ``_channel_rule``).  ``exp(g_t)`` scales the state's ROWS,
``S' = Diag(exp(g_t)) S_{t-1}``, and the rest of the recurrence stands.
Chunked, ``G`` [C, dk] and every decay moves to the KEY side::

    A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)       for i > j, else 0
    P_ij =        sum_c q_ic k_jc exp(G_ic - G_jc)       for i >= j, else 0
    U    = T (beta * v),   W = T (beta * exp(G) * k)
    V'   = U - W S
    S   <- Diag(exp(G_C)) S + (k * exp(G_C - G))^T V'
    O    = (q * exp(G)) S + P V'

``exp(G_i - G_j)`` is [C, C, dk] and is never made for a whole chunk, nor
split into ``exp(G_i) exp(-G_j)`` (the second overflows).  It splits about
a point BETWEEN the two (``_pair_scores``): the chunk is cut into
SUB_BLOCKS sub-blocks of ``sub = C / SUB_BLOCKS`` tokens (``sub_block``);
for i in sub-block a and j in an earlier one, with s the last token before
a, ``G_i - G_j = (G_i - G_s) + (G_s - G_j)``, both <= 0, so those tiles are
products of ``x * exp(G - G_s)`` rows with ``k * exp(G_s - G)`` rows; the
SUB_BLOCKS diagonal tiles alone are made element by element, [sub, sub, dk]
each, masked before the exponential.

Its backward (``_channel_rule``'s ``jax.custom_vjp``, PR 53) is the scalar
one with every decay moved to the key side: the five operands alone are
kept, ``_channel_system`` and ``_channel_walk`` are made again (scores and
diagonal tiles with them: nothing of them is kept), ``P^T dO`` and
``(q * gamma)^T dO`` are made for all chunks at once, and ONE walk from the
last chunk to the first carries ``dS``, two products a step
(``E = k * exp(G_C - G)``, what is left of each token's key at the chunk's
end)::

    dV'  = P^T dO + E dS
    dS  <- (q * gamma)^T dO + Diag(exp(G_C)) dS - W^T dV'

and the rest is made for all chunks at once::

    dU   = dV',   dW = -dV' S^T
    d(beta * v) = T^T dU,   d(beta * gamma * k) = T^T dW
    dA   = -d(beta * v) U^T - d(beta * gamma * k) W^T    kept where i > j
    dkk_ij = beta_i dA_ij,   dP = dO V'^T                (its upper half
                                                         is never read)
    for M_ij = sum_c x_ic k_jc exp(G_ic - G_jc), x = k (kk) and x = q (P):
      dx_ic = sum_j dM_ij k_jc exp(G_ic - G_jc)
      dk_jc = sum_i dM_ij x_ic exp(G_ic - G_jc)
      dG_ic += x_ic dx_ic,   dG_jc -= k_jc dk_jc
    dq   = dx(P) + gamma * (dO S^T)
    dk   = dx(kk) + dk(kk) + dk(P) + exp(G_C - G) * de
           + beta * gamma * d(beta gamma k),             de = V' dS^T
    dv   = beta * d(beta * v)
    dbeta_i = d(beta v)_i . v_i + (gamma * d(beta gamma k))_i . k_i
              + sum_j dA_ij kk_ij
    dG  += q * gamma * (dO S^T) + beta * gamma * k * d(beta gamma k)
           - E * de
    dG_C += sum_i (E * de)_i + exp(G_C) * sum_v dS * S   by state row
    dg   = the running sum of dG from the chunk's end

``dx`` and ``dk`` go through the split the scores were made by
(``_pair_scores_bwd``): off the diagonal tiles ``dx = exp(G - G_s) * (dM
right)`` with the same ``right`` rows, and ``dk`` of a token of an earlier
sub-block is ``dM^T (x * exp(G - G_s))`` times that token's own
``exp(G_s - G)``; the SUB_BLOCKS diagonal tiles element by element,
[sub, sub, dk] each, masked before the exponential.  ``G``'s cotangent
needs no pass of its own: every term that holds an ``exp`` of ``G`` comes
back multiplied by that same ``exp``, nothing is divided by a decay and no
exponent is positive.  The two rules share the inverse, its closed-form
cotangent and the helpers, and no walk: the scalar path above is untouched
by this one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_exact = functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)

#: the sub-blocks a chunk's scores are made in under a decay a key channel
SUB_BLOCKS = 4


def sub_block(chunk):
    """The tokens of one of a chunk's SUB_BLOCKS sub-blocks."""
    if chunk % SUB_BLOCKS:
        raise ValueError(f"delta rule: a chunk of {chunk} tokens is not cut "
                         f"into {SUB_BLOCKS} sub-blocks, as a decay a key "
                         f"channel needs it")
    return chunk // SUB_BLOCKS


def _dot(low, spec, a, b):
    """A contraction with float32 accumulation, its inputs in ``low`` (the
    AMP compute type where AMP is on, else None: as they are)."""
    return jnp.einsum(spec, _cast(low, a), _cast(low, b),
                      preferred_element_type=jnp.float32)


def l2norm(x, eps):
    """``x * rsqrt(sum(x^2, last axis) + eps)`` in float32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + jnp.float32(eps))


def unit_lower_inverse(a):
    """``(I + a)^{-1}`` for ``a`` [..., C, C] strictly lower triangular: the
    block forward substitution, from blocks of one to the whole.  With X the
    inverse of the diagonal blocks of size s (zero elsewhere) and L the
    blocks of ``a`` that join an odd block to the even one before it,
    ``X - X L X`` is the inverse of the diagonal blocks of size 2s: two
    [C, C] products a level, log2(C) levels, no loop over rows."""
    c = a.shape[-1]
    row, col = jnp.arange(c)[:, None], jnp.arange(c)[None, :]

    def joins(s):       # block (2p + 1, 2p) of size s
        return (row // (2 * s) == col // (2 * s)) & ((row // s) % 2 == 1) \
            & ((col // s) % 2 == 0)

    x = jnp.eye(c, dtype=a.dtype) - jnp.where(joins(1), a, 0)
    s = 2
    while s < c:
        x = x - _exact(_exact(x, jnp.where(joins(s), a, 0)), x)
        s *= 2
    return x


def solve_cotangents(inv, z, dz):
    """For ``z = inv x`` with ``inv = (I + a)^{-1}``: ``(dx, da)`` from
    ``dz``, ``da`` before the mask that keeps it below the diagonal.
    ``dx = inv^T dz``, and the inverse's closed form ``da = -inv^T dinv
    inv^T`` with ``dinv = dz x^T`` is ``-dx z^T``: no [C, C] x [C, C]
    product is left of it."""
    dx = _exact(_t(inv), dz)
    return dx, -_exact(dx, _t(z))


def _chunks(x, n, c):
    """[B, n * c, H, ...] -> [n, B, H, c, ...]."""
    x = x.reshape((x.shape[0], n, c) + x.shape[2:])
    return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)


def _t(x):
    return jnp.swapaxes(x, -1, -2)


def _cast(low, x):
    return x if low is None else x.astype(low)


def _system(low, qc, kc, vc, gc, bc):
    """Everything of every chunk that no state enters.  qc, kc:
    [n,B,Hk,C,dk]; vc: [n,B,Hk,R,C,dv]; gc, bc: [n,B,Hk,R,C]; all float32."""
    c = qc.shape[-2]
    at = jnp.arange(c)
    gsum = jnp.cumsum(gc, -1)
    decay = jnp.exp(jnp.where(at[:, None] >= at[None, :],
                              gsum[..., :, None] - gsum[..., None, :],
                              -jnp.inf))                    # [n,B,Hk,R,C,C]
    gamma = jnp.exp(gsum)
    kk = _dot(low, "nbhid,nbhjd->nbhij", kc, kc)[:, :, :, None]
    below = at[:, None] > at[None, :]
    inv = unit_lower_inverse(
        jnp.where(below, bc[..., :, None] * kk * decay, 0.0))
    xu = bc[..., None] * vc
    xw = (bc * gamma)[..., None] * kc[:, :, :, None]
    qk = _dot(low, "nbhid,nbhjd->nbhij", qc, kc)[:, :, :, None]
    return dict(
        decay=decay, gamma=gamma, kk=kk, below=below, inv=inv,
        u=_exact(inv, xu), w=_exact(inv, xw), scores=qk * decay,
        # what is left of each token's write, and of the state, at the
        # chunk's end
        to_end=jnp.exp(gsum[..., -1:] - gsum), kept=gamma[..., -1])


def _walk(low, kc, m):
    """The forward walk: (what each token writes, the state each chunk
    starts from), the states in the type the products against them take."""
    def step(state, xs):
        k_i, u_i, w_i, e_i, a_i = xs
        start = _cast(low, state)
        wrote = u_i - _dot(low, "bhrck,bhrkv->bhrcv", w_i, start)
        state = a_i[..., None, None] * state + _dot(
            low, "bhck,bhrcv->bhrkv", k_i, e_i[..., None] * wrote)
        return state, (wrote, start)

    u = m["u"]                                          # [n,B,Hk,R,C,dv]
    _, (wrote, starts) = lax.scan(
        step, jnp.zeros(u.shape[1:4] + (kc.shape[-1], u.shape[-1]),
                        jnp.float32),
        (_cast(low, kc), u, _cast(low, m["w"]), m["to_end"], m["kept"]))
    return wrote, starts


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rule(low, qc, kc, vc, gc, bc):
    """The rule over chunked float32 operands (``_system``'s) ->
    [n,B,Hk,R,C,dv] float32; ``low``: the AMP type's name or None."""
    m = _system(low, qc, kc, vc, gc, bc)
    wrote, starts = _walk(low, kc, m)
    return m["gamma"][..., None] * _dot(
        low, "nbhck,nbhrkv->nbhrcv", qc, starts) \
        + _dot(low, "nbhrcj,nbhrjv->nbhrcv", m["scores"], wrote)


def _rule_fwd(low, *operands):
    return _rule(low, *operands), operands


def _rule_bwd(low, operands, dout):
    """The five cotangents from the five operands and ``dout`` alone (the
    module's docstring has the equations)."""
    qc, kc, vc, gc, bc = operands
    m = _system(low, *operands)
    wrote, starts = _walk(low, kc, m)
    gamma, decay, inv, to_end = m["gamma"], m["decay"], m["inv"], m["to_end"]
    from_out = _dot(low, "nbhrij,nbhriv->nbhrjv", m["scores"], dout)
    read = gamma[..., None] * dout

    def step(dstate, xs):
        k_i, w_i, e_i, a_i, out_i, read_i = xs
        dwrote = out_i + e_i[..., None] * _dot(
            low, "bhck,bhrkv->bhrcv", k_i, dstate)
        before = read_i + a_i[..., None, None] * dstate - _dot(
            low, "bhrck,bhrcv->bhrkv", w_i, dwrote)
        return before, (dwrote, dstate)

    _, (dwrote, dnext) = lax.scan(
        step, jnp.zeros(starts.shape[1:], jnp.float32),
        (_cast(low, kc), _cast(low, m["w"]), to_end, m["kept"], from_out,
         _dot(low, "nbhck,nbhrcv->nbhrkv", qc, read)), reverse=True)

    # the inverse and its two products, float32 at the highest precision
    dw = -_dot(low, "nbhrcv,nbhrkv->nbhrck", dwrote, starts)
    dxu, da_u = solve_cotangents(inv, m["u"], dwrote)
    dxw, da_w = solve_cotangents(inv, m["w"], dw)
    da = jnp.where(m["below"], da_u + da_w, 0.0)
    # dA * A without its beta_i: beta's cotangent by row, and (times beta)
    # that of G_i - G_j
    through = da * m["kk"] * decay
    dkk = jnp.sum(bc[..., :, None] * da * decay, 3)
    dstep = jnp.sum(dxw * kc[:, :, :, None], -1)       # of beta * gamma
    # the scores and the read of the state
    dscores = _dot(low, "nbhriv,nbhrjv->nbhrij", dout, wrote)
    dqk = jnp.sum(dscores * decay, 3)
    dq_read = _dot(low, "nbhrcv,nbhrkv->nbhrck", read, starts)
    written = to_end[..., None] * wrote
    dq = jnp.sum(dq_read, 3) + _dot(low, "nbhij,nbhjd->nbhid", dqk, kc)
    dk = _dot(low, "nbhij,nbhid->nbhjd", dqk, qc) \
        + _dot(low, "nbhrcv,nbhrkv->nbhck", written, dnext) \
        + jnp.sum((bc * gamma)[..., None] * dxw, 3) \
        + _dot(low, "nbhij,nbhjd->nbhid", dkk + _t(dkk), kc)
    dbeta = jnp.sum(dxu * vc, -1) + gamma * dstep + jnp.sum(through, -1)
    # every exponential of G: gamma (read and W), G_i - G_j (scores and A),
    # G_C - G (the write's decay to the chunk's end), G_C (the state's)
    pair = dscores * m["scores"] + bc[..., :, None] * through
    dto_end = jnp.sum((dwrote - from_out) * wrote, -1)
    dgsum = jnp.sum(dq_read * qc[:, :, :, None], -1) \
        + bc * gamma * dstep + jnp.sum(pair, -1) - jnp.sum(pair, -2) - dto_end
    dgsum = dgsum.at[..., -1].add(
        jnp.sum(dto_end, -1) + m["kept"] * jnp.sum(
            dnext * starts.astype(jnp.float32), (-1, -2)))
    dg = lax.cumsum(dgsum, dgsum.ndim - 1, reverse=True)
    return dq, dk, bc[..., None] * dxu, dg, dbeta


_rule.defvjp(_rule_fwd, _rule_bwd)


def _split(kc, gsum):
    """A chunk's decays about its sub-blocks' edges, from kc, gsum
    [..., C, dk]: ``to_edge`` [..., C, dk], each token's decay since the
    last token before its sub-block; ``from_edge`` [..., ns, C, dk], what
    is left at that edge of sub-block a of a token of an EARLIER sub-block
    (0 for every other token); ``within`` [..., ns, sub, sub, dk],
    ``exp(G_i - G_j)`` inside a sub-block where ``i >= j``, else 0; and the
    shape [..., ns, sub, dk] of a chunk cut into its sub-blocks.  Every
    mask comes before its exponential."""
    c, dk = kc.shape[-2:]
    ns, sub = SUB_BLOCKS, sub_block(c)
    at = jnp.arange(c)
    # G at the last token before each sub-block (0 before the first)
    edge = jnp.concatenate([jnp.zeros_like(gsum[..., :1, :]),
                            gsum[..., sub - 1:c - 1:sub, :]], -2)
    to_edge = jnp.exp(gsum - jnp.repeat(edge, sub, -2))         # [.., C, dk]
    earlier = at[None, :] < (jnp.arange(ns) * sub)[:, None]     # [ns, C]
    from_edge = jnp.exp(jnp.where(
        earlier[:, :, None],
        edge[..., :, None, :] - gsum[..., None, :, :], -jnp.inf))
    tiles = kc.shape[:-2] + (ns, sub, dk)
    gd = gsum.reshape(tiles)
    i, j = jnp.arange(sub)[:, None, None], jnp.arange(sub)[None, :, None]
    within = jnp.exp(jnp.where(
        i >= j, gd[..., :, None, :] - gd[..., None, :, :], -jnp.inf))
    return to_edge, from_edge, within, tiles


def _pair_scores(low, xs, kc, gsum):
    """``sum_c x_ic k_jc exp(G_ic - G_jc)`` where ``i >= j``, else 0, for
    every ``x`` of ``xs``: [..., C, C] each from x, kc, gsum [..., C, dk].
    No exponent is positive and nothing [C, C, dk] is made: the module's
    docstring has the split."""
    c = kc.shape[-2]
    lead = kc.shape[:-2]
    to_edge, from_edge, within, tiles = _split(kc, gsum)
    right = kc[..., None, :, :] * from_edge
    kd = kc.reshape(tiles)
    eye = jnp.eye(SUB_BLOCKS, dtype=jnp.float32)
    out = []
    for x in xs:
        off = _dot(low, "...aid,...ajd->...aij",
                   (x * to_edge).reshape(tiles), right)
        diag = jnp.sum(x.reshape(tiles)[..., :, None, :]
                       * kd[..., None, :, :] * within, -1)
        out.append(off.reshape(lead + (c, c)) + jnp.einsum(
            "...aij,ab->...aibj", diag, eye).reshape(lead + (c, c)))
    return out


def _pair_scores_bwd(low, xs, kc, gsum, dms):
    """``_pair_scores``'s cotangents through the same split: for every
    ``x`` of ``xs`` and its ``dm`` [..., C, C] (the cotangent of its
    scores; what it holds above the diagonal is never read, ON the diagonal
    it counts), ``(every dx, dk, dgsum)`` with ``dk`` and ``dgsum`` summed
    over ``xs``.  Off the diagonal tiles ``dx = to_edge * (dm right)`` and
    ``dk`` is ``dm^T (x * to_edge)`` times the token's own decay to the
    sub-block's edge; the diagonal tiles go element by element.  ``G``'s
    needs no pass of its own: ``x * dx`` by row less ``k * dk`` by column,
    every exponential coming back times itself."""
    c = kc.shape[-2]
    ns, sub = SUB_BLOCKS, sub_block(c)
    lead = kc.shape[:-2]
    to_edge, from_edge, within, tiles = _split(kc, gsum)
    right = kc[..., None, :, :] * from_edge
    kd = kc.reshape(tiles)
    dxs, dk_off, dk_diag = [], 0.0, 0.0
    for x, dm in zip(xs, dms):
        rows = dm.reshape(lead + (ns, sub, c))
        dx_off = to_edge * _dot(low, "...aij,...ajd->...aid", rows,
                                right).reshape(x.shape)
        dk_off += _dot(low, "...aij,...aid->...ajd", rows,
                       (x * to_edge).reshape(tiles))
        tile = jnp.stack([dm[..., a * sub:(a + 1) * sub,
                             a * sub:(a + 1) * sub] for a in range(ns)],
                         -3)[..., None] * within       # [.., ns,sub,sub,dk]
        dxs.append(dx_off + jnp.sum(tile * kd[..., None, :, :], -2
                                    ).reshape(x.shape))
        dk_diag += tile * x.reshape(tiles)[..., :, None, :]
    dk = jnp.sum(dk_off * from_edge, -3) + jnp.sum(dk_diag, -3).reshape(
        kc.shape)
    return dxs, dk, sum(x * dx for x, dx in zip(xs, dxs)) - kc * dk


def _channel_system(low, qc, kc, vc, gc, bc):
    """Everything of every chunk that no state enters, under a decay a key
    channel.  qc, kc, gc: [n,B,H,C,dk]; vc: [n,B,H,C,dv]; bc: [n,B,H,C];
    all float32."""
    c = qc.shape[-2]
    gsum = jnp.cumsum(gc, -2)
    gamma = jnp.exp(gsum)
    kk, scores = _pair_scores(low, (kc, qc), kc, gsum)
    at = jnp.arange(c)
    below = at[:, None] > at[None, :]
    inv = unit_lower_inverse(jnp.where(below, bc[..., None] * kk, 0.0))
    # what is left of each token's write, and of the state's rows, at the
    # chunk's end
    left = jnp.exp(gsum[..., -1:, :] - gsum)
    return dict(
        gsum=gsum, gamma=gamma, kk=kk, below=below, inv=inv, scores=scores,
        u=_exact(inv, bc[..., None] * vc),
        w=_exact(inv, bc[..., None] * gamma * kc),
        left=left, to_end=kc * left, kept=gamma[..., -1, :])


def _channel_walk(low, m):
    """The forward walk: (what each token writes, the state each chunk
    starts from), the states in the type the products against them take."""
    def step(state, xs):
        u_i, w_i, e_i, a_i = xs
        start = _cast(low, state)
        wrote = u_i - _dot(low, "bhck,bhkv->bhcv", w_i, start)
        state = a_i[..., None] * state + _dot(low, "bhck,bhcv->bhkv", e_i,
                                              wrote)
        return state, (wrote, start)

    u, w = m["u"], m["w"]                       # [n,B,H,C,dv], [n,B,H,C,dk]
    _, (wrote, starts) = lax.scan(
        step, jnp.zeros(u.shape[1:3] + (w.shape[-1], u.shape[-1]),
                        jnp.float32),
        (u, _cast(low, w), _cast(low, m["to_end"]), m["kept"]))
    return wrote, starts


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _channel_rule(low, qc, kc, vc, gc, bc):
    """The rule under a decay a key channel over chunked float32 operands
    (``_channel_system``'s) -> [n,B,H,C,dv] float32; ``low``: the AMP
    type's name or None."""
    m = _channel_system(low, qc, kc, vc, gc, bc)
    wrote, starts = _channel_walk(low, m)
    return _dot(low, "nbhck,nbhkv->nbhcv", qc * m["gamma"], starts) \
        + _dot(low, "nbhcj,nbhjv->nbhcv", m["scores"], wrote)


def _channel_rule_fwd(low, *operands):
    return _channel_rule(low, *operands), operands


def _channel_rule_bwd(low, operands, dout):
    """The five cotangents from the five operands and ``dout`` alone (the
    module's docstring has the equations)."""
    qc, kc, vc, gc, bc = operands
    m = _channel_system(low, *operands)
    wrote, starts = _channel_walk(low, m)
    gamma, inv, to_end = m["gamma"], m["inv"], m["to_end"]
    reads = qc * gamma
    from_out = _dot(low, "nbhij,nbhiv->nbhjv", m["scores"], dout)

    def step(dstate, xs):
        w_i, e_i, a_i, out_i, read_i = xs
        dwrote = out_i + _dot(low, "bhck,bhkv->bhcv", e_i, dstate)
        before = read_i + a_i[..., None] * dstate - _dot(
            low, "bhck,bhcv->bhkv", w_i, dwrote)
        return before, (dwrote, dstate)

    _, (dwrote, dnext) = lax.scan(
        step, jnp.zeros(starts.shape[1:], jnp.float32),
        (_cast(low, m["w"]), _cast(low, to_end), m["kept"], from_out,
         _dot(low, "nbhck,nbhcv->nbhkv", reads, dout)), reverse=True)

    # the inverse and its two products, float32 at the highest precision
    dw = -_dot(low, "nbhcv,nbhkv->nbhck", dwrote, starts)
    dxu, da_u = solve_cotangents(inv, m["u"], dwrote)
    dxw, da_w = solve_cotangents(inv, m["w"], dw)
    da = jnp.where(m["below"], da_u + da_w, 0.0)
    # both score matrices; what dP holds above the diagonal is not read
    (dk_rows, dq), dk, dgsum = _pair_scores_bwd(
        low, (kc, qc), kc, m["gsum"],
        (bc[..., None] * da,
         _dot(low, "nbhiv,nbhjv->nbhij", dout, wrote)))
    # the read of the state, the write's decay to the chunk's end, W's
    # operand
    dreads = _dot(low, "nbhcv,nbhkv->nbhck", dout, starts)
    dto_end = _dot(low, "nbhcv,nbhkv->nbhck", wrote, dnext)
    dstep = gamma * dxw                                 # of beta * k
    written = to_end * dto_end
    dq = dq + gamma * dreads
    dk = dk + dk_rows + m["left"] * dto_end + bc[..., None] * dstep
    dbeta = jnp.sum(dxu * vc, -1) + jnp.sum(kc * dstep, -1) \
        + jnp.sum(da * m["kk"], -1)
    # every exponential of G: gamma (read and W), G_i - G_j (scores and A,
    # above), G_C - G (the write's decay to the chunk's end), G_C (the
    # state's rows)
    dgsum = dgsum + reads * dreads + bc[..., None] * kc * dstep - written
    dgsum = dgsum.at[..., -1, :].add(
        jnp.sum(written, -2) + m["kept"] * jnp.sum(
            dnext * starts.astype(jnp.float32), -1))
    dg = lax.cumsum(dgsum, dgsum.ndim - 2, reverse=True)
    return dq, dk, bc[..., None] * dxu, dg, dbeta


_channel_rule.defvjp(_channel_rule_fwd, _channel_rule_bwd)


def kernel_declines(q, k, v, g, chunk):
    """Why the Pallas kernels (``ops/pallas_delta_rule.py``: the scalar
    rule's for ``g`` [B, T, Hv], the channel rule's for ``g`` [B, T, Hv,
    dk]) do not take these operands of ``chunked``: '' where they do, else
    ``pallas_delta_rule.supported``'s reason ('chunk', 'width', 'heads'),
    and None where the ``flash`` gate is closed and nothing was asked of
    them."""
    from . import kernel_choice, pallas_delta_rule

    if not kernel_choice.gate("flash"):
        return None
    return pallas_delta_rule.supported(q, k, v, g, chunk)


def chunked(q, k, v, g, beta, chunk=64, scale=0.0, norm_eps=0.0):
    """q, k: [B, T, Hk, dk]; v: [B, T, Hv, dv]; g, beta: [B, T, Hv] ->
    [B, T, Hv, dv] in v's type.  ``scale`` multiplies q (0: ``dk ** -0.5``);
    ``norm_eps`` > 0: q and k are l2-normed per head first, with that
    epsilon.  ``T`` need not be a multiple of ``chunk`` (for the kernels: of
    a grid step's tokens): the tail is padded with tokens that write
    nothing (beta 0) and decay nothing (g 0).  A
    ``g`` [B, T, Hv, dk] is a decay a key channel (``_channel_rule``), whose
    chunk is a multiple of SUB_BLOCKS.  Norm, scale and padding are made
    here for either path; then the Pallas kernels of that kind of decay
    where they are asked and take the operands (``kernel_declines``), else
    ``_rule`` or ``_channel_rule``."""
    from ..fluid import amp

    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    rep = hv // hk
    f32 = jnp.float32
    if norm_eps:
        q, k = l2norm(q, norm_eps), l2norm(k, norm_eps)
    q = q.astype(f32) * f32(scale or dk ** -0.5)
    k = k.astype(f32)
    takes, whole = kernel_declines(q, k, v, g, chunk) == "", chunk
    if takes:
        from . import pallas_delta_rule

        whole = pallas_delta_rule.TOKENS    # a grid step: a few chunks
    pad = -t % whole
    if pad:
        q, k, v, g, beta = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (
            a.ndim - 2)) for a in (q, k, v, g, beta))
    n = (t + pad) // chunk
    if takes:
        rule = pallas_delta_rule.rule if g.ndim == 3 \
            else pallas_delta_rule.channel_rule
        out = rule(amp.compute_dtype(), q, k, v, g.astype(f32),
                   beta.astype(f32))
        # behind a barrier: where the norm after the rule reads the kernel's
        # result as it lies, XLA keeps that norm's statistic broadcast to
        # the result's shape from the forward pass to the backward, 134 MB
        # a layer of Qwen3-Next (read from ``preallocated-temp`` of a
        # described-chip compile: 5.89 GB without, 5.17 with, 5.57 on the
        # XLA path)
        return lax.optimization_barrier(out[:, :t])
    if g.ndim == 4:
        # every value head its own decayed keys: a key head is repeated
        q, k = (jnp.repeat(a, rep, 2) if rep > 1 else a for a in (q, k))
        out = _channel_rule(amp.compute_dtype(), *(
            _chunks(a.astype(f32), n, chunk) for a in (q, k, v, g, beta)))
        out = jnp.transpose(out, (1, 0, 3, 2, 4)).reshape(b, t + pad, hv, dv)
        return out[:, :t].astype(v.dtype)

    def per_value(a):       # [B, T, Hv, ...] -> [n, B, Hk, R, C, ...]
        a = a.astype(f32).reshape((b, t + pad, hk, rep) + a.shape[3:])
        return jnp.moveaxis(_chunks(a, n, chunk), 4, 3)

    out = _rule(amp.compute_dtype(), _chunks(q, n, chunk),
                _chunks(k, n, chunk), per_value(v), per_value(g),
                per_value(beta))
    # [n,B,Hk,R,C,dv] -> [B, n*C, Hv, dv]
    out = jnp.transpose(out, (1, 0, 4, 2, 3, 5)).reshape(b, t + pad, hv, dv)
    return out[:, :t].astype(v.dtype)
