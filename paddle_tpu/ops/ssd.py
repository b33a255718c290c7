"""A selective state-space scan (the recurrence of a Mamba-2 mixer) in the
chunked "state-space dual" form a training step needs, with its backward
written by hand.  The mathematics of the op ``ssd_scan`` and of its grad op
(``ops/decoder_ops.py``), plain ``jax.numpy`` with one ``lax.scan`` over the
chunks forward and one backward: the XLA lowering, the twin and the oracle
of the Pallas kernels beside it (``ops/pallas_ssd.py``, the same equations
with a chunk's decays, scores and cotangents in VMEM), which ``chunked``
runs where the ``flash`` gate is open and ``pallas_ssd.supported`` gives no
reason against (``kernel_declines``); it is what the CPU runs and what
operands the kernels refuse run everywhere.

For one head h of P columns (it reads the B and C of group ``h // (H //
G)``: group j serves the heads ``j * H / G`` and the ``H / G - 1`` after
it), with ``S_0 = 0`` in ``R^{P x N}``, a step ``delta_t > 0`` and one
negative number ``A`` a head::

    S_t = exp(delta_t A) S_{t-1} + delta_t u_t B_t^T
    y_t = S_t C_t + D u_t

A decay a head and token, diagonal in the state; no delta correction, so a
chunk holds no system to solve and no inverse.  Chunked (``c`` tokens a
chunk, ``a = delta A``, ``cum`` the running sum of ``a`` inside the chunk,
``x = delta * u``, ``L_ts = exp(cum_t - cum_s)`` for ``s <= t`` else 0,
``S`` the state the chunk starts from)::

    Y    = ((C B^T) * L) x + exp(cum) * (C S^T) + D u
    Z    = (exp(cum_c - cum) * x)^T B                what the chunk writes
    S   <- exp(cum_c) S + Z                          (the walk: this line)

Everything but the last line is made for all chunks at once, ``Z`` too: no
state enters it.  ``_walk`` goes through the chunks one after another,
carries the float32 state and nothing else, one multiply-add a step and no
product, and emits the state every chunk STARTED from.

The backward (``_scan``'s ``jax.custom_vjp``; nothing differentiates
through the walk) keeps the operands alone.  It makes ``cum``, ``L``,
``C B^T``, ``Z`` and every chunk's ``S`` again (no ``Y``), makes the
cotangent that each chunk's READ of its state leaves, ``R = (exp(cum) *
dY)^T C``, for all chunks at once, then walks from the last chunk to the
first with ``dS`` (zero behind the last), again one multiply-add a step,
emitting the ``dS`` of the state each chunk hands ON::

    dS  <- R + exp(cum_c) dS

and everything else is made for all chunks at once from ``dY``, ``dS`` and
``S``::

    dM   = dY x^T                 of M = (C B^T) * L, read where s <= t
    dx   = M^T dY + e * (B dS^T),                 e = exp(cum_c - cum)
    dC   = sum_heads (dM * L) B + sum_heads (exp(cum) * dY) S
    dB   = sum_heads (dM * L)^T C + sum_heads (e * x) dS
    dcum = rows(dM * M) - cols(dM * M) + (exp(cum) * dY) . (C S^T)
           - e * (x . (B dS^T))
    dcum_c += sum_t e_t (x . (B dS^T))_t + exp(cum_c) <dS, S>
    da   = the running sum of dcum from the chunk's end
    du   = delta * dx + D dY,   ddelta = u . dx + A da
    dA   = sum da * delta,      dD = sum dY . u

so every term that holds an ``exp`` of ``cum`` comes back multiplied by
that same ``exp``, nothing is divided by a decay, and no exponent is ever
positive: ``exp`` sees ``cum_t - cum_s`` for ``t >= s`` only, the rest is
masked to ``-inf`` BEFORE the exponential, so a decay that underflows
inside a chunk gives zeros and never ``inf * 0``.

Precision.  The step, the decays, their running sums and the carried ``S``
and ``dS`` are float32.  Every contraction takes its inputs in the AMP type
where ``fluid.amp`` is on (``L`` times the scores and the states among
them) and accumulates in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _cast(low, x):
    return x if low is None else x.astype(low)


def _dot(low, spec, a, b):
    """A contraction with float32 accumulation, its inputs in ``low`` (the
    AMP compute type where AMP is on, else None: as they are)."""
    return jnp.einsum(spec, _cast(low, a), _cast(low, b),
                      preferred_element_type=jnp.float32)


def _chunk_terms(low, uc, dc, a, bc, cc):
    """Everything of every chunk that no state enters.  uc: [n,B,G,R,c,P];
    dc: [n,B,G,R,c]; a: [G,R]; bc, cc: [n,B,G,c,N]; all float32."""
    c = uc.shape[-2]
    at = jnp.arange(c)
    cum = jnp.cumsum(dc * a[:, :, None], -1)                 # [n,B,G,R,c]
    decay = jnp.exp(jnp.where(at[:, None] >= at[None, :],
                              cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                     # [n,B,G,R,c,c]
    x = dc[..., None] * uc
    to_end = jnp.exp(cum[..., -1:] - cum)
    # what is left at the chunk's end of what each token adds
    left = to_end[..., None] * x
    return dict(
        x=x, decay=decay, gamma=jnp.exp(cum), to_end=to_end, left=left,
        kept=jnp.exp(cum[..., -1]),
        scores=_dot(low, "nbgtk,nbgsk->nbgts", cc, bc)[:, :, :, None]
        * decay,
        wrote=_dot(low, "nbgrcp,nbgck->nbgrpk", left, bc))


def _walk(kept, wrote, reverse=False):
    """The state each chunk starts from (``reverse``: the cotangent of the
    state each chunk hands on), float32 [n,B,G,R,P,N], by ``S <- kept * S +
    wrote`` from zeros."""
    def step(state, xs):
        k_i, w_i = xs
        return k_i[..., None, None] * state + w_i, state

    return lax.scan(step, jnp.zeros(wrote.shape[1:], jnp.float32),
                    (kept, wrote), reverse=reverse)[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scan(low, uc, dc, a, bc, cc, d):
    """The scan over chunked float32 operands (``_chunk_terms``'s, and d:
    [G,R]) -> [n,B,G,R,c,P] float32; ``low``: the AMP type's name or None."""
    m = _chunk_terms(low, uc, dc, a, bc, cc)
    starts = _walk(m["kept"], m["wrote"])
    return _dot(low, "nbgrts,nbgrsp->nbgrtp", m["scores"], m["x"]) \
        + m["gamma"][..., None] * _dot(low, "nbgck,nbgrpk->nbgrcp", cc,
                                       starts) \
        + d[:, :, None, None] * uc


def _scan_fwd(low, *operands):
    return _scan(low, *operands), operands


def _scan_bwd(low, operands, dout):
    """The six cotangents from the six operands and ``dout`` alone (the
    module's docstring has the equations)."""
    uc, dc, a, bc, cc, d = operands
    m = _chunk_terms(low, uc, dc, a, bc, cc)
    x, decay, gamma, to_end, kept = (m[k] for k in (
        "x", "decay", "gamma", "to_end", "kept"))
    starts = _walk(kept, m["wrote"])
    read = gamma[..., None] * dout
    dnext = _walk(kept, _dot(low, "nbgrcp,nbgck->nbgrpk", read, cc),
                  reverse=True)
    # the scores: L times C B^T, read below the diagonal and on it
    dscores = _dot(low, "nbgrtp,nbgrsp->nbgrts", dout, x)
    dcb = jnp.sum(dscores * decay, 3)                        # [n,B,G,c,c]
    pair = dscores * m["scores"]
    # what each token wrote, decayed to the chunk's end, meets dS
    met = _dot(low, "nbgck,nbgrpk->nbgrcp", bc, dnext)
    dx = _dot(low, "nbgrts,nbgrtp->nbgrsp", m["scores"], dout) \
        + to_end[..., None] * met
    dto_end = to_end * jnp.sum(met * x, -1)
    dc_ = _dot(low, "nbgts,nbgsk->nbgtk", dcb, bc) \
        + _dot(low, "nbgrcp,nbgrpk->nbgck", read, starts)
    db = _dot(low, "nbgts,nbgtk->nbgsk", dcb, cc) \
        + _dot(low, "nbgrcp,nbgrpk->nbgck", m["left"], dnext)
    # every exponential of cum: L (scores), gamma (the read of the state),
    # cum_c - cum (the write's decay to the chunk's end), cum_c (the state's)
    dcum = jnp.sum(pair, -1) - jnp.sum(pair, -2) - dto_end + jnp.sum(
        read * _dot(low, "nbgck,nbgrpk->nbgrcp", cc, starts), -1)
    dcum = dcum.at[..., -1].add(
        jnp.sum(dto_end, -1) + kept * jnp.sum(dnext * starts, (-1, -2)))
    da = lax.cumsum(dcum, dcum.ndim - 1, reverse=True)
    return (dc[..., None] * dx + d[:, :, None, None] * dout,
            jnp.sum(dx * uc, -1) + da * a[:, :, None],
            jnp.sum(da * dc, (0, 1, 4)), db, dc_,
            jnp.sum(dout * uc, (0, 1, 4, 5)))


_scan.defvjp(_scan_fwd, _scan_bwd)


def kernel_declines(u, delta, b, c, chunk, groups):
    """Why the Pallas kernels (``ops/pallas_ssd.py``) do not take these
    operands of ``chunked``: '' where they do, else ``pallas_ssd.supported``'s
    reason ('chunk', 'width', 'heads'), and None where the ``flash`` gate is
    closed and nothing was asked of them."""
    from . import kernel_choice, pallas_ssd

    if not kernel_choice.gate("flash"):
        return None
    return pallas_ssd.supported(u, delta, b, c, chunk, groups)


def chunked(u, delta, a, b, c, d, chunk=128, groups=1):
    """u: [B, T, H, P]; delta: [B, T, H] (> 0); a, d: [H] (a < 0); b, c:
    [B, T, groups * N] -> [B, T, H, P] in u's type.  Head h reads group
    ``h // (H // groups)``.  ``T`` need not be a multiple of ``chunk`` (for
    the kernels: of a grid step's tokens): the tail is padded with tokens
    whose step is 0, which decay nothing and write nothing.  The Pallas
    kernels where they are asked and take the operands
    (``kernel_declines``), else ``_scan``."""
    from ..fluid import amp

    bsz, t, h, p = u.shape
    if h % groups or b.shape[-1] % groups:
        raise ValueError(f"ssd scan: {h} heads and B, C {b.shape[-1]} wide "
                         f"do not divide over {groups} groups")
    rep, state = h // groups, b.shape[-1] // groups
    f32 = jnp.float32
    if kernel_declines(u, delta, b, c, chunk, groups) == "":
        from . import pallas_ssd

        pad = -t % pallas_ssd.TOKENS        # a grid step: a few chunks
        if pad:
            u, delta, b, c = (jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (
                x.ndim - 2)) for x in (u, delta, b, c))
        out = pallas_ssd.scan(amp.compute_dtype(), groups, u,
                              delta.astype(f32), a.astype(f32), b, c,
                              d.astype(f32))
        # behind a barrier, as the delta rule's kernels' result: XLA must
        # not keep the statistic of the norm that reads it, broadcast to
        # its shape, from the forward pass to the backward
        return lax.optimization_barrier(out[:, :t])
    pad = -t % chunk
    n = (t + pad) // chunk

    def chunks(x, *tail):       # [B, T, ...] -> [n, B, ..., c, ...]
        x = x.astype(f32)
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        return jnp.moveaxis(x.reshape((bsz, n, chunk) + tail), 1, 0)

    # [n,B,c,G,R,..] -> [n,B,G,R,c,..]; [n,B,c,G,N] -> [n,B,G,c,N]
    out = _scan(
        amp.compute_dtype(),
        jnp.transpose(chunks(u, groups, rep, p), (0, 1, 3, 4, 2, 5)),
        jnp.transpose(chunks(delta, groups, rep), (0, 1, 3, 4, 2)),
        a.astype(f32).reshape(groups, rep),
        jnp.swapaxes(chunks(b, groups, state), 2, 3),
        jnp.swapaxes(chunks(c, groups, state), 2, 3),
        d.astype(f32).reshape(groups, rep))
    # [n,B,G,R,c,P] -> [B, n*c, H, P]
    out = jnp.transpose(out, (1, 0, 4, 2, 3, 5)).reshape(bsz, t + pad, h, p)
    return out[:, :t].astype(u.dtype)


def scan_flops(tokens, heads, head_dim, state):
    """FLOPs of the RECURRENCE over ``tokens`` tokens as it is stated: a
    token and head decays its ``[head_dim, state]`` state, adds a rank-one
    write to it and reads it along C, three multiply-adds an element of the
    state at two operations each (``D u`` is not counted).  The chunked
    form's own products are how the program gets there and are not
    counted."""
    return 2 * tokens * heads * 3 * head_dim * state
