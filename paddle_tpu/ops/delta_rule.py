"""The gated delta rule, a linear attention whose state is a matrix a head
that every token decays, corrects and reads, in the chunked form a training
step needs.  The mathematics of the op ``gated_delta_rule``
(``ops/decoder_ops.py``), plain ``jax.numpy`` with one ``lax.scan`` over the
chunks: the XLA lowering, and what the CPU runs.

For one value head (its key head is ``h // (Hv // Hk)``: key head j serves
the value heads ``j * Hv / Hk`` and the ``Hv / Hk - 1`` after it), with
``S_0 = 0`` in ``R^{dk x dv}``, a decay ``g_t <= 0`` and a step ``beta_t``::

    S'  = exp(g_t) * S_{t-1}
    u_t = beta_t * (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

Chunked (``C`` tokens a chunk, ``G_i`` the running sum of g inside the
chunk, ``S`` the state the chunk starts from)::

    A_ij = beta_i (k_i . k_j) exp(G_i - G_j)             for i > j, else 0
    T    = (I + A)^{-1}                                  unit lower triangular
    U    = T (beta * v),   W = T (beta * exp(G) * k)
    V'   = U - W S                                       what each token writes
    o_i  = exp(G_i) (q_i S) + sum_{j <= i} (q_i . k_j) exp(G_i - G_j) V'_j
    S   <- exp(G_C) S + sum_j exp(G_C - G_j) k_j V'_j^T

Everything up to ``W`` is made for all chunks at once; only the last three
lines walk the chunks one after another.  Decays, their sums, the inverse,
its two products and the state are float32 (the inverse and its products at
the highest matmul precision); the other contractions take their inputs in
the AMP type where ``fluid.amp`` is on and accumulate in float32.  No
exponent is ever positive: ``exp`` sees ``G_i - G_j`` for ``i >= j`` only,
the rest is masked to ``-inf`` BEFORE the exponential, so a decay that
underflows inside a chunk gives zeros and never ``inf * 0``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_exact = functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)


def _dot(spec, a, b):
    """A contraction with float32 accumulation, its inputs in the AMP
    compute type where AMP is on."""
    from ..fluid import amp

    low = amp.compute_dtype()
    if low is not None:
        a, b = a.astype(low), b.astype(low)
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


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


def _chunks(x, n, c):
    """[B, n * c, H, ...] -> [n, B, H, c, ...]."""
    x = x.reshape((x.shape[0], n, c) + x.shape[2:])
    return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)


def chunked(q, k, v, g, beta, chunk=64, scale=0.0, norm_eps=0.0):
    """q, k: [B, T, Hk, dk]; v: [B, T, Hv, dv]; g, beta: [B, T, Hv] ->
    [B, T, Hv, dv] in v's type.  ``scale`` multiplies q (0: ``dk ** -0.5``);
    ``norm_eps`` > 0: q and k are l2-normed per head first, with that
    epsilon.  ``T`` need not be a multiple of ``chunk``: the tail is padded
    with tokens that write nothing (beta 0) and decay nothing (g 0)."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    rep = hv // hk
    f32 = jnp.float32
    if norm_eps:
        q, k = l2norm(q, norm_eps), l2norm(k, norm_eps)
    q = q.astype(f32) * f32(scale or dk ** -0.5)
    k = k.astype(f32)
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (
            a.ndim - 2)) for a in (q, k, v, g, beta))
    n = (t + pad) // chunk

    def per_value(a):       # [B, T, Hv, ...] -> [n, B, Hk, R, C, ...]
        a = a.astype(f32).reshape((b, t + pad, hk, rep) + a.shape[3:])
        return jnp.moveaxis(_chunks(a, n, chunk), 4, 3)

    qc, kc = _chunks(q, n, chunk), _chunks(k, n, chunk)     # [n,B,Hk,C,dk]
    vc, gc, bc = per_value(v), per_value(g), per_value(beta)
    gsum = jnp.cumsum(gc, -1)
    at = jnp.arange(chunk)
    seen = at[:, None] >= at[None, :]
    decay = jnp.exp(jnp.where(seen, gsum[..., :, None] - gsum[..., None, :],
                              -jnp.inf))                    # [n,B,Hk,R,C,C]
    kk = _dot("nbhid,nbhjd->nbhij", kc, kc)[:, :, :, None]
    a = jnp.where(at[:, None] > at[None, :],
                  bc[..., :, None] * kk * decay, 0.0)
    inv = unit_lower_inverse(a)
    u = _exact(inv, bc[..., None] * vc)                     # [n,B,Hk,R,C,dv]
    w = _exact(inv, (bc * jnp.exp(gsum))[..., None] * kc[:, :, :, None])
    scores = _dot("nbhid,nbhjd->nbhij", qc, kc)[:, :, :, None] * decay
    # what is left of each token's write at the chunk's end
    to_end = jnp.exp(gsum[..., -1:] - gsum)

    @jax.checkpoint
    def step(state, xs):
        q_i, k_i, u_i, w_i, s_i, g_i, e_i = xs
        wrote = u_i - _dot("bhrck,bhrkv->bhrcv", w_i, state)
        out = jnp.exp(g_i)[..., None] \
            * _dot("bhck,bhrkv->bhrcv", q_i, state) \
            + _dot("bhrcj,bhrjv->bhrcv", s_i, wrote)
        state = jnp.exp(g_i[..., -1])[..., None, None] * state \
            + _dot("bhck,bhrcv->bhrkv", k_i, e_i[..., None] * wrote)
        return state, out

    _, out = lax.scan(step, jnp.zeros((b, hk, rep, dk, dv), f32),
                      (qc, kc, u, w, scores, gsum, to_end))
    # [n,B,Hk,R,C,dv] -> [B, n*C, Hv, dv]
    out = jnp.transpose(out, (1, 0, 4, 2, 3, 5)).reshape(b, t + pad, hv, dv)
    return out[:, :t].astype(v.dtype)
