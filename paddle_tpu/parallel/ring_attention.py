"""Ring attention: sequence/context parallelism over the ICI ring.

A capability the reference does NOT have (SURVEY.md §5.7 / §2.6: SP/CP are
"Absent" — the reference scales sequence length only by LoD packing on one
device).  Here long sequences shard across the "sp" mesh axis: each device
holds a [T/S] slice of Q, K and V, and attention runs as S ring steps — the
local Q block attends to the resident K/V block while K/V rotate one
neighbor per step via ``lax.ppermute`` (pure ICI traffic, no all-gather).
Softmax is computed ONLINE (running max / denominator, the flash-attention
recurrence), so memory stays O(T/S * T/S) per step instead of O(T^2) and
the result is bit-for-bit equivalent to full softmax attention up to fp
reassociation.

Ref analogues for the mechanics it replaces: the pserver would ship whole
tensors (grpc_server.cc); GSPMD's default for sharded-sequence attention
would all-gather K/V.  The ring keeps peak memory flat and overlaps
transfer with compute — the standard TPU recipe (Liu et al., Ring
Attention; jax-ml scaling-book collectives chapter).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _amp_einsum(spec, a, b):
    """Contraction under the shared AMP recipe (fluid/amp.py einsum):
    bf16 operands on the MXU, fp32 activation contract restored."""
    from ..fluid import amp

    return amp.einsum(spec, a, b)


def _block_attend(q, k, v, q_off, k_off, scale, causal, m, l, o,
                  bias=None):
    """One online-softmax accumulation step of q against a (k, v) block.

    q: [B, H, Tq, D]; k/v: [B, H, Tk, D]; m/l/o are the running max,
    denominator and (unnormalized) output; bias, if given, is an additive
    [B, 1, 1, Tk] key-position bias (padding mask) for THIS k block."""
    s = _amp_einsum("bhqd,bhkd->bhqk", q, k) * scale  # [B,H,Tq,Tk]
    if bias is not None:
        s = s + bias
    if causal:
        tq, tk = q.shape[2], k.shape[2]
        qpos = q_off + jnp.arange(tq)[:, None]
        kpos = k_off + jnp.arange(tk)[None, :]
        s = jnp.where(qpos >= kpos, s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    # guard fully-masked rows (m_new = -inf): exp(-inf - -inf) -> use 0
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(jnp.where(jnp.isfinite(s), s - safe_m, -jnp.inf))
    p = jnp.where(jnp.isfinite(p), p, 0.0)
    corr = jnp.exp(jnp.where(jnp.isfinite(m), m - safe_m, -jnp.inf))
    corr = jnp.where(jnp.isfinite(corr), corr, 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    o_new = o * corr + _amp_einsum("bhqk,bhkd->bhqd", p, v)
    return m_new, l_new, o_new


def _ring_body(q, k, v, bias, axis_name, causal, scale):
    """Runs inside shard_map: q/k/v are the LOCAL [B, H, T/S, D] blocks;
    bias (or None) is the LOCAL [B, 1, 1, T/S] key-bias block, which
    rotates around the ring together with its k/v block."""
    n_dev = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    t_local = q.shape[2]
    q_off = my * t_local

    m = jnp.full(q.shape[:3] + (1,), -jnp.inf, q.dtype)
    l = jnp.zeros(q.shape[:3] + (1,), q.dtype)
    o = jnp.zeros_like(q)

    perm = [(j, (j + 1) % n_dev) for j in range(n_dev)]

    def step(i, carry):
        k_cur, v_cur, b_cur, m, l, o = carry
        src = (my - i) % n_dev  # whose K/V block we hold at step i
        m, l, o = _block_attend(q, k_cur, v_cur, q_off, src * t_local,
                                scale, causal, m, l, o, bias=b_cur)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        b_nxt = (lax.ppermute(b_cur, axis_name, perm)
                 if b_cur is not None else None)
        return k_nxt, v_nxt, b_nxt, m, l, o

    carry = (k, v, bias, m, l, o)
    # python loop: n_dev is static, XLA overlaps ppermute with the next
    # step's einsum (no scan-carried dynamic shapes)
    for i in range(n_dev):
        carry = step(i, carry)
    _, _, _, m, l, o = carry
    return o / jnp.maximum(l, jnp.finfo(l.dtype).tiny)


def ring_attention(q, k, v, mesh: Mesh, sp_axis: str = "sp",
                   causal: bool = False, scale=None, bias=None):
    """Sequence-parallel attention over ``mesh[sp_axis]``.

    q, k, v: [B, H, T, D] global arrays (T divisible by the sp size);
    returns [B, H, T, D] with the same sharding.  Batch may additionally be
    sharded on a "dp" axis — the spec below only constrains T.  bias, if
    given, is an additive [B, 1, 1, T] key-position bias (padding mask);
    it shards over sp on its key dim and rides the ring with k/v."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    # batch stays dp-sharded when the mesh has a dp axis — otherwise the
    # shard_map boundary would all-gather B across dp and every replica
    # would redo the full-batch attention
    b_axis = "dp" if "dp" in mesh.axis_names else None
    spec = P(b_axis, None, sp_axis, None)
    if bias is None:
        fn = _shard_map(
            partial(_ring_body, bias=None, axis_name=sp_axis, causal=causal,
                    scale=scale),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        return fn(q, k, v)
    bspec = P(b_axis, None, None, sp_axis)
    fn = _shard_map(
        partial(_ring_body, axis_name=sp_axis, causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec, bspec), out_specs=spec)
    return fn(q, k, v, bias)


def full_attention(q, k, v, causal: bool = False, scale=None, bias=None):
    """Single-device reference (used as the oracle and as the fallback when
    no sp mesh is active)."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    s = _amp_einsum("bhqd,bhkd->bhqk", q, k) * scale
    if bias is not None:
        s = s + bias
    if causal:
        t_q, t_k = q.shape[2], k.shape[2]
        mask = jnp.arange(t_q)[:, None] >= jnp.arange(t_k)[None, :]
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return _amp_einsum("bhqk,bhkd->bhqd", p, v)
