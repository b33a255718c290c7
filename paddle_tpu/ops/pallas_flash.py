"""Flash attention as Pallas TPU kernels — forward AND backward.

The hot op of the transformer/BERT path gets hand-scheduled kernels
(SURVEY.md §7.3: "Pallas only where XLA underperforms"): one grid step
owns a [BLOCK, D] tile resident in VMEM and streams the opposing tiles
through the MXU with the online-softmax recurrence, so the [T, T] score
matrix never hits HBM — forward, dQ, and dK/dV alike.  Every contraction
takes q, k, v and dO in the dtype they arrive in (bf16 under AMP) and
accumulates in fp32; the tiles a kernel makes itself (P, dS) are rounded
to the other operand's dtype at the contraction and nowhere else.
Scores, softmax statistics, masks and the VMEM accumulators are fp32
regardless of the input dtype (the same master-accumulator discipline as
fluid.amp).  (On the v5e, at d = 64, Mosaic multiplies float32 tiles in
one bf16 pass too: float32 copies of bf16 operands gave the same bits in
the same time, PERF.md Findings PR 33.  What a grid step pays for is its
trips through VMEM scratch and every relayout, hence:)

Where one tile pair covers the sequence (t <= the block size: the
Transformer-base step's 256) nothing is carried from one grid step to
the next, and each kernel writes its tile's result straight out instead
of through the scratch state.  All three kernels hold scores as [bq, bk]
— queries on sublanes, keys on lanes — so lse and delta, [bq, 1] columns
as the forward wrote them, broadcast along lanes; dK/dV contract P and dS
over their first dim rather than asking for those columns as rows.

Backward (Dao FlashAttention-2 formulation): the forward emits the
per-row logsumexp L, so each backward tile recomputes P = exp(S - L)
locally; with delta = rowsum(dO ∘ O) precomputed (one fused elementwise
reduce in XLA):

    dV = Pᵀ dO;   dS = P ∘ (dO Vᵀ - delta);   dQ = scale·dS K;
    dK = scale·dSᵀ Q

split into two kernels matching the reduction directions: a dQ kernel
(q-tile resident, streams K/V) and a dK/dV kernel (k-tile resident,
streams Q/dO).  Both skip dead causal blocks.

``bias`` is the additive KEY-padding bias ([B, 1, 1, Tk], the shape the
models build) — broadcast into the logits inside the kernels; it gets no
gradient (it is derived from input padding, never trained).

Falls back to interpret mode off-TPU, so the same kernel code is testable
on the CPU mesh.  ref: the reference's fused scaled_dot_product kernels
live in paddle/fluid/operators/math/ + cuDNN; this is the TPU-native
counterpart.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import kernel_choice

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256
NEG_INF = -1e30


def block_index(*idx):
    """``BlockSpec`` index-map results, all int32.  The package runs jax in
    x64 mode, where a Python literal in an index map becomes an i64 result
    that Mosaic refuses to legalize (interpret mode never notices)."""
    return tuple(jnp.asarray(i, jnp.int32) for i in idx)


def _causal_mask(logits, q_off, k_off):
    qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
    kpos = k_off + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    return jnp.where(qpos >= kpos, logits, jnp.float32(NEG_INF))


def _dot(a, b, contract):
    """``a`` contracted with ``b`` over dims ``contract`` = (of a, of b),
    accumulated in fp32."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.float32)


def _logits(q, k, bias_ref, scale, causal, q_off, k_off):
    """scale · q kᵀ + key bias, causal-masked: fp32 [bq, bk], queries on
    sublanes and keys on lanes in all three kernels, so the per-query
    columns (max, sum, lse, delta: [bq, 1]) broadcast along lanes and are
    never turned into rows."""
    s = _dot(q, k, (1, 1)) * jnp.float32(scale)
    if bias_ref is not None:
        s = s + bias_ref[0].astype(jnp.float32)        # [1, bk]
    if causal:
        s = _causal_mask(s, q_off, k_off)
    return s


def _tile_offsets(q_ref, k_ref, qi, ki):
    # all index math in i32: under the package-wide x64 mode python ints
    # promote to i64, which Mosaic's index ops reject
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    q_off = qi * jnp.int32(bq)
    k_off = ki * jnp.int32(bk)
    return q_off, k_off, k_off <= q_off + jnp.int32(bq - 1)


def _flash_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, n_k,
                  has_bias):
    """Forward grid step (bh, q-block, k-block): one [bq, d] query tile
    against one [bk, d] K/V tile, online-softmax state (m, l, acc) in fp32
    VMEM scratch carried across the (sequential, minormost) k dimension —
    VMEM holds one K/V TILE at a time, t_kv can be arbitrarily long.
    Where one K/V tile IS the sequence (n_k == 1) there is nothing to
    carry: the softmax of the tile goes straight to the results."""
    if has_bias:
        bias_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
        bias_ref = None
    ki = pl.program_id(2)
    q_off, k_off, on_or_below_diagonal = _tile_offsets(
        q_ref, k_ref, pl.program_id(1), ki)

    def tile(m_old):
        logits = _logits(q_ref[0], k_ref[0], bias_ref, scale, causal,
                         q_off, k_off)
        m = jnp.max(logits, axis=1, keepdims=True)
        if m_old is not None:
            m = jnp.maximum(m_old, m)
        p = jnp.exp(logits - m)
        v = v_ref[0]
        return (m, jnp.sum(p, axis=1, keepdims=True),
                _dot(p.astype(v.dtype), v, (1, 0)))

    def flush(m, l, acc):
        l = jnp.maximum(l, jnp.float32(1e-30))
        o_ref[0] = (acc / l).astype(o_ref.dtype)
        lse_ref[0] = m + jnp.log(l)

    if n_k == 1:
        flush(*tile(None))
        return

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # under causal masking, blocks strictly above the diagonal contribute
    # nothing — skip both MXU contractions for them (~2x FLOPs at long T)
    @pl.when(on_or_below_diagonal if causal else True)
    def _attend():
        m_old = m_ref[:]
        m, l, pv = tile(m_old)
        corr = jnp.exp(m_old - m)
        m_ref[:] = m
        l_ref[:] = l_ref[:] * corr + l
        acc_ref[:] = acc_ref[:] * corr + pv

    @pl.when(ki == n_k - 1)
    def _flush():
        flush(m_ref[:], l_ref[:], acc_ref[:])


def _backward_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   bias_ref, scale, causal, q_off, k_off):
    """P and dS of one tile pair, fp32 [bq, bk], from the forward's lse
    and delta = rowsum(dO ∘ O)."""
    s = _logits(q_ref[0], k_ref[0], bias_ref, scale, causal, q_off, k_off)
    p = jnp.exp(s - lse_ref[0])
    dp = _dot(do_ref[0], v_ref[0], (1, 1))
    return p, p * (dp - delta_ref[0])


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
               scale, causal, n_k, has_bias):
    """dQ grid step (bh, q-block, k-block): q/dO/lse/delta tiles resident,
    K/V tiles stream; dq accumulates in fp32 scratch over ki (one K/V
    tile: straight to the result)."""
    if has_bias:
        bias_ref, dq_ref, dq_acc = rest
    else:
        dq_ref, dq_acc = rest
        bias_ref = None
    ki = pl.program_id(2)
    q_off, k_off, on_or_below_diagonal = _tile_offsets(
        q_ref, k_ref, pl.program_id(1), ki)

    def tile():
        _, ds = _backward_tile(q_ref, k_ref, v_ref, do_ref, lse_ref,
                               delta_ref, bias_ref, scale, causal, q_off,
                               k_off)
        k = k_ref[0]
        return jnp.float32(scale) * _dot(ds.astype(k.dtype), k, (1, 0))

    if n_k == 1:
        dq_ref[0] = tile().astype(dq_ref.dtype)
        return

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(on_or_below_diagonal if causal else True)
    def _accum():
        dq_acc[:] += tile()

    @pl.when(ki == n_k - 1)
    def _flush():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                scale, causal, n_q, has_bias):
    """dK/dV grid step (bh, k-block, q-block): K/V tiles resident, Q/dO/
    lse/delta tiles stream; dk/dv accumulate in fp32 scratch over qi (one
    Q tile: straight to the results).  P and dS are [bq, bk] as in dQ and
    are contracted over their FIRST dim (Pᵀ dO, dSᵀ Q): the MXU takes the
    transposed operand, where a [bk, bq] tile would want lse and delta as
    rows, a relayout of two [bq, 1] columns every step."""
    if has_bias:
        bias_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
        bias_ref = None
    qi = pl.program_id(2)
    q_off, k_off, on_or_below_diagonal = _tile_offsets(
        q_ref, k_ref, qi, pl.program_id(1))

    def tile():
        p, ds = _backward_tile(q_ref, k_ref, v_ref, do_ref, lse_ref,
                               delta_ref, bias_ref, scale, causal, q_off,
                               k_off)
        q, do = q_ref[0], do_ref[0]
        return (jnp.float32(scale) * _dot(ds.astype(q.dtype), q, (0, 0)),
                _dot(p.astype(do.dtype), do, (0, 0)))

    if n_q == 1:
        dk, dv = tile()
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)
        return

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(on_or_below_diagonal if causal else True)
    def _accum():
        dk, dv = tile()
        dk_acc[:] += dk
        dv_acc[:] += dv

    @pl.when(qi == n_q - 1)
    def _flush():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _blocks(t, t_kv, block_q, block_k):
    bq = min(block_q, t)
    bk = min(block_k, t_kv)
    while t % bq:
        bq //= 2
    while t_kv % bk:
        bk //= 2
    return bq, bk


def bias_supported(bias, b, t_kv) -> bool:
    """Whether the kernels can take this additive bias: key-padding shaped
    [B|1, 1, 1, Tk] or [B|1, Tk].  The SAME predicate gates the op-level
    routing (ops/attention_ops.py), so an unsupported bias falls back to
    the XLA path instead of crashing here."""
    if bias is None:
        return True
    if bias.ndim == 4:
        return (bias.shape[1] == 1 and bias.shape[2] == 1
                and bias.shape[0] in (1, b) and bias.shape[3] == t_kv)
    return bias.ndim == 2 and bias.shape[0] in (1, b) \
        and bias.shape[1] == t_kv


def _bias_2d(bias, b, h, t_kv):
    """Normalize a supported bias (see bias_supported) to [B, Tk]."""
    if bias is None:
        return None
    if not bias_supported(bias, b, t_kv):
        raise ValueError(
            f"flash_attention bias must be key-padding shaped "
            f"[B|1, 1, 1, Tk] or [B|1, Tk]; got {bias.shape}")
    if bias.ndim == 4:
        bias = bias.reshape(bias.shape[0], bias.shape[3])
    if bias.shape[0] == 1 and b > 1:
        bias = jnp.broadcast_to(bias, (b, t_kv))
    return bias


def _index_maps(h):
    """Index maps over a (batch*head, outer, inner) grid: ``resident``
    tiles follow the outer grid dim, ``streamed`` tiles the inner
    (sequential) one, and ``key_bias(dim)`` picks the [B, 1, Tk] bias tile
    of this head's batch row at the key block that grid dim ``dim`` walks.
    The batch row is ``lax.div``, not ``//``: floor-division's sign fix-up
    does not lower in a Mosaic index map."""
    def resident(i, j, s):
        return block_index(i, j, 0)

    def streamed(i, j, s):
        return block_index(i, s, 0)

    def key_bias(dim):
        return lambda *g: block_index(
            jax.lax.div(g[0], jnp.int32(h)), 0, g[dim])

    return resident, streamed, key_bias


def _flash_forward(q, k, v, bias, scale, causal, block_q, block_k,
                   interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, h, t, d = q.shape
    t_kv = k.shape[2]
    bq, bk = _blocks(t, t_kv, block_q, block_k)
    n_k = t_kv // bk
    # grid iterates k-blocks innermost: TPU grids run sequentially on a
    # core, so the scratch online-softmax state carries across ki steps
    grid = (b * h, t // bq, n_k)
    qr = q.reshape(b * h, t, d)
    kr = k.reshape(b * h, t_kv, d)
    vr = v.reshape(b * h, t_kv, d)
    resident, streamed, key_bias = _index_maps(h)
    in_specs = [
        pl.BlockSpec((1, bq, d), resident),
        pl.BlockSpec((1, bk, d), streamed),
        pl.BlockSpec((1, bk, d), streamed),
    ]
    args = [qr, kr, vr]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, 1, bk), key_bias(2)))
        args.append(bias.reshape(b, 1, t_kv))
    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          n_k=n_k, has_bias=bias is not None),
        out_shape=[jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
                   jax.ShapeDtypeStruct((b * h, t, 1), jnp.float32)],
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, bq, d), resident),
                   pl.BlockSpec((1, bq, 1), resident)],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denominator
            pltpu.VMEM((bq, d), jnp.float32),   # fp32 accumulator
        ],
        interpret=interpret,
    )(*args)
    return out.reshape(b, h, t, d), lse.reshape(b, h, t, 1)


def _flash_backward(q, k, v, bias, out, lse, do, scale, causal, block_q,
                    block_k, interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, h, t, d = q.shape
    t_kv = k.shape[2]
    bq, bk = _blocks(t, t_kv, block_q, block_k)
    n_q, n_k = t // bq, t_kv // bk
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)            # [b, h, t, 1]
    qr = q.reshape(b * h, t, d)
    kr = k.reshape(b * h, t_kv, d)
    vr = v.reshape(b * h, t_kv, d)
    dor = do.reshape(b * h, t, d)
    lser = lse.reshape(b * h, t, 1)
    dr = delta.reshape(b * h, t, 1)
    has_bias = bias is not None
    bias_args = [bias.reshape(b, 1, t_kv)] if has_bias else []

    resident, streamed, key_bias = _index_maps(h)

    # dQ: q-tile resident, k innermost
    q_res = [pl.BlockSpec((1, bq, d), resident),
             pl.BlockSpec((1, bk, d), streamed),
             pl.BlockSpec((1, bk, d), streamed),
             pl.BlockSpec((1, bq, d), resident),
             pl.BlockSpec((1, bq, 1), resident),
             pl.BlockSpec((1, bq, 1), resident)]
    if has_bias:
        q_res.append(pl.BlockSpec((1, 1, bk), key_bias(2)))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          n_k=n_k, has_bias=has_bias),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        grid=(b * h, n_q, n_k),
        in_specs=q_res,
        out_specs=pl.BlockSpec((1, bq, d), resident),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, dr, *bias_args)

    # dK/dV: k-tile resident, q innermost
    kv_res = [pl.BlockSpec((1, bq, d), streamed),
              pl.BlockSpec((1, bk, d), resident),
              pl.BlockSpec((1, bk, d), resident),
              pl.BlockSpec((1, bq, d), streamed),
              pl.BlockSpec((1, bq, 1), streamed),
              pl.BlockSpec((1, bq, 1), streamed)]
    if has_bias:
        kv_res.append(pl.BlockSpec((1, 1, bk), key_bias(1)))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          n_q=n_q, has_bias=has_bias),
        out_shape=[jax.ShapeDtypeStruct((b * h, t_kv, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, t_kv, d), v.dtype)],
        grid=(b * h, n_k, n_q),
        in_specs=kv_res,
        out_specs=[pl.BlockSpec((1, bk, d), resident),
                   pl.BlockSpec((1, bk, d), resident)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, dr, *bias_args)
    return (dq.reshape(b, h, t, d), dk.reshape(b, h, t_kv, d),
            dv.reshape(b, h, t_kv, d))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def flash_attention(q, k, v, bias=None, scale=None, causal=False,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=None):
    """softmax(scale · q kᵀ + bias [+ causal mask]) v, streamed (never
    materializes the [T, T] scores).  q/k/v: [B, H, T, D]; bias: additive
    key-padding bias [B, 1, 1, Tk] (or [B, Tk]) or None, non-trainable."""
    out, _ = _flash_fwd_impl(q, k, v, bias, scale, causal, block_q,
                             block_k, interpret)
    return out


def resolve(q, scale, interpret):
    """(scale, interpret) with what a caller left open filled in: the
    head's width to the -1/2, and ``kernel_choice.interpret``."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    return scale, kernel_choice.interpret(interpret)


def _flash_fwd_impl(q, k, v, bias, scale, causal, block_q, block_k,
                    interpret):
    scale, interpret = resolve(q, scale, interpret)
    bias = _bias_2d(bias, q.shape[0], q.shape[1], k.shape[2])
    return _flash_forward(q, k, v, bias, scale, causal, block_q, block_k,
                          interpret)


def _flash_fwd(q, k, v, bias, scale, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd_impl(q, k, v, bias, scale, causal, block_q,
                               block_k, interpret)
    return out, (q, k, v, bias, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, do):
    q, k, v, bias, out, lse = res
    scale, interpret = resolve(q, scale, interpret)
    bias2 = _bias_2d(bias, q.shape[0], q.shape[1], k.shape[2])
    dq, dk, dv = _flash_backward(q, k, v, bias2, out, lse, do, scale,
                                 causal, block_q, block_k, interpret)
    dbias = None if bias is None else jnp.zeros_like(bias)
    return dq, dk, dv, dbias


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def flash_bwd_reference(q, k, v, do, bias=None, scale=None, causal=False):
    """jnp recompute backward (the pre-r5 path) — kept as the OpTest
    reference the Pallas dQ/dK/dV kernels are verified against."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    qf, kf, vf = (a.astype(jnp.float32) for a in (q, k, v))
    dof = do.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        tq, tk = q.shape[2], k.shape[2]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    of = jnp.einsum("bhqk,bhkd->bhqd", p, vf)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
    dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vf)
    delta = jnp.sum(dof * of, axis=-1, keepdims=True)
    ds = p * (dp - delta)
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kf) * scale
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf) * scale
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)
