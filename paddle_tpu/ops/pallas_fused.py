"""Fused Pallas kernels beyond attention: streaming softmax-cross-entropy
and multi-tensor optimizer updates, with tp-sharded lowerings.

This completes the fused-kernel layer ROADMAP item 2 reserves for Pallas
("Pallas only where XLA underperforms") next to ``ops/pallas_flash.py``:

 - **Streaming softmax-with-cross-entropy** (fwd + bwd): the loss head of
   every classifier/LM tiles over the vocab/class dimension with the
   online-softmax (logsumexp) recurrence in fp32 VMEM scratch, so the
   ``[batch, vocab]`` probability matrix never materializes in HBM; the
   backward recomputes ``P = exp(logits - lse)`` per tile from the saved
   logsumexp (the FlashAttention discipline applied to the loss boundary).
   The target takes three forms and all stream: hard labels (an int32
   column, with ``ignore_index``), soft labels (a ``[batch, vocab]``
   distribution read tile by tile), and hard labels SMOOTHED uniformly,
   ``(1 - eps) * onehot + eps / V``, which is still the int32 column:
   the loss is ``lse - (1 - eps) * x[label] - (eps / V) * sum_j x_j``
   (one more row accumulator) and the gradient's target a select between
   two constants.  ``eps`` is the op's ``smooth_epsilon`` attribute,
   which ``layers.softmax_with_cross_entropy`` sets where the soft label
   it is handed is ``label_smooth(one_hot(ids))`` with no prior
   (``fluid/layers/nn.py`` ``_uniform_smoothing``), so a program written
   the reference's way never makes the dense distribution.
 - **Fused optimizer updates**: momentum and adam as single multi-tensor
   kernels — one grid sweep reads param + grad + moments and writes the
   updated buffers back through ``input_output_aliases``, instead of the
   handful of separate XLA elementwise ops per parameter.  The executor's
   SSA rebinding + donation (PR 6) make the update in place on device.
 - **tp-sharded lowerings**: under an active :func:`spmd.active_mesh`
   every kernel lowers through ``shard_map`` so column/row-parallel
   operands stay sharded through the kernel (GSPMD cannot partition an
   opaque ``pallas_call``).  The softmax-xent kernel handles a tp-sharded
   vocab dim with a cross-shard max/sum (logsumexp) exchange; optimizer
   updates run on the local shard of param/moment buffers per the PR 7
   spec table; flash attention shards its head dim.

Whether a kernel here or its XLA twin runs is ``kernel_choice.gate("fused")``
(``PADDLE_TPU_FUSED`` where set, else the platform; ``ops/kernel_choice.py``)
and this module's own checks of the operands (``xent_fusable``,
``opt_declined``); every dispatch that takes a kernel increments an
``ops.fused.<kind>`` counter (mesh-labeled under a mesh), so a run's numbers
are attributable to kernels.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from jax import shard_map as _shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from . import kernel_choice
from .pallas_flash import block_index

DEFAULT_BLOCK_R = 256    # rows (flattened batch) per grid step
DEFAULT_BLOCK_V = 512    # vocab/class columns per grid step
DEFAULT_BLOCK_N = 1024   # optimizer-sweep rows per grid step
LANE = 128
NEG_INF = -1e30

#: dtypes the kernels accumulate in fp32 for; anything else (f64 under the
#: package-wide x64 mode) falls back to the unfused XLA lowering.
_FUSABLE_DTYPES = ("float32", "bfloat16", "float16")


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------


def _active_mesh():
    from ..parallel import spmd

    return spmd.active_mesh()


def _note(family: str, /, **labels) -> None:
    """One ``ops.fused.<family>`` dispatch-decision counter per trace
    (mesh-labeled under an active mesh) — the observe-side evidence that
    a program actually lowered through the fused kernel, or, as
    ``ops.fused.declined{kind=...,why=...}``, that the gate was open and
    the update kept XLA's lowering."""
    try:
        from .. import observe
        from ..parallel.mesh import mesh_label

        mesh = _active_mesh()
        if mesh is not None:
            labels["mesh"] = mesh_label(mesh)
        observe.registry().inc(f"ops.fused.{family}", labels=labels or None)
    except Exception:
        pass  # accounting must never fail the trace it measures


def _fit_block(size, block):
    """Block extent along one dim: the whole dim when it fits in
    ``block``, else ``block`` itself (a multiple of the 8x128 tile) over a
    ``pl.cdiv`` grid.  The last block may then hang over the array's edge:
    Mosaic drops the writes past it, and a kernel that reduces along that
    dim masks the reads (see ``_xent_partial_kernel``)."""
    return size if size <= block else block


# ---------------------------------------------------------------------------
# streaming softmax-cross-entropy
# ---------------------------------------------------------------------------


def _xent_partial_kernel(x_ref, lab_ref, *out_refs, bv, n_v, v, soft, eps):
    """Grid step (row-block, vocab-block): online-logsumexp state (m, l)
    plus the label accumulator(s) in fp32 VMEM scratch, carried across the
    (sequential, minormost) vocab dimension — VMEM holds one [br, bv]
    logits tile at a time, the class dim can be arbitrarily long.

    Emits the PARTIAL per-row state (m, l, a[, b]) instead of the final
    loss, so one kernel serves both the single-device path (finalized in
    four trivial [R, 1] jnp ops) and the tp-sharded path (finalized after
    a cross-shard max/sum exchange).  ``a`` is the picked-logit sum (hard)
    or ``sum(y * logits)`` (soft); ``b`` is ``sum(y)`` (soft) or, for hard
    labels smoothed by ``eps > 0``, the row sum of the logits, which is all
    the uniform share of the target needs (``_finalize_loss``)."""
    if soft or eps:
        m_out, l_out, a_out, b_out, m_ref, l_ref, a_ref, b_ref = out_refs
    else:
        m_out, l_out, a_out, m_ref, l_ref, a_ref = out_refs
        b_out = b_ref = None
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        a_ref[:] = jnp.zeros_like(a_ref)
        if b_ref is not None:
            b_ref[:] = jnp.zeros_like(b_ref)

    x = x_ref[...].astype(jnp.float32)               # [br, bv]
    ragged = v % bv != 0
    if ragged or not soft:
        # all index math in i32: under the package-wide x64 mode python
        # ints promote to i64, which Mosaic's index ops reject
        cols = j * jnp.int32(bv) + lax.broadcasted_iota(
            jnp.int32, x.shape, 1)
    xs = x                          # what the smoothed form's row sum adds
    if ragged:
        # the last vocab block hangs over the array's edge and reads
        # unspecified values there: they must not reach max/sum
        live = cols < jnp.int32(v)
        if eps:
            xs = jnp.where(live, x, 0.0)
        x = jnp.where(live, x, jnp.float32(NEG_INF))
    m = m_ref[:]
    m_new = jnp.maximum(m, jnp.max(x, axis=1, keepdims=True))
    p = jnp.exp(x - m_new)
    corr = jnp.exp(m - m_new)
    m_ref[:] = m_new
    l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=1, keepdims=True)
    if soft:
        y = lab_ref[...].astype(jnp.float32)         # [br, bv]
        yx = y * x
        if ragged:
            y = jnp.where(live, y, 0.0)
            yx = jnp.where(live, yx, 0.0)
        a_ref[:] = a_ref[:] + jnp.sum(yx, axis=1, keepdims=True)
        b_ref[:] = b_ref[:] + jnp.sum(y, axis=1, keepdims=True)
    else:
        lab = lab_ref[...]                           # [br, 1] int32
        a_ref[:] = a_ref[:] + jnp.sum(
            jnp.where(cols == lab, x, 0.0), axis=1, keepdims=True)
        if eps:
            b_ref[:] = b_ref[:] + jnp.sum(xs, axis=1, keepdims=True)

    @pl.when(j == n_v - 1)
    def _flush():
        m_out[...] = m_ref[:]
        l_out[...] = l_ref[:]
        a_out[...] = a_ref[:]
        if b_out is not None:
            b_out[...] = b_ref[:]


def _xent_bwd_kernel(x_ref, lab_ref, lse_ref, g1_ref, g2_ref, dx_ref, *,
                     bv, soft, eps, v):
    """Backward grid step — tiles are independent (no carry): recompute
    ``P = exp(x - lse)`` for this [br, bv] tile from the saved logsumexp
    and emit ``dx = g1 * P - g2 * target`` where target is the one-hot
    (hard), ``(1 - eps) * onehot + eps / v`` (hard, smoothed over the
    ``v`` classes of the whole row) or the soft-label tile.  ``g1``/``g2``
    are per-row coefficients precomputed on the host side of the trace
    (they fold the incoming loss cotangent, the ignore mask, ``sum(y)``
    and any lse cotangent)."""
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    p = jnp.exp(x - lse_ref[...])
    g1 = g1_ref[...]
    g2 = g2_ref[...]
    if soft:
        tgt = lab_ref[...].astype(jnp.float32)
    else:
        cols = j * jnp.int32(bv) + lax.broadcasted_iota(
            jnp.int32, x.shape, 1)
        if eps:
            tgt = jnp.where(cols == lab_ref[...],
                            jnp.float32(1.0 - eps + eps / v),
                            jnp.float32(eps / v))
        else:
            tgt = (cols == lab_ref[...]).astype(jnp.float32)
    dx_ref[...] = (g1 * p - g2 * tgt).astype(dx_ref.dtype)


def _tile(i, j):
    """Index map of a [block_r, block_v] logits/label/dx tile."""
    return block_index(i, j)


def _row_col(i, j):
    """Index map of a per-row [block_r, 1] column (labels, m/l/a/b, lse)."""
    return block_index(i, 0)


def _xent_partial(x2, lab2, soft, block_r, block_v, interpret, eps):
    """Run the streaming kernel over ``x2 [R, V]``; returns per-row fp32
    ``(m, l, a, b)`` columns (``b`` is None for hard labels with no
    smoothing)."""
    from jax.experimental.pallas import tpu as pltpu

    r, v = x2.shape
    br = _fit_block(r, block_r)
    bv = _fit_block(v, block_v)
    n_v = pl.cdiv(v, bv)
    col = jax.ShapeDtypeStruct((r, 1), jnp.float32)
    lab_spec = (pl.BlockSpec((br, bv), _tile) if soft
                else pl.BlockSpec((br, 1), _row_col))
    out_spec = pl.BlockSpec((br, 1), _row_col)
    n_out = 4 if soft or eps else 3
    outs = pl.pallas_call(
        functools.partial(_xent_partial_kernel, bv=bv, n_v=n_v, v=v,
                          soft=soft, eps=eps),
        out_shape=[col] * n_out,
        grid=(pl.cdiv(r, br), n_v),
        in_specs=[pl.BlockSpec((br, bv), _tile), lab_spec],
        out_specs=[out_spec] * n_out,
        scratch_shapes=[pltpu.VMEM((br, 1), jnp.float32)] * n_out,
        interpret=kernel_choice.interpret(interpret),
    )(x2, lab2)
    if n_out == 4:
        m, l, a, b = outs
    else:
        (m, l, a), b = outs, None
    return m, l, a, b


def _xent_bwd_call(x2, lab2, lse, g1, g2, soft, block_r, block_v,
                   interpret, eps, v_all):
    """``v_all`` is the width the smoothing spreads ``eps`` over: the whole
    row's, which under a tp-sharded vocab is not ``x2``'s."""
    r, v = x2.shape
    br = _fit_block(r, block_r)
    bv = _fit_block(v, block_v)
    lab_spec = (pl.BlockSpec((br, bv), _tile) if soft
                else pl.BlockSpec((br, 1), _row_col))
    col = pl.BlockSpec((br, 1), _row_col)
    return pl.pallas_call(
        functools.partial(_xent_bwd_kernel, bv=bv, soft=soft, eps=eps,
                          v=v_all),
        out_shape=jax.ShapeDtypeStruct((r, v), x2.dtype),
        grid=(pl.cdiv(r, br), pl.cdiv(v, bv)),
        in_specs=[pl.BlockSpec((br, bv), _tile), lab_spec, col, col, col],
        out_specs=pl.BlockSpec((br, bv), _tile),
        interpret=kernel_choice.interpret(interpret),
    )(x2, lab2, lse, g1, g2)


def _finalize_loss(m, l, a, b, lab2, soft, ignore_index, eps, v):
    """``eps > 0`` (hard labels): the target is ``(1 - eps) * onehot +
    eps / v`` over the ``v`` classes of the whole row, and ``b`` the row
    sum of the logits."""
    lse = m + jnp.log(jnp.maximum(l, jnp.float32(1e-30)))
    if soft:
        loss = lse * b - a
    else:
        loss = (lse - jnp.float32(1.0 - eps) * a - jnp.float32(eps / v) * b
                if eps else lse - a)
        if ignore_index >= 0:
            loss = jnp.where(lab2 == jnp.int32(ignore_index), 0.0, loss)
    return loss, lse


def _bwd_coeffs(lab2, b, dloss, dlse, soft, ignore_index):
    """Per-row coefficients for the backward kernel.  ``dlse`` is the
    cotangent of the lse output (nonzero only when the op's Softmax output
    — reconstructed as ``exp(x - lse)`` — is actually consumed)."""
    e = dloss.astype(jnp.float32)
    if not soft and ignore_index >= 0:
        e = jnp.where(lab2 == jnp.int32(ignore_index), 0.0, e)
    sy = b if soft else 1.0
    g1 = e * sy + dlse.astype(jnp.float32)
    return g1, e


def _label_zeros(label):
    """The Label cotangent for custom_vjp: labels never get gradients
    (no_grad_inputs contract) — float0 for integer labels, zeros for soft
    float labels."""
    if jnp.issubdtype(label.dtype, jnp.inexact):
        return jnp.zeros_like(label)
    return np.zeros(np.shape(label), jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def softmax_xent(logits2, label2, soft_label=False, ignore_index=-100,
                 block_r=DEFAULT_BLOCK_R, block_v=DEFAULT_BLOCK_V,
                 interpret=None, smooth_epsilon=0.0):
    """Streamed ``softmax_with_cross_entropy`` over ``[R, V]`` logits.

    Returns ``(loss [R, 1] fp32, lse [R, 1] fp32)``; the probability
    matrix is never materialized — callers reconstruct softmax lazily as
    ``exp(logits - lse)`` (dead-code-eliminated when unused).  Matches
    ``ops/loss_ops.py:softmax_with_cross_entropy`` semantics: hard integer
    labels [R, 1] with ``ignore_index``, or soft [R, V] distributions.
    ``smooth_epsilon > 0`` (a Python float, hard labels only) makes the
    target ``(1 - eps) * onehot(label) + eps / V`` without anyone writing
    that distribution down."""
    loss, lse, _ = _xent_fwd(logits2, label2, soft_label, ignore_index,
                             block_r, block_v, interpret, smooth_epsilon)
    return loss, lse


def _xent_fwd(logits2, label2, soft, ignore, block_r, block_v, interpret,
              eps):
    m, l, a, b = _xent_partial(logits2, label2, soft, block_r, block_v,
                               interpret, eps)
    loss, lse = _finalize_loss(m, l, a, b, label2, soft, ignore, eps,
                               logits2.shape[1])
    return loss, lse, (logits2, label2, lse, b if soft else None)


def _xent_fwd_vjp(logits2, label2, soft, ignore, block_r, block_v,
                  interpret, eps):
    loss, lse, res = _xent_fwd(logits2, label2, soft, ignore, block_r,
                               block_v, interpret, eps)
    return (loss, lse), res


def _xent_bwd_vjp(soft, ignore, block_r, block_v, interpret, eps, res, ct):
    dloss, dlse = ct
    logits2, label2, lse, b = res
    g1, g2 = _bwd_coeffs(label2, b, dloss, dlse, soft, ignore)
    dx = _xent_bwd_call(logits2, label2, lse, g1, g2, soft, block_r,
                        block_v, interpret, eps, logits2.shape[1])
    return dx, _label_zeros(label2)


softmax_xent.defvjp(_xent_fwd_vjp, _xent_bwd_vjp)


# -- tp-sharded lowering ----------------------------------------------------


def _xent_specs(mesh, shape, soft):
    """(rows_axis, vocab_axis) per-dim degraded to the mesh: rows shard
    over dp when divisible, vocab over the tp axis when divisible."""
    from ..parallel.spmd import resolve_tp_axis

    r, v = shape
    row_ax = ("dp" if "dp" in mesh.axis_names and mesh.shape["dp"] > 1
              and r % mesh.shape["dp"] == 0 else None)
    tp = resolve_tp_axis(mesh)
    col_ax = (tp if tp in mesh.axis_names and mesh.shape[tp] > 1
              and v % mesh.shape[tp] == 0 else None)
    xspec = P(row_ax, col_ax)
    lspec = P(row_ax, col_ax) if soft else P(row_ax, None)
    cspec = P(row_ax, None)
    return xspec, lspec, cspec, col_ax


def _shift_labels(lab_loc, col_ax, vloc, soft):
    """Hard labels arrive replicated across the vocab axis; shifting them
    by this shard's vocab offset makes the unchanged kernel's local
    column-index match exactly the global label (out-of-shard labels never
    match, contributing zero to the psum)."""
    if soft or col_ax is None:
        return lab_loc
    return lab_loc - lax.axis_index(col_ax) * jnp.int32(vloc)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7, 8))
def softmax_xent_sharded(logits2, label2, mesh, soft_label=False,
                         ignore_index=-100, block_r=DEFAULT_BLOCK_R,
                         block_v=DEFAULT_BLOCK_V, interpret=None,
                         smooth_epsilon=0.0):
    """:func:`softmax_xent` lowered through ``shard_map`` on ``mesh``:
    rows stay dp-sharded, the vocab dim stays tp-sharded through the
    kernel, and the per-shard partial (m, l, a[, b]) state is combined
    with one cross-shard max/sum exchange (psum/pmax over tp) before the
    loss finalizes — the logsumexp exchange of Megatron-style vocab
    parallelism.  Outputs replicate over tp (loss is a per-row scalar).
    ``smooth_epsilon`` spreads over the GLOBAL width, and the row sum of
    the logits it needs is exchanged like the picked logit."""
    loss, lse, _ = _xent_sharded_fwd(logits2, label2, mesh, soft_label,
                                     ignore_index, block_r, block_v,
                                     interpret, smooth_epsilon)
    return loss, lse


def _xent_sharded_fwd(logits2, label2, mesh, soft, ignore, block_r,
                      block_v, interpret, eps):
    xspec, lspec, cspec, col_ax = _xent_specs(mesh, logits2.shape, soft)
    v_all = logits2.shape[1]

    def body(x_loc, lab_loc):
        lab_k = _shift_labels(lab_loc, col_ax, x_loc.shape[1], soft)
        m, l, a, b = _xent_partial(x_loc, lab_k, soft, block_r, block_v,
                                   interpret, eps)
        if col_ax is not None:
            m_g = lax.pmax(m, col_ax)
            l = lax.psum(l * jnp.exp(m - m_g), col_ax)
            a = lax.psum(a, col_ax)
            if b is not None:
                b = lax.psum(b, col_ax)
            m = m_g
        # the ignore mask needs the ORIGINAL (unshifted) label
        loss, lse = _finalize_loss(m, l, a, b, lab_loc, soft, ignore, eps,
                                   v_all)
        if not soft:
            b = jnp.ones_like(lse)
        return loss, lse, b

    loss, lse, b = _shard_map(
        body, mesh=mesh, in_specs=(xspec, lspec),
        out_specs=(cspec, cspec, cspec), check_vma=False)(logits2, label2)
    return loss, lse, (logits2, label2, lse, b)


def _xent_sharded_fwd_vjp(logits2, label2, mesh, soft, ignore, block_r,
                          block_v, interpret, eps):
    loss, lse, res = _xent_sharded_fwd(logits2, label2, mesh, soft,
                                       ignore, block_r, block_v, interpret,
                                       eps)
    return (loss, lse), res


def _xent_sharded_bwd_vjp(mesh, soft, ignore, block_r, block_v, interpret,
                          eps, res, ct):
    dloss, dlse = ct
    logits2, label2, lse, b = res
    g1, g2 = _bwd_coeffs(label2, b if soft else None, dloss, dlse, soft,
                         ignore)
    xspec, lspec, cspec, col_ax = _xent_specs(mesh, logits2.shape, soft)

    def body(x_loc, lab_loc, lse_loc, g1_loc, g2_loc):
        lab_k = _shift_labels(lab_loc, col_ax, x_loc.shape[1], soft)
        return _xent_bwd_call(x_loc, lab_k, lse_loc, g1_loc, g2_loc, soft,
                              block_r, block_v, interpret, eps,
                              logits2.shape[1])

    dx = _shard_map(
        body, mesh=mesh, in_specs=(xspec, lspec, cspec, cspec, cspec),
        out_specs=xspec, check_vma=False)(logits2, label2, lse, g1, g2)
    return dx, _label_zeros(label2)


softmax_xent_sharded.defvjp(_xent_sharded_fwd_vjp, _xent_sharded_bwd_vjp)


# -- op-level entry (dispatched from ops/loss_ops.py) -----------------------


def xent_fusable(logits, label, soft) -> bool:
    """Static suitability of this softmax_with_cross_entropy instance for
    the streaming kernel (the gate itself is ``kernel_choice.gate``)."""
    if str(logits.dtype) not in _FUSABLE_DTYPES:
        return False
    if logits.ndim < 2 or logits.shape[-1] < 2:
        return False
    if soft:
        return label.shape == logits.shape
    return True


def _as_rows(logits, label, soft):
    """``(x2 [R, V], lab2)`` as the kernels take them: a soft label
    ``[R, V]``, a hard one an int32 column ``[R, 1]``."""
    v = logits.shape[-1]
    if soft:
        return logits.reshape(-1, v), label.reshape(-1, v)
    li = label
    if li.ndim == logits.ndim and li.shape[-1] == 1:
        li = li.reshape(li.shape[:-1])
    return logits.reshape(-1, v), li.astype(jnp.int32).reshape(-1, 1)


def softmax_xent_op(logits, label, soft, ignore, eps=0.0):
    """The ``softmax_with_cross_entropy`` op lowered through the streaming
    kernels.  The Softmax output slot is reconstructed lazily from the
    logsumexp (``exp(logits - lse)``) so it costs nothing when the program
    never reads it (the common training graph fetches only Loss; XLA DCEs
    the reconstruction).  ``Lse`` is that logsumexp, float32
    ``logits.shape[:-1] + (1,)``: what :func:`softmax_xent_grad` takes, so
    that the grad op need not run the forward kernel again.  ``eps`` is the
    op's ``smooth_epsilon``."""
    in_dtype = logits.dtype
    lead = tuple(logits.shape[:-1])
    x2, lab2 = _as_rows(logits, label, soft)
    mesh = _active_mesh()
    if mesh is not None:
        loss2, lse2 = softmax_xent_sharded(x2, lab2, mesh, soft, ignore,
                                           smooth_epsilon=eps)
    else:
        loss2, lse2 = softmax_xent(x2, lab2, soft, ignore,
                                   smooth_epsilon=eps)
    _note("softmax_xent",
          target="soft" if soft else "smoothed" if eps else "hard")
    loss = loss2.reshape(lead + (1,))
    lse = lse2.reshape(lead + (1,))
    sm = jnp.exp(logits.astype(jnp.float32) - lse).astype(in_dtype)
    return {"Softmax": sm, "Loss": loss, "Lse": lse}


def softmax_xent_grad(logits, label, lse, dloss, ignore, eps=0.0):
    """``Logits@GRAD`` of :func:`softmax_xent_op` for hard labels (smoothed
    by ``eps`` or not) from the forward's own ``lse``: the backward kernel
    alone, through the very function that is the kernels' ``custom_vjp``
    backward (sharded under an active mesh), handed the residual the
    forward would have kept.  A soft label's residual also holds ``sum(y)``,
    which no output of the op carries: that one keeps the generic grad."""
    x2, lab2 = _as_rows(logits, label, False)
    res = (x2, lab2, lse.reshape(-1, 1), None)
    dloss2 = dloss.reshape(-1, 1)
    ct = (dloss2, jnp.zeros_like(dloss2))  # nobody differentiates Lse
    blocks = (DEFAULT_BLOCK_R, DEFAULT_BLOCK_V, None)
    mesh = _active_mesh()
    if mesh is not None:
        dx, _ = _xent_sharded_bwd_vjp(mesh, False, ignore, *blocks, eps,
                                      res, ct)
    else:
        dx, _ = _xent_bwd_vjp(False, ignore, *blocks, eps, res, ct)
    return dx.reshape(logits.shape)


# ---------------------------------------------------------------------------
# fused optimizer updates (multi-tensor single-sweep kernels)
# ---------------------------------------------------------------------------


def _momentum_kernel(p_ref, g_ref, v_ref, lr_ref, po_ref, vo_ref, *, mu,
                     nesterov):
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    lr = lr_ref[0, 0]
    v_out = jnp.float32(mu) * v + g
    if nesterov:
        p_out = p - (g + jnp.float32(mu) * v_out) * lr
    else:
        p_out = p - lr * v_out
    po_ref[...] = p_out.astype(po_ref.dtype)
    vo_ref[...] = v_out.astype(vo_ref.dtype)


def _adam_kernel(p_ref, g_ref, m1_ref, m2_ref, lr_ref, po_ref, m1o_ref,
                 m2o_ref, *, b1, b2, eps):
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m1 = m1_ref[...].astype(jnp.float32)
    m2 = m2_ref[...].astype(jnp.float32)
    lr = lr_ref[0, 0]
    m1o = jnp.float32(b1) * m1 + jnp.float32(1.0 - b1) * g
    m2o = jnp.float32(b2) * m2 + jnp.float32(1.0 - b2) * g * g
    po = p - lr * m1o / (jnp.sqrt(m2o) + jnp.float32(eps))
    po_ref[...] = po.astype(po_ref.dtype)
    m1o_ref[...] = m1o.astype(m1o_ref.dtype)
    m2o_ref[...] = m2o.astype(m2o_ref.dtype)


def _sweep_view(shape):
    """2-D view ``(rows, cols)`` of one parameter for the sweep, or None
    where there is none.  The rule: the kernel never asks XLA for a
    relayout.  A ``pallas_call`` takes its operands row-major, so the
    view has to be one the tiled device layout already is: a lane-aligned
    last dim is kept and the leading dims collapse onto it; a 1-D tensor
    becomes rows of 128, or one ragged row that the sweep walks in column
    blocks.  A tensor of two or more dims with a ragged last dim has no
    such view: an OIHW convolution filter (last dim 1, 3 or 7) lives with
    its channel dims minor, and flattening it is a copy through a layout
    that pads every 3x3 patch to a 4x128 tile, for each operand and
    result.  It keeps the op's own XLA lowering, in the layout it has."""
    n = int(np.prod(shape, dtype=np.int64))
    if len(shape) >= 2:
        return (n // shape[-1], shape[-1]) if shape[-1] % LANE == 0 else None
    return (n // LANE, LANE) if n % LANE == 0 else (1, n)


def _sweep_blocks(rows, cols):
    """Block ``(br, bc)`` of ``DEFAULT_BLOCK_N`` 128-lane rows (512 KiB of
    fp32) whatever the view's aspect, so the seven operands of an Adam
    sweep, double-buffered, stay far inside VMEM.  ``bc`` leaves room for
    eight rows: a lone ragged row pads to eight sublanes there anyway."""
    block = DEFAULT_BLOCK_N * LANE
    bc = _fit_block(cols, block // 8)
    br = _fit_block(rows, max(8, block // bc // 8 * 8))
    return br, bc


def _opt_sweep(kernel, arrays, lr, n_out, interpret):
    """One multi-tensor grid sweep: every tensor of the update (param,
    grad, moments) takes the same 2-D view, one grid step updates one
    block of ALL of them, and ``input_output_aliases`` writes the
    param/moment outputs back into their (donated) input buffers."""
    from jax.experimental.pallas import tpu as pltpu

    shape = arrays[0].shape
    rows, cols = _sweep_view(shape)
    br, bc = _sweep_blocks(rows, cols)
    flat = [a.reshape(rows, cols) for a in arrays]
    lr2 = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    blk = pl.BlockSpec((br, bc), lambda i, j: block_index(i, j))
    scal = pl.BlockSpec((1, 1), lambda i, j: block_index(0, 0),
                        memory_space=pltpu.SMEM)
    # outputs alias the param/moment INPUTS (grad at index 1 is read-only)
    aliases = {0: 0}
    for k in range(1, n_out):
        aliases[k + 1] = k
    outs = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((rows, cols), a.dtype)
                   for a in (arrays[:1] + arrays[2:2 + n_out - 1])],
        grid=(pl.cdiv(rows, br), pl.cdiv(cols, bc)),
        in_specs=[blk] * len(flat) + [scal],
        out_specs=[blk] * n_out,
        input_output_aliases=aliases,
        interpret=kernel_choice.interpret(interpret),
    )(*flat, lr2)
    return [o.reshape(shape) for o in outs]


def opt_declined(p, g, var_name: Optional[str] = None) -> Optional[str]:
    """Why one optimizer update keeps the op's own XLA lowering although
    the gate is open (the ``why`` label of ``ops.fused.declined``), or
    None where it takes the fused sweep.  Under an active mesh the sweep
    runs on the local shard of ``var_name``'s spec, so the layout rule
    reads that shard's shape."""
    if str(p.dtype) not in _FUSABLE_DTYPES:
        return "dtype"
    n = int(np.prod(p.shape, dtype=np.int64))
    if n == 0 or g is None or g.shape != p.shape:
        return "shape"
    if _sweep_view(_local_shape(p.shape, var_name)) is None:
        return "layout"
    # a non-lane-aligned tensor runs as one [1, n] row that fills one
    # sublane in eight; past this size the unfused lowering is the better
    if n % LANE and n > (1 << 17):
        return "ragged"
    return None


def _param_spec(mesh, var_name: Optional[str], shape):
    """The spec-table PartitionSpec for this update's param — published by
    the sharded runners via ``spmd.param_spec_scope`` — degraded per dim
    to what the mesh/shape actually supports (absent table or name runs
    replicated inside the same shard_map)."""
    from ..parallel import spmd

    specs = spmd.active_param_specs() or {}
    spec = specs.get(var_name) if var_name else None
    if spec is None:
        return P()
    dims = [ax if (d < len(shape) and ax is not None
                   and ax in mesh.axis_names
                   and shape[d] % mesh.shape[ax] == 0) else None
            for d, ax in enumerate(tuple(spec))]
    return P(*dims)


def _local_shape(shape, var_name: Optional[str]):
    """The shape ``_opt_sweep`` is handed: the array's own, or under an
    active mesh its local shard per :func:`_param_spec`."""
    mesh = _active_mesh()
    if mesh is None:
        return tuple(shape)
    spec = _param_spec(mesh, var_name, shape)
    return NamedSharding(mesh, spec).shard_shape(tuple(shape))


def opt_specs_aligned(out_names) -> bool:
    """Whether every operand of one optimizer update (param + its
    accumulators, named by the op's ``*Out`` output vars) shares ONE
    PartitionSpec in the published table.  ZeRO-1 shards accumulators over
    dp while the param stays replicated — those updates keep the unfused
    lowering so GSPMD keeps the optimizer math dp-sharded (forcing the
    param's spec would reshard the moments every window and break the
    window-over-window donation aliasing)."""
    mesh = _active_mesh()
    if mesh is None:
        return True
    from ..parallel import spmd

    specs = spmd.active_param_specs()
    if specs is None:
        return True
    ss = [tuple(specs.get(n) or P()) for n in out_names if n]
    return all(s == ss[0] for s in ss) if ss else True


def _run_opt(kernel, arrays, lr, n_out, var_name, interpret):
    mesh = _active_mesh()
    if mesh is None:
        return _opt_sweep(kernel, arrays, lr, n_out, interpret)
    # sharded lowering: the update runs on the LOCAL shard of every
    # operand (elementwise math needs no exchange); a degraded/absent
    # spec runs replicated inside the same shard_map, so GSPMD never sees
    # an opaque pallas_call on sharded operands
    spec = _param_spec(mesh, var_name, arrays[0].shape)

    def body(*local):
        return tuple(_opt_sweep(kernel, list(local[:-1]), local[-1],
                                n_out, interpret))

    outs = _shard_map(body, mesh=mesh,
                      in_specs=tuple([spec] * len(arrays)) + (P(),),
                      out_specs=tuple([spec] * n_out), check_vma=False)(
        *arrays, jnp.asarray(lr, jnp.float32).reshape(()))
    return list(outs)


def fused_momentum(p, g, v, lr, mu, nesterov, var_name=None):
    """Momentum update as ONE kernel sweep over (param, grad, velocity)."""
    kernel = functools.partial(_momentum_kernel, mu=float(mu),
                               nesterov=bool(nesterov))
    po, vo = _run_opt(kernel, [p, g, v], lr, 2, var_name, None)
    _note("momentum")
    return po, vo


def fused_adam(p, g, m1, m2, lr_eff, b1, b2, eps, var_name=None):
    """Adam update as ONE kernel sweep over (param, grad, m, v); the
    bias-corrected ``lr_eff`` and the beta-pow counters are scalar math
    computed outside (they are [1]-shaped; fusing them buys nothing)."""
    kernel = functools.partial(_adam_kernel, b1=float(b1), b2=float(b2),
                               eps=float(eps))
    po, m1o, m2o = _run_opt(kernel, [p, g, m1, m2], lr_eff, 3, var_name,
                            None)
    _note("adam")
    return po, m1o, m2o


# ---------------------------------------------------------------------------
# tp-sharded flash attention (heads stay sharded through the kernel)
# ---------------------------------------------------------------------------


def flash_tp_axis(q, mesh) -> Optional[str]:
    """The axis to shard flash attention's head dim over, or None when the
    mesh has no usable tp axis / heads don't divide."""
    if mesh is None:
        return None
    from ..parallel.spmd import resolve_tp_axis

    tp = resolve_tp_axis(mesh)
    if tp in mesh.axis_names and mesh.shape[tp] > 1 \
            and q.shape[1] % mesh.shape[tp] == 0:
        return tp
    return None


def flash_attention_sharded(q, k, v, bias, scale, causal, mesh,
                            tp_axis: Optional[str] = None):
    """``pallas_flash.flash_attention`` under ``shard_map``: each tp shard
    runs the full streaming kernel on its local heads (attention is
    head-independent — no exchange), batch stays dp-sharded.  This is the
    lowering that keeps column-parallel qkv projections sharded INTO the
    kernel instead of GSPMD all-gathering around an opaque pallas_call.
    ``tp_axis=None`` (no usable tp axis / indivisible heads) still wraps,
    with heads replicated — a bare pallas_call has no partitioning rule
    under a mesh."""
    from .pallas_flash import flash_attention

    b_axis = ("dp" if "dp" in mesh.axis_names and mesh.shape["dp"] > 1
              and q.shape[0] % mesh.shape["dp"] == 0 else None)
    if bias is not None and bias.ndim and bias.shape[0] > 1 \
            and b_axis is not None \
            and bias.shape[0] % mesh.shape[b_axis] != 0:
        b_axis = None  # a per-row bias must shard WITH the batch or not at all
    spec = P(b_axis, tp_axis, None, None)

    def body(ql, kl, vl, *rest):
        bl = rest[0] if rest else None
        return flash_attention(ql, kl, vl, bl, scale, causal)

    args = [q, k, v]
    in_specs = [spec, spec, spec]
    if bias is not None:
        args.append(bias)
        ba = b_axis if (bias.ndim and bias.shape[0] > 1) else None
        in_specs.append(P(ba, *([None] * (bias.ndim - 1))))
    out = _shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                     out_specs=spec, check_vma=False)(*args)
    note_flash(q, k, v)
    return out


def note_flash(q, k, v) -> None:
    """A dispatch through ``pallas_flash.flash_attention``: the family's
    counter, and beside it the dtype its contractions take their operands
    in (the kernels contract q, k, v and dO as they arrive: bf16 under
    AMP, float32 otherwise)."""
    _note("flash_attention")
    _note("flash_contraction",
          operands="/".join(sorted({str(a.dtype) for a in (q, k, v)})))
