"""Paged decode attention as a Pallas TPU kernel (ISSUE 19 tentpole).

The decode engine's K/V cache lives as fixed-size pages in one
``[num_pages + 1, page_size, d_model]`` buffer per layer (the last row is
the trash page absorbing inactive-slot writes), and each tick feeds a
``[slots, pages_per_slot]`` page table.  The dense decode step gathers the
whole table with ``jnp.take`` before one big attention matmul; this kernel
moves the gather INSIDE the attention loop: the page table rides the
grid's scalar-prefetch slot, so each (slot, page) grid step DMAs exactly
one K/V page — ``BlockSpec`` index maps read ``pt[s, j]`` — and the
``[slots, L]`` score matrix never round-trips through a gathered HBM copy.

Bitwise discipline (the PR 15 sequential-equivalence invariant): scores
accumulate per page into a VMEM ``[n_pages, page_size, 1]`` scratch (one
key per sublane, so every store is a whole leading-dim slot and nothing
lands at an unaligned lane offset — Mosaic refuses those) and the softmax
at the LAST page iteration replays ``jax.nn.softmax``'s exact sequence
(max, exp(x - max), divide by sum) over the full row — NOT the online
recurrence flash attention uses, which is numerically but not bitwise
equal.  Validity masking arrives as the same additive ``-inf`` bias the
dense step uses, so trash/stale pages contribute exp(-inf) = 0 exactly.
(Kernel vs the XLA fallback still differs at fp32 ULP under jit —
reduction-order freedom in the batched dots — which is why the engine
pins ONE lowering per deployment: the sequential-equivalence oracle is
exact within either lowering, and ``PADDLE_TPU_FUSED=0`` restores the
unfused one verbatim.)

Falls back to interpret mode off-TPU so CPU tier-1 exercises the same
page-table math (``ops/decode_ops.py`` holds the XLA ``take`` unfused
twin behind the ``PADDLE_TPU_FUSED`` kill switch).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import kernel_choice
from .pallas_flash import block_index


def _paged_kernel(pt_ref, q_ref, k_ref, v_ref, bias_ref, o_ref,
                  scores_ref, vbuf_ref, *, scale, n_pages):
    """Grid step (slot, page): score ONE gathered K/V page against the
    slot's single query row, park the page's score column + fp32 V copy
    in VMEM scratch, and run the exact full-row softmax at the last page.

    Keys run down the sublanes throughout (scores are ``[ps, 1]`` columns,
    scratch is indexed by page on its leading dim), so no store lands at
    an unaligned lane offset and nothing is relaid out between the page
    loop and the flush; with one query row the two contractions are VPU
    multiply-reduces, not MXU passes.

    ``pt_ref`` is the scalar-prefetched page table — it is consumed by the
    in_spec index maps (``pt[s, j]`` picks the cache block), not read here.
    """
    del pt_ref
    j = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                    # [1, d]
    if scale != 1.0:
        q = q * jnp.float32(scale)
    k = k_ref[0].astype(jnp.float32)                    # [ps, d]
    s = jnp.sum(k * q, axis=1, keepdims=True)           # [ps, 1]
    scores_ref[j] = s + bias_ref[0, 0].astype(jnp.float32)
    vbuf_ref[j] = v_ref[0].astype(jnp.float32)

    @pl.when(j == jnp.int32(n_pages - 1))
    def _flush():
        z = scores_ref[:]                               # [n_pages, ps, 1]
        m = jnp.max(jnp.max(z, axis=0), axis=0, keepdims=True)
        e = jnp.exp(z - m)
        p = e / jnp.sum(jnp.sum(e, axis=0), axis=0, keepdims=True)
        o = jnp.sum(jnp.sum(p * vbuf_ref[:], axis=0), axis=0,
                    keepdims=True)                      # [1, d]
        o_ref[0] = o.astype(o_ref.dtype)


def paged_attention(q, cache_k, cache_v, page_table, bias, scale=1.0,
                    interpret=None):
    """``softmax(scale · q Kᵀ + bias) V`` where K/V are gathered through
    ``page_table`` from a paged cache.

    q: ``[S, 1, D]`` (one decode step per slot); cache_k/cache_v:
    ``[P + 1, ps, D]`` (row P is the trash page); page_table: ``[S,
    n_pages]`` int (unmapped entries point at the trash page); bias:
    ``[S, 1, L]`` additive validity bias with ``L == n_pages * ps`` and
    exact ``-inf`` beyond each slot's live length.  Returns ``[S, 1, D]``.
    """
    from jax.experimental.pallas import tpu as pltpu

    s_n, _, d = q.shape
    n_pages = page_table.shape[1]
    ps = cache_k.shape[1]
    ell = n_pages * ps
    if bias.shape != (s_n, 1, ell):
        raise ValueError(
            f"paged_attention bias must be [S, 1, n_pages * page_size] = "
            f"[{s_n}, 1, {ell}]; got {bias.shape}")
    interpret = kernel_choice.interpret(interpret)

    def slot(s, j, pt):
        return block_index(s, 0, 0)

    def page(s, j, pt):
        return block_index(pt[s, j], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s_n, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, d), slot),
            pl.BlockSpec((1, ps, d), page),
            pl.BlockSpec((1, ps, d), page),
            # one page's bias as a [ps, 1] column: a [1, ps] row block
            # would have a lane extent that is neither 128-aligned nor
            # the array's, which the TPU lowering refuses
            pl.BlockSpec((1, 1, ps, 1),
                         lambda s, j, pt: block_index(s, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), slot),
        scratch_shapes=[
            pltpu.VMEM((n_pages, ps, 1), jnp.float32),   # full score row
            pltpu.VMEM((n_pages, ps, d), jnp.float32),   # gathered fp32 V
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, scale=float(scale),
                          n_pages=n_pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_n, 1, d), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), q, cache_k, cache_v,
      bias.reshape(s_n, n_pages, ps, 1))
