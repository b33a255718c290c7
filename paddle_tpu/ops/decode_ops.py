"""Decode-step op surface for continuous batching (ISSUE 15).

Two ops make an autoregressive decode step expressible as a fixed-shape
fluid program the serving engine can dispatch once per iteration:

 - ``kv_cache_update``: scatter a window of freshly projected K/V rows
   into a persistable ``[max_slots, max_len, ...]`` cache at per-row
   (slot, position) destinations.  The op's output IS the cache var
   (in-place by name), so the executor commits it as persistent state
   after every dispatch and — with ``program._donate_state`` set — the
   donation machinery aliases the cache buffer window-over-window
   instead of copying it (the PR 6 donated-carry idiom, applied to the
   serving path).
 - ``token_select``: greedy next-token choice per slot —
   ``argmax(logits)`` where the slot is active, the ``end_id`` pad token
   where it is not, so retired/free slots emit inert tokens without a
   host round trip inside the step.

Both are row-independent over the slot dim on purpose: a slot's token
stream is a function of its own prompt and cache rows only, which is
what makes continuous-batching output bitwise identical to per-request
sequential decode (the ISSUE 15 convoy oracle's correctness half).

ISSUE 19 adds ``paged_attention``: decode attention over a page-pool
cache (``[num_pages + 1, page_size, d_model]`` + a per-tick ``[slots,
pages_per_slot]`` page table from serving/kvpool).  Dispatch follows the
PR 12 fused discipline — ``PADDLE_TPU_FUSED`` gates the Pallas kernel
(ops/pallas_paged.py, scalar-prefetch gather inside the kernel) against
an XLA ``take``-based unfused twin that runs the exact same page-table
math, so CPU tier-1 proves the indirection and the kill switch restores
the unfused lowering bitwise.

ISSUE 20 adds the speculative-decode pair:

 - ``kv_cache_scatter``: per-token K/V writes at explicit (row, offset)
   destinations.  The verify step writes k + 1 positions per slot in one
   dispatch; ``kv_cache_update``'s whole-row scatter loses writes when
   the same slot appears twice (last duplicated row wins), so the wide
   step needs true element-granular destinations.  One op covers both
   layouts: dense caches pass (slot, absolute position), paged caches
   pass (page, in-page offset).  Out-of-range rows are JAX-scatter-
   dropped — the dense-mode "trash slot" that mirrors the pool's trash
   page.
 - ``spec_accept``: device-side greedy acceptance — the longest prefix
   where the draft token equals the verify argmax, plus the first
   correction token.  Because every emitted token IS a target argmax
   at a position whose cache prefix matches sequential decode, accepted
   output is bitwise identical to one-token greedy by construction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register_op

__all__ = []


@register_op("kv_cache_update", stateful=True,
             no_grad_inputs=("Slots", "Pos"))
def kv_cache_update(ctx):
    """Cache [S, L, ...], New [n, w, ...], Slots [n] int, Pos [n] int ->
    Out = Cache with ``New[j]`` written at ``Cache[Slots[j], Pos[j]:
    Pos[j]+w]``.  Callers keep ``Pos[j] + w <= L`` (the engine's
    max_len admission check); ``dynamic_update_slice`` clamps anything
    else rather than corrupting neighbor rows."""
    cache = ctx.input("Cache")
    new = ctx.input("New").astype(cache.dtype)
    slots = ctx.input("Slots").astype(jnp.int32).reshape(-1)
    pos = ctx.input("Pos").astype(jnp.int32).reshape(-1)
    rows = jnp.take(cache, slots, axis=0)          # [n, L, ...]

    def write(row, window, p):
        start = (p,) + (jnp.int32(0),) * (row.ndim - 1)
        return jax.lax.dynamic_update_slice(row, window, start)

    rows = jax.vmap(write)(rows, new, pos)
    return {"Out": cache.at[slots].set(rows)}


@register_op("kv_cache_scatter", stateful=True,
             no_grad_inputs=("Rows", "Offs"))
def kv_cache_scatter(ctx):
    """Cache [R, W, ...], New [n, ...], Rows [n] int, Offs [n] int ->
    Out = Cache with ``New[j]`` written at ``Cache[Rows[j], Offs[j]]``.
    Unlike ``kv_cache_update`` this scatters single positions, so a slot
    may appear in ``Rows`` many times (the verify step's k + 1 writes)
    as long as each (row, off) pair is unique.  Rows >= R (or < 0) are
    dropped by JAX scatter semantics — callers steer masked-out lanes
    there on purpose."""
    cache = ctx.input("Cache")
    new = ctx.input("New").astype(cache.dtype)
    rows = ctx.input("Rows").astype(jnp.int32).reshape(-1)
    offs = ctx.input("Offs").astype(jnp.int32).reshape(-1)
    return {"Out": cache.at[rows, offs].set(new)}


@register_op("spec_accept", no_grad_inputs=("Draft", "Mask"))
def spec_accept(ctx):
    """Logits [S, k+1, V], Draft [S, k] int (+ optional Mask [S]) ->
    Tokens [S, k+1] int64, NumAccept [S] int64.

    ``Tokens[s] = argmax(Logits[s], -1)`` is what sequential greedy
    decode would emit at each of the k + 1 scored positions given the
    accepted prefix; ``NumAccept[s] = n`` is the longest prefix with
    ``Draft[s, i] == Tokens[s, i]`` — the engine consumes tokens
    ``Tokens[s, :n+1]`` (n accepted + 1 correction/bonus), all of them
    target argmaxes, so output is bitwise greedy by construction.
    Inactive slots (mask == 0) emit ``end_id`` everywhere and accept 0,
    the token_select idiom widened."""
    logits = ctx.input("Logits")
    draft = ctx.input("Draft").astype(jnp.int64)
    end_id = int(ctx.attr("end_id", 0))
    toks = jnp.argmax(logits, axis=-1).astype(jnp.int64)   # [S, k+1]
    match = (draft == toks[:, :-1]).astype(jnp.int64)      # [S, k]
    nacc = jnp.cumprod(match, axis=1).sum(axis=1)          # [S]
    mask = ctx.input("Mask") if ctx.has_input("Mask") else None
    if mask is not None:
        live = mask.reshape(-1) > 0
        toks = jnp.where(live[:, None], toks, jnp.int64(end_id))
        nacc = jnp.where(live, nacc, jnp.int64(0))
    return {"Tokens": toks, "NumAccept": nacc}


@register_op("paged_attention", no_grad_inputs=("PageTable", "Bias"))
def paged_attention_op(ctx):
    """Q [S, 1, D], CacheK/CacheV [P + 1, ps, D], PageTable [S, n] int,
    Bias [S, 1, n·ps] -> Out [S, 1, D]: one decode step of attention with
    K/V gathered through the page table (row P is the trash page; the
    bias carries exact ``-inf`` past each slot's live length, so trash
    and stale pages contribute exp(-inf) = 0 — the same masking that
    makes the dense step's retired slots inert).

    The unfused lowering mirrors the dense step's op sequence exactly
    (``matmul`` with transposed Y, ``+ bias``, ``jax.nn.softmax``,
    ``matmul``) over the ``jnp.take``-gathered pages, so with the same
    fp32 cache content it is bitwise identical to the dense attention —
    the paged≡dense sequential-equivalence oracle rides on that."""
    q = ctx.input("Q")
    ck = ctx.input("CacheK")
    cv = ctx.input("CacheV")
    pt = ctx.input("PageTable")
    bias = ctx.input("Bias")
    scale = float(ctx.attr("scale", 1.0))
    from . import kernel_choice, pallas_fused

    # The Pallas kernel is specialized to one query row per slot; the
    # speculative verify step passes k + 1 rows and always takes the
    # generic unfused lowering (bitwise-identical math either way).
    if q.shape[1] == 1 and kernel_choice.gate("fused"):
        from .pallas_paged import paged_attention

        out = paged_attention(q, ck, cv, pt, bias, scale)
        pallas_fused._note("paged_attention")
        return {"Out": out}
    qs = q if scale == 1.0 else q * q.dtype.type(scale)
    pt32 = pt.astype(jnp.int32)
    n_pages = pt32.shape[1]
    ps = ck.shape[1]
    gk = jnp.take(ck, pt32, axis=0).reshape(
        q.shape[0], n_pages * ps, ck.shape[2])
    gv = jnp.take(cv, pt32, axis=0).reshape(
        q.shape[0], n_pages * ps, cv.shape[2])
    scores = jnp.matmul(qs, jnp.swapaxes(gk, -1, -2)) + bias
    probs = jax.nn.softmax(scores, axis=-1)
    return {"Out": jnp.matmul(probs, gv)}


@register_op("token_select", no_grad_inputs=("Mask",))
def token_select(ctx):
    """Logits [S, V] (+ optional Mask [S]) -> Out [S] int64: per-slot
    greedy argmax; inactive slots (mask == 0) emit ``end_id`` so free
    slots never contribute spurious tokens.  argmax ties break to the
    lowest index — deterministic for a fixed executable, part of the
    bitwise sequential-equivalence contract."""
    logits = ctx.input("Logits")
    end_id = int(ctx.attr("end_id", 0))
    out = jnp.argmax(logits, axis=-1).astype(jnp.int64)
    mask = ctx.input("Mask") if ctx.has_input("Mask") else None
    if mask is not None:
        out = jnp.where(mask.reshape(-1) > 0, out, jnp.int64(end_id))
    return {"Out": out}
