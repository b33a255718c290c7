"""Ops of a decoder-only language model with routed experts and a learned
sparse attention: RMS norm, rotary positions, the indexer that selects each
query's keys, grouped-query attention over that selection, the short
convolution (between two gates where it is a layer's whole mixer, or
followed by SiLU, a bias a channel before it where the op has one, in front
of a linear attention or a state-space scan), the gated delta rule, the
selective state-space scan, the share of a routed expert layer that the
experts held here give, and a mean in which every row counts by a weight.

Each is a pure JAX function; gradients go through the generic vjp path
(``ops/registry.py``) except where noted.  ``sparse_attention`` and
``moe_experts`` count which path each call took at lowering
(``ops.sparse_attention.calls{topk,seq,path}`` and, under a causal window,
``window``, under the block rule of diffusion over blocks ``block``; ``ops.moe.calls{held,routed,path}`` and, for a router that is
not the softmax one, ``score``, and, where the layer walks its sorted rows
in slabs of fewer than all (``parallel/moe.slab_rows``), ``slab``;
``...declined{why}`` for every fallback;
``ops.moe.bias_updates`` for every ``moe_bias_update`` lowered;
``ops.short_conv.calls{channels,taps,path}`` for every ``short_conv``
lowered, its backward not counted, with ``gated="0"`` where the op is the
filter and SiLU alone;
``ops.delta_rule.calls{key_heads,value_heads,dim,chunk,path}`` for every
``gated_delta_rule`` lowered (``path="pallas"``: the kernels of
``ops/pallas_delta_rule.py``, the scalar rule's or, under a decay a key
channel, the channel rule's, where the ``flash`` gate is open and they
take the operands; ``"xla"``: ``ops/delta_rule.py``, with
``ops.delta_rule.declined{why}`` (``why``: ``chunk``, ``width`` or
``heads``, of either family) where the kernels were asked, would have
been compiled and not interpreted, and gave a reason), its backward not
counted there but as
``ops.delta_rule.grad_calls{chunk,path}`` for every
``gated_delta_rule_grad`` lowered (``path="pallas"``: the kernels' own
backward; ``"by_hand"``: the backward written out in
``ops/delta_rule.py``, no autodiff through the walk over the chunks, the
inverse or the scores, under a decay a value head and under one a key
channel alike), and
``ops.delta_rule.channel_calls{key_heads,dim,chunk,sub}`` beside ``calls``
for every forward lowered with such a G, whichever path it took;
``ops.ssd.scans{heads,dim,groups,state,chunk,path}`` for every ``ssd_scan``
lowered (``path="pallas"``: the kernels of ``ops/pallas_ssd.py``, where the
``flash`` gate is open and they take the operands; ``"xla"``:
``ops/ssd.py``, with ``ops.ssd.declined{why}`` (``why``: ``chunk``,
``width`` or ``heads``) where the kernels were asked, would have been
compiled and not interpreted, and gave a reason) and
``ops.ssd.grad_scans{chunk,path}`` for every ``ssd_scan_grad``
(``path="pallas"``: the kernels' own backward; ``"by_hand"``: the backward
written out in ``ops/ssd.py``);
``ops.moe.ungated_layers`` beside ``ops.moe.calls`` for every
``moe_experts`` lowered whose experts are two matrices about a squared ReLU;
``ops.moe.row_moves{pass,how="gather"}``, which ``parallel/moe.py`` counts
for every row gather it traces (a walk's, whether the layer walks every
``N * top_k`` row at once or a slab of them a trip of its loop): two
``pass="forward"`` for every trace of the layer's forward, of which
``moe_experts`` makes one and ``moe_experts_grad`` another that only its
routing plan outlives, and three ``pass="backward"`` for every
``moe_experts_grad`` lowered;
``ops.moe.column_tiles{kernel,width,tile,tiles,ragged}``, which
``ops/pallas_grouped.py`` counts the same way for every grouped-product
kernel call it traces: the column tile that product took;
``ops.sparse_attention.tiles{kernel,kind}``, which
``ops/pallas_sparse_flash.py`` counts the same way for every attention
kernel call it traces: the live tiles one head walks there,
``kind="interior"`` where no positional mask is made and ``"edge"``;
``ops.weighted_mean.live_rows`` and ``.rows``, STEP GAUGES that
``weighted_mean`` publishes from the weights it is fed: the rows that bear
weight, of how many).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register_grad, register_op

INDEX_Q_BLOCK = 512


def _count(name, value=1, **labels):
    try:
        from .. import observe

        observe.registry().inc(name, value,
                               labels={k: str(v) for k, v in labels.items()})
    except Exception:
        pass  # accounting must never fail the trace it measures


@register_op("rms_norm")
def rms_norm_op(ctx):
    """x * rsqrt(mean(x^2, last axis) + eps) * scale; statistics in float32
    whatever the input's type.  Scale: [x.shape[-1]], so the same op is the
    per-row norm ([B, T, D]) and the per-head one ([B, T, H, Dh]).  The attr
    ``groups`` n > 1: the mean runs over each of the last axis's n equal
    groups of columns by itself, under the one scale."""
    x, scale = ctx.input("X"), ctx.input("Scale")
    xf = x.astype(jnp.float32)
    groups = int(ctx.attr("groups", 1))
    if groups > 1:
        xf = xf.reshape(x.shape[:-1] + (groups, x.shape[-1] // groups))
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                           + jnp.float32(ctx.attr("epsilon", 1e-6)))
    return {"Y": (y.reshape(x.shape) * scale.astype(jnp.float32))
            .astype(x.dtype)}


def rotary(x, theta, start=0, dims=0, interleaved=False, inv_freq=None,
           period=0):
    """x: [B, T, H, D]; position t (the index along axis 1) rotates the
    pair (i, i + n/2) of the ``n = dims`` columns from ``start`` on
    (``dims`` 0: to the head's end) by ``t * theta^(-2i/n)``: the
    rotate-half form.  ``interleaved``: the pair is (2i, 2i + 1) instead.
    ``inv_freq``: the n/2 frequencies themselves, in ``theta``'s place (a
    table that a scaling rule blended).  Columns outside the part pass.
    ``period`` p > 0: axis 1 holds copies of a sequence of p tokens side by
    side, and index i has position ``i mod p``."""
    d = x.shape[-1]
    n = dims or d - start
    if (start, n) != (0, d):
        part = rotary(x[..., start:start + n], theta, 0, 0, interleaved,
                      inv_freq, period)
        return jnp.concatenate(
            [x[..., :start], part, x[..., start + n:]], -1)
    t = x.shape[1]
    inv = jnp.float32(theta) ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d) \
        if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
    at = jnp.arange(t, dtype=jnp.float32)
    if period:
        at = (jnp.arange(t, dtype=jnp.int32)
              % jnp.int32(period)).astype(jnp.float32)
    ang = at[:, None] * inv[None, :]
    if interleaved:
        cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
        pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         -1).reshape(x.shape).astype(x.dtype)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :d // 2], xf[..., d // 2:]
    return (xf * cos + jnp.concatenate([-x2, x1], -1) * sin).astype(x.dtype)


def _rotary_attrs(ctx):
    """``rotary``'s keyword arguments as the op states them; every default
    is the whole head, rotate-half, theta's table."""
    table = ctx.attr("inv_freq", None)
    return dict(theta=ctx.attr("theta", 10000.0),
                start=int(ctx.attr("start", 0)),
                dims=int(ctx.attr("dims", 0)),
                interleaved=bool(ctx.attr("interleaved", False)),
                inv_freq=tuple(table) if table else None,
                period=int(ctx.attr("period", 0)))


@register_op("rotary_embedding")
def rotary_embedding_op(ctx):
    x, how = ctx.input("X"), _rotary_attrs(ctx)
    _count("ops.rotary.calls",
           dims=how["dims"] or x.shape[-1] - how["start"],
           pairing="interleaved" if how["interleaved"] else "half",
           scaled=int(how["inv_freq"] is not None),
           **({"period": how["period"]} if how["period"] else {}))
    return {"Out": rotary(x, **how)}


@register_grad("rotary_embedding")
def rotary_embedding_grad(ctx):
    """The rotation is orthogonal and has no parameter: the cotangent is
    turned back by the same angles (the transpose, through ``jax.vjp``),
    and the forward's lowering is not counted a second time."""
    x, how = ctx.input("X"), _rotary_attrs(ctx)
    _, vjp = jax.vjp(lambda a: rotary(a, **how), x)
    return {"X@GRAD": vjp(ctx.input("Out@GRAD").astype(x.dtype))[0]}


def _order_key(x):
    """float32 -> int32 whose order is the floats' (-0.0 below +0.0)."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)


def select_top_k(score, q0, topk):
    """[bq, tk] int8: for the query at row r (position q0 + r) the ``topk``
    keys ``s <= q0 + r`` of largest score, every such key while there are
    no more than ``topk``; the lowest index wins a tie, as ``lax.top_k``
    has it.  An exact selection without a sort: the k-th largest score of
    each row by bisection on its bit pattern (32 counts), then, among the
    keys that tie with it, the lowest indices by bisection on the index."""
    bq, tk = score.shape
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, tk), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (bq, tk), 1)
    causal = qpos >= kpos
    if topk >= tk:
        return causal.astype(jnp.int8)
    lowest = jnp.int32(-2 ** 31)
    key = jnp.where(causal, _order_key(score.astype(jnp.float32)), lowest)
    want = jnp.minimum(jnp.int32(topk), qpos[:, :1] + 1)         # [bq, 1]

    def count(mask):
        return jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True)

    # largest thr with count(key >= thr) >= want, from the sign bit down
    def value_bit(i, thr):
        bit = jnp.left_shift(jnp.int32(1), jnp.int32(30) - i)
        cand = thr + bit
        return jnp.where(count(key >= cand) >= want, cand, thr)

    thr = jnp.where(count(key >= 0) >= want, jnp.int32(0), lowest)
    thr = jax.lax.fori_loop(0, 31, value_bit,
                            jnp.broadcast_to(thr, (bq, 1)))
    above = key > thr
    ties = key == thr
    need = want - count(above)                                   # >= 1
    # smallest index bound with count(ties & kpos <= bound) >= need
    bits = max(1, (tk - 1).bit_length())

    def index_bit(i, bound):
        bit = jnp.left_shift(jnp.int32(1), jnp.int32(bits - 1) - i)
        cand = bound - bit
        return jnp.where(count(ties & (kpos <= cand)) >= need, cand, bound)

    bound = jax.lax.fori_loop(
        0, bits, index_bit,
        jnp.full((bq, 1), (1 << bits) - 1, jnp.int32))
    return ((above | (ties & (kpos <= bound))) & causal).astype(jnp.int8)


def index_select(x, wq, wk, ww, heads, topk, theta):
    """The indexer of one layer and its top-k: [B, T, T] int8, 1 where
    query t attends key s.  Scores are made in query tiles against the keys
    up to the tile's end, contraction inputs in the AMP type with float32
    accumulation: ``I[t,s] = sum_j w[t,j] relu(qI[t,j].kI[s]) / sqrt(dI)``."""
    from ..fluid import amp

    b, t, _ = x.shape
    di = wk.shape[-1]
    qi = rotary(amp.matmul(x, wq).reshape(b, t, heads, di), theta)
    ki = rotary(amp.matmul(x, wk).reshape(b, t, 1, di), theta)[:, :, 0]
    w = amp.matmul(x, ww).astype(jnp.float32) * jnp.float32(di ** -0.5)
    bq = min(INDEX_Q_BLOCK, t)
    rows = []
    for q0 in range(0, t, bq):
        q1 = min(q0 + bq, t)
        dots = amp.einsum("bqjd,bsd->bqjs", qi[:, q0:q1], ki[:, :q1])
        score = jnp.einsum("bqj,bqjs->bqs", w[:, q0:q1],
                           jax.nn.relu(dots.astype(jnp.float32)))
        sel = jax.vmap(lambda s: select_top_k(s, q0, topk))(score)
        rows.append(jnp.pad(sel, ((0, 0), (0, 0), (0, t - q1))))
    return jnp.concatenate(rows, axis=1)


@register_op("sparse_indexer")
def sparse_indexer_op(ctx):
    sel = index_select(ctx.input("X"), ctx.input("WQ"), ctx.input("WK"),
                       ctx.input("WW"), int(ctx.attr("num_heads")),
                       int(ctx.attr("topk")), ctx.attr("theta", 10000.0))
    return {"Sel": sel}


@register_grad("sparse_indexer")
def sparse_indexer_grad(ctx):
    """The selection is piecewise constant in the indexer's inputs and
    weights: under a loss that reads it only through the attention, their
    gradients are exactly zero (the indexer's own alignment loss is not
    built)."""
    return {slot: jnp.zeros_like(ctx.input(slot[:-5]))
            for slot in ctx.outputs_spec}


def rule_keeps(q0, q1, tokens, block):
    """The keys that the queries ``[q0, q1)`` of ONE copy count under the
    block rule, as ``(first key, last key + 1, keep [q1 - q0, keys])``
    spans of axis T, in the order their columns lie side by side.  With
    ``B(i) = (i mod tokens) // block``: a clean query counts the clean keys
    of blocks ``<= B(t)``, its own block whole; a noised one the clean keys
    of blocks ``< B(t)`` and the noised keys of block ``B(t)``."""
    noised, at = q0 >= tokens, q0 % tokens
    qb = ((at + jnp.arange(q1 - q0)) // block)[:, None]
    end = min(-(-(at + q1 - q0) // block) * block, tokens)
    kb = (jnp.arange(end) // block)[None]
    if not noised:
        return [(0, end, kb <= qb)]
    own = at // block * block
    return [(0, end, kb < qb),
            (tokens + own, tokens + end, kb[:, own:] == qb)]


def blocked_attention(q, k, v, sel, scale, block=512, window=0, rule=None):
    """The XLA path of ``sparse_attention``: query tiles against the keys
    up to the tile's end (under a ``window``, from the first key that the
    tile's first query still sees), the selection and the window as masks,
    the window's made from positions and never a [B, T, T] tensor; each
    tile a checkpoint so that the backward holds one tile's [Hq, bq, T]
    scores at a time.  ``rule`` = (tokens a copy, block length): T holds a
    clean and a noised copy of a sequence, a tile lies in one of them, and
    the keys that count are ``rule_keeps``'s, in one softmax."""
    from ..fluid import amp

    b, hq, t, d = q.shape
    hkv = k.shape[1]
    qg = q.reshape(b, hkv, hq // hkv, t, d)
    dv = v.shape[-1]        # need not be the key's

    @jax.checkpoint
    def tile(qt, kt, vt, keep):
        s = amp.einsum("bgrqd,bgsd->bgrqs", qt, kt).astype(jnp.float32) \
            * jnp.float32(scale)
        s = jnp.where(keep[:, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return amp.einsum("bgrqs,bgsd->bgrqd", p.astype(vt.dtype), vt)

    def cut(x, spans):
        parts = [x[:, :, k0:k1] for k0, k1, _ in spans]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 2)

    # a copy's tiles end with the copy (without a rule T is the one copy)
    copy = rule[0] if rule else t
    bq = min(block, copy)
    outs = []
    for q0, q1 in ((c + a, c + min(a + bq, copy))
                   for c in range(0, t, copy) for a in range(0, copy, bq)):
        if rule:
            spans = rule_keeps(q0, q1, *rule)
            keep = jnp.concatenate([m for _, _, m in spans], 1)
            keep = jnp.broadcast_to(keep[None], (b,) + keep.shape)
            outs.append(tile(qg[:, :, :, q0:q1], cut(k, spans),
                             cut(v, spans), keep))
            continue
        k0 = max(0, q0 - window + 1) if window else 0
        qpos = (q0 + jnp.arange(q1 - q0))[:, None]
        kpos = jnp.arange(k0, q1)[None]
        keep = qpos >= kpos
        if window:
            keep = keep & (qpos - kpos < window)
        keep = jnp.broadcast_to(keep[None], (b,) + keep.shape)
        if sel is not None:
            keep = keep & (sel[:, q0:q1, k0:q1] > 0)
        outs.append(tile(qg[:, :, :, q0:q1], k[:, :, k0:q1], v[:, :, k0:q1],
                         keep))
    return jnp.concatenate(outs, axis=3).reshape(b, hq, t, dv).astype(q.dtype)


def _attention_path(ctx, q, k, v, sel, window, rule, count):
    """'pallas' where the flash gate is open (``kernel_choice.gate``: the
    environment switch where set, else the platform; the op states no
    wish) and the kernels take the operands, else 'xla'; counted where
    ``count``."""
    from . import kernel_choice
    from . import pallas_sparse_flash as psf

    path = "xla"
    if kernel_choice.gate("flash"):
        why = psf.supported(q, k, sel, window, v, rule)
        if not why:
            path = "pallas"
        elif count:
            _count("ops.sparse_attention.declined", why=why)
    if count:
        # the label as the op states it, a window that cuts nothing too
        stated = int(ctx.attr("window", 0))
        _count("ops.sparse_attention.calls", path=path,
               topk=ctx.attr("topk", 0), seq=q.shape[2],
               **({"window": stated} if stated else {}),
               **({"block": rule[1]} if rule else {}))
    return path


def _attention_operands(ctx):
    """(q, k, v, sel, scale, window, rule); a window that reaches every key
    ``s <= t`` is none; ``rule``: None, or (tokens a copy, block length) of
    the block rule, which goes with neither a selection nor a window."""
    q, k, v = ctx.input("Q"), ctx.input("K"), ctx.input("V")
    sel = ctx.input("Sel") if ctx.has_input("Sel") else None
    window = int(ctx.attr("window", 0))
    if window < 0:
        raise ValueError(f"sparse_attention: window {window} is negative")
    tokens, block = int(ctx.attr("copy_tokens", 0)), int(ctx.attr("block", 0))
    rule = (tokens, block) if tokens or block else None
    if rule and (window or sel is not None or block < 1
                 or q.shape[2] != 2 * tokens or tokens % block):
        raise ValueError(
            f"sparse_attention: the block rule over two copies of {tokens} "
            f"tokens in blocks of {block} takes {2 * tokens} positions in "
            f"whole blocks, not {q.shape[2]}, and neither a selection nor a "
            "window")
    return (q, k, v, sel, ctx.attr("scale", 0.0) or q.shape[-1] ** -0.5,
            0 if window >= q.shape[2] else window, rule)


@register_op("sparse_attention", no_grad_inputs=("Sel",))
def sparse_attention_op(ctx):
    """Grouped-query attention, causal unless the block rule is stated:
    optionally over a per-query selection and, with the attr ``window``
    (0: none), over the last ``window`` keys only: key s counts for query t
    iff ``0 <= t - s < window``.  With the attrs ``copy_tokens`` L and
    ``block``, T = 2L holds a clean and a noised copy of a sequence side by
    side and the keys that count are the block rule's (``rule_keeps``),
    which is NOT causal: a query sees the later tokens of its own block.
    Q: [B, Hq, T, D]; K: [B, Hkv, T, D]; V: [B, Hkv, T, Dv] (Out:
    [B, Hq, T, Dv]); Sel: [B, T, T] int8 or absent.  The Pallas kernels
    where the flash gate is open and they take the operands (``Dv = D``
    among the rest), else the blocked XLA path.  Lse ([B, Hq, T, 1]
    float32) is the kernels' log-sum-exp, kept for their backward; zeros on
    the XLA path, whose backward is the generic vjp."""
    from . import pallas_sparse_flash as psf

    q, k, v, sel, scale, window, rule = _attention_operands(ctx)
    if _attention_path(ctx, q, k, v, sel, window, rule, True) == "pallas":
        out, lse = psf.forward(q, k, v, sel, scale, window=window, rule=rule)
        return {"Out": out, "Lse": lse}
    return {"Out": blocked_attention(q, k, v, sel, scale, window=window,
                                     rule=rule),
            "Lse": jnp.zeros(q.shape[:3] + (1,), jnp.float32)}


@register_grad("sparse_attention")
def sparse_attention_grad(ctx):
    """The dQ and dK/dV kernels from the forward's own Out and Lse: the
    generic vjp would trace, and the chip would run, the forward kernel a
    second time.  The XLA path keeps the generic vjp."""
    from . import pallas_sparse_flash as psf
    from . import registry

    q, k, v, sel, scale, window, rule = _attention_operands(ctx)
    if _attention_path(ctx, q, k, v, sel, window, rule, False) != "pallas":
        return registry.run_grad_generic(
            registry.get_op_def("sparse_attention"), ctx)
    dq, dk, dv = psf.backward(q, k, v, sel, ctx.input("Out"),
                              ctx.input("Lse"),
                              ctx.input("Out@GRAD").astype(q.dtype), scale,
                              window=window, rule=rule)
    grads = {"Q@GRAD": dq, "K@GRAD": dk, "V@GRAD": dv}
    return {s: g for s, g in grads.items() if s in ctx.outputs_spec}


@register_op("weighted_mean", no_grad_inputs=("Weight",))
def weighted_mean_op(ctx):
    """``Out = sum(X * Weight) / X.size``: a mean over ALL rows of values
    that count by a weight each (0: not at all), as a loss over the masked
    tokens of a diffusion step is.  X and Weight of one size, float32 sums;
    Out: [1].  Publishes how many rows bear weight and how many there are
    as the step gauges ``ops.weighted_mean.live_rows`` and ``.rows``."""
    from .. import observe

    x = ctx.input("X")
    w = ctx.input("Weight").reshape(x.shape).astype(jnp.float32)
    observe.step_gauge("ops.weighted_mean.live_rows",
                       jnp.sum((w > 0).astype(jnp.float32)))
    observe.step_gauge("ops.weighted_mean.rows", jnp.float32(x.size))
    return {"Out": (jnp.sum(x.astype(jnp.float32) * w)
                    / jnp.float32(x.size)).reshape(1).astype(x.dtype)}


@register_grad("weighted_mean")
def weighted_mean_grad(ctx):
    """``dX = dOut * Weight / X.size``; the weight gets none.  Written out,
    so that the forward (and its gauges) is not traced a second time."""
    x = ctx.input("X")
    w = ctx.input("Weight").reshape(x.shape).astype(jnp.float32)
    dout = ctx.input("Out@GRAD").astype(jnp.float32).reshape(())
    return {"X@GRAD": (dout * w / jnp.float32(x.size)).astype(x.dtype)}


def causal_filter(z, w):
    """``sum_j w[:, j] * z[t - (L - 1) + j]`` in float32 for z [batch, T,
    channels] and w [channels, L]: one causal L-tap filter a channel,
    ``z[s]`` zero for s < 0.  L shifted multiply-adds over the padded
    input, which XLA fuses into one loop."""
    taps = w.shape[1]
    t = z.shape[1]
    z = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    return sum(wf[:, j] * z[:, j:j + t].astype(jnp.float32)
               for j in range(taps))


def gated_short_conv(x, w):
    """``y[t] = C[t] * sum_j w[:, j] * (B * u)[t - (L - 1) + j]`` for
    x = [B | C | u] ([batch, T, 3 * channels], three chunks in this order)
    and w [channels, L] (``causal_filter`` of ``B * u``).  The gates
    multiply in x's type (bf16 under AMP); the taps are summed in
    float32."""
    c = w.shape[0]
    acc = causal_filter(x[..., :c] * x[..., 2 * c:], w)
    return (x[..., c:2 * c].astype(jnp.float32) * acc).astype(x.dtype)


def silu_short_conv(x, w, bias=None):
    """``y = SiLU(causal_filter(x) [+ bias])`` for x [batch, T, channels]:
    the filter alone, as it stands in front of a linear attention or a
    state-space scan, ``bias`` [channels] where the filter has one; filter,
    bias and SiLU in float32."""
    acc = causal_filter(x, w)
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    return jax.nn.silu(acc).astype(x.dtype)


def _short_conv_form(ctx):
    """(the op's form as a function of its operands, is it the gated one,
    the operands: X and Filter, and Bias where the op has one)."""
    gated = bool(ctx.attr("gated", True))
    operands = [ctx.input("X"), ctx.input("Filter")]
    if ctx.has_input("Bias"):
        if gated:
            raise ValueError("short_conv: a bias on the gated form, which "
                             "has none")
        operands.append(ctx.input("Bias"))
    return (gated_short_conv if gated else silu_short_conv), gated, operands


@register_op("short_conv")
def short_conv_op(ctx):
    """The causal per-channel filter over the sequence, in one of two
    forms.  ``gated`` (the default), the token mixer of a layer without
    attention: both gates around the filter (``gated_short_conv``), X:
    [B, T, 3C]; Filter: [C, L]; Out: [B, T, C].  Not ``gated``: the filter
    and SiLU (``silu_short_conv``), X and Out [B, T, C], with the input
    Bias [C] where the filter has one.  No state crosses sequences: each
    row of the batch is padded on its own."""
    form, gated, operands = _short_conv_form(ctx)
    w = operands[1]
    _count("ops.short_conv.calls", channels=w.shape[0], taps=w.shape[1],
           path="xla", **({} if gated else {"gated": 0}),
           **({"bias": 1} if len(operands) == 3 else {}))
    return {"Out": form(*operands)}


@register_grad("short_conv")
def short_conv_grad(ctx):
    """From X and Filter (and Bias) alone: the filter's input and its sum
    are made again, nothing but the op's inputs is kept from the forward."""
    form, _, operands = _short_conv_form(ctx)
    _, vjp = jax.vjp(form, *operands)
    grads = dict(zip(("X@GRAD", "Filter@GRAD", "Bias@GRAD"),
                     vjp(ctx.input("Out@GRAD").astype(operands[0].dtype))))
    return {s: g for s, g in grads.items() if s in ctx.outputs_spec}


def _delta_rule(ctx):
    """(the rule as a function of the op's five inputs, the inputs, why the
    Pallas kernels do not take them: ``delta_rule.kernel_declines``)."""
    from . import delta_rule

    chunk = int(ctx.attr("chunk", 64))

    def rule(q, k, v, g, beta):
        return delta_rule.chunked(
            q, k, v, g, beta, chunk=chunk,
            scale=float(ctx.attr("scale", 0.0)),
            norm_eps=float(ctx.attr("norm_eps", 0.0)))

    operands = [ctx.input(s) for s in ("Q", "K", "V", "G", "Beta")]
    return rule, operands, delta_rule.kernel_declines(*operands[:4], chunk)


@register_op("gated_delta_rule")
def gated_delta_rule_op(ctx):
    """The gated delta rule over the sequence (``ops/delta_rule.py``), in
    chunks of ``chunk`` tokens.  Q, K: [B, T, Hk, dk]; V: [B, T, Hv, dv]
    with Hv a multiple of Hk; G (the log of each token's decay, <= 0) and
    Beta (its step): [B, T, Hv]; Out: [B, T, Hv, dv].  ``scale`` multiplies
    Q (0: ``dk ** -0.5``); ``norm_eps`` > 0: Q and K are l2-normed per head
    first.  The state starts at zero in every row of the batch and nothing
    crosses from one row to the next.  G [B, T, Hv, dk]: a decay a key
    channel, the state's rows each by their own."""
    from . import delta_rule, kernel_choice

    rule, operands, why = _delta_rule(ctx)
    q, v, g = operands[0], operands[2], operands[3]
    chunk = int(ctx.attr("chunk", 64))
    # a refusal is counted where the kernels would have been compiled: off
    # the TPU they are interpreted, a correctness tool, and what the tests
    # and the benchmark's rehearsals run (chunks of 16, heads of 8) is no
    # rule they are for; ``calls{path}`` says which path ran there too
    if why and not kernel_choice.interpret():
        _count("ops.delta_rule.declined", why=why)
    _count("ops.delta_rule.calls", key_heads=q.shape[2],
           value_heads=v.shape[2], dim=v.shape[3], chunk=chunk,
           path="pallas" if why == "" else "xla")
    if g.ndim == 4:
        _count("ops.delta_rule.channel_calls", key_heads=q.shape[2],
               dim=q.shape[3], chunk=chunk, sub=delta_rule.sub_block(chunk))
    return {"Out": rule(*operands)}


@register_grad("gated_delta_rule")
def gated_delta_rule_grad(ctx):
    """From the op's five inputs alone, by the backward ``delta_rule.chunked``
    carries (a ``jax.custom_vjp`` written by hand under either kind of
    decay, which ``jax.vjp`` below meets: nothing differentiates through
    the walk, the inverse or the scores): everything a chunk needs (decays,
    the inverse, what each token writes) and the state at every chunk's
    start are made again, the outputs are not, then the chunks are walked
    backwards; nothing but the inputs is kept from the forward."""
    rule, operands, why = _delta_rule(ctx)
    _count("ops.delta_rule.grad_calls", chunk=int(ctx.attr("chunk", 64)),
           path="pallas" if why == "" else "by_hand")
    # behind a barrier with the cotangent in it, as ``jax.checkpoint`` puts
    # one: without it XLA finds the second forward to be the first and
    # keeps a gigabyte a layer (every chunk's state, inverse and writes)
    # from the forward pass to here
    operands, dout = jax.lax.optimization_barrier(
        (operands, ctx.input("Out@GRAD")))
    _, vjp = jax.vjp(rule, *operands)
    grads = dict(zip(("Q@GRAD", "K@GRAD", "V@GRAD", "G@GRAD", "Beta@GRAD"),
                     vjp(dout.astype(operands[2].dtype))))
    return {s: g for s, g in grads.items() if s in ctx.outputs_spec}


def _ssd_scan(ctx):
    """(the scan as a function of the op's six inputs, the inputs, why the
    Pallas kernels do not take them: ``ssd.kernel_declines``)."""
    from . import ssd

    chunk, groups = int(ctx.attr("chunk", 128)), int(ctx.attr("groups", 1))

    def scan(u, delta, a, b, c, d):
        return ssd.chunked(u, delta, a, b, c, d, chunk=chunk, groups=groups)

    operands = [ctx.input(s) for s in ("U", "Delta", "A", "B", "C", "D")]
    u, delta, _, b, c, _ = operands
    return scan, operands, ssd.kernel_declines(u, delta, b, c, chunk, groups)


@register_op("ssd_scan")
def ssd_scan_op(ctx):
    """A selective state-space scan over the sequence (``ops/ssd.py``; its
    Pallas kernels, ``ops/pallas_ssd.py``, where they take the operands),
    in chunks of ``chunk`` tokens.  U: [B, T, H, P]; Delta (each token's
    step, > 0): [B, T, H]; A (< 0) and D: [H]; B and C: [B, T, groups * N],
    head h reading group ``h // (H // groups)``; Out: [B, T, H, P].  Every
    head keeps a [P, N] state that a token decays by ``exp(Delta A)``, adds
    ``Delta u B^T`` to and reads along C; ``D u`` passes beside it.  The
    state starts at zero in every row of the batch and nothing crosses from
    one row to the next."""
    from . import kernel_choice

    scan, operands, why = _ssd_scan(ctx)
    u, groups = operands[0], int(ctx.attr("groups", 1))
    # a refusal is counted where the kernels would have been compiled, as
    # the delta rule's is: what the tests and the benchmark's rehearsals
    # run (chunks of 16, heads of 8) is no scan they are for
    if why and not kernel_choice.interpret():
        _count("ops.ssd.declined", why=why)
    _count("ops.ssd.scans", heads=u.shape[2], dim=u.shape[3], groups=groups,
           state=operands[3].shape[-1] // groups,
           chunk=int(ctx.attr("chunk", 128)),
           path="pallas" if why == "" else "xla")
    return {"Out": scan(*operands)}


@register_grad("ssd_scan")
def ssd_scan_grad(ctx):
    """From the op's six inputs alone, by the backward ``ssd.chunked``
    carries (a ``jax.custom_vjp`` written by hand on either path, the
    kernels' in ``ops/pallas_ssd.py`` and the XLA lowering's in
    ``ops/ssd.py``, which ``jax.vjp`` below meets: nothing differentiates
    through the walk): the chunks' decays, scores and writes and the state
    at every chunk's start are made again, the outputs are not, then the
    chunks are walked backwards."""
    scan, operands, why = _ssd_scan(ctx)
    _count("ops.ssd.grad_scans", chunk=int(ctx.attr("chunk", 128)),
           path="pallas" if why == "" else "by_hand")
    dout = ctx.input("Out@GRAD")
    if why != "":
        # the XLA lowering: behind a barrier with the cotangent in it, as
        # the delta rule's: XLA must not find the second forward to be the
        # first and keep every chunk's decays, scores and states from the
        # forward pass to here.  The kernels make no second forward (their
        # backward's first pass emits the states alone), and a barrier over
        # their operands costs 105 MB of the step's reserved region
        # (Nemotron's step compiled for the described chip: 4.92 GB with,
        # 4.81 without, 4.73 at the parent)
        operands, dout = jax.lax.optimization_barrier((operands, dout))
    _, vjp = jax.vjp(scan, *operands)
    grads = dict(zip(("U@GRAD", "Delta@GRAD", "A@GRAD", "B@GRAD", "C@GRAD",
                      "D@GRAD"), vjp(dout.astype(operands[0].dtype))))
    return {s: g for s, g in grads.items() if s in ctx.outputs_spec}


@register_op("moe_experts", no_grad_inputs=("Bias",))
def moe_experts_op(ctx):
    """``parallel/moe.routed_experts`` as an op.  With the input ``Bias``
    ([num_routed], a selection bias that chooses and does not weigh) the
    op also gives ``Counts`` ([num_routed] int32, the step's assignments to
    every routed expert), which ``moe_bias_update`` reads.  Without the
    input ``W3`` the experts are two matrices about a squared ReLU."""
    from ..parallel import moe

    w1 = ctx.input("W1")
    routed = int(ctx.attr("num_routed"))
    held, offset = int(ctx.attr("experts_held")), int(ctx.attr(
        "expert_offset", 0))
    if w1.shape[0] != held or ctx.input("RouterW").shape[-1] != routed \
            or offset < 0 or offset + held > routed:
        raise ValueError(
            f"moe_experts: {w1.shape[0]} expert weights and a router "
            f"{ctx.input('RouterW').shape[-1]} wide for experts_held="
            f"{held}, expert_offset={offset}, num_routed={routed}")
    score = ctx.attr("score", "softmax")
    bias = ctx.input("Bias") if ctx.has_input("Bias") else None
    if bias is not None and bias.shape != (routed,):
        raise ValueError(f"moe_experts: a selection bias {bias.shape} for "
                         f"num_routed={routed}")
    top_k = int(ctx.attr("top_k"))
    path, rows, slab = moe.walk_of(ctx.input("X"), ctx.input("RouterW"), w1,
                                   ctx.input("W2"), top_k, bias)
    _count("ops.moe.calls", held=held, routed=routed, path=path,
           **({} if score == "softmax" else {"score": score}),
           **({} if slab == rows else {"slab": slab}))
    w3 = ctx.input("W3") if ctx.has_input("W3") else None
    if w3 is None:
        _count("ops.moe.ungated_layers")
    out = moe.routed_experts(
        ctx.input("X"), ctx.input("RouterW"), w1, w3,
        ctx.input("W2"), top_k=top_k,
        expert_offset=offset, norm_topk=bool(ctx.attr("norm_topk", True)),
        score=score, bias=bias,
        norm_eps=float(ctx.attr("norm_eps", 0.0)),
        scale=float(ctx.attr("route_scale", 1.0)),
        with_counts=bias is not None)
    if bias is None:
        return {"Out": out}
    return {"Out": out[0], "Counts": out[1]}


@register_op("moe_bias_update", no_grad_inputs=("Bias", "Counts"))
def moe_bias_update_op(ctx):
    """The balancing rule of a router's selection bias, after the step:
    ``BiasOut = Bias + coeff * sign(mean(Counts) - Counts)``
    (``parallel/moe.balance_bias``).  State changed by a rule: the op has
    no gradient and the bias gets none."""
    from ..parallel import moe

    _count("ops.moe.bias_updates")
    return {"BiasOut": moe.balance_bias(
        ctx.input("Bias"), ctx.input("Counts"), float(ctx.attr("coeff")))}
