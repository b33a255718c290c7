"""Stacked transformer layer blocks: scan-over-layers single-device, GPipe
pipeline over a "pp" mesh axis, Megatron-style tensor parallelism over "mp",
and ring-attention sequence parallelism over "sp" — composable on one mesh.

This is the TPU-first formulation of a transformer encoder/decoder stack
(used by models/transformer.py when cfg.pipeline_stages is set): every
layer's parameters are STACKED on a leading [L, ...] dim, so

 - single-device, the stack is a ``lax.scan`` over layers (one compiled
   layer body instead of L unrolled copies — faster compiles, same math);
 - with a "pp" mesh axis, layers shard over stages (dim 0) and microbatches
   flow through a GPipe ``ppermute`` schedule (parallel/pipeline.py design,
   generalized to a tree-valued carry so the encoder output / attention
   biases ride along with the activations);
 - with an "mp" axis, the per-layer matmuls run Megatron column/row
   parallel INSIDE the same shard_map body (q/k/v + ffn1 column-split,
   o + ffn2 row-split with one ``psum`` each);
 - with an "sp" axis, attention runs the ring schedule
   (parallel/ring_attention.py) over the sequence dim.

The reference has none of these (SURVEY.md §2.6: PP/SP/EP "Absent in
Fluid"); its transformer test model (python/paddle/fluid/tests/unittests/
transformer_model.py) is the functional contract for the per-layer math:
post-norm residual sublayers, scaled-dot-product attention with additive
biases, relu FFN.

Dropout matches fluid.layers.dropout's default ``downgrade_in_infer``
semantics and is applied to sublayer OUTPUTS (residual dropout).  Attention-
probability dropout is intentionally absent: under ring attention the
[T, T] probability matrix never materializes, so there is nothing to mask —
the residual dropout keeps the regularization story while staying identical
across every mesh layout.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.random_ops import keep_mask
from . import ring_attention as ra

# slot -> (index of the dim sharded over "mp", or None).  Dim 0 is always
# the stacked layer dim (sharded over "pp" when present).  Column-parallel
# weights split their OUTPUT dim, row-parallel their INPUT dim (Megatron).
ENCODER_SLOTS = {
    "WQ": 2, "WK": 2, "WV": 2,          # [L, d, d]   column
    "WO": 1,                             # [L, d, d]   row
    "FFN1W": 2, "FFN1B": 1,              # [L, d, di] / [L, di] column
    "FFN2W": 1,                          # [L, di, d]  row
    "FFN2B": None,                       # [L, d]      replicated
    "LN1S": None, "LN1B": None, "LN2S": None, "LN2B": None,  # [L, d]
}
DECODER_SLOTS = dict(ENCODER_SLOTS)
DECODER_SLOTS.update({
    "CQ": 2, "CK": 2, "CV": 2, "CO": 1,  # cross-attention projections
    "LN3S": None, "LN3B": None,
})


def dist_spec_for(slot: str, ndim: int, decoder: bool) -> tuple:
    """Per-dim mesh-axis hints for a stacked param (consumed by
    spmd.infer_param_specs): dim 0 -> "pp", the Megatron dim -> "mp"."""
    table = DECODER_SLOTS if decoder else ENCODER_SLOTS
    mp_dim = table[slot]
    spec = ["pp"] + [None] * (ndim - 1)
    if mp_dim is not None:
        spec[mp_dim] = "mp"
    return tuple(spec)


def _layer_norm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * scale + bias


def _dropout(x, key, rate, is_test):
    """fluid.layers.dropout default (downgrade_in_infer) semantics."""
    if not rate:
        return x
    if is_test:
        return x * (1.0 - rate)
    return x * keep_mask(key, 1.0 - rate, x.shape).astype(x.dtype)


def _attend(q, k, v, bias, causal, local_heads, sp_axis, flash=False):
    """[b, tq, dh] x [b, tk, dh] -> [b, tq, dh] with dh split into
    ``local_heads`` heads; bias is [b, 1, 1, tk(-local)] or None.  Inside a
    shard_map with an sp axis the ring schedule runs over it; with
    flash=True the Pallas streamed kernel (fwd + bwd) runs instead of the
    XLA full-softmax; ``scale`` uses the GLOBAL head dim, which equals the
    local head dim (mp splits heads, not head size)."""
    b, tq, dh = q.shape
    tk = k.shape[1]
    dk = dh // local_heads
    q4 = q.reshape(b, tq, local_heads, dk).transpose(0, 2, 1, 3)
    k4 = k.reshape(b, tk, local_heads, dk).transpose(0, 2, 1, 3)
    v4 = v.reshape(b, tk, local_heads, dk).transpose(0, 2, 1, 3)
    scale = dk ** -0.5
    if sp_axis is not None:
        ctx = ra._ring_body(q4, k4, v4, bias, axis_name=sp_axis,
                            causal=causal, scale=scale)
    elif flash and _flash_bias_ok(bias, b, tk):
        from ..ops.pallas_flash import flash_attention

        ctx = flash_attention(q4, k4, v4, bias, scale, causal)
    else:
        ctx = ra.full_attention(q4, k4, v4, causal=causal, scale=scale,
                                bias=bias)
    return ctx.transpose(0, 2, 1, 3).reshape(b, tq, dh)


def _flash_bias_ok(bias, b, t_kv):
    from ..ops.pallas_flash import bias_supported

    return bias_supported(bias, b, t_kv)


def _attend_in_shard_map(local_heads, sp_axis, flash=False):
    """Attention callable for code already INSIDE a shard_map body."""
    def go(q, k, v, bias, causal):
        return _attend(q, k, v, bias, causal, local_heads, sp_axis,
                       flash=flash)

    return go


def _attend_gspmd_ring(n_head, mesh, sp_axis):
    """Attention callable for the scan path with an sp axis: the ring runs
    via the mesh-aware wrapper (its own shard_map); GSPMD owns the rest."""
    def go(q, k, v, bias, causal):
        b, tq, dh = q.shape
        tk = k.shape[1]
        dk = dh // n_head

        def to4(a, t):
            return a.reshape(b, t, n_head, dk).transpose(0, 2, 1, 3)

        ctx = ra.ring_attention(to4(q, tq), to4(k, tk), to4(v, tk), mesh,
                                sp_axis, causal=causal, bias=bias)
        return ctx.transpose(0, 2, 1, 3).reshape(b, tq, dh)

    return go


def _mm(a, b):
    """Matmul under the shared AMP recipe (fluid/amp.py matmul): bf16
    operands on the MXU, fp32 activation contract restored."""
    from ..fluid import amp

    return amp.matmul(a, b)


def _mha(p, prefix, x, kv, bias, causal, attend, mp_axis):
    """Projections + attention + output projection for one attention
    sublayer; prefix selects self ("W") or cross ("C") weights."""
    q = _mm(x, p[prefix + "Q"])
    k = _mm(kv, p[prefix + "K"])
    v = _mm(kv, p[prefix + "V"])
    out = _mm(attend(q, k, v, bias, causal), p[prefix + "O"])
    if mp_axis is not None:
        out = lax.psum(out, mp_axis)
    return out


def _ffn_sublayer(p, x, key, dropout, is_test, mp_axis, ln):
    h = jax.nn.relu(_mm(x, p["FFN1W"]) + p["FFN1B"])
    ff = _mm(h, p["FFN2W"])
    if mp_axis is not None:
        ff = lax.psum(ff, mp_axis)
    ff = ff + p["FFN2B"]
    return _layer_norm(x + _dropout(ff, key, dropout, is_test),
                       p[ln + "S"], p[ln + "B"])


def _encoder_layer(p: Dict[str, jnp.ndarray], x, bias, key, *, attend,
                   dropout, is_test, mp_axis):
    """One post-norm encoder layer.  p holds THIS layer's (possibly
    mp-local) param slices; x: [b, t, d]; bias: [b, 1, 1, t] or None.
    ``attend`` is the attention callable (full softmax / in-shard_map ring
    / GSPMD ring) — the single layer body serves every mesh layout."""
    k1, k2 = jax.random.split(key)
    attn = _mha(p, "W", x, x, bias, False, attend, mp_axis)
    x = _layer_norm(x + _dropout(attn, k1, dropout, is_test),
                    p["LN1S"], p["LN1B"])
    return _ffn_sublayer(p, x, k2, dropout, is_test, mp_axis, "LN2")


def _decoder_layer(p, x, enc, src_bias, key, *, attend, dropout, is_test,
                   mp_axis):
    """One post-norm decoder layer: causal self-attn, cross-attn, FFN."""
    k1, k2, k3 = jax.random.split(key, 3)
    sa = _mha(p, "W", x, x, None, True, attend, mp_axis)
    x = _layer_norm(x + _dropout(sa, k1, dropout, is_test),
                    p["LN1S"], p["LN1B"])
    ca = _mha(p, "C", x, enc, src_bias, False, attend, mp_axis)
    x = _layer_norm(x + _dropout(ca, k2, dropout, is_test),
                    p["LN2S"], p["LN2B"])
    return _ffn_sublayer(p, x, k3, dropout, is_test, mp_axis, "LN3")


def _scan_layers(layer_fn, params, carry_x, key, n_layer):
    """No-pp path: fold the stacked params with lax.scan (one compiled
    layer body).  GSPMD handles any mp/sp sharding of the scanned slices."""
    def body(x, inp):
        i, p = inp
        return layer_fn(p, x, jax.random.fold_in(key, i)), None

    x, _ = lax.scan(body, carry_x,
                    (jnp.arange(n_layer), params))
    return x


# ---------------------------------------------------------------------------
# GPipe schedule with a tree-valued carry
# ---------------------------------------------------------------------------


def _gpipe_tree_body(params, xs: Dict[str, jnp.ndarray], *, stage_fn,
                     pp_axis, n_micro, out_slot):
    """Runs inside shard_map.  xs: dict of LOCAL [n, ...] arrays that flow
    together through the pipeline (activations + context like enc_out /
    biases); stage_fn(params, tree, t) -> tree updates ``out_slot`` and
    passes the rest through.  Returns the final ``out_slot`` stream."""
    s_total = lax.axis_size(pp_axis)
    stage = lax.axis_index(pp_axis)
    n = next(iter(xs.values())).shape[0]
    if n % n_micro:
        raise ValueError(
            f"per-stage local batch {n} not divisible by n_micro {n_micro}")
    mb = n // n_micro
    xmb = {k: v.reshape((n_micro, mb) + v.shape[1:]) for k, v in xs.items()}
    perm = [(j, (j + 1) % s_total) for j in range(s_total)]

    def pick(t):
        return {k: lax.dynamic_index_in_dim(v, jnp.clip(t, 0, n_micro - 1),
                                            0, keepdims=False)
                for k, v in xmb.items()}

    def step(carry, t):
        cur, out_buf = carry
        recv = {k: lax.ppermute(v, pp_axis, perm) for k, v in cur.items()}
        mine = pick(t)
        my_in = {k: jnp.where(stage == 0, mine[k], recv[k]) for k in cur}
        out = stage_fn(params, my_in, t)
        o_idx = jnp.clip(t - (s_total - 1), 0, n_micro - 1)
        write = (stage == s_total - 1) & (t >= s_total - 1) \
            & (t - (s_total - 1) < n_micro)
        out_buf = jnp.where(
            write,
            lax.dynamic_update_index_in_dim(out_buf, out[out_slot], o_idx, 0),
            out_buf)
        return (out, out_buf), None

    cur0 = {k: lax.pcast(jnp.zeros_like(v[0]), (pp_axis,), to="varying")
            for k, v in xmb.items()}
    buf0 = lax.pcast(jnp.zeros_like(xmb[out_slot]), (pp_axis,), to="varying")
    (_, out_buf), _ = lax.scan(step, (cur0, buf0),
                               jnp.arange(n_micro + s_total - 1))
    out_buf = lax.psum(
        jnp.where(stage == s_total - 1, out_buf, jnp.zeros_like(out_buf)),
        pp_axis)
    return out_buf.reshape((n,) + xs[out_slot].shape[1:])


def _axis(mesh: Optional[Mesh], name: str) -> Optional[str]:
    if mesh is not None and name in mesh.axis_names \
            and mesh.shape[name] > 1:
        return name
    return None


def _xspec(mesh, dp, sp, ndim, seq_dim=1):
    dims = [dp] + [None] * (ndim - 1)
    dims[seq_dim] = sp
    return P(*dims)


def _pspecs(params, decoder, mesh, pp, mp):
    out = {}
    for slot, a in params.items():
        hint = dist_spec_for(slot, a.ndim, decoder)
        dims = []
        for d, ax in enumerate(hint):
            ok = (ax == "pp" and pp) or (ax == "mp" and mp)
            ok = ok and a.shape[d] % mesh.shape[ax] == 0
            dims.append(ax if ok else None)
        out[slot] = P(*dims)
    return out


def stack_apply(kind: str, x, enc, bias, params: Dict[str, jnp.ndarray],
                key, *, n_head: int, dropout: float, is_test: bool,
                n_micro: int, mesh: Optional[Mesh],
                recompute: bool = False, flash: bool = False):
    """Apply a stacked encoder ('enc') or decoder ('dec') to x.

    x: [N, T, D]; enc: [N, Ts, D] (decoder only); bias: [N, 1, 1, Tk] or
    None (encoder self / decoder cross key bias); params: stacked arrays
    keyed by ENCODER_SLOTS/DECODER_SLOTS; key: PRNG key (ignored when
    dropout=0 or is_test).

    recompute=True wraps each layer in ``jax.checkpoint``: the backward
    pass rematerializes activations layer by layer instead of saving them
    all, cutting peak memory from O(L*T*D) to O(T*D) + one extra forward —
    the standard long-sequence recipe (and exactly what the reference's
    memory_optimize pass tried to approximate with var reuse).
    """
    decoder = kind == "dec"
    n_layer = params["WQ"].shape[0]
    pp = _axis(mesh, "pp")
    mp = _axis(mesh, "mp")
    sp = _axis(mesh, "sp")
    dp = _axis(mesh, "dp")

    if pp is None:
        # scan path; mp (GSPMD) and sp (mesh-aware ring op) still apply
        attend = (_attend_in_shard_map(n_head, None, flash=flash)
                  if sp is None else _attend_gspmd_ring(n_head, mesh, sp))
        if decoder:
            def layer_fn(p, xx, kk):
                return _decoder_layer(p, xx, enc, bias, kk, attend=attend,
                                      dropout=dropout, is_test=is_test,
                                      mp_axis=None)
        else:
            def layer_fn(p, xx, kk):
                return _encoder_layer(p, xx, bias, kk, attend=attend,
                                      dropout=dropout, is_test=is_test,
                                      mp_axis=None)
        if recompute:
            layer_fn = jax.checkpoint(layer_fn)
        return _scan_layers(layer_fn, params, x, key, n_layer)

    # pp path: one shard_map over the whole mesh; stages hold L/S layers
    s = mesh.shape[pp]
    if n_layer % s != 0:
        raise ValueError(f"n_layer {n_layer} not divisible by pp size {s}")
    mp_size = mesh.shape[mp] if mp else 1
    if n_head % mp_size != 0:
        raise ValueError(f"n_head {n_head} not divisible by mp size {mp_size}")
    if mp_size > 1:
        # The pp layer body psums partial row-parallel outputs over mp, which
        # is only correct when every Megatron-sharded weight dim actually
        # splits mp_size ways; _pspecs degrading a dim to replicated here
        # would silently scale outputs by mp_size.
        table = DECODER_SLOTS if decoder else ENCODER_SLOTS
        for slot, mp_dim in table.items():
            if mp_dim is not None and params[slot].shape[mp_dim] % mp_size:
                raise ValueError(
                    f"param {slot} dim {mp_dim} (= "
                    f"{params[slot].shape[mp_dim]}) not divisible by mp size "
                    f"{mp_size}; d_model and d_inner must be divisible "
                    f"by mp")
    local_heads = n_head // mp_size

    xs = {"x": x}
    if decoder:
        xs["enc"] = enc
    if bias is not None:
        xs["bias"] = bias

    attend = _attend_in_shard_map(local_heads, sp, flash=flash)

    def one_layer(p_i, xx, tree, kk):
        if decoder:
            return _decoder_layer(
                p_i, xx, tree.get("enc"), tree.get("bias"), kk,
                attend=attend, dropout=dropout, is_test=is_test,
                mp_axis=mp)
        return _encoder_layer(
            p_i, xx, tree.get("bias"), kk, attend=attend,
            dropout=dropout, is_test=is_test, mp_axis=mp)

    if recompute:
        one_layer = jax.checkpoint(one_layer)

    def stage_fn(local_params, tree, t):
        # local_params leaves: [L/S, ...] (this stage's layers)
        xx = tree["x"]
        for i in range(n_layer // s):
            p_i = {k: v[i] for k, v in local_params.items()}
            kk = jax.random.fold_in(
                jax.random.fold_in(jax.random.fold_in(
                    key, lax.axis_index(pp)), t), i)
            if dp is not None:
                kk = jax.random.fold_in(kk, lax.axis_index(dp))
            xx = one_layer(p_i, xx, tree, kk)
        return {**tree, "x": xx}

    in_specs = (
        _pspecs(params, decoder, mesh, pp, mp),
        {k: (_xspec(mesh, dp, sp, v.ndim, seq_dim=3) if k == "bias"
             else _xspec(mesh, dp, sp, v.ndim)) for k, v in xs.items()},
    )
    out_spec = _xspec(mesh, dp, sp, x.ndim)
    fn = _shard_map(
        partial(_gpipe_tree_body, stage_fn=stage_fn, pp_axis=pp,
                n_micro=n_micro, out_slot="x"),
        mesh=mesh, in_specs=in_specs, out_specs=out_spec)
    return fn(params, xs)
