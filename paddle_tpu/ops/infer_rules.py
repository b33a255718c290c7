"""Explicit static shape/dtype infer rules (paddle_tpu.analysis pass 1).

Ops without a rule here are abstractly evaluated through ``jax.eval_shape``
over their registered forward impl (analysis/infer.py), which covers the
long tail for free.  A rule earns its place by one of:

 - a *named* diagnostic beating a generic trace error — the matmul-family
   contraction check reports "K mismatch: x[64,32] @ y[16,10]" with the
   operand VAR names instead of a dot_general stack trace;
 - catching what abstract evaluation cannot: the integer-id ops coerce
   their index inputs with ``.astype(int32)``, so a float label/id tensor
   traces fine and silently truncates at runtime — only a static dtype
   rule sees it;
 - skipping a jax trace for the hottest op families (elementwise chains,
   optimizer updates) so whole-program verification stays in the
   sub-50ms budget.

Rule contract (ops/registry.py:register_infer): ``rule(op, ins)`` with
``ins[slot] = [(shape, dtype) | None, ...]``; return ``{slot: [(shape,
dtype) | None]}`` (None = unknown), or raise ``InferMismatch``.
"""

from __future__ import annotations

import math

import numpy as np

from .registry import InferMismatch, register_infer

_INT_DTYPES = ("int8", "int16", "int32", "int64", "uint8", "bool")


def _in(ins, slot, i=0):
    vals = ins.get(slot) or []
    return vals[i] if i < len(vals) and vals[i] is not None else None


def _names(op, slot):
    return ", ".join(repr(n) for n in op.inputs.get(slot, []) if n) or slot


def _require_int(op, ins, slot):
    v = _in(ins, slot)
    if v is not None and v[1] is not None and v[1] not in _INT_DTYPES:
        raise InferMismatch(
            f"{op.type}: input {_names(op, slot)} must be an integer "
            f"index/label tensor, got dtype {v[1]} (the kernel would "
            f"silently truncate it with astype(int32))", code="AN102")
    return v


def _flat2(shape, ncol):
    lead = int(np.prod(shape[:ncol], dtype=np.int64)) if ncol else 1
    rest = int(np.prod(shape[ncol:], dtype=np.int64)) if ncol < len(shape) \
        else 1
    return lead, rest


@register_infer("mul")
def infer_mul(op, ins):
    x, y = _in(ins, "X"), _in(ins, "Y")
    if x is None or y is None:
        return None
    xnc = op.attr("x_num_col_dims", 1)
    ync = op.attr("y_num_col_dims", 1)
    _, k1 = _flat2(x[0], xnc)
    k2, _ = _flat2(y[0], ync)
    if k1 != k2:
        raise InferMismatch(
            f"mul: contraction mismatch — {_names(op, 'X')} {list(x[0])} "
            f"flattened at {xnc} gives K={k1}, but {_names(op, 'Y')} "
            f"{list(y[0])} flattened at {ync} gives K={k2}")
    out = tuple(x[0][:xnc]) + tuple(y[0][ync:])
    return {"Out": [(out, x[1])]}


@register_infer("matmul")
def infer_matmul(op, ins):
    x, y = _in(ins, "X"), _in(ins, "Y")
    if x is None or y is None:
        return None
    xs, ys = list(x[0]), list(y[0])
    if len(xs) == 1:
        xs = [1] + xs
    if len(ys) == 1:
        ys = ys + [1]
    if op.attr("transpose_X", False):
        xs[-1], xs[-2] = xs[-2], xs[-1]
    if op.attr("transpose_Y", False):
        ys[-1], ys[-2] = ys[-2], ys[-1]
    if xs[-1] != ys[-2]:
        raise InferMismatch(
            f"matmul: contraction mismatch — {_names(op, 'X')} "
            f"{list(x[0])} x {_names(op, 'Y')} {list(y[0])} contracts "
            f"{xs[-1]} against {ys[-2]}")
    try:
        batch = tuple(np.broadcast_shapes(tuple(xs[:-2]), tuple(ys[:-2])))
    except ValueError:
        raise InferMismatch(
            f"matmul: batch dims of {_names(op, 'X')} {list(x[0])} and "
            f"{_names(op, 'Y')} {list(y[0])} do not broadcast")
    return {"Out": [(batch + (xs[-2], ys[-1]), x[1])]}


def _infer_elementwise(op, ins):
    x, y = _in(ins, "X"), _in(ins, "Y")
    if x is None:
        return None
    if y is None:
        return {"Out": [x]}
    xs, ys = x[0], y[0]
    axis = op.attr("axis", -1)
    if len(ys) > len(xs):
        # a higher-rank Y still works when plain numpy broadcasting does
        # (scalar-ish operands: [] + [1] -> [1])
        try:
            return {"Out": [(tuple(np.broadcast_shapes(xs, ys)), x[1])]}
        except ValueError:
            raise InferMismatch(
                f"{op.type}: operand {_names(op, 'Y')} {list(ys)} does "
                f"not broadcast against {_names(op, 'X')} {list(xs)}")
    if axis is None or axis == -1:
        axis = len(xs) - len(ys)
    for d, yd in enumerate(ys):
        xd = xs[axis + d] if 0 <= axis + d < len(xs) else None
        if yd != 1 and xd is not None and yd != xd:
            raise InferMismatch(
                f"{op.type}: operand {_names(op, 'Y')} {list(ys)} does "
                f"not broadcast against {_names(op, 'X')} {list(xs)} "
                f"at axis {axis} (dim {yd} vs {xd})")
    return {"Out": [x]}


for _t in ("elementwise_add", "elementwise_sub", "elementwise_mul",
           "elementwise_div", "elementwise_max", "elementwise_min",
           "elementwise_pow"):
    register_infer(_t)(_infer_elementwise)


@register_infer("lookup_table")
def infer_lookup_table(op, ins):
    ids = _require_int(op, ins, "Ids")
    w = _in(ins, "W")
    if ids is None or w is None or len(w[0]) != 2:
        return None
    idshape = tuple(ids[0])
    if len(idshape) >= 2 and idshape[-1] == 1:
        idshape = idshape[:-1]
    return {"Out": [(idshape + (w[0][1],), w[1])]}


@register_infer("cross_entropy")
def infer_cross_entropy(op, ins):
    x = _in(ins, "X")
    if not op.attr("soft_label", False):
        _require_int(op, ins, "Label")
    if x is None:
        return None
    return {"Y": [(tuple(x[0][:-1]) + (1,), "float32"
                   if x[1] in ("float16", "bfloat16") else x[1])]}


@register_infer("softmax_with_cross_entropy")
def infer_softmax_xent(op, ins):
    logits = _in(ins, "Logits")
    if not op.attr("soft_label", False):
        _require_int(op, ins, "Label")
    if logits is None:
        return None
    loss = tuple(logits[0][:-1]) + (1,)
    return {"Softmax": [logits], "Loss": [(loss, logits[1])],
            "Lse": [(loss, "float32")]}


@register_infer("mean")
def infer_mean(op, ins):
    x = _in(ins, "X")
    return {"Out": [((1,), x[1]) if x is not None else None]}


@register_infer("weighted_mean")
def infer_weighted_mean(op, ins):
    x, w = _in(ins, "X"), _in(ins, "Weight")
    if x is not None and w is not None and \
            math.prod(x[0]) != math.prod(w[0]):
        raise InferMismatch(
            f"weighted_mean: {_names(op, 'Weight')} {list(w[0])} is not a "
            f"weight for every value of {_names(op, 'X')} {list(x[0])}")
    return {"Out": [((1,), x[1]) if x is not None else None]}


@register_infer("sum")
def infer_sum(op, ins):
    vals = [v for v in ins.get("X", []) if v is not None]
    if not vals:
        return None
    shapes = {tuple(v[0]) for v in vals}
    if len(shapes) > 1:
        raise InferMismatch(
            f"sum: operands {_names(op, 'X')} disagree on shape: "
            f"{sorted(map(list, shapes))}")
    return {"Out": [vals[0]]}


@register_infer("cast")
def infer_cast(op, ins):
    from ..fluid import core as _core

    x = _in(ins, "X")
    if x is None:
        return None
    dt = str(np.dtype(_core.np_dtype(
        op.attr("out_dtype", op.attr("dtype", "float32")))))
    return {"Out": [(x[0], dt)]}


def _infer_same(op, ins):
    """Out mirrors X — the unary activation/identity family."""
    x = _in(ins, "X")
    out = {}
    for slot in op.outputs:
        out[slot] = [x] * len(op.outputs[slot])
    return out


for _t in ("relu", "sigmoid", "tanh", "softmax", "exp", "log", "sqrt",
           "square", "abs", "relu6", "leaky_relu", "elu", "softplus",
           "softsign", "gelu", "scale", "clip", "sign", "dropout",
           "fill_any_like", "assign", "floor", "ceil", "round",
           "softshrink", "hard_sigmoid", "swish", "pow", "brelu",
           "layer_norm_noop"):
    register_infer(_t)(_infer_same)


@register_infer("reshape", "reshape2")
def infer_reshape(op, ins):
    x = _in(ins, "X")
    if x is None:
        return None
    want = list(op.attr("shape") or ())
    if not want:
        return None
    n = int(np.prod(x[0], dtype=np.int64))
    fixed = int(np.prod([d for d in want if d > 0], dtype=np.int64))
    if 0 in want:
        want = [x[0][i] if d == 0 and i < len(x[0]) else d
                for i, d in enumerate(want)]
        fixed = int(np.prod([d for d in want if d > 0], dtype=np.int64))
    if -1 in want:
        if fixed == 0 or n % fixed:
            raise InferMismatch(
                f"reshape: {_names(op, 'X')} {list(x[0])} ({n} elements) "
                f"does not fit target shape {want}")
        want = [n // fixed if d == -1 else d for d in want]
    elif fixed != n:
        raise InferMismatch(
            f"reshape: {_names(op, 'X')} {list(x[0])} has {n} elements, "
            f"target shape {want} has {fixed}")
    out = {"Out": [(tuple(int(d) for d in want), x[1])]}
    if "XShape" in op.outputs:
        out["XShape"] = [((0,) + tuple(x[0]), x[1])]
    return out


@register_infer("concat")
def infer_concat(op, ins):
    vals = [v for v in ins.get("X", []) if v is not None]
    if len(vals) != len(ins.get("X", [])) or not vals:
        return None
    axis = op.attr("axis", 0)
    base = list(vals[0][0])
    axis = axis if axis >= 0 else axis + len(base)
    total = 0
    for v in vals:
        s = list(v[0])
        if len(s) != len(base) or any(
                i != axis and s[i] != base[i] for i in range(len(base))):
            raise InferMismatch(
                f"concat: operands {_names(op, 'X')} disagree off axis "
                f"{axis}: {[list(v[0]) for v in vals]}")
        total += s[axis]
    base[axis] = total
    return {"Out": [(tuple(base), vals[0][1])]}


@register_infer("fill_constant")
def infer_fill_constant(op, ins):
    from ..fluid import core as _core

    shape = tuple(int(d) for d in (op.attr("shape") or ()))
    dt = str(np.dtype(_core.np_dtype(op.attr("dtype", "float32"))))
    return {"Out": [(shape, dt)]}


def _infer_random(op, ins):
    """Shape-attr random initializers — the bulk of every startup
    program, so a rule here keeps startup verification trivially cheap."""
    from ..fluid import core as _core

    shape = tuple(int(d) for d in (op.attr("shape") or ()))
    if not shape or any(d < 0 for d in shape):
        return None
    dt = str(np.dtype(_core.np_dtype(op.attr("dtype", "float32"))))
    return {"Out": [(shape, dt)]}


for _t in ("uniform_random", "gaussian_random",
           "truncated_gaussian_random"):
    register_infer(_t)(_infer_random)


@register_infer("ring_attention")
def infer_ring_attention(op, ins):
    """Out mirrors Q — an explicit rule so the verifier never abstractly
    evaluates the Pallas flash / shard_map lowerings (fast, and priced
    identically whichever kernel the env gate picks at dispatch time)."""
    q = _in(ins, "Q")
    return {"Out": [q]}


@register_infer("kv_cache_update")
def infer_kv_cache_update(op, ins):
    """Decode-step KV-cache scatter (ISSUE 15): Out mirrors Cache, and the
    static contract — window fits the cache, index vectors are integer
    and agree with the window's row count — is exactly what abstract
    evaluation cannot name (a bad Pos dtype would silently truncate, a
    too-long window would silently clamp)."""
    cache, new = _in(ins, "Cache"), _in(ins, "New")
    slots = _require_int(op, ins, "Slots")
    pos = _require_int(op, ins, "Pos")
    if cache is None:
        return None
    if new is not None:
        if len(new[0]) != len(cache[0]):
            raise InferMismatch(
                f"kv_cache_update: window {_names(op, 'New')} "
                f"{list(new[0])} must match cache {_names(op, 'Cache')} "
                f"{list(cache[0])} rank (rows, window, feature...)")
        if new[0][1] > cache[0][1]:
            raise InferMismatch(
                f"kv_cache_update: window length {new[0][1]} exceeds "
                f"cache max_len {cache[0][1]} "
                f"({_names(op, 'New')} vs {_names(op, 'Cache')})")
        if tuple(new[0][2:]) != tuple(cache[0][2:]):
            raise InferMismatch(
                f"kv_cache_update: feature dims {list(new[0][2:])} of "
                f"{_names(op, 'New')} do not match cache feature dims "
                f"{list(cache[0][2:])}")
        for slot_name, v in (("Slots", slots), ("Pos", pos)):
            if v is not None and int(np.prod(v[0], dtype=np.int64)) \
                    != new[0][0]:
                raise InferMismatch(
                    f"kv_cache_update: {slot_name} {_names(op, slot_name)} "
                    f"{list(v[0])} must carry one index per window row "
                    f"({new[0][0]})")
    return {"Out": [cache]}


@register_infer("kv_cache_scatter")
def infer_kv_cache_scatter(op, ins):
    """Per-token KV scatter (ISSUE 20): Out mirrors Cache; New must carry
    the cache's feature dims, and Rows/Offs one integer index per written
    token (a float index would silently truncate, a count mismatch would
    silently drop or duplicate writes)."""
    cache, new = _in(ins, "Cache"), _in(ins, "New")
    rows = _require_int(op, ins, "Rows")
    offs = _require_int(op, ins, "Offs")
    if cache is None:
        return None
    if new is not None:
        if tuple(new[0][1:]) != tuple(cache[0][2:]):
            raise InferMismatch(
                f"kv_cache_scatter: token rows {_names(op, 'New')} "
                f"{list(new[0])} must carry the cache feature dims "
                f"{list(cache[0][2:])} ({_names(op, 'Cache')})")
        for slot_name, v in (("Rows", rows), ("Offs", offs)):
            if v is not None and int(np.prod(v[0], dtype=np.int64)) \
                    != new[0][0]:
                raise InferMismatch(
                    f"kv_cache_scatter: {slot_name} "
                    f"{_names(op, slot_name)} {list(v[0])} must carry one "
                    f"index per written token ({new[0][0]})")
    return {"Out": [cache]}


@register_infer("spec_accept")
def infer_spec_accept(op, ins):
    """Greedy speculative acceptance (ISSUE 20): Tokens is [S, k+1]
    int64, NumAccept [S] int64; the draft must be exactly one token
    narrower than the scored window (k drafted, k + 1 verified) and the
    mask one flag per slot — off-by-one here would silently accept the
    wrong prefix."""
    logits = _in(ins, "Logits")
    draft = _require_int(op, ins, "Draft")
    mask = _in(ins, "Mask")
    if logits is None:
        return None
    if len(logits[0]) != 3:
        raise InferMismatch(
            f"spec_accept: logits {_names(op, 'Logits')} "
            f"{list(logits[0])} must be [slots, k+1, vocab]")
    if draft is not None:
        if len(draft[0]) != 2 or draft[0][0] != logits[0][0] \
                or draft[0][1] != logits[0][1] - 1:
            raise InferMismatch(
                f"spec_accept: draft {_names(op, 'Draft')} "
                f"{list(draft[0])} must be [slots, k] against verify "
                f"logits {list(logits[0])} (k + 1 scored positions)")
    if mask is not None and int(np.prod(mask[0], dtype=np.int64)) \
            != logits[0][0]:
        raise InferMismatch(
            f"spec_accept: mask {_names(op, 'Mask')} {list(mask[0])} "
            f"must carry one flag per slot ({logits[0][0]})")
    return {"Tokens": [(tuple(logits[0][:-1]), "int64")],
            "NumAccept": [((logits[0][0],), "int64")]}


@register_infer("paged_attention")
def infer_paged_attention(op, ins):
    """Paged decode attention (ISSUE 19): Out mirrors Q — an explicit
    rule (like ring_attention's) so the verifier never abstractly
    evaluates the Pallas paged kernel, plus the static page-table
    contract abstract evaluation cannot name: an integer table, one row
    per query slot, and ``pages_per_slot * page_size`` exactly covering
    the bias's key length (a mismatch would silently attend to a
    truncated or over-gathered window)."""
    q = _in(ins, "Q")
    ck = _in(ins, "CacheK")
    bias = _in(ins, "Bias")
    pt = _require_int(op, ins, "PageTable")
    if ck is not None and len(ck[0]) != 3:
        raise InferMismatch(
            f"paged_attention: cache {_names(op, 'CacheK')} {list(ck[0])} "
            f"must be [num_pages + 1, page_size, d_model]")
    if q is not None and pt is not None and len(pt[0]) == 2 \
            and pt[0][0] != q[0][0]:
        raise InferMismatch(
            f"paged_attention: page table {_names(op, 'PageTable')} "
            f"{list(pt[0])} must carry one row per query slot "
            f"({q[0][0]})")
    if pt is not None and ck is not None and bias is not None \
            and len(pt[0]) == 2 and len(bias[0]) == 3 \
            and pt[0][1] * ck[0][1] != bias[0][2]:
        raise InferMismatch(
            f"paged_attention: gathered length {pt[0][1]} pages x "
            f"{ck[0][1]} tokens/page != bias key length {bias[0][2]} "
            f"({_names(op, 'PageTable')} vs {_names(op, 'Bias')})")
    if q is not None and ck is not None and q[0][-1] != ck[0][-1]:
        raise InferMismatch(
            f"paged_attention: feature dim {q[0][-1]} of {_names(op, 'Q')} "
            f"does not match cache feature dim {ck[0][-1]}")
    return {"Out": [q]}


@register_infer("token_select")
def infer_token_select(op, ins):
    """Greedy token choice: Out is [S] int64 off [S, V] logits; an
    inactive-slot mask must be one value per slot."""
    logits = _in(ins, "Logits")
    mask = _in(ins, "Mask")
    if logits is None:
        return None
    if len(logits[0]) < 2:
        raise InferMismatch(
            f"token_select: logits {_names(op, 'Logits')} "
            f"{list(logits[0])} must be [slots, vocab]")
    if mask is not None and int(np.prod(mask[0], dtype=np.int64)) \
            != logits[0][0]:
        raise InferMismatch(
            f"token_select: mask {_names(op, 'Mask')} {list(mask[0])} "
            f"must carry one flag per slot ({logits[0][0]})")
    return {"Out": [(tuple(logits[0][:-1]), "int64")]}


def _infer_param_update(op, ins):
    """Optimizer-family updates: each '<X>Out' output mirrors input slot
    '<X>' (ParamOut <- Param, MomentOut <- Moment, ...)."""
    out = {}
    for slot, names in op.outputs.items():
        src = slot[:-3] if slot.endswith("Out") else slot
        out[slot] = [_in(ins, src, i) for i in range(len(names))]
    return out


for _t in ("sgd", "momentum", "adam", "adamax", "adagrad", "rmsprop",
           "decayed_adagrad", "ftrl", "lars_momentum"):
    register_infer(_t)(_infer_param_update)


# -- the decoder path (ops/decoder_ops.py) ---------------------------------

@register_infer("rms_norm")
def infer_rms_norm(op, ins):
    x, scale = _in(ins, "X"), _in(ins, "Scale")
    if x is not None and scale is not None \
            and tuple(scale[0]) != (x[0][-1],):
        raise InferMismatch(
            f"rms_norm: scale {_names(op, 'Scale')} {list(scale[0])} must "
            f"be [{x[0][-1]}], the last dim of {_names(op, 'X')} "
            f"{list(x[0])}")
    groups = int(op.attr("groups", 1))
    if groups < 1 or (x is not None and x[0][-1] % groups):
        raise InferMismatch(
            f"rms_norm: the last dim of {_names(op, 'X')} {list(x[0])} "
            f"does not divide into {groups} groups")
    return {"Y": [x]}


@register_infer("rotary_embedding")
def infer_rotary_embedding(op, ins):
    x = _in(ins, "X")
    if x is None:
        return {"Out": [x]}
    start, dims = int(op.attr("start", 0)), int(op.attr("dims", 0))
    head = x[0][-1] if len(x[0]) == 4 else 0
    part = dims or head - start
    if len(x[0]) != 4 or start < 0 or part < 2 or part % 2 \
            or start + part > head:
        raise InferMismatch(
            f"rotary_embedding: {_names(op, 'X')} {list(x[0])} must be "
            f"[batch, positions, heads, an even head width]"
            + (f", of which it turns the {part} columns from {start} on"
               if start or dims else ""))
    if int(op.attr("period", 0)) < 0:
        raise InferMismatch(
            f"rotary_embedding: period {op.attr('period')} is negative (0: "
            f"one sequence along axis 1)")
    table = op.attr("inv_freq", None)
    if table and len(table) != part // 2:
        raise InferMismatch(
            f"rotary_embedding: inv_freq has {len(table)} frequencies for "
            f"the {part // 2} pairs of the {part} columns it turns")
    return {"Out": [x]}


@register_infer("sparse_indexer")
def infer_sparse_indexer(op, ins):
    """Sel is [B, T, T] int8: an explicit rule so the verifier never
    abstractly evaluates T/512 tiles of bisection."""
    x = _in(ins, "X")
    wq, wk, ww = _in(ins, "WQ"), _in(ins, "WK"), _in(ins, "WW")
    heads = int(op.attr("num_heads", 0))
    if x is None:
        return None
    if len(x[0]) != 3:
        raise InferMismatch(
            f"sparse_indexer: {_names(op, 'X')} {list(x[0])} must be "
            f"[batch, positions, hidden]")
    if wq is not None and wk is not None and ww is not None and (
            wq[0][1] != heads * wk[0][1] or ww[0][1] != heads
            or {wq[0][0], wk[0][0], ww[0][0]} != {x[0][-1]}):
        raise InferMismatch(
            f"sparse_indexer: weights {list(wq[0])}, {list(wk[0])}, "
            f"{list(ww[0])} do not make {heads} query heads, one key head "
            f"of the same width and {heads} head weights over hidden "
            f"{x[0][-1]}")
    return {"Sel": [((x[0][0], x[0][1], x[0][1]), "int8")]}


@register_infer("sparse_attention")
def infer_sparse_attention(op, ins):
    """Out mirrors Q at V's width (never evaluated abstractly, as
    ring_attention's); the grouped heads and the selection's shape are
    checked here, where the vars have names."""
    q, k, v, sel = (_in(ins, s) for s in ("Q", "K", "V", "Sel"))
    lse = None if q is None else (tuple(q[0][:3]) + (1,), "float32")
    if q is None or k is None:
        return {"Out": [q], "Lse": [lse]}
    if len(q[0]) != 4 or len(k[0]) != 4 or (
            v is not None and (len(v[0]) != 4
                               or tuple(v[0][:3]) != tuple(k[0][:3]))):
        raise InferMismatch(
            f"sparse_attention: {_names(op, 'Q')} {list(q[0])}, "
            f"{_names(op, 'K')} {list(k[0])} and V must be [B, H, T, D] "
            f"with K and V alike but for their width")
    if q[0][1] % k[0][1] or q[0][2:] != k[0][2:]:
        raise InferMismatch(
            f"sparse_attention: {q[0][1]} query heads of {_names(op, 'Q')} "
            f"{list(q[0])} do not group over the {k[0][1]} key-value heads "
            f"of {_names(op, 'K')} {list(k[0])} at equal length and width")
    if sel is not None and (tuple(sel[0][1:]) != (q[0][2], q[0][2])
                            or sel[1] not in _INT_DTYPES):
        raise InferMismatch(
            f"sparse_attention: selection {_names(op, 'Sel')} "
            f"{list(sel[0])} {sel[1]} must be an integer "
            f"[B, {q[0][2]}, {q[0][2]}] mask")
    if int(op.attr("window", 0)) < 0:
        raise InferMismatch(
            f"sparse_attention: window {op.attr('window')} is negative "
            f"(0: none; else the last `window` keys s <= t)")
    tokens, block = int(op.attr("copy_tokens", 0)), int(op.attr("block", 0))
    if (tokens or block) and (
            block < 1 or tokens % block or q[0][2] != 2 * tokens
            or sel is not None or int(op.attr("window", 0))):
        raise InferMismatch(
            f"sparse_attention: the block rule over two copies of {tokens} "
            f"tokens in blocks of {block} takes {_names(op, 'Q')} "
            f"{list(q[0])} with {2 * tokens} positions in whole blocks, and "
            f"neither a selection nor a window")
    out = q if v is None else (tuple(q[0][:3]) + (v[0][3],), q[1])
    return {"Out": [out], "Lse": [lse]}


@register_infer("moe_experts")
def infer_moe_experts(op, ins):
    x, r, w1 = _in(ins, "X"), _in(ins, "RouterW"), _in(ins, "W1")
    held, routed = int(op.attr("experts_held", 0)), int(
        op.attr("num_routed", 0))
    offset, top_k = int(op.attr("expert_offset", 0)), int(op.attr("top_k", 0))
    if not 0 < top_k <= routed or offset < 0 or offset + held > routed:
        raise InferMismatch(
            f"moe_experts: experts [{offset}, {offset + held}) and top_k "
            f"{top_k} do not fit a router over {routed} experts")
    if r is not None and w1 is not None and (r[0][-1] != routed
                                             or w1[0][0] != held):
        raise InferMismatch(
            f"moe_experts: router {_names(op, 'RouterW')} {list(r[0])} and "
            f"expert weights {_names(op, 'W1')} {list(w1[0])} must be "
            f"{routed} wide and {held} experts")
    if op.attr("score", "softmax") not in ("softmax", "sigmoid"):
        raise InferMismatch(
            f"moe_experts: score {op.attr('score')!r} is neither 'softmax' "
            f"nor 'sigmoid'")
    bias = _in(ins, "Bias")
    if bias is None:
        return {"Out": [x]}
    if tuple(bias[0]) != (routed,):
        raise InferMismatch(
            f"moe_experts: selection bias {_names(op, 'Bias')} "
            f"{list(bias[0])} must be [{routed}], one per routed expert")
    return {"Out": [x], "Counts": [((routed,), "int32")]}


@register_infer("moe_bias_update")
def infer_moe_bias_update(op, ins):
    bias, counts = _in(ins, "Bias"), _in(ins, "Counts")
    if bias is not None and counts is not None and (
            tuple(bias[0]) != tuple(counts[0])
            or counts[1] not in _INT_DTYPES):
        raise InferMismatch(
            f"moe_bias_update: bias {_names(op, 'Bias')} {list(bias[0])} "
            f"and counts {_names(op, 'Counts')} {list(counts[0])} "
            f"{counts[1]} must be one float and one integer per routed "
            f"expert")
    return {"BiasOut": [bias]}


@register_infer("short_conv")
def infer_short_conv(op, ins):
    x, w = _in(ins, "X"), _in(ins, "Filter")
    if x is None:
        return None
    bias = _in(ins, "Bias")
    if bias is not None and (bool(op.attr("gated", True))
                             or tuple(bias[0]) != (x[0][-1],)):
        raise InferMismatch(
            f"short_conv: bias {_names(op, 'Bias')} {list(bias[0])} must be "
            f"[{x[0][-1]}], one a channel of the filter's input alone "
            f"(the gated form has none)")
    if not bool(op.attr("gated", True)):
        if len(x[0]) != 3 or (w is not None and (
                len(w[0]) != 2 or w[0][0] != x[0][-1])):
            raise InferMismatch(
                f"short_conv: {_names(op, 'X')} {list(x[0])} must be "
                f"[batch, positions, channels] (the filter's input alone: "
                f"gated is off) and filter {_names(op, 'Filter')} "
                f"{list(w[0]) if w is not None else '?'} [channels, taps]")
        return {"Out": [x]}
    if len(x[0]) != 3 or x[0][-1] % 3 or (
            w is not None and (len(w[0]) != 2
                               or 3 * w[0][0] != x[0][-1])):
        raise InferMismatch(
            f"short_conv: {_names(op, 'X')} {list(x[0])} must be [batch, "
            f"positions, 3 * channels] (both gates and the filter's input "
            f"side by side) and filter {_names(op, 'Filter')} "
            f"{list(w[0]) if w is not None else '?'} [channels, taps]")
    return {"Out": [(tuple(x[0][:2]) + (x[0][-1] // 3,), x[1])]}


@register_infer("gated_delta_rule")
def infer_gated_delta_rule(op, ins):
    q, k, v = _in(ins, "Q"), _in(ins, "K"), _in(ins, "V")
    g, beta = _in(ins, "G"), _in(ins, "Beta")
    if q is None or v is None:
        return {"Out": [v]}
    if len(q[0]) != 4 or len(v[0]) != 4 or (
            k is not None and tuple(k[0]) != tuple(q[0])) \
            or tuple(q[0][:2]) != tuple(v[0][:2]) or v[0][2] % q[0][2]:
        raise InferMismatch(
            f"gated_delta_rule: {_names(op, 'Q')} {list(q[0])} and K must "
            f"be alike, [B, T, key heads, dk], and {_names(op, 'V')} "
            f"{list(v[0])} [B, T, value heads, dv] over the same tokens "
            f"with the value heads a multiple of the key heads")
    by_head = tuple(v[0][:3])
    by_channel = by_head + (q[0][3],)
    for name, gate, takes in (("G", g, (by_head, by_channel)),
                              ("Beta", beta, (by_head,))):
        if gate is not None and tuple(gate[0]) not in takes:
            raise InferMismatch(
                f"gated_delta_rule: {_names(op, name)} {list(gate[0])} "
                f"must be {list(by_head)}, one number a token and value "
                f"head" + (f", or {list(by_channel)}, one a key channel"
                           if by_channel in takes else ""))
    if int(op.attr("chunk", 64)) < 1:
        raise InferMismatch(
            f"gated_delta_rule: chunk {op.attr('chunk')} is not positive")
    return {"Out": [v]}


@register_infer("ssd_scan")
def infer_ssd_scan(op, ins):
    u, delta = _in(ins, "U"), _in(ins, "Delta")
    if u is None:
        return None
    groups = int(op.attr("groups", 1))
    if len(u[0]) != 4 or groups < 1 or u[0][2] % groups or (
            delta is not None and tuple(delta[0]) != tuple(u[0][:3])):
        raise InferMismatch(
            f"ssd_scan: {_names(op, 'U')} {list(u[0])} must be [B, T, "
            f"heads, head width] with the heads a multiple of the {groups} "
            f"groups, and {_names(op, 'Delta')} "
            f"{list(delta[0]) if delta is not None else '?'} one step a "
            f"token and head")
    for name in ("A", "D"):
        one = _in(ins, name)
        if one is not None and tuple(one[0]) != (u[0][2],):
            raise InferMismatch(
                f"ssd_scan: {_names(op, name)} {list(one[0])} must be "
                f"[{u[0][2]}], one number a head")
    b, c = _in(ins, "B"), _in(ins, "C")
    for name, one in (("B", b), ("C", c)):
        if one is not None and (len(one[0]) != 3
                                or tuple(one[0][:2]) != tuple(u[0][:2])
                                or one[0][2] % groups
                                or (b is not None and one[0] != b[0])):
            raise InferMismatch(
                f"ssd_scan: {_names(op, name)} {list(one[0])} must be "
                f"[B, T, groups * state] over U's tokens, {groups} groups, "
                f"B and C alike")
    if int(op.attr("chunk", 128)) < 1:
        raise InferMismatch(
            f"ssd_scan: chunk {op.attr('chunk')} is not positive")
    return {"Out": [u]}
