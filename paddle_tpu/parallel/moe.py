"""Mixture-of-experts with expert parallelism over an "ep" mesh axis.

A capability beyond the reference (SURVEY.md §2.6: MoE/EP "Absent" — its
nearest analogue is the pserver-sharded distributed lookup table,
ref distribute_transpiler.py:379-382).  Here routing is the GShard/Switch
einsum-dispatch formulation: a differentiable dense dispatch/combine pair of
[N, E, C] tensors instead of data-dependent gather/scatter, so the whole
layer stays a static-shape XLA program.  Under GSPMD with the expert
dimension of the weights sharded on "ep", the dispatch einsum lowers to the
all-to-all over ICI that a hand-written MPI implementation would issue —
no manual collectives needed.

Dropped-token semantics: tokens beyond an expert's capacity contribute zero
to the layer output (callers add a residual connection, as all MoE
transformer blocks do).
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax


def moe_capacity(n_tokens: int, num_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    return max(1, int(math.ceil(n_tokens * top_k / num_experts
                                * capacity_factor)))


def top_k_gating(x, gate_w, top_k: int, capacity_factor: float
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Compute (combine [N,E,C], dispatch [N,E,C], aux_loss scalar).

    x: [N, D] tokens; gate_w: [D, E].  Routing follows Switch/GShard:
    softmax gate, top-k experts per token, per-expert capacity with
    first-come-first-served overflow dropping, gate values renormalized
    over the chosen k.  aux_loss is the Switch load-balancing loss
    E * sum_e(frac_tokens_e * mean_prob_e), which is 1.0 at perfect
    balance.
    """
    n, _ = x.shape
    e = gate_w.shape[-1]
    cap = moe_capacity(n, e, top_k, capacity_factor)
    # gate math in fp32: tiny logit differences decide routing, and bf16
    # softmax would make single- vs multi-chip routing diverge
    logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # [N, E]
    gate_vals, gate_idx = lax.top_k(probs, top_k)  # [N, k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    combine = jnp.zeros((n, e, cap), jnp.float32)
    counts = jnp.zeros((e,), jnp.float32)
    for j in range(top_k):
        oh = jax.nn.one_hot(gate_idx[:, j], e, dtype=jnp.float32)  # [N, E]
        # position this token would take in each expert's buffer
        pos = counts[None, :] + jnp.cumsum(oh, axis=0) - oh  # [N, E]
        keep = oh * (pos < cap)  # drop overflow
        counts = counts + jnp.sum(keep, axis=0)
        slot = jnp.sum(pos * keep, axis=-1).astype(jnp.int32)  # [N]
        slot_oh = jax.nn.one_hot(slot, cap, dtype=jnp.float32)  # [N, C]
        combine = combine + (gate_vals[:, j, None, None]
                             * keep[:, :, None] * slot_oh[:, None, :])
    dispatch = (combine > 0).astype(jnp.float32)

    frac_routed = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], e,
                                          dtype=jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux_loss = e * jnp.sum(frac_routed * mean_prob)
    return combine, dispatch, aux_loss


def moe_ffn(x, gate_w, w1, b1, w2, b2, top_k: int = 2,
            capacity_factor: float = 1.25, activation: str = "relu"
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert feed-forward over routed tokens.

    x: [..., D]; gate_w: [D, E]; w1: [E, D, H]; b1: [E, H]; w2: [E, H, D];
    b2: [E, D].  Returns (y [..., D], aux_loss scalar).  All expert math
    happens at [E, C, ·] — with w1/w2 sharded on the "ep" axis GSPMD keeps
    each expert's tokens and FLOPs on its own devices.
    """
    orig_shape = x.shape
    d = orig_shape[-1]
    xt = x.reshape((-1, d))
    combine, dispatch, aux = top_k_gating(xt, gate_w, top_k, capacity_factor)
    dtype = x.dtype
    from .pipeline import _apply_act

    expert_in = jnp.einsum("nec,nd->ecd", dispatch.astype(dtype), xt)
    h = _apply_act(jnp.einsum("ecd,edh->ech", expert_in, w1)
                   + b1[:, None, :], activation)
    expert_out = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]
    y = jnp.einsum("nec,ecd->nd", combine.astype(dtype), expert_out)
    return y.reshape(orig_shape), aux.astype(dtype)


def route_top_k(x, router_w, top_k: int, norm_topk: bool = True,
                score: str = "softmax", bias=None, norm_eps: float = 0.0,
                scale: float = 1.0):
    """(weights [N, k] float32, experts [N, k] int32) of the published
    router: scores over ALL ``router_w.shape[-1]`` routed experts in
    float32 (at the highest matmul precision: a logit's last bits decide
    the choice), the top k, renormalized over the chosen when
    ``norm_topk`` (``/ (sum + norm_eps)``), times ``scale``.  ``score`` is
    ``softmax`` or ``sigmoid`` (each expert's score its own).  ``bias``
    ([routed], no gradient) is added to the scores for the CHOICE only: it
    says which k experts a token takes, and their weights are made from the
    scores without it."""
    logits = jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    if score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"route_top_k: score {score!r} is neither "
                         "'softmax' nor 'sigmoid'")
    _, idx = lax.top_k(lax.stop_gradient(
        scores if bias is None else scores + bias.astype(jnp.float32)),
        top_k)
    # the chosen scores read by a one-hot select, summed over the columns
    # (one of them is the score, the others exact zeros): the same values
    # as ``lax.top_k``'s own, and a transpose that is a select summed over
    # the k choices where a gather's would be a scatter into [N, R]
    chosen = idx[..., None] == jnp.arange(scores.shape[-1], dtype=idx.dtype)
    vals = jnp.sum(jnp.where(chosen, scores[..., None, :], 0), axis=-1)
    if norm_topk:
        total = jnp.sum(vals, axis=-1, keepdims=True)
        vals = vals / ((total + jnp.float32(norm_eps)) if norm_eps
                       else total)
    if scale != 1.0:
        vals = vals * jnp.float32(scale)
    return vals, idx.astype(jnp.int32)


def assignment_counts(idx, routed: int):
    """[routed] int32: how many of the assignments ``idx`` ([N, k], over all
    ``routed`` experts, held here or not) each expert got."""
    i32 = jnp.int32
    return jnp.sum((idx.reshape(-1)[:, None]
                    == jnp.arange(routed, dtype=i32)).astype(i32),
                   axis=0, dtype=i32)


def balance_bias(bias, counts, coeff: float):
    """The selection bias after a step in which the routed experts got
    ``counts`` assignments: ``b_e += coeff * sign(mean(n) - n_e)``, up for
    an expert that got fewer than the mean, down for one that got more.  A
    rule, not a gradient (auxiliary-loss-free balancing)."""
    n = counts.astype(jnp.float32)
    return bias + jnp.float32(coeff) * jnp.sign(jnp.mean(n) - n)


def routed_experts(x, router_w, w1, w3, w2, top_k: int,
                   expert_offset: int = 0, norm_topk: bool = True,
                   score: str = "softmax", bias=None, norm_eps: float = 0.0,
                   scale: float = 1.0, with_counts: bool = False):
    """The share of a routed expert layer that the experts held here give;
    ``with_counts``: a pair of it and ``assignment_counts`` of the step
    (``score``, ``bias``, ``norm_eps``, ``scale``: ``route_top_k``).

    x: [..., D]; router_w: [D, R] over all R routed experts; w1, w3:
    [E, D, F] and w2: [E, F, D], the E experts ``[expert_offset,
    expert_offset + E)`` with SiLU-gated feed-forwards and no bias.  Every
    token routes over all R; an assignment to an expert held here is
    computed, one to an absent expert is left out (its chip adds it in a
    deployment; nothing stands in for it here).  There is no capacity and
    nothing is dropped: the ``N * top_k`` assignments are sorted by expert
    (absent ones last, as zero rows) and multiplied as grouped products
    (``grouped_product``), so a step in which every token chose held experts
    only is as right as any other.

    The routing plan (the router's weights and choices, the sort by expert
    and its inverse, the group sizes) is made once and kept for the
    backward, with the layer's inputs and nothing else.  The backward is
    written by hand (``_share``): it makes the sorted rows and the two
    hidden products again (a gather, two products) and NOT the last
    product, whose one reader there was the gate's cotangent (that is
    ``<dy @ w2^T, h>`` over a row, and the backward has both).  Rows move
    by gathers in both passes and nothing is scattered: two ``[N * top_k,
    D]`` gathers forward, three backward; the combine's cotangent goes out
    to the sorted rows from ``[N, D]`` and meets the gate on the hidden
    side, never as ``[N, top_k, D]``.  A layer and step: 8 products and 3
    weights' gradients.
    """
    shape = x.shape
    e = w1.shape[0]
    xt = x.reshape((-1, shape[-1]))
    n = xt.shape[0]

    # the routing plan, made ONCE, outside ``_share``, which takes it as
    # arguments and keeps it: the backward sorts and counts nothing again
    vals, idx = route_top_k(xt, router_w, top_k, norm_topk, score, bias,
                            norm_eps, scale)
    local = idx - jnp.int32(expert_offset)
    held = (local >= 0) & (local < e)
    group = jnp.where(held, local, e).reshape(-1)      # absent: last
    # where each assignment lands once sorted by expert: its group's
    # first row plus how many earlier assignments chose the same group
    # (a cumulative count; no scatter).  int32 throughout: the package
    # runs jax in x64 mode, where a sum of int32 is int64, which the
    # TPU's grouped product refuses
    i32 = jnp.int32
    chose = (group[:, None] == jnp.arange(e + 1, dtype=i32)
             ).astype(i32)                             # [N*k, E+1]
    counts = jnp.sum(chose, axis=0, dtype=i32)
    first = jnp.cumsum(counts, dtype=i32) - counts
    # ``back`` (assignment -> sorted row) and ``order`` (sorted row ->
    # assignment) are each other's inverse: either is a gather's index and
    # the other the index of that gather's transpose
    back = jnp.sum(chose * (first[None, :] - 1
                            + jnp.cumsum(chose, axis=0, dtype=i32)),
                   axis=1, dtype=i32)
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    live = (jnp.arange(order.shape[0], dtype=i32) < first[e])[:, None]
    # DEBT (ROADMAP S11, PERF.md section 7): the absent experts'
    # assignments ride along as zero rows at the end of the LAST held
    # group, so all N * top_k rows are gathered, multiplied and gathered
    # back, forward and backward: 7 of every 8 where an eighth of the
    # experts is held and the router is even (Keye's 16 of 128).  What
    # that costs is the products' and the gathers' time over rows (no
    # scatter is left): 8 products, 3 weights' gradients and 5 row gathers
    # a layer and step.  THE RECORD of what the padding costs is the step
    # gauges published below: ``ops.moe.live_rows`` of ``ops.moe.rows``
    # a layer and step is the share of what is walked that is work (the
    # benchmark's ``moe_live_rows_pct``), read late and never waited for.
    # What dropping it can buy in the resident cells is
    # what PR 30 read with XLA's ``ragged_dot`` (a call of which took 2.0
    # ms at 8,192 and at 65,536 live rows; the Pallas kernels that stand
    # since PR 37 take 1.2-1.5 ms over all rows and have not been read
    # with fewer): 414.3 -> 394.6-405.6 ms a step with the cell's spread
    # lost.  Without this line (sizes = counts[:e]) the products skip the
    # row tiles past the last group and a step's time follows the router,
    # which drifts toward the experts held as it trains without the absent
    # ones; nothing else depends on it.
    sizes = counts[:e].at[e - 1].add(counts[e])
    _publish_load(first[e], n * top_k, jnp.max(counts[:e]))
    gate = jnp.where(held, vals, 0.0)
    # the layer's ONE choice of kernels or XLA's grouped product, part of
    # the plan: the tables that tell the kernels' grid steps row tiles and
    # groups, for all eleven calls of the layer, or None
    tables = None
    if product_path(x, w1, w2, top_k) == "pallas":
        from ..ops import pallas_grouped

        tables = pallas_grouped.plan(sizes, n * top_k)

    y = _share(top_k, xt, gate, w1, w3, w2,
               (held, sizes, order, back, live, tables))
    y = y.astype(x.dtype).reshape(shape)
    if with_counts:
        return y, assignment_counts(idx, router_w.shape[-1])
    return y


# XLA's grouped product on the TPU leaves the rows outside every group
# UNWRITTEN, in its results and in the cotangents it hands back (NaN
# gradients on the chip; the CPU zero-fills them).  So what a product
# returns for a row that holds no held assignment is SELECTED away before
# anything reads it, never multiplied by zero, in both passes of ``_share``:
# the two hidden products and the cotangent of the hidden rows by ``live``,
# the last product and the cotangent of the sorted rows where their rows are
# gathered back, by ``held``.  Each select sits in a fusion that reads the
# rows anyway.  The sorted rows themselves need none: a row without a held
# assignment is some token's row, read by products whose results are
# selected away and met by exact zeros in the weights' gradients.  Right
# whatever ``sizes`` covers.
def _sorted_and_hidden(top_k, xt, w1, w3, w2, plan, which):
    """What both passes of ``_share`` make first: the tokens' rows sorted by
    expert, ``xs`` [rows, D] in AMP's type; the two hidden products of
    them, float32 [rows, F], selected by ``live`` (``_gated`` of the two is
    the experts' hidden rows); and the three weights in AMP's type."""
    from ..fluid import amp

    _, sizes, order, _, live, tables = plan
    low, *weights, _ = amp.cast_operands(xt, w1, w3, w2)
    xs = _rows_out(low, order, top_k, which)
    a, b = (jnp.where(live, grouped_product(xs, w, sizes, tables), 0)
            .astype(jnp.float32) for w in weights[:2])
    return xs, a, b, weights


def _gated(a, b):
    return jax.nn.silu(a) * b


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _share(top_k, xt, gate, w1, w3, w2, plan):
    """[N, D] float32: the held experts' rows of ``xt`` [N, D], weighted by
    ``gate`` [N, top_k] (0 where an assignment is absent) and summed over a
    token's choices; ``plan``: what ``routed_experts`` made of the router's
    choices.  Its backward is its own (``_share_bwd``)."""
    held, sizes, _, back, _, tables = plan
    xs, a, b, (_, _, a2) = _sorted_and_hidden(top_k, xt, w1, w3, w2, plan,
                                              "forward")
    ys = grouped_product(_gated(a, b).astype(xs.dtype), a2, sizes,
                         tables)  # [N*k, D]
    # back to assignment order, weighted, summed over a token's choices
    return jnp.einsum("nk,nkd->nd", gate, _rows_home(
        ys, back, held, "forward").astype(jnp.float32))


def _share_fwd(top_k, xt, gate, w1, w3, w2, plan):
    # kept: the plan and the layer's inputs, no [N * top_k, .] array (1 GB
    # a layer at 8,192 tokens x 8 choices)
    return (_share(top_k, xt, gate, w1, w3, w2, plan),
            (xt, gate, w1, w3, w2, plan))


def _share_bwd(top_k, kept, dy):
    # behind a barrier, as ``jax.checkpoint`` puts one: without it XLA
    # finds the sorted rows and the hidden products below to be the
    # forward's own and keeps those from the forward instead.  With the
    # cotangent in it, or XLA makes them as soon as the layer's inputs are
    # there, before the loss, and they lie across the step's fullest point
    (xt, gate, w1, w3, w2, plan), dy = lax.optimization_barrier((kept, dy))
    held, sizes, order, back, live, tables = plan
    xs, a, b, (a1, a3, a2) = _sorted_and_hidden(top_k, xt, w1, w3, w2, plan,
                                                "backward")
    low = xs.dtype
    h, silu_bwd = jax.vjp(_gated, a, b)
    # the combine's cotangent goes out to the sorted rows from [N, D], as
    # the tokens' rows do, and meets the gate on the hidden side
    dy_s = _rows_out(dy.astype(low), order, top_k, "backward")
    gate_s = _permuted(gate.reshape(-1), order)[:, None]
    hg = (gate_s * h).astype(low)
    to_h, to_w2 = product_transposes(hg, a2, sizes, tables)
    u = jnp.where(live, to_h(dy_s), 0).astype(jnp.float32)
    # <dy, ys> over a row is <dy @ w2^T, h> over the same row
    dgate_s = jnp.sum(u * h, axis=1)
    da, db = (d.astype(low) for d in silu_bwd(gate_s * u))
    to_xs1, to_w1 = product_transposes(xs, a1, sizes, tables)
    to_xs3, to_w3 = product_transposes(xs, a3, sizes, tables)
    dxt = jnp.sum(_rows_home(to_xs1(da) + to_xs3(db), back, held,
                             "backward"),
                  axis=1, dtype=jnp.promote_types(xt.dtype, jnp.float32))
    dgate = jnp.where(held, _permuted(dgate_s, back).reshape(held.shape), 0)
    return (dxt.astype(xt.dtype), dgate.astype(gate.dtype),
            to_w1(xs, da).astype(w1.dtype), to_w3(xs, db).astype(w3.dtype),
            to_w2(hg, dy_s).astype(w2.dtype), None)


_share.defvjp(_share_fwd, _share_bwd)


def _rows_out(rows, order, top_k: int, which: str):
    """[N * top_k, D]: the tokens' ``rows`` [N, D] out to their ``top_k``
    assignments in expert order (``order``: sorted row -> assignment).  Its
    transpose is ``_rows_home`` through ``order``'s inverse, summed over a
    token's choices, which autodiff would lower as a scatter-add of every
    row (``unique_indices`` false, a row at a time on the TPU)."""
    _count_row_move(which)
    return _permuted(rows, order // top_k)


def _rows_home(rows, back, held, which: str):
    """[N, top_k, D]: the sorted ``rows`` [N * top_k, D] back in assignment
    order (``back``: assignment -> sorted row), those of an absent
    assignment, which may never have been written, selected away; a view of
    the gathered rows for the sum over a token's choices that reads it."""
    _count_row_move(which)
    return jnp.where(held[..., None],
                     _permuted(rows, back).reshape(held.shape + (-1,)), 0)


def _publish_load(live, rows: int, fullest):
    # the layer's load as step gauges (``observe.step_gauge``; the label
    # ``scope`` comes from the op), from what the plan already holds: the
    # assignments that chose an expert held here, the rows walked, the
    # fullest held expert (with ``live_rows / held`` the operator's max
    # over mean).  Device values that leave the step unfetched;
    # ``step_gauge`` never fails the trace it measures.
    from .. import observe

    observe.step_gauge("ops.moe.live_rows", live)
    observe.step_gauge("ops.moe.rows", rows)
    observe.step_gauge("ops.moe.fullest_group", fullest)


def _count_row_move(which: str):
    # one for every [N * top_k, D] gather traced: two in a forward, three
    # in a backward
    try:
        from .. import observe

        observe.registry().inc("ops.moe.row_moves",
                               labels={"pass": which, "how": "gather"})
    except Exception:
        pass  # accounting must never fail the trace it measures


def _permuted(rows, index):
    # every index is a row number by construction (a permutation, or one
    # divided by ``top_k``): said so, the gather needs no bounds check and no
    # select over the rows it returns (a pass of its own after a TPU gather)
    return rows.at[index].get(mode="promise_in_bounds")


def grouped_product(rows, weights, sizes, tables=None):
    """[M, N]: the rows of group g (``sizes[g]`` of them, groups in order)
    times ``weights[g]``; rows [M, K], weights [G, K, N].  By the Pallas
    kernels of ``ops/pallas_grouped``, forward and backward, where
    ``tables`` is their plan for these groups; by ``lax.ragged_dot`` and its
    own transposes where it is None.  ``routed_experts`` decides that once
    a layer; a caller with no plan asks ``product_tables``."""
    if tables is None:
        return lax.ragged_dot(rows, weights, sizes)
    return _kernel_product(rows, weights, tables)


def _declined(m, dtype, *weights) -> str:
    """'' where the Pallas kernels take the products of ``m`` rows of
    ``dtype`` with every one of ``weights`` [G, K, .] (lane-aligned widths,
    whole row tiles, bf16 or float32), else the first reason against.  By
    the operands alone, and the one place that asks."""
    from ..ops import pallas_grouped

    for w in weights:
        why = pallas_grouped.supported(
            jax.ShapeDtypeStruct((m, w.shape[1]), dtype), w)
        if why:
            return why
    return ""


def product_tables(rows, weights, sizes):
    """``tables`` for a caller with no routing plan: the kernels' plan
    where they take ``rows`` [M, K] times ``weights`` [G, K, N], else None."""
    from ..ops import pallas_grouped

    if _declined(rows.shape[0], rows.dtype, weights):
        return None
    return pallas_grouped.plan(sizes, rows.shape[0])


def product_path(x, w1, w2, top_k: int) -> str:
    """'pallas' where ``routed_experts`` on these operands multiplies by
    the Pallas kernels, 'ragged_dot' where by XLA's grouped product."""
    from ..fluid import amp

    low, a1, a2 = jax.eval_shape(lambda *a: amp.cast_operands(*a)[:-1],
                                 x, w1, w2)
    m = x.size // x.shape[-1] * top_k
    return "ragged_dot" if _declined(m, low.dtype, a1, a2) else "pallas"


def product_transposes(rows, weights, sizes, tables=None):
    """``(to_rows, to_weights)``, the two transposes of ``grouped_product(
    rows, weights, sizes, tables)`` on the path that ``tables`` says:
    ``to_rows(d)`` [M, K] is the cotangent ``d`` [M, N] of group g's rows
    times ``weights[g].T``, and ``to_weights(rows, d)`` [G, K, N] the
    weights' gradient from ANY rows [M, K] in those groups.  For a backward
    written by hand, which has no product to differentiate: of ``rows`` only
    the shape and type are read."""
    if tables is not None:
        return _kernel_transposes(weights, tables)

    # XLA's own transposes of its product, by ``jax.vjp`` (bilinear: where
    # the vjp is taken does not matter, and the product it traces has no
    # reader); ``jax.linear_transpose`` would refuse a product that brings
    # a vjp of its own, as the tests' poisoned one does
    def to_rows(d):
        return jax.vjp(lambda r: lax.ragged_dot(r, weights, sizes),
                       rows)[1](d)[0]

    def to_weights(rows, d):
        return jax.vjp(lambda w: lax.ragged_dot(rows, w, sizes),
                       weights)[1](d)[0]

    return to_rows, to_weights


def _kernel_transposes(weights, tables):
    from ..ops import pallas_grouped

    # the rows' cotangent reads the weights as they lie (no transposed
    # copy); the weights' gradient is summed in float32 inside the kernel
    def to_rows(d):
        return pallas_grouped.grouped_matmul(d, weights, None,
                                             transpose=True, plan=tables)

    def to_weights(rows, d):
        return pallas_grouped.grouped_matmul_t(rows, d, None, plan=tables)

    return to_rows, to_weights


@jax.custom_vjp
def _kernel_product(rows, weights, tables):
    from ..ops import pallas_grouped

    return pallas_grouped.grouped_matmul(rows, weights, None, plan=tables)


def _kernel_product_fwd(rows, weights, tables):
    return _kernel_product(rows, weights, tables), (rows, weights, tables)


def _kernel_product_bwd(kept, d):
    rows, weights, tables = kept
    to_rows, to_weights = _kernel_transposes(weights, tables)
    d = d.astype(rows.dtype)
    return to_rows(d), to_weights(rows, d), None


_kernel_product.defvjp(_kernel_product_fwd, _kernel_product_bwd)
