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
    backward; the rows themselves (``N * top_k`` sorted rows, the hidden
    products) are made again there, not kept.  Rows move by gathers in both
    passes: the backward takes the cotangent rows through the inverse
    permutation the forward already has (``_take_rows``) and scatters
    nothing, so what a row costs is the products' and the gathers' time.
    """
    from ..fluid import amp

    shape = x.shape
    e = w1.shape[0]
    xt = x.reshape((-1, shape[-1]))
    n = xt.shape[0]

    # the routing plan, made ONCE, outside the checkpoint below, which
    # takes it as arguments: the backward sorts and counts nothing again
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
    # the other the index of that gather's transpose (``_take_rows``)
    back = jnp.sum(chose * (first[None, :] - 1
                            + jnp.cumsum(chose, axis=0, dtype=i32)),
                   axis=1, dtype=i32)
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    live = (jnp.arange(order.shape[0], dtype=i32) < first[e])[:, None]
    # DEBT (ROADMAP S11, PERF.md section 7): the absent experts'
    # assignments ride along as zero rows at the end of the LAST held
    # group, so all N * top_k rows are gathered, multiplied and gathered
    # back, forward and backward: 8 of every 9 where an eighth of the
    # experts is held.  What that costs is the products' and the gathers'
    # time over rows (no scatter is left); what dropping it can buy in the
    # resident cells is what PR 30 read, 414.3 -> 394.6-405.6 ms a step
    # with the cell's spread lost (a product call took 2.0 ms at 8,192 and
    # at 65,536 live rows).  Without this line (sizes = counts[:e]) the
    # products skip the row tiles past the last group and a step's time
    # follows the router, which drifts toward the experts held as it
    # trains without the absent ones; nothing else depends on it.
    sizes = counts[:e].at[e - 1].add(counts[e])
    gate = jnp.where(held, vals, 0.0)
    # part of the plan where the products are the Pallas kernels': the
    # tables that tell their grid steps row tiles and groups, for all
    # twelve calls of the layer
    tables = None
    if product_path(x, w1, w2, top_k) == "pallas":
        from ..ops import pallas_grouped

        tables = pallas_grouped.plan(sizes, n * top_k)

    # a checkpoint: the backward makes the sorted rows and the experts'
    # hidden activations again instead of keeping N * top_k rows of them
    # per layer (1 GB a layer at 8,192 tokens x 8 choices).  The plan is
    # kept, not made again: [N, k] and [N*k] integers and the gate
    @jax.checkpoint
    def share(xt, gate, w1, w3, w2, held, sizes, order, back, live,
              tables):
        # XLA's grouped product on the TPU leaves the rows outside every
        # group UNWRITTEN, in its results and in the cotangents it hands
        # back (NaN gradients on the chip; the CPU zero-fills them).  So
        # what a product returns for a row that holds no held assignment
        # is SELECTED away before anything reads it, never multiplied by
        # zero, and the cotangent the same on its way back: the two hidden
        # products by ``live`` (``where``'s own vjp selects their
        # cotangents), the last product where its rows are gathered, by
        # ``held``, and the cotangent of xs where ITS rows are gathered
        # back, by ``held`` too (``_take_rows``'s ``keep``).  Each select
        # sits in a fusion that reads the rows anyway; xs itself needs
        # none: a row without a held assignment is some token's row, read
        # by products whose results are selected away and met by exact
        # zeros in the weights' gradients.  Right whatever ``sizes`` covers.
        def live_rows(rows):
            return jnp.where(live, rows, 0)

        # the tokens' rows are cast where they are taken, so that their
        # cotangent comes back through the gather in AMP's type too
        low, a1, a3, a2, _ = amp.cast_operands(xt, w1, w3, w2)
        xs = _take_rows(xt, order, back, held.reshape(-1), top_k, low.dtype)
        h = jax.nn.silu(live_rows(grouped_product(xs, a1, sizes, tables))
                        .astype(jnp.float32)) \
            * live_rows(grouped_product(xs, a3, sizes, tables)
                        ).astype(jnp.float32)
        ys = grouped_product(h.astype(xs.dtype), a2, sizes,
                             tables)  # [N*k, D]
        # back to assignment order, weighted, summed over a token's choices
        ys = jnp.where(held[..., None],
                       _take_rows(ys, back, order, None, 1, ys.dtype)
                       .reshape(n, top_k, -1), 0)
        return jnp.einsum("nk,nkd->nd", gate, ys.astype(jnp.float32))

    y = share(xt, gate, w1, w3, w2, held, sizes, order, back, live, tables)
    y = y.astype(x.dtype).reshape(shape)
    if with_counts:
        return y, assignment_counts(idx, router_w.shape[-1])
    return y


def grouped_product(rows, weights, sizes, tables=None):
    """[M, N]: the rows of group g (``sizes[g]`` of them, groups in order)
    times ``weights[g]``; rows [M, K], weights [G, K, N].  The Pallas
    kernels of ``ops/pallas_grouped`` where they take the shapes (lane-
    aligned widths, whole row tiles, bf16 or float32), forward and
    backward, else ``lax.ragged_dot`` and its own transposes: a choice by
    what the operands are, which ``product_path`` states for a layer.
    ``tables``: ``pallas_grouped.plan(sizes, M)`` where the caller made it
    already for several products over the same groups."""
    from ..ops import pallas_grouped

    if pallas_grouped.supported(rows, weights):
        return lax.ragged_dot(rows, weights, sizes)
    if tables is None:
        tables = pallas_grouped.plan(sizes, rows.shape[0])
    return _kernel_product(rows, weights, tables)


def product_path(x, w1, w2, top_k: int) -> str:
    """'pallas' where ``routed_experts`` on these operands multiplies by
    the Pallas kernels, 'ragged_dot' where by XLA's grouped product."""
    from ..fluid import amp
    from ..ops import pallas_grouped

    low, a1, a2 = jax.eval_shape(lambda *a: amp.cast_operands(*a)[:-1],
                                 x, w1, w2)
    m = x.size // x.shape[-1] * top_k

    def declined(w):
        return pallas_grouped.supported(
            jax.ShapeDtypeStruct((m, w.shape[1]), low.dtype), w)

    return "ragged_dot" if declined(a1) or declined(a2) else "pallas"


@jax.custom_vjp
def _kernel_product(rows, weights, tables):
    from ..ops import pallas_grouped

    return pallas_grouped.grouped_matmul(rows, weights, None, plan=tables)


def _kernel_product_fwd(rows, weights, tables):
    return _kernel_product(rows, weights, tables), (rows, weights, tables)


def _kernel_product_bwd(kept, d):
    from ..ops import pallas_grouped

    rows, weights, tables = kept
    d = d.astype(rows.dtype)
    # the rows' cotangent reads the weights as they lie (no transposed
    # copy); the weights' gradient is summed in float32 inside the kernel
    return (pallas_grouped.grouped_matmul(d, weights, None, transpose=True,
                                          plan=tables),
            pallas_grouped.grouped_matmul_t(rows, d, None, plan=tables),
            None)


_kernel_product.defvjp(_kernel_product_fwd, _kernel_product_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _take_rows(rows, index, inverse, keep, repeat: int, dtype):
    """``rows.astype(dtype)[index // repeat]``, where ``index`` is a
    permutation of ``repeat * len(rows)`` row numbers and ``inverse`` the
    permutation that undoes it.  The transpose of that gather, which
    autodiff would lower as a scatter-add of every row (``unique_indices``
    false, a row at a time on the TPU), is then a gather too: the cotangent
    rows, in ``dtype``, taken through ``inverse``, and the ``repeat`` rows
    that came from one summed in float32; ``keep`` (a mask over
    ``inverse``, or None) says which of them count: the others, which may
    never have been written, are selected away inside that sum.
    ``routed_experts`` moves rows both ways with it: tokens out to their
    ``top_k`` assignments in expert order (``order``, with ``back`` its
    inverse, ``repeat = top_k``), and the experts' rows back to assignment
    order (``back``, ``order``, 1)."""
    return _permuted(rows.astype(dtype), index // repeat)


def _permuted(rows, index):
    # every index is a row number by construction (a permutation, or one
    # divided by ``repeat``): said so, the gather needs no bounds check and no
    # select over the rows it returns (a pass of its own after a TPU gather)
    return rows.at[index].get(mode="promise_in_bounds")


def _take_rows_fwd(rows, index, inverse, keep, repeat, dtype):
    # the empty array carries the cotangent's type to the backward
    return (_take_rows(rows, index, inverse, keep, repeat, dtype),
            (inverse, keep, jnp.zeros((0,), rows.dtype)))


def _take_rows_bwd(repeat, dtype, kept, d):
    from ..ops.decoder_ops import _count

    inverse, keep, like = kept
    # one for every row move whose backward is traced as a gather: two an
    # expert layer in each program lowered
    _count("ops.moe.row_moves", **{"pass": "backward", "how": "gather"})
    d = _permuted(d, inverse)
    if keep is not None:
        d = jnp.where(keep[:, None], d, 0)
    if repeat > 1:
        d = jnp.sum(d.reshape((-1, repeat) + d.shape[1:]), axis=1,
                    dtype=jnp.promote_types(like.dtype, jnp.float32))
    return d.astype(like.dtype), None, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)
