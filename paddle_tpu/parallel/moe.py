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

import dataclasses
import functools
import math
import operator
import threading
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
    expert_offset + E)`` with SiLU-gated feed-forwards and no bias
    (``W2(silu(W1 m) * W3 m)``); ``w3`` None: experts of TWO matrices about
    a squared ReLU, ``W2 relu(W1 m)^2``, one hidden product a walk in
    either pass and no third gradient.  Every
    token routes over all R; an assignment to an expert held here is
    computed, one to an absent expert is left out (its chip adds it in a
    deployment; nothing stands in for it here).  There is no capacity and
    nothing is dropped: the ``N * top_k`` assignments are sorted by expert
    (absent ones last) and the sorted rows are walked a SLAB at a time
    (``slab_rows`` of them, as grouped products, ``grouped_product``): one
    trip of a ``lax.while_loop`` while the assignments to the experts held
    fit a slab, as many more as they need beyond it, so a step in which
    every token chose held experts only is as right as any other.  Where
    the slab is every row (a quarter of the experts or more held, an
    eighth under a router with no balancing ``bias``: ``slab_rows``) no loop
    is built.

    The routing plan (the router's weights and choices, the sort by expert
    and its inverse, the group sizes) is made once and kept for the
    backward, with the layer's inputs and nothing else.  The backward is
    written by hand (``_share``): it makes the sorted rows and the two
    hidden products again (a gather, two products) and NOT the last
    product, whose one reader there was the gate's cotangent (that is
    ``<dy @ w2^T, h>`` over a row, and the backward has both).  Rows move
    by gathers in both passes and nothing is scattered: a walk gathers
    twice forward and three times backward (out to the walk's sorted rows,
    ``[slab, D]``; home to the assignments, ``[N * top_k, D]``, in a
    loop's body a choice at a time, ``_choices_home``); the
    combine's cotangent goes out to the sorted rows from ``[N, D]`` and
    meets the gate on the hidden side, never as ``[N, top_k, D]``.  A walk
    of both passes: 8 products and 3 weights' gradients, traced ONCE a
    lowering whatever the trips.
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
    # the layer's ONE choice of kernels or XLA's grouped product, and the
    # rows a trip walks: both from the operands alone
    path, rows, slab = walk_of(x, router_w, w1, w2, top_k, bias)
    kernels = path == "pallas"
    if slab == rows:
        # every row in ONE walk and no loop: the absent experts'
        # assignments ride as zero rows at the end of the LAST held group
        # (were the sizes to stop at the last held assignment, the products
        # would skip the row tiles past it and a step's time follow the
        # router)
        live = (jnp.arange(rows, dtype=i32) < first[e])[:, None]
        sizes = counts[:e].at[e - 1].add(counts[e])
        trips = None
    else:
        # at least one trip, so that a step's gauges always have rows
        trips = jnp.maximum((first[e] + i32(slab - 1)) // i32(slab), i32(1))
    _publish_load(first[e], rows if trips is None else trips * i32(slab),
                  jnp.max(counts[:e]), trips)
    gate = jnp.where(held, vals, 0.0)
    if trips is None:
        plan = (held, sizes, order, back, live, _tables(sizes, rows, kernels))
    else:
        plan = _slabs(held, first, order, back, trips, slab, kernels)
    y = _share(top_k, xt, gate, w1, w3, w2, plan)
    y = y.astype(x.dtype).reshape(shape)
    if with_counts:
        return y, assignment_counts(idx, router_w.shape[-1])
    return y


#: The rows a trip of the layer walks, over the rows an EVEN router sends to
#: the experts held.  A chip that holds ``held`` of ``routed`` experts sees
#: that share of the ``N * top_k`` assignments and the rest are absent:
#: walked as zero rows they cost the products', the gathers' and the
#: elementwise passes' time over rows, forward and backward (ledger, PR 54:
#: with 10.45% of the rows live in ``trinity_mini.resident`` and 3.14% in
#: ``kimi_linear_48b_a3b.resident`` the expert layer was 99 and 37 ms of
#: their steps; ledger, PR 56: a slab of 24,576 of Trinity's 49,152 rows
#: gave back 46 ms a step, one of 4,096 of Kimi-Linear's 16,384 rows 13;
#: ledger, PR 58: at 8 times the even share 80-87% of the rows walked were
#: still zero rows, 51 / 87 / 19.5 ms of expert layer a step in Trinity,
#: Nemotron and Kimi-Linear, and Instella, 8 of 64 held, had no slab at
#: all: 134 ms).  WHY 4: a router trained on one chip's share drifts toward
#: the experts it holds, and the slab needs room for it, since a trip
#: beyond the first costs the whole slab's time again (8 products, 3
#: weights' gradients and 5 row gathers over ``slab`` rows, and the carried
#: sums read and written once more: a step in two trips walks what the
#: factor of 8 walked in one and gains nothing, it does not lose).  The
#: LARGEST live count a layer showed in one step of a timed window, over
#: every step of every window PR 59 ran on the chip (PERF.md section 6, PR
#: 59; a window opens with the router's first swing toward the experts
#: held, which the balancing rule pulls back within some thirty steps),
#: beside the slab at 4: Trinity 9,776 rows (3.2 times its even share of
#: 3,072; 80% of 12,288), Nemotron 10,033 (3.3 times 3,072; 82% of
#: 12,288), Kimi-Linear 951 (1.9 times 512; 46% of 2,048), Instella 18,361
#: (3.0 times 6,144; 75% of 24,576): one trip in every step read, with 1.2
#: to 2.2 times room, where the accounts of PRs 54 to 58, which print a
#: layer's first, median and last step, had read 7,646 (2.5 times even,
#: not the 2.0 written here before) / 6,267 / 778 / 12,288.  At 3 the
#: first two would take a second trip in those steps.  Without such a
#: rule a router drifts further and does not come back (Qwen3-Next 3.4
#: to 3.6 times, 11.3% of all rows, and not stopped at the window's last
#: step; Keye 6.0 times: ledger, PR 66), so a layer whose router has no
#: bias walks TWICE this factor (``slab_rows``' ``balanced``); and however
#: far a router drifts, a layer in slabs walks less than one slab more
#: than the one walk does (``ceil(live / slab)`` trips).  A quarter of the
#: experts or more held is every row; without a bias, an eighth.
SLAB_OVER_EVEN = 4


def slab_rows(rows: int, held: int, routed: int, kernels: bool,
              balanced: bool = True) -> int:
    """How many of a layer's ``rows`` (``N * top_k``) sorted rows one trip
    walks where ``held`` of ``routed`` experts are here: ``SLAB_OVER_EVEN``
    times the even share of them, in whole row tiles where the Pallas
    ``kernels`` multiply; all of them where that is no fewer.  From the
    operands alone: no argument of the layer, attribute, environment
    variable or configuration's name chooses.

    ``balanced`` (the router has a selection bias that ``balance_bias``
    moves) is a rule of ROUTERS, with a reading behind it: a router without
    a bias has nothing that pulls it back, and on one chip's share of the
    experts it learns to choose those that are held.  ``moe_live_rows_pct``
    over the even share (ledger, PR 66): Qwen3-Next 10.57 over 3.125, 3.4
    times and still climbing at the window's last step (9,257 rows, 11.3%,
    at most); Mellum2 38.5 over 12.5, 3.1 times; SDAR 62.1 over 12.5, 5.0;
    Keye 74.7 over 12.5, 6.0.  Four times the even share cannot hold that,
    so such a layer has TWICE the room, ``2 * SLAB_OVER_EVEN`` times the
    even share: Qwen3-Next 20,480 of 81,920 rows, one trip in every step
    read (my chip runs, PR 67: the four layers' mean 49% of the slab at the
    window's middle, and the fourth layer, which drifts furthest, 18,783
    rows, 92% of it, at most and climbing at the window's last step; the
    ledger's 10.57% is the layers' mean, not the fullest layer's; a window
    four times as long took up to three trips there, exact, 57,764 of the
    81,920 rows live, and its ``step_ms_p95`` read 244.4 ms for 214.1);
    Keye, SDAR and Mellum2, an eighth of their
    experts held, every row and no loop.  (Until PR 67 the argument sent
    such a router to every row, for memory and not for drift: with its
    eight loops Qwen3-Next's step reserved more than its bound allows, 279
    MB over the parent's 5,171.8 at PR 67.  What gave that back, and 157 MB
    more, is ``_choices_home``, in every looped layer.)  Whether
    ``balanced`` can go altogether, Mellum2 at half its rows and Keye and
    SDAR at two trips a step, waits for a reading of what a SECOND trip
    costs against one walk (ROADMAP S11 (b))."""
    from ..ops.pallas_grouped import ROW_TILE

    over = SLAB_OVER_EVEN if balanced else 2 * SLAB_OVER_EVEN
    tile = ROW_TILE if kernels else 1
    return min(rows, tile * -(-over * rows * held // (routed * tile)))


def walk_of(x, router_w, w1, w2, top_k: int, bias):
    """``(path, rows, slab)`` of ``routed_experts`` on these operands: the
    product path (``product_path``), the ``N * top_k`` sorted rows, and how
    many of them one trip walks (``slab_rows``; ``rows`` where the layer
    walks them all at once and builds no loop)."""
    path = product_path(x, w1, w2, top_k)
    rows = x.size // x.shape[-1] * top_k
    return path, rows, slab_rows(rows, w1.shape[0], router_w.shape[-1],
                                 path == "pallas", bias is not None)


def _tables(sizes, m: int, kernels: bool):
    # the kernels' visit tables of a walk over ``m`` rows in groups of
    # ``sizes``, for all eleven calls of the walk, or None where XLA's
    # grouped product multiplies
    if not kernels:
        return None
    from ..ops import pallas_grouped

    return pallas_grouped.plan(sizes, m)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["held", "sizes", "order", "back", "live",
                                "trips"], meta_fields=["kernels"])
@dataclasses.dataclass(frozen=True)
class _Slabs:
    """The routing plan of a layer that walks its sorted rows in slabs of
    ``order.shape[1]``: what every slab's walk is made of, made once
    (``_slabs``) and kept for the backward as the one walk's plan is.
    ``walk(t)`` is trip ``t``'s, in the form of the one walk over all
    rows."""
    held: jnp.ndarray       # [N, top_k] bool: the assignment is to an
    #                         expert held here
    sizes: jnp.ndarray      # [slabs, E] int32: each slab's rows by group
    order: jnp.ndarray      # [slabs, slab] int32: sorted row -> assignment
    back: jnp.ndarray       # [N * top_k] int32: assignment -> sorted row
    live: jnp.ndarray       # int32: the sorted rows that hold an
    #                         assignment to an expert held here, the first
    trips: jnp.ndarray      # int32: the slabs that hold one of them, >= 1
    kernels: bool           # the layer's choice of product (static)

    def walk(self, t):
        i32 = jnp.int32
        slab = self.order.shape[1]
        lo = t * i32(slab)
        sizes = lax.dynamic_index_in_dim(self.sizes, t, keepdims=False)
        # home come only the assignments whose sorted row lies in this
        # slab, from their row inside it; a row that no trip walks is an
        # absent assignment's
        here = (self.back >= lo) & (self.back < lo + i32(slab))
        return (self.held & here.reshape(self.held.shape), sizes,
                lax.dynamic_index_in_dim(self.order, t, keepdims=False),
                jnp.clip(self.back - lo, 0, i32(slab - 1)),
                (lo + jnp.arange(slab, dtype=i32) < self.live)[:, None],
                _slab_tables(sizes, slab) if self.kernels else None)


def _slabs(held, first, order, back, trips, slab: int, kernels: bool):
    """``_Slabs`` of the sorted rows ``[t * slab, (t + 1) * slab)`` for
    every ``t``: the groups' rows clipped to the slab and what is left of
    it added to the last group, so that a slab's sizes sum to ``slab`` and
    every walked row is multiplied, as in the one walk (a trip's time does
    not follow the router); the last slab may run past the rows there are,
    into rows that no assignment holds (``order`` 0 there, never live)."""
    i32 = jnp.int32
    e = first.shape[0] - 1
    n_slabs = -(-order.shape[0] // slab)
    lo = (jnp.arange(n_slabs, dtype=i32) * i32(slab))[:, None]
    at = jnp.clip(first[None, :], lo, lo + i32(slab)) - lo
    sizes = (at[:, 1:] - at[:, :-1]).at[:, e - 1].add(i32(slab) - at[:, e])
    order = jnp.pad(order, (0, n_slabs * slab - order.shape[0]))
    return _Slabs(held, sizes, order.reshape(n_slabs, slab), back, first[e],
                  trips, kernels)


# a trip's tables are made in the loop's body, from its own sizes (as many
# table gathers a layer and step as the one walk's plan, which the op makes
# once and its grad op again); jitted and inlined, so that the hundred small
# operations are traced once a process and not once a body
@functools.partial(jax.jit, static_argnums=(1,), inline=True)
def _slab_tables(sizes, slab: int):
    return _tables(sizes, slab, True)


def _walks(walk, top_k, operands, plan, sums):
    """``walk(top_k, operands, plan of a walk, types, looped)`` summed over
    the trips of the layer.  ``sums``: for each result ``(operand whose
    shape and type it leaves in, type the trips are summed in)``; ``types``
    is what a walk hands its results over in, ``looped`` whether it is a
    loop's body.  One slab of every row: the walk itself, handed over in
    the operands' types.  Else ``_looped``."""
    if not isinstance(plan, _Slabs):
        return walk(top_k, operands, plan,
                    tuple(of.dtype for of, _ in sums), False)
    from .. import observe
    from ..ops import kernel_choice

    leaves, tree = jax.tree_util.tree_flatten((operands, plan))
    key = (walk, top_k, tuple((of.shape, jnp.dtype(of.dtype), jnp.dtype(to))
                              for of, to in sums),
           plan.kernels and kernel_choice.interpret(), tree,
           tuple((t.shape, t.dtype, t.weak_type)
                 for t in map(jax.typeof, leaves)))
    made = getattr(_TRACES, "made", 0)
    total = _looped(key, operands, plan)
    if getattr(_TRACES, "made", 0) == made:
        # the kept trace served this call: its counters count a trace
        # (``ops.moe.row_moves``, ``ops.moe.column_tiles``), so a layer
        # and pass count what they counted while it was made
        with _COUNTED_LOCK:
            counted = _COUNTED.get(key, ())
        observe.registry().replay(counted)
    return total


#: the traces of ``_looped`` a thread has made, and what each counted while
#: it was made, by what decides a trace (``_walks``' ``key``)
_TRACES = threading.local()
_COUNTED: dict = {}
_COUNTED_LOCK = threading.Lock()


@functools.partial(jax.jit, static_argnums=(0,), inline=True)
def _looped(key, operands, plan):
    """ONE ``lax.while_loop`` whose body is the walk: every result of a
    walk is a sum over sorted rows and a sorted row lies in one slab, so the
    trips' results add up, from zeros of the body's own types (a carry of
    another type, or a weak one, would have the body traced a second time
    to promote it).  An inlined ``jax.jit``: the layers of a model, the op
    and its grad op, and the programs of a process that walk operands of
    equal types share ONE trace of the loop and its body and each lowers
    its own copy (``ops/pallas_grouped``'s kernels do the same a call; on
    the v5e's host a body costs 0.14 s to trace, PERF.md section 6, PR 56,
    and a step of four routed layers and its comparison held 24).  ``key``
    is all that decides the trace."""
    from .. import observe

    walk, top_k, sums = key[:3]
    types = tuple(to for _, _, to in sums)
    with observe.registry().tape() as counted:
        def trip(carry):
            t, so_far = carry
            return t + jnp.int32(1), tuple(a + b for a, b in zip(
                so_far, walk(top_k, operands, plan.walk(t), types, True)))

        _, total = lax.while_loop(
            lambda carry: carry[0] < plan.trips, trip,
            (jnp.int32(0), tuple(jnp.zeros(shape, to)
                                 for shape, _, to in sums)))
    with _COUNTED_LOCK:
        _COUNTED[key] = tuple(counted)
    _TRACES.made = getattr(_TRACES, "made", 0) + 1
    return tuple(a.astype(dtype) for a, (_, dtype, _) in zip(total, sums))


def _wide(of):
    return jnp.promote_types(of.dtype, jnp.float32)


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
# whatever ``sizes`` covers, and for every slab: a sorted row that no trip
# walks is an absent assignment's.
def _sorted_and_hidden(top_k, low, weights, plan, which):
    """What a walk of either pass of ``_share`` makes first: the tokens'
    rows ``low`` [N, D] sorted by expert, ``xs`` [rows, D]; and the two
    hidden products of them with ``weights[:2]``, float32 [rows, F],
    selected by ``live`` (``_hidden`` of the two is the experts' hidden
    rows; the second is None where the experts have no ``w3``).  Operands
    in AMP's type (``_low``)."""
    _, sizes, order, _, live, tables = plan
    xs = _rows_out(low, order, top_k, which)
    a, b = (None if w is None else
            jnp.where(live, grouped_product(xs, w, sizes, tables), 0)
            .astype(jnp.float32) for w in weights[:2])
    return xs, a, b


def _lane_rows(width: int) -> int:
    """``width`` in whole lane rows: the hidden width the Pallas kernels
    multiply at."""
    from ..ops.pallas_grouped import LANE

    return -(-width // LANE) * LANE


def _low(xt, w1, w3, w2, kernels=False):
    # the tokens' rows and the three weights in AMP's type, once a pass
    # (``w3`` None stays None: experts of two matrices).  ``kernels``: an
    # expert width that is not whole lane rows (1,856 = 14.5 x 128) is
    # filled up with ZERO columns of w1 and w3 and zero rows of w2, in the
    # pass that casts them: the hidden columns they give are exact zeros
    # in both passes (silu(0) * 0, relu(0)^2, and their cotangents), the
    # parameters and what is kept for the backward stay as they are, and
    # ``_share_bwd`` cuts the weights' gradients back
    from ..fluid import amp

    if w3 is None:
        low, a1, a2, _ = amp.cast_operands(xt, w1, w2)
        weights = [a1, None, a2]
    else:
        low, *weights, _ = amp.cast_operands(xt, w1, w3, w2)
    fill = _lane_rows(w2.shape[1]) - w2.shape[1] if kernels else 0
    if fill:
        a1, a3, a2 = weights
        columns, rows = ((0, 0), (0, 0), (0, fill)), ((0, 0), (0, fill),
                                                      (0, 0))
        weights = [jnp.pad(a1, columns),
                   a3 if a3 is None else jnp.pad(a3, columns),
                   jnp.pad(a2, rows)]
    return low, weights


def _by_kernels(plan) -> bool:
    # the layer's one choice of product, as its plan carries it
    return plan.kernels if isinstance(plan, _Slabs) else plan[5] is not None


def _hidden(a, b):
    # an expert's hidden row from its hidden products: SiLU-gated, or the
    # squared ReLU of the one product where there is no second
    if b is None:
        return jnp.square(jax.nn.relu(a))
    return jax.nn.silu(a) * b


def _forward_walk(top_k, operands, plan, types, looped):
    low, weights, gate = operands
    held, sizes, _, back, _, tables = plan
    xs, a, b = _sorted_and_hidden(top_k, low, weights, plan, "forward")
    ys = grouped_product(_hidden(a, b).astype(xs.dtype), weights[2],
                         sizes, tables)  # [rows, D]
    # back to assignment order, weighted, summed over a token's choices
    if looped:
        return _choices_home(ys, back, held, "forward", jnp.float32,
                             gate).astype(types[0]),
    return jnp.einsum("nk,nkd->nd", gate, _rows_home(
        ys, back, held, "forward").astype(jnp.float32)).astype(types[0]),


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _share(top_k, xt, gate, w1, w3, w2, plan):
    """[N, D] float32: the held experts' rows of ``xt`` [N, D], weighted by
    ``gate`` [N, top_k] (0 where an assignment is absent) and summed over a
    token's choices; ``plan``: what ``routed_experts`` made of the router's
    choices, the one walk's or a ``_Slabs``; ``w3`` None: experts of two
    matrices.  Its backward is its own (``_share_bwd``)."""
    low, weights = _low(xt, w1, w3, w2, _by_kernels(plan))
    y = jax.ShapeDtypeStruct(xt.shape, jnp.float32)
    return _walks(_forward_walk, top_k, (low, tuple(weights), gate), plan,
                  ((y, jnp.float32),))[0]


def _share_fwd(top_k, xt, gate, w1, w3, w2, plan):
    # kept: the plan and the layer's inputs, no [N * top_k, .] array (1 GB
    # a layer at 8,192 tokens x 8 choices)
    return (_share(top_k, xt, gate, w1, w3, w2, plan),
            (xt, gate, w1, w3, w2, plan))


def _backward_walk(top_k, operands, plan, types, looped):
    low, (a1, a3, a2), gate, dy = operands
    held, sizes, order, back, live, tables = plan
    xs, a, b = _sorted_and_hidden(top_k, low, (a1, a3), plan, "backward")
    h, hidden_bwd = jax.vjp(_hidden, a, b)
    # the combine's cotangent goes out to the sorted rows from [N, D], as
    # the tokens' rows do, and meets the gate on the hidden side
    dy_s = _rows_out(dy.astype(low.dtype), order, top_k, "backward")
    gate_s = _permuted(gate.reshape(-1), order)[:, None]
    hg = (gate_s * h).astype(low.dtype)
    to_h, to_w2 = product_transposes(hg, a2, sizes, tables)
    u = jnp.where(live, to_h(dy_s), 0).astype(jnp.float32)
    # <dy, ys> over a row is <dy @ w2^T, h> over the same row
    dgate_s = jnp.sum(u * h, axis=1)
    # the hidden products there are (one where the experts have no w3) and
    # their cotangents, in step
    ups = [w for w in (a1, a3) if w is not None]
    cots = [d.astype(low.dtype) for d in hidden_bwd(gate_s * u)
            if d is not None]
    to_xs, to_w = zip(*(product_transposes(xs, w, sizes, tables)
                        for w in ups))

    def weights_gradients():
        return (*(t(xs, d).astype(typ)
                  for t, d, typ in zip(to_w, cots, types[2:])),
                to_w2(hg, dy_s).astype(types[-1]))

    if looped:
        # in a loop's body the weights' gradients come FIRST, behind a
        # barrier: the compiler schedules a body by itself and put them
        # last, with the sorted rows, the sorted cotangent and the hidden
        # rows alive across the rows' cotangents and their gather home, the
        # body's fullest point and the step's (my described-chip compiles,
        # PR 56: 4,946 MB of reserved memory in Trinity's step without the
        # barrier, 4,813 MB with it, 4,864 MB at the parent)
        dws = weights_gradients()
        cots, dws = lax.optimization_barrier((cots, dws))
    dxs = functools.reduce(operator.add,
                           (t(d) for t, d in zip(to_xs, cots)))
    wide = jnp.promote_types(types[0], jnp.float32)
    if looped:
        dxt = _choices_home(dxs, back, held, "backward", wide)
    else:
        dxt = jnp.sum(_rows_home(dxs, back, held, "backward"), axis=1,
                      dtype=wide)
    dgate = jnp.where(held, _permuted(dgate_s, back).reshape(held.shape), 0)
    if not looped:
        dws = weights_gradients()
    return dxt.astype(types[0]), dgate.astype(types[1]), *dws


def _share_bwd(top_k, kept, dy):
    # behind a barrier, as ``jax.checkpoint`` puts one: without it XLA
    # finds the sorted rows and the hidden products below to be the
    # forward's own and keeps those from the forward instead.  With the
    # cotangent in it, or XLA makes them as soon as the layer's inputs are
    # there, before the loss, and they lie across the step's fullest point
    (xt, gate, w1, w3, w2, plan), dy = lax.optimization_barrier((kept, dy))
    low, (a1, a3, a2) = _low(xt, w1, w3, w2, _by_kernels(plan))
    # the rows' cotangents are summed over the trips in float32; a weights'
    # gradient in the type its product hands it over in (AMP's: the kernel
    # sums a group's rows in float32 and rounds ONCE a call, as in the one
    # walk).  An expert's rows are consecutive, so its gradient is one
    # trip's and exact zeros from the others, unless its rows lie across a
    # slab's edge: then two rounded parts are added and rounded once more.
    # Summed in float32 the three [E, D, F] gradients would wait for the
    # optimizer at twice their bytes, where the one walk keeps them in
    # AMP's type until the sweep reads them (PERF.md section 6, PR 55: 1.2%
    # of Trinity's peak memory, 0.6 GB of Qwen3-Next's step)
    held = [(w, a) for w, a in ((w1, a1), (w3, a3), (w2, a2))
            if w is not None]
    dxt, dgate, *dws = _walks(
        _backward_walk, top_k, (low, (a1, a3, a2), gate, dy), plan,
        ((xt, _wide(xt)), (gate, _wide(gate)),
         *((jax.ShapeDtypeStruct(a.shape, w.dtype), a.dtype)
           for w, a in held)))
    # a width that ``_low`` filled up to whole lane rows: cut back
    dws = [d if d.shape == w.shape else
           d[tuple(slice(0, n) for n in w.shape)]
           for d, (w, _) in zip(dws, held)]
    if w3 is None:
        dws.insert(1, None)
    return (dxt, dgate, *dws, None)


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


def _choices_home(rows, back, held, which: str, wide, gate=None):
    """[N, D] in the type ``wide``: ``_rows_home`` summed over a token's
    choices, each weighted by its ``gate`` [N, top_k] where one is given,
    as a loop's body makes it: ONE GATHER OF [N, D] A CHOICE, added up as
    they come, so that the [N, top_k, D] array of every assignment's row
    never exists.  In a body it was the fullest point, and with loops in
    the step the step's: the compiler schedules a body by itself, around
    whatever the step keeps waiting outside it (with a ``while`` among the
    backward ops it sinks the weights' gradients and the optimizer's updates
    behind the last one, and the head's logits wait with them).
    Qwen3-Next's step with its eight loops reserved 5,450.7 MB with the one
    gather, 335 MB in a body, and 5,014.9 MB this way, where the parent's
    every-row walk reserves 5,171.8; Trinity 4,744.5 -> 4,698.0, Nemotron
    4,964.1 -> 4,917.7, Kimi-Linear 2,296.2 -> 2,287.9, Instella 5,104.3 ->
    5,097.4 (my described-chip compiles, PR 67).  As many rows are moved
    either way, and counted as one move; the pass that summed the one
    gather's rows is gone, 12.6 ms of Qwen3-Next's step (my chip runs, PR
    67: 63.3 -> 50.7 ms a step for the layer's two ops)."""
    _count_row_move(which)
    back = back.reshape(held.shape)
    total = None
    for j in range(held.shape[1]):
        row = jnp.where(held[:, j, None], _permuted(rows, back[:, j]),
                        0).astype(wide)
        if gate is not None:
            row = gate[:, j, None] * row
        total = row if total is None else total + row
    return total


def _publish_load(live, walked, fullest, trips=None):
    # the layer's load as step gauges (``observe.step_gauge``; the label
    # ``scope`` comes from the op), from what the plan already holds: the
    # assignments that chose an expert held here, the rows walked (``slab *
    # trips``, a device value; all ``N * top_k``, a constant, where no loop
    # is built), the fullest held expert (with ``live_rows / held`` the
    # operator's max over mean) and, where the layer walks in slabs, the
    # trips of its loop: 1 in an ordinary step, more once the router has
    # drifted onto this chip.  Device values that leave the step unfetched;
    # ``step_gauge`` never fails the trace it measures.
    from .. import observe

    observe.step_gauge("ops.moe.live_rows", live)
    observe.step_gauge("ops.moe.rows", walked)
    observe.step_gauge("ops.moe.fullest_group", fullest)
    if trips is not None:
        observe.step_gauge("ops.moe.slab_trips", trips)


def _count_row_move(which: str):
    # one for every row gather traced: two in a walk of the forward, three
    # in one of the backward, and a pass traces ONE walk whatever its trips
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
    # the kernels multiply at the hidden width in whole lane rows (``_low``)
    wide = _lane_rows(a1.shape[2])
    a1 = jax.ShapeDtypeStruct(a1.shape[:2] + (wide,), a1.dtype)
    a2 = jax.ShapeDtypeStruct((a2.shape[0], wide, a2.shape[2]), a2.dtype)
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
