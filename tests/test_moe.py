"""Mixture-of-experts / expert parallelism tests.

MoE/EP is a TPU-native capability beyond the reference (SURVEY.md §2.6 lists
MoE/EP "Absent"; its nearest analogue is the pserver-sharded lookup table,
ref distribute_transpiler.py:379-382).  The parallel-mode bar is the same as
for DP/TP (SURVEY.md §4.4): loss-equivalence vs the single-device run.
"""

import numpy as np
from jax.sharding import PartitionSpec as P

import paddle_tpu.fluid as fluid
import paddle_tpu.fluid.executor as _executor
from paddle_tpu.fluid.executor import BlockPlan
from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.parallel.spmd import ShardedTrainStep, infer_param_specs


def test_gating_invariants():
    """Per-token combine weights sum to 1 with ample capacity; dispatch is
    0/1 with at most top_k slots per token; perfect-balance aux loss == 1."""
    import jax.numpy as jnp

    from paddle_tpu.parallel import moe

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.normal(size=(32, 8)).astype(np.float32))
    gate_w = jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))
    combine, dispatch, aux = moe.top_k_gating(x, gate_w, top_k=2,
                                              capacity_factor=4.0)
    per_token = np.asarray(combine.sum(axis=(1, 2)))
    np.testing.assert_allclose(per_token, np.ones(32), rtol=1e-5)
    d = np.asarray(dispatch)
    assert set(np.unique(d)) <= {0.0, 1.0}
    assert (d.sum(axis=(1, 2)) <= 2).all()
    assert float(aux) > 0.99  # >= 1 by Cauchy-Schwarz; 1 at perfect balance


def test_capacity_drops_overflow():
    from paddle_tpu.parallel import moe

    # all 16 tokens want expert 0 (gate heavily biased)
    import jax.numpy as jnp

    x = jnp.ones((16, 4), jnp.float32)
    gate_w = jnp.zeros((4, 2), jnp.float32).at[:, 0].set(10.0)
    combine, dispatch, _ = moe.top_k_gating(x, gate_w, top_k=1,
                                            capacity_factor=1.0)
    # capacity = ceil(16*1/2*1.0) = 8 -> exactly 8 tokens kept
    assert float(dispatch.sum()) == 8.0
    assert float(dispatch[:, 1].sum()) == 0.0  # nothing routed to expert 1


def _build_moe_model(seed=7):
    fluid.default_main_program().random_seed = seed
    fluid.default_startup_program().random_seed = seed
    img = fluid.layers.data(name="img", shape=[16], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    h = fluid.layers.fc(input=img, size=32, act="relu")
    moe_out, aux = fluid.layers.moe_ffn(h, num_experts=4, hidden_size=32,
                                        top_k=2, capacity_factor=2.0)
    h2 = fluid.layers.elementwise_add(h, moe_out)  # residual
    pred = fluid.layers.fc(input=h2, size=10, act="softmax")
    ce = fluid.layers.mean(fluid.layers.cross_entropy(input=pred,
                                                      label=label))
    loss = fluid.layers.elementwise_add(
        ce, fluid.layers.scale(aux, scale=0.01))
    fluid.optimizer.Adam(learning_rate=2e-3).minimize(loss)
    return loss


def test_moe_trains_single_device():
    loss = _build_moe_model()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(1)
    losses = []
    for _ in range(8):
        x = rng.normal(size=(32, 16)).astype(np.float32)
        y = (x[:, :1] > 0).astype(np.int64)
        (l,) = exe.run(fluid.default_main_program(),
                       feed={"img": x, "label": y}, fetch_list=[loss])
        losses.append(float(np.asarray(l).reshape(-1)[0]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_moe_ep_matches_executor():
    """dp2 x ep4: expert weights shard over "ep", loss curve must equal the
    single-device executor's (the SURVEY.md §4.4 oracle)."""
    loss = _build_moe_model()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = _executor._global_scope
    init = {k: np.asarray(scope.get(k)) for k in scope.keys()}

    rng = np.random.RandomState(2)
    data = []
    for _ in range(5):
        x = rng.normal(size=(16, 16)).astype(np.float32)
        data.append((x, (x[:, :1] > 0).astype(np.int64)))

    base = []
    for x, y in data:
        (l,) = exe.run(fluid.default_main_program(),
                       feed={"img": x, "label": y}, fetch_list=[loss])
        base.append(float(np.asarray(l).reshape(-1)[0]))
    assert np.isfinite(base).all()

    for k, v in init.items():
        scope.set(k, v)
    mesh = make_mesh(8, tp=4, axis_names=("dp", "ep"))
    step = ShardedTrainStep(fluid.default_main_program(), ["img", "label"],
                            [loss.name], mesh)
    ep_sharded = [n for n, s in step.specs.items()
                  if s is not None and "ep" in tuple(s)]
    assert ep_sharded, f"no var got ep-sharded; specs={step.specs}"
    # the w1/w2 expert weights AND their Adam moments must be ep-sharded
    assert sum(1 for n in ep_sharded if "moment" in n) >= 2, ep_sharded

    state = step.place_state()
    out = []
    for x, y in data:
        placed = step.place_feed({"img": x, "label": y})
        fetches, new_state = step(placed, state)
        state = {**state, **new_state}
        out.append(float(np.asarray(fetches[0]).reshape(-1)[0]))
    np.testing.assert_allclose(base, out, rtol=1e-3, atol=1e-3)


def test_moe_expert_param_specs():
    """infer_param_specs honors dist_hint="ep" for expert params and their
    accumulators; gate weight stays replicated (it is not an expert param)."""
    loss = _build_moe_model()
    prog = fluid.default_main_program()
    mesh = make_mesh(8, tp=4, axis_names=("dp", "ep"))
    plan = BlockPlan(prog, 0, ["img", "label"], [loss.name])
    specs = infer_param_specs(prog, plan, mesh)
    gb = prog.global_block()
    expert_params = [v.name for v in gb.vars.values()
                     if getattr(v, "dist_hint", None) == "ep"]
    assert len(expert_params) == 4  # w1, b1, w2, b2
    for n in expert_params:
        assert specs[n] is not None and tuple(specs[n])[0] == "ep", \
            (n, specs[n])


# == the routed share walks its sorted rows a slab at a time ================
# (``parallel/moe.py`` ``slab_rows``, ``_Slabs``, ``_walks``, ``_looped``): ``4 *
# held / routed`` of the ``N * top_k`` rows a trip of ONE ``lax.while_loop``
# a pass, one trip while the assignments to the experts held fit a slab,
# exact beyond it, and the one walk with no loop where that is every row.

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from paddle_tpu.ops import pallas_grouped  # noqa: E402
from paddle_tpu.parallel import moe  # noqa: E402

#: 512 tokens x 4 choices = 2,048 rows, 8 of 128 experts held from expert 8
#: on: a slab of 4 * 2,048 * 8 / 128 = 512 rows, ONE row tile, four of them
#: at most
TOKENS, ROUTED, HELD, TOP_K, OFFSET = 512, 128, 8, 4, 8
ROWS, SLAB = TOKENS * TOP_K, 512
#: the selection bias of each regime (``route_top_k``: it chooses and does
#: not weigh): ``(held experts every token takes, whether the other held
#: experts are shut, trips)``
REGIMES = {"under_one_slab": (0, False, 1), "exactly_one_slab": (1, True, 1),
           "two_trips": (1, False, 2), "three_trips": (3, True, 3),
           "every_assignment_held": (4, False, 4), "no_live_row": (0, True, 1)}


def slab_counters(prefix):
    return {k: v for k, v in fluid.profiler.counters().items()
            if k.startswith(prefix)}


def slab_operands(regime, width, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(TOKENS, width), jnp.float32)
    wr = jnp.asarray(rng.randn(width, ROUTED) / np.sqrt(width), jnp.float32)
    w1, w3, w2 = (jnp.asarray(rng.randn(HELD, width, width) / np.sqrt(width),
                              jnp.float32) for _ in range(3))
    taken, shut, trips = REGIMES[regime]
    bias = jnp.zeros(ROUTED, jnp.float32)
    if shut:
        bias = bias.at[OFFSET:OFFSET + HELD].set(-50.0)
    bias = bias.at[OFFSET:OFFSET + taken].set(50.0)
    mix = jnp.asarray(rng.randn(TOKENS, width), jnp.float32)
    return (x, wr, w1, w3, w2), dict(top_k=TOP_K, expert_offset=OFFSET,
                                     bias=bias), mix, trips


def unsorted_share(x, wr, w1, w3, w2, top_k, expert_offset, bias):
    """The share with nothing sorted and nothing grouped, in float32: every
    token through every held expert, weighted by what the router gave that
    expert (0 where it was not chosen), differentiated by jax."""
    vals, idx = moe.route_top_k(x, wr, top_k, bias=bias)
    high = jax.lax.Precision.HIGHEST
    y = 0.0
    for j in range(w1.shape[0]):
        weight = jnp.sum(jnp.where(idx == expert_offset + j, vals, 0.0), 1)
        hidden = jax.nn.silu(jnp.matmul(x, w1[j], precision=high)) \
            * jnp.matmul(x, w3[j], precision=high)
        y = y + weight[:, None] * jnp.matmul(hidden, w2[j], precision=high)
    return y


def value_and_cotangents(fn, args, kw, mix):
    """(the layer's result [N, D], its five cotangents under ``mix``)."""
    def mixed(*a):
        y = fn(*a, **kw)
        return jnp.sum(mix * y), y

    # bare: the regimes of one path differ in a bias alone, so dispatched
    # they share every primitive's executable after the first case; under
    # ``jax.jit`` each case compiles its two programs anew (the file 84 s
    # dispatched, 136 s compiled: PR 63)
    (_, y), grads = jax.value_and_grad(mixed, range(5), has_aux=True)(*args)
    return y, grads


#: a cell's routed layer: ``(N, top_k, held, routed)``
CELLS = {"trinity_mini": (6144, 8, 8, 128),
         "nemotron_twotower_30b_a3b": (8192, 6, 8, 128),
         "kimi_linear_48b_a3b": (2048, 8, 8, 256),
         "instella_moe_16b_a3b": (8192, 6, 8, 64),
         "qwen3_next_80b_a3b": (8192, 10, 16, 512)}
#: the cells in slabs whose router has no balancing bias
NO_BIAS = {"qwen3_next_80b_a3b"}


@pytest.mark.parametrize("n,top_k,held,routed,balanced,slab", [
    (*CELLS["trinity_mini"], True, 12288),  # 24 tiles of 49,152 rows
    (*CELLS["kimi_linear_48b_a3b"], True, 2048),    # of 16,384
    (*CELLS["qwen3_next_80b_a3b"], False, 20480),   # of 81,920
    # keye_vl_2_0_30b_a3b and sdar_30b_a3b_chat, the same shapes: every row
    (8192, 8, 16, 128, False, 65536),
    (8192, 4, 8, 32, True, 32768),      # lfm2_8b_a1b: a quarter held, all
    (*CELLS["instella_moe_16b_a3b"], True, 24576),  # of 49,152
    (8192, 8, 8, 64, False, 65536),     # mellum2_12b_a2_5b: every row
    (*CELLS["nemotron_twotower_30b_a3b"], True, 12288),     # of 49,152
], ids=["trinity", "kimi_linear", "qwen3_next", "keye_sdar", "lfm2",
        "instella", "mellum2", "nemotron"])
def test_the_slab_of_each_cell(n, top_k, held, routed, balanced, slab):
    """The rows a trip walks in the nine MoE cells, from ``(N, top_k, held,
    routed)`` and whether the router has a balancing bias: 4 times the even
    share under a bias, a slab in Trinity, Nemotron, Kimi-Linear and
    Instella and every row where a quarter of the experts or more is held;
    8 times the even share without one, since nothing pulls such a router
    back from the experts held (``slab_rows``' ``balanced``): a quarter of
    Qwen3-Next's 81,920 rows, every row where an eighth is held (Keye, SDAR,
    Mellum2)."""
    assert moe.slab_rows(n * top_k, held, routed, True, balanced) == slab
    assert slab % pallas_grouped.ROW_TILE == 0
    # all experts held: every row, with a bias or without
    assert moe.slab_rows(n * top_k, routed, routed, True, balanced) \
        == n * top_k


@pytest.mark.parametrize("rows,held,routed,kernels,balanced,slab", [
    (81920, 16, 512, True, True, 10240),    # Qwen3-Next's shapes under a
    (81920, 16, 512, True, False, 20480),   # bias would walk half as many
    (81920, 32, 512, True, False, 40960),   # twice the experts, twice
    (81920, 64, 512, True, False, 81920),   # an eighth held: every row
    (6, 1, 64, True, True, 6),      # fewer rows than a row tile: every row
    (6, 1, 64, True, False, 6),
    # XLA's grouped product takes any count: the even share's, rounded up
    (100, 1, 64, False, True, 7),
    (100, 1, 64, False, False, 13),
], ids=["biased", "unbiased", "twice_held", "eighth_held", "tiny",
        "tiny_unbiased", "xla", "xla_unbiased"])
def test_the_slab_by_the_rule(rows, held, routed, kernels, balanced, slab):
    """``slab_rows`` by its rule alone: ``SLAB_OVER_EVEN`` times the even
    share of the rows under a balancing bias and twice that without one, in
    whole row tiles where the kernels walk them, every row where that is no
    fewer."""
    assert moe.SLAB_OVER_EVEN == 4
    assert moe.slab_rows(rows, held, routed, kernels, balanced) == slab


#: the LARGEST live count a layer of the cell showed in one step of a timed
#: window on the chip: ``(as ISSUE 59 had it from PERF.md's accounts of PRs
#: 54, 56 and 58, which print a layer's first, median and last step; over
#: EVERY step of every window PR 59 ran, PERF.md section 6, PR 59)``
LARGEST = {"trinity_mini": (7646, 9776),
           "nemotron_twotower_30b_a3b": (6267, 10033),
           "kimi_linear_48b_a3b": (778, 951),
           "instella_moe_16b_a3b": (12288, 18361),
           # no window of PRs 54 to 59 walked this cell in slabs: the most
           # one layer read in a step of PR 67's pairs on the chip, at the
           # parent (every row walked, 124 steps a run) and in slabs (160
           # to 169 steps a run: its router has no bias and drifts on, the
           # fourth layer furthest, 92% of its slab; the mean of the four
           # layers is what the ledger's 10.57% at PR 66 was)
           "qwen3_next_80b_a3b": (15715, 18783)}


@pytest.mark.parametrize("read", ["before_pr_59", "in_pr_59", "one_over"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_largest_load_read_is_one_trip(monkeypatch, cell, read):
    """What ``SLAB_OVER_EVEN`` rests on, as numbers a change of it has to
    argue against: the LARGEST live count any layer of the five cells in
    slabs showed in a step of a window on the chip (``LARGEST``) is ONE
    trip of its layer, and one row over a slab is two.  Through the layer's
    own plan at the cell's ``(N, top_k, held, routed)``: a router that
    sends exactly so many assignments to the experts held, the products
    left out."""
    n, top_k, held, routed = CELLS[cell]
    bias = None if cell in NO_BIAS else jnp.zeros(routed, jnp.float32)
    slab = moe.slab_rows(n * top_k, held, routed, True, bias is not None)
    before, since = LARGEST[cell]
    assert max(before, since) <= slab < n * top_k
    live = {"before_pr_59": before, "in_pr_59": since,
            "one_over": slab + 1}[read]
    over = int(live > slab)
    # token i takes ``taken[i]`` held experts (the last ``held`` of the
    # routed) and its other choices from the first experts
    taken = np.clip(live - np.arange(n) * top_k, 0, top_k)
    width = max(256, routed)
    x = np.zeros((n, width), np.float32)
    choice = np.arange(top_k)[None, :]
    x[np.arange(n)[:, None], np.where(
        choice < taken[:, None], routed - held + choice, choice)] = 10.0
    plans = []
    slabs = moe._slabs
    monkeypatch.setattr(moe, "_slabs", lambda *a: (
        plans.append(slabs(*a)), plans[-1])[1])
    monkeypatch.setattr(moe, "_share", lambda top_k, xt, *a: xt)
    w = jnp.zeros((held, width, 128), jnp.float32)
    moe.routed_experts(jnp.asarray(x),
                       jnp.eye(width, routed, dtype=jnp.float32),
                       w, w, w.transpose(0, 2, 1), top_k=top_k,
                       expert_offset=routed - held, bias=bias)
    plan, = plans
    assert plan.order.shape == (n * top_k // slab, slab) and plan.kernels
    assert int(plan.live) == live
    assert int(plan.trips) == 1 + over
    walked = [int(plan.walk(jnp.int32(t))[4].sum()) for t in range(2)]
    assert walked == [min(live, slab), over]


@pytest.mark.parametrize("regime", sorted(REGIMES))
@pytest.mark.parametrize("path", ["ragged_dot", "pallas"])
def test_slabs_equal_the_one_walk_and_the_unsorted_share(monkeypatch, path,
                                                         regime):
    """Value and all five cotangents (x, router, w1, w3, w2) of the layer in
    slabs of 512 of its 2,048 rows against the one walk over all rows (the
    constant out of the way) and against the float32 share that sorts
    nothing, to the tolerance of the hand-written backward's own tests:
    live rows under one slab, exactly one slab, two to four trips up to
    every assignment held, and no live row; under a selection bias, with
    ``Counts``."""
    width = 128 if path == "pallas" else 16
    args, kw, mix, trips = slab_operands(regime, width)
    if path == "ragged_dot":
        monkeypatch.setattr(pallas_grouped, "supported", lambda *a: "off")
    assert moe.product_path(args[0], args[2], args[4], TOP_K) == path
    assert moe.slab_rows(ROWS, HELD, ROUTED, path == "pallas") == SLAB
    _, landed = moe.routed_experts(*args, **kw, with_counts=True)
    np.testing.assert_array_equal(landed, moe.assignment_counts(
        moe.route_top_k(args[0], args[1], TOP_K, bias=kw["bias"])[1], ROUTED))
    live = int(landed[OFFSET:OFFSET + HELD].sum())
    assert max(1, -(-live // SLAB)) == trips
    assert (live == SLAB) == (regime == "exactly_one_slab")
    assert (live == ROWS) == (regime == "every_assignment_held")
    assert (live == 0) == (regime == "no_live_row")

    got = value_and_cotangents(moe.routed_experts, args, kw, mix)
    want = value_and_cotangents(unsorted_share, args, kw, mix)
    monkeypatch.setattr(moe, "SLAB_OVER_EVEN", ROUTED)
    assert moe.slab_rows(ROWS, HELD, ROUTED, path == "pallas") == ROWS
    whole = value_and_cotangents(moe.routed_experts, args, kw, mix)
    for other in (whole, want):
        for name, g, r in zip("y x router w1 w3 w2".split(),
                              (got[0], *got[1]), (other[0], *other[1])):
            assert g.dtype == r.dtype == jnp.float32
            assert np.abs(g - r).max() <= 1e-5 * np.abs(r).max(), name
            assert bool(np.any(np.asarray(r))) == (live > 0), name


def test_a_tokens_choices_straddle_two_slabs():
    """Every token takes the held experts 8 and 9 and no other of the
    eight held: expert 8's 512 assignments are the first slab and expert
    9's the second, so EVERY token's sum over its choices, and its
    cotangent, is made of two trips' parts."""
    args, kw, mix, _ = slab_operands("exactly_one_slab", 16)
    kw["bias"] = kw["bias"].at[OFFSET + 1].set(50.0)
    _, idx = moe.route_top_k(args[0], args[1], TOP_K, bias=kw["bias"])
    took = np.asarray(idx)
    for expert in range(OFFSET, OFFSET + HELD):
        assert (took == expert).any(axis=1).all() == (expert < OFFSET + 2)
        assert (took == expert).any() == (expert < OFFSET + 2)
    assert moe.slab_rows(ROWS, HELD, ROUTED, False) == SLAB == TOKENS
    got = value_and_cotangents(moe.routed_experts, args, kw, mix)
    want = value_and_cotangents(unsorted_share, args, kw, mix)
    for name, g, r in zip("y x router w1 w3 w2".split(),
                          (got[0], *got[1]), (want[0], *want[1])):
        assert np.abs(g - r).max() <= 1e-5 * np.abs(r).max(), name
        assert np.any(np.asarray(r)), name
    # the experts 10 to 15 are held and chosen by no token
    assert not np.any(np.asarray(got[1][2][2:]))


def test_every_held_assignment_lies_in_one_slab_and_sizes_sum_to_it():
    """``_slabs`` alone, with values: a slab's sizes are the groups' rows
    clipped to it with what is left in the last group, its live rows the
    first ``live`` sorted rows, and home come only the assignments sorted
    into it, from their row inside it; the last slab runs past the rows
    there are."""
    rng = np.random.RandomState(3)
    e, slab, rows = 3, 8, 28
    group = jnp.asarray(rng.randint(0, e + 1, rows), jnp.int32)
    counts = jnp.bincount(group, length=e + 1).astype(jnp.int32)
    first = jnp.cumsum(counts, dtype=jnp.int32) - counts
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    back = jnp.argsort(order).astype(jnp.int32)
    held = (group < e).reshape(rows // 2, 2)
    plan = moe._slabs(held, first, order, back, jnp.int32(4), slab, False)
    assert plan.order.shape == (4, slab) and not plan.kernels
    assert plan.sizes.dtype == jnp.int32
    padded = np.asarray(plan.order).reshape(-1)
    np.testing.assert_array_equal(padded[:rows], order)
    live_rows, seen = int(first[e]), np.zeros(rows, int)
    for t in range(4):
        held_t, sizes, order_t, back_t, live, tables = plan.walk(jnp.int32(t))
        assert tables is None and int(sizes.sum()) == slab
        at = np.arange(t * slab, (t + 1) * slab)
        np.testing.assert_array_equal(np.asarray(live)[:, 0], at < live_rows)
        mine = np.asarray(group)[padded[at]]
        for g in range(e):
            assert int(sizes[g]) == int(np.sum(mine[at < live_rows] == g)) \
                + (int(np.sum(at >= live_rows)) if g == e - 1 else 0)
        flat = np.asarray(held_t).reshape(-1)
        seen += flat
        # an assignment that comes home reads the row it was sorted to
        np.testing.assert_array_equal(
            np.asarray(order_t)[np.asarray(back_t)[flat]],
            np.arange(rows)[flat])
    np.testing.assert_array_equal(seen, np.asarray(held).reshape(-1))


def equations(jaxpr, looped=False):
    """``(equation, whether it lies inside a ``while``)`` for every equation
    of a jaxpr and of its inner jaxprs, a kernel's own body left out (its
    chunk loop is no loop of the layer's)."""
    for eqn in jaxpr.eqns:
        yield eqn, looped
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from equations(
                        sub, looped or eqn.primitive.name == "while")


def loops(jaxpr):
    return [eqn for eqn, _ in equations(jaxpr)
            if eqn.primitive.name == "while"]


def kernel_calls(jaxpr, inside_loops):
    """How often each Pallas kernel stands in a jaxpr, by name, in or out
    of the layer's loops, and the rows its operands have."""
    calls, rows = {}, set()
    for eqn, looped in equations(jaxpr):
        if eqn.primitive.name == "pallas_call" and looped == inside_loops:
            calls[eqn.params["name"]] = calls.get(eqn.params["name"], 0) + 1
            rows |= {v.aval.shape[0] for v in eqn.invars if v.aval.ndim == 2}
    return calls, rows


@pytest.fixture
def fresh_traces():
    """The process's kept traces dropped before and after: a test that
    watches a walk being traced, or swaps a function under it, must not be
    served a trace an earlier test made (``moe._looped``,
    ``pallas_grouped._matmul``: inlined ``jax.jit``s)."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("weighted", [False, True],
                         ids=["backward", "forward"])
@pytest.mark.parametrize("top_k", [1, 2, 6])
def test_the_choices_come_home_summed(top_k, weighted, dtype):
    """``moe._choices_home``, what a loop's body sums a token's choices
    with, a gather of ``[N, D]`` a choice: the sum over the choices of
    ``_rows_home``'s ``[N, top_k, D]`` rows, as the one walk takes it in
    either pass (weighted by the gate forward, in float32), an absent
    assignment's row selected away whatever lies in it; and no value it
    makes has a row for every assignment."""
    n, d = 24, 16
    rng = np.random.RandomState(top_k)
    rows = jnp.asarray(rng.randn(n * top_k, d), dtype)
    back = jnp.asarray(rng.permutation(n * top_k), jnp.int32)
    held = jnp.asarray(rng.rand(n, top_k) < 0.6)
    # what no held assignment wrote may be anything
    live = np.zeros(n * top_k, bool)
    live[np.asarray(back)[np.asarray(held).reshape(-1)]] = True
    rows = jnp.where(live[:, None], rows, jnp.nan)
    gate = jnp.asarray(rng.rand(n, top_k), jnp.float32) if weighted else None
    home = moe._rows_home(rows, back, held, "forward")
    if weighted:
        want = jnp.einsum("nk,nkd->nd", gate, home.astype(jnp.float32))
    else:
        want = jnp.sum(home, axis=1, dtype=jnp.float32)

    def summed(rows):
        return moe._choices_home(rows, back, held, "forward", jnp.float32,
                                 gate)

    got = summed(rows)
    assert got.dtype == jnp.float32 and got.shape == (n, d)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert not any(v.aval.shape[:1] == (n * top_k,) or
                   v.aval.shape[:2] == (n, top_k) and v.aval.ndim == 3
                   and v.aval.shape[2] == d
                   for eqn, _ in equations(jax.make_jaxpr(summed)(rows).jaxpr)
                   for v in eqn.outvars if top_k > 1)


@pytest.mark.parametrize("regime", ["under_one_slab", "three_trips"])
def test_no_body_holds_a_row_for_every_assignment(fresh_traces, regime):
    """In the bodies of the layer's two loops no value has the ``N * top_k``
    rows of every assignment and a layer's width beside them, as
    ``[N * top_k, D]`` or ``[N, top_k, D]``: the rows come home a choice at
    a time (``moe._choices_home``).  That array was a body's fullest point,
    335 MB in Qwen3-Next's, and with its loops in the step the step's."""
    width = 128
    args, kw, mix, _ = slab_operands(regime, width)

    def layer(*a):
        return jnp.sum(mix * moe.routed_experts(*a, **kw))

    both = jax.make_jaxpr(jax.grad(layer, range(5)))(*args).jaxpr
    assert len(loops(both)) == 2
    wide = [v.aval.shape for eqn, looped in equations(both) if looped
            for v in eqn.outvars
            if v.aval.shape in ((TOKENS * TOP_K, width),
                                (TOKENS, TOP_K, width))]
    assert wide == []


@pytest.mark.parametrize("regime", ["under_one_slab", "three_trips"])
def test_a_pass_traces_its_walk_once(monkeypatch, fresh_traces, regime):
    """Whatever the trips, the first lowering of the layer and its backward
    traces the walk ONCE a pass, as the one walk over all rows is traced:
    two row gathers forward and three backward, 8 ``grouped_matmul`` and 3
    ``grouped_matmul_t`` with their column tiles counted once each, all in
    the bodies of the two loops (one a pass), over the slab's rows
    (``test_every_row_in_one_slab_lowers_to_the_parents_text`` counts the
    ``while``s of a lowered text, where no kernel is interpreted).  And a
    PROCESS traces it once: the backward's forward, the next layer of equal
    operand types and the next program are served the kept trace
    (``moe._looped``), lower their own copy of it, and count what it
    counted."""
    args, kw, mix, _ = slab_operands(regime, 128)
    assert moe.product_path(args[0], args[2], args[4], TOP_K) == "pallas"
    walks = []
    walk = moe._sorted_and_hidden
    monkeypatch.setattr(moe, "_sorted_and_hidden", lambda *a: (
        walks.append(a[-1]), walk(*a))[1])

    def layer(*a):
        return jnp.sum(mix * moe.routed_experts(*a, **kw))

    forward = jax.make_jaxpr(layer)(*args).jaxpr
    assert walks == ["forward"]
    assert kernel_calls(forward, True) == ({"grouped_matmul": 3}, {SLAB})
    assert len(loops(forward)) == 1
    assert slab_counters("ops.moe.row_moves") == {
        'ops.moe.row_moves{how="gather",pass="forward"}': 2}
    tiles = sum(slab_counters("ops.moe.column_tiles").values())
    assert tiles == 3
    for lowering in (1, 2):
        del walks[:]
        both = jax.make_jaxpr(jax.grad(layer, range(5)))(*args).jaxpr
        # the forward's walk is the kept one; so is the backward's the
        # second time
        assert walks == (["backward"] if lowering == 1 else [])
        assert kernel_calls(both, True) == (
            {"grouped_matmul": 8, "grouped_matmul_t": 3}, {SLAB})
        assert kernel_calls(both, False) == ({}, set())
        assert len(loops(both)) == 2
        assert slab_counters("ops.moe.row_moves") == {
            'ops.moe.row_moves{how="gather",pass="forward"}': 2 + 2 * lowering,
            'ops.moe.row_moves{how="gather",pass="backward"}': 3 * lowering}
        assert sum(slab_counters("ops.moe.column_tiles").values()
                   ) == 3 + 11 * lowering
    # a layer of other operand types is another trace
    del walks[:]
    jax.make_jaxpr(layer)(args[0].astype(jnp.bfloat16), *args[1:])
    assert walks == ["forward"]


def test_a_kept_trace_lowers_to_the_text_of_a_fresh_one(fresh_traces):
    """What a call served by the kept trace lowers to is what the call
    that made the trace lowered to, character for character, and two
    layers of one program each hold their own loops."""
    args, kw, mix, _ = slab_operands("two_trips", 64)

    def layers(*a):
        y = moe.routed_experts(*a, **kw)
        return jnp.sum(mix * moe.routed_experts(y, *a[1:], **kw))

    def step():
        return jax.jit(jax.value_and_grad(layers, range(5)))

    fresh = step().lower(*args).as_text()
    assert fresh.count("stablehlo.while") == 4
    assert step().lower(*args).as_text() == fresh


def all_rows_share(x, router_w, w1, w3, w2, top_k, expert_offset=0,
                   norm_topk=True, score="softmax", bias=None, norm_eps=0.0,
                   scale=1.0, with_counts=False):
    """THE PARENT'S FORMULA of ``moe.routed_experts``, kept: the plan of one
    walk over all ``N * top_k`` rows as it stood before the layer walked in
    slabs (the absent experts' assignments as zero rows at the end of the
    last held group), its three gauges, handed to ``moe._share`` as it
    is."""
    shape = x.shape
    e = w1.shape[0]
    xt = x.reshape((-1, shape[-1]))
    n = xt.shape[0]
    vals, idx = moe.route_top_k(xt, router_w, top_k, norm_topk, score, bias,
                                norm_eps, scale)
    local = idx - jnp.int32(expert_offset)
    held = (local >= 0) & (local < e)
    group = jnp.where(held, local, e).reshape(-1)
    i32 = jnp.int32
    chose = (group[:, None] == jnp.arange(e + 1, dtype=i32)).astype(i32)
    counts = jnp.sum(chose, axis=0, dtype=i32)
    first = jnp.cumsum(counts, dtype=i32) - counts
    back = jnp.sum(chose * (first[None, :] - 1
                            + jnp.cumsum(chose, axis=0, dtype=i32)),
                   axis=1, dtype=i32)
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    live = (jnp.arange(order.shape[0], dtype=i32) < first[e])[:, None]
    sizes = counts[:e].at[e - 1].add(counts[e])
    moe._publish_load(first[e], n * top_k, jnp.max(counts[:e]))
    gate = jnp.where(held, vals, 0.0)
    tables = None
    if moe.product_path(x, w1, w2, top_k) == "pallas":
        tables = pallas_grouped.plan(sizes, n * top_k)
    y = moe._share(top_k, xt, gate, w1, w3, w2,
                   (held, sizes, order, back, live, tables))
    y = y.astype(x.dtype).reshape(shape)
    if with_counts:
        return y, moe.assignment_counts(idx, router_w.shape[-1])
    return y


@pytest.mark.parametrize("program", ["decoder", "decoder_with_bias",
                                     "no_routed_layer"])
def test_a_step_with_every_row_in_one_slab_lowers_to_the_parents(
        monkeypatch, program):
    """Whole training steps, lowered through ``Executor.lower_step``: the
    tiny decoder (4 of 8 experts held, softmax router) and the same with a
    sigmoid router under a balancing bias lower, with the gauges in the
    step, to the text they lower to with the parent's formula in the
    layer's place, and hold no ``while``; a program with no routed layer
    never reaches the layer."""
    from paddle_tpu.fluid import layers
    from paddle_tpu.models import decoder_lm

    if program == "no_routed_layer":
        x = layers.data(name="x", shape=[16, 32], dtype="float32")
        loss = layers.mean(layers.square(layers.fc(x, size=32)))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        feed = {"x": np.random.RandomState(0).randn(2, 16, 32)
                .astype("float32")}
    else:
        cfg = decoder_lm.tiny_config()
        if program == "decoder_with_bias":
            cfg.router_score, cfg.route_bias_coeff = "sigmoid", 1e-3
        assert moe.SLAB_OVER_EVEN * cfg.experts_held >= cfg.num_routed
        _, _, loss = decoder_lm.build(cfg, seq_len=16)
        ids = np.random.RandomState(0).randint(
            0, cfg.vocab_size, size=(1, 17)).astype(np.int64)
        feed = {"tokens": ids[:, :-1], "labels": ids[:, 1:, None]}
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    main = fluid.default_main_program()
    mine = exe.lower_step(main, feed, [loss]).as_text()
    called = []
    monkeypatch.setattr(moe, "routed_experts", lambda *a, **kw: (
        called.append(1), all_rows_share(*a, **kw))[1])
    parents = exe.lower_step(main, feed, [loss]).as_text()
    assert mine == parents
    assert bool(called) == (program != "no_routed_layer")
    assert "stablehlo.while" not in mine


def small_layer(n, held, routed, width):
    """``(x, router_w, w1, w3, w2)`` of a layer of ``n`` tokens, the
    operands of the benchmark's own slab test (``tests/chipbench/
    test_decoder_steps_lower_alike.py``)."""
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(n, width), jnp.float32)
    wr = jnp.asarray(rng.randn(width, routed), jnp.float32)
    return (x, wr, *(jnp.asarray(0.2 * rng.randn(held, width, width),
                                 jnp.float32) for _ in range(3)))


@pytest.mark.parametrize("held,routed,offset,width,bias", [
    (16, 128, 8, 16, True), (8, 8, 0, 16, True), (4, 16, 4, 128, True),
    (16, 128, 8, 16, False), (2, 128, 8, 16, False), (2, 128, 8, 16, True),
    (32, 128, 8, 16, True)])
def test_every_row_in_one_slab_lowers_to_the_parents_text(held, routed,
                                                          offset, width,
                                                          bias):
    """A quarter of the experts held or more (all of them; a quarter, on
    the kernels and on XLA's product), or an eighth under a router with no
    balancing bias (Keye's, SDAR's and Mellum2's share): the slab is every
    row, the decision is static, no ``while`` is lowered in either pass and
    the text of the layer and its backward equals, byte for byte, the text
    of the parent's formula kept above.  Two of 128 held, with a bias or
    without, or an eighth of them under a bias: the same layer in slabs
    lowers another text, with one ``while`` a pass."""
    n, k = 256, 2
    x, wr, w1, w3, w2 = small_layer(n, held, routed, width)
    kw = dict(top_k=k, expert_offset=offset,
              bias=jnp.zeros(routed, jnp.float32) if bias else None)
    kernels = moe.product_path(x, w1, w2, k) == "pallas"
    assert kernels == (width == 128)
    every_row = (4 if bias else 8) * held >= routed
    assert (moe.slab_rows(n * k, held, routed, kernels, bias) == n * k) \
        == every_row

    def lowered(share):
        return jax.jit(jax.grad(lambda *a: jnp.sum(share(*a, **kw) ** 2),
                                range(5))).lower(x, wr, w1, w3, w2).as_text()

    mine, parents = lowered(moe.routed_experts), lowered(all_rows_share)
    assert (mine == parents) == every_row
    assert mine.count("stablehlo.while") \
        == parents.count("stablehlo.while") + (0 if every_row else 2)
    if not kernels:
        assert "stablehlo.while" not in parents


def test_a_layer_in_slabs_wires_its_backward_body_through_the_barrier():
    """The twin of the benchmark's ``test_a_layer_in_slabs_lowers_both_
    passes_to_the_text_before`` (``tests/chipbench``), on its operands: 2 of
    128 experts under a bias, 256 tokens, both passes on XLA's grouped
    product.  That one holds a digest of the lowered text, which carries
    the slab's SIZE; this one holds what the digest is there to guard,
    from the jaxpr, whatever the size: one loop a pass, and in the
    backward's body the three weights' gradients are made BEFORE the one
    barrier and go through it with the hidden products' cotangents, and
    the rows' cotangents are products of the barrier's RESULTS: nothing
    behind the barrier reads a cotangent from before it (with that wiring
    lost the step still computed the same and Trinity's read 0.4% faster
    and 65 MB smaller on the chip, PERF.md section 6, PR 58: not what the
    parent lowers)."""
    n, k, held, routed, width = 256, 2, 2, 128, 16
    x, wr, w1, w3, w2 = small_layer(n, held, routed, width)
    bias = jnp.zeros(routed, jnp.float32)
    path, rows, slab = moe.walk_of(x, wr, w1, w2, k, bias)
    assert path == "ragged_dot" and slab < rows == n * k

    def loss(x, wr, w1, w3, w2):
        return jnp.sum(moe.routed_experts(
            x, wr, w1, w3, w2, top_k=k, expert_offset=8, bias=bias) ** 2)

    grad = jax.grad(loss, range(5))
    assert jax.jit(grad).lower(x, wr, w1, w3, w2).as_text().count(
        "stablehlo.while") == 2
    forward, backward = loops(jax.make_jaxpr(grad)(x, wr, w1, w3, w2).jaxpr)
    assert not [e for e, _ in equations(forward.params["body_jaxpr"].jaxpr)
                if e.primitive.name == "optimization_barrier"]
    body = backward.params["body_jaxpr"].jaxpr
    at, = (i for i, e in enumerate(body.eqns)
           if e.primitive.name == "optimization_barrier")
    barrier = body.eqns[at]
    shapes = [v.aval.shape for v in barrier.invars]
    assert shapes == [(slab, width)] * 2 + [(held, width, width)] * 3
    made_by = {v: e for e in body.eqns[:at] for v in e.outvars}
    for gradient in barrier.invars[2:]:
        # a weights' gradient: rows x rows -> [E, D, F], before the barrier
        product = made_by[gradient]
        assert product.primitive.name == "ragged_dot_general"
        assert [v.aval.shape for v in product.invars[:2]] \
            == [(slab, width)] * 2
    behind = body.eqns[at + 1:]

    def readers_of(var):
        return [e for e in behind if any(v is var for v in e.invars)]

    for cotangent in barrier.invars[:2]:
        assert not readers_of(cotangent)
    for cotangent in barrier.outvars[:2]:
        # the rows' cotangents: a barrier's result x weights -> [slab, D]
        readers = readers_of(cotangent)
        assert readers and all(
            e.primitive.name == "ragged_dot_general"
            and e.outvars[0].aval.shape == (slab, width) for e in readers)
    # and the trips' sums of the weights' gradients add the barrier's
    for gradient in barrier.outvars[2:]:
        reader, = readers_of(gradient)
        assert reader.primitive.name == "add"
        assert any(v is reader.outvars[0] for v in body.outvars)


def test_under_amp_a_weights_gradient_is_summed_in_amps_type(monkeypatch):
    """What the loops carry under the cells' AMP: the rows' sums (the
    result, the tokens' and the gate's cotangents) in float32, the three
    weights' gradients in bfloat16, the type their products hand them over
    in and the one walk keeps them in until the optimizer reads them; no
    carry is weakly typed (the body would be traced again to promote it);
    at three trips every cotangent stays within bfloat16's rounding of the
    one walk's."""
    from paddle_tpu.fluid import amp

    args, kw, mix, trips = slab_operands("three_trips", 128)
    args = (args[0].astype(jnp.bfloat16),) + args[1:]

    def layer(*a):
        return jnp.sum(mix * moe.routed_experts(*a, **kw))

    amp.enable("bfloat16", keep_activations=True)
    try:
        jaxpr = jax.make_jaxpr(jax.grad(layer, range(5)))(*args).jaxpr
        got = jax.jit(jax.grad(layer, range(5)))(*args)
        monkeypatch.setattr(moe, "SLAB_OVER_EVEN", ROUTED)
        whole = jax.jit(jax.grad(layer, range(5)))(*args)
    finally:
        amp.disable()
    forward, backward = loops(jaxpr)
    assert not any(v.aval.weak_type for loop in (forward, backward)
                   for v in loop.outvars)
    carried = [[(v.aval.shape, str(v.aval.dtype)) for v in loop.outvars
                if v.aval.ndim >= 2] for loop in (forward, backward)]
    assert carried[0] == [((TOKENS, 128), "float32")]
    assert carried[1] == [((TOKENS, 128), "float32"),
                          ((TOKENS, TOP_K), "float32")] + \
        [((HELD, 128, 128), "bfloat16")] * 3
    for name, g, r in zip("x router w1 w3 w2".split(), got, whole):
        assert g.dtype == r.dtype
        g, r = (np.asarray(v, np.float32) for v in (g, r))
        assert np.abs(g - r).max() <= 2.0 ** -6 * np.abs(r).max(), name
        assert np.any(r), name
