"""Step gauges (``paddle_tpu/observe/gauges.py``): a device value an op's
lowering publishes leaves the compiled step unfetched and is read late.  The
first publisher is the routed expert layer (``parallel/moe.py``)."""

import threading

import jax
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observe
from paddle_tpu.fluid import executor, framework, layers
from paddle_tpu.observe import gauges

TOKENS, WIDTH, ROUTED, HELD, OFFSET, TOP_K = 16, 32, 8, 4, 2, 2
LAYERS = ("layer0.ffn", "layer1.ffn")


def routed_program(train=True, counts=False):
    """Two routed layers under their name scopes; ``counts``: the layers'
    own ``Counts`` outputs too (``assignment_counts`` of the step)."""
    x = layers.data(name="x", shape=[TOKENS, WIDTH], dtype="float32")
    h, fetched = x, []
    for i in range(2):
        with fluid.name_scope(f"layer{i}"), fluid.name_scope("ffn"):
            out = layers.moe_experts(
                h, num_routed=ROUTED, experts_held=HELD, hidden_size=WIDTH,
                top_k=TOP_K, expert_offset=OFFSET, name=f"moe{i}",
                select_bias=counts)
            if counts:
                out, _, c = out
                fetched.append(c)
            h = h + out
    loss = layers.mean(layers.square(h))
    if train:
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return loss, fetched


def feed_of(seed, batch=2, steps=None):
    shape = (batch, TOKENS, WIDTH) if steps is None else \
        (steps, batch, TOKENS, WIDTH)
    return {"x": np.random.RandomState(seed).randn(*shape)
            .astype("float32")}


def started():
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    return exe


def dropped():
    return {k: v for k, v in fluid.profiler.counters().items()
            if k.startswith(gauges.DROPPED)}


def of(values, name, scope):
    return values[f'{name}{{scope="{scope}"}}']


def test_gauges_equal_the_assignment_counts_step_by_step():
    loss, counts = routed_program(counts=True)
    exe = started()
    want = []
    for seed in (1, 2, 1):
        out = exe.run(framework.default_main_program(), feed=feed_of(seed),
                      fetch_list=[loss] + counts)
        want.append([c[OFFSET:OFFSET + HELD] for c in out[1:]])
    got = observe.step_gauges(wait=True)
    # the index of the step is the process's, whatever ran before
    assert [e.step - got[0].step for e in got] == [0, 1, 2]
    assert len({e.span_id for e in got}) == 3
    assert got[0].t < got[1].t < got[2].t
    for entry, held in zip(got, want):
        assert len(entry.values) == 3 * len(LAYERS)
        for scope, mine in zip(LAYERS, held):
            assert of(entry.values, "ops.moe.live_rows", scope) == mine.sum()
            assert of(entry.values, "ops.moe.fullest_group",
                      scope) == mine.max()
            assert of(entry.values, "ops.moe.rows",
                      scope) == 2 * TOKENS * TOP_K
    # two different feeds read differently, the same feed after two steps
    # of training is another step all the same
    assert got[0].values != got[1].values
    # the newest value of each gauge is a gauge of THE registry
    flat = observe.registry().flat()
    assert {k: flat[k] for k in got[-1].values} == got[-1].values
    assert observe.step_gauges(since=got[1].t) == got[1:]
    assert not dropped()


#: a routed layer that walks in slabs: 2 x 256 tokens x top-2 = 1,024 rows,
#: 2 of 32 experts held, so ``moe.slab_rows`` gives 4 * 1,024 * 2 / 32 = 256
SLAB_TOKENS, SLAB_ROUTED, SLAB_HELD, SLAB = 256, 32, 2, 256


def slabbed_program():
    x = layers.data(name="x", shape=[SLAB_TOKENS, WIDTH], dtype="float32")
    with fluid.name_scope("layer0"), fluid.name_scope("ffn"):
        out, _, counts = layers.moe_experts(
            x, num_routed=SLAB_ROUTED, experts_held=SLAB_HELD,
            hidden_size=WIDTH, top_k=TOP_K, expert_offset=OFFSET, name="moe",
            select_bias=True)
    loss = layers.mean(layers.square(x + out))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return loss, counts


def slabbed_feed(drifted, batch=2, steps=None):
    """``drifted``: how many of a sequence's 256 tokens carry the feature
    that ``drift_the_router`` turns toward the two experts held."""
    shape = (batch, SLAB_TOKENS, WIDTH) if steps is None else \
        (steps, batch, SLAB_TOKENS, WIDTH)
    x = np.random.RandomState(3).randn(*shape).astype("float32")
    x[..., 0] = 0.0
    x[..., :drifted, 0] = 1.0
    return {"x": x}


def drift_the_router():
    scope = executor.global_scope()
    w = np.array(scope.get("moe_router_w"))
    w[0, OFFSET:OFFSET + SLAB_HELD] = 60.0
    scope.set("moe_router_w", w)


def test_rows_are_the_rows_walked_and_the_trips_are_gauged():
    """Through ``Executor.run``: ``ops.moe.rows`` is ``slab * trips`` and
    ``ops.moe.slab_trips`` the trips of the layer's forward loop, 1 while
    the assignments to the experts held fit a slab and 3 for a feed that
    sends 144 tokens of every 256 to both of them; the static label
    ``slab`` of ``ops.moe.calls`` is the slab's rows."""
    from paddle_tpu.parallel import moe

    rows = 2 * SLAB_TOKENS * TOP_K
    assert moe.slab_rows(rows, SLAB_HELD, SLAB_ROUTED, False) == SLAB < rows
    loss, counts = slabbed_program()
    exe = started()
    drift_the_router()
    for drifted, trips in ((0, 1), (144, 3)):
        _, landed = exe.run(framework.default_main_program(),
                            feed=slabbed_feed(drifted),
                            fetch_list=[loss, counts])
        live = int(landed[OFFSET:OFFSET + SLAB_HELD].sum())
        assert max(1, -(-live // SLAB)) == trips
        entry = observe.step_gauges(wait=True)[-1]
        assert len(entry.values) == 4
        assert of(entry.values, "ops.moe.live_rows", "layer0.ffn") == live
        assert of(entry.values, "ops.moe.rows", "layer0.ffn") == trips * SLAB
        assert of(entry.values, "ops.moe.slab_trips", "layer0.ffn") == trips
    calls = {k: v for k, v in fluid.profiler.counters().items()
             if k.startswith("ops.moe.calls")}
    assert calls == {
        f'ops.moe.calls{{held="{SLAB_HELD}",path="ragged_dot",'
        f'routed="{SLAB_ROUTED}",slab="{SLAB}"}}': 2}
    assert not dropped()


def test_a_program_without_a_routed_layer_lowers_as_without_the_collector(
        monkeypatch):
    x = layers.data(name="x", shape=[TOKENS, WIDTH], dtype="float32")
    loss = layers.mean(layers.square(layers.fc(x, size=WIDTH)))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = started()
    main, feed = framework.default_main_program(), feed_of(1)
    out = exe.run(main, feed=feed, fetch_list=[loss])
    assert len(out) == 1 and observe.step_gauges(wait=True) == []
    assert gauges.in_flight() == 0
    with_collector = exe.lower_step(main, feed, [loss]).as_text()
    traced = executor.trace_block
    monkeypatch.setattr(
        executor, "trace_block",
        lambda *a, gauges=None, **kw: traced(*a, **kw))
    assert exe.lower_step(main, feed, [loss]).as_text() == with_collector


def test_the_routed_step_has_one_more_output_and_nothing_else(monkeypatch):
    loss, _ = routed_program()
    exe = started()
    main, feed = framework.default_main_program(), feed_of(1)
    mine = exe.lower_step(main, feed, [loss])
    traced = executor.trace_block
    monkeypatch.setattr(
        executor, "trace_block",
        lambda *a, gauges=None, **kw: traced(*a, **kw))
    bare = exe.lower_step(main, feed, [loss])
    shapes = [str(s.shape) for s in jax.tree_util.tree_leaves(mine.out_info)]
    bare_shapes = [str(s.shape)
                   for s in jax.tree_util.tree_leaves(bare.out_info)]
    assert shapes == bare_shapes + [str((3 * len(LAYERS),))]


def test_the_grad_ops_second_trace_publishes_nothing_and_leaks_nothing():
    loss, _ = routed_program()
    exe = started()
    with jax.checking_leaks():
        exe.run(framework.default_main_program(), feed=feed_of(1),
                fetch_list=[loss])
    # the generic vjp traced ``routed_experts`` a second time a layer
    calls = sum(v for k, v in fluid.profiler.counters().items()
                if k.startswith("ops.moe.calls"))
    assert calls == 2 * len(LAYERS)
    entry, = observe.step_gauges(wait=True)
    assert sorted(entry.values) == sorted(
        f'{name}{{scope="{scope}"}}' for scope in LAYERS
        for name in ("ops.moe.live_rows", "ops.moe.rows",
                     "ops.moe.fullest_group"))
    assert not dropped()


def test_a_value_of_an_inner_trace_is_declined():
    seen = []

    def inner(v):
        observe.step_gauge("inner", v)
        return v * 2

    collector = gauges.Collector()

    @jax.jit
    def fn(v):
        with collector.op("here"):
            observe.step_gauge("outer", v)
            jax.vjp(inner, v)
            jax.lax.scan(lambda c, x: (inner(c), x), v, None, length=2)
        seen.append(collector.finish()[1])
        return v

    with jax.checking_leaks():
        fn(np.float32(1.0))
    layout, = seen
    assert layout.keys == ('outer{scope="here"}',)
    assert dropped() == {gauges.DROPPED + '{path="inner_trace"}': 2}
    observe.step_gauge("nobody listens", 1.0)     # no collector: nothing


def test_the_dispatch_path_reads_nothing(monkeypatch):
    """No step has "retired" (the reader's host copy is held back) and the
    calling thread may not read a device array: the vectors stay arrays."""
    loss, _ = routed_program()
    exe = started()
    main = framework.default_main_program()
    feed = {k: jax.numpy.asarray(v) for k, v in feed_of(1).items()}
    exe.run(main, feed=feed, fetch_list=[loss])       # compiled, and read
    assert len(observe.step_gauges(wait=True)) == 1

    retired, real = threading.Event(), np.asarray
    caller = threading.current_thread()

    def held_back(vector):
        retired.wait(30)
        return real(vector)

    def refusing(a, *args, **kw):
        if isinstance(a, jax.Array) and threading.current_thread() is caller:
            raise AssertionError("np.asarray of a device array on the "
                                 "dispatch path")
        return real(a, *args, **kw)

    def never(*a, **kw):
        raise AssertionError("the dispatch path waited for the device")

    monkeypatch.setattr(gauges, "_to_host", held_back)
    with monkeypatch.context() as m:
        m.setattr(np, "asarray", refusing)
        m.setattr(jax, "block_until_ready", never)
        m.setattr(jax.Array, "block_until_ready", never, raising=False)
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss], return_numpy=False)
    assert gauges.in_flight() == 3
    with gauges._cv:
        assert all(isinstance(e[3], jax.Array) for e in gauges._flight)
    assert len(observe.step_gauges()) == 1            # does not wait
    retired.set()
    got = observe.step_gauges(wait=True)
    assert [e.step - got[0].step for e in got] == [0, 1, 2, 3]
    assert gauges.in_flight() == 0


def test_the_queues_are_bounded_and_reset_clears_them(monkeypatch):
    layout = gauges.Layout([("g", (), 0, 1, False)])
    retired = threading.Event()
    monkeypatch.setattr(gauges, "_to_host",
                        lambda v: (retired.wait(30), np.asarray(v))[1])
    for i in range(gauges.IN_FLIGHT + 5):
        gauges.keep(i, None, float(i), np.float32([i]), layout)
    assert gauges.in_flight() == gauges.IN_FLIGHT
    assert dropped() == {gauges.DROPPED + '{path="overrun"}': 5}
    retired.set()
    got = observe.step_gauges(wait=True)
    # the oldest QUEUED entries went; the one the reader had already taken
    # (the first, where its thread was waiting for it) could not
    steps = [e.step for e in got]
    assert steps[1:] == list(range(6, gauges.IN_FLIGHT + 5))
    assert steps[0] in (0, 5)
    for i in range(gauges.RING_ENTRIES + 3):
        gauges.keep(i, None, float(i), np.float32([i]), layout)
        if i % gauges.IN_FLIGHT == 0:
            observe.step_gauges(wait=True)
    got = observe.step_gauges(wait=True)
    assert len(got) == gauges.RING_ENTRIES
    assert got[-1].values == {"g": float(gauges.RING_ENTRIES + 2)}
    observe.reset()
    assert observe.step_gauges(wait=True) == [] and gauges.in_flight() == 0


def test_an_array_gauge_takes_an_index_and_a_twin_a_call_label():
    collector = gauges.Collector()
    with collector.op("s"):
        observe.step_gauge("a", np.float32([1, 2]), kind="k")
        observe.step_gauge("b", 3)
        observe.step_gauge("b", 4)
        observe.step_gauge("long", np.zeros(gauges.MAX_LENGTH + 1))
    vector, layout = collector.finish()
    assert layout.keys == ('a{i="0",kind="k",scope="s"}',
                           'a{i="1",kind="k",scope="s"}', 'b{scope="s"}',
                           'b{call="2",scope="s"}')
    assert np.asarray(vector).tolist() == [1.0, 2.0, 3.0, 4.0]
    # what was published as an integer is one again on the host
    got = layout.values(np.asarray(vector))
    assert [type(v) for v in got.values()] == [float, float, int, int]
    assert dropped() == {gauges.DROPPED + '{path="too_long"}': 1}
    assert gauges.Collector().finish() == (None, None)


@pytest.mark.parametrize("walk", ["every_row", "slabs"])
@pytest.mark.parametrize("entry", ["run", "run_steps", "sharded_step",
                                   "sharded_window"])
def test_every_entry_point_runs_the_routed_program(entry, walk):
    """``Executor.run`` carries the vector; the three other entry points
    install a collector that only counts what it leaves behind, once a
    lowering.  None breaks and none leaks a tracer, with the layer's loops
    over its slabs in the program (three trips a step: a ``while`` inside
    ``run_steps``' scan and under the sharded entry points) as without."""
    if walk == "slabs":
        loss, _ = slabbed_program()

        def feed_of(seed, batch=2, steps=None):
            return slabbed_feed(144, batch, steps)
    else:
        loss, _ = routed_program()
        feed_of = globals()["feed_of"]
    exe = started()
    if walk == "slabs":
        drift_the_router()
    main = framework.default_main_program()
    with jax.checking_leaks():
        if entry == "run":
            out = exe.run(main, feed=feed_of(1), fetch_list=[loss])
        elif entry == "run_steps":
            out = exe.run_steps(main, feed_of(1, steps=2), [loss], 2,
                                feed_per_step=True)
        else:
            pe = fluid.ParallelExecutor(loss_name=loss.name,
                                        main_program=main)
            batch = pe.device_count
            if entry == "sharded_step":
                out = pe.run([loss.name], feed=feed_of(1, batch=batch))
            else:
                out = pe.run_steps([loss.name],
                                   feed=feed_of(1, batch=batch, steps=2),
                                   n_steps=2, feed_per_step=True)
    assert np.isfinite(np.asarray(out[0])).all()
    got = observe.step_gauges(wait=True)
    if entry == "run":
        assert len(got) == 1 and not dropped()
    else:
        assert got == []
        assert dropped() == {gauges.DROPPED + f'{{path="{entry}"}}': 1}
