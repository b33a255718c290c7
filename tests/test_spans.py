"""ISSUE 27: one span primitive on the profiler's clock.

Oracles:
 - the primitive: a span is ALWAYS returned (no sink, ``PADDLE_TRACE=0``),
   lands in the bounded ring with its parent id, thread and step, and in
   the host plane of an open ``jax.profiler`` session with its attributes;
   ``observe.reset()`` clears the ring;
 - a span never syncs: a ``run_steps`` window under a sink passes with
   ``jax.block_until_ready`` patched to raise;
 - ``Executor.run`` and ``ParallelExecutor.run`` (four virtual devices) each
   leave one ``fluid.run`` root whose children carry the same six names, in
   order, disjoint, inside the root;
 - the compile path from inside: jax's trace / lower / backend phases are
   children of the first call's ``fluid.run.call``, and
   ``executor.relowerings`` counts the lowering jax makes again for an
   entry the executor held.
"""

import glob
import threading

import jax
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observe
from paddle_tpu.fluid.executor import RNG_STATE_VAR
from paddle_tpu.fluid.parallel_executor import ParallelExecutor
from paddle_tpu.observe import trace
from paddle_tpu.observe.fleet import fleet_events

SIX = ["fluid.run.feed", "fluid.run.lookup", "fluid.run.state",
       "fluid.run.call", "fluid.run.commit", "fluid.run.observe"]


def _build_train(dropout=False):
    x = fluid.layers.data(name="x", shape=[8], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    h = fluid.layers.fc(input=x, size=16, act="relu")
    if dropout:
        h = fluid.layers.dropout(h, dropout_prob=0.5)
    pred = fluid.layers.fc(input=h, size=1, act=None)
    loss = fluid.layers.mean(
        fluid.layers.square_error_cost(input=pred, label=y))
    fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    return exe, loss


def _feed(batch=8):
    rng = np.random.RandomState(0)
    return {"x": rng.normal(size=(batch, 8)).astype(np.float32),
            "y": rng.normal(size=(batch, 1)).astype(np.float32)}


def _by_name(name):
    return [r for r in trace.recorded() if r.name == name]


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------


def test_ring_keeps_nesting_parent_ids_and_step():
    observe.note_step(41)
    with trace.span("outer", kind="test") as outer:
        with trace.span("inner") as inner:
            assert trace.current() is inner
            assert inner.parent_id == outer.span_id
            assert inner.trace_id == outer.trace_id
        after = trace.emit_span("measured", 1.0, 2.5)
    assert trace.current() is None
    got = trace.recorded()
    assert [r.name for r in got] == ["inner", "measured", "outer"]
    inner_r, measured, outer_r = got
    assert inner_r.parent_id == outer_r.span_id == measured.parent_id
    assert outer_r.parent_id is None
    assert measured.span_id == after
    assert (measured.t0, measured.t1) == (1.0, 2.5)
    assert outer_r.t0 <= inner_r.t0 <= inner_r.t1 <= outer_r.t1
    assert {r.step for r in got} == {41}
    assert len(outer_r.span_id) == 16 and len(outer.trace_id) == 32


def test_ring_is_bounded_and_drops_the_oldest():
    assert trace._ring.maxlen == trace.RING_SPANS
    with trace.span("first"):
        pass
    for i in range(trace.RING_SPANS):
        trace.emit_span("filler", float(i), float(i) + 0.5)
    got = trace.recorded()
    assert len(got) == trace.RING_SPANS
    assert got[0].name == "filler" and got[0].t0 == 0.0  # "first" fell out
    assert got[-1].t0 == float(trace.RING_SPANS - 1)


def test_ring_carries_thread_ids_and_async_handoff():
    with trace.span("main"):
        pass
    handed = trace.start_span("handed.off")  # opened here, closed there

    def worker():
        with trace.span("worker"):
            pass
        handed.end(status="ok")

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    by = {r.name: r for r in trace.recorded()}
    assert by["main"].tid == trace.thread_tid()
    assert by["worker"].tid != by["main"].tid
    # the hand-off span keeps the row of the thread that opened it, and is
    # no parent of what the closing thread had open
    assert by["handed.off"].tid == by["main"].tid
    assert by["worker"].parent_id is None
    assert handed.end() is None  # idempotent


def test_reset_clears_the_ring():
    with trace.span("gone"):
        pass
    assert trace.recorded()
    observe.reset()
    assert trace.recorded() == []


@pytest.mark.parametrize("sink,paddle_trace", [(False, None), (True, "0"),
                                               (True, "1")])
def test_span_is_always_returned(tmp_path, monkeypatch, sink, paddle_trace):
    if paddle_trace is not None:
        monkeypatch.setenv("PADDLE_TRACE", paddle_trace)
    if sink:
        observe.configure(str(tmp_path), flush_s=60.0)
    logged = sink and paddle_trace == "1"
    with trace.span("always", k=1) as sp:
        assert sp is not None and sp.logged is logged
        child = trace.start_span("child")
        assert child is not None and child.logged is logged
        child.end()
    assert [r.name for r in trace.recorded()] == ["child", "always"]
    if sink:
        observe.get_sink().flush()
        in_log = [r["event"] for r in fleet_events(str(tmp_path))]
        assert in_log == (["child", "always"] if logged else [])


def test_span_lands_in_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with trace.span("fluid.test.root", entry="executor") as root:
            with trace.span("fluid.test.child"):
                pass
            root.set(fresh=False)
        trace.emit_span("fluid.test.after_the_fact", 0.0, 1.0)
    finally:
        jax.profiler.stop_trace()
    (pb,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = {}
    for plane in ProfileData.from_file(pb).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("fluid.test."):
                    found[ev.name] = (ev.start_ns, ev.duration_ns,
                                      dict(ev.stats))
    # an after-the-fact interval cannot enter the profiler's trace
    assert set(found) == {"fluid.test.root", "fluid.test.child"}
    r0, rd, stats = found["fluid.test.root"]
    c0, cd, _ = found["fluid.test.child"]
    assert r0 <= c0 and c0 + cd <= r0 + rd
    assert stats["entry"] == "executor" and stats["fresh"] == 0


# ---------------------------------------------------------------------------
# a span never syncs and never lowers
# ---------------------------------------------------------------------------


def test_run_steps_under_a_sink_never_waits_for_the_device(tmp_path,
                                                           monkeypatch):
    observe.configure(str(tmp_path), flush_s=60.0)
    exe, loss = _build_train()

    def refuse(*_a, **_k):
        raise AssertionError("a span waited for the device")

    monkeypatch.setattr(jax, "block_until_ready", refuse)
    before = observe.registry().flat().get("compile.lowerings", 0)
    for _ in range(2):
        (lv,) = exe.run_steps(fluid.default_main_program(), feed=_feed(),
                              fetch_list=[loss], n_steps=4)
        assert np.isfinite(lv).all()
    windows = _by_name("executor.window")
    assert len(windows) == 2
    # lowered by its dispatches alone: nothing lowers a window a second
    # time to read a cost or a memory table from it
    disp = {r.span_id for r in _by_name("executor.dispatch")}
    lowered = [r for r in _by_name("fluid.compile.lower")
               if r.t0 >= windows[0].t0]
    assert lowered and all(r.parent_id in disp for r in lowered)
    flat = observe.registry().flat()
    assert flat["compile.lowerings"] - before == len(lowered)
    assert not [k for k in flat if k.startswith("device.")]


# ---------------------------------------------------------------------------
# one root, the same six children, under both per-step entry points
# ---------------------------------------------------------------------------


def _children_of(root):
    kids = sorted((r for r in trace.recorded()
                   if r.parent_id == root.span_id), key=lambda r: r.t0)
    assert root.t0 <= kids[0].t0 and kids[-1].t1 <= root.t1
    assert all(a.t1 <= b.t0 for a, b in zip(kids, kids[1:]))
    return [k.name for k in kids]


def test_executor_run_leaves_one_root_with_the_six_children_in_order():
    exe, loss = _build_train()
    exe.run(fluid.default_main_program(), feed=_feed(), fetch_list=[loss])
    observe.reset()
    exe.run(fluid.default_main_program(), feed=_feed(), fetch_list=[loss],
            return_numpy=False)
    (root,) = _by_name("fluid.run")
    assert _children_of(root) == SIX
    # the one wait, the host copy return_numpy asks for, has its own name
    # and comes last
    observe.reset()
    exe.run(fluid.default_main_program(), feed=_feed(), fetch_list=[loss])
    (root,) = _by_name("fluid.run")
    assert _children_of(root) == SIX + ["fluid.run.fetch"]


def test_parallel_executor_run_leaves_the_same_root_and_children():
    exe, loss = _build_train()
    pe = ParallelExecutor(loss_name=loss.name,
                          main_program=fluid.default_main_program(),
                          mesh="dp4")
    assert pe.device_count == 4
    pe.run([loss], feed=_feed(), return_numpy=False)
    observe.reset()
    pe.run([loss], feed=_feed(), return_numpy=False)
    (root,) = _by_name("fluid.run")
    names = _children_of(root)
    # the feed's sharded placement needs the step the lookup finds, so it
    # is a second span of the same name: a reader sums the two
    assert names == ["fluid.run.feed", "fluid.run.lookup", "fluid.run.feed",
                     "fluid.run.state", "fluid.run.call", "fluid.run.commit",
                     "fluid.run.observe"]
    assert sorted(set(names)) == sorted(SIX)


def test_root_attributes_say_entry_step_and_whether_the_cache_missed(
        tmp_path):
    observe.configure(str(tmp_path), flush_s=60.0)
    exe, loss = _build_train()
    for _ in range(2):
        exe.run(fluid.default_main_program(), feed=_feed(),
                fetch_list=[loss])
    observe.get_sink().flush()
    roots = [r for r in fleet_events(str(tmp_path))
             if r["event"] == "fluid.run"]
    # startup, then the two steps
    assert [r["fresh"] for r in roots] == [True, True, False]
    assert {r["entry"] for r in roots} == {"executor"}
    # the process-wide training step index, one apart
    assert roots[2]["step"] - roots[1]["step"] == 1
    builds = [r for r in fleet_events(str(tmp_path))
              if r["event"] == "fluid.run.build"]
    assert len(builds) == 2


# ---------------------------------------------------------------------------
# the compile path from inside
# ---------------------------------------------------------------------------


def test_first_call_holds_jaxs_compile_phases_as_children():
    exe, loss = _build_train()
    observe.reset()
    exe.run(fluid.default_main_program(), feed=_feed(), fetch_list=[loss],
            return_numpy=False)
    (call,) = _by_name("fluid.run.call")
    kids = [r.name for r in trace.recorded() if r.parent_id == call.span_id]
    assert kids == ["fluid.compile.trace", "fluid.compile.lower",
                    "fluid.compile.backend"]
    for r in trace.recorded():
        if r.parent_id == call.span_id:
            assert call.t0 <= r.t0 + 1e-3 and r.t1 <= call.t1
    flat = observe.registry().flat()
    assert flat["compile.lowerings"] == 1
    assert flat["compile.backend_compiles"] == 1
    assert "executor.relowerings" not in flat


def _commit_state(exe):
    """What the benchmark's comparison step does to the scope as a side
    effect: every array it touches committed to the executor's device.
    It has nothing random in it, so the RNG key stays as the startup
    program left it."""
    scope = fluid.global_scope()
    dev = jax.devices("cpu")[0]
    for name in list(scope.keys()):
        val = scope.get(name)
        if isinstance(val, jax.Array) and name != RNG_STATE_VAR:
            scope.set(name, jax.device_put(val, dev))


@pytest.mark.parametrize("dropout,expected", [(True, 1), (False, 0)])
def test_relowerings_counts_the_uncommitted_rng_key(dropout, expected):
    """The executor makes the RNG key with ``jax.random.PRNGKey`` (or the
    startup program leaves it), uncommitted, and the step returns it
    committed: the second call of a program with dropout misses jit's
    cache and lowers again (PERF.md section 7 (a)).  The ``perf_opt`` PR
    that cures it turns the 1 into 0."""
    exe, loss = _build_train(dropout=dropout)
    _commit_state(exe)
    observe.reset()
    for _ in range(2):
        exe.run(fluid.default_main_program(), feed=_feed(),
                fetch_list=[loss], return_numpy=False)
    flat = observe.registry().flat()
    assert flat.get("executor.relowerings", 0) == expected
    assert flat["compile.lowerings"] == 1 + expected
    # the ring shows which call it was
    calls = _by_name("fluid.run.call")
    lowered = [sum(1 for r in _by_name("fluid.compile.lower")
                   if r.parent_id == c.span_id) for c in calls]
    assert lowered == [1, expected]


def test_relowerings_sees_the_uncommitted_outputs_of_the_startup_program():
    """Found with the counter (PR 27): the startup program has no input, so
    jit leaves its outputs uncommitted, and the first step returns them
    committed.  Straight after a startup program EVERY training program is
    lowered twice, dropout or not; the benchmark's cells do not see it
    because their comparison step commits the state first.  Today's count,
    for the ``perf_opt`` PR that commits what ``_gather_state`` gathers."""
    exe, loss = _build_train(dropout=False)
    observe.reset()
    for _ in range(3):
        exe.run(fluid.default_main_program(), feed=_feed(),
                fetch_list=[loss], return_numpy=False)
    assert observe.registry().flat().get("executor.relowerings", 0) == 1
