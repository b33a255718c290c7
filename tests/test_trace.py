"""Distributed tracing and the SLO watchdog (ISSUE 9; one primitive
since ISSUE 27, whose own cases are in tests/test_spans.py).

Oracles:
 - span API: W3C-style ids, automatic parenting via the thread context
   stack, deterministic sampling, PADDLE_TRACE=0 keeps the event log
   empty and the ring filling;
 - executor propagation: a ``run_steps`` window leaves an
   ``executor.window`` span whose stage/dispatch/observe children share
   its trace id, and the ``window.*_ms`` host breakdown gauges; no
   device sync, no second lowering;
 - prefetch propagation: staging spans live on the worker THREAD row and
   the consumer can link them (``last_stage_span``);
 - serving propagation: a request's latency decomposes into queue /
   batch / dispatch / resolve child spans of its request span;
 - watchdog: median+MAD baselines fire on an injected regression
   (fault.py IO delay through the windowed trainer) and stay quiet on a
   clean run;
 - cross-process stitching: a 2-generation supervised run merges into
   ONE trace — generation spans share the run trace id, worker window
   spans parent to their generation span, and the guardian trip carries
   span ids.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observe
from paddle_tpu.fluid import fault
from paddle_tpu.fluid.prefetch import DevicePrefetcher
from paddle_tpu.observe import trace, watchdog
from paddle_tpu.observe.export import chrome_trace
from paddle_tpu.observe.fleet import fleet_events

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build_train(batch=8, feat=8):
    x = fluid.layers.data(name="x", shape=[feat], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    h = fluid.layers.fc(input=x, size=16, act="relu")
    pred = fluid.layers.fc(input=h, size=1, act=None)
    loss = fluid.layers.mean(
        fluid.layers.square_error_cost(input=pred, label=y))
    fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    return exe, loss


# ---------------------------------------------------------------------------
# span API
# ---------------------------------------------------------------------------


def test_span_api_ids_nesting_and_event_stamping(tmp_path):
    observe.configure(str(tmp_path), flush_s=60.0)
    with trace.span("outer", kind="test") as outer:
        assert outer is not None
        assert len(outer.trace_id) == 32 and len(outer.span_id) == 16
        assert trace.current() is outer
        observe.emit("inner.event")  # stamped with the open span
        with trace.span("inner") as inner:
            assert inner.parent_id == outer.span_id
            assert inner.trace_id == outer.trace_id
        assert trace.current() is outer
    assert trace.current() is None
    observe.get_sink().flush()
    recs = fleet_events(str(tmp_path))
    by = {r["event"]: r for r in recs}
    assert by["inner"]["parent_span"] == by["outer"]["span_id"]
    assert by["outer"]["dur_s"] >= by["inner"]["dur_s"]
    # a NON-span record inside the span carries its identity
    assert by["inner.event"]["span_id"] == by["outer"]["span_id"]
    assert by["inner.event"]["trace_id"] == by["outer"]["trace_id"]


def test_tracing_disabled_and_no_sink(tmp_path, monkeypatch):
    # no sink: a span is returned and kept in the ring, and not logged
    assert observe.get_sink() is None
    sp = trace.start_span("x")
    assert sp is not None and not sp.logged
    sp.end()
    with trace.span("y") as sp:
        assert sp is not None and not sp.logged
    assert [r.name for r in trace.recorded()] == ["x", "y"]
    # sink but PADDLE_TRACE=0: the event log stays empty, the ring fills
    monkeypatch.setenv("PADDLE_TRACE", "0")
    observe.configure(str(tmp_path), flush_s=60.0)
    assert not trace.enabled()
    with trace.span("z") as sp:
        assert not sp.logged
        observe.emit("inside.z")  # not stamped: its span is not in the log
    observe.get_sink().flush()
    recs = fleet_events(str(tmp_path))
    assert [r["event"] for r in recs] == ["inside.z"]
    assert "span_id" not in recs[0]
    assert [r.name for r in trace.recorded()] == ["x", "y", "z"]


def test_root_sampling_deterministic(tmp_path, monkeypatch):
    observe.configure(str(tmp_path), flush_s=60.0)
    monkeypatch.setenv("PADDLE_TRACE_SAMPLE", "0.5")
    got = [trace.start_span("s").logged for _ in range(8)]
    assert sum(got) == 4  # every other root, regardless of phase
    monkeypatch.setenv("PADDLE_TRACE_SAMPLE", "0")
    assert not trace.start_span("s").logged
    monkeypatch.setenv("PADDLE_TRACE_SAMPLE", "1.0")
    sp = trace.start_span("s")
    assert sp.logged
    # children are exempt from sampling: they follow their parent, in
    # both directions
    monkeypatch.setenv("PADDLE_TRACE_SAMPLE", "0")
    child = trace.start_span("c", parent=sp)
    assert child.logged and child.parent_id == sp.span_id
    unsampled = trace.start_span("s")
    monkeypatch.setenv("PADDLE_TRACE_SAMPLE", "1.0")
    assert not trace.start_span("c", parent=unsampled).logged


def test_traceparent_round_trip():
    tid, pid = "ab" * 16, "cd" * 8
    assert trace.parse_traceparent(
        trace.format_traceparent(tid, pid)) == (tid, pid)
    assert trace.parse_traceparent(f"{tid}-{pid}") == (tid, pid)
    assert trace.parse_traceparent(tid) == (tid, None)
    assert trace.parse_traceparent("") == (None, None)


def test_traceparent_env_adopted(tmp_path, monkeypatch):
    tid, pid = "12" * 16, "34" * 8
    monkeypatch.setenv("PADDLE_TRACEPARENT",
                       trace.format_traceparent(tid, pid))
    observe.reset()  # re-arm late binding under the new env
    observe.configure(str(tmp_path), flush_s=60.0)
    sp = trace.start_span("root")
    assert sp.trace_id == tid and sp.parent_id == pid


# ---------------------------------------------------------------------------
# executor propagation + attribution
# ---------------------------------------------------------------------------


def test_run_steps_window_spans_and_attribution(tmp_path):
    observe.configure(str(tmp_path), flush_s=60.0)
    exe, loss = _build_train()
    rng = np.random.RandomState(0)
    feed = {"x": rng.normal(size=(8, 8)).astype(np.float32),
            "y": rng.normal(size=(8, 1)).astype(np.float32)}
    for _ in range(2):
        exe.run_steps(fluid.default_main_program(), feed=feed,
                      fetch_list=[loss], n_steps=4)
    observe.get_sink().flush()
    recs = fleet_events(str(tmp_path))
    windows = [r for r in recs if r["event"] == "executor.window"]
    assert len(windows) == 2
    wids = {w["span_id"] for w in windows}
    for kind in ("executor.stage", "executor.dispatch", "executor.observe"):
        kids = [r for r in recs if r["event"] == kind]
        assert len(kids) == 2, kind
        assert all(k["parent_span"] in wids for k in kids), kind
    # one trace id across the whole run, and the compile-or-cache span
    # (executor.trace) joined it
    assert len({r["trace_id"] for r in recs if r.get("trace_id")}) == 1
    assert any(r["event"] == "executor.trace" for r in recs)

    flat = observe.registry().flat()
    for k in ("window.host_ms", "window.stage_ms", "window.dispatch_ms",
              "window.observe_ms"):
        assert k in flat, flat.keys()
    # the cost-analysis family over host time is gone, and the window was
    # lowered by its own first dispatch alone (no second lowering to
    # read a cost from): every lowering of the window function `kfn` is
    # the child of a dispatch span
    assert not [k for k in flat if k.startswith("device.")
                or k.startswith("window.device")], flat.keys()
    disp = {r["span_id"] for r in recs if r["event"] == "executor.dispatch"}
    lowers = [r for r in recs if r["event"] == "fluid.compile.lower"
              and r["fun"] == "jit(kfn)"]
    assert lowers and all(r["parent_span"] in disp for r in lowers)
    # the children were stamped as they happened: in order, disjoint,
    # inside their window (ring stamps are perf_counter seconds)
    ring = {r.span_id: r for r in trace.recorded()}
    for w in windows:
        kids = sorted((r for r in ring.values()
                       if r.parent_id == w["span_id"]
                       and r.name.startswith("executor.")),
                      key=lambda r: r.t0)
        assert [k.name for k in kids] == [
            "executor.stage", "executor.dispatch", "executor.observe"]
        win = ring[w["span_id"]]
        assert win.t0 <= kids[0].t0 and kids[-1].t1 <= win.t1
        assert all(a.t1 <= b.t0 for a, b in zip(kids, kids[1:]))


def test_run_steps_untraced_emits_nothing(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TRACE", "0")
    observe.configure(str(tmp_path), flush_s=60.0)
    exe, loss = _build_train()
    rng = np.random.RandomState(0)
    feed = {"x": rng.normal(size=(8, 8)).astype(np.float32),
            "y": rng.normal(size=(8, 1)).astype(np.float32)}
    exe.run_steps(fluid.default_main_program(), feed=feed,
                  fetch_list=[loss], n_steps=4)
    observe.get_sink().flush()
    assert not [r for r in fleet_events(str(tmp_path))
                if r.get("span_id")]
    # PADDLE_TRACE=0 is about the event log alone: the same spans are in
    # the ring, and the host breakdown gauges are published
    names = [r.name for r in trace.recorded()]
    for kind in ("executor.window", "executor.stage", "executor.dispatch",
                 "executor.observe", "fluid.compile.lower"):
        assert kind in names, names
    assert "window.dispatch_ms" in observe.registry().flat()


# ---------------------------------------------------------------------------
# prefetch propagation
# ---------------------------------------------------------------------------


def test_prefetch_stage_spans_on_worker_thread(tmp_path):
    observe.configure(str(tmp_path), flush_s=60.0)

    def batches():
        r = np.random.RandomState(1)
        for _ in range(6):
            yield {"x": r.normal(size=(4, 8)).astype(np.float32)}

    links = []
    with DevicePrefetcher(batches(), n_steps=2, place=fluid.CPUPlace(),
                          depth=2) as pf:
        for _feed, _count in pf:
            links.append(pf.last_stage_span)
    assert len(links) == 3 and all(links)
    observe.get_sink().flush()
    stages = [r for r in fleet_events(str(tmp_path))
              if r["event"] == "prefetch.stage"]
    assert {r["span_id"] for r in stages} == set(links)
    # staged on the background thread: a different tid than this thread's
    assert all(r["tid"] != trace.thread_tid() for r in stages)


def test_trainer_window_links_staged_span(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_SPD", "2")
    observe.configure(str(tmp_path), flush_s=60.0)

    def train_func():
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(input=x, size=1, act=None)
        return fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))

    def batched():
        r = np.random.RandomState(4)
        for _ in range(4):
            x = r.normal(size=(8, 8)).astype(np.float32)
            yield [(x[i], x[i, :1]) for i in range(8)]

    trainer = fluid.Trainer(
        train_func=train_func,
        optimizer_func=lambda: fluid.optimizer.SGD(learning_rate=0.05),
        place=fluid.CPUPlace())
    trainer.train(num_epochs=1, event_handler=lambda ev: None,
                  reader=batched, feed_order=["x", "y"])
    observe.get_sink().flush()
    recs = fleet_events(str(tmp_path))
    train_wins = [r for r in recs if r["event"] == "train.window"]
    stages = {r["span_id"] for r in recs if r["event"] == "prefetch.stage"}
    assert train_wins and stages
    # the async hand-off link: each consuming window names the worker-
    # thread span that staged its input
    assert all(w.get("staged_span") in stages for w in train_wins)
    # and the executor window nests inside the trainer window
    exec_wins = [r for r in recs if r["event"] == "executor.window"]
    tw_ids = {w["span_id"] for w in train_wins}
    assert exec_wins and all(w["parent_span"] in tw_ids for w in exec_wins)


# ---------------------------------------------------------------------------
# serving propagation
# ---------------------------------------------------------------------------


def _save_mlp(tmpdir):
    import paddle_tpu.fluid.executor as _executor

    img = fluid.layers.data(name="img", shape=[16], dtype="float32")
    h = fluid.layers.fc(img, size=8, act="relu")
    pred = fluid.layers.fc(h, size=4, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    fluid.io.save_inference_model(str(tmpdir), ["img"], [pred], exe)
    _executor._global_scope = _executor.Scope()


def test_serving_request_span_decomposition(tmp_path):
    from paddle_tpu.inference import AnalysisConfig, PaddleTensor
    from paddle_tpu.serving import ServingConfig, create_serving_engine

    observe.configure(str(tmp_path / "observe"), flush_s=60.0)
    _save_mlp(tmp_path / "model")
    eng = create_serving_engine(
        AnalysisConfig(model_dir=str(tmp_path / "model"), use_tpu=False),
        ServingConfig(max_batch_size=4, max_wait_ms=1.0))
    try:
        eng.warmup()
        rng = np.random.RandomState(0)
        futs = [eng.submit([PaddleTensor(
            name="img", data=rng.normal(size=(1, 16)).astype(np.float32))])
            for _ in range(5)]
        for f in futs:
            f.result(timeout=30)
    finally:
        eng.shutdown()
    observe.get_sink().flush()
    recs = fleet_events(str(tmp_path / "observe"))
    reqs = [r for r in recs if r["event"] == "serving.request"]
    assert len(reqs) == 5 and all(r["status"] == "ok" for r in reqs)
    req_ids = {r["span_id"] for r in reqs}
    for kind in ("serving.queue", "serving.batch", "serving.dispatch",
                 "serving.resolve"):
        kids = [r for r in recs if r["event"] == kind]
        assert len(kids) == 5, kind
        assert all(k["parent_span"] in req_ids for k in kids), kind
    # the decomposition is consistent: a request's children cover less
    # than (or about) its own duration, and queue+dispatch are the two
    # the p99 story decomposes into
    for r in reqs:
        kids = [k for k in recs if k.get("parent_span") == r["span_id"]]
        assert sum(k["dur_s"] for k in kids) <= r["dur_s"] * 1.5 + 0.05


def test_serving_expired_request_span_status(tmp_path):
    from paddle_tpu.inference import AnalysisConfig, PaddleTensor
    from paddle_tpu.serving import (RequestTimeout, ServingConfig,
                                    create_serving_engine)

    observe.configure(str(tmp_path / "observe"), flush_s=60.0)
    _save_mlp(tmp_path / "model")
    eng = create_serving_engine(
        AnalysisConfig(model_dir=str(tmp_path / "model"), use_tpu=False),
        ServingConfig(max_batch_size=4, max_wait_ms=50.0))
    try:
        eng.warmup()
        fault.install(fault.FaultPlan(serve_delay_ms=80, mode="raise"))
        rng = np.random.RandomState(0)
        f1 = eng.submit([PaddleTensor(
            name="img", data=rng.normal(size=(1, 16)).astype(np.float32))])
        # second request expires while the first one's batch delays
        f2 = eng.submit([PaddleTensor(
            name="img", data=rng.normal(size=(1, 16)).astype(np.float32))],
            timeout_ms=1.0)
        f1.result(timeout=30)
        with pytest.raises(RequestTimeout):
            f2.result(timeout=30)
    finally:
        fault.clear()
        eng.shutdown()
    observe.get_sink().flush()
    recs = fleet_events(str(tmp_path / "observe"))
    statuses = sorted(r["status"] for r in recs
                      if r["event"] == "serving.request")
    assert "expired" in statuses and "ok" in statuses


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------


def test_watchdog_unit_breach_logic():
    wd = watchdog.SLOWatchdog(window=16, factor=3.0, min_samples=4,
                              cooldown_s=0.0)
    # baseline phase: nothing can fire before min_samples
    for v in (1.0, 1.1, 0.9, 1.0):
        assert not wd.observe("m", v)
    # in-band values stay quiet
    assert not wd.observe("m", 1.2)
    assert not wd.observe("m", 2.5)  # < 3x median
    # regression fires
    assert wd.observe("m", 10.0)
    med, mad, n = wd.baseline("m")
    assert 0.9 <= med <= 1.2 and n >= 5
    assert wd.breaches["m"] == 1
    # near-zero-variance metric with a tiny absolute wiggle: the MAD
    # guard (value > median + 3*MAD) still lets a 3x jump through, but a
    # zero median never fires
    wd2 = watchdog.SLOWatchdog(window=16, factor=3.0, min_samples=2,
                               cooldown_s=0.0)
    for _ in range(4):
        assert not wd2.observe("z", 0.0)
    assert not wd2.observe("z", 1.0)  # median 0 -> no ratio defined


def test_watchdog_cooldown_and_disarmed(monkeypatch):
    wd = watchdog.SLOWatchdog(window=8, factor=2.0, min_samples=2,
                              cooldown_s=60.0)
    for v in (1.0, 1.0, 1.0):
        wd.observe("m", v)
    assert wd.observe("m", 5.0)
    assert not wd.observe("m", 5.0)  # inside the cooldown window
    assert wd.breaches["m"] == 1
    # disarmed by default: module-level feed is a no-op
    monkeypatch.delenv("PADDLE_SLO", raising=False)
    watchdog.reset()
    assert watchdog.get_watchdog() is None
    assert watchdog.observe_value("m", 1e9) is False


def test_watchdog_io_delay_regression_e2e(tmp_path, monkeypatch):
    """Acceptance: slo.breach fires on an injected (fault.py IO-delay)
    step-time regression through the windowed trainer, and NOT on the
    clean phase — and the breach record carries span ids."""
    monkeypatch.setenv("PADDLE_TPU_SPD", "2")
    monkeypatch.setenv("PADDLE_SLO", "1")
    monkeypatch.setenv("PADDLE_SLO_MIN_SAMPLES", "4")
    monkeypatch.setenv("PADDLE_SLO_FACTOR", "8")
    monkeypatch.setenv("PADDLE_SLO_COOLDOWN_S", "0")
    observe.configure(str(tmp_path), flush_s=60.0)

    def train_func():
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(input=x, size=1, act=None)
        return fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))

    def batched():
        r = np.random.RandomState(4)
        for _ in range(12):
            x = r.normal(size=(8, 8)).astype(np.float32)
            yield [(x[i], x[i, :1]) for i in range(8)]

    trainer = fluid.Trainer(
        train_func=train_func,
        optimizer_func=lambda: fluid.optimizer.SGD(learning_rate=0.05),
        place=fluid.CPUPlace())
    # a fixed per-window floor keeps the clean baseline far above timer/
    # scheduler jitter, so factor-8 cannot false-fire
    handler = lambda ev: time.sleep(0.02) \
        if isinstance(ev, fluid.EndStepEvent) else None

    trainer.train(num_epochs=1, event_handler=handler, reader=batched,
                  feed_order=["x", "y"])
    observe.get_sink().flush()
    clean = [r for r in fleet_events(str(tmp_path))
             if r["event"] == "slo.breach"]
    assert not clean, clean

    # injected regression: every staged window now pays 400 ms of IO
    fault.install(fault.FaultPlan(io_delay_ms=400, mode="raise"))
    try:
        trainer.train(num_epochs=1, event_handler=handler, reader=batched,
                      feed_order=["x", "y"])
    finally:
        fault.clear()
    observe.get_sink().flush()
    breaches = [r for r in fleet_events(str(tmp_path))
                if r["event"] == "slo.breach"]
    assert breaches, "IO-delay regression did not trip the watchdog"
    b = breaches[0]
    assert b["metric"] == "train.step_time_s"
    assert b["value"] > b["baseline_median"] * 8
    assert b.get("span_id") and b.get("trace_id")  # joined the trace tree
    assert observe.registry().flat()[
        'slo.breaches{metric="train.step_time_s"}'] >= 1


# ---------------------------------------------------------------------------
# cross-process stitching (2-generation supervised run)
# ---------------------------------------------------------------------------

_TRACED_WORKER = textwrap.dedent("""
    import os, sys
    import numpy as np
    sys.path.insert(0, %r)
    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import guardian

    fluid.default_main_program().random_seed = 7
    fluid.default_startup_program().random_seed = 7
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    h = fluid.layers.fc(input=x, size=8, act="relu")
    pred = fluid.layers.fc(input=h, size=1)
    loss = fluid.layers.mean(
        fluid.layers.square_error_cost(input=pred, label=y))
    fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)

    guardian.enable(policy="halt")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(1)
    feed = {"x": rng.normal(size=(4, 8, 4)).astype(np.float32),
            "y": rng.normal(size=(4, 8, 1)).astype(np.float32)}
    for i in range(3):
        exe.run_steps(fluid.default_main_program(), feed=feed,
                      fetch_list=[loss], n_steps=4, feed_per_step=True)
    guardian.flush()
""" % REPO)


def test_supervised_two_generation_trace_stitching(tmp_path):
    """Acceptance: a gen-0 guardian halt + gen-1 clean resume produce ONE
    merged trace — generation spans share the run trace id, every worker
    window span parents to its generation's span, and the trip record
    carries (trace_id, span_id)."""
    from paddle_tpu.parallel.elastic import ElasticSupervisor
    from paddle_tpu.parallel.master import Backoff

    workdir = str(tmp_path)
    script = os.path.join(workdir, "worker.py")
    with open(script, "w") as f:
        f.write(_TRACED_WORKER)

    sup = ElasticSupervisor(
        f"{sys.executable} {script}", nproc=1, workdir=workdir,
        max_restarts=1, backoff=Backoff(base=0.05, factor=1.0),
        deadline=240.0,
        extra_env={"XLA_FLAGS":
                   "--xla_force_host_platform_device_count=1"},
        # gen 0 only: in-graph grad-Inf at step 2 -> guardian halt
        fault_env={"PADDLE_FAULT_GRAD_INF_STEP": "2"})
    result = sup.run()
    assert result["status"] == "finished", result
    assert result["generations"] == 2, result
    run_trace = result["trace_id"]
    assert run_trace and len(run_trace) == 32

    events = fleet_events(result["observe_dir"])

    # 1. one generation span per generation, all in the run trace
    gens = [r for r in events if r["event"] == "elastic.generation"]
    assert [g["generation"] for g in gens] == [0, 1]
    assert all(g["trace_id"] == run_trace for g in gens)
    assert all(g["dur_s"] > 0 for g in gens)
    gen_span = {g["generation"]: g["span_id"] for g in gens}
    assert gen_span[0] != gen_span[1]

    # 2. worker window spans from BOTH generations joined the run trace,
    # each parented to its own generation's span (the traceparent
    # handoff)
    windows = [r for r in events if r["event"] == "executor.window"]
    assert {w["gen"] for w in windows} == {0, 1}
    assert all(w["trace_id"] == run_trace for w in windows)
    for w in windows:
        assert w["parent_span"] == gen_span[w["gen"]], w

    # 3. the guardian trip is stamped INTO the trace: its span id is one
    # of gen 0's window spans
    (trip,) = [r for r in events if r["event"] == "guardian_trip"
               and r.get("source") != "supervisor"]
    assert trip["trace_id"] == run_trace
    gen0_windows = {w["span_id"] for w in windows if w["gen"] == 0}
    assert trip["span_id"] in gen0_windows

    # 4. the chrome export renders it as one multi-process trace: spans
    # are "X" events and both generations' pids appear
    tr = chrome_trace(events)
    xs = [e for e in tr["traceEvents"] if e.get("ph") == "X"]
    assert any(e["args"].get("span_id") in gen0_windows for e in xs)


# ---------------------------------------------------------------------------
# exporters / CLI / tools
# ---------------------------------------------------------------------------


def test_chrome_trace_span_thread_rows():
    recs = [{"ts": 1.0, "event": "w", "host": "h", "rank": 0, "gen": 3,
             "dur_s": 0.5, "span_id": "a" * 16, "tid": 0},
            {"ts": 1.2, "event": "stage", "host": "h", "rank": 0, "gen": 3,
             "dur_s": 0.1, "span_id": "b" * 16, "tid": 1},
            {"ts": 1.4, "event": "legacy", "host": "h", "rank": 0,
             "gen": 3, "dur_s": 0.1}]
    evs = chrome_trace(recs)["traceEvents"]
    tids = {e["name"]: e["tid"] for e in evs if e.get("ph") == "X"}
    # span records keep their emitting-thread rows; legacy ones keep gen
    assert tids == {"w": 0, "stage": 1, "legacy": 3}


def test_trace_cli_renders_tree(tmp_path):
    observe.configure(str(tmp_path), flush_s=60.0)
    exe, loss = _build_train()
    rng = np.random.RandomState(0)
    exe.run_steps(fluid.default_main_program(),
                  feed={"x": rng.normal(size=(8, 8)).astype(np.float32),
                        "y": rng.normal(size=(8, 1)).astype(np.float32)},
                  fetch_list=[loss], n_steps=2)
    observe.get_sink().flush()
    observe.disable()
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.observe", "trace",
         "--dir", str(tmp_path)],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert r.returncode == 0, r.stderr
    assert "trace " in r.stdout
    assert "executor.window" in r.stdout
    # children indent under the window
    win_line = [l for l in r.stdout.splitlines()
                if "executor.window" in l][0]
    disp_line = [l for l in r.stdout.splitlines()
                 if "executor.dispatch" in l][0]
    assert disp_line.index("executor.dispatch") > win_line.index(
        "executor.window")


def test_trace_smoke_tool():
    """tools/trace_smoke.py: the tier-1 oracle (<5 s): window + served
    requests -> spans in the log and the ring, lowerings the counter
    agrees with, chrome round trip, an empty log and a filling ring under
    PADDLE_TRACE=0."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import trace_smoke
    finally:
        sys.path.pop(0)
    report = trace_smoke.main()
    assert report["ok"], report
    assert report["elapsed_s"] < 5.0, report


def test_span_emission_thread_safe(tmp_path):
    """Many threads opening/closing spans concurrently: every span lands
    exactly once and the context stacks never cross threads."""
    observe.configure(str(tmp_path), flush_s=60.0)
    n_threads, n_spans = 8, 25
    errors = []

    def hammer(i):
        try:
            for k in range(n_spans):
                with trace.span(f"t{i}", k=k) as sp:
                    assert trace.current() is sp
                assert trace.current() is None
        except BaseException as exc:  # pragma: no cover
            errors.append(exc)

    threads = [threading.Thread(target=hammer, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    observe.get_sink().flush()
    recs = [r for r in fleet_events(str(tmp_path)) if r.get("span_id")]
    assert len(recs) == n_threads * n_spans
    assert len({r["span_id"] for r in recs}) == len(recs)
