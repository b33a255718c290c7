"""paddle_tpu.observe: the unified observability subsystem (ISSUE 5).

Oracles:
 - the lost-increment race regression: N threads x M increments through
   ``fluid.profiler.record_counter`` must total EXACTLY N*M (the old
   module-dict read-modify-write dropped updates under concurrency);
 - the exporter round trip: registry -> Prometheus text -> parse -> the
   same values;
 - the fleet path: two real processes write their own metric/event files,
   the aggregator produces one merged snapshot with per-worker and summed
   views;
 - the serving ``/metrics`` endpoint: Prometheus counters identical to
   ``ServingMetrics.snapshot()``;
 - run-event correlation: a supervised run with a guardian trip and a
   compile-cache warm start leaves ONE event stream where the gen-0 trip
   and the gen-1 cache hit share a program fingerprint, and every record
   is stamped (host, rank, gen, step).
"""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observe
from paddle_tpu.fluid import profiler
from paddle_tpu.observe.export import (chrome_trace, parse_prometheus_text,
                                       prometheus_text)
from paddle_tpu.observe.fleet import (fleet_events, fleet_snapshot,
                                      label_sums)
from paddle_tpu.observe.registry import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# satellite: the lost-increment race
# ---------------------------------------------------------------------------


def test_record_counter_exact_under_8_threads():
    """The regression oracle for the old unlocked read-modify-write on the
    profiler's counter dict: 8 threads x 2000 increments == exactly
    16000."""
    n_threads, m_incs = 8, 2000
    barrier = threading.Barrier(n_threads)

    def hammer():
        barrier.wait()  # maximize interleaving
        for _ in range(m_incs):
            profiler.record_counter("race.counter")

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert profiler.counters()["race.counter"] == n_threads * m_incs


def test_record_event_aggregate_exact_under_threads():
    """record_event's [calls, total, min, max] aggregate (the other racy
    dict) counts every call under concurrency."""
    profiler.start_profiler()
    try:
        n_threads, m_events = 8, 500

        def emit():
            for _ in range(m_events):
                profiler.record_event("race.event", 0.001)

        threads = [threading.Thread(target=emit) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        calls = observe.registry().timings()["race.event"][0]
        assert calls == n_threads * m_events
    finally:
        profiler.stop_profiler(profile_path=None)


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


def test_registry_labels_histograms_and_flat_view():
    reg = MetricsRegistry(buckets=(0.01, 0.1, 1.0))
    reg.inc("req", 3, labels={"bucket": "8"})
    reg.inc("req", 2, labels={"bucket": "16"})
    reg.set_gauge("depth", 7)
    for v in (0.005, 0.05, 0.5, 5.0):
        reg.observe("lat", v)
    flat = reg.flat()
    assert flat['req{bucket="8"}'] == 3 and flat['req{bucket="16"}'] == 2
    assert flat["depth"] == 7
    snap = reg.snapshot()
    h = snap["histograms"]["lat"]
    assert h["count"] == 4 and h["counts"] == [1, 1, 1, 1]
    assert abs(h["sum"] - 5.555) < 1e-9


def test_a_tape_takes_a_threads_increments_and_replay_counts_them_again():
    """``MetricsRegistry.tape`` / ``replay``: what a thread counts while a
    tape is open is counted as ever and kept, labels rendered; another
    thread's increments are not taken; a replay adds the same again and is
    itself taken by a tape that is open around it."""
    reg = MetricsRegistry()
    with reg.tape() as outer:
        reg.inc("a.calls", labels={"kind": "x"})
        with reg.tape() as inner:
            reg.inc("a.calls", 2, labels={"kind": "x"})
            other = threading.Thread(target=reg.inc, args=("a.other",))
            other.start()
            other.join()
        reg.inc("a.plain")
    reg.inc("a.after")
    assert inner == [('a.calls{kind="x"}', 2)]
    assert outer == [('a.calls{kind="x"}', 1), ('a.calls{kind="x"}', 2),
                     ("a.plain", 1)]
    assert reg.flat()['a.calls{kind="x"}'] == 3
    with reg.tape() as again:
        reg.replay(outer)
    assert again == outer
    assert reg.flat()['a.calls{kind="x"}'] == 6
    assert reg.flat()["a.plain"] == 2
    assert reg.flat()["a.other"] == reg.flat()["a.after"] == 1


def test_prometheus_round_trip():
    """Registry -> exposition text -> parse -> the same values (the CI
    oracle for the exporter, including labeled metrics and histograms)."""
    reg = MetricsRegistry(buckets=(0.01, 0.1))
    reg.inc("compile_cache.hit", 4)
    reg.inc("serving.completed", 11, labels={"model": "mlp"})
    reg.set_gauge("executor.jit_cache.size", 3)
    reg.observe("serving.latency_s", 0.05)
    reg.observe("serving.latency_s", 0.2)
    text = prometheus_text(reg.snapshot())
    parsed = parse_prometheus_text(text)
    assert parsed["counters"]["compile_cache_hit"] == 4
    assert parsed["counters"]['serving_completed{model="mlp"}'] == 11
    assert parsed["gauges"]["executor_jit_cache_size"] == 3
    h = parsed["histograms"]["serving_latency_s"]
    assert h["count"] == 2 and abs(h["sum"] - 0.25) < 1e-9
    # dots sanitize to underscores; exposition declares types
    assert "# TYPE compile_cache_hit counter" in text
    assert "serving_latency_s_bucket" in text


# ---------------------------------------------------------------------------
# sink + event log
# ---------------------------------------------------------------------------


def test_sink_writes_stamped_events_and_snapshots(tmp_path):
    sink = observe.configure(str(tmp_path), flush_s=60.0)
    profiler.record_counter("sink.test", 5)
    observe.note_step(12)
    observe.note_program("abcdef123456")
    observe.emit("unit.event", detail="x")
    sink.flush()
    observe.disable()

    files = os.listdir(str(tmp_path))
    assert any(f.startswith("events-") for f in files)
    assert any(f.startswith("metrics-") and f.endswith(".json")
               for f in files)
    assert any(f.endswith(".prom") for f in files)
    recs = fleet_events(str(tmp_path))
    (rec,) = [r for r in recs if r["event"] == "unit.event"]
    assert rec["step"] == 12 and rec["program"] == "abcdef123456"
    assert rec["detail"] == "x"
    for k in ("ts", "host", "pid", "rank", "gen"):
        assert k in rec
    snap = fleet_snapshot(str(tmp_path))
    assert snap["counters_sum"]["sink.test"] == 5


def test_emit_is_noop_without_observe_dir():
    assert observe.get_sink() is None
    assert observe.emit("nobody.listens") is None


# ---------------------------------------------------------------------------
# fleet aggregation across real processes
# ---------------------------------------------------------------------------

_FLEET_WORKER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, %r)
    from paddle_tpu import observe
    from paddle_tpu.fluid import profiler

    idx = int(sys.argv[1])
    profiler.record_counter("fleet.requests", 5 + idx)
    profiler.record_counter("fleet.shared", 10)
    profiler.record_counter("fleet.depth", value=idx)  # gauge
    observe.emit("fleet.worker_start", idx=idx)
    observe.emit("fleet.worker_done", idx=idx)
    observe.get_sink().close()  # final snapshot flush
""" % REPO)


def test_fleet_two_process_merge(tmp_path):
    """Each process writes its own metric/event files under the shared
    observe dir; the aggregator produces per-worker views, summed
    counters, and one wall-clock-ordered event stream."""
    script = str(tmp_path / "worker.py")
    with open(script, "w") as f:
        f.write(_FLEET_WORKER)
    root = str(tmp_path / "observe")
    for idx, host in ((0, "hostA"), (1, "hostB")):
        env = dict(os.environ)
        env.update({"PADDLE_OBSERVE_DIR": root,
                    "PADDLE_TRAINER_ID": str(idx),
                    "PADDLE_ELASTIC_GENERATION": "0"})
        r = subprocess.run([sys.executable, script, str(idx)], env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr

    snap = fleet_snapshot(root)
    assert len(snap["workers"]) == 2
    # summed across workers: (5+0) + (5+1)
    assert snap["counters_sum"]["fleet.requests"] == 11
    assert snap["counters_sum"]["fleet.shared"] == 20
    # per-worker views keep each process's own numbers
    per = snap["per_worker"]
    vals = sorted(w["counters"]["fleet.requests"] for w in per.values())
    assert vals == [5, 6]
    # gauges are not summed — reported per worker
    assert sorted(snap["gauges_by_worker"]["fleet.depth"].values()) == [0, 1]

    events = fleet_events(root)
    starts = [r for r in events if r["event"] == "fleet.worker_start"]
    assert sorted(r["rank"] for r in starts) == [0, 1]
    assert all({"ts", "host", "pid", "rank", "gen"} <= set(r)
               for r in events)
    assert all(events[i]["ts"] <= events[i + 1]["ts"]
               for i in range(len(events) - 1))


def test_fleet_sums_latest_generation_only(tmp_path):
    """A restarted worker's counters restart from zero: summing every
    generation would double-count the survivor's history, so fleet sums
    take each (host, rank)'s newest generation."""
    from paddle_tpu.observe.export import write_snapshot

    root = str(tmp_path)
    for gen, steps in ((0, 100), (1, 40)):
        write_snapshot(root, {"counters": {"steps": steps}, "gauges": {},
                              "histograms": {}},
                       stem=f"metrics-hostA-r0-g{gen}",
                       meta={"host": "hostA", "rank": 0, "gen": gen})
    snap = fleet_snapshot(root)
    assert snap["counters_sum"]["steps"] == 40  # gen 1 only
    assert len(snap["workers"]) == 2  # both generations stay visible


def test_fleet_partial_merge_truncated_rank(tmp_path, monkeypatch):
    """ISSUE 11 satellite: a missing/truncated per-rank snapshot must not
    take the fleet view down — surviving ranks merge, the casualty is
    listed under ``partial``, and a ``fleet.partial`` run event lands in
    the aggregating process's own sink."""
    from paddle_tpu.observe.export import write_snapshot

    root = str(tmp_path / "fleet")
    os.makedirs(root)
    for rank, steps in ((0, 10), (1, 25)):
        write_snapshot(root, {"counters": {"steps": steps}, "gauges": {},
                              "histograms": {}},
                       stem=f"metrics-hostA-r{rank}-g0",
                       meta={"host": "hostA", "rank": rank, "gen": 0})
    # rank 2's snapshot is torn mid-write (truncated JSON)
    with open(os.path.join(root, "metrics-hostA-r2-g0.json"), "w") as f:
        f.write('{"meta": {"host": "hostA", "rank": 2')

    agg_dir = str(tmp_path / "agg_sink")
    monkeypatch.setenv("PADDLE_OBSERVE_DIR", agg_dir)
    observe.reset()
    snap = fleet_snapshot(root)  # must not raise
    assert snap["counters_sum"]["steps"] == 35  # survivors merged
    assert len(snap["workers"]) == 2
    assert snap["partial"] == ["metrics-hostA-r2-g0.json"]
    sink = observe.get_sink()
    assert sink is not None
    recs = [json.loads(line) for line in open(sink.events.path)]
    partial = [r for r in recs if r["event"] == "fleet.partial"]
    assert partial and partial[0]["skipped"] == ["metrics-hostA-r2-g0.json"]
    assert len(partial[0]["survivors"]) == 2
    # a truncated EVENTS file degrades the same way: torn lines skip
    with open(os.path.join(root, "events-hostA-r2-g0.jsonl"), "w") as f:
        f.write('{"ts": 1.0, "event": "ok", "host": "hostA", "rank": 2, '
                '"gen": 0, "pid": 1}\n{"ts": 2.0, "event": "torn')
    evs = fleet_events(root)
    assert [r["event"] for r in evs] == ["ok"]


# ---------------------------------------------------------------------------
# CLI smoke (tier-1 CI round-trip, pattern of tools/cache_ctl.py --smoke)
# ---------------------------------------------------------------------------


def test_observe_cli_smoke():
    r = subprocess.run([sys.executable, "-m", "paddle_tpu.observe",
                        "--smoke"], capture_output=True, text=True,
                       timeout=120, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    report = json.loads(r.stdout)
    assert report["ok"] and report["race_exact"]
    assert report["elapsed_s"] < 2.0, report


# ---------------------------------------------------------------------------
# serving: windowed rates + /metrics endpoint
# ---------------------------------------------------------------------------


def test_serving_metrics_windowed_rates():
    from paddle_tpu.serving import ServingMetrics

    m = ServingMetrics()
    m.inc("completed", 100)
    m.observe_batch(80, 100)
    s0 = m.snapshot()
    time.sleep(0.05)
    m.inc("completed", 50)
    m.inc("shed", 3)
    m.observe_batch(40, 50)
    s1 = m.snapshot()

    win = ServingMetrics.window(s0, s1)
    assert win["completed"] == 50 and win["shed"] == 3
    assert win["interval_s"] > 0
    # interval qps reflects THIS window's 50 completions, not the 150
    # lifetime total
    assert abs(win["qps"] - 50 / win["interval_s"]) / win["qps"] < 0.5
    assert win["mean_batch_occupancy"] == 40 / 50

    # interval(): each call diffs against the previous call
    m2 = ServingMetrics()
    m2.inc("completed", 10)
    first = m2.interval()
    assert first["completed"] == 10
    m2.inc("completed", 7)
    second = m2.interval()
    assert second["completed"] == 7


def _save_mlp(tmpdir, seed=11):
    import paddle_tpu.fluid.executor as _executor

    fluid.default_main_program().random_seed = seed
    fluid.default_startup_program().random_seed = seed
    img = fluid.layers.data(name="img", shape=[16], dtype="float32")
    h = fluid.layers.fc(img, size=8, act="relu")
    pred = fluid.layers.fc(h, size=4, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    fluid.io.save_inference_model(str(tmpdir), ["img"], [pred], exe)
    _executor._global_scope = _executor.Scope()


def test_serving_metrics_endpoint_matches_snapshot(tmp_path):
    """Acceptance: the engine's /metrics Prometheus counters equal
    ``ServingMetrics.snapshot()``, and /healthz reports engine state."""
    from paddle_tpu.inference import AnalysisConfig, PaddleTensor
    from paddle_tpu.serving import ServingConfig, create_serving_engine

    _save_mlp(tmp_path)
    eng = create_serving_engine(
        AnalysisConfig(model_dir=str(tmp_path), use_tpu=False),
        ServingConfig(max_batch_size=4, max_wait_ms=2.0, metrics_port=0))
    try:
        assert eng.metrics_server is not None
        base = f"http://127.0.0.1:{eng.metrics_server.port}"
        eng.warmup()
        rng = np.random.RandomState(0)
        for i in range(6):
            eng.infer([PaddleTensor(
                name="img",
                data=rng.normal(size=(1, 16)).astype(np.float32))])

        text = urllib.request.urlopen(f"{base}/metrics",
                                      timeout=10).read().decode()
        parsed = parse_prometheus_text(text)
        snap = eng.metrics.snapshot()
        for name in ("completed", "submitted", "dispatches", "shed",
                     "rows_real", "rows_padded"):
            assert parsed["counters"][f"serving_{name}"] == snap[name], name
        assert parsed["counters"]["serving_completed"] == 6
        # the endpoint reports current (per-scrape window) throughput
        assert "serving_interval_qps" in parsed["gauges"]

        health = json.loads(urllib.request.urlopen(
            f"{base}/healthz", timeout=10).read().decode())
        assert health["ok"] and health["warm"]
    finally:
        eng.shutdown()
    assert eng.metrics_server is None  # endpoint closed with the engine


def test_serving_metrics_label_dimension_round_trip():
    """ISSUE 17 satellite: replica-scoped ServingMetrics stamp their
    process-registry mirrors with model=/replica= labels, the labeled
    names survive the Prometheus text round trip, and the fleet
    aggregation sums per-model through ``label_sums`` (structured label
    join, no metric-name string-parsing)."""
    from paddle_tpu.serving import ServingMetrics

    replicas = {("chat", "chat-r0"): 5, ("chat", "chat-r1"): 7,
                ("code", "code-r0"): 3}
    for (model, replica), n in replicas.items():
        m = ServingMetrics(labels={"model": model, "replica": replica})
        m.inc("completed", n)
        m.set_gauge("slots_active", n % 2)
        m.observe_latency(0.01)
        # the PRIVATE registry (snapshot keys) stays flat — per-engine
        # identity comes from object ownership, not labels
        assert m.snapshot()["completed"] == n

    flat = observe.registry().flat()
    assert flat['serving.completed{model="chat",replica="chat-r0"}'] == 5
    assert flat['serving.completed{model="chat",replica="chat-r1"}'] == 7
    assert flat['serving.completed{model="code",replica="code-r0"}'] == 3

    # Prometheus exposition round trip keeps the label identity
    text = prometheus_text(observe.registry().snapshot())
    parsed = parse_prometheus_text(text)
    assert parsed["counters"][
        'serving_completed{model="chat",replica="chat-r1"}'] == 7

    # fleet view: per-model sums over the replica dimension...
    per_model = label_sums(flat, "model", prefix="serving.")
    assert per_model["chat"]["serving.completed"] == 12
    assert per_model["code"]["serving.completed"] == 3
    # ...and per-replica slices keep each replica separate
    per_replica = label_sums(flat, "replica", prefix="serving.")
    assert per_replica["chat-r1"]["serving.completed"] == 7


# ---------------------------------------------------------------------------
# chrome-trace export + tools/timeline.py multi-host merge
# ---------------------------------------------------------------------------


def test_chrome_trace_distinct_pids_per_host():
    recs = [{"ts": 1.0, "event": "a", "host": "h0", "rank": 0, "gen": 0},
            {"ts": 1.5, "event": "b", "host": "h1", "rank": 0, "gen": 0,
             "dur_s": 0.25},
            {"ts": 2.0, "event": "c", "host": "h0", "rank": 1, "gen": 1}]
    trace = chrome_trace(recs, counter_samples=[
        {"ts": 10.0, "name": "queue_depth", "value": 3}])
    evs = trace["traceEvents"]
    names = {e["args"]["name"] for e in evs
             if e.get("name") == "process_name"}
    assert names == {"h0:r0", "h1:r0", "h0:r1"}
    assert len({e["pid"] for e in evs if e.get("ph") != "M"
                and e.get("ph") != "C"}) == 3
    assert any(e["ph"] == "X" for e in evs)  # the span
    assert any(e["ph"] == "C" for e in evs)  # the counter track


def test_timeline_tool_merges_hosts_and_emits_counters(tmp_path):
    """tools/timeline.py (satellite): multiple host logs merge with
    distinct pids + process_name rows, and profiler counter samples become
    chrome-trace counter events ("ph": "C")."""
    paths = []
    for i, host in enumerate(("tpu-a", "tpu-b")):
        log = {"events": [{"name": f"step{i}", "ts": 10.0 * i, "dur": 5.0}],
               "counters": [{"ts": 1.0, "name": "cache.hits",
                             "value": i + 1}],
               "host": host, "trace_dir": None}
        p = str(tmp_path / f"profile{i}.json")
        with open(p, "w") as f:
            json.dump(log, f)
        paths.append(p)
    out = str(tmp_path / "timeline.json")
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "timeline.py"),
                        "--profile_path", *paths, "--timeline_path", out],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    with open(out) as f:
        trace = json.load(f)
    evs = trace["traceEvents"]
    meta = [e for e in evs if e.get("name") == "process_name"]
    assert {m["args"]["name"] for m in meta} == {"paddle_tpu:tpu-a",
                                                "paddle_tpu:tpu-b"}
    assert {m["pid"] for m in meta} == {0, 1}
    counters = [e for e in evs if e.get("ph") == "C"]
    assert {(c["pid"], c["args"]["value"]) for c in counters} \
        == {(0, 1), (1, 2)}
    regions = [e for e in evs if e.get("ph") == "X"]
    assert {r_["pid"] for r_ in regions} == {0, 1}


def test_profiler_log_carries_host_and_counter_samples(tmp_path):
    """stop_profiler's JSON now feeds the multi-host merge: host stamp +
    per-change counter samples recorded during the session."""
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    y = fluid.layers.fc(input=x, size=2)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    ppath = str(tmp_path / "profile.json")
    profiler.start_profiler()
    exe.run(fluid.default_main_program(),
            feed={"x": np.ones((2, 4), np.float32)}, fetch_list=[y])
    profiler.record_counter("session.counter", 3)
    profiler.stop_profiler(profile_path=ppath)
    with open(ppath) as f:
        log = json.load(f)
    assert log["host"]
    assert any(s["name"] == "session.counter" and s["value"] == 3
               for s in log["counters"])


# ---------------------------------------------------------------------------
# run-event correlation (the acceptance oracle)
# ---------------------------------------------------------------------------


def test_executor_events_stamped_with_step_and_program(tmp_path):
    """With observe + compile cache enabled, a training run's cache events
    carry the program fingerprint and subsequent events carry the step."""
    import paddle_tpu.compile_cache as cc
    from paddle_tpu.fluid import fault

    fault.clear()  # deterministic step indices (the counter starts at 0)
    observe.configure(str(tmp_path / "observe"), flush_s=60.0)
    cc.configure(str(tmp_path / "cache"))
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    ylab = fluid.layers.data(name="y", shape=[1], dtype="float32")
    pred = fluid.layers.fc(input=x, size=1)
    loss = fluid.layers.mean(
        fluid.layers.square_error_cost(input=pred, label=ylab))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(3)
    for i in range(3):
        exe.run(fluid.default_main_program(),
                feed={"x": rng.normal(size=(4, 4)).astype(np.float32),
                      "y": rng.normal(size=(4, 1)).astype(np.float32)},
                fetch_list=[loss])
    observe.emit("train.done")
    recs = fleet_events(str(tmp_path / "observe"))
    observe.disable()
    miss = [r for r in recs if r["event"] == "compile_cache.miss"]
    assert miss and all(r["fingerprint"] for r in miss)
    (done,) = [r for r in recs if r["event"] == "train.done"]
    assert done["step"] == 2  # three steps ran: 0, 1, 2
    assert done["program"] == miss[-1]["fingerprint"]


_GUARDIAN_WORKER = textwrap.dedent("""
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
    for i in range(5):
        exe.run(fluid.default_main_program(),
                feed={"x": rng.normal(size=(8, 4)).astype(np.float32),
                      "y": rng.normal(size=(8, 1)).astype(np.float32)},
                fetch_list=[loss])
    guardian.flush()
""" % REPO)


def test_supervised_run_one_correlated_event_log(tmp_path):
    """Acceptance: a supervised run with a gen-0 guardian trip and a gen-1
    compile-cache warm start produces ONE run-event stream in which the
    trip, the cache hits, and the generation restart are all present and
    correlated by (host, generation, step) — and the gen-1 hit carries the
    SAME program fingerprint the gen-0 compile registered."""
    from paddle_tpu.parallel.elastic import ElasticSupervisor
    from paddle_tpu.parallel.master import Backoff

    workdir = str(tmp_path)
    script = os.path.join(workdir, "worker.py")
    with open(script, "w") as f:
        f.write(_GUARDIAN_WORKER)

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

    events = fleet_events(result["observe_dir"])
    assert events, "no run-event stream written"

    # 1. the guardian trip: gen 0, at the injected step, fully stamped
    (trip,) = [r for r in events if r["event"] == "guardian_trip"
               and r.get("source") != "supervisor"]
    assert trip["gen"] == 0 and trip["step"] == 2
    assert trip["policy"] == "halt" and trip["finite"] is False
    assert trip["host"] and trip["rank"] == 0

    # 2. the restart decision, in the same stream (supervisor source)
    gens = [r for r in events if r["event"] == "generation_start"]
    assert [g["generation"] for g in gens] == [0, 1]
    assert all(g.get("source") == "supervisor" for g in gens)
    exits = [r for r in events if r["event"] == "worker_exit"]
    assert exits and exits[0]["generation"] == 0

    # 3. the warm start: gen 0 missed (cold compile), gen 1 HIT the same
    # program fingerprint — the cross-generation correlation
    misses = [r for r in events if r["event"] == "compile_cache.miss"]
    hits = [r for r in events if r["event"] == "compile_cache.hit"]
    assert any(r["gen"] == 0 for r in misses)
    gen1_hits = [r for r in hits if r["gen"] == 1]
    assert gen1_hits, (misses, hits)
    gen0_fps = {r["fingerprint"] for r in misses if r["gen"] == 0}
    assert any(r["fingerprint"] in gen0_fps for r in gen1_hits)

    # 4. one wall-clock-ordered stream: trip (gen 0) precedes the gen-1
    # restart which precedes the gen-1 warm start
    assert trip["ts"] <= gens[1]["ts"] <= gen1_hits[0]["ts"]

    # 5. fleet snapshot aggregated at end of run: the gen-0 worker's trip
    # counter survives in its per-worker view (fleet sums take only the
    # LATEST generation, which restarted clean)
    assert result["fleet_snapshot"] and os.path.exists(
        result["fleet_snapshot"])
    with open(result["fleet_snapshot"]) as f:
        fleet = json.load(f)
    gen0 = [w for k, w in fleet["per_worker"].items() if k.endswith(":g0")]
    assert gen0 and any(
        w["counters"].get("guardian_trips", 0) >= 1 for w in gen0), fleet
    assert fleet["counters_sum"].get("guardian_steps", 0) >= 1  # gen 1 ran
