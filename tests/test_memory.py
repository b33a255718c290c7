"""Memory observability (ISSUE 11): HBM accounting, pre-flight, ledger.

Three-tier oracle set:

 - **compiled truth**: ``memory.peak_bytes{mesh=}`` gauges and
   ``memory.profile`` events come from the REAL
   ``compiled.memory_analysis()`` on the sharded window, the traced
   single-device window, and serving warmup — and re-report from the
   compile-cache / warmup manifests on warm starts without re-lowering;
 - **pre-flight**: the AN501 static estimate lands within 2x of the
   compiled peak on the MLP and tiny-transformer tier-1 models, stays
   info-severity on clean programs (zero false positives), and a
   ``PADDLE_MEM_BUDGET_MB``-exceeding program raises AN502 in strict
   mode BEFORE any compile;
 - **ledger**: scope residency and prefetch staging feed the
   ``memory.live_bytes`` gauge family, watermark events round-trip
   through the chrome-trace exporter as counter tracks, and an injected
   ``PADDLE_FAULT_MEM_PRESSURE`` leak trips a ``slo.breach`` on
   ``memory.live_bytes``.
"""

import json
import os
import sys
import warnings

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import analysis, observe
from paddle_tpu.fluid import fault
from paddle_tpu.observe import memory as obsmem

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def clean_fault():
    fault.clear()
    yield
    fault.clear()


def _build_mlp():
    img = fluid.layers.data(name="img", shape=[16], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    h = fluid.layers.fc(input=img, size=32, act="relu")
    pred = fluid.layers.fc(input=h, size=10, act="softmax")
    loss = fluid.layers.mean(
        fluid.layers.cross_entropy(input=pred, label=label))
    fluid.optimizer.Momentum(learning_rate=0.05, momentum=0.9).minimize(loss)
    return loss


def _mlp_feed(batch=8):
    return {"img": np.zeros((batch, 16), np.float32),
            "label": np.zeros((batch, 1), np.int64)}


# ---------------------------------------------------------------------------
# compiled truth: memory_stats + the AOT probe
# ---------------------------------------------------------------------------


def test_memory_stats_of_compiled():
    import jax
    import jax.numpy as jnp

    def f(a, b):
        return jnp.tanh(a @ b).sum()

    compiled = jax.jit(f).lower(jnp.ones((64, 128), jnp.float32),
                                jnp.ones((128, 32), jnp.float32)).compile()
    stats = obsmem.memory_stats(compiled)
    assert stats is not None
    assert stats["argument_bytes"] == (64 * 128 + 128 * 32) * 4
    assert stats["peak_bytes"] >= stats["argument_bytes"]
    assert stats["peak_bytes"] >= stats["temp_bytes"]


def test_executor_compiled_memory_probe():
    loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    stats = exe.compiled_memory_stats(fluid.default_main_program(),
                                      _mlp_feed(), [loss])
    assert stats is not None and stats["peak_bytes"] > 0
    # params + feeds are arguments of the traced step
    assert stats["argument_bytes"] > 4096


# ---------------------------------------------------------------------------
# pre-flight estimate: accuracy, cleanliness, budget
# ---------------------------------------------------------------------------


def test_preflight_within_2x_of_compiled_mlp():
    loss = _build_mlp()
    prog = fluid.default_main_program()
    feed = _mlp_feed()
    report = analysis.verify_program(prog, feed=feed, fetch_list=[loss])
    assert report.clean, report.format("warn")
    est = report.memory_estimate
    assert est and est["peak_bytes"] > 0
    assert "AN501" in {d.code for d in report.diagnostics}
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    truth = exe.compiled_memory_stats(prog, feed, [loss])
    ratio = est["peak_bytes"] / truth["peak_bytes"]
    assert 0.5 <= ratio <= 2.0, (est, truth)
    # per-op attribution: the top live tensors at the peak are named
    assert est["top"] and all(
        {"var", "bytes", "op_type"} <= set(r) for r in est["top"])


def test_preflight_within_2x_of_compiled_transformer():
    from paddle_tpu.models import transformer

    src, tgt, lbl, cost = transformer.build(transformer.tiny_config(),
                                            src_len=8, tgt_len=8)
    prog = fluid.default_main_program()
    feed = {src.name: np.zeros((8, 8), np.int64),
            tgt.name: np.zeros((8, 8), np.int64),
            lbl.name: np.zeros((8, 8, 1), np.int64)}
    report = analysis.verify_program(prog, feed=feed, fetch_list=[cost])
    est = report.memory_estimate
    assert est and est["peak_bytes"] > 0
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    truth = exe.compiled_memory_stats(prog, feed, [cost])
    ratio = est["peak_bytes"] / truth["peak_bytes"]
    assert 0.5 <= ratio <= 2.0, (est, truth)


def test_preflight_sharded_divides_by_mesh():
    """The dp2,tp2 estimate must be strictly below the single-device one:
    activations shard over dp, chain weights over tp."""
    loss = _build_mlp()
    prog = fluid.default_main_program()
    single = analysis.verify_program(
        prog, feed=_mlp_feed(), fetch_list=[loss]).memory_estimate
    sharded = analysis.verify_program(
        prog, feed=_mlp_feed(), fetch_list=[loss],
        mesh="dp2,tp2", kind="pe_run_steps").memory_estimate
    assert sharded["peak_bytes"] < single["peak_bytes"]
    assert sharded["persistent_bytes"] < single["persistent_bytes"]
    assert sharded["transient_high_water_bytes"] \
        < single["transient_high_water_bytes"]


def test_over_budget_an502_strict_raises_before_compile(monkeypatch):
    loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    # budget above the startup program's footprint, below the train step's
    monkeypatch.setenv("PADDLE_MEM_BUDGET_MB", "0.008")
    monkeypatch.setenv("PADDLE_TPU_VERIFY", "strict")
    analysis.reset()
    exe2 = fluid.Executor(fluid.CPUPlace())
    with pytest.raises(analysis.VerifyError, match="AN502"):
        exe2.run(fluid.default_main_program(), feed=_mlp_feed(),
                 fetch_list=[loss])
    # strict raised BEFORE compile: nothing entered the jit cache and no
    # dispatch ran
    assert len(exe2._cache) == 0


def test_within_budget_headroom_an503(monkeypatch):
    loss = _build_mlp()
    prog = fluid.default_main_program()
    est = analysis.verify_program(prog, feed=_mlp_feed(),
                                  fetch_list=[loss]).memory_estimate
    mb = est["peak_bytes"] / (1 << 20)
    monkeypatch.setenv("PADDLE_MEM_BUDGET_MB", f"{mb * 1.05:.6f}")
    report = analysis.verify_program(prog, feed=_mlp_feed(),
                                     fetch_list=[loss])
    assert "AN503" in {d.code for d in report.warnings}
    assert not report.errors


def test_no_budget_no_findings_above_info():
    """Zero false positives: without a budget the memcheck pass only ever
    adds the AN501 info note — clean programs stay strict-clean."""
    loss = _build_mlp()
    report = analysis.verify_program(fluid.default_main_program(),
                                     feed=_mlp_feed(), fetch_list=[loss])
    an5 = [d for d in report.diagnostics if d.code.startswith("AN5")]
    assert [d.code for d in an5] == ["AN501"]
    assert all(d.severity == "info" for d in an5)


# ---------------------------------------------------------------------------
# execution wiring: windows publish gauges/events; manifests re-report
# ---------------------------------------------------------------------------


def _window_feed(n_steps=4, batch=8):
    rng = np.random.RandomState(0)
    return {"img": rng.randn(n_steps, batch, 16).astype(np.float32),
            "label": rng.randint(0, 10, (n_steps, batch, 1))
            .astype(np.int64)}


def test_sharded_window_memory_gauges_and_events(tmp_path, monkeypatch):
    from paddle_tpu.fluid.parallel_executor import ParallelExecutor

    monkeypatch.setenv("PADDLE_OBSERVE_DIR", str(tmp_path))
    monkeypatch.setenv("PADDLE_TPU_MESH", "dp2,tp2")
    fluid.default_main_program().random_seed = 3
    fluid.default_startup_program().random_seed = 3
    loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    pe = ParallelExecutor(main_program=fluid.default_main_program(),
                          loss_name=loss.name)
    pe.run_steps([loss], feed=_window_feed(), n_steps=4,
                 feed_per_step=True)
    label = pe.mesh_label
    gauges = observe.registry().snapshot()["gauges"]
    assert gauges.get('memory.peak_bytes{mesh="%s"}' % label, 0) > 0, \
        sorted(gauges)
    assert gauges.get('memory.temp_bytes{mesh="%s"}' % label, 0) > 0
    assert gauges.get(
        'memory.live_bytes{mesh="%s",scope="train"}' % label, 0) > 0
    sink = observe.get_sink()
    recs = [json.loads(line) for line in open(sink.events.path)]
    prof = [r for r in recs if r["event"] == "memory.profile"]
    assert prof and prof[0]["mesh"] == label
    assert prof[0]["peak_bytes"] > 0 and prof[0]["kind"] == "sharded_window"
    wm = [r for r in recs if r["event"] == "memory.watermark"]
    assert wm and wm[0]["high_water_bytes"] >= wm[0]["live_bytes"] > 0
    # chrome trace renders the watermark counters as a "C" track
    from paddle_tpu.observe.export import chrome_trace

    tracks = {e["name"] for e in chrome_trace(recs)["traceEvents"]
              if e.get("ph") == "C"}
    assert any(n.startswith("memory.live_bytes") for n in tracks), tracks


def test_single_device_window_memory_is_the_callers_compile(tmp_path,
                                                            monkeypatch):
    """A span never lowers: a run_steps window under an observe sink is
    lowered ONCE, by its own first dispatch (it used to be lowered and
    compiled a second time to read its memory).  The compiled truth of a
    single-device program is what the caller asks for, through
    ``Executor.compiled_memory_stats``."""
    monkeypatch.setenv("PADDLE_OBSERVE_DIR", str(tmp_path))
    fluid.default_main_program().random_seed = 5
    fluid.default_startup_program().random_seed = 5
    loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    before = observe.registry().flat().get("compile.lowerings", 0)
    exe.run_steps(fluid.default_main_program(), _mlp_feed(), [loss],
                  n_steps=4)
    flat = observe.registry().flat()
    assert flat["compile.lowerings"] - before == 1, flat
    assert "memory.peak_bytes" not in flat
    stats = exe.compiled_memory_stats(fluid.default_main_program(),
                                      _mlp_feed(), [loss])
    from paddle_tpu.observe import memory as obsmem

    obsmem.note_compiled_memory(stats, kind="run")
    gauges = observe.registry().snapshot()["gauges"]
    assert gauges.get("memory.peak_bytes", 0) > 0, sorted(gauges)
    recs = [json.loads(line)
            for line in open(observe.get_sink().events.path)]
    prof = [r for r in recs if r["event"] == "memory.profile"]
    assert prof and prof[0]["kind"] == "run"


def test_warm_start_reports_memory_without_relowering(tmp_path,
                                                      monkeypatch):
    """The compile-cache manifest carries the per-executable memory
    table; a probe HIT republishes the gauges with cached=True and no
    lowering of any kind."""
    from paddle_tpu import compile_cache as _cc

    monkeypatch.setenv("PADDLE_COMPILE_CACHE_DIR", str(tmp_path / "cc"))
    monkeypatch.setenv("PADDLE_OBSERVE_DIR", str(tmp_path / "obs"))
    _cc.reset()
    loss = _build_mlp()
    prog = fluid.default_main_program()
    feed = _mlp_feed()
    stats = {"peak_bytes": 12345, "argument_bytes": 6000,
             "output_bytes": 5000, "temp_bytes": 1345,
             "generated_code_bytes": 0, "alias_bytes": 0}
    probe = _cc.executor_probe(prog, feed, ["loss"],
                               extra={"kind": "sharded_window"})
    assert probe is not None and not probe.hit
    probe.finish(0.5, prog, meta={"kind": "sharded_window",
                                  "mesh": "dp2xtp2", "n_steps": 4,
                                  "memory": stats})
    observe.reset()  # wipe gauges; the warm path must restore them
    probe2 = _cc.executor_probe(prog, feed, ["loss"],
                                extra={"kind": "sharded_window"})
    assert probe2 is not None and probe2.hit
    probe2.finish(0.01, prog)
    gauges = observe.registry().snapshot()["gauges"]
    assert gauges.get('memory.peak_bytes{mesh="dp2xtp2"}') == 12345.0
    recs = [json.loads(line)
            for line in open(observe.get_sink().events.path)]
    prof = [r for r in recs if r["event"] == "memory.profile"]
    assert prof and prof[-1]["cached"] is True


def test_serving_bucket_bytes_and_cached_rewarm(tmp_path, monkeypatch):
    from paddle_tpu import compile_cache as _cc
    from paddle_tpu.inference import NativeConfig, PaddlePredictor
    from paddle_tpu.serving.engine import ServingConfig, ServingEngine

    monkeypatch.setenv("PADDLE_COMPILE_CACHE_DIR", str(tmp_path / "cc"))
    _cc.reset()
    img = fluid.layers.data(name="img", shape=[16], dtype="float32")
    h = fluid.layers.fc(input=img, size=8, act="relu")
    pred = fluid.layers.fc(input=h, size=4, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    mdl = str(tmp_path / "model")
    fluid.io.save_inference_model(mdl, ["img"], [pred], exe)
    cfg = NativeConfig()
    cfg.model_dir = mdl
    manifest = str(tmp_path / "buckets.json")
    eng = ServingEngine(PaddlePredictor(cfg),
                        ServingConfig(max_batch_size=2,
                                      manifest_path=manifest))
    try:
        eng.warmup()
        assert eng.metrics.counter("warmup_dispatches") == 2
        gauges = observe.registry().snapshot()["gauges"]
        per_bucket = {k: v for k, v in gauges.items()
                      if k.startswith("serving.bucket_bytes")}
        assert set(per_bucket) == {'serving.bucket_bytes{bucket="1"}',
                                   'serving.bucket_bytes{bucket="2"}'}
        assert all(v > 0 for v in per_bucket.values())
        doc = json.load(open(manifest))
        assert sorted(doc["memory"]) == ["1", "2"]
        assert doc["memory"]["2"]["peak_bytes"] > 0
    finally:
        eng.shutdown()
    # cached re-warm: same manifest + warm store -> zero dispatches, the
    # SAME per-bucket numbers re-reported without re-lowering
    observe.reset()
    eng2 = ServingEngine(PaddlePredictor(cfg),
                         ServingConfig(max_batch_size=2,
                                       manifest_path=manifest))
    try:
        eng2.warmup()
        assert eng2.metrics.counter("warmup_dispatches") == 0
        assert eng2.metrics.counter("warmup_cached") == 2
        gauges = observe.registry().snapshot()["gauges"]
        assert gauges.get('serving.bucket_bytes{bucket="2"}') == \
            per_bucket['serving.bucket_bytes{bucket="2"}']
    finally:
        eng2.shutdown()


# ---------------------------------------------------------------------------
# ledger: scope residency, prefetch staging, leak detection
# ---------------------------------------------------------------------------


def test_ledger_live_and_high_water():
    import jax.numpy as jnp

    scope = fluid.Scope()
    scope.set("w", jnp.zeros((128, 64), jnp.float32))
    scope.set("host_side", np.zeros((999, 999)))  # host numpy: not HBM
    nbytes = obsmem.scope_live_bytes(scope)
    assert nbytes == 128 * 64 * 4
    obsmem.note_scope_live(scope, scope_label="t1", emit_event=False)
    scope.set("w2", jnp.zeros((32,), jnp.float32))
    obsmem.note_scope_live(scope, scope_label="t1", emit_event=False)
    scope._values.pop("w2")
    obsmem.note_scope_live(scope, scope_label="t1", emit_event=False)
    led = obsmem.ledger()
    assert led.live("t1") == nbytes
    assert led.high_water("t1") == nbytes + 32 * 4
    gauges = observe.registry().snapshot()["gauges"]
    assert gauges['memory.live_bytes{scope="t1"}'] == nbytes
    assert gauges['memory.live_high_water_bytes{scope="t1"}'] == \
        nbytes + 32 * 4


def test_prefetcher_reports_staged_bytes():
    from paddle_tpu.fluid.prefetch import DevicePrefetcher

    feeds = [{"x": np.ones((4, 8), np.float32)} for _ in range(6)]
    seen = []
    with DevicePrefetcher(feeds, n_steps=2, depth=1) as pf:
        for feed_dev, count in pf:
            seen.append(count)
    assert seen == [2, 2, 2]
    led = obsmem.ledger()
    # every staged window was handed off on consumption
    assert led.live("prefetch") == 0
    assert led.high_water("prefetch") >= 2 * 4 * 8 * 4  # >= one window


def test_injected_mem_pressure_trips_slo_breach(tmp_path, monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setenv("PADDLE_OBSERVE_DIR", str(tmp_path))
    monkeypatch.setenv("PADDLE_SLO", "1")
    monkeypatch.setenv("PADDLE_FAULT_MEM_PRESSURE", "16")
    observe.reset()
    fault.install(None)
    fault._plan = fault._UNSET  # re-arm env late-binding
    scope = fluid.Scope()
    scope.set("w", jnp.ones((64, 64), jnp.float32))
    for step in range(14):
        obsmem.note_scope_live(scope, scope_label="train", step=step)
    counters = observe.registry().snapshot()["counters"]
    assert counters.get('slo.breaches{metric="memory.live_bytes"}', 0) >= 1
    recs = [json.loads(line)
            for line in open(observe.get_sink().events.path)]
    breach = [r for r in recs if r["event"] == "slo.breach"
              and r.get("metric") == "memory.live_bytes"]
    assert breach, sorted({r["event"] for r in recs})


def test_mem_pressure_and_budget_over_budget_event(tmp_path, monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setenv("PADDLE_OBSERVE_DIR", str(tmp_path))
    monkeypatch.setenv("PADDLE_MEM_BUDGET_MB", "1")
    monkeypatch.setenv("PADDLE_FAULT_MEM_PRESSURE", "4")
    monkeypatch.setenv("PADDLE_FAULT_MEM_PRESSURE_AT", "2")
    observe.reset()
    fault.install(None)
    fault._plan = fault._UNSET
    scope = fluid.Scope()
    scope.set("w", jnp.ones((8, 8), jnp.float32))
    for step in range(6):
        obsmem.note_scope_live(scope, scope_label="train", step=step)
    counters = observe.registry().snapshot()["counters"]
    assert counters.get("memory.over_budget", 0) >= 1
    recs = [json.loads(line)
            for line in open(observe.get_sink().events.path)]
    assert any(r["event"] == "memory.over_budget" for r in recs)


# ---------------------------------------------------------------------------
# satellites: contrib shim, observe CLI, smoke tool
# ---------------------------------------------------------------------------


def test_memory_usage_calc_delegates_same_or_better():
    from paddle_tpu.fluid.contrib import memory_usage_calc as muc

    loss = _build_mlp()
    prog = fluid.default_main_program()
    with pytest.warns(DeprecationWarning, match="memcheck"):
        low, high = muc.memory_usage(prog, batch_size=8)
    assert 0 < low <= high
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    truth_mb = exe.compiled_memory_stats(prog, _mlp_feed(),
                                         [loss])["peak_bytes"] / (1 << 20)
    legacy_low, legacy_high = muc._legacy_memory_usage(prog, 8)
    new_mid = (low + high) / 2
    legacy_mid = (legacy_low + legacy_high) / 2
    # same-or-better: the delegated estimate is at least as close to the
    # compiled truth as the retired sum-every-var heuristic
    assert abs(new_mid - truth_mb) <= abs(legacy_mid - truth_mb)
    # and the band brackets the truth
    assert low <= truth_mb <= high * 1.5


def test_memory_usage_calc_rejects_bad_batch():
    from paddle_tpu.fluid.contrib import memory_usage_calc as muc

    _build_mlp()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        with pytest.raises(ValueError):
            muc.memory_usage(fluid.default_main_program(), batch_size=0)


def test_observe_memory_cli(tmp_path, monkeypatch):
    from paddle_tpu.observe.__main__ import main as observe_main

    monkeypatch.setenv("PADDLE_OBSERVE_DIR", str(tmp_path))
    observe.reset()
    obsmem.note_compiled_memory(
        {"peak_bytes": 1000, "argument_bytes": 600, "output_bytes": 300,
         "temp_bytes": 100, "generated_code_bytes": 0, "alias_bytes": 0},
        mesh="dp2xtp2", kind="sharded_window", n_steps=4)
    scope = fluid.Scope()
    import jax.numpy as jnp

    scope.set("w", jnp.ones((16,), jnp.float32))
    obsmem.note_scope_live(scope, scope_label="train", mesh="dp2xtp2")
    observe.get_sink().flush()
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = observe_main(["memory", "--dir", str(tmp_path)])
    assert rc == 0
    out = json.loads(buf.getvalue())
    assert out["profiles"]["sharded_window@dp2xtp2"]["peak_bytes"] == 1000
    assert out["watermarks"]["train@dp2xtp2"]["live_bytes"] == 64
    assert any(k.startswith("memory.peak_bytes")
               for k in out["gauges_by_worker"])


def test_mem_smoke_tool():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import mem_smoke
    finally:
        sys.path.pop(0)
    report = mem_smoke.main()
    assert report["ok"], report
    assert report["elapsed_s"] < 5.0, report
