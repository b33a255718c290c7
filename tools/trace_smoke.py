"""Span tracing smoke (CPU, < 5 s).

The CI oracle for the span primitive (``observe.trace``): with an observe
dir configured,

 - a 16-step training window produces an ``executor.window`` span with
   ``executor.stage`` / ``executor.dispatch`` / ``executor.observe``
   children sharing one trace id, the ``window.*_ms`` host breakdown
   gauges, and the same spans in the in-memory ring, where the first
   window's dispatch holds one ``fluid.compile.lower`` and whatever the
   later ones hold is what ``executor.relowerings`` counted;
 - 8 served requests produce per-request ``serving.request`` spans that
   decompose into queue / batch / dispatch / resolve children;
 - the merged stream round-trips through the chrome-trace exporter as
   ``"ph": "X"`` complete events carrying span ids;
 - ``PADDLE_TRACE=0`` runs the SAME paths and writes ZERO spans to the
   event log while the ring still fills: the two differ by the log
   writes alone (a span never syncs or lowers), and both per-window
   timings are reported so that is visible.

Run directly (``python tools/trace_smoke.py``) or from tier-1 via
``tests/test_trace.py::test_trace_smoke_tool``.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_STEPS = 16
N_REQUESTS = 8


def _build_train(fluid):
    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = startup.random_seed = 11
    with fluid.program_guard(prog, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=16, act="relu")
        pred = fluid.layers.fc(input=h, size=1, act=None)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(
            loss, startup_program=startup)
    return prog, startup, loss


def _run_window(fluid, np, prog, startup, loss, n_windows=1):
    """Run ``n_windows`` fused 16-step windows; returns per-window ms."""
    rng = np.random.RandomState(3)
    feed = {"x": rng.normal(size=(N_STEPS, 8, 8)).astype(np.float32),
            "y": rng.normal(size=(N_STEPS, 8, 1)).astype(np.float32)}
    times = []
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for _ in range(n_windows):
            t = time.perf_counter()
            (lv,) = exe.run_steps(prog, feed=feed, fetch_list=[loss],
                                  n_steps=N_STEPS, feed_per_step=True)
            np.asarray(lv)
            times.append((time.perf_counter() - t) * 1e3)
    return times


def main() -> dict:
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu import observe
    from paddle_tpu.observe import trace
    from paddle_tpu.observe.export import chrome_trace
    from paddle_tpu.observe.fleet import fleet_events

    t0 = time.perf_counter()
    root = tempfile.mkdtemp(prefix="trace_smoke_")
    report = {"ok": False, "root": root}
    os.environ["PADDLE_TRACE"] = "1"
    observe.configure(root, flush_s=60.0)
    try:
        # -- 1. traced training window ---------------------------------
        prog, startup, loss = _build_train(fluid)
        traced_ms = _run_window(fluid, np, prog, startup, loss,
                                n_windows=2)
        flat = observe.registry().flat()
        report["breakdown_gauges"] = all(
            f"window.{k}_ms" in flat
            for k in ("host", "stage", "dispatch", "observe"))
        ring = trace.recorded()
        dispatches = [r for r in ring if r.name == "executor.dispatch"]
        lowered = [sum(1 for r in ring if r.name == "fluid.compile.lower"
                       and r.parent_id == d.span_id) for d in dispatches]
        report["ring_lowerings_per_window"] = lowered
        report["relowerings"] = int(flat.get("executor.relowerings", 0))
        report["relowerings_agree"] = (
            lowered[0] == 1 and sum(lowered[1:]) == report["relowerings"])

        # -- 2. traced serving requests --------------------------------
        from paddle_tpu.inference import (AnalysisConfig, PaddleTensor)
        from paddle_tpu.serving import ServingConfig, create_serving_engine

        model_dir = os.path.join(root, "model")
        with fluid.scope_guard(fluid.Scope()):
            iprog, istartup = fluid.Program(), fluid.Program()
            with fluid.program_guard(iprog, istartup), \
                    fluid.unique_name.guard():
                img = fluid.layers.data(name="img", shape=[16],
                                        dtype="float32")
                out = fluid.layers.fc(input=img, size=4, act="softmax")
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(istartup)
            fluid.io.save_inference_model(model_dir, ["img"], [out], exe,
                                          main_program=iprog)
        eng = create_serving_engine(
            AnalysisConfig(model_dir=model_dir, use_tpu=False),
            ServingConfig(max_batch_size=4, max_wait_ms=1.0))
        try:
            eng.warmup()
            rng = np.random.RandomState(0)
            futs = [eng.submit([PaddleTensor(
                name="img",
                data=rng.normal(size=(1, 16)).astype(np.float32))])
                for _ in range(N_REQUESTS)]
            for f in futs:
                f.result(timeout=30)
        finally:
            eng.shutdown()

        # -- 3. span inventory + chrome round trip ---------------------
        observe.get_sink().flush()
        recs = fleet_events(root)
        spans = [r for r in recs if r.get("span_id")]
        kinds = {}
        for r in spans:
            kinds[r["event"]] = kinds.get(r["event"], 0) + 1
        report["span_kinds"] = kinds
        report["window_spans"] = kinds.get("executor.window", 0) >= 2
        report["window_children"] = all(
            kinds.get(k, 0) >= 2 for k in
            ("executor.stage", "executor.dispatch", "executor.observe"))
        report["request_spans"] = kinds.get("serving.request",
                                            0) == N_REQUESTS
        report["request_children"] = all(
            kinds.get(k, 0) == N_REQUESTS for k in
            ("serving.queue", "serving.dispatch"))
        req = [r for r in spans if r["event"] == "serving.request"]
        q = [r for r in spans if r["event"] == "serving.queue"]
        report["request_decomposes"] = bool(req) and all(
            any(c["parent_span"] == r["span_id"] for c in q) for r in req)
        one_trace = {r["trace_id"] for r in spans
                     if r["event"].startswith("executor.")}
        report["one_trace_per_run"] = len(one_trace) == 1

        trace_json = json.loads(json.dumps(chrome_trace(recs)))
        xs = [e for e in trace_json["traceEvents"] if e.get("ph") == "X"]
        report["chrome_x_events"] = len(xs)
        # duration records only: span-stamped INSTANTS (memory.watermark,
        # memory.profile, cache hits inside a window) render as "i"/"C"
        dur_spans = [r for r in spans if r.get("dur_s") is not None]
        report["chrome_round_trip"] = (
            len(xs) >= len(dur_spans)
            and any(e["args"].get("span_id") for e in xs))

        # -- 4. PADDLE_TRACE=0: nothing in the log, the ring still fills
        os.environ["PADDLE_TRACE"] = "0"
        n_spans_before = len(spans)
        n_ring_before = sum(1 for r in trace.recorded()
                            if r.name == "executor.window")
        prog2, startup2, loss2 = _build_train(fluid)
        untraced_ms = _run_window(fluid, np, prog2, startup2, loss2,
                                  n_windows=2)
        observe.get_sink().flush()
        spans_after = [r for r in fleet_events(root) if r.get("span_id")]
        report["disabled_no_spans"] = len(spans_after) == n_spans_before
        report["disabled_ring_fills"] = sum(
            1 for r in trace.recorded()
            if r.name == "executor.window") == n_ring_before + 2
        report["window_ms_traced"] = round(traced_ms[-1], 2)
        report["window_ms_untraced"] = round(untraced_ms[-1], 2)

        report["elapsed_s"] = round(time.perf_counter() - t0, 2)
        report["ok"] = all(report[k] for k in (
            "relowerings_agree", "breakdown_gauges", "window_spans",
            "window_children", "request_spans", "request_children",
            "request_decomposes", "one_trace_per_run",
            "chrome_round_trip", "disabled_no_spans",
            "disabled_ring_fills"))
    except Exception as exc:  # a broken smoke must still print its JSON
        import traceback

        report["error"] = f"{type(exc).__name__}: {exc}"
        report["trace"] = traceback.format_exc(limit=8)
    finally:
        os.environ.pop("PADDLE_TRACE", None)
        observe.reset()
        import shutil

        shutil.rmtree(root, ignore_errors=True)
    print(json.dumps(report, indent=1))
    return report


if __name__ == "__main__":
    sys.exit(0 if main()["ok"] else 1)
