#!/usr/bin/env python
"""Serving-fleet smoke (CPU, < 10 s) — the ISSUE 17 CI oracle.

Two models x two replicas behind one router, end to end through the
fleet lifecycle:

 1. all four replicas warm from ONE shared compile store: only the
    first replica of the architecture actually compiles; every other
    cold start is cache-hit-only;
 2. a replica is killed MID-LOAD by the deterministic fault hook
    (``PADDLE_FAULT_REPLICA_KILL_AFTER``): its in-flight requests fail
    over through the router to the survivor with zero shed and bitwise
    the same outputs, and the census re-spawns a replacement whose
    re-warm dispatches NOTHING (``warmup_dispatches == 0``);
 3. a load spike overflows the router's hard queue bound: the scale
    policy's last-chance hook fires an emergency ``fleet.scale_out``
    strictly before any shed — the spike completes with shed == 0 and
    a third replica serving.

Run directly (``python tools/router_smoke.py``) or from tier-1 via
``tests/test_router.py::test_router_smoke_tool_runs_clean``.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _wait(pred, timeout_s=30.0, tick=None):
    deadline = time.perf_counter() + timeout_s
    while not pred():
        if time.perf_counter() > deadline:
            return False
        if tick is not None:
            tick()
        time.sleep(0.01)
    return True


def main() -> dict:
    # the shared compile store is the POINT of the fleet's warm path:
    # replicas 2..N and every respawn must come up cache-hit-only
    if not os.environ.get("PADDLE_COMPILE_CACHE_DIR"):
        from paddle_tpu import compile_cache

        os.environ["PADDLE_COMPILE_CACHE_DIR"] = \
            compile_cache.checkout_root()

    import numpy as np

    from paddle_tpu import observe
    from paddle_tpu.fluid import fault as _fault
    from paddle_tpu.models import transformer
    from paddle_tpu.serving import (AutoscalePolicy, DecodeEngine,
                                    RouterConfig, ServingFleet)
    from paddle_tpu.observe.fleet import fleet_events

    t_start = time.perf_counter()
    report = {"ok": False}
    fleet = None
    obs_root = tempfile.mkdtemp(prefix="router_smoke_obs_")
    observe.configure(obs_root)

    def events(name):
        observe.get_sink().flush()
        return [r for r in fleet_events(obs_root)
                if r.get("event") == name]

    def factory(seed):
        def make(labels):
            model = transformer.DecodeModel(
                cfg=transformer.decode_lm_config(), max_slots=2,
                max_len=32, prefill_buckets=[4], seed=seed)
            return DecodeEngine(model, metrics_labels=labels)
        return make

    try:
        fleet = ServingFleet(
            {"chat": factory(5), "code": factory(9)},
            replicas=2,
            hb_dir=tempfile.mkdtemp(prefix="router_smoke_hb_"),
            # min_replicas=2 + a long cooldown pin the baseline fleet
            # shape; eval_s=30 idles the monitor so the smoke drives
            # poll_once() deterministically
            policy=AutoscalePolicy(min_replicas=2, max_replicas=3,
                                   cooldown_s=60.0, queue_high=6,
                                   hysteresis_ticks=2),
            router_config=RouterConfig(queue_hard=16),
            eval_s=30.0)

        # -- 1. four replicas, one compile --------------------------------
        fleet.start(wait_ready_s=90.0)
        ok_ready = _wait(lambda: all(
            fleet.status()["models"][m]["ready"] == 2
            for m in ("chat", "code")), timeout_s=60.0)
        report["all_ready"] = ok_ready
        report["warm_s"] = round(time.perf_counter() - t_start, 2)
        ready_events = events("fleet.replica_ready")
        report["initial_replicas"] = len(ready_events)
        report["cold_compiles"] = sum(
            1 for e in ready_events if e.get("warmup_dispatches", 0) > 0)
        report["cached_warms"] = sum(
            1 for e in ready_events
            if e.get("warmup_dispatches") == 0
            and e.get("warmup_cached", 0) > 0)

        rng = np.random.RandomState(7)
        prompts = [[int(t) for t in rng.randint(2, 60, size=3)]
                   for _ in range(4)]
        base = {m: [fleet.generate(m, p, 6) for p in prompts]
                for m in ("chat", "code")}
        report["models_disagree"] = base["chat"] != base["code"]

        # -- 2. kill one replica mid-load: zero-shed failover -------------
        served_now = max(r["served"] for r in
                         fleet.status()["models"]["chat"]["replicas"])
        _fault.install(_fault.FaultPlan(
            replica_kill_after=served_now + 2))
        try:
            futs = [fleet.submit("chat", prompts[i % 4], 6)
                    for i in range(10)]
            got = [f.result(timeout=60) for f in futs]
        finally:
            _fault.clear()
        report["failover_bitwise"] = all(
            got[i] == base["chat"][i % 4] for i in range(10))
        dead = events("fleet.replica_dead")
        report["killed"] = [e["replica"] for e in dead
                            if e.get("reason") == "fault_injected"]

        # census: account the death, re-spawn on a surviving device
        _wait(lambda: fleet.status()["models"]["chat"]["ready"] >= 2,
              timeout_s=60.0, tick=fleet.poll_once)
        respawns = events("fleet.respawn")
        report["respawned"] = [e["replica"] for e in respawns]
        new_names = {e["replica"] for e in respawns}
        rewarm = [e for e in events("fleet.replica_ready")
                  if e["replica"] in new_names]
        report["rewarm_dispatches"] = \
            [e.get("warmup_dispatches") for e in rewarm]
        report["rewarm_cached"] = [e.get("warmup_cached") for e in rewarm]
        report["post_respawn_bitwise"] = \
            [fleet.generate("chat", p, 6) for p in prompts] \
            == base["chat"]

        # -- 3. load spike: scale-out strictly before any shed ------------
        primers = [fleet.submit("code", prompts[i % 4], 12)
                   for i in range(4)]  # occupy every code slot
        spike = [fleet.submit("code", prompts[i % 4], 4)
                 for i in range(64)]
        spike_ok = sum(1 for f in spike
                       if f.result(timeout=120) is not None)
        for f in primers:
            f.result(timeout=120)
        report["spike_completed"] = spike_ok
        scale_outs = [e for e in events("fleet.scale_out")
                      if e.get("model") == "code"]
        report["scale_out_reasons"] = \
            [e.get("reason") for e in scale_outs]
        report["shed_events"] = len(events("fleet.shed"))
        status = fleet.status()
        report["shed"] = {m: status["models"][m]["shed"]
                          for m in ("chat", "code")}
        report["code_replicas_ready"] = _wait(
            lambda: fleet.status()["models"]["code"]["ready"] >= 3,
            timeout_s=60.0)

        report["elapsed_s"] = round(time.perf_counter() - t_start, 2)
        report["ok"] = bool(
            report["all_ready"]
            and report["initial_replicas"] >= 4
            and report["cold_compiles"] <= 1
            and report["cached_warms"] >= 3
            and report["models_disagree"]
            and report["failover_bitwise"]
            and len(report["killed"]) == 1
            and len(report["respawned"]) == 1
            and report["rewarm_dispatches"] == [0]
            and all(c > 0 for c in report["rewarm_cached"])
            and report["post_respawn_bitwise"]
            and report["spike_completed"] == 64
            and len(scale_outs) >= 1
            and report["shed_events"] == 0
            and report["shed"] == {"chat": 0, "code": 0}
            and report["code_replicas_ready"])
    except Exception as exc:  # a broken smoke must still print its JSON
        import traceback

        report["error"] = f"{type(exc).__name__}: {exc}"
        report["trace"] = traceback.format_exc(limit=5)
    finally:
        _fault.clear()
        if fleet is not None:
            try:
                fleet.shutdown(timeout_s=15)
            except Exception:
                pass
    print(json.dumps(report, indent=1))
    return report


if __name__ == "__main__":
    sys.exit(0 if main()["ok"] else 1)
