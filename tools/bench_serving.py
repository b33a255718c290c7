#!/usr/bin/env python
"""Open-loop load generator for the serving engine (docs/SERVING.md).

Open-loop means requests are fired on a fixed arrival schedule derived
from --qps, NOT when the previous response returns — the generator never
slows down to match the server, so queueing/shedding behavior under a
genuinely offered load is visible (a closed-loop generator would hide
overload by self-throttling, the classic coordinated-omission mistake).

Builds a mnist-sized MLP in-process (or serves --model-dir), saves it,
stands up a ServingEngine, warms the buckets, offers load for --duration
seconds, and emits ONE BENCH-style JSON line on stdout:

    {"metric": "serving_mlp784_openloop_cpu", "value": <qps>,
     "unit": "req/s", "offered_qps": ..., "p50_ms": ..., "p95_ms": ...,
     "p99_ms": ..., "mean_batch_occupancy": ..., "shed": ..., ...}

Modes:
    --smoke     2-second CPU sanity pass for CI (exit 0 + valid JSON is
                the contract; tests/tier-2 can parse the line)
    --decode    continuous-batching decode workload (ISSUE 15): open-loop
                generation requests with a mixed short/long token-budget
                distribution through the DecodeEngine; the BENCH line
                reports tokens/s, TTFT p50/p99, inter-token p99 and the
                executable count (fixed-set invariant:
                compiles_after_warmup must be 0)
    --router    serving-fleet workload (ISSUE 17): --models x --replicas
                decode replicas behind one router; the BENCH line
                reports per-model qps/p50/p99/shed plus the
                ready-replica-count trajectory sampled through the run
    default     --duration/--qps as given; --device TPU serves from the
                accelerator when one is attached
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _build_and_save(model_dir: str, hidden: int = 64) -> None:
    """Train-free mnist-sized MLP (784 -> hidden -> 10 softmax)."""
    import paddle_tpu.fluid as fluid

    fluid.default_main_program().random_seed = 17
    fluid.default_startup_program().random_seed = 17
    img = fluid.layers.data(name="img", shape=[784], dtype="float32")
    h = fluid.layers.fc(img, size=hidden, act="relu")
    pred = fluid.layers.fc(h, size=10, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    fluid.io.save_inference_model(model_dir, ["img"], [pred], exe)


def run_bench(args) -> dict:
    import numpy as np

    from paddle_tpu.inference import AnalysisConfig, PaddleTensor
    from paddle_tpu.serving import (EngineOverloaded, ServingConfig,
                                    create_serving_engine)

    model_dir = args.model_dir
    if not model_dir:
        model_dir = tempfile.mkdtemp(prefix="bench_serving_")
        _build_and_save(model_dir)

    cfg = AnalysisConfig(model_dir=model_dir,
                         use_tpu=(args.device.upper() == "TPU"))
    eng = create_serving_engine(cfg, ServingConfig(
        max_batch_size=args.max_batch_size,
        max_wait_ms=args.max_wait_ms,
        max_queue_depth=args.queue_depth))
    sample = [PaddleTensor(name=n, data=r) for n, r in zip(
        eng._feed_names, _sample_rows(eng))] if args.model_dir else None
    eng.warmup(sample_inputs=sample)
    warm = eng.metrics.snapshot()

    rng = np.random.RandomState(0)
    # pre-generate a pool of request payloads so the generator's hot loop
    # is submit-only (payload synthesis must not gate the offered rate)
    pool = [[PaddleTensor(name=eng._feed_names[0],
                          data=rng.normal(size=(1, 784)).astype(np.float32))]
            for _ in range(256)] if not args.model_dir else \
           [sample for _ in range(256)]

    results = {"ok": 0, "shed": 0, "err": 0}
    rlock = threading.Lock()

    def on_done(fut):
        with rlock:
            if fut.exception() is None:
                results["ok"] += 1
            else:
                results["err"] += 1

    period = 1.0 / args.qps
    t_end = time.perf_counter() + args.duration
    next_fire = time.perf_counter()
    sent = 0
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if now < next_fire:
            time.sleep(min(next_fire - now, 0.002))
            continue
        # open loop: the schedule advances by the period even when we fell
        # behind, so the offered rate stays honest
        next_fire += period
        try:
            eng.submit(pool[sent % len(pool)]).add_done_callback(on_done)
            sent += 1
        except EngineOverloaded:
            with rlock:
                results["shed"] += 1
    eng.drain(timeout_s=60.0)
    snap = eng.metrics.snapshot()
    eng.shutdown()

    # windowed interval rates (warm-snapshot -> final-snapshot diff): the
    # cumulative snapshot qps includes warmup dead time and decays toward
    # the lifetime mean; the window is the actual serving interval
    from paddle_tpu.serving import ServingMetrics

    win = ServingMetrics.window(warm, snap)
    out = {
        "metric": f"serving_mlp784_openloop_{args.device.lower()}",
        "value": win["qps"],
        "unit": "req/s",
        "offered_qps": args.qps,
        "duration_s": args.duration,
        "window_s": win["interval_s"],
        "sent": sent,
        "completed": results["ok"],
        "shed": results["shed"] + win["shed"],
        "errors": results["err"],
        "p50_ms": snap["p50_ms"],
        "p95_ms": snap["p95_ms"],
        "p99_ms": snap["p99_ms"],
        "mean_batch_occupancy": win["mean_batch_occupancy"],
        "dispatches": win["dispatches"],
        "dispatch_rate": win["dispatch_rate"],
        "bucket_compiles": snap["bucket_compiles"],
        "compiles_after_warmup":
            snap["bucket_compiles"] - warm["bucket_compiles"],
        "max_batch_size": args.max_batch_size,
        "max_wait_ms": args.max_wait_ms,
        "queue_depth": args.queue_depth,
        "smoke": bool(args.smoke),
    }
    return out


def _sample_rows(eng):
    """Zero rows from the model's own feed shapes (for --model-dir)."""
    return list(eng._zero_rows().values())


def run_decode_bench(args) -> dict:
    """Open-loop mixed-length decode workload through the DecodeEngine.

    Arrivals fire on the --qps schedule; each request draws a token
    budget from a bimodal distribution (80% short --short-new, 20% long
    --long-new) — the convoy-forming mix iteration-level scheduling
    exists for.  Reported rates come from a warm->final
    ``ServingMetrics.window`` so warmup dead time never dilutes them."""
    import numpy as np

    from paddle_tpu.models import transformer
    from paddle_tpu.serving import (DecodeConfig, DecodeEngine,
                                    EngineOverloaded, ServingMetrics)

    paged = None if args.paged < 0 else bool(args.paged)
    if getattr(args, "prefix_share", -1) >= 0:
        import os as _os

        _os.environ["PADDLE_SERVE_PREFIX_SHARE"] = str(args.prefix_share)
    model = transformer.DecodeModel(
        cfg=transformer.decode_lm_config(),
        max_slots=args.slots, max_len=args.max_len,
        prefill_buckets=[4, 8], paged=paged,
        page_size=args.page_size, num_pages=args.num_pages)
    # --spec k arms speculative decoding (ISSUE 20).  --draft-layers
    # defaults to 0 = full-depth self-draft: the acceptance ceiling
    # (rate 1.0), so the line measures the draft+verify machinery's
    # throughput headroom; pass a small n for a realistic cheap draft.
    spec_k = int(getattr(args, "spec", 0) or 0)
    eng = DecodeEngine(model, DecodeConfig(
        max_queue_depth=args.queue_depth,
        spec=spec_k if spec_k > 0 else None,
        spec_draft_layers=getattr(args, "draft_layers", None)))
    eng.warmup()
    warm = eng.metrics.snapshot()
    # dense KV footprint for the equal-HBM comparison in either mode
    kv_dense_bytes = (model.max_slots * model.max_len
                      * model.cfg.d_model * 4 * 2 * model.cfg.n_layer)

    rng = np.random.RandomState(0)
    if args.shared_prefix:
        # every prompt shares one full first page (page-size tokens of
        # common prefix + one distinct tail token): under prefix sharing
        # concurrent admissions hit the resident page and, with the tail
        # on the private page boundary, skip their prefill outright
        ps = model.page_size if getattr(model, "paged", False) else 4
        base = [int(t) for t in rng.randint(2, model.vocab_size - 1,
                                            size=ps)]
        pool = [base + [int(t)]
                for t in rng.randint(2, model.vocab_size - 1, size=64)]
    elif spec_k > 0:
        # repetitive prompts: the draftable load speculation pays on
        pool = [[int(t)] * 3
                for t in rng.randint(2, model.vocab_size - 1, size=64)]
    else:
        pool = [[int(t) for t in rng.randint(2, model.vocab_size - 1,
                                             size=3)]
                for _ in range(64)]
    budgets = [args.long_new if rng.random_sample() < 0.2
               else args.short_new for _ in range(256)]

    # --swaps N: hot-swap N fresh serials THROUGH the open-loop window
    # (ISSUE 16 acceptance: zero shed, p99 inside the no-swap band).
    # The registry's own background watcher does the swapping; the
    # arrival loop only commits serials on schedule, like a trainer
    # publishing checkpoints mid-traffic.
    reg = None
    swap_serials = []
    n_swaps = int(getattr(args, "swaps", 0) or 0)
    if n_swaps > 0:
        import tempfile

        from paddle_tpu.serving import ModelRegistry, write_weights_serial

        swap_root = tempfile.mkdtemp(prefix="bench_swap_")
        w0 = eng.snapshot_weights(model.weight_names())
        prng = np.random.RandomState(1)

        def _serial_weights():
            return {n: (np.asarray(a)
                        + 0.01 * prng.normal(size=np.shape(a))
                        ).astype(np.asarray(a).dtype)
                    if np.issubdtype(np.asarray(a).dtype, np.floating)
                    else np.array(a, copy=True)
                    for n, a in w0.items()}

        reg = ModelRegistry(eng, swap_root, policy=args.swap_policy,
                            canary_requests=0, serial=0)
        reg.start(poll_s=0.1)
        _write_serial = write_weights_serial

    results = {"ok": 0, "shed": 0, "err": 0}
    rlock = threading.Lock()

    def on_done(fut):
        with rlock:
            if fut.exception() is None:
                results["ok"] += 1
            else:
                results["err"] += 1

    period = 1.0 / args.qps
    t_start = time.perf_counter()
    t_end = t_start + args.duration
    next_fire = t_start
    # commit serials at evenly spaced points INSIDE the window so every
    # swap happens under live load, none in the drain tail
    commit_at = [t_start + args.duration * (i + 1) / (n_swaps + 1)
                 for i in range(n_swaps)]
    sent = 0
    kv_peak_pages = 0
    peak_active = 0
    while True:
        now = time.perf_counter()
        peak_active = max(peak_active, eng._n_active)
        if eng._pool is not None:
            kv_peak_pages = max(kv_peak_pages, eng._pool.pages_live)
        if now >= t_end:
            break
        if commit_at and now >= commit_at[0]:
            commit_at.pop(0)
            serial = len(swap_serials) + 1
            _write_serial(swap_root, serial, _serial_weights())
            swap_serials.append(serial)
        if now < next_fire:
            time.sleep(min(next_fire - now, 0.002))
            continue
        next_fire += period
        try:
            eng.submit(pool[sent % len(pool)],
                       budgets[sent % len(budgets)]) \
                .add_done_callback(on_done)
            sent += 1
        except EngineOverloaded:
            with rlock:
                results["shed"] += 1
    if reg is not None:
        # give the watcher one beat to ingest the last committed serial,
        # then stop it before the drain (no swaps against an empty engine)
        deadline = time.perf_counter() + 5.0
        while swap_serials and reg.serial < swap_serials[-1] \
                and time.perf_counter() < deadline:
            time.sleep(0.02)
        reg.stop()
    eng.drain(timeout_s=60.0)
    snap = eng.metrics.snapshot()
    executables = eng.executables()
    spec = eng._spec
    eng.shutdown()

    win = ServingMetrics.window(warm, snap)
    spec_ticks_d = snap["spec_ticks"] - warm["spec_ticks"]
    drafted_d = snap["spec_draft_tokens"] - warm["spec_draft_tokens"]
    accepted_d = snap["spec_accepted_tokens"] - warm["spec_accepted_tokens"]
    ticks_d = snap["decode_ticks"] - warm["decode_ticks"]
    tokens_d = snap["tokens_generated"] - warm["tokens_generated"]
    return {
        "metric": f"serving_decode_openloop_{args.device.lower()}",
        "value": win["tokens_per_s"],
        "unit": "tokens/s",
        "offered_qps": args.qps,
        "duration_s": args.duration,
        "window_s": win["interval_s"],
        "sent": sent,
        "completed": results["ok"],
        "shed": results["shed"] + win["shed"],
        "errors": results["err"],
        "qps": win["qps"],
        "tick_rate": win["tick_rate"],
        "ttft_p50_ms": snap["ttft_p50_ms"],
        "ttft_p99_ms": snap["ttft_p99_ms"],
        "intertoken_p99_ms": snap["intertoken_p99_ms"],
        "p50_ms": snap["p50_ms"],
        "p99_ms": snap["p99_ms"],
        "tokens_generated": snap["tokens_generated"],
        "executables": executables,
        "compiles_after_warmup":
            snap["bucket_compiles"] - warm["bucket_compiles"],
        "slots": args.slots,
        "max_len": args.max_len,
        "short_new": args.short_new,
        "long_new": args.long_new,
        # paged KV cache (ISSUE 19): device KV footprint in both modes
        # (kvpool_hbm_bytes = the page pool incl. trash page; dense =
        # the [slots, max_len] caches) so two BENCH lines prove the
        # more-slots-at-equal-HBM claim, plus the sharing counters
        "paged": bool(getattr(model, "paged", False)),
        "page_size": model.page_size if getattr(model, "paged", False)
        else None,
        "num_pages": model.num_pages if getattr(model, "paged", False)
        else None,
        "kvpool_hbm_bytes": ((model.num_pages + 1) * model.page_size
                             * model.cfg.d_model * 4 * 2
                             * model.cfg.n_layer
                             if getattr(model, "paged", False) else None),
        "kvpool_peak_live_pages": (kv_peak_pages
                                   if getattr(model, "paged", False)
                                   else None),
        "kv_dense_bytes": kv_dense_bytes,
        "peak_active_slots": peak_active,
        "prefix_hits": snap["prefix_hits"] - warm["prefix_hits"],
        "prefill_skips": snap["prefill_skips"] - warm["prefill_skips"],
        "page_requeues": snap["page_requeues"] - warm["page_requeues"],
        "prefills": snap["prefills"] - warm["prefills"],
        "shared_prefix": bool(args.shared_prefix),
        "swaps": snap["model_swaps"] - warm["model_swaps"],
        "swap_policy": args.swap_policy if n_swaps > 0 else None,
        # speculative decoding (ISSUE 20): window acceptance, committed
        # tokens per engine tick (all slots; plain decode caps at one
        # per ACTIVE slot per tick, speculation at k+1), and the
        # per-spec-tick draft/verify cost split
        "spec_k": spec_k,
        "draft_layers": (spec.draft.model.cfg.n_layer
                         if spec is not None else None),
        "acceptance_rate": (round(accepted_d / drafted_d, 4)
                            if drafted_d else None),
        "tokens_per_tick": (round(tokens_d / ticks_d, 4)
                            if ticks_d else None),
        "spec_fallbacks": snap["spec_fallbacks"] - warm["spec_fallbacks"],
        "draft_ms": (round(spec.draft_s / spec_ticks_d * 1e3, 3)
                     if spec is not None and spec_ticks_d else None),
        "verify_ms": (round(spec.verify_s / spec_ticks_d * 1e3, 3)
                      if spec is not None and spec_ticks_d else None),
        "smoke": bool(args.smoke),
    }


def run_router_bench(args) -> dict:
    """Open-loop multi-model load through a ServingFleet (ISSUE 17).

    ``--models M x --replicas R`` tiny decode models behind one router;
    arrivals round-robin the models on the --qps schedule.  Latencies
    are measured end to end at the CLIENT (router queueing + failover
    included), per model; a sampler thread records the ready-replica
    count per model every 250 ms so the BENCH line carries the fleet's
    scaling trajectory, not just its endpoint."""
    import numpy as np

    from paddle_tpu.models import transformer
    from paddle_tpu.serving import (AutoscalePolicy, DecodeEngine,
                                    EngineOverloaded, ServingFleet)

    # the shared compile store is what makes an R-replica fleet warm in
    # one compile's time; give the bench one even when the env has none
    if not os.environ.get("PADDLE_COMPILE_CACHE_DIR"):
        from paddle_tpu import compile_cache

        os.environ["PADDLE_COMPILE_CACHE_DIR"] = \
            compile_cache.checkout_root()

    models = [f"m{i}" for i in range(args.models)]

    def factory(seed):
        def make(labels):
            model = transformer.DecodeModel(
                cfg=transformer.decode_lm_config(), max_slots=args.slots,
                max_len=args.max_len, prefill_buckets=[4, 8], seed=seed)
            return DecodeEngine(model, metrics_labels=labels)
        return make

    fleet = ServingFleet(
        {m: factory(11 + 2 * i) for i, m in enumerate(models)},
        replicas=args.replicas,
        hb_dir=tempfile.mkdtemp(prefix="bench_router_hb_"),
        # the bench measures the offered load, not idle-downscale churn:
        # pin the floor at the starting shape, let pressure scale out
        policy=AutoscalePolicy(min_replicas=args.replicas))
    t_warm = time.perf_counter()
    fleet.start(wait_ready_s=300.0)
    warm_s = time.perf_counter() - t_warm

    rng = np.random.RandomState(0)
    pool = [[int(t) for t in rng.randint(2, 60, size=3)]
            for _ in range(64)]
    budgets = [args.long_new if rng.random_sample() < 0.2
               else args.short_new for _ in range(256)]

    lat = {m: [] for m in models}       # client-side e2e seconds
    results = {m: {"ok": 0, "shed": 0, "err": 0} for m in models}
    rlock = threading.Lock()

    def on_done(model, t0):
        def cb(fut):
            dt = time.perf_counter() - t0
            with rlock:
                if fut.exception() is None:
                    results[model]["ok"] += 1
                    lat[model].append(dt)
                else:
                    results[model]["err"] += 1
        return cb

    trajectory = []
    stop_sampler = threading.Event()

    def sample():
        t0 = time.perf_counter()
        while not stop_sampler.wait(0.25):
            st = fleet.status()
            trajectory.append(
                {"t_s": round(time.perf_counter() - t0, 2),
                 **{m: st["models"][m]["ready"] for m in models}})

    sampler = threading.Thread(target=sample, daemon=True)
    sampler.start()

    period = 1.0 / args.qps
    t_start = time.perf_counter()
    t_end = t_start + args.duration
    next_fire = t_start
    sent = 0
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if now < next_fire:
            time.sleep(min(next_fire - now, 0.002))
            continue
        next_fire += period
        model = models[sent % len(models)]
        try:
            fleet.submit(model, pool[sent % len(pool)],
                         budgets[sent % len(budgets)]) \
                .add_done_callback(on_done(model, time.perf_counter()))
        except EngineOverloaded:
            with rlock:
                results[model]["shed"] += 1
        sent += 1
    fleet.router.drain(timeout_s=120.0)
    stop_sampler.set()
    sampler.join(timeout=5.0)
    window_s = time.perf_counter() - t_start
    status = fleet.status()
    fleet.shutdown(timeout_s=60.0)

    def pct(vals, q):
        return round(float(np.percentile(vals, q)) * 1e3, 3) \
            if vals else None

    per_model = {}
    for m in models:
        r = results[m]
        per_model[m] = {
            "completed": r["ok"],
            "qps": round(r["ok"] / window_s, 3),
            "p50_ms": pct(lat[m], 50),
            "p99_ms": pct(lat[m], 99),
            "shed": r["shed"] + status["models"][m]["shed"],
            "errors": r["err"],
            "replicas_final": status["models"][m]["ready"],
            "dispatched": status["models"][m]["dispatched"],
        }
    completed = sum(r["ok"] for r in results.values())
    return {
        "metric": f"serving_fleet_openloop_{args.device.lower()}",
        "value": round(completed / window_s, 3),
        "unit": "req/s",
        "offered_qps": args.qps,
        "duration_s": args.duration,
        "window_s": round(window_s, 3),
        "warm_s": round(warm_s, 3),
        "sent": sent,
        "completed": completed,
        "shed": sum(v["shed"] for v in per_model.values()),
        "errors": sum(r["err"] for r in results.values()),
        "p50_ms": pct([d for v in lat.values() for d in v], 50),
        "p99_ms": pct([d for v in lat.values() for d in v], 99),
        "models": per_model,
        "replica_trajectory": trajectory,
        "n_models": args.models,
        "replicas": args.replicas,
        "slots": args.slots,
        "max_len": args.max_len,
        "short_new": args.short_new,
        "long_new": args.long_new,
        "smoke": bool(args.smoke),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--model-dir", default="",
                   help="serve this saved inference model instead of the "
                        "built-in mnist-sized MLP")
    p.add_argument("--device", default="CPU", choices=["CPU", "TPU",
                                                       "cpu", "tpu"])
    p.add_argument("--duration", type=float, default=10.0,
                   help="seconds of offered load")
    p.add_argument("--qps", type=float, default=500.0,
                   help="open-loop offered request rate")
    p.add_argument("--max-batch-size", type=int, default=16)
    p.add_argument("--max-wait-ms", type=float, default=5.0)
    p.add_argument("--queue-depth", type=int, default=512)
    p.add_argument("--decode", action="store_true",
                   help="continuous-batching decode workload (DecodeEngine "
                        "with a mixed short/long token-budget mix)")
    p.add_argument("--slots", type=int, default=8,
                   help="decode slots (concurrent KV-cache streams)")
    p.add_argument("--max-len", type=int, default=128,
                   help="decode KV-cache capacity per slot")
    p.add_argument("--short-new", type=int, default=8,
                   help="short-request token budget (80%% of arrivals)")
    p.add_argument("--long-new", type=int, default=64,
                   help="long-request token budget (20%% of arrivals)")
    p.add_argument("--paged", type=int, default=-1, choices=[-1, 0, 1],
                   help="paged KV cache for --decode: 1 on, 0 dense, "
                        "-1 defer to PADDLE_SERVE_PAGED (ISSUE 19)")
    p.add_argument("--page-size", type=int, default=None,
                   help="tokens per KV page (--paged; default "
                        "PADDLE_SERVE_PAGE_SIZE)")
    p.add_argument("--num-pages", type=int, default=None,
                   help="device page-pool size (--paged; 0/unset = "
                        "max_slots * max_len / page_size).  Size this to "
                        "a SMALLER dense engine's kv_cache_bytes to "
                        "measure more slots at equal HBM")
    p.add_argument("--prefix-share", type=int, default=-1,
                   choices=[-1, 0, 1],
                   help="prefix sharing for --paged (default "
                        "PADDLE_SERVE_PREFIX_SHARE)")
    p.add_argument("--shared-prefix", action="store_true",
                   help="decode workload where every prompt shares one "
                        "full first page (drives prefix_hits / "
                        "prefill_skips)")
    p.add_argument("--spec", type=int, default=0,
                   help="speculative decoding: k draft tokens per tick "
                        "through a self-drafted verify dispatch "
                        "(ISSUE 20; 0 = off)")
    p.add_argument("--draft-layers", type=int, default=None,
                   help="self-draft depth for --spec (default "
                        "PADDLE_SERVE_SPEC_DRAFT_LAYERS; 0 = full-depth "
                        "self-draft, the acceptance-1.0 throughput "
                        "ceiling)")
    p.add_argument("--swaps", type=int, default=0,
                   help="hot-swap this many fresh serials through the "
                        "decode window (registry watcher; ISSUE 16)")
    p.add_argument("--swap-policy", default="immediate",
                   choices=["immediate", "drain"],
                   help="in-flight policy for --swaps")
    p.add_argument("--router", action="store_true",
                   help="multi-model fleet workload: --models x "
                        "--replicas decode replicas behind one router "
                        "(per-model qps/p50/p99/shed + the "
                        "replica-count trajectory)")
    p.add_argument("--models", type=int, default=2,
                   help="distinct models behind the router (--router)")
    p.add_argument("--replicas", type=int, default=2,
                   help="starting replicas per model (--router)")
    p.add_argument("--smoke", action="store_true",
                   help="2-second CPU sanity pass for CI")
    args = p.parse_args(argv)
    if args.smoke:
        args.duration = 2.0
        args.qps = min(args.qps, 40.0 if args.decode or args.router
                       else 200.0)
        args.device = "CPU"
        if args.decode or args.router:
            args.slots = min(args.slots, 4)
            args.max_len = min(args.max_len, 64)
            args.long_new = min(args.long_new, 32)
        if args.router:
            args.models = min(args.models, 2)
            args.replicas = min(args.replicas, 2)

    out = run_router_bench(args) if args.router \
        else run_decode_bench(args) if args.decode else run_bench(args)
    print(json.dumps(out))
    # smoke contract: the pass fails loudly if nothing was actually served
    if args.smoke and (out["completed"] == 0 or out["p50_ms"] is None):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
