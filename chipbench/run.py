#!/usr/bin/env python3
"""The chip benchmark's one command.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

One new process per run.  It finds the cell in ``BENCHMARK.json``, its
configuration under ``chipbench/configs/<config>/`` and its traffic mix in
``chipbench/traffic/<traffic>.json``; builds the ``Program`` through the
normal entry points with bf16 AMP and every kernel gate in AUTO; makes the
weights and the feed from ``--seed``; warms up the cell's own shapes;
measures for ``--seconds``; reads the device's memory as the window closes;
then, and only then, lets the window's state go, seeds again and compares
one step with the configuration's float32 reference; prints the contract's
JSON object as the last line.  Nothing of the comparison exists in the
process before the memory is read, so ``peak_hbm_gib`` and ``setup_s`` are
the trainer's own.

No chip is a failure, never a fallback.  ``--rehearse`` (CPU, the
configuration's ``tiny`` sizes, Pallas interpreted, four virtual devices)
walks every code path and prints counts only.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")
CACHE = os.path.join(ROOT, ".cache", "chipbench")


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"{what} {name!r} is not in BENCHMARK.json")


def metrics_for(entries, cell_name):
    """The metrics of a group that this cell reports: those with no
    ``workloads`` key, and those that list the cell."""
    return [m for m in entries
            if "workloads" not in m or cell_name in m["workloads"]]


def cell_sizes(config_entry, rehearse):
    """The configuration's sizes as this run uses them.  A file that breaks
    the rules of a cut configuration (``chipbench/cuts.py``) is refused
    here, before anything is built; the cut is the record's first line."""
    from chipbench import cuts

    sizes = read_json(ROOT, config_entry["file"])
    wrong = cuts.problems(sizes, config_entry)
    if wrong:
        raise SystemExit(f"{config_entry['file']}: " + "; ".join(wrong))
    print(cuts.line(sizes), flush=True)
    if rehearse:
        sizes = {**sizes, **sizes["tiny"]}
    return sizes


def prepare_environment(rehearse: bool, chips: int) -> None:
    """Before jax is imported: the compile caches at fixed paths inside the
    checkout (the path is part of the cache key), and the rehearsal's CPU."""
    # a rehearsal keeps its own: CPU entries written here must never reach
    # a chip run's directory (jax's size-bounded cache stops writing when
    # it finds entries another configuration left without their stamps)
    cache = os.path.join(CACHE, "rehearsal") if rehearse else CACHE
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cache, "jax")
    os.environ["PADDLE_COMPILE_CACHE_DIR"] = os.path.join(cache, "paddle")
    # no size bound: with one, jax evicts by last use, and a cell whose
    # programs another cell's pushed out would compile again in a warm run
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.makedirs(os.path.join(cache, "jax"), exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={max(chips, 4)} "
            "--xla_cpu_enable_concurrency_optimized_scheduler=false")
        os.environ["PADDLE_TPU_FLASH"] = "1"   # Pallas, interpreted
        os.environ["PADDLE_TPU_FUSED"] = "1"
    else:
        for knob in ("PADDLE_TPU_FLASH", "PADDLE_TPU_FUSED",
                     "PADDLE_TPU_SPD", "PADDLE_TPU_MESH"):
            os.environ.pop(knob, None)          # every gate in AUTO


class CompileWatch:
    """Counts what jax compiles or loads from its cache, while armed."""

    def __init__(self):
        import jax.monitoring

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if self.armed and event.endswith("backend_compile_duration"):
            self.count += 1


def counters(fluid, *names):
    c = fluid.profiler.counters()
    return {n: sum(v for k, v in c.items()
                   if k == n or k.startswith(n + "{")) for n in names}


class Cell:
    """One built cell: programs, executor, state from the seed.  It keeps
    no array: the state lives in the scope alone, and the reference makes
    its weights from the seed again when the comparison needs them."""

    def __init__(self, cell, config_entry, traffic, sizes, rehearse):
        import jax
        import numpy as np

        import paddle_tpu.fluid as fluid
        from chipbench import plugins

        self.jax, self.np, self.fluid = jax, np, fluid
        self.cell, self.traffic, self.sizes = cell, traffic, sizes
        self.rehearse = rehearse
        self.chips = cell["chips"]
        cfg_dir = os.path.dirname(config_entry["file"])
        rel = os.path.relpath(os.path.join(ROOT, cfg_dir), HERE)
        self.config_dir = rel
        self.builder = plugins.load(rel, "build")
        self.reference = plugins.load(rel, "reference")
        self.times = {}

        t0 = time.perf_counter()
        if sizes["precision"] != "bfloat16":
            raise SystemExit("this harness states bf16 AMP; the "
                             f"configuration states {sizes['precision']!r}")
        fluid.amp.enable("bfloat16", keep_activations=True)
        self.main = fluid.default_main_program()
        self.startup = fluid.default_startup_program()
        built = self.builder.build(fluid, sizes)
        self.loss = built["loss"]
        self.batch = int(sizes["batch_per_chip"] * self.chips
                         * traffic.get("batch_per_chip_scale", 1))
        self.units_per_step = built["units_per_sample"] * self.batch
        # the step compared with the reference: the same graph, nothing
        # random in it, same names so that it trains the same scope
        self.check_main = fluid.Program()
        with fluid.program_guard(self.check_main, fluid.Program()), \
                fluid.unique_name.guard():
            self.check_loss = self.builder.build(
                fluid, sizes, deterministic=True)["loss"]
        self.names = self.builder.trainable_names(self.main)
        if self.names != self.builder.trainable_names(self.check_main):
            raise SystemExit("the two builds name their parameters apart")
        # what the startup program sets, and sets again when it runs again
        self.startup_names = sorted({
            n for op in self.startup.global_block().ops
            for n in op.output_arg_names if n})
        self.times["build_s"] = time.perf_counter() - t0

    # -- state from the seed ------------------------------------------
    def seed_state(self, seed: int) -> dict:
        """Startup program (optimizer state, statistics), then the weights
        the REFERENCE makes from the seed, put into the scope by name.  No
        second copy of the parameters is alive at any time: an earlier
        state is let go before the startup program runs, the startup
        program's own parameters before the reference makes its, and the
        reference's arrays ARE the scope's.  The step donates them, so
        nothing keeps the list: ``check`` makes it from the seed again.
        Returns the seconds it took, by part."""
        fluid, jax = self.fluid, self.jax
        t0 = time.perf_counter()
        self.main.random_seed = self.startup.random_seed = seed
        self.check_main.random_seed = seed
        scope = fluid.global_scope()
        # an earlier state goes first: its slots stay, empty, until the
        # startup program fills them again
        for name in self.startup_names:
            if scope.has(name):
                scope.set(name, None)
        fluid.Executor(fluid.TPUPlace()).run(self.startup)
        took = {"startup_s": time.perf_counter() - t0}
        t0 = time.perf_counter()
        spec = self.reference.param_spec(self.sizes)
        if len(spec) != len(self.names):
            raise SystemExit(f"reference has {len(spec)} parameters, the "
                             f"program {len(self.names)}")
        for (ref_name, shape, _), name in zip(spec, self.names):
            have = tuple(self.np.shape(scope.get(name)))
            if have != tuple(shape):
                raise SystemExit(f"{name} is {have}, reference "
                                 f"{ref_name} is {tuple(shape)}")
            scope.set(name, None)
        # as they come, like the startup program's own: arrays that jax has
        # not committed to a device, which is why a trainer's SECOND call
        # lowers its step again (`relowerings` 1; PERF.md, Open questions)
        weights = self.reference.init_params(seed, self.sizes)
        for name, w in zip(self.names, weights):
            scope.set(name, w)
        del weights
        jax.block_until_ready([scope.get(n) for n in self.names])
        took["weights_s"] = time.perf_counter() - t0
        return took

    def feed(self, seed: int, batch: int, stream: int = 0):
        rng = self.np.random.RandomState((seed + 7919 * stream) % (2 ** 32))
        return self.builder.make_feed(self.sizes, batch, rng)

    def make_step(self, feed):
        """(dispatch, finish, lower) over the cell's entry point."""
        from chipbench import loop

        return loop.make_step(self.fluid, self.traffic, self.main,
                              self.loss, feed, self.chips)

    # -- the comparison that decides `correct` ------------------------
    def check(self, seed: int, dispatch, matmul_dtype=None) -> dict:
        """One deterministic step of the program on a small seeded batch
        against the float32 reference (or, with ``matmul_dtype``, the
        CONTROL in the program's place).  Must run right after
        ``seed_state(seed)``: the optimizer comparison is of the first
        step.  The reference makes its weights from the seed here, its own
        copy beside the scope's, and they go when this returns."""
        from chipbench import check, loop

        fluid = self.fluid
        feed = self.feed(seed, self.sizes["check_batch"] * self.chips
                         if self.traffic["entry"] != "executor"
                         else self.sizes["check_batch"], stream=1)
        weights = self.reference.init_params(seed, self.sizes)
        if matmul_dtype is not None:
            return check.control(self.reference, self.sizes, weights,
                                 feed, matmul_dtype)
        grads = [n + "@GRAD" for n in self.names]
        outs = loop.device_arrays(dispatch(
            program=self.check_main, feed=feed,
            fetch=[self.check_loss] + grads))
        scope = fluid.global_scope()
        after = [scope.get(n) for n in self.names]
        # loss, gradients and parameters go in as the device arrays they
        # are: nothing is copied to the host and sent back
        return check.program(self.reference, self.sizes, weights, feed,
                             outs[0], outs[1:], after)


def print_arrays_peak(devices, when):
    """``memory after <when>: arrays <peak_bytes_in_use> ...`` of the
    fullest chip, one line a phase in the order the phases run: weights,
    warm-up, window (where ``device_record`` takes the reading), comparison.
    Both marks only ever rise, so the first line that shows a value names
    the phase that reached it; up to the window's line they are the timed
    path's, and what the comparison's line shows above them is the
    harness's and is reported nowhere.  Returns the two marks added."""
    stats = [d.memory_stats() or {} for d in devices]
    if not any(stats):
        print(f"memory after {when}: this backend reports none", flush=True)
        return 0
    full = max(stats, key=lambda s: int(s.get("peak_bytes_in_use", 0))
               + int(s.get("peak_bytes_reserved", 0)))
    print(f"memory after {when}: arrays {full.get('peak_bytes_in_use')} at "
          f"the peak, {full.get('bytes_in_use')} now, program temporaries "
          f"{full.get('peak_bytes_reserved')} of {full.get('bytes_limit')} "
          "bytes", flush=True)
    return int(full.get("peak_bytes_in_use", 0)) \
        + int(full.get("peak_bytes_reserved", 0))


def device_record(jax, devices):
    """The device as jax reports it, and the peak HBM set aside on the
    fullest of the cell's chips: ``peak_bytes_in_use`` (arrays: weights,
    optimizer state, feeds, fetches) plus ``peak_bytes_reserved`` (the
    temporaries of the largest program that ran: activations, workspace).
    ``main`` calls it as the window closes, before the traced stretch and
    before anything of the comparison exists, so both marks belong to the
    timed path and to one time: the arrays' mark is the state, the feed,
    the fetches in flight and whatever seeding held beside them (at most
    one parameter tensor), the other is the step's temporaries.

    On the v5e the two do not overlap and the second is no fixed pool
    (chip run, PR 26): it is 0 in a new process and, after a program has
    run, within 0.004% of that program's ``temp_size_in_bytes`` from
    ``compiled.memory_analysis()`` (5.37, 8.59 and 12.89 GB for three
    programs of rising size), while ``bytes_in_use`` stayed at the 1 GiB
    argument throughout.  It never shrinks, so it is held to the end.  The
    traced run prints the step's own memory analysis beside it."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        in_use = int(stats.get("peak_bytes_in_use", 0))
        reserved = int(stats.get("peak_bytes_reserved", 0))
        print(f"memory {d.id}: arrays {in_use} + program temporaries "
              f"{reserved} of {stats.get('bytes_limit')} bytes; "
              + json.dumps(stats, sort_keys=True), flush=True)
        peak = max(peak, in_use + reserved)
    all_devs = jax.devices()
    return {"platform": all_devs[0].platform, "kind": all_devs[0].device_kind,
            "count": len(all_devs), "memory_peak_bytes": peak}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the raw trace and the lowered step here")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    bench = read_json(ROOT, "BENCHMARK.json")
    cell = find(bench["workloads"], args.workload, "workload")
    config_entry = find(bench["configs"], cell["config"], "config")
    traffic = read_json(HERE, "traffic", cell["traffic"] + ".json")
    sizes = cell_sizes(config_entry, args.rehearse)
    seconds = args.seconds if args.seconds is not None \
        else float(bench["run_seconds"])
    if args.rehearse:
        seconds = min(seconds, 1.0)
    prepare_environment(args.rehearse, cell["chips"])

    import jax

    devs = jax.devices()
    if not args.rehearse and devs[0].platform != "tpu":
        print(f"chipbench: jax found no TPU (devices: {devs})",
              file=sys.stderr)
        return 3
    if len(devs) < cell["chips"]:
        print(f"chipbench: the cell needs {cell['chips']} chips, jax sees "
              f"{len(devs)}", file=sys.stderr)
        return 3
    used = devs[:cell["chips"]]
    t_import = time.perf_counter() - T_START

    from chipbench import check, loop, plugins, stats

    watch = CompileWatch()
    built = Cell(cell, config_entry, traffic, sizes, args.rehearse)
    fluid = built.fluid
    built.times["import_and_device_s"] = t_import
    built.times.update(built.seed_state(args.seed))
    print_arrays_peak(used, "weights")
    feed = built.feed(args.seed, built.batch)
    dispatch, finish, lower = built.make_step(feed)

    t0 = time.perf_counter()
    first_loss = finish(dispatch())
    built.times["first_call_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm = loop.run_window(dispatch, finish, steps=traffic["warmup_steps"],
                           lookahead=traffic["lookahead"])
    built.times["warmup_s"] = time.perf_counter() - t0
    print_arrays_peak(used, "warm-up")

    # a mix the standard loop cannot express brings its own, as a file
    own = plugins.load("traffic", cell["traffic"])
    run_window = own.run_window if own else loop.run_window

    before = counters(fluid, "executor.dispatches", "compile_cache.miss")
    watch.armed = True
    setup_s = time.perf_counter() - T_START
    window = run_window(dispatch, finish, seconds=seconds,
                        lookahead=traffic["lookahead"])
    watch.armed = False
    after = counters(fluid, "executor.dispatches", "compile_cache.miss")

    run = {
        "workload": cell["name"], "chips": cell["chips"],
        "stamps": window["stamps"], "dispatch_s": window["dispatch_s"],
        "units_per_step": built.units_per_step, "setup_s": setup_s,
        "samples_per_step": built.batch,
        "times": dict(built.times), "steps": len(window["stamps"]) - 1,
        "dispatches": after["executor.dispatches"]
        - before["executor.dispatches"],
        "dispatched_steps": window["attempted"],
        "compiles_in_window": watch.count + after["compile_cache.miss"]
        - before["compile_cache.miss"],
        "device_kind": devs[0].device_kind,
    }
    losses = [first_loss] + warm["losses"] + window["losses"]
    attempted = 1 + warm["attempted"] + window["attempted"]
    # the reading, as the window closes: state, feed, fetches, the loaded
    # step and its temporaries, and nothing of the comparison's
    print_arrays_peak(used, "window")
    device = device_record(jax, used)
    run["memory_peak_bytes"] = read = device["memory_peak_bytes"]

    if args.trace:
        from chipbench import tracing

        run.update(tracing.traced_stretch(
            built, dispatch, finish, lower, traffic,
            os.path.join(CACHE, "trace", cell["name"]), args.keep_trace))
        losses += run.pop("traced_losses")
        attempted += run["steps_traced"]
    failed = sum(1 for v in losses if v != v or abs(v) == float("inf"))

    # the comparison, last: the window's state goes, the seed makes it
    # again, and the first step from it is set against the reference
    t_compare = time.perf_counter()
    after_window = {"reseed_s": sum(built.seed_state(args.seed).values())}
    t0 = time.perf_counter()
    numbers = built.check(args.seed, dispatch)
    limits = sizes.get("limits", {})
    verdict = check.decide(numbers, limits)
    after_window["reference_check_s"] = time.perf_counter() - t0
    compared = list(check.report(numbers, limits))
    print("\n".join(compared), flush=True)
    print(f"comparison: began {t_compare - T_START:.3f} s after the "
          f"process, which opened its window at {setup_s:.3f} s",
          flush=True)
    needs = print_arrays_peak(used, "comparison")
    if needs:
        print(f"the comparison's own need: {needs - read} bytes over the "
              f"reading of {read} (arrays at their peak + program "
              "temporaries, while `correct` was decided, less the same as "
              "the window closed); reported nowhere", flush=True)

    correct = bool(verdict and failed == 0
                   and run["compiles_in_window"] == 0)
    group = "per_layer" if args.trace else "end_to_end"
    kind = "layer_metrics" if args.trace else "metrics"
    metrics = {}
    for m in metrics_for(bench[group], cell["name"]):
        mod = plugins.load(kind, m["name"])
        if mod is None:
            raise SystemExit(f"no chipbench/{kind}/{m['name']}.py")
        if args.rehearse and m["unit"] != "count":
            continue                      # a CPU gives counts only
        v = mod.value(run)
        if v is None:
            continue                      # nothing to read: left out
        metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    print("set-up: " + ", ".join(f"{k} {v:.3f}"
                                 for k, v in run["times"].items())
          + "; after the window, in no metric: "
          + ", ".join(f"{k} {v:.3f}" for k, v in after_window.items()),
          flush=True)
    if run.get("step_memory"):
        print("step memory (compiler): " + json.dumps(run["step_memory"]),
              flush=True)
    print(f"window: {run['steps']} steps, compiles in window "
          f"{run['compiles_in_window']}, losses first {losses[0]:.5f} "
          f"last {losses[-1]:.5f}", flush=True)
    # a stall that the 95th percentile does not see and the throughput
    # does: where it was and how long, for whoever reads a far-off run
    gaps = stats.intervals(window["stamps"])
    median = stats.percentile(gaps, 50.0)
    late = [(i, g) for i, g in enumerate(gaps) if g > 2 * median]
    print(f"intervals: median {1e3 * median:.3f} ms, longest "
          f"{1e3 * max(gaps):.3f} ms at step {gaps.index(max(gaps))}, "
          f"{len(late)} over twice the median taking "
          f"{sum(g for _, g in late):.3f} s of the window's "
          f"{window['stamps'][-1] - window['stamps'][0]:.3f} s", flush=True)
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if args.trace:
        if not args.rehearse:
            device["busy_s"] = run["trace"]["busy_s"]
            device["window_s"] = run["trace"]["window_s"]
            out["breakdown"] = run["breakdown"]
    if args.rehearse:
        print("rehearsal counts: " + json.dumps(
            {"steps": run["steps"], "dispatches": run["dispatches"],
             "trace": run.get("trace_counts")}), flush=True)
    # the numbers compared, beside their limits, as the line's last key and
    # as the last lines of the errors too: of a run that is not correct the
    # driver keeps the end of each
    # (a number that is not finite goes as its name: JSON has no NaN)
    out["compared"] = {
        k: {"value": v if v is None or abs(v) < float("inf") else repr(v),
            "limit": limits.get(k)}
        for k in check.KEYS for v in [numbers.get(k)]}
    print("\n".join(compared), file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
