#!/usr/bin/env python3
"""Where is the host while a benchmark window stalls?  (ROADMAP S13)

Runs ``chipbench/run.py`` unchanged, in this process, and watches its timed
window from the outside of the loop:

- every ``dispatch`` and ``finish`` of the window is stamped, so a late
  interval (one over ``--factor`` times the median) is split into the time
  inside each;
- ``gc.callbacks`` records every collection with its generation;
- a second THREAD wakes every 20 ms and notes the main thread's stack: it
  needs a timer and the GIL only, so where its own ticks stop the whole
  process stood still (or something held the GIL);
- a second PROCESS that imports nothing sleeps 20 ms at a time and logs its
  own gaps over 60 ms on the machine's monotonic clock: where it stops at
  the same moments, the machine stood still and not this process.

    python3 tools/stallwatch.py --out chiprun_out/watch.json -- \\
        --workload <cell> --seed <n> --seconds 34 --trace 0

prints ``stallwatch: ...`` lines after run.py's own and writes the record to
``--out``.  PR 52 read with it that the stall is the machine's: PERF.md
section 6.  The watcher costs the window nothing that shows (the cell's
throughput under it is the unwatched one's to 0.002%).
"""

import argparse
import gc
import json
import os
import runpy
import subprocess
import sys
import threading
import time

HEARTBEAT = """
import sys, time
prev = time.perf_counter()
with open(sys.argv[1], "w") as f:
    while True:
        time.sleep(0.02)
        t = time.perf_counter()
        if t - prev > 0.06:
            f.write("%r %r\\n" % (prev, t)); f.flush()
        prev = t
"""


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True, help="the record, as JSON")
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="the checkout whose run.py runs")
    ap.add_argument("--factor", type=float, default=2.0,
                    help="an interval is late over this many medians")
    ap.add_argument("--min-seconds", type=float, default=5.0,
                    help="watch only a window asked to last longer")
    ap.add_argument("run_args", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    run_args = [a for a in args.run_args if a != "--"]

    sys.path.insert(0, args.root)
    os.chdir(args.root)
    clock = time.perf_counter
    main_id = threading.main_thread().ident
    samples, collections, phases, state = [], [], [], {"on": False}

    def on_gc(phase, info):
        if phase == "start":
            state["gc"] = clock()
        else:
            collections.append((state["gc"], clock(), info["generation"]))

    gc.callbacks.append(on_gc)

    def sampler():
        while True:
            time.sleep(0.02)
            if not state["on"]:
                continue
            t, frame, where = clock(), sys._current_frames().get(main_id), []
            while frame is not None and len(where) < 4:
                where.append(f"{os.path.basename(frame.f_code.co_filename)}:"
                             f"{frame.f_lineno}:{frame.f_code.co_name}")
                frame = frame.f_back
            samples.append((t, " < ".join(where)))

    threading.Thread(target=sampler, daemon=True).start()
    beat_log = args.out + ".heartbeat"
    beat = subprocess.Popen([sys.executable, "-c", HEARTBEAT, beat_log])

    from chipbench import loop

    plain = loop.run_window

    def watched(dispatch, finish, **kw):
        def stamped(kind, call):
            def inner(*a):
                t0 = clock()
                out = call(*a)
                phases.append((kind, t0, clock()))
                return out
            return inner

        timed = (kw.get("seconds") or 0) > args.min_seconds
        state["on"] = timed
        window = plain(stamped("dispatch", dispatch),
                       stamped("finish", finish), **kw)
        if timed:
            state["on"], state["window"] = False, window
        return window

    loop.run_window = watched

    def gaps_of(stamps, over):
        return [(a, b - a) for a, b in zip(stamps, stamps[1:])
                if b - a > over]

    def report():
        window = state.get("window")
        if not window:
            print("stallwatch: no timed window seen", flush=True)
            return
        stamps = window["stamps"]
        first, last = stamps[0], stamps[-1]
        steps = [b - a for a, b in zip(stamps, stamps[1:])]
        median = sorted(steps)[len(steps) // 2]
        late = [i for i, g in enumerate(steps) if g > args.factor * median]
        timed = [c for c in collections if first <= c[0] <= last]
        mine = gaps_of([s[0] for s in samples], 0.06)
        other = []
        try:
            with open(beat_log) as f:
                other = [tuple(map(float, line.split())) for line in f]
        except OSError:
            pass
        other = [(a, b - a) for a, b in other if b > first and a < last]
        print(f"stallwatch: {len(steps)} intervals, median "
              f"{1e3 * median:.2f} ms, {len(late)} late; {len(timed)} "
              f"collections, longest "
              f"{1e3 * max([c[1] - c[0] for c in timed] or [0]):.1f} ms, "
              f"generations {sorted({c[2] for c in timed})}; the sampler "
              f"stood still over 60 ms {len(mine)} times, longest "
              f"{1e3 * max([g for _, g in mine] or [0]):.1f} ms; the other "
              f"process {len(other)} times, longest "
              f"{1e3 * max([g for _, g in other] or [0]):.1f} ms",
              flush=True)
        record = {
            "median_ms": 1e3 * median, "late": [],
            "collections": [(c[0] - first, c[1] - c[0], c[2])
                            for c in timed],
            "sampler_gaps": [(a - first, g) for a, g in mine],
            "other_process_gaps": [(a - first, g) for a, g in other],
            "slow_intervals": [(i, stamps[i] - first, g) for i, g in
                               enumerate(steps) if g > 1.1 * median]}
        for i in late:
            a, b = stamps[i], stamps[i + 1]
            seen = [s for s in samples if a <= s[0] <= b]
            stacks = {}
            for _, where in seen:
                stacks[where] = stacks.get(where, 0) + 1
            entry = {
                "step": i, "seconds": b - a,
                "phases": [(k, t0 - a, t1 - t0) for k, t0, t1 in phases
                           if t1 > a and t0 < b],
                "collections": [(c[0] - a, c[1] - c[0], c[2])
                                for c in collections
                                if c[1] > a and c[0] < b],
                "sampler_ticks": len(seen),
                "sampler_gaps": [(x - a, g) for x, g in mine
                                 if x + g > a and x < b],
                "other_process_gaps": [(x - a, g) for x, g in other
                                       if x + g > a and x < b],
                "stacks": sorted(stacks.items(), key=lambda kv: -kv[1])[:4]}
            record["late"].append(entry)
            print("stallwatch LATE " + json.dumps(entry), flush=True)
        with open(args.out, "w") as f:
            json.dump(record, f)

    sys.argv = [os.path.join(args.root, "chipbench", "run.py")] + run_args
    try:
        runpy.run_path(sys.argv[0], run_name="__main__")
    finally:
        beat.kill()
        report()


if __name__ == "__main__":
    main()
