"""What the program says of itself: the spans of ``paddle_tpu.observe.trace``.

The harness times ``run()`` and set-up from outside (``host_dispatch_ms``,
``first_call_s``).  Since PR 27 the program writes spans of its own, through
one primitive, and this file holds the two readers of them.  Where the
program has no such spans (a commit before PR 27) both find nothing, return
None, and the metric is left out of the line.

**The ring** (same process, ``trace.recorded()``: every ended span with its
``time.perf_counter`` stamps, the clock of ``run["stamps"]``):

- ``run_split``: per ``fluid.run`` root that BEGINS inside the unprofiled
  window (``stamps[0]`` .. ``stamps[-1]``), the SELF time of each child
  (its duration less its own children's: ``fluid.run.lookup`` less a
  ``fluid.run.build``, ``fluid.run.call`` less jax's compile phases), summed
  by name under that root (``ParallelExecutor.run`` has two
  ``fluid.run.feed``), and the median of each over the roots.
- ``compile_seconds``: the union of the ``fluid.compile.*`` spans of the
  given names that ended before the window opened.  A union, not a sum: an
  eager jax operation inside a traced one would otherwise count twice.
- ``relowerings``: the program's ``executor.relowerings`` counter.

**The trace** the run just wrote (``.cache/chipbench/trace/<workload>/``,
newest ``.xplane.pb``): host spans whose names start ``fluid.`` or
``bench.``, and the device planes as ``trace_reduce`` reads them.

- ``idle_under_run``: device idle time in the gaps whose midpoint lies
  inside a ``fluid.run`` root, over the traced window, worst device; and
  every gap named by the INNERMOST host span open at its midpoint.

The clock.  ``trace_reduce.from_profile`` places device events by
``device_offset_ps`` and host spans by ``start_ns``.  The two agree to under
a nanosecond as NUMBERS on one chip (``plane_clock``; by up to 59 us on a
four-chip host, per plane), but on the v5e the device's
clock runs behind the host's: after a drain the first step's module starts,
on the device's clock, 1.7 ms BEFORE the runtime enqueues it on the host's
(``DoEnqueueProgram``), and 63 us before ``fluid.run.call`` even opens (my
chip run, PR 27; 0.95 ms on the four-chip host).  ``device_skew_ns``
measures that per device, as the least shift that puts the first step's
start after the last host event that must precede it, and ``read_trace``
moves the device events by it.  The fetches
bound it from above (a fetch closes only after its step ended on the device).
"""

from __future__ import annotations

import glob
import itertools
import os
import statistics
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from chipbench import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACES = os.path.join(ROOT, ".cache", "chipbench", "trace")

RUN_ROOT = "fluid.run"
CHILDREN = ("feed", "lookup", "state", "call", "commit", "observe")
HOST_PREFIXES = ("fluid.", "bench.")


# -- the ring -----------------------------------------------------------


def ring():
    """The program's ended spans, oldest first; None where the program
    keeps none."""
    try:
        from paddle_tpu.observe import trace

        return trace.recorded()
    except (ImportError, AttributeError):
        return None


def split_of(spans, t_open: float, t_close: float) -> Optional[dict]:
    """The host split of one ``run()``: for the ``fluid.run`` roots that
    begin in [t_open, t_close], the median root duration and the median
    self time of each child name, in ms.  None when there is no root."""
    kids: Dict[Optional[str], list] = {}
    for s in spans:
        kids.setdefault(s.parent_id, []).append(s)

    def self_s(s):
        return (s.t1 - s.t0) - sum(c.t1 - c.t0
                                   for c in kids.get(s.span_id, ()))

    roots = [s for s in spans
             if s.name == RUN_ROOT and t_open <= s.t0 <= t_close]
    if not roots:
        return None
    per_root = []
    for r in roots:
        by = dict.fromkeys(CHILDREN, 0.0)
        other = 0.0
        for c in kids.get(r.span_id, ()):
            name = c.name[len(RUN_ROOT) + 1:]
            if name in by:
                by[name] += self_s(c)
            else:
                other += c.t1 - c.t0
        per_root.append((r.t1 - r.t0, by, other))
    out = {"roots": len(roots),
           "root_ms": 1e3 * statistics.median(d for d, _, _ in per_root),
           "other_children_ms": 1e3 * statistics.median(
               o for _, _, o in per_root)}
    for name in CHILDREN:
        out[name + "_ms"] = 1e3 * statistics.median(
            by[name] for _, by, _ in per_root)
    return out


def run_split(run) -> Optional[dict]:
    """The split of this run, made and printed once: kept in the run's own
    record, which six metrics read in turn."""
    if "program_split" in run:
        return run["program_split"]
    spans = ring()
    split = run["program_split"] = split_of(
        spans, run["stamps"][0], run["stamps"][-1]) if spans else None
    if split is not None:
        six = sum(split[c + "_ms"] for c in CHILDREN)
        outside = 1e3 * statistics.median(run["dispatch_s"])
        print("run() host split, median self ms over "
              f"{split['roots']} fluid.run roots: "
              + ", ".join(f"{c} {split[c + '_ms']:.4f}" for c in CHILDREN)
              + f"; six sum {six:.4f}, root {split['root_ms']:.4f}, "
              f"from outside (host_dispatch_ms) {outside:.4f}", flush=True)
        a, b, inside = longest_interval(spans, run["stamps"])
        print(f"longest interval: {1e3 * (b - a):.3f} ms, of which "
              f"{1e3 * inside:.3f} ms inside fluid.run roots (the rest the "
              "host spent waiting for a fetch, or outside the program)",
              flush=True)
    return split


def longest_interval(spans, stamps):
    """(start, end, seconds of it inside ``fluid.run`` roots) of the
    longest interval between consecutive completion stamps: whether a
    stalled window (PERF.md, PR 26 finding 6) froze inside ``run()`` or
    waited on the device."""
    a, b = max(zip(stamps, stamps[1:]), key=lambda ab: ab[1] - ab[0])
    inside = sum(max(0.0, min(s.t1, b) - max(s.t0, a))
                 for s in spans if s.name == RUN_ROOT)
    return a, b, inside


def run_child_ms(run, child: str) -> Optional[float]:
    split = run_split(run)
    return None if split is None else split[child + "_ms"]


def compile_seconds(run, names: Sequence[str]) -> Optional[float]:
    """Seconds in the ``fluid.compile.*`` spans named, from process start
    to the window's first stamp."""
    spans = ring()
    if spans is None:
        return None
    t_open = run["stamps"][0]
    got = [(s.t0, s.t1) for s in spans
           if s.name in names and s.t1 <= t_open]
    if not run.get("program_setup_printed"):
        run["program_setup_printed"] = True
        by = {}
        for s in spans:
            if s.name.startswith("fluid.compile.") and s.t1 <= t_open:
                n, t = by.get(s.name, (0, 0.0))
                by[s.name] = (n + 1, t + s.t1 - s.t0)
        t = run["times"]
        # the comparison runs after the window: its spans end after
        # ``t_open`` and its seconds are no part of ``times``
        outside = sum(t.get(k, 0.0) for k in (
            "startup_s", "first_call_s", "warmup_s"))
        print("set-up from inside, before the window: " + ", ".join(
            f"{n} x{c} {s:.3f} s" for n, (c, s) in sorted(by.items()))
            + f"; from outside startup + first_call + "
            f"warmup {outside:.3f} s", flush=True)
    # the union is unit-free: seconds in, seconds out
    return trace_reduce.union_ns(got)


def relowerings(run) -> Optional[float]:
    """``executor.relowerings``: lowerings jax made under a step call for
    which the executor's own cache held an entry.  A count, so a rehearsal
    prints it too, with the counts it rests on."""
    spans = ring()
    if spans is None:
        return None
    import paddle_tpu.fluid as fluid

    c = fluid.profiler.counters()
    roots = [s for s in spans if s.name == RUN_ROOT]
    in_window = [s for s in roots
                 if run["stamps"][0] <= s.t0 <= run["stamps"][-1]]
    print(f"program spans: {len(spans)} in the ring, {len(roots)} "
          f"fluid.run roots ({len(in_window)} begin in the window), "
          f"lowerings {int(c.get('compile.lowerings', 0))}, backend "
          f"compiles or loads {int(c.get('compile.backend_compiles', 0))}, "
          f"relowerings {int(c.get('executor.relowerings', 0))}",
          flush=True)
    return float(c.get("executor.relowerings", 0))


# -- the trace ----------------------------------------------------------


class HostSpan(NamedTuple):
    name: str
    start_ns: float
    end_ns: float
    line: str


def newest_trace(workload: str) -> Optional[str]:
    pbs = glob.glob(os.path.join(TRACES, workload, "**", "*.xplane.pb"),
                    recursive=True)
    return max(pbs, key=os.path.getmtime) if pbs else None


MODULES_LINE = "XLA Modules"


def plane_clock(plane):
    """For one device plane: (how far ``start_ns`` lies ahead of
    ``device_offset_ps / 1000``, a constant of the plane read from its
    first operations; the start on the ``device_offset_ps`` base of the
    first STEP the plane ran: the first module at least half as long as
    its longest, so that a small program placed before it, a feed's
    re-sharding, is not taken for it; None without a modules line)."""
    diffs, modules = [], []
    for line in plane.lines:
        if line.name in trace_reduce.OPS_LINES:
            for ev in itertools.islice(line.events, 64):
                off = dict(ev.stats).get("device_offset_ps")
                if off is not None:
                    diffs.append(float(ev.start_ns) - float(off) / 1e3)
        elif line.name == MODULES_LINE:
            for ev in line.events:
                st = dict(ev.stats)
                off, dur = (st.get("device_offset_ps"),
                            st.get("device_duration_ps"))
                modules.append((float(off) / 1e3 if off is not None
                                else float(ev.start_ns),
                                float(dur) / 1e3 if dur is not None
                                else float(ev.duration_ns)))
    offset = statistics.median(diffs) if diffs else 0.0
    if not modules:
        return offset, None
    longest = max(d for _, d in modules)
    return offset, min(s for s, d in modules if d >= longest / 2)


#: host events of the runtime that must precede the start of a program on
#: the device, the latest in the launch first
LAUNCH_ANCHORS = ("DoEnqueueProgram", "tpu::System::Execute",
                  "PJRT_LoadedExecutable_Execute")


def device_skew_ns(first_device_ns: float, launches: dict,
                   call_open_ns: Optional[float]):
    """(shift in ns, name of the anchor): how far the device events must
    move for the first of them, after a drain, not to precede the host
    event that launched it.  ``launches`` maps a ``LAUNCH_ANCHORS`` name to
    the start of its first occurrence once the first ``fluid.run.call`` was
    open; without one the call's own opening is the anchor.  0 when the
    order already holds: the bases may still differ, by less than the
    launch takes."""
    for name in LAUNCH_ANCHORS:
        if name in launches:
            return max(0.0, launches[name] - first_device_ns), name
    if call_open_ns is None:
        return 0.0, None
    return max(0.0, call_open_ns - first_device_ns), "fluid.run.call"


def read_trace(path: str):
    """(device plane -> op events moved onto the host's base, host spans
    of the program and of the benchmark, what was learned of the clock)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices = trace_reduce.from_profile(pd).devices
    host: List[HostSpan] = []
    runtime, planes = [], {}
    for plane in pd.planes:
        if plane.name in devices:
            planes[plane.name] = plane_clock(plane)
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                start = float(ev.start_ns)
                if ev.name.startswith(HOST_PREFIXES):
                    host.append(HostSpan(ev.name, start,
                                         start + float(ev.duration_ns),
                                         line.name))
                elif ev.name in LAUNCH_ANCHORS:
                    runtime.append((start, ev.name))
    host.sort(key=lambda s: (s.start_ns, -s.end_ns))
    call = next((s for s in host if s.name == RUN_ROOT + ".call"), None)
    launches = {}
    for start, name in sorted(runtime):
        if call is not None and start >= call.start_ns:
            launches.setdefault(name, start)
    clock = {"number_offset_ns": {}, "skew_ns": {}, "step_start_ns": {},
             "anchor": None}
    for name, events in devices.items():
        offset, step_start = planes[name]
        if step_start is None:
            step_start = events[0].start_ns
        skew, anchor = device_skew_ns(
            step_start + offset, launches,
            call.start_ns if call is not None else None)
        clock["number_offset_ns"][name] = offset
        clock["skew_ns"][name], clock["anchor"] = skew, anchor
        clock["step_start_ns"][name] = step_start + offset + skew
        if offset + skew:
            devices[name] = [
                trace_reduce.Event(e.name, e.start_ns + offset + skew,
                                   e.dur_ns) for e in events]
    return devices, host, clock


def gaps_of(events) -> List[Tuple[float, float]]:
    """(midpoint, length) in ns of every idle gap between consecutive
    device operations, as ``trace_reduce.idle_gaps`` finds them."""
    out, end = [], None
    for e in events:
        if end is not None and e.start_ns > end:
            out.append(((end + e.start_ns) / 2, e.start_ns - end))
        end = e.end_ns if end is None else max(end, e.end_ns)
    return out


def innermost_at(host: Sequence[HostSpan], mids: Sequence[float]):
    """For each midpoint (ascending), (name of the innermost span open
    there, whether a ``fluid.run`` root is open there).  ``host`` is
    sorted by start; the innermost is the latest to have started."""
    out, active, i = [], [], 0
    for mid in mids:
        while i < len(host) and host[i].start_ns <= mid:
            active.append(host[i])
            i += 1
        active = [s for s in active if s.end_ns >= mid]
        if not active:
            out.append(("host:none", False))
            continue
        fluid = [s for s in active if s.name.startswith("fluid.")]
        inner = max(fluid or active, key=lambda s: s.start_ns)
        out.append((inner.name, any(s.name == RUN_ROOT for s in active)))
    return out


def idle_by_span(devices, host) -> Optional[dict]:
    """Worst device's idle share under ``fluid.run`` roots, and its idle
    seconds by innermost host span, longest first."""
    worst = None
    for name, events in devices.items():
        _, window_s = trace_reduce.busy_and_window(events)
        if window_s <= 0:
            continue
        gaps = gaps_of(events)
        where = innermost_at(host, [m for m, _ in gaps])
        under = sum(g for (_, g), (_, r) in zip(gaps, where) if r) / 1e9
        by: Dict[str, float] = {}
        for (_, g), (n, _) in zip(gaps, where):
            by[n] = by.get(n, 0.0) + g / 1e9
        got = {"device": name, "pct": 100.0 * under / window_s,
               "under_run_s": under, "window_s": window_s,
               "by_span": sorted(by.items(), key=lambda kv: -kv[1])}
        if worst is None or got["pct"] > worst["pct"]:
            worst = got
    return worst


def clock_check(step_starts: Sequence[float], host) -> Optional[dict]:
    """The start of the devices' first step (moved) against the first
    ``fluid.run.call`` and the first ``bench.fetch``: after a drain the
    device can start the step only once the call has opened, and the fetch
    that waits for that step closes only after it."""
    call = next((s for s in host if s.name == "fluid.run.call"), None)
    fetch = next((s for s in host if s.name == "bench.fetch"), None)
    if not step_starts or call is None or fetch is None:
        return None
    first = min(step_starts)
    return {"first_device_ns": first, "call_opens_ns": call.start_ns,
            "fetch_closes_ns": fetch.end_ns,
            "holds": call.start_ns < first < fetch.end_ns}


def idle_under_run(run) -> Optional[float]:
    if not run.get("trace"):
        return None               # no device plane (a rehearsal), no trace
    path = newest_trace(run["workload"])
    if path is None:
        return None
    devices, host, clock = read_trace(path)
    if not any(s.name == RUN_ROOT for s in host):
        return None               # the program wrote no span
    got = idle_by_span(devices, host)
    if got is None:
        return None
    print("idle gaps by program span: " + ", ".join(
        f"{n} {s:.6f} s" for n, s in got["by_span"][:12])
        + f"; under fluid.run {got['under_run_s']:.6f} s of a "
        f"{got['window_s']:.6f} s window on {got['device']}", flush=True)
    check = clock_check(list(clock["step_start_ns"].values()), host)
    if check is not None:
        skews = ", ".join(
            f"{d} {v:.0f} (+ {clock['number_offset_ns'][d]:.3f} between "
            "the bases as numbers)"
            for d, v in sorted(clock["skew_ns"].items()))
        print(f"clock: device events moved later by (ns) {skews} so that "
              f"the first step follows {clock['anchor']}; then "
              f"the first fluid.run.call opens at "
              f"{check['call_opens_ns']:.0f} ns, the first step starts "
              f"on a device at {check['first_device_ns']:.0f} ns, the first "
              f"bench.fetch closes at {check['fetch_closes_ns']:.0f} ns: "
              f"call < device < fetch "
              f"{'holds' if check['holds'] else 'DOES NOT HOLD'}",
              flush=True)
    return got["pct"]
