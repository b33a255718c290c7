"""From a profiler trace (``.xplane.pb``) to numbers, with jax alone.

The reduction every PR is measured with lives here, beside the benchmark,
and is checked against small traces under ``chipbench/testdata/``:

- ``read(path)``: device planes (their op-level events) and the host spans
  the benchmark wrote with ``jax.profiler.TraceAnnotation``.
- ``busy_and_window``: the union of the intervals in which an operation ran
  on a device, and the window from its first event's start to its last
  event's end.
- ``self_times``: per-event time minus what its children on the same line
  cover, so that a nested event is not counted twice.
- ``kernel_roofline``: sum of least times of the matched kernel events,
  each from its own shapes, over the sum of the same events' durations;
  withheld when no family's events equal its calls.
- ``exposed_collective_s``: collective time during which no other operation
  runs on that device.
- ``idle_gaps``: the longest gaps between device operations, named by the
  host span open at that time.
"""

from __future__ import annotations

import re
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

OPS_LINES = ("XLA Ops",)
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|collective-broadcast")
HOST_SPAN_PREFIX = "bench."


class Event(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self):
        return self.start_ns + self.dur_ns


class Trace(NamedTuple):
    devices: Dict[str, List[Event]]             # plane name -> op events
    host_spans: List[Tuple[str, float, float]]  # (name, start_ns, end_ns)


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "SparseCore" not in name \
        and not name.startswith("/device:CUSTOM")


def read(path: str) -> Trace:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


def from_profile(pd) -> Trace:
    devices, spans = {}, []
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            evs = []
            for line in plane.lines:
                if line.name not in OPS_LINES:
                    continue
                for ev in line.events:
                    # the device's own picosecond clock where the profiler
                    # recorded it: start_ns / duration_ns are cut to whole
                    # nanoseconds, 0.1% of a short operation
                    st = dict(ev.stats)
                    start = st.get("device_offset_ps")
                    dur = st.get("device_duration_ps")
                    evs.append(Event(
                        ev.name,
                        float(start) / 1e3 if start is not None
                        else float(ev.start_ns),
                        float(dur) / 1e3 if dur is not None
                        else float(ev.duration_ns)))
            if evs:
                evs.sort(key=lambda e: (e.start_ns, -e.dur_ns))
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        spans.append((ev.name, float(ev.start_ns),
                                      float(ev.start_ns + ev.duration_ns)))
    spans.sort(key=lambda s: s[1])
    return Trace(devices, spans)


def union_ns(intervals: Sequence[Tuple[float, float]]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_and_window(events: Sequence[Event]) -> Tuple[float, float]:
    """(busy seconds, window seconds) of one device."""
    if not events:
        return 0.0, 0.0
    busy = union_ns([(e.start_ns, e.end_ns) for e in events])
    window = max(e.end_ns for e in events) - min(e.start_ns for e in events)
    return busy / 1e9, window / 1e9


def self_times(events: Sequence[Event]) -> List[float]:
    """Per event (in the given, start-sorted order): its duration minus the
    part its direct children cover, in ns."""
    out = [e.dur_ns for e in events]
    stack: List[int] = []
    for i, e in enumerate(events):
        while stack and events[stack[-1]].end_ns <= e.start_ns:
            stack.pop()
        if stack and e.end_ns <= events[stack[-1]].end_ns + 1e-6:
            out[stack[-1]] -= e.dur_ns
        stack.append(i)
    return [max(0.0, v) for v in out]


def device_summary(trace: Trace) -> dict:
    """busy_s averaged over the devices, window_s the longest, and the
    idle share of the WORST device."""
    per = {n: busy_and_window(evs) for n, evs in trace.devices.items()}
    if not per:
        return {"busy_s": 0.0, "window_s": 0.0, "idle_pct_worst": None,
                "devices": 0}
    worst = max(1.0 - b / w for b, w in per.values() if w > 0)
    return {"busy_s": sum(b for b, _ in per.values()) / len(per),
            "window_s": max(w for _, w in per.values()),
            "idle_pct_worst": 100.0 * worst, "devices": len(per)}


def time_by_label(events: Sequence[Event], label) -> Dict[str, float]:
    """Self time in seconds, summed by ``label(event)``."""
    out: Dict[str, float] = {}
    for e, t in zip(events, self_times(events)):
        k = label(e)
        out[k] = out.get(k, 0.0) + t / 1e9
    return out


class Call(NamedTuple):
    """One Pallas call of the lowered step."""
    family: str
    signature: str       # hlo.signature(call): what its events show
    flops: float         # operations the call needs, from its shapes
    declared_bytes: int  # operands + results the lowered call declares


def kernel_roofline(events: Sequence[Event], calls: Sequence[Call],
                    steps: int, describe, peaks: dict) -> dict:
    """Sum of the least times of the matched events, each from ITS OWN
    shapes, over the sum of the same events' durations.

    ``describe(event)`` gives (signature, HBM bytes) for the event of a
    Pallas call and None otherwise; an event is matched to a call by its
    signature, because the kernel's name is not in the trace.  An event's
    least time is max(FLOPs / peak, bytes / HBM bandwidth) with the bytes
    its call declares, less what the compiled program keeps on chip.  A
    family is counted only when the events matched to it equal its calls in
    the step times the steps traced, and when none of its signatures is
    also another family's.  ``pct`` is None (withheld) when no family
    qualifies: the bytes of calls whose events were not all found are
    never set against the time of those that were.

    ``declared_pct`` is the same share with ALL the bytes the calls
    declare, on-chip operands included.  It is printed beside ``pct`` and
    is no metric: it passes 100% where XLA keeps operands on chip (180% for
    ResNet's momentum sweeps), and it is what shows that ``pct`` moved
    because XLA placed operands elsewhere, not because a kernel changed."""
    from chipbench.peaks import least_seconds

    owner, by_sig, want = {}, {}, {}
    for c in calls:
        owner.setdefault(c.signature, set()).add(c.family)
        by_sig[c.signature] = c
        want[c.family] = want.get(c.family, 0) + steps
    got = {f: [] for f in want}
    for e in events:
        d = describe(e)
        if d is None:
            continue
        sig, hbm_bytes = d
        fams = owner.get(sig)
        if fams and len(fams) == 1:
            c = by_sig[sig]
            least = least_seconds(c.flops, min(c.declared_bytes, hbm_bytes),
                                  peaks)
            declared = least_seconds(c.flops, c.declared_bytes, peaks)
            got[c.family].append((least, declared, e.dur_ns / 1e9))
    ambiguous = {f for fams in owner.values() if len(fams) > 1 for f in fams}
    least = declared = took = 0.0
    families = {}
    for fam, n in want.items():
        ok = fam not in ambiguous and len(got[fam]) == n
        families[fam] = {"events": len(got[fam]), "calls": n, "counted": ok}
        if ok:
            l, d, t = (sum(x) for x in zip(*got[fam]))
            families[fam]["pct"] = 100.0 * l / t
            families[fam]["declared_pct"] = 100.0 * d / t
            least, declared, took = least + l, declared + d, took + t
    return {"pct": 100.0 * least / took if took > 0 else None,
            "declared_pct": 100.0 * declared / took if took > 0 else None,
            "least_s": least, "took_s": took, "families": families}


def family_pct(roofline: Optional[dict], family: str) -> Optional[float]:
    """One family's share of its roofline (the HBM-bytes reading) out of
    ``kernel_roofline``'s result; None when the run was not traced, the
    step calls no kernel of the family, or its events were not all found."""
    fam = ((roofline or {}).get("families") or {}).get(family) or {}
    return fam.get("pct")


def exposed_collective_s(events: Sequence[Event]) -> Tuple[float, float]:
    """(exposed, total) collective seconds of one device: total is the
    union of the collective events' intervals, exposed the part of it that
    no non-collective event overlaps."""
    coll = [(e.start_ns, e.end_ns) for e in events
            if COLLECTIVE.search(e.name)]
    if not coll:
        return 0.0, 0.0
    other = [(e.start_ns, e.end_ns) for e in events
             if not COLLECTIVE.search(e.name)]
    total = union_ns(coll)
    # |coll ∩ other| = |coll| + |other| - |coll ∪ other|
    both = total + union_ns(other) - union_ns(coll + other)
    return (total - both) / 1e9, total / 1e9


def idle_gaps(events: Sequence[Event],
              spans: Sequence[Tuple[str, float, float]],
              top: int = 10) -> List[List]:
    """Idle seconds between consecutive device operations, summed by the
    host span open at the middle of each gap (``host:none`` where no span
    of the benchmark is open), longest first."""
    gaps: Dict[str, float] = {}
    end = None
    for e in events:
        if end is not None and e.start_ns > end:
            mid = (end + e.start_ns) / 2
            name = next((n for n, s, t in spans if s <= mid <= t),
                        "host:none")
            gaps[name] = gaps.get(name, 0.0) + (e.start_ns - end) / 1e9
        end = e.end_ns if end is None else max(end, e.end_ns)
    return [[n, s] for n, s in sorted(gaps.items(),
                                      key=lambda kv: -kv[1])[:top]]
