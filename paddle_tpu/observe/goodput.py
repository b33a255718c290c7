"""Goodput accounting + cross-rank straggler attribution (ISSUE 13).

The observability stack built in ISSUEs 5/9/11 emits every raw timing a
fleet operator could want — executor window spans, ``compile_seconds``,
``data.wait_ms``, ``checkpoint.commit``, elastic generation boundaries —
but nothing answers the two questions an autoscaler or elastic-resharding
policy actually asks:

 1. **How much of the wall-clock trained?**  Every second of a run is
    classified into one of the :data:`STATES` — device compute, compile,
    data wait, checkpoint commit, barrier/collective wait,
    restart/re-warm gap, idle/unknown — and
    ``goodput.fraction = device_seconds / wall_seconds`` is the headline
    number (ROADMAP items 1 and 4 consume it: a fleet whose goodput
    craters on every preemption needs resharding, not more replicas).
 2. **Which rank drags the fleet?**  Per-rank step times (the
    ``executor.window`` spans every rank already emits) are compared with
    a leave-one-out median+MAD skew test (:func:`fleet.rank_skew`) and a
    flagged rank lands in the run-event stream as a
    ``straggler.detected{rank=}`` record next to the watchdog's
    ``slo.breach`` events.

Two halves, same state classes:

**Live accumulator** (:class:`GoodputAccumulator`, armed by
``PADDLE_GOODPUT``, default on): the executor/trainer/multihost/data hook
points call :func:`note` with measured seconds; the accumulator keeps
per-state totals, publishes the always-on ``goodput.seconds{state=}``
counters and the ``goodput.fraction{mesh=}`` gauge, and emits one
``goodput.report`` run event every ``PADDLE_GOODPUT_REPORT_S`` seconds.
Stall states additionally feed the SLO watchdog (``goodput.stall_s``) so
a sustained stall regression breaches like a slow step.

**Offline ledger** (:func:`build_ledger`): re-derives the same breakdown
from the PERSISTED event stream alone — no re-run, no live process — by
sweeping the classified span intervals per (host, rank): ``executor.window``
spans are device time, ``executor.trace``/``executor.compile`` spans and
compile-flagged dispatches are compile time, ``checkpoint.save`` /
``barrier.wait`` / ``data.stall`` records are their states, and the gap
between one elastic generation's last activity and the next generation's
first is the restart/re-warm cost of that preemption (priced in lost
steps via the heartbeat ``commit_step`` the incidents carry).  Overlaps
resolve by priority (compile > barrier > data wait > checkpoint > device
> restart) so an async checkpoint writing under a running window counts
as device compute, and every rank's states sum to its wall-clock
exactly.  ``python -m paddle_tpu.observe goodput`` prints it; the
chrome-trace export draws it as a per-rank state track.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

__all__ = [
    "STATES", "GoodputAccumulator", "get_accumulator", "note", "report",
    "build_ledger", "classify_intervals", "reset",
]

#: the wall-clock classes.  "idle" is never noted explicitly — it is
#: whatever the other states do not claim.
STATES = ("device", "compile", "data_wait", "checkpoint", "barrier",
          "restart", "idle")

#: sweep priority for overlapping intervals (higher wins).  device beats
#: checkpoint so a BACKGROUND checkpoint writer under a running window
#: stays productive time; compile beats device because the sharded
#: runner's AOT compile happens inside its window region.
_PRIORITY = {"compile": 6, "barrier": 5, "data_wait": 4, "device": 3,
             "checkpoint": 2, "restart": 1}

#: run-event kinds that map 1:1 onto a state interval ``[ts-dur_s, ts]``
_SPAN_STATES = {
    "executor.window": "device",
    "executor.trace": "compile",
    "executor.compile": "compile",
    "checkpoint.save": "checkpoint",
    "barrier.wait": "barrier",
}

#: states whose live seconds also feed the SLO watchdog as
#: ``goodput.stall_s`` (sustained growth breaches like a slow step)
_STALL_STATES = ("data_wait", "barrier", "checkpoint")


def _ec_get(name: str):
    from ..fluid import envcontract

    return envcontract.get(name)


# ---------------------------------------------------------------------------
# live accumulator
# ---------------------------------------------------------------------------


class GoodputAccumulator:
    """Per-process wall-clock state totals, fed by the runtime hook points.

    ``t0`` anchors the wall-clock denominator; the module anchors it at
    observe import (close to process start) so restart re-warm — imports,
    jax init, checkpoint restore — is visible: on the FIRST device note of
    an elastic generation > 0, the un-attributed time since ``t0`` is
    booked as ``restart`` (generation 0's equivalent stays idle/unknown —
    a cold start is not a restart)."""

    def __init__(self, report_s: Optional[float] = None,
                 t0: Optional[float] = None, gen: Optional[int] = None):
        import os

        self._lock = threading.Lock()
        self.t0 = float(t0 if t0 is not None else _ANCHOR_WALL)
        self.report_s = float(report_s if report_s is not None
                              else _ec_get("PADDLE_GOODPUT_REPORT_S"))
        self.gen = int(gen if gen is not None
                       else os.environ.get("PADDLE_ELASTIC_GENERATION",
                                           "0") or 0)
        self.seconds: Dict[str, float] = {s: 0.0 for s in STATES
                                          if s != "idle"}
        self._last_report = time.time()
        self._rewarm_booked = False

    # -- feeding --
    def note(self, state: str, seconds: float,
             mesh: Optional[str] = None) -> None:
        """Attribute ``seconds`` of wall-clock to ``state`` and refresh the
        published counters/gauges.  Never raises."""
        if state not in self.seconds:
            return
        seconds = max(0.0, float(seconds))
        with self._lock:
            if state == "device" and not self._rewarm_booked:
                self._rewarm_booked = True
                if self.gen > 0:
                    # everything before the first device window of a
                    # RESTARTED generation that no other state claimed is
                    # re-warm cost (imports, jax init, checkpoint load)
                    pre = (time.time() - seconds) - self.t0 \
                        - sum(self.seconds.values())
                    if pre > 0.0:
                        self.seconds["restart"] += pre
                        self._publish("restart", pre, None)
            self.seconds[state] += seconds
            fraction = self.fraction_locked()
        self._publish(state, seconds, mesh, fraction=fraction)
        if state in _STALL_STATES:
            try:
                from . import watchdog

                watchdog.observe_value("goodput.stall_s", seconds,
                                       state=state)
            except Exception:
                pass
        self.maybe_report(mesh=mesh)

    def _publish(self, state: str, seconds: float, mesh: Optional[str],
                 fraction: Optional[float] = None) -> None:
        try:
            from . import registry

            reg = registry()
            reg.inc("goodput.seconds", seconds, labels={"state": state})
            if fraction is not None:
                reg.set_gauge("goodput.fraction", round(fraction, 6))
                if mesh:
                    reg.set_gauge("goodput.fraction", round(fraction, 6),
                                  labels={"mesh": mesh})
        except Exception:
            pass  # accounting must never fail the run it measures

    # -- reading --
    def elapsed(self) -> float:
        return max(1e-9, time.time() - self.t0)

    def fraction_locked(self) -> float:
        return min(1.0, self.seconds["device"] / self.elapsed())

    def fraction(self) -> float:
        with self._lock:
            return self.fraction_locked()

    def snapshot(self) -> dict:
        with self._lock:
            states = dict(self.seconds)
            elapsed = self.elapsed()
        states["idle"] = max(0.0, elapsed - sum(states.values()))
        return {"elapsed_s": round(elapsed, 6),
                "states": {k: round(v, 6) for k, v in states.items()},
                "fraction": round(min(1.0, states["device"] / elapsed), 6),
                "gen": self.gen}

    def maybe_report(self, mesh: Optional[str] = None,
                     force: bool = False) -> Optional[dict]:
        """Emit one ``goodput.report`` run event when the report interval
        elapsed (or ``force``); returns the report payload when emitted."""
        now = time.time()
        with self._lock:
            if not force and now - self._last_report < self.report_s:
                return None
            self._last_report = now
        snap = self.snapshot()
        try:
            from . import emit

            emit("goodput.report", mesh=mesh, **snap)
        except Exception:
            pass
        return snap


# anchored at module import (observe imports goodput at package import, so
# this is within milliseconds of the first paddle_tpu import — close
# enough to process start for re-warm attribution)
_ANCHOR_WALL = time.time()

# late-binding singleton (the watchdog/_UNSET contract: a subprocess that
# sets PADDLE_GOODPUT before first use is honored)
_UNSET = object()
_acc = _UNSET
_acc_lock = threading.Lock()


def get_accumulator() -> Optional[GoodputAccumulator]:
    """The process accumulator, or None when ``PADDLE_GOODPUT=0``."""
    global _acc
    if _acc is _UNSET:
        with _acc_lock:
            if _acc is _UNSET:
                try:
                    _acc = GoodputAccumulator() \
                        if _ec_get("PADDLE_GOODPUT") else None
                except Exception:
                    _acc = None
    return _acc


def note(state: str, seconds: float, mesh: Optional[str] = None) -> None:
    """Feed the process accumulator; no-op when disarmed.  Never raises."""
    try:
        acc = get_accumulator()
        if acc is not None:
            acc.note(state, seconds, mesh=mesh)
    except Exception:
        pass


def report(force: bool = True) -> Optional[dict]:
    """Emit a ``goodput.report`` now (the trainer's end-of-run flush and
    the smoke tool call this); None when disarmed."""
    acc = get_accumulator()
    if acc is None:
        return None
    return acc.maybe_report(force=force)


def reset() -> None:
    """Drop the singleton and re-arm env late-binding (test hook, called
    from ``observe.reset``)."""
    global _acc
    with _acc_lock:
        _acc = _UNSET


# ---------------------------------------------------------------------------
# offline ledger: persisted event stream -> per-rank state breakdown
# ---------------------------------------------------------------------------


def _record_interval(r: dict) -> Optional[Tuple[float, float, str]]:
    """(start, end, state) for one run-event record, or None."""
    ev = r.get("event")
    state = _SPAN_STATES.get(ev)
    if state is not None:
        dur = r.get("dur_s")
        if dur is None:
            return None
        ts = float(r.get("ts", 0.0))
        return ts - float(dur), ts, state
    if ev == "executor.dispatch" and r.get("compile"):
        # the single-device path compiles lazily inside its first
        # dispatch; that dispatch is compile cost, not steady-state
        dur = r.get("dur_s")
        if dur is None:
            return None
        ts = float(r.get("ts", 0.0))
        return ts - float(dur), ts, "compile"
    if ev == "data.stall":
        wait_ms = r.get("wait_ms")
        if wait_ms is None:
            return None
        ts = float(r.get("ts", 0.0))
        return ts - float(wait_ms) / 1e3, ts, "data_wait"
    return None


def classify_intervals(records: List[dict]) -> Dict[str, dict]:
    """Group the merged stream per worker ``host:r<rank>``: classified
    state intervals plus per-generation activity bounds (restart gaps are
    derived from the latter).  Supervisor-sourced records are excluded
    from per-rank timelines (they are not worker wall-clock)."""
    per: Dict[str, dict] = {}
    for r in records:
        if r.get("source") == "supervisor":
            continue
        key = f"{r.get('host', '?')}:r{r.get('rank', 0)}"
        w = per.setdefault(key, {"intervals": [], "gens": {},
                                 "host": r.get("host", "?"),
                                 "rank": int(r.get("rank", 0) or 0)})
        iv = _record_interval(r)
        ts = float(r.get("ts", 0.0))
        lo = iv[0] if iv is not None else ts
        gen = int(r.get("gen", 0) or 0)
        bounds = w["gens"].get(gen)
        if bounds is None:
            w["gens"][gen] = [lo, ts]
        else:
            bounds[0] = min(bounds[0], lo)
            bounds[1] = max(bounds[1], ts)
        if iv is not None:
            w["intervals"].append(iv)
    # restart gaps: between consecutive generations' activity, per rank
    for w in per.values():
        gens = sorted(w["gens"])
        for a, b in zip(gens, gens[1:]):
            end_prev, start_next = w["gens"][a][1], w["gens"][b][0]
            if start_next > end_prev:
                w["intervals"].append((end_prev, start_next, "restart"))
    return per


def _sweep(intervals: List[Tuple[float, float, str]], t0: float,
           t1: float) -> Tuple[Dict[str, float], List[dict]]:
    """Priority sweep of ``[t0, t1]``: per-state seconds (always summing
    to exactly ``t1 - t0``, unclaimed time is idle) plus the swept
    non-idle segments (the chrome state track)."""
    seconds = {s: 0.0 for s in STATES}
    segments: List[dict] = []
    ivs = [(max(t0, s), min(t1, e), st) for s, e, st in intervals
           if e > t0 and s < t1 and e > s]
    pts = sorted({t0, t1, *(p for s, e, _ in ivs for p in (s, e))})
    for a, b in zip(pts, pts[1:]):
        if b <= a:
            continue
        state = "idle"
        prio = 0
        for s, e, st in ivs:
            if s < b and e > a and _PRIORITY.get(st, 0) > prio:
                state, prio = st, _PRIORITY[st]
        seconds[state] += b - a
        if state != "idle":
            if segments and segments[-1]["state"] == state \
                    and abs(segments[-1]["t1"] - a) < 1e-9:
                segments[-1]["t1"] = b
            else:
                segments.append({"state": state, "t0": a, "t1": b})
    return seconds, segments


def _restart_pricing(records: List[dict], per: Dict[str, dict]) -> List[dict]:
    """One entry per (rank, generation gap), priced in lost steps where a
    worker_exit/heartbeat_timeout incident carries progress-at-death
    (``last_step`` vs heartbeat ``commit_step`` — ISSUE 13 satellite).
    A supervisor ``mesh.downgrade`` incident covering the gap's target
    generation additionally prices the TOPOLOGY transition
    (``mesh_from``/``mesh_to``/``nproc_from``/``nproc_to`` — ISSUE 14):
    a restart that also shrank the mesh is a different cost class from a
    same-size relaunch, and the ledger is where an autoscaler reads
    that."""
    deaths: Dict[Tuple[int, int], dict] = {}
    downgrades: Dict[int, dict] = {}
    for r in records:
        if r.get("event") in ("worker_exit", "heartbeat_timeout"):
            g = r.get("generation")
            rk = r.get("rank")
            if g is not None and rk is not None:
                deaths[(int(g), int(rk))] = r
        elif r.get("event") == "mesh.downgrade":
            g = r.get("generation")
            if g is not None:
                downgrades[int(g)] = r
    out: List[dict] = []
    for key, w in sorted(per.items()):
        gens = sorted(w["gens"])
        for a, b in zip(gens, gens[1:]):
            gap = w["gens"][b][0] - w["gens"][a][1]
            entry = {"worker": key, "rank": w["rank"], "from_gen": a,
                     "to_gen": b, "gap_s": round(max(0.0, gap), 6)}
            death = deaths.get((a, w["rank"]))
            if death is not None:
                last = death.get("last_step")
                commit = death.get("commit_step")
                entry["last_step"] = last
                entry["commit_step"] = commit
                if isinstance(last, int) and isinstance(commit, int):
                    entry["lost_steps"] = max(0, last - commit)
            down = downgrades.get(b)
            if down is not None:
                entry["mesh_from"] = down.get("from_mesh")
                entry["mesh_to"] = down.get("to_mesh")
                entry["nproc_from"] = down.get("from_nproc")
                entry["nproc_to"] = down.get("to_nproc")
            out.append(entry)
    return out


def build_ledger(records: List[dict]) -> dict:
    """The whole-run goodput ledger from a merged event stream (the
    ``observe goodput`` CLI's payload; needs no live process).

    Per worker: state seconds summing exactly to its wall window
    (first-to-last activity) and the swept state segments.  Fleet level:
    summed state seconds, ``fraction = device / total``, the restart list
    with lost-work pricing, and the straggler events already persisted in
    the stream."""
    per = classify_intervals(records)
    ranks: Dict[str, dict] = {}
    fleet = {s: 0.0 for s in STATES}
    segments: List[dict] = []
    total = 0.0
    for key, w in sorted(per.items()):
        t0 = min(b[0] for b in w["gens"].values())
        t1 = max(b[1] for b in w["gens"].values())
        seconds, segs = _sweep(w["intervals"], t0, t1)
        wall = t1 - t0
        for s, v in seconds.items():
            fleet[s] += v
        total += wall
        for seg in segs:
            seg.update(worker=key, host=w["host"], rank=w["rank"])
        segments.extend(segs)
        ranks[key] = {
            "t0": t0, "t1": t1, "wall_s": round(wall, 6),
            "states": {s: round(v, 6) for s, v in seconds.items()},
            "coverage": round(sum(seconds.values()) / wall, 6)
            if wall > 0 else 1.0,
            "generations": sorted(w["gens"]),
        }
    stragglers = [r for r in records
                  if r.get("event") == "straggler.detected"]
    return {
        "workers": sorted(ranks),
        "ranks": ranks,
        "states": {s: round(v, 6) for s, v in fleet.items()},
        "total_s": round(total, 6),
        "fraction": round(fleet["device"] / total, 6) if total > 0 else 0.0,
        "restarts": _restart_pricing(records, per),
        "straggler_events": [
            {k: r.get(k) for k in ("ts", "rank", "host", "generation",
                                   "median_step_s", "baseline_step_s",
                                   "ratio")}
            for r in stragglers],
        "segments": segments,
    }
