"""Thread-safe metrics registry: counters, gauges, histograms, timings.

The predecessor was ``fluid.profiler``'s module-level plain dicts — an
unlocked read-modify-write per increment that silently dropped updates
whenever serving workers, the guardian's observer, and the training loop
emitted concurrently (ISSUE 5 satellite: N threads x M increments must be
exactly N*M).  Every mutation here happens under ONE re-entrant lock, which
is also exported (``registry.lock``) so adjacent aggregation state that
must stay consistent with the metrics (the profiler's timeline) can share
it instead of growing a second lock with ordering rules.

Metric model (deliberately the Prometheus one, so the text exporter is a
straight rendering):

 - **counter**: monotonically accumulating float/int (``inc``);
 - **gauge**: last-write-wins absolute value (``set_gauge``);
 - **histogram**: cumulative bucket counts + sum + count (``observe``);
 - **timing**: the reference profiler's [calls, total, min, max] aggregate
   per event name (``record_timing``) — host-span statistics that back
   ``fluid.profiler.stop_profiler``'s table.

Labels: any metric accepts ``labels={...}``; the (name, sorted label
items) pair is the identity.  The flat rendering is the Prometheus exposition
form ``name{k="v"}``.

Naming scheme (docs/OBSERVABILITY.md): dot-separated
``<subsystem>.<metric>`` — e.g. ``compile_cache.hit``,
``executor.jit_cache.size``, ``serving.completed``, ``guardian_trips``
(pre-existing flat names are kept for compatibility).  The Prometheus
exporter maps dots to underscores.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Iterable, Optional, Tuple

__all__ = ["MetricsRegistry", "render_name", "split_name",
           "DEFAULT_BUCKETS"]

#: default histogram bucket upper bounds, in seconds — log-spaced to cover
#: sub-ms serving latencies through multi-second compiles
DEFAULT_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                   0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)


def _label_key(labels: Optional[dict]) -> Tuple[Tuple[str, str], ...]:
    if not labels:
        return ()
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def render_name(name: str, label_key: Tuple[Tuple[str, str], ...]) -> str:
    """``name`` or ``name{k="v",k2="v2"}`` (Prometheus exposition form)."""
    if not label_key:
        return name
    inner = ",".join(f'{k}="{v}"' for k, v in label_key)
    return f"{name}{{{inner}}}"


def split_name(rendered: str) -> Tuple[str, Tuple[Tuple[str, str], ...]]:
    """Inverse of :func:`render_name` (for the Prometheus parser)."""
    if "{" not in rendered:
        return rendered, ()
    name, _, rest = rendered.partition("{")
    rest = rest.rstrip("}")
    labels = []
    for part in rest.split(","):
        if not part:
            continue
        k, _, v = part.partition("=")
        labels.append((k.strip(), v.strip().strip('"')))
    return name, tuple(sorted(labels))


class MetricsRegistry:
    """One lock, four metric families.  Safe for any number of writer
    threads; snapshots are consistent cuts (taken under the lock)."""

    def __init__(self, buckets: Iterable[float] = DEFAULT_BUCKETS):
        self.lock = threading.RLock()
        self._buckets = tuple(sorted(float(b) for b in buckets))
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, float] = {}
        # rendered name -> [bucket counts..., +Inf count], sum, count
        self._hists: Dict[str, list] = {}
        # event name -> [calls, total, min, max] (profiler aggregate)
        self._timings: Dict[str, list] = {}
        # optional (ts_us, rendered_name, value) counter/gauge samples for
        # the chrome-trace exporter ("ph": "C" events); enabled by the
        # profiler session so steady-state production pays nothing
        self._samples: Optional[list] = None
        self._samples_t0 = 0.0
        self._samples_cap = 200_000
        # the open ``tape()``s of each thread
        self._tapes = threading.local()

    # ------------------------------------------------------------------
    # writing
    # ------------------------------------------------------------------

    def inc(self, name: str, value: float = 1,
            labels: Optional[dict] = None) -> float:
        """Add ``value`` to a counter; returns the new total."""
        key = render_name(name, _label_key(labels))
        for tape in getattr(self._tapes, "open", ()):
            tape.append((key, value))
        with self.lock:
            new = self._counters.get(key, 0) + value
            self._counters[key] = new
            self._sample(key, new)
        return new

    @contextlib.contextmanager
    def tape(self):
        """A list that takes ``(rendered name, value)`` of every ``inc``
        this thread makes while the context is open, beside the counters
        themselves.  For code whose counters count a TRACE and whose trace
        one call makes for many (an inlined ``jax.jit``): the calls that the
        kept trace serves ``replay`` what it counted."""
        taken: list = []
        opened = getattr(self._tapes, "open", ())
        self._tapes.open = opened + (taken,)
        try:
            yield taken
        finally:
            self._tapes.open = opened

    def replay(self, taken) -> None:
        """Count again what a ``tape()`` took."""
        with self.lock:
            for key, value in taken:
                new = self._counters.get(key, 0) + value
                self._counters[key] = new
                self._sample(key, new)
        for tape in getattr(self._tapes, "open", ()):
            tape.extend(taken)

    def set_gauge(self, name: str, value: float,
                  labels: Optional[dict] = None) -> None:
        key = render_name(name, _label_key(labels))
        with self.lock:
            self._gauges[key] = value
            self._sample(key, value)

    def set_gauges(self, rendered: Dict[str, float]) -> None:
        """Many gauges under ONE hold of the lock, by their rendered names
        (``render_name``): a step's worth of step gauges, a step."""
        with self.lock:
            self._gauges.update(rendered)
            if self._samples is not None:
                for key, value in rendered.items():
                    self._sample(key, value)

    def observe(self, name: str, value: float,
                labels: Optional[dict] = None) -> None:
        """One histogram observation."""
        key = render_name(name, _label_key(labels))
        v = float(value)
        with self.lock:
            h = self._hists.get(key)
            if h is None:
                h = [[0] * (len(self._buckets) + 1), 0.0, 0]
                self._hists[key] = h
            counts, _, _ = h
            for i, ub in enumerate(self._buckets):
                if v <= ub:
                    counts[i] += 1
                    break
            else:
                counts[-1] += 1
            h[1] += v
            h[2] += 1

    def record_timing(self, name: str, seconds: float) -> None:
        """Profiler-style [calls, total, min, max] aggregate."""
        s = float(seconds)
        with self.lock:
            e = self._timings.get(name)
            if e is None:
                self._timings[name] = [1, s, s, s]
            else:
                e[0] += 1
                e[1] += s
                e[2] = min(e[2], s)
                e[3] = max(e[3], s)

    def _sample(self, key: str, value) -> None:
        # caller holds self.lock
        if self._samples is None or len(self._samples) >= self._samples_cap:
            return
        ts = (time.perf_counter() - self._samples_t0) * 1e6
        self._samples.append({"name": key, "ts": ts, "value": value})

    # ------------------------------------------------------------------
    # sampling control (profiler session hooks)
    # ------------------------------------------------------------------

    def start_sampling(self, t0: Optional[float] = None) -> None:
        """Begin recording per-change counter samples (chrome-trace "C"
        events), timestamped relative to ``t0`` (perf_counter)."""
        with self.lock:
            self._samples = []
            self._samples_t0 = time.perf_counter() if t0 is None else t0

    def stop_sampling(self) -> list:
        with self.lock:
            out, self._samples = self._samples or [], None
        return out

    # ------------------------------------------------------------------
    # reading
    # ------------------------------------------------------------------

    def flat(self) -> Dict[str, float]:
        """Counters + gauges as one rendered-name -> value dict (the
        ``fluid.profiler.counters()`` compatibility view)."""
        with self.lock:
            out = dict(self._counters)
            out.update(self._gauges)
        return out

    def timings(self) -> Dict[str, tuple]:
        with self.lock:
            return {k: tuple(v) for k, v in self._timings.items()}

    def snapshot(self) -> dict:
        """Structured consistent cut: counters / gauges / histograms
        (each histogram: bucket bounds, cumulative counts, sum, count)."""
        with self.lock:
            hists = {k: {"buckets": list(self._buckets),
                         "counts": list(h[0]),
                         "sum": h[1], "count": h[2]}
                     for k, h in self._hists.items()}
            return {"counters": dict(self._counters),
                    "gauges": dict(self._gauges),
                    "histograms": hists}

    def clear(self, timings_only: bool = False) -> None:
        with self.lock:
            self._timings.clear()
            if not timings_only:
                self._counters.clear()
                self._gauges.clear()
                self._hists.clear()
