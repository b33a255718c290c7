"""Step gauges: values computed ON the device inside a compiled step, read
late.

The registry's counters count what is TRACED, once a lowering; a span times
the host; the device trace has times, not values.  A step gauge is the one
way a value that exists only on the device (how many rows of a routed
layer were work) reaches the same registry, and it does so without the
dispatch ever waiting for it:

 - **publishing**: an op's lowering calls ``step_gauge(name, value,
   **labels)`` at trace time with a traced scalar (or a short 1-D array).
   With no :class:`Collector` open on the thread it does nothing; a
   collector is open only while ``fluid.executor.run_op`` runs a FORWARD
   op's function at the top level of a step that carries the output.  A
   value from any other trace (a grad op's second forward under
   ``jax.vjp``, a scan body) is declined, never kept: it would be an inner
   trace's tracer.
 - **collecting**: :meth:`Collector.finish` casts what was published to
   float32 and concatenates it, in the order published, into ONE vector,
   the step's one extra output, beside its static :class:`Layout`.  A step
   that published nothing has no extra output and lowers as before.
 - **carrying**: :func:`keep` puts ``(step, span id of the fluid.run root,
   perf_counter at the call, the vector)`` into a bounded queue of entries
   IN FLIGHT, as the device array it is, and that is ALL the dispatch path
   does: it reads nothing, asks nothing (``is_ready()`` alone is 10 us on
   the v5e's host, the host copy of a vector whose step HAS retired 0.43
   ms: my chip run, PR 54).  One daemon thread, started by the first
   vector, takes the oldest entry, waits for its step on its own time,
   copies the vector to the host and lets the device buffer go.  At most
   :data:`IN_FLIGHT` device buffers are alive: an older one is dropped
   unread and counted.
 - **reading**: :func:`step_gauges` returns the materialised entries
   (``wait=True``: after the thread has taken every entry in flight, the
   one call that blocks); the newest value of each gauge is also a gauge
   of THE ``MetricsRegistry`` (``fluid.profiler.counters()``,
   ``/metrics``, the fleet snapshot).  A value is at least one retired
   dispatch old.

``observe.step_gauges.dropped{path}`` counts what was published and not
carried, once a lowering: ``path`` is the entry point that carries no
vector (``run_steps``, ``sharded_step``, ``sharded_window``), ``inner_trace``, ``too_long``; or,
once an entry, ``overrun`` and ``unreadable``.
"""

from __future__ import annotations

import collections
import contextlib
import threading
from typing import Dict, List, NamedTuple, Optional

import numpy as np

from .registry import _label_key, render_name

__all__ = ["step_gauge", "step_gauges", "Collector", "Layout", "Entry",
           "keep", "in_flight", "reset", "RING_ENTRIES", "IN_FLIGHT",
           "MAX_LENGTH", "DROPPED"]

#: materialised entries kept (host memory: one float32 vector each)
RING_ENTRIES = 4096
#: device buffers alive at most (one vector each: a few hundred bytes),
#: the one the reading thread holds among them
IN_FLIGHT = 8
#: elements one gauge may have
MAX_LENGTH = 256
DROPPED = "observe.step_gauges.dropped"

_tls = threading.local()


def _count_dropped(path: str) -> None:
    try:
        from . import registry

        registry().inc(DROPPED, labels={"path": path})
    except Exception:
        pass


class Layout:
    """Where each gauge lies in a step's vector, static: ``slots`` is
    ``[(name, label key, offset, length, whole)]``; ``keys`` the rendered
    name of every ELEMENT, in order (an array gauge's elements take the
    label ``i``).  ``whole``: the value was published as an integer (a
    count of rows) and is one again on the host, though it crossed as
    float32, exact to 2**24."""

    __slots__ = ("slots", "keys", "_whole")

    def __init__(self, slots):
        self.slots = tuple(slots)
        keys, whole = [], []
        for name, label_key, _, length, is_whole in self.slots:
            if length == 1:
                keys.append(render_name(name, label_key))
            else:
                keys += [render_name(name, _label_key(
                    {**dict(label_key), "i": i})) for i in range(length)]
            whole += [is_whole] * length
        self.keys, self._whole = tuple(keys), tuple(whole)

    def values(self, host) -> Dict[str, float]:
        """``{rendered name: value}`` of a step's vector on the host."""
        return {k: int(v) if w else v
                for k, v, w in zip(self.keys, host.tolist(), self._whole)}


class Collector:
    """What one trace of a step publishes.  ``drop``: the path's name where
    the step carries no vector; what is published is then counted, once,
    and not kept."""

    def __init__(self, drop: Optional[str] = None):
        self.drop = drop
        self.items: list = []   # (name, label key, float32 [length], whole)
        self._seen: Dict[tuple, int] = {}
        self._counted = False
        self._scope = ""
        self._trace = None

    @contextlib.contextmanager
    def op(self, scope: Optional[str]):
        """Open for one forward op's function (``scope``: the op's
        ``fluid.name_scope`` path), closed again behind it."""
        import jax

        prev = getattr(_tls, "collector", None)
        self._scope = scope or ""
        self._trace = jax.core.get_opaque_trace_state()
        _tls.collector = self
        try:
            yield self
        finally:
            _tls.collector = prev

    def publish(self, name, value, labels) -> None:
        import jax
        import jax.numpy as jnp

        if jax.core.get_opaque_trace_state() != self._trace:
            return _count_dropped("inner_trace")
        if self.drop is not None:
            if not self._counted:
                self._counted = True
                _count_dropped(self.drop)
            return
        value = jnp.ravel(jnp.asarray(value))
        whole = bool(jnp.issubdtype(value.dtype, jnp.integer))
        value = value.astype(jnp.float32)
        if value.shape[0] > MAX_LENGTH:
            return _count_dropped("too_long")
        if "scope" not in labels and self._scope:
            labels = {**labels, "scope": self._scope}
        key = (name, _label_key(labels))
        n = self._seen[key] = self._seen.get(key, 0) + 1
        if n > 1:               # the same gauge twice in one step: told apart
            key = (name, _label_key({**labels, "call": n}))
        self.items.append(key + (value, whole))

    def finish(self):
        """``(vector, layout)`` of what was published: one float32 array in
        the order published, or ``(None, None)`` for nothing.  The
        collector lets go of the traced values."""
        items, self.items = self.items, []
        if not items:
            return None, None
        import jax.numpy as jnp

        slots, offset = [], 0
        for name, label_key, v, whole in items:
            slots.append((name, label_key, offset, v.shape[0], whole))
            offset += v.shape[0]
        return jnp.concatenate([v for _, _, v, _ in items]), Layout(slots)


def step_gauge(name: str, value, **labels) -> None:
    """Publish a traced scalar (or short 1-D array) as the gauge ``name``
    of the step being traced.  Nothing where no collector is open.  The
    label ``scope`` is the op's ``fluid.name_scope`` path unless given.
    Never fails the trace it measures."""
    try:
        collector = getattr(_tls, "collector", None)
        if collector is not None:
            collector.publish(name, value, labels)
    except Exception:
        pass


# ---------------------------------------------------------------------------
# carrying and reading
# ---------------------------------------------------------------------------


class Entry(NamedTuple):
    """One step's gauges as host numbers."""
    step: Optional[int]
    span_id: Optional[str]
    t: float                       # time.perf_counter at the step's call
    values: Dict[str, float]       # rendered name -> value


_cv = threading.Condition()
# (step, span_id, t, device vector, layout), oldest first
_flight: "collections.deque[tuple]" = collections.deque()
# (step, span_id, t, host vector, layout), oldest first
_done: "collections.deque[tuple]" = collections.deque(maxlen=RING_ENTRIES)
_held = 0        # entries the reading thread holds (0 or 1)
_epoch = 0       # reset() counts up: what was in flight before it is void
_reader: Optional[threading.Thread] = None


def keep(step, span_id, t: float, vector, layout: Layout) -> None:
    """A dispatched step's vector, kept as the device array it is: queued
    for the reading thread and not looked at."""
    global _reader
    with _cv:
        _flight.append((step, span_id, t, vector, layout))
        overrun = len(_flight) + _held > IN_FLIGHT
        if overrun:
            _flight.popleft()
        if _reader is None:
            _reader = threading.Thread(target=_read_loop, daemon=True,
                                       name="observe-step-gauges")
            _reader.start()
        _cv.notify_all()
    if overrun:
        _count_dropped("overrun")


def _to_host(vector) -> np.ndarray:
    return np.asarray(vector)       # waits for the step to retire


def _read_loop() -> None:
    """The reading thread: oldest entry first, each waited for HERE."""
    global _held
    from . import registry

    while True:
        with _cv:
            while not _flight:
                _cv.wait()
            step, span_id, t, vector, layout = _flight.popleft()
            _held, epoch = 1, _epoch
        try:
            host = _to_host(vector)
        except Exception:
            host = None                     # a deleted buffer, a dead backend
            _count_dropped("unreadable")
        del vector                          # the device buffer's last reference
        with _cv:
            _held = 0
            kept = host is not None and epoch == _epoch
            if kept:
                _done.append((step, span_id, t, host, layout))
            _cv.notify_all()
        if kept:
            registry().set_gauges(layout.values(host))


def step_gauges(since: Optional[float] = None,
                wait: bool = False) -> List[Entry]:
    """The materialised entries, oldest first; ``since``: those whose ``t``
    (``time.perf_counter`` at the call) is at or after it.  ``wait`` first
    waits until every entry in flight has been materialised: the one call
    here that blocks, for a run's end and for tests."""
    with _cv:
        while wait and (_flight or _held):
            _cv.wait()
        done = list(_done)
    return [Entry(step, span_id, t, layout.values(host))
            for step, span_id, t, host, layout in done
            if since is None or t >= since]


def in_flight() -> int:
    """How many vectors are still device arrays."""
    with _cv:
        return len(_flight) + _held


def reset() -> None:
    """Clear both queues (``observe.reset``)."""
    global _epoch
    with _cv:
        _epoch += 1
        _flight.clear()
        _done.clear()
        _cv.notify_all()
    _tls.collector = None
