"""The one span primitive: host regions on the profiler's clock.

A span is one timed host region with W3C-style identity: a 32-hex
``trace_id`` shared by everything in one logical run/request and a 16-hex
``span_id`` per region, with ``parent_span`` links forming the tree.
Every span does three things, always:

 - it enters a ``jax.profiler.TraceAnnotation`` of the same name, so it
   lands in the host plane of whatever profiler session is open, beside
   the device operations and on their clock (outside a session this is a
   flag test);
 - when it ends, ``(name, t0, t1, span_id, parent_id, tid, step)`` is
   appended to ONE bounded in-memory ring (:data:`RING_SPANS` entries,
   ``time.perf_counter`` stamps); :func:`recorded` reads it,
   ``observe.reset()`` clears it.  Spans are kept in memory and read when
   the run ends;
 - it is written to the run-event JSONL (the per-process stream the fleet
   aggregator merges, so one ``chrome://tracing`` export shows supervisor
   generations, executor windows, prefetch staging on its worker thread
   and per-request serving breakdowns) when, and only when, a sink exists
   (``PADDLE_OBSERVE_DIR``), ``PADDLE_TRACE`` is on and the root is
   sampled (:attr:`Span.logged`).

A span never waits for the device and never lowers anything: tracing does
not change what it measures.

API surface (every call returns a span):

 - ``span(name, **attrs)``: context manager; pushes the span onto the
   calling thread's context stack so nested spans parent automatically
   and every ``observe.emit`` record inside a logged span is stamped with
   (trace_id, span_id);
 - ``start_span(name, parent=..., **attrs)`` / ``Span.end(**attrs)``:
   explicit pair for async hand-offs (a serving request's span lives
   across the batcher thread; a prefetch stage span lives on the worker
   thread);
 - ``emit_span(name, t0, t1, parent=...)``: record an already-measured
   ``perf_counter`` interval (queue waits are known only after the fact;
   such a span is in the ring and the log, not in the profiler's trace).

``PADDLE_TRACE_SAMPLE`` keeps every Nth root span in the event log
(deterministic counter-based sampling, no RNG on the hot path); children
inherit their root's decision.

The compile path from inside: one pair of ``jax.monitoring`` listeners,
registered when this module is imported, turns jax's own duration events
(jaxpr trace, jaxpr -> MLIR module, backend compile or persistent-cache
load) into ``fluid.compile.trace`` / ``.lower`` / ``.backend`` spans
under whatever span is open on that thread, and into the counters
``compile.lowerings``, ``compile.backend_compiles`` and
``executor.relowerings`` (see :func:`_on_jax_duration`).

Cross-process stitching: ``PADDLE_TRACEPARENT`` (W3C ``traceparent``
shape, ``00-<trace>-<span>-01``) seeds this process's trace id and
default root parent.  The elastic supervisor mints ONE trace id per run,
opens a span per generation, and hands each generation
``PADDLE_TRACEPARENT`` pointing at its generation span, so a
kill-and-resume run merges into one trace tree spanning processes.

:func:`note_window_breakdown` publishes the per-window ``window.host_ms``
/ ``window.stage_ms`` / ``window.dispatch_ms`` / ``window.observe_ms``
gauge family: host times all four, the third the time to ENQUEUE the
window, not the time the device took (the device trace has that).
"""

from __future__ import annotations

import collections
import itertools
import os
import threading
import time
from typing import List, NamedTuple, Optional

import jax.monitoring
from jax.profiler import TraceAnnotation

from . import current_step

__all__ = [
    "Span", "Recorded", "RING_SPANS", "span", "start_span", "emit_span",
    "recorded", "current", "enabled", "trace_context", "set_trace_context",
    "new_span_id", "format_traceparent", "parse_traceparent", "thread_tid",
    "note_window_breakdown", "reset",
]

# one wall/perf anchor pair so perf_counter intervals map onto the event
# log's unix-seconds timebase consistently within a process
_PERF0 = time.perf_counter()
_WALL0 = time.time()


def _wall(perf_t: float) -> float:
    return _WALL0 + (perf_t - _PERF0)


def _gen_id(nbytes: int) -> str:
    return os.urandom(nbytes).hex()


# ---------------------------------------------------------------------------
# the ring: every ended span, in memory, bounded
# ---------------------------------------------------------------------------


class Recorded(NamedTuple):
    """One ended span as the ring keeps it (``time.perf_counter`` stamps)."""
    name: str
    t0: float
    t1: float
    span_id: str
    parent_id: Optional[str]
    tid: int
    step: Optional[int]


#: Ring capacity.  A per-step ``run`` leaves 7 spans (root + six children),
#: so a 34 s benchmark window of the quickest cell (157 ms a step, 216
#: steps) leaves ~1,500 and set-up a few hundred: 40 times over.
RING_SPANS = 1 << 16

_ring: "collections.deque[Recorded]" = collections.deque(maxlen=RING_SPANS)


def recorded() -> List[Recorded]:
    """The ended spans still in the ring, oldest first (a copy)."""
    return list(_ring)


# ---------------------------------------------------------------------------
# process trace context + thread-local span stack
# ---------------------------------------------------------------------------

_tls = threading.local()
_state_lock = threading.Lock()
_trace_id: Optional[str] = None    # lazily: env traceparent or random
_env_parent: Optional[str] = None  # parent span id inherited from the env
_root_seq = itertools.count(1)     # deterministic sampling sequence
_tid_lock = threading.Lock()
_tids = {}                         # thread ident -> small stable int


def thread_tid() -> int:
    """Small stable per-thread integer (chrome-trace ``tid``), assigned
    in first-use order so the executor thread is usually tid 0."""
    ident = threading.get_ident()
    t = _tids.get(ident)
    if t is None:
        with _tid_lock:
            t = _tids.setdefault(ident, len(_tids))
    return t


def parse_traceparent(raw: str):
    """(trace_id, span_id) out of a W3C-ish traceparent string; tolerant
    of the bare ``<trace>`` and ``<trace>-<span>`` shapes."""
    parts = [p for p in (raw or "").strip().split("-") if p]
    # strip the W3C version/flags fields when present
    if parts and len(parts[0]) <= 2:
        parts = parts[1:]
    if parts and len(parts[-1]) <= 2:
        parts = parts[:-1]
    if not parts:
        return None, None
    trace = parts[0] if len(parts[0]) >= 16 else None
    parent = parts[1] if len(parts) > 1 and len(parts[1]) >= 8 else None
    return trace, parent


def format_traceparent(trace_id: str, span_id: Optional[str]) -> str:
    return f"00-{trace_id}-{span_id or '0' * 16}-01"


def trace_context():
    """This process's (trace_id, inherited parent span id).  Adopted from
    ``PADDLE_TRACEPARENT`` on first use (late-bound, same contract as the
    observe sink) or minted fresh."""
    global _trace_id, _env_parent
    if _trace_id is None:
        with _state_lock:
            if _trace_id is None:
                from ..fluid import envcontract

                tid, pid = parse_traceparent(
                    envcontract.get("PADDLE_TRACEPARENT") or "")
                _env_parent = pid
                _trace_id = tid or _gen_id(16)
    return _trace_id, _env_parent


def set_trace_context(trace_id: Optional[str],
                      parent_span: Optional[str] = None) -> None:
    """Pin the process trace context programmatically (the supervisor
    uses this for its own records; tests use it for determinism)."""
    global _trace_id, _env_parent
    with _state_lock:
        _trace_id = trace_id
        _env_parent = parent_span


#: span ids: 8 random hex digits drawn once per process, then a counter.
#: A draw per span is a system call per span, and where that is slow (a
#: sealed VM) it was most of a span's cost.
_SPAN_PREFIX = _gen_id(4)
_span_seq = itertools.count(1)


def new_span_id() -> str:
    return _SPAN_PREFIX + format(next(_span_seq) & 0xFFFFFFFF, "08x")


def current() -> Optional["Span"]:
    """The calling thread's innermost open ``span(...)`` context."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


def enabled() -> bool:
    """Spans reach the EVENT LOG: an observe sink exists AND
    ``PADDLE_TRACE`` is truthy.  The ring and the profiler annotation do
    not depend on it."""
    from . import get_sink

    if get_sink() is None:
        return False
    from ..fluid import envcontract

    return bool(envcontract.get("PADDLE_TRACE"))


def _sample_root() -> bool:
    from ..fluid import envcontract

    try:
        rate = float(envcontract.get("PADDLE_TRACE_SAMPLE"))
    except (TypeError, ValueError):
        rate = 1.0
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        return False
    n = next(_root_seq)
    return int(n * rate) != int((n - 1) * rate)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def _do_emit(emit_fn, event: str, **fields) -> None:
    try:
        if emit_fn is None:
            from . import emit as emit_fn
        emit_fn(event, **fields)
    except Exception:
        pass  # telemetry must never fail the work it measures


def _keep(name, t0, t1, trace_id, span_id, parent_id, tid, logged,
          emit_fn, attrs) -> None:
    """An ended span goes into the ring and, when logged, the event log."""
    _ring.append(Recorded(name, t0, t1, span_id, parent_id, tid,
                          current_step()))
    if logged:
        _do_emit(emit_fn, name, ts=_wall(t1),
                 dur_s=round(max(0.0, t1 - t0), 6), trace_id=trace_id,
                 span_id=span_id, parent_span=parent_id, tid=tid, **attrs)


def _annotation(name: str, attrs: dict) -> TraceAnnotation:
    """An entered ``TraceAnnotation``.  The attributes are formatted only
    while a profiler session is recording: outside one the annotation is
    a flag test and must stay one."""
    ann = None
    if attrs and TraceAnnotation.is_enabled():
        try:
            ann = TraceAnnotation(name, **{
                k: v if isinstance(v, (str, int, float)) else repr(v)
                for k, v in attrs.items()})
        except Exception:
            ann = None
    if ann is None:
        ann = TraceAnnotation(name)
    ann.__enter__()
    return ann


class Span:
    """One open timed region.  ``end()`` closes the profiler annotation,
    appends the span to the ring and, when :attr:`logged`, emits a single
    run-event record carrying ``dur_s`` + the trace identity; it is
    idempotent, returns the duration in seconds, and never raises.  As a
    context manager it also sits on its thread's context stack."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "attrs",
                 "tid", "ended", "logged", "t0", "_emit", "_ann")

    def __init__(self, name: str, trace_id: str, parent_id: Optional[str],
                 attrs: dict, logged: bool, emit_fn=None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = new_span_id()
        self.parent_id = parent_id
        self.attrs = attrs
        self.tid = thread_tid()
        self.ended = False
        self.logged = logged
        self._emit = emit_fn
        self._ann = _annotation(name, attrs)
        self.t0 = time.perf_counter()

    def set(self, **attrs) -> None:
        """Attributes learned while the span is open (whether the
        executor's cache missed is known only after the lookup)."""
        self.attrs.update(attrs)
        try:
            if TraceAnnotation.is_enabled():
                self._ann.set_metadata(**attrs)
        except Exception:
            pass

    def end(self, **extra) -> Optional[float]:
        if self.ended:
            return None
        self.ended = True
        t1 = time.perf_counter()
        try:
            if extra:
                self.set(**extra)
            self._ann.__exit__(None, None, None)
        except Exception:
            pass  # telemetry must never fail the work it measures
        _keep(self.name, self.t0, t1, self.trace_id, self.span_id,
              self.parent_id, self.tid, self.logged, self._emit, self.attrs)
        return t1 - self.t0

    def __enter__(self) -> "Span":
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc) -> bool:
        stack = getattr(_tls, "stack", None)
        if stack and stack[-1] is self:
            stack.pop()
        self.end()
        return False


def _identity(parent: Optional[Span]):
    """(trace_id, parent_id, logged) for a span under ``parent`` (default:
    the thread's innermost open span).  A root takes the process trace
    context and its own sampling decision; a child follows its parent."""
    if parent is None:
        parent = current()
    if parent is not None:
        return parent.trace_id, parent.span_id, parent.logged
    trace_id, parent_id = trace_context()
    return trace_id, parent_id, enabled() and _sample_root()


def start_span(name: str, parent: Optional[Span] = None, emit_fn=None,
               **attrs) -> Span:
    """Open a span WITHOUT touching the thread context stack (async
    hand-off form: the opener and the closer may be different threads)."""
    trace_id, parent_id, logged = _identity(parent)
    return Span(name, trace_id, parent_id, attrs, logged, emit_fn)


def span(name: str, **attrs) -> Span:
    """Context-manager span: children opened inside parent to it, and
    ``observe.emit`` records inside are stamped with its identity when it
    is logged.  ``with span(...) as sp`` yields the Span."""
    return start_span(name, **attrs)


def emit_span(name: str, t0: float, t1: float,
              parent: Optional[Span] = None, emit_fn=None,
              **attrs) -> str:
    """Record an already-measured ``perf_counter`` interval (queue waits,
    jax's own compile phases: intervals whose boundaries are only known
    after the fact) under ``parent``, default the thread's innermost open
    span.  Goes to the ring and, under a logged parent, to the event log;
    the profiler's trace cannot take a span after the fact.  Returns the
    new span id."""
    trace_id, parent_id, logged = _identity(parent)
    span_id = new_span_id()
    _keep(name, t0, t1, trace_id, span_id, parent_id, thread_tid(), logged,
          emit_fn, attrs)
    return span_id


# ---------------------------------------------------------------------------
# the compile path from inside: jax's own duration events as spans
# ---------------------------------------------------------------------------

_JAX_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_JAX_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_JAX_EVENTS = {  # jax 0.9.0, jax/_src/dispatch.py
    _JAX_TRACE: "fluid.compile.trace",
    _JAX_LOWER: "fluid.compile.lower",
    # pxla's compile_or_get_cached: the backend compile, or the load of
    # the executable from jax's persistent cache
    "/jax/core/compile/backend_compile_duration": "fluid.compile.backend",
}

#: the span a step's jit call runs under -> the root that says whether
#: the executor's own cache held the entry
_CALL_ROOTS = {"fluid.run.call": "fluid.run",
               "executor.dispatch": "executor.window"}


def _on_jax_start(event: str, _value, **_kw) -> None:
    """jax stamps the START of each timed phase as a scalar event.  Only
    the depth of open traces and lowerings is kept: tracing a step traces
    every jitted function it calls, and lowering an interpreted Pallas
    call traces its body (36,669 trace events in a Transformer rehearsal,
    a few dozen outermost), and only an outermost trace becomes a span."""
    if event == _JAX_TRACE or event == _JAX_LOWER:
        _tls.compiling = getattr(_tls, "compiling", 0) + 1


def _on_jax_duration(event: str, duration: float, **kw) -> None:
    """One ended phase of jax's compile path -> a span under whatever is
    open on this thread (``fluid.run.call``, usually), and the counters
    ``compile.lowerings`` / ``compile.backend_compiles``.

    ``executor.relowerings`` counts a lowering inside a ``fluid.run.call``
    (or a window's ``executor.dispatch``) whose ``fluid.run`` (or
    ``executor.window``) root has ``fresh`` false: the executor held a
    compiled entry for that program and feed, and jax lowered it again."""
    name = _JAX_EVENTS.get(event)
    if name is None:
        return
    try:
        if event == _JAX_TRACE or event == _JAX_LOWER:
            depth = _tls.compiling = max(
                0, getattr(_tls, "compiling", 1) - 1)
            if depth and event == _JAX_TRACE:
                return
        t1 = time.perf_counter()
        emit_span(name, t1 - duration, t1, fun=kw.get("fun_name"))
        if event == _JAX_TRACE:
            return
        from . import registry

        reg = registry()
        if event != _JAX_LOWER:
            reg.inc("compile.backend_compiles")
            return
        reg.inc("compile.lowerings")
        stack = getattr(_tls, "stack", None)
        root = _CALL_ROOTS.get(stack[-1].name) if stack else None
        if root is not None and any(
                s.name == root and s.attrs.get("fresh") is False
                for s in reversed(stack)):
            reg.inc("executor.relowerings")
    except Exception:
        pass  # telemetry must never fail the compile it measures


jax.monitoring.register_scalar_listener(_on_jax_start)
jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


# ---------------------------------------------------------------------------
# the per-window host breakdown
# ---------------------------------------------------------------------------


def note_window_breakdown(host_ms: float, stage_ms: float,
                          dispatch_ms: float, observe_ms: float,
                          mesh: Optional[str] = None) -> None:
    """The per-window host breakdown gauge family: host-side prep / H2D
    staging / the enqueue of the window (trace + compile on a fresh
    entry; NOT the device's time) / host observe tail, milliseconds."""
    try:
        from . import registry

        reg = registry()
        labels = {"mesh": mesh} if mesh else None
        for name, v in (("window.host_ms", host_ms),
                        ("window.stage_ms", stage_ms),
                        ("window.dispatch_ms", dispatch_ms),
                        ("window.observe_ms", observe_ms)):
            reg.set_gauge(name, round(float(v), 3), labels=labels)
    except Exception:
        pass


def reset() -> None:
    """Clear the ring, re-arm env late-binding and clear this thread's
    context stack (test-harness hook, called from ``observe.reset``)."""
    global _trace_id, _env_parent
    _ring.clear()
    with _state_lock:
        _trace_id = None
        _env_parent = None
    if getattr(_tls, "stack", None):
        _tls.stack = []
