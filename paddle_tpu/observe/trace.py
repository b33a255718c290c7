"""Distributed span tracer + device-time attribution (ISSUE 9 tentpole).

PR 5's run-event stream answers *what happened*; this module answers
*where the time went*.  A span is one timed region with W3C-style
identity — a 32-hex ``trace_id`` shared by everything in one logical
run/request and a 16-hex ``span_id`` per region, with ``parent_span``
links forming the tree — emitted into the SAME per-process run-event
JSONL the fleet aggregator already merges, so one ``chrome://tracing``
export shows supervisor generations, executor windows, prefetch staging
on its worker thread, and per-request serving breakdowns as nested
duration events.

API surface (all no-ops returning ``None`` when tracing is off):

 - ``span(name, **attrs)`` — context manager; pushes the span onto the
   calling thread's context stack so nested spans parent automatically
   and every ``observe.emit`` record inside is stamped with
   (trace_id, span_id);
 - ``start_span(name, parent=..., **attrs)`` / ``Span.end(**attrs)`` —
   explicit pair for async hand-offs (a serving request's span lives
   across the batcher thread; a prefetch stage span lives on the worker
   thread);
 - ``emit_span(name, t0, t1, parent=...)`` — record an already-measured
   ``perf_counter`` interval as a child span (queue-wait spans are known
   only after the fact).

Enablement: ``PADDLE_TRACE`` (default on) gates everything, and spans
only materialize when an observe sink exists (``PADDLE_OBSERVE_DIR``) —
so production runs without an observe dir pay a single dict lookup per
window, and ``PADDLE_TRACE=0`` forces the hot paths back to their exact
pre-trace shape (no device sync, no extra lowering).
``PADDLE_TRACE_SAMPLE`` keeps every Nth root span (deterministic
counter-based sampling — no RNG on the hot path); children inherit their
root's decision by construction (an unsampled root returns ``None`` and
its would-be children become roots of their own sampling decision).

Cross-process stitching: ``PADDLE_TRACEPARENT`` (W3C ``traceparent``
shape, ``00-<trace>-<span>-01``) seeds this process's trace id and
default root parent.  The elastic supervisor mints ONE trace id per run,
opens a span per generation, and hands each generation
``PADDLE_TRACEPARENT`` pointing at its generation span — so a
kill-and-resume run merges into one trace tree spanning processes.

Device-time attribution: :func:`cost_of` reads ``cost_analysis()`` off a
jax ``Lowered``/``Compiled`` (flops + bytes accessed of the whole fused
window program) and :func:`note_device_cost` turns it into the
``device.flops_per_window`` / ``device.mfu{mesh=...}`` gauges
(model-flops-utilization = flops / wall / peak);
:func:`note_window_breakdown` publishes the per-window
``window.host_ms`` / ``window.stage_ms`` / ``window.device_ms`` /
``window.observe_ms`` gauge family the step-time breakdown view reads.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import threading
import time
from typing import Optional

__all__ = [
    "Span", "span", "start_span", "emit_span", "current", "enabled",
    "trace_context", "set_trace_context", "new_span_id",
    "format_traceparent", "parse_traceparent", "thread_tid",
    "cost_of", "device_peak_flops", "peak_tflops", "note_device_cost",
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


def new_span_id() -> str:
    return _gen_id(8)


def current() -> Optional["Span"]:
    """The calling thread's innermost open ``span(...)`` context."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


def enabled() -> bool:
    """Tracing is on: ``PADDLE_TRACE`` truthy AND an observe sink exists
    (spans land in the run-event stream; without a stream there is
    nowhere to put them, so the hot paths skip all measurement)."""
    from ..fluid import envcontract

    if not envcontract.get("PADDLE_TRACE"):
        return False
    from . import get_sink

    return get_sink() is not None


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


class Span:
    """One open timed region.  ``end()`` emits a single run-event record
    carrying ``dur_s`` + the trace identity; it is idempotent, returns
    the duration in seconds, and never raises."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "attrs",
                 "tid", "ended", "_t0", "_emit")

    def __init__(self, name: str, trace_id: str, parent_id: Optional[str],
                 attrs: dict, emit_fn=None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = _gen_id(8)
        self.parent_id = parent_id
        self.attrs = attrs
        self.tid = thread_tid()
        self.ended = False
        self._t0 = time.perf_counter()
        self._emit = emit_fn

    def end(self, **extra) -> Optional[float]:
        if self.ended:
            return None
        self.ended = True
        t1 = time.perf_counter()
        dur = t1 - self._t0
        fields = dict(self.attrs)
        fields.update(extra)
        _do_emit(self._emit, self.name, ts=_wall(t1),
                 dur_s=round(dur, 6), trace_id=self.trace_id,
                 span_id=self.span_id, parent_span=self.parent_id,
                 tid=self.tid, **fields)
        return dur


def start_span(name: str, parent: Optional[Span] = None, emit_fn=None,
               **attrs) -> Optional[Span]:
    """Open a span WITHOUT touching the thread context stack (async
    hand-off form — the opener and the closer may be different threads).
    Returns None when tracing is off or the root sampler says skip."""
    try:
        if not enabled():
            return None
        if parent is None:
            parent = current()
        if parent is not None:
            trace_id, parent_id = parent.trace_id, parent.span_id
        else:
            if not _sample_root():
                return None
            trace_id, parent_id = trace_context()
        return Span(name, trace_id, parent_id, attrs, emit_fn)
    except Exception:
        return None


@contextlib.contextmanager
def span(name: str, **attrs):
    """Context-manager span: children opened inside parent to it, and
    ``observe.emit`` records inside are stamped with its identity.
    Yields the Span (or None when tracing is off/sampled out)."""
    sp = start_span(name, **attrs)
    if sp is None:
        yield None
        return
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    stack.append(sp)
    try:
        yield sp
    finally:
        if stack and stack[-1] is sp:
            stack.pop()
        sp.end()


def emit_span(name: str, t0: float, t1: float,
              parent: Optional[Span] = None, emit_fn=None,
              **attrs) -> Optional[str]:
    """Record an already-measured ``perf_counter`` interval as a child of
    ``parent`` (queue waits, H2D staging, dispatch segments — intervals
    whose boundaries are only known after the fact).  Returns the new
    span id, or None when there is no live parent to hang it off."""
    if parent is None:
        return None
    try:
        span_id = _gen_id(8)
        _do_emit(emit_fn, name, ts=_wall(t1),
                 dur_s=round(max(0.0, t1 - t0), 6),
                 trace_id=parent.trace_id, span_id=span_id,
                 parent_span=parent.span_id, tid=thread_tid(), **attrs)
        return span_id
    except Exception:
        return None


# ---------------------------------------------------------------------------
# device-time attribution: compiled cost -> flops/MFU/breakdown gauges
# ---------------------------------------------------------------------------

#: Peak dense bf16 TFLOP/s of one chip, keyed by ``device_kind`` exactly
#: as jax reports it — the one such table in the repo (bench.py reads it
#: too).  A kind that is not here is an error, never a default: add it
#: with its source when such a chip is attached.
#:  - "TPU v5 lite" (v5e): 197, Google Cloud documentation, "TPU v5e".
#:  - "cpu": a documented NOMINAL figure, there only so that the
#:    ``device.mfu`` ratio stays defined on the test backend; a ratio
#:    against it is a diagnostic, not a device metric.
PEAK_BF16_TFLOPS = {"TPU v5 lite": 197.0, "cpu": 0.5}


def peak_tflops(device_kind: str) -> float:
    """Peak bf16 TFLOP/s of one ``device_kind`` chip; unknown kinds raise."""
    try:
        return PEAK_BF16_TFLOPS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak rate recorded for device_kind {device_kind!r}; add "
            f"it with its source to observe.trace.PEAK_BF16_TFLOPS "
            f"(known: {sorted(PEAK_BF16_TFLOPS)})") from None


def device_peak_flops(device=None) -> float:
    """Peak FLOPs/s of ``device`` (default: the first jax device)."""
    import jax

    if device is None:
        device = jax.devices()[0]
    return peak_tflops(device.device_kind) * 1e12


def cost_of(stage) -> Optional[dict]:
    """``{"flops": f, "bytes": b}`` from a jax ``Lowered`` or ``Compiled``
    stage's ``cost_analysis()`` (list-of-dict on some backends); None when
    the backend exposes no cost model."""
    try:
        ca = stage.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    flops = float(ca.get("flops", 0.0) or 0.0)
    nbytes = float(ca.get("bytes accessed", 0.0) or 0.0)
    if flops <= 0.0:
        return None
    return {"flops": flops, "bytes": nbytes}


def note_device_cost(cost: Optional[dict], wall_s: float, n_steps: int,
                     mesh: Optional[str] = None, device=None) -> Optional[float]:
    """Publish the device-attribution gauges for one executed window:
    ``device.flops_per_window`` / ``device.bytes_per_window`` (the whole
    fused program's cost) and ``device.mfu{mesh=...}`` = flops / wall /
    peak.  Returns the MFU, or None when no cost is available."""
    if not cost or wall_s <= 0.0:
        return None
    peak = device_peak_flops(device)  # an unknown chip is an error
    try:
        from . import registry

        reg = registry()
        labels = {"mesh": mesh} if mesh else None
        reg.set_gauge("device.flops_per_window", cost["flops"],
                      labels=labels)
        reg.set_gauge("device.bytes_per_window", cost["bytes"],
                      labels=labels)
        mfu = cost["flops"] / wall_s / peak
        reg.set_gauge("device.mfu", mfu, labels=labels)
        reg.set_gauge("device.flops_per_sec", cost["flops"] / wall_s,
                      labels=labels)
        return mfu
    except Exception:
        return None


def note_window_breakdown(host_ms: float, stage_ms: float,
                          device_ms: float, observe_ms: float,
                          mesh: Optional[str] = None) -> None:
    """The per-window step-time breakdown gauge family: host-side prep /
    H2D staging / device execution / host observe tail, milliseconds."""
    try:
        from . import registry

        reg = registry()
        labels = {"mesh": mesh} if mesh else None
        for name, v in (("window.host_ms", host_ms),
                        ("window.stage_ms", stage_ms),
                        ("window.device_ms", device_ms),
                        ("window.observe_ms", observe_ms)):
            reg.set_gauge(name, round(float(v), 3), labels=labels)
    except Exception:
        pass


def reset() -> None:
    """Re-arm env late-binding and clear this thread's context stack
    (test-harness hook, called from ``observe.reset``)."""
    global _trace_id, _env_parent
    with _state_lock:
        _trace_id = None
        _env_parent = None
    if getattr(_tls, "stack", None):
        _tls.stack = []
