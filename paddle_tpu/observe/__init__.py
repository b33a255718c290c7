"""paddle_tpu.observe — the unified observability subsystem (ISSUE 5).

Four subsystems (executor, serving engine, guardian, compile cache,
elastic supervisor) used to emit counters into ``fluid.profiler``'s
module-level plain dict: unlabeled, racy under serving threads, invisible
across processes, unexportable.  This package is the single place they all
emit into now:

 - :mod:`registry` — the process-wide thread-safe
   :class:`~paddle_tpu.observe.registry.MetricsRegistry` (counters /
   gauges / histograms / timings, label support);
 - :mod:`events`   — the structured run-event log (JSONL, stamped with
   host / rank / elastic generation / step / program fingerprint);
 - :mod:`export`   — Prometheus-text + JSON snapshot writers and the
   chrome-trace exporter;
 - :mod:`http`     — the localhost ``/metrics`` + ``/healthz`` endpoint;
 - :mod:`fleet`    — cross-process aggregation of many workers' files.

Env contract (late-bound, same pattern as ``compile_cache``: a subprocess
that sets the env before first use is honored with no import-order
dependency)::

    PADDLE_OBSERVE_DIR      enable file output, rooted here (events JSONL
                            + periodic metric snapshots per process)
    PADDLE_OBSERVE_FLUSH_S  snapshot flush interval, seconds (default 5)
    PADDLE_OBSERVE_PORT     serve /metrics + /healthz on 127.0.0.1:<port>
                            (0 picks an ephemeral port; the endpoint is
                            part of the sink, so it requires
                            PADDLE_OBSERVE_DIR to be set too)

CLI: ``python -m paddle_tpu.observe {tail,summary,export,serve}`` and
``--smoke`` (tier-1 CI round-trip).  Operate guide: docs/OBSERVABILITY.md.
"""

from __future__ import annotations

import atexit
import os
import threading
import time
from typing import Optional

from .events import EventLog, host_name
from .registry import MetricsRegistry

__all__ = [
    "MetricsRegistry", "EventLog", "registry", "get_sink", "configure",
    "disable", "reset", "emit", "span", "note_step", "note_program",
    "note_mesh", "note_commit_step", "current_step", "current_program",
    "current_mesh", "current_commit_step", "step_gauge", "step_gauges",
    "http_server", "ENV_DIR", "ENV_FLUSH", "ENV_PORT",
    # submodules re-exported for discoverability: observe.trace (the span
    # primitive + the ring), observe.watchdog (SLO breaches),
    # observe.memory (HBM accounting + live-buffer ledger),
    # observe.goodput (wall-clock state accounting + straggler ledger)
    "trace", "watchdog", "memory", "goodput",
]

ENV_DIR = "PADDLE_OBSERVE_DIR"
ENV_FLUSH = "PADDLE_OBSERVE_FLUSH_S"
ENV_PORT = "PADDLE_OBSERVE_PORT"

# ---------------------------------------------------------------------------
# process-wide registry + execution context
# ---------------------------------------------------------------------------

_registry = MetricsRegistry()

# set by the executor at step boundaries / program (re)binds; read by every
# EventLog.emit so all subsystems' events correlate on (step, program)
# without plumbing arguments through their APIs.  Plain attribute writes —
# atomic under the GIL, and a torn read costs one stale stamp, not
# correctness.
_step: Optional[int] = None
_program: Optional[str] = None
_mesh: Optional[str] = None
_commit_step: Optional[int] = None


def registry() -> MetricsRegistry:
    """THE process metrics registry (``fluid.profiler.record_counter``'s
    backend; serving/guardian/compile-cache counters all land here)."""
    return _registry


def note_step(step: Optional[int]) -> None:
    global _step
    _step = step


def note_program(fingerprint: Optional[str]) -> None:
    """Record the executing program's fingerprint (first 12 hex chars are
    plenty for correlation) for event stamping."""
    global _program
    _program = fingerprint


def note_mesh(label: Optional[str]) -> None:
    """Record the executing mesh topology (``dp4xtp2``-style label from
    ``parallel.mesh.mesh_label``) for event stamping — so fleet views can
    distinguish what topology a trip/cache-hit/checkpoint happened on."""
    global _mesh
    _mesh = label


def note_commit_step(step: Optional[int]) -> None:
    """Record the last CHECKPOINT-COMMITTED step (set at every _SUCCESS
    write, single-process and sharded).  Heartbeat files carry it so
    ``incidents.jsonl`` shows progress-at-death and the goodput ledger can
    price the work a restart loses (``last_step - commit_step``)."""
    global _commit_step
    _commit_step = step


def current_step() -> Optional[int]:
    return _step


def current_program() -> Optional[str]:
    return _program


def current_mesh() -> Optional[str]:
    return _mesh


def current_commit_step() -> Optional[int]:
    return _commit_step


# ---------------------------------------------------------------------------
# sink: the per-process file/endpoint writer
# ---------------------------------------------------------------------------


class Sink:
    """Owns this process's observability outputs: the event log file, the
    periodic metric-snapshot flusher, and (optionally) the HTTP endpoint.

    One sink per process; files are named for the (host, rank, generation)
    stamp so concurrent workers and successive elastic generations never
    share a file (``fleet`` merges them)."""

    def __init__(self, root: str, flush_s: Optional[float] = None,
                 port: Optional[int] = None, *,
                 host: Optional[str] = None, rank: Optional[int] = None,
                 gen: Optional[int] = None,
                 reg: Optional[MetricsRegistry] = None):
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)
        self.registry = reg if reg is not None else _registry
        self.host = host if host is not None else host_name()
        self.rank = int(rank if rank is not None
                        else os.environ.get("PADDLE_TRAINER_ID", "0") or 0)
        self.gen = int(gen if gen is not None
                       else os.environ.get("PADDLE_ELASTIC_GENERATION",
                                           "0") or 0)
        self._stem = f"{self.host}-r{self.rank}-g{self.gen}"
        self.events = EventLog(
            os.path.join(self.root, f"events-{self._stem}.jsonl"),
            host=self.host, rank=self.rank, gen=self.gen)
        if flush_s is None:
            try:
                flush_s = float(os.environ.get(ENV_FLUSH, "") or 5.0)
            except ValueError:
                flush_s = 5.0
        self.flush_s = max(0.05, float(flush_s))
        self.server = None
        if port is None:
            p = os.environ.get(ENV_PORT, "").strip()
            port = int(p) if p else None
        if port is not None:
            from .http import MetricsServer

            self.server = MetricsServer(
                port, providers=[self.registry.snapshot],
                health=lambda: {"ok": True, "host": self.host,
                                "rank": self.rank, "gen": self.gen})
        self._stop = threading.Event()
        self._flusher = threading.Thread(target=self._flush_loop,
                                         name="observe-flusher", daemon=True)
        self._flusher.start()
        # short-lived workers (one elastic generation) must still leave a
        # final snapshot behind for the fleet aggregator
        atexit.register(self.flush)

    def metrics_stem(self) -> str:
        return f"metrics-{self._stem}"

    def flush(self) -> None:
        """Write this process's metric snapshot files (atomic)."""
        from .export import write_snapshot

        try:
            write_snapshot(
                self.root, self.registry.snapshot(),
                stem=self.metrics_stem(),
                meta={"host": self.host, "rank": self.rank, "gen": self.gen,
                      "pid": os.getpid(), "ts": time.time(),
                      "step": current_step()})
        except OSError:
            pass  # a full disk must not take the training down with it

    def _flush_loop(self) -> None:
        while not self._stop.wait(self.flush_s):
            self.flush()

    def close(self) -> None:
        self._stop.set()
        if self.server is not None:
            self.server.close()
        self.flush()
        try:
            atexit.unregister(self.flush)
        except Exception:
            pass


# late-binding singleton (same _UNSET contract as compile_cache.get_store)
_UNSET = object()
_sink = _UNSET
_sink_lock = threading.Lock()


def get_sink() -> Optional[Sink]:
    """The process sink, built lazily from the env; None = file output and
    endpoint disabled (the in-memory registry always works)."""
    global _sink
    if _sink is _UNSET:
        with _sink_lock:
            if _sink is _UNSET:
                d = os.environ.get(ENV_DIR, "").strip()
                if not d:
                    _sink = None
                else:
                    try:
                        _sink = Sink(d)
                    except Exception:
                        _sink = None  # unusable dir must not fail the run
    return _sink


def configure(root: str, flush_s: Optional[float] = None,
              port: Optional[int] = None, **kw) -> Sink:
    """Enable programmatically (overrides the env)."""
    global _sink
    with _sink_lock:
        if _sink not in (None, _UNSET):
            _sink.close()
        _sink = Sink(root, flush_s=flush_s, port=port, **kw)
    return _sink


def disable() -> None:
    global _sink
    with _sink_lock:
        if _sink not in (None, _UNSET):
            _sink.close()
        _sink = None


def reset() -> None:
    """Close the sink, clear the registry and context, and re-arm env
    late-binding.  Test-harness hook (tests/conftest.py)."""
    global _sink, _step, _program, _mesh, _commit_step
    with _sink_lock:
        if _sink not in (None, _UNSET):
            _sink.close()
        _sink = _UNSET
    _registry.clear()
    _registry.stop_sampling()
    _step = None
    _program = None
    _mesh = None
    _commit_step = None
    # span tracer + SLO watchdog + memory ledger + goodput accumulator
    # piggyback on the sink lifecycle: re-arm their env late-binding /
    # clear their state with it
    from . import goodput as _goodput
    from . import memory as _memory
    from . import gauges as _gauges
    from . import trace as _trace
    from . import watchdog as _watchdog

    _trace.reset()
    _gauges.reset()
    _watchdog.reset()
    _memory.reset()
    _goodput.reset()


def http_server():
    """The sink's MetricsServer, or None (serving engine attaches its
    provider here when the env endpoint is up)."""
    sink = get_sink()
    return sink.server if sink is not None else None


# ---------------------------------------------------------------------------
# module-level emit helpers (the API subsystems call)
# ---------------------------------------------------------------------------


def emit(event: str, **fields) -> Optional[dict]:
    """Append one stamped record to the process event log; no-op (None)
    when no observe dir is configured.  Never raises."""
    try:
        sink = get_sink()
        if sink is None:
            return None
        return sink.events.emit(event, **fields)
    except Exception:
        return None


# submodules imported last (they only import observe lazily, so there is
# no cycle): observe.trace / observe.watchdog / observe.memory /
# observe.goodput are part of the public API
from . import goodput, memory, trace, watchdog  # noqa: E402,F401  (re-export)

#: the one span primitive, under its short name
span = trace.span

# device-valued gauges of a compiled step (observe/gauges.py): an op's
# lowering publishes with ``step_gauge``, ``step_gauges`` reads them late
from .gauges import step_gauge, step_gauges  # noqa: E402
