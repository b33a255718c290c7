"""Device-memory observability: compiled truth + the live-buffer ledger.

PR 9 answered *where the time went*; this module answers *where the HBM
went* — the other half of TPU production observability.  An OOM used to
surface as an opaque XLA RESOURCE_EXHAUSTED with no gauge, event or
pre-flight warning; now three tiers cover it:

 1. **Compiled truth** (:func:`memory_stats` / :func:`note_compiled_memory`)
    — ``compiled.memory_analysis()`` read at the existing AOT-lower points
    (the PR 7 ``ShardedWindowRunner``, ``ServingEngine.warmup()``; for a
    single-device program ``Executor.compiled_memory_stats`` when the
    caller asks) into always-on gauges
    ``memory.peak_bytes{mesh=...}`` / ``memory.argument_bytes`` /
    ``memory.output_bytes`` / ``memory.temp_bytes`` /
    ``memory.generated_code_bytes`` plus one ``memory.profile`` run event
    per executable.  The stats also land in the compile-cache manifest, so
    a warm start re-reports memory WITHOUT re-lowering
    (``compile_cache._Probe.finish``).

 2. **Pre-flight estimate** — ``paddle_tpu.analysis.memcheck`` (AN5xx):
    the static twin of this module, cross-checked against
    :func:`memory_stats` in tests the way AN204's collective estimate is
    cross-checked against ``spmd.collective_bytes``.

 3. **Live-buffer ledger** (:class:`LiveBufferLedger`) — host-side
    tracking of live ``jax.Array`` bytes per (scope, mesh): the executor
    paths report their scope's device residency after each state commit,
    the prefetcher reports its staged-window bytes, and the ledger turns
    them into ``memory.live_bytes{scope=,mesh=}`` /
    ``memory.live_high_water_bytes`` gauges, ``memory.watermark`` run
    events at window boundaries (gated by ``PADDLE_MEM_WATERMARK``), a
    ``memory.over_budget`` event when residency exceeds
    ``PADDLE_MEM_BUDGET_MB``, and an SLO-watchdog feed
    (``memory.live_bytes``) so monotonic growth across windows or elastic
    generations breaches like a slow step — leak detection with the same
    median+MAD machinery that catches latency regressions.
    ``PADDLE_FAULT_MEM_PRESSURE`` synthesizes that growth
    deterministically (``fluid.fault.mem_pressure_bytes``).

Chrome-trace integration: watermark events carry a ``counters`` field the
exporter renders as ``"ph": "C"`` counter tracks, and the gauges are
sampled by the profiler session (``registry.start_sampling``), so both
``python -m paddle_tpu.observe export`` and ``tools/timeline.py`` show
HBM residency alongside the span timeline.

Costs: reading ``memory_analysis()`` needs a *compiled* executable.  The
sharded window runner already AOT-compiles (free); a single-device
window is never lowered a second time for it (a span never lowers:
``Executor.compiled_memory_stats`` costs one backend compile, which the
persistent backend cache dedupes, and is the caller's decision); warmup
is the precompile path by definition.
The ledger is a sum of ``nbytes`` over scope entries per window — host
arithmetic, no device sync.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Tuple

__all__ = [
    "memory_stats", "note_compiled_memory", "LiveBufferLedger", "ledger",
    "scope_live_bytes", "note_scope_live", "adjust_staged", "reset",
]

#: gauge names published by note_compiled_memory, in stat-key order
COMPILED_GAUGES = (
    ("peak_bytes", "memory.peak_bytes"),
    ("argument_bytes", "memory.argument_bytes"),
    ("output_bytes", "memory.output_bytes"),
    ("temp_bytes", "memory.temp_bytes"),
    ("generated_code_bytes", "memory.generated_code_bytes"),
)


def memory_stats(compiled) -> Optional[dict]:
    """``memory_analysis()`` of a jax ``Compiled`` as a plain dict:
    ``{"peak_bytes", "argument_bytes", "output_bytes", "temp_bytes",
    "generated_code_bytes", "alias_bytes"}`` — per-device bytes of the
    executable.  ``peak_bytes`` is the standard buffer-assignment
    approximation ``argument + output - alias + temp + generated_code``
    (donated outputs alias their argument buffers and must not double
    count).  None when the backend exposes no memory analysis."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if isinstance(ma, (list, tuple)):
        ma = ma[0] if ma else None
    if ma is None:
        return None

    def _get(attr) -> int:
        try:
            return int(getattr(ma, attr, 0) or 0)
        except (TypeError, ValueError):
            return 0

    arg = _get("argument_size_in_bytes")
    out = _get("output_size_in_bytes")
    temp = _get("temp_size_in_bytes")
    code = _get("generated_code_size_in_bytes")
    alias = _get("alias_size_in_bytes")
    if arg + out + temp + code <= 0:
        return None
    peak = max(arg + out - alias + temp + code, arg, temp)
    return {"peak_bytes": peak, "argument_bytes": arg, "output_bytes": out,
            "temp_bytes": temp, "generated_code_bytes": code,
            "alias_bytes": alias}


def note_compiled_memory(stats: Optional[dict], mesh: Optional[str] = None,
                         kind: Optional[str] = None,
                         n_steps: Optional[int] = None,
                         cached: bool = False) -> None:
    """Publish one executable's memory stats: the ``memory.*`` gauge
    family (mesh-labeled on sharded runs) plus one ``memory.profile`` run
    event.  ``cached=True`` marks a warm-start re-report from a
    compile-cache manifest (no lowering happened).  Never raises."""
    if not stats:
        return
    try:
        from . import emit, registry

        reg = registry()
        labels = {"mesh": mesh} if mesh else None
        for key, gauge in COMPILED_GAUGES:
            v = stats.get(key)
            if isinstance(v, (int, float)):
                reg.set_gauge(gauge, float(v), labels=labels)
        emit("memory.profile", mesh=mesh, kind=kind, n_steps=n_steps,
             cached=bool(cached) or None,
             **{k: stats.get(k) for k, _ in COMPILED_GAUGES},
             alias_bytes=stats.get("alias_bytes"))
    except Exception:
        pass  # accounting must never fail the run it measures


# ---------------------------------------------------------------------------
# live-buffer ledger
# ---------------------------------------------------------------------------


def scope_live_bytes(scope) -> int:
    """Total bytes of device-resident ``jax.Array`` values a Scope holds
    (logical/global bytes; divide by the shard count for per-device).
    Host numpy state counts zero — it is not HBM."""
    import jax

    total = 0
    for val in list(scope._values.values()):
        if isinstance(val, jax.Array):
            try:
                total += int(val.nbytes)
            except Exception:
                pass
    return total


class LiveBufferLedger:
    """Thread-safe live/high-water accounting per (scope label, mesh).

    One process-wide instance (``ledger()``); writers are the executor
    window paths (scope residency after each state commit), the device
    prefetcher (staged-window bytes), and anything else holding device
    buffers worth attributing.  Every update refreshes the gauges; the
    TOTAL across keys feeds the SLO watchdog and the budget check."""

    def __init__(self):
        self._lock = threading.Lock()
        self._live: Dict[Tuple[str, str], int] = {}
        self._high: Dict[Tuple[str, str], int] = {}

    def _key(self, scope_label: str, mesh: Optional[str]):
        return (str(scope_label), mesh or "")

    def update(self, scope_label: str, nbytes: int,
               mesh: Optional[str] = None, step: Optional[int] = None,
               emit_event: bool = False) -> int:
        """Set one key's live bytes (absolute).  Returns the process-total
        live bytes after the update (fault mem-pressure included)."""
        nbytes = max(0, int(nbytes))
        key = self._key(scope_label, mesh)
        with self._lock:
            self._live[key] = nbytes
            high = max(self._high.get(key, 0), nbytes)
            self._high[key] = high
            total = sum(self._live.values())
        try:
            from ..fluid import fault as _fault

            total += _fault.mem_pressure_bytes()
        except Exception:
            pass
        self._publish(key, nbytes, high, total, step, emit_event)
        return total

    def adjust(self, scope_label: str, delta: int,
               mesh: Optional[str] = None) -> int:
        """Relative update (the prefetcher's +staged/-consumed path)."""
        key = self._key(scope_label, mesh)
        with self._lock:
            cur = max(0, self._live.get(key, 0) + int(delta))
        return self.update(scope_label, cur, mesh=mesh)

    def live(self, scope_label: str, mesh: Optional[str] = None) -> int:
        with self._lock:
            return self._live.get(self._key(scope_label, mesh), 0)

    def high_water(self, scope_label: str,
                   mesh: Optional[str] = None) -> int:
        with self._lock:
            return self._high.get(self._key(scope_label, mesh), 0)

    def _publish(self, key, nbytes, high, total, step, emit_event) -> None:
        try:
            from . import emit, registry
            from .watchdog import observe_value
            from ..fluid import envcontract

            scope_label, mesh = key
            labels = {"scope": scope_label}
            if mesh:
                labels["mesh"] = mesh
            reg = registry()
            reg.set_gauge("memory.live_bytes", float(nbytes), labels=labels)
            reg.set_gauge("memory.live_high_water_bytes", float(high),
                          labels=labels)
            reg.set_gauge("memory.live_total_bytes", float(total))
            # leak detection: the TOTAL feeds the watchdog, so growth in
            # any scope (or an injected PADDLE_FAULT_MEM_PRESSURE ramp)
            # breaches like a slow step
            observe_value("memory.live_bytes", float(total), step=step,
                          scope=scope_label)
            budget_mb = envcontract.get("PADDLE_MEM_BUDGET_MB")
            over = (budget_mb is not None
                    and total > float(budget_mb) * (1 << 20))
            if over:
                reg.inc("memory.over_budget")
            if emit_event and envcontract.get("PADDLE_MEM_WATERMARK"):
                from .registry import render_name

                emit("memory.watermark", scope=scope_label,
                     mesh=mesh or None, live_bytes=int(nbytes),
                     high_water_bytes=int(high), total_bytes=int(total),
                     counters={render_name(
                         "memory.live_bytes",
                         tuple(sorted(labels.items()))): int(nbytes)})
            if over:
                emit("memory.over_budget", scope=scope_label,
                     mesh=mesh or None, total_bytes=int(total),
                     budget_mb=budget_mb)
        except Exception:
            pass

    def clear(self) -> None:
        with self._lock:
            self._live.clear()
            self._high.clear()


_ledger = LiveBufferLedger()


def ledger() -> LiveBufferLedger:
    """THE process live-buffer ledger."""
    return _ledger


def note_scope_live(scope, scope_label: str = "train",
                    mesh: Optional[str] = None, step: Optional[int] = None,
                    emit_event: bool = True) -> int:
    """Report a Scope's current device residency to the ledger — the
    executor window paths call this right after committing new state.
    ``emit_event=False`` is the per-step path's quiet form (gauges only,
    no watermark record per step).  Never raises; returns total bytes."""
    try:
        return _ledger.update(scope_label, scope_live_bytes(scope),
                              mesh=mesh, step=step, emit_event=emit_event)
    except Exception:
        return 0


def adjust_staged(delta: int, mesh: Optional[str] = None) -> None:
    """Prefetcher hook: add (staged) / subtract (consumed) window bytes
    under the ``prefetch`` scope label."""
    try:
        _ledger.adjust("prefetch", delta, mesh=mesh)
    except Exception:
        pass


def reset() -> None:
    """Clear ledger state (test-harness hook, via ``observe.reset``)."""
    _ledger.clear()
