"""paddle_tpu.compile_cache — persistent, cross-process compilation cache.

Without it every process pays full XLA compilation from zero: about five
minutes before a benchmark cell's first step (``first_setup_s``, PERF.md
section 2), every elastic-supervisor
generation recompiling the exact program the dead generation ran, every
serving restart re-AOT-compiling its whole bucket set.  This package makes
compiled programs a durable artifact:

 - :mod:`fingerprint` — a stable content hash over the ProgramDesc + jit
   configuration + toolchain, invariant to variable-name noise;
 - :mod:`store` — an on-disk artifact store (atomic ``_SUCCESS`` commits,
   LRU size budget, corruption-tolerant loads), and the one function
   that places jax's persistent compilation cache for the backend
   executables (``store.backend_cache_dir``);
 - this module — process-level wiring: the env-driven singleton and the
   Executor-facing probe API.

Env contract::

    PADDLE_COMPILE_CACHE_DIR        enable, rooted here
    PADDLE_COMPILE_CACHE_BUDGET_MB  optional LRU size budget

Operate it with ``tools/cache_ctl.py`` (ls/stats/verify/prune/clear).
"""

from __future__ import annotations

import os
from typing import Optional

from .fingerprint import program_fingerprint, program_signature
from .store import (CompileCacheStore, backend_cache_dir, checkout_root,
                    place_backend_cache)

__all__ = [
    "program_fingerprint", "program_signature", "CompileCacheStore",
    "get_store", "configure", "disable", "reset", "executor_probe",
    "backend_cache_dir", "checkout_root",
]

ENV_DIR = "PADDLE_COMPILE_CACHE_DIR"
ENV_BUDGET = "PADDLE_COMPILE_CACHE_BUDGET_MB"

# _UNSET = env not yet consulted (same late-binding contract as
# fluid.fault: a subprocess that sets PADDLE_COMPILE_CACHE_DIR before
# first executor use is honored without import-order dependencies)
_UNSET = object()
_store = _UNSET


def get_store() -> Optional[CompileCacheStore]:
    """The process-wide store, built lazily from the env; None = disabled."""
    global _store
    if _store is _UNSET:
        d = os.environ.get(ENV_DIR, "").strip()
        if not d:
            _store = None
        else:
            # an unusable root raises here: a cache that was asked for
            # and silently is not there would pass for a cold start
            configure(d, os.environ.get(ENV_BUDGET, "").strip() or None)
    return _store


def configure(root: str,
              budget_mb: Optional[float] = None) -> CompileCacheStore:
    """Enable programmatically (overrides the env)."""
    global _store
    _store = CompileCacheStore(root, budget_mb)
    place_backend_cache(True)
    return _store


def disable() -> None:
    global _store
    _store = None


def reset() -> None:
    """Back to the unconsulted state (env honored on next use) and detach
    the backend cache dir.  Test-harness hook."""
    global _store
    if _store not in (None, _UNSET):
        place_backend_cache(False)
    _store = _UNSET


# ---------------------------------------------------------------------------
# Executor-facing probe
# ---------------------------------------------------------------------------


class _Probe:
    """One pending compile: created at store-lookup time (before tracing),
    finished after the first dispatch (which is where jax actually traces
    AND compiles).  ``finish`` is idempotent and never raises — cache
    bookkeeping must not fail the run it measures."""

    __slots__ = ("store", "fp", "hit", "done", "manifest")

    def __init__(self, store: CompileCacheStore, fp: str, hit: bool,
                 manifest: Optional[dict] = None):
        self.store = store
        self.fp = fp
        self.hit = hit
        self.manifest = manifest
        self.done = False

    def finish(self, seconds: float, program=None,
               meta: Optional[dict] = None) -> None:
        if self.done:
            return
        self.done = True
        try:
            from ..fluid import profiler as _prof
            from .. import observe

            _prof.record_counter("compile_cache.compile_seconds",
                                 inc=round(float(seconds), 6))
            # warm starts and cold compiles belong in the run-event stream
            # next to guardian trips and generation restarts — a restarted
            # generation's cache hits are the proof its recovery was cheap
            observe.emit("compile_cache.hit" if self.hit
                         else "compile_cache.miss",
                         fingerprint=self.fp[:12],
                         first_dispatch_s=round(float(seconds), 6),
                         kind=(meta or {}).get("kind"))
            if self.hit and isinstance(self.manifest, dict) \
                    and isinstance(self.manifest.get("memory"), dict):
                # the per-executable memory table persisted at compile
                # time: a warm start republishes the memory.peak_bytes
                # gauge family WITHOUT re-lowering anything
                from ..observe import memory as _obsmem

                _obsmem.note_compiled_memory(
                    self.manifest["memory"],
                    mesh=self.manifest.get("mesh"),
                    kind=self.manifest.get("kind"),
                    n_steps=self.manifest.get("n_steps"), cached=True)
            if not self.hit and program is not None:
                m = dict(meta or {})
                m["compile_seconds"] = round(float(seconds), 6)
                self.store.put(self.fp, program.serialize_to_string(), m)
        except Exception:
            try:
                from ..fluid import profiler as _prof

                _prof.record_counter("compile_cache.error")
            except Exception:
                pass


def executor_probe(program, feed_arrays=None, fetch_names=None,
                   extra=None, spec_table=None) -> Optional[_Probe]:
    """Consult the store for an executor-shaped program specialization.

    Called by ``Executor.run``/``run_steps`` (and the SPMD step/window
    runners, which also pass their mesh-derived ``spec_table``) right
    before building a fresh jit entry (i.e. on every in-process cache
    miss).  Returns None when the cache is disabled or fingerprinting
    fails; otherwise a :class:`_Probe` whose hit/miss was already
    counted."""
    store = get_store()
    if store is None:
        return None
    try:
        feeds = [(k, tuple(v.shape), str(v.dtype))
                 for k, v in sorted((feed_arrays or {}).items())]
        fp = program_fingerprint(program, feeds=feeds,
                                 fetches=list(fetch_names or []),
                                 extra=extra, spec_table=spec_table)
        manifest = store.get(fp)
        from .. import observe

        # every event the run emits from here on correlates to this program
        observe.note_program(fp[:12])
        return _Probe(store, fp, manifest is not None, manifest)
    except Exception:
        try:
            from ..fluid import profiler as _prof

            _prof.record_counter("compile_cache.error")
        except Exception:
            pass
        return None
