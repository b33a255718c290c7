"""Run-event log: append-only JSONL, stamped for cross-process correlation.

Every record carries the full correlation key the fleet aggregator joins
on — ``(host, rank, gen, step)`` plus the executing program's fingerprint
— so a guardian trip in generation 0, the compile-cache hits that made
generation 1's restart cheap, and the supervisor's ``generation_start``
decision all line up in ONE stream ordered by wall clock:

    {"ts": 1722777601.22, "event": "guardian_trip", "host": "tpu-a",
     "pid": 911, "rank": 0, "gen": 0, "step": 2, "program": "a31f09e2c4d1",
     "policy": "halt", "loss": Infinity, ...}

Writes are one ``write()`` of one line on a file opened in append mode
under a lock — atomic enough for many threads in one process; cross-process
writers use DISTINCT files (one per (host, rank, generation), see
``observe.Sink``) that the aggregator merges by timestamp, so there is no
shared-file interleaving to get wrong.

Schema contract (docs/OBSERVABILITY.md): ``ts`` (unix seconds), ``event``
(dot-separated kind), the stamp fields above, then free-form JSON fields.
``dur_s`` marks a span (emitted when an ``observe.trace`` span ends).
"""

from __future__ import annotations

import json
import os
import socket
import threading
import time
from typing import Iterable, List, Optional

__all__ = ["EventLog", "read_events", "merge_events", "host_name"]


def host_name() -> str:
    try:
        return socket.gethostname() or "localhost"
    except OSError:
        return "localhost"


def _env_int(name: str, default: int = 0) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


class EventLog:
    """One append-only JSONL event stream.

    ``host``/``rank``/``gen`` default from the standard pod env
    (``PADDLE_TRAINER_ID`` / ``PADDLE_ELASTIC_GENERATION``) read at
    construction; ``step``/``program`` are read per-event from the
    process-wide context (``observe.note_step`` / ``note_program``) so the
    executor's hot path stamps events without threading arguments through
    every subsystem."""

    def __init__(self, path: str, *, host: Optional[str] = None,
                 rank: Optional[int] = None, gen: Optional[int] = None,
                 source: Optional[str] = None):
        self.path = os.path.abspath(path)
        self.host = host if host is not None else host_name()
        self.rank = rank if rank is not None \
            else _env_int("PADDLE_TRAINER_ID")
        self.gen = gen if gen is not None \
            else _env_int("PADDLE_ELASTIC_GENERATION")
        self.source = source
        self._lock = threading.Lock()
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)

    def emit(self, event: str, **fields) -> dict:
        """Append one stamped record; returns it.  Never raises — losing a
        telemetry line must not fail the run it describes."""
        from . import current_mesh, current_program, current_step

        rec = {"ts": time.time(), "event": event, "host": self.host,
               "pid": os.getpid(), "rank": self.rank, "gen": self.gen,
               "step": current_step(), "program": current_program()}
        mesh = current_mesh()
        if mesh is not None:
            # topology stamp (dp4xtp2) — only present on sharded runs, so
            # single-device streams keep their exact record shape
            rec["mesh"] = mesh
        try:
            from . import trace as _trace

            sp = _trace.current()
            if sp is not None and sp.logged:
                # trace stamp: any record emitted inside an open span
                # that is itself in this log (guardian trips, cache
                # probes, slo breaches) joins the span tree.  Span
                # records override via `fields` below.
                rec["trace_id"] = sp.trace_id
                rec["span_id"] = sp.span_id
        except Exception:
            pass
        if self.source:
            rec["source"] = self.source
        rec.update(fields)
        try:
            line = json.dumps(rec, default=repr) + "\n"
            with self._lock, open(self.path, "a") as f:
                f.write(line)
        except (OSError, ValueError):
            pass
        return rec


def read_events(path: str) -> List[dict]:
    """Parse one JSONL event file, skipping torn/corrupt lines."""
    out = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except ValueError:
                    continue
    except OSError:
        pass
    return out


def merge_events(paths: Iterable[str]) -> List[dict]:
    """All records from ``paths`` in one wall-clock-ordered stream."""
    recs = []
    for p in paths:
        recs.extend(read_events(p))
    recs.sort(key=lambda r: r.get("ts", 0))
    return recs
