"""On-disk program artifact store: the persistent half of the compile cache.

Layout (everything under one root, shareable between processes on a host —
or between hosts only when platform/toolchain match, see
docs/PERFORMANCE.md)::

    <root>/entries/<fingerprint>/
        manifest.json   fingerprint, jit config summary, compile_seconds,
                        program_sha256, created timestamp
        program.bin     the serialized Program (Program.serialize_to_string)
        _SUCCESS        commit marker, written LAST — the same durability
                        convention as the checkpoint subsystem
                        (trainer.save_checkpoint / multihost serials)
    <root>/serving/     bucket manifests written by ServingEngine.warmup
    <root>/tmp/         staging dirs for atomic commits

jax's persistent compilation cache (the backend XLA executables) is NOT
under the root: :func:`backend_cache_dir` alone decides where it lives —
``JAX_COMPILATION_CACHE_DIR`` where the operator set it (jax's own
setting, left alone), else one fixed path inside the checkout.  The
directory is part of what makes an entry hit, so it never follows a pid,
a timestamp or a temp dir.

Durability rules, mirrored from the checkpoint subsystem:

 - commit is staged-dir -> rename -> ``_SUCCESS`` last: a crash mid-write
   leaves an unmarked dir that loads ignore and ``prune`` deletes;
 - loads are corruption-TOLERANT: any failure (missing marker, unreadable
   manifest, payload checksum mismatch, or an armed
   ``PADDLE_FAULT_CACHE_CORRUPT`` injection) quarantines the entry and
   returns a miss — a broken cache must never fail the run, only slow it;
 - a size budget (``PADDLE_COMPILE_CACHE_BUDGET_MB``) is enforced by LRU
   eviction over entries, keyed on last-use mtime (hits ``touch`` their
   entry); the backend directory is jax's to bound
   (``JAX_COMPILATION_CACHE_MAX_SIZE``).

Telemetry flows through ``fluid.profiler.record_counter`` (always-on):
``compile_cache.hit`` / ``.miss`` / ``.put`` / ``.evict`` /
``.corrupt_fallback`` / ``.error`` and the accumulated
``compile_cache.compile_seconds``.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import time
from typing import Dict, List, Optional

__all__ = ["CompileCacheStore", "SUCCESS_MARK", "backend_cache_dir",
           "checkout_root", "place_backend_cache"]

SUCCESS_MARK = "_SUCCESS"
ENTRIES_DIR = "entries"
SERVING_DIR = "serving"
TMP_DIR = "tmp"
MANIFEST_FILE = "manifest.json"
PROGRAM_FILE = "program.bin"


def _counter(name: str, inc=1, value=None) -> None:
    from ..fluid import profiler as _prof

    _prof.record_counter(f"compile_cache.{name}", inc=inc, value=value)


_BACKEND_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def checkout_root() -> str:
    """A fixed store root inside the checkout for tools that want the
    cache on without being told where (``PADDLE_COMPILE_CACHE_DIR``
    unset): the same path on every run, so the second run is warm."""
    return os.path.join(_CHECKOUT, ".cache", "paddle")


def _backend_env() -> str:
    return os.environ.get(_BACKEND_ENV, "").strip()


def backend_cache_dir() -> str:
    """Where jax's persistent compilation cache lives — the one function
    that decides it."""
    return _backend_env() or os.path.join(_CHECKOUT, ".cache", "jax")


def place_backend_cache(on: bool) -> None:
    """Switch jax's persistent cache on at :func:`backend_cache_dir`, or
    back off.  Where ``JAX_COMPILATION_CACHE_DIR`` is set jax has placed
    the cache itself and its setting is not touched either way."""
    import jax

    if not _backend_env():
        jax.config.update("jax_compilation_cache_dir",
                          backend_cache_dir() if on else None)
    if on:
        # test-scale programs compile in <1s; without this the backend
        # would skip persisting exactly the entries we want warm
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def _tree_bytes(path: str) -> int:
    total = 0
    for dirpath, _dirs, files in os.walk(path):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(dirpath, f))
            except OSError:
                pass
    return total


class CompileCacheStore:
    """One cache root; safe for concurrent use by many processes (atomic
    rename commits; last-writer-wins on identical fingerprints)."""

    def __init__(self, root: str, budget_mb: Optional[float] = None):
        self.root = os.path.abspath(root)
        self.budget_bytes = (None if not budget_mb
                             else int(float(budget_mb) * (1 << 20)))
        for d in (ENTRIES_DIR, SERVING_DIR, TMP_DIR):
            os.makedirs(os.path.join(self.root, d), exist_ok=True)

    # -- paths --
    def entry_dir(self, fp: str) -> str:
        return os.path.join(self.root, ENTRIES_DIR, str(fp))

    def serving_manifest_path(self, key: str) -> str:
        return os.path.join(self.root, SERVING_DIR, f"{key}.json")

    # -- read path --
    def complete(self, fp: str) -> bool:
        return os.path.exists(os.path.join(self.entry_dir(fp), SUCCESS_MARK))

    def get(self, fp: str, count: bool = True) -> Optional[dict]:
        """Manifest of a complete, uncorrupted entry, else None (miss).

        Any load failure — including the deterministic
        ``PADDLE_FAULT_CACHE_CORRUPT`` injection — quarantines the entry
        and reports a miss: the caller compiles fresh and re-``put``s.
        """
        d = self.entry_dir(fp)
        marker = os.path.join(d, SUCCESS_MARK)
        if not os.path.exists(marker):
            if count:
                _counter("miss")
            return None
        from ..fluid import fault as _fault

        try:
            if _fault.cache_corrupt():
                raise IOError("injected cache corruption "
                              "(PADDLE_FAULT_CACHE_CORRUPT)")
            with open(os.path.join(d, MANIFEST_FILE)) as f:
                manifest = json.load(f)
            with open(os.path.join(d, PROGRAM_FILE), "rb") as f:
                blob = f.read()
            if hashlib.sha256(blob).hexdigest() \
                    != manifest.get("program_sha256"):
                raise IOError("payload checksum mismatch")
        except Exception:
            # corrupt-tolerant fallback: drop the entry, report a miss —
            # the run recompiles and rewrites it; never raise
            shutil.rmtree(d, ignore_errors=True)
            if count:
                _counter("corrupt_fallback")
                _counter("miss")
            return None
        if count:
            _counter("hit")
        try:
            os.utime(marker)  # LRU recency
        except OSError:
            pass
        return manifest

    def program_blob(self, fp: str) -> Optional[bytes]:
        """Raw serialized Program of a complete entry (cache_ctl / debug)."""
        try:
            with open(os.path.join(self.entry_dir(fp), PROGRAM_FILE),
                      "rb") as f:
                return f.read()
        except OSError:
            return None

    # -- write path --
    def put(self, fp: str, program_blob: bytes,
            meta: Optional[dict] = None) -> bool:
        """Commit one entry atomically; True when this call created it.
        Existing complete entries are only touched (freshened for LRU)."""
        d = self.entry_dir(fp)
        if self.complete(fp):
            try:
                os.utime(os.path.join(d, SUCCESS_MARK))
            except OSError:
                pass
            return False
        manifest = dict(meta or {})
        manifest.update({
            "fingerprint": str(fp),
            "program_sha256": hashlib.sha256(program_blob).hexdigest(),
            "program_bytes": len(program_blob),
            "created": time.time(),
        })
        tmp = os.path.join(self.root, TMP_DIR,
                           f"{fp}.{os.getpid()}.{time.monotonic_ns()}")
        from ..fluid import fault as _fault
        from ..fluid.retry import retry_io

        try:
            os.makedirs(tmp)

            # staged writes + _SUCCESS get bounded transient retry (keyed
            # on the DESTINATION dir — the tmp name is unique per call);
            # the rename race below stays unretried: contention is a
            # protocol outcome, not a storage blip
            def _stage():
                _fault.io_error(os.path.join(d, PROGRAM_FILE), "write")
                with open(os.path.join(tmp, PROGRAM_FILE), "wb") as f:
                    f.write(program_blob)
                with open(os.path.join(tmp, MANIFEST_FILE), "w") as f:
                    json.dump(manifest, f)

            retry_io(_stage, what="cache.stage")
            try:
                os.rename(tmp, d)
            except OSError:
                # racer committed first, or a stale partial dir squats the
                # name: clear an UNMARKED corpse once, else concede
                if self.complete(fp):
                    shutil.rmtree(tmp, ignore_errors=True)
                    return False
                shutil.rmtree(d, ignore_errors=True)
                os.rename(tmp, d)

            # _SUCCESS last: the commit point (checkpoint convention)
            def _commit():
                _fault.io_error(os.path.join(d, SUCCESS_MARK), "write")
                with open(os.path.join(d, SUCCESS_MARK), "w") as f:
                    f.write(str(fp))

            retry_io(_commit, what="cache.success")
        except OSError:
            shutil.rmtree(tmp, ignore_errors=True)
            _counter("error")
            return False
        _counter("put")
        self.evict_to_budget(protect=fp)
        return True

    # -- eviction / maintenance --
    def _lru_items(self) -> List[tuple]:
        """(mtime, path, bytes) for every entry dir."""
        items = []
        ed = os.path.join(self.root, ENTRIES_DIR)
        for name in os.listdir(ed):
            d = os.path.join(ed, name)
            marker = os.path.join(d, SUCCESS_MARK)
            try:
                mtime = os.path.getmtime(
                    marker if os.path.exists(marker) else d)
            except OSError:
                continue
            items.append((mtime, d, _tree_bytes(d)))
        items.sort()
        return items

    def evict_to_budget(self, budget_bytes: Optional[int] = None,
                        protect: Optional[str] = None) -> int:
        """LRU-evict until total bytes fit the budget; returns evictions.
        ``protect`` pins one fingerprint (the entry just written) so a
        budget smaller than a single entry cannot evict its own write."""
        budget = self.budget_bytes if budget_bytes is None else budget_bytes
        if budget is None:
            return 0
        items = self._lru_items()
        total = sum(sz for _, _, sz in items)
        evicted = 0
        for _mtime, path, sz in items:
            if total <= budget:
                break
            if protect and os.path.basename(path) == str(protect):
                continue
            shutil.rmtree(path, ignore_errors=True)
            total -= sz
            evicted += 1
            _counter("evict")
        return evicted

    def entries(self) -> List[dict]:
        """One summary dict per entry (cache_ctl ls/verify)."""
        out = []
        ed = os.path.join(self.root, ENTRIES_DIR)
        for name in sorted(os.listdir(ed)):
            d = os.path.join(ed, name)
            rec = {"fingerprint": name, "dir": d,
                   "complete": os.path.exists(os.path.join(d, SUCCESS_MARK)),
                   "bytes": _tree_bytes(d)}
            try:
                with open(os.path.join(d, MANIFEST_FILE)) as f:
                    rec["manifest"] = json.load(f)
            except (OSError, ValueError):
                rec["manifest"] = None
            out.append(rec)
        return out

    def verify_entry(self, fp: str) -> str:
        """'ok' | 'incomplete' | 'corrupt:<why>' — read-only integrity
        check (no quarantine, no counters; ``get`` does those)."""
        d = self.entry_dir(fp)
        if not os.path.exists(os.path.join(d, SUCCESS_MARK)):
            return "incomplete"
        try:
            with open(os.path.join(d, MANIFEST_FILE)) as f:
                manifest = json.load(f)
        except (OSError, ValueError) as exc:
            return f"corrupt:manifest ({exc})"
        try:
            with open(os.path.join(d, PROGRAM_FILE), "rb") as f:
                blob = f.read()
        except OSError as exc:
            return f"corrupt:payload ({exc})"
        if hashlib.sha256(blob).hexdigest() != manifest.get("program_sha256"):
            return "corrupt:checksum mismatch"
        return "ok"

    def prune(self, budget_bytes: Optional[int] = None) -> dict:
        """Drop incomplete/corrupt entries and stale tmp dirs, then evict
        to budget.  Returns a report dict."""
        removed = []
        for rec in self.entries():
            status = self.verify_entry(rec["fingerprint"])
            if status != "ok":
                shutil.rmtree(rec["dir"], ignore_errors=True)
                removed.append({"fingerprint": rec["fingerprint"],
                                "status": status})
        tmp_root = os.path.join(self.root, TMP_DIR)
        for name in os.listdir(tmp_root):
            shutil.rmtree(os.path.join(tmp_root, name), ignore_errors=True)
        evicted = self.evict_to_budget(budget_bytes)
        return {"removed": removed, "evicted": evicted,
                "stats": self.stats()}

    def clear(self) -> None:
        for d in (ENTRIES_DIR, SERVING_DIR, TMP_DIR):
            p = os.path.join(self.root, d)
            shutil.rmtree(p, ignore_errors=True)
            os.makedirs(p, exist_ok=True)

    def stats(self) -> Dict[str, object]:
        recs = self.entries()
        return {
            "root": self.root,
            "budget_mb": (None if self.budget_bytes is None
                          else round(self.budget_bytes / (1 << 20), 3)),
            "entries": len(recs),
            "complete": sum(1 for r in recs if r["complete"]),
            "entry_bytes": sum(r["bytes"] for r in recs),
            "xla_dir": backend_cache_dir(),
            "xla_bytes": _tree_bytes(backend_cache_dir()),
            "serving_manifests": len(os.listdir(
                os.path.join(self.root, SERVING_DIR))),
        }
