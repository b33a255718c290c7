"""Deterministic fault injection for robustness testing.

The reference stack's fault tolerance was *testable* because its Go master
and pserver shipped with chaos hooks (go/master timeout requeue, pserver
checkpoint-on-notify); this module is the TPU build's equivalent: a single
place that can deterministically reproduce the failures a production pod
actually sees — preempted workers, checkpoints killed mid-write, slow/wedged
storage, silent NaNs, stalled collectives — so the recovery paths in
``trainer``/``multihost``/``parallel.elastic`` are exercised by fast tests
instead of discovered in production.

Faults are armed either programmatically (``install(FaultPlan(...))``) or
via environment flags, which is how the elastic supervisor injects them into
worker processes:

    PADDLE_FAULT_KILL_STEP=N      die at the step-N boundary (os._exit 137,
                                  a SIGKILL stand-in: no atexit, no flush)
    PADDLE_FAULT_RANK=r           restrict any armed fault to rank r
                                  (default: every rank; rank source is
                                  PADDLE_TRAINER_ID)
    PADDLE_FAULT_CKPT_CRASH=before|after
                                  crash during a checkpoint save, just
                                  before / just after the _SUCCESS marker
    PADDLE_FAULT_CKPT_POISON_SERIAL=n
                                  NaN-poison every float weight file of
                                  checkpoint serial n at save time —
                                  committed WITH a valid _SUCCESS marker,
                                  unlike the pre-commit corruption hooks:
                                  the checkpoint looks perfectly healthy
                                  to the watcher/loader and only the
                                  serving canary's output-sanity sentinel
                                  can catch it (the deterministic
                                  forced-bad-checkpoint oracle for the
                                  hot-swap auto-rollback path)
    PADDLE_FAULT_IO_DELAY_MS=t    sleep t ms inside every checkpoint write
    PADDLE_FAULT_NAN_VAR=name     overwrite var `name` with NaN once
    PADDLE_FAULT_NAN_STEP=N       ...at step N (default 0)
    PADDLE_FAULT_GRAD_INF_STEP=N  poison step N's backward seed so every
                                  gradient goes Inf IN-GRAPH (the guardian
                                  sentinel and fp16 loss scaler's overflow
                                  oracle; flows through the real grad ops,
                                  so a replay bundle reproduces it)
    PADDLE_FAULT_GRAD_INF_VALUE=v seed multiplier (default inf; a large
                                  finite value like 1e30 models a partial
                                  fp16 overflow instead)
    PADDLE_FAULT_LOSS_SPIKE_STEP=N
                                  multiply the observed loss at step N by
                                  PADDLE_FAULT_LOSS_SPIKE_FACTOR (default
                                  1e4) — the corrupt-batch oracle for the
                                  guardian's spike detector
    PADDLE_FAULT_BARRIER_STALL=s  sleep s seconds before the next collective
                                  barrier (one-shot), simulating a wedged
                                  host that trips the supervisor's timeout
    PADDLE_FAULT_HOST_LOSS_RANK=r
                                  permanent host loss: rank r exits hard at
                                  the PADDLE_FAULT_HOST_LOSS_AT_STEP step
                                  boundary AND drops a host_lost marker in
                                  the supervisor's heartbeat dir, so the
                                  survivor census sees a smaller fleet —
                                  unlike kill-at-step, the replacement
                                  generation cannot be the same size; the
                                  deterministic oracle for the supervisor's
                                  mesh-ladder downgrade (PADDLE_TPU_MESH_
                                  LADDER).  Keyed on its own rank knob like
                                  the straggler, so it composes with other
                                  rank-scoped faults in one scenario.
    PADDLE_FAULT_REPLICA_KILL_AFTER=n
                                  serving-fleet replica death: the fleet
                                  consults :func:`replica_kill` after every
                                  completed request; the call whose running
                                  total reaches n returns True ONCE, and
                                  the fleet kills the replica that served
                                  that request (resident futures fail, the
                                  pool census re-spawns it on surviving
                                  devices) — the deterministic oracle for
                                  the router's zero-shed failover and
                                  cache-hit re-warm path.  Never a process
                                  exit: a replica dies, the fleet survives.
    PADDLE_FAULT_SERVE_DELAY_MS=t sleep t ms per serving-engine request
                                  (slow-model / GC-pause simulation on the
                                  inference path)
    PADDLE_FAULT_SERVE_FAIL_EVERY=N
                                  fail every Nth serving request with an
                                  InjectedFault delivered on that request's
                                  future (the engine must isolate it: the
                                  rest of the batch still completes)
    PADDLE_FAULT_DECODE_STALL_MS=t
                                  stall every continuous-batching decode
                                  TICK t ms (DecodeEngine worker loop) —
                                  inflates inter-token latency on every
                                  in-flight stream at once, the
                                  deterministic oracle for the SLO
                                  watchdog's serving.intertoken_s breach
    PADDLE_FAULT_CACHE_CORRUPT=1  treat every persistent compile-cache
                                  entry load as corrupt (the deterministic
                                  oracle for the cache's fallback path:
                                  the run must recompile fresh and still
                                  succeed — see paddle_tpu.compile_cache)
    PADDLE_FAULT_DATA_STALL_MS=t  stall the input pipeline t ms per pulled
                                  sample (slow reader); with
                                  PADDLE_FAULT_DATA_STALL_AT=N the stall
                                  fires ONCE, at source-cursor N — the
                                  SLO-breach oracle for train.data_wait_s
    PADDLE_FAULT_SHARD_CORRUPT=1  truncate the next data_state blob write
                                  (one-shot): the resumed run must detect
                                  the corrupt cursor and fall back to the
                                  previous complete serial
    PADDLE_FAULT_STRAGGLER_RANK=r
                                  deterministic straggler oracle: rank r
                                  sleeps PADDLE_FAULT_STRAGGLER_MS ms per
                                  training step at the step boundary —
                                  INSIDE the executor window span, so the
                                  rank's per-step time inflates exactly
                                  like a thermally-throttled / failing
                                  chip's would and the cross-rank skew
                                  detector (observe.fleet.rank_skew) must
                                  flag it.  Keyed on its own rank knob, NOT
                                  PADDLE_FAULT_RANK: one scenario may kill
                                  rank 0 while rank 1 straggles.
    PADDLE_FAULT_MEM_PRESSURE=mb  synthesize a memory leak: starting at the
                                  PADDLE_FAULT_MEM_PRESSURE_AT-th (default
                                  8th) live-buffer-ledger observation, add
                                  mb MB of phantom live bytes, DOUBLING per
                                  observation — the deterministic oracle
                                  for the memory.live_bytes SLO breach and
                                  the PADDLE_MEM_BUDGET_MB over-budget
                                  event (see observe.memory)
    PADDLE_FAULT_KV_PAGE_LEAK=n   paged-KV leak oracle: the serving page
                                  pool's allocator SKIPS its next n page
                                  frees (one-shot), so retired requests
                                  leave pages marked live forever —
                                  kvpool.pages_free never returns to its
                                  initial level after drain, the
                                  kvpool.hbm_bytes gauge and live-buffer
                                  ledger climb, and the leak is
                                  deterministic enough for the memcheck /
                                  watchdog tests to assert on exact page
                                  counts (see serving.kvpool.PagePool)
    PADDLE_FAULT_SPEC_DRAFT_POISON=n  speculative-draft poison oracle:
                                  from engine tick n on, every token the
                                  draft model proposes is replaced with
                                  deterministic garbage, so draft
                                  acceptance collapses to ~1/vocab — the
                                  specdec adaptive controller must fire
                                  its specdec.fallback event while the
                                  emitted stream stays bitwise correct
                                  (every accepted/correction token is a
                                  target argmax regardless of what the
                                  draft proposed; see serving/specdec)
    PADDLE_FAULT_IO_ERROR_RATE=f  transient-storage oracle: the fraction
                                  f of (path, op) keys whose FIRST
                                  read/write attempt raises OSError —
                                  seeded (PADDLE_FAULT_IO_ERROR_SEED) and
                                  keyed on the path's tail, so the SAME
                                  files fail on every run and the retry
                                  attempt for a failed key always
                                  succeeds.  Transient by construction:
                                  bounded retry (fluid.retry.retry_io)
                                  must recover, while an unretried call
                                  site still sees a hard failure — and
                                  content corruption (ValueError) never
                                  goes through this hook, so the
                                  serial-condemnation fallback stays
                                  distinct from the transient path
    PADDLE_FAULT_MODE=exit|raise  crash flavor: hard process exit (default)
                                  or an InjectedFault raise (in-process
                                  tests of the recovery path)

Hook points (each a no-op costing one attribute read when nothing is
armed): ``Executor.run``/``run_steps`` call :func:`on_step` at the training
step boundary and :func:`corrupt_state` on the step's outputs;
``trainer.save_checkpoint``/``multihost.save_sharded_serial`` call
:func:`ckpt_crash_point` around their _SUCCESS writes and :func:`io_delay`
in their write loops; ``multihost.barrier`` calls :func:`barrier_stall`;
``serving.ServingEngine`` calls :func:`serving_request` once per admitted
request at batch formation.

Determinism contract: a fault keyed to step N fires exactly at step N of
the *caller-provided* step index when one is given (the elastic worker
passes its global resume-aware step, so a restarted worker never re-fires a
kill it already survived), else of an internal per-process counter.
"""

from __future__ import annotations

import os
import time
from typing import Optional

__all__ = [
    "FaultPlan", "InjectedFault", "install", "clear", "active",
    "on_step", "corrupt_state", "ckpt_crash_point", "ckpt_poison",
    "io_delay", "io_error",
    "barrier_stall", "serving_request", "decode_stall", "replica_kill",
    "kv_page_leak", "spec_draft_poison", "sentinel_injection",
    "sentinel_injection_window", "cache_corrupt", "data_stall",
    "shard_corrupt", "mem_pressure_bytes", "straggler_delay",
    "current_step", "KILL_EXIT_CODE",
]

#: exit code of an injected kill — 128+9, what a real SIGKILL reports
KILL_EXIT_CODE = 137


class InjectedFault(BaseException):
    """Raise-mode crash.  A BaseException on purpose: recovery code that
    catches ``Exception`` must treat an injected crash like a real process
    death, not swallow it."""


class FaultPlan:
    """One armed fault scenario.  All fields optional; ``None``/0 disarms
    the corresponding fault."""

    def __init__(self, kill_step: Optional[int] = None,
                 ckpt_crash: Optional[str] = None,
                 ckpt_poison_serial: Optional[int] = None,
                 io_delay_ms: float = 0.0,
                 nan_var: Optional[str] = None, nan_step: int = 0,
                 grad_inf_step: Optional[int] = None,
                 grad_inf_value: float = float("inf"),
                 loss_spike_step: Optional[int] = None,
                 loss_spike_factor: float = 1e4,
                 barrier_stall_s: float = 0.0,
                 serve_delay_ms: float = 0.0, serve_fail_every: int = 0,
                 decode_stall_ms: float = 0.0,
                 kv_page_leak: Optional[int] = None,
                 spec_draft_poison: Optional[int] = None,
                 replica_kill_after: Optional[int] = None,
                 cache_corrupt: bool = False,
                 data_stall_ms: float = 0.0,
                 data_stall_at: Optional[int] = None,
                 shard_corrupt: bool = False,
                 mem_pressure_mb: float = 0.0,
                 mem_pressure_at: int = 8,
                 straggler_rank: Optional[int] = None,
                 straggler_ms: float = 0.0,
                 host_loss_rank: Optional[int] = None,
                 host_loss_at_step: int = 0,
                 io_error_rate: float = 0.0, io_error_seed: int = 0,
                 rank: Optional[int] = None, mode: str = "exit"):
        if ckpt_crash not in (None, "before", "after"):
            raise ValueError(
                f"ckpt_crash must be 'before' or 'after' (the _SUCCESS "
                f"marker), got {ckpt_crash!r}")
        if mode not in ("exit", "raise"):
            raise ValueError(f"mode must be 'exit' or 'raise', got {mode!r}")
        self.kill_step = None if kill_step is None else int(kill_step)
        self.ckpt_crash = ckpt_crash
        self.ckpt_poison_serial = None if ckpt_poison_serial is None \
            else int(ckpt_poison_serial)
        self.io_delay_ms = float(io_delay_ms)
        self.nan_var = nan_var
        self.nan_step = int(nan_step)
        self.grad_inf_step = None if grad_inf_step is None else int(grad_inf_step)
        self.grad_inf_value = float(grad_inf_value)
        self.loss_spike_step = None if loss_spike_step is None \
            else int(loss_spike_step)
        self.loss_spike_factor = float(loss_spike_factor)
        self.barrier_stall_s = float(barrier_stall_s)
        self.serve_delay_ms = float(serve_delay_ms)
        self.serve_fail_every = int(serve_fail_every)
        self.decode_stall_ms = float(decode_stall_ms)
        self.kv_page_leak = None if kv_page_leak is None \
            else int(kv_page_leak)
        self.spec_draft_poison = None if spec_draft_poison is None \
            else int(spec_draft_poison)
        self.replica_kill_after = None if replica_kill_after is None \
            else int(replica_kill_after)
        self.cache_corrupt = bool(cache_corrupt)
        self.data_stall_ms = float(data_stall_ms)
        self.data_stall_at = None if data_stall_at is None \
            else int(data_stall_at)
        self.shard_corrupt = bool(shard_corrupt)
        self.mem_pressure_mb = float(mem_pressure_mb)
        self.mem_pressure_at = int(mem_pressure_at)
        self.straggler_rank = None if straggler_rank is None \
            else int(straggler_rank)
        self.straggler_ms = float(straggler_ms)
        self.host_loss_rank = None if host_loss_rank is None \
            else int(host_loss_rank)
        self.host_loss_at_step = int(host_loss_at_step)
        self.io_error_rate = float(io_error_rate)
        self.io_error_seed = int(io_error_seed)
        self.rank = None if rank is None else int(rank)
        self.mode = mode
        # one-shot disarm state
        self._io_error_attempts: dict = {}
        self._replica_kill_fired = False
        self._nan_fired = False
        self._stall_fired = False
        self._serve_count = 0
        self._data_stall_fired = False
        self._shard_corrupt_fired = False
        self._mem_pressure_calls = 0
        self._kv_leaks_left = 0 if self.kv_page_leak is None \
            else self.kv_page_leak

    @classmethod
    def from_env(cls, env=None) -> Optional["FaultPlan"]:
        """Parse the PADDLE_FAULT_* contract; None when nothing is armed.

        Every knob is read through the envcontract registry's typed
        parser (ISSUE 18 satellite): the declaration in
        ``fluid.envcontract`` — name, type, default — is the single
        source of truth the chaos schedule auto-discovers from and
        ``repo_lint`` enforces, so an undeclared fault knob cannot be
        consumed here.  ``env`` may be any mapping (the supervisor's
        per-worker dicts in tests); the default is the live process
        environment."""
        env = os.environ if env is None else env
        if not any(k.startswith("PADDLE_FAULT_") and (v or "").strip()
                   for k, v in env.items()):
            return None
        from . import envcontract as _ec

        def val(name):
            knob = _ec.REGISTRY[name]  # KeyError = undeclared: on purpose
            return knob.parse(env.get(name))

        return cls(
            kill_step=val("PADDLE_FAULT_KILL_STEP"),
            ckpt_crash=val("PADDLE_FAULT_CKPT_CRASH"),
            ckpt_poison_serial=val("PADDLE_FAULT_CKPT_POISON_SERIAL"),
            io_delay_ms=val("PADDLE_FAULT_IO_DELAY_MS"),
            nan_var=val("PADDLE_FAULT_NAN_VAR"),
            nan_step=val("PADDLE_FAULT_NAN_STEP"),
            grad_inf_step=val("PADDLE_FAULT_GRAD_INF_STEP"),
            grad_inf_value=val("PADDLE_FAULT_GRAD_INF_VALUE"),
            loss_spike_step=val("PADDLE_FAULT_LOSS_SPIKE_STEP"),
            loss_spike_factor=val("PADDLE_FAULT_LOSS_SPIKE_FACTOR"),
            barrier_stall_s=val("PADDLE_FAULT_BARRIER_STALL"),
            serve_delay_ms=val("PADDLE_FAULT_SERVE_DELAY_MS"),
            serve_fail_every=val("PADDLE_FAULT_SERVE_FAIL_EVERY"),
            decode_stall_ms=val("PADDLE_FAULT_DECODE_STALL_MS"),
            kv_page_leak=val("PADDLE_FAULT_KV_PAGE_LEAK"),
            spec_draft_poison=val("PADDLE_FAULT_SPEC_DRAFT_POISON"),
            replica_kill_after=val("PADDLE_FAULT_REPLICA_KILL_AFTER"),
            cache_corrupt=val("PADDLE_FAULT_CACHE_CORRUPT"),
            data_stall_ms=val("PADDLE_FAULT_DATA_STALL_MS"),
            data_stall_at=val("PADDLE_FAULT_DATA_STALL_AT"),
            shard_corrupt=val("PADDLE_FAULT_SHARD_CORRUPT"),
            mem_pressure_mb=val("PADDLE_FAULT_MEM_PRESSURE"),
            mem_pressure_at=val("PADDLE_FAULT_MEM_PRESSURE_AT"),
            straggler_rank=val("PADDLE_FAULT_STRAGGLER_RANK"),
            straggler_ms=val("PADDLE_FAULT_STRAGGLER_MS"),
            host_loss_rank=val("PADDLE_FAULT_HOST_LOSS_RANK"),
            host_loss_at_step=val("PADDLE_FAULT_HOST_LOSS_AT_STEP"),
            io_error_rate=val("PADDLE_FAULT_IO_ERROR_RATE"),
            io_error_seed=val("PADDLE_FAULT_IO_ERROR_SEED"),
            rank=val("PADDLE_FAULT_RANK"),
            mode=val("PADDLE_FAULT_MODE"),
        )

    # -- firing --
    def _applies_to_this_rank(self) -> bool:
        if self.rank is None:
            return True
        return self.rank == int(os.environ.get("PADDLE_TRAINER_ID", "0"))

    def _crash(self, what: str):
        if self.mode == "raise":
            raise InjectedFault(what)
        from .log import LOG

        LOG(f"fault: injected crash ({what}) — exiting {KILL_EXIT_CODE}")
        os._exit(KILL_EXIT_CODE)


# module state: the armed plan (None = nothing armed; _UNSET = env not yet
# consulted, so subprocesses that set PADDLE_FAULT_* before first use are
# honored without an import-order dependency) and the step counter
_UNSET = object()
_plan = _UNSET
_step = 0


def install(plan: Optional[FaultPlan]) -> None:
    """Arm a plan programmatically (overrides the env)."""
    global _plan, _step
    _plan = plan
    _step = 0


def clear() -> None:
    """Disarm everything, including any env-derived plan."""
    install(None)


def active() -> Optional[FaultPlan]:
    global _plan
    if _plan is _UNSET:
        _plan = FaultPlan.from_env()
    return _plan


def current_step() -> int:
    return _step


def _host_loss_fire(plan: FaultPlan, lo: int, hi: int) -> None:
    """Permanent-host-loss oracle: when the armed rank reaches its step,
    drop a ``host_lost_g<gen>_r<rank>`` marker into the supervisor's
    heartbeat dir (the survivor census input — this "host" never
    rejoins) and crash hard.  Keyed on ``host_loss_rank`` alone, like the
    straggler, so it composes with PADDLE_FAULT_RANK-scoped faults."""
    if plan.host_loss_rank is None:
        return
    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0") or 0)
    if plan.host_loss_rank != rank:
        return
    if not lo <= plan.host_loss_at_step < hi:
        return
    hb_dir = os.environ.get("PADDLE_ELASTIC_HB_DIR")
    if hb_dir:
        gen = os.environ.get("PADDLE_ELASTIC_GENERATION", "0") or "0"
        marker = os.path.join(hb_dir, f"host_lost_g{gen}_r{rank}")

        def _write_marker():
            os.makedirs(hb_dir, exist_ok=True)
            with open(marker, "w") as f:
                f.write(str(time.time()))

        try:
            from .retry import retry_io

            # the census marker is the survivor count's only input — a
            # transient blip here must not silently shrink the record
            retry_io(_write_marker, what="census.host_lost")
        except OSError:
            pass  # the crash below still fires; census just sees a kill
    plan._crash(
        f"host loss (rank {rank}) at step {plan.host_loss_at_step}")


def on_step(step: Optional[int] = None) -> int:
    """Training-step boundary, called BEFORE the step executes.  ``step``
    pins the index explicitly (resume-aware callers); default is an
    internal monotonic per-process counter.  Fires kill-at-step-N and
    the permanent host-loss fault."""
    global _step
    if step is not None:
        _step = int(step)
    plan = active()
    if plan is not None:
        if plan.kill_step is not None and _step == plan.kill_step \
                and plan._applies_to_this_rank():
            plan._crash(f"kill at step {_step}")
        _host_loss_fire(plan, _step, _step + 1)
    fired = _step
    if step is None:
        _step += 1
    else:
        _step = int(step) + 1
    return fired


def advance(n: int) -> None:
    """Bulk step advance for fused multi-step dispatches (run_steps): a
    kill (or host loss) armed anywhere inside the window fires before
    the dispatch — the finest granularity a single XLA dispatch
    allows."""
    global _step
    plan = active()
    if plan is not None:
        if plan.kill_step is not None \
                and _step <= plan.kill_step < _step + n \
                and plan._applies_to_this_rank():
            plan._crash(f"kill inside step window [{_step}, {_step + n})")
        _host_loss_fire(plan, _step, _step + n)
    _step += n


def corrupt_state(named_vals: dict) -> dict:
    """NaN-poison the armed var once its step arrives (one-shot).  Called
    with a step's new state; returns it (possibly rewritten).  The injected
    NaN then flows into the scope exactly like a real numerical blow-up, so
    check_nan_inf / supervisor NaN policies see the genuine article."""
    plan = active()
    if plan is None or plan.nan_var is None or plan._nan_fired \
            or _step <= plan.nan_step or not plan._applies_to_this_rank():
        return named_vals
    if plan.nan_var in named_vals:
        import numpy as np

        val = named_vals[plan.nan_var]
        poisoned = np.asarray(val, dtype=np.result_type(val, np.float32))
        poisoned = np.full_like(poisoned, np.nan)
        named_vals = dict(named_vals)
        named_vals[plan.nan_var] = poisoned
        plan._nan_fired = True
    return named_vals


def sentinel_injection(step: int):
    """Per-step numerics-fault multipliers for the guardian's sentinel:
    ``(seed_mul, loss_mul)``, both 1.0 when nothing is armed for ``step``.

    ``seed_mul`` scales the backward seed IN-GRAPH (the @LOSS_SEED_MUL@
    entry the guarded executor step feeds into the tagged __loss_seed__
    op), so a grad-Inf injection flows through the real gradient ops and
    a dumped replay bundle reproduces it bit-for-bit.  ``loss_mul``
    scales the observed loss (the corrupt-batch spike oracle).  Keyed on
    exact step equality, so the injection is naturally one-shot per step
    and a resumed run that re-executes the step re-fires it — which is
    what a deterministic oracle should do."""
    plan = active()
    if plan is None or not plan._applies_to_this_rank():
        return 1.0, 1.0
    seed_mul = plan.grad_inf_value \
        if plan.grad_inf_step == step else 1.0
    loss_mul = plan.loss_spike_factor \
        if plan.loss_spike_step == step else 1.0
    return seed_mul, loss_mul


def sentinel_injection_window(start: int, n_steps: int):
    """Vectorized :func:`sentinel_injection` for a fused ``run_steps``
    window: ``(seed_mul, loss_mul)`` float32 arrays of shape ``(n_steps,)``
    covering absolute steps ``[start, start + n_steps)``.  The guarded scan
    consumes slice ``i`` at window step ``i``, so a grad-Inf armed at an
    absolute step inside the window fires at exactly that step of the
    scanned loop — same determinism contract as the per-step path."""
    import numpy as np

    seed = np.ones(n_steps, np.float32)
    loss = np.ones(n_steps, np.float32)
    plan = active()
    if plan is not None and plan._applies_to_this_rank():
        if plan.grad_inf_step is not None \
                and start <= plan.grad_inf_step < start + n_steps:
            seed[plan.grad_inf_step - start] = plan.grad_inf_value
        if plan.loss_spike_step is not None \
                and start <= plan.loss_spike_step < start + n_steps:
            loss[plan.loss_spike_step - start] = plan.loss_spike_factor
    return seed, loss


def ckpt_crash_point(where: str) -> None:
    """Checkpoint-save crash hook; ``where`` is 'before' or 'after' the
    _SUCCESS marker write."""
    plan = active()
    if plan is not None and plan.ckpt_crash == where \
            and plan._applies_to_this_rank():
        plan._crash(f"checkpoint crash {where} _SUCCESS")


def ckpt_poison(serial: int, dirname: str) -> bool:
    """Committed-but-bad checkpoint oracle: when ``ckpt_poison_serial``
    matches ``serial``, rewrite every float array file under ``dirname``
    as all-NaN IN PLACE, before the caller commits its _SUCCESS marker.
    Unlike :func:`ckpt_crash_point`, the serial ends up fully committed
    and structurally valid — the watcher/loader trusts it, only the
    serving canary's output-sanity sentinel can catch it (the
    deterministic trigger for hot-swap auto-rollback).  Walks the dir
    recursively so sharded serials (``shard_*/``) are poisoned too;
    integer arrays and unparseable files are left intact.  Returns True
    when it fired."""
    plan = active()
    if plan is None or plan.ckpt_poison_serial is None \
            or plan.ckpt_poison_serial != int(serial) \
            or not plan._applies_to_this_rank():
        return False
    import numpy as np

    fired = False
    for root, _dirs, files in os.walk(dirname):
        for fname in files:
            path = os.path.join(root, fname)
            try:
                arr = np.load(path, allow_pickle=False)
            except Exception:
                continue  # markers / manifests / non-npy payloads
            if not np.issubdtype(arr.dtype, np.floating):
                continue
            with open(path, "wb") as f:
                np.save(f, np.full_like(arr, np.nan), allow_pickle=False)
            fired = True
    if fired:
        from .log import LOG

        LOG(f"fault: NaN-poisoned checkpoint serial {serial} at {dirname}")
    return fired


def io_delay() -> None:
    """Slow-storage simulation: sleep inside checkpoint write paths."""
    plan = active()
    if plan is not None and plan.io_delay_ms > 0 \
            and plan._applies_to_this_rank():
        time.sleep(plan.io_delay_ms / 1000.0)


def _io_error_key(path: str) -> str:
    """Stable identity for a file across runs: the path's last two
    components (``checkpoint_0/fc_0.w_0``, ``heartbeats/hb_1``) — the
    enclosing temp/work dir differs per run, the tail does not, so the
    SAME logical files fail under the same seed in every drill."""
    parts = [p for p in os.path.normpath(path).split(os.sep) if p]
    return "/".join(parts[-2:])


def io_error(path: str, op: str) -> None:
    """Deterministic transient-I/O oracle, consulted immediately before
    each raw read/write of durable state (checkpoint var files, _SUCCESS
    commits, census heartbeats/markers, warmup manifests, compile-cache
    commits).  A seeded hash of ``(seed, path tail, op)`` picks the
    fraction ``io_error_rate`` of keys that fail; for a picked key the
    FIRST attempt raises OSError and every later attempt succeeds —
    transient by construction, so bounded retry (``fluid.retry.
    retry_io``) always recovers while an unretried site sees a hard
    failure.  Content corruption never flows through here: a torn or
    bit-rotted payload surfaces as ValueError at parse time and keeps
    taking the serial-condemnation fallback, never the retry path."""
    plan = active()
    if plan is None or plan.io_error_rate <= 0 \
            or not plan._applies_to_this_rank():
        return
    import hashlib

    key = (_io_error_key(path), str(op))
    digest = hashlib.sha1(
        f"{plan.io_error_seed}|{key[0]}|{key[1]}".encode()).hexdigest()
    if int(digest[:8], 16) / float(0xFFFFFFFF) >= plan.io_error_rate:
        return
    attempts = plan._io_error_attempts.get(key, 0)
    plan._io_error_attempts[key] = attempts + 1
    if attempts == 0:
        raise OSError(
            f"injected transient I/O error ({key[1]} {key[0]}, "
            f"attempt 1 — retry succeeds)")


def serving_request() -> None:
    """Serving-path hook, called once per admitted request at batch
    formation.  Applies the per-request injected delay, then fails every
    Nth request by RAISING InjectedFault — always a raise regardless of
    ``mode``, because a per-request fault models a failed request, not a
    dead server (the engine delivers it on that request's future and the
    rest of the batch must still complete)."""
    plan = active()
    if plan is None or not plan._applies_to_this_rank():
        return
    if plan.serve_delay_ms > 0:
        time.sleep(plan.serve_delay_ms / 1000.0)
    if plan.serve_fail_every > 0:
        plan._serve_count += 1
        if plan._serve_count % plan.serve_fail_every == 0:
            raise InjectedFault(
                f"injected serving failure (request #{plan._serve_count})")


def decode_stall(n_ticks: int = 1) -> None:
    """Continuous-batching tick stall: the DecodeEngine worker calls this
    once per iteration (admit -> step -> retire), so an armed stall
    inflates EVERY in-flight stream's inter-token latency by the same
    deterministic amount — the oracle for the SLO watchdog breaching on
    ``serving.intertoken_s`` (unlike SERVE_DELAY_MS, which delays whole
    requests at batch formation, this models a slow decode step)."""
    plan = active()
    if plan is None or plan.decode_stall_ms <= 0 \
            or not plan._applies_to_this_rank():
        return
    time.sleep(plan.decode_stall_ms * max(1, int(n_ticks)) / 1000.0)


def replica_kill(served_total: int) -> bool:
    """Serving-fleet replica-death oracle, consulted by the fleet after
    every completed request with the fleet-wide served total.  True
    EXACTLY ONCE, when the total first reaches ``replica_kill_after`` —
    the fleet then kills the replica that served that request (its
    resident futures fail, the pool census re-spawns it on surviving
    devices).  Deliberately never a process exit, whatever ``mode`` says:
    the fault models a dead replica inside a living fleet, and an
    ``os._exit`` would take the router and every other replica with it."""
    plan = active()
    if plan is None or plan.replica_kill_after is None \
            or plan._replica_kill_fired \
            or not plan._applies_to_this_rank():
        return False
    if int(served_total) < plan.replica_kill_after:
        return False
    plan._replica_kill_fired = True
    from .log import LOG

    LOG(f"fault: replica kill after {served_total} served requests")
    return True


def kv_page_leak() -> bool:
    """Paged-KV leak oracle, consulted by ``serving.kvpool.PagePool``
    once per page free: True for the first ``kv_page_leak`` calls
    (decrementing — one skipped free per True), then permanently False.
    A True return makes the allocator SKIP that free, so the page stays
    accounted live forever: the deterministic paged twin of the
    MEM_PRESSURE synthetic leak, visible in ``kvpool.pages_free`` /
    ``kvpool.hbm_bytes`` and the live-buffer ledger."""
    plan = active()
    if plan is None or plan._kv_leaks_left <= 0 \
            or not plan._applies_to_this_rank():
        return False
    plan._kv_leaks_left -= 1
    return True


def spec_draft_poison() -> Optional[int]:
    """Speculative-draft poison oracle, consulted by ``serving.specdec``
    once per spec tick: the armed tick threshold, or None when disarmed.
    From engine tick >= threshold the SpecDecoder replaces every drafted
    token with deterministic garbage, collapsing acceptance to ~1/vocab.
    Proves two things at once: the adaptive controller fires
    ``specdec.fallback`` within its window, and the emitted stream stays
    bitwise correct anyway (acceptance only ever keeps target argmaxes,
    so a garbage draft costs throughput, never correctness)."""
    plan = active()
    if plan is None or plan.spec_draft_poison is None \
            or not plan._applies_to_this_rank():
        return None
    return plan.spec_draft_poison


def cache_corrupt() -> bool:
    """Compile-cache read-corruption oracle: when armed, every persistent
    cache entry load is treated as corrupt, forcing the fresh-compile
    fallback (``CompileCacheStore.get`` quarantines the entry and reports
    a miss; the run must still succeed).  Deterministic by construction —
    the hook is consulted at every load, so a run under this flag
    exercises the fallback path on every single lookup."""
    plan = active()
    return (plan is not None and plan.cache_corrupt
            and plan._applies_to_this_rank())


def data_stall(index: int) -> None:
    """Input-pipeline stall injection, consulted by the pipeline source
    once per pulled sample (``index`` is the source's epoch cursor).
    With ``data_stall_at`` unset the stall applies to EVERY sample (a
    constantly slow reader); with it set, the stall fires exactly once,
    at that cursor — the deterministic oracle for the data-wait SLO
    (one window's ``train.data_wait_s`` spikes, the watchdog breaches)."""
    plan = active()
    if plan is None or plan.data_stall_ms <= 0 \
            or not plan._applies_to_this_rank():
        return
    if plan.data_stall_at is None:
        time.sleep(plan.data_stall_ms / 1000.0)
    elif not plan._data_stall_fired and int(index) == plan.data_stall_at:
        plan._data_stall_fired = True
        time.sleep(plan.data_stall_ms / 1000.0)


def shard_corrupt() -> bool:
    """Data-state corruption oracle: True exactly once when armed — the
    next ``data_state`` blob write is truncated mid-payload, so the
    resumed run must detect the corrupt cursor at load time and fall
    back to the previous complete serial (never resume at a garbage
    position)."""
    plan = active()
    if plan is None or not plan.shard_corrupt or plan._shard_corrupt_fired \
            or not plan._applies_to_this_rank():
        return False
    plan._shard_corrupt_fired = True
    return True


def mem_pressure_bytes() -> int:
    """Synthetic-leak oracle, consulted by the live-buffer ledger once per
    observation: zero until the ``mem_pressure_at``-th call, then
    ``mem_pressure_mb`` MB doubling per observation — deterministic
    monotonic growth that trips the SLO watchdog's factor-over-median
    breach (and, with ``PADDLE_MEM_BUDGET_MB`` set, the over-budget
    event) within a few windows, like a real accumulating leak."""
    plan = active()
    if plan is None or plan.mem_pressure_mb <= 0 \
            or not plan._applies_to_this_rank():
        return 0
    plan._mem_pressure_calls += 1
    past = plan._mem_pressure_calls - plan.mem_pressure_at
    if past <= 0:
        return 0
    return int(plan.mem_pressure_mb * (1 << 20)) << min(past - 1, 16)


def straggler_delay(n_steps: int = 1) -> None:
    """Straggler oracle: the armed rank sleeps ``straggler_ms`` per step
    at the training step boundary (a fused window sleeps once for its
    whole span).  Deliberately keyed on ``straggler_rank`` alone —
    ``PADDLE_FAULT_RANK`` scopes the OTHER faults, so a kill on rank 0
    and a straggler on rank 1 compose in one scenario."""
    plan = active()
    if plan is None or plan.straggler_ms <= 0:
        return
    if plan.straggler_rank is not None and plan.straggler_rank != \
            int(os.environ.get("PADDLE_TRAINER_ID", "0") or 0):
        return
    time.sleep(plan.straggler_ms * max(1, int(n_steps)) / 1000.0)


def barrier_stall(tag: str = "") -> None:
    """Wedged-collective simulation: one-shot sleep before a barrier, long
    enough for the supervisor's heartbeat timeout to classify this process
    as wedged."""
    plan = active()
    if plan is not None and plan.barrier_stall_s > 0 \
            and not plan._stall_fired and plan._applies_to_this_rank():
        plan._stall_fired = True
        time.sleep(plan.barrier_stall_s)
