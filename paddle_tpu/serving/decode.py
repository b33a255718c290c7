"""Continuous batching for autoregressive decode (ISSUE 15 tentpole).

The PR 2 :class:`~paddle_tpu.serving.engine.ServingEngine` batches at
REQUEST granularity: a batch runs to completion before its members
resolve, so one long generation convoys every short request behind it,
and each distinct live-batch shape risks a fresh XLA executable.  This
module is the canonical fix (the Orca/vLLM iteration-level design,
shaped TPU-first):

 - **Slot-based KV cache**: the decode state is a persistable
   ``[max_slots, max_len, d_model]`` pytree of per-layer K/V caches that
   lives DEVICE-RESIDENT across dispatches (executor scope state, donated
   buffers aliasing window-over-window — the PR 6 machinery, opted in
   via ``program._donate_state``).  A request owns one slot from
   admission to retirement.
 - **Iteration-level scheduling**: every engine tick runs ONE compiled
   decode step over ALL slots — fixed ``[max_slots, ...]`` shapes mean
   exactly one decode executable plus a small bucketed-prefill set, so
   the compile counter stays flat in steady state no matter how requests
   arrive (the shape discipline the bucket manifest and compile cache
   were built for).  New requests enter free slots mid-flight via a
   bucketed prefill that writes their K/V prefix in place; finished
   slots retire IMMEDIATELY, so a short request's latency is
   O(own length), not O(longest cohabitant).
 - **Worker loop**: ``admit -> step -> retire``, one thread owning every
   dispatch (single jit-cache writer, donation-safe).

Correctness contract: the decode-step program is row-independent over
the slot dim and masks stale cache positions with EXACT ``-inf`` bias
(zero attention weight in IEEE), so generated tokens are bitwise
identical to per-request sequential decode — continuous batching is
purely a scheduling change.  :meth:`DecodeEngine.decode_static` keeps
the request-granularity baseline alive as the convoy oracle's
comparator.

Observability: ``serving.request`` spans gain ``serving.prefill`` and
``serving.decode_step`` × N children (iteration-level preemption is
visible in the span tree); :class:`ServingMetrics` gains TTFT and
inter-token latency series plus ``slots_active``/``slots_free`` gauges
mirrored into the process registry; the SLO watchdog watches
``serving.ttft_s``/``serving.intertoken_s`` (deterministic breach
oracle: ``PADDLE_FAULT_DECODE_STALL_MS``).

Hot model swap (ISSUE 16): weights are shared BY NAME across the
startup/prefill/step programs through the engine's one scope, and the
executor re-gathers state from the scope on every dispatch — so
:meth:`DecodeEngine.swap_weights` is a scope rebind between ticks under
``_dispatch_lock``, never a recompile, and the fixed-executable-set
invariant holds across arbitrarily many checkpoint swaps.  The
per-tick monitor hook (:meth:`DecodeEngine.set_tick_monitor`) hands the
step's logits to ``serving.registry``'s canary sentinel.

Paged KV cache (ISSUE 19): with ``PADDLE_SERVE_PAGED=1`` the model's
per-layer caches become ``[num_pages + 1, page_size, d_model]`` page
pools and the engine drives a host-side :class:`~.kvpool.PagePool` —
admission allocates pages (or re-queues on exhaustion: backpressure,
never a crash), decode growth allocates one page per ``page_size``
ticks (a dry pool stalls the slot one bitwise-invisible tick), retire
and deadline expiry return pages EXPLICITLY, and full prompt pages are
refcount-shared across requests with a common prefix (``full_hit``
admissions skip the prefill dispatch outright).  Decode output stays
bitwise identical to the dense engine — the page indirection only moves
where K/V rows live, never what they contain or how they reduce.

Knobs (``fluid.envcontract``): ``PADDLE_SERVE_DECODE`` (kill switch),
``PADDLE_SERVE_SLOTS``, ``PADDLE_SERVE_MAX_LEN``,
``PADDLE_SERVE_PREFILL_BUCKETS``; paged mode adds
``PADDLE_SERVE_PAGED``, ``PADDLE_SERVE_PAGE_SIZE``,
``PADDLE_SERVE_NUM_PAGES``, ``PADDLE_SERVE_PREFIX_SHARE``; speculative
decoding (ISSUE 20, ``serving.specdec``) adds ``PADDLE_SERVE_SPEC``,
``PADDLE_SERVE_SPEC_DRAFT_LAYERS``, ``PADDLE_SERVE_SPEC_MIN_ACCEPT``,
``PADDLE_SERVE_SPEC_WINDOW``.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .engine import (DrainTimeout, EngineClosed, EngineOverloaded,
                     RequestTimeout, _Request)
from .metrics import ServingMetrics

__all__ = ["DecodeConfig", "DecodeEngine", "create_decode_engine"]


@dataclass
class DecodeConfig:
    """Scheduling policy for a :class:`DecodeEngine`.  The SHAPE knobs
    (slots, max_len, prefill buckets) live on the model — they define
    the executable set — while this carries pure policy:

    ``max_queue_depth``    pending requests beyond this shed with
                           :class:`EngineOverloaded` (same fast-fail
                           backpressure as the batch engine);
    ``default_timeout_ms`` per-request deadline when submit() gets none.
                           Decode deadlines are checked PER TOKEN: a
                           request can expire mid-generation and free
                           its slot for the queue;
    ``idle_wait_s``        worker-condition wait while fully idle;
    ``spec``               speculation depth k (draft+verify ticks,
                           ISSUE 20).  None = use ``PADDLE_SERVE_SPEC``
                           (config beats env; 0 is the kill switch);
    ``spec_draft_layers``  self-draft depth override for
                           ``PADDLE_SERVE_SPEC_DRAFT_LAYERS`` (0 =
                           full-depth self-draft);
    ``spec_draft_serial``  registry serial directory to load the draft
                           model's weights from instead of sharing the
                           target's (serving.registry
                           ``load_serial_weights`` path).
    """
    max_queue_depth: int = 256
    default_timeout_ms: Optional[float] = None
    idle_wait_s: float = 0.05
    spec: Optional[int] = None
    spec_draft_layers: Optional[int] = None
    spec_draft_serial: Optional[str] = None


class DecodeEngine:
    """Iteration-level-scheduled generation over one step-form decode
    model (:class:`paddle_tpu.models.transformer.DecodeModel`).

    ``submit(prompt_ids, max_new_tokens)`` returns a Future of the
    generated token-id list (greedy decode; ends at the model's
    ``end_id``, the token budget, or cache capacity).  Use as a context
    manager or call ``shutdown()``."""

    def __init__(self, model=None, config: Optional[DecodeConfig] = None,
                 place=None, metrics_labels: Optional[Dict[str, str]] = None):
        from ..fluid import envcontract as _ec

        if not _ec.get("PADDLE_SERVE_DECODE"):
            raise EngineClosed(
                "continuous-batching decode is disabled "
                "(PADDLE_SERVE_DECODE=0)")
        if model is None:
            from ..models.transformer import DecodeModel

            model = DecodeModel()
        self.model = model
        self.config = config or DecodeConfig()
        # metrics_labels (e.g. {"model": ..., "replica": ...}) dimension
        # this engine's process-registry mirrors so a fleet of engines
        # stays separable in one registry (serving/fleet.py sets them)
        self.metrics = ServingMetrics(labels=metrics_labels)
        from ..fluid import core as _core
        from ..fluid.executor import Executor, Scope

        self._scope = Scope()
        self._exe = Executor(place if place is not None
                             else _core.CPUPlace())
        self._exe.run(model.startup, scope=self._scope)
        # paged KV cache (ISSUE 19): when the model was built paged, all
        # page policy lives in this host-side pool — the worker consults
        # it under _dispatch_lock for admissions (backpressure), growth
        # (per-tick stalls) and frees (retire/expiry/reap)
        self._pool = None
        if getattr(model, "paged", False):
            from .kvpool import PagePool

            page_bytes = (model.page_size * model.cfg.d_model * 4
                          * 2 * model.cfg.n_layer)
            self._pool = PagePool(
                model.num_pages, model.page_size, model.pages_per_slot,
                model.max_slots, page_bytes=page_bytes,
                prefix_share=bool(_ec.get("PADDLE_SERVE_PREFIX_SHARE")),
                metrics=self.metrics)
        # speculative decoding (ISSUE 20): PADDLE_SERVE_SPEC=k>0 arms
        # draft+verify ticks; DecodeConfig fields beat the env knobs.
        # k=0 is the kill switch — the plain tick runs verbatim and no
        # draft model is even built.
        self._spec = None
        spec_k = (self.config.spec if self.config.spec is not None
                  else int(_ec.get("PADDLE_SERVE_SPEC") or 0))
        if spec_k > 0:
            from .specdec import SpecDecoder

            draft_layers = (
                self.config.spec_draft_layers
                if self.config.spec_draft_layers is not None
                else int(_ec.get("PADDLE_SERVE_SPEC_DRAFT_LAYERS")))
            self._spec = SpecDecoder(
                self, spec_k, draft_layers,
                min_accept=float(_ec.get("PADDLE_SERVE_SPEC_MIN_ACCEPT")),
                window=int(_ec.get("PADDLE_SERVE_SPEC_WINDOW")),
                serial=self.config.spec_draft_serial)
        self._cond = threading.Condition(threading.Lock())
        self._queue: collections.deque = collections.deque()
        self._slots: List[Optional[_Request]] = [None] * model.max_slots
        self._n_active = 0
        self._ticks = 0
        self._draining = False
        self._paused = False  # hot-swap drain: hold admissions, keep queue
        self._stopped = False
        self._rid = itertools.count()
        self._tick_monitor = None  # registry canary sentinel (or None)
        self._last_logits = None
        # serializes every dispatch: the worker holds it per iteration,
        # warmup()/decode_static() grab it between iterations
        self._dispatch_lock = threading.Lock()
        self.metrics.note_slots(0, model.max_slots)
        self._worker = threading.Thread(target=self._loop, daemon=True,
                                        name="decode-worker")
        self._worker.start()
        # piggyback on the process observe endpoint when one is up, like
        # the batch engine's port-less mode
        from .. import observe

        srv = observe.http_server()
        if srv is not None:
            srv.add_provider(self.metrics.export_snapshot)
            srv.add_health(self._health)

    @property
    def alive(self) -> bool:
        """False once the engine stopped (shutdown, kill, worker death) —
        the fleet census's liveness probe."""
        return not self._stopped and self._worker.is_alive()

    def _health(self) -> dict:
        with self._cond:
            return {"ok": not self._stopped and not self._draining,
                    "queue_depth": len(self._queue),
                    "slots_active": self._n_active,
                    "slots_free": self.model.max_slots - self._n_active}

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------

    def submit(self, prompt_ids: Sequence[int], max_new_tokens: int,
               timeout_ms: Optional[float] = None) -> Future:
        """Enqueue one generation request; returns a Future of the
        generated token ids (list of int, excluding the prompt)."""
        prompt = [int(t) for t in prompt_ids]
        if not prompt:
            raise ValueError("empty prompt")
        if any(not 0 <= t < self.model.vocab_size for t in prompt):
            raise ValueError(f"prompt token out of vocab range "
                             f"[0, {self.model.vocab_size})")
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self.model.bucket_for(len(prompt)) is None:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds the largest "
                f"prefill bucket ({self.model.prefill_buckets[-1]})")
        if len(prompt) + max_new > self.model.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new}) "
                f"exceed the KV-cache capacity "
                f"(max_len {self.model.max_len})")
        if timeout_ms is None:
            timeout_ms = self.config.default_timeout_ms
        now = time.perf_counter()
        fut: Future = Future()
        req = _Request(None, 1, None, fut, now + timeout_ms / 1000.0
                       if timeout_ms else None, now)
        req.prompt, req.max_new, req.out_tokens = prompt, max_new, []
        req.rid = f"d{next(self._rid)}"
        with self._cond:
            if self._stopped or self._draining:
                raise EngineClosed("decode engine is draining/stopped")
            if len(self._queue) >= self.config.max_queue_depth:
                self.metrics.inc("shed")
                from .. import observe

                observe.emit("serving.shed", kind="decode",
                             queue_depth=self.config.max_queue_depth)
                raise EngineOverloaded(
                    f"decode queue full ({self.config.max_queue_depth} "
                    f"pending); request shed")
            from ..observe import trace as _trace

            req.span = _trace.start_span("serving.request", kind="decode",
                                         prompt_len=len(prompt),
                                         max_new=max_new)
            self._queue.append(req)
            self.metrics.inc("submitted")
            self.metrics.set_gauge("queue_depth", len(self._queue))
            self._cond.notify()
        return fut

    def generate(self, prompt_ids: Sequence[int], max_new_tokens: int,
                 timeout_ms: Optional[float] = None) -> List[int]:
        """Blocking submit."""
        return self.submit(prompt_ids, max_new_tokens,
                           timeout_ms=timeout_ms).result()

    # ------------------------------------------------------------------
    # the worker loop: admit -> step -> retire
    # ------------------------------------------------------------------

    def _loop(self):
        from ..fluid import fault as _fault

        while True:
            with self._cond:
                # a paused engine (mid hot-swap drain) must not spin on
                # its queue: only admissible work or live slots wake it
                while not self._n_active and not self._stopped \
                        and not (self._queue and not self._paused):
                    self._cond.wait(self.config.idle_wait_s)
                if self._stopped:
                    break
            with self._dispatch_lock:
                # robustness-harness hook: per-tick injected stall (the
                # deterministic inter-token-latency breach oracle)
                _fault.decode_stall()
                self._reap_abandoned()
                self._admit()
                if self._n_active:
                    self._tick()
            with self._cond:
                self._cond.notify_all()  # drain() watches progress
            # a lock is not fair: this thread would take the dispatch lock
            # again before a thread that waits for it (a hot swap's
            # snapshot) is scheduled, tick after tick, for as long as a
            # slot is resident; give the interpreter away once a loop
            time.sleep(0)
        self._fail_leftovers()

    def _reap_abandoned(self):
        """Free slots whose futures were already resolved from outside
        the worker (the bounded-drain timeout fails stuck futures with
        DrainTimeout; their slots must not keep decoding dead work)."""
        for i, r in enumerate(self._slots):
            if r is not None and r.future.done():
                self._slots[i] = None
                self._n_active -= 1
                if self._pool is not None:
                    self._pool.release(i)
        self.metrics.note_slots(self._n_active,
                                self.model.max_slots - self._n_active)

    def _fail_leftovers(self):
        """Worker exit with work still resident (drain timeout path):
        nothing will ever resolve these futures — fail them loudly."""
        leftovers = [r for r in self._slots if r is not None]
        if self._pool is not None:
            for i, r in enumerate(self._slots):
                if r is not None:
                    self._pool.release(i)
        self._slots = [None] * self.model.max_slots
        self._n_active = 0
        with self._cond:
            leftovers += list(self._queue)
            self._queue.clear()
        for r in leftovers:
            if r.future.done():
                continue  # already failed by the bounded-drain path
            self.metrics.inc("failed")
            if r.span is not None:
                r.span.end(status="engine_stopped")
            r.future.set_exception(
                EngineClosed("decode engine stopped"))

    def _admit(self):
        """Fill free slots from the queue: one bucketed prefill dispatch
        per admitted request writes its K/V prefix in place."""
        if self._paused:
            return  # hot-swap drain: queue keeps building, nothing sheds
        while True:
            free = next((i for i, r in enumerate(self._slots)
                         if r is None), None)
            if free is None:
                return
            req = None
            with self._cond:
                while self._queue:
                    cand = self._queue.popleft()
                    now = time.perf_counter()
                    if cand.deadline is not None and now > cand.deadline:
                        self.metrics.inc("expired")
                        if cand.span is not None:
                            cand.span.end(status="expired")
                        cand.future.set_exception(RequestTimeout(
                            f"deadline expired after "
                            f"{(now - cand.t_submit) * 1e3:.1f} ms in "
                            f"queue"))
                        continue
                    req = cand
                    break
                self.metrics.set_gauge("queue_depth", len(self._queue))
                if req is not None:
                    # reserve the slot HERE, still under _cond: between
                    # the queue pop and the end of the prefill dispatch
                    # the request must stay visible to the bounded-drain
                    # abort (which scans queue + slots under _cond) — a
                    # drain expiry in that window would otherwise miss
                    # it and the request would decode to completion
                    # unaborted
                    self._slots[free] = req
                    self._n_active += 1
            if req is None:
                return
            if self._pool is not None:
                grant = self._pool.admit(
                    free, req.prompt,
                    self.model.bucket_for(len(req.prompt)))
                if grant is None:
                    # admission backpressure: not enough free pages —
                    # put the request BACK at the head of the queue and
                    # give the slot up.  Resident streams retire pages
                    # over the next ticks; the request re-admits then.
                    with self._cond:
                        self._slots[free] = None
                        self._n_active -= 1
                        self._queue.appendleft(req)
                        self.metrics.inc("page_requeues")
                        self.metrics.set_gauge("queue_depth",
                                               len(self._queue))
                        idle = self._n_active == 0
                    if idle:
                        # nothing is retiring pages: don't busy-spin the
                        # worker against a dry pool (release() notifies
                        # nobody; the idle wait is the retry cadence)
                        time.sleep(self.config.idle_wait_s)
                    return
                req.grant = grant
            self._prefill(req, free)

    def _prefill(self, req: _Request, slot: int):
        from ..observe import trace as _trace

        model = self.model
        plen = len(req.prompt)
        bucket = model.bucket_for(plen)
        tokens = np.zeros((1, bucket), np.int64)
        tokens[0, :plen] = req.prompt
        t0 = time.perf_counter()
        # prefix sharing: when every page the prefill would write below
        # plen-1 is already resident (full_hit), the dispatch is pure
        # re-derivation of bit-identical K/V — skip it entirely.  On a
        # PARTIAL hit the prefill still runs: rewriting a shared page
        # with the same (bucket, prefix) content is bitwise idempotent.
        grant = getattr(req, "grant", None)
        skip = (self._pool is not None and grant is not None
                and grant.full_hit)
        if not skip:
            feeds = {model.PF_TOKENS: tokens}
            if self._pool is not None:
                feeds[model.PF_PAGES] = self._pool.prefill_pages(slot,
                                                                 bucket)
            else:
                feeds[model.PF_SLOT] = np.asarray([slot], np.int64)
            self._run(model.prefill_program(bucket), feeds, [])
            self.metrics.inc("prefills")
        else:
            self.metrics.inc("prefill_skips")
            from .. import observe

            observe.registry().inc("kvpool.prefill_skips")
        if self._spec is not None:
            # the draft cache is private and unshared: its prefill runs
            # even when the target's was a full-hit skip
            self._spec.prefill(slot, tokens, bucket)
        t1 = time.perf_counter()
        req.t_taken = t0
        req.slot = slot
        # the first decode tick re-derives position plen-1 (same token,
        # same weights => bit-identical K/V) and emits the first token
        req.pos = plen - 1
        self.metrics.note_slots(self._n_active,
                                model.max_slots - self._n_active)
        if req.span is not None:
            _trace.emit_span("serving.queue", req.t_submit, t0,
                             parent=req.span)
            if not skip:
                _trace.emit_span("serving.prefill", t0, t1,
                                 parent=req.span, bucket=bucket,
                                 slot=slot, prompt_len=plen)

    def _tick_feeds(self, slots):
        """Fixed-shape decode-step feeds off the current slot table.
        Returns ``(feeds, stalled)``: in paged mode a slot whose cache
        growth found the pool dry STALLS this tick — its active flag
        drops, its write aims at the trash page and the caller discards
        its token (the next tick re-derives the same bits, so a stall is
        invisible in the output stream)."""
        model = self.model
        s = model.max_slots
        tokens = np.zeros((s, 1), np.int64)
        pos = np.zeros((s,), np.int64)
        active = np.zeros((s,), np.float32)
        stalled = set()
        if self._pool is not None:
            wpage = np.full((s,), self._pool.trash_page, np.int64)
            woff = np.zeros((s,), np.int64)
        for i, r in enumerate(slots):
            if r is None:
                continue
            if self._pool is not None:
                if not self._pool.ensure(i, int(r.pos)):
                    stalled.add(i)
                    continue  # active stays 0: masked like a free slot
                wpage[i], woff[i] = self._pool.write_loc(i, int(r.pos))
            active[i] = 1.0
            tokens[i, 0] = (r.out_tokens[-1] if r.out_tokens
                            else r.prompt[-1])
            pos[i] = r.pos
        feeds = {model.DC_TOKENS: tokens, model.DC_POS: pos,
                 model.DC_ACTIVE: active,
                 model.DC_POSENC:
                     model.posenc_rows(pos).astype(np.float32),
                 model.DC_BIAS: model.validity_bias(pos)}
        if self._pool is not None:
            feeds[model.DC_PTABLE] = self._pool.table()
            feeds[model.DC_WPAGE] = wpage
            feeds[model.DC_WOFF] = woff
        return feeds, stalled

    def _step_dispatch(self, slots, count_tick=True):
        """ONE compiled decode step over all slots; returns the [S] next
        tokens (host ints), the set of paged slots that stalled this
        tick, and the [S, V] logits.  The logits ride along as a second
        fetch of the SAME executable (a fixed fetch set from warmup on,
        so the canary sentinel never perturbs the compile counter) and
        land in ``_last_logits`` for the tick monitor.

        ``count_tick=False`` runs the dispatch without advancing the
        engine tick (the spec tick's tail dispatch: slots too close to
        max_len to speculate ride the plain step INSIDE the one spec
        tick, so one scheduling iteration still counts once)."""
        feeds, stalled = self._tick_feeds(slots)
        nxt, logits = self._run(self.model.step_program, feeds,
                                [self.model.step_fetch,
                                 self.model.logits_fetch])
        logits = np.asarray(logits)
        if count_tick:
            self._ticks += 1
            self.metrics.inc("decode_ticks")
            self._last_logits = logits
        return np.asarray(nxt).reshape(-1), stalled, logits

    def _consume(self, i: int, req: _Request, tok: int, t0: float,
                 t1: float) -> bool:
        """Commit ONE generated token to slot ``i`` with all the stream
        bookkeeping (latency observations, span, retirement on end_id /
        token budget / cache capacity, the per-token deadline).  Shared
        by the plain tick and the spec tick's accepted-prefix commit so
        the two paths cannot drift.  Returns True when the request
        retired (caller must stop feeding it tokens)."""
        from ..observe import trace as _trace

        model = self.model
        req.out_tokens.append(tok)
        req.pos += 1
        self.metrics.inc("tokens_generated")
        if len(req.out_tokens) == 1:
            self.metrics.observe_ttft(t1 - req.t_submit)
        else:
            self.metrics.observe_intertoken(t1 - req.t_prev_token)
        req.t_prev_token = t1
        if req.span is not None:
            _trace.emit_span("serving.decode_step", t0, t1,
                             parent=req.span, slot=i,
                             token_index=len(req.out_tokens) - 1,
                             tick=self._ticks)
        done = (tok == model.end_id
                or len(req.out_tokens) >= req.max_new
                or req.pos >= model.max_len)
        if done:
            self._retire(i)
            return True
        if req.deadline is not None and t1 > req.deadline:
            # per-token deadline: expire MID-GENERATION and free the
            # slot for the queue instead of decoding a dead request
            self._retire(i, error=RequestTimeout(
                f"deadline expired after {len(req.out_tokens)} "
                f"generated tokens"))
            return True
        return False

    def _stall_expire(self, i: int, req: _Request, t1: float) -> None:
        """Pool-dry stall: the row ran masked (trash write, active=0) —
        its token is discarded, pos keeps, and it retries next tick once
        a retirement frees pages.  Deadlines still apply: an expired
        staller must retire and return its pages, or mutual stalls could
        live-lock the pool."""
        if req.deadline is not None and t1 > req.deadline:
            self._retire(i, error=RequestTimeout(
                f"deadline expired after {len(req.out_tokens)} "
                f"generated tokens (pool-stalled)"))

    def _run_monitor(self, logits, dispatched) -> None:
        """Canary sentinel invocation: this tick's logits + the slot
        table they were computed for (pre-retire copy, so completions
        are visible to the probation counter).  A sentinel fault must
        never take down the worker it watches."""
        mon = self._tick_monitor
        if mon is None:
            return
        try:
            mon(logits, dispatched)
        except Exception:
            import traceback

            from .. import observe

            observe.emit("model.monitor_error",
                         error=traceback.format_exc(limit=3))

    def _tick(self):
        if self._spec is not None and self._spec.run_tick():
            return  # draft+verify tick ran (specdec.SpecDecoder)
        t0 = time.perf_counter()
        dispatched = list(self._slots)  # rows the logits correspond to
        nxt, stalled, _ = self._step_dispatch(self._slots)
        t1 = time.perf_counter()
        for i, req in enumerate(list(self._slots)):
            if req is None:
                continue
            if i in stalled:
                self._stall_expire(i, req, t1)
                continue
            self._consume(i, req, int(nxt[i]), t0, t1)
        self._run_monitor(self._last_logits, dispatched)

    def _retire(self, slot: int, error: Optional[Exception] = None):
        req = self._slots[slot]
        self._slots[slot] = None
        self._n_active -= 1
        if self._pool is not None:
            # explicit page return on EVERY retirement path — completion
            # AND deadline expiry (the lazy-reclaim bug: an expired
            # stream's rows used to stay resident until slot reuse).
            # Refcounted prefix pages survive until their last sharer.
            self._pool.release(slot)
        if self._spec is not None:
            # the next resident of this slot id starts with a fresh
            # rolling acceptance rate
            self._spec.controller.retire_slot(slot)
        self.metrics.note_slots(self._n_active,
                                self.model.max_slots - self._n_active)
        if req.future.done():
            return  # failed externally (bounded-drain timeout)
        if error is not None:
            self.metrics.inc("expired" if isinstance(error, RequestTimeout)
                             else "failed")
            if req.span is not None:
                req.span.end(status="expired"
                             if isinstance(error, RequestTimeout)
                             else "error")
            req.future.set_exception(error)
            return
        now = time.perf_counter()
        self.metrics.inc("completed")
        self.metrics.observe_latency(now - req.t_submit)
        if req.span is not None:
            req.span.end(status="ok", slot=slot,
                         tokens=len(req.out_tokens))
        req.future.set_result(list(req.out_tokens))

    # ------------------------------------------------------------------
    # dispatch plumbing + warmup
    # ------------------------------------------------------------------

    def _run(self, program, feed, fetch_list, scope=None):
        """Executor dispatch with compile-counter accounting: any jit-
        cache growth under traffic shows up on ``bucket_compiles`` — the
        fixed-executable-set invariant's counter (must stay flat after
        warmup).  ``scope`` overrides the engine scope (the spec draft
        model dispatches against its own scope through the SAME executor
        so its compiles land on the same counter)."""
        before = len(self._exe._cache)
        outs = self._exe.run(program, feed=feed, fetch_list=fetch_list,
                             scope=scope if scope is not None
                             else self._scope)
        grown = len(self._exe._cache) - before
        if grown > 0:
            self.metrics.inc("bucket_compiles", grown)
        return outs

    def executables(self) -> int:
        """Compiled executables resident in the engine's jit cache (the
        fixed set: one decode step + one per warmed prefill bucket)."""
        return len(self._exe._cache)

    def _warm_fingerprints(self) -> Dict[str, str]:
        """Content fingerprints of the fixed executable set, keyed
        ``prefill:<bucket>`` / ``step`` — the decode twin of the batch
        engine's bucket fingerprints.  The model builds its programs
        rename-invariantly from a deterministic seed, so two separately
        constructed engines over the same config (fleet replicas) hash
        identically and share store entries.  Empty dict on any
        fingerprint failure (caller falls back to full dispatch)."""
        from .. import compile_cache as _cc

        model = self.model
        paged = self._pool is not None
        fps: Dict[str, str] = {}
        try:
            for b in model.prefill_buckets:
                if paged:
                    pf_feeds = [(model.PF_PAGES,
                                 (int(b) // model.page_size,), "int64"),
                                (model.PF_TOKENS, (1, int(b)), "int64")]
                else:
                    pf_feeds = [(model.PF_SLOT, (1,), "int64"),
                                (model.PF_TOKENS, (1, int(b)), "int64")]
                fps[f"prefill:{int(b)}"] = _cc.program_fingerprint(
                    model.prefill_program(b),
                    feeds=pf_feeds,
                    fetches=[],
                    extra={"kind": "decode_prefill", "bucket": int(b),
                           "paged": paged})
            step_feed = self._tick_feeds([None] * model.max_slots)[0]
            fps["step"] = _cc.program_fingerprint(
                model.step_program,
                feeds=sorted((k, tuple(v.shape), str(v.dtype))
                             for k, v in step_feed.items()),
                fetches=[model.step_fetch, model.logits_fetch],
                extra={"kind": "decode_step", "paged": paged})
        except Exception:
            return {}
        return fps

    def _write_warm_manifest(self, fps: Dict[str, str]) -> None:
        """Atomic (tmp + rename) decode warmup manifest next to the batch
        engine's bucket manifests under ``<store>/serving/``; never fails
        warmup.  A re-spawned replica's cold start is driven by the SAME
        store entries, the manifest records what the set was."""
        import json
        import os

        from .. import compile_cache as _cc

        store = _cc.get_store()
        if store is None or "step" not in fps:
            return
        model = self.model
        manifest = {
            "version": 1,
            "created": time.time(),
            "kind": "decode",
            "max_slots": int(model.max_slots),
            "max_len": int(model.max_len),
            "prefill_buckets": [int(b) for b in model.prefill_buckets],
            "paged": self._pool is not None,
            "page_size": (int(model.page_size) if self._pool is not None
                          else None),
            "fingerprints": dict(fps),
        }
        try:
            path = store.serving_manifest_path(f"decode-{fps['step']}")
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(manifest, f, indent=1)
            os.replace(tmp, path)
        except Exception:
            pass

    def warmup(self, only_missing: Optional[bool] = None) -> int:
        """Precompile the ENTIRE fixed executable set — the one decode
        step plus every prefill bucket — before traffic, so steady state
        never compiles (any later ``bucket_compiles`` growth is a bug:
        an unplanned shape reached the executor).

        With the persistent compile cache enabled (``only_missing`` left
        at its default), programs whose fingerprints are already in the
        store are NOT dispatched: a prior process — or another replica of
        the same model — compiled them into the shared backend cache, so
        a scale-out/re-spawned replica's warm is cache-hit-only
        (``warmup_cached`` counts up, ``warmup_dispatches`` stays 0; the
        executable loads from the store on first use).
        ``only_missing=False`` forces full dispatch.

        Safe to call again; returns the executable count."""
        from .. import compile_cache as _cc

        store = _cc.get_store()
        if only_missing is None:
            only_missing = store is not None
        model = self.model
        fps = self._warm_fingerprints() if store is not None else {}

        def _cached(key: str) -> bool:
            fp = fps.get(key)
            return bool(only_missing and store is not None
                        and fp is not None and store.get(fp) is not None)

        def _record(key: str, program, meta: dict) -> None:
            fp = fps.get(key)
            if store is None or fp is None:
                return
            try:  # cache bookkeeping never fails warmup
                store.put(fp, program.serialize_to_string(), meta)
            except Exception:
                pass

        with self._dispatch_lock:
            for b in model.prefill_buckets:
                key = f"prefill:{int(b)}"
                if _cached(key):
                    self.metrics.inc("warmup_cached")
                    continue
                feeds = {model.PF_TOKENS: np.zeros((1, b), np.int64)}
                if self._pool is not None:
                    # warm against the trash page: zero-token K/V lands
                    # nowhere a real stream will ever read
                    feeds[model.PF_PAGES] = np.full(
                        (b // model.page_size,), self._pool.trash_page,
                        np.int64)
                else:
                    feeds[model.PF_SLOT] = np.zeros((1,), np.int64)
                self._run(model.prefill_program(b), feeds, [])
                self.metrics.inc("warmup_dispatches")
                _record(key, model.prefill_program(b),
                        {"kind": "decode_prefill", "bucket": int(b)})
            if _cached("step"):
                self.metrics.inc("warmup_cached")
            else:
                self._step_dispatch([None] * model.max_slots)
                self.metrics.inc("warmup_dispatches")
                _record("step", model.step_program,
                        {"kind": "decode_step"})
            if self._spec is not None:
                # the spec additions to the executable set (draft
                # prefills, draft step, verify) precompile here too —
                # spec traffic must not grow bucket_compiles either
                self._spec.warmup()
        self._write_warm_manifest(fps)
        from .. import observe

        observe.emit("serving.warmup", kind="decode",
                     prefill_buckets=model.prefill_buckets,
                     max_slots=model.max_slots, max_len=model.max_len,
                     dispatched=self.metrics.counter("warmup_dispatches"),
                     cached=self.metrics.counter("warmup_cached"),
                     executables=self.executables())
        return self.executables()

    # ------------------------------------------------------------------
    # static-batching baseline (the convoy oracle's comparator)
    # ------------------------------------------------------------------

    def decode_static(self, batch: Sequence[Tuple[Sequence[int], int]]
                      ) -> List[Tuple[List[int], float]]:
        """Request-granularity batching over the SAME model/executables:
        admit the whole batch, tick until EVERY member finishes, and
        resolve all of them at batch end — exactly the convoy the
        iteration-level scheduler removes.  A one-request batch is the
        per-request sequential baseline (the bitwise-identity oracle).
        Returns ``[(tokens, latency_s), ...]``; only callable while the
        engine is otherwise idle (test/bench comparator, not a serving
        path)."""
        if len(batch) > self.model.max_slots:
            raise ValueError(f"static batch ({len(batch)}) exceeds "
                             f"max_slots ({self.model.max_slots})")
        with self._dispatch_lock:
            if self._n_active or self._queue:
                raise RuntimeError("decode_static requires an idle engine")
            slots: List[Optional[_Request]] = [None] * self.model.max_slots
            t_start = []
            admitted: List[int] = []
            try:
                for i, (prompt, max_new) in enumerate(batch):
                    fut: Future = Future()
                    t0 = time.perf_counter()
                    req = _Request(None, 1, None, fut, None, t0)
                    req.prompt = [int(t) for t in prompt]
                    req.max_new = int(max_new)
                    req.out_tokens = []
                    plen = len(req.prompt)
                    bucket = self.model.bucket_for(plen)
                    tokens = np.zeros((1, bucket), np.int64)
                    tokens[0, :plen] = req.prompt
                    feeds = {self.model.PF_TOKENS: tokens}
                    skip = False
                    if self._pool is not None:
                        grant = self._pool.admit(i, req.prompt, bucket)
                        if grant is None:
                            raise RuntimeError(
                                f"page pool cannot admit static batch "
                                f"member {i} "
                                f"({self._pool.pages_free} pages free)")
                        admitted.append(i)
                        skip = grant.full_hit
                        feeds[self.model.PF_PAGES] = \
                            self._pool.prefill_pages(i, bucket)
                    else:
                        feeds[self.model.PF_SLOT] = \
                            np.asarray([i], np.int64)
                    if not skip:
                        self._run(self.model.prefill_program(bucket),
                                  feeds, [])
                    req.pos = plen - 1
                    slots[i] = req
                    t_start.append(t0)
                finished = [False] * len(batch)
                while not all(finished):
                    live = [r if r is not None and not finished[j]
                            else None
                            for j, r in enumerate(slots[:len(batch)])]
                    live += [None] * (self.model.max_slots - len(live))
                    nxt, stalled, _ = self._step_dispatch(live)
                    progressed = False
                    for j, req in enumerate(slots[:len(batch)]):
                        if finished[j] or j in stalled:
                            continue
                        progressed = True
                        tok = int(nxt[j])
                        req.out_tokens.append(tok)
                        req.pos += 1
                        finished[j] = (tok == self.model.end_id
                                       or len(req.out_tokens)
                                       >= req.max_new
                                       or req.pos >= self.model.max_len)
                        if finished[j] and self._pool is not None:
                            self._pool.release(j)
                            if j in admitted:
                                admitted.remove(j)
                    if not progressed:
                        # every live slot stalled and none can retire:
                        # a static batch has no churn to free pages
                        raise RuntimeError(
                            "page pool exhausted with the whole static "
                            "batch resident — no retirement can free "
                            "pages; use a smaller batch or more pages")
                t_end = time.perf_counter()
                return [(list(slots[j].out_tokens), t_end - t_start[j])
                        for j in range(len(batch))]
            finally:
                if self._pool is not None:
                    for j in list(admitted):
                        self._pool.release(j)

    # ------------------------------------------------------------------
    # hot model swap surface (serving.registry drives these)
    # ------------------------------------------------------------------

    def snapshot_weights(self, names: Sequence[str]) -> Dict[str, np.ndarray]:
        """Host copies of the named scope vars, taken between dispatches
        — the registry's rollback set (the old serial stays resident as
        plain host arrays until the new one is promoted)."""
        with self._dispatch_lock:
            out = {}
            for name in names:
                val = self._scope.get(name)
                if val is None:
                    raise KeyError(f"no scope var named {name!r}")
                out[name] = np.array(val, copy=True)
            return out

    def _rebind_weights(self, weights: Dict[str, np.ndarray]) -> None:
        """Scope rebind — caller MUST hold ``_dispatch_lock`` (or be the
        worker inside a tick).  The executor re-gathers state from the
        scope on every dispatch and the jit cache key carries no state
        values, so the next tick runs the SAME executables over the new
        weights: a swap is never a recompile."""
        for name, arr in weights.items():
            self._scope.set(name, np.asarray(arr))
        if self._pool is not None:
            # resident prefix pages were written by the OLD weights: a
            # new admission's prefill would produce different bits, so
            # the share index must forget them (holders keep decoding —
            # their whole cache is old-weight-consistent until retire)
            self._pool.flush_index()
        if self._spec is not None:
            # a self-draft shares weights BY NAME: re-copy so draft and
            # target keep agreeing (serial-backed drafts are pinned and
            # sync() is a no-op for them)
            self._spec.draft.sync(self._scope)

    def swap_weights(self, weights: Dict[str, np.ndarray]) -> None:
        """Atomically rebind the named weights between decode ticks."""
        with self._dispatch_lock:
            self._rebind_weights(weights)

    def _scrub_caches(self) -> None:
        """Zero every slot K/V cache — caller holds ``_dispatch_lock``
        (or is the worker inside a tick).  The rollback path needs this:
        a poisoned canary serial writes NaN into resident caches, and
        NaN rides THROUGH the -inf validity mask (NaN + -inf = NaN), so
        rebinding healthy weights alone would leave every future request
        in that slot poisoned.  Zeros restore the engine-start state:
        fresh admissions prefill over them and are bitwise-clean."""
        for v in self.model.startup.list_vars():
            if not v.persistable or "_cache_" not in v.name:
                continue
            cur = self._scope.get(v.name)
            if cur is not None:
                self._scope.set(v.name, np.zeros(np.shape(cur),
                                                 np.asarray(cur).dtype))
        if self._pool is not None:
            self._pool.flush_index()  # scrubbed pages share nothing
        if self._spec is not None:
            self._spec.draft.scrub()  # draft caches are poisonable too

    def pause_admissions(self) -> None:
        """Hold admissions (the drain swap policy): submits still land in
        the queue — nothing sheds — but no slot is filled until
        :meth:`resume_admissions`.  Resident slots keep ticking."""
        with self._cond:
            self._paused = True
            self._cond.notify_all()

    def resume_admissions(self) -> None:
        with self._cond:
            self._paused = False
            self._cond.notify_all()

    def wait_idle(self, timeout_s: float = 60.0) -> bool:
        """Wait until no slot is resident (queued work may remain when
        admissions are paused).  Returns False on timeout."""
        deadline = time.perf_counter() + timeout_s
        with self._cond:
            while self._n_active:
                left = deadline - time.perf_counter()
                if left <= 0:
                    return False
                self._cond.wait(min(left, 0.05))
        return True

    def abort_resident(self, what: str = "swap drain") -> List[str]:
        """Fail every resident request's future with :class:`DrainTimeout`
        (the bounded-drain expiry path, reused by the drain swap policy
        when old-version slots refuse to retire).  Returns the stuck
        request ids; the worker reaps the dead slots on its next pass."""
        stuck = [r for r in self._slots
                 if r is not None and not r.future.done()]
        ids = [r.rid for r in stuck]
        if stuck:
            exc = DrainTimeout(
                f"{what} timed out with {len(ids)} resident "
                f"request(s) still generating: {', '.join(ids)}", ids)
            for r in stuck:
                self.metrics.inc("failed")
                if r.span is not None:
                    r.span.end(status="drain_timeout")
                if not r.future.done():
                    r.future.set_exception(exc)
        with self._cond:
            self._cond.notify_all()
        return ids

    def set_tick_monitor(self, fn) -> None:
        """Install/remove (None) the per-tick monitor: called on the
        worker thread after each decode tick with ``(logits, slots)`` —
        the [S, V] logits of the dispatch and the slot table it ran
        over.  The registry's canary output-sanity sentinel lives here."""
        self._tick_monitor = fn

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def drain(self, timeout_s: float = 60.0) -> bool:
        """Stop admitting; wait until every queued and resident request
        has resolved.  Returns True when fully drained.  On expiry every
        outstanding future fails with :class:`DrainTimeout` naming the
        stuck request ids — callers never block forever on a wedged
        generation."""
        deadline = time.perf_counter() + timeout_s
        with self._cond:
            self._draining = True
            self._cond.notify_all()
            while self._queue or self._n_active:
                left = deadline - time.perf_counter()
                if left <= 0:
                    self._abort_outstanding_locked("drain")
                    return False
                self._cond.wait(min(left, 0.05))
        return True

    def _abort_outstanding_locked(self, what: str) -> None:
        """Fail every queued + resident future with DrainTimeout (caller
        holds ``_cond``).  Resident slots are left for the worker's
        reap pass — the worker may be mid-tick holding the dispatch
        lock, so they cannot be cleared from here."""
        stuck = list(self._queue) + [r for r in self._slots
                                     if r is not None
                                     and not r.future.done()]
        self._queue.clear()
        self.metrics.set_gauge("queue_depth", 0)
        if not stuck:
            return
        ids = [r.rid for r in stuck]
        exc = DrainTimeout(
            f"{what} timed out after {len(ids)} outstanding decode "
            f"request(s): {', '.join(ids)}", ids)
        for r in stuck:
            self.metrics.inc("failed")
            if r.span is not None:
                r.span.end(status="drain_timeout")
            if not r.future.done():
                r.future.set_exception(exc)

    def shutdown(self, timeout_s: float = 60.0) -> bool:
        ok = self.drain(timeout_s=timeout_s)
        with self._cond:
            self._stopped = True
            self._cond.notify_all()
        self._worker.join(timeout=timeout_s)
        return ok

    def kill(self, join_timeout_s: float = 10.0) -> List[str]:
        """Hard stop WITHOUT drain — the replica-death path (crash
        simulation: ``PADDLE_FAULT_REPLICA_KILL_AFTER``, exercised by
        ``serving/fleet.py``).  Every queued and resident request fails
        with :class:`EngineClosed` when the worker exits; the fleet's
        router re-enqueues those, so a killed replica never sheds.
        Returns the request ids that were in flight."""
        with self._cond:
            in_flight = [r.rid for r in
                         list(self._queue) + [s for s in self._slots
                                              if s is not None]
                         if not r.future.done()]
            self._stopped = True
            self._cond.notify_all()
        if threading.current_thread() is not self._worker:
            self._worker.join(timeout=join_timeout_s)
        from .. import observe

        observe.emit("serving.engine_killed", kind="decode",
                     in_flight=len(in_flight))
        return in_flight

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False


def create_decode_engine(cfg=None, config: Optional[DecodeConfig] = None,
                         metrics_labels: Optional[Dict[str, str]] = None,
                         **model_kwargs) -> DecodeEngine:
    """Build a DecodeEngine over a fresh step-form decode model.  ``cfg``
    is a transformer Config (default: CPU-test-scale decode LM);
    ``model_kwargs`` forward to DecodeModel (max_slots / max_len /
    prefill_buckets default from the env contract)."""
    from ..models.transformer import DecodeModel

    return DecodeEngine(DecodeModel(cfg=cfg, **model_kwargs), config,
                        metrics_labels=metrics_labels)
