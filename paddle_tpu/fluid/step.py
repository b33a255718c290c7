"""The life of one training dispatch, each decision written once.

``Executor.run``, ``Executor.run_steps``, ``ParallelExecutor.run`` (through
``parallel.spmd.ShardedTrainStep``) and ``ParallelExecutor.run_steps``
(through ``ShardedWindowRunner``) keep their own builders and spans and walk
the same sequence:

  ``coerce_feed`` -> ``signature`` (cache key + compile-cache extra) ->
  ``plan_step`` -> ``Dispatch`` (the boundary) -> ``gather_state`` ->
  ``.split`` -> ``.call`` -> ``commit`` -> ``.report``

A caller takes what it has and skips what it has not: the per-step sharded
path has no guard, no step boundary and no donation (ROADMAP D15), so it
opens no ``Dispatch`` and calls the plain functions.  This module imports
nothing from ``executor.py``, ``parallel_executor.py`` or ``parallel/``;
they import it.
"""

from __future__ import annotations

import os
import time
import warnings
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import core
from .framework import RNG_STATE_VAR, Program
from ..ops import registry as _reg



def feed_dtype(program, name, value):
    """The dtype rule: a fed value takes the dtype its variable declares
    BEFORE the cache key is made, so a float64-from-list feed compiles no
    second executable.  A jax array stays on its device (astype is lazy)."""
    if not isinstance(value, jax.Array):
        value = np.asarray(value)
    gb = program.global_block()
    if gb._has_var_recursive(name):
        want = core.np_dtype(gb._var_recursive(name).dtype)
        if value.dtype != want:
            value = value.astype(want)
    return value


def coerce_feed(program, name, value):
    """One fed value as ``(array, lod)``: LoDTensors and the ``(array,
    recursive_sequence_lengths)`` form unwrapped, then :func:`feed_dtype`."""
    lod = None
    from .lod_tensor import LoDTensor

    if isinstance(value, LoDTensor):
        lod = value.lod() or None
        # unwrap WITHOUT np.asarray: a device-resident LoDTensor (what
        # run(return_numpy=False) returns) must stay on device, avoiding a
        # blocking D2H + re-upload round trip on the decode hot path
        value = value._data
    elif isinstance(value, tuple) and len(value) == 2 \
            and isinstance(value[1], (list, tuple)):
        from .lod_tensor import _lengths_to_offsets

        value, lengths = value
        lod = tuple(tuple(_lengths_to_offsets(l)) for l in lengths) or None
    if lod is not None:
        lod = tuple(tuple(int(x) for x in level) for level in lod)
    return feed_dtype(program, name, value), lod


def coerce_feeds(program, feed):
    """A feed dict as ``(arrays, lods)``, the lods of those that have one."""
    arrays, lods = {}, {}
    for k, v in dict(feed or {}).items():
        arrays[k], lod = coerce_feed(program, k, v)
        if lod:
            lods[k] = lod
    return arrays, lods


# "this path has no guarded wrapper": its signature carries no guard entry
UNGUARDED = object()


def signature(kind, program, fetch_names, feed_arrays, guard=UNGUARDED,
              **own):
    """What one compiled step (``kind``: run, run_steps, sharded_step,
    sharded_window) is specialised on, as ``(key, extra)``: the key of the
    in-process cache and the ``extra`` of ``compile_cache.executor_probe``.
    ``own`` are the caller's parts (lods, n_steps, platform, mesh, ...).

    The execution-mode toggles are listed HERE and nowhere else: a toggle
    that changes what a block traces to and is missing from this list
    serves a stale executable."""
    from . import amp
    from ..ops import kernel_choice

    mode = {"amp": amp.compute_dtype(), **kernel_choice.switches()}
    if guard is not UNGUARDED:
        mode["guard"] = guard.cache_token() if guard is not None else None
    # the program's serial, never id(): a dropped program's id is recycled
    key = (kind, program._cache_token, program._version, tuple(fetch_names),
           tuple(sorted((k, tuple(v.shape), str(v.dtype))
                        for k, v in feed_arrays.items())),
           tuple(sorted(own.items())), tuple(sorted(mode.items())))
    return key, {"kind": kind, **own, **mode}


_SIDE_EFFECT_OPS = frozenset(["print", "save", "save_combine"])
_SKIP_OPS = frozenset(["feed", "fetch", "read", "create_py_reader"])


def _resolve_opdef(op_type):
    if _reg.is_registered(op_type):
        return _reg.get_op_def(op_type)
    if op_type.endswith("_grad") and _reg.is_registered(op_type[:-5]):
        return _reg.get_op_def(op_type[:-5])
    return None


class BlockPlan:
    """Static analysis of a block: which ops are live for the requested
    fetches (dead ops are pruned — XLA would DCE them anyway, but pruning
    first avoids demanding un-fed inputs), which names come from scope
    (state_in), which persistables are (re)written (state_out)."""

    def __init__(self, program: Program, block_idx: int,
                 feed_names: Sequence[str], fetch_names: Sequence[str]):
        block = program.block(block_idx)
        self.block = block
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)

        def _is_persistable(name: str) -> bool:
            return block._has_var_recursive(name) and \
                block._var_recursive(name).persistable

        # 1. live-op slice: keep ops needed for fetches or persistable updates
        needed = set(fetch_names)
        kept = []
        for op in reversed(block.ops):
            if op.type in _SKIP_OPS:
                continue
            outs = [n for n in op.output_arg_names if n]
            live = (op.type in _SIDE_EFFECT_OPS
                    or any(n in needed for n in outs)
                    or any(_is_persistable(n) for n in outs))
            if not live:
                continue
            kept.append(op)
            needed.update(n for n in op.input_arg_names if n)
        self.ops = list(reversed(kept))

        # 2. dataflow analysis over the kept ops
        written = set(feed_names)
        state_in: List[str] = []
        self.needs_rng = False
        self.needs_eager = False

        def _scan_rng(op):
            d = _resolve_opdef(op.type)
            if d is not None and d.stateful:
                self.needs_rng = True
            sub = op.attr("sub_block") if hasattr(op, "attr") else None
            if isinstance(sub, int):
                for bop in program.block(sub).ops:
                    _scan_rng(bop)

        def _op_is_eager(op) -> bool:
            """Data-dependent op (or control flow containing one) — must run
            outside jit."""
            from ..ops.array_ops import EAGER_OPS

            base = op.type[:-5] if op.type.endswith("_grad") else op.type
            if base in EAGER_OPS:
                return True
            sub = op.attr("sub_block") if hasattr(op, "attr") else None
            if isinstance(sub, int):
                return any(_op_is_eager(b) for b in program.block(sub).ops)
            return False

        for op in self.ops:
            _scan_rng(op)

        # eager-island segmentation (SURVEY.md §7 hard part #1): contiguous
        # runs of traceable ops become jittable segments; only the
        # data-dependent islands between them run eagerly.  A beam-search
        # decode program keeps its whole encoder in one compiled segment.
        self.segments: List[Tuple[str, list]] = []
        for op in self.ops:
            kind = "eager" if _op_is_eager(op) else "jit"
            if self.segments and self.segments[-1][0] == kind:
                self.segments[-1][1].append(op)
            else:
                self.segments.append((kind, [op]))
        self.needs_eager = any(k == "eager" for k, _ in self.segments)
        for op in self.ops:
            for name in op.input_arg_names:
                if not name:
                    continue
                if name not in written and name not in state_in:
                    state_in.append(name)
            for name in op.output_arg_names:
                if name:
                    written.add(name)
        state_out: List[str] = []
        for op in self.ops:
            for name in op.output_arg_names:
                if not name or name in state_out:
                    continue
                if name in state_in or _is_persistable(name):
                    state_out.append(name)
        # fetches that are never produced in-block must come from state
        for name in self.fetch_names:
            if name not in written and name not in state_in:
                state_in.append(name)
        self.state_in = state_in
        self.state_out = state_out


def plan_step(program, feed_names, fetch_names, guard, eager_error=None):
    """``(plan, guard)`` of one step: the block planned for the user's
    fetches plus, when guarded, the sentinel's own (loss and gradients),
    with the scaler's variables gathered beside the rest of the state.

    The eager-island policy is the caller's: a window cannot scan
    data-dependent ops and raises ``eager_error``; the per-step path (None)
    warns, drops the guard and plans again."""
    feed_names, fetch_names = list(feed_names), list(fetch_names)
    extra = guard.extra_fetch_names() if guard is not None else []
    plan = BlockPlan(program, 0, feed_names, fetch_names + extra)
    if plan.needs_eager and (guard is not None or eager_error):
        if guard is not None and guard.scale_vars is not None:
            raise RuntimeError(
                "dynamic fp16 loss scaling is not supported for "
                "programs with data-dependent eager ops")
        if eager_error:
            raise RuntimeError(eager_error)
        warnings.warn(
            "guardian: program contains data-dependent eager ops; "
            "the numerics sentinel is disabled for it")
        guard = None
        plan = BlockPlan(program, 0, feed_names, fetch_names)
    if guard is not None and guard.scale_vars:
        # the scale / good-steps vars are read and written only by the
        # guarded wrapper (no IR op touches the counter), so liveness never
        # saw them: gather them with the rest of the state
        for n in guard.scale_vars:
            if n not in plan.state_in:
                plan.state_in.append(n)
    return plan, guard


def donate_argnums(program):
    """Donation argnums for the jitted step: the mutable-state arg
    (index 2) is donated so XLA aliases its buffers into the updated
    state — a true in-place parameter update.  Modern jax implements
    donation on every backend (cpu/gpu/tpu), and the executor already
    protects the one read-after-donate hazard (fetches aliasing
    mutated state are copied on return, executor.run's donated-fetch
    path), so it is on for every TRAINING program (built via
    optimizer.minimize, whose step loop is single-threaded by
    contract).  Inference/eval programs never donate: predictor
    clones run concurrently against one shared scope, and a donated
    buffer deleted under a sibling thread's in-flight dispatch is the
    one hazard copy-on-return cannot fix.  ``PADDLE_TPU_DONATE=0``
    opts out entirely (debugging buffer lifetimes).

    Exception to the inference rule: a program that sets
    ``_donate_state = True`` (the serving DecodeEngine's decode-step
    / prefill programs, whose persistable KV cache is rewritten by
    exactly one engine worker thread per the single-dispatcher
    contract) opts back in, so the [max_slots, max_len, ...] cache
    buffers alias window-over-window instead of copying every
    tick."""
    if program is not None and program._params_grads is None \
            and not getattr(program, "_donate_state", False):
        return ()
    from . import envcontract

    if not envcontract.get("PADDLE_TPU_DONATE"):
        return ()
    return (2,)



def step_boundary(n_steps=1):
    """Training-step boundary: fires armed step faults (kill-at-step-N)
    and emits an elastic-supervisor heartbeat when a heartbeat dir is
    configured.  A fused run_steps dispatch advances the whole window at
    once — a kill armed inside it fires before the dispatch.  Returns
    the step index this dispatch executes (window start for fused)."""
    from . import fault as _fault

    fired = _fault.current_step()
    if _fault.active() is not None:
        if n_steps == 1:
            fired = _fault.on_step()
        else:
            _fault.advance(n_steps)
        # straggler oracle: the armed rank's sleep lands here, INSIDE
        # the window span, so its per-step time inflates like a real
        # slow chip's and the skew detector must flag it
        _fault.straggler_delay(n_steps)
    else:
        _fault._step += n_steps  # keep the index flowing for the guardian
    from .. import observe

    # every subsystem's events from here to the next boundary correlate
    # to this step (guardian trips, cache hits, checkpoint commits)
    observe.note_step(fired)
    hb_dir = os.environ.get("PADDLE_ELASTIC_HB_DIR")
    if hb_dir:
        from ..parallel.elastic import write_heartbeat

        write_heartbeat(hb_dir, step=_fault.current_step())
    return fired


_MISSING = object()


def one_step_feed(feed_arrays, feed_per_step):
    """What the pre-compile verifier is shown: stacked ``(n_steps, batch,
    ...)`` windows verify as ONE step's slice."""
    if not feed_per_step:
        return feed_arrays
    return {k: v[0] if getattr(v, "ndim", 0) > 0 else v
            for k, v in feed_arrays.items()}


def gather_state(program, plan, scope):
    """What the plan reads from the scope, as the scope holds it (the
    caller converts or places it).  The one uninitialised / not-fed error,
    and the one place that makes the first RNG key."""
    state = {}
    for name in plan.state_in:
        val = scope.get(name, _MISSING)
        if val is _MISSING:
            gb = program.global_block()
            if gb._has_var_recursive(name) and \
                    gb._var_recursive(name).is_data:
                raise RuntimeError(
                    f"Data variable '{name}' was not fed. Pass it in the "
                    f"feed dict (feed keys were misspelled or missing).")
            raise RuntimeError(
                f"Variable '{name}' is not initialized in the scope. "
                f"Did you run the startup program?")
        state[name] = val
    if plan.needs_rng:
        rk = scope.get(RNG_STATE_VAR, _MISSING)
        if rk is _MISSING:
            rk = jax.random.PRNGKey(program.random_seed or 0)
            scope.set(RNG_STATE_VAR, rk)
        state[RNG_STATE_VAR] = rk
    return state


def check_nan_inf(new_state, fetch_names=(), fetches=()):
    """Debug mode (ref FLAGS_check_nan_inf, operator.cc:643): fault
    with the variable NAME on the first non-finite value.  Host-side
    materialization forces a sync per step — debug only."""
    if not core.GLOBAL_FLAGS.get("check_nan_inf"):
        return
    for name, val in list(new_state.items()) + list(zip(fetch_names,
                                                        fetches)):
        arr = np.asarray(val)
        if np.issubdtype(arr.dtype, np.floating) \
                and not np.isfinite(arr).all():
            raise FloatingPointError(
                f"check_nan_inf: variable '{name}' contains "
                f"NaN/Inf after op block execution")


def mutable_names(plan, guard=None):
    """The state a step rewrites.  Only this is donated; read-only state
    (lr, params in eval programs) must keep its buffers alive in the scope.
    ``guard``: the scaler's variables, which only the wrapper writes."""
    names = set(plan.state_out)
    if plan.needs_rng:
        names.add(RNG_STATE_VAR)
    if guard is not None and guard.scale_vars:
        names.update(guard.scale_vars)
    return names


def split_state(state_vals, mut_names):
    """``(const_state, mut_state)``: the kept argument and the donated."""
    return ({k: v for k, v in state_vals.items() if k not in mut_names},
            {k: v for k, v in state_vals.items() if k in mut_names})


class Dispatch:
    """One dispatch of a program from its boundary to its report, and what
    the stages hand each other on the way: the index of its first step, the
    live guardian, the names of the state it rewrites, the sentinel's
    inputs, the state a dump bundle keeps.  ``n_steps`` says it is a window
    (a ``lax.scan`` of that many steps), ``mesh`` labels a sharded one.

    Opening it IS the boundary: the training-step boundary of a program
    built via ``optimizer.minimize`` (fault hooks, heartbeat; any other
    program starts at 0), then the guardian's, which observes the PREVIOUS
    dispatch's health (it has retired, materializing two scalars is free)
    and applies policy BEFORE this one runs."""

    def __init__(self, program, scope, plan, guard=None, n_steps=None,
                 mesh=None):
        self.program, self.scope, self.plan = program, scope, plan
        self.guard, self.n_steps, self.mesh = guard, n_steps, mesh
        self.training = program._params_grads is not None
        self.start = step_boundary(n_steps or 1) if self.training else 0
        self.g = self.sentinel = self.dump_state = self.gauges = None
        if guard is not None:
            from . import guardian as _guardian

            self.g = _guardian.current()
        if self.g is not None:
            self.g.on_boundary()
        # a window carries the scaler's variables; the per-step guarded
        # step reads them as constants and returns new ones
        self.mut_names = mutable_names(
            plan, guard if n_steps is not None else None)

    def split(self, state_vals, donated=None, place=None):
        """``(const_state, mut_state)`` of the gathered state: the kept
        argument and the donated.  A guarded dispatch also gets its
        sentinel here (the loss cap and the fault oracle's multipliers:
        scalars for a step, one entry a step for a window; ``place`` puts
        them where a sharded executable wants them) and the state its dump
        bundle keeps: donation invalidates the mutated buffers after the
        dispatch, so ``dump_and_halt`` copies them on the device."""
        if self.guard is not None:
            from . import fault as _fault

            if self.n_steps is None:
                seed_mul, loss_mul = map(
                    np.float32, _fault.sentinel_injection(self.start))
            else:
                seed_mul, loss_mul = _fault.sentinel_injection_window(
                    self.start, self.n_steps)
            cap = self.g.loss_cap() if self.g is not None else float("inf")
            self.sentinel = {"loss_cap": np.float32(cap),
                             "seed_mul": seed_mul, "loss_mul": loss_mul}
            if place is not None:
                self.sentinel = {k: place(v)
                                 for k, v in self.sentinel.items()}
            self.dump_state = state_vals
            if donated is None:
                donated = donate_argnums(self.program)
            if self.g is not None and donated \
                    and self.g.config.policy == "dump_and_halt":
                self.dump_state = {
                    k: (jnp.array(v, copy=True) if k in self.mut_names
                        else v) for k, v in state_vals.items()}
        return split_state(state_vals, self.mut_names)

    def call(self, fn, feed_dev, const_state, mut_state):
        """``(fetches, new_state, health)`` of the built step.  A per-step
        unguarded step takes no sentinel; every other one does.  A step
        whose ops published device gauges returns their vector last; it is
        held here as the device array it is until ``keep_gauges``.  Nothing
        here waits on the device, profiling or not."""
        args = (feed_dev, const_state, mut_state)
        if self.guard is not None or self.n_steps is not None:
            args += (self.sentinel,)
        fetches, new_state, *rest = fn(*args)
        health = rest.pop(0) if rest and self.guard is not None else None
        self.gauges = rest[0] if rest else None
        return fetches, new_state, health

    def keep_gauges(self, layout, span_id, t):
        """Hand the step's vector of device gauges (``layout``: where each
        lies, from the step's trace) to ``observe.gauges``, unread: with the
        index of the step, the id of its ``fluid.run`` root and the host
        clock at its call."""
        if self.gauges is not None and layout is not None:
            from ..observe import gauges as _gauges

            _gauges.keep(self.start, span_id, t, self.gauges, layout)
        self.gauges = None

    def report(self, fetches, new_state, health, t0, call, fresh=False,
               compile_s=None, probe=None, meta=None, feed_per_step=False,
               **replay):
        """The dispatch happened.  ``call`` is ``(start, seconds)`` of its
        enqueue, ``t0`` the host clock where the time booked for it begins,
        ``probe`` / ``meta`` the compile-cache probe of a fresh entry,
        ``replay`` what a guarded one keeps for its dump bundle beside the
        state and the sentinel (feeds, feed_lods, fetch_names)."""
        from . import profiler as _prof

        window = self.n_steps is not None
        if _prof.is_profiling():
            what = f"{len(self.plan.ops)}ops" + (
                f" x{self.n_steps}steps" if window else "") + (
                f" mesh={self.mesh}" if self.mesh else "")
            _prof.record_event(f"executor_run[{what}]", call[1],
                               start=call[0])
        count_dispatch(self.n_steps, self.mesh)
        if probe is not None:
            # first dispatch of a fresh entry = trace + compile; commit the
            # artifact (miss) / freshen it (hit) now that it exists
            probe.finish(call[1], self.program, meta=meta)
        check_nan_inf(new_state, self.plan.fetch_names, fetches)
        if self.g is not None and health is not None:
            replay.update(program=self.program, state=self.dump_state,
                          sentinel=self.sentinel, duration_s=call[1])
            if window:
                replay["window"] = {
                    "start": self.start, "n_steps": self.n_steps,
                    "feed_per_step": feed_per_step}
            self.g.defer(self.guard, self.start, health, replay)
        if not self.training:
            return
        from .. import observe
        from ..observe import memory as _obsmem
        from ..observe import watchdog as _watchdog

        last = self.start
        if window:
            # events emitted after the window (checkpoint commits, cache
            # probes) correlate to its LAST executed step, not its first
            last = self.start + self.n_steps - 1
            observe.note_step(last)
        # live-buffer ledger: scope residency + watermark (gauges,
        # high-water, watchdog feed); the per-step path is quiet: a
        # watermark EVENT a step would flood the stream, windows own the
        # event cadence
        _obsmem.note_scope_live(self.scope, scope_label="train",
                                mesh=self.mesh, step=last,
                                emit_event=window)
        elapsed = time.perf_counter() - t0
        # SLO watchdog: per-step time of this dispatch (no-op unless
        # PADDLE_SLO is armed); async dispatch means the per-step path
        # measures submit-to-submit pacing, which is what regresses under
        # load
        _watchdog.observe_value(
            "executor.step_time_s", elapsed / max(1, self.n_steps or 1),
            step=last, **({"mesh": self.mesh} if self.mesh else {}))
        book_time(elapsed, fresh, compile_s, self.mesh)


def commit(scope, new_state, lod_box=None, faults=True):
    """Write a dispatch's new state back to the scope, with the lods its
    trace recorded.  ``faults``: the NaN-poison oracle rewrites the armed
    var on its step; a path with no step boundary has no fault hooks."""
    if faults:
        from . import fault as _fault

        if _fault.active() is not None:
            new_state = _fault.corrupt_state(new_state)
    for name, val in new_state.items():
        scope.set(name, val)
        if lod_box and name in lod_box:
            scope._lods[name] = lod_box[name]
    return new_state


def count_dispatch(n_steps=None, mesh=None):
    """The always-on counters of one dispatch (the smoke oracle counts
    dispatches; ``window_steps`` tracks amortization), once more under its
    mesh label for a sharded one."""
    from .. import observe

    reg = observe.registry()
    counts = [("executor.dispatches", 1)]
    if n_steps is not None:
        counts += [("executor.windows", 1),
                   ("executor.window_steps", n_steps)]
    for name, inc in counts:
        reg.inc(name, inc)
        if mesh:
            reg.inc(name, inc, labels={"mesh": mesh})


def book_time(elapsed, fresh=False, compile_s=None, mesh=None):
    """Goodput ledger: of a training dispatch's ``elapsed`` seconds, a
    fresh entry's trace + compile (``compile_s``; with lazy jit, None, the
    whole first dispatch) is compile cost, everything else device
    compute."""
    from ..observe import goodput as _goodput

    comp = 0.0
    if fresh:
        comp = elapsed if compile_s is None else compile_s
    if comp > 0.0:
        _goodput.note("compile", comp, mesh=mesh)
    if elapsed > comp:
        _goodput.note("device", elapsed - comp, mesh=mesh)
