"""Executor: runs a Program on a Place by tracing it into one XLA computation.

The reference's Executor is a per-op C++ interpreter (ref: executor.cc:129,
hot loop :354 ``for op in ctx->ops_: op->Run(scope, place)``) — every op is a
separate kernel launch.  On TPU that model wastes the machine: the idiomatic
design is to trace the *whole block* into a single jitted function
(feed, state) -> (fetches, new_state) and let XLA fuse/schedule it.  The Scope
survives as the host-side name->buffer table holding persistable state
(parameters, optimizer accumulators, RNG key) between runs.

Mutation semantics (SURVEY.md hard part #2): Fluid ops mutate scope vars in
place (sgd writes ParamOut into the Param var).  Tracing SSA-ifies this by
rebinding names in a trace-time environment; vars that were read from the
scope and rewritten become donated inputs / fresh outputs of the XLA program,
so XLA can alias their buffers (true in-place update on TPU HBM).
"""

from __future__ import annotations

import contextlib
from collections import OrderedDict
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import core
from . import step as _step
from .framework import (NAME_SCOPE_ATTR, NAME_SCOPE_MARK, OpRole, Program,
                        RNG_STATE_VAR, Variable, default_main_program)
from .step import BlockPlan  # noqa: F401  (planned there, imported from here)
from ..ops import registry as _reg

# ---------------------------------------------------------------------------
# Scope (ref: scope.h:41 — hierarchical name->Variable map)
# ---------------------------------------------------------------------------


class _ScopeTensor:
    """Minimal LoDTensor-view over a scope entry, for API parity
    (supports np.array(t), t.set(arr, place), t.shape)."""

    def __init__(self, scope, name):
        self._scope = scope
        self._name = name

    def __array__(self, dtype=None):
        v = self._scope._values[self._name]
        if v is _UNINIT:
            raise ValueError(
                f"Variable '{self._name}' exists in the scope but holds no "
                f"tensor yet (created via Scope.var but never set — the "
                f"reference faults the same way on an uninitialized var)")
        a = np.asarray(v)
        return a.astype(dtype) if dtype is not None else a

    def set(self, array, place=None):
        self._scope._values[self._name] = np.asarray(array)

    @property
    def shape(self):
        v = self._scope._values[self._name]
        if v is _UNINIT:
            raise ValueError(
                f"Variable '{self._name}' holds no tensor yet")
        return tuple(v.shape)

    def recursive_sequence_lengths(self):
        # scope._lods stores offsets form; convert at the API surface
        from .lod_tensor import _offsets_to_lengths

        off = self._scope._lods.get(self._name) or ()
        return [_offsets_to_lengths(level) for level in off]

    def set_recursive_sequence_lengths(self, lengths):
        from .lod_tensor import _lengths_to_offsets

        self._scope._lods[self._name] = tuple(
            _lengths_to_offsets(l) for l in lengths)

    def lod(self):
        return self._scope._lods.get(self._name) or ()

    def set_lod(self, lod):
        self._scope._lods[self._name] = tuple(
            tuple(int(x) for x in level) for level in lod)


class _ScopeVar:
    def __init__(self, scope, name):
        self._scope = scope
        self._name = name

    def get_tensor(self):
        return _ScopeTensor(self._scope, self._name)


class Scope:
    """name -> value table; values are host numpy or device jax arrays."""

    def __init__(self, parent: Optional["Scope"] = None):
        self._values: Dict[str, object] = {}
        self._lods: Dict[str, list] = {}
        self._parent = parent
        self._kids: List[Scope] = []

    def var(self, name) -> _ScopeVar:
        # creation API (ref scope.h Scope::Var creates an UNINITIALIZED
        # Variable): the slot exists but reads fault until set() — a
        # misspelled var name must not silently read zeros
        if name not in self._values:
            self._values[name] = _UNINIT
        return _ScopeVar(self, name)

    def find_var(self, name) -> Optional[_ScopeVar]:
        s = self
        while s is not None:
            if name in s._values:
                return _ScopeVar(s, name)
            s = s._parent
        return None

    def new_scope(self) -> "Scope":
        k = Scope(self)
        self._kids.append(k)
        return k

    def drop_kids(self):
        self._kids.clear()

    # -- internal fast path --
    def get(self, name, default=None):
        s = self
        while s is not None:
            if name in s._values:
                v = s._values[name]
                return default if v is _UNINIT else v
            s = s._parent
        return default

    def set(self, name, value):
        self._values[name] = value

    def has(self, name) -> bool:
        return self.get(name, _MISSING) is not _MISSING

    def keys(self):
        return self._values.keys()


_MISSING = object()
_UNINIT = object()
_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


def scope_guard(scope):
    import contextlib

    @contextlib.contextmanager
    def _guard():
        global _global_scope
        old = _global_scope
        _global_scope = scope
        try:
            yield
        finally:
            _global_scope = old

    return _guard()


# ---------------------------------------------------------------------------
# Block tracing
# ---------------------------------------------------------------------------



def build_window_fn(program: Program, plan: "BlockPlan", guard, n_user: int,
                    n_steps: int, feed_per_step: bool,
                    trace=None, finalize=None, gauges="run_steps"):
    """Build the fused-window step function ``kfn(feed_vals, const_state,
    mut_state, sentinel)`` — a ``lax.scan`` over the traced step with the
    mutable state (plus, when guarded, the aggregated health record) riding
    the carry.  Shared by ``Executor.run_steps`` (single device) and the
    SPMD window runner (``parallel.spmd.ShardedWindowRunner``), so the
    sharded path scans the EXACT same body the single-device oracle tests
    pin down.

    ``trace(feed, state, gauges)`` overrides the default ``trace_block``
    call (the sharded runner wraps it in a ``mesh_scope``); ``finalize(last,
    mut_final, agg)`` post-processes the outputs inside the trace (the
    sharded runner pins shardings there; ``agg`` is None unguarded).  A
    window carries no vector of step gauges out of its scan: what its ops
    publish is counted once a lowering as
    ``observe.step_gauges.dropped{path=gauges}``.
    """
    import jax.numpy as _jnp
    from jax import lax as _lax

    from . import guardian as _guardian
    from ..observe.gauges import Collector

    if trace is None:
        def trace(feed_vals, state_vals, gauges):
            return trace_block(program, 0, plan, feed_vals, state_vals,
                               gauges=gauges)
    if finalize is None:
        def finalize(last, mut_final, agg):
            return last, mut_final, agg

    def kfn(feed_vals, const_state, mut_state, sentinel):
        dropping = Collector(drop=gauges)

        def body(carry, xs):
            if guard is not None:
                mut, _prev_fetch, agg = carry
            else:
                mut, _prev_fetch = carry
            step_feed = dict(xs["feed"] if feed_per_step
                             else feed_vals)
            state = dict(const_state)
            state.update(mut)
            if guard is not None:
                step_sent = {"loss_cap": sentinel["loss_cap"],
                             "seed_mul": xs["seed_mul"],
                             "loss_mul": xs["loss_mul"]}
                step_feed[_guardian.LOSS_SEED_MUL] = \
                    _guardian.seed_multiplier(guard, state, step_sent)
            fetches, new_state = trace(step_feed, state, dropping)
            # fetches ride the carry: only the LAST step's values
            # survive, with no (n_steps, ...) stacking buffer
            if guard is not None:
                committed, health = _guardian.fold_health(
                    guard, fetches[n_user:], new_state, mut, state,
                    step_sent)
                agg = _guardian.window_health_update(
                    agg, health, xs["i"], n_steps)
                return ({**mut, **committed}, fetches[:n_user],
                        agg), None
            return ({**mut, **new_state}, fetches), None

        first_feed = (
            {k: v[0] for k, v in feed_vals.items()}
            if feed_per_step else feed_vals)
        fetch0, state0 = jax.eval_shape(
            lambda st: trace(first_feed, {**const_state, **st}, dropping),
            mut_state)
        fetch0 = [_jnp.zeros(t.shape, t.dtype)
                  for t in fetch0[:n_user]]
        # write-only persistables (written before first read, e.g.
        # a decayed lr var) appear in new_state but not in
        # _gather_state's mut_state — seed them so the carry
        # structure is stable across scan iterations
        mut_state = dict(mut_state)
        for k, t in state0.items():
            if k not in mut_state:
                mut_state[k] = _jnp.zeros(t.shape, t.dtype)
        xs = {"i": _jnp.arange(n_steps, dtype=_jnp.int32)}
        if feed_per_step:
            xs["feed"] = feed_vals
        if guard is not None:
            xs["seed_mul"] = sentinel["seed_mul"]
            xs["loss_mul"] = sentinel["loss_mul"]
            carry0 = (mut_state, fetch0,
                      _guardian.window_health_init(n_steps))
            (mut_final, last, agg), _ = _lax.scan(
                body, carry0, xs, length=n_steps)
            last, mut_final, agg = finalize(last, mut_final, agg)
            return last, mut_final, agg
        (mut_final, last), _ = _lax.scan(
            body, (mut_state, fetch0), xs, length=n_steps)
        last, mut_final, _ = finalize(last, mut_final, None)
        return last, mut_final

    return kfn


LOD_SUFFIX = "@LOD"


def trace_block(program: Program, block_idx: int, plan: BlockPlan,
                feed_vals: Dict[str, jnp.ndarray],
                state_vals: Dict[str, jnp.ndarray],
                static_env: Optional[Dict[str, object]] = None,
                lod_box: Optional[Dict[str, object]] = None,
                gauges=None):
    """Run every op in the block symbolically; returns (fetches, new_state).

    ``static_env`` carries compile-time-constant entries — notably
    ``<name>@LOD`` sequence metadata (tuples of offset tuples).  LoD is
    *static* in this framework (SURVEY.md §5.7: the TPU answer to variable
    length is bucketing + segment ids, not dynamic shapes): packed sequence
    data keeps a static [sum_len, ...] shape and the offsets are baked into
    the trace, so XLA sees fully static programs.  ``lod_box``, if given,
    receives the lod of every fetch/state name produced by the trace.

    ``gauges`` is the ``observe.gauges.Collector`` of the step's device
    gauges, which the caller made and finishes (one with ``drop`` set only
    counts what a path that carries no vector leaves behind).  It is open
    while a FORWARD op of THIS block runs and nowhere else: ``run_op`` hands
    it on to nothing.
    """
    env: Dict[str, object] = {}
    if static_env:
        env.update(static_env)
    env.update(state_vals)
    env.update(feed_vals)
    rng_box = None
    if plan.needs_rng:
        rng_box = [state_vals[RNG_STATE_VAR]]
    for op in plan.ops:
        run_op(op, env, rng_box, gauges)
    return _block_outputs(plan, env, rng_box, lod_box)


def _block_outputs(plan, env, rng_box, lod_box):
    """``(fetches, new_state)`` out of the environment a block ran in."""
    fetches = [env[n] for n in plan.fetch_names]
    new_state = {n: env[n] for n in plan.state_out if n in env}
    if rng_box is not None:
        new_state[RNG_STATE_VAR] = rng_box[0]
    if lod_box is not None:
        for n in list(plan.fetch_names) + list(plan.state_out):
            lod = env.get(n + LOD_SUFFIX)
            if lod is not None:
                lod_box[n] = lod
    return fetches, new_state


def run_op(op, env: Dict[str, object], rng_box=None, gauges=None):
    """Execute one IR op against a trace environment.  ``gauges``: the
    step's collector of device gauges (``trace_block``), open around a
    forward op's function only: a grad op traces its forward a second time
    under ``jax.vjp`` and a sub-block runs inside a loop's trace, and a
    value published there would be an inner trace's tracer."""
    from . import control_flow_exec

    if op.type in control_flow_exec.HANDLERS:
        control_flow_exec.HANDLERS[op.type](op, env, rng_box, run_op)
        return

    is_grad = (not _reg.is_registered(op.type)) and op.type.endswith("_grad") \
        and _reg.is_registered(op.type[:-5])
    opdef = _reg.get_op_def(op.type[:-5] if is_grad else op.type)

    inputs = {}
    for slot, names in op.inputs.items():
        inputs[slot] = [env.get(n) if n else None for n in names]
        # companion static LoD entries (sequence metadata; see trace_block)
        lods = [env.get(n + LOD_SUFFIX) if n else None for n in names]
        if any(l is not None for l in lods):
            inputs[slot + LOD_SUFFIX] = lods
    # current values of in-out outputs (tensor arrays accumulate)
    for slot, names in op.outputs.items():
        cur = [env.get(n) if n else None for n in names]
        if any(c is not None for c in cur):
            inputs[slot + "@CURRENT"] = cur

    # host inputs (loop counters, array indices) mutate in place between
    # forward and backward; forward ops stash theirs so the matching grad op
    # (linked via __fwd_op_idx__, see backward.py) replays the values it
    # actually saw
    if is_grad:
        fwd_idx = op.attr("__fwd_op_idx__")
        if fwd_idx is not None and fwd_idx < len(op.block.ops):
            stash = env.get("@FWD_HOST@", {}).get(
                id(op.block.ops[fwd_idx]))
            if stash:
                inputs.update(stash)
    else:
        host_slots = {
            slot: vals for slot, vals in inputs.items()
            if not slot.endswith(LOD_SUFFIX)
            and any(isinstance(v, np.ndarray) for v in vals)}
        if host_slots:
            env.setdefault("@FWD_HOST@", {})[id(op)] = {
                s: list(v) for s, v in host_slots.items()}
    outputs_spec = {slot: list(names) for slot, names in op.outputs.items() if names}
    ctx = _reg.ExecContext(op.type, inputs, outputs_spec, op.attrs, rng_box)

    # the scope names land in XLA HLO metadata
    # (op_name="jit(..)/<type>/~<name scope>/..") so device profiles
    # attribute per-HLO-op time back to framework ops and, beneath them,
    # to the model's blocks (ref: platform/device_tracer.h:49
    # correlation_id -> op role; here the correlation is carried by the
    # compiler instead of CUPTI ids).  The op type stays FIRST; the
    # fluid.name_scope path is ONE segment behind NAME_SCOPE_MARK, which
    # no segment jax makes starts with.  Metadata only, made at trace time.
    path = op.attrs.get(NAME_SCOPE_ATTR)
    with jax.named_scope(op.type), \
            jax.named_scope(NAME_SCOPE_MARK + path) if path \
            else contextlib.nullcontext():
        if is_grad:
            if opdef.grad_fn is not None:
                raw = opdef.grad_fn(ctx)
            else:
                raw = _reg.run_grad_generic(opdef, ctx)
        elif gauges is not None:
            with gauges.op(path):
                raw = opdef.fn(ctx)
        else:
            raw = opdef.fn(ctx)

    # split off "<slot>@LOD" returns (each a list of lods parallel to the
    # slot's output names) before array normalization
    out_lods = {}
    if raw:
        for k in [k for k in raw if k.endswith(LOD_SUFFIX)]:
            v = raw.pop(k)
            out_lods[k[: -len(LOD_SUFFIX)]] = v if isinstance(v, list) else [v]
    outs = _reg._normalize_outputs(raw)

    # default ShareLoD (ref: ops declare ShareLoD in InferShape; here a
    # guarded heuristic): a unique input lod propagates to any output whose
    # leading dim still equals the packed row count
    share_lod = None
    in_lods = {tuple(map(tuple, l))
               for k, ls in inputs.items() if k.endswith(LOD_SUFFIX)
               for l in ls if l is not None}
    if len(in_lods) == 1:
        share_lod = next(iter(in_lods))

    for slot, names in op.outputs.items():
        vals = outs.get(slot)
        lods = out_lods.get(slot)
        for i, name in enumerate(names):
            if not name:
                continue
            if vals is not None and i < len(vals) and vals[i] is not None:
                env[name] = vals[i]
                # rebinding a var invalidates any previous LoD; it is
                # re-attached below only if this op declares/shares one
                env.pop(name + LOD_SUFFIX, None)
                if (lods is None or i >= len(lods)) and share_lod is not None \
                        and getattr(vals[i], "shape", None) \
                        and vals[i].shape[0] == share_lod[-1][-1]:
                    env[name + LOD_SUFFIX] = share_lod
            if lods is not None and i < len(lods) and lods[i] is not None:
                env[name + LOD_SUFFIX] = tuple(tuple(l) for l in lods[i])

    # backward-seed scaling (dynamic fp16 loss scale and/or the guardian's
    # grad-Inf fault injection): the op append_backward tagged __loss_seed__
    # has its output multiplied by the traced @LOSS_SEED_MUL@ scalar the
    # guarded step placed in the env.  One dict lookup for every other op.
    if "__loss_seed__" in op.attrs:
        mul = env.get(_guardian_mod().LOSS_SEED_MUL)
        if mul is not None:
            for names in op.outputs.values():
                for n in names:
                    if n and n in env:
                        env[n] = env[n] * jnp.asarray(mul, env[n].dtype)


def _guardian_mod():
    from . import guardian

    return guardian


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------


class _JitCache:
    """Bounded in-process jit cache (LRU by last use).

    The old dict grew without bound across programs — a long-lived process
    cycling many Programs (serving several models, notebooks, the test
    suite) pinned every compiled executable plus its donated-buffer
    metadata forever.  ``PADDLE_EXECUTOR_CACHE_CAP`` bounds it (default
    64 entries, comfortably above any serving bucket set); size and
    evictions surface as always-on profiler counters."""

    def __init__(self, cap: Optional[int] = None):
        if cap is None:
            from . import envcontract

            cap = envcontract.get("PADDLE_EXECUTOR_CACHE_CAP")
        self.cap = max(1, int(cap))
        self.evictions = 0
        self._od: "OrderedDict[tuple, tuple]" = OrderedDict()

    def get(self, key):
        entry = self._od.get(key)
        if entry is not None:
            self._od.move_to_end(key)
        return entry

    def __setitem__(self, key, entry):
        from . import profiler as _prof

        self._od[key] = entry
        self._od.move_to_end(key)
        while len(self._od) > self.cap:
            self._od.popitem(last=False)
            self.evictions += 1
            _prof.record_counter("executor.jit_cache.evictions")
        _prof.record_counter("executor.jit_cache.size",
                             value=len(self._od))

    def __len__(self):
        return len(self._od)

    def __contains__(self, key):
        return key in self._od

    def clear(self):
        self._od.clear()


class Executor:
    """ref: python/paddle/fluid/executor.py:256.  ``place`` selects the JAX
    device; everything else is handled by XLA."""

    def __init__(self, place=None):
        self.place = place if place is not None else core.CPUPlace()
        self._cache = _JitCache()
        # feed-name -> (host snapshot, device buffer): unchanged feeds are
        # NOT re-shipped every step, so a loop that feeds the same batch
        # again pays the H2D copy once.
        self._feed_cache = {}

    def close(self):
        self._cache.clear()
        self._feed_cache.clear()

    def run_steps(self, program, feed, fetch_list, n_steps,
                  scope=None, feed_per_step=False):
        """Run ``n_steps`` training steps inside ONE device dispatch.

        A ``lax.scan`` over the traced step with the mutable state as the
        (donated) carry — the standard TPU host-loop amortization: per-step
        dispatch latency vanishes, parameters never leave the device, and
        XLA pipelines step k+1's compute behind step k (the analogue of
        the reference's `--use_reader_op` in-graph data loop, ref
        benchmark/fluid/fluid_benchmark.py:149 + read op).

        ``feed_per_step=False``: every step consumes the same feed dict
        (synthetic-data benchmarking, ref --use_fake_data).
        ``feed_per_step=True``: each feed array carries a leading
        ``n_steps`` dim and step i consumes slice i.

        Guardian-gated and dynamic-fp16-loss-scaled programs scan too: the
        per-step sentinel (health reduction + ``where(ok)`` commit gate)
        and the loss-scale update ride the carry, and the host observes ONE
        aggregated health record per window (first-trip step index + worst
        values) with the usual one-boundary lag — policy applies at window
        granularity, and a dump bundle captures the PRE-WINDOW state so
        replay reproduces the trip (guardian.replay walks the window).

        Returns the fetches of the LAST step (host numpy).  Programs with
        data-dependent eager islands cannot be scanned and raise.
        """
        import time as _time

        from . import guardian as _guardian
        from ..observe import trace as _trace

        program = program or default_main_program()
        scope = scope or global_scope()
        n_steps = int(n_steps)
        fetch_names = [f.name if isinstance(f, Variable) else str(f)
                       for f in fetch_list or []]
        feed_arrays, feed_lods = _step.coerce_feeds(program, feed)
        if feed_lods:
            raise RuntimeError(
                "run_steps: LoD feeds are not supported in the "
                "scanned loop; use Executor.run per step")
        # guarded window: sentinel + dynamic loss scale fold into the scan
        # body exactly like Executor.run's single guarded step
        guard = _guardian.for_program(program)
        key, extra = _step.signature(
            "run_steps", program, fetch_names, feed_arrays, guard,
            n_steps=n_steps, feed_per_step=bool(feed_per_step),
            platform=self.place.device_type)
        entry = self._cache.get(key)
        probe = None
        fresh_entry = entry is None
        if entry is None:
            from ..observe import goodput as _goodput

            t_trace0 = _time.perf_counter()
            with _trace.span("executor.trace", n_steps=n_steps):
                entry, probe = self._build_entry(
                    "run_steps", program, feed_arrays, fetch_names, guard,
                    extra,
                    lambda plan, guard: jax.jit(
                        build_window_fn(program, plan, guard,
                                        len(fetch_names), n_steps,
                                        feed_per_step),
                        donate_argnums=_step.donate_argnums(program)),
                    verify_feed=_step.one_step_feed(feed_arrays,
                                                    feed_per_step),
                    eager_error="run_steps: program contains data-dependent "
                                "eager ops; use Executor.run per step")
                self._cache[key] = entry
            if program._params_grads is not None:
                # host tracing/verification is compile-state wall-clock
                # (the backend compile itself lands in the first dispatch,
                # booked below)
                _goodput.note("compile", _time.perf_counter() - t_trace0)
        plan, fn, guard = entry

        # the window span wraps the WHOLE dispatch cycle, so guardian
        # trips / cache probes / slo breaches emitted inside it carry its
        # span id; its children are stamped as they happen, and none of
        # them waits for the device: the window's wait is the host copy
        # of the fetches at its end
        with _trace.span("executor.window", n_steps=n_steps,
                         fresh=fresh_entry):
            t_host0 = _time.perf_counter()
            d = _step.Dispatch(program, scope, plan, guard, n_steps)
            t_stage0 = _time.perf_counter()
            with _trace.span("executor.stage"):
                const_state, mut_state = d.split(
                    self._gather_state(program, plan, scope))
                device = core.get_jax_device(self.place)
                feed_dev = {k: self._put_feed(k, v, device)
                            for k, v in feed_arrays.items()}
            t_stage1 = t = _time.perf_counter()
            # `compile`: the goodput ledger books a fresh entry's first
            # dispatch (lazy jit: trace + compile on the host) as compile
            with _trace.span("executor.dispatch", compile=fresh_entry):
                fetches, new_state, agg = d.call(fn, feed_dev, const_state,
                                                 mut_state)
            t_disp1 = _time.perf_counter()
            with _trace.span("executor.observe"):
                new_state = _step.commit(scope, new_state)
                d.report(fetches, new_state, agg, t_host0, (t, t_disp1 - t),
                         fresh_entry, compile_s=t_disp1 - t, probe=probe,
                         meta={"kind": "run_steps", "n_steps": n_steps},
                         feeds=feed_arrays, feed_lods={},
                         fetch_names=fetch_names,
                         feed_per_step=bool(feed_per_step))
            t_obs1 = _time.perf_counter()
            # the host breakdown of this window (host_ms = everything not
            # in the other three); dispatch_ms is the ENQUEUE, plus trace
            # and compile on a fresh entry
            _trace.note_window_breakdown(
                host_ms=(t_stage0 - t_host0) * 1e3,
                stage_ms=(t_stage1 - t_stage0) * 1e3,
                dispatch_ms=(t_disp1 - t) * 1e3,
                observe_ms=(t_obs1 - t_disp1) * 1e3)
            return [np.asarray(v) for v in fetches]

    def run(self, program=None, feed=None, fetch_list=None, feed_var_name="feed",
            fetch_var_name="fetch", scope=None, return_numpy=True,
            use_program_cache=True):
        from ..observe import trace as _trace

        # one root span per step, the same six children under
        # ParallelExecutor.run: where the host time of a step goes
        # (docs/OBSERVABILITY.md section 7).  None of them waits for the
        # device; the one wait, the host copy that return_numpy asks for,
        # is `fluid.run.fetch`, last.
        with _trace.span("fluid.run", entry="executor") as root:
            return self._run(root, program or default_main_program(),
                             dict(feed or {}), fetch_list or [],
                             scope or global_scope(), return_numpy,
                             use_program_cache)

    def _run(self, root, program, feed, fetch_list, scope, return_numpy,
             use_program_cache):
        import time as _time

        from . import guardian as _guardian
        from ..observe import trace as _trace

        with _trace.span("fluid.run.feed"):
            # host infeed: pop one batch per `read` op from its reader
            # queue and make it this step's feed (ref: the C++ read op
            # pulls from LoDTensorBlockingQueue inside the executor loop)
            for op in program.global_block().ops:
                if op.type != "read":
                    continue
                from .layers import io as _io
                from .lod_tensor import LoDTensor

                state = _io._reader_state(op.inputs["Reader"][0])
                batch = state.next_batch()  # raises core.EOFException
                for name, (arr, lod) in zip(op.outputs["Out"], batch):
                    feed[name] = LoDTensor(arr, lod) if lod else arr

            fetch_names = [f.name if isinstance(f, Variable) else str(f)
                           for f in fetch_list]
            feed_arrays, feed_lods = _step.coerce_feeds(program, feed)
            device = core.get_jax_device(self.place)
            feed_dev = {k: self._put_feed(k, v, device)
                        for k, v in feed_arrays.items()}

        with _trace.span("fluid.run.lookup"):
            program = self._prune_for_unfed(program, feed_arrays,
                                            fetch_names, scope)

            # lods recorded on persistable state vars by earlier runs
            # re-enter the trace as static metadata, exactly like feed lods
            state_lods = {n: lod for n, lod in scope._lods.items()
                          if lod
                          and program.global_block()._has_var_recursive(n)}

            # guarded training step: the numerics sentinel / dynamic loss
            # scaler fold a health reduction + conditional state commit
            # into the same jitted program (guardian.py module docstring)
            guard = _guardian.for_program(program)

            key, extra = _step.signature(
                "run", program, fetch_names, feed_arrays, guard,
                feed_lods=tuple(sorted(feed_lods.items())),
                state_lods=tuple(sorted(state_lods.items())),
                platform=self.place.device_type)
            entry = self._cache.get(key) if use_program_cache else None
            probe = None
            fresh = entry is None
            root.set(fresh=fresh)
            if fresh:
                with _trace.span("fluid.run.build"):
                    # filled by the step's trace: the lods, and the
                    # layout of its vector of step gauges (None: none)
                    lod_box, gauge_box = {}, [None]
                    entry, probe = self._build_entry(
                        "run", program, feed_arrays, fetch_names, guard,
                        extra,
                        lambda plan, guard: self._build(
                            program, plan, {**state_lods, **feed_lods},
                            lod_box, guard=guard, n_user=len(fetch_names),
                            gauge_box=gauge_box))
                    entry += (lod_box, gauge_box)
                if use_program_cache:
                    self._cache[key] = entry
            plan, fn, guard, lod_box, gauge_box = entry

        with _trace.span("fluid.run.state"):
            d = _step.Dispatch(program, scope, plan, guard)
            root.set(step=d.start)
            const_state, mut_state = d.split(
                self._gather_state(program, plan, scope))

        t = _time.perf_counter()
        with _trace.span("fluid.run.call"):
            fetches, new_state, health = d.call(fn, feed_dev, const_state,
                                                mut_state)
        call_s = _time.perf_counter() - t

        with _trace.span("fluid.run.commit"):
            new_state = _step.commit(scope, new_state, lod_box)
            # the arrays the scope just let go of (donated, or replaced)
            # die with their last reference: here, in the span that
            # replaced them, not when this frame ends (0.9 ms a step for
            # the Transformer's 190 on the v5e's host)
            del mut_state, const_state
            out = fetches
            if not return_numpy:
                from .lod_tensor import LoDTensor

                # keep fetches device-resident: conversion happens lazily
                # on first numpy access, so a training loop that only
                # inspects the loss occasionally is not throttled by one
                # D2H sync per step.  A fetch that is ALSO a mutated state
                # var aliases a buffer the next run will donate: copy
                # those on device so the returned handle survives
                # (donation would otherwise delete it under the caller).
                out = []
                for n, v in zip(plan.fetch_names, fetches):
                    if n in d.mut_names and isinstance(v, jax.Array):
                        v = jnp.array(v, copy=True)
                    out.append(LoDTensor(v, lod_box.get(n)))

        with _trace.span("fluid.run.observe"):
            # the step's device gauges stay the device array they are
            d.keep_gauges(gauge_box[0], root.span_id, t)
            d.report(fetches, new_state, health, t, (t, call_s), fresh,
                     probe=probe,
                     meta={"kind": "run", "ops": len(plan.ops),
                           "fetches": len(plan.fetch_names)},
                     feeds=feed_arrays, feed_lods=feed_lods,
                     fetch_names=fetch_names)

        if return_numpy:
            with _trace.span("fluid.run.fetch"):
                out = [np.asarray(v) for v in fetches]
        return out

    def _build_entry(self, kind, program, feed_arrays, fetch_names, guard,
                     extra, build, verify_feed=None, eager_error=None):
        """The executor's cache missed: verify, consult the persistent
        compile cache, plan the block and ``build(plan, guard)`` the
        (lazily compiled) jit.  Returns ``(plan, fn, guard)`` and the
        compile-cache probe."""
        from .log import VLOG
        from .. import analysis as _analysis
        from .. import compile_cache as _cc

        # pre-compile verifier (PADDLE_TPU_VERIFY=warn|strict|off): named
        # diagnostics in milliseconds instead of an XLA trace error seconds
        # into compile; strict mode raises VerifyError here, before any
        # backend work
        _analysis.check_before_compile(
            program, feed=feed_arrays if verify_feed is None else verify_feed,
            fetch_list=fetch_names, kind=kind)
        # persistent-cache consult BEFORE tracing: a hit means another
        # process already compiled this exact (program, jit config) and the
        # backend executable loads from the shared jax disk cache
        probe = _cc.executor_probe(program, feed_arrays, fetch_names,
                                   extra=extra)
        VLOG(1, f"Executor.{kind}: compiling block "
                f"({len(program.global_block().ops)} ops, "
                f"fetches={fetch_names}"
                f"{', guarded' if guard is not None else ''})")
        plan, guard = _step.plan_step(program, feed_arrays, fetch_names,
                                      guard, eager_error)
        return (plan, build(plan, guard), guard), probe

    def lower_step(self, program, feed, fetch_list, scope=None):
        """AOT-lower the SAME traced step ``Executor.run`` would jit for
        one (program, feed) specialization, against the state the scope
        holds now; returns the jax ``Lowered`` stage (``as_text()`` for
        the program the backend is handed, ``compile()`` for its memory
        analysis).  None for programs with no single lowering: LoD feeds
        re-trace per lod, eager-island programs never trace whole."""
        scope = scope or global_scope()
        fetch_names = [f.name if isinstance(f, Variable) else str(f)
                       for f in fetch_list or []]
        feed_arrays, feed_lods = _step.coerce_feeds(program, feed)
        if feed_lods:
            return None
        program = self._prune_for_unfed(program, feed_arrays, fetch_names,
                                        scope)
        plan = BlockPlan(program, 0, list(feed_arrays), fetch_names)
        if plan.needs_eager:
            return None
        fn = self._build(program, plan)
        device = core.get_jax_device(self.place)

        def norm(v):
            # a scope that last committed a SHARDED run holds mesh
            # arrays; gather them so the step lowers single-device
            if isinstance(v, jax.Array) and len(v.devices()) > 1:
                v = np.asarray(v)
            return jax.device_put(jnp.asarray(v), device)

        state_vals = {k: norm(v) for k, v in
                      self._gather_state(program, plan, scope).items()}
        const_state, mut_state = _step.split_state(
            state_vals, _step.mutable_names(plan))
        feed_dev = {k: jax.device_put(jnp.asarray(v), device)
                    for k, v in feed_arrays.items()}
        return fn.lower(feed_dev, const_state, mut_state)

    def compiled_memory_stats(self, program, feed, fetch_list, scope=None):
        """Compiled-truth memory stats for one (program, feed)
        specialization: :meth:`lower_step` + compile, then the backend's
        ``memory_analysis()``.  Costs one backend compile (deduped by the
        persistent backend cache when enabled) — callers own that
        decision: ``ServingEngine.warmup`` (the precompile path by
        definition) and the memcheck cross-check tests.  Returns the
        ``observe.memory.memory_stats`` dict, or None (eager-island
        programs, backends without memory analysis)."""
        try:
            lowered = self.lower_step(program, feed, fetch_list, scope)
            if lowered is None:
                return None
            from ..observe import memory as _obsmem

            return _obsmem.memory_stats(lowered.compile())
        except Exception:
            return None

    # -- helpers --
    def _put_feed(self, name, arr, device):
        """H2D-transfer a feed value, skipping the copy when the bytes are
        identical to what this feed name already holds on device.

        Safety: a full host-side ``array_equal`` guards the hit (memcmp at
        host memory bandwidth — orders of magnitude cheaper than re-shipping
        over PCIe), so in-place mutation of a reused
        feed buffer is still detected and re-transferred.  Values that are
        already jax Arrays (e.g. pre-placed by the caller) pass through.
        """
        if isinstance(arr, jax.Array):
            if device in arr.devices():
                return arr
            return jax.device_put(arr, device)
        if device.platform == "cpu":
            # host device: device_put is (near) free; skip cache bookkeeping
            return jax.device_put(arr, device)
        ent = self._feed_cache.get(name)
        if ent is not None:
            snap, dev_arr, misses = ent
            if misses is None:
                # retired entry: snap records the (shape, dtype) that
                # retired it.  Same geometry keeps transferring (fresh
                # batches every step), but a geometry CHANGE — e.g. the
                # name switching from train batches to a fixed eval feed —
                # re-arms the cache instead of transferring forever
                if snap == (arr.shape, str(arr.dtype)):
                    return jax.device_put(arr, device)
                ent = None
            elif snap.shape == arr.shape and snap.dtype == arr.dtype \
                    and np.array_equal(snap, arr):
                ent[2] = 0
                return dev_arr
            elif misses + 1 >= 3:
                # fresh batch every step (the normal training loop): stop
                # paying the compare+snapshot tax and just transfer
                self._feed_cache[name] = [(arr.shape, str(arr.dtype)),
                                          None, None]
                return jax.device_put(arr, device)
        dev_arr = jax.device_put(arr, device)
        prev_misses = ent[2] if ent is not None else 0
        self._feed_cache[name] = [np.array(arr, copy=True), dev_arr,
                                  prev_misses + 1 if ent is not None else 0]
        return dev_arr

    def _build(self, program, plan, feed_lods=None, lod_box=None,
               guard=None, n_user=None, gauge_box=None):
        """The jitted step ``(feed, const_state, mut_state[, sentinel]) ->
        (fetches, new_state[, health][, gauges])``.  ``gauges`` is there
        only where the block's forward ops published step gauges
        (``observe.step_gauge``): one float32 vector, whose static layout
        the trace leaves in ``gauge_box`` as ``lod_box`` gets the lods.  A
        step that publishes nothing returns what it always did."""
        from ..observe.gauges import Collector

        donate = _step.donate_argnums(program)
        static_env = {k + LOD_SUFFIX: lod
                      for k, lod in (feed_lods or {}).items()}

        def traced(feed_vals, state):
            gauges = Collector()
            fetches, new_state = trace_block(
                program, 0, plan, feed_vals, state, static_env=static_env,
                lod_box=lod_box, gauges=gauges)
            vector, layout = gauges.finish()
            if gauge_box is not None:
                gauge_box[:] = [layout]
            return fetches, new_state, (() if vector is None else (vector,))

        if guard is not None:
            from . import guardian as _g

            def gfn(feed_vals, const_state, mut_state, sentinel):
                state = dict(const_state)
                state.update(mut_state)
                feed_vals = dict(feed_vals)
                # backward-seed multiplier (loss scale x fault injection),
                # consumed by the __loss_seed__-tagged op in run_op
                feed_vals[_g.LOSS_SEED_MUL] = _g.seed_multiplier(
                    guard, state, sentinel)
                fetches, new_state, gauges = traced(feed_vals, state)
                new_state, health = _g.fold_health(
                    guard, fetches[n_user:], new_state, mut_state, state,
                    sentinel)
                return (fetches[:n_user], new_state, health) + gauges

            return jax.jit(gfn, donate_argnums=donate)

        def fn(feed_vals, const_state, mut_state):
            state = dict(const_state)
            state.update(mut_state)
            fetches, new_state, gauges = traced(feed_vals, state)
            return (fetches, new_state) + gauges

        if plan.needs_eager:
            # programs with data-dependent ops (beam search, mask split):
            # eager-ISLAND execution — contiguous traceable runs compile as
            # cached jit segments, only the islands run op-by-op
            # (SURVEY.md §7 hard part #1/#2)
            return self._build_segmented(plan, static_env, lod_box)
        return jax.jit(fn, donate_argnums=donate)

    def _build_segmented(self, plan, static_env, lod_box):
        seg_cache: Dict[tuple, tuple] = {}

        def _classify(v):
            return "arr" if isinstance(v, jax.Array) else "host"

        def run_segments(feed_vals, const_state, mut_state):
            env: Dict[str, object] = {}
            env.update(static_env)
            env.update(const_state)
            env.update(mut_state)
            env.update(feed_vals)
            rng_box = [env[RNG_STATE_VAR]] if plan.needs_rng else None
            import time as _time

            from . import profiler as _prof

            def timed(label, fn, *args):
                if not _prof.is_profiling():
                    return fn(*args)
                t = _time.perf_counter()
                fn(*args)
                _prof.record_event(label, _time.perf_counter() - t, start=t)

            for si, (kind, ops) in enumerate(plan.segments):
                if kind == "eager":
                    for op in ops:
                        timed(f"eager:{op.type}", run_op, op, env, rng_box)
                else:
                    timed(f"jit_segment[{si}:{len(ops)}ops]",
                          self._run_jit_segment, si, ops, env, rng_box,
                          seg_cache)
            return _block_outputs(plan, env, rng_box, lod_box)

        return run_segments

    def _run_jit_segment(self, si, ops, env, rng_box, seg_cache):
        """Run one traceable segment through a cached jitted function.

        Device (jax) values in the env become traced arguments; host values
        (numpy counters, LoD tuples, forward-host stashes) are trace-time
        constants keyed into the cache, so a host change retraces while the
        steady state (e.g. the encoder prefix of a decode program) reuses
        one compiled executable.  Host values PRODUCED at trace time are
        replayed from the cache — they are deterministic functions of the
        host inputs."""
        import hashlib

        from ..ops.array_ops import TensorArray

        def _is_traceable(v):
            if isinstance(v, jax.Array):
                return True
            if isinstance(v, TensorArray):
                return any(isinstance(x, (jax.Array, jax.core.Tracer))
                           for x in v.vals if x is not None)
            return False

        arr_in: Dict[str, object] = {}
        host_env: Dict[str, object] = {}
        for name, val in env.items():
            if _is_traceable(val):
                arr_in[name] = val
            else:
                host_env[name] = val

        from ..ops.array_ops import RankTable

        def _host_key(v):
            if isinstance(v, np.ndarray):
                return (v.shape, str(v.dtype),
                        hashlib.blake2b(v.tobytes(), digest_size=8).hexdigest())
            if isinstance(v, dict):
                return tuple(sorted((str(k), _host_key(x))
                                    for k, x in v.items()))
            if isinstance(v, (list, tuple)):
                return tuple(_host_key(x) for x in v)
            if isinstance(v, RankTable):
                return ("ranktable", tuple(map(tuple, v.items)))
            if isinstance(v, TensorArray):  # host-valued array
                return ("ta", tuple(_host_key(x) for x in v.vals),
                        _host_key(v.lods))
            if v is None or isinstance(v, (bool, int, float, str, bytes)):
                return v
            # unknown host object: key by content so equal values hit the
            # cache and changed values retrace (identity keying would either
            # never hit or replay stale trace-time constants)
            import pickle

            try:
                return ("pickled", hashlib.blake2b(
                    pickle.dumps(v), digest_size=8).hexdigest())
            except Exception:
                return ("id", id(v))

        def _arr_sig(v):
            if isinstance(v, jax.Array):
                return (tuple(v.shape), str(v.dtype))
            # TensorArray: per-element shape signature
            return tuple((tuple(x.shape), str(x.dtype)) if x is not None
                         else None for x in v.vals)

        # '@'-prefixed entries (forward-host stashes) ARE part of the key:
        # they get baked into the trace as constants, so a changed stash
        # must miss the cache, not silently replay into grad ops
        key = (si,
               tuple(sorted((n, _arr_sig(v)) for n, v in arr_in.items())),
               _host_key(host_env))
        entry = seg_cache.get(key)
        if entry is None:
            side = {}
            captured_host = dict(host_env)

            def traced(arrs, rng_key):
                env2: Dict[str, object] = dict(captured_host)
                env2.update(arrs)
                before = {n: id(v) for n, v in env2.items()}
                box = [rng_key] if rng_key is not None else None
                for op in ops:
                    run_op(op, env2, box)
                from ..ops.array_ops import TensorArray as _TA

                arr_out, host_out = {}, {}
                for n, v in env2.items():
                    if before.get(n) == id(v):
                        continue
                    if isinstance(v, (jax.Array, jax.core.Tracer, _TA)):
                        arr_out[n] = v
                    else:
                        host_out[n] = v
                side["host"] = host_out
                return arr_out, (box[0] if box is not None else None)

            jitted = jax.jit(traced)
            entry = (jitted, side)
            seg_cache[key] = entry
        jitted, side = entry
        arr_out, new_key = jitted(arr_in, rng_box[0] if rng_box else None)
        env.update(arr_out)
        env.update(side.get("host", {}))
        if rng_box is not None and new_key is not None:
            rng_box[0] = new_key

    def _prune_for_unfed(self, program, feed_arrays, fetch_names, scope):
        """Reference executors run whole mixed programs and tolerate
        unfed data vars in NON-fetched branches (book decode_main reuses
        the train program's default main; the C++ ops just see empty
        tensors).  The static-shape equivalent: when an unfed data var
        exists, prune to the fetch targets — dropping backward/optimize
        ops like the reference's pruning (prune.cc honors op roles) so a
        kept decode branch does not drag the train branch back in via
        shared parameters.  If the unfed var is still needed after
        pruning, keep the original program so the clear 'was not fed'
        error fires."""
        if not fetch_names:
            return program
        gb = program.global_block()
        # cheap first: the (small) set of declared-but-unfed data vars
        candidates = [v.name for v in gb.vars.values()
                      if getattr(v, "is_data", False)
                      and v.name not in feed_arrays
                      and scope.get(v.name, None) is None]
        if not candidates:
            return program
        consumed = set()
        for op in gb.ops:
            consumed.update(op.input_arg_names)
        unfed = sorted(n for n in candidates if n in consumed)
        if not unfed:
            return program
        # cache holds ONE version's entries; a program mutation replaces
        # it wholesale (each entry pins a full clone)
        cache_ver, cache = getattr(program, "_unfed_prune_cache",
                                   (None, None))
        if cache_ver != program._version:
            cache = {}
            program._unfed_prune_cache = (program._version, cache)
        key = (tuple(fetch_names), tuple(unfed))
        pruned = cache.get(key)
        if pruned is None:
            pruned = self._try_prunes(program, fetch_names, unfed, scope,
                                      feed_arrays)
            cache[key] = pruned
        return pruned

    @staticmethod
    def _try_prunes(program, fetch_names, unfed, scope, feed_arrays):
        """Two attempts, most-conservative first:

        A. liveness slice keeping persistable-writers (BlockPlan's rule)
           — a TRAIN fetch keeps its optimizer while an unrelated unfed
           decode branch drops away;
        B. role-dropping slice (no backward/optimize, the reference's
           inference pruning) — a DECODE fetch sheds the whole train
           branch that shares its parameters.

        Adopt an attempt only if it clears every unfed var AND still
        produces all fetches; else the original program keeps the clear
        'was not fed' error."""

        def _viable(p):
            produced, consumed = set(), set()
            for op in p.global_block().ops:
                produced.update(op.output_arg_names)
                consumed.update(op.input_arg_names)
            if any(n in consumed for n in unfed):
                return False
            for f in fetch_names:
                if f not in produced and f not in feed_arrays \
                        and scope.get(f, None) is None:
                    return False
            return True

        # attempt A: keep persistable-writers
        a = program.clone()
        gb = a.global_block()

        def _writes_persistable(op):
            return any(gb._has_var_recursive(n)
                       and gb._var_recursive(n).persistable
                       for n in op.output_arg_names)

        needed = set(fetch_names)
        kept = []
        for op in reversed(gb.ops):
            if any(n in needed for n in op.output_arg_names) \
                    or _writes_persistable(op):
                kept.append(op)
                needed.update(op.input_arg_names)
        gb.ops = list(reversed(kept))
        if _viable(a):
            return a

        # attempt B: drop backward/optimize like inference pruning
        b = program._prune(fetch_names,
                           drop_roles=(OpRole.Backward, OpRole.Optimize))
        if _viable(b):
            return b
        return program  # pruning cannot help; keep the error

    def _gather_state(self, program, plan, scope):
        return {k: v if isinstance(v, jax.Array) else jnp.asarray(v)
                for k, v in _step.gather_state(program, plan,
                                               scope).items()}
