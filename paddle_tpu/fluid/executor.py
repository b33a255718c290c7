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

import os
import warnings
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import core
from .framework import (OpRole, Program, RNG_STATE_VAR, Variable,
                        default_main_program)
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


_SIDE_EFFECT_OPS = frozenset(["print", "save", "save_combine"])


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


def _resolve_opdef(op_type):
    if _reg.is_registered(op_type):
        return _reg.get_op_def(op_type)
    if op_type.endswith("_grad") and _reg.is_registered(op_type[:-5]):
        return _reg.get_op_def(op_type[:-5])
    return None


_SKIP_OPS = frozenset(["feed", "fetch", "read", "create_py_reader"])


def build_window_fn(program: Program, plan: "BlockPlan", guard, n_user: int,
                    n_steps: int, feed_per_step: bool,
                    trace=None, finalize=None):
    """Build the fused-window step function ``kfn(feed_vals, const_state,
    mut_state, sentinel)`` — a ``lax.scan`` over the traced step with the
    mutable state (plus, when guarded, the aggregated health record) riding
    the carry.  Shared by ``Executor.run_steps`` (single device) and the
    SPMD window runner (``parallel.spmd.ShardedWindowRunner``), so the
    sharded path scans the EXACT same body the single-device oracle tests
    pin down.

    ``trace(feed, state)`` overrides the default ``trace_block`` call
    (the sharded runner wraps it in a ``mesh_scope``); ``finalize(last,
    mut_final, agg)`` post-processes the outputs inside the trace (the
    sharded runner pins shardings there; ``agg`` is None unguarded).
    """
    import jax.numpy as _jnp
    from jax import lax as _lax

    from . import guardian as _guardian

    if trace is None:
        def trace(feed_vals, state_vals):
            return trace_block(program, 0, plan, feed_vals, state_vals)
    if finalize is None:
        def finalize(last, mut_final, agg):
            return last, mut_final, agg

    def kfn(feed_vals, const_state, mut_state, sentinel):
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
            fetches, new_state = trace(step_feed, state)
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
            lambda st: trace(first_feed, {**const_state, **st}),
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
                lod_box: Optional[Dict[str, object]] = None):
    """Run every op in the block symbolically; returns (fetches, new_state).

    ``static_env`` carries compile-time-constant entries — notably
    ``<name>@LOD`` sequence metadata (tuples of offset tuples).  LoD is
    *static* in this framework (SURVEY.md §5.7: the TPU answer to variable
    length is bucketing + segment ids, not dynamic shapes): packed sequence
    data keeps a static [sum_len, ...] shape and the offsets are baked into
    the trace, so XLA sees fully static programs.  ``lod_box``, if given,
    receives the lod of every fetch/state name produced by the trace.
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
        run_op(op, env, rng_box)
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


def run_op(op, env: Dict[str, object], rng_box=None):
    """Execute one IR op against a trace environment."""
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

    # the scope name lands in XLA HLO metadata (op_name="jit(..)/<type>/..")
    # so device profiles attribute per-HLO-op time back to framework ops
    # (ref: platform/device_tracer.h:49 correlation_id -> op role; here the
    # correlation is carried by the compiler instead of CUPTI ids)
    with jax.named_scope(op.type):
        if is_grad:
            if opdef.grad_fn is not None:
                raw = opdef.grad_fn(ctx)
            else:
                raw = _reg.run_grad_generic(opdef, ctx)
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
        program = program or default_main_program()
        scope = scope or global_scope()
        n_steps = int(n_steps)
        fetch_names = [f.name if isinstance(f, Variable) else str(f)
                       for f in fetch_list or []]
        feed_arrays = {}
        for k, v in dict(feed or {}).items():
            arr, _lod = self._coerce_feed(program, k, v)
            if _lod:
                raise RuntimeError(
                    "run_steps: LoD feeds are not supported in the "
                    "scanned loop; use Executor.run per step")
            feed_arrays[k] = arr
        from . import amp as _amp
        from . import guardian as _guardian

        # guarded window: sentinel + dynamic loss scale fold into the scan
        # body exactly like Executor.run's single guarded step
        guard = _guardian.for_program(program)
        n_user = len(fetch_names)

        from ..observe import trace as _trace

        key = ("run_steps", program._cache_token, program._version,
               tuple(fetch_names), n_steps, bool(feed_per_step),
               tuple(sorted((k, tuple(v.shape), str(v.dtype))
                            for k, v in feed_arrays.items())),
               self.place.device_type,
               # execution-mode toggles invalidate compiled fns (same
               # contract as Executor.run's cache key)
               _amp.compute_dtype(),
               guard.cache_token() if guard is not None else None,
               os.environ.get("PADDLE_TPU_FLASH", ""),
               os.environ.get("PADDLE_TPU_FUSED", ""))
        entry = self._cache.get(key)
        probe = None
        fresh_entry = entry is None
        if entry is None:
            import time as _t

            from .log import VLOG
            from .. import analysis as _analysis
            from .. import compile_cache as _cc
            from ..observe import goodput as _goodput

            t_trace0 = _t.perf_counter()
            with _trace.span("executor.trace", n_steps=n_steps):
                # pre-compile verifier (PADDLE_TPU_VERIFY): milliseconds of
                # static checks before seconds of trace/compile; strict mode
                # raises VerifyError here, before any backend work.  Stacked
                # per-step feeds verify as ONE step's slice.
                _analysis.check_before_compile(
                    program,
                    feed=({k: v[0] if getattr(v, "ndim", 0) > 0 else v
                           for k, v in feed_arrays.items()}
                          if feed_per_step else feed_arrays),
                    fetch_list=fetch_names, kind="run_steps")
                # persistent-cache consult BEFORE tracing: a hit means
                # another process already compiled this exact (program, jit
                # config) — the backend executable loads from the shared
                # disk cache
                probe = _cc.executor_probe(
                    program, feed_arrays, fetch_names,
                    extra={"kind": "run_steps", "n_steps": n_steps,
                           "feed_per_step": bool(feed_per_step),
                           "platform": self.place.device_type,
                           "amp": _amp.compute_dtype(),
                           "guard": (guard.cache_token()
                                     if guard is not None else None),
                           "flash": os.environ.get("PADDLE_TPU_FLASH", ""),
                           "fused": os.environ.get("PADDLE_TPU_FUSED", "")})
                VLOG(1, f"Executor.run_steps: compiling {n_steps}-step scan"
                        f"{' (guarded)' if guard is not None else ''}")
                plan_fetches = list(fetch_names)
                if guard is not None:
                    plan_fetches += guard.extra_fetch_names()
                plan = BlockPlan(program, 0, list(feed_arrays), plan_fetches)
                if plan.needs_eager:
                    if guard is not None and guard.scale_vars is not None:
                        raise RuntimeError(
                            "dynamic fp16 loss scaling is not supported for "
                            "programs with data-dependent eager ops")
                    raise RuntimeError(
                        "run_steps: program contains data-dependent eager "
                        "ops; use Executor.run per step")
                if guard is not None and guard.scale_vars:
                    # the scale/good-steps vars are read/written only by the
                    # guarded wrapper (no IR op touches the counter), so
                    # liveness never saw them — gather with the rest of
                    # state
                    for n in guard.scale_vars:
                        if n not in plan.state_in:
                            plan.state_in.append(n)

                kfn = build_window_fn(program, plan, guard, n_user, n_steps,
                                      feed_per_step)
                device = core.get_jax_device(self.place)
                donate = self._donate_argnums(device, program)
                entry = (plan, jax.jit(kfn, donate_argnums=donate), guard)
                self._cache[key] = entry
            if program._params_grads is not None:
                # host tracing/verification is compile-state wall-clock
                # (the backend compile itself lands in the first dispatch,
                # booked below)
                _goodput.note("compile", _t.perf_counter() - t_trace0)
        plan, fn, guard = entry

        import time as _time

        from . import fault as _fault
        from . import profiler as _prof
        from ..observe import watchdog as _watchdog

        # the window span wraps the WHOLE dispatch cycle, so guardian
        # trips / cache probes / slo breaches emitted inside it carry its
        # span id; its children are stamped as they happen, and none of
        # them waits for the device: the window's wait is the host copy
        # of the fetches at its end
        with _trace.span("executor.window", n_steps=n_steps,
                         fresh=fresh_entry):
            t_host0 = _time.perf_counter()
            window_start = 0
            if program._params_grads is not None:
                window_start = self._step_boundary(_fault, n_steps)
            g = _guardian.current() if guard is not None else None
            if g is not None:
                # one-window-lag sentinel: observe the PREVIOUS dispatch's
                # aggregated health and apply policy BEFORE this window runs
                g.on_boundary()
            t_stage0 = _time.perf_counter()
            with _trace.span("executor.stage"):
                state_vals = self._gather_state(program, plan, scope)
                mut_names = set(plan.state_out)
                if plan.needs_rng:
                    mut_names.add(RNG_STATE_VAR)
                if guard is not None and guard.scale_vars:
                    mut_names.update(guard.scale_vars)
                mut_state = {k: v for k, v in state_vals.items()
                             if k in mut_names}
                const_state = {k: v for k, v in state_vals.items()
                               if k not in mut_names}
                device = core.get_jax_device(self.place)
                feed_dev = {k: self._put_feed(k, v, device)
                            for k, v in feed_arrays.items()}
            t_stage1 = _time.perf_counter()
            sentinel = None
            dump_state = None
            if guard is not None:
                seed_mul, loss_mul = _fault.sentinel_injection_window(
                    window_start, n_steps)
                sentinel = {
                    "loss_cap": np.float32(g.loss_cap() if g is not None
                                           else float("inf")),
                    "seed_mul": seed_mul,
                    "loss_mul": loss_mul,
                }
                dump_state = state_vals
                if g is not None and g.config.policy == "dump_and_halt" \
                        and self._donate_argnums(device, program):
                    # donation invalidates mutated input buffers after the
                    # dispatch; dump mode keeps pre-window device copies
                    # alive
                    dump_state = {k: (jnp.array(v, copy=True)
                                      if k in mut_names else v)
                                  for k, v in state_vals.items()}
            agg = None
            t = _time.perf_counter()
            # `compile`: the goodput ledger books a fresh entry's first
            # dispatch (lazy jit: trace + compile on the host) as compile
            with _trace.span("executor.dispatch", compile=fresh_entry):
                if guard is not None:
                    fetches, new_state, agg = fn(feed_dev, const_state,
                                                 mut_state, sentinel)
                else:
                    fetches, new_state = fn(feed_dev, const_state,
                                            mut_state, None)
                if _prof.is_profiling() and guard is None:
                    # fluid.profiler's timeline wants the device time; no
                    # span, sink or PADDLE_TRACE setting ever waits here
                    jax.block_until_ready((fetches, new_state))
            t_disp1 = _time.perf_counter()
            with _trace.span("executor.observe"):
                if _prof.is_profiling():
                    _prof.record_event(
                        f"executor_run[{len(plan.ops)}ops x{n_steps}steps]",
                        t_disp1 - t, start=t)
                # window visibility in the always-on counters (the smoke
                # oracle counts dispatches; window_steps tracks
                # amortization)
                _prof.record_counter("executor.dispatches")
                _prof.record_counter("executor.windows")
                _prof.record_counter("executor.window_steps", inc=n_steps)
                if probe is not None:
                    probe.finish(t_disp1 - t, program,
                                 meta={"kind": "run_steps",
                                       "n_steps": n_steps})
                if _fault.active() is not None:
                    new_state = _fault.corrupt_state(new_state)
                for name, val in new_state.items():
                    scope.set(name, val)
                self._check_nan_inf(list(new_state.items())
                                    + list(zip(plan.fetch_names, fetches)))
                if g is not None and agg is not None:
                    g.defer(guard, window_start, agg, {
                        "program": program, "feeds": feed_arrays,
                        "feed_lods": {}, "fetch_names": fetch_names,
                        "state": dump_state, "sentinel": sentinel,
                        "duration_s": t_disp1 - t,
                        "window": {"start": window_start,
                                   "n_steps": n_steps,
                                   "feed_per_step": bool(feed_per_step)}})
                if program._params_grads is not None:
                    from .. import observe
                    from ..observe import memory as _obsmem

                    # events emitted after the window (checkpoint commits,
                    # cache probes) correlate to its LAST executed step,
                    # not its first
                    observe.note_step(window_start + n_steps - 1)
                    # live-buffer ledger: scope residency + watermark at
                    # the window boundary (gauges, high-water, watchdog
                    # feed)
                    _obsmem.note_scope_live(scope, scope_label="train",
                                            step=window_start + n_steps - 1)
            t_obs1 = _time.perf_counter()
            # the host breakdown of this window (host_ms = everything not
            # in the other three); dispatch_ms is the ENQUEUE, plus trace
            # and compile on a fresh entry
            _trace.note_window_breakdown(
                host_ms=((t_stage0 - t_host0) + (t - t_stage1)) * 1e3,
                stage_ms=(t_stage1 - t_stage0) * 1e3,
                dispatch_ms=(t_disp1 - t) * 1e3,
                observe_ms=(t_obs1 - t_disp1) * 1e3)
            if program._params_grads is not None:
                # SLO watchdog: per-step time of this dispatch (no-op
                # unless PADDLE_SLO is armed)
                _watchdog.observe_value(
                    "executor.step_time_s",
                    (t_obs1 - t_host0) / max(1, n_steps),
                    step=window_start + n_steps - 1)
                from ..observe import goodput as _goodput

                # goodput ledger: a fresh entry's first dispatch is
                # compile cost (lazy jit), everything else device compute
                disp = t_disp1 - t
                if fresh_entry:
                    _goodput.note("compile", disp)
                    _goodput.note("device",
                                  max(0.0, (t_obs1 - t_host0) - disp))
                else:
                    _goodput.note("device", t_obs1 - t_host0)
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

        from . import amp as _amp
        from . import fault as _fault
        from . import guardian as _guardian
        from . import profiler as _prof
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
            feed_arrays, feed_lods = {}, {}
            for k, v in feed.items():
                arr, lod = self._coerce_feed(program, k, v)
                feed_arrays[k] = arr
                if lod:
                    feed_lods[k] = lod
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

            key = (program._cache_token, program._version,
                   tuple(fetch_names),
                   tuple(sorted((k, tuple(v.shape), str(v.dtype))
                                for k, v in feed_arrays.items())),
                   tuple(sorted(feed_lods.items())),
                   tuple(sorted(state_lods.items())),
                   self.place.device_type,
                   # execution-mode toggles invalidate compiled fns
                   _amp.compute_dtype(),
                   guard.cache_token() if guard is not None else None,
                   os.environ.get("PADDLE_TPU_FLASH", ""),
                   os.environ.get("PADDLE_TPU_FUSED", ""))
            entry = self._cache.get(key) if use_program_cache else None
            probe = None
            fresh = entry is None
            root.set(fresh=fresh)
            if fresh:
                with _trace.span("fluid.run.build"):
                    entry, probe = self._build_entry(
                        program, feed_arrays, feed_lods, state_lods,
                        fetch_names, guard)
                if use_program_cache:
                    self._cache[key] = entry
            plan, fn, lod_box, guard = entry

        with _trace.span("fluid.run.state"):
            step_idx = 0
            if program._params_grads is not None:
                # training-step boundary (programs built via
                # optimizer.minimize; hook points for fault injection +
                # elastic liveness)
                step_idx = self._step_boundary(_fault)
            root.set(step=step_idx)
            g = _guardian.current() if guard is not None else None
            if g is not None:
                # one-step-lag sentinel: observe the PREVIOUS step's health
                # (its dispatch has retired, materializing two scalars is
                # free) and apply policy BEFORE this step runs
                g.on_boundary()
            state_vals = self._gather_state(program, plan, scope)

            # only vars that get rewritten are donated; read-only state
            # (lr, params in eval programs) must keep its buffers alive in
            # the scope
            mut_names = set(plan.state_out)
            if plan.needs_rng:
                mut_names.add(RNG_STATE_VAR)
            mut_state = {k: v for k, v in state_vals.items()
                         if k in mut_names}
            const_state = {k: v for k, v in state_vals.items()
                           if k not in mut_names}
            sentinel = None
            dump_state = None
            if guard is not None:
                seed_mul, loss_mul = _fault.sentinel_injection(step_idx)
                sentinel = {
                    "loss_cap": np.float32(g.loss_cap() if g is not None
                                           else float("inf")),
                    "seed_mul": np.float32(seed_mul),
                    "loss_mul": np.float32(loss_mul),
                }
                dump_state = state_vals
                if g is not None and g.config.policy == "dump_and_halt" \
                        and self._donate_argnums(device, program):
                    # donation invalidates mutated input buffers after the
                    # dispatch; dump mode keeps pre-step device copies
                    # alive
                    dump_state = {k: (jnp.array(v, copy=True)
                                      if k in mut_names else v)
                                  for k, v in state_vals.items()}

        health = None
        t = _time.perf_counter()
        with _trace.span("fluid.run.call"):
            if guard is not None:
                fetches, new_state, health = fn(feed_dev, const_state,
                                                mut_state, sentinel)
            else:
                fetches, new_state = fn(feed_dev, const_state, mut_state)
                if _prof.is_profiling():
                    # fluid.profiler's timeline wants the device time; no
                    # span, sink or PADDLE_TRACE setting ever waits here
                    jax.block_until_ready(fetches)
        call_s = _time.perf_counter() - t

        with _trace.span("fluid.run.commit"):
            if _fault.active() is not None:
                new_state = _fault.corrupt_state(new_state)
            for name, val in new_state.items():
                scope.set(name, val)
                if name in lod_box:
                    scope._lods[name] = lod_box[name]
            # the arrays the scope just let go of (donated, or replaced)
            # die with their last reference: here, in the span that
            # replaced them, not when this frame ends (0.9 ms a step for
            # the Transformer's 190 on the v5e's host)
            del state_vals, mut_state, const_state
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
                donated = set(plan.state_out) | (
                    {RNG_STATE_VAR} if plan.needs_rng else set())
                out = []
                for n, v in zip(plan.fetch_names, fetches):
                    if n in donated and isinstance(v, jax.Array):
                        v = jnp.array(v, copy=True)
                    out.append(LoDTensor(v, lod_box.get(n)))

        with _trace.span("fluid.run.observe"):
            if _prof.is_profiling():
                _prof.record_event(f"executor_run[{len(plan.ops)}ops]",
                                   call_s, start=t)
            _prof.record_counter("executor.dispatches")
            if probe is not None:
                # first dispatch of a fresh entry = trace + compile; commit
                # the artifact (miss) / freshen it (hit) now that it exists
                probe.finish(call_s, program,
                             meta={"kind": "run",
                                   "ops": len(plan.ops),
                                   "fetches": len(plan.fetch_names)})
            self._check_nan_inf(list(new_state.items())
                                + list(zip(plan.fetch_names, fetches)))
            if g is not None and health is not None:
                g.defer(guard, step_idx, health, {
                    "program": program, "feeds": feed_arrays,
                    "feed_lods": feed_lods, "fetch_names": fetch_names,
                    "state": dump_state, "sentinel": sentinel,
                    "duration_s": _time.perf_counter() - t})
            if program._params_grads is not None:
                from ..observe import goodput as _goodput
                from ..observe import memory as _obsmem
                from ..observe import watchdog as _watchdog

                # SLO watchdog on the per-step training path (no-op unless
                # PADDLE_SLO is armed); async dispatch means this measures
                # submit-to-submit pacing, which is what regresses under
                # load
                _watchdog.observe_value("executor.step_time_s",
                                        _time.perf_counter() - t,
                                        step=step_idx)
                # ledger gauges only (quiet): per-step watermark EVENTS
                # would flood the stream, windows own the event cadence
                _obsmem.note_scope_live(scope, scope_label="train",
                                        step=step_idx, emit_event=False)
                # per-step training dispatch: a fresh entry's first
                # dispatch is compile cost (lazy jit), everything after
                # device compute
                _goodput.note("compile" if fresh else "device",
                              _time.perf_counter() - t)

        if return_numpy:
            with _trace.span("fluid.run.fetch"):
                out = [np.asarray(v) for v in fetches]
        return out

    def _build_entry(self, program, feed_arrays, feed_lods, state_lods,
                     fetch_names, guard):
        """The executor's cache missed: verify, consult the persistent
        compile cache, plan the block and build the (lazily compiled) jit.
        Returns the cache entry and the compile-cache probe."""
        from . import amp as _amp
        from .log import VLOG
        from .. import analysis as _analysis
        from .. import compile_cache as _cc

        # pre-compile verifier (PADDLE_TPU_VERIFY=warn|strict|off): named
        # diagnostics in milliseconds instead of an XLA trace error seconds
        # into compile
        _analysis.check_before_compile(
            program, feed=feed_arrays, fetch_list=fetch_names, kind="run")
        # persistent-cache consult BEFORE tracing (hit/miss counters +
        # backend warm start through the shared jax disk cache)
        probe = _cc.executor_probe(
            program, feed_arrays, fetch_names,
            extra={"kind": "run",
                   "feed_lods": tuple(sorted(feed_lods.items())),
                   "state_lods": tuple(sorted(state_lods.items())),
                   "platform": self.place.device_type,
                   "amp": _amp.compute_dtype(),
                   "guard": (guard.cache_token()
                             if guard is not None else None),
                   "flash": os.environ.get("PADDLE_TPU_FLASH", ""),
                   "fused": os.environ.get("PADDLE_TPU_FUSED", "")})
        VLOG(1, f"Executor: compiling block "
                f"({len(program.global_block().ops)} ops, "
                f"fetches={fetch_names})")
        plan_fetches = list(fetch_names)
        if guard is not None:
            plan_fetches += guard.extra_fetch_names()
        plan = BlockPlan(program, 0, list(feed_arrays), plan_fetches)
        if guard is not None and plan.needs_eager:
            if guard.scale_vars is not None:
                raise RuntimeError(
                    "dynamic fp16 loss scaling is not supported for "
                    "programs with data-dependent eager ops")
            warnings.warn(
                "guardian: program contains data-dependent eager ops; "
                "the numerics sentinel is disabled for it")
            guard = None
            plan = BlockPlan(program, 0, list(feed_arrays), fetch_names)
        if guard is not None and guard.scale_vars:
            # the good-steps counter is read/written only by the guarded
            # wrapper (no IR op touches it), so liveness never saw it:
            # gather it with the rest of the state
            for n in guard.scale_vars:
                if n not in plan.state_in:
                    plan.state_in.append(n)
        lod_box = {}
        all_lods = dict(state_lods)
        all_lods.update(feed_lods)
        fn = self._build(program, plan, all_lods, lod_box,
                         guard=guard, n_user=len(fetch_names))
        return (plan, fn, lod_box, guard), probe

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
        feed_arrays = {}
        for k, v in dict(feed or {}).items():
            arr, lod = self._coerce_feed(program, k, v)
            if lod:
                return None
            feed_arrays[k] = arr
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
        mut_names = set(plan.state_out)
        if plan.needs_rng:
            mut_names.add(RNG_STATE_VAR)
        mut_state = {k: v for k, v in state_vals.items() if k in mut_names}
        const_state = {k: v for k, v in state_vals.items()
                       if k not in mut_names}
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
    @staticmethod
    def _donate_argnums(device, program):
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

    @staticmethod
    def _step_boundary(_fault, n_steps=1):
        """Training-step boundary: fires armed step faults (kill-at-step-N)
        and emits an elastic-supervisor heartbeat when a heartbeat dir is
        configured.  A fused run_steps dispatch advances the whole window at
        once — a kill armed inside it fires before the dispatch.  Returns
        the step index this dispatch executes (window start for fused)."""
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

    @staticmethod
    def _check_nan_inf(named_vals):
        """Debug mode (ref FLAGS_check_nan_inf, operator.cc:643): fault
        with the variable NAME on the first non-finite value.  Host-side
        materialization forces a sync per step — debug only."""
        if not core.GLOBAL_FLAGS.get("check_nan_inf"):
            return
        for name, val in named_vals:
            arr = np.asarray(val)
            if np.issubdtype(arr.dtype, np.floating) \
                    and not np.isfinite(arr).all():
                raise FloatingPointError(
                    f"check_nan_inf: variable '{name}' contains "
                    f"NaN/Inf after op block execution")

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
               guard=None, n_user=None):
        device = core.get_jax_device(self.place)
        donate = self._donate_argnums(device, program)
        static_env = {k + LOD_SUFFIX: lod
                      for k, lod in (feed_lods or {}).items()}

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
                fetches, new_state = trace_block(
                    program, 0, plan, feed_vals, state,
                    static_env=static_env, lod_box=lod_box)
                new_state, health = _g.fold_health(
                    guard, fetches[n_user:], new_state, mut_state, state,
                    sentinel)
                return fetches[:n_user], new_state, health

            return jax.jit(gfn, donate_argnums=donate)

        def fn(feed_vals, const_state, mut_state):
            state = dict(const_state)
            state.update(mut_state)
            return trace_block(program, 0, plan, feed_vals, state,
                               static_env=static_env, lod_box=lod_box)

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
            from . import profiler as _prof

            for si, (kind, ops) in enumerate(plan.segments):
                if kind == "eager":
                    for op in ops:
                        if _prof.is_profiling():
                            import time as _time

                            t = _time.perf_counter()
                            run_op(op, env, rng_box)
                            _prof.record_event(
                                f"eager:{op.type}",
                                _time.perf_counter() - t, start=t)
                        else:
                            run_op(op, env, rng_box)
                    continue
                if _prof.is_profiling():
                    import time as _time

                    t = _time.perf_counter()
                    self._run_jit_segment(si, ops, env, rng_box, seg_cache)
                    _prof.record_event(
                        f"jit_segment[{si}:{len(ops)}ops]",
                        _time.perf_counter() - t, start=t)
                else:
                    self._run_jit_segment(si, ops, env, rng_box, seg_cache)
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
            state[name] = val if isinstance(val, jax.Array) else jnp.asarray(val)
        if plan.needs_rng:
            rk = scope.get(RNG_STATE_VAR, _MISSING)
            if rk is _MISSING:
                rk = jax.random.PRNGKey(program.random_seed or 0)
                scope.set(RNG_STATE_VAR, rk)
            state[RNG_STATE_VAR] = rk
        return state

    def _coerce_feed(self, program, name, value):
        lod = None
        from .lod_tensor import LoDTensor

        if isinstance(value, LoDTensor):
            lod = value.lod() or None
            # unwrap WITHOUT np.asarray: a device-resident LoDTensor (what
            # run(return_numpy=False) returns) must stay on device — the
            # jax.Array branch below passes it through, avoiding a blocking
            # D2H + re-upload round trip on the decode hot path
            value = value._data
        elif isinstance(value, tuple) and len(value) == 2 \
                and isinstance(value[1], (list, tuple)):
            # (array, recursive_sequence_lengths) convenience form
            from .lod_tensor import _lengths_to_offsets

            value, lengths = value
            lod = tuple(tuple(_lengths_to_offsets(l)) for l in lengths) or None
        if isinstance(value, jax.Array):
            # pre-placed device array: keep it on device (astype stays lazy)
            gb = program.global_block()
            if gb._has_var_recursive(name):
                want = core.np_dtype(gb._var_recursive(name).dtype)
                if value.dtype != want:
                    value = value.astype(want)
            return value, lod
        arr = np.asarray(value)
        gb = program.global_block()
        if gb._has_var_recursive(name):
            want = core.np_dtype(gb._var_recursive(name).dtype)
            if arr.dtype != want:
                arr = arr.astype(want)
        if lod is not None:
            lod = tuple(tuple(int(x) for x in level) for level in lod)
        return arr, lod
