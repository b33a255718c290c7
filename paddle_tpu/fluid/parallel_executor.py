"""ParallelExecutor: data-parallel execution over a device mesh.

The reference's ParallelExecutor (ref: parallel_executor.cc:119, SSA-graph
engine in framework/details/) replicates the program per GPU and inserts NCCL
all-reduce op-handles per gradient.  The TPU-native equivalent needs none of
that machinery: the same traced block function is jitted under a 1-D
``jax.sharding.Mesh`` with the batch dimension of every fed tensor sharded
across devices and all state replicated.  XLA's SPMD partitioner then derives
the per-device program and inserts the gradient all-reduce collectives over
ICI automatically — the multi_devices_graph_pass, AllReduceOpHandle and
ThreadedSSAGraphExecutor collapse into GSPMD.

Loss scaling: the reference writes a 1/N constant per device
(ScaleLossGradOpHandle).  Here the loss `mean` already averages over the
*global* batch, so gradients match the single-device program exactly — the
"same loss single vs parallel" oracle (SURVEY.md §4.4) holds by construction.
"""

from __future__ import annotations

from typing import Dict

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import step as _step
from .executor import global_scope
from .framework import Variable, default_main_program
from ..parallel.mesh import env_mesh_spec, mesh_from_spec, mesh_label
from ..parallel.spmd import ShardedTrainStep, ShardedWindowRunner


class ExecutionStrategy:
    """ref: pybind.cc:605-620.  Most knobs are XLA's business now; kept for
    API parity and honored where meaningful."""

    class ExecutorType:
        Default = 0
        Experimental = 1

    def __init__(self):
        self.num_threads = 0
        self.use_cuda = False
        self.allow_op_delay = False
        self.num_iteration_per_drop_scope = 100
        self.type = ExecutionStrategy.ExecutorType.Default


class BuildStrategy:
    """ref: pybind.cc:621-643."""

    class ReduceStrategy:
        AllReduce = 0   # replicated params (psum grads) — GSPMD default
        Reduce = 1      # sharded optimizer states (ZeRO-1 style)

    class GradientScaleStrategy:
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = \
            BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        self.debug_graphviz_path = ""


class ParallelExecutor:
    """ref: python/paddle/fluid/parallel_executor.py:32.

    Single-process: a "dp" mesh over the local devices.  Multi-process: if
    the program carries DistributeTranspiler dist info (or num_trainers>1),
    the coordination service is joined (parallel.multihost) and the mesh
    spans ALL processes' devices — each process feeds its local batch shard
    and GSPMD runs one global program, which is the redesigned pserver path.

    BuildStrategy.ReduceStrategy.Reduce enables ZeRO-1 optimizer-state
    sharding (see parallel.spmd.infer_param_specs)."""

    def __init__(self, use_cuda=False, loss_name=None, main_program=None,
                 share_vars_from=None, exec_strategy=None, build_strategy=None,
                 num_trainers=1, trainer_id=0, scope=None, use_tpu=None,
                 devices=None, mesh=None, **kwargs):
        from ..parallel import multihost as _mh

        self._program = main_program or default_main_program()
        self._loss_name = loss_name
        self._scope = scope or global_scope()
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        self._build_strategy = build_strategy or BuildStrategy()

        dist_info = getattr(self._program, "_dist_info", None) or {}
        if num_trainers > 1 and not dist_info:
            dist_info = {"trainers": num_trainers, "trainer_id": trainer_id}
        _mh.ensure_init(dist_info)
        self._multihost = _mh.process_count() > 1

        # mesh selection: explicit Mesh > explicit devices (1-D dp) >
        # spec string from _dist_info / PADDLE_TPU_MESH ("dp4,tp2") >
        # the degenerate all-devices dp mesh.  The spec path is how
        # DistributeTranspiler-annotated programs pick their topology.
        mesh_spec = mesh if isinstance(mesh, str) else None
        if mesh_spec is None and not isinstance(mesh, Mesh):
            mesh_spec = dist_info.get("mesh") or env_mesh_spec()
        if isinstance(mesh, Mesh):
            self._mesh = mesh
        elif devices is not None:
            self._devices = list(devices)
            self._mesh = (mesh_from_spec(mesh_spec, devices=self._devices)
                          if mesh_spec
                          else Mesh(np.array(self._devices), ("dp",)))
        elif mesh_spec:
            self._mesh = mesh_from_spec(mesh_spec)  # global device order
        else:
            self._mesh = _mh.global_mesh(("dp",))  # global when multihost
        self._devices = list(self._mesh.devices.reshape(-1))
        self._cache = {}
        self._window_cache = {}

    @property
    def device_count(self):
        return len(self._devices)

    @property
    def mesh(self):
        return self._mesh

    @property
    def mesh_label(self):
        return mesh_label(self._mesh)

    def run(self, fetch_list, feed=None, feed_dict=None, return_numpy=True):
        from ..observe import trace as _trace

        # the same root and children as Executor.run, so that one reader
        # serves one chip and four (docs/OBSERVABILITY.md section 7)
        with _trace.span("fluid.run", entry="parallel_executor") as root:
            return self._run(root, fetch_list,
                             feed if feed is not None else feed_dict,
                             return_numpy)

    def _run(self, root, fetch_list, feed, return_numpy):
        from ..observe import trace as _trace

        fetch_names = [f.name if isinstance(f, Variable) else str(f)
                       for f in fetch_list]
        with _trace.span("fluid.run.feed"):
            if isinstance(feed, list):
                # per-device feed dicts: concatenate along batch
                merged: Dict[str, np.ndarray] = {}
                for d in feed:
                    for k, v in d.items():
                        merged.setdefault(k, []).append(np.asarray(v))
                feed = {k: np.concatenate(v, 0) for k, v in merged.items()}
            feed_arrays = {k: _step.feed_dtype(self._program, k, v)
                           for k, v in (feed or {}).items()}

        with _trace.span("fluid.run.lookup"):
            # the mesh and the reduce strategy are this executor's own and
            # fixed, so its key needs neither
            key, _ = _step.signature("sharded_step", self._program,
                                     fetch_names, feed_arrays)
            step = self._cache.get(key)
            root.set(fresh=step is None)
            if step is None:
                with _trace.span("fluid.run.build"):
                    step = self._build_step(feed_arrays, fetch_names)
                self._cache[key] = step

        # the feed's second half: its sharded placement needs the step,
        # so it follows the lookup under the same name (a reader sums the
        # two)
        with _trace.span("fluid.run.feed"):
            feed_dev = step.place_feed(feed_arrays)

        with _trace.span("fluid.run.state"):
            self._check_initialized(step.plan)
            state_vals = step.place_state(self._scope)

        with _trace.span("fluid.run.call"):
            fetches, new_state = step(feed_dev, state_vals)

        with _trace.span("fluid.run.commit"):
            # this path has no step boundary, hence no fault hooks
            # (ROADMAP D15)
            _step.commit(self._scope, new_state, faults=False)
            # the arrays the scope just let go of die with their last
            # reference: here, not when this frame ends
            del state_vals, feed_dev

        with _trace.span("fluid.run.observe"):
            if self._program._params_grads is not None:
                from ..observe import memory as _obsmem

                # ledger gauges only: per-step events would flood the
                # stream
                _obsmem.note_scope_live(self._scope, scope_label="train",
                                        mesh=self.mesh_label,
                                        emit_event=False)
        if return_numpy:
            with _trace.span("fluid.run.fetch"):
                return [step.fetch_to_host(v) for v in fetches]
        return list(fetches)

    def _build_step(self, feed_arrays, fetch_names):
        """The step cache missed: verify, then plan and jit the program
        over the mesh (compiled lazily, by its first call)."""
        from .. import analysis as _analysis

        # pre-compile verifier: turns the runtime rejects below (and the
        # opaque GSPMD sharding errors) into named diagnostics
        _analysis.check_before_compile(
            self._program, feed=feed_arrays, fetch_list=fetch_names,
            mesh=self._mesh, kind="pe_run")
        if getattr(self._program, "_loss_scale_vars", None) is not None:
            # the per-step sharded path has no guarded wrapper: the
            # backward seed would go unscaled while append_unscale_ops
            # still divides grads by the scale: silently wrong math
            raise RuntimeError(
                "dynamic fp16 loss scaling requires the windowed "
                "sharded path: use ParallelExecutor.run_steps")
        zero1 = (self._build_strategy.reduce_strategy ==
                 BuildStrategy.ReduceStrategy.Reduce)
        return ShardedTrainStep(
            self._program, list(feed_arrays), fetch_names, self._mesh,
            zero1=zero1, multihost=self._multihost)

    def run_steps(self, fetch_list, feed=None, n_steps=1,
                  feed_per_step=False, return_numpy=True):
        """N training steps in ONE dispatch over the mesh — the sharded
        twin of ``Executor.run_steps`` (same scan body via
        ``executor.build_window_fn``, guardian sentinel + dynamic fp16
        loss scale riding the carry), with the spec-table shardings pinned
        on the carried state and the mutable state donated.

        ``feed_per_step=True``: each feed array carries a leading
        ``n_steps`` dim and scanned step i consumes slice i; the batch
        (dim 1) shards over the mesh's dp axes and must divide them —
        indivisible batches raise a clear ValueError rather than an
        opaque XLA sharding error."""
        from . import guardian as _guardian

        n_steps = int(n_steps)
        fetch_names = [f.name if isinstance(f, Variable) else str(f)
                       for f in fetch_list or []]
        feed_arrays = {k: _step.feed_dtype(self._program, k, v)
                       for k, v in dict(feed or {}).items()}
        key, _ = _step.signature(
            "sharded_window", self._program, fetch_names, feed_arrays,
            _guardian.for_program(self._program), n_steps=n_steps,
            feed_per_step=bool(feed_per_step), mesh=self.mesh_label)
        runner = self._window_cache.get(key)
        if runner is None:
            from .. import analysis as _analysis
            from ..observe import trace as _trace

            with _trace.span("executor.trace", n_steps=n_steps,
                             mesh=self.mesh_label):
                _analysis.check_before_compile(
                    self._program,
                    feed=_step.one_step_feed(feed_arrays, feed_per_step),
                    fetch_list=fetch_names, mesh=self._mesh,
                    kind="pe_run_steps")
                zero1 = (self._build_strategy.reduce_strategy ==
                         BuildStrategy.ReduceStrategy.Reduce)
                runner = ShardedWindowRunner(
                    self._program, list(feed_arrays), fetch_names,
                    self._mesh, n_steps=n_steps,
                    feed_per_step=feed_per_step, zero1=zero1,
                    multihost=self._multihost)
                self._window_cache[key] = runner
        self._check_initialized(runner.plan)
        return runner.run(feed_arrays, scope=self._scope,
                          return_numpy=return_numpy)

    def stage_window(self, window):
        """Place one stacked ``(n_steps, batch, ...)`` feed window with the
        mesh's window sharding (batch dim 1 over the dp axes) — the
        ``DevicePrefetcher`` ``stage_fn`` for sharded training, so window
        k+1 lands shard-placed while the device runs window k."""
        from ..parallel.spmd import batch_spec

        arrays = {k: np.asarray(v) for k, v in window.items()}
        bspec = batch_spec(self._mesh)
        axes = [ax for ax in bspec if ax is not None]
        div = 1
        for ax in axes:
            div *= self._mesh.shape[ax]
        out = {}
        for k, arr in arrays.items():
            divisible = arr.ndim > 1 and arr.shape[1] % div == 0
            spec = P(*([None] + list(bspec))) if divisible else P()
            out[k] = jax.device_put(arr, NamedSharding(self._mesh, spec))
        return out

    def _check_initialized(self, plan):
        _step.gather_state(self._program, plan, self._scope)

    def bcast_params(self):
        """ref: parallel_executor.cc:234 BCastParamsToDevices — replication is
        expressed via sharding; nothing to broadcast eagerly."""
        return None
