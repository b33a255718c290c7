"""Multi-host (multi-process) runtime over DCN.

This is the TPU-native replacement for the reference's distributed transport
(ref: operators/distributed/ gRPC client/server, send/recv/listen_and_serv
ops, gen_nccl_id): instead of a parameter-server var transport, processes
join one JAX coordination service (`jax.distributed.initialize`) and execute
ONE GSPMD program over the global device mesh; gradient/parameter movement
becomes XLA collectives over ICI/DCN.

Role mapping:
  - pserver endpoint list  -> coordination-service address (first endpoint)
  - trainer_id / trainers  -> process_id / num_processes
  - gen_nccl_id handshake  -> jax.distributed.initialize barrier
  - send/recv param blocks -> GSPMD all-reduce / all-gather over the mesh

Env contract mirrors the reference cluster env (fluid_benchmark.py:34-82):
PADDLE_TRAINER_ID, PADDLE_TRAINERS, PADDLE_COORDINATOR_ADDR (falls back to
the first entry of PADDLE_PSERVER_EPS).
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_initialized = False


def is_initialized() -> bool:
    return _initialized


def _local_device_ids_from_env() -> Optional[list]:
    """PADDLE_LOCAL_DEVICE_IDS="0,1,2,3" -> [0, 1, 2, 3]; blank entries
    (trailing commas from shell templating) are skipped like the
    PADDLE_PSERVER_EPS list handling below."""
    ids = os.environ.get("PADDLE_LOCAL_DEVICE_IDS", "")
    parsed = [int(x) for x in ids.split(",") if x.strip()]
    return parsed or None


def init(coordinator_addr: Optional[str] = None,
         num_processes: Optional[int] = None,
         process_id: Optional[int] = None,
         local_device_ids: Optional[Sequence[int]] = None) -> tuple:
    """Join the pod-wide coordination service.  Arguments fall back to the
    PADDLE_* cluster env vars.  Idempotent; no-op for a 1-process world.

    Returns (process_id, num_processes)."""
    global _initialized
    if coordinator_addr is None:
        coordinator_addr = os.environ.get("PADDLE_COORDINATOR_ADDR")
        if not coordinator_addr:
            eps = os.environ.get("PADDLE_PSERVER_EPS", "")
            coordinator_addr = eps.split(",")[0].strip() or None
    if num_processes is None:
        num_processes = int(os.environ.get("PADDLE_TRAINERS", "1"))
    if process_id is None:
        process_id = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    if local_device_ids is None:
        local_device_ids = _local_device_ids_from_env()
    if num_processes <= 1:
        return process_id, num_processes
    if _initialized:
        return jax.process_index(), jax.process_count()
    if coordinator_addr is None:
        raise ValueError(
            "multihost.init: trainers > 1 but no coordinator address; set "
            "PADDLE_COORDINATOR_ADDR (or PADDLE_PSERVER_EPS) or pass "
            "coordinator_addr")
    from ..fluid.log import VLOG

    VLOG(1, f"multihost: jax.distributed.initialize coordinator="
            f"{coordinator_addr} procs={num_processes} id={process_id}")
    try:
        if jax.config.jax_platforms == "cpu" or \
                os.environ.get("JAX_PLATFORMS", "") == "cpu":
            # the CPU PJRT client refuses cross-process computations
            # ("Multiprocess computations aren't implemented on the CPU
            # backend") unless the gloo collectives implementation is
            # selected BEFORE backend init — without this, every
            # multi-process CPU test/run dies at its first collective
            jax.config.update("jax_cpu_collectives_implementation",
                              "gloo")
        jax.distributed.initialize(coordinator_addr, num_processes,
                                   process_id, local_device_ids)
    except RuntimeError as exc:
        raise RuntimeError(
            "jax.distributed.initialize failed — it must run BEFORE any JAX "
            "computation initializes the backend.  Call "
            "DistributeTranspiler.transpile() (or multihost.init()) before "
            "running the startup program or any other device work."
        ) from exc
    _initialized = True
    return jax.process_index(), jax.process_count()


def ensure_init(dist_info: dict) -> None:
    """Initialize from a DistributeTranspiler annotation (program._dist_info)."""
    if dist_info and int(dist_info.get("trainers", 1)) > 1:
        init(dist_info.get("coordinator"), int(dist_info["trainers"]),
             int(dist_info.get("trainer_id", 0)))


def shutdown() -> None:
    global _initialized
    if _initialized:
        jax.distributed.shutdown()
        _initialized = False


def process_index() -> int:
    return jax.process_index() if _initialized else 0


def process_count() -> int:
    return jax.process_count() if _initialized else 1


def barrier(tag: str = "barrier", timeout_s: float = 300.0) -> float:
    """Pod-wide rendezvous (no-op in a 1-process world).  THE hook point
    for the wedged-collective fault: an armed barrier stall sleeps here,
    which is exactly where a real wedged host stops heartbeating from.

    Prefers the coordination-service barrier (control-plane gRPC with a
    real timeout — a dead peer surfaces as an error here instead of a
    silent infinite hang, and no device computation is involved, so it
    also works on hosts whose backend cannot run multiprocess XLA);
    falls back to a device sync when no coordination client exists.

    Returns this rank's wait time (seconds): per-rank barrier-wait is the
    straggler signature in a gang-scheduled fleet — the SLOW rank arrives
    last and waits ~zero, every healthy rank's wait inflates — so each
    wait is published as a ``barrier.wait`` run event + counter and
    ``barrier``-state goodput time (ISSUE 13)."""
    import time as _t

    from ..fluid import fault as _fault

    _fault.barrier_stall(tag)
    if not _initialized:
        return 0.0
    t0 = _t.perf_counter()
    client = getattr(
        __import__("jax._src.distributed", fromlist=["global_state"])
        .global_state, "client", None)
    if client is not None:
        client.wait_at_barrier(tag, int(timeout_s * 1000))
    else:
        from jax.experimental import multihost_utils as mhu

        mhu.sync_global_devices(tag)
    dur = _t.perf_counter() - t0
    try:
        from .. import observe
        from ..observe import goodput as _goodput

        observe.registry().inc("barrier.wait_seconds", dur)
        observe.emit("barrier.wait", tag=tag, dur_s=round(dur, 6))
        _goodput.note("barrier", dur)
    except Exception:
        pass  # accounting must never wedge the rendezvous it measures
    return dur


def heartbeat(step: Optional[int] = None) -> None:
    """Emit an elastic-supervisor liveness heartbeat for this process when
    a heartbeat dir is configured (PADDLE_ELASTIC_HB_DIR — set by
    parallel.elastic when it launches the pod); no-op otherwise."""
    hb_dir = os.environ.get("PADDLE_ELASTIC_HB_DIR")
    if hb_dir:
        from .elastic import write_heartbeat

        write_heartbeat(hb_dir, step=step, rank=process_index())


def global_mesh(axis_names: Sequence[str] = ("dp",),
                mesh_shape: Optional[Sequence[int]] = None) -> Mesh:
    """Mesh over ALL processes' devices (ICI within a host, DCN across).

    With no mesh_shape, all devices land on the first axis — pure DP.
    A multi-axis shape lays the LAST axis over the fastest-varying device
    index so tp/sp collectives ride ICI, dp rides DCN."""
    devices = np.array(jax.devices())
    if mesh_shape is None:
        mesh_shape = [len(devices)] + [1] * (len(axis_names) - 1)
    return Mesh(devices.reshape(tuple(mesh_shape)), tuple(axis_names))


def host_local_to_global(arr, mesh: Mesh, spec: P):
    """Per-process host value -> global jax.Array (batch-sharded feeds use
    P('dp'): global batch = num_processes x local batch; P() replicates)."""
    from jax.experimental import multihost_utils as mhu

    return mhu.host_local_array_to_global_array(np.asarray(arr), mesh, spec)


def fetch_to_host(val) -> np.ndarray:
    """Materialize a (replicated) global array on this host."""
    if hasattr(val, "is_fully_addressable") and not val.is_fully_addressable:
        return np.asarray(val.addressable_data(0))
    return np.asarray(val)


# ---------------------------------------------------------------------------
# Sharded checkpointing (the multihost face of trainer.save_checkpoint)
#
# ref analogue: the pserver saves its own param shards on checkpoint_notify
# (go/pserver/service.go:346 saves the local shard + etcd meta;
# io.py:771 _save_lookup_tables_by_notify).  Here each process writes only
# its ADDRESSABLE shards of every global array plus an index manifest; the
# checkpoint directory is assumed shared (GCS/NFS — the same assumption the
# reference's save_dirname on a cluster makes), so restore can rebuild
# global arrays on any number of processes, even a different process count.
# ---------------------------------------------------------------------------


def _safe_name(name: str) -> str:
    return name.replace("/", "%2F").replace("@", "%40")


def save_sharded(state: dict, ckpt_dir: str) -> None:
    """Write this process's addressable shards of every array in ``state``.

    Layout: ckpt_dir/shard_<pid>/<var>.<i>.npy + manifest.json recording
    each shard's global index slices.  Replicated values are written once,
    by a deterministically assigned process (round-robin over var names),
    so checkpoint IO spreads across hosts instead of duplicating."""
    import json

    from ..fluid import fault as _fault
    from ..fluid.retry import retry_io
    from ..fluid.transpiler.ps_dispatcher import assign_writer

    def _save_npy(path, host_arr):
        def _write():
            _fault.io_delay()
            _fault.io_error(path, "write")
            np.save(path, host_arr)

        retry_io(_write, what="ckpt.shard_write")

    pid = process_index()
    d = os.path.join(ckpt_dir, f"shard_{pid}")
    os.makedirs(d, exist_ok=True)
    # balance replicated-var writes across hosts (the pserver-shard write
    # layout, ref go/pserver/service.go:346) instead of every process (or
    # only process 0) writing identical full blobs; every process derives
    # the identical name->writer map.  NOTE a replicated array in a
    # multihost world is NOT fully_addressable (its sharding spans other
    # processes' devices) — replication shows up as a local shard whose
    # index covers the whole array, handled in the shard loop below.
    writer_of = assign_writer(list(state), max(1, process_count()))
    manifest = {}
    for name, arr in state.items():
        if not isinstance(arr, jax.Array):
            arr = jax.numpy.asarray(arr)
        entry = {"shape": [int(s) for s in arr.shape],
                 "dtype": str(np.dtype(arr.dtype)), "shards": []}
        if arr.is_fully_addressable:
            # whole value visible on this host (replicated, or a single-
            # host run): one blob, written by its assigned process
            if writer_of.get(name, 0) == pid or not _initialized:
                fn = f"{_safe_name(name)}.full.npy"
                _save_npy(os.path.join(d, fn), np.asarray(arr))
                entry["shards"].append({"file": fn, "index": None})
        else:
            seen = set()
            for i, sh in enumerate(arr.addressable_shards):
                idx = tuple(
                    (0 if sl.start is None else int(sl.start),
                     int(dim) if sl.stop is None else int(sl.stop))
                    for sl, dim in zip(sh.index, arr.shape))
                if idx in seen:  # replicated across local devices
                    continue
                seen.add(idx)
                full_cover = all(a == 0 and b == dim for (a, b), dim
                                 in zip(idx, arr.shape))
                if full_cover and writer_of.get(name, 0) != pid:
                    # replicated across processes (incl. scalars, whose
                    # empty index is trivially full): one assigned writer
                    continue
                fn = f"{_safe_name(name)}.{i}.npy"
                _save_npy(os.path.join(d, fn), np.asarray(sh.data))
                entry["shards"].append({"file": fn,
                                        "index": [list(p) for p in idx]})
        if entry["shards"]:
            manifest[name] = entry
    # manifest is written LAST: its presence marks this process's shard dir
    # complete (a preempted writer leaves .npy files but no manifest)
    mf_path = os.path.join(d, "manifest.json")

    def _write_manifest():
        _fault.io_error(mf_path, "write")
        with open(mf_path, "w") as f:
            json.dump({"process_count": process_count(),
                       "vars": manifest}, f)

    retry_io(_write_manifest, what="ckpt.shard_manifest")


def load_sharded(ckpt_dir: str, mesh: Optional[Mesh], specs: dict) -> dict:
    """Rebuild global arrays from every shard_*/ manifest under ckpt_dir.

    Requires the checkpoint directory to be readable by all processes
    (shared storage).  Arrays come back with NamedSharding(mesh,
    specs.get(name, P())), so restore works across a different process
    count than the save ran with.  ``mesh=None`` skips device placement
    and returns host numpy arrays (scope-level restore)."""
    import json

    from ..fluid import fault as _fault
    from ..fluid.retry import retry_io

    def _read_json(path):
        # transient OSError retries; garbage content raises ValueError
        # unretried — load_sharded_latest's corrupt-serial fallback owns it
        def _read():
            _fault.io_error(path, "read")
            with open(path) as f:
                return f.read()

        return json.loads(retry_io(_read, what="ckpt.shard_manifest"))

    # process 0's manifest is canonical for the world size: stale higher-
    # index shard dirs from an older, larger-world save in the same
    # directory must be ignored, not merged over fresh weights
    mf0 = os.path.join(ckpt_dir, "shard_0", "manifest.json")
    if not os.path.exists(mf0):
        raise IOError(
            f"sharded checkpoint {ckpt_dir}: shard_0/manifest.json missing "
            f"— no complete checkpoint here")
    expected_procs = int(_read_json(mf0).get("process_count", 1))

    assembled: dict = {}
    covered: dict = {}
    found_procs = set()
    for sub in sorted(os.listdir(ckpt_dir)):
        sd = os.path.join(ckpt_dir, sub)
        mf = os.path.join(sd, "manifest.json")
        if not sub.startswith("shard_"):
            continue
        pid = int(sub.split("_", 1)[1])
        if pid >= expected_procs:
            continue  # stale dir from an older save with more processes
        if not os.path.exists(mf):
            raise IOError(
                f"sharded checkpoint {ckpt_dir}: {sub} has no manifest — "
                f"its writer was interrupted; checkpoint is incomplete")
        payload = _read_json(mf)
        found_procs.add(pid)
        for name, entry in payload["vars"].items():
            shape = tuple(entry["shape"])
            if name not in assembled:
                assembled[name] = np.zeros(shape, np.dtype(entry["dtype"]))
                covered[name] = 0
            for sh in entry["shards"]:
                shard_path = os.path.join(sd, sh["file"])

                def _read_shard(path=shard_path):
                    _fault.io_error(path, "read")
                    return np.load(path)

                data = retry_io(_read_shard, what="ckpt.shard_read")
                if sh["index"] is None:
                    assembled[name][...] = data
                    covered[name] = assembled[name].size
                else:
                    sl = tuple(slice(a, b) for a, b in sh["index"])
                    assembled[name][sl] = data
                    covered[name] += int(data.size)
    if expected_procs is not None and \
            found_procs != set(range(expected_procs)):
        raise IOError(
            f"sharded checkpoint {ckpt_dir}: expected shards from "
            f"{expected_procs} processes, found {sorted(found_procs)}")
    # every element of every array must be covered by some shard — a gap
    # would otherwise restore as silent zeros (disjoint rectangular GSPMD
    # partitions make element-count a sound cover test)
    for name, host in assembled.items():
        if covered[name] < host.size:
            raise IOError(
                f"sharded checkpoint {ckpt_dir}: var '{name}' covers "
                f"{covered[name]}/{host.size} elements — missing shards")
    if mesh is None:
        return assembled
    out = {}
    for name, host in assembled.items():
        spec = specs.get(name, P())
        sharding = NamedSharding(mesh, spec if spec is not None else P())
        out[name] = jax.make_array_from_callback(
            host.shape, sharding, lambda idx, h=host: h[idx])
    return out


# ---------------------------------------------------------------------------
# Serial-dir protocol over sharded checkpoints (the multihost face of
# trainer.save_checkpoint's checkpoint_<n>/_SUCCESS convention, shared with
# the elastic supervisor): every process writes its shards of
# <root>/checkpoint_<n>/, a pod barrier proves all writers finished, then
# process 0 alone commits the serial with meta.json + _SUCCESS.  A worker
# preempted at ANY point leaves either a complete older serial or an
# unmarked dir that restore skips/cleans — never a half-readable state.
# ---------------------------------------------------------------------------

SERIAL_PREFIX = "checkpoint"
SUCCESS_MARK = "_SUCCESS"
META_FILE = "meta.json"


def _sharded_serial_dirs(root: str):
    if not os.path.isdir(root):
        return []
    out = []
    for name in os.listdir(root):
        if name.startswith(SERIAL_PREFIX + "_"):
            try:
                out.append((int(name.rsplit("_", 1)[1]), name))
            except ValueError:
                continue
    return sorted(out)


def latest_complete_sharded(root: str) -> int:
    """Newest serial whose _SUCCESS marker exists, or -1."""
    for serial, name in reversed(_sharded_serial_dirs(root)):
        if os.path.exists(os.path.join(root, name, SUCCESS_MARK)):
            return serial
    return -1


def serial_meta_topology(mesh=None) -> dict:
    """The topology stamp every sharded serial's meta carries: the mesh
    axes this fleet laid state out with (an explicit ``mesh``,  the
    active SPMD mesh, or the ``PADDLE_TPU_MESH`` env spec — whichever is
    known), the process count, and every rank's data-shard assignment.
    ``parallel.reshard`` reads exactly these keys to decide whether a
    resume needs re-layout and how to remap the per-rank cursors."""
    from ..data.sharding import shard_layout
    from .mesh import axes_of

    if mesh is None:
        from .spmd import active_mesh

        mesh = active_mesh()
    axes = axes_of(mesh)
    procs = max(1, process_count())
    out = {"process_count": procs}
    if axes:
        out["mesh_axes"] = [[a, int(e)] for a, e in axes.items()]
    try:
        out["data_shards"] = {
            str(r): [int(n), int(i)]
            for r, (n, i) in shard_layout(mesh, procs).items()}
    except ValueError:
        # a topology/host pair the data plane cannot tile never trained
        # a pipeline; record nothing rather than a wrong layout
        pass
    return out


def save_sharded_serial(state: dict, root: str, serial: int,
                        meta: Optional[dict] = None,
                        max_num: Optional[int] = None,
                        data_state: Optional[dict] = None,
                        mesh=None) -> str:
    """Commit ``state`` as <root>/checkpoint_<serial>/ under the _SUCCESS
    protocol.  ``serial`` is caller-assigned (typically the global step) so
    every process independently derives the same value with no filesystem
    race; restore hands the resume point back via ``meta``.

    ``data_state`` is this RANK's input-pipeline cursor
    (``paddle_tpu.data``): every process writes its own
    ``data_state_<rank>.json`` blob before the all-writers barrier, so
    process 0's single _SUCCESS commit covers the whole fleet's data
    plane atomically with the model shards.

    ``meta`` always lands on disk (an empty dict when the caller passed
    none) and is always enriched with the fleet topology
    (:func:`serial_meta_topology`: ``mesh_axes`` / ``process_count`` /
    per-rank ``data_shards``) — the record ``parallel.reshard`` needs to
    resume this serial on a DIFFERENT mesh.  ``mesh`` pins the topology
    explicitly; by default the active SPMD mesh or the
    ``PADDLE_TPU_MESH`` env spec is recorded.

    Ordering: shards (+ data state) -> barrier (all writers done) ->
    [p0] meta + _SUCCESS -> barrier (everyone may now trust the serial)
    -> [p0] prune.  The fault hooks bracket the _SUCCESS write exactly
    like the single-process trainer checkpoint."""
    import json as _json
    import shutil
    import time as _t

    from ..fluid import fault as _fault
    from .mesh import axes_label

    t_save0 = _t.perf_counter()
    cur = os.path.join(root, f"{SERIAL_PREFIX}_{serial}")
    os.makedirs(cur, exist_ok=True)
    save_sharded(state, cur)
    if data_state is not None:
        from ..data.checkpoint import save_data_state

        save_data_state(cur, data_state, rank=process_index())
    meta = dict(meta or {})
    topo = serial_meta_topology(mesh)
    for key, val in topo.items():
        meta.setdefault(key, val)
    mesh_tag = axes_label({a: e for a, e in meta.get("mesh_axes") or []})
    barrier_s = barrier(f"ckpt_shards_{serial}")
    if process_index() == 0:
        from ..fluid.retry import retry_io

        meta_path = os.path.join(cur, META_FILE)

        def _write_meta():
            _fault.io_error(meta_path, "write")
            with open(meta_path, "w") as f:
                _json.dump(meta, f)

        retry_io(_write_meta, what="ckpt.meta")
        # poison hook before the commit: a matching serial is rewritten
        # NaN (every rank's shards — the walk is recursive) yet still
        # gets its _SUCCESS, the serving canary's rollback oracle
        _fault.ckpt_poison(int(serial), cur)
        _fault.ckpt_crash_point("before")
        success_path = os.path.join(cur, SUCCESS_MARK)

        def _write_success():
            _fault.io_error(success_path, "write")
            with open(success_path, "w") as f:
                f.write("")

        retry_io(_write_success, what="ckpt.success")
        _fault.ckpt_crash_point("after")
        from .. import observe

        # the commit point: after _SUCCESS the serial is trusted, and the
        # run-event stream shows which step's state survives a restart
        # (mesh-labeled, so the goodput ledger prices a downgraded
        # generation's commits against the topology they ran on)
        commit_fields = {"serial": int(serial), "path": cur}
        if mesh_tag is not None:
            commit_fields["mesh"] = mesh_tag
        observe.emit("checkpoint.commit", **commit_fields)
    barrier_s += barrier(f"ckpt_commit_{serial}")
    from .. import observe
    from ..observe import goodput as _goodput

    # all ranks' shards are now covered by p0's _SUCCESS: record the
    # committed step so heartbeats price work-at-risk, book the IO as
    # checkpoint-state time (barrier waits already counted by barrier()),
    # and leave one per-rank checkpoint.save span in the stream
    commit_step = meta.get("step") if isinstance(meta, dict) else None
    observe.note_commit_step(int(commit_step) if commit_step is not None
                             else int(serial))
    dur = _t.perf_counter() - t_save0
    _goodput.note("checkpoint", max(0.0, dur - barrier_s))
    observe.emit("checkpoint.save", serial=int(serial),
                 dur_s=round(dur, 6), barrier_s=round(barrier_s, 6))
    if process_index() == 0 and max_num is not None:
        complete = [(s, n) for s, n in _sharded_serial_dirs(root)
                    if os.path.exists(os.path.join(root, n, SUCCESS_MARK))]
        for _, name in complete[:max(0, len(complete) - max_num)]:
            shutil.rmtree(os.path.join(root, name), ignore_errors=True)
    return cur


def load_sharded_latest(root: str, mesh: Optional[Mesh], specs: dict,
                        clean_incomplete: bool = True):
    """Restore the newest complete serial under ``root``.

    Returns (serial, meta, state) or (-1, None, None) when no complete
    checkpoint exists — INCLUDING an absent/empty root and a root whose
    only serials are unmarked leftovers (the empty-root regression: this
    function must never fall off the end and hand back a bare ``None``
    the caller cannot unpack).  When the serial carries a ``data_state``
    blob for THIS rank it is returned under ``meta["data_state"]`` so
    the worker can restart its input pipeline at the first un-committed
    sample; an unreadable blob condemns the whole serial (fallback),
    absence just means legacy step-replay resume.  A complete-but-
    unreadable serial (truncated shard after commit) falls back to the
    previous complete one, mirroring trainer.load_checkpoint.

    Reshard-on-load (ISSUE 14): when the serial's recorded topology
    (``meta["mesh_axes"]`` / ``meta["process_count"]``) differs from the
    live one, the load routes through ``parallel.reshard`` — the logical
    view is assembled from the old fleet's shards, re-laid out under
    ``mesh``'s shardings, and the per-rank data cursors are merged/split
    onto this fleet's shard layout; ``meta["resharded"]`` records the
    transition.  A same-topology load takes the path below untouched.
    A topology the serial cannot viably land on raises
    ``reshard.ReshardError`` immediately (older serials are equally
    unviable — falling back would only bury the named error).

    ``clean_incomplete`` removes unmarked serial dirs left by a dead
    generation (process 0 only, behind a barrier) so a resumed run
    re-using their serial numbers never mixes stale shards with fresh
    ones."""
    import json as _json
    import shutil

    from . import reshard as _reshard

    if clean_incomplete:
        if process_index() == 0:
            for serial, name in _sharded_serial_dirs(root):
                if not os.path.exists(os.path.join(root, name,
                                                   SUCCESS_MARK)):
                    shutil.rmtree(os.path.join(root, name),
                                  ignore_errors=True)
        barrier("ckpt_clean")
    complete = [s for s, name in _sharded_serial_dirs(root)
                if os.path.exists(os.path.join(root, name, SUCCESS_MARK))]
    last_exc = None
    for serial in reversed(complete):
        cur = os.path.join(root, f"{SERIAL_PREFIX}_{serial}")
        try:
            meta = {}
            meta_path = os.path.join(cur, META_FILE)
            if os.path.exists(meta_path):
                with open(meta_path) as f:
                    meta = _json.load(f)
            if _reshard.needs_reshard(meta, mesh):
                state, data_state, info = _reshard.load_resharded(
                    cur, meta, mesh, specs)
                meta["resharded"] = info
            else:
                state = load_sharded(cur, mesh, specs)
                from ..data.checkpoint import load_data_state

                data_state = load_data_state(cur, rank=process_index())
        except _reshard.ReshardError:
            raise
        except Exception as exc:
            from ..fluid.log import LOG

            LOG(f"sharded checkpoint {cur} is unreadable ({exc!r}); "
                f"falling back to the previous complete serial")
            last_exc = exc
            continue
        if data_state is not None:
            meta["data_state"] = data_state
        return serial, meta, state
    if last_exc is not None:
        raise IOError(
            f"no loadable sharded checkpoint under {root}: every complete "
            f"serial failed to read") from last_exc
    return -1, None, None
