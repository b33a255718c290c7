"""Env-knob contract: one declared registry for every ``PADDLE_*`` knob.

The subsystems grown in PRs 1-7 each invented env knobs ad hoc (fault
injection, elastic supervisor, compile cache, observe, AMP, SPMD meshes,
windowed training).  This module is the single source of truth: every knob
is declared here with its type, default and owning subsystem, values are
read through :func:`get` (live — a subprocess that sets the env before
first use is honored, same late-binding contract as ``compile_cache``),
and two pieces of tooling hang off the registry:

 - ``tools/repo_lint.py`` ASTs the tree and fails CI on any
   ``os.environ`` read of a ``PADDLE_*`` key that is not declared here —
   so a typo'd or undocumented knob cannot ship;
 - ``python -m paddle_tpu.fluid.envcontract`` regenerates ``docs/ENV.md``
   (the committed file is diffed against the generator in tier-1, so the
   doc cannot drift from the code).

Declaring is cheap on purpose: ``declare("PADDLE_X", "int", 4, "executor",
"what it does")``.  Families with dynamic suffixes (the PADDLE_FAULT_*
contract) declare each member; :func:`declared` also accepts names covered
by a declared ``prefix`` entry.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

__all__ = ["EnvKnob", "declare", "get", "get_raw", "declared", "knobs",
           "generate_markdown", "REGISTRY"]

_TYPES = ("str", "int", "float", "bool", "enum", "path", "prefix")

_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")


@dataclass(frozen=True)
class EnvKnob:
    name: str
    type: str                      # one of _TYPES
    default: object                # the value `get` returns when unset
    subsystem: str                 # owning module family (docs grouping)
    help: str
    choices: Tuple[str, ...] = ()  # for type == "enum"

    def parse(self, raw: Optional[str]):
        """Typed value for a raw env string (None/empty -> default)."""
        if raw is None:
            return self.default
        raw = raw.strip()
        if raw == "":
            return self.default
        if self.type == "int":
            return int(raw)
        if self.type == "float":
            return float(raw)
        if self.type == "bool":
            low = raw.lower()
            if low in _TRUE:
                return True
            if low in _FALSE:
                return False
            return self.default
        if self.type == "enum":
            low = raw.lower()
            return low if low in self.choices else self.default
        return raw  # str / path / prefix


REGISTRY: Dict[str, EnvKnob] = {}


def declare(name: str, type: str, default, subsystem: str, help: str,
            choices: Tuple[str, ...] = ()) -> EnvKnob:
    if type not in _TYPES:
        raise ValueError(f"knob type must be one of {_TYPES}, got {type!r}")
    if name in REGISTRY:
        raise ValueError(f"env knob {name} declared twice")
    knob = EnvKnob(name, type, default, subsystem, help, tuple(choices))
    REGISTRY[name] = knob
    return knob


def get(name: str):
    """Typed live read of a declared knob — unset/empty returns the
    declared default (raises KeyError on undeclared names: reading
    through the contract IS the contract)."""
    knob = REGISTRY.get(name)
    if knob is None:
        raise KeyError(
            f"env knob {name!r} is not declared in fluid.envcontract — "
            f"declare it (name, type, default, subsystem) before reading")
    return knob.parse(os.environ.get(name))


def get_raw(name: str) -> str:
    """The raw (stripped) env string of a declared knob; "" when unset."""
    if name not in REGISTRY and not declared(name):
        raise KeyError(f"env knob {name!r} is not declared")
    return os.environ.get(name, "").strip()


def declared(name: str) -> bool:
    """True if `name` is a declared knob or covered by a prefix family."""
    if name in REGISTRY:
        return True
    return any(k.type == "prefix" and name.startswith(k.name)
               for k in REGISTRY.values())


def knobs() -> List[EnvKnob]:
    return sorted(REGISTRY.values(), key=lambda k: (k.subsystem, k.name))


# ---------------------------------------------------------------------------
# The contract.  Grouped by subsystem; keep help to one line.
# ---------------------------------------------------------------------------

# -- executor / runtime --
declare("PADDLE_EXECUTOR_CACHE_CAP", "int", 64, "executor",
        "Bound on the in-process jit cache (LRU entries)")
declare("PADDLE_TPU_DONATE", "bool", True, "executor",
        "Donate mutable training state to XLA (0 disables, for buffer "
        "lifetime debugging)")
declare("PADDLE_TPU_VERIFY", "enum", "warn", "analysis",
        "Pre-compile program verifier mode", choices=("warn", "strict",
                                                      "off"))
declare("PADDLE_TPU_FLASH", "enum", "auto", "ops",
        "Pallas attention-kernel gate (flash, sparse, windowed): 0 forces "
        "the XLA twin, 1 forces the kernel (interpret mode off-TPU), AUTO = "
        "TPU-backend-only; the only way to overrule the platform (tests, "
        "the benchmark's rehearsal)",
        choices=("0", "1", "true", "false", "auto"))
declare("PADDLE_TPU_FUSED", "enum", "auto", "ops",
        "Pallas fused-kernel gate (softmax-xent, optimizer sweeps, paged "
        "attention): 0 restores the unfused XLA lowering, 1 forces on "
        "(interpret mode off-TPU), AUTO = TPU-backend-only; the only way to "
        "overrule the platform (tests, the benchmark's rehearsal)",
        choices=("0", "1", "true", "false", "auto"))
declare("PADDLE_TPU_SPD", "int", 0, "trainer",
        "Steps per dispatch: K>1 runs the trainer loop as K-step fused "
        "windows (Executor.run_steps)")
declare("PADDLE_TPU_PREFETCH_DEPTH", "int", 2, "trainer",
        "Device prefetch depth for windowed training (0 = synchronous)")

# -- AMP --
declare("PADDLE_TPU_AMP", "enum", None, "amp",
        "Enable mixed precision at import", choices=("bfloat16", "float16"))
declare("PADDLE_TPU_AMP_KEEP", "bool", False, "amp",
        "Keep activations in the low compute dtype (pure-low regime)")
declare("PADDLE_TPU_AMP_INIT_SCALE", "float", 2.0 ** 15, "amp",
        "Initial dynamic fp16 loss scale")
declare("PADDLE_TPU_AMP_SCALE_INTERVAL", "int", 1000, "amp",
        "Overflow-free steps between loss-scale growth events")

# -- guardian --
declare("PADDLE_TPU_GUARDIAN", "str", None, "guardian",
        "Arm the numerics guardian (skip|halt|dump_and_halt, or 1=skip)")
declare("PADDLE_TPU_GUARDIAN_SPIKE", "float", 0.0, "guardian",
        "Loss-spike rejection factor over the window median (0 = off)")
declare("PADDLE_TPU_GUARDIAN_WINDOW", "int", 32, "guardian",
        "Spike-median window length (steps)")
declare("PADDLE_TPU_GUARDIAN_RING", "int", 128, "guardian",
        "Flight-recorder ring size (steps)")
declare("PADDLE_TPU_GUARDIAN_DIR", "path", None, "guardian",
        "Flight-recorder replay-bundle directory")

# -- SPMD / distributed --
declare("PADDLE_TPU_MESH", "str", None, "parallel",
        "Named mesh spec, e.g. dp4,tp2 (axis order = spec order)")
declare("PADDLE_TRAINERS", "int", 1, "parallel",
        "Process count for the multihost coordination service")
declare("PADDLE_TRAINER_ID", "int", 0, "parallel",
        "This process's rank")
declare("PADDLE_COORDINATOR_ADDR", "str", None, "parallel",
        "host:port of the jax coordination service (process 0)")
declare("PADDLE_PSERVER_EPS", "str", None, "parallel",
        "Legacy pserver endpoint list (transpiler compatibility)")
declare("PADDLE_LOCAL_DEVICE_IDS", "str", None, "parallel",
        "Comma-separated local device ids visible to this process")

# -- elastic supervisor --
declare("PADDLE_TPU_MESH_LADDER", "str", None, "elastic",
        "Semicolon-ordered mesh downgrade ladder, largest first (e.g. "
        "'dp4;dp2;dp1'): after a permanent host loss the supervisor "
        "relaunches on the largest entry the survivor census can run")
declare("PADDLE_ELASTIC_HB_DIR", "path", None, "elastic",
        "Heartbeat directory the supervisor watches (set per generation)")
declare("PADDLE_ELASTIC_INCIDENTS", "path", None, "elastic",
        "incidents.jsonl path guardian trips are appended to")
declare("PADDLE_ELASTIC_GENERATION", "int", 0, "elastic",
        "Elastic generation index of this worker process")

# -- compile cache --
declare("PADDLE_COMPILE_CACHE_DIR", "path", None, "compile_cache",
        "Enable the persistent compile cache, rooted here")
declare("PADDLE_COMPILE_CACHE_BUDGET_MB", "int", None, "compile_cache",
        "LRU size budget over cache entries + the jax xla cache (MB)")

# -- observability --
declare("PADDLE_OBSERVE_DIR", "path", None, "observe",
        "Enable file output (events JSONL + metric snapshots), rooted here")
declare("PADDLE_OBSERVE_FLUSH_S", "float", 5.0, "observe",
        "Metric snapshot flush interval (seconds)")
declare("PADDLE_OBSERVE_PORT", "int", None, "observe",
        "Serve /metrics + /healthz on 127.0.0.1:<port> (0 = ephemeral)")
declare("PADDLE_TRACE", "bool", True, "observe",
        "Spans reach the event log of a configured observe dir (0 keeps "
        "them out of it; the in-memory ring and the profiler annotation "
        "do not depend on it)")
declare("PADDLE_TRACE_SAMPLE", "float", 1.0, "observe",
        "Fraction of root spans written to the event log (deterministic "
        "every-Nth sampling; children follow their root's decision)")
declare("PADDLE_TRACEPARENT", "str", None, "observe",
        "Inherited trace context, W3C-style '00-<trace>-<span>-01' (the "
        "elastic supervisor sets it so worker spans join the run trace)")
declare("PADDLE_SLO", "bool", False, "observe",
        "Arm the SLO watchdog (rolling median+MAD baselines; emits "
        "slo.breach run events on regression)")
declare("PADDLE_SLO_FACTOR", "float", 3.0, "observe",
        "Breach when a value exceeds factor x rolling median (and clears "
        "the MAD noise guard)")
declare("PADDLE_SLO_WINDOW", "int", 64, "observe",
        "Rolling baseline window per watched metric (samples)")
declare("PADDLE_SLO_MIN_SAMPLES", "int", 8, "observe",
        "Baseline samples required before the watchdog may fire")
declare("PADDLE_SLO_COOLDOWN_S", "float", 1.0, "observe",
        "Minimum seconds between breach events for one metric")
declare("PADDLE_GOODPUT", "bool", True, "observe",
        "Arm the always-on goodput accumulator (wall-clock state "
        "counters + goodput.fraction gauge; 0 disables all accounting)")
declare("PADDLE_GOODPUT_REPORT_S", "float", 30.0, "observe",
        "Seconds between periodic goodput.report run events")
declare("PADDLE_GOODPUT_SCAN_S", "float", 5.0, "observe",
        "Elastic supervisor's straggler-scan interval over the fleet "
        "event stream (0 disables the in-flight scan)")
declare("PADDLE_GOODPUT_STRAGGLER_FACTOR", "float", 1.5, "observe",
        "Flag a rank whose median step time exceeds factor x the other "
        "ranks' median (plus their 3xMAD noise guard)")
declare("PADDLE_GOODPUT_MIN_SAMPLES", "int", 4, "observe",
        "Window samples required per rank before the skew test may flag")

# -- serving (continuous-batching decode path) --
declare("PADDLE_SERVE_DECODE", "bool", True, "serving",
        "Continuous-batching decode master switch (0 makes DecodeEngine "
        "construction refuse — the static request-granularity engine "
        "remains the only serving path)")
declare("PADDLE_SERVE_SLOTS", "int", 8, "serving",
        "Decode slots: concurrent KV-cache-resident streams per engine "
        "(the fixed leading dim of the one compiled decode step)")
declare("PADDLE_SERVE_MAX_LEN", "int", 128, "serving",
        "KV-cache capacity per slot (prompt + generated tokens); "
        "admission rejects requests that cannot fit")
declare("PADDLE_SERVE_PREFILL_BUCKETS", "str", "4,8,16", "serving",
        "Comma-separated prompt-length buckets each compiled once; a "
        "prompt pads up to its enclosing bucket (executable set = these "
        "buckets + the one decode step)")
declare("PADDLE_SERVE_SWAP_POLICY", "enum", "drain", "serving",
        "Hot checkpoint swap in-flight policy: drain = resident slots "
        "finish on the old serial (admissions pause, nothing sheds), "
        "immediate = slots continue on the new weights over their old "
        "KV caches", choices=("drain", "immediate"))
declare("PADDLE_SERVE_CANARY_REQUESTS", "int", 0, "serving",
        "Canary probation: completed requests the new serial must serve "
        "under the SLO watchdog + output-sanity sentinel before "
        "promotion (0 = promote immediately, no canary)")
declare("PADDLE_SERVE_SWAP_POLL_S", "float", 2.0, "serving",
        "Model-registry checkpoint-dir watcher poll interval (seconds)")
declare("PADDLE_SERVE_SENTINEL_ENTROPY", "float", 0.05, "serving",
        "Canary sentinel floor (nats): argmax-entropy collapse below "
        "this across 3 consecutive decode ticks triggers auto-rollback")
declare("PADDLE_SERVE_PAGED", "bool", False, "serving",
        "Paged KV cache (serving/kvpool): per-layer K/V storage becomes "
        "a [num_pages, page_size, d_model] page pool with a host-side "
        "allocator and a per-tick page-table feed; 0 (default) keeps the "
        "dense [max_slots, max_len, d_model] cache — the bitwise-restore "
        "kill switch")
declare("PADDLE_SERVE_PAGE_SIZE", "int", 4, "serving",
        "KV-cache page length in token positions; must divide max_len "
        "AND every prefill bucket (prefill scatters whole pages)")
declare("PADDLE_SERVE_NUM_PAGES", "int", 0, "serving",
        "Page-pool capacity in pages (per layer, K+V share the table); "
        "0 = auto: max_slots * max_len / page_size, i.e. dense-equal "
        "capacity — set lower to oversubscribe slots against real usage")
declare("PADDLE_SERVE_PREFIX_SHARE", "bool", True, "serving",
        "Hash-share read-only full-prompt-page K/V across concurrently "
        "resident slots (refcounted; kvpool.prefix_hits counts shared "
        "pages, full-prefix hits skip the prefill dispatch entirely)")
declare("PADDLE_SERVE_SPEC", "int", 0, "serving",
        "Speculative decoding depth k (serving/specdec): each engine "
        "tick runs k cheap draft steps then ONE wide verify step scoring "
        "k+1 positions per slot; greedy acceptance keeps output bitwise "
        "identical to sequential decode. 0 (default) = kill switch, the "
        "plain one-token tick verbatim")
declare("PADDLE_SERVE_SPEC_DRAFT_LAYERS", "int", 1, "serving",
        "Self-draft depth: the draft model reuses the target's first n "
        "decoder layers (+ embeddings/head, shared by name) with its own "
        "dense KV cache; 0 = full-depth self-draft (every draft token "
        "accepted — a throughput ceiling probe, not a speedup). Ignored "
        "when DecodeConfig.spec_draft_serial loads a registry serial")
declare("PADDLE_SERVE_SPEC_MIN_ACCEPT", "float", 0.3, "serving",
        "Adaptive-fallback floor: rolling draft-acceptance rate below "
        "this over a full PADDLE_SERVE_SPEC_WINDOW of spec ticks drops "
        "the engine to plain one-token ticks (specdec.fallback event), "
        "re-arming after a cooldown of the same length")
declare("PADDLE_SERVE_SPEC_WINDOW", "int", 32, "serving",
        "Spec-tick window for the rolling acceptance-rate gauge and the "
        "adaptive controller (also the fallback cooldown length, in "
        "plain ticks)")

# -- serving fleet (router over N engine replicas; serving/fleet.py) --
declare("PADDLE_ROUTER_MAX_REPLICAS", "int", 4, "router",
        "Autoscale ceiling: replicas per model the scale-out policy may "
        "reach (also bounded by the fleet's device pool)")
declare("PADDLE_ROUTER_MIN_REPLICAS", "int", 1, "router",
        "Autoscale floor: scale-in never drops a model below this")
declare("PADDLE_ROUTER_COOLDOWN_S", "float", 5.0, "router",
        "Seconds between scale/drain actions on one model (hysteresis: "
        "a fresh replica must prove itself before the next decision)")
declare("PADDLE_ROUTER_QUEUE_HIGH", "int", 8, "router",
        "Per-model router-queue depth above which sustained pressure "
        "reads as overload (scale-out watermark)")
declare("PADDLE_ROUTER_QUEUE_LOW", "int", 1, "router",
        "Per-model router-queue depth below which sustained idleness "
        "reads as overprovisioning (scale-in watermark)")
declare("PADDLE_ROUTER_QUEUE_HARD", "int", 64, "router",
        "Per-model router-queue hard cap: submits beyond it shed with "
        "EngineOverloaded — but only AFTER the scale policy has had its "
        "chance (a poked scale-out admits the overflow while warming)")
declare("PADDLE_ROUTER_HYSTERESIS_TICKS", "int", 2, "router",
        "Consecutive policy evaluations a watermark must hold before "
        "the decision fires (debounces arrival bursts)")
declare("PADDLE_ROUTER_EVAL_S", "float", 0.25, "router",
        "Autoscale policy evaluation interval (seconds)")
declare("PADDLE_ROUTER_STRAGGLER_FACTOR", "float", 3.0, "router",
        "Drain-and-replace a replica whose median inter-token latency "
        "exceeds factor x the median of its peers (leave-one-out)")
declare("PADDLE_ROUTER_CANARY_FRACTION", "float", 0.125, "router",
        "Fraction of a model's traffic routed to its canary replica "
        "while a new serial is on probation (the fleet-level x% canary)")
declare("PADDLE_ROUTER_HB_TIMEOUT_S", "float", 2.0, "router",
        "Replica heartbeat staleness beyond which the pool census "
        "declares the replica dead and re-spawns it")

# -- fault injection (PADDLE_FAULT_* family; deterministic test faults) --
declare("PADDLE_FAULT_", "prefix", None, "fault",
        "Family prefix: any PADDLE_FAULT_* key is part of the injection "
        "contract parsed by fluid.fault.FaultPlan.from_env")
declare("PADDLE_FAULT_KILL_STEP", "int", None, "fault",
        "Kill this process at training step N")
declare("PADDLE_FAULT_MODE", "str", "exit", "fault",
        "Crash flavor: hard process exit (default) or an in-process "
        "InjectedFault raise (exit|raise)")
declare("PADDLE_FAULT_RANK", "int", None, "fault",
        "Restrict armed faults to one trainer rank")
declare("PADDLE_FAULT_CKPT_CRASH", "str", None, "fault",
        "Crash inside checkpoint save (before|after the _SUCCESS commit)")
declare("PADDLE_FAULT_IO_DELAY_MS", "float", 0.0, "fault",
        "Inject IO delay into reader/prefetch paths (ms)")
declare("PADDLE_FAULT_NAN_VAR", "str", None, "fault",
        "Corrupt this state var with NaNs after a step")
declare("PADDLE_FAULT_NAN_STEP", "int", 0, "fault",
        "Step at which the NaN corruption fires")
declare("PADDLE_FAULT_GRAD_INF_STEP", "int", None, "fault",
        "Poison the backward seed with Inf at step N (in-graph)")
declare("PADDLE_FAULT_GRAD_INF_VALUE", "float", float("inf"), "fault",
        "Poison value for the grad-Inf injection")
declare("PADDLE_FAULT_LOSS_SPIKE_STEP", "int", None, "fault",
        "Multiply the observed loss at step N (spike injection)")
declare("PADDLE_FAULT_LOSS_SPIKE_FACTOR", "float", 1e4, "fault",
        "Spike multiplication factor")
declare("PADDLE_FAULT_BARRIER_STALL", "float", 0.0, "fault",
        "Stall this rank's barrier entry (seconds)")
declare("PADDLE_FAULT_SERVE_DELAY_MS", "float", 0.0, "fault",
        "Per-request serving delay injection (ms)")
declare("PADDLE_FAULT_SERVE_FAIL_EVERY", "int", 0, "fault",
        "Fail every Nth serving request with InjectedFault")
declare("PADDLE_FAULT_DECODE_STALL_MS", "float", 0.0, "fault",
        "Stall every continuous-batching decode tick (ms): deterministic "
        "inter-token-latency inflation, the serving.intertoken_s SLO "
        "breach oracle")
declare("PADDLE_FAULT_CKPT_POISON_SERIAL", "int", None, "fault",
        "NaN-poison checkpoint serial n at save time, committed WITH a "
        "valid _SUCCESS — the structurally-healthy bad checkpoint only "
        "the serving canary catches (hot-swap rollback oracle)")
declare("PADDLE_FAULT_CACHE_CORRUPT", "bool", False, "fault",
        "Deterministically corrupt the next compile-cache read")
declare("PADDLE_FAULT_DATA_STALL_MS", "float", 0.0, "fault",
        "Stall the input pipeline per pulled sample (ms)")
declare("PADDLE_FAULT_DATA_STALL_AT", "int", None, "fault",
        "Fire the data stall once, at this source sample cursor")
declare("PADDLE_FAULT_SHARD_CORRUPT", "bool", False, "fault",
        "Truncate the next data_state blob write (one-shot)")
declare("PADDLE_FAULT_MEM_PRESSURE", "float", 0.0, "fault",
        "Synthesize a memory leak: after PADDLE_FAULT_MEM_PRESSURE_AT "
        "ledger observations, add this many MB of phantom live bytes, "
        "doubling per observation (deterministic memory.live_bytes "
        "breach / budget-overrun oracle)")
declare("PADDLE_FAULT_MEM_PRESSURE_AT", "int", 8, "fault",
        "Ledger observation count at which the synthetic leak starts "
        "(past the SLO watchdog's min-samples baseline)")
declare("PADDLE_FAULT_STRAGGLER_RANK", "int", None, "fault",
        "Deterministic straggler oracle: slow down exactly this trainer "
        "rank (ignores PADDLE_FAULT_RANK — the two faults may target "
        "different ranks in one scenario)")
declare("PADDLE_FAULT_STRAGGLER_MS", "float", 0.0, "fault",
        "Per-step delay (ms) injected into the straggler rank's step "
        "boundary — inflates its window spans so the skew detector "
        "must flag it")
declare("PADDLE_FAULT_HOST_LOSS_RANK", "int", None, "fault",
        "Permanent host loss: this rank exits hard at the armed step "
        "boundary and drops a host_lost marker the supervisor census "
        "reads — the replacement fleet is SMALLER (mesh-ladder oracle)")
declare("PADDLE_FAULT_HOST_LOSS_AT_STEP", "int", 0, "fault",
        "Training step at which the host-loss fault fires")
declare("PADDLE_FAULT_REPLICA_KILL_AFTER", "int", None, "fault",
        "Serving-fleet replica death: kill the replica that served the "
        "n-th fleet request (one-shot) — the deterministic oracle for "
        "the router's re-spawn + cache-hit re-warm path")
declare("PADDLE_FAULT_IO_ERROR_RATE", "float", 0.0, "fault",
        "Transient-storage oracle: fraction of (path, op) keys whose "
        "FIRST read/write attempt raises OSError (seeded per-path hash; "
        "the retry always succeeds — bounded retry must recover, an "
        "unretried call site sees a hard failure)")
declare("PADDLE_FAULT_IO_ERROR_SEED", "int", 0, "fault",
        "Seed for the transient-I/O oracle's per-path failure hash")
declare("PADDLE_FAULT_KV_PAGE_LEAK", "int", None, "fault",
        "Paged-KV leak oracle: the page-pool allocator SKIPS the next n "
        "frees (one-shot), so kvpool.pages_free never returns to its "
        "initial level and the live-buffer ledger / SLO watchdog must "
        "surface the leak deterministically")
declare("PADDLE_FAULT_SPEC_DRAFT_POISON", "int", None, "fault",
        "Speculative-draft poison oracle: from engine tick n on, every "
        "drafted token is replaced with deterministic garbage, so "
        "acceptance collapses to ~1/vocab — the adaptive controller "
        "must fire specdec.fallback while emitted output stays bitwise "
        "correct (corrections are always the target argmax)")

# -- chaos engine (seeded multi-fault drills; paddle_tpu.chaos) --
declare("PADDLE_CHAOS_SEED", "int", None, "chaos",
        "Seed for the chaos schedule's deterministic K-fault plan "
        "sampling (python -m paddle_tpu.chaos run; CLI --seed overrides)")

# -- transient-I/O retry (fluid.retry, wraps durable-state read/write) --
declare("PADDLE_IO_RETRIES", "int", 3, "io",
        "Bounded attempts for transient OSErrors on checkpoint, census "
        "and manifest I/O (1 = no retry; corruption is never retried)")
declare("PADDLE_IO_RETRY_BASE_S", "float", 0.05, "io",
        "Base backoff delay between transient-I/O retries (seconds, "
        "doubling per attempt, capped at 2 s)")

# -- memory observability --
declare("PADDLE_MEM_BUDGET_MB", "float", None, "memory",
        "Per-device HBM budget: the AN502 pre-flight verifier pass and "
        "the live-buffer ledger diagnose programs/residency exceeding it")
declare("PADDLE_MEM_WATERMARK", "bool", True, "memory",
        "Emit memory.watermark run events (live/high-water bytes) at "
        "window boundaries (0 keeps the gauges but silences the events)")

# -- data plane --
declare("PADDLE_DATA_CKPT", "bool", True, "data",
        "Commit/restore checkpointable-reader state with checkpoints "
        "(0 falls back to legacy sample-skip replay)")
declare("PADDLE_DATA_STALL_EVENT_MS", "float", 100.0, "data",
        "Input waits above this emit a data.stall run event")


# ---------------------------------------------------------------------------
# docs/ENV.md generation
# ---------------------------------------------------------------------------


def _fmt_default(knob: EnvKnob) -> str:
    d = knob.default
    if d is None:
        return "unset"
    if isinstance(d, bool):
        return "1" if d else "0"
    if isinstance(d, float) and d == float("inf"):
        return "inf"
    return str(d)


def generate_markdown() -> str:
    lines = [
        "# Environment contract",
        "",
        "Every `PADDLE_*` knob the runtime reads, by subsystem.  GENERATED",
        "by `python -m paddle_tpu.fluid.envcontract > docs/ENV.md` from the",
        "declarations in `paddle_tpu/fluid/envcontract.py` — edit those,",
        "not this file (tier-1 `tools/repo_lint.py` diffs the two, and also",
        "fails on any `os.environ` read of an undeclared `PADDLE_*` key).",
        "",
    ]
    by_sub: Dict[str, List[EnvKnob]] = {}
    for k in knobs():
        by_sub.setdefault(k.subsystem, []).append(k)
    for sub in sorted(by_sub):
        lines.append(f"## {sub}")
        lines.append("")
        lines.append("| knob | type | default | description |")
        lines.append("|---|---|---|---|")
        for k in by_sub[sub]:
            typ = k.type if k.type != "enum" \
                else "enum(" + "|".join(k.choices) + ")"
            name = k.name + "*" if k.type == "prefix" else k.name
            lines.append(f"| `{name}` | {typ} | {_fmt_default(k)} "
                         f"| {k.help} |")
        lines.append("")
    return "\n".join(lines) + ""


if __name__ == "__main__":  # pragma: no cover - exercised via repo_lint
    print(generate_markdown())
