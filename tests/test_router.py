"""Serving fleet + router (ISSUE 17): least-loaded dispatch, bounded
queues with the last-chance shed gate, zero-shed failover on replica
death, and the fleet-level canary (x% traffic slice, fleet-wide promote,
bitwise-isolated rollback).

Two layers:
 - **Router unit tests** drive the :class:`Router` with STUB replicas
   (the duck-typed ``engine``/``load()``/``submit()``/``note_dead()``
   surface) — queueing/dispatch/requeue logic with no engines at all;
 - **Fleet tests** run one module-scoped two-replica fleet of real tiny
   decode engines through the canary lifecycle: a healthy serial
   promotes FLEET-WIDE, a poisoned serial rolls back on the canary
   replica with the sibling replica's weights bitwise untouched.

The kill-mid-load / cache-hit-respawn / spike-scale-out oracles live in
``tools/router_smoke.py`` (wired in at the bottom); definition order is
load-bearing under the tier-1 ``-p no:randomly`` contract: the promote
test must precede the poison test (serial 1, then serial 2).
"""

import os
import time
from concurrent.futures import Future

import numpy as np
import pytest

from paddle_tpu.fluid import fault as _fault
from paddle_tpu.models import transformer
from paddle_tpu.serving import (AutoscalePolicy, DecodeEngine,
                                EngineClosed, EngineOverloaded,
                                RequestTimeout, Router, RouterConfig,
                                ServingFleet, write_weights_serial)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# router unit tests (stub replicas, no engines)
# ---------------------------------------------------------------------------


class _StubEngine:
    def __init__(self):
        self.alive = True


class _StubReplica:
    """Duck-typed replica: resolves every submit immediately with a
    tag identifying which replica served it."""

    def __init__(self, name, load=0.0, fail=False):
        self.name = name
        self.engine = _StubEngine()
        self._load = load
        self.fail = fail
        self.served = 0
        self.dead_noted = 0

    def load(self):
        return self._load

    def submit(self, prompt_ids, max_new_tokens, timeout_ms=None):
        fut = Future()
        self.served += 1
        if self.fail:
            fut.set_exception(EngineClosed("stub replica down"))
        else:
            fut.set_result([self.name, list(prompt_ids),
                            int(max_new_tokens)])
        return fut

    def note_dead(self):
        self.dead_noted += 1
        self.engine.alive = False


def test_least_loaded_dispatch():
    light = _StubReplica("light", load=0.0)
    heavy = _StubReplica("heavy", load=9.0)
    with Router(lambda m, s: [light, heavy],
                RouterConfig(queue_hard=64)) as router:
        outs = [router.generate("m", [1, 2], 4) for _ in range(6)]
    assert all(o[0] == "light" for o in outs)
    assert heavy.served == 0


def test_dead_replica_fails_over_to_survivor():
    """An EngineClosed future is a replica death, not a client error:
    the request requeues at the front and a survivor serves it."""
    dying = _StubReplica("dying", load=0.0, fail=True)   # always picked
    backup = _StubReplica("backup", load=5.0)
    with Router(lambda m, s: [dying, backup],
                RouterConfig(queue_hard=64)) as router:
        out = router.generate("m", [7], 3)
    assert out[0] == "backup"
    assert dying.dead_noted >= 1


def test_retry_cap_bounds_replica_loss_loop():
    """A model whose every replica keeps eating requests must fail them
    after retry_limit losses, not spin forever."""

    class _Zombie(_StubReplica):
        def note_dead(self):       # claims alive, keeps failing
            self.dead_noted += 1

    zombie = _Zombie("zombie", fail=True)
    with Router(lambda m, s: [zombie],
                RouterConfig(queue_hard=64, retry_limit=2)) as router:
        fut = router.submit("m", [1], 2)
        with pytest.raises(EngineClosed, match="giving up"):
            fut.result(timeout=10)


def test_queue_hard_sheds_without_last_chance():
    with Router(lambda m, s: [], RouterConfig(queue_hard=2)) as router:
        futs = [router.submit("m", [1], 2) for _ in range(2)]
        with pytest.raises(EngineOverloaded):
            router.submit("m", [1], 2)
        assert router.shed_count("m") == 1
        router.stop()  # queued (undispatched) requests fail closed
        for f in futs:
            with pytest.raises(EngineClosed):
                f.result(timeout=10)


def test_last_chance_accepts_overflow():
    """The scale policy gets the final word: a True last_chance admits
    past queue_hard (capacity is on its way) — zero shed."""
    asked = []

    def last_chance(model_id):
        asked.append(model_id)
        return True

    with Router(lambda m, s: [], RouterConfig(queue_hard=2),
                last_chance=last_chance) as router:
        for _ in range(5):
            router.submit("m", [1], 2)
        assert router.queue_depth("m") == 5
        assert router.shed_count("m") == 0
        assert asked == ["m", "m", "m"]


def test_queues_are_per_model():
    """One model at its hard bound never sheds another model's traffic."""
    rep = _StubReplica("r0")
    with Router(lambda m, s: [rep] if m == "served" else [],
                RouterConfig(queue_hard=2)) as router:
        for _ in range(2):
            router.submit("starved", [1], 2)
        with pytest.raises(EngineOverloaded):
            router.submit("starved", [1], 2)
        assert router.generate("served", [5], 2)[0] == "r0"


def test_deadline_expires_in_queue():
    with Router(lambda m, s: [], RouterConfig(queue_hard=8)) as router:
        fut = router.submit("m", [1], 2, timeout_ms=30.0)
        with pytest.raises(RequestTimeout):
            fut.result(timeout=10)


# ---------------------------------------------------------------------------
# the fleet canary (real engines)
# ---------------------------------------------------------------------------


def _perturb(weights, seed, scale=0.05):
    rng = np.random.RandomState(seed)
    out = {}
    for name in sorted(weights):
        a = np.asarray(weights[name])
        if np.issubdtype(a.dtype, np.floating):
            out[name] = (a + scale * rng.normal(size=a.shape)
                         ).astype(a.dtype)
        else:
            out[name] = np.array(a, copy=True)
    return out


@pytest.fixture(scope="module")
def _cache_env(tmp_path_factory):
    """Shared compile store for the module fleet: replica 2 warms
    cache-hit-only, so the fixture costs one compile, not two.  The
    conftest autouse reset re-arms late-binding between tests; the env
    stays pinned for the module, so every re-resolve lands here."""
    from paddle_tpu import compile_cache as _cc

    old = os.environ.get("PADDLE_COMPILE_CACHE_DIR")
    os.environ["PADDLE_COMPILE_CACHE_DIR"] = \
        str(tmp_path_factory.mktemp("cc"))
    _cc.reset()
    yield
    if old is None:
        os.environ.pop("PADDLE_COMPILE_CACHE_DIR", None)
    else:
        os.environ["PADDLE_COMPILE_CACHE_DIR"] = old
    _cc.reset()


@pytest.fixture(scope="module")
def fleet(_cache_env, tmp_path_factory):
    def factory(labels):
        model = transformer.DecodeModel(
            cfg=transformer.decode_lm_config(), max_slots=2,
            max_len=32, prefill_buckets=[4], seed=5)
        return DecodeEngine(model, metrics_labels=labels)

    fl = ServingFleet(
        {"chat": factory},
        replicas=2,
        hb_dir=str(tmp_path_factory.mktemp("hb")),
        # pinned shape + idle monitor: tests drive poll_once() and the
        # canary probation completes after 2 canary-served requests.  The
        # straggler rule is part of the pin: it compares inter-token p50s
        # of two or three requests a replica, and on a loaded machine one
        # of them reads slow enough to be drained and replaced mid-test
        # (``ms.ready()`` then holds one replica; ROADMAP D11).  The rule's
        # own tests are tests/test_autoscale_policy.py's.
        policy=AutoscalePolicy(min_replicas=2, max_replicas=3,
                               cooldown_s=600.0, straggler_factor=1e9),
        canary_requests=2,
        canary_fraction=0.25,   # every 4th request probes the canary
        eval_s=30.0)
    fl.start(wait_ready_s=90.0)
    deadline = time.perf_counter() + 60.0
    while fl.status()["models"]["chat"]["ready"] < 2 \
            and time.perf_counter() < deadline:
        time.sleep(0.01)
    assert fl.status()["models"]["chat"]["ready"] == 2, \
        "two READY replicas of 'chat' never came within 60 s: " \
        f"{fl.status()['models']['chat']}"
    ckpt = str(tmp_path_factory.mktemp("ckpt"))
    fl.watch_checkpoints("chat", ckpt, serial=0)
    fl._ckpt_root_for_tests = ckpt
    yield fl
    fl.shutdown(timeout_s=30.0)


def _drive_until(fleet, pred, n=60, timeout_s=60.0):
    """Interleave traffic with monitor ticks until pred() holds; the
    canary slice only advances when requests actually flow."""
    prompt = [3, 5, 7]
    deadline = time.perf_counter() + timeout_s
    for _ in range(n):
        if pred() or time.perf_counter() > deadline:
            break
        fleet.generate("chat", prompt, 4)
        fleet.poll_once()
    return pred()


def test_fleet_canary_promotes_fleet_wide(fleet):
    ms = fleet._models["chat"]
    assert ms.registry is not None
    eng0 = ms.ready()[0].engine
    names = eng0.model.weight_names()
    w0 = eng0.snapshot_weights(names)
    prompt = [9, 11, 13]
    base = fleet.generate("chat", prompt, 6)

    write_weights_serial(fleet._ckpt_root_for_tests, 1,
                         _perturb(w0, seed=3))
    # discovery tick: the canary replica swaps to serial 1 on probation
    fleet.poll_once()
    assert ms.canary_routing
    assert ms.fleet_serial == 0   # the FLEET is still on serial 0

    # the sibling keeps serving serial 0 while probation runs: only the
    # canary slice sees serial 1
    canary = ms.canary_replica()
    sibling = next(r for r in ms.ready() if r is not canary)
    assert sibling.engine.generate(prompt, 6) == base

    # traffic drives the probation; a survived canary promotes and the
    # fleet rolls serial 1 out to every sibling
    assert _drive_until(fleet, lambda: ms.fleet_serial == 1)
    assert not ms.canary_routing
    served_new = [r.engine.generate(prompt, 6) for r in ms.ready()]
    assert served_new[0] == served_new[1]       # fleet-consistent
    assert served_new[0] != base                # actually the new serial


def test_fleet_canary_poison_rolls_back_sibling_untouched(fleet):
    """The poison oracle at fleet scope: a NaN serial trips the canary
    sentinel and rolls back — the sibling replica's weights are BITWISE
    untouched and the fleet serial never moves."""
    ms = fleet._models["chat"]
    canary = ms.canary_replica()
    sibling = next(r for r in ms.ready() if r is not canary)
    names = sibling.engine.model.weight_names()
    w_sib = sibling.engine.snapshot_weights(names)
    w1 = canary.engine.snapshot_weights(names)
    prompt = [9, 11, 13]
    base = fleet.generate("chat", prompt, 6)

    _fault.install(_fault.FaultPlan(ckpt_poison_serial=2))
    try:
        write_weights_serial(fleet._ckpt_root_for_tests, 2,
                             _perturb(w1, seed=4))
    finally:
        _fault.clear()
    fleet.poll_once()
    assert ms.canary_routing   # serial 2 on probation (canary slice)

    assert _drive_until(
        fleet, lambda: ms.registry is not None
        and ms.registry.vetoed() == [2])
    assert not ms.canary_routing
    assert ms.fleet_serial == 1                 # never advanced
    w_sib_after = sibling.engine.snapshot_weights(names)
    assert all(np.array_equal(np.asarray(w_sib[n]),
                              np.asarray(w_sib_after[n])) for n in names)
    # post-rollback: every replica still serves serial 1, bitwise
    assert [r.engine.generate(prompt, 6) for r in ms.ready()] \
        == [base, base]
    assert fleet.status()["models"]["chat"]["shed"] == 0


# ---------------------------------------------------------------------------
# the smoke tool (kill mid-load / cache-hit respawn / spike scale-out)
# ---------------------------------------------------------------------------


def test_router_smoke_tool_runs_clean(tmp_path, monkeypatch):
    """tools/router_smoke.py is the tier-1 fleet smoke: 2 models x 2
    replicas warm off one compile; a fault-injected replica kill fails
    over bitwise with zero shed and re-spawns cache-hit-only
    (warmup_dispatches == 0); a load spike scales out strictly before
    any shed."""
    import sys

    monkeypatch.setenv("PADDLE_COMPILE_CACHE_DIR",
                       str(tmp_path / "cache"))
    # the smoke is about a kill, a respawn and a spike, not stragglers: on
    # a loaded machine the straggler rule drains a replica on the p50 of a
    # few requests and the report counts a second respawn (ROADMAP D11)
    monkeypatch.setenv("PADDLE_ROUTER_STRAGGLER_FACTOR", "1e9")
    sys.path.insert(0, REPO)
    try:
        import tools.router_smoke as smoke

        report = smoke.main()
    finally:
        sys.path.remove(REPO)
    assert report["ok"], report
