"""AutoscalePolicy unit tests (ISSUE 17): pure signal streams in, exact
decisions out — no engines, no threads, no clocks.  Every test passes
explicit ``now`` timestamps, so hysteresis and cooldown arithmetic is
fully deterministic.

The signal classes under test (the policy's whole job is telling these
apart):
 - *queue pressure*  -> ``scale_out`` after ``hysteresis_ticks``;
 - *SLO breaches*    -> ``scale_out`` even with an empty queue (the
   cumulative breach counter ADVANCING is the signal, not its level);
 - *compile stall*   -> ``wait`` while any replica is warming, however
   bad the queue looks — capacity is already on its way;
 - *straggler*       -> ``drain_replica`` naming the slow replica
   (leave-one-out median over sibling inter-token p50s);
 - *idle*            -> ``scale_in`` down to ``min_replicas``, gated by
   BOTH the scale cooldown and a startup grace from first sight.
"""

import pytest

from paddle_tpu.serving import AutoscalePolicy, ModelSignals


def _policy(**kw):
    """Exact knobs (never the env): hysteresis 2, cooldown 5 s."""
    base = dict(max_replicas=4, min_replicas=1, cooldown_s=5.0,
                queue_high=8, queue_low=1, hysteresis_ticks=2,
                straggler_factor=3.0)
    base.update(kw)
    return AutoscalePolicy(**base)


def _sig(**kw):
    base = dict(queue_depth=0, replicas_ready=2, replicas_warming=0,
                slots_active=4, slots_total=8, breaches=0)
    base.update(kw)
    return ModelSignals(**base)


# ---------------------------------------------------------------------------
# queue pressure
# ---------------------------------------------------------------------------


def test_queue_pressure_scales_out_after_hysteresis():
    p = _policy()
    assert p.decide("m", _sig(queue_depth=20), now=0.0).action == "none"
    d = p.decide("m", _sig(queue_depth=20), now=1.0)
    assert (d.action, d.reason) == ("scale_out", "queue_pressure")


def test_single_pressure_tick_never_scales():
    """Hysteresis: a one-tick blip resets; the fleet shape is stable."""
    p = _policy()
    assert p.decide("m", _sig(queue_depth=20), now=0.0).action == "none"
    assert p.decide("m", _sig(queue_depth=0, slots_active=8),
                    now=1.0).action == "none"
    # the counter reset: pressure must re-earn both ticks
    assert p.decide("m", _sig(queue_depth=20), now=2.0).action == "none"
    assert p.decide("m", _sig(queue_depth=20),
                    now=3.0).action == "scale_out"


def test_scale_out_bounded_by_max_replicas():
    p = _policy(max_replicas=2)
    sig = _sig(queue_depth=20, replicas_ready=2)
    p.decide("m", sig, now=0.0)
    d = p.decide("m", sig, now=1.0)
    assert (d.action, d.reason) == ("none", "at_max_replicas")


def test_warming_replica_counts_toward_the_cap():
    """ready+warming at max: the in-flight spawn IS the capacity."""
    p = _policy(max_replicas=3)
    sig = _sig(queue_depth=20, replicas_ready=2, replicas_warming=1)
    assert p.decide("m", sig, now=0.0).action == "wait"


def test_cooldown_blocks_back_to_back_scale_outs():
    p = _policy()
    sig = _sig(queue_depth=20)
    p.decide("m", sig, now=0.0)
    assert p.decide("m", sig, now=1.0).action == "scale_out"
    # pressure persists: hysteresis re-arms but cooldown holds the line
    p.decide("m", sig, now=2.0)
    d = p.decide("m", sig, now=3.0)
    assert (d.action, d.reason) == ("wait", "cooldown")
    # the over-streak rides THROUGH the cooldown: the first tick past
    # the window scales without re-earning hysteresis from zero
    assert p.decide("m", sig, now=6.5).action == "scale_out"


# ---------------------------------------------------------------------------
# SLO breaches
# ---------------------------------------------------------------------------


def test_breach_stream_scales_out_with_empty_queue():
    """slo.breach events arrive (cumulative counter advances) while the
    queue stays empty: latency pressure without depth pressure."""
    p = _policy()
    assert p.decide("m", _sig(breaches=1), now=0.0).action == "none"
    d = p.decide("m", _sig(breaches=3), now=1.0)
    assert (d.action, d.reason) == ("scale_out", "slo_breach")


def test_flat_breach_counter_is_not_pressure():
    """The LEVEL of the cumulative counter is history, not signal: only
    a delta since the last tick counts."""
    p = _policy()
    p.decide("m", _sig(breaches=5), now=0.0)   # delta 5: over tick 1
    # counter stays at 5: no new breaches — the over streak breaks and
    # the policy never scales however long the level persists
    assert p.decide("m", _sig(breaches=5), now=1.0).action == "none"
    assert p.decide("m", _sig(breaches=5), now=2.0).action == "none"
    assert p.decide("m", _sig(breaches=5), now=3.0).action == "none"


# ---------------------------------------------------------------------------
# compile stall (warming replica)
# ---------------------------------------------------------------------------


def test_warming_replica_means_wait_not_scale():
    """Queue pressure WHILE capacity warms is a compile stall: stacking
    another spawn on top would thrash the device pool."""
    p = _policy()
    sig = _sig(queue_depth=50, replicas_warming=1)
    for now in (0.0, 1.0, 2.0, 3.0):
        d = p.decide("m", sig, now=now)
        assert (d.action, d.reason) == ("wait", "replica_warming")


def test_warming_resets_hysteresis_streaks():
    p = _policy()
    p.decide("m", _sig(queue_depth=20), now=0.0)        # over tick 1
    p.decide("m", _sig(queue_depth=20, replicas_warming=1), now=1.0)
    # the warming tick cleared the streak: pressure starts from zero
    assert p.decide("m", _sig(queue_depth=20), now=2.0).action == "none"
    assert p.decide("m", _sig(queue_depth=20),
                    now=3.0).action == "scale_out"


# ---------------------------------------------------------------------------
# straggler
# ---------------------------------------------------------------------------


def test_straggler_drained_by_name():
    p = _policy()
    d = p.decide("m", _sig(replicas_ready=3, intertoken_p50_ms={
        "m-r0": 10.0, "m-r1": 11.0, "m-r2": 40.0}), now=0.0)
    assert d.action == "drain_replica"
    assert d.replica == "m-r2"
    assert "straggler" in d.reason


def test_straggler_needs_two_ready_replicas():
    """One replica has no siblings to be slow against."""
    p = _policy()
    d = p.decide("m", _sig(replicas_ready=1,
                           intertoken_p50_ms={"m-r0": 500.0}), now=0.0)
    assert d.action != "drain_replica"


def test_uniform_slowness_is_not_a_straggler():
    """Everyone slow = load problem, not a bad replica (and with an
    over-threshold queue it becomes scale-out pressure instead)."""
    p = _policy()
    sig = _sig(replicas_ready=3, queue_depth=20, intertoken_p50_ms={
        "m-r0": 40.0, "m-r1": 41.0, "m-r2": 42.0})
    p.decide("m", sig, now=0.0)
    assert p.decide("m", sig, now=1.0).action == "scale_out"


def test_straggler_respects_cooldown():
    """A drain counts as a scaling action: no replace-storm."""
    p = _policy()
    sig = _sig(replicas_ready=3, intertoken_p50_ms={
        "m-r0": 10.0, "m-r1": 11.0, "m-r2": 40.0})
    assert p.decide("m", sig, now=0.0).action == "drain_replica"
    assert p.decide("m", sig, now=1.0).action != "drain_replica"
    assert p.decide("m", sig, now=6.0).action == "drain_replica"


# ---------------------------------------------------------------------------
# scale-in
# ---------------------------------------------------------------------------


def test_idle_scales_in_after_grace():
    p = _policy()
    idle = _sig(queue_depth=0, slots_active=0, replicas_ready=3)
    assert p.decide("m", idle, now=0.0).action == "none"
    # hysteresis met but the startup grace (now - birth) holds it
    d = p.decide("m", idle, now=1.0)
    assert (d.action, d.reason) == ("none", "cooldown")
    d = p.decide("m", idle, now=6.0)
    assert (d.action, d.reason) == ("scale_in", "idle")


def test_scale_in_bounded_by_min_replicas():
    p = _policy(min_replicas=2)
    idle = _sig(queue_depth=0, slots_active=0, replicas_ready=2)
    p.decide("m", idle, now=0.0)
    d = p.decide("m", idle, now=6.0)
    assert (d.action, d.reason) == ("none", "at_min_replicas")


def test_busy_slots_block_scale_in():
    """Empty queue but >25% slot utilization: the fleet is WORKING
    through resident requests, not idle."""
    p = _policy()
    busy = _sig(queue_depth=0, slots_active=4, slots_total=8,
                replicas_ready=3)
    for now in (0.0, 6.0, 12.0):
        assert p.decide("m", busy, now=now).action == "none"


def test_models_keep_independent_state():
    """Two models' streams through one policy never cross-talk."""
    p = _policy()
    hot = _sig(queue_depth=20)
    idle = _sig(queue_depth=0, slots_active=0, replicas_ready=3)
    p.decide("hot", hot, now=0.0)
    p.decide("idle", idle, now=0.0)
    assert p.decide("hot", hot, now=1.0).action == "scale_out"
    assert p.decide("idle", idle, now=6.0).action == "scale_in"


# ---------------------------------------------------------------------------
# env-contract defaults
# ---------------------------------------------------------------------------


def test_knobs_default_from_env_contract(monkeypatch):
    monkeypatch.setenv("PADDLE_ROUTER_MAX_REPLICAS", "7")
    monkeypatch.setenv("PADDLE_ROUTER_QUEUE_HIGH", "33")
    p = AutoscalePolicy()
    assert p.max_replicas == 7
    assert p.queue_high == 33


def test_constructor_overrides_env(monkeypatch):
    monkeypatch.setenv("PADDLE_ROUTER_MAX_REPLICAS", "7")
    assert AutoscalePolicy(max_replicas=2).max_replicas == 2
