"""Elastic fleet controller (serving/fleet.py, ISSUE 14).

Covers the tentpole's acceptance bar on the mock-device (CPU
tiny-engine) cluster:

  * a forced drain live-migrates 100% of a replica's resident sessions
    through the handoff path, with temp-0 BIT-EQUALITY vs the no-drain
    monolithic baseline — greedy, grammar-constrained JSON, and
    speculative — cached-token parity on the resumed round, and ZERO
    leaked handoff envelopes;
  * a synthetic signal trace replayed twice through the FleetController
    yields the IDENTICAL action ledger (deterministic policy), with
    hysteresis and cooldown semantics asserted tick by tick;
  * router graceful ``mark_draining`` (ISSUE 14 satellite): excluded
    from new placements, affinities survive until each migration lands
    — distinct from ``mark_failed``;
  * live scale-up/scale-down (replica registration/retirement) and the
    re-tier role flip, all bit-equality-gated;
  * registry coherence: quoracle_fleet_* instruments, TOPIC_FLEET ring,
    fleet_* flight events, the fleet.migrate chaos point, /api/fleet,
    pool_sizing's fleet envelope, and Runtime flag refusal.
"""

import pytest

from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
from quoracle_tpu.serving.cluster import ClusterPlane
from quoracle_tpu.serving.fleet import (
    FleetAction, FleetConfig, FleetController, FleetSignals,
    ReplicaSignal,
)

MEMBER = "xla:tiny"
MSGS = [{"role": "user", "content": "hello elastic fleet, please "
                                    "elaborate at length"}]


def req(msgs=MSGS, sid=None, cj=False, max_tokens=20):
    return QueryRequest(MEMBER, msgs, temperature=0.0,
                        max_tokens=max_tokens, session_id=sid,
                        constrain_json=cj)


@pytest.fixture(scope="module")
def mono():
    b = TPUBackend([MEMBER], continuous_chunk=8)
    yield b
    b.close()


@pytest.fixture(scope="module")
def cluster():
    """1 prefill + 2 decode replicas: a drain always has a live
    migration target."""
    c = ClusterPlane.build([MEMBER], replicas=3, disaggregate=True,
                           continuous_chunk=8)
    yield c
    c.close()


@pytest.fixture(scope="module")
def fleet(cluster):
    return FleetController(cluster, FleetConfig(
        min_replicas=1, max_replicas=4, hysteresis_ticks=2,
        cooldown_ticks=2, seed=7))


# ---------------------------------------------------------------------------
# Drain-migration equality (the acceptance gate)
# ---------------------------------------------------------------------------

def _drain_round_trip(mono, cluster, fleet, sid, cj=False):
    """Round 1 lands the session on a decode replica; a forced drain
    live-migrates it; round 2 must resume on the NEW replica bit-equal
    to the monolithic run with cached-token parity."""
    a1 = mono.query([req(sid=sid, cj=cj)])[0]
    b1 = cluster.query([req(sid=sid, cj=cj)])[0]
    assert a1.ok and b1.ok, (a1.error, b1.error)
    assert b1.text == a1.text
    src = cluster.router.affinity_of(sid)
    assert src is not None and src.role == "decode"
    summary = fleet.drain(src.replica_id, reason="test")
    assert summary["migrated"] >= 1 and summary["failed"] == 0
    assert not summary["died"]
    dst = cluster.router.affinity_of(sid)
    assert dst is not None and dst.replica_id != src.replica_id
    # zero envelope leaks: every migrated session's envelope forgotten
    assert cluster.handoff.stats()["inflight"] == 0
    msgs2 = MSGS + [{"role": "assistant", "content": a1.text},
                    {"role": "user", "content": "continue."}]
    exports_before = cluster.handoff.exports
    a2 = mono.query([req(msgs2, sid=sid, cj=cj)])[0]
    b2 = cluster.query([req(msgs2, sid=sid, cj=cj)])[0]
    assert a2.ok and b2.ok, (a2.error, b2.error)
    assert b2.text == a2.text
    # the resumed round rode the MIGRATED pages: no new handoff, and
    # the cached-token count matches the never-drained monolithic run
    assert cluster.handoff.exports == exports_before
    assert b2.cached_tokens == a2.cached_tokens > 0
    cluster.drop_session(sid)
    mono.drop_session(sid)
    return summary


def test_drain_migration_greedy_bit_equal(mono, cluster, fleet):
    _drain_round_trip(mono, cluster, fleet, "fleet-g1")


def test_drain_migration_constrained_bit_equal(mono, cluster, fleet):
    _drain_round_trip(mono, cluster, fleet, "fleet-c1", cj=True)


def test_drain_migration_speculative_bit_equal():
    """Sessions migrated mid-stream compose with the decode tier's
    speculative path bit-exactly: the migrated pages resume under
    draft/verify rounds."""
    mono = TPUBackend([MEMBER], continuous_chunk=8,
                      draft_map={MEMBER: MEMBER}, draft_k=4)
    cl = ClusterPlane.build([MEMBER], replicas=3, disaggregate=True,
                            continuous_chunk=8,
                            draft_map={MEMBER: MEMBER}, draft_k=4)
    fc = FleetController(cl)
    try:
        a1 = mono.query([req(sid="fleet-sp", cj=True,
                             max_tokens=24)])[0]
        b1 = cl.query([req(sid="fleet-sp", cj=True, max_tokens=24)])[0]
        assert a1.ok and b1.ok, (a1.error, b1.error)
        assert b1.text == a1.text
        src = cl.router.affinity_of("fleet-sp")
        summary = fc.drain(src.replica_id, reason="test")
        assert summary["migrated"] >= 1 and not summary["died"]
        msgs2 = MSGS + [{"role": "assistant", "content": a1.text},
                        {"role": "user", "content": "continue."}]
        a2 = mono.query([req(msgs2, sid="fleet-sp", cj=True,
                             max_tokens=24)])[0]
        b2 = cl.query([req(msgs2, sid="fleet-sp", cj=True,
                           max_tokens=24)])[0]
        assert a2.ok and b2.ok, (a2.error, b2.error)
        assert b2.text == a2.text
        assert b2.cached_tokens == a2.cached_tokens > 0
        assert b2.spec_rounds > 0         # the migrated row drafted
        assert cl.handoff.stats()["inflight"] == 0
    finally:
        mono.close()
        cl.close()


def test_forced_drain_migrates_every_resident_session(cluster, fleet):
    """100% of a draining replica's sessions move: park several
    sessions on one decode replica, drain it, and assert the summary
    counted every one with the source replica EMPTY afterward."""
    sids = [f"fleet-all{i}" for i in range(3)]
    for sid in sids:
        out = cluster.query([req(sid=sid, max_tokens=10)])[0]
        assert out.ok, out.error
    src = cluster.router.affinity_of(sids[0])
    eng = src.backend.engines[MEMBER]
    with eng.sessions.lock:
        resident = len(eng.sessions._sessions) \
            + len(eng.sessions.tier.host.sessions)
    assert resident >= 1
    summary = fleet.drain(src.replica_id, reason="migrate-all")
    assert summary["migrated"] == resident
    assert summary["failed"] == 0
    with eng.sessions.lock:
        assert not eng.sessions._sessions
        assert not eng.sessions.tier.host.sessions
    assert cluster.handoff.stats()["inflight"] == 0
    for sid in sids:
        rep = cluster.router.affinity_of(sid)
        assert rep is None or rep.replica_id != src.replica_id
        cluster.drop_session(sid)


# ---------------------------------------------------------------------------
# Deterministic policy (the ledger-replay acceptance gate)
# ---------------------------------------------------------------------------

def _trace():
    """A synthetic signal trace exercising scale-up (burn), re-tier
    (prefill-starved mix), and scale-down (idle)."""
    ticks = []
    for t in range(24):
        if 1 <= t <= 5:
            dec_depth, pre_depth, burn = 12.0, 0.0, 1.8
        elif 8 <= t <= 12:
            dec_depth, pre_depth, burn = 0.5, 9.0, 0.0
        else:
            dec_depth, pre_depth, burn = 0.0, 0.0, 0.0
        ticks.append(FleetSignals(replicas=(
            ReplicaSignal("prefill-0", "prefill", pre_depth),
            ReplicaSignal("decode-1", "decode", dec_depth),
            ReplicaSignal("decode-2", "decode", dec_depth),
            ReplicaSignal("decode-3", "decode", dec_depth),
        ), slo_burn=burn))
    return ticks


def test_synthetic_trace_replay_identical_ledger():
    cfg = FleetConfig(min_replicas=2, max_replicas=4,
                      hysteresis_ticks=2, cooldown_ticks=2, seed=11)
    a = FleetController(None, cfg)
    b = FleetController(None, cfg)
    for sig in _trace():
        a.tick(sig)
    for sig in _trace():
        b.tick(sig)
    assert a.ledger_tuples() == b.ledger_tuples()
    actions = [t[1] for t in a.ledger_tuples()]
    assert "scale_up" in actions
    assert "retier" in actions
    assert "scale_down" in actions
    # the ledger is replayable wholesale: tick, target, role, AND the
    # reason string are all pure functions of the trace
    assert all(len(t) == 5 and t[4] for t in a.ledger_tuples())


def test_seed_changes_tie_breaks_not_structure():
    """Different seeds may pick different equally-loaded victims but
    never invent different action kinds for the same trace."""
    cfg7 = FleetConfig(min_replicas=2, max_replicas=4,
                       hysteresis_ticks=2, cooldown_ticks=2, seed=7)
    cfg8 = FleetConfig(min_replicas=2, max_replicas=4,
                       hysteresis_ticks=2, cooldown_ticks=2, seed=8)
    a = FleetController(None, cfg7)
    b = FleetController(None, cfg8)
    for sig in _trace():
        a.tick(sig)
        b.tick(sig)
    assert [t[:2] for t in a.ledger_tuples()] \
        == [t[:2] for t in b.ledger_tuples()]


def test_hysteresis_and_cooldown():
    """One pressured tick never acts (hysteresis); after an action the
    cooldown window holds even under continued pressure."""
    cfg = FleetConfig(min_replicas=1, max_replicas=4,
                      hysteresis_ticks=2, cooldown_ticks=3, seed=0)
    fc = FleetController(None, cfg)
    burn = FleetSignals(replicas=(
        ReplicaSignal("decode-1", "unified", 20.0),), slo_burn=2.0)
    assert fc.tick(burn) is None          # 1 tick < hysteresis bound
    act = fc.tick(burn)
    assert act is not None and act.action == "scale_up"
    for _ in range(cfg.cooldown_ticks):   # cooldown holds under burn
        assert fc.tick(burn) is None
    # pressure persisted through the cooldown: the next evaluated
    # ticks re-accumulate the streak from zero
    assert fc.tick(burn) is None
    assert fc.tick(burn).action == "scale_up"


def test_scale_bounds_respected():
    cfg = FleetConfig(min_replicas=1, max_replicas=1,
                      hysteresis_ticks=1, cooldown_ticks=0, seed=0)
    fc = FleetController(None, cfg)
    one = FleetSignals(replicas=(
        ReplicaSignal("unified-0", "unified", 50.0),), slo_burn=3.0)
    assert fc.tick(one) is None           # at max: no scale-up
    idle = FleetSignals(replicas=(
        ReplicaSignal("unified-0", "unified", 0.0),), slo_burn=0.0)
    assert fc.tick(idle) is None          # at min: no scale-down
    assert fc.ledger() == []


# ---------------------------------------------------------------------------
# Router draining semantics (ISSUE 14 satellite)
# ---------------------------------------------------------------------------

def test_router_mark_draining_vs_mark_failed():
    from quoracle_tpu.serving.router import ClusterRouter

    class _Rep:
        def __init__(self, rid, role):
            self.replica_id, self.role = rid, role
            self.alive = True
            self.backend = type("B", (), {"qos_controller": None,
                                          "scheduler_stats":
                                          staticmethod(dict)})()

    router = ClusterRouter()
    a, b = _Rep("decode-a", "decode"), _Rep("decode-b", "decode")
    router.register(a)
    router.register(b)
    router.set_affinity("s1", "decode-a")
    router.mark_draining("decode-a")
    # excluded from NEW placements...
    assert [r.replica_id for r in router.replicas("decode")] \
        == ["decode-b"]
    assert router.place("decode").replica_id == "decode-b"
    # ...but the affinity SURVIVES and still places (no spurious cold
    # re-prefill mid-drain) — the difference from mark_failed
    assert router.affinity_of("s1").replica_id == "decode-a"
    assert router.place("decode", session_id="s1").replica_id \
        == "decode-a"
    assert router.is_draining("decode-a")
    router.clear_draining("decode-a")
    assert len(router.replicas("decode")) == 2
    # mark_failed purges the affinity outright
    router.mark_failed("decode-a", "test")
    assert router.affinity_of("s1") is None
    # revive restores placement with a clean slate
    assert router.revive("decode-a")
    assert a.alive and len(router.replicas("decode")) == 2
    # deregister removes entirely, dropping its affinities
    router.set_affinity("s2", "decode-b")
    router.deregister("decode-b")
    assert router.affinity_of("s2") is None
    assert [r.replica_id for r in router.replicas("decode")] \
        == ["decode-a"]


# ---------------------------------------------------------------------------
# Live scale + re-tier
# ---------------------------------------------------------------------------

def test_live_scale_up_and_retire(mono, cluster, fleet):
    n0 = len(cluster.replicas)
    rep = cluster.add_replica("decode")
    assert len(cluster.replicas) == n0 + 1
    assert rep.replica_id in cluster.router.stats()["replicas"]
    # the new replica actually serves: park a session on it by load
    # (it is the emptiest) and check bit-equality
    want = mono.query([req(max_tokens=10)])[0]
    got = cluster.query([req(max_tokens=10)])[0]
    assert got.ok and got.text == want.text
    summary = fleet.drain(rep.replica_id, retire=True,
                          reason="retire-test")
    assert not summary["died"]
    assert len(cluster.replicas) == n0
    assert rep.replica_id not in cluster.router.stats()["replicas"]


def test_live_retier_round_trip(mono, cluster, fleet):
    """decode → prefill → decode: the flip drains first, the flipped
    replica serves its new role, and outputs never move a bit."""
    want = mono.query([req(max_tokens=10)])[0]
    victim = sorted(r.replica_id for r in cluster.replicas
                    if r.role == "decode")[0]
    fleet.drain(victim, new_role="prefill", reason="retier-test")
    roles = {r.replica_id: r.role for r in cluster.replicas}
    assert roles[victim] == "prefill"
    got = cluster.query([req(max_tokens=10)])[0]
    assert got.ok and got.text == want.text
    fleet.drain(victim, new_role="decode", reason="retier-back")
    assert next(r.role for r in cluster.replicas
                if r.replica_id == victim) == "decode"
    got2 = cluster.query([req(max_tokens=10)])[0]
    assert got2.ok and got2.text == want.text


def test_policy_tick_executes_on_live_plane(cluster, fleet):
    """A burn trace through tick() drives a REAL scale-up on the plane
    (the executed ledger entry carries the plane-assigned id)."""
    n0 = len(cluster.replicas)

    def burn():
        return FleetSignals(replicas=tuple(
            ReplicaSignal(r.replica_id, r.role,
                          30.0 if r.role == "decode" else 0.0)
            for r in cluster.replicas), slo_burn=2.0)

    fc = FleetController(cluster, FleetConfig(
        min_replicas=1, max_replicas=n0 + 1, hysteresis_ticks=2,
        cooldown_ticks=0, seed=1))
    assert fc.tick(burn()) is None
    act = fc.tick(burn())
    assert act is not None and act.action == "scale_up"
    assert len(cluster.replicas) == n0 + 1
    assert any(r.replica_id == act.target for r in cluster.replicas)
    # retire it again so the module fixtures see the original topology
    fc.drain(act.target, retire=True, reason="cleanup")
    assert len(cluster.replicas) == n0


# ---------------------------------------------------------------------------
# Chaos point: replica killed during its own drain
# ---------------------------------------------------------------------------

def test_drain_killed_mid_drain_degrades_structurally(mono):
    from quoracle_tpu.chaos.faults import CHAOS, FaultPlan, FaultRule
    cl = ClusterPlane.build([MEMBER], replicas=3, disaggregate=True,
                            continuous_chunk=8)
    fc = FleetController(cl)
    try:
        a1 = mono.query([req(sid="fleet-kill")])[0]
        b1 = cl.query([req(sid="fleet-kill")])[0]
        assert b1.text == a1.text
        src = cl.router.affinity_of("fleet-kill")
        plan = FaultPlan(3, [FaultRule("fleet.migrate", "crash",
                                       max_fires=1)])
        with CHAOS.arming(plan):
            summary = fc.drain(src.replica_id, retire=True,
                               reason="killed")
        assert summary["died"] and summary["failed"] >= 1
        # the corpse left the topology; its affinity purged
        assert src.replica_id not in cl.router.stats()["replicas"]
        assert cl.router.affinity_of("fleet-kill") is None
        assert cl.handoff.stats()["inflight"] == 0
        # the session re-prefills cold on a survivor — bits unchanged.
        # Drop the monolithic twin too: the honest comparison is cold
        # vs cold, exactly what a client sees after the replica died
        # (a resumed-vs-cold diff would measure tokenizer round-trip
        # asymmetry on the gibberish tiny-model text, not recovery).
        mono.drop_session("fleet-kill")
        msgs2 = MSGS + [{"role": "assistant", "content": a1.text},
                        {"role": "user", "content": "continue."}]
        a2 = mono.query([req(msgs2, sid="fleet-kill")])[0]
        b2 = cl.query([req(msgs2, sid="fleet-kill")])[0]
        assert a2.ok and b2.ok, (a2.error, b2.error)
        assert b2.text == a2.text
        mono.drop_session("fleet-kill")
    finally:
        cl.close()


# ---------------------------------------------------------------------------
# Registries, payloads, wiring
# ---------------------------------------------------------------------------

def test_fleet_registry_coherence():
    from quoracle_tpu.chaos.faults import INJECTION_POINTS
    from quoracle_tpu.infra.bus import TOPIC_FLEET
    from quoracle_tpu.infra.flightrec import FLIGHT_EVENTS
    from quoracle_tpu.infra.telemetry import METRICS

    assert TOPIC_FLEET == "fleet:events"
    for kind in ("fleet_action", "fleet_drain", "fleet_migrate_failed",
                 "fabric_peer_rejoin"):
        assert kind in FLIGHT_EVENTS
    assert "fleet.migrate" in INJECTION_POINTS
    text = METRICS.render_prometheus()
    for name in ("quoracle_fleet_actions_total",
                 "quoracle_fleet_ticks_total",
                 "quoracle_fleet_sessions_migrated_total",
                 "quoracle_fleet_drain_ms",
                 "quoracle_fleet_draining"):
        assert name in text


def test_fleet_stats_payload(cluster, fleet):
    st = fleet.stats()
    assert st["enabled"] and not st["dry_run"]
    assert st["config"]["max_replicas"] == 4
    assert "router" in st and "ledger" in st
    assert st["drains"] >= 1              # earlier tests drained


def test_fleet_events_ring_and_panel(cluster, fleet):
    """TOPIC_FLEET events ring in EventHistory and the telemetry panel
    renders the ledger."""
    from quoracle_tpu.infra.bus import EventBus
    from quoracle_tpu.infra.event_history import EventHistory
    from quoracle_tpu.web.views import fleet_panel

    bus = EventBus()
    history = EventHistory(bus)
    cluster.attach_bus(bus)
    try:
        src = None
        out = cluster.query([req(sid="fleet-ring", max_tokens=8)])[0]
        assert out.ok
        src = cluster.router.affinity_of("fleet-ring")
        fleet.drain(src.replica_id, reason="ring-test")
        events = history.replay_fleet()
        assert any(e.get("event") == "fleet_drain" for e in events)
    finally:
        cluster.drop_session("fleet-ring")
        history.close()
    html = fleet_panel(fleet.stats())
    assert "elastic fleet" in html and "fleet-state" in html


def test_pool_sizing_fleet_envelope():
    from quoracle_tpu.parallel.mesh import pool_sizing
    plan = pool_sizing(["llama-3-8b"], n_devices=8, replicas=4,
                       disaggregate=True, host_kv_mb=256,
                       fleet_min=1, fleet_max=4)
    f = plan["fleet"]
    assert f["serving_role"] == "decode"
    assert f["max_replicas"] == 4
    assert f["resident_sessions_max"] \
        == 4 * (f["resident_sessions_min"] // 1)
    assert isinstance(f["fits_at_max"], bool)


def test_runtime_refuses_fleet_without_cluster():
    from quoracle_tpu.runtime import Runtime, RuntimeConfig
    with pytest.raises(ValueError, match="--fleet-max"):
        Runtime(RuntimeConfig(backend="mock", fleet_max=4))


def test_fleet_config_validation():
    with pytest.raises(ValueError):
        FleetConfig(min_replicas=0).validate()
    with pytest.raises(ValueError):
        FleetConfig(min_replicas=3, max_replicas=2).validate()
    assert isinstance(
        FleetAction(1, "drain", "r", "decode", "x").as_dict(), dict)
