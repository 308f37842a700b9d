"""Disaggregated serving plane (serving/cluster.py, ISSUE 10).

Covers the subsystem's acceptance bar end to end on a mock-device
(CPU tiny-engine) cluster:

  * temp-0 BIT-EQUALITY of a prompt prefilled on a prefill replica and
    decoded on a decode replica vs the same prompt on a monolithic
    backend — greedy, grammar-constrained JSON, and speculative;
  * session affinity: round 2 of a conversation resumes on the decode
    replica holding its pages with cached-token parity;
  * degraded modes: decode-replica death mid-stream (re-placed via the
    retained handoff envelope, or failed with a structured error —
    never silently lost), prefill/decode KV-signature mismatch rejected
    at handoff (request still served, cold), all decode replicas shed
    (429 contract with MAX retry-after);
  * the AdmissionController's structured SignalSnapshot + staleness
    guard (ISSUE 10 satellite);
  * prefill-tier role restriction; pool_sizing replica tiers;
    /api/cluster + /api/history "cluster" payloads; flight events.
"""

import time

import jax
import jax.numpy as jnp
import pytest

from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
from quoracle_tpu.serving.cluster import ClusterPlane, ReplicaFailedError
from quoracle_tpu.serving.handoff import HandoffError, KVHandoff

MEMBER = "xla:tiny"
MSGS = [{"role": "user", "content": "hello disaggregated world, "
                                    "please elaborate at length"}]


def req(msgs=MSGS, sid=None, cj=False, temperature=0.0, max_tokens=20,
        priority=None, tenant="default"):
    return QueryRequest(MEMBER, msgs, temperature=temperature,
                        max_tokens=max_tokens, session_id=sid,
                        constrain_json=cj, priority=priority,
                        tenant=tenant)


@pytest.fixture(scope="module")
def mono():
    b = TPUBackend([MEMBER], continuous_chunk=8)
    yield b
    b.close()


@pytest.fixture(scope="module")
def cluster():
    c = ClusterPlane.build([MEMBER], replicas=2, disaggregate=True,
                           continuous_chunk=8)
    yield c
    c.close()


# ---------------------------------------------------------------------------
# The acceptance gate: temp-0 bit-equality vs a monolithic backend
# ---------------------------------------------------------------------------

def test_disagg_greedy_bit_equal(mono, cluster):
    a = mono.query([req()])[0]
    b = cluster.query([req()])[0]
    assert a.ok and b.ok, (a.error, b.error)
    assert b.text == a.text
    # the flow really disaggregated: a handoff happened
    assert cluster.handoff.exports >= 1
    assert cluster.handoff.adopts >= 1


def test_disagg_constrained_json_bit_equal(mono, cluster):
    a = mono.query([req(cj=True, max_tokens=32)])[0]
    b = cluster.query([req(cj=True, max_tokens=32)])[0]
    assert a.ok and b.ok, (a.error, b.error)
    assert b.text == a.text


def test_prefill_replica_serves_through_its_own_batcher(mono, cluster):
    """A ``role='prefill'`` replica has a batcher like any other: a row of
    its own backend rides the batcher, the engine's role holds it to a
    budget of one token (so it retires after its first tick; a larger
    budget is refused, that row alone), and the session it leaves
    is adoptable by the decode replica, which then resumes it to the
    monolithic backend's bits."""
    pre = next(r for r in cluster.replicas if r.role == "prefill")
    dec = next(r for r in cluster.replicas if r.role == "decode")
    pe, de = pre.backend.engines[MEMBER], dec.backend.engines[MEMBER]
    assert pe.role == "prefill"
    # a budget over one token is refused by the engine's role, row-level
    over = pre.backend.query([req(sid="pf0")])[0]
    assert not over.ok and "prefill-tier replica" in over.error
    before = pre.backend.scheduler_stats()[MEMBER]
    first = pre.backend.query([req(sid="pf1", max_tokens=1)])[0]
    assert first.ok, first.error
    assert first.usage.completion_tokens == 1
    after = pre.backend.scheduler_stats()[MEMBER]
    assert after["retired"] == before["retired"] + 1
    assert after["steps"] == before["steps"] + 1
    env = cluster.handoff.export(pe, "pf1", MEMBER,
                                 src_replica=pre.replica_id)
    try:
        cluster.handoff.adopt(de, env, dst_replica=dec.replica_id)
        want = mono.query([req()])[0]
        got = dec.backend.query([req(sid="pf1")])[0]
        assert got.ok and want.ok, (got.error, want.error)
        assert got.text == want.text
        assert want.text.startswith(first.text)
        assert got.cached_tokens > 0       # resumed, not re-prefilled
    finally:
        cluster.handoff.forget(MEMBER, "pf1")
        de.drop_session("pf1")
        pe.drop_session("pf1")


def test_disagg_speculative_bit_equal():
    """Decode replicas run the production continuous+speculative path;
    the handed-off row's grammar state and session resume compose with
    draft/verify rounds bit-exactly."""
    mono = TPUBackend([MEMBER], continuous_chunk=8,
                      draft_map={MEMBER: MEMBER}, draft_k=4)
    cl = ClusterPlane.build([MEMBER], replicas=2, disaggregate=True,
                            continuous_chunk=8,
                            draft_map={MEMBER: MEMBER}, draft_k=4)
    try:
        a = mono.query([req(sid="sp1", cj=True, max_tokens=24)])[0]
        b = cl.query([req(sid="sp1", cj=True, max_tokens=24)])[0]
        assert a.ok and b.ok, (a.error, b.error)
        assert b.text == a.text
        assert b.spec_rounds > 0          # decode phase actually drafted
    finally:
        mono.close()
        cl.close()


def test_session_affinity_round2_bit_equal(mono, cluster):
    """Round 1 lands the session on a decode replica; round 2 routes by
    affinity (no second handoff) and resumes the resident pages with
    cached-token parity against the monolithic run."""
    a1 = mono.query([req(sid="conv1")])[0]
    b1 = cluster.query([req(sid="conv1")])[0]
    assert b1.text == a1.text
    exports_before = cluster.handoff.exports
    msgs2 = MSGS + [{"role": "assistant", "content": a1.text},
                    {"role": "user", "content": "continue."}]
    a2 = mono.query([req(msgs2, sid="conv1")])[0]
    b2 = cluster.query([req(msgs2, sid="conv1")])[0]
    assert a2.ok and b2.ok, (a2.error, b2.error)
    assert b2.text == a2.text
    # affinity: the resumed round did NOT re-enter the prefill tier
    assert cluster.handoff.exports == exports_before
    assert b2.cached_tokens == a2.cached_tokens > 0
    rep = cluster.router.affinity_of("conv1")
    assert rep is not None and rep.role == "decode"
    cluster.drop_session("conv1")
    mono.drop_session("conv1")
    assert cluster.router.affinity_of("conv1") is None


# ---------------------------------------------------------------------------
# Degraded modes
# ---------------------------------------------------------------------------

def _decode_reps(cl):
    return [r for r in cl.replicas if r.role == "decode"]


def test_decode_replica_death_replaces_row():
    """A decode replica dying mid-row: the retained handoff envelope
    adopts into the survivor and the output is still bit-identical; a
    second death with no survivor left fails the row with a STRUCTURED
    error naming the replica — never a silent loss."""
    mono = TPUBackend([MEMBER], continuous_chunk=8)
    cl = ClusterPlane.build([MEMBER], replicas=3, disaggregate=True,
                            continuous_chunk=8)
    try:
        want = mono.query([req()])[0]
        decs = _decode_reps(cl)
        assert len(decs) == 2
        # kill the replica placement will pick first (both idle → the
        # load-score tie breaks to the first registered decode replica)
        first = cl.router.place("decode")
        assert first.role == "decode"
        for cb in first.backend._cbatchers.values():
            cb.close()
        got = cl.query([req()])[0]
        assert got.ok, got.error
        assert got.text == want.text
        assert cl.handoff.replaced >= 1
        stats = cl.router.stats()
        assert stats["replicas"][first.replica_id]["alive"] is False
        # now kill the survivor too: structured failure, not silence
        survivor = [r for r in decs
                    if r.replica_id != first.replica_id][0]
        for cb in survivor.backend._cbatchers.values():
            cb.close()
        got2 = cl.query([req()])[0]
        assert not got2.ok
        assert "replica_failed" in got2.error
        assert survivor.replica_id in got2.error
    finally:
        mono.close()
        cl.close()


def test_signature_mismatch_rejected_at_handoff():
    """Engines of different KV geometry/dtype must never exchange
    bytes: adopt() rejects BEFORE the destination tier sees them."""
    from quoracle_tpu.models.config import get_model_config
    from quoracle_tpu.models.tokenizer import ByteTokenizer
    from quoracle_tpu.models.transformer import init_params
    from quoracle_tpu.models.generate import GenerateEngine

    cfg = get_model_config(MEMBER)
    p32 = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    p16 = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    src = GenerateEngine(cfg, p32, ByteTokenizer(), max_seq=512,
                         prompt_buckets=(32, 64, 128, 256))
    dst = GenerateEngine(cfg, p16, ByteTokenizer(), max_seq=512,
                         prompt_buckets=(32, 64, 128, 256))
    src.attach_tier(host_mb=64)
    dst.attach_tier(host_mb=64)
    assert src.kv_signature() != dst.kv_signature()
    prompt = ByteTokenizer().encode("signature test prompt",
                                    add_bos=True)
    src.generate([prompt], temperature=0.0, max_new_tokens=1,
                 session_ids=["h1"])
    ho = KVHandoff()
    env = ho.export(src, "h1", MEMBER)
    with pytest.raises(HandoffError) as ei:
        ho.adopt(dst, env)
    assert ei.value.reason == "signature"
    assert ho.rejects == 1
    # the bytes never landed: the destination tier holds nothing
    assert not dst.sessions.tier.has_session("h1")


def test_signature_mismatch_degrades_to_cold_prefill(mono, cluster,
                                                     monkeypatch):
    """At the cluster level a skewed pair still SERVES the request —
    cold re-prefill on the decode tier, output unchanged."""
    dec = _decode_reps(cluster)[0]
    eng = dec.backend.engines[MEMBER]
    # instance-level patch: only the DECODE engine reports skew (a
    # class-level patch would skew the prefill side identically and
    # the signatures would still match)
    monkeypatch.setattr(eng, "kv_signature",
                        lambda: "skewed-signature", raising=False)
    want = mono.query([req()])[0]
    got = cluster.query([req()])[0]
    assert got.ok, got.error
    assert got.text == want.text


def test_all_decode_replicas_shed_propagates_max_retry_after():
    """The 429 contract at the cluster front door: every decode replica
    sheds → OverloadedError with the MAX retry-after across them."""
    from quoracle_tpu.serving.admission import OverloadedError
    from quoracle_tpu.serving.qos import Priority

    cl = ClusterPlane.build([MEMBER], replicas=3, disaggregate=True,
                            continuous_chunk=8,
                            qos=True)
    try:
        decs = _decode_reps(cl)
        assert len(decs) == 2
        for i, rep in enumerate(decs):
            ctrl = rep.backend.qos_controller
            # a zero depth bound sheds EVERYTHING — at the front door
            # (router.admit) and inside cb.submit alike; distinct base
            # retries make the MAX propagation observable
            ctrl.config.max_queue_depth = 0
            ctrl.config.base_retry_ms = 1000 * (i + 1)
        with pytest.raises(OverloadedError) as ei:
            cl.router.admit(tenant="t1", priority=Priority.INTERACTIVE)
        retries = []
        for rep in decs:
            ctrl = rep.backend.qos_controller
            try:
                ctrl.admit(tenant="probe",
                           priority=Priority.INTERACTIVE)
            except OverloadedError as e:
                retries.append(e.retry_after_ms)
        assert len(retries) == 2
        # the MAX across replicas is the backoff BASE (ISSUE 11
        # satellite): the first consecutive shed propagates it with
        # deterministic jitter applied, never less than the max itself
        from quoracle_tpu.serving.admission import escalate_retry_ms
        assert ei.value.retry_after_ms == escalate_retry_ms(
            max(retries), 1)
        assert ei.value.retry_after_ms >= max(retries)
        assert cl.router.shed == 1
        # and through the serving path: a structured reject, not a hang
        got = cl.query([req(priority=Priority.INTERACTIVE)])[0]
        assert not got.ok
        assert "admission_rejected" in got.error
    finally:
        cl.close()


def test_router_retry_after_backs_off_monotonically():
    """ISSUE 11 satellite: under REPEATED aggregate shed the router's
    propagated retry_after_ms escalates exponentially with
    deterministic jitter — successive 429s are non-decreasing up to
    the cap, so a saturated cluster de-synchronizes its retry storm
    instead of re-summoning it; one successful admit resets the
    streak."""
    from types import SimpleNamespace

    from quoracle_tpu.serving.admission import (
        BACKOFF_CAP_MS, AdmissionController, OverloadedError,
        escalate_retry_ms,
    )
    from quoracle_tpu.serving.router import ClusterRouter

    def make_rep(rid):
        ctrl = AdmissionController()
        ctrl.config.max_queue_depth = 0          # shed everything
        ctrl.register_depth_source("q", lambda: 1)
        return SimpleNamespace(replica_id=rid, role="decode",
                               alive=True,
                               backend=SimpleNamespace(
                                   qos_controller=ctrl))

    router = ClusterRouter()
    reps = [make_rep("decode-1"), make_rep("decode-2")]
    for r in reps:
        router.register(r)

    hints = []
    for _ in range(10):
        with pytest.raises(OverloadedError) as ei:
            router.admit(tenant="t1")
        hints.append(ei.value.retry_after_ms)
    assert hints == sorted(hints), hints          # non-decreasing
    assert hints[-1] == BACKOFF_CAP_MS            # reaches the cap
    assert hints[0] < hints[3] < hints[-1]        # actually escalates
    assert router.stats()["shed_streak"] == 10
    assert router.stats()["last_retry_after_ms"] == BACKOFF_CAP_MS

    # one successful admit resets the streak — the next shed starts
    # from the base hint again
    for r in reps:
        r.backend.qos_controller.config.max_queue_depth = 64
    router.admit(tenant="t1")
    assert router.stats()["shed_streak"] == 0
    for r in reps:
        r.backend.qos_controller.config.max_queue_depth = 0
    with pytest.raises(OverloadedError) as ei:
        router.admit(tenant="t1")
    assert ei.value.retry_after_ms == hints[0]

    # the jitter is deterministic: same (base, attempt) → same hint
    assert [escalate_retry_ms(1000, n) for n in range(1, 8)] \
        == [escalate_retry_ms(1000, n) for n in range(1, 8)]


# ---------------------------------------------------------------------------
# Satellite: structured admission signals + staleness guard
# ---------------------------------------------------------------------------

def test_signal_snapshot_is_the_shed_ladders_numbers():
    from quoracle_tpu.serving.admission import AdmissionController

    ctrl = AdmissionController()
    ctrl.register_depth_source("q", lambda: 7)
    snap = ctrl.signals()
    assert snap.queue_depth == 7
    assert snap.admit_wait_p95_ms == ctrl.admit_wait_p95_ms
    assert snap.hbm_headroom == ctrl.hbm_headroom
    d = snap.as_dict()
    assert {"ts", "refreshed_ts", "queue_depth", "admit_wait_p95_ms",
            "hbm_headroom", "admitted", "shed"} <= set(d)


def test_signal_snapshot_staleness_guard():
    from quoracle_tpu.serving.admission import AdmissionController

    ctrl = AdmissionController()
    t0 = time.monotonic()
    s0 = ctrl.signals(now=t0)
    assert s0.age_s(t0) == 0.0
    # inside the refresh window nothing re-samples: the snapshot ages
    s1 = ctrl.signals(now=t0 + 0.5)
    assert s1.refreshed_ts == s0.refreshed_ts
    assert s1.age_s(t0 + 0.5) == pytest.approx(0.5)
    assert s1.stale(0.2, now=t0 + 0.5)
    # max_age_s forces a refresh even inside refresh_s
    s2 = ctrl.signals(now=t0 + 0.6, max_age_s=0.2)
    assert s2.refreshed_ts == t0 + 0.6
    assert not s2.stale(0.2, now=t0 + 0.6)


# ---------------------------------------------------------------------------
# Role restriction + unified mode + capacity plan
# ---------------------------------------------------------------------------

def test_prefill_role_engine_rejects_decode(cluster):
    pre = [r for r in cluster.replicas if r.role == "prefill"][0]
    eng = pre.backend.engines[MEMBER]
    assert eng.role == "prefill"
    with pytest.raises(ValueError, match="prefill-tier"):
        eng.generate([[1, 2, 3]], temperature=0.0, max_new_tokens=4)


def test_unified_replicas_serve_bit_equal(mono):
    cl = ClusterPlane.build([MEMBER], replicas=2, disaggregate=False,
                            continuous_chunk=8)
    try:
        assert not cl.disaggregated
        a = mono.query([req(sid="u1")])[0]
        b = cl.query([req(sid="u1")])[0]
        assert b.ok and b.text == a.text
        # no prefill tier → no handoff machinery engaged
        assert cl.handoff.exports == 0
        assert cl.router.affinity_of("u1") is not None
        mono.drop_session("u1")
    finally:
        cl.close()


def test_pool_sizing_replica_tiers():
    from quoracle_tpu.parallel.mesh import pool_sizing

    plan = pool_sizing([MEMBER], 8, host_kv_mb=512, replicas=2,
                       disaggregate=True)
    tiers = plan["replica_tiers"]
    assert tiers["disaggregate"] is True
    assert tiers["prefill"]["replicas"] == 1
    assert tiers["decode"]["replicas"] == 1
    assert tiers["prefill"]["devices"] + tiers["decode"]["devices"] \
        == tiers["total_devices_needed"]
    # prefill replicas hold sessions only transiently (handoff moves
    # them out): steady-state residency is a decode-tier number
    assert tiers["prefill"]["resident_sessions"] == 0
    assert tiers["decode"]["resident_sessions"] > 0
    assert tiers["decode"]["host_tier_sessions"] > 0
    assert tiers["fits"] is True
    flat = pool_sizing([MEMBER], 8, replicas=3, disaggregate=False)
    assert flat["replica_tiers"]["unified"]["replicas"] == 3
    assert "prefill" not in flat["replica_tiers"]
    assert "replica_tiers" not in pool_sizing([MEMBER], 8)


# ---------------------------------------------------------------------------
# Observability surfaces
# ---------------------------------------------------------------------------

def test_cluster_stats_and_api_payload(cluster):
    stats = cluster.cluster_stats()
    assert stats["enabled"] and stats["disaggregated"]
    roles = sorted(r["role"] for r in stats["replicas"])
    assert roles == ["decode", "prefill"]
    assert "handoff" in stats and "router" in stats
    for rep in stats["router"]["replicas"].values():
        if rep["signals"] is not None:
            assert "queue_depth" in rep["signals"]
    # the dashboard payload wraps it with the counter snapshots; the
    # server only touches runtime.backend, so a stub runtime suffices
    from types import SimpleNamespace
    from quoracle_tpu.web.server import DashboardServer

    d = DashboardServer(SimpleNamespace(backend=cluster))
    payload = d.cluster_payload()
    assert payload["enabled"]
    assert "handoffs" in payload["counters"]
    # non-cluster backends answer disabled, same shape
    d2 = DashboardServer(SimpleNamespace(backend=object()))
    assert d2.cluster_payload()["enabled"] is False


def test_cluster_events_ring_and_flight_registration():
    from quoracle_tpu.infra.bus import EventBus, TOPIC_CLUSTER
    from quoracle_tpu.infra.event_history import EventHistory
    from quoracle_tpu.infra.flightrec import FLIGHT_EVENTS

    for kind in ("kv_handoff_export", "kv_handoff_adopt",
                 "kv_handoff_reject", "kv_handoff_replace",
                 "cluster_replica_dead", "router_all_shed"):
        assert kind in FLIGHT_EVENTS
    bus = EventBus()
    hist = EventHistory(bus)
    try:
        bus.broadcast(TOPIC_CLUSTER, {"event": "replica_failed",
                                      "replica": "decode-1"})
        ring = hist.replay_cluster()
        assert ring and ring[-1]["replica"] == "decode-1"
    finally:
        hist.close()


def test_runtime_builds_cluster_backend():
    """--replicas/--disaggregate plumbing: a tpu-backend Runtime with
    replicas > 1 serves through a ClusterPlane (watchdog sources and
    the default pool carry over); the mock backend refuses the flags
    loudly instead of silently serving scripted responses."""
    from quoracle_tpu.runtime import Runtime, RuntimeConfig

    rt = Runtime(RuntimeConfig(backend="tpu", model_pool=[MEMBER],
                               replicas=2, disaggregate=True))
    try:
        assert isinstance(rt.backend, ClusterPlane)
        assert rt.backend.disaggregated
        assert rt.default_pool() == [MEMBER]
        names = [n for n, _ in rt.backend.watchdog_sources()]
        assert any(n.startswith("decode-") for n in names)
    finally:
        rt.close()
        rt.backend.close()
    with pytest.raises(ValueError, match="--replicas"):
        Runtime(RuntimeConfig(backend="mock", replicas=2))


def test_kv_and_qos_stats_aggregate_per_replica(cluster):
    kv = cluster.kv_stats()
    assert kv["enabled"] and kv["cluster"]
    assert set(kv["replicas"]) == {r.replica_id
                                   for r in cluster.replicas}
    assert "handoff" in kv
    sched = cluster.scheduler_stats()
    # every replica, whatever its role, runs one batcher per member
    assert {k.split("/", 1)[0] for k in sched} \
        == {r.replica_id for r in cluster.replicas}
    # engines surface is replica-qualified for HBM attribution
    assert {k.split("@", 1)[0] for k in cluster.engines} \
        == {r.replica_id for r in cluster.replicas}
