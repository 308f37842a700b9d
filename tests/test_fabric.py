"""Cross-host cluster fabric (serving/fabric/, ISSUE 12).

The tentpole's acceptance bar, end to end on the loopback fabric (every
byte rides the real wire codec; no sockets in tier-1):

  * temp-0 BIT-EQUALITY: a monolithic backend vs two replica
    "processes" (prefill + decode FabricPeers) joined over the loopback
    fabric — greedy, grammar-constrained JSON, and speculative — with
    the session handed off OVER THE WIRE mid-stream;
  * a replica warm-started PURELY from the fleet prefix service
    (no local disk), bit-equal with cached-token proof;
  * degraded modes: decode-peer death mid-row re-placed through the
    front door's retained envelope BYTES (or structured failure),
    signature skew rejected before page bytes with cold degrade,
    silent signals → worst-rank → mark-failed, all-peers-shed 429 with
    MAX retry-after — the PR 10 contracts, now over the wire;
  * per-host mesh sizing (host_layout / pool_sizing hosts=),
    Runtime/CLI flags, /api/fabric + the history "fabric" ring, and
    registry coherence (instruments / topics / flight events / lockdep
    ranks / chaos points).
"""

import time

import pytest

from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
from quoracle_tpu.serving.cluster import RemoteReplica
from quoracle_tpu.serving.fabric import wire
from quoracle_tpu.serving.fabric.frontdoor import FabricPlane
from quoracle_tpu.serving.fabric.peer import FabricPeer
from quoracle_tpu.serving.fabric.transport import LoopbackTransport
from quoracle_tpu.serving.fabric.wire import TransportError

pytestmark = pytest.mark.fabric

MEMBER = "xla:tiny"
MSGS = [{"role": "user", "content": "hello fabric world, please "
                                    "elaborate at length"}]


def req(msgs=MSGS, sid=None, cj=False, max_tokens=20, priority=None,
        tenant="default"):
    return QueryRequest(MEMBER, msgs, temperature=0.0,
                        max_tokens=max_tokens, session_id=sid,
                        constrain_json=cj, priority=priority,
                        tenant=tenant)


def _remote(peer, **kw):
    return RemoteReplica(LoopbackTransport(peer.handle,
                                           peer.replica_id, **kw))


@pytest.fixture(scope="module")
def mono():
    b = TPUBackend([MEMBER], continuous_chunk=8)
    yield b
    b.close()


@pytest.fixture(scope="module")
def fabric():
    """Two replica 'processes' joined over the loopback fabric: one
    prefill peer, one decode peer, a front-door plane."""
    peers = [FabricPeer.build([MEMBER], role="prefill",
                              replica_id="prefill-0",
                              continuous_chunk=8),
             FabricPeer.build([MEMBER], role="decode",
                              replica_id="decode-0",
                              continuous_chunk=8)]
    plane = FabricPlane([_remote(p) for p in peers])
    yield plane, peers
    plane.close()
    for p in peers:
        p.close()


# ---------------------------------------------------------------------------
# The acceptance gate: temp-0 bit-equality over the wire
# ---------------------------------------------------------------------------

def test_fabric_greedy_bit_equal(mono, fabric):
    plane, peers = fabric
    a = mono.query([req()])[0]
    b = plane.query([req()])[0]
    assert a.ok and b.ok, (a.error, b.error)
    assert b.text == a.text
    # the flow really crossed the wire: a framed envelope moved
    assert plane.wire_handoffs >= 1
    assert peers[0].handoff.exports >= 1
    assert peers[1].handoff.adopts >= 1


def test_fabric_constrained_json_bit_equal(mono, fabric):
    plane, _ = fabric
    a = mono.query([req(cj=True, max_tokens=32)])[0]
    b = plane.query([req(cj=True, max_tokens=32)])[0]
    assert a.ok and b.ok, (a.error, b.error)
    assert b.text == a.text


def test_fabric_speculative_bit_equal():
    """Decode peers run the production continuous+speculative path; the
    wire-handed-off row's grammar state and session resume compose with
    draft/verify rounds bit-exactly."""
    mono = TPUBackend([MEMBER], continuous_chunk=8,
                      draft_map={MEMBER: MEMBER}, draft_k=4)
    pre = FabricPeer.build([MEMBER], role="prefill",
                           replica_id="prefill-0", continuous_chunk=8,
                           draft_map={MEMBER: MEMBER}, draft_k=4)
    dec = FabricPeer.build([MEMBER], role="decode",
                           replica_id="decode-0", continuous_chunk=8,
                           draft_map={MEMBER: MEMBER}, draft_k=4)
    plane = FabricPlane([_remote(pre), _remote(dec)])
    try:
        a = mono.query([req(sid="sp1", cj=True, max_tokens=24)])[0]
        b = plane.query([req(sid="sp1", cj=True, max_tokens=24)])[0]
        assert a.ok and b.ok, (a.error, b.error)
        assert b.text == a.text
        assert b.spec_rounds > 0          # decode phase actually drafted
    finally:
        mono.close()
        plane.close()
        pre.close()
        dec.close()


def test_session_handed_off_over_wire_then_affinity(mono, fabric):
    """Round 1: the session prefills on the prefill peer and its KV
    crosses the wire mid-stream. Round 2 routes by affinity to the
    decode peer holding the pages — no second handoff, cached-token
    parity with the monolithic run."""
    plane, _ = fabric
    a1 = mono.query([req(sid="conv1")])[0]
    b1 = plane.query([req(sid="conv1")])[0]
    assert b1.text == a1.text
    handoffs = plane.wire_handoffs
    msgs2 = MSGS + [{"role": "assistant", "content": a1.text},
                    {"role": "user", "content": "continue."}]
    a2 = mono.query([req(msgs2, sid="conv1")])[0]
    b2 = plane.query([req(msgs2, sid="conv1")])[0]
    assert a2.ok and b2.ok, (a2.error, b2.error)
    assert b2.text == a2.text
    assert plane.wire_handoffs == handoffs   # affinity, not re-handoff
    assert b2.cached_tokens == a2.cached_tokens > 0
    peer = plane.router.affinity_of("conv1")
    assert peer is not None and peer.role == "decode"
    plane.drop_session("conv1")
    mono.drop_session("conv1")
    assert plane.router.affinity_of("conv1") is None


# ---------------------------------------------------------------------------
# Fleet prefix service: warm-start purely from prefixd
# ---------------------------------------------------------------------------

def test_replica_warm_starts_purely_from_fleet_prefixd(tmp_path):
    """A donor publishes its prefix blocks to the fleet service; a
    FRESH peer (no disk dir, empty host tier) warm-starts from the
    fleet alone — bit-equal output with cached tokens served."""
    from quoracle_tpu.serving.fabric.prefixd import PrefixService

    svc = PrefixService(str(tmp_path))
    prompt = ("system: shared policy preamble for every agent session. "
              * 6 + "task: restate the rules briefly.")
    msgs = [{"role": "user", "content": prompt}]

    donor = FabricPeer.build([MEMBER], replica_id="donor",
                             continuous_chunk=8, host_kv_mb=32)
    donor.attach_prefixd(LoopbackTransport(svc.handle, "prefixd",
                                           lock_name="fabric.prefixd"))
    want = donor.backend.query([req(msgs, sid="d1", max_tokens=12)])[0]
    donor.backend.drop_session("d1")
    tier = donor.backend.engines[MEMBER].sessions.tier
    tier.flush_spills()
    assert tier.prefixd.published >= 1
    donor.close()

    fresh = FabricPeer.build([MEMBER], replica_id="fresh",
                             continuous_chunk=8, host_kv_mb=32)
    fresh.attach_prefixd(LoopbackTransport(svc.handle, "prefixd",
                                           lock_name="fabric.prefixd"))
    got = fresh.backend.query([req(msgs, sid="f1", max_tokens=12)])[0]
    tier2 = fresh.backend.engines[MEMBER].sessions.tier
    assert got.ok and got.text == want.text
    assert got.cached_tokens > 0
    assert tier2.prefixd.hits >= 1
    assert tier2.stats()["prefixd"]["hits"] >= 1
    fresh.close()


def test_prefixd_corrupt_entry_rejected_serverside(tmp_path):
    """The service loads through DiskPrefixStore.load, so a corrupted
    file is crc-rejected, unlinked, and answered as a MISS — a bad
    fleet entry can never poison a replica's prefix."""
    import os

    import numpy as np

    from quoracle_tpu.serving.fabric.prefixd import (
        PrefixdClient, PrefixService,
    )
    from quoracle_tpu.serving.kvtier import DiskPrefixStore

    svc = PrefixService(str(tmp_path))
    client = PrefixdClient(
        LoopbackTransport(svc.handle, "prefixd",
                          lock_name="fabric.prefixd"), "sig-a")
    tokens = list(range(128))
    key = DiskPrefixStore.block_key(tokens)
    k = np.ones((2, 128, 2, 4), np.float32)
    assert client.publish(key, tokens, k, k * 2)
    got = client.fetch(key, tokens)
    assert got is not None and np.array_equal(got[0], k)
    # corrupt the stored file in place
    (entry,) = [f for f in os.listdir(tmp_path / "sig-a")
                if f.endswith(".npz")]
    p = tmp_path / "sig-a" / entry
    raw = bytearray(p.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    p.write_bytes(bytes(raw))
    assert client.fetch(key, tokens) is None       # miss, not poison
    assert not p.exists()                          # unlinked serverside
    # chaos 'unavailable' degrades to a miss + degraded counter
    from quoracle_tpu.chaos.faults import CHAOS, FaultPlan, FaultRule
    with CHAOS.arming(FaultPlan(0, [FaultRule("fabric.prefixd",
                                              "unavailable")])):
        assert client.fetch(key, tokens) is None
    assert client.degraded == 1


# ---------------------------------------------------------------------------
# Degraded modes over the wire
# ---------------------------------------------------------------------------

def test_decode_peer_death_replaces_row_via_retained_bytes(mono):
    """A decode peer dying mid-row: the front door re-places its
    RETAINED envelope bytes onto the survivor bit-identically; a second
    death with no survivor fails the row with a structured error naming
    the peer — never a silent loss."""
    peers = [FabricPeer.build([MEMBER], role="prefill",
                              replica_id="prefill-0",
                              continuous_chunk=8),
             FabricPeer.build([MEMBER], role="decode",
                              replica_id="decode-1",
                              continuous_chunk=8),
             FabricPeer.build([MEMBER], role="decode",
                              replica_id="decode-2",
                              continuous_chunk=8)]
    plane = FabricPlane([_remote(p) for p in peers])
    by_id = {p.replica_id: p for p in peers}
    try:
        want = mono.query([req()])[0]
        first = plane.router.place("decode")
        for cb in by_id[first.replica_id].backend._cbatchers.values():
            cb.close()
        got = plane.query([req()])[0]
        assert got.ok, got.error
        assert got.text == want.text
        assert plane.replaced >= 1
        assert plane.router.stats()["replicas"][
            first.replica_id]["alive"] is False
        survivor = [p for p in peers
                    if p.role == "decode"
                    and p.replica_id != first.replica_id][0]
        for cb in survivor.backend._cbatchers.values():
            cb.close()
        got2 = plane.query([req()])[0]
        assert not got2.ok
        assert "replica_failed" in got2.error
        assert survivor.replica_id in got2.error
    finally:
        plane.close()
        for p in peers:
            p.close()


def test_signature_skew_rejected_before_bytes_cold_degrade(
        mono, fabric, monkeypatch):
    """A version-skewed decode peer rejects the envelope from its
    HEADER (before a page byte is parsed) and the front door serves the
    request cold on the decode tier — output unchanged."""
    plane, peers = fabric
    dec = peers[1]
    eng = dec.backend.engines[MEMBER]
    monkeypatch.setattr(eng, "kv_signature", lambda: "skewed-signature",
                        raising=False)
    cold0 = plane.cold_failovers
    want = mono.query([req()])[0]
    got = plane.query([req()])[0]
    assert got.ok, got.error
    assert got.text == want.text
    assert plane.cold_failovers == cold0 + 1
    # the peer survived the reject: it was the bytes, not the peer
    assert all(p.alive for p in plane.peers)


def _fake_peer_handler(name, role, shed_ms=None, silent=None):
    """An engine-less peer: hello + signals + admit, enough surface for
    router-level tests without building backends."""
    def handler(msg_type, payload):
        if silent is not None and silent["on"] \
                and msg_type != wire.MSG_HELLO:
            raise TransportError(f"{name} partitioned")
        if msg_type == wire.MSG_HELLO:
            return wire.MSG_OK, wire.encode_json(
                {"replica_id": name, "role": role, "pool": [MEMBER]})
        if msg_type == wire.MSG_SIGNALS_POLL:
            return wire.MSG_SIGNALS, wire.encode_json(
                {"qos": True, "queue_depth": 1, "admit_wait_p95_ms": None,
                 "hbm_headroom": None, "admitted": 0, "shed": 0,
                 "age_s": 0.0})
        if msg_type == wire.MSG_ADMIT:
            if shed_ms is not None:
                from quoracle_tpu.serving.admission import OverloadedError
                raise OverloadedError(f"{name} saturated",
                                      retry_after_ms=shed_ms)
            return wire.MSG_ADMITTED, wire.encode_json({"priority": 1})
        return wire.MSG_ERROR, wire.error_payload("nope")
    return handler


def test_silent_signals_worst_rank_then_mark_failed():
    """A peer whose SignalSnapshot polls fail is scored worst-rank
    (placement avoids it but the front door never stalls); after the
    bounded silence streak it is marked FAILED and drops out."""
    from quoracle_tpu.serving.router import SILENT_SIGNALS_LIMIT

    silent = {"on": False}
    a = RemoteReplica(LoopbackTransport(
        _fake_peer_handler("decode-a", "decode", silent=silent),
        "decode-a", retries=0))
    b = RemoteReplica(LoopbackTransport(
        _fake_peer_handler("decode-b", "decode"), "decode-b"))
    plane = FabricPlane([a, b])
    silent["on"] = True
    for i in range(SILENT_SIGNALS_LIMIT):
        # the healthy proxy caches its snapshot briefly; expire it so
        # every placement really scores both candidates
        b.backend.qos_controller._cached = None
        assert plane.router.place("decode").replica_id == "decode-b"
    assert a.alive is False
    st = plane.router.stats()
    assert st["replicas"]["decode-a"]["alive"] is False
    # in-flight re-placement path is the PR 10 death path: placement
    # now excludes the corpse entirely
    assert plane.router.place("decode").replica_id == "decode-b"


def test_peer_rejoin_after_mark_failed():
    """ISSUE 14 satellite: a peer marked failed (silent signals) that
    answers its hello again is RESTORED to the placement set via
    ``rejoin_peer`` — no front-door restart — with a fabric_peer_rejoin
    flight event; while it stays down, the sweep is a no-op, and a
    DIFFERENT identity at the same address is refused."""
    from quoracle_tpu.infra.flightrec import FLIGHT
    from quoracle_tpu.serving.router import SILENT_SIGNALS_LIMIT

    silent = {"on": False}
    down = {"on": False}
    base = _fake_peer_handler("decode-a", "decode", silent=silent)

    def handler(msg_type, payload):
        if down["on"]:
            raise TransportError("decode-a fully partitioned")
        return base(msg_type, payload)

    a = RemoteReplica(LoopbackTransport(handler, "decode-a",
                                        retries=0))
    b = RemoteReplica(LoopbackTransport(
        _fake_peer_handler("decode-b", "decode"), "decode-b"))
    plane = FabricPlane([a, b])
    silent["on"] = True
    for _ in range(SILENT_SIGNALS_LIMIT):
        b.backend.qos_controller._cached = None
        plane.router.place("decode")
    assert a.alive is False
    # still fully partitioned (hellos fail too): the sweep restores
    # nothing
    down["on"] = True
    assert plane.try_rejoin_dead_peers() == 0
    assert a.alive is False
    # link back: the hello answers and the peer rejoins
    down["on"] = False
    silent["on"] = False
    assert plane.try_rejoin_dead_peers() == 1
    assert a.alive is True
    st = plane.router.stats()
    assert st["replicas"]["decode-a"]["alive"] is True
    assert st["silent"].get("decode-a") is None
    assert any(e.get("kind") == "fabric_peer_rejoin"
               and e.get("peer") == "decode-a"
               for e in FLIGHT.snapshot())
    # the restored peer is placeable again
    a.backend.qos_controller._cached = None
    b.backend.qos_controller._cached = None
    assert plane.router.place("decode").replica_id in ("decode-a",
                                                       "decode-b")
    # an imposter (same address, different identity) must NOT inherit
    # the slot: re-fail the peer, then swap the handler's identity
    imposter = RemoteReplica(LoopbackTransport(
        _fake_peer_handler("decode-c", "decode"), "decode-c"))
    plane.peers.append(imposter)
    plane.router.register(imposter)
    imposter.alive = False
    plane.router.mark_failed("decode-c", "test")
    imposter.replica_id = "decode-c"      # hello will answer decode-c
    imposter.role = "prefill"             # ...but the ROLE changed
    assert plane.rejoin_peer("decode-c") is False
    assert imposter.alive is False


def test_frontdoor_add_and_remove_peer_loopback():
    """The fleet's door-side registration surface: a peer attached at a
    RUNNING front door joins placement; removing it deregisters and
    drops its affinities."""
    a = RemoteReplica(LoopbackTransport(
        _fake_peer_handler("decode-a", "decode"), "decode-a"))
    plane = FabricPlane([a])
    b = RemoteReplica(LoopbackTransport(
        _fake_peer_handler("decode-b", "decode"), "decode-b"))
    plane.peers.append(b)
    plane.router.register(b)
    assert len(plane.router.replicas("decode")) == 2
    plane.router.set_affinity("s1", "decode-b")
    assert plane.remove_peer("decode-b")
    assert [r.replica_id for r in plane.router.replicas("decode")] \
        == ["decode-a"]
    assert plane.router.affinity_of("s1") is None
    assert plane.fabric_stats()["peers"][0]["replica_id"] == "decode-a"


def test_all_decode_peers_shed_propagates_max_retry_after():
    """The 429 contract at the fabric front door: every decode peer
    sheds OVER THE WIRE → OverloadedError with the escalated MAX
    retry-after across them."""
    from quoracle_tpu.serving.admission import (
        OverloadedError, escalate_retry_ms,
    )

    a = RemoteReplica(LoopbackTransport(
        _fake_peer_handler("decode-a", "decode", shed_ms=1000),
        "decode-a"))
    b = RemoteReplica(LoopbackTransport(
        _fake_peer_handler("decode-b", "decode", shed_ms=2000),
        "decode-b"))
    plane = FabricPlane([a, b])
    with pytest.raises(OverloadedError) as ei:
        plane.qos_controller.admit(tenant="t1")
    assert ei.value.retry_after_ms == escalate_retry_ms(2000, 1)
    assert ei.value.retry_after_ms >= 2000
    assert plane.router.shed == 1


# ---------------------------------------------------------------------------
# Per-host mesh sizing
# ---------------------------------------------------------------------------

def test_host_layout_and_mesh():
    from quoracle_tpu.parallel.mesh import host_layout, make_host_mesh

    lay = host_layout(4, 8, tp=4)
    assert (lay["dp"], lay["fsdp"], lay["tp"]) == (2, 4, 4)
    assert lay["dp"] * lay["fsdp"] * lay["tp"] == 32
    # tp never spans a host
    assert lay["tp"] <= lay["chips_per_host"]
    # degenerate single-chip case still resolves
    tiny = host_layout(1, 1)
    assert (tiny["dp"], tiny["fsdp"], tiny["tp"]) == (1, 1, 1)
    with pytest.raises(ValueError, match="devices"):
        make_host_mesh(4, 8)              # CPU host has 1 device


def test_pool_sizing_hosts_dimension():
    from quoracle_tpu.parallel.mesh import pool_sizing

    plan = pool_sizing([MEMBER], 4, host_kv_mb=256, replicas=4,
                       disaggregate=True, hosts=2)
    h = plan["hosts"]
    assert h["total_chips"] == 8
    assert h["chips_per_host"] == 4
    assert h["replicas_per_host"] >= 1
    assert h["hosts_needed"] <= 2 and h["fits"]
    assert h["layout"]["n_hosts"] == 2
    # replica tiers size against the full cross-host device set
    assert plan["replica_tiers"]["fits"]
    # hosts=1 keeps the original shape (no hosts block)
    assert "hosts" not in pool_sizing([MEMBER], 8)
    # a pool too wide for one host's chips cannot fit host-locally
    wide = pool_sizing([MEMBER] * 9, 4, replicas=2, hosts=4)
    assert wide["hosts"]["replicas_per_host"] == 0
    assert not wide["fits"]


# ---------------------------------------------------------------------------
# Runtime / CLI / registries / surfaces
# ---------------------------------------------------------------------------

def test_runtime_fabric_flags_mock_refusal_and_cli():
    from quoracle_tpu.cli import build_parser
    from quoracle_tpu.runtime import Runtime, RuntimeConfig

    for kw in ({"fabric_listen": "prefill@127.0.0.1:9400"},
               {"fabric_peers": ["127.0.0.1:9400"]},
               {"prefixd": "127.0.0.1:9470"}):
        with pytest.raises(ValueError, match="--fabric|--prefixd"):
            Runtime(RuntimeConfig(backend="mock", **kw))
    with pytest.raises(ValueError, match="front-door"):
        Runtime(RuntimeConfig(backend="tpu", model_pool=[MEMBER],
                              fabric_peers=["127.0.0.1:1"],
                              fabric_listen="127.0.0.1:2"))
    ns = build_parser().parse_args(
        ["serve", "--fabric-listen", "decode@0.0.0.0:9400",
         "--fabric-peers", "prefill@h1:9400,decode@h2:9400",
         "--prefixd", "h3:9470"])
    assert ns.fabric_listen == "decode@0.0.0.0:9400"
    assert ns.fabric_peers == "prefill@h1:9400,decode@h2:9400"
    assert ns.prefixd == "h3:9470"


def test_runtime_peer_and_frontdoor_over_real_tcp(mono):
    """End-to-end over real sockets: a Runtime serving its backend as a
    fabric peer (--fabric-listen) and a front-door Runtime connecting
    to it (--fabric-peers) — one greedy request, bit-equal."""
    from quoracle_tpu.runtime import Runtime, RuntimeConfig

    rt = Runtime(RuntimeConfig(backend="tpu", model_pool=[MEMBER],
                               fabric_listen="unified@127.0.0.1:0"))
    try:
        addr = rt._fabric_peer._server.addr
        door = Runtime(RuntimeConfig(backend="tpu",
                                     fabric_peers=[f"unified@{addr}"]))
        try:
            assert isinstance(door.backend, FabricPlane)
            assert door.default_pool() == [MEMBER]
            want = mono.query([req()])[0]
            got = door.backend.query([req()])[0]
            assert got.ok, got.error
            assert got.text == want.text
        finally:
            door.close()
            door.backend.close()
    finally:
        rt.close()
        rt.backend.close()


def test_fabric_registries_and_surfaces():
    from quoracle_tpu.analysis.lockdep import COARSE, RANKS
    from quoracle_tpu.chaos.faults import INJECTION_POINTS
    from quoracle_tpu.infra.bus import EventBus, TOPIC_FABRIC
    from quoracle_tpu.infra.event_history import EventHistory
    from quoracle_tpu.infra.flightrec import FLIGHT_EVENTS
    from quoracle_tpu.infra.telemetry import METRICS

    for kind in ("fabric_frame_reject", "fabric_peer_dead",
                 "fabric_handoff_wire", "fabric_prefixd_degraded"):
        assert kind in FLIGHT_EVENTS
    text = METRICS.render_prometheus()
    for name in ("quoracle_fabric_requests_total",
                 "quoracle_fabric_rtt_ms",
                 "quoracle_fabric_retries_total",
                 "quoracle_fabric_frame_rejects_total",
                 "quoracle_fabric_peers",
                 "quoracle_fabric_prefixd_total"):
        assert name in text
    # ranked locks: plane below router? no — plane sits between router
    # and the peer-side locks; transports are coarse I/O serializers
    assert RANKS["router"] < RANKS["fabric.plane"] < RANKS["batcher"]
    assert RANKS["fabric.transport"] < RANKS["batcher"]
    assert RANKS["session.store"] < RANKS["fabric.prefixd"] \
        < RANKS["tier.disk"]
    assert "fabric.transport" in COARSE and "fabric.prefixd" in COARSE
    assert "fabric.send" in INJECTION_POINTS
    assert "fabric.prefixd" in INJECTION_POINTS
    # the TOPIC_FABRIC ring backs /api/history "fabric"
    bus = EventBus()
    hist = EventHistory(bus)
    try:
        bus.broadcast(TOPIC_FABRIC, {"event": "peer_failed",
                                     "peer": "decode-1"})
        ring = hist.replay_fabric()
        assert ring and ring[-1]["peer"] == "decode-1"
    finally:
        hist.close()


def test_api_fabric_payload(fabric):
    from types import SimpleNamespace

    from quoracle_tpu.web.server import DashboardServer

    plane, _ = fabric
    d = DashboardServer(SimpleNamespace(backend=plane))
    payload = d.fabric_payload()
    assert payload["enabled"] and payload["disaggregated"]
    roles = sorted(p["role"] for p in payload["peers"])
    assert roles == ["decode", "prefill"]
    assert "router" in payload
    assert "requests" in payload["counters"]
    # non-fabric backends answer disabled, same shape
    d2 = DashboardServer(SimpleNamespace(backend=object()))
    assert d2.fabric_payload()["enabled"] is False
