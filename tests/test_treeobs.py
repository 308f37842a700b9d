"""Session-graph observability (infra/treeobs.py, ISSUE 20).

The plane's acceptance bar:

  * lineage is O(1) — ``depth_of`` equals the agent-registry parent
    walk it replaces (the QoS depth→class read path), with the walk
    kept as the disabled-plane fallback;
  * rollup conservation is EXACT — recursive subtree totals equal the
    flat per-node sums in integer arithmetic, asserted inside
    ``tree_view`` itself, never approximate;
  * one tree across two loopback wire peers (prefill→decode handoff
    mid-stream) assembles into a SINGLE coherent ``pull_tree`` view,
    and survives a fleet drain migration;
  * a killed peer's nodes surface as ORPHANS (flagged once, rooted as
    fragments), never silently unparented — and only on the kill;
  * temp-0 outputs are bit-identical with the plane on vs off across
    greedy, grammar-constrained, and speculative decode;
  * the sim replay ledger's lineage column reconciles exactly with the
    generated trace (``sim_tree_conservation``), and tampering trips
    the invariant.
"""

import pytest

from quoracle_tpu.infra import treeobs
from quoracle_tpu.infra.flightrec import FLIGHT
from quoracle_tpu.infra.telemetry import TREE_ORPHANS_TOTAL
from quoracle_tpu.infra.treeobs import (
    TreeContext, TreeRegistry, merge_states, tree_view,
)
from quoracle_tpu.models.runtime import QueryRequest

MEMBER = "xla:tiny"
MSGS = [{"role": "user", "content": "hello session graph, elaborate"}]


@pytest.fixture(autouse=True)
def _clean_plane():
    treeobs.reset()
    treeobs.enable()
    yield
    treeobs.reset()
    treeobs.enable()


def req(sid=None, max_tokens=16, content=None, tree=None, cj=False):
    msgs = MSGS if content is None else [{"role": "user",
                                          "content": content}]
    return QueryRequest(MEMBER, msgs, temperature=0.0,
                        max_tokens=max_tokens, session_id=sid,
                        constrain_json=cj, tree=tree)


def _flight_count(kind):
    return sum(1 for e in FLIGHT.snapshot() if e["kind"] == kind)


# ---------------------------------------------------------------------------
# Unit layer: context, lineage, rollups, orphans, budgets, kill switch
# ---------------------------------------------------------------------------

def test_tree_context_roundtrip_and_survives_garbage():
    ctx = TreeContext(tree_id="t1", node_id="n1", parent_id="p1",
                      depth=2, ordinal=1)
    assert TreeContext.from_dict(ctx.to_dict()) == ctx
    for garbage in (None, "str", 7, [], {}, {"tree_id": "t"},
                    {"node_id": "n"}, {"tree_id": "", "node_id": "n"},
                    {"tree_id": "t", "node_id": 3},
                    {"tree_id": "t", "node_id": "n", "parent_id": 9},
                    {"tree_id": "t", "node_id": "n", "depth": "x"}):
        assert TreeContext.from_dict(garbage) is None
    # binding None leaves the current binding untouched
    with treeobs.bind(ctx):
        assert treeobs.current() == ctx
        with treeobs.bind(None):
            assert treeobs.current() == ctx
    assert treeobs.current() is None


def test_depth_o1_equals_registry_walk_and_qos_class():
    """Satellite 1: the O(1) TreeRegistry depth equals the per-tick
    agent-registry parent walk it replaces, so the QoS depth→class
    mapping is unchanged."""
    from quoracle_tpu.serving.qos import priority_for_depth
    reg = TreeRegistry()
    parent = {"r": None}
    reg.register_spawn("r", tree_id="task-d")
    cur = "r"
    for i in range(6):                     # a deep chain
        nid = f"c{i}"
        reg.register_spawn(nid, parent_id=cur)
        parent[nid] = cur
        cur = nid
    for i in range(3):                     # plus siblings off the root
        nid = f"s{i}"
        reg.register_spawn(nid, parent_id="r")
        parent[nid] = "r"

    def walk(nid):                         # the replaced read path
        d, p = 0, parent[nid]
        while p is not None:
            d, p = d + 1, parent[p]
        return d

    for nid in parent:
        assert reg.depth_of(nid) == walk(nid), nid
        assert priority_for_depth(reg.depth_of(nid)) == \
            priority_for_depth(walk(nid))
    assert reg.depth_of("ghost") is None   # unknown → caller falls back


def test_rollup_conservation_exact_and_critical_path():
    r = treeobs.register_spawn("root", tree_id="task-c")
    a = treeobs.register_spawn("a", parent_id="root")
    b = treeobs.register_spawn("b", parent_id="root")
    a1 = treeobs.register_spawn("a1", parent_id="a")
    treeobs.charge_decide(r, 1.0, 10, audit={"entropy_bits": 0.5,
                                             "margin": 0.25,
                                             "dissent": True})
    treeobs.charge_decide(a, 2.0, 40)
    treeobs.charge_decide(b, 0.5, 5)
    treeobs.charge_decide(a1.to_dict(), 3.0, 60)   # dict form too
    treeobs.charge_row_waits(a, {"waits_ns": {"queue": 7, "decode": 3}})
    view = treeobs.tree_payload("task-c")
    assert view["enabled"] and view["conserved"]
    assert view["n_nodes"] == 4 and view["orphans"] == []
    # EXACT integer totals: flat sum == recursive rollup (asserted
    # inside tree_view; re-checked here against hand arithmetic)
    assert view["totals"] == {"chip_ns": 6_500_000, "tokens": 115,
                              "wait_ns": 10}
    rows = {n["node_id"]: n for n in view["nodes"]}
    assert rows["root"]["subtree"] == view["totals"]
    assert rows["a"]["subtree"] == {"chip_ns": 5_000_000, "tokens": 100,
                                    "wait_ns": 10}
    assert rows["a"]["waits"] == {"queue": 7, "decode": 3}
    assert rows["root"]["entropy_mean"] == 0.5
    assert rows["root"]["dissents"] == 1
    # critical path: root → a → a1 (a's chain dominates b's)
    assert view["critical_path"]["node_ids"] == ["root", "a", "a1"]
    assert view["critical_path"]["cost_ns"] == \
        1_000_000 + (2_000_000 + 10) + 3_000_000
    on = [n["node_id"] for n in view["nodes"] if n["on_critical_path"]]
    assert sorted(on) == ["a", "a1", "root"]
    assert view["fanout"] == {"0": 2.0, "1": 0.5, "2": 0.0}


def test_budget_inherited_and_overrun_fires_once_per_node():
    before = _flight_count("tree_budget_overrun")
    treeobs.register_spawn("root", tree_id="task-b", token_budget=100)
    child = treeobs.register_spawn("kid", parent_id="root")
    # inherited: the child's record carries the parent's budget
    state = treeobs.local_tree_state("task-b")
    assert state["trees"]["task-b"]["kid"]["token_budget"] == 100
    treeobs.charge_decide(child, 1.0, 150)
    # both the child and the root subtree overspent: one trip EACH
    assert _flight_count("tree_budget_overrun") == before + 2
    treeobs.charge_decide(child, 1.0, 500)
    assert _flight_count("tree_budget_overrun") == before + 2  # latched
    evs = [e for e in FLIGHT.snapshot()
           if e["kind"] == "tree_budget_overrun"][-2:]
    assert {e["node"] for e in evs} == {"root", "kid"}


def test_completed_trees_age_out_of_bounded_lru():
    reg = TreeRegistry(max_done_trees=2)
    for i in range(5):
        reg.register_spawn(f"t{i}-root", tree_id=f"t{i}")
        reg.complete_node(f"t{i}-root")
    st = reg.stats()
    assert st["done"] == 2 and st["trees"] == 2 and st["nodes"] == 2
    # the two NEWEST completed trees are the survivors
    assert reg.depth_of("t4-root") == 0 and reg.depth_of("t0-root") is None
    # a live tree is never evicted
    reg.register_spawn("live-root", tree_id="live")
    for i in range(5, 9):
        reg.register_spawn(f"t{i}-root", tree_id=f"t{i}")
        reg.complete_node(f"t{i}-root")
    assert reg.depth_of("live-root") == 0


def test_kill_switch_disables_everything(monkeypatch):
    monkeypatch.setenv("QUORACLE_TREEOBS", "0")
    treeobs.reset()
    assert not treeobs.enabled()
    assert treeobs.register_spawn("n", tree_id="t") is None
    assert treeobs.depth_of("n") is None
    treeobs.charge_decide(TreeContext("t", "n"), 1.0, 10)
    treeobs.charge_row_waits(TreeContext("t", "n"),
                             {"waits_ns": {"q": 1}})
    assert treeobs.REGISTRY.stats()["nodes"] == 0
    assert treeobs.tree_payload("t") == {"enabled": False,
                                         "tree_id": "t"}
    assert treeobs.fanout_signals() is None
    monkeypatch.setenv("QUORACLE_TREEOBS", "1")
    treeobs.reset()
    assert treeobs.enabled()


def test_merge_dedups_loopback_registries_sums_distinct_ones():
    door, peer = TreeRegistry(), TreeRegistry()
    ctx = door.register_spawn("root", tree_id="task-m")
    door.charge_decide(ctx, 1.0, 10)
    peer.charge_decide(ctx, 2.0, 20)       # remote slice of same node
    ds, ps = (door.local_state("task-m"), peer.local_state("task-m"))
    # loopback peers re-serve ONE process registry: counted once
    same = merge_states([ds, ds, ds], "task-m")
    assert same["root"]["tokens"] == 10
    # distinct registries (a real remote peer) are summed
    both = merge_states([ds, ps, ds, ps], "task-m")
    assert both["root"]["tokens"] == 30
    assert both["root"]["chip_ns"] == 3_000_000
    view = tree_view("task-m", [ds, ps], registry=door)
    assert view["totals"]["tokens"] == 30 and view["conserved"]


def test_killed_peer_nodes_flagged_orphaned_once_never_unparented():
    door, peer = TreeRegistry(), TreeRegistry()
    door.register_spawn("root", tree_id="task-k")
    kid = door.register_spawn("kid", parent_id="root")
    peer.charge_decide(kid, 2.0, 50)       # the peer only ever charged
    # both registries reachable: ONE coherent tree, zero orphans
    healthy = tree_view("task-k", [door.local_state("task-k"),
                                   peer.local_state("task-k")],
                        registry=door)
    assert healthy["orphans"] == [] and healthy["roots"] == ["root"]
    assert healthy["totals"]["tokens"] == 50
    # the door's registry died with its peer (replica kill): the kid's
    # parent record is MISSING from the assembled view — flagged, rooted
    # as a fragment, flight-fired ONCE across repeated assemblies
    before = TREE_ORPHANS_TOTAL.value()
    orphaned = tree_view("task-k", [peer.local_state("task-k")],
                         registry=peer)
    assert orphaned["orphans"] == ["kid"] and orphaned["roots"] == ["kid"]
    row = orphaned["nodes"][0]
    assert row["orphaned"] and row["parent_id"] == "root"  # kept!
    assert orphaned["conserved"]
    assert TREE_ORPHANS_TOTAL.value() == before + 1
    tree_view("task-k", [peer.local_state("task-k")], registry=peer)
    assert TREE_ORPHANS_TOTAL.value() == before + 1        # once only
    assert _flight_count("tree_orphan") >= 1


def test_fanout_priors_exported_read_only_into_fleet_signals():
    treeobs.register_spawn("r", tree_id="t-f")
    for i in range(3):
        treeobs.register_spawn(f"c{i}", parent_id="r")
    treeobs.register_spawn("g0", parent_id="c0")
    pri = treeobs.fanout_signals()
    assert pri == {"0": 3.0, "1": round(1 / 3, 4), "2": 0.0}
    # FleetSignals carries it observed-only (None when plane off)
    from quoracle_tpu.serving.fleet import FleetSignals
    sig = FleetSignals(replicas=(), tree_fanout=pri)
    assert sig.tree_fanout == pri
    treeobs.disable()
    assert treeobs.fanout_signals() is None


# ---------------------------------------------------------------------------
# Sim lineage: ledger column reconciles exactly with the trace
# ---------------------------------------------------------------------------

def test_sim_tree_conservation_reconciles_and_catches_tampering():
    from quoracle_tpu.sim.gate import SIM_SCENARIOS, sim_tree_conservation
    from quoracle_tpu.sim.replay import ReplayDriver
    from quoracle_tpu.sim.workload import (
        canonical_spec, generate, tree_id_of,
    )
    trace = generate(canonical_spec("agent_tree", seed=11))
    ledger = ReplayDriver(
        trace, capacity=SIM_SCENARIOS["agent_tree"].capacity).run()
    assert any(tree_id_of(e) for e in trace.events)
    ok = sim_tree_conservation(trace, ledger)
    assert ok.ok, ok.detail
    # tamper a tree row's token count: EXACT reconciliation must trip
    row = next(r for r in ledger.rows if r[9] and r[3] == "ok")
    row[8] += 1
    assert not sim_tree_conservation(trace, ledger).ok
    row[8] -= 1
    # tamper the lineage id itself
    row[9] = "tree999-r9"
    bad = sim_tree_conservation(trace, ledger)
    assert not bad.ok and row[0] in bad.detail


# ---------------------------------------------------------------------------
# Durability: one tree across two wire peers, drain, temp-0 equality
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fabric():
    from quoracle_tpu.serving.cluster import RemoteReplica
    from quoracle_tpu.serving.fabric.frontdoor import FabricPlane
    from quoracle_tpu.serving.fabric.peer import FabricPeer
    from quoracle_tpu.serving.fabric.transport import LoopbackTransport
    peers = [FabricPeer.build([MEMBER], role="prefill",
                              replica_id="prefill-0",
                              continuous_chunk=8),
             FabricPeer.build([MEMBER], role="decode",
                              replica_id="decode-0",
                              continuous_chunk=8)]
    plane = FabricPlane([RemoteReplica(LoopbackTransport(p.handle,
                                                         p.replica_id))
                         for p in peers])
    yield plane
    plane.close()
    for p in peers:
        p.close()


@pytest.mark.fabric
def test_tree_across_two_wire_peers_is_one_coherent_view(fabric):
    """The acceptance gate: a stamped request prefills on one wire peer
    and decodes on another (mid-stream handoff), and ``pull_tree``
    assembles door + both peers into ONE conserved tree."""
    treeobs.register_spawn("agent-root", tree_id="task-w")
    kid = treeobs.register_spawn("agent-kid", parent_id="agent-root")
    out = fabric.query([req(sid="tree-w-1", tree=kid.to_dict())])
    assert out[0].ok, out[0].error
    view = fabric.pull_tree("task-w")
    assert view["enabled"] and view["conserved"]
    assert view["n_nodes"] == 2 and view["orphans"] == []
    assert view["roots"] == ["agent-root"]
    rows = {n["node_id"]: n for n in view["nodes"]}
    # the row's wait decomposition landed on the stamped node from the
    # PEER-side schedulers (shared loopback registry, deduped once)
    assert rows["agent-kid"]["wait_ns"] > 0
    assert rows["agent-kid"]["depth"] == 1
    assert rows["agent-root"]["subtree"]["wait_ns"] == \
        rows["agent-kid"]["wait_ns"]
    assert view["critical_path"]["node_ids"] == ["agent-root",
                                                 "agent-kid"]


@pytest.mark.fabric
def test_handoff_envelope_carries_lineage_over_the_wire(fabric):
    """The wire header round-trips the stamp byte-faithfully, and an
    un-upgraded payload (no ``tree`` key) decodes to None."""
    from quoracle_tpu.serving.fabric import wire
    ctx = TreeContext(tree_id="task-e", node_id="n-e", parent_id="p-e",
                      depth=3, ordinal=2)
    r = req(sid="env-1", tree=ctx.to_dict())
    d = wire.request_to_dict(r)
    assert d["tree"] == ctx.to_dict()
    back = wire.request_from_dict(d)
    assert TreeContext.from_dict(back.tree) == ctx
    d.pop("tree")                          # un-upgraded sender
    assert wire.request_from_dict(d).tree is None


@pytest.mark.fabric
def test_temp0_bits_identical_plane_on_vs_off(fabric):
    """Greedy + grammar-constrained through the two-peer fabric: the
    plane is measurement only, bit-for-bit."""
    treeobs.register_spawn("eq-root", tree_id="task-eq")
    stamp = treeobs.REGISTRY.context_of("eq-root").to_dict()
    on_g = fabric.query([req(content="tree equality probe",
                             tree=stamp)])[0]
    on_c = fabric.query([req(content="tree equality probe json",
                             tree=stamp, cj=True)])[0]
    treeobs.disable()
    off_g = fabric.query([req(content="tree equality probe")])[0]
    off_c = fabric.query([req(content="tree equality probe json",
                              cj=True)])[0]
    assert all(o.ok for o in (on_g, on_c, off_g, off_c))
    assert off_g.text == on_g.text
    assert off_c.text == on_c.text


def test_speculative_temp0_bit_identical_plane_on_vs_off():
    import jax
    import jax.numpy as jnp
    from quoracle_tpu.models.config import get_model_config
    from quoracle_tpu.models.speculative import SpeculativeDecoder
    from quoracle_tpu.models.tokenizer import ByteTokenizer
    from quoracle_tpu.models.transformer import init_params
    cfg = get_model_config(MEMBER)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    spec = SpeculativeDecoder(cfg, params, cfg, params, ByteTokenizer(),
                              k=4, max_seq=256, cache_dtype=jnp.float32)
    p = ByteTokenizer().encode("user: speculative tree test",
                               add_bos=True)
    ctx = treeobs.register_spawn("spec-root", tree_id="task-s")
    with treeobs.bind(ctx):
        on = spec.generate(p, temperature=0.0, max_new_tokens=24)
    treeobs.disable()
    off = spec.generate(p, temperature=0.0, max_new_tokens=24)
    assert off.token_ids == on.token_ids
    assert off.finish_reason == on.finish_reason


# ---------------------------------------------------------------------------
# Drain migration: lineage survives the envelope hop
# ---------------------------------------------------------------------------

@pytest.mark.fabric
def test_tree_survives_fleet_drain_migration():
    from quoracle_tpu.serving.cluster import ClusterPlane
    from quoracle_tpu.serving.fleet import FleetConfig, FleetController
    cl = ClusterPlane.build([MEMBER], replicas=3, disaggregate=True,
                            continuous_chunk=8)
    fleet = FleetController(cl, FleetConfig(
        min_replicas=1, max_replicas=4, hysteresis_ticks=2,
        cooldown_ticks=2, seed=7))
    try:
        treeobs.register_spawn("dr-root", tree_id="task-dr")
        kid = treeobs.register_spawn("dr-kid", parent_id="dr-root")
        sid = "tree-drain-1"
        b1 = cl.query([req(sid=sid, tree=kid.to_dict())])[0]
        assert b1.ok, b1.error
        waits_before = {n["node_id"]: n["wait_ns"]
                        for n in cl.pull_tree("task-dr")["nodes"]}
        assert waits_before["dr-kid"] > 0
        src = cl.router.affinity_of(sid)
        summary = fleet.drain(src.replica_id, reason="treeobs-test")
        assert summary["migrated"] >= 1 and not summary["died"]
        msgs2 = MSGS + [{"role": "assistant", "content": b1.text},
                        {"role": "user", "content": "continue."}]
        b2 = cl.query([QueryRequest(MEMBER, msgs2, temperature=0.0,
                                    max_tokens=16, session_id=sid,
                                    tree=kid.to_dict())])[0]
        assert b2.ok, b2.error
        view = cl.pull_tree("task-dr")
        # still ONE coherent tree, same root, no orphans, and the
        # post-drain round kept booking to the SAME node
        assert view["conserved"] and view["orphans"] == []
        assert view["roots"] == ["dr-root"] and view["n_nodes"] == 2
        rows = {n["node_id"]: n for n in view["nodes"]}
        assert rows["dr-kid"]["wait_ns"] > waits_before["dr-kid"]
        cl.drop_session(sid)
    finally:
        cl.close()


# ---------------------------------------------------------------------------
# Registries and surfaces
# ---------------------------------------------------------------------------

def test_registries_and_surfaces():
    from quoracle_tpu.analysis.lockdep import RANKS
    from quoracle_tpu.infra.flightrec import FLIGHT_EVENTS
    from quoracle_tpu.infra.telemetry import METRICS
    from quoracle_tpu.serving.fabric import wire
    for name in ("quoracle_tree_nodes_total",
                 "quoracle_tree_orphans_total",
                 "quoracle_tree_budget_overruns_total",
                 "quoracle_tree_depth",
                 "quoracle_tree_fanout"):
        assert name in METRICS.snapshot(), name
    assert "tree_orphan" in FLIGHT_EVENTS
    assert "tree_budget_overrun" in FLIGHT_EVENTS
    assert wire.op_name(wire.MSG_OBS) == "obs"
    assert RANKS["train.capture"] < RANKS["treeobs"] < RANKS[
        "chaos.plan"]
