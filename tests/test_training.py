"""Serving flywheel (quoracle_tpu/training/, ISSUE 19).

The acceptance bar, in the order the flywheel turns:

  * capture store — crc-framed append-only segments: round-trip
    equality, byte-budget oldest-first eviction, deterministic
    sampling, O(1) stats, and crash-safe recovery that unlinks a
    corrupt-tail segment while every intact segment survives;
  * read-only serving — temp-0 output is BIT-IDENTICAL with capture on
    vs off (greedy, grammar-constrained, speculative) on the
    monolithic backend, the 2-replica cluster plane, and a loopback
    wire peer; the env kill switch really kills;
  * chaos ``train.capture`` — drop/crash injections never block or
    corrupt serving, only capture;
  * the full loop — capture real speculative rounds, pjit-train a
    candidate from them, replay held-out capture through the REAL
    verify_chunk path, beat a lobotomized incumbent, promote through a
    live 2-replica drain/hot-swap (ledgered, zero downtime), then
    force a live acceptance regression and watch the guard auto-roll
    back; a chaos ``train.promote`` crash mid-rollout leaves the
    incumbent serving.
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from quoracle_tpu.models.config import ModelConfig
from quoracle_tpu.models.generate import GenerateEngine
from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
from quoracle_tpu.models.scheduler import _Row
from quoracle_tpu.models.speculative import BatchedSpeculator
from quoracle_tpu.models.tokenizer import ByteTokenizer
from quoracle_tpu.models.transformer import init_params
from quoracle_tpu.training import capture as capmod
from quoracle_tpu.training.capture import CAPTURE, CaptureStore
from quoracle_tpu.training.evaluate import compare, greedy_equal
from quoracle_tpu.training.promote import (
    AcceptanceGuard, PromotionPolicy, Promoter, gate,
)
from quoracle_tpu.training.trainer import (
    TrainerConfig, heldout_split, rows_from_capture, train_from_capture,
)

pytestmark = pytest.mark.train

MEMBER = "xla:tiny"
MSGS = [{"role": "user", "content": "hello flywheel world, please "
                                    "elaborate at length"}]

TARGET = ModelConfig(
    name="flyw-t", vocab_size=512, dim=96, n_layers=3, n_heads=4,
    n_kv_heads=2, ffn_dim=192, context_window=1024, output_limit=256)
DRAFT = ModelConfig(
    name="flyw-d", vocab_size=512, dim=48, n_layers=2, n_heads=2,
    n_kv_heads=2, ffn_dim=96, context_window=1024, output_limit=256)


@pytest.fixture(autouse=True)
def _clean_plane():
    CAPTURE.reset()
    capmod.enable()
    yield
    CAPTURE.reset()
    capmod.enable()


@pytest.fixture(scope="module")
def params():
    tp = init_params(TARGET, jax.random.PRNGKey(0), dtype=jnp.float32)
    dp = init_params(DRAFT, jax.random.PRNGKey(1), dtype=jnp.float32)
    return tp, dp


def t_engine(params, **kw):
    return GenerateEngine(TARGET, params[0], ByteTokenizer(),
                          max_seq=kw.pop("max_seq", 512),
                          prompt_buckets=(32, 64, 128), **kw)


def d_engine(cfg_params, cfg=DRAFT, **kw):
    return GenerateEngine(cfg, cfg_params, ByteTokenizer(),
                          max_seq=kw.pop("max_seq", 512),
                          prompt_buckets=(32, 64, 128), **kw)


def enc(text):
    return ByteTokenizer().encode(text, add_bos=True)


def rec(i, n_ctx=6):
    return {"kind": "spec_round", "ctx": list(range(1, n_ctx + 1)),
            "proposal": [i % 509 + 1] * 3, "verified": [i % 509 + 1] * 3,
            "accepted": 3, "correction": None, "i": i}


# ---------------------------------------------------------------------------
# Capture store: framing, budget, sampling, recovery
# ---------------------------------------------------------------------------

def test_capture_round_trip_and_o1_stats(tmp_path):
    store = CaptureStore(str(tmp_path / "cap"), budget_mb=4.0)
    recs = [rec(i) for i in range(25)]
    for r in recs:
        assert store.append("spec", r) == "ok"
    store.flush()
    got = list(store.read_all("spec"))
    # byte-exact round trip (read_all stamps the source it filtered by)
    assert [{k: v for k, v in g.items() if k != "source"}
            for g in got] == recs
    st = store.stats()
    assert st["appended"] == 25 and st["dropped"] == 0
    assert st["disk_records"] == 25 and st["buffered_records"] == 0
    # O(1) stats agree with a real dir walk
    walked = sum(os.path.getsize(os.path.join(store.path, f))
                 for f in os.listdir(store.path))
    assert st["disk_bytes"] == walked
    assert st["segments"] == len(os.listdir(store.path))


def test_capture_budget_evicts_oldest_first(tmp_path):
    store = CaptureStore(str(tmp_path / "cap"), budget_mb=0.01,
                         segment_kb=1)
    for i in range(300):
        store.append("spec", rec(i))
    store.flush()
    st = store.stats()
    assert st["evicted_segments"] > 0
    assert st["disk_bytes"] <= 0.01 * (1 << 20) + 2048  # one segment slack
    survivors = list(store.read_all("spec"))
    assert survivors                       # newest records survive...
    assert survivors[-1]["i"] == 299
    assert survivors[0]["i"] > 0           # ...oldest were evicted


def test_capture_sampling_is_seed_deterministic(tmp_path):
    kept = []
    for run in range(2):
        store = CaptureStore(str(tmp_path / f"cap{run}"),
                             sample_every=3, seed=42)
        marks = [store.append("spec", rec(i)) for i in range(60)]
        kept.append(marks)
        st = store.stats()
        assert st["sampled_out"] > 0 and st["appended"] > 0
    assert kept[0] == kept[1]              # same seed → same subset


def test_capture_crash_safe_recovery_unlinks_corrupt_tail(tmp_path):
    path = str(tmp_path / "cap")
    store = CaptureStore(path, segment_kb=1)
    for i in range(60):
        store.append("spec", rec(i))
    store.flush()
    segs = sorted(os.listdir(path))
    assert len(segs) >= 3
    # torn write: the NEWEST segment loses its tail mid-frame
    victim = os.path.join(path, segs[-1])
    data = open(victim, "rb").read()
    open(victim, "wb").write(data[:len(data) - 7])
    store2 = CaptureStore(path)            # crash-restart
    st = store2.stats()
    assert st["corrupt_segments"] == 1
    assert not os.path.exists(victim)      # skip-and-unlink
    survivors = list(store2.read_all("spec"))
    assert survivors and survivors[0]["i"] == 0
    assert st["disk_records"] == len(survivors)


def test_capture_read_time_corruption_skips_and_unlinks(tmp_path):
    path = str(tmp_path / "cap")
    store = CaptureStore(path, segment_kb=1)
    for i in range(40):
        store.append("spec", rec(i))
    store.flush()
    segs = sorted(os.listdir(path))
    victim = os.path.join(path, segs[0])
    raw = bytearray(open(victim, "rb").read())
    raw[-3] ^= 0xFF                        # flip a byte in the LAST frame
    open(victim, "wb").write(bytes(raw))
    got = list(store.read_all("spec"))
    # records before the corruption still yield; the tainted segment is
    # unlinked so the next read never re-pays the crc miss
    assert got and len(got) < 40
    assert [g["i"] for g in got] == sorted(g["i"] for g in got)
    assert not os.path.exists(victim)
    assert store.stats()["corrupt_segments"] == 1


def test_env_kill_switch(tmp_path, monkeypatch):
    monkeypatch.setenv("QUORACLE_TRAIN_CAPTURE", "0")
    CAPTURE.reset()                        # re-reads the env
    assert not capmod.enabled()
    CAPTURE.install(str(tmp_path / "cap"))
    assert not CAPTURE.active
    CAPTURE.observe_spec_round("m", "d", [rec(0)])
    CAPTURE.store.flush()
    assert list(CAPTURE.store.read_all("spec")) == []


# ---------------------------------------------------------------------------
# Read-only serving: capture on/off bit-equality on all three planes
# ---------------------------------------------------------------------------

def _ask(b, sid, cj=False):
    return b.query([QueryRequest(MEMBER, MSGS, temperature=0.0,
                                 max_tokens=20, constrain_json=cj,
                                 session_id=sid)])[0]


def _on_off_gate(backend, tmp_path):
    """Query with capture OFF, install a store, query again: texts must
    be bit-identical and the store must hold real spec rounds."""
    off_g, off_c = _ask(backend, "off-g"), _ask(backend, "off-c", cj=True)
    assert off_g.ok and off_c.ok, (off_g.error, off_c.error)
    CAPTURE.install(str(tmp_path / "cap"))
    on_g, on_c = _ask(backend, "on-g"), _ask(backend, "on-c", cj=True)
    assert on_g.ok and on_c.ok, (on_g.error, on_c.error)
    assert on_g.text == off_g.text
    assert on_c.text == off_c.text
    assert on_g.spec_rounds > 0            # the speculative path ran
    CAPTURE.store.flush()
    recs = list(CAPTURE.store.read_all("spec"))
    assert recs and all(r["kind"] == "spec_round" for r in recs)
    assert all(isinstance(r["proposal"], list) and r["proposal"]
               for r in recs)


def test_capture_on_off_bit_identical_mono(tmp_path):
    b = TPUBackend([MEMBER], continuous_chunk=8,
                   draft_map={MEMBER: MEMBER}, draft_k=4)
    try:
        _on_off_gate(b, tmp_path)
    finally:
        b.close()


def test_capture_on_off_bit_identical_cluster(tmp_path):
    from quoracle_tpu.serving.cluster import ClusterPlane
    cl = ClusterPlane.build([MEMBER], replicas=2, continuous_chunk=8,
                            draft_map={MEMBER: MEMBER}, draft_k=4)
    try:
        _on_off_gate(cl, tmp_path)
    finally:
        cl.close()


def test_capture_on_off_bit_identical_wire_peer(tmp_path):
    from quoracle_tpu.serving.cluster import RemoteReplica
    from quoracle_tpu.serving.fabric.frontdoor import FabricPlane
    from quoracle_tpu.serving.fabric.peer import FabricPeer
    from quoracle_tpu.serving.fabric.transport import LoopbackTransport
    peer = FabricPeer.build([MEMBER], role="unified",
                            replica_id="flyw-peer", continuous_chunk=8,
                            draft_map={MEMBER: MEMBER}, draft_k=4)
    plane = FabricPlane([RemoteReplica(
        LoopbackTransport(peer.handle, peer.replica_id))])
    try:
        _on_off_gate(plane, tmp_path)
    finally:
        plane.close()
        peer.close()


# ---------------------------------------------------------------------------
# Chaos train.capture: serving never blocks, only capture degrades
# ---------------------------------------------------------------------------

def test_chaos_capture_crash_never_reaches_serving(tmp_path):
    from quoracle_tpu.chaos.faults import CHAOS, FaultPlan, FaultRule
    from quoracle_tpu.infra.flightrec import FLIGHT
    b = TPUBackend([MEMBER], continuous_chunk=8,
                   draft_map={MEMBER: MEMBER}, draft_k=4)
    try:
        want = _ask(b, "chaos-w")
        CAPTURE.install(str(tmp_path / "cap"))
        CHAOS.arm(FaultPlan(0, [FaultRule("train.capture", "crash")]))
        try:
            got = _ask(b, "chaos-g")
        finally:
            CHAOS.disarm()
        assert got.ok and got.text == want.text   # invariant: read-only
        st = CAPTURE.stats()
        assert st["degraded"]              # the crash was absorbed
        assert st["store"]["dropped"] > 0
        assert any(e["kind"] == "train_capture_degraded"
                   for e in FLIGHT.snapshot())
    finally:
        b.close()


def test_chaos_capture_drop_loses_records_not_output(tmp_path):
    from quoracle_tpu.chaos.faults import CHAOS, FaultPlan, FaultRule
    b = TPUBackend([MEMBER], continuous_chunk=8,
                   draft_map={MEMBER: MEMBER}, draft_k=4)
    try:
        want = _ask(b, "drop-w")
        CAPTURE.install(str(tmp_path / "cap"))
        CHAOS.arm(FaultPlan(0, [FaultRule("train.capture", "drop")]))
        try:
            got = _ask(b, "drop-g")
        finally:
            CHAOS.disarm()
        assert got.ok and got.text == want.text
        CAPTURE.store.flush()
        assert list(CAPTURE.store.read_all("spec")) == []
        assert CAPTURE.store.stats()["dropped"] > 0
        assert not CAPTURE.stats()["degraded"]    # drop is not a crash
    finally:
        b.close()


# ---------------------------------------------------------------------------
# Gate + guard mechanics (pure)
# ---------------------------------------------------------------------------

def _report(margin, n=20):
    inc = 0.10
    return {"model": "m", "n": n,
            "incumbent": {"p50": inc, "p95": inc, "mean": inc, "n": n},
            "candidate": {"p50": inc + margin, "p95": inc + margin,
                          "mean": inc + margin, "n": n},
            "margin_p50": margin}


def test_gate_decisions():
    pol = PromotionPolicy(margin_p50=0.02, min_examples=8)
    assert gate(_report(0.05), pol, True)[0]
    ok, why = gate(_report(0.01), pol, True)
    assert not ok and "margin" in why
    ok, why = gate(_report(0.05, n=3), pol, True)
    assert not ok and why == "too_few_examples"
    ok, why = gate(_report(0.05), pol, False)
    assert not ok and why == "greedy_mismatch"
    assert gate(_report(0.05), PromotionPolicy(
        require_greedy_equal=False), False)[0]


def test_acceptance_guard_trips_on_consecutive_breaches_only():
    pol = PromotionPolicy(min_rounds=5, trip_after=3)
    g = AcceptanceGuard(floor=0.5, policy=pol)
    assert not g.observe(0.1, rounds=2)    # warmup: too few rounds
    assert not g.observe(0.1, rounds=10)   # breach 1
    assert not g.observe(0.9, rounds=11)   # recovery resets the streak
    assert not g.observe(0.1, rounds=12)
    assert not g.observe(0.1, rounds=13)
    assert g.observe(0.1, rounds=14)       # third consecutive: trip
    assert g.tripped
    assert not g.observe(0.1, rounds=15)   # trips exactly once


def test_heldout_split_is_deterministic():
    recs = [rec(i) for i in range(200)]
    a = heldout_split(recs, frac=0.2, seed=3)
    b = heldout_split(recs, frac=0.2, seed=3)
    assert a == b
    assert 10 < len(a[1]) < 80             # roughly the asked fraction
    assert len(a[0]) + len(a[1]) == 200


# ---------------------------------------------------------------------------
# The full flywheel: capture → train → eval → promote → regress → rollback
# ---------------------------------------------------------------------------

def _mk_row(prompt, sid, max_new=48):
    import time
    from concurrent.futures import Future
    return _Row(prompt=list(prompt), temperature=0.0, top_p=1.0,
                max_new=max_new, session_id=sid, constrain=False,
                action_enum=None, future=Future(),
                t_submit=time.monotonic(), owns_session=True)


PROMPTS = [
    "user: tell me a story about consensus machines",
    "user: alpha question goes here",
    "user: beta goes further into the protocol",
    "user: gamma asks about replicated logs",
    "user: delta wants the quorum math",
    "user: epsilon closes the flywheel loop",
]


def _fill_capture(params, path):
    """Serve real speculative rounds (random draft, so corrections and
    partial accepts both land) with the capture tap on."""
    CAPTURE.install(path, budget_mb=8.0)
    eng = t_engine(params)
    dr = d_engine(params[1])
    spec = BatchedSpeculator(eng, dr, k=4, accept_floor=0.0)
    for i, text in enumerate(PROMPTS):
        row = _mk_row(enc(text), f"fill-{i}")
        for _ in range(24):
            fin = spec.run_round([row])
            if fin.get(id(row)) == "stop" or \
                    len(row.emitted) >= row.max_new:
                break
        spec.drop_session(f"fill-{i}")
        eng.drop_session(f"fill-{i}")
    store = CAPTURE.store
    store.flush()
    return eng, store


def test_flywheel_end_to_end(params, tmp_path):
    """The whole loop on one process: captured speculative rounds train
    a candidate that beats a lobotomized (random-weights) incumbent on
    held-out replay through the REAL verify_chunk path, and the
    promotion gate passes it."""
    eng, store = _fill_capture(params, str(tmp_path / "cap"))
    records = list(store.read_all("spec"))
    assert len(records) >= 30
    train_recs, held = heldout_split(records, frac=0.25, seed=0)
    assert train_recs and held

    tcfg = TrainerConfig(steps=60, batch=8, seq=160, lr=1e-3, seed=0,
                         accept_weight=0.25, dp=1)
    cand_params = init_params(DRAFT, jax.random.PRNGKey(2),
                              dtype=jnp.float32)
    trainer, treport = train_from_capture(DRAFT, cand_params, store,
                                          tcfg=tcfg)
    assert treport["steps_run"] == 60
    assert treport["capture_records"] == len(records)

    incumbent = d_engine(params[1])        # the lobotomized baseline
    candidate = d_engine(trainer.params)
    report = compare(eng, incumbent, candidate, held, max_k=6)
    assert report["candidate"]["n"] == report["incumbent"]["n"] > 0
    assert report["candidate"]["p50"] > report["incumbent"]["p50"]

    g_ok = greedy_equal(eng, candidate, [enc(PROMPTS[0])], k=4,
                        max_new=24)
    assert g_ok                            # spec decode is lossless
    pol = PromotionPolicy(margin_p50=0.01, min_examples=4)
    ok, reason = gate(report, pol, g_ok)
    assert ok, (reason, report)


def test_flywheel_trainer_rows_weight_corrections(params, tmp_path):
    """The distillation projection: every captured round yields a row
    whose correction position (when present) carries full weight and
    whose accepted prefix carries accept_weight."""
    _, store = _fill_capture(params, str(tmp_path / "cap"))
    records = list(store.read_all("spec"))
    rows = rows_from_capture(records, seq=160, pad_id=TARGET.eos_token_id,
                             accept_weight=0.25)
    assert rows
    saw_correction = False
    for tokens, targets, weights in rows:
        assert len(tokens) == len(targets) == len(weights) == 160
        ws = set(float(w) for w in weights)
        assert ws <= {0.0, 0.25, 1.0}
        if 1.0 in ws:
            saw_correction = True
    assert saw_correction                  # a random draft gets corrected


def test_flywheel_promote_drain_rollback_live(tmp_path):
    """Promotion mechanics on a LIVE 2-replica cluster: gate → per-
    replica drain/hot-swap (ledgered, sessions intact) → serving stays
    bit-identical → forced acceptance regression → the guard auto-rolls
    back to the recorded incumbents with a train_rollback flight event.
    Then a chaos ``train.promote`` crash on a fresh promotion leaves
    the incumbent serving."""
    from quoracle_tpu.chaos.faults import (
        CHAOS, FaultPlan, FaultRule, InjectedFault,
    )
    from quoracle_tpu.infra.flightrec import FLIGHT
    from quoracle_tpu.models.config import get_model_config
    from quoracle_tpu.serving.cluster import ClusterPlane
    from quoracle_tpu.serving.fleet import FleetController

    # unified replicas: a disaggregated prefill tier carries no drafts,
    # so promotion would (correctly) skip it — here we want both swapped
    cl = ClusterPlane.build([MEMBER], replicas=2, disaggregate=False,
                            continuous_chunk=8,
                            draft_map={MEMBER: MEMBER}, draft_k=4)
    fc = FleetController(cl)
    try:
        want = _ask(cl, "promo-s")         # a session that must survive
        assert want.ok, want.error

        tiny = get_model_config("tiny")
        cand_params = init_params(tiny, jax.random.PRNGKey(9),
                                  dtype=jnp.float32)

        def factory():
            return GenerateEngine(tiny, cand_params, ByteTokenizer(),
                                  max_seq=256,
                                  prompt_buckets=(32, 64, 128))

        promoter = Promoter(PromotionPolicy(
            margin_p50=0.01, min_examples=4, min_rounds=0,
            trip_after=2, require_greedy_equal=True))
        res = promoter.promote_fleet(
            fc, MEMBER, factory, draft_name="tiny-cand",
            report=_report(0.05), greedy_ok=True)
        assert res["promoted"] and res["replicas"] == 2
        # ledgered per replica, zero-downtime drain (no migration)
        swaps = [a for a in fc.stats()["ledger"]
                 if a["action"] == "swap_draft"]
        assert len(swaps) == 2
        for rep in cl.replicas:
            spec = rep.backend._speculators[MEMBER]
            assert spec.draft.cfg is tiny   # candidate serving
            assert rep.backend.draft_map[MEMBER] == "tiny-cand"
        # serving continuity: same session, temp-0 output unchanged
        # (greedy equality holds for ANY draft — that's the spec
        # invariant the whole flywheel leans on)
        msgs2 = MSGS + [{"role": "assistant", "content": want.text},
                        {"role": "user", "content": "continue."}]
        after = cl.query([QueryRequest(MEMBER, msgs2, temperature=0.0,
                                       max_tokens=16,
                                       session_id="promo-s")])[0]
        assert after.ok, after.error
        assert after.cached_tokens > 0      # the session never moved

        # forced live regression: EWMA pinned under the floor trips the
        # guard after trip_after consecutive observations
        assert promoter.observe(MEMBER, ewma=0.0, rounds=100,
                                controller=fc) is None
        rb = promoter.observe(MEMBER, ewma=0.0, rounds=101,
                              controller=fc)
        assert rb is not None and rb["replicas"] == 2
        for rep in cl.replicas:
            assert rep.backend.draft_map[MEMBER] == MEMBER  # restored
        assert any(e["kind"] == "train_rollback"
                   and e.get("outcome") == "regression"
                   for e in FLIGHT.snapshot())
        st = promoter.stats()
        assert st["rollouts"][0]["rolled_back"]
        assert st["rollouts"][0]["rollback_reason"] \
            == "acceptance_regression"
        # still serving after rollback
        again = _ask(cl, "promo-post")
        assert again.ok and again.text == want.text

        # chaos: a crash at train.promote fails the rollout with the
        # incumbent untouched (the swap never started)
        CHAOS.arm(FaultPlan(0, [FaultRule("train.promote", "crash")]))
        try:
            with pytest.raises(InjectedFault):
                promoter.promote_fleet(
                    fc, MEMBER, factory, draft_name="tiny-cand2",
                    report=_report(0.05), greedy_ok=True)
        finally:
            CHAOS.disarm()
        for rep in cl.replicas:
            assert rep.backend.draft_map[MEMBER] == MEMBER
        assert any(e["kind"] == "train_rollback"
                   and e.get("outcome") == "failed"
                   for e in FLIGHT.snapshot())
        final = _ask(cl, "promo-final")
        assert final.ok and final.text == want.text
    finally:
        cl.close()


def test_promoter_rejects_without_touching_fleet():
    promoter = Promoter(PromotionPolicy(margin_p50=0.02))

    class _Boom:
        @property
        def plane(self):               # pragma: no cover - must not run
            raise AssertionError("rejected promotion touched the fleet")

    res = promoter.promote_fleet(_Boom(), MEMBER, lambda: None,
                                 draft_name="x", report=_report(0.001),
                                 greedy_ok=True)
    assert not res["promoted"]
    assert promoter.stats()["rejected"] == 1


# ---------------------------------------------------------------------------
# Registry coherence
# ---------------------------------------------------------------------------

def test_registry_rows_exist():
    from quoracle_tpu.analysis.lockdep import HIERARCHY
    from quoracle_tpu.chaos.faults import INJECTION_POINTS
    from quoracle_tpu.infra.bus import TOPIC_TRAIN
    from quoracle_tpu.infra.flightrec import FLIGHT_EVENTS
    from quoracle_tpu.infra.telemetry import (
        TRAIN_CAPTURE_RECORDS_TOTAL, TRAIN_PROMOTIONS_TOTAL,
    )
    names = {name for name, _, _ in HIERARCHY}
    assert {"train.promote", "train.capture"} <= names
    assert {"train.capture", "train.promote"} <= set(INJECTION_POINTS)
    assert {"train_capture_degraded", "train_capture_evict",
            "train_promote", "train_rollback"} <= set(FLIGHT_EVENTS)
    assert TOPIC_TRAIN == "train:events"
    assert TRAIN_CAPTURE_RECORDS_TOTAL.name \
        == "quoracle_train_capture_records_total"
    assert TRAIN_PROMOTIONS_TOTAL.name == "quoracle_train_promotions_total"


def test_pool_sizing_trainer_section():
    from quoracle_tpu.parallel.mesh import pool_sizing
    plan = pool_sizing(["tiny"], n_devices=8, trainer_chips=4,
                       capture_events_per_s=2.0, capture_mb=128.0)
    tr = plan["trainer"]
    assert tr["chips"] == 4 and tr["layout"]["dp"] == 4
    assert tr["checkpoint_gb"] > 0
    assert tr["capture"]["mb_per_day"] > 0
    assert tr["capture"]["retention_days"] is not None
    assert "trainer" not in pool_sizing(["tiny"], n_devices=8)


def test_api_train_payload(tmp_path):
    """The dashboard surface, without a server: capture census +
    promoter table + counters serialize."""
    from quoracle_tpu.web.server import DashboardServer
    CAPTURE.install(str(tmp_path / "cap"))
    CAPTURE.observe_spec_round("m", "d", [rec(0)])

    class _RT:
        _promoter = Promoter()

    payload = DashboardServer(_RT()).train_payload()
    assert payload["capture"]["installed"]
    assert payload["promoter"]["rejected"] == 0
    assert "promotions" in payload["counters"]
    json.dumps(payload)                    # wire-serializable
