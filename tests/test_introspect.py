"""Liveness & hotspot plane (infra/introspect.py, ISSUE 18).

The plane's acceptance bar:

  * stall detection — an active-but-frozen progress source trips
    within TWO heartbeat intervals, and the trip bundle carries every
    thread's stack, the cross-thread TrackedLock holder snapshot, and
    the sampling thread's own (EMPTY) held-lock list — the watchdog
    never samples while holding a ranked lock;
  * wait exactness — every row's named waits plus the computed
    ``other`` remainder sum EXACTLY to the observed wall in integer
    ns (the chip-ledger remainder-booking idiom, ISSUE 17), with
    deterministic largest-bucket trimming when measurements skew;
  * read-only — temp-0 output is BIT-IDENTICAL with the plane on and
    off, across greedy, grammar-constrained and speculative decode;
  * burn-triggered capture — a budget trip opens a deterministic-id
    incident whose bundle holds this process's profile + stacks.
"""

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from quoracle_tpu.analysis import lockdep
from quoracle_tpu.analysis.lockdep import LOCKDEP, named_lock
from quoracle_tpu.infra import costobs, fleetobs, introspect
from quoracle_tpu.models.config import get_model_config
from quoracle_tpu.models.generate import GenerateEngine
from quoracle_tpu.models.tokenizer import ByteTokenizer
from quoracle_tpu.models.transformer import init_params

MEMBER = "xla:tiny"


@pytest.fixture(autouse=True)
def _clean_plane():
    introspect.reset()
    introspect.enable()
    yield
    introspect.reset()
    introspect.enable()


def make_engine(**kw):
    cfg = get_model_config(MEMBER)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return GenerateEngine(cfg, params, ByteTokenizer(),
                          max_seq=kw.pop("max_seq", 256),
                          prompt_buckets=kw.pop("prompt_buckets",
                                                (32, 64, 128)), **kw)


def enc(text):
    return ByteTokenizer().encode(text, add_bos=True)


# ---------------------------------------------------------------------------
# WaitClock: exact by construction
# ---------------------------------------------------------------------------

def test_wait_clock_books_exact_remainder():
    c = introspect.WaitClock(t0_ns=0)
    c.note("queue", 300)
    c.note("dispatch", 500)
    closed = c.close(t_end_ns=1000)
    assert closed["wall_ns"] == 1000
    assert closed["waits_ns"]["other"] == 200
    assert sum(closed["waits_ns"].values()) == closed["wall_ns"]
    assert closed["skew_ns"] == 0
    # negative/zero notes are dropped, repeated notes accumulate
    c2 = introspect.WaitClock(t0_ns=0)
    c2.note("lock", -5)
    c2.note("wire", 0)
    c2.note("kv_restore", 10)
    c2.note("kv_restore", 15)
    closed2 = c2.close(t_end_ns=100)
    assert closed2["waits_ns"] == {"kv_restore": 25, "other": 75}


def test_wait_clock_skew_trims_largest_buckets_deterministically():
    def run():
        c = introspect.WaitClock(t0_ns=0)
        c.note("queue", 900)
        c.note("dispatch", 500)
        return c.close(t_end_ns=1000)

    a, b = run(), run()
    assert a == b                          # deterministic trim
    assert a["skew_ns"] == 400
    assert a["waits_ns"]["queue"] == 500   # largest trimmed first
    assert a["waits_ns"]["dispatch"] == 500
    assert a["waits_ns"]["other"] == 0
    assert sum(a["waits_ns"].values()) == a["wall_ns"] == 1000


def test_record_row_waits_aggregates_and_flags_skew():
    from quoracle_tpu.infra.flightrec import FLIGHT
    c = introspect.WaitClock(t0_ns=0)
    c.note("queue", 2_000_000)
    introspect.record_row_waits("m", c.close(t_end_ns=5_000_000))
    tot = introspect.wait_totals()["m"]
    assert tot["rows"] == 1
    assert tot["by_state_ns"]["queue"] == 2_000_000
    assert tot["by_state_ns"]["other"] == 3_000_000
    # a skewed close leaves a wait_skew witness in the flight ring
    before = len([e for e in FLIGHT.snapshot()
                  if e["kind"] == "wait_skew"])
    s = introspect.WaitClock(t0_ns=0)
    s.note("dispatch", 9_000_000)
    introspect.record_row_waits("m", s.close(t_end_ns=1_000_000))
    skews = [e for e in FLIGHT.snapshot() if e["kind"] == "wait_skew"]
    assert len(skews) == before + 1
    assert skews[-1]["skew_ns"] == 8_000_000


# ---------------------------------------------------------------------------
# Heartbeats + gating
# ---------------------------------------------------------------------------

def test_heartbeats_advance_and_gate_off():
    introspect.beat("x.stage")
    introspect.beat("x.stage", 5)
    assert introspect.heartbeat_count("x.stage") == 6
    introspect.disable()
    introspect.beat("x.stage")
    assert introspect.heartbeat_count("x.stage") == 6
    assert lockdep.LOCK_WAIT_HOOK is None  # hook uninstalled with plane
    introspect.enable()
    assert lockdep.LOCK_WAIT_HOOK is introspect._lock_wait


# ---------------------------------------------------------------------------
# Stall detector: trips within two intervals, bundles the evidence
# ---------------------------------------------------------------------------

def test_stall_detector_trips_wedged_stage_within_two_intervals(
        monkeypatch, tmp_path):
    monkeypatch.setenv("QUORACLE_INCIDENT_DIR", str(tmp_path))
    det = introspect.StallDetector(interval_s=1.0)
    progress = {"n": 7, "active": True}
    det.watch("mock.stage", lambda: (progress["active"], progress["n"]))
    assert det.check(now=0.0) == []        # baseline observation
    assert det.check(now=1.9) == []        # < 2 intervals: armed, quiet
    tripped = det.check(now=2.0)           # exactly 2 intervals: trip
    assert tripped == ["mock.stage"]
    assert det.trips == 1
    b = det.last_bundle
    assert b["source"] == "mock.stage"
    assert b["stalled_s"] == 2.0
    # every live thread's stack is in the bundle, this one included
    me = threading.current_thread()
    assert any(k.startswith(f"{me.name}:") for k in b["stacks"])
    assert all(rows for rows in b["stacks"].values())
    assert isinstance(b["holders"], dict)
    # one bundle per distinct wedge: still frozen → no re-trip
    assert det.check(now=5.0) == []
    assert det.trips == 1
    # progress resumes, then freezes again → a fresh trip
    progress["n"] = 8
    assert det.check(now=6.0) == []
    assert det.check(now=8.5) == ["mock.stage"]
    assert det.trips == 2
    # inactive sources never trip, however stale
    progress["active"] = False
    assert det.check(now=99.0) == []
    # the trip opened a deterministic-id incident with this process's
    # introspect attachment beside the flight-ring dump
    stalls = [i for i in fleetobs.INCIDENTS.list()
              if i["kind"] == "stall" and i["key"] == "mock.stage"]
    assert len(stalls) == 2
    att = [f for f in stalls[0]["files"]
           if f.startswith("introspect-stall-")]
    assert att, stalls[0]["files"]
    with open(os.path.join(stalls[0]["path"], att[0])) as f:
        dump = json.load(f)
    assert dump["source"] == "mock.stage"
    assert "stacks" in dump and "profile" in dump and \
        "heartbeats" in dump


def test_stall_capture_never_samples_under_a_ranked_lock(monkeypatch,
                                                         tmp_path):
    """The lockdep assertion (ISSUE 18 satellite): the watchdog thread
    holds NO ranked lock while it walks frames or calls sources — the
    bundle records the sampler's own held stack so the discipline is
    checked on every real trip, not just here."""
    monkeypatch.setenv("QUORACLE_INCIDENT_DIR", str(tmp_path))
    det = introspect.StallDetector(interval_s=1.0)
    held_at_call = []
    det.watch("wedge", lambda: (held_at_call.append(LOCKDEP.held()),
                                (True, 1))[1])
    det.check(now=0.0)
    det.check(now=2.0)
    assert det.trips == 1
    # sources are polled outside the plane lock
    assert held_at_call and all(h == [] for h in held_at_call)
    # and the frame walk ran lock-free too
    assert det.last_bundle["sampler_held"] == []


def test_lockdep_holders_sees_other_threads():
    lk = named_lock("quality")
    seen = threading.Event()
    release = threading.Event()

    def holder():
        with lk:
            seen.set()
            release.wait(timeout=5)

    t = threading.Thread(target=holder, name="holder-thread",
                         daemon=True)
    t.start()
    assert seen.wait(timeout=5)
    try:
        assert lockdep.enabled(), "conftest must enable the sanitizer"
        h = LOCKDEP.holders()
        mine = [v for k, v in h.items() if k.startswith("holder-thread:")]
        assert mine and mine[0][0][0] == "quality"
    finally:
        release.set()
        t.join(timeout=5)


def test_lock_wait_hook_times_contended_acquires_only():
    lk = named_lock("quality")
    introspect.drain_inner_waits()
    with lk:
        pass                              # uncontended: try-acquire wins
    assert introspect.drain_inner_waits() == (0, 0)
    entered = threading.Event()

    def holder():
        with lk:
            entered.set()
            time.sleep(0.05)

    t = threading.Thread(target=holder, daemon=True)
    t.start()
    assert entered.wait(timeout=5)
    with lk:                              # contended: blocking wait timed
        pass
    t.join(timeout=5)
    _, lock_ns = introspect.drain_inner_waits()
    assert lock_ns > 0
    assert introspect.drain_inner_waits() == (0, 0)   # drained


# ---------------------------------------------------------------------------
# Profiler
# ---------------------------------------------------------------------------

def test_profiler_folds_collapsed_stacks_and_rotates():
    from quoracle_tpu.infra.flightrec import FLIGHT
    p = introspect.WallProfiler()
    p.WINDOW_S = 0.0                      # every sample rotates
    p._t_started = time.monotonic()
    stop = threading.Event()
    t = threading.Thread(target=stop.wait, name="prof-target",
                         daemon=True)
    t.start()
    try:
        assert p.sample_once() >= 1       # at least prof-target folded
        assert p.sample_once() >= 1
    finally:
        stop.set()
        t.join(timeout=5)
    snap = p.snapshot()
    assert snap["samples"] == 2
    assert snap["windows"], snap
    win = snap["windows"][-1]
    assert win["samples"] >= 1
    # collapsed form: outermost-first file:func frames joined by ';'
    stack = next(iter(win["stacks"]))
    assert ";" in stack or ":" in stack
    assert any(e["kind"] == "profile_window" for e in FLIGHT.snapshot())
    assert 0.0 <= snap["overhead_frac"] < 1.0


def test_profiler_disabled_samples_nothing():
    introspect.disable()
    p = introspect.WallProfiler()
    assert p.sample_once() == 0
    p.start()
    assert p._thread is None


def test_jax_trace_window_degrades_on_cpu(tmp_path):
    with introspect.jax_trace_window(str(tmp_path)) as armed:
        assert isinstance(armed, bool)
    introspect.disable()
    with introspect.jax_trace_window(str(tmp_path)) as armed:
        assert armed is False


# ---------------------------------------------------------------------------
# Read-only: temp-0 bit-equality with the plane on/off
# ---------------------------------------------------------------------------

def test_engine_temp0_bit_equal_introspect_on_off():
    eng = make_engine()
    p = enc("user: tell me about the liveness plane")
    on_g = eng.generate([p], temperature=0.0, max_new_tokens=24)[0]
    on_c = eng.generate([p], temperature=0.0, max_new_tokens=32,
                        constrain_json=[True])[0]
    assert introspect.heartbeat_count(
        f"engine.tokens:{eng.cfg.name}") > 0
    introspect.disable()
    off_g = eng.generate([p], temperature=0.0, max_new_tokens=24)[0]
    off_c = eng.generate([p], temperature=0.0, max_new_tokens=32,
                         constrain_json=[True])[0]
    assert off_g.token_ids == on_g.token_ids
    assert off_g.text == on_g.text
    assert off_c.token_ids == on_c.token_ids


def test_speculative_temp0_bit_equal_introspect_on_off():
    from quoracle_tpu.models.speculative import SpeculativeDecoder
    cfg = get_model_config(MEMBER)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    spec = SpeculativeDecoder(cfg, params, cfg, params, ByteTokenizer(),
                              k=4, max_seq=256,
                              cache_dtype=jnp.float32)
    p = enc("user: speculative liveness test")
    on = spec.generate(p, temperature=0.0, max_new_tokens=24)
    introspect.disable()
    off = spec.generate(p, temperature=0.0, max_new_tokens=24)
    assert off.token_ids == on.token_ids
    assert off.finish_reason == on.finish_reason


# ---------------------------------------------------------------------------
# Scheduler integration: per-row decomposition, exact on real traffic
# ---------------------------------------------------------------------------

def test_backend_rows_book_exact_waits_on_decode_spans():
    from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
    fleetobs.ensure_ring()
    fleetobs.SPANS.clear()
    b = TPUBackend([MEMBER], continuous_chunk=8)
    try:
        out = b.query([QueryRequest(
            MEMBER, [{"role": "user", "content":
                      "hello liveness plane"}],
            temperature=0.0, max_tokens=20, tenant="acme")])[0]
        assert out.ok, out.error
        eng_name = b.engines[MEMBER].cfg.name
        # heartbeats advanced on the hot path
        beats = introspect.heartbeats()
        assert beats.get(f"sched.tick:{eng_name}", 0) > 0
        assert beats.get(f"sched.retired:{eng_name}", 0) >= 1
        assert beats.get(f"engine.tokens:{eng_name}", 0) > 0
        # every retired row's waits sum EXACTLY to its traced wall
        rows = [s for s in fleetobs.SPANS.spans()
                if s.get("name") == "sched.decode"
                and s.get("waits_ns") is not None]
        assert rows, "no decode span carried waits_ns"
        for s in rows:
            waits = s["waits_ns"]
            assert sum(waits.values()) == s["wall_ns"]
            assert set(waits) <= set(introspect.WAIT_STATES)
            assert waits["other"] >= 0
        # the aggregate the plane serves at /api/profile
        tot = introspect.wait_totals()[eng_name]
        assert tot["rows"] >= 1
        assert sum(tot["by_state_ns"].values()) > 0
        # /api/timeline rolls the same attrs up with an exactness flag
        tl = fleetobs.assemble_timeline(fleetobs.SPANS.spans())
        assert tl["waits"] is not None
        assert tl["waits"]["rows"] >= 1
        assert tl["waits"]["exact"] is True
    finally:
        b.close()


def test_backend_temp0_bit_equal_introspect_on_off():
    from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
    b = TPUBackend([MEMBER], continuous_chunk=8)
    try:
        def q():
            return b.query([QueryRequest(
                MEMBER, [{"role": "user", "content":
                          "scheduler equality probe"}],
                temperature=0.0, max_tokens=20)])[0]
        on = q()
        assert on.ok, on.error
        introspect.disable()
        off = q()
        assert off.ok, off.error
        assert off.text == on.text
    finally:
        b.close()


# ---------------------------------------------------------------------------
# Burn-triggered capture
# ---------------------------------------------------------------------------

def test_budget_trip_opens_deterministic_incident_with_profile(
        monkeypatch, tmp_path):
    monkeypatch.setenv("QUORACLE_INCIDENT_DIR", str(tmp_path))
    costobs.reset()
    costobs.enable()
    tr = costobs.BudgetTracker()
    for i in range(40):
        tr.record("acme", "interactive", True, 10.0 + i)
    for i in range(10):
        tr.record("acme", "interactive", False, 60.0 + i)
    burns = [i for i in fleetobs.INCIDENTS.list()
             if i["kind"] == "burn"
             and i["key"].startswith("acme:interactive:")]
    # both windows (1h, 6h) tripped — one incident each, ids are
    # sha256(kind:key:occurrence), reproducible by construction
    assert {i["key"] for i in burns} == \
        {"acme:interactive:1h", "acme:interactive:6h"}
    for inc in burns:
        # the occurrence counter is process-global (survives incident-dir
        # changes), so recompute the id from the manifest's own occurrence
        # — the determinism claim is id == f(kind, key, occurrence)
        expect = fleetobs.IncidentManager._incident_id(
            "burn", inc["key"], inc["occurrence"])
        assert inc["incident_id"] == expect
        att = [f for f in inc["files"]
               if f.startswith("introspect-burn-")]
        assert att, inc["files"]
        with open(os.path.join(inc["path"], att[0])) as f:
            dump = json.load(f)
        assert dump["incident_id"] == inc["incident_id"]
        assert "profile" in dump and "stacks" in dump
    # the plane off: trips still fire (costobs owns them) but no
    # introspect capture rides along
    introspect.disable()
    tr2 = costobs.BudgetTracker()
    for i in range(40):
        tr2.record("zorp", "interactive", True, 10.0 + i)
    for i in range(10):
        tr2.record("zorp", "interactive", False, 60.0 + i)
    zorp = [i for i in fleetobs.INCIDENTS.list()
            if i["kind"] == "burn" and i["key"].startswith("zorp:")]
    assert zorp == []
    costobs.reset()
    costobs.enable()


# ---------------------------------------------------------------------------
# The /api/profile surface
# ---------------------------------------------------------------------------

def test_profile_payload_shape_and_gate():
    introspect.beat("x.probe")
    out = introspect.profile_payload()
    assert out["enabled"] is True
    assert out["heartbeats"]["x.probe"] == 1
    assert set(out) == {"enabled", "profiler", "heartbeats", "stalls",
                        "waits", "rows"}
    assert "hz" in out["profiler"] and "windows" in out["profiler"]
    assert "watches" in out["stalls"]
    json.dumps(out)                       # wire/HTTP serializable
