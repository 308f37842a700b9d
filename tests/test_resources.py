"""Resource observability (ISSUE 3): the MetricsRegistry collector
mechanism, process gauges, device-memory sampling under the CPU fallback,
the compile registry (hit/miss/storm), scheduler queue health, the stall
watchdog → flight-recorder dump round-trip, prefix-cache occupancy, and
the /api/resources + /api/flightrec/dump endpoints."""

import asyncio
import json
import os
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp

from quoracle_tpu.infra.flightrec import FlightRecorder
from quoracle_tpu.infra.telemetry import METRICS, MetricsRegistry
from quoracle_tpu.models.runtime import MockBackend
from quoracle_tpu.runtime import Runtime, RuntimeConfig, StallWatchdog


# --- collector mechanism ----------------------------------------------------

def test_collector_runs_at_scrape_time_and_exceptions_swallowed():
    reg = MetricsRegistry()
    calls = []

    def good():
        calls.append(1)
        reg.gauge("live_value").set(len(calls))

    reg.register_collector(lambda: 1 / 0)     # must not break the scrape
    reg.register_collector(good)
    snap = reg.snapshot()
    assert snap["live_value"]["series"][""] == 1
    text = reg.render_prometheus()
    assert "live_value 2" in text             # re-sampled, not cached
    reg.remove_collector(good)
    reg.snapshot()
    assert len(calls) == 2                    # removed → no third run


def test_process_gauges_in_snapshot_and_prometheus():
    """Satellite: uptime / thread-count / open-fd gauges ride the
    process-wide registry via the collector (so /api/metrics and
    GET /metrics both carry them)."""
    snap = METRICS.snapshot()
    for name in ("quoracle_process_uptime_s", "quoracle_process_threads"):
        assert name in snap, name
        assert list(snap[name]["series"].values())[0] > 0
    if os.path.isdir("/proc/self/fd"):
        assert list(snap["quoracle_process_open_fds"]
                    ["series"].values())[0] > 0
    text = METRICS.render_prometheus()
    assert "quoracle_process_uptime_s" in text
    assert "quoracle_process_threads" in text


# --- device memory ----------------------------------------------------------

def test_device_memory_stats_cpu_fallback():
    """Under JAX_PLATFORMS=cpu the allocator may expose no memory_stats;
    the live_arrays fallback must still attribute held buffers."""
    from quoracle_tpu.infra import resources
    big = jnp.zeros((256, 1024), jnp.float32)    # keep a live ref
    jax.block_until_ready(big)
    devs = resources.device_memory_stats()
    assert devs, "no devices reported"
    for d in devs:
        assert d["source"] in ("memory_stats", "live_arrays")
        assert d["bytes_in_use"] >= 0
    # the buffer lives on SOME device and is visible in the totals
    assert sum(d["bytes_in_use"] for d in devs) >= big.nbytes / 2
    assert resources.headroom_fraction(
        [{"bytes_in_use": 4, "bytes_limit": 16},
         {"bytes_in_use": 12, "bytes_limit": 16}]) == 0.25
    assert resources.headroom_fraction(
        [{"bytes_in_use": 4, "bytes_limit": 0}]) is None
    del big


# --- compile registry -------------------------------------------------------

def test_compile_registry_hit_miss_and_storm(monkeypatch):
    from quoracle_tpu.infra.telemetry import (
        COMPILE_MISSES_IN_WINDOW, COMPILE_STORM,
    )
    from quoracle_tpu.models.generate import CompileRegistry

    reg = CompileRegistry("tmodel", window_s=0.2, threshold=3)
    assert reg.record((1, 32, 96, 64, False), 1500.0) is True   # miss
    assert reg.record((1, 32, 96, 64, False), 12.0) is False    # hit
    assert reg.record((2, 64, 192, 64, False), 1600.0) is True  # new shape
    assert (reg.hits, reg.misses) == (1, 2)
    assert not reg.storm
    # third distinct shape inside the window → storm trips
    assert reg.record((4, 128, 256, 128, True), 1700.0) is True
    assert reg.storm and reg.storms_total == 1
    assert COMPILE_STORM.value(model="tmodel") == 1.0
    assert COMPILE_MISSES_IN_WINDOW.value(model="tmodel") == 3
    snap = reg.snapshot()
    assert snap["n_shapes"] == 3 and snap["storm"] is True
    assert snap["hit_rate"] == 0.25
    # wall times ledgered, most expensive first
    assert snap["shapes"][0]["compile_ms"] == 1700.0
    # the window ages out → refresh() clears the storm without traffic
    time.sleep(0.25)
    reg.refresh()
    assert not reg.storm
    assert COMPILE_STORM.value(model="tmodel") == 0.0


def test_engine_compile_registry_bucketed_recall_is_hit():
    """Acceptance: a re-call landing in an already-compiled shape bucket
    is a HIT; a new bucket is a MISS (replaces the first-shape-only
    heuristic)."""
    from quoracle_tpu.models.config import get_model_config
    from quoracle_tpu.models.generate import GenerateEngine
    from quoracle_tpu.models.tokenizer import ByteTokenizer
    from quoracle_tpu.models.transformer import init_params

    cfg = get_model_config("xla:tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = GenerateEngine(cfg, params, ByteTokenizer(), max_seq=256,
                         prompt_buckets=(32, 64, 128))
    tok = ByteTokenizer()
    p_short = tok.encode("user: hi", add_bos=True)
    eng.generate([p_short], temperature=0.0, max_new_tokens=8)
    assert (eng.compiles.misses, eng.compiles.hits) == (1, 0)
    # same bucket (different prompt, same T/B/max_new buckets) → hit
    eng.generate([tok.encode("user: yo", add_bos=True)],
                 temperature=0.0, max_new_tokens=8)
    assert (eng.compiles.misses, eng.compiles.hits) == (1, 1)
    # longer prompt crosses the T bucket → miss
    eng.generate([tok.encode("user: " + "x" * 60, add_bos=True)],
                 temperature=0.0, max_new_tokens=8)
    assert eng.compiles.misses == 2
    snap = eng.compiles.snapshot()
    assert snap["n_shapes"] == 2
    assert abs(snap["hit_rate"] - 1 / 3) < 1e-3


# --- scheduler queue health -------------------------------------------------

def test_scheduler_health_metrics_and_stats():
    from quoracle_tpu.infra.telemetry import SCHED_ADMIT_WAIT_MS
    from quoracle_tpu.models.config import get_model_config
    from quoracle_tpu.models.generate import GenerateEngine
    from quoracle_tpu.models.scheduler import ContinuousBatcher
    from quoracle_tpu.models.tokenizer import ByteTokenizer
    from quoracle_tpu.models.transformer import init_params

    cfg = get_model_config("xla:tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = GenerateEngine(cfg, params, ByteTokenizer(), max_seq=256,
                         prompt_buckets=(32, 64, 128))
    tok = ByteTokenizer()
    _, _, n_before = SCHED_ADMIT_WAIT_MS.counts(model="tiny")
    cb = ContinuousBatcher(eng, chunk=4)
    try:
        futs = [cb.submit(tok.encode(f"user: job {i}", add_bos=True),
                          temperature=0.0, max_new_tokens=6)
                for i in range(3)]
        for f in futs:
            f.result(120)
    finally:
        cb.close()
    s = cb.stats()
    assert s["retired"] == 3 and s["failed"] == 0
    assert s["steps"] >= 1 and s["queued"] == 0 and s["closed"]
    active, steps = cb.progress()
    assert active is False and steps == s["steps"]
    _, _, n_after = SCHED_ADMIT_WAIT_MS.counts(model="tiny")
    assert n_after - n_before == 3         # one admission wait per row


# --- watchdog + flight recorder ---------------------------------------------

def test_watchdog_trip_dumps_flight_recorder(tmp_path, monkeypatch):
    """Acceptance: a forced stall produces a readable dump containing the
    last resource samples and spans, a TOPIC_RESOURCES bus event with the
    dump path, and the stalled gauge — which clears when progress
    resumes."""
    monkeypatch.setenv("QUORACLE_FLIGHTREC_DIR", str(tmp_path))
    import quoracle_tpu.runtime as rt_mod
    from quoracle_tpu.infra.bus import TOPIC_RESOURCES, EventBus
    from quoracle_tpu.infra.telemetry import WATCHDOG_STALLED

    flight = FlightRecorder(directory=str(tmp_path))
    flight.record("resource_sample", headroom_frac=0.42, bytes_in_use=123)
    flight.record_span({"event": "span", "name": "generate.decode",
                        "trace_id": "t-1", "duration_ms": 7.5})
    monkeypatch.setattr(rt_mod, "FLIGHT", flight)

    bus = EventBus()
    got = []
    bus.subscribe(TOPIC_RESOURCES, lambda t, e: got.append(e))

    progress = {"active": True, "n": 7}
    wd = StallWatchdog(bus, deadline_s=0.05, poll_s=10.0)
    wd.add_source("decode-loop:test",
                  lambda: (progress["active"], progress["n"]))
    assert wd.check_now() == []            # baseline recorded, no trip
    time.sleep(0.08)
    assert wd.check_now() == ["decode-loop:test"]
    assert wd.check_now() == []            # one trip per wedge, not per poll
    assert WATCHDOG_STALLED.value(source="decode-loop:test") == 1.0
    assert wd.status()["tripped"] == ["decode-loop:test"]

    assert got and got[0]["event"] == "watchdog_stall"
    path = got[0]["dump_path"]
    assert path and os.path.exists(path)
    with open(path) as f:
        dump = json.load(f)
    kinds = [e["kind"] for e in dump["events"]]
    assert "resource_sample" in kinds and "span" in kinds
    assert "watchdog_stall" in kinds
    assert dump["reason"].startswith("watchdog-")
    assert dump["n_events"] == len(dump["events"])

    # progress resumes → gauge clears
    progress["n"] = 8
    wd.check_now()
    assert WATCHDOG_STALLED.value(source="decode-loop:test") == 0.0
    assert wd.status()["tripped"] == []
    wd.close()


def test_watchdog_rearms_after_cooldown(tmp_path, monkeypatch):
    """Regression (ISSUE 11 satellite): the watchdog used to trip once
    per wedge per PROCESS — a second stall (or a wedge outliving the
    first dump) went undetected. Now a still-frozen source re-trips
    after ``rearm_cooldown_s``, and a resolve → re-stall cycle trips
    again immediately."""
    monkeypatch.setenv("QUORACLE_FLIGHTREC_DIR", str(tmp_path))
    import quoracle_tpu.runtime as rt_mod
    flight = FlightRecorder(directory=str(tmp_path))
    monkeypatch.setattr(rt_mod, "FLIGHT", flight)

    progress = {"active": True, "n": 1}
    wd = StallWatchdog(None, deadline_s=0.05, poll_s=10.0,
                       rearm_cooldown_s=0.2)
    wd.add_source("decode-loop:test",
                  lambda: (progress["active"], progress["n"]))
    assert wd.check_now() == []
    time.sleep(0.08)
    assert wd.check_now() == ["decode-loop:test"]
    assert wd.check_now() == []           # inside the cooldown: armed off
    assert wd.trips == 1
    # the SAME wedge persists past the cooldown: fresh trip, fresh dump
    time.sleep(0.25)
    assert wd.check_now() == ["decode-loop:test"]
    assert wd.trips == 2
    # resolve, then a SECOND distinct stall in the same process
    progress["n"] = 2
    wd.check_now()
    assert wd.status()["tripped"] == []
    time.sleep(0.08)
    assert wd.check_now() == ["decode-loop:test"]
    assert wd.trips == 3
    assert wd.status()["rearm_cooldown_s"] == 0.2
    wd.close()


def test_flightrec_dumps_on_sigterm(tmp_path):
    """ISSUE 11 satellite: a SIGTERM (chaos kill, operator drain,
    supervisor timeout) leaves a post-mortem flight dump BEFORE the
    process honors the signal — and the default disposition still runs
    (exit status is the signal's, exactly as without the hook)."""
    import signal
    import subprocess
    import sys

    code = (
        "import os, signal\n"
        "from quoracle_tpu.infra.flightrec import FlightRecorder\n"
        f"fr = FlightRecorder(directory={str(tmp_path)!r})\n"
        "fr.install()\n"
        "fr.record('resource_sample', marker='pre-sigterm')\n"
        "os.kill(os.getpid(), signal.SIGTERM)\n"
        "raise SystemExit('signal did not terminate the process')\n"
    )
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, timeout=120)
    assert proc.returncode == -signal.SIGTERM, (proc.returncode,
                                                proc.stderr[-500:])
    dumps = [f for f in os.listdir(tmp_path)
             if f.startswith("flightrec-") and "signal-SIGTERM" in f]
    assert dumps, os.listdir(tmp_path)
    with open(os.path.join(tmp_path, dumps[0])) as f:
        dump = json.load(f)
    kinds = [e["kind"] for e in dump["events"]]
    assert "signal_dump" in kinds and "resource_sample" in kinds
    assert dump["reason"] == "signal-SIGTERM"


def test_flight_recorder_ring_bound_retention_and_status(tmp_path):
    fr = FlightRecorder(capacity=8, directory=str(tmp_path), retention=3)
    for i in range(20):
        fr.record("tick", i=i)
    events = fr.snapshot()
    assert len(events) == 8                      # bounded ring
    assert [e["i"] for e in events] == list(range(12, 20))
    # five dumps may share the second-resolution stamp; the reason suffix
    # keeps the filenames distinct and the sort order stable
    paths = [fr.dump(reason=f"r{i}") for i in range(5)]
    remaining = sorted(f for f in os.listdir(tmp_path)
                       if f.startswith("flightrec-"))
    assert len(remaining) == 3                   # retention pruned oldest
    assert os.path.basename(paths[-1]) in remaining
    st = fr.status()
    assert st["dumps"] == 5 and st["last_dump"] == paths[-1]
    assert st["n_events"] == 8


# --- prefix-cache occupancy -------------------------------------------------

def test_prefix_cache_occupancy_counts():
    from quoracle_tpu.models.generate import PAGE, SessionStore

    st = SessionStore(max_tokens=PAGE * 8)
    toks = list(range(PAGE * 2))
    pages = st.alloc(2)
    st.insert_prefix(toks, pages)
    with st.lock:
        occ = st.prefix_cache.occupancy()
    # session still holds its reference → referenced, nothing evictable
    assert occ == {"resident_pages": 2, "referenced_pages": 2,
                   "evictable_leaf_pages": 0}
    st.release(pages)                     # session gone; tree refs remain
    with st.lock:
        occ = st.prefix_cache.occupancy()
    # only the LEAF is evictable this pass (its parent still has a child)
    assert occ == {"resident_pages": 2, "referenced_pages": 0,
                   "evictable_leaf_pages": 1}


# --- endpoints --------------------------------------------------------------

async def _get_json(url, token=None):
    def call():
        headers = {}
        if token:
            headers["authorization"] = f"Bearer {token}"
        req = urllib.request.Request(url, headers=headers)
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read() or b"{}")
    return await asyncio.get_running_loop().run_in_executor(None, call)


def test_api_resources_endpoint_and_dump(tmp_path, monkeypatch):
    """Acceptance: GET /api/resources answers under JAX_PLATFORMS=cpu
    (fallback path) with live attribution/compile/scheduler blocks and
    is bearer-gated like /metrics; POST /api/flightrec/dump writes a
    readable file."""
    monkeypatch.setenv("QUORACLE_FLIGHTREC_DIR", str(tmp_path))
    from quoracle_tpu.web import DashboardServer

    async def main():
        rt = Runtime(RuntimeConfig(), backend=MockBackend())
        server = await DashboardServer(rt, port=0).start()
        base = server.url
        try:
            status, r = await _get_json(base + "/api/resources")
            assert status == 200
            assert set(r) == {"process", "devices", "hbm", "compile",
                              "scheduler", "watchdog", "flight_recorder"}
            assert r["process"]["uptime_s"] >= 0
            assert r["process"]["threads"] >= 2
            assert r["devices"] and all(
                d["source"] in ("memory_stats", "live_arrays")
                for d in r["devices"])
            assert r["hbm"]["members"] == {}       # MockBackend: honest empty
            assert r["hbm"]["totals"]["tail_reserve_bytes"] > 0
            assert r["watchdog"]["sources"] == []
            assert r["flight_recorder"]["capacity"] > 0

            # dump on demand
            def post():
                req = urllib.request.Request(
                    base + "/api/flightrec/dump", method="POST",
                    data=json.dumps({"reason": "unit"}).encode(),
                    headers={"content-type": "application/json"})
                with urllib.request.urlopen(req, timeout=10) as resp:
                    return resp.status, json.loads(resp.read())
            status, d = await asyncio.get_running_loop() \
                .run_in_executor(None, post)
            assert status == 201
            assert os.path.exists(d["path"])
            with open(d["path"]) as f:
                assert json.load(f)["reason"] == "unit"

            # /api/history now carries the resources ring
            status, h = await _get_json(base + "/api/history")
            assert status == 200 and "resources" in h
        finally:
            await server.stop()
            rt.close()
    asyncio.run(asyncio.wait_for(main(), 60))


def test_api_resources_bearer_gated(monkeypatch):
    monkeypatch.delenv("QUORACLE_DASHBOARD_TOKEN", raising=False)
    from quoracle_tpu.web import DashboardServer

    async def main():
        rt = Runtime(RuntimeConfig(), backend=MockBackend())
        server = await DashboardServer(rt, port=0,
                                       auth_token="rsrc").start()
        try:
            status, _ = await _get_json(server.url + "/api/resources")
            assert status == 401
            status, r = await _get_json(server.url + "/api/resources",
                                        token="rsrc")
            assert status == 200 and "hbm" in r
        finally:
            await server.stop()
            rt.close()
    asyncio.run(asyncio.wait_for(main(), 60))


def test_tpu_backend_resources_attribution_live():
    """Against a real tiny engine: params/kv-pool bytes attributed, the
    compile block carries the registry snapshot, and the continuous
    scheduler block reports retired rows through /api/resources."""
    from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
    from quoracle_tpu.web import DashboardServer

    async def main():
        backend = TPUBackend(pool=["xla:tiny"], continuous_chunk=4)
        rt = Runtime(RuntimeConfig(), backend=backend)
        server = await DashboardServer(rt, port=0).start()
        try:
            msgs = [{"role": "user", "content": "observe me"}]
            res = backend.query([QueryRequest("xla:tiny", msgs,
                                              temperature=0.0,
                                              max_tokens=8,
                                              session_id="agent-r")])
            assert res[0].ok, res[0].error
            status, r = await _get_json(server.url + "/api/resources")
            assert status == 200
            m = r["hbm"]["members"]["xla:tiny"]
            assert m["params_bytes"] > 0
            assert m["kv_pool_bytes"] > 0         # sessioned call → pool
            assert m["sessions"] == 1
            c = r["compile"]["xla:tiny"]
            assert c["misses"] >= 1
            s = r["scheduler"]["xla:tiny"]
            assert s["retired"] == 1 and s["max_slots"] == 8
            assert r["watchdog"]["sources"] == ["decode-loop:xla:tiny"]
            assert r["watchdog"]["running"] is True
            # the collector also feeds the Prometheus exposition
            text = await asyncio.get_running_loop().run_in_executor(
                None, lambda: urllib.request.urlopen(
                    server.url + "/metrics", timeout=10).read().decode())
            assert "quoracle_hbm_component_bytes" in text
            assert "quoracle_sched_rows_total" in text
        finally:
            await server.stop()
            backend.close()
            rt.close()
    asyncio.run(asyncio.wait_for(main(), 120))


def test_watchdog_only_starts_with_sources():
    rt = Runtime(RuntimeConfig(), backend=MockBackend())
    try:
        assert rt.watchdog.status()["running"] is False
    finally:
        rt.close()
