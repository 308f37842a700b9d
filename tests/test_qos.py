"""Serving QoS (ISSUE 4): priority classes, per-tenant token buckets,
weighted-fair DRR admission with an aging floor, overload shedding with
structured rejects, deadline-aware drops, and SLO-driven demotion.

The invariants under test:
  * DRR service shares converge to the configured weights (property);
  * the aging floor bounds starvation — one INTERACTIVE row behind a
    BATCH flood is admitted within the floor;
  * QoS reorders SCHEDULING only: temp-0 outputs are bit-identical with
    QoS on or off;
  * a deadline-expired row fails with the DISTINCT DeadlineExceededError
    (at admit, never decoded) and the consensus engine treats it as a
    member miss, not a pool failure;
  * every shed is a structured reject with retry_after_ms + a
    flight-recorder event — nothing is silently dropped;
  * close() zeroes the scheduler gauges (no phantom depth post-shutdown).
"""

import time
import types

import jax
import jax.numpy as jnp
import pytest

from quoracle_tpu.models.config import get_model_config
from quoracle_tpu.models.generate import GenerateEngine
from quoracle_tpu.models.scheduler import ContinuousBatcher
from quoracle_tpu.models.tokenizer import ByteTokenizer
from quoracle_tpu.models.transformer import init_params
from quoracle_tpu.serving.admission import (
    AdmissionConfig, AdmissionController, DeadlineExceededError,
    OverloadedError, RateLimitedError,
)
from quoracle_tpu.serving.qos import (
    FifoPolicy, Priority, TenantPolicy, TokenBucket, WeightedFairPolicy,
    priority_for_depth,
)
from quoracle_tpu.serving.slo import SLOTracker


def make_engine(**kw):
    cfg = get_model_config("xla:tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return GenerateEngine(cfg, params, ByteTokenizer(),
                          max_seq=kw.pop("max_seq", 256),
                          prompt_buckets=kw.pop("prompt_buckets",
                                                (32, 64, 128)), **kw)


def enc(text):
    return ByteTokenizer().encode(text, add_bos=True)


def row(priority, age_s: float = 0.0):
    return types.SimpleNamespace(priority=priority,
                                 t_submit=time.monotonic() - age_s)


# ---------------------------------------------------------------------------
# qos.py: token bucket + DRR + aging floor (synthetic, no engine)
# ---------------------------------------------------------------------------


def test_token_bucket_spends_refills_and_reports_retry():
    b = TokenBucket(rate_per_s=10.0, burst=2.0)
    now = time.monotonic()
    assert b.try_acquire(now=now) == 0.0
    assert b.try_acquire(now=now) == 0.0
    wait = b.try_acquire(now=now)            # bucket empty
    assert 0.0 < wait <= 0.1 + 1e-6
    # after the reported wait the token exists
    assert b.try_acquire(now=now + wait + 1e-6) == 0.0


def test_drr_shares_converge_to_weights_over_1k_admits():
    """Property (ISSUE 4 satellite): with every class backlogged, 1k+
    pops split within a few percent of the configured 8/4/2/1 shares."""
    pol = WeightedFairPolicy(aging_floor_s=1e9)   # isolate pure DRR
    n = 1500
    for _ in range(n + 8):                        # keep queues backlogged
        for p in Priority:
            pol.put(row(p))
    got = {p: 0 for p in Priority}
    for _ in range(n):
        got[pol.pop().priority] += 1
    total_w = sum(pol.weights.values())
    for p in Priority:
        share = got[p] / n
        want = pol.weights[p] / total_w
        assert abs(share - want) < 0.05, (p, share, want)


def test_aging_floor_serves_stale_row_over_higher_class():
    """A BACKGROUND row past the floor preempts fresh INTERACTIVE work —
    the anti-starvation override beats every weight."""
    pol = WeightedFairPolicy(aging_floor_s=2.0)
    stale = row(Priority.BACKGROUND, age_s=5.0)
    pol.put(stale)
    for _ in range(4):
        pol.put(row(Priority.INTERACTIVE))
    assert pol.pop() is stale
    assert pol.snapshot()["aged_served"] == 1


def test_policy_drain_returns_everything_and_empties():
    pol = WeightedFairPolicy()
    for p in Priority:
        pol.put(row(p))
    assert len(pol.drain()) == len(Priority)
    assert pol.qsize() == 0 and pol.pop() is None


def test_priority_for_depth_root_outranks_grandchildren():
    assert priority_for_depth(0) == Priority.AGENT
    assert priority_for_depth(1) == Priority.BATCH
    assert priority_for_depth(2) == Priority.BATCH
    assert priority_for_depth(3) == Priority.BACKGROUND
    assert priority_for_depth(9) == Priority.BACKGROUND


# ---------------------------------------------------------------------------
# admission.py: shedding, rate limits, tenant clamps
# ---------------------------------------------------------------------------


def test_controller_sheds_bulk_first_then_agent_then_everything():
    ctrl = AdmissionController(AdmissionConfig(max_queue_depth=10))
    # below bound: everyone admitted
    for p in Priority:
        ctrl.admit(priority=p, queue_depth=9)
    # past bound: BATCH sheds with a structured retry hint
    with pytest.raises(OverloadedError) as ei:
        ctrl.admit(priority=Priority.BATCH, queue_depth=10)
    assert ei.value.retry_after_ms > 0
    assert ei.value.as_dict()["reason"] == "overload"
    ctrl.admit(priority=Priority.AGENT, queue_depth=10)      # still in
    # past 2x: AGENT sheds, INTERACTIVE survives
    with pytest.raises(OverloadedError):
        ctrl.admit(priority=Priority.AGENT, queue_depth=20)
    ctrl.admit(priority=Priority.INTERACTIVE, queue_depth=20)
    # past the 4x hard cap: everything sheds
    with pytest.raises(OverloadedError):
        ctrl.admit(priority=Priority.INTERACTIVE, queue_depth=40)
    stats = ctrl.stats()
    assert stats["shed"] == 3 and stats["admitted"] == 6


def test_controller_rate_limits_tenant_and_clamps_class():
    # refill rate ~1 token/17min: the bucket cannot refill mid-test even
    # on a heavily loaded CI host (a 1000/s rate flaked at +1ms wall)
    ctrl = AdmissionController(tenants={
        "bulk": TenantPolicy(name="bulk", rate_per_s=0.001, burst=2,
                             max_class=Priority.BATCH)})
    # the tenant floor: a "bulk" request claiming INTERACTIVE runs BATCH
    assert ctrl.admit(tenant="bulk",
                      priority=Priority.INTERACTIVE,
                      queue_depth=0) == Priority.BATCH
    ctrl.admit(tenant="bulk", priority=Priority.BATCH, queue_depth=0)
    with pytest.raises(RateLimitedError) as ei:
        ctrl.admit(tenant="bulk", priority=Priority.BATCH, queue_depth=0)
    assert ei.value.retry_after_ms >= 1
    assert ei.value.tenant == "bulk"


def test_controller_sheds_on_low_hbm_headroom_bulk_only():
    ctrl = AdmissionController(AdmissionConfig(min_hbm_headroom=0.05),
                               headroom_fn=lambda: 0.01)
    ctrl.refresh_signals(now=time.monotonic() + 10)   # force a refresh
    assert ctrl.hbm_headroom == 0.01
    with pytest.raises(OverloadedError) as ei:
        ctrl.admit(priority=Priority.BATCH, queue_depth=0)
    assert "HBM headroom" in str(ei.value)
    ctrl.admit(priority=Priority.AGENT, queue_depth=0)   # spared


def test_shed_lands_in_flight_recorder():
    from quoracle_tpu.infra.flightrec import FLIGHT
    before = sum(1 for e in FLIGHT.snapshot()
                 if e.get("kind") == "qos_shed")
    ctrl = AdmissionController(AdmissionConfig(max_queue_depth=1))
    with pytest.raises(OverloadedError):
        ctrl.admit(priority=Priority.BATCH, queue_depth=99)
    sheds = [e for e in FLIGHT.snapshot() if e.get("kind") == "qos_shed"]
    assert len(sheds) == before + 1
    assert sheds[-1]["reason"] == "overload"
    assert sheds[-1]["retry_after_ms"] > 0


# ---------------------------------------------------------------------------
# slo.py: EWMA tail tracking + demotion
# ---------------------------------------------------------------------------


def test_slo_demotes_bulk_weight_on_interactive_burn_and_recovers():
    slo = SLOTracker(targets_ms={Priority.INTERACTIVE: 100.0})
    assert slo.weight_multiplier(Priority.BATCH) == 1.0
    for _ in range(6):
        slo.observe(Priority.INTERACTIVE, 500.0)   # tail way over target
    assert slo.demoted
    assert slo.weight_multiplier(Priority.BATCH) == slo.demote_to
    assert slo.weight_multiplier(Priority.BACKGROUND) == slo.demote_to
    # INTERACTIVE and AGENT are never demoted
    assert slo.weight_multiplier(Priority.INTERACTIVE) == 1.0
    assert slo.weight_multiplier(Priority.AGENT) == 1.0
    assert slo.demotions == 1
    for _ in range(40):
        slo.observe(Priority.INTERACTIVE, 10.0)    # burn over
    assert not slo.demoted
    assert slo.weight_multiplier(Priority.BATCH) == 1.0


def test_slo_demotion_scales_drr_weight_live():
    slo = SLOTracker(targets_ms={Priority.INTERACTIVE: 100.0})
    pol = WeightedFairPolicy(aging_floor_s=1e9,
                             weight_fn=slo.weight_multiplier)
    for _ in range(6):
        slo.observe(Priority.INTERACTIVE, 500.0)
    for _ in range(200):
        pol.put(row(Priority.AGENT))
        pol.put(row(Priority.BATCH))
    got = {Priority.AGENT: 0, Priority.BATCH: 0}
    for _ in range(200):
        got[pol.pop().priority] += 1
    # undemoted ratio would be 4:2; demotion (x0.25) pushes it past 6:1
    assert got[Priority.AGENT] / max(1, got[Priority.BATCH]) > 6


# ---------------------------------------------------------------------------
# scheduler integration: real engine, real decode loop
# ---------------------------------------------------------------------------


def test_temp0_equality_qos_on_vs_off():
    """QoS reorders scheduling, never results: one-shot, FIFO-batched,
    and weighted-fair-batched greedy decodes are bit-identical."""
    eng = make_engine()
    p = enc("user: equality under admission policies")
    want = eng.generate([p], temperature=0.0, max_new_tokens=24)[0]
    for policy in (FifoPolicy(),
                   WeightedFairPolicy(model="xla:tiny")):
        cb = ContinuousBatcher(eng, chunk=4, policy=policy,
                               admission=AdmissionController(),
                               slo=SLOTracker())
        try:
            got = cb.submit(p, temperature=0.0, max_new_tokens=24,
                            priority=Priority.INTERACTIVE).result(120)
        finally:
            cb.close()
        assert got.token_ids == want.token_ids, type(policy).__name__
        assert got.text == want.text


def test_interactive_admit_wait_bounded_under_batch_flood():
    """Starvation bound (ISSUE 4 satellite): flood BATCH rows, then
    submit one INTERACTIVE row — its measured admit wait stays under the
    aging floor (it actually rides the class weights to the queue head;
    the floor is the guarantee, the weights are the mechanism)."""
    from quoracle_tpu.infra.telemetry import QOS_ADMIT_WAIT_MS

    floor_s = 3.0
    eng = make_engine()
    cb = ContinuousBatcher(
        eng, chunk=4, max_slots=2,
        policy=WeightedFairPolicy(aging_floor_s=floor_s,
                                  model="xla:tiny"))
    try:
        # pay compiles up front: the flood's own tick shapes (two slots
        # of 4-token chunks), through the batcher that will serve it
        for f in [cb.submit(enc(f"user: bulk warm-up item {i}"),
                            temperature=0.0, max_new_tokens=32,
                            priority=Priority.BATCH) for i in range(4)]:
            f.result(300)
        flood = [cb.submit(enc(f"user: bulk backlog item {i}"),
                           temperature=0.0, max_new_tokens=32,
                           priority=Priority.BATCH)
                 for i in range(10)]
        time.sleep(0.2)                    # flood occupies the slots
        _, s0, n0 = QOS_ADMIT_WAIT_MS.counts(cls="interactive")
        fut = cb.submit(enc("user: a human is waiting"),
                        temperature=0.0, max_new_tokens=4,
                        priority=Priority.INTERACTIVE)
        fut.result(180)
        _, s1, n1 = QOS_ADMIT_WAIT_MS.counts(cls="interactive")
        assert n1 == n0 + 1
        admit_wait_ms = s1 - s0            # exact: histogram sums are raw
        assert admit_wait_ms < floor_s * 1000, admit_wait_ms
        for f in flood:                    # flood still completes fully
            f.result(300)
    finally:
        cb.close()


def test_deadline_expired_row_fails_at_admit_not_decoded():
    """A row whose deadline passed in the queue gets the DISTINCT
    exception type and zero decode work (retired counter untouched)."""
    eng = make_engine()
    cb = ContinuousBatcher(eng, chunk=4)
    try:
        retired0 = cb.retired
        fut = cb.submit(enc("user: too late"), temperature=0.0,
                        max_new_tokens=8,
                        deadline_s=time.monotonic() - 0.001)
        with pytest.raises(DeadlineExceededError) as ei:
            fut.result(60)
        assert ei.value.retry_after_ms == 0
        # live row still serves normally afterwards
        ok = cb.submit(enc("user: on time"), temperature=0.0,
                       max_new_tokens=4).result(120)
        assert ok.n_gen_tokens >= 1
        assert cb.retired == retired0 + 1      # only the live row retired
        assert cb.failed >= 1
    finally:
        cb.close()
    assert len(eng.sessions) == 0              # expired row's session freed


def test_backend_deadline_maps_to_member_miss_error():
    """TPUBackend continuous + deadline_ms=0: the row comes back as a
    deadline_exceeded QueryResult error (a member miss), never a raise."""
    from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
    backend = TPUBackend(pool=["xla:tiny"], continuous_chunk=4)
    try:
        msgs = [{"role": "user", "content": "hello"}]
        res = backend.query([
            QueryRequest("xla:tiny", msgs, temperature=0.0, max_tokens=8,
                         deadline_ms=0.0),
            QueryRequest("xla:tiny", msgs, temperature=0.0, max_tokens=8),
        ])
        assert res[0].error is not None
        assert res[0].error.startswith("deadline_exceeded")
        assert not res[0].permanent_error
        assert res[1].ok, res[1].error
    finally:
        backend.close()


def test_qos_alone_admits_every_row_through_the_controller():
    """``TPUBackend(qos=True)`` and no other argument: the controller it
    builds is the one its member's batcher asks on every submit, and the
    member's weighted-fair queue is the controller's depth source."""
    from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
    backend = TPUBackend(pool=["xla:tiny"], qos=True)
    try:
        ctrl = backend.qos_controller
        cb = backend._cbatchers["xla:tiny"]
        assert cb.admission is ctrl
        assert ctrl.stats()["admitted"] == 0
        assert ctrl.stats()["depth_sources"] == ["xla:tiny"]
        msgs = [{"role": "user", "content": "admit me"}]
        res = backend.query([
            QueryRequest("xla:tiny", msgs, temperature=0.0, max_tokens=4,
                         tenant="t1"),
            QueryRequest("xla:tiny", msgs, temperature=0.0, max_tokens=4,
                         tenant="t2")])
        assert all(r.ok for r in res), [r.error for r in res]
        assert ctrl.stats()["admitted"] == 2
        q = backend.qos_stats()
        assert q["enabled"] and q["admission"]["admitted"] == 2
        assert q["queues"]["xla:tiny"] is not None
        # the SLO tracker saw both rows retire
        assert sum(c["observed"]
                   for c in q["slo"]["classes"].values()) == 2
    finally:
        backend.close()


def test_consensus_treats_deadline_as_member_miss_not_pool_failure():
    """One member missing its deadline must not fail the round: the
    other members' proposals carry it (status ok, deadline_misses=1)."""
    from quoracle_tpu.consensus.engine import ConsensusConfig, ConsensusEngine
    from quoracle_tpu.models.runtime import MockBackend, QueryResult

    class DeadlineyBackend(MockBackend):
        def query(self, requests):
            out = super().query(requests)
            # the first member's row "missed its deadline"
            out[0] = QueryResult(model_spec=out[0].model_spec,
                                 error="deadline_exceeded: 50ms budget "
                                       "elapsed before dispatch")
            return out

    backend = DeadlineyBackend()
    eng = ConsensusEngine(backend, ConsensusConfig(
        model_pool=list(MockBackend.DEFAULT_POOL),
        priority=int(Priority.AGENT), deadline_ms=50.0))
    out = eng.decide({m: [{"role": "user", "content": "go"}]
                      for m in MockBackend.DEFAULT_POOL})
    assert out.status == "ok"
    assert out.deadline_misses == 1
    assert out.decision is not None
    assert any(f.error.startswith("deadline_exceeded")
               for f in out.failures)
    # QoS fields rode the QueryRequests
    assert all(r.priority == int(Priority.AGENT) for r in backend.calls)
    assert all(r.deadline_ms == 50.0 for r in backend.calls)


def test_consensus_temp0_equality_with_qos_fields_mock():
    """MockBackend path: identical decisions with QoS attribution on vs
    off — the fields annotate rows, they never change results."""
    from quoracle_tpu.consensus.engine import ConsensusConfig, ConsensusEngine
    from quoracle_tpu.models.runtime import MockBackend

    def decide(with_qos: bool):
        backend = MockBackend()
        cfg = ConsensusConfig(model_pool=list(MockBackend.DEFAULT_POOL))
        if with_qos:
            cfg.priority = int(Priority.BACKGROUND)
            cfg.tenant = "acme"
            cfg.deadline_ms = 60000.0
        eng = ConsensusEngine(backend, cfg)
        return eng.decide({m: [{"role": "user", "content": "same input"}]
                           for m in MockBackend.DEFAULT_POOL})

    a, b = decide(False), decide(True)
    assert a.status == b.status == "ok"
    assert a.decision.action == b.decision.action
    assert a.decision.params == b.decision.params


def test_close_zeroes_scheduler_gauges():
    """ISSUE 4 satellite bugfix: close() must reset the queue-depth and
    slots-busy gauges — a post-shutdown /metrics scrape shows 0, not the
    last live values."""
    from quoracle_tpu.infra.telemetry import (
        METRICS, SCHED_QUEUE_DEPTH, SCHED_SLOTS_BUSY,
    )
    eng = make_engine()
    cb = ContinuousBatcher(eng, chunk=4, max_slots=2)
    futs = [cb.submit(enc(f"user: row {i}"), temperature=0.0,
                      max_new_tokens=16) for i in range(6)]
    time.sleep(0.2)             # worker admits some; gauges go non-zero
    cb.close()
    for f in futs:
        try:
            f.result(60)
        except RuntimeError:
            pass                # queued-at-close rows fail loudly
    assert SCHED_QUEUE_DEPTH.value(model="tiny") == 0
    assert SCHED_SLOTS_BUSY.value(model="tiny") == 0
    text = METRICS.render_prometheus()
    assert 'quoracle_sched_queue_depth{model="tiny"} 0' in text
    assert 'quoracle_sched_slots_busy{model="tiny"} 0' in text


# ---------------------------------------------------------------------------
# agent depth → priority derivation
# ---------------------------------------------------------------------------


def test_agent_priority_derived_from_tree_depth():
    from quoracle_tpu.agent.core import AgentCore
    from quoracle_tpu.agent.state import AgentConfig, AgentDeps
    from quoracle_tpu.models.runtime import MockBackend

    deps = AgentDeps.for_tests(MockBackend())
    pool = list(MockBackend.DEFAULT_POOL)

    def spawn(agent_id, parent_id=None, **kw):
        core = AgentCore(AgentConfig(agent_id=agent_id, task_id="t1",
                                     model_pool=pool, parent_id=parent_id,
                                     **kw), deps)
        deps.registry.register(agent_id, core, parent_id, "t1")
        return core

    root = spawn("root")
    child = spawn("child", parent_id="root")
    grand = spawn("grand", parent_id="child")
    great = spawn("great", parent_id="grand")
    assert root.engine.config.priority == int(Priority.AGENT)
    assert child.engine.config.priority == int(Priority.BATCH)
    assert grand.engine.config.priority == int(Priority.BATCH)
    assert great.engine.config.priority == int(Priority.BACKGROUND)
    # tenant flows into the consensus config; explicit override wins
    t = spawn("tenant-root", tenant="acme",
              qos_priority=int(Priority.INTERACTIVE))
    assert t.engine.config.tenant == "acme"
    assert t.engine.config.priority == int(Priority.INTERACTIVE)


# ---------------------------------------------------------------------------
# dashboard: /api/qos + 429 with Retry-After on shed
# ---------------------------------------------------------------------------


def test_dashboard_qos_endpoint_and_429_shed():
    import asyncio
    import json as json_mod
    import urllib.error
    import urllib.request

    from quoracle_tpu.models.runtime import MockBackend
    from quoracle_tpu.runtime import Runtime, RuntimeConfig
    from quoracle_tpu.web import DashboardServer

    async def main():
        rt = Runtime(RuntimeConfig(), backend=MockBackend())
        # bearer token → tenant mapping (the DEPLOY.md stanza)
        rt.store.set_setting("qos_tenants", {"acme-token": "acme"})
        # a controller whose hard cap is 0 sheds EVERYTHING — the web
        # layer must surface 429 + Retry-After, never hang the caller
        rt.backend.qos_controller = AdmissionController(
            AdmissionConfig(max_queue_depth=4),
            tenants={"acme": TenantPolicy(name="acme", rate_per_s=0.001,
                                          burst=1)})
        server = await DashboardServer(rt, port=0).start()
        loop = asyncio.get_running_loop()

        def get(path):
            with urllib.request.urlopen(server.url + path,
                                        timeout=10) as r:
                return r.status, json_mod.loads(r.read())

        def post(path, body, token=None):
            req = urllib.request.Request(
                server.url + path, method="POST",
                data=json_mod.dumps(body).encode(),
                headers={"content-type": "application/json",
                         **({"authorization": f"Bearer {token}"}
                            if token else {})})
            try:
                with urllib.request.urlopen(req, timeout=10) as r:
                    return r.status, dict(r.headers), \
                        json_mod.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, dict(e.headers), \
                    json_mod.loads(e.read() or b"{}")

        try:
            status, qos = await loop.run_in_executor(
                None, get, "/api/qos")
            assert status == 200
            assert qos["enabled"] is False      # MockBackend: no QoS wiring
            assert "counters" in qos
            assert qos["tenant_map_configured"] is True

            # default tenant: unlimited → task creation admitted
            status, _, created = await loop.run_in_executor(
                None, lambda: post("/api/tasks",
                                   {"description": "fine"}))
            assert status == 201, created

            # the mapped tenant burns its 1-token bucket, then sheds
            status, _, _ = await loop.run_in_executor(
                None, lambda: post("/api/tasks", {"description": "a"},
                                   token="acme-token"))
            assert status == 201
            status, headers, body = await loop.run_in_executor(
                None, lambda: post("/api/tasks", {"description": "b"},
                                   token="acme-token"))
            assert status == 429
            assert body["reason"] == "rate_limit"
            assert body["tenant"] == "acme"
            assert body["retry_after_ms"] > 0
            assert int(headers["Retry-After"]) >= 1
            # /api/messages rides the same gate
            status, _, body = await loop.run_in_executor(
                None, lambda: post("/api/messages",
                                   {"agent_id": "x", "content": "hi"},
                                   token="acme-token"))
            assert status == 429
            assert body["retry_after_ms"] > 0
        finally:
            await server.stop()
            await rt.shutdown()

    asyncio.run(main())
