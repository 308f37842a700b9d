"""Speculative decoding in the PRODUCTION consensus path (ISSUE 6):
batched draft/verify rounds riding the ContinuousBatcher's live slots
(models/speculative.BatchedSpeculator + GenerateEngine.verify_chunk).

The acceptance bar is the same one PRs 4-5 held QoS and quality to:
temperature-0 output must be BIT-IDENTICAL with speculation on vs off,
at the engine level and through the full continuous+QoS pool path —
any divergence is a cache/commit/grammar bug, never sampling noise.
"""

import time

import jax
import jax.numpy as jnp
import pytest

from quoracle_tpu.models.config import ModelConfig
from quoracle_tpu.models.generate import GenerateEngine
from quoracle_tpu.models.scheduler import ContinuousBatcher, _Row
from quoracle_tpu.models.speculative import BatchedSpeculator
from quoracle_tpu.models.tokenizer import ByteTokenizer
from quoracle_tpu.models.transformer import init_params

TARGET = ModelConfig(
    name="cspec-t", vocab_size=512, dim=96, n_layers=3, n_heads=4,
    n_kv_heads=2, ffn_dim=192, context_window=1024, output_limit=256)
DRAFT = ModelConfig(
    name="cspec-d", vocab_size=512, dim=48, n_layers=2, n_heads=2,
    n_kv_heads=2, ffn_dim=96, context_window=1024, output_limit=256)


@pytest.fixture(scope="module")
def params():
    tp = init_params(TARGET, jax.random.PRNGKey(0), dtype=jnp.float32)
    dp = init_params(DRAFT, jax.random.PRNGKey(1), dtype=jnp.float32)
    return tp, dp


def t_engine(params, **kw):
    return GenerateEngine(TARGET, params[0], ByteTokenizer(),
                          max_seq=kw.pop("max_seq", 512),
                          prompt_buckets=(32, 64, 128), **kw)


def d_engine(params, **kw):
    return GenerateEngine(DRAFT, params[1], ByteTokenizer(),
                          max_seq=kw.pop("max_seq", 512),
                          prompt_buckets=(32, 64, 128), **kw)


def enc(text):
    return ByteTokenizer().encode(text, add_bos=True)


# ---------------------------------------------------------------------------
# verify_chunk: the engine-level primitive
# ---------------------------------------------------------------------------


def test_verify_chunk_verdicts_match_vanilla_argmax(params):
    """Teacher-forced verify verdicts ARE the greedy continuation: feeding
    the target's own greedy tokens as proposals must accept every
    position (ids[t] == proposals[t]), because the chunk forward sees the
    same cache state vanilla decode did."""
    eng = t_engine(params)
    prompt = enc("user: verify primitive")
    want = eng.generate([prompt], temperature=0.0, max_new_tokens=12,
                        session_ids=["vc1"])[0]
    ctx = prompt + want.token_ids
    K = 6
    proposals = eng.generate([ctx], temperature=0.0, max_new_tokens=K,
                             session_ids=["vc1"])[0].token_ids[:K]
    assert len(proposals) >= 1
    res = eng.verify_chunk([ctx + proposals[:-1]], ["vc1"],
                           [len(proposals)], temperature=0.0)[0]
    assert res["ids"] == proposals
    eng.drop_session("vc1")


def test_verify_chunk_requires_sessions(params):
    eng = t_engine(params)
    with pytest.raises(AssertionError):
        eng.verify_chunk([enc("x")], [None], [1])


# ---------------------------------------------------------------------------
# continuous-path equality (engine level)
# ---------------------------------------------------------------------------


def test_continuous_spec_greedy_equals_one_shot(params):
    """Self-draft through the batcher: the spec path's commit/rollback
    against the paged session KV must reproduce one-shot greedy tokens
    bit-for-bit (and accept everything — draft == target)."""
    ref = t_engine(params)
    p = enc("user: tell me a story about consensus machines")
    want = ref.generate([p], temperature=0.0, max_new_tokens=40)[0]

    eng = t_engine(params)
    spec = BatchedSpeculator(eng, eng, k=4)
    cb = ContinuousBatcher(eng, chunk=8, speculator=spec)
    try:
        got = cb.submit(p, temperature=0.0, max_new_tokens=40).result(300)
    finally:
        cb.close()
    assert got.token_ids == want.token_ids
    assert got.finish_reason == want.finish_reason
    assert got.spec_rounds > 0
    assert got.spec_accepted_tokens == got.spec_drafted_tokens
    assert len(eng.sessions) == 0          # owned sessions dropped


def test_continuous_spec_trained_draft_shape_equality(params):
    """A REAL (different-weights) draft: whatever it proposes, accepted
    or rejected, greedy output must equal vanilla — corrections carry the
    stream when the draft is wrong."""
    ref = t_engine(params)
    eng = t_engine(params)
    dr = d_engine(params)
    spec = BatchedSpeculator(eng, dr, k=4, accept_floor=0.0)  # never off
    cb = ContinuousBatcher(eng, chunk=8, speculator=spec)
    try:
        for text in ("user: alpha question", "user: beta goes further"):
            p = enc(text)
            want = ref.generate([p], temperature=0.0,
                                max_new_tokens=32)[0]
            got = cb.submit(p, temperature=0.0,
                            max_new_tokens=32).result(300)
            assert got.token_ids == want.token_ids, text
    finally:
        cb.close()
    st = spec.stats()
    assert st["rounds"] > 0 and st["drafted_tokens"] > 0
    assert len(dr.sessions) == 0           # draft shadow sessions dropped


def test_batched_constrained_drafting_matches_single_row(params):
    """DFA-mask equivalence (ISSUE 6 satellite): three constrained rows
    with DIFFERENT action enums speculating in ONE shared batch must each
    equal (a) the vanilla engine and (b) their own single-row speculative
    run — the stacked-grammar walk in the batched verify can never drift
    from the single-row mask."""
    ref = t_engine(params)
    enums = [("wait", "todo"), ("send_message",), None]
    prompts = [enc("user: act one"), enc("user: act two"),
               enc("user: act three json")]
    wants = [ref.generate([p], temperature=0.0, max_new_tokens=40,
                          constrain_json=[True], action_enums=[e])[0]
             for p, e in zip(prompts, enums)]

    # batched: all three rows share the decode loop + speculator
    eng = t_engine(params)
    dr = d_engine(params)
    cb = ContinuousBatcher(eng, chunk=8,
                           speculator=BatchedSpeculator(
                               eng, dr, k=3, accept_floor=0.0))
    try:
        futs = [cb.submit(p, temperature=0.0, max_new_tokens=40,
                          constrain_json=True, action_enum=e)
                for p, e in zip(prompts, enums)]
        batched = [f.result(300) for f in futs]
    finally:
        cb.close()
    # single-row: same engines fresh, one row at a time
    eng2 = t_engine(params)
    dr2 = d_engine(params)
    cb2 = ContinuousBatcher(eng2, chunk=8,
                            speculator=BatchedSpeculator(
                                eng2, dr2, k=3, accept_floor=0.0))
    try:
        single = [cb2.submit(p, temperature=0.0, max_new_tokens=40,
                             constrain_json=True,
                             action_enum=e).result(300)
                  for p, e in zip(prompts, enums)]
    finally:
        cb2.close()
    for i, (b, s, w) in enumerate(zip(batched, single, wants)):
        assert b.token_ids == w.token_ids, f"row {i} batched != vanilla"
        assert s.token_ids == w.token_ids, f"row {i} single != vanilla"
        assert b.text.lstrip().startswith("{")


def test_mixed_batch_eligible_and_ineligible_rows(params):
    """One tick may hold BOTH kinds: a greedy constrained row (eligible,
    speculates) and a nucleus-sampled row (ineligible, vanilla) — both
    finish correctly, the greedy row bit-equal to vanilla, and the
    fallback is attributed."""
    ref = t_engine(params)
    pg = enc("user: greedy eligible row")
    ps = enc("user: sampled ineligible row")
    want = ref.generate([pg], temperature=0.0, max_new_tokens=24)[0]

    eng = t_engine(params)
    dr = d_engine(params)
    spec = BatchedSpeculator(eng, dr, k=3, accept_floor=0.0)
    cb = ContinuousBatcher(eng, chunk=8, speculator=spec)
    try:
        fg = cb.submit(pg, temperature=0.0, max_new_tokens=24)
        fs = cb.submit(ps, temperature=0.9, top_p=0.5, max_new_tokens=16)
        gg, gs = fg.result(300), fs.result(300)
    finally:
        cb.close()
    assert gg.token_ids == want.token_ids
    assert gg.spec_rounds > 0
    assert gs.n_gen_tokens >= 1 and gs.spec_rounds == 0
    assert spec.stats()["fallbacks"].get("sampling", 0) > 0


def test_sampled_top_p1_rows_speculate_validly(params):
    """temp > 0 with top_p == 1 is ELIGIBLE (greedy one-hot drafting +
    rejection sampling): tokens must be valid vocab ids within budget;
    distribution equality is the construction's guarantee."""
    eng = t_engine(params)
    dr = d_engine(params)
    spec = BatchedSpeculator(eng, dr, k=3, accept_floor=0.0)
    cb = ContinuousBatcher(eng, chunk=8, speculator=spec)
    try:
        g = cb.submit(enc("user: sampled but eligible"), temperature=0.8,
                      top_p=1.0, max_new_tokens=20).result(300)
    finally:
        cb.close()
    assert 1 <= g.n_gen_tokens <= 20
    assert all(0 <= t < TARGET.vocab_size for t in g.token_ids)
    assert g.spec_rounds > 0


# ---------------------------------------------------------------------------
# adaptive K: collapse → shrink → vanilla fallback → re-probe
# ---------------------------------------------------------------------------


def _mk_row(prompt, sid, max_new=64):
    from concurrent.futures import Future
    return _Row(prompt=list(prompt), temperature=0.0, top_p=1.0,
                max_new=max_new, session_id=sid, constrain=False,
                action_enum=None, future=Future(),
                t_submit=time.monotonic(), owns_session=True)


def test_acceptance_collapse_shrinks_then_disengages_then_reprobes(
        params):
    """The full adaptive-K round trip (ISSUE 6 satellite), driven
    synchronously: a hopeless draft (random init vs random init) sags the
    EWMA → K shrinks toward k_min → after ≥3 rounds of evidence the
    member DISENGAGES (vanilla fallback) → ``reprobe_after`` vanilla
    ticks later it re-probes at k_min — and the tokens emitted through
    the whole ordeal still equal vanilla greedy decode."""
    ref = t_engine(params)
    p = enc("user: a long enough prompt to decode through collapse")
    want = ref.generate([p], temperature=0.0, max_new_tokens=64)[0]

    eng = t_engine(params)
    dr = d_engine(params)
    spec = BatchedSpeculator(eng, dr, k=4, k_min=2, accept_floor=0.35,
                             reprobe_after=2)
    row = _mk_row(p, "adapt1")
    rounds = 0
    while spec.engaged and rounds < 20:
        fin = spec.run_round([row])
        rounds += 1
        if fin.get(id(row)) == "stop" or len(row.emitted) >= row.max_new:
            break
    st = spec.stats()
    assert not spec.engaged, f"never disengaged: {st}"
    assert rounds >= 3                      # evidence grace before the cut
    assert st["disengages"] == 1
    # K shrank on the way down (k_init 4 → k_min 2 before the cut)
    assert st["k"] == spec.k_init           # reset for the next engage
    # vanilla fallback: ineligible while disengaged
    assert spec.ineligible_reason(len(p), 0.0, 1.0) == "disengaged"
    # re-probe after reprobe_after vanilla ticks, at k_min
    spec.tick_vanilla()
    assert not spec.engaged
    spec.tick_vanilla()
    assert spec.engaged
    assert spec.k == spec.k_min
    assert spec.stats()["reprobes"] == 1
    # everything committed so far equals the vanilla prefix (corrections
    # carried the stream even at acceptance ~0)
    assert row.emitted == want.token_ids[:len(row.emitted)]
    assert len(row.emitted) > 0
    eng.drop_session("adapt1")
    dr.drop_session("adapt1")


def test_self_draft_grows_k_to_max(params):
    """The other direction: sustained full acceptance grows K toward
    k_max — the sweep start (SPECULATIVE k_sweep) is a floor, not a
    ceiling."""
    eng = t_engine(params)
    spec = BatchedSpeculator(eng, eng, k=3, k_max=6, grow_above=0.85)
    row = _mk_row(enc("user: growth prompt"), "grow1", max_new=48)
    for _ in range(8):
        fin = spec.run_round([row])
        if fin.get(id(row)) == "stop" or len(row.emitted) >= row.max_new:
            break
    assert spec.k > 3
    eng.drop_session("grow1")


# ---------------------------------------------------------------------------
# pool level: continuous + QoS, speculation on vs off
# ---------------------------------------------------------------------------


def test_pool_continuous_qos_spec_on_off_bit_identical():
    """The PR 4-5 gate extended to speculation (acceptance criterion):
    TPUBackend with continuous batching + QoS serves draft_map'd members
    without error, and temp-0 responses — including a session-resident
    refinement round — are bit-identical with speculation on vs off.
    Also covers ConsensusOutcome-bound telemetry: the speculative run
    reports spec_rounds/spec_accepted_tokens on its QueryResults."""
    from quoracle_tpu.models.runtime import QueryRequest, TPUBackend

    pool = ["xla:tiny"]
    off = TPUBackend(pool, continuous_chunk=8, qos=True)
    on = TPUBackend(pool, continuous_chunk=8, qos=True,
                    draft_map={"xla:tiny": "xla:tiny"}, draft_k=4)
    try:
        assert "xla:tiny" in on._speculators
        msgs = [{"role": "user", "content": "hello speculative world"}]

        def ask(b, m, sid):
            return b.query([QueryRequest(
                "xla:tiny", m, temperature=0.0, max_tokens=20,
                constrain_json=True, session_id=sid)])[0]

        w1, g1 = ask(off, msgs, "a1"), ask(on, msgs, "a1")
        assert w1.ok and g1.ok, (w1.error, g1.error)
        assert g1.text == w1.text
        assert g1.spec_rounds > 0 and g1.spec_accepted_tokens > 0
        assert w1.spec_rounds == 0
        msgs2 = msgs + [{"role": "assistant", "content": w1.text},
                        {"role": "user", "content": "refine."}]
        w2, g2 = ask(off, msgs2, "a1"), ask(on, msgs2, "a1")
        assert w2.ok and g2.ok
        assert g2.text == w2.text
        assert g2.cached_tokens > 0          # session residency survived
        stats = on.spec_stats()
        assert stats["enabled"]
        m = stats["members"]["xla:tiny"]
        assert m["rounds"] > 0 and m["acceptance_rate"] is not None
    finally:
        off.close()
        on.close()


def test_draft_map_builds_a_batched_speculator_a_member():
    """A draft_map builds ONE BatchedSpeculator per drafted member and
    hands it to that member's batcher; the draft engine loads but is not
    a servable pool member."""
    from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
    b = TPUBackend(["xla:tiny"],
                   draft_map={"xla:tiny": "xla:tiny-gemma"})
    try:
        assert list(b._speculators) == ["xla:tiny"]
        assert b._cbatchers["xla:tiny"].speculator \
            is b._speculators["xla:tiny"]
        assert "xla:tiny-gemma" in b.engines
        assert "xla:tiny-gemma" not in b.pool
        bad = b.query([QueryRequest(
            "xla:tiny-gemma", [{"role": "user", "content": "hi"}],
            max_tokens=8)])[0]
        assert not bad.ok and bad.permanent_error
    finally:
        b.close()


# ---------------------------------------------------------------------------
# observability satellites
# ---------------------------------------------------------------------------


def test_hbm_attribution_tags_draft_engines():
    """ISSUE 6 satellite: draft params must show up ROLE-TAGGED in the
    per-engine HBM breakdown (never unattributed tail); the draft's
    shadow sessions live in its own page pool, so nothing of a drafted
    member is resident outside an engine's attribution."""
    from quoracle_tpu.infra.resources import hbm_attribution
    from quoracle_tpu.models.runtime import QueryRequest, TPUBackend

    b = TPUBackend(["xla:tiny"],
                   draft_map={"xla:tiny": "xla:tiny-gemma"})
    try:
        # one speculative, sessioned query so the draft engine holds a
        # shadow session
        r = b.query([QueryRequest(
            "xla:tiny",
            [{"role": "user", "content": "attribute me"}],
            temperature=0.0, max_tokens=8, session_id="hbm1")])[0]
        assert r.ok, r.error
        att = hbm_attribution(b)
        members = att["members"]
        assert members["xla:tiny"]["role"] == "member"
        assert members["xla:tiny-gemma"]["role"] == "draft"
        assert members["xla:tiny-gemma"]["draft_for"] == "xla:tiny"
        assert members["xla:tiny-gemma"]["params_bytes"] > 0
        assert b.engines["xla:tiny-gemma"].sessions.get("hbm1") \
            is not None
        assert members["xla:tiny-gemma"]["kv_pool_bytes"] > 0
        assert att["totals"]["draft_params_bytes"] \
            == members["xla:tiny-gemma"]["params_bytes"]
        assert "spec_cache_bytes" not in att["totals"]
    finally:
        b.close()


def test_consensus_outcome_carries_spec_attribution():
    """ISSUE 6 small fix: ConsensusOutcome sums spec_accepted_tokens /
    spec_rounds from the round's QueryResults and the audit record
    exposes them (queryable at /api/consensus)."""
    from quoracle_tpu.consensus.engine import (
        ConsensusConfig, ConsensusEngine,
    )
    from quoracle_tpu.models.runtime import (
        MockBackend, QueryResult,
    )

    class SpecMock(MockBackend):
        def query(self, requests):
            out = super().query(requests)
            return [QueryResult(
                model_spec=r.model_spec, text=r.text, usage=r.usage,
                latency_ms=r.latency_ms, spec_rounds=3,
                spec_accepted_tokens=14) for r in out]

    backend = SpecMock()
    eng = ConsensusEngine(backend, ConsensusConfig(
        model_pool=list(MockBackend.DEFAULT_POOL), session_key="spec-t",
        task_id="task-spec"))
    msgs = {m: [{"role": "user", "content": "go"}]
            for m in MockBackend.DEFAULT_POOL}
    outcome = eng.decide(msgs)
    assert outcome.status == "ok"
    assert outcome.spec_rounds == 3 * len(MockBackend.DEFAULT_POOL)
    assert outcome.spec_accepted_tokens == 14 * len(
        MockBackend.DEFAULT_POOL)
    assert outcome.audit is not None
    assert outcome.audit["spec_accepted_tokens"] \
        == outcome.spec_accepted_tokens
    assert outcome.audit["spec_rounds"] == outcome.spec_rounds


def test_spec_metrics_exported(params):
    """quoracle_spec_* instruments flow from a served round: rounds /
    drafted / accepted counters move, the K and engaged gauges are set,
    and the Prometheus exposition carries the series."""
    from quoracle_tpu.infra.telemetry import (
        METRICS, SPEC_ACCEPTED, SPEC_DRAFTED, SPEC_ENGAGED, SPEC_ROUNDS,
    )
    eng = t_engine(params)
    spec = BatchedSpeculator(eng, eng, k=3)
    model = TARGET.name
    r0 = SPEC_ROUNDS.value(model=model)
    row = _mk_row(enc("user: metrics"), "met1", max_new=16)
    spec.run_round([row])
    assert SPEC_ROUNDS.value(model=model) == r0 + 1
    assert SPEC_DRAFTED.value(model=model) > 0
    assert SPEC_ACCEPTED.value(model=model) > 0
    assert SPEC_ENGAGED.value(model=model) == 1.0
    text = METRICS.render_prometheus()
    assert "quoracle_spec_rounds_total" in text
    assert "quoracle_spec_acceptance" in text
    eng.drop_session("met1")
