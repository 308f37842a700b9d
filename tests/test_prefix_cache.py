"""Radix prefix cache (models/prefix_cache.py): ref-counted, copy-on-write
KV page sharing across sessions.

Covers the subsystem's invariants end to end:
  * tree mechanics — page-aligned match, dedupe on insert, LRU leaf
    eviction that never touches a referenced page (I1/I3);
  * pool pressure — SessionStore.alloc evicts unreferenced cache leaves
    before resident sessions, exact attainability accounting, and a
    post-eviction lookup re-prefills correctly;
  * temperature-0 outputs bit-identical with the cache on vs off;
  * copy-on-write — a session extending/diverging inside a shared page
    swaps a fresh copy and never corrupts its sibling (I2);
  * the consensus fan-out shape — K rows sharing a prompt in ONE batch
    prefill it once (intra-batch wave split), and continuous-batching
    rows hit the cache too;
  * telemetry — hit/miss/evict/COW counters via stats() and the
    TPUBackend serving broadcast.
"""

import jax
import jax.numpy as jnp

from quoracle_tpu.models.config import get_model_config
from quoracle_tpu.models.generate import (
    GenerateEngine, SessionStore, _Session,
)
from quoracle_tpu.models.tokenizer import ByteTokenizer
from quoracle_tpu.models.transformer import init_params


def make_engine(**kw):
    cfg = get_model_config("xla:tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return GenerateEngine(cfg, params, ByteTokenizer(), max_seq=256,
                          prompt_buckets=(32, 64, 128), **kw)


def enc(text):
    return ByteTokenizer().encode(text, add_bos=True)


SHARED_SYS = "system: " + "policy rules apply here. " * 7   # > 1 page


# ---------------------------------------------------------------------------
# Tree mechanics (store-level, page=4 for readable numbers)
# ---------------------------------------------------------------------------

def test_match_is_page_aligned_and_capped():
    store = SessionStore(max_tokens=6 * 4, page=4)
    toks = list(range(12))
    pages = store.alloc(3)
    store.insert_prefix(toks, pages)
    pc = store.prefix_cache
    # full 3-page prefix cached; max_reuse caps the walk page-aligned
    assert pc.match_len(toks, len(toks)) == 12
    assert pc.match_len(toks, 11) == 8      # len-1 cap -> one page less
    assert pc.match_len(toks, 3) == 0       # under a page: no match
    # divergence inside page 2 matches only the aligned prefix before it
    assert pc.match_len(toks[:8] + [99, 99, 99, 99], 12) == 8
    got, n = pc.match(toks, 11)
    assert n == 8 and got == pages[:2]
    assert pc.stats()["hits"] == 1 and pc.stats()["hit_tokens"] == 8


def test_insert_dedupes_onto_existing_nodes():
    store = SessionStore(max_tokens=6 * 4, page=4)
    toks = list(range(8))
    pa = store.alloc(2)
    store.insert_prefix(toks, pa)
    # a second session stores the SAME blocks under different pages: the
    # tree keeps the first copy, the duplicate stays the session's own
    pb = store.alloc(2)
    added = store.insert_prefix(toks, pb)
    assert added == 0
    assert store.prefix_cache.match(toks, 8)[0] == pa


def test_eviction_prefers_unreferenced_leaves_over_sessions():
    """Satellite: fill the pool with referenced pages; new allocations
    evict only unreferenced cache leaves, never shared live pages."""
    store = SessionStore(max_tokens=6 * 4, page=4)   # 6 usable pages
    # dead session "a": its prefix lives on only in the tree
    toks_a = list(range(8))
    pa = store.alloc(2)
    store.put("a", _Session(tokens=toks_a, pages=pa))
    store.insert_prefix(toks_a, pa)
    store.drop("a")                       # pages now cache-only (ref 1)
    # live session "b": resident AND cached (ref 2)
    toks_b = [90 + i for i in range(8)]
    pb = store.alloc(2)
    store.put("b", _Session(tokens=toks_b, pages=pb))
    store.insert_prefix(toks_b, pb)
    assert store.free_pages() == 2
    # need 4 pages with "b" protected: 2 free + a's 2 cache leaves; b's
    # live/shared pages must survive untouched
    got = store.alloc(4, protect=("b",))
    assert got is not None and len(got) == 4
    assert store.get("b") is not None
    assert set(pb).isdisjoint(got)
    assert store.prefix_cache.match_len(toks_b, 8) == 8   # b still cached
    assert store.prefix_cache.match_len(toks_a, 8) == 0   # a evicted
    assert store.prefix_cache.stats()["evicted_pages"] == 2
    # nothing left to take: protected + live-referenced pages never evict,
    # and the refusal evicts nothing (exact attainability precheck)
    assert store.alloc(1, protect=("b",)) is None
    assert store.get("b") is not None
    assert store.prefix_cache.match_len(toks_b, 8) == 8


def test_tree_eviction_is_lru():
    store = SessionStore(max_tokens=3 * 4, page=4)    # 3 usable pages
    toks_x, toks_y = [1, 2, 3, 4], [5, 6, 7, 8]
    px = store.alloc(1)
    store.insert_prefix(toks_x, px)
    store.release(px)                      # cache-only
    py = store.alloc(1)
    store.insert_prefix(toks_y, py)
    store.release(py)                      # cache-only, more recent
    store.prefix_cache.match(toks_x, 4)    # bump X: now Y is LRU
    got = store.alloc(2)                   # 1 free + evict exactly one
    assert got is not None
    assert store.prefix_cache.match_len(toks_x, 4) == 4
    assert store.prefix_cache.match_len(toks_y, 4) == 0


# ---------------------------------------------------------------------------
# Engine integration: the equalities hold on both paged paths (conftest
# ``paged_path``), the ragged programs and the gather fallback
# ---------------------------------------------------------------------------

def test_adoption_survives_donor_death(paged_path):
    """The cache's own page references keep a prefix adoptable after the
    session that prefilled it is dropped — the old donor-scan sharing
    could not do this."""
    eng = make_engine()
    plain = make_engine()
    plain.prefix_sharing = False
    pa = enc(SHARED_SYS + "user: task alpha")
    eng.generate([pa], temperature=0.0, max_new_tokens=8,
                 session_ids=["a"])
    eng.drop_session("a")                  # donor dead, prefix cached
    pb = enc(SHARED_SYS + "user: task beta")
    rb = eng.generate([pb], temperature=0.0, max_new_tokens=8,
                      session_ids=["b"])
    assert rb[0].n_cached_tokens >= 128, \
        "cached prefix not adopted after donor drop"
    want = plain.generate([pb], temperature=0.0, max_new_tokens=8,
                          session_ids=["w"])
    assert rb[0].token_ids == want[0].token_ids


def test_temperature0_bit_identical_cache_on_vs_off(paged_path):
    """Satellite: greedy outputs must be bit-identical with the prefix
    cache enabled vs disabled, across fresh sessions that hit the cache."""
    on = make_engine()
    off = make_engine()
    off.prefix_sharing = False
    for sid, task in [("a", "alpha"), ("b", "beta"), ("c", "gamma")]:
        p = enc(SHARED_SYS + "user: task " + task)
        got = on.generate([p], temperature=0.0, max_new_tokens=10,
                          session_ids=[sid])
        want = off.generate([p], temperature=0.0, max_new_tokens=10,
                            session_ids=[sid])
        assert got[0].token_ids == want[0].token_ids, \
            f"cache-on output diverged for session {sid}"
    st = on.sessions.prefix_cache.stats()
    assert st["hits"] >= 2 and st["hit_tokens"] >= 256   # b and c hit
    assert off.sessions.prefix_cache.stats()["hits"] == 0


def test_cow_shared_page_extension_preserves_sibling(paged_path):
    """Satellite: a session diverging INSIDE a shared page (extending the
    partially reused boundary) must copy-on-write — the swap counter
    moves and the sibling's adopted KV stays byte-intact."""
    eng = make_engine()
    plain = make_engine()
    plain.prefix_sharing = False
    pa = enc(SHARED_SYS + "user: task alpha")
    eng.generate([pa], temperature=0.0, max_new_tokens=8,
                 session_ids=["a"])
    pb = enc(SHARED_SYS + "user: task beta")
    rb = eng.generate([pb], temperature=0.0, max_new_tokens=8,
                      session_ids=["b"])
    assert rb[0].n_cached_tokens >= 128
    assert eng.sessions.prefix_cache.cow_copies == 0
    # "a" extends a PARTIALLY REUSED shared page: divergence at token 100
    # lands mid-page-0, which the cache and "b" both reference
    pa_div = pa[:100] + enc("user: rewritten after condensation")[1:]
    ra2 = eng.generate([pa_div], temperature=0.0, max_new_tokens=8,
                       session_ids=["a"])
    assert eng.sessions.prefix_cache.cow_copies >= 1, \
        "divergent write into a shared page did not COW"
    want_div = plain.generate([pa_div], temperature=0.0, max_new_tokens=8,
                              session_ids=["wa"])
    assert ra2[0].token_ids == want_div[0].token_ids
    # sibling "b" continues on the shared prefix, uncorrupted
    pb2 = pb + rb[0].token_ids + enc(" more")[1:]
    rb2 = eng.generate([pb2], temperature=0.0, max_new_tokens=8,
                       session_ids=["b"])
    wb = plain.generate([pb], temperature=0.0, max_new_tokens=8,
                        session_ids=["wb"])
    pwb2 = pb + wb[0].token_ids + enc(" more")[1:]
    wb2 = plain.generate([pwb2], temperature=0.0, max_new_tokens=8,
                         session_ids=["wb"])
    assert rb2[0].token_ids == wb2[0].token_ids, \
        "COW failed: sibling read a rewritten shared page"


def test_eviction_under_pressure_then_lookup_reprefills(paged_path):
    """Satellite: pool pressure evicts the cached prefix; the next lookup
    misses cleanly and re-prefills to the same greedy tokens."""
    # 6 usable pages (768 tokens at 512 B/token for xla:tiny fp32)
    eng = make_engine(session_max_bytes=768 * 512)
    plain = make_engine()
    plain.prefix_sharing = False
    assert eng.sessions.n_pages == 7
    pa = enc(SHARED_SYS + "user: task alpha")
    eng.generate([pa], temperature=0.0, max_new_tokens=8,
                 session_ids=["a"])
    eng.drop_session("a")                 # 1+ page stays cache-only
    assert eng.sessions.prefix_cache.stats()["cached_pages"] >= 1
    # unrelated sessions flood the pool; the cache leaf must be reclaimed
    # rather than starving the live allocations
    for k in range(4):
        filler = enc(f"user: filler conversation {k} " + "z" * 160)
        eng.generate([filler], temperature=0.0, max_new_tokens=8,
                     session_ids=[f"f{k}"])
    assert eng.sessions.prefix_cache.stats()["evicted_pages"] >= 1
    # post-eviction: same-prefix session misses (or partially hits) and
    # still generates exactly the fresh-engine tokens
    pb = enc(SHARED_SYS + "user: task beta")
    rb = eng.generate([pb], temperature=0.0, max_new_tokens=8,
                      session_ids=["b"])
    want = plain.generate([pb], temperature=0.0, max_new_tokens=8,
                          session_ids=["w"])
    assert rb[0].token_ids == want[0].token_ids


def test_consensus_fanout_batch_prefills_shared_prompt_once(paged_path):
    """Acceptance shape: 3 rows (shared prompt, distinct suffixes, fresh
    sessions) in ONE batched call — rows 2..K prefill only their suffix
    via the intra-batch wave split."""
    eng = make_engine()
    plain = make_engine()
    plain.prefix_sharing = False
    prompts = [enc(SHARED_SYS + f"user: agent {k} does its own thing")
               for k in range(3)]
    res = eng.generate(prompts, temperature=0.0, max_new_tokens=8,
                       session_ids=["a1", "a2", "a3"])
    assert res[0].n_cached_tokens == 0
    for r in res[1:]:
        assert r.n_cached_tokens >= 128, \
            "fan-out row re-prefilled the shared prompt"
        # suffix-only prefill: everything but the aligned shared prefix
        assert r.n_prompt_tokens - r.n_cached_tokens \
            <= len(prompts[0]) - 128 + 64
    # engine-level prefill counter covers both waves
    total = sum(len(p) for p in prompts)
    assert eng.last_prefill_tokens <= total - 2 * 128
    # outputs match a sharing-disabled engine run with the same wave
    # shapes (row 0 solo, rows 1-2 batched)
    w0 = plain.generate([prompts[0]], temperature=0.0, max_new_tokens=8,
                        session_ids=["w0"])
    w12 = plain.generate([prompts[1], prompts[2]], temperature=0.0,
                         max_new_tokens=8, session_ids=["w1", "w2"])
    assert res[0].token_ids == w0[0].token_ids
    assert res[1].token_ids == w12[0].token_ids
    assert res[2].token_ids == w12[1].token_ids


def test_scheduler_rows_hit_prefix_cache():
    """Continuous-batching rows (models/scheduler.py) go through the same
    cache: a later row adopts the prefix an earlier row prefilled, even
    though the earlier row's scheduler-owned session is already dropped."""
    from quoracle_tpu.models.scheduler import ContinuousBatcher
    eng = make_engine()
    cb = ContinuousBatcher(eng, chunk=8)
    try:
        r1 = cb.submit(enc(SHARED_SYS + "user: first agent"),
                       temperature=0.0, max_new_tokens=8).result(120)
        assert r1.n_gen_tokens >= 1
        r2 = cb.submit(enc(SHARED_SYS + "user: second agent"),
                       temperature=0.0, max_new_tokens=8).result(120)
    finally:
        cb.close()
    assert r2.n_cached_tokens >= 128, \
        "continuous-batching row missed the prefix cache"
    assert len(eng.sessions) == 0          # owned sessions dropped
    assert eng.sessions.prefix_cache.stats()["cached_pages"] >= 1


def test_backend_broadcasts_serving_telemetry():
    """TPUBackend.attach_bus: each query round broadcasts phase timings +
    prefix-cache counters on TOPIC_SERVING (ring-buffered by
    EventHistory for the dashboard's /api/history replay)."""
    from quoracle_tpu.infra.bus import EventBus, TOPIC_SERVING
    from quoracle_tpu.infra.event_history import EventHistory
    from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
    backend = TPUBackend(pool=["xla:tiny"])
    bus = EventBus()
    history = EventHistory(bus)
    backend.attach_bus(bus)
    msgs = [{"role": "system", "content": SHARED_SYS},
            {"role": "user", "content": "round one"}]
    res = backend.query([QueryRequest("xla:tiny", msgs, temperature=0.0,
                                      max_tokens=6, session_id="ag1")])[0]
    assert res.ok
    events = history.replay_serving()
    assert events and events[0]["event"] == "serving_round"
    member = events[0]["members"]["xla:tiny"]
    assert "prefix_cache" in member and "hits" in member["prefix_cache"]
    # a second agent with the shared system prompt shows up as a hit AND
    # as cached_tokens on its QueryResult (consensus layer telemetry)
    res2 = backend.query([QueryRequest(
        "xla:tiny",
        [{"role": "system", "content": SHARED_SYS},
         {"role": "user", "content": "round one, another agent"}],
        temperature=0.0, max_tokens=6, session_id="ag2")])[0]
    assert res2.ok and res2.cached_tokens >= 128
    events = history.replay_serving()
    assert events[-1]["members"]["xla:tiny"]["prefix_cache"]["hits"] >= 1
    history.close()
