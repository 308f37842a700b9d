"""Paged KV cache (VERDICT r2 item 4): sessions are page lists into one
device-resident pool — resume moves no KV bytes through the host, response
KV is retained, pages recycle, and sliding-window models keep a
window-bounded resident footprint (with correct outputs after trimming).
The session behaviours hold on both paged paths (conftest ``paged_path``):
the ragged programs every engine serves with, and the gather programs a
tick falls back to.
"""

import jax
import jax.numpy as jnp

from quoracle_tpu.models.config import ModelConfig, get_model_config, register_model
from quoracle_tpu.models.generate import PAGE, GenerateEngine, _Session
from quoracle_tpu.models.tokenizer import ByteTokenizer
from quoracle_tpu.models.transformer import init_params


def make_engine(name="xla:tiny", **kw):
    cfg = get_model_config(name)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return GenerateEngine(cfg, params, ByteTokenizer(),
                          max_seq=kw.pop("max_seq", 256),
                          prompt_buckets=kw.pop("prompt_buckets", (32, 64, 128)),
                          **kw)


def enc(text):
    return ByteTokenizer().encode(text, add_bos=True)


TINY_WINDOW = register_model(ModelConfig(
    name="tiny-window",
    vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    ffn_dim=128, sliding_window=64, context_window=2048, output_limit=128,
))


def test_sessions_hold_page_ids_not_kv_copies(paged_path):
    """The 'no full-buffer copy' criterion: a stored session is host ints
    (tokens + page ids + offset) — zero device arrays per session; the KV
    lives only in the shared pool, and resume prefills only the suffix."""
    eng = make_engine()
    p1 = enc("user: the conversation so far")
    r1 = eng.generate([p1], temperature=0.0, max_new_tokens=8,
                      session_ids=["a"])[0]
    s = eng.sessions.get("a")
    assert isinstance(s, _Session)
    assert all(isinstance(p, int) for p in s.pages)
    assert not any(isinstance(v, jax.Array) for v in vars(s).values())
    # pool is allocated once, pages cover prompt + response KV
    assert eng.sessions.k is not None
    assert len(s.tokens) == len(p1) + len(r1.token_ids) - 1

    p2 = p1 + r1.token_ids + enc(" more")[1:]
    eng.generate([p2], temperature=0.0, max_new_tokens=8, session_ids=["a"])
    # O(new tokens): only the suffix beyond prompt+response KV prefilled
    assert eng.last_prefill_tokens == len(p2) - (len(p1) + len(r1.token_ids) - 1)


def test_pages_recycle_on_drop_and_divergence(paged_path):
    eng = make_engine()
    free0 = None
    for round_trip in range(3):
        p = enc(f"user: conversation number {round_trip} with some length")
        eng.generate([p], temperature=0.0, max_new_tokens=8,
                     session_ids=["s"])
        eng.sessions.drop("s")
        free = eng.sessions.free_pages()
        if free0 is None:
            free0 = free
        # dropping returns every page — no leak across rounds
        assert free == free0


def test_eviction_recycles_lru_session_pages(paged_path):
    # small pool: 4 usable pages
    eng = make_engine(session_max_bytes=1)  # floor → PAGE tokens minimum
    eng.sessions.__init__(max_tokens=4 * PAGE)
    p = enc("x" * 200)
    eng.generate([p], temperature=0.0, max_new_tokens=4, session_ids=["a"])
    eng.generate([p], temperature=0.0, max_new_tokens=4, session_ids=["b"])
    eng.generate([p], temperature=0.0, max_new_tokens=4, session_ids=["c"])
    # pool holds at most 4 pages of sessions; the oldest evicted
    live = [k for k in ("a", "b", "c") if eng.sessions.get(k) is not None]
    assert "c" in live and len(live) <= 4
    # DISTINCT pages: identical prompts share prefix pages across
    # sessions (cross-session prefix sharing), so physical occupancy —
    # the pool invariant this test guards — is the set, not the sum
    total_pages = len({p for k in live
                       for p in eng.sessions.get(k).pages})
    assert total_pages <= 4


def test_sliding_window_bounds_resident_footprint(paged_path):
    """Mistral-style model: the session's resident KV stays within
    window + one page regardless of conversation length (VERDICT done
    criterion: 'Mistral's KV footprint is window-bounded')."""
    cfg = get_model_config("xla:tiny-window")
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    eng = GenerateEngine(cfg, params, ByteTokenizer(), max_seq=1024,
                         prompt_buckets=(64, 128, 256, 512))
    W = cfg.sliding_window
    prompt = enc("u: " + "long conversation " * 20)     # ~360 tokens
    for rnd in range(3):
        r = eng.generate([prompt], temperature=0.0, max_new_tokens=8,
                         session_ids=["w"])[0]
        prompt = prompt + r.token_ids + enc(f" turn {rnd}")[1:]
    s = eng.sessions.get("w")
    assert s.start_pos > 0                      # leading pages were dropped
    assert s.resident_len <= W + 2 * eng.sessions.page
    assert len(s.pages) * eng.sessions.page >= W   # window stays covered


def test_sliding_window_resume_matches_fresh(paged_path):
    """Trimmed-session resume (nonzero kv position offset) must produce
    exactly the tokens a fresh full prefill produces."""
    cfg = get_model_config("xla:tiny-window")
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    cached = GenerateEngine(cfg, params, ByteTokenizer(), max_seq=1024,
                            prompt_buckets=(64, 128, 256, 512))
    fresh = GenerateEngine(cfg, params, ByteTokenizer(), max_seq=1024,
                           prompt_buckets=(64, 128, 256, 512))
    p = enc("u: " + "window test " * 30)                # ~360 tokens > W
    r1 = cached.generate([p], temperature=0.0, max_new_tokens=8,
                         session_ids=["w"])[0]
    assert cached.sessions.get("w").start_pos > 0
    p2 = p + r1.token_ids + enc(" continue")[1:]
    want = fresh.generate([p2], temperature=0.0, max_new_tokens=8)[0]
    got = cached.generate([p2], temperature=0.0, max_new_tokens=8,
                          session_ids=["w"])[0]
    assert got.token_ids == want.token_ids
    assert got.n_cached_tokens > 0


def test_windowed_divergence_discards_reuse(paged_path):
    """A divergent prompt on a windowed model cannot reuse the trimmed
    window (hole below the new tokens' attention span) — must fall back to
    full prefill with matching output."""
    cfg = get_model_config("xla:tiny-window")
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    cached = GenerateEngine(cfg, params, ByteTokenizer(), max_seq=1024,
                            prompt_buckets=(64, 128, 256, 512))
    fresh = GenerateEngine(cfg, params, ByteTokenizer(), max_seq=1024,
                           prompt_buckets=(64, 128, 256, 512))
    p = enc("u: " + "divergence base " * 30)
    cached.generate([p], temperature=0.0, max_new_tokens=8,
                    session_ids=["w"])
    p2 = p[: len(p) // 2] + enc("completely different tail " * 10)[1:]
    want = fresh.generate([p2], temperature=0.0, max_new_tokens=8)[0]
    got = cached.generate([p2], temperature=0.0, max_new_tokens=8,
                          session_ids=["w"])[0]
    assert got.token_ids == want.token_ids
    assert got.n_cached_tokens == 0             # no partial reuse


def test_duplicate_session_id_in_batch_stores_once(paged_path):
    eng = make_engine()
    pa, pb = enc("row one"), enc("row two, different")
    res = eng.generate([pa, pb], temperature=0.0, max_new_tokens=4,
                       session_ids=["dup", "dup"])
    assert len(res) == 2
    s = eng.sessions.get("dup")
    # first occurrence owns the session
    assert s.tokens[:len(pa)] == list(pa)


def test_pool_exhaustion_serves_without_storing():
    eng = make_engine(max_seq=1024, prompt_buckets=(64, 128, 256, 512))
    eng.sessions.__init__(max_tokens=PAGE)      # floor: 2 usable pages
    p = enc("x" * 400)                          # needs 3+ pages
    r = eng.generate([p], temperature=0.0, max_new_tokens=4,
                     session_ids=["big"])[0]
    assert r.n_gen_tokens > 0                   # served fine
    assert eng.sessions.get("big") is None      # just not stored


