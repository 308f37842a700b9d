"""KV residency: session prefix reuse must be token-identical to fresh
prefill, must actually skip recomputing the shared prefix, and must survive
divergence (condensation) and eviction. VERDICT r1 item 4.
"""

import jax
import jax.numpy as jnp
import numpy as np

from quoracle_tpu.models.config import get_model_config
from quoracle_tpu.models.generate import GenerateEngine, SessionStore, _Session, _lcp
from quoracle_tpu.models.tokenizer import ByteTokenizer
from quoracle_tpu.models.transformer import init_params


def make_engine(**kw):
    cfg = get_model_config("xla:tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return GenerateEngine(cfg, params, ByteTokenizer(), max_seq=256,
                          prompt_buckets=(32, 64, 128), **kw)


def enc(text):
    return ByteTokenizer().encode(text, add_bos=True)


def test_lcp():
    assert _lcp([1, 2, 3], [1, 2, 4]) == 2
    assert _lcp([], [1]) == 0
    assert _lcp([1, 2], [1, 2]) == 2


def test_session_reuse_matches_fresh_greedy(paged_path):
    """Round 2 extends round 1's prompt (refinement shape). With session
    reuse the suffix-prefill path must produce identical greedy tokens."""
    fresh = make_engine()
    cached = make_engine()

    p1 = enc("system: you are an agent\nuser: decide an action")
    r1_fresh = fresh.generate([p1], temperature=0.0, max_new_tokens=12)
    r1_cached = cached.generate([p1], temperature=0.0, max_new_tokens=12,
                                session_ids=["agent-1"])
    assert r1_fresh[0].token_ids == r1_cached[0].token_ids
    assert r1_cached[0].n_cached_tokens == 0       # first round: no prefix

    # round 2: previous prompt + the response + a refinement message
    p2 = p1 + r1_fresh[0].token_ids + enc("\nuser: reviewers disagree, refine")[1:]
    r2_fresh = fresh.generate([p2], temperature=0.0, max_new_tokens=12)
    r2_cached = cached.generate([p2], temperature=0.0, max_new_tokens=12,
                                session_ids=["agent-1"])
    assert r2_fresh[0].token_ids == r2_cached[0].token_ids
    # the whole round-1 prompt AND its response KV are reused (every
    # emitted token except the last sampled one, whose KV never ran
    # forward) — VERDICT r2 weak #5: response KV must not be re-prefilled
    n_resp_kv = len(r1_fresh[0].token_ids) - 1
    assert r2_cached[0].n_cached_tokens == len(p1) + n_resp_kv
    # and only the genuinely-new suffix was prefilled
    assert cached.last_prefill_tokens == len(p2) - len(p1) - n_resp_kv


def test_session_divergence_partial_reuse(paged_path):
    """Condensation rewrites history mid-way: only the still-matching
    prefix (system prompt) is reused; output equals fresh."""
    fresh = make_engine()
    cached = make_engine()
    sys_part = enc("system: stable system prompt here")
    p1 = sys_part + enc("user: original long history")[1:]
    cached.generate([p1], temperature=0.0, max_new_tokens=8,
                    session_ids=["a"])
    p2 = sys_part + enc("user: condensed summary instead")[1:]
    r_f = fresh.generate([p2], temperature=0.0, max_new_tokens=8)
    r_c = cached.generate([p2], temperature=0.0, max_new_tokens=8,
                          session_ids=["a"])
    assert r_f[0].token_ids == r_c[0].token_ids
    assert 0 < r_c[0].n_cached_tokens == _lcp(p1, p2)  # only the shared prefix


def test_identical_reprompt_still_generates():
    """lcp == full prompt: at least one token must re-run to produce
    logits; output equals fresh."""
    cached = make_engine()
    p = enc("user: same prompt twice")
    a = cached.generate([p], temperature=0.0, max_new_tokens=8,
                        session_ids=["x"])
    b = cached.generate([p], temperature=0.0, max_new_tokens=8,
                        session_ids=["x"])
    assert a[0].token_ids == b[0].token_ids
    assert b[0].n_cached_tokens == len(p) - 1


def test_mixed_batch_sessions_and_fresh_rows(paged_path):
    eng = make_engine()
    pa = enc("user: row a")
    pb = enc("user: row b, no session")
    eng.generate([pa], temperature=0.0, max_new_tokens=6, session_ids=["a"])
    pa2 = pa + enc(" more")[1:]
    fresh = make_engine()
    want = [r.token_ids for r in
            fresh.generate([pa2, pb], temperature=0.0, max_new_tokens=6)]
    got = [r.token_ids for r in
           eng.generate([pa2, pb], temperature=0.0, max_new_tokens=6,
                        session_ids=["a", None])]
    assert got == want


def test_session_store_lru_page_eviction():
    """Pool of 2 usable pages (+scratch): allocating for a second session
    evicts the LRU one and recycles its pages."""
    store = SessionStore(max_tokens=2 * store_page(), page=store_page())
    pa = store.alloc(2)
    assert sorted(pa) == [1, 2]
    store.put("a", _Session(tokens=[1] * 6, pages=pa))
    pb = store.alloc(2, protect=("b",))      # must evict "a"
    assert sorted(pb) == [1, 2]
    store.put("b", _Session(tokens=[2] * 6, pages=pb))
    assert store.get("a") is None and store.get("b") is not None
    # protected sessions never evict: a second alloc cannot be satisfied
    assert store.alloc(2, protect=("b",)) is None
    # drop returns the pages
    store.drop("b")
    assert store.free_pages() == 2


def store_page():
    return 4


def test_session_reuse_on_tp_mesh(eight_devices):
    from quoracle_tpu.parallel.mesh import make_mesh
    cfg = get_model_config("xla:tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    mesh = make_mesh(2, tp=2, devices=eight_devices[:2])
    eng = GenerateEngine(cfg, params, ByteTokenizer(), max_seq=256,
                         prompt_buckets=(32, 64), mesh=mesh)
    fresh = make_engine()
    p1 = enc("user: sharded sessions")
    eng.generate([p1], temperature=0.0, max_new_tokens=6, session_ids=["s"])
    p2 = p1 + enc(" extended")[1:]
    want = [r.token_ids for r in
            fresh.generate([p2], temperature=0.0, max_new_tokens=6)]
    got = [r.token_ids for r in
           eng.generate([p2], temperature=0.0, max_new_tokens=6,
                        session_ids=["s"])]
    assert got == want


def test_backend_threads_sessions_through(monkeypatch):
    """TPUBackend passes QueryRequest.session_id into the engine; a second
    identical-prefix round reuses the cache."""
    from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
    backend = TPUBackend(pool=["xla:tiny"])
    msgs = [{"role": "system", "content": "sys"},
            {"role": "user", "content": "round one"}]
    backend.query([QueryRequest("xla:tiny", msgs, temperature=0.0,
                                max_tokens=6, session_id="ag1")])
    eng = backend.engines["xla:tiny"]
    assert len(eng.sessions) == 1
    msgs2 = msgs + [{"role": "assistant", "content": "resp"},
                    {"role": "user", "content": "round two"}]
    res = backend.query([QueryRequest("xla:tiny", msgs2, temperature=0.0,
                                      max_tokens=6, session_id="ag1")])[0]
    assert res.ok
    # round 2 prefilled strictly fewer tokens than the full prompt
    full = len(eng.tokenizer.encode_chat(msgs2))
    assert eng.last_prefill_tokens < full
    backend.close()


def test_mixed_batch_long_fresh_row_does_not_corrupt_resumed_row(paged_path):
    """Review r2 repro: a resumed row (large prefix, short suffix) batched
    with a LONG fresh row once made cache_len < prefix + T_padded;
    dynamic_update_slice clamps, scribbling the pad chunk over valid prefix
    KV. cache_len must cover max(prefix) + T."""
    eng = make_engine()
    fresh = make_engine()
    # session with a long prompt (prefix ~120)
    pa = enc("x" * 118)
    eng.generate([pa], temperature=0.0, max_new_tokens=4, session_ids=["a"])
    pa2 = pa + enc("!!")[1:]                    # short suffix
    pb = enc("y" * 126)                         # long fresh row: T pads to 128
    want = [r.token_ids for r in
            fresh.generate([pa2, pb], temperature=0.0, max_new_tokens=6)]
    got = [r.token_ids for r in
           eng.generate([pa2, pb], temperature=0.0, max_new_tokens=6,
                        session_ids=["a", None])]
    assert got == want


def test_session_budget_derived_from_bytes():
    """The store bound is bytes-denominated: a big-KV config gets far fewer
    resident tokens than a small one for the same byte budget."""
    cfg = get_model_config("xla:tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    small = GenerateEngine(cfg, params, ByteTokenizer(), max_seq=256,
                           prompt_buckets=(32,), session_max_bytes=1 << 20)
    # tiny: 2 layers x 2 kv x 32 hd x 4B x 2 = 1 KiB/token -> ~1024 tokens
    assert 512 <= small.sessions.max_tokens <= 2048


def test_splice_recovers_response_ids():
    """Refinement re-encodes the assistant text, so the plain token LCP dies
    at the previous prompt's end when gen ids don't re-encode identically
    (out-of-tokenizer-range ids here; BPE boundary merges in general). The
    splice keeps the session's ACTUAL ids for the shared text and re-encodes
    only the new suffix."""
    from quoracle_tpu.models.generate import splice_session_prompt
    tok = ByteTokenizer()
    render1 = "<|user|>\nhi\n<|assistant|>\n"
    p1 = tok.encode(render1, add_bos=True)
    gen = [ord("H") + 3, 300, ord("i") + 3]     # "Hi" + out-of-range id
    sess = p1 + gen
    raw = tok.decode(gen)
    assert raw == "Hi"
    p2 = tok.encode(render1 + raw + "\n<|user|>\nrefine\n<|assistant|>\n",
                    add_bos=True)
    assert _lcp(sess, p2) < len(sess)           # plain ids miss the response
    spliced = splice_session_prompt(tok, sess, p2)
    assert spliced is not None
    assert spliced[:len(sess)] == sess          # full session reuse
    assert tok.decode_raw(spliced) == tok.decode_raw(p2)  # same text


def test_splice_no_gain_returns_none():
    """Divergence at the TEXT level (condensation rewrote history): the
    shared text prefix equals the plain token LCP on a reversible
    tokenizer, so splicing buys nothing and must return None."""
    from quoracle_tpu.models.generate import splice_session_prompt
    tok = ByteTokenizer()
    sys_part = "<|system|>\nstable\n<|user|>\n"
    sess = tok.encode(sys_part + "old history\n", add_bos=True) + [300]
    p2 = tok.encode(sys_part + "condensed summary\n<|assistant|>\n",
                    add_bos=True)
    assert splice_session_prompt(tok, sess, p2) is None


def test_splice_identical_conversation_keeps_one_suffix_token():
    """canonical == session text: the splice must back off so >= 1 suffix
    token still runs through prefill (last-position logits)."""
    from quoracle_tpu.models.generate import splice_session_prompt
    tok = ByteTokenizer()
    p1 = tok.encode("<|user|>\nsame\n<|assistant|>\n", add_bos=True)
    sess = list(p1)
    spliced = splice_session_prompt(tok, sess, list(p1))
    # plain ids already match everywhere -> nothing to gain
    assert spliced is None


def test_splice_recovers_past_mid_utf8_pocket():
    """The prefix predicate is non-monotone when a token boundary cuts a
    multi-byte char: decode(sess[:k]) ends in U+FFFD and fails while k+1
    decodes cleanly. The bisection can settle BELOW such a pocket — the
    bounded lookahead must probe past the failing k and recover the true
    maximal shared region (ADVICE r3; splice_session_prompt)."""
    from quoracle_tpu.models.generate import splice_session_prompt

    class PocketTok:
        # id -> utf-8 bytes; 2+3 are the two halves of "é", 5 is "é" whole
        TOK = {0: b"a", 1: b"b", 2: b"\xc3", 3: b"\xa9", 4: b"Z",
               5: b"\xc3\xa9", 6: b"c"}
        CANON = {"a": 0, "b": 1, "Z": 4, "c": 6, "é": 5}

        def decode_raw(self, ids):
            return b"".join(self.TOK[i] for i in ids).decode(
                "utf-8", "replace")

        def encode(self, text, add_bos=False):
            return [self.CANON[ch] for ch in text]

    tok = PocketTok()
    # session decodes "abéZ" with é SPLIT across ids 2,3; the new canonical
    # prompt is "abéc" (é one token). Predicate by k: T T F T F — bisection
    # probes k=3 (the U+FFFD pocket), discards the upper true region, and
    # settles at k=2; lookahead must land on k=4.
    sess = [0, 1, 2, 3, 4]
    plain = tok.encode("abéc")
    spliced = splice_session_prompt(tok, sess, plain)
    assert spliced == [0, 1, 2, 3, 6]   # keeps BOTH halves of é from sess
    assert tok.decode_raw(spliced) == "abéc"


def test_splice_recovers_chained_pockets():
    """Pockets CHAIN when byte-fallback tokens straddle char boundaries:
    two adjacent 4-byte emoji split as [f0][9f][98][80 f0][9f][98][80] give
    predicate T F F F F F F T — wider than any per-char bound. The scan
    must keep probing while the mismatch is only the trailing U+FFFD run,
    and still stop at genuine divergence."""
    from quoracle_tpu.models.generate import splice_session_prompt

    class StraddleTok:
        TOK = {0: b"\xf0", 1: b"\x9f", 2: b"\x98", 3: b"\x80\xf0",
               4: b"\x9f", 5: b"\x98", 6: b"\x80", 7: b"Z",
               8: "😀".encode(), 9: b"c"}

        def decode_raw(self, ids):
            return b"".join(self.TOK[i] for i in ids).decode(
                "utf-8", "replace")

        def encode(self, text, add_bos=False):
            return [{"😀": 8, "c": 9, "Z": 7}[ch] for ch in text]

    tok = StraddleTok()
    sess = [0, 1, 2, 3, 4, 5, 6, 7]          # "😀😀" byte-split, then "Z"
    plain = tok.encode("😀😀c")               # canonical: whole-emoji ids
    spliced = splice_session_prompt(tok, sess, plain)
    assert spliced == [0, 1, 2, 3, 4, 5, 6, 9]  # full 7-token KV reuse + "c"
    assert tok.decode_raw(spliced) == "😀😀c"


def test_backend_splices_response_kv(monkeypatch):
    """Consensus-shaped round 2 (history + assistant raw text + refinement
    message) through TPUBackend: prefill must run only the new template
    glue + refinement message — the response KV resumes from the session
    even though re-encoding the response text yields different ids."""
    from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
    backend = TPUBackend(pool=["xla:tiny"])
    eng = backend.engines["xla:tiny"]
    msgs = [{"role": "user", "content": "round one"}]
    r1 = backend.query([QueryRequest("xla:tiny", msgs, temperature=1.0,
                                     max_tokens=24, session_id="ag")])[0]
    assert r1.ok and r1.text
    sess_len = len(eng.session_tokens("ag"))
    msgs2 = msgs + [{"role": "assistant", "content": r1.text},
                    {"role": "user", "content": "refine"}]
    r2 = backend.query([QueryRequest("xla:tiny", msgs2, temperature=0.0,
                                     max_tokens=6, session_id="ag")])[0]
    assert r2.ok
    # new text = (up to one length-capped trailing token's chars) +
    # "\n" + "<|user|>\nrefine\n<|assistant|>\n"
    glue = len(eng.tokenizer.encode("\n<|user|>\nrefine\n<|assistant|>\n"))
    assert eng.last_prefill_tokens <= glue + 8
    # and the resident session grew on top of the old one, not from scratch
    assert len(eng.session_tokens("ag")) > sess_len
    backend.close()


def test_drop_session_frees_engine_state():
    from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
    backend = TPUBackend(pool=["xla:tiny"])
    msgs = [{"role": "user", "content": "hello"}]
    backend.query([QueryRequest("xla:tiny", msgs, temperature=0.0,
                                max_tokens=4, session_id="gone")])
    assert len(backend.engines["xla:tiny"].sessions) == 1
    backend.drop_session("gone")
    assert len(backend.engines["xla:tiny"].sessions) == 0
    backend.close()


# ---------------------------------------------------------------------------
# Cross-session prefix sharing (SURVEY §7 hard part 2: system-prompt cache)
# ---------------------------------------------------------------------------

SHARED_SYS = "system: " + "policy rules apply here. " * 7   # > 1 page


def test_cross_session_prefix_sharing_token_exact(paged_path):
    """A NEW session whose prompt starts with another session's
    page-aligned prefix adopts those pages: the first prefill skips the
    shared system prompt, and greedy output is identical to a
    sharing-disabled engine."""
    eng = make_engine()
    plain = make_engine()
    plain.prefix_sharing = False
    pa = enc(SHARED_SYS + "user: task alpha")
    pb = enc(SHARED_SYS + "user: task beta")
    ra = eng.generate([pa], temperature=0.0, max_new_tokens=10,
                      session_ids=["a"])
    assert ra[0].n_cached_tokens == 0           # first agent: no donor
    rb = eng.generate([pb], temperature=0.0, max_new_tokens=10,
                      session_ids=["b"])
    assert rb[0].n_cached_tokens >= 128, \
        "adoption did not reuse the page-aligned shared prefix"
    want = plain.generate([pb], temperature=0.0, max_new_tokens=10,
                          session_ids=["b2"])
    assert rb[0].token_ids == want[0].token_ids, \
        "prefix-shared decode diverged from the sharing-disabled engine"


def test_prefix_sharing_survives_donor_drop_and_frees_pages(paged_path):
    """Refcounts: dropping the DONOR must not free pages an adopter still
    reads; after dropping everyone the only pages still out are the radix
    prefix cache's (by design — cached prefixes outlive their sessions),
    and clearing the cache returns the pool to baseline exactly."""
    eng = make_engine()
    plain = make_engine()
    plain.prefix_sharing = False
    baseline = eng.sessions.free_pages()
    pa = enc(SHARED_SYS + "user: task alpha")
    pb = enc(SHARED_SYS + "user: task beta")
    eng.generate([pa], temperature=0.0, max_new_tokens=8,
                 session_ids=["a"])
    rb = eng.generate([pb], temperature=0.0, max_new_tokens=8,
                      session_ids=["b"])
    assert rb[0].n_cached_tokens >= 128
    eng.drop_session("a")                        # donor gone, pages shared
    # the adopter continues its conversation on the adopted prefix
    pb2 = pb + rb[0].token_ids + enc(" more")[1:]
    rb2 = eng.generate([pb2], temperature=0.0, max_new_tokens=8,
                       session_ids=["b"])
    want = plain.generate([pb], temperature=0.0, max_new_tokens=8,
                          session_ids=["w"])
    pw2 = pb + want[0].token_ids + enc(" more")[1:]
    want2 = plain.generate([pw2], temperature=0.0, max_new_tokens=8,
                           session_ids=["w"])
    assert rb2[0].token_ids == want2[0].token_ids
    eng.drop_session("b")
    st = eng.sessions
    cached = st.prefix_cache.stats()["cached_pages"]
    assert cached >= 1, "prefix cache retained nothing"
    assert st.free_pages() == baseline - cached, \
        "shared pages leaked or double-freed"
    with st.lock:
        st.prefix_cache.clear()
    assert st.free_pages() == baseline, \
        "prefix-cache clear did not return the pool to baseline"


def test_prefix_sharing_donor_divergence_does_not_corrupt_adopter(paged_path):
    """A donor whose conversation diverges (condensation) rewrites its
    dst pages — shared pages beyond the identical-prefix region must be
    swapped for fresh ones so the adopter's KV stays intact."""
    eng = make_engine()
    plain = make_engine()
    plain.prefix_sharing = False
    pa = enc(SHARED_SYS + "user: task alpha")
    pb = enc(SHARED_SYS + "user: task beta")
    eng.generate([pa], temperature=0.0, max_new_tokens=8,
                 session_ids=["a"])
    rb = eng.generate([pb], temperature=0.0, max_new_tokens=8,
                      session_ids=["b"])
    assert rb[0].n_cached_tokens >= 128
    # donor DIVERGES: same session id, totally different prompt (its old
    # pages become dst for different content)
    eng.generate([enc("user: condensed fresh start after reflection")],
                 temperature=0.0, max_new_tokens=8, session_ids=["a"])
    # the adopter's next round must still read CORRECT prefix KV
    pb2 = pb + rb[0].token_ids + enc(" go on")[1:]
    rb2 = eng.generate([pb2], temperature=0.0, max_new_tokens=8,
                       session_ids=["b"])
    want = plain.generate([pb], temperature=0.0, max_new_tokens=8,
                          session_ids=["w"])
    pw2 = pb + want[0].token_ids + enc(" go on")[1:]
    want2 = plain.generate([pw2], temperature=0.0, max_new_tokens=8,
                           session_ids=["w"])
    assert rb2[0].token_ids == want2[0].token_ids, \
        "donor divergence corrupted the adopter's shared prefix"


