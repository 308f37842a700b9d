"""Laguna's toy (tests/test_window_moe.py) THROUGH THE ENGINE: the page
allocator in which the window layers let go of the pages behind the window.
Prefill then decode through the pages against the reference's full forward —
across page boundaries, past the window, after pages were released, after a
resume, after a divergence the window no longer covers, after adopting a
cached prefix at a boundary the window straddles, under eviction — and the
allocator's properties: a session's holding in the window group is bounded
by the window whatever its length, page counts balance to zero after every
drop, the radix cache's pages outlive the session that made them and go
when the window group needs them. A file of its own so that the two halves
run on two workers (`--dist loadfile`)."""

import jax.numpy as jnp
import numpy as np
import pytest

from quoracle_tpu.infra.telemetry import (
    KV_GROUP_PAGES_TOTAL, KV_SESSION_HELD_TOKENS_TOTAL, tick_close,
    tick_open,
)
from quoracle_tpu.models import transformer as tr
from quoracle_tpu.models.generate import GenerateEngine
from quoracle_tpu.models.tokenizer import ByteTokenizer
from tests.test_window_moe import (   # noqa: F401  (toy: a fixture)
    PAGE, RAW, TOL, WINDOW, f32, reference_logits, tokens_of, toy,
)


def new_engine(toy, **kw):
    cfg, params, _ = toy
    return GenerateEngine(cfg, f32(params), ByteTokenizer(), max_seq=2048,
                          prompt_buckets=(32, 64, 128, 256, 512, 1024), **kw)


@pytest.fixture()
def engine(toy):
    return new_engine(toy)


def served(eng, ref, prompt, sid, n=6):
    """Serve `prompt` greedily under session `sid`; returns (the result,
    the larger of: how far the chunk forward's logits at the prompt's end
    lie from the reference's, and the widest gap by which a token the
    decode loop then served lies below the reference's best at its
    position)."""
    chunk = eng._step_paged_ragged
    seen = []

    def spy(*a, **kw):
        out = chunk(*a, **kw)
        seen.append(np.asarray(out[0][0]))
        return out

    eng._step_paged_ragged = spy
    try:
        res = eng.generate([list(prompt)], temperature=0.0,
                           max_new_tokens=n, session_ids=[sid])[0]
    finally:
        eng._step_paged_ragged = chunk
    ids = list(prompt) + res.token_ids
    rows = np.arange(len(prompt) - 1, len(ids) - 1)
    want = reference_logits(ref, ids, rows)
    gaps = want.max(-1) - want[np.arange(len(rows)), res.token_ids]
    return res, max(float(gaps.max()), float(np.abs(seen[0] - want[0]).max()))


def ids_of(seed, n):
    return [int(t) for t in tokens_of(seed, n)]


def held(eng, sid):
    """(pages of the full group, pages of the window group) a session
    holds."""
    s = eng.sessions.get(sid)
    return len([p for p in s.pages if p]), len([p for p in s.wpages if p])


def balance(eng):
    """Pages out of each group's free list."""
    st = eng.sessions
    return (st.n_pages - 1 - st.free_pages(),
            st.window.n_pages - 1 - len(st.window._free))


# -- sessions: release, resume, adoption --------------------------------------

def test_a_session_lets_go_behind_the_window_and_resumes(engine, toy):
    cfg, _, ref = toy
    a = ids_of(31, 300)
    r1, gap = served(engine, ref, a, "a")
    assert gap < TOL and r1.n_cached_tokens == 0
    s = engine.sessions.get("a")
    # 305 tokens with KV: positions 146.. are in reach of the next query,
    # so page 0 went and pages 1, 2 stay; the full group keeps all three
    assert [bool(p) for p in s.wpages] == [False, True, True]
    assert all(s.pages) and len(s.pages) == 3
    b = a + r1.token_ids + ids_of(32, 150)
    r2, gap = served(engine, ref, b, "a")
    assert gap < TOL and r2.n_cached_tokens == len(a) + len(r1.token_ids) - 1
    assert held(engine, "a") == (4, 2)
    # a long tool result: more new tokens than the window in one tick
    c = b + r2.token_ids + ids_of(33, 400)
    r3, gap = served(engine, ref, c, "a")
    assert gap < TOL and held(engine, "a") == (7, 2)


@pytest.mark.parametrize("n", [100, 333, 700, 1500])
def test_the_window_groups_holding_is_bounded_by_the_window(engine, n):
    """Whatever a session's length, what it holds in the window group is at
    most the window and a page, rounded up to pages; the full group holds
    it all."""
    engine.generate([ids_of(n, n)], temperature=0.0, max_new_tokens=4,
                    session_ids=["s"])
    full, window = held(engine, "s")
    assert full == -(-(n + 3) // PAGE)
    assert window <= -(-(WINDOW + PAGE) // PAGE) and window >= min(full, 2)
    engine.drop_session("s")
    # the radix cache keeps its own references in both groups
    cached = engine.sessions.prefix_cache.stats()
    assert cached["cached_pages"] == (n + 3) // PAGE
    assert balance(engine) == (cached["cached_pages"],
                               cached["cached_window_pages"])


def test_page_counts_balance_to_zero_after_every_drop(engine, toy):
    ref = toy[2]
    engine.prefix_sharing = False
    assert balance(engine) == (0, 0)
    for turn, sid in enumerate(["a", "b", "a", "c", "b", "a"]):
        have = engine.session_tokens(sid) or []
        engine.generate([have + ids_of(40 + turn, 90 + 70 * turn)],
                        temperature=0.0, max_new_tokens=5, session_ids=[sid])
    full, window = balance(engine)
    assert full == sum(held(engine, s)[0] for s in "abc")
    assert window == sum(held(engine, s)[1] for s in "abc") < full
    for sid in "abc":
        engine.drop_session(sid)
        assert balance(engine) == (
            sum(held(engine, s)[0] for s in "abc" if engine.sessions.get(s)),
            sum(held(engine, s)[1] for s in "abc" if engine.sessions.get(s)))
    assert balance(engine) == (0, 0)
    assert not engine.sessions._refs and not engine.sessions.window._refs


@pytest.mark.parametrize("boundary", [2, 3])
def test_adopting_a_cached_prefix_at_a_boundary_the_window_straddles(
        engine, toy, boundary):
    """A new session shares `boundary` whole pages with a cached prompt:
    it adopts the full group's pages whole and of the window group's the
    last two (a window of 160 reaches across a page boundary), and serves
    the reference's logits behind them."""
    cfg, _, ref = toy
    prompt = ids_of(51, 450)
    served(engine, ref, prompt, "donor")
    engine.drop_session("donor")          # the cache's pages survive it
    before = KV_GROUP_PAGES_TOTAL.value(model=cfg.name, group="window",
                                        event="adopted")
    mine = prompt[:boundary * PAGE] + ids_of(52, 60)
    res, gap = served(engine, ref, mine, "new")
    assert gap < TOL and res.n_cached_tokens == boundary * PAGE
    s = engine.sessions.get("new")
    assert [bool(p) for p in s.wpages] == [False] * (boundary - 1) + [True] * 2
    assert KV_GROUP_PAGES_TOTAL.value(
        model=cfg.name, group="window", event="adopted") - before == 2
    # ... and continues from there
    more = mine + res.token_ids + ids_of(53, 200)
    assert served(engine, ref, more, "new")[1] < TOL


def test_a_divergence_the_window_no_longer_covers_starts_over(engine, toy):
    """The session diverges 300 tokens back: the window layers' rows there
    were let go, so it is forgotten and the row starts over — from the
    radix cache, which still holds the prompt's pages in both groups."""
    cfg, _, ref = toy
    a = ids_of(61, 600)
    r1, _ = served(engine, ref, a, "a")
    assert held(engine, "a") == (5, 2)
    b = a[:300] + ids_of(62, 40)
    r2, gap = served(engine, ref, b, "a")
    assert gap < TOL and r2.n_cached_tokens == 256
    # a divergence inside the window resumes, from a page boundary
    c = b + r2.token_ids
    c = c[:len(c) - 3] + ids_of(63, 20)
    r3, gap = served(engine, ref, c, "a")
    assert gap < TOL and r3.n_cached_tokens == 256
    engine.drop_session("a")
    engine.sessions.prefix_cache.clear()
    assert balance(engine) == (0, 0)


def test_the_cache_gives_window_pages_back_under_pressure(toy):
    """A window group of 10 pages: sessions come and go, the radix cache
    keeps their blocks and, when the window group runs dry, lets go of
    window pages nobody reads, least recently matched first; a prefix whose
    last window was stripped is adoptable only up to where it still is; a
    live session is evicted last, and everything served is the reference's."""
    cfg, _, ref = toy
    eng = new_engine(toy, session_max_bytes=2 * 10 * PAGE * 6 * 2 * 32 * 4)
    st = eng.sessions
    assert st.window.n_pages == 11 and st.n_pages > st.window.n_pages
    hot = ids_of(71, 3 * PAGE)
    for i in range(6):
        res, gap = served(eng, ref, hot + ids_of(72 + i, 300), f"s{i}")
        assert gap < TOL
        assert res.n_cached_tokens == (3 * PAGE if i else 0)
        eng.drop_session(f"s{i}")
    stats = st.prefix_cache.stats()
    assert stats["stripped_window_pages"] > 0 and stats["evicted_pages"] == 0
    assert stats["cached_pages"] > stats["cached_window_pages"]
    assert len(st.window._free) + stats["cached_window_pages"] == 10
    # the hot prompt's last window is still cached: touched at every match
    assert st.prefix_cache.match_len(hot + [5], 3 * PAGE) == 3 * PAGE
    # two live sessions fill the group; a third evicts the older one
    for i, n in enumerate((900, 900, 900)):
        assert served(eng, ref, ids_of(80 + i, n), f"live{i}")[1] < TOL
    assert st.get("live0") is None and st.get("live2") is not None
    for sid in ("live1", "live2"):
        eng.drop_session(sid)
    st.prefix_cache.clear()
    assert balance(eng) == (0, 0)


def test_a_batch_of_new_sessions_shares_one_prefill(engine, toy):
    """The consensus fan-out: rows of one batch with one prompt prefill it
    once (two waves), the later rows adopting both groups' pages."""
    ref = toy[2]
    prompt = ids_of(91, 400)
    rows = [prompt + ids_of(92 + i, 20) for i in range(3)]
    out = engine.generate(rows, temperature=0.0, max_new_tokens=4,
                          session_ids=["x", "y", "z"])
    assert [r.n_cached_tokens for r in out] == [0, 384, 384]
    for row, r in zip(rows, out):
        ids = row + r.token_ids
        want = reference_logits(ref, ids, np.arange(len(row) - 1,
                                                    len(ids) - 1))
        assert (want.max(-1) - want[np.arange(4), r.token_ids]).max() < TOL


def test_rows_without_a_session_take_pages_for_the_tick_only(engine, toy):
    ref = toy[2]
    prompt = ids_of(95, 333)
    r = engine.generate([prompt], temperature=0.0, max_new_tokens=4)[0]
    ids = prompt + r.token_ids
    want = reference_logits(ref, ids, np.arange(332, 336))
    assert (want.max(-1) - want[np.arange(4), r.token_ids]).max() < TOL
    assert balance(engine) == (0, 0)


# -- the instruments ----------------------------------------------------------

def test_a_tick_books_both_groups(engine, toy):
    cfg = toy[0]
    count = lambda group, event: KV_GROUP_PAGES_TOTAL.value(   # noqa: E731
        model=cfg.name, group=group, event=event)
    tokens = lambda group: KV_SESSION_HELD_TOKENS_TOTAL.value(  # noqa: E731
        model=cfg.name, group=group)
    before = (count("window", "allocated"),
              count("window", "released_behind_window"),
              tokens("full"), tokens("window"))
    rec = tick_open(cfg.name)
    try:
        engine.generate([ids_of(97, 600)], temperature=0.0,
                        max_new_tokens=8, session_ids=["t"])
    finally:
        tick_close()
    assert count("window", "allocated") - before[0] == 5
    assert count("window", "released_behind_window") - before[1] == 3
    assert tokens("full") - before[2] == 607
    assert tokens("window") - before[3] == 2 * PAGE
    args = rec.args
    assert args["window_pages_released"] == 3
    for name in ("attn_kv_reads", "attn_pairs", "attn_kv_streamed",
                 "attn_walk_steps"):
        assert 0 < args[name + "_window"] < args[name], name
    # a window layer's queries reach 160 keys each at most
    assert args["attn_pairs_window"] <= (600 + 7) * WINDOW
    assert args["attn_pairs"] == 600 * 601 // 2 + sum(600 + i
                                                      for i in range(1, 8))
    assert {"page_alloc", "session_put", "prefix_insert"} <= set(rec.op_ns)
    stats = engine.quant_stats()
    assert stats["kv_bytes_per_token"] == 3 * 2 * 32 * 4
    assert stats["window_kv_bytes_per_token"] == 6 * 2 * 32 * 4
    assert stats["resident_window_kv_tokens"] \
        == (engine.sessions.window.n_pages - 1) * PAGE
    assert "-G3w0+6w160" in engine.kv_signature()


# -- what cannot carry the model says so, by the mechanism's name ------------

REFUSALS = {
    "forward_hidden": lambda e: tr.forward_hidden(
        e.params, e.cfg, jnp.zeros((1, 4), jnp.int32),
        jnp.zeros((1, 4), jnp.int32), None, None, None),
    "host and disk KV tiers": lambda e: e.attach_tier(host_mb=8),
    "handoff": lambda e: __import__(
        "quoracle_tpu.serving.handoff", fromlist=["KVHandoff"]
    ).KVHandoff().export(e, "a", "xla:toy-laguna"),
    "drafts": lambda e: __import__(
        "quoracle_tpu.models.speculative", fromlist=["BatchedSpeculator"]
    ).BatchedSpeculator(e, e),
    "baton drafts": lambda e: __import__(
        "quoracle_tpu.models.speculative", fromlist=["SpeculativeDecoder"]
    ).SpeculativeDecoder(e.cfg, e.params, e.cfg, e.params, e.tokenizer),
    "verify_chunk": lambda e: e.verify_chunk([[5, 6, 7]], ["v"], [1]),
}


@pytest.mark.parametrize("path", sorted(REFUSALS))
def test_a_path_that_cannot_carry_two_groups_refuses(engine, path):
    with pytest.raises(ValueError) as e:
        REFUSALS[path](engine)
    assert "window and full attention layers mixed" in str(e.value)
    assert "ragged paged path of one device" in str(e.value)


@pytest.mark.parametrize("kw,what", [
    (dict(quantize_kv=True), "--quantize-kv"),
    (dict(quantize_weights=True), "--quantize-weights"),
    (dict(mesh="a mesh"), "--tp > 1"),
])
def test_an_engine_option_that_cannot_carry_it_refuses_at_start(toy, kw,
                                                                what):
    cfg, params, _ = toy
    with pytest.raises(ValueError) as e:
        GenerateEngine(cfg, params, ByteTokenizer(), max_seq=256, **kw)
    assert what in str(e.value)
    assert "window and full attention layers mixed" in str(e.value)


def test_the_gather_fallback_refuses_and_leaks_no_page(engine):
    engine.generate([ids_of(99, 200)], temperature=0.0, max_new_tokens=4,
                    session_ids=["kept"])
    before = balance(engine)
    engine._force_gather_decode = True
    try:
        with pytest.raises(RuntimeError, match="gather fallback"):
            engine.generate([[5, 6, 7, 8]], temperature=0.0,
                            max_new_tokens=4, session_ids=["g"])
        with pytest.raises(RuntimeError, match="gather fallback"):
            engine.generate([engine.session_tokens("kept") + [9, 9]],
                            temperature=0.0, max_new_tokens=4,
                            session_ids=["kept"])
    finally:
        engine._force_gather_decode = False
    # the new row's pages went back; the resumed row's session is forgotten
    # with what the tick had dealt it, the cache's blocks stay
    assert engine.sessions.get("kept") is None
    assert balance(engine) == (1, 1) and before == (2, 2)
