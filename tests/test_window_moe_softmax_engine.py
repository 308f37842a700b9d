"""Mellum's toy (tests/test_window_moe_softmax.py) THROUGH THE ENGINE: the
two-group page allocator with the period's FULL layer last and no leading
dense segment. Prefill then decode through the pages against the reference's
full forward — across page boundaries, past the window, after pages were
released, after a resume, after adopting a shared prompt in both groups —
and what the engine states of itself: `quant_stats()`, the group counters,
the expert counters with the grouped kernel's two. A file of its own so that
the two halves run on two workers (`--dist loadfile`)."""

import numpy as np
import pytest

from quoracle_tpu.infra.telemetry import (
    KV_GROUP_PAGES_TOTAL, KV_SESSION_HELD_TOKENS_TOTAL,
    MOE_ASSIGNMENTS_TOTAL, MOE_BLOCK_ROWS_TOTAL, METRICS, tick_close,
    tick_open,
)
from quoracle_tpu.models.generate import GenerateEngine
from quoracle_tpu.models.tokenizer import ByteTokenizer
from tests.test_window_moe_engine import (
    REFUSALS, balance, held, ids_of, served,
)
from tests.test_window_moe_softmax import (   # noqa: F401  (toy: a fixture)
    L, PAGE, TOL, WINDOW, f32, reference_logits, toy,
)


def new_engine(toy, **kw):
    cfg, params, _ = toy
    return GenerateEngine(cfg, f32(params), ByteTokenizer(), max_seq=2048,
                          prompt_buckets=(32, 64, 128, 256, 512, 1024), **kw)


@pytest.fixture()
def engine(toy):
    return new_engine(toy)


# -- sessions: release, resume, adoption --------------------------------------

def test_a_session_outgrows_the_window_lets_go_and_resumes(engine, toy):
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
    engine.generate([ids_of(n, n)], temperature=0.0, max_new_tokens=4,
                    session_ids=["s"])
    full, window = held(engine, "s")
    assert full == -(-(n + 3) // PAGE)
    assert window <= -(-(WINDOW + PAGE) // PAGE) and window >= min(full, 2)
    engine.drop_session("s")
    cached = engine.sessions.prefix_cache.stats()
    assert cached["cached_pages"] == (n + 3) // PAGE
    assert balance(engine) == (cached["cached_pages"],
                               cached["cached_window_pages"])


def test_twelve_short_sessions_balance_to_zero(engine, toy):
    """The cell's shape at toy size: twelve sessions take turns, each
    outgrowing the window, then all are dropped."""
    engine.prefix_sharing = False
    assert balance(engine) == (0, 0)
    sids = [f"s{i}" for i in range(12)]
    for turn in range(2):
        for i, sid in enumerate(sids):
            have = engine.session_tokens(sid) or []
            engine.generate([have + ids_of(40 + 13 * turn + i, 120 + 9 * i)],
                            temperature=0.0, max_new_tokens=3,
                            session_ids=[sid])
    full, window = balance(engine)
    assert full == sum(held(engine, s)[0] for s in sids)
    assert window == sum(held(engine, s)[1] for s in sids) < full
    assert all(held(engine, s)[1] <= 3 for s in sids)
    for sid in sids:
        engine.drop_session(sid)
    assert balance(engine) == (0, 0)
    assert not engine.sessions._refs and not engine.sessions.window._refs


@pytest.mark.parametrize("boundary", [2, 3])
def test_a_new_session_adopts_a_shared_prompt_in_both_groups(engine, toy,
                                                              boundary):
    """A new session shares `boundary` whole pages with a cached prompt:
    it adopts the full group's pages whole and of the window group's the
    last two (a window of 160 reaches across a page boundary), and serves
    the reference's logits behind them."""
    cfg, _, ref = toy
    prompt = ids_of(51, 450)
    served(engine, ref, prompt, "donor")
    engine.drop_session("donor")          # the cache's pages survive it
    count = lambda group: KV_GROUP_PAGES_TOTAL.value(          # noqa: E731
        model=cfg.name, group=group, event="adopted")
    before = (count("full"), count("window"))
    mine = prompt[:boundary * PAGE] + ids_of(52, 60)
    res, gap = served(engine, ref, mine, "new")
    assert gap < TOL and res.n_cached_tokens == boundary * PAGE
    s = engine.sessions.get("new")
    assert [bool(p) for p in s.wpages] == [False] * (boundary - 1) + [True] * 2
    assert (count("full") - before[0], count("window") - before[1]) \
        == (boundary, 2)
    more = mine + res.token_ids + ids_of(53, 200)
    assert served(engine, ref, more, "new")[1] < TOL


def test_a_batch_of_new_sessions_shares_one_prefill(engine, toy):
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


# -- the instruments ----------------------------------------------------------

def test_a_tick_books_both_groups_and_the_experts(engine, toy):
    cfg = toy[0]
    count = lambda group, event: KV_GROUP_PAGES_TOTAL.value(   # noqa: E731
        model=cfg.name, group=group, event=event)
    tokens = lambda group: KV_SESSION_HELD_TOKENS_TOTAL.value(  # noqa: E731
        model=cfg.name, group=group)
    before = (count("window", "allocated"),
              count("window", "released_behind_window"),
              tokens("full"), tokens("window"),
              MOE_ASSIGNMENTS_TOTAL.value(model=cfg.name, held="true"),
              MOE_ASSIGNMENTS_TOTAL.value(model=cfg.name, held="false"))
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
    # every expert of a layer is held: each of a token's 8 choices counts
    assert args["moe_assignments"] == args["moe_held"] == 607 * 8 * L
    assert args["moe_layer_steps"] == 8 * L
    assert MOE_ASSIGNMENTS_TOTAL.value(model=cfg.name, held="true") \
        - before[4] == 607 * 8 * L
    assert MOE_ASSIGNMENTS_TOTAL.value(model=cfg.name, held="false") \
        == before[5]
    # the loop over blocks serves here (no TPU): the grouped kernel's two
    # counts are not booked
    assert "moe_blocks" not in args
    stats = engine.quant_stats()
    assert stats["kv_bytes_per_token"] == 2 * 2 * 32 * 4
    assert stats["window_kv_bytes_per_token"] == 6 * 2 * 32 * 4
    assert stats["resident_window_kv_tokens"] \
        == (engine.sessions.window.n_pages - 1) * PAGE
    assert "-G2w0+6w160" in engine.kv_signature()


def test_the_grouped_kernels_counts_reach_the_span_and_the_counter(engine,
                                                                   toy):
    """What the two programs return where the grouped kernel ran the
    experts (six counts: `transformer.moe_counts`) is booked once a tick:
    `moe_blocks` and `moe_block_rows` on the tick span, the rows that hold
    an assignment and the rows run on `quoracle_moe_block_rows_total` —
    and nothing of it where the loop served (four counts)."""
    cfg = toy[0]
    rows = lambda kind: MOE_BLOCK_ROWS_TOTAL.value(            # noqa: E731
        model=cfg.name, kind=kind)
    before = (rows("assigned"), rows("run"))
    rec = tick_open(cfg.name)
    try:
        # a 128-token tick at 64 experts, 8 a token: 64 blocks of 128 rows
        engine._note_moe(np.asarray([1024, 1024, 64, 1, 64, 64 * 128]))
    finally:
        tick_close()
    assert rec.args["moe_blocks"] == 64
    assert rec.args["moe_block_rows"] == 8192
    assert rec.args["moe_held"] == 1024 and rec.args["moe_reached"] == 64
    assert (rows("assigned") - before[0], rows("run") - before[1]) \
        == (1024, 8192)
    rec = tick_open(cfg.name)
    try:
        engine._note_moe(np.asarray([1024, 1024, 64, 1]))
    finally:
        tick_close()
    assert "moe_blocks" not in rec.args
    assert (rows("assigned") - before[0], rows("run") - before[1]) \
        == (1024, 8192)
    assert "quoracle_moe_block_rows_total" in METRICS.render_prometheus()


# -- what cannot carry the model says so, by the mechanism's name ------------

@pytest.mark.parametrize("path", sorted(REFUSALS))
def test_a_path_that_cannot_carry_it_refuses_by_the_mechanisms_name(engine,
                                                                    path):
    with pytest.raises(ValueError) as e:
        REFUSALS[path](engine)
    assert "window and full attention layers mixed" in str(e.value)
    assert "routed experts" in str(e.value)
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
