"""Unified ragged serving kernel (ISSUE 8, ops/paged_attention.py
ragged_attend / models/generate.py _run_unified): one token-major launch
per layer for the whole mixed tick — prefill suffixes, continuations,
decode steps and speculative-verify windows — with KV written straight to
pages. Tier-1 asserts three things:

  * the Pallas kernel (interpret mode off-TPU) agrees with the dense
    gather oracle across geometries: GQA groupings, page sizes, empty
    (inert) blocks, single-token rows, and rows at the sliding-window
    edge;
  * temp-0 BIT-EQUALITY of the unified path vs the gather path for
    greedy, grammar-constrained, and speculative-verify decodes — the
    same bar every serving layer in this repo holds;
  * the compile-count COLLAPSE: a 50-tick mixed-shape run through the
    unified path lands on ≤ RAGGED_PROGRAM_BOUND CompileRegistry keys
    (one (chunk, decode) program pair per (token-budget, table-width)
    bucket), strictly fewer than the bucketed gather baseline compiles
    for the identical traffic.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quoracle_tpu.models.config import get_model_config
from quoracle_tpu.models.generate import (
    PAGE, RAGGED_TQ, GenerateEngine,
)
from quoracle_tpu.models.tokenizer import ByteTokenizer
from quoracle_tpu.models.transformer import init_params

# Documented program-count bound for the 50-tick mixed-shape traffic in
# test_compile_collapse_vs_bucketed_baseline (ARCHITECTURE.md §10): each
# CompileRegistry key is one ("ragged", token-budget bucket, table width,
# decode bound) tuple = one chunk + one decode program. The traffic below
# spans ≤ 4 token-budget buckets × ≤ 2 table widths.
RAGGED_PROGRAM_BOUND = 8


def make_engine(name="xla:tiny", seed=0, **kw):
    cfg = get_model_config(name)
    params = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)
    return GenerateEngine(cfg, params, ByteTokenizer(),
                          max_seq=kw.pop("max_seq", 256),
                          prompt_buckets=kw.pop("prompt_buckets",
                                                (32, 64, 128)),
                          **kw)


def enc(text):
    return ByteTokenizer().encode(text, add_bos=True)


def _gather(eng):
    eng._force_gather_decode = True     # the equality/fallback seam
    return eng


# --- kernel vs dense oracle -------------------------------------------------


def _random_case(rng, rows, H, KV, hd, page, n_pages, window, tile=0,
                 quant=False):
    """Build a flat layout from (prefix, q_len) rows and run kernel
    (interpret) vs the dense gather oracle: one program a block, or with
    ``tile`` one a tile of that many tokens (``ragged_tiles`` of the same
    block table, plus two unused slots)."""
    from quoracle_tpu.ops.paged_attention import (
        ragged_attend, ragged_attend_ref, ragged_tiles,
    )
    tq = RAGGED_TQ
    maxp = max(-(-(pre + q) // page) for pre, q in rows if q > 0)
    NB = sum(-(-q // tq) if q else 1 for pre, q in rows)
    Tp = NB * tq
    q = jnp.asarray(rng.standard_normal((Tp, H, hd)), jnp.float32)
    # the pools as the engine stores them, [L, n_pages, page, KV·hd]: the
    # kernel is handed all of them and reads layer 1 of 3 — its
    # neighbours hold other numbers, so a wrong layer cannot agree
    layer = 1
    if quant:
        kp, vp = (jnp.asarray(rng.integers(
            -127, 128, (3, n_pages, page, KV * hd)), jnp.int8)
            for _ in range(2))
        extra = dict(zip(("k_scale", "v_scale"), (jnp.asarray(rng.uniform(
            0.002, 0.02, (3, n_pages, KV, page)), jnp.float32)
            for _ in range(2))))
    else:
        kp, vp = (jnp.asarray(rng.standard_normal(
            (3, n_pages, page, KV * hd)), jnp.float32) for _ in range(2))
        extra = {}
    rtab = np.zeros((len(rows), maxp), np.int32)
    bmeta = np.zeros((4, NB), np.int32)     # kv_len, qpos0, nq, row
    next_page = 1
    cur_blk = 0
    for r, (pre, qlen) in enumerate(rows):
        nb = -(-qlen // tq) if qlen else 1
        rtab[r] = [(next_page + j) % (n_pages - 1) + 1
                   for j in range(maxp)]
        next_page += maxp
        for b in range(nb):
            bmeta[:, cur_blk + b] = (pre + qlen, pre + b * tq,
                                     max(0, min(tq, qlen - b * tq)), r)
        cur_blk += nb
    ref = ragged_attend_ref(q, kp, vp, jnp.asarray(rtab),
                            jnp.asarray(bmeta), layer, tq=tq,
                            sliding_window=window, **extra)
    if tile:
        tiles = ragged_tiles(bmeta, tq, tile)
        extra.update(tile=tile, tiles=jnp.asarray(np.concatenate(
            [tiles, np.zeros((6, 2), np.int32)], axis=1)))
    krn = ragged_attend(q, kp, vp, jnp.asarray(rtab), jnp.asarray(bmeta),
                        layer, tq=tq, sliding_window=window,
                        interpret=jax.devices()[0].platform != "tpu",
                        **extra)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(krn),
                               rtol=2e-4, atol=2e-4)
    return np.asarray(krn), bmeta


def test_ragged_kernel_matches_oracle_geometries():
    """Interpret-mode kernel vs the dense oracle: GQA groupings, two page
    sizes, decode (single-token) rows, chunk rows, and empty (inert)
    blocks in one grid."""
    rng = np.random.default_rng(3)
    #       rows: (prefix, q_len); q_len 0 = inert block (padding slot)
    rows = [(40, 1), (17, 11), (0, 19), (5, 0), (63, 1)]
    for H, KV in ((8, 2), (4, 4), (6, 1)):
        for page in (8, 16):
            _random_case(rng, rows, H, KV, 32, page, 24, None)


def test_ragged_kernel_window_edges():
    """Sliding-window masking at the hard spots: window smaller than a
    page, window exactly at a page boundary, query at position 0, and a
    decode token whose window excludes every resident page but its own."""
    rng = np.random.default_rng(4)
    page = 16
    for window in (3, page, page + 1, 24):
        rows = [(0, 9),              # fresh chunk, window inside chunk
                (2 * page, 1),       # decode at a page boundary
                (window, 1),         # window exactly excludes the prefix
                (37, 5)]             # straddles pages mid-way
        _random_case(rng, rows, 8, 2, 32, page, 24, window)


def test_ragged_kernel_empty_and_inert_blocks_are_zero():
    """nq = 0 blocks (padding) must come out exactly zero — no NaNs to
    poison downstream einsums."""
    rng = np.random.default_rng(5)
    out, bmeta = _random_case(rng, [(12, 3), (9, 0)], 8, 2, 32, 16, 12,
                              None)
    tq = RAGGED_TQ
    assert np.all(np.isfinite(out))
    # row 0: queries 3..7 of block 0 are padding; row 1's block is inert
    assert np.all(out[3:tq] == 0.0)
    assert np.all(out[tq:] == 0.0)


# One row's pages walked once per TILE of its queries (ISSUE 30): the same
# block table grouped by ``ragged_tiles``. (prefix, q_len) rows; G = H / KV.
TILE_CASES = {
    # every length around a tile's edge in ONE launch: a decode row, one
    # block, a block and a token, a tile less one, a tile, a tile and a
    # token, and a row of 8 tiles whose last is short (1000 = 7·128 + 104)
    "lengths-1-to-1000": dict(
        rows=[(40, 1), (3, 8), (0, 9), (17, 127), (0, 128), (5, 129),
              (0, 1000)], tile=128, KV=1),
    # a segment that is no multiple of the tile, after a resident prefix
    # of several pages; inert blocks between rows
    "prefix-and-ragged-suffix": dict(
        rows=[(5 * 16 + 3, 70), (9, 0), (200, 33), (0, 0), (64, 1)],
        tile=32),
    "tile-ends-at-the-rows-end": dict(
        rows=[(0, 64), (16, 128), (7, 32)], tile=32),
    # the window's first page falls inside a tile (its first and last
    # query start on different pages) and between two tiles
    "window-edge-inside-a-tile": dict(
        rows=[(100, 70), (0, 90), (48, 1)], tile=64, window=20),
    "window-edge-between-tiles": dict(
        rows=[(96, 64), (0, 200)], tile=32, window=32),
    "window-wider-than-a-tile": dict(
        rows=[(30, 150), (250, 9)], tile=32, window=100),
    "window-one-page": dict(rows=[(0, 100), (77, 40)], tile=32, window=16),
    # a 128-token tile at G = 8 (1,024 score rows a kv head) whose window
    # is a third of it: most of a page's columns are masked for most rows
    "window-inside-a-tall-tile": dict(
        rows=[(100, 200), (0, 90), (300, 33)], tile=128, window=40, KV=1),
    "g1": dict(rows=[(20, 300), (90, 1), (0, 17)], tile=128, H=2, KV=2),
    "g4": dict(rows=[(20, 300), (90, 1), (0, 17)], tile=128, H=8, KV=2),
    "g8": dict(rows=[(20, 300), (90, 1), (0, 17)], tile=128, H=8, KV=1),
    "g3-page8": dict(rows=[(20, 100), (9, 1)], tile=64, H=6, KV=2, page=8),
    "int8": dict(rows=[(40, 1), (17, 41), (0, 70), (5, 0), (63, 200)],
                 tile=64, quant=True),
    "int8-window": dict(rows=[(100, 70), (0, 90), (48, 1)], tile=32,
                        window=20, quant=True, KV=1),
    # the smallest tile is a block: the table is the block table's twin
    "tile-of-one-block": dict(rows=[(40, 1), (17, 11), (0, 19), (5, 0)],
                              tile=8),
}


@pytest.mark.parametrize("case", TILE_CASES.values(), ids=TILE_CASES)
def test_tile_kernel_matches_oracle(case):
    """The tile kernel (interpret mode) against the dense oracle, whole
    output: padding tokens and inert tiles come out zero as the oracle's
    do, so one comparison covers them."""
    rows, page = case["rows"], case.get("page", 16)
    need = sum(-(-(pre + q) // page) for pre, q in rows if q) + 2
    out, _ = _random_case(
        np.random.default_rng(30), rows, case.get("H", 8),
        case.get("KV", 2), 32, page, need, case.get("window"),
        tile=case["tile"], quant=case.get("quant", False))
    assert np.all(np.isfinite(out))


@pytest.mark.parametrize("seed", range(4))
def test_tiles_partition_the_flat_layout(seed):
    """``ragged_tiles`` of a random block table: the tiles' spans tile
    the flat tokens without gap or overlap, no tile crosses a row or
    holds more than ``tile`` tokens, its first query is its first
    block's, the queries add up, and the engine's static slot count is
    never short."""
    from quoracle_tpu.ops.paged_attention import (
        ragged_tile_slots, ragged_tiles,
    )
    rng = np.random.default_rng(seed)
    tq, tile = RAGGED_TQ, int(rng.choice([8, 32, 128]))
    n_rows = int(rng.integers(1, 9))
    segs = rng.integers(1, 600, n_rows)
    pres = rng.integers(0, 900, n_rows)
    nb = -(-segs // tq)
    NB = int(nb.sum()) + int(rng.integers(0, 40))       # tail padding
    meta = np.zeros((4, NB), np.int32)
    cur = 0
    for r in range(n_rows):
        b = np.arange(nb[r])
        meta[:, cur + b] = (np.full(nb[r], pres[r] + segs[r]),
                            pres[r] + b * tq,
                            np.minimum(tq, segs[r] - b * tq),
                            np.full(nb[r], r))
        cur += nb[r]
    slots = ragged_tile_slots(NB, 8, tq, tile)
    tiles = ragged_tiles(meta, tq, tile, slots)
    assert tiles.shape == (6, slots)
    kv_len, qpos0, nq, row, tok0, span = tiles
    used = span > 0
    assert not np.any(tiles[:, ~used])
    assert np.array_equal(tok0[used],
                          np.r_[0, np.cumsum(span[used])[:-1]])
    assert span[used].sum() == NB * tq
    assert np.all(span <= tile) and np.all(span % tq == 0)
    live = nq > 0
    assert np.all(span[live] == -(-nq[live] // tq) * tq)
    assert nq.sum() == segs.sum()
    first = tok0[live] // tq
    assert np.array_equal(tiles[[0, 1, 3]][:, live],
                          meta[[0, 1, 3]][:, first])
    for r in range(n_rows):             # a row's tiles: full, then a rest
        assert nq[live & (row == r)].tolist() == \
            [tile] * int(segs[r] // tile) + [segs[r] % tile] * int(
                segs[r] % tile > 0)


def test_a_cold_prompt_streams_its_keys_once_per_tile():
    """``attn_kv_streamed``: a cold 2,048-token row walked a block at a
    time brings n² / 16 resident tokens into VMEM a layer; walked a
    128-token tile at a time, under a sixth of that (a sixteenth, and a
    page for the diagonal)."""
    from quoracle_tpu.ops.paged_attention import (
        ragged_tile_walk, ragged_tiles,
    )
    tq, n, page = RAGGED_TQ, 2048, 128
    b = np.arange(n // tq)
    meta = np.stack([np.full_like(b, n), b * tq, np.full_like(b, tq),
                     np.zeros_like(b)])
    by_block, programs = ragged_tile_walk(ragged_tiles(meta, tq, tq), page)
    assert programs == n // tq
    # block i sees ceil((i + 1)·8 / 128) pages
    assert by_block == page * sum(-(-(i + 1) * tq // page) for i in b)
    by_tile, programs = ragged_tile_walk(ragged_tiles(meta, tq, 128), page)
    assert programs == n // 128
    assert by_tile == page * sum(range(1, n // 128 + 1))
    assert by_tile * 6 < by_block
    # a window cuts the walk at the tile's FIRST query's reach
    windowed, _ = ragged_tile_walk(ragged_tiles(meta, tq, 128), page, 256)
    assert windowed == page * (1 + 2 + 3 * 14)


def test_tick_span_counts_what_the_kernel_streamed():
    """A ragged tick under an open tick record notes, beside
    ``attn_kv_reads``, the resident tokens its kernel programs brought
    into VMEM and how many programs walked pages: the chunk forward's
    tiles, and one one-token tile a row a decode step."""
    from quoracle_tpu.infra.telemetry import tick_close, tick_open
    eng = make_engine(max_seq=1024, prompt_buckets=(64, 128, 256, 512))
    page, tile = eng.sessions.page, eng._ragged_tile
    long, short = enc("user: " + "a long cold prompt " * 20), enc("u: hi")
    tick_open("m")
    try:
        res = eng.generate([long, short], temperature=0.0,
                           max_new_tokens=5, session_ids=["a", "b"])
    finally:
        args = tick_close().args
    pages = lambda n: -(-n // page)         # noqa: E731
    # the chunk forward: row r's tiles end at tile, 2·tile, …, its length
    chunk = [pages(min(n, (t + 1) * tile)) for n in (len(long), len(short))
             for t in range(-(-n // tile))]
    # decode forward j sees the prompt and j sampled tokens; the last
    # sampled token of a row is never fed back
    dec = [pages(n + j) for n, r in zip((len(long), len(short)), res)
           for j in range(1, len(r.token_ids))]
    assert args["attn_tiles"] == len(chunk) + len(dec)
    assert args["attn_kv_streamed"] == page * (sum(chunk) + sum(dec))
    assert args["attn_kv_streamed"] >= args["attn_kv_reads"] > 0
    # the walks' loop iterations: a tile's a page each, a decode row's a
    # block of ``walk_pages`` pages (4 at these widths: one turn a step)
    assert eng._walk_block == 4
    assert args["attn_walk_steps"] == sum(chunk) + len(dec)
    # ... and the decode steps' walks: one a row a step, every one of a
    # step but its first started while the walk before it ran
    assert args["attn_walks"] == len(dec)
    steps = max(len(r.token_ids) for r in res) - 1
    assert args["attn_walks_started_ahead"] == len(dec) - steps


# --- the decode program's shared walk (ISSUE 32) ----------------------------
#
# Decode rows: (run, common, own, tail, live) — the row's table is the first
# ``common`` pages of shared run ``run`` (a run's ids are the same for every
# row that names it), then ``own`` pages of its own, the last holding
# ``tail`` tokens; ``live`` 0 is a row that is done (nq = 0).

from quoracle_tpu.ops import paged_attention as pa        # noqa: E402

M = pa.SHARED_MIN_PAGES
SHARED_CASES = {
    "one-group-of-all-rows": dict(
        rows=[("a", M + 2, 1, 3, 1), ("a", M + 2, 2, 16, 1),
              ("a", M + 2, 1, 9, 1), ("a", M + 2, 3, 1, 1)],
        walks={0: (M + 2, [0, 1, 2, 3])}),
    "two-groups-and-a-loner": dict(
        rows=[("a", M, 2, 5, 1), ("b", M + 3, 1, 7, 1), (None, 0, M + 2, 4, 1),
              ("a", M, 1, 16, 1), ("b", M + 3, 2, 2, 1), ("a", M, 3, 11, 1)],
        walks={0: (M, [0, 3, 5]), 1: (M + 3, [1, 4])}),
    "a-member-is-done-from-the-first-step": dict(
        rows=[("a", M + 1, 1, 3, 1), ("a", M + 1, 2, 8, 0),
              ("a", M + 1, 1, 12, 1)],
        walks={0: (M + 1, [0, 1, 2])}),
    "the-leader-is-done": dict(
        rows=[("a", M + 1, 1, 3, 0), ("a", M + 1, 2, 8, 1),
              ("a", M + 1, 1, 12, 1)],
        walks={0: (M + 1, [0, 1, 2])}),
    "every-member-is-done": dict(
        rows=[("a", M + 1, 1, 3, 0), ("a", M + 1, 2, 8, 0),
              (None, 0, 2, 5, 1)],
        walks={0: (M + 1, [0, 1])}),
    # a row holds one token of the page behind the shared ones, another
    # has filled it to its last slot, a third is mid-page
    "rows-end-in-the-page-after-the-shared-ones": dict(
        rows=[("a", M, 1, 1, 1), ("a", M, 1, 16, 1), ("a", M, 1, 7, 1)],
        walks={0: (M, [0, 1, 2])}),
    # a row whose last shared-run page is not full yet shares one less
    "the-cap-at-whole-pages": dict(
        rows=[("a", M + 2, 0, 9, 1), ("a", M + 2, 1, 4, 1)],
        walks={0: (M + 1, [0, 1])}),
    "nothing-shared": dict(
        rows=[(None, 0, M + 1, 3, 1), (None, 0, M, 16, 1),
              (None, 0, 1, 2, 1), (None, 0, 0, 0, 0)],
        walks={}),
    # more rows than one walk serves: two walks over the same pages
    "eleven-rows-two-walks": dict(
        rows=[("a", M, 1, 1 + r, 1) for r in range(11)],
        walks={0: (M, list(range(8))), 8: (M, [8, 9, 10])}),
    "int8": dict(
        rows=[("a", M + 1, 1, 3, 1), (None, 0, 2, 9, 1),
              ("a", M + 1, 2, 8, 1)],
        walks={0: (M + 1, [0, 2])}, quant=True),
    "qwen-16-2": dict(
        rows=[("a", M + 2, 1, 3, 1), ("a", M + 2, 2, 16, 1),
              (None, 0, 2, 5, 1), ("a", M + 2, 1, 9, 1)],
        walks={0: (M + 2, [0, 1, 3])}, H=16, KV=2),
    "mistral-32-8": dict(
        rows=[("a", M + 2, 1, 3, 1), ("a", M + 2, 2, 16, 1),
              (None, 0, 2, 5, 1), ("a", M + 2, 1, 9, 1)],
        walks={0: (M + 2, [0, 1, 3])}, H=32, KV=8),
}


def _decode_tables(rows, page):
    """(tables [R, maxp], resident tokens [R], block meta [4, R], pages
    used) of decode rows as above, one tq = 1 block a row."""
    runs, nxt, tabs = {}, 1, []
    for run, common, own, tail, live in rows:
        if run is not None and run not in runs:
            runs[run] = list(range(nxt, nxt + 16))
            nxt += 16
        tabs.append((runs[run][:common] if run else [])
                    + list(range(nxt, nxt + own)))
        nxt += own
    tables = np.zeros((len(rows), max(map(len, tabs)) + 1), np.int32)
    for r, t in enumerate(tabs):
        tables[r, :len(t)] = t
    lens = np.asarray([max(len(t) - 1, 0) * page + row[3]
                       for t, row in zip(tabs, rows)], np.int32)
    nq = np.asarray([row[4] for row in rows], np.int32)
    # what decode_ragged hands the kernel for a row of ``lens`` tokens
    meta = np.stack([lens + nq, lens - (1 - nq), nq,
                     np.arange(len(rows), dtype=np.int32)])
    return tables, lens, meta, nxt


@pytest.mark.parametrize("case", SHARED_CASES.values(), ids=SHARED_CASES)
def test_shared_walk_matches_oracle(case):
    """The decode call with ``shared_walks``' table of its rows (interpret
    mode) against the dense oracle: the groups are the expected ones, a
    row's output does not depend on who walked its leading pages, a done
    row's is zero, and a table with no group gives the output of the call
    without one bit for bit."""
    page, hd = 16, 32
    H, KV = case.get("H", 8), case.get("KV", 2)
    quant = case.get("quant", False)
    tables, lens, meta, n_pages = _decode_tables(case["rows"], page)
    shared = pa.shared_walks(tables, lens, page)
    R = len(case["rows"])
    assert shared.shape == (2 + pa.SHARED_ROWS, R)
    want = np.zeros((R,), np.int32)
    for lead, (n, members) in case["walks"].items():
        want[members] = n
        assert sorted(set(shared[2:, lead])) == members
    assert shared[0].tolist() == want.tolist()
    assert np.flatnonzero(shared[1]).tolist() == sorted(case["walks"])
    rng = np.random.default_rng(32)
    q = jnp.asarray(rng.standard_normal((R, H, hd)), jnp.float32)
    if quant:
        kp, vp = (jnp.asarray(rng.integers(
            -127, 128, (3, n_pages, page, KV * hd)), jnp.int8)
            for _ in range(2))
        extra = dict(zip(("k_scale", "v_scale"), (jnp.asarray(rng.uniform(
            0.002, 0.02, (3, n_pages, KV, page)), jnp.float32)
            for _ in range(2))))
    else:
        kp, vp = (jnp.asarray(rng.standard_normal(
            (3, n_pages, page, KV * hd)), jnp.float32) for _ in range(2))
        extra = {}
    args = (q, kp, vp, jnp.asarray(tables), jnp.asarray(meta), 1)
    interpret = jax.devices()[0].platform != "tpu"
    ref = np.asarray(pa.ragged_attend_ref(*args, tq=1, **extra))
    got = np.asarray(pa.ragged_attend(*args, tq=1, interpret=interpret,
                                      shared=jnp.asarray(shared), **extra))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
    assert np.all(got[meta[2] == 0] == 0.0)
    if not case["walks"]:
        plain = np.asarray(pa.ragged_attend(*args, tq=1,
                                            interpret=interpret, **extra))
        assert np.array_equal(got, plain)


def _walk_groups(shared) -> dict:
    """{leader: (pages, member rows)} of a shared-walk table."""
    return {int(r): (int(shared[0, r]), sorted(set(shared[2:, r].tolist())))
            for r in np.flatnonzero(shared[1])}


GROUPING = {
    # rows 0, 2, 5 hold run a (12 pages), rows 1, 4 run b (9); row 3 alone
    "base": dict(order=[0, 1, 2, 3, 4, 5],
                 want={0: (12, [0, 2, 5]), 1: (9, [1, 4])}),
    "permuted": dict(order=[4, 3, 5, 1, 0, 2],
                     want={0: (9, [0, 3]), 2: (12, [2, 4, 5])}),
    # slots the tick does not use: zero tables, zero tokens
    "padded-slots": dict(order=[0, 1, 2, 3, 4, 5], pad=10,
                         want={0: (12, [0, 2, 5]), 1: (9, [1, 4])}),
    # row 2 holds run a's pages but only 7 of them whole before the loop
    "cap-at-the-fewest-whole-pages": dict(
        order=[0, 1, 2, 3, 4, 5], lens={2: 7 * 128 + 5},
        want={0: (7, [0, 2, 5]), 1: (9, [1, 4])}),
    "sliding-window": dict(order=[0, 1, 2, 3, 4, 5], window=4096, want={}),
    # one more common page than pays, and one less: in, and out
    "least-pages": dict(order=[0, 1, 2, 3, 4, 5], trim=pa.SHARED_MIN_PAGES,
                        want={0: (pa.SHARED_MIN_PAGES, [0, 2, 5]),
                              1: (pa.SHARED_MIN_PAGES, [1, 4])}),
    "too-few-pages": dict(order=[0, 1, 2, 3, 4, 5],
                          trim=pa.SHARED_MIN_PAGES - 1, want={}),
    # rows 0 and 2 go on together for 20 more pages; row 5 left them after
    # run a: the pair's 32 pages save more than the three rows' 12
    "a-deeper-pair": dict(order=[0, 1, 2, 3, 4, 5], deeper=20,
                          want={0: (32, [0, 2]), 1: (9, [1, 4])}),
}


@pytest.mark.parametrize("case", GROUPING.values(), ids=GROUPING)
def test_shared_walks_is_a_function_of_the_tables(case):
    """``shared_walks`` from (tables, resident tokens, page) alone: groups
    follow the rows wherever they sit, unused slots join nothing, a walk
    stops at the fewest whole pages a member holds, a window shares
    nothing, and of two nestings the one that saves more reads is taken."""
    page, width = 128, 64
    run_a, run_b = np.arange(100, 112), np.arange(200, 209)
    trim = case.get("trim")
    if trim is not None:
        run_a, run_b = run_a[:trim], run_b[:trim]
    tabs = [list(run_a) + [1, 2], list(run_b) + [3], list(run_a) + [4],
            [5, 6, 7, 8, 9, 10], list(run_b) + [11, 12], list(run_a) + [13]]
    if "deeper" in case:
        more = list(range(300, 300 + case["deeper"]))
        tabs[0], tabs[2] = list(run_a) + more + [1], list(run_a) + more + [4]
    lens = [(len(t) - 1) * page + 17 for t in tabs]
    for r, n in case.get("lens", {}).items():
        lens[r] = n
    R = len(tabs) + case.get("pad", 0)
    tables = np.zeros((R, width), np.int32)
    pool_lens = np.zeros((R,), np.int32)
    for at, r in enumerate(case["order"]):
        tables[at, :len(tabs[r])] = tabs[r]
        pool_lens[at] = lens[r]
    shared = pa.shared_walks(tables, pool_lens, page, case.get("window"))
    assert shared.shape == (2 + pa.SHARED_ROWS, R)
    assert shared.dtype == np.int32
    assert _walk_groups(shared) == case["want"]
    in_a_group = sorted(r for _, rows in case["want"].values() for r in rows)
    assert np.flatnonzero(shared[0]).tolist() == in_a_group
    for n, rows in case["want"].values():
        assert shared[0, rows].tolist() == [n] * len(rows)
        assert n * page <= pool_lens[rows].min()
    # rows that lead nothing list themselves: a harmless read in the kernel
    quiet = shared[1] == 0
    assert np.array_equal(shared[2:, quiet],
                          np.tile(np.flatnonzero(quiet), (8, 1)))


def test_shared_prompt_decode_walks_common_pages_once(monkeypatch):
    """Three sessions on one long system prompt, decoded in one tick: with
    the prefix cache on their tables begin with the same pages, the tick
    notes the rows and pages a shared walk served and streams fewer
    resident tokens than its rows needed. The greedy tokens are those of
    the same run with the cache off; the program keys, and the one decode
    program behind them, are those of the same run with no walk (the
    least count of pages out of reach)."""
    from quoracle_tpu.infra.telemetry import (
        ATTN_SHARED_KV_TOKENS_TOTAL, tick_close, tick_open,
    )
    page = PAGE
    system = "system: " + "policy rules apply to every agent here. " * 24
    n_shared = len(enc(system)) // page
    assert n_shared > pa.SHARED_MIN_PAGES
    asks = [enc(system + f"user: task {name}") for name in
            ("alpha", "beta please", "gamma, the third one")]

    def run(sharing):
        eng = make_engine(max_seq=2048,
                          prompt_buckets=(256, 512, 1024, 2048))
        eng.prefix_sharing = sharing
        eng.generate([enc(system + "user: the donor")], temperature=0.0,
                     max_new_tokens=4, session_ids=["donor"])
        tick_open("m")
        try:
            res = eng.generate(asks, temperature=0.0, max_new_tokens=6,
                               session_ids=["a", "b", "c"])
        finally:
            args = tick_close().args
        keys = {e["shape"] for e in
                eng.compiles.snapshot(max_shapes=64)["shapes"]}
        return ([r.token_ids for r in res], args,
                (keys, eng._step_paged_decode_ragged._cache_size()), res)

    def counted():
        return [ATTN_SHARED_KV_TOKENS_TOTAL.value(model="tiny", kind=kind)
                for kind in ("needed", "walked")]

    before = counted()
    want, _, _, _ = run(False)
    with monkeypatch.context() as patch:
        patch.setattr(pa, "SHARED_MIN_PAGES", 10 ** 6)
        same, off, programs_off, _ = run(True)
    assert counted() == before
    got, on, programs_on, res = run(True)
    assert got == same == want
    assert programs_on == programs_off
    assert all(r.n_cached_tokens == n_shared * page for r in res)
    assert off["attn_shared_rows"] == 0 and off["attn_shared_pages"] == 0
    assert off["attn_kv_streamed"] >= off["attn_kv_reads"]
    assert on["attn_shared_rows"] == 3
    assert on["attn_shared_pages"] == n_shared
    assert on["attn_kv_streamed"] < on["attn_kv_reads"] \
        == off["attn_kv_reads"]
    # 5 decode forwards a row (the sixth token is never fed back), 2 layers
    steps, layers = 5, 2
    needed, walked = (now - was for now, was in zip(counted(), before))
    assert needed == 3 * n_shared * page * steps * layers
    assert walked == n_shared * page * steps * layers
    assert off["attn_kv_streamed"] - on["attn_kv_streamed"] == \
        2 * n_shared * page * steps
    # three walks of the common pages a step became one, a block of 4
    # pages a turn; the rows' own walks behind them are a turn each
    turns = -(-n_shared // 4)
    assert off["attn_walk_steps"] - on["attn_walk_steps"] == \
        (3 * turns - (turns + 3)) * steps
    # a step's call makes three walks without the table and four with it
    # (the group's, then each row's own): all but its first started ahead
    assert (off["attn_walks"], off["attn_walks_started_ahead"]) == \
        (3 * steps, 2 * steps)
    assert (on["attn_walks"], on["attn_walks_started_ahead"]) == \
        (4 * steps, 3 * steps)


# --- the block walk (ISSUE 38) -----------------------------------------------
#
# The block kernel's walks move B pages a loop iteration and update the
# softmax state once a block (``pa.walk_pages`` of a page's bytes: 8 at these
# widths; 4 is asked for too); ``walk_block=1`` is the walk of one page an
# iteration, reachable from here alone. A case is one decode launch of 8
# rows: ``walk`` the pages of the walk under test (1, B − 1, B, B + 1, 2B + 3:
# a partial block, a full one, one page over, two blocks and a rest) — each
# row's own walk where nothing is shared, else the group's shared walk with
# ``tail`` pages of its own behind it for every row.

_BW_PAGE, _BW_WIDTH, _BW_POOL = 8, 32, 256


def _block_walk_cases():
    cases = {}
    pools = {"bf16-hd128": dict(hd=128), "int8-hd128": dict(hd=128,
                                                            quant=True),
             "bf16-hd64-packed": dict(hd=64)}
    for block in (None, 4):
        B = block or 8
        walks = {"1": 1, "B-1": B - 1, "B": B, "B+1": B + 1,
                 "2B+3": 2 * B + 3}
        for pool, geo in pools.items():
            if block and pool != "bf16-hd128":
                continue            # Mistral's B: one geometry is enough
            for name, walk in walks.items():
                tag = f"{pool}-b{B}-walk-{name}"
                for window in (False, True):
                    cases[f"{tag}-{'window' if window else 'no-window'}"] = \
                        dict(geo, walk=walk, window=window, block=block)
                for size in (2, 8):
                    for tail in (1, B + 1):
                        if block and (size, tail) != (8, B + 1):
                            continue
                        cases[f"{tag}-group{size}-tail{tail}"] = dict(
                            geo, walk=walk, group=size, tail=tail,
                            block=block)
    return cases


BLOCK_WALK_CASES = _block_walk_cases()


def _block_walk_tables(walk, window, group, tail):
    """(tables, block meta, shared-walk table or None, window) of a case:
    8 decode rows, the last one done. Nothing shared: row r holds r % 3
    pages in front of its ``walk`` visible ones (under a window they lie
    outside it, so the first page of the walk differs by row). A group:
    its members' tables begin with the same ``walk`` pages, every row
    holds ``tail`` pages of its own behind, and the walk table is made by
    hand (``shared_walks`` forms no group under ``SHARED_MIN_PAGES``)."""
    page, R = _BW_PAGE, 8
    tables = np.zeros((R, _BW_WIDTH), np.int32)
    nxt = 1
    common = np.arange(nxt, nxt + walk)
    nxt += walk
    members = {2: [1, 5], 8: list(range(R))}.get(group, [])
    kv_len = np.zeros((R,), np.int32)
    fill = 3                                # tokens in a row's last page
    for r in range(R):
        if r in members:
            lead, own = common, tail
        else:
            lead, own = common[:0], (tail if group else walk + r % 3)
        tables[r, :len(lead)] = lead
        tables[r, len(lead):len(lead) + own] = np.arange(nxt, nxt + own)
        nxt += own
        kv_len[r] = (len(lead) + own - 1) * page + \
            (fill if window else 1 + (3 * r) % page)
    assert nxt <= _BW_POOL
    nq = np.ones((R,), np.int32)
    nq[R - 1] = 0                           # a row that is done
    lens = kv_len - 1
    meta = np.stack([lens + nq, lens - (1 - nq), nq,
                     np.arange(R, dtype=np.int32)])
    shared = None
    if group:
        shared = np.zeros((2 + pa.SHARED_ROWS, R), np.int32)
        shared[2:] = np.arange(R)
        shared[0, members] = walk
        shared[1, members[0]] = 1
        shared[2:, members[0]] = (members + members[:1] * 8)[:8]
    # the newest ``walk`` pages of a row and no token more: its walk
    # starts r % 3 pages into its table
    return tables, meta, shared, \
        ((walk - 1) * page + fill if window else None)


@pytest.mark.parametrize("case", BLOCK_WALK_CASES.values(),
                         ids=BLOCK_WALK_CASES)
def test_block_walk_agrees_with_the_one_page_walk(case):
    """The decode call walking B pages a loop iteration, one update of
    the softmax state a block (interpret mode): the output of the walk of
    one page an iteration (``walk_block=1``: one update a page, the
    arithmetic the kernel had) to float32 rounding — the same float32
    products summed in another order, no operand narrower — and the dense
    oracle's within the file's limit; the done row's output is zero."""
    page, hd = _BW_PAGE, case["hd"]
    H, KV = 4, 2
    quant = case.get("quant", False)
    tables, meta, shared, window = _block_walk_tables(
        case["walk"], case.get("window", False), case.get("group", 0),
        case.get("tail", 0))
    assert pa.decode_walk_pages(page, KV, hd, 1 if quant else 2) == 8
    rng = np.random.default_rng(38)
    q = jnp.asarray(rng.standard_normal((8, H, hd)), jnp.float32)
    if quant:
        kp, vp = (jnp.asarray(rng.integers(
            -127, 128, (3, _BW_POOL, page, KV * hd)), jnp.int8)
            for _ in range(2))
        extra = dict(zip(("k_scale", "v_scale"), (jnp.asarray(rng.uniform(
            0.002, 0.02, (3, _BW_POOL, KV, page)), jnp.float32)
            for _ in range(2))))
    else:
        kp, vp = (jnp.asarray(rng.standard_normal(
            (3, _BW_POOL, page, KV * hd)), jnp.bfloat16) for _ in range(2))
        extra = {}
    args = (q, kp, vp, jnp.asarray(tables), jnp.asarray(meta), 1)
    kw = dict(tq=1, sliding_window=window,
              interpret=jax.devices()[0].platform != "tpu", **extra)
    if shared is not None:
        kw["shared"] = jnp.asarray(shared)
    got = np.asarray(pa.ragged_attend(*args, walk_block=case["block"], **kw))
    one = np.asarray(pa.ragged_attend(*args, walk_block=1, **kw))
    np.testing.assert_allclose(got, one, rtol=2e-6, atol=2e-6)
    ref = np.asarray(pa.ragged_attend_ref(
        *args, tq=1, sliding_window=window, **extra))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
    assert np.all(got[meta[2] == 0] == 0.0) and np.all(np.isfinite(got))


# --- every walk's first block is started ahead (ISSUE 45) ---------------------
#
# A walk's first block is started by whatever runs before it in the call: the
# program before, or in a leader's program its shared walk; the first walk
# of a call starts cold. A row's output must not depend on what ran before
# its walks: in a call where only that row (with a shared walk: its group)
# has a query, its walks start cold, through the same code, and give the
# same bits. A case's rows: (run, common, own, live) as ``SHARED_CASES``
# has them, ``own`` pages of the row's own with its last query in the last
# one, ``front`` more pages of its own in front of them (the window cases);
# the walk table is made by hand, so a group of any length forms.

_AH_PAGE, _AH_POOL = 8, 256


def _ahead_cases():
    cases = {}
    for B in (2, 4, 8):
        for n in sorted({1, B - 1, B, B + 1, 3 * B}):
            # each walk of n pages runs behind walks of other lengths
            cases[f"b{B}-walks-of-{n}"] = dict(
                B=B, rows=[(None, 0, n, 1), (None, 0, 1, 1),
                           (None, 0, n, 1), (None, 0, B + 1, 1),
                           (None, 0, n, 1), (None, 0, 2 * B, 1)])
        for n in sorted({1, B, B + 1, 3 * B}):
            cases[f"b{B}-shared-walk-of-{n}"] = dict(
                B=B, rows=[(None, 0, 2, 1), ("a", n, 1, 1), ("a", n, B, 1),
                           (None, 0, 3, 1), ("a", n, 2, 1)])
    loners = [(None, 0, 3, 1), (None, 0, 5, 1), (None, 0, 2, 1)]
    pad = (None, 0, 0, 0)
    cases.update({
        "a-padding-row-first": dict(B=4, rows=[pad] + loners),
        "padding-rows-between-live-rows": dict(
            B=4, rows=[loners[0], pad, loners[1], pad, pad, loners[2]]),
        "a-padding-row-last": dict(B=4, rows=loners + [pad]),
        "a-done-row-with-pages-between-live-rows": dict(
            B=4, rows=[loners[0], (None, 0, 6, 0), loners[1]]),
        "a-member-with-no-own-pages": dict(
            B=4, rows=[("a", 5, 2, 1), ("a", 5, 0, 1), ("a", 5, 1, 1),
                       loners[0]]),
        "a-leader-with-no-own-pages": dict(
            B=4, rows=[loners[0], ("a", 5, 0, 1), ("a", 5, 2, 1),
                       loners[1]]),
        "a-leader-with-no-own-pages-last": dict(
            B=4, rows=[loners[0], ("a", 5, 0, 1), ("a", 5, 0, 1)]),
        "a-leader-that-is-done": dict(
            B=4, rows=[loners[0], ("a", 6, 1, 0), pad, ("a", 6, 2, 1),
                       ("a", 6, 5, 1)]),
        "a-group-that-is-done": dict(
            B=4, rows=[loners[0], ("a", 6, 1, 0), ("a", 6, 2, 0),
                       loners[1]]),
        "two-groups-in-one-call": dict(
            B=4, rows=[("a", 6, 1, 1), ("b", 9, 2, 1), ("a", 6, 5, 1), pad,
                       loners[0], ("b", 9, 1, 1), ("a", 6, 2, 1)]),
        "two-groups-b2": dict(
            B=2, rows=[("a", 3, 1, 1), ("b", 4, 2, 1), ("a", 3, 3, 1),
                       ("b", 4, 1, 1)]),
        "int8": dict(
            B=4, quant=True,
            rows=[("a", 5, 1, 1), loners[1], pad, ("a", 5, 6, 1),
                  loners[0]]),
        "int8-b8": dict(
            B=8, quant=True, rows=[loners[1], (None, 0, 9, 1), pad,
                                   (None, 0, 8, 1)]),
    })
    for B in (2, 8):
        # a window of a block and three tokens: a walk's first page
        # differs by row, and nothing is shared whatever the table says
        cases[f"window-b{B}"] = dict(
            B=B, window=B * _AH_PAGE + 3, front=[0, 2, 1, 0, 3, 1],
            rows=[(None, 0, 3, 1), (None, 0, 1, 1), (None, 0, B + 1, 1),
                  pad, (None, 0, 2 * B, 1), (None, 0, 2, 1)])
        cases[f"window-b{B}-no-table"] = dict(
            cases[f"window-b{B}"], table=False)
    cases["no-table"] = dict(B=4, table=False, rows=loners + [pad] + loners)
    return cases


AHEAD_CASES = _ahead_cases()


def _ahead_tables(rows, front):
    """(tables, block meta, walk table, groups) of a case; row r's table
    holds its run's common pages, then ``front[r]`` + ``own`` of its
    own."""
    page, R = _AH_PAGE, len(rows)
    runs, nxt, tabs = {}, 1, []
    for (run, common, own, _), f in zip(rows, front):
        if run is not None and run not in runs:
            runs[run] = list(range(nxt, nxt + common))
            nxt += common
        tabs.append((runs[run] if run else [])
                    + list(range(nxt, nxt + f + own)))
        nxt += f + own
    assert nxt <= _AH_POOL
    tables = np.zeros((R, max(map(len, tabs)) + 1), np.int32)
    for r, t in enumerate(tabs):
        tables[r, :len(t)] = t
    # a row's new token is the (3 + r)-th of its last page; with no page
    # of its own it has just filled its last shared one
    kv_len = np.asarray([len(t) * page - (page - 3 - r % 4 if row[2] else 0)
                         for r, (t, row) in enumerate(zip(tabs, rows))],
                        np.int32)
    nq = np.asarray([row[3] for row in rows], np.int32)
    meta = np.stack([kv_len, np.maximum(kv_len - 1, 0), nq,
                     np.arange(R, dtype=np.int32)])
    shared = np.zeros((2 + pa.SHARED_ROWS, R), np.int32)
    shared[2:] = np.arange(R)
    groups = {}
    for r, row in enumerate(rows):
        if row[0] is not None:
            groups.setdefault(row[0], []).append(r)
    for members in groups.values():
        shared[0, members] = rows[members[0]][1]
        shared[1, members[0]] = 1
        shared[2:, members[0]] = (members + members[:1] * 8)[:8]
    return tables, meta, shared, list(groups.values())


@pytest.mark.parametrize("case", AHEAD_CASES.values(), ids=AHEAD_CASES)
def test_a_walk_started_ahead_gives_what_it_gave_started_cold(case):
    """The decode call (interpret mode), every walk's first block started
    by the walk before it: each row's output is, bit for bit, its output in
    a call where only it — with a shared walk, only its group — has a
    query, so that nothing runs before its walks and they start cold as
    every walk once did; the whole is the dense oracle's within the file's
    limit, a row with no query gets zeros, and nothing is left that is not
    finite (a block attended before its copies, or in the wrong half of
    the scratch, reads what another walk left there)."""
    page, hd, H, KV = _AH_PAGE, 128, 4, 2
    rows, quant = case["rows"], case.get("quant", False)
    front = case.get("front", [0] * len(rows))
    tables, meta, shared, groups = _ahead_tables(rows, front)
    window = case.get("window")     # tokens: a walk begins where it does
    R = len(rows)
    rng = np.random.default_rng(45)
    q = jnp.asarray(rng.standard_normal((R, H, hd)), jnp.float32)
    if quant:
        kp, vp = (jnp.asarray(rng.integers(
            -127, 128, (2, _AH_POOL, page, KV * hd)), jnp.int8)
            for _ in range(2))
        extra = dict(zip(("k_scale", "v_scale"), (jnp.asarray(rng.uniform(
            0.002, 0.02, (2, _AH_POOL, KV, page)), jnp.float32)
            for _ in range(2))))
    else:
        kp, vp = (jnp.asarray(rng.standard_normal(
            (2, _AH_POOL, page, KV * hd)), jnp.bfloat16) for _ in range(2))
        extra = {}
    kw = dict(tq=1, sliding_window=window, walk_block=case["B"],
              interpret=jax.devices()[0].platform != "tpu", **extra)
    if case.get("table", True):
        kw["shared"] = jnp.asarray(shared)

    def call(live):
        m = meta.copy()
        m[2] = np.where(live, m[2], 0)
        return np.asarray(pa.ragged_attend(
            q, kp, vp, jnp.asarray(tables), jnp.asarray(m), 1, **kw))

    full = call(np.ones((R,), bool))
    ref = np.asarray(pa.ragged_attend_ref(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(meta), 1, tq=1,
        sliding_window=window, **extra))
    np.testing.assert_allclose(full, ref, rtol=2e-5, atol=2e-5)
    assert np.all(full[meta[2] == 0] == 0.0) and np.all(np.isfinite(full))
    sharing = case.get("table", True) and not window
    alone = [g for g in groups if sharing] + [
        [r] for r in range(R) if not (sharing and rows[r][0])]
    for live in alone:
        if not meta[2, live].any():
            continue
        solo = call(np.isin(np.arange(R), live))
        assert np.array_equal(solo[live], full[live]), live
        assert not solo[np.setdiff1d(np.arange(R), live)].any()


def test_decode_walks_counts_a_calls_walks_and_those_started_ahead():
    """``attn_walks`` / ``attn_walks_started_ahead`` are counted from the
    tables as the other tick arguments are. Enumerated plainly here: a
    decode step is one call; a row that runs the step walks its own pages
    if it has any behind its shared ones, a group's leader walks the
    common pages first in every step one of its members runs, and every
    walk of a call but its first is started by the one before it."""
    page = 128
    #       resident tokens, decode forwards, leading pages a walk covers
    rows = [(1000, 3, 0), (9 * page - 1, 2, 6), (6 * page + 5, 4, 6),
            (17 * page + 60, 5, 0), (6 * page, 0, 0)]
    ctx, fwd, skip = (np.asarray(c, np.int64) for c in zip(*rows))
    shared = np.zeros((2 + pa.SHARED_ROWS, 5), np.int32)
    shared[2:] = np.arange(5)
    shared[0, [1, 2]], shared[1, 1] = 6, 1
    shared[2:, 1] = [1, 2] + [1] * 6
    walks = ahead = 0
    for step in range(1, fwd.max() + 1):
        call = []
        for r in range(5):
            if r == 1 and (fwd[[1, 2]] >= step).any():
                call.append(("shared", 6))
            if fwd[r] >= step and -(-(ctx[r] + step) // page) > skip[r]:
                call.append((r, -(-(ctx[r] + step) // page) - skip[r]))
        walks += len(call)
        ahead += len(call[1:])
    steps = np.arange(1, fwd.max() + 1)
    seen = ctx[:, None] + steps
    decode = np.stack([seen, seen - 1, steps <= fwd[:, None]])
    assert pa.decode_walks(decode, page, skip=skip[:, None],
                           shared=shared) == (walks, ahead)
    # steps 1-2: four rows and the group's walk; 3: three rows + it; 4:
    # rows 2, 3 + it; 5: row 3 alone, whose walk starts cold
    assert (walks, ahead) == (5 + 5 + 4 + 3 + 1, 4 + 4 + 3 + 2 + 0)
    # with no table a step's walks are its rows', under a window too
    assert pa.decode_walks(decode, page) == (14, 14 - 5)
    assert pa.decode_walks(decode, page, 4 * page, shared=shared) == (14, 9)
    # a loop that ran no step made no walk
    assert pa.decode_walks(decode[:, :, :0], page) == (0, 0)


# --- engine equality: unified vs gather -------------------------------------


def test_unified_matches_gather_greedy():
    """Temp-0 bit-equality for a mixed batch (sessioned + sessionless
    rows) across a fresh call and a resumed refinement round."""
    def run(eng):
        pa = enc("user: compare decode paths please")
        pb = enc("user: a sessionless neighbor row")
        r = eng.generate([pa, pb], temperature=0.0, max_new_tokens=10,
                         session_ids=["s", None])
        pa2 = pa + r[0].token_ids + enc(" go on")[1:]
        r2 = eng.generate([pa2, pb], temperature=0.0, max_new_tokens=10,
                          session_ids=["s", None])
        return [x.token_ids for x in r + r2]

    got, want = run(make_engine()), run(_gather(make_engine()))
    assert got == want


def test_unified_matches_gather_constrained_json():
    """Grammar-constrained decode (action-enum JSON) through the unified
    kernel must be token- AND state-identical to the gather path."""
    def run(eng):
        p1 = enc("user: emit an action")
        p2 = enc("user: second row same grammar")
        r = eng.generate([p1, p2], temperature=0.0, max_new_tokens=20,
                         session_ids=["a", "b"],
                         constrain_json=[True, True],
                         action_enums=[("walk", "talk"), ("walk", "talk")])
        return [(x.token_ids, x.json_state) for x in r]

    got, want = run(make_engine()), run(_gather(make_engine()))
    assert got == want


def test_unified_matches_gather_speculative_verify():
    """verify_chunk — the speculative target side — through the unified
    kernel: identical verdict ids, probs, and cached-token counts."""
    def run(eng, need_probs):
        p = enc("user: verify me please with some context")
        r = eng.generate([p], temperature=0.0, max_new_tokens=6,
                         session_ids=["v"])[0]
        ctx = p + r.token_ids
        props = [5, 6, 7, 8]
        out = eng.verify_chunk([ctx + props], ["v"], [4],
                               need_probs=need_probs)[0]
        return r.token_ids, out["ids"], out["n_cached"], out["probs"]

    for need_probs in (False, True):
        t1, v1, c1, p1 = run(make_engine(), need_probs)
        t2, v2, c2, p2 = run(_gather(make_engine()), need_probs)
        assert (t1, v1, c1) == (t2, v2, c2)
        if need_probs:
            np.testing.assert_array_equal(p1, p2)   # one-hot at temp 0


def test_unified_matches_gather_constrained_verify():
    """Constrained verify: the in-device grammar walk over the window must
    apply the same masks on both paths (bit-equal verdicts)."""
    def run(eng):
        p = enc("user: act")
        r = eng.generate([p], temperature=0.0, max_new_tokens=8,
                         session_ids=["cv"], constrain_json=[True],
                         action_enums=[("walk", "talk")])[0]
        ctx = p + r.token_ids
        props = enc('{"a')[1:][:3]
        out = eng.verify_chunk([ctx + props], ["cv"], [3],
                               constrain_json=[True],
                               action_enums=[("walk", "talk")],
                               initial_json_state=[r.json_state])[0]
        return r.token_ids, out["ids"]

    assert run(make_engine()) == run(_gather(make_engine()))


def test_unified_windowed_resume_matches_fresh():
    """Sliding-window model through the unified kernel: a trimmed-session
    resume (nonzero kv position offset) must match a fresh full prefill
    — the window mask is buffer-relative inside the kernel."""
    import tests.test_paged_kv  # noqa: F401 — registers xla:tiny-window
    cfg = get_model_config("xla:tiny-window")
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    cached = GenerateEngine(cfg, params, ByteTokenizer(), max_seq=1024,
                            prompt_buckets=(64, 128, 256, 512))
    fresh = GenerateEngine(cfg, params, ByteTokenizer(), max_seq=1024,
                           prompt_buckets=(64, 128, 256, 512))
    p = enc("u: " + "window test " * 30)
    r1 = cached.generate([p], temperature=0.0, max_new_tokens=8,
                         session_ids=["w"])[0]
    assert cached.sessions.get("w").start_pos > 0
    p2 = p + r1.token_ids + enc(" continue")[1:]
    want = fresh.generate([p2], temperature=0.0, max_new_tokens=8)[0]
    got = cached.generate([p2], temperature=0.0, max_new_tokens=8,
                          session_ids=["w"])[0]
    assert got.token_ids == want.token_ids
    assert got.n_cached_tokens > 0


def test_unified_releases_temp_pages():
    """Sessionless rows borrow pool pages for the unified tick; every
    page must come back after the call."""
    eng = make_engine()
    p = enc("user: temp page bookkeeping")
    eng.generate([p], temperature=0.0, max_new_tokens=6,
                 session_ids=["a"])
    free0 = eng.sessions.free_pages()
    p2 = enc("user: another prompt entirely")
    eng.generate([p, p2], temperature=0.0, max_new_tokens=6,
                 session_ids=["a", None])
    assert eng.sessions.free_pages() == free0


# --- the rule of the paged path (generate.ragged_fallback) -------------------


def _decode_paths(eng) -> list:
    """Record which paged decode program each of ``eng``'s ticks runs."""
    ran = []
    for name in ("_step_paged_decode_ragged", "_step_paged_decode"):
        def step(*a, _step=getattr(eng, name), _name=name, **kw):
            ran.append("ragged" if _name.endswith("ragged") else "gather")
            return _step(*a, **kw)
        setattr(eng, name, step)
    return ran


def test_every_platform_takes_the_ragged_path(tmp_path, monkeypatch):
    """A fresh dense engine, on whatever platform the tests run on and
    with nothing in its environment, serves sessions through the ragged
    programs: every CompileRegistry key is the ragged program identity.
    The operator-supplied gate file is gone with the choice it made:
    whatever the rehearsal configuration still exports (the variable
    that once named the file) pointed at a file that says OFF changes
    nothing, because nothing reads it."""
    import json
    import os

    def keys():
        eng = make_engine()
        p = enc("user: which path serves me")
        r = eng.generate([p, enc("user: a sessionless neighbour")],
                         temperature=0.0, max_new_tokens=6,
                         session_ids=["s", None])
        eng.generate([p + r[0].token_ids + enc(" and again")[1:]],
                     temperature=0.0, max_new_tokens=6, session_ids=["s"])
        # the snapshot lists the dearest compile first: an order that
        # follows the machine's load and the compile cache, not the path
        return sorted(e["shape"] for e in eng.compiles.snapshot()["shapes"])

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark/configs/tiny-l2.json")) as f:
        exported = json.load(f).get("env", {})
    for var in exported:
        monkeypatch.delenv(var, raising=False)
    bare = keys()
    assert bare and all(k.startswith("ragged") for k in bare), bare
    off = tmp_path / "gates.json"
    off.write_text(json.dumps({
        "decode_min_resident": None, "prefill_min_resident": None,
        "prefill_max_chunk": 1024, "unified_min_resident": None,
        "device_kind": getattr(jax.devices()[0], "device_kind", "")}))
    for var in exported:
        monkeypatch.setenv(var, str(off))
    assert keys() == bare


LONG = dict(max_seq=1024, prompt_buckets=(64, 128, 256, 512))


def _outgrows_the_pool(eng):
    """Tick 2 resumes session "a" with a suffix the pool cannot hold even
    after eviction: the row reuses its prefix, its store is declined."""
    p = enc("user: a session that will outgrow the pool")
    r1 = eng.generate([p], temperature=0.0, max_new_tokens=4,
                      session_ids=["a"])[0]
    big = p + r1.token_ids + enc(" and then " + "x" * 400)[1:]
    r2 = eng.generate([big], temperature=0.0, max_new_tokens=4,
                      session_ids=["a"])[0]
    assert r2.n_cached_tokens > 0
    r3 = eng.generate([p + r1.token_ids + enc(" go on")[1:]],
                      temperature=0.0, max_new_tokens=4,
                      session_ids=["a"])[0]
    return [r1, r2, r3]


def _diverges_inside_a_shared_page(eng):
    """Tick 2 diverges session "a" in the middle of its second page,
    which the radix prefix cache holds too: the boundary page is swapped
    for a fresh one (copy-on-write) with its reused head unwritten."""
    pa = enc("system: " + "policy rules apply here. " * 12
             + "user: task alpha")                      # > 2 pages
    r1 = eng.generate([pa], temperature=0.0, max_new_tokens=8,
                      session_ids=["a"])[0]
    div = pa[:150] + enc("user: a different continuation")[1:]
    r2 = eng.generate([div], temperature=0.0, max_new_tokens=8,
                      session_ids=["a"])[0]
    r3 = eng.generate([div + r2.token_ids + enc(" next")[1:]],
                      temperature=0.0, max_new_tokens=8,
                      session_ids=["a"])[0]
    assert r3.n_cached_tokens >= len(div)    # resumes on the swapped page
    return [r1, r2, r3]


def _neighbour_finds_no_scratch(eng):
    """Tick 2 carries a sessionless row longer than the free list: the
    one reason found only by trying (no page for its temporaries)."""
    p = enc("user: a short resident session")
    r1 = eng.generate([p], temperature=0.0, max_new_tokens=4,
                      session_ids=["a"])[0]
    again = p + r1.token_ids + enc(" go on")[1:]
    r2 = eng.generate([again, enc("x" * 400)], temperature=0.0,
                      max_new_tokens=4, session_ids=["a", None])
    r3 = eng.generate([again + r2[0].token_ids + enc(" more")[1:]],
                      temperature=0.0, max_new_tokens=4,
                      session_ids=["a"])[0]
    return [r1, *r2, r3]


@pytest.mark.parametrize("drive,pool_tokens", [
    (_outgrows_the_pool, 2 * PAGE),
    (_diverges_inside_a_shared_page, None),
    (_neighbour_finds_no_scratch, 2 * PAGE),
], ids=["declined_store", "swapped_boundary_page", "no_temporary_page"])
def test_a_tick_falls_back_for_a_reason_it_can_observe(drive, pool_tokens):
    """Each condition of ``ragged_fallback`` in turn routes ONE tick, the
    second of three, to the gather programs: the tokens are those of an
    engine no condition can arise in (room to spare, no shared pages) on
    the same prompts, which serves all three ticks ragged; the tick after
    is ragged again; and no page leaks."""
    eng, ref = make_engine(**LONG), make_engine(**LONG)
    if pool_tokens:
        eng.sessions.__init__(max_tokens=pool_tokens)
    ref.prefix_sharing = False
    baseline = eng.sessions.free_pages()
    ran, ran_ref = _decode_paths(eng), _decode_paths(ref)
    got, want = drive(eng), drive(ref)
    assert ran == ["ragged", "gather", "ragged"]
    assert ran_ref == ["ragged"] * 3
    assert [r.token_ids for r in got] == [r.token_ids for r in want]
    eng.drop_session("a")
    with eng.sessions.lock:
        eng.sessions.prefix_cache.clear()
    assert eng.sessions.free_pages() == baseline


def test_engine_builds_only_programs_it_can_reach():
    """The step programs of a plain single-device engine are this list:
    three ragged, the four gather programs a tick falls back to
    (``ragged_fallback``), and the dense-cache pair of a sessionless
    call. A further family of paged programs cannot grow back unseen."""
    eng = make_engine()
    built = {k for k, v in vars(eng).items()
             if k.startswith("_step_") and v is not None}
    assert built == {
        "_step_paged_ragged", "_step_paged_decode_ragged",
        "_step_paged_ragged_verify",
        "_step_paged_prefill", "_step_paged_decode", "_step_paged_verify",
        "_step_scatter_prompt",
        "_step_prefill", "_step_decode"}


# --- padding telemetry ------------------------------------------------------


def test_padding_telemetry_quantifies_raggedness():
    """quoracle_sched_{real,padded}_tokens_total: both paths count the
    same real tokens; the unified path's padded slots are bounded by the
    per-row tq round-up (strictly fewer than the [B·T] rectangle for
    ragged traffic)."""
    from quoracle_tpu.infra.telemetry import (
        SCHED_PADDED_TOKENS_TOTAL, SCHED_REAL_TOKENS_TOTAL,
    )
    prompts = [enc("user: short"), enc("user: a much longer neighbor "
                                       "row that pads the bucket " * 3)]

    def run(eng):
        name = eng.cfg.name
        r0 = SCHED_REAL_TOKENS_TOTAL.value(model=name)
        p0 = SCHED_PADDED_TOKENS_TOTAL.value(model=name)
        eng.generate(prompts, temperature=0.0, max_new_tokens=4,
                     session_ids=["x", "y"])
        return (SCHED_REAL_TOKENS_TOTAL.value(model=name) - r0,
                SCHED_PADDED_TOKENS_TOTAL.value(model=name) - p0)

    real_u, padded_u = run(make_engine())
    real_g, padded_g = run(_gather(make_engine()))
    assert real_u == real_g == sum(len(p) for p in prompts)
    assert padded_u >= real_u and padded_g >= real_g
    assert padded_u < padded_g          # raggedness reclaimed padding
    stats = make_engine().padding_stats()
    assert stats["ticks"] == 0 and stats["waste_ratio"] is None


# --- compile-count collapse --------------------------------------------------


def _mixed_traffic():
    """50 ticks of mixed-shape traffic: batch sizes 1-5, short interactive
    rows next to long agent rows, fresh sessions each tick (dropped after
    — shapes, not capacity, are under test)."""
    base = ("user: tell me a thing",
            "agent: a considerably longer preamble with lots of words "
            "that lands this row in a larger prompt bucket " * 2,
            "user: mid sized request with some extra words",
            "user: tiny",
            "agent: another long row " * 6)
    ticks = []
    for t in range(50):
        nrows = 1 + t % 5
        ticks.append([enc(base[(t + j) % 5] + f" t{t}")
                      for j in range(nrows)])
    return ticks


def test_compile_collapse_vs_bucketed_baseline():
    """The acceptance gate (ISSUE 8): 50 mixed-shape ticks through the
    unified kernel compile ≤ RAGGED_PROGRAM_BOUND CompileRegistry keys —
    and strictly fewer than the bucketed gather baseline compiles for
    identical traffic (batch-bucket × prompt-bucket matrix collapsed to
    token-budget buckets)."""
    ticks = _mixed_traffic()

    def run(eng):
        for t, prompts in enumerate(ticks):
            sids = [f"t{t}-{j}" for j in range(len(prompts))]
            eng.generate(prompts, temperature=0.0, max_new_tokens=4,
                         session_ids=sids)
            for s in sids:
                eng.drop_session(s)
        return eng.compiles

    uni = run(make_engine())
    gat = run(_gather(make_engine()))
    assert uni.misses <= RAGGED_PROGRAM_BOUND, uni.snapshot()
    assert uni.misses < gat.misses, (uni.snapshot(), gat.snapshot())
    # every unified key is the ragged program identity, not a [B, T] shape
    assert all(e["shape"].startswith("ragged")
               for e in uni.snapshot()["shapes"])


# --- the pool in place (ISSUE 25): a plain per-layer reference --------------
#
# forward_hidden_ragged carries the WHOLE pool, stored [L, n_pages, page,
# KV·hd], through its layer scan and writes each layer's fresh rows at a
# whole-pool index; decode_ragged carries it through its loop. The
# reference below does none of that: one row at a time, one layer at a
# time, a pool it indexes as [layer][page][slot][kv-head], dense attention
# over the row's own tokens.

from quoracle_tpu.models import transformer as tr          # noqa: E402
from quoracle_tpu.models.config import ModelConfig         # noqa: E402
from quoracle_tpu.models.generate import decode_ragged     # noqa: E402
from quoracle_tpu.models.quant import kv_dequant, kv_quant  # noqa: E402

PG = 8          # tokens a page in these tests


def _tiny(n_layers=2, n_kv_heads=2, window=None):
    return ModelConfig(name=f"pool-l{n_layers}-kv{n_kv_heads}-w{window}",
                       vocab_size=97, dim=32, n_layers=n_layers, n_heads=4,
                       n_kv_heads=n_kv_heads, ffn_dim=64, head_dim=16,
                       sliding_window=window)


class _PlainPool:
    """[L, n_pages, PG, KV, hd] float32, or int8 with scales
    [L, n_pages, KV, PG]: the 5-D view of what the engine stores."""

    def __init__(self, cfg, n_pages, rng, quant):
        shape = (cfg.n_layers, n_pages, PG, cfg.n_kv_heads, cfg.head_dim)
        self.quant = quant
        if quant:
            self.k, self.ks = map(np.array, kv_quant(
                jnp.asarray(rng.standard_normal(shape), jnp.float32)))
            self.v, self.vs = map(np.array, kv_quant(
                jnp.asarray(rng.standard_normal(shape), jnp.float32)))
            self.ks = self.ks.transpose(0, 1, 3, 2).copy()
            self.vs = self.vs.transpose(0, 1, 3, 2).copy()
        else:
            self.k = rng.standard_normal(shape).astype(np.float32)
            self.v = rng.standard_normal(shape).astype(np.float32)
            self.ks = self.vs = None

    def stored(self):
        """The engine's arrays: (k, v, k_scale, v_scale), lane-flat."""
        flat = self.k.shape[:3] + (-1,)
        s = (None, None) if not self.quant else (jnp.asarray(self.ks),
                                                 jnp.asarray(self.vs))
        return (jnp.asarray(self.k.reshape(flat)),
                jnp.asarray(self.v.reshape(flat))) + s

    def write(self, layer, page, slot, k, v):
        """One token's K and V ([KV, hd]) into its slot."""
        for pool, scales, x in ((self.k, self.ks, k), (self.v, self.vs, v)):
            if self.quant:
                q, s = kv_quant(x)
                pool[layer, page, slot] = np.asarray(q)
                scales[layer, page, :, slot] = np.asarray(s)
            else:
                pool[layer, page, slot] = np.asarray(x)

    def read(self, layer, pages):
        """A row's K and V over its page list: [len(pages)·PG, KV, hd]."""
        out = []
        for pool, scales in ((self.k, self.ks), (self.v, self.vs)):
            x = pool[layer, pages]                    # [n, PG, KV, hd]
            if self.quant:
                x = np.asarray(kv_dequant(
                    jnp.asarray(x),
                    jnp.asarray(scales[layer, pages].transpose(0, 2, 1))))
            out.append(x.reshape(-1, *x.shape[2:]).astype(np.float32))
        return out


def _plain_row_forward(params, cfg, pool, table, start, tokens):
    """One row's ``tokens`` at buffer positions start.. through every
    layer against ``pool`` (written as it goes); the last token's logits."""
    T = len(tokens)
    pos = start + np.arange(T)
    positions = jnp.asarray(pos, jnp.int32)[None]
    x = tr._embed(params, cfg, jnp.asarray(tokens, jnp.int32)[None])
    G = cfg.n_heads // cfg.n_kv_heads
    for layer in range(cfg.n_layers):
        p = jax.tree.map(lambda a: a[layer], params["layers"])
        q, k, v = tr._qkv(x, p, cfg, 1, T, positions)
        for t in range(T):
            pool.write(layer, table[pos[t] // PG], pos[t] % PG,
                       k[0, t], v[0, t])
        ks, vs = pool.read(layer, table)
        s_idx = np.arange(ks.shape[0])
        mask = s_idx[None, :] <= pos[:, None]
        if cfg.sliding_window is not None:
            mask &= pos[:, None] - s_idx[None, :] < cfg.sliding_window
        qf = np.asarray(q[0], np.float32) * cfg.head_dim ** -0.5
        scores = np.einsum("thd,shd->hts", qf, np.repeat(ks, G, axis=1))
        scores = np.where(mask[None], scores, -1e30)
        prob = np.exp(scores - scores.max(-1, keepdims=True))
        prob = np.where(mask[None], prob, 0.0)
        prob /= prob.sum(-1, keepdims=True)
        attn = np.einsum("hts,shd->thd", prob, np.repeat(vs, G, axis=1))
        x = tr._attn_out(x, jnp.asarray(attn, x.dtype)[None], p, cfg)
        x = tr._mlp(x, p, cfg)
    hidden = tr._final_norm(x, params, cfg)
    return np.asarray(tr.project_logits(params, cfg, hidden[:, -1:])[0, 0])


def _flat_tick(rows, tq, n_tok):
    """The token-major layout ``_run_unified`` builds, for rows of
    (table, resident tokens, chunk tokens)."""
    NB = sum(-(-len(c) // tq) for _, _, c in rows)
    Tp, maxp = NB * tq, max(len(t) for t, _, _ in rows)
    tok = np.zeros((Tp,), np.int32)
    posn = np.zeros((Tp,), np.int32)
    dst = np.full((Tp,), n_tok, np.int32)        # padding slots drop
    meta = np.zeros((4, NB), np.int32)
    tables = np.zeros((len(rows), maxp), np.int32)
    last = np.zeros((len(rows),), np.int32)
    cur = 0
    for r, (table, pre, chunk) in enumerate(rows):
        s = len(chunk)
        nb = -(-s // tq)
        p = pre + np.arange(s)
        tok[cur:cur + s] = chunk
        posn[cur:cur + s] = p
        dst[cur:cur + s] = np.asarray(table)[p // PG] * PG + p % PG
        blk = cur // tq + np.arange(nb)
        meta[0, blk], meta[3, blk] = pre + s, r
        meta[1, blk] = pre + np.arange(nb) * tq
        meta[2, blk] = np.minimum(tq, s - np.arange(nb) * tq)
        tables[r, :len(table)] = table
        last[r] = cur + s - 1
        cur += nb * tq
    lens = np.asarray([pre + len(c) for _, pre, c in rows], np.int32)
    return tok, posn, dst, meta, tables, last, lens


def _stored_view(arrs, cfg):
    """(k, v[, k_scale, v_scale]) as stored → numpy, K and V 5-D."""
    k, v, *scales = arrs
    five = k.shape[:3] + (cfg.n_kv_heads, cfg.head_dim)
    return [np.asarray(k).reshape(five), np.asarray(v).reshape(five)] \
        + [np.asarray(s) for s in scales if s is not None]


def _assert_pool(got, pool, quant):
    want = [pool.k, pool.v] + ([pool.ks, pool.vs] if quant else [])
    for g, w in zip(got, want, strict=True):
        if quant and g.dtype == np.int8:
            # a value on a rounding edge may land one level apart between
            # the flattened batch's matmul and the row's own
            assert np.abs(g.astype(np.int32) - w).max() <= 1
            assert (g != w).mean() < 1e-3
        else:
            np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("interpret", [None, True, "tiles"],
                         ids=["xla", "kernel", "tile-kernel"])
@pytest.mark.parametrize("case", [
    dict(), dict(window=6), dict(n_kv_heads=4), dict(quant=True),
    dict(share=True),
], ids=["kv2", "kv2-window6", "kv4", "kv2-int8", "kv2-shared-walk"])
def test_pool_in_place_matches_plain_per_layer_reference(case, interpret):
    """``forward_hidden_ragged`` then ``decode_ragged`` — the pool a scan
    carry, then a loop carry, written at whole-pool indices and read by
    layer index — against the plain reference: the same greedy tokens and
    the same pool, every page of every layer. ``tile-kernel``: the chunk
    forward's attention walks two blocks a tile (the engine's call).
    ``shared-walk``: two of the rows begin with the same pages, and the
    decode loop is told so (``shared_walks``), as the engine tells it."""
    from quoracle_tpu.ops.paged_attention import ragged_tiles, shared_walks
    case_tiles, tiled = interpret == "tiles", {}
    interpret = None if interpret is None else True
    quant = case.get("quant", False)
    cfg = _tiny(3, case.get("n_kv_heads", 2), case.get("window"))
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    rng = np.random.default_rng(7)
    n_pages, tq, max_new = 9, 4, 5
    pool = _PlainPool(cfg, n_pages, rng, quant)
    stored = pool.stored()
    # (page table, resident tokens, chunk): a fresh row, a resumed row
    # whose chunk crosses a page, a one-token continuation
    rows = [([3, 7], 0, rng.integers(1, 97, 7)),
            ([5, 1, 8], 9, rng.integers(1, 97, 6)),
            ([2, 6], 4, rng.integers(1, 97, 1))]
    shared = None
    if case.get("share"):
        # rows 0 and 1 resume behind the same resident pages, row 1 from
        # their very end; the pool is larger by those pages
        common = list(range(9, 9 + pa.SHARED_MIN_PAGES))
        n_pages, held = 9 + len(common), len(common) * PG
        rows = [(common + [3, 7], held + 2, rng.integers(1, 97, 3)),
                (common + [5, 1], held, rng.integers(1, 97, 6)),
                ([2, 6], 4, rng.integers(1, 97, 1))]
        pool = _PlainPool(cfg, n_pages, rng, quant)
        stored = pool.stored()
    tok, posn, dst, meta, tables, last, lens0 = _flat_tick(
        rows, tq, n_pages * PG)
    if case.get("share"):
        shared = shared_walks(tables, lens0, PG)
        assert shared[0].tolist() == [len(common), len(common), 0]
        shared = jnp.asarray(shared)
    if case_tiles:
        tiled = dict(tile=2 * tq,
                     tiles=jnp.asarray(ragged_tiles(meta, tq, 2 * tq)))

    @jax.jit
    def tick(k, v, ks, vs):
        out = tr.forward_hidden_ragged(
            params, cfg, jnp.asarray(tok)[None], jnp.asarray(posn)[None],
            k, v, jnp.asarray(tables), jnp.asarray(meta), jnp.asarray(dst),
            tq=tq, interpret=interpret, k_scale=ks, v_scale=vs, **tiled)
        hidden, pools = out[0], out[1:]      # (k, v, k_scale, v_scale)
        first = tr.project_logits(params, cfg,
                                  hidden[0][last][:, None])[:, 0]
        R = len(rows)
        res = decode_ragged(
            params, cfg, pools[0], pools[1], jnp.asarray(tables),
            jnp.asarray(lens0), jnp.zeros((R,), jnp.int32),
            first, jax.random.PRNGKey(0), jnp.zeros((R,)), jnp.ones((R,)),
            max_new, -1, active=jnp.ones((R,), bool),
            row_limit=jnp.full((R,), max_new, jnp.int32),
            interpret=interpret, k_scale=pools[2], v_scale=pools[3],
            shared=shared)
        return res[0], res[1], res[2], res[3:7]

    out, n_emitted, lens, pools_out = tick(*stored)

    want = []
    for table, pre, chunk in rows:
        logits = _plain_row_forward(params, cfg, pool, table, pre,
                                    list(chunk))
        toks, at = [int(logits.argmax())], pre + len(chunk)
        for _ in range(max_new - 1):
            logits = _plain_row_forward(params, cfg, pool, table, at,
                                        [toks[-1]])
            toks.append(int(logits.argmax()))
            at += 1
        want.append(toks)
    assert np.asarray(out).tolist() == want
    assert np.asarray(n_emitted).tolist() == [max_new] * 3
    assert np.asarray(lens).tolist() == [
        pre + len(c) + max_new - 1 for _, pre, c in rows]
    _assert_pool(_stored_view(pools_out, cfg), pool, quant)


@pytest.mark.parametrize("interpret", [None, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("n_layers", [1, 2, 3])
def test_dropped_writes_stay_dropped_in_every_layer(n_layers, interpret):
    """The sentinel trap: a dropped write carries the index n_tok — out of
    range of ONE layer's pages, and the first slot of the NEXT layer's
    once the layer offset is added. A tick with padding slots, then a
    decode with a done row and a row at its page table's edge, must leave
    every page it does not own bit-identical in every layer — scratch
    page 0 included, where layer l's dropped write would land in layer
    l+1."""
    cfg = _tiny(n_layers)
    params = init_params(cfg, jax.random.PRNGKey(2), dtype=jnp.float32)
    rng = np.random.default_rng(11)
    n_pages, tq, max_new = 8, 4, 4
    pool = _PlainPool(cfg, n_pages, rng, False)
    k0, v0, _, _ = pool.stored()
    # row 0 writes (pages 3 and 5); row 1 will be done from the start
    # (page 6); row 2 fills its one-page table to the edge (page 2)
    rows = [([3, 5], 5, rng.integers(1, 97, 2)),
            ([6, 0], 2, rng.integers(1, 97, 1)),
            ([2, 0], PG - 3, rng.integers(1, 97, 3))]
    tok, posn, dst, meta, tables0, last, lens0 = _flat_tick(
        rows, tq, n_pages * PG)
    assert (dst == n_pages * PG).sum() == 6        # the padding slots

    @jax.jit
    def tick(k, v):
        hidden, k, v, *_ = tr.forward_hidden_ragged(
            params, cfg, jnp.asarray(tok)[None], jnp.asarray(posn)[None],
            k, v, jnp.asarray(tables0), jnp.asarray(meta),
            jnp.asarray(dst), tq=tq, interpret=interpret)
        first = tr.project_logits(params, cfg,
                                  hidden[0][last][:, None])[:, 0]
        # decode: row 0 runs on (table [3, 5]); row 1 inactive; row 2's
        # table is its one full page, so lens // page >= maxp at once
        outs = []
        for tbl, act in ((tables0, [True, False, False]),
                         (np.asarray([[2], [2], [2]], np.int32),
                          [False, False, True])):
            res = decode_ragged(
                params, cfg, k, v, jnp.asarray(tbl),
                jnp.asarray(lens0), jnp.zeros((3,), jnp.int32),
                first, jax.random.PRNGKey(0), jnp.zeros((3,)),
                jnp.ones((3,)), max_new, -1, active=jnp.asarray(act),
                row_limit=jnp.full((3,), max_new, jnp.int32),
                interpret=interpret)
            outs.append(res[1])
            k, v = res[3], res[4]
        return k, v, outs

    k1, v1, emitted = tick(k0, v0)
    assert np.asarray(emitted[0]).tolist() == [max_new, 0, 0]
    assert np.asarray(emitted[1]).tolist() == [0, 0, max_new]
    five = (n_layers, n_pages, PG, cfg.n_kv_heads, cfg.head_dim)
    # what the tick owns: row 0's slots 5..6 + its 3 decode steps (7..9),
    # row 1's slot 2, row 2's slots PG-3..PG-1 — in every layer
    owned = np.zeros((n_pages, PG), bool)
    owned[3, 5:8] = owned[5, 0:2] = True
    owned[6, 2] = True
    owned[2, PG - 3:] = True
    for before, after in ((k0, k1), (v0, v1)):
        before = np.asarray(before).reshape(five)
        after = np.asarray(after).reshape(five)
        changed = (before != after).any(axis=(3, 4))     # [L, pages, PG]
        for layer in range(n_layers):
            assert (changed[layer] & ~owned).sum() == 0, (
                layer, np.argwhere(changed[layer] & ~owned))
            assert changed[layer][owned].all(), layer
