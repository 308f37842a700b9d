"""The ragged kernel and its tile walk (ISSUE 8, ISSUE 30,
ops/paged_attention.py ragged_attend): the Pallas kernel (interpret mode
off-TPU) agrees with the dense gather oracle across geometries — GQA
groupings, page sizes, empty (inert) blocks, single-token rows, rows at
the sliding-window edge — walked a block at a time and a tile at a time,
and a tick notes what its programs streamed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quoracle_tpu.models.generate import RAGGED_TQ
from tests._ragged_cases import enc, make_engine

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
