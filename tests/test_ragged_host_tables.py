"""The host-side tables the ragged kernels are driven by
(ops/paged_attention.py ``ragged_tiles``, ``shared_walks``,
``decode_walks``): each is a function of the tick's block and page tables
alone, enumerated plainly here."""

import numpy as np
import pytest

from quoracle_tpu.models.generate import RAGGED_TQ
from quoracle_tpu.ops import paged_attention as pa

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
