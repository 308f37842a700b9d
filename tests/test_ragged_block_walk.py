"""The block walk (ISSUE 38): the decode kernels' walks of B pages a loop
iteration against the walk of one page an iteration."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quoracle_tpu.ops import paged_attention as pa

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
