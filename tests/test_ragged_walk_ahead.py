"""Every walk's first block is started ahead (ISSUE 45): a row's output
does not depend on what ran before its walks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quoracle_tpu.ops import paged_attention as pa

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
