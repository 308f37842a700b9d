"""The decode program's shared walk (ISSUE 32): rows whose tables begin
with the same pages walk them once, through their leader."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quoracle_tpu.models.generate import PAGE
from quoracle_tpu.ops import paged_attention as pa
from tests._ragged_cases import enc, make_engine

# --- the decode program's shared walk (ISSUE 32) ----------------------------
#
# Decode rows: (run, common, own, tail, live) — the row's table is the first
# ``common`` pages of shared run ``run`` (a run's ids are the same for every
# row that names it), then ``own`` pages of its own, the last holding
# ``tail`` tokens; ``live`` 0 is a row that is done (nq = 0).

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
