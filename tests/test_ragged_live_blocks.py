"""The dense chunk forward's live blocks (ISSUE 49): a tick of two blocks
of ``LIVE_BLOCK`` slots or more runs a layer's per-token work over the
blocks that hold a token; what every real slot and both pools receive is
what the whole-bucket form gives them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quoracle_tpu.models import transformer as tr
from quoracle_tpu.models.config import ModelConfig
from quoracle_tpu.models.quant import kv_quant
from quoracle_tpu.models.transformer import init_params

PG, TQ = 8, 8       # tokens a page, tokens a block of the kernel's table
BLK = 32            # LIVE_BLOCK in these tests: a bucket of 128 is four


CASES = {
    # a window, no biases, 4 query heads on 2 kv heads
    "mistral-like": dict(sliding_window=24),
    # q/k/v biases, 2 kv heads under 8 query heads, the head tied
    "qwen-like": dict(attn_bias=True, n_heads=8, tie_embeddings=True),
    "int8-pages": dict(quant=True),
}


def _model(case):
    kw = dict(CASES[case])
    quant = kw.pop("quant", False)
    kw.setdefault("n_heads", 4)
    cfg = ModelConfig(name=f"live-{case}", vocab_size=97, dim=32,
                      n_layers=3, n_kv_heads=2, ffn_dim=64, head_dim=16,
                      **kw)
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    if cfg.attn_bias:       # a random Qwen's biases are zero: give it some
        keys = iter(jax.random.split(jax.random.PRNGKey(4), 3))
        for name in ("bq", "bk", "bv"):
            b = params["layers"][name]
            params["layers"][name] = 0.1 * jax.random.normal(
                next(keys), b.shape, b.dtype)
    return cfg, params, quant


def _tick(Tp, real, n_pages, rng):
    """``_run_unified``'s layout of rows that fill ``real`` slots of a
    bucket of ``Tp``: a resumed row, then fresh ones; the last ends three
    tokens short of its block."""
    lens, left = [], real
    while left > 0:
        lens.append(min(48, left))
        left -= lens[-1]
    lens[-1] -= 3
    tok = rng.integers(1, 97, Tp).astype(np.int32)   # dead slots: garbage
    pos = np.zeros((Tp,), np.int32)
    dst = np.full((Tp,), n_pages * PG, np.int32)
    meta = np.zeros((4, Tp // TQ), np.int32)
    tables = np.zeros((8, 8), np.int32)
    cur, page = 0, 1
    for r, s in enumerate(lens):
        pre = 5 if r == 0 else 0
        nb = -(-s // TQ)
        n_pg = -(-(pre + s) // PG)
        tables[r, :n_pg] = page + np.arange(n_pg)
        page += n_pg
        p = pre + np.arange(s)
        pos[cur:cur + s] = p
        dst[cur:cur + s] = tables[r, p // PG] * PG + p % PG
        blk = cur // TQ + np.arange(nb)
        meta[0, blk], meta[3, blk] = pre + s, r
        meta[1, blk] = pre + np.arange(nb) * TQ
        meta[2, blk] = np.minimum(TQ, s - np.arange(nb) * TQ)
        cur += nb * TQ
    assert cur == real and page <= n_pages
    live = np.zeros((Tp,), bool)
    for b in np.flatnonzero(meta[2]):
        live[b * TQ:b * TQ + meta[2, b]] = True
    return tok, pos, dst, meta, tables, live


def _pools(cfg, n_pages, quant, rng):
    shape = (cfg.n_layers, n_pages, PG, cfg.n_kv_heads, cfg.head_dim)
    flat = shape[:3] + (-1,)
    k, v = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for _ in range(2))
    if not quant:
        return k.reshape(flat), v.reshape(flat), None, None
    (k, ks), (v, vs) = kv_quant(k), kv_quant(v)
    return (k.reshape(flat), v.reshape(flat), ks.transpose(0, 1, 3, 2),
            vs.transpose(0, 1, 3, 2))


def _forward(monkeypatch, blk, cfg, params, pools, tick):
    """(hidden [Tp, D], the four pools) of one chunk forward with
    ``LIVE_BLOCK`` = blk."""
    monkeypatch.setattr(tr, "LIVE_BLOCK", blk)
    tok, pos, dst, meta, tables, _ = tick
    k, v, ks, vs = pools

    @jax.jit
    def run(k, v, ks, vs, tok):
        return tr.forward_hidden_ragged(
            params, cfg, tok[None], jnp.asarray(pos)[None], k, v,
            jnp.asarray(tables), jnp.asarray(meta), jnp.asarray(dst),
            tq=TQ, k_scale=ks, v_scale=vs)[:5]

    hidden, *out = run(k, v, ks, vs, jnp.asarray(tok))
    return np.asarray(hidden[0]), [None if a is None else np.asarray(a)
                                   for a in out]


def _assert_close(got, want):
    """Equal to float32's rounding: the CPU's matmul of 32 rows sums in
    another order than its matmul of 128, so the two forms are not bit
    for bit the same program here (an int8 value on a rounding edge may
    land one level apart)."""
    if got.dtype == np.int8:
        assert np.abs(got.astype(np.int32) - want).max() <= 1
        assert (got != want).mean() < 1e-3
    else:
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("real", [24, BLK, BLK + 8, 4 * BLK],
                         ids=["one-live", "blk-exactly", "blk-plus-8",
                              "all-live"])
@pytest.mark.parametrize("case", CASES)
def test_live_blocks_give_every_real_slot_and_both_pools_the_same(
        monkeypatch, case, real):
    """At a bucket of four blocks the block form's hidden states on every
    real slot, and both pools whole (an int8 engine's scale pools too),
    are the whole-bucket form's; other token ids in the dead slots change
    neither by a bit."""
    cfg, params, quant = _model(case)
    rng = np.random.default_rng(11)
    Tp, n_pages = 4 * BLK, 24
    tick = _tick(Tp, real, n_pages, rng)
    live = tick[-1]
    pools = _pools(cfg, n_pages, quant, rng)
    want_h, want_p = _forward(monkeypatch, Tp, cfg, params, pools, tick)
    got_h, got_p = _forward(monkeypatch, BLK, cfg, params, pools, tick)
    assert live.sum() == real - 3
    assert (got_p[2] is not None) == (got_p[3] is not None) == quant
    _assert_close(got_h[live], want_h[live])
    for g, w in zip(got_p, want_p, strict=True):
        assert (g is None) == (w is None)
        if g is not None:
            _assert_close(g, w)
    other = tick[0].copy()
    other[~live] = rng.integers(1, 97, int((~live).sum()))
    again_h, again_p = _forward(monkeypatch, BLK, cfg, params, pools,
                                (other,) + tick[1:])
    np.testing.assert_array_equal(again_h[live], got_h[live])
    for g, w in zip(again_p, got_p, strict=True):
        if g is not None:
            np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("case", CASES)
def test_a_tick_under_two_blocks_lowers_to_the_whole_form(monkeypatch,
                                                          case):
    """The form follows the shape alone: a bucket under two blocks lowers
    with no loop but the layer scan (as many ``while`` operations as the
    whole-bucket form has), a bucket of two blocks with two more."""
    cfg, params, quant = _model(case)
    rng = np.random.default_rng(5)
    n_pages = 24
    pools = _pools(cfg, n_pages, quant, rng)

    def whiles(blk, Tp):
        monkeypatch.setattr(tr, "LIVE_BLOCK", blk)
        tok, pos, dst, meta, tables, _ = _tick(Tp, 24, n_pages, rng)
        text = jax.jit(lambda k, v, ks, vs: tr.forward_hidden_ragged(
            params, cfg, jnp.asarray(tok)[None], jnp.asarray(pos)[None],
            k, v, jnp.asarray(tables), jnp.asarray(meta), jnp.asarray(dst),
            tq=TQ, k_scale=ks, v_scale=vs)[:5]).lower(*pools).as_text()
        return text.count("stablehlo.while")

    whole = whiles(1 << 20, BLK)
    assert whole >= 1
    assert whiles(BLK, BLK) == whole                # one block: no loop
    assert whiles(BLK, 2 * BLK) == whole + 2        # two: before and behind
    assert whiles(1 << 20, 2 * BLK) == whole


# --- the counter: the slots whose per-token work ran -------------------------


@pytest.mark.parametrize("blk,bucket,filled,live", [
    (1024, 8192, 4608, 5120), (512, 8192, 4608, 4608),
    (1024, 16384, 8200, 9216), (1024, 16384, 16384, 16384),
    (1024, 1024, 72, 1024), (512, 1024, 72, 512), (1024, 64, 16, 64),
])
def test_live_token_slots_round_the_filled_slots_up_to_a_block(
        monkeypatch, blk, bucket, filled, live):
    monkeypatch.setattr(tr, "LIVE_BLOCK", blk)
    assert tr.live_token_slots(bucket, filled) == live
    assert tr.live_token_slots(bucket, filled, sharded=True) == bucket


def test_a_tick_notes_the_slots_its_forward_ran(monkeypatch):
    """``token_slots_live`` beside ``real_tokens`` / ``padded_tokens`` on
    the tick span, ``quoracle_sched_live_token_slots_total`` beside the
    two counters and ``padding_stats()["live_slot_share"]``: a tick of
    two blocks or more notes the blocks that hold a token, a shorter one
    its bucket; ``padded_tokens`` stays the bucket."""
    from quoracle_tpu.infra.telemetry import (
        SCHED_LIVE_TOKEN_SLOTS_TOTAL, SCHED_PADDED_TOKENS_TOTAL, tick_close,
        tick_open,
    )
    from tests._ragged_cases import make_engine
    monkeypatch.setattr(tr, "LIVE_BLOCK", 32)
    eng = make_engine(max_seq=512, prompt_buckets=(32, 64, 128, 256))
    name = eng.cfg.name

    def tick(lens, sid):
        live0 = SCHED_LIVE_TOKEN_SLOTS_TOTAL.value(model=name)
        pad0 = SCHED_PADDED_TOKENS_TOTAL.value(model=name)
        tick_open(name)
        try:
            eng.generate([list(range(3, 3 + n)) for n in lens],
                         temperature=0.0, max_new_tokens=1,
                         session_ids=[f"{sid}{i}" for i in range(len(lens))])
        finally:
            args = tick_close().args
        assert SCHED_LIVE_TOKEN_SLOTS_TOTAL.value(model=name) - live0 \
            == args["token_slots_live"]
        assert SCHED_PADDED_TOKENS_TOTAL.value(model=name) - pad0 \
            == args["padded_tokens"]
        return args

    # 40 + 24 + 9 tokens fill 40 + 24 + 16 = 80 slots of a bucket of 128:
    # three blocks of 32 hold a token
    args = tick([40, 24, 9], "a")
    assert (args["real_tokens"], args["padded_tokens"],
            args["token_slots_live"]) == (73, 128, 96)
    assert args["program"].startswith("raggedx128x")
    stats = eng.padding_stats()
    assert stats["live_slot_share"] == 0.75 and stats["padded_tokens"] == 128
    # under two blocks the whole bucket runs
    args = tick([20, 9], "b")
    assert (args["real_tokens"], args["padded_tokens"],
            args["token_slots_live"]) == (29, 64, 64)
    stats = eng.padding_stats()
    assert stats["live_slot_share"] == round((96 + 64) / (128 + 64), 4)
    assert stats["waste_ratio"] == round(1 - (73 + 29) / (128 + 64), 4)


@pytest.mark.parametrize("quantized", [
    {}, {"quantize_kv": True}, {"quantize_weights": True}],
    ids=["plain", "int8-pages", "int8-weights"])
def test_engine_serves_the_same_tokens_over_live_blocks(monkeypatch,
                                                        quantized):
    """Through the engine (layout, tile table, store-back, a resumed
    round): a tick of four blocks with two live serves the greedy tokens
    the whole-bucket form serves (int8 weights: the MLP's stacks are
    pairs of payload and scales, sliced inside a block's turn alike)."""
    from tests._ragged_cases import make_engine

    def run(blk):
        monkeypatch.setattr(tr, "LIVE_BLOCK", blk)
        eng = make_engine(max_seq=512, prompt_buckets=(32, 64, 128, 256),
                          **quantized)
        prompts = [list(range(3, 43)), list(range(50, 59))]
        first = eng.generate(prompts, temperature=0.0, max_new_tokens=6,
                             session_ids=["s", "t"])
        again = eng.generate(
            [prompts[0] + first[0].token_ids + list(range(60, 100)),
             prompts[1]], temperature=0.0, max_new_tokens=6,
            session_ids=["s", None])
        keys = sorted(k["shape"] for k in eng.compiles.snapshot()["shapes"])
        return [r.token_ids for r in first + again], keys

    got, keys = run(16)
    want, want_keys = run(1 << 20)
    assert got == want
    assert keys == want_keys and any(k.startswith("raggedx64x") for k in keys)
