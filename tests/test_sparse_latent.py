"""Learned sparse attention inside paged latent attention (ISSUE 31), at
toy sizes on the CPU with seeded random weights: the program against the
benchmark's plain reference (`benchmark/families/sparse_latent_moe.py`,
written apart from it), through BOTH pools — latent rows and index keys —
in prefill, decode, a resumed session and an adopted prefix; the selected
sets against the reference's; a short context against the same model with
the indexer off; the router's correction bias; the Pallas kernels in
interpret mode against their gather references.

Tolerances as in test_latent_moe.py: both sides float32 at "highest".
A selection that differed in one key would move a logit by far more.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import sparse_latent_moe as fam
from quoracle_tpu.models import transformer as tr
from quoracle_tpu.models.config import MoEConfig, get_model_config
from quoracle_tpu.ops import paged_attention as pa
from tests.test_latent_moe import (
    CHUNK_WALK_CASES, RAW as AXK1, SHARED_WALK_CASES, chunk_walk_case, f32,
    serves_the_parents_tokens, shared_walk_case,
)

TOL = 2e-4
PAGE = 128

# test_latent_moe's toy with the V3.2 additions: an indexer of 4 heads of
# 32 selecting 16 keys, the router's bias, 2 dense + 2 expert layers
RAW = {**AXK1, "name": "toy-v32", "family": "sparse_latent_moe",
       "model_type": "deepseek_v32", "topk_method": "noaux_tc",
       "first_k_dense_replace": 2, "num_hidden_layers": 4,
       "index_n_heads": 4, "index_head_dim": 32, "index_topk": 16}
SEED = 2 ** 31 + 31


@pytest.fixture(scope="module")
def toy():
    cfg = get_model_config(fam.register(RAW))
    params = tr.init_params(cfg, jax.random.PRNGKey(SEED), dtype=jnp.bfloat16)
    return cfg, params, fam.Reference(RAW, SEED)


def new_pools(cfg, n_pages=33):
    return tuple(jnp.zeros((cfg.n_layers, n_pages, PAGE, w), jnp.float32)
                 for w in cfg.kv_pools)


def flat_tick(cfg, params, pools, rows, n_pages=33, tq=8, budget=0):
    """One ragged forward of `rows` = [(tokens, prefix already resident)]
    (row r's pages: r*4 + 1 ..), padded to `budget` tokens; returns (the
    real tokens' hidden states in order, the two pools)."""
    toks, pos, dst, meta, take = [], [], [], [], []
    tables = np.zeros((8, 4), np.int32)
    for r, (t, pre) in enumerate(rows):
        tables[r] = r * 4 + 1 + np.arange(4)
        nb = -(-len(t) // tq)
        take += list(range(len(toks), len(toks) + len(t)))
        meta += [(pre + len(t), pre + b * tq, min(tq, len(t) - b * tq), r)
                 for b in range(nb)]
        p = pre + np.arange(len(t))
        pad = nb * tq - len(t)
        toks += list(t) + [0] * pad
        pos += list(p) + [0] * pad
        dst += list(tables[r][p // PAGE] * PAGE + p % PAGE) \
            + [n_pages * PAGE] * pad
    pad = max(0, budget - len(toks))
    toks += [0] * pad
    pos += [0] * pad
    dst += [n_pages * PAGE] * pad
    meta += [(0, 0, 0, 0)] * (pad // tq)
    with jax.default_matmul_precision("highest"):
        out = tr.forward_hidden_ragged(
            params, cfg, jnp.asarray(toks, jnp.int32)[None],
            jnp.asarray(pos, jnp.int32)[None], pools[0], pools[1],
            jnp.asarray(tables), jnp.asarray(np.array(meta).T, jnp.int32),
            jnp.asarray(dst, jnp.int32), tq=tq)
    return out[0][0][jnp.asarray(take)], (out[1], out[2])


def logits_of(cfg, params, hid):
    with jax.default_matmul_precision("highest"):
        return np.asarray(tr.project_logits(params, cfg, hid[None]))[0]


def test_both_sides_draw_the_same_bits(toy):
    cfg, params, ref = toy
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.n_params
    for mine, theirs in (("dense_layers", "dense"), ("layers", "experts")):
        for k, leaf in ref.w[theirs].items():
            assert leaf.dtype == params[mine][k].dtype, k
            assert bool(jnp.all(leaf == params[mine][k])), k
    assert params["layers"]["router_bias"].dtype == jnp.float32
    assert 0.003 < float(jnp.abs(params["layers"]["router_bias"]).mean()) \
        < 0.03
    # ... and the attention's five leaves are the indexer-less model's:
    # the new leaves come after them
    plain = tr.init_params(
        dataclasses.replace(cfg, indexer=None, moe=dataclasses.replace(
            cfg.moe, router_bias=False)), jax.random.PRNGKey(SEED))
    for k in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo"):
        assert bool(jnp.all(plain["layers"][k] == params["layers"][k])), k


def test_prefill_then_decode_through_both_pools(toy):
    """A prompt's chunk (190 tokens: every query past the 16th selects),
    then one token at a time (`decode_ragged`'s own step, a tq=1 block a
    row) reading latent rows AND index keys off the pages: the logits at
    every position are the reference's whole forward over the sequence."""
    cfg, params, ref = toy
    p32 = f32(params)
    toks = np.random.default_rng(1).integers(3, 512, 200).astype(np.int32)
    n0 = 190
    want = ref.logits(np.pad(toks, (0, 56)), np.arange(200))
    assert np.abs(want).max() > 1.0
    hid, pools = flat_tick(cfg, p32, new_pools(cfg), [(toks[:n0], 0)])
    assert np.abs(logits_of(cfg, p32, hid) - want[:n0]).max() < TOL
    assert float(jnp.abs(pools[1][:, 1]).max()) > 0.1    # index keys landed
    tables = jnp.asarray(np.array([[1, 2, 3, 4]] + [[0] * 4] * 7, np.int32))
    for t in range(n0, 200):
        meta = np.zeros((4, 8), np.int32)
        meta[:, 0] = (t + 1, t, 1, 0)
        meta[3] = np.arange(8)
        flat = np.full((8,), 33 * PAGE, np.int32)
        flat[0] = (1 + t // PAGE) * PAGE + t % PAGE
        cur, pos = np.zeros((2, 8), np.int32)
        cur[0], pos[0] = toks[t], t
        with jax.default_matmul_precision("highest"):
            hid, k, v, _, _, _ = tr.forward_hidden_ragged(
                p32, cfg, jnp.asarray(cur)[None], jnp.asarray(pos)[None],
                pools[0], pools[1], tables, jnp.asarray(meta),
                jnp.asarray(flat), tq=1)
            pools = (k, v)
            got = np.asarray(tr.project_logits(p32, cfg, hid))[0, 0]
        assert np.abs(got - want[t]).max() < TOL, t


def test_a_tick_longer_than_a_chunk_attends_chunk_by_chunk(toy,
                                                           monkeypatch):
    """A tick past `INDEX_CHUNK` tokens runs its attention in chunks of
    that many; the pools ride the inner scan. Two rows, one against a
    resident context, equal the same tick in one piece."""
    cfg, params, _ = toy
    p32 = f32(params)
    rng = np.random.default_rng(2)
    a, b = (rng.integers(3, 512, n).astype(np.int32) for n in (237, 60))
    _, pools = flat_tick(cfg, p32, new_pools(cfg), [(a[:100], 0)])
    rows = [(a[100:], 100), (b, 0)]
    whole, _ = flat_tick(cfg, p32, pools, rows, budget=256)
    alone, _ = flat_tick(cfg, p32, new_pools(cfg), [(a, 0)])
    assert np.abs(np.asarray(whole[:137] - alone[100:])).max() < 1e-5
    monkeypatch.setattr(tr, "INDEX_CHUNK", 64)
    chunked, _ = flat_tick(cfg, p32, pools, rows, budget=256)
    assert np.abs(np.asarray(chunked - whole)).max() < 1e-5


def layer_selection(cfg, p, x, pos0=0):
    """The program's selection for one layer's input x [T, D] (a single
    row prefilled whole): bool [T, T]."""
    T = x.shape[0]
    tq, nb = 8, -(-T // 8)
    with jax.default_matmul_precision("highest"):
        _, _, h, cq = tr._latent_qkv(x[None], p, cfg, jnp.arange(T)[None])
        qi, ki, w = tr._index_inputs(h, cq, p, cfg, jnp.arange(T)[None])
        pool = jnp.zeros((1, 3, PAGE, ki.shape[-1])).at[0, 1:3].set(
            jnp.pad(ki, ((0, 2 * PAGE - T), (0, 0))).reshape(2, PAGE, -1))
        meta = jnp.asarray(np.array(
            [(T, b * tq, min(tq, T - b * tq), 0) for b in range(nb)]).T,
            jnp.int32)
        pad = nb * tq - T
        scores = pa.index_scores_ref(
            jnp.pad(qi, ((0, pad), (0, 0), (0, 0))),
            jnp.pad(w, ((0, pad), (0, 0))), pool,
            jnp.asarray([[1, 2]], jnp.int32), meta, 0, tq=tq)
        sel = tr.select_keys(scores, meta, tq, cfg.indexer.topk)
    return np.asarray(sel[:T, :T] != 0)


def test_the_selected_sets_are_the_references(toy):
    """Layer by layer over the reference's own hidden states: the sets the
    program selects (scores from the index-key pool, the sort-free exact
    top-k) are the sets the reference's `lax.top_k` selects, every query
    keeps min(t + 1, 16) keys, and the choice is not the most recent 16."""
    cfg, params, ref = toy
    s = ref.s
    toks = np.random.default_rng(3).integers(3, 512, 256).astype(np.int32)
    x = fam._widen(ref.w["embed"][jnp.asarray(toks)])
    recent = np.tril(np.ones((256, 256), bool)) \
        & ~np.tril(np.ones((256, 256), bool), -16)
    for stack, mine, n, fn in (("dense", "dense_layers", 2, ref._dense),
                               ("experts", "layers", 2, ref._expert)):
        for l in range(n):
            with jax.default_matmul_precision("highest"):
                h = fam._rmsnorm(x, s["eps"])
                cq = fam._rmsnorm(h @ fam._at(ref.w[stack]["wq_a"], l),
                                  s["eps"])
                want = np.asarray(fam.selection(
                    s, *fam.index_parts(s, ref.w[stack], h, cq, l)))
            p = jax.tree.map(lambda a: a[l].astype(jnp.float32), {
                k: v for k, v in params[mine].items()
                if not k.startswith("we_")})
            got = layer_selection(cfg, p, x)
            assert (got == want).all(), (stack, l)
            assert (got.sum(-1) == np.minimum(np.arange(256) + 1, 16)).all()
            assert (got != recent)[100:].any(-1).mean() > 0.9
            x = fn(ref.w[stack], x, l, select=True)


def test_ties_go_to_the_lower_position_and_short_rows_keep_all():
    """`select_keys` by hand: equal scores (the ReLU gives exact zeros,
    and a negative head weight a -0.0), a row shorter than the selection,
    a padding query, garbage past what a query may see."""
    sc = np.full((8, 128), np.nan, np.float32)
    sc[0, :6] = [9, 5, 5, 5, 0, 5]          # three of four fives fit
    sc[1, :7] = [0, -0.0, 2, 0, -0.0, 0, 0]  # zeros tie, whatever the sign
    sc[2, :3] = [7, -1, 3]                  # 3 visible, 4 wanted: all stay
    sc[3, :9] = [-3, -1, -2, -1, -9, -1, -1, -8, -1]
    meta = jnp.asarray(np.pad([[6, 7, 3, 9], [5, 6, 2, 8], [1, 1, 1, 1],
                               [0, 1, 2, 3]], ((0, 0), (0, 4))), jnp.int32)
    sel = np.asarray(tr.select_keys(jnp.asarray(sc), meta, 1, 4))
    assert sel.shape == (8, 128) and sel[4:].sum() == 0
    assert np.flatnonzero(sel[0]).tolist() == [0, 1, 2, 3]
    assert np.flatnonzero(sel[1]).tolist() == [0, 1, 2, 3]
    assert np.flatnonzero(sel[2]).tolist() == [0, 1, 2]
    assert np.flatnonzero(sel[3]).tolist() == [1, 3, 5, 6]
    # a block of 8 queries of one row, positions 40..47, the last two
    # padding: query t sees 41 + t scores; top 4 of a rising ramp
    ramp = np.tile(np.arange(128, dtype=np.float32), (8, 1))
    sel = np.asarray(tr.select_keys(
        jnp.asarray(ramp), jnp.asarray([[46], [40], [6], [0]], jnp.int32),
        8, 4))
    for t in range(6):
        assert np.flatnonzero(sel[t]).tolist() == list(range(37 + t, 41 + t))
    assert sel[6:].sum() == 0


def test_a_context_within_the_selection_is_the_model_without_indexer(toy):
    """16 keys selected: a 16-token prompt and its first decode steps see
    nothing else, so the model is the same model with the indexer off
    (its weights are the same draws: the indexer's leaves come after the
    attention's); one token later it is not."""
    cfg, params, _ = toy
    p32 = f32(params)
    off = dataclasses.replace(cfg, indexer=None)
    toks = np.random.default_rng(4).integers(3, 512, 60).astype(np.int32)
    for n, same in ((16, True), (60, False)):
        with_ix, _ = flat_tick(cfg, p32, new_pools(cfg), [(toks[:n], 0)])
        out = tr.forward_hidden_ragged(
            p32, off, jnp.asarray(np.pad(toks[:n], (0, -n % 8)))[None],
            jnp.asarray(np.pad(np.arange(n), (0, -n % 8)))[None],
            new_pools(cfg)[0], None, jnp.asarray([[1, 2, 3, 4]], jnp.int32),
            jnp.asarray(np.array([(n, b * 8, min(8, n - b * 8), 0)
                                  for b in range(-(-n // 8))]).T, jnp.int32),
            jnp.asarray(np.pad(PAGE + np.arange(n), (0, -n % 8),
                               constant_values=33 * PAGE), jnp.int32), tq=8)
        diff = np.abs(np.asarray(with_ix - out[0][0][:n])).max()
        assert (diff < 1e-5) == same, (n, diff)


def test_the_routers_bias_chooses_and_the_gates_stay_bare():
    """8 experts in 4 groups of 2, 2 groups stay, 3 a token. Scores .9 .1
    | .5 .6 | .8 .7 | .2 .3 with a bias of +.5 on expert 6 and -.5 on
    expert 4: groups score 1.0, 1.1, 1.0 (.3 + .7), 1.0 (.7 + .3): groups
    1 and 0 stay (the lower of the tied), experts 0, 3, 2 are chosen, and
    the gates are THEIR bare scores, 2 x s / 2.0."""
    m = MoEConfig(n_routed=8, n_held=8, per_token=3, expert_dim=4,
                  n_group=4, topk_group=2, routed_scale=2.0,
                  router_bias=True)
    s = np.array([[.9, .1, .5, .6, .8, .7, .2, .3]], np.float32)
    b = np.array([0, 0, 0, 0, -.5, 0, .5, 0], np.float32)
    idx, gates = tr.moe_select(jnp.asarray(np.log(s / (1 - s))), m,
                               jnp.asarray(b))
    assert idx.tolist() == [[0, 3, 2]]
    assert np.allclose(gates, 2.0 * np.array([.9, .6, .5]) / 2.0, atol=1e-6)
    ref_idx, ref_gates = fam.select_experts(jnp.asarray(s), jnp.asarray(b),
                                            dict(n_group=4, topk_group=2,
                                                 k=3, norm_topk=True,
                                                 routed_scale=2.0))
    assert ref_idx.tolist() == [[0, 3, 2]]
    assert np.allclose(ref_gates, gates, atol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer_with_the_bias_in_it(toy):
    """PR 27's test with the correction bias in the router: the routed
    parts of all four shares of 8 experts, the shared expert counted once,
    are the uncut layer, and the uncut layer routes as the reference."""
    from tests.test_latent_moe import moe_layer
    cfg, _, _ = toy
    x = jnp.asarray(np.random.default_rng(5).normal(size=(90, cfg.dim)),
                    jnp.float32)
    key = jax.random.PRNGKey(SEED)

    def share(first, n):
        c = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, held_start=first, n_held=n))
        return moe_layer(c, f32(tr.init_params(c, key)), x)

    uncut, st = share(0, 32)
    assert int(st[1]) == int(st[0]) == 90 * 4
    parts = [share(8 * r, 8) for r in range(4)]
    assert sum(int(s[1]) for _, s in parts) == 90 * 4
    p = f32(tr.init_params(cfg, key))["layers"]
    with jax.default_matmul_precision("highest"):
        h = tr.rmsnorm(x, p["mlp_norm"][0], cfg.norm_eps, False)
        shared = tr._gated(h, p["ws_gate"][0], p["ws_up"][0],
                           p["ws_down"][0], "silu")
        mine = tr.moe_select(h @ p["router"][0], cfg.moe,
                             p["router_bias"][0])
        unbiased = tr.moe_select(h @ p["router"][0], cfg.moe)
        theirs = fam.select_experts(jax.nn.sigmoid(h @ p["router"][0]),
                                    p["router_bias"][0], fam.shapes(RAW))
    total = sum(y for y, _ in parts) - 3 * shared
    assert np.abs(np.asarray(total - uncut)).max() < 1e-4
    assert bool(jnp.all(mine[0] == theirs[0]))
    assert np.abs(np.asarray(mine[1] - theirs[1])).max() < 1e-6
    assert bool(jnp.any(mine[0] != unbiased[0]))      # the bias did choose


# -- the kernels ------------------------------------------------------------

def ragged_blocks(tq):
    """Block meta of three ragged rows (a row shorter than its block) and
    an inert block; tables over 9 pages."""
    meta = []
    for r, (kv, nq) in enumerate([(300, 40), (150, 150), (7, 7)]):
        nq = nq if tq > 1 else 1
        meta += [(kv, kv - nq + b * tq, min(tq, nq - b * tq), r)
                 for b in range(-(-nq // tq))]
    meta.append((0, 0, 0, 0))
    return jnp.asarray(np.array(meta).T, jnp.int32)


def seen(bm, tq, S):
    """[NB·tq, S] bool: positions each query of a block table may see."""
    kv, q0, nq, _ = (np.repeat(np.asarray(bm[j]), tq) for j in range(4))
    t = np.arange(len(kv)) % tq
    s = np.arange(S)[None]
    return (s <= (q0 + t)[:, None]) & (s < kv[:, None]) & (t < nq)[:, None]


@pytest.mark.parametrize("tq", [8, 1])
def test_index_scores_kernel_is_its_reference(tq):
    rng = np.random.default_rng(6)
    bm = ragged_blocks(tq)
    tables = jnp.asarray(rng.permutation(9).reshape(3, 3), jnp.int32)
    pool = jnp.asarray(rng.normal(size=(2, 9, PAGE, 128)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(bm.shape[1] * tq, 8, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(bm.shape[1] * tq, 8)), jnp.float32)
    got = np.asarray(pa.index_scores(q, w, pool, tables, bm, 1, tq=tq,
                                     interpret=True))
    want = np.asarray(pa.index_scores_ref(q, w, pool, tables, bm, 1, tq=tq))
    vis = seen(bm, tq, 3 * PAGE)
    assert vis.sum() > 400
    assert np.abs(np.where(vis, got - want, 0)).max() < 1e-4


@pytest.mark.parametrize("tq", [8, 1])
def test_latent_kernel_honours_a_selection_as_its_reference(tq):
    """The masked walk (interpret mode) against the gather reference, and
    against that reference computed over the selected rows ALONE: the
    mask is the selection, not a damping of it."""
    rng = np.random.default_rng(7)
    bm = ragged_blocks(tq)
    NB = bm.shape[1]
    tables = jnp.asarray(rng.permutation(9).reshape(3, 3), jnp.int32)
    pool = jnp.asarray(rng.normal(size=(2, 9, PAGE, 256)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(NB * tq, 8, 256)), jnp.float32)
    select = jnp.asarray(rng.random((NB * tq, 3 * PAGE)) < 0.3, jnp.int32)
    kw = dict(tq=tq, v_lanes=128, scale=0.07)
    got = pa.ragged_attend_latent(q, pool, tables, bm, 1, interpret=True,
                                  select=select, **kw)
    want = pa.ragged_attend_latent_ref(q, pool, tables, bm, 1,
                                       select=select, **kw)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    dense = pa.ragged_attend_latent_ref(q, pool, tables, bm, 1, **kw)
    assert np.abs(np.asarray(dense - want)).max() > 0.05
    # by hand, query 3 of the first block: softmax over its selected rows
    vis = seen(bm, tq, 3 * PAGE) & (np.asarray(select) != 0)
    i = 3 if tq > 1 else 0
    rows = np.asarray(pool[1, tables[0]]).reshape(-1, 256)[vis[i]]
    sc = np.asarray(q[i]) @ rows.T * 0.07
    p = np.exp(sc - sc.max(-1, keepdims=True))
    hand = (p / p.sum(-1, keepdims=True)) @ rows[:, :128]
    assert np.abs(hand - np.asarray(want[i])).max() < 1e-4


@pytest.mark.parametrize("walk_block", [1, 2, None],
                         ids=["a-page-a-turn", "two-pages", "as-served"])
@pytest.mark.parametrize("case", sorted(CHUNK_WALK_CASES))
def test_latent_chunk_walk_honours_each_querys_selection(case, walk_block):
    """The chunk forward's latent call under a selection at DeepSeek-V3.2's
    128 heads (tq = 8, interpret mode) against the gather reference: a
    block's visibility is built once for its 8 queries and each query's
    row of it masks its own 128 score rows — query 1 of every block keeps
    NOTHING of the first four pages (a whole block of keys, as served),
    its neighbours about a third of them."""
    rng = np.random.default_rng(45)
    pool, tables, bm = chunk_walk_case(CHUNK_WALK_CASES[case], rng)
    NB, H = bm.shape[1], 128
    q = jnp.asarray(rng.normal(size=(NB * 8, H, pool.shape[-1])),
                    jnp.float32)
    select = (rng.random((NB * 8, tables.shape[1] * PAGE)) < 0.3
              ).astype(np.int32)
    select[1::8, :4 * PAGE] = 0
    kw = dict(tq=8, v_lanes=128, scale=0.07, select=jnp.asarray(select))
    got = pa.ragged_attend_latent(q, pool, tables, bm, 1, interpret=True,
                                  walk_block=walk_block, **kw)
    want = pa.ragged_attend_latent_ref(q, pool, tables, bm, 1, **kw)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    dense = pa.ragged_attend_latent_ref(q, pool, tables, bm, 1,
                                        **{**kw, "select": None})
    vis = seen(bm, 8, tables.shape[1] * PAGE)
    kept = (vis & (select != 0)).sum(axis=1)
    some = (kept > 0) & (kept < vis.sum(axis=1))
    assert some.sum() >= 4
    assert np.abs(np.asarray(dense - want))[some].max() > 0.05
    # a query whose every visible key was dropped attends nothing
    none = vis.any(axis=1) & (kept == 0)
    assert not np.asarray(got)[none].any()


@pytest.mark.parametrize("case", sorted(SHARED_WALK_CASES))
def test_latent_shared_walk_honours_each_members_selection(case):
    """The decode call's shared walk under a selection (interpret mode)
    against the gather reference: a shared page is multiplied once for the
    group and masked a member at a time — the first member of every group
    keeps NOTHING on its second page, its neighbours about a third."""
    rng = np.random.default_rng(41)
    spec = SHARED_WALK_CASES[case]
    pool, tables, lens, meta, want = shared_walk_case(spec, rng)
    shared = pa.shared_walks(tables, lens, PAGE)
    assert shared[0].tolist() == want.tolist()
    R, H = len(lens), 8
    select = (rng.random((R, tables.shape[1] * PAGE)) < 0.3).astype(np.int32)
    for rows, _ in spec["groups"]:
        select[rows[0], PAGE:2 * PAGE] = 0
    q = jnp.asarray(rng.normal(size=(R, H, pool.shape[-1])), jnp.float32)
    args = (q, pool, jnp.asarray(tables), jnp.asarray(meta), 1)
    kw = dict(tq=1, v_lanes=128, scale=0.07, select=jnp.asarray(select))
    ref = pa.ragged_attend_latent_ref(*args, **kw)
    got = pa.ragged_attend_latent(*args, interpret=True,
                                  shared=jnp.asarray(shared), **kw)
    assert np.abs(np.asarray(got - ref)).max() < 1e-5
    dense = pa.ragged_attend_latent_ref(*args, **{**kw, "select": None})
    live = np.asarray(meta[2]) > 0
    assert np.abs(np.asarray(dense - ref))[live].max() > 0.05


@pytest.mark.parametrize("case", sorted(SHARED_WALK_CASES))
def test_index_scores_with_the_shared_walk_are_the_walk_alones(case):
    """The decode call's index scores with ``shared_walks``' table
    (interpret mode): a group's common pages are scored in one walk for all
    of its members, and every visible score is BIT-equal to the walk with
    a zero table, to the kernel the chunk forward keeps, and to the
    reference — a score is a sum of independent products."""
    rng = np.random.default_rng(42)
    pool, tables, lens, meta, want = shared_walk_case(
        SHARED_WALK_CASES[case], rng, lanes=128)
    shared = pa.shared_walks(tables, lens, PAGE)
    assert shared[0].tolist() == want.tolist()
    R, Hi = len(lens), 8
    q = jnp.asarray(rng.normal(size=(R, Hi, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(R, Hi)), jnp.float32)
    args = (q, w, pool, jnp.asarray(tables), jnp.asarray(meta), 1)
    zero = np.zeros_like(shared)
    zero[2:] = np.arange(R)
    vis = seen(jnp.asarray(meta), 1, tables.shape[1] * PAGE)
    assert vis.sum() > 2000

    def scores(**kw):
        return np.where(vis, np.asarray(pa.index_scores(
            *args, tq=1, interpret=True, **kw)), 0)

    got = scores(shared=jnp.asarray(shared))
    assert np.array_equal(got, scores(shared=jnp.asarray(zero)))
    assert np.array_equal(got, scores(shared=jnp.asarray(shared),
                                      walk_block=1))
    assert np.array_equal(got, scores())
    ref = np.where(vis, np.asarray(pa.index_scores_ref(*args, tq=1)), 0)
    assert np.abs(got - ref).max() < 1e-4


# -- through the engine -----------------------------------------------------

@pytest.fixture(scope="module")
def engine(toy):
    from quoracle_tpu.models.generate import GenerateEngine
    from quoracle_tpu.models.tokenizer import get_tokenizer
    cfg, params, _ = toy
    return GenerateEngine(cfg, f32(params), get_tokenizer("tiny"),
                          max_seq=512, prompt_buckets=(32, 64, 128, 256))


def gaps(ref, prompt, res):
    ids = prompt + res.token_ids
    lg = ref.logits(np.pad(np.asarray(ids, np.int32), (0, 256 - len(ids))),
                    np.arange(len(prompt) - 1, len(ids) - 1))
    return lg.max(-1) - lg[np.arange(len(res.token_ids)), res.token_ids]


def test_engine_serves_it_and_an_adopted_prefix_is_a_cold_one(engine, toy):
    """Sessions, resume and the radix prefix cache through `generate`: the
    pools are TWO arrays of unequal width under one page id; a prompt
    whose first page is ADOPTED from another session's (latent rows and
    index keys both: its queries score and select among adopted keys)
    says what the same prompt says served cold, which is the reference's
    arg-max; span arguments and counters tick."""
    from quoracle_tpu.infra.telemetry import (
        SPARSE_ATTN_PAIRS_TOTAL, tick_close, tick_open,
    )
    cfg, _, ref = toy
    rng = np.random.default_rng(8)
    shared = [int(t) for t in rng.integers(3, 512, 130)]    # > one page
    a = shared + [int(t) for t in rng.integers(3, 512, 20)]
    b = shared + [int(t) for t in rng.integers(3, 512, 9)]
    cold = engine.generate([b], temperature=0.0, max_new_tokens=10)[0]
    assert cold.n_cached_tokens == 0
    ra = engine.generate([a], temperature=0.0, max_new_tokens=10,
                         session_ids=["a"])[0]
    tick_open("m")
    try:
        rb = engine.generate([b], temperature=0.0, max_new_tokens=10,
                             session_ids=["b"])[0]
    finally:
        args = tick_close().args
    assert rb.n_cached_tokens == 128            # a's first page, adopted
    assert rb.token_ids == cold.token_ids
    st = engine.sessions
    assert st.k.shape == (4, st.n_pages, PAGE, 128)
    assert st.v.shape == (4, st.n_pages, PAGE, 32)
    for prompt, res in ((a, ra), (b, rb)):
        assert gaps(ref, prompt, res).max() < TOL
    # the tick: 11 suffix tokens at 128..138 visible, then 9 decode
    # forwards; every query selects 16
    n_q = 11 + 9
    assert args["attn_selected_pairs"] == 16 * n_q
    assert args["index_pairs"] == args["attn_pairs"] \
        == sum(range(129, 129 + n_q))
    assert args["index_kv_reads"] == args["attn_kv_reads"]
    sel, vis = (SPARSE_ATTN_PAIRS_TOTAL.value(model=cfg.name, kind=k)
                for k in ("selected", "visible"))
    assert 0 < sel < vis / 4
    # resume: the session's next turn prefills its suffix only
    more = a + ra.token_ids + [5, 6, 7]
    r2 = engine.generate([more], temperature=0.0, max_new_tokens=6,
                         session_ids=["a"])[0]
    assert r2.n_cached_tokens >= len(a)
    assert gaps(ref, more, r2).max() < TOL
    q = engine.quant_stats()
    assert q["kv_bytes_per_token"] == 4 * (128 + 32) * 4 \
        == fam.stated_precision(RAW)["kv_bytes_per_token"]
    assert "xlatent128+32-" in engine.kv_signature()
    free = st.free_pages()
    engine.drop_session("a")
    engine.drop_session("b")
    assert st.free_pages() >= free


# read off the parent commit (PR 33: d58388d) through this fixture's engine
PARENT_GREEDY = [
    [454, 26, 431, 79, 373, 185, 13, 30, 434, 17, 479, 387, 154, 262, 252,
     46, 431, 477, 73, 311, 442, 217, 345, 471],
    [88, 373, 373, 314, 432, 311, 505, 454, 367, 205, 328, 86, 211, 504,
     122, 331, 52, 495, 322, 225, 84, 86, 115, 181]]
PARENT_RESUMED = [42, 382, 503, 20, 485, 497, 293, 278]


def test_greedy_tokens_are_the_parents(engine):
    """PR 36 changed the FORM of the output projection (`wo` contracted
    over one flat dimension, tests/test_latent_moe.py), not what it
    computes: this toy's greedy tokens through the engine — every query
    selecting its 16 keys — are the ones the parent commit served."""
    serves_the_parents_tokens(engine, PARENT_GREEDY, PARENT_RESUMED)


def test_a_context_past_the_last_prompt_bucket_lands_on_a_bounded_key(
        engine):
    """The engine's prompt buckets end at 256 here (8,192 as served);
    contexts past them still land on (token budget, table width) keys of
    the two ladders: a budget of RAGGED_TOKEN_BUCKETS, a power-of-two
    width, no length in a key."""
    from quoracle_tpu.models.generate import RAGGED_TOKEN_BUCKETS
    rng = np.random.default_rng(9)
    for n in (300, 333, 401):
        engine.generate([[int(t) for t in rng.integers(3, 512, n)]],
                        temperature=0.0, max_new_tokens=2)
    keys = [k["shape"].split("x") for k in
            engine.compiles.snapshot(max_shapes=256)["shapes"]]
    assert all(k[0] == "ragged" for k in keys)
    assert {int(k[1]) for k in keys} <= set(RAGGED_TOKEN_BUCKETS)
    widths = {int(k[3]) for k in keys}
    assert all(w & (w - 1) == 0 for w in widths) and max(widths) == 4


def test_one_statement_of_what_a_resident_token_holds():
    from benchmark import configs
    raw = configs.load_config("deepseek-v3.2-ep16-l5")
    cfg = get_model_config(fam.register(raw))
    assert cfg.kv_pools == (640, 128)
    assert cfg.kv_bytes_per_token() == 5 * 768 * 2 == 7680 \
        == fam.stated_precision(raw)["kv_bytes_per_token"]
    assert cfg.n_params == 4_867_187_968            # ISSUE 31's count
    assert fam.decode_weight_bytes(raw) == 3_633_891_840 == 2 * (
        cfg.n_params - 4 * 16 * 44_040_192 - 32320 * 7168)
    from quoracle_tpu.parallel.mesh import pool_sizing
    (member,) = pool_sizing([f"xla:{raw['name']}"], n_devices=1)["members"]
    assert member["kv_bytes_per_token_per_chip"] == 7680
