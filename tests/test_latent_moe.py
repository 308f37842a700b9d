"""Latent attention (MLA) and routed experts on the ragged paged path
(ISSUE 27), at tiny sizes on the CPU with seeded random weights: the program
against the benchmark's plain reference (`benchmark/families/latent_moe.py`,
written apart from it), the folded form against the unfolded one, a suffix
prefill against a resident latent context, the expert shares adding up to
the uncut layer, group-limited selection, the worst-case routing, YaRN, each
refusal, and the dense models' programs left as they were.

Tolerances. Program and reference are both float32 at matmul precision
"highest" here and agree to about 1e-5 on logits of size 4: 2e-4 leaves
room for the different order of their sums (folded against unfolded
attention, grouped against looped experts) and is two orders of magnitude
below what one expert, one gate or the rotary scale left out would move.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import latent_moe
from quoracle_tpu.models import transformer as tr
from quoracle_tpu.models.config import (
    LatentConfig, ModelConfig, MoEConfig, get_model_config,
)
from quoracle_tpu.ops import paged_attention as pa

TOL = 2e-4
PAGE = 128

# a configuration file's keys at toy widths: 32 routed experts in 4 groups,
# experts 8-15 held here (the second of four shares), 1 dense + 2 expert
# layers, YaRN over an original context of 64
RAW = dict(
    name="toy-axk1", family="latent_moe", model_type="axk1",
    attention_bias=False, first_k_dense_replace=1, hidden_act="silu",
    hidden_size=64, intermediate_size=96, kv_lora_rank=32, q_lora_rank=48,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    num_attention_heads=4, num_key_value_heads=4, num_hidden_layers=3,
    moe_intermediate_size=32, n_routed_experts=8, n_shared_experts=1,
    n_group=4, topk_group=2, num_experts_per_tok=4, norm_topk_prob=True,
    routed_scaling_factor=2.5, scoring_func="sigmoid", topk_method="none",
    rms_norm_eps=1e-6, rope_theta=10000, vocab_size=512,
    rope_scaling=dict(type="yarn", factor=32, beta_fast=32, beta_slow=1,
                      mscale=1, mscale_all_dim=1,
                      original_max_position_embeddings=64),
    tie_word_embeddings=False, torch_dtype="float32", eos_token_id=2,
    bos_token_id=1, held_experts_first=8,
    reduced_from=dict(n_routed_experts=32),
    serving=dict(context_window=512, output_limit=128))
SEED = 2 ** 31 + 27


@pytest.fixture(scope="module")
def toy():
    """(cfg, float32 params, the reference) of RAW at SEED."""
    cfg = get_model_config(latent_moe.register(RAW))
    params = tr.init_params(cfg, jax.random.PRNGKey(SEED), dtype=jnp.bfloat16)
    ref = latent_moe.Reference(RAW, SEED)
    return cfg, params, ref


def f32(params):
    return jax.tree.map(lambda a: a.astype(jnp.float32), params)


def flat_tick(cfg, params, pool, rows, n_pages):
    """One ragged forward of `rows` = [(tokens, prefix already resident)]:
    row r's pages are r*4 + 1 ..; returns (hidden [T, D] of the real tokens
    in order, pool, stats)."""
    TQ = 8
    toks, pos, dst, meta, take = [], [], [], [], []
    tables = np.zeros((8, 4), np.int32)
    for r, (t, pre) in enumerate(rows):
        tables[r] = r * 4 + 1 + np.arange(4)
        nb = -(-len(t) // TQ)
        base = len(toks)
        for b in range(nb):
            meta.append((pre + len(t), pre + b * TQ,
                         min(TQ, len(t) - b * TQ), r))
        p = pre + np.arange(len(t))
        pad = nb * TQ - len(t)
        toks += list(t) + [0] * pad
        pos += list(p) + [0] * pad
        dst += list(tables[r][p // PAGE] * PAGE + p % PAGE) \
            + [n_pages * PAGE] * pad
        take += list(range(base, base + len(t)))
    with jax.default_matmul_precision("highest"):
        out = tr.forward_hidden_ragged(
            params, cfg, jnp.asarray(toks, jnp.int32)[None],
            jnp.asarray(pos, jnp.int32)[None], pool, None,
            jnp.asarray(tables), jnp.asarray(np.array(meta).T, jnp.int32),
            jnp.asarray(dst, jnp.int32), tq=TQ)
    return out[0][0][jnp.asarray(take)], out[1], out[5]


def new_pool(cfg, n_pages=33, dtype=jnp.float32):
    return jnp.zeros((cfg.n_layers, n_pages, PAGE, cfg.kv_pools[0]), dtype)


def test_both_sides_draw_the_same_bits(toy):
    cfg, params, ref = toy
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.n_params
    for mine, theirs in (("dense_layers", "dense"), ("layers", "experts")):
        for k, leaf in ref.w[theirs].items():
            assert bool(jnp.all(leaf == params[mine][k])), k
    assert bool(jnp.all(ref.w["embed"] == params["embed"]))
    assert bool(jnp.all(ref.w["lm_head"] == params["lm_head"]))


def test_reference_agrees_with_the_ragged_forward(toy):
    cfg, params, ref = toy
    toks = np.random.default_rng(0).integers(3, 512, 150).astype(np.int32)
    hid, _, stats = flat_tick(cfg, f32(params), new_pool(cfg),
                              [(toks, 0)], 33)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(tr.project_logits(f32(params), cfg, hid[None]))[0]
    want = ref.logits(np.pad(toks, (0, 10)), np.arange(150))
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < TOL
    total, held, reached, steps = (int(v) for v in stats)
    assert (total, steps) == (150 * 4 * 2, 2)     # 2 expert layers
    assert 0 < held < total and 0 < reached <= 16


def test_prefill_then_decode_through_the_latent_pool(toy):
    """A prompt's chunk, then one token at a time through
    `decode_ragged`'s own step (a tq=1 block a row), reading everything
    off the latent pages: the logits at every decoded position are the
    reference's whole forward pass over the sequence."""
    cfg, params, ref = toy
    p32 = f32(params)
    toks = np.random.default_rng(1).integers(3, 512, 140).astype(np.int32)
    n0 = 131                      # the prompt crosses a page; so does decode
    _, pool, _ = flat_tick(cfg, p32, new_pool(cfg), [(toks[:n0], 0)], 33)
    want = ref.logits(np.pad(toks, (0, 20)), np.arange(140))
    tables = jnp.asarray(np.array([[1, 2, 3, 4]] + [[0] * 4] * 7, np.int32))
    for t in range(n0, 140):
        meta = np.zeros((4, 8), np.int32)
        meta[:, 0] = (t + 1, t, 1, 0)
        meta[3] = np.arange(8)
        flat = np.full((8,), 33 * PAGE, np.int32)
        flat[0] = (1 + t // PAGE) * PAGE + t % PAGE
        cur = np.zeros((8,), np.int32)
        cur[0] = toks[t]
        pos = np.zeros((8,), np.int32)
        pos[0] = t
        with jax.default_matmul_precision("highest"):
            hid, pool, _, _, _, st = tr.forward_hidden_ragged(
                p32, cfg, jnp.asarray(cur)[None], jnp.asarray(pos)[None],
                pool, None, tables, jnp.asarray(meta), jnp.asarray(flat),
                tq=1)
            got = np.asarray(tr.project_logits(p32, cfg, hid))[0, 0]
        assert np.abs(got - want[t]).max() < TOL, t
        assert int(st[0]) == 4 * 2        # one live row: k x expert layers


def test_suffix_prefill_against_a_resident_latent_context(toy):
    """A tool turn: 37 new tokens attend to 200 cached latents, beside a
    second row that prefills whole. Equal to one whole prefill."""
    cfg, params, _ = toy
    p32 = f32(params)
    rng = np.random.default_rng(2)
    a, b = (rng.integers(3, 512, n).astype(np.int32) for n in (237, 60))
    whole, _, _ = flat_tick(cfg, p32, new_pool(cfg), [(a, 0)], 33)
    _, pool, _ = flat_tick(cfg, p32, new_pool(cfg), [(a[:200], 0)], 33)
    both, _, _ = flat_tick(cfg, p32, pool, [(a[200:], 200), (b, 0)], 33)
    assert np.abs(np.asarray(both[:37] - whole[200:])).max() < 1e-5
    alone, _, _ = flat_tick(cfg, p32, new_pool(cfg), [(b, 0)], 33)
    assert np.abs(np.asarray(both[37:] - alone)).max() < 1e-5


def test_folded_attention_is_the_unfolded_one(toy):
    """One layer's attention block: the program's folded form (key
    up-projection in the query, value up-projection in the output, scores
    against the stored row) against the reference's unfolded one."""
    cfg, params, ref = toy
    p = jax.tree.map(lambda a: a[1].astype(jnp.float32), {
        k: v for k, v in params["layers"].items()
        if not k.startswith("we_")})
    T = 72
    x = jnp.asarray(np.random.default_rng(3).normal(size=(1, T, cfg.dim)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        q, row, _, _ = tr._latent_qkv(x, p, cfg, jnp.arange(T)[None])
        assert row.shape == (T, 128) and q.shape == (T, 4, 128)
        assert bool(jnp.all(row[:, 40:] == 0)) and bool(jnp.all(
            q[..., 40:] == 0))                       # 32 + 8, then the pad
        pool = jnp.zeros((1, 2, PAGE, 128)).at[0, 1, :T].set(row)
        meta = jnp.asarray(np.array(
            [(T, b * 8, 8, 0) for b in range(T // 8)]).T, jnp.int32)
        attn = pa.ragged_attend_latent_ref(
            q, pool, jnp.asarray([[1]], jnp.int32), meta, 0, tq=8,
            v_lanes=32, scale=tr.attn_softmax_scale(cfg))
        got = tr._latent_attn_out(x, attn, p, cfg)[0]
        want = latent_moe._attention(ref.s, ref.w["experts"], x[0], 1)
    assert np.abs(np.asarray(got - want)).max() < 1e-4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("heads", [8, 64])
def test_the_output_projection_is_the_contraction_over_heads_and_v(toy,
                                                                   heads,
                                                                   dtype):
    """`_latent_attn_out` contracts `wo` over ONE flat dimension of H·v
    (so that a layer scan's slice of the stacked weight fuses into the
    matmul, PERF.md §6, PR 36): the same dot products as the contraction
    over (heads, v) apart, written out here as the function had it, at a
    ragged number of tokens. float32: the order of the sums alone
    differs; bfloat16 (products summed in float32, rounded once): at most
    one rounding of the projection, carried into the rounded sum."""
    cfg = dataclasses.replace(toy[0], n_heads=heads, n_kv_heads=heads)
    la = cfg.latent
    assert (la.kv_rank, la.nope_dim, la.v_dim, cfg.dim) == (32, 16, 16, 64)
    rng = np.random.default_rng(heads)
    T = 37
    x = jnp.asarray(rng.normal(size=(1, T, 64)), dtype)
    attn = jnp.asarray(rng.normal(size=(T, heads, 32)), jnp.float32)
    p = {"wkv_b": jnp.asarray(rng.normal(size=(32, heads * 32)) / 6, dtype),
         "wo": jnp.asarray(rng.normal(size=(heads * 16, 64))
                           / np.sqrt(heads * 16), dtype)}
    with jax.default_matmul_precision("highest"):
        got = tr._latent_attn_out(x, attn, p, cfg)
        w_v = p["wkv_b"].reshape(32, heads, 32)[..., 16:]
        o = jnp.einsum("thc,chv->thv", attn.astype(dtype), w_v)
        y = jnp.einsum("thv,hvD->tD", o, p["wo"].reshape(heads, 16, 64))
        want = x + y[None]
    assert got.dtype == dtype and got.shape == (1, T, 64)
    got, want, y = (np.asarray(a, np.float32) for a in (got, want, y))
    assert np.abs(y).max() > 1.0
    if dtype == jnp.float32:
        assert np.abs(got - want).max() < 1e-6
    else:
        def ulp(a):     # a bfloat16 keeps 8 bits: below 2^k, 2^(k-8) apart
            return 2.0 ** (np.ceil(np.log2(np.maximum(np.abs(a), 1e-30)))
                           - 8)
        assert np.all(np.abs(got - want) <= ulp(y)[None] + ulp(want))


@pytest.mark.parametrize("tq", [8, 1])
def test_latent_kernel_is_its_reference(tq):
    """The Pallas kernel (interpret mode) against the gather reference:
    ragged rows, a row shorter than its block, an inert block."""
    rng = np.random.default_rng(4)
    L, n_pages, lanes, v_lanes, H = 2, 9, 256, 128, 8
    pool = jnp.asarray(rng.normal(size=(L, n_pages, PAGE, lanes)),
                       jnp.float32)
    meta = []
    for r, (kv, nq) in enumerate([(300, 40 if tq > 1 else 1),
                                  (150, 150 if tq > 1 else 1), (7, 7 if tq > 1 else 1)]):
        for b in range(-(-nq // tq)):
            meta.append((kv, kv - nq + b * tq, min(tq, nq - b * tq), r))
    meta.append((0, 0, 0, 0))
    bm = jnp.asarray(np.array(meta).T, jnp.int32)
    tables = jnp.asarray(rng.permutation(n_pages).reshape(3, 3), jnp.int32)
    q = jnp.asarray(rng.normal(size=(len(meta) * tq, H, lanes)), jnp.float32)
    got = pa.ragged_attend_latent(q, pool, tables, bm, 1, tq=tq,
                                  v_lanes=v_lanes, scale=0.07,
                                  interpret=True)
    want = pa.ragged_attend_latent_ref(q, pool, tables, bm, 1, tq=tq,
                                       v_lanes=v_lanes, scale=0.07)
    assert np.abs(np.asarray(got - want)).max() < 1e-5


# -- the chunk forward's walk, a block of pages a turn (ISSUE 44) -------------
#
# A case: rows of (resident tokens with the chunk written, the chunk's first
# position, its queries), cut into blocks of 8 queries as the engine cuts
# them, over tables 8 pages wide; ``blocks`` are block-meta columns added as
# they stand (kv_len, qpos0, nq, row).
CHUNK_WALK_CASES = {
    # 8 pages: whole blocks at 1, 2 and 4 pages a turn
    "whole-blocks": dict(rows=[(1024, 1008, 16)]),
    # 6, 3 and 5 pages: the last block of a walk holds 2, 1 or 3 of 4
    "a-partial-last-block": dict(
        rows=[(768, 744, 24), (384, 376, 8), (640, 600, 40)]),
    # the last page holds 17 tokens; another row's whole context is 5
    "a-kv-len-inside-a-page": dict(rows=[(401, 393, 8), (5, 0, 5)]),
    # 11 queries: a block of 8 and one of 3; a row of one query
    "fewer-queries-than-the-block": dict(
        rows=[(300, 289, 11), (700, 699, 1)]),
    # a block with resident tokens and no query, and an inert one
    "a-block-with-no-query": dict(
        rows=[(520, 512, 8)], blocks=[(520, 512, 0, 0), (0, 0, 0, 0)]),
    # a chunk that begins and ends anywhere: its blocks straddle a page
    # boundary (queries 125..132 see one page, then two), the chunk's own
    # tokens past a block's last query are written and hidden
    "a-chunk-cut-anywhere": dict(
        rows=[(650, 125, 77), (1000, 509, 11)]),
}


def chunk_walk_case(case, rng, tq=8, lanes=256, width=8):
    """(pool, tables, block meta) of a ``CHUNK_WALK_CASES`` entry."""
    meta = []
    for r, (kv, first, nq) in enumerate(case["rows"]):
        meta += [(kv, first + b * tq, min(tq, nq - b * tq), r)
                 for b in range(-(-nq // tq))]
    meta += case.get("blocks", [])
    R = len(case["rows"])
    tables = rng.permutation(R * width + 1)[:R * width].reshape(R, width)
    pool = jnp.asarray(rng.normal(size=(2, R * width + 1, PAGE, lanes)),
                       jnp.float32)
    return (pool, jnp.asarray(tables, jnp.int32),
            jnp.asarray(np.array(meta).T, jnp.int32))


@pytest.mark.parametrize("walk_block", [1, 2, None],
                         ids=["a-page-a-turn", "two-pages", "as-served"])
@pytest.mark.parametrize("case", sorted(CHUNK_WALK_CASES))
def test_latent_chunk_walk_is_its_reference(case, walk_block):
    """The chunk forward's latent call (tq = 8, interpret mode) at A.X-K1's
    64 heads against the gather reference, which knows no walk: a block of
    pages attended as one run of keys gives what the reference gives,
    whatever the pages a turn; a block with no query writes zeros."""
    rng = np.random.default_rng(44)
    pool, tables, bm = chunk_walk_case(CHUNK_WALK_CASES[case], rng)
    H = 64
    q = jnp.asarray(rng.normal(size=(bm.shape[1] * 8, H, pool.shape[-1])),
                    jnp.float32)
    kw = dict(tq=8, v_lanes=128, scale=0.07)
    got = pa.ragged_attend_latent(q, pool, tables, bm, 1, interpret=True,
                                  walk_block=walk_block, **kw)
    want = pa.ragged_attend_latent_ref(q, pool, tables, bm, 1, **kw)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    idle = np.repeat(np.asarray(bm[2]), 8) <= np.tile(np.arange(8),
                                                      bm.shape[1])
    assert not np.asarray(got)[idle].any()
    assert np.asarray(got)[~idle].any(axis=(1, 2)).all()


# -- the decode call's shared walk (ISSUE 40) --------------------------------
#
# A case: 8 one-token rows; ``groups`` [(rows, common pages)]: those rows'
# tables begin with the same ``common`` pages; ``own`` pages a row holds
# behind them (0: the row's new token lands in the last common page's last
# slot, so that page is not whole and the walk covers one page less for
# every member); ``walked`` {row: pages} where that differs from
# ``groups``; ``done`` rows have no query (nq = 0).
MIN = pa.SHARED_MIN_PAGES
SHARED_WALK_CASES = {
    "a-pair": dict(groups=[((1, 4), MIN + 1)], own=[1, 2, 1, 1, 3, 1, 2, 1]),
    "five-beside-rows-in-no-group": dict(
        groups=[((0, 2, 3, 5, 6), MIN + 2)], own=[1, 2, 1, 1, 3, 2, 2, 1]),
    "all-eight": dict(groups=[(tuple(range(8)), MIN)],
                      own=[1, 2, 1, 1, 3, 1, 2, 1]),
    "two-groups-of-three-and-four": dict(
        groups=[((0, 1, 2), MIN + 3), ((3, 4, 6, 7), MIN)],
        own=[2, 1, 1, 1, 2, 3, 1, 1]),
    # its last token lies in the last common page: nothing of its own
    # behind the shared pages, and the walk ends a page earlier for all
    "a-member-with-no-page-of-its-own": dict(
        groups=[((0, 3, 5), MIN + 1)], own=[1, 2, 1, 0, 3, 2, 2, 1],
        walked={0: MIN, 3: MIN, 5: MIN}),
    "common-pages-at-the-break-even": dict(
        groups=[((2, 6), MIN)], own=[1, 2, 1, 1, 3, 1, 2, 1]),
    "one-under-the-break-even": dict(
        groups=[((2, 6), MIN - 1)], own=[1, 2, 1, 1, 3, 1, 2, 1],
        walked={}),
    "a-done-row-inside-a-group": dict(
        groups=[((1, 2, 4, 7), MIN + 1)], own=[1, 2, 1, 1, 3, 1, 2, 1],
        done=(2,)),
    "a-done-leader": dict(
        groups=[((1, 2, 4), MIN + 1)], own=[1, 2, 1, 1, 3, 1, 2, 1],
        done=(1,)),
}


def shared_walk_case(case, rng, lanes=256):
    """(pool, tables, lens, block meta, the pages a shared walk should cover
    a row) of a ``SHARED_WALK_CASES`` entry."""
    R = len(case["own"])
    ids = iter(range(1, 10_000))
    tables = np.zeros((R, 16), np.int32)
    lens = np.zeros((R,), np.int32)
    want = np.zeros((R,), np.int32)
    heads = {}
    for rows, common in case["groups"]:
        run = [next(ids) for _ in range(common)]
        for r in rows:
            heads[r] = run
            want[r] = common
    for r in range(R):
        pages = list(heads.get(r, [])) + [next(ids)
                                          for _ in range(case["own"][r])]
        tables[r, :len(pages)] = pages
        # resident tokens: the last page partly filled; a member with no
        # page of its own holds its last common page but for one slot
        lens[r] = len(pages) * PAGE - (1 if not case["own"][r]
                                       else int(rng.integers(1, PAGE)))
    if "walked" in case:
        want[:] = 0
        for r, n in case["walked"].items():
            want[r] = n
    live = np.array([r not in case.get("done", ()) for r in range(R)],
                    np.int32)
    meta = np.stack([lens + live, lens - (1 - live), live, np.arange(R)])
    pool = jnp.asarray(rng.normal(size=(2, next(ids), PAGE, lanes)),
                       jnp.float32)
    return pool, tables, lens, meta.astype(np.int32), want


@pytest.mark.parametrize("walk_block", [None, 1],
                         ids=["block-as-served", "a-page-a-turn"])
@pytest.mark.parametrize("case", sorted(SHARED_WALK_CASES))
def test_latent_shared_walk_is_its_reference(case, walk_block):
    """The latent decode call with ``shared_walks``' table of its rows
    (interpret mode) against the gather reference, which knows no walk,
    and against the same kernel with a zero table: rows of a group have
    their common pages multiplied once between them and come out as they
    do alone."""
    rng = np.random.default_rng(40)
    pool, tables, lens, meta, want = shared_walk_case(
        SHARED_WALK_CASES[case], rng)
    shared = pa.shared_walks(tables, lens, PAGE)
    assert shared[0].tolist() == want.tolist()
    R, H = len(lens), 8
    q = jnp.asarray(rng.normal(size=(R, H, pool.shape[-1])), jnp.float32)
    args = (q, pool, jnp.asarray(tables), jnp.asarray(meta), 1)
    kw = dict(tq=1, v_lanes=128, scale=0.07)
    ref = pa.ragged_attend_latent_ref(*args, **kw)
    got = pa.ragged_attend_latent(*args, interpret=True, walk_block=walk_block,
                                  shared=jnp.asarray(shared), **kw)
    alone = pa.ragged_attend_latent(
        *args, interpret=True, walk_block=walk_block,
        shared=jnp.zeros_like(jnp.asarray(shared)).at[2:].set(
            jnp.arange(R)), **kw)
    assert np.abs(np.asarray(got - ref)).max() < 1e-5
    assert np.abs(np.asarray(alone - ref)).max() < 1e-5
    assert np.abs(np.asarray(got - alone)).max() < 1e-5
    done = list(SHARED_WALK_CASES[case].get("done", ()))
    assert not np.asarray(got)[done].any()


# -- the expert layer -------------------------------------------------------

def moe_layer(cfg, params, x, valid=None):
    """The expert layer 0 of `params["layers"]` on x [T, D]: its output
    less the residual, and its stats."""
    p = {k: v[0] for k, v in params["layers"].items()
         if not k.startswith("we_")}
    experts = tuple(params["layers"][k]
                    for k in ("we_gate", "we_up", "we_down"))
    valid = jnp.ones((x.shape[0],), bool) if valid is None else valid
    with jax.default_matmul_precision("highest"):
        y, stats = tr._moe(x[None], p, experts, 0, cfg, valid)
    return y[0] - x, stats


def test_the_shares_add_up_to_the_uncut_layer(toy):
    """32 routed experts in 4 shares of 8: the routed parts of all shares,
    with the shared expert that every share computes counted once, are the
    uncut layer. An expert's weights do not depend on the share that holds
    it (`_expert_leaf`), so each share is drawn on its own."""
    cfg, _, _ = toy
    x = jnp.asarray(np.random.default_rng(5).normal(size=(90, cfg.dim)),
                    jnp.float32)
    key = jax.random.PRNGKey(SEED)

    def share(first, n):
        c = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, held_start=first, n_held=n))
        return moe_layer(c, f32(tr.init_params(c, key)), x)

    uncut, st = share(0, 32)
    assert int(st[1]) == int(st[0]) == 90 * 4         # every one is held
    parts = [share(8 * r, 8) for r in range(4)]
    assert sum(int(s[1]) for _, s in parts) == 90 * 4
    p = f32(tr.init_params(cfg, key))["layers"]
    with jax.default_matmul_precision("highest"):
        h = tr.rmsnorm(x, p["mlp_norm"][0], cfg.norm_eps, False)
        shared = tr._gated(h, p["ws_gate"][0], p["ws_up"][0],
                           p["ws_down"][0], "silu")
    total = sum(y for y, _ in parts) - 3 * shared
    assert np.abs(np.asarray(shared)).max() > 0.1
    assert np.abs(np.asarray(total - uncut)).max() < 1e-4
    # and the uncut layer is the reference's, which loops over the experts
    raw = {**RAW, "n_routed_experts": 32, "held_experts_first": 0}
    ref = latent_moe.Reference(raw, SEED)
    with jax.default_matmul_precision("highest"):
        hh = latent_moe._rmsnorm(x, 1e-6)
        idx, gates = latent_moe.select(jax.nn.sigmoid(
            hh @ ref.w["experts"]["router"][0].astype(jnp.float32)), ref.s)
    with jax.default_matmul_precision("highest"):
        mine = tr.moe_select(h @ p["router"][0], cfg.moe)
    assert bool(jnp.all(mine[0] == idx))
    assert np.abs(np.asarray(mine[1] - gates)).max() < 1e-6


def test_group_limited_selection_by_hand():
    """8 experts in 4 groups of 2, 2 groups stay, 3 experts a token.
    Scores (after the sigmoid) .9 .1 | .5 .6 | .8 .7 | .2 .3: groups score
    1.0, 1.1, 1.5, 0.5, so groups 2 and 1 stay and expert 0, the largest
    score of all, is NOT selected; inside them .8, .7, .6 are: experts 4,
    5, 3, with gates 2 x score / 2.1."""
    m = MoEConfig(n_routed=8, n_held=8, per_token=3, expert_dim=4,
                  n_group=4, topk_group=2, routed_scale=2.0)
    s = np.array([[.9, .1, .5, .6, .8, .7, .2, .3]], np.float32)
    idx, gates = tr.moe_select(jnp.asarray(np.log(s / (1 - s))), m)
    assert idx.tolist() == [[4, 5, 3]]
    assert np.allclose(gates, 2.0 * np.array([.8, .7, .6]) / 2.1, atol=1e-6)
    # the reference's own selection reads the same
    ref_idx, ref_gates = latent_moe.select(jnp.asarray(s), dict(
        n_group=4, topk_group=2, k=3, norm_topk=True, routed_scale=2.0))
    assert ref_idx.tolist() == [[4, 5, 3]]
    assert np.allclose(ref_gates, gates, atol=1e-6)
    # without the norm the gates are the scores times the scale
    _, raw_gates = tr.moe_select(
        jnp.asarray(np.log(s / (1 - s))),
        dataclasses.replace(m, norm_topk=False))
    assert np.allclose(raw_gates, 2.0 * np.array([.8, .7, .6]), atol=1e-6)


def test_worst_case_routing_is_exact(toy):
    """Every token of a tick choosing held experts only (the router is
    bent towards them): 600 tokens give each of 4 chosen experts 600
    assignments, three blocks of 256 each with the last one part full, and
    the result is the plain sum over experts, token by token; padded
    tokens are neither computed nor counted."""
    cfg, params, _ = toy
    c = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_group=1, topk_group=1))
    p = f32(params)
    # held: 8-15; coordinate 0 is positive in every token below
    bent = p["layers"]["router"].at[:, 0, 8:12].add(40.0)
    p = {**p, "layers": {**p["layers"], "router": bent}}
    T = 600
    x = jnp.asarray(np.random.default_rng(6).normal(size=(T, cfg.dim)),
                    jnp.float32).at[:, 0].set(3.0)
    valid = jnp.arange(T) % 7 != 3
    got, st = moe_layer(c, p, x, valid)
    n_valid = int(valid.sum())
    assert [int(v) for v in st] == [4 * n_valid, 4 * n_valid, 4, 1]
    L = p["layers"]
    with jax.default_matmul_precision("highest"):
        h = tr.rmsnorm(x, L["mlp_norm"][0], cfg.norm_eps, False)
        idx, gates = tr.moe_select(h @ L["router"][0], c.moe)
        want = tr._gated(h, L["ws_gate"][0], L["ws_up"][0], L["ws_down"][0],
                         "silu")
        for e in range(8):
            g = jnp.where((idx == 8 + e) & valid[:, None], gates, 0).sum(-1)
            want = want + g[:, None] * tr._gated(
                h, L["we_gate"][0, e], L["we_up"][0, e], L["we_down"][0, e],
                "silu")
    assert set(np.unique(np.asarray(idx))) == {8, 9, 10, 11}
    assert np.abs(np.asarray(got - want)).max() < 1e-4


def test_yarn_frequencies_against_the_closed_form():
    """A.X-K1's rotary: 64 dimensions, theta 10000, factor 32, beta 32 / 1
    over 4,096 positions. The blend runs from dimension 10 to 23: below it
    a frequency is as published, above it divided by 32."""
    low, high = tr.yarn_correction_range(32, 1, 64, 10000.0, 4096)
    assert (low, high) == (10, 23)
    i = np.arange(32)
    base = 10000.0 ** (-2 * i / 64)
    ramp = np.clip((i - 10) / 13, 0, 1)
    want = base / 32 * ramp + base * (1 - ramp)
    assert np.allclose(latent_moe.yarn_inv_freq(64, 10000.0, 32, 32, 1,
                                                4096), want, rtol=1e-6)
    assert want[10] == base[10] and want[23] == base[23] / 32
    # the program rotates by exactly these: position 1 gives the angles
    x = jnp.ones((1, 1, 1, 64), jnp.float32)
    out = np.asarray(tr.rope(x, jnp.ones((1, 1), jnp.int32), 10000.0,
                             ("yarn", 32.0, 32.0, 1.0, 4096, 1.0, 1.0)))
    assert np.allclose(out[0, 0, 0, :32], np.cos(want) - np.sin(want),
                       atol=1e-6)
    # the softmax scale carries mscale squared: 0.1 ln 32 + 1 = 1.3466
    cfg = ModelConfig(name="s", vocab_size=8, dim=8, n_layers=1, n_heads=1,
                      n_kv_heads=1, ffn_dim=8,
                      rope_scaling=("yarn", 32.0, 32.0, 1.0, 4096, 1.0, 1.0),
                      latent=LatentConfig(8, 8, 128, 64, 128),
                      moe=MoEConfig(8, 8, 2, 8))
    assert tr.attn_softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * 1.3466 ** 2, rel=1e-4)


def test_one_statement_of_what_a_resident_token_holds():
    from benchmark import configs
    raw = configs.load_config("ax-k1-ep16-l7")
    cfg = get_model_config(latent_moe.register(raw))
    assert cfg.kv_pools == (640,)
    assert cfg.kv_bytes_per_token() == 7 * 640 * 2 == 8960 \
        == latent_moe.stated_precision(raw)["kv_bytes_per_token"]
    assert cfg.n_params == 4_841_331_712            # ISSUE 27's count
    assert cfg.n_active_params == cfg.n_params - 6 * 4 * 44_040_192
    assert latent_moe.decode_weight_bytes(raw) == 2 * (
        cfg.n_params - 6 * 12 * 44_040_192 - 20480 * 7168)
    dense = get_model_config("mistral-7b")
    assert dense.kv_pools == (1024, 1024) and dense.plain
    assert dense.kv_bytes_per_token(tp=2) == 65536
    from quoracle_tpu.parallel.mesh import pool_sizing
    (member,) = pool_sizing([f"xla:{raw['name']}"], n_devices=1)["members"]
    assert member["kv_bytes_per_token_per_chip"] == 8960


# -- through the engine -----------------------------------------------------

@pytest.fixture(scope="module")
def engine(toy):
    from quoracle_tpu.models.generate import GenerateEngine
    from quoracle_tpu.models.tokenizer import get_tokenizer
    cfg, params, _ = toy
    return GenerateEngine(cfg, f32(params), get_tokenizer("tiny"),
                          max_seq=512, prompt_buckets=(32, 64, 128, 256))


def test_engine_serves_it_on_the_ragged_path(engine, toy):
    """Sessions, resume, the radix prefix cache and sessionless rows, all
    through `generate`: greedy tokens are the reference's arg-max at every
    step, the pool is ONE latent array, the counters tick."""
    from quoracle_tpu.infra.telemetry import (
        MOE_ASSIGNMENTS_TOTAL, MOE_LAYER_STEPS_TOTAL,
    )
    cfg, _, ref = toy
    rng = np.random.default_rng(7)
    shared = [int(t) for t in rng.integers(3, 512, 130)]    # > one page
    a = shared + [int(t) for t in rng.integers(3, 512, 20)]
    b = shared + [int(t) for t in rng.integers(3, 512, 9)]
    before = MOE_LAYER_STEPS_TOTAL.value(model=cfg.name)
    ra = engine.generate([a], temperature=0.0, max_new_tokens=10,
                         session_ids=["a"])[0]
    rb = engine.generate([b], temperature=0.0, max_new_tokens=10,
                         session_ids=["b"])[0]
    assert rb.n_cached_tokens == 128            # a's first page, adopted
    st = engine.sessions
    assert st.v is None and st.k.shape[0] == 3 and st.k.shape[-1] == 128
    assert all(k["shape"].startswith("ragged")
               for k in engine.compiles.snapshot()["shapes"])
    for prompt, res in ((a, ra), (b, rb)):
        ids = prompt + res.token_ids
        lg = ref.logits(np.pad(np.asarray(ids, np.int32),
                               (0, 256 - len(ids))),
                        np.arange(len(prompt) - 1, len(ids) - 1))
        gaps = lg.max(-1) - lg[np.arange(len(res.token_ids)),
                               res.token_ids]
        assert gaps.max() < TOL
    # resume: the session's next turn prefills its suffix only
    more = a + ra.token_ids + [5, 6, 7]
    r2 = engine.generate([more], temperature=0.0, max_new_tokens=6,
                         session_ids=["a"])[0]
    assert r2.n_cached_tokens >= len(a)
    # a row with no session rides scratch pages of the same path
    free = st.free_pages()
    r3 = engine.generate([a], temperature=0.0, max_new_tokens=10)[0]
    assert r3.token_ids == ra.token_ids and st.free_pages() == free
    assert MOE_LAYER_STEPS_TOTAL.value(model=cfg.name) > before
    held = MOE_ASSIGNMENTS_TOTAL.value(model=cfg.name, held="true")
    rest = MOE_ASSIGNMENTS_TOTAL.value(model=cfg.name, held="false")
    assert 0.1 < held / (held + rest) < 0.4         # 8 of 32 are held
    q = engine.quant_stats()
    assert q["kv_bytes_per_token"] == 3 * 128 * 4 and not q["quantize_kv"]
    assert "xlatent128-" in engine.kv_signature()


# read off the parent commit (PR 33: d58388d) through this fixture's engine
PARENT_GREEDY = [
    [358, 161, 448, 120, 20, 439, 372, 335, 178, 509, 214, 488, 321, 296,
     94, 441, 461, 223, 94, 278, 358, 315, 192, 374],
    [13, 272, 268, 399, 58, 511, 153, 240, 294, 399, 147, 321, 340, 174,
     194, 436, 147, 268, 324, 436, 448, 195, 141, 263]]
PARENT_RESUMED = [321, 294, 104, 10, 401, 142, 385, 9]


def serves_the_parents_tokens(engine, greedy, resumed):
    """Two sessionless rows in one tick — a prompt past a page and a
    short one (rng 36) — then a session's resumed turn: the tokens
    recorded at the parent commit."""
    rng = np.random.default_rng(36)
    long = [int(t) for t in rng.integers(3, 512, 150)]
    short = [int(t) for t in rng.integers(3, 512, 21)]
    res = engine.generate([long, short], temperature=0.0,
                          max_new_tokens=24)
    assert [r.token_ids for r in res] == greedy
    r1 = engine.generate([long], temperature=0.0, max_new_tokens=8,
                         session_ids=["p"])[0]
    r2 = engine.generate([long + r1.token_ids + [5, 6, 7]],
                         temperature=0.0, max_new_tokens=8,
                         session_ids=["p"])[0]
    engine.drop_session("p")
    assert r1.token_ids == greedy[0][:8]
    assert (r2.token_ids, r2.n_cached_tokens) == (resumed, 157)


def test_greedy_tokens_are_the_parents(engine):
    """PR 36 changed the FORM of the output projection, not what it
    computes: the toy's greedy tokens through the engine are the ones
    the parent commit served."""
    serves_the_parents_tokens(engine, PARENT_GREEDY, PARENT_RESUMED)


REFUSALS = {
    "forward_hidden": lambda e: tr.forward_hidden(
        e.params, e.cfg, jnp.zeros((1, 4), jnp.int32),
        jnp.zeros((1, 4), jnp.int32), None, None, None),
    "host and disk KV tiers": lambda e: e.attach_tier(host_mb=8),
    "handoff": lambda e: __import__(
        "quoracle_tpu.serving.handoff", fromlist=["KVHandoff"]
    ).KVHandoff().export(e, "a", "xla:toy-axk1"),
    "drafts": lambda e: __import__(
        "quoracle_tpu.models.speculative", fromlist=["BatchedSpeculator"]
    ).BatchedSpeculator(e, e),
    "baton drafts": lambda e: __import__(
        "quoracle_tpu.models.speculative", fromlist=["SpeculativeDecoder"]
    ).SpeculativeDecoder(e.cfg, e.params, e.cfg, e.params, e.tokenizer),
}


@pytest.mark.parametrize("path", sorted(REFUSALS))
def test_a_path_that_cannot_serve_it_refuses(engine, path):
    with pytest.raises(ValueError, match="ragged paged path of one device"):
        REFUSALS[path](engine)


@pytest.mark.parametrize("kw,what", [
    (dict(quantize_kv=True), "--quantize-kv"),
    (dict(quantize_weights=True), "--quantize-weights"),
    (dict(mesh="a mesh"), "--tp > 1"),
])
def test_an_engine_option_that_cannot_serve_it_refuses_at_start(toy, kw,
                                                                what):
    from quoracle_tpu.models.generate import GenerateEngine
    from quoracle_tpu.models.tokenizer import get_tokenizer
    cfg, params, _ = toy
    with pytest.raises(ValueError) as e:
        GenerateEngine(cfg, params, get_tokenizer("tiny"), max_seq=256,
                       **kw)
    assert what in str(e.value) and "latent attention" in str(e.value)


def test_the_gather_fallback_refuses_and_leaks_no_page(engine):
    free = engine.sessions.free_pages()
    engine._force_gather_decode = True
    try:
        with pytest.raises(RuntimeError, match="gather fallback"):
            engine.generate([[5, 6, 7, 8]], temperature=0.0,
                            max_new_tokens=4, session_ids=["g"])
    finally:
        engine._force_gather_decode = False
    assert engine.sessions.free_pages() == free
    out = engine.generate([[5, 6, 7, 8]], temperature=0.0, max_new_tokens=4,
                          session_ids=["g"])[0]
    assert len(out.token_ids) == 4
    engine.drop_session("g")


def test_a_latent_tick_counts_the_walk_its_kernel_made(engine):
    """`attn_kv_streamed` / `attn_tiles` on a latent engine: its kernel
    walks a row's pages once per 8-token BLOCK, so the tick is priced from
    the block table (the dense engines': from their 128-token tiles), and
    the engine builds and ships no tile table."""
    from quoracle_tpu.infra.telemetry import tick_close, tick_open
    from quoracle_tpu.models.generate import RAGGED_TQ
    assert engine._ragged_tile == 0
    rng = np.random.default_rng(11)
    rows = [[int(t) for t in rng.integers(3, 512, n)] for n in (150, 5)]
    tick_open("m")
    try:
        res = engine.generate(rows, temperature=0.0, max_new_tokens=5)
    finally:
        args = tick_close().args
    pages = lambda n: -(-n // PAGE)         # noqa: E731
    chunk = [pages(min(len(r), (b + 1) * RAGGED_TQ)) for r in rows
             for b in range(-(-len(r) // RAGGED_TQ))]
    dec = [pages(len(r) + j) for r, out in zip(rows, res)
           for j in range(1, len(out.token_ids))]
    assert args["attn_tiles"] == len(chunk) + len(dec)
    assert args["attn_kv_streamed"] == PAGE * (sum(chunk) + sum(dec))
    assert args["attn_kv_streamed"] > 3 * args["attn_kv_reads"] > 0
    # a latent pool's walks, the chunk forward's and the decode steps',
    # turn once a block of ``latent_walk_pages`` (every walk here fits one)
    assert engine._walk_block >= max(dec + chunk)
    assert args["attn_walk_steps"] == len(chunk) + len(dec)
    # nothing in common: no row in a group, no page walked for two
    assert args["attn_shared_rows"] == args["attn_shared_pages"] == 0


# -- the dense models keep their programs -----------------------------------

def test_the_dense_decode_program_is_the_parents():
    """The two-kind layer stack costs a dense model nothing: its decode
    program (`step_paged_decode_ragged`, AOT on the CPU at `tiny` with the
    gather reference in the kernel's place) holds the operations and the
    temporaries it held at the parent commit, where I read them off the
    same lowering (PR 27: 4fd7b5c), but for what PR 28 added to every
    model's sampler."""
    from quoracle_tpu.models.generate import GenerateEngine
    from quoracle_tpu.models.tokenizer import ByteTokenizer
    cfg = get_model_config("tiny")
    S = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda k: tr.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    eng = GenerateEngine(cfg, params, ByteTokenizer(), max_seq=256)
    st = eng.sessions
    pool = S((cfg.n_layers, st.n_pages, st.page,
              cfg.n_kv_heads * cfg.head_dim), eng.pool_dtype)
    R, i32, f = 8, jnp.int32, jnp.float32
    lowered = eng._step_paged_decode_ragged.lower(
        params, pool, pool, None, None, S((R, 4), i32), S((10, R), i32),
        S((R,), i32), S((R,), i32), S((R, cfg.vocab_size), f),
        S((2,), jnp.uint32),
        S((R,), f), S((R,), f), S((R,), jnp.bool_), S((R,), i32), None,
        None, max_new=32)
    ops = [ln for ln in lowered.as_text().splitlines()
           if " = " in ln and "stablehlo." in ln]
    assert len(ops) == DENSE_DECODE_OPS
    mem = lowered.compile().memory_analysis()
    assert mem.temp_size_in_bytes == DENSE_DECODE_TEMP_BYTES


# read off the parent commit (4fd7b5c): 955 operations, 5,500,376 bytes.
# PR 28 put the sampler's nucleus in a conditional, at the first draw and
# in the loop body: 22 operations and 328 bytes more, for every model.
DENSE_DECODE_OPS = 977
DENSE_DECODE_TEMP_BYTES = 5_500_704
