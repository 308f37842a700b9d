"""Gated short convolutions beside per-head attention, with many small
experts (LFM2; ISSUE 33), at toy widths on the CPU with seeded random
weights: the program against the benchmark's plain reference
(`benchmark/families/shortconv_moe.py`, written apart from it), on logits;
chunks cut anywhere; rows of one tick apart; the state a session resumes
from, adopts with a cached page, re-prefills for want of, copies on write
and frees with the page; the decode loop; each refusal.

Tolerances. Program and reference are both float32 here and agree to about
1e-5 on logits of size 4: 2e-4 leaves room for the different order of their
sums (grouped against looped experts, a chunk's matmul against the whole
sequence's) and is two orders of magnitude below what one tap, one expert
or a state read from the wrong record moves (the zeroed-record cases below
read 0.05 and more).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import shortconv_moe as fam
from quoracle_tpu.models import transformer as tr
from quoracle_tpu.models.config import MoEConfig, get_model_config
from quoracle_tpu.models.generate import (
    GenerateEngine, conv_past, decode_ragged,
)
from quoracle_tpu.models.tokenizer import ByteTokenizer

TOL = 2e-4
PAGE = 128
TYPES = ["conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv", "conv", "conv"]

# the configuration file's keys at toy widths: the cut's own pattern (a
# leading dense conv layer, two periods of attention, conv, conv, conv),
# 8 experts of which a token takes 2
RAW = dict(
    name="toy-lfm2", family="shortconv_moe", model_type="lfm2_moe",
    conv_L_cache=3, conv_bias=False, hidden_size=64, intermediate_size=96,
    layer_types=TYPES, max_position_embeddings=1024,
    moe_intermediate_size=32, norm_eps=1e-5, norm_topk_prob=True,
    num_attention_heads=4, num_dense_layers=1, num_experts=8,
    num_experts_per_tok=2, num_hidden_layers=9, num_key_value_heads=2,
    rope_parameters=dict(rope_theta=1000000, rope_type="default"),
    routed_scaling_factor=1, use_expert_bias=True, vocab_size=512,
    tie_word_embeddings=True, gate_normaliser_eps=1e-6,
    torch_dtype="float32", eos_token_id=2, bos_token_id=1,
    serving=dict(context_window=1024, output_limit=128))
SEED = 2 ** 31 + 33


def f32(params):
    return jax.tree.map(lambda a: a.astype(jnp.float32), params)


def model(raw):
    cfg = get_model_config(fam.register(raw))
    params = tr.init_params(cfg, jax.random.PRNGKey(SEED), dtype=jnp.bfloat16)
    return cfg, params, fam.Reference(raw, SEED)


@pytest.fixture(scope="module")
def toy():
    return model(RAW)


def tokens_of(seed, n):
    return np.random.default_rng(seed).integers(3, 512, n).astype(np.int32)


# -- the forward, called as the engine's programs call it -------------------

N_PAGES = 33


def new_pools(cfg):
    kv = jnp.zeros((cfg.n_attn_layers, N_PAGES, PAGE, cfg.kv_pools[0]),
                   jnp.float32)
    return kv, kv, jnp.zeros((cfg.n_conv_layers * N_PAGES, cfg.state_lanes),
                             jnp.float32)


def by_page(cfg, state):
    """The state pool, stored flat over (layer, page), as [layer, page,
    lanes] on the host."""
    return np.asarray(state).reshape(cfg.n_conv_layers, -1, cfg.state_lanes)


@functools.partial(jax.jit, static_argnames=("cfg", "tq"))
def _forward(params, cfg, toks, pos, kp, vp, tables, meta, dst, conv, take,
             tq):
    """Jitted, so that a test of many ticks loads one executable a shape
    (an eager scan loads one a call, and some hundreds exhaust the
    process's executable memory)."""
    out = tr.forward_hidden_ragged(params, cfg, toks[None], pos[None], kp,
                                   vp, tables, meta, dst, tq=tq, conv=conv)
    logits = tr.project_logits(params, cfg, out[0][0][take][None])[0]
    return logits, (out[1], out[2], out[6])


def tick(cfg, params, pools, rows, tq=8):
    """One ragged forward of `rows` = [(tokens, prefix already resident)]
    (row r's pages: r*4 + 1 ..), laid out and described to the conv layers
    as `GenerateEngine._run_unified` does it; returns (logits [T, V] of the
    real tokens in order, pools)."""
    kp, vp, sp = pools
    toks, pos, dst, meta, take = [], [], [], [], []
    c_row, c_idx, rec_src, rec_dst = [], [], [], []
    tables = np.zeros((8, 4), np.int32)
    src = np.full((8,), -1, np.int32)
    for r, (t, pre) in enumerate(rows):
        tables[r] = r * 4 + 1 + np.arange(4)
        nb = -(-len(t) // tq)
        base = len(toks)
        for b in range(nb):
            meta.append((pre + len(t), pre + b * tq,
                         min(tq, len(t) - b * tq), r))
        p = pre + np.arange(len(t))
        pad = nb * tq - len(t)
        toks += list(t) + [0] * pad
        pos += list(p) + [0] * pad
        dst += list(tables[r][p // PAGE] * PAGE + p % PAGE) \
            + [N_PAGES * PAGE] * pad
        take += list(range(base, base + len(t)))
        c_row += [r] * (len(t) + pad)
        c_idx += list(range(len(t))) + [10 ** 6] * pad
        if pre:
            src[r] = tables[r][(pre - 1) // PAGE]
        for i in sorted(set(np.flatnonzero((p + 1) % PAGE == 0))
                        | {len(t) - 1}):
            rec_src.append(base + i)
            rec_dst.append(tables[r][p[i] // PAGE])
    i32 = lambda a: jnp.asarray(np.asarray(a), jnp.int32)      # noqa: E731
    conv = tr.ConvTick(sp, i32(src), i32(conv_past(
        np.asarray(c_row), np.asarray(c_idx), cfg.conv_cache)),
        i32(rec_src), i32(rec_dst))
    logits, pools = _forward(params, cfg, i32(toks), i32(pos), kp, vp,
                             i32(tables), i32(np.array(meta).T), i32(dst),
                             conv, i32(take), tq=tq)
    return np.asarray(logits), pools


def test_both_sides_draw_the_same_bits(toy):
    cfg, params, ref = toy
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.n_params
    n = 0
    for mine, theirs in zip(params["segments"], ref.w["segments"]):
        for p, w in zip(mine, theirs):
            for k, leaf in w.items():
                assert bool(jnp.all(leaf == p[k])), k
                n += 1
    assert n == 6 + 9 + 3 * 8           # a conv operator has one leaf less
    assert bool(jnp.all(ref.w["embed"] == params["embed"]))
    assert "lm_head" not in params and "lm_head" not in ref.w


def test_the_plan_scans_whole_periods():
    cfg = get_model_config(fam.register(RAW))
    lead, period, tail = cfg.layer_plan
    assert lead == ((("conv", "dense"),), 1)
    assert period == ((("attention", "experts"),) + (("conv", "experts"),) * 3,
                      2)
    assert tail == ((), 0)
    # the published depth: two dense layers, nine periods, and the rest of
    # a tenth unrolled behind them
    full = dataclasses.replace(
        cfg, n_layers=40, moe=dataclasses.replace(cfg.moe, first_dense=2),
        layer_types=("conv", "conv") + ("attention", "conv", "conv",
                                        "conv") * 9 + ("attention", "conv"))
    lead, period, tail = full.layer_plan
    assert [n for _, n in (lead, period, tail)] == [1, 9, 1]
    assert len(lead[0]) == 2 and len(period[0]) == 4 and len(tail[0]) == 2
    assert (full.n_attn_layers, full.n_conv_layers) == (10, 30)


@pytest.mark.parametrize("K", [3, 4])
def test_reference_agrees_with_the_ragged_forward(K):
    cfg, params, ref = model({**RAW, "name": f"toy-lfm2-k{K}",
                              "conv_L_cache": K})
    toks = tokens_of(0, 150)
    got, _ = tick(cfg, f32(params), new_pools(cfg), [(toks, 0)])
    want = ref.logits(np.pad(toks, (0, 10)), np.arange(150))
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < TOL


def test_the_program_chooses_the_references_experts(toy):
    """Float32 on the CPU: the same experts, ties to the lower index, the
    gates the bare scores over their sum + 1e-6."""
    cfg, params, ref = toy
    rng = np.random.default_rng(5)
    logits = rng.standard_normal((64, 8)).astype(np.float32)
    logits[3] = 0.0                             # an eight-way tie
    logits[4, 5] = logits[4, 1]
    bias = (0.01 * rng.standard_normal(8)).astype(np.float32)
    bias[:2] = 0.0
    idx, gates = tr.moe_select(jnp.asarray(logits), cfg.moe,
                               jnp.asarray(bias))
    want_idx, want_gates = fam.select(jax.nn.sigmoid(jnp.asarray(logits)),
                                      jnp.asarray(bias), ref.s)
    assert np.array_equal(np.asarray(idx), np.asarray(want_idx))
    flat = jnp.zeros((8,), jnp.float32)
    assert list(np.asarray(tr.moe_select(jnp.asarray(logits), cfg.moe,
                                         flat)[0][3])) == [0, 1]
    assert list(np.asarray(fam.select(
        jax.nn.sigmoid(jnp.asarray(logits)), flat, ref.s)[0][3])) == [0, 1]
    assert np.abs(np.asarray(gates) - np.asarray(want_gates)).max() < 1e-6
    s = jax.nn.sigmoid(jnp.asarray(logits))[0, np.asarray(idx[0])]
    assert np.allclose(np.asarray(gates[0]), s / (s.sum() + 1e-6), atol=1e-7)


def test_prefill_then_decode_through_pages_and_state(toy):
    """A prompt's chunk, then one token at a time (a tq=1 block a row)
    from the pages and the records: the logits at every decoded position
    are the reference's whole forward pass. The prompt ends 3 tokens
    before a page's end, so decode crosses it."""
    cfg, params, ref = toy
    p32 = f32(params)
    toks = tokens_of(1, 140)
    want = ref.logits(np.pad(toks, (0, 20)), np.arange(140))
    got, pools = tick(cfg, p32, new_pools(cfg), [(toks[:125], 0)])
    assert np.abs(got - want[:125]).max() < TOL
    for t in range(125, 140):
        got, pools = tick(cfg, p32, pools, [(toks[t:t + 1], t)], tq=1)
        assert np.abs(got[0] - want[t]).max() < TOL, t


@pytest.mark.parametrize("step", [1, 2, 3, 32])
def test_a_chunk_may_be_cut_anywhere(toy, step):
    """The same prompt in one tick and in ticks of `step` tokens: the same
    logits and the same records (a chunk shorter than conv_L_cache - 1
    takes one predecessor from the record and passes the other on)."""
    cfg, params, _ = toy
    p32 = f32(params)
    toks = tokens_of(2, 135)
    whole, (_, _, state) = tick(cfg, p32, new_pools(cfg), [(toks, 0)])
    pools, got = new_pools(cfg), []
    for t in range(0, 135, step):
        lg, pools = tick(cfg, p32, pools, [(toks[t:t + step], t)])
        got.append(lg)
    assert np.abs(np.concatenate(got) - whole).max() < 1e-4
    # page 1's record is the state at token 127, page 2's at the end
    state, cut = by_page(cfg, state), by_page(cfg, pools[2])
    assert np.abs(cut[:, 1:3] - state[:, 1:3]).max() < 1e-4
    assert np.abs(state[:, 1]).max() > 0.01
    assert not np.allclose(state[:, 1], state[:, 2])


def test_rows_of_one_tick_never_see_each_other(toy):
    """Two rows interleaved in one tick — one of them a single token that
    continues a resident context, laid out right behind the other's — give
    what each gives alone; slots that are padding, and pages of rows that
    are not there, keep their records."""
    cfg, params, _ = toy
    p32 = f32(params)
    a, b = tokens_of(3, 21), tokens_of(4, 131)
    _, pools = tick(cfg, p32, new_pools(cfg), [([0], 0), (b[:130], 0)])
    before = by_page(cfg, pools[2])
    both, after = tick(cfg, p32, pools, [(a, 0), (b[130:], 130)])
    alone_a, _ = tick(cfg, p32, new_pools(cfg), [(a, 0)])
    whole_b, _ = tick(cfg, p32, new_pools(cfg), [([0], 0), (b, 0)])
    assert np.abs(both[:21] - alone_a).max() < 1e-4
    assert np.abs(both[21] - whole_b[-1]).max() < 1e-4
    # written: row 0's page 1 (its end), row 1's page 6 (token 130)
    changed = {int(p) for p in np.flatnonzero(
        np.abs(by_page(cfg, after[2]) - before).max(axis=(0, 2)) > 0)}
    assert changed == {1, 6}


def test_a_done_rows_state_stands_still_through_the_decode_loop(toy):
    """`decode_ragged` with one live row and one that stops at its first
    token: the first's records follow its tokens across a page's end, the
    second's page keeps the record it had."""
    cfg, params, ref = toy
    p32 = f32(params)
    a, b = tokens_of(6, 126), tokens_of(7, 40)
    lg, (kp, vp, sp) = tick(cfg, p32, new_pools(cfg), [(a, 0), (b, 0)])
    first = jnp.zeros((8, cfg.vocab_size), jnp.float32).at[:2].set(
        jnp.asarray(lg[[125, 165]]))
    tables = np.zeros((8, 4), np.int32)
    tables[0], tables[1] = 1 + np.arange(4), 5 + np.arange(4)
    active = np.zeros((8,), bool)
    active[:2] = True
    limits = np.ones((8,), np.int32)
    limits[0] = 6
    before = by_page(cfg, sp)
    res = decode_ragged(
        p32, cfg, kp, vp, jnp.asarray(tables),
        jnp.asarray([126, 40, 0, 0, 0, 0, 0, 0], jnp.int32),
        jnp.zeros((8,), jnp.int32), first, jax.random.PRNGKey(0),
        jnp.zeros((8,), jnp.float32), jnp.ones((8,), jnp.float32), 8,
        eos_id=-1, active=jnp.asarray(active),
        row_limit=jnp.asarray(limits), state=sp)
    out, n_emitted, lens, state = res[0], res[1], res[2], res[9]
    assert list(np.asarray(n_emitted[:2])) == [6, 1]
    assert list(np.asarray(lens[:2])) == [131, 40]
    after = by_page(cfg, state)
    changed = {int(p) for p in np.flatnonzero(
        np.abs(after - before).max(axis=(0, 2)) > 0)}
    assert changed == {1, 2}                 # row 0's two pages, not page 5
    # the tokens the loop emitted are the reference's, and the state it
    # left is the state of the whole sequence forwarded at once
    seq = np.concatenate([a, np.asarray(out[0, :5])])
    want = ref.logits(np.pad(seq, (0, 29)), np.arange(125, 131))
    gaps = want.max(-1) - want[np.arange(6), np.asarray(out[0, :6])]
    assert gaps.max() < TOL
    _, (_, _, whole) = tick(cfg, p32, new_pools(cfg), [(seq, 0)])
    assert np.abs(after[:, 1:3] - by_page(cfg, whole)[:, 1:3]).max() < 1e-4


def test_the_shares_add_up_to_the_uncut_layer(toy):
    """An expert layer beside per-head attention, no shared expert, told
    which experts it holds (`n_held` < `n_routed`): the four shares'
    results, the residual counted once, sum to the layer with all
    experts."""
    cfg, params, _ = toy
    p = jax.tree.map(lambda a: a[0].astype(jnp.float32),
                     params["segments"][1][0])
    x = jnp.asarray(np.random.default_rng(8).standard_normal((1, 24, 64)),
                    jnp.float32)
    valid = jnp.ones((24,), bool)
    experts = lambda lo, hi: tuple(                            # noqa: E731
        p[k][None, lo:hi] for k in ("we_gate", "we_up", "we_down"))
    whole, stats = tr._moe(x, p, experts(0, 8), 0, cfg, valid)
    assert [int(v) for v in stats] == [48, 48, int(stats[2]), 1]
    parts = []
    for first in range(0, 8, 2):
        share = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, n_held=2, held_start=first))
        y, st = tr._moe(x, p, experts(first, first + 2), 0, share, valid)
        assert 0 <= int(st[1]) < 48
        parts.append(y - x)
    assert float(jnp.abs(whole - x).max()) > 0.01
    assert float(jnp.abs(x + sum(parts) - whole).max()) < 1e-5


@pytest.mark.parametrize("T,E,held,dtype,tol", [
    (8, 16, (0, 16), jnp.float32, 1e-5),      # a decode step's eight rows
    (40, 8, (2, 4), jnp.float32, 1e-5),       # a share of the experts held
    (300, 8, (0, 8), jnp.float32, 1e-5),      # runs longer than a block
    (8, 64, (0, 64), jnp.bfloat16, 0.05),     # LFM2's 64, 4 a token
])
def test_the_blocks_in_one_kernel_give_the_loops_sum(T, E, held, dtype, tol):
    """`_routed_experts_grouped` (the experts' blocks in one Pallas kernel a
    layer, which the pattern forward runs on the TPU; interpreted here)
    against `_routed_experts` (the loop over blocks, which this machine
    and the latent models run): the same sum for the same routing, the
    same counts (`moe_counts`), rows that are not valid left at zero, and nothing at all
    valid leaves zeros, not what an unwritten block holds."""
    rng = np.random.default_rng(T + E)
    D, F, k = 128, 256, 4 if E > 8 else 2
    m = MoEConfig(n_routed=E, n_held=held[1], per_token=k, expert_dim=F,
                  n_shared=0, held_start=held[0], router_bias=True,
                  gate_eps=1e-6)
    h = jnp.asarray(rng.standard_normal((T, D)), dtype)
    w = tuple(jnp.asarray(rng.standard_normal((2, held[1], *shape)) * 0.1,
                          dtype)
              for shape in ((D, F), (D, F), (F, D)))
    idx, gates = tr.moe_select(
        jnp.asarray(rng.standard_normal((T, E)), jnp.float32), m,
        jnp.zeros((E,), jnp.float32))
    valid = jnp.asarray(rng.random(T) > 0.2)
    for rows in (valid, jnp.zeros((T,), bool)):
        want, n = tr._routed_experts(h, idx, gates, w, 1, m, "silu", rows)
        got, per_expert = tr._routed_experts_grouped(h, idx, gates, w, 1, m,
                                                     "silu", rows, True)
        counts = [int(v) for v in tr.moe_counts(per_expert, rows, m, 1)]
        assert [int(v) for v in n] == counts[:4]
        # and behind them what the layout cost: an expert's assignments
        # padded up to whole blocks of the tick's height
        blk = tr.moe_block_rows(T)
        assert counts[4:] == [sum(-(-int(c) // blk) for c in per_expert[0]),
                              blk * int(per_expert[2].sum())]
        assert counts[2] <= counts[4] and counts[1] <= counts[5]
        assert float(jnp.abs(got - want).max()) < tol
        assert not bool(jnp.any(got[~np.asarray(rows)]))
    assert float(jnp.abs(want).max()) == 0 and int(n[2]) == 0


def test_the_forward_with_its_kernels_is_the_forward_without(toy):
    """The whole pattern forward as the TPU runs it (`interpret=True`: the
    attention kernel and the experts' kernel, interpreted) against the one
    this machine runs: the same logits, pools, records and expert counts."""
    cfg, params, _ = toy
    p32 = f32(params)
    toks = tokens_of(9, 40)
    pos = jnp.arange(40, dtype=jnp.int32)
    kp, vp, sp = new_pools(cfg)
    conv = tr.ConvTick(sp, jnp.full((8,), -1, jnp.int32), jnp.asarray(
        conv_past(np.zeros(40, np.int32), np.arange(40), 3)),
        jnp.asarray([39], jnp.int32), jnp.asarray([1], jnp.int32))
    meta = jnp.asarray([(40, 8 * b, 8, 0) for b in range(5)], jnp.int32).T
    outs = [tr.forward_hidden_ragged(
        p32, cfg, jnp.asarray(toks)[None], pos[None], kp, vp,
        jnp.asarray(np.arange(1, 5)[None].repeat(8, 0), jnp.int32), meta,
        PAGE + pos, tq=8, conv=conv, interpret=kernels)
        for kernels in (None, True)]
    for a, b in zip(*[(o[0], o[1][:, 1], o[6], o[5][:4]) for o in outs]):
        assert float(jnp.abs(a - b).max()) < 1e-4
    # the kernels' forward says what its blocks cost besides: 48-row blocks
    blocks, rows = (int(v) for v in outs[1][5][4:])
    assert blocks >= int(outs[1][5][2]) and rows == 48 * blocks
    assert [int(v) for v in outs[0][5]] == [40 * 2 * 8, 40 * 2 * 8,
                                            int(outs[1][5][2]), 8]


def test_one_statement_of_what_a_session_holds(toy):
    cfg, _, _ = toy
    assert cfg.kv_pools == (32, 32) and cfg.n_attn_layers == 2
    assert cfg.kv_bytes_per_token() == 2 * 64 * 2
    assert cfg.state_lanes == 2 * 64
    assert cfg.state_bytes_per_record() == 7 * 128 * 2
    assert fam.stated_precision({**RAW, "torch_dtype": "bfloat16"}) == {
        "kv_bytes_per_token": 256, "state_bytes_per_record": 1792}
    assert not cfg.plain and get_model_config("tiny").plain
    assert get_model_config("tiny").state_bytes_per_record() == 0


def test_heads_narrower_than_a_lane_tile_are_read_as_stored():
    """LFM2's heads are 64 wide: two kv heads lie in one 128-lane tile of
    the stored row. The kernel reads them as one head (`_pack_queries`):
    the block, tile and shared walks against the gather reference, and
    nothing the size of the pool is made on the way."""
    from quoracle_tpu.ops import paged_attention as pa
    rng = np.random.default_rng(0)
    H, KV, hd, n_pages = 32, 8, 64, 24
    kp, vp = (jnp.asarray(rng.standard_normal((2, n_pages, PAGE, KV * hd)),
                          jnp.float32) for _ in range(2))
    tables = np.zeros((8, 16), np.int32)
    tables[0, :9] = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    tables[1, :9] = [1, 2, 3, 4, 5, 6, 7, 10, 11]
    tables[2, :8] = np.arange(12, 20)
    lens = np.array([1100, 1050, 900, 0, 0, 0, 0, 0], np.int32)
    meta = np.stack([lens, lens - 1, (lens > 0).astype(np.int32),
                     np.arange(8)]).astype(np.int32)
    q = jnp.asarray(rng.standard_normal((8, H, hd)), jnp.float32)
    shared = pa.shared_walks(tables, lens, PAGE)
    assert list(shared[0, :3]) == [7, 7, 0]
    args = (kp, vp, jnp.asarray(tables), jnp.asarray(meta), 1)
    want = pa.ragged_attend_ref(q, *args, tq=1)
    for sh in (None, jnp.asarray(shared)):
        got = pa.ragged_attend(q, *args, tq=1, interpret=True, shared=sh)
        assert float(jnp.abs(got - want)[:3].max()) < 1e-5
    # a chunk: 20 tokens behind 130 resident, and one token of another row
    meta = np.array([(150, 130 + 8 * b, min(8, 20 - 8 * b), 0)
                     for b in range(3)] + [(201, 200, 1, 1)], np.int32).T
    q = jnp.asarray(rng.standard_normal((32, H, hd)), jnp.float32)
    real = np.r_[0:20, 24]
    args = (kp, vp, jnp.asarray(tables), jnp.asarray(meta), 0)
    want = pa.ragged_attend_ref(q, *args, tq=8)
    tile = pa.ragged_tile(H, hd, 8)
    tiles = pa.ragged_tiles(meta, 8, tile, pa.ragged_tile_slots(4, 8, 8, tile))
    for kw in ({}, dict(tiles=jnp.asarray(tiles), tile=tile)):
        got = pa.ragged_attend(q, *args, tq=8, interpret=True, **kw)
        assert float(jnp.abs(got - want)[real].max()) < 1e-5
    jaxpr = str(jax.make_jaxpr(lambda q: pa.ragged_attend(
        q, *args, tq=8, interpret=True))(q))
    assert "kv_layout" not in jaxpr and "pad" not in jaxpr.split("pallas")[0]
