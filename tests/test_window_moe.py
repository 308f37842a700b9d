"""Window and full attention layers mixed, per-kind head counts and rotary, a
gate a head, routed experts with a shared one (Laguna; ISSUE 39), at toy
widths with the published STRUCTURE — a full layer then three sliding ones,
query groups of 6 and 9 to a kv head, a window (160) longer than a page (128)
and shorter than the contexts used — on the CPU with seeded random weights:
the program against the benchmark's plain reference
(`benchmark/families/window_moe.py`, written apart from it), on logits; two
groups of pools under page ids of their own; chunks cut anywhere; the
kernels at groups of 6 and 9; the partial-rotary YaRN against its closed
form; the shares of the experts; what the configuration states.

Tolerances. Program and reference are both float32 here and agree to a few
1e-6 on logits of size 4: 2e-4 leaves room for the different order of their
sums (looped against per-expert sums, a chunk's matmul against the whole
sequence's, the online softmax) and is far below what the mechanisms move:
the window lifted reads 0.05 and more, bfloat16 activations 0.02 and more
(`test_the_tolerance_tells_bfloat16_from_float32`).
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import window_moe as fam
from quoracle_tpu.models import transformer as tr
from quoracle_tpu.models.config import get_model_config, unsupported_path
from quoracle_tpu.ops import paged_attention as pa

TOL = 2e-4
PAGE = 128
WINDOW = 160
L = 9
TYPES = [fam.FULL if i % 4 == 0 else fam.SLIDING for i in range(L)]

# the configuration file's keys at toy widths: the cut's own pattern (a
# leading dense full-attention layer, two periods of sliding x 3, full), 16
# published experts of which 4 are held and a token takes 3
RAW = dict(
    name="toy-laguna", family="window_moe", model_type="laguna",
    vocab_size=512, hidden_size=64, intermediate_size=96,
    num_hidden_layers=L, num_attention_heads=12, num_key_value_heads=2,
    head_dim=16, max_position_embeddings=4096, attention_bias=False,
    rms_norm_eps=1e-6, num_experts=4, num_experts_per_tok=3,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[0],
    tie_word_embeddings=False, gating="per-head", sliding_window=WINDOW,
    rope_parameters={
        fam.FULL: dict(rope_theta=500000, rope_type="yarn", factor=128,
                       original_max_position_embeddings=64, beta_slow=1,
                       beta_fast=32, attention_factor=0.1 * math.log(128) + 1,
                       partial_rotary_factor=0.5),
        fam.SLIDING: dict(rope_type="default", rope_theta=10000,
                          partial_rotary_factor=1)},
    layer_types=TYPES, moe_apply_router_weight_on_input=False,
    mlp_layer_types=["dense"] + ["sparse"] * (L - 1),
    gating_types=["per_head"] * L, moe_routed_scaling_factor=2.5,
    num_attention_heads_per_layer=[12 if t == fam.FULL else 18
                                   for t in TYPES],
    moe_router_logit_softcapping=0, torch_dtype="float32",
    eos_token_id=2, bos_token_id=1, reduced_from=dict(num_experts=16),
    serving=dict(context_window=2048, output_limit=128))
SEED = 2 ** 31 + 39


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


def reference_logits(ref, ids, rows, pad_to=1024):
    return ref.logits(np.pad(np.asarray(ids, np.int32),
                             (0, pad_to - len(ids))), np.asarray(rows))


# -- the forward, called as the engine's programs call it -------------------

N_FULL, N_WIN = 41, 23          # pages of each group's pools: not the same


def new_pools(cfg, dtype=jnp.float32):
    return tuple(tuple(
        jnp.zeros((layers, n, PAGE, cfg.kv_pools[0]), dtype)
        for (_, layers), n in zip(cfg.kv_groups, (N_FULL, N_WIN)))
        for _ in range(2))


@functools.partial(jax.jit, static_argnames=("cfg", "tq"))
def _forward(params, cfg, toks, pos, kp, vp, tables, meta, dst, take, tq):
    out = tr.forward_hidden_ragged(params, cfg, toks[None], pos[None], kp,
                                   vp, tables, meta, dst, tq=tq)
    logits = tr.project_logits(params, cfg, out[0][0][take][None])[0]
    return logits, (out[1], out[2])


def tick(cfg, params, pools, rows, tq=8, released=()):
    """One ragged forward of `rows` = [(tokens, prefix already resident)],
    laid out as `GenerateEngine._run_unified` does it: row r's pages are
    r*8 + 1 .. in the full group and, counted down from the pool's end, in
    the window group — two id spaces — and `released` lists (row, page
    index) entries of the window group's table to zero, as a session that
    let those pages go has them. Returns (logits [T, V] of the real tokens
    in order, pools)."""
    toks, pos, meta, take = [], [], [], []
    dsts = ([], [])
    tables = [np.zeros((8, 8), np.int32) for _ in range(2)]
    for r, (t, pre) in enumerate(rows):
        tables[0][r] = r * 8 + 1 + np.arange(8)
        tables[1][r] = N_WIN - 1 - r * 8 - np.arange(8)
        for (rr, j) in released:
            if rr == r:
                tables[1][r, j] = 0
        nb = -(-len(t) // tq)
        base = len(toks)
        for b in range(nb):
            meta.append((pre + len(t), pre + b * tq,
                         min(tq, len(t) - b * tq), r))
        p = pre + np.arange(len(t))
        pad = nb * tq - len(t)
        toks += list(t) + [0] * pad
        pos += list(p) + [0] * pad
        for g, n in enumerate((N_FULL, N_WIN)):
            dsts[g].extend(list(tables[g][r][p // PAGE] * PAGE + p % PAGE)
                           + [n * PAGE] * pad)
        take += list(range(base, base + len(t)))
    i32 = lambda a: jnp.asarray(np.asarray(a), jnp.int32)      # noqa: E731
    logits, pools = _forward(
        params, cfg, i32(toks), i32(pos), *pools,
        tuple(i32(t) for t in tables), i32(np.asarray(meta).T),
        tuple(i32(d) for d in dsts), i32(take), tq)
    return np.asarray(logits), pools


# -- both sides are one model ------------------------------------------------

def test_both_sides_draw_the_same_bits(toy):
    cfg, params, ref = toy
    assert bool((ref.w["embed"] == params["embed"]).all())
    assert bool((ref.w["lm_head"] == params["lm_head"]).all())
    n = 0
    for seg_r, seg_p in zip(ref.w["segments"], params["segments"]):
        for leaves_r, leaves_p in zip(seg_r, seg_p):
            for name, leaf in leaves_r.items():
                assert leaf.shape == leaves_p[name].shape, name
                assert bool((leaf == leaves_p[name]).all()), name
                n += 1
    assert n == 8 + 4 * 12          # a dense layer's leaves, a period's


def test_the_plan_scans_whole_periods_of_kinds(toy):
    cfg = toy[0]
    lead, period, tail = cfg.layer_plan
    assert lead == (((fam.FULL, "dense"),), 1)
    assert period == (((fam.SLIDING, "experts"),) * 3
                      + ((fam.FULL, "experts"),), 2)
    assert tail == ((), 0)
    assert fam.plan(fam.shapes(RAW)) == [
        ([(fam.FULL, False)], 1),
        ([(fam.SLIDING, True)] * 3 + [(fam.FULL, True)], 2), ([], 0)]


def test_one_statement_of_what_a_session_holds(toy):
    cfg = toy[0]
    assert cfg.kv_groups == ((None, 3), (WINDOW, 6))
    assert [cfg.kv_group_of(t) for t in (fam.FULL, fam.SLIDING)] == [0, 1]
    assert cfg.kv_pools == (32, 32) and cfg.n_attn_layers == L
    assert cfg.kv_bytes_per_token(dtype_bytes=4) == 9 * 2 * 32 * 4
    said = fam.stated_precision(RAW)
    assert said == {"kv_bytes_per_token": 3 * 2 * 32 * 4,
                    "window_kv_bytes_per_token": 6 * 2 * 32 * 4}
    assert cfg.kv_bytes_per_token(dtype_bytes=4, group=0) \
        == said["kv_bytes_per_token"]
    assert cfg.kv_bytes_per_token(dtype_bytes=4, group=1) \
        == said["window_kv_bytes_per_token"]
    assert not cfg.plain and cfg.max_heads == 18
    assert cfg.attn_kind(fam.SLIDING).window == WINDOW
    assert cfg.attn_kind(fam.FULL).rotary_dim == 8
    # every other model is one group, its window the model's
    assert get_model_config("mistral-7b-l16").kv_groups == ((4096, 16),)
    assert get_model_config("tiny").kv_groups == ((None, 2),)


def test_the_parameter_count_is_the_leaves(toy):
    cfg, params, _ = toy
    assert cfg.n_params == tr.param_count(params)
    s = fam.shapes(RAW)
    # decode_weight_bytes: everything but the routed experts and norms
    routed = 8 * 4 * 3 * 64 * 32
    norms = 2 * 64 * L + 64
    assert fam.decode_weight_bytes(RAW) == 4 * (
        cfg.n_params - routed - norms - s["V"] * s["D"])


def test_the_path_names_what_it_cannot_carry(toy):
    said = unsupported_path(toy[0], "a mesh")
    for what in ("window and full attention layers mixed",
                 "a gate on the attention's heads", "routed experts"):
        assert what in said
    assert "window and full" not in unsupported_path(
        dataclasses.replace(get_model_config("tiny"), qk_norm=True), "x")


# -- the program against the reference ---------------------------------------

@pytest.mark.parametrize("n", [40, 200, 333, 520])
def test_reference_agrees_with_the_ragged_forward(toy, n):
    """One chunk of n tokens: within a page, across one, past the window,
    past the window by pages."""
    cfg, params, ref = toy
    ids = tokens_of(n, n)
    got, _ = tick(cfg, f32(params), new_pools(cfg), [(ids, 0)])
    want = reference_logits(ref, ids, np.arange(n))
    assert np.abs(got - want).max() < TOL


def test_the_window_is_seen(toy):
    """The reference with the window lifted — what a program would compute
    whose sliding layers read pages they should have let go — is far from
    the program past the window and the program itself up to it."""
    cfg, params, ref = toy
    ids = tokens_of(7, 400)
    got, _ = tick(cfg, f32(params), new_pools(cfg), [(ids, 0)])
    ref.lift_window = True
    try:
        lifted = reference_logits(ref, ids, np.arange(400))
    finally:
        ref.lift_window = False
    assert np.abs(got - lifted)[:WINDOW].max() < TOL
    assert np.abs(got - lifted)[WINDOW + 40:].max() > 0.05


def test_the_tolerance_tells_bfloat16_from_float32(toy):
    cfg, params, ref = toy
    ids = tokens_of(9, 200)
    got, _ = tick(cfg, params, new_pools(cfg, jnp.bfloat16), [(ids, 0)])
    want = reference_logits(ref, ids, np.arange(200))
    assert np.abs(got - want).max() > 0.02 > 50 * TOL


@pytest.mark.parametrize("step", [1, 3, 32, 150])
def test_a_chunk_may_be_cut_anywhere(toy, step):
    """Prefill then decode through the pages: a prefix, then ticks of
    `step` tokens (1: the decode program's shape), across page boundaries
    and past the window, each against the reference's full forward."""
    cfg, params, ref = toy
    params = f32(params)
    ids = tokens_of(11, 300 + 3 * step)
    want = reference_logits(ref, ids, np.arange(len(ids)))
    got, pools = tick(cfg, params, new_pools(cfg), [(ids[:300], 0)])
    assert np.abs(got - want[:300]).max() < TOL
    for pre in range(300, len(ids), step):
        got, pools = tick(cfg, params, pools, [(ids[pre:pre + step], pre)],
                          tq=1 if step == 1 else 8)
        assert np.abs(got - want[pre:pre + step]).max() < TOL, pre


def test_pages_behind_the_window_are_never_read(toy):
    """A row whose window-group table holds 0 where the session let pages
    go computes what it computed with them: the sliding layers' walk starts
    at the first page the window reaches. With a page the window DOES reach
    taken away the logits move."""
    cfg, params, ref = toy
    params = f32(params)
    ids = tokens_of(13, 420)
    want = reference_logits(ref, ids, np.arange(420))
    _, pools = tick(cfg, params, new_pools(cfg), [(ids[:400], 0)])
    # a query at 400 reaches back to 241: page 1 (128..255) still counts
    got, _ = tick(cfg, params, pools, [(ids[400:], 400)],
                  released=[(0, 0)])
    assert np.abs(got - want[400:]).max() < TOL
    bad, _ = tick(cfg, params, pools, [(ids[400:], 400)],
                  released=[(0, 0), (0, 1)])
    assert np.abs(bad - want[400:]).max() > 1e-3


def test_rows_of_one_tick_never_see_each_other(toy):
    cfg, params, ref = toy
    params = f32(params)
    a, b = tokens_of(15, 290), tokens_of(16, 37)
    got, pools = tick(cfg, params, new_pools(cfg), [(a[:260], 0), (b, 0)])
    assert np.abs(got[:260] - reference_logits(
        ref, a, np.arange(260))).max() < TOL
    assert np.abs(got[260:] - reference_logits(
        ref, b, np.arange(37))).max() < TOL
    got, _ = tick(cfg, params, pools, [(a[260:], 260)])
    assert np.abs(got - reference_logits(
        ref, a, np.arange(260, 290))).max() < TOL


def test_the_program_chooses_the_references_experts(toy):
    cfg, params, ref = toy
    s = fam.shapes(RAW)
    x = jax.random.normal(jax.random.PRNGKey(5), (24, 16), jnp.float32)
    idx, gates = tr.moe_select(x, cfg.moe)
    ridx, rgates = fam.select(jax.nn.sigmoid(x), s)
    assert np.array_equal(np.asarray(idx), np.asarray(ridx))
    assert np.abs(np.asarray(gates) - np.asarray(rgates)).max() < 1e-6
    assert abs(float(gates.sum(-1)[0]) - 2.5) < 1e-5


def test_the_shares_add_up_to_the_uncut_layer(toy):
    """Four shares of the 16 published experts (4 held each), the shared
    expert counted once, equal the layer with all 16 held."""
    cfg, _, _ = toy
    m = cfg.moe
    whole = dataclasses.replace(cfg, name="toy-laguna-whole",
                                moe=dataclasses.replace(m, n_held=16))
    pw = f32(tr.init_params(whole, jax.random.PRNGKey(SEED)))
    layer = pw["segments"][1][0]
    p = {k: v[1] for k, v in layer.items() if not k.startswith("we_")}
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 64), jnp.float32)
    valid = jnp.ones((40,), bool)
    experts = tuple(layer[k] for k in ("we_gate", "we_up", "we_down"))
    want, _ = tr._moe(x, p, experts, 1, whole, valid)
    h = tr.rmsnorm(x, p["mlp_norm"], whole.norm_eps, False)[0]
    shared = tr._gated(h, p["ws_gate"], p["ws_up"], p["ws_down"], "silu")
    total = jnp.zeros_like(x)
    for share in range(4):
        part = dataclasses.replace(cfg, name=f"toy-laguna-{share}",
                                   moe=dataclasses.replace(
                                       m, held_start=4 * share))
        held = tuple(w[:, 4 * share:4 * share + 4] for w in experts)
        y, stats = tr._moe(x, p, held, 1, part, valid)
        total = total + (y - x) - shared[None]
        assert int(stats[0]) == 40 * 3
    assert np.abs(np.asarray(total + shared[None] + x - want)).max() < 1e-5


# -- the rotary ---------------------------------------------------------------

def test_partial_rotary_yarn_against_the_closed_form():
    """The program's rotary of a full layer — the first half of a head, the
    frequencies blended by parts over that half, cos and sin times the
    attention factor — against the closed form, value by value; the sliding
    layers' plain rotary over the whole head."""
    s = fam.shapes({**RAW, "head_dim": 128, "rope_parameters": {
        **RAW["rope_parameters"], fam.FULL: dict(
            RAW["rope_parameters"][fam.FULL],
            original_max_position_embeddings=8192)}})
    ro = s["rotary"][fam.FULL]
    assert ro["r"] == 64 and abs(ro["yarn"][4] - 1.4852030263919618) < 1e-15
    freq = fam.inv_freq(ro).astype(np.float64)
    # by parts: dimension i keeps theta^(-2i/64) where it turns more than
    # 32 times over 8,192 positions, is divided by 128 where less than once
    plain = 500000.0 ** (-2 * np.arange(32) / 64)
    turns = 8192 * plain / (2 * np.pi)
    assert np.allclose(freq[turns > 40], plain[turns > 40], rtol=1e-6)
    assert np.allclose(freq[turns < 0.9], plain[turns < 0.9] / 128,
                       rtol=1e-6)
    assert ((freq <= plain * (1 + 1e-6)) & (freq >= plain / 128 * (1 - 1e-6))
            ).all()
    x = np.random.default_rng(0).standard_normal((5, 1, 3, 128)).astype(
        np.float32)
    pos = np.asarray([[0], [1], [777], [8192], [100000]], np.int32)
    got = np.asarray(tr.rope(jnp.asarray(x), jnp.asarray(pos), 500000.0,
                             ("yarn", 128.0, 32.0, 1.0, 8192, 1.0, 0.0), 64))
    # (the angle as float32 forms it: at position 100,000 its rounding
    # alone is 0.006 rad)
    ang = (pos[:, :, None, None].astype(np.float32)
           * freq.astype(np.float32)).astype(np.float64)
    c, sn = np.cos(ang) * ro["yarn"][4], np.sin(ang) * ro["yarn"][4]
    want = np.concatenate([x[..., :32] * c - x[..., 32:64] * sn,
                           x[..., 32:64] * c + x[..., :32] * sn,
                           x[..., 64:]], -1)
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()
    assert np.array_equal(got[..., 64:], x[..., 64:])
    # the reference's own rotation is the same function
    ref = np.asarray(fam._rope(jnp.asarray(x[:, 0]), ro))[:2]
    assert np.abs(ref - want[:2, 0]).max() < 1e-5
    whole = np.asarray(tr.rope(jnp.asarray(x), jnp.asarray(pos), 10000.0))
    assert not np.array_equal(whole[1:, ..., 64:], x[1:, ..., 64:])


# -- the kernels at query groups of 6 and 9 ----------------------------------

@pytest.mark.parametrize("G,window", [(6, None), (9, 300), (9, 130)])
@pytest.mark.parametrize("walk", ["block", "tile", "decode"])
def test_the_kernels_serve_groups_of_six_and_nine(G, window, walk):
    """`ragged_attend` interpreted against its reference at H = G·KV: the
    block kernel (8 queries a program), the tile kernel and the decode
    call (one query a row, with a shared walk where there is no window),
    rows of 40 to 900 resident tokens, released pages zeroed in the table
    under a window."""
    KV, hd, R, W = 2, 128, 4, 8
    rng = np.random.default_rng(G)
    tq = 1 if walk == "decode" else 8
    lens = [900, 800, 40, 129]
    seg = [1] * R if walk == "decode" else [64, 24, 40, 8]
    kp = jnp.asarray(rng.standard_normal((2, 40, PAGE, KV * hd)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((2, 40, PAGE, KV * hd)),
                     jnp.float32)
    tables = np.zeros((R, W), np.int32)
    tables[:, :] = 1 + np.arange(W)[None] + 8 * np.arange(R)[:, None]
    tables[1, :6] = tables[0, :6]                   # rows 0, 1 share pages
    if window is not None:
        for r in range(R):                          # what was let go
            tables[r, :max(lens[r] - seg[r] + 1 - window, 0) // PAGE] = 0
    meta, q_rows = [], 0
    for r in range(R):
        for b in range(-(-seg[r] // tq)):
            meta.append((lens[r], lens[r] - seg[r] + b * tq,
                         min(tq, seg[r] - b * tq), r))
        q_rows += -(-seg[r] // tq) * tq
    meta = np.asarray(meta, np.int32).T
    q = jnp.asarray(rng.standard_normal((q_rows, G * KV, hd)), jnp.float32)
    kw = {}
    if walk == "tile":
        kw = dict(tiles=jnp.asarray(pa.ragged_tiles(meta, tq, 32)), tile=32)
    if walk == "decode":
        shared = pa.shared_walks(tables, np.asarray(lens), PAGE, window)
        assert (shared[0].max() > 0) == (window is None)
        kw = dict(shared=jnp.asarray(shared))
    got = pa.ragged_attend(q, kp, vp, jnp.asarray(tables),
                           jnp.asarray(meta), 1, tq=tq,
                           sliding_window=window, interpret=True, **kw)
    want = pa.ragged_attend_ref(q, kp, vp, jnp.asarray(tables),
                                jnp.asarray(meta), 1, tq=tq,
                                sliding_window=window)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5


def test_the_forward_with_its_kernels_is_the_forward_without():
    """The same tick with every kernel interpreted (attention at both
    kinds' groups, the grouped experts) against the XLA references the CPU
    serves with, at a head of 128."""
    raw = {**RAW, "name": "toy-laguna-128", "head_dim": 128,
           "num_hidden_layers": 5, "layer_types": TYPES[:5],
           "mlp_layer_types": ["dense"] + ["sparse"] * 4,
           "gating_types": ["per_head"] * 5,
           "num_attention_heads_per_layer": [12, 18, 18, 18, 12]}
    cfg, params, _ = model(raw)
    params = f32(params)
    ids = tokens_of(21, 300)
    kp, vp = new_pools(cfg)
    i32 = lambda a: jnp.asarray(np.asarray(a), jnp.int32)      # noqa: E731
    tables = (i32(np.arange(1, 9)[None].repeat(8, 0)),
              i32(np.arange(9, 17)[None].repeat(8, 0)))
    p = np.arange(300)
    meta = i32([[300] * 38, list(range(0, 304, 8)),
                [8] * 37 + [4], [0] * 38])
    pad = lambda a, fill: np.r_[a, [fill] * 4]                  # noqa: E731
    dst = tuple(i32(pad(np.asarray(t[0])[p // PAGE] * PAGE + p % PAGE,
                        n * PAGE))
                for t, n in zip(tables, (N_FULL, N_WIN)))
    tiles = i32(pa.ragged_tiles(np.asarray(meta), 8, 32))
    outs = [tr.forward_hidden_ragged(
        params, cfg, i32(pad(ids, 0))[None], i32(pad(p, 0))[None], kp, vp,
        tables, meta, dst, tq=8, interpret=interp, tiles=tl, tile=32)
        for interp, tl in ((None, None), (True, tiles))]
    a, b = (np.asarray(o[0][0, :300]) for o in outs)
    assert np.abs(a - b).max() < 5e-4 * np.abs(a).max()
    assert np.array_equal(np.asarray(outs[0][5]), np.asarray(outs[1][5])[:4])
    blocks, rows = (int(v) for v in outs[1][5][4:])
    assert blocks >= int(outs[1][5][2]) and rows == 256 * blocks


def test_a_long_ticks_experts_go_through_a_chunk_at_a_time(toy, monkeypatch):
    """Past MOE_TICK tokens the grouped experts take the tick in pieces (the
    layout holds a row for every assignment: 3 GiB at the benchmark's
    16,384-token tick whole): the same sum, the same counts."""
    cfg = toy[0]
    pw = f32(tr.init_params(cfg, jax.random.PRNGKey(SEED)))
    layer = pw["segments"][1][0]
    p = {k: v[0] for k, v in layer.items() if not k.startswith("we_")}
    experts = tuple(layer[k] for k in ("we_gate", "we_up", "we_down"))
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 96, 64), jnp.float32)
    valid = jnp.arange(96) < 90
    whole, counts = tr._moe(x, p, experts, 0, cfg, valid, True, True)
    monkeypatch.setattr(tr, "MOE_TICK", 32)
    parts, part_counts = tr._moe(x, p, experts, 0, cfg, valid, True, True)
    assert np.abs(np.asarray(whole - parts)).max() < 1e-5
    assert np.array_equal(np.asarray(counts)[:2], np.asarray(part_counts)[:2])
    # the blocks are each piece's own: 3 pieces of 32-row blocks hold what
    # one layout of 96-row blocks held, an expert's rows padded in each
    assert int(counts[2].sum()) == int((counts[0] > 0).sum())
    assert int(part_counts[2].sum()) >= int(counts[2].sum())
    loop, _ = tr._moe(x, p, experts, 0, cfg, valid)
    assert np.abs(np.asarray(whole - loop)).max() < 1e-5
