"""Window and full attention layers mixed with routed experts in EVERY layer
behind a SOFTMAX router — no gate, no shared expert, no dense layer (Mellum 2;
ISSUE 41) — at toy widths with the published STRUCTURE: three sliding layers
then a full one (the period's full layer LAST, and no leading segment), query
groups of 8 to a kv head, a window (160) longer than a page (128) and shorter
than the contexts used, 8 of 16 experts a token, all of them held. On the CPU
with seeded random weights: the program against the benchmark's plain
reference (`benchmark/families/window_moe_softmax.py`, written apart from it),
on logits; the softmax gates against their closed form and against sigmoid;
the whole-head YaRN against its closed form; what the configuration states;
the cut in depth against a deeper model of the same seed. The forward's
helpers are `tests/test_window_moe.py`'s (two groups of pools under page ids
of their own).

Tolerances. Program and reference are both float32 here and agree to a few
1e-6 on logits of size 4: 2e-4 leaves room for the different order of their
sums (looped against per-expert sums, a chunk's matmul against the whole
sequence's, the online softmax) and is far below what the mechanisms move:
the window lifted reads 0.05 and more, sigmoid scores in softmax's place
0.05 and more, bfloat16 activations 0.02 and more
(`test_the_tolerance_tells_bfloat16_from_float32`).
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import window_moe_softmax as fam
from benchmark.families.window_moe import inv_freq
from quoracle_tpu.models import transformer as tr
from quoracle_tpu.models.config import (
    MoEConfig, get_model_config, unsupported_path,
)
from tests.test_window_moe import (
    f32, new_pools, reference_logits, tick, tokens_of,
)

TOL = 2e-4
PAGE = 128
WINDOW = 160
L = 8
PERIOD = [fam.SLIDING] * 3 + [fam.FULL]

# the configuration file's keys at toy widths: two whole periods of
# (sliding x 3, full), 16 experts of which a token takes 8, all held
RAW = dict(
    name="toy-mellum", family="window_moe_softmax", model_type="mellum",
    vocab_size=512, hidden_size=64, intermediate_size=96,
    num_hidden_layers=L, num_attention_heads=16, num_key_value_heads=2,
    head_dim=16, max_position_embeddings=4096, attention_bias=False,
    hidden_act="silu", rms_norm_eps=1e-6, num_experts=16,
    num_experts_per_tok=8, moe_intermediate_size=32, norm_topk_prob=True,
    tie_word_embeddings=False, sliding_window=WINDOW,
    use_sliding_window=True, max_window_layers=0,
    rope_parameters={
        fam.FULL: dict(rope_type="yarn", rope_theta=500000, factor=16,
                       original_max_position_embeddings=64, beta_fast=32,
                       beta_slow=1, attention_factor=0.1 * math.log(16) + 1),
        fam.SLIDING: dict(rope_type="default", rope_theta=500000)},
    layer_types=PERIOD * (L // 4), mlp_layer_types=["sparse"] * L,
    torch_dtype="float32", eos_token_id=2, bos_token_id=1,
    serving=dict(context_window=2048, output_limit=128))
SEED = 2 ** 31 + 41


def model(raw):
    cfg = get_model_config(fam.register(raw))
    params = tr.init_params(cfg, jax.random.PRNGKey(SEED), dtype=jnp.bfloat16)
    return cfg, params, fam.Reference(raw, SEED)


@pytest.fixture(scope="module")
def toy():
    return model(RAW)


def deeper(raw, layers):
    return {**raw, "name": f"{raw['name']}-l{layers}",
            "num_hidden_layers": layers,
            "layer_types": PERIOD * (layers // 4),
            "mlp_layer_types": ["sparse"] * layers}


# -- both sides are one model ------------------------------------------------

def test_both_sides_draw_the_same_bits(toy):
    cfg, params, ref = toy
    assert bool((ref.w["embed"] == params["embed"]).all())
    assert bool((ref.w["lm_head"] == params["lm_head"]).all())
    assert ref.w["segments"][0] == [] and params["segments"][0] == ()
    n = 0
    for seg_r, seg_p in zip(ref.w["segments"], params["segments"]):
        for leaves_r, leaves_p in zip(seg_r, seg_p):
            assert set(leaves_r) | {"attn_norm", "mlp_norm"} == set(leaves_p)
            for name, leaf in leaves_r.items():
                assert leaf.shape == leaves_p[name].shape, name
                assert bool((leaf == leaves_p[name]).all()), name
                n += 1
    assert n == 4 * 8               # a period's four layers of eight leaves


def test_the_plan_is_whole_periods_with_the_full_layer_last(toy):
    cfg = toy[0]
    lead, period, tail = cfg.layer_plan
    assert lead == ((), 0) and tail == ((), 0)
    assert period == (((fam.SLIDING, "experts"),) * 3
                      + ((fam.FULL, "experts"),), 2)
    assert fam.plan(fam.shapes(RAW)) == [
        ([], 0), ([(fam.SLIDING, True)] * 3 + [(fam.FULL, True)], 2),
        ([], 0)]
    m = cfg.moe
    assert (m.n_routed, m.n_held, m.per_token, m.n_shared, m.first_dense,
            m.score) == (16, 16, 8, 0, 0, "softmax")
    assert not cfg.attn_gate and cfg.n_dense_layers == 0


def test_one_statement_of_what_a_session_holds(toy):
    cfg = toy[0]
    assert cfg.kv_groups == ((None, 2), (WINDOW, 6))
    assert [cfg.kv_group_of(t) for t in (fam.FULL, fam.SLIDING)] == [0, 1]
    assert cfg.kv_pools == (32, 32) and cfg.n_attn_layers == L
    said = fam.stated_precision(RAW)
    assert said == {"kv_bytes_per_token": 2 * 2 * 32 * 4,
                    "window_kv_bytes_per_token": 6 * 2 * 32 * 4}
    assert cfg.kv_bytes_per_token(dtype_bytes=4, group=0) \
        == said["kv_bytes_per_token"]
    assert cfg.kv_bytes_per_token(dtype_bytes=4, group=1) \
        == said["window_kv_bytes_per_token"]
    assert not cfg.plain and cfg.max_heads == 16
    assert cfg.attn_kind(fam.SLIDING).window == WINDOW
    assert cfg.attn_kind(fam.FULL).window is None
    # the rotary is over the whole head in both kinds
    assert cfg.attn_kind(fam.FULL).rotary_dim is None
    assert cfg.attn_kind(fam.SLIDING).rope_scaling is None


def test_the_parameter_count_is_the_leaves(toy):
    cfg, params, _ = toy
    assert cfg.n_params == tr.param_count(params)
    s = fam.shapes(RAW)
    # decode_weight_bytes: everything outside the routed experts but the
    # norms and the embedding, and 8 experts a layer
    expert = 3 * 64 * 32
    assert fam.routed_expert_bytes(RAW) == 4 * expert
    norms = 2 * 64 * L + 64
    assert fam.decode_weight_bytes(RAW) == 4 * (
        cfg.n_params - L * 16 * expert - norms - s["V"] * s["D"]
        + L * 8 * expert)
    assert fam.decode_step_mark(RAW)["per_step"] == L


@pytest.mark.parametrize("key,value", [
    ("gating", "per-head"), ("shared_expert_intermediate_size", 32),
    ("mlp_only_layers", [0]), ("num_attention_heads_per_layer", [16] * L),
    ("moe_routed_scaling_factor", 2.5), ("attention_bias", True),
    ("mlp_layer_types", ["dense"] + ["sparse"] * (L - 1)),
    ("hidden_act", "gelu"), ("max_window_layers", 4),
])
def test_the_family_refuses_another_forms_keys(key, value):
    """A file that carries a key of a mechanism this family does not
    compute (Laguna's gate, shared expert, dense layer, per-layer heads) is
    refused, not computed without it."""
    with pytest.raises(ValueError, match="window_moe_softmax"):
        fam.shapes({**RAW, key: value})


def test_a_partial_rotary_is_refused():
    rp = {**RAW["rope_parameters"], fam.SLIDING: dict(
        RAW["rope_parameters"][fam.SLIDING], partial_rotary_factor=0.5)}
    with pytest.raises(ValueError, match="whole head"):
        fam.shapes({**RAW, "rope_parameters": rp})


def test_the_path_names_what_it_cannot_carry(toy):
    said = unsupported_path(toy[0], "a mesh")
    for what in ("window and full attention layers mixed", "routed experts"):
        assert what in said
    assert "a gate" not in said and "short-conv" not in said


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
    """The reference with the window lifted is far from the program past
    the window and the program itself up to it."""
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


def test_the_routers_score_function_is_seen(toy):
    """The reference with sigmoid scores in softmax's place — what every
    other expert configuration's router computes, and what a program would
    serve that lost the new field — chooses the same experts and gates them
    otherwise: far from the program at every position, the first too."""
    cfg, params, ref = toy
    ids = tokens_of(8, 200)
    got, _ = tick(cfg, f32(params), new_pools(cfg), [(ids, 0)])
    ref.sigmoid_router = True
    try:
        other = reference_logits(ref, ids, np.arange(200))
    finally:
        ref.sigmoid_router = False
    assert np.abs(got - other).max(-1).min() > 10 * TOL
    assert np.abs(got - other).max() > 0.05 > 50 * TOL
    # and the program with the field left at its default is that model
    sig = dataclasses.replace(cfg, name="toy-mellum-sigmoid",
                              moe=dataclasses.replace(cfg.moe,
                                                      score="sigmoid"))
    lost, _ = tick(sig, f32(params), new_pools(sig), [(ids, 0)])
    assert np.abs(lost - other).max() < TOL


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
    go computes what it computed with them; with a page the window DOES
    reach taken away the logits move."""
    cfg, params, ref = toy
    params = f32(params)
    ids = tokens_of(13, 420)
    want = reference_logits(ref, ids, np.arange(420))
    _, pools = tick(cfg, params, new_pools(cfg), [(ids[:400], 0)])
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


def test_depth_is_the_only_cut(toy):
    """The cut keeps whole periods and every width: the model of 8 layers
    IS the first 8 layers of the model of 12 at the same seed — the same
    bits in every leaf, and the same logits as the deeper reference's
    layers run by hand up to there."""
    _, _, ref = toy
    deep = fam.Reference(deeper(RAW, 12), SEED)
    assert bool((deep.w["embed"] == ref.w["embed"]).all())
    assert bool((deep.w["lm_head"] == ref.w["lm_head"]).all())
    for a, b in zip(ref.w["segments"][1], deep.w["segments"][1]):
        for name in a:
            assert a[name].shape[0] == 2 and b[name].shape[0] == 3
            assert bool((a[name] == b[name][:2]).all()), name
    ids = np.pad(tokens_of(17, 300), (0, 1024 - 300))
    rows = np.arange(300)
    x = deep.w["embed"][jnp.asarray(ids)].astype(jnp.float32)
    kinds = fam.plan(deep.s)[1][0]
    for rep in range(2):
        for w, (kind, _) in zip(deep.w["segments"][1], kinds):
            x = deep._layer(w, x, rep, kind, False, False)
    by_hand = np.asarray(deep._head(deep.w["lm_head"], x, jnp.asarray(rows)))
    assert np.array_equal(by_hand, ref.logits(ids, rows))
    # ... and the whole deeper model is another model
    assert np.abs(deep.logits(ids, rows) - by_hand).max() > 0.05


# -- the router ----------------------------------------------------------------

def test_softmax_gates_against_their_closed_form(toy):
    """softmax over all 16, the 8 largest, renormalised: the gates are
    exp(logit) over the CHOSEN exps' sum, whatever the others score."""
    cfg = toy[0]
    s = fam.shapes(RAW)
    x = jax.random.normal(jax.random.PRNGKey(5), (24, 16), jnp.float32) * 2
    idx, gates = tr.moe_select(x, cfg.moe)
    ridx, rgates = fam.select(jax.nn.softmax(x, -1), s)
    assert np.array_equal(np.asarray(idx), np.asarray(ridx))
    assert np.abs(np.asarray(gates) - np.asarray(rgates)).max() < 1e-6
    xs = np.asarray(x, np.float64)
    order = np.argsort(-xs, axis=-1, kind="stable")[:, :8]
    assert np.array_equal(np.asarray(idx), order)
    e = np.exp(np.take_along_axis(xs, order, -1))
    assert np.abs(np.asarray(gates) - e / e.sum(-1, keepdims=True)).max() \
        < 1e-6
    assert np.abs(np.asarray(gates.sum(-1)) - 1).max() < 1e-6
    # sigmoid chooses the same experts (both rise with the logit) and
    # gates them otherwise, by far more than any tolerance here
    sig = dataclasses.replace(cfg.moe, score="sigmoid")
    sidx, sgates = tr.moe_select(x, sig)
    assert np.array_equal(np.asarray(sidx), np.asarray(idx))
    assert np.abs(np.asarray(sgates) - np.asarray(gates)).max() > 0.1
    # a tie goes to the lower index
    tied = jnp.zeros((1, 16), jnp.float32)
    assert list(np.asarray(tr.moe_select(tied, cfg.moe)[0][0])) \
        == list(range(8))


def _moe_select_as_it_was(logits, m, bias=None):
    """`transformer.moe_select` as PR 40 left it (sigmoid scores, no score
    field), kept here word for word as the yardstick."""
    T, E = logits.shape
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    pick = s if bias is None else s + bias
    if m.n_group > 1:
        g = pick.reshape(T, m.n_group, E // m.n_group)
        group = jax.lax.top_k(g, 2)[0].sum(-1)
        keep = jax.lax.top_k(group, m.topk_group)[1]
        mask = jnp.zeros((T, m.n_group), bool).at[
            jnp.arange(T)[:, None], keep].set(True)
        pick = jnp.where(mask[:, :, None], g,
                         -1.0 if bias is None else -jnp.inf).reshape(T, E)
    gates, idx = jax.lax.top_k(pick, m.per_token)
    if bias is not None:
        gates = jnp.take_along_axis(s, idx, axis=-1)
    if m.norm_topk:
        gates = gates / (gates.sum(-1, keepdims=True) + m.gate_eps)
    return idx.astype(jnp.int32), gates * m.routed_scale


# the accepted expert configurations' routers, at their toys' sizes: with
# groups (ax-k1), with the correction bias (deepseek, lfm2), with a scale
ROUTERS = {
    "lfm2": (MoEConfig(n_routed=16, n_held=16, per_token=4, expert_dim=32,
                       n_shared=0, first_dense=1, router_bias=True,
                       gate_eps=1e-6), True),
    "laguna": (MoEConfig(n_routed=16, n_held=4, per_token=3, expert_dim=32,
                         routed_scale=2.5, first_dense=1), False),
    "ax-k1": (MoEConfig(n_routed=16, n_held=4, per_token=4, expert_dim=32,
                        n_group=4, topk_group=2, routed_scale=2.5,
                        first_dense=1), False),
    "deepseek": (MoEConfig(n_routed=16, n_held=4, per_token=4, expert_dim=32,
                           n_group=4, topk_group=2, routed_scale=2.5,
                           first_dense=1, router_bias=True), True),
}


@pytest.mark.parametrize("name", sorted(ROUTERS))
def test_a_router_without_the_new_field_selects_as_it_did(name):
    """A `MoEConfig` built without `score` selects and gates BIT FOR BIT as
    `moe_select` did before the field: the accepted configurations' routers
    keep their programs' arithmetic."""
    m, biased = ROUTERS[name]
    assert m.score == "sigmoid"
    rng = np.random.default_rng(len(name))
    logits = jnp.asarray(rng.standard_normal((64, 16)) * 3, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(16) * 0.01, jnp.float32) \
        if biased else None
    got = jax.jit(lambda x: tr.moe_select(x, m, bias))(logits)
    want = jax.jit(lambda x: _moe_select_as_it_was(x, m, bias))(logits)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a),
                                                     np.asarray(b))


def test_an_unknown_score_function_is_refused():
    with pytest.raises(AssertionError):
        MoEConfig(n_routed=16, n_held=16, per_token=4, expert_dim=32,
                  score="sparsemax")


# -- the rotary ---------------------------------------------------------------

def test_whole_head_yarn_against_the_closed_form():
    """The program's rotary of a full layer at the published head — all 128
    values rotate, the frequencies blended by parts over the head, cos and
    sin times the attention factor — against the closed form, value by
    value; the sliding layers' plain rotary at the same theta differs only
    where YaRN divides."""
    s = fam.shapes({**RAW, "head_dim": 128, "rope_parameters": {
        **RAW["rope_parameters"], fam.FULL: dict(
            RAW["rope_parameters"][fam.FULL],
            original_max_position_embeddings=8192)}})
    ro = s["rotary"][fam.FULL]
    assert ro["r"] == 128 and abs(ro["yarn"][4] - 1.2772588722239782) < 1e-15
    freq = inv_freq(ro).astype(np.float64)
    plain = 500000.0 ** (-2 * np.arange(64) / 128)
    assert np.allclose(inv_freq(s["rotary"][fam.SLIDING]), plain, rtol=1e-6)
    turns = 8192 * plain / (2 * np.pi)
    assert np.allclose(freq[turns > 40], plain[turns > 40], rtol=1e-6)
    assert np.allclose(freq[turns < 0.9], plain[turns < 0.9] / 16, rtol=1e-6)
    assert ((freq <= plain * (1 + 1e-6)) & (freq >= plain / 16 * (1 - 1e-6))
            ).all()
    x = np.random.default_rng(0).standard_normal((5, 1, 3, 128)).astype(
        np.float32)
    pos = np.asarray([[0], [1], [777], [8192], [100000]], np.int32)
    got = np.asarray(tr.rope(jnp.asarray(x), jnp.asarray(pos), 500000.0,
                             ("yarn", 16.0, 32.0, 1.0, 8192, 1.0, 0.0)))
    ang = (pos[:, :, None, None].astype(np.float32)
           * freq.astype(np.float32)).astype(np.float64)
    c, sn = np.cos(ang) * ro["yarn"][4], np.sin(ang) * ro["yarn"][4]
    want = np.concatenate([x[..., :64] * c - x[..., 64:] * sn,
                           x[..., 64:] * c + x[..., :64] * sn], -1)
    # (a frequency's last float32 bit is 6e-8 of it: times 100,000
    # positions that alone is some 1e-3 rad at the fastest)
    err = np.abs(got - want).reshape(5, -1).max(1)
    assert err[:4].max() < 2e-5 * np.abs(want).max()
    assert err[4] < 2e-4 * np.abs(want).max()
    # position 0 is the head times the attention factor: nothing passes
    assert np.allclose(got[0], x[0] * ro["yarn"][4], rtol=1e-6)


# -- the experts' kernel -------------------------------------------------------

def test_the_forward_with_its_kernels_is_the_forward_without():
    """The same tick with every kernel interpreted (attention at groups of
    8 in both kinds, the grouped experts with all 16 held) against the XLA
    references the CPU serves with, at a head of 128; the kernels' forward
    also says what its blocks cost."""
    from quoracle_tpu.ops import paged_attention as pa
    raw = {**RAW, "name": "toy-mellum-128", "head_dim": 128,
           "num_hidden_layers": 4, "layer_types": PERIOD,
           "mlp_layer_types": ["sparse"] * 4}
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
                for t, n in zip(tables, (41, 23)))
    tiles = i32(pa.ragged_tiles(np.asarray(meta), 8, 32))
    outs = [tr.forward_hidden_ragged(
        params, cfg, i32(pad(ids, 0))[None], i32(pad(p, 0))[None], kp, vp,
        tables, meta, dst, tq=8, interpret=interp, tiles=tl, tile=32)
        for interp, tl in ((None, None), (True, tiles))]
    a, b = (np.asarray(o[0][0, :300]) for o in outs)
    assert np.abs(a - b).max() < 5e-4 * np.abs(a).max()
    loop, grouped = (np.asarray(o[5]) for o in outs)
    assert len(loop) == tr.MOE_STATS and len(grouped) == tr.MOE_STATS_GROUPED
    assert np.array_equal(loop, grouped[:4])
    # 300 tokens x 8 a token over 16 experts in 4 layers, all held: an
    # expert reached has some 150 rows, one block of 256 or two, so the
    # blocks are about half full
    assert list(grouped[:2]) == [300 * 8 * 4] * 2 and grouped[3] == 4
    assert 56 <= grouped[2] <= 64
    assert grouped[2] <= grouped[4] <= grouped[2] + 4 * (300 * 8 // 256)
    assert grouped[5] == 256 * grouped[4]
    assert 0.4 < grouped[1] / grouped[5] < 0.65


def test_the_decode_loop_books_its_blocks():
    """`decode_ragged` with the kernels interpreted, two live rows of
    eight: the six counts ride the loop (`moe_stats_len`), and a step's
    layer runs one 16-row block for each expert its rows reached — 2 rows
    x 8 of 16 experts are 16 assignments in 9 to 16 blocks of 16 rows."""
    from quoracle_tpu.models.generate import decode_ragged
    from tests.test_window_moe import N_WIN
    raw = {**RAW, "name": "toy-mellum-128d", "head_dim": 128,
           "num_hidden_layers": 4, "layer_types": PERIOD,
           "mlp_layer_types": ["sparse"] * 4}
    cfg, params, ref = model(raw)
    p32 = f32(params)
    assert tr.moe_stats_len(cfg, True) == tr.MOE_STATS_GROUPED
    assert tr.moe_stats_len(cfg, None) == tr.MOE_STATS
    a, b = tokens_of(6, 200), tokens_of(7, 40)
    lg, (kp, vp) = tick(cfg, p32, new_pools(cfg), [(a, 0), (b, 0)])
    first = jnp.zeros((8, cfg.vocab_size), jnp.float32).at[:2].set(
        jnp.asarray(lg[[199, 239]]))
    tables = [np.zeros((8, 8), np.int32) for _ in range(2)]
    for r in range(2):                       # `tick`'s layout of its rows
        tables[0][r] = r * 8 + 1 + np.arange(8)
        tables[1][r] = N_WIN - 1 - r * 8 - np.arange(8)
    active = np.zeros((8,), bool)
    active[:2] = True
    res = decode_ragged(
        p32, cfg, kp, vp, tuple(jnp.asarray(t) for t in tables),
        jnp.asarray([200, 40, 0, 0, 0, 0, 0, 0], jnp.int32),
        jnp.zeros((8,), jnp.int32), first, jax.random.PRNGKey(0),
        jnp.zeros((8,), jnp.float32), jnp.ones((8,), jnp.float32), 8,
        eos_id=-1, active=jnp.asarray(active),
        row_limit=jnp.full((8,), 5, jnp.int32), interpret=True)
    out, n_emitted, moe = res[0], res[1], np.asarray(res[8])
    assert list(np.asarray(n_emitted[:2])) == [5, 5]
    # four forwards (the first token comes from the prefill's logits) of
    # two rows through four layers
    total, held, reached, steps, blocks, rows = (int(v) for v in moe)
    assert (total, held, steps) == (4 * 2 * 8 * 4,) * 2 + (4 * 4,)
    assert 9 * 16 <= reached <= 16 * 16
    assert blocks == reached and rows == 16 * blocks
    # ... and what the loop served is the reference's
    seq = np.concatenate([a, np.asarray(out[0, :4])])
    want = reference_logits(ref, seq, np.arange(199, 204))
    gaps = want.max(-1) - want[np.arange(5), np.asarray(out[0, :5])]
    assert gaps.max() < 5e-4
