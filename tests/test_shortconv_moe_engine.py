"""LFM2's toy (tests/test_shortconv_moe.py) THROUGH THE ENGINE: the state a
session resumes from, adopts with a cached page, re-prefills for want of,
copies on write and frees with the page; a fan-out's shared prefill; each
refusal. A file of its own so that the two halves run on two workers
(`--dist loadfile`)."""

import jax.numpy as jnp
import numpy as np
import pytest

from quoracle_tpu.models import transformer as tr
from quoracle_tpu.models.generate import GenerateEngine
from quoracle_tpu.models.tokenizer import ByteTokenizer
from tests.test_shortconv_moe import (   # noqa: F401  (toy: a fixture)
    PAGE, RAW, TOL, by_page, f32, model, tokens_of, toy,
)


# -- through the engine: sessions, the prefix cache, the decode loop --------

def new_engine(toy):
    cfg, params, _ = toy
    return GenerateEngine(cfg, f32(params), ByteTokenizer(), max_seq=1024,
                          prompt_buckets=(32, 64, 128, 256, 512))


@pytest.fixture()
def engine(toy):
    return new_engine(toy)


def served(eng, ref, prompt, sid, n=6):
    """Serve `prompt` greedily under session `sid`; returns (the result,
    the larger of: how far the chunk forward's logits at the prompt's end
    lie from the reference's, and the widest gap by which a token the
    decode loop then served lies below the reference's best at its
    position)."""
    chunk = eng._step_paged_ragged
    seen = []

    def spy(*a, **kw):
        out = chunk(*a, **kw)
        seen.append(np.asarray(out[0][0]))
        return out

    eng._step_paged_ragged = spy
    try:
        res = eng.generate([list(prompt)], temperature=0.0,
                           max_new_tokens=n, session_ids=[sid])[0]
    finally:
        eng._step_paged_ragged = chunk
    ids = list(prompt) + res.token_ids
    rows = np.arange(len(prompt) - 1, len(ids) - 1)
    want = ref.logits(np.pad(np.asarray(ids, np.int32),
                             (0, 640 - len(ids))), rows)
    gaps = want.max(-1) - want[np.arange(len(rows)), res.token_ids]
    return res, max(float(gaps.max()), float(np.abs(seen[0] - want[0]).max()))


def state_counts(cfg):
    from quoracle_tpu.infra.telemetry import (
        CONV_STATE_REPREFILL_TOKENS_TOTAL, CONV_STATE_ROWS_TOTAL,
    )
    return {**{s: CONV_STATE_ROWS_TOTAL.value(model=cfg.name, source=s)
               for s in ("carried", "adopted", "zero")},
            "reprefill": CONV_STATE_REPREFILL_TOKENS_TOTAL.value(
                model=cfg.name)}


def moved(cfg, before):
    return {k: v - before[k] for k, v in state_counts(cfg).items()}


def test_a_session_resumes_from_its_own_end(engine, toy):
    cfg, _, ref = toy
    prompt = [int(t) for t in tokens_of(10, 300)]
    first, gap = served(engine, ref, prompt, "a")
    assert gap < TOL and first.n_cached_tokens == 0
    before = state_counts(cfg)
    again = prompt + first.token_ids + [5, 6, 7]
    second, gap = served(engine, ref, again, "a")
    # all but the last generated token is resident: 300 + 5
    assert second.n_cached_tokens == 305 and gap < TOL
    assert moved(cfg, before) == {"carried": 1, "adopted": 0, "zero": 0,
                                  "reprefill": 0}
    q = engine.quant_stats()
    assert q["kv_bytes_per_token"] == 2 * 64 * 4       # float32 pools here
    assert q["state_bytes_per_record"] == 7 * 128 * 4
    assert "-A2-conv7x128-" in engine.kv_signature()
    st = engine.sessions
    assert st.k.shape == (2, st.n_pages, PAGE, 32)
    assert st.state.shape == (7 * st.n_pages, 128)


@pytest.mark.parametrize("zeroed", [False, True])
def test_adopting_a_cached_prefix_starts_from_the_state_at_its_end(
        engine, toy, zeroed):
    """A new session whose prompt begins with two cached pages adopts them
    and the records at their end: the cold run's tokens. With the records
    zeroed it does NOT: the check sees the mechanism."""
    cfg, _, ref = toy
    prompt = [int(t) for t in tokens_of(11, 300)]
    served(engine, ref, prompt, "donor")
    if zeroed:
        engine.sessions.state = jnp.zeros_like(engine.sessions.state)
    before = state_counts(cfg)
    res, gap = served(engine, ref, prompt[:270] + [9, 8, 7], "adopter")
    assert res.n_cached_tokens == 256
    assert moved(cfg, before) == {"carried": 0, "adopted": 1, "zero": 0,
                                  "reprefill": 0}
    assert (gap > 0.05) if zeroed else (gap < TOL)


def test_a_match_inside_a_page_rounds_down_to_where_state_is_held(engine,
                                                                  toy):
    """An edited history: the session's own tokens match up to 200, no
    record is held there, so reuse ends at 128 and 72 tokens run again."""
    cfg, _, ref = toy
    prompt = [int(t) for t in tokens_of(12, 300)]
    served(engine, ref, prompt, "s")
    before = state_counts(cfg)
    edited = prompt[:200] + [int(t) for t in tokens_of(13, 70)]
    res, gap = served(engine, ref, edited, "s")
    assert res.n_cached_tokens == 128 and gap < TOL
    assert moved(cfg, before) == {"carried": 0, "adopted": 1, "zero": 0,
                                  "reprefill": 72}
    assert engine.last_prefill_tokens == 270 - 128


def test_copy_on_write_leaves_the_donors_record_alone(engine, toy):
    """The session's second page is also the radix cache's. An edited
    history rewrites it from its first token: on a FRESH page with a
    record of its own, while the cached page keeps its content and its
    record (I2), and a later adopter of the cached prefix still gets the
    cold run's tokens."""
    cfg, _, ref = toy
    st = engine.sessions
    prompt = [int(t) for t in tokens_of(14, 300)]
    served(engine, ref, prompt, "s")
    cached = st.get("s").pages[1]
    assert st.prefix_cache.holds(cached)
    kept = (by_page(cfg, st.state)[:, cached], np.asarray(st.k[:, cached]))
    cows = st.prefix_cache.cow_copies
    edited = prompt[:200] + [int(t) for t in tokens_of(15, 100)]
    _, gap = served(engine, ref, edited, "s")
    assert gap < TOL and st.prefix_cache.cow_copies == cows + 1
    mine = st.get("s").pages[1]
    assert mine != cached and st.prefix_cache.holds(cached)
    assert np.array_equal(by_page(cfg, st.state)[:, cached], kept[0])
    assert np.array_equal(np.asarray(st.k[:, cached]), kept[1])
    assert not np.array_equal(by_page(cfg, st.state)[:, mine], kept[0])
    res, gap = served(engine, ref, prompt[:280], "late")
    assert res.n_cached_tokens == 256 and gap < TOL


def test_eviction_frees_the_page_and_its_record_with_it(toy):
    """A pool of 6 pages: a second conversation evicts the first's pages
    (the cache's too), takes them over with their stale records, and is
    served as if on a fresh pool."""
    cfg, params, ref = toy
    eng = GenerateEngine(cfg, f32(params), ByteTokenizer(), max_seq=1024,
                         prompt_buckets=(32, 64, 128, 256, 512),
                         session_max_bytes=6 * PAGE * 2 * 64 * 4)
    st = eng.sessions
    eng._ensure_pool()
    assert st.n_pages == 7 and st.state.shape == (7 * 7, 128)
    _, gap = served(eng, ref, [int(t) for t in tokens_of(16, 400)], "one")
    assert gap < TOL and st.free_pages() == 2
    stale = np.asarray(st.state)
    _, gap = served(eng, ref, [int(t) for t in tokens_of(17, 500)], "two")
    assert gap < TOL
    assert st.get("one") is None and st.prefix_cache.evicted_pages >= 1
    assert not np.array_equal(np.asarray(st.state), stale)


def test_a_batch_of_new_sessions_shares_one_prefill(engine, toy):
    """The consensus fan-out: three new sessions with one long prompt in
    ONE call. The second wave adopts the first row's pages and records."""
    cfg, _, ref = toy
    prompt = [int(t) for t in tokens_of(18, 280)]
    before = state_counts(cfg)
    out = engine.generate([prompt + [7], prompt + [8], prompt + [9]],
                          temperature=0.0, max_new_tokens=4,
                          session_ids=["x", "y", "z"])
    assert [r.n_cached_tokens for r in out] == [0, 256, 256]
    assert moved(cfg, before) == {"carried": 0, "adopted": 2, "zero": 1,
                                  "reprefill": 0}
    for r, last in zip(out, (7, 8, 9)):
        ids = prompt + [last] + r.token_ids
        want = ref.logits(np.pad(np.asarray(ids, np.int32),
                                 (0, 384 - len(ids))),
                          np.arange(280, len(ids) - 1))
        gaps = want.max(-1) - want[np.arange(4), r.token_ids]
        assert gaps.max() < TOL


REFUSALS = {
    "forward_hidden": lambda e: tr.forward_hidden(
        e.params, e.cfg, jnp.zeros((1, 4), jnp.int32),
        jnp.zeros((1, 4), jnp.int32), None, None, None),
    "host and disk KV tiers": lambda e: e.attach_tier(host_mb=8),
    "handoff": lambda e: __import__(
        "quoracle_tpu.serving.handoff", fromlist=["KVHandoff"]
    ).KVHandoff().export(e, "a", "xla:toy-lfm2"),
    "drafts": lambda e: __import__(
        "quoracle_tpu.models.speculative", fromlist=["BatchedSpeculator"]
    ).BatchedSpeculator(e, e),
    "baton drafts": lambda e: __import__(
        "quoracle_tpu.models.speculative", fromlist=["SpeculativeDecoder"]
    ).SpeculativeDecoder(e.cfg, e.params, e.cfg, e.params, e.tokenizer),
    "verify_chunk": lambda e: e.verify_chunk([[5, 6, 7]], ["v"], [1]),
}


@pytest.mark.parametrize("path", sorted(REFUSALS))
def test_a_path_that_cannot_carry_the_state_refuses(engine, path):
    with pytest.raises(ValueError) as e:
        REFUSALS[path](engine)
    assert "short-conv state beside the paged KV" in str(e.value)
    assert "ragged paged path of one device" in str(e.value)


@pytest.mark.parametrize("kw,what", [
    (dict(quantize_kv=True), "--quantize-kv"),
    (dict(quantize_weights=True), "--quantize-weights"),
    (dict(mesh="a mesh"), "--tp > 1"),
])
def test_an_engine_option_that_cannot_carry_it_refuses_at_start(toy, kw,
                                                                what):
    cfg, params, _ = toy
    with pytest.raises(ValueError) as e:
        GenerateEngine(cfg, params, ByteTokenizer(), max_seq=256, **kw)
    assert what in str(e.value) and "short-conv state" in str(e.value)
    assert "routed experts" in str(e.value)


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


def test_an_expert_model_without_conv_layers_is_a_pattern_too():
    """Experts beside per-head attention and nothing else (layer_types all
    attention): the same forward, no state pool, the reference's logits."""
    raw = {**RAW, "name": "toy-attn-moe",
           "layer_types": ["full_attention"] * 3, "num_hidden_layers": 3}
    cfg, params, ref = model(raw)
    assert cfg.n_conv_layers == 0 and cfg.layer_plan[1][1] == 2
    eng = GenerateEngine(cfg, f32(params), ByteTokenizer(), max_seq=1024,
                         prompt_buckets=(32, 64, 128, 256, 512))
    _, gap = served(eng, ref, [int(t) for t in tokens_of(19, 200)], "a")
    assert gap < TOL and eng.sessions.state is None


# -- a change to another family's forward leaves these programs alone -------

def test_both_serving_programs_are_the_parents(engine, toy):
    """As `tests/test_latent_moe.py` holds the dense decode program to the
    parent's operation count: this family's two serving programs, lowered
    at the toy's widths (8 rows, a 256-token chunk), hold the operations
    they held before the latent models' output projection changed its
    form (PR 36; read off PR 33's commit d58388d, where both programs
    also hash to the same StableHLO text)."""
    import jax
    from quoracle_tpu.models.generate import RAGGED_TQ
    from quoracle_tpu.ops import paged_attention as pa
    cfg, st = toy[0], engine.sessions
    S, i32, f = jax.ShapeDtypeStruct, jnp.int32, jnp.float32
    R, W, tb = 8, 4, 256
    kv = S((cfg.n_attn_layers, st.n_pages, st.page, cfg.kv_pools[0]),
           engine.pool_dtype)
    state = S((cfg.n_conv_layers * st.n_pages, cfg.state_lanes),
              engine.pool_dtype)
    slots = pa.ragged_tile_slots(tb // RAGGED_TQ, R, RAGGED_TQ,
                                 engine._ragged_tile)
    n_rec = tb // st.page + 2 * R
    chunk = engine._step_paged_ragged.lower(
        engine.params, kv, kv, None, None, S((tb,), i32), S((tb,), i32),
        S((R, W), i32), S((4, tb // RAGGED_TQ), i32), S((6, slots), i32),
        S((tb,), i32), S((R,), i32), state,
        (S((R,), i32), S((tb, cfg.conv_cache - 1), i32), S((n_rec,), i32),
         S((n_rec,), i32)), tq=RAGGED_TQ, tile=engine._ragged_tile)
    decode = engine._step_paged_decode_ragged.lower(
        engine.params, kv, kv, None, None, S((R, W), i32),
        S((2 + pa.SHARED_ROWS, R), i32), S((R,), i32), S((R,), i32),
        S((R, cfg.vocab_size), f), S((2,), jnp.uint32), S((R,), f),
        S((R,), f), S((R,), jnp.bool_), S((R,), i32), None, None, state,
        max_new=32)
    assert [len([ln for ln in low.as_text().splitlines()
                 if " = " in ln and "stablehlo." in ln])
            for low in (chunk, decode)] == SHORTCONV_OPS


SHORTCONV_OPS = [2031, 2537]        # chunk forward, decode loop
