"""Speculative decoding (models/speculative.py): greedy output must be
BIT-IDENTICAL to vanilla GenerateEngine decode — every accepted draft
token equals the target argmax and every correction IS the target argmax,
so any divergence is a cache/rollback bug, not sampling noise.

Self-draft sanity: when the draft IS the target, greedy acceptance is
total — rounds ≈ ceil(max_new / K) — proving the verify chunk reproduces
the step-by-step decode distribution from the same cache state.
"""

import jax
import jax.numpy as jnp
import pytest

from quoracle_tpu.models.config import ModelConfig
from quoracle_tpu.models.generate import GenerateEngine
from quoracle_tpu.models.speculative import SpeculativeDecoder
from quoracle_tpu.models.tokenizer import ByteTokenizer
from quoracle_tpu.models.transformer import init_params

TARGET = ModelConfig(
    name="spec-target", vocab_size=512, dim=96, n_layers=3, n_heads=4,
    n_kv_heads=2, ffn_dim=192, context_window=1024, output_limit=256)
DRAFT = ModelConfig(
    name="spec-draft", vocab_size=512, dim=48, n_layers=2, n_heads=2,
    n_kv_heads=2, ffn_dim=96, context_window=1024, output_limit=256)


@pytest.fixture(scope="module")
def models():
    tp = init_params(TARGET, jax.random.PRNGKey(0), dtype=jnp.float32)
    dp = init_params(DRAFT, jax.random.PRNGKey(1), dtype=jnp.float32)
    return tp, dp


@pytest.fixture(scope="module")
def target_engine(models):
    tp, _ = models
    return GenerateEngine(TARGET, tp, ByteTokenizer(), max_seq=512,
                          prompt_buckets=(32, 64))


def make_spec(models, k=4):
    tp, dp = models
    return SpeculativeDecoder(TARGET, tp, DRAFT, dp, ByteTokenizer(),
                              k=k, max_seq=512, cache_dtype=jnp.float32)


def test_greedy_equals_vanilla_decode(models, target_engine):
    tok = ByteTokenizer()
    spec = make_spec(models, k=4)
    for text in ("speculative decoding test", "a", "the quick brown fox"):
        prompt = tok.encode(text, add_bos=True)
        want = target_engine.generate([prompt], temperature=0.0,
                                      max_new_tokens=48)[0]
        got = spec.generate(prompt, temperature=0.0, max_new_tokens=48)
        assert got.token_ids == want.token_ids, (
            f"spec diverged for {text!r}: accepted={got.accepted}/"
            f"{got.drafted} rounds={got.rounds}")
        assert got.finish_reason == want.finish_reason
        assert got.n_gen_tokens == want.n_gen_tokens


def test_greedy_equality_across_k(models, target_engine):
    tok = ByteTokenizer()
    prompt = tok.encode("k sweep equality", add_bos=True)
    want = target_engine.generate([prompt], temperature=0.0,
                                  max_new_tokens=40)[0].token_ids
    for k in (1, 2, 3, 6, 8):
        got = make_spec(models, k=k).generate(
            prompt, temperature=0.0, max_new_tokens=40)
        assert got.token_ids == want, f"k={k} diverged"


def test_self_draft_accepts_everything(models):
    """Draft == target → greedy proposals always match the verify argmax:
    acceptance is total and rounds collapse to ceil(max_new / K)."""
    tp, _ = models
    tok = ByteTokenizer()
    spec = SpeculativeDecoder(TARGET, tp, TARGET, tp, tok, k=8,
                              max_seq=512, cache_dtype=jnp.float32)
    prompt = tok.encode("self draft acceptance", add_bos=True)
    res = spec.generate(prompt, temperature=0.0, max_new_tokens=32)
    assert res.n_gen_tokens == 32
    assert res.accepted == res.drafted, \
        f"self-draft rejected tokens: {res.accepted}/{res.drafted}"
    assert res.rounds == 4                       # ceil(32 / 8)
    assert res.tokens_per_round == 8.0


def test_sampled_mode_mechanics(models):
    """Temperature > 0: the rejection sampler must produce valid tokens,
    respect max_new, and report acceptance stats; exact distribution
    equality is the algorithm's guarantee, not unit-testable cheaply."""
    tok = ByteTokenizer()
    spec = make_spec(models, k=4)
    prompt = tok.encode("sampled speculative", add_bos=True)
    res = spec.generate(prompt, temperature=0.8, max_new_tokens=24,
                        rng=jax.random.PRNGKey(7))
    assert 0 < res.n_gen_tokens <= 24
    assert all(0 <= t < TARGET.vocab_size for t in res.token_ids)
    assert res.drafted >= res.accepted >= 0
    assert res.rounds >= res.n_gen_tokens / (spec.k + 1) - 1e-9
    with pytest.raises(AssertionError):
        spec.generate(prompt, temperature=0.8, top_p=0.9)


def test_stop_token_truncates(models, target_engine):
    """A stop token inside an accepted draft run truncates the output at
    the stop, matching vanilla semantics."""
    tok = ByteTokenizer()
    spec = make_spec(models, k=4)
    # find a prompt whose greedy continuation hits eos within the budget,
    # if any; regardless, spec must agree with vanilla exactly
    prompt = tok.encode("stop handling", add_bos=True)
    want = target_engine.generate([prompt], temperature=0.0,
                                  max_new_tokens=64)[0]
    got = spec.generate(prompt, temperature=0.0, max_new_tokens=64)
    assert got.token_ids == want.token_ids
    assert got.finish_reason == want.finish_reason


def test_constrained_greedy_equals_vanilla_constrained(models,
                                                       target_engine):
    """Grammar-masked speculation must match the engine's constrained
    greedy decode token for token — the draft proposes under the same
    token-DFA mask and the verify pass re-applies it per position."""
    tok = ByteTokenizer()
    spec = make_spec(models, k=4)
    enum = ("wait", "todo", "send_message")
    for text in ("emit an action", "respond with json"):
        prompt = tok.encode(text, add_bos=True)
        want = target_engine.generate(
            [prompt], temperature=0.0, max_new_tokens=48,
            constrain_json=[True], action_enums=[enum])[0]
        got = spec.generate(prompt, temperature=0.0, max_new_tokens=48,
                            constrain_json=True, action_enum=enum)
        assert got.token_ids == want.token_ids, (
            f"constrained spec diverged for {text!r}:\n"
            f" want {tok.decode(want.token_ids)!r}\n"
            f"  got {tok.decode(got.token_ids)!r}")
        assert got.finish_reason == want.finish_reason
        # the output really is grammar-shaped
        assert got.text.lstrip().startswith("{")


def test_constrained_plain_json_no_enum(models, target_engine):
    tok = ByteTokenizer()
    spec = make_spec(models, k=3)
    prompt = tok.encode("plain json please", add_bos=True)
    want = target_engine.generate([prompt], temperature=0.0,
                                  max_new_tokens=32,
                                  constrain_json=[True])[0]
    got = spec.generate(prompt, temperature=0.0, max_new_tokens=32,
                        constrain_json=True)
    assert got.token_ids == want.token_ids
    assert got.text.lstrip().startswith("{")


def test_session_resume_splices_and_matches_fresh(models, target_engine):
    """Speculative sessions: a refinement-shaped second round (prior
    prompt + response + new message) reuses the resident prefix — only
    the glue forwards — and its output is identical to a fresh
    speculative run AND to vanilla engine decode."""
    tok = ByteTokenizer()
    spec = make_spec(models, k=4)
    p1 = tok.encode("round one prompt", add_bos=True)
    r1 = spec.generate(p1, temperature=0.0, max_new_tokens=24,
                       session_id="s")
    assert r1.n_cached_tokens == 0
    p2 = p1 + r1.token_ids + tok.encode(" refine the answer")
    r2 = spec.generate(p2, temperature=0.0, max_new_tokens=24,
                       session_id="s")
    assert r2.n_cached_tokens == len(p1) + len(r1.token_ids)
    fresh = make_spec(models, k=4).generate(p2, temperature=0.0,
                                            max_new_tokens=24)
    assert r2.token_ids == fresh.token_ids, "session resume diverged"
    want = target_engine.generate([p2], temperature=0.0,
                                  max_new_tokens=24)[0]
    assert r2.token_ids == want.token_ids
    # a divergent prompt drops the session and runs fresh, correctly
    p3 = tok.encode("completely different task", add_bos=True)
    r3 = spec.generate(p3, temperature=0.0, max_new_tokens=12,
                       session_id="s")
    assert r3.n_cached_tokens == 0
    want3 = target_engine.generate([p3], temperature=0.0,
                                   max_new_tokens=12)[0]
    assert r3.token_ids == want3.token_ids
    spec.drop_session("s")
    assert "s" not in spec._sessions


def test_session_resume_constrained(models, target_engine):
    """Sessions compose with the grammar: each round's JSON block starts
    at the grammar start state while the KV prefix splices."""
    tok = ByteTokenizer()
    spec = make_spec(models, k=4)
    enum = ("wait", "todo")
    p1 = tok.encode("emit action one", add_bos=True)
    r1 = spec.generate(p1, temperature=0.0, max_new_tokens=32,
                       constrain_json=True, action_enum=enum,
                       session_id="cs")
    p2 = p1 + r1.token_ids + tok.encode(" now refine")
    r2 = spec.generate(p2, temperature=0.0, max_new_tokens=32,
                       constrain_json=True, action_enum=enum,
                       session_id="cs")
    assert r2.n_cached_tokens == len(p1) + len(r1.token_ids)
    want = target_engine.generate([p2], temperature=0.0,
                                  max_new_tokens=32, constrain_json=[True],
                                  action_enums=[enum])[0]
    assert r2.token_ids == want.token_ids
    assert r2.text.lstrip().startswith("{")


def test_property_greedy_equality_random_shapes(models, target_engine):
    """Randomized edge shapes (seeded, not hypothesis — each case costs a
    device call): prompt lengths down to 1, K from 1 up, max_new down to
    1, random token ids. Greedy speculation must match vanilla decode on
    every one — the shapes most likely to break the splice/rollback
    arithmetic are exactly the tiny ones."""
    import random
    rng = random.Random(20260730)
    spec_by_k = {}
    for case in range(12):
        k = rng.choice([1, 2, 3, 5, 8])
        n_prompt = rng.choice([1, 2, 3, 7, 19, 40])
        max_new = rng.choice([1, 2, 5, 17, 32])
        prompt = [rng.randrange(4, TARGET.vocab_size)
                  for _ in range(n_prompt)]
        want = target_engine.generate([prompt], temperature=0.0,
                                      max_new_tokens=max_new)[0]
        dec = spec_by_k.setdefault(k, make_spec(models, k=k))
        got = dec.generate(prompt, temperature=0.0,
                           max_new_tokens=max_new)
        assert got.token_ids == want.token_ids, (
            f"case {case}: k={k} n_prompt={n_prompt} max_new={max_new}")
        assert got.finish_reason == want.finish_reason, (
            f"case {case}: k={k} n_prompt={n_prompt} max_new={max_new}")


def test_vocab_mismatch_rejected(models):
    tp, dp = models
    bad = ModelConfig(name="bad-draft", vocab_size=256, dim=48, n_layers=2,
                      n_heads=2, n_kv_heads=2, ffn_dim=96)
    with pytest.raises(AssertionError):
        SpeculativeDecoder(TARGET, tp, bad,
                           init_params(bad, jax.random.PRNGKey(2)),
                           ByteTokenizer())
