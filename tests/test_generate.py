"""Generate engine: batched sampling with per-row params."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quoracle_tpu.models.config import get_model_config
from quoracle_tpu.models.generate import GenerateEngine, _round_up
from quoracle_tpu.models.tokenizer import ByteTokenizer
from quoracle_tpu.models.transformer import init_params
from quoracle_tpu.models.sampling import sample_tokens


@pytest.fixture(scope="module")
def engine():
    cfg = get_model_config("xla:tiny")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return GenerateEngine(cfg, params, ByteTokenizer(), max_seq=256,
                          prompt_buckets=(32, 64, 128))


def test_round_up():
    assert _round_up(3, (4, 8)) == 4
    assert _round_up(9, (4, 8)) == 9  # beyond buckets: exact, never truncate


def test_generate_shapes_and_determinism(engine):
    tok = engine.tokenizer
    prompts = [tok.encode("hello", add_bos=True), tok.encode("a much longer prompt here", add_bos=True)]
    rng = jax.random.PRNGKey(42)
    r1 = engine.generate(prompts, temperature=0.0, max_new_tokens=8, rng=rng)
    r2 = engine.generate(prompts, temperature=0.0, max_new_tokens=8, rng=rng)
    assert len(r1) == 2
    for a, b in zip(r1, r2):
        assert a.token_ids == b.token_ids  # greedy => deterministic
        assert a.n_gen_tokens <= 8
        assert a.n_prompt_tokens == len(prompts[r1.index(a)])


def test_batch_independence(engine):
    """Row i's greedy output must not depend on other rows in the batch."""
    tok = engine.tokenizer
    p = tok.encode("independence", add_bos=True)
    solo = engine.generate([p], temperature=0.0, max_new_tokens=6,
                           rng=jax.random.PRNGKey(7))[0]
    batched = engine.generate([tok.encode("xxxx", add_bos=True), p, tok.encode("yy", add_bos=True)],
                              temperature=0.0, max_new_tokens=6,
                              rng=jax.random.PRNGKey(7))[1]
    assert solo.token_ids == batched.token_ids


def test_per_row_temperature(engine):
    tok = engine.tokenizer
    prompts = [tok.encode("same prompt", add_bos=True)] * 2
    res = engine.generate(prompts, temperature=[0.0, 1.5], max_new_tokens=8,
                          rng=jax.random.PRNGKey(0))
    greedy_again = engine.generate([prompts[0]], temperature=0.0, max_new_tokens=8,
                                   rng=jax.random.PRNGKey(1))[0]
    # Greedy row reproduces regardless of rng; hot row is whatever it is.
    assert res[0].token_ids == greedy_again.token_ids


def test_max_tokens_respected(engine):
    tok = engine.tokenizer
    res = engine.generate([tok.encode("abc", add_bos=True)], temperature=1.0,
                          max_new_tokens=5)[0]
    assert res.n_gen_tokens <= 5


def test_overlong_prompt_raises(engine):
    from quoracle_tpu.models.generate import ContextOverflowError
    tok = engine.tokenizer
    with pytest.raises(ContextOverflowError):
        engine.generate([tok.encode("x" * 300, add_bos=True)], max_new_tokens=4)


def test_per_row_limit_near_window(engine):
    """A prompt near the window decodes only up to the window, not past it."""
    tok = engine.tokenizer
    p = tok.encode("x" * 250, add_bos=True)  # 251 tokens, max_seq=256
    r = engine.generate([p], temperature=1.0, max_new_tokens=64)[0]
    assert r.n_gen_tokens <= 256 - 251


def test_sample_tokens_greedy_vs_temp():
    logits = jnp.asarray([[0.0, 5.0, 1.0], [0.0, 5.0, 1.0]], jnp.float32)
    out = sample_tokens(logits, jax.random.PRNGKey(0),
                        temperature=jnp.asarray([0.0, 0.0]),
                        top_p=jnp.asarray([1.0, 1.0]))
    assert out.tolist() == [1, 1]


def test_sample_tokens_top_p_excludes_tail():
    # One dominant token (p≈0.97); top_p=0.5 must always pick it.
    logits = jnp.asarray([[10.0, 5.0, 1.0]], jnp.float32)
    for seed in range(5):
        out = sample_tokens(logits, jax.random.PRNGKey(seed),
                            temperature=jnp.asarray([1.0]),
                            top_p=jnp.asarray([0.5]))
        assert out.tolist() == [0]


def parent_sample_tokens(logits, rng, temperature, top_p):
    """``sample_tokens`` as it was before the nucleus became conditional
    (PR 28): the plain reference, which sorts for every batch."""
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(sorted_probs, axis=-1)
    keep = jnp.sum(cum - sorted_probs < top_p[:, None], axis=-1)
    cutoff = jnp.take_along_axis(sorted_logits, (keep - 1)[:, None],
                                 axis=-1)
    masked = jnp.where(scaled < cutoff, -jnp.inf, scaled)
    sampled = jax.random.categorical(rng, masked, axis=-1)
    return jnp.where(temperature <= 0, greedy, sampled).astype(jnp.int32)


# A vocabulary of 16 whose rarest token keeps a ten-thousandth of the
# mass or more at the temperatures below: the cumulative sum cannot round
# to 1 before the last token, so at top_p 1.0 the reference masks nothing
# either. Row 0 has one dominant token (p > 0.98).
NUCLEUS_LOGITS = jnp.concatenate([
    jnp.asarray([[10.0] + [1.0] * 15], jnp.float32),
    1.5 * jax.random.normal(jax.random.PRNGKey(28), (3, 16), jnp.float32)])


@pytest.fixture
def nucleus_runs(monkeypatch):
    """How often the nucleus branch RAN (a branch not taken is traced,
    and its callback never fires)."""
    from quoracle_tpu.models import sampling
    runs: list = []
    real = sampling._nucleus_mask

    def counted(scaled, top_p):
        jax.debug.callback(lambda: runs.append(1))
        return real(scaled, top_p)
    monkeypatch.setattr(sampling, "_nucleus_mask", counted)
    return runs


@pytest.mark.parametrize("seed", range(4))
def test_sample_tokens_without_a_nucleus_is_the_full_softmax(nucleus_runs,
                                                             seed):
    # every row at top_p 1.0: nothing is sorted, and the tokens are the
    # ones the sorting formula draws with the same key
    temperature = jnp.asarray([1.0, 0.7, 0.0, 1.3])
    top_p = jnp.ones((4,))
    key = jax.random.PRNGKey(seed)
    out = sample_tokens(NUCLEUS_LOGITS, key, temperature, top_p)
    jax.effects_barrier()
    assert nucleus_runs == []
    assert out.tolist() == parent_sample_tokens(
        NUCLEUS_LOGITS, key, temperature, top_p).tolist()
    assert out[2] == jnp.argmax(NUCLEUS_LOGITS[2])


@pytest.mark.parametrize("seed", range(4))
def test_sample_tokens_one_nucleus_row_runs_the_parents_arithmetic(
        nucleus_runs, seed):
    # one sampled row at top_p 0.5 engages the sort for the batch: every
    # row is the reference's, and the dominant-token row always picks it
    temperature = jnp.asarray([1.0, 0.7, 0.0, 1.3])
    top_p = jnp.asarray([0.5, 1.0, 1.0, 0.9])
    key = jax.random.PRNGKey(seed)
    out = sample_tokens(NUCLEUS_LOGITS, key, temperature, top_p)
    jax.effects_barrier()
    assert nucleus_runs == [1]
    assert out.tolist() == parent_sample_tokens(
        NUCLEUS_LOGITS, key, temperature, top_p).tolist()
    assert out[0] == 0


def test_sample_tokens_greedy_row_asks_for_no_nucleus(nucleus_runs):
    # top_p < 1 on a GREEDY row changes nothing, so it sorts nothing
    temperature = jnp.asarray([0.0, 0.7, 1.0, 1.3])
    top_p = jnp.asarray([0.5, 1.0, 1.0, 1.0])
    key = jax.random.PRNGKey(3)
    out = sample_tokens(NUCLEUS_LOGITS, key, temperature, top_p)
    jax.effects_barrier()
    assert nucleus_runs == []
    assert out[0] == jnp.argmax(NUCLEUS_LOGITS[0])
    assert out.tolist() == parent_sample_tokens(
        NUCLEUS_LOGITS, key, temperature, jnp.ones((4,))).tolist()


def test_sample_tokens_one_compile_serves_both_regimes(nucleus_runs):
    # temperature and top_p are traced [B] arrays: flipping a row's top_p
    # takes the other branch of the SAME program
    step = jax.jit(sample_tokens)
    temperature = jnp.asarray([1.0, 0.7, 0.0, 1.3])
    key = jax.random.PRNGKey(5)
    for top_p, runs in ((jnp.ones((4,)), []),
                        (jnp.asarray([1.0, 0.6, 1.0, 1.0]), [1]),
                        (jnp.ones((4,)), [1])):
        out = step(NUCLEUS_LOGITS, key, temperature, top_p)
        jax.effects_barrier()
        assert nucleus_runs == runs
        assert out.tolist() == parent_sample_tokens(
            NUCLEUS_LOGITS, key, temperature, top_p).tolist()
    assert step._cache_size() == 1


def test_tokenizer_roundtrip():
    tok = ByteTokenizer()
    s = "Hello, wörld! 🚀"
    assert tok.decode(tok.encode(s)) == s
    assert tok.count("abc") == 3
