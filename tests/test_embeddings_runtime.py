"""Embedding encoder, TTL cache, and the ModelBackend seam."""

import jax
import numpy as np
import pytest

from quoracle_tpu.models.config import get_model_config
from quoracle_tpu.models.embeddings import (
    EmbeddingEncoder, HashingEmbedder, cosine_similarity,
)
from quoracle_tpu.models.runtime import (
    MockBackend, QueryRequest, TPUBackend,
)
from quoracle_tpu.models.tokenizer import ByteTokenizer
from quoracle_tpu.models.transformer import init_params
from quoracle_tpu.utils.cache import TTLCache, text_key


# --- TTLCache ---------------------------------------------------------------

def test_ttl_cache_lru_eviction():
    c = TTLCache(max_entries=2, ttl_s=100)
    c.put("a", 1); c.put("b", 2); c.put("c", 3)
    assert c.get("a") is None and c.get("b") == 2 and c.get("c") == 3


def test_ttl_cache_expiry_with_injected_clock():
    now = [0.0]
    c = TTLCache(max_entries=10, ttl_s=10, clock=lambda: now[0])
    c.put("k", "v")
    assert c.get("k") == "v"
    now[0] = 11.0
    assert c.get("k") is None


def test_text_key_namespacing():
    assert text_key("x", "a") != text_key("x", "b")


# --- EmbeddingEncoder -------------------------------------------------------

@pytest.fixture(scope="module")
def encoder():
    cfg = get_model_config("tiny")
    params = init_params(cfg, jax.random.PRNGKey(3))
    return EmbeddingEncoder(cfg, params, ByteTokenizer(), max_tokens=128,
                            chunk_tokens=32)


def test_embed_unit_norm_and_shape(encoder):
    vecs = encoder.embed(["hello world", "goodbye"])
    assert len(vecs) == 2
    for v in vecs:
        assert v.shape == (encoder.dim,)
        np.testing.assert_allclose(np.linalg.norm(v), 1.0, rtol=1e-5)


def test_embed_deterministic_and_cached(encoder):
    v1 = encoder.embed(["same text"])[0]
    hits_before = encoder.cache.hits
    v2 = encoder.embed(["same text"])[0]
    assert encoder.cache.hits == hits_before + 1
    np.testing.assert_allclose(v1, v2)


def test_embed_long_text_chunks(encoder):
    long = "word " * 100  # 500 bytes > chunk_tokens=32
    v = encoder.embed([long])[0]
    np.testing.assert_allclose(np.linalg.norm(v), 1.0, rtol=1e-5)


def test_hashing_embedder_similarity_ordering():
    e = HashingEmbedder()
    a, b, c = e.embed(["create a file named report.txt",
                       "create a file called report.txt",
                       "launch the rocket into orbit"])
    assert cosine_similarity(a, b) > cosine_similarity(a, c)


# --- Backends ---------------------------------------------------------------

def test_mock_backend_scripts_and_recording():
    mb = MockBackend(scripts={"m1": ["r1", "r2"], "m2": ["__error__"]})
    res = mb.query([QueryRequest("m1", [{"role": "user", "content": "q"}]),
                    QueryRequest("m2", [{"role": "user", "content": "q"}])])
    assert res[0].ok and res[0].text == "r1"
    assert not res[1].ok
    assert len(mb.calls) == 2
    res2 = mb.query([QueryRequest("m1", [{"role": "user", "content": "q"}])])
    assert res2[0].text == "r2"


def test_tpu_backend_pool_query_batches_per_model():
    backend = TPUBackend(pool=["xla:tiny", "xla:tiny-gemma"], seed=0)
    msgs = [{"role": "user", "content": "act"}]
    reqs = [QueryRequest("xla:tiny", msgs, temperature=0.0, max_tokens=8),
            QueryRequest("xla:tiny-gemma", msgs, temperature=0.5, max_tokens=8),
            QueryRequest("xla:tiny", msgs, temperature=1.0, max_tokens=8)]
    res = backend.query(reqs)
    assert len(res) == 3
    assert [r.model_spec for r in res] == ["xla:tiny", "xla:tiny-gemma", "xla:tiny"]
    for r in res:
        assert r.ok and r.usage.completion_tokens <= 8
        assert r.usage.prompt_tokens > 0 and r.usage.cost > 0
    backend.close()


def test_tpu_backend_unknown_model_is_permanent_error():
    backend = TPUBackend(pool=["xla:tiny"], seed=0)
    res = backend.query([QueryRequest("xla:nope", [{"role": "user", "content": "x"}])])
    assert not res[0].ok and res[0].permanent_error
    backend.close()


def test_tpu_backend_embed():
    backend = TPUBackend(pool=["xla:tiny"], seed=0)
    v = backend.embed(["abc"])[0]
    assert v.shape == (64,)
    backend.close()


def test_tpu_backend_per_request_budget_enforced():
    """Grouped same-model requests keep their own max_tokens caps."""
    backend = TPUBackend(pool=["xla:tiny"], seed=0)
    msgs = [{"role": "user", "content": "go"}]
    res = backend.query([
        QueryRequest("xla:tiny", msgs, temperature=1.0, max_tokens=4),
        QueryRequest("xla:tiny", msgs, temperature=1.0, max_tokens=32),
    ])
    assert res[0].usage.completion_tokens <= 4
    assert res[1].usage.completion_tokens <= 32
    backend.close()


def test_tpu_backend_per_row_overflow_isolates():
    """One oversized prompt errors alone; its groupmates still run."""
    backend = TPUBackend(pool=["xla:tiny"], seed=0)
    ok = [{"role": "user", "content": "hi"}]
    huge = [{"role": "user", "content": "x" * 2000}]  # tiny window = 512
    res = backend.query([
        QueryRequest("xla:tiny", huge, max_tokens=4),
        QueryRequest("xla:tiny", ok, max_tokens=4),
    ])
    assert not res[0].ok and "context_overflow" in res[0].error
    assert res[1].ok
    backend.close()
