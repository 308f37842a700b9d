"""On-device embedding encoder.

Replaces the reference's HTTP embedding provider
(reference lib/quoracle/models/embeddings.ex) with an XLA encoder: mean-pooled
final hidden states of a catalog model, L2-normalized. Embeddings sit on the
consensus CRITICAL PATH (semantic-similarity merge rules call the embedder
during clustering — reference consensus/aggregator.ex:246-289), so this must
be a fast local call: one jitted batched encode, SHA-256 LRU cache in front
(same 1h TTL / 1000 entries semantics as the reference's ETS cache), long
texts token-chunked and averaged (reference embeddings.ex TokenChunker).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from quoracle_tpu.models.config import ModelConfig
from quoracle_tpu.models.tokenizer import Tokenizer
from quoracle_tpu.models.transformer import forward_hidden, init_cache
from quoracle_tpu.utils.cache import TTLCache, text_key


class EmbeddingEncoder:
    """Batched text -> unit vector encoder over a catalog model's hidden states."""

    BATCH_BUCKETS = (1, 4, 16, 64)

    def __init__(self, cfg: ModelConfig, params: dict, tokenizer: Tokenizer,
                 max_tokens: int = 512, cache: Optional[TTLCache] = None,
                 chunk_tokens: int = 256, shard: Optional[tuple] = None):
        """``shard``: the owning engine's ``attn_shard`` when ``params``
        live on a mesh (ops/flash_attention.attend_auto)."""
        self.cfg = cfg
        self.shard = shard
        self.params = params
        self.tokenizer = tokenizer
        self.max_tokens = max_tokens
        self.chunk_tokens = min(chunk_tokens, max_tokens)
        self.cache = cache if cache is not None else TTLCache()
        self._encode = self._build_encode()

    @property
    def dim(self) -> int:
        return self.cfg.dim

    def _build_encode(self):
        cfg = self.cfg

        @jax.jit
        def encode(params, tokens, lens):
            B, T = tokens.shape
            cache = init_cache(cfg, B, T)
            positions = jnp.broadcast_to(
                jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
            hidden, _ = forward_hidden(
                params, cfg, tokens, positions, cache,
                write_offset=jnp.zeros((B,), jnp.int32), kv_lens=lens,
                shard=self.shard)
            mask = (positions < lens[:, None]).astype(jnp.float32)[..., None]
            pooled = jnp.sum(hidden.astype(jnp.float32) * mask, axis=1) \
                / jnp.maximum(jnp.sum(mask, axis=1), 1.0)
            return pooled / jnp.maximum(
                jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)

        return encode

    def _encode_token_batch(self, token_lists: list[list[int]]) -> np.ndarray:
        n = len(token_lists)
        B = next((b for b in self.BATCH_BUCKETS if n <= b), n)
        T = max(8, max(len(t) for t in token_lists))
        T = 1 << (T - 1).bit_length()  # pow2 bucket
        tokens = np.zeros((B, T), np.int32)
        lens = np.ones((B,), np.int32)
        for i, t in enumerate(token_lists):
            tokens[i, :len(t)] = t
            lens[i] = max(1, len(t))
        out = self._encode(self.params, jnp.asarray(tokens), jnp.asarray(lens))
        return np.asarray(out)[:n]

    def embed(self, texts: Sequence[str]) -> list[np.ndarray]:
        """Cached batched embedding. Long texts are chunked and averaged."""
        results: dict[int, np.ndarray] = {}
        pending: list[tuple[int, list[list[int]]]] = []  # (text idx, chunks)
        for i, text in enumerate(texts):
            key = text_key(text, namespace=self.cfg.name)
            hit = self.cache.get(key)
            if hit is not None:
                results[i] = hit
                continue
            ids = self.tokenizer.encode(text or " ")
            chunks = [ids[j:j + self.chunk_tokens]
                      for j in range(0, len(ids), self.chunk_tokens)] or [[0]]
            pending.append((i, chunks))

        if pending:
            flat: list[list[int]] = []
            spans: list[tuple[int, int, int]] = []  # (text idx, start, count)
            for i, chunks in pending:
                spans.append((i, len(flat), len(chunks)))
                flat.extend(chunks)
            vecs = self._encode_token_batch(flat)
            for i, start, count in spans:
                v = vecs[start:start + count].mean(axis=0)
                v = v / max(float(np.linalg.norm(v)), 1e-9)
                results[i] = v
                self.cache.put(text_key(texts[i], namespace=self.cfg.name), v)

        return [results[i] for i in range(len(texts))]


def cosine_similarity(a: np.ndarray, b: np.ndarray) -> float:
    na, nb = float(np.linalg.norm(a)), float(np.linalg.norm(b))
    if na == 0.0 or nb == 0.0:
        return 0.0
    return float(np.dot(a, b) / (na * nb))


@functools.lru_cache(maxsize=None)
def _hash_basis(dim: int, seed: int = 7) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.standard_normal((256, dim)).astype(np.float32)


class HashingEmbedder:
    """Deterministic, model-free embedder for tests (injectable the way the
    reference injects ``embedding_fn`` — aggregator.ex:250-267): byte-ngram
    counts projected through a fixed random basis. Similar strings land close;
    no device work."""

    def __init__(self, dim: int = 64):
        self._dim = dim

    @property
    def dim(self) -> int:
        return self._dim

    def embed(self, texts: Sequence[str]) -> list[np.ndarray]:
        basis = _hash_basis(self._dim)
        out = []
        for text in texts:
            counts = np.zeros(256, np.float32)
            data = text.encode("utf-8", errors="replace")
            for b in data:
                counts[b] += 1.0
            for a, b2 in zip(data, data[1:]):
                counts[(a * 31 + b2) % 256] += 0.5
            v = counts @ basis
            n = float(np.linalg.norm(v))
            out.append(v / n if n > 0 else v)
        return out
