"""The dense family's plain reference against the program's own dense
forward at a tiny size, for a windowed, a biased-and-tied and a plain
configuration: the two are written apart and must agree; and the weights
both draw from one seed are the same bits. Both sides are built from one
configuration file's published keys, so the family's mapping is under test
too."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import dense
from benchmark.reference import gaps_of, served_logits
from quoracle_tpu.models.config import ModelConfig
from quoracle_tpu.models.transformer import (
    forward_hidden, init_cache, init_params, project_logits,
)

BASE = dict(name="t", family="dense", vocab_size=512, hidden_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=128,
            max_position_embeddings=512, rms_norm_eps=1e-6, rope_theta=1e6,
            hidden_act="silu", tie_word_embeddings=False,
            torch_dtype="bfloat16", eos_token_id=2, bos_token_id=1)


@pytest.mark.parametrize("extra", [
    dict(sliding_window=24),
    dict(attention_bias=True, tie_word_embeddings=True,
         num_key_value_heads=1),
    dict(),
], ids=["windowed", "biased-tied", "plain"])
def test_reference_agrees_with_forward_hidden(extra):
    raw = {**BASE, **extra}
    cfg = ModelConfig(**dense.model_kwargs(raw))
    seed = 2 ** 31 + 11
    params = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.bfloat16)
    T = 96
    toks = np.random.default_rng(0).integers(3, 512, (1, T)).astype(np.int32)
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        hid, _ = forward_hidden(
            p32, cfg, jnp.asarray(toks), jnp.arange(T)[None],
            init_cache(cfg, 1, T, dtype=jnp.float32),
            jnp.zeros((1,), jnp.int32), jnp.asarray([T]))
        want = np.asarray(project_logits(p32, cfg, hid))[0]
    ref = dense.Reference(raw, seed)
    assert bool(jnp.all(ref.w["wq"] == params["layers"]["wq"]))
    assert bool(jnp.all(ref.w["embed"] == params["embed"]))
    got = ref.logits(np.pad(toks[0], (0, 32)), np.arange(T))
    assert np.abs(got - want).max() < 1e-4       # float32 against float32
    # the greedy continuation of the reference has gap 0; a wrong token
    # lies whole logits below
    ids = [int(t) for t in toks[0, :64]]
    ids.append(int(want[63].argmax()))
    gaps = gaps_of(served_logits(ref, ids, 64, 128), np.asarray(ids[64:]))
    assert len(gaps) == 1 and gaps[0] == pytest.approx(0.0, abs=1e-4)
    ids[-1] = int(want[63].argmin())
    assert gaps_of(served_logits(ref, ids, 64, 128),
                   np.asarray(ids[64:]))[0] > 1.0
