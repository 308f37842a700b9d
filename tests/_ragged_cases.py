"""What the ragged-attention test files share (tests/test_ragged_*.py,
one file a kernel so that ``--dist loadfile`` can spread them): the tiny
engine, the byte encoder and the seam that pins an engine to the gather
programs."""

import jax
import jax.numpy as jnp

from quoracle_tpu.models.config import get_model_config
from quoracle_tpu.models.generate import GenerateEngine
from quoracle_tpu.models.tokenizer import ByteTokenizer
from quoracle_tpu.models.transformer import init_params


def make_engine(name="xla:tiny", seed=0, **kw):
    cfg = get_model_config(name)
    params = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)
    return GenerateEngine(cfg, params, ByteTokenizer(),
                          max_seq=kw.pop("max_seq", 256),
                          prompt_buckets=kw.pop("prompt_buckets",
                                                (32, 64, 128)),
                          **kw)


def enc(text):
    return ByteTokenizer().encode(text, add_bos=True)


def _gather(eng):
    eng._force_gather_decode = True     # the equality/fallback seam
    return eng
