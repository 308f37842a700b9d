"""Per-row token sampling.

The consensus pipeline needs a DIFFERENT temperature per pool member per
refinement round (reference lib/quoracle/consensus/temperature.ex:84-98 —
temperature descent), so sampling params are [B] arrays, not scalars: one
batched generate step serves heterogeneous sampling configs.

The nucleus runs when a sampled row asks for it: the sort of the
vocabulary, its softmax, cumulative sum and cutoff are one branch of a
``lax.cond`` on the [B] arrays the program already gets, taken when some
row has ``temperature > 0`` and ``top_p < 1``. A batch with no such row
(``top_p`` = 1.0 is what the system sends) draws from the full softmax of
``logits / temperature`` and sorts nothing. Both branches are in every
program: no flag, no static argument, no second compile.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _nucleus_mask(scaled: jax.Array, top_p: jax.Array) -> jax.Array:
    """-inf on every token of ``scaled`` [B, V] beyond its row's top-p
    cumulative mass (at least one token of a row is kept)."""
    sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(sorted_probs, axis=-1)
    # Number of tokens to keep per row (always >= 1).
    keep = jnp.sum(cum - sorted_probs < top_p[:, None], axis=-1)
    cutoff = jnp.take_along_axis(sorted_logits, (keep - 1)[:, None],
                                 axis=-1)
    return jnp.where(scaled < cutoff, -jnp.inf, scaled)


def sample_tokens(
    logits: jax.Array,       # [B, V] fp32
    rng: jax.Array,
    temperature: jax.Array,  # [B] fp32; <= 0 means greedy for that row
    top_p: jax.Array,        # [B] fp32 in (0, 1]; 1.0 asks for no nucleus
) -> jax.Array:
    """Returns [B] int32 sampled token ids. Fully shape-static."""
    greedy = jnp.argmax(logits, axis=-1)

    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp

    # Nucleus mask: drop tokens beyond the top-p cumulative mass, in a
    # batch where a sampled row asks for one. A tick in which one row
    # does runs the mask's arithmetic for all its rows (a row at 1.0
    # keeps everything but what float32 rounds off the cumulative sum).
    with jax.named_scope("top_p"):
        masked = jax.lax.cond(
            jnp.any((temperature > 0) & (top_p < 1)),
            _nucleus_mask, lambda scaled, _: scaled, scaled, top_p)

    sampled = jax.random.categorical(rng, masked, axis=-1)
    return jnp.where(temperature <= 0, greedy, sampled).astype(jnp.int32)
