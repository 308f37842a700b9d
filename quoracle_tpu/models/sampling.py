"""Per-row token sampling.

The consensus pipeline needs a DIFFERENT temperature per pool member per
refinement round (reference lib/quoracle/consensus/temperature.ex:84-98 —
temperature descent), so sampling params are [B] arrays, not scalars: one
batched generate step serves heterogeneous sampling configs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def sample_tokens(
    logits: jax.Array,       # [B, V] fp32
    rng: jax.Array,
    temperature: jax.Array,  # [B] fp32; <= 0 means greedy for that row
    top_p: jax.Array,        # [B] fp32 in (0, 1]; 1.0 disables
) -> jax.Array:
    """Returns [B] int32 sampled token ids. Fully shape-static."""
    B, V = logits.shape

    greedy = jnp.argmax(logits, axis=-1)

    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp

    # Nucleus mask: drop tokens beyond the top-p cumulative mass.
    with jax.named_scope("top_p"):
        sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
        sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(sorted_probs, axis=-1)
        # Number of tokens to keep per row (always >= 1).
        keep = jnp.sum(cum - sorted_probs < top_p[:, None], axis=-1)
        cutoff = jnp.take_along_axis(sorted_logits, (keep - 1)[:, None],
                                     axis=-1)
        masked = jnp.where(scaled < cutoff, -jnp.inf, scaled)

    sampled = jax.random.categorical(rng, masked, axis=-1)
    return jnp.where(temperature <= 0, greedy, sampled).astype(jnp.int32)
