"""Token sampling: greedy / temperature / top-k / top-p, jit-friendly
(static shapes, no data-dependent control flow)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("sample")
def sample_logits(logits: jnp.ndarray, rng: jax.Array,
                  temperature: float = 0.0, top_k: int = 0,
                  top_p: float = 1.0) -> jnp.ndarray:
    """Sample token ids from ``logits`` [..., vocab].

    ``temperature == 0`` → greedy. top_k/top_p are applied before sampling;
    all branches keep static shapes so one jitted graph serves every request.
    """
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)

    logits = logits.astype(jnp.float32) / max(temperature, 1e-6)

    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)

    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative prob >= top_p (always keep 1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)

    return jax.random.categorical(rng, logits, axis=-1)
