"""Rotary position embeddings (RoPE): the angles of the rows a program feeds.

:func:`rope_rows` takes the positions of a forward's tokens (``[B, 1]`` in a
decode step, a chunk's or a group's width in prefill) and returns their
``sin`` / ``cos``; :func:`apply_rope` rotates by them. A forward makes the
one pair and every layer, and every pass of a looped decoder, reuses it.
No program builds a table over ``max_position_embeddings`` rows to gather a
handful of them: the compiler did not fold that table, and building it was
0.84 ms of every 11.15 ms decode step of ``kimi-docs`` (262,144 x 32 sines
and cosines for the 16 x 32 values the step used; ledger, PR 59).
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature (arXiv:2309.00071, section 3.4, in the
    form DeepSeek-V3's modeling code has): ``0.1 mscale ln(factor) + 1``
    for a context stretched ``factor`` times, 1 for one that is not."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(half: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> jnp.ndarray:
    """YaRN's frequencies ``[half]``: dimension ``i`` of ``half`` turns
    ``f_i = theta^(-i / half)`` a position. One that turns more than
    ``beta_fast`` times over the ``original`` context keeps ``f_i``, one
    that turns fewer than ``beta_slow`` times is slowed to ``f_i / factor``,
    and a linear ramp over the dimensions in between blends the two."""
    def turns_at(turns: float) -> float:
        # the dimension that turns ``turns`` times over ``original``
        return half * math.log(original / (turns * 2 * math.pi)) \
            / math.log(theta)

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), 2 * half - 1)
    if low == high:
        high += 0.001
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def rope_rows(positions: jnp.ndarray, head_dim: int, theta: float = 10000.0,
              yarn: tuple = ()) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (sin, cos) of per-token ``positions`` [..., T], each
    [..., T, head_dim//2], f32. ``yarn`` ``(factor, original_max_positions,
    beta_fast, beta_slow)``: YaRN's frequencies (:func:`yarn_inv_freq`) in
    place of ``theta``'s own. (A position below 2^24 is exact in float32.)"""
    half = head_dim // 2
    if yarn:
        freqs = yarn_inv_freq(half, theta, *yarn)
    else:
        freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32)
                                 / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(angles), jnp.cos(angles)


def apply_rope(x: jnp.ndarray, sin: jnp.ndarray,
               cos: jnp.ndarray) -> jnp.ndarray:
    """Rotate ``x`` [..., T, H, D] by its tokens' ``sin`` / ``cos``
    [..., T, D/2] (:func:`rope_rows` of their positions).

    Uses the split-halves convention (x = [x1, x2]; rotate pairs (x1_i, x2_i))
    — the layout used by Llama/Gemma reference JAX implementations.
    """
    dtype = x.dtype
    # broadcast over the heads axis: x is [..., T, H, D], rows [..., T, D/2]
    s = sin[..., None, :]
    c = cos[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(dtype)
