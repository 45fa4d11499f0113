"""Rotary position embeddings (RoPE), precomputed-table style.

The table is computed once per model (static shapes, f32) and gathered by
position ids — decode steps index it with dynamic positions without
recomputing sin/cos, keeping the decode graph tiny for XLA.
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


def rope_table(max_len: int, head_dim: int, theta: float = 10000.0,
               yarn: tuple = ()) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (sin, cos), each [max_len, head_dim//2], f32. ``yarn``
    ``(factor, original_max_positions, beta_fast, beta_slow)``: YaRN's
    frequencies (:func:`yarn_inv_freq`) in place of ``theta``'s own."""
    half = head_dim // 2
    if yarn:
        freqs = yarn_inv_freq(half, theta, *yarn)
    else:
        freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32)
                                 / half))
    angles = jnp.arange(max_len, dtype=jnp.float32)[:, None] * freqs[None, :]
    return jnp.sin(angles), jnp.cos(angles)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               sin: jnp.ndarray, cos: jnp.ndarray) -> jnp.ndarray:
    """Rotate ``x`` [..., T, H, D] by per-token ``positions`` [..., T].

    Uses the split-halves convention (x = [x1, x2]; rotate pairs (x1_i, x2_i))
    — the layout used by Llama/Gemma reference JAX implementations.
    """
    dtype = x.dtype
    s = sin[positions].astype(jnp.float32)   # [..., T, D/2]
    c = cos[positions].astype(jnp.float32)
    # broadcast over the heads axis: x is [..., T, H, D], tables [..., T, D/2]
    s = s[..., None, :]
    c = c[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(dtype)
