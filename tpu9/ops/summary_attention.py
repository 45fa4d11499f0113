"""Attention that is exact inside a window and reads chunk summaries of
everything before it.

A query at position ``t`` attends, in ONE softmax, to the keys of its own
window ``{j : j // W == t // W, j <= t}`` (block-diagonal and causal: the
window does not slide) and to one summary for every ``C``-token chunk of every
EARLIER window. A chunk's summary is a softmax-weighted mean of its keys and
of its values, weighted by what the keys score against two learned vectors a
head (``mu`` for the keys, ``phi`` for the values):

    k~_c = sum_j softmax_j(k_j . mu)  k_j      v~_c = sum_j softmax_j(k_j . phi) v_j

(keys after rotary; softmax over the chunk's ``C`` tokens; float32). So a
sequence's cache holds ``W / C`` rows for each closed window and one row a
token only for the open one.

Two things are here, both plain ``jax.numpy``:

- :func:`summarise` — a closed window's keys and values to its summaries:
  the one new device operation of this attention, run where a window closes
  (``serving.graphs``), under the ``kv.summarise`` scope;
- :func:`windowed_summary_attention` — the whole rule densely, for a forward
  pass without a cache (tests, training, whole-prompt prefill).

With a cache nothing else is new: a token at position ``p`` lives at cache
ENTRY ``(W / C) (p // W) + p % W`` (``DecoderConfig.kv_entry``), summaries of
earlier windows sit at the entries below the open window's first, and causal
order in entries IS the rule above — the paged decode kernel and the chunk
kernel run unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import NEG_INF


def init_vectors(rng: jax.Array, kv_heads: int, head_dim: int) -> dict:
    """A layer's two summary vectors for seeded weights, ``[KH, D]`` float32
    like the norm vectors: a normal clipped to +-1 times ``head_dim ** -0.5``,
    so that a key of unit scale scores O(1) against them and the softmax
    over a chunk is neither flat nor one token. A checkpoint brings its
    own."""
    return {name: jnp.clip(jax.random.normal(
        jax.random.fold_in(rng, salt), (kv_heads, head_dim), jnp.float32),
        -1.0, 1.0) * head_dim ** -0.5
        for salt, name in enumerate(("summary_mu", "summary_phi"))}


def summarise(k: jnp.ndarray, v: jnp.ndarray, mu: jnp.ndarray,
              phi: jnp.ndarray, chunk: int) -> tuple:
    """Summaries of ``k``, ``v`` ``[..., T, KH, D]`` (``T`` a multiple of
    ``chunk``; keys after rotary) by ``mu``, ``phi`` ``[KH, D]``:
    ``(k~, v~)``, each ``[..., T / chunk, KH, D]`` in float32."""
    *lead, t, heads, d = k.shape
    kc = k.astype(jnp.float32).reshape(*lead, t // chunk, chunk, heads, d)
    vc = v.astype(jnp.float32).reshape(*lead, t // chunk, chunk, heads, d)

    def weights(vec):
        return jax.nn.softmax(
            jnp.sum(kc * vec.astype(jnp.float32), axis=-1), axis=-2)

    return (jnp.sum(weights(mu)[..., None] * kc, axis=-3),
            jnp.sum(weights(phi)[..., None] * vc, axis=-3))


def windowed_summary_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                               mu: jnp.ndarray, phi: jnp.ndarray,
                               window: int, chunk: int) -> jnp.ndarray:
    """The rule over whole sequences at positions ``0 .. T-1``: q
    ``[B, T, QH, D]``, k/v ``[B, T, KH, D]``. Scores and softmax in float32;
    the summaries are rounded to the keys' type, as a cache would hold
    them."""
    b, t, q_heads, d = q.shape
    pad = -t % chunk
    kp, vp = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (k, v))
    ks, vs = (x.astype(k.dtype) for x in summarise(kp, vp, mu, phi, chunk))
    group = q_heads // k.shape[2]
    if group > 1:
        k, v, ks, vs = (jnp.repeat(x, group, axis=2) for x in (k, v, ks, vs))
    pos = jnp.arange(t)
    own = (pos[:, None] // window == pos[None, :] // window) \
        & (pos[None, :] <= pos[:, None])                      # [T, T]
    # chunk c lies in an earlier window: it starts before the query's own
    earlier = (jnp.arange(ks.shape[1])[None, :] * chunk
               < (pos[:, None] // window) * window)           # [T, T/C]
    qf = q.astype(jnp.float32) * d ** -0.5
    scores = jnp.concatenate([
        jnp.where(own, jnp.einsum("bthd,bshd->bhts", qf,
                                  k.astype(jnp.float32)), NEG_INF),
        jnp.where(earlier, jnp.einsum("bthd,bshd->bhts", qf,
                                      ks.astype(jnp.float32)), NEG_INF)], -1)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs[..., :t],
                     v.astype(jnp.float32)) \
        + jnp.einsum("bhts,bshd->bthd", probs[..., t:], vs.astype(jnp.float32))
    return out.astype(q.dtype)
