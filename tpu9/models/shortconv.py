"""The gated short convolution of a listed pattern (a ``"conv"`` layer: the
``lfm2`` family, three such layers to one of plain rotary attention, each a
WHOLE layer closed by a dense SwiGLU or an expert layer).
``models.transformer`` walks the layers and calls in here for the mixer; the
refusals are ``models.ssm.refuse_unbuilt_list``'s, the seeded layer
``models.transformer.init_pattern_layer``'s.

The mixer, with ``u`` the normed input and ``K = conv_taps``:

    [B | C | X] = u W_in                          (no bias; ``[D, 3 D]``)
    z = B * X
    c_t = sum_j w_j * z_{t-(K-1)+j}               depthwise, causal, K taps,
                                                  z = 0 before the sequence
    out = (C * c) W_out                           no activation

What a running sequence keeps is the last ``K - 1`` rows of ``z``, in the
model's type: a WINDOW and not a recurrence, so the state at any row is two
rows of a tensor the forward has in hand. That is what lets the prefix cache
stand beside it (``models.kvstate``: the tail a lane, and the tail a BLOCK
that a chunk's forward leaves for every page it fills). The equations, with
every assumption, are in the plain reference the benchmark holds this to
(``benchmark/reference/lfm2.py``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..ops.delta_rule import causal_conv
from ..ops.quant import maybe_matmul
from . import kvstate
from .hybrid import dense_init

F32 = jnp.float32
# device scopes of the mixer, beside ``transformer.DEVICE_SCOPES`` (a tuple of
# their own, as the other kinds': the benchmark's accepted tests pin those):
# the two projections / the gates and the taps. ``attn.qk_norm`` is the plain
# attention's norm a head on queries and keys (``DecoderConfig.qk_norm``)
CONV_SCOPES = ("attn.conv.proj", "attn.conv.mix", "attn.qk_norm")


def init_conv_mixer(rng: jax.Array, cfg) -> dict:
    """One mixer, seeded: the projections normal at the fan-in / fan-out
    scale like every matrix, the taps uniform over ``+- 1 / sqrt(taps)`` (a
    depthwise convolution's default). A checkpoint brings its own."""
    d, k = cfg.dim, cfg.conv_taps
    r_in, r_taps, r_out = jax.random.split(rng, 3)
    return {"w_in": dense_init(r_in, d, 3 * d, cfg.dtype, fan_out=d),
            "conv": jax.random.uniform(r_taps, (k, d), F32,
                                       -k ** -0.5, k ** -0.5),
            "w_out": dense_init(r_out, d, d, cfg.dtype)}


def conv_block(p: dict, u: jnp.ndarray, cfg, kv_cache: Optional[dict],
               plane: int, decode: bool, n_valid, positions):
    """One gated short convolution over the normed input ``u`` [B, T, D].
    With a cache dict the layer's tail is read at ``plane`` and written back
    as of the first ``n_valid[b]`` tokens of lane ``b`` (a padded chunk tail
    and an idle lane leave it untouched), and a chunk over the paged engine's
    scratch also leaves the tail of every page it fills; without one the
    sequence starts from zeros and nothing is kept. Returns ``(y [B, T, D],
    kv_cache)``."""
    b, t, d = u.shape
    if kv_cache is None:
        tail = jnp.zeros((b, cfg.conv_taps - 1, d), u.dtype)
    else:
        (tail,) = kvstate.lane_read(kv_cache, plane, "conv")
    with jax.named_scope("attn.conv.proj"):
        proj = maybe_matmul(u, p["w_in"])
    with jax.named_scope("attn.conv.mix"):
        # the product is rounded to the model's type before the taps, as the
        # tail keeps it: a row convolved in its own chunk and one carried
        # into the next are then the same number
        z = (proj[..., :d].astype(F32) * proj[..., 2 * d:].astype(F32)
             ).astype(u.dtype)
        mixed, tail = causal_conv(z, p["conv"], tail, n_valid)
        gated = (proj[..., d:2 * d].astype(F32) * mixed).astype(u.dtype)
    if kv_cache is not None:
        kv_cache = kvstate.lane_write(kv_cache, plane, tail, kind="conv")
        if not decode:
            kv_cache = kvstate.block_tails_write(kv_cache, plane, z,
                                                 positions[0, 0])
    with jax.named_scope("attn.conv.proj"):
        return maybe_matmul(gated, p["w_out"]), kv_cache
