"""Chunked-prefill attention: a pallas kernel over the dense scratch that
costs the keys that are written, not ``max_seq_len``.

The XLA form (``tpu9.ops.attention.xla_chunk_prefill_attention``) multiplies
a chunk's queries against the WHOLE ``S``-wide scratch in float32 with the
KV heads repeated to the query heads: a ``[QH, W, S]`` score tensor in HBM,
written and re-read for the mask, the softmax and the second product, in
every layer, whatever the prompt's length. Here:

- the scratch ``[L, B, S, KH, D]`` is read where it lies; the layer and the
  chunk's offset are scalar-prefetch operands, so one kernel body serves
  every layer (and every pass of a looped decoder) and nothing is sliced;
- the key axis is cut into blocks of 512 or 1,024 keys, all KV heads of a
  block in one copy. The block index CLAMPS at the last block that holds a key a
  query may see: a later grid step maps to the block before it, which
  Mosaic does not copy again, and computes nothing — so what is copied and
  multiplied is bounded by the written keys to a block. (Taking the block
  the diagonal crosses by its written 128-key pages, in runs of 1, 2, 4, 8,
  was built and measured: with 1,024-key blocks a Mixtral chunk call at
  offset 0 read 24.2 µs and a group call 76.5, against 23.4 and 78.4 for
  512-key blocks masked whole — and its six more update bodies cost every
  bring-up 2.9–3.5 s: PERF.md §6, PR 44.)
- the ``QH / KH`` query heads of a KV head are rows of ONE query tile
  (``group * block_q`` rows), so a KV head's keys are read once for its
  whole group and nothing is repeated;
- online softmax: running maximum, denominator and accumulator in VMEM,
  float32. A bfloat16 cache is multiplied as bfloat16 with float32
  accumulation — what the XLA form's DEFAULT-precision dots do on the chip
  (PERF.md §6, PR 44) — and a float32 cache as float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import (NEG_INF, _VMEM_LIMIT, _as_pool,
                              head_pair_words, widen_half)

# the narrowest block, of queries or of keys
PAGE = 128
# rows of one query tile: the ``group`` query heads of a KV head, ``block_q``
# positions each. On a v5e an unmasked update of 512 rows costs ≈ 0.45 µs
# and 1.37 µs a 1,024 keys (the two products at the MXU's bf16 peak need
# 1.36), a masked one ≈ 3 µs a 1,024 keys (PERF.md §6, PR 44), so the tile is
# as tall as the float32 scores of one update allow (512 x 1,024: 2 MB).
TILE_ROWS = 512
# a key block: the widest power of two in [MIN, MAX] that divides the cache
# and whose copy (every KV head's rows) stays under BLOCK_BYTES, or MIN. The
# first block's copy is exposed, and the block the diagonal crosses is
# multiplied whole, so narrower is better where a block holds many heads; two
# heads a chip want the widest (an update a head is all a grid step does).
# Swept on the chip at 512 / 1,024 / 2,048 (PERF.md §6, PR 44).
MIN_BLOCK_K, MAX_BLOCK_K = 512, 1024
BLOCK_BYTES = 1 << 20


def chunk_blocks(t: int, s: int, kv_heads: int, group: int, head_dim: int,
                 itemsize: int) -> tuple:
    """``(block_q, block_k)`` for ``t`` queries against an ``s``-wide cache
    (both multiples of 128): powers of two that divide them. The three
    served shapes (KV heads a chip, group): Mixtral (8, 4) → 128 x 512,
    four-chip Mistral (2, 4) → 128 x 1,024, Ouro (16, 1) → 128 or 512 x
    512."""
    block_q = PAGE
    while block_q * 2 * group <= TILE_ROWS and t % (block_q * 2) == 0:
        block_q *= 2
    block_k = PAGE
    while (block_k * 2 <= MAX_BLOCK_K and s % (block_k * 2) == 0
           and (block_k * 2 <= MIN_BLOCK_K or block_k * 2 * kv_heads
                * head_dim * itemsize <= BLOCK_BYTES)):
        block_k *= 2
    return block_q, block_k


def _lanes(x, n: int):
    """``x [R, 128]``, the same in every lane, as ``[R, n]``."""
    if n % 128:
        return x[:, :1] if n > 128 else x[:, :n]
    return pltpu.repeat(x, n // 128, axis=1) if n > 128 else x


def _chunk_kernel(offset_ref, layer_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                  l_scr, acc_scr, *, block_q: int, block_k: int,
                  kv_heads: int):
    """One key block of one query tile a grid step; the KV heads are a loop
    in here, because a block of the cache ``[block_k, KH, D]`` holds them
    all.

    ``q_ref`` / ``o_ref`` ``[KH, R, D]``: row ``r`` of a KV head is its query
    head ``r // block_q`` at position ``first_q + r % block_q``; the queries
    arrive scaled, in the cache's type. A block wholly at or before the
    tile's FIRST query is one unmasked update a head. A block the diagonal
    crosses is one update masked by position; its value rows past the
    tile's LAST query are zeroed, because probability 0 times whatever the
    cache holds there (NaN included) must stay 0. A block past the last
    query was not copied (the index map clamped) and is not touched."""
    del layer_ref
    b, qi, kb = (pl.program_id(i) for i in range(3))
    first_q = offset_ref[b] + qi * block_q
    last_q = first_q + block_q - 1
    first_k = kb * block_k
    rows_q = q_ref.shape[1]

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def block_of_keys(masked: bool):
        """Every KV head's online-softmax update over the block."""
        if masked:
            q_pos = first_q + (jax.lax.broadcasted_iota(
                jnp.int32, (rows_q, block_k), 0) & (block_q - 1))
            visible = first_k + jax.lax.broadcasted_iota(
                jnp.int32, (rows_q, block_k), 1) <= q_pos
            written = first_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0) <= last_q

        def update(h, k, v):
            s = jax.lax.dot_general(q_ref[h], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if masked:
                s = jnp.where(visible, s, NEG_INF)
            # the running maximum and sum are kept the same in all 128
            # lanes, so that they widen to the scores and the accumulator
            # by repeating registers and no lane is broadcast
            m_prev = m_scr[h]                                  # [R, 128]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # key 0 is visible to every query and comes first, so a row's
            # maximum is a real score from its first update on: a masked
            # score's exp underflows to exactly 0
            p = jnp.exp(s - _lanes(m_new, block_k))
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * _lanes(alpha, acc_scr.shape[-1]) \
                + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            m_scr[h] = m_new

        if (k_ref.dtype != jnp.bfloat16 or kv_heads % 2
                or k_ref.shape[-1] != 128):
            for h in range(kv_heads):
                k, v = k_ref[:, h, :], v_ref[:, h, :]
                update(h, k, jnp.where(written, v, 0) if masked else v)
            return

        # a bfloat16 cache of 128-lane rows (Mosaic's strided word load
        # takes no other): a pair of heads by one strided load of 32-bit
        # words (``head_pair_words``), each half back to bfloat16 exactly.
        # The pairs are a loop and not unrolled code (PERF.md §6, PR 40):
        # four update bodies are traced a kernel (two heads, masked or not)
        def one_pair(j, _):
            kw, vw = (head_pair_words(ref, 0, block_k, j)
                      for ref in (k_ref, v_ref))
            if masked:
                vw = jnp.where(written, vw, jnp.uint32(0))
            for odd in (0, 1):
                update(2 * j + odd, *(widen_half(w, odd).astype(jnp.bfloat16)
                                      for w in (kw, vw)))

        if kv_heads == 2:
            one_pair(0, None)
        else:
            jax.lax.fori_loop(0, kv_heads // 2, one_pair, None)

    last_k = first_k + block_k - 1
    pl.when(last_k <= first_q)(functools.partial(block_of_keys, False))
    pl.when((last_k > first_q) & (first_k <= last_q))(
        functools.partial(block_of_keys, True))

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finalize():
        def one(h, _):
            o_ref[h] = (acc_scr[h] / _lanes(l_scr[h], o_ref.shape[-1])).astype(
                o_ref.dtype)
        jax.lax.fori_loop(0, kv_heads, one, None)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_chunk_prefill_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                                  v_cache: jnp.ndarray, offset: jnp.ndarray,
                                  layer=0, block_k: int = 0,
                                  interpret: bool = False) -> jnp.ndarray:
    """``xla_chunk_prefill_attention`` as a flash kernel over the written
    prefix. q [B, T, QH, D] at absolute positions ``offset[b]`` …
    ``offset[b] + T - 1`` (T and S multiples of 128, ``offset + T <= S``);
    k/v_cache the WHOLE dense cache [L, B, S, KH, D], read at ``layer`` (an
    int or an int32 scalar) where it lives, or one layer's [B, S, KH, D].
    ``block_k`` 0: :func:`chunk_blocks`' (a sweep on the chip passes its
    own; nothing that serves does)."""
    layer, k_cache, v_cache = _as_pool(layer, k_cache, v_cache)
    batch, t, q_heads, head_dim = q.shape
    _, _, s, kv_heads, _ = k_cache.shape
    assert q_heads % kv_heads == 0 and t % PAGE == 0 and s % PAGE == 0
    group = q_heads // kv_heads
    block_q, widest = chunk_blocks(t, s, kv_heads, group, head_dim,
                                   k_cache.dtype.itemsize)
    block_k = block_k or widest
    assert s % block_k == 0 and block_k % PAGE == 0
    n_qb, n_kb, rows = t // block_q, s // block_k, group * block_q

    # [B, KH, T/block_q, group * block_q, D], scaled, in the cache's type (on
    # the chip the XLA form's DEFAULT dot rounds its scaled float32 queries
    # to bfloat16 just so): one small fusion, and one of the result back
    qt = (q.astype(jnp.float32) * head_dim ** -0.5).astype(k_cache.dtype)
    qt = qt.reshape(batch, n_qb, block_q, kv_heads, group, head_dim)
    qt = qt.transpose(0, 3, 1, 4, 2, 5).reshape(
        batch, kv_heads, n_qb, rows, head_dim)

    def q_index(b, qi, kb, offset, layer):
        return (b, 0, qi, 0, 0)

    def kv_index(b, qi, kb, offset, layer):
        # the last block that holds a key the tile's last query sees
        last = jnp.minimum(
            jax.lax.div(offset[b] + (qi + 1) * block_q - 1, block_k),
            n_kb - 1)
        return ((layer[0] * batch + b) * n_kb + jnp.minimum(kb, last), 0, 0)

    q_spec = pl.BlockSpec((None, kv_heads, None, rows, head_dim), q_index)
    # the cache as rows of [KH, D], which is how it lies: a free reshape
    kv_spec = pl.BlockSpec((block_k, kv_heads, head_dim), kv_index)
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, block_q=block_q, block_k=block_k,
                          kv_heads=kv_heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch, n_qb, n_kb),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((kv_heads, rows, 128), jnp.float32),
                pltpu.VMEM((kv_heads, rows, 128), jnp.float32),
                pltpu.VMEM((kv_heads, rows, head_dim), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        # inside jit_chunk / jit_group, and no prefix of the decode step
        # marker's name (the benchmark counts decode steps by that)
        name="chunk_prefill_attention",
        interpret=interpret,
    )(offset.astype(jnp.int32), layer, qt,
      *(x.reshape(-1, kv_heads, head_dim) for x in (k_cache, v_cache)))
    return out.reshape(batch, kv_heads, n_qb, group, block_q,
                       head_dim).transpose(0, 2, 4, 1, 3, 5).reshape(q.shape)
