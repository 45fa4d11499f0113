"""Ragged decode attention: a pallas kernel that reads only each sequence's
valid cache prefix.

Decode attention is HBM-bandwidth-bound: the XLA fallback
(`tpu9.ops.attention.decode_attention`) streams the FULL [S_max] cache per
step and masks. With continuous batching, sequences mostly occupy a small
prefix, so skipping blocks past ``cache_len`` cuts decode HBM traffic by
~S_max/len̄ (the idea behind ragged/paged attention in TPU serving stacks).

How the skipping actually works: the per-sequence length is a scalar-prefetch
operand, and the k/v BlockSpec index maps CLAMP the block index to the last
valid block — Mosaic elides the copy when consecutive grid steps map to the
same block, so clamped (out-of-range) steps issue no DMA; ``pl.when`` then
skips their compute. The kernel consumes the cache in its native
[B, S, KH, D] layout (blocking the S axis directly) — no transpose/copy of
the cache is ever materialized.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _head_update(h, q, k, v, sb, seq_len, m_scr, l_scr, acc_scr,
                 block_s: int):
    """One kv head's online-softmax update for one sequence block — the
    body shared by the bf16 and int8-dequant kernels (q/k/v arrive f32,
    q pre-scaled; dequantization, if any, already happened)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    pos = sb * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < seq_len, s, NEG_INF)

    m_prev = m_scr[h]
    l_prev = l_scr[h]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :1])
    p = jnp.where(pos < seq_len, p, 0.0)
    l_scr[h] = alpha * l_prev + jnp.broadcast_to(
        jnp.sum(p, axis=-1, keepdims=True), l_prev.shape)
    acc_scr[h] = acc_scr[h] * alpha[:, :1] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[h] = m_new


def _finalize_heads(o_ref, m_scr, l_scr, acc_scr, kv_heads: int):
    for h in range(kv_heads):
        l = l_scr[h][:, :1]
        o_ref[0, h] = (acc_scr[h] / jnp.maximum(l, 1e-30)).astype(
            o_ref.dtype)


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_s: int, num_sb: int, kv_heads: int):
    b = pl.program_id(0)
    sb = pl.program_id(1)
    seq_len = len_ref[b]

    @pl.when(sb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(sb * block_s < seq_len)
    def _compute():
        # static unroll over kv heads: Mosaic wants 2D dots, and a KH-sized
        # head block is what makes the k/v BlockSpec tile-legal on TPU (the
        # last two block dims must equal the array's [KH, D])
        for h in range(kv_heads):
            q = q_ref[0, h].astype(jnp.float32) * scale     # [group, D]
            k = k_ref[0, :, h, :].astype(jnp.float32)       # [block_s, D]
            v = v_ref[0, :, h, :].astype(jnp.float32)
            _head_update(h, q, k, v, sb, seq_len, m_scr, l_scr, acc_scr,
                         block_s)

    @pl.when(sb == num_sb - 1)
    def _finalize():
        _finalize_heads(o_ref, m_scr, l_scr, acc_scr, kv_heads)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def ragged_decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                            v_cache: jnp.ndarray, cache_len: jnp.ndarray,
                            block_s: int = 256,
                            interpret: bool = False) -> jnp.ndarray:
    """q [B,1,QH,D]; k/v_cache [B,S,KH,D] (S % block_s == 0); cache_len [B]
    counts valid positions incl. the current token. Returns [B,1,QH,D]."""
    batch, _, q_heads, head_dim = q.shape
    s_max = k_cache.shape[1]
    kv_heads = k_cache.shape[2]
    assert q_heads % kv_heads == 0 and s_max % block_s == 0
    group = q_heads // kv_heads
    num_sb = s_max // block_s

    # [B, KH, group, D]: query heads sharing a kv head form the q rows
    # (pure reshape of contiguous [B, 1, QH, D] — no data movement)
    qt = q.reshape(batch, kv_heads, group, head_dim)

    grid = (batch, num_sb)
    kernel = functools.partial(_kernel, scale=head_dim ** -0.5,
                               block_s=block_s, num_sb=num_sb,
                               kv_heads=kv_heads)

    def kv_index(b, sb, lens):
        # clamp past-the-end steps to the last valid block: same index as the
        # previous step ⇒ Mosaic skips the DMA ⇒ only ceil(len/block_s)
        # blocks of cache are actually read per sequence
        last = jnp.maximum(
            jax.lax.div(lens[b] + block_s - 1, block_s) - 1, 0)
        return (b, jnp.minimum(sb, last), 0, 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, kv_heads, group, head_dim),
                             lambda b, sb, lens: (b, 0, 0, 0)),
                pl.BlockSpec((1, block_s, kv_heads, head_dim), kv_index),
                pl.BlockSpec((1, block_s, kv_heads, head_dim), kv_index),
            ],
            out_specs=pl.BlockSpec((1, kv_heads, group, head_dim),
                                   lambda b, sb, lens: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((kv_heads, group, 128), jnp.float32),
                pltpu.VMEM((kv_heads, group, 128), jnp.float32),
                pltpu.VMEM((kv_heads, group, head_dim), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
    )(cache_len.astype(jnp.int32), qt, k_cache, v_cache)

    return out.reshape(batch, 1, q_heads, head_dim)


# ---------------------------------------------------------------------------
# block-table paged decode: cache lives in a shared block POOL
# ---------------------------------------------------------------------------

def _table_block(table, b, sb, lens, block_s: int):
    """Physical pool block for grid step ``sb``: past-the-end steps CLAMP
    to the sequence's last valid block (same physical index as the
    previous step ⇒ Mosaic elides the DMA), so only ceil(len/BS) pool
    blocks are read per sequence regardless of table width. ONE
    implementation — the bf16 and int8 kernels' index maps (payload AND
    scale planes) must never diverge on this."""
    last = jnp.maximum(
        jax.lax.div(lens[b] + block_s - 1, block_s) - 1, 0)
    return table[b, jnp.minimum(sb, last)]


def _paged_kernel(table_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, block_s: int,
                  num_sb: int, kv_heads: int):
    """Same online-softmax body as _kernel; the difference is entirely in
    the BlockSpec index maps (physical blocks come from the table, the
    pool's layer from ``layer_ref``)."""
    del table_ref, layer_ref
    _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            scale=scale, block_s=block_s, num_sb=num_sb, kv_heads=kv_heads)


def _as_pool(layer, k_pool: jnp.ndarray, *rest: jnp.ndarray):
    """``(layer [1] int32, k_pool, *rest)`` as the kernels take them: the
    pool ``[L, N, BS, ...]`` and the layer to read, a scalar-prefetch
    operand like the table. One layer's plane ``[N, BS, KH, D]`` (with its
    companions) is layer 0 of a one-layer pool, a free reshape, and has no
    other. A kernel never takes a plane cut out of a stacked pool: a pallas
    call takes whole operands, so XLA would copy ``pool[layer]`` out first —
    the index maps pick the layer and a decode step reads the pool where
    it lives. The layer is an operand and not a constant of the index map
    so that every layer of a model runs ONE kernel, traced and lowered
    once: a constant costs a trace per layer in every bring-up (PERF.md §6,
    PR 25)."""
    if k_pool.ndim == 4:
        layer = 0
        k_pool, *rest = (x[None] for x in (k_pool, *rest))
    return (jnp.asarray(layer, jnp.int32).reshape(1), k_pool, *rest)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, block_table: jnp.ndarray,
                           cache_len: jnp.ndarray, layer=0,
                           interpret: bool = False) -> jnp.ndarray:
    """Block-table paged decode attention (vLLM-style, TPU-first).

    q [B,1,QH,D]; k/v_pool [L, N_BLOCKS, BS, KH, D] — the whole POOL, every
    layer of it, shared by every sequence — read at ``layer``, an int or an
    int32 scalar (one layer's [N_BLOCKS, BS, KH, D] plane is taken as a
    one-layer pool); block_table [B, MAX_BLOCKS] int32 maps each sequence's
    logical block i to a physical pool block (entries past the valid prefix
    are ignored); cache_len [B] valid tokens incl. current. Returns
    [B,1,QH,D].

    Reference analogue: the engine-side KV management the reference's
    LLM router assumes (pkg/abstractions/pod/llm.go token pressure); the
    kernel itself is the TPU equivalent of paged_attention — physical
    blocks are DMA'd straight from the pool by table lookup in the
    BlockSpec index map (scalar-prefetch), so fragmentation-free sharing
    (prefix reuse) costs nothing on the read path, and neither does the
    layer (:func:`_as_pool`).
    """
    layer, k_pool, v_pool = _as_pool(layer, k_pool, v_pool)
    batch, _, q_heads, head_dim = q.shape
    _, n_blocks, block_s, kv_heads, _ = k_pool.shape
    max_sb = block_table.shape[1]
    assert q_heads % kv_heads == 0
    group = q_heads // kv_heads

    qt = q.reshape(batch, kv_heads, group, head_dim)
    grid = (batch, max_sb)
    kernel = functools.partial(_paged_kernel, scale=head_dim ** -0.5,
                               block_s=block_s, num_sb=max_sb,
                               kv_heads=kv_heads)

    def kv_index(b, sb, table, lens, layer):
        return (layer[0], _table_block(table, b, sb, lens, block_s),
                0, 0, 0)

    def q_index(b, sb, table, lens, layer):
        return (b, 0, 0, 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, kv_heads, group, head_dim), q_index),
                pl.BlockSpec((None, 1, block_s, kv_heads, head_dim),
                             kv_index),
                pl.BlockSpec((None, 1, block_s, kv_heads, head_dim),
                             kv_index),
            ],
            out_specs=pl.BlockSpec((1, kv_heads, group, head_dim), q_index),
            scratch_shapes=[
                pltpu.VMEM((kv_heads, group, 128), jnp.float32),
                pltpu.VMEM((kv_heads, group, 128), jnp.float32),
                pltpu.VMEM((kv_heads, group, head_dim), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), cache_len.astype(jnp.int32), layer,
      qt, k_pool, v_pool)

    return out.reshape(batch, 1, q_heads, head_dim)


def _paged_quant_kernel(table_ref, len_ref, layer_ref, q_ref, k_ref, v_ref,
                        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr, *,
                        scale: float, block_s: int, num_sb: int,
                        kv_heads: int):
    """int8-pool variant of :func:`_paged_kernel`: the k/v blocks DMA'd by
    table lookup are int8 and the per-vector scales ride in two small f32
    side inputs with the SAME index map — dequantization is one in-register
    multiply per block, so HBM moves half the cache bytes."""
    del table_ref, layer_ref
    b = pl.program_id(0)
    sb = pl.program_id(1)
    seq_len = len_ref[b]

    @pl.when(sb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(sb * block_s < seq_len)
    def _compute():
        for h in range(kv_heads):
            q = q_ref[0, h].astype(jnp.float32) * scale     # [group, D]
            k = (k_ref[0, :, h, :].astype(jnp.float32)
                 * ks_ref[0, :, h][:, None])                # [block_s, D]
            v = (v_ref[0, :, h, :].astype(jnp.float32)
                 * vs_ref[0, :, h][:, None])
            _head_update(h, q, k, v, sb, seq_len, m_scr, l_scr, acc_scr,
                         block_s)

    @pl.when(sb == num_sb - 1)
    def _finalize():
        _finalize_heads(o_ref, m_scr, l_scr, acc_scr, kv_heads)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_quant(q: jnp.ndarray, k_pool: jnp.ndarray,
                                 v_pool: jnp.ndarray,
                                 k_scale: jnp.ndarray,
                                 v_scale: jnp.ndarray,
                                 block_table: jnp.ndarray,
                                 cache_len: jnp.ndarray, layer=0,
                                 interpret: bool = False) -> jnp.ndarray:
    """:func:`paged_decode_attention` over an int8 pool: k/v_pool
    [L, N_BLOCKS, BS, KH, D] int8, k/v_scale [L, N_BLOCKS, BS, KH] f32 (one
    absmax scale per (token, head) vector — ``tpu9.ops.quant.quantize_kv``),
    or one layer's planes of both. Identical masking/softmax semantics; the
    only difference is the in-kernel dequant multiply after each block DMA."""
    layer, k_pool, v_pool, k_scale, v_scale = _as_pool(
        layer, k_pool, v_pool, k_scale, v_scale)
    batch, _, q_heads, head_dim = q.shape
    _, n_blocks, block_s, kv_heads, _ = k_pool.shape
    max_sb = block_table.shape[1]
    assert q_heads % kv_heads == 0
    group = q_heads // kv_heads

    qt = q.reshape(batch, kv_heads, group, head_dim)
    grid = (batch, max_sb)
    kernel = functools.partial(_paged_quant_kernel, scale=head_dim ** -0.5,
                               block_s=block_s, num_sb=max_sb,
                               kv_heads=kv_heads)

    def kv_index(b, sb, table, lens, layer):
        return (layer[0], _table_block(table, b, sb, lens, block_s),
                0, 0, 0)

    def sc_index(b, sb, table, lens, layer):
        return (layer[0], _table_block(table, b, sb, lens, block_s), 0, 0)

    def q_index(b, sb, table, lens, layer):
        return (b, 0, 0, 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, kv_heads, group, head_dim), q_index),
                pl.BlockSpec((None, 1, block_s, kv_heads, head_dim),
                             kv_index),
                pl.BlockSpec((None, 1, block_s, kv_heads, head_dim),
                             kv_index),
                pl.BlockSpec((None, 1, block_s, kv_heads), sc_index),
                pl.BlockSpec((None, 1, block_s, kv_heads), sc_index),
            ],
            out_specs=pl.BlockSpec((1, kv_heads, group, head_dim), q_index),
            scratch_shapes=[
                pltpu.VMEM((kv_heads, group, 128), jnp.float32),
                pltpu.VMEM((kv_heads, group, 128), jnp.float32),
                pltpu.VMEM((kv_heads, group, head_dim), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), cache_len.astype(jnp.int32), layer,
      qt, k_pool, v_pool, k_scale, v_scale)

    return out.reshape(batch, 1, q_heads, head_dim)


def gather_paged(pool: jnp.ndarray, block_table: jnp.ndarray,
                 scale: jnp.ndarray = None,
                 dtype=None, layer: int = 0) -> jnp.ndarray:
    """Densify a paged cache: pool [L,N,BS,KH,D] at ``layer`` (or one
    layer's plane [N,BS,KH,D]) + table [B,MB] → [B, MB*BS, KH, D]. The XLA
    fallback path and the chunked-prefill prefix view both use this: ONE
    gather at ``[layer, table]``, so no plane of a stacked pool is built
    on the way. ``scale`` (one rank less) marks an int8 pool: the scale
    planes are gathered by the SAME table and the result is dequantized
    to ``dtype`` — one implementation of densify+dequant so the
    decode-oracle and verify paths cannot drift."""
    b, mb = block_table.shape
    bs, kh, d = pool.shape[-3:]
    flat = block_table.reshape(-1)

    def rows(x):
        return x[layer, flat] if pool.ndim == 5 else x[flat]

    dense = rows(pool).reshape(b, mb * bs, kh, d)
    if scale is not None:
        from .quant import dequantize_kv
        sc = rows(scale).reshape(b, mb * bs, kh)
        dense = dequantize_kv(dense, sc, dtype or jnp.bfloat16)
    return dense


def xla_paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                               v_pool: jnp.ndarray,
                               block_table: jnp.ndarray,
                               cache_len: jnp.ndarray,
                               k_scale: jnp.ndarray = None,
                               v_scale: jnp.ndarray = None,
                               layer: int = 0) -> jnp.ndarray:
    """Correctness oracle + CPU path: densify then regular ragged decode.
    ``k_scale``/``v_scale`` mark an int8 pool — blocks are dequantized
    right after the gather. Pools and ``layer`` as :func:`gather_paged`."""
    from .attention import xla_decode_attention
    k = gather_paged(k_pool, block_table, k_scale, q.dtype, layer)
    v = gather_paged(v_pool, block_table, v_scale, q.dtype, layer)
    return xla_decode_attention(q, k, v, cache_len)
