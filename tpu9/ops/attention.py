"""Attention: blocked flash attention as a Pallas TPU kernel, with an XLA
fallback, GQA support, and a decode-step path.

Design notes (see /opt/skills/guides/pallas_guide.md):
- grid = (batch*q_heads, q_blocks, k_blocks); k is the innermost sequential
  dimension so VMEM scratch (running max/denominator/accumulator) carries
  across k blocks — the standard online-softmax flash schedule.
- blocks are (128, head_dim): MXU-shaped, satisfies bf16 (16,128) tiling.
- causal blocks fully above the diagonal are skipped via ``pl.when`` so the
  kernel does ~half the work of the dense path at long sequence lengths.
- accumulation in f32; inputs may be bf16.
- chunked prefill has a kernel of its own (``ops/chunk_attention.py``): the
  same schedule over the dense scratch where it lies, bounded by the keys
  that are written.

On CPU (tests) the same kernel runs with ``interpret=True``; model code picks
the XLA path automatically when not on TPU.

The dispatchers (:func:`attention`, :func:`decode_attention`,
:func:`chunk_prefill_attention`, :func:`paged_attention_dispatch`) take the
serving mesh: GSPMD cannot partition a Mosaic kernel, so on a mesh each chip
runs the kernel on its own heads under ``shard_map``. Each has a
``*_kernel_declined`` twin that says why a shape takes the XLA oracle instead
— the engine logs and reports it at build, so a TPU replica that is not
running the kernels is visible.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..utils import on_tpu

NEG_INF = -1e30

# mesh axis the q/kv HEAD axis shards over (serving.shard's tp axis)
HEAD_AXIS = "tp"
_KERNEL_HEAD_DIMS = (64, 128, 256)
# every head-carrying operand keeps its heads on axis 2: q [B,T,QH,D],
# k/v cache [B,S,KH,D], one layer's plane of the paged pool [N,BS,KH,D] and
# of its scales [N,BS,KH]; the whole pool has the layer axis in front
_HEADS4 = P(None, None, HEAD_AXIS, None)
_HEADS3 = P(None, None, HEAD_AXIS)
_POOL5 = P(None, None, None, HEAD_AXIS, None)
_POOL4 = P(None, None, None, HEAD_AXIS)


def _per_chip_heads(kernel, mesh, in_specs):
    """``kernel`` as is on one chip; on a mesh, under ``shard_map`` with
    the head axis split over ``tp`` (table/length operands replicated, as
    is everything across the other mesh axes). The result is q-shaped."""
    if mesh is None:
        return kernel
    return jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                         out_specs=_HEADS4, check_vma=False)


def _no_kernel_for(head_dim: int) -> str:
    if not on_tpu():
        return f"backend {jax.default_backend()} is not tpu"
    if head_dim not in _KERNEL_HEAD_DIMS:
        return f"head_dim {head_dim} not in {_KERNEL_HEAD_DIMS}"
    return ""


def _expand_gqa(k: jnp.ndarray, q_heads: int) -> jnp.ndarray:
    """[B, S, KH, D] -> [B, S, QH, D] by repeating kv heads."""
    kv_heads = k.shape[2]
    if kv_heads == q_heads:
        return k
    group = q_heads // kv_heads
    return jnp.repeat(k, group, axis=2)


def xla_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  causal: bool = True,
                  kv_offset: int = 0) -> jnp.ndarray:
    """Reference/fallback attention. q: [B, T, QH, D], k/v: [B, S, KH, D].

    ``kv_offset`` positions q tokens at absolute offset within the kv sequence
    (prefill-with-cache and chunked prefill).
    """
    q_heads = q.shape[2]
    k = _expand_gqa(k, q_heads)
    v = _expand_gqa(v, q_heads)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    if causal:
        t, s = q.shape[1], k.shape[1]
        q_pos = jnp.arange(t)[:, None] + kv_offset
        k_pos = jnp.arange(s)[None, :]
        mask = k_pos <= q_pos
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas flash kernel
# ---------------------------------------------------------------------------


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scratch, l_scratch, acc_scratch,
                  *, scale: float, causal: bool, block_q: int, block_k: int,
                  num_kb: int):
    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # [Bq, D]
        k = k_ref[0].astype(jnp.float32)                  # [Bk, D]
        v = v_ref[0].astype(jnp.float32)                  # [Bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [Bq, Bk]
        if causal:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)

        m_prev = m_scratch[...]                           # [Bq, 128]
        l_prev = l_scratch[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)        # [Bq, 1]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)                   # rescale factor
        p = jnp.exp(s - m_new[:, :1])                     # [Bq, Bk]
        l_new = alpha * l_prev + jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True), l_prev.shape)
        acc_scratch[...] = acc_scratch[...] * alpha[:, :1] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scratch[...] = m_new
        l_scratch[...] = l_new

    if causal:
        # skip blocks fully above the diagonal
        below_diag = kb * block_k <= qb * block_q + (block_q - 1)
        pl.when(below_diag)(_compute)
    else:
        _compute()

    @pl.when(kb == num_kb - 1)
    def _finalize():
        l = l_scratch[...][:, :1]
        o_ref[0] = (acc_scratch[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False) -> jnp.ndarray:
    """Flash attention. q: [B, T, QH, D]; k/v: [B, S, KH, D] with KH | QH.

    T and S must be multiples of the block sizes (model code pads); head_dim
    should be a multiple of 128 for MXU tiling (64 works but underutilizes).
    """
    batch, t, q_heads, head_dim = q.shape
    s = k.shape[1]
    kv_heads = k.shape[2]
    assert q_heads % kv_heads == 0
    group = q_heads // kv_heads
    assert t % block_q == 0 and s % block_k == 0, (t, s, block_q, block_k)

    # layout: [B*QH, T, D] so the grid's leading axis walks batch*heads
    qt = q.transpose(0, 2, 1, 3).reshape(batch * q_heads, t, head_dim)
    kt = k.transpose(0, 2, 1, 3).reshape(batch * kv_heads, s, head_dim)
    vt = v.transpose(0, 2, 1, 3).reshape(batch * kv_heads, s, head_dim)

    num_qb = t // block_q
    num_kb = s // block_k
    grid = (batch * q_heads, num_qb, num_kb)

    def q_index(bh, qb, kb):
        return (bh, qb, 0)

    def kv_index(bh, qb, kb):
        return (bh // group, kb, 0)

    kernel = functools.partial(
        _flash_kernel, scale=head_dim ** -0.5, causal=causal,
        block_q=block_q, block_k=block_k, num_kb=num_kb)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, head_dim), q_index),
            pl.BlockSpec((1, block_k, head_dim), kv_index),
            pl.BlockSpec((1, block_k, head_dim), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, head_dim), q_index),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # running denominator
            pltpu.VMEM((block_q, head_dim), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(qt, kt, vt)

    return out.reshape(batch, q_heads, t, head_dim).transpose(0, 2, 1, 3)


def flash_kernel_declined(t: int, s: int, head_dim: int,
                          kv_offset: int = 0) -> str:
    """Why :func:`attention` takes the XLA path for these shapes ('' = the
    flash kernel runs)."""
    if (why := _no_kernel_for(head_dim)):
        return why
    if kv_offset:
        return "the flash kernel has no kv_offset"
    if t % 128 or s % 128:
        return f"sequence ({t}, {s}) is not a multiple of the 128 block"
    return ""


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              causal: bool = True, kv_offset: int = 0,
              mesh=None) -> jnp.ndarray:
    """Dispatch: pallas flash on TPU for block-aligned shapes, XLA otherwise."""
    if flash_kernel_declined(q.shape[1], k.shape[1], q.shape[-1], kv_offset):
        return xla_attention(q, k, v, causal=causal, kv_offset=kv_offset)
    return _per_chip_heads(
        functools.partial(flash_attention, causal=causal), mesh,
        (_HEADS4, _HEADS4, _HEADS4))(q, k, v)


def ragged_kernel_declined(s_max: int, head_dim: int) -> str:
    """Why :func:`decode_attention` takes the XLA path ('' = the ragged
    kernel runs)."""
    if (why := _no_kernel_for(head_dim)):
        return why
    if s_max < 512 or s_max % 256:
        return f"cache length {s_max} is not a multiple of 256 from 512 up"
    return ""


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     cache_len: jnp.ndarray, mesh=None) -> jnp.ndarray:
    """Single-token decode attention against a contiguous KV cache.

    q: [B, 1, QH, D]; k_cache/v_cache: [B, S_max, KH, D]; cache_len: [B]
    (valid prefix length per sequence, including the current token).

    On TPU with aligned shapes this dispatches to the ragged pallas kernel
    (reads only each sequence's valid prefix — decode is HBM-bound, so
    skipped blocks are saved bandwidth); otherwise one fused XLA graph with
    a masked softmax over the full cache.
    """
    if ragged_kernel_declined(k_cache.shape[1], q.shape[-1]):
        return xla_decode_attention(q, k_cache, v_cache, cache_len)
    from .paged_attention import ragged_decode_attention
    return _per_chip_heads(
        ragged_decode_attention, mesh,
        (_HEADS4, _HEADS4, _HEADS4, P()))(q, k_cache, v_cache, cache_len)


def xla_chunk_prefill_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                                v_cache: jnp.ndarray,
                                positions: jnp.ndarray) -> jnp.ndarray:
    """Attention for one prefill CHUNK against the whole written prefix, as
    one XLA graph: the oracle of the chunk kernel and what serves the shapes
    it declines (:func:`chunk_prefill_attention` dispatches).

    q [B, C, QH, D] are the chunk's queries at absolute ``positions``
    [B, C]; k/v_cache [B, S, KH, D] already contain the prefix AND this
    chunk. A key at position p is visible to query at position t iff
    p <= t — that single mask covers both the cross-chunk prefix and the
    causal structure within the chunk (and hides garbage past the written
    region, since garbage positions exceed every query position).

    The graph's shapes are (C, S) whatever the prompt's length, which is
    what lets a long prompt prefill without a compile bucket of its own;
    the cost is float32 scores ``[QH, C, S]`` in HBM over ALL ``S``
    positions, with the KV heads repeated to the query heads.
    """
    q_heads = q.shape[2]
    k = _expand_gqa(k_cache, q_heads)
    v = _expand_gqa(v_cache, q_heads)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    s_max = k.shape[1]
    key_pos = jnp.arange(s_max)[None, None, :]           # [1, 1, S]
    mask = key_pos <= positions[:, :, None]              # [B, C, S]
    logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def chunk_kernel_declined(t: int, s: int, head_dim: int) -> str:
    """Why :func:`chunk_prefill_attention` takes the XLA path for ``t``
    queries against an ``s``-wide cache ('' = the chunk kernel runs)."""
    if (why := _no_kernel_for(head_dim)):
        return why
    if t % 128 or s % 128:
        return f"chunk ({t}, {s}) is not a multiple of the 128 block"
    return ""


def chunk_prefill_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                            v_cache: jnp.ndarray, positions: jnp.ndarray,
                            layer=0, mesh=None) -> jnp.ndarray:
    """Chunked-prefill dispatch: q [B, C, QH, D] at absolute ``positions``
    [B, C], each row's contiguous from its first, against the dense cache
    [L, B, S, KH, D] at ``layer`` (or one layer's [B, S, KH, D]) that
    already holds the prefix AND the chunk. On TPU, for block-aligned
    shapes, the pallas kernel (``ops/chunk_attention.py``), which reads the
    cache where it lies and only as far as it is written; otherwise
    :func:`xla_chunk_prefill_attention` over the layer's whole plane."""
    if chunk_kernel_declined(q.shape[1], k_cache.shape[-3], q.shape[-1]):
        if k_cache.ndim == 5:
            with jax.named_scope("kv.slice"):
                k_cache, v_cache = k_cache[layer], v_cache[layer]
        return xla_chunk_prefill_attention(q, k_cache, v_cache, positions)
    from .chunk_attention import flash_chunk_prefill_attention
    cache = _POOL5 if k_cache.ndim == 5 else _HEADS4
    return _per_chip_heads(
        flash_chunk_prefill_attention, mesh,
        (_HEADS4, cache, cache, P(), P()))(
            q, k_cache, v_cache, positions[:, 0],
            jnp.asarray(layer, jnp.int32))


def paged_verify_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, block_table: jnp.ndarray,
                           positions: jnp.ndarray,
                           k_scale: jnp.ndarray = None,
                           v_scale: jnp.ndarray = None,
                           layer: int = 0) -> jnp.ndarray:
    """Multi-token attention against the paged pool for one speculative
    VERIFY pass: q [B, T, QH, D] are the window's queries at absolute
    ``positions`` [B, T]; k/v_pool [L, N, BS, KH, D] at ``layer`` (or one
    layer's plane [N, BS, KH, D]) already contain the window's keys
    (scattered by the caller).

    Each slot's block-table row is densified with an XLA gather and the
    per-query position mask (key_pos <= q_pos) hides everything past each
    query — including the trash column and table padding, whose key
    positions exceed every real query position by construction. One
    forward verifies ``T = 1 + spec_len`` positions for the whole batch,
    which is the entire point of speculative decoding in the
    bandwidth-bound decode regime: the weight stream is paid once for T
    tokens instead of once per token. The densified rows go through
    :func:`chunk_prefill_attention`, whose shape rule gives a window of
    ``1 + spec_len`` queries the XLA form (the chunk kernel takes multiples
    of 128). A kernel that walks the table itself, without the densify
    copy, is not built (ROADMAP R10).

    An int8 pool passes ``k_scale``/``v_scale`` (one rank less) — blocks
    are dequantized right after the gather (per-vector scales, see
    ``tpu9.ops.quant.quantize_kv``; densify+dequant shared with the
    decode oracle via ``paged_attention.gather_paged``)."""
    from .paged_attention import gather_paged
    k = gather_paged(k_pool, block_table, k_scale, q.dtype, layer)
    v = gather_paged(v_pool, block_table, v_scale, q.dtype, layer)
    return chunk_prefill_attention(q, k, v, positions)


def paged_kernel_declined(block_s: int, head_dim: int) -> str:
    """Why :func:`paged_attention_dispatch` takes the XLA oracle ('' = the
    paged kernel runs)."""
    if (why := _no_kernel_for(head_dim)):
        return why
    if block_s % 128:
        return f"kv block {block_s} is not a multiple of 128"
    return ""


def paged_kernel_form(k_pool, q_heads: int, table_width: int,
                      mesh=None) -> str:
    """Which body of the paged kernel :func:`paged_attention_dispatch` runs
    over ``k_pool`` (anything with the whole pool's ``shape`` and ``dtype``),
    in words: what ``engine.stats()["attention_decode"]`` says on
    ``/health``. On a mesh the kernel sees one chip's heads
    (:func:`_per_chip_heads`), so the words are about that share."""
    from .paged_attention import paged_decode_form
    tp = 1 if mesh is None else mesh.shape[HEAD_AXIS]
    *lead, kv_heads, head_dim = k_pool.shape
    return paged_decode_form(
        jax.ShapeDtypeStruct((*lead, kv_heads // tp, head_dim), k_pool.dtype),
        q_heads // tp, table_width)


def paged_attention_dispatch(q: jnp.ndarray, k_pool: jnp.ndarray,
                             v_pool: jnp.ndarray, block_table: jnp.ndarray,
                             cache_len: jnp.ndarray,
                             k_scale: jnp.ndarray = None,
                             v_scale: jnp.ndarray = None,
                             mesh=None, layer=0) -> jnp.ndarray:
    """Block-table paged decode dispatch: pallas kernel on TPU (physical
    blocks DMA'd by table lookup inside the kernel — no densify copy),
    gather + XLA oracle elsewhere. k/v_pool are the whole pool
    [L, N, BS, KH, D], read at ``layer`` where it lives, or one layer's
    plane [N, BS, KH, D]. ``k_scale``/``v_scale`` (one rank less) mark an
    int8 pool — the kernel dequantizes in-register after the DMA, so HBM
    only ever moves the int8 payload + the per-vector scales."""
    from .paged_attention import (paged_decode_attention,
                                  paged_decode_attention_quant,
                                  xla_paged_decode_attention)
    if paged_kernel_declined(k_pool.shape[-3], q.shape[-1]):
        return xla_paged_decode_attention(q, k_pool, v_pool, block_table,
                                          cache_len, k_scale, v_scale,
                                          layer)
    pool, scales = (_POOL5, _POOL4) if k_pool.ndim == 5 \
        else (_HEADS4, _HEADS3)
    layer = jnp.asarray(layer, jnp.int32)
    if k_scale is not None:
        return _per_chip_heads(
            paged_decode_attention_quant, mesh,
            (_HEADS4, pool, pool, scales, scales, P(), P(), P()))(
                q, k_pool, v_pool, k_scale, v_scale, block_table, cache_len,
                layer)
    return _per_chip_heads(
        paged_decode_attention, mesh,
        (_HEADS4, pool, pool, P(), P(), P()))(
            q, k_pool, v_pool, block_table, cache_len, layer)


def xla_decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                         v_cache: jnp.ndarray,
                         cache_len: jnp.ndarray) -> jnp.ndarray:
    """Reference/fallback decode graph: masked softmax over the full cache.
    Also the correctness oracle the bench validates the ragged pallas
    kernel against — keep semantics in lockstep with it."""
    q_heads = q.shape[2]
    k = _expand_gqa(k_cache, q_heads)
    v = _expand_gqa(v_cache, q_heads)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))       # [B, H, 1, S]
    s_max = k.shape[1]
    mask = jnp.arange(s_max)[None, :] < cache_len[:, None]       # [B, S]
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)
