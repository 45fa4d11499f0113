"""Attention over a cache of LATENTS (MLA, arXiv:2405.04434): a token's cache
row is one latent ``c`` [d_c] and one rotated key ``k_rope`` [d_rope] for all
heads, from which a head's key is ``[W_uk,h c | k_rope]`` and its value
``W_uv,h c``.

- :func:`expanded_attention` — a prefill over a SHORT cache: keys and values
  expanded from the latents of every cache row, queries masked by absolute
  position, taken a block of queries at a time so that the float32 scores of
  a wide call fit.
- :func:`blocked_prefill_attention` — a prefill over a LONG cache (a chunk or
  an admission group against the batch-1 scratch), BLOCKED OVER KEYS: an
  online softmax over blocks of latents, a block's keys and values made from
  its latents where they are used and never kept, and only the blocks that
  hold a row some query may see. Expanding every row of a 57,344-row scratch
  for 64 heads is 1.9 GB of keys and values a layer and 7.5 GB of float32
  scores a 512-token chunk; blocked, nothing wider than a block exists. A
  Pallas kernel on the chip (:func:`blocked_prefill_attention_kernel`: the
  expansion, the scores and the weighted sum of a block in VMEM) and the same
  sums in ``jax.numpy`` elsewhere (:func:`blocked_prefill_attention_xla`).
- :func:`paged_latent_attention` — a decode step, ABSORBED: ``W_uk`` has gone
  into the query and ``W_uv`` comes after, so the step attends the latents
  themselves and reads each cache row once for all heads:

      score_h(s) = (q^_h . c_s + q_rope_h . k_rope_s) . scale,   q^_h = W_uk,h^T q_nope_h
      o^_h = sum_s p_h(s) c_s          (then o_h = W_uv,h o^_h, the caller's)

  Two bodies, one result up to the order of the sums. The XLA form gathers
  the lanes' table rows into ``[B, S, d_c]`` and ``[B, S, d_r]`` — EVERY
  column of the table, whatever the lanes hold — and takes three einsums
  over them: the oracle, and what runs off the chip.
  :func:`paged_latent_attention_kernel` walks a lane's own pages
  (``ops.paged_attention``'s walk, PR 40, with ONE row a token for all
  heads): a grid step a lane, ``ceil(len / BS)`` pages copied in
  double-buffered waves — a page's latents and, with them, its rotated keys
  — the next wave — after a lane's last, the next lane's first — in flight
  under the arithmetic, BOTH parts of the scores and an online softmax over
  a wave's rows. :func:`paged_latent_attention` chooses. A pool's rotated
  keys lie two tokens a 128-lane row (:func:`pack_rotated`), so that a page
  of them is a block the copy engine takes as it lies; the dense scratch of
  the prefills keeps a token a row.

bf16 operands, float32 accumulation and softmax, as the other attention ops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .chunk_attention import _lanes

F32 = jnp.float32
NEG_INF = -1e30
# queries of a block of :func:`expanded_attention`
QUERY_BLOCK = 512
# pages of a wave of the decode kernel: 8 x 128 rows of 576 numbers are
# 1.1 MB a slot in VMEM, and a wave is ONE softmax update over 1,024 rows.
# One call alone at Kimi's / Ling's shapes, 2 / 4 / 8 pages a wave: 1,748 /
# 1,207 / 971 and 1,100 / 791 / 664 us (PR 53): what a wave costs beside its
# products — the waits, the statistics, the accumulator's rescale — is paid
# half as often
WAVE_PAGES = 8
LATENT_KERNEL = "paged_latent_attention"
# the blocked prefill: its kernel's name in a trace; the scratch rows from
# which a chunk takes it (under them :func:`expanded_attention` holds
# everything at once, as it always did: 32 heads x 512 queries x 4,096 rows
# of float32 scores are 0.27 GB); keys a block; the most queries a tile; the
# most heads a grid step (their weights, queries and accumulators together
# in VMEM: a grid step costs ~0.35 us whatever it does, and the key axis of a
# long scratch has many that do nothing)
PREFILL_KERNEL = "latent_prefill_attention"
BLOCKED_MIN_ROWS = 8192
PREFILL_BLOCK_K = 512
PREFILL_BLOCK_Q = 1024
PREFILL_HEADS = 8


def _softmax_rows(scores, mask):
    """Softmax over the last axis where ``mask``; a row with nothing to
    attend (an idle lane) gives zeros, not NaN."""
    scores = jnp.where(mask, scores, -1e30)
    probs = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    probs = jnp.where(mask, probs, 0.0)
    return probs / jnp.maximum(jnp.sum(probs, axis=-1, keepdims=True), 1e-30)


def expanded_attention(q_nope, q_rope, k_nope, k_rope, v, q_pos, scale):
    """``q_nope`` [T, H, dn], ``q_rope`` [T, H, dr] at absolute positions
    ``q_pos`` [T]; ``k_nope`` [S, H, dn], ``k_rope`` [S, dr], ``v`` [S, H, dv]
    for cache rows 0..S-1. Query t attends rows ``s <= q_pos[t]``. Returns
    [T, H, dv] in ``v``'s type."""
    t = q_nope.shape[0]
    rows = jnp.arange(k_nope.shape[0])

    def block(args):
        qn, qr, pos = args
        scores = (jnp.einsum("thd,shd->hts", qn, k_nope,
                             preferred_element_type=F32)
                  + jnp.einsum("thd,sd->hts", qr, k_rope,
                               preferred_element_type=F32)) * scale
        probs = _softmax_rows(scores, rows[None, None, :] <= pos[None, :, None])
        return jnp.einsum("hts,shd->thd", probs.astype(v.dtype), v,
                          preferred_element_type=F32).astype(v.dtype)

    if t <= QUERY_BLOCK or t % QUERY_BLOCK:
        return block((q_nope, q_rope, q_pos))
    n = t // QUERY_BLOCK
    out = jax.lax.map(block, tuple(
        a.reshape((n, QUERY_BLOCK) + a.shape[1:])
        for a in (q_nope, q_rope, q_pos)))
    return out.reshape((t,) + out.shape[2:])


def kernel_declined(heads: int, latent: int, rope: int, block_s: int,
                    dtype) -> str:
    """Why a decode step takes the XLA form ('' = the kernel runs). The
    kernel copies a page as two halves of ``block_s / 2`` latents and one
    ``[block_s / 2, 2 * rope]`` block of rotated keys: each whole tiles."""
    from ..utils import on_tpu
    if not on_tpu():
        return "no TPU backend"
    if dtype != jnp.bfloat16:
        return f"a {jnp.dtype(dtype).name} cache"
    if heads % 8 or latent % 128 or 2 * rope % 128 or block_s % 32:
        return (f"heads={heads}, latent={latent}, rope={rope}, "
                f"block={block_s}: not whole tiles")
    return ""


def pack_rotated(rows, axis: int):
    """Rotated keys ``[..., BS, ..., d_r]`` (``axis`` the tokens' of one
    page) as the POOL holds them, ``[..., BS / 2, ..., 2 d_r]``: token ``j``
    in lanes ``0 .. d_r - 1`` of row ``j``, token ``j + BS / 2`` in lanes
    ``d_r .. 2 d_r - 1`` of the same row. A rotated key is 64 numbers, half a
    128-lane row, which the chip's copy engine will not cut out of HBM; two a
    row, a page's are one block that it copies as it lies, and each half of
    a row block is a contiguous run of tokens."""
    first, second = jnp.split(rows, 2, axis=axis)
    return jnp.concatenate([first, second], axis=-1)


def unpack_rotated(packed, axis: int):
    """:func:`pack_rotated` undone: ``[..., BS, ..., d_r]`` in token order."""
    first, second = jnp.split(packed, 2, axis=-1)
    return jnp.concatenate([first, second], axis=axis)


def paged_latent_attention(q_lat, q_rope, c_pool, r_pool, table, lengths,
                           layer, scale):
    """``q_lat`` [B, H, d_c] (``W_uk`` absorbed), ``q_rope`` [B, H, d_r];
    the latents' pool ``[L, N, BS, 1, d_c]`` and the rotated keys' ``[L, N,
    BS / 2, 1, 2 d_r]`` (:func:`pack_rotated`); ``table`` [B, MB]; lane b
    attends its first ``lengths[b]`` rows (0: none, zeros out). Returns
    [B, H, d_c] float32. The kernel where it runs, else the XLA form."""
    if not kernel_declined(q_lat.shape[1], c_pool.shape[-1],
                           q_rope.shape[-1], c_pool.shape[2], c_pool.dtype):
        return paged_latent_attention_kernel(
            q_lat, q_rope, c_pool, r_pool, table, lengths, layer, scale)
    return paged_latent_attention_xla(q_lat, q_rope, c_pool, r_pool, table,
                                      lengths, layer, scale)


def paged_latent_attention_xla(q_lat, q_rope, c_pool, r_pool, table, lengths,
                               layer, scale):
    """:func:`paged_latent_attention` in ``jax.numpy``."""
    b, mb = table.shape
    bs = c_pool.shape[2]
    c = c_pool[layer, table].reshape(b, mb * bs, -1)             # [B, S, dc]
    r = unpack_rotated(r_pool[layer, table], 2).reshape(b, mb * bs, -1)
    scores = (jnp.einsum("bhc,bsc->bhs", q_lat.astype(c.dtype), c,
                         preferred_element_type=F32)
              + jnp.einsum("bhr,bsr->bhs", q_rope.astype(r.dtype), r,
                           preferred_element_type=F32)) * scale
    mask = jnp.arange(mb * bs)[None, None, :] < lengths[:, None, None]
    probs = _softmax_rows(scores, mask)
    return jnp.einsum("bhs,bsc->bhc", probs.astype(c.dtype), c,
                      preferred_element_type=F32)


# -- the decode step as a Pallas kernel ----------------------------------------

def _latent_kernel(table_ref, len_ref, q_lat_ref, q_rope_ref, c_hbm, r_hbm,
                   o_ref, c_buf, r_buf, sem, slot_ref, m_scr, l_scr, acc_scr,
                   *, scale: float, layer: int, block_s: int, wave: int):
    """One lane a grid step. ``c_hbm`` ``[L, N, BS, d_c]`` and ``r_hbm``
    ``[L, N, BS / 2, 2 d_r]`` are the pool whole, in HBM. A page comes as its
    rows lie in ``r_hbm``, in two HALVES: half 0 its first ``BS / 2`` tokens
    (lanes ``0 .. d_r - 1`` of the rotated keys' rows), half 1 the rest.
    ``c_buf`` ``[2, 2, wave * BS / 2, d_c]`` (slot, half) and ``r_buf``
    ``[2, wave * BS / 2, 2 d_r]`` hold two waves of pages, ``sem`` ``[2]`` a
    slot; a softmax does not care in which order a wave's rows come, so no
    score is ever shuffled into token order. ``slot_ref`` (SMEM, alive
    across grid steps) is the slot of this step's first wave, whose copies
    the step before started. Running maximum and sum ``[H, 128]`` (the same
    in all lanes of a row), accumulator ``[H, d_c]``, float32."""
    b = pl.program_id(0)
    batch = pl.num_programs(0)
    half_s = block_s // 2
    dr = q_rope_ref.shape[-1]

    def pages_of(lane):
        return jnp.minimum(
            jax.lax.div(len_ref[lane] + block_s - 1, block_s),
            table_ref.shape[1])

    def for_wave(lane, w, slot, act):
        """``act`` on every copy that brings wave ``w`` of ``lane`` into
        ``slot``: three a page the lane holds there."""
        def one(page, _):
            block = table_ref[lane, w * wave + page]
            rows = pl.ds(pl.multiple_of(page * half_s, half_s), half_s)
            for half in range(2):
                act(pltpu.make_async_copy(
                    c_hbm.at[layer, block, pl.ds(half * half_s, half_s)],
                    c_buf.at[slot, half, rows], sem.at[slot]))
            act(pltpu.make_async_copy(r_hbm.at[layer, block],
                                      r_buf.at[slot, rows], sem.at[slot]))
        jax.lax.fori_loop(
            0, jnp.minimum(pages_of(lane) - w * wave, wave), one, None)

    def start(lane, w, slot):
        for_wave(lane, w, slot, lambda copy: copy.start())

    def wait(lane, w, slot):
        for_wave(lane, w, slot, lambda copy: copy.wait())

    seq_len = len_ref[b]
    n_pages = pages_of(b)
    n_waves = jax.lax.div(n_pages + wave - 1, wave)

    @pl.when(b == 0)
    def _first_lane():
        slot_ref[0] = 0
        start(0, 0, 0)

    first_slot = slot_ref[0]
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    q_lat, q_rope = q_lat_ref[0], q_rope_ref[0]
    rows = wave * half_s

    def place(shape, axis):
        """Where row ``i`` of a half wave's buffer lies in half 0 of the
        wave's tokens: page ``i // half_s``, row ``i % half_s`` of it."""
        i = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
        return i + half_s * sum((i >= page * half_s).astype(jnp.int32)
                                for page in range(1, wave))

    down, across = place((rows, 1), 0), place((q_lat.shape[0], rows), 1)

    def one_wave(w, _):
        slot = jax.lax.rem(first_slot + w, 2)

        @pl.when(w + 1 < n_waves)
        def _next_wave():
            start(b, w + 1, 1 - slot)

        @pl.when((w + 1 == n_waves) & (b + 1 < batch))
        def _next_lane():
            start(b + 1, 0, 1 - slot)

        wait(b, w, slot)
        r = r_buf[slot]
        c, s, seen = [], [], []
        for half in range(2):
            first = w * wave * block_s + half * half_s
            # a row past the lane's length — the tail of its last page, a
            # page of the wave it does not hold — is whatever VMEM held:
            # its latent zeroed before it meets a probability of 0, its
            # score (the rotated key's part with it) replaced
            c.append(jnp.where(first + down < seq_len, c_buf[slot, half], 0))
            seen.append(first + across < seq_len)
            s.append(jnp.where(seen[half], (
                jax.lax.dot_general(q_lat, c[half], (((1,), (1,)), ((), ())),
                                    preferred_element_type=F32)
                + jax.lax.dot_general(
                    q_rope, r[:, half * dr:(half + 1) * dr],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=F32)) * scale, NEG_INF))
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(
            jnp.maximum(s[0], s[1]), axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = [jnp.where(seen[half], jnp.exp(s[half] - m_new[:, :1]), 0.0)
             for half in range(2)]
        l_scr[...] = alpha * l_prev + jnp.sum(p[0] + p[1], axis=-1,
                                              keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + sum(
            jax.lax.dot_general(p[half].astype(c[half].dtype), c[half],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=F32)
            for half in range(2))
        m_scr[...] = m_new

    jax.lax.fori_loop(0, n_waves, one_wave, None)

    @pl.when((n_waves == 0) & (b + 1 < batch))
    def _idle_lane():
        start(b + 1, 0, first_slot)

    slot_ref[0] = jax.lax.rem(first_slot + n_waves, 2)
    o_ref[0] = acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)


def paged_latent_attention_kernel(q_lat, q_rope, c_pool, r_pool, table,
                                  lengths, layer: int, scale: float,
                                  interpret: bool = False):
    """:func:`paged_latent_attention` by :func:`_latent_kernel`: a row —
    the latent and its rotated key — is read where it lies, the pages that
    hold tokens and each once for all heads; an idle lane costs one empty
    grid step. Nothing is gathered in front of the kernel and no score
    exists outside it."""
    batch, heads, dc = q_lat.shape
    dr = q_rope.shape[-1]
    mb = table.shape[1]
    n_layers, n_blocks, block_s = c_pool.shape[:3]
    wave = min(WAVE_PAGES, mb)
    rows = wave * block_s // 2
    # the pools' unit axis away: free reshapes
    c_pool = c_pool.reshape(n_layers, n_blocks, block_s, dc)
    r_pool = r_pool.reshape(n_layers, n_blocks, block_s // 2, 2 * dr)

    def lane(b, table, lens):
        return (b, 0, 0)

    return pl.pallas_call(
        functools.partial(_latent_kernel, scale=scale, layer=layer,
                          block_s=block_s, wave=wave),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch,),
            in_specs=[pl.BlockSpec((1, heads, dc), lane),
                      pl.BlockSpec((1, heads, dr), lane),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, heads, dc), lane),
            scratch_shapes=[
                pltpu.VMEM((2, 2, rows, dc), c_pool.dtype),
                pltpu.VMEM((2, rows, 2 * dr), r_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((heads, 128), F32),
                pltpu.VMEM((heads, 128), F32),
                pltpu.VMEM((heads, dc), F32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((batch, heads, dc), F32),
        # the slot and the copies in flight pass from one step to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        name=LATENT_KERNEL,
        interpret=interpret,
    )(table.astype(jnp.int32), lengths.astype(jnp.int32),
      q_lat.astype(c_pool.dtype), q_rope.astype(r_pool.dtype), c_pool,
      r_pool)


# -- a prefill over a long cache, blocked over keys -----------------------------

def blocked_prefill_declined(s: int) -> str:
    """Why a chunk against an ``s``-row scratch takes
    :func:`expanded_attention` ('' = the blocked prefill runs)."""
    if s < BLOCKED_MIN_ROWS:
        return (f"a scratch of {s} rows, under {BLOCKED_MIN_ROWS}: every "
                "row expanded at once")
    if s % PREFILL_BLOCK_K:
        return f"a scratch of {s} rows: not whole blocks of " \
            f"{PREFILL_BLOCK_K} keys"
    return ""


def prefill_kernel_declined(t: int, dn: int, dr: int, dv: int, dc: int,
                            dtype) -> str:
    """Why a blocked prefill of ``t`` queries takes the ``jax.numpy`` form
    ('' = the kernel runs)."""
    from ..utils import on_tpu
    if not on_tpu():
        return "no TPU backend"
    if dtype != jnp.bfloat16:
        return f"a {jnp.dtype(dtype).name} cache"
    if t % 128 or dn % 128 or dv % 128 or dc % 128 or dr % 64:
        return (f"{t} queries, widths nope={dn}, rope={dr}, v={dv}, "
                f"latent={dc}: not whole tiles")
    return ""


def blocked_prefill_attention(q_nope, q_rope, c_cache, r_cache, w_ukv,
                              offset, layer, scale):
    """``q_nope`` [T, H, dn], ``q_rope`` [T, H, dr] at the contiguous
    positions ``offset .. offset + T - 1``; the dense scratch ``c_cache``
    [L, 1, S, 1, dc] / ``r_cache`` [L, 1, S, 1, dr], read at ``layer``, which
    already holds the chunk's own rows; ``w_ukv`` [dc, H, dn + dv]. Query t
    attends rows ``s <= offset + t``. Returns [T, H, dv] in the cache's type.
    The kernel where it runs, else the ``jax.numpy`` form."""
    dn, dr = q_nope.shape[-1], q_rope.shape[-1]
    dc = c_cache.shape[-1]
    if not prefill_kernel_declined(q_nope.shape[0], dn, dr,
                                   w_ukv.shape[-1] - dn, dc, c_cache.dtype):
        return blocked_prefill_attention_kernel(
            q_nope, q_rope, c_cache, r_cache, w_ukv, offset, layer, scale)
    return blocked_prefill_attention_xla(
        q_nope, q_rope, c_cache, r_cache, w_ukv, offset, layer, scale)


def blocked_prefill_attention_xla(q_nope, q_rope, c_cache, r_cache, w_ukv,
                                  offset, layer, scale, block_k: int = 0):
    """:func:`blocked_prefill_attention` in ``jax.numpy``: a device loop
    over the key blocks that hold a row some query may see."""
    t, heads, dn = q_nope.shape
    s = c_cache.shape[2]
    block_k = min(block_k or PREFILL_BLOCK_K, s)
    latents, rotated = c_cache[layer, 0, :, 0], r_cache[layer, 0, :, 0]
    w_uk, w_uv = w_ukv[..., :dn], w_ukv[..., dn:]
    q_pos = offset + jnp.arange(t)

    def one_block(kb, carry):
        m, l, acc = carry
        first = kb * block_k
        c = jax.lax.dynamic_slice_in_dim(latents, first, block_k)
        r = jax.lax.dynamic_slice_in_dim(rotated, first, block_k)
        k = jnp.einsum("sc,chd->shd", c, w_uk)
        v = jnp.einsum("sc,chd->shd", c, w_uv)
        scores = (jnp.einsum("thd,shd->hts", q_nope, k,
                             preferred_element_type=F32)
                  + jnp.einsum("thd,sd->hts", q_rope, r,
                               preferred_element_type=F32)) * scale
        seen = (first + jnp.arange(block_k))[None, None, :] \
            <= q_pos[None, :, None]
        scores = jnp.where(seen, scores, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(seen, jnp.exp(scores - m_new[..., None]), 0.0)
        acc = acc * alpha[..., None] + jnp.einsum(
            "hts,shd->htd", p.astype(v.dtype), v, preferred_element_type=F32)
        return m_new, alpha * l + jnp.sum(p, axis=-1), acc

    n_blocks = jnp.minimum((offset + t + block_k - 1) // block_k,
                           s // block_k)
    _, l, acc = jax.lax.fori_loop(
        0, n_blocks, one_block,
        (jnp.full((heads, t), NEG_INF, F32), jnp.zeros((heads, t), F32),
         jnp.zeros((heads, t, w_uv.shape[-1]), F32)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(1, 0, 2).astype(c_cache.dtype)


def _prefill_kernel(offset_ref, layer_ref, qn_ref, qr_ref, w_ref, c_ref,
                    r_ref, o_ref, m_scr, l_scr, acc_scr, *, scale: float,
                    block_q: int, block_k: int, dn: int):
    """One key block of one query tile of ``heads`` heads a grid step.
    ``qn_ref`` [heads, block_q, dn], ``qr_ref`` [heads, block_q, dr],
    ``w_ref`` [heads, dc, dn + dv]; ``c_ref`` [block_k, dc] and ``r_ref``
    [block_k, dr] the block's latents and rotated keys. A head's keys and
    values of the block are made here, from the latents, and live as long as
    its update. A block wholly at or before the tile's first query is one
    unmasked update a head; one the diagonal crosses is masked by position,
    its latents past the tile's last query zeroed (probability 0 times
    whatever the scratch holds there must stay 0); one past the last query
    was not copied (the index map clamped) and is not touched."""
    del layer_ref
    qi, kb = pl.program_id(0), pl.program_id(2)
    first_q = offset_ref[0] + qi * block_q
    last_q = first_q + block_q - 1
    first_k = kb * block_k
    last_k = first_k + block_k - 1
    heads = qn_ref.shape[0]

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def block_of_keys(masked: bool):
        c = c_ref[...]
        r = r_ref[...]
        if masked:
            written = first_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0) <= last_q
            c = jnp.where(written, c, jnp.zeros_like(c))
            r = jnp.where(written, r, jnp.zeros_like(r))
            visible = first_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1) <= first_q \
                + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

        def one_head(h, _):
            kv = jax.lax.dot_general(
                c, w_ref[h], (((1,), (0,)), ((), ())),
                preferred_element_type=F32).astype(c.dtype)
            k, v = kv[:, :dn], kv[:, dn:]
            s = (jax.lax.dot_general(qn_ref[h], k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=F32)
                 + jax.lax.dot_general(qr_ref[h], r, (((1,), (1,)), ((), ())),
                                       preferred_element_type=F32)) * scale
            if masked:
                s = jnp.where(visible, s, NEG_INF)
            m_prev = m_scr[h]                                    # [R, 128]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # row 0 is visible to every query and comes first, so a row's
            # maximum is a real score from its first update on: a masked
            # score's exp underflows to exactly 0
            p = jnp.exp(s - _lanes(m_new, block_k))
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * _lanes(alpha, acc_scr.shape[-1]) \
                + jax.lax.dot_general(p.astype(v.dtype), v,
                                      (((1,), (0,)), ((), ())),
                                      preferred_element_type=F32)
            m_scr[h] = m_new

        if heads == 1:
            one_head(0, None)
        else:
            jax.lax.fori_loop(0, heads, one_head, None)

    pl.when(last_k <= first_q)(functools.partial(block_of_keys, False))
    pl.when((last_k > first_q) & (first_k <= last_q))(
        functools.partial(block_of_keys, True))

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finalize():
        def one(h, _):
            o_ref[h] = (acc_scr[h] / _lanes(l_scr[h], o_ref.shape[-1])
                        ).astype(o_ref.dtype)
        jax.lax.fori_loop(0, heads, one, None)


@functools.partial(jax.jit, static_argnames=("scale", "block_k", "interpret"))
def blocked_prefill_attention_kernel(q_nope, q_rope, c_cache, r_cache, w_ukv,
                                     offset, layer, scale: float,
                                     block_k: int = 0,
                                     interpret: bool = False):
    """:func:`blocked_prefill_attention` by :func:`_prefill_kernel`. The
    scratch is read where it lies, at ``layer`` (an int or an int32 scalar)
    and only as far as it is written: the key block's index clamps at the
    last block that holds a row the tile's last query sees. The heads are a
    grid axis, ``PREFILL_HEADS`` a step, each with its own slice of
    ``w_ukv``; the key axis is innermost, so a step's weights, queries and
    accumulators stay in VMEM while the latents stream past."""
    t, heads, dn = q_nope.shape
    dr, dc = q_rope.shape[-1], c_cache.shape[-1]
    dv = w_ukv.shape[-1] - dn
    n_layers, _, s = c_cache.shape[:3]
    block_k = min(block_k or PREFILL_BLOCK_K, s)
    block_q = next(b for b in (PREFILL_BLOCK_Q, 512, 256, 128, t)
                   if b <= t and t % b == 0)
    hg = next(g for g in (PREFILL_HEADS, 4, 2, 1) if heads % g == 0)
    assert s % block_k == 0, (s, block_k)
    n_qb, n_kb = t // block_q, s // block_k
    dt = c_cache.dtype
    # [H, T, d] queries, [H, dc, dn + dv] weights: a head's are one block
    qn = q_nope.astype(dt).transpose(1, 0, 2)
    qr = q_rope.astype(dt).transpose(1, 0, 2)
    w = w_ukv.astype(dt).transpose(1, 0, 2)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    offset = jnp.reshape(jnp.asarray(offset, jnp.int32), (1,))

    def q_index(qi, h, kb, offset, layer):
        return (h, qi, 0)

    def w_index(qi, h, kb, offset, layer):
        return (h, 0, 0)

    def kv_index(qi, h, kb, offset, layer):
        last = jnp.minimum(
            jax.lax.div(offset[0] + (qi + 1) * block_q - 1, block_k),
            n_kb - 1)
        return (layer[0] * n_kb + jnp.minimum(kb, last), 0)

    out = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, dn=dn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_qb, heads // hg, n_kb),
            in_specs=[pl.BlockSpec((hg, block_q, dn), q_index),
                      pl.BlockSpec((hg, block_q, dr), q_index),
                      pl.BlockSpec((hg, dc, dn + dv), w_index),
                      pl.BlockSpec((block_k, dc), kv_index),
                      pl.BlockSpec((block_k, dr), kv_index)],
            out_specs=pl.BlockSpec((hg, block_q, dv), q_index),
            scratch_shapes=[
                pltpu.VMEM((hg, block_q, 128), F32),
                pltpu.VMEM((hg, block_q, 128), F32),
                pltpu.VMEM((hg, block_q, dv), F32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((heads, t, dv), dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=96 * 1024 * 1024),
        name=PREFILL_KERNEL,
        interpret=interpret,
    )(offset, layer, qn, qr, w,
      c_cache.reshape(n_layers * s, dc), r_cache.reshape(n_layers * s, dr))
    return out.transpose(1, 0, 2)
