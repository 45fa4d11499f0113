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

The block-table PAGED kernel (what serving runs) does not spend a grid step
on a table column: a grid step is a sequence, and the kernel walks that
sequence's ``ceil(len / BS)`` pages itself, copying them out of the pool a
wave at a time (:func:`_walk_kernel`). The unit of its arithmetic is a BLOCK
of KV heads (:func:`_heads_per_update`): the query rows of as many heads as
fill a float32 tile's eight sublanes are one tile of scores, so the online
softmax runs once a block on full vector registers — at one query row a KV
head (plain multi-head attention) eight heads an update, at four the pair
one 32-bit word of the pool holds (PERF.md §6, PR 47). Pools whose pages
Mosaic cannot cut out of HBM (int8 with its scale planes, heads under 128
wide) keep the grid-over-columns form (:func:`_page_grid`), a head an update.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _head_update(h, q, k, v, first_pos, seq_len, m_scr, l_scr, acc_scr):
    """One kv head's online-softmax update for a run of cache rows that
    starts at position ``first_pos`` — the body every kernel here shares
    (q/k/v arrive f32, q pre-scaled; dequantization, if any, already
    happened)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    pos = first_pos + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
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


def head_pair_words(buf, first_row, rows: int, j):
    """Rows ``first_row`` … ``first_row + rows`` of KV heads ``2j`` and
    ``2j + 1`` out of a bfloat16 buffer laid ``[..., rows, KH, D]`` as the
    cache is, as ``[rows, D]`` 32-bit words: one word holds the same lane of
    the two neighbouring heads, so ONE strided load brings the pair where
    ``buf[:, h, :]`` re-reads the whole buffer for every head.
    :func:`widen_half` makes each half float32."""
    pairs = buf.shape[-2] // 2
    flat = buf.reshape(-1, buf.shape[-1]).bitcast(jnp.uint32)
    at = first_row * pairs + j
    return flat[pl.ds(at, rows, stride=pairs) if pairs > 1
                else pl.ds(at, rows), :]


def widen_half(x, odd):
    """The even (low half) or odd head of :func:`head_pair_words` as float32:
    a shift or a mask, which is exact."""
    x = x & jnp.uint32(0xFFFF0000) if odd else x << 16
    return pltpu.bitcast(x, jnp.float32)


def _finalize_heads(o_ref, m_scr, l_scr, acc_scr, kv_heads: int):
    for h in range(kv_heads):
        l = l_scr[h][:, :1]
        o_ref[0, h] = (acc_scr[h] / jnp.maximum(l, 1e-30)).astype(
            o_ref.dtype)


def _kernel(len_ref, q_ref, k_ref, v_ref, *refs, scale: float, block_s: int,
            num_sb: int, kv_heads: int):
    """One cache block a grid step. ``refs``: an int8 cache's two scale
    blocks (f32, one scale a (token, head) vector; dequantization is one
    multiply after the block's DMA), then the output block and the online
    softmax's running maximum, sum and accumulator."""
    *scales, o_ref, m_scr, l_scr, acc_scr = refs
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
            if scales:
                k = k * scales[0][0, :, h][:, None]
                v = v * scales[1][0, :, h][:, None]
            _head_update(h, q, k, v, sb * block_s, seq_len, m_scr, l_scr,
                         acc_scr)

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

# K bytes a wave copies: the page walk fetches this much of a sequence's keys
# (and as much of its values) at once, every page's copy in flight together,
# while it works on the wave before. A page is 64 KB (two KV heads a chip) to
# 1 MB (thirty-two) on the served shapes, so a wave is 32 / 8 / 4 / 2 pages:
# a static shape, no knob. Swept on the chip from 256 KB to 4 MB at 2, 8 and
# 16 heads (PERF.md §6, PR 40): smaller waves pay a copy's latency and an
# update's fixed cost more often (256 KB: + 15…40 % a call), larger ones gain
# nothing on two shapes of three and double the 8 MB of buffers. Swept again
# with the heads in blocks (PR 47, call 7, this body;
# ``scripts/paged_kernel_bench.py --wave-bytes``): at 32 heads 2 / 4 / 8 pages
# a wave cost a call 155.3 / 159.1 / 168.0 µs, at 16 heads 2 / 4 / 8 pages
# 32.0 / 31.7 / 33.9 µs — 2 MB stays.
WAVE_BYTES = 2 << 20
# and at most this many pages: a wave's copies are started one by one
MAX_WAVE_PAGES = 32
_VMEM_LIMIT = 64 * 1024 * 1024


def _pages_per_wave(page_bytes: int, table_width: int) -> int:
    """A power of two: a wave's arithmetic goes in runs of W, W/2, ... 1
    pages (:func:`_walk_kernel`)."""
    pages = max(1, min(WAVE_BYTES // page_bytes, MAX_WAVE_PAGES, table_width))
    return 1 << (pages.bit_length() - 1)


def _heads_per_update(kv_heads: int, group: int) -> int:
    """KV heads one online-softmax update covers: as many as fill a float32
    tile's eight rows with their ``group`` query rows each, and at least the
    pair one 32-bit word of a bfloat16 pool holds — 8 at one query row a KV
    head, 2 at four. Where that does not divide the heads, or its rows are
    not whole tiles, all heads are one block (its rows then start at 0, and
    nothing is sliced at an offset that is not a tile's): a bfloat16 pool of
    two or four heads at one row a head, or with a group that is no power of
    two (28 query heads on 4 KV heads), and a float32 pool of any head count
    — each of which Mosaic lowers for a described v5e."""
    heads = max(2, 8 // group)
    if kv_heads % heads or (heads * group) % 8:
        heads = kv_heads
    return heads


def _walk_kernel(table_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
                 k_buf, v_buf, sem, slot_ref, q_scr, m_scr, l_scr, acc_scr,
                 *, scale: float, block_s: int, wave: int, kv_heads: int,
                 heads: int):
    """One sequence a grid step; the walk over its pages is in here.

    ``k_hbm`` / ``v_hbm`` are the pools whole, in HBM; ``k_buf`` / ``v_buf``
    ``[2, wave * BS, KH, D]`` hold two waves of pages each; ``sem`` ``[2, 2]``
    (slot, k or v); ``slot_ref`` (SMEM; scratch lives across grid steps) is
    the slot of this step's first wave. The query ``[QH, D]``, scaled, and
    the online softmax's running maximum, sum and accumulator are laid one
    row a QUERY head (``q_scr``, ``m_scr``, ``l_scr``, ``acc_scr``): the rows
    of a block of ``heads`` KV heads are one slice of whole tiles.

    A sequence owns ``ceil(len / BS)`` pages, taken ``wave`` at a time: one
    copy a page from ``pool[layer, table[b, page]]``, a wave's copies all in
    flight together, into the slot the arithmetic is not reading. The next
    wave — after a sequence's last one, the next sequence's first — is
    started before the arithmetic on the current one, so a call costs the
    pages that hold tokens, and a slot without tokens one empty step. The
    table is read only below ``ceil(len / BS)``.

    The arithmetic on a wave goes in runs of pages: a full wave is one
    online-softmax update over ``wave * BS`` positions (an update's fixed
    cost, paid once a page, was the larger part of a page's time), a
    shorter last wave takes the runs of W/2, W/4, ... 1 pages that its
    count's binary digits name, so no row without a page is ever computed
    on, whatever VMEM holds there.
    """
    b = pl.program_id(0)
    batch = pl.num_programs(0)
    layer = layer_ref[0]

    def pages_of(seq):
        return jnp.minimum(
            jax.lax.div(len_ref[seq] + block_s - 1, block_s),
            table_ref.shape[1])

    def for_wave(seq, w, slot, act):
        """``act`` on every copy that brings wave ``w`` of ``seq`` into
        ``slot``: two a page the sequence holds there."""
        def one(page, _):
            block = table_ref[seq, w * wave + page]
            rows = pl.ds(pl.multiple_of(page * block_s, block_s), block_s)
            for i, (pool, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                act(pltpu.make_async_copy(pool.at[layer, block],
                                          buf.at[slot, rows],
                                          sem.at[slot, i]))
        jax.lax.fori_loop(
            0, jnp.minimum(pages_of(seq) - w * wave, wave), one, None)

    def start(seq, w, slot):
        for_wave(seq, w, slot, lambda copy: copy.start())

    def wait(seq, w, slot):
        for_wave(seq, w, slot, lambda copy: copy.wait())

    seq_len = len_ref[b]
    n_pages = pages_of(b)
    n_waves = jax.lax.div(n_pages + wave - 1, wave)

    @pl.when(b == 0)
    def _first_sequence():
        slot_ref[0] = 0
        start(0, 0, 0)

    first_slot = slot_ref[0]
    q_scr[...] = q_ref[0].astype(jnp.float32) * scale
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    group = q_scr.shape[0] // kv_heads
    block_rows = heads * group

    def block_update(i, slot, first, rows, first_pos):
        """The online-softmax update of KV heads ``i * heads`` … over
        ``rows`` cache rows: ONE tile of scores ``[heads * group, rows]``.

        A head's two products stay what they were, its ``[rows, D]`` keys
        and values against the query rows — but against the whole block's
        rows (a tile's eight sublanes cost the matrix unit what one does),
        of which the head's own are kept: the same numbers as a product
        with its rows alone, bit for bit on the chip (the output's digest
        at the four served shapes and around every page's and wave's edge,
        ``scripts/paged_kernel_bench.py``). What changes is that ``max``,
        ``exp``, ``where``, ``sum`` and the rescale run once a block on full
        vector registers and the scratch is read and written once a block:
        at one query row a KV head they ran on one sublane of eight, once a
        head (PERF.md §6, PR 47: 296.7 → 154.7 µs a call at 32 heads). The
        heads of a block are straight code so that one head's products hide
        the latency of the next one's (as a loop: 201.2 µs).

        Both products are float32 ``dot_general``s at the default precision,
        as they always were: on the chip Mosaic feeds the matrix unit the
        operands' bfloat16 roundings and accumulates in float32 (measured,
        same place: 4e-4 … 1.4e-3 against float64, where q, K, V and a
        three-term p as exact bfloat16 read 4e-7 at the same speed — a more
        exact result, but another one)."""
        at = pl.ds(i * block_rows if isinstance(i, int)
                   else pl.multiple_of(i * block_rows, block_rows),
                   block_rows)
        q = q_scr[at, :]

        def head_rows(buf):
            """``[rows, D]`` float32 of each head of the block, in order. A
            page lies in VMEM as it does in the pool, ``[BS, KH, D]``: a
            head's rows are ``KH`` apart, and cutting them out one head at a
            time re-reads the whole page for every head. A bfloat16 pool is
            read as 32-bit words instead: one word holds the same lane of
            two neighbouring heads, so ONE strided load brings a pair of
            heads' rows, and each half widens to float32 by a shift or a
            mask, which is exact."""
            if buf.dtype != jnp.bfloat16:
                for h in range(heads):
                    yield buf[slot, pl.ds(first, rows), i * heads + h,
                              :].astype(jnp.float32)
                return
            for j in range(heads // 2):
                words = head_pair_words(buf, slot * wave * block_s + first,
                                        rows, i * (heads // 2) + j)
                for odd in (0, 1):
                    yield widen_half(words, odd)

        def own_rows(per_head):
            """One ``[heads * group, n]`` array a head, in order; rows
            ``h * group`` … of the ``h``-th are head ``h``'s."""
            per_head = iter(per_head)
            out = next(per_head)
            row = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
            for h, x in enumerate(per_head, 1):
                out = jnp.where(row >= h * group, x, out)
            return out

        s = own_rows(jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) for k in head_rows(k_buf))
        pos = first_pos + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < seq_len, s, NEG_INF)

        m_prev = m_scr[at, :]
        l_prev = l_scr[at, :]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        p = jnp.where(pos < seq_len, p, 0.0)
        l_scr[at, :] = alpha * l_prev + jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True), l_prev.shape)
        acc_scr[at, :] = acc_scr[at, :] * alpha[:, :1] + own_rows(
            jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            for v in head_rows(v_buf))
        m_scr[at, :] = m_new

    def run_of_pages(slot, first, rows, first_pos):
        """Every block's update over ``rows`` rows from row ``first`` of
        ``slot``, which start at position ``first_pos``. The blocks of a
        bfloat16 pool are a loop, one block a trip, and not unrolled code:
        every bring-up traces and lowers this body, and every head of every
        run length cost it seconds (PERF.md §6, PR 40)."""
        blocks = kv_heads // heads
        if blocks == 1 or k_buf.dtype != jnp.bfloat16:
            for i in range(blocks):
                block_update(i, slot, first, rows, first_pos)
        else:
            jax.lax.fori_loop(
                0, blocks, lambda i, _: block_update(
                    i, slot, first, rows, first_pos), None)

    def one_wave(w, _):
        slot = jax.lax.rem(first_slot + w, 2)

        @pl.when(w + 1 < n_waves)
        def _next_wave():
            start(b, w + 1, 1 - slot)

        @pl.when((w + 1 == n_waves) & (b + 1 < batch))
        def _next_sequence():
            start(b + 1, 0, 1 - slot)

        wait(b, w, slot)
        held = jnp.minimum(n_pages - w * wave, wave)
        run = wave
        while run:
            @pl.when((held & run) != 0)
            def _run(run=run):
                # the pages of the larger runs come first
                page = held & (-2 * run)
                run_of_pages(slot, page * block_s, run * block_s,
                             (w * wave + page) * block_s)
            run //= 2

    jax.lax.fori_loop(0, n_waves, one_wave, None)

    @pl.when((n_waves == 0) & (b + 1 < batch))
    def _empty_slot():
        start(b + 1, 0, first_slot)

    slot_ref[0] = jax.lax.rem(first_slot + n_waves, 2)
    o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)).astype(
        o_ref.dtype)


def _as_pool(layer, k_pool: jnp.ndarray, *rest: jnp.ndarray):
    """``(layer [1] int32, k_pool, *rest)`` as the kernel takes them: the
    pool ``[L, N, BS, ...]`` and the layer to read, a scalar-prefetch
    operand like the table. One layer's plane ``[N, BS, KH, D]`` (with its
    companions) is layer 0 of a one-layer pool, a free reshape, and has no
    other. The kernel never takes a plane cut out of a stacked pool: a pallas
    call takes whole operands, so XLA would copy ``pool[layer]`` out first —
    the page copies pick the layer and a decode step reads the pool where
    it lives. The layer is an operand and not a constant of the kernel
    so that every layer of a model runs ONE kernel, traced and lowered
    once: a constant costs a trace per layer in every bring-up (PERF.md §6,
    PR 25)."""
    if k_pool.ndim == 4:
        layer = 0
        k_pool, *rest = (x[None] for x in (k_pool, *rest))
    return (jnp.asarray(layer, jnp.int32).reshape(1), k_pool, *rest)


def _page_walk(q, k_pool, v_pool, block_table, cache_len, layer, interpret):
    """:func:`paged_decode_attention` by :func:`_walk_kernel`."""
    layer, k_pool, v_pool = _as_pool(layer, k_pool, v_pool)
    batch, _, q_heads, head_dim = q.shape
    _, _, block_s, kv_heads, _ = k_pool.shape
    assert q_heads % kv_heads == 0
    group = q_heads // kv_heads
    wave = _pages_per_wave(
        block_s * kv_heads * head_dim * k_pool.dtype.itemsize,
        block_table.shape[1])

    # a row a query head: head ``h``'s rows are ``h * group`` … (a free
    # reshape of the contiguous ``[B, 1, QH, D]``)
    qt = q.reshape(batch, q_heads, head_dim)

    def q_index(b, table, lens, layer):
        return (b, 0, 0)

    out = pl.pallas_call(
        functools.partial(_walk_kernel, scale=head_dim ** -0.5,
                          block_s=block_s, wave=wave, kv_heads=kv_heads,
                          heads=_heads_per_update(kv_heads, group)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(batch,),
            in_specs=[pl.BlockSpec((1, q_heads, head_dim), q_index),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, q_heads, head_dim), q_index),
            scratch_shapes=[
                pltpu.VMEM((2, wave * block_s, kv_heads, head_dim),
                           k_pool.dtype),
                pltpu.VMEM((2, wave * block_s, kv_heads, head_dim),
                           v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((q_heads, head_dim), jnp.float32),
                pltpu.VMEM((q_heads, 128), jnp.float32),
                pltpu.VMEM((q_heads, 128), jnp.float32),
                pltpu.VMEM((q_heads, head_dim), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        # the slot and the copies in flight pass from one step to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        # the benchmark counts decode steps by this operation's calls
        name="paged_decode_attention",
        interpret=interpret,
    )(block_table.astype(jnp.int32), cache_len.astype(jnp.int32), layer,
      qt, k_pool, v_pool)
    return out.reshape(batch, 1, q_heads, head_dim)


def _table_block(table, b, sb, lens, block_s: int):
    """Physical pool block for grid step ``sb`` of :func:`_page_grid`:
    past-the-end steps CLAMP to the sequence's last valid block (same
    physical index as the previous step ⇒ Mosaic elides the DMA), so only
    ceil(len/BS) pool blocks are read per sequence regardless of table
    width, and a table entry past them is never dereferenced."""
    last = jnp.maximum(
        jax.lax.div(lens[b] + block_s - 1, block_s) - 1, 0)
    return table[b, jnp.minimum(sb, last)]


def _grid_kernel(table_ref, len_ref, layer_ref, *refs, **static):
    """:func:`_kernel`; the difference is entirely in the BlockSpec index
    maps (physical blocks come from the table, the pool's layer from
    ``layer_ref``)."""
    del table_ref, layer_ref
    _kernel(len_ref, *refs, **static)


def _page_grid(q, pools, block_table, cache_len, layer, interpret):
    """The walk as a grid, one step a table COLUMN, the pages fetched by
    the BlockSpec pipeline: every column costs a step whether or not it
    holds a page (PERF.md §6, PR 40: ≈ 0.2 µs each), so this is only for
    the pools :func:`_page_walk` cannot take."""
    layer, *pools = _as_pool(layer, *pools)
    batch, _, q_heads, head_dim = q.shape
    _, _, block_s, kv_heads, _ = pools[0].shape
    max_sb = block_table.shape[1]
    assert q_heads % kv_heads == 0
    group = q_heads // kv_heads

    qt = q.reshape(batch, kv_heads, group, head_dim)

    def page_index(trailing):
        def index(b, sb, table, lens, layer):
            return (layer[0], _table_block(table, b, sb, lens, block_s),
                    *(0,) * trailing)
        return index

    def q_index(b, sb, table, lens, layer):
        return (b, 0, 0, 0)

    out = pl.pallas_call(
        functools.partial(_grid_kernel, scale=head_dim ** -0.5,
                          block_s=block_s, num_sb=max_sb, kv_heads=kv_heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(batch, max_sb),
            in_specs=[pl.BlockSpec((1, kv_heads, group, head_dim), q_index)]
            + [pl.BlockSpec((None, 1, *pool.shape[2:]),
                            page_index(pool.ndim - 2)) for pool in pools],
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
      qt, *pools)
    return out.reshape(batch, 1, q_heads, head_dim)


def _pages_can_be_cut(pool: jnp.ndarray) -> bool:
    """Whether the kernel itself may copy ``pool[layer, block]`` out of HBM.
    Mosaic slices an HBM operand only along dimensions its tiling does not
    pad (asked of the compiler for a described v5e, PR 40): a head of whole
    128-lane rows and, below 32 bits, as many KV heads as a tile has rows
    (2, 4, or a multiple of 8); an int8 pool never, for its scale planes
    ``[BS, KH]`` are padded to 128 lanes. The BlockSpec pipeline has no such
    limit. The one predicate of walk or grid: :func:`paged_decode_attention`
    decides by it and :func:`paged_decode_form` says what it decided."""
    *_, kv_heads, head_dim = pool.shape
    return pool.dtype != jnp.int8 and head_dim % 128 == 0 and (
        pool.dtype.itemsize == 4 or kv_heads in (2, 4) or kv_heads % 8 == 0)


def paged_decode_form(pool, q_heads: int, table_width: int) -> str:
    """Which body :func:`paged_decode_attention` (or its int8 twin) runs over
    ONE chip's ``pool`` (anything with the pool's ``shape`` and ``dtype``),
    in words (``ops.attention.paged_kernel_form`` takes a chip's share of an
    engine's pool and hands it here). Static, like the shapes it follows
    from."""
    *_, block_s, kv_heads, head_dim = pool.shape
    if not _pages_can_be_cut(pool):
        return "pallas grid, 1 head an update, 1 page a step"
    group = q_heads // kv_heads
    wave = _pages_per_wave(
        block_s * kv_heads * head_dim * pool.dtype.itemsize, table_width)
    return (f"pallas walk, {_heads_per_update(kv_heads, group)} heads x "
            f"{group} row{'s' * (group > 1)} an update, {wave} pages a wave")


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
    are never read); cache_len [B] valid tokens incl. current. Returns
    [B,1,QH,D].

    Reference analogue: the engine-side KV management the reference's
    LLM router assumes (pkg/abstractions/pod/llm.go token pressure); the
    kernel itself is the TPU equivalent of paged_attention — physical
    blocks are DMA'd straight from the pool by table lookup inside the
    kernel (:func:`_walk_kernel`), so fragmentation-free sharing
    (prefix reuse) costs nothing on the read path, and neither does the
    layer (:func:`_as_pool`).
    """
    if not _pages_can_be_cut(k_pool):
        return _page_grid(q, (k_pool, v_pool), block_table, cache_len,
                          layer, interpret)
    return _page_walk(q, k_pool, v_pool, block_table, cache_len, layer,
                      interpret)


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
    only difference is the in-kernel dequant multiply after each block DMA,
    so HBM moves half the cache bytes. Always the grid: a scale page
    ``[BS, KH]`` is padded to 128 lanes in HBM (:func:`_pages_can_be_cut`)."""
    return _page_grid(q, (k_pool, v_pool, k_scale, v_scale), block_table,
                      cache_len, layer, interpret)


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
