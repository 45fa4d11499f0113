"""What the KV state of a ``DecoderConfig`` is, and how a layer writes it and
attends over it: THE one owner of the cache's format. ``models.transformer``
and ``models.hybrid`` call the verbs; the pool, the feasibility gate, the
graph factory, the sharding policy and the engine ask for shapes and bytes.
Nothing else under ``models/`` reads a key of the cache dict, and ``serving/``
names only what it moves itself (the ``"k"`` / ``"v"`` planes its splice and
gather programs carry, the table it pushes, the wire format's header), so a
new kind of cache is written here.

**The format.** A running sequence keeps two sorts of state:

- PAGED planes, addressed by cache ENTRY (``DecoderConfig.kv_entry``: a
  token's position for plain attention): ``"k"`` and ``"v"``, each
  ``[depth, ., ., *row]`` with ``depth = cfg.kv_layers`` and the rows of
  ``cfg.kv_row`` — per-head keys and values ``(KH, D)``, or for latent
  attention one latent ``(1, mla_latent)`` under ``"k"`` and its rotated key
  ``(1, mla_rope)`` under ``"v"``. In a POOL the two middle axes are
  ``[n_blocks, block]`` and a ``"table"`` ``[lanes, columns]`` names each
  lane's blocks; an int8 pool holds the rows as int8 beside float32
  ``"k_scale"`` / ``"v_scale"`` planes of one rank less (one absmax scale a
  (token, head) vector, ``ops.quant.quantize_kv``). A listed pattern's heads
  narrower than the chip's 128 lanes lie ``cfg.kv_pack`` to a row
  (:func:`heads_per_row`, :func:`pack_heads`: a row
  of 64-wide heads is half-empty 128-lane registers, and the chip's
  compiler then keeps the pool in a layout of its own and copies it to the
  kernels' and back, every write of every step: PR 55's first traced run),
  which ``cfg.kv_row`` states and every shape below follows. A pool's
  ROTATED KEYS
  lie two tokens a row, ``[depth, n_blocks, block / 2, 1, 2 mla_rope]``:
  token ``j`` of a page in the first ``mla_rope`` lanes of row ``j``, token
  ``j + block / 2`` in the rest (``ops.latent_attention.pack_rotated``) —
  the same bytes a block, and a page of them is one 128-lane block that the
  decode kernel copies where it lies, which a 64-wide row is not.
  :func:`splice_block` packs a block on its way in, :func:`read_blocks`
  gives rows back in token order, :func:`write` puts a decode step's row
  into its half row; no caller sees the packing. In a DENSE cache (the
  dense engine's, the batch-1 prefill scratch, a prefill bucket) they are
  ``[lanes, rows]``, always in the model's type. Axis ``HEAD_AXIS`` is the
  KV heads' in every one of them: what a mesh shards.
- state a LANE, the same size at any length: a KDA layer's float32 matrix
  ``"kda_state"`` ``[P, lanes, H, d, d]`` and the last ``kda_conv - 1`` inputs
  of its short convolution ``"kda_conv"`` ``[P, lanes, K-1, 3 H d]`` (``P`` KDA
  layers), beside latent rows; a state-space layer's float32 matrix
  ``"ssm_state"`` ``[P, lanes, H / pack, N, pack d]`` (as ``ops.ssd`` stores
  it: ``pack`` heads side by side along 128 lanes) and its convolution's
  last inputs
  ``"ssm_conv"`` ``[P, lanes, K-1, H d + 2 G N]`` (``P`` such layers of a
  listed pattern), beside PER-HEAD rows. No table, no pages; the dense
  scratch carries one lane of it. A listed pattern's gated short
  convolutions (``"conv"``) keep ONE array, ``"conv_tail"`` ``[P, lanes, K-1,
  D]``: the last ``conv_taps - 1`` rows of the product they convolve — for
  them the tail IS the state. ``LANE_KINDS`` names the arrays of each kind
  (a state where the kind has one, then its convolution's tail);
  ``DecoderConfig.lane_state`` says which kinds a decoder has.
- state a BLOCK, beside such a tail: ``BLOCK_TAIL`` ``[P, n_blocks, K-1,
  D]`` in a pool — for every page, every convolution layer's tail as of the
  page's LAST row, which is what a sequence admitted behind that page (a
  prefix hit) starts from — and ``[P, lanes, rows / block, K-1, D]`` in the
  batch-1 scratch, where a chunk's forward leaves the tails of the pages it
  fills (:func:`block_tails_write`) for the splice to carry into the pool
  (:func:`splice_block_tails`; :func:`block_tail_read` is the hit's read).
  The first plane that is neither a row a token nor state a lane. A decode
  step writes none: the prefix cache shares PROMPT pages alone.

**The verbs.** :func:`write` puts a layer's fresh rows where their entries
say and :func:`attend` runs the layer's queries over what is written, through
the dispatchers of ``ops.attention``; latent attention reads through
:func:`latent_attend` (a decode step over the pool) and :func:`latent_rows`
or :func:`latent_planes` (a chunk over the scratch, every row expanded or a
block of keys at a time), a KDA or state-space layer through
:func:`lane_read` and :func:`lane_write`. The cache dict is carried WHOLE
from layer to layer:
every write is into the ``[depth, ...]`` arrays in place (a donated or
carried array), nothing is sliced out and stacked back.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import (attention, chunk_prefill_attention,
                             decode_attention, paged_attention_dispatch,
                             paged_verify_attention)
from ..ops.latent_attention import (pack_rotated, paged_latent_attention,
                                     unpack_rotated)
from ..ops.quant import quantize_kv
from ..ops.ssd import state_shape as ssd_state_shape

TABLE = "table"
# the arrays a kind of layer keeps by LANE: (state, convolution tail), or the
# tail alone where that is all the state there is (a short convolution's)
LANE_KINDS = {"kda": ("kda_state", "kda_conv"),
              "ssm": ("ssm_state", "ssm_conv"),
              "conv": ("conv_tail",)}
# the plane a pool keeps a BLOCK: every convolution layer's tail as of the
# block's last row
BLOCK_TAIL = "conv_block_tail"
# of every paged array, pool and dense alike: [depth, ., ., KH, ...]
HEAD_AXIS = 3
_SCALE = {"k": "k_scale", "v": "v_scale"}


# -- the format ---------------------------------------------------------------

def paged_planes(cfg, quantized: bool = False) -> dict:
    """``name -> (row shape, dtype)`` of the planes a POOL keeps an entry a
    row of. Latent rows are read as they are written, in the model's type:
    ``quantized`` is for per-head rows alone."""
    rows = dict(zip(_SCALE, cfg.kv_row))
    if cfg.latent_rows or not quantized:
        return {name: (row, cfg.dtype) for name, row in rows.items()}
    # scales: the payload's [N, BS, KH] indexing, so every write and read
    # shares the table math
    return {**{name: (row, jnp.int8) for name, row in rows.items()},
            **{_SCALE[name]: (row[:-1], jnp.float32)
               for name, row in rows.items()}}


def pool_shapes(cfg, n_blocks: int, block: int,
                quantized: bool = False) -> dict:
    """``name -> (shape, dtype)`` of a pool of ``n_blocks`` blocks of
    ``block`` entries (its table and the lanes' state apart). Latent
    attention's rotated keys lie two tokens a row (the module's head)."""
    planes = paged_planes(cfg, quantized)
    shapes = {name: ((cfg.kv_layers, n_blocks, block) + row, dt)
              for name, (row, dt) in planes.items()}
    if cfg.latent_rows:
        if block % 2:
            raise ValueError(f"a block of {block} entries: the rotated keys "
                             "of a latent pool lie two tokens a row")
        (heads, rope), dt = planes["v"]
        shapes["v"] = ((cfg.kv_layers, n_blocks, block // 2, heads,
                        2 * rope), dt)
    return shapes


def lane_shapes(cfg, lanes: int) -> dict:
    """``name -> (shape, dtype)`` of the state that the layers of
    ``cfg.lane_state``'s kinds — KDA, state-space, short convolutions — keep
    for ``lanes`` running sequences, a plane a layer of the kind; empty for
    a decoder without such layers."""
    planes = {kind: (len(cfg.layers_of(kind)), lanes)
              for kind in cfg.lane_state}
    shapes = {}
    if "kda" in planes:
        h, d = cfg.n_heads, cfg.head_dim
        shapes.update(
            kda_state=(planes["kda"] + (h, d, d), jnp.float32),
            kda_conv=(planes["kda"] + (cfg.kda_conv - 1, 3 * h * d),
                      cfg.dtype))
    if "ssm" in planes:
        h, d = cfg.ssm_heads, cfg.ssm_head_dim
        width = h * d + 2 * cfg.ssm_groups * cfg.ssm_state
        # (the matrix as ``ops.ssd`` stores it: ``state_shape`` says why)
        shapes.update(
            ssm_state=(planes["ssm"] + ssd_state_shape(
                h, d, cfg.ssm_state, cfg.ssm_groups), jnp.float32),
            ssm_conv=(planes["ssm"] + (cfg.ssm_conv - 1, width), cfg.dtype))
    if "conv" in planes:
        shapes["conv_tail"] = (planes["conv"] + (cfg.conv_taps - 1, cfg.dim),
                               cfg.dtype)
    return shapes


def block_tail_shapes(cfg, n_blocks: int, lanes: int = 0) -> dict:
    """``name -> (shape, dtype)`` of the state kept a BLOCK, for a pool of
    ``n_blocks`` blocks (``lanes`` 0) or a dense cache of as many a lane:
    the tails of a listed pattern's short convolutions (the module's head);
    empty for every other decoder."""
    if not cfg.conv_taps:
        return {}
    blocks = (lanes, n_blocks) if lanes else (n_blocks,)
    return {BLOCK_TAIL: ((len(cfg.layers_of("conv")),) + blocks
                         + (cfg.conv_taps - 1, cfg.dim), cfg.dtype)}


def dense_shapes(cfg, lanes: int, rows: int, dtype=None,
                 block: int = 0) -> dict:
    """``name -> (shape, dtype)`` of a dense cache of ``rows`` entries a
    lane — the dense engine's, the batch-1 scratch that chunked prefill
    writes through, a prefill bucket — with the lanes' state beside it, and
    where the cache is cut into pages of ``block`` entries (the scratch) the
    state a block."""
    shapes = {name: ((cfg.kv_layers, lanes, rows) + row, dtype or dt)
              for name, (row, dt) in paged_planes(cfg).items()}
    if block and rows % block:
        raise ValueError(f"a dense cache of {rows} rows in pages of {block}")
    return {**shapes, **lane_shapes(cfg, lanes),
            **(block_tail_shapes(cfg, rows // block, lanes) if block else {})}


def _bytes(shapes: dict) -> int:
    return sum(math.prod(shape) * np.dtype(dt).itemsize
               for shape, dt in shapes.values())


def block_bytes(cfg, block: int, quantized: bool = False) -> int:
    """Bytes ONE pool block of ``block`` entries holds across the whole
    depth of the state: ``block`` rows of :func:`paged_planes` a plane,
    however a pool lays them (any ``block``: a sequence's whole length is
    priced as one). What the engine's equal-HBM pool sizing, the feasibility
    gate and the tier's statistics all price blocks with."""
    return cfg.kv_layers * block * _bytes(paged_planes(cfg, quantized))


def lane_bytes(cfg, lanes: int = 1) -> int:
    """Bytes of :func:`lane_shapes`."""
    return _bytes(lane_shapes(cfg, lanes))


def block_tail_bytes(cfg, n_blocks: int = 1) -> int:
    """Bytes of :func:`block_tail_shapes`: what ``n_blocks`` pool blocks
    hold BESIDE their rows (:func:`block_bytes` prices those)."""
    return _bytes(block_tail_shapes(cfg, n_blocks))


def head_axis(name: str, ndim: int) -> Optional[int]:
    """The axis of state array ``name`` (of rank ``ndim``) that holds the KV
    heads, which a mesh shards; None for the table and the lanes' state."""
    paged = name in _SCALE or name in _SCALE.values()
    return HEAD_AXIS if paged and ndim > HEAD_AXIS else None


def init_kv_cache(cfg, batch: int, max_len: int = 0, dtype=None,
                  block: int = 0) -> dict:
    """Contiguous per-sequence KV cache: k/v ``[L, B, S, *row]``, ``L`` the
    depth of the KV state (``cfg.kv_layers``), and the state kept a lane —
    and, in pages of ``block``, a block — beside it (:func:`dense_shapes`)."""
    return {name: jnp.zeros(shape, dt) for name, (shape, dt) in dense_shapes(
        cfg, batch, max_len or cfg.max_seq_len, dtype, block).items()}


# -- heads packed to whole rows -------------------------------------------------

# numbers a vector register holds side by side on the chip
ROW_LANES = 128


def heads_per_row(head_dim: int, heads: int) -> int:
    """KV heads of ``head_dim`` numbers that a cache row holds side by side
    where narrow heads are packed (``DecoderConfig.kv_pack`` says where; 1 =
    a head a row): heads narrower than ``ROW_LANES`` lie as many as fill the
    lanes — two of 64, all of them where they are fewer — when that is a
    whole number of heads a row and of rows."""
    if head_dim >= ROW_LANES or ROW_LANES % head_dim:
        return 1
    pack = min(ROW_LANES // head_dim, heads)
    return pack if heads % pack == 0 else 1


def pack_heads(q, k, v, pack: int):
    """Keys, values and queries ``[B, T, heads, D]`` as a cache of ``pack``
    KV heads a row takes them (:func:`heads_per_row`): ``k`` and ``v``
    ``[B, T, KH / pack, pack D]`` — consecutive heads side by side, a free
    reshape — and each query ``[B, T, QH, pack D]``, its ``D`` numbers where
    its KV head lies in the row and zero elsewhere, so that its product with
    a row is its product with its own head. It also carries ``sqrt(pack)``
    (multiplied in float32, rounded once to the queries' type): every
    attention form divides the scores by the root of the ROW's width."""
    b, t, kh, d = k.shape
    group = q.shape[2] // kh
    k, v = (a.reshape(b, t, kh // pack, pack * d) for a in (k, v))
    q = (q.astype(jnp.float32) * pack ** 0.5).astype(q.dtype).reshape(
        b, t, kh // pack, pack, group, 1, d)
    here = jnp.eye(pack, dtype=q.dtype)[:, None, :, None]   # [pack,1,pack,1]
    return (q * here).reshape(b, t, kh * group, pack * d), k, v


def unpack_heads(out, kv_heads: int, pack: int):
    """The attention's output over packed rows ``[B, T, QH, pack D]`` as the
    heads' own ``[B, T, QH, D]``: of each query's row-wide result the part
    that its KV head's values gave."""
    b, t, qh, width = out.shape
    d, group = width // pack, qh // kv_heads
    out = out.reshape(b, t, kv_heads // pack, pack, group, pack, d)
    here = jnp.eye(pack, dtype=out.dtype)[:, None, :, None]
    return jnp.sum(out * here, axis=5).reshape(b, t, qh, d)


# -- what a cache dict is ------------------------------------------------------

def is_paged(kv: dict) -> bool:
    """Whether ``kv`` is a pool read through a block table (else dense)."""
    return TABLE in kv


def dense_len(kv: dict) -> int:
    """Rows a lane of a dense cache (0 for a pool: its table bounds it)."""
    return 0 if is_paged(kv) else kv["k"].shape[2]


def _packed(pool: dict) -> bool:
    """Whether ``pool`` is latent attention's: its ``"v"`` plane the rotated
    keys, two tokens a row."""
    return pool["v"].shape[2] != pool["k"].shape[2]


def pool_rows(pool: dict, k, v) -> list:
    """The rows ``k`` and ``v`` ``[..., KH, D]`` as a per-head ``pool``
    stores them, ``[(name, rows)]`` in the order they are written: an int8
    pool's are quantized per (token, head) vector, their scales ``[..., KH]``
    first."""
    if _SCALE["k"] not in pool:
        return [("k", k), ("v", v)]
    k, sk = quantize_kv(k)
    v, sv = quantize_kv(v)
    return [(_SCALE["k"], sk), (_SCALE["v"], sv), ("k", k), ("v", v)]


def _lanes_view(plane):
    """The rotated keys' pool ``[L, N, BS / 2, 1, 2 d_r]`` without its unit
    axis, as the decode kernel takes it: a free reshape, and the view every
    write goes through. Written as it is, the chip's compiler gave the
    plane another tiling for the write than for the kernel and copied it
    whole, there and back, a layer of a decode step and a splice (0.5 GB
    each way at Kimi's sizes: PR 53's first traced run)."""
    return plane.reshape(plane.shape[:3] + plane.shape[4:])


def splice_block(pool: dict, phys, j: int, k, v) -> dict:
    """``pool`` with one block's scratch rows ``k`` and ``v`` ``[L, BS, KH,
    D]`` written at physical block ``phys[j]`` of every plane, as the pool
    stores them: an int8 pool's quantized (:func:`pool_rows`), a latent
    pool's rotated keys packed two tokens a row. (``phys[j]`` is taken once
    a plane, as the splice always did: a per-head pool's programs lower to
    the text they had.)"""
    pool = dict(pool)
    if not _packed(pool):
        for name, rows in pool_rows(pool, k, v):
            pool[name] = pool[name].at[:, phys[j]].set(rows)
        return pool
    block = phys[j]
    pool["k"] = pool["k"].at[:, block].set(k)
    # one scatter, TWO windows of ``[BS / 4, 2 d_r]`` a plane: a single
    # window (any pool of one plane) is a ``dynamic_update_slice`` to the
    # chip's compiler, which then tiles the whole plane to suit the update
    # and copies it there and back (compiled for a described v5e: three
    # copies of Ling's 67 MB plane a group program, two of Kimi's 0.7 GB)
    rows = pack_rotated(v, 1)[:, :, 0]                 # [L, BS / 2, 2 d_r]
    l, half_s, width = rows.shape
    planes, first = jnp.meshgrid(
        jnp.arange(l), jnp.arange(2) * (half_s // 2), indexing="ij")
    at = jnp.stack([planes, jnp.full_like(planes, block), first],
                   axis=-1).reshape(2 * l, 3)
    pool["v"] = jax.lax.scatter(
        _lanes_view(pool["v"]), at, rows.reshape(2 * l, half_s // 2, width),
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=(1, 2), inserted_window_dims=(0, 1),
            scatter_dims_to_operand_dims=(0, 1, 2))
    ).reshape(pool["v"].shape)
    return pool


def read_blocks(pool: dict, name: str, index, flat: bool = False):
    """Blocks ``index`` of plane ``name`` at every depth, ``[L, len(index),
    BS, ...]`` in token order; an int8 pool's are dequantized (float32), a
    latent pool's rotated keys unpacked. ``flat``: the
    blocks are taken from the pool as ``[L * N, BS, ...]`` (a free reshape)
    by ``layer * N + index``, a gather along the MAJOR axis. Taken along
    axis 1, as without it, the chip's compiler first copies the whole pool
    so that the gathered axis leads — 4.0 GB of temporaries for a 3.8 GB
    plane of latents against 0.35 GB (compiled for a described v5e, PR 52);
    the programs of the per-head pools keep the form they were measured
    with."""
    if flat:
        p = pool[name]
        l, n = p.shape[:2]
        g = p.reshape((l * n,) + p.shape[2:])[
            jnp.arange(l)[:, None] * n + index[None, :]]
    else:
        g = pool[name][:, index]
    sc = pool.get(_SCALE[name])
    if sc is not None:
        g = g.astype(jnp.float32) * sc[:, index][..., None]
    return unpack_rotated(g, 2) if name == "v" and _packed(pool) else g


# -- write --------------------------------------------------------------------

def _pool_write(pool: jnp.ndarray, layer: int, bi, oi, value):
    """The paged pool ``[L, N, BS, ...]`` with ``value`` written at
    ``[layer, bi, oi]``: a scatter into the whole array, which XLA does in
    place on a donated or carried pool — no plane is cut out and none is
    stacked back."""
    with jax.named_scope("kv.write"):
        return pool.at[layer, bi, oi].set(value)


def _packed_write(pool: jnp.ndarray, layer: int, bi, oi, value):
    """The rotated keys' pool ``[L, N, BS / 2, 1, 2 d_r]`` with a decode
    step's ``value`` ``[B, d_r]`` written at entry ``oi`` of block ``bi``:
    each lane's key into its half of row ``oi % (BS / 2)``. One scatter of
    ``B`` windows of ``d_r`` numbers, in place as :func:`_pool_write`'s."""
    half_s, dr = pool.shape[2], value.shape[-1]
    at = jnp.stack([jnp.full_like(bi, layer), bi, oi % half_s,
                    oi // half_s * dr], axis=-1)
    with jax.named_scope("kv.write"):
        return jax.lax.scatter(
            _lanes_view(pool), at, value, jax.lax.ScatterDimensionNumbers(
                update_window_dims=(1,), inserted_window_dims=(0, 1, 2),
                scatter_dims_to_operand_dims=(0, 1, 2, 3))
        ).reshape(pool.shape)


def _cache_write(cache: jnp.ndarray, layer: int, item, positions):
    """The dense cache ``[L, B, S, KH, D]`` with ``item`` ``[B, T, KH, D]``
    written at ``layer``, each row's ``T`` tokens from that row's first
    position on: ``dynamic_update_slice`` into the whole array at batch 1,
    a scatter with the same clamp of the start over several rows."""
    b, t = item.shape[:2]
    with jax.named_scope("kv.write"):
        if b == 1:
            return jax.lax.dynamic_update_slice(
                cache, item[None], (layer, 0, positions[0, 0], 0, 0))
        start = jnp.clip(positions[:, :1], 0, cache.shape[2] - t)
        return cache.at[layer, jnp.arange(b)[:, None],
                        start + jnp.arange(t)].set(item)


def write(kv: dict, layer, k, v, entries, decode: bool) -> dict:
    """``kv`` with this layer's fresh rows written at plane ``layer``, where
    ``entries`` ``[B, T]`` says. ``k`` and ``v`` are per-head keys and values
    ``[B, T, KH, D]``, or latent rows and their rotated keys ``[B, T, width]``
    (no head axis: the cache keeps ONE row for all heads).

    Paged: the rows are scattered into the lanes' physical pool blocks. The
    pool ``[L, N_BLOCKS, BS, ...]`` is shared by all sequences — prefix
    blocks can be referenced by many tables (prefix reuse). An int8 pool
    quantizes the write per (token, head) vector. ``decode`` writes one row a
    lane; otherwise a multi-token VERIFY (speculative decoding): all T
    window tokens are written in one shot. Rejected draft positions simply
    hold garbage after the window — attention masks by position, and the
    next window's writes overwrite them (paged scratch re-splice semantics).

    Dense ``[L, B, S, ...]``: a decode step's row at each lane's position; a
    CHUNK at its PER-ROW offset — graph shapes are (C, S) no matter how long
    the prompt is; the engine admits chunks at batch 1, but the signature
    accepts [B, C] positions, and row 0's offset applied to every row would
    write other rows' chunks at the wrong cache slots (silently wrong
    logits), so the write is per row; a whole prompt at [0, t)."""
    if not is_paged(kv):
        if k.ndim == 3:       # a latent row under the cache's one-head axis
            return dict(
                kv, k=_cache_write(kv["k"], layer, k[:, :, None], entries),
                v=_cache_write(kv["v"], layer, v[:, :, None], entries))
        return dict(kv, k=_cache_write(kv["k"], layer, k, entries),
                    v=_cache_write(kv["v"], layer, v, entries))
    b = entries.shape[0]
    table = kv[TABLE]                              # [B, MB]
    bs = kv["k"].shape[2]                          # [L, N, BS, ...]
    if k.ndim == 3:
        # a decode step's latent row (``hybrid.mla_block`` refuses a verify
        # window over latents)
        pos = entries[:, 0]
        bi, oi = table[jnp.arange(b), pos // bs], pos % bs
        return dict(kv, k=_pool_write(kv["k"], layer, bi, oi, k[:, 0, None]),
                    v=_packed_write(kv["v"], layer, bi, oi, v[:, 0]))
    if decode:
        pos = entries[:, 0]                        # [B]
        bi = table[jnp.arange(b), pos // bs]
        k, v = k[:, 0], v[:, 0]                    # [B, KH, D]
    else:
        pos = entries                              # [B, T]
        bi = jnp.take_along_axis(table, pos // bs, axis=1)
    oi = pos % bs
    with jax.named_scope("kv.write"):
        rows = pool_rows(kv, k, v)                 # [.., KH, D], [.., KH]
    kv = dict(kv)
    for name, value in rows:
        kv[name] = _pool_write(kv[name], layer, bi, oi, value)
    return kv


# -- attend -------------------------------------------------------------------

def attend(kv: dict, layer, q, k, v, entries, cache_len, decode: bool,
           mesh=None):
    """Attention of this layer's queries ``q`` ``[B, T, H, D]`` over what
    :func:`write` left at plane ``layer``; ``k`` and ``v`` are the fresh rows
    themselves, which a whole-prompt prefill attends without reading back.

    Paged: a decode step (T = 1) is block-table paged attention over the
    prefix, an int8 pool dequantized after the block read; a verify window's
    queries each attend over their own absolute-position prefix. Dense: a
    decode step over the prefix of its lane; a chunk (``cache_len`` given)
    over prefix + chunk with the absolute-position mask; a whole prompt,
    causal within itself."""
    if is_paged(kv):
        scales = (kv[_SCALE["k"]], kv[_SCALE["v"]]) \
            if _SCALE["k"] in kv else ()
        with jax.named_scope("attn.core"):
            if decode:
                return paged_attention_dispatch(
                    q, kv["k"], kv["v"], kv[TABLE], cache_len, *scales,
                    mesh=mesh, layer=layer)
            return paged_verify_attention(
                q, kv["k"], kv["v"], kv[TABLE], entries, *scales,
                layer=layer)
    if not decode and cache_len is None:
        with jax.named_scope("attn.core"):
            return attention(q, k, v, causal=True, mesh=mesh)
    if decode:
        # one layer's plane, for a decode attention that takes ``[B, S, KH,
        # D]``: an XLA consumer fuses the slice; the ragged pallas kernel has
        # it materialised. Chunked prefill cuts no plane: its kernel reads
        # the cache at its layer (the XLA form it falls back to slices
        # there, under the same scope)
        with jax.named_scope("kv.slice"):
            k_cache, v_cache = kv["k"][layer], kv["v"][layer]
        with jax.named_scope("attn.core"):
            return decode_attention(q, k_cache, v_cache, cache_len,
                                    mesh=mesh)
    with jax.named_scope("attn.core"):
        return chunk_prefill_attention(q, kv["k"], kv["v"], entries,
                                       layer=layer, mesh=mesh)


def latent_attend(kv: dict, layer, q_lat, q_rope, cache_len, scale):
    """A decode step of latent attention over the pool: the queries, already
    absorbed into the latent's space, over the lanes' latent rows and their
    rotated keys at plane ``layer`` (``ops.latent_attention``)."""
    return paged_latent_attention(q_lat, q_rope, kv["k"], kv["v"],
                                  kv[TABLE], cache_len, layer, scale)


def latent_rows(kv: dict, layer):
    """Every row of the batch-1 dense scratch at plane ``layer``:
    ``(latents [S, mla_latent], rotated keys [S, mla_rope])``, for a chunk
    that expands keys and values from them."""
    with jax.named_scope("kv.slice"):
        return kv["k"][layer, 0, :, 0], kv["v"][layer, 0, :, 0]


def latent_planes(kv: dict):
    """The batch-1 dense scratch's latents and rotated keys WHOLE, ``([L, 1,
    S, 1, mla_latent], [L, 1, S, 1, mla_rope])``: what the blocked prefill
    reads at its layer, where they lie."""
    return kv["k"], kv["v"]


# -- state a lane --------------------------------------------------------------

def lane_read(kv: dict, plane: int, kind: str = "kda"):
    """``(state [B, H, ., .], convolution tail [B, K-1, channels])`` of the
    ``plane``-th layer of ``kind`` (``LANE_KINDS``), every lane's; ``(tail,)``
    of a kind that keeps the one array."""
    return tuple(kv[name][plane] for name in LANE_KINDS[kind])


def lane_states(kv: dict, kind: str = "kda"):
    """Every ``kind`` layer's state, whole ``[P, B, H, ., .]``: what the
    Pallas step updates in place at its plane."""
    return kv[LANE_KINDS[kind][0]]


def lane_write(kv: dict, plane: int, tail, state=None, states=None,
               kind: str = "kda") -> dict:
    """``kv`` with the state and convolution tail of the ``plane``-th layer
    of ``kind`` replaced: ``state`` ``[B, H, ., .]`` written at the plane, or
    ``states`` the whole array as a step in place left it; the tail alone for
    a kind that keeps the one array."""
    *name, conv = LANE_KINDS[kind]
    if name and states is None:
        states = kv[name[0]].at[plane].set(state)
    return dict(kv, **dict.fromkeys(name, states),
                **{conv: kv[conv].at[plane].set(tail)})


# -- state a block --------------------------------------------------------------

def block_tails_write(kv: dict, plane: int, z, first):
    """The dense scratch ``kv`` with the tails of the pages a chunk fills:
    ``z`` ``[1, T, D]`` is what the ``plane``-th convolution layer convolves
    over the chunk's ``T`` rows, which start at entry ``first`` (a page's
    first row: chunks are whole pages). The tail as of a page's last row is
    that page's own last ``K - 1`` rows of ``z`` — a strided read of a tensor
    the forward has in hand. A cache that keeps no state a block (a test's
    dense cache, a decode step's pool) comes back as it is."""
    if BLOCK_TAIL not in kv or is_paged(kv):
        return kv
    tails = kv[BLOCK_TAIL]                     # [P, 1, S / BS, K-1, D]
    block, keep = dense_len(kv) // tails.shape[2], tails.shape[3]
    b, t, d = z.shape
    if t % block or keep > block:
        raise ValueError(f"a chunk of {t} rows over pages of {block} with "
                         f"tails of {keep}: whole pages, each at least as "
                         "long as a tail")
    ends = z.reshape(b, t // block, block, d)[:, :, block - keep:]
    with jax.named_scope("kv.write"):
        return dict(kv, **{BLOCK_TAIL: jax.lax.dynamic_update_slice(
            tails, ends.astype(tails.dtype)[None],
            (plane, 0, first // block, 0, 0))})


def splice_block_tails(pool: dict, scratch_tails, first_page, phys) -> dict:
    """``pool`` with the tails of the scratch's pages ``first_page ..
    first_page + len(phys) - 1`` (``scratch_tails`` ``[P, 1, S / BS, K-1,
    D]``) written at the physical blocks ``phys`` ``[n]``, every convolution
    layer's in one scatter (the trash block may stand several times in
    ``phys``: whichever page lands there is never read)."""
    pages = jax.lax.dynamic_slice_in_dim(scratch_tails[:, 0], first_page,
                                         phys.shape[0], axis=1)
    return dict(pool, **{BLOCK_TAIL: pool[BLOCK_TAIL].at[:, phys].set(pages)})


def block_tail_read(plane, block):
    """Every convolution layer's tail as of the last row of physical block
    ``block`` of a pool's ``BLOCK_TAIL`` ``plane``, as one lane of state
    ``[P, 1, K-1, D]``: what a sequence admitted behind that page starts
    from."""
    return jax.lax.dynamic_index_in_dim(plane, block, axis=1)
