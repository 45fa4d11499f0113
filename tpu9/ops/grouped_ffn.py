"""Grouped expert FFN: every expert's FFN over that expert's rows only.

The serving MoE dispatch (``tpu9.models.moe.moe_ffn_sorted``) lays the
(token, slot) rows of a call out by expert, each expert's rows padded to
whole ``ROW_TILE``-row tiles. This module multiplies them:
``y[rows of e] = (act(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]`` — three
grouped matmuls over the E row groups, fused so that the ``[rows, hidden]``
intermediate never leaves VMEM. Experts of TWO matrices (``stacks`` = ``(w_up,
w_down)``: ``y = act(x @ w_up[e]) @ w_down[e]``, ungated) go through the
same kernel and the same oracle without the gate's operand.

How the kernel gets ONE pass over the expert weights: the grid is
``(segment, hidden tile)``, a segment being up to ``SEGMENT_TILES`` row
tiles of one expert; a step's weight blocks ``w_gate/w_up[e, :, j]`` and
``w_down[e, j, :]`` are fetched once and stay while the segment's row tiles
pass under them in an in-kernel loop whose trip count is a prefetched
scalar. The segment's rows are copied into VMEM once (``j == 0``), its output
accumulates in float32 over the hidden tiles and is written once
(``j == last``): besides the weights the kernel moves each row in and out
once. An expert without rows has no segment and costs nothing; one with
more rows than a segment holds streams its weights once per segment, and is
compute-bound by then (four tiles of FLOPs a step take twice its fetch). Work
is the tiles that hold rows, and VMEM is the same, whatever the routing and
however many tokens the call has.

Off the TPU the dispatcher takes ``jax.lax.ragged_dot`` over the same
layout (the XLA oracle, as ``tpu9.ops.attention`` does); tests run the
kernel with ``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import on_tpu

# rows of a tile: the MXU's edge on a v5e — a smaller tile re-loads the
# systolic array's weights for fewer rows, a larger one pads more (an
# expert's last tile is half empty on average)
ROW_TILE = 128
# hidden columns of a step: 3 x [dim, 512] bf16 blocks of a 4096-wide model
# are 12 MB a step, double-buffered 24 MB of the chip's 128 MiB of VMEM
HIDDEN_TILE = 512
# row tiles of a segment: its rows (bf16) and their float32 accumulator stay
# in VMEM, 4 + 8 MB at 4096 wide; an admission group's expert (at most 512
# rows) is one segment
SEGMENT_TILES = 4
_VMEM_LIMIT = 100 * 1024 * 1024


def _act(x, act: str):
    if act == "silu":
        return jax.nn.silu(x)
    if act == "relu2":
        return jnp.square(jax.nn.relu(x))
    return jax.nn.gelu(x, approximate=True)


# the weight blocks of a step, double-buffered, where a hidden width that
# neither 512 nor 256 divides is taken WHOLE (2,688 = 21 x 128 in a latent
# of 1,024: two matrices of 5.5 MB, 22 MB twice, where tiles of 128 columns
# would be 21 steps of 256-byte rows)
WHOLE_BYTES = 48 * 1024 * 1024


def _hidden_tile(hidden: int, block_bytes: int = 0) -> int:
    """Hidden columns of a step. ``block_bytes``: what a step's weight
    blocks take at the whole hidden width."""
    for tile in (HIDDEN_TILE, 256):
        if hidden % tile == 0:
            return tile
    if 0 < 2 * block_bytes <= WHOLE_BYTES:
        return hidden
    return 128 if hidden % 128 == 0 else hidden


def _into_hidden(x, w_in) -> list:
    """``x`` times each of the one or two matrices (or blocks of them) that
    lead into the hidden width, float32."""
    return [jnp.dot(x, w[...], preferred_element_type=jnp.float32)
            for w in w_in]


def _gated(into: list, act: str):
    """``act(x @ w_gate) * (x @ w_up)`` of :func:`_into_hidden`'s two
    products, or ungated ``act(x @ w_up)`` of its one."""
    hidden = _act(into[0], act)
    return hidden if len(into) == 1 else hidden * into[1]


def _kernel(expert_ref, first_ref, count_ref, x_hbm, *refs, act: str):
    # (the one or two matrices into the hidden width, then the one out)
    *w_in, wd_ref, out_hbm, x_rows, acc, sem = refs
    del expert_ref                      # read by the weights' index maps
    g, j = pl.program_id(0), pl.program_id(1)
    first, count = first_ref[g], count_ref[g]

    def copy_tiles(make):
        # start every tile's copy, then wait for each: one semaphore
        jax.lax.fori_loop(0, count, lambda t, _: make(t).start(), None)
        jax.lax.fori_loop(0, count, lambda t, _: make(t).wait(), None)

    def rows_of(tile):
        return pl.ds(pl.multiple_of(tile * ROW_TILE, ROW_TILE), ROW_TILE)

    @pl.when(j == 0)
    def _load_rows():
        copy_tiles(lambda t: pltpu.make_async_copy(
            x_hbm.at[rows_of(first + t)], x_rows.at[rows_of(t)], sem))

    def one_tile(t, _):
        rows = rows_of(t)
        x = x_rows[rows, :]
        h = _gated(_into_hidden(x, w_in), act).astype(x.dtype)
        y = jnp.dot(h, wd_ref[...], preferred_element_type=jnp.float32)

        @pl.when(j == 0)
        def _first():
            acc[rows, :] = y

        @pl.when(j > 0)
        def _rest():
            acc[rows, :] += y

    jax.lax.fori_loop(0, count, one_tile, None)

    @pl.when(j == pl.num_programs(1) - 1)
    def _store_rows():
        copy_tiles(lambda t: pltpu.make_async_copy(
            acc.at[rows_of(t)], out_hbm.at[rows_of(first + t)], sem))


def _segments(tiles, n_segments: int):
    """Cut every expert's run of tiles into segments of at most
    ``SEGMENT_TILES``: (expert, first tile, tile count) of each of
    ``n_segments`` grid rows, int32. Rows past the last segment repeat its
    expert with a count of 0, so they fetch and compute nothing."""
    per_expert = -(-tiles // SEGMENT_TILES)
    ends = jnp.cumsum(per_expert)
    g = jnp.arange(n_segments, dtype=jnp.int32)
    used = g < ends[-1]
    expert = jnp.searchsorted(ends, jnp.minimum(g, ends[-1] - 1),
                              side="right").astype(jnp.int32)
    expert = jnp.minimum(expert, tiles.shape[0] - 1)
    done = (g - (ends - per_expert)[expert]) * SEGMENT_TILES
    first = (jnp.cumsum(tiles) - tiles)[expert] + done
    count = jnp.where(used, jnp.clip(tiles[expert] - done, 0, SEGMENT_TILES),
                      0)
    return expert, first.astype(jnp.int32), count.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def grouped_ffn_kernel(xs, tiles, *stacks, act: str = "silu",
                       interpret: bool = False):
    """xs [R, d]: rows grouped by expert, expert ``e`` owning ``tiles[e]``
    whole ``ROW_TILE``-row tiles, in order, from row 0 (R a multiple of
    ``ROW_TILE``; ``tiles`` int32 [E]); ``stacks`` the experts' ``(w_gate,
    w_up, w_down)`` or ungated ``(w_up, w_down)``, ``[E, d, hidden]`` into
    the hidden width and ``[E, hidden, d]`` out of it. Returns float32 [R,
    d]; rows of tiles past ``tiles.sum()`` are not written."""
    r, d = xs.shape
    *w_in, w_down = stacks
    n_experts, _, hidden = w_down.shape[0], d, w_down.shape[1]
    # (the gated three keep the tiles they were measured with)
    th = _hidden_tile(hidden) if len(w_in) == 2 else _hidden_tile(
        hidden, len(stacks) * d * hidden * w_down.dtype.itemsize)
    last = hidden // th - 1
    # every expert's last segment may be short: at most one more each
    n_segments = r // ROW_TILE // SEGMENT_TILES + n_experts

    def gate_block(g, j, expert, first, count):
        # a row past the last segment holds the block it was left with
        return expert[g], 0, jnp.where(count[g] > 0, j, last)

    def down_block(g, j, expert, first, count):
        return expert[g], jnp.where(count[g] > 0, j, last), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_segments, last + 1),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            *[pl.BlockSpec((None, d, th), gate_block) for _ in w_in],
            pl.BlockSpec((None, th, d), down_block),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((SEGMENT_TILES * ROW_TILE, d), xs.dtype),
            pltpu.VMEM((SEGMENT_TILES * ROW_TILE, d), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    weight_bytes = sum(w.size * w.dtype.itemsize for w in stacks)
    return pl.pallas_call(
        functools.partial(_kernel, act=act),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * len(stacks) * r * d * hidden,
            transcendentals=r * hidden,
            bytes_accessed=weight_bytes + r * d * (xs.dtype.itemsize + 4)),
        name="grouped_ffn",
        interpret=interpret,
    )(*_segments(tiles.astype(jnp.int32), n_segments), xs, *stacks)


def grouped_ffn_xla(xs, tiles, *stacks, act: str = "silu"):
    """The same layout through ``jax.lax.ragged_dot``: the oracle the kernel
    is held to, and what a backend without the kernel runs."""
    dot = functools.partial(jax.lax.ragged_dot,
                            group_sizes=tiles.astype(jnp.int32) * ROW_TILE,
                            preferred_element_type=jnp.float32)
    *w_in, w_down = stacks
    h = _act(dot(xs, w_in[0]), act)
    if len(w_in) == 2:
        h = h * dot(xs, w_in[1])
    return dot(h.astype(xs.dtype), w_down)


def grouped_ffn(xs, tiles, *stacks, act: str = "silu"):
    if on_tpu():
        return grouped_ffn_kernel(xs, tiles, *stacks, act=act)
    return grouped_ffn_xla(xs, tiles, *stacks, act=act)
