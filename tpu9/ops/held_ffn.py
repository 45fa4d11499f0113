"""Held expert FFN: every row against each TOUCHED expert of a chip's share.

A decode step's expert layer (``tpu9.models.moe.moe_ffn_held``) has few rows
— a row a lane — and holds many experts, of which its live rows' picks reach
some: ``out = sum over touched e of (act(x @ w_gate[e]) * (x @ w_up[e]) *
weight[:, e]) @ w_down[e]``, ``weight[n, e]`` the gate of row ``n`` for held
expert ``e`` (0 for all but its picks). An expert no live row picked adds
nothing whatever its weights are, so its weights need not cross HBM. Experts
of TWO matrices (``stacks`` = ``(w_up, w_down)``: ``act(x @ w_up[e]) *
weight[:, e]) @ w_down[e]``, ungated) go through the same kernel and the same
oracle without the gate's operand.

How the kernel reads only the touched experts: the grid is ``(slot, hidden
tile)``, one slot a held expert; the touched experts' ids, ascending (the
reads go forward through the stacks) and padded with the last of them, and
their count are scalar-prefetch operands that the weights' index maps read.
A slot past the count asks for the block the slot before it held — no fetch —
and its body is under ``pl.when``: the rule of ``grouped_ffn._segments``. A
step's blocks are an expert's whole ``w_gate[e]``, ``w_up[e]`` and
``w_down[e]`` as they are stored (contiguous; ``hidden`` is tiled only where
three whole matrices, twice, would not fit ``STEP_BYTES``). The rows, their
gates and the float32 sum stay in VMEM for the whole call: the sum is zeroed
at the first step and written once after the last, so a call whose rows
touch no held expert returns zeros. Besides the touched weights the call
moves its rows in once and their sum out once.

Off the TPU the dispatcher takes the batched einsums over EVERY held expert
(the XLA oracle: an untouched expert's term is a product with gates of 0);
tests run the kernel with ``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import on_tpu
from .grouped_ffn import (_VMEM_LIMIT, _act, _gated, _hidden_tile,
                          _into_hidden)

# the weight blocks of a step, double-buffered: an expert's three matrices
# whole while they fit (Ling: 3 x 2560 x 768 bf16 = 11.8 MB, 23.6 MB twice)
STEP_BYTES = 48 * 1024 * 1024


def touched_experts(local, live, n_experts: int):
    """The held experts at least one LIVE row picked. ``local`` int32
    [N, k]: every row's picks as held experts' local ids (a pick held
    elsewhere is outside ``[0, n_experts)``), ``live`` bool [N]. Returns
    (ids int32 [n_experts], count int32 [1]): the touched ids ascending in
    the first ``count`` slots, the last of them in the rest (0 where none is
    touched)."""
    hit = jax.nn.one_hot(local, n_experts, dtype=jnp.bool_) \
        & live[:, None, None]
    touched = hit.any((0, 1))
    ends = jnp.cumsum(touched.astype(jnp.int32))
    slot = jnp.arange(n_experts, dtype=jnp.int32)
    # slot s holds the (s + 1)-th touched expert: as many experts as end
    # with at most s touched before or at them
    ids = jnp.searchsorted(ends, jnp.minimum(slot, ends[-1] - 1),
                           side="right", method="compare_all")
    return (jnp.clip(ids, 0, n_experts - 1).astype(jnp.int32),
            ends[-1:])


def _step_tile(d: int, hidden: int, itemsize: int, matrices: int = 3) -> int:
    """Hidden columns of a step: all of them where an expert's ``matrices``
    matrices fit ``STEP_BYTES`` twice, else ``grouped_ffn``'s tile."""
    if 2 * matrices * d * hidden * itemsize <= STEP_BYTES:
        return hidden
    return _hidden_tile(hidden)


def _kernel(ids_ref, count_ref, x_ref, gates_ref, *refs, act: str):
    # (the one or two matrices into the hidden width, then the one out)
    *w_in, wd_ref, out_ref = refs
    s, j = pl.program_id(0), pl.program_id(1)

    @pl.when((s == 0) & (j == 0))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(s < count_ref[0])
    def _expert():
        x = x_ref[...]
        into = _into_hidden(x, w_in)
        # the rows' gates for this expert: its column of [N, E], picked by a
        # mask (a lane cannot be sliced at a traced index)
        gates = gates_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, gates.shape, 1)
        column = jnp.sum(jnp.where(lane == ids_ref[s], gates, 0.0), axis=1,
                         keepdims=True)
        h = (_gated(into, act) * column).astype(x.dtype)
        out_ref[...] += jnp.dot(h, wd_ref[...],
                                preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def held_ffn_kernel(x, weight, ids, count, *stacks, act: str = "silu",
                    interpret: bool = False):
    """x [N, d]; weight float32 [N, E]; ``ids`` int32 [E] and ``count``
    int32 [1] as :func:`touched_experts` gives them; ``stacks`` the experts'
    ``(w_gate, w_up, w_down)`` or ungated ``(w_up, w_down)``, [E, d, h] into
    the hidden width and [E, h, d] out of it, in ``x``'s type. Returns
    float32 [N, d]."""
    n, d = x.shape
    *w_in, w_down = stacks
    n_experts, hidden = w_down.shape[:2]
    th = _step_tile(d, hidden, w_down.dtype.itemsize, len(stacks))
    last = hidden // th - 1

    def rows(s, j, ids, count):
        return 0, 0

    def gate_block(s, j, ids, count):
        # a slot past the last touched expert holds the block it was left
        return ids[s], 0, jnp.where(s < count[0], j, last)

    def down_block(s, j, ids, count):
        return ids[s], jnp.where(s < count[0], j, last), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_experts, last + 1),
        in_specs=[
            pl.BlockSpec((n, d), rows),
            pl.BlockSpec((n, n_experts), rows),
            *[pl.BlockSpec((None, d, th), gate_block) for _ in w_in],
            pl.BlockSpec((None, th, d), down_block),
        ],
        out_specs=pl.BlockSpec((n, d), rows),
    )
    weight_bytes = sum(w.size * w.dtype.itemsize for w in stacks)
    return pl.pallas_call(
        functools.partial(_kernel, act=act),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        # every held expert touched, the upper bound: the count is a traced
        # value, and a step reads count / n_experts of these weights
        cost_estimate=pl.CostEstimate(
            flops=2 * len(stacks) * n * d * hidden * n_experts,
            transcendentals=n * hidden * n_experts,
            bytes_accessed=weight_bytes + n * d * (x.dtype.itemsize + 4)),
        name="held_ffn",
        interpret=interpret,
    )(ids, count, x, weight, *stacks)


def held_ffn_xla(x, weight, ids, count, *stacks, act: str = "silu"):
    """Every held expert over every row, the untouched ones too (their gates
    are 0): the oracle the kernel is held to, and what a backend without the
    kernel runs."""
    del ids, count
    *w_in, w_down = stacks
    n_experts = w_down.shape[0]
    # the rows as a BATCHED operand, one copy an expert: a product a held
    # expert over the stacks as they are stored. Without the batch dimension
    # the compiler takes ONE product over all experts' columns, and
    # transposes both stacks a call to get it
    h = jnp.broadcast_to(x, (n_experts, *x.shape))
    hidden = _act(jnp.einsum("end,edh->enh", h, w_in[0]), act)
    if len(w_in) == 2:
        hidden = hidden * jnp.einsum("end,edh->enh", h, w_in[1])
    # the gate weights the hidden rows, so that the down projection sums
    # over experts and hidden at once: no [E, N, d] product
    hidden = hidden.astype(jnp.float32) * weight.T[..., None]
    return jnp.einsum("enh,ehd->nd", hidden.astype(x.dtype), w_down,
                      preferred_element_type=jnp.float32)


def held_ffn(x, weight, ids, count, *stacks, act: str = "silu"):
    if on_tpu():
        return held_ffn_kernel(x, weight, ids, count, *stacks, act=act)
    return held_ffn_xla(x, weight, ids, count, *stacks, act=act)
