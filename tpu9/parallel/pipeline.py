"""Pipeline parallelism: GPipe-style microbatch schedule over a ``pp``
mesh axis.

Reference has no in-framework pipeline parallelism (SURVEY.md §2.10); this
is tpu9 compute-layer machinery like ring attention.

TPU-first design: layers are STACKED (leading layer dim) and sharded over
``pp`` so each stage owns a contiguous block of layers; activations move
stage→stage with ``ppermute`` inside one ``shard_map``-ed SPMD program —
no host round-trips, a single compiled schedule of ``M + S - 1`` steps for
``M`` microbatches over ``S`` stages. Everything is ``lax.scan``-based, so
``jax.grad`` flows through (the transpose of ppermute is the reverse
ppermute — backward pipelining falls out of autodiff).

Bubble fraction is the textbook ``(S-1)/(M+S-1)``; pick M >= S.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Params = Any


def stack_layers(layers: list) -> Params:
    """[{w: [..]}, ...] → {w: [L, ..]} — the pp-shardable layout."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def stage_specs(stacked: Params, axis: str = "pp") -> Params:
    """Shard the stacked layer dim over the pipeline axis."""
    return jax.tree.map(
        lambda x: P(axis, *([None] * (x.ndim - 1))), stacked)


def pipeline_forward(block_fn: Callable[[Params, jnp.ndarray], jnp.ndarray],
                     stacked_params: Params, x: jnp.ndarray, mesh: Mesh,
                     axis: str = "pp", n_microbatches: int = 0) -> jnp.ndarray:
    """Run ``block_fn`` over every layer with the layer dim pipelined.

    ``stacked_params``: pytree with leading layer dim L (see
    :func:`stack_layers`), L divisible by the ``pp`` mesh size; sharded or
    shardable as :func:`stage_specs`.
    ``x``: [B, ...] replicated batch; split into ``n_microbatches`` (default
    = pipeline size) along B.

    Returns [B, ...] replicated, differentiable end-to-end.
    """
    s = mesh.shape[axis]
    m = n_microbatches or s
    b = x.shape[0]
    assert b % m == 0, f"batch {b} not divisible into {m} microbatches"
    mb = b // m
    xs = x.reshape(m, mb, *x.shape[1:])

    p_specs = stage_specs(stacked_params, axis)
    x_spec = P(*([None] * xs.ndim))

    @jax.tree_util.Partial
    def local_forward(local_params, act):
        # act [mb, ...] through this stage's layer block
        def body(a, layer):
            return block_fn(layer, a), None
        out, _ = jax.lax.scan(body, act, local_params)
        return out

    def _pipe(local_params, xs_rep):
        stage = jax.lax.axis_index(axis)
        fwd_perm = [(i, (i + 1) % s) for i in range(s)]

        def step(carry, t):
            act, outbuf = carry
            # stage 0 feeds microbatch t (beyond M: recycle 0, masked later)
            inject = xs_rep[jnp.clip(t, 0, m - 1)]
            cur = jnp.where(stage == 0, inject, act)
            y = local_forward(local_params, cur)
            # last stage records its result for microbatch t-(S-1)
            w = t - (s - 1)
            widx = jnp.clip(w, 0, m - 1)
            valid = jnp.logical_and(stage == s - 1,
                                    jnp.logical_and(w >= 0, w < m))
            outbuf = outbuf.at[widx].set(
                jnp.where(valid, y, outbuf[widx]))
            # rotate activations forward one stage
            act_next = jax.lax.ppermute(y, axis, fwd_perm)
            return (act_next, outbuf), None

        act0 = jnp.zeros_like(xs_rep[0])
        out0 = jnp.zeros_like(xs_rep)
        (_, outbuf), _ = jax.lax.scan(step, (act0, out0),
                                      jnp.arange(m + s - 1))
        # only the last stage holds real outputs — replicate across pp
        outbuf = jnp.where(stage == s - 1, outbuf, 0.0)
        return jax.lax.psum(outbuf, axis)

    out = jax.shard_map(
        _pipe, mesh=mesh, in_specs=(p_specs, x_spec), out_specs=x_spec,
        check_vma=False)(stacked_params, xs)
    return out.reshape(b, *x.shape[1:])
