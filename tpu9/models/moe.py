"""Sparse mixture-of-experts FFN with expert parallelism.

Reference has no in-framework MoE (SURVEY.md §2.10 — parallelism is
delegated to user containers); this module is part of tpu9's TPU-first
compute layer alongside TP/FSDP/ring attention.

TPU-first design (GShard/Switch dispatch, not scatter/gather): routing
builds a dense one-hot dispatch tensor ``[tokens, experts, capacity]`` and
all data movement is einsums — which XLA lowers to all-to-alls when the
expert dimension is sharded over the ``ep`` mesh axis, keeping every
FLOP on the MXU and every transfer on ICI. No dynamic shapes, no host
control flow: over-capacity tokens are dropped (their residual stream
passes through untouched), exactly the standard capacity-factor contract.

Params layout: every expert tensor has a leading ``n_experts`` dim sharded
``P("ep")`` — one ``ep`` shard holds ``n_experts / ep`` full experts.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Params = Any


@dataclass(frozen=True)
class MoeConfig:
    dim: int = 512
    hidden_dim: int = 1024
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    act: str = "silu"
    dtype: Any = jnp.bfloat16


def init_moe_layer(rng: jax.Array, cfg: MoeConfig) -> Params:
    r1, r2, r3, r4 = jax.random.split(rng, 4)
    dt = cfg.dtype
    e, d, h = cfg.n_experts, cfg.dim, cfg.hidden_dim

    def dense(r, shape, fan):
        scale = (2.0 / sum(fan)) ** 0.5
        return (jax.random.normal(r, shape, jnp.float32) * scale).astype(dt)

    return {
        "router": dense(r1, (d, e), (d, e)).astype(jnp.float32),
        "w_gate": dense(r2, (e, d, h), (d, h)),
        "w_up": dense(r3, (e, d, h), (d, h)),
        "w_down": dense(r4, (e, h, d), (h, d)),
    }


def moe_param_specs(params: Params, axis: str = "ep") -> Params:
    """Sharding: router replicated, expert stacks sharded over ``axis``
    (the expert-parallel axis by default; decoder_param_specs passes tp
    for mixtral layers on plain serving meshes). Per-expert int8 entries
    (``{q: [E,in,out], scale: [E,1,out]}`` — tpu9.ops.quant) shard both
    planes along the expert axis, mirroring sharding._quant_aware for
    the dense 2-D weights."""

    def stack(leaf):
        from ..ops.quant import is_quantized_entry
        spec = P(axis, None, None)
        if is_quantized_entry(leaf):
            return {"q": spec, "scale": spec}
        return spec

    return {
        "router": P(),
        "w_gate": stack(params["w_gate"]),
        "w_up": stack(params["w_up"]),
        "w_down": stack(params["w_down"]),
    }


def _top_k_gates(params: Params, xf: jnp.ndarray, k: int):
    """Routing, f32 for numerics: xf [N, d] → (probs [N, E], the chosen
    gates [N, k] renormalised to a convex combination, their experts)."""
    logits = xf.astype(jnp.float32) @ params["router"]          # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)               # [N, k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    return probs, gate_vals, gate_idx


def _capacity(n_tokens: int, cfg: MoeConfig) -> int:
    cap = int(cfg.top_k * n_tokens * cfg.capacity_factor / cfg.n_experts)
    # capacity must be static, positive, and lane-friendly
    return max(8, -(-cap // 8) * 8)


def moe_ffn(params: Params, x: jnp.ndarray, cfg: MoeConfig,
            ep_sharded: bool = True):
    """x: [B, T, dim] → ([B, T, dim], aux) where aux carries the
    load-balancing loss (Switch §2.2: E * Σ_e f_e·p_e) and router stats.

    Dropped tokens (over expert capacity) contribute zero here — callers
    add the residual stream, so they pass through unchanged.
    """
    b, t, d = x.shape
    n = b * t
    e, k = cfg.n_experts, cfg.top_k
    c = _capacity(n, cfg)
    xf = x.reshape(n, d)

    # -- routing (f32 for numerics) ------------------------------------------
    with jax.named_scope("moe.route"):
        probs, gate_vals, gate_idx = _top_k_gates(params, xf, k)

        # one-hot expert assignment per (token, slot): [N, k, E]
        assign = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)

        # position of each (token, slot) within its expert's buffer:
        # running count of earlier claims on the same expert (token-major,
        # slot-minor priority — earlier tokens win capacity, the GShard
        # convention)
        flat = assign.reshape(n * k, e)
        pos = jnp.cumsum(flat, axis=0) - flat                    # [N*k, E]
        pos = (pos * flat).sum(-1).reshape(n, k).astype(jnp.int32)  # [N, k]
        in_cap = (pos < c).astype(jnp.float32)

        # dispatch [N, E, C]: 1 where token n goes to expert e at slot c
        slot_oh = jax.nn.one_hot(pos, c, dtype=jnp.float32)      # [N, k, C]
        dispatch = jnp.einsum("nke,nkc->nec", assign,
                              slot_oh * in_cap[..., None])
        combine = jnp.einsum("nke,nkc,nk->nec", assign,
                             slot_oh * in_cap[..., None], gate_vals)

    # -- expert compute (leading E dim sharded over ep) ----------------------
    with jax.named_scope("moe.experts"):
        xe = jnp.einsum("nec,nd->ecd", dispatch.astype(cfg.dtype),
                        xf.astype(cfg.dtype))                    # [E, C, d]
        if ep_sharded:
            xe = jax.lax.with_sharding_constraint(xe, P("ep", None, None))
        # maybe_einsum: expert stacks may be per-expert int8 entries
        # (tpu9.ops.quant.quantize_weight_stacked) — the int8 operand
        # stays int8 in HBM, scales [E, 1, out] apply on the einsum output
        from ..ops.quant import maybe_einsum
        h = maybe_einsum("ecd,edh->ech", xe, params["w_gate"])
        if cfg.act == "silu":
            h = jax.nn.silu(h)
        else:
            h = jax.nn.gelu(h, approximate=True)
        h = h * maybe_einsum("ecd,edh->ech", xe, params["w_up"])
        ye = maybe_einsum("ech,ehd->ecd", h, params["w_down"])   # [E, C, d]
        if ep_sharded:
            ye = jax.lax.with_sharding_constraint(ye, P("ep", None, None))

    with jax.named_scope("moe.combine"):
        out = jnp.einsum("nec,ecd->nd", combine.astype(cfg.dtype), ye)

    # -- aux: load-balance loss + stats --------------------------------------
    # fraction of tokens whose TOP-1 lands on e, times mean router prob
    top1 = jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32)
    frac_tokens = top1.mean(0)
    mean_prob = probs.mean(0)
    balance_loss = e * jnp.sum(frac_tokens * mean_prob)
    dropped = 1.0 - in_cap.mean()
    aux = {"balance_loss": balance_loss, "dropped_frac": dropped,
           "expert_load": frac_tokens}
    return out.reshape(b, t, d).astype(x.dtype), aux


# -- serving: dropless, by sorting --------------------------------------------

# A serving call of more than this many tokens takes the sorted form. The
# number is the chip-independent reading of "E*n rows of FLOPs exceed one
# pass over the experts", rounded up to the next chunk multiple: the
# one-hot form multiplies every expert by a capacity of n rows, so its cost
# is E*n rows whatever holds a token, and it sits at its HBM floor (one read
# of the expert stacks) up to peak FLOP/s over peak bytes/s rows a call —
# about 240 for bf16 on a v5e (197e12 / 819e9) — and is compute-bound on
# empty rows above. The programs put 32 (a decode step), 128 (a chunk) or
# 512 (an admission group) rows into a call, so nothing sits near it.
SORTED_MIN_TOKENS = 256


@functools.partial(jax.jit, static_argnames=("cfg", "grouped_ffn"))
def moe_ffn_sorted(params: Params, x: jnp.ndarray, cfg: MoeConfig,
                   grouped_ffn=None) -> jnp.ndarray:
    """Dropless top-k by sorting: x [B, T, dim] → [B, T, dim].

    The N*k (token, slot) assignments are ordered by expert with a counting
    sort — stable, so a token's place inside an expert is its place in the
    call — the rows are gathered into that order, each expert's rows padded
    to whole row tiles, and ``tpu9.ops.grouped_ffn`` multiplies every
    expert's weights by that expert's tiles only. No capacity: every token
    reaches all k of its experts under any routing, and the work is N*k
    rows plus at most a tile an expert, whatever the routing. Routing in
    f32, operands in ``cfg.dtype`` with f32 accumulation, the per-token sum
    in f32: the precision of :func:`moe_ffn`, whose dropless case
    (``capacity_factor = E/k``) this equals. ``grouped_ffn`` overrides the
    backend's choice of grouped matmul (tests: the kernel, interpreted).
    Jitted so that a program traces and lowers it once for all its layers."""
    from ..ops import grouped_ffn as ops
    b, t, d = x.shape
    n, e, k = b * t, cfg.n_experts, cfg.top_k
    tm = ops.ROW_TILE
    # an expert's rows end inside a tile: at most tm - 1 rows of padding each
    n_rows = (n * k + e * (tm - 1)) // tm * tm
    xf = x.reshape(n, d)

    with jax.named_scope("moe.route"):
        _, gate_vals, gate_idx = _top_k_gates(params, xf, k)
        expert = gate_idx.reshape(n * k)            # token-major, slot-minor
        assign = jax.nn.one_hot(expert, e, dtype=jnp.int32)      # [N*k, E]
        counts = assign.sum(0)
        # a (token, slot)'s rank among its expert's rows: earlier claims
        rank = ((jnp.cumsum(assign, axis=0) - assign) * assign).sum(-1)
        tiles = (counts + tm - 1) // tm
        first_row = (jnp.cumsum(tiles) - tiles) * tm   # an expert's first
        dest = first_row[expert] + rank             # the row that holds it
        # the layout's rows, back to their tokens (padding rows: token 0,
        # whose product no token reads)
        token = jnp.zeros(n_rows, jnp.int32).at[dest].set(
            jnp.arange(n * k, dtype=jnp.int32) // k, unique_indices=True)
        xs = xf.astype(cfg.dtype)[token]                         # [R, d]

    with jax.named_scope("moe.experts"):
        ys = (grouped_ffn or ops.grouped_ffn)(
            xs, tiles, params["w_gate"], params["w_up"], params["w_down"],
            act=cfg.act)                                         # [R, d] f32

    with jax.named_scope("moe.combine"):
        out = (ys[dest.reshape(n, k)] * gate_vals[..., None]).sum(1)
    return out.reshape(b, t, d).astype(x.dtype)


def takes_sorted_form(params: Params, n_tokens: int, mesh=None) -> bool:
    """The shape rule of a serving call: more tokens than
    ``SORTED_MIN_TOKENS`` and expert stacks that are plain bf16 arrays in
    one device's memory. Int8 entries keep their scaled einsum and stacks
    sharded over a mesh keep the einsums XLA partitions (a kernel is not
    partitioned): both stay with the one-hot form."""
    from ..ops.quant import is_quantized_entry
    stacks = [params[w] for w in ("w_gate", "w_up", "w_down")]
    return (n_tokens > SORTED_MIN_TOKENS
            and (mesh is None or mesh.size == 1)
            and not any(is_quantized_entry(w) for w in stacks)
            and all(w.dtype == jnp.bfloat16 for w in stacks))
