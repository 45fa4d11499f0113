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
    # a chip's share of an expert layer (off: the layer holds every expert
    # it routes over). ``n_routed`` experts are ROUTED over — the router's
    # width — of which this layer HOLDS ``n_experts``, the global ids
    # ``held_first .. held_first + n_experts - 1``; it computes the held
    # experts' terms of the sum, with gates normalised over all ``top_k``
    # chosen. 0 = ``n_experts``
    n_routed: int = 0
    held_first: int = 0
    # one shared expert of this width beside the routed ones; 0 = none
    shared_dim: int = 0
    # the gate form: "softmax" scores renormalised over the chosen (the
    # default), or "sigmoid" scores with, each on its own: a learned bias
    # that enters the CHOICE only, group-limited choice (``n_groups`` groups
    # scored by the sum of their top 2, the best ``top_groups`` kept), gates
    # renormalised over the chosen, and a scale
    score: str = "softmax"
    select_bias: bool = False
    n_groups: int = 0
    top_groups: int = 0
    renormalise: bool = True
    gate_scale: float = 1.0
    # the experts' form: the gated three matrices ``(act(x W_gate) * x W_up)
    # W_down`` (the default), or two, ``act(x W_up) W_down`` — no ``w_gate``
    # in the tree, the shared expert of the same form. ``act`` may then be
    # "relu2", ``relu(.) ** 2``
    gated: bool = True
    # routed experts that work in a LATENT of this many numbers (0 = at
    # ``dim``): their stacks are ``[E, latent_dim, hidden]`` / ``[E, hidden,
    # latent_dim]``, and two matrices shared by all experts, ``w_latent_in``
    # ``[dim, latent_dim]`` in front of the dispatch and ``w_latent_out``
    # behind the combine, lead into the latent and out of it. The router
    # and the shared expert read the full ``dim``
    latent_dim: int = 0

    @property
    def routed(self) -> int:
        return self.n_routed or self.n_experts

    @property
    def expert_dim(self) -> int:
        """The width the routed experts read and write."""
        return self.latent_dim or self.dim

    @property
    def stacks(self) -> tuple:
        """The names of an expert's matrices in the tree."""
        return ("w_gate", "w_up", "w_down") if self.gated \
            else ("w_up", "w_down")

    @property
    def share(self) -> bool:
        """Whether the layer is TOLD which experts it holds (it may hold
        them all): it then takes the dropless forms alone and returns the
        experts its tokens chose."""
        return self.n_routed > 0


def init_moe_layer(rng: jax.Array, cfg: MoeConfig) -> Params:
    r1, r2, r3, r4 = jax.random.split(rng, 4)
    dt = cfg.dtype
    e, d, h = cfg.n_experts, cfg.dim, cfg.hidden_dim
    de = cfg.expert_dim

    def dense(r, shape, fan):
        scale = (2.0 / sum(fan)) ** 0.5
        return (jax.random.normal(r, shape, jnp.float32) * scale).astype(dt)

    params = {
        "router": dense(r1, (d, cfg.routed), (d, cfg.routed)).astype(
            jnp.float32),
        "w_up": dense(r3, (e, de, h), (de, h)),
        "w_down": dense(r4, (e, h, de), (h, de)),
    }
    if cfg.gated:
        params["w_gate"] = dense(r2, (e, de, h), (de, h))
    if cfg.latent_dim:
        l1, l2 = jax.random.split(jax.random.fold_in(rng, 7))
        params["w_latent_in"] = dense(l1, (d, de), (d, de))
        params["w_latent_out"] = dense(l2, (de, d), (de, d))
    if cfg.select_bias:
        # seeded weights only: large enough to flip some choices
        params["bias"] = jax.random.normal(
            jax.random.fold_in(rng, 5), (cfg.routed,), jnp.float32) * 0.02
    if cfg.shared_dim:
        s1, s2, s3 = jax.random.split(jax.random.fold_in(rng, 6), 3)
        sh = cfg.shared_dim
        params["shared"] = {"w_up": dense(s2, (d, sh), (d, sh)),
                            "w_down": dense(s3, (sh, d), (sh, d))}
        if cfg.gated:
            params["shared"]["w_gate"] = dense(s1, (d, sh), (d, sh))
    return params


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

    specs = {"router": P(),
             **{name: stack(params[name])
                for name in ("w_gate", "w_up", "w_down") if name in params}}
    # a chip's share: the selection bias, the projections into the experts'
    # latent and out of it, and the shared expert replicate
    for name in ("bias", "w_latent_in", "w_latent_out"):
        if name in params:
            specs[name] = P()
    if "shared" in params:
        specs["shared"] = {name: P() for name in params["shared"]}
    return specs


def _top_k_gates(params: Params, xf: jnp.ndarray, cfg: MoeConfig):
    """Routing, f32 for numerics, in the gate form ``cfg`` states: xf [N, d]
    → (scores [N, E], the chosen gates [N, k], their experts' GLOBAL ids).
    Softmax: the gates renormalised to a convex combination."""
    k = cfg.top_k
    if cfg.score == "softmax":
        logits = xf.astype(jnp.float32) @ params["router"]      # [N, E]
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, k)           # [N, k]
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        return probs, gate_vals, gate_idx
    # hundreds of sigmoid scores lie close together: the product in full
    # float32 (the chip's default rounds a float32 operand to bfloat16), so
    # that the choice is the one the stored router makes
    scores = jax.nn.sigmoid(jnp.matmul(
        xf.astype(jnp.float32), params["router"],
        precision=jax.lax.Precision.HIGHEST))                   # [N, E]
    # the bias enters the choice and never a gate
    choice = scores + params["bias"] if cfg.select_bias else scores
    if cfg.n_groups:
        n, e = choice.shape
        per = e // cfg.n_groups
        group_score = jax.lax.top_k(
            choice.reshape(n, cfg.n_groups, per), 2)[0].sum(-1)  # [N, G]
        kept = jax.lax.top_k(group_score, cfg.top_groups)[1]
        keep = jnp.zeros((n, cfg.n_groups), bool).at[
            jnp.arange(n)[:, None], kept].set(True)
        choice = jnp.where(jnp.repeat(keep, per, axis=1), choice, -jnp.inf)
    gate_idx = jax.lax.top_k(choice, k)[1]
    gate_vals = jnp.take_along_axis(scores, gate_idx, axis=1)
    if cfg.renormalise:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
    return scores, gate_vals * cfg.gate_scale, gate_idx


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
        probs, gate_vals, gate_idx = _top_k_gates(params, xf, cfg)

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
        _, gate_vals, gate_idx = _top_k_gates(params, xf, cfg)
        expert = gate_idx.reshape(n * k)            # token-major, slot-minor
        if cfg.share:
            # a chip's share: the held experts' local ids; a pick that fell
            # on an expert held elsewhere has no row here and a gate of 0
            expert = expert - cfg.held_first
            held = (expert >= 0) & (expert < e)
            gate_vals = gate_vals * held.reshape(n, k)
        assign = jax.nn.one_hot(expert, e, dtype=jnp.int32)      # [N*k, E]
        counts = assign.sum(0)
        # a (token, slot)'s rank among its expert's rows: earlier claims
        rank = ((jnp.cumsum(assign, axis=0) - assign) * assign).sum(-1)
        tiles = (counts + tm - 1) // tm
        first_row = (jnp.cumsum(tiles) - tiles) * tm   # an expert's first
        # the layout's rows, back to their tokens (padding rows: token 0,
        # whose product no token reads)
        if cfg.share:
            # past the layout for a pick not held: the scatter drops it,
            # the gather below clamps it onto a row that is selected away
            dest = jnp.where(held, first_row[jnp.clip(expert, 0, e - 1)]
                             + rank, n_rows)
            token = jnp.zeros(n_rows, jnp.int32).at[dest].set(
                jnp.arange(n * k, dtype=jnp.int32) // k, mode="drop")
        else:
            dest = first_row[expert] + rank         # the row that holds it
            token = jnp.zeros(n_rows, jnp.int32).at[dest].set(
                jnp.arange(n * k, dtype=jnp.int32) // k, unique_indices=True)
        rows = xf.astype(cfg.dtype)
    if cfg.latent_dim:
        rows = _into_latent(params, rows)
    with jax.named_scope("moe.route"):
        xs = rows[token]                                         # [R, d]

    with jax.named_scope("moe.experts"):
        ys = (grouped_ffn or ops.grouped_ffn)(
            xs, tiles, *(params[w] for w in cfg.stacks),
            act=cfg.act)                                         # [R, d] f32

    with jax.named_scope("moe.combine"):
        picked = ys[dest.reshape(n, k)]
        if cfg.share:
            # a pick not held gathered a row no tile holds: the kernel
            # never wrote it, and whatever the memory held (NaN as likely as
            # not) times a gate of 0 is not 0 — selected away, not weighted
            picked = jnp.where(held.reshape(n, k, 1), picked, 0.0)
        out = (picked * gate_vals[..., None]).sum(1)
    if cfg.latent_dim:
        out = _out_of_latent(params, out, cfg)
    if cfg.shared_dim:
        out = out + shared_ffn(params["shared"], xf, cfg)
    if cfg.share:
        # a share also says which experts each token chose (global ids)
        return out.reshape(b, t, d).astype(x.dtype), \
            gate_idx.reshape(b, t, k)
    return out.reshape(b, t, d).astype(x.dtype)


def _into_latent(params: Params, rows: jnp.ndarray) -> jnp.ndarray:
    """The rows ``[N, dim]`` as the routed experts read them, ``[N,
    latent_dim]``: one matrix for all experts, in front of the dispatch."""
    with jax.named_scope("moe.latent.in"):
        return rows @ params["w_latent_in"]


def _out_of_latent(params: Params, out: jnp.ndarray, cfg: MoeConfig):
    """The experts' weighted sum ``[N, latent_dim]`` (float32) back at the
    model's width, float32: one matrix for all experts, behind the combine
    (the sum is rounded to the model's type once, as a matmul's operand)."""
    with jax.named_scope("moe.latent.out"):
        return jnp.matmul(out.astype(cfg.dtype), params["w_latent_out"],
                          preferred_element_type=jnp.float32)


def shared_ffn(shared: Params, xf: jnp.ndarray, cfg: MoeConfig):
    """The shared expert: every token, float32 out; gated or not as the
    routed experts are."""
    with jax.named_scope("moe.shared"):
        from ..ops.grouped_ffn import _act
        from ..ops.quant import maybe_matmul
        h = xf.astype(cfg.dtype)
        hidden = _act(maybe_matmul(
            h, shared["w_gate" if cfg.gated else "w_up"]), cfg.act)
        if cfg.gated:
            hidden = hidden * maybe_matmul(h, shared["w_up"])
        return maybe_matmul(hidden, shared["w_down"]).astype(jnp.float32)


def moe_ffn_held(params: Params, x: jnp.ndarray, cfg: MoeConfig, live=None):
    """Dropless top-k at FEW rows (a decode step) over the experts this layer
    holds, a chip's share or all of them (``held_first`` 0): every
    row against each held expert that a LIVE row picked, weighted by the
    row's gate for it (0 for all but its picks). x [B, T, dim] → ([B, T,
    dim], the chosen experts' global ids int32 [B, T, k]). ``live`` bool
    [B, T]: the rows that are real (None: all) — an idle lane's or a padded
    tail's gates are 0 and its picks, which it still returns, put no expert
    on the list.

    ``tpu9.ops.held_ffn`` reads the touched experts' weights once a call and
    no others. Its FLOPs are those of a row a touched expert and token, so
    it is for calls of at most ``SORTED_MIN_TOKENS`` rows, where the
    weights' stream hides them. No capacity: the reference has none."""
    from ..ops import held_ffn as ops
    b, t, d = x.shape
    n, e = b * t, cfg.n_experts
    xf = x.reshape(n, d)
    with jax.named_scope("moe.route"):
        _, gate_vals, gate_idx = _top_k_gates(params, xf, cfg)
        local = gate_idx - cfg.held_first                        # [N, k]
        live = jnp.ones(n, bool) if live is None else live.reshape(n)
        # [N, E]: a live token's gate for each held expert (a pick held
        # elsewhere is no row of the one-hot)
        weight = (jax.nn.one_hot(local, e, dtype=jnp.float32)
                  * (gate_vals * live[:, None])[..., None]).sum(1)
        ids, count = ops.touched_experts(local, live, e)
    rows = xf.astype(cfg.dtype)
    if cfg.latent_dim:
        rows = _into_latent(params, rows)
    with jax.named_scope("moe.experts"):
        out = ops.held_ffn(
            rows, weight, ids, count, *(params[w] for w in cfg.stacks),
            act=cfg.act)
    if cfg.latent_dim:
        out = _out_of_latent(params, out, cfg)
    if cfg.shared_dim:
        out = out + shared_ffn(params["shared"], xf, cfg)
    return out.reshape(b, t, d).astype(x.dtype), \
        gate_idx.reshape(b, t, cfg.top_k)


def share_forms(cfg: MoeConfig, decode_rows: int, prefill_rows) -> dict:
    """Which form an expert layer that is told what it holds takes, in
    words (``/health``'s ``ffn_decode`` / ``ffn_prefill``): by its rows a
    call, as ``transformer._mlp_block`` decides, and by the backend, as the
    dispatchers of ``ops.held_ffn`` and ``ops.grouped_ffn`` do."""
    from ..utils import on_tpu
    ran = "pallas" if on_tpu() else "xla: no TPU backend"
    what = ("gated" if cfg.gated else "ungated") + f" {cfg.act}" \
        + (f", in a latent of {cfg.latent_dim}" if cfg.latent_dim else "")

    def form(rows: int) -> str:
        if rows > SORTED_MIN_TOKENS:
            return f"sorted by expert, grouped_ffn ({what}): {ran}"
        return f"the touched of the held experts, held_ffn ({what}): {ran}"

    return {"decode": form(decode_rows),
            "prefill": "; ".join(f"{rows} rows: {form(rows)}"
                                 for rows in sorted(set(prefill_rows)))}


def _one_devices_bf16_stacks(params: Params, mesh=None) -> bool:
    """Expert stacks that are plain bf16 arrays in one device's memory, which
    the kernels read as they are stored. Int8 entries keep their scaled
    einsum and stacks sharded over a mesh keep the einsums XLA partitions (a
    kernel is not partitioned): both stay with the one-hot form."""
    from ..ops.quant import is_quantized_entry
    stacks = [params[w] for w in ("w_gate", "w_up", "w_down")]
    return ((mesh is None or mesh.size == 1)
            and not any(is_quantized_entry(w) for w in stacks)
            and all(w.dtype == jnp.bfloat16 for w in stacks))


def takes_sorted_form(params: Params, n_tokens: int, mesh=None) -> bool:
    """The shape rule of a wide serving call: more tokens than
    ``SORTED_MIN_TOKENS`` over stacks a kernel can read."""
    return n_tokens > SORTED_MIN_TOKENS \
        and _one_devices_bf16_stacks(params, mesh)


# A decode step takes the touched form where the stacks it may leave unread
# outweigh what the kernel costs a call whatever it reads: its pipeline's
# prologue is ≈ 14 µs (PERF.md §5, ``ling-reason``: 5 x 14 µs a step), the
# stream of 11.5 MB at a v5e's 819 GB/s. Every served expert layer is far
# over it (Ling's share 1.5 GB, Mixtral's 2.8 GB a layer); the tiny models of
# the tests and rehearsals (0.8 MB) stay with the einsums they always ran.
HELD_MIN_STACK_BYTES = 16 * 1024 * 1024


def takes_held_form(params: Params, n_tokens: int, live, mesh=None) -> bool:
    """The shape rule of a decode step: a serving call that says which of
    its rows are LIVE, of at most ``SORTED_MIN_TOKENS`` rows, over stacks a
    kernel can read and of at least ``HELD_MIN_STACK_BYTES``, takes
    :func:`moe_ffn_held` — the experts no live row picked are not read."""
    stacks = [params[w] for w in ("w_gate", "w_up", "w_down")]
    return live is not None and n_tokens <= SORTED_MIN_TOKENS \
        and _one_devices_bf16_stacks(params, mesh) \
        and sum(w.size * w.dtype.itemsize
                for w in stacks) >= HELD_MIN_STACK_BYTES
