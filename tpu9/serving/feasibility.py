"""HBM feasibility math for LLM deployments (VERDICT r03 #8).

Before a deployment schedules real chips, the weights + KV cache + runtime
overhead must provably fit the slice's HBM — the reference relies on CUDA
OOMs at runtime; tpu9 validates at deploy time so config #4 (llama3-70b
on v5e-8, BASELINE.md) is accepted or rejected with arithmetic, not a
crashed container.

Accounting (per chip, tensor-parallel over ``tp`` chips):
- weights: matmul params at 1 B (int8 weight-only) or 2 B (bf16) + scales,
  embeddings always bf16; all divided by tp (row/col-sharded)
- KV cache: ``2 (k,v) × layers × max_batch × max_seq × kv_heads × head_dim
  × 2 B`` divided by tp (head-sharded; n_kv_heads % tp may force
  replication — accounted)
- overhead: XLA workspace / fragmentation reserve (default 10%) + the
  paged engine's batch-1 prefill scratch
"""

from __future__ import annotations

from dataclasses import dataclass

from ..types import TpuSpec, parse_tpu_spec


class InfeasibleDeployment(ValueError):
    """Raised at deploy time when the model + KV cannot fit the slice."""


@dataclass(frozen=True)
class HbmBudget:
    tpu: str
    chips: int
    tp: int
    # weight-only sharding on top of tp (ISSUE 9 planner): weights divide
    # by tp×fsdp, KV/scratch by the tp head shard only — fsdp chips add
    # zero KV capacity, which is why the planner prefers tp when heads
    # allow it
    fsdp: int
    hbm_per_chip_gb: float
    weight_gb_per_chip: float
    kv_gb_per_chip: float
    scratch_gb_per_chip: float
    overhead_frac: float
    # sequences the SAME kv_gb holds relative to bf16 (int8 pool: ~1.94x
    # at head_dim 128). The budget's kv bytes don't shrink under kv_quant
    # (equal-HBM auto sizing); this factor is where the win shows.
    kv_capacity_factor: float = 1.0

    @property
    def required_gb_per_chip(self) -> float:
        raw = (self.weight_gb_per_chip + self.kv_gb_per_chip
               + self.scratch_gb_per_chip)
        return raw * (1.0 + self.overhead_frac)

    @property
    def fits(self) -> bool:
        return self.required_gb_per_chip <= self.hbm_per_chip_gb

    def as_dict(self) -> dict:
        return {
            "tpu": self.tpu, "chips": self.chips, "tp": self.tp,
            "fsdp": self.fsdp,
            "hbm_per_chip_gb": round(self.hbm_per_chip_gb, 2),
            "weight_gb_per_chip": round(self.weight_gb_per_chip, 3),
            "kv_gb_per_chip": round(self.kv_gb_per_chip, 3),
            "scratch_gb_per_chip": round(self.scratch_gb_per_chip, 3),
            "overhead_frac": self.overhead_frac,
            "kv_capacity_factor": round(self.kv_capacity_factor, 3),
            "required_gb_per_chip": round(self.required_gb_per_chip, 3),
            "fits": self.fits,
        }


def matmul_param_count(cfg) -> int:
    """Per-model matmul parameters (the int8-quantizable set)."""
    per_layer = (cfg.dim * cfg.n_heads * cfg.head_dim
                 + 2 * cfg.dim * cfg.n_kv_heads * cfg.head_dim
                 + cfg.n_heads * cfg.head_dim * cfg.dim)
    if getattr(cfg, "n_experts", 0):
        per_layer += 3 * cfg.dim * cfg.hidden_dim * cfg.n_experts
        per_layer += cfg.dim * cfg.n_experts          # router
    else:
        per_layer += 3 * cfg.dim * cfg.hidden_dim
    total = per_layer * cfg.n_layers
    if not getattr(cfg, "tie_embeddings", False):
        total += cfg.dim * cfg.vocab_size             # lm_head
    return total


def weight_bytes(cfg, quantized: bool) -> int:
    """EXACT bytes of the preset's served param tree, priced on abstract
    shapes: ``jax.eval_shape`` over the same init fns ``build_params``
    uses, summed by ``ops.quant.quantized_bytes``. One source of truth —
    the HBM gate, the ``.tpu9w`` shard sizes a checkpoint emits, and the
    warm-pool ``weight_pool_mb`` sizing can no longer disagree about a
    quantized tree (the old hand-rolled estimate budgeted MoE experts at
    bf16 because per-expert int8 didn't exist; now it does, and this
    derivation tracks whatever the quantizer actually emits)."""
    import jax
    if quantized:
        from ..ops.quant import init_quantized_decoder as init
    else:
        from ..models import init_decoder as init
    from ..ops.quant import quantized_bytes
    spec = jax.eval_shape(lambda rng: init(rng, cfg), jax.random.PRNGKey(0))
    return quantized_bytes(spec)


def kv_cache_bytes(cfg, max_batch: int, max_seq: int,
                   kv_quant: bool = False) -> int:
    """Dense-equivalent KV bytes: ``max_batch`` sequences of ``max_seq``
    tokens, priced by the SAME sum over the pool's planes that the engine's
    pool sizing divides by (``models.kvstate.block_bytes`` — one arithmetic,
    no drift when modes are added) — per cache ENTRY such a sequence can
    address (``kv_entries_peak``: a token, for plain attention)."""
    from ..models import kvstate
    return max_batch * kvstate.block_bytes(
        cfg, cfg.kv_entries_peak(max_seq), kv_quant)


def lane_state_bytes(cfg, max_batch: int) -> int:
    """Bytes of the state that layers of linear attention keep for
    ``max_batch`` running sequences, beside the cache and whatever their
    length (``models.kvstate.lane_shapes``: 0 for a decoder without such
    layers). Never sharded: a mesh is refused with it."""
    from ..models import kvstate
    return kvstate.lane_bytes(cfg, max_batch)


def hbm_budget(preset: str, tpu: "str | TpuSpec", *, max_batch: int = 8,
               max_seq_len: int = 2048, tp: int = 0, fsdp: int = 1,
               overhead_frac: float = 0.10,
               quantize: "str | None" = None,
               kv_quant: bool = False, kv_pool_blocks: int = 0,
               kv_block_size: int = 0) -> HbmBudget:
    """Compute the per-chip HBM budget for serving ``preset`` on ``tpu``
    with tensor parallelism ``tp`` (default: all chips of the slice) and
    optional weight-only ``fsdp`` sharding on top (ISSUE 9 topology
    planner: weights divide by tp×fsdp; KV divides by the tp head shard
    only). ``quantize="int8"`` prices a PLAIN preset name as int8 weights
    — the same opt-in surface ``load_engine(quantize=)``/TPU9_QUANTIZE
    uses, so a knob-opted deployment is not mispriced as bf16.
    ``kv_pool_blocks`` of ``kv_block_size`` tokens price a PINNED paged
    pool (``EngineConfig.kv_pool_blocks``, plus its trash block) instead
    of the dense-parity one — what a model whose KV state is many planes
    deep deploys with: the pool's reservation, not ``max_batch``, then
    bounds the batch."""
    from .presets import resolve_preset
    cfg, quantized = resolve_preset(preset, quantize)
    spec = parse_tpu_spec(tpu) if isinstance(tpu, str) else tpu
    if spec is None:
        raise ValueError("feasibility needs a TPU spec")
    tp = tp or spec.chips

    w = weight_bytes(cfg, quantized) / (tp * max(fsdp, 1))
    # KV is head-sharded; the EVEN shard is gcd(tp, kv_heads) — min()
    # would assume a tp=6 mesh splits 8 heads 6 ways and under-count
    # per-chip KV 3x, approving deploys that OOM at runtime
    import math
    kv_shard = math.gcd(tp, cfg.n_kv_heads)
    # kv_quant does NOT shrink the budget: the engine's auto pool sizing
    # (kv_pool_blocks=0) deliberately spends the SAME HBM as the bf16
    # pool on ~2x the blocks — the win is capacity, not bytes. Pricing
    # the int8 byte count here would under-count the pool the engine
    # actually allocates ~2x and approve deploys that OOM at engine
    # construction. Deployments that pin kv_pool_blocks explicitly can
    # price themselves with kv_cache_bytes(kv_quant=True) directly.
    if kv_pool_blocks:
        if not kv_block_size:
            raise ValueError("a pinned kv_pool_blocks is priced by its "
                             "kv_block_size: give both")
        from ..models import kvstate
        kv = (kv_pool_blocks + 1) \
            * kvstate.block_bytes(cfg, kv_block_size, kv_quant) / kv_shard
    else:
        kv = kv_cache_bytes(cfg, max_batch, max_seq_len) / kv_shard
    # paged engine's batch-1 dense prefill scratch rides on one chip's
    # shard of the kv lanes (always model-dtype — the int8 pool
    # quantizes at splice, the scratch itself stays bf16)
    scratch = kv_cache_bytes(cfg, 1, max_seq_len) / kv_shard
    # state a lane (linear-attention layers): every lane's, and the
    # scratch's one lane
    kv += lane_state_bytes(cfg, max_batch)
    scratch += lane_state_bytes(cfg, 1)
    if kv_block_size:
        # state a BLOCK (short convolutions' tails, one a page): the pool's
        # pages — pinned, or dense parity — and the scratch's
        from ..models import kvstate
        reach = -(-cfg.kv_entries_peak(max_seq_len) // kv_block_size)
        kv += kvstate.block_tail_bytes(
            cfg, kv_pool_blocks + 1 if kv_pool_blocks
            else max_batch * reach + 1)
        scratch += kvstate.block_tail_bytes(cfg, reach)

    return HbmBudget(
        tpu=spec.name, chips=spec.chips, tp=tp, fsdp=max(fsdp, 1),
        hbm_per_chip_gb=float(spec.hbm_gb_per_chip),
        weight_gb_per_chip=w / 1e9,
        kv_gb_per_chip=kv / 1e9,
        scratch_gb_per_chip=scratch / 1e9,
        overhead_frac=overhead_frac,
        kv_capacity_factor=(
            kv_cache_bytes(cfg, max_batch, max_seq_len)
            / kv_cache_bytes(cfg, max_batch, max_seq_len, kv_quant=True)
            if kv_quant else 1.0))


def validate_llm_deployment(preset: str, tpu: "str | TpuSpec", *,
                            max_batch: int = 8, max_seq_len: int = 2048,
                            tp: int = 0, quantize: "str | None" = None,
                            kv_quant: bool = False, kv_pool_blocks: int = 0,
                            kv_block_size: int = 0) -> HbmBudget:
    """Deploy-time gate: raises :class:`InfeasibleDeployment` with the
    arithmetic when the configuration cannot fit; returns the budget when
    it can. Suggests the standard remedies in the message. ``quantize``/
    ``kv_quant`` mirror the ``load_engine`` opt-ins so knob-opted int8
    deployments are priced as what they serve."""
    budget = hbm_budget(preset, tpu, max_batch=max_batch,
                        max_seq_len=max_seq_len, tp=tp,
                        quantize=quantize, kv_quant=kv_quant,
                        kv_pool_blocks=kv_pool_blocks,
                        kv_block_size=kv_block_size)
    if not budget.fits:
        d = budget.as_dict()
        raise InfeasibleDeployment(
            f"{preset} on {d['tpu']} (tp={d['tp']}) needs "
            f"{d['required_gb_per_chip']} GB/chip "
            f"(weights {d['weight_gb_per_chip']} + KV {d['kv_gb_per_chip']}"
            f" + scratch {d['scratch_gb_per_chip']} + "
            f"{int(budget.overhead_frac * 100)}% overhead) but the chip "
            f"has {d['hbm_per_chip_gb']} GB. Remedies: int8 weights "
            f"(-50% weight bytes), smaller max_batch/max_seq_len (KV "
            f"scales linearly), a pinned kv_pool_blocks, or a larger "
            f"slice.")
    return budget
