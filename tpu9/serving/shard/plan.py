"""Topology planner: pick a tp / tp×fsdp submesh shape per preset (ISSUE 9).

The MULTICHIP probes proved the mechanisms (tp=2 serving forward, paged KV
under tp, dp×fsdp×tp meshes); this module decides the SHAPE. Planning is
pure host arithmetic over ``feasibility.py``'s exact HBM pricing — weights
(quantization-aware, via ``jax.eval_shape`` over the real init fns) + KV
pool + scratch + headroom per chip — so a deployment either provably fits
its submesh or is rejected with numbers, never an OOM at bind time.

Rules:
- candidate chip counts are powers of two up to the slice size (ICI meshes
  come in powers of two; a 3-chip submesh has no layout);
- ``tp`` takes as many chips as divide ``n_kv_heads`` exactly — the paged
  KV pool shards on the head axis and a non-dividing tp would replicate KV
  (all the HBM cost, none of the capacity win); excess chips go to
  ``fsdp``, which shards weights only;
- the SMALLEST chip count that fits wins: serving economics is tokens/sec
  per chip, and spreading a model that fits N chips over 2N halves it.

Explicit overrides (``load_engine(topology=...)`` / ``TPU9_TOPOLOGY``)
bypass the planner entirely — ``parse_topology`` is the shared syntax.
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass
from typing import Any, Optional


@dataclass(frozen=True)
class Topology:
    """A serving submesh shape: ``tp`` chips tensor-parallel (innermost,
    fastest ICI; shards weights AND the paged-KV head axis) × ``fsdp``
    chips weight-sharded on top. ``1x1`` is the single-chip engine and
    must behave bit-identically to a topology-oblivious build."""

    tp: int = 1
    fsdp: int = 1

    def __post_init__(self) -> None:
        if self.tp < 1 or self.fsdp < 1:
            raise ValueError(f"topology axes must be >= 1, got {self}")

    @property
    def n_chips(self) -> int:
        return self.tp * self.fsdp

    @property
    def is_single(self) -> bool:
        return self.n_chips == 1

    def as_dict(self) -> dict:
        return {"tp": self.tp, "fsdp": self.fsdp, "n_chips": self.n_chips}

    def __str__(self) -> str:
        return f"{self.tp}x{self.fsdp}"


def parse_topology(value: "str | Topology | None") -> Optional[Topology]:
    """Parse a topology override: ``"2"`` (tp only), ``"2x4"`` (tp×fsdp),
    or ``"tp=2,fsdp=4"``. ``None``/``""`` → None (caller decides the
    default); a :class:`Topology` passes through."""
    if value is None:
        return None
    if isinstance(value, Topology):
        return value
    s = str(value).strip().lower()
    if not s:
        return None
    if "=" in s:
        axes = {"tp": 1, "fsdp": 1}
        for part in s.split(","):
            k, _, v = part.partition("=")
            k = k.strip()
            if k not in axes:
                raise ValueError(f"unknown topology axis {k!r} in {value!r}"
                                 " (tp/fsdp)")
            axes[k] = int(v)
        return Topology(**axes)
    if "x" in s:
        tp_s, _, fsdp_s = s.partition("x")
        return Topology(tp=int(tp_s), fsdp=int(fsdp_s))
    return Topology(tp=int(s))


def topology_from_env(env: str = "TPU9_TOPOLOGY") -> Optional[Topology]:
    """The runner-facing override: ``TPU9_TOPOLOGY=2x1`` etc. ``auto`` is
    NOT resolved here — it needs a slice spec, which only the deploy-time
    caller has."""
    raw = os.environ.get(env, "")
    if not raw or raw.strip().lower() == "auto":
        return None
    return parse_topology(raw)


@dataclass(frozen=True)
class TopologyPlan:
    """A planner decision plus the HBM arithmetic that justifies it
    (``budget`` is the winning submesh's :class:`HbmBudget`; ``rejected``
    records each smaller candidate and why it lost — the deploy log line
    that makes 'why 4 chips?' answerable)."""

    preset: str
    topology: Topology
    budget: Any                      # serving.feasibility.HbmBudget
    rejected: tuple = ()             # ((Topology, required_gb, have_gb), ..)

    def as_dict(self) -> dict:
        return {"preset": self.preset, **self.topology.as_dict(),
                "budget": self.budget.as_dict(),
                "rejected": [
                    {**t.as_dict(), "required_gb_per_chip": req,
                     "hbm_gb_per_chip": have}
                    for t, req, have in self.rejected]}


def candidate_topologies(n_kv_heads: int, max_chips: int) -> list[Topology]:
    """Power-of-two chip counts, smallest first; per count, tp takes the
    largest factor that divides ``n_kv_heads`` (exact KV head sharding),
    fsdp the rest."""
    out: list[Topology] = []
    n = 1
    while n <= max_chips:
        tp = math.gcd(n, n_kv_heads)
        out.append(Topology(tp=tp, fsdp=n // tp))
        n *= 2
    return out


def plan_topology(preset: str, tpu: "str | Any", *, max_batch: int = 8,
                  max_seq_len: int = 2048, quantize: "str | None" = None,
                  kv_quant: bool = False,
                  overhead_frac: float = 0.10, kv_pool_blocks: int = 0,
                  kv_block_size: int = 0) -> TopologyPlan:
    """Smallest power-of-two submesh of ``tpu`` that provably serves
    ``preset``. Raises :class:`InfeasibleDeployment` (with the full
    arithmetic of the LARGEST candidate) when even the whole slice cannot
    hold it — same failure surface as ``validate_llm_deployment``."""
    from ..feasibility import InfeasibleDeployment, hbm_budget
    from ..presets import resolve_preset
    from ...types import parse_tpu_spec
    cfg, _ = resolve_preset(preset, quantize)
    spec = parse_tpu_spec(tpu) if isinstance(tpu, str) else tpu
    if spec is None:
        raise ValueError("plan_topology needs a TPU spec")

    rejected: list = []
    budget = None
    for topo in candidate_topologies(cfg.n_kv_heads, spec.chips):
        budget = hbm_budget(preset, spec, max_batch=max_batch,
                            max_seq_len=max_seq_len, tp=topo.tp,
                            fsdp=topo.fsdp, overhead_frac=overhead_frac,
                            quantize=quantize, kv_quant=kv_quant,
                            kv_pool_blocks=kv_pool_blocks,
                            kv_block_size=kv_block_size)
        if budget.fits:
            return TopologyPlan(preset=preset, topology=topo, budget=budget,
                                rejected=tuple(rejected))
        rejected.append((topo, round(budget.required_gb_per_chip, 3),
                         budget.hbm_per_chip_gb))
    d = budget.as_dict()
    raise InfeasibleDeployment(
        f"{preset} does not fit {spec.name} at any submesh up to "
        f"{spec.chips} chips: largest candidate tp={d['tp']} "
        f"fsdp={d['fsdp']} still needs {d['required_gb_per_chip']} GB/chip "
        f"(weights {d['weight_gb_per_chip']} + KV {d['kv_gb_per_chip']} + "
        f"scratch {d['scratch_gb_per_chip']}) against "
        f"{d['hbm_per_chip_gb']} GB. Remedies: int8 weights, int8 KV, "
        f"smaller max_batch/max_seq_len, or a larger slice.")


def resolve_topology(topology: "str | Topology | None" = None,
                     preset: str = "", tpu: "str | Any | None" = None,
                     **plan_kw) -> Topology:
    """Override chain for the serving stack: explicit arg → TPU9_TOPOLOGY
    env → planner (when a slice spec is known) → single chip. The string
    ``"auto"`` forces the planner (and then REQUIRES ``tpu``)."""
    want_auto = isinstance(topology, str) \
        and topology.strip().lower() == "auto"
    if not want_auto:
        explicit = parse_topology(topology)
        if explicit is not None:
            return explicit
        env = topology_from_env()
        if env is not None:
            return env
        want_auto = (os.environ.get("TPU9_TOPOLOGY", "")
                     .strip().lower() == "auto")
    if want_auto or (topology is None and tpu is not None and preset):
        if not (tpu and preset):
            raise ValueError(
                "topology='auto' needs a preset and a TPU spec to plan "
                "against (set topology explicitly, e.g. '2x1')")
        return plan_topology(preset, tpu, **plan_kw).topology
    return Topology(1, 1)
