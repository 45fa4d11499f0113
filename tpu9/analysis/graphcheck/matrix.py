"""The declared preset × topology verification matrix (ISSUE 11).

Each cell names a preset and a topology plus the engine knobs graphcheck
lowers the serving graphs with. Every check is shape-level (jaxpr +
lowered/compiled artifact on a forced CPU mesh), so cells are
depth-reduced: ``n_layers=2`` keeps flagship-shaped per-layer tensors
(the sharding/dtype/donation invariants are per-layer identical — layer
3 traces the same eqns as layer 2) while the full matrix stays inside
the tier-1 budget (400 s of CPU time, about 55 s of wall clock alone).
Per-layer SHAPES are never reduced: head counts, head_dim, hidden/vocab
dims are the flagship's, so divisibility (the silent-replication trap) is
checked against the real arithmetic.

Extending the matrix when adding a preset or a graph: add a Cell (or a
knob) here; Pass A derives everything else from the GraphFactory's own
``lowering_jobs`` enumeration, so a new graph is covered the moment
precompile knows it.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Cell:
    preset: str
    topology: str                 # "1x1", "2x1", "2x2", ...
    quantize: str = ""            # "" | "int8" weight quantization
    kv_quant: str = ""            # "" | "int8" paged-KV pool
    n_layers: int = 2             # depth reduction (shapes stay flagship)
    loop_steps: int = 0           # passes of a looped preset (0 = its own)
    paged: bool = True            # False = legacy dense-cache graph set
    max_batch: int = 2
    max_seq_len: int = 256
    kv_block_size: int = 64
    chunk: int = 128              # prefill chunk (paged) / smallest bucket
    prefill_buckets: tuple = (128, 256)   # dense-mode buckets
    decode_steps: tuple = (1, 4)
    spec_len: int = 4             # speculative-verify graph length
    admit_group_chunks: int = 2   # fused admission group size
    kv_pool_blocks: int = 8

    @property
    def name(self) -> str:
        tags = [t for t in (self.quantize and f"w{self.quantize}",
                            self.kv_quant and f"kv{self.kv_quant}",
                            "" if self.paged else "dense") if t]
        return f"{self.preset}@{self.topology}" + \
            ("+" + "+".join(tags) if tags else "")


# The shipped matrix. Flagship presets × {1x1, tp=2, 2x2} is the floor
# (ISSUE 11); the quantized and MoE cells cover the int8 scale planes
# and per-expert sharding, the dense cell the legacy bucket/dsplice
# graph set.
MATRIX: tuple = (
    # flagship: the config the v5e serving economics are priced on
    Cell("llama3-8b", "1x1"),
    Cell("llama3-8b", "2x1"),
    Cell("llama3-8b", "2x2"),
    # quantized serving end-to-end: int8 weights + int8 paged KV — the
    # scale planes must ride the same head-axis specs as the payload
    Cell("llama3-8b", "2x1", quantize="int8", kv_quant="int8"),
    # second flagship family: 16 KV heads, 256-wide heads
    Cell("gemma-7b", "1x1"),
    Cell("gemma-7b", "2x1"),
    Cell("gemma-7b", "2x2"),
    # MoE flagship: stacked per-expert tensors shard over tp too
    Cell("mixtral-8x7b", "1x1"),
    Cell("mixtral-8x7b", "2x1"),
    Cell("mixtral-8x7b", "2x2"),
    # legacy dense cache: prefill buckets + dense splice graphs
    Cell("llama3-8b", "2x1", paged=False),
    # looped decoder: a pool ``kv_layers`` deep carried through the device
    # loop over the passes (two passes of two layers trace what four of
    # forty-eight do), the exit gate and sandwich norms replicated
    Cell("ouro-2.6b", "1x1", loop_steps=2),
    Cell("ouro-2.6b", "2x1", loop_steps=2),
)


def find_cells(names=None) -> list:
    """Subset the matrix by cell name (None = all), loudly rejecting
    unknown names so a typo'd --cell can't silently verify nothing."""
    if not names:
        return list(MATRIX)
    by_name = {c.name: c for c in MATRIX}
    out = []
    for n in names:
        if n not in by_name:
            raise KeyError(
                f"unknown graphcheck cell {n!r}; have {sorted(by_name)}")
        out.append(by_name[n])
    return out
