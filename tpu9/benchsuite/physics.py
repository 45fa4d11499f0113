"""Physics sanity checks for accelerator benchmarks.

A measured number that implies more FLOP/s than the chip's peak or more
bytes/s than its HBM can stream is not a measurement — it is a timing bug
(a decode "throughput" implying ~23 TB/s of HBM bandwidth on a v5e is what
timing the enqueue instead of the completed step produces).  Every
throughput-style benchmark phase must pass its
numbers through :func:`decode_physics` / :func:`matmul_physics` and treat
``mbu >= 1`` or ``mfu >= 1`` as a hard failure, the same
evidence-or-fail stance as ``tpu9.benchsuite.validators`` (reference
analogue: ``benchmarks/b9bench/validators.py:6-60``).

Peak numbers are the public per-chip figures (bf16 MXU peak, HBM size and
bandwidth) for each TPU generation, keyed by ``device_kind``. A device that
is not in the table is an error, not a default: utilization against an
invented peak is not a number.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_bf16_tflops: float     # dense MXU peak, bf16 in / f32 acc
    hbm_gib: float
    hbm_gbps: float             # GB/s (decimal)


# keyed on substrings of jax Device.device_kind (lowercased)
_CHIP_SPECS: tuple[tuple[str, ChipSpec], ...] = (
    ("v6 lite", ChipSpec("tpu-v6e", 918.0, 32.0, 1640.0)),
    ("v6e", ChipSpec("tpu-v6e", 918.0, 32.0, 1640.0)),
    ("v5 lite", ChipSpec("tpu-v5e", 197.0, 16.0, 819.0)),
    ("v5litepod", ChipSpec("tpu-v5e", 197.0, 16.0, 819.0)),
    ("v5e", ChipSpec("tpu-v5e", 197.0, 16.0, 819.0)),
    ("v5p", ChipSpec("tpu-v5p", 459.0, 95.0, 2765.0)),
    ("v5", ChipSpec("tpu-v5p", 459.0, 95.0, 2765.0)),
    ("v4", ChipSpec("tpu-v4", 275.0, 32.0, 1228.0)),
    ("v3", ChipSpec("tpu-v3", 123.0, 32.0, 900.0)),
)

def chip_spec(device_kind: str) -> ChipSpec:
    dk = (device_kind or "").lower()
    for needle, spec in _CHIP_SPECS:
        if needle in dk:
            return spec
    raise KeyError(f"no peak figures for device kind {device_kind!r}; "
                   "add it to _CHIP_SPECS with its source")


# ---------------------------------------------------------------------------
# decode (autoregressive, weight-streaming-bound)
# ---------------------------------------------------------------------------

def _ratio(x: float) -> float:
    """Round a utilization ratio to 4 SIGNIFICANT digits, not 4 decimal
    places: a run far under the chip's ceiling produces honest ratios
    in the 1e-5 range, and fixed-point rounding collapses them to a flat
    0.0 — which reads as 'no evidence' instead of 'tiny but real' (ISSUE
    5 satellite)."""
    return float(f"{x:.4g}")


def decode_physics(*, step_ms: float, batch: int, streamed_bytes: int,
                   kv_bytes_per_step: int, matmul_params: int,
                   attn_flops_per_step: float = 0.0,
                   spec: ChipSpec) -> dict:
    """Model-bandwidth-utilization + MFU for one decode step.

    streamed_bytes: weight bytes read from HBM per step (all matmul weights
    at their stored precision; embedding-gather rows excluded — a gather
    reads ``batch`` rows, not the table).
    kv_bytes_per_step: KV-cache bytes read (+written) per step.
    matmul_params: number of matmul weight *parameters* per step (each
    contributes 2*batch FLOPs regardless of stored precision — int8 weights
    are dequantized into bf16 MXU ops).
    """
    step_s = step_ms / 1e3
    bytes_per_step = streamed_bytes + kv_bytes_per_step
    flops_per_step = 2.0 * matmul_params * batch + attn_flops_per_step
    achieved_gbps = bytes_per_step / step_s / 1e9
    achieved_tflops = flops_per_step / step_s / 1e12
    mbu = achieved_gbps / spec.hbm_gbps
    mfu = achieved_tflops / spec.peak_bf16_tflops
    return {
        "chip": spec.name,
        "step_ms": round(step_ms, 4),
        "bytes_per_step": bytes_per_step,
        "flops_per_step": int(flops_per_step),
        "achieved_gbps": _ratio(achieved_gbps),
        "achieved_tflops": _ratio(achieved_tflops),
        "mbu": _ratio(mbu),
        "mfu": _ratio(mfu),
        "min_step_ms_bandwidth": round(bytes_per_step / spec.hbm_gbps / 1e6, 4),
    }


def matmul_physics(*, elapsed_ms: float, flops: float, bytes_moved: int,
                   spec: ChipSpec) -> dict:
    """MFU/MBU for a compute-style kernel timing (attention, matmul)."""
    s = elapsed_ms / 1e3
    achieved_tflops = flops / s / 1e12
    achieved_gbps = bytes_moved / s / 1e9
    return {
        "chip": spec.name,
        "elapsed_ms": round(elapsed_ms, 4),
        "achieved_tflops": _ratio(achieved_tflops),
        "achieved_gbps": _ratio(achieved_gbps),
        "mfu": _ratio(achieved_tflops / spec.peak_bf16_tflops),
        "mbu": _ratio(achieved_gbps / spec.hbm_gbps),
    }


def physics_violations(report: dict, *, what: str,
                       ceiling: float = 1.0) -> list[str]:
    """Hard failures: utilization at or above the physical ceiling means the
    timing did not measure real execution. (A small grace above 1.0 is NOT
    given — peaks are already theoretical maxima no end-to-end decode
    reaches.)"""
    fails = []
    if report.get("mbu", 0.0) >= ceiling:
        fails.append(
            f"{what}: MBU {report['mbu']:.3f} >= {ceiling} — implies "
            f"{report['achieved_gbps']:.0f} GB/s vs chip HBM "
            f"{chip_by_name(report['chip']).hbm_gbps:.0f} GB/s; the timing "
            f"window did not fence device execution")
    if report.get("mfu", 0.0) >= ceiling:
        fails.append(
            f"{what}: MFU {report['mfu']:.3f} >= {ceiling} — implies "
            f"{report['achieved_tflops']:.0f} TFLOP/s vs chip peak "
            f"{chip_by_name(report['chip']).peak_bf16_tflops:.0f}; the "
            f"timing window did not fence device execution")
    return fails


def linear_scaling_violations(elapsed_1x: float, elapsed_2x: float, *,
                              what: str, lo: float = 1.5,
                              hi: float = 2.6) -> list[str]:
    """Doubling the work must ~double elapsed time. A ratio near 1.0 means
    the backend queued work asynchronously and the clock stopped before the
    device ran it (round-2 failure: 64 decode steps 'took' ~2 real steps)."""
    if elapsed_1x <= 0:
        return [f"{what}: non-positive base elapsed {elapsed_1x}"]
    ratio = elapsed_2x / elapsed_1x
    if not (lo <= ratio <= hi):
        return [f"{what}: 2x-work elapsed ratio {ratio:.2f} outside "
                f"[{lo}, {hi}] — timing does not track device execution"]
    return []


def chip_by_name(name: str) -> ChipSpec:
    for _, spec in _CHIP_SPECS:
        if spec.name == name:
            return spec
    return _UNKNOWN


# ---------------------------------------------------------------------------
# model accounting helpers
# ---------------------------------------------------------------------------

def decode_byte_counts(params, cfg, batch: int, mean_ctx: int) -> dict:
    """Bytes/FLOPs accounting for one decode step of a decoder param tree
    (plain or int8-quantized entries).

    - streamed weight bytes: every matmul weight at stored width. The
      embedding table is excluded (token gather reads B rows); a tied
      lm_head IS streamed (it is a matmul).
    - matmul params: same tensors counted in parameters.
    - kv bytes: read of ``mean_ctx`` K+V rows per layer per sequence plus
      the single-row write.

    A looped decoder (``cfg.loop_steps`` passes over one set of layers)
    streams everything under ``layers`` once a PASS and passes a token
    through those matrices as often; its KV state is ``cfg.kv_layers``
    deep. What is outside the layers (head, final norm, gate) is read
    once — the final norm's few bytes a pass are left at once.
    """
    import numpy as np

    streamed = 0
    matmul_params = 0

    passes = getattr(cfg, "loop_steps", 1)
    kv_layers = getattr(cfg, "kv_layers", cfg.n_layers)

    def walk(node, path=()):
        nonlocal streamed, matmul_params
        times = passes if path[:1] == ("layers",) else 1
        if isinstance(node, dict):
            if "q" in node and "scale" in node and getattr(
                    node["q"], "ndim", 0) == 2:   # quantized entry
                streamed_local = (node["q"].size * node["q"].dtype.itemsize
                                  + node["scale"].size
                                  * node["scale"].dtype.itemsize)
                streamed += times * streamed_local
                matmul_params += times * int(node["q"].size)
                return
            for k, v in node.items():
                walk(v, path + (k,))
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(v, path + (str(i),))
        else:
            if not hasattr(node, "ndim"):
                return
            name = path[-1] if path else ""
            if name == "embed":
                if getattr(cfg, "tie_embeddings", False):
                    streamed += node.size * node.dtype.itemsize
                    matmul_params += int(node.size)
                return                      # gather: B rows, negligible
            if node.ndim >= 2:              # projection / moe weight
                streamed += times * node.size * node.dtype.itemsize
                matmul_params += times * int(node.size)
            else:                           # norm vectors: tiny but real
                streamed += times * node.size * node.dtype.itemsize

    walk(params)

    kv_dtype_bytes = 2  # bf16 cache
    kv_row = cfg.n_kv_heads * cfg.head_dim * kv_dtype_bytes
    kv_read = 2 * kv_layers * batch * mean_ctx * kv_row         # K and V
    kv_write = 2 * kv_layers * batch * kv_row
    # attention FLOPs: qk^T + att*v over mean_ctx keys, grouped-query
    attn_flops = 4.0 * batch * mean_ctx * cfg.n_heads * cfg.head_dim \
        * kv_layers
    return {
        "streamed_bytes": int(streamed),
        "matmul_params": int(matmul_params),
        "kv_bytes_per_step": int(kv_read + kv_write),
        "attn_flops_per_step": float(attn_flops),
        "param_count": int(np.sum([matmul_params])),
    }
