"""The table of peaks, and the functions that compute what a step needs —
operations and bytes from SHAPES — kept with the benchmark so that no PR that
claims a gain can change the yardstick. Copies of the table and the decode
accounting of ``tpu9/benchsuite/physics.py``, taken from sizes and not from a
weight tree, so that the harness's jax-free parent can use them.

Peaks per chip, Google Cloud documentation ("TPU v5e": 197 TFLOP/s bf16,
16 GB HBM at 819 GB/s; "TPU v6e": 918 TFLOP/s, 32 GB at 1,640 GB/s). A device
that is not in the table is an error, not a default.
"""

from __future__ import annotations

# keyed on substrings of jax's Device.device_kind, lowercased
PEAKS = (
    ("v5 lite", {"chip": "tpu-v5e", "bf16_tflops": 197.0, "hbm_gbps": 819.0,
                 "hbm_bytes": 16e9}),
    ("v5e", {"chip": "tpu-v5e", "bf16_tflops": 197.0, "hbm_gbps": 819.0,
             "hbm_bytes": 16e9}),
    ("v6 lite", {"chip": "tpu-v6e", "bf16_tflops": 918.0, "hbm_gbps": 1640.0,
                 "hbm_bytes": 32e9}),
    ("v6e", {"chip": "tpu-v6e", "bf16_tflops": 918.0, "hbm_gbps": 1640.0,
             "hbm_bytes": 32e9}),
)

BF16, F32 = 2, 4


def chip_peaks(device_kind: str) -> dict:
    kind = (device_kind or "").lower()
    for needle, peaks in PEAKS:
        if needle in kind:
            return peaks
    raise KeyError(f"no peak figures for device kind {device_kind!r}: add "
                   "it to benchmark/peaks.py with its source")


def matmul_params(model: dict) -> dict:
    """Parameters of the matrices one token passes through, by part."""
    d, heads, kv = model["hidden_size"], model["num_attention_heads"], \
        model["num_key_value_heads"]
    hd, inter = model["head_dim"], model["intermediate_size"]
    return {"attention": 2 * d * heads * hd + 2 * d * kv * hd,   # q,o + k,v
            "ffn": 3 * d * inter,                                # one expert
            "router": d * model["num_local_experts"],
            "head": d * model["vocab_size"]}


def experts_touched(model: dict, batch: float) -> float:
    """Expected number of distinct experts that ``batch`` tokens reach in one
    layer under uniform top-k routing: E (1 - (1 - k/E)^batch). A dense FFN
    is one expert, always touched."""
    e, k = model["num_local_experts"], model["num_experts_per_tok"]
    if not e:
        return 1.0
    return e * (1.0 - (1.0 - k / e) ** max(batch, 0.0))


def decode_bytes_per_step(model: dict, batch: float,
                          resident_context: float) -> float:
    """Bytes one decode step has to read, whole model: every matrix a token
    of the batch passes through, at its stored width (bf16; the router and
    the norms float32), and the keys and values of every resident context
    token. The embedding gather (``batch`` rows) is left out."""
    p = matmul_params(model)
    layers, d = model["num_hidden_layers"], model["hidden_size"]
    per_layer = (p["attention"] * BF16
                 + experts_touched(model, batch) * p["ffn"] * BF16
                 + p["router"] * F32 + 2 * d * F32)
    kv_row = 2 * model["num_key_value_heads"] * model["head_dim"] * BF16
    return (layers * per_layer + p["head"] * BF16 + d * F32
            + layers * kv_row * resident_context)


def prefill_flops_per_token(model: dict) -> float:
    """Matmul FLOPs one prompt token needs: 2 per parameter it passes
    through (attention projections, its top-k experts, the router). The
    attention scores (4 x context x heads x head_dim per layer) depend on the
    context and are NOT counted, nor is the head (one row per prompt): the
    share computed from this is a lower bound and cannot pass 100 %."""
    p = matmul_params(model)
    k = model["num_experts_per_tok"] or 1
    return 2.0 * model["num_hidden_layers"] * (
        p["attention"] + k * p["ffn"] + p["router"])
