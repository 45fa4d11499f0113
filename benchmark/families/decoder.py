"""The Mistral / Mixtral family: a uniform pre-norm decoder of grouped-query
attention with rotary positions and a SwiGLU feed-forward, dense or of sparse
experts (top-k of E, gates renormalised over the chosen), untied head, bf16.

Everything the harness knows about this architecture is here and nowhere
else: which published ``config.json`` keys it builds, the program's model
config, what a step needs in bytes and operations (from SHAPES, kept with the
benchmark so that no PR that claims a gain can change the yardstick; copies
of the decode accounting of ``tpu9/benchsuite/physics.py``, taken from sizes
and not from a weight tree so that the harness's jax-free parent can use
them), the kernel whose calls count decode steps, and which device scopes
each of the three decode shares sums. A configuration names this file with
``"family": "decoder"``; an architecture it does not build brings a family
file of its own.
"""

from __future__ import annotations

from benchmark import manifest
from benchmark.peaks import BF16, F32

# the published keys this family builds: always there / there for experts
SIZES = ("hidden_size", "intermediate_size", "num_attention_heads",
         "num_key_value_heads", "num_hidden_layers", "vocab_size",
         "max_position_embeddings", "rope_theta", "rms_norm_eps")
EXPERTS = ("num_local_experts", "num_experts_per_tok")
# published keys it builds at one value only
ONLY = (("hidden_act", "silu"), ("tie_word_embeddings", False),
        ("sliding_window", None), ("torch_dtype", "bfloat16"))
# published keys that say nothing about the shape
SHAPELESS = ("architectures",)

# the kernel whose calls count decode steps: one call a layer
STEP_MARKER = "paged_decode_attention"

# which device scopes (``tpu9.models.transformer.DEVICE_SCOPES``) each of
# the three decode shares sums
SCOPE_GROUPS = {
    "kv_pool": ("kv.slice", "kv.write", "kv.pack", "kv.gather", "kv.splice"),
    "attention": ("attn.core",),
    "ffn": ("ffn", "moe.route", "moe.experts", "moe.combine"),
}


def model_sizes(config: dict) -> dict:
    """The sizes the reference and the program need, from a configuration
    file in the published ``config.json`` vocabulary."""
    known = SIZES + EXPERTS + SHAPELESS + tuple(k for k, _ in ONLY) \
        + manifest.HARNESS_KEYS
    for key in config:
        if key not in known:
            raise ValueError(f"{key}={config[key]!r}: the decoder family "
                             "does not build this key")
    assumed = {k: v["value"] for k, v in config.get("assumed", {}).items()}
    model = {k: config[k] for k in SIZES}
    model["num_local_experts"] = config.get("num_local_experts", 0)
    model["num_experts_per_tok"] = config.get("num_experts_per_tok", 0)
    model["head_dim"] = assumed.get(
        "head_dim", model["hidden_size"] // model["num_attention_heads"])
    model["moe_capacity_factor"] = assumed.get("moe_capacity_factor", 0.0)
    for key, want in ONLY:
        if config.get(key, want) != want:
            raise ValueError(f"{key}={config[key]!r}: this harness builds "
                             f"only {want!r}")
    return model


def program_config(model: dict):
    from tpu9.models.transformer import DecoderConfig
    moe = {}
    if model["num_local_experts"]:
        moe = dict(n_experts=model["num_local_experts"],
                   moe_top_k=model["num_experts_per_tok"],
                   moe_capacity_factor=model["moe_capacity_factor"])
    return DecoderConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        hidden_dim=model["intermediate_size"], norm_eps=model["rms_norm_eps"],
        rope_theta=model["rope_theta"],
        max_seq_len=model["max_position_embeddings"], act="silu",
        tie_embeddings=False, **moe)


def marker_calls_per_step(model: dict) -> int:
    return model["num_hidden_layers"]


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


def kv_row_bytes(model: dict) -> int:
    """Bytes of one context token's keys and values in one layer."""
    return 2 * model["num_key_value_heads"] * model["head_dim"] * BF16


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
    return (layers * per_layer + p["head"] * BF16 + d * F32
            + layers * kv_row_bytes(model) * resident_context)


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


def kernel_cost(kernel: str, model: dict, engine: dict, batch: float,
                resident_context: float):
    """``{"bytes", "flops"}`` that one decode step NEEDS of the kernel the
    device trace prints as ``kernel``, whole model, all its calls of the
    step together; None for a kernel this family has no count for. What the
    step needs, not what the kernel moves: whole blocks of ``engine``'s
    ``kv_block_size`` and padded tables are the kernel's own affair."""
    if kernel == STEP_MARKER:
        # every resident context token's keys and values once a layer; the
        # scores and the weighted sum: 2 x 2 x heads x head_dim a token. The
        # queries and outputs (``batch`` rows a layer) are left out.
        layers = model["num_hidden_layers"]
        return {"bytes": layers * kv_row_bytes(model) * resident_context,
                "flops": layers * 4.0 * model["num_attention_heads"]
                * model["head_dim"] * resident_context}
    return None
