"""The hybrid state-space family (``model_type: granitemoehybrid`` with no
routed experts, Granite 4.0-H): a layer pattern given as a LIST
(``layer_types``) — Mamba-2 state-space mixers over a float32 state a LANE
that no cache holds, and every tenth layer or so grouped-query attention
WITHOUT positions over per-head rows of a pool only as deep as there are
such layers — every layer closed by the same dense SwiGLU
(``shared_intermediate_size``), a tied head, and four multipliers
(embeddings, residual branches, the softmax scale, the logits).

Everything the harness knows about this architecture is here: which
published keys it builds and at which values (every other key or value is a
``ValueError``), what is assumed (each under ``assumed`` in the
configuration's file, and only these values build), the program's model
config, what a step and a kernel need in bytes and operations, the kernel
whose calls count decode steps, and the scope groups. It imports the looped
family for nothing but its reading of the program's fields.
"""

from __future__ import annotations

from benchmark import manifest
from benchmark.families import looped
from benchmark.peaks import BF16, F32

# published keys this family builds as sizes
SIZES = ("hidden_size", "intermediate_size", "shared_intermediate_size",
         "num_attention_heads", "num_key_value_heads", "num_hidden_layers",
         "vocab_size", "max_position_embeddings", "rope_theta",
         "rms_norm_eps", "layer_types", "attention_multiplier",
         "embedding_multiplier", "residual_multiplier", "logits_scaling",
         "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv",
         "mamba_expand", "mamba_chunk_size")
# published keys it builds at one value only: no routed experts, no
# positions, no biases but the convolution's, one group of B and C
ONLY = (("model_type", "granitemoehybrid"), ("num_local_experts", 0),
        ("num_experts_per_tok", 0), ("position_embedding_type", "nope"),
        ("rope_scaling", None), ("attention_bias", False),
        ("hidden_act", "silu"), ("normalization_function", "rmsnorm"),
        ("mamba_conv_bias", True), ("mamba_proj_bias", False),
        ("mamba_n_groups", 1), ("tie_word_embeddings", True))
LAYER_KINDS = {"mamba": "ssm", "attention": "full"}
# keys of the harness's own that its list of them does not have
OWN_HARNESS = ("correct_tolerance_readings",)
# what ``config.json`` has no key for: a configuration states each under
# ``assumed``, and only these values build (``head_dim``: hidden_size /
# num_attention_heads, whatever the sizes)
ASSUMED = {
    "torch_dtype": "bfloat16",
    "ssm_state_dtype": "float32",
    "gated_norm": "gate_before_norm_one_group",
    "dt_limits": "none",
    "attention_scale": "attention_multiplier_is_the_softmax_scale",
    "seeded_weights": "tied table normal at 0.02 / embedding_multiplier; "
                      "A_log = log(uniform[1, 16]); dt_bias = inverse "
                      "softplus of dt log-uniform over [0.001, 0.1]; D = 1; "
                      "conv taps and bias uniform +- 1/sqrt(d_conv)"}
# ``assumed.torch_dtype``: the model's own type, and float32 for the CPU
# rehearsal's tiny sizes alone (exact against the reference: the rehearsal
# holds the WALK, the chip the precision)
DTYPES = ("bfloat16", "float32")
# the fields the program's model config needs for this family
DESCRIPTORS = ("layer_pattern", "ssm_heads", "ssm_head_dim", "ssm_state",
               "ssm_conv", "rope", "attn_scale", "embed_mult",
               "residual_mult", "logit_div")

# the kernel whose calls count decode steps: the paged attention kernel, one
# call an attention layer (4 of 40); the state's step kernel
# (``tpu9.ops.ssd.STEP_KERNEL``) runs once a state-space layer
STEP_MARKER = "paged_decode_attention"
SSM_STEP_KERNEL = "ssm_state_step"
SSM_STATE_SCOPE = "attn.ssm.state"
# the three decode shares: the mixers are attention
SCOPE_GROUPS = {
    "kv_pool": ("kv.slice", "kv.write", "kv.pack", "kv.gather", "kv.splice"),
    "attention": ("attn.core", "attn.ssm.proj", SSM_STATE_SCOPE),
    "ffn": ("ffn", "moe.route", "moe.experts", "moe.combine"),
}


def model_sizes(config: dict) -> dict:
    """The sizes the reference and the program need. Refuses, before
    anything is started, a key or value this family does not build and a
    program that cannot run a listed pattern of state-space layers."""
    # as the looped family: read from the program's source, because the
    # driver tries a new cell on the parent commit under THESE files, and
    # that run has to fail at once, in the harness's own process
    lacks = [f for f in DESCRIPTORS if f not in looped._program_fields()]
    if lacks:
        raise ValueError(f"the program's DecoderConfig has no {lacks}: it "
                         "cannot run a layer pattern given as a list, "
                         "state-space layers or attention without positions")
    known = SIZES + OWN_HARNESS + tuple(k for k, _ in ONLY) \
        + manifest.HARNESS_KEYS
    for key in config:
        if key not in known:
            raise ValueError(f"{key}={config[key]!r}: the granitehybrid "
                             "family does not build this key")
    for key, want in ONLY:
        if key not in config or config[key] != want:
            raise ValueError(f"{key}={config.get(key)!r}: the granitehybrid "
                             f"family builds only {want!r}")
    assumed = {k: v["value"] for k, v in config.get("assumed", {}).items()}
    if set(assumed) != set(ASSUMED) | {"head_dim"}:
        raise ValueError("assumed: the granitehybrid family builds exactly "
                         f"{sorted(set(ASSUMED) | {'head_dim'})}, the file "
                         f"states {sorted(assumed)}")
    for key, value in assumed.items():
        if key != "head_dim" and value != ASSUMED[key] and not (
                key == "torch_dtype" and value in DTYPES):
            raise ValueError(f"assumed {key}={value!r}: the granitehybrid "
                             f"family builds only {ASSUMED[key]!r}")
    model = {k: config[k] for k in SIZES}
    model["mamba_n_groups"] = config["mamba_n_groups"]
    layers = model["num_hidden_layers"]
    kinds = list(model["layer_types"])
    if len(kinds) != layers or any(k not in LAYER_KINDS for k in kinds) \
            or not {"mamba", "attention"} <= set(kinds):
        raise ValueError(f"layer_types: {layers} entries of "
                         f"{sorted(LAYER_KINDS)}, both kinds present")
    heads = model["num_attention_heads"]
    if model["hidden_size"] % heads \
            or assumed["head_dim"] != model["hidden_size"] // heads:
        raise ValueError(f"assumed head_dim={assumed['head_dim']!r}: "
                         "hidden_size / num_attention_heads only")
    model["head_dim"] = assumed["head_dim"]
    if model["mamba_n_heads"] * model["mamba_d_head"] \
            != model["mamba_expand"] * model["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head must be mamba_expand "
                         "x hidden_size")
    if model["intermediate_size"] != model["shared_intermediate_size"]:
        raise ValueError("intermediate_size: with no routed experts the "
                         "layer's one SwiGLU is shared_intermediate_size "
                         "wide, and both keys state it")
    if model["mamba_chunk_size"] < 1 or model["mamba_d_conv"] < 2:
        raise ValueError("mamba_chunk_size / mamba_d_conv: a block of the "
                         "chunked form, at least 2 taps")
    for key in ("attention_multiplier", "embedding_multiplier",
                "residual_multiplier", "logits_scaling"):
        model[key] = float(model[key])
        if model[key] <= 0:
            raise ValueError(f"{key}={config[key]!r}: positive")
    model["torch_dtype"] = assumed["torch_dtype"]
    return model


def layer_kinds(model: dict) -> list:
    """The program's kind of every layer, as ``layer_types`` lists them."""
    return [LAYER_KINDS[k] for k in model["layer_types"]]


def program_config(model: dict):
    import jax.numpy as jnp

    from tpu9.models import kvstate
    from tpu9.models.transformer import DecoderConfig
    cfg = DecoderConfig(
        dtype=getattr(jnp, model["torch_dtype"]),
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        hidden_dim=model["shared_intermediate_size"],
        norm_eps=model["rms_norm_eps"], rope_theta=model["rope_theta"],
        max_seq_len=model["max_position_embeddings"], act="silu",
        tie_embeddings=True, layer_pattern=tuple(layer_kinds(model)),
        ssm_heads=model["mamba_n_heads"], ssm_head_dim=model["mamba_d_head"],
        ssm_state=model["mamba_d_state"], ssm_groups=model["mamba_n_groups"],
        ssm_conv=model["mamba_d_conv"], rope=False,
        attn_scale=model["attention_multiplier"],
        embed_mult=model["embedding_multiplier"],
        residual_mult=model["residual_multiplier"],
        logit_div=model["logits_scaling"])
    # ``assumed.ssm_state_dtype``: the state's float32 is part of the
    # configuration's RESULT, and one run's margin cannot tell a state
    # rounded to bfloat16 from the sound program (the file's
    # ``correct_tolerance_why``): a program that keeps the lanes' state in
    # anything narrower is refused here, before anything is started
    kept = kvstate.lane_shapes(cfg, 1)["ssm_state"][1]
    if jnp.dtype(kept) != jnp.dtype(ASSUMED["ssm_state_dtype"]):
        raise ValueError(f"the program keeps the lanes' state in "
                         f"{jnp.dtype(kept).name}: this configuration's is "
                         f"{ASSUMED['ssm_state_dtype']}")
    return cfg


def marker_calls_per_step(model: dict) -> int:
    return layer_kinds(model).count("full")


def conv_width(model: dict) -> int:
    return model["mamba_n_heads"] * model["mamba_d_head"] \
        + 2 * model["mamba_n_groups"] * model["mamba_d_state"]


def matmul_params(model: dict) -> dict:
    """Parameters of the matrices one token passes through, by part."""
    d, heads, kv = model["hidden_size"], model["num_attention_heads"], \
        model["num_key_value_heads"]
    hd = model["head_dim"]
    inner = model["mamba_n_heads"] * model["mamba_d_head"]
    return {"ssm": d * (inner + conv_width(model) + model["mamba_n_heads"])
            + inner * d,
            "full": 2 * d * heads * hd + 2 * d * kv * hd,
            "ffn": 3 * d * model["shared_intermediate_size"],
            "head": d * model["vocab_size"]}


def ssm_vector_params(model: dict) -> int:
    """The float32 vectors of one mixer: taps, bias, ``dt_bias``, ``A_log``,
    ``D``, the gated norm."""
    return (model["mamba_d_conv"] + 1) * conv_width(model) \
        + 3 * model["mamba_n_heads"] \
        + model["mamba_n_heads"] * model["mamba_d_head"]


def state_elements(model: dict) -> int:
    """Numbers of a lane's state in ONE state-space layer."""
    return model["mamba_n_heads"] * model["mamba_d_head"] \
        * model["mamba_d_state"]


def state_bytes_per_lane(model: dict) -> float:
    """A lane's state, one layer: the float32 matrix a head, and the
    convolution's last inputs in bf16."""
    return state_elements(model) * F32 \
        + (model["mamba_d_conv"] - 1) * conv_width(model) * BF16


def kv_row_bytes(model: dict) -> int:
    """Bytes of one context token's keys and values in one attention layer."""
    return 2 * model["num_key_value_heads"] * model["head_dim"] * BF16


def decode_bytes_per_step(model: dict, batch: float,
                          resident_context: float) -> float:
    """Bytes one decode step has to move, whole model: every matrix once at
    its stored width (bf16; the mixers' vectors and the norms float32), the
    state of every live lane READ AND WRITTEN in every state-space layer
    (the float32 matrix and the convolution's tail), and the keys and values
    of every resident context token in the attention layers' planes. The
    head is the embedding table (tied), read once; the embedding gather
    (``batch`` rows) is left out."""
    p = matmul_params(model)
    d = model["hidden_size"]
    total = p["head"] * BF16 + d * F32
    for kind in layer_kinds(model):
        total += (p[kind] + p["ffn"]) * BF16 + 2 * d * F32
        if kind == "ssm":
            total += ssm_vector_params(model) * F32 \
                + 2 * batch * state_bytes_per_lane(model)
        else:
            total += kv_row_bytes(model) * resident_context
    return total


def prefill_flops_per_token(model: dict) -> float:
    """Matmul FLOPs one prompt token needs: 2 per parameter it passes
    through (the mixer's or the attention's projections, the SwiGLU). The
    recurrence, the attention scores and the head are not counted: a lower
    bound."""
    p = matmul_params(model)
    return 2.0 * sum(p[kind] + p["ffn"] for kind in layer_kinds(model))


def kernel_cost(kernel: str, model: dict, engine: dict, batch: float,
                resident_context: float):
    """``{"bytes", "flops"}`` one decode step NEEDS, whole model. Of the
    state's step (the kernel the trace prints as ``ssm_state_step``, or its
    scope ``attn.ssm.state``): every LIVE lane's float32 state read once and
    written once in every state-space layer; decay, input and read-out: 5
    operations a state element. Of the paged attention kernel: every
    resident token's keys and values once an attention layer; the scores
    and the weighted sum 2 x 2 x heads x head_dim a token."""
    kinds = layer_kinds(model)
    if kernel in (SSM_STEP_KERNEL, SSM_STATE_SCOPE):
        per_lane = state_elements(model)
        return {"bytes": kinds.count("ssm") * batch * 2 * per_lane * F32,
                "flops": kinds.count("ssm") * batch * 5.0 * per_lane}
    if kernel == STEP_MARKER:
        planes = kinds.count("full")
        return {"bytes": planes * kv_row_bytes(model) * resident_context,
                "flops": planes * 4.0 * model["num_attention_heads"]
                * model["head_dim"] * resident_context}
    return None
