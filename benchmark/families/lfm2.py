"""The gated-short-convolution / attention hybrid with sparse experts
(``model_type: lfm2_moe``, LFM2-8B-A1B): a layer pattern given as a LIST
(``layer_types``) of WHOLE layers — ``"conv"`` a gated short convolution whose
whole state is the last ``conv_L_cache - 1`` rows of a product, kept a LANE
and, for the prefix cache, a BLOCK; ``"full_attention"`` grouped-query
attention with rotary positions under an RMSNorm a head on queries and keys,
over per-head rows of a pool only as deep as there are such layers — the
first ``num_dense_layers`` closed by a dense SwiGLU, the others by
``num_experts`` sigmoid-routed experts with a bias in the choice only, all of
them held, and a tied head. A configuration may be one STAGE of a pipeline
(``deployment``): the first layers of the published list, every layer whole
on its chip.

Everything the harness knows about this architecture is here: which
published keys it builds and at which values (every other key or value is a
``ValueError``), what is assumed (each under ``assumed`` in the
configuration's file, and only these values build), the program's model
config, what a step and a kernel need in bytes and operations, the kernel
whose calls count decode steps, and the scope groups. It imports the looped
family for nothing but its reading of the program's fields, and the
hybrid-linear family for ``experts_touched`` alone.

``correct`` for this family is decided on the routing the program SERVED, as
the hybrid-linear family's: the program keeps the experts each sequence chose
(``tpu9.serving.routed_experts``), ``program_config`` hands that record to
the reference's door (``reference/served_routing.py``), and the reference
takes a served choice where it is a tie within ``correct_routing_tie`` by
its own float32 scores and nowhere else (``reference/lfm2.py``).
"""

from __future__ import annotations

from benchmark import manifest
from benchmark.families import looped
# (the expected number of HELD experts a batch's picks reach under uniform
# routing: the hybrid-linear family's, over ``experts_routed`` and
# ``experts_held`` — here every routed expert is held)
from benchmark.families.ling import experts_touched
from benchmark.peaks import BF16, F32

# published keys this family builds as sizes
SIZES = ("hidden_size", "intermediate_size", "moe_intermediate_size",
         "num_attention_heads", "num_key_value_heads", "num_hidden_layers",
         "layer_types", "vocab_size", "max_position_embeddings", "rope_theta",
         "norm_eps", "conv_L_cache", "num_dense_layers", "num_experts",
         "num_experts_per_tok", "routed_scaling_factor")
# published keys it builds at one value only: no bias in the convolution,
# gates renormalised over the chosen, a bias in the choice
ONLY = (("model_type", "lfm2_moe"), ("conv_bias", False),
        ("norm_topk_prob", True), ("use_expert_bias", True))
LAYER_KINDS = {"conv": "conv", "full_attention": "full"}
# keys of the harness's own that its list of them does not have
OWN_HARNESS = ("correct_tolerance_readings", "correct_routing_tie")
# what ``config.json`` (as the catalog keeps it) has no key for: a
# configuration states each under ``assumed``, and only these values build
# (``head_dim``: hidden_size / num_attention_heads, whatever the sizes)
ASSUMED = {
    "torch_dtype": "bfloat16",
    "tie_word_embeddings": True,
    "gate_renormalisation_eps": 1e-6,
    "conv_tail_dtype": "the_models_own",
    "residual_dtype": "float32_wider_than_stated",
    "router": "sigmoid_bias_in_choice_only_float32",
    "seeded_weights": "every matrix normal at sqrt(2 / (fan_in + fan_out)), "
                      "each third of the mixer's in-projection at the D x D "
                      "scale; the tied table normal at 0.02; conv taps "
                      "uniform +- 1/sqrt(conv_L_cache); q and k norm weights "
                      "1; expert bias normal x 0.02"}
# ``assumed.torch_dtype``: the model's own type, and float32 for the CPU
# rehearsal's tiny sizes alone (exact against the reference: the rehearsal
# holds the WALK, the chip the precision)
DTYPES = ("bfloat16", "float32")
# the fields the program's model config needs for this family
DESCRIPTORS = ("layer_pattern", "conv_taps", "qk_norm", "moe_dense_layers",
               "moe_routed", "moe_select_bias")

# the kernel whose calls count decode steps: the paged attention kernel, one
# call an attention layer; the held experts' kernel (``tpu9.ops.held_ffn``)
# runs once an expert layer. The mixers run no kernel of their own
STEP_MARKER = "paged_decode_attention"
EXPERT_STEP_KERNEL = "held_ffn"
# the mixers' scopes (``tpu9.models.shortconv.CONV_SCOPES``): the two
# projections, the gates and the taps
CONV_SCOPES = ("attn.conv.proj", "attn.conv.mix")
# the three decode shares: the mixers are attention
SCOPE_GROUPS = {
    "kv_pool": ("kv.slice", "kv.write", "kv.pack", "kv.gather", "kv.splice"),
    "attention": ("attn.core",) + CONV_SCOPES,
    "ffn": ("ffn", "moe.route", "moe.experts", "moe.combine"),
}


def model_sizes(config: dict) -> dict:
    """The sizes the reference and the program need. Refuses, before
    anything is started, a key or value this family does not build and a
    program that cannot run a list of short convolutions."""
    # as the looped family: read from the program's source, because the
    # driver tries a new cell on the parent commit under THESE files, and
    # that run has to fail at once, in the harness's own process
    lacks = [f for f in DESCRIPTORS if f not in looped._program_fields()]
    if lacks:
        raise ValueError(f"the program's DecoderConfig has no {lacks}: it "
                         "cannot run a layer pattern of gated short "
                         "convolutions, or attention under a norm a head")
    known = SIZES + OWN_HARNESS + tuple(k for k, _ in ONLY) \
        + manifest.HARNESS_KEYS
    for key in config:
        if key not in known:
            raise ValueError(f"{key}={config[key]!r}: the lfm2 family does "
                             "not build this key")
    for key, want in ONLY:
        if key not in config or config[key] != want:
            raise ValueError(f"{key}={config.get(key)!r}: the lfm2 family "
                             f"builds only {want!r}")
    assumed = {k: v["value"] for k, v in config.get("assumed", {}).items()}
    if set(assumed) != set(ASSUMED) | {"head_dim"}:
        raise ValueError("assumed: the lfm2 family builds exactly "
                         f"{sorted(set(ASSUMED) | {'head_dim'})}, the file "
                         f"states {sorted(assumed)}")
    for key, value in assumed.items():
        if key != "head_dim" and value != ASSUMED[key] and not (
                key == "torch_dtype" and value in DTYPES):
            raise ValueError(f"assumed {key}={value!r}: the lfm2 family "
                             f"builds only {ASSUMED[key]!r}")
    model = {k: config[k] for k in SIZES}
    layers, kinds = model["num_hidden_layers"], list(model["layer_types"])
    if len(kinds) != layers or any(k not in LAYER_KINDS for k in kinds) \
            or set(kinds) != set(LAYER_KINDS):
        raise ValueError(f"layer_types: {layers} entries of "
                         f"{sorted(LAYER_KINDS)}, both kinds present")
    heads = model["num_attention_heads"]
    if model["hidden_size"] % heads \
            or assumed["head_dim"] != model["hidden_size"] // heads:
        raise ValueError(f"assumed head_dim={assumed['head_dim']!r}: "
                         "hidden_size / num_attention_heads only")
    model["head_dim"] = assumed["head_dim"]
    if model["conv_L_cache"] < 2 \
            or not 0 <= model["num_dense_layers"] < layers \
            or not 0 < model["num_experts_per_tok"] <= model["num_experts"]:
        raise ValueError("conv_L_cache / num_dense_layers / "
                         "num_experts_per_tok: at least 2 taps, a leading "
                         "run of dense layers with an expert layer behind "
                         "it, a token picks some of the experts")
    # a stage of a pipeline holds the FIRST layers of the published list,
    # every layer whole on its chip
    stage = config["deployment"]
    if stage["chips_sharing_a_layer"] != 1 or stage["stage"] != 0 \
            or not 0 < layers <= stage["num_hidden_layers_published"]:
        raise ValueError(f"deployment={stage}: stage 0 of a pipeline whose "
                         "layers are whole on their chip is what is built "
                         "(the embedding and the first layers of the list)")
    model["experts_routed"] = model["num_experts"]
    model["experts_held"] = [0, model["num_experts"]]
    model["norm_topk_prob"] = True
    model["routed_scaling_factor"] = float(model["routed_scaling_factor"])
    model["torch_dtype"] = assumed["torch_dtype"]
    model["routing_tie"] = float(config["correct_routing_tie"])
    return model


def layer_kinds(model: dict) -> list:
    """``[(mixer, ffn)]`` a layer: the program's kind of the mixer as
    ``layer_types`` lists it, and ``"dense"`` below ``num_dense_layers``."""
    return [(LAYER_KINDS[k],
             "dense" if l < model["num_dense_layers"] else "experts")
            for l, k in enumerate(model["layer_types"])]


def program_config(model: dict):
    """The program's model config. Building it is also where the process
    that will run the program connects the reference to the program's own
    record of the experts it served each sequence with (the hybrid-linear
    family's door, ``reference/served_routing.py``)."""
    import jax.numpy as jnp

    from benchmark.reference import served_routing
    from tpu9.models import kvstate
    from tpu9.models.transformer import DecoderConfig
    from tpu9.serving import routed_experts
    served_routing.provider = routed_experts.records
    cfg = DecoderConfig(
        dtype=getattr(jnp, model["torch_dtype"]),
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        hidden_dim=model["intermediate_size"], norm_eps=model["norm_eps"],
        rope_theta=model["rope_theta"],
        max_seq_len=model["max_position_embeddings"], act="silu",
        tie_embeddings=True,
        layer_pattern=tuple(k for k, _ in layer_kinds(model)),
        conv_taps=model["conv_L_cache"], qk_norm=True,
        n_experts=model["num_experts"], moe_routed=model["num_experts"],
        moe_top_k=model["num_experts_per_tok"],
        moe_hidden_dim=model["moe_intermediate_size"],
        moe_dense_layers=model["num_dense_layers"], moe_score="sigmoid",
        moe_select_bias=True, moe_renormalise=True,
        moe_gate_scale=model["routed_scaling_factor"])
    # ``assumed.conv_tail_dtype``: the tail is the model's own type, a lane
    # and a block: a program that keeps either narrower is refused here
    kept = {**kvstate.lane_shapes(cfg, 1), **kvstate.block_tail_shapes(cfg, 1)}
    if set(kept) != {"conv_tail", "conv_block_tail"} or any(
            jnp.dtype(dt) != jnp.dtype(cfg.dtype) for _, dt in kept.values()):
        raise ValueError(f"the program keeps {kept}: this configuration's "
                         "state is one tail a lane and one a block, in the "
                         "model's own type")
    return cfg


def marker_calls_per_step(model: dict) -> int:
    return sum(1 for mixer, _ in layer_kinds(model) if mixer == "full")


def matmul_params(model: dict) -> dict:
    """Parameters of the matrices one token passes through, by part."""
    d, heads, kv = model["hidden_size"], model["num_attention_heads"], \
        model["num_key_value_heads"]
    hd = model["head_dim"]
    return {"conv": 3 * d * d + d * d,
            "full": 2 * d * heads * hd + 2 * d * kv * hd,
            "dense": 3 * d * model["intermediate_size"],
            "expert": 3 * d * model["moe_intermediate_size"],
            "router": d * model["num_experts"],
            "head": d * model["vocab_size"]}


def tail_bytes_per_lane(model: dict) -> int:
    """A lane's state, one mixer: the last ``conv_L_cache - 1`` rows of the
    product it convolves, bf16."""
    return (model["conv_L_cache"] - 1) * model["hidden_size"] * BF16


def kv_row_bytes(model: dict) -> int:
    """Bytes of one context token's keys and values in one attention layer."""
    return 2 * model["num_key_value_heads"] * model["head_dim"] * BF16


def decode_bytes_per_step(model: dict, batch: float,
                          resident_context: float) -> float:
    """Bytes one decode step has to move, whole model: every matrix a token
    of the batch passes through at its stored width (bf16; the router, its
    bias, the taps and the norms float32) — of the experts those the batch's
    picks touch under uniform routing — the tail of every live lane READ AND
    WRITTEN in every mixer, and the keys and values of every resident
    context token in the attention layers' planes. The head is the embedding
    table (tied), read once; the embedding gather (``batch`` rows) is left
    out."""
    p = matmul_params(model)
    d = model["hidden_size"]
    total = p["head"] * BF16 + d * F32
    for mixer, ffn in layer_kinds(model):
        total += p[mixer] * BF16 + 2 * d * F32            # and the two norms
        if mixer == "conv":
            total += model["conv_L_cache"] * d * F32 \
                + 2 * batch * tail_bytes_per_lane(model)
        else:
            total += 2 * model["head_dim"] * F32 \
                + kv_row_bytes(model) * resident_context
        if ffn == "experts":
            total += experts_touched(model, batch) * p["expert"] * BF16 \
                + (p["router"] + model["num_experts"]) * F32
        else:
            total += p["dense"] * BF16
    return total


def prefill_flops_per_token(model: dict) -> float:
    """Matmul FLOPs one prompt token needs: 2 per parameter it passes
    through — the mixer's or the attention's projections, then the dense
    SwiGLU or the router and its ``num_experts_per_tok`` picks — and the tied
    table once, as the head: the chunk programs compute the logits of every
    row they are fed (968 M active parameters at the published sizes, the
    table 134 M of them). The taps and the attention scores are not counted:
    a lower bound."""
    p = matmul_params(model)
    total = float(p["head"])
    for mixer, ffn in layer_kinds(model):
        total += p[mixer] + (p["dense"] if ffn == "dense" else
                             model["num_experts_per_tok"] * p["expert"]
                             + p["router"])
    return 2.0 * total


def expert_kernel_bytes(model: dict, touched: float, batch: float) -> float:
    """Bytes ONE call of the held experts' decode kernel needs: the three
    matrices of every TOUCHED expert once (``touched`` of them, a number the
    program counts), the ``batch`` rows in (bf16) and their float32 sum
    out."""
    return touched * matmul_params(model)["expert"] * BF16 \
        + batch * model["hidden_size"] * (BF16 + F32)


def kernel_cost(kernel: str, model: dict, engine: dict, batch: float,
                resident_context: float, touched=None):
    """``{"bytes", "flops"}`` one decode step NEEDS, whole model. Of the
    paged attention kernel: every resident token's keys and values once an
    attention layer (the pool keeps two 64-wide heads a row: the bytes are
    the heads' own); the scores and the weighted sum 2 x 2 x heads x
    head_dim a token. Of the held experts' kernel (``held_ffn``):
    :func:`expert_kernel_bytes` an expert layer, at ``touched`` experts a
    layer (the program's own count where the reader has it, else uniform
    routing's expectation); 2 operations a parameter of a touched expert and
    row."""
    kinds = layer_kinds(model)
    if kernel == STEP_MARKER:
        planes = marker_calls_per_step(model)
        return {"bytes": planes * kv_row_bytes(model) * resident_context,
                "flops": planes * 4.0 * model["num_attention_heads"]
                * model["head_dim"] * resident_context}
    if kernel == EXPERT_STEP_KERNEL:
        layers = sum(1 for _, f in kinds if f == "experts")
        if touched is None:
            touched = experts_touched(model, batch)
        return {"bytes": layers * expert_kernel_bytes(model, touched, batch),
                "flops": layers * touched * batch * 2.0
                * matmul_params(model)["expert"]}
    return None
