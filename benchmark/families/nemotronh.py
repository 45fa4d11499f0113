"""The hybrid Mamba-2 / LatentMoE family (``model_type: nemotron_h``,
Nemotron 3): a layer pattern given as a STRING (``hybrid_override_pattern``)
in which every layer is ONE sub-layer — ``M`` a Mamba-2 state-space mixer
over a float32 state a LANE that no cache holds, ``*`` grouped-query
attention WITHOUT positions over per-head rows of a pool only as deep as
there are such layers, ``E`` an expert layer alone — each ``x + f(norm(x))``
with one norm. The expert layer is a LatentMoE: sigmoid-routed experts with
a selection-only bias, ungated (two matrices, ``relu(.) ** 2``), working in a
latent of ``moe_latent_size`` numbers behind one shared projection down and
in front of one up, beside one shared expert at the full width; of the
routed experts this chip holds its SHARE (``deployment``): the router keeps
its published width, the chip the experts ``chip x n_routed_experts``
onwards and the rows of the vocabulary that ``deployment.vocab_rows`` names
(``[first, count]``: ``vocab_size`` stays the published number; the program,
the reference and the traffic see ``count`` rows).

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
its own float32 scores and nowhere else (``reference/nemotronh.py``).
"""

from __future__ import annotations

from benchmark import manifest
from benchmark.families import looped
# (the expected number of HELD experts a batch's picks reach under uniform
# routing: the hybrid-linear family's, over the same three sizes)
from benchmark.families.ling import experts_touched
from benchmark.peaks import BF16, F32

# published keys this family builds as sizes
SIZES = ("hidden_size", "head_dim", "num_attention_heads",
         "num_key_value_heads", "num_hidden_layers",
         "hybrid_override_pattern", "vocab_size", "max_position_embeddings",
         "layer_norm_epsilon", "mamba_num_heads", "mamba_head_dim",
         "ssm_state_size", "n_groups", "conv_kernel", "expand", "chunk_size",
         "n_routed_experts", "num_experts_per_tok", "moe_intermediate_size",
         "moe_latent_size", "moe_shared_expert_intermediate_size",
         "routed_scaling_factor")
# published keys it builds at one value only: no biases but the
# convolution's, silu in the mixer and relu2 in the experts, no group limit
# on the choice, one shared expert beside (not overlapped with) the routed
# ones, gates renormalised, no window, an untied head, and NO prediction
# module (``num_nextn_predict_layers`` 0: the shared-weight ``*E`` draft head
# is self-speculation, which needs verify beside state a lane — refused by
# the engine; the main model's logits do not depend on it)
ONLY = (("model_type", "nemotron_h"), ("attention_bias", False),
        ("mamba_proj_bias", False), ("mlp_bias", False), ("use_bias", False),
        ("use_conv_bias", True), ("mamba_hidden_act", "silu"),
        ("mlp_hidden_act", "relu2"), ("n_group", 1), ("topk_group", 1),
        ("n_shared_experts", 1), ("moe_shared_expert_overlap", False),
        ("norm_topk_prob", True), ("sliding_window", None),
        ("tie_word_embeddings", False), ("num_nextn_predict_layers", 0),
        ("residual_in_fp32", False))
# published keys that nothing run here reads, each for its reason: the
# attention applies no rotary embedding (``assumed.positions``); the dense
# feed-forward width is that of ``-`` layers, of which the pattern kept has
# none; the time-step keys and ``rescale_prenorm_residual`` describe an
# INITIALISATION (the seeded one is ``assumed.seeded_weights``);
# ``num_logits_to_keep`` and ``use_mamba_kernels`` are switches of the public
# code's own forward; the prediction module's pattern goes with
# ``num_nextn_predict_layers`` 0; ``norm_eps`` repeats ``layer_norm_epsilon``
READ_BY_NOTHING = ("rope_theta", "partial_rotary_factor", "intermediate_size",
                   "time_step_floor", "time_step_max", "time_step_min",
                   "rescale_prenorm_residual", "num_logits_to_keep",
                   "use_mamba_kernels", "mtp_hybrid_override_pattern",
                   "norm_eps")
# the pattern's characters: (attention kind, feed-forward kind) of the
# program's lists
LAYER_KINDS = {"M": ("ssm", "none"), "*": ("full", "none"),
               "E": ("none", "experts")}
# keys of the harness's own that its list of them does not have
OWN_HARNESS = ("correct_tolerance_readings", "correct_routing_tie")
# what ``config.json`` has no key for: a configuration states each under
# ``assumed``, and only these values build
ASSUMED = {
    "torch_dtype": "bfloat16",
    "positions": "none",
    "ssm_norm_groups": "n_groups_gate_before_norm",
    "ssm_state_dtype": "float32",
    "residual_dtype": "float32_wider_than_stated",
    "dt_limits": "none",
    "router": "sigmoid_bias_in_choice_only_float32",
    "seeded_weights": "every matrix normal at sqrt(2 / (fan_in + fan_out)), "
                      "the embedding normal at 0.02, the head untied; expert "
                      "bias normal x 0.02; A_log = log(uniform[1, 16]); "
                      "dt_bias = inverse softplus of dt log-uniform over "
                      "[0.001, 0.1]; D = 1; conv taps and bias uniform +- "
                      "1/sqrt(conv_kernel)"}
# ``assumed.torch_dtype``: the model's own type, and float32 for the CPU
# rehearsal's tiny sizes alone (exact against the reference: the rehearsal
# holds the WALK, the chip the precision)
DTYPES = ("bfloat16", "float32")
# the fields the program's model config needs for this family
DESCRIPTORS = ("layer_pattern", "ffn_pattern", "ssm_norm_groups",
               "moe_gated", "moe_latent_dim", "moe_routed", "moe_held_first",
               "moe_shared_dim", "rope")

# the kernel whose calls count decode steps: the paged attention kernel, one
# call an attention layer; the state's step kernel
# (``tpu9.ops.ssd.STEP_KERNEL``) runs once a mixer, the held experts' kernel
# (``tpu9.ops.held_ffn``) once an expert layer
STEP_MARKER = "paged_decode_attention"
SSM_STEP_KERNEL = "ssm_state_step"
SSM_STATE_SCOPE = "attn.ssm.state"
EXPERT_STEP_KERNEL = "held_ffn"
# the scopes of the expert layer, each a part of ``ffn``
MOE_SCOPES = ("moe.route", "moe.latent.in", "moe.experts", "moe.latent.out",
              "moe.shared", "moe.combine")
# the three decode shares: the mixers are attention
SCOPE_GROUPS = {
    "kv_pool": ("kv.slice", "kv.write", "kv.pack", "kv.gather", "kv.splice"),
    "attention": ("attn.core", "attn.ssm.proj", SSM_STATE_SCOPE),
    "ffn": ("ffn",) + MOE_SCOPES,
}


def model_sizes(config: dict) -> dict:
    """The sizes the reference and the program need. Refuses, before
    anything is started, a key or value this family does not build and a
    program that cannot run a list of half-layers with a latent expert
    layer."""
    # as the looped family: read from the program's source, because the
    # driver tries a new cell on the parent commit under THESE files, and
    # that run has to fail at once, in the harness's own process
    lacks = [f for f in DESCRIPTORS if f not in looped._program_fields()]
    if lacks:
        raise ValueError(f"the program's DecoderConfig has no {lacks}: it "
                         "cannot run a listed pattern whose layers are one "
                         "half each, ungated experts in a latent, or the "
                         "mixer's grouped norm")
    known = SIZES + READ_BY_NOTHING + OWN_HARNESS \
        + tuple(k for k, _ in ONLY) + manifest.HARNESS_KEYS
    for key in config:
        if key not in known:
            raise ValueError(f"{key}={config[key]!r}: the nemotronh family "
                             "does not build this key")
    for key, want in ONLY:
        if key not in config or config[key] != want:
            raise ValueError(f"{key}={config.get(key)!r}: the nemotronh "
                             f"family builds only {want!r}")
    assumed = {k: v["value"] for k, v in config.get("assumed", {}).items()}
    if set(assumed) != set(ASSUMED):
        raise ValueError("assumed: the nemotronh family builds exactly "
                         f"{sorted(ASSUMED)}, the file states "
                         f"{sorted(assumed)}")
    for key, value in assumed.items():
        if value != ASSUMED[key] and not (key == "torch_dtype"
                                          and value in DTYPES):
            raise ValueError(f"assumed {key}={value!r}: the nemotronh "
                             f"family builds only {ASSUMED[key]!r}")
    model = {k: config[k] for k in SIZES}
    if config.get("norm_eps", model["layer_norm_epsilon"]) \
            != model["layer_norm_epsilon"]:
        raise ValueError("norm_eps: the one epsilon is layer_norm_epsilon, "
                         "and both keys state it")
    layers, pattern = model["num_hidden_layers"], \
        model["hybrid_override_pattern"]
    if len(pattern) != layers or set(pattern) != set(LAYER_KINDS):
        raise ValueError(f"hybrid_override_pattern={pattern!r}: "
                         f"{layers} characters of {sorted(LAYER_KINDS)}, "
                         "every kind present (a dense '-' layer is not "
                         "built)")
    if model["mamba_num_heads"] * model["mamba_head_dim"] \
            != model["expand"] * model["hidden_size"]:
        raise ValueError("mamba_num_heads x mamba_head_dim must be expand x "
                         "hidden_size")
    if model["mamba_num_heads"] % model["n_groups"] \
            or model["chunk_size"] < 1 or model["conv_kernel"] < 2:
        raise ValueError("n_groups / chunk_size / conv_kernel: the groups "
                         "divide the mixer's heads, a block of the chunked "
                         "form, at least 2 taps")
    share = config["deployment"]
    chips, chip = share["chips_sharing_a_layer"], share["chip"]
    model["experts_routed"] = model["n_routed_experts"] * chips
    model["experts_held"] = [model["n_routed_experts"] * chip,
                             model["n_routed_experts"]]
    if model["experts_routed"] != share["n_routed_experts_published"] \
            or not 0 < model["num_experts_per_tok"] <= model["experts_routed"]:
        raise ValueError("deployment: the chips that share a layer hold the "
                         "published n_routed_experts between them, and a "
                         "token picks some of them")
    # the chip's slice of the vocabulary: what everything downstream calls
    # the vocabulary
    first, rows = share["vocab_rows"]
    if first != chip * rows or rows * chips != model["vocab_size"]:
        raise ValueError(f"deployment.vocab_rows={share['vocab_rows']}: chip "
                         f"{chip} of {chips} holds an even share of the "
                         f"{model['vocab_size']} published rows")
    model["vocab_size_published"], model["vocab_size"] = \
        model["vocab_size"], rows
    model["norm_topk_prob"] = True
    model["routed_scaling_factor"] = float(model["routed_scaling_factor"])
    model["torch_dtype"] = assumed["torch_dtype"]
    model["routing_tie"] = float(config["correct_routing_tie"])
    return model


def layer_kinds(model: dict) -> list:
    """``[(attention, ffn)]`` a layer, as the pattern's characters say."""
    return [LAYER_KINDS[c] for c in model["hybrid_override_pattern"]]


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
    first, held = model["experts_held"]
    kinds = layer_kinds(model)
    cfg = DecoderConfig(
        dtype=getattr(jnp, model["torch_dtype"]),
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        hidden_dim=model["moe_intermediate_size"],
        norm_eps=model["layer_norm_epsilon"],
        max_seq_len=model["max_position_embeddings"], act="relu2",
        tie_embeddings=False,
        layer_pattern=tuple(a for a, _ in kinds),
        ffn_pattern=tuple(f for _, f in kinds),
        ssm_heads=model["mamba_num_heads"],
        ssm_head_dim=model["mamba_head_dim"],
        ssm_state=model["ssm_state_size"], ssm_groups=model["n_groups"],
        ssm_conv=model["conv_kernel"], ssm_norm_groups=model["n_groups"],
        rope=False, n_experts=held, moe_top_k=model["num_experts_per_tok"],
        moe_hidden_dim=model["moe_intermediate_size"],
        moe_routed=model["experts_routed"], moe_held_first=first,
        moe_shared_dim=model["moe_shared_expert_intermediate_size"],
        moe_score="sigmoid", moe_select_bias=True, moe_renormalise=True,
        moe_gate_scale=model["routed_scaling_factor"], moe_gated=False,
        moe_latent_dim=model["moe_latent_size"])
    # ``assumed.ssm_state_dtype``: as the hybrid state-space family, a
    # program that keeps the lanes' state narrower is refused here
    kept = kvstate.lane_shapes(cfg, 1)["ssm_state"][1]
    if jnp.dtype(kept) != jnp.dtype(ASSUMED["ssm_state_dtype"]):
        raise ValueError(f"the program keeps the lanes' state in "
                         f"{jnp.dtype(kept).name}: this configuration's is "
                         f"{ASSUMED['ssm_state_dtype']}")
    return cfg


def marker_calls_per_step(model: dict) -> int:
    return sum(1 for a, _ in layer_kinds(model) if a == "full")


def conv_width(model: dict) -> int:
    return model["mamba_num_heads"] * model["mamba_head_dim"] \
        + 2 * model["n_groups"] * model["ssm_state_size"]


def matmul_params(model: dict) -> dict:
    """Parameters of the matrices one token passes through, by part."""
    d, heads, kv = model["hidden_size"], model["num_attention_heads"], \
        model["num_key_value_heads"]
    hd, latent = model["head_dim"], model["moe_latent_size"]
    inner = model["mamba_num_heads"] * model["mamba_head_dim"]
    return {"ssm": d * (inner + conv_width(model) + model["mamba_num_heads"])
            + inner * d,
            "full": 2 * d * heads * hd + 2 * d * kv * hd,
            "expert": 2 * latent * model["moe_intermediate_size"],
            "latent": 2 * d * latent,
            "shared": 2 * d * model["moe_shared_expert_intermediate_size"],
            "router": d * model["experts_routed"],
            "head": d * model["vocab_size"]}


def ssm_vector_params(model: dict) -> int:
    """The float32 vectors of one mixer: taps, bias, ``dt_bias``, ``A_log``,
    ``D``, the gated norm."""
    return (model["conv_kernel"] + 1) * conv_width(model) \
        + 3 * model["mamba_num_heads"] \
        + model["mamba_num_heads"] * model["mamba_head_dim"]


def state_elements(model: dict) -> int:
    """Numbers of a lane's state in ONE mixer."""
    return model["mamba_num_heads"] * model["mamba_head_dim"] \
        * model["ssm_state_size"]


def state_bytes_per_lane(model: dict) -> float:
    """A lane's state, one mixer: the float32 matrix a head, and the
    convolution's last inputs in bf16."""
    return state_elements(model) * F32 \
        + (model["conv_kernel"] - 1) * conv_width(model) * BF16


def kv_row_bytes(model: dict) -> int:
    """Bytes of one context token's keys and values in one attention layer."""
    return 2 * model["num_key_value_heads"] * model["head_dim"] * BF16


def decode_bytes_per_step(model: dict, batch: float,
                          resident_context: float) -> float:
    """Bytes one decode step has to move, whole model: every matrix a token
    of the batch passes through at its stored width (bf16; the router, its
    bias, the mixers' vectors and the norms float32) — of the held experts
    those the batch's picks touch under uniform routing — the state of every
    live lane READ AND WRITTEN in every mixer, and the keys and values of
    every resident context token in the attention layers' planes. The
    embedding gather (``batch`` rows) is left out."""
    p = matmul_params(model)
    d = model["hidden_size"]
    total = p["head"] * BF16 + d * F32
    for attention, ffn in layer_kinds(model):
        total += d * F32                                   # the one norm
        if attention == "ssm":
            total += p["ssm"] * BF16 + ssm_vector_params(model) * F32 \
                + 2 * batch * state_bytes_per_lane(model)
        elif attention == "full":
            total += p["full"] * BF16 \
                + kv_row_bytes(model) * resident_context
        if ffn == "experts":
            total += (experts_touched(model, batch) * p["expert"]
                      + p["latent"] + p["shared"]) * BF16 \
                + (p["router"] + model["experts_routed"]) * F32
    return total


def prefill_flops_per_token(model: dict) -> float:
    """Matmul FLOPs one prompt token needs: 2 per parameter it passes
    through — the mixer's or the attention's projections, or the router, the
    latent projections, the shared expert and the HELD share of its picks
    (``k x held / routed``: 22 x 128 / 512 = 5.5 experts). The recurrence,
    the attention scores and the head are not counted: a lower bound."""
    p = matmul_params(model)
    picks_here = model["num_experts_per_tok"] * model["experts_held"][1] \
        / model["experts_routed"]
    total = 0.0
    for attention, ffn in layer_kinds(model):
        if attention != "none":
            total += p[attention]
        if ffn == "experts":
            total += picks_here * p["expert"] + p["latent"] + p["shared"] \
                + p["router"]
    return 2.0 * total


def expert_kernel_bytes(model: dict, touched: float, batch: float) -> float:
    """Bytes ONE call of the held experts' decode kernel needs: the two
    matrices of every TOUCHED expert once (``touched`` of them, a number the
    program counts), the ``batch`` rows in (bf16) and their float32 sum out,
    both in the latent."""
    latent = model["moe_latent_size"]
    return touched * 2 * latent * model["moe_intermediate_size"] * BF16 \
        + batch * latent * (BF16 + F32)


def kernel_cost(kernel: str, model: dict, engine: dict, batch: float,
                resident_context: float, touched=None):
    """``{"bytes", "flops"}`` one decode step NEEDS, whole model. Of the
    state's step (``ssm_state_step`` or its scope): every LIVE lane's float32
    state read once and written once in every mixer; 5 operations a state
    element. Of the paged attention kernel: every resident token's keys and
    values once an attention layer. Of the held experts' kernel
    (``held_ffn``): :func:`expert_kernel_bytes` an expert layer, at
    ``touched`` experts a layer (the program's own count where the reader
    has it, else uniform routing's expectation); 2 x 2 operations a
    parameter of a touched expert and row."""
    kinds = layer_kinds(model)
    mixers = sum(1 for a, _ in kinds if a == "ssm")
    if kernel in (SSM_STEP_KERNEL, SSM_STATE_SCOPE):
        per_lane = state_elements(model)
        return {"bytes": mixers * batch * 2 * per_lane * F32,
                "flops": mixers * batch * 5.0 * per_lane}
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
