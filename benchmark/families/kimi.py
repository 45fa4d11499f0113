"""The Kimi-K2 family (``model_type: kimi_k2``: the DeepSeek-V3 block): latent
attention (MLA) in EVERY layer — a low-rank query with a norm of its own,
YaRN positions with the attention temperature in the softmax scale, no output
gate — over a cache of one 576-wide row a token, which the prefix cache
shares like any paged rows (no layer keeps state a lane); a dense SwiGLU in
the leading layers, then sigmoid-routed experts with a selection-only bias,
no group limit and one shared expert, of which this chip holds its SHARE
(``deployment``): the router keeps its published width, the chip the experts
``chip x n_routed_experts`` onwards and the rows of the vocabulary that
``deployment.vocab_rows`` names (``[first, count]``: ``vocab_size`` stays the
published number; the program, the reference and the traffic see ``count``
rows).

Everything the harness knows about this architecture is here: which
published keys it builds and at which values (every other key or value is a
``ValueError``), what is assumed (each under ``assumed`` in the
configuration's file, and only these values build), the program's model
config, what a step and a kernel need in bytes and operations, the kernel
whose calls count decode steps, and the scope groups. It imports the looped
family for nothing but the reader of the program's fields.

``correct`` for this family is decided on the routing the program SERVED, as
the hybrid-linear family's is (``families/ling.py`` says why):
``program_config`` hands the program's record of the experts each sequence
chose (``tpu9.serving.routed_experts``) to the reference's door
(``reference/served_routing.py``), and ``reference/kimi.py`` takes a served
choice where it is a tie within ``correct_routing_tie`` by its own float32
scores and nowhere else.
"""

from __future__ import annotations

from benchmark import manifest
from benchmark.families import looped
from benchmark.peaks import BF16, F32

# published keys this family builds as sizes
SIZES = ("hidden_size", "intermediate_size", "num_attention_heads",
         "num_key_value_heads", "num_hidden_layers", "vocab_size",
         "max_position_embeddings", "rope_theta", "rms_norm_eps",
         "first_k_dense_replace", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "n_routed_experts", "num_experts_per_tok", "routed_scaling_factor",
         "moe_intermediate_size", "rope_scaling")
# published keys it builds at one value only: no bias in attention, an
# expert layer in every layer after the leading ones, one shared expert (as
# wide as a routed one), NO group limit, renormalised sigmoid gates with the
# selection-only bias of ``noaux_tc``, no multi-token-prediction layer
ONLY = (("attention_bias", False), ("hidden_act", "silu"),
        ("tie_word_embeddings", False), ("moe_layer_freq", 1),
        ("n_shared_experts", 1), ("n_group", 1), ("topk_group", 1),
        ("norm_topk_prob", True), ("scoring_func", "sigmoid"),
        ("topk_method", "noaux_tc"), ("num_nextn_predict_layers", 0),
        ("model_type", "kimi_k2"))
# published keys that say nothing about the shape of what is served: the
# training losses' switches, and the checkpoint's own expert-parallel degree
# (1: the file holds every expert; the deployment here is ``deployment``'s)
SHAPELESS = ("seq_aux", "tf_legacy_loss", "ep_size")
# ``rope_scaling``: YaRN with these keys, copied whole
YARN_KEYS = ("beta_fast", "beta_slow", "factor", "mscale", "mscale_all_dim",
             "original_max_position_embeddings", "type")
# keys of the harness's own that its list of them does not have
OWN_HARNESS = ("correct_tolerance_readings", "correct_routing_tie")
# what ``config.json`` has no key for: a configuration states each under
# ``assumed``, and only these values build
ASSUMED = {
    "torch_dtype": "bfloat16",
    "rotary_form": "half_split",
    "norms": "pre-norm on both halves of a layer; RMSNorm of the query "
             "latent and of the kv latent alone; a final norm",
    "shared_expert_width": "n_shared_experts x moe_intermediate_size",
    "seeded_weights": "expert bias normal x 0.02"}
# ``assumed.torch_dtype``: the model's own type, and float32 for the CPU
# rehearsal's tiny sizes alone (exact against the reference, so that the
# rehearsal holds the WALK — pages spliced, a prefix gathered — to the
# comparison that decides ``correct``); the precision is the chip's to hold
DTYPES = ("bfloat16", "float32")
# the fields the program's model config needs for this family
DESCRIPTORS = ("layer_group", "mla_latent", "mla_q_latent", "mla_out_gate",
               "mla_mscale", "rope_yarn", "moe_routed", "moe_held_first",
               "moe_shared_dim", "moe_score")

# the kernel whose calls count decode steps, and whose bytes grow with the
# resident context: latent attention's decode kernel, one call a layer (the
# name ``tpu9.ops.latent_attention.LATENT_KERNEL`` gives it)
STEP_MARKER = "paged_latent_attention"
# the blocked prefill's kernel (``tpu9.ops.latent_attention.PREFILL_KERNEL``)
PREFILL_KERNEL = "latent_prefill_attention"
# latent attention's scopes (prefixes of scope names): the expansions of the
# latent (absorbed into query and output at decode) / its attention, in the
# decode step and in the blocked prefill alike; the query's low-rank path
MLA_SCOPES = ("attn.mla.absorb", "attn.mla.core")
MLA_QUERY_SCOPE = "attn.mla.q"
# the three decode shares: all of latent attention is attention (its query
# path with it), the shared expert ffn
SCOPE_GROUPS = {
    "kv_pool": ("kv.slice", "kv.write", "kv.pack", "kv.gather", "kv.splice"),
    "attention": ("attn.core", MLA_QUERY_SCOPE) + MLA_SCOPES,
    "ffn": ("ffn", "moe.route", "moe.experts", "moe.combine", "moe.shared"),
}


def model_sizes(config: dict) -> dict:
    """The sizes the reference and the program need. Refuses, before
    anything is started, a key or value this family does not build and a
    program that cannot run latent attention in every layer."""
    # as the looped family: read from the program's source, because the
    # driver tries a new cell on the parent commit under THESE files, and
    # that run has to fail at once, in the harness's own process
    lacks = [f for f in DESCRIPTORS if f not in looped._program_fields()]
    if lacks:
        raise ValueError(f"the program's DecoderConfig has no {lacks}: it "
                         "cannot run latent attention in every layer with a "
                         "query latent, YaRN positions and a chip's share of "
                         "the experts")
    known = SIZES + SHAPELESS + OWN_HARNESS + tuple(k for k, _ in ONLY) \
        + manifest.HARNESS_KEYS
    for key in config:
        if key not in known:
            raise ValueError(f"{key}={config[key]!r}: the kimi family does "
                             "not build this key")
    for key, want in ONLY:
        if key not in config or config[key] != want:
            raise ValueError(f"{key}={config.get(key)!r}: the kimi family "
                             f"builds only {want!r}")
    assumed = {k: v["value"] for k, v in config.get("assumed", {}).items()}
    if set(assumed) != set(ASSUMED):
        raise ValueError("assumed: the kimi family builds exactly "
                         f"{sorted(ASSUMED)}, the file states "
                         f"{sorted(assumed)}")
    for key, value in assumed.items():
        if value != ASSUMED[key] and not (key == "torch_dtype"
                                          and value in DTYPES):
            raise ValueError(f"assumed {key}={value!r}: the kimi family "
                             f"builds only {ASSUMED[key]!r}")
    model = {k: config[k] for k in SIZES}
    yarn = model["rope_scaling"]
    if not isinstance(yarn, dict) or sorted(yarn) != sorted(YARN_KEYS) \
            or yarn["type"] != "yarn" \
            or yarn["mscale"] != yarn["mscale_all_dim"]:
        raise ValueError(f"rope_scaling={yarn!r}: the kimi family builds "
                         f"YaRN with exactly {sorted(YARN_KEYS)} and mscale "
                         "= mscale_all_dim (cos and sin unscaled)")
    if model["num_key_value_heads"] != model["num_attention_heads"]:
        raise ValueError("num_key_value_heads: as many as query heads only")
    layers = model["num_hidden_layers"]
    if not 0 < model["first_k_dense_replace"] < layers:
        raise ValueError("num_hidden_layers / first_k_dense_replace: a "
                         "leading dense layer and an expert layer")
    share = config["deployment"]
    chips, chip = share["chips_sharing_a_layer"], share["chip"]
    model["experts_routed"] = model["n_routed_experts"] * chips
    if model["experts_routed"] != share["n_routed_experts_published"]:
        raise ValueError("deployment: n_routed_experts x "
                         "chips_sharing_a_layer is the published count")
    model["experts_held"] = [model["n_routed_experts"] * chip,
                             model["n_routed_experts"]]
    # the chip's slice of the vocabulary: what everything downstream calls
    # the vocabulary. The vocabulary is cut ``vocab_shards`` ways and each
    # slice replicated over the chips that share it
    first, rows = share["vocab_rows"]
    shards = share["vocab_shards"]
    if chips % shards or rows * shards != model["vocab_size"] \
            or first != (chip % shards) * rows:
        raise ValueError(f"deployment.vocab_rows={share['vocab_rows']}: "
                         f"chip {chip} holds an even share (1 of {shards}) "
                         f"of the {model['vocab_size']} published rows")
    model["vocab_size_published"], model["vocab_size"] = \
        model["vocab_size"], rows
    model["norm_topk_prob"] = True
    model["moe_shared_expert_intermediate_size"] = \
        config["n_shared_experts"] * model["moe_intermediate_size"]
    model["torch_dtype"] = assumed["torch_dtype"]
    model["routing_tie"] = float(config["correct_routing_tie"])
    return model


def layer_kinds(model: dict) -> list:
    """``[(attention, ffn)]`` a layer: latent attention in every one."""
    return [("mla", "experts" if l >= model["first_k_dense_replace"]
             else "dense") for l in range(model["num_hidden_layers"])]


def program_config(model: dict):
    """The program's model config. Building it is also where the process
    that will run the program connects the reference to the program's own
    record of the experts it served each sequence with (as
    ``families/ling.py``)."""
    import jax.numpy as jnp

    from benchmark.reference import served_routing
    from tpu9.models.transformer import DecoderConfig
    from tpu9.ops.rotary import yarn_mscale
    from tpu9.serving import routed_experts
    served_routing.provider = routed_experts.records
    first, held = model["experts_held"]
    yarn = model["rope_scaling"]
    return DecoderConfig(
        dtype=getattr(jnp, model["torch_dtype"]),
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        # no layer reads it (a KDA layer's width): the value's, for the
        # engine's own estimates
        head_dim=model["v_head_dim"],
        hidden_dim=model["intermediate_size"],
        norm_eps=model["rms_norm_eps"], rope_theta=model["rope_theta"],
        max_seq_len=model["max_position_embeddings"], act="silu",
        tie_embeddings=False,
        # a group of one layer: latent attention in every layer
        layer_group=1,
        mla_latent=model["kv_lora_rank"], mla_nope=model["qk_nope_head_dim"],
        mla_rope=model["qk_rope_head_dim"], mla_v=model["v_head_dim"],
        mla_q_latent=model["q_lora_rank"], mla_out_gate=False,
        mla_mscale=yarn_mscale(yarn["factor"], yarn["mscale_all_dim"]),
        rope_yarn=(float(yarn["factor"]),
                   int(yarn["original_max_position_embeddings"]),
                   float(yarn["beta_fast"]), float(yarn["beta_slow"])),
        n_experts=held, moe_top_k=model["num_experts_per_tok"],
        moe_dense_layers=model["first_k_dense_replace"],
        moe_hidden_dim=model["moe_intermediate_size"],
        moe_routed=model["experts_routed"], moe_held_first=first,
        moe_shared_dim=model["moe_shared_expert_intermediate_size"],
        moe_score="sigmoid", moe_select_bias=True,
        moe_groups=0, moe_top_groups=0, moe_renormalise=True,
        moe_gate_scale=float(model["routed_scaling_factor"]))


def marker_calls_per_step(model: dict) -> int:
    return model["num_hidden_layers"]


def matmul_params(model: dict) -> dict:
    """Parameters of the matrices one token passes through, by part."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    dn, dr = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    dv, dc, dq = model["v_head_dim"], model["kv_lora_rank"], \
        model["q_lora_rank"]
    return {"mla": d * dq + dq * h * (dn + dr) + d * (dc + dr)
            + dc * h * (dn + dv) + h * dv * d,
            "dense": 3 * d * model["intermediate_size"],
            "expert": 3 * d * model["moe_intermediate_size"],
            "shared": 3 * d * model["moe_shared_expert_intermediate_size"],
            "router": d * model["experts_routed"],
            "head": d * model["vocab_size"]}


def experts_touched(model: dict, batch: float) -> float:
    """Expected number of HELD experts that ``batch`` tokens reach in one
    layer under uniform routing: each token picks k of the routed E, so a
    held expert is missed by all with (1 - k/E)^batch."""
    k, routed = model["num_experts_per_tok"], model["experts_routed"]
    return model["experts_held"][1] \
        * (1.0 - (1.0 - k / routed) ** max(batch, 0.0))


def latent_row_bytes(model: dict) -> int:
    return (model["kv_lora_rank"] + model["qk_rope_head_dim"]) * BF16


def decode_bytes_per_step(model: dict, batch: float,
                          resident_context: float) -> float:
    """Bytes one decode step has to move, whole model: every matrix a token
    of the batch passes through at its stored width (bf16; the router, its
    bias and the norms float32) — of the held experts those the batch's
    picks touch — and the latent row of every resident context token in
    every layer. The embedding gather (``batch`` rows) is left out."""
    p = matmul_params(model)
    d = model["hidden_size"]
    total = p["head"] * BF16 + d * F32
    for _, ffn in layer_kinds(model):
        total += 2 * d * F32 + p["mla"] * BF16 \
            + (model["q_lora_rank"] + model["kv_lora_rank"]) * F32 \
            + latent_row_bytes(model) * resident_context
        if ffn == "dense":
            total += p["dense"] * BF16
        else:
            total += (experts_touched(model, batch) * p["expert"]
                      + p["shared"]) * BF16 \
                + (p["router"] + model["experts_routed"]) * F32
    return total


def prefill_flops_per_token(model: dict) -> float:
    """Matmul FLOPs one prompt token needs: 2 per parameter it passes
    through — latent attention's projections, the dense FFN, or the router,
    the shared expert and the HELD share of its picks (``k x held /
    routed``: 8 x 12 / 384 = a quarter of an expert). The attention over the
    cache (``kernel_cost`` of the prefill kernel) and the head are not
    counted: a lower bound."""
    p = matmul_params(model)
    picks_here = model["num_experts_per_tok"] * model["experts_held"][1] \
        / model["experts_routed"]
    total = 0.0
    for _, ffn in layer_kinds(model):
        total += p["mla"]
        total += p["dense"] if ffn == "dense" else \
            picks_here * p["expert"] + p["shared"] + p["router"]
    return 2.0 * total


def kernel_cost(kernel: str, model: dict, engine: dict, batch: float,
                resident_context: float):
    """``{"bytes", "flops"}`` NEEDED, whole model (every layer), of latent
    attention.

    A decode step (``attn.mla*`` or its kernel, ``paged_latent_attention``):
    every resident token's latent row — 512 latent and 64 rotary numbers —
    once a layer; scores over 576 and the weighted sum over 512 numbers a
    head and token. ``resident_context`` is the rows the live lanes attend.

    The blocked prefill (``latent_prefill_attention``): ``resident_context``
    is the cache rows the dispatches attended (each dispatch's own rows
    included) and ``batch`` the (query, row) pairs their causal masks let
    through. A row's keys and values are made once a dispatch (2 x 512
    latent numbers x 256 a head), a pair costs a score over 192 numbers and
    a weighted value of 128 a head; the bytes are the rows' latents, once a
    dispatch. What the dispatches need, not what the kernel does: it makes a
    row's keys and values again for every query tile, and multiplies the
    block the diagonal crosses whole."""
    layers = model["num_hidden_layers"]
    h = model["num_attention_heads"]
    dn, dr = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    dv, dc = model["v_head_dim"], model["kv_lora_rank"]
    if kernel == PREFILL_KERNEL:
        return {"bytes": layers * latent_row_bytes(model) * resident_context,
                "flops": layers * 2.0 * h * (
                    dc * (dn + dv) * resident_context
                    + (dn + dr + dv) * batch)}
    if kernel.startswith("attn.mla") or kernel == STEP_MARKER:
        return {"bytes": layers * latent_row_bytes(model) * resident_context,
                "flops": layers * 2.0 * h * (2 * dc + dr) * resident_context}
    return None
