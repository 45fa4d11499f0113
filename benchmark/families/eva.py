"""The EvaByte family (``attention_class: "eva"``): the decoder family's
uniform pre-norm blocks of rotary multi-head attention and a SwiGLU
feed-forward, but attention is exact only inside a window of ``window_size``
positions and reads ONE summary for every ``chunk_size`` positions of every
earlier window; RMSNorm weights are ``1 + g``, the residual stream is
float32, the vocabulary is bytes.

What the decoder family says of shapes, bytes and operations holds here
wherever the cache's length does not enter; this file imports it and changes
the rest: a sequence of ``n`` tokens holds ``(W / C) (n // W) + n % W`` cache
ENTRIES, not ``n`` rows, and the accepted readers hand ``decode_bytes_per_
step`` and ``kernel_cost`` resident TOKENS, which are turned into entries
here (:func:`resident_entries`); and one more device operation has a count,
the summarise of a window that closes.
"""

from __future__ import annotations

from benchmark.families import decoder, looped

# published keys beyond the decoder family's: sizes, and those built at one
# value (a key of ``config.json`` that is missing reads as that value)
EVA_SIZES = ("window_size", "chunk_size", "max_seq_length", "num_pred_heads")
EVA_ONLY = (("attention_class", "eva"), ("attention_bias", False),
            ("fp32_ln", False), ("fp32_logits", True),
            ("fp32_skip_add", True), ("mixedp_attn", True),
            ("norm_add_unit_offset", True), ("num_chunks", None),
            ("rope_scaling", None), ("num_pred_heads", 1))
# published keys that say nothing about the shape of what is run: how a
# checkpoint was initialised (the weights here come from the seed)
EVA_SHAPELESS = ("model_type", "init_fn", "init_std", "init_cutoff_factor",
                 "lazy_init")
# a key of the harness's own that its list of them does not have: the
# readings the tolerance lies between, as numbers a test can hold it to
EVA_HARNESS = ("correct_tolerance_readings",)
# what ``config.json`` has no key for, from the modeling code published
# beside the checkpoint (``eva_prep_kv``; parameters ``adaptive_mu_k`` and
# ``adaptive_phi``): a configuration states each under ``assumed``, and only
# these values build
ASSUMED = {"head_dim": 128, "torch_dtype": "bfloat16",
           "chunk_summary": "softmax_pooled_keys_and_values",
           "summary_vectors_per_head": 2,
           "summary_vector_init": "normal_clipped_1_over_sqrt_head_dim"}
# the fields the program's model config needs for this family
DESCRIPTORS = ("attn_window", "attn_chunk")

_program_fields = looped._program_fields

STEP_MARKER = decoder.STEP_MARKER
# the decoder's three decode shares; the summarise of a closing window is
# apart (``eva_summarise_share``), so that the three stay the three
SCOPE_GROUPS = dict(decoder.SCOPE_GROUPS)
EVA_SCOPES = ("kv.summarise",)
# the name the summarise has in a trace: its scope, in every program
SUMMARISE = EVA_SCOPES[0]


def model_sizes(config: dict) -> dict:
    """The sizes the reference and the program need. Refuses, before
    anything is started, a key this family does not build and a program
    that cannot run this attention."""
    # as the looped family: read from the program's source, because the
    # driver tries a new cell on the parent commit under THESE files, and
    # that run has to fail at once, in the harness's own process
    lacks = [f for f in DESCRIPTORS if f not in _program_fields()]
    if lacks:
        raise ValueError(f"the program's DecoderConfig has no {lacks}: it "
                         "cannot run attention over window summaries "
                         "(attention_class \"eva\")")
    for key, want in EVA_ONLY:
        if config.get(key, want) != want:
            raise ValueError(f"{key}={config[key]!r}: the eva family builds "
                             f"only {want!r}")
    assumed = {k: v["value"] for k, v in config.get("assumed", {}).items()}
    for key, value in assumed.items():
        if key not in ASSUMED:
            raise ValueError(f"assumed {key}={value!r}: the eva family "
                             "does not build this key")
        if value != ASSUMED[key] and key != "head_dim":
            raise ValueError(f"assumed {key}={value!r}: the eva family "
                             f"builds only {ASSUMED[key]!r}")
    own = set(EVA_SIZES) | {k for k, _ in EVA_ONLY} | set(EVA_SHAPELESS) \
        | set(EVA_HARNESS)
    plain = {k: v for k, v in config.items() if k not in own}
    plain["assumed"] = {k: {"value": v} for k, v in assumed.items()
                        if k == "head_dim"}
    plain["torch_dtype"] = assumed.get("torch_dtype", "bfloat16")
    try:
        model = decoder.model_sizes(plain)
    except ValueError as exc:
        raise ValueError(str(exc).replace("decoder family",
                                          "eva family")) from None
    if model["num_local_experts"]:
        raise ValueError("num_local_experts: the eva family builds a dense "
                         "feed-forward only")
    if model["num_key_value_heads"] != model["num_attention_heads"]:
        raise ValueError(
            f"num_key_value_heads={model['num_key_value_heads']}: the eva "
            "family builds multi-head attention only (a summary vector a "
            "head)")
    window, chunk = int(config["window_size"]), int(config["chunk_size"])
    if window <= 0 or chunk <= 0 or window % chunk:
        raise ValueError(f"window_size={window}, chunk_size={chunk}: a "
                         "window is a whole number of chunks")
    if config.get("max_seq_length",
                  model["max_position_embeddings"]) \
            != model["max_position_embeddings"]:
        raise ValueError(f"max_seq_length={config['max_seq_length']}: not "
                         "max_position_embeddings")
    model["window_size"], model["chunk_size"] = window, chunk
    return model


def program_config(model: dict):
    import dataclasses
    return dataclasses.replace(
        decoder.program_config(model), norm_offset=1.0,
        attn_window=model["window_size"], attn_chunk=model["chunk_size"])


def marker_calls_per_step(model: dict) -> int:
    return decoder.marker_calls_per_step(model)


def resident_entries(model: dict, batch: float,
                     resident_context: float) -> float:
    """Cache entries that hold ``resident_context`` tokens of ``batch``
    sequences. A sequence of ``n`` tokens, ``r = n % W`` of them in its open
    window, holds ``(n - r) / C + r = n / C + (1 - 1 / C) r`` entries; with
    its phase in the window uniform, ``r`` is ``W / 2`` on average:

        n / C + (1 - 1 / C) W / 2       (C = 16, W = 2048:  n / 16 + 960)

    — and never more than ``n`` (a sequence inside its first window holds a
    row a token)."""
    w, c = model["window_size"], model["chunk_size"]
    return min(resident_context,
               resident_context / c + (1.0 - 1.0 / c) * w / 2.0 * batch)


def decode_bytes_per_step(model: dict, batch: float,
                          resident_context: float) -> float:
    """The decoder family's count, with the keys and values of every
    resident cache ENTRY and not of every resident token. The two summary
    vectors a layer are read only where a window closes: left out."""
    return decoder.decode_bytes_per_step(
        model, batch, resident_entries(model, batch, resident_context))


def prefill_flops_per_token(model: dict) -> float:
    """The decoder family's count: the projections and the feed-forward.
    The summarise (a few operations a key) and the attention scores are not
    counted: a lower bound, as there."""
    return decoder.prefill_flops_per_token(model)


def kernel_cost(kernel: str, model: dict, engine: dict, batch: float,
                resident_context: float):
    """The paged attention kernel: the decoder family's count over the
    resident ENTRIES. The summarise (``kv.summarise``): what closing ONE
    window needs, whole model — ``batch`` and ``resident_context`` do not
    enter: the window's keys and values read once in every layer, a page of
    summaries written; a multiply-add a key element for each of the two
    scores and each of the two weighted sums."""
    if kernel == SUMMARISE:
        layers = model["num_hidden_layers"]
        row = decoder.kv_row_bytes(model)
        w, c = model["window_size"], model["chunk_size"]
        return {"bytes": layers * row * (w + w // c),
                "flops": layers * 4.0 * 2 * w
                * model["num_key_value_heads"] * model["head_dim"]}
    return decoder.kernel_cost(
        kernel, model, engine, batch,
        resident_entries(model, batch, resident_context))

