"""The looped decoder family (Ouro / LoopLM): the decoder family's attention
and SwiGLU feed-forward, but the layers run ``total_ut_steps`` times a token
over ONE set of weights, each pass with keys and values of its own, four
RMSNorms a layer, the final norm closing every pass, and an exit gate that
picks the pass the head reads.

What the decoder family says of shapes, bytes and operations holds here
wherever no pass and no KV depth enters; this file imports it and changes
the rest: every layer's matrices are streamed once a PASS, a prompt token
passes through them as often, and a token owns ``R L`` planes of keys and
values, so the paged attention kernel is called ``R L`` times a step.
"""

from __future__ import annotations

import ast
import os

from benchmark import manifest
from benchmark.families import decoder
from benchmark.peaks import BF16, F32

# published keys beyond the decoder family's, and those built at one value
LOOP_SIZES = ("total_ut_steps", "early_exit_threshold", "head_dim",
              "layer_types", "max_window_layers", "model_type")
LOOP_ONLY = (("use_sliding_window", False), ("sliding_window", None),
             ("rope_scaling", None), ("early_exit_threshold", 1.0))
# what ``config.json`` has no key for, from the published modeling code: a
# configuration states each under ``assumed``, and only these values build
ASSUMED = {"norms_per_layer": 4, "norm_closes_every_pass": True,
           "exit_gate_bias": True, "torch_dtype": "bfloat16"}
# the fields the program's model config needs for this family
DESCRIPTORS = ("loop_steps", "sandwich_norm", "exit_gate", "exit_threshold")

STEP_MARKER = decoder.STEP_MARKER
# the decoder's three decode shares; what the loop adds beside the layers
# (``decode_loop_share``) is apart, so that the three stay the three
SCOPE_GROUPS = dict(decoder.SCOPE_GROUPS)
LOOP_SCOPES = ("loop.norm", "loop.gate", "loop.select")


def _program_fields() -> set:
    """The fields of the program's ``DecoderConfig``, read from its source:
    the harness's parent process imports no jax. For ``model_sizes``'
    refusal alone: the driver tries a new cell on the parent commit under
    THESE files, and without the refusal that run fails only when the
    runner's ``program_config`` has raised through 600 s of readiness
    probes (builder, PR 34) — not cleanly, and not soon."""
    path = os.path.join(manifest.ROOT, "tpu9", "models", "transformer.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        if isinstance(node, ast.ClassDef) and node.name == "DecoderConfig":
            return {s.target.id for s in node.body
                    if isinstance(s, ast.AnnAssign)}
    return set()


def model_sizes(config: dict) -> dict:
    """The sizes the reference and the program need. Refuses, before
    anything is started, a key this family does not build and a program
    that cannot run a looped decoder."""
    lacks = [f for f in DESCRIPTORS if f not in _program_fields()]
    if lacks:
        raise ValueError(f"the program's DecoderConfig has no {lacks}: it "
                         "cannot run a looped decoder")
    own = set(LOOP_SIZES) | {k for k, _ in LOOP_ONLY}
    for key, want in LOOP_ONLY:
        if config.get(key, want) != want:
            raise ValueError(f"{key}={config[key]!r}: the looped family "
                             f"builds only {want!r}")
    assumed = {k: v["value"] for k, v in config.get("assumed", {}).items()}
    for key, value in assumed.items():
        if key not in ASSUMED:
            raise ValueError(f"assumed {key}={value!r}: the looped family "
                             "does not build this key")
        if value != ASSUMED[key]:
            raise ValueError(f"assumed {key}={value!r}: the looped family "
                             f"builds only {ASSUMED[key]!r}")
    plain = {k: v for k, v in config.items() if k not in own}
    plain["assumed"] = {"head_dim": {"value": config["head_dim"]}}
    plain["torch_dtype"] = assumed.get("torch_dtype", "bfloat16")
    try:
        model = decoder.model_sizes(plain)
    except ValueError as exc:
        raise ValueError(str(exc).replace("decoder family",
                                          "looped family")) from None
    if model["num_local_experts"]:
        raise ValueError("num_local_experts: the looped family builds a "
                         "dense feed-forward only")
    layers = model["num_hidden_layers"]
    if list(config["layer_types"]) != ["full_attention"] * layers:
        raise ValueError("layer_types: the looped family builds "
                         "full_attention in every layer only")
    if config["max_window_layers"] != layers:
        raise ValueError(f"max_window_layers={config['max_window_layers']}"
                         f": {layers} layers and no window")
    if int(config["total_ut_steps"]) < 1:
        raise ValueError(f"total_ut_steps={config['total_ut_steps']!r}")
    model["total_ut_steps"] = int(config["total_ut_steps"])
    model["early_exit_threshold"] = float(config["early_exit_threshold"])
    return model


def program_config(model: dict):
    import dataclasses
    return dataclasses.replace(
        decoder.program_config(model), loop_steps=model["total_ut_steps"],
        sandwich_norm=True, exit_gate=True,
        exit_threshold=model["early_exit_threshold"])


def kv_planes(model: dict) -> int:
    """Planes of keys and values a token owns: one a (pass, layer)."""
    return model["total_ut_steps"] * model["num_hidden_layers"]


def marker_calls_per_step(model: dict) -> int:
    return kv_planes(model)


def decode_bytes_per_step(model: dict, batch: float,
                          resident_context: float) -> float:
    """Bytes one decode step has to read, whole model: every layer's
    matrices (bf16) and its four norm vectors (float32) once a PASS, the
    closing norm once a pass, the gate and the head once, and the keys and
    values of every resident context token in each of the ``R L`` planes.
    The embedding gather (``batch`` rows) is left out."""
    p = decoder.matmul_params(model)
    d, passes = model["hidden_size"], model["total_ut_steps"]
    per_layer = (p["attention"] + p["ffn"]) * BF16 + 4 * d * F32
    return (passes * model["num_hidden_layers"] * per_layer
            + passes * d * F32 + (d + 1) * F32 + p["head"] * BF16
            + kv_planes(model) * decoder.kv_row_bytes(model)
            * resident_context)


def prefill_flops_per_token(model: dict) -> float:
    """Matmul FLOPs one prompt token needs: the decoder family's count, once
    a pass. Attention scores, the gate and the head are not counted: a
    lower bound, as there."""
    return model["total_ut_steps"] * decoder.prefill_flops_per_token(model)


def kernel_cost(kernel: str, model: dict, engine: dict, batch: float,
                resident_context: float):
    """The decoder family's count of the paged attention kernel, over the
    ``R L`` planes a step reads and not over the ``L`` layers of weights."""
    cost = decoder.kernel_cost(kernel, model, engine, batch, resident_context)
    if cost is None:
        return None
    passes = model["total_ut_steps"]
    return {k: passes * v for k, v in cost.items()}
