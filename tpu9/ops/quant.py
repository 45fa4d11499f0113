"""Weight-only int8 quantization for serving.

Decode on TPU is HBM-bandwidth-bound streaming weights through the MXU;
storing projection matrices as int8 with per-output-channel scales halves
the bytes read per step (the standard weight-only recipe). Dequantization
happens in-register (XLA fuses the scale multiply into the matmul epilogue).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

Params = dict[str, Any]

# decoder projection weights worth quantizing (2-D, large)
_TARGETS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head")

# quantization modes the serving stack understands (fp8 is the ROADMAP
# follow-up — add it HERE and every knob's validation picks it up)
SUPPORTED_MODES = ("int8",)


def validate_quant_mode(mode, what: str = "quantize") -> str:
    """Normalize a quantization-mode knob: ``None``/``""`` → ``""`` (off),
    a supported mode passes through, anything else raises. The ONE
    validation every layer's knob (`presets.resolve_preset`/`load_engine`,
    `weights.save_params`, `runner.ckpt.save_params`, `EngineConfig`)
    funnels through, so a new mode cannot be accepted at one layer and
    rejected at another."""
    if mode in (None, ""):
        return ""
    if mode not in SUPPORTED_MODES:
        raise ValueError(f"unknown {what} mode {mode!r} "
                         f"(supported: {', '.join(SUPPORTED_MODES)})")
    return mode


def _quantize_along(w: jnp.ndarray, axis: int) -> dict:
    """ONE symmetric-absmax int8 recipe (per-output-channel scales along
    ``axis``), shared by the 2-D and stacked-expert entry points so a
    future recipe change (clipping, epsilon) cannot drift between them."""
    wf = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(wf), axis=axis, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "scale": scale.astype(jnp.float32)}


def quantize_weight(w: jnp.ndarray) -> dict:
    """[in, out] → int8 values + f32 per-output-channel scales [1, out]."""
    return _quantize_along(w, axis=0)


def quantize_weight_stacked(w: jnp.ndarray) -> dict:
    """Stacked expert weights [E, in, out] → per-expert per-output-channel
    int8 (scales [E, 1, out]): quantization never mixes experts, so each
    expert's error bound matches the 2-D recipe exactly."""
    return _quantize_along(w, axis=1)


def quantized_einsum(spec: str, x: jnp.ndarray, entry: dict) -> jnp.ndarray:
    """Batched (stacked-expert) variant of :func:`quantized_matmul`:
    ``einsum(spec, x, w)`` where ``w`` is a stacked int8 entry. The scale
    multiply happens on the OUTPUT (scale broadcasts as [E, 1, out]), so
    the weight operand stays int8 in HBM — same recipe, one expert axis
    along for the ride."""
    acc = jnp.einsum(spec, x.astype(jnp.bfloat16),
                     entry["q"].astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return (acc * entry["scale"]).astype(x.dtype)


def dequantize_weight(entry: dict, dtype=jnp.bfloat16) -> jnp.ndarray:
    return (entry["q"].astype(jnp.float32) * entry["scale"]).astype(dtype)


def quantized_matmul(x: jnp.ndarray, entry: dict) -> jnp.ndarray:
    """x @ dequant(w) with the scale applied after the int8-weight matmul so
    XLA keeps the weight operand int8 in HBM."""
    acc = jax.lax.dot_general(
        x.astype(jnp.bfloat16), entry["q"].astype(jnp.bfloat16),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (acc * entry["scale"]).astype(x.dtype)


def is_quantized_entry(w) -> bool:
    """True for a ``{q, scale}`` pair this module produced."""
    return isinstance(w, dict) and "q" in w


def quantize_decoder(params: Params) -> Params:
    """Quantize a decoder param tree's projections in place-shape (norms and
    embeddings stay high precision; embeddings are gathers, not matmuls).
    Stacked MoE expert weights (``layer["moe"]["w_*"]`` [E, in, out])
    quantize per-expert — a Mixtral's bytes are ~85% experts, so skipping
    them would leave the tree effectively bf16. IDEMPOTENT: already-
    quantized entries pass through untouched, so mixed trees and double
    application (e.g. an int8-preset tree saved with TPU9_CKPT_QUANT set)
    are safe."""
    out = dict(params)
    if "lm_head" in params and not is_quantized_entry(params["lm_head"]):
        out["lm_head"] = quantize_weight(params["lm_head"])
    out["layers"] = []
    for layer in params["layers"]:
        new_layer = dict(layer)
        for name in _TARGETS:
            # 2-D only: no init path stores stacked 3-D weights flat in a
            # layer (MoE stacks live under layer["moe"], handled below) —
            # and the dense forward/sharding paths could not consume one
            if name in layer and getattr(layer[name], "ndim", 0) == 2:
                new_layer[name] = quantize_weight(layer[name])
        if "moe" in layer:
            moe = dict(layer["moe"])
            for name in ("w_gate", "w_up", "w_down"):
                if not is_quantized_entry(moe[name]):
                    moe[name] = quantize_weight_stacked(moe[name])
            new_layer["moe"] = moe            # router stays f32 (tiny)
        out["layers"].append(new_layer)
    return out


def _random_quantized(rng, in_dim: int, out_dim: int) -> dict:
    """A random int8 weight entry with realistic scales, built WITHOUT the
    full-precision intermediate. For benchmark/e2e use where weights are
    random anyway: an 8B model in bf16 (16 GiB) cannot be materialized on a
    16 GiB-HBM chip just to quantize it down to 8 GiB."""
    rq, rs = jax.random.split(rng)
    q = jax.random.randint(rq, (in_dim, out_dim), -127, 128, dtype=jnp.int8)
    # per-output-channel scales matching ``hybrid.dense_init``'s variance:
    # std = sqrt(2/(in+out)); int8 values ~U[-127,127] have std ~73, so
    # scale ≈ std/73 reproduces the dense init's magnitude
    std = (2.0 / (in_dim + out_dim)) ** 0.5
    scale = (jax.random.uniform(rs, (1, out_dim), jnp.float32,
                                0.8, 1.2) * std / 73.0)
    return {"q": q, "scale": scale}


def _random_quantized_stacked(rng, n_experts: int, in_dim: int,
                              out_dim: int) -> dict:
    """Stacked-expert analogue of :func:`_random_quantized`: int8 values
    [E, in, out] + scales [E, 1, out], synthesized without the bf16
    intermediate."""
    rq, rs = jax.random.split(rng)
    q = jax.random.randint(rq, (n_experts, in_dim, out_dim), -127, 128,
                           dtype=jnp.int8)
    std = (2.0 / (in_dim + out_dim)) ** 0.5
    scale = (jax.random.uniform(rs, (n_experts, 1, out_dim), jnp.float32,
                                0.8, 1.2) * std / 73.0)
    return {"q": q, "scale": scale}


def init_quantized_decoder(rng, cfg) -> Params:
    """``init_decoder``-shaped tree with int8 projections synthesized
    directly on device. Same tree structure/path names as
    ``tpu9.models.transformer.init_decoder`` so sharding rules and
    ``decoder_forward`` apply unchanged. MoE configs get per-expert int8
    stacks under ``layer["moe"]`` (router f32, like ``init_moe_layer``); a
    looped decoder's extra norm vectors and exit gate stay float32, under
    ``init_decoder``'s names (``tpu9.ops`` may not import the models)."""
    per_layer = 5 if cfg.n_experts else 7   # 4 attn + 1 moe | 4 attn + 3 ffn
    n_rngs = cfg.n_layers * per_layer + 3
    rngs = jax.random.split(rng, n_rngs)
    it = iter(range(n_rngs))

    def nxt():
        return rngs[next(it)]

    dt = cfg.dtype
    params: Params = {
        "embed": (jax.random.normal(nxt(), (cfg.vocab_size, cfg.dim),
                                    dtype=jnp.float32) * 0.02).astype(dt),
        "final_norm": jnp.ones((cfg.dim,), jnp.float32) - cfg.norm_offset,
        "layers": [],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _random_quantized(nxt(), cfg.dim, cfg.vocab_size)
    else:
        nxt()
    if cfg.exit_gate:
        params["exit_gate"] = {
            "w": jax.random.normal(jax.random.fold_in(rng, cfg.dim),
                                   (cfg.dim,), jnp.float32)
            * (2.0 / (cfg.dim + 1)) ** 0.5,
            "b": jnp.zeros((1,), jnp.float32)}
    q_dim = cfg.n_heads * cfg.head_dim
    kv_dim = cfg.n_kv_heads * cfg.head_dim
    for li in range(cfg.n_layers):
        layer = {
            "attn_norm": jnp.ones((cfg.dim,), jnp.float32) - cfg.norm_offset,
            "mlp_norm": jnp.ones((cfg.dim,), jnp.float32) - cfg.norm_offset,
            "wq": _random_quantized(nxt(), cfg.dim, q_dim),
            "wk": _random_quantized(nxt(), cfg.dim, kv_dim),
            "wv": _random_quantized(nxt(), cfg.dim, kv_dim),
            "wo": _random_quantized(nxt(), q_dim, cfg.dim),
        }
        if cfg.n_experts:
            e = cfg.n_experts
            r_router, r_gate, r_up, r_down = jax.random.split(nxt(), 4)
            scale = (2.0 / (cfg.dim + e)) ** 0.5
            layer["moe"] = {
                "router": jax.random.normal(
                    r_router, (cfg.dim, e), jnp.float32) * scale,
                "w_gate": _random_quantized_stacked(
                    r_gate, e, cfg.dim, cfg.hidden_dim),
                "w_up": _random_quantized_stacked(
                    r_up, e, cfg.dim, cfg.hidden_dim),
                "w_down": _random_quantized_stacked(
                    r_down, e, cfg.hidden_dim, cfg.dim),
            }
        else:
            layer["w_gate"] = _random_quantized(nxt(), cfg.dim,
                                                cfg.hidden_dim)
            layer["w_up"] = _random_quantized(nxt(), cfg.dim,
                                              cfg.hidden_dim)
            layer["w_down"] = _random_quantized(nxt(), cfg.hidden_dim,
                                                cfg.dim)
        if cfg.sandwich_norm:
            # as ``init_post_norms``: the scaled residual initialisation
            for name in ("attn_post_norm", "mlp_post_norm"):
                layer[name] = jnp.full((cfg.dim,),
                                       (2.0 * cfg.n_layers) ** -0.5,
                                       jnp.float32) - cfg.norm_offset
        if cfg.attn_window:
            from .summary_attention import init_vectors
            layer.update(init_vectors(jax.random.fold_in(rng, li),
                                      cfg.n_kv_heads, cfg.head_dim))
        params["layers"].append(layer)
    return params


def maybe_matmul(x: jnp.ndarray, w) -> jnp.ndarray:
    """Matmul that accepts either a plain array or a quantized entry —
    lets the decoder forward run on mixed trees."""
    if is_quantized_entry(w):
        return quantized_matmul(x, w)
    return x @ w


def project_heads(x: jnp.ndarray, w, n_heads: int,
                  head_dim: int) -> jnp.ndarray:
    """``x [..., D] @ w [D, n_heads * head_dim]`` split into heads:
    ``[..., n_heads, head_dim]``. The product stays 2-D up to the barrier,
    so the compiler reads ``w`` in the layout it is stored in, as it does
    ``wo`` and the feed-forward matrices. Left to fold the split into the
    product at a decode step's few rows, it takes the form that wants the
    CONTRACTED dimension minor, and every decode call re-lays the whole
    parameter into a temporary before its first step (ISSUE 63;
    ``scripts/program_copies.py`` counts such copies)."""
    y = jax.lax.optimization_barrier(maybe_matmul(x, w))
    return y.reshape(*y.shape[:-1], n_heads, head_dim)


def maybe_einsum(spec: str, x: jnp.ndarray, w) -> jnp.ndarray:
    """Einsum that accepts a plain stacked array or a stacked int8 entry
    (the MoE forward's mixed-tree analogue of :func:`maybe_matmul`)."""
    if is_quantized_entry(w):
        return quantized_einsum(spec, x, w)
    return jnp.einsum(spec, x, w)


# ---------------------------------------------------------------------------
# int8 KV cache (paged pool)
# ---------------------------------------------------------------------------

def quantize_kv(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize KV vectors along the head_dim axis: ``x [..., D]`` →
    ``(int8 [..., D], f32 scales [...])`` with one symmetric absmax scale
    per (token, head) vector. Per-vector scales mean a decode write is a
    PURE LOCAL op — a new token can never force requantization of the
    blocks already in the pool (a coarser per-block scale would)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray,
                  dtype=jnp.bfloat16) -> jnp.ndarray:
    """Inverse of :func:`quantize_kv` (scale broadcasts over head_dim)."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def quantized_bytes(params: Params) -> int:
    """HBM bytes of a (possibly mixed) param tree at its stored dtypes.
    Works on abstract trees too (``jax.eval_shape`` output) — the
    feasibility gate prices presets with it without materializing them."""
    import numpy as np
    return sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
               for x in jax.tree_util.tree_leaves(params))
