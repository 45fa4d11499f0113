"""A layer pattern stated as a LIST (``DecoderConfig.layers`` is what it
says): which layers are Mamba-2 state-space mixers (``"ssm"``) and which the
plain attention of a uniform decoder (``"full"``) — the hybrid state-space
family (``granitemoehybrid``: nine mixers to one attention layer, every layer
closed by the same dense SwiGLU). ``models.transformer`` walks the layers
and calls in here for the mixers; the plain-attention layers of a list are
``transformer._attn_block``'s own, at the plane their place among
themselves gives.

A list may state the OTHER half of every layer too (``ffn_pattern``), and
then a layer is one sub-layer alone (``nemotron_h``: a mixer, attention or
an expert layer, each ``x + f(norm(x))`` with one norm): ``"none"`` is the
half a layer lacks, and it keeps neither that half's norm nor its weights.
The expert layer of such a list is ``models.moe``'s, told which experts it
holds (``moe_routed``), its experts ungated (``moe_gated`` False, ``act``
``"relu2"``) in a latent (``moe_latent_dim``); the mixer's gated norm may
run over groups of channels (``ssm_norm_groups``).

What a running sequence keeps differs by kind:

- an ``"ssm"`` layer keeps STATE A LANE — a float32 matrix ``[H, P, N]``
  (stored as ``ops.ssd.state_shape`` says) and
  the last taps-less-one inputs of its short convolution — the same size at
  any length: no table, no pages (``models.kvstate.lane_shapes``).
- a ``"full"`` layer keeps per-head keys and values a token, in a pool only
  as deep as there are such layers.

The mixer, with ``u`` the normed input:

    [z | xBC | dt] = u W_in                       (no bias)
    xBC = silu(conv(xBC) + b_conv)                depthwise, causal, K taps
    [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A_h = -exp(A_log_h)
    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D_h x_t
    out = RMSNorm_w(y * silu(z)) W_out            the gate BEFORE the norm,
                                                  over each of ``ssm_norm_groups``
                                                  groups of channels

``ops.ssd`` has the recurrence in its four forms; the equations, with every
assumption, are in the plain reference the benchmark holds this to
(``benchmark/reference/granitehybrid.py``).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..ops import ssd
from ..ops.delta_rule import causal_conv
from ..ops.norms import rms_norm
from ..ops.quant import maybe_matmul
from . import kvstate
from .hybrid import dense_init, project32

F32 = jnp.float32
# device scopes of a state-space layer, beside ``transformer.DEVICE_SCOPES``
# and ``hybrid.HYBRID_SCOPES`` (a tuple of their own: the benchmark's
# accepted tests pin those two to the families that have them): the
# projection in, the convolution, ``dt`` and the gate / the recurrence (a
# decode step or a prefill's scan over blocks)
SSM_SCOPES = ("attn.ssm.proj", "attn.ssm.state")
KINDS = ("ssm", "full", "conv")
# what ``ffn_pattern`` may say of a layer (and ``layer_pattern``, beside
# ``KINDS``, where it is stated): the half that is there, or "none"
HALF_KINDS = ("experts", "none")


def refuse_unbuilt_list(cfg) -> None:
    """``DecoderConfig.__post_init__`` for a listed pattern and for the
    descriptors that came with it: every combination that is not built is
    refused, with its reason."""
    def refuse(what: str, why: str):
        raise ValueError(f"{what}: {why}")

    if cfg.attn_scale:
        exact = cfg.attn_scale * cfg.head_dim ** 0.5
        if cfg.attn_scale < 0 or math.frexp(exact)[0] != 0.5:
            refuse(f"attn_scale={cfg.attn_scale} at head_dim={cfg.head_dim}",
                   "the kernels fix head_dim ** -0.5 and the queries carry "
                   f"the rest, {exact}: only a power of two multiplies a "
                   "bfloat16 query without rounding it")
    if min(cfg.embed_mult, cfg.residual_mult, cfg.logit_div) <= 0:
        refuse(f"embed_mult={cfg.embed_mult}, residual_mult="
               f"{cfg.residual_mult}, logit_div={cfg.logit_div}",
               "a multiplier is positive (1 = off)")
    multiplied = bool(cfg.attn_scale) or cfg.embed_mult != 1.0 \
        or cfg.residual_mult != 1.0 or cfg.logit_div != 1.0
    halves = bool(cfg.ffn_pattern)
    # (a listed pattern of half-layers runs its expert layers beside
    # attention without positions; the multipliers it has never run with)
    if (multiplied or not cfg.rope) and (
            cfg.layer_group or cfg.looped or cfg.attn_window
            or (cfg.n_experts and (multiplied or not halves))):
        refuse("attention without rotary or at a scale of its own, or a "
               "multiplier on embeddings, residuals or logits, with a "
               "layer_group, a pass loop, attn_window or experts",
               "latent attention has positions and a temperature of its "
               "own, the other branches add to the stream in their own "
               "code; no served model has both, not run")
    if not halves and (not cfg.moe_gated or cfg.moe_latent_dim
                       or cfg.act == "relu2"):
        refuse("ungated experts, moe_latent_dim or relu2 without an "
               "ffn_pattern", "they are a list's expert layers': no layer "
               "would read them")
    sizes = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv)
    conv = "conv" in cfg.layer_pattern
    if (cfg.conv_taps or cfg.qk_norm) and not conv:
        refuse(f"conv_taps={cfg.conv_taps}, qk_norm={cfg.qk_norm} without a "
               "\"conv\" layer in a layer_pattern",
               "the taps are a short convolution's, and the norm a head on "
               "queries and keys was built and run with that list's rotary "
               "attention alone: no other layer would read them")
    if not cfg.layer_pattern:
        if any(sizes) or cfg.ssm_groups != 1 or cfg.ssm_norm_groups != 1 \
                or halves:
            refuse(f"ssm sizes {sizes}, ssm_norm_groups="
                   f"{cfg.ssm_norm_groups} or an ffn_pattern without a "
                   "layer_pattern", "no layer would read them")
        return
    what = f"layer_pattern of {len(cfg.layer_pattern)} layers"
    kinds = KINDS + ("none",) * halves
    if len(cfg.layer_pattern) != cfg.n_layers \
            or any(k not in kinds for k in cfg.layer_pattern):
        refuse(what, f"one kind of {kinds} for each of the {cfg.n_layers} "
               "layers (KDA and MLA layers are stated by layer_group; "
               "\"none\" only beside an ffn_pattern)")
    if cfg.layer_group:
        refuse(f"{what} with layer_group={cfg.layer_group}",
               "a pattern is stated once, as the rule or as the list")
    if cfg.looped or cfg.attn_window or cfg.sandwich_norm \
            or (cfg.n_experts and not (halves or conv)):
        refuse(f"{what} with a pass loop, attn_window, sandwich_norm or "
               "experts", "a lane's state would need a plane a pass, a "
               "window's summarise knows no pool of fewer planes than "
               "layers, and no listed pattern was run with output norms; an "
               "expert layer is built for a list that states its ffn_pattern "
               "or, in whole layers, for one around short convolutions")
    if cfg.embed_scale or cfg.logit_softcap or cfg.norm_offset \
            or cfg.act != ("relu2" if halves else "silu"):
        refuse(f"{what} with a descriptor of another family (sqrt(dim) "
               "embeddings, soft-capped logits, offset norms, gelu; relu2 "
               "is the ungated experts' of an ffn_pattern, and only theirs)",
               "no served model has both; not run")
    if conv:
        return _refuse_unbuilt_conv_list(cfg, what, sizes, refuse)
    if halves:
        _refuse_unbuilt_halves(cfg, what, refuse)
    if "ssm" not in cfg.lane_state:
        refuse(f"{what} without an ssm layer",
               "that is a uniform decoder, stated without a list: a list "
               "packs narrow heads to whole cache rows "
               "(kvstate.heads_per_row), which only the engine's refusals "
               "beside state a lane keep from an int8 pool and a mesh")
    if min(sizes) <= 0 or cfg.ssm_conv < 2 or cfg.ssm_groups < 1 \
            or cfg.ssm_heads % cfg.ssm_groups:
        refuse(f"{what} with ssm sizes {sizes}, ssm_groups="
               f"{cfg.ssm_groups}",
               "heads, a head's width, the state's width and at least "
               "2 taps are all needed, and the groups divide the heads")
    if cfg.ssm_norm_groups < 1 or cfg.ssm_heads % cfg.ssm_norm_groups:
        refuse(f"{what} with ssm_norm_groups={cfg.ssm_norm_groups}",
               "the gated norm's groups are whole heads: they divide "
               f"ssm_heads={cfg.ssm_heads}")


def _refuse_unbuilt_conv_list(cfg, what: str, sizes, refuse) -> None:
    """A listed pattern with ``"conv"`` layers: what is built is a list of
    WHOLE layers — gated short convolutions around plain rotary attention,
    each closed by the rule's feed-forward part (``moe_dense_layers`` dense,
    then expert layers that hold all they route over) — and nothing wider."""
    what = f"{what} with \"conv\" layers"
    if cfg.ffn_pattern or "ssm" in cfg.lane_state or any(sizes) \
            or cfg.ssm_groups != 1 or cfg.ssm_norm_groups != 1:
        refuse(f"{what} and an ffn_pattern, \"ssm\" layers or ssm sizes",
               "short convolutions were built and run in whole layers "
               "beside plain attention alone: half-layers and a second kind "
               "of state a lane beside theirs were never run")
    if not cfg.layers_of("full"):
        refuse(f"{what} and no \"full\" layer",
               "a pool of no plane: no program pages nothing, not run")
    if cfg.conv_taps < 2:
        refuse(f"{what}, conv_taps={cfg.conv_taps}",
               "at least 2 taps: the state is the taps-less-one rows before")
    if not cfg.rope or cfg.attn_scale or cfg.embed_mult != 1.0 \
            or cfg.residual_mult != 1.0 or cfg.logit_div != 1.0:
        refuse(f"{what} without rotary, or with a scale or multiplier",
               "their attention was built and run with positions at "
               "head_dim ** -0.5 and a plain stream; no served model has "
               "both")
    if not cfg.n_experts:
        if cfg.moe_dense_layers:
            refuse(f"{what}, moe_dense_layers={cfg.moe_dense_layers} and no "
                   "experts", "no layer would read it")
        return
    from .hybrid import refuse_unbuilt_share
    refuse_unbuilt_share(cfg, refuse)
    if cfg.moe_routed != cfg.n_experts or cfg.moe_held_first \
            or cfg.moe_shared_dim or cfg.moe_latent_dim or not cfg.moe_gated \
            or cfg.moe_groups:
        refuse(f"{what}, {cfg.n_experts} of moe_routed={cfg.moe_routed} "
               f"experts from {cfg.moe_held_first}, moe_shared_dim="
               f"{cfg.moe_shared_dim}, moe_latent_dim={cfg.moe_latent_dim}, "
               f"moe_gated={cfg.moe_gated}, moe_groups={cfg.moe_groups}",
               "the expert layers of whole listed layers hold every expert "
               "they route over (moe_routed = n_experts), gated, at the "
               "model's width, with no shared expert and no groups: the "
               "only form run")
    if not 0 <= cfg.moe_dense_layers < cfg.n_layers:
        refuse(f"{what}, moe_dense_layers={cfg.moe_dense_layers}",
               "a leading run of dense layers, then at least one expert "
               "layer")


def _refuse_unbuilt_halves(cfg, what: str, refuse) -> None:
    """A listed pattern that states its ``ffn_pattern``: what is built is a
    list of HALF-layers — a mixer, attention or an expert layer alone — whose
    expert layers are told which experts they hold, ungated relu2 experts
    with or without a latent; everything wider is refused."""
    what = f"{what} with an ffn_pattern of {len(cfg.ffn_pattern)}"
    if len(cfg.ffn_pattern) != cfg.n_layers \
            or any(k not in HALF_KINDS for k in cfg.ffn_pattern):
        refuse(what, f"one kind of {HALF_KINDS} for each of the "
               f"{cfg.n_layers} layers (a dense feed-forward part is the "
               "rule's, stated without an ffn_pattern)")
    if any((a == "none") == (f == "none") for a, f in cfg.layers):
        refuse(what, "every layer is ONE half, a mixer or attention or a "
               "feed-forward part (exactly one of the two lists says "
               "\"none\"): whole layers beside half-layers were never run")
    if "experts" not in cfg.ffn_pattern or not cfg.n_experts \
            or cfg.moe_dense_layers:
        refuse(f"{what}, n_experts={cfg.n_experts}, moe_dense_layers="
               f"{cfg.moe_dense_layers}",
               "the list says which layers are expert layers: it names at "
               "least one, there are experts, and no rule beside it")
    from .hybrid import refuse_unbuilt_share
    refuse_unbuilt_share(cfg, refuse)
    if cfg.moe_gated or cfg.moe_latent_dim < 0:
        refuse(f"{what}, moe_gated={cfg.moe_gated}, moe_latent_dim="
               f"{cfg.moe_latent_dim}",
               "a list's expert layers are built and run with ungated "
               "relu2 experts alone (two matrices an expert), in a latent "
               "of moe_latent_dim numbers or (0) at the model's width")


def conv_width(cfg) -> int:
    """Channels of the short convolution: ``x``, ``B`` and ``C``."""
    return cfg.ssm_heads * cfg.ssm_head_dim + 2 * cfg.ssm_groups * cfg.ssm_state


def init_mixer(r, cfg) -> dict:
    """One state-space mixer, seeded from the rngs ``r`` yields. What a
    checkpoint would bring and a seed has to choose is Mamba-2's published
    initialisation: ``A_log = log(uniform[1, 16])``, ``dt_bias`` the inverse
    softplus of a ``dt`` log-uniform over [0.001, 0.1], ``D = 1``, the
    convolution's taps and bias uniform over ``+- 1 / sqrt(taps)`` (a
    depthwise convolution's default)."""
    dt, d = cfg.dtype, cfg.dim
    h, inner = cfg.ssm_heads, cfg.ssm_heads * cfg.ssm_head_dim
    width, bound = conv_width(cfg), cfg.ssm_conv ** -0.5
    step = jnp.exp(jax.random.uniform(
        next(r), (h,), F32, math.log(0.001), math.log(0.1)))
    return {
        "w_in": dense_init(next(r), d, inner + width + h, dt),
        "conv": jax.random.uniform(next(r), (cfg.ssm_conv, width), F32,
                                   -bound, bound),
        "conv_bias": jax.random.uniform(next(r), (width,), F32,
                                        -bound, bound),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "a_log": jnp.log(jax.random.uniform(next(r), (h,), F32, 1.0, 16.0)),
        "d_skip": jnp.ones((h,), F32),
        "norm": jnp.ones((inner,), F32),
        "w_out": dense_init(next(r), inner, d, dt)}


def step_form(cfg) -> str:
    """Which form of the recurrence a decode step takes, in words
    (``/health``'s kernel report)."""
    why = ssd.step_kernel_declined(cfg.ssm_heads, cfg.ssm_head_dim,
                                   cfg.ssm_state, cfg.ssm_groups)
    return f"xla: {why}" if why else (
        "pallas, in place, one call walks the live lanes, "
        f"{ssd.GROUP_LANES} read, stepped and written back at a time")


def scan_form(width: int) -> str:
    """Which form of the recurrence a prefill of ``width`` tokens takes."""
    block = min(ssd.BLOCK, width)
    if width % block:
        return "xla: a token at a time (not whole blocks)"
    return f"xla: chunkwise (SSD), blocks of {block}"


def ssm_block(p: dict, u: jnp.ndarray, cfg, kv_cache: Optional[dict],
              plane: int, decode: bool, n_valid):
    """One state-space layer's mixer over the normed input ``u`` [B, T, D].
    With a cache dict the layer's state is read at ``plane`` and written
    back, advanced by the first ``n_valid[b]`` tokens of lane ``b`` only (a
    padded tail and an idle lane leave it untouched); without one the
    sequence starts from zero state and nothing is kept. Returns ``(y [B,
    T, D], kv_cache)``."""
    b, t, _ = u.shape
    heads, hd, n, g = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, \
        cfg.ssm_groups
    inner, width = heads * hd, conv_width(cfg)
    if n_valid is None:
        n_valid = jnp.full((b,), t, jnp.int32)
    if kv_cache is None:
        state = jnp.zeros((b, heads, hd, n), F32)
        tail = jnp.zeros((b, cfg.ssm_conv - 1, width), u.dtype)
    else:
        # (the state as it is stored; the ``jax.numpy`` forms unpack it)
        state, tail = kvstate.lane_read(kv_cache, plane, "ssm")
    with jax.named_scope("attn.ssm.proj"):
        # the gate's and dt's pre-activations stay float32 (``project32``
        # says why); the convolution's input is rounded to the model's type,
        # as its tail keeps it
        proj = project32(u, p["w_in"])
        z = proj[..., :inner]
        xbc, tail = causal_conv(proj[..., inner:inner + width].astype(u.dtype),
                                p["conv"], tail, n_valid, p["conv_bias"])
        xbc = jax.nn.silu(xbc)
        x = xbc[..., :inner].reshape(b, t, heads, hd)
        bm = xbc[..., inner:inner + g * n].reshape(b, t, g, n)
        cm = xbc[..., inner + g * n:].reshape(b, t, g, n)
        dt = jax.nn.softplus(proj[..., inner + width:] + p["dt_bias"])
        a_head = -jnp.exp(p["a_log"])
    with jax.named_scope("attn.ssm.state"):
        live = n_valid > 0
        if decode and kv_cache is not None and not ssd.step_kernel_declined(
                heads, hd, n, g):
            # the Pallas step: the live lanes' states in place at this plane
            states, y = ssd.step_pallas(
                kvstate.lane_states(kv_cache, "ssm"), plane, x[:, 0],
                dt[:, 0], a_head, bm[:, 0], cm[:, 0], live=live)
            kv_cache = kvstate.lane_write(kv_cache, plane, tail,
                                          states=states, kind="ssm")
            y = y[:, None]
        else:
            if kv_cache is not None:
                state = ssd.unpack_state(state, hd)
            if decode:
                state, y = ssd.step(state, x[:, 0], dt[:, 0], a_head,
                                    bm[:, 0], cm[:, 0], live=live)
                y = y[:, None]
            else:
                valid = jnp.arange(t)[None, :] < n_valid[:, None]
                whole = t % min(ssd.BLOCK, t) == 0
                state, y = (ssd.chunked if whole else ssd.scan)(
                    state, x, dt, a_head, bm, cm, valid)
            if kv_cache is not None:
                kv_cache = kvstate.lane_write(
                    kv_cache, plane, tail, kind="ssm",
                    state=ssd.pack_state(state, ssd.head_pack(heads, hd, g)))
        y = y + p["d_skip"][:, None] * x
    with jax.named_scope("attn.out"):
        y = (y * jax.nn.silu(z).reshape(b, t, heads, hd)).reshape(b, t, inner)
        ng = cfg.ssm_norm_groups
        if ng == 1:
            y = rms_norm(y, p["norm"], cfg.norm_eps)
        else:
            # the norm over each group's channels, the weight a channel
            y = rms_norm(y.reshape(b, t, ng, inner // ng),
                         p["norm"].reshape(ng, inner // ng),
                         cfg.norm_eps).reshape(b, t, inner)
        return maybe_matmul(y.astype(u.dtype), p["w_out"]), kv_cache
