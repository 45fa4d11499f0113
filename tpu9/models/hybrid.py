"""The two attention kinds of the latent family's layer PATTERN: delta-rule
linear attention with a decay a channel (KDA) and latent attention (MLA). A
pattern is WHICH LAYERS ARE KDA AND WHICH MLA — ``DecoderConfig.layers`` says
it, and a pattern may have no KDA layer at all (MLA in every layer, no state
a lane, and a cache the prefix cache can share); ``models.transformer`` walks
the layers and calls in here.

What a running sequence keeps differs by kind, and that is the point:

- a KDA layer keeps STATE A LANE — a float32 matrix ``[H, d, d]`` and the
  last taps-less-one inputs of its short convolution — the same size at any
  length, whatever the lanes (the chunked prefill's scratch keeps one): no
  table, no pages.
- an MLA layer keeps ONE ROW A TOKEN — the latent and its rotated key
  (``DecoderConfig.kv_row``) — addressed by the table like any paged cache. A
  prefill expands keys and values from the latents — over a long scratch a
  block of keys at a time inside a kernel (``ops.latent_attention.
  blocked_prefill_attention``), over a short one every row at once; a decode
  step absorbs the expansions into the query and the output and attends the
  latents themselves, one kernel over the pages a lane holds that scores
  the latents and the rotated keys alike (``ops.latent_attention``; the
  pool keeps the rotated keys two tokens a row for it, which is
  ``models.kvstate``'s to know). Its query is one full-rank
  matrix or goes through a latent with a norm of its own
  (``mla_q_latent``), its output has a sigmoid gate a head or none
  (``mla_out_gate``), its positions are plain rotary or YaRN's with the
  attention temperature in the softmax scale (``rope_yarn``, ``mla_mscale``).

Where either lives in the cache dict, and how it is read and written, is
``models.kvstate``'s to say; the layers here call it.

The equations, with every assumption, are in the plain references the
benchmark holds this to (``benchmark/reference/ling.py``: KDA closed by one
MLA layer a group; ``benchmark/reference/kimi.py``: MLA in every layer).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..ops import delta_rule
from ..ops.latent_attention import (blocked_prefill_attention,
                                    blocked_prefill_declined,
                                    expanded_attention)
from ..ops.norms import rms_norm
from ..ops.quant import maybe_matmul, project_heads
from ..ops.rotary import apply_rope
from . import kvstate

F32 = jnp.float32
# device scopes of the two kinds, beside ``transformer.DEVICE_SCOPES``:
# KDA's projections, convolution and gates / its recurrence (a decode step
# or a prefill's scan over blocks); MLA's expansions of the latent (absorbed
# into query and output at decode) / its attention; the shared expert
HYBRID_SCOPES = ("attn.kda.proj", "attn.kda.state", "attn.mla.absorb",
                 "attn.mla.core", "moe.shared")
# the query's low-rank path, emitted only where the query goes through a
# latent (``mla_q_latent``). Apart from ``HYBRID_SCOPES``, as the loop's and
# the summarise's scopes are from ``DEVICE_SCOPES``: the benchmark's
# accepted tests pin that every scope of that tuple is in a group of the
# hybrid-linear family, whose programs run nothing under this one
MLA_QUERY_SCOPES = ("attn.mla.q",)


def refuse_unbuilt_pattern(cfg) -> None:
    """``DecoderConfig.__post_init__`` for a layer pattern: every
    combination that is not built is refused, with its reason."""
    def refuse(what: str, why: str):
        raise ValueError(f"{cfg.pattern_label} with {what}: {why}")

    if cfg.layer_group < 1:
        refuse("a negative group", "the last layer of every group of "
               "layer_group layers is MLA, the others KDA")
    if cfg.looped:
        refuse("a pass loop (loop_steps > 1 or exit_gate)",
               "a lane's KDA state would need a plane a pass; not built")
    if cfg.attn_window:
        refuse("attn_window", "window summaries replace rows of a per-head "
               "cache; the latent cache has no summarise")
    if cfg.sandwich_norm:
        refuse("sandwich_norm", "neither kind of layer is built with a norm "
               "on its output")
    if cfg.n_kv_heads != cfg.n_heads:
        refuse(f"n_kv_heads={cfg.n_kv_heads}",
               "KDA is built with as many key heads as query heads, and the "
               "latent cache has no KV heads at all")
    if min(cfg.mla_latent, cfg.mla_nope, cfg.mla_rope, cfg.mla_v) <= 0 \
            or cfg.mla_rope % 2:
        refuse("a latent-attention width that is not set",
               "mla_latent, mla_nope, mla_rope (even) and mla_v are all "
               "needed")
    if "kda" not in cfg.lane_state:
        if cfg.kda_conv or cfg.kda_gate_bound:
            refuse(f"kda_conv={cfg.kda_conv}, kda_gate_bound="
                   f"{cfg.kda_gate_bound}",
                   "a group of one layer has no KDA layer to read them")
    elif cfg.kda_conv < 2 or cfg.kda_gate_bound >= 0:
        refuse(f"kda_conv={cfg.kda_conv}, kda_gate_bound="
               f"{cfg.kda_gate_bound}",
               "the short convolution has at least 2 taps and the log-decay "
               "a negative lower bound")
    if cfg.mla_q_latent < 0 or cfg.mla_mscale <= 0 or (
            cfg.rope_yarn and (len(cfg.rope_yarn) != 4
                               or cfg.rope_yarn[0] < 1
                               or cfg.rope_yarn[1] <= 0
                               or cfg.rope_yarn[2] <= cfg.rope_yarn[3])):
        refuse(f"mla_q_latent={cfg.mla_q_latent}, mla_mscale="
               f"{cfg.mla_mscale}, rope_yarn={cfg.rope_yarn}",
               "a query latent is a width, the temperature positive, and "
               "YaRN is (factor >= 1, original positions, beta_fast > "
               "beta_slow)")
    if cfg.tie_embeddings or cfg.embed_scale or cfg.logit_softcap \
            or cfg.norm_offset or cfg.act != "silu":
        refuse("a descriptor of another family (tied or scaled embeddings, "
               "soft-capped logits, offset norms, gelu)",
               "no served model has both; not run")
    if cfg.n_experts:
        refuse_unbuilt_share(cfg, refuse)


def refuse_unbuilt_share(cfg, refuse) -> None:
    """The expert layer of a pattern (by rule, or ``models.ssm``'s list):
    what is not built of its share and its gates, through the caller's
    ``refuse(what, why)``."""
    if not cfg.moe_routed:
        refuse("moe_routed=0", "a pattern's expert layer is told which "
               "experts it holds (all of them: moe_routed = n_experts): "
               "the capacity form is not run under a pattern")
    routed = cfg.moe_routed
    if cfg.moe_held_first < 0 \
            or cfg.moe_held_first + cfg.n_experts > routed:
        refuse(f"experts {cfg.moe_held_first}..+{cfg.n_experts} held of "
               f"{routed}", "the held experts lie inside the routed ones")
    if cfg.moe_score not in ("softmax", "sigmoid"):
        refuse(f"moe_score={cfg.moe_score!r}", "softmax or sigmoid")
    if cfg.moe_groups and (routed % cfg.moe_groups
                           or not 0 < cfg.moe_top_groups <= cfg.moe_groups
                           or routed // cfg.moe_groups < 2):
        refuse(f"moe_groups={cfg.moe_groups}, moe_top_groups="
               f"{cfg.moe_top_groups}",
               "groups divide the routed experts, hold at least 2 each "
               "(a group's score is the sum of its top 2), and some are "
               "kept")
    if cfg.moe_score == "softmax" and (cfg.moe_select_bias
                                       or cfg.moe_groups):
        refuse("a selection bias or groups under softmax scores",
               "built for sigmoid scores only")


def dense_init(rng, in_dim: int, out_dim: int, dtype, fan_out: int = 0):
    """A seeded matrix ``[in, out]``, normal at the fan-in / fan-out scale
    (``fan_out``: of the part of ``out_dim`` that is one projection's)."""
    scale = (2.0 / (in_dim + (fan_out or out_dim))) ** 0.5
    return (jax.random.normal(rng, (in_dim, out_dim), F32)
            * scale).astype(dtype)


def init_kda(r, cfg) -> dict:
    """One KDA layer's attention, seeded from the rngs ``r`` yields. What a
    checkpoint would bring and a seed has to choose: the convolution's taps
    normal x 0.5; ``a_log`` uniform in log-space over [1/4, 4], so that
    decays differ by head; ``b_a`` normal around -2, so that a typical
    token's decay is neither 1 nor the bound."""
    dt, d_model, h, d = cfg.dtype, cfg.dim, cfg.n_heads, cfg.head_dim
    return {
        "w_qkv": dense_init(next(r), d_model, 3 * h * d, dt, fan_out=h * d),
        "conv": jax.random.normal(next(r), (cfg.kda_conv, 3 * h * d),
                                  F32) * 0.5,
        "w_a": dense_init(next(r), d_model, h * d, dt),
        "b_a": jax.random.normal(next(r), (h * d,), F32) - 2.0,
        "a_log": jax.random.uniform(next(r), (h,), F32,
                                    -jnp.log(4.0), jnp.log(4.0)),
        "w_b": dense_init(next(r), d_model, h, dt),
        "w_g": dense_init(next(r), d_model, h * d, dt),
        "o_norm": jnp.ones((d,), F32),
        "wo": dense_init(next(r), h * d, d_model, dt)}


def init_mla(r, cfg) -> dict:
    """One MLA layer's attention, seeded from the rngs ``r`` yields."""
    dt, d_model, h = cfg.dtype, cfg.dim, cfg.n_heads
    dn, dr, dv, dc = cfg.mla_nope, cfg.mla_rope, cfg.mla_v, cfg.mla_latent
    # the rngs in the order the full-rank, gated layer always drew them
    rq, rd, ru, rg, ro = (next(r) for _ in range(5))
    mla = {"w_dkv": dense_init(rd, d_model, dc + dr, dt),
           "kv_norm": jnp.ones((dc,), F32),
           "w_ukv": dense_init(ru, dc, h * (dn + dv), dt),
           "wo": dense_init(ro, h * dv, d_model, dt)}
    if cfg.mla_q_latent:
        rq, ruq = jax.random.split(rq)
        mla.update(w_dq=dense_init(rq, d_model, cfg.mla_q_latent, dt),
                   q_norm=jnp.ones((cfg.mla_q_latent,), F32),
                   w_uq=dense_init(ruq, cfg.mla_q_latent, h * (dn + dr), dt))
    else:
        mla["wq"] = dense_init(rq, d_model, h * (dn + dr), dt)
    if cfg.mla_out_gate:
        mla["w_gate"] = dense_init(rg, d_model, h, dt)
    return mla


def project32(h, w):
    """``h @ w`` with the product kept in float32 (the operands stay in the
    model's type): a gate's pre-activation. Rounded to bfloat16 it is off by
    up to 0.4 % of its size, and the decay's is scaled by ``exp(A_h)`` and
    compounds over every token the state remembers."""
    return jnp.matmul(h, w, preferred_element_type=F32)


def _unit(x):
    """x / |x|_2 over the last axis (float32)."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-24)


def kda_block(p: dict, h: jnp.ndarray, cfg, kv_cache: Optional[dict],
              plane: int, decode: bool, n_valid):
    """One KDA layer's attention over the normed input ``h`` [B, T, D].
    With a cache dict the layer's state is read at ``plane`` and written
    back, advanced by the first ``n_valid[b]`` tokens of lane ``b`` only
    (a padded tail and an idle lane leave it untouched); without one the
    sequence starts from zero state and nothing is kept. Returns
    ``(y [B, T, D], kv_cache)``."""
    b, t, _ = h.shape
    heads, d = cfg.n_heads, cfg.head_dim
    if kv_cache is None:
        state = jnp.zeros((b, heads, d, d), F32)
        tail = jnp.zeros((b, cfg.kda_conv - 1, 3 * heads * d), h.dtype)
        n_valid = jnp.full((b,), t, jnp.int32)
    else:
        state, tail = kvstate.lane_read(kv_cache, plane)
    with jax.named_scope("attn.kda.proj"):
        qkv, tail = delta_rule.causal_conv(
            maybe_matmul(h, p["w_qkv"]), p["conv"], tail, n_valid)
        q, k, v = (a.reshape(b, t, heads, d) for a in
                   jnp.split(jax.nn.silu(qkv), 3, axis=-1))
        q, k = _unit(q) * d ** -0.5, _unit(k)
        gate_in = (project32(h, p["w_a"]) + p["b_a"]).reshape(
            b, t, heads, d) * jnp.exp(p["a_log"])[None, None, :, None]
        log_alpha = cfg.kda_gate_bound * jax.nn.sigmoid(gate_in)
        beta = jax.nn.sigmoid(project32(h, p["w_b"]))
        out_gate = jax.nn.sigmoid(
            project32(h, p["w_g"])).reshape(b, t, heads, d)
    with jax.named_scope("attn.kda.state"):
        if decode and kv_cache is not None \
                and not delta_rule.step_kernel_declined(heads, d):
            # the Pallas step: the lanes' states in place at this plane
            states, o = delta_rule.step_pallas(
                kvstate.lane_states(kv_cache), plane, q[:, 0], k[:, 0],
                v[:, 0], log_alpha[:, 0], beta[:, 0], live=n_valid > 0)
            return _kda_out(p, o[:, None], out_gate, h, cfg), \
                kvstate.lane_write(kv_cache, plane, tail, states=states)
        if decode:
            state, o = delta_rule.step(
                state, q[:, 0], k[:, 0], v[:, 0], log_alpha[:, 0],
                beta[:, 0], live=n_valid > 0)
            o = o[:, None]
        else:
            valid = jnp.arange(t)[None, :] < n_valid[:, None]
            whole = t % min(delta_rule.BLOCK, t) == 0
            state, o = (delta_rule.chunked if whole else delta_rule.scan)(
                state, q, k, v, log_alpha, beta, valid)
        if kv_cache is not None:
            kv_cache = kvstate.lane_write(kv_cache, plane, tail, state=state)
    return _kda_out(p, o, out_gate, h, cfg), kv_cache


def _kda_out(p: dict, o, out_gate, h, cfg):
    """KDA's output: the per-head norm, the gate, ``W_o``."""
    b, t, heads, d = o.shape
    with jax.named_scope("attn.out"):
        o = rms_norm(o, p["o_norm"], cfg.norm_eps) * out_gate
        return maybe_matmul(o.reshape(b, t, heads * d).astype(h.dtype),
                            p["wo"])


def mla_block(p: dict, h: jnp.ndarray, cfg, positions, sin, cos,
              kv_cache: Optional[dict], plane: int, cache_len, decode: bool):
    """One MLA layer's attention over the normed input ``h`` [B, T, D]; the
    cache holds latents and their rotated keys at ``plane``. Returns
    ``(y [B, T, D], kv_cache)``."""
    b, t, _ = h.shape
    heads = cfg.n_heads
    dn, dr, dv, dc = cfg.mla_nope, cfg.mla_rope, cfg.mla_v, cfg.mla_latent
    scale = (dn + dr) ** -0.5 * cfg.mla_mscale ** 2
    if cfg.mla_q_latent:
        # the query through its latent, with a norm of its own
        with jax.named_scope("attn.mla.q"):
            c_q = rms_norm(maybe_matmul(h, p["w_dq"]), p["q_norm"],
                           cfg.norm_eps).astype(h.dtype)
            q = project_heads(c_q, p["w_uq"], heads, dn + dr)
    with jax.named_scope("attn.qkv"):
        if not cfg.mla_q_latent:
            q = project_heads(h, p["wq"], heads, dn + dr)
        down = maybe_matmul(h, p["w_dkv"])                   # [B, T, dc+dr]
        c = rms_norm(down[..., :dc], p["kv_norm"], cfg.norm_eps)
        if cfg.mla_out_gate:
            gate = jax.nn.sigmoid(maybe_matmul(h, p["w_gate"]).astype(F32))
    with jax.named_scope("attn.rope"):
        q_nope = q[..., :dn]
        q_rope = apply_rope(q[..., dn:], sin, cos)
        k_rope = apply_rope(down[..., None, dc:], sin,
                            cos)[..., 0, :]                  # [B, T, dr]
    w_ukv = p["w_ukv"].reshape(dc, heads, dn + dv)

    def expand(latents):                # [..., dc] -> keys, values per head
        with jax.named_scope("attn.mla.absorb"):
            kv = jnp.einsum("...c,chd->...hd", latents, w_ukv)
            return kv[..., :dn], kv[..., dn:]

    if kv_cache is None:
        k_nope, v = expand(c)
        with jax.named_scope("attn.mla.core"):
            out = jax.vmap(expanded_attention,
                           in_axes=(0, 0, 0, 0, 0, 0, None))(
                q_nope, q_rope, k_nope, k_rope, v, positions, scale)
    elif kvstate.is_paged(kv_cache) and decode:
        kv_cache = kvstate.write(kv_cache, plane, c, k_rope, positions,
                                 decode)
        with jax.named_scope("attn.mla.absorb"):
            q_lat = jnp.einsum("bhd,chd->bhc", q_nope[:, 0], w_ukv[..., :dn])
        with jax.named_scope("attn.mla.core"):
            o_lat = kvstate.latent_attend(kv_cache, plane, q_lat,
                                          q_rope[:, 0], cache_len, scale)
        with jax.named_scope("attn.mla.absorb"):
            out = jnp.einsum("bhc,chd->bhd", o_lat.astype(h.dtype),
                             w_ukv[..., dn:])[:, None]
    elif not kvstate.is_paged(kv_cache) and not decode \
            and cache_len is not None and b == 1:
        # chunked prefill through the batch-1 scratch: this chunk's rows
        # written at its offset, then every row's keys and values expanded
        kv_cache = kvstate.write(kv_cache, plane, c, k_rope, positions,
                                 decode)
        if not blocked_prefill_declined(kvstate.dense_len(kv_cache)):
            # a long scratch: a block of keys at a time, each block's keys
            # and values made from its latents where they are used
            with jax.named_scope("attn.mla.core"):
                out = blocked_prefill_attention(
                    q_nope[0], q_rope[0], *kvstate.latent_planes(kv_cache),
                    w_ukv, positions[0, 0], plane, scale)[None]
        else:
            latents, rotated = kvstate.latent_rows(kv_cache, plane)
            k_nope, v = expand(latents)
            with jax.named_scope("attn.mla.core"):
                out = expanded_attention(q_nope[0], q_rope[0], k_nope,
                                         rotated, v, positions[0],
                                         scale)[None]
    else:
        raise NotImplementedError(
            "latent attention is built for a forward pass without a cache, "
            "for chunked prefill through the batch-1 scratch and for a "
            "decode step over the paged pool: a verify window and a dense "
            "decode cache are refused when the engine is made")
    with jax.named_scope("attn.out"):
        if cfg.mla_out_gate:
            out = out * gate[..., None]
        out = out.astype(h.dtype)
        return maybe_matmul(out.reshape(b, t, heads * dv), p["wo"]), kv_cache
