"""Decoder-only transformer core shared by the Llama and Gemma families.

Functional style: ``init_decoder`` builds a param pytree (nested dicts with
stable path names the sharding rules in ``tpu9.parallel.sharding`` pattern-
match), ``decoder_forward`` runs prefill/train/decode from the same code path
with static shapes (XLA traces one graph per (batch, seq) bucket).

Weight layout is MXU-friendly: all projections stored as [in, out] so the
forward pass is plain ``x @ w`` row-major matmuls in bf16.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..ops.attention import attention, decode_attention
from ..ops.norms import rms_norm
from ..ops.quant import maybe_matmul, quantize_kv
from ..ops.rotary import apply_rope, rope_table

Params = dict[str, Any]


@dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    hidden_dim: int = 14336
    norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    max_seq_len: int = 8192
    # family switches
    act: str = "silu"              # silu (llama) | gelu (gemma)
    norm_offset: float = 0.0       # 1.0 for gemma's (1+w) RMSNorm
    embed_scale: bool = False      # gemma scales embeddings by sqrt(dim)
    logit_softcap: float = 0.0     # gemma-2 style; 0 = off
    tie_embeddings: bool = False   # output head = embed^T
    # sparse-MoE FFN (mixtral family): n_experts 0 = dense
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads


def _dense_init(rng, in_dim: int, out_dim: int, dtype) -> jnp.ndarray:
    scale = (2.0 / (in_dim + out_dim)) ** 0.5
    return (jax.random.normal(rng, (in_dim, out_dim), dtype=jnp.float32)
            * scale).astype(dtype)


def init_decoder(rng: jax.Array, cfg: DecoderConfig) -> Params:
    n_rngs = cfg.n_layers * 7 + 3
    rngs = jax.random.split(rng, n_rngs)
    it = iter(range(n_rngs))
    dt = cfg.dtype

    def nxt():
        return rngs[next(it)]

    params: Params = {
        "embed": (jax.random.normal(nxt(), (cfg.vocab_size, cfg.dim),
                                    dtype=jnp.float32) * 0.02).astype(dt),
        "final_norm": jnp.ones((cfg.dim,), dtype=jnp.float32) - cfg.norm_offset,
        "layers": [],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_init(nxt(), cfg.dim, cfg.vocab_size, dt)
    else:
        nxt()

    q_dim = cfg.n_heads * cfg.head_dim
    kv_dim = cfg.n_kv_heads * cfg.head_dim
    for li in range(cfg.n_layers):
        layer = {
            "attn_norm": jnp.ones((cfg.dim,), dtype=jnp.float32) - cfg.norm_offset,
            "mlp_norm": jnp.ones((cfg.dim,), dtype=jnp.float32) - cfg.norm_offset,
            "wq": _dense_init(nxt(), cfg.dim, q_dim, dt),
            "wk": _dense_init(nxt(), cfg.dim, kv_dim, dt),
            "wv": _dense_init(nxt(), cfg.dim, kv_dim, dt),
            "wo": _dense_init(nxt(), q_dim, cfg.dim, dt),
        }
        if cfg.n_experts:
            from .moe import MoeConfig, init_moe_layer
            layer["moe"] = init_moe_layer(
                jax.random.fold_in(nxt(), li), _moe_cfg(cfg))
            nxt(), nxt()   # keep the rng schedule aligned with dense
        else:
            layer["w_gate"] = _dense_init(nxt(), cfg.dim, cfg.hidden_dim, dt)
            layer["w_up"] = _dense_init(nxt(), cfg.dim, cfg.hidden_dim, dt)
            layer["w_down"] = _dense_init(nxt(), cfg.hidden_dim, cfg.dim, dt)
        params["layers"].append(layer)
    return params


def _moe_cfg(cfg: DecoderConfig):
    from .moe import MoeConfig
    return MoeConfig(dim=cfg.dim, hidden_dim=cfg.hidden_dim,
                     n_experts=cfg.n_experts, top_k=cfg.moe_top_k,
                     capacity_factor=cfg.moe_capacity_factor,
                     act=cfg.act, dtype=cfg.dtype)


def init_kv_cache(cfg: DecoderConfig, batch: int, max_len: int = 0,
                  dtype=None) -> Params:
    """Contiguous per-sequence KV cache: k/v [L, B, S, KH, D]."""
    s = max_len or cfg.max_seq_len
    dt = dtype or cfg.dtype
    shape = (cfg.n_layers, batch, s, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype=dt), "v": jnp.zeros(shape, dtype=dt)}


def _act(x: jnp.ndarray, kind: str) -> jnp.ndarray:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(kind)


# Device scopes (ISSUE 24): the ``jax.named_scope`` names the forward pass
# runs under — they reach every HLO instruction's ``op_name``, so a
# profiler trace's device time can be summed by part of the model.
# ``serving.graphs`` adds ``kv.gather``/``kv.splice`` around the pool
# plumbing; norms, the rng split and the length update carry none. Since
# ISSUE 25 carries the cache whole, nothing runs under ``kv.pack`` and
# ``kv.slice`` is only the read of a dense cache at its layer — both stay
# declared, because the benchmark's readers sum the ``kv.*`` names.
DEVICE_SCOPES = ("embed", "attn.qkv", "attn.rope", "kv.slice", "kv.write",
                 "kv.pack", "kv.gather", "kv.splice", "attn.core",
                 "attn.out", "ffn", "moe.route", "moe.experts",
                 "moe.combine", "head", "sample")


def _pool_write(pool: jnp.ndarray, layer_idx: int, bi, oi, value):
    """The paged pool ``[L, N, BS, ...]`` with ``value`` written at
    ``[layer_idx, bi, oi]``: a scatter into the whole array, which XLA
    does in place on a donated or carried pool — no plane is cut out and
    none is stacked back."""
    with jax.named_scope("kv.write"):
        return pool.at[layer_idx, bi, oi].set(value)


def _cache_write(cache: jnp.ndarray, layer_idx: int, item, positions):
    """The dense cache ``[L, B, S, KH, D]`` with ``item`` ``[B, T, KH, D]``
    written at ``layer_idx``, each row's ``T`` tokens from that row's first
    position on: ``dynamic_update_slice`` into the whole array at batch 1,
    a scatter with the same clamp of the start over several rows."""
    b, t = item.shape[:2]
    with jax.named_scope("kv.write"):
        if b == 1:
            return jax.lax.dynamic_update_slice(
                cache, item[None], (layer_idx, 0, positions[0, 0], 0, 0))
        start = jnp.clip(positions[:, :1], 0, cache.shape[2] - t)
        return cache.at[layer_idx, jnp.arange(b)[:, None],
                        start + jnp.arange(t)].set(item)


def _cache_read(cache: jnp.ndarray, layer_idx: int):
    """One layer's plane of a dense cache, for an attention that takes
    ``[B, S, KH, D]``: an XLA consumer fuses the slice; the ragged pallas
    kernel has it materialised."""
    with jax.named_scope("kv.slice"):
        return cache[layer_idx]


def _attn_block(layer: Params, x: jnp.ndarray, cfg: DecoderConfig,
                positions: jnp.ndarray, sin, cos,
                kv_cache: Optional[Params], layer_idx: int,
                cache_len: Optional[jnp.ndarray], decode: bool,
                mesh=None):
    """One layer's attention. Returns ``(x, kv_cache)``: the cache dict is
    carried WHOLE from layer to layer — every branch writes this layer's
    k/v into the ``[L, ...]`` arrays in place and reads them at
    ``layer_idx``; nothing is sliced out and re-stacked."""
    b, t, _ = x.shape
    h = rms_norm(x, layer["attn_norm"], cfg.norm_eps, cfg.norm_offset)
    with jax.named_scope("attn.qkv"):
        q = maybe_matmul(h, layer["wq"]).reshape(
            b, t, cfg.n_heads, cfg.head_dim)
        k = maybe_matmul(h, layer["wk"]).reshape(
            b, t, cfg.n_kv_heads, cfg.head_dim)
        v = maybe_matmul(h, layer["wv"]).reshape(
            b, t, cfg.n_kv_heads, cfg.head_dim)
    with jax.named_scope("attn.rope"):
        q = apply_rope(q, positions, sin, cos)
        k = apply_rope(k, positions, sin, cos)

    if kv_cache is None:
        with jax.named_scope("attn.core"):
            out = attention(q, k, v, causal=True, mesh=mesh)
    elif "table" in kv_cache:
        # paged: scatter the window's k/v into the slots' physical pool
        # blocks, then attend over each slot's block table. Pool layout
        # [L, N_BLOCKS, BS, KH, D] is shared by all sequences — prefix
        # blocks can be referenced by many tables (prefix reuse). An int8
        # pool ("k_scale" present) quantizes the write per (token, head)
        # vector and the attention dequantizes after the block read.
        #
        # decode (T = 1): block-table paged attention over the prefix.
        # Otherwise a multi-token VERIFY (speculative decoding): all T
        # window tokens are written in one shot and each query attends
        # over its own absolute-position prefix. Rejected draft positions
        # simply hold garbage KV after the window — attention masks by
        # position, and the next window's writes overwrite them (paged
        # scratch re-splice semantics).
        from ..ops.attention import (paged_attention_dispatch,
                                     paged_verify_attention)
        table = kv_cache["table"]                      # [B, MB]
        bs = kv_cache["k"].shape[2]                    # [L,N,BS,KH,D]
        if decode:
            pos = positions[:, 0]                      # [B]
            bi = table[jnp.arange(b), pos // bs]
            k, v = k[:, 0], v[:, 0]                    # [B,KH,D]
        else:
            pos = positions                            # [B,T]
            bi = jnp.take_along_axis(table, pos // bs, axis=1)
        oi = pos % bs
        kv_cache = dict(kv_cache)
        scales = ()
        if "k_scale" in kv_cache:
            with jax.named_scope("kv.write"):
                k, sk = quantize_kv(k)                 # [..,KH,D], [..,KH]
                v, sv = quantize_kv(v)
            for name, sc in (("k_scale", sk), ("v_scale", sv)):
                kv_cache[name] = _pool_write(kv_cache[name], layer_idx,
                                             bi, oi, sc)
            scales = (kv_cache["k_scale"], kv_cache["v_scale"])
        kv_cache["k"] = _pool_write(kv_cache["k"], layer_idx, bi, oi, k)
        kv_cache["v"] = _pool_write(kv_cache["v"], layer_idx, bi, oi, v)
        with jax.named_scope("attn.core"):
            if decode:
                out = paged_attention_dispatch(
                    q, kv_cache["k"], kv_cache["v"], table, cache_len,
                    *scales, mesh=mesh, layer=layer_idx)
            else:
                out = paged_verify_attention(
                    q, kv_cache["k"], kv_cache["v"], table, positions,
                    *scales, layer=layer_idx)
    else:
        # dense cache [L, B, S, KH, D]. Decode: this token's k/v at each
        # row's position, then attention over the prefix. CHUNKED prefill
        # (cache_len given): this chunk at its PER-ROW offset, then
        # attention over prefix + chunk with the absolute-position mask —
        # graph shapes are (C, S) no matter how long the prompt is; the
        # engine admits chunks at batch 1, but the signature accepts
        # [B, C] positions, and row 0's offset applied to every row would
        # write other rows' chunks at the wrong cache slots (silently
        # wrong logits), so the write is per row. Whole-prompt prefill:
        # [0, t), then causal attention within the prompt itself.
        kv_cache = dict(
            kv_cache,
            k=_cache_write(kv_cache["k"], layer_idx, k, positions),
            v=_cache_write(kv_cache["v"], layer_idx, v, positions))
        if not decode and cache_len is None:
            with jax.named_scope("attn.core"):
                out = attention(q, k, v, causal=True, mesh=mesh)
        else:
            k_cache = _cache_read(kv_cache["k"], layer_idx)
            v_cache = _cache_read(kv_cache["v"], layer_idx)
            with jax.named_scope("attn.core"):
                if decode:
                    out = decode_attention(q, k_cache, v_cache, cache_len,
                                           mesh=mesh)
                else:
                    from ..ops.attention import chunk_prefill_attention
                    out = chunk_prefill_attention(q, k_cache, v_cache,
                                                  positions)

    with jax.named_scope("attn.out"):
        out = out.reshape(b, t, cfg.n_heads * cfg.head_dim)
        return x + maybe_matmul(out, layer["wo"]), kv_cache


def _mlp_block(layer: Params, x: jnp.ndarray, cfg: DecoderConfig):
    h = rms_norm(x, layer["mlp_norm"], cfg.norm_eps, cfg.norm_offset)
    if cfg.n_experts:
        from .moe import moe_ffn
        y, aux = moe_ffn(layer["moe"], h, _moe_cfg(cfg), ep_sharded=False)
        with jax.named_scope("moe.combine"):
            return x + y, aux
    with jax.named_scope("ffn"):
        gated = _act(maybe_matmul(h, layer["w_gate"]), cfg.act) \
            * maybe_matmul(h, layer["w_up"])
        return x + maybe_matmul(gated, layer["w_down"]), None


def decoder_forward(params: Params, tokens: jnp.ndarray, cfg: DecoderConfig,
                    positions: Optional[jnp.ndarray] = None,
                    kv_cache: Optional[Params] = None,
                    cache_len: Optional[jnp.ndarray] = None,
                    decode: bool = False,
                    return_hidden: bool = False,
                    return_moe_aux: bool = False,
                    mesh=None):
    """Run the decoder.

    - train/eval: ``decoder_forward(params, tokens, cfg)`` → logits [B,T,V]
    - prefill:   pass ``kv_cache`` (positions default to arange) → (logits, cache)
    - decode:    ``decode=True`` with tokens [B,1], positions [B,1], cache_len [B]
                 → (logits [B,1,V], cache)
    - ``mesh``:  the serving mesh when params and cache are sharded over one
                 (``MeshPolicy.mesh``) — the attention kernels then run per
                 chip on its own heads
    """
    b, t = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))

    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.dim ** 0.5, dtype=cfg.dtype)

    # the rope table must cover every cache slot: positions past the table
    # are CLAMPED by JAX's gather, rotating distinct positions identically
    # (silent long-context degradation, no error) — catch the static-shape
    # mismatch at trace time instead
    rope_len = cfg.max_seq_len
    if kv_cache is not None and "table" not in kv_cache:
        cache_s = kv_cache["k"].shape[2]
        if cache_s > rope_len:
            raise ValueError(
                f"kv cache length {cache_s} exceeds rope table "
                f"{rope_len} — positions past it would alias")
    with jax.named_scope("attn.rope"):
        sin, cos = rope_table(rope_len, cfg.head_dim, cfg.rope_theta)

    moe_balance = jnp.zeros((), jnp.float32)
    for i, layer in enumerate(params["layers"]):
        x, kv_cache = _attn_block(layer, x, cfg, positions, sin, cos,
                                  kv_cache, i, cache_len, decode, mesh)
        x, aux = _mlp_block(layer, x, cfg)
        if aux is not None:
            moe_balance = moe_balance + aux["balance_loss"]

    x = rms_norm(x, params["final_norm"], cfg.norm_eps, cfg.norm_offset)
    if return_hidden:
        logits = None
    else:
        with jax.named_scope("head"):
            if cfg.tie_embeddings:
                logits = (x @ params["embed"].T.astype(cfg.dtype)).astype(
                    jnp.float32)
            else:
                logits = maybe_matmul(x, params["lm_head"]).astype(
                    jnp.float32)
            if cfg.logit_softcap > 0:
                logits = cfg.logit_softcap * jnp.tanh(
                    logits / cfg.logit_softcap)

    out = x if return_hidden else logits

    if return_moe_aux:
        # mean balance loss across layers (training regularizer)
        aux = moe_balance / max(cfg.n_layers, 1)
        if kv_cache is not None:
            return out, kv_cache, aux
        return out, aux
    if kv_cache is not None:
        return out, kv_cache
    return out


def count_params(params: Params) -> int:
    return sum(p.size for p in jax.tree_util.tree_leaves(params))
