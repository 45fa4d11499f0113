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
# plumbing; norms, the rng split and the length update carry none.
DEVICE_SCOPES = ("embed", "attn.qkv", "attn.rope", "kv.slice", "kv.write",
                 "kv.pack", "kv.gather", "kv.splice", "attn.core",
                 "attn.out", "ffn", "moe.route", "moe.experts",
                 "moe.combine", "head", "sample")


def _pool_write(pool: jnp.ndarray, layer_idx: int, idx, value):
    """One layer's plane of the paged pool with ``value`` written at
    ``idx``: the per-layer read of the pool, then the write."""
    with jax.named_scope("kv.slice"):
        plane = pool[layer_idx]
    with jax.named_scope("kv.write"):
        return plane.at[idx].set(value)


def _cache_write(cache: jnp.ndarray, layer_idx: int, item, start):
    """One layer's plane of a dense cache with ``item`` written at
    ``start`` (``dynamic_update_slice``)."""
    with jax.named_scope("kv.slice"):
        plane = cache[layer_idx]
    with jax.named_scope("kv.write"):
        return jax.lax.dynamic_update_slice(plane, item, start)


def _attn_block(layer: Params, x: jnp.ndarray, cfg: DecoderConfig,
                positions: jnp.ndarray, sin, cos,
                kv_cache: Optional[Params], layer_idx: int,
                cache_len: Optional[jnp.ndarray], decode: bool,
                mesh=None):
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

    new_cache = None
    if kv_cache is None:
        with jax.named_scope("attn.core"):
            out = attention(q, k, v, causal=True, mesh=mesh)
    elif decode and "table" in kv_cache:
        # paged decode: scatter this token's k/v into the slot's physical
        # pool block, then block-table paged attention over the prefix.
        # Pool layout [N_BLOCKS, BS, KH, D] is shared by all sequences —
        # prefix blocks can be referenced by many tables (prefix reuse).
        # An int8 pool ("k_scale" present) quantizes the write per
        # (token, head) vector and the attention dequantizes in-kernel.
        from ..ops.attention import paged_attention_dispatch
        table = kv_cache["table"]                      # [B, MB]
        bs = kv_cache["k"].shape[2]                    # [L,N,BS,KH,D]
        pos = positions[:, 0]                          # [B]
        rows = jnp.arange(b)
        bi = table[rows, pos // bs]
        oi = pos % bs
        if "k_scale" in kv_cache:
            with jax.named_scope("kv.write"):
                qk, sk = quantize_kv(k[:, 0])          # [B,KH,D], [B,KH]
                qv, sv = quantize_kv(v[:, 0])
            k_pool = _pool_write(kv_cache["k"], layer_idx, (bi, oi), qk)
            v_pool = _pool_write(kv_cache["v"], layer_idx, (bi, oi), qv)
            k_sc = _pool_write(kv_cache["k_scale"], layer_idx, (bi, oi), sk)
            v_sc = _pool_write(kv_cache["v_scale"], layer_idx, (bi, oi), sv)
            with jax.named_scope("attn.core"):
                out = paged_attention_dispatch(q, k_pool, v_pool, table,
                                               cache_len, k_sc, v_sc,
                                               mesh=mesh)
            new_cache = (k_pool, v_pool, k_sc, v_sc)
        else:
            k_pool = _pool_write(kv_cache["k"], layer_idx, (bi, oi), k[:, 0])
            v_pool = _pool_write(kv_cache["v"], layer_idx, (bi, oi), v[:, 0])
            with jax.named_scope("attn.core"):
                out = paged_attention_dispatch(q, k_pool, v_pool, table,
                                               cache_len, mesh=mesh)
            new_cache = (k_pool, v_pool)
    elif "table" in kv_cache:
        # paged multi-token VERIFY (speculative decoding): scatter all T
        # window tokens' k/v into the slots' physical pool blocks in one
        # shot, then attend each query over its own absolute-position
        # prefix. Rejected draft positions simply hold garbage KV after
        # the window — attention masks by position, and the next window's
        # writes overwrite them (paged scratch re-splice semantics).
        from ..ops.attention import paged_verify_attention
        table = kv_cache["table"]                      # [B, MB]
        bs = kv_cache["k"].shape[2]                    # [L,N,BS,KH,D]
        bi = jnp.take_along_axis(table, positions // bs, axis=1)  # [B,T]
        oi = positions % bs
        if "k_scale" in kv_cache:
            with jax.named_scope("kv.write"):
                qk, sk = quantize_kv(k)                # [B,T,KH,D],[B,T,KH]
                qv, sv = quantize_kv(v)
            k_pool = _pool_write(kv_cache["k"], layer_idx, (bi, oi), qk)
            v_pool = _pool_write(kv_cache["v"], layer_idx, (bi, oi), qv)
            k_sc = _pool_write(kv_cache["k_scale"], layer_idx, (bi, oi), sk)
            v_sc = _pool_write(kv_cache["v_scale"], layer_idx, (bi, oi), sv)
            with jax.named_scope("attn.core"):
                out = paged_verify_attention(q, k_pool, v_pool, table,
                                             positions, k_sc, v_sc)
            new_cache = (k_pool, v_pool, k_sc, v_sc)
        else:
            k_pool = _pool_write(kv_cache["k"], layer_idx, (bi, oi), k)
            v_pool = _pool_write(kv_cache["v"], layer_idx, (bi, oi), v)
            with jax.named_scope("attn.core"):
                out = paged_verify_attention(q, k_pool, v_pool, table,
                                             positions)
            new_cache = (k_pool, v_pool)
    elif decode:
        # scatter this token's k/v at positions, then attend over the prefix
        k_cache = _cache_write(
            kv_cache["k"], layer_idx, k,
            (0, positions[0, 0], 0, 0)) if b == 1 else _scatter_kv(
                kv_cache["k"][layer_idx], k, positions)
        v_cache = _cache_write(
            kv_cache["v"], layer_idx, v,
            (0, positions[0, 0], 0, 0)) if b == 1 else _scatter_kv(
                kv_cache["v"][layer_idx], v, positions)
        with jax.named_scope("attn.core"):
            out = decode_attention(q, k_cache, v_cache, cache_len, mesh=mesh)
        new_cache = (k_cache, v_cache)
    elif cache_len is not None:
        # CHUNKED prefill: write this chunk at its PER-ROW offset, then
        # attend over prefix + chunk with the absolute-position mask —
        # graph shapes are (C, S) no matter how long the prompt is. The
        # engine admits chunks at batch 1, but the signature accepts
        # [B, C] positions: applying row 0's offset to every row would
        # write other rows' chunks at the wrong cache slots (and their
        # queries would then mask out their own chunk) — silently wrong
        # logits, so scatter per row.
        from ..ops.attention import chunk_prefill_attention
        if b == 1:
            off = positions[0, 0]
            k_cache = _cache_write(kv_cache["k"], layer_idx, k,
                                   (0, off, 0, 0))
            v_cache = _cache_write(kv_cache["v"], layer_idx, v,
                                   (0, off, 0, 0))
        else:
            def write_chunk(c, item, off0):
                return jax.lax.dynamic_update_slice(c, item, (off0, 0, 0))

            with jax.named_scope("kv.write"):
                k_cache = jax.vmap(write_chunk)(
                    kv_cache["k"][layer_idx], k, positions[:, 0])
                v_cache = jax.vmap(write_chunk)(
                    kv_cache["v"][layer_idx], v, positions[:, 0])
        with jax.named_scope("attn.core"):
            out = chunk_prefill_attention(q, k_cache, v_cache, positions)
        new_cache = (k_cache, v_cache)
    else:
        # prefill: write [0, t) then causal-attend within the prefix
        k_cache = _cache_write(kv_cache["k"], layer_idx, k, (0, 0, 0, 0))
        v_cache = _cache_write(kv_cache["v"], layer_idx, v, (0, 0, 0, 0))
        with jax.named_scope("attn.core"):
            out = attention(q, k, v, causal=True, mesh=mesh)
        new_cache = (k_cache, v_cache)

    with jax.named_scope("attn.out"):
        out = out.reshape(b, t, cfg.n_heads * cfg.head_dim)
        return x + maybe_matmul(out, layer["wo"]), new_cache


def _scatter_kv(cache: jnp.ndarray, kv: jnp.ndarray,
                positions: jnp.ndarray) -> jnp.ndarray:
    """Per-sequence scatter of one token: cache [B,S,KH,D], kv [B,1,KH,D],
    positions [B,1]."""
    idx = positions[:, 0]

    def write_one(c, item, i):
        return jax.lax.dynamic_update_slice(c, item, (i, 0, 0))

    with jax.named_scope("kv.write"):
        return jax.vmap(write_one)(cache, kv, idx)


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

    updates: list = []        # per-layer (k, v[, k_scale, v_scale]) tuples
    moe_balance = jnp.zeros((), jnp.float32)
    for i, layer in enumerate(params["layers"]):
        x, updated = _attn_block(layer, x, cfg, positions, sin, cos,
                                 kv_cache, i, cache_len, decode, mesh)
        if updated is not None:
            updates.append(updated)
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

    @jax.named_scope("kv.pack")
    def _pack_cache():
        cache = {"k": jnp.stack([u[0] for u in updates]),
                 "v": jnp.stack([u[1] for u in updates])}
        if updates and len(updates[0]) == 4:     # int8 pool: scales ride
            cache["k_scale"] = jnp.stack([u[2] for u in updates])
            cache["v_scale"] = jnp.stack([u[3] for u in updates])
        if "table" in (kv_cache or {}):
            cache["table"] = kv_cache["table"]   # paged: table rides along
        return cache

    if return_moe_aux:
        # mean balance loss across layers (training regularizer)
        aux = moe_balance / max(cfg.n_layers, 1)
        if kv_cache is not None:
            return out, _pack_cache(), aux
        return out, aux
    if kv_cache is not None:
        return out, _pack_cache()
    return out


def count_params(params: Params) -> int:
    return sum(p.size for p in jax.tree_util.tree_leaves(params))
