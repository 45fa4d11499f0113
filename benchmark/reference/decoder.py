"""Plain reference for decoder-only transformers of the Mistral family:
Mistral-7B (dense SwiGLU FFN) and Mixtral-8x7B (sparse top-k experts).

Written from the published equations (Jiang et al., "Mistral 7B",
arXiv:2310.06825; "Mixtral of Experts", arXiv:2401.04088, section 2.1) in
plain ``jax.numpy``: float32 throughout, matmuls at ``highest`` precision,
no cache, no paging, no kernels, no expert capacity. It imports nothing from
``tpu9``: the only thing it shares with the program is the layout of the
weight tree, which it has to read.

    x_0   = E[tokens]
    a_l   = x_l + W_o . Attn(RoPE(W_q n), RoPE(W_k n), W_v n),  n = RMSNorm(x_l)
    x_l+1 = a_l + FFN(RMSNorm(a_l))
    FFN(h) = W_down (silu(W_gate h) * W_up h)                       (dense)
    FFN(h) = sum_{e in top_k(softmax(W_r h))} g_e/sum(g) . FFN_e(h)  (experts)
    logits = W_head RMSNorm(x_L)

Attention is causal softmax(q k^T / sqrt(d)) with grouped queries: query head
i reads KV head i // (n_heads / n_kv_heads). RoPE is the half-split form of
the released checkpoints (rotate_half): dimension i pairs with i + d/2.

Weight tree (tpu9's, every matrix stored [in, out]): ``embed`` [V, D],
``lm_head`` [D, V], ``final_norm`` [D], and per layer ``attn_norm``,
``mlp_norm``, ``wq``, ``wk``, ``wv``, ``wo``, then either ``w_gate``,
``w_up``, ``w_down`` or ``moe`` = {``router`` [D, E], ``w_gate`` [E, D, H],
``w_up`` [E, D, H], ``w_down`` [E, H, D]}.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _mm(x, w):
    return jnp.matmul(x, w.astype(F32), precision=jax.lax.Precision.HIGHEST)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight.astype(F32)


def _rope(x, theta):
    """x [T, H, d] at positions 0..T-1."""
    t, _, d = x.shape
    half = d // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv_freq[None, :]   # [T, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(layer, n, model):
    t = n.shape[0]
    heads, kv_heads = model["num_attention_heads"], model["num_key_value_heads"]
    d = model["head_dim"]
    q = _rope(_mm(n, layer["wq"]).reshape(t, heads, d), model["rope_theta"])
    k = _rope(_mm(n, layer["wk"]).reshape(t, kv_heads, d), model["rope_theta"])
    v = _mm(n, layer["wv"]).reshape(t, kv_heads, d)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) / (d ** 0.5)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v,
                     precision=jax.lax.Precision.HIGHEST)
    return _mm(out.reshape(t, heads * d), layer["wo"])


def _swiglu(h, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(h, w_gate)) * _mm(h, w_up), w_down)


def _experts(moe, h, model):
    """Every token through its top-k experts, weights renormalised over the
    chosen k. No capacity: each expert is applied to all tokens and masked."""
    k = model["num_experts_per_tok"]
    probs = jax.nn.softmax(_mm(h, moe["router"]), axis=-1)        # [T, E]
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    def one_expert(out, e):
        weight = jnp.sum(jnp.where(top_i == e, top_p, 0.0), axis=-1)  # [T]
        return out + weight[:, None] * _swiglu(
            h, moe["w_gate"][e], moe["w_up"][e], moe["w_down"][e]), None

    # a sequential loop, so that only one expert is held in float32 at a time
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          jnp.arange(model["num_local_experts"]))
    return out


def forward(params, tokens, model: dict):
    """Logits [T, V] in float32 for one sequence ``tokens`` [T]."""
    eps = model["rms_norm_eps"]
    x = params["embed"].astype(F32)[tokens]
    for layer in params["layers"]:
        x = x + _attention(layer, _rms_norm(x, layer["attn_norm"], eps), model)
        h = _rms_norm(x, layer["mlp_norm"], eps)
        if "moe" in layer:
            x = x + _experts(layer["moe"], h, model)
        else:
            x = x + _swiglu(h, layer["w_gate"], layer["w_up"],
                            layer["w_down"])
    return _mm(_rms_norm(x, params["final_norm"], eps), params["lm_head"])
