"""Plain reference for the hybrid state-space decoder (``model_type:
granitemoehybrid`` with no routed experts; Granite 4.0-H Micro's
``config.json`` under https://huggingface.co/ibm-granite): Mamba-2 mixers (arXiv:2405.21060) in
the layers ``layer_types`` calls ``"mamba"``, grouped-query attention WITHOUT
positions in those it calls ``"attention"``, every layer closed by the same
dense SwiGLU, and four multipliers (on the embeddings, on every branch, in
the softmax, under the logits).

Written from the equations in plain ``jax.numpy``: float32 throughout,
matmuls at ``highest`` precision, the recurrence as a plain scan over tokens
(NOT the chunked form), no cache, no state carried between calls, no
kernels, nothing imported from ``tpu9``. It works in blocks — queries
``QUERY_BLOCK`` at a time, the tied head's vocabulary ``VOCAB_BLOCK`` rows at
a time — only so that it fits beside a served model that fills three
quarters of a chip; a block changes no sum's terms.

    x_0 = embedding_multiplier . E[tokens]
    a_l = x_l + residual_multiplier . Mixer_l(N(x_l))
    x_l+1 = a_l + residual_multiplier . W_down(silu(W_gate N(a_l)) * W_up N(a_l))
    logits = N(x_L) E^T / logits_scaling                    (tie_word_embeddings)
    N = RMSNorm, eps = rms_norm_eps; SwiGLU of shared_intermediate_size.

Mamba-2 mixer, ``u = N(x)`` ``[T, D]``; H = mamba_n_heads, P = mamba_d_head,
N = mamba_d_state, G = mamba_n_groups, K = mamba_d_conv, d_inner = H P =
mamba_expand x D:

    [z | xBC | dt] = u W_in          widths d_inner | d_inner + 2 G N | H   (mamba_proj_bias false)
    xBC_t = silu( sum_{j<K} w_j o xBC_{t-(K-1)+j} + b_conv )          depthwise, causal, zeros before the start
    [x | B | C] = xBC;   x [T, H, P];  B, C [T, G, N]  (head h reads group h G / H)
    dt_t = softplus(dt_t + dt_bias) [H];   A_h = -exp(A_log_h)
    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t    in R^{P x N} a head, S_{-1} = 0
    y_t = S_t C_t + D_h x_t
    out = RMSNorm_w(y * silu(z)) W_out               the gate BEFORE the norm, over all d_inner (one group)

Attention layer, H_q = num_attention_heads over H_kv = num_key_value_heads
heads of d = hidden_size / num_attention_heads, no biases, NO positions
(``position_embedding_type: "nope"``; ``rope_theta`` is unused):

    score_h(t, s) = q_h(t) . k_{h H_kv / H_q}(s) . attention_multiplier,   causal softmax
    y_t = W_o [ sum_s p_h(t, s) v(s) ]_h

``attention_multiplier`` is the softmax scale itself (0.015625 = 1/64 at
d = 64), NOT ``d^-1/2``.

Assumed, each stated in the configuration's file under ``assumed``: bfloat16
weights; ``head_dim`` = hidden_size / num_attention_heads; the recurrent
state in float32 (this reference's type, and the served program's); the
gate before the norm and one norm group; no clamp on ``dt`` (the public
code's default limits are (0, inf)); the seeded initialisation.

Builder's controls in ``model["control"]``, never set by a configuration
(``tools/probe_controls.py`` sets them to show that the comparison that
decides ``correct`` tells them from the sound program): ``int8_weights``
(every matrix rounded to int8 and back, per output channel at absmax / 127:
the nearest precision below bfloat16), ``bf16_state`` (the state rounded to
bfloat16 after every token), ``no_decay`` (``A = 0``), ``no_d`` (``D = 0``),
``sqrt_scale`` (``d^-1/2`` in place of ``attention_multiplier``), ``rotary``
(rotary positions, half-split, applied to q and k), ``residual_one``
(``residual_multiplier`` 1).

Weight tree (tpu9's, every matrix stored [in, out]): ``embed`` [V, D] (the
head too), ``final_norm`` [D]; a layer has ``attn_norm``, ``mlp_norm``,
``w_gate``, ``w_up``, ``w_down`` and either ``ssm`` = {``w_in`` [D, 2 d_inner
+ 2 G N + H], ``conv`` [K, d_inner + 2 G N], ``conv_bias``, ``dt_bias`` [H],
``a_log`` [H], ``d_skip`` [H], ``norm`` [d_inner], ``w_out`` [d_inner, D]}
or ``wq``, ``wk``, ``wv``, ``wo``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
# queries a block of the attention: float32 scores [H_q, 512, T]
QUERY_BLOCK = 512
# rows of the tied head a block: the table is never whole in float32
VOCAB_BLOCK = 12544


def _control(model, name):
    return name in model.get("control", ())


def _w(w, model):
    """A matrix as the reference uses it: float32, or (control) rounded to
    int8 and back per output channel first."""
    w = w.astype(F32)
    if _control(model, "int8_weights") and w.ndim >= 2:
        scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        scale = jnp.where(scale > 0, scale, 1.0)
        w = jnp.round(w / scale) * scale
    return w


def _mm(x, w, model):
    return jnp.matmul(x, _w(w, model), precision=HIGHEST)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight.astype(F32)


def recurrence(x, dt, a_head, bm, cm, model=None):
    """The state-space recurrence, a token at a time from zero state: ``x``
    [T, H, P], ``dt`` [T, H], ``a_head`` [H], ``bm, cm`` [T, G, N]. Returns
    ``y`` [T, H, P] (without the skip)."""
    t, h, p = x.shape
    g, n = bm.shape[1:]
    round_state = model is not None and _control(model, "bf16_state")

    def one(s, xs):
        xt, dtt, bt, ct = xs
        bt, ct = (jnp.repeat(m, h // g, axis=0) for m in (bt, ct))   # [H, N]
        s = jnp.exp(dtt * a_head)[:, None, None] * s \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        if round_state:
            # (``reduce_precision`` and not a cast there and back: the
            # chip's compiler is allowed excess precision and drops such a
            # pair of converts — PR 55's first readings of this control were
            # the sound program's, digit for digit)
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.einsum("hpn,hn->hp", s, ct, precision=HIGHEST)

    _, y = jax.lax.scan(one, jnp.zeros((h, p, n), F32), (x, dt, bm, cm))
    return y


def _mixer(p, u, model):
    t = u.shape[0]
    h, hd = model["mamba_n_heads"], model["mamba_d_head"]
    n, g, k = model["mamba_d_state"], model["mamba_n_groups"], \
        model["mamba_d_conv"]
    inner = h * hd
    width = inner + 2 * g * n
    proj = _mm(u, p["w_in"], model)
    z, xbc, dt = proj[:, :inner], proj[:, inner:inner + width], \
        proj[:, inner + width:]
    padded = jnp.concatenate([jnp.zeros((k - 1, width), F32), xbc], axis=0)
    taps = p["conv"].astype(F32)
    xbc = jax.nn.silu(sum(taps[j] * padded[j:j + t] for j in range(k))
                      + p["conv_bias"].astype(F32))
    x = xbc[:, :inner].reshape(t, h, hd)
    bm = xbc[:, inner:inner + g * n].reshape(t, g, n)
    cm = xbc[:, inner + g * n:].reshape(t, g, n)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))
    a_head = -jnp.exp(p["a_log"].astype(F32))
    if _control(model, "no_decay"):
        a_head = jnp.zeros_like(a_head)
    y = recurrence(x, dt, a_head, bm, cm, model)
    if not _control(model, "no_d"):
        y = y + p["d_skip"].astype(F32)[:, None] * x
    y = (y.reshape(t, inner) * jax.nn.silu(z))
    return _mm(_rms_norm(y, p["norm"], model["rms_norm_eps"]), p["w_out"],
               model)


def _rope(x, theta):
    """(control) x [T, H, d] at positions 0..T-1, half-split rotary."""
    t, _, d = x.shape
    half = d // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(layer, u, model):
    t = u.shape[0]
    heads, kv_heads = model["num_attention_heads"], \
        model["num_key_value_heads"]
    d = model["head_dim"]
    q = _mm(u, layer["wq"], model).reshape(t, heads, d)
    k = _mm(u, layer["wk"], model).reshape(t, kv_heads, d)
    v = _mm(u, layer["wv"], model).reshape(t, kv_heads, d)
    if _control(model, "rotary"):
        q, k = _rope(q, model["rope_theta"]), _rope(k, model["rope_theta"])
    scale = d ** -0.5 if _control(model, "sqrt_scale") \
        else model["attention_multiplier"]
    k, v = (jnp.repeat(a, heads // kv_heads, axis=1) for a in (k, v))
    block = min(QUERY_BLOCK, t)
    pad = (-t) % block
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))

    def rows(first):
        qb = jax.lax.dynamic_slice_in_dim(q, first, block, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * scale
        causal = jnp.arange(t)[None, :] <= first + jnp.arange(block)[:, None]
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                               axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)

    out = jax.lax.map(rows, jnp.arange(0, t + pad, block))
    return _mm(out.reshape(t + pad, heads * d)[:t], layer["wo"], model)


def _swiglu(h, layer, model):
    return _mm(jax.nn.silu(_mm(h, layer["w_gate"], model))
               * _mm(h, layer["w_up"], model), layer["w_down"], model)


def _head(h, embed, model):
    """``h E^T`` a block of the vocabulary's rows at a time."""
    v = embed.shape[0]
    block = VOCAB_BLOCK if v % VOCAB_BLOCK == 0 else v

    def rows(first):
        e = jax.lax.dynamic_slice_in_dim(embed, first, block, axis=0)
        return jnp.matmul(h, _w(e.T, model), precision=HIGHEST)

    out = jax.lax.map(rows, jnp.arange(0, v, block))        # [V / b, T, b]
    return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], v)


def forward(params, tokens, model: dict):
    """Logits [T, V] in float32 for one sequence ``tokens`` [T]."""
    eps = model["rms_norm_eps"]
    res = 1.0 if _control(model, "residual_one") \
        else model["residual_multiplier"]
    # (a token's row is a column of the head's matrix: the control rounds
    # it as the head's, per row)
    x = _w(params["embed"][tokens].T, model).T * model["embedding_multiplier"]
    for layer in params["layers"]:
        u = _rms_norm(x, layer["attn_norm"], eps)
        mixed = _mixer(layer["ssm"], u, model) if "ssm" in layer \
            else _attention(layer, u, model)
        x = x + res * mixed
        x = x + res * _swiglu(_rms_norm(x, layer["mlp_norm"], eps), layer,
                              model)
    return _head(_rms_norm(x, params["final_norm"], eps), params["embed"],
                 model) / model["logits_scaling"]
