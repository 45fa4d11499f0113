"""Plain reference for looped decoder-only transformers (Ouro / LoopLM:
Zhu et al., "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741, and the ``modeling_ouro.py`` published beside the
checkpoints): one set of layers run ``R = total_ut_steps`` times a token.

Written from the published equations in plain ``jax.numpy``: float32
throughout, matmuls at ``highest`` precision, no cache, no paging, no
kernels. It imports nothing from ``tpu9``; the plain helpers (matmul, RMS
norm, rotary, causal attention, SwiGLU) are those of the decoder reference.

    x          = E[tokens]
    for pass u = 0 .. R-1, for layer l = 0 .. L-1 (the same weights in every pass):
      a        = x + N2_l( W_o . Attn(RoPE(W_q n), RoPE(W_k n), W_v n) ),  n = N1_l(x)
      x        = a + N4_l( W_down (silu(W_gate m) * W_up m) ),             m = N3_l(a)
    h_u        = N_f(x)          # closes EVERY pass; pass u+1 starts from h_u
    lambda_u   = sigmoid(w_g . h_u + b_g)
    p_u        = lambda_u prod_{j<u}(1 - lambda_j)  (u < R-1),  p_{R-1} = prod_{j<R-1}(1 - lambda_j)
    exit step  = first u with sum_{j<=u} p_j >= early_exit_threshold, else R-1
    logits     = W_head h_{exit step}

With no cache, pass ``u`` at layer ``l`` attends over the keys and values
that the same pass and layer computed for the earlier positions: what a
cache indexed by ``u L + l`` holds.

Weight tree (tpu9's, every matrix stored [in, out]): ``embed`` [V, D],
``lm_head`` [D, V], ``final_norm`` [D] (N_f), ``exit_gate`` = {``w`` [D],
``b`` [1]}, and per layer ``attn_norm`` (N1), ``attn_post_norm`` (N2),
``mlp_norm`` (N3), ``mlp_post_norm`` (N4), ``wq``, ``wk``, ``wv``, ``wo``,
``w_gate``, ``w_up``, ``w_down``.

Departures from the published code, none of which changes a number:
- the embedding row is taken before the cast to float32 (the published code
  casts nothing: it runs in the checkpoint's type), so that the whole table
  is never held in float32 beside a nearly full chip;
- the passes are a ``fori_loop`` and not a Python loop, so that the 48 layer
  bodies are compiled once and one layer's float32 weights are live at a
  time; the published code collects every pass's state and picks afterwards,
  here the pick rides the loop (same rule, same order of the sums);
- the published code returns the last pass's logits unless early exit is
  asked for; with ``early_exit_threshold`` 1.0 the rule below picks the
  last pass too, except where a gate saturates to exactly 1.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.decoder import (F32, _attention, _mm, _rms_norm,
                                         _swiglu)


def _layer(layer, x, model):
    eps = model["rms_norm_eps"]
    n = _rms_norm(x, layer["attn_norm"], eps)
    a = x + _rms_norm(_attention(layer, n, model), layer["attn_post_norm"],
                      eps)
    m = _rms_norm(a, layer["mlp_norm"], eps)
    return a + _rms_norm(
        _swiglu(m, layer["w_gate"], layer["w_up"], layer["w_down"]),
        layer["mlp_post_norm"], eps)


def forward(params, tokens, model: dict):
    """Logits [T, V] in float32 for one sequence ``tokens`` [T]."""
    eps = model["rms_norm_eps"]
    steps = model["total_ut_steps"]
    threshold = float(model["early_exit_threshold"])
    gate = params["exit_gate"]
    t = tokens.shape[0]

    def one_pass(u, carry):
        x, remaining, cdf, chosen, selected = carry
        for layer in params["layers"]:
            x = _layer(layer, x, model)
        h = _rms_norm(x, params["final_norm"], eps)
        lam = jax.nn.sigmoid(jnp.sum(h * gate["w"].astype(F32), axis=-1)
                             + gate["b"].astype(F32)[0])
        p = jnp.where(u == steps - 1, remaining, lam * remaining)
        cdf = cdf + p
        take = ~chosen & ((cdf >= threshold) | (u == steps - 1))
        selected = jnp.where(take[:, None], h, selected)
        return h, remaining * (1.0 - lam), cdf, chosen | take, selected

    x = params["embed"][tokens].astype(F32)
    carry = (x, jnp.ones((t,), F32), jnp.zeros((t,), F32),
             jnp.zeros((t,), bool), jnp.zeros_like(x))
    selected = jax.lax.fori_loop(0, steps, one_pass, carry)[-1]
    return _mm(selected, params["lm_head"])
