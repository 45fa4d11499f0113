"""Plain reference for EvaByte's decoder (``attention_class: "eva"``;
https://huggingface.co/EvaByte/EvaByte, ``config.json`` and the
``modeling_evabyte.py`` / ``eva_prep_kv`` published beside the checkpoint;
Zheng et al., "Efficient Attention via Control Variates", arXiv:2302.04542,
is the attention's paper): a byte-level decoder whose attention is exact
inside a window of ``W = window_size`` positions and reads one summary for
every ``C = chunk_size`` positions of every earlier window.

Written from the equations in plain ``jax.numpy``, in POSITION coordinates:
float32 throughout, matmuls at ``highest`` precision, no cache, no paging, no
kernels, nothing imported from ``tpu9``. The plain helpers (matmul, rotary,
SwiGLU) are those of the decoder reference.

    x_0   = E[tokens]
    a_l   = x_l + W_o . Attn_l(N(x_l)),   x_l+1 = a_l + W_down (silu(W_gate m) * W_up m),  m = N(a_l)
    N(x)  = x / rms(x) * (1 + g)                                  (norm_add_unit_offset)
    logits = W_head N(x_L)

    q, k, v = W_q n, W_k n, W_v n; rotary on q and k at the true position t
    chunk c = positions C c .. C c + C - 1:
      k~_c = sum_j softmax_j(k_j . mu_h)  k_j        (k after rotary; softmax over the chunk)
      v~_c = sum_j softmax_j(k_j . phi_h) v_j
    query t, with w(t) = t // W:
      S_t = {j : w(j) = w(t), j <= t}                (its own window, causal; the window does not slide)
      R_t = {c : C c < W w(t)}                       (every chunk of every EARLIER window)
      o_t = [sum_{S_t} e^{s q.k_j} v_j + sum_{R_t} e^{s q.k~_c} v~_c] / [sum_{S_t} e^{s q.k_j} + sum_{R_t} e^{s q.k~_c}],  s = d^-1/2

One softmax over the two kinds of keys. Two builder's controls in ``model``,
never set by a configuration (``tools/probe_controls.py`` sets them to show
that the comparison that decides ``correct`` tells them from the sound
program): ``skip_summaries`` leaves ``R_t`` out — what a program that forgot
the summaries would compute; ``int8_weights`` rounds to int8 and back, as it
is used, every matrix that the program's own int8 weight mode stores as int8
(``tpu9.ops.quant.quantize_decoder``: the seven matrices of every layer and
the head; the embedding, a gather, stays), per output channel at absmax /
127, bit-equal to that rounding: the nearest precision below the bfloat16
the configuration states.

Weight tree (tpu9's, every matrix stored [in, out]): ``embed`` [V, D],
``lm_head`` [D, V], ``final_norm`` [D], and per layer ``attn_norm``,
``mlp_norm`` (both the ``g`` above), ``wq``, ``wk``, ``wv``, ``wo``,
``w_gate``, ``w_up``, ``w_down``, ``summary_mu``, ``summary_phi`` [H, d].

Departures from the published code, none of which changes a number: the
published code keeps the window's keys, the summaries and a running
random-feature state in a cache and attends chunk by chunk; here every
query's two key sets are written as masks over the whole sequence (a
``[T, T]`` window mask and a ``[T, T/C]`` summary mask). The queries are
taken ``QUERY_BLOCK`` at a time, so that the float32 scores of 2,746
positions x 32 heads (1 GB at once) fit beside the weights a chip serves.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import decoder
from benchmark.reference.decoder import F32, _rope

QUERY_BLOCK = 256
HIGHEST = jax.lax.Precision.HIGHEST


def _matrices(tree, names, model):
    """``tree``'s matrices ``names`` as the reference multiplies them: as
    stored, or (the ``int8_weights`` control) rounded to int8 and back."""
    if not model.get("int8_weights", False):
        return {n: tree[n] for n in names}

    def rounded(w):
        w = w.astype(F32)
        scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True),
                            1e-8) / 127.0
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale

    return {n: rounded(tree[n]) for n in names}


LAYER_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


_mm = decoder._mm


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + gain.astype(F32))


def _summaries(k, v, mu, phi, chunk):
    """k, v [T, H, d] (T a multiple of ``chunk``) -> k~, v~ [T/C, H, d]."""
    t, heads, d = k.shape
    kc = k.reshape(t // chunk, chunk, heads, d)
    vc = v.reshape(t // chunk, chunk, heads, d)
    wk = jax.nn.softmax(jnp.einsum("cjhd,hd->cjh", kc, mu.astype(F32),
                                   precision=HIGHEST), axis=1)
    wv = jax.nn.softmax(jnp.einsum("cjhd,hd->cjh", kc, phi.astype(F32),
                                   precision=HIGHEST), axis=1)
    return (jnp.einsum("cjh,cjhd->chd", wk, kc, precision=HIGHEST),
            jnp.einsum("cjh,cjhd->chd", wv, vc, precision=HIGHEST))


def _attention(layer, w, n, model):
    t = n.shape[0]
    heads, d = model["num_attention_heads"], model["head_dim"]
    window, chunk = model["window_size"], model["chunk_size"]
    theta = model["rope_theta"]
    q = _rope(_mm(n, w["wq"]).reshape(t, heads, d), theta)
    k = _rope(_mm(n, w["wk"]).reshape(t, heads, d), theta)
    v = _mm(n, w["wv"]).reshape(t, heads, d)

    pad = (0, -t % chunk), (0, 0), (0, 0)
    ks, vs = _summaries(jnp.pad(k, pad), jnp.pad(v, pad),
                        layer["summary_mu"], layer["summary_phi"], chunk)
    key_pos = jnp.arange(t)
    chunk_start = jnp.arange(ks.shape[0]) * chunk
    use_summaries = not model.get("skip_summaries", False)

    def block(first):
        pos = first + jnp.arange(QUERY_BLOCK)                    # [Q]
        qb = jax.lax.dynamic_slice_in_dim(q, first, QUERY_BLOCK) * d ** -0.5
        own = (key_pos[None, :] // window == pos[:, None] // window) \
            & (key_pos[None, :] <= pos[:, None])                 # [Q, T]
        earlier = chunk_start[None, :] < (pos[:, None] // window) * window
        if not use_summaries:
            earlier = jnp.zeros_like(earlier)
        scores = jnp.concatenate([
            jnp.where(own[None], jnp.einsum(
                "qhd,khd->hqk", qb, k, precision=HIGHEST), -jnp.inf),
            jnp.where(earlier[None], jnp.einsum(
                "qhd,chd->hqc", qb, ks, precision=HIGHEST), -jnp.inf)], -1)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs[..., :t], v,
                          precision=HIGHEST) \
            + jnp.einsum("hqc,chd->qhd", probs[..., t:], vs,
                         precision=HIGHEST)

    n_blocks = -(-t // QUERY_BLOCK)
    q = jnp.pad(q, ((0, n_blocks * QUERY_BLOCK - t), (0, 0), (0, 0)))
    out = jax.lax.map(block, jnp.arange(n_blocks) * QUERY_BLOCK)
    return _mm(out.reshape(-1, heads * d)[:t], w["wo"])


def forward(params, tokens, model: dict):
    """Logits [T, V] in float32 for one sequence ``tokens`` [T]."""
    eps = model["rms_norm_eps"]
    x = params["embed"][tokens].astype(F32)
    for layer in params["layers"]:
        w = _matrices(layer, LAYER_MATRICES, model)
        x = x + _attention(layer, w, _rms_norm(x, layer["attn_norm"], eps),
                           model)
        x = x + decoder._swiglu(_rms_norm(x, layer["mlp_norm"], eps),
                                w["w_gate"], w["w_up"], w["w_down"])
    head = _matrices(params, ("lm_head",), model)["lm_head"]
    return _mm(_rms_norm(x, params["final_norm"], eps), head)
