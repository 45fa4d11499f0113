"""Plain reference for the hybrid Mamba-2 / attention / LatentMoE decoder
(``model_type: nemotron_h``; NVIDIA-Nemotron-3-Super-120B-A12B-BF16's
``config.json`` under https://huggingface.co/nvidia): a pattern given as a
string (``hybrid_override_pattern``) in which every layer is ONE sub-layer —
``M`` a Mamba-2 mixer (arXiv:2405.21060), ``*`` grouped-query attention
without positions, ``E`` an expert layer — each ``x + f(RMSNorm(x))`` with
one norm; an untied head.

Written from the equations in plain ``jax.numpy``: float32 throughout,
matmuls at ``highest`` precision, the recurrence as a plain scan over tokens
(NOT the chunked form: ``chunk_size`` is a blocking of the same sum),
experts one at a time, no cache, no state carried between calls, no kernels,
nothing imported from ``tpu9``. It works in blocks — queries ``QUERY_BLOCK``
at a time, the head's vocabulary ``VOCAB_BLOCK`` columns at a time — only so
that it fits beside a served model that fills three quarters of a chip; a
block changes no sum's terms.

    x_0 = E[tokens];  x_l+1 = x_l + f_l(N_l(x_l));  logits = N(x_L) W_head
    N = RMSNorm, eps = layer_norm_epsilon; f_l by the l-th character of the pattern.

``M``, ``u = N(x)`` ``[T, D]``; H = mamba_num_heads, P = mamba_head_dim, N =
ssm_state_size, G = n_groups, K = conv_kernel, d_inner = H P = expand x D:

    [z | xBC | dt] = u W_in          widths d_inner | d_inner + 2 G N | H   (mamba_proj_bias false)
    xBC_t = silu( sum_{j<K} w_j o xBC_{t-(K-1)+j} + b_conv )          depthwise, causal, zeros before the start
    [x | B | C] = xBC;   x [T, H, P];  B, C [T, G, N]  (head h reads group h G / H)
    dt_t = softplus(dt_t + dt_bias) [H];   A_h = -exp(A_log_h)
    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t    in R^{P x N} a head, S_{-1} = 0
    y_t = S_t C_t + D_h x_t
    out = GroupRMSNorm_w(y * silu(z)) W_out          the gate BEFORE the norm; the norm over each of the G
                                                     groups' d_inner / G channels, one weight a channel

``*``, H_q = num_attention_heads over H_kv = num_key_value_heads heads of
head_dim, no biases, NO positions (``rope_theta`` and
``partial_rotary_factor`` are read by nothing):

    score_h(t, s) = q_h(t) . k_{h H_kv / H_q}(s) / sqrt(head_dim),   causal softmax
    y_t = W_o [ sum_s p_h(t, s) v(s) ]_h

``E`` (LatentMoE), ``n = N(x)``; E = the published ``n_routed_experts``, k =
num_experts_per_tok, L = moe_latent_size, relu2(a) = relu(a) ** 2:

    s = sigmoid(n W_r) in R^E;  chosen = the k largest of s + b   (b enters the CHOICE only; n_group 1 = no group limit)
    g_e = routed_scaling_factor . s_e / sum_{chosen} s            (norm_topk_prob)
    l = n W_1                                                     D -> L, one matrix for all experts
    r = sum_{e in chosen, e HELD} g_e . relu2(l W_up^e) W_down^e  L -> moe_intermediate_size -> L, no gate matrix
    f(x) = r W_2 + relu2(n W_su) W_sd                             L -> D; the shared expert D -> S -> D, ungated

The chip's share (``model["experts_held"] = [first, count]``, and the rows of
``embed`` / columns of ``lm_head`` the tree holds): the router keeps its ``E``
outputs and every rule above, the gates are normalised over all ``k``
chosen, only the held experts' terms are summed, ``W_1``, ``W_2``, the router
and the shared expert are computed as on every chip — that partial ``f`` goes
on to the next layer. Given all experts (``[0, E]``) and the whole vocabulary
this is the uncut model.

Assumed, each stated in the configuration's file under ``assumed``: bfloat16
weights; no positions in the attention layers; the gated norm over ``n_groups``
groups, gate before norm; the recurrent state in float32; the residual stream
in float32 (``residual_in_fp32`` false is published: this reference, and the
served program's stream, are WIDER than stated, not narrower); no clamp on
``dt`` beyond the seeded range (``time_step_floor`` bounds an initialisation);
the seeded initialisation.

The routing the system under test SERVED (``reference/served_routing.py``,
``model["routing_tie"]``), as ``reference/ling.py``: hundreds of sigmoid
scores lie close together, and a program that normalises a bfloat16 hidden
state ranks two of them the other way round now and then; from that token on
the two sides would run different experts. Where the harness's adapter has
set ``served_routing.provider``, the reference takes the system's choice at
a (token, layer) IF that choice is what its own rule gives once every score
``s + b`` of a served expert is raised by ``routing_tie`` and every other
lowered by it — a tie within ``routing_tie`` by the reference's own float32
scores — and keeps its own choice anywhere else. The gates are always the
reference's own scores of the experts run. With no provider or
``routing_tie`` 0 (every test of the model itself) it routes by its own
scores alone.

Builder's controls in ``model["control"]``, never set by a configuration
(``tools/probe_controls.py`` and ``tools/ling_routing.py`` set them to show
that the comparison that decides ``correct`` tells them from the sound
program): ``int8_weights`` (every matrix rounded to int8 and back, per output
channel at absmax / 127: the nearest precision below bfloat16), ``gated``
(``silu(a) * a`` in place of ``relu2(a)`` in the experts and the shared
expert: what a SwiGLU whose gate and up matrices are one computes),
``no_latent_scale`` (gates without ``routed_scaling_factor``), ``whole_norm``
(the mixer's norm over all ``d_inner`` channels), ``no_shared`` (the shared
expert left out), ``bf16_state`` (the state rounded to bfloat16 after every
token), ``no_decay`` (``A = 0``).

Weight tree (tpu9's, every matrix stored [in, out]): ``embed`` [V, D],
``lm_head`` [D, V], ``final_norm`` [D]; an ``M`` layer has ``attn_norm`` and
``ssm`` = {``w_in`` [D, 2 d_inner + 2 G N + H], ``conv`` [K, d_inner + 2 G N],
``conv_bias``, ``dt_bias`` [H], ``a_log`` [H], ``d_skip`` [H], ``norm``
[d_inner], ``w_out`` [d_inner, D]}; a ``*`` layer ``attn_norm``, ``wq``,
``wk``, ``wv``, ``wo``; an ``E`` layer ``mlp_norm`` and ``moe`` = {``router``
[D, E], ``bias`` [E], ``w_latent_in`` [D, L], ``w_latent_out`` [L, D],
``w_up`` [held, L, F], ``w_down`` [held, F, L], ``shared`` = {``w_up`` [D, S],
``w_down`` [S, D]}}.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import served_routing

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
# queries a block of the attention: float32 scores [H_q, 512, T]
QUERY_BLOCK = 512
# columns of the head a block: the matrix is never whole in float32
VOCAB_BLOCK = 8192


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


def _relu2(a, model):
    if _control(model, "gated"):
        return jax.nn.silu(a) * a
    return jnp.square(jax.nn.relu(a))


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
            # chip's compiler drops such a pair of converts)
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.einsum("hpn,hn->hp", s, ct, precision=HIGHEST)

    _, y = jax.lax.scan(one, jnp.zeros((h, p, n), F32), (x, dt, bm, cm))
    return y


def group_norm(y, weight, groups: int, eps: float):
    """RMSNorm of ``y`` [T, d_inner] over each of ``groups`` groups of
    consecutive channels, one weight a channel."""
    t, inner = y.shape
    y = y.reshape(t, groups, inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return y.reshape(t, inner) * weight.astype(F32)


def _mixer(p, u, model):
    t = u.shape[0]
    h, hd = model["mamba_num_heads"], model["mamba_head_dim"]
    n, g, k = model["ssm_state_size"], model["n_groups"], \
        model["conv_kernel"]
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
    y = recurrence(x, dt, a_head, bm, cm, model) \
        + p["d_skip"].astype(F32)[:, None] * x
    y = y.reshape(t, inner) * jax.nn.silu(z)
    groups = 1 if _control(model, "whole_norm") else g
    return _mm(group_norm(y, p["norm"], groups, model["layer_norm_epsilon"]),
               p["w_out"], model)


def _attention(layer, u, model):
    t = u.shape[0]
    heads, kv_heads = model["num_attention_heads"], \
        model["num_key_value_heads"]
    d = model["head_dim"]
    q = _mm(u, layer["wq"], model).reshape(t, heads, d)
    k = _mm(u, layer["wk"], model).reshape(t, kv_heads, d)
    v = _mm(u, layer["wv"], model).reshape(t, kv_heads, d)
    k, v = (jnp.repeat(a, heads // kv_heads, axis=1) for a in (k, v))
    block = min(QUERY_BLOCK, t)
    pad = (-t) % block
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))

    def rows(first):
        qb = jax.lax.dynamic_slice_in_dim(q, first, block, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) \
            * d ** -0.5
        causal = jnp.arange(t)[None, :] <= first + jnp.arange(block)[:, None]
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                               axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)

    out = jax.lax.map(rows, jnp.arange(0, t + pad, block))
    return _mm(out.reshape(t + pad, heads * d)[:t], layer["wo"], model)


def choose(choice, model):
    """The ``k`` experts [T, k] that the scores of choice ``s + b`` [T, E]
    select: the ``k`` largest (``n_group`` 1: no group limit)."""
    return jax.lax.top_k(choice, model["num_experts_per_tok"])[1]


def route(moe, n, model, served=None, told=None):
    """``(gates [T, k], experts [T, k])`` over the published expert count:
    sigmoid scores, the bias in the choice only. ``served`` [T, k] (rows of
    -1: none): the system's choice, taken where it is a tie within
    ``model["routing_tie"]`` (the module's docstring). ``told``: a list that
    receives ``{"choice", "own", "served", "taken"}``."""
    scores = jax.nn.sigmoid(jnp.matmul(n, moe["router"].astype(F32),
                                       precision=HIGHEST))      # [T, E]
    choice = scores + moe["bias"].astype(F32)
    own = chosen = choose(choice, model)                         # [T, k]
    taken = None
    if served is not None:
        e = choice.shape[1]
        its = jnp.any(jax.nn.one_hot(served, e, dtype=bool), axis=1)  # [T, E]
        tie = model["routing_tie"]
        nudged = choose(choice + jnp.where(its, tie, -tie), model)
        taken = jnp.all(jnp.sort(nudged, -1) == jnp.sort(served, -1), -1)
        chosen = jnp.where(taken[:, None], served, own)
    if told is not None:
        told.append({"choice": choice, "own": own, "served": served,
                     "taken": taken})
    gates = jnp.take_along_axis(scores, chosen, 1)
    if model["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    if not _control(model, "no_latent_scale"):
        gates = gates * model["routed_scaling_factor"]
    return gates, chosen


def _experts(moe, n, model, served=None, told=None):
    first, count = model["experts_held"]
    gates, chosen = route(moe, n, model, served, told)
    latent = _mm(n, moe["w_latent_in"], model)                   # [T, L]

    def one_expert(out, j):
        weight = jnp.sum(jnp.where(chosen == first + j, gates, 0.0), -1)
        y = _mm(_relu2(_mm(latent, moe["w_up"][j], model), model),
                moe["w_down"][j], model)
        return out + weight[:, None] * y, None

    # one held expert at a time, so that only one is held in float32
    r, _ = jax.lax.scan(one_expert, jnp.zeros_like(latent),
                        jnp.arange(count))
    out = _mm(r, moe["w_latent_out"], model)
    if not _control(model, "no_shared"):
        shared = moe["shared"]
        out = out + _mm(_relu2(_mm(n, shared["w_up"], model), model),
                        shared["w_down"], model)
    return out


def _served(tokens, layers: int, model: dict):
    """What ``served_routing.provider`` keeps of ``tokens`` [T], as a traced
    value ``[T, expert layers, k]``: the picks of the newest kept sequence
    whose routed positions are a prefix of ``tokens``, -1 past them and
    where there is none. The records are constants of the trace; which of
    them ``tokens`` continues is decided on the device."""
    import numpy as np
    t, k = tokens.shape[0], model["num_experts_per_tok"]
    served = jnp.full((t, layers, k), -1, jnp.int32)
    for fed, picks in served_routing.provider():            # oldest first
        n = len(fed)
        if not 0 < n <= t or picks.shape[1:] != (layers, k):
            continue
        padded = np.zeros((t,), np.int32)
        padded[:n] = fed
        whole = np.full((t, layers, k), -1, np.int32)
        whole[:n] = picks
        same = jnp.all((tokens == padded) | (jnp.arange(t) >= n))
        served = jnp.where(same, whole, served)
    return served


def _head(h, lm_head, model):
    """``h W_head`` a block of the vocabulary's columns at a time."""
    v = lm_head.shape[1]
    block = VOCAB_BLOCK if v % VOCAB_BLOCK == 0 else v

    def columns(first):
        w = jax.lax.dynamic_slice_in_dim(lm_head, first, block, axis=1)
        return _mm(h, w, model)

    out = jax.lax.map(columns, jnp.arange(0, v, block))     # [V / b, T, b]
    return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], v)


def forward(params, tokens, model: dict, told=None):
    """Logits [T, V] in float32 for one sequence ``tokens`` [T]. ``told``:
    a list that receives what :func:`route` says of every expert layer."""
    eps = model["layer_norm_epsilon"]
    x = params["embed"][tokens].astype(F32)
    served = None
    if served_routing.provider is not None \
            and model.get("routing_tie", 0) > 0:
        served = _served(tokens, sum("moe" in l for l in params["layers"]),
                         model)
    at = 0
    for layer in params["layers"]:
        if "moe" in layer:
            n = _rms_norm(x, layer["mlp_norm"], eps)
            x = x + _experts(layer["moe"], n, model,
                             None if served is None else served[:, at], told)
            at += 1
            continue
        u = _rms_norm(x, layer["attn_norm"], eps)
        x = x + (_mixer(layer["ssm"], u, model) if "ssm" in layer
                 else _attention(layer, u, model))
    return _head(_rms_norm(x, params["final_norm"], eps), params["lm_head"],
                 model)
