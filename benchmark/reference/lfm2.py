"""Plain reference for the gated-short-convolution / attention hybrid with
sparse experts (``model_type: lfm2_moe``; LFM2-8B-A1B's ``config.json``,
https://huggingface.co/LiquidAI/LFM2-8B-A1B): in the layers ``layer_types``
calls ``"conv"`` a gated short convolution, in those it calls
``"full_attention"`` grouped-query attention with rotary positions and an
RMSNorm a head on queries and keys; every layer whole — the first
``num_dense_layers`` closed by a dense SwiGLU, the others by
``num_experts`` sigmoid-routed experts, ``num_experts_per_tok`` a token, with
a bias in the choice only and no shared expert; a tied head.

Written from the equations in plain ``jax.numpy``: float32 throughout,
matmuls at ``highest`` precision, the convolution as shifted sums, one
expert at a time, no cache, no state carried between calls, no kernels,
nothing imported from ``tpu9``. It works in blocks — queries ``QUERY_BLOCK``
at a time, the tied head's vocabulary ``VOCAB_BLOCK`` rows at a time — only
so that it fits beside a served model that fills most of a chip; a block
changes no sum's terms.

    x_0 = E[tokens]
    h_l = x_l + Mixer_l(N1(x_l));   x_l+1 = h_l + Ffn_l(N2(h_l))
    logits = N(x_L) E^T                                    (the tied head)
    N = RMSNorm with a weight, eps = norm_eps; no bias anywhere (conv_bias false).

Conv mixer, ``u = N1(x)`` ``[T, D]``, K = conv_L_cache:

    [B | C | X] = u W_in                 W_in [D, 3 D], split in that order
    z = B * X
    c_t = sum_{j<K} w_j * z_{t-(K-1)+j}  depthwise, causal, z = 0 before the start
    out = (C * c) W_out                  no activation

Attention, H_q = num_attention_heads over H_kv = num_key_value_heads heads of
d = hidden_size / num_attention_heads:

    q = RMSNorm_{w_q}(u W_q),  k = RMSNorm_{w_k}(u W_k)   over each head's d numbers
    rotary over the whole head (rope_theta, half-split pairs), then
    score_h(t, s) = q_h(t) . k_{h H_kv / H_q}(s) d^-1/2,  causal softmax;  W_o

Dense ffn (layers below num_dense_layers): ``W_2 (silu(W_1 u) * W_3 u)``,
``intermediate_size`` wide. Expert ffn (the others), ``u = N2(h)``:

    s = sigmoid(u W_r)                   num_experts scores, float32
    chosen = the num_experts_per_tok largest of s + b     (use_expert_bias: b in the CHOICE only)
    g = s[chosen] / (sum s[chosen] + 1e-6)                (norm_topk_prob)  x routed_scaling_factor
    out = sum_e g_e W_2^e (silu(W_1^e u) * W_3^e u)       moe_intermediate_size wide

Departures from the public code, each noted where it is: the public gates'
``1e-6`` is kept here (the served program divides by ``max(sum, 1e-9)``: with
four sigmoid scores a sum near 2 the two differ by 5e-7 of a gate, far under
a bfloat16 step); the public code rounds every activation to bfloat16 and
this reference none; its convolution is a ``conv1d`` with padding, the same
sums.

Assumed, each stated in the configuration's file under ``assumed``: the head
tied to the embedding table, ``head_dim`` = hidden_size /
num_attention_heads, the ``1e-6`` above, bfloat16 weights, the convolution's
tail kept in bfloat16 by the served program (this reference keeps none), a
float32 residual stream, the seeded initialisation.

The routing the system under test SERVED (``reference/served_routing.py``,
``model["routing_tie"]``), as ``reference/ling.py`` and
``reference/nemotronh.py``: sigmoid scores lie close together, and a program
that normalises a bfloat16 hidden state ranks two of them the other way round
now and then; from that token on the two sides would run different experts.
Where the harness's adapter has set ``served_routing.provider``, the
reference takes the system's choice at a (token, layer) IF that choice is
what its own rule gives once every score ``s + b`` of a served expert is
raised by ``routing_tie`` and every other lowered by it, and keeps its own
choice anywhere else. The gates are always the reference's own scores of the
experts run. With no provider or ``routing_tie`` 0 (every test of the model
itself) it routes by its own scores alone.

Builder's controls in ``model["control"]``, never set by a configuration
(``tools/probe_controls.py`` sets them to show that the comparison that
decides ``correct`` tells them from the sound program), each leaves out one
thing: ``int8_weights`` (every matrix rounded to int8 and back, per output
channel at absmax / 127: the nearest precision below bfloat16),
``no_conv_gate`` (``C`` = 1), ``no_in_gate`` (``B`` = 1), ``two_taps`` (the
oldest tap left out: ``w_0`` = 0), ``no_qk_norm``, ``bias_in_gates`` (``g``
from ``s + b``), ``no_renormalise`` (``g = s[chosen]``).

Weight tree (tpu9's, every matrix stored [in, out]): ``embed`` [V, D] (the
head too), ``final_norm`` [D]; a layer has ``attn_norm``, ``mlp_norm``, its
mixer — ``conv`` = {``w_in`` [D, 3 D], ``conv`` [K, D], ``w_out`` [D, D]} or
``wq``, ``wk``, ``wv``, ``wo``, ``q_norm`` [d], ``k_norm`` [d] — and its ffn —
``w_gate`` (W_1), ``w_up`` (W_3), ``w_down`` (W_2), or ``moe`` = {``router``
[D, E], ``bias`` [E], ``w_gate`` / ``w_up`` [E, D, F], ``w_down`` [E, F, D]}.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import served_routing

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
# queries a block of the attention: float32 scores [H_q, 256, T]
QUERY_BLOCK = 256
# rows of the tied head a block: the table is never whole in float32
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
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight.astype(F32)


def _conv_mixer(p, u, model):
    t, d = u.shape
    taps = p["conv"].astype(F32)                               # [K, D]
    k = taps.shape[0]
    if _control(model, "two_taps"):
        taps = taps.at[0].set(0.0)
    proj = _mm(u, p["w_in"], model)
    b, c, x = proj[:, :d], proj[:, d:2 * d], proj[:, 2 * d:]
    if _control(model, "no_in_gate"):
        b = jnp.ones_like(b)
    if _control(model, "no_conv_gate"):
        c = jnp.ones_like(c)
    z = jnp.concatenate([jnp.zeros((k - 1, d), F32), b * x])   # [K-1+T, D]
    mixed = sum(taps[j] * z[j:j + t] for j in range(k))
    return _mm(c * mixed, p["w_out"], model)


def _rotate(x, positions, theta):
    """Rotary over the whole head, half-split pairs: x [T, H, d]."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    angle = positions.astype(F32)[:, None] * freqs[None]       # [T, half]
    sin, cos = jnp.sin(angle)[:, None], jnp.cos(angle)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(layer, u, model):
    t = u.shape[0]
    heads, kv_heads = model["num_attention_heads"], \
        model["num_key_value_heads"]
    d, eps = model["head_dim"], model["norm_eps"]
    q = _mm(u, layer["wq"], model).reshape(t, heads, d)
    k = _mm(u, layer["wk"], model).reshape(t, kv_heads, d)
    v = _mm(u, layer["wv"], model).reshape(t, kv_heads, d)
    if not _control(model, "no_qk_norm"):
        q = _rms_norm(q, layer["q_norm"], eps)
        k = _rms_norm(k, layer["k_norm"], eps)
    positions = jnp.arange(t)
    q = _rotate(q, positions, model["rope_theta"])
    k = _rotate(k, positions, model["rope_theta"])
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


def _dense_ffn(layer, n, model):
    return _mm(jax.nn.silu(_mm(n, layer["w_gate"], model))
               * _mm(n, layer["w_up"], model), layer["w_down"], model)


def choose(choice, model):
    """The ``k`` experts [T, k] that the scores of choice ``s + b`` [T, E]
    select: the ``k`` largest."""
    return jax.lax.top_k(choice, model["num_experts_per_tok"])[1]


def route(moe, n, model, served=None, told=None):
    """``(gates [T, k], experts [T, k])``: sigmoid scores, the bias in the
    choice only. ``served`` [T, k] (rows of -1: none): the system's choice,
    taken where it is a tie within ``model["routing_tie"]`` (the module's
    docstring). ``told``: a list that receives ``{"choice", "own", "served",
    "taken"}``."""
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
    gates = jnp.take_along_axis(
        choice if _control(model, "bias_in_gates") else scores, chosen, 1)
    if model["norm_topk_prob"] and not _control(model, "no_renormalise"):
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
    return gates * model["routed_scaling_factor"], chosen


def _experts(moe, n, model, served=None, told=None):
    gates, chosen = route(moe, n, model, served, told)

    def one_expert(out, j):
        weight = jnp.sum(jnp.where(chosen == j, gates, 0.0), -1)
        y = _mm(jax.nn.silu(_mm(n, moe["w_gate"][j], model))
                * _mm(n, moe["w_up"][j], model), moe["w_down"][j], model)
        return out + weight[:, None] * y, None

    # one expert at a time, so that only one is held in float32
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(n),
                          jnp.arange(moe["w_up"].shape[0]))
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


def _head(h, table, model):
    """``h E^T`` a block of the vocabulary's rows at a time, each block
    written where it lies in the one ``[T, V]`` result."""
    v = table.shape[0]
    block = VOCAB_BLOCK if v % VOCAB_BLOCK == 0 else v

    def rows(i, out):
        w = jax.lax.dynamic_slice_in_dim(table, i * block, block, axis=0)
        return jax.lax.dynamic_update_slice_in_dim(
            out, _mm(h, w.T, model), i * block, axis=1)

    return jax.lax.fori_loop(0, v // block, rows,
                             jnp.zeros((h.shape[0], v), F32))


def forward(params, tokens, model: dict, told=None):
    """Logits [T, V] in float32 for one sequence ``tokens`` [T]. ``told``:
    a list that receives what :func:`route` says of every expert layer."""
    eps = model["norm_eps"]
    x = params["embed"][tokens].astype(F32)
    served = None
    if served_routing.provider is not None \
            and model.get("routing_tie", 0) > 0:
        served = _served(tokens, sum("moe" in l for l in params["layers"]),
                         model)
    at = 0
    for layer in params["layers"]:
        u = _rms_norm(x, layer["attn_norm"], eps)
        x = x + (_conv_mixer(layer["conv"], u, model) if "conv" in layer
                 else _attention(layer, u, model))
        n = _rms_norm(x, layer["mlp_norm"], eps)
        if "moe" in layer:
            x = x + _experts(layer["moe"], n, model,
                             None if served is None else served[:, at], told)
            at += 1
        else:
            x = x + _dense_ffn(layer, n, model)
    return _head(_rms_norm(x, params["final_norm"], eps), params["embed"],
                 model)
