"""Plain reference for the language model of Kimi-K2.6 (``model_type:
kimi_k2``; https://huggingface.co/moonshotai/Kimi-K2.6 ``config.json``): the
DeepSeek-V3 block — latent attention (MLA, arXiv:2405.04434) in EVERY layer,
with a low-rank query, YaRN positions (arXiv:2309.00071) and no output gate;
a dense SwiGLU in the leading layer, then sigmoid-routed experts with a
selection-only bias and no group limit, and one shared expert
(arXiv:2412.19437, section 2.1.2, ``topk_method: noaux_tc``).

Written from the equations in plain ``jax.numpy``: float32 throughout,
matmuls at ``highest`` precision, keys and values EXPANDED from the latent
(nothing absorbed), no cache, no state carried between calls, no kernels,
nothing imported from ``tpu9``. It works in blocks — queries ``QUERY_BLOCK`` at
a time, experts one at a time — only so that it fits beside a served model
that fills three quarters of a chip; a block changes no sum's terms. ``s()``
is the logistic function; ``H = num_attention_heads`` heads.

    x_0 = E[tokens];  a_l = x_l + Attn_l(N(x_l));  x_l+1 = a_l + FFN_l(N(a_l));  logits = W_head N(x_L)
    N = RMSNorm, eps = rms_norm_eps, pre-norm on both halves of a layer.
    FFN_l is dense SwiGLU (intermediate_size) for l < first_k_dense_replace, else the expert layer.

MLA, d_nope = qk_nope_head_dim, d_rope = qk_rope_head_dim, d_v = v_head_dim, d_c = kv_lora_rank, n = N(x):

    c_q = N_q(W_dq n)  [q_lora_rank];        [q_nope | q_r]_h = W_uq,h c_q
    [c~ | k_r] = W_dkv n;   c = N_kv(c~);    k_rope = R(pos) k_r, one for all heads;   q_rope_h = R(pos) q_r,h
    [k_nope | v]_h = W_ukv,h c
    score_h(t, s) = (q_nope_h(t).k_nope_h(s) + q_rope_h(t).k_rope(s)) . (d_nope + d_rope)^-1/2 . m^2,  causal softmax
    m = 0.1 . mscale_all_dim . ln(factor) + 1      (YaRN's attention temperature; 1.4159 at factor 64)
    y_t = W_o [ sum_s p_h(t, s) v_h(s) ]_h         no gate, no bias

R, YaRN over the d_rope rotary dimensions, half-split form (dimension i pairs with i + d_rope/2):

    f_i = rope_theta^(-2i / d_rope),  i < d_rope/2
    dim(r) = d_rope ln(original_max_position_embeddings / (2 pi r)) / (2 ln rope_theta)
    low = max(floor(dim(beta_fast)), 0);  high = min(ceil(dim(beta_slow)), d_rope - 1)
    ramp_i = clip((i - low) / (high - low), 0, 1)
    inv_freq_i = (f_i / factor) ramp_i + f_i (1 - ramp_i)
    cos and sin are scaled by mscale(factor, mscale) / mscale(factor, mscale_all_dim) = 1 (the two are equal)

Experts, E = ``n_routed_experts`` routed over, k = num_experts_per_tok, ``n_group`` = ``topk_group`` = 1 (no group limit):

    s = s(W_r h) in R^E, float32;  chosen = the k largest of s + b  (b enters the CHOICE only)
    g_e = routed_scaling_factor . s_e / (sum_{chosen} s + 1e-20)        (norm_topk_prob)
    FFN(h) = sum_{e in chosen, e HELD} g_e . SwiGLU_e(h) + SwiGLU_shared(h)

The chip's share (``model["experts_held"] = [first, count]``, and the rows of
``embed`` / ``lm_head`` the tree holds): the router keeps its ``E`` outputs,
the gates are normalised over all ``k`` chosen, and only the held experts'
terms are summed, with the shared expert — that partial result goes on to
the next layer. Given all experts (``[0, E]``) and the whole vocabulary this
is the uncut model; the sum over the chips' partial expert sums, the shared
expert counted once, is the uncut layer (``tests/test_kimi_layers.py``).

Assumed, each stated in the configuration's file under ``assumed`` with the
key it reads: bfloat16 weights; the rotary pairs in the half-split form (the
published modeling code de-interleaves ``q_r`` and ``k_r`` first, which under
seeded weights is a permutation of ``W_uq``'s and ``W_dkv``'s columns); the
norms' places (pre-norms, ``N_q`` on the query latent, ``N_kv`` on the
latent alone, a final norm); the seeded selection bias.

The routing the system under test SERVED (``reference/served_routing.py``,
``model["routing_tie"]``), as ``reference/ling.py`` has it: where the harness's
adapter has set ``served_routing.provider``, the reference takes the system's
choice at a (token, layer) IF that choice is what its own rule gives once
every score ``s + b`` of a served expert is raised by ``routing_tie`` and every
other lowered by it — a tie within ``routing_tie`` by the reference's own
float32 scores. Any other served choice is NOT taken. The gates are always
the reference's own scores of the experts run. With no provider or
``routing_tie`` 0 the reference routes by its own scores alone.

Builder's controls in ``model["control"]``, never set by a configuration
(``tools/probe_controls.py`` sets them to show that the comparison that
decides ``correct`` tells them from the sound program): ``int8_weights``
(every matrix rounded to int8 and back, per output channel at absmax / 127:
the nearest precision below bfloat16), ``no_mscale`` (``m^2`` left out of
the scale), ``plain_rope`` (``f_i`` in place of YaRN's ``inv_freq_i``),
``no_q_norm`` (``N_q`` left out), ``no_shared`` (the shared expert left out).

Weight tree (tpu9's, every matrix stored [in, out]): ``embed`` [V, D],
``lm_head`` [D, V], ``final_norm`` [D]; a layer has ``attn_norm``,
``mlp_norm``, ``mla`` = {``w_dq`` [D, q_lora_rank], ``q_norm``
[q_lora_rank], ``w_uq`` [q_lora_rank, H (d_nope + d_rope)], ``w_dkv`` [D, d_c
+ d_rope], ``kv_norm`` [d_c], ``w_ukv`` [d_c, H (d_nope + d_v)], ``wo`` [H
d_v, D]}, then ``w_gate``, ``w_up``, ``w_down`` or ``moe`` = {``router`` [D,
E], ``bias`` [E], ``w_gate`` [held, D, F], ``w_up``, ``w_down`` [held, F, D],
``shared`` = {``w_gate``, ``w_up``, ``w_down``}}.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference import served_routing

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
# queries of a block of the attention: 64 heads x 512 x 2,800 rows of float32
# scores are 0.37 GB
QUERY_BLOCK = 512


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


def mscale(factor: float, scale: float) -> float:
    """YaRN's attention temperature for a context stretched ``factor``
    times."""
    return 1.0 if factor <= 1 else 0.1 * scale * math.log(factor) + 1.0


def inv_freq(model: dict):
    """The ``d_rope / 2`` rotary frequencies: YaRN's blend of ``f_i`` and
    ``f_i / factor`` (the equations above); ``f_i`` alone where the
    configuration has no ``rope_scaling`` or under ``plain_rope``."""
    d = model["qk_rope_head_dim"]
    theta = float(model["rope_theta"])
    f = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    yarn = model.get("rope_scaling")
    if not yarn or _control(model, "plain_rope"):
        return f

    def dim_of(turns):
        return d * math.log(yarn["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(yarn["beta_fast"])), 0)
    high = min(math.ceil(dim_of(yarn["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    return f / yarn["factor"] * ramp + f * (1.0 - ramp)


def _rope(x, freqs):
    """x [T, ..., d] at positions 0..T-1, half-split form."""
    t, d = x.shape[0], x.shape[-1]
    half = d // 2
    ang = jnp.arange(t, dtype=F32)[:, None] * freqs[None, :]      # [T, d/2]
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(h, w, model):
    return _mm(jax.nn.silu(_mm(h, w["w_gate"], model))
               * _mm(h, w["w_up"], model), w["w_down"], model)


def _mla(p, n, model):
    t = n.shape[0]
    heads = model["num_attention_heads"]
    d_nope, d_rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    d_v, d_c = model["v_head_dim"], model["kv_lora_rank"]
    eps = model["rms_norm_eps"]
    freqs = inv_freq(model)
    c_q = _mm(n, p["w_dq"], model)
    if not _control(model, "no_q_norm"):
        c_q = _rms_norm(c_q, p["q_norm"], eps)
    q = _mm(c_q, p["w_uq"], model).reshape(t, heads, d_nope + d_rope)
    q_nope, q_rope = q[..., :d_nope], _rope(q[..., d_nope:], freqs)
    down = _mm(n, p["w_dkv"], model)
    c = _rms_norm(down[:, :d_c], p["kv_norm"], eps)
    k_rope = _rope(down[:, d_c:], freqs)                         # [T, d_rope]
    kv = _mm(c, p["w_ukv"], model).reshape(t, heads, d_nope + d_v)
    k_nope, v = kv[..., :d_nope], kv[..., d_nope:]
    scale = (d_nope + d_rope) ** -0.5
    yarn = model.get("rope_scaling")
    if yarn and not _control(model, "no_mscale"):
        scale = scale * mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2
    rows = jnp.arange(t)
    out = []
    for first in range(0, t, QUERY_BLOCK):
        qn, qr = q_nope[first:first + QUERY_BLOCK], \
            q_rope[first:first + QUERY_BLOCK]
        scores = (jnp.einsum("qhd,khd->hqk", qn, k_nope, precision=HIGHEST)
                  + jnp.einsum("qhd,kd->hqk", qr, k_rope,
                               precision=HIGHEST)) * scale
        causal = rows[None, :] <= (first + jnp.arange(qn.shape[0]))[:, None]
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        out.append(jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST))
    out = jnp.concatenate(out, 0)
    return _mm(out.reshape(t, heads * d_v), p["wo"], model)


def choose(choice, model):
    """The ``k`` experts [T, k] that the scores of choice ``s + b`` [T, E]
    select: the ``k`` largest (``n_group`` 1: no group limit)."""
    return jax.lax.top_k(choice, model["num_experts_per_tok"])[1]


def route(moe, h, model, served=None, told=None):
    """``(gates [T, k], experts [T, k])`` over the published expert count:
    sigmoid scores, the bias in the choice only. ``served`` [T, k] (rows of
    -1: none): the system's choice, taken where it is a tie within
    ``model["routing_tie"]`` (the module's docstring). ``told``: a list
    that receives ``{"choice", "own", "served", "taken"}``."""
    scores = jax.nn.sigmoid(jnp.matmul(h, moe["router"].astype(F32),
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
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return gates * model["routed_scaling_factor"], chosen


def _experts(moe, h, model, served=None, told=None):
    first, count = model["experts_held"]
    gates, chosen = route(moe, h, model, served, told)

    def one_expert(out, j):
        weight = jnp.sum(jnp.where(chosen == first + j, gates, 0.0), -1)
        w = {name: moe[name][j] for name in ("w_gate", "w_up", "w_down")}
        return out + weight[:, None] * _swiglu(h, w, model), None

    # one held expert at a time, so that only one is held in float32
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(count))
    if not _control(model, "no_shared"):
        out = out + _swiglu(h, moe["shared"], model)
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


def forward(params, tokens, model: dict, told=None):
    """Logits [T, V] in float32 for one sequence ``tokens`` [T]. ``told``:
    a list that receives what :func:`route` says of every expert layer."""
    eps = model["rms_norm_eps"]
    x = params["embed"][tokens].astype(F32)
    served = None
    if served_routing.provider is not None \
            and model.get("routing_tie", 0) > 0:
        served = _served(tokens, sum("moe" in l for l in params["layers"]),
                         model)
    at = 0
    for layer in params["layers"]:
        x = x + _mla(layer["mla"], _rms_norm(x, layer["attn_norm"], eps),
                     model)
        h = _rms_norm(x, layer["mlp_norm"], eps)
        if "moe" in layer:
            x = x + _experts(layer["moe"], h, model,
                             None if served is None else served[:, at], told)
            at += 1
        else:
            x = x + _swiglu(h, layer, model)
    return _mm(_rms_norm(x, params["final_norm"], eps), params["lm_head"],
               model)
