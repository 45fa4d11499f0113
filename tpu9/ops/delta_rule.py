"""The gated delta rule with a decay a channel (KDA, arXiv:2510.26692; the
gated delta rule of arXiv:2412.06464): linear attention whose state is a
``[d_k, d_v]`` matrix a head, the same size at any sequence length.

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

Three forms of the one recurrence, float32 throughout (the state is carried
for thousands of tokens; the operands are tiny beside the projections):

- :func:`step`: one token a lane — a decode step.
- :func:`scan`: a token at a time under ``lax.scan`` — the oracle.
- :func:`chunked`: the chunkwise-parallel form — a prefill. Inside a block of
  ``BLOCK`` tokens write ``g_t = sum_{i<=t} log alpha_i`` (per channel) and
  ``u_t = beta_t (v_t - (Diag(alpha_t) S_{t-1})^T k_t)``; then with ``Z`` the
  state entering the block

      A[t, i] = sum_c k_t[c] k_i[c] e^{g_t[c] - g_i[c]}      (i < t)
      (I + Diag(beta) A) U = Diag(beta) (V - (K o e^g) Z)      unit lower triangular
      O = (Q o e^g) Z + B U,   B[t, i] = sum_c q_t[c] k_i[c] e^{g_t[c] - g_i[c]}   (i <= t)
      S_end = Diag(e^{g_C}) Z + sum_i (k_i o e^{g_C - g_i}) u_i^T

  Every exponent is of a difference ``g_t - g_i <= 0`` with ``i <= t``, so
  nothing overflows however strong the decay (``log alpha`` reaches -5 a
  token): the price is a ``[BLOCK, BLOCK, d_k]`` tensor of exponentials a
  head, made block by block inside the scan over blocks.

A token that is padding (``valid`` false) has ``alpha = 1, beta = 0``: it
leaves the state as it is.

:func:`causal_conv` is the short depthwise convolution in front of the rule:
its state is the last ``taps - 1`` inputs of a lane.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
# tokens of a block of the chunkwise form: 64 as in the papers' kernels
BLOCK = 64


def causal_conv(x: jnp.ndarray, taps: jnp.ndarray, tail: jnp.ndarray,
                n_valid: jnp.ndarray, bias=None):
    """Depthwise causal convolution over time. ``x`` [B, T, C] follows
    ``tail`` [B, K-1, C] (the lane's last inputs; zeros at a sequence's
    start), ``taps`` [K, C]: ``y_t = sum_j taps[j] x_{t-(K-1)+j}`` (plus
    ``bias`` [C] where one is given: the state-space mixer's). Returns
    ``(y [B, T, C] float32, new tail)``: the last ``K-1`` inputs once
    ``n_valid`` [B] of the ``T`` tokens are taken as real — with 0 real
    tokens the tail comes back as it was."""
    k = taps.shape[0]
    t = x.shape[1]
    seq = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # [B, K-1+T, C]
    seq32 = seq.astype(F32)
    y = sum(taps[j].astype(F32) * seq32[:, j:j + t] for j in range(k))
    if bias is not None:
        y = y + bias.astype(F32)
    new_tail = jax.vmap(
        lambda s, n: jax.lax.dynamic_slice_in_dim(s, n, k - 1, axis=0))(
            seq, n_valid.astype(jnp.int32))
    return y, new_tail.astype(tail.dtype)


def step_kernel_declined(heads: int, head_dim: int) -> str:
    """Why a decode step takes :func:`step` and not the Pallas kernel ('' =
    the kernel runs)."""
    from ..utils import on_tpu
    if not on_tpu():
        return "no TPU backend"
    if head_dim % 128 or heads % 8:
        return f"heads={heads} x head_dim={head_dim}: not whole (8, 128) tiles"
    return ""


def step(state, q, k, v, log_alpha, beta, live=None):
    """One token a lane. ``state`` [B, H, dk, dv]; ``q, k, log_alpha``
    [B, H, dk]; ``v`` [B, H, dv]; ``beta`` [B, H]; ``live`` [B] bool or
    None. Returns ``(state, o [B, H, dv])``; a lane that is not live keeps
    its state."""
    decayed = jnp.exp(log_alpha)[..., None] * state
    read = jnp.einsum("bhkv,bhk->bhv", decayed, k, precision=HIGHEST)
    new = decayed + (beta[..., None] * k)[..., None] \
        * (v - read)[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", new, q, precision=HIGHEST)
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, state)
    return new, o


# -- the decode step as a Pallas kernel ---------------------------------------

STEP_KERNEL = "kda_state_step"


def _step_kernel(s_ref, a_ref, k_ref, kb_ref, q_ref, v_ref, s_out, o_ref):
    """One lane, every head: ``s_ref`` [H, dk, dv]; ``a`` (the decay),
    ``k``, ``kb`` (beta k) and ``q`` as [dk, H], so that a head's vector is
    a COLUMN that broadcasts along the lanes of its ``[dk, dv]`` state;
    ``v_ref`` and ``o_ref`` [H, dv], rows. Sums over ``dk`` run down the
    sublanes."""
    for h in range(s_ref.shape[0]):
        col = slice(h, h + 1)
        decayed = s_ref[h] * a_ref[:, col]
        read = jnp.sum(decayed * k_ref[:, col], axis=0, keepdims=True)
        new = decayed + kb_ref[:, col] * (v_ref[col, :] - read)
        s_out[h] = new
        o_ref[col, :] = jnp.sum(new * q_ref[:, col], axis=0, keepdims=True)


def step_pallas(states, plane: int, q, k, v, log_alpha, beta, live=None,
                interpret: bool = False):
    """:func:`step` on plane ``plane`` of ``states`` [P, B, H, dk, dv], in
    place: the kernel reads a lane's ``[H, dk, dv]`` state once and writes it
    once (what the rule has to move), one grid step a lane, and the other
    planes are not touched (the array is aliased to the output). Returns
    ``(states, o [B, H, dv])``. An idle lane gets ``alpha = 1, beta = 0``,
    which leaves its state bit for bit."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    _, b, h, dk, dv = states.shape
    if live is not None:
        log_alpha = jnp.where(live[:, None, None], log_alpha, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)

    def columns(x):                     # [B, H, dk] -> [B, dk, H]
        return jnp.swapaxes(x.astype(F32), 1, 2)

    vec = pl.BlockSpec((None, dk, h), lambda i: (i, 0, 0))
    row = pl.BlockSpec((None, h, dv), lambda i: (i, 0, 0))
    state = pl.BlockSpec((None, None, h, dk, dv),
                         lambda i: (plane, i, 0, 0, 0))
    states, o = pl.pallas_call(
        _step_kernel,
        grid=(b,),
        in_specs=[state, vec, vec, vec, vec, row],
        out_specs=[state, row],
        out_shape=[jax.ShapeDtypeStruct(states.shape, F32),
                   jax.ShapeDtypeStruct((b, h, dv), F32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=64 * 1024 * 1024),
        cost_estimate=pl.CostEstimate(
            flops=8 * b * h * dk * dv, transcendentals=0,
            bytes_accessed=2 * b * h * dk * dv * 4),
        name=STEP_KERNEL,
        interpret=interpret,
    )(states, columns(jnp.exp(log_alpha)), columns(k),
      columns(beta[..., None] * k), columns(q), v.astype(F32))
    return states, o


def _mask_padding(log_alpha, beta, valid):
    if valid is None:
        return log_alpha, beta
    return (jnp.where(valid[..., None, None], log_alpha, 0.0),
            jnp.where(valid[..., None], beta, 0.0))


def scan(state, q, k, v, log_alpha, beta, valid=None):
    """A token at a time: ``q, k, log_alpha`` [B, T, H, dk], ``v``
    [B, T, H, dv], ``beta`` [B, T, H], ``valid`` [B, T] bool or None.
    Returns ``(state, o [B, T, H, dv])``."""
    log_alpha, beta = _mask_padding(log_alpha, beta, valid)

    def body(s, xs):
        return step(s, *xs)

    state, o = jax.lax.scan(
        body, state, tuple(jnp.moveaxis(a, 1, 0)
                           for a in (q, k, v, log_alpha, beta)))
    return state, jnp.moveaxis(o, 0, 1)


def chunked(state, q, k, v, log_alpha, beta, valid=None, block: int = BLOCK):
    """The chunkwise-parallel form of :func:`scan` (same arguments, same
    result up to rounding): blocks of ``block`` tokens, a ``lax.scan`` over
    the blocks that carries the state. ``T`` must be a multiple of the
    block; a shorter call is one block."""
    b, t, h, dk = q.shape
    c = min(block, t)
    if t % c:
        raise ValueError(f"{t} tokens are not whole blocks of {c}")
    log_alpha, beta = _mask_padding(log_alpha, beta, valid)
    n = t // c

    def blocks(a):          # [B, T, H, ...] -> [N, B, H, C, ...]
        a = a.reshape((b, n, c) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 2, 3)

    lower = jnp.tril(jnp.ones((c, c), bool))
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    eye = jnp.eye(c, dtype=F32)

    def one_block(z, xs):
        qb, kb, vb, la, bb = xs       # [B,H,C,dk] x2, [B,H,C,dv], .., [B,H,C]
        g = jnp.cumsum(la, axis=2)                         # [B, H, C, dk]
        # e^{g_t - g_i} where i <= t, 0 elsewhere (masked BEFORE the
        # exponential: above the diagonal the difference is positive)
        decay = jnp.exp(jnp.where(
            lower[:, :, None], g[:, :, :, None, :] - g[:, :, None, :, :],
            -jnp.inf))                                     # [B, H, C, C, dk]
        kk = jnp.einsum("bhtc,bhic,bhtic->bhti", kb, kb, decay,
                        precision=HIGHEST)
        qk = jnp.einsum("bhtc,bhic,bhtic->bhti", qb, kb, decay,
                        precision=HIGHEST)
        eg = jnp.exp(g)
        rhs = bb[..., None] * (vb - jnp.einsum(
            "bhtk,bhkv->bhtv", kb * eg, z, precision=HIGHEST))
        system = eye + bb[..., None] * jnp.where(strict, kk, 0.0)
        u = jax.lax.linalg.triangular_solve(
            system, rhs, left_side=True, lower=True, unit_diagonal=True)
        o = jnp.einsum("bhtk,bhkv->bhtv", qb * eg, z, precision=HIGHEST) \
            + jnp.einsum("bhti,bhiv->bhtv", qk, u, precision=HIGHEST)
        to_end = jnp.exp(g[:, :, -1:, :] - g)              # e^{g_C - g_i}
        z = eg[:, :, -1, :, None] * z + jnp.einsum(
            "bhik,bhiv->bhkv", kb * to_end, u, precision=HIGHEST)
        return z, o

    state, o = jax.lax.scan(
        one_block, state,
        (blocks(q), blocks(k), blocks(v), blocks(log_alpha),
         blocks(beta)))
    # [N, B, H, C, dv] -> [B, T, H, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1).reshape(b, t, h, -1)
    return state, o
