"""Ring attention: exact long-context attention with the sequence sharded
across devices (context parallelism).

Each device holds a [B, T/n, H, D] shard of q/k/v. k/v blocks rotate around
the ring via ``ppermute`` while every device accumulates online-softmax
statistics for its local q block — communication overlaps the compute XLA
schedules between steps, and peak memory per device is O(T/n) instead of O(T).
(Liu et al., "Ring Attention with Blockwise Transformers", 2023 — see
PAPERS.md; implementation here is an independent jax shard_map design.)

Causal masking is handled by comparing global block offsets: a rotation step
whose k block sits entirely in the future contributes nothing and XLA drops
its matmul behind the mask select.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _local_block(q, k, v, q_off, k_off, causal, scale):
    """f32 blockwise attention stats. q [B,Tq,H,D], k/v [B,Tk,H,D] (already
    GQA-expanded). Returns (numerator [B,Tq,H,D], max [B,Tq,H], denom [B,Tq,H])."""
    s = jnp.einsum("bthd,bshd->bhts", q * scale, k)
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        q_pos = q_off + jnp.arange(tq)[:, None]
        k_pos = k_off + jnp.arange(tk)[None, :]
        s = jnp.where((k_pos <= q_pos)[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)                          # [B,H,Tq]
    p = jnp.exp(s - m[..., None])
    # rows with every position masked (m == NEG_INF) must contribute zero,
    # not exp(0) == 1
    p = jnp.where((m[..., None] > NEG_INF / 2), p, 0.0)
    l = jnp.sum(p, axis=-1)                          # [B,H,Tq]
    o = jnp.einsum("bhts,bshd->bthd", p, v)          # [B,Tq,H,D]
    return o, m.transpose(0, 2, 1), l.transpose(0, 2, 1)


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   mesh: Mesh, axis: str = "sp",
                   causal: bool = True) -> jnp.ndarray:
    """q/k/v: [B, T, H, D] globally, sharded on T along ``axis``.

    Returns [B, T, H, D] with the same sharding. kv heads must equal q heads
    (expand GQA before calling — the expansion is free under jit since it
    broadcasts within each device's shard).
    """
    n = mesh.shape[axis]
    scale = q.shape[-1] ** -0.5

    spec = P(None, axis, None, None)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False)
    def _ring(q_blk, k_blk, v_blk):
        idx = jax.lax.axis_index(axis)
        tq = q_blk.shape[1]
        qf = q_blk.astype(jnp.float32)

        def step(carry, r):
            k_cur, v_cur, acc, m_run, l_run = carry
            # k block currently held came from device (idx - r) mod n
            k_owner = (idx - r) % n
            o, m_blk, l_blk = _local_block(
                qf, k_cur.astype(jnp.float32), v_cur.astype(jnp.float32),
                q_off=idx * tq, k_off=k_owner * tq, causal=causal, scale=scale)
            m_new = jnp.maximum(m_run, m_blk)
            alpha_run = jnp.exp(m_run - m_new)
            alpha_blk = jnp.exp(m_blk - m_new)
            acc = acc * alpha_run[..., None] + o * alpha_blk[..., None]
            l_new = l_run * alpha_run + l_blk * alpha_blk
            perm = [(i, (i + 1) % n) for i in range(n)]
            k_nxt = jax.lax.ppermute(k_cur, axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis, perm)
            return (k_nxt, v_nxt, acc, m_new, l_new), None

        b, _, h, d = q_blk.shape
        acc0 = jnp.zeros((b, tq, h, d), jnp.float32)
        m0 = jnp.full((b, tq, h), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, tq, h), jnp.float32)
        (_, _, acc, _, l), _ = jax.lax.scan(
            step, (k_blk, v_blk, acc0, m0, l0), jnp.arange(n))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.astype(q_blk.dtype)

    return _ring(q, k, v)
