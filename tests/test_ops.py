import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu9.ops import (apply_rope, decode_attention, flash_attention, rms_norm,
                      rope_rows, sample_logits, xla_attention)
from tpu9.ops.rotary import yarn_inv_freq


def rand(shape, seed=0, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype=dtype)


class TestAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_flash_matches_xla(self, causal):
        B, T, H, KH, D = 2, 256, 4, 2, 64
        q, k, v = rand((B, T, H, D)), rand((B, T, KH, D), 1), rand((B, T, KH, D), 2)
        ref = xla_attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_flash_rectangular_blocks(self):
        B, T, H, D = 1, 256, 2, 64
        q, k, v = rand((B, T, H, D)), rand((B, T, H, D), 1), rand((B, T, H, D), 2)
        ref = xla_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=128,
                              interpret=True)
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_decode_attention_masks_cache(self):
        B, S, H, D = 2, 64, 4, 32
        kc, vc = rand((B, S, H, D), 1), rand((B, S, H, D), 2)
        q = rand((B, 1, H, D))
        lens = jnp.array([10, 37])
        out = decode_attention(q, kc, vc, lens)
        # manually truncate for seq 0
        ref = xla_attention(q[:1], kc[:1, :10], vc[:1, :10], causal=False)
        np.testing.assert_allclose(out[0], ref[0], atol=1e-5)
        # changing cache contents beyond the valid length must not matter
        kc2 = kc.at[:, 50:].set(99.0)
        out2 = decode_attention(q, kc2, vc, lens)
        np.testing.assert_allclose(out, out2, atol=1e-6)

    def test_kv_offset_prefix_consistency(self):
        # attending with kv_offset equals slicing rows from the full result
        B, T, H, D = 1, 32, 2, 16
        q = rand((B, T, H, D))
        k, v = rand((B, T, H, D), 1), rand((B, T, H, D), 2)
        full = xla_attention(q, k, v, causal=True)
        tail = xla_attention(q[:, 16:], k, v, causal=True, kv_offset=16)
        np.testing.assert_allclose(full[:, 16:], tail, atol=1e-5)


def rope_table(max_len, head_dim, theta=10000.0, yarn=()):
    """The oracle ``rope_rows`` is held to: the table every program built
    inside itself until PR 60, (sin, cos) each [max_len, head_dim//2], f32,
    a row a position the model could hold."""
    half = head_dim // 2
    if yarn:
        freqs = yarn_inv_freq(half, theta, *yarn)
    else:
        freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32)
                                 / half))
    angles = jnp.arange(max_len, dtype=jnp.float32)[:, None] * freqs[None, :]
    return jnp.sin(angles), jnp.cos(angles)


def apply_rope_table(x, positions, sin, cos):
    """The rotation as it was: the tables' rows gathered by ``positions``."""
    s = sin[positions].astype(jnp.float32)[..., None, :]
    c = cos[positions].astype(jnp.float32)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


# ``rope_scaling`` of kimi-k2.6-l6-ep32 as ``families/kimi.py`` hands it on
KIMI_YARN = (64.0, 4096, 32.0, 1.0)
MAX_POSITIONS = 262144


class TestRope:
    def test_rotation_preserves_norm(self):
        x = rand((2, 16, 4, 32))
        pos = jnp.broadcast_to(jnp.arange(16), (2, 16))
        y = apply_rope(x, *rope_rows(pos, 32))
        np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                                   jnp.linalg.norm(x, axis=-1), rtol=1e-5)

    def test_position_zero_identity(self):
        x = rand((1, 1, 2, 16))
        y = apply_rope(x, *rope_rows(jnp.zeros((1, 1), jnp.int32), 16))
        np.testing.assert_allclose(y, x, atol=1e-6)

    def test_relative_property(self):
        # <rope(q, m), rope(k, n)> depends only on m - n
        q, k = rand((1, 1, 1, 32)), rand((1, 1, 1, 32), 1)

        def dot_at(m, n):
            qr = apply_rope(q, *rope_rows(jnp.array([[m]]), 32))
            kr = apply_rope(k, *rope_rows(jnp.array([[n]]), 32))
            return float(jnp.sum(qr * kr))

        assert abs(dot_at(5, 3) - dot_at(10, 8)) < 1e-4

    @pytest.mark.parametrize("head_dim", [64, 128])
    @pytest.mark.parametrize("yarn", [(), KIMI_YARN], ids=["plain", "yarn"])
    @pytest.mark.parametrize("theta", [1e4, 1e6, 6e6])
    def test_the_rows_are_the_tables_rows_bit_for_bit(self, theta, yarn,
                                                      head_dim):
        """``rope_rows`` of a position IS that row of the table, and the
        rotation by the rows IS the rotation by the gathered rows: ``==``,
        like for like — jitted both (as the programs are) or eager both
        (XLA's own ``theta ** x`` is not the eager op's to the last bit)."""
        rng = np.random.default_rng(int(theta) + head_dim + len(yarn))
        batches = (jnp.array([0, 1, 4095, 131071, 262143], jnp.int32),
                   jnp.asarray(rng.integers(0, MAX_POSITIONS, (3, 40)),
                               jnp.int32),
                   jnp.asarray(rng.integers(0, MAX_POSITIONS, (16, 1)),
                               jnp.int32))

        def eager(fn, **_):
            return fn

        for run in (eager, jax.jit):
            table = run(rope_table, static_argnums=(0, 1, 2, 3))(
                MAX_POSITIONS, head_dim, theta, yarn)
            rows = run(rope_rows, static_argnums=(1, 2, 3))
            for positions in batches:
                sin, cos = rows(positions, head_dim, theta, yarn)
                assert sin.dtype == cos.dtype == jnp.float32
                assert sin.shape == positions.shape + (head_dim // 2,)
                assert np.array_equal(sin, table[0][positions])
                assert np.array_equal(cos, table[1][positions])
                for dtype in (jnp.bfloat16, jnp.float32):
                    x = rand(positions.shape + (4, head_dim), 7, dtype)
                    got = run(apply_rope)(x, sin, cos)
                    assert got.dtype == dtype
                    assert np.array_equal(
                        got, run(apply_rope_table)(x, positions, *table))


class TestNormSampling:
    def test_rms_norm(self):
        x = rand((4, 32))
        w = jnp.ones((32,))
        y = rms_norm(x, w)
        rms = jnp.sqrt(jnp.mean(y * y, axis=-1))
        np.testing.assert_allclose(rms, 1.0, rtol=1e-3)

    def test_gemma_offset_norm(self):
        x = rand((4, 32))
        w = jnp.zeros((32,))  # gemma stores w-1; offset=1 → scale 1
        y = rms_norm(x, w, offset=1.0)
        rms = jnp.sqrt(jnp.mean(y * y, axis=-1))
        np.testing.assert_allclose(rms, 1.0, rtol=1e-3)

    def test_greedy_sampling(self):
        logits = jnp.array([[0.1, 5.0, 0.2], [3.0, 0.0, 0.1]])
        out = sample_logits(logits, jax.random.PRNGKey(0), temperature=0.0)
        assert out.tolist() == [1, 0]

    def test_top_k_restricts_support(self):
        logits = jnp.array([[0.0, 1.0, 2.0, 3.0]])
        rng = jax.random.PRNGKey(0)
        seen = set()
        for i in range(50):
            tok = int(sample_logits(logits, jax.random.fold_in(rng, i),
                                    temperature=1.0, top_k=2)[0])
            seen.add(tok)
        assert seen <= {2, 3}

    def test_top_p_restricts_support(self):
        logits = jnp.array([[10.0, 9.0, -10.0, -10.0]])
        rng = jax.random.PRNGKey(0)
        seen = set()
        for i in range(50):
            tok = int(sample_logits(logits, jax.random.fold_in(rng, i),
                                    temperature=1.0, top_p=0.9)[0])
            seen.add(tok)
        assert seen <= {0, 1}


def _masked_decode_reference(q, k, v, lens):
    """Independent dense reference (never dispatches to the kernel, unlike
    decode_attention on TPU hosts)."""
    from tpu9.ops.attention import _expand_gqa, NEG_INF
    qh = q.shape[2]
    k = _expand_gqa(k, qh)
    v = _expand_gqa(v, qh)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    mask = jnp.arange(k.shape[1])[None, :] < lens[:, None]
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", probs, v.astype(jnp.float32)).astype(q.dtype)


class TestRaggedDecode:
    def test_matches_masked_reference(self):
        from tpu9.ops.paged_attention import ragged_decode_attention
        B, S, QH, KH, D = 3, 512, 8, 2, 64
        q = rand((B, 1, QH, D))
        k = rand((B, S, KH, D), 1)
        v = rand((B, S, KH, D), 2)
        lens = jnp.array([10, 256, 511])
        ref = _masked_decode_reference(q, k, v, lens)
        out = ragged_decode_attention(q, k, v, lens, block_s=128,
                                      interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_garbage_beyond_len_ignored(self):
        from tpu9.ops.paged_attention import ragged_decode_attention
        B, S, H, D = 1, 256, 2, 64
        q = rand((B, 1, H, D))
        k = rand((B, S, H, D), 1)
        v = rand((B, S, H, D), 2)
        lens = jnp.array([100])
        out1 = ragged_decode_attention(q, k, v, lens, block_s=128,
                                       interpret=True)
        k2 = k.at[:, 128:].set(1e6)   # poison blocks past the valid prefix
        v2 = v.at[:, 128:].set(-1e6)
        out2 = ragged_decode_attention(q, k2, v2, lens, block_s=128,
                                       interpret=True)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                   atol=1e-6)
