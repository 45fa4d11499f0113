import jax
import jax.numpy as jnp
import numpy as np

from tpu9.models import (classifier_forward, clip_vision_forward,
                         decoder_forward, init_classifier, init_clip_vision,
                         init_decoder, init_kv_cache, lora)
from tpu9.models.classifier import TEXTCLS_TINY
from tpu9.models.clip_vit import CLIP_VIT_TINY
from tpu9.models.gemma import GEMMA_PRESETS
from tpu9.models.llama import LLAMA_PRESETS
from tpu9.models.transformer import count_params
import pytest

TINY = LLAMA_PRESETS["llama-tiny"]
GTINY = GEMMA_PRESETS["gemma-tiny"]


def f32(cfg):
    from dataclasses import replace
    return replace(cfg, dtype=jnp.float32)


class TestDecoder:
    def test_forward_shapes(self):
        cfg = f32(TINY)
        params = init_decoder(jax.random.PRNGKey(0), cfg)
        tokens = jnp.array([[1, 2, 3, 4, 5, 6, 7, 8]])
        logits = decoder_forward(params, tokens, cfg)
        assert logits.shape == (1, 8, cfg.vocab_size)
        assert logits.dtype == jnp.float32

    def test_causality(self):
        cfg = f32(TINY)
        params = init_decoder(jax.random.PRNGKey(0), cfg)
        t1 = jnp.array([[1, 2, 3, 4, 9, 9, 9, 9]])
        t2 = jnp.array([[1, 2, 3, 4, 5, 6, 7, 8]])
        l1 = decoder_forward(params, t1, cfg)
        l2 = decoder_forward(params, t2, cfg)
        np.testing.assert_allclose(l1[:, :4], l2[:, :4], atol=1e-4)

    def test_prefill_then_decode_matches_full_forward(self):
        cfg = f32(TINY)
        params = init_decoder(jax.random.PRNGKey(0), cfg)
        seq = [3, 17, 94, 5, 211, 7, 42, 99]
        full = decoder_forward(params, jnp.array([seq]), cfg)

        # prefill the first 5 tokens, then decode 3 more one at a time
        cache = init_kv_cache(cfg, 1, 64)
        logits, cache = decoder_forward(params, jnp.array([seq[:5]]), cfg,
                                        kv_cache=cache)
        np.testing.assert_allclose(logits, full[:, :5], atol=2e-3)
        for i in range(5, 8):
            tok = jnp.array([[seq[i]]])
            pos = jnp.array([[i]])
            step_logits, cache = decoder_forward(
                params, tok, cfg, positions=pos, kv_cache=cache,
                cache_len=jnp.array([i + 1]), decode=True)
            np.testing.assert_allclose(step_logits[:, 0], full[:, i], atol=2e-3)

    def test_a_cache_longer_than_the_models_positions_is_refused(self):
        # at trace time, from the shapes: the model has no such positions
        cfg = f32(TINY)
        params = init_decoder(jax.random.PRNGKey(0), cfg)
        cache = init_kv_cache(cfg, 1, cfg.max_seq_len + 16)
        with pytest.raises(ValueError, match=f"exceeds the model's "
                                             f"{cfg.max_seq_len} positions"):
            decoder_forward(params, jnp.array([[1, 2, 3]]), cfg,
                            kv_cache=cache)
        logits, _ = decoder_forward(params, jnp.array([[1, 2, 3]]), cfg,
                                    kv_cache=init_kv_cache(cfg, 1,
                                                           cfg.max_seq_len))
        assert logits.shape == (1, 3, cfg.vocab_size)

    def test_gemma_forward_and_tied_head(self):
        cfg = f32(GTINY)
        params = init_decoder(jax.random.PRNGKey(0), cfg)
        assert "lm_head" not in params
        logits = decoder_forward(params, jnp.array([[1, 2, 3, 4]]), cfg)
        assert logits.shape == (1, 4, cfg.vocab_size)
        assert bool(jnp.isfinite(logits).all())

    def test_param_counts_scale(self):
        cfg = f32(TINY)
        params = init_decoder(jax.random.PRNGKey(0), cfg)
        n = count_params(params)
        assert n > 100_000  # tiny but real


class TestLora:
    def test_zero_init_is_identity(self):
        cfg = f32(TINY)
        params = init_decoder(jax.random.PRNGKey(0), cfg)
        adapters = lora.init_lora(jax.random.PRNGKey(1), params, rank=4)
        merged = lora.merge(params, adapters, scale=2.0)
        tokens = jnp.array([[1, 2, 3, 4]])
        np.testing.assert_allclose(decoder_forward(params, tokens, cfg),
                                   decoder_forward(merged, tokens, cfg),
                                   atol=1e-5)

    def test_nonzero_b_changes_output(self):
        cfg = f32(TINY)
        params = init_decoder(jax.random.PRNGKey(0), cfg)
        adapters = lora.init_lora(jax.random.PRNGKey(1), params, rank=4)
        adapters["layers"][0]["wq"]["b"] = jnp.ones_like(
            adapters["layers"][0]["wq"]["b"])
        merged = lora.merge(params, adapters, scale=2.0)
        tokens = jnp.array([[1, 2, 3, 4]])
        a = decoder_forward(params, tokens, cfg)
        b = decoder_forward(merged, tokens, cfg)
        assert float(jnp.abs(a - b).max()) > 1e-4

    def test_trainable_fraction(self):
        cfg = f32(TINY)
        params = init_decoder(jax.random.PRNGKey(0), cfg)
        adapters = lora.init_lora(jax.random.PRNGKey(1), params, rank=4)
        assert lora.trainable_count(adapters) < 0.2 * count_params(params)


class TestClip:
    def test_embedding_normalized(self):
        params = init_clip_vision(jax.random.PRNGKey(0), CLIP_VIT_TINY)
        images = jax.random.uniform(jax.random.PRNGKey(1), (3, 28, 28, 3))
        emb = clip_vision_forward(params, images, CLIP_VIT_TINY)
        assert emb.shape == (3, CLIP_VIT_TINY.embed_dim)
        np.testing.assert_allclose(jnp.linalg.norm(emb, axis=-1), 1.0, rtol=1e-4)

    def test_patchify_layout(self):
        from tpu9.models.clip_vit import patchify
        img = jnp.arange(2 * 4 * 4 * 3).reshape(2, 4, 4, 3).astype(jnp.float32)
        p = patchify(img, 2)
        assert p.shape == (2, 4, 12)
        # first patch = rows 0..1 x cols 0..1
        expected = img[0, :2, :2].reshape(-1)
        np.testing.assert_allclose(p[0, 0], expected)


class TestClassifier:
    def test_padding_invariance(self):
        cfg = TEXTCLS_TINY
        params = init_classifier(jax.random.PRNGKey(0), cfg)
        t1 = jnp.array([[5, 6, 7, 0, 0, 0, 0, 0]])
        m1 = jnp.array([[1, 1, 1, 0, 0, 0, 0, 0]])
        t2 = jnp.array([[5, 6, 7, 99, 98, 97, 96, 95]])  # garbage in padding
        l1 = classifier_forward(params, t1, m1, cfg)
        l2 = classifier_forward(params, t2, m1, cfg)
        assert l1.shape == (1, cfg.n_classes)
        np.testing.assert_allclose(l1, l2, atol=1e-4)


# ---------------------------------------------------------------------------
# mixtral (sparse-MoE decoder family)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_mixtral_decoder_paths():
    from dataclasses import replace

    from tpu9.models import (MIXTRAL_PRESETS, decoder_forward, init_decoder,
                             init_kv_cache)

    cfg = replace(MIXTRAL_PRESETS["mixtral-tiny"], dtype=jnp.float32)
    params = init_decoder(jax.random.PRNGKey(0), cfg)
    assert "moe" in params["layers"][0]
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    logits = decoder_forward(params, toks, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))

    # balance aux is exposed for training
    _, aux = decoder_forward(params, toks, cfg, return_moe_aux=True)
    assert float(aux) >= 1.0 - 1e-4

    # prefill + decode through the kv cache
    cache = init_kv_cache(cfg, 2, 64)
    lg, cache = decoder_forward(params, toks[:, :8], cfg, kv_cache=cache)
    tok = lg[:, -1:].argmax(-1).astype(jnp.int32)
    lg2, cache = decoder_forward(
        params, tok, cfg, positions=jnp.full((2, 1), 8, jnp.int32),
        kv_cache=cache, cache_len=jnp.full((2,), 9, jnp.int32), decode=True)
    assert lg2.shape == (2, 1, cfg.vocab_size)


def test_mixtral_tp_sharded_matches_single_device():
    from dataclasses import replace

    import numpy as np

    from tpu9.models import MIXTRAL_PRESETS, decoder_forward, init_decoder
    from tpu9.parallel import decoder_param_specs, make_mesh, shard_params

    cfg = replace(MIXTRAL_PRESETS["mixtral-tiny"], dtype=jnp.float32)
    params = init_decoder(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    ref = decoder_forward(params, toks, cfg)

    mesh = make_mesh(dp=1, fsdp=2, sp=1, tp=4)
    sharded = shard_params(params, mesh, decoder_param_specs(params))
    with mesh:
        out = jax.jit(lambda p, t: decoder_forward(p, t, cfg))(sharded, toks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
