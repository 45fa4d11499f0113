"""The plain float32 reference against the program at tiny sizes, and the
margin method that decides ``correct``."""

import asyncio
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness, manifest, serve

M = manifest.load()
CONFIGS = [c["name"] for c in M["configs"]]
MIXTRAL = "mixtral-8x7b-l4"     # the checks of its own numbers


def tiny(name):
    """Configuration ``name`` at its rehearsal sizes, on one chip."""
    config = manifest.load_config(M, name)
    with open(os.path.join(manifest.HERE, "rehearsal", f"{name}.json")) as f:
        reh = json.load(f)
    config.update(reh["model"])
    config["assumed"].update(reh["assumed"])
    config["engine"].update(reh["engine"], topology="1x1")
    return config


def built(config):
    """(sizes, the program's config) through the configuration's family."""
    family = manifest.family(config)
    model = family.model_sizes(config)
    return model, family.program_config(model)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_equals_the_program_in_float32(name):
    """Same seeded weights, float32 on both sides, no dropped tokens: the
    published equations and ``decoder_forward`` agree to rounding."""
    from tpu9.models import init_decoder
    from tpu9.models.transformer import decoder_forward
    config = tiny(name)
    reference = correctness.load_reference(config["reference"])
    model, cfg = built(config)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = init_decoder(serve.seed_key(2 ** 31 + 7), cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(3, 512, 70),
                         jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = decoder_forward(params, tokens[None], cfg)[0]
    got = jax.jit(lambda p, x: reference.forward(p, x, model))(params, tokens)
    assert got.dtype == jnp.float32 and got.shape == (70, 512)
    # float32 rounding over two layers; a wrong mask or routing shows as O(0.1)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


def test_reference_is_independent_of_the_programs_model_code():
    for name in {manifest.load_config(M, c)["reference"] for c in CONFIGS}:
        with open(correctness.load_reference(name).__file__) as f:
            text = f.read()
        assert "import tpu9" not in text and "from tpu9" not in text


def test_seed_key_takes_seeds_past_32_bits():
    a, b = serve.seed_key(5), serve.seed_key(2 ** 31 + 5)
    assert not np.array_equal(jax.random.key_data(a), jax.random.key_data(b))
    assert np.array_equal(jax.random.key_data(serve.seed_key(5)),
                          jax.random.key_data(a))


@pytest.fixture(scope="module")
def served():
    """The tiny Mixtral through the program's engine, bf16 as served."""
    from tpu9.serving import InferenceEngine
    from tpu9.serving.shard import make_policy
    config = tiny(MIXTRAL)
    model, cfg = built(config)
    policy = make_policy("1x1")
    params = serve.build_params(cfg, policy, 11)
    engine = InferenceEngine(params, cfg,
                             serve.engine_config(config["engine"]),
                             policy=policy)
    rng = np.random.default_rng(3)
    probes = [{"name": f"p{n}", "prompt": rng.integers(3, 512, n).tolist()}
              for n in (12, 40, 100)]

    async def run():
        await engine.start()
        for p in probes:
            p["tokens"] = await engine.generate(p["prompt"], max_new_tokens=8)
        await engine.stop()

    asyncio.run(run())
    return params, model, config, probes


def test_served_tokens_are_within_the_margin_of_the_reference(served):
    params, model, config, probes = served
    out = correctness.probe_margins(params, model, probes, config["reference"])
    assert out["tokens_checked"] == 24
    assert out["worst_margin"] <= config["correct_tolerance_logit"]


def test_a_wrong_token_is_outside_the_margin(served):
    params, model, config, probes = served
    wrong = [dict(p, tokens=[(t + 1) % 512 for t in p["tokens"]])
             for p in probes]
    out = correctness.probe_margins(params, model, wrong, config["reference"])
    assert out["worst_margin"] > config["correct_tolerance_logit"]


@pytest.mark.parametrize("key,value", [("hidden_act", "gelu"),
                                       ("sliding_window", 4096),
                                       ("torch_dtype", "float16"),
                                       ("tie_word_embeddings", True),
                                       ("norm_topk_prob", False),
                                       ("num_experts", 64)])
def test_a_configuration_the_harness_cannot_build_is_refused(key, value):
    config = dict(tiny(MIXTRAL), **{key: value})
    with pytest.raises(ValueError):
        manifest.family(config).model_sizes(config)


def test_published_widths_reach_the_programs_config():
    _, cfg = built(manifest.load_config(M, MIXTRAL))
    assert (cfg.dim, cfg.hidden_dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.n_experts, cfg.moe_top_k, cfg.vocab_size, cfg.n_layers) == \
        (4096, 14336, 32, 8, 128, 8, 2, 32000, 4)
    assert cfg.moe_capacity_factor == 4.0 and cfg.rope_theta == 1e6
