"""Multi-chip sharding tests on the virtual 8-device CPU mesh."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpu9.models import decoder_forward, init_decoder, lora
from tpu9.models.llama import LLAMA_PRESETS
from tpu9.ops.attention import xla_attention
from tpu9.parallel import (decoder_param_specs, fsdp_specs, make_mesh,
                           mesh_for_spec, ring_attention, shard_params)
from tpu9.train import build_lora_train_step, causal_lm_loss, build_train_step
from tpu9.train.trainer import TrainState, init_train_state
from tpu9.types import parse_tpu_spec

TINY = replace(LLAMA_PRESETS["llama-tiny"], dtype=jnp.float32)


def test_device_count():
    assert len(jax.devices()) == 8


def test_make_mesh_shapes():
    mesh = make_mesh(dp=2, fsdp=2, sp=1, tp=2)
    assert mesh.shape == {"dp": 2, "fsdp": 2, "sp": 1, "tp": 2}
    with pytest.raises(ValueError):
        make_mesh(dp=16)


def test_mesh_for_spec_defaults():
    mesh = mesh_for_spec(parse_tpu_spec("v5e-8"))
    assert mesh.shape["tp"] == 8          # single-host slice: all chips tp
    mesh2 = mesh_for_spec(parse_tpu_spec("v5e-8"), tp=4)
    assert mesh2.shape["tp"] == 4 and mesh2.shape["fsdp"] == 2


def test_tp_fsdp_forward_matches_single_device():
    params = init_decoder(jax.random.PRNGKey(0), TINY)
    tokens = jnp.array([[1, 2, 3, 4, 5, 6, 7, 8],
                        [8, 7, 6, 5, 4, 3, 2, 1]])
    expected = decoder_forward(params, tokens, TINY)

    mesh = make_mesh(dp=1, fsdp=2, sp=1, tp=4)
    specs = decoder_param_specs(params)
    sharded = shard_params(params, mesh, specs)

    with mesh:
        fwd = jax.jit(lambda p, t: decoder_forward(p, t, TINY))
        got = fwd(sharded, tokens)
    np.testing.assert_allclose(got, expected, atol=2e-3)


def test_dp_tp_forward_matches():
    params = init_decoder(jax.random.PRNGKey(0), TINY)
    tokens = jnp.array([[1, 2, 3, 4, 5, 6, 7, 8],
                        [8, 7, 6, 5, 4, 3, 2, 1]])
    expected = decoder_forward(params, tokens, TINY)
    mesh = make_mesh(dp=2, fsdp=1, sp=1, tp=4)
    sharded = shard_params(params, mesh, decoder_param_specs(params))
    with mesh:
        got = jax.jit(lambda p, t: decoder_forward(p, t, TINY))(sharded, tokens)
    np.testing.assert_allclose(got, expected, atol=2e-3)


def test_ring_attention_matches_dense():
    B, T, H, D = 2, 64, 4, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, T, H, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, T, H, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, T, H, D))
    mesh = make_mesh(dp=1, fsdp=1, sp=8, tp=1)
    ref = xla_attention(q, k, v, causal=True)
    out = ring_attention(q, k, v, mesh, axis="sp", causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    ref_nc = xla_attention(q, k, v, causal=False)
    out_nc = ring_attention(q, k, v, mesh, axis="sp", causal=False)
    np.testing.assert_allclose(np.asarray(out_nc), np.asarray(ref_nc), atol=2e-5)


@pytest.mark.slow
def test_fsdp_train_step_loss_decreases():
    mesh = make_mesh(dp=2, fsdp=2, sp=1, tp=2)
    params = init_decoder(jax.random.PRNGKey(0), TINY)
    opt = optax.adam(1e-3)
    specs = decoder_param_specs(params)
    state = init_train_state(params, opt, mesh, specs)
    step = build_train_step(TINY, opt, remat=True)

    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                TINY.vocab_size)
    with jax.set_mesh(mesh):
        losses = []
        for _ in range(5):
            state, metrics = step(state, tokens)
            losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses


@pytest.mark.slow
def test_lora_fsdp_train_step():
    mesh = make_mesh(dp=1, fsdp=4, sp=1, tp=2)
    params = init_decoder(jax.random.PRNGKey(0), TINY)
    sharded = shard_params(params, mesh, decoder_param_specs(params))
    adapters = lora.init_lora(jax.random.PRNGKey(1), params, rank=4)
    adapters = shard_params(adapters, mesh, fsdp_specs(adapters, min_size=1))
    opt = optax.adam(1e-2)
    opt_state = opt.init(adapters)
    step = build_lora_train_step(TINY, opt, scale=2.0, remat=True)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0,
                                TINY.vocab_size)
    with jax.set_mesh(mesh):
        losses = []
        for _ in range(5):
            adapters, opt_state, metrics = step(adapters, opt_state, sharded,
                                                tokens)
            losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses


def test_causal_lm_loss_masking():
    logits = jnp.zeros((1, 4, 8))
    tokens = jnp.array([[1, 2, 3, 4]])
    mask = jnp.array([[1, 1, 0, 0]])
    full = causal_lm_loss(logits, tokens)
    masked = causal_lm_loss(logits, tokens, mask)
    # uniform logits: nll = log(8) either way
    np.testing.assert_allclose(full, jnp.log(8.0), rtol=1e-5)
    np.testing.assert_allclose(masked, jnp.log(8.0), rtol=1e-5)


# ---------------------------------------------------------------------------
# expert parallelism (MoE)
# ---------------------------------------------------------------------------

def test_moe_ffn_ep_sharded_matches_unsharded():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu9.models.moe import (MoeConfig, init_moe_layer, moe_ffn,
                                 moe_param_specs)
    from tpu9.parallel import make_named_mesh

    cfg = MoeConfig(dim=64, hidden_dim=128, n_experts=8, top_k=2,
                    dtype=jnp.float32)
    params = init_moe_layer(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64), jnp.float32)

    ref, aux = moe_ffn(params, x, cfg, ep_sharded=False)
    assert ref.shape == x.shape
    assert float(aux["balance_loss"]) >= 1.0 - 1e-5   # lower bound is 1

    mesh = make_named_mesh({"ep": 8})
    specs = moe_param_specs(params)
    sharded = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
               for k, v in params.items()}
    with mesh:
        out, aux2 = jax.jit(
            lambda p, x: moe_ffn(p, x, cfg))(sharded, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_moe_capacity_drops_and_balance_grads():
    from tpu9.models.moe import MoeConfig, init_moe_layer, moe_ffn

    # capacity_factor tiny → forced drops, reported honestly
    cfg = MoeConfig(dim=32, hidden_dim=64, n_experts=4, top_k=1,
                    capacity_factor=0.1, dtype=jnp.float32)
    params = init_moe_layer(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 128, 32), jnp.float32)
    out, aux = moe_ffn(params, x, cfg, ep_sharded=False)
    assert float(aux["dropped_frac"]) > 0

    # balance loss is differentiable wrt the router
    def loss_fn(p):
        y, aux = moe_ffn(p, x, cfg, ep_sharded=False)
        return jnp.mean(y ** 2) + 0.01 * aux["balance_loss"]

    g = jax.grad(loss_fn)(params)
    assert float(jnp.abs(g["router"]).sum()) > 0
    assert float(jnp.abs(g["w_down"]).sum()) > 0


def test_moe_train_step_loss_decreases():
    from jax.sharding import NamedSharding

    from tpu9.models.moe import (MoeConfig, init_moe_layer, moe_ffn,
                                 moe_param_specs)
    from tpu9.parallel import make_named_mesh

    cfg = MoeConfig(dim=32, hidden_dim=64, n_experts=4, top_k=2,
                    dtype=jnp.float32)
    params = init_moe_layer(jax.random.PRNGKey(0), cfg)
    mesh = make_named_mesh({"ep": 4})
    specs = moe_param_specs(params)
    params = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
              for k, v in params.items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 32), jnp.float32)
    target = jnp.roll(x, 1, axis=-1)
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state):
        def loss_fn(p):
            y, aux = moe_ffn(p, x, cfg)
            return jnp.mean((y - target) ** 2) + 0.01 * aux["balance_loss"]
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    with mesh:
        losses = []
        for _ in range(8):
            params, opt_state, loss = step(params, opt_state)
            losses.append(float(loss))
    assert losses[-1] < losses[0], losses


# ---------------------------------------------------------------------------
# pipeline parallelism
# ---------------------------------------------------------------------------

def _mlp_layer_params(rng, n_layers, dim):
    ks = jax.random.split(rng, n_layers * 2)
    return [{"w1": jax.random.normal(ks[2 * i], (dim, dim)) * 0.1,
             "w2": jax.random.normal(ks[2 * i + 1], (dim, dim)) * 0.1}
            for i in range(n_layers)]


def _mlp_block(layer, x):
    return x + jnp.tanh(x @ layer["w1"]) @ layer["w2"]


def test_pipeline_forward_matches_sequential():
    from tpu9.parallel import (make_named_mesh, pipeline_forward,
                               stack_layers)

    dim, n_layers = 16, 8
    layers = _mlp_layer_params(jax.random.PRNGKey(0), n_layers, dim)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, dim))

    ref = x
    for layer in layers:
        ref = _mlp_block(layer, ref)

    mesh = make_named_mesh({"pp": 4})
    stacked = stack_layers(layers)
    out = pipeline_forward(_mlp_block, stacked, x, mesh,
                           n_microbatches=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # more microbatches than stages also works (smaller bubble)
    out8 = pipeline_forward(_mlp_block, stacked, x, mesh,
                            n_microbatches=8)
    np.testing.assert_allclose(np.asarray(out8), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_pipeline_is_differentiable():
    from tpu9.parallel import (make_named_mesh, pipeline_forward,
                               stack_layers)

    dim, n_layers = 8, 4
    layers = _mlp_layer_params(jax.random.PRNGKey(0), n_layers, dim)
    stacked = stack_layers(layers)
    mesh = make_named_mesh({"pp": 4})
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 2, dim))
    target = jnp.ones_like(x)

    def loss_fn(p):
        y = pipeline_forward(_mlp_block, p, x, mesh, n_microbatches=4)
        return jnp.mean((y - target) ** 2)

    # grads through ppermute match the sequential program's grads
    def seq_loss(p_list):
        y = x
        for layer in p_list:
            y = _mlp_block(layer, y)
        return jnp.mean((y - target) ** 2)

    g_pipe = jax.grad(loss_fn)(stacked)
    g_seq = jax.grad(seq_loss)(layers)
    g_seq_stacked = stack_layers(g_seq)
    for k in ("w1", "w2"):
        np.testing.assert_allclose(np.asarray(g_pipe[k]),
                                   np.asarray(g_seq_stacked[k]),
                                   rtol=1e-4, atol=1e-5)
