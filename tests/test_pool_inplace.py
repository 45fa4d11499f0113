"""The KV pool carried whole (ISSUE 25): the paged kernels read the stacked
``[L, N, BS, KH, D]`` pool at a layer, ``decoder_forward`` writes one token
in place at ``[layer, block, offset]`` and returns the pool it was given
with just those rows changed, and the lowered decode programs neither cut a
plane out of the pool nor stack one back. Everything here runs on the plain
CPU backend (kernels interpreted, dispatchers on their XLA oracles)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu9.models import init_decoder
from tpu9.models.llama import LLAMA_PRESETS
from tpu9.models.mixtral import MIXTRAL_PRESETS
from tpu9.models.transformer import _mlp_block, decoder_forward
from tpu9.ops.attention import (paged_attention_dispatch,
                                paged_verify_attention)
from tpu9.ops.norms import rms_norm
from tpu9.ops.paged_attention import (paged_decode_attention,
                                      paged_decode_attention_quant,
                                      xla_paged_decode_attention)
from tpu9.ops.quant import maybe_matmul, quantize_kv
from tpu9.ops.rotary import apply_rope, rope_rows
from tpu9.serving.engine import EngineConfig, InferenceEngine
from tpu9.serving.graphs import GraphFactory
from tpu9.serving.shard.policy import SingleDevicePolicy

L, N, BS, KH, D, QH, B, MB = 3, 9, 16, 2, 32, 4, 2, 4
PRESETS = {"dense": LLAMA_PRESETS["llama-tiny"],
           "moe": MIXTRAL_PRESETS["mixtral-tiny"]}


def _random_pool(rng, layers, kh, d, quant, dtype=jnp.float32):
    """{"k", "v"[, "k_scale", "v_scale"]} of random content."""
    shape = (layers, N, BS, kh, d)
    if not quant:
        return {n: jnp.asarray(rng.standard_normal(shape), dtype)
                for n in ("k", "v")}
    pool = {n: jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
            for n in ("k", "v")}
    for n in ("k_scale", "v_scale"):
        pool[n] = jnp.asarray(rng.uniform(0.005, 0.02, shape[:-1]),
                              jnp.float32)
    return pool


def _table():
    """Distinct blocks per row, none of them block 0."""
    return (jnp.arange(B * MB, dtype=jnp.int32) + 1).reshape(B, MB)


# ---------------------------------------------------------------------------
# (a) the kernels on the whole pool, at a layer
# ---------------------------------------------------------------------------

def _kernel_out(q, pool, table, lens, quant, layer=0, plane=False):
    arrays = [pool[n][layer] if plane else pool[n] for n in sorted(pool)]
    layer = 0 if plane else layer
    if quant:                                   # k, k_scale, v, v_scale
        k, ks, v, vs = arrays
        return paged_decode_attention_quant(q, k, v, ks, vs, table, lens,
                                            layer=layer, interpret=True)
    k, v = arrays
    return paged_decode_attention(q, k, v, table, lens, layer=layer,
                                  interpret=True)


def _oracle_out(q, pool, table, lens, layer):
    return xla_paged_decode_attention(
        q, pool["k"][layer], pool["v"][layer], table, lens,
        *(pool[n][layer] for n in ("k_scale", "v_scale") if n in pool))


@pytest.mark.parametrize("layer", [0, 1, L - 1])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_kernel_reads_the_stacked_pool_at_its_layer(quant, layer):
    rng = np.random.default_rng(layer + 10 * quant)
    q = jnp.asarray(rng.standard_normal((B, 1, QH, D)), jnp.float32)
    pool = _random_pool(rng, L, KH, D, quant)
    lens = jnp.asarray([BS * MB, BS + 3], jnp.int32)
    got = _kernel_out(q, pool, _table(), lens, quant, layer)
    want = _oracle_out(q, pool, _table(), lens, layer)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # a traced layer is the same kernel (one compile for every layer)
    traced = jax.jit(lambda i: _kernel_out(q, pool, _table(), lens, quant,
                                           i))(jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(traced), np.asarray(got))


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_kernel_takes_one_layers_plane_as_before(quant):
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((B, 1, QH, D)), jnp.float32)
    pool = _random_pool(rng, L, KH, D, quant)
    lens = jnp.asarray([40, 17], jnp.int32)
    got = _kernel_out(q, pool, _table(), lens, quant, layer=1, plane=True)
    whole = _kernel_out(q, pool, _table(), lens, quant, layer=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(whole))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_oracle_out(q, pool, _table(), lens, 1)),
        atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_xla_paths_gather_the_stacked_pool_at_the_layer(quant):
    """The oracle behind the dispatcher (what the CPU serves) and the
    verify attention index ``pool[layer, table]`` in one gather."""
    rng = np.random.default_rng(7)
    pool = _random_pool(rng, L, KH, D, quant)
    scales = [pool[n] for n in ("k_scale", "v_scale") if n in pool]
    planes = [s[2] for s in scales]
    lens = jnp.asarray([33, 64], jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, 1, QH, D)), jnp.float32)
    got = paged_attention_dispatch(q, pool["k"], pool["v"], _table(), lens,
                                   *scales, layer=2)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(_oracle_out(q, pool, _table(), lens, 2)))
    qt = jnp.asarray(rng.standard_normal((B, 3, QH, D)), jnp.float32)
    pos = jnp.asarray([[30, 31, 32], [61, 62, 63]], jnp.int32)
    got = paged_verify_attention(qt, pool["k"], pool["v"], _table(), pos,
                                 *scales, layer=2)
    want = paged_verify_attention(qt, pool["k"][2], pool["v"][2], _table(),
                                  pos, *planes)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# (b) decoder_forward: the pool it returns, against slice-and-stack
# ---------------------------------------------------------------------------

def _slice_and_stack_forward(params, tokens, cfg, positions, cache,
                             cache_len, decode):
    """The semantics the in-place write replaced, from the model's own
    pieces: every layer cuts its plane out of the pool, writes the window's
    tokens into the plane, attends over the plane, and the planes are
    stacked into a new pool at the end."""
    b, t = tokens.shape
    table, bs = cache["table"], cache["k"].shape[2]
    names = [n for n in cache if n != "table"]
    x = params["embed"][tokens].astype(cfg.dtype)
    sin, cos = rope_rows(positions, cfg.head_dim, cfg.rope_theta)
    bi = jnp.take_along_axis(table, positions // bs, axis=1)      # [B, T]
    oi = positions % bs
    planes = {n: [] for n in names}
    for i, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps, cfg.norm_offset)
        q, k, v = (maybe_matmul(h, layer[w]).reshape(b, t, heads,
                                                     cfg.head_dim)
                   for w, heads in (("wq", cfg.n_heads),
                                    ("wk", cfg.n_kv_heads),
                                    ("wv", cfg.n_kv_heads)))
        q = apply_rope(q, sin, cos)
        new = {"k": apply_rope(k, sin, cos), "v": v}
        if "k_scale" in cache:
            new["k"], new["k_scale"] = quantize_kv(new["k"])
            new["v"], new["v_scale"] = quantize_kv(new["v"])
        plane = {n: cache[n][i].at[bi, oi].set(new[n]) for n in names}
        scales = [plane[n] for n in ("k_scale", "v_scale") if n in plane]
        if decode:
            out = xla_paged_decode_attention(q, plane["k"], plane["v"],
                                             table, cache_len, *scales)
        else:
            out = paged_verify_attention(q, plane["k"], plane["v"], table,
                                         positions, *scales)
        x = x + maybe_matmul(out.reshape(b, t, -1), layer["wo"])
        x, _ = _mlp_block(layer, x, cfg)
        for n in names:
            planes[n].append(plane[n])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, cfg.norm_offset)
    logits = maybe_matmul(x, params["lm_head"]).astype(jnp.float32)
    return logits, {n: jnp.stack(planes[n]) for n in names}, (bi, oi)


@pytest.fixture(scope="module")
def models():
    return {name: (cfg, init_decoder(jax.random.PRNGKey(0), cfg))
            for name, cfg in PRESETS.items()}


@pytest.mark.parametrize("model", ["dense", "moe"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("t", [1, 3], ids=["decode", "verify"])
def test_forward_writes_only_the_windows_rows_in_place(models, model, quant,
                                                       t):
    cfg, params = models[model]
    rng = np.random.default_rng(t + 10 * quant)
    cache = _random_pool(rng, cfg.n_layers, cfg.n_kv_heads, cfg.head_dim,
                         quant, cfg.dtype)
    cache["table"] = _table()
    clen = jnp.asarray([5, 37], jnp.int32)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, t)), jnp.int32)
    positions = clen[:, None] + jnp.arange(t)[None, :]
    decode = t == 1
    logits, got = decoder_forward(params, tokens, cfg, positions=positions,
                                  kv_cache=dict(cache), cache_len=clen + t,
                                  decode=decode)
    want_logits, want, (bi, oi) = _slice_and_stack_forward(
        params, tokens, cfg, positions, cache, clen + t, decode)
    assert set(got) == set(cache)
    np.testing.assert_array_equal(np.asarray(got["table"]),
                                  np.asarray(cache["table"]))
    written = np.zeros((cfg.n_layers, N, BS), bool)
    written[:, np.asarray(bi), np.asarray(oi)] = True
    assert written.sum() == cfg.n_layers * B * t
    for name in want:
        new, old = np.asarray(got[name]), np.asarray(cache[name])
        # the pool slice-and-stack would have built, bit for bit
        np.testing.assert_array_equal(new, np.asarray(want[name]))
        # and nothing but the window's rows of each layer has changed
        np.testing.assert_array_equal(new[~written], old[~written])
        assert (new[written] != old[written]).any()
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# (c) a K-step window is K one-step windows
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_k4_window_equals_four_single_steps(models, quant):
    cfg, params = models["dense"]
    ecfg = EngineConfig(max_batch=B, max_seq_len=BS * MB, decode_steps=(1, 4),
                        kv_block_size=BS, kv_pool_blocks=N - 1,
                        prefill_chunk=BS,
                        kv_quant="int8" if quant else "")
    graphs = GraphFactory(cfg, ecfg, SingleDevicePolicy(), chunk=BS,
                          kv_quant=quant)
    rng = np.random.default_rng(3)
    cache = _random_pool(rng, cfg.n_layers, cfg.n_kv_heads, cfg.head_dim,
                         quant, cfg.dtype)
    cache["table"] = _table()
    last = jnp.asarray([[7], [11]], jnp.int32)
    clen = jnp.asarray([14, 30], jnp.int32)         # row 0 crosses a block
    steps = jnp.asarray([4, 4], jnp.int32)       # neither lane is parked
    key = jax.random.PRNGKey(0)

    def fresh():                                    # the pool is donated
        return {n: jnp.array(a) for n, a in cache.items()}

    last4, kv4, clen4, _, toks4 = graphs.decode_k(4)(
        params, fresh(), last, clen, steps, key)
    kv, step_last, step_len, r, toks = fresh(), last, clen, key, []
    for _ in range(4):
        step_last, kv, step_len, r, tok = graphs.decode_k(1)(
            params, kv, step_last, step_len, steps, r)
        toks.append(np.asarray(tok)[0])
    np.testing.assert_array_equal(np.asarray(toks4), np.stack(toks))
    np.testing.assert_array_equal(np.asarray(last4), np.asarray(step_last))
    np.testing.assert_array_equal(np.asarray(clen4), np.asarray(step_len))
    for name in cache:
        np.testing.assert_array_equal(np.asarray(kv4[name]),
                                      np.asarray(kv[name]))


# ---------------------------------------------------------------------------
# (e) the lowered decode programs: no plane cut out, none stacked back
# ---------------------------------------------------------------------------

ENGINE = dict(max_batch=2, max_seq_len=256, prefill_buckets=(32, 64),
              decode_steps=(1, 4), kv_block_size=32, kv_pool_blocks=16,
              prefill_chunk=32, prefix_cache_blocks=4)


@pytest.fixture(scope="module")
def lowered(models):
    out = {}
    for name, (cfg, params) in models.items():
        eng = InferenceEngine(params, cfg, EngineConfig(**ENGINE))
        pool = tuple(eng.kv_cache["k"].shape)
        for key, fn, args in eng.graphs.lowering_jobs(
                eng.params, eng.kv_cache, eng._pool_dict(), eng._scratch,
                eng._mb, eng._buckets, eng._spec_lens, eng._rng):
            if key[0] == "decode":
                out[name, key[1]] = (
                    pool, fn.lower(*args).as_text(debug_info=True))
    return out


@pytest.mark.parametrize("model", ["dense", "moe"])
@pytest.mark.parametrize("k", [1, 4])
def test_lowered_decode_neither_slices_nor_stacks_the_pool(lowered, model,
                                                           k):
    pool, text = lowered[model, k]
    dims = "x".join(str(d) for d in pool)           # LxNxBSxKHxD
    plane = "x".join(str(d) for d in pool[1:])
    assert f"tensor<{dims}x" in text                # the pool is in there
    assert not re.search(r'["/]kv\.pack["/]', text)
    assert not re.search(r'["/]kv\.slice["/]', text)
    for line in text.splitlines():
        result = line.rsplit("->", 1)[-1]
        if "stablehlo.concatenate" in line:         # jnp.stack of planes
            assert f"tensor<{dims}x" not in result, line
        if re.search(r"stablehlo\.(dynamic_)?slice\b", line):
            # one layer's plane, with or without its leading 1
            assert f"tensor<{plane}x" not in result \
                and f"tensor<1x{plane}x" not in result, line
    # what is left of the pool plumbing: one scatter per layer for k and v
    assert text.count('"stablehlo.scatter"(') == 2 * PRESETS[model].n_layers
