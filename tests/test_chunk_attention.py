"""The chunked-prefill kernel (ISSUE 44): a chunk's queries attend the keys
that are WRITTEN — a block past the last query is never copied or
multiplied — with the query heads of a KV head in one tile.
Interpreted on the CPU at the three benchmark cells' per-chip shapes,
against the XLA oracle; then through the programs and the engine that
dispatch it."""

import asyncio
import contextlib
import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import tpu9.ops.attention as attention_ops
import tpu9.ops.chunk_attention as chunk_ops
from tpu9.models import init_decoder
from tpu9.models.llama import LLAMA_PRESETS
from tpu9.serving.engine import EngineConfig, InferenceEngine
from tpu9.serving.graphs import GraphFactory
from tpu9.serving.shard.policy import SingleDevicePolicy

D, LAYERS, LAYER = 128, 2, 1
# cell: KV heads a chip, query heads a KV head, the scratch's width
SHAPES = {"mixtral": (8, 4, 4096), "tp4-long": (2, 4, 16384),
          "ouro": (16, 1, 1024)}
# f32: summation order alone. bf16: the result's own rounding (2**-8
# relative, values up to 4 where a query sees few keys) and the
# probabilities' on their way into the second product
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 3e-2}


def _offsets(w, s):
    """A prompt's first chunk, its second, one that starts inside a page in
    the middle of the scratch, and the last the scratch holds."""
    return {"first": 0, "second": w, "mid": s // 2 + 77, "last": s - w}


def _case(kh, group, s, w, offset, dtype, batch=1, seed=0):
    """(q, k, v, positions): a scratch that holds NaN in every position
    past ``offset + w`` and in the whole of the other layer."""
    rng = np.random.default_rng(seed + offset + w)
    k, v = (rng.standard_normal((LAYERS, batch, s, kh, D)).astype(np.float32)
            for _ in range(2))
    for cache in (k, v):
        cache[LAYER, :, offset + w:] = np.nan
        cache[1 - LAYER] = np.nan
    q = rng.standard_normal((batch, w, kh * group, D)).astype(np.float32)
    positions = offset + np.arange(w, dtype=np.int32)[None].repeat(batch, 0)
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(v, dtype), jnp.asarray(positions))


def _oracle(q, k, v, positions, layer=LAYER):
    """The XLA form over the layer's plane with the NaN taken out: it
    multiplies what it masks, so it cannot be asked about NaN."""
    return attention_ops.xla_chunk_prefill_attention(
        q, jnp.nan_to_num(k[layer]), jnp.nan_to_num(v[layer]), positions)


def _close(got, want, dtype):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("at", ["first", "second", "mid", "last"])
@pytest.mark.parametrize("w", [128, 512])
@pytest.mark.parametrize("cell", list(SHAPES))
def test_the_kernel_attends_the_written_keys_and_nothing_else(cell, w, at,
                                                              dtype):
    """A chunk (128) and an admission group (512) at a cell's shapes: equal
    to the oracle, and finite although everything past ``offset + w`` and
    all of the other layer is NaN — which is the bound: a NaN copied into a
    product would be in the result, masked or not."""
    kh, group, s = SHAPES[cell]
    q, k, v, positions = _case(kh, group, s, w, _offsets(w, s)[at], dtype)
    got = chunk_ops.flash_chunk_prefill_attention(
        q, k, v, positions[:, 0], LAYER, interpret=True)
    assert got.shape == q.shape and got.dtype == q.dtype
    _close(got, _oracle(q, k, v, positions), dtype)


@pytest.mark.parametrize("kh,group,w,s,itemsize,want", [
    (8, 4, 128, 4096, 2, (128, 512)), (8, 4, 512, 4096, 2, (128, 512)),
    (2, 4, 128, 16384, 2, (128, 1024)), (16, 1, 128, 1024, 2, (128, 512)),
    (16, 1, 512, 1024, 2, (512, 512)), (16, 1, 384, 1024, 2, (128, 512)),
    # four heads a chip: 1,024 keys are the megabyte; float32 halves them;
    # a narrow scratch bounds the block
    (4, 4, 128, 4096, 2, (128, 1024)), (4, 4, 128, 4096, 4, (128, 512)),
    (4, 2, 256, 384, 2, (256, 128)),
])
def test_blocks_follow_the_shape(kh, group, w, s, itemsize, want):
    """No knob: at most 512 query rows a KV head; a key block of 512 keys,
    or 1,024 where every head's rows of it stay under a megabyte, dividing
    the scratch."""
    assert chunk_ops.chunk_blocks(w, s, kh, group, D, itemsize) == want


def test_rows_of_a_batch_have_their_own_offsets_and_a_plane_is_a_pool():
    """Two rows at different offsets in one call (a dense engine's chunked
    prefill), over one layer's plane ``[B, S, KH, D]``."""
    kh, group, s, w = 2, 2, 512, 128
    q, k, v, _ = _case(kh, group, s, w, 0, jnp.float32, batch=2)
    k, v = (jnp.nan_to_num(x[LAYER]) for x in (k, v))
    positions = jnp.asarray([[5], [300]]) + jnp.arange(w)[None]
    got = chunk_ops.flash_chunk_prefill_attention(
        q, k, v, positions[:, 0], interpret=True)
    _close(got, attention_ops.xla_chunk_prefill_attention(q, k, v, positions),
           jnp.float32)


@pytest.mark.parametrize("head_dim,dtype", [
    (64, jnp.bfloat16), (256, jnp.bfloat16), (64, jnp.float32)],
    ids=["64-bf16", "256-bf16", "64-f32"])
def test_heads_that_are_not_128_wide_are_loaded_a_head_at_a_time(head_dim,
                                                                 dtype):
    """The pair-of-heads word load is for 128-lane bfloat16 rows; the other
    widths the dispatchers admit take ``ref[:, h, :]``."""
    rng = np.random.default_rng(head_dim)
    kh, group, s, w, offset = 4, 2, 1024, 128, 600
    q = jnp.asarray(rng.standard_normal((1, w, kh * group, head_dim)), dtype)
    k, v = (jnp.asarray(rng.standard_normal((1, s, kh, head_dim)), dtype)
            for _ in range(2))
    positions = offset + jnp.arange(w)[None]
    got = chunk_ops.flash_chunk_prefill_attention(
        q, k, v, positions[:, 0], interpret=True)
    _close(got, attention_ops.xla_chunk_prefill_attention(q, k, v, positions),
           dtype)


# ---------------------------------------------------------------------------
# the dispatcher and its twin
# ---------------------------------------------------------------------------


@pytest.fixture
def kernel_interpreted(monkeypatch):
    """Chunked prefill as a TPU replica dispatches it, on the CPU:
    ``chunk_prefill_attention`` takes the kernel, interpreted, wherever the
    kernel's own shape rule admits the call — the backend and the head
    widths the MXU wants are all that is waived. ``seen["traced"]`` counts
    the kernel's traces."""
    @contextlib.contextmanager
    def interpreted():
        rule, kernel = (attention_ops.chunk_kernel_declined,
                        chunk_ops.flash_chunk_prefill_attention)
        seen = {"traced": 0}

        def declined(t, s, head_dim):
            with monkeypatch.context() as on_chip:
                on_chip.setattr(attention_ops, "on_tpu", lambda: True)
                on_chip.setattr(attention_ops, "_KERNEL_HEAD_DIMS",
                                (head_dim,))
                return rule(t, s, head_dim)

        @functools.wraps(kernel)
        def run(*args, **kwargs):
            seen["traced"] += 1
            return kernel(*args, interpret=True, **kwargs)

        with monkeypatch.context() as patched:
            patched.setattr(attention_ops, "chunk_kernel_declined", declined)
            patched.setattr(chunk_ops, "flash_chunk_prefill_attention", run)
            yield seen

    return interpreted


def test_the_twin_says_why_a_shape_takes_the_xla_form(monkeypatch):
    declined = attention_ops.chunk_kernel_declined
    assert declined(128, 4096, 128) == "backend cpu is not tpu"
    monkeypatch.setattr(attention_ops, "on_tpu", lambda: True)
    assert declined(128, 4096, 128) == "" == declined(512, 16384, 64)
    assert declined(128, 4096, 96).startswith("head_dim 96 not in")
    # a speculative verify window of 1 + 4 queries
    assert declined(5, 4096, 128) == \
        "chunk (5, 4096) is not a multiple of the 128 block"
    assert "(128, 200)" in declined(128, 200, 128)


def test_a_verify_window_keeps_the_xla_form_on_the_chip(monkeypatch):
    """``paged_verify_attention`` shares the dispatcher; its 5-wide window
    is declined by shape, so on a TPU it still equals its oracle (and
    would raise here if it took the kernel: no interpreter is asked for)."""
    monkeypatch.setattr(attention_ops, "on_tpu", lambda: True)
    rng = np.random.default_rng(3)
    b, t, kh, group, bs, mb = 2, 5, 2, 2, 128, 3
    pool_k, pool_v = (jnp.asarray(rng.standard_normal(
        (b * mb + 1, bs, kh, D)), jnp.float32) for _ in range(2))
    table = jnp.arange(1, b * mb + 1, dtype=jnp.int32).reshape(b, mb)
    q = jnp.asarray(rng.standard_normal((b, t, kh * group, D)), jnp.float32)
    positions = jnp.asarray([[130], [7]]) + jnp.arange(t)[None]
    got = attention_ops.paged_verify_attention(q, pool_k, pool_v, table,
                                               positions)
    dense_k, dense_v = (pool[table].reshape(b, mb * bs, kh, D)
                        for pool in (pool_k, pool_v))
    want = attention_ops.xla_chunk_prefill_attention(q, dense_k, dense_v,
                                                     positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-6)


def test_the_dispatcher_slices_for_the_xla_form_and_not_for_the_kernel(
        kernel_interpreted):
    """Off the chip the whole scratch and a layer give what the layer's
    plane gives; with the kernel the same call reads the scratch whole."""
    kh, group, s, w = 2, 2, 512, 128
    q, k, v, positions = _case(kh, group, s, w, 256, jnp.float32)
    k, v = jnp.nan_to_num(k), jnp.nan_to_num(v)
    want = attention_ops.xla_chunk_prefill_attention(q, k[LAYER], v[LAYER],
                                                     positions)
    by_xla = attention_ops.chunk_prefill_attention(q, k, v, positions,
                                                   layer=LAYER)
    np.testing.assert_array_equal(np.asarray(by_xla), np.asarray(want))
    with kernel_interpreted() as seen:
        by_kernel = attention_ops.chunk_prefill_attention(
            q, k, v, positions, layer=LAYER)
    assert seen["traced"] == 1
    _close(by_kernel, want, jnp.float32)


@pytest.mark.multichip
@pytest.mark.parametrize("layers", [(), (3,)], ids=["plane", "scratch"])
def test_the_kernel_under_shard_map_matches_the_oracle(kernel_interpreted,
                                                       layers):
    """On a mesh each chip runs the kernel on its own heads: four virtual
    CPU devices, the scratch and the queries sharded on the head axis, the
    offset and the layer replicated."""
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 1, 1, 4),
                ("dp", "fsdp", "sp", "tp"))
    kh, group, s, w, layer = 8, 2, 512, 128, 2 if layers else 0
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((1, w, kh * group, D)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((*layers, 1, s, kh, D)),
                        jnp.float32) for _ in range(2))
    positions = 200 + jnp.arange(w)[None]
    cache = attention_ops._POOL5 if layers else attention_ops._HEADS4
    heads = NamedSharding(mesh, attention_ops._HEADS4)
    with kernel_interpreted() as seen:
        got = jax.jit(functools.partial(
            attention_ops.chunk_prefill_attention, layer=layer, mesh=mesh))(
                jax.device_put(q, heads),
                jax.device_put(k, NamedSharding(mesh, cache)),
                jax.device_put(v, NamedSharding(mesh, cache)), positions)
    assert seen["traced"] == 1
    want = attention_ops.xla_chunk_prefill_attention(
        q, k[layer] if layers else k, v[layer] if layers else v, positions)
    _close(got, want, jnp.float32)
    assert got.sharding.spec == P(None, None, "tp", None)


# ---------------------------------------------------------------------------
# the programs and the engine that dispatch it
# ---------------------------------------------------------------------------

C, S_ENGINE = 128, 1536


@pytest.fixture(scope="module")
def tiny():
    cfg = replace(LLAMA_PRESETS["llama-tiny"], dtype=jnp.float32,
                  max_seq_len=2048)         # the positions: no parameter
    return cfg, init_decoder(jax.random.PRNGKey(0), cfg)


def _ecfg(**kw):
    return EngineConfig(**{**dict(
        max_batch=2, max_seq_len=S_ENGINE, prefill_buckets=(C,),
        decode_steps=(1, 4), kv_block_size=32, kv_pool_blocks=96,
        prefill_chunk=C, prefix_cache_blocks=40, admit_group_chunks=4),
        **kw})


@pytest.mark.parametrize("last_idx", [C - 1, 40], ids=["full", "partial"])
@pytest.mark.parametrize("offset", [0, 3 * C], ids=["off0", "off3C"])
def test_a_chunk_step_with_the_kernel_gives_the_xla_forms_logits(
        tiny, kernel_interpreted, offset, last_idx):
    """``traced_chunk_step``, the body the ``chunk`` and ``group`` programs
    share, with a full chunk and with a partly filled last one (the logits
    are read at ``last_idx``; the padding behind it is written and attended
    like tokens, by both forms)."""
    cfg, params = tiny
    rng = np.random.default_rng(offset + last_idx)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, C), jnp.int32)
    scratch = {n: jnp.asarray(rng.standard_normal(
        (cfg.n_layers, 1, S_ENGINE, cfg.n_kv_heads, cfg.head_dim)),
        cfg.dtype) for n in ("k", "v")}

    def step():
        graphs = GraphFactory(cfg, _ecfg(), SingleDevicePolicy(), chunk=C)
        return jax.jit(graphs.traced_chunk_step)(
            params, dict(scratch), tokens, offset, last_idx)

    want, want_scratch = step()
    with kernel_interpreted() as seen:
        got, got_scratch = step()
    assert seen["traced"] == cfg.n_layers
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)
    for n in ("k", "v"):
        np.testing.assert_allclose(np.asarray(got_scratch[n]),
                                   np.asarray(want_scratch[n]),
                                   atol=2e-4, rtol=2e-4)


def _serve(engine, prompts, new=6):
    async def go():
        await engine.start()
        out = [await engine.generate(list(p), max_new_tokens=new)
               for p in prompts]
        await engine.stop()
        return out
    return asyncio.run(go())


@pytest.mark.parametrize("prefix", ["cold", "hit"])
@pytest.mark.parametrize("n_chunks", [1, 5])
def test_an_engine_with_the_kernel_serves_the_xla_forms_tokens(
        tiny, kernel_interpreted, n_chunks, prefix):
    """One chunk, and five (an admission group and a tail) with a partly
    filled last one; ``hit``: a first prompt over the same leading half, so
    the second finds its blocks in the prefix cache, is gathered into the
    scratch and starts its chunks inside a key block. Token for token what
    the XLA form serves, and ``stats()`` says which form was built."""
    cfg, params = tiny
    rng = np.random.default_rng(100 * n_chunks)
    prompt = rng.integers(3, 250, n_chunks * C - 23).tolist()
    prompts = [prompt]
    if prefix == "hit":
        prompts.insert(0, prompt[:max(len(prompt) // 2, 40)]
                       + rng.integers(3, 250, 9).tolist())
    xla = InferenceEngine(params, cfg, _ecfg())
    want = _serve(xla, prompts)
    assert xla.stats()["attention_prefill"] == "xla: backend cpu is not tpu"
    with kernel_interpreted() as seen:
        engine = InferenceEngine(params, cfg, _ecfg())
        got = _serve(engine, prompts)
        assert engine.stats()["attention_prefill"] == "pallas"
    assert seen["traced"] >= cfg.n_layers
    assert got == want
    stats = engine.stats()
    assert stats["admit_chunks_grouped"] == xla.stats()["admit_chunks_grouped"]
    if prefix == "hit":
        assert engine.prefix_cache.stats()["hits"] == 1
    else:
        assert (stats["admit_chunks"], stats["admit_chunks_grouped"]) \
            == (n_chunks, n_chunks // 4 * 4)


def test_an_engine_whose_chunk_is_no_multiple_of_128_says_so(tiny,
                                                             monkeypatch):
    """On the chip, a 32-token chunk is declined by shape at both admitted
    widths (32 and its group of 4 is 128: that one the kernel takes)."""
    cfg, params = tiny
    monkeypatch.setattr(attention_ops, "on_tpu", lambda: True)
    monkeypatch.setattr(attention_ops, "_KERNEL_HEAD_DIMS", (cfg.head_dim,))
    engine = InferenceEngine(params, cfg, _ecfg(
        prefill_chunk=32, prefill_buckets=(32,), max_seq_len=256,
        kv_pool_blocks=16, prefix_cache_blocks=0))
    assert engine.stats()["attention_prefill"] == \
        "xla: chunk (32, 256) is not a multiple of the 128 block"
    assert engine.stats()["attention_decode"] == \
        "xla: kv block 32 is not a multiple of 128"


def test_an_engine_names_the_paged_decode_kernels_body(tiny, monkeypatch):
    """``stats()["attention_decode"]`` on the chip: which body of the paged
    kernel this engine's pool takes (a float32 pool of heads under 128 wide
    keeps the grid, a head an update; the walk's blocks of heads are named
    in ``test_paged_walk``)."""
    cfg, params = tiny
    monkeypatch.setattr(attention_ops, "on_tpu", lambda: True)
    monkeypatch.setattr(attention_ops, "_KERNEL_HEAD_DIMS", (cfg.head_dim,))
    engine = InferenceEngine(params, cfg, _ecfg(
        kv_block_size=128, kv_pool_blocks=24, prefix_cache_blocks=0))
    said = engine.stats()["attention_decode"]
    assert said == "pallas grid, 1 head an update, 1 page a step"
    assert said == attention_ops.paged_kernel_form(
        engine.kv_cache["k"], cfg.n_heads, engine._mb)
    assert engine.stats()["attention_prefill"] == "pallas"
