"""The admission group as ONE forward (ISSUE 33): the ``group`` program runs
``decoder_forward`` once over the g·C contiguous positions of g chunks and
splices their g·C/BS blocks, where it used to ``lax.scan`` over g chunk
forwards and read every weight g times. Its pool blocks, scratch and last
logits are those of g calls of ``chunk`` + ``splice``; the lowered program
holds no loop; the engine counts the chunks that went through it."""

import asyncio
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu9.analysis.graphcheck.passes import walk_eqns
from tpu9.models import init_decoder
from tpu9.models.llama import LLAMA_PRESETS
from tpu9.models.mixtral import MIXTRAL_PRESETS
from tpu9.serving.engine import EngineConfig, InferenceEngine
from tpu9.serving.graphs import GraphFactory
from tpu9.serving.shard.policy import SingleDevicePolicy

G, C, BS, S, N = 4, 32, 16, 256, 24
TRASH = N - 1
_MOE = MIXTRAL_PRESETS["mixtral-tiny"]
# dropless, as the benchmark's Mixtral states it: capacity factor = E / k
# makes an expert's capacity the tokens of the call, so nothing is dropped
# at either width. (Under E / k the set of dropped tokens already depends
# on where a chunk ends — earlier tokens of a call win capacity — so a
# group and its g chunks would differ there by design; not tested, and
# not "fixed" by this program.)
_DROPLESS = replace(_MOE, moe_capacity_factor=_MOE.n_experts / _MOE.moe_top_k)
CONFIGS = {
    "dense-f32": replace(LLAMA_PRESETS["llama-tiny"], dtype=jnp.float32),
    "moe-f32": replace(_DROPLESS, dtype=jnp.float32),
    "dense-bf16": replace(LLAMA_PRESETS["llama-tiny"], dtype=jnp.bfloat16),
    "moe-bf16": replace(_DROPLESS, dtype=jnp.bfloat16),
}
# f32: summation order alone. bf16: one rounding is 2**-8 relative, on
# activations of order 1 summed over two layers
TOLERANCE = {"f32": 1e-5, "bf16": 2e-2}


@pytest.fixture(scope="module")
def models():
    return {name: (cfg, init_decoder(jax.random.PRNGKey(0), cfg))
            for name, cfg in CONFIGS.items()}


def _factory(cfg):
    ecfg = EngineConfig(max_batch=2, max_seq_len=S, decode_steps=(1,),
                        kv_block_size=BS, kv_pool_blocks=N - 1,
                        prefill_chunk=C, admit_group_chunks=G)
    return GraphFactory(cfg, ecfg, SingleDevicePolicy(), chunk=C)


def _state(cfg, rng):
    """A random pool and a scratch holding a random prefix everywhere (the
    programs are donated theirs: every call takes a fresh copy)."""
    def arrays(*shape):
        return {n: jnp.asarray(rng.standard_normal(shape), cfg.dtype)
                for n in ("k", "v")}
    pool = arrays(cfg.n_layers, N, BS, cfg.n_kv_heads, cfg.head_dim)
    scratch = arrays(cfg.n_layers, 1, S, cfg.n_kv_heads, cfg.head_dim)
    return pool, scratch


def _fresh(tree):
    return {n: jnp.array(a) for n, a in tree.items()}


@pytest.mark.parametrize("last_valid", [C, 5], ids=["full", "partial"])
@pytest.mark.parametrize("offset", [0, 2 * C], ids=["off0", "off2C"])
@pytest.mark.parametrize("model", list(CONFIGS))
def test_group_equals_g_chunks_and_splices(models, model, offset,
                                           last_valid):
    cfg, params = models[model]
    graphs = _factory(cfg)
    rng = np.random.default_rng(offset + last_valid)
    pool, scratch = _state(cfg, rng)
    toks = rng.integers(1, cfg.vocab_size, (G, C)).astype(np.int32)
    toks[-1, last_valid:] = 0                   # the last chunk's padding
    phys = (1 + np.arange(G * C // BS, dtype=np.int32)).reshape(G, C // BS)
    if last_valid <= BS:                        # a padded block: to trash
        phys[-1, 1:] = TRASH
    last_idx = last_valid - 1

    got_pool, got_scratch, got_last = graphs.chunk_group_fn(G)(
        params, _fresh(pool), _fresh(scratch), jnp.asarray(toks), offset,
        last_idx, jnp.asarray(phys))

    want_pool, want_scratch = _fresh(pool), _fresh(scratch)
    for i in range(G):
        off = offset + i * C
        want_last, want_scratch = graphs.chunk_fn()(
            params, jnp.asarray(toks[i:i + 1]), off, want_scratch,
            last_idx if i == G - 1 else C - 1)
        want_pool = graphs.splice_fn()(
            want_pool, want_scratch["k"], want_scratch["v"], off,
            jnp.asarray(phys[i]))

    tol = TOLERANCE[model.split("-")[1]]
    written = np.zeros(N, bool)
    written[phys.ravel()] = True
    for name in ("k", "v"):
        got, want = (np.asarray(t[name], np.float32)
                     for t in (got_pool, want_pool))
        # the trash block holds whichever padded block was written last
        np.testing.assert_allclose(np.delete(got, TRASH, axis=1),
                                   np.delete(want, TRASH, axis=1),
                                   atol=tol, rtol=tol)
        # blocks the group was not given are the pool's own, bit for bit
        np.testing.assert_array_equal(got[:, ~written],
                                      np.asarray(pool[name], np.float32)
                                      [:, ~written])
        got, want, before = (np.asarray(t[name], np.float32) for t in
                             (got_scratch, want_scratch, scratch))
        np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
        span = slice(offset, offset + G * C)
        outside = np.ones(S, bool)
        outside[span] = False
        np.testing.assert_array_equal(got[:, :, outside],
                                      before[:, :, outside])
        assert (got[:, :, span] != before[:, :, span]).any()
    np.testing.assert_allclose(np.asarray(got_last), np.asarray(want_last),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("model", ["dense-f32", "moe-f32"])
def test_lowered_group_reads_each_weight_once(models, model):
    """No ``scan`` / ``while`` anywhere in the traced program, and as many
    ``dot_general`` over an expert stack (dense: an FFN matrix) as there
    are such weights: three a layer, each run once."""
    cfg, params = models[model]
    graphs = _factory(cfg)
    pool, scratch = _state(cfg, np.random.default_rng(0))
    i32 = jnp.int32
    args = (params, pool, scratch, jax.ShapeDtypeStruct((G, C), i32), 0, 0,
            jax.ShapeDtypeStruct((G, C // BS), i32))
    fn = graphs.chunk_group_fn(G)
    eqns = list(walk_eqns(fn.trace(*args).jaxpr.jaxpr))
    assert not {"scan", "while"} & {e.primitive.name for e in eqns}
    layer = params["layers"][0]
    ffn = layer["moe"] if cfg.n_experts else layer
    shapes = {tuple(ffn[w].shape) for w in ("w_gate", "w_up", "w_down")}
    dots = [e for e in eqns if e.primitive.name == "dot_general"
            and shapes & {tuple(v.aval.shape) for v in e.invars}]
    assert len(dots) == 3 * cfg.n_layers
    assert "stablehlo.while" not in fn.lower(*args).as_text()


def _serve(engine, prompt):
    async def go():
        await engine.start()
        out = await engine.generate(list(prompt), max_new_tokens=2)
        await engine.stop()
        return out
    return asyncio.run(go())


@pytest.mark.parametrize("n_chunks, grouped", [(5, 4), (3, 0)])
def test_engine_counts_the_chunks_that_went_through_the_group(
        models, n_chunks, grouped):
    cfg, params = models["dense-f32"]
    engine = InferenceEngine(params, cfg, EngineConfig(
        max_batch=2, max_seq_len=S, prefill_buckets=(C,), decode_steps=(1,),
        kv_block_size=BS, kv_pool_blocks=N - 1, prefill_chunk=C,
        admit_group_chunks=G))
    prompt = [(i * 7) % 250 + 1 for i in range(n_chunks * C - 3)]
    assert len(_serve(engine, prompt)) == 2
    st = engine.stats()
    assert (st["admit_chunks"], st["admit_chunks_grouped"]) \
        == (n_chunks, grouped)
    # one dispatch a group, two (chunk + splice) a single chunk
    assert st["admit_dispatches"] == grouped // G + 2 * (n_chunks - grouped)


def test_a_scratch_shorter_than_one_group_has_no_group_program(models):
    """g·C positions do not fit such a scratch and no prompt it admits
    holds g chunks: the program is neither enumerated nor warmed."""
    cfg, params = models["dense-f32"]
    engine = InferenceEngine(params, cfg, EngineConfig(
        max_batch=2, max_seq_len=2 * C, prefill_buckets=(C,),
        decode_steps=(1,), kv_block_size=BS, kv_pool_blocks=8,
        prefill_chunk=C, admit_group_chunks=G))
    engine.warmup()
    assert engine.graphs.group_chunks == 1
    keys = engine.graphs.reachable_keys(engine._buckets, engine._spec_lens)
    assert not [k for k in set(engine.graphs.compiled) | keys
                if isinstance(k, tuple) and k[0] == "chunkgroup"]
    assert len(_serve(engine, [3, 4, 5] * 15)) == 2     # two chunks
    assert engine.stats()["admit_chunks_grouped"] == 0
