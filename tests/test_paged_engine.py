"""Paged-KV serving engine (VERDICT r03 #5): block-table allocator,
chunked prefill, engine-level prefix reuse.

Reference analogue: the KV accounting the reference's LLM router assumes
(pkg/abstractions/pod/llm.go:124 token pressure, :211 prefix affinity) —
here the engine actually implements the mechanics behind those signals.
"""

import asyncio
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu9.models import init_decoder
from tpu9.models.llama import LLAMA_PRESETS
from tpu9.serving.engine import EngineConfig, InferenceEngine
from tpu9.serving.paged_kv import BlockAllocator, PrefixCache, blocks_for


@pytest.fixture(scope="module")
def tiny():
    cfg = LLAMA_PRESETS["llama-tiny"]
    return cfg, init_decoder(jax.random.PRNGKey(0), cfg)


def _engine(tiny, **kw):
    cfg, params = tiny
    base = dict(max_batch=2, max_seq_len=256, prefill_buckets=(32, 64),
                decode_steps=(1, 4), kv_block_size=32, kv_pool_blocks=16,
                prefill_chunk=32)
    base.update(kw)
    return InferenceEngine(params, cfg, EngineConfig(**base))


def _run(coro):
    return asyncio.run(coro)


def _generate(engine, prompt, max_new):
    """start → generate → stop, the harness every engine test repeats."""

    async def go():
        await engine.start()
        out = await engine.generate(list(prompt), max_new_tokens=max_new)
        await engine.stop()
        return out

    return _run(go())


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------

def test_allocator_alloc_free_refcounts():
    a = BlockAllocator(8, 32)
    got = a.alloc(3)
    assert len(got) == 3 and a.used_count == 3
    a.retain(got[:2])                     # shared by a second holder
    a.release(got)
    assert a.used_count == 2              # two blocks still held
    a.release(got[:2])
    assert a.used_count == 0 and a.free_count == 8
    assert a.alloc(9) is None             # over capacity → refused, not torn


def test_allocator_reservations():
    a = BlockAllocator(8, 32)
    assert a.can_reserve(8 * 32)
    n = a.reserve(8 * 32)
    assert not a.can_reserve(1)
    a.unreserve(n)
    assert a.can_reserve(32)
    assert blocks_for(33, 32) == 2 and blocks_for(32, 32) == 1


def test_prefix_cache_longest_match_and_eviction():
    a = BlockAllocator(16, 4)
    pc = PrefixCache(a, max_blocks=3)
    blocks = a.alloc(3)
    prompt = list(range(12))              # 3 full blocks of 4
    pc.insert(prompt, blocks)
    assert pc.held_blocks == 3
    hit = pc.lookup(prompt + [99])
    assert hit is not None and hit.n_tokens == 12
    pc.release_pin(hit)                   # lookup pins until blocks retained
    # a diverging prompt must not match
    assert pc.lookup([7] + prompt) is None
    a.release(blocks)                     # slot retires; cache refs remain
    assert a.used_count == pc.held_blocks

    # an entry alone bigger than the budget is refused, not flip-flopped
    big = a.alloc(4)
    pc.insert(list(range(16)), big)
    assert pc.held_blocks == 3
    a.release(big)

    # LRU: inserting another entry evicts the older one past the budget
    b2 = a.alloc(2)
    pc.insert(list(range(50, 58)), b2)    # 2 blocks
    assert pc.held_blocks <= 3
    a.release(b2)


def test_prefix_cache_pin_blocks_eviction():
    """Regression (ISSUE 2 satellite): evict_for_space racing a lookup.
    An admission's lookup returns an entry; before it retains the blocks,
    a concurrent admission running dry calls evict_for_space — which used
    to evict the entry and release its blocks, handing the first
    admission freed (possibly re-allocated) block ids. The lookup pin
    must make the entry untouchable until the blocks are retained."""
    a = BlockAllocator(8, 4)
    pc = PrefixCache(a, max_blocks=4)
    blocks = a.alloc(3)
    prompt = list(range(12))
    pc.insert(prompt, blocks)
    a.release(blocks)                     # only the cache holds them now
    assert a.used_count == 3

    # admission A: lookup returns the (pinned) entry
    entry = pc.lookup(prompt + [1])
    assert entry is not None and entry.pins == 1

    # admission B, interleaved: allocator is short — try to evict
    pc.evict_for_space(8)                 # wants more than exists
    assert pc._entries, "pinned entry was evicted out from under a lookup"
    assert a.used_count == 3              # blocks NOT released

    # A retains its shared blocks and drops the pin — now eviction may run
    a.retain(entry.blocks)
    pc.release_pin(entry)
    pc.evict_for_space(8)
    assert not pc._entries                # unpinned → evictable
    assert a.used_count == 3              # A's retain keeps them alive
    a.release(entry.blocks)
    assert a.used_count == 0


# ---------------------------------------------------------------------------
# engine behavior
# ---------------------------------------------------------------------------

def test_paged_matches_dense_greedy(tiny, check_tracer_leaks):
    cfg, params = tiny
    dense = InferenceEngine(params, cfg, EngineConfig(
        max_batch=2, max_seq_len=256, prefill_buckets=(32, 64),
        decode_steps=(1, 4)))
    paged = _engine(tiny, prefix_cache_blocks=4)

    async def run(engine):
        await engine.start()
        a = await engine.generate([3, 1, 4, 1, 5, 9, 2, 6],
                                  max_new_tokens=8)
        b = await engine.generate(list(range(2, 40)), max_new_tokens=6)
        await engine.stop()
        return a, b

    assert _run(run(dense)) == _run(run(paged))


def test_long_prompt_without_full_length_bucket(tiny):
    """A prompt LONGER than every prefill bucket must serve via chunked
    prefill — the dense engine rejects it, the paged one chunks it."""
    cfg, params = tiny
    prompt = [(i * 7) % 250 + 1 for i in range(150)]   # > max bucket 64

    dense = InferenceEngine(params, cfg, EngineConfig(
        max_batch=2, max_seq_len=256, prefill_buckets=(32, 64),
        decode_steps=(1, 4)))
    with pytest.raises(ValueError):
        _run(dense.generate(prompt, max_new_tokens=4))

    paged = _engine(tiny)

    async def run():
        await paged.start()
        out = await paged.generate(prompt, max_new_tokens=6)
        await paged.stop()
        return out

    out = _run(run())
    assert len(out) == 6
    # correctness oracle: the full-context forward's argmax continuation
    from tpu9.models.transformer import decoder_forward
    toks = jnp.asarray([prompt], jnp.int32)
    logits = decoder_forward(params, toks, cfg)
    assert out[0] == int(jnp.argmax(logits[0, len(prompt) - 1]))


def test_kv_memory_scales_with_live_tokens(tiny):
    """The VERDICT 'Done' criterion: allocated blocks track live tokens,
    not max_batch × max_seq."""
    paged = _engine(tiny, kv_pool_blocks=16)
    base = paged.allocator.used_count          # trash block only
    assert base == 1

    async def run():
        await paged.start()
        gen = await paged.generate(list(range(1, 33)),  # 32 = 1 block
                                   max_new_tokens=4)
        # DURING decode the slot held ceil((32+4+~k)/32) ≈ 2 blocks —
        # far below the dense equivalent (256/32 = 8 per slot)
        await paged.stop()
        return gen

    _run(run())
    # after retirement everything is back (no prefix cache configured)
    assert paged.allocator.used_count == base
    assert paged.allocator.reserved == 0


def test_admission_queues_when_pool_full(tiny):
    """Pool smaller than two worst-case requests: the second must wait in
    _wait_room (not crash mid-decode), then complete after the first
    retires."""
    paged = _engine(tiny, kv_pool_blocks=3, max_batch=2)

    async def run():
        await paged.start()
        a, b = await asyncio.gather(
            paged.generate(list(range(1, 30)), max_new_tokens=16),
            paged.generate(list(range(40, 70)), max_new_tokens=16))
        await paged.stop()
        return a, b

    a, b = _run(run())
    assert len(a) == 16 and len(b) == 16


def test_oversized_request_fails_loudly(tiny):
    paged = _engine(tiny, kv_pool_blocks=2)

    async def run():
        await paged.start()
        try:
            with pytest.raises(ValueError, match="KV pool capacity"):
                await asyncio.wait_for(
                    paged.generate(list(range(1, 100)),
                                   max_new_tokens=100), 30)
        finally:
            await paged.stop()

    _run(run())


def test_prefix_reuse_hits_and_is_correct(tiny):
    """Second request sharing a 128-token prefix must reuse cached blocks
    (hit recorded, fewer chunk prefills) and produce the same output as a
    cold engine."""
    prefix = [(i * 5) % 200 + 1 for i in range(128)]
    tail_a = [7, 7, 7]
    tail_b = [9, 9, 9]

    cold = _engine(tiny, prefix_cache_blocks=0)
    warm = _engine(tiny, prefix_cache_blocks=8)

    async def run(engine):
        await engine.start()
        a = await engine.generate(prefix + tail_a, max_new_tokens=5)
        b = await engine.generate(prefix + tail_b, max_new_tokens=5)
        await engine.stop()
        return a, b

    cold_out = _run(run(cold))
    warm_out = _run(run(warm))
    assert cold_out == warm_out
    st = warm.prefix_cache.stats()
    assert st["hits"] >= 1
    assert st["tokens_reused"] >= 96      # ≥ 3 full blocks of the prefix
    # each admission hashed its prompt once (ISSUE 58): the lookup's walk
    # over the four whole pages is the insert's too, and nothing hashes
    # behind the engine's counter; a cache that is off hashes nothing
    assert warm.stats()["prefix_tokens_hashed"] == st["tokens_hashed"] \
        == 2 * 128 <= warm.stats()["admit_tokens"] + st["tokens_reused"]
    assert cold.stats()["prefix_tokens_hashed"] == 0


def test_prefix_reuse_is_faster(tiny):
    """The measured warm-prefix latency win the VERDICT asks for: admission
    with a cached 192-token prefix must beat cold admission (it skips
    most chunk-prefill compute)."""
    import time
    prefix = [(i * 11) % 199 + 1 for i in range(192)]
    warm = _engine(tiny, prefix_cache_blocks=8, max_seq_len=256)

    async def run():
        await warm.start()
        t0 = time.perf_counter()
        await warm.generate(prefix + [5], max_new_tokens=2)
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        await warm.generate(prefix + [8], max_new_tokens=2)
        warm_s = time.perf_counter() - t0
        await warm.stop()
        return cold_s, warm_s

    cold_s, warm_s = _run(run())
    assert warm.prefix_cache.stats()["hits"] >= 1
    # compile costs are shared (same graphs), so the warm pass should
    # clearly win; generous factor keeps CI noise out
    assert warm_s < cold_s, (cold_s, warm_s)


def test_chunk_smaller_than_block_rejected(tiny):
    """Review regression: prefill_chunk < kv_block_size would make the
    splice a silent no-op (nb == 0) and decode against zero prompt KV."""
    cfg, params = tiny
    with pytest.raises(ValueError, match="multiple of"):
        InferenceEngine(params, cfg, EngineConfig(
            max_batch=2, max_seq_len=512, kv_block_size=256,
            prefill_chunk=128))


@pytest.mark.slow
def test_load_engine_defaults_are_consistent(tiny):
    """load_engine's auto block/chunk choice must always produce a valid
    paged config — including the quick-bench shape that originally hit
    the no-op-splice bug (buckets (32, 64) with block 256)."""
    from tpu9.serving.presets import load_engine

    eng = load_engine("llama-tiny", max_batch=2, max_seq_len=256,
                      prefill_buckets=(32, 64), decode_steps=(1, 4))
    assert eng.paged
    assert eng._chunk % eng.ecfg.kv_block_size == 0

    dense = load_engine("llama-tiny", max_batch=2, max_seq_len=256,
                        prefill_buckets=(32, 64), decode_steps=(1, 4),
                        paged=False)

    assert _generate(eng, range(3, 45), 6) == _generate(dense,
                                                        range(3, 45), 6)


def test_near_full_cache_prompt_does_not_overflow_table(tiny):
    """Review regression: a prompt near max_seq_len once made the decode
    window demand more blocks than the table width (ValueError in
    _push_table → dead serve loop). The engine must serve it and stop at
    the cache edge."""
    paged = _engine(tiny, max_seq_len=128, kv_pool_blocks=8,
                    decode_steps=(1, 4))
    prompt = [(i * 3) % 250 + 1 for i in range(120)]   # 120 of 128
    out = _generate(paged, prompt, 64)
    # the cache caps generation: 120 + len(out) <= 128
    assert 1 <= len(out) <= 8


@pytest.mark.slow
def test_paged_matches_dense_under_tp8_sharding():
    """Config #4's serving shape: the paged engine must produce identical
    greedy outputs to the dense engine when params are tensor-parallel
    sharded over the 8-device mesh (block pool + tables ride XLA's
    sharding propagation)."""
    from tpu9.models.llama import llama_config
    from tpu9.parallel import (decoder_param_specs, mesh_for_spec,
                               shard_params)
    from tpu9.types import parse_tpu_spec

    cfg = llama_config(vocab_size=256, dim=128, n_layers=2, n_heads=8,
                       n_kv_heads=8, head_dim=16, hidden_dim=256,
                       max_seq_len=128)
    mesh = mesh_for_spec(parse_tpu_spec("v5e-8"))
    assert mesh.devices.size == 8
    dense_params = init_decoder(jax.random.PRNGKey(0), cfg)
    params = shard_params(dense_params, mesh,
                          decoder_param_specs(dense_params))

    def run(paged: bool):
        eng = InferenceEngine(params, cfg, EngineConfig(
            max_batch=2, max_seq_len=128, prefill_buckets=(16, 64),
            decode_steps=(1, 4),
            kv_block_size=16 if paged else 0,
            kv_pool_blocks=20 if paged else 0,
            prefill_chunk=16 if paged else 0))
        return _generate(eng, range(3, 40), 6)

    assert run(False) == run(True)


@pytest.mark.slow
def test_unaligned_prefix_hit_does_not_corrupt_kv(tiny):
    """Advisor r04 (medium): a prefix-cache hit at p with p % prefill_chunk
    != 0 put the final chunk window past max_seq_len; dynamic_update_slice
    then CLAMPS the write start backwards, silently overwriting valid
    prefix KV. Block 16 / chunk 32 makes cached prefixes land on 16-token
    boundaries; the warm engine must still match the cold one exactly."""
    prompt_a = [(i * 13) % 251 + 1 for i in range(50)]    # caches 48 tokens
    prompt_b = prompt_a[:48] + [(i * 7) % 251 + 1 for i in range(72)]  # 120

    def make(prefix_blocks):
        return _engine(tiny, max_seq_len=128, kv_block_size=16,
                       prefill_chunk=32, kv_pool_blocks=24,
                       prefix_cache_blocks=prefix_blocks)

    async def run(engine):
        await engine.start()
        await engine.generate(prompt_a, max_new_tokens=2)
        out = await engine.generate(prompt_b, max_new_tokens=6)
        await engine.stop()
        return out

    cold = _run(run(make(0)))
    warm_engine = make(4)
    warm = _run(run(warm_engine))
    assert warm_engine.prefix_cache.stats()["hits"] >= 1
    assert warm == cold


@pytest.mark.parametrize("max_seq_len,n,recomputed,chunks", [
    (256, 76, 0, 1),       # room: resume at the hit's own page, 28 tokens
    (128, 120, 16, 3),     # 48 + 3 x 32 passes 128: round down to 32
])
def test_a_hit_resumes_at_its_own_page_where_the_scratch_has_room(
        tiny, max_seq_len, n, recomputed, chunks):
    """ISSUE 54; a fast twin of the slow test above. Block 16 / chunk 32: a
    hit of 48 tokens is no chunk multiple. Where the admission's last chunk
    window stays inside the scratch the suffix starts at token 48 — one
    chunk fewer than from 32 — and nothing cached is computed again; where
    that window would pass ``max_seq_len`` the hit rounds down, as it always
    did. Either way the tokens are the cold engine's."""
    prompt_a = [(i * 13) % 251 + 1 for i in range(50)]    # caches 48 tokens
    prompt_b = prompt_a[:48] + [(i * 7) % 251 + 1 for i in range(n - 48)]

    def make(prefix_blocks):
        return _engine(tiny, max_seq_len=max_seq_len, kv_block_size=16,
                       prefill_chunk=32, prefill_buckets=(32,),
                       kv_pool_blocks=24, prefix_cache_blocks=prefix_blocks)

    async def run(engine):
        await engine.start()
        await engine.generate(prompt_a, max_new_tokens=2)
        before = engine.stats()["admit_chunks"]
        out = await engine.generate(prompt_b, max_new_tokens=6)
        await engine.stop()
        return out, engine.stats()["admit_chunks"] - before

    cold, _ = _run(run(make(0)))
    warm_engine = make(4)
    warm, warm_chunks = _run(run(warm_engine))
    stats = warm_engine.stats()
    assert stats["prefix_cache"]["hits"] == 1
    assert stats["prefix_rows_recomputed"] == recomputed
    # from token 32 the suffix is 2 chunks (44 tokens) and 3 (88)
    assert warm_chunks == chunks == -(-(n - 32) // 32) - (recomputed == 0)
    assert warm == cold


def test_max_seq_len_not_chunk_multiple_rejected(tiny):
    """Advisor r04 (medium): max_seq_len % prefill_chunk != 0 lets the
    final chunk of even an UNCACHED long prompt clamp past the cache end —
    the config must be rejected at construction, not corrupt silently."""
    cfg, params = tiny
    with pytest.raises(ValueError, match="max_seq_len"):
        InferenceEngine(params, cfg, EngineConfig(
            max_batch=2, max_seq_len=192, kv_block_size=64,
            prefill_chunk=128))


@pytest.mark.slow
def test_fused_admission_dispatch_count(tiny):
    """VERDICT r04 #6 'Done': a 2048-token prompt admits in a handful of
    fused dispatches (16 chunks / group 4 = 4 forwards of 512 positions),
    not 32 chunk+splice calls — and zero host syncs inside admission (the
    loop's single firsts-sync is the only one)."""
    cfg, params = tiny
    cfg = replace(cfg, max_seq_len=2048)    # the positions: no parameter
    paged = InferenceEngine(params, cfg, EngineConfig(
        max_batch=2, max_seq_len=2048, prefill_buckets=(128,),
        decode_steps=(1, 4), kv_block_size=128, kv_pool_blocks=40,
        prefill_chunk=128, admit_group_chunks=4))
    prompt = [(i * 7) % 250 + 1 for i in range(2048 - 8)]
    out = _generate(paged, prompt, 4)
    assert len(out) == 4
    st = paged.stats()
    # 2040 tokens / 128 = 16 chunks → 4 fused groups
    assert st["admit_dispatches"] == 4, st
    assert st["admit_chunks"] == st["admit_chunks_grouped"] == 16, st

    # correctness oracle: full-context forward argmax
    from tpu9.models.transformer import decoder_forward
    logits = decoder_forward(params, jnp.asarray([prompt], jnp.int32), cfg)
    assert out[0] == int(jnp.argmax(logits[0, len(prompt) - 1]))


def test_decode_interleaves_with_long_admission(tiny):
    """While a long prompt admits, the already-running stream must keep
    producing tokens (interleaved decode windows), and outputs must be
    identical to an engine that never interleaves."""
    cfg, params = tiny

    def build(group):
        return InferenceEngine(params, cfg, EngineConfig(
            max_batch=2, max_seq_len=512, prefill_buckets=(32,),
            decode_steps=(1, 4), kv_block_size=32, kv_pool_blocks=40,
            prefill_chunk=32, admit_group_chunks=group))

    long_prompt = [(i * 11) % 250 + 1 for i in range(480)]

    async def run(engine):
        await engine.start()
        a_task = asyncio.create_task(
            engine.generate([5, 6, 7], max_new_tokens=40))
        await asyncio.sleep(0.05)        # a is decoding
        b = await engine.generate(long_prompt, max_new_tokens=4)
        a = await a_task
        await engine.stop()
        return a, b

    interleaved = build(4)
    out_i = _run(run(interleaved))
    out_serial = _run(run(build(1)))
    assert out_i == out_serial
    assert interleaved.stats()["admit_interleaved_windows"] >= 1, \
        interleaved.stats()
