from dataclasses import replace

import jax
import jax.numpy as jnp

from tpu9.models import init_decoder
from tpu9.models.llama import LLAMA_PRESETS
from tpu9.serving import EngineConfig, InferenceEngine

TINY = replace(LLAMA_PRESETS["llama-tiny"], dtype=jnp.float32)


def make_engine(max_batch=2, max_seq_len=128):
    params = init_decoder(jax.random.PRNGKey(0), TINY)
    ecfg = EngineConfig(max_batch=max_batch, max_seq_len=max_seq_len,
                        prefill_buckets=(16, 64), temperature=0.0)
    return InferenceEngine(params, TINY, ecfg)


async def test_single_generate_deterministic():
    eng = make_engine()
    await eng.start()
    try:
        out1 = await eng.generate([5, 3, 9], max_new_tokens=8)
        out2 = await eng.generate([5, 3, 9], max_new_tokens=8)
        assert out1 == out2
        assert len(out1) == 8
        assert all(0 <= t < TINY.vocab_size for t in out1)
    finally:
        await eng.stop()


async def test_concurrent_matches_sequential():
    import asyncio
    eng = make_engine(max_batch=4)
    await eng.start()
    try:
        prompts = [[1, 2, 3], [9, 8, 7, 6], [42]]
        seq_results = []
        for p in prompts:
            seq_results.append(await eng.generate(p, max_new_tokens=6))
        # now fire them concurrently — continuous batching must not change
        # greedy results
        conc = await asyncio.gather(
            *[eng.generate(p, max_new_tokens=6) for p in prompts])
        assert list(conc) == seq_results
    finally:
        await eng.stop()


async def test_streaming():
    eng = make_engine()
    await eng.start()
    try:
        req = await eng.generate([4, 4, 4], max_new_tokens=5, stream=True)
        toks = []
        while True:
            t = await req.queue.get()
            if t is None:
                break
            toks.append(t)
        assert len(toks) == 5
        assert toks == req.generated
    finally:
        await eng.stop()


async def test_stats_and_pressure():
    import asyncio
    eng = make_engine(max_batch=4)
    await eng.start()
    try:
        await eng.generate([1, 2], max_new_tokens=4)
        s = eng.stats()
        assert s["tokens_generated"] >= 3
        assert 0.0 <= s["token_pressure"] <= 1.0
        assert s["active_streams"] == 0
        # served proof: the engine's own counter accounts for every token
        # its callers received (a request's first token is sampled by its
        # prefill and is not in tokens_generated: counted once a request)
        outs = await asyncio.gather(*[
            eng.generate([3 + i, 5, 7], max_new_tokens=6) for i in range(6)])
        received = sum(len(o) for o in outs)
        assert received == 36
        counted = eng.stats()["tokens_generated"] - s["tokens_generated"]
        assert counted + len(outs) >= received, (counted, received)
    finally:
        await eng.stop()


async def test_moe_engine_generates():
    """The continuous-batching engine serves the sparse-MoE (mixtral)
    family through the same decode path as dense models."""
    from tpu9.models.mixtral import MIXTRAL_PRESETS

    cfg = replace(MIXTRAL_PRESETS["mixtral-tiny"], dtype=jnp.float32)
    params = init_decoder(jax.random.PRNGKey(0), cfg)
    ecfg = EngineConfig(max_batch=2, max_seq_len=128,
                        prefill_buckets=(16, 64), temperature=0.0)
    engine = InferenceEngine(params, cfg, ecfg)
    await engine.start()
    try:
        out = await engine.generate([1, 2, 3, 4], max_new_tokens=8)
        assert len(out) == 8
        # determinism at temperature 0
        out2 = await engine.generate([1, 2, 3, 4], max_new_tokens=8)
        assert out == out2
    finally:
        await engine.stop()


async def test_bucket_wider_than_cache_is_clamped():
    """Serving review (high): default buckets (128,512,2048) with a
    smaller max_seq_len picked a bucket wider than the cache — the splice
    became a trace-time error that killed the serve loop."""
    import asyncio

    params = init_decoder(jax.random.PRNGKey(0), TINY)
    eng = InferenceEngine(params, TINY, EngineConfig(
        max_batch=2, max_seq_len=64, prefill_buckets=(16, 128),
        temperature=0.0))
    await eng.start()
    try:
        out = await asyncio.wait_for(
            eng.generate(list(range(2, 42)), max_new_tokens=4), 60)
        assert len(out) == 4
    finally:
        await eng.stop()


async def test_dead_engine_fails_fast_not_hangs():
    """Serving review (high): after the serve loop dies, generate() must
    raise immediately (and /health must see engine_dead) — not enqueue
    into a black hole forever."""
    import asyncio

    eng = make_engine()
    await eng.start()
    try:
        async def boom(req, slot):
            raise RuntimeError("injected engine failure")

        eng._admit = boom
        # infrastructure failures surface as RuntimeError (ISSUE 15): the
        # runner maps them to 500 and the gateway failover retries them —
        # ValueError stays reserved for request-shape problems (400)
        with __import__("pytest").raises(RuntimeError,
                                         match="engine failure"):
            await asyncio.wait_for(eng.generate([1, 2, 3]), 30)
        assert eng.stats()["engine_dead"] is True
        with __import__("pytest").raises(RuntimeError, match="dead"):
            await eng.generate([1, 2, 3])
    finally:
        await eng.stop()


async def test_stop_releases_pending_callers():
    """Serving review (high): stop() must not strand callers awaiting
    queued requests."""
    import asyncio

    eng = make_engine(max_batch=1)
    await eng.start()
    a = asyncio.create_task(eng.generate([1, 2, 3], max_new_tokens=64))
    b = asyncio.create_task(eng.generate([4, 5, 6], max_new_tokens=64))
    await asyncio.sleep(0.2)
    await eng.stop()
    for t in (a, b):
        with __import__("pytest").raises((ValueError, RuntimeError)):
            await asyncio.wait_for(t, 10)


async def test_cancel_request_frees_slot():
    """Serving review (high): a client abandoning a stream must free the
    slot (bounded overshoot), not decode the full budget into a dead
    queue."""
    import asyncio

    eng = make_engine(max_batch=1)
    await eng.start()
    try:
        req = await eng.generate([1, 2, 3], max_new_tokens=10_000,
                                 stream=True)
        await req.queue.get()              # stream is producing
        eng.cancel_request(req)
        await asyncio.wait_for(req.done.wait(), 30)
        # the slot must come free for new work well before 10k tokens
        out = await asyncio.wait_for(
            eng.generate([7, 8, 9], max_new_tokens=4), 60)
        assert len(out) == 4
        assert len(req.generated) < 10_000
    finally:
        await eng.stop()


async def test_compile_ahead_abstract_precompile_then_bind():
    """ISSUE 1 compile-ahead: every serving graph AOT-compiles from shapes
    alone (abstract params), and after bind_params the engine serves the
    SAME tokens as one built the classic way — on both cache layouts."""
    from tpu9.serving.engine import abstract_params

    params = init_decoder(jax.random.PRNGKey(0), TINY)
    for paged_kw in ({}, {"kv_block_size": 8, "prefill_chunk": 16,
                          "admit_group_chunks": 2}):
        ecfg = EngineConfig(max_batch=2, max_seq_len=128,
                            prefill_buckets=(16, 64), decode_steps=(1, 4),
                            temperature=0.0, **paged_kw)
        ahead = InferenceEngine(abstract_params(params), TINY, ecfg)
        timings = ahead.precompile()
        assert timings, "precompile compiled nothing"
        ahead.bind_params(params)
        ahead.warmup()

        classic = InferenceEngine(params, TINY, ecfg)
        await ahead.start()
        await classic.start()
        try:
            want = await classic.generate([5, 3, 9], max_new_tokens=6)
            got = await ahead.generate([5, 3, 9], max_new_tokens=6)
            assert got == want, (got, want, paged_kw)
        finally:
            await ahead.stop()
            await classic.stop()


async def test_load_engine_compile_ahead_overlaps_weight_build():
    """presets.load_engine(compile_ahead=True): the engine comes back
    bound, precompiled (timings recorded), and servable — and the bring-up
    emits its restore.load/compile_ahead/bind span tree + decomposition
    (ISSUE 13)."""
    from tpu9.observability import coldstart as cs
    from tpu9.observability.trace import tracer
    from tpu9.serving.presets import load_engine

    with tracer.span("runner.bringup") as root:
        eng = load_engine("llama-tiny", max_batch=2, max_seq_len=128,
                          prefill_buckets=(16, 64), decode_steps=(1, 4),
                          compile_ahead=True)
    assert eng.compile_ahead_timings
    # bring-up decomposition: every phase recorded, overlap measured, and
    # the flat coldstart_* scalars ride stats() for the heartbeat
    for key in ("load_s", "compile_ahead_s", "bind_s",
                "compile_overlap_s"):
        assert key in eng.bringup, eng.bringup
    assert eng.bringup["compile_overlap_s"] <= \
        eng.bringup["compile_ahead_s"] + 1e-6
    assert eng.stats()["coldstart_load_s"] == eng.bringup["load_s"]
    # one gapless tree under the bring-up root, wall-anchor containment
    spans = tracer.export(trace_id=root.trace_id)
    names = {sp["name"] for sp in spans}
    assert {cs.SPAN_LOAD, cs.SPAN_COMPILE_AHEAD, cs.SPAN_BIND} <= names
    rootd = [sp for sp in spans if sp["name"] == "runner.bringup"][0]
    for sp in spans:
        if sp["name"] in (cs.SPAN_LOAD, cs.SPAN_COMPILE_AHEAD,
                          cs.SPAN_BIND):
            assert sp["parentSpanId"] == rootd["spanId"]
            assert sp["startTimeUnixNano"] >= \
                rootd["startTimeUnixNano"] - 50e6
            assert sp["endTimeUnixNano"] <= rootd["endTimeUnixNano"] + 50e6
    traced = cs.decompose_spans(spans)
    assert cs.agreement(traced["compile_ahead_s"],
                        eng.bringup["compile_ahead_s"]) < 0.10
    eng.warmup()
    await eng.start()
    try:
        out = await eng.generate([1, 2, 3], max_new_tokens=4)
        assert len(out) == 4
    finally:
        await eng.stop()


# -- request deadlines (ISSUE 15) ---------------------------------------------

async def test_non_positive_budget_raises_before_enqueue():
    import pytest
    eng = make_engine()
    await eng.start()
    try:
        with pytest.raises(TimeoutError, match="deadline_exceeded"):
            await eng.generate([1, 2, 3], max_new_tokens=4, budget_s=0.0)
    finally:
        await eng.stop()


async def test_expired_request_is_never_prefilled():
    """A request whose deadline passed while queued must be answered
    WITHOUT a prefill: zero tokens, deadline error, counter bumped."""
    import asyncio
    import time as _time
    eng = make_engine()
    # enqueue BEFORE the loop starts, then expire the deadline: the
    # loop's first admission pass must reject it at the door
    req = await eng.generate([5, 3, 9], max_new_tokens=8, stream=True,
                             budget_s=60.0)
    req.deadline_mono = _time.monotonic() - 1.0
    await eng.start()
    try:
        await asyncio.wait_for(req.done.wait(), 30)
        assert req.error.startswith("deadline_exceeded")
        assert "before prefill" in req.error
        assert req.generated == []
        assert eng.stats()["deadline_expired"] == 1
        # the stream queue is released (None sentinel), not stranded
        assert await asyncio.wait_for(req.queue.get(), 5) is None
    finally:
        await eng.stop()


async def test_deadline_mid_decode_retires_slot_and_frees_kv():
    """Deadline passing mid-generation retires the slot at the next
    window boundary: partial tokens delivered, KV blocks back in the
    pool immediately — not after the remaining budget decodes."""
    import asyncio
    import time as _time
    from tpu9.serving import EngineConfig, InferenceEngine
    params = init_decoder(jax.random.PRNGKey(0), TINY)
    eng = InferenceEngine(params, TINY, EngineConfig(
        max_batch=2, max_seq_len=256, prefill_buckets=(16, 64),
        kv_block_size=16))
    base_used = eng.allocator.used_count       # the permanent trash block
    await eng.start()
    try:
        req = await eng.generate([5, 3, 9], max_new_tokens=200,
                                 stream=True, budget_s=120.0)
        got = []
        got.append(await asyncio.wait_for(req.queue.get(), 30))
        # a few tokens in: expire the deadline under the running slot
        req.deadline_mono = _time.monotonic() - 0.001
        while True:
            t = await asyncio.wait_for(req.queue.get(), 30)
            if t is None:
                break
            got.append(t)
        assert req.error.startswith("deadline_exceeded")
        assert "mid-decode" in req.error
        assert 0 < len(got) < 200
        assert eng.stats()["deadline_expired"] == 1
        # slot + KV fully released (no prefix cache configured)
        assert eng.allocator.used_count == base_used
        assert eng.allocator.reserved == 0
        assert eng.stats()["active_streams"] == 0
    finally:
        await eng.stop()


async def test_generous_budget_changes_nothing():
    eng = make_engine()
    await eng.start()
    try:
        a = await eng.generate([5, 3, 9], max_new_tokens=8)
        b = await eng.generate([5, 3, 9], max_new_tokens=8, budget_s=300.0)
        assert a == b
        assert eng.stats()["deadline_expired"] == 0
    finally:
        await eng.stop()
