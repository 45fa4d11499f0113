"""Latent attention in every layer through the serving path (ISSUE 52):
chunked prefill + paged decode and a PREFIX HIT over latent pages held to the
plain reference ``benchmark/reference/kimi.py``, with the expanded prefill and
with the blocked one; the record of the served routing across a hit; the
counters; that a pattern without KDA layers has no lane program; and every
engine refusal that stays, by its message. The layers themselves:
``test_kimi_layers.py``, whose tiny configuration this file takes."""

import asyncio
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness
from test_kimi_layers import SMALL, TOL, _model
from tpu9.models import init_decoder, kvstate
from tpu9.models import hybrid
from tpu9.ops import latent_attention as la
from tpu9.serving.engine import EngineConfig, InferenceEngine

C, S, G, BS = 16, 256, 2, 16


@pytest.fixture(scope="module")
def params():
    return init_decoder(jax.random.PRNGKey(52), SMALL)


def _ecfg(**kw):
    base = dict(max_batch=2, max_seq_len=S, prefill_buckets=(C,),
                decode_steps=(1, 8), kv_block_size=BS, kv_pool_blocks=60,
                prefill_chunk=C, prefix_cache_blocks=64,
                admit_group_chunks=G)
    base.update(kw)
    return EngineConfig(**base)


def _serve(engine, probes, new):
    async def go():
        await engine.start()
        for p in probes:
            p["tokens"] = await engine.generate(list(p["prompt"]),
                                                max_new_tokens=new)
        await engine.stop()
    asyncio.run(go())


def _probes(seed=2):
    """The benchmark's probe mix at tiny sizes: a short one, one of several
    chunks (two groups, a chunk and a tail), and a pair that shares a
    prefix of more than two pages."""
    rng = np.random.default_rng(seed)

    def toks(n):
        return rng.integers(3, 250, n).tolist()

    # 37 tokens: both prompts' whole pages (2 of 45 tokens) lie inside it,
    # and an entry is a prompt's whole pages
    shared = toks(2 * C + C // 3)
    return [{"name": "short", "prompt": toks(12)},
            {"name": "multi_chunk", "prompt": toks(5 * C + C // 3)},
            {"name": "prefix_a", "prompt": shared + toks(8)},
            {"name": "prefix_b", "prompt": shared + toks(8)}]


@pytest.fixture(scope="module", params=["expanded", "blocked"])
def served(request, params):
    """The probes, one at a time, through an engine with a prefix cache;
    once with every scratch row expanded (a short scratch) and once with the
    blocked prefill (what a long scratch takes)."""
    rows, block = la.BLOCKED_MIN_ROWS, la.PREFILL_BLOCK_K
    if request.param == "blocked":
        la.BLOCKED_MIN_ROWS, la.PREFILL_BLOCK_K = 0, 64
    try:
        engine = InferenceEngine(params, SMALL, _ecfg())
        probes = _probes()
        _serve(engine, probes, 12)
        stats = engine.stats()
        paths = engine._attention_paths()
    finally:
        la.BLOCKED_MIN_ROWS, la.PREFILL_BLOCK_K = rows, block
    return engine, probes, stats, paths, request.param


def test_engine_tokens_are_within_the_margin_of_the_reference(params,
                                                              served):
    _, probes, _, paths, form = served
    out = correctness.probe_margins(params, _model(), probes, "kimi")
    assert out["tokens_checked"] == 4 * 12
    assert out["worst_margin"] < TOL
    for control in ("no_mscale", "plain_rope", "no_q_norm"):
        bare = correctness.probe_margins(
            params, _model(control=(control,)), probes, "kimi")
        assert bare["worst_margin"] > 100 * TOL, control
    if form == "blocked":
        assert paths["prefill"] == ("latent attention, blocked over keys: "
                                    "xla: no TPU backend")
    else:
        assert paths["prefill"] == "xla: latent attention, expanded"
    assert "kda" not in paths["decode"] + paths["prefill"]


def test_the_second_request_is_served_over_the_firsts_pages(params, served):
    """``prefix_b`` shares two whole pages with ``prefix_a``: its admission
    gathers them into the scratch and prefills the suffix alone, and its
    tokens are those of the same request on an engine that has served
    nothing (no hit)."""
    _, probes, stats, _, _ = served
    cache = stats["prefix_cache"]
    assert cache["hits"] == 1 and cache["misses"] == 3
    assert stats["prefix_rows_reused"] == 2 * C
    assert stats["prompt_rows_admitted"] == sum(len(p["prompt"])
                                                for p in probes)
    fresh = InferenceEngine(params, SMALL, _ecfg(prefix_cache_blocks=0))
    again = [dict(probes[-1], tokens=None)]
    _serve(fresh, again, 12)
    assert again[0]["tokens"] == probes[-1]["tokens"]
    assert fresh.stats()["prefix_rows_reused"] == 0


def test_the_counters_mirror_the_lengths(served):
    _, probes, stats, _, _ = served
    assert stats["latent_decode_steps"] == stats["decode_steps"] > 0
    # a probe at a time: one live lane a step, attending its whole length.
    # Step i of a request of n prompt tokens attends n + i + 1 rows; windows
    # of 8 may run past the request's end (their tokens are discarded)
    least = sum(sum(len(p["prompt"]) + i + 1 for i in range(11))
                for p in probes)
    assert stats["latent_rows_attended"] >= least
    assert stats["latent_rows_attended"] <= least + 4 * 8 * S
    # chunks: 12 -> 1; 85 -> 3 groups of 2; 45 -> 1 group + 1 chunk; 45
    # behind 32 cached rows -> 1 chunk
    assert stats["admit_chunks"] == 1 + 6 + 3 + 1
    widths = [(0, C), (0, 2 * C), (2 * C, 2 * C), (4 * C, 2 * C),
              (0, 2 * C), (2 * C, C), (2 * C, C)]
    assert stats["prefill_rows_attended"] == sum(o + w for o, w in widths)
    assert stats["prefill_pairs_attended"] == sum(
        w * o + w * (w + 1) // 2 for o, w in widths)
    assert stats["graph_compiles_post_warmup"] == 0
    assert stats["kv_layers"] == 3
    assert "state_bytes" in stats and stats["state_bytes"] == 0


def test_the_served_routing_of_a_hit_is_whole(params, served, monkeypatch):
    """``prefix_b`` routed only its suffix: its record takes the cached
    positions' choices from ``prefix_a``'s, and the reference takes every
    served choice at a tie of 1e-5 (float32 on both sides)."""
    from benchmark.reference import served_routing
    from tpu9.serving import routed_experts
    _, probes, _, _, _ = served
    monkeypatch.setattr(served_routing, "provider", routed_experts.records)
    kept_of = {tuple(fed): picks for fed, picks in routed_experts.records()}
    reference = correctness.load_reference("kimi")
    for p in probes:
        seq = p["prompt"] + p["tokens"]
        kept = kept_of[tuple(seq[:-1])]
        assert kept.shape == (len(seq) - 1, 2, 4)
        told = []
        reference.forward(params, jnp.asarray(seq, jnp.int32),
                          _model(routing_tie=1e-5), told)
        for layer, said in enumerate(told):
            assert (np.asarray(said["served"])[:len(kept)]
                    == kept[:, layer]).all()
            assert np.asarray(said["taken"])[:len(kept)].all()
    a, b = (kept_of[tuple((p["prompt"] + p["tokens"])[:-1])]
            for p in probes[2:])
    assert (a[:2 * C] == b[:2 * C]).all()


def test_a_page_aligned_hit_resumes_at_its_page_and_its_record_is_whole(
        params, monkeypatch):
    """ISSUE 54, at the rehearsal's engine shapes (block 16, chunk 32): a
    hit of three pages is no chunk multiple. The suffix starts at token 48
    all the same — through one chunk program, and through a group and a
    chunk — no cached row is computed again, the tokens are those of an
    engine without a cache, and the record takes positions 0–47 from the
    sequence that made the pages."""
    from benchmark.reference import served_routing
    from tpu9.serving import routed_experts
    rng = np.random.default_rng(54)
    shared = rng.integers(3, 250, 3 * BS + 5).tolist()
    probes = [{"prompt": shared + rng.integers(3, 250, n).tolist()}
              for n in (8, 8, 70)]
    kw = dict(prefill_chunk=2 * BS, prefill_buckets=(2 * BS,))
    engine = InferenceEngine(params, SMALL, _ecfg(**kw))
    _serve(engine, probes, 6)
    stats = engine.stats()
    assert stats["prefix_cache"]["hits"] == 2
    assert stats["prefix_rows_reused"] == 2 * 3 * BS
    assert stats["prefix_rows_recomputed"] == 0
    # 61 tokens cold: a group of 2; 13 behind 48: a chunk; 75 behind 48: a
    # group at token 48 and a chunk at 112
    assert stats["admit_chunks"] == 2 + 1 + 3
    assert stats["admit_chunks_grouped"] == 2 + 0 + 2
    assert stats["graph_compiles_post_warmup"] == 0
    fresh = InferenceEngine(params, SMALL,
                            _ecfg(prefix_cache_blocks=0, **kw))
    again = [dict(p, tokens=None) for p in probes[1:]]
    _serve(fresh, again, 6)
    assert [p["tokens"] for p in again] == [p["tokens"] for p in probes[1:]]

    monkeypatch.setattr(served_routing, "provider", routed_experts.records)
    kept_of = {tuple(fed): picks for fed, picks in routed_experts.records()}
    reference = correctness.load_reference("kimi")
    first = kept_of[tuple((probes[0]["prompt"] + probes[0]["tokens"])[:-1])]
    for p in probes[1:]:
        seq = p["prompt"] + p["tokens"]
        kept = kept_of[tuple(seq[:-1])]
        assert kept.shape == (len(seq) - 1, 2, 4)
        assert (kept[:3 * BS] == first[:3 * BS]).all()
        told = []
        reference.forward(params, jnp.asarray(seq, jnp.int32),
                          _model(routing_tie=1e-5), told)
        for layer, said in enumerate(told):
            assert (np.asarray(said["served"])[:len(kept)]
                    == kept[:, layer]).all()
            assert np.asarray(said["taken"])[:len(kept)].all()


def test_a_record_behind_a_hit_nobody_made_is_left_out():
    from tpu9.serving import routed_experts
    before = list(routed_experts._finished)
    try:
        routed_experts._finished.clear()
        picks = np.zeros((5, 2, 4), np.int32)
        routed_experts.note([1, 2, 3, 4, 5, 6, 7], [8, 9],
                            [picks], cached=3)
        assert routed_experts.records() == []
        routed_experts._finished.clear()
        routed_experts.note([1, 2, 3, 4], [8, 9], [np.ones((5, 2, 4))])
        routed_experts.note([1, 2, 3, 4, 5, 6, 7], [8, 9],
                            [picks], cached=3)
        (_, first), (fed, whole) = routed_experts.records()
        assert len(whole) == len(fed) == 8
        assert (whole[:3] == 1).all() and (whole[3:] == 0).all()
    finally:
        routed_experts._finished.clear()
        routed_experts._finished.extend(before)


def test_a_pattern_without_kda_layers_has_no_lane_program(params):
    assert hybrid.MLA_QUERY_SCOPES == ("attn.mla.q",)
    engine = InferenceEngine(params, SMALL, _ecfg(decode_steps=(1,)))
    timings = engine.precompile()
    assert "lanesplice" not in engine.graphs.reachable_keys((C,), ())
    assert "lanesplice" not in engine.graphs.compiled
    assert not any("lane" in k for k in timings)
    assert engine._lane_state_names == ()
    maps = engine.graphs.device_scopes
    for program in ("decode_1", f"chunk_{C}", f"chunkgroup_{G}"):
        for scope in ("attn.mla.q", "attn.mla.absorb", "attn.mla.core",
                      "moe.shared"):
            assert maps[program][scope], (program, scope)
        assert "attn.kda.state" not in maps[program]
    assert sorted(engine._scratch) == ["k", "v"]


def test_feasibility_prices_the_latent_pool_and_the_scratch():
    """The published widths: 6 planes of a 576-wide bf16 row, a page of 128
    entries, a 57,344-row scratch."""
    from tpu9.serving.feasibility import kv_cache_bytes, lane_state_bytes
    from tpu9.serving.paged_kv import kv_block_bytes
    kimi = replace(SMALL, dim=7168, n_layers=6, n_heads=64, n_kv_heads=64,
                   head_dim=128, mla_latent=512, mla_nope=128, mla_rope=64,
                   mla_v=128, mla_q_latent=1536, dtype=jnp.bfloat16,
                   max_seq_len=262144)
    assert kv_block_bytes(kimi, 1) == 6 * 1152
    assert kv_block_bytes(kimi, 128) == 884736
    assert kv_cache_bytes(kimi, 1, 57344) == 57344 * 6912
    assert lane_state_bytes(kimi, 16) == 0
    assert kvstate.pool_shapes(kimi, 4865, 128)["k"][0] == \
        (6, 4865, 128, 1, 512)


@pytest.mark.parametrize("kw,needle", [
    (dict(kv_block_size=0, prefill_chunk=0), "dense cache"),
    (dict(spec_len=4), "no program attends a window over the pool's latent"),
    (dict(kv_quant="int8"), "scale planes"),
    (dict(kv_host_pool_mb=64), "no place in either format"),
])
def test_the_engine_refuses_what_is_not_built_for_latent_rows(params, kw,
                                                              needle):
    with pytest.raises(ValueError, match=needle):
        InferenceEngine(params, SMALL, _ecfg(**kw))


def test_the_engine_refuses_a_mesh_and_int8_weights(params):
    from tpu9.ops.quant import quantize_decoder
    from tpu9.serving.shard import make_policy
    with pytest.raises(ValueError, match="no head axis to shard"):
        InferenceEngine(params, SMALL, _ecfg(), policy=make_policy("tp=2"))
    from tpu9.models.transformer import DecoderConfig
    plain = DecoderConfig(vocab_size=256, dim=128, n_layers=2, n_heads=4,
                          n_kv_heads=2, head_dim=32, hidden_dim=256,
                          max_seq_len=256, dtype=jnp.float32)
    quantized = dict(params, lm_head=quantize_decoder(
        init_decoder(jax.random.PRNGKey(0), plain))["lm_head"])
    with pytest.raises(ValueError, match="int8 weights"):
        InferenceEngine(quantized, SMALL, _ecfg())


def test_the_prefix_cache_is_the_states_refusal_not_the_rows(params):
    """With KDA layers the prefix cache is refused (their state); without,
    it is built."""
    from test_hybrid_layers import SMALL as LING
    ling = init_decoder(jax.random.PRNGKey(1), LING)
    with pytest.raises(ValueError, match="snapshot"):
        InferenceEngine(ling, LING, _ecfg())
    engine = InferenceEngine(params, SMALL, _ecfg())
    assert engine.ecfg.prefix_cache_blocks == 64
    assert engine.export_prefix_kv(list(range(3, 40))) is None
    assert engine.export_request_kv("nobody") is None


@pytest.mark.parametrize("program", ["splice", "decode-write"])
def test_the_pools_rotated_keys_come_back_as_they_were_written(program):
    """ISSUE 53: a pool keeps the rotated keys two tokens a row, and no
    program sees it. Scratch rows spliced into scattered blocks (the splice
    program) or written a decode step at a time (``kvstate.write``) and
    gathered back by the gather program are the rows that went in, in token
    order, both planes; the pool's plane is the packed one."""
    from tpu9.serving.graphs import GraphFactory
    from tpu9.serving.shard.policy import SingleDevicePolicy
    ecfg = _ecfg()
    graphs = GraphFactory(SMALL, ecfg, SingleDevicePolicy(), chunk=C)
    rows = graphs.scratch_len
    scratch = {n: jax.random.normal(jax.random.PRNGKey(i), shape).astype(dt)
               for i, (n, (shape, dt)) in enumerate(
                   kvstate.dense_shapes(SMALL, 1, rows).items())}
    pool = {n: jnp.zeros(shape, dt) for n, (shape, dt)
            in kvstate.pool_shapes(SMALL, 61, BS).items()}
    assert pool["v"].shape == (SMALL.kv_layers, 61, BS // 2, 1,
                               2 * SMALL.mla_rope)
    mb = S // BS + 1
    blocks = np.random.default_rng(3).permutation(60)[:mb - 1] + 1
    table = np.zeros((1, mb), np.int32)
    table[0, :mb - 1] = blocks
    n = 3 * C + 5                 # three whole chunks and a tail of 5 tokens
    if program == "splice":
        for start in range(0, n, C):
            pool = graphs.splice_fn()(
                pool, scratch["k"], scratch["v"], start, jnp.asarray(
                    blocks[start // BS:(start + C) // BS], jnp.int32))
        n = -(-n // C) * C        # a splice moves whole blocks
    else:
        kv = dict(pool, table=jnp.asarray(table))
        for pos in range(n):
            for plane in range(SMALL.kv_layers):
                kv = kvstate.write(
                    kv, plane, scratch["k"][plane, :, pos:pos + 1, 0],
                    scratch["v"][plane, :, pos:pos + 1, 0],
                    jnp.asarray([[pos]]), True)
        pool = {name: kv[name] for name in pool}
    back = graphs.gather_fn()(pool, jnp.asarray(table[0]))
    for name in ("k", "v"):
        assert back[name].shape == scratch[name].shape
        np.testing.assert_array_equal(np.asarray(back[name][:, :, :n]),
                                      np.asarray(scratch[name][:, :, :n]))
        assert not np.asarray(back[name][:, :, n:]).any()
