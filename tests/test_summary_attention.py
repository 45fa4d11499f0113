"""Attention over window summaries (ISSUE 46): exact inside a window of
``attn_window`` positions, one summary for every ``attn_chunk`` positions of
every earlier window, a cache addressed by ENTRY and not by position — held
to the plain reference ``benchmark/reference/eva.py`` without a cache,
program by program (group, chunk, splice, decode K = 1 and 8 across two
rollovers) and through the engine; the entry arithmetic, the reservation,
and every combination the engine refuses."""

import asyncio
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness
from benchmark.reference import eva as reference
from tpu9.models import decoder_forward, init_decoder, init_kv_cache
from tpu9.models.llama import LLAMA_PRESETS
from tpu9.models.transformer import (DEVICE_SCOPES, SUMMARY_SCOPES,
                                     DecoderConfig)
from tpu9.ops.summary_attention import summarise
from tpu9.serving.engine import EngineConfig, InferenceEngine
from tpu9.serving.graphs import GraphFactory, hlo_scopes
from tpu9.serving.paged_kv import blocks_for, scratch_len
from tpu9.serving.shard.policy import SingleDevicePolicy

# the rehearsal's tiny widths: window 64, chunk 4, so a page of 16 entries
W, CH, BS = 64, 4, 16
TINY = DecoderConfig(vocab_size=320, dim=128, n_layers=2, n_heads=4,
                     n_kv_heads=4, head_dim=32, hidden_dim=256,
                     max_seq_len=512, norm_offset=1.0, rope_theta=1e5,
                     attn_window=W, attn_chunk=CH, dtype=jnp.float32)
PLAIN = replace(LLAMA_PRESETS["llama-tiny"], dtype=jnp.float32)
C, S, G = 16, 256, 2
# float32 on both sides: the order of the sums alone
TOL = 2e-4


def _model(cfg=TINY, **more):
    return dict({"num_attention_heads": cfg.n_heads,
                 "head_dim": cfg.head_dim, "rope_theta": cfg.rope_theta,
                 "rms_norm_eps": cfg.norm_eps, "window_size": cfg.attn_window,
                 "chunk_size": cfg.attn_chunk}, **more)


@pytest.fixture(scope="module")
def params():
    return init_decoder(jax.random.PRNGKey(46), TINY)


def _ref_logits(params, tokens, **more):
    return np.asarray(jax.jit(
        lambda p, x: reference.forward(p, x, _model(**more)))(
            params, jnp.asarray(tokens, jnp.int32)))


def _margin(row, token):
    return float(row.max() - row[token])


# ---------------------------------------------------------------------------
# the rule without a cache, and the summarise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t", [40, W, W + 1, 2 * W + 23, 3 * W])
def test_forward_without_a_cache_equals_the_reference(params, t):
    """Every length: inside the first window (plain causal attention), at
    its very end, one past it, and with two and three windows closed."""
    tokens = np.random.default_rng(t).integers(3, 320, t)
    with jax.default_matmul_precision("highest"):
        got = decoder_forward(params, jnp.asarray(tokens)[None], TINY)[0]
    assert np.abs(np.asarray(got) - _ref_logits(params, tokens)).max() < TOL


def test_the_reference_without_summaries_differs_only_past_the_window(
        params):
    """The control the tolerance is set against: leaving the summaries out
    moves no logit inside the first window and every one after it."""
    tokens = np.random.default_rng(0).integers(3, 320, 2 * W)
    full = _ref_logits(params, tokens)
    bare = _ref_logits(params, tokens, skip_summaries=True)
    assert np.abs(full - bare)[:W].max() == 0.0
    assert np.abs(full - bare)[W:].max(axis=-1).min() > 100 * TOL


def test_summarise_against_a_loop_oracle():
    rng = np.random.default_rng(3)
    k, v = (rng.normal(size=(W, 3, 8)).astype(np.float32) for _ in range(2))
    mu, phi = (rng.normal(size=(3, 8)).astype(np.float32) for _ in range(2))
    ks, vs = (np.asarray(x) for x in summarise(
        jnp.asarray(k), jnp.asarray(v), jnp.asarray(mu), jnp.asarray(phi),
        CH))
    assert ks.shape == vs.shape == (W // CH, 3, 8)
    for c in range(W // CH):
        for h in range(3):
            rows = slice(CH * c, CH * c + CH)
            for vec, src, got in ((mu, k, ks), (phi, v, vs)):
                score = k[rows, h] @ vec[h]
                weight = np.exp(score - score.max())
                want = (weight / weight.sum()) @ src[rows, h]
                assert np.abs(got[c, h] - want).max() < 1e-5


# ---------------------------------------------------------------------------
# where a token lives in its cache
# ---------------------------------------------------------------------------

def test_entry_coordinates():
    e = W // CH
    pos = np.arange(4 * W)
    want = e * (pos // W) + pos % W
    assert (TINY.kv_entry(pos) == want).all()
    assert (np.asarray(TINY.kv_entry(jnp.asarray(pos))) == want).all()
    assert TINY.window_entries == e
    # a window is summarised when the NEXT one opens: W tokens hold W rows
    assert [int(TINY.kv_entries(n)) for n in (0, 1, W, W + 1, 2 * W)] \
        == [0, 1, W, e + 1, e + W]
    assert [TINY.kv_entries_peak(n) for n in (1, W, W + 1, 2 * W, 2 * W + 1,
                                              4 * W)] \
        == [1, W, W, e + W, e + W, 3 * e + W]
    # the peak is what a life addresses: never under any state on the way
    for n in (W + 5, 3 * W, 3 * W + 40):
        assert TINY.kv_entries_peak(n) == max(
            int(TINY.kv_entries(m)) for m in range(n + 1))


def test_plain_attention_passes_through_as_the_identity():
    pos = jnp.arange(7)
    assert PLAIN.kv_entry(pos) is pos and PLAIN.kv_entries(pos) is pos
    assert PLAIN.kv_entries_peak(300) == 300 and PLAIN.window_entries == 0
    assert scratch_len(PLAIN, 256, 32) == 256
    assert scratch_len(TINY, S, C) == 3 * (W // CH) + W          # 112
    assert scratch_len(TINY, S, 32) == 128                       # whole chunks


@pytest.mark.parametrize("kw,needle", [
    (dict(attn_window=64, attn_chunk=0), "whole number"),
    (dict(attn_window=0, attn_chunk=4), "whole number"),
    (dict(attn_window=64, attn_chunk=5), "whole number"),
    (dict(attn_window=64, attn_chunk=4, n_experts=4), "experts"),
    (dict(attn_window=64, attn_chunk=4, loop_steps=2), "pass loop"),
])
def test_the_config_refuses_what_is_not_built(kw, needle):
    with pytest.raises(ValueError, match=needle):
        replace(PLAIN, **kw)


# ---------------------------------------------------------------------------
# program by program
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def programs(params):
    """One sequence through the serving programs. Prefill: three groups of
    two chunks (the third opens window 1: it summarises in the scratch and
    its splice carries the page of summaries), a chunk, a partial chunk —
    123 tokens. Decode: K = 1, then K = 8 over positions 124..131 (the lane
    rolls over at 128, MID-window of the program), K = 8 up to 187, K = 1 up
    to 192 (the rollover is the K = 1 program's own step), one K = 8 more.
    Returns the logits each prefill program gave and the served tokens."""
    ecfg = EngineConfig(max_batch=2, max_seq_len=S, decode_steps=(1, 8),
                        kv_block_size=BS, kv_pool_blocks=23,
                        prefill_chunk=C, admit_group_chunks=G)
    graphs = GraphFactory(TINY, ecfg, SingleDevicePolicy(), chunk=C)
    prompt = np.random.default_rng(1).integers(3, 320, 123).tolist()
    n_blocks = 24
    pool = {n: jnp.zeros((TINY.kv_layers, n_blocks, BS, TINY.n_kv_heads,
                          TINY.head_dim), jnp.float32) for n in ("k", "v")}
    scratch = init_kv_cache(TINY, 1, graphs.scratch_len)
    mb = TINY.kv_entries_peak(S) // BS + 1
    blocks = list(range(1, mb))                        # block 0 is trash

    def phys(start, n_chunks):
        """The splice operand for ``n_chunks`` from position ``start``."""
        first = TINY.kv_entry(start) // BS
        lead = blocks[first - 1] if start and start % W == 0 else 0
        return jnp.asarray(
            [lead] + blocks[first:first + n_chunks * C // BS], jnp.int32)

    got = {"prompt": prompt}
    with jax.default_matmul_precision("highest"):
        for start in (0, G * C, 2 * G * C):
            toks = jnp.asarray(prompt[start:start + G * C],
                               jnp.int32).reshape(G, C)
            pool, scratch, got[f"group{start}"] = graphs.chunk_group_fn(G)(
                params, pool, scratch, toks, start, C - 1, phys(start, G))
        for name, start, real in (("chunk", 96, C), ("partial", 112, 11)):
            row = prompt[start:start + real] + [0] * (C - real)
            got[name], scratch = graphs.chunk_fn()(
                params, jnp.asarray([row], jnp.int32), start, scratch,
                real - 1)
            pool = graphs.splice_fn()(pool, scratch["k"], scratch["v"],
                                      start, phys(start, 1))
        table = np.zeros((2, mb), np.int32)
        table[0, :len(blocks)] = blocks
        kv = dict(pool, table=jnp.asarray(table))
        n = len(prompt)
        first_tok = int(np.asarray(got["partial"]).argmax())
        last = jnp.asarray([[first_tok], [0]], jnp.int32)
        clen = jnp.asarray([n, 0], jnp.int32)
        # steps each lane may run a call: lane 1 is idle
        steps = jnp.asarray([8, 0], jnp.int32)
        key = jax.random.PRNGKey(0)
        served = [first_tok]
        for k in (1, 8) + (8,) * 7 + (1,) * 5 + (8,):
            last, kv, clen, key, toks = graphs.decode_k(k)(
                params, kv, last, clen, steps, key)
            served += np.asarray(toks)[:, 0].tolist()
        got["served"] = served
        got["cache_len"] = int(clen[0])
        got["idle_len"] = int(clen[1])
    return got


@pytest.mark.parametrize("program,position", [
    ("group0", 31), ("group32", 63), ("group64", 95), ("chunk", 111),
    ("partial", 122)])
def test_prefill_programs_give_the_reference_logits(params, programs,
                                                    program, position):
    """``group64`` and what follows read the summaries of window 0 from the
    scratch, at the entries below the open window's first."""
    ref = _ref_logits(params, programs["prompt"])
    assert np.abs(np.asarray(programs[program]) - ref[position]).max() < TOL


def test_decode_windows_follow_the_reference_across_two_rollovers(
        params, programs):
    """Every served token is the reference's choice at its position,
    teacher-forced over prompt + served tokens: the pool holds the prefill's
    summaries of window 0, the K = 8 program summarised window 1 at its
    fifth step and the K = 1 program window 2, each before the token that
    opens the next window was written over the closed one's second page."""
    prompt, served = programs["prompt"], programs["served"]
    ref = _ref_logits(params, prompt + served)
    n = len(prompt)
    margins = [_margin(ref[n - 1 + j], t) for j, t in enumerate(served)]
    assert max(margins) < TOL, (max(margins), int(np.argmax(margins)))
    assert programs["cache_len"] == n + 78 and n + 78 > 3 * W
    assert programs["idle_len"] == 0
    # the control: the same tokens held to a reference without summaries
    bare = _ref_logits(params, prompt + served, skip_summaries=True)
    assert max(_margin(bare[n - 1 + j], t)
               for j, t in enumerate(served)) > 100 * TOL


def test_the_programs_that_summarise_name_the_scope(params):
    """``kv.summarise`` is in the decode, chunk and group programs of this
    attention, holds the loop's body, and is in no plain program."""
    ecfg = EngineConfig(max_batch=2, max_seq_len=S, decode_steps=(1,),
                        kv_block_size=BS, kv_pool_blocks=23,
                        prefill_chunk=C, admit_group_chunks=G)
    assert SUMMARY_SCOPES == ("kv.summarise",)
    assert not set(SUMMARY_SCOPES) & set(DEVICE_SCOPES)
    engine = InferenceEngine(params, TINY, ecfg)
    engine.precompile()
    maps = engine.graphs.device_scopes
    for program in ("decode_1", f"chunk_{C}", f"chunkgroup_{G}"):
        assert maps[program]["kv.summarise"], program
    assert "kv.summarise" not in maps.get("splice", {})
    plain = InferenceEngine(init_decoder(jax.random.PRNGKey(0), PLAIN),
                            PLAIN, replace(ecfg, prefill_chunk=32,
                                           prefill_buckets=(32,)))
    plain.precompile()
    assert all("kv.summarise" not in m
               for m in plain.graphs.device_scopes.values())
    text = plain.graphs.compiled[("decode", 1)].as_text()
    assert not hlo_scopes(text, SUMMARY_SCOPES)


# ---------------------------------------------------------------------------
# the engine: reservation in entries, counters, refusals
# ---------------------------------------------------------------------------

def _ecfg(**kw):
    base = dict(max_batch=2, max_seq_len=S, prefill_buckets=(C,),
                decode_steps=(1, 8), kv_block_size=BS, kv_pool_blocks=20,
                prefill_chunk=C, prefix_cache_blocks=0, admit_group_chunks=G)
    base.update(kw)
    return EngineConfig(**base)


def _serve(engine, probes, new, together=False):
    async def go():
        await engine.start()
        if together:
            outs = await asyncio.gather(*(
                engine.generate(list(p["prompt"]), max_new_tokens=new)
                for p in probes))
            for p, out in zip(probes, outs):
                p["tokens"] = out
        else:
            for p in probes:
                p["tokens"] = await engine.generate(list(p["prompt"]),
                                                    max_new_tokens=new)
        await engine.stop()
    asyncio.run(go())


@pytest.fixture(scope="module")
def served(params):
    """Three probes one at a time: one that closes a window in prefill and
    two while decoding; one whose prompt is exactly a window (the first
    decode step rolls over, from the pool as prefill left it); a short one
    that crosses its first window's end while decoding."""
    engine = InferenceEngine(params, TINY, _ecfg())
    rng = np.random.default_rng(2)
    probes = [{"name": "long", "prompt": rng.integers(3, 320, 123).tolist()},
              {"name": "window", "prompt": rng.integers(3, 320, W).tolist()},
              {"name": "short", "prompt": rng.integers(3, 320, 30).tolist()}]
    peak = {"used": 0}
    alloc = engine.allocator.alloc

    def counting(n):
        out = alloc(n)
        peak["used"] = max(peak["used"], engine.allocator.used_count)
        return out

    engine.allocator.alloc = counting
    with jax.default_matmul_precision("highest"):
        _serve(engine, probes, new=80)
    return engine, probes, peak


def test_engine_tokens_are_within_the_margin_of_the_reference(params, served):
    _, probes, _ = served
    out = correctness.probe_margins(params, _model(), probes, "eva")
    assert out["tokens_checked"] == 240 and out["worst_margin"] < TOL, out
    bare = correctness.probe_margins(params, _model(skip_summaries=True),
                                     probes, "eva")
    assert bare["worst_margin"] > 100 * TOL, bare


def test_pages_never_exceed_what_the_entries_give_and_are_freed(served):
    engine, probes, peak = served
    # one request at a time: the pool never held more pages than the peak
    # entries of the longest life (its reservation) plus the trash block
    longest = max(len(p["prompt"]) + 80 + 9 for p in probes)
    assert peak["used"] <= 1 + blocks_for(
        TINY.kv_entries_peak(longest), BS)
    assert peak["used"] < 1 + blocks_for(longest, BS)     # a row a token
    assert engine.allocator.used_count == 1               # the trash block
    assert engine.allocator.reserved == 0


def test_summary_counters(served):
    engine, probes, _ = served
    st = engine.stats()
    # long: 128 and 192; window: 64 and 128; short: 64
    assert st["windows_closed_decode"] == 5
    assert st["windows_closed_prefill"] == 1              # long, at 64
    assert 0 < st["decode_summary_entries"] < st["decode_resident_entries"] \
        < st["decode_resident_tokens"]
    # pages in use never passed the reservations, which are in entries
    assert st["kv_pages_over_reservation"] == 0
    assert 0 < st["kv_pages_used_peak"] <= blocks_for(
        TINY.kv_entries_peak(max(len(p["prompt"]) + 80 + 9
                                 for p in probes)), BS)
    assert st["graph_compiles_post_warmup"] == 0 or not engine.graphs._sealed
    plain = InferenceEngine(init_decoder(jax.random.PRNGKey(0), PLAIN),
                            PLAIN, _ecfg(prefill_chunk=32,
                                         prefill_buckets=(32,)))
    assert not [k for k in plain.stats()
                if k.startswith(("windows_closed", "decode_resident",
                                 "decode_summary", "kv_pages_"))]


def test_a_full_pool_queues_and_does_not_fail(params):
    """Reserved in entries, a 200-token life asks for 6 pages of 16; the
    pool has 9, so the second request waits for the first and both are
    served. Reserved in tokens each would ask for 13 and neither fit."""
    engine = InferenceEngine(params, TINY, _ecfg(kv_pool_blocks=9))
    rng = np.random.default_rng(5)
    probes = [{"prompt": rng.integers(3, 320, 150).tolist()}
              for _ in range(2)]
    life = 150 + 40 + 9
    assert blocks_for(TINY.kv_entries_peak(life), BS) == 6
    assert blocks_for(life, BS) > 9
    with jax.default_matmul_precision("highest"):
        _serve(engine, probes, new=40, together=True)
    assert [len(p["tokens"]) for p in probes] == [40, 40]
    assert engine.allocator.used_count == 1 and engine.allocator.reserved == 0
    out = correctness.probe_margins(params, _model(), probes, "eva")
    assert out["worst_margin"] < TOL, out


@pytest.mark.parametrize("kw,needle", [
    (dict(kv_block_size=0, prefill_buckets=(64,)), "dense cache"),
    (dict(kv_block_size=32, prefill_chunk=32, prefill_buckets=(32,)),
     "exactly one page"),
    (dict(prefill_chunk=48, prefill_buckets=(48,), max_seq_len=192,
          admit_group_chunks=1), "straddles"),
    (dict(admit_group_chunks=3), "admission group"),
    (dict(prefix_cache_blocks=4), "shared by two sequences"),
    (dict(kv_quant="int8"), "scale planes"),
    (dict(spec_len=4), "verify"),
    (dict(kv_host_pool_mb=64), "by entry"),
])
def test_the_engine_refuses_what_is_not_built(params, kw, needle):
    with pytest.raises(ValueError, match=needle):
        InferenceEngine(params, TINY, _ecfg(**kw))


def test_the_engine_refuses_a_mesh(params):
    from tpu9.serving.shard import make_policy
    with pytest.raises(ValueError, match="one chip's program"):
        InferenceEngine(params, TINY, _ecfg(), policy=make_policy("tp=2"))


def test_no_export_of_an_entry_addressed_pool(params):
    """KV export / import have no knob to refuse at construction: they
    decline, and the callers re-prefill as for any miss."""
    engine = InferenceEngine(params, TINY, _ecfg())
    assert engine.export_prefix_kv(list(range(3, 40))) is None
    assert engine.export_request_kv("nobody") is None
    assert engine.adopt_kv(b"") is False


def test_a_dense_cache_is_refused_at_trace_time(params):
    cache = init_kv_cache(TINY, 1, 128)
    with pytest.raises(NotImplementedError, match="chunked prefill alone"):
        decoder_forward(params, jnp.zeros((1, 8), jnp.int32), TINY,
                        kv_cache=cache)


def test_feasibility_prices_the_pool_by_entry():
    from tpu9.serving.feasibility import kv_cache_bytes
    from tpu9.serving.paged_kv import kv_block_bytes
    assert kv_cache_bytes(TINY, 2, S) \
        == 2 * kv_block_bytes(TINY, TINY.kv_entries_peak(S))
    assert kv_cache_bytes(TINY, 2, S) < kv_cache_bytes(
        replace(TINY, attn_window=0, attn_chunk=0), 2, S)
