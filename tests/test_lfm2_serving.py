"""The list of whole layers around gated short convolutions (ISSUE 62) on the
serving path, at ``test_lfm2_layers.py``'s tiny sizes: admission in fused
groups with the tail carried chunk to chunk and spliced into its lane,
windowed decode over the packed pool with the held experts' routing kept —
and the PREFIX CACHE beside that state: a sequence admitted behind a hit (at
a page boundary inside a chunk, at a chunk boundary, behind pages another
lane wrote) is served as the same sequence without the cache, because the
pool keeps every page's tail; with the restored tail zeroed it is not. What
the engine refuses, what it says of itself, what feasibility prices."""

import asyncio
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness
from tests.test_lfm2_layers import (SMALL, TOL, _model, _ref_logits,
                                    params)  # noqa: F401  (the fixture)
from tpu9.models import kvstate, shortconv
from tpu9.serving.engine import (EngineConfig, InferenceEngine,
                                 refuse_unbuilt_with_lane_state)

C, S, G, BS = 32, 512, 2, 16


def _ecfg(**kw):
    base = dict(max_batch=2, max_seq_len=S, prefill_buckets=(C,),
                decode_steps=(1, 8), kv_block_size=BS, kv_pool_blocks=96,
                prefill_chunk=C, prefix_cache_blocks=64, admit_group_chunks=G)
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


def _toks(n, seed):
    return np.random.default_rng(seed).integers(3, 250, n).tolist()


def _session(first: int, turn: int, turns: int, seed: int) -> list:
    """A session's prompts: every turn resends the whole history (the
    earlier prompt AND what was answered is the caller's to add) + ``turn``
    new tokens."""
    history = _toks(first, seed)
    out = []
    for t in range(turns):
        out.append({"name": f"s{seed}t{t}", "prompt": list(history)})
        history = history + _toks(turn, 1000 * seed + t)
    return out


# the hit's last page ends: inside a chunk (3 pages of 16 = 48 of chunk 32's
# second window), at a chunk boundary (4 pages = 64), and after a long
# prompt admitted in fused groups (9 pages = 144)
HITS = {"page-inside-a-chunk": 55, "chunk-boundary": 70, "after-groups": 150}


@pytest.fixture(scope="module")
def served(params):
    """Three sessions of two turns, one after another on an engine WITH the
    prefix cache: every second turn is admitted behind its first turn's
    pages, the tails restored."""
    engine = InferenceEngine(params, SMALL, _ecfg())
    probes = [p for seed, first in enumerate(HITS.values(), 1)
              for p in _session(first, 20, 2, seed)]
    _serve(engine, probes, 12)
    return engine, probes


def test_a_hit_is_served_as_the_sequence_without_the_cache(params, served):
    engine, probes = served
    bare = InferenceEngine(params, SMALL, _ecfg(prefix_cache_blocks=0))
    again = [dict(p, tokens=None) for p in probes]
    _serve(bare, again, 12)
    for a, p in zip(again, probes):
        assert a["tokens"] == p["tokens"], p["name"]
    st = engine.stats()
    # every second turn hit, at the pages its first turn's prompt filled
    assert st["conv_tail_restores"] == 3
    assert st["prefix_rows_reused"] == sum(n // BS * BS
                                           for n in HITS.values())
    assert st["prompt_rows_admitted"] == sum(2 * n + 20
                                             for n in HITS.values())
    assert st["prefix_rows_recomputed"] == 0
    assert "conv_tail_restores" not in bare.stats() \
        or bare.stats()["conv_tail_restores"] == 0


def test_the_served_tokens_are_within_the_margin_of_the_reference(params,
                                                                  served):
    _, probes = served
    out = correctness.probe_margins(params, _model(), probes, "lfm2")
    assert out["tokens_checked"] == 6 * 12
    std = _ref_logits(params, probes[0]["prompt"], _model()).std()
    assert out["worst_margin"] < TOL * std
    wrong = correctness.probe_margins(
        params, _model(control=("two_taps",)), probes, "lfm2")
    assert wrong["worst_margin"] > 10 * TOL * std


def test_with_the_restored_tail_zeroed_a_hit_is_not_that_sequence(
        params, served, monkeypatch):
    """The tails are what makes the hit exact: an engine whose restore hands
    back zeros serves the hit's turns differently (their lanes' state, and
    here their tokens), and its first turns the same."""
    _, probes = served
    engine = InferenceEngine(params, SMALL, _ecfg())
    real = engine.graphs.restore_tails
    monkeypatch.setattr(
        engine.graphs, "restore_tails",
        lambda kv, block: jax.tree_util.tree_map(jnp.zeros_like,
                                                 real(kv, block)))
    again = [dict(p, tokens=None) for p in probes]
    _serve(engine, again, 12)
    firsts = [(a, p) for a, p in zip(again, probes)
              if p["name"].endswith("t0")]
    hits = [(a, p) for a, p in zip(again, probes) if p["name"].endswith("t1")]
    assert all(a["tokens"] == p["tokens"] for a, p in firsts)
    assert any(a["tokens"] != p["tokens"] for a, p in hits)
    out = correctness.probe_margins(params, _model(), [a for a, _ in hits],
                                    "lfm2")
    std = _ref_logits(params, probes[0]["prompt"], _model()).std()
    assert out["worst_margin"] > 10 * TOL * std


def test_a_hit_behind_pages_another_lane_wrote(params):
    """Two lanes at once: a long answer keeps lane 0 busy while a prompt is
    admitted on lane 1 and ends; its successor — the same prompt and more —
    is then admitted on a lane behind pages that the OTHER admission wrote,
    and is served as on an engine without the cache."""
    base = _toks(100, 21)
    probes = [{"name": "busy", "prompt": _toks(40, 20)},
              {"name": "writer", "prompt": base},
              {"name": "reader", "prompt": base + _toks(30, 22)}]
    out = {}
    for name, blocks in (("cached", 64), ("bare", 0)):
        engine = InferenceEngine(params, SMALL,
                                 _ecfg(prefix_cache_blocks=blocks))

        async def go():
            await engine.start()
            busy = asyncio.ensure_future(engine.generate(
                list(probes[0]["prompt"]), max_new_tokens=120))
            got = [await engine.generate(list(p["prompt"]),
                                         max_new_tokens=10)
                   for p in probes[1:]]
            got.append(await busy)
            await engine.stop()
            return got
        out[name] = asyncio.run(go()), engine.stats()
    assert out["cached"][0] == out["bare"][0]
    assert out["cached"][1]["conv_tail_restores"] == 1
    assert out["cached"][1]["prefix_rows_reused"] == 96


def test_a_hit_rounded_down_to_a_chunk_recomputes_its_pages(params):
    """Where the suffix's last chunk would pass ``max_seq_len`` the hit is
    rounded down to a chunk (``_admit_lookup``): the tail restored is then
    that EARLIER page's, the pages between are prefilled again, and the
    tokens are the bare engine's."""
    small = dict(max_seq_len=160, kv_pool_blocks=40)
    base = _toks(60, 31)                     # 3 pages cached: 48 rows
    probes = [{"name": "a", "prompt": base},
              {"name": "b", "prompt": base + _toks(90, 32)}]   # 150 rows
    engine = InferenceEngine(params, SMALL, _ecfg(**small))
    _serve(engine, probes, 8)
    bare = InferenceEngine(params, SMALL,
                           _ecfg(prefix_cache_blocks=0, **small))
    again = [dict(p, tokens=None) for p in probes]
    _serve(bare, again, 8)
    assert [a["tokens"] for a in again] == [p["tokens"] for p in probes]
    st = engine.stats()
    assert st["prefix_rows_recomputed"] == 16 and st["conv_tail_restores"] == 1


def test_lanes_are_reused_and_admissions_interleave(params):
    """Five sequences on two lanes, all offered at once, without the cache:
    every lane is reused (the tail spliced over whatever the lane held) and
    admissions interleave with the other lane's decode."""
    engine = InferenceEngine(params, SMALL, _ecfg(prefix_cache_blocks=0))
    probes = [{"name": f"p{n}", "prompt": _toks(n, n)}
              for n in (123, 64, 30, 7, 100)]
    _serve(engine, probes, 16, together=True)
    out = correctness.probe_margins(params, _model(), probes, "lfm2")
    std = _ref_logits(params, probes[0]["prompt"], _model()).std()
    assert out["tokens_checked"] == 5 * 16
    assert out["worst_margin"] < TOL * std
    assert engine.stats()["graph_compiles_post_warmup"] == 0


# -- what the engine says of itself --------------------------------------------

def test_the_engine_states_its_state_and_counts_its_experts(served):
    engine, _ = served
    st = engine.stats()
    assert st["state_kinds"] == ["conv"]
    per_lane = 8 * 2 * 64 * 4
    assert st["state_bytes"] == 2 * per_lane
    assert st["state_bytes_per_lane"] == per_lane
    assert st["kv_layers"] == 2
    assert st["layers_by_kind"] == {"conv": 8, "dense": 2, "full": 2,
                                    "experts": 8}
    assert st["moe_experts_held"] == 8 and st["moe_step_layers"] > 0
    assert 0 < st["moe_held_touched"] <= st["moe_step_layers"] * 8
    # (by rows a call: the tiny chunks are under ``SORTED_MIN_TOKENS``)
    assert "held_ffn" in st["ffn_decode"] and "32 rows" in st["ffn_prefill"]
    assert st["attention_decode"].endswith("short convolution: xla")
    assert st["conv_tail_blocks_written"] > 0
    assert st["graph_compiles_post_warmup"] == 0
    # a finished request leaves the experts it was served with
    from tpu9.serving import routed_experts
    fed, picks = routed_experts.records()[-1]
    assert picks.shape == (len(fed), 8, 2) and picks.max() < 8


def test_the_programs_name_their_scopes(params):
    from tpu9.models import hybrid, ssm
    from tpu9.models.transformer import (DEVICE_SCOPES, LOOP_SCOPES,
                                         SUMMARY_SCOPES)
    assert shortconv.CONV_SCOPES == ("attn.conv.proj", "attn.conv.mix",
                                     "attn.qk_norm")
    assert not set(shortconv.CONV_SCOPES) & set(
        DEVICE_SCOPES + LOOP_SCOPES + SUMMARY_SCOPES + hybrid.HYBRID_SCOPES
        + hybrid.MLA_QUERY_SCOPES + ssm.SSM_SCOPES)
    engine = InferenceEngine(params, SMALL, _ecfg(decode_steps=(1,)))
    engine.precompile()
    maps = engine.graphs.device_scopes
    for program in ("decode_1", f"chunk_{C}", f"chunkgroup_{G}"):
        for scope in shortconv.CONV_SCOPES + ("attn.core", "attn.qkv",
                                              "attn.rope", "ffn",
                                              "moe.route", "moe.experts"):
            assert maps[program][scope], (program, scope)
        assert not set(ssm.SSM_SCOPES) & set(maps[program])
    keys = engine.graphs.reachable_keys((C,), ())
    assert {"lanesplice", "tailrestore"} <= keys
    assert "tailrestore" in engine.graphs.compiled
    bare = InferenceEngine(params, SMALL, _ecfg(prefix_cache_blocks=0))
    assert "tailrestore" not in bare.graphs.reachable_keys((C,), ())


# -- what is refused -----------------------------------------------------------

@pytest.mark.parametrize("kw,needle", [
    (dict(kv_block_size=0, prefill_chunk=0), "dense cache"),
    (dict(spec_len=4), "roll back"),
    (dict(kv_quant="int8"), "two narrow heads to a cache row"),
    (dict(kv_host_pool_mb=64), "no state a lane or a block"),
])
def test_the_engine_refuses_what_is_not_built(params, kw, needle):
    with pytest.raises(ValueError, match=needle) as err:
        InferenceEngine(params, SMALL, _ecfg(**kw))
    assert "layer_pattern with state a lane (conv)" in str(err.value)


def test_a_page_shorter_than_a_tail_is_refused():
    with pytest.raises(ValueError, match="a page is at least that long"):
        refuse_unbuilt_with_lane_state(
            replace(SMALL, conv_taps=6), _ecfg(kv_block_size=4),
            {"tp": 1})


def test_the_prefix_cache_stays_refused_beside_a_matrix_a_head():
    """The kinds are told apart by what a snapshot keeps: the prefix cache
    is lifted for the window of rows and stays refused, with the bytes, for
    the delta rule's and the state-space recurrence's matrices."""
    from tests.test_granite_layers import SMALL as GRANITE
    from tests.test_hybrid_layers import SMALL as LING
    topo = {"tp": 1}
    refuse_unbuilt_with_lane_state(SMALL, _ecfg(), topo)      # built
    for cfg, kind in ((GRANITE, "state-space"), (LING, "delta rule")):
        each = kvstate.lane_bytes(cfg)
        with pytest.raises(ValueError, match="snapshot") as err:
            refuse_unbuilt_with_lane_state(cfg, _ecfg(), topo)
        assert kind in str(err.value) and f"{each:,} B" in str(err.value)


def test_the_engine_refuses_a_mesh_and_int8_weights(params):
    from tpu9.models import init_decoder
    from tpu9.models.llama import LLAMA_PRESETS
    from tpu9.ops.quant import quantize_decoder
    from tpu9.serving.shard import make_policy
    with pytest.raises(ValueError, match="one chip's"):
        InferenceEngine(params, SMALL, _ecfg(), policy=make_policy("tp=2"))
    plain = replace(LLAMA_PRESETS["llama-tiny"], dtype=jnp.float32)
    lm_head = quantize_decoder(
        init_decoder(jax.random.PRNGKey(0), plain))["lm_head"]
    layers = [dict(params["layers"][0], w_up=lm_head)] + params["layers"][1:]
    with pytest.raises(ValueError, match="int8 weights"):
        InferenceEngine(dict(params, layers=layers), SMALL, _ecfg())


def test_no_export_of_pages_without_their_tails(params):
    """KV export / import have no knob to refuse at construction: they
    decline (kvwire ships rows and no tail a block), and callers
    re-prefill."""
    engine = InferenceEngine(params, SMALL, _ecfg())
    assert engine.export_prefix_kv(list(range(3, 40))) is None
    assert engine.export_request_kv("nobody") is None
    assert engine.adopt_kv(b"") is False


def test_feasibility_prices_the_rows_the_lanes_and_the_pages_tails():
    from tpu9.serving.feasibility import kv_cache_bytes, lane_state_bytes
    from tpu9.serving.paged_kv import kv_block_bytes
    assert kv_block_bytes(SMALL, BS) == 2 * BS * 2 * 2 * 16 * 4
    assert kv_cache_bytes(SMALL, 2, S) == 2 * kv_block_bytes(SMALL, S)
    assert lane_state_bytes(SMALL, 2) == 2 * 8 * 2 * 64 * 4
    # the published widths, stage 0's fourteen layers: 6 KB a token of rows,
    # 8,192 B a layer and lane of tail, 90,112 B a block of tails beside
    # 786,432 B of rows
    full = replace(SMALL, dim=2048, n_layers=14, n_heads=32, n_kv_heads=8,
                   head_dim=64, hidden_dim=7168, vocab_size=65536,
                   layer_pattern=("conv", "conv")
                   + ("full", "conv", "conv", "conv") * 3,
                   n_experts=32, moe_routed=32, moe_top_k=4,
                   moe_hidden_dim=1792, dtype=jnp.bfloat16)
    assert full.kv_row == ((4, 128), (4, 128))
    assert kv_block_bytes(full, 1) == 6144
    assert lane_state_bytes(full, 1) == 11 * 8192
    assert kvstate.block_tail_bytes(full) == 90112
    assert kv_block_bytes(full, 128) == 786432


def test_the_budget_holds_the_pages_tails(monkeypatch):
    """``hbm_budget`` prices a pinned pool's tails with its rows."""
    from tpu9.serving import feasibility, presets
    full = replace(SMALL, dim=2048, head_dim=64, n_heads=32, n_kv_heads=8,
                   dtype=jnp.bfloat16)
    monkeypatch.setattr(presets, "resolve_preset",
                        lambda preset, quantize: (full, False))
    kw = dict(max_batch=4, max_seq_len=1024, kv_pool_blocks=100)
    with_tails = feasibility.hbm_budget("x", "v5e-1", kv_block_size=128,
                                        **kw)
    rows = 101 * kvstate.block_bytes(full, 128) \
        + kvstate.lane_bytes(full, 4)
    assert with_tails.kv_gb_per_chip * 1e9 == pytest.approx(
        rows + kvstate.block_tail_bytes(full, 101))
