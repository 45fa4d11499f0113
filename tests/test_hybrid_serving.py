"""A layer pattern through the serving path (ISSUE 48): program by program
(group, chunk, partial chunk, the splices of the latents' pages and of the
lane's state, decode K = 1 and 8 beside an idle lane) and through the engine
— lanes reused, counters, what ``/health`` states — held to the plain
reference ``benchmark/reference/ling.py``; and everything the engine refuses
for it. The layers themselves: ``test_hybrid_layers.py``, whose tiny
configuration this file takes."""

import asyncio
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness
from test_hybrid_layers import (PLAIN, SMALL, TOL, _margin, _model,
                                _ref_logits)
from tpu9.models import decoder_forward, init_decoder, init_kv_cache
from tpu9.models import hybrid, kvstate
from tpu9.models.transformer import (DEVICE_SCOPES, LOOP_SCOPES,
                                     SUMMARY_SCOPES)
from tpu9.serving.engine import EngineConfig, InferenceEngine
from tpu9.serving.graphs import GraphFactory, hlo_scopes
from tpu9.serving.shard.policy import SingleDevicePolicy

C, S, G, BS = 16, 256, 2, 16


@pytest.fixture(scope="module")
def params():
    return init_decoder(jax.random.PRNGKey(48), SMALL)


# ---------------------------------------------------------------------------
# program by program: chunked prefill, then decode through state and pool
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def programs(params):
    """One sequence through the serving programs. Prefill: two groups of two
    chunks, a chunk, a partial chunk of 11 — 91 tokens, the scratch carrying
    the KDA state from program to program; the splice of the latents' pages
    and of the lane's state into lane 0 of two. Decode: K = 1, K = 8 five
    times, K = 1 — lane 1 idle, holding state that must not move."""
    ecfg = EngineConfig(max_batch=2, max_seq_len=S, decode_steps=(1, 8),
                        kv_block_size=BS, kv_pool_blocks=20,
                        prefill_chunk=C, admit_group_chunks=G)
    graphs = GraphFactory(SMALL, ecfg, SingleDevicePolicy(), chunk=C)
    prompt = np.random.default_rng(1).integers(3, 256, 91).tolist()
    pool = {n: jnp.zeros(shape, jnp.float32) for n, (shape, _)
            in kvstate.pool_shapes(SMALL, 21, BS).items()}
    # a scratch another sequence has used: the first chunk starts from zero
    scratch = jax.tree_util.tree_map(
        lambda a: a + 3.0, init_kv_cache(SMALL, 1, graphs.scratch_len))
    mb = S // BS + 1
    blocks = list(range(1, mb))                        # block 0 is trash
    got = {"prompt": prompt}
    picks = []
    for start in (0, G * C):
        toks = jnp.asarray(prompt[start:start + G * C],
                           jnp.int32).reshape(G, C)
        phys = jnp.asarray(blocks[start // BS:start // BS + G * C // BS],
                           jnp.int32).reshape(G, C // BS)
        pool, scratch, got[f"group{start}"], chose = \
            graphs.chunk_group_fn(G)(params, pool, scratch, toks, start,
                                     C - 1, phys)
        picks.append(np.asarray(chose))
    for name, start, real in (("chunk", 64, C), ("partial", 80, 11)):
        row = prompt[start:start + real] + [0] * (C - real)
        got[name], scratch, chose = graphs.chunk_fn()(
            params, jnp.asarray([row], jnp.int32), start, scratch, real - 1)
        picks.append(np.asarray(chose)[:real])
        pool = graphs.splice_fn()(
            pool, scratch["k"], scratch["v"], start,
            jnp.asarray(blocks[start // BS:start // BS + 1], jnp.int32))
    names = tuple(kvstate.lane_shapes(SMALL, 1))
    junk = {n: jnp.full(shape, 7.0, dt) for n, (shape, dt)
            in kvstate.lane_shapes(SMALL, 2).items()}
    got["lane1_before"] = {n: np.asarray(junk[n][:, 1]) for n in names}
    lanes = graphs.lane_splice_fn()(junk, {n: scratch[n] for n in names}, 0)
    table = np.zeros((2, mb), np.int32)
    table[0, :len(blocks)] = blocks
    kv = dict(pool, table=jnp.asarray(table), **lanes)
    n = len(prompt)
    first_tok = int(np.asarray(got["partial"]).argmax())
    last = jnp.asarray([[first_tok], [0]], jnp.int32)
    clen = jnp.asarray([n, 0], jnp.int32)
    # steps each lane may run a call: lane 1 is idle
    steps = jnp.asarray([8, 0], jnp.int32)
    key = jax.random.PRNGKey(0)
    served = [first_tok]
    for k in (1,) + (8,) * 5 + (1,):
        last, kv, clen, key, toks, chose = graphs.decode_k(k)(
            params, kv, last, clen, steps, key)
        served += np.asarray(toks)[:, 0].tolist()
        picks.append(np.asarray(chose)[:, 0])
    got.update(served=served, cache_len=int(clen[0]), idle_len=int(clen[1]),
               picks=np.concatenate(picks),
               lane1_after={n: np.asarray(kv[n][:, 1]) for n in names},
               lane0_after={n: np.asarray(kv[n][:, 0]) for n in names})
    return got


@pytest.mark.parametrize("program,position", [
    ("group0", 31), ("group32", 63), ("chunk", 79), ("partial", 90)])
def test_prefill_programs_give_the_reference_logits(params, programs,
                                                    program, position):
    ref = _ref_logits(params, programs["prompt"])
    assert np.abs(np.asarray(programs[program]) - ref[position]).max() < TOL


def test_decode_through_state_and_pool_follows_the_reference(params,
                                                             programs):
    """Every served token is the reference's choice at its position,
    teacher-forced over prompt + served tokens, the reference recomputing
    the whole sequence with no cache and no state."""
    prompt, served = programs["prompt"], programs["served"]
    ref = _ref_logits(params, prompt + served)
    n = len(prompt)
    margins = [_margin(ref[n - 1 + j], t) for j, t in enumerate(served)]
    assert max(margins) < TOL, (max(margins), int(np.argmax(margins)))
    assert programs["cache_len"] == n + 42 and programs["idle_len"] == 0


def test_an_idle_lane_keeps_its_state_bit_for_bit(programs):
    for name, before in programs["lane1_before"].items():
        assert (programs["lane1_after"][name] == before).all(), name
        assert not (programs["lane0_after"][name] == 7.0).all(), name


def _own_choices(params, tokens):
    """The reference's own choice of experts, [T, expert layers, k] sorted
    (no provider is asked: ``routing_tie`` is not set)."""
    told = []
    correctness.load_reference("ling").forward(
        params, jnp.asarray(tokens, jnp.int32), _model(), told)
    return np.sort(np.stack([np.asarray(t["own"]) for t in told], 1), -1)


def test_every_program_says_which_experts_its_tokens_chose(params, programs):
    """Beside its logits a prefill program returns the experts every token
    of its row chose in each of the 5 expert layers, a decode program those
    of every lane and step: in float32 the reference's own choice at every
    position that was fed in — the prompt and all served tokens but the
    last."""
    prompt, served = programs["prompt"], programs["served"]
    assert programs["picks"].shape == (91 + 42, 5, 4)
    want = _own_choices(params, prompt + served[:-1])
    assert (np.sort(programs["picks"], -1) == want).all()


def test_the_pattern_programs_name_their_scopes(params):
    assert hybrid.HYBRID_SCOPES == (
        "attn.kda.proj", "attn.kda.state", "attn.mla.absorb",
        "attn.mla.core", "moe.shared")
    assert not set(hybrid.HYBRID_SCOPES) & set(
        DEVICE_SCOPES + LOOP_SCOPES + SUMMARY_SCOPES)
    ecfg = EngineConfig(max_batch=2, max_seq_len=S, decode_steps=(1,),
                        kv_block_size=BS, kv_pool_blocks=20,
                        prefill_chunk=C, admit_group_chunks=G)
    engine = InferenceEngine(params, SMALL, ecfg)
    engine.precompile()
    maps = engine.graphs.device_scopes
    for program in ("decode_1", f"chunk_{C}", f"chunkgroup_{G}"):
        for scope in hybrid.HYBRID_SCOPES:
            assert maps[program][scope], (program, scope)
        # the query's low-rank path, which this model lacks
        assert not set(hybrid.MLA_QUERY_SCOPES) & set(maps[program])
    assert "lanesplice" in engine.graphs.reachable_keys((C,), ())
    plain = InferenceEngine(init_decoder(jax.random.PRNGKey(0), PLAIN),
                            PLAIN, replace(ecfg, prefill_chunk=32,
                                           prefill_buckets=(32,)))
    plain.precompile()
    assert all(not set(hybrid.HYBRID_SCOPES) & set(m)
               for m in plain.graphs.device_scopes.values())
    assert "lanesplice" not in plain.graphs.reachable_keys((32,), ())
    text = plain.graphs.compiled[("decode", 1)].as_text()
    assert not hlo_scopes(text, hybrid.HYBRID_SCOPES)


# ---------------------------------------------------------------------------
# the engine: lanes, counters, refusals
# ---------------------------------------------------------------------------

def _ecfg(**kw):
    base = dict(max_batch=2, max_seq_len=S, prefill_buckets=(C,),
                decode_steps=(1, 8), kv_block_size=BS, kv_pool_blocks=40,
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


def _probes(lengths, seed=2):
    rng = np.random.default_rng(seed)
    return [{"name": f"p{n}", "prompt": rng.integers(3, 250, n).tolist()}
            for n in lengths]


@pytest.fixture(scope="module")
def served(params):
    """Five sequences on two lanes, all offered at once: every lane is
    reused, and admissions interleave with the other lane's decode."""
    engine = InferenceEngine(params, SMALL, _ecfg())
    probes = _probes((123, 64, 30, 7, 100))
    _serve(engine, probes, 24, together=True)
    return engine, probes


def test_engine_tokens_are_within_the_margin_of_the_reference(params,
                                                              served):
    _, probes = served
    out = correctness.probe_margins(params, _model(), probes, "ling")
    assert out["tokens_checked"] == 5 * 24
    assert out["worst_margin"] < TOL
    bare = correctness.probe_margins(
        params, _model(control=("no_decay",)), probes, "ling")
    assert bare["worst_margin"] > 100 * TOL


def test_a_reused_lane_starts_from_zero(params, served):
    """A sequence served last on a lane four others have used equals the
    same sequence on an engine that has served nothing."""
    _, probes = served
    fresh = InferenceEngine(params, SMALL, _ecfg())
    again = [dict(probes[-1], tokens=None)]
    _serve(fresh, again, 24)
    assert again[0]["tokens"] == probes[-1]["tokens"]


def test_the_engine_states_its_lanes_state_and_routing(served):
    engine, _ = served
    st = engine.stats()
    per_lane = 4 * (4 * 32 * 32 * 4 + 3 * 384 * 4)
    assert st["state_bytes"] == 2 * per_lane
    assert st["state_bytes_per_lane"] == per_lane
    assert st["state_lanes_in_use"] == 0 and st["moe_experts_held"] == 16
    # all 16 experts held: every pick of every live lane is a local one
    assert st["moe_token_layers"] > 0
    assert st["moe_local_picks"] == 4 * st["moe_token_layers"]
    assert 0 < st["moe_held_touched"] <= st["moe_local_picks"]
    assert st["moe_step_layers"] % 5 == 0
    # how many picks each held expert took: all of them, expert by expert
    assert len(st["moe_held_pick_hist"]) == 16
    assert sum(st["moe_held_pick_hist"]) == st["moe_local_picks"]
    assert st["graph_compiles_post_warmup"] == 0
    assert "kda step" in engine._attention_paths()["decode"]


def test_the_engine_keeps_the_experts_each_sequence_was_served_with(
        params, served, monkeypatch):
    """``routed_experts.records``: for a served sequence the experts chosen
    at every position that was fed in — the prompt through the chunk and
    group programs (admissions interleaved with the other lane's decode),
    the served tokens through the decode windows, a retired lane's last
    window included — and nothing for a sequence nobody served. Connected
    to the reference, every served choice is one the reference TAKES at a
    tie of 1e-5 (float32 on both sides: its own choice, or a tie)."""
    from benchmark.reference import served_routing
    from tpu9.serving import routed_experts
    _, probes = served
    monkeypatch.setattr(served_routing, "provider", routed_experts.records)
    assert 5 <= len(routed_experts.records()) <= routed_experts.KEEP
    reference = correctness.load_reference("ling")
    kept_of = {tuple(fed): picks for fed, picks in routed_experts.records()}
    differ = 0
    for p in probes:
        seq = p["prompt"] + p["tokens"]
        kept = kept_of[tuple(seq[:-1])]
        assert kept.shape == (len(seq) - 1, 5, 4) and kept.dtype == np.int32
        told = []
        reference.forward(params, jnp.asarray(seq + [0] * 9, jnp.int32),
                          _model(routing_tie=1e-5), told)
        for layer, said in enumerate(told):
            assert (np.asarray(said["served"])[:len(kept)]
                    == kept[:, layer]).all()
            assert (np.asarray(said["served"])[len(kept):] == -1).all()
            assert np.asarray(said["taken"])[:len(kept)].all()
            assert not np.asarray(said["taken"])[len(kept):].any()
            differ += int((np.sort(np.asarray(said["own"])[:len(kept)], -1)
                           != np.sort(kept[:, layer], -1)).any(-1).sum())
    assert differ <= 3          # of 2,195 (token, layer)s: ties in float32
    assert tuple(probes[0]["prompt"][:20]) not in kept_of


def test_feasibility_prices_the_latent_rows_and_the_lanes_state():
    from tpu9.serving.feasibility import kv_cache_bytes, lane_state_bytes
    from tpu9.serving.paged_kv import kv_block_bytes
    # one row of 64 + 16 numbers a token in the 2 MLA layers, float32 here
    assert kv_block_bytes(SMALL, BS) == 2 * BS * (64 + 16) * 4
    assert kv_cache_bytes(SMALL, 2, S) == 2 * kv_block_bytes(SMALL, S)
    assert lane_state_bytes(SMALL, 2) == kvstate.lane_bytes(SMALL, 2)
    assert lane_state_bytes(PLAIN, 8) == 0
    # the published widths: 576 numbers a token in bf16, 10.6 MB a lane
    ling = replace(SMALL, dim=2560, n_heads=32, n_kv_heads=32, head_dim=128,
                   layer_group=6, mla_latent=512, mla_nope=128, mla_rope=64,
                   mla_v=128, dtype=jnp.bfloat16)
    assert kv_block_bytes(ling, 1) == 1152
    assert lane_state_bytes(ling, 1) == 5 * (32 * 128 * 128 * 4
                                             + 3 * 3 * 4096 * 2)


@pytest.mark.parametrize("kw,needle", [
    (dict(kv_block_size=0, prefill_chunk=0), "dense cache"),
    (dict(prefix_cache_blocks=8), "snapshot"),
    (dict(spec_len=4), "roll back"),
    (dict(kv_quant="int8"), "scale planes"),
    (dict(kv_host_pool_mb=64), "no state a lane"),
])
def test_the_engine_refuses_what_is_not_built(params, kw, needle):
    with pytest.raises(ValueError, match=needle):
        InferenceEngine(params, SMALL, _ecfg(**kw))


def test_the_engine_refuses_a_mesh_and_int8_weights(params):
    from tpu9.ops.quant import quantize_decoder
    from tpu9.serving.shard import make_policy
    with pytest.raises(ValueError, match="one chip's"):
        InferenceEngine(params, SMALL, _ecfg(), policy=make_policy("tp=2"))
    plain = init_decoder(jax.random.PRNGKey(0), PLAIN)
    quantized = dict(params, lm_head=quantize_decoder(plain)["lm_head"])
    with pytest.raises(ValueError, match="int8 weights"):
        InferenceEngine(quantized, SMALL, _ecfg())


def test_no_export_of_a_lanes_state(params):
    """KV export / import have no knob to refuse at construction: they
    decline (kvwire ships rows and no state), and callers re-prefill."""
    engine = InferenceEngine(params, SMALL, _ecfg())
    assert engine.export_prefix_kv(list(range(3, 40))) is None
    assert engine.export_request_kv("nobody") is None
    assert engine.adopt_kv(b"") is False


def test_a_verify_window_and_a_dense_decode_are_refused_at_trace_time(
        params):
    cache = init_kv_cache(SMALL, 2, 64)
    with pytest.raises(NotImplementedError, match="verify window"):
        jax.eval_shape(lambda: decoder_forward(
            params, jnp.zeros((2, 4), jnp.int32), SMALL, kv_cache=cache,
            cache_len=jnp.asarray([4, 4])))
