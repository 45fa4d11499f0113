"""A listed pattern of half-layers through the serving path (ISSUE 59): state
a lane AND expert layers told what they hold in one engine, beside a pool one
plane deep, under continuous batching — lanes admitted, retired and reused,
chunked prefill against one shot, K = 8 windows against eight K = 1 steps —
held to the plain reference ``benchmark/reference/nemotronh.py``; the picks a
decode window and a prefill dispatch return and what the engine keeps of
them; the counters and the kernel report on ``/health``; the device scopes;
and every engine refusal, by its message. The layers themselves:
``test_nemotron_layers.py``, whose tiny configuration this file takes."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness
from benchmark.reference import served_routing
from test_nemotron_layers import SMALL, TOL, _model, _ref_logits
from tpu9.models import hybrid, init_decoder, moe, ssm
from tpu9.models.transformer import (DEVICE_SCOPES, LATENT_MOE_SCOPES,
                                     LOOP_SCOPES, SUMMARY_SCOPES, moe_cfg)
from tpu9.serving import routed_experts
from tpu9.serving.engine import EngineConfig, InferenceEngine

C, S, G, BS = 16, 256, 2, 16


@pytest.fixture(scope="module")
def params():
    return init_decoder(jax.random.PRNGKey(59), SMALL)


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
    routed_experts._finished.clear()
    engine = InferenceEngine(params, SMALL, _ecfg())
    probes = _probes((123, 64, 30, 7, 100))
    _serve(engine, probes, 24, together=True)
    return engine, probes, routed_experts.records()


def test_engine_tokens_are_within_the_margin_of_the_reference(params,
                                                              served):
    _, probes, _ = served
    out = correctness.probe_margins(params, _model(), probes, "nemotronh")
    assert out["tokens_checked"] == 5 * 24
    std = _ref_logits(params, probes[0]["prompt"], _model()).std()
    assert out["worst_margin"] < TOL * std
    for control in ("whole_norm", "no_latent_scale", "gated"):
        bare = correctness.probe_margins(
            params, _model(control=(control,)), probes, "nemotronh")
        assert bare["worst_margin"] > 10 * TOL * std, control


def test_the_engine_keeps_the_experts_every_position_chose(params, served):
    """A finished request leaves its routing in ``routed_experts``: one row a
    position fed in (the prompt's from the prefill dispatches, a generated
    token's from the decode window that fed it back), five expert layers, 4
    picks — and in float32 they are the reference's own choice, which the
    reference then takes through the door."""
    _, probes, records = served
    assert len(records) == 5
    by_len = {len(fed): picks for fed, picks in records}
    ref = correctness.load_reference("nemotronh")
    for p in probes:
        fed = p["prompt"] + p["tokens"][:-1]
        picks = by_len[len(fed)]
        assert picks.shape == (len(fed), 5, 4)
        told = []
        ref.forward(params, jnp.asarray(fed, jnp.int32), _model(), told)
        assert len(told) == 5
        for layer, said in enumerate(told):
            assert (np.sort(picks[:, layer], -1)
                    == np.sort(np.asarray(said["own"]), -1)).all(), layer
    # through the door: every served choice is taken, nothing changes
    served_routing.provider = lambda: records
    try:
        told = []
        p = probes[0]
        seq = jnp.asarray(p["prompt"] + p["tokens"], jnp.int32)
        got = ref.forward(params, seq, _model(routing_tie=0.01), told)
        assert all(bool(said["taken"][:-1].all()) for said in told)
        assert not bool(told[0]["taken"][-1])        # past the record: -1
        want = ref.forward(params, seq, _model())
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    finally:
        served_routing.provider = None


def test_a_reused_lane_starts_from_its_own_prompt_alone(params, served):
    _, probes, _ = served
    fresh = InferenceEngine(params, SMALL, _ecfg())
    again = [dict(probes[-1], tokens=None)]
    _serve(fresh, again, 24)
    assert again[0]["tokens"] == probes[-1]["tokens"]


@pytest.mark.parametrize("change", [
    dict(prefill_chunk=128, prefill_buckets=(128,), admit_group_chunks=1),
    dict(decode_steps=(1,)),
], ids=["one-shot-prefill", "k1-steps-only"])
def test_the_walk_does_not_change_the_tokens(params, served, change):
    """Chunked prefill (8 chunks, fused in twos) = one shot of 128; K = 8
    windows = eight K = 1 steps: the same tokens, sequence by sequence."""
    _, probes, _ = served
    other = InferenceEngine(params, SMALL, _ecfg(**change))
    again = [dict(p, tokens=None) for p in probes[:2]]
    _serve(other, again, 24)
    for a, p in zip(again, probes):
        assert a["tokens"] == p["tokens"], a["name"]


def test_a_wide_prefill_takes_the_sorted_form_and_gives_the_same_tokens(
        params, served, monkeypatch):
    """A dispatch of more rows than ``SORTED_MIN_TOKENS`` sorts its rows by
    expert (``moe_ffn_sorted``): here every prefill dispatch does, and the
    tokens are the held form's."""
    _, probes, _ = served
    monkeypatch.setattr(moe, "SORTED_MIN_TOKENS", 8)
    seen = []
    sorted_form = moe.moe_ffn_sorted
    monkeypatch.setattr(moe, "moe_ffn_sorted", lambda *a, **kw: (
        seen.append(a[1].shape), sorted_form(*a, **kw))[1])
    other = InferenceEngine(params, SMALL, _ecfg())
    again = [dict(probes[i], tokens=None) for i in (1, 3)]
    _serve(other, again, 24)
    assert [a["tokens"] for a in again] == [probes[i]["tokens"]
                                            for i in (1, 3)]
    assert (1, C, 64) in seen and (1, G * C, 64) in seen
    assert "16 rows: sorted by expert, grouped_ffn" \
        in other.stats()["ffn_prefill"]


def test_the_engine_states_its_state_its_experts_and_its_forms(served):
    engine, _, _ = served
    st = engine.stats()
    per_lane = 5 * (8 * 16 * 32 * 4 + 3 * (128 + 128) * 4)
    assert st["state_bytes"] == 2 * per_lane
    assert st["state_bytes_per_lane"] == per_lane
    assert st["state_lanes_in_use"] == 0
    assert st["kv_layers"] == 1
    assert st["graph_compiles_post_warmup"] == 0
    assert st["layers_by_kind"] == {"ssm": 5, "experts": 5, "full": 1}
    assert st["moe_latent"] == 32 and st["moe_experts_held"] == 4
    assert st["ffn_decode"] == (
        "the touched of the held experts, held_ffn (ungated relu2, in a "
        "latent of 32): xla: no TPU backend")
    assert st["ffn_prefill"].startswith("16 rows: the touched of the held")
    assert "32 rows: " in st["ffn_prefill"]
    assert st["attention_decode"].endswith("ssm step: xla: no TPU backend")
    # the counters are over the five expert layers of every decode step
    assert st["moe_step_layers"] == 5 * st["decode_steps"]
    assert 0 < st["moe_held_touched"] <= 4 * st["moe_step_layers"]
    assert st["moe_local_picks"] >= st["moe_held_touched"]
    assert st["moe_token_layers"] % 5 == 0
    assert sum(st["moe_held_pick_hist"]) == st["moe_local_picks"]


def test_the_forms_report_follows_the_rows_and_the_backend(monkeypatch):
    import tpu9.utils
    cfg = moe_cfg(SMALL)
    monkeypatch.setattr(tpu9.utils, "on_tpu", lambda: True)
    forms = moe.share_forms(cfg, 64, (512, 2048))
    assert forms["decode"] == ("the touched of the held experts, held_ffn "
                               "(ungated relu2, in a latent of 32): pallas")
    assert forms["prefill"] == "; ".join(
        f"{rows} rows: sorted by expert, grouped_ffn (ungated relu2, in a "
        "latent of 32): pallas" for rows in (512, 2048))


def test_the_programs_name_their_scopes(params):
    assert LATENT_MOE_SCOPES == ("moe.latent.in", "moe.latent.out")
    assert not set(LATENT_MOE_SCOPES) & set(
        DEVICE_SCOPES + LOOP_SCOPES + SUMMARY_SCOPES + hybrid.HYBRID_SCOPES
        + hybrid.MLA_QUERY_SCOPES + ssm.SSM_SCOPES)
    engine = InferenceEngine(params, SMALL, _ecfg(decode_steps=(1,)))
    engine.precompile()
    maps = engine.graphs.device_scopes
    for program in ("decode_1", f"chunk_{C}", f"chunkgroup_{G}"):
        for scope in ssm.SSM_SCOPES + LATENT_MOE_SCOPES + (
                "attn.core", "attn.qkv", "moe.route", "moe.experts",
                "moe.shared"):
            assert maps[program][scope], (program, scope)
        # no positions, no dense feed-forward part
        assert "attn.rope" not in maps[program]
        assert "ffn" not in maps[program]
    assert "lanesplice" in engine.graphs.reachable_keys((C,), ())


@pytest.mark.parametrize("kw,needle", [
    (dict(kv_block_size=0, prefill_chunk=0), "dense cache"),
    (dict(prefix_cache_blocks=8), "snapshot"),
    (dict(spec_len=4), "roll back"),
    (dict(kv_quant="int8"), "float32 by the configuration"),
    (dict(kv_host_pool_mb=64), "no state a lane"),
])
def test_the_engine_refuses_what_is_not_built(params, kw, needle):
    with pytest.raises(ValueError, match=needle) as err:
        InferenceEngine(params, SMALL, _ecfg(**kw))
    assert "layer_pattern with state a lane (ssm)" in str(err.value)


def test_the_engine_refuses_a_mesh_and_int8_weights(params):
    from dataclasses import replace

    from tpu9.models.llama import LLAMA_PRESETS
    from tpu9.ops.quant import quantize_decoder
    from tpu9.serving.shard import make_policy
    with pytest.raises(ValueError, match="one chip's") as err:
        InferenceEngine(params, SMALL, _ecfg(), policy=make_policy("tp=2"))
    assert "held experts take the dropless kernels on one device" \
        in str(err.value)
    plain = replace(LLAMA_PRESETS["llama-tiny"], dtype=jnp.float32)
    lm_head = quantize_decoder(
        init_decoder(jax.random.PRNGKey(0), plain))["lm_head"]
    with pytest.raises(ValueError, match="int8 weights"):
        InferenceEngine(dict(params, lm_head=lm_head), SMALL, _ecfg())
