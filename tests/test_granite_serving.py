"""A listed pattern through the serving path (ISSUE 55): state a lane beside
per-head paged rows under continuous batching — lanes admitted, retired and
reused, chunked prefill against one shot, K = 8 windows against eight K = 1
steps — held to the plain reference ``benchmark/reference/granitehybrid.py``;
the counters and the kernel report on ``/health``; the device scopes; and
every engine refusal, by its message. The layers themselves:
``test_granite_layers.py``, whose tiny configuration this file takes."""

import asyncio
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness
from test_granite_layers import ONE, SMALL, TOL, _model, _ref_logits
from tpu9.models import init_decoder, kvstate
from tpu9.models import hybrid, ssm
from tpu9.ops import ssd
from tpu9.models.transformer import (DEVICE_SCOPES, LOOP_SCOPES,
                                     SUMMARY_SCOPES)
from tpu9.serving.engine import EngineConfig, InferenceEngine

C, S, G, BS = 16, 256, 2, 16


@pytest.fixture(scope="module")
def params():
    return init_decoder(jax.random.PRNGKey(55), SMALL)


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
    out = correctness.probe_margins(params, _model(), probes, "granitehybrid")
    assert out["tokens_checked"] == 5 * 24
    std = _ref_logits(params, probes[0]["prompt"], _model()).std()
    assert out["worst_margin"] < TOL * std
    bare = correctness.probe_margins(
        params, _model(control=("no_decay",)), probes, "granitehybrid")
    assert bare["worst_margin"] > 10 * TOL * std


def test_a_reused_lane_starts_from_its_own_prompt_alone(params, served):
    """A sequence served last on a lane four others have used equals the
    same sequence on an engine that has served nothing: the lane's state
    was zero at its admission, whatever the lane held before."""
    _, probes = served
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
    _, probes = served
    other = InferenceEngine(params, SMALL, _ecfg(**change))
    again = [dict(p, tokens=None) for p in probes[:2]]
    _serve(other, again, 24)
    for a, p in zip(again, probes):
        assert a["tokens"] == p["tokens"], a["name"]


def test_the_engine_states_its_lanes_state_and_its_steps(served):
    engine, _ = served
    st = engine.stats()
    per_lane = 18 * (4 * 32 * 128 * 4 + 3 * (128 + 256) * 4)
    assert st["state_bytes"] == 2 * per_lane
    assert st["state_bytes_per_lane"] == per_lane
    assert st["state_lanes_in_use"] == 0
    assert st["kv_layers"] == 2
    # (no counter of lanes stepped and carried: the host can only restate
    # which form of the step was chosen; ``ssm_state_bw_share`` reads the
    # kernel's traced time against the live lanes' bytes)
    assert not [k for k in st if k.startswith("ssm_lanes")]
    assert st["graph_compiles_post_warmup"] == 0
    assert "moe_experts_held" not in st
    assert st["attention_decode"].endswith(
        "ssm step: xla: no TPU backend")
    assert "ssm scan: xla: chunkwise (SSD), blocks of 16" \
        in st["attention_prefill"]


def test_the_kernel_report_says_which_form_ran(monkeypatch):
    import tpu9.utils
    assert ssm.scan_form(512) == "xla: chunkwise (SSD), blocks of 256"
    assert ssm.scan_form(300) == "xla: a token at a time (not whole blocks)"
    monkeypatch.setattr(tpu9.utils, "on_tpu", lambda: True)
    # (the words name the kernel's constant, ``ops.ssd.GROUP_LANES``)
    assert ssm.step_form(replace(SMALL, ssm_head_dim=64)) == (
        "pallas, in place, one call walks the live lanes, "
        f"{ssd.GROUP_LANES} read, stepped and written back at a time")
    assert ssm.step_form(replace(SMALL, ssm_head_dim=48)).startswith("xla: ")


def test_the_listed_programs_name_their_scopes(params):
    assert ssm.SSM_SCOPES == ("attn.ssm.proj", "attn.ssm.state")
    assert not set(ssm.SSM_SCOPES) & set(
        DEVICE_SCOPES + LOOP_SCOPES + SUMMARY_SCOPES + hybrid.HYBRID_SCOPES
        + hybrid.MLA_QUERY_SCOPES)
    engine = InferenceEngine(params, SMALL, _ecfg(decode_steps=(1,)))
    engine.precompile()
    maps = engine.graphs.device_scopes
    for program in ("decode_1", f"chunk_{C}", f"chunkgroup_{G}"):
        for scope in ssm.SSM_SCOPES + ("attn.core", "attn.qkv", "ffn"):
            assert maps[program][scope], (program, scope)
        # no positions: nothing runs under the rotary's scope
        assert "attn.rope" not in maps[program]
        assert not set(hybrid.HYBRID_SCOPES) & set(maps[program])
    assert "lanesplice" in engine.graphs.reachable_keys((C,), ())


def test_feasibility_prices_the_rows_and_the_lanes_state():
    from tpu9.serving.feasibility import kv_cache_bytes, lane_state_bytes
    from tpu9.serving.paged_kv import kv_block_bytes
    # keys and values of 2 heads x 16 (one packed row of 32) in the 2
    # attention layers, float32
    assert kv_block_bytes(SMALL, BS) == 2 * BS * 2 * 2 * 16 * 4
    assert SMALL.kv_row == ((1, 32), (1, 32))
    assert kv_cache_bytes(SMALL, 2, S) == 2 * kv_block_bytes(SMALL, S)
    assert lane_state_bytes(SMALL, 2) == kvstate.lane_bytes(SMALL, 2)
    # the published widths: 4 planes of 8 x 64 in bf16, 75.6 MB a lane
    full = replace(SMALL, dim=2048, n_layers=40, n_heads=32, n_kv_heads=8,
                   head_dim=64, layer_pattern=ONE.layer_pattern * 4,
                   ssm_heads=64, ssm_head_dim=64,
                   dtype=jnp.bfloat16)
    assert full.kv_row == ((4, 128), (4, 128))
    assert kvstate.lane_shapes(full, 64)["ssm_state"][0] \
        == (36, 64, 32, 128, 128)
    assert kv_block_bytes(full, 1) == 4 * 2 * 8 * 64 * 2
    assert lane_state_bytes(full, 1) == 36 * (64 * 64 * 128 * 4
                                              + 3 * 4352 * 2)


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
    from tpu9.ops.quant import quantize_decoder
    from tpu9.models.llama import LLAMA_PRESETS
    from tpu9.serving.shard import make_policy
    with pytest.raises(ValueError, match="one chip's"):
        InferenceEngine(params, SMALL, _ecfg(), policy=make_policy("tp=2"))
    plain = replace(LLAMA_PRESETS["llama-tiny"], dtype=jnp.float32)
    lm_head = quantize_decoder(
        init_decoder(jax.random.PRNGKey(0), plain))["lm_head"]
    layers = [dict(params["layers"][0], w_up=lm_head)] + params["layers"][1:]
    with pytest.raises(ValueError, match="int8 weights"):
        InferenceEngine(dict(params, layers=layers), SMALL, _ecfg())


def test_no_export_of_a_lanes_state(params):
    """KV export / import have no knob to refuse at construction: they
    decline (kvwire ships rows and no state), and callers re-prefill."""
    engine = InferenceEngine(params, SMALL, _ecfg())
    assert engine.export_prefix_kv(list(range(3, 40))) is None
    assert engine.export_request_kv("nobody") is None
    assert engine.adopt_kv(b"") is False
