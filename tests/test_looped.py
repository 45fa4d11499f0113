"""The looped decoder (ISSUE 34): ``loop_steps`` passes over one set of
layers as a device loop, a KV state ``kv_layers`` deep, four norms a layer,
the final norm closing every pass, an exit gate and its selection — held to
the plain reference ``benchmark/reference/looped.py`` program by program
(chunk, group, splice, decode K = 1 and 8, verify) and through the engine
(prefix hit, counters), and everything that prices or ships the KV state at
its real depth."""

import asyncio
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness
from benchmark.reference import looped as reference
from tpu9.models import decoder_forward, init_decoder, init_kv_cache
from tpu9.models.llama import LLAMA_PRESETS
from tpu9.models.ouro import OURO_PRESETS, ouro_config
from tpu9.models.transformer import (DEVICE_SCOPES, LOOP_SCOPES,
                                     DecoderConfig)
from tpu9.serving.engine import EngineConfig, InferenceEngine
from tpu9.serving.graphs import GraphFactory, hlo_scopes
from tpu9.serving.shard.policy import SingleDevicePolicy

TINY = replace(OURO_PRESETS["ouro-tiny"], dtype=jnp.float32)
PLAIN = replace(LLAMA_PRESETS["llama-tiny"], dtype=jnp.float32)
R, L = TINY.loop_steps, TINY.n_layers
C, BS, S, G, N = 32, 16, 256, 2, 24
# float32 on both sides: summation order alone
TOL = 2e-4


def _model(cfg):
    """The reference's sizes of a program config."""
    return {"num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "total_ut_steps": cfg.loop_steps,
            "early_exit_threshold": cfg.exit_threshold}


@pytest.fixture(scope="module")
def params():
    return init_decoder(jax.random.PRNGKey(34), TINY)


def _ref_logits(params, tokens, cfg=TINY):
    return np.asarray(jax.jit(
        lambda p, x: reference.forward(p, x, _model(cfg)))(
            params, jnp.asarray(tokens, jnp.int32)))


def _margin(logits_row, token):
    return float(logits_row.max() - logits_row[token])


# ---------------------------------------------------------------------------
# the forward pass and the programs against the reference
# ---------------------------------------------------------------------------

def test_forward_equals_the_reference(params):
    tokens = np.random.default_rng(0).integers(3, 512, 70)
    with jax.default_matmul_precision("highest"):
        got, exits = decoder_forward(params, jnp.asarray(tokens)[None], TINY,
                                     return_exit=True)
    assert np.abs(np.asarray(got[0]) - _ref_logits(params, tokens)).max() \
        < TOL
    # threshold 1.0: the published rule picks the last pass, computed;
    # beside it the passes the loop's own carry counted
    assert np.asarray(exits)[..., 0].tolist() == [[R - 1] * 70]
    assert np.asarray(exits)[..., 1].tolist() == [[R] * 70]


@pytest.fixture(scope="module")
def programs(params):
    """One sequence through the serving programs: a group of two chunks, a
    full chunk, a partial chunk, a K = 1 and a K = 8 decode window, then a
    verify of four drafts. Returns what each gave."""
    ecfg = EngineConfig(max_batch=2, max_seq_len=S, decode_steps=(1, 8),
                        kv_block_size=BS, kv_pool_blocks=N - 1,
                        prefill_chunk=C, admit_group_chunks=G)
    graphs = GraphFactory(TINY, ecfg, SingleDevicePolicy(), chunk=C)
    rng = np.random.default_rng(1)
    prompt = rng.integers(3, 512, 2 * C + C + 20).tolist()     # 116 tokens
    pool = {n: jnp.zeros((TINY.kv_layers, N, BS, TINY.n_kv_heads,
                          TINY.head_dim), jnp.float32) for n in ("k", "v")}
    scratch = init_kv_cache(TINY, 1, S)
    mb = S // BS + 1
    blocks = list(range(1, 1 + S // BS))               # block 0 is trash
    got = {"prompt": prompt}
    with jax.default_matmul_precision("highest"):
        toks = jnp.asarray(prompt[:G * C], jnp.int32).reshape(G, C)
        phys = jnp.asarray(blocks[:G * C // BS], jnp.int32).reshape(G, -1)
        pool, scratch, got["group"] = graphs.chunk_group_fn(G)(
            params, pool, scratch, toks, 0, C - 1, phys)
        for name, start, real in (("chunk", G * C, C),
                                  ("partial", G * C + C, 20)):
            row = prompt[start:start + real] + [0] * (C - real)
            got[name], scratch = graphs.chunk_fn()(
                params, jnp.asarray([row], jnp.int32), start, scratch,
                real - 1)
            first = start // BS
            pool = graphs.splice_fn()(
                pool, scratch["k"], scratch["v"], start,
                jnp.asarray(blocks[first:first + C // BS], jnp.int32))
        table = np.zeros((2, mb), np.int32)
        table[0, :len(blocks)] = blocks
        kv = dict(pool, table=jnp.asarray(table))
        n = len(prompt)
        first_tok = int(np.asarray(got["partial"]).argmax())
        last = jnp.asarray([[first_tok], [0]], jnp.int32)
        clen = jnp.asarray([n, 0], jnp.int32)
        active = jnp.asarray([True, False])
        # steps each lane may run a decode call: lane 1 is idle
        steps = jnp.asarray([8, 0], jnp.int32)
        key = jax.random.PRNGKey(0)
        served = [first_tok]
        got["exits"] = []
        for k in (1, 8):
            last, kv, clen, key, toks, exits = graphs.decode_k(k)(
                params, kv, last, clen, steps, key)
            served += np.asarray(toks)[:, 0].tolist()
            got["exits"] += np.asarray(exits)[:, 0, 0].tolist()
            got["ran"] = got.get("ran", []) + \
                np.asarray(exits)[:, 0, 1].tolist()
        got["served"] = served                          # 1 + 1 + 8 tokens
        got["cache_len"] = int(clen[0])
        # verify: two drafts the model would choose itself, then a wrong one
        ref = _ref_logits(params, prompt + served)
        want = [int(ref[n + len(served) - 1].argmax())]
        for _ in range(3):
            want.append(int(_ref_logits(
                params, prompt + served + want)[-1].argmax()))
        drafts = [want[0], want[1], (want[2] + 1) % 512, 5]
        bonus, kv, clen, key, out, n_acc = graphs.verify_fn(4)(
            params, kv, last, jnp.asarray([drafts, [0] * 4], jnp.int32),
            clen, active, key)
        got.update(want=want, out=np.asarray(out)[0].tolist(),
                   n_acc=int(n_acc[0]), bonus=int(bonus[0, 0]),
                   verify_len=int(clen[0]))
    return got


@pytest.mark.parametrize("program,position", [
    ("group", G * C - 1), ("chunk", G * C + C - 1),
    ("partial", G * C + C + 19)])
def test_prefill_programs_give_the_reference_logits(params, programs,
                                                    program, position):
    ref = _ref_logits(params, programs["prompt"])
    assert np.abs(np.asarray(programs[program]) - ref[position]).max() < TOL


def test_decode_windows_through_the_paged_pool_follow_the_reference(
        params, programs):
    """K = 1 then K = 8: every served token is the reference's choice at
    its position, teacher-forced over prompt + served tokens; the pool is
    read at plane ``u * n_layers + l``, or the later tokens would not be."""
    prompt, served = programs["prompt"], programs["served"]
    ref = _ref_logits(params, prompt + served)
    n = len(prompt)
    worst = max(_margin(ref[n - 1 + j], t) for j, t in enumerate(served))
    assert worst < TOL, worst
    assert programs["cache_len"] == n + 9
    assert programs["exits"] == [R - 1] * 9
    assert programs["ran"] == [R] * 9


def test_verify_accepts_what_the_reference_would_choose(programs):
    assert programs["n_acc"] == 2
    assert programs["out"][:3] == programs["want"][:3]
    assert programs["bonus"] == programs["want"][2]
    assert programs["verify_len"] == programs["cache_len"] + 3


# ---------------------------------------------------------------------------
# the engine: prefix hit, counters, stats, span
# ---------------------------------------------------------------------------

def _ecfg(**kw):
    base = dict(max_batch=2, max_seq_len=S, prefill_buckets=(C,),
                decode_steps=(1, 8), kv_block_size=BS, kv_pool_blocks=40,
                prefill_chunk=C, prefix_cache_blocks=8, admit_group_chunks=G)
    base.update(kw)
    return EngineConfig(**base)


def _serve(engine, probes, new=12):
    async def go():
        await engine.start()
        for p in probes:
            p["tokens"] = await engine.generate(list(p["prompt"]),
                                                max_new_tokens=new)
        await engine.stop()
    asyncio.run(go())


@pytest.fixture(scope="module")
def served(params):
    engine = InferenceEngine(params, TINY, _ecfg())
    rng = np.random.default_rng(2)
    shared = rng.integers(3, 512, 2 * C + 7).tolist()
    probes = [{"name": "multi", "prompt": rng.integers(3, 512, 5 * C + 9)
               .tolist()},
              {"name": "prefix_a", "prompt": shared + [11, 12, 13]},
              {"name": "prefix_b", "prompt": shared + [21, 22]}]
    with jax.default_matmul_precision("highest"):
        _serve(engine, probes)
    return engine, probes


def test_engine_tokens_are_within_the_margin_of_the_reference(params, served):
    engine, probes = served
    out = correctness.probe_margins(params, _model(TINY), probes, "looped")
    assert out["tokens_checked"] == 36 and out["worst_margin"] < TOL, out
    assert engine.prefix_cache.stats()["hits"] >= 1      # gather + prefix


def test_the_reference_at_one_pass_less_fails_the_margin_check(params,
                                                               served):
    """The comparison sees the mechanism: the same served tokens held to
    the reference run with R - 1 passes are far outside any tolerance."""
    _, probes = served
    model = dict(_model(TINY), total_ut_steps=R - 1)
    out = correctness.probe_margins(params, model, probes, "looped")
    assert out["worst_margin"] > 0.14, out


def test_the_reference_on_int8_rounded_weights_fails_the_margin_check(
        params, served):
    """The comparison sees a precision step: the same served tokens held to
    the reference on weights rounded to int8 and back (the nearest
    precision below what the model states) are outside the tolerance that
    the sound program keeps."""
    from tpu9.ops.quant import dequantize_weight, quantize_weight
    _, probes = served
    rounded = dict(params, layers=jax.tree_util.tree_map(
        lambda x: dequantize_weight(quantize_weight(x), x.dtype)
        if x.ndim == 2 else x, params["layers"]))
    out = correctness.probe_margins(rounded, _model(TINY), probes, "looped")
    assert out["worst_margin"] > 10 * TOL, out


def test_looped_counters_and_stats(served):
    engine, probes = served
    st = engine.stats()
    assert (st["loop_steps"], st["kv_layers"]) == (R, R * L)
    assert st["kv_bytes_per_token"] == 2 * R * L * TINY.n_kv_heads \
        * TINY.head_dim * 4
    # every token but each request's first comes from a decode window
    tokens = sum(len(p["tokens"]) for p in probes) - len(probes)
    assert st["loop_tokens"] == tokens and st["loop_passes"] == R * tokens
    assert st["loop_exit_hist"] == [0] * (R - 1) + [tokens]
    assert st["decode_bytes_per_token_per_chip"] > 0


def test_a_plain_decoder_has_no_loop_counters_and_no_loop_scope():
    params = init_decoder(jax.random.PRNGKey(0), PLAIN)
    engine = InferenceEngine(params, PLAIN, _ecfg())
    probes = [{"prompt": list(range(3, 40))}]
    _serve(engine, probes, new=4)
    st = engine.stats()
    assert not [k for k in st if k.startswith("loop_")]
    assert st["kv_layers"] == PLAIN.n_layers
    text = engine.graphs.decode_k(1).lower(
        params, engine.kv_cache, engine.last_token, engine.cache_len,
        jnp.asarray(engine.active, jnp.int32), engine._rng
        ).compile().as_text()
    assert not hlo_scopes(text, LOOP_SCOPES)
    assert hlo_scopes(text, DEVICE_SCOPES)


def test_a_looped_decode_program_names_the_loop_scopes(params):
    assert LOOP_SCOPES == ("loop.norm", "loop.gate", "loop.select")
    assert not set(LOOP_SCOPES) & set(DEVICE_SCOPES)
    engine = InferenceEngine(params, TINY, _ecfg())
    text = engine.graphs.decode_k(1).lower(
        params, engine.kv_cache, engine.last_token, engine.cache_len,
        jnp.asarray(engine.active, jnp.int32), engine._rng
        ).compile().as_text()
    scopes = hlo_scopes(text, DEVICE_SCOPES + LOOP_SCOPES)
    assert set(LOOP_SCOPES) <= set(scopes), sorted(scopes)
    # what the engine reports on /health after a precompile knows them too
    engine.precompile()
    assert set(LOOP_SCOPES) <= set(engine.stats()["device_scopes"]["decode_1"])
    # the passes are a device loop: the layer bodies are traced once
    jaxpr = str(jax.make_jaxpr(
        lambda p, t: decoder_forward(p, t, TINY))(
            params, jnp.zeros((1, 4), jnp.int32)))
    assert jaxpr.count("scan[") + jaxpr.count("while[") == 1


def test_the_decode_span_carries_loop_steps(params):
    from tpu9.observability.trace import tracer
    engine = InferenceEngine(params, TINY, _ecfg())
    seen = []
    orig = tracer.record_span

    def record(name, *a, **kw):
        seen.append((name, kw.get("attrs")))
        return orig(name, *a, **kw)

    async def go():
        await engine.start()
        await engine.generate(list(range(3, 30)), max_new_tokens=6,
                              trace=("t" * 32, "p" * 16))
        await engine.stop()

    tracer.record_span = record
    try:
        asyncio.run(go())
    finally:
        tracer.record_span = orig
    spans = [a for n, a in seen if n == "engine.decode"]
    assert spans and spans[0]["loop_steps"] == R


# ---------------------------------------------------------------------------
# the descriptors: a plain decoder is untouched, the exit rule
# ---------------------------------------------------------------------------

def test_loop_steps_one_and_no_descriptor_is_the_plain_decoder():
    cfg = replace(PLAIN, loop_steps=1, sandwich_norm=False, exit_gate=False)
    assert cfg == PLAIN and not cfg.looped and cfg.kv_layers == cfg.n_layers
    a = init_decoder(jax.random.PRNGKey(3), PLAIN)
    assert set(a) == {"embed", "final_norm", "layers", "lm_head"}
    assert set(a["layers"][0]) == {"attn_norm", "mlp_norm", "wq", "wk", "wv",
                                   "wo", "w_gate", "w_up", "w_down"}
    # the rng schedule of the shared leaves does not depend on descriptors
    b = init_decoder(jax.random.PRNGKey(3),
                     replace(PLAIN, loop_steps=2, sandwich_norm=True,
                             exit_gate=True))
    for name in ("wq", "w_down"):
        assert jnp.array_equal(a["layers"][1][name], b["layers"][1][name])
    assert jnp.array_equal(a["lm_head"], b["lm_head"])
    tokens = jnp.arange(3, 20)[None]
    out = decoder_forward(a, tokens, PLAIN)
    plain = str(jax.make_jaxpr(
        lambda p, t: decoder_forward(p, t, PLAIN))(a, tokens))
    assert "while[" not in plain and "scan[" not in plain
    # one pass of the loop with no gate and two norms IS the plain decoder
    looped_once = decoder_forward(a, tokens, PLAIN, return_exit=True)
    assert jnp.array_equal(out, looped_once[0])
    assert not np.asarray(looped_once[1])[..., 0].any()
    assert (np.asarray(looped_once[1])[..., 1] == 1).all()


def _saturated(params, bias):
    gate = dict(params["exit_gate"], b=jnp.full((1,), bias, jnp.float32))
    return dict(params, exit_gate=gate)


@pytest.mark.parametrize("bias,exit_at", [(0.0, 2), (100.0, 0), (-100.0, 2)])
def test_the_exit_rule_in_the_program_and_the_reference(params, bias,
                                                        exit_at):
    """Threshold 1.0: the last pass, unless a gate saturates to exactly 1 —
    then the first pass's state is what the head reads, in both."""
    cfg = replace(TINY, loop_steps=3)
    p = _saturated(params, bias)
    tokens = np.random.default_rng(5).integers(3, 512, 24)
    with jax.default_matmul_precision("highest"):
        got, exits = decoder_forward(p, jnp.asarray(tokens)[None], cfg,
                                     return_exit=True)
    assert np.asarray(exits)[..., 0].tolist() == [[exit_at] * 24]
    assert (np.asarray(exits)[..., 1] == 3).all()     # every pass still ran
    assert np.abs(np.asarray(got[0]) - _ref_logits(p, tokens, cfg)).max() \
        < TOL
    if exit_at == 0:
        one = replace(cfg, loop_steps=1)
        with jax.default_matmul_precision("highest"):
            first = decoder_forward(p, jnp.asarray(tokens)[None], one)
        assert np.abs(np.asarray(got - first)).max() < TOL


def test_a_threshold_below_one_is_refused():
    with pytest.raises(ValueError, match="per-sequence pass"):
        ouro_config(exit_threshold=0.9)
    with pytest.raises(ValueError, match="loop_steps"):
        DecoderConfig(loop_steps=0)
    assert DecoderConfig(exit_threshold=0.5).exit_gate is False   # no gate


# ---------------------------------------------------------------------------
# everything that prices or ships the KV state, at kv_layers
# ---------------------------------------------------------------------------

def test_kv_depth_in_block_bytes_pool_scratch_and_wire(params):
    from tpu9.serving import kvwire
    from tpu9.serving.graphs import abstract_state
    from tpu9.serving.kvpool import KvPool
    from tpu9.serving.paged_kv import kv_block_bytes
    big = OURO_PRESETS["ouro-2.6b"]
    assert big.kv_layers == 192
    assert kv_block_bytes(big, 1) == 1_572_864
    assert kv_block_bytes(big, 128) == 201_326_592
    assert kv_block_bytes(TINY, BS, True) == 2 * R * L * BS \
        * TINY.n_kv_heads * (TINY.head_dim + 4)
    ecfg = _ecfg()
    pool = KvPool(TINY, ecfg, False, SingleDevicePolicy())
    assert pool.array_shapes()["k"][0] == (R * L, 41, BS, TINY.n_kv_heads,
                                           TINY.head_dim)
    st = abstract_state(TINY, ecfg, SingleDevicePolicy())
    assert st["scratch"]["k"].shape == (R * L, 1, S, TINY.n_kv_heads,
                                        TINY.head_dim)
    assert init_kv_cache(TINY, 2, 64)["v"].shape[0] == R * L
    assert kvwire.geometry(TINY, ecfg, False)["n_layers"] == R * L


def test_kvwire_round_trip_and_depth_mismatch(params):
    """An export adopts only into a pool of the same depth."""
    from tpu9.serving import kvwire
    from tpu9.serving.kvpool import KvPool
    from tpu9.serving.paged_kv import PrefixCache
    cfg = replace(TINY, dtype=jnp.bfloat16)

    def pool_of(c):
        pool = KvPool(c, _ecfg(), False, SingleDevicePolicy())
        return pool, pool.init_arrays()

    a, kv_a = pool_of(cfg)
    blocks = a.alloc_blocks(2)
    rng = np.random.default_rng(0)
    for name in a.wire_names():
        shape, dt = a.array_shapes()[name]
        vals = rng.standard_normal((shape[0], 2) + tuple(shape[2:]))
        kv_a[name] = kv_a[name].at[:, jnp.asarray(blocks)].set(
            jnp.asarray(vals, dt))
    tokens = list(range(1, 2 * BS + 1))
    payload = a.export_blocks(kv_a, blocks, PrefixCache._key(tokens),
                              len(tokens))
    header, planes = kvwire.decode_blocks(payload)
    assert header["n_layers"] == R * L and planes["k"].shape[0] == R * L
    b, kv_b = pool_of(cfg)
    kv_b, adopted, _ = b.import_blocks(kv_b, payload)
    assert adopted
    entry = b.prefix_cache.acquire_for_export(tokens)
    assert b.export_blocks(kv_b, entry.blocks, entry.key,
                           entry.n_tokens) == payload
    shallow, kv_c = pool_of(replace(cfg, loop_steps=1))
    with pytest.raises(kvwire.KvWireError, match="n_layers"):
        shallow.import_blocks(kv_c, payload)


def test_feasibility_and_the_planner_price_the_real_depth():
    from tpu9.serving.feasibility import (InfeasibleDeployment, hbm_budget,
                                          validate_llm_deployment)
    from tpu9.serving.shard.plan import plan_topology
    knobs = dict(max_batch=16, max_seq_len=1024, kv_pool_blocks=36,
                 kv_block_size=128)
    b = hbm_budget("ouro-2.6b", "v5e-1", **knobs)
    assert round(b.weight_gb_per_chip, 2) == 5.34
    assert round(b.kv_gb_per_chip, 3) == round(37 * 201_326_592 / 1e9, 3)
    assert round(b.scratch_gb_per_chip, 2) == 1.61
    assert b.fits
    plan = plan_topology("ouro-2.6b", "v5e-4", **knobs)
    assert (plan.topology.tp, plan.topology.fsdp) == (1, 1)
    assert plan.rejected == ()
    # dense parity (16 sequences x 1024 tokens x 1.57 MB) is 25.8 GB: the
    # pinned pool is what makes one chip enough
    with pytest.raises(InfeasibleDeployment):
        validate_llm_deployment("ouro-2.6b", "v5e-1", max_batch=16,
                                max_seq_len=1024)


def test_the_deploy_gates_price_a_pinned_pool():
    """The gateway's gate and ``tpu9 llm deploy`` forward the pinned pool
    the planner prices: a declarative ``ouro-2.6b`` stub is refused at
    dense parity and accepted with the pool its deployment pins."""
    import asyncio

    from tpu9.testing.localstack import LocalStack

    def stub(name, **extra):
        return {"name": name, "stub_type": "endpoint", "config": {
            "handler": "app:load", "runtime": {"tpu": "v5e-1"},
            "extra": dict(runner="llm", model="ouro-2.6b", max_batch=16,
                          max_seq_len=1024, **extra)}}

    async def run():
        async with LocalStack() as stack:
            status, out = await stack.api(
                "POST", "/rpc/stub/get-or-create", json_body=stub("dense"))
            assert status == 400 and "kv_pool_blocks" in out["error"], out
            status, out = await stack.api(
                "POST", "/rpc/stub/get-or-create",
                json_body=stub("no-size", kv_pool_blocks=31))
            assert status == 400 and "kv_block_size" in out["error"], out
            status, out = await stack.api(
                "POST", "/rpc/stub/get-or-create",
                json_body=stub("pinned", kv_pool_blocks=31,
                               kv_block_size=128))
            assert status == 200, out

    asyncio.run(run())

    from click.testing import CliRunner

    from tpu9.cli.main import cli
    refused = CliRunner().invoke(cli, [
        "llm", "deploy", "--model", "ouro-2.6b", "--max-batch", "16",
        "--max-seq-len", "1024"])
    assert refused.exit_code != 0
    assert "InfeasibleDeployment" in repr(refused.exception)


def test_the_runner_pins_a_named_presets_pool(monkeypatch):
    """A handler that returns a preset's NAME gets its pool pinned by
    ``TPU9_KV_POOL_BLOCKS``, as the other per-deployment knobs are."""
    from tpu9.runner import llm
    from tpu9.serving import presets
    seen = {}
    monkeypatch.setattr(presets, "load_engine",
                        lambda name, **kw: seen.update(kw, name=name))
    monkeypatch.setenv("TPU9_KV_POOL_BLOCKS", "31")
    llm._build_engine("ouro-tiny")
    assert seen["name"] == "ouro-tiny" and seen["kv_pool_blocks"] == 31


def test_physics_counts_passes_and_planes(params):
    from tpu9.benchsuite.physics import decode_byte_counts
    once = replace(TINY, loop_steps=1)
    a = decode_byte_counts(params, TINY, batch=2, mean_ctx=64)
    b = decode_byte_counts(params, once, batch=2, mean_ctx=64)
    layers = sum(x.size * x.dtype.itemsize
                 for x in jax.tree_util.tree_leaves(params["layers"]))
    assert a["streamed_bytes"] - b["streamed_bytes"] == (R - 1) * layers
    assert a["kv_bytes_per_step"] == R * b["kv_bytes_per_step"]
    assert a["attn_flops_per_step"] == R * b["attn_flops_per_step"]
    matmuls = sum(x.size for x in jax.tree_util.tree_leaves(params["layers"])
                  if x.ndim >= 2)
    assert a["matmul_params"] - b["matmul_params"] == (R - 1) * matmuls
    engine = InferenceEngine(params, TINY, _ecfg())
    resident = sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(params))
    assert engine._phys_bytes_per_token_per_chip == resident + (R - 1) * layers


def test_weights_file_quantizer_and_sharding_keep_the_new_leaves(params,
                                                                 tmp_path):
    from jax.sharding import PartitionSpec as P
    from tpu9.ops.quant import init_quantized_decoder, quantize_decoder
    from tpu9.parallel import decoder_param_specs
    from tpu9.serving import weights as wfmt
    wfmt.save_params(params, str(tmp_path / "w"))
    back = wfmt.load_params(str(tmp_path / "w"))
    flat_a = jax.tree_util.tree_leaves_with_path(params)
    flat_b = jax.tree_util.tree_leaves_with_path(back)
    assert [k for k, _ in flat_a] == [k for k, _ in flat_b]
    for (_, x), (_, y) in zip(flat_a, flat_b):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert "exit_gate" in back and "attn_post_norm" in back["layers"][0]
    new = ("attn_post_norm", "mlp_post_norm")
    for tree in (quantize_decoder(params),
                 init_quantized_decoder(jax.random.PRNGKey(0), TINY)):
        assert set(tree["exit_gate"]) == {"w", "b"}
        assert all(n in layer for layer in tree["layers"] for n in new)
        assert "q" in tree["layers"][0]["wq"]
    specs = decoder_param_specs(params)
    assert specs["exit_gate"] == {"w": P(), "b": P()}
    assert all(specs["layers"][0][n] == P() for n in new)
    # a plain decoder's quantized tree gains nothing
    plain = init_quantized_decoder(jax.random.PRNGKey(0), PLAIN)
    assert "exit_gate" not in plain and new[0] not in plain["layers"][0]


def test_presets_resolve_and_size():
    from tpu9.serving.feasibility import weight_bytes
    from tpu9.serving.presets import resolve_preset
    cfg, quantized = resolve_preset("ouro-2.6b")
    assert not quantized and (cfg.loop_steps, cfg.sandwich_norm,
                              cfg.exit_gate) == (4, True, True)
    assert resolve_preset("ouro-tiny")[0].loop_steps == 2
    # 48 x 51,380,224 + 2 x 49,152 x 2,048 matrix parameters in bf16, the
    # norms (4 a layer + 1) and the gate in float32
    want = (48 * 51_380_224 + 2 * 49_152 * 2_048) * 2 \
        + (48 * 4 + 1) * 2_048 * 4 + (2_048 + 1) * 4
    assert weight_bytes(cfg, False) == want
