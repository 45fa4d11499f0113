"""The Kimi-K2 family of the benchmark (``families/kimi.py``): its cost
functions against hand arithmetic at the published sizes (latent attention's
projections, the latent rows a step reads, the held experts a batch touches,
the blocked prefill's operations), its refusals, the configuration and mix
files of its cell, the rehearsal walk of the cell — a prefix hit over latent
pages among its probes — and the readers the cell brings on synthetic
contexts."""

import json
import math
import os
import subprocess
import sys

import pytest

from benchmark import device_scopes, manifest, readers, scope_events

M = manifest.load()
CONFIG = "kimi-k2.6-l6-ep32"
CELL = "kimi-docs"
BODY = manifest.load_config(M, CONFIG)
FAMILY = manifest.family(BODY)
MODEL = FAMILY.model_sizes(BODY)
D, H = 7168, 64
MLA = D * 1536 + 1536 * H * 192 + D * 576 + 512 * H * 256 + H * 128 * D
DENSE, EXPERT = 3 * D * 18432, 3 * D * 2048
ROUTER, HEAD = D * 384, D * 20480


def rehearsal():
    with open(os.path.join(manifest.HERE, "rehearsal", f"{CONFIG}.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# cost functions at the published sizes
# ---------------------------------------------------------------------------

def test_sizes_are_the_published_ones():
    assert (MODEL["num_hidden_layers"], MODEL["hidden_size"],
            MODEL["intermediate_size"], MODEL["num_attention_heads"],
            MODEL["num_key_value_heads"], MODEL["vocab_size"]) == \
        (6, 7168, 18432, 64, 64, 20480)
    assert (MODEL["q_lora_rank"], MODEL["kv_lora_rank"],
            MODEL["qk_nope_head_dim"], MODEL["qk_rope_head_dim"],
            MODEL["v_head_dim"]) == (1536, 512, 128, 64, 128)
    assert (MODEL["moe_intermediate_size"],
            MODEL["moe_shared_expert_intermediate_size"],
            MODEL["num_experts_per_tok"], MODEL["routed_scaling_factor"],
            MODEL["first_k_dense_replace"]) == (2048, 2048, 8, 2.827, 1)
    assert MODEL["rope_theta"] == 50000 and MODEL["rms_norm_eps"] == 1e-5
    assert MODEL["rope_scaling"]["factor"] == 64
    # the chip's share: 12 held of 384 routed, from expert 0; an eighth of
    # the vocabulary
    assert MODEL["experts_routed"] == 384
    assert MODEL["experts_held"] == [0, 12]
    assert MODEL["vocab_size_published"] == 163840
    assert FAMILY.layer_kinds(MODEL) == [("mla", "dense")] \
        + [("mla", "experts")] * 5


def test_the_parts_are_the_issues_arithmetic():
    p = FAMILY.matmul_params(MODEL)
    assert p == {"mla": MLA, "dense": DENSE, "expert": EXPERT,
                 "shared": EXPERT, "router": ROUTER, "head": HEAD}
    assert round(MLA / 1e6, 1) == 101.1 and round(EXPERT / 1e6, 1) == 44.0
    # an expert layer here: 676.4 M = 1.353 GB; layer 0: 497.5 M
    layer = MLA + 12 * EXPERT + EXPERT + ROUTER
    assert round(layer / 1e6, 1) == 676.4
    assert round((MLA + DENSE) / 1e6, 1) == 497.5
    weights = 2 * ((MLA + DENSE) + 5 * layer + 2 * HEAD)
    assert round(weights / 1e9, 2) == 8.35


def test_sixteen_lanes_touch_a_third_of_the_held_experts():
    touched = FAMILY.experts_touched(MODEL, 16)
    assert touched == pytest.approx(12 * (1 - (1 - 8 / 384) ** 16))
    assert 3.3 < touched < 3.6
    # the deployment's 512 tokens a step reach all 12
    assert FAMILY.experts_touched(MODEL, 512) > 11.99
    assert FAMILY.experts_touched(MODEL, 0) == 0


def test_a_decode_step_moves_weights_and_latents():
    rows = 16 * 31000.0
    got = FAMILY.decode_bytes_per_step(MODEL, 16, rows)
    touched = FAMILY.experts_touched(MODEL, 16)
    want = HEAD * 2 + D * 4
    want += 6 * (2 * D * 4 + MLA * 2 + (1536 + 512) * 4 + 1152 * rows)
    want += DENSE * 2
    want += 5 * ((touched * EXPERT + EXPERT) * 2 + (ROUTER + 384) * 4)
    assert got == pytest.approx(want)
    # 3.4 GB of latents beside the weights a step passes through
    assert 6 * 1152 * rows == pytest.approx(3.43e9, rel=0.01)
    assert FAMILY.decode_bytes_per_step(MODEL, 16, 0) == \
        pytest.approx(want - 6 * 1152 * rows)


def test_a_prompt_token_passes_a_quarter_of_an_expert():
    got = FAMILY.prefill_flops_per_token(MODEL)
    want = 2.0 * (6 * MLA + DENSE + 5 * (0.25 * EXPERT + EXPERT + ROUTER))
    assert got == pytest.approx(want)


def test_the_kernels_are_priced_by_what_they_must_do():
    rows = 500000.0
    for name in (FAMILY.STEP_MARKER, "attn.mla.core", "attn.mla"):
        cost = FAMILY.kernel_cost(name, MODEL, BODY["engine"], 16, rows)
        assert cost == {"bytes": 6 * 1152 * rows,
                        "flops": 6 * 2.0 * 64 * (1024 + 64) * rows}
    # one chunk of 512 queries behind 30,208 cached rows
    at, width = 30208, 512
    pairs = width * at + width * (width + 1) // 2
    cost = FAMILY.kernel_cost(FAMILY.PREFILL_KERNEL, MODEL, BODY["engine"],
                              pairs, at + width)
    assert cost["bytes"] == 6 * 1152 * (at + width)
    assert cost["flops"] == pytest.approx(
        6 * 2.0 * 64 * (512 * 256 * (at + width) + 320 * pairs))
    assert 6.5e12 < cost["flops"] < 7.5e12        # the issue's 7 TFLOP
    assert FAMILY.kernel_cost("paged_decode_attention", MODEL,
                              BODY["engine"], 16, rows) is None
    assert FAMILY.marker_calls_per_step(MODEL) == 6


def test_scope_groups_hold_the_new_scope():
    groups = FAMILY.SCOPE_GROUPS
    assert sorted(groups) == ["attention", "ffn", "kv_pool"]
    assert {"attn.mla.q", "attn.mla.absorb", "attn.mla.core",
            "attn.core"} == set(groups["attention"])
    assert "moe.shared" in groups["ffn"]
    from tpu9.models.hybrid import HYBRID_SCOPES, MLA_QUERY_SCOPES
    from tpu9.models.transformer import DEVICE_SCOPES
    named = {s for scopes in groups.values() for s in scopes}
    assert named <= set(DEVICE_SCOPES + HYBRID_SCOPES + MLA_QUERY_SCOPES)
    assert FAMILY.MLA_SCOPES == ("attn.mla.absorb", "attn.mla.core")


def test_the_programs_config_carries_the_descriptors():
    import jax.numpy as jnp
    cfg = FAMILY.program_config(MODEL)
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.hidden_dim,
            cfg.vocab_size, cfg.max_seq_len) == \
        (7168, 6, 64, 18432, 20480, 262144)
    assert cfg.layer_group == 1 and cfg.layers_of("mla") == tuple(range(6))
    assert not cfg.layers_of("kda") and cfg.kv_layers == 6
    assert cfg.kv_row == ((1, 512), (1, 64))
    assert (cfg.mla_q_latent, cfg.mla_out_gate) == (1536, False)
    assert cfg.mla_mscale == pytest.approx(0.1 * math.log(64) + 1)
    assert cfg.rope_yarn == (64.0, 4096, 32.0, 1.0)
    assert (cfg.n_experts, cfg.moe_routed, cfg.moe_held_first,
            cfg.moe_top_k, cfg.moe_groups, cfg.moe_shared_dim,
            cfg.moe_hidden_dim, cfg.moe_dense_layers) == \
        (12, 384, 0, 8, 0, 2048, 2048, 1)
    assert cfg.moe_score == "sigmoid" and cfg.moe_select_bias
    assert cfg.moe_gate_scale == 2.827 and cfg.dtype == jnp.bfloat16
    from tpu9.models import kvstate
    assert kvstate.block_bytes(cfg, 128) == 884736
    # the family connected the reference to the program's routing record
    from benchmark.reference import served_routing
    from tpu9.serving import routed_experts
    assert served_routing.provider is routed_experts.records


# ---------------------------------------------------------------------------
# refusals
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("hidden_act", "gelu"),
    ("tie_word_embeddings", True), ("moe_layer_freq", 2),
    ("n_shared_experts", 2), ("n_group", 8), ("topk_group", 4),
    ("norm_topk_prob", False), ("scoring_func", "softmax"),
    ("topk_method", "greedy"), ("num_nextn_predict_layers", 1),
    ("model_type", "deepseek_v3"), ("num_key_value_heads", 8),
    ("first_k_dense_replace", 0), ("first_k_dense_replace", 6),
    ("rope_scaling", None),
    ("rope_scaling", dict(BODY["rope_scaling"], type="linear")),
    ("rope_scaling", dict(BODY["rope_scaling"], mscale=0.707)),
    ("rope_scaling", {"factor": 64, "type": "yarn"}),
    ("sliding_window", 4096), ("layer_group_size", 6),
    ("vision_config", {"depth": 27}), ("num_local_experts", 8),
    ("deployment", dict(BODY["deployment"], vocab_rows=[0, 20000])),
    ("deployment", dict(BODY["deployment"], vocab_rows=[20480, 20480])),
    ("deployment", dict(BODY["deployment"], chips_sharing_a_layer=16)),
    ("deployment", dict(BODY["deployment"], vocab_shards=5))])
def test_a_key_the_family_does_not_build_is_refused(key, value):
    with pytest.raises(ValueError):
        FAMILY.model_sizes(dict(BODY, **{key: value}))


@pytest.mark.parametrize("key,value", [
    ("rotary_form", "interleaved"), ("torch_dtype", "float16"),
    ("norms", "post-norm"), ("shared_expert_width", "none"),
    ("seeded_weights", "zeros"), ("output_gate", "sigmoid")])
def test_an_assumption_the_family_does_not_build_is_refused(key, value):
    assumed = dict(BODY["assumed"], **{key: {"value": value}})
    with pytest.raises(ValueError):
        FAMILY.model_sizes(dict(BODY, assumed=assumed))


def test_a_missing_assumption_is_refused():
    assumed = {k: v for k, v in BODY["assumed"].items() if k != "norms"}
    with pytest.raises(ValueError, match="exactly"):
        FAMILY.model_sizes(dict(BODY, assumed=assumed))


def test_a_program_without_the_descriptors_is_refused_before_any_start(
        monkeypatch):
    """On a tree whose ``DecoderConfig`` has no query latent and no YaRN
    (the parent commit) the cell fails at once, in the harness's own
    process: no stack is started, no chip is opened."""
    from benchmark.families import looped
    monkeypatch.setattr(looped, "_program_fields",
                        lambda: {"vocab_size", "dim", "layer_group",
                                 "mla_latent", "moe_routed", "moe_score",
                                 "moe_held_first", "moe_shared_dim"})
    with pytest.raises(ValueError, match="cannot run latent attention in "
                                         "every layer"):
        FAMILY.model_sizes(BODY)


def test_the_parent_commit_fails_at_once_on_the_cell():
    """The parent's ``transformer.py`` (its ``DecoderConfig`` fields, read
    as the family reads them) lacks the descriptors: the driver's try of
    the new cell on the parent ends in ``model_sizes``."""
    from benchmark.families import looped
    assert {"mla_q_latent", "mla_out_gate", "mla_mscale", "rope_yarn"} \
        <= looped._program_fields()


def test_the_other_families_refuse_the_kimi_keys():
    from benchmark.families import decoder, eva, ling, looped
    for family in (decoder, eva, ling, looped):
        with pytest.raises((ValueError, KeyError)):
            family.model_sizes(dict(BODY, family=family.__name__))


# ---------------------------------------------------------------------------
# the files of the cell
# ---------------------------------------------------------------------------

def test_the_configuration_file_states_what_it_runs():
    entry = manifest.config_entry(M, CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert set(BODY["reduced"]) == set(entry["reduced"])
    for key, published, here in (("num_hidden_layers", 61, 6),
                                 ("n_routed_experts", 384, 12)):
        cut = BODY["reduced"][key]
        assert (cut["published"], cut["here"]) == (published, here)
        assert BODY[key] == here and cut["why"]
    assert sorted(BODY["assumed"]) == sorted(FAMILY.ASSUMED)
    for stated in BODY["assumed"].values():
        assert stated["why"]
    share = BODY["deployment"]
    assert (share["chips_sharing_a_layer"], share["chip"],
            share["n_routed_experts_published"], share["vocab_shards"],
            share["vocab_rows"]) == (32, 0, 384, 8, [0, 20480])
    assert BODY["vocab_size"] == 163840 == 8 * 20480
    knobs = BODY["engine"]
    assert (knobs["max_batch"], knobs["max_seq_len"], knobs["kv_block_size"],
            knobs["prefill_chunk"], knobs["admit_group_chunks"],
            knobs["decode_steps"], knobs["topology"]) == \
        (16, 57344, 128, 512, 4, [1, 8], "1x1")
    # the pool holds the documents and every turn of the window; the prefix
    # budget counts an entry's pages whoever shares them
    assert knobs["kv_pool_blocks"] * 128 >= 429000 + 16 * 40 * 256
    assert knobs["prefix_cache_blocks"] >= 16 * 57344 // 128
    # resident: weights, pool, scratch — three quarters of the chip
    resident = 8.35e9 + (knobs["kv_pool_blocks"] + 1) * 884736 \
        + 57344 * 6912
    assert 0.74 < resident / 16.909e9 < 0.80


def test_the_tolerance_lies_between_the_sound_readings_and_the_controls():
    tol, got = BODY["correct_tolerance_logit"], \
        BODY["correct_tolerance_readings"]
    assert got["sound_largest"] < tol < got["int8_weights_median"]
    # the worst of 96 margins spreads 13 x between seeds of one precision:
    # no limit on it fails int8 on every seed, and the file says on how many
    assert got["int8_weights_seeds_under_the_limit"] <= 3
    assert got["int8_weights_smallest"] > got["sound_median"]
    for control in ("no_mscale", "plain_rope", "no_q_norm"):
        assert got[f"{control}_smallest"] > 10 * tol, control
    for control in ("int8_weights", "no_mscale", "plain_rope", "no_q_norm"):
        assert control in BODY["correct_tolerance_why"]
    assert FAMILY.model_sizes(BODY)["routing_tie"] == \
        BODY["correct_routing_tie"] > 0


def test_the_configuration_holds_every_number_of_the_catalog_entry():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Kimi-K2.6")
    assert BODY["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in BODY["reduced"]:
            assert BODY["reduced"][key]["published"] == value
            assert BODY[key] == BODY["reduced"][key]["here"]
        else:
            assert BODY[key] == value, key


def test_the_mix_is_the_one_the_cell_states():
    cell = manifest.cell(M, CELL)
    assert (cell["config"], cell["chips"], cell["traffic"]) == \
        (CONFIG, 1, CELL)
    mix = manifest.load_traffic(cell["traffic"])
    assert mix["kind"] == "closed_sessions"
    assert (mix["sessions"], mix["max_turns"], mix["stagger_s"]) == \
        (16, 40, 0.5)
    assert mix["sessions"] == BODY["engine"]["max_batch"]
    (cls,) = mix["classes"]
    assert cls["judged"] and cls["share"] == 1.0
    assert cls["context_tokens"] == {"dist": "loguniform", "lo": 16384,
                                     "hi": 40960}
    assert cls["turn_tokens"] == {"dist": "fixed", "value": 128}
    assert cls["output_tokens"] == {"dist": "fixed", "value": 128}
    # every turn fits the cache, its worst case included
    assert 40960 + 40 * 256 + 9 < BODY["engine"]["max_seq_len"]
    plan = manifest.traffic_kind("closed_sessions").plan(
        mix, 2 ** 31 + 52, 45.0, MODEL["vocab_size"])
    docs = sorted(len(s["document"]) for s in plan["sessions"])
    assert len(docs) == 16 and 16384 < docs[0] and docs[-1] < 40960
    assert 425000 < sum(docs) < 433000
    assert max(max(s["document"]) for s in plan["sessions"]) < 20480
    names = [m["name"] for m in manifest.cell_metrics(M, CELL, "end_to_end")]
    assert names == ["tpot_p50_ms", "setup_s"]
    layer = {m["name"] for m in manifest.cell_metrics(M, CELL, "per_layer")}
    new = {"latent_attn_bw_share": ("kernels", "device_trace", "higher"),
           "mla_prefill_flops_share": ("kernels", "device_trace", "higher"),
           "latent_rows_per_step": ("model step", "program_counter", "lower"),
           "prefix_rows_reused_share": ("KV pool", "program_counter",
                                        "higher"),
           "ttft_p50_ms.kimi-docs": ("client", "host_clock", "lower")}
    # (not ``mla_attn_share`` and the two routing counters' metrics, which
    # ISSUE 52 also named: ``test_bench_ling.py`` pins their lists to its
    # own cell, and an accepted test is not this PR's to edit)
    joined = {"prefill_ms_per_ktok",
              "engine_admit_ms", "first_token_hold_ms", "stream_lag_ms",
              "gateway_pre_forward_ms", "runner_door_ms", "runner_ingest_ms",
              "gateway_first_relay_ms", "client_hop_ms"}
    assert set(new) | joined | {"decode_bw_share", "decode_attention_share",
                                "decode_ffn_share", "decode_step_ms",
                                "host_ms_per_window"} <= layer
    assert not {"prefix_hit_share", "prefill_flops_share",
                "paged_attn_bw_share", "kda_state_share",
                "collective_share", "moe_touched_share"} & layer
    for name, (where, source, better) in new.items():
        entry = next(m for m in M["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["layer"] == where
        assert (entry["source"], entry["better"]) == (source, better)
        assert entry["moves"] == "tpot_p50_ms"
        assert os.path.exists(manifest.layer_reader_path(name))
    for name in joined:
        entry = next(m for m in M["per_layer"] if m["name"] == name)
        assert entry["workloads"][-1] == CELL
    out = next(m for m in M["end_to_end"] if m["name"] == "out_tok_s")
    assert CELL not in out.get("workloads", [])


def test_the_rehearsal_sizes_are_the_unit_tests():
    reh = rehearsal()
    config = dict(BODY, **reh["model"])
    config["assumed"] = dict(BODY["assumed"], **reh["assumed"])
    model = FAMILY.model_sizes(config)
    cfg = FAMILY.program_config(model)
    assert (cfg.dim, cfg.hidden_dim, cfg.n_heads, cfg.n_layers,
            cfg.vocab_size, cfg.layer_group, cfg.mla_latent, cfg.mla_rope,
            cfg.mla_q_latent, cfg.n_experts, cfg.moe_routed, cfg.moe_top_k,
            cfg.moe_groups) == \
        (128, 256, 4, 3, 512, 1, 64, 16, 48, 4, 16, 4, 0)
    assert cfg.rope_yarn == (8.0, 64, 32.0, 1.0)
    assert model["experts_held"] == [0, 4]


def test_rehearsal_walks_the_cell():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "run.py"), "--workload",
         CELL, "--seed", str(2 ** 31 + 5252), "--seconds", "8", "--trace",
         "1", "--rehearse"], cwd=manifest.ROOT, env=env, capture_output=True,
        text=True, timeout=420)
    assert proc.returncode == 3, proc.stderr[-3000:]
    infos = [json.loads(ln)["info"] for ln in proc.stdout.splitlines()
             if ln.startswith('{"info"')]
    (line,) = [i["rehearsal_line"] for i in infos if "rehearsal_line" in i]
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    # the counters' metrics are read on the CPU too; the trace's are not.
    # Every turn sends its whole history: all but the suffix is reused
    assert got["prefix_rows_reused_share"]["value"] > 80
    assert got["latent_rows_per_step"]["value"] > 100
    for name in ("latent_attn_bw_share", "mla_prefill_flops_share"):
        assert name not in got
    if line["attempted"]:
        assert "ttft_p50_ms.kimi-docs" in got
    assert got["post_warmup_compiles"]["value"] == 0
    ref = next(i["reference"] for i in infos if "reference" in i)
    assert ref["tokens_checked"] == 96
    # the longest probe is five chunks and a tail
    assert ref["seq_len"] > 5 * 32
    assert ref["worst_margin"] <= BODY["correct_tolerance_logit"]


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

def context(**over):
    ctx = {"health0": {"latent_rows_attended": 1000, "latent_decode_steps": 10,
                       "prefill_rows_attended": 0,
                       "prefill_pairs_attended": 0, "admit_chunks": 16,
                       "prefix_rows_reused": 100, "prompt_rows_admitted": 500,
                       "tokens_generated": 100, "decode_steps": 10,
                       "latency": {"ttft_count": 4}},
           "health1": {"latent_rows_attended": 1000 + 400 * 500000,
                       "latent_decode_steps": 410,
                       "prefill_rows_attended": 100 * 30720,
                       "prefill_pairs_attended": 100 * (
                           512 * 30208 + 512 * 513 // 2),
                       "admit_chunks": 116,
                       "prefix_rows_reused": 100 + 98 * 30000,
                       "prompt_rows_admitted": 500 + 100 * 30000,
                       "tokens_generated": 6596, "decode_steps": 410,
                       "latency": {"ttft_count": 100}},
           "health_ready": {}, "trace": None, "family": FAMILY,
           "model": MODEL, "engine": BODY["engine"], "records": [],
           "seconds": 45.0, "chips": 1, "cell": CELL,
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    ctx.update(over)
    return ctx


def test_the_counter_readers_read_the_counters():
    rows = manifest.layer_reader("latent_rows_per_step").read
    reused = manifest.layer_reader("prefix_rows_reused_share").read
    assert rows(context()) == 500000.0
    assert reused(context()) == pytest.approx(98.0)
    # an engine without the counters (every other family): left out
    bare = {"tokens_generated": 5, "decode_steps": 1}
    assert rows(context(health0=bare, health1=bare)) is None
    assert reused(context(health0=bare, health1=bare)) is None
    # no decode step, no admission inside the window
    same = context()["health1"]
    assert rows(context(health0=same)) is None
    assert reused(context(health0=same)) is None


def test_the_decode_kernels_share_of_the_bandwidth(monkeypatch):
    read = manifest.layer_reader("latent_attn_bw_share").read
    seconds = {"attn.mla.core": 0.8, "attn.mla.absorb": 0.2, "ffn": 1.0}
    monkeypatch.setattr(device_scopes, "decode_seconds", lambda c: seconds)
    ctx = context(trace={"programs": {"jit_decode": {"steps": 160}},
                         "file": "x"})
    # 500,000 rows a step x 6 layers x 1,152 B in 5 ms a step
    want = 100.0 * (6 * 1152 * 500000) / (0.8 / 160) / 819e9
    assert read(ctx) == pytest.approx(want) and 84 < want < 85
    assert read(context(trace={"programs": {}, "file": "x"})) is None
    monkeypatch.setattr(device_scopes, "decode_seconds",
                        lambda c: {"ffn": 1.0, "attn.core": 1.0})
    assert read(ctx) is None
    bare = {"tokens_generated": 5, "decode_steps": 1}
    monkeypatch.setattr(device_scopes, "decode_seconds", lambda c: seconds)
    assert read(dict(ctx, health0=bare, health1=bare)) is None


def test_the_prefills_share_of_the_peak(monkeypatch):
    read = manifest.layer_reader("mla_prefill_flops_share").read
    # 20 traced chunks: 10,240 tokens; 0.9 s under the two scopes
    monkeypatch.setattr(readers, "prefill_time_and_tokens",
                        lambda c: (2.0, 20 * 512))
    from benchmark import host_phases
    monkeypatch.setattr(host_phases, "load",
                        lambda path: {"ops": [], "modules": []})
    seen = {}

    def under(ops, modules, maps, scopes):
        seen.update(maps=maps, scopes=scopes)
        return {"seconds": 0.9, "trips": 3}
    monkeypatch.setattr(scope_events, "under", under)
    maps = {"decode_1": {"attn.mla.core": ["a"]},
            "chunk_512": {"attn.mla.core": ["b"]},
            "chunkgroup_4": {"attn.mla.core": ["c"]}}
    ctx = context(trace={"file": "x", "programs": {}},
                  health_ready={"device_scopes": maps})
    # a chunk's mean over the window (100 chunks, each 512 queries behind
    # 30,208 rows) times the 20 traced
    pairs = 512 * 30208 + 512 * 513 // 2
    flops = 20 * 6 * 2.0 * 64 * (512 * 256 * 30720 + 320 * pairs)
    want = 100.0 * flops / 0.9 / 197e12
    assert read(ctx) == pytest.approx(want) and 70 < want < 90
    assert sorted(seen["maps"]) == ["chunk_512", "chunkgroup_4"]
    assert seen["scopes"] == FAMILY.MLA_SCOPES
    # a trace without prefill runs; a family without the kernel; an engine
    # without the counters
    monkeypatch.setattr(readers, "prefill_time_and_tokens",
                        lambda c: (None, 0))
    assert read(ctx) is None
    monkeypatch.setattr(readers, "prefill_time_and_tokens",
                        lambda c: (2.0, 20 * 512))
    from benchmark.families import decoder
    assert read(dict(ctx, family=decoder)) is None
    bare = {"tokens_generated": 5, "decode_steps": 1}
    assert read(dict(ctx, health0=bare, health1=bare)) is None
    monkeypatch.setattr(scope_events, "under", lambda *a: {})
    assert read(ctx) is None


def test_the_demoted_latency_reads_the_records():
    read = manifest.layer_reader(f"ttft_p50_ms.{CELL}").read
    records = [{"ok": True, "judged": True, "due_s": float(i),
                "token_s": [i + 0.5 + i, i + 9.0 + i]} for i in range(10)]
    assert read(context(records=records)) == pytest.approx(5000.0)
