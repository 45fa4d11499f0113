"""The hybrid Mamba-2 / LatentMoE family of the benchmark
(``families/nemotronh.py``): its cost functions against hand arithmetic at
the published sizes (the mixer's, the attention's and the expert layer's
parts, the state a lane reads and writes a step, the touched experts' two
matrices), its refusals, the configuration and mix files of its cell, the
rehearsal walk of the cell and the readers the cell brings on synthetic
contexts."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import device_scopes, manifest

M = manifest.load()
CONFIG = "nemotron-3-super-l11-ep4"
CELL = "nemotron-agents"
BODY = manifest.load_config(M, CONFIG)
FAMILY = manifest.family(BODY)
MODEL = FAMILY.model_sizes(BODY)
D, INNER, WIDTH, H, LATENT = 4096, 8192, 10240, 128, 1024
SSM = D * (INNER + WIDTH + H) + INNER * D
ATTN = 2 * D * 32 * 128 + 2 * D * 2 * 128
EXPERT, PROJ, SHARED = 2 * LATENT * 2688, 2 * D * LATENT, 2 * D * 5376
ROUTER, HEAD = D * 512, D * 32768
VECTORS = 5 * WIDTH + 3 * H + INNER
STATE = 128 * 64 * 128
PATTERN = "MEMEMEM*EME"


def rehearsal():
    with open(os.path.join(manifest.HERE, "rehearsal", f"{CONFIG}.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# cost functions at the published sizes
# ---------------------------------------------------------------------------

def test_sizes_are_the_published_ones():
    assert (MODEL["num_hidden_layers"], MODEL["hidden_size"],
            MODEL["num_attention_heads"], MODEL["num_key_value_heads"],
            MODEL["head_dim"], MODEL["vocab_size"],
            MODEL["vocab_size_published"]) == \
        (11, 4096, 32, 2, 128, 32768, 131072)
    assert (MODEL["mamba_num_heads"], MODEL["mamba_head_dim"],
            MODEL["ssm_state_size"], MODEL["n_groups"],
            MODEL["conv_kernel"], MODEL["expand"], MODEL["chunk_size"]) == \
        (128, 64, 128, 8, 4, 2, 128)
    assert (MODEL["experts_routed"], MODEL["experts_held"],
            MODEL["num_experts_per_tok"], MODEL["moe_intermediate_size"],
            MODEL["moe_latent_size"],
            MODEL["moe_shared_expert_intermediate_size"],
            MODEL["routed_scaling_factor"]) == \
        (512, [0, 128], 22, 2688, 1024, 5376, 5.0)
    kinds = FAMILY.layer_kinds(MODEL)
    assert kinds == [FAMILY.LAYER_KINDS[c] for c in PATTERN]
    assert [a for a, _ in kinds].count("ssm") == 5
    assert [f for _, f in kinds].count("experts") == 5
    assert FAMILY.marker_calls_per_step(MODEL) == 1


def test_the_parts_are_the_issues_arithmetic():
    p = FAMILY.matmul_params(MODEL)
    assert p == {"ssm": SSM, "full": ATTN, "expert": EXPERT, "latent": PROJ,
                 "shared": SHARED, "router": ROUTER, "head": HEAD}
    # an M layer 109.6 M, the * layer 35.7 M, an expert 5.5 M (11.0 MB), an
    # E layer's share 759 M, the head over a quarter of the vocabulary 134 M
    assert round((SSM + VECTORS) / 1e6, 1) == 109.6
    assert round(ATTN / 1e6, 1) == 35.7
    assert round(EXPERT / 1e6, 1) == 5.5 and round(EXPERT * 2 / 1e6) == 11
    share = 128 * EXPERT + ROUTER + PROJ + SHARED
    assert round(share / 1e6) == 759
    whole = 512 * EXPERT + ROUTER + PROJ + SHARED
    assert round(whole / 1e9, 2) == 2.87
    total = 5 * (SSM + VECTORS) + ATTN + 5 * (share + 512) + 2 * HEAD \
        + 12 * D
    assert round(total * 2 / 1e9, 1) == 9.3
    assert FAMILY.ssm_vector_params(MODEL) == VECTORS
    assert FAMILY.conv_width(MODEL) == WIDTH
    # a lane's state: 5 planes of a float32 [128, 64, 128] and a 3 x 10240
    # bf16 tail = 21.3 MB
    assert FAMILY.state_bytes_per_lane(MODEL) == STATE * 4 + 3 * WIDTH * 2
    assert round(5 * FAMILY.state_bytes_per_lane(MODEL) / 1e6, 1) == 21.3
    assert round(STATE * 4 / 1e6, 2) == 4.19


def test_a_decode_step_moves_weights_state_and_rows():
    fixed = (5 * SSM + ATTN + 5 * (PROJ + SHARED) + HEAD) * 2 \
        + (5 * VECTORS + 12 * D + 5 * (ROUTER + 512)) * 4
    assert FAMILY.decode_bytes_per_step(MODEL, 0, 0) == fixed
    assert FAMILY.experts_touched(MODEL, 0) == 0
    # 45 lanes x 22 picks of 512 miss a held expert with 0.957 ** 45
    touched = 128 * (1 - (1 - 22 / 512) ** 45)
    assert FAMILY.experts_touched(MODEL, 45) == pytest.approx(touched)
    assert 109 < touched < 111
    lane = 5 * 2 * (STATE * 4 + 3 * WIDTH * 2)
    got = FAMILY.decode_bytes_per_step(MODEL, 45, 45 * 1200)
    assert got == pytest.approx(fixed + 5 * touched * EXPERT * 2 + 45 * lane
                                + 45 * 1200 * 1024)
    # the touched experts are most of a step's bytes, the state a fifth
    assert 0.55 < 5 * touched * EXPERT * 2 / got < 0.65
    assert 0.15 < 45 * lane / got < 0.25


def test_a_prompt_token_passes_its_held_picks_and_what_every_chip_computes():
    assert FAMILY.prefill_flops_per_token(MODEL) == 2.0 * (
        5 * SSM + ATTN + 5 * (5.5 * EXPERT + PROJ + SHARED + ROUTER))


def test_the_kernels_are_priced_by_what_they_must_do():
    engine = BODY["engine"]
    for name in ("ssm_state_step", "attn.ssm.state"):
        cost = FAMILY.kernel_cost(name, MODEL, engine, 45, 0)
        assert cost == {"bytes": 5 * 45 * 2 * STATE * 4,
                        "flops": 5 * 45 * 5.0 * STATE}
    paged = FAMILY.kernel_cost("paged_decode_attention", MODEL, engine, 45,
                               54000)
    assert paged == {"bytes": 1024 * 54000,
                     "flops": 4.0 * 32 * 128 * 54000}
    # the held experts' kernel: the touched experts' two matrices a layer,
    # 45 rows in (bf16) and out (float32) in the latent
    held = FAMILY.kernel_cost("held_ffn", MODEL, engine, 45, 0, touched=100)
    assert held["bytes"] == 5 * (100 * EXPERT * 2 + 45 * LATENT * 6)
    assert held["flops"] == 5 * 100 * 45 * 2.0 * EXPERT
    assert round(held["bytes"] / 1e9, 2) == 5.51
    uniform = FAMILY.kernel_cost("held_ffn", MODEL, engine, 45, 0)
    assert uniform["bytes"] == pytest.approx(5 * (
        FAMILY.experts_touched(MODEL, 45) * EXPERT * 2 + 45 * LATENT * 6))
    assert FAMILY.kernel_cost("kda_state_step", MODEL, engine, 45, 0) is None


def test_scope_groups_hold_the_new_scopes_inside_ffn():
    from tpu9.models.hybrid import HYBRID_SCOPES
    from tpu9.models.ssm import SSM_SCOPES
    from tpu9.models.transformer import DEVICE_SCOPES, LATENT_MOE_SCOPES
    from tpu9.ops import ssd
    groups = FAMILY.SCOPE_GROUPS
    assert set(groups) == {"kv_pool", "attention", "ffn"}
    assert set(SSM_SCOPES) <= set(groups["attention"])
    assert set(LATENT_MOE_SCOPES) | {"moe.shared"} <= set(groups["ffn"])
    assert set(FAMILY.MOE_SCOPES) <= set(groups["ffn"])
    assert set(FAMILY.MOE_SCOPES) <= set(
        DEVICE_SCOPES + HYBRID_SCOPES + LATENT_MOE_SCOPES)
    assert FAMILY.SSM_STEP_KERNEL == ssd.STEP_KERNEL
    assert FAMILY.STEP_MARKER == "paged_decode_attention"
    assert FAMILY.EXPERT_STEP_KERNEL == "held_ffn"


def test_the_programs_config_carries_the_descriptors():
    import jax.numpy as jnp
    cfg = FAMILY.program_config(MODEL)
    assert cfg.layer_pattern == tuple(a for a, _ in FAMILY.layer_kinds(MODEL))
    assert cfg.ffn_pattern == tuple(f for _, f in FAMILY.layer_kinds(MODEL))
    assert cfg.layer_group == 0 and cfg.moe_dense_layers == 0
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.n_layers,
            cfg.vocab_size) == (4096, 32, 2, 128, 11, 32768)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv, cfg.ssm_norm_groups) == (128, 64, 128, 8, 4, 8)
    assert (cfg.n_experts, cfg.moe_routed, cfg.moe_held_first, cfg.moe_top_k,
            cfg.moe_hidden_dim, cfg.moe_latent_dim, cfg.moe_shared_dim,
            cfg.moe_gated, cfg.act) == \
        (128, 512, 0, 22, 2688, 1024, 5376, False, "relu2")
    assert (cfg.moe_score, cfg.moe_select_bias, cfg.moe_groups,
            cfg.moe_renormalise, cfg.moe_gate_scale) == \
        ("sigmoid", True, 0, True, 5.0)
    assert (cfg.rope, cfg.attn_scale, cfg.embed_mult, cfg.residual_mult,
            cfg.logit_div, cfg.tie_embeddings) == \
        (False, 0.0, 1.0, 1.0, 1.0, False)
    assert cfg.dtype == jnp.bfloat16 and cfg.norm_eps == 1e-5
    # a KV head of 128 a cache row, a pool one plane deep
    assert cfg.kv_pack == 1
    assert cfg.kv_layers == 1 and cfg.kv_row == ((2, 128), (2, 128))
    assert cfg.lane_state == ("ssm",)
    # the reference is connected to the program's record of its routing
    from benchmark.reference import served_routing
    from tpu9.serving import routed_experts
    assert served_routing.provider is routed_experts.records
    served_routing.provider = None


@pytest.mark.parametrize("key,value", [
    ("num_nextn_predict_layers", 1), ("mlp_hidden_act", "silu"),
    ("mamba_hidden_act", "gelu"), ("n_group", 8), ("topk_group", 4),
    ("n_shared_experts", 2), ("moe_shared_expert_overlap", True),
    ("norm_topk_prob", False), ("sliding_window", 4096),
    ("tie_word_embeddings", True), ("attention_bias", True),
    ("mamba_proj_bias", True), ("use_conv_bias", False), ("use_bias", True),
    ("mlp_bias", True), ("residual_in_fp32", True),
    ("model_type", "granitemoehybrid"), ("norm_eps", 1e-6),
    ("hybrid_override_pattern", "MEMEMEM*EM-"),
    ("hybrid_override_pattern", "MEMEMEMMEME"),
    ("hybrid_override_pattern", "MEMEMEM*EMEM"), ("expand", 4),
    ("n_groups", 3), ("conv_kernel", 1), ("num_experts_per_tok", 0),
    ("score_function", "softmax"), ("n_routed_experts", 64),
])
def test_a_key_or_value_the_family_does_not_build_is_refused(key, value):
    with pytest.raises(ValueError):
        FAMILY.model_sizes(dict(BODY, **{key: value}))


@pytest.mark.parametrize("key,value", [
    ("ssm_state_dtype", "bfloat16"), ("positions", "rotary"),
    ("ssm_norm_groups", "one_group"), ("residual_dtype", "bfloat16"),
    ("dt_limits", "[0.001, 0.1]"), ("router", "softmax"),
    ("torch_dtype", "float16")])
def test_an_assumption_the_family_does_not_build_is_refused(key, value):
    assumed = dict(BODY["assumed"], **{key: {"value": value, "why": "x"}})
    with pytest.raises(ValueError, match="assumed"):
        FAMILY.model_sizes(dict(BODY, assumed=assumed))


def test_a_missing_assumption_is_refused():
    assumed = {k: v for k, v in BODY["assumed"].items() if k != "positions"}
    with pytest.raises(ValueError, match="exactly"):
        FAMILY.model_sizes(dict(BODY, assumed=assumed))


@pytest.mark.parametrize("change", [
    dict(vocab_rows=[0, 65536]), dict(vocab_rows=[32768, 32768]),
    dict(n_routed_experts_published=256), dict(chips_sharing_a_layer=8)])
def test_a_share_that_is_not_the_deployments_is_refused(change):
    with pytest.raises(ValueError, match="deployment"):
        FAMILY.model_sizes(dict(BODY, deployment=dict(BODY["deployment"],
                                                      **change)))


def test_a_program_without_the_descriptors_is_refused_before_any_start(
        monkeypatch):
    """On a tree whose ``DecoderConfig`` has no list of half-layers (the
    parent commit) the cell fails at once, in the harness's own process: no
    stack is started, no chip is opened."""
    from benchmark.families import looped
    fields = looped._program_fields()
    monkeypatch.setattr(looped, "_program_fields", lambda: fields - {
        "ffn_pattern", "ssm_norm_groups", "moe_gated", "moe_latent_dim"})
    with pytest.raises(ValueError, match="cannot run a listed pattern whose "
                                         "layers are one half each"):
        FAMILY.model_sizes(BODY)
    monkeypatch.undo()
    assert set(FAMILY.DESCRIPTORS) <= looped._program_fields()


def test_the_other_families_refuse_the_keys():
    from benchmark.families import (decoder, eva, granitehybrid, kimi, ling,
                                    looped)
    for family in (decoder, eva, granitehybrid, kimi, ling, looped):
        with pytest.raises((ValueError, KeyError)):
            family.model_sizes(dict(BODY, family=family.__name__))


# ---------------------------------------------------------------------------
# the files of the cell
# ---------------------------------------------------------------------------

def test_the_configuration_file_states_what_it_runs():
    entry = manifest.config_entry(M, CONFIG)
    assert sorted(entry["reduced"]) == sorted(BODY["reduced"]) == sorted(
        ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
         "num_nextn_predict_layers"])
    for key, cut in BODY["reduced"].items():
        assert cut["here"] == BODY[key] and cut["why"], key
        assert cut["published"] != cut["here"], key
    assert BODY["reduced"]["num_hidden_layers"]["published"] == 88
    assert BODY["reduced"]["n_routed_experts"]["published"] == 512
    assert BODY["reduced"]["hybrid_override_pattern"]["published"] \
        .startswith(PATTERN)
    assert sorted(BODY["assumed"]) == sorted(FAMILY.ASSUMED)
    for stated in BODY["assumed"].values():
        assert stated["why"]
    share = BODY["deployment"]
    assert (share["chips_sharing_a_layer"], share["chip"],
            share["n_routed_experts_published"], share["vocab_rows"]) == \
        (4, 0, 512, [0, 32768])
    knobs = BODY["engine"]
    assert (knobs["max_batch"], knobs["max_seq_len"], knobs["kv_block_size"],
            knobs["prefill_chunk"], knobs["prefix_cache_blocks"],
            knobs["decode_steps"], knobs["topology"]) == \
        (64, 4096, 128, 512, 0, [1, 8], "1x1")
    # the pool holds the traffic's worst case: every lane at 2,048 + 1,024
    assert knobs["kv_pool_blocks"] >= knobs["max_batch"] * 24
    # resident: weights, the lanes' state, the pool — well over a quarter
    # of the chip
    resident = 9.32e9 + 64 * 5 * (STATE * 4 + 3 * WIDTH * 2) \
        + knobs["kv_pool_blocks"] * 128 * 1024
    assert 0.6 < resident / 16.909e9 < 0.85


def test_the_configuration_holds_every_number_of_the_catalog_entry():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as f:
        entry = next(
            e for e in map(json.loads, f)
            if e["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert BODY["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in BODY["reduced"]:
            assert BODY["reduced"][key]["published"] == value, key
        else:
            assert BODY[key] == value, key
    # every key of the row is one the family builds, at one value or as a
    # size, or names as read by nothing
    named = set(FAMILY.SIZES) | set(FAMILY.READ_BY_NOTHING) \
        | {k for k, _ in FAMILY.ONLY}
    assert set(entry["config"]) <= named


def test_a_program_with_a_narrower_state_is_refused(monkeypatch):
    """One run's margin cannot tell a bfloat16 state; the family does."""
    import jax.numpy as jnp

    from benchmark.reference import served_routing
    from tpu9.models import kvstate
    shapes = kvstate.lane_shapes
    monkeypatch.setattr(kvstate, "lane_shapes", lambda cfg, lanes: {
        k: (shape, jnp.bfloat16) for k, (shape, _) in
        shapes(cfg, lanes).items()})
    with pytest.raises(ValueError, match="keeps the lanes' state in bfloat16"):
        FAMILY.program_config(MODEL)
    served_routing.provider = None


def test_the_mix_is_the_one_the_cell_states():
    cell = manifest.cell(M, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, CELL, 1)
    mix = manifest.load_traffic(CELL)
    assert mix["kind"] == "open_stratified" and mix["arrangement_seed"] == 59
    assert mix["rate_rps"] == pytest.approx(0.7 * mix["knee_rps"], rel=0.03)
    (task,) = mix["classes"]
    assert task["judged"] and task["share"] == 1.0
    assert task["prompt_tokens"] == {"dist": "loguniform", "lo": 256,
                                     "hi": 2048}
    assert task["output_tokens"] == {"dist": "loguniform", "lo": 128,
                                     "hi": 1024}
    assert mix["trace_steps"] > 0 and mix["trace_steps_why"]
    # every new reader lists the cell, and moves the metric the cell reports
    for name in ("latent_ffn_bw_share", "latent_ffn_share",
                 "latent_ffn_rows_per_expert", f"ttft_p50_ms.{CELL}",
                 f"ttft_p90_ms.{CELL}"):
        (entry,) = [m for m in M["per_layer"] if m["name"] == name]
        assert CELL in entry["workloads"]
        assert entry["moves"] == "tpot_p50_ms"
    # the accepted metrics that list the cell: membership, never last place
    for name in ("prefill_flops_share", "paged_attn_bw_share",
                 "gen_late_p99_ms", "engine_queue_wait_ms", "tpot_relay_ms"):
        (entry,) = [m for m in M["per_layer"] if m["name"] == name]
        assert CELL in entry["workloads"]
    e2e = [m["name"] for m in manifest.cell_metrics(M, CELL, "end_to_end")]
    assert sorted(e2e) == ["setup_s", "tpot_p50_ms"]
    # the lists an accepted test pins stay as they are (PERF.md section 7)
    for name in ("ssm_state_bw_share", "moe_held_touched_share"):
        (entry,) = [m for m in M["per_layer"] if m["name"] == name]
        assert CELL not in entry["workloads"]


def test_the_rehearsal_sizes_are_the_same_eleven_layers():
    reh = rehearsal()
    config = dict(BODY, **reh["model"])
    config["assumed"] = dict(BODY["assumed"], **reh["assumed"])
    model = FAMILY.model_sizes(config)
    cfg = FAMILY.program_config(model)
    from benchmark.reference import served_routing
    served_routing.provider = None
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.n_layers,
            cfg.vocab_size, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
            cfg.ssm_groups, cfg.ssm_norm_groups) == \
        (64, 4, 2, 16, 11, 512, 8, 16, 32, 2, 2)
    assert (cfg.n_experts, cfg.moe_routed, cfg.moe_top_k, cfg.moe_hidden_dim,
            cfg.moe_latent_dim, cfg.moe_shared_dim) == (4, 16, 4, 48, 32, 96)
    assert cfg.layer_pattern == tuple(
        FAMILY.LAYER_KINDS[c][0] for c in PATTERN)
    assert cfg.kv_layers == 1 and len(cfg.layers_of("ssm")) == 5


def test_rehearsal_walks_the_cell():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "run.py"), "--workload",
         CELL, "--seed", str(2 ** 31 + 5959), "--seconds", "8", "--trace",
         "1", "--rehearse"], cwd=manifest.ROOT, env=env, capture_output=True,
        text=True, timeout=420)
    assert proc.returncode == 3, proc.stderr[-3000:]
    infos = [json.loads(ln)["info"] for ln in proc.stdout.splitlines()
             if ln.startswith('{"info"')]
    (line,) = [i["rehearsal_line"] for i in infos if "rehearsal_line" in i]
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    # the trace's metrics are not read on the CPU (no device plane)
    for name in ("latent_ffn_bw_share", "latent_ffn_share"):
        assert name not in got
    if line["attempted"]:
        assert f"ttft_p50_ms.{CELL}" in got and f"ttft_p90_ms.{CELL}" in got
        assert got["latent_ffn_rows_per_expert"]["value"] >= 1.0
    assert got["post_warmup_compiles"]["value"] == 0
    ref = next(i["reference"] for i in infos if "reference" in i)
    assert ref["tokens_checked"] == 96
    assert ref["seq_len"] > 5 * 16
    assert ref["worst_margin"] <= BODY["correct_tolerance_logit"]
    cold = next(i["coldstart"] for i in infos if "coldstart" in i)
    assert "coldstart_compile_lanesplice_s" in cold


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

def context(**over):
    ctx = {"health0": {"tokens_generated": 100, "decode_steps": 10,
                       "latency": {"ttft_count": 4},
                       "moe_held_touched": 1000, "moe_step_layers": 50,
                       "moe_local_picks": 3000},
           "health1": {"tokens_generated": 100 + 400 * 45 + 96,
                       "decode_steps": 410,
                       "latency": {"ttft_count": 100},
                       "moe_held_touched": 1000 + 2000 * 110,
                       "moe_step_layers": 50 + 2000,
                       "moe_local_picks": 3000 + 2000 * 45 * 5.5},
           "health_ready": {}, "trace": None, "family": FAMILY,
           "model": MODEL, "engine": BODY["engine"], "records": [],
           "seconds": 45.0, "chips": 1, "cell": CELL,
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    ctx.update(over)
    return ctx


def test_the_held_kernels_share_of_the_bandwidth():
    read = manifest.layer_reader("latent_ffn_bw_share").read
    trace = {"programs": {"jit_decode": {"steps": 160}},
             "op_seconds": {"jit_decode/held_ffn:1": 1.0,
                            "jit_decode/held_ffn:2": 0.6,
                            "jit_decode/ssm_state_step:1": 0.3,
                            "jit_chunk/grouped_ffn:1": 9.0}}
    # 110 touched experts x 11.0 MB x 5 layers in 10 ms a step
    need = 5 * (110 * EXPERT * 2 + 45 * LATENT * 6)
    want = 100.0 * need / (1.6 / 160) / 819e9
    assert read(context(trace=trace)) == pytest.approx(want)
    assert 73 < want < 75
    assert read(context()) is None                      # no trace
    assert read(context(trace={"programs": {}, "op_seconds": {}})) is None
    quiet = dict(trace, op_seconds={"jit_decode/fusion:1": 1.0})
    assert read(context(trace=quiet)) is None           # the einsums ran
    from benchmark.families import decoder, granitehybrid
    for other in (decoder, granitehybrid):
        assert read(context(trace=trace, family=other)) is None
    bare = {"tokens_generated": 5, "decode_steps": 1}
    assert read(context(trace=trace, health0=bare, health1=bare)) is None


def test_the_expert_layers_share_of_the_step(monkeypatch):
    read = manifest.layer_reader("latent_ffn_share").read
    seconds = {"moe.experts": 0.5, "moe.latent.in": 0.05,
               "moe.latent.out": 0.05, "moe.shared": 0.1, "moe.route": 0.1,
               "attn.ssm.state": 0.6, "attn.ssm.proj": 0.4, "head": 0.2}
    monkeypatch.setattr(device_scopes, "decode_seconds", lambda c: seconds)
    assert read(context()) == pytest.approx(40.0)
    # a program that runs nothing under the latent's scopes has no such layer
    monkeypatch.setattr(device_scopes, "decode_seconds",
                        lambda c: {"moe.experts": 1.0, "attn.core": 1.0})
    assert read(context()) is None
    monkeypatch.setattr(device_scopes, "decode_seconds", lambda c: {})
    assert read(context()) is None
    from benchmark.families import ling
    monkeypatch.setattr(device_scopes, "decode_seconds", lambda c: seconds)
    assert read(context(family=ling)) is None


def test_the_rows_a_touched_expert_served():
    read = manifest.layer_reader("latent_ffn_rows_per_expert").read
    # 45 lanes x 5.5 held picks over 110 touched: 2.25 rows an expert
    assert read(context()) == pytest.approx(45 * 5.5 / 110)
    bare = {"tokens_generated": 5, "decode_steps": 1}
    assert read(context(health0=bare, health1=bare)) is None
    still = dict(context()["health0"])
    assert read(context(health1=still)) is None         # no step in between


@pytest.mark.parametrize("name,want", [("ttft_p50_ms", 5000.0),
                                       ("ttft_p90_ms", 8500.0)])
def test_the_demoted_latencies_read_the_records(name, want):
    read = manifest.layer_reader(f"{name}.{CELL}").read
    records = [{"ok": True, "judged": True, "due_s": float(i),
                "token_s": [i + 0.5 + i, i + 9.0 + i]} for i in range(10)]
    assert read(context(records=records)) == pytest.approx(want, rel=0.06)
    assert read(context()) is None


def test_the_tolerance_stands_over_the_sound_readings_and_under_the_controls():
    import statistics
    tol, got = BODY["correct_tolerance_logit"], \
        BODY["correct_tolerance_readings"]
    assert len(got["sound"]) == got["seeds"] >= 16
    # every sound reading passes with room
    assert 1.5 * max(got["sound"] + got["sound_runs_of_the_cell"]) < tol
    # int8: most seeds fail, the median by 2 x; the seeds that pass are said
    int8 = got["int8_weights"]
    under = sum(v <= tol for v in int8)
    assert under == got["int8_weights_seeds_under_the_limit"]
    assert under <= len(int8) // 4 and statistics.median(int8) > 2 * tol
    assert "16 of the 20" in BODY["correct_tolerance_why"]
    # the structural controls fail on every seed, five limits away or more
    for control in ("gated", "no_latent_scale", "whole_norm", "no_shared"):
        assert len(got[control]) == len(got["control_seeds"])
        assert min(got[control]) > 5 * tol, control
    # what one run cannot tell is said, not hidden
    assert "bf16_state" in BODY["correct_tolerance_why"]
    assert max(got["bf16_state"]) <= tol
    assert "reduce_precision" in BODY["correct_tolerance_why"]
    # the tie is three times what any served choice needed, and refused none
    assert BODY["correct_routing_tie"] >= 3 * got["tie_needed_largest"]
    assert MODEL["routing_tie"] == BODY["correct_routing_tie"]
    # without the door the two sides part: over the limit on every seed
    assert min(got["none_taken"]) > 2 * tol
