"""The hybrid state-space family of the benchmark
(``families/granitehybrid.py``): its cost functions against hand arithmetic
at the published sizes (the mixer's and the attention's projections, the
state a lane reads and writes a step, the four planes' rows), its refusals,
the configuration and mix files of its cell, the rehearsal walk of the cell
and the readers the cell brings on synthetic contexts."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import device_scopes, manifest

M = manifest.load()
CONFIG = "granite-4.0-h-micro"
CELL = "granite-chat"
BODY = manifest.load_config(M, CONFIG)
FAMILY = manifest.family(BODY)
MODEL = FAMILY.model_sizes(BODY)
D, INNER, WIDTH, H = 2048, 4096, 4352, 64
SSM = D * (INNER + WIDTH + H) + INNER * D
ATTN = 2 * D * 32 * 64 + 2 * D * 8 * 64
FFN, HEAD = 3 * D * 8192, D * 100352
VECTORS = 5 * WIDTH + 3 * H + INNER
STATE = 64 * 64 * 128


def rehearsal():
    with open(os.path.join(manifest.HERE, "rehearsal", f"{CONFIG}.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# cost functions at the published sizes
# ---------------------------------------------------------------------------

def test_sizes_are_the_published_ones():
    assert (MODEL["num_hidden_layers"], MODEL["hidden_size"],
            MODEL["shared_intermediate_size"], MODEL["num_attention_heads"],
            MODEL["num_key_value_heads"], MODEL["head_dim"],
            MODEL["vocab_size"]) == (40, 2048, 8192, 32, 8, 64, 100352)
    assert (MODEL["mamba_n_heads"], MODEL["mamba_d_head"],
            MODEL["mamba_d_state"], MODEL["mamba_n_groups"],
            MODEL["mamba_d_conv"], MODEL["mamba_expand"],
            MODEL["mamba_chunk_size"]) == (64, 64, 128, 1, 4, 2, 256)
    assert (MODEL["attention_multiplier"], MODEL["embedding_multiplier"],
            MODEL["residual_multiplier"], MODEL["logits_scaling"]) == \
        (0.015625, 12.0, 0.22, 8.0)
    kinds = FAMILY.layer_kinds(MODEL)
    assert [l for l, k in enumerate(kinds) if k == "full"] == [5, 15, 25, 35]
    assert kinds.count("ssm") == 36
    assert FAMILY.marker_calls_per_step(MODEL) == 4


def test_the_parts_are_the_issues_arithmetic():
    p = FAMILY.matmul_params(MODEL)
    assert p == {"ssm": SSM, "full": ATTN, "ffn": FFN, "head": HEAD}
    # a Mamba layer 25.8 M + 50.3 M of SwiGLU, an attention layer 10.5 M +
    # 50.3 M, the tied embedding 205.5 M: 3.19 B parameters
    assert round(SSM / 1e6, 1) == 25.8 and round(ATTN / 1e6, 1) == 10.5
    assert round(FFN / 1e6, 1) == 50.3 and round(HEAD / 1e6, 1) == 205.5
    total = 36 * (SSM + VECTORS + FFN) + 4 * (ATTN + FFN) + HEAD \
        + 81 * D
    assert round(total / 1e9, 2) == 3.19
    assert FAMILY.ssm_vector_params(MODEL) == VECTORS
    assert FAMILY.conv_width(MODEL) == WIDTH
    # a lane's state: 36 planes of a float32 [64, 64, 128] and a 3 x 4352
    # bf16 tail = 75.6 MB
    assert FAMILY.state_bytes_per_lane(MODEL) == STATE * 4 + 3 * WIDTH * 2
    assert round(36 * FAMILY.state_bytes_per_lane(MODEL) / 1e6, 1) == 76.4
    assert round(36 * STATE * 4 / 1e6, 1) == 75.5


def test_a_decode_step_moves_weights_state_and_rows():
    weights = (36 * (SSM + FFN) + 4 * (ATTN + FFN) + HEAD) * 2 \
        + (36 * VECTORS + 81 * D) * 4
    assert round(weights / 1e9, 2) == 6.38
    assert FAMILY.decode_bytes_per_step(MODEL, 0, 0) == weights
    lane = 36 * 2 * (STATE * 4 + 3 * WIDTH * 2)
    rows = 4 * 2 * 8 * 64 * 2
    got = FAMILY.decode_bytes_per_step(MODEL, 40, 40 * 700)
    assert got == weights + 40 * lane + 40 * 700 * rows
    # at 40 live lanes the state stream is as large as the weight stream
    assert 0.9 < 40 * lane / weights < 1.0
    assert 40 * 700 * rows < 0.04 * weights


def test_a_prompt_token_passes_every_matrix_but_the_head():
    assert FAMILY.prefill_flops_per_token(MODEL) == \
        2.0 * (36 * (SSM + FFN) + 4 * (ATTN + FFN))


def test_the_kernels_are_priced_by_what_they_must_do():
    engine = BODY["engine"]
    for name in ("ssm_state_step", "attn.ssm.state"):
        cost = FAMILY.kernel_cost(name, MODEL, engine, 40, 0)
        assert cost == {"bytes": 36 * 40 * 2 * STATE * 4,
                        "flops": 36 * 40 * 5.0 * STATE}
    assert round(cost["bytes"] / 1e9, 2) == 6.04
    paged = FAMILY.kernel_cost("paged_decode_attention", MODEL, engine, 40,
                               28000)
    assert paged == {"bytes": 4 * 2048 * 28000,
                     "flops": 4 * 4.0 * 32 * 64 * 28000}
    assert FAMILY.kernel_cost("held_ffn", MODEL, engine, 40, 0) is None


def test_scope_groups_hold_the_new_scopes():
    from tpu9.models.ssm import SSM_SCOPES
    from tpu9.ops import ssd
    groups = FAMILY.SCOPE_GROUPS
    assert set(groups) == {"kv_pool", "attention", "ffn"}
    assert set(SSM_SCOPES) <= set(groups["attention"])
    assert "attn.core" in groups["attention"]
    assert FAMILY.SSM_STATE_SCOPE in SSM_SCOPES
    assert FAMILY.SSM_STEP_KERNEL == ssd.STEP_KERNEL
    assert FAMILY.STEP_MARKER == "paged_decode_attention"


def test_the_programs_config_carries_the_descriptors():
    import jax.numpy as jnp
    cfg = FAMILY.program_config(MODEL)
    assert cfg.layer_pattern == tuple(FAMILY.layer_kinds(MODEL))
    assert cfg.layer_group == 0 and cfg.n_experts == 0
    assert (cfg.dim, cfg.hidden_dim, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.n_layers, cfg.vocab_size) == \
        (2048, 8192, 32, 8, 64, 40, 100352)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv) == (64, 64, 128, 1, 4)
    assert (cfg.rope, cfg.attn_scale, cfg.embed_mult, cfg.residual_mult,
            cfg.logit_div, cfg.tie_embeddings) == \
        (False, 0.015625, 12.0, 0.22, 8.0, True)
    assert cfg.dtype == jnp.bfloat16 and cfg.norm_eps == 1e-5
    # two KV heads of 64 side by side in a cache row of 128 lanes
    assert cfg.kv_pack == 2
    assert cfg.kv_layers == 4 and cfg.kv_row == ((4, 128), (4, 128))
    assert cfg.lane_state == ("ssm",)


@pytest.mark.parametrize("key,value", [
    ("num_local_experts", 8), ("num_experts_per_tok", 2),
    ("position_embedding_type", "rope"), ("mamba_n_groups", 8),
    ("mamba_proj_bias", True), ("mamba_conv_bias", False),
    ("tie_word_embeddings", False), ("attention_bias", True),
    ("hidden_act", "gelu"), ("rope_scaling", {"type": "yarn"}),
    ("model_type", "granitemoe"), ("sliding_window", 4096),
    ("shared_intermediate_size", 4096), ("mamba_expand", 4),
    ("layer_types", ["mamba"] * 40), ("layer_types", ["mamba"] * 39),
    ("layer_types", ["mamba"] * 39 + ["full_attention"]),
    ("residual_multiplier", 0), ("mamba_d_conv", 1),
])
def test_a_key_or_value_the_family_does_not_build_is_refused(key, value):
    with pytest.raises(ValueError):
        FAMILY.model_sizes(dict(BODY, **{key: value}))


@pytest.mark.parametrize("key,value", [
    ("ssm_state_dtype", "bfloat16"), ("gated_norm", "norm_before_gate"),
    ("dt_limits", "[0.001, 0.1]"), ("head_dim", 128),
    ("torch_dtype", "float16")])
def test_an_assumption_the_family_does_not_build_is_refused(key, value):
    assumed = dict(BODY["assumed"], **{key: {"value": value, "why": "x"}})
    with pytest.raises(ValueError, match="assumed"):
        FAMILY.model_sizes(dict(BODY, assumed=assumed))


def test_a_missing_assumption_is_refused():
    assumed = {k: v for k, v in BODY["assumed"].items()
               if k != "ssm_state_dtype"}
    with pytest.raises(ValueError, match="exactly"):
        FAMILY.model_sizes(dict(BODY, assumed=assumed))


def test_a_program_without_the_descriptors_is_refused_before_any_start(
        monkeypatch):
    """On a tree whose ``DecoderConfig`` has no listed pattern (the parent
    commit) the cell fails at once, in the harness's own process: no stack
    is started, no chip is opened."""
    from benchmark.families import looped
    monkeypatch.setattr(looped, "_program_fields",
                        lambda: {"vocab_size", "dim", "layer_group",
                                 "tie_embeddings", "embed_scale"})
    with pytest.raises(ValueError, match="cannot run a layer pattern given "
                                         "as a list"):
        FAMILY.model_sizes(BODY)
    monkeypatch.undo()
    assert set(FAMILY.DESCRIPTORS) <= looped._program_fields()


def test_the_other_families_refuse_the_keys():
    from benchmark.families import decoder, eva, kimi, ling, looped
    for family in (decoder, eva, kimi, ling, looped):
        with pytest.raises((ValueError, KeyError)):
            family.model_sizes(dict(BODY, family=family.__name__))


# ---------------------------------------------------------------------------
# the files of the cell
# ---------------------------------------------------------------------------

def test_the_configuration_file_states_what_it_runs():
    entry = manifest.config_entry(M, CONFIG)
    assert entry["reduced"] == [] and BODY["reduced"] == {}
    assert sorted(BODY["assumed"]) == sorted(set(FAMILY.ASSUMED)
                                             | {"head_dim"})
    for stated in BODY["assumed"].values():
        assert stated["why"]
    assert BODY["assumed"]["ssm_state_dtype"]["value"] == "float32"
    knobs = BODY["engine"]
    assert (knobs["max_seq_len"], knobs["kv_block_size"],
            knobs["prefill_chunk"], knobs["prefix_cache_blocks"],
            knobs["decode_steps"], knobs["topology"]) == \
        (4096, 128, 512, 0, [1, 8], "1x1")
    assert knobs["max_batch"] % 8 == 0 and 8 <= knobs["max_batch"] <= 64
    # the pool holds the traffic's worst case: every lane at 1,024 + 512
    assert knobs["kv_pool_blocks"] >= knobs["max_batch"] * 12
    assert knobs["why"]
    # resident: weights, the lanes' state, the pool — over a quarter of
    # the chip whatever the lanes
    resident = 6.38e9 + knobs["max_batch"] * 36 * (STATE * 4 + 3 * WIDTH * 2) \
        + knobs["kv_pool_blocks"] * 4 * 128 * 2048
    assert 0.45 < resident / 16.909e9 < 0.85


def test_the_configuration_holds_every_number_of_the_catalog_entry():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == CONFIG)
    assert BODY["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        assert BODY[key] == value, key


def test_a_program_with_a_narrower_state_is_refused(monkeypatch):
    """One run's margin cannot tell a bfloat16 state; the family does."""
    import jax.numpy as jnp

    from tpu9.models import kvstate
    shapes = kvstate.lane_shapes
    FAMILY.program_config(MODEL)
    monkeypatch.setattr(kvstate, "lane_shapes", lambda cfg, lanes: {
        k: (shape, jnp.bfloat16) for k, (shape, _) in
        shapes(cfg, lanes).items()})
    with pytest.raises(ValueError, match="keeps the lanes' state in bfloat16"):
        FAMILY.program_config(MODEL)


def test_the_mix_is_the_one_the_cell_states():
    cell = manifest.cell(M, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, CELL, 1)
    mix = manifest.load_traffic(CELL)
    assert mix["kind"] == "open_stratified" and mix["arrangement_seed"] == 55
    assert mix["rate_rps"] == pytest.approx(0.7 * mix["knee_rps"], rel=0.03)
    (chat,) = mix["classes"]
    assert chat["judged"] and chat["share"] == 1.0
    assert chat["prompt_tokens"] == {"dist": "loguniform", "lo": 64,
                                     "hi": 1024}
    assert chat["output_tokens"] == {"dist": "loguniform", "lo": 64,
                                     "hi": 512}
    assert mix["trace_steps"] > 0 and mix["trace_steps_why"]
    # every new reader lists the cell, and the cell reports tpot and setup
    for name in ("ssm_state_bw_share", "ssm_state_share",
                 "ssm_prefill_ms_per_ktok", f"ttft_p50_ms.{CELL}",
                 f"ttft_p90_ms.{CELL}"):
        (entry,) = [m for m in M["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "tpot_p50_ms"
    # the accepted metrics that list the cell: membership, not last place,
    # so that the next cell is appended behind this one without an edit
    # here (nine more read this cell; PERF.md section 7 says why their
    # lists stay as they are)
    for name in ("prefill_flops_share", "paged_attn_bw_share",
                 "gen_late_p99_ms", "engine_queue_wait_ms"):
        (entry,) = [m for m in M["per_layer"] if m["name"] == name]
        assert CELL in entry["workloads"]
    out = next(m for m in M["end_to_end"] if m["name"] == "out_tok_s")
    assert CELL not in out.get("workloads", [])
    # the KDA metrics keep the list an accepted test pins
    (kda,) = [m for m in M["per_layer"] if m["name"] == "kda_state_bw_share"]
    assert kda["workloads"] == ["ling-reason"]


def test_the_rehearsal_sizes_are_one_whole_period():
    reh = rehearsal()
    config = dict(BODY, **reh["model"])
    config["assumed"] = dict(BODY["assumed"], **reh["assumed"])
    model = FAMILY.model_sizes(config)
    cfg = FAMILY.program_config(model)
    assert (cfg.dim, cfg.hidden_dim, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.n_layers, cfg.vocab_size, cfg.ssm_heads,
            cfg.ssm_head_dim, cfg.ssm_state) == \
        (64, 128, 4, 2, 16, 10, 512, 4, 32, 128)
    assert cfg.layer_pattern == ("ssm",) * 5 + ("full",) + ("ssm",) * 4
    assert cfg.attn_scale == 1 / 16 and cfg.kv_layers == 1
    assert cfg.kv_pack == 2 and cfg.kv_row == ((1, 32), (1, 32))


def test_rehearsal_walks_the_cell():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "run.py"), "--workload",
         CELL, "--seed", str(2 ** 31 + 5555), "--seconds", "8", "--trace",
         "1", "--rehearse"], cwd=manifest.ROOT, env=env, capture_output=True,
        text=True, timeout=420)
    assert proc.returncode == 3, proc.stderr[-3000:]
    infos = [json.loads(ln)["info"] for ln in proc.stdout.splitlines()
             if ln.startswith('{"info"')]
    (line,) = [i["rehearsal_line"] for i in infos if "rehearsal_line" in i]
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    # the trace's metrics are not read on the CPU (no device plane)
    for name in ("ssm_state_bw_share", "ssm_state_share",
                 "ssm_prefill_ms_per_ktok"):
        assert name not in got
    if line["attempted"]:
        assert f"ttft_p50_ms.{CELL}" in got and f"ttft_p90_ms.{CELL}" in got
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
                       "latency": {"ttft_count": 4}},
           "health1": {"tokens_generated": 100 + 400 * 40 + 96,
                       "decode_steps": 410,
                       "latency": {"ttft_count": 100}},
           "health_ready": {}, "trace": None, "family": FAMILY,
           "model": MODEL, "engine": BODY["engine"], "records": [],
           "seconds": 45.0, "chips": 1, "cell": CELL,
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    ctx.update(over)
    return ctx


def test_the_prefill_reader_counts_whole_chunks():
    read = manifest.layer_reader("ssm_prefill_ms_per_ktok").read
    trace = {"programs": {"jit_chunk": {"runs": 8, "seconds": 0.216},
                          "jit_group": {"runs": 3, "seconds": 0.153},
                          "jit_lane_splice": {"runs": 11, "seconds": 0.003},
                          "jit_decode": {"runs": 49, "seconds": 1.47}}}
    # 8 chunks of 512 and 3 fused groups of 2 x 512: 7,168 tokens
    assert read(context(trace=trace)) == pytest.approx(369.0 / 7.168)
    assert read(context()) is None                      # no trace
    only = {"programs": {"jit_decode": {"runs": 4, "seconds": 0.1}}}
    assert read(context(trace=only)) is None            # no prefill program
    from benchmark.families import decoder
    assert read(context(trace=trace, family=decoder)) is None


def test_the_step_kernels_share_of_the_bandwidth():
    read = manifest.layer_reader("ssm_state_bw_share").read
    trace = {"programs": {"jit_decode": {"steps": 160}},
             "op_seconds": {"jit_decode/ssm_state_step:1": 1.0,
                            "jit_decode/ssm_state_step:2": 0.6,
                            "jit_decode/paged_decode_attention:1": 0.3,
                            "jit_chunk/ssm_state_step:1": 9.0}}
    # 40 lanes x 36 planes x 2 x 2.1 MB in 10 ms a step
    want = 100.0 * (36 * 40 * 2 * STATE * 4) / (1.6 / 160) / 819e9
    assert read(context(trace=trace)) == pytest.approx(want)
    assert 73 < want < 74
    assert read(context()) is None                      # no trace
    assert read(context(trace={"programs": {}, "op_seconds": {}})) is None
    quiet = dict(trace, op_seconds={"jit_decode/fusion:1": 1.0})
    assert read(context(trace=quiet)) is None           # the XLA step ran
    from benchmark.families import decoder
    assert read(context(trace=trace, family=decoder)) is None
    bare = {"tokens_generated": 5, "decode_steps": 1}
    assert read(context(trace=trace, health0=bare, health1=bare)) is None


def test_the_recurrences_share_of_the_step(monkeypatch):
    read = manifest.layer_reader("ssm_state_share").read
    seconds = {"attn.ssm.state": 0.8, "attn.ssm.proj": 0.4, "ffn": 0.8}
    monkeypatch.setattr(device_scopes, "decode_seconds", lambda c: seconds)
    assert read(context()) == pytest.approx(40.0)
    monkeypatch.setattr(device_scopes, "decode_seconds",
                        lambda c: {"ffn": 1.0, "attn.core": 1.0})
    assert read(context()) is None
    monkeypatch.setattr(device_scopes, "decode_seconds", lambda c: {})
    assert read(context()) is None
    from benchmark.families import decoder
    monkeypatch.setattr(device_scopes, "decode_seconds", lambda c: seconds)
    assert read(context(family=decoder)) is None


@pytest.mark.parametrize("name,want", [("ttft_p50_ms", 5000.0),
                                       ("ttft_p90_ms", 8500.0)])
def test_the_demoted_latencies_read_the_records(name, want):
    read = manifest.layer_reader(f"{name}.{CELL}").read
    records = [{"ok": True, "judged": True, "due_s": float(i),
                "token_s": [i + 0.5 + i, i + 9.0 + i]} for i in range(10)]
    assert read(context(records=records)) == pytest.approx(want, rel=0.06)
    assert read(context()) is None


def test_the_tolerance_lies_between_the_sound_readings_and_int8():
    tol, got = BODY["correct_tolerance_logit"], \
        BODY["correct_tolerance_readings"]
    assert len(got["sound"]) == len(got["int8_weights"]) == got["seeds"]
    # every sound reading passes with room, every int8 reading fails
    assert 1.5 * max(got["sound"]) < tol < min(got["int8_weights"])
    # the structural controls fail on every seed, five limits away or more
    for control in ("no_decay", "no_d", "residual_one", "sqrt_scale"):
        assert min(got[control]) > 5 * tol, control
    # what one run cannot tell is said, not hidden
    for control in ("bf16_state", "rotary"):
        assert control in BODY["correct_tolerance_why"]
        assert max(got[control]) <= tol
    assert "reduce_precision" in BODY["correct_tolerance_why"]
