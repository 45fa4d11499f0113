"""The gated-short-convolution family of the benchmark (``families/lfm2.py``):
its cost functions against ISSUE 62's hand arithmetic at the published sizes
(the mixers, the attention, the dense and expert layers, the tied table, the
6 KB a token of rows and the 8 KB a layer and lane of tail), its refusals, the
configuration and mix files of its cell letter for letter, the lists the cell
joins and those a loop cut at the window's end may not, a replay of the
sessions against the real prefix cache at the engine's sizes, the rehearsal
walk of the cell — a prefix hit with restored tails among its probes — and
the readers the cell brings on synthetic contexts."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import device_scopes, manifest

M = manifest.load()
CONFIG = "lfm2-8b-a1b-l14"
CELL = "lfm2-sessions"
BODY = manifest.load_config(M, CONFIG)
FAMILY = manifest.family(BODY)
MODEL = FAMILY.model_sizes(BODY)
D = 2048
CONV = 3 * D * D + D * D
ATTN = 2 * D * D + 2 * D * 512
DENSE, EXPERT = 3 * D * 7168, 3 * D * 1792
ROUTER, TABLE = D * 32, 65536 * D
LAYERS = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 3


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
            MODEL["intermediate_size"], MODEL["moe_intermediate_size"]) == \
        (14, 2048, 32, 8, 64, 65536, 7168, 1792)
    assert (MODEL["conv_L_cache"], MODEL["num_dense_layers"],
            MODEL["num_experts"], MODEL["num_experts_per_tok"],
            MODEL["routed_scaling_factor"], MODEL["rope_theta"],
            MODEL["norm_eps"], MODEL["max_position_embeddings"]) == \
        (3, 2, 32, 4, 1.0, 1000000, 1e-5, 128000)
    assert MODEL["layer_types"] == LAYERS
    kinds = FAMILY.layer_kinds(MODEL)
    assert [k for k, _ in kinds].count("conv") == 11
    assert [f for _, f in kinds] == ["dense"] * 2 + ["experts"] * 12
    assert FAMILY.marker_calls_per_step(MODEL) == 3


def test_the_parts_are_the_issues_arithmetic():
    p = FAMILY.matmul_params(MODEL)
    assert p == {"conv": CONV, "full": ATTN, "dense": DENSE,
                 "expert": EXPERT, "router": ROUTER, "head": TABLE}
    # the issue's table: 16.78 M, 10.49 M, 44.04 M, 32 x 11.01 M = 352.32 M
    # + a router of 0.07 M, a table of 134.2 M
    assert round((CONV + 3 * D) / 1e6, 2) == 16.78
    assert round(ATTN / 1e6, 2) == 10.49
    assert round(DENSE / 1e6, 2) == 44.04
    assert round(32 * EXPERT / 1e6, 2) == 352.32
    assert round(ROUTER / 1e6, 2) == 0.07
    assert round(TABLE / 1e6, 1) == 134.2
    # this cut: 184.6 + 31.5 + 88.1 + 4,228.6 + 134.2 = 4,667 M = 9.33 GB
    parts = (11 * (CONV + 3 * D), 3 * ATTN, 2 * DENSE,
             12 * (32 * EXPERT + ROUTER), TABLE)
    assert [round(x / 1e6, 1) for x in parts] == \
        [184.6, 31.5, 88.1, 4228.6, 134.2]
    assert round(sum(parts) / 1e6) == 4667
    assert round(sum(parts) * 2 / 1e9, 2) == 9.33
    # the whole model: 8.34 B with one table (8.47 B with two), over a v5e
    whole = 18 * (CONV + 3 * D) + 6 * ATTN + 2 * DENSE \
        + 22 * (32 * EXPERT + ROUTER) + TABLE
    assert round(whole / 1e9, 2) == 8.34
    assert round((whole + TABLE) / 1e9, 2) == 8.47
    assert whole * 2 > 16e9
    # the state: 8,192 B a layer and lane; rows 2 KB a token and layer, 6 KB
    # a token in this cut
    assert FAMILY.tail_bytes_per_lane(MODEL) == 8192
    assert FAMILY.kv_row_bytes(MODEL) == 2048
    assert 3 * FAMILY.kv_row_bytes(MODEL) == 6144


def test_a_decode_step_moves_weights_tails_and_rows():
    fixed = (11 * CONV + 3 * ATTN + 2 * DENSE + TABLE) * 2 \
        + (11 * 3 * D + 3 * 2 * 64 + 29 * D + 12 * (ROUTER + 32)) * 4
    assert FAMILY.decode_bytes_per_step(MODEL, 0, 0) == fixed
    # the issue's "other weights 0.88 GB"
    assert round(fixed / 1e9, 2) == 0.88
    assert FAMILY.experts_touched(MODEL, 0) == 0
    # 24 lanes x 4 picks of 32 miss an expert with 0.875 ** 24: 95.9 %
    touched = 32 * (1 - 0.875 ** 24)
    assert FAMILY.experts_touched(MODEL, 24) == pytest.approx(touched)
    assert round(100 * touched / 32, 1) == 95.9
    experts = 12 * touched * EXPERT * 2
    assert round(experts / 1e9, 2) == 8.11
    rows = 24 * 17000
    got = FAMILY.decode_bytes_per_step(MODEL, 24, rows)
    assert got == pytest.approx(fixed + experts + 11 * 2 * 24 * 8192
                                + rows * 6144)
    # 11.5 GB a step: 14.0 ms at the chip's 819 GB/s
    assert round(got / 1e9, 1) == 11.5
    assert round(got / 819e9 * 1e3, 1) == 14.0


def test_a_prompt_token_passes_its_picks_and_the_table():
    active = 11 * CONV + 3 * ATTN + 2 * DENSE \
        + 12 * (4 * EXPERT + ROUTER) + TABLE
    assert FAMILY.prefill_flops_per_token(MODEL) == 2.0 * active
    # the issue's 968 M active parameters, 1.94 GFLOP a token
    assert round(active / 1e6) == 968
    assert round(2.0 * active / 1e9, 2) == 1.94


def test_the_kernels_are_priced_by_what_they_must_do():
    engine = BODY["engine"]
    paged = FAMILY.kernel_cost("paged_decode_attention", MODEL, engine, 24,
                               400000)
    # three planes of 64-wide heads, two a row: the heads' own bytes
    assert paged == {"bytes": 3 * 2048 * 400000,
                     "flops": 3 * 4.0 * 32 * 64 * 400000}
    held = FAMILY.kernel_cost("held_ffn", MODEL, engine, 24, 0, touched=30)
    assert held["bytes"] == 12 * (30 * EXPERT * 2 + 24 * D * 6)
    assert held["flops"] == 12 * 30 * 24 * 2.0 * EXPERT
    uniform = FAMILY.kernel_cost("held_ffn", MODEL, engine, 24, 0)
    assert uniform["bytes"] == pytest.approx(12 * (
        FAMILY.experts_touched(MODEL, 24) * EXPERT * 2 + 24 * D * 6))
    for other in ("ssm_state_step", "kda_state_step", "latent_attention"):
        assert FAMILY.kernel_cost(other, MODEL, engine, 24, 0) is None


def test_scope_groups_hold_the_mixers_inside_attention():
    from tpu9.models.shortconv import CONV_SCOPES
    from tpu9.models.transformer import DEVICE_SCOPES
    assert sorted(FAMILY.SCOPE_GROUPS) == ["attention", "ffn", "kv_pool"]
    assert FAMILY.CONV_SCOPES == CONV_SCOPES[:2]
    assert set(FAMILY.CONV_SCOPES) < set(FAMILY.SCOPE_GROUPS["attention"])
    grouped = {s for g in FAMILY.SCOPE_GROUPS.values() for s in g}
    assert grouped <= set(DEVICE_SCOPES) | set(CONV_SCOPES)
    assert FAMILY.STEP_MARKER == "paged_decode_attention"


def test_the_programs_config_carries_the_descriptors():
    import jax.numpy as jnp

    from tpu9.models import kvstate
    cfg = FAMILY.program_config(MODEL)
    assert (cfg.dim, cfg.hidden_dim, cfg.moe_hidden_dim, cfg.n_heads,
            cfg.n_kv_heads, cfg.head_dim, cfg.n_layers, cfg.vocab_size) == \
        (2048, 7168, 1792, 32, 8, 64, 14, 65536)
    assert cfg.layer_pattern == ("conv", "conv") \
        + ("full", "conv", "conv", "conv") * 3
    assert (cfg.conv_taps, cfg.qk_norm, cfg.rope, cfg.rope_theta,
            cfg.tie_embeddings, cfg.max_seq_len) == \
        (3, True, True, 1000000, True, 128000)
    assert (cfg.n_experts, cfg.moe_routed, cfg.moe_top_k,
            cfg.moe_dense_layers, cfg.moe_score, cfg.moe_select_bias,
            cfg.moe_renormalise, cfg.moe_gate_scale, cfg.moe_shared_dim) == \
        (32, 32, 4, 2, "sigmoid", True, True, 1.0, 0)
    assert cfg.dtype == jnp.bfloat16 and cfg.ffn_pattern == ()
    assert cfg.lane_state == ("conv",) and cfg.kv_layers == 3
    assert cfg.kv_row == ((4, 128), (4, 128))
    assert kvstate.block_bytes(cfg, 128) == 786432
    assert kvstate.block_tail_bytes(cfg) == 90112
    assert kvstate.lane_bytes(cfg) == 11 * 8192
    # the engine is made for it: the prefix cache beside this state
    from benchmark import serve
    from tpu9.serving.engine import refuse_unbuilt_with_lane_state
    ecfg = serve.engine_config(BODY["engine"])
    assert ecfg.prefix_cache_blocks > 0
    refuse_unbuilt_with_lane_state(cfg, ecfg, {"tp": 1})


# ---------------------------------------------------------------------------
# refusals
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("key,value", [
    ("model_type", "lfm2"), ("conv_bias", True), ("norm_topk_prob", False),
    ("use_expert_bias", False), ("conv_L_cache", 1), ("num_dense_layers", 14),
    ("num_experts_per_tok", 64), ("sliding_window", 4096),
    ("layer_types", LAYERS[:13] + ["mamba"]),
    ("layer_types", ["conv"] * 14), ("num_hidden_layers", 13)])
def test_a_key_the_family_does_not_build_is_refused(key, value):
    with pytest.raises(ValueError):
        FAMILY.model_sizes(dict(BODY, **{key: value}))


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", False), ("gate_renormalisation_eps", 1e-9),
    ("conv_tail_dtype", "float32"), ("torch_dtype", "float16"),
    ("head_dim", 128), ("router", "softmax")])
def test_an_assumption_the_family_does_not_build_is_refused(key, value):
    assumed = dict(BODY["assumed"])
    assumed[key] = {"value": value, "why": "test"}
    with pytest.raises(ValueError):
        FAMILY.model_sizes(dict(BODY, assumed=assumed))


def test_a_missing_assumption_and_another_stage_are_refused():
    assumed = {k: v for k, v in BODY["assumed"].items()
               if k != "tie_word_embeddings"}
    with pytest.raises(ValueError, match="assumed"):
        FAMILY.model_sizes(dict(BODY, assumed=assumed))
    for change in ({"stage": 1}, {"chips_sharing_a_layer": 2},
                   {"num_hidden_layers_published": 12}):
        with pytest.raises(ValueError, match="stage 0"):
            FAMILY.model_sizes(dict(
                BODY, deployment=dict(BODY["deployment"], **change)))


def test_a_program_without_the_descriptors_is_refused_before_any_start(
        monkeypatch):
    """On a tree whose ``DecoderConfig`` has no taps and no norm a head (the
    parent commit) the cell fails at once, in the harness's own process: no
    stack is started, no chip is opened."""
    from benchmark.families import looped
    have = looped._program_fields()
    assert {"conv_taps", "qk_norm"} <= have
    monkeypatch.setattr(looped, "_program_fields",
                        lambda: have - {"conv_taps", "qk_norm"})
    with pytest.raises(ValueError, match="cannot run a layer pattern of "
                                         "gated short convolutions"):
        FAMILY.model_sizes(BODY)


def test_the_other_families_refuse_the_lfm2_keys():
    from benchmark.families import (decoder, eva, granitehybrid, kimi, ling,
                                    looped, nemotronh)
    for family in (decoder, eva, granitehybrid, kimi, ling, looped,
                   nemotronh):
        with pytest.raises((ValueError, KeyError)):
            family.model_sizes(dict(BODY, family=family.__name__))


# ---------------------------------------------------------------------------
# the files of the cell
# ---------------------------------------------------------------------------

def test_the_configuration_file_states_what_it_runs():
    entry = manifest.config_entry(M, CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
    assert sorted(BODY["reduced"]) == sorted(entry["reduced"])
    assert BODY["reduced"]["num_hidden_layers"]["published"] == 24
    assert BODY["reduced"]["layer_types"]["here"] \
        == BODY["reduced"]["layer_types"]["published"][:14] == LAYERS
    assert (BODY["family"], BODY["reference"]) == ("lfm2", "lfm2")
    stage = BODY["deployment"]
    assert (stage["pipeline_stages"], stage["stage"],
            stage["chips_sharing_a_layer"],
            stage["num_hidden_layers_published"]) == (2, 0, 1, 24)
    assert set(BODY["assumed"]) == set(FAMILY.ASSUMED) | {"head_dim"}
    assert all(v["why"] for v in BODY["assumed"].values())
    engine = BODY["engine"]
    assert (engine["topology"], engine["max_batch"], engine["kv_block_size"],
            engine["prefill_chunk"]) == ("1x1", 32, 128, 512)
    # 24,576 + 32 x 256 = 32,768 plus a page, in whole chunks
    assert engine["max_seq_len"] >= 32768 + 128
    assert engine["max_seq_len"] % engine["prefill_chunk"] == 0
    assert engine["prefix_cache_blocks"] > 0 and engine["why"]
    assert BODY["endpoint"] == {"tpu": "v5e-1", "memory": "32Gi"}
    # the memory the issue reckons: weights 9.33 GB, the pool's rows and
    # their tails, under the chip's 16 GB and over a quarter of it
    blocks = engine["kv_pool_blocks"] + 1
    held = 9.33e9 + blocks * (786432 + 90112)
    assert 0.25 * 16e9 < held < 15e9
    assert blocks >= 4336


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(manifest.HERE, "reference", "lfm2.py")) as f:
        text = f.read()
    assert "tpu9" not in text.replace("tpu9's", "").split('"""', 2)[2]
    for control in ("int8_weights", "no_conv_gate", "no_in_gate", "two_taps",
                    "no_qk_norm", "bias_in_gates", "no_renormalise"):
        assert f'"{control}"' in text, control


def test_the_tolerance_lies_between_the_sound_readings_and_the_controls():
    tol, got = BODY["correct_tolerance_logit"], \
        BODY["correct_tolerance_readings"]
    assert len(got["sound"]) == got["seeds"] >= 10
    sound = got["sound"] + got["sound_runs_of_the_cell"]
    assert max(sound) < tol < min(got["int8_weights"])
    assert len(got["int8_weights"]) == got["seeds"]
    for control in ("no_conv_gate", "no_in_gate", "two_taps", "no_qk_norm",
                    "bias_in_gates", "no_renormalise"):
        assert control in got and control in BODY["correct_tolerance_why"]
    assert BODY["correct_routing_tie"] > 0
    assert MODEL["routing_tie"] == BODY["correct_routing_tie"]


def test_the_configuration_holds_every_number_of_the_catalog_entry():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "LFM2-8B-A1B")
    assert BODY["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in BODY["reduced"]:
            assert BODY["reduced"][key]["published"] == value
            assert BODY[key] == BODY["reduced"][key]["here"]
        else:
            assert BODY[key] == value, key


# the lists this cell does not join: open loops' arrivals, and those an
# accepted test pins to its own cell. (``tpot_relay_ms`` it JOINS, against
# ISSUE 62's list: ``test_bench_gap.py`` holds every cell but three named
# loops to that list, and the reader prints wherever the streams the
# window's end cuts — one a session — are under a tenth of those that ended:
# ten turns a session a window, which 24 sessions at <= 4.5 s a turn make.
# PR 61's cell joined for the same test and made too few turns: PERF.md
# section 7)
NOT_JOINED = ("gen_late_p99_ms", "engine_queue_wait_ms",
              "prefill_ms_per_ktok", "engine_admit_ms",
              "first_token_hold_ms", "stream_lag_ms",
              "gateway_pre_forward_ms", "runner_door_ms", "runner_ingest_ms",
              "gateway_first_relay_ms", "client_hop_ms", "prefix_hit_share",
              "prefix_rows_reused_share", "prefill_flops_share",
              "moe_held_touched_share", "ssm_state_share",
              "kda_state_share", "latent_attn_bw_share")


def test_the_mix_is_the_one_the_issue_states():
    cell = manifest.cell(M, CELL)
    assert (cell["config"], cell["chips"], cell["traffic"]) == \
        (CONFIG, 1, CELL)
    assert len(cell["why"]) <= 200
    mix = manifest.load_traffic(cell["traffic"])
    assert mix["kind"] == "closed_sessions"
    assert (mix["sessions"], mix["max_turns"], mix["stagger_s"]) == \
        (24, 32, 0.5)
    assert mix["sessions"] <= BODY["engine"]["max_batch"]
    (cls,) = mix["classes"]
    assert cls["judged"] and cls["share"] == 1.0
    assert cls["context_tokens"] == {"dist": "loguniform", "lo": 8192,
                                     "hi": 24576}
    assert cls["turn_tokens"] == {"dist": "fixed", "value": 128}
    assert cls["output_tokens"] == {"dist": "fixed", "value": 128}
    # every turn fits the cache, its worst case included
    assert 24576 + 32 * 256 + 9 < BODY["engine"]["max_seq_len"]
    assert mix["trace_steps"] > 0 and mix["trace_steps_why"]
    plan = manifest.traffic_kind("closed_sessions").plan(
        mix, 2 ** 31 + 62, 45.0, MODEL["vocab_size"])
    docs = sorted(len(s["document"]) for s in plan["sessions"])
    assert len(docs) == 24 and 8192 < docs[0] and docs[-1] < 24576
    assert 354000 < sum(docs) < 362000
    names = [m["name"] for m in manifest.cell_metrics(M, CELL, "end_to_end")]
    assert names == ["tpot_p50_ms", "setup_s"]
    layer = {m["name"] for m in manifest.cell_metrics(M, CELL, "per_layer")}
    new = {"conv_mix_share": ("model step", "device_trace", "lower", "%"),
           "conv_prefix_rows_reused_share": ("KV pool", "program_counter",
                                             "higher", "%"),
           "conv_tail_restores_per_turn": ("engine", "program_counter",
                                           "higher", "restores/turn"),
           f"ttft_p50_ms.{CELL}": ("client", "host_clock", "lower", "ms")}
    joined = {"moe_touched_share", "paged_attn_bw_share", "tpot_relay_ms"}
    unlisted = {m["name"] for m in M["per_layer"] if "workloads" not in m}
    assert {"decode_step_ms", "decode_bw_share", "decode_kv_pool_share",
            "decode_attention_share", "decode_ffn_share",
            "device_idle_share", "idle_admit_share", "engine_tpot_ms",
            "prefill_pad_share"} <= unlisted
    assert layer == set(new) | joined | unlisted
    assert not set(NOT_JOINED) & layer
    for name, (where, source, better, unit) in new.items():
        entry = next(m for m in M["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["layer"] == where
        assert (entry["source"], entry["better"], entry["unit"]) == \
            (source, better, unit)
        assert entry["moves"] == "tpot_p50_ms"
        assert sorted(entry) == ["better", "layer", "moves", "name",
                                 "source", "unit", "workloads"]
        assert os.path.exists(manifest.layer_reader_path(name))
    for name in joined:
        entry = next(m for m in M["per_layer"] if m["name"] == name)
        assert entry["workloads"][-1] == CELL
    out = next(m for m in M["end_to_end"] if m["name"] == "out_tok_s")
    assert CELL not in out.get("workloads", [])


def test_the_sessions_never_lose_a_live_history():
    """A replay of the schedule against the real ``PrefixCache`` at the
    engine's sizes (PR 54's lesson): every session to ``max_turns``, turn
    about, each admission holding its worst case. Every turn hits its whole
    earlier prompt, nothing a session still needs is ever evicted, and the
    reservations fit the pool."""
    from tpu9.serving.paged_kv import (BlockAllocator, PrefixCache,
                                       blocks_for)
    engine = BODY["engine"]
    bs, slack = engine["kv_block_size"], max(engine["decode_steps"]) + 1
    mix = manifest.load_traffic(CELL)
    from benchmark.traffic import dist
    docs = dist.quantiles(mix["classes"][0]["context_tokens"],
                          mix["sessions"])
    alloc = BlockAllocator(engine["kv_pool_blocks"] + 1, bs)
    alloc.alloc(1)                                    # the trash block
    alloc.reserve_capacity = engine["kv_pool_blocks"]
    cache = PrefixCache(alloc, engine["prefix_cache_blocks"])
    tick = iter(range(10 ** 9))
    cache.clock = lambda: next(tick)
    # a session's tokens: its own id, so nothing is shared between sessions
    history = {s: [s + 3] * (n + 1) for s, n in enumerate(docs)}
    slots = {}

    def admit(s, prompt, new):
        assert alloc.can_reserve(len(prompt) + new + slack)
        reserved = alloc.reserve(len(prompt) + new + slack)
        keys = cache.walk(prompt)
        entry = cache.lookup(prompt, keys)
        shared = list(entry.blocks) if entry else []
        alloc.retain(shared)
        if entry is not None:
            cache.release_pin(entry)
        fresh = alloc.alloc(blocks_for(len(prompt) + 1, bs) - len(shared))
        if fresh is None:
            cache.evict_for_space(blocks_for(len(prompt) + 1, bs))
            fresh = alloc.alloc(blocks_for(len(prompt) + 1, bs)
                                - len(shared))
        assert fresh is not None
        cache.insert(prompt, shared + fresh, keys)
        slots[s] = (shared + fresh, reserved)
        return entry.n_tokens if entry else 0

    def retire(s, grown):
        blocks, reserved = slots.pop(s)
        more = blocks_for(grown, bs) - len(blocks)
        if more > 0:
            blocks = blocks + alloc.alloc(more)
        alloc.release(blocks)
        alloc.unreserve(reserved)

    for s in history:                                 # set-up: the contexts
        assert admit(s, history[s][:-1], 1) == 0
        retire(s, len(history[s]))
    for turn in range(mix["max_turns"]):
        live = []
        for s in history:                             # all 24 at once
            prompt = history[s] + [s + 3] * 128
            hit = admit(s, prompt, 128)
            # all of the earlier prompt's whole pages (the history less
            # what was answered to it): the suffix is 256-383 tokens, and
            # 129-256 behind the context's one token of set-up
            earlier = len(history[s]) - (128 if turn else 1)
            assert hit == earlier // bs * bs
            assert len(prompt) - hit - (earlier % bs) == (256 if turn
                                                          else 129)
            live.append((s, prompt))
        for s, prompt in live:
            history[s] = prompt + [s + 3] * 128
            retire(s, len(history[s]))
    assert cache.evictions == 0
    assert max(map(len, history.values())) <= 24576 + 32 * 256 + 1
    assert cache.held_blocks <= engine["prefix_cache_blocks"]
    assert alloc.reserved == 0


def test_the_rehearsal_sizes_are_the_unit_tests():
    reh = rehearsal()
    config = dict(BODY, **reh["model"])
    config["assumed"] = dict(BODY["assumed"], **reh["assumed"])
    model = FAMILY.model_sizes(config)
    cfg = FAMILY.program_config(model)
    from tests.test_lfm2_layers import SMALL
    assert (cfg.dim, cfg.hidden_dim, cfg.moe_hidden_dim, cfg.n_heads,
            cfg.n_kv_heads, cfg.head_dim, cfg.n_layers, cfg.n_experts,
            cfg.moe_top_k, cfg.conv_taps, cfg.layer_pattern) == \
        (SMALL.dim, SMALL.hidden_dim, SMALL.moe_hidden_dim, SMALL.n_heads,
         SMALL.n_kv_heads, SMALL.head_dim, SMALL.n_layers, SMALL.n_experts,
         SMALL.moe_top_k, SMALL.conv_taps, SMALL.layer_pattern)
    assert cfg.vocab_size == 512


def test_rehearsal_walks_the_cell():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "run.py"), "--workload",
         CELL, "--seed", str(2 ** 31 + 6262), "--seconds", "8", "--trace",
         "1", "--rehearse"], cwd=manifest.ROOT, env=env, capture_output=True,
        text=True, timeout=420)
    assert proc.returncode == 3, proc.stderr[-3000:]
    infos = [json.loads(ln)["info"] for ln in proc.stdout.splitlines()
             if ln.startswith('{"info"')]
    (line,) = [i["rehearsal_line"] for i in infos if "rehearsal_line" in i]
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    # the counters' metrics are read on the CPU too; the trace's are not.
    # Every turn sends its whole history: all but the suffix is reused, and
    # every turn started from restored tails
    assert got["conv_prefix_rows_reused_share"]["value"] > 80
    assert got["conv_tail_restores_per_turn"]["value"] == 1.0
    assert "conv_mix_share" not in got
    if line["attempted"]:
        assert f"ttft_p50_ms.{CELL}" in got
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
    ctx = {"health0": {"conv_tail_restores": 24, "gap_admissions": 30,
                       "prefix_rows_reused": 10000,
                       "prompt_rows_admitted": 400000},
           "health1": {"conv_tail_restores": 24 + 200,
                       "gap_admissions": 30 + 200,
                       "prefix_rows_reused": 10000 + 200 * 15000,
                       "prompt_rows_admitted": 400000 + 200 * 15300},
           "health_ready": {}, "trace": None, "family": FAMILY,
           "model": MODEL, "engine": BODY["engine"], "records": [],
           "seconds": 45.0, "chips": 1, "cell": CELL,
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    ctx.update(over)
    return ctx


def test_the_counter_readers_read_the_counters():
    share = manifest.layer_reader("conv_prefix_rows_reused_share").read
    per_turn = manifest.layer_reader("conv_tail_restores_per_turn").read
    assert share(context()) == pytest.approx(100 * 15000 / 15300)
    assert per_turn(context()) == 1.0
    # a turn whose pages were let go prefills its context again
    lost = dict(context()["health1"], conv_tail_restores=24 + 150)
    assert per_turn(context(health1=lost)) == 0.75
    # an engine without the counters (the parent; another family's): left out
    bare = {"prefix_rows_reused": 5, "prompt_rows_admitted": 9,
            "gap_admissions": 3}
    later = {"prefix_rows_reused": 50, "prompt_rows_admitted": 90,
             "gap_admissions": 30}
    assert share(context(health0=bare, health1=later)) is None
    assert per_turn(context(health0=bare, health1=later)) is None
    still = context()["health0"]
    assert share(context(health1=still)) is None
    assert per_turn(context(health1=still)) is None


def test_the_mixers_share_of_the_step(monkeypatch):
    read = manifest.layer_reader("conv_mix_share").read
    seconds = {"attn.conv.proj": 0.6, "attn.conv.mix": 0.2, "attn.core": 0.4,
               "moe.experts": 2.4, "head": 0.4}
    monkeypatch.setattr(device_scopes, "decode_seconds", lambda c: seconds)
    assert read(context()) == pytest.approx(20.0)
    # a program that runs nothing under the mixers' scopes has no such layer
    monkeypatch.setattr(device_scopes, "decode_seconds",
                        lambda c: {"moe.experts": 1.0, "attn.core": 1.0})
    assert read(context()) is None
    monkeypatch.setattr(device_scopes, "decode_seconds", lambda c: {})
    assert read(context()) is None
    from benchmark.families import granitehybrid
    monkeypatch.setattr(device_scopes, "decode_seconds", lambda c: seconds)
    assert read(context(family=granitehybrid)) is None


def test_the_demoted_latency_reads_the_records():
    read = manifest.layer_reader(f"ttft_p50_ms.{CELL}").read
    records = [{"ok": True, "judged": True, "due_s": float(i),
                "token_s": [i + 0.5 + i, i + 9.0 + i]} for i in range(10)]
    assert read(context(records=records)) == pytest.approx(5000.0, rel=0.06)
    assert read(context()) is None
