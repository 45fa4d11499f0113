"""The six readers of a token's gap (ISSUE 57) on hand-made snapshots — a
window whose windows, lanes and streams are known, told to the readers the
way a run tells them: cumulative counters and cumulative mean and count of
each summary at the window's two ends — and ``tools/tpot.py`` on a saved
run's ``context.json`` and on a hand-made trace."""

import copy
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest

NEW = ("engine_tpot_ms", "tpot_admit_stall_ms", "decode_period_ms",
       "stream_gap_max_ms", "tpot_relay_ms", "prefill_pad_share")
GATEWAY_GAP = "tpu9_gateway_stream_gap_s"

# one stream: tokens, then ms — the gateway's first -> last write, the
# runner's, the engine's first token -> last delivery, its largest period
BEFORE = [dict(n=11, g=260, r=240, e=230, worst=40)] * 3
WINDOW = [dict(n=129, g=2816, r=2624, e=2560, worst=90),
          dict(n=65, g=1600, r=1408, e=1344, worst=70),
          dict(n=33, g=704, r=672, e=640, worst=25),
          dict(n=257, g=5376, r=5248, e=5120, worst=110)]
# what the engine had counted by the window's two ends. Over the window:
# 480 tokens in 9.6 lane-seconds (20 ms a token) and 500 lane-steps, 400 of
# them in 6.0 s of clean windows (15 ms a step); the other 100 steps sat in
# 3.6 s, 2.1 s more than their own steps take; 2.4 s of admission episodes
COUNTERS0 = dict(gap_tokens=30, gap_lane_period_s=0.69,
                 gap_lane_admit_s=0.09, gap_lane_steps=32,
                 gap_clean_lane_period_s=0.4, gap_clean_lane_steps=20,
                 gap_admissions=3, admit_dispatches=6, admit_tokens=600,
                 admit_tokens_padded=768)
COUNTERS1 = dict(gap_tokens=510, gap_lane_period_s=10.29,
                 gap_lane_admit_s=2.49, gap_lane_steps=532,
                 gap_clean_lane_period_s=6.4, gap_clean_lane_steps=420,
                 gap_admissions=7, admit_dispatches=30, admit_tokens=1900,
                 admit_tokens_padded=2816)
# seconds the serve loop's admission phases had taken by the two ends
ADMIT_S = (0.2, 1.5)
SUMMARIES = {"tpot": lambda q: q["e"] / (q["n"] - 1),
             "runner_gap": lambda q: q["r"] / (q["n"] - 1),
             "gap_max": lambda q: q["worst"],
             GATEWAY_GAP: lambda q: q["g"] / (q["n"] - 1)}


def _mean(fn, streams=WINDOW):
    return sum(fn(q) for q in streams) / len(streams)


def _snapshots(streams, counters, admit_s):
    """(gateway /api/v1/metrics, runner /health) after ``streams``."""
    gateway, latency = {}, {}
    n = len(streams)
    for part, fn in SUMMARIES.items():
        mean = _mean(fn, streams) / 1e3
        if part == GATEWAY_GAP:
            gateway[part] = {"count": n, "mean": mean}
        else:
            latency[f"{part}_count"] = n
            latency[f"{part}_mean_s"] = mean
    phases = {"engine.admit.dispatch": admit_s * 0.5,
              "engine.first_sync": admit_s * 0.25,
              "engine.window.fanout": 3.0}
    return {"summaries": gateway}, dict(counters, latency=latency,
                                        host_phase_s=phases)


@pytest.fixture()
def ctx():
    gateway0, health0 = _snapshots(BEFORE, COUNTERS0, ADMIT_S[0])
    gateway1, health1 = _snapshots(BEFORE + WINDOW, COUNTERS1, ADMIT_S[1])
    # the client saw each stream's tokens 2 ms a gap later than the gateway
    records = [{"due_s": 1.0 + i, "judged": i % 2 == 0, "ok": True,
                "token_s": [2.0 + i + j * (q["g"] / (q["n"] - 1) + 2.0) / 1e3
                            for j in range(q["n"])]}
               for i, q in enumerate(WINDOW)]
    records += [{"due_s": None, "token_s": [0.5, 0.6], "judged": False,
                 "ok": True},
                {"due_s": 9.0, "token_s": [9.5], "judged": True, "ok": True}]
    return {"gateway0": gateway0, "gateway1": gateway1, "health0": health0,
            "health1": health1, "records": records, "seconds": 10.0,
            "engine": {"max_batch": 2}, "cell": "a-cell"}


def _read(name, ctx):
    return manifest.layer_reader(name).read(ctx)


@pytest.mark.parametrize("name, want", [
    ("engine_tpot_ms", _mean(SUMMARIES["tpot"])),
    ("stream_gap_max_ms", _mean(SUMMARIES["gap_max"])),
    ("tpot_relay_ms", _mean(SUMMARIES[GATEWAY_GAP])
     - _mean(SUMMARIES["tpot"])),
    # deltas, not totals: (3.6 s - 100 steps x 15 ms) over 480 tokens
    ("tpot_admit_stall_ms", 4.375),
    ("decode_period_ms", 15.0),             # 6.0 s over 400 lane-steps
    ("prefill_pad_share", 100.0 * (1 - 1300 / 2048)),
])
def test_a_reader_takes_the_windows_delta(ctx, name, want):
    assert _read(name, ctx) == pytest.approx(want, abs=1e-6)


@pytest.mark.parametrize("name, missing", [
    ("engine_tpot_ms", "tpot"),
    ("stream_gap_max_ms", "gap_max"),
    ("tpot_relay_ms", "tpot"),
    ("tpot_relay_ms", GATEWAY_GAP),
    ("tpot_relay_ms", "runner_gap"),
    ("tpot_admit_stall_ms", "gap_lane_period_s"),
    ("tpot_admit_stall_ms", "gap_lane_steps"),
    ("tpot_admit_stall_ms", "gap_clean_lane_period_s"),
    ("tpot_admit_stall_ms", "gap_clean_lane_steps"),
    ("tpot_admit_stall_ms", "gap_tokens"),
    ("decode_period_ms", "gap_clean_lane_period_s"),
    ("decode_period_ms", "gap_clean_lane_steps"),
    ("prefill_pad_share", "admit_tokens"),
    ("prefill_pad_share", "admit_tokens_padded"),
])
def test_a_reader_finds_nothing_where_its_source_is_missing(ctx, name,
                                                            missing):
    """As on a program that tells no such summary or counter (the parent
    commit): None, and no exception."""
    for end in "01":
        ctx[f"gateway{end}"]["summaries"].pop(missing, None)
        ctx[f"health{end}"].pop(missing, None)
        for key in (f"{missing}_count", f"{missing}_mean_s"):
            ctx[f"health{end}"]["latency"].pop(key, None)
    assert _read(name, ctx) is None


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_on_snapshots_of_an_older_program(ctx, name):
    bare = dict(ctx, gateway0={}, gateway1={"summaries": {}},
                health0={}, health1={"latency": {}})
    assert _read(name, bare) is None


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_a_window_where_nothing_moved(ctx, name):
    """A zero divisor — no token delivered, no clean step, nothing admitted,
    no stream retired — is nothing to read, not a division."""
    still = dict(ctx, gateway1=ctx["gateway0"], health1=ctx["health0"],
                 records=[])
    assert _read(name, still) is None


def test_the_stall_is_what_the_touched_windows_cost_beyond_their_steps(ctx):
    """More decode interleaved inside the same episodes — the touched
    windows' steps up, their seconds as they were — lowers the stall, where
    the admission episodes' own share of a gap would not move."""
    more = copy.deepcopy(ctx)
    more["health1"]["gap_lane_steps"] += 40
    more["health1"]["gap_tokens"] += 40
    assert _read("tpot_admit_stall_ms", more) == pytest.approx(
        (3.6 - 140 * 0.015) / 520 * 1e3)
    assert _read("tpot_admit_stall_ms", more) < _read("tpot_admit_stall_ms",
                                                       ctx)
    # no clean window in the window: no step to hold the others to
    none = copy.deepcopy(ctx)
    for key in ("gap_clean_lane_period_s", "gap_clean_lane_steps"):
        none["health1"][key] = none["health0"][key]
    assert _read("tpot_admit_stall_ms", none) is None
    assert _read("decode_period_ms", none) is None


@pytest.mark.parametrize("gateway, runner, engine, told", [
    (198, 198, 198, True),          # an open loop that drains: mixtral-chat
    (213, 213, 229, True),          # kimi-docs: the cut's 16 sessions
    (255, 224, 256, False),         # mixtral-batch: a loop cut at the end
    (260, 170, 267, False),         # ling-reason, call 113 of PR 57's log
    (261, 167, 266, False),         # and its other run
    (0, 0, 0, False),
])
def test_relay_reads_nothing_where_the_counts_are_of_other_streams(
        ctx, gateway, runner, engine, told):
    """The three hops' summaries have to be of one set of streams, within a
    tenth of the smallest count — ``max_batch`` (128 on ``ling-reason``)
    does not bound it."""
    ctx["engine"]["max_batch"] = 128
    ctx["gateway1"]["summaries"][GATEWAY_GAP]["count"] = 3 + gateway
    ctx["health1"]["latency"]["runner_gap_count"] = 3 + runner
    ctx["health1"]["latency"]["tpot_count"] = 3 + engine
    assert (_read("tpot_relay_ms", ctx) is not None) == told
    assert (_read("engine_tpot_ms", ctx) is not None) == (engine > 0)


@pytest.mark.parametrize("name", NEW)
def test_the_manifest_lists_the_metric_for_every_cell(name):
    """By membership: wherever a later PR puts its own entries."""
    m = manifest.load()
    entry = [e for e in m["per_layer"] if e["name"] == name]
    assert len(entry) == 1
    assert entry[0]["moves"] == "tpot_p50_ms"
    assert entry[0]["layer"] in {e["layer"] for e in m["per_layer"]
                                 if e["name"] not in NEW}
    assert os.path.exists(manifest.layer_reader_path(name))
    # the relay is read where the three hops see one set of streams: not
    # on the loops whose streams are cut at the window's end
    left_out = {"ling-reason", "mixtral-batch", "kimi-docs"} \
        if name == "tpot_relay_ms" else set()
    assert ("workloads" in entry[0]) == bool(left_out)
    for cell in m["workloads"]:
        listed = name in {e["name"] for e in manifest.cell_metrics(
            m, cell["name"], "per_layer")}
        assert listed == (cell["name"] not in left_out)


def test_the_tool_prints_the_waterfall_of_a_saved_run(ctx, tmp_path):
    run_dir = tmp_path / "a-cell.seed1.trace1"
    run_dir.mkdir()
    (run_dir / "context.json").write_text(json.dumps(ctx))
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "tools", "tpot.py"),
         str(run_dir)], capture_output=True, text=True, check=True)
    got = json.loads(out.stdout)
    assert got["cell"] == "a-cell" and "check" not in got      # no trace
    assert list(got["waterfall_ms"]) == ["client", "gateway", "runner",
                                         "engine"]
    w = got["waterfall_ms"]
    assert w["gateway"] == pytest.approx(_mean(SUMMARIES[GATEWAY_GAP]))
    assert w["client"] == pytest.approx(w["gateway"] + 2.0)
    assert w["runner"] == pytest.approx(_mean(SUMMARIES["runner_gap"]))
    assert w["engine"] == pytest.approx(_mean(SUMMARIES["tpot"]))
    assert got["hops_ms"]["client_less_gateway"] == pytest.approx(2.0)
    assert sum(got["hops_ms"].values()) == pytest.approx(
        w["client"] - w["engine"])
    # the engine's gap over the delivered tokens, and its two parts
    assert got["engine_gap_ms"] == pytest.approx(20.0)      # 9.6 s / 480
    assert got["tpot_admit_stall_ms"] == pytest.approx(4.375)
    assert got["decode_period_ms"] == pytest.approx(15.0)
    # the decode part: the clean step for each of the lanes' steps
    assert got["decode_ms"] == pytest.approx(15.0 * 500 / 480)
    assert got["decode_ms"] + got["tpot_admit_stall_ms"] == pytest.approx(
        got["engine_gap_ms"])
    assert got["admit_episode_ms"] == pytest.approx(5.0)
    assert got["stream_gap_max_ms"] == pytest.approx(73.75)
    assert got["admission"]["host_phases_s"] == pytest.approx(1.3 * 0.75)
    assert got["admission"]["admissions"] == 4
    assert got["lanes"] == {"tokens": 480, "lane_steps": 500,
                            "clean_lane_steps": 400}
    assert set(got["observations"].values()) == {4}
    assert got["client_tpot_p50_ms"] == pytest.approx(
        (SUMMARIES[GATEWAY_GAP](WINDOW[0])
         + SUMMARIES[GATEWAY_GAP](WINDOW[2])) / 2 + 2.0)


def test_the_tool_says_nothing_of_an_older_programs_run(ctx, tmp_path):
    bare = dict(ctx, gateway0={}, gateway1={"summaries": {}},
                health0={}, health1={"latency": {}})
    run_dir = tmp_path / "a-cell.seed2.trace1"
    run_dir.mkdir()
    (run_dir / "context.json").write_text(json.dumps(bare))
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "tools", "tpot.py"),
         str(run_dir)], capture_output=True, text=True, check=True)
    got = json.loads(out.stdout)
    assert got["waterfall_ms"]["client"] is not None
    assert got["waterfall_ms"]["engine"] is None
    assert got["engine_gap_ms"] is None and got["decode_ms"] is None


# -- the trace's side, on a hand-made phase line and chip ---------------------

MS = 1e6        # a trace's clock is in nanoseconds


def _tool():
    spec = importlib.util.spec_from_file_location(
        "tpot_tool", os.path.join(manifest.HERE, "tools", "tpot.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _trace():
    """Three clean windows of 4 steps, 40 ms apart; an admission episode of
    60 ms (two chunk programs of 20 ms, a decode window interleaved between
    them, 10 ms of the chip idle under ``engine.first_sync``) before the
    fourth fan-out, whose window is behind it; one more clean window."""
    stats, modules, ops = [], [], []

    def phase(name, a, d, **st):
        stats.append((name, a * MS, d * MS, st))

    def run(name, a, d):
        modules.append((f"{name}(1)", a * MS, d * MS))
        ops.append(("%fusion.1 = f32[8]{0} fusion()", a * MS, d * MS))

    phase("engine.window.dispatch", 0, 1, k=4)
    run("jit_decode", 0, 10)                    # opens the traced span
    t = 0.0
    for i in range(1, 4):                       # fan-outs at 40, 80, 120
        run("jit_decode", t + 2, 36)
        t += 40.0
        phase("engine.window.fanout", t, 1, lanes=2, tokens=8, k=4, clean=1,
              period_us=40000 if i > 1 else 10 ** 9, admit_us=0)
    phase("engine.admit", 122, 40, chunks=2)
    phase("engine.admit.dispatch", 123, 2, g=1)
    run("jit_chunk", 124, 20)
    run("jit_decode", 144, 9)                   # interleaved
    run("jit_chunk", 153, 20)
    phase("engine.first_sync", 162, 18, n=1)    # the chip idle 173 -> 180
    phase("engine.deliver_first", 180, 2)       # the episode ends at 182
    run("jit_lane_splice", 183, 1)              # past it: not counted
    phase("engine.window.fanout", 185, 1, lanes=2, tokens=2, k=1, clean=0,
          period_us=65000, admit_us=60000)
    run("jit_decode", 186, 36)
    phase("engine.window.fanout", 225, 1, lanes=3, tokens=12, k=4, clean=1,
          period_us=40000, admit_us=0)
    run("jit_decode", 226, 30)                  # closes the traced span
    return {"stats": stats, "phases": [e[:3] for e in stats],
            "ops": ops, "modules": modules}


def test_the_tools_check_reads_the_episodes_against_the_chip():
    tool = _tool()
    got = tool.check(_trace(), 9.5)
    # the first fan-out's period starts before the trace: left out
    assert got["fanouts"] == 4 and got["episodes"] == 1
    assert got["admit_ms"] == pytest.approx(60.0)
    assert got["period_ms"] == pytest.approx(40 + 40 + 65 + 40)
    assert got["episodes_ms"] == pytest.approx(60.0)
    dev = got["device_in_episodes_ms"]
    assert dev["prefill"] == pytest.approx(40.0)
    assert dev["decode"] == pytest.approx(9.0)
    # idle inside the episode: 122 -> 124 under engine.admit(.dispatch),
    # 173 -> 182 under first_sync and deliver_first
    assert dev["idle_admit"] == pytest.approx(2.0 + 9.0)
    assert dev["idle_other"] == dev["other"] == 0.0
    assert got["covered_pct"] == pytest.approx(100.0)
    # the clean windows: 3 x 40 ms over 12 steps, beside the trace's step
    assert got["clean_fanouts"] == 3 and got["clean_steps"] == 12
    assert got["clean_period_per_step_ms"] == pytest.approx(10.0)
    assert got["period_over_step_pct"] == pytest.approx(100 * (10 / 9.5 - 1))
    # the one touched fan-out: 65 ms for 1 step of 10, against the
    # episode's two chunks and the chip's 11 ms idle under it
    assert got["stall_ms"] == pytest.approx(55.0)
    assert got["episodes_not_decode_ms"] == pytest.approx(51.0)
    assert got["stall_covered_pct"] == pytest.approx(100 * 51 / 55)
    # the decode runs inside the fan-outs' span, summed and as covered time
    assert got["decode_runs"] == {"runs": 4, "sum_ms": pytest.approx(117.0),
                                  "union_ms": pytest.approx(117.0)}


def test_the_tools_check_says_so_where_the_trace_holds_no_fanout():
    tool = _tool()
    data = _trace()
    older = [(n, a, d, {k: v for k, v in st.items()
                        if k in ("tokens", "k", "g", "n", "chunks")})
             for n, a, d, st in data["stats"]]
    assert tool.check(dict(data, stats=older), 9.5) == {"fanouts": 0}
    assert tool.episodes(data["phases"]) == [(122 * MS, 182 * MS)]
