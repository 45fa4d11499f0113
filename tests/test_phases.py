"""Host phases, device scopes and the TTFT decomposition (ISSUE 24): the
``phase`` primitive, the serve loop's phases on the host plane of a profiler
trace, ``jax.named_scope`` names in the lowered programs, the two
per-request summaries, and the map from HLO instructions back to scopes."""

import asyncio
import glob
import os
import re
import time

import jax
import pytest

from tpu9.models import init_decoder
from tpu9.models.llama import LLAMA_PRESETS
from tpu9.models.transformer import DEVICE_SCOPES
from tpu9.observability import trace as trace_mod
from tpu9.observability.trace import PhaseTotals, phase
from tpu9.serving.engine import EngineConfig, InferenceEngine
from tpu9.serving.graphs import hlo_scopes

ENGINE = dict(max_batch=2, max_seq_len=256, prefill_buckets=(32, 64),
              decode_steps=(1, 4), kv_block_size=32, kv_pool_blocks=16,
              prefill_chunk=32, prefix_cache_blocks=4)


@pytest.fixture(scope="module")
def tiny():
    cfg = LLAMA_PRESETS["llama-tiny"]
    return cfg, init_decoder(jax.random.PRNGKey(0), cfg)


def _engine(tiny, **kw):
    cfg, params = tiny
    return InferenceEngine(params, cfg, EngineConfig(**dict(ENGINE, **kw)))


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------

def test_phase_totals_are_self_time_and_counts():
    totals = PhaseTotals()
    with phase("outer", totals, k=1):
        time.sleep(0.02)
        for _ in range(2):
            with phase("inner", totals) as ph:
                time.sleep(0.01)
                ph.set(tokens=3)
    assert totals["outer"][0] == 1 and totals["inner"][0] == 2
    assert 0.02 <= totals["inner"][1] < 0.04
    # the outer phase's seconds leave out what ran inside it
    assert 0.02 <= totals["outer"][1] < 0.035
    assert totals.open == []


def test_phase_closes_on_an_exception_and_keeps_the_table_whole():
    totals = PhaseTotals()
    with pytest.raises(KeyError):
        with phase("outer", totals):
            with phase("inner", totals):
                raise KeyError("x")
    assert totals.open == []
    assert totals["outer"][0] == totals["inner"][0] == 1


def test_phase_without_a_table_or_a_profiler_records_nothing():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    ring = len(trace_mod.tracer.finished)
    with phase("engine.window.dispatch", k=8) as ph:
        ph.set(batch=3)
    assert len(trace_mod.tracer.finished) == ring     # no span, no id
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        with phase("engine.yield"):
            pass
    assert (time.perf_counter() - t0) / n < 50e-6      # microseconds, not ms


def test_phase_is_a_no_op_where_jax_is_absent(monkeypatch):
    monkeypatch.setattr(trace_mod, "_annotation", trace_mod._no_annotation)
    totals = PhaseTotals()
    with phase("runner.heartbeat", totals, spans=2) as ph:
        ph.set(spans=3)
    assert totals["runner.heartbeat"][0] == 1


# ---------------------------------------------------------------------------
# the serve loop's phases on the profiler's clock
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced(tiny, tmp_path_factory):
    """A tiny engine serving one traced request under ``arm_profile``."""
    eng = _engine(tiny)
    eng.warmup()
    out_dir = str(tmp_path_factory.mktemp("profile"))
    trace_id = "ab" * 16

    async def go():
        await eng.start()
        info = eng.arm_profile(seconds=30, out_dir=out_dir)
        await asyncio.sleep(0.2)            # the trace has started
        await eng.generate(list(range(100)), max_new_tokens=10,
                           trace=(trace_id, "cd" * 8))
        await eng.stop()                    # cuts the armed seconds short
        return info

    info = asyncio.run(go())
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            found = [(ev.name, dict(ev.stats)) for ev in line.events
                     if ev.name.startswith(("engine.", "runner."))]
            if found:
                events.append(found)
    return {"engine": eng, "info": info, "lines": events,
            "trace_id": trace_id}


@pytest.mark.parametrize("name", [
    "engine.admit", "engine.admit.lookup", "engine.admit.plan",
    "engine.admit.dispatch", "engine.admit.finish", "engine.first_sync",
    "engine.deliver_first", "engine.window.dispatch", "engine.window.sync",
    "engine.window.fanout", "engine.yield"])
def test_serve_loop_phase_is_on_the_host_plane(traced, name):
    assert len(traced["lines"]) == 1, "phases belong to one thread's line"
    assert name in {n for n, _ in traced["lines"][0]}


def test_phase_events_carry_their_attributes(traced):
    by_name = {}
    for name, stats in traced["lines"][0]:
        by_name.setdefault(name, []).append(stats)
    admit = by_name["engine.admit"][0]
    assert admit["request_id"] == traced["trace_id"]
    assert admit["prompt_tokens"] == 100 and admit["chunks"] == 4
    assert admit["cached_tokens"] == 0
    window = by_name["engine.window.dispatch"][0]
    assert window["kind"] == "decode" and window["k"] in (1, 4)
    assert window["batch"] == 1 and window["pick"]
    assert sum(s["tokens"] for s in by_name["engine.window.fanout"]) == 9
    assert by_name["engine.first_sync"][0]["n"] == 1
    assert by_name["engine.window.sync"][0]["windows"] >= 1


def test_host_phase_totals_reach_stats(traced):
    s = traced["engine"].stats()
    for name in ("engine.admit", "engine.window.dispatch",
                 "engine.window.sync", "engine.window.fanout",
                 "engine.yield", "engine.park"):
        assert s["host_phase_n"][name] >= 1, name
        assert s["host_phase_s"][name] >= 0.0
    assert s["host_phase_n"]["engine.admit.dispatch"] == 1     # one group
    assert set(s["host_phase_s"]) == set(s["host_phase_n"])


def test_profile_dump_holds_the_scope_maps(traced):
    assert traced["engine"].stats()["profile"]["error"] == ""
    # this engine compiled nothing ahead: no maps, no file — no error
    assert not os.path.exists(os.path.join(traced["info"]["path"],
                                           "device_scopes.json"))


# ---------------------------------------------------------------------------
# TTFT decomposition
# ---------------------------------------------------------------------------

def test_first_hold_and_stream_lag_reach_the_latency_summary(tiny):
    eng = _engine(tiny)

    async def go():
        await eng.start()
        req = await eng.generate(list(range(40)), max_new_tokens=4,
                                 stream=True)
        first = await req.queue.get()
        eng.note_first_write(req)           # what the runner's handler does
        toks = [first]
        while (tok := await req.queue.get()) is not None:
            toks.append(tok)
        await eng.stop()
        return toks

    assert len(asyncio.run(go())) == 4
    lat = eng.stats()["latency"]
    for part in ("queue_wait", "prefill", "first_hold", "stream_lag"):
        assert lat[f"{part}_count"] == 1, part
    # the engine's TTFT is its three parts, to the clock reads between them
    parts = sum(lat[f"{p}_mean_s"]
                for p in ("queue_wait", "prefill", "first_hold"))
    assert parts == pytest.approx(lat["ttft_mean_s"], abs=2e-3)
    # the lag lies inside the request's own lifetime; a bound in seconds
    # would be a bound on how busy the test machine is (1.4-2.0 s read
    # under ten busy processes; PR 34)
    assert 0.0 <= lat["stream_lag_mean_s"] <= lat["e2e_mean_s"]


# ---------------------------------------------------------------------------
# device scopes
# ---------------------------------------------------------------------------

def _lowered_text(eng) -> dict:
    g = eng.graphs
    return {str(key): fn.lower(*args).as_text(debug_info=True)
            for key, fn, args in g.lowering_jobs(
                eng.params, eng.kv_cache, eng._pool_dict(), eng._scratch,
                eng._mb, eng._buckets, eng._spec_lens, eng._rng)}


@pytest.fixture(scope="module")
def lowered(tiny):
    dense = _lowered_text(_engine(tiny))
    from tpu9.models.mixtral import MIXTRAL_PRESETS
    cfg = MIXTRAL_PRESETS["mixtral-tiny"]
    moe = InferenceEngine(init_decoder(jax.random.PRNGKey(0), cfg), cfg,
                          EngineConfig(**ENGINE))
    return {"dense": dense, "moe": _lowered_text(moe)}


DECODE = ("embed", "attn.qkv", "attn.rope", "kv.write", "attn.core",
          "attn.out", "head", "sample")
CHUNK = ("embed", "attn.qkv", "attn.rope", "kv.slice", "kv.write",
         "attn.core", "attn.out", "head")
CASES = [("dense", "('decode', 4)", s) for s in DECODE + ("ffn",)] \
    + [("dense", "('decode', 1)", s) for s in DECODE + ("ffn",)] \
    + [("dense", "('chunk', 32)", s) for s in CHUNK + ("ffn",)] \
    + [("dense", "('chunkgroup', 4)", s) for s in CHUNK + ("kv.splice",)] \
    + [("dense", "splice", "kv.splice"), ("dense", "gather", "kv.gather")] \
    + [("moe", "('decode', 4)", s)
       for s in ("moe.route", "moe.experts", "moe.combine")] \
    + [("moe", "('chunk', 32)", s)
       for s in ("moe.route", "moe.experts", "moe.combine")]
# since ISSUE 25 the cache is carried whole: no program stacks planes back
# (``kv.pack``), and a decode program cuts none out (``kv.slice``) — the
# paged kernel reads the pool at its layer. The chunk programs still read
# ``scratch[layer]`` for an XLA attention, which fuses the slice.
ABSENT = [("dense", f"('decode', {k})", s)
          for k in (4, 1) for s in ("kv.slice", "kv.pack")] \
    + [("dense", "('chunk', 32)", "kv.pack"),
       ("dense", "('chunkgroup', 4)", "kv.pack")]


def _scope_in(text: str, scope: str):
    # a path component of a location: loc("kv.write/scatter"(...)),
    # loc("jit(decode)/.../sample"(...))
    return re.search(rf'["/]{re.escape(scope)}["/]', text)


@pytest.mark.parametrize("model,program,scope", CASES)
def test_scope_reaches_the_lowered_program(lowered, model, program, scope):
    assert _scope_in(lowered[model][program], scope)


@pytest.mark.parametrize("model,program,scope", ABSENT)
def test_scope_is_gone_from_the_lowered_program(lowered, model, program,
                                                scope):
    assert not _scope_in(lowered[model][program], scope)


def test_every_declared_scope_is_checked_somewhere():
    assert {s for _, _, s in CASES + ABSENT} == set(DEVICE_SCOPES)


HLO = '''HloModule jit_decode

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(decode)/while/body/attn.qkv/mul"}
  ROOT %convert.2 = f32[8]{0} convert(%mul.1)
}

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  ROOT %add.3 = f32[8]{0} add(%p.1, %p.1), metadata={op_name="jit(decode)/while/body/ffn/add"}
}

%body (arg: (f32[8])) -> (f32[8]) {
  %arg = (f32[8]{0}) parameter(0)
  %gte = f32[8]{0} get-tuple-element(%arg), index=0, metadata={op_name="jit(decode)/while/body/kv.slice/squeeze"}
  %fusion = f32[8]{0} fusion(%gte), kind=kLoop, calls=%fused_computation
  %fusion.1 = f32[8]{0} fusion(%fusion), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(decode)/while/body/moe.experts/sample/dot_general"}
  %copy.7 = f32[8]{0} copy(%fusion.1), metadata={op_name="jit(decode)/while/body/closed_call/kv.write/scatter"}
  %custom-call.2 = f32[8]{0} custom-call(%copy.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode)/while/body/attn.core/pallas_call"}
  %negate.9 = f32[8]{0} negate(%custom-call.2), metadata={op_name="jit(decode)/while/body/neg"}
  %copy-start.4 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%negate.9)
  %copy-done.4 = f32[8]{0} copy-done(%copy-start.4)
  %slice-start.6 = ((f32[8]{0}), f32[4]{0}, s32[]) slice-start(%arg)
  %slice-done.6 = f32[4]{0} slice-done(%slice-start.6)
  %fusion.8 = f32[8]{0} fusion(%copy-done.4, %slice-done.6), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(decode)/while/body/ffn/dot_general"}
  %copy-done.5 = f32[8]{0} copy-done(%fusion.8)
  ROOT %tuple = (f32[8]{0}) tuple(%negate.9)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %while = (f32[8]{0}) while(%x), condition=%cond, body=%body
  ROOT %out = f32[8]{0} get-tuple-element(%while), index=0
}
'''


def test_hlo_scopes_on_hand_made_text():
    got = hlo_scopes(HLO)
    assert got == {
        "attn.qkv": ["fusion"],             # no op_name: its body's root's
        "sample": ["fusion.1"],             # the innermost scope wins
        "kv.write": ["copy.7"],
        "attn.core": ["custom-call.2"],
        # the compiler's own instructions: an asynchronous copy and a
        # weight prefetch belong to what they feed, a copy out to what
        # made its operand
        "ffn": ["copy-done.4", "slice-done.6", "fusion.8", "copy-done.5"]}
    # what never runs alone (parameters, tuple plumbing, the -start half of
    # an asynchronous pair), what sits inside a fusion, and what the program
    # named without a scope (negate.9: it takes no neighbour's) are left out
    named = {n for names in got.values() for n in names}
    assert not named & {"gte", "mul.1", "add.3", "negate.9", "x", "while",
                        "copy-start.4", "slice-start.6"}
    assert hlo_scopes("HloModule empty\n") == {}


def test_precompile_reports_the_scope_maps(tiny):
    eng = _engine(tiny)
    eng.precompile()
    maps = eng.stats()["device_scopes"]
    assert {"decode_1", "decode_4", "chunk_32", "gather", "splice",
            "chunkgroup_4"} <= set(maps)
    for program in ("decode_1", "decode_4"):
        assert {"attn.core", "kv.write", "ffn"} <= set(maps[program])
        names = [n for v in maps[program].values() for n in v]
        assert len(names) == len(set(names))    # one scope per instruction
    assert "device_scopes" not in _engine(tiny).stats()   # nothing compiled
