"""Engine flight recorder + request-lifecycle observability (ISSUE 8):
the bounded per-window ring, engine trace spans under a remote context,
latency decomposition metrics, and the on-demand profiling hook."""

import asyncio

import jax
import pytest

from tpu9.models import init_decoder
from tpu9.models.llama import LLAMA_PRESETS
from tpu9.serving.engine import EngineConfig, InferenceEngine
from tpu9.serving.flight import FlightRecorder


@pytest.fixture(scope="module")
def tiny():
    cfg = LLAMA_PRESETS["llama-tiny"]
    return cfg, init_decoder(jax.random.PRNGKey(0), cfg)


def _engine(tiny, **kw):
    cfg, params = tiny
    base = dict(max_batch=2, max_seq_len=256, prefill_buckets=(32, 64),
                decode_steps=(1, 4), kv_block_size=32, kv_pool_blocks=16,
                prefill_chunk=32)
    base.update(kw)
    return InferenceEngine(params, cfg, EngineConfig(**base))


def _run(coro):
    return asyncio.run(coro)


# ---------------------------------------------------------------------------
# recorder unit behavior
# ---------------------------------------------------------------------------

def test_ring_bounds_and_drop_accounting():
    fr = FlightRecorder(cap=4)
    for i in range(10):
        fr.record("decode", k=i)
    assert len(fr.snapshot()) == 4
    s = fr.summary()
    assert s == {"records": 4, "cap": 4, "recorded": 10, "dropped": 6,
                 "last_seq": 10}
    # oldest records fell off; the tail is the newest 4, oldest-first
    assert [r["k"] for r in fr.snapshot()] == [6, 7, 8, 9]


def test_since_seq_incremental_polling():
    fr = FlightRecorder(cap=16)
    for i in range(6):
        fr.record("decode", k=i)
    first = fr.snapshot(limit=3)
    assert [r["seq"] for r in first] == [4, 5, 6]
    # poll from the last seen seq: only newer records come back
    fr.record("verify", k=9)
    newer = fr.snapshot(since_seq=first[-1]["seq"])
    assert [r["kind"] for r in newer] == ["verify"]
    assert fr.snapshot(since_seq=999) == []


# ---------------------------------------------------------------------------
# engine integration: records, spans, latency, profile
# ---------------------------------------------------------------------------

def test_engine_records_admits_and_windows(tiny):
    eng = _engine(tiny, prefix_cache_blocks=4)

    async def go():
        await eng.start()
        out = await eng.generate(list(range(40)), max_new_tokens=10)
        # same prompt again: the prefix cache should serve blocks
        out2 = await eng.generate(list(range(40)), max_new_tokens=4)
        await eng.stop()
        return out, out2

    out, out2 = _run(go())
    assert len(out) == 10 and len(out2) == 4
    recs = eng.flight_records()
    kinds = [r["kind"] for r in recs]
    assert kinds.count("admit") == 2
    assert "decode" in kinds
    admit2 = [r for r in recs if r["kind"] == "admit"][1]
    assert admit2["prompt_tokens"] == 40
    assert admit2["cached_tokens"] > 0, "prefix reuse must be recorded"
    dec = [r for r in recs if r["kind"] == "decode"][0]
    # per-window evidence: slots + tokens + K + why + KV accounting
    assert dec["batch"] >= 1 and dec["k"] in (1, 4)
    assert dec["pick"] in ("max", "budget", "admission", "interleave")
    assert set(dec["slots"]) == set(dec["tokens"]) or dec["tokens"] == {} \
        or set(dec["tokens"]) <= set(dec["slots"])
    assert dec["wait_s"] >= 0 and dec["host_s"] >= 0
    assert dec["kv_used"] + dec["kv_free"] == 17    # pool + trash block
    assert "prefix_evictions" in dec and "prefix_pinned" in dec
    # stats surface: summary + latency decomposition
    s = eng.stats()
    assert s["flight"]["records"] == len(recs)
    assert s["flight"]["last_seq"] == recs[-1]["seq"]
    lat = s["latency"]
    for phase in ("ttft", "queue_wait", "prefill", "first_hold",
                  "decode_window", "e2e"):
        assert f"{phase}_p50_s" in lat, (phase, lat)
    assert lat["ttft_count"] == 2
    # decomposition sanity at unit scale: queue+prefill ≤ ttft ≤ e2e
    assert lat["ttft_p50_s"] <= lat["e2e_p50_s"]
    assert lat["prefill_p50_s"] <= lat["ttft_p50_s"] * 1.001


def test_engine_spans_under_remote_context(tiny):
    from tpu9.observability.trace import tracer
    eng = _engine(tiny)
    # an id no other file uses: the tracer is process-wide, and whichever
    # test files share an xdist worker share its ring (test_phases.py
    # traces "ab" * 16 with a 100-token prompt)
    trace_id, parent = "f1" * 16, "cd" * 8

    async def go():
        await eng.start()
        out = await eng.generate(list(range(8)), max_new_tokens=6,
                                 trace=(trace_id, parent))
        # untraced request: must record NO spans
        before = len(tracer.finished)
        await eng.generate(list(range(8)), max_new_tokens=2)
        after = len(tracer.finished)
        await eng.stop()
        return out, before, after

    out, before, after = _run(go())
    assert len(out) == 6
    assert before == after, "untraced requests must not create spans"
    spans = tracer.export(trace_id=trace_id)
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp["name"], []).append(sp)
    req = by_name["engine.request"][0]
    assert req["parentSpanId"] == parent
    assert req["attributes"]["prompt_tokens"] == 8
    assert req["attributes"]["tokens_generated"] == 6
    for child in ("engine.queue_wait", "engine.prefill", "engine.decode"):
        assert child in by_name, (child, list(by_name))
        for sp in by_name[child]:
            assert sp["parentSpanId"] == req["spanId"]
            # gapless: children sit inside the request span's interval
            assert sp["startTimeUnixNano"] >= req["startTimeUnixNano"]
            assert sp["endTimeUnixNano"] <= req["endTimeUnixNano"] + 10**6
    # ONE decode span per request, whatever the number of windows
    # (ISSUE 24): per-window detail is the flight recorder's
    assert "engine.decode_window" not in by_name
    assert len(by_name["engine.decode"]) == 1
    dec = by_name["engine.decode"][0]["attributes"]
    assert dec["tokens"] == 5                       # 6 - the first token
    assert 1 <= dec["windows"] <= 5
    assert 0 <= dec["k1_windows"] <= dec["windows"]
    assert dec["interleaved_windows"] == 0
    flight = [r for r in eng.flight_records() if r["kind"] == "decode"
              and dec["request_id"] in r["slots"].values()]
    assert sum(sum(r["tokens"].values()) for r in flight) >= 5
    # the prefill span ends where the decode span may begin
    assert dec["windows"] == sum(
        1 for r in flight if any(r["tokens"].values()))


def test_verify_windows_record_spec_outcome():
    """Speculative windows must record proposed/accepted/rollback — the
    per-window acceptance evidence the EWMA gate is tuned with. Uses the
    test_spec_decode recipe (f32 + a prompt whose greedy trajectory turns
    repetitive early) so speculation actually engages."""
    from dataclasses import replace

    import jax.numpy as jnp
    cfg = replace(LLAMA_PRESETS["llama-tiny"], dtype=jnp.float32)
    params = init_decoder(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_batch=2, max_seq_len=512, prefill_buckets=(32, 64),
        decode_steps=(1, 4), kv_block_size=32, kv_pool_blocks=0,
        prefill_chunk=32, spec_len=4))
    prompt = [7, 8, 9, 7, 8, 9, 7, 8]   # CYCLER: drifts into a short cycle

    async def go():
        await eng.start()
        out = await eng.generate(prompt, max_new_tokens=200)
        await eng.stop()
        return out

    out = _run(go())
    assert len(out) == 200
    assert eng.stats()["spec_windows"] > 0, eng.stats()
    vers = [r for r in eng.flight_records() if r["kind"] == "verify"]
    assert vers, "repetitive generation must dispatch verify windows"
    v = vers[-1]
    assert v["spec_proposed"] >= v["spec_accepted"] >= 0
    assert v["spec_rollback"] == v["spec_proposed"] - v["spec_accepted"]
    assert v["spec_len"] == 4 and v["k"] == 5
    assert v["pick"] == "spec"


def test_flight_disabled_is_inert(tiny):
    eng = _engine(tiny, flight_cap=0)

    async def go():
        await eng.start()
        out = await eng.generate(list(range(8)), max_new_tokens=4)
        await eng.stop()
        return out

    assert len(_run(go())) == 4
    assert eng.flight is None
    assert eng.flight_records() == []
    assert "flight" not in eng.stats()


def test_arm_profile_runs_and_stops(tiny):
    """``arm_profile(seconds=...)`` (ISSUE 24): returns at once, traces
    from a worker thread — the serve loop keeps serving meanwhile — refuses
    a second arm, and stops on its own after the stated seconds."""
    import os
    import time
    eng = _engine(tiny)

    async def go():
        await eng.start()
        t0 = time.monotonic()
        info = eng.arm_profile(seconds=1.0)
        assert time.monotonic() - t0 < 0.5, "arming must not trace inline"
        assert eng.stats()["profile"]["active"] is True
        # double-arm reports the in-flight one instead of clobbering it
        again = eng.arm_profile(seconds=5)
        assert again.get("already_armed") and again["path"] == info["path"]
        # the loop is not held: requests are served while the trace runs
        out = await asyncio.wait_for(
            eng.generate(list(range(8)), max_new_tokens=12), 30)
        assert len(out) == 12
        for _ in range(200):
            if not eng.stats()["profile"]["active"]:
                break
            await asyncio.sleep(0.05)
        # it stops on its own (live replicas never call stop())
        assert not eng.stats()["profile"]["active"], "profile never stopped"
        await eng.stop()
        return info

    info = _run(go())
    s = eng.stats()["profile"]
    assert s["active"] is False and s["seconds"] == 1.0
    assert s["error"] == "", s
    assert s["path"] == info["path"] and os.path.isdir(info["path"])
    events = [r for r in eng.flight_records() if r["kind"] == "profile"]
    assert [e["event"] for e in events] == ["armed", "stopped"]
    assert 0.9 <= events[1]["traced_s"] <= 3.0 and events[1]["error"] == ""

    with pytest.raises(ValueError):
        eng.arm_profile(seconds=0)


def test_arm_profile_is_cut_short_by_stop_and_can_be_armed_again(tiny):
    """An armed profile must not outlive the engine: ``stop()`` cuts the
    stated seconds short and waits for the dump; afterwards the hook is
    re-armable (not already_armed forever)."""
    import time
    eng = _engine(tiny)

    async def go():
        await eng.start()
        info = eng.arm_profile(seconds=600)
        await eng.generate(list(range(8)), max_new_tokens=6)
        t0 = time.monotonic()
        await eng.stop()
        assert time.monotonic() - t0 < 60
        assert not eng.stats()["profile"]["active"]
        again = eng.arm_profile(seconds=0.2)
        assert not again.get("already_armed"), again
        assert again["path"] != info["path"]
        eng._profile_thread.join(60)
        return info

    info = _run(go())
    events = [r for r in eng.flight_records() if r["kind"] == "profile"]
    stops = [e for e in events if e["event"] == "stopped"]
    assert len(stops) == 2 and stops[0]["path"] == info["path"]
    assert stops[0]["traced_s"] < 60          # cut short, honestly
    assert all(e["error"] == "" for e in stops)
