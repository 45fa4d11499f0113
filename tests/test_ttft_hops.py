"""A streamed request's time to first token, hop by hop (ISSUE 41): the
gateway's and the runner's legs as nested intervals beside the engine's —
one summary observation and one span an interval, once a request, nothing a
token. Driven the way ``tests/test_e2e_llm.py`` streams: a tiny engine behind
a real runner container and the gateway's stream path; one stack serves the
whole module, and the tests read what it left behind."""

import asyncio
import json
import os
import time

import aiohttp
import pytest
from aiohttp import web

from tpu9.gateway.gateway import Gateway
from tpu9.testing.localstack import LocalStack

pytestmark = pytest.mark.e2e

LLM_APP = """
def load_engine():
    from dataclasses import replace
    import jax
    from tpu9.models import init_decoder
    from tpu9.models.llama import LLAMA_PRESETS
    from tpu9.serving import EngineConfig, InferenceEngine

    cfg = replace(LLAMA_PRESETS["llama-tiny"])
    params = init_decoder(jax.random.PRNGKey(0), cfg)
    return InferenceEngine(params, cfg,
                           EngineConfig(max_batch=2, max_seq_len=256,
                                        prefill_buckets=(16, 64),
                                        kv_block_size=16))
"""

GATEWAY = {"pre": "tpu9_gateway_stream_pre_s",
           "connect": "tpu9_gateway_stream_connect_s",
           "first": "tpu9_gateway_stream_first_s"}
RUNNER = ("ingest", "runner_first")         # /health latency.<x>_*
ENGINE = ("ttft", "stream_lag", "queue_wait", "prefill", "first_hold")
# a token's gap, the last token's hops as the first's (ISSUE 57)
GAP = "tpu9_gateway_stream_gap_s"
GAPS = ("runner_gap", "tpot", "gap_max")    # /health latency.<x>_*
N_STREAMS = 5
N_TOKENS = 16
PROMPT = [5, 3, 9, 4]


def _gateway_totals(snap: dict) -> dict:
    """{part: (count, seconds)} of the three stream summaries."""
    out = {}
    for part, name in GATEWAY.items():
        s = snap["summaries"].get(name)
        out[part] = (s["count"], s["mean"] * s["count"]) if s else (0, 0.0)
    return out


def _latency_totals(health: dict) -> dict:
    lat = health.get("latency") or {}
    return {part: (lat.get(f"{part}_count", 0),
                   lat.get(f"{part}_mean_s", 0.0) * lat.get(f"{part}_count", 0))
            for part in RUNNER + ENGINE + GAPS}


def _delta(a: dict, b: dict) -> dict:
    return {k: (b[k][0] - a[k][0], b[k][1] - a[k][1]) for k in b}


async def _stream(stack, endpoint: str, max_new: int, on_tokens=None) -> dict:
    """One streamed request through the gateway: the tokens, the response's
    trace id, and the client's own stamps."""
    tokens, done = [], None
    t_send = time.monotonic()
    t_first = None
    async with aiohttp.ClientSession() as sess:
        async with sess.post(
                stack.base_url + endpoint,
                json={"tokens": PROMPT, "max_new_tokens": max_new,
                      "stream": True},
                headers={"Accept": "text/event-stream",
                         "Authorization":
                         f"Bearer {stack.gateway.default_token}"},
                timeout=aiohttp.ClientTimeout(total=240)) as resp:
            assert resp.status == 200, await resp.text()
            trace_id = resp.headers.get("X-Tpu9-Trace-Id", "")
            buf = b""
            async for chunk in resp.content.iter_any():
                buf += chunk
                while b"\n\n" in buf:
                    frame, buf = buf.split(b"\n\n", 1)
                    if not frame.startswith(b"data: "):
                        continue
                    ev = json.loads(frame[6:])
                    assert "error" not in ev, ev
                    if "token" in ev:
                        if t_first is None:
                            t_first = time.monotonic()
                        tokens.append(ev["token"])
                    elif ev.get("done"):
                        done = ev
                if on_tokens is not None:
                    on_tokens(len(tokens))
    assert done is not None and done["tokens"] == tokens
    return {"tokens": tokens, "trace_id": trace_id,
            "client_ttft_s": t_first - t_send}


async def _spans_of(stack, trace_id: str, want: set) -> list:
    """The merged trace once the runner's ring has shipped (its pressure
    heartbeat carries it; a finished request nudges a beat)."""
    deadline = time.monotonic() + 30.0
    while True:
        status, out = await stack.api(
            "GET", f"/api/v1/traces?trace_id={trace_id}")
        assert status == 200, out
        if want <= {s["name"] for s in out["spans"]} \
                or time.monotonic() > deadline:
            return out["spans"]
        await asyncio.sleep(0.25)


SPAN_NAMES = {"gateway.invoke", "gateway.pre_forward", "gateway.connect",
              "gateway.first_token", "runner.ingest", "runner.first_token",
              "engine.request", "engine.queue_wait", "engine.prefill",
              "engine.first_hold", "engine.decode",
              "gateway.stream", "runner.stream"}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """What a handful of streamed requests, and then one that fails over
    mid-stream, leave behind."""
    flag_dir = str(tmp_path_factory.mktemp("flags"))
    entries, first_writes = [], []
    mp = pytest.MonkeyPatch()

    # the test's own stamps of the gateway's leg: the first gateway code to
    # see a request, and `write` having returned for a response's first
    # token event (the runner is another process: these are the gateway's)
    real_entry, real_write = Gateway._quota_middleware, web.StreamResponse.write

    @web.middleware
    async def entry(self, request, handler):
        if request.path.startswith("/endpoint/") and request.method == "POST":
            entries.append(time.monotonic())
        return await real_entry(self, request, handler)

    async def write(self, data):
        await real_write(self, data)
        if b'"token"' in data and not getattr(self, "_t9_first", False):
            self._t9_first = True
            first_writes.append(time.monotonic())

    mp.setattr(Gateway, "_quota_middleware", entry)
    mp.setattr(web.StreamResponse, "write", write)

    async def health_of(stack, name):
        status, h = await stack.api("GET", f"/endpoint/{name}/health")
        assert status == 200, h
        return h

    async def metrics_of(stack):
        status, m = await stack.api("GET", "/api/v1/metrics")
        assert status == 200, m
        return m

    async def go():
        got = {}
        async with LocalStack() as stack:
            await stack.deploy_endpoint(
                "hops", {"app.py": LLM_APP}, "app:load_engine",
                config_extra={"timeout_s": 240.0,
                              "extra": {"runner": "llm"},
                              "env": {"TPU9_PRESSURE_INTERVAL_S": "0.5"},
                              "autoscaler": {"max_containers": 1}})
            # compile everything a stream will use, outside the counts
            await _stream(stack, "/endpoint/hops", N_TOKENS)
            del entries[:], first_writes[:]
            got["gateway0"] = await metrics_of(stack)
            got["health0"] = await health_of(stack, "hops")
            got["streams"] = [await _stream(stack, "/endpoint/hops", N_TOKENS)
                              for _ in range(N_STREAMS)]
            got["gateway1"] = await metrics_of(stack)
            got["health1"] = await health_of(stack, "hops")
            got["gateway_leg_s"] = [b - a for a, b
                                    in zip(entries, first_writes)]
            got["spans"] = await _spans_of(
                stack, got["streams"][-1]["trace_id"], SPAN_NAMES)

            # a second deployment of two replicas; the one serving the next
            # stream dies once the client holds five of its tokens
            dep = await stack.deploy_endpoint(
                "hops2", {"app.py": LLM_APP}, "app:load_engine",
                config_extra={"timeout_s": 240.0, "concurrent_requests": 2,
                              "extra": {"runner": "llm"},
                              "env": {"TPU9_FAULTS": "crash:flag=1",
                                      "TPU9_FAULTS_FLAG_DIR": flag_dir,
                                      "TPU9_PRESSURE_INTERVAL_S": "0.5"},
                              "autoscaler": {"max_containers": 2,
                                             "min_containers": 2}})
            await stack.wait_running(dep["stub_id"], 2, timeout=120.0)
            for _ in range(4):      # both replicas compiled
                await _stream(stack, "/endpoint/hops2", 4)
            router = stack.gateway.fleet_router
            victim = []

            def kill_the_server(n_tokens):
                if not victim and n_tokens >= 5:
                    live = [cid for cid, n
                            in router.budgets._inflight.items() if n > 0]
                    assert len(live) == 1, live
                    victim.append(live[0])
                    open(os.path.join(flag_dir, f"crash-{live[0]}"),
                         "w").close()

            got["gateway2"] = await metrics_of(stack)
            got["failover"] = await _stream(stack, "/endpoint/hops2", 200,
                                            on_tokens=kill_the_server)
            got["gateway3"] = await metrics_of(stack)
            got["failover_spans"] = await _spans_of(
                stack, got["failover"]["trace_id"], {"gateway.failover"})
        return got

    try:
        return asyncio.run(go())
    finally:
        mp.undo()


@pytest.mark.parametrize("part", GATEWAY)
def test_a_streamed_request_observes_each_gateway_summary_once(served, part):
    d = _delta(_gateway_totals(served["gateway0"]),
               _gateway_totals(served["gateway1"]))
    # N_TOKENS tokens a stream: an observation a token would read 16 x
    assert d[part][0] == N_STREAMS, d


@pytest.mark.parametrize("part", RUNNER)
def test_a_streamed_request_observes_each_runner_summary_once(served, part):
    d = _delta(_latency_totals(served["health0"]),
               _latency_totals(served["health1"]))
    assert d[part][0] == N_STREAMS, d
    assert d["ttft"][0] == d["stream_lag"][0] == N_STREAMS, d


def test_the_gateway_parts_add_up_to_its_leg(served):
    """pre + connect + first == entry -> the first token written, as the
    test stamped them, within 2 ms a request."""
    d = _delta(_gateway_totals(served["gateway0"]),
               _gateway_totals(served["gateway1"]))
    legs = served["gateway_leg_s"]
    assert len(legs) == N_STREAMS
    parts = sum(d[p][1] for p in GATEWAY)
    assert abs(parts - sum(legs)) / N_STREAMS < 2e-3, (d, legs)
    # and the client's own time to first token contains the gateway's leg
    for leg, s in zip(legs, served["streams"]):
        assert s["client_ttft_s"] >= leg - 1e-4, (leg, s)


def test_the_hops_nest(served):
    g = _delta(_gateway_totals(served["gateway0"]),
               _gateway_totals(served["gateway1"]))
    r = _delta(_latency_totals(served["health0"]),
               _latency_totals(served["health1"]))
    # the runner's ingest lies inside the gateway's send -> headers back
    assert g["connect"][1] >= r["ingest"][1], (g, r)
    # headers -> first token written is what the engine calls ttft plus the
    # stream lag, less the step from the enqueue to the headers: 5 ms
    runner_first = r["runner_first"][1] / N_STREAMS
    engine = (r["ttft"][1] + r["stream_lag"][1]) / N_STREAMS
    assert abs(runner_first - engine) < 5e-3, r
    # and the engine's three parts are its ttft (test_phases.py holds the
    # engine to that; here through a real runner)
    parts = (r["queue_wait"][1] + r["prefill"][1] + r["first_hold"][1]) \
        / N_STREAMS
    assert abs(parts - r["ttft"][1] / N_STREAMS) < 5e-3, r
    # the first token leaves the runner before the gateway has relayed it
    assert g["first"][1] + g["connect"][1] \
        >= r["runner_first"][1] + r["ingest"][1], (g, r)


def test_a_failover_attempt_observes_nothing_again(served):
    assert len(served["failover"]["tokens"]) == 200
    assert [s["name"] for s in served["failover_spans"]].count(
        "gateway.failover") == 1
    d = _delta(_gateway_totals(served["gateway2"]),
               _gateway_totals(served["gateway3"]))
    assert {p: d[p][0] for p in GATEWAY} == dict.fromkeys(GATEWAY, 1), d
    names = [s["name"] for s in served["failover_spans"]]
    for name in ("gateway.pre_forward", "gateway.connect",
                 "gateway.first_token"):
        assert names.count(name) == 1, names


def _gap_total(snap: dict) -> tuple:
    s = snap["summaries"].get(GAP)
    return (s["count"], s["mean"] * s["count"]) if s else (0, 0.0)


def test_a_streams_gap_is_told_once_a_hop(served):
    """One observation a stream at the gateway, the runner and the engine
    (N_TOKENS tokens a stream: one a token would read 16 x), and the three
    nest: each hop's interval opens after and closes before the next
    outer one's, over the same tokens less one."""
    n0, t0 = _gap_total(served["gateway0"])
    n1, t1 = _gap_total(served["gateway1"])
    assert n1 - n0 == N_STREAMS
    r = _delta(_latency_totals(served["health0"]),
               _latency_totals(served["health1"]))
    assert {part: r[part][0] for part in GAPS} == dict.fromkeys(
        GAPS, N_STREAMS), r
    sp = {s["name"]: s for s in served["spans"]}
    for name in ("gateway.stream", "runner.stream"):
        assert sp[name]["attributes"]["tokens"] == N_TOKENS, sp[name]
    dec = sp["engine.decode"]["attributes"]
    assert dec["tokens"] == N_TOKENS - 1
    assert dec["admissions_behind"] == 0 and dec["admit_stall_ms"] == 0
    assert dec["gap_max_ms"] >= dec["gap_mean_ms"] > 0
    # a stream alone: its decode span IS its gaps, first token -> last
    assert sp["engine.decode"]["durationMs"] == pytest.approx(
        dec["gap_mean_ms"] * (N_TOKENS - 1), abs=0.02)


def test_a_failover_tells_no_gap(served):
    """The first attempt died before its done event and the second is not
    the first: neither tells the stream's gap (its tokens came from two
    replicas, with a failover between)."""
    assert _gap_total(served["gateway3"])[0] \
        == _gap_total(served["gateway2"])[0]
    assert "gateway.stream" not in [
        s["name"] for s in served["failover_spans"]]


def test_the_response_names_its_trace(served):
    ids = [s["trace_id"] for s in served["streams"]]
    assert all(len(i) == 32 for i in ids) and len(set(ids)) == N_STREAMS
    assert {s["traceId"] for s in served["spans"]} == {ids[-1]}


@pytest.mark.parametrize("name", sorted(SPAN_NAMES))
def test_one_trace_holds_every_hop(served, name):
    spans = served["spans"]
    hit = [s for s in spans if s["name"] == name]
    assert len(hit) == 1, sorted(s["name"] for s in spans)
    sp = hit[0]
    assert sp["durationMs"] >= 0
    assert sp["endTimeUnixNano"] >= sp["startTimeUnixNano"]
    by_name = {s["name"]: s for s in spans}
    root = by_name["gateway.invoke"]
    if name == "gateway.invoke":
        assert sp["parentSpanId"] == ""
    elif name.startswith(("gateway.", "runner.")) or name == "engine.request":
        assert sp["parentSpanId"] == root["spanId"], sp
    else:
        assert sp["parentSpanId"] == by_name["engine.request"]["spanId"], sp


def test_the_trace_reads_as_a_waterfall(served):
    sp = {s["name"]: s for s in served["spans"]}

    def ms(name):
        return sp[name]["durationMs"]

    def starts(name):
        return sp[name]["startTimeUnixNano"]

    # the root covers entry -> headers; its first two children tile it and
    # the third starts where it ends (and outlives it)
    assert abs(ms("gateway.invoke")
               - ms("gateway.pre_forward") - ms("gateway.connect")) < 1.0
    assert starts("gateway.pre_forward") == starts("gateway.invoke")
    assert starts("gateway.pre_forward") <= starts("gateway.connect") \
        <= starts("gateway.first_token")
    # client > gateway > runner > engine, each on its own process's clock
    assert ms("gateway.connect") >= ms("runner.ingest")
    assert ms("runner.first_token") + 5.0 >= ms("engine.queue_wait") \
        + ms("engine.prefill") + ms("engine.first_hold")
    attrs = sp["gateway.pre_forward"]["attributes"]
    assert attrs["admit_s"] >= 0 and attrs["acquire_s"] >= 0
    assert attrs["admit_s"] + attrs["acquire_s"] \
        <= ms("gateway.pre_forward") / 1e3 + 1e-3
    attrs = sp["runner.ingest"]["attributes"]
    assert attrs["prompt_tokens"] == len(PROMPT)
    assert 0 <= attrs["parse_s"] <= ms("runner.ingest") / 1e3 + 1e-6
