"""E2E: LLM endpoint (baseline config #2 path) — the llm runner hosts a tiny
continuous-batching engine inside a real container; requests flow
gateway → buffer → engine; pressure heartbeats feed the router table."""

import asyncio

import pytest

from tpu9.testing.localstack import LocalStack

pytestmark = pytest.mark.e2e

LLM_APP = """
def load_engine():
    # tiny random-weight model; the runner wraps it in an InferenceEngine
    from dataclasses import replace
    import jax
    from tpu9.models import init_decoder
    from tpu9.models.llama import LLAMA_PRESETS
    from tpu9.serving import EngineConfig, InferenceEngine

    cfg = replace(LLAMA_PRESETS["llama-tiny"])
    params = init_decoder(jax.random.PRNGKey(0), cfg)
    return InferenceEngine(params, cfg,
                           EngineConfig(max_batch=2, max_seq_len=128,
                                        prefill_buckets=(16, 64)))
"""


@pytest.mark.slow
async def test_llm_endpoint_generates_and_heartbeats():
    async with LocalStack() as stack:
        dep = await stack.deploy_endpoint(
            "llm", {"app.py": LLM_APP}, "app:load_engine",
            config_extra={
                "timeout_s": 240.0,
                "extra": {"runner": "llm"},
                "autoscaler": {"type": "token_pressure",
                               "max_containers": 2}})
        status, out = await stack.api(
            "POST", "/endpoint/llm",
            json_body={"tokens": [5, 3, 9], "max_new_tokens": 8},
            timeout=240)
        assert status == 200, out
        assert len(out["tokens"]) == 8
        assert all(isinstance(t, int) for t in out["tokens"])

        # deterministic greedy: same prompt → same completion
        status, out2 = await stack.api(
            "POST", "/endpoint/llm",
            json_body={"tokens": [5, 3, 9], "max_new_tokens": 8},
            timeout=120)
        assert out2["tokens"] == out["tokens"]

        # pressure heartbeat lands in the router table within a few seconds
        states = await stack.running_containers(dep["stub_id"])
        assert states
        from tpu9.abstractions.llm import LlmRouter
        router = LlmRouter(stack.store)
        seen = None
        for _ in range(60):
            seen = await router.pressure(states[0].container_id)
            if seen is not None:
                break
            await asyncio.sleep(0.5)
        assert seen is not None, "no pressure heartbeat arrived"
        assert "token_pressure" in seen
        # speculative-decoding acceptance rides the same heartbeat (ISSUE
        # 5): present for every engine (0.0 when speculation is off) so
        # /api/v1/metrics' engines section and the router's fleet-wide
        # tpu9_router_spec_* gauges always have the field
        assert "spec_acceptance_rate" in seen

        # bad request surfaces cleanly
        status, bad = await stack.api("POST", "/endpoint/llm",
                                      json_body={"nope": 1}, timeout=60)
        assert status == 400 and "tokens" in bad["error"]


TP_LLM_APP = """
import os
from tpu9.utils import force_cpu
force_cpu(host_devices=8)     # the runner's 8 "chips" (virtual CPU mesh)

def load_engine():
    import jax
    from tpu9.models import init_decoder
    from tpu9.models.llama import llama_config
    from tpu9.parallel import decoder_param_specs, mesh_for_spec, shard_params
    from tpu9.serving import EngineConfig, InferenceEngine
    from tpu9.types import parse_tpu_spec

    # the worker handed this container a full v5e-8 host slice
    # (in the platform's spelling, which libtpu reads)
    assert os.environ.get("TPU_ACCELERATOR_TYPE") == "v5litepod-8", \\
        os.environ.get("TPU_ACCELERATOR_TYPE")
    assert len(os.environ.get("TPU_VISIBLE_CHIPS", "").split(",")) == 8

    # 70B-SHAPED pjit path at toy dims: same mesh/spec/shard code as
    # examples/04_llama70b_tp_v5e8.py, tp=8 over the host slice
    cfg = llama_config(vocab_size=256, dim=128, n_layers=2, n_heads=8,
                       n_kv_heads=8, head_dim=16, hidden_dim=256,
                       max_seq_len=128)
    mesh = mesh_for_spec(parse_tpu_spec("v5e-8"))
    assert mesh.devices.size == 8, mesh
    params = init_decoder(jax.random.PRNGKey(0), cfg)
    params = shard_params(params, mesh, decoder_param_specs(params))
    # PAGED KV under tensor parallelism — the config-#4 serving shape
    # (block pool + tables work on sharded params; verified equal to the
    # dense engine in test_paged_engine.py)
    engine = InferenceEngine(params, cfg,
                             EngineConfig(max_batch=2, max_seq_len=128,
                                          prefill_buckets=(16, 64),
                                          kv_block_size=16,
                                          kv_pool_blocks=20,
                                          prefill_chunk=16,
                                          prefix_cache_blocks=4))
    engine.mesh = mesh
    return engine
"""


@pytest.mark.slow
async def test_tp8_engine_through_endpoint():
    """Weak-#5 closure: a tensor-parallel (tp=8) engine — the 70B example's
    exact mesh/shard path at toy dims — serves through @endpoint tpu=v5e-8
    on a worker that hands the container the full host slice."""
    async with LocalStack(pool_tpu_type="v5e-8") as stack:
        await stack._worker_factory(tpu_chips=8, tpu_generation="v5e")
        dep = await stack.deploy_endpoint(
            "llm-tp8", {"app.py": TP_LLM_APP}, "app:load_engine",
            config_extra={
                "timeout_s": 240.0,
                "extra": {"runner": "llm"},
                "runtime": {"tpu": "v5e-8", "cpu_millicores": 500,
                            "memory_mb": 1024},
                "autoscaler": {"max_containers": 1}})
        status, out = await stack.api(
            "POST", "/endpoint/llm-tp8",
            json_body={"tokens": [7, 2, 11], "max_new_tokens": 6},
            timeout=240)
        assert status == 200, out
        assert len(out["tokens"]) == 6
        # deterministic greedy through the sharded engine
        status, out2 = await stack.api(
            "POST", "/endpoint/llm-tp8",
            json_body={"tokens": [7, 2, 11], "max_new_tokens": 6},
            timeout=120)
        assert out2["tokens"] == out["tokens"]
        # the slice really was reserved for the serving container
        workers = await stack.gateway.workers.list()
        assert any(w.tpu_chip_count == 8 and w.tpu_free_chips == 0
                   for w in workers), [w.to_dict() for w in workers]


async def test_llm_token_streaming_sse():
    """Token streaming end-to-end: the runner emits SSE events per token
    and the gateway relays them INCREMENTALLY (events arrive before the
    generation finishes, not as one buffered blob)."""
    import aiohttp as _aiohttp
    import json as _json

    async with LocalStack() as stack:
        await stack.deploy_endpoint(
            "llm-sse", {"app.py": LLM_APP}, "app:load_engine",
            config_extra={
                "timeout_s": 240.0,
                "extra": {"runner": "llm"},
                "autoscaler": {"max_containers": 1}})
        # warm (compile) through the buffered path first
        status, warm = await stack.api(
            "POST", "/endpoint/llm-sse",
            json_body={"tokens": [5, 3, 9], "max_new_tokens": 8},
            timeout=240)
        assert status == 200, warm

        # 64 tokens ⇒ many decode windows ⇒ many SSE flush points spread
        # over real device compute: the incremental-delivery proof below
        # is an ORDERING assertion over reads, and needs genuinely
        # interleaved generation to be load-robust (with only 8 tokens —
        # one or two windows — a briefly descheduled client coroutine
        # legitimately receives everything in a single read, which is
        # the baseline flake this test used to have)
        events = []
        read_of_event = []        # read index that delivered each event
        reads = 0
        async with _aiohttp.ClientSession() as sess:
            async with sess.post(
                    stack.base_url + "/endpoint/llm-sse",
                    json={"tokens": [5, 3, 9], "max_new_tokens": 64,
                          "stream": True},
                    headers={"Accept": "text/event-stream",
                             "Authorization":
                             f"Bearer {stack.gateway.default_token}"},
                    timeout=_aiohttp.ClientTimeout(total=240)) as resp:
                assert resp.status == 200, await resp.text()
                assert "text/event-stream" in resp.headers.get(
                    "Content-Type", "")
                buf = b""
                async for chunk in resp.content.iter_any():
                    reads += 1
                    buf += chunk
                    while b"\n\n" in buf:
                        frame, buf = buf.split(b"\n\n", 1)
                        if frame.startswith(b"data: "):
                            events.append(_json.loads(frame[6:]))
                            read_of_event.append(reads)

        toks = [e["token"] for e in events if "token" in e]
        final = next(e for e in events if e.get("done"))
        assert toks == final["tokens"]
        assert len(toks) == 64
        # greedy determinism: the stream's prefix matches the buffered
        # result (same greedy path, longer budget)
        assert toks[:len(warm["tokens"])] == warm["tokens"]
        # INCREMENTAL proof (ordering, not wall-clock): some token event
        # arrived in an EARLIER read than the done event — i.e. the
        # gateway relayed tokens while the generation was still running,
        # instead of buffering the stream into one terminal blob
        assert read_of_event[0] < read_of_event[-1], (
            f"all {len(events)} events arrived in read "
            f"{read_of_event[-1]} of {reads} — stream was buffered")

        # served proof, on the container's side: the runner's own counter
        # on /health accounts for every token the two requests received
        # (a first token is sampled by its prefill: counted once a request)
        status, health = await stack.api("GET", "/endpoint/llm-sse/health")
        assert status == 200, health
        assert int(health["tokens_generated"]) + 2 \
            >= len(warm["tokens"]) + len(toks), health


@pytest.mark.slow
async def test_llm_streaming_scales_from_zero():
    """Review regression: forward_stream must register autoscaler demand
    BEFORE admission — a streaming request to a scaled-to-zero endpoint
    has to trigger scale-up, not 504."""
    import aiohttp as _aiohttp
    import json as _json

    async with LocalStack() as stack:
        dep = await stack.deploy_endpoint(
            "llm-sse0", {"app.py": LLM_APP}, "app:load_engine",
            config_extra={
                "timeout_s": 240.0,
                "extra": {"runner": "llm"},
                "autoscaler": {"max_containers": 1}})
        status, warm = await stack.api(
            "POST", "/endpoint/llm-sse0",
            json_body={"tokens": [5, 3, 9], "max_new_tokens": 4},
            timeout=240)
        assert status == 200, warm
        await stack.scale_to_zero(dep)

        events = []
        async with _aiohttp.ClientSession() as sess:
            async with sess.post(
                    stack.base_url + "/endpoint/llm-sse0",
                    json={"tokens": [5, 3, 9], "max_new_tokens": 4,
                          "stream": True},
                    headers={"Accept": "text/event-stream",
                             "Authorization":
                             f"Bearer {stack.gateway.default_token}"},
                    timeout=_aiohttp.ClientTimeout(total=240)) as resp:
                assert resp.status == 200, await resp.text()
                buf = b""
                async for chunk in resp.content.iter_any():
                    buf += chunk
                for frame in buf.split(b"\n\n"):
                    if frame.startswith(b"data: "):
                        events.append(_json.loads(frame[6:]))
        final = next(e for e in events if e.get("done"))
        assert final["tokens"] == warm["tokens"]


@pytest.mark.slow
async def test_request_lifecycle_trace_e2e():
    """ISSUE 8 acceptance: one request through gateway → FleetRouter →
    engine yields a single trace id whose span tree is gapless —
    gateway.invoke ⊃ router queue-wait/admission/dispatch ⊃ engine.request
    ⊃ queue-wait/prefill/≥1 decode window — via /api/v1/traces, with the
    engine spans arriving on the runner's pressure heartbeat. Also covers
    the endpoint's limit/since bounding."""
    async with LocalStack() as stack:
        dep = await stack.deploy_endpoint(
            "llmtrace", {"app.py": LLM_APP}, "app:load_engine",
            config_extra={
                "timeout_s": 240.0,
                "extra": {"runner": "llm"},
                "autoscaler": {"type": "token_pressure",
                               "max_containers": 1}})
        status, out = await stack.api(
            "POST", "/endpoint/llmtrace",
            json_body={"tokens": [5, 3, 9], "max_new_tokens": 8},
            timeout=240)
        assert status == 200, out
        assert len(out["tokens"]) == 8

        # the engine spans ship on the next pressure heartbeat (~2s);
        # poll the merged endpoint until the full tree is visible
        tree: list = []
        for _ in range(120):
            status, data = await stack.api(
                "GET", "/api/v1/traces?limit=4000")
            assert status == 200
            invokes = [
                s for s in data["spans"]
                if s["name"] == "gateway.invoke"
                and s["attributes"].get("stub_id") == dep["stub_id"]]
            if invokes:
                trace_id = invokes[0]["traceId"]
                status, filt = await stack.api(
                    "GET", f"/api/v1/traces?trace_id={trace_id}")
                assert status == 200
                tree = filt["spans"]
                if {"engine.prefill", "engine.decode"} <= \
                        {s["name"] for s in tree}:
                    break
            await asyncio.sleep(0.5)
        by_name: dict = {}
        for sp in tree:
            by_name.setdefault(sp["name"], []).append(sp)
        assert {"engine.prefill", "engine.decode"} <= set(by_name), \
            f"engine spans never arrived: {sorted(by_name)}"

        # ONE trace id across every layer
        assert len({s["traceId"] for s in tree}) == 1

        invoke = by_name["gateway.invoke"][0]
        assert invoke["parentSpanId"] == ""          # the root
        # router children hang off the invoke span
        for name in ("router.admission", "router.queue_wait",
                     "router.dispatch"):
            assert name in by_name, sorted(by_name)
            for sp in by_name[name]:
                assert sp["parentSpanId"] == invoke["spanId"], (name, sp)
        assert by_name["router.admission"][0]["attributes"][
            "decision"] in ("queued", "admitted")
        disp = by_name["router.dispatch"][0]["attributes"]
        assert "replica" in disp and "affinity_hit" in disp

        # engine.request hangs off the invoke span (X-Tpu9-Trace), and
        # queue-wait/prefill/decode hang off engine.request: one decode
        # span for the request, whatever its number of windows
        req = by_name["engine.request"][0]
        assert req["parentSpanId"] == invoke["spanId"]
        assert req["attributes"]["tokens_generated"] == 8
        windows = by_name["engine.decode"]
        assert len(windows) == 1
        assert windows[0]["attributes"]["tokens"] == 7
        assert windows[0]["attributes"]["windows"] >= 1
        for name in ("engine.queue_wait", "engine.prefill",
                     "engine.decode"):
            for sp in by_name[name]:
                assert sp["parentSpanId"] == req["spanId"], (name, sp)

        # gapless containment: every engine child sits inside the
        # engine.request interval, which sits inside gateway.invoke
        # (same-host wall anchors; 50ms slack for anchor skew)
        slack = 50 * 10**6
        for sp in (by_name["engine.queue_wait"] + by_name["engine.prefill"]
                   + windows):
            assert sp["startTimeUnixNano"] >= req["startTimeUnixNano"] - slack
            assert sp["endTimeUnixNano"] <= req["endTimeUnixNano"] + slack
        assert req["startTimeUnixNano"] >= \
            invoke["startTimeUnixNano"] - slack
        assert req["endTimeUnixNano"] <= invoke["endTimeUnixNano"] + slack
        # runner spans were workspace-stamped at ingest (tenancy scoping)
        assert req["attributes"]["workspace_id"] == \
            invoke["attributes"]["workspace_id"]

        # decomposition sanity at e2e scale: children cover the request
        # span — queue_wait + prefill + decode windows ≈ engine e2e
        covered = sum(s["endTimeUnixNano"] - s["startTimeUnixNano"]
                      for s in (by_name["engine.queue_wait"]
                                + by_name["engine.prefill"] + windows))
        span_len = req["endTimeUnixNano"] - req["startTimeUnixNano"]
        assert covered >= span_len * 0.5, (covered, span_len)

        # ---- limit/since stay bounded (ISSUE 8 satellite) ----
        status, lim = await stack.api("GET", "/api/v1/traces?limit=3")
        assert status == 200 and len(lim["spans"]) <= 3
        import time as _time
        status, fut = await stack.api(
            "GET", f"/api/v1/traces?since={_time.time() + 3600}")
        assert status == 200 and fut["spans"] == []
        status, past = await stack.api(
            "GET", f"/api/v1/traces?trace_id={invoke['traceId']}&since=1")
        assert status == 200 and len(past["spans"]) == len(tree)

        # ---- /api/v1/flight surfaces the engine's ring e2e ----
        status, fl = await stack.api(
            "GET", f"/api/v1/flight?stub_id={dep['stub_id']}&limit=32")
        assert status == 200, fl
        kinds = [r["kind"] for r in fl["flight"]]
        assert "admit" in kinds and "decode" in kinds, kinds
        seqs = [r["seq"] for r in fl["flight"]]
        assert seqs == sorted(seqs)
        # incremental poll from the last seq returns only newer records
        status, fl2 = await stack.api(
            "GET", f"/api/v1/flight?stub_id={dep['stub_id']}"
                   f"&since_seq={seqs[-1]}")
        assert status == 200
        assert all(r["seq"] > seqs[-1] for r in fl2["flight"])

        # ---- decision ledger (ISSUE 19): the buffered request's WHY
        # chain joins on the SAME trace id — the admission verdict, then
        # the dispatch placement whose chosen replica matches the
        # router.dispatch span, with the evidence signals attached
        status, dec = await stack.api(
            "GET", f"/api/v1/decisions?request_id={invoke['traceId']}")
        assert status == 200
        chain = dec["records"]
        planes = [(r["plane"], r["decision"]) for r in chain]
        adm = next(r for r in chain if r["plane"] == "admission")
        assert adm["decision"] in ("queued", "admitted")
        assert adm["chosen"] == "admit"
        assert adm["signals"]["tenant"]
        place = next(r for r in chain if r["decision"] == "dispatch")
        assert planes.index((adm["plane"], adm["decision"])) \
            < planes.index(("placement", "dispatch"))
        assert place["chosen"] == disp["replica"]
        assert place["signals"]["queue_wait_s"] >= 0.0
        # a cold-start dispatch (no replicas yet) honestly reports an
        # empty candidate set; a warm one counts the preference order
        assert place["signals"]["candidates"] == disp["candidates"]
        assert place["workspace_id"] == invoke["attributes"]["workspace_id"]
