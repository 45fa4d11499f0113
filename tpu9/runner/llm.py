"""LLM serving runner: hosts an InferenceEngine behind the endpoint protocol.

This is the runner image for baseline configs #2/#4 (Llama on v5e-1 /
Llama-70B TP on v5e-8): the worker spawns it with a handler that returns
either an :class:`tpu9.serving.InferenceEngine` or a ``(params, cfg)`` pair /
preset name; it serves:

- ``POST /``            {"tokens": [...], "max_new_tokens": n} → {"tokens": [...]}
- ``POST /generate``    same (alias)
- ``GET /health``       readiness + engine stats

and heartbeats token-pressure/active-streams to the gateway so the
token-pressure autoscaler and the prefix-affinity router see real engine
load (reference pod/llm.go's per-container snapshots).

Multi-host gangs call ``initialize_multihost()`` before touching jax, so a
v5p-64 deployment's 16 runners join one jax.distributed job.
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import sys
from typing import Optional

import aiohttp
from aiohttp import web

from ..config import (env_bind_host, env_checkpoint_enabled,
                      env_faults_spec, env_gateway_url, env_kv_tier_on,
                      env_token)
from .common import FunctionHandler, RunnerConfig, error_payload

log = logging.getLogger("tpu9.runner")


def _build_engine(obj):
    """Accept an InferenceEngine, a (params, cfg) pair, or a preset name."""
    from ..serving import EngineConfig, InferenceEngine
    if hasattr(obj, "generate") and hasattr(obj, "stats"):
        return obj
    if isinstance(obj, tuple) and len(obj) in (2, 3):
        params, cfg = obj[0], obj[1]
        ecfg = obj[2] if len(obj) == 3 else EngineConfig()
        return InferenceEngine(params, cfg, ecfg)
    if isinstance(obj, str):
        # preset name, optionally "-int8"-suffixed (weight-only quantized).
        # compile-ahead: the serving graphs AOT-compile from the preset's
        # abstract shapes concurrently with weight materialization, so the
        # post-build warmup() below dispatches precompiled executables
        # instead of serializing XLA behind the weight load.
        # TPU9_SPEC_LEN opts the deployment into self-speculative decoding
        # (prompt-lookup drafts, ISSUE 5) without a handler change —
        # greedy output is identical either way, only tokens/sec moves.
        # TPU9_QUANTIZE / TPU9_KV_QUANT (e.g. "int8") opt into quantized
        # serving (ISSUE 6): int8 weights / int8 paged KV pool — same
        # no-handler-change contract, per-deployment.
        # TPU9_KV_POOL_BLOCKS pins the paged pool (0: dense parity), which
        # a model whose KV state is many planes deep needs to fit a chip.
        from ..serving.presets import load_engine
        spec_len = int(os.environ.get("TPU9_SPEC_LEN", "0") or 0)
        quantize = os.environ.get("TPU9_QUANTIZE", "") or None
        kv_quant = os.environ.get("TPU9_KV_QUANT", "") or None
        pool = int(os.environ.get("TPU9_KV_POOL_BLOCKS", "0") or 0)
        return load_engine(obj, compile_ahead=True, spec_len=spec_len,
                           quantize=quantize, kv_quant=kv_quant,
                           kv_pool_blocks=pool)
    raise TypeError(f"handler must return an engine, (params, cfg) or a "
                    f"preset name; got {type(obj)}")


def _kv_transport():
    """CacheClient for shipped paged-KV blocks (ISSUE 16), or None when
    the deployment has no kv cache plane. TPU9_KV_CACHE_DIR points the
    replica at its content-addressed store (a shared dir in dev makes
    every ship a local hit); TPU9_CACHE_PEERS ("host:port,host:port")
    adds the HRW/hedged peer tier. The engine itself never sees this —
    the runner moves bytes between transport and engine, keeping the
    serving stack transport-free (BND001)."""
    cache_dir = os.environ.get("TPU9_KV_CACHE_DIR", "")
    if not cache_dir:
        return None
    from ..cache.client import CacheClient
    from ..cache.store import DiskStore
    peers = [p.strip() for p in
             os.environ.get("TPU9_CACHE_PEERS", "").split(",") if p.strip()]

    async def peer_fn():
        return peers

    return CacheClient(DiskStore(cache_dir), peer_fn,
                       self_address=os.environ.get("TPU9_CACHE_SELF", ""))


async def amain() -> None:
    cfg = RunnerConfig.from_env()
    gateway_url = env_gateway_url()
    token = env_token()

    # fault-injection plane (ISSUE 15): env-gated, None in production.
    # The import is lazy on purpose — tpu9.testing.faults is restricted
    # to the declared hook sites (boundaries.toml) and a production
    # container without TPU9_FAULTS never imports it.
    faults = None
    if env_faults_spec():
        from ..testing.faults import FaultPlane
        faults = FaultPlane.from_env()
        log.warning("fault plane ACTIVE: %s", sorted(faults.specs))

    # multi-host gang? join the slice-wide jax.distributed job first
    from ..parallel.distributed import initialize_multihost
    initialize_multihost()

    # kvwire transport (ISSUE 16): optional, env-gated — block shipping
    # (disagg handoff / drain migration / failover resume) degrades to
    # plain re-prefill wherever this is None
    kv_client = _kv_transport()

    # KV-motion spans + migration decision records (ISSUE 19): block
    # movement shows up inline with the request's prefill/decode spans,
    # and the adopt/drain verdicts ride the pressure heartbeat to the
    # gateway's decision API exactly like engine spans do
    from ..observability.decisions import ledger as decision_ledger, rej
    from ..observability.trace import tracer as _tracer

    # "beat": request completions set this to nudge the pressure loop into
    # an immediate heartbeat, so a completed request's engine spans ship
    # BEFORE an aggressive scale-to-zero can kill the replica (ISSUE 8)
    state = {"ready": False, "engine": None, "beat": asyncio.Event()}

    async def health(request: web.Request) -> web.Response:
        if not state["ready"]:
            return web.json_response({"ready": False}, status=503)
        stats = state["engine"].stats()
        if stats.get("engine_dead"):
            # the serve loop died: stop advertising ready or the gateway
            # keeps routing requests into a black hole
            return web.json_response({"ready": False, **stats}, status=503)
        return web.json_response({"ready": True, **stats})

    def _trace_ctx(request: web.Request):
        """(trace_id, parent_span_id) from the gateway-minted
        X-Tpu9-Trace header, or None — the engine records its request/
        prefill/decode-window spans under this remote parent (ISSUE 8)."""
        raw = request.headers.get("X-Tpu9-Trace", "")
        if not raw or ":" not in raw:
            return None
        trace_id, _, parent = raw.partition(":")
        return (trace_id, parent) if trace_id else None

    def _budget_s(request: web.Request):
        """Remaining deadline budget from the gateway's X-Tpu9-Budget-S
        header (relative seconds — relative survives clock skew across
        the RPC boundary; the gateway deducts spent budget per attempt).
        None = no deadline."""
        raw = request.headers.get("X-Tpu9-Budget-S", "")
        if not raw:
            return None
        try:
            return float(raw)
        except ValueError:
            return None

    import time as _now

    async def _kv_adopt(adopt, trace=None) -> None:
        """Best-effort pre-generate adopt of shipped KV blocks: fetch by
        key, splice into the pool, register the exporter's prefix. Every
        failure path (no transport, fetch miss, induced kv_ship_error,
        malformed payload, pool pressure) degrades to plain re-prefill —
        the request itself NEVER fails because a ship did."""
        key = str((adopt or {}).get("key") or "")
        if not key:
            return
        tid, parent = trace or ("", "")
        t0w, t0m = _now.time(), _now.monotonic()
        want_tokens = int((adopt or {}).get("n_tokens") or 0)

        def _verdict(outcome: str, reason: str = "") -> None:
            # kv.adopt span on the request's trace tree + the runner half
            # of the migration decision chain (ISSUE 19) — both ride the
            # pressure heartbeat to the gateway
            if tid:
                _tracer.record_span(
                    "kv.adopt", tid, parent, t0w, t0m,
                    attrs={"key": key[:16], "outcome": outcome,
                           "n_tokens": want_tokens},
                    status="ok" if outcome == "adopted" else "error")
            decision_ledger.record(
                "migration", "adopt", request_id=tid, chosen=outcome,
                rejected=[] if outcome == "adopted"
                else [rej("block_ship", reason)],
                signals={"n_tokens": want_tokens,
                         "container_id": cfg.container_id})

        engine = state["engine"]
        if kv_client is None:
            engine.note_kvwire_fallback()
            _verdict("re_prefill", "no_kv_transport")
            return
        if faults is not None and faults.fire("kv_ship_error"):
            log.warning("fault plane: induced kv ship error (adopt %s)",
                        key[:12])
            engine.note_kvwire_fallback()
            _verdict("re_prefill", "induced_kv_ship_error")
            return
        t0 = _now.monotonic()
        try:
            data = await kv_client.get_kv(key)
        except Exception as exc:    # noqa: BLE001 — transport, not request
            log.warning("kv ship fetch failed (%s): %s", key[:12], exc)
            data = None
        if data is None:
            engine.note_kvwire_fallback()
            _verdict("re_prefill", "fetch_miss")
            return
        try:
            if engine.adopt_kv(data):   # False self-counts the fallback
                engine.note_kvwire_ship(_now.monotonic() - t0)
                _verdict("adopted")
            else:
                _verdict("re_prefill", "adopt_declined")
        except Exception as exc:    # noqa: BLE001 — KvWireError and kin
            log.warning("kv adopt rejected (%s): %s", key[:12], exc)
            engine.note_kvwire_fallback()
            _verdict("re_prefill", "adopt_rejected")

    async def _kv_publish(tokens: list, trace=None) -> Optional[dict]:
        """export_after_prefill: serialize the prefix-cached blocks the
        prefill just inserted and publish them under the kv: namespace.
        Returns the ``{"kv_key", "n_tokens"}`` announcement (the SSE
        event body / JSON response fields), or None when there is
        nothing to ship."""
        if kv_client is None:
            return None
        tid, parent = trace or ("", "")
        engine = state["engine"]
        try:
            t0w, t0m = _now.time(), _now.monotonic()
            payload = engine.export_prefix_kv(tokens)
            if payload is None:
                return None
            from ..serving.kvwire import decode_header
            header, _ = decode_header(payload)
            n_tok = int(header.get("n_tokens", 0))
            if tid:
                # kv.export: serialize time; kv.ship: transport time —
                # two spans so a slow ship is distinguishable from a
                # slow pool walk on the trace tree (ISSUE 19)
                _tracer.record_span(
                    "kv.export", tid, parent, t0w, t0m,
                    attrs={"n_tokens": n_tok, "bytes": len(payload)})
            t1w, t1m = _now.time(), _now.monotonic()
            digest = await kv_client.put_kv(payload)
            engine.note_kvwire_ship(_now.monotonic() - t1m)
            if tid:
                _tracer.record_span(
                    "kv.ship", tid, parent, t1w, t1m,
                    attrs={"key": digest[:16], "n_tokens": n_tok,
                           "bytes": len(payload)})
            return {"kv_key": digest, "n_tokens": n_tok}
        except Exception as exc:    # noqa: BLE001 — ship is best-effort
            log.warning("kv export/publish failed: %s", exc)
            return None

    async def generate(request: web.Request) -> web.StreamResponse:
        # the runner's leg of a request starts here (ISSUE 41): the one
        # (wall, monotonic) anchor its intervals are told against
        t_in = (_now.time(), _now.monotonic())
        if not state["ready"]:
            return web.json_response({"error": "not ready"}, status=503)
        if faults is not None and faults.fire("rpc_error"):
            # induced RPC transport error: the gateway's forward sees a
            # mid-request connection reset, exactly like a NIC/proxy blip
            if request.transport is not None:
                request.transport.close()
            raise ConnectionResetError(
                "tpu9.testing.faults: induced rpc transport error")
        try:
            payload = json.loads(await request.read() or b"{}")
            tokens = payload.get("tokens") or payload.get("prompt_tokens")
            if not isinstance(tokens, list) or not tokens:
                return web.json_response(
                    {"error": "body must include 'tokens': [int, ...]"},
                    status=400)
            prompt = [int(t) for t in tokens]
            max_new = int(payload.get("max_new_tokens", 32))
            trace = _trace_ctx(request)
            budget = _budget_s(request)
            if budget is not None and budget <= 0:
                # past budget at the door: never even enqueue (the
                # engine would reject it too; answering here saves the
                # queue round-trip)
                return web.json_response(
                    {"error": "deadline_exceeded: budget exhausted "
                              "before dispatch"}, status=504)
            # kvwire request modes (ISSUE 16): adopt shipped blocks
            # BEFORE admission (the prefix cache then serves them to the
            # ordinary prefix-reuse path); export after prefill when the
            # router asked for a disagg handoff
            if payload.get("adopt_kv"):
                await _kv_adopt(payload.get("adopt_kv"), trace)
            kv_export = bool(payload.get("kv_export")
                             or payload.get("export_after_prefill"))
            if payload.get("stream") or \
                    "text/event-stream" in request.headers.get("Accept", ""):
                return await _generate_sse(request, prompt, max_new, t_in,
                                           trace, budget,
                                           kv_export=kv_export)
            out = await state["engine"].generate(prompt,
                                                 max_new_tokens=max_new,
                                                 trace=trace,
                                                 budget_s=budget)
            state["beat"].set()
            resp = {"tokens": out}
            if kv_export:
                resp.update(await _kv_publish(prompt, trace) or {})
            return web.json_response(resp)
        except TimeoutError as exc:
            # engine deadline expiry (ISSUE 15): 504, not 400/500 — the
            # gateway must neither blame the request nor retry it
            if "deadline_exceeded" in str(exc):
                return web.json_response({"error": str(exc)}, status=504)
            return web.json_response(error_payload(exc), status=500)
        except ValueError as exc:
            return web.json_response({"error": str(exc)}, status=400)
        except Exception as exc:  # noqa: BLE001
            return web.json_response(error_payload(exc), status=500)

    async def _generate_sse(request: web.Request, prompt: list,
                            max_new: int, t_in: tuple, trace=None,
                            budget=None,
                            kv_export: bool = False) -> web.StreamResponse:
        """Server-sent token stream: one `data: {"token": N}` event per
        generated token, then `data: {"done": true, "tokens": [...]}` —
        relayed incrementally by the gateway's streaming proxy. Dict
        items in the request queue (drain-migration ``kv_key``
        announcements) pass through as their own events."""
        req = await state["engine"].generate(prompt, max_new_tokens=max_new,
                                             stream=True, trace=trace,
                                             budget_s=budget)
        t_enqueued = _now.monotonic()
        sr = web.StreamResponse(
            status=200, headers={"Content-Type": "text/event-stream",
                                 "Cache-Control": "no-cache",
                                 "X-Accel-Buffering": "no"})
        await sr.prepare(request)
        state["engine"].note_ingest(req, t_in, t_enqueued, _now.monotonic())
        out: list = []
        t_written = 0.0     # the newest token's write (ISSUE 57)
        # export_after_prefill (ISSUE 16): announce once, right after the
        # first token proves prefill (and its prefix-cache insert) is done
        kv_pending = kv_export and kv_client is not None
        try:
            while True:
                tok = await req.queue.get()
                if tok is None:
                    break
                if isinstance(tok, dict):
                    await sr.write(f"data: {json.dumps(tok)}\n\n".encode())
                    continue
                out.append(tok)
                if faults is not None and faults.fire("proc_exit",
                                                      tokens=len(out)):
                    # hard replica death mid-stream: the strongest chaos
                    # case — transport cut, no error event, no goodbye
                    log.warning("fault plane: proc_exit after %d tokens",
                                len(out) - 1)
                    os._exit(17)
                await sr.write(
                    f"data: {json.dumps({'token': tok})}\n\n".encode())
                t_written = _now.monotonic()
                if len(out) == 1:
                    # stream lag and the runner's first-token interval:
                    # both end where the first token is written
                    state["engine"].note_first_write(req)
                if kv_pending:
                    kv_pending = False
                    ev = await _kv_publish(prompt, trace)
                    if ev:
                        await sr.write(
                            f"data: {json.dumps(ev)}\n\n".encode())
            if req.error:
                await sr.write(
                    f"data: {json.dumps({'error': req.error})}\n\n".encode())
            else:
                # the runner's own gap between tokens: first write -> last
                state["engine"].note_last_write(req, t_written)
                await sr.write(
                    f"data: {json.dumps({'done': True, 'tokens': out})}\n\n"
                    .encode())
            await sr.write_eof()
            state["beat"].set()
        except ConnectionResetError:
            # client went away: tell the ENGINE — otherwise the slot keeps
            # decoding the full budget into a queue nobody reads, pinning
            # batch capacity with dead work
            state["engine"].cancel_request(req)
        except asyncio.CancelledError:
            # server teardown / disconnect cancellation: same engine-side
            # cleanup, but the cancellation must still propagate
            state["engine"].cancel_request(req)
            raise
        return sr

    async def flight(request: web.Request) -> web.Response:
        """Flight-recorder tail (ISSUE 8): the gateway's /api/v1/flight
        proxies here through the request buffer."""
        if not state["ready"]:
            return web.json_response({"error": "not ready"}, status=503)
        try:
            limit = int(request.query.get("limit", 256))
            since_seq = int(request.query.get("since_seq", 0))
        except ValueError:
            return web.json_response(
                {"error": "limit/since_seq must be integers"}, status=400)
        return web.json_response({
            "container_id": cfg.container_id,
            "flight": state["engine"].flight_records(
                limit=limit, since_seq=since_seq)})

    async def profile(request: web.Request) -> web.Response:
        """Trace the next ``seconds`` seconds with jax.profiler from a
        worker thread of the engine (ISSUE 24); returns the dump path on
        THIS replica immediately."""
        if not state["ready"]:
            return web.json_response({"error": "not ready"}, status=503)
        try:
            payload = json.loads(await request.read() or b"{}")
            out = state["engine"].arm_profile(
                seconds=float(payload.get("seconds", 4.0)),
                out_dir=str(payload.get("out_dir", "") or ""))
        except ValueError as exc:
            return web.json_response({"error": str(exc)}, status=400)
        out["container_id"] = cfg.container_id
        return web.json_response(out)

    async def drain(request: web.Request) -> web.Response:
        """Graceful-drain migration (ISSUE 16): export every in-flight
        stream's full-block KV prefix, publish it under the kv:
        namespace, and push a ``kv_key`` event into each live SSE stream
        — so when this replica stops, the gateway resumes those
        generations on a survivor by block ship instead of re-prefill.
        Best-effort per stream: a failed export just means that stream
        falls back to re-prefill at failover."""
        if not state["ready"]:
            return web.json_response({"error": "not ready"}, status=503)
        try:
            payload = json.loads(await request.read() or b"{}")
        except ValueError:
            payload = {}
        min_tokens = int(payload.get("min_tokens", 32))
        engine = state["engine"]
        migrated: dict = {}
        if kv_client is not None:
            from ..serving.kvwire import decode_header
            for req in engine.active_stream_requests():
                tid, parent = req.trace or ("", "")
                if len(req.prompt) + len(req.generated) < min_tokens:
                    decision_ledger.record(
                        "migration", "drain_export", request_id=tid,
                        chosen="skip",
                        rejected=[rej("block_ship",
                                      f"under_min_tokens_{min_tokens}")],
                        signals={"tokens": len(req.prompt)
                                 + len(req.generated),
                                 "container_id": cfg.container_id})
                    continue
                t0w, t0m = _now.time(), _now.monotonic()
                try:
                    blob = engine.export_request_kv(req.request_id)
                    if blob is None:
                        continue
                    header, _ = decode_header(blob)
                    t0 = _now.monotonic()
                    digest = await kv_client.put_kv(blob)
                    engine.note_kvwire_ship(_now.monotonic() - t0)
                except Exception as exc:    # noqa: BLE001 — per-stream
                    log.warning("drain export failed (%s): %s",
                                req.request_id, exc)
                    decision_ledger.record(
                        "migration", "drain_export", request_id=tid,
                        chosen="re_prefill",
                        rejected=[rej("block_ship", type(exc).__name__)],
                        signals={"container_id": cfg.container_id})
                    continue
                ev = {"kv_key": digest,
                      "n_tokens": int(header.get("n_tokens", 0))}
                if tid:
                    # kv.drain: the drain re-export's block motion on the
                    # stream's own trace tree (ISSUE 19)
                    _tracer.record_span(
                        "kv.drain", tid, parent, t0w, t0m,
                        attrs={"key": digest[:16], "bytes": len(blob),
                               "n_tokens": ev["n_tokens"]})
                decision_ledger.record(
                    "migration", "drain_export", request_id=tid,
                    chosen="block_ship",
                    signals={"n_tokens": ev["n_tokens"],
                             "bytes": len(blob),
                             "container_id": cfg.container_id})
                migrated[req.request_id] = ev
                req.queue.put_nowait(dict(ev))
        return web.json_response({"container_id": cfg.container_id,
                                  "migrated": migrated,
                                  "kv_transport": kv_client is not None})

    app = web.Application(client_max_size=64 * 1024 * 1024)
    app.router.add_get("/health", health)
    app.router.add_post("/", generate)
    app.router.add_post("/generate", generate)
    app.router.add_get("/flight", flight)
    app.router.add_post("/profile", profile)
    app.router.add_post("/drain", drain)
    runner = web.AppRunner(app)
    await runner.setup()
    await web.TCPSite(runner, env_bind_host(),
                      cfg.port).start()

    # build the engine off the loop (model init / weight load can be slow)
    # — under one runner.bringup span carrying the container's minted
    # trace id (ISSUE 13), so the handler's restore.load, load_engine's
    # compile_ahead/bind and the warmup below merge with the worker's
    # restore.request tree into ONE bring-up trace at /api/v1/traces
    # (spans ship on the pressure heartbeat; the gateway stamps tenancy)
    import time as _time

    from ..observability.trace import tracer
    from ..observability import coldstart as _cs
    handler = FunctionHandler(cfg)
    t_bring = _time.monotonic()
    with tracer.span(_cs.SPAN_BRINGUP,
                     trace_id=os.environ.get("TPU9_TRACE_ID", ""),
                     attrs={"container_id": cfg.container_id,
                            "restored":
                            os.environ.get("TPU9_RESTORED", "0")}):
        result = await handler.call()
        engine = _build_engine(result)
        # handler wall INCLUDES the engine build (load_engine's weight
        # materialization + overlapped precompile live inside it);
        # warmup_s below is only the pre-readiness graph warmup
        t_load_done = _time.monotonic()
        # compile every serving graph BEFORE readiness: the first user
        # request must never pay a multi-second XLA compile (readiness ==
        # serveable)
        with tracer.span(_cs.SPAN_WARMUP):
            timings = await asyncio.get_event_loop().run_in_executor(
                None, engine.warmup)
        t_warm_done = _time.monotonic()
    ahead = getattr(engine, "compile_ahead_timings", None)
    if ahead:
        log.info("compile-ahead (overlapped with weight load): %s",
                 {k: round(v, 2) for k, v in ahead.items()})
    log.info("engine warmup: %s",
             {k: round(v, 2) for k, v in timings.items()})
    if faults is not None:
        # serve-loop fault hooks (crash / stall) patch the INSTANCE —
        # the plane never imports the serving stack
        faults.instrument_engine(engine)
    await engine.start()
    state["engine"] = engine
    state["ready"] = True
    # runner-half coldstart record fields (the worker half rides the
    # coldstart:<cid> store key): handler wall covers restore.load +
    # load_engine; ready_s is the whole bring-up to serveable
    bringup = dict(getattr(engine, "bringup", None) or {})
    bringup["handler_s"] = round(t_load_done - t_bring, 4)
    bringup["warmup_s"] = round(t_warm_done - t_load_done, 4)
    bringup["ready_s"] = round(_time.monotonic() - t_bring, 4)
    bringup["restored"] = int(os.environ.get("TPU9_RESTORED", "0") == "1")
    engine.bringup = bringup
    if env_checkpoint_enabled():
        from . import ckpt
        ckpt.mark_ready({"handler": cfg.handler})
    log.info("llm engine ready")

    async def pressure_loop() -> None:
        if not gateway_url:
            return
        rejected_logged = False
        from ..utils.aio import event_wait
        # span-ship watermark (ISSUE 8): MONOTONIC (an NTP step must not
        # gate shipping), and only advances after a heartbeat the gateway
        # ACCEPTED — a gateway blip retries the same window next beat
        # instead of silently dropping engine spans (bounded by the
        # tracer ring, same honesty as the worker/OTLP paths)
        last_span_ship = 0.0
        # decision-record ship cursor (ISSUE 19): seq-keyed, same
        # retry-don't-drop contract — a rejected beat re-ships the window
        last_dec_ship = 0
        # kv-tier delta cursor (ISSUE 20): the eviction/spill journal
        # ships as a heartbeat delta and the cursor only advances on an
        # ACCEPTED beat — a gateway blip re-ships the same retractions
        # instead of leaving the directory believing a prefix survived
        last_tier_delta = 0
        kvtier_hb = env_kv_tier_on()
        # peer-cache publications this replica made ((key_hex16, digest,
        # n_tokens)) — re-advertised each beat, bounded
        peer_pub: list = []
        from ..observability.trace import RING_CAP, phase, tracer
        # replica health plane (ISSUE 14): the watchdog classifies the
        # engine's liveness watermark each beat and the verdict rides the
        # heartbeat — this loop is exactly the "runner still alive while
        # the serve loop is wedged" side of a gray failure, so it must
        # never await the engine, only read its stats dict
        from ..observability.health import (EngineWatchdog, WatchdogConfig,
                                            build_postmortem)
        watchdog = EngineWatchdog(WatchdogConfig.from_env())
        beat_s = float(os.environ.get("TPU9_PRESSURE_INTERVAL_S", "")
                       or 2.0)
        crash_shipped = False
        pending_pm: Optional[dict] = None
        # post-mortem ship retry budgets (ISSUE 15 satellite: the shared
        # backoff helper replaces the hand-rolled 5/30 counters). The
        # heartbeat paces the loop, so the DELAY side is unused — only
        # the attempt accounting and give-up classification.
        from ..utils.backoff import BackoffPolicy, RetryState
        pm_retry = RetryState(BackoffPolicy(base_s=beat_s, jitter=0.0),
                              permanent_max=5, transient_max=30)
        async with aiohttp.ClientSession(
                headers={"Authorization": f"Bearer {token}"}) as session:
            while True:
                try:
                    with phase("runner.heartbeat", engine.host_phases):
                        stats = engine.stats()
                        # fleet-router / observability extras (ISSUE 2
                        # satellite): queue depth, KV headroom, prefix-cache
                        # hit rate — flat scalars only (the pressure table is
                        # a store hash; nested dicts don't round-trip)
                        extra = {"queued": stats.get("queued", 0)}
                        for k in ("kv_blocks_free", "kv_blocks_used",
                                  "kv_blocks_reserved", "kv_block_size",
                                  # int8 KV pool flag (ISSUE 6): the block
                                  # counts already reflect the 2x pool, this
                                  # labels WHY a replica reports double
                                  "kv_quant",
                                  # speculative-decoding acceptance (ISSUE 5):
                                  # the router aggregates these into the
                                  # fleet-wide tpu9_router_spec_* signals
                                  "spec_proposed", "spec_accepted",
                                  "spec_acceptance_rate",
                                  # serving submesh (ISSUE 9): which topology
                                  # this replica runs and its worst-chip live
                                  # HBM — the fleet view's multichip evidence
                                  "topo_tp", "topo_fsdp", "topo_n_chips",
                                  "hbm_used_gb_per_chip",
                                  # HBM watermarks + liveness watermark
                                  # (ISSUE 14): peak/predicted/limit make
                                  # planner-vs-reality drift graphable; the
                                  # ages are the watchdog's raw evidence,
                                  # surfaced so `tpu9 top` / the black box
                                  # can show WHY a verdict was reached
                                  "hbm_peak_gb_per_chip",
                                  "hbm_predicted_gb_per_chip",
                                  "hbm_limit_gb_per_chip",
                                  "windows_processed",
                                  "last_dispatch_age_s",
                                  "last_progress_age_s",
                                  # recompile sentinel (ISSUE 11): a non-zero
                                  # post_warmup count is a mid-serve XLA
                                  # compile — the closed-signature invariant
                                  # broke at runtime
                                  "graph_compiles",
                                  "graph_compiles_post_warmup",
                                  # fleet timeline + goodput accounting
                                  # (ISSUE 12): windowed tokens/sec, the
                                  # cumulative counters the gateway's
                                  # accountant differentiates, and the decode
                                  # physics constants the control plane
                                  # prices MFU/MBU from
                                  "tokens_per_sec", "tokens_generated",
                                  "graph_compile_stall_s",
                                  "decode_bytes_per_token_per_chip",
                                  "decode_flops_per_token_per_chip",
                                  # the devices the engine is placed on, as
                                  # its own jax reports them
                                  "device_platform", "device_kind",
                                  "device_count"):
                            if k in stats:
                                extra[k] = stats[k]
                        pc = stats.get("prefix_cache")
                        if isinstance(pc, dict):
                            hits = pc.get("hits", 0)
                            misses = pc.get("misses", 0)
                            extra["prefix_hits"] = hits
                            extra["prefix_misses"] = misses
                            extra["prefix_hit_rate"] = (
                                hits / (hits + misses) if hits + misses else 0.0)
                        # cold-start decomposition (ISSUE 13): the runner half
                        # of the per-replica readiness record — flat
                        # coldstart_* scalars merged by /api/v1/coldstart
                        for k, v in stats.items():
                            if k.startswith("coldstart_"):
                                extra[k] = v
                        # kvwire (ISSUE 16): block-ship counters + latency
                        # percentiles — one prefix covers the whole family
                        # (engine.stats() keeps them flat on purpose)
                        for k, v in stats.items():
                            if k.startswith("kvwire_"):
                                extra[k] = v
                        # kv tiering (ISSUE 20): occupancy/paging counters
                        # (same one-startswith-loop contract as kvwire_*),
                        # then the directory summaries: a bounded top-K
                        # prefix-key digest, the eviction-delta retractions,
                        # and this replica's peer-cache publications — never
                        # full key lists
                        for k, v in stats.items():
                            if k.startswith("kvtier_"):
                                extra[k] = v
                    tier_hi = last_tier_delta
                    if kvtier_hb and state["engine"] is not None:
                        # serving-plane kv_tier choices (spill scoring,
                        # up-page pulls, lost-copy recomputes) arrive as
                        # plain journal dicts; the RUNNER records them —
                        # the serving plane must not import the ledger
                        # (BND001), same flow as spans/health verdicts
                        for d in state["engine"].drain_kvtier_decisions():
                            decision_ledger.record(
                                "kv_tier", d.pop("decision", "spill"), **d)
                        if kv_client is not None:
                            for khex, payload, n_tok in \
                                    state["engine"].drain_kv_spills():
                                try:
                                    t0m = _now.monotonic()
                                    digest = await kv_client.put_kv(
                                        payload)
                                    state["engine"].note_kvwire_ship(
                                        _now.monotonic() - t0m)
                                    peer_pub.append(
                                        (khex, digest, n_tok))
                                except Exception as exc:  # noqa: BLE001
                                    log.warning(
                                        "kv tier peer spill failed: %s",
                                        exc)
                            del peer_pub[:-32]
                        digest_s = state["engine"].kvtier_digest()
                        if digest_s:
                            extra["kvtier_keys"] = digest_s
                        deltas, tier_hi = state["engine"].kvtier_deltas(
                            last_tier_delta)
                        lost = [hx for kind, hx in deltas
                                if kind in ("evict", "peer")]
                        if lost:
                            extra["kvtier_evicted"] = ",".join(lost)
                        if peer_pub:
                            extra["kvtier_peer"] = ",".join(
                                f"{hx}:{dig}:{nt}"
                                for hx, dig, nt in peer_pub)
                    with phase("runner.heartbeat", engine.host_phases) as beat:
                        # scale-out readiness (ISSUE 17): per-group bind
                        # progress of a streaming restore — the router's
                        # partial-readiness admission reads these off the
                        # pressure hash, the coordinator off the heartbeat
                        for k, v in stats.items():
                            if k.startswith("scaleout_"):
                                extra[k] = v
                        # latency decomposition (ISSUE 8): per-phase p50/p95
                        # flat scalars → /api/v1/metrics "engines" section
                        for k, v in (stats.get("latency") or {}).items():
                            extra[k] = v
                        fl = stats.get("flight")
                        if isinstance(fl, dict):
                            extra["flight_records"] = fl.get("records", 0)
                            extra["flight_last_seq"] = fl.get("last_seq", 0)
                        # health verdict (ISSUE 14): classified HERE, shipped
                        # on the same beat — the gateway folds it into the
                        # engines merge and the router ejects on `stalled`
                        health, reason = watchdog.assess(stats)
                        extra["health"] = health
                        extra["health_reason"] = reason
                        extra["health_since_s"] = round(watchdog.in_state_s, 3)
                        # post-mortem triggers: a watchdog trip (once per
                        # incident) or the serve loop's own death (the crash
                        # handler left engine.last_postmortem behind). The
                        # record is held until the gateway ACCEPTS it — a
                        # gateway blip must not eat the black box.
                        if pending_pm is None:
                            pm_reason = pm_exc = ""
                            if stats.get("engine_dead") and not crash_shipped:
                                crash_shipped = True
                                pm_reason, pm_exc = ("engine_dead",
                                                     "serve loop dead")
                                # the dead engine trips the watchdog's stall
                                # flag too — SAME incident: consume it, or
                                # the next beat ships a duplicate
                                # watchdog_stall record for this death
                                watchdog.pop_stall_trip()
                            elif watchdog.pop_stall_trip():
                                pm_reason, pm_exc = "watchdog_stall", reason
                            if pm_reason:
                                # blackbox() reads live engine state next to
                                # a dead/wedged loop — a failing snapshot
                                # must degrade to a header-only record, never
                                # kill THIS loop (the replica would fall
                                # silent, the outcome the watchdog prevents)
                                try:
                                    raw = (engine.last_postmortem
                                           if pm_reason == "engine_dead"
                                           and engine.last_postmortem
                                           else engine.blackbox(pm_reason,
                                                                pm_exc))
                                    pending_pm = build_postmortem(
                                        container_id=cfg.container_id, **raw)
                                except Exception:   # noqa: BLE001
                                    log.exception(
                                        "post-mortem snapshot failed")
                                    pending_pm = build_postmortem(
                                        reason=pm_reason,
                                        exception=f"{pm_exc} (snapshot "
                                                  "failed; header only)",
                                        container_id=cfg.container_id,
                                        stats={k: v for k, v in stats.items()
                                               if isinstance(v, (int, float,
                                                                 str, bool))})
                        # engine spans ride the heartbeat the way worker rings
                        # ride the keepalive (worker.py ship analogue)
                        spans, ship_hi = tracer.export_new(
                            since_mono=last_span_ship, limit=RING_CAP)
                        decs, dec_hi = decision_ledger.export_new(
                            since_seq=last_dec_ship, limit=512)
                        beat.set(spans=len(spans))
                    if faults is not None and faults.active(
                            "heartbeat_loss"):
                        # induced heartbeat loss: the replica falls
                        # SILENT (stale-aging + health plane must catch
                        # it) without touching the serve loop; the span
                        # watermark does not advance, so spans re-ship
                        # once the window clears
                        await event_wait(state["beat"], timeout=beat_s)
                        state["beat"].clear()
                        continue
                    async with session.post(
                            gateway_url + "/rpc/llm/pressure",
                            json={"container_id": cfg.container_id,
                                  "token_pressure": stats["token_pressure"],
                                  "active_streams": stats["active_streams"],
                                  "extra": extra, "spans": spans,
                                  "decisions": decs},
                            timeout=aiohttp.ClientTimeout(total=5)) as resp:
                        if resp.status >= 400 and not rejected_logged:
                            rejected_logged = True
                            log.warning(
                                "pressure heartbeat rejected (%d): %s — "
                                "router/autoscaler will see no engine load",
                                resp.status, (await resp.text())[:200])
                        elif resp.status < 400:
                            rejected_logged = False
                            last_span_ship = ship_hi
                            last_dec_ship = dec_hi
                            last_tier_delta = tier_hi
                    # black-box ship AFTER the heartbeat, in its own
                    # error scope: the heartbeat is what keeps this
                    # replica visible to the fleet — a persistently
                    # failing postmortem endpoint must never starve it
                    # (3 missed beats and a HEALTHY replica reads as
                    # silent, ejected by the very plane observing it).
                    # Bounded retry on EVERY path: transient errors get
                    # 30 beats, a gateway that actively REJECTS the
                    # record (4xx — container state expired) gets 5, then
                    # the record is dropped so the trigger checks above
                    # can capture the next incident's evidence.
                    if pending_pm is not None:
                        pm_retry.next_delay()     # count the attempt;
                        # the heartbeat cadence IS the pacing
                        pm_status = 0
                        try:
                            async with session.post(
                                    gateway_url + "/rpc/llm/postmortem",
                                    json={"container_id": cfg.container_id,
                                          "record": pending_pm},
                                    timeout=aiohttp.ClientTimeout(
                                        total=5)) as resp:
                                pm_status = resp.status
                                if resp.status < 400:
                                    log.warning(
                                        "shipped post-mortem record (%s)",
                                        pending_pm.get("reason"))
                                    pending_pm = None
                                    pm_retry.reset()
                        except (aiohttp.ClientError,
                                asyncio.TimeoutError) as exc:
                            log.debug("post-mortem ship failed: %s", exc)
                        if pending_pm is not None and pm_retry.give_up(
                                permanent=400 <= pm_status < 500):
                            log.error(
                                "dropping post-mortem record (%s) after "
                                "%d attempts (last status %d)",
                                pending_pm.get("reason"),
                                pm_retry.attempts, pm_status)
                            pending_pm = None
                            pm_retry.reset()
                except (aiohttp.ClientError, asyncio.TimeoutError) as exc:
                    log.debug("pressure heartbeat failed: %s", exc)
                # request completions nudge the next beat immediately: an
                # aggressive scale-to-zero otherwise kills the replica
                # before the beat tick and its engine spans die with it
                await event_wait(state["beat"], timeout=beat_s)
                state["beat"].clear()

    await pressure_loop() if gateway_url else await asyncio.Event().wait()


def main() -> None:
    logging.basicConfig(level=logging.INFO, stream=sys.stderr)
    cfg = RunnerConfig.from_env()
    if not cfg.handler:
        print("TPU9_HANDLER not set", file=sys.stderr)
        sys.exit(2)
    asyncio.run(amain())


if __name__ == "__main__":
    main()
