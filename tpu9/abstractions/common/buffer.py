"""Request buffer: holds client requests, discovers ready containers, and
forwards with per-container concurrency admission.

Reference analogue: ``pkg/abstractions/endpoint/buffer.go`` — request ring,
container discovery via address keys + health probes (:303,334,359),
per-container concurrency tokens (:457-506), reverse proxying (:666). tpu9's
buffer forwards JSON/bytes bodies over aiohttp and exposes wait-slots the
autoscaler samples as queue depth.
"""

from __future__ import annotations

import asyncio
import contextlib
import logging
import os
import random
import time
from dataclasses import dataclass, field
from typing import Any, Optional  # noqa: F401

import aiohttp

from ...repository import ContainerRepository
from ...utils.aio import reap, spawn
from ...types import ContainerStatus, Stub

log = logging.getLogger("tpu9.abstractions")


@dataclass
class BufferedRequest:
    method: str = "POST"
    path: str = "/"
    headers: Any = None            # CIMultiDict (duplicates preserved)
    body: bytes = b""
    enqueued_at: float = field(default_factory=time.monotonic)
    future: Optional[asyncio.Future] = None
    # fleet-router replica preference (container ids, best first) — see
    # tpu9.router.fleet: affinity/JSQ ordering computed above the buffer
    prefer: list = field(default_factory=list)
    # replicas observed FAILING this request's earlier attempts (gateway
    # failover, ISSUE 15): deprioritized below every other candidate —
    # only reused when nothing else exists (serving a maybe-dead replica
    # beats a guaranteed 502 on a one-replica fleet)
    avoid: list = field(default_factory=list)
    # per-request override of the buffer's timeout (gateway↔runner
    # control RPCs ride RouterConfig.rpc_timeout_s; 0 = buffer default)
    timeout_s: float = 0.0


@dataclass
class ForwardResult:
    status: int
    body: bytes
    # list of (name, value) pairs: duplicate response headers (multiple
    # Set-Cookie) must survive the proxy hop
    headers: list = field(default_factory=list)
    container_id: str = ""


class StreamHandle:
    """A container response relayed incrementally (SSE token streams,
    chunked downloads). Holds the container's concurrency token and the
    buffer's demand signal until closed — the autoscaler must not scale
    the serving container away mid-stream."""

    def __init__(self, resp, container_id: str, release,
                 acquire_s: float, t_send_mono: float):
        # the gateway's leg of a streamed request (ISSUE 41): how long
        # admission waited for a container, and the monotonic stamps just
        # before the request was sent and, here, with its headers back
        self.acquire_s = acquire_s
        self.t_send_mono = t_send_mono
        self.t_open_mono = time.monotonic()
        self._resp = resp
        self.container_id = container_id
        self._release = release
        self.status = resp.status
        self.headers = list(resp.headers.items())
        self._closed = False
        # optional sync callback fired once after release (the fleet
        # router's stream budget slot rides the handle's lifetime)
        self.on_close = None

    async def iter_chunks(self):
        async for chunk in self._resp.content.iter_any():
            yield chunk

    async def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            self._resp.close()
        except Exception:      # noqa: BLE001
            pass
        await self._release()
        if self.on_close is not None:
            self.on_close()


class RequestBuffer:
    def __init__(self, stub: Stub, containers: ContainerRepository,
                 request_timeout_s: float = 180.0, router=None, dialer=None,
                 drain_check=None):
        self.stub = stub
        self.containers = containers
        self.router = router    # optional LlmRouter for pressure/affinity
        self.dialer = dialer    # optional cross-host Dialer (network/relay)
        # optional (container_id) -> bool: the fleet router marks replicas
        # draining during graceful scale-down; placing NEW work on one
        # would be killed mid-flight moments later
        self.drain_check = drain_check
        self.request_timeout_s = request_timeout_s
        self._queue: asyncio.Queue[BufferedRequest] = asyncio.Queue()
        self._session: Optional[aiohttp.ClientSession] = None
        self._wake = None
        self._task: Optional[asyncio.Task] = None
        self._inflight = 0
        self._open = 0     # unresolved requests: queued + in-hand + in-flight

    @property
    def depth(self) -> int:
        """Open (unresolved) requests — the autoscaler's queue-depth signal.
        Counts requests the loop is holding between queue and container too,
        otherwise a request waiting for the first container to exist is
        invisible and scale-from-zero never triggers."""
        return self._open

    async def start(self) -> "RequestBuffer":
        if self._session is None:
            self._session = aiohttp.ClientSession()
        if self._wake is None:
            # admission wakeups: token releases + containers turning RUNNING
            # (published by ContainerRepository) — waiting is event-driven
            # with a bounded-poll fallback, not a sleep loop
            from ...repository import Keys
            self._wake = self.containers.store.subscribe(
                Keys.stub_wake(self.stub.stub_id))
        if self._task is None:
            self._task = asyncio.create_task(self._process_loop())
        return self

    async def stop(self) -> None:
        if self._task:
            # reap: swallows the child's CancelledError but re-raises if
            # stop() itself is cancelled mid-drain (ASY003)
            await reap(self._task)
            self._task = None
        if self._wake is not None:
            self._wake.close()
            self._wake = None
        if self._session:
            await self._session.close()
            self._session = None

    async def _wait_wake(self, timeout: float) -> None:
        """Block until an admission signal arrives (or the fallback timeout
        elapses — the poll guard against a lost wakeup)."""
        if self._wake is None:
            await asyncio.sleep(min(timeout, 0.05))
            return
        await self._wake.get(timeout=timeout)

    # -- public forwarding API -----------------------------------------------

    async def forward(self, method: str = "POST", path: str = "/",
                      headers=None, body: bytes = b"",
                      prefer: Optional[list] = None,
                      avoid: Optional[set] = None,
                      timeout_s: Optional[float] = None) -> ForwardResult:
        """``headers`` may be a dict or a list of (name, value) pairs
        (duplicates preserved). ``timeout_s`` overrides the buffer's
        request timeout for this call (control RPCs pass the shorter
        RouterConfig.rpc_timeout_s bound)."""
        from multidict import CIMultiDict
        budget = timeout_s or self.request_timeout_s
        req = BufferedRequest(method=method, path=path,
                              headers=CIMultiDict(headers or {}), body=body,
                              future=asyncio.get_running_loop().create_future(),
                              prefer=list(prefer or []),
                              avoid=list(avoid or []),
                              timeout_s=budget)
        self._open += 1
        req.future.add_done_callback(lambda _f: self._dec_open())
        await self._queue.put(req)
        try:
            return await asyncio.wait_for(req.future, budget)
        except asyncio.TimeoutError:
            if not req.future.done():
                req.future.cancel()
            return ForwardResult(status=504, body=b'{"error":"request timed out"}')

    def _dec_open(self) -> None:
        self._open -= 1

    async def forward_stream(self, method: str = "POST", path: str = "/",
                             headers=None, body: bytes = b"",
                             prefer: Optional[list] = None,
                             avoid: Optional[set] = None,
                             gap_s: Optional[float] = None):
        """Streaming forward: returns a :class:`StreamHandle` whose chunks
        arrive as the container produces them (LLM token streams), or a
        :class:`ForwardResult` on admission/connect failure. The caller
        MUST ``close()`` the handle (token + demand are held until then).

        ``gap_s`` bounds the silent gap between chunks (ISSUE 15
        mid-stream stall detection). Only callers that can RECOVER from
        the resulting timeout (the gateway's resumable relay) should set
        it — None keeps the legacy request-timeout bound, so a
        legitimately quiet non-resumable stream is never truncated."""
        from multidict import CIMultiDict
        # demand registers BEFORE admission: scale-from-zero only triggers
        # if the autoscaler can see this request waiting (same contract as
        # the buffered path and _ws_proxy's hold_demand)
        self._open += 1
        t_acquire = time.monotonic()
        # full request timeout for admission, same as the buffered path —
        # a scale-from-zero LLM cold start routinely exceeds 30s and a
        # streaming request must ride it out like any other
        target = await self.acquire(deadline_s=self.request_timeout_s,
                                    body=body, prefer=prefer, avoid=avoid)
        if target is None:
            self._dec_open()
            return ForwardResult(status=504,
                                 body=b'{"error":"no capacity"}')
        container_id, address = target
        acquire_s = time.monotonic() - t_acquire
        released = False

        async def release() -> None:
            nonlocal released
            if released:
                return
            released = True
            self._dec_open()
            await self.containers.release_request_token(self.stub.stub_id,
                                                        container_id)

        # per-chunk gap bound (ISSUE 15): a replica that wedges mid-stream
        # (gray stall) produces no bytes and no error — without a gap
        # bound the relay would park for the whole request timeout before
        # the gateway's failover could resume the stream elsewhere.
        # TPU9_STREAM_GAP_S overrides for chaos tests.
        gap_s = float(os.environ.get("TPU9_STREAM_GAP_S", "") or 0) \
            or min(gap_s or self.request_timeout_s,
                   self.request_timeout_s)
        t_send = time.monotonic()
        try:
            resp = await self._session.request(
                method, f"http://{address}{path}", data=body or None,
                headers=CIMultiDict(headers or {}),
                # no total timeout: a long generation streams for minutes;
                # sock_read bounds per-chunk gaps instead
                timeout=aiohttp.ClientTimeout(
                    total=None, sock_connect=10.0,
                    sock_read=gap_s))
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as exc:
            await release()
            return ForwardResult(
                status=502,
                body=f'{{"error":"{type(exc).__name__}"}}'.encode(),
                container_id=container_id)
        return StreamHandle(resp, container_id, release,
                            acquire_s=acquire_s, t_send_mono=t_send)

    @contextlib.contextmanager
    def hold_demand(self):
        """Register demand with the autoscaler without a buffered request.
        Websocket sessions hold this for their WHOLE lifetime — demand is
        what keeps the autoscaler from scaling the serving container away
        mid-session (request tokens do not influence scale-down)."""
        self._open += 1
        try:
            yield
        finally:
            self._dec_open()

    # -- hot loop --------------------------------------------------------------

    async def _process_loop(self) -> None:
        assert self._session is not None
        while True:
            req = await self._queue.get()
            try:
                await self._process_one(req)
            except asyncio.CancelledError:
                raise
            except Exception as exc:    # noqa: BLE001 — one store blip
                # must not kill forwarding for the STUB forever (a dead
                # loop = every request 504s until gateway restart);
                # re-queue the request so the retry path still owns it
                import logging
                logging.getLogger("tpu9.abstractions").warning(
                    "request-buffer pass failed: %s", exc)
                if req.future is not None and not req.future.done():
                    await self._queue.put(req)
                await self._wait_wake(0.25)

    async def _process_one(self, req: "BufferedRequest") -> None:
        if req.future is not None and req.future.done():
            return     # caller gave up (timeout/cancel) while queued
        if (time.monotonic() - req.enqueued_at) > (req.timeout_s
                                                   or self.request_timeout_s):
            if req.future and not req.future.done():
                req.future.set_result(ForwardResult(
                    status=504, body=b'{"error":"expired in queue"}'))
            return
        target = await self._acquire_container(req.body, prefer=req.prefer,
                                               avoid=set(req.avoid))
        if target is None:
            # no capacity: requeue, then block on the next admission
            # signal (token release / container RUNNING) with a 250 ms
            # fallback poll as the lost-wakeup guard
            await self._queue.put(req)
            await self._wait_wake(0.25)
            return
        container_id, address = target
        self._inflight += 1
        # spawn, not bare create_task (ASY002): the loop weak-refs tasks, so
        # a GC'd forward would strand the request AND leak the inflight slot
        spawn(self._forward_one(req, container_id, address),
              name=f"buffer-forward-{container_id[-8:]}")

    async def acquire(self, deadline_s: float = 30.0,
                      body: bytes = b"",
                      prefer: Optional[list] = None,
                      avoid: Optional[set] = None
                      ) -> Optional[tuple[str, str]]:
        """Public admission: wait for a container with a concurrency token
        until ``deadline_s`` elapses (websocket sessions and other direct
        consumers; HTTP requests ride the buffered _process_loop). Waiting
        is driven by admission wakeups, with a bounded fallback poll."""
        deadline = time.monotonic() + deadline_s
        while time.monotonic() < deadline:
            target = await self._acquire_container(body, prefer=prefer,
                                                   avoid=avoid)
            if target is not None:
                return target
            await self._wait_wake(min(0.25, max(deadline
                                                - time.monotonic(), 0.01)))
        return None

    async def _acquire_container(self, body: bytes = b"",
                                 prefer: Optional[list] = None,
                                 avoid: Optional[set] = None
                                 ) -> Optional[tuple[str, str]]:
        """Discover RUNNING containers and grab a concurrency token on one.
        Plain stubs spread randomly; LLM stubs route by pressure + prefix
        affinity through the router; the fleet router's preference order
        (when given) takes precedence over both."""
        states = await self.containers.containers_by_stub(
            self.stub.stub_id, status=ContainerStatus.RUNNING.value)
        if self.drain_check is not None:
            # the router's prefer list never contains draining replicas,
            # but the token-fallback walk below must not land on one
            # either — its in-flight work is about to be stopped
            alive = [s for s in states
                     if not self.drain_check(s.container_id)]
            # draining the LAST replica: serving it beats a guaranteed 504
            states = alive or states
        if avoid:
            # replicas that already failed this request's earlier
            # attempts (gateway failover): skipped entirely unless
            # they are ALL that exists
            fresh = [s for s in states if s.container_id not in avoid]
            states = fresh or states
        phash = ""
        if self.router is not None:
            from ..llm import prefix_hash
            phash = prefix_hash(body) if body else ""
            states = await self.router.rank(self.stub.stub_id, states, body,
                                            phash=phash)
        else:
            random.shuffle(states)
        if prefer:
            # stable sort: preferred replicas in the router's order first,
            # everything else keeps its rank/shuffle order as fallback
            pos = {cid: i for i, cid in enumerate(prefer)}
            states.sort(key=lambda s: pos.get(s.container_id, len(pos)))
        limit = max(self.stub.config.concurrent_requests, 1)
        for s in states:
            address = s.address or await self.containers.get_address(
                s.container_id)
            if not address:
                continue
            if await self.containers.acquire_request_token(
                    self.stub.stub_id, s.container_id, limit):
                if self.dialer is not None:
                    # AFTER winning the token (don't pay probe/tunnel setup
                    # for candidates we then skip): unroutable addresses
                    # (BYOC machines behind NAT) come back as loopback
                    # relay-tunnel endpoints
                    address = await self.dialer.ensure_route(address,
                                                             s.worker_id)
                if self.router is not None and phash:
                    await self.router.record_served(self.stub.stub_id, phash,
                                                    s.container_id)
                return s.container_id, address
        return None

    async def _forward_one(self, req: BufferedRequest, container_id: str,
                           address: str) -> None:
        assert self._session is not None
        url = f"http://{address}{req.path}"
        try:
            async with self._session.request(
                    req.method, url, data=req.body or None,
                    headers=req.headers,
                    timeout=aiohttp.ClientTimeout(
                        total=req.timeout_s or self.request_timeout_s)
            ) as resp:
                body = await resp.read()
                result = ForwardResult(status=resp.status, body=body,
                                       headers=list(resp.headers.items()),
                                       container_id=container_id)
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as exc:
            result = ForwardResult(status=502,
                                   body=f'{{"error":"{type(exc).__name__}"}}'.encode(),
                                   container_id=container_id)
        finally:
            self._inflight -= 1
            await self.containers.release_request_token(self.stub.stub_id,
                                                        container_id)
        if req.future and not req.future.done():
            req.future.set_result(result)
