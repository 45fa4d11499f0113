"""The tpu9 gateway (control plane).

Reference analogue: ``pkg/gateway/gateway.go`` — boots repositories,
scheduler, abstraction services; serves the SDK API + REST + invoke routes;
re-hydrates deployments on restart (InstanceController, instance.go:444);
drains before shutdown. One process, one port, embedded state server for
workers to join (the reference serves repos to workers over gRPC the same
way, gateway.go:353).

Route map:
  /api/v1/...                REST management API (auth: workspace token)
  /rpc/...                   SDK RPC (JSON bodies; auth: workspace token)
  /endpoint/{name}[/...]     invoke active deployment by name
  /health                    unauthenticated liveness
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import logging
import os
import re
import time
from typing import Optional

from aiohttp import web

from ..utils.fsio import atomic_write_bytes
from ..abstractions.endpoint import EndpointService
from ..abstractions.function import FunctionService
from ..abstractions.image import ImageService
from ..abstractions.pod import PodService
from ..observability import EventBus, metrics
from ..scheduler.pool_health import PoolMonitor
from ..abstractions.primitives import (MapService, OutputService,
                                       PrimitiveError, QueueService,
                                       SignalService, VolumeFiles)
from ..abstractions.taskqueue import TaskQueueService
from ..images import ImageBuilder, ImageSpec
from ..backend import BackendDB
from ..config import AppConfig, env_no_egress
from ..repository import ContainerRepository, TaskRepository, WorkerRepository
from ..repository.keys import Keys
from ..scheduler import Scheduler
from ..statestore import MemoryStore, RemoteStore, StateServer, StateStore
from ..task import Dispatcher
from ..types import (Stub, StubConfig, StubType, TaskPolicy, Workspace,
                     new_id)

log = logging.getLogger("tpu9.gateway")


class Gateway:
    def __init__(self, cfg: AppConfig,
                 store: Optional[StateStore] = None,
                 backend: Optional[BackendDB] = None,
                 pools: Optional[dict] = None):
        self.cfg = cfg
        self.store = store or MemoryStore()
        if backend is None:
            # database.path accepts a postgresql:// DSN (HA control plane:
            # concurrent gateways over one Postgres) or a file path
            # (single-binary SQLite default)
            from ..backend.pg import open_backend
            backend = open_backend(cfg.database.path,
                                   secret_key=cfg.database.secret_key)
        self.backend = backend
        from ..scheduler.quota import QuotaService
        self.quota = QuotaService(self.store, self.backend)
        # agent-mode pools are self-hosted (machines reconcile against the
        # backend/store directly), so the gateway can always construct them
        self._pools_provided = pools is not None
        pools = dict(pools or {})
        from ..scheduler.pools import AgentMachinePool
        for p in cfg.pools:
            if p.mode == "agent" and p.name not in pools:
                pools[p.name] = AgentMachinePool(p, self.backend, self.store)
        self.scheduler = Scheduler(self.store, cfg.scheduler,
                                   pools=pools, quota=self.quota)
        self.workers = WorkerRepository(self.store, cfg.worker.keepalive_ttl_s)
        self.containers = ContainerRepository(self.store)
        self.tasks = TaskRepository(self.store)
        from ..abstractions.common.tokens import RunnerTokenCache
        self.runner_tokens = RunnerTokenCache(self.backend)
        # containers read this to reach us; filled once the port is bound
        self.runner_env: dict[str, str] = {}
        # fleet inference router (ISSUE 2): KV-affinity routing, per-tenant
        # fair queuing, SLO-aware shedding on the invoke paths
        self.fleet_router = None
        if cfg.router.enabled:
            from ..router import FleetRouter
            self.fleet_router = FleetRouter(cfg.router, self.store,
                                            self.containers,
                                            backend=self.backend)
        self.endpoints = EndpointService(self.backend, self.scheduler,
                                         self.containers,
                                         runner_env=self.runner_env,
                                         runner_tokens=self.runner_tokens)
        self.endpoints.fleet_router = self.fleet_router
        # request survivability (ISSUE 15): idempotency journal for
        # client-supplied X-Tpu9-Request-Id retries — a client retry of
        # an in-flight/completed request attaches to the journal instead
        # of double-executing
        from .survival import RequestJournal
        self.journal = RequestJournal(self.store,
                                      ttl_s=cfg.router.journal_ttl_s,
                                      body_cap=cfg.router.journal_body_cap)
        self.dispatcher = Dispatcher(self.store, self.backend)

        async def _container_alive(container_id: str) -> bool:
            return await self.containers.get_state(container_id) is not None

        self.dispatcher.container_alive = _container_alive
        self.taskqueues = TaskQueueService(self.backend, self.scheduler,
                                           self.containers, self.dispatcher,
                                           runner_env=self.runner_env,
                                           runner_tokens=self.runner_tokens)
        self.functions = FunctionService(self.backend, self.scheduler,
                                         self.containers, self.dispatcher,
                                         runner_env=self.runner_env,
                                         runner_tokens=self.runner_tokens)
        self.images = ImageService(
            self.backend,
            ImageBuilder(cfg.image.registry_dir,
                         network_ok=not env_no_egress()),
            scheduler=self.scheduler,
            runner_env=self.runner_env,
            runner_tokens=self.runner_tokens,
            build_mode=cfg.image.build_mode,
            build_timeout_s=cfg.image.build_timeout_s,
            build_cpu_millicores=cfg.image.build_cpu_millicores,
            build_memory_mb=cfg.image.build_memory_mb)
        self.pods = PodService(self.backend, self.scheduler, self.containers,
                               self.store, runner_env=self.runner_env,
                               runner_tokens=self.runner_tokens)
        from ..abstractions.disk import DiskService
        self.disks = DiskService(self.backend, self.store)
        # every request-building service decorates disk mounts with
        # snapshot ids + placement affinity
        self.pods.disks = self.disks
        self.endpoints.disks = self.disks
        self.taskqueues.disks = self.disks
        self.functions.disks = self.disks
        from ..abstractions.bot import BotService
        self.bots = BotService(self.backend, self.scheduler, self.containers,
                               self.dispatcher, self.store,
                               runner_env=self.runner_env,
                               runner_tokens=self.runner_tokens)
        self.bots.disks = self.disks
        self.maps = MapService(self.store)
        self.queues = QueueService(self.store)
        self.signals = SignalService(self.store)
        self.outputs = OutputService(self.backend, cfg.storage.local_root)
        from ..storage import make_store
        self.volume_files = VolumeFiles(self.backend, cfg.storage.local_root,
                                        store=make_store(cfg.storage))
        # (ws, name) -> (listing fingerprint, manifest json) for CacheFS
        # volume mounts — re-chunking a stable multi-GB volume per mount
        # would dwarf the mount itself
        self._volume_manifest_cache: dict[tuple, tuple[str, str]] = {}
        self._volume_manifest_builds: dict[tuple, asyncio.Task] = {}
        self.events = EventBus(self.store, sink_url=cfg.monitoring.events_http_url
                               if cfg.monitoring.events_sink == "http" else "",
                               cluster=cfg.cluster_name)
        from ..observability import UsageService
        self.usage = UsageService(self.store, self.backend)
        # decision ledger caps (ISSUE 19): re-bound the module singleton
        # from config before any plane records into it
        from ..observability.decisions import ledger as decision_ledger
        decision_ledger.configure(
            capacity=cfg.slo.decisions_capacity,
            max_requests=cfg.slo.decisions_max_requests,
            per_request=cfg.slo.decisions_per_request,
            idle_ttl_s=cfg.slo.decisions_idle_ttl_s)
        # fleet SLO / timeline / goodput layer (ISSUE 12): bounded
        # time-series store + burn-rate evaluator + per-tenant goodput
        # accounting behind /api/v1/{timeline,slo} and `tpu9 top`
        self.fleetobs = None
        # scale-out plane (ISSUE 17): the gateway-side multicast-tree
        # coordinator — fed by the observer's cache-plane/heartbeat
        # cadences, publishing the tree plan joining workers read
        self.scaleout = None
        if cfg.slo.enabled:
            from ..scaleout import scaleout_on
            if scaleout_on(cfg.scaleout):
                from ..scaleout.coordinator import ScaleoutCoordinator
                self.scaleout = ScaleoutCoordinator(cfg.scaleout)
            from .fleetobs import FleetObserver
            self.fleetobs = FleetObserver(cfg.slo, self.store,
                                          fleet_router=self.fleet_router,
                                          scaleout=self.scaleout)
        self.pool_monitor = PoolMonitor(
            self.store, pools,
            {p.name: p for p in cfg.pools},
            quota=self.quota) if (self._pools_provided or pools) else None
        self.extra_services: dict[str, object] = {}
        self.state_server: Optional[StateServer] = None
        self.relay = None              # Optional[RelayServer]
        self.dialer = None             # Optional[Dialer]
        self.otlp = None               # Optional[OtlpExporter]
        self._proxy_session = None     # shared pod-proxy ClientSession
        # verified (proc_id → container_id) pairings for sandbox output
        # polls: one worker round-trip per proc, then bus reads only
        self._sbx_proc_owner: dict[str, str] = {}
        self._runner: Optional[web.AppRunner] = None
        self._shutting_down = asyncio.Event()
        self.port = cfg.gateway.http_port
        self.app = self._build_app()

    # ------------------------------------------------------------------

    def _build_app(self) -> web.Application:
        app = web.Application(middlewares=[self._quota_middleware,
                                           self._auth_middleware],
                              client_max_size=512 * 1024 * 1024)
        r = app.router
        r.add_get("/health", self._health)
        # SDK RPC
        r.add_post("/rpc/auth/check", self._rpc_auth_check)
        r.add_post("/rpc/stub/get-or-create", self._rpc_get_or_create_stub)
        r.add_post("/rpc/object/put", self._rpc_put_object)
        r.add_get("/rpc/object/{object_id}", self._rpc_get_object)
        r.add_post("/rpc/deploy", self._rpc_deploy)
        # tasks / queues / functions
        r.add_post("/rpc/taskqueue/put", self._rpc_tq_put)
        r.add_post("/rpc/taskqueue/pop", self._rpc_tq_pop)
        r.add_get("/rpc/taskqueue/status/{stub_id}", self._rpc_tq_status)
        r.add_post("/rpc/function/invoke", self._rpc_fn_invoke)
        r.add_post("/rpc/schedule/register", self._rpc_schedule_register)
        r.add_get("/rpc/task/{task_id}", self._rpc_task_get)
        r.add_get("/rpc/task/{task_id}/result", self._rpc_task_result)
        r.add_post("/rpc/task/{task_id}/claim", self._rpc_task_claim)
        r.add_post("/rpc/task/{task_id}/complete", self._rpc_task_complete)
        r.add_post("/rpc/task/{task_id}/cancel", self._rpc_task_cancel)
        r.add_post("/rpc/llm/pressure", self._rpc_llm_pressure)
        r.add_post("/rpc/llm/postmortem", self._rpc_llm_postmortem)
        # bot (petri-net orchestration)
        r.add_post("/rpc/bot/session", self._rpc_bot_session_create)
        r.add_get("/rpc/bot/{stub_id}/sessions", self._rpc_bot_sessions)
        r.add_delete("/rpc/bot/{stub_id}/session/{session_id}",
                     self._rpc_bot_session_delete)
        r.add_post("/rpc/bot/{stub_id}/session/{session_id}/push",
                   self._rpc_bot_push)
        r.add_post("/rpc/bot/{stub_id}/session/{session_id}/pop",
                   self._rpc_bot_pop)
        r.add_get("/rpc/bot/{stub_id}/session/{session_id}/state",
                  self._rpc_bot_state)
        r.add_get("/rpc/bot/{stub_id}/session/{session_id}/events",
                  self._rpc_bot_events)
        # pods / sandboxes
        r.add_post("/rpc/pod/create", self._rpc_pod_create)
        r.add_get("/rpc/pod/{container_id}/status", self._rpc_pod_status)
        r.add_post("/rpc/pod/{container_id}/exec", self._rpc_pod_exec)
        # sandbox depth: process manager / fs API / snapshots
        # (reference sdk sandbox.py:137,376,916)
        r.add_post("/rpc/pod/{container_id}/proc", self._rpc_sbx_spawn)
        r.add_get("/rpc/pod/{container_id}/proc", self._rpc_sbx_ps)
        r.add_get("/rpc/pod/{container_id}/proc/{proc_id}",
                  self._rpc_sbx_status)
        r.add_post("/rpc/pod/{container_id}/proc/{proc_id}/stdin",
                   self._rpc_sbx_stdin)
        r.add_post("/rpc/pod/{container_id}/proc/{proc_id}/kill",
                   self._rpc_sbx_kill)
        r.add_get("/rpc/pod/{container_id}/proc/{proc_id}/out",
                  self._rpc_sbx_out)
        r.add_post("/rpc/pod/{container_id}/fs", self._rpc_sbx_fs)
        r.add_post("/rpc/pod/{container_id}/snapshot",
                   self._rpc_sbx_snapshot)
        r.add_post("/rpc/pod/{container_id}/criu-checkpoint",
                   self._rpc_criu_checkpoint)
        r.add_get("/rpc/pod/snapshots", self._rpc_sbx_snapshots)
        r.add_route("*", "/pod/{container_id}/{tail:.*}", self._pod_proxy)
        # primitives
        r.add_post("/rpc/map/{name}", self._rpc_map)
        r.add_post("/rpc/queue/{name}", self._rpc_queue)
        r.add_post("/rpc/signal/{name}", self._rpc_signal)
        r.add_post("/rpc/output/save", self._rpc_output_save)
        r.add_get("/rpc/output/{output_id}", self._rpc_output_get)
        # durable disks
        r.add_get("/api/v1/disk", self._list_disks)
        r.add_post("/api/v1/disk/{name}/snapshot", self._disk_snapshot)
        r.add_delete("/api/v1/disk/{name}", self._disk_delete)
        # worker-token disk internals (manifest store/fetch + chunk sink
        # ride the image chunk registry)
        r.add_post("/rpc/internal/disk/{workspace_id}/{name}/manifest/"
                   "{snapshot_id}", self._internal_disk_manifest_put)
        r.add_get("/rpc/internal/disk/manifest/{snapshot_id}",
                  self._internal_disk_manifest_get)
        r.add_post("/rpc/internal/sbxsnap/{workspace_id}/{container_id}/"
                   "{snapshot_id}", self._internal_sbxsnap_put)
        r.add_get("/rpc/internal/sbxsnap/manifest/{snapshot_id}",
                  self._internal_sbxsnap_get)
        # container checkpoints (readiness-trigger restore fast path):
        # workers record the row, stream chunks into the distributed cache,
        # then land the manifest here; the scheduler's checkpoint_lookup
        # only hands out rows the status endpoint marked 'available'
        r.add_post("/rpc/internal/ckpt/{workspace_id}/{stub_id}/"
                   "{container_id}", self._internal_ckpt_record)
        r.add_post("/rpc/internal/ckpt/status/{checkpoint_id}",
                   self._internal_ckpt_status)
        r.add_post("/rpc/internal/ckpt/manifest/{checkpoint_id}",
                   self._internal_ckpt_manifest_put)
        r.add_get("/rpc/internal/ckpt/manifest/{checkpoint_id}",
                  self._internal_ckpt_manifest_get)
        r.add_get("/api/v1/volume", self._list_volumes)
        r.add_post("/api/v1/volume/{name}", self._create_volume)
        r.add_delete("/api/v1/volume/{name}", self._delete_volume)
        r.add_get("/rpc/volume/{name}/files", self._volume_list)
        r.add_put("/rpc/volume/{name}/files/{path:.+}", self._volume_put)
        r.add_get("/rpc/volume/{name}/files/{path:.+}", self._volume_get)
        r.add_delete("/rpc/volume/{name}/files/{path:.+}", self._volume_delete)
        # multipart volume transfer (reference sdk multipart.py)
        # worker-token volume reads for cross-host sync (repo-over-gRPC
        # semantics: workers act on behalf of any workspace)
        r.add_get("/rpc/internal/volume/{workspace_id}/{name}/manifest",
                  self._internal_volume_manifest)
        r.add_get("/rpc/internal/volume/{workspace_id}/{name}/files",
                  self._internal_volume_list)
        r.add_get("/rpc/internal/volume/{workspace_id}/{name}/files/{path:.+}",
                  self._internal_volume_get)
        r.add_put("/rpc/internal/volume/{workspace_id}/{name}/files/{path:.+}",
                  self._internal_volume_put)
        r.add_post("/rpc/volume/{name}/multipart/initiate/{path:.+}",
                   self._volume_mp_initiate)
        r.add_put("/rpc/volume/{name}/multipart/{upload_id}/{index}",
                  self._volume_mp_part)
        r.add_post("/rpc/volume/{name}/multipart/{upload_id}/complete",
                   self._volume_mp_complete)
        r.add_delete("/rpc/volume/{name}/multipart/{upload_id}",
                     self._volume_mp_abort)
        # images
        r.add_post("/rpc/image/verify", self._rpc_image_verify)
        r.add_post("/rpc/image/build", self._rpc_image_build)
        r.add_get("/rpc/image/status/{image_id}", self._rpc_image_status)
        r.add_get("/rpc/image/manifest/{image_id}", self._rpc_image_manifest)
        r.add_get("/rpc/image/chunk/{digest}", self._rpc_image_chunk)
        # build-runner upload API (runner/worker tokens)
        r.add_post("/rpc/image/chunk/{digest}", self._rpc_image_chunk_put)
        r.add_post("/rpc/image/manifest/{image_id}",
                   self._rpc_image_manifest_put)
        r.add_post("/rpc/image/complete/{image_id}",
                   self._rpc_image_complete)
        # REST v1 (management)
        r.add_get("/api/v1/deployment", self._list_deployments)
        r.add_delete("/api/v1/deployment/{id}", self._delete_deployment)
        r.add_get("/api/v1/container", self._list_containers)
        r.add_post("/api/v1/container/{id}/stop", self._stop_container)
        r.add_get("/api/v1/container/{id}/logs", self._container_logs)
        r.add_get("/api/v1/container/{id}/shell", self._container_shell)
        r.add_get("/api/v1/task", self._list_tasks)
        r.add_get("/api/v1/worker", self._list_workers)
        r.add_get("/api/v1/stub", self._list_stubs)
        r.add_get("/api/v1/secret", self._list_secrets)
        r.add_post("/api/v1/secret", self._upsert_secret)
        r.add_delete("/api/v1/secret/{name}", self._delete_secret)
        r.add_get("/api/v1/scheduler/stats", self._scheduler_stats)
        r.add_get("/api/v1/metrics", self._metrics)
        r.add_get("/api/v1/usage", self._usage_report)
        r.add_get("/api/v1/timeline", self._timeline)
        r.add_get("/api/v1/slo", self._slo)
        r.add_get("/api/v1/traces", self._traces)
        r.add_get("/api/v1/decisions", self._decisions)
        r.add_get("/api/v1/coldstart", self._coldstart)
        r.add_get("/api/v1/scaleout", self._scaleout)
        r.add_get("/api/v1/postmortem", self._postmortem)
        # engine flight recorder + on-demand TPU profiling (ISSUE 8)
        r.add_get("/api/v1/flight", self._flight)
        r.add_post("/api/v1/profile", self._profile)
        # per-workspace concurrency quotas (reference concurrencylimit.go);
        # reads are self-service, writes are operator-only
        r.add_get("/api/v1/concurrency-limit", self._get_concurrency_limit)
        r.add_post("/api/v1/concurrency-limit/{workspace_id}",
                   self._set_concurrency_limit)
        r.add_delete("/api/v1/concurrency-limit/{workspace_id}",
                     self._delete_concurrency_limit)
        # apps: deployment grouping (reference /api/v1/app group)
        r.add_get("/api/v1/app", self._list_apps)
        r.add_delete("/api/v1/app/{app_id}", self._delete_app)
        r.add_get("/api/v1/events", self._events)
        r.add_get("/api/v1/pools", self._pools)
        # workspaces (reference /api/v1/workspace group)
        r.add_post("/api/v1/workspace", self._workspace_create)
        r.add_post("/api/v1/workspace/{workspace_id}/token",
                   self._workspace_token)
        # tokens: self-service CRUD (reference /api/v1/token group)
        r.add_get("/api/v1/token", self._token_list)
        r.add_post("/api/v1/token", self._token_create)
        r.add_delete("/api/v1/token/{token_id}", self._token_revoke)
        # machines: BYOC agent fleet (reference pkg/agent + /api/v1/machine)
        r.add_post("/api/v1/machine", self._machine_create)
        r.add_get("/api/v1/machine", self._machine_list)
        r.add_delete("/api/v1/machine/{machine_id}", self._machine_delete)
        r.add_post("/api/v1/machine/join", self._machine_join)
        r.add_get("/api/v1/machine/{machine_id}/desired",
                  self._machine_desired)
        r.add_post("/api/v1/machine/{machine_id}/heartbeat",
                   self._machine_heartbeat)
        r.add_post("/api/v1/machine/{machine_id}/release",
                   self._machine_release)
        # worker-log relay through the agent (reference log_writer.go):
        # agents POST batches; operators read the tail
        r.add_post("/api/v1/machine/{machine_id}/logs",
                   self._machine_logs_push)
        r.add_get("/api/v1/machine/{machine_id}/logs",
                  self._machine_logs_get)
        # invoke
        r.add_route("*", "/endpoint/{name}", self._invoke)
        r.add_route("*", "/endpoint/{name}/{tail:.*}", self._invoke)
        # subdomain routing (reference middleware/subdomain.go:30): a request
        # whose Host is <subdomain>.<anything> hits its deployment directly.
        # Registered last so explicit routes win.
        r.add_route("*", "/{tail:.*}", self._subdomain_invoke)
        return app

    # -- lifecycle -----------------------------------------------------------

    async def start(self) -> "Gateway":
        if isinstance(self.store, RemoteStore):
            await self.store.connect()
        elif isinstance(self.store, MemoryStore) and self.cfg.gateway.state_port:
            # expose the embedded store to out-of-process workers
            # (state_port 0 disables; -1 means "any free port")
            port = max(self.cfg.gateway.state_port, 0)
            self.state_server = await StateServer(
                store=self.store, host=self.cfg.gateway.host, port=port,
                auth_token=self.cfg.database.state_auth_token).start()
        if self.cfg.gateway.relay_port:
            adv = self.cfg.gateway.advertise_host or self.cfg.gateway.host
            if adv in ("", "0.0.0.0", "::"):
                # a wildcard bind is not dialable by workers; external_url's
                # host is the address they actually reach us at
                ext = self.cfg.gateway.external_url
                adv = ext.split("://", 1)[-1].split("/", 1)[0] \
                    .rsplit(":", 1)[0] if ext else ""
            if adv:
                from ..network import Dialer, RelayServer
                # bind where the gateway itself binds: loopback-only dev
                # setups must not grow a world-reachable port
                self.relay = await RelayServer(
                    host=self.cfg.gateway.host or "0.0.0.0",
                    port=max(self.cfg.gateway.relay_port, 0)).start()
                self.dialer = await Dialer(self.store, self.relay,
                                           advertise_host=adv).start()
                # every container-proxy surface routes through the dialer
                self.endpoints.dialer = self.dialer
            else:
                log.warning(
                    "relay disabled: gateway binds %r and neither "
                    "gateway.advertise_host nor gateway.external_url is set "
                    "— workers could never dial back",
                    self.cfg.gateway.host)
        if self.cfg.monitoring.otlp_endpoint:
            from ..observability.otel import OtlpExporter
            self.otlp = await OtlpExporter(
                self.cfg.monitoring.otlp_endpoint,
                service=f"tpu9-gateway-{self.cfg.cluster_name}",
                interval_s=self.cfg.monitoring.otlp_interval_s).start()
        await self.scheduler.start()
        await self.dispatcher.start()
        await self.functions.start()
        await self.usage.start()
        if self.fleetobs is not None:
            await self.fleetobs.start()
        if self.pool_monitor is not None:
            await self.pool_monitor.start()
        # shutdown grace: long-polls exit instantly via _bounded_longpoll
        # (the _shutting_down event), so this bound only backstops
        # genuinely slow handlers — 15s keeps normal invokes intact while
        # a stop never waits aiohttp's default 60s
        self._runner = web.AppRunner(self.app, shutdown_timeout=15.0)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.cfg.gateway.host, self.port)
        await site.start()
        if self.port == 0:
            self.port = self._runner.addresses[0][1]
        self.runner_env["TPU9_GATEWAY_URL"] = (
            self.cfg.gateway.external_url
            or f"http://{self.cfg.gateway.host}:{self.port}")
        await self._ensure_default_workspace()
        await self._rehydrate_deployments()
        log.info("gateway on %s:%d", self.cfg.gateway.host, self.port)
        return self

    async def _bounded_longpoll(self, coro):
        """Race a long-poll against gateway shutdown: a stop releases every
        waiting pop/result request immediately with its empty answer
        (clients retry after reconnect) instead of holding the HTTP drain
        for the poll's full timeout."""
        wait = asyncio.ensure_future(coro)
        stop = asyncio.ensure_future(self._shutting_down.wait())
        try:
            done, _ = await asyncio.wait({wait, stop},
                                         return_when=asyncio.FIRST_COMPLETED)
            if wait in done:
                return wait.result()
            return None
        finally:
            # runs on BOTH exits AND on handler cancellation (client
            # disconnect): an orphaned pop would otherwise keep running —
            # possibly dequeuing a task whose response nobody receives —
            # and the stray Event waiter would accumulate per request
            for t in (wait, stop):
                if not t.done():
                    t.cancel()
            # gather, not `except BaseException: pass` (ASY003): absorbs
            # the cancelled poll's CancelledError but re-raises if the
            # handler itself is cancelled while draining
            await asyncio.gather(wait, return_exceptions=True)

    async def stop(self) -> None:
        self._shutting_down.set()       # FIRST: releases every long-poll
        if self.pool_monitor is not None:
            await self.pool_monitor.stop()
        if self.fleet_router is not None:
            await self.fleet_router.stop()
        await self.endpoints.shutdown()
        await self.taskqueues.shutdown()
        await self.functions.stop()
        await self.dispatcher.stop()
        await self.scheduler.stop()
        if self.fleetobs is not None:
            await self.fleetobs.stop()
        await self.usage.stop()
        if self.otlp is not None:
            await self.otlp.stop()
        if self._proxy_session is not None and not self._proxy_session.closed:
            await self._proxy_session.close()
        if self.dialer is not None:
            await self.dialer.stop()
        if self.relay is not None:
            await self.relay.stop()
        if self._runner:
            await self._runner.cleanup()
        if self.state_server:
            await self.state_server.stop()
        await self.backend.close()

    async def _ensure_default_workspace(self) -> None:
        """Dev bootstrap: a default workspace + user/worker tokens, printed
        once (the reference seeds via migrations/CLI config flow)."""
        ws = await self.backend.get_workspace_by_name("default")
        if ws is None:
            ws = await self.backend.create_workspace("default")
            tok = await self.backend.create_token(ws.workspace_id)
            self.default_token = tok.key
            log.info("created default workspace; token=%s", tok.key)
        else:
            toks = await self.backend.list_tokens(ws.workspace_id)
            # ACTIVE only: a revoked key must not be resurrected as the
            # printed default (or, worse, handed to every joining machine)
            user = [t for t in toks
                    if t.token_type == "workspace" and t.active]
            self.default_token = user[0].key if user else ""
        worker_toks = [t for t in await self.backend.list_tokens(ws.workspace_id)
                       if t.token_type == "worker" and t.active]
        if worker_toks:
            self.worker_token = worker_toks[0].key
        else:
            wt = await self.backend.create_token(ws.workspace_id,
                                                 token_type="worker")
            self.worker_token = wt.key
        self.default_workspace = ws

    async def _rehydrate_deployments(self) -> None:
        """Re-create autoscaled instances for active deployments after a
        restart (instance.go:444-530)."""
        for dep in await self.backend.list_active_deployments():
            stub = await self.backend.get_stub(dep.stub_id)
            if stub is None:
                continue
            if stub.stub_type in (StubType.ENDPOINT.value,
                                  StubType.ASGI.value,
                                  StubType.REALTIME.value):
                await self.endpoints.get_or_create_instance(stub)
            elif stub.stub_type == StubType.TASK_QUEUE.value:
                await self.taskqueues.get_or_create_instance(stub)

    @web.middleware
    async def _quota_middleware(self, request: web.Request, handler):
        """Concurrency-quota rejections surface as 429 wherever the request
        originated (pod create, task submit, deploy scale-up...)."""
        from ..scheduler.quota import QuotaExceeded
        # the first gateway code that sees a request: the (wall, monotonic)
        # anchor a streamed request's hop intervals are told against
        # (ISSUE 41, `_serve_stub_stream_inner`)
        request["t_gateway"] = (time.time(), time.monotonic())
        try:
            return await handler(request)
        except QuotaExceeded as exc:
            return web.json_response({"error": str(exc)}, status=429)

    # -- auth ----------------------------------------------------------------

    @web.middleware
    async def _auth_middleware(self, request: web.Request, handler):
        if request.path in ("/health",):
            return await handler(request)
        token = ""
        auth = request.headers.get("Authorization", "")
        if auth.startswith("Bearer "):
            token = auth[len("Bearer "):]
        tok = await self.backend.authorize_token(token) if token else None
        if tok is None:
            # explicit allowlist of maybe-public surfaces: named invoke
            # routes and the subdomain catch-all (which 404s unknown hosts);
            # everything else is auth-required by default (fail closed)
            route_handler = getattr(request.match_info, "handler", None)
            # bound-method comparison needs ==, not `is` (fresh object per
            # attribute access)
            if (request.path.startswith("/endpoint/")
                    or request.path == "/api/v1/machine/join"
                    or route_handler == self._subdomain_invoke):
                # machine join authenticates with its one-time join token
                # in the body, not a workspace bearer token
                request["workspace"] = None
                return await handler(request)
            return web.json_response({"error": "unauthorized"}, status=401)
        request["workspace"] = await self.backend.get_workspace(tok.workspace_id)
        # worker tokens may read cross-workspace artifacts (objects, chunks)
        # the way the reference serves repos to workers over gRPC
        request["is_worker"] = tok.token_type == "worker"
        request["token_type"] = tok.token_type
        return await handler(request)

    def _ws(self, request: web.Request) -> Workspace:
        ws = request.get("workspace")
        if ws is None:
            raise web.HTTPUnauthorized(
                text=json.dumps({"error": "unauthorized"}),
                content_type="application/json")
        return ws

    # -- handlers: health/misc ----------------------------------------------

    async def _health(self, request: web.Request) -> web.Response:
        return web.json_response({
            "ok": True,
            "backlog": await self.scheduler.backlog_depth(),
            "workers": len(await self.workers.list()),
        })

    async def _scheduler_stats(self, request: web.Request) -> web.Response:
        self._require_operator(request)   # fleet internals: operator-only
        return web.json_response(self.scheduler.stats)

    async def _usage_report(self, request: web.Request) -> web.Response:
        """Per-workspace metered usage: container-seconds, chip-seconds,
        requests (usage_openmeter.go:18 analogue, hourly buckets)."""
        ws = self._ws(request)
        hours = min(int(self._q_float(request, "hours", 24)), 24 * 31)
        return web.json_response(
            await self.usage.query(ws.workspace_id, hours=hours))

    async def _traces(self, request: web.Request) -> web.Response:
        """Merged fleet traces: this process's span ring + rings workers
        ship on their heartbeat (common/trace.go:12 analogue). Workspace-
        scoped: spans are stamped with the workspace they served, and a
        caller only sees its own."""
        ws = self._ws(request)
        from ..observability import tracer
        trace_id = request.query.get("trace_id", "")
        since = self._q_float(request, "since", 0)
        limit = min(int(self._q_float(request, "limit", 1000)), 5000)

        def visible(sp: dict) -> bool:
            if trace_id and sp.get("traceId") != trace_id:
                return False
            if sp.get("endTimeUnixNano", 0) / 1e9 < since:
                return False
            return (sp.get("attributes", {}).get("workspace_id")
                    == ws.workspace_id)

        seen: set[str] = set()
        spans = []
        for sp in tracer.export(trace_id=trace_id, since=since, limit=limit):
            if visible(sp) and sp.get("spanId") not in seen:
                seen.add(sp.get("spanId", ""))
                spans.append(sp)
        # worker rings (cold-start spans) + runner rings (engine spans
        # shipped on the pressure heartbeat, ISSUE 8) — one merged,
        # workspace-scoped timeline per trace id
        for pattern in ("worker:traces:*", "runner:traces:*"):
            for key in await self.store.keys(pattern):
                raw = await self.store.get(key)
                if not raw:
                    continue
                try:
                    for sp in json.loads(raw):
                        # dedup by spanId: in-process topologies share one
                        # ring, so every worker ships the same spans
                        if visible(sp) and sp.get("spanId") not in seen:
                            seen.add(sp.get("spanId", ""))
                            spans.append(sp)
                except (ValueError, TypeError):
                    continue
        spans.sort(key=lambda s: s.get("startTimeUnixNano", 0))
        return web.json_response({"spans": spans[:limit]})

    async def _decisions(self, request: web.Request) -> web.Response:
        """Merged fleet decision ledger (ISSUE 19): this process's ring
        (admission / placement / failover / autoscaler records) + the
        rings LLM runners ship on the pressure heartbeat (migration
        adopt/drain evidence). Workspace-scoped like /api/v1/traces —
        records are stamped with the workspace they served and a caller
        only sees its own; records with no workspace stamp (autoscaler
        ticks, tree replans) are fleet history, operator-only."""
        ws = self._ws(request)
        operator = self._is_operator(request)
        from ..observability.decisions import ledger as decision_ledger
        request_id = request.query.get("request_id", "")
        plane = request.query.get("plane", "")
        since = self._q_float(request, "since", 0.0)
        limit = min(int(self._q_float(request, "limit", 500)), 5000)

        def visible(rec: dict) -> bool:
            rws = rec.get("workspace_id", "")
            return operator or rws == ws.workspace_id

        records = [r for r in decision_ledger.query(
            request_id=request_id, plane=plane, since=since, limit=limit)
            if visible(r)]
        # dedup by (container_id, seq): each process numbers its own
        # records, and only runner-shipped ones carry a container stamp
        seen = {(r.get("container_id", ""), r.get("seq")) for r in records}
        for key in await self.store.keys("runner:decisions:*"):
            raw = await self.store.get(key)
            if not raw:
                continue
            try:
                ring = json.loads(raw)
            except (ValueError, TypeError):
                continue
            for rec in ring:
                if not isinstance(rec, dict) or not visible(rec):
                    continue
                if request_id and rec.get("request_id") != request_id:
                    continue
                if plane and rec.get("plane") != plane:
                    continue
                if rec.get("ts", 0.0) < since:
                    continue
                k = (rec.get("container_id", ""), rec.get("seq"))
                if k in seen:
                    continue
                seen.add(k)
                records.append(rec)
        records.sort(key=lambda r: (r.get("ts", 0.0), r.get("seq", 0)))
        return web.json_response({"records": records[:limit]})

    async def _coldstart(self, request: web.Request) -> web.Response:
        """Per-replica cold-start decomposition records (ISSUE 13):
        worker-half restore records (coldstart:<container_id> keys shipped
        on the worker heartbeat — plan/fetch/put intervals, bytes by cache
        tier, hedge outcomes) merged with the runner-half coldstart_*
        pressure extras (load/compile_ahead/bind/warmup/ready). Workspace-
        scoped like /api/v1/traces; ?container_id= pins one replica,
        ?stub_id= filters a deployment. This record is the artifact the
        ROADMAP item-3 `--phase scaleout` bench gates on."""
        ws = self._ws(request)
        operator = self._is_operator(request)
        want_cid = request.query.get("container_id", "")
        want_stub = request.query.get("stub_id", "")
        out = await self._coldstart_records(ws, operator, want_cid,
                                            want_stub)
        return web.json_response({"replicas": out})

    async def _coldstart_records(self, ws, operator: bool, want_cid: str,
                                 want_stub: str) -> dict:
        """Workspace-scoped merged coldstart records, shared by
        /api/v1/coldstart and /api/v1/scaleout (ISSUE 17)."""
        from ..observability.coldstart import merge_record
        # both key families are suffixed by container id — a pinned query
        # reads exactly two keys instead of scanning the fleet
        pressure_keys = [f"llm:pressure:{want_cid}"] if want_cid \
            else await self.store.keys("llm:pressure:*")
        coldstart_keys = [f"coldstart:{want_cid}"] if want_cid \
            else await self.store.keys("coldstart:*")
        # runner halves, keyed by container: the same pressure hashes
        # /api/v1/metrics "engines" reads
        runner_halves: dict[str, dict] = {}
        for key in pressure_keys:
            snap = await self.store.hgetall(key)
            if snap:
                runner_halves[key.rsplit(":", 1)[-1]] = snap
        replicas: dict[str, dict] = {}
        for key in coldstart_keys:
            raw = await self.store.get(key)
            if not raw:
                continue
            try:
                rec = json.loads(raw)
            except (ValueError, TypeError):
                continue
            cid = rec.get("container_id", key.rsplit(":", 1)[-1])
            replicas[cid] = rec
        # runner-only replicas (no streamed restore — cold boot or warm
        # pool on a fresh node) still get a record from their heartbeat
        for cid in runner_halves:
            replicas.setdefault(cid, {"container_id": cid})
        out: dict[str, dict] = {}
        for cid, rec in replicas.items():
            if want_cid and cid != want_cid:
                continue
            if not rec.get("workspace_id"):
                # stamp identity from the authoritative container state —
                # never trust (or serve) an unattributed record across
                # tenants (same invariant as _ingest_runner_spans)
                state = await self.containers.get_state(cid)
                if state is not None:
                    rec.setdefault("workspace_id", state.workspace_id)
                    rec.setdefault("stub_id", state.stub_id)
            if want_stub and rec.get("stub_id", "") != want_stub:
                continue
            if not operator and rec.get("workspace_id") != ws.workspace_id:
                continue
            out[cid] = merge_record(rec, runner_halves.get(cid))
        return out

    async def _scaleout(self, request: web.Request) -> web.Response:
        """Scale-out plane report (ISSUE 17): per replica — multicast
        tree position (primary parent per group + children it re-serves),
        groups held/ready, execute-while-scaling readiness fraction, and
        bytes by tree edge from the coldstart record's per-peer split —
        joined from the coordinator's group ledger and the same merged
        coldstart records /api/v1/coldstart serves. Workspace-scoped the
        same way; ?container_id= / ?stub_id= filter identically."""
        if self.scaleout is None:
            return web.json_response(
                {"enabled": False, "replicas": [], "tree": {}})
        ws = self._ws(request)
        operator = self._is_operator(request)
        want_cid = request.query.get("container_id", "")
        want_stub = request.query.get("stub_id", "")
        records = await self._coldstart_records(ws, operator, want_cid,
                                                want_stub)
        from ..scaleout.coordinator import build_report
        snap = self.scaleout.ledger.snapshot()
        if not operator:
            # ledger rows carry no workspace; visibility comes from the
            # workspace-filtered record join (worker-id rows are
            # operator-only — they aggregate across tenants)
            snap = {k: v for k, v in snap.items() if k in records}
        if want_cid:
            snap = {k: v for k, v in snap.items() if k == want_cid}
        report = build_report(snap, self.scaleout.plan, records=records)
        report["enabled"] = True
        report["coordinator"] = self.scaleout.stats()
        return web.json_response(report)

    async def _postmortem(self, request: web.Request) -> web.Response:
        """Replica black-box records (ISSUE 14): the bounded forensic
        dumps engines leave behind on crash/OOM/watchdog-trip (last-K
        flight windows, recent spans, KV-pool + scheduler state, HBM
        breakdown, exception), shipped by the runner over
        ``/rpc/llm/postmortem`` and stored per container. Workspace-
        scoped like /api/v1/traces; ?container_id= pins one replica,
        ?stub_id= filters a deployment. The evidence survives the
        process it describes — the whole point of a black box."""
        ws = self._ws(request)
        operator = self._is_operator(request)
        want_cid = request.query.get("container_id", "")
        want_stub = request.query.get("stub_id", "")
        from ..observability.health import load_postmortems
        keys = [f"postmortem:{want_cid}"] if want_cid \
            else await self.store.keys("postmortem:*")
        out: dict[str, list] = {}
        for key in keys:
            records = await load_postmortems(self.store, key)
            if not records:
                continue
            cid = key.split(":", 1)[-1]
            # identity was stamped at ingest from the authenticated
            # container state; filter on it, never trust the payload
            visible = [r for r in records if isinstance(r, dict)
                       and (operator
                            or r.get("workspace_id") == ws.workspace_id)
                       and (not want_stub
                            or r.get("stub_id") == want_stub)]
            if visible:
                out[cid] = visible
        return web.json_response({"replicas": out})

    async def _flight(self, request: web.Request) -> web.Response:
        """Engine flight-recorder tail for one LLM deployment (ISSUE 8):
        proxies the runner's /flight RPC through the request buffer
        (?stub_id= required; ?container_id= pins a replica, ?limit= /
        ?since_seq= page the ring). Workspace-scoped via stub ownership.
        Routes like any invoke, so a scaled-to-zero deployment cold-starts
        a replica rather than answering from nothing."""
        stub = await self._stub_for(request, request.query.get("stub_id", ""))
        limit = int(self._q_float(request, "limit", 256))
        since_seq = int(self._q_float(request, "since_seq", 0))
        cid = request.query.get("container_id", "")
        result = await self.endpoints.forward(
            stub, "GET", f"/flight?limit={limit}&since_seq={since_seq}",
            [], b"", prefer=[cid] if cid else [],
            timeout_s=self.cfg.router.rpc_timeout_s)
        return web.Response(status=result.status, body=result.body,
                            content_type="application/json")

    async def _profile(self, request: web.Request) -> web.Response:
        """Trace a live replica with jax.profiler for the next N seconds
        (ISSUE 24): body {stub_id, seconds, container_id?}; returns the
        runner-side dump path immediately. The dump lands on the replica's
        filesystem — fetch it with `tpu9 shell`/volume tooling."""
        data = await request.json()
        stub = await self._stub_for(request, data.get("stub_id", ""))
        seconds = float(data.get("seconds", 4.0))
        cid = data.get("container_id", "")
        result = await self.endpoints.forward(
            stub, "POST", "/profile",
            [("Content-Type", "application/json")],
            json.dumps({"seconds": seconds,
                        "out_dir": data.get("out_dir", "")}).encode(),
            prefer=[cid] if cid else [],
            timeout_s=self.cfg.router.rpc_timeout_s)
        return web.Response(status=result.status, body=result.body,
                            content_type="application/json")

    async def _metrics(self, request: web.Request) -> web.Response:
        # fleet-wide registries (every worker's shipped counters) are
        # infrastructure state, not tenant data — operator-only, like
        # _traces' workspace scoping but for the whole surface
        self._require_operator(request)
        if request.query.get("format") == "prometheus":
            return web.Response(text=metrics.prometheus_text(),
                                content_type="text/plain")
        out = metrics.to_dict()
        # merge worker-shipped registries (fleet view)
        out["workers"] = {}
        for key in await self.store.keys("worker:metrics:*"):
            raw = await self.store.get(key)
            if raw:
                out["workers"][key.rsplit(":", 1)[-1]] = json.loads(raw)
        # cache-plane snapshots (ISSUE 13): per-worker tier/hedge/per-peer
        # evidence + warm weights pool occupancy, heartbeated by workers —
        # the restore/weight-distribution side of the fleet view
        out["cache"] = {}
        for key in await self.store.keys("worker:cache:*"):
            raw = await self.store.get(key)
            if raw:
                try:
                    out["cache"][key.rsplit(":", 1)[-1]] = json.loads(raw)
                except (ValueError, TypeError):
                    continue
        # per-engine serving stats (ISSUE 2 satellite): queue depth, active
        # streams, KV headroom, prefix hit rate — heartbeated by runners
        # into the pressure table, readable here without SSHing a node
        out["engines"] = {}
        for key in await self.store.keys("llm:pressure:*"):
            snap = await self.store.hgetall(key)
            if snap:
                out["engines"][key.rsplit(":", 1)[-1]] = snap
        if self.fleetobs is not None:
            # stale-replica aging (ISSUE 12): stamp last_seen/age_s from
            # the heartbeat; replicas silent > N beats are dropped rather
            # than served as live stats until the store TTL
            out["engines"] = self.fleetobs.filter_engines(out["engines"])
            # per-tenant / per-stub goodput decomposition joined against
            # usage.py chip-second buckets
            out["goodput"] = await self.fleetobs.metrics_section()
        if self.fleet_router is not None:
            out["router"] = self.fleet_router.snapshot_all()
        return web.json_response(out)

    async def _timeline(self, request: web.Request) -> web.Response:
        """Bounded in-gateway time-series rings (ISSUE 12): fleet history
        for the snapshot /api/v1/metrics can't answer. ?series=a,b,c
        (trailing * prefix-matches), ?since= (wall anchor), ?limit= newest
        N per series; no ?series= lists the available names."""
        self._require_operator(request)
        if self.fleetobs is None:
            return web.json_response({"error": "slo layer disabled"},
                                     status=404)
        limit = int(self._q_float(request, "limit", 0)) or None
        return web.json_response(self.fleetobs.timeline_payload(
            request.query.get("series", ""),
            self._q_float(request, "since", 0.0), limit))

    async def _slo(self, request: web.Request) -> web.Response:
        """Declared objectives + per-stub multi-window burn rates, with
        the pressure fold the autoscaler sees (ISSUE 12)."""
        self._require_operator(request)
        if self.fleetobs is None:
            return web.json_response({"error": "slo layer disabled"},
                                     status=404)
        return web.json_response(self.fleetobs.slo_payload())

    async def _events(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        rows = await self.events.query(
            kind_prefix=request.query.get("kind", ""),
            since=self._q_float(request, "since", 0.0),
            limit=int(self._q_float(request, "limit", 500)))
        # workspace scoping (same invariant _traces enforces): only the
        # operator sees the cluster-wide stream — container/task/deploy
        # events carry other tenants' ids and payloads
        if not self._is_operator(request):
            rows = [r for r in rows
                    if r.get("workspace_id") in ("", ws.workspace_id)]
        return web.json_response(rows)

    def _is_operator(self, request: web.Request) -> bool:
        try:
            self._require_operator(request)
            return True
        except web.HTTPForbidden:
            return False

    @staticmethod
    def _q_float(request: web.Request, name: str, default: float) -> float:
        """Query-param float with a 400 (not a 500) on garbage input."""
        raw = request.query.get(name)
        if raw is None:
            return default
        try:
            return float(raw)
        except ValueError:
            raise web.HTTPBadRequest(
                text=json.dumps({"error": f"{name} must be a number"}),
                content_type="application/json")

    async def _pools(self, request: web.Request) -> web.Response:
        self._ws(request)
        if self.pool_monitor is None:
            return web.json_response({})
        return web.json_response({
            name: vars(st) for name, st in self.pool_monitor.status.items()})

    # -- handlers: SDK RPC ----------------------------------------------------

    async def _rpc_auth_check(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        return web.json_response({"workspace_id": ws.workspace_id,
                                  "workspace_name": ws.name})

    async def _rpc_get_or_create_stub(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        data = await request.json()
        try:
            StubType(data.get("stub_type", ""))
        except ValueError:
            # fail loudly: an unknown type would silently boot the default
            # runner and e.g. never poll a task queue
            return web.json_response(
                {"error": f"unknown stub_type {data.get('stub_type')!r} "
                          f"(valid: {[t.value for t in StubType]})"},
                status=400)
        config = StubConfig.from_dict(data.get("config", {}))
        if (config.pricing is not None and config.pricing.enabled
                and not config.authorized):
            # pricing only bills authenticated external callers; on a
            # public endpoint every caller could go anonymous and free —
            # reject the combination instead of silently giving away
            # paid compute
            return web.json_response(
                {"error": "pricing requires authorized=True (a public "
                          "endpoint cannot be billed)"}, status=400)
        # HBM feasibility gate for declarative LLM deployments (VERDICT
        # r03 #8): weights + KV + scratch must fit the slice's HBM, proven
        # arithmetically HERE — not discovered as an OOM on real chips.
        # Applies when the stub declares its model (extra.model); app-code
        # engines (load() in user code) can't be checked statically.
        if (config.extra.get("runner") == "llm"
                and config.extra.get("model") and config.runtime.tpu):
            from ..serving.feasibility import (InfeasibleDeployment,
                                               validate_llm_deployment)
            try:
                budget = validate_llm_deployment(
                    config.extra["model"], config.runtime.tpu,
                    max_batch=int(config.extra.get("max_batch", 8)),
                    max_seq_len=int(config.extra.get("max_seq_len", 2048)),
                    tp=int(config.extra.get("tp", 0)),
                    # a pinned paged pool is priced as pinned (a model whose
                    # KV state is many planes deep deploys with one)
                    kv_pool_blocks=int(
                        config.extra.get("kv_pool_blocks", 0)),
                    kv_block_size=int(config.extra.get("kv_block_size", 0)))
            except InfeasibleDeployment as exc:
                return web.json_response({"error": str(exc)}, status=400)
            except (KeyError, ValueError) as exc:
                return web.json_response(
                    {"error": f"llm config invalid: {exc}"}, status=400)
            config.extra["hbm_budget"] = budget.as_dict()
        stub = await self.backend.get_or_create_stub(
            workspace_id=ws.workspace_id,
            name=data["name"],
            stub_type=data["stub_type"],
            config=config,
            object_id=data.get("object_id", ""),
            app_name=data.get("app_name", ""),
            force_create=data.get("force_create", False))
        return web.json_response({"stub_id": stub.stub_id})

    async def _rpc_put_object(self, request: web.Request) -> web.Response:
        """Workspace code upload (reference PutObjectStream, gateway.proto:36).
        Body: raw zip bytes; dedupe by hash."""
        ws = self._ws(request)
        body = await request.read()
        obj_hash = hashlib.sha256(body).hexdigest()
        existing = await self.backend.find_object_by_hash(ws.workspace_id,
                                                          obj_hash)
        if existing:
            return web.json_response({"object_id": existing["object_id"],
                                      "deduped": True})
        objects_dir = os.path.join(self.cfg.storage.local_root,
                                   ws.workspace_id, "objects")
        os.makedirs(objects_dir, exist_ok=True)
        path = os.path.join(objects_dir, f"{obj_hash}.zip")
        # off-loop tmp+rename (ASY004): zips are MBs, and concurrent
        # same-hash uploads racing a _rpc_get_object reader must never
        # see a half-written or re-truncated file
        await atomic_write_bytes(path, body)
        object_id = await self.backend.create_object(ws.workspace_id, obj_hash,
                                                     len(body), path)
        return web.json_response({"object_id": object_id, "deduped": False})

    async def _rpc_get_object(self, request: web.Request) -> web.Response:
        """Workers (cross-workspace, worker token) and owners download synced
        code archives here (reference: repo-over-gRPC object access)."""
        ws = self._ws(request)
        obj = await self.backend.get_object(request.match_info["object_id"])
        if obj is None or (not request.get("is_worker")
                           and obj["workspace_id"] != ws.workspace_id):
            return web.json_response({"error": "object not found"},
                                     status=404)
        return web.FileResponse(obj["path"])

    async def _rpc_deploy(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        data = await request.json()
        stub = await self.backend.get_stub(data["stub_id"])
        if stub is None or stub.workspace_id != ws.workspace_id:
            return web.json_response({"error": "stub not found"}, status=404)
        dep = await self.backend.create_deployment(
            ws.workspace_id, data["name"], stub.stub_id, app_id=stub.app_id)
        # warm the instance immediately (InstanceController warmup)
        if stub.stub_type in (StubType.ENDPOINT.value, StubType.ASGI.value,
                              StubType.REALTIME.value):
            await self.endpoints.get_or_create_instance(stub)
        elif stub.stub_type == StubType.TASK_QUEUE.value:
            await self.taskqueues.get_or_create_instance(stub)
        invoke_url = (f"http://{self.cfg.gateway.host}:{self.port}"
                      f"/endpoint/{dep.name}")
        return web.json_response({"deployment_id": dep.deployment_id,
                                  "version": dep.version,
                                  "subdomain": dep.subdomain,
                                  "invoke_url": invoke_url})

    # -- handlers: tasks / queues / functions ---------------------------------

    async def _stub_for(self, request: web.Request, stub_id: str) -> Stub:
        ws = self._ws(request)
        stub = await self.backend.get_stub(stub_id)
        if stub is None or stub.workspace_id != ws.workspace_id:
            raise web.HTTPNotFound(
                text=json.dumps({"error": "stub not found"}),
                content_type="application/json")
        return stub

    async def _rpc_tq_put(self, request: web.Request) -> web.Response:
        data = await request.json()
        stub = await self._stub_for(request, data["stub_id"])
        msg = await self.taskqueues.put(stub, data.get("args", []),
                                        data.get("kwargs", {}))
        return web.json_response({"task_id": msg.task_id})

    async def _rpc_tq_pop(self, request: web.Request) -> web.Response:
        data = await request.json()
        stub = await self._stub_for(request, data["stub_id"])
        msg = await self._bounded_longpoll(self.taskqueues.pop(
            stub.workspace_id, stub.stub_id, data.get("container_id", ""),
            timeout=min(float(data.get("timeout", 25.0)), 30.0)))
        if msg is None:
            return web.json_response({"task": None})
        return web.json_response({"task": {
            "task_id": msg.task_id, "args": msg.handler_args,
            "kwargs": msg.handler_kwargs, "retry_count": msg.retry_count}})

    async def _rpc_tq_status(self, request: web.Request) -> web.Response:
        stub = await self._stub_for(request, request.match_info["stub_id"])
        return web.json_response(await self.taskqueues.queue_status(stub))

    async def _rpc_fn_invoke(self, request: web.Request) -> web.Response:
        data = await request.json()
        stub = await self._stub_for(request, data["stub_id"])
        policy = None
        if "policy" in data:
            policy = TaskPolicy.from_dict(data["policy"])
        msg = await self.functions.invoke(stub, data.get("args", []),
                                          data.get("kwargs", {}), policy)
        if not data.get("wait", True):
            return web.json_response({"task_id": msg.task_id})
        # cap the blocking wait under client/proxy timeouts; callers poll the
        # result route with the task_id after a 504
        wait_s = float(data.get("timeout") or stub.config.timeout_s or 60.0)
        result = await self.dispatcher.retrieve(msg.task_id,
                                                timeout=min(max(wait_s, 1.0),
                                                            110.0))
        if result is None:
            return web.json_response({"task_id": msg.task_id,
                                      "error": "timeout waiting for result"},
                                     status=504)
        return web.json_response({"task_id": msg.task_id, **result})

    async def _rpc_schedule_register(self, request: web.Request) -> web.Response:
        data = await request.json()
        stub = await self._stub_for(request, data["stub_id"])
        if stub.stub_type not in (StubType.SCHEDULE.value,
                                  StubType.FUNCTION.value):
            return web.json_response(
                {"error": f"schedules require a function/schedule stub, "
                          f"got {stub.stub_type}"}, status=400)
        try:
            schedule_id = await self.functions.register_schedule(
                stub, data["cron"])
        except ValueError as exc:
            return web.json_response({"error": str(exc)}, status=400)
        return web.json_response({"schedule_id": schedule_id})

    async def _task_for(self, request: web.Request):
        """Workspace-scoped task lookup (404 on missing or foreign tasks)."""
        ws = self._ws(request)
        task_id = request.match_info["task_id"]
        msg = await self.dispatcher.tasks.get_message(task_id)
        if msg is None or msg.workspace_id != ws.workspace_id:
            raise web.HTTPNotFound(
                text=json.dumps({"error": "task not found"}),
                content_type="application/json")
        return msg

    async def _rpc_task_get(self, request: web.Request) -> web.Response:
        msg = await self._task_for(request)
        return web.json_response({"task_id": msg.task_id, "status": msg.status,
                                  "args": msg.handler_args,
                                  "kwargs": msg.handler_kwargs,
                                  "container_id": msg.container_id})

    async def _rpc_task_result(self, request: web.Request) -> web.Response:
        msg = await self._task_for(request)
        timeout = min(self._q_float(request, "timeout", 0.0), 110.0)
        result = await self._bounded_longpoll(
            self.dispatcher.retrieve(msg.task_id, timeout=timeout))
        if result is None:
            return web.json_response({"pending": True}, status=202)
        return web.json_response(result)

    async def _rpc_task_claim(self, request: web.Request) -> web.Response:
        msg = await self._task_for(request)
        data = await request.json()
        claimed = await self.dispatcher.claim(msg.task_id,
                                              data.get("container_id", ""))
        return web.json_response({"ok": claimed is not None})

    async def _rpc_task_complete(self, request: web.Request) -> web.Response:
        msg = await self._task_for(request)
        data = await request.json()
        ok = await self.dispatcher.complete(
            msg.task_id, result=data.get("result"),
            error=data.get("error"),
            container_id=data.get("container_id", "")) is not None
        return web.json_response({"ok": ok})

    async def _rpc_task_cancel(self, request: web.Request) -> web.Response:
        msg = await self._task_for(request)
        return web.json_response({"ok": await self.dispatcher.cancel(msg.task_id)})

    async def _rpc_llm_pressure(self, request: web.Request) -> web.Response:
        """Engine pressure heartbeat from LLM runners (pod/llm.go:460
        equivalent). Workspace-scoped: a tenant can only report pressure for
        its own containers."""
        ws = self._ws(request)
        d = await request.json()
        state = await self.containers.get_state(d.get("container_id", ""))
        if state is None or state.workspace_id != ws.workspace_id:
            return web.json_response({"error": "container not found"},
                                     status=404)
        from ..abstractions.llm import LlmRouter
        router = LlmRouter(self.store)
        await router.record_pressure(
            state.container_id, float(d.get("token_pressure", 0.0)),
            int(d.get("active_streams", 0)), extra=d.get("extra"))
        if self.fleetobs is not None:
            # timeline + goodput sampling rides the heartbeat cadence
            # (ISSUE 12) — same accepted-beat channel the spans use
            self.fleetobs.ingest_heartbeat(
                state.container_id, state.workspace_id, state.stub_id,
                float(d.get("token_pressure", 0.0)),
                int(d.get("active_streams", 0)),
                extra=d.get("extra") if isinstance(d.get("extra"), dict)
                else None)
        spans = d.get("spans")
        if isinstance(spans, list) and spans:
            await self._ingest_runner_spans(state, spans)
        decisions = d.get("decisions")
        if isinstance(decisions, list) and decisions:
            await self._ingest_runner_decisions(state, decisions)
        return web.json_response({"ok": True})

    async def _rpc_llm_postmortem(self, request: web.Request) -> web.Response:
        """Black-box ingest (ISSUE 14): a dying/wedged engine's forensic
        record, shipped by the runner. Workspace-scoped like the pressure
        heartbeat; identity is stamped HERE from the authenticated
        container state (a tenant must not plant records into another
        workspace's /api/v1/postmortem view), the record re-clamped to
        the size bound server-side (the runner's clamp is not trusted),
        and the per-replica list kept at the last N records."""
        ws = self._ws(request)
        d = await request.json()
        state = await self.containers.get_state(d.get("container_id", ""))
        if state is None or state.workspace_id != ws.workspace_id:
            return web.json_response({"error": "container not found"},
                                     status=404)
        rec = d.get("record")
        if not isinstance(rec, dict):
            return web.json_response({"error": "record must be a dict"},
                                     status=400)
        from ..observability.health import (clamp_postmortem,
                                            store_postmortem)
        rec = clamp_postmortem(rec)
        rec["workspace_id"] = state.workspace_id
        rec["stub_id"] = state.stub_id
        rec["container_id"] = state.container_id
        # atomic list append: the worker's exit record for the same
        # container may land concurrently from another process
        await store_postmortem(self.store, state.container_id, rec)
        log.warning("post-mortem stored for %s (%s)",
                    state.container_id, rec.get("reason", ""))
        return web.json_response({"ok": True})

    async def _ingest_runner_spans(self, state, spans: list) -> None:
        """Engine/runner spans riding the pressure heartbeat (ISSUE 8 —
        the same channel worker rings use). The workspace stamp is applied
        HERE from the authenticated container state, never trusted from
        the runner payload: a tenant container must not be able to plant
        spans into another workspace's /api/v1/traces view."""
        cleaned = []
        for sp in spans[:2048]:         # bound one beat's ingest
            if not isinstance(sp, dict) or not sp.get("traceId"):
                continue
            attrs = sp.get("attributes")
            if not isinstance(attrs, dict):
                attrs = {}
            attrs["workspace_id"] = state.workspace_id
            attrs["container_id"] = state.container_id
            sp["attributes"] = attrs
            cleaned.append(sp)
        if not cleaned:
            return
        key = f"runner:traces:{state.container_id}"
        existing = await self.store.get(key)
        try:
            merged = (json.loads(existing) if existing else [])[-1500:]
        except (ValueError, TypeError):
            merged = []
        merged.extend(cleaned)
        await self.store.set(key, json.dumps(merged), ttl=3600.0)

    async def _ingest_runner_decisions(self, state, decisions: list) -> None:
        """Runner decision records riding the pressure heartbeat (ISSUE
        19 — the same accepted-beat channel the engine spans use, so the
        runner's seq watermark only advances on a 2xx). Identity is
        stamped HERE from the authenticated container state, never
        trusted from the payload: a tenant container must not plant
        decision evidence into another workspace's /api/v1/decisions."""
        cleaned = []
        for rec in decisions[:1024]:    # bound one beat's ingest
            if not isinstance(rec, dict) or not rec.get("plane"):
                continue
            rec["workspace_id"] = state.workspace_id
            rec["container_id"] = state.container_id
            cleaned.append(rec)
        if not cleaned:
            return
        key = f"runner:decisions:{state.container_id}"
        existing = await self.store.get(key)
        try:
            merged = (json.loads(existing) if existing else [])[-1000:]
        except (ValueError, TypeError):
            merged = []
        merged.extend(cleaned)
        await self.store.set(key, json.dumps(merged), ttl=3600.0)

    # -- handlers: pods ---------------------------------------------------------

    async def _pod_container_for(self, request: web.Request):
        return await self._container_for(request, key="container_id",
                                         allow_worker=False)

    # -- bot (petri-net orchestration; pkg/abstractions/experimental/bot) ----

    async def _rpc_bot_session_create(self, request: web.Request) -> web.Response:
        from ..abstractions.bot import BotError
        data = await request.json()
        stub = await self._stub_for(request, data["stub_id"])
        try:
            return web.json_response(await self.bots.create_session(stub))
        except BotError as e:
            raise web.HTTPBadRequest(text=json.dumps({"error": str(e)}),
                                     content_type="application/json")

    async def _rpc_bot_sessions(self, request: web.Request) -> web.Response:
        stub = await self._stub_for(request, request.match_info["stub_id"])
        return web.json_response(await self.bots.list_sessions(stub))

    async def _rpc_bot_session_delete(self, request: web.Request) -> web.Response:
        from ..abstractions.bot import BotError
        stub = await self._stub_for(request, request.match_info["stub_id"])
        try:
            ok = await self.bots.delete_session(
                stub, request.match_info["session_id"])
        except BotError as e:
            raise web.HTTPBadRequest(text=json.dumps({"error": str(e)}),
                                     content_type="application/json")
        return web.json_response({"ok": ok})

    async def _rpc_bot_push(self, request: web.Request) -> web.Response:
        from ..abstractions.bot import BotError
        from ..schema import ValidationError
        stub = await self._stub_for(request, request.match_info["stub_id"])
        data = await request.json()
        try:
            out = await self.bots.push_marker(
                stub, request.match_info["session_id"],
                data["location"], data.get("marker", {}))
        except (BotError, ValidationError) as e:
            raise web.HTTPBadRequest(text=json.dumps({"error": str(e)}),
                                     content_type="application/json")
        return web.json_response(out)

    async def _rpc_bot_pop(self, request: web.Request) -> web.Response:
        from ..abstractions.bot import BotError
        stub = await self._stub_for(request, request.match_info["stub_id"])
        data = await request.json()
        try:
            marker = await self.bots.pop_marker(
                stub, request.match_info["session_id"], data["location"])
        except BotError as e:
            raise web.HTTPBadRequest(text=json.dumps({"error": str(e)}),
                                     content_type="application/json")
        return web.json_response({"marker": marker})

    async def _rpc_bot_state(self, request: web.Request) -> web.Response:
        from ..abstractions.bot import BotError
        stub = await self._stub_for(request, request.match_info["stub_id"])
        try:
            return web.json_response(await self.bots.session_state(
                stub, request.match_info["session_id"]))
        except BotError as e:
            raise web.HTTPBadRequest(text=json.dumps({"error": str(e)}),
                                     content_type="application/json")

    async def _rpc_bot_events(self, request: web.Request) -> web.Response:
        stub = await self._stub_for(request, request.match_info["stub_id"])
        # ownership: events are keyed by session, session list is per stub
        session_id = request.match_info["session_id"]
        if await self.bots.get_session(stub, session_id) is None:
            raise web.HTTPNotFound(
                text=json.dumps({"error": "session not found"}),
                content_type="application/json")
        entries = await self.bots.events(
            session_id, last_id=request.query.get("since", "0"))
        return web.json_response([{"id": eid, **e} for eid, e in entries])

    async def _rpc_pod_create(self, request: web.Request) -> web.Response:
        data = await request.json()
        stub = await self._stub_for(request, data["stub_id"])
        from_snapshot = data.get("from_snapshot", "")
        from_criu = data.get("from_criu_snapshot", "")
        for snap_id, want_kind in ((from_snapshot, "workdir"),
                                   (from_criu, "criu")):
            if snap_id:
                # snapshots are workspace-scoped (foreign ids 404) AND
                # kind-checked: feeding a workdir snapshot to criu restore
                # (or CRIU images to a working tree) must fail loudly here
                snap = await self.backend.get_sandbox_snapshot(snap_id)
                if snap is None or snap["workspace_id"] != stub.workspace_id:
                    return web.json_response({"error": "snapshot not found"},
                                             status=404)
                if snap.get("kind", "workdir") != want_kind:
                    return web.json_response(
                        {"error": f"snapshot {snap_id} is "
                                  f"{snap.get('kind')!r}, not {want_kind!r}"},
                        status=400)
        out = await self.pods.create(stub, name=data.get("name", ""),
                                     from_snapshot=from_snapshot,
                                     from_criu_snapshot=from_criu)
        if data.get("wait", True):
            address = await self.pods.wait_running(
                out["container_id"],
                timeout=min(float(data.get("timeout", 60.0)), 110.0))
            out["address"] = address
            out["running"] = address is not None
        return web.json_response(out)

    async def _rpc_pod_status(self, request: web.Request) -> web.Response:
        state = await self._pod_container_for(request)
        return web.json_response(state.to_dict())

    async def _rpc_pod_exec(self, request: web.Request) -> web.Response:
        state = await self._pod_container_for(request)
        data = await request.json()
        out = await self.pods.exec(state.container_id,
                                   list(data.get("cmd", [])),
                                   timeout=min(float(data.get("timeout", 60)),
                                               110.0))
        return web.json_response(out)

    # -- handlers: sandbox depth (process mgr / fs / snapshots) --------------

    async def _rpc_sbx_spawn(self, request: web.Request) -> web.Response:
        state = await self._pod_container_for(request)
        data = await request.json()
        out = await self.pods.sbx(state.container_id, {
            "op": "spawn", "cmd": list(data.get("cmd", []))})
        return web.json_response(out)

    async def _rpc_sbx_ps(self, request: web.Request) -> web.Response:
        state = await self._pod_container_for(request)
        return web.json_response(
            await self.pods.sbx(state.container_id, {"op": "ps"}))

    async def _rpc_sbx_status(self, request: web.Request) -> web.Response:
        state = await self._pod_container_for(request)
        return web.json_response(await self.pods.sbx(
            state.container_id,
            {"op": "status", "proc_id": request.match_info["proc_id"]}))

    async def _rpc_sbx_stdin(self, request: web.Request) -> web.Response:
        state = await self._pod_container_for(request)
        data = await request.json()
        return web.json_response(await self.pods.sbx(
            state.container_id,
            {"op": "stdin", "proc_id": request.match_info["proc_id"],
             "data": data.get("data", "")}))

    async def _rpc_sbx_kill(self, request: web.Request) -> web.Response:
        state = await self._pod_container_for(request)
        return web.json_response(await self.pods.sbx(
            state.container_id,
            {"op": "kill", "proc_id": request.match_info["proc_id"]}))

    async def _rpc_sbx_out(self, request: web.Request) -> web.Response:
        # tenancy: the container lookup gates access, and the proc must
        # belong to that container. Pairing is verified against the worker
        # ONCE and cached — subsequent output polls read straight off the
        # state bus with no worker round-trip (wait() polls at ~5 Hz).
        state = await self._pod_container_for(request)
        proc_id = request.match_info["proc_id"]
        if self._sbx_proc_owner.get(proc_id) != state.container_id:
            check = await self.pods.sbx(
                state.container_id, {"op": "status", "proc_id": proc_id})
            if check.get("error"):
                return web.json_response(check, status=404)
            if len(self._sbx_proc_owner) > 10000:
                self._sbx_proc_owner.clear()
            self._sbx_proc_owner[proc_id] = state.container_id
        out = await self.pods.proc_output(
            proc_id,
            last_id=request.query.get("last_id", "0"),
            timeout=min(self._q_float(request, "timeout", 0.0), 30.0))
        return web.json_response(out)

    async def _rpc_sbx_fs(self, request: web.Request) -> web.Response:
        state = await self._pod_container_for(request)
        data = await request.json()
        out = await self.pods.sbx(state.container_id, {
            "op": "fs", "fs_op": data.get("op", ""),
            "path": data.get("path", ""), "data": data.get("data", "")})
        return web.json_response(out)

    async def _rpc_sbx_snapshot(self, request: web.Request) -> web.Response:
        state = await self._pod_container_for(request)
        out = await self.pods.sbx(state.container_id, {
            "op": "snapshot", "workspace_id": state.workspace_id},
            timeout=120.0)
        return web.json_response(out)

    async def _rpc_criu_checkpoint(self, request: web.Request) -> web.Response:
        """CPU process-tree checkpoint (criu.go:668 analogue); restore by
        creating a pod with from_criu_snapshot."""
        state = await self._pod_container_for(request)
        out = await self.pods.sbx(state.container_id, {
            "op": "criu_checkpoint", "workspace_id": state.workspace_id},
            timeout=300.0)
        return web.json_response(out)

    async def _rpc_sbx_snapshots(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        return web.json_response(
            await self.backend.list_sandbox_snapshots(ws.workspace_id))

    async def _pod_proxy(self, request: web.Request) -> web.Response:
        state = await self._pod_container_for(request)
        if not state.address:
            return web.json_response({"error": "pod not running"}, status=503)
        import aiohttp as _aiohttp
        tail = request.match_info.get("tail", "")
        address = state.address
        if self.dialer is not None:
            address = await self.dialer.ensure_route(address, state.worker_id)
        url = f"http://{address}/{tail}"
        if request.query_string:
            url += f"?{request.query_string}"
        # forward end-to-end headers, not hop-by-hop/host ones
        fwd_headers = {k: v for k, v in request.headers.items()
                       if k.lower() not in ("host", "connection",
                                            "transfer-encoding",
                                            "content-length",
                                            "authorization")}
        body = await request.read()
        if self._proxy_session is None or self._proxy_session.closed:
            self._proxy_session = _aiohttp.ClientSession()
        try:
            async with self._proxy_session.request(
                    request.method, url, data=body or None,
                    headers=fwd_headers,
                    timeout=_aiohttp.ClientTimeout(total=110)) as resp:
                out = await resp.read()
                proxied = web.Response(status=resp.status, body=out)
                proxied.headers["Content-Type"] = resp.headers.get(
                    "Content-Type", "application/octet-stream")
                return proxied
        except (_aiohttp.ClientError, asyncio.TimeoutError) as exc:
            return web.json_response({"error": type(exc).__name__},
                                     status=502)

    # -- handlers: primitives ---------------------------------------------------

    async def _rpc_map(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        name = request.match_info["name"]
        d = await request.json()
        op = d.get("op")
        try:
            if op == "set":
                await self.maps.set(ws.workspace_id, name, d["field"],
                                    d.get("value"))
                return web.json_response({"ok": True})
            if op == "get":
                return web.json_response({"value": await self.maps.get(
                    ws.workspace_id, name, d["field"])})
            if op == "delete":
                return web.json_response({"ok": await self.maps.delete(
                    ws.workspace_id, name, d["field"])})
            if op == "keys":
                return web.json_response({"keys": await self.maps.keys(
                    ws.workspace_id, name)})
            if op == "items":
                return web.json_response({"items": await self.maps.items(
                    ws.workspace_id, name)})
        except PrimitiveError as exc:
            return web.json_response({"error": str(exc)}, status=400)
        return web.json_response({"error": f"bad op {op!r}"}, status=400)

    async def _rpc_queue(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        name = request.match_info["name"]
        d = await request.json()
        op = d.get("op")
        try:
            if op == "push":
                depth = await self.queues.push(ws.workspace_id, name,
                                               d.get("value"))
                return web.json_response({"depth": depth})
            if op == "pop":
                value = await self.queues.pop(
                    ws.workspace_id, name,
                    timeout=min(float(d.get("timeout", 0)), 30.0))
                return web.json_response({"value": value})
            if op == "depth":
                return web.json_response({"depth": await self.queues.depth(
                    ws.workspace_id, name)})
        except PrimitiveError as exc:
            return web.json_response({"error": str(exc)}, status=400)
        return web.json_response({"error": f"bad op {op!r}"}, status=400)

    async def _rpc_signal(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        name = request.match_info["name"]
        d = await request.json()
        op = d.get("op")
        if op == "set":
            await self.signals.set(ws.workspace_id, name, ttl=d.get("ttl"))
            return web.json_response({"ok": True})
        if op == "clear":
            await self.signals.clear(ws.workspace_id, name)
            return web.json_response({"ok": True})
        if op == "is_set":
            return web.json_response({"set": await self.signals.is_set(
                ws.workspace_id, name)})
        if op == "wait":
            fired = await self.signals.wait(
                ws.workspace_id, name,
                timeout=min(float(d.get("timeout", 30.0)), 60.0))
            return web.json_response({"set": fired})
        return web.json_response({"error": f"bad op {op!r}"}, status=400)

    async def _rpc_output_save(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        filename = request.query.get("filename", "output.bin")
        data = await request.read()
        try:
            output_id = await self.outputs.save(ws.workspace_id, filename,
                                                data)
        except PrimitiveError as exc:
            return web.json_response({"error": str(exc)}, status=400)
        return web.json_response({
            "output_id": output_id,
            "url": f"/rpc/output/{output_id}"})

    async def _rpc_output_get(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        path = await self.outputs.path(ws.workspace_id,
                                       request.match_info["output_id"])
        if path is None:
            return web.json_response({"error": "output not found"},
                                     status=404)
        return web.FileResponse(path)

    async def _list_volumes(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        return web.json_response(await self.backend.list_volumes(
            ws.workspace_id))

    async def _create_volume(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        vol = await self.volume_files.ensure(ws.workspace_id,
                                             request.match_info["name"])
        return web.json_response(vol)

    async def _delete_volume(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        ok = await self.backend.delete_volume(ws.workspace_id,
                                              request.match_info["name"])
        return web.json_response({"ok": ok})

    async def _volume_list(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        return web.json_response(await self.volume_files.list(
            ws.workspace_id, request.match_info["name"],
            prefix=request.query.get("prefix", "")))

    async def _volume_put(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        data = await request.read()
        try:
            n = await self.volume_files.write(
                ws.workspace_id, request.match_info["name"],
                request.match_info["path"], data)
        except PrimitiveError as exc:
            return web.json_response({"error": str(exc)}, status=400)
        return web.json_response({"size": n})

    async def _volume_get(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        try:
            data = await self.volume_files.read(
                ws.workspace_id, request.match_info["name"],
                request.match_info["path"])
        except PrimitiveError as exc:
            return web.json_response({"error": str(exc)}, status=400)
        if data is None:
            return web.json_response({"error": "file not found"}, status=404)
        return web.Response(body=data,
                            content_type="application/octet-stream")

    async def _volume_delete(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        try:
            ok = await self.volume_files.delete(
                ws.workspace_id, request.match_info["name"],
                request.match_info["path"])
        except PrimitiveError as exc:
            return web.json_response({"error": str(exc)}, status=400)
        return web.json_response({"ok": ok})

    def _require_worker(self, request: web.Request) -> None:
        self._ws(request)
        if not request.get("is_worker"):
            raise web.HTTPForbidden(
                text=json.dumps({"error": "worker token required"}),
                content_type="application/json")

    async def _internal_volume_list(self, request: web.Request) -> web.Response:
        self._require_worker(request)
        entries = await self.volume_files.list(
            request.match_info["workspace_id"], request.match_info["name"])
        return web.json_response(entries)

    async def _internal_volume_manifest(self,
                                        request: web.Request) -> web.Response:
        """Chunk manifest of a workspace volume (VERDICT r04 #5): workers
        CacheFS-mount it read-through instead of syncing the whole volume
        down — a container is ready before a multi-GB volume is local, and
        page faults stream exactly the chunks touched. Chunks land in the
        same content-addressed store as image chunks (the worker cache's
        source path already knows how to fetch them). Recomputed only when
        the volume's listing fingerprint (paths+sizes+mtimes) moves."""
        self._require_worker(request)
        ws = request.match_info["workspace_id"]
        name = request.match_info["name"]
        entries = await self.volume_files.list(ws, name)
        fingerprint = hashlib.sha256(json.dumps(
            sorted([e["path"], e["size"], e.get("mtime") or 0]
                   for e in entries), sort_keys=True,
            default=str).encode()).hexdigest()
        cached = self._volume_manifest_cache.get((ws, name))
        if cached is not None and cached[0] == fingerprint:
            return web.Response(text=cached[1],
                                content_type="application/json")
        # chunking a multi-GB volume takes longer than a worker's request
        # timeout — build in a background task, answer within a bounded
        # wait, and return 503 if still building (the worker falls back to
        # sync-down for THIS container; the next mount hits the cache).
        # Keyed by FINGERPRINT: awaiting an in-flight build for an older
        # listing would return a stale manifest as if it were current
        key = (ws, name, fingerprint)
        for k in [k for k, t in self._volume_manifest_builds.items()
                  if t.done()]:
            del self._volume_manifest_builds[k]
        build = self._volume_manifest_builds.get(key)
        if build is None:
            build = asyncio.create_task(
                self._build_volume_manifest(ws, name, entries, fingerprint))
            self._volume_manifest_builds[key] = build
        try:
            blob = await asyncio.wait_for(asyncio.shield(build),
                                          timeout=120.0)
        except asyncio.TimeoutError:
            return web.json_response(
                {"error": "manifest build in progress"}, status=503)
        except Exception as exc:        # noqa: BLE001 — surface, don't 500
            return web.json_response(
                {"error": f"manifest build failed: {exc}"}, status=503)
        return web.Response(text=blob, content_type="application/json")

    async def _build_volume_manifest(self, ws: str, name: str,
                                     entries: list, fingerprint: str) -> str:
        from ..images.manifest import DEFAULT_CHUNK, FileEntry, ImageManifest
        manifest = ImageManifest(
            image_id=f"vol-{ws}-{name}-{fingerprint[:12]}", kind="env")

        def _hash_and_store(blob: bytes) -> str:
            digest = hashlib.sha256(blob).hexdigest()
            self.images.accept_chunk(digest, blob)
            return digest

        for e in entries:
            # ranged reads + per-chunk thread hops: a multi-GB file never
            # buffers whole in gateway RAM, and the event loop keeps
            # serving between chunks
            chunks = []
            size = 0
            for off in range(0, int(e["size"]), DEFAULT_CHUNK):
                blob = await self.volume_files.read_range(
                    ws, name, e["path"], off, DEFAULT_CHUNK)
                if not blob:
                    break               # file shrank/vanished mid-walk
                chunks.append(await asyncio.to_thread(_hash_and_store,
                                                      blob))
                size += len(blob)
            manifest.files.append(FileEntry(
                path=e["path"], mode=0o644, size=size, chunks=chunks))
            manifest.total_bytes += size
        blob = manifest.to_json()
        self._volume_manifest_cache[(ws, name)] = (fingerprint, blob)
        return blob

    async def _internal_volume_get(self, request: web.Request) -> web.Response:
        self._require_worker(request)
        try:
            data = await self.volume_files.read(
                request.match_info["workspace_id"],
                request.match_info["name"], request.match_info["path"])
        except PrimitiveError as exc:
            return web.json_response({"error": str(exc)}, status=400)
        if data is None:
            return web.json_response({"error": "not found"}, status=404)
        return web.Response(body=data,
                            content_type="application/octet-stream")

    async def _internal_volume_put(self, request: web.Request) -> web.Response:
        self._require_worker(request)
        data = await request.read()
        try:
            n = await self.volume_files.write(
                request.match_info["workspace_id"],
                request.match_info["name"], request.match_info["path"], data)
        except PrimitiveError as exc:
            return web.json_response({"error": str(exc)}, status=400)
        return web.json_response({"size": n})

    async def _volume_mp_initiate(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        try:
            upload_id = await self.volume_files.multipart_initiate(
                ws.workspace_id, request.match_info["name"],
                request.match_info["path"])
        except PrimitiveError as exc:
            return web.json_response({"error": str(exc)}, status=400)
        return web.json_response({"upload_id": upload_id})

    async def _volume_mp_part(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        data = await request.read()
        try:
            await self.volume_files.multipart_put_part(
                ws.workspace_id, request.match_info["upload_id"],
                int(request.match_info["index"]), data)
        except (PrimitiveError, ValueError) as exc:
            return web.json_response({"error": str(exc)}, status=400)
        return web.json_response({"ok": True, "size": len(data)})

    async def _volume_mp_complete(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        body = await request.json()
        try:
            size = await self.volume_files.multipart_complete(
                ws.workspace_id, request.match_info["upload_id"],
                int(body.get("parts", 0)))
        except (PrimitiveError, ValueError) as exc:
            return web.json_response({"error": str(exc)}, status=400)
        return web.json_response({"ok": True, "size": size})

    async def _volume_mp_abort(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        ok = await self.volume_files.multipart_abort(
            ws.workspace_id, request.match_info["upload_id"])
        return web.json_response({"ok": ok})

    # -- handlers: images ------------------------------------------------------

    async def _rpc_image_verify(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        spec = ImageSpec.from_dict(await request.json())
        return web.json_response(
            await self.images.verify(spec, workspace_id=ws.workspace_id))

    async def _rpc_image_build(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        spec = ImageSpec.from_dict(await request.json())
        return web.json_response(await self.images.build(ws.workspace_id,
                                                         spec))

    async def _image_access_ok(self, request: web.Request,
                               image_id: str) -> bool:
        """Workspace scoping for image reads: worker tokens (the pullers)
        see everything, user tokens only their own workspace's images.
        Manifests bake in spec.env so cross-tenant reads leak secrets."""
        if request.get("is_worker"):
            return True
        ws = self._ws(request)
        row = await self.backend.get_image(image_id)
        if row is not None and row["workspace_id"] == ws.workspace_id:
            return True
        # dedupe case: the build/verify call granted an access row even
        # though another workspace owns the image record
        return await self.backend.has_image_access(image_id, ws.workspace_id)

    async def _rpc_image_status(self, request: web.Request) -> web.Response:
        image_id = request.match_info["image_id"]
        if not await self._image_access_ok(request, image_id):
            return web.json_response({"error": "image not found"}, status=404)
        return web.json_response(await self.images.status(image_id))

    async def _rpc_image_manifest(self, request: web.Request) -> web.Response:
        image_id = request.match_info["image_id"]
        if not await self._image_access_ok(request, image_id):
            return web.json_response({"error": "image not found"}, status=404)
        blob = self.images.manifest_json(image_id)
        if blob is None:
            return web.json_response({"error": "image not found"}, status=404)
        return web.Response(text=blob, content_type="application/json")

    async def _rpc_image_chunk(self, request: web.Request) -> web.Response:
        # Chunks are content-addressed and shared across images, so a bare
        # digest can't be workspace-scoped. Workers (the only pull path) may
        # read any chunk; user tokens must name an image they own whose
        # manifest actually contains the digest.
        self._ws(request)
        digest = request.match_info["digest"]
        if not request.get("is_worker"):
            image_id = request.query.get("image_id", "")
            if not await self._image_access_ok(request, image_id):
                return web.json_response({"error": "chunk not found"},
                                         status=404)
            m = self.images.builder.load_manifest(image_id)
            if m is None or digest not in m.all_chunks():
                return web.json_response({"error": "chunk not found"},
                                         status=404)
        data = self.images.chunk(digest)
        if data is None:
            return web.json_response({"error": "chunk not found"}, status=404)
        return web.Response(body=data,
                            content_type="application/octet-stream")

    async def _image_uploader_ws(self, request: web.Request,
                                 image_id: str) -> Optional[str]:
        """Authorize a build-runner upload. STRICTER than read access: only
        the workspace that owns the image ROW (the build requester — whose
        runner token the build container carries) may upload, not
        dedupe-granted readers; otherwise any tenant who proves knowledge of
        a spec could overwrite the shared image other tenants execute.
        Returns the workspace id to record, or None → 404."""
        ws = self._ws(request)
        if request.get("is_worker"):
            return ws.workspace_id
        row = await self.backend.get_image(image_id)
        if row is not None and row["workspace_id"] == ws.workspace_id:
            return ws.workspace_id
        return None

    async def _rpc_image_chunk_put(self, request: web.Request) -> web.Response:
        # chunks are content-addressed and verified against their digest, so
        # any authenticated runner may contribute them (a bad upload can't
        # poison another image — mismatches are rejected)
        self._ws(request)
        digest = request.match_info["digest"]
        data = await request.read()
        if not self.images.accept_chunk(digest, data):
            return web.json_response({"error": "digest mismatch"}, status=400)
        return web.json_response({"ok": True})

    async def _rpc_image_manifest_put(self, request: web.Request) -> web.Response:
        image_id = request.match_info["image_id"]
        workspace_id = await self._image_uploader_ws(request, image_id)
        if workspace_id is None:
            return web.json_response({"error": "image not found"}, status=404)
        out = await self.images.accept_manifest(
            image_id, workspace_id, await request.text())
        if "error" in out:
            return web.json_response(out, status=400)
        return web.json_response(out)

    async def _rpc_image_complete(self, request: web.Request) -> web.Response:
        image_id = request.match_info["image_id"]
        workspace_id = await self._image_uploader_ws(request, image_id)
        if workspace_id is None:
            return web.json_response({"error": "image not found"}, status=404)
        data = await request.json()
        await self.images.complete(image_id, workspace_id,
                                   bool(data.get("ok")),
                                   list(data.get("logs", [])))
        return web.json_response({"ok": True})

    # -- handlers: invoke ------------------------------------------------------

    async def _subdomain_invoke(self, request: web.Request) -> web.Response:
        host = request.headers.get("Host", "").split(":")[0]
        sub = host.split(".")[0] if "." in host else ""
        dep = await self.backend.get_deployment_by_subdomain(sub) if sub \
            else None
        if dep is None:
            return web.json_response({"error": "not found"}, status=404)
        return await self._serve_deployment(
            request, dep, request.match_info.get("tail", ""))

    async def _invoke(self, request: web.Request) -> web.Response:
        name = request.match_info["name"]
        tail = request.match_info.get("tail", "")
        ws = request.get("workspace")
        workspace_id = ws.workspace_id if ws else None

        dep = None
        if workspace_id:
            dep = await self.backend.get_deployment(workspace_id, name)
        if dep is None:
            dep = await self.backend.get_deployment_by_subdomain(name)
        if dep is None and not workspace_id:
            return web.json_response({"error": "unauthorized"}, status=401)
        if dep is None:
            return web.json_response({"error": f"no deployment {name!r}"},
                                     status=404)
        return await self._serve_deployment(request, dep, tail)

    async def _serve_deployment(self, request: web.Request, dep,
                                tail: str) -> web.Response:
        ws = request.get("workspace")
        stub = await self.backend.get_stub(dep.stub_id)
        if stub is None:
            return web.json_response({"error": "stub missing"}, status=500)
        pricing = stub.config.pricing
        external = ws is not None and ws.workspace_id != stub.workspace_id
        # a priced deployment is invokable by OTHER authenticated workspaces
        # (reference deployment.go:91: pricing overrides the owner-only
        # check). Billing only applies to authorized deployments — a PUBLIC
        # (authorized=False) endpoint is free for everyone; charging only
        # the callers who happened to send a token would be both unfair and
        # trivially bypassed by dropping the header.
        priced_external = (external and pricing is not None
                           and pricing.enabled and stub.config.authorized)
        if stub.config.authorized and (ws is None or
                                       (external and not priced_external)):
            return web.json_response({"error": "unauthorized"}, status=401)
        if priced_external:
            return await self._serve_priced(request, stub, ws, pricing, tail)
        return await self._serve_stub(request, stub, tail)

    async def _serve_priced(self, request: web.Request, stub: Stub, ws,
                            pricing, tail: str) -> web.Response:
        """External pay-per-use call: gate on max_in_flight, serve, then
        bill the caller and credit the owner (usage.go TrackTaskCost)."""
        # in-flight tracking as timestamped entries, not a bare counter: a
        # crash-leaked entry expires individually (its deadline passes and
        # the next admission prunes it) without the counter-corruption a
        # whole-key TTL causes under continuous load. Deliberately
        # lock-free: concurrent racers can overshoot the cap by the number
        # of same-instant admissions — max_in_flight is a protective
        # bound, and a bounded transient overshoot beats serializing every
        # paid request through a store mutex (4 RTTs under contention).
        key = f"paid:inflight:{stub.stub_id}"
        req_entry = new_id("pr")
        deadline = time.time() + max(600.0, stub.config.timeout_s * 2)
        now_ts = time.time()
        entries = await self.store.hgetall(key) or {}
        stale = [k for k, v in entries.items() if float(v) <= now_ts]
        if stale:
            await self.store.hdel(key, *stale)
        if len(entries) - len(stale) >= max(1, pricing.max_in_flight):
            return web.json_response(
                {"error": "paid capacity exhausted, retry later"},
                status=429)
        await self.store.hset(key, req_entry, deadline)
        try:
            t0 = time.monotonic()
            resp = await self._serve_stub(request, stub, tail)
            duration_ms = (time.monotonic() - t0) * 1000.0
            if resp.status < 500:
                if pricing.cost_model == "duration":
                    cents = pricing.cost_per_task_duration_ms * duration_ms \
                        * 100.0
                else:
                    cents = pricing.cost_per_task * 100.0
                sid = stub.stub_id
                await self.usage.record_request(
                    ws.workspace_id, 1, metric=f"paid_tasks:{sid}")
                await self.usage.record_request(
                    ws.workspace_id, cents, metric=f"paid_cost_cents:{sid}")
                await self.usage.record_request(
                    stub.workspace_id, cents, metric=f"earned_cents:{sid}")
            return resp
        finally:
            await self.store.hdel(key, req_entry)

    async def _serve_stub(self, request: web.Request, stub: Stub,
                          tail: str) -> web.Response:
        if (stub.stub_type == StubType.REALTIME.value
                and request.headers.get("Upgrade", "").lower() == "websocket"):
            return await self._ws_proxy(stub, request)

        body = await request.read()
        # forward the full request surface (query string + end-to-end
        # headers) — ASGI apps depend on both; hop-by-hop headers stay
        path = "/" + tail if tail else "/"
        if request.query_string:
            path += f"?{request.query_string}"
        # NEVER forward the platform bearer token into a tenant container
        # (a priced/public endpoint's app would capture the CALLER'S
        # workspace credential); runners do no inbound auth of their own.
        # x-tpu9-trace is stripped too: the trace context is gateway-minted
        # below, never client-supplied (a forged header would parent a
        # tenant's engine spans under someone else's trace).
        # x-tpu9-budget-s / x-tpu9-request-id are gateway-level contracts
        # (ISSUE 15): the budget is re-emitted per attempt with spent time
        # deducted; the request id drives the idempotency journal here.
        skip_req = {"host", "connection", "transfer-encoding",
                    "content-length", "authorization", "x-tpu9-trace",
                    "x-tpu9-budget-s", "x-tpu9-request-id",
                    "x-tpu9-no-retry"}
        fwd_headers = [(k, v) for k, v in request.headers.items()
                       if k.lower() not in skip_req]

        # streaming relay (LLM token streams / SSE): the caller opts in via
        # Accept OR the JSON body's stream flag — both hops must agree, or
        # the runner would emit SSE that this proxy buffers whole
        wants_stream = "text/event-stream" in request.headers.get(
            "Accept", "")
        if not wants_stream and b'"stream"' in body[:4096]:
            try:
                wants_stream = bool(json.loads(body).get("stream"))
            except (ValueError, AttributeError):
                pass
        from . import survival as sv

        # request survivability context (ISSUE 15): one monotonic
        # deadline minted from the client's relative budget header, plus
        # the idempotency journal for client-supplied request ids
        ctx = sv.RequestContext.from_headers(request.headers)
        if ctx.expired():
            return web.json_response(
                {"error": "deadline_exceeded: budget exhausted at the "
                          "gateway"}, status=504)
        if ctx.request_id:
            dedup = await self._journal_gate(stub, ctx, stream=wants_stream)
            if dedup is not None:
                return dedup

        if wants_stream:
            return await self._serve_stub_stream(request, stub, path,
                                                 fwd_headers, body, ctx)
        try:
            return await self._serve_stub_buffered(request, stub, path,
                                                   fwd_headers, body, ctx)
        except BaseException:
            # an escaping exception/cancellation between journal-begin
            # and journal-finish must not strand the entry INFLIGHT (it
            # would 409 every retry of this id for the whole TTL);
            # finish(500) CLEARS it so the retry executes afresh. Guarded
            # by journal_closed: a cancellation AFTER the terminal write
            # (client already disconnected to retry) must not delete the
            # DONE entry — that would re-open the double-execution hole
            if ctx.request_id and not ctx.journal_closed:
                try:
                    await self.journal.finish(
                        stub.workspace_id, ctx.request_id, 500,
                        stub_id=stub.stub_id)
                except Exception:   # noqa: BLE001 — best-effort cleanup
                    pass
            raise

    async def _serve_stub_buffered(self, request: web.Request, stub: Stub,
                                   path: str, fwd_headers: list,
                                   body: bytes, ctx) -> web.Response:
        from . import survival as sv
        from ..observability import tracer
        from ..utils.backoff import BackoffPolicy
        rcfg = self.cfg.router
        # X-Tpu9-No-Retry: client opt-out for non-idempotent handlers —
        # at-most-once dispatch, failures surface verbatim
        attempts = 1 if (self.fleet_router is None
                         or request.headers.get(sv.NO_RETRY_HEADER)) \
            else rcfg.failover_max_attempts
        budget = sv.FailoverBudget(
            attempts,
            BackoffPolicy(base_s=rcfg.failover_backoff_base_s,
                          max_s=rcfg.failover_backoff_max_s),
            deadline_mono=ctx.deadline_mono)
        with tracer.span("gateway.invoke",
                         attrs={"stub_id": stub.stub_id,
                                "workspace_id": stub.workspace_id,
                                "method": request.method}) as sp:
            # propagate the span context across the runner RPC boundary:
            # the llm runner parses this header and the engine records its
            # prefill/decode-window spans under the SAME trace id, shipped
            # back on the pressure heartbeat (ISSUE 8)
            trace_hdr = ("X-Tpu9-Trace", f"{sp.trace_id}:{sp.span_id}")
            if self.fleet_router is not None:
                # fleet front door: fair-queue by the CALLING tenant (a
                # priced endpoint's external callers compete with each
                # other, not under the owner's lane), place by KV
                # affinity, shed with 429/503 + Retry-After
                caller = request.get("workspace")
                tenant = caller.workspace_id if caller else stub.workspace_id

                async def _attempt(attempt: int, avoid: set):
                    hdrs = list(fwd_headers) + [trace_hdr]
                    rem = ctx.remaining_s()
                    if rem is not None:
                        # spent budget is DEDUCTED across attempts —
                        # the replica sees what is actually left
                        hdrs.append((sv.BUDGET_HEADER, f"{rem:.3f}"))

                    async def _fwd(prefer):
                        return await self.endpoints.forward(
                            stub, request.method, path, hdrs, body,
                            prefer=prefer, avoid=avoid or None)

                    return await self.fleet_router.submit(
                        stub, tenant, body, _fwd,
                        deadline_mono=ctx.deadline_mono)

                def _on_failover(attempt, failed, delay):
                    # automatic failover (ISSUE 15): counter + a span on
                    # the request's existing trace tree; the failed
                    # replica's affinity entries drop so repeat prefixes
                    # re-home now
                    self.fleet_router.signals.failover(
                        stub.stub_id, reason=f"http_{failed.status}")
                    if failed.container_id:
                        self.fleet_router.note_dispatch_failure(
                            failed.container_id)
                    now_m = time.monotonic()
                    tracer.record_span(
                        "gateway.failover", sp.trace_id, sp.span_id,
                        time.time(), now_m,
                        attrs={"stub_id": stub.stub_id,
                               "workspace_id": stub.workspace_id,
                               "attempt": attempt,
                               "failed_status": failed.status,
                               "failed_replica": failed.container_id or "",
                               "backoff_s": round(delay, 4)},
                        end_mono=now_m)

                result = await sv.submit_with_failover(
                    _attempt, budget, on_failover=_on_failover)
                if budget.attempt > 1:
                    self.fleet_router.signals.retry_result(
                        stub.stub_id, recovered=result.status < 400)
            else:
                hdrs = list(fwd_headers) + [trace_hdr]
                rem = ctx.remaining_s()
                if rem is not None:
                    hdrs.append((sv.BUDGET_HEADER, f"{rem:.3f}"))
                result = await self.endpoints.forward(stub, request.method,
                                                      path, hdrs,
                                                      body)
            sp.attrs["status"] = result.status
            if budget.attempt > 1:
                sp.attrs["attempts"] = budget.attempt
        if ctx.request_id:
            ctype = next((v for k, v in result.headers
                          if k.lower() == "content-type"), "")
            await self.journal.finish(stub.workspace_id, ctx.request_id,
                                      result.status, result.body,
                                      attempts=budget.attempt,
                                      stub_id=stub.stub_id,
                                      content_type=ctype)
            ctx.journal_closed = True
        await self.usage.record_request(stub.workspace_id)
        # preserve the container's response headers (ASGI apps set their own
        # content types and custom headers, incl. duplicates like
        # Set-Cookie); drop hop-by-hop ones. content-encoding excluded: the
        # buffer's client session already decompressed the body.
        resp = web.Response(status=result.status, body=result.body)
        skip = {"connection", "transfer-encoding", "content-length", "server",
                "date", "content-encoding"}
        for k, v in result.headers:
            if k.lower() not in skip:
                resp.headers.add(k, v)
        resp.headers.setdefault("Content-Type", "application/json")
        return resp

    async def _journal_gate(self, stub: Stub, ctx,
                            stream: bool = False) -> Optional[web.Response]:
        """Idempotency gate for client-supplied request ids (ISSUE 15):
        None = this caller owns execution; otherwise the dedup response.
        A retry of an IN-FLIGHT request gets 409 + Retry-After instead of
        a second execution; a retry of a COMPLETED one gets the stored
        result replayed (buffered) or a completion summary (streams)."""
        from . import survival as sv
        state, rec = await self.journal.begin(stub.workspace_id,
                                              ctx.request_id,
                                              stub_id=stub.stub_id)
        if state == sv.NEW:
            return None
        if state == sv.INFLIGHT:
            resp = web.json_response(
                {"error": "request already in flight (idempotent retry "
                          "refused — the original attempt is still "
                          "executing)",
                 "request_id": ctx.request_id,
                 "watermark": rec.get("watermark", 0),
                 "attempts": rec.get("attempts", 1)}, status=409)
            resp.headers["Retry-After"] = "1"
            return resp
        body = sv.RequestJournal.replay_body(rec)
        if body is not None and not stream:
            resp = web.Response(status=int(rec.get("status", 200)),
                                body=body,
                                content_type=str(rec.get("ctype", "")
                                                 or "application/json"))
            resp.headers[sv.REPLAY_HEADER] = "1"
            return resp
        resp = web.json_response(
            {"error": "request already completed",
             "request_id": ctx.request_id,
             "status": rec.get("status", 200),
             "tokens_delivered": rec.get("watermark", 0),
             "attempts": rec.get("attempts", 1)}, status=409)
        resp.headers[sv.REPLAY_HEADER] = "1"
        return resp

    async def _serve_stub_stream(self, request: web.Request, stub: Stub,
                                 path: str, fwd_headers: list,
                                 body: bytes, ctx) -> web.StreamResponse:
        # ctx is REQUIRED: re-minting it from headers here would restart
        # the monotonic deadline at 'now' and silently grant the full
        # budget again — the opposite of the deduction invariant
        try:
            return await self._serve_stub_stream_inner(
                request, stub, path, fwd_headers, body, ctx)
        except BaseException:
            # same journal hygiene as the buffered path: an escaping
            # exception must not strand the entry INFLIGHT for the TTL
            # (journal_closed: never delete a terminal write)
            if ctx.request_id and not ctx.journal_closed:
                try:
                    await self.journal.finish(
                        stub.workspace_id, ctx.request_id, 500,
                        stub_id=stub.stub_id)
                except Exception:   # noqa: BLE001 — best-effort cleanup
                    pass
            raise

    async def _serve_stub_stream_inner(self, request: web.Request,
                                       stub: Stub, path: str,
                                       fwd_headers: list, body: bytes,
                                       ctx) -> web.StreamResponse:
        """Incremental relay: container chunks reach the client as they
        are produced (buffer.go:666's streaming proxy role). Used for LLM
        token streams — a buffered proxy would hold every token until the
        generation finished.

        Survivability (ISSUE 15): for LLM token-stream bodies the relay
        parses the SSE events it forwards and keeps the token watermark;
        when the serving replica dies or stalls mid-generation, the
        stream RESUMES on a healthy replica by replaying
        ``prompt + delivered`` as a fresh prefill with the budget reduced
        by the watermark — the client sees one seamless, duplicate-free
        token sequence. Non-LLM streams keep the legacy single-attempt
        relay (there is no watermark to splice on)."""
        import aiohttp as _aiohttp

        from ..abstractions.common.buffer import ForwardResult
        from ..observability import tracer
        from ..observability.decisions import ledger, rej
        from ..utils.backoff import BackoffPolicy
        from . import survival as sv

        rcfg = self.cfg.router
        llm = sv.parse_llm_stream_body(body) \
            if self.fleet_router is not None else None
        resume = sv.StreamResumption(llm["prompt"], llm["max_new"],
                                     llm["payload"]) if llm else None
        # kvwire block shipping (ISSUE 16): ask the serving replica to
        # export its prefill KV — the kv_key announcement primes O(1)
        # failover resume (and the disagg decode handoff reuses the same
        # request mode). TPU9_KV_SHIP=0/1 overrides for chaos runs.
        ship_env = os.environ.get("TPU9_KV_SHIP", "")
        if (resume is not None and not llm["payload"].get("adopt_kv")
                and len(llm["prompt"]) >= rcfg.kv_ship_min_tokens
                and (ship_env == "1" if ship_env
                     else rcfg.kv_ship_enabled)):
            body = json.dumps({**llm["payload"], "kv_export": True,
                               "stream": True}).encode()
        # prefix-directory peer adopt (ISSUE 20): when the directory says
        # this body's longest prefix lives ONLY in the peer cache (its
        # last serving replica is gone — scale-to-zero, death), hand the
        # chosen replica the adopt hint so it pulls the tier instead of
        # recomputing. Reuses the ISSUE 15 adopt_kv splice path verbatim;
        # the hint is advisory — a lost peer entry degrades to prefill.
        if (llm is not None and not llm["payload"].get("adopt_kv")
                and self.fleet_router is not None):
            adopt = self.fleet_router.kv_adopt_hint(body)
            if adopt is not None:
                payload = json.loads(body)
                payload["adopt_kv"] = adopt
                body = json.dumps(payload).encode()
        budget = sv.FailoverBudget(
            rcfg.failover_max_attempts
            if (resume is not None
                and not request.headers.get(sv.NO_RETRY_HEADER)) else 1,
            BackoffPolicy(base_s=rcfg.failover_backoff_base_s,
                          max_s=rcfg.failover_backoff_max_s),
            deadline_mono=ctx.deadline_mono)
        caller = request.get("workspace")
        tenant = caller.workspace_id if caller else stub.workspace_id
        avoid: set = set()
        sr: Optional[web.StreamResponse] = None
        trace_ref = ["", ""]           # [trace_id, span_id] for failover
        # hop intervals (ISSUE 41): entry -> sent -> headers back -> first
        # token written, each a child of gateway.invoke and a summary of
        # the process registry; once a client request, the first attempt
        t_gateway = request["t_gateway"]        # `_quota_middleware`
        admit_s = 0.0
        hop_attrs = {"stub_id": stub.stub_id,
                     "workspace_id": stub.workspace_id}

        t_first_written = 0.0

        def first_token_written() -> None:
            # a child that outlives its parent: gateway.invoke ended with
            # the headers, this with the first token on the client's socket
            nonlocal t_first_written
            t_first_written = time.monotonic()
            tracer.record_interval(
                "gateway.first_token", metrics,
                "tpu9_gateway_stream_first_s", t_gateway,
                handle.t_open_mono, t_first_written, trace=trace_ref,
                attrs=dict(hop_attrs))

        def stream_done(t_last_written: float) -> None:
            # the client's gap between tokens as the gateway wrote them
            # (ISSUE 57): first token written -> last, over the tokens of
            # this attempt, the first (the watermark counts them)
            n = resume.watermark
            if n >= 2 and t_first_written:
                tracer.record_interval(
                    "gateway.stream", metrics, "tpu9_gateway_stream_gap_s",
                    t_gateway, t_first_written, t_last_written,
                    trace=trace_ref, attrs={**hop_attrs, "tokens": n},
                    per=n - 1)
        finished = False
        terminal_error = False         # stream ended on a forwarded error
        last_failure: Optional[sv.AttemptOutcome] = None

        async def _finish_journal(status: int) -> None:
            if ctx.request_id:
                await self.journal.finish(
                    stub.workspace_id, ctx.request_id, status,
                    watermark=resume.watermark if resume else 0,
                    attempts=budget.attempt, stub_id=stub.stub_id)
                ctx.journal_closed = True

        async def _client_error(status: int, payload: dict,
                                headers=()) -> web.StreamResponse:
            """Terminal failure: plain response if nothing was sent yet,
            else an SSE error event on the already-prepared stream."""
            await _finish_journal(status)
            if sr is None:
                resp = web.json_response(payload, status=status)
                for k, v in headers:
                    resp.headers[k] = v
                return resp
            try:
                await sr.write(
                    f"data: {json.dumps(payload)}\n\n".encode())
                await sr.write_eof()
            except (ConnectionResetError, OSError) as exc:
                log.debug("client gone during stream error: %s", exc)
            return sr

        while True:
            # all owed tokens already delivered — or the generation
            # visibly ENDED (client-declared eos_id as the last token) —
            # but the terminal event was lost with the replica:
            # synthesize completion, no replay (replaying past EOS would
            # mint tokens the unfailed stream never produces)
            if resume is not None and budget.attempt > 1 \
                    and (resume.remaining == 0 or resume.ended_on_eos):
                ledger.record(
                    "failover", "resume_mode", request_id=trace_ref[0],
                    chosen="synthesize_done",
                    rejected=[rej("replay", "all_tokens_delivered"
                                  if resume.remaining == 0
                                  else "ended_on_eos")],
                    signals={"watermark": resume.watermark,
                             "attempt": budget.attempt},
                    stub_id=stub.stub_id, workspace_id=stub.workspace_id)
                finished = True
                break
            if resume is not None and budget.attempt > 1:
                attempt_body = resume.resume_payload()
                # the ship-vs-reprefill outcome (ISSUE 19): did this
                # resume splice shipped KV blocks or pay a re-prefill?
                ledger.record(
                    "failover", "resume_mode", request_id=trace_ref[0],
                    chosen="block_ship" if resume.kv_key else "re_prefill",
                    rejected=[] if resume.kv_key
                    else [rej("block_ship", "no_kv_key_announced")],
                    signals={"watermark": resume.watermark,
                             "remaining": resume.remaining,
                             "kv_tokens": resume.kv_tokens,
                             "attempt": budget.attempt},
                    stub_id=stub.stub_id, workspace_id=stub.workspace_id)
            else:
                attempt_body = body
            hdrs = list(fwd_headers)
            rem = ctx.remaining_s()
            if rem is not None:
                if rem <= 0:
                    return await _client_error(
                        504, {"error": "deadline_exceeded: budget "
                                       "exhausted at the gateway"})
                hdrs.append((sv.BUDGET_HEADER, f"{rem:.3f}"))

            if budget.attempt == 1:
                # the stream-setup span covers admission + placement +
                # connect (the TTFT-shaped part a stream's caller feels);
                # the relay loop stays OUTSIDE — a span held open for a
                # minutes-long stream would only reach the ring at close.
                # Resume attempts parent onto this same context.
                span_cm = tracer.span("gateway.invoke",
                                      attrs={"stub_id": stub.stub_id,
                                             "workspace_id":
                                             stub.workspace_id,
                                             "method": request.method,
                                             "stream": True})
            else:
                span_cm = None
            sp = span_cm.__enter__() if span_cm is not None else None
            try:
                if sp is not None:
                    # backdated to the gateway's entry, as engine.request
                    # is to the enqueue: the root covers entry -> headers
                    sp.start, sp.start_mono = t_gateway
                    trace_ref[0], trace_ref[1] = sp.trace_id, sp.span_id
                hdrs.append(("X-Tpu9-Trace",
                             f"{trace_ref[0]}:{trace_ref[1]}"))
                prefer: list = []
                if self.fleet_router is not None:
                    # streams skip the fair queue (a token stream holds
                    # its replica for minutes) but still shed at the door
                    # and carry the router's affinity preference; their
                    # budget slot rides the handle's lifetime via on_close
                    t_admit = time.monotonic()
                    shed, prefer = await self.fleet_router.admit_stream(
                        stub, tenant, attempt_body,
                        deadline_mono=ctx.deadline_mono)
                    admit_s = time.monotonic() - t_admit
                    if shed is not None:
                        # usage records for sheds on BOTH paths: metrics/
                        # billing must not diverge between buffered and
                        # streaming for identical client behavior (first
                        # attempt only — failover re-admissions are
                        # gateway-initiated, not billable)
                        if budget.attempt == 1:
                            await self.usage.record_request(
                                stub.workspace_id)
                        if sp is not None:
                            sp.attrs["status"] = shed.status
                        return await _client_error(
                            shed.status, json.loads(shed.body),
                            headers=shed.headers)
                handle = await self.endpoints.forward_stream(
                    stub, request.method, path, hdrs, attempt_body,
                    prefer=prefer, avoid=avoid or None,
                    # the per-chunk gap bound only applies to RESUMABLE
                    # streams — the relay recovers from the timeout; a
                    # legacy stream keeps the full request budget so a
                    # legitimately quiet app is never truncated
                    gap_s=rcfg.stream_gap_s if resume is not None
                    else None)
                if sp is not None:
                    sp.attrs["status"] = getattr(handle, "status", 0)
            finally:
                if span_cm is not None:
                    span_cm.__exit__(None, None, None)
            # usage records ONCE per client request (first attempt) —
            # gateway-initiated failover attempts must not inflate the
            # tenant's billing (the buffered path bills once too)
            if budget.attempt == 1:
                await self.usage.record_request(stub.workspace_id)

            if isinstance(handle, ForwardResult):
                failed = sv.AttemptOutcome(
                    kind="failed", reason=f"connect_{handle.status}",
                    replica=handle.container_id, error_body=handle.body)
                verdict = sv.classify_result(handle.status, handle.body)
            elif handle.status >= 400:
                # connected but the replica refused (engine dead → 500,
                # booting → 503): drain the small error body for the
                # classifier, then treat like a connect failure
                err = b""
                try:
                    async for chunk in handle.iter_chunks():
                        err += chunk
                        if len(err) > 4096:
                            break
                except (ConnectionResetError, OSError, _aiohttp.ClientError,
                        asyncio.TimeoutError):
                    pass
                await handle.close()
                failed = sv.AttemptOutcome(
                    kind="failed", reason=f"http_{handle.status}",
                    replica=handle.container_id, error_body=err)
                verdict = sv.classify_result(handle.status, err)
            else:
                if self.fleet_router is not None and handle.container_id:
                    handle.on_close = self.fleet_router.stream_started(
                        stub, attempt_body, handle.container_id)
                if resume is None:
                    # legacy verbatim relay (non-LLM streams): single
                    # attempt, bytes forwarded untouched. The journal
                    # entry still closes — leaving it INFLIGHT would
                    # 409 every retry of this id for the whole TTL
                    out = await self._relay_stream_legacy(request, handle)
                    await _finish_journal(getattr(handle, "status", 200))
                    return out
                if budget.attempt == 1:
                    tracer.record_interval(
                        "gateway.pre_forward", metrics,
                        "tpu9_gateway_stream_pre_s", t_gateway,
                        t_gateway[1], handle.t_send_mono, trace=trace_ref,
                        attrs={**hop_attrs, "admit_s": round(admit_s, 6),
                               "acquire_s": round(handle.acquire_s, 6)})
                    tracer.record_interval(
                        "gateway.connect", metrics,
                        "tpu9_gateway_stream_connect_s", t_gateway,
                        handle.t_send_mono, handle.t_open_mono,
                        trace=trace_ref,
                        attrs={**hop_attrs,
                               "container_id": handle.container_id})
                if sr is None:
                    sr = web.StreamResponse(status=handle.status)
                    skip = {"connection", "transfer-encoding",
                            "content-length", "server", "date",
                            "content-encoding"}
                    for k, v in handle.headers:
                        if k.lower() not in skip:
                            sr.headers.add(k, v)
                    # the id a user quotes for a slow request:
                    # /api/v1/traces?trace_id=<id> is its waterfall
                    sr.headers["X-Tpu9-Trace-Id"] = trace_ref[0]
                    try:
                        await sr.prepare(request)
                    except (ConnectionResetError, OSError) as exc:
                        log.debug("client gone before stream start: %s",
                                  exc)
                        await handle.close()
                        await _finish_journal(499)
                        return sr
                first = budget.attempt == 1
                outcome = await self._relay_stream_events(
                    handle, resume, sr,
                    first_token_written if first else None,
                    stream_done if first else None)
                await handle.close()
                if outcome.kind == "done":
                    finished = True
                    terminal_error = outcome.reason == "error_event"
                    break
                if outcome.kind == "client_gone":
                    await _finish_journal(499)
                    return sr
                failed = outcome
                verdict = sv.RETRYABLE

            # ---- failover decision -------------------------------------
            last_failure = failed
            budget.note_failure()
            delay = budget.next_delay() if verdict == sv.RETRYABLE else None
            if delay is None:
                ledger.record(
                    "failover",
                    "final" if verdict != sv.RETRYABLE else "give_up",
                    request_id=trace_ref[0], chosen="return_error",
                    rejected=[rej("retry", f"verdict:{verdict}"
                                  if verdict != sv.RETRYABLE
                                  else "budget_exhausted")],
                    signals={"reason": failed.reason,
                             "attempt": budget.attempt,
                             "max_attempts": budget.max_attempts,
                             "watermark": resume.watermark if resume
                             else 0},
                    stub_id=stub.stub_id, workspace_id=stub.workspace_id)
                if self.fleet_router is not None and budget.attempt > 1:
                    self.fleet_router.signals.retry_result(
                        stub.stub_id, recovered=False)
                status = 502 if failed.kind == "failed" else 500
                if failed.reason.startswith(("connect_", "http_")):
                    try:
                        status = int(failed.reason.split("_", 1)[1])
                    except ValueError:
                        pass
                payload = None
                if failed.error_body:
                    try:
                        payload = json.loads(failed.error_body)
                    except ValueError:
                        payload = {"error": failed.error_body.decode(
                            errors="replace")[:500]}
                if verdict != sv.RETRYABLE and payload is not None:
                    # non-retryable upstream error (request shape, app
                    # 4xx): forward the ORIGINAL status + body verbatim
                    # — the legacy relay's contract; a generic
                    # "failover exhausted" message here would bury the
                    # actual diagnostic
                    return await _client_error(status, payload)
                out_payload = {
                    "error": "stream failed and failover budget "
                             f"exhausted ({failed.reason})",
                    "attempts": budget.attempt,
                    "tokens_delivered": resume.watermark
                    if resume else 0}
                if payload is not None:
                    out_payload["last_error"] = payload.get(
                        "error", payload) if isinstance(payload, dict) \
                        else payload
                return await _client_error(status, out_payload)
            if failed.replica:
                avoid.add(failed.replica)
            if self.fleet_router is not None:
                self.fleet_router.signals.failover(stub.stub_id,
                                                   reason=failed.reason)
                if failed.replica:
                    self.fleet_router.note_dispatch_failure(failed.replica)
            if trace_ref[0]:
                now_m = time.monotonic()
                tracer.record_span(
                    "gateway.failover", trace_ref[0], trace_ref[1],
                    time.time(), now_m,
                    attrs={"stub_id": stub.stub_id,
                           "workspace_id": stub.workspace_id,
                           "attempt": budget.attempt,
                           "reason": failed.reason,
                           "failed_replica": failed.replica,
                           "watermark": resume.watermark if resume else 0,
                           "backoff_s": round(delay, 4)},
                    end_mono=now_m)
            # next_delay() consumed the retry: budget.attempt is the one
            # about to run — the record mirrors survival's buffered path
            ledger.record(
                "failover", "retry", request_id=trace_ref[0],
                chosen=f"attempt_{budget.attempt}",
                rejected=[rej(failed.replica or "replica", failed.reason)],
                signals={"verdict": verdict,
                         "failed_attempt": budget.attempt - 1,
                         "max_attempts": budget.max_attempts,
                         "watermark": resume.watermark if resume else 0,
                         "kv_key_known": bool(resume and resume.kv_key),
                         "backoff_s": round(delay, 4)},
                stub_id=stub.stub_id, workspace_id=stub.workspace_id)
            if ctx.request_id and resume is not None:
                await self.journal.update(stub.workspace_id,
                                          ctx.request_id,
                                          resume.watermark, budget.attempt,
                                          stub_id=stub.stub_id)
            log.warning(
                "stream failover for %s: attempt %d, reason=%s, "
                "watermark=%d, replica=%s", stub.stub_id, budget.attempt,
                failed.reason, resume.watermark if resume else 0,
                failed.replica or "?")
            await asyncio.sleep(delay)

        # ---- terminal: one seamless done event (or the forwarded error) --
        if self.fleet_router is not None and budget.attempt > 1:
            self.fleet_router.signals.retry_result(
                stub.stub_id, recovered=not terminal_error)
        # an error-terminal stream (deadline/app error forwarded to the
        # client) must not journal as a completed 200 — finish(500)
        # clears the entry so a retry with this id executes afresh
        await _finish_journal(500 if terminal_error else 200)
        if sr is None:
            # finished before anything streamed (resume.remaining == 0 on
            # a zero-attempt splice) — degenerate but possible
            sr = web.StreamResponse(status=200)
            sr.headers["Content-Type"] = "text/event-stream"
            try:
                await sr.prepare(request)
            except (ConnectionResetError, OSError):
                return sr
        try:
            if resume is not None and finished and not terminal_error:
                await sr.write(
                    f"data: {json.dumps(resume.done_event())}\n\n"
                    .encode())
            await sr.write_eof()
        except (ConnectionResetError, OSError) as exc:
            log.debug("client gone at stream end: %s", exc)
        return sr

    async def _relay_stream_legacy(self, request: web.Request,
                                   handle) -> web.StreamResponse:
        """Pre-ISSUE-15 verbatim relay for non-resumable streams."""
        import aiohttp as _aiohttp
        sr = web.StreamResponse(status=handle.status)
        skip = {"connection", "transfer-encoding", "content-length",
                "server", "date", "content-encoding"}
        for k, v in handle.headers:
            if k.lower() not in skip:
                sr.headers.add(k, v)
        try:
            await sr.prepare(request)
            async for chunk in handle.iter_chunks():
                await sr.write(chunk)
            await sr.write_eof()
        except (ConnectionResetError, OSError, _aiohttp.ClientError,
                asyncio.TimeoutError) as exc:
            # client went away OR the container died / stalled mid-stream:
            # the prepared response can only be dropped, not rewritten —
            # but it must not escape as an unhandled handler exception
            log.debug("stream relay ended early: %s", exc)
        finally:
            await handle.close()
        return sr

    async def _relay_stream_events(self, handle, resume,
                                   sr: web.StreamResponse,
                                   on_first_token=None, on_done=None):
        """Event-aware relay for one attempt of a resumable LLM stream:
        forward token events (advancing the watermark), swallow the
        attempt's own done/error events (the terminal event is owned by
        the failover loop — a resumed attempt's done only knows its own
        suffix), and classify how the attempt ended. ``on_first_token``
        is called once, when the first token event of the attempt has
        been written to the client; ``on_done`` at the attempt's done
        event, with the stamp at which its last token had been written."""
        import aiohttp as _aiohttp
        from . import survival as sv
        parser = sv.SseParser()
        it = handle.iter_chunks().__aiter__()
        t_written = 0.0
        while True:
            try:
                chunk = await it.__anext__()
            except StopAsyncIteration:
                # upstream closed without a terminal event: the replica
                # (or its runner process) died mid-stream
                return sv.AttemptOutcome(kind="failed",
                                         reason="stream_eof",
                                         replica=handle.container_id)
            except asyncio.TimeoutError:
                return sv.AttemptOutcome(kind="failed",
                                         reason="stream_gap",
                                         replica=handle.container_id)
            except (ConnectionResetError, OSError,
                    _aiohttp.ClientError) as exc:
                return sv.AttemptOutcome(
                    kind="failed", reason=f"transport_"
                    f"{type(exc).__name__}", replica=handle.container_id)
            for ev in parser.feed(chunk):
                if "token" in ev:
                    resume.note_token(ev["token"])
                    try:
                        await sr.write(
                            f"data: {json.dumps({'token': ev['token']})}"
                            "\n\n".encode())
                    except (ConnectionResetError, OSError) as exc:
                        log.debug("client gone mid-stream: %s", exc)
                        return sv.AttemptOutcome(kind="client_gone")
                    t_written = time.monotonic()
                    if on_first_token is not None:
                        on_first_token()
                        on_first_token = None
                elif "kv_key" in ev:
                    # kvwire announcement (ISSUE 16): the exporting
                    # replica published this stream's KV blocks —
                    # remember the key for block-ship resume, never
                    # forward transport bookkeeping to the client
                    resume.note_kv(str(ev.get("kv_key", "")),
                                   int(ev.get("n_tokens", 0) or 0))
                elif ev.get("done"):
                    if on_done is not None:
                        on_done(t_written)
                    return sv.AttemptOutcome(kind="done")
                elif "error" in ev:
                    msg = str(ev.get("error", ""))
                    if sv.classify_result(
                            500, msg.encode()) == sv.RETRYABLE:
                        return sv.AttemptOutcome(
                            kind="failed", reason="engine_error",
                            replica=handle.container_id,
                            error_body=msg.encode())
                    # non-retryable engine error (deadline, request
                    # shape): surface it verbatim and end the stream
                    try:
                        await sr.write(
                            f"data: {json.dumps(ev)}\n\n".encode())
                    except (ConnectionResetError, OSError):
                        return sv.AttemptOutcome(kind="client_gone")
                    return sv.AttemptOutcome(kind="done",
                                             reason="error_event")
                else:
                    # unknown/raw frame: forward untouched
                    raw = ev.get("_raw")
                    out = raw + b"\n\n" if raw else \
                        f"data: {json.dumps(ev)}\n\n".encode()
                    try:
                        await sr.write(out)
                    except (ConnectionResetError, OSError):
                        return sv.AttemptOutcome(kind="client_gone")

    async def _ws_proxy(self, stub: Stub, request: web.Request) -> web.StreamResponse:
        """Bidirectional websocket proxy for @realtime deployments
        (endpoint/buffer.go:644 equivalent). Holds a concurrency token on the
        chosen container for the socket's lifetime."""
        import aiohttp as _aiohttp

        inst = await self.endpoints.get_or_create_instance(stub)
        # demand is held for the WHOLE session: it both triggers
        # scale-from-zero and prevents keep-warm scale-down from killing the
        # serving container while the socket is open
        with inst.buffer.hold_demand():
            target = await inst.buffer.acquire(
                deadline_s=min(stub.config.timeout_s, 30.0))
            if target is None:
                return web.json_response({"error": "no capacity"}, status=503)
            container_id, address = target

            ws_client = web.WebSocketResponse()
            try:
                await ws_client.prepare(request)
                if self._proxy_session is None or self._proxy_session.closed:
                    self._proxy_session = _aiohttp.ClientSession()
                async with self._proxy_session.ws_connect(
                        f"http://{address}/",
                        # bounds the websocket CLOSE handshake (TMO001);
                        # the session itself is deliberately unbounded —
                        # realtime sockets live for hours
                        timeout=_aiohttp.ClientWSTimeout(
                            ws_close=self.cfg.router.rpc_timeout_s)
                        ) as ws_upstream:

                    async def pump_up():
                        async for msg in ws_client:
                            if msg.type == web.WSMsgType.TEXT:
                                await ws_upstream.send_str(msg.data)
                            elif msg.type == web.WSMsgType.BINARY:
                                await ws_upstream.send_bytes(msg.data)
                        await ws_upstream.close()

                    async def pump_down():
                        async for msg in ws_upstream:
                            if msg.type == _aiohttp.WSMsgType.TEXT:
                                await ws_client.send_str(msg.data)
                            elif msg.type == _aiohttp.WSMsgType.BINARY:
                                await ws_client.send_bytes(msg.data)
                        await ws_client.close()

                    await asyncio.gather(pump_up(), pump_down(),
                                         return_exceptions=True)
            finally:
                await self.containers.release_request_token(stub.stub_id,
                                                            container_id)
        return ws_client

    # -- handlers: REST v1 ----------------------------------------------------

    async def _list_deployments(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        deps = await self.backend.list_deployments(ws.workspace_id)
        return web.json_response([d.to_dict() for d in deps])

    async def _delete_deployment(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        dep = await self.backend.get_deployment_by_id(request.match_info["id"])
        if dep is None or dep.workspace_id != ws.workspace_id:
            return web.json_response({"error": "not found"}, status=404)
        await self.backend.set_deployment_active(dep.deployment_id, False)
        await self.endpoints.drain_stub(dep.stub_id)
        return web.json_response({"ok": True})

    # -- concurrency limits + apps -------------------------------------------

    # -- workspaces ----------------------------------------------------------

    async def _workspace_create(self, request: web.Request) -> web.Response:
        """Operator mints a workspace + its first token (reference
        /api/v1/workspace)."""
        self._require_operator(request)
        data = await request.json()
        name = data.get("name", "")
        if not name:
            raise web.HTTPBadRequest(
                text=json.dumps({"error": "name required"}),
                content_type="application/json")
        if await self.backend.get_workspace_by_name(name) is not None:
            raise web.HTTPConflict(
                text=json.dumps({"error": f"workspace {name!r} exists"}),
                content_type="application/json")
        ws = await self.backend.create_workspace(name)
        tok = await self.backend.create_token(ws.workspace_id)
        return web.json_response({"workspace_id": ws.workspace_id,
                                  "name": ws.name, "token": tok.key})

    async def _workspace_token(self, request: web.Request) -> web.Response:
        self._require_operator(request)
        workspace_id = request.match_info["workspace_id"]
        if await self.backend.get_workspace(workspace_id) is None:
            raise web.HTTPNotFound(
                text=json.dumps({"error": "workspace not found"}),
                content_type="application/json")
        tok = await self.backend.create_token(workspace_id)
        return web.json_response({"token": tok.key,
                                  "token_id": tok.token_id})

    # -- tokens (self-service; reference /api/v1/token) ----------------------

    def _require_user_token(self, request: web.Request):
        """Token management is for WORKSPACE tokens only. Runner tokens ride
        inside user-controlled containers (build steps, handlers) — letting
        one mint a durable workspace key or revoke the owner's tokens would
        be privilege escalation."""
        if request.get("token_type") != "workspace":
            raise web.HTTPForbidden(
                text=json.dumps({"error": "workspace token required"}),
                content_type="application/json")

    async def _token_list(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        self._require_user_token(request)
        out = []
        for t in await self.backend.list_tokens(ws.workspace_id):
            out.append({"token_id": t.token_id,
                        "key_prefix": t.key[:8],     # never the full key
                        "token_type": t.token_type,
                        "active": t.active,
                        "created_at": t.created_at})
        return web.json_response(out)

    async def _token_create(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        self._require_user_token(request)
        tok = await self.backend.create_token(ws.workspace_id)
        # the ONLY response carrying the full key
        return web.json_response({"token_id": tok.token_id,
                                  "token": tok.key})

    async def _token_revoke(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        self._require_user_token(request)
        token_id = request.match_info["token_id"]
        mine = {t.token_id for t in
                await self.backend.list_tokens(ws.workspace_id)}
        if token_id not in mine:
            raise web.HTTPNotFound(
                text=json.dumps({"error": "token not found"}),
                content_type="application/json")
        return web.json_response(
            {"ok": await self.backend.revoke_token(token_id)})

    # -- machines (BYOC agents; reference pkg/agent + machine API) -----------

    async def _machine_create(self, request: web.Request) -> web.Response:
        self._require_operator(request)
        data = await request.json()
        if not data.get("name"):
            raise web.HTTPBadRequest(
                text=json.dumps({"error": "name required"}),
                content_type="application/json")
        m = await self.backend.create_machine(
            data["name"], data.get("pool", "default"),
            max_workers=int(data.get("max_workers", 1)))
        # the ONLY response that carries the join token — it is one-time
        return web.json_response(m)

    async def _machine_list(self, request: web.Request) -> web.Response:
        self._require_operator(request)
        out = []
        for m in await self.backend.list_machines(
                request.query.get("pool", "")):
            m.pop("join_token", None)
            try:
                m["preflight"] = json.loads(m.get("preflight") or "[]")
            except ValueError:
                m["preflight"] = []
            hb = await self.store.get(Keys.machine_heartbeat(m["machine_id"]))
            m["alive"] = hb is not None
            m["telemetry"] = hb or {}
            m["desired_workers"] = int(
                await self.store.get(
                    Keys.machine_desired(m["machine_id"])) or 0)
            out.append(m)
        return web.json_response(out)

    async def _machine_delete(self, request: web.Request) -> web.Response:
        self._require_operator(request)
        machine_id = request.match_info["machine_id"]
        await self.store.delete(Keys.machine_desired(machine_id),
                                Keys.machine_heartbeat(machine_id),
                                Keys.machine_logs(machine_id))
        return web.json_response(
            {"ok": await self.backend.delete_machine(machine_id)})

    async def _machine_join(self, request: web.Request) -> web.Response:
        data = await request.json()
        m = await self.backend.register_machine(
            data.get("token", ""), data.get("hostname", ""),
            int(data.get("cpu_millicores", 0)),
            int(data.get("memory_mb", 0)),
            int(data.get("tpu_chips", 0)),
            data.get("tpu_generation", ""),
            hourly_cost_micros=int(data.get("hourly_cost_micros", 0)),
            reliability=float(data.get("reliability", 1.0)),
            preflight=self._bounded_preflight(data.get("preflight", [])))
        if m is None:
            # invalid OR already-consumed token — indistinguishable on
            # purpose (don't confirm which tokens once existed)
            raise web.HTTPForbidden(
                text=json.dumps({"error": "invalid join token"}),
                content_type="application/json")
        # the ACTUAL bound port, not the configured one — state_port may be
        # -1 ("any free port") and an agent can't dial 'host:-1'
        state_port = (self.state_server.port if self.state_server
                      else self.cfg.gateway.state_port)
        return web.json_response({
            "machine_id": m["machine_id"],
            "pool": m["pool"],
            "max_workers": m["max_workers"],
            "worker_token": self.worker_token,
            "state_port": state_port,
            "state_auth_token": self.cfg.database.state_auth_token,
        })

    def _machine_for_worker(self, request: web.Request) -> str:
        if not request.get("is_worker"):
            raise web.HTTPForbidden(
                text=json.dumps({"error": "worker token required"}),
                content_type="application/json")
        return request.match_info["machine_id"]

    async def _machine_desired(self, request: web.Request) -> web.Response:
        machine_id = self._machine_for_worker(request)
        if await self.backend.get_machine(machine_id) is None:
            raise web.HTTPNotFound(
                text=json.dumps({"error": "machine not found"}),
                content_type="application/json")
        n = int(await self.store.get(Keys.machine_desired(machine_id)) or 0)
        return web.json_response({"workers": n})

    async def _machine_heartbeat(self, request: web.Request) -> web.Response:
        machine_id = self._machine_for_worker(request)
        if await self.backend.get_machine(machine_id) is None:
            raise web.HTTPNotFound(
                text=json.dumps({"error": "machine not found"}),
                content_type="application/json")
        data = await request.json()
        await self.backend.touch_machine(machine_id)
        await self.store.set(Keys.machine_heartbeat(machine_id),
                             {"ts": time.time(), **data}, ttl=60.0)
        return web.json_response({"ok": True})

    async def _machine_release(self, request: web.Request) -> web.Response:
        """Agent reports voluntary worker exits (idle spindown, rc=0): the
        desired count drops so the agent doesn't respawn forever what the
        platform deliberately shut down."""
        machine_id = self._machine_for_worker(request)
        if await self.backend.get_machine(machine_id) is None:
            raise web.HTTPNotFound(
                text=json.dumps({"error": "machine not found"}),
                content_type="application/json")
        data = await request.json()
        n = max(1, int(data.get("count", 1)))
        left = await self.store.incr(Keys.machine_desired(machine_id),
                                     by=-n, floor=0)
        return web.json_response({"workers": left})

    MACHINE_LOG_CAP = 5000            # per-machine tail kept in the store

    @staticmethod
    def _bounded_preflight(report) -> str:
        """Serialize the agent's preflight report bounded per FIELD (≤32
        checks, 64-char names, 256-char details ⇒ ≤ ~12 KB total) — never
        by slicing the serialized string mid-token, which machine-list
        would silently read back as []."""
        if not isinstance(report, list):
            return "[]"
        return json.dumps(
            [{"name": str(c.get("name", ""))[:64],
              "ok": bool(c.get("ok")),
              "critical": bool(c.get("critical")),
              "detail": str(c.get("detail", ""))[:256]}
             for c in report[:32] if isinstance(c, dict)])

    async def _machine_logs_push(self, request: web.Request) -> web.Response:
        machine_id = self._machine_for_worker(request)
        if await self.backend.get_machine(machine_id) is None:
            raise web.HTTPNotFound(
                text=json.dumps({"error": "machine not found"}),
                content_type="application/json")
        data = await request.json()
        lines = [str(ln)[:4096] for ln in data.get("lines", [])][:1000]
        if lines:
            key = Keys.machine_logs(machine_id)
            await self.store.rpush(key, *lines)
            # capped tail in ONE store call (not N lpop round-trips)
            await self.store.ltrim(key, -self.MACHINE_LOG_CAP, -1)
        return web.json_response({"ok": True, "accepted": len(lines)})

    async def _machine_logs_get(self, request: web.Request) -> web.Response:
        self._require_operator(request)
        machine_id = request.match_info["machine_id"]
        if await self.backend.get_machine(machine_id) is None:
            raise web.HTTPNotFound(
                text=json.dumps({"error": "machine not found"}),
                content_type="application/json")
        try:
            tail = int(request.query.get("tail", 200))
        except ValueError:
            raise web.HTTPBadRequest(
                text=json.dumps({"error": "tail must be an integer"}),
                content_type="application/json")
        tail = max(1, min(tail, self.MACHINE_LOG_CAP))
        lines = await self.store.lrange(Keys.machine_logs(machine_id),
                                        -tail, -1)
        return web.json_response({"lines": lines})

    def _require_operator(self, request: web.Request):
        """Quota writes are operator actions (the reference gates them on
        cluster-admin tokens); tpu9's operator is the default workspace —
        with a USER token. Runner/worker tokens of the default workspace
        ride inside user-controlled containers (builds run arbitrary user
        commands with one); token-type-blind operator checks would be a
        straight privilege escalation to minting durable keys."""
        ws = self._ws(request)
        if (ws.workspace_id != self.default_workspace.workspace_id
                or request.get("token_type") != "workspace"):
            raise web.HTTPForbidden(
                text=json.dumps({"error": "operator token required"}),
                content_type="application/json")
        return ws

    async def _get_concurrency_limit(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        limit = await self.backend.get_concurrency_limit(ws.workspace_id)
        cpu, chips = await self.quota.in_use(ws.workspace_id)
        return web.json_response({
            "limit": limit, "in_use": {"cpu_millicores": cpu,
                                       "tpu_chips": chips}})

    async def _set_concurrency_limit(self, request: web.Request) -> web.Response:
        self._require_operator(request)
        data = await request.json()
        await self.backend.set_concurrency_limit(
            request.match_info["workspace_id"],
            tpu_chip_limit=int(data.get("tpu_chip_limit", 0)),
            cpu_millicore_limit=int(data.get("cpu_millicore_limit", 0)))
        return web.json_response({"ok": True})

    async def _delete_concurrency_limit(self, request: web.Request) -> web.Response:
        self._require_operator(request)
        ok = await self.backend.delete_concurrency_limit(
            request.match_info["workspace_id"])
        return web.json_response({"ok": ok})

    async def _deployments_by_app(self, workspace_id: str) -> dict[str, list]:
        """app_id → deployments, one stub fetch per deployment."""
        grouped: dict[str, list] = {}
        for dep in await self.backend.list_deployments(workspace_id):
            stub = await self.backend.get_stub(dep.stub_id)
            if stub is not None:
                grouped.setdefault(stub.app_id, []).append(dep)
        return grouped

    async def _list_apps(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        grouped = await self._deployments_by_app(ws.workspace_id)
        return web.json_response([
            {**app, "deployments": [d.to_dict() for d in
                                    grouped.get(app["app_id"], [])]}
            for app in await self.backend.list_apps(ws.workspace_id)])

    async def _delete_app(self, request: web.Request) -> web.Response:
        """Delete an app: deactivate + drain every deployment under it
        (reference app group's delete semantics)."""
        ws = self._ws(request)
        apps = await self.backend.list_apps(ws.workspace_id)
        app = next((a for a in apps
                    if a["app_id"] == request.match_info["app_id"]), None)
        if app is None:
            return web.json_response({"error": "not found"}, status=404)
        grouped = await self._deployments_by_app(ws.workspace_id)
        drained = 0
        for dep in grouped.get(app["app_id"], []):
            await self.backend.set_deployment_active(dep.deployment_id,
                                                     False)
            await self.endpoints.drain_stub(dep.stub_id)
            drained += 1
        await self.backend.delete_app(app["app_id"])
        return web.json_response({"ok": True, "deployments_drained": drained})

    async def _list_containers(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        out = []
        for stub in await self.backend.list_stubs(ws.workspace_id):
            for st in await self.containers.containers_by_stub(stub.stub_id):
                out.append(st.to_dict())
        return web.json_response(out)

    async def _container_for(self, request: web.Request, key: str = "id",
                             allow_worker: bool = True):
        """Workspace-scoped container lookup — 404 on missing or foreign
        containers. ``allow_worker`` lets worker tokens act cross-workspace
        like the reference's repo-over-gRPC services."""
        ws = self._ws(request)
        container_id = await self.containers.resolve(
            request.match_info[key])
        state = await self.containers.get_state(container_id)
        worker_ok = allow_worker and request.get("is_worker")
        if state is None or (not worker_ok
                             and state.workspace_id != ws.workspace_id):
            raise web.HTTPNotFound(
                text=json.dumps({"error": "container not found"}),
                content_type="application/json")
        return state

    async def _stop_container(self, request: web.Request) -> web.Response:
        state = await self._container_for(request)
        ok = await self.scheduler.stop_container(state.container_id)
        return web.json_response({"ok": ok})

    async def _container_logs(self, request: web.Request) -> web.Response:
        # post-mortem reads must outlive the 60 s state TTL: fall back to the
        # durable ownership key when state is gone but logs remain
        ws = self._ws(request)
        container_id = await self.containers.resolve(request.match_info["id"])
        state = await self.containers.get_state(container_id)
        owner = (state.workspace_id if state is not None
                 else await self.containers.get_owner(container_id))
        if owner is None or (not request.get("is_worker")
                             and owner != ws.workspace_id):
            raise web.HTTPNotFound(
                text=json.dumps({"error": "container not found"}),
                content_type="application/json")
        since = request.query.get("since", "0")
        entries = await self.containers.read_logs(container_id,
                                                  last_id=since)
        return web.json_response(
            [{"id": eid, **e} for eid, e in entries])

    async def _container_shell(self, request: web.Request) -> web.StreamResponse:
        """Interactive shell: websocket ⇄ worker PTY over the state bus
        (reference: shell abstraction's gateway TCP tunnel, shell/http.go).
        Client sends JSON {d: b64} input / {resize: [rows, cols]}; receives
        JSON {d: b64} output and a final {exit: code}."""
        state = await self._container_for(request)
        if not state.worker_id:
            return web.json_response({"error": "container has no worker"},
                                     status=409)
        session_id = f"shell-{hashlib.sha1(os.urandom(16)).hexdigest()[:12]}"
        ws = web.WebSocketResponse()
        await ws.prepare(request)

        # first-frame protocol: a client may open with {"cmd": [...]} to run
        # a one-shot command under the PTY instead of an interactive shell
        # (scripted `tpu9 shell` with piped stdin). Interactive clients send
        # a resize first, which simply forwards as normal input below.
        cmd = None
        first_payload = None
        try:
            first = await ws.receive(timeout=2.0)
            if first.type == web.WSMsgType.TEXT:
                first_payload = json.loads(first.data)
                if isinstance(first_payload.get("cmd"), list):
                    cmd = first_payload["cmd"]
                    first_payload = None
        except (asyncio.TimeoutError, json.JSONDecodeError):
            pass

        publish_payload = {
            "container_id": state.container_id, "session": session_id,
        }
        if cmd:
            publish_payload["cmd"] = cmd
        subscribers = await self.store.publish(
            f"container:shell:{state.worker_id}", publish_payload)
        if not subscribers:
            # pubsub is fire-and-forget: zero subscribers means the worker
            # is down/restarting — error now instead of hanging the client
            await ws.send_json({"error": "worker unavailable", "exit": -1})
            await ws.close()
            return ws
        out_key = f"shell:out:{session_id}"

        async def pump_down() -> None:
            last_id = "0"
            while not ws.closed:
                entries = await self.containers.store.xread(
                    out_key, last_id=last_id, timeout=1.0)
                for eid, entry in entries:
                    last_id = eid
                    await ws.send_json(entry)
                    if "exit" in entry:
                        await ws.close()
                        return

        down = asyncio.create_task(pump_down())
        try:
            if first_payload is not None:
                await self.store.xadd(f"shell:in:{session_id}",
                                      first_payload)
            async for msg in ws:
                if msg.type != web.WSMsgType.TEXT:
                    continue
                try:
                    payload = json.loads(msg.data)
                except json.JSONDecodeError:
                    continue
                await self.store.xadd(f"shell:in:{session_id}", payload)
        finally:
            await self.store.xadd(f"shell:in:{session_id}", {"close": True})
            down.cancel()
        return ws

    async def _list_disks(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        return web.json_response(await self.disks.list(ws.workspace_id))

    async def _disk_snapshot(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        out = await self.disks.snapshot(ws.workspace_id,
                                        request.match_info["name"])
        status = 200 if "error" not in out else 409
        return web.json_response(out, status=status)

    async def _disk_delete(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        ok = await self.disks.delete(ws.workspace_id,
                                     request.match_info["name"])
        return web.json_response({"ok": ok})

    async def _internal_disk_manifest_put(self, request: web.Request) -> web.Response:
        self._require_worker(request)
        blob = await request.text()
        from ..images import ImageManifest
        try:
            manifest = ImageManifest.from_json(blob)
        except Exception as exc:   # noqa: BLE001
            return web.json_response({"error": f"bad manifest: {exc}"},
                                     status=400)
        await self.backend.set_disk_snapshot(
            request.match_info["workspace_id"], request.match_info["name"],
            request.match_info["snapshot_id"], blob, manifest.total_bytes)
        return web.json_response({"ok": True})

    async def _internal_disk_manifest_get(self, request: web.Request) -> web.Response:
        self._require_worker(request)
        blob = await self.backend.get_disk_snapshot_manifest(
            request.match_info["snapshot_id"])
        if blob is None:
            return web.json_response({"error": "not found"}, status=404)
        return web.Response(text=blob, content_type="application/json")

    async def _internal_sbxsnap_put(self, request: web.Request) -> web.Response:
        self._require_worker(request)
        blob = await request.text()
        from ..images import ImageManifest
        try:
            manifest = ImageManifest.from_json(blob)
        except Exception as exc:   # noqa: BLE001
            return web.json_response({"error": f"bad manifest: {exc}"},
                                     status=400)
        kind = request.query.get("kind", "workdir")
        if kind not in ("workdir", "criu"):
            return web.json_response({"error": f"bad kind {kind!r}"},
                                     status=400)
        await self.backend.put_sandbox_snapshot(
            request.match_info["snapshot_id"],
            request.match_info["workspace_id"],
            request.match_info["container_id"], blob, manifest.total_bytes,
            kind=kind)
        return web.json_response({"ok": True})

    def _ckpt_manifest_path(self, checkpoint_id: str) -> str:
        # checkpoint manifests are ImageManifests, stored the way the image
        # registry stores its own (JSON files under registry_dir) — NOT as
        # backend rows like sandbox snapshots: the registry dir is already
        # the durability domain for every manifest the scheduler hands out
        if not re.fullmatch(r"[A-Za-z0-9_.-]+", checkpoint_id):
            raise web.HTTPBadRequest(
                text=json.dumps({"error": "bad checkpoint id"}),
                content_type="application/json")
        d = os.path.join(self.cfg.image.registry_dir, "checkpoints")
        os.makedirs(d, exist_ok=True)
        return os.path.join(d, f"{checkpoint_id}.json")

    async def _internal_ckpt_record(self, request: web.Request) -> web.Response:
        self._require_worker(request)
        checkpoint_id = await self.backend.create_checkpoint(
            request.match_info["stub_id"],
            request.match_info["workspace_id"],
            request.match_info["container_id"])
        return web.json_response({"checkpoint_id": checkpoint_id})

    async def _internal_ckpt_status(self, request: web.Request) -> web.Response:
        self._require_worker(request)
        body = await request.json()
        await self.backend.update_checkpoint(
            request.match_info["checkpoint_id"],
            str(body.get("status", "failed")),
            str(body.get("remote_key", "")), int(body.get("size", 0)))
        return web.json_response({"ok": True})

    async def _internal_ckpt_manifest_put(self,
                                          request: web.Request) -> web.Response:
        self._require_worker(request)
        blob = await request.text()
        from ..images import ImageManifest
        try:
            ImageManifest.from_json(blob)
        except Exception as exc:   # noqa: BLE001
            return web.json_response({"error": f"bad manifest: {exc}"},
                                     status=400)
        path = self._ckpt_manifest_path(request.match_info["checkpoint_id"])

        def _write() -> None:      # multi-MB manifests must not stall the
            tmp = f"{path}.tmp"    # event loop (every request shares it)
            with open(tmp, "w") as f:
                f.write(blob)
            os.replace(tmp, path)  # readers never see a partial manifest

        await asyncio.to_thread(_write)
        return web.json_response({"ok": True})

    async def _internal_ckpt_manifest_get(self,
                                          request: web.Request) -> web.Response:
        self._require_worker(request)
        path = self._ckpt_manifest_path(request.match_info["checkpoint_id"])
        if not os.path.exists(path):
            return web.json_response({"error": "not found"}, status=404)
        blob = await asyncio.to_thread(lambda: open(path).read())
        return web.Response(text=blob, content_type="application/json")

    async def _internal_sbxsnap_get(self, request: web.Request) -> web.Response:
        self._require_worker(request)
        snap = await self.backend.get_sandbox_snapshot(
            request.match_info["snapshot_id"])
        if snap is None:
            return web.json_response({"error": "not found"}, status=404)
        return web.Response(text=snap["manifest"],
                            content_type="application/json")

    async def _list_tasks(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        return web.json_response(await self.backend.list_tasks(ws.workspace_id))

    async def _list_workers(self, request: web.Request) -> web.Response:
        self._require_operator(request)   # fleet topology: operator-only
        workers = await self.workers.list()
        out = []
        for w in workers:
            d = w.to_dict()
            d["alive"] = await self.workers.is_alive(w.worker_id)
            out.append(d)
        return web.json_response(out)

    async def _list_stubs(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        return web.json_response(
            [s.to_dict() for s in await self.backend.list_stubs(ws.workspace_id)])

    async def _list_secrets(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        return web.json_response(await self.backend.list_secrets(ws.workspace_id))

    async def _upsert_secret(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        data = await request.json()
        await self.backend.upsert_secret(ws.workspace_id, data["name"],
                                         data["value"])
        return web.json_response({"ok": True})

    async def _delete_secret(self, request: web.Request) -> web.Response:
        ws = self._ws(request)
        ok = await self.backend.delete_secret(ws.workspace_id,
                                              request.match_info["name"])
        return web.json_response({"ok": ok})
