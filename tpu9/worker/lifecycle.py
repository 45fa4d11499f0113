"""Container cold-start orchestration with per-phase metrics.

Reference analogue: ``pkg/worker/lifecycle.go`` — RunContainer's parallel
image-load ∥ storage-mount, port reservation, spec synthesis, device inject,
spawn, readiness, address publish; each phase timed
(``metrics.RecordWorkerStartupPhase``). The phase names here mirror
:class:`tpu9.types.LifecyclePhase` so the startup report tooling can build the
same p50/p95 breakdown the reference's ``sandbox_startup_report.py`` does.
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import socket
import sys
import time
from typing import Awaitable, Callable, Optional

import aiohttp

from ..config import WorkerConfig
from ..repository import ContainerRepository
from ..runtime.base import ContainerSpec, Runtime
from ..types import (ContainerRequest, ContainerState, ContainerStatus,
                     LifecyclePhase, StopReason, StubType)
from ..utils.aio import spawn
from ..utils.paths import compile_cache_dir, validate_path_part
from .tpu_manager import TpuDeviceManager

log = logging.getLogger("tpu9.worker")

READINESS_TIMEOUT_S = 120.0
# the LLM runner is ready when it is SERVEABLE: weights built, every graph
# compiled and warmed. Cold, llama3-8b-int8 on a v5e does not fit 120 s (PR 21:
# two starts were killed at the deadline, each leaving a warmer compile cache,
# before a third came up in 66 s)
LLM_READINESS_TIMEOUT_S = 600.0

# identity tenant serving containers drop to under NativeRuntime ("nobody")
UNPRIVILEGED_UID = 65534


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _validate_volume_name(name: str) -> None:
    validate_path_part(name, "volume name")


class ContainerLifecycle:
    def __init__(self, worker_id: str, cfg: WorkerConfig, runtime: Runtime,
                 containers: ContainerRepository, tpu: TpuDeviceManager,
                 object_resolver: Optional[Callable[[str], Awaitable[str]]] = None,
                 image_resolver: Optional[Callable[[str], Awaitable[str]]] = None,
                 volume_sync=None,
                 checkpoints=None,
                 phase_cb: Optional[Callable[[str, str, float], None]] = None):
        self.worker_id = worker_id
        self.cfg = cfg
        self.runtime = runtime
        self.containers = containers
        self.tpu = tpu
        self.object_resolver = object_resolver
        self.image_resolver = image_resolver
        # async (workspace_id, volume_name) -> local dir: pulls volume
        # contents from the gateway's object store when this worker doesn't
        # share the storage root (cfg.storage_shared False; geesefs analogue
        # without FUSE — sync-down at start, push-back at exit)
        self.volume_sync = volume_sync
        # async (workspace_id, volume_name, local_dir) -> None
        self.volume_push = None
        # CacheFS read-through volume mounts (VERDICT r04 #5): set by the
        # Worker when the host supports FUSE; large volumes mount lazily
        # instead of syncing down, with an overlay upper pushed on exit
        self.volmount = None
        # durable disks (set by the Worker): DiskManager + attach notifier
        self.disks = None
        self.disk_attached = None
        # sandbox agent (set by the Worker): workdir snapshot restores
        self.sandboxes = None
        # ImagePuller (set by the Worker): lazy-fill state for open gating
        self.image_puller = None
        # CRIU manager (set by the Worker): CPU-process checkpoint/restore
        self.criu = None
        # container -> [(workspace_id, volume_name, local_dir)] to push back
        self._synced_volumes: dict[str, list[tuple[str, str, str]]] = {}
        # bundle runtime metadata pre-read off the loop in _prepare_image
        # (a CacheFS-backed bundle read can fault through this very loop)
        self._env_meta: dict[str, dict] = {}
        self.checkpoints = checkpoints   # Optional[CheckpointManager]
        # per-container cold-start restore records (ISSUE 13): the worker
        # heartbeat ships these to coldstart:<container_id> store keys,
        # where /api/v1/coldstart merges them with the runner half.
        # Bounded: shipped entries are popped by the heartbeat.
        self.coldstart_records: dict[str, dict] = {}
        self.phase_cb = phase_cb
        self._active: dict[str, asyncio.Task] = {}
        self._exited: dict[str, int] = {}
        # containers being started or running, with their memory limits —
        # the OOM watcher polices this set from the moment of spawn
        self.memory_limits: dict[str, int] = {}
        # live requests (usage metering reads workspace/chips per container)
        self.requests: dict[str, ContainerRequest] = {}
        # per-container log token buckets (one runaway container must not
        # flood the state bus; reference worker logger rate limiting)
        self._log_limiters: dict[str, "LogLimiter"] = {}
        # stop reasons decided in-process (OOM watcher, stop_container)
        # consumed by the supervisor at exit — avoids read-modify-write races
        # on the shared container state
        self._pending_reasons: dict[str, str] = {}
        # stops that arrived while (or before) the container was cold-starting:
        # runtime.kill is a no-op until the process spawns, so run_container
        # checks this at phase boundaries and aborts instead of starting a
        # container the scheduler already rolled back
        self._stop_requested: dict[str, float] = {}

    def note_stop_reason(self, container_id: str, reason: str) -> None:
        self._pending_reasons[container_id] = reason

    def _phase(self, container_id: str, phase: LifecyclePhase, t0: float) -> None:
        if self.phase_cb:
            self.phase_cb(container_id, phase.value, time.monotonic() - t0)

    # ------------------------------------------------------------------

    async def run_container(self, request: ContainerRequest) -> None:
        """Full cold-start; returns once the container is RUNNING (or failed).
        Exit supervision continues in a background task."""
        t0 = time.monotonic()
        container_id = request.container_id
        state = ContainerState(
            container_id=container_id, stub_id=request.stub_id,
            workspace_id=request.workspace_id, worker_id=self.worker_id,
            status=ContainerStatus.SCHEDULED.value,
            gang_id=request.gang.gang_id if request.gang else "")
        await self.containers.update_state(state)
        self._phase(container_id, LifecyclePhase.WORKER_RECEIVED, t0)
        self.memory_limits[container_id] = request.memory_mb
        self.requests[container_id] = request

        def check_aborted() -> None:
            if container_id in self._stop_requested:
                raise RuntimeError("stopped before start")

        # cold-start boot gate (VERDICT r04 #3): background image fills
        # yield until this container is ready — their sha256/disk work
        # otherwise contends with runner boot on the cold-pull critical
        # path. Faulted reads bypass the gate, so a boot that NEEDS bytes
        # still gets them immediately.
        _gate_puller = getattr(self, "image_puller", None)
        if _gate_puller is not None:
            _gate_puller.boot_started()
        try:
            check_aborted()
            # image materialization ∥ workspace fetch (lifecycle.go:355-368)
            image_task = asyncio.create_task(self._prepare_image(request))
            object_task = asyncio.create_task(self._prepare_workspace(request))
            rootfs = await image_task
            self._phase(container_id, LifecyclePhase.IMAGE_READY, t0)
            workdir = await object_task
            self._phase(container_id, LifecyclePhase.STORAGE_READY, t0)
            check_aborted()

            assignment = self.tpu.assign(request)
            self._phase(container_id, LifecyclePhase.DEVICES_READY, t0)

            # user-pinned port (pods whose entrypoint binds a fixed port)
            # wins; otherwise allocate a free one and pass it via TPU9_PORT
            port = request.ports[0] if request.ports else free_port()
            spec = self._spec_from_request(request, rootfs, workdir, port,
                                           assignment)
            if request.criu_snapshot_id:
                # CPU-container process restore: boot as a FOREGROUND criu
                # restore — criu parents the resurrected tree, so the
                # runtime supervises it like any entrypoint (criu.go:429).
                # Process-runtime only: rootfs-isolated runtimes would need
                # criu + the dump dir INSIDE the container (same gating
                # rationale as the vcache host-path injection).
                if self.criu is None:
                    raise RuntimeError("worker has no criu manager "
                                       "(cannot restore process snapshot)")
                if self.runtime.name != "process":
                    raise RuntimeError(
                        f"criu restore requires the process runtime "
                        f"(got {self.runtime.name!r})")
                dump_dir = await self.criu.materialize_into(
                    container_id, request.criu_snapshot_id)
                spec.entrypoint = self.criu.restore_entrypoint(dump_dir)
                # the resurrected sockets live on the CHECKPOINTED port —
                # readvertise it instead of the fresh allocation
                restored_port = self.criu.restored_port(dump_dir)
                if restored_port:
                    port = restored_port
                    spec.env["TPU9_PORT"] = str(port)
                self._phase(container_id,
                            LifecyclePhase.CHECKPOINT_RESTORED, t0)
            self._phase(container_id, LifecyclePhase.SPEC_READY, t0)

            from ..observability import LogLimiter
            limiter = self._log_limiters.setdefault(container_id,
                                                    LogLimiter())

            def log_cb(line: str, stream: str) -> None:
                # invoked from the runtime's pump coroutine → loop is running
                admit, dropped = limiter.admit()
                # spawn (ASY002): a GC'd append_log task would silently
                # drop container log lines mid-flight
                if dropped:
                    spawn(self.containers.append_log(
                        container_id,
                        f"[tpu9] log rate limited: {dropped} lines dropped",
                        "stderr"), name="lifecycle-log-drop")
                if admit:
                    spawn(self.containers.append_log(
                        container_id, line, stream), name="lifecycle-log")

            check_aborted()
            handle = await self.runtime.run(spec, log_cb=log_cb)
            self._phase(container_id, LifecyclePhase.RUNTIME_STARTED, t0)
            # a stop that raced the spawn: the kill may have hit nothing, so
            # re-check now that the process exists (the except path reaps it)
            check_aborted()

            address = f"127.0.0.1:{port}"
            needs_probe = request.stub_type in (
                StubType.ENDPOINT.value, StubType.ASGI.value,
                StubType.REALTIME.value, StubType.TASK_QUEUE.value,
                StubType.FUNCTION.value, StubType.SCHEDULE.value)
            if needs_probe:
                ready = await self._wait_ready(
                    container_id, address,
                    LLM_READINESS_TIMEOUT_S
                    if request.env.get("TPU9_RUNNER") == "llm"
                    else READINESS_TIMEOUT_S)
                if not ready:
                    # one-shot containers (function/schedule) can finish
                    # their whole job before the probe ever succeeds — a
                    # clean exit is completion, not a failed start. Hand
                    # straight to the supervisor (exit bookkeeping, volume
                    # push-back) instead of the failure path.
                    h = await self.runtime.state(container_id)
                    if (request.stub_type in (StubType.FUNCTION.value,
                                              StubType.SCHEDULE.value)
                            and h is not None and h.exit_code == 0):
                        self._active[container_id] = asyncio.create_task(
                            self._supervise(request, state))
                        return
                    raise RuntimeError("container failed readiness probe")
            elif request.stub_type == StubType.POD.value:
                # pods with a server: best-effort TCP readiness so the proxy
                # doesn't race the bind; batch pods just time out the probe —
                # but a pod whose process already exited is a hard failure
                await self._wait_tcp(container_id, address, budget_s=15.0)
                handle = await self.runtime.state(container_id)
                if handle is not None and handle.exit_code not in (None, 0):
                    raise RuntimeError(
                        f"pod entrypoint exited with {handle.exit_code} "
                        f"before becoming ready")

            state.status = ContainerStatus.RUNNING.value
            state.address = address
            state.started_at = time.time()
            await self.containers.set_address(container_id, address)
            await self.containers.update_state(state)
            self._phase(container_id, LifecyclePhase.CONTAINER_READY, t0)

            # readiness-trigger checkpoint (criu.go:392 analogue): snapshot
            # once the runner marks its state saved — skipped for restores
            if (self.checkpoints is not None and not request.checkpoint_id
                    and request.env.get("TPU9_CHECKPOINT_ENABLED") == "1"):
                spawn(self.checkpoints.auto_checkpoint(
                    request.stub_id, request.workspace_id, container_id,
                    spec.workdir), name=f"auto-ckpt-{container_id[-8:]}")

            self._active[container_id] = asyncio.create_task(
                self._supervise(request, state))
        except Exception as exc:
            log.warning("container %s failed to start: %s", container_id, exc)
            # reap the spawned process if it exists — otherwise it leaks and
            # keeps holding the chips we're about to hand out again
            try:
                await self.runtime.kill(container_id, 9)
            except Exception:
                pass
            self.tpu.release(container_id)
            self.memory_limits.pop(container_id, None)
            self.requests.pop(container_id, None)
            self._log_limiters.pop(container_id, None)
            self._stop_requested.pop(container_id, None)
            self._synced_volumes.pop(container_id, None)
            if self.volmount is not None:
                try:
                    # failed start: unmount without pushing (the container
                    # never ran — the upper holds nothing worth keeping)
                    await self.volmount.release(container_id, push=False)
                except Exception:           # noqa: BLE001
                    pass
            state.status = ContainerStatus.FAILED.value
            # an abort requested by the scheduler/user is not a crash —
            # preserve the noted reason so monitors don't count it as one
            state.stop_reason = (self._pending_reasons.pop(container_id, "")
                                 or StopReason.EXIT.value)
            state.exit_code = 1
            await self.containers.update_state(state)
            # reason prefix is machine-readable (breakers distinguish
            # deliberate stops from crashes); the exception text follows
            await self.containers.set_exit_code(
                container_id, 1, f"{state.stop_reason}: {exc}")
            raise
        finally:
            if _gate_puller is not None:
                _gate_puller.boot_finished()

    async def _record_exit_postmortem(self, state: ContainerState,
                                      code: int) -> None:
        """Worker-witnessed black box for a process-level death (ISSUE
        14): reason ``oom_killed``/``process_exit`` + exit code, tenancy
        stamped from the authoritative container state. Merged into the
        same per-replica list the runner's watchdog/crash records use,
        so `tpu9 postmortem` shows hard kills next to soft wedges.
        Evidence is best-effort — a store blip must not break teardown."""
        try:
            from ..observability.health import (build_postmortem,
                                                store_postmortem)
            rec = build_postmortem(
                reason=("oom_killed"
                        if state.stop_reason == StopReason.OOM.value
                        else "process_exit"),
                exception=f"container process exited with code {code}",
                container_id=state.container_id,
                stats={"exit_code": code,
                       "stop_reason": state.stop_reason,
                       "worker_id": self.worker_id})
            rec["workspace_id"] = state.workspace_id
            rec["stub_id"] = state.stub_id
            # atomic list append: the runner's richer engine_crash record
            # may be landing via the gateway at the same moment — a
            # get→append→set here could erase it
            await store_postmortem(self.containers.store,
                                   state.container_id, rec)
        except Exception as exc:    # noqa: BLE001 — evidence only
            log.warning("exit post-mortem for %s failed: %s",
                        state.container_id, exc)

    async def _supervise(self, request: ContainerRequest,
                         state: ContainerState) -> None:
        container_id = request.container_id
        code = await self.runtime.wait(container_id)
        self._exited[container_id] = code
        self.tpu.release(container_id)
        # the authoritative stop reason: locally-noted (OOM watcher / stop
        # requests) wins, then the live state's, then exit-code inference
        live = await self.containers.get_state(container_id)
        if live is not None:
            state = live
        noted = self._pending_reasons.pop(container_id, "")
        state.status = (ContainerStatus.STOPPED.value if code == 0
                        else ContainerStatus.FAILED.value)
        reason = noted or state.stop_reason
        if not reason and code in (137, -9):
            # normalize SIGKILL exits → OOM like the reference's 137
            # handling (lifecycle.go:1539); asyncio reports them as -signum
            reason = StopReason.OOM.value
        state.stop_reason = reason or StopReason.EXIT.value
        state.exit_code = code
        await self.containers.update_state(state)
        await self.containers.set_exit_code(container_id, code,
                                            state.stop_reason)
        if code != 0 and state.stop_reason in (StopReason.OOM.value,
                                               StopReason.EXIT.value):
            # unorchestrated death (ISSUE 14): an OOM-killed or crashed
            # process can never ship its own black box — the worker is
            # the only witness left, so it records the minimal header
            # (exit code, OOM/exit reason) under the same postmortem:*
            # key the runner's richer records use. Orchestrated stops
            # (user/ttl/scale_down) are not incidents and record nothing.
            await self._record_exit_postmortem(state, code)
        self._active.pop(container_id, None)
        self.memory_limits.pop(container_id, None)
        self.requests.pop(container_id, None)
        self._log_limiters.pop(container_id, None)
        self._stop_requested.pop(container_id, None)
        # cross-host volumes: push container writes back to the object store
        # (last-writer-wins, like the reference's S3-FUSE semantics)
        for ws_id, vol_name, local_dir in self._synced_volumes.pop(
                container_id, []):
            if self.volume_push is not None:
                try:
                    await self.volume_push(ws_id, vol_name, local_dir)
                    log.info("volume %s/%s pushed back from %s",
                             ws_id, vol_name, container_id)
                except Exception as exc:    # noqa: BLE001
                    log.warning("volume push %s/%s failed: %s",
                                ws_id, vol_name, exc)
        # CacheFS-mounted volumes: unmount + push the overlay upper (only
        # the files the container actually wrote)
        if self.volmount is not None:
            try:
                await self.volmount.release(container_id)
            except Exception as exc:        # noqa: BLE001
                log.warning("volume unmount for %s failed: %s",
                            container_id, exc)

    async def stop_container(self, container_id: str,
                             reason: str = StopReason.USER.value) -> bool:
        self.note_stop_reason(container_id, reason)
        now = time.monotonic()
        self._stop_requested[container_id] = now
        # bound the tombstone set: entries older than 10 min belong to
        # containers that either aborted long ago or never arrived
        for cid, ts in list(self._stop_requested.items()):
            if now - ts > 600.0:
                del self._stop_requested[cid]
        delivered = await self.runtime.kill(container_id, 15)
        if not delivered and container_id not in self._active \
                and container_id not in self.requests:
            # the container already exited (or never existed here): its
            # supervisor has run — or never will. Writing STOPPING now
            # would RESURRECT a terminal state row back into the stub
            # index (update_state re-hsets it; only a terminal write
            # removes it), and with no supervisor left to terminalize it
            # the phantom survives every TTL refresh a retrying stop loop
            # grants it — scale-downs then spin on a container that is
            # already gone. Kill-first ordering keeps the user-visible
            # STOPPING status for every genuinely delivered stop.
            self._pending_reasons.pop(container_id, None)
            return False
        state = await self.containers.get_state(container_id)
        if state and state.status not in (ContainerStatus.STOPPED.value,
                                          ContainerStatus.FAILED.value):
            state.status = ContainerStatus.STOPPING.value
            state.stop_reason = reason
            await self.containers.update_state(state)
            if container_id in self._exited \
                    and container_id not in self._active:
                # TOCTOU repair: a trap-and-exit-fast container can have
                # its supervisor finish ENTIRELY between our get_state and
                # the STOPPING write above — then ours was the last write
                # and just resurrected the row. Both paths run on this
                # worker's loop, so "exited recorded + supervisor gone"
                # here proves the terminal write already happened; while
                # the supervisor is still in _active its terminal write is
                # still coming and will overwrite ours. Re-assert terminal
                # state (idempotent with the supervisor's).
                code = self._exited[container_id]
                state.status = (ContainerStatus.STOPPED.value if code == 0
                                else ContainerStatus.FAILED.value)
                state.exit_code = code   # keep the supervisor's record
                await self.containers.update_state(state)
        return delivered

    def active_ids(self) -> list[str]:
        return list(self._active.keys())

    # ------------------------------------------------------------------

    def _lazy_so_path(self) -> str:
        from ..utils import native_binary
        return self.cfg.lazy_so or native_binary("t9lazy_preload.so")

    async def _prepare_image(self, request: ContainerRequest) -> str:
        """Resolve the image bundle for the request. v0: the host environment
        is the image when no image_id is set; the image system (lazy index +
        cache) plugs in through image_resolver."""
        if request.image_id and self.image_resolver:
            rootfs = await self.image_resolver(request.image_id)
            # pre-read the bundle's runtime metadata OFF the event loop:
            # for a CacheFS-mounted bundle this read may page-fault a
            # chunk whose fetch is served BY this loop — a blocking read
            # here would deadlock the whole worker
            meta_path = os.path.join(rootfs, ".tpu9-env.json") \
                if rootfs else ""
            if meta_path:
                def _read_meta() -> dict:
                    # EVERY fs touch of the bundle happens in this thread,
                    # including the site-dir probe _spec_from_request
                    # needs — it must never stat a FUSE path on the loop
                    if not os.path.exists(meta_path):
                        return {}
                    with open(meta_path) as f:
                        meta = json.load(f)
                    site_rel = meta.get("env", {}).get(
                        "TPU9_IMAGE_SITE", "env/site-packages")
                    site_abs = os.path.join(rootfs, site_rel)
                    meta["_image_site"] = site_abs \
                        if os.path.isdir(site_abs) else ""
                    return meta
                try:
                    self._env_meta[request.container_id] = \
                        await asyncio.to_thread(_read_meta)
                except (OSError, ValueError) as exc:
                    log.warning("image metadata read failed for %s: %s",
                                request.container_id, exc)
                    self._env_meta[request.container_id] = {}
            puller = getattr(self, "image_puller", None)
            if puller is not None and not os.path.exists(
                    self._lazy_so_path()):
                # no open-gating shim on this host → an ungated container
                # would read placeholder zeros; fall back to waiting for
                # the background fill (still better than eager: concurrent
                # pulls of the same image share one stream)
                fill = puller.active_fill(request.image_id)
                if fill is not None:
                    log.warning("t9lazy_preload.so not built; waiting for "
                                "full fill of %s", request.image_id)
                    await fill.wait()
            return rootfs
        return ""

    async def _prepare_workspace(self, request: ContainerRequest) -> str:
        """Materialize the synced user code into the sandbox workdir and link
        workspace volumes at their mount paths (process runtime: symlinks
        under the workdir; runc: real bind mounts from the same sources)."""
        base = os.path.join(self.cfg.containers_dir, request.container_id,
                            "workspace")
        os.makedirs(base, exist_ok=True)
        restored = False
        if request.checkpoint_id and self.checkpoints is not None:
            # per-container metrics sink: the manager (and its
            # last_restore_metrics) is shared by every concurrently
            # starting container on this worker
            restore_metrics: dict = {}
            restored = await self.checkpoints.restore(
                request.checkpoint_id, base, metrics_out=restore_metrics)
            if restored:
                self._phase(request.container_id,
                            LifecyclePhase.CHECKPOINT_RESTORED,
                            time.monotonic())
                # worker half of the replica's coldstart record (ISSUE
                # 13): restore decomposition + identity; the heartbeat
                # ships it, /api/v1/coldstart merges the runner half
                self.coldstart_records[request.container_id] = {
                    "container_id": request.container_id,
                    "stub_id": request.stub_id,
                    "workspace_id": request.workspace_id,
                    "worker_id": self.worker_id,
                    "checkpoint_id": request.checkpoint_id,
                    "ts": time.time(),
                    "restore": restore_metrics}
        if not restored and request.object_id and self.object_resolver:
            archive = await self.object_resolver(request.object_id)
            if archive and os.path.exists(archive):
                import zipfile
                await asyncio.to_thread(
                    lambda: zipfile.ZipFile(archive).extractall(base))
        if request.workdir_snapshot_id:
            # sandbox-from-snapshot: materialize the parent sandbox's working
            # tree before the entrypoint starts (raises on failure — never
            # silently start empty, same contract as the disk branch below)
            if self.sandboxes is None:
                raise RuntimeError("worker has no sandbox agent "
                                   "(cannot restore workdir snapshot)")
            await self.sandboxes.restore_into(base,
                                              request.workdir_snapshot_id)
        for mount in request.mounts:
            if mount.kind == "disk" and mount.target:
                if self.disks is None:
                    raise RuntimeError("worker has no disk manager")
                disk_dir = await self.disks.attach(
                    request.workspace_id, mount.source,
                    request.disk_snapshots.get(mount.source, ""),
                    disk_id=request.disk_ids.get(mount.source, ""))
                if self.disk_attached is not None:
                    await self.disk_attached(request.workspace_id,
                                             mount.source)
                link = os.path.realpath(
                    os.path.join(base, mount.target.lstrip("/")))
                if not link.startswith(os.path.realpath(base) + os.sep):
                    raise ValueError(
                        f"mount path escapes workdir: {mount.target!r}")
                os.makedirs(os.path.dirname(link), exist_ok=True)
                if not os.path.lexists(link):
                    os.symlink(disk_dir, link)
                continue
            if mount.kind != "volume" or not mount.target:
                continue
            # worker-side name validation stays on BOTH branches (defense in
            # depth with volume_mounts(): a crafted source must never become
            # a path outside the volume root)
            _validate_volume_name(mount.source)
            host_dir = None
            if not self.cfg.storage_shared and self.volmount is not None:
                # CacheFS read-through first: the container goes ready
                # before a multi-GB volume is local; falls through (None)
                # for small volumes / unsupported hosts
                host_dir = await self.volmount.try_mount(
                    request.workspace_id, mount.source,
                    request.container_id)
            if host_dir is None and not self.cfg.storage_shared \
                    and self.volume_sync is not None:
                host_dir = await self.volume_sync(request.workspace_id,
                                                  mount.source)
                self._synced_volumes.setdefault(
                    request.container_id, []).append(
                        (request.workspace_id, mount.source, host_dir))
            elif host_dir is None:
                host_dir = self._safe_volume_dir(request.workspace_id,
                                                 mount.source)
            os.makedirs(host_dir, exist_ok=True)
            link = os.path.realpath(
                os.path.join(base, mount.target.lstrip("/")))
            if not link.startswith(os.path.realpath(base) + os.sep):
                raise ValueError(
                    f"mount path escapes workdir: {mount.target!r}")
            os.makedirs(os.path.dirname(link), exist_ok=True)
            if not os.path.lexists(link):
                os.symlink(host_dir, link)
        return base

    def _safe_volume_dir(self, workspace_id: str, name: str) -> str:
        """Volume name must be a single path component inside the workspace's
        volume root (same containment contract as VolumeFiles._safe — a
        crafted name like '../../<other-ws>/volumes/x' must never resolve
        cross-tenant)."""
        _validate_volume_name(name)
        base = os.path.realpath(os.path.join(self.cfg.storage_root,
                                             workspace_id, "volumes"))
        full = os.path.realpath(os.path.join(base, name))
        if not (full == base or full.startswith(base + os.sep)):
            raise ValueError(f"volume path escapes workspace: {name!r}")
        return full

    def _spec_from_request(self, request: ContainerRequest, rootfs: str,
                           workdir: str, port: int, assignment) -> ContainerSpec:
        env = dict(request.env)
        image_site = ""
        if rootfs:
            # image bundles ship runtime metadata (.tpu9-env.json); apply
            # image env under the request's env. ALL bundle reads —
            # including the site-dir probe — were done by _prepare_image
            # OFF the event loop: a CacheFS-backed stat here would fault
            # through the very loop that serves the fault (deadlock)
            meta = self._env_meta.pop(request.container_id, {}) or {}
            for k, v in meta.get("env", {}).items():
                env.setdefault(k, v)
            image_site = meta.get("_image_site", "")
        env.update({
            "TPU9_CONTAINER_ID": request.container_id,
            "TPU9_STUB_ID": request.stub_id,
            "TPU9_WORKSPACE_ID": request.workspace_id,
            "TPU9_PORT": str(port),
            "TPU9_WORKDIR": workdir,
            "PYTHONPATH": workdir + os.pathsep + env.get("PYTHONPATH", ""),
            "PYTHONUNBUFFERED": "1",
        })
        # persistent XLA compile cache: jit recompiles are the real TPU
        # cold-start tail; share them across containers on this host. The
        # worker's own rule (utils.compile_cache_dir) is forwarded as is —
        # the path is part of the cache key, so it must not move with
        # containers_dir
        env.setdefault("JAX_COMPILATION_CACHE_DIR", compile_cache_dir())
        if request.checkpoint_id:
            env["TPU9_RESTORED"] = "1"
        if image_site:
            env["PYTHONPATH"] = (env["PYTHONPATH"] + os.pathsep + image_site)

        # volume-cache LD_PRELOAD shim (reference file_cache.go:21-24 injects
        # volume_cache.so + VOLUME_CACHE_MAP the same way): reads of volume
        # files hit the node-local cache copy when one exists
        volume_targets = [m for m in request.mounts
                          if m.kind == "volume" and m.target]
        # ProcessRuntime only: under runc the .so and cache dirs live outside
        # the rootfs — injecting host paths would just make ld.so error on
        # every exec (bind-mount wiring for OCI is in ROADMAP.md)
        if self.cfg.vcache_so and os.path.exists(self.cfg.vcache_so) \
                and volume_targets and self.runtime.name == "process":
            pairs = []
            for m in volume_targets:
                if "/" in m.source or m.source in ("", ".", ".."):
                    continue   # same containment contract as _safe_volume_dir
                cache_dir = os.path.join(self.cfg.vcache_dir,
                                         request.workspace_id, m.source)
                os.makedirs(cache_dir, exist_ok=True)
                # the shim sees the path as the container does: under the
                # workdir for the process runtime
                container_path = os.path.join(workdir, m.target.lstrip("/"))
                pairs.append(f"{container_path}={cache_dir}")
            env["LD_PRELOAD"] = (self.cfg.vcache_so + ":"
                                 + env.get("LD_PRELOAD", "")).rstrip(":")
            env["TPU9_VCACHE_MAP"] = ":".join(pairs)
        # lazy-image open gating: while this image's bundle is still
        # streaming (puller.active_fill), containers gate open() on the
        # fill's fault socket via t9lazy_preload.so — container.ready no
        # longer waits for the whole tree (reference: PullLazy + CLIP FUSE,
        # image.go:274; tpu9 gates opens instead of mounting FUSE)
        lazy_sock_bind = ""
        puller = getattr(self, "image_puller", None)
        if request.image_id and puller is not None \
                and puller.active_fill(request.image_id) is not None:
            lazy_so = self._lazy_so_path()
            if os.path.exists(lazy_so):
                sock = puller.lazy_sock(request.image_id)
                env["TPU9_LAZY_DIRS"] = puller.bundle_path(request.image_id)
                env["TPU9_LAZY_SOCK"] = sock
                env["LD_PRELOAD"] = (lazy_so + ":"
                                     + env.get("LD_PRELOAD", "")).rstrip(":")
                # the socket dir rides into namespaced containers rw —
                # connect(2) needs write permission on the socket inode
                lazy_sock_bind = os.path.dirname(sock)

        devices: list[str] = []
        if assignment is not None:
            env.update(assignment.env)
            devices = assignment.devices
        else:
            # CPU-only containers must not grab the TPU backend
            env.setdefault("JAX_PLATFORMS", "cpu")

        entrypoint = list(request.entrypoint)
        if not entrypoint and request.stub_type == StubType.SANDBOX.value:
            # t9proc as PID 1 (reference: goproc bind-mounted as sandbox
            # init, lifecycle.go:1299-1325): supervised spawn/stdin/kill
            # through its unix socket on the rw workdir bind + zombie
            # reaping. Fallback: plain idle loop (exec path still works).
            from ..utils import native_binary
            t9proc = native_binary("t9proc")
            if os.path.exists(t9proc) and workdir not in ("", "/"):
                entrypoint = [t9proc, "--sock",
                              os.path.join(workdir, ".t9proc.sock")]
            else:
                entrypoint = [sys.executable, "-c",
                              "import time\nwhile True: time.sleep(3600)"]
        if not entrypoint:
            if env.get("TPU9_RUNNER") == "llm":
                runner_mod = "tpu9.runner.llm"
            else:
                runner_mod = {
                    StubType.ENDPOINT.value: "tpu9.runner.endpoint",
                    StubType.ASGI.value: "tpu9.runner.endpoint",
                    StubType.REALTIME.value: "tpu9.runner.endpoint",
                    StubType.TASK_QUEUE.value: "tpu9.runner.taskqueue",
                    StubType.FUNCTION.value: "tpu9.runner.function",
                    StubType.SCHEDULE.value: "tpu9.runner.function",
                    StubType.BOT.value: "tpu9.runner.function",
                    "build": "tpu9.runner.build",
                }.get(request.stub_type, "tpu9.runner.endpoint")
            entrypoint = [sys.executable, "-m", runner_mod]
            # the runner package must be importable inside the sandbox
            repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
            env["PYTHONPATH"] = env["PYTHONPATH"] + os.pathsep + repo_root

        # privilege drop (NativeRuntime only; 0 = stay root): tenant
        # serving/queue/function containers run as an unprivileged uid.
        # Root is kept where it's load-bearing: TPU containers must open
        # /dev/accel* (root-owned device nodes), builds write image env
        # trees, pod/sandbox/bot run arbitrary user entrypoints (the
        # reference's gVisor runs those as sandboxed root too). Seccomp +
        # capability-bounding drop + no_new_privs apply to ALL of them.
        keep_root = (bool(devices)
                     or request.stub_type in ("build", StubType.POD.value,
                                              StubType.SANDBOX.value,
                                              StubType.BOT.value))
        run_as = 0 if keep_root else UNPRIVILEGED_UID

        spec_mounts = []
        if lazy_sock_bind:
            spec_mounts.append((lazy_sock_bind, lazy_sock_bind, False))
        for mount in request.mounts:
            if mount.kind == "volume":
                # CacheFS overlay first, then a volume_sync'd local dir
                # (cross-host: _safe_volume_dir under storage_root is
                # EMPTY on this worker), shared storage last
                mounted = self.volmount.mounted_dir(
                    request.container_id, mount.source) \
                    if self.volmount is not None else None
                if mounted is None:
                    for _ws, vol, local_dir in self._synced_volumes.get(
                            request.container_id, []):
                        if vol == mount.source:
                            mounted = local_dir
                            break
                host_dir = mounted or self._safe_volume_dir(
                    request.workspace_id, mount.source)
                spec_mounts.append((host_dir, mount.target, mount.read_only))
            elif mount.kind == "disk" and self.disks is not None:
                spec_mounts.append((self.disks.disk_dir(
                    request.workspace_id, mount.source,
                    request.disk_ids.get(mount.source, "")),
                    mount.target, mount.read_only))
            elif mount.kind == "bind":
                spec_mounts.append((mount.source, mount.target,
                                    mount.read_only))

        return ContainerSpec(
            container_id=request.container_id,
            entrypoint=entrypoint,
            env=env,
            workdir=workdir,
            rootfs=rootfs,
            mounts=spec_mounts,
            cpu_millicores=request.cpu_millicores,
            memory_mb=request.memory_mb,
            devices=devices,
            ports={port: port},
            # only these keys may be loopback-rewritten/proxied by the
            # native runtime — they are injected by the control plane
            # (runner_env / gang env), never taken from tenant env
            cp_env_keys=["TPU9_GATEWAY_URL", "TPU9_COORDINATOR_ADDR"],
            run_as_uid=run_as, run_as_gid=run_as,
            seccomp_mode=request.seccomp_mode
            or os.environ.get("TPU9_SECCOMP_MODE", ""),
        )

    async def _wait_tcp(self, container_id: str, address: str,
                        budget_s: float = 15.0) -> bool:
        host, _, port = address.rpartition(":")
        deadline = time.monotonic() + budget_s
        while time.monotonic() < deadline:
            handle = await self.runtime.state(container_id)
            if handle is not None and handle.exit_code is not None:
                return False
            try:
                _r, w = await asyncio.wait_for(
                    asyncio.open_connection(host, int(port)), 0.5)
                w.close()
                return True
            except (OSError, asyncio.TimeoutError):
                await asyncio.sleep(0.05)
        return False

    async def _wait_ready(self, container_id: str, address: str,
                          timeout_s: float) -> bool:
        """Poll the runner's /health endpoint (buffer.go:334 equivalent)."""
        deadline = time.monotonic() + timeout_s
        url = f"http://{address}/health"
        async with aiohttp.ClientSession() as session:
            while time.monotonic() < deadline:
                handle = await self.runtime.state(container_id)
                if handle is not None and handle.exit_code is not None:
                    return False
                try:
                    async with session.get(
                            url, timeout=aiohttp.ClientTimeout(total=1.0)) as r:
                        if r.status == 200:
                            return True
                except (aiohttp.ClientError, asyncio.TimeoutError, OSError):
                    pass
                await asyncio.sleep(0.05)
        return False
