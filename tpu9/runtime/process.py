"""Process runtime: containers as supervised host subprocesses.

Each container gets a private sandbox dir (scratch + workspace), its env is
fully specified (no inheritance beyond an allowlist), stdout/stderr stream to
the worker's log callback, and resource limits are applied via RLIMIT where
the platform allows. This is the rootless path the test suite, the bench
cold-start harness, and dev machines use; runc swaps in transparently on
real workers (same ContainerSpec).
"""

from __future__ import annotations

import asyncio
import os
import shutil
import signal
import sys
from typing import Optional

from .base import (ContainerHandle, ContainerSpec, Runtime, RuntimeState,
                   ShellSession)
from .zygote_client import ZygoteClient
from ..utils.aio import cancellable_wait, spawn

_ENV_ALLOWLIST = ("PATH", "HOME", "LANG", "TERM")

# runner modules eligible for zygote (pre-warmed fork) starts. llm/build
# are excluded: llm containers take accelerators with env the fork must
# not half-inherit, builds run arbitrary shell.
_ZYGOTE_MODULES = ("tpu9.runner.endpoint", "tpu9.runner.taskqueue",
                   "tpu9.runner.function")


class ProcessRuntime(Runtime):
    name = "process"

    def __init__(self, base_dir: str = "/tmp/tpu9/containers") -> None:
        self.base_dir = base_dir
        self._procs: dict[str, asyncio.subprocess.Process] = {}
        self._handles: dict[str, ContainerHandle] = {}
        self._waiters: dict[str, asyncio.Task] = {}
        self._log_tasks: dict[str, list[asyncio.Task]] = {}
        self._specs: dict[str, ContainerSpec] = {}
        # pre-warmed fork-server (VERDICT r03 #4): jax/numpy/aiohttp are
        # imported once per worker, runner containers fork from it.
        # TPU9_ZYGOTE=0 disables.
        self._zygote: ZygoteClient | None = None
        if os.environ.get("TPU9_ZYGOTE", "1") != "0":
            self._zygote = ZygoteClient(
                os.path.join(base_dir, ".zygote.sock"))

    def sandbox_dir(self, container_id: str) -> str:
        return os.path.join(self.base_dir, container_id)

    def _zygote_module(self, spec: ContainerSpec) -> str:
        """The runner module to fork for this spec, or '' for exec path."""
        ep = spec.entrypoint
        if (self._zygote is not None and len(ep) == 3
                and ep[0] == sys.executable and ep[1] == "-m"
                and ep[2] in _ZYGOTE_MODULES
                and "LD_PRELOAD" not in spec.env):
            # LD_PRELOAD (vcache/lazy shims) needs a fresh exec to take
            # effect — a fork inherits the zygote's (shimless) libc state
            return ep[2]
        return ""

    async def run(self, spec: ContainerSpec, log_cb=None) -> ContainerHandle:
        sandbox = self.sandbox_dir(spec.container_id)
        os.makedirs(sandbox, exist_ok=True)

        env = {k: v for k in _ENV_ALLOWLIST
               if (v := os.environ.get(k)) is not None}
        env.update(spec.env)
        env.setdefault("TPU9_SANDBOX", sandbox)

        workdir = spec.workdir if spec.workdir not in ("", "/") else sandbox

        def preexec() -> None:
            os.setsid()  # own process group so kill() reaps the whole tree
            # NOTE: no RLIMIT_AS — jax/TF reserve address space far beyond
            # their RSS, so an AS cap spuriously kills ML containers at
            # import. Memory is enforced as RSS by the worker's OOM watcher
            # (reference pkg/runtime/oom_watcher.go), which SIGKILLs over-
            # limit containers → exit 137 → normalized to an OOM stop reason.

        proc = None
        module = self._zygote_module(spec)
        if module and await self._zygote.ensure_started():
            try:
                proc = await self._zygote.spawn(env, workdir, module)
            except Exception as exc:        # noqa: BLE001 — fall back
                import logging
                logging.getLogger("tpu9.worker").warning(
                    "zygote spawn failed (%s); exec fallback", exc)
                proc = None
        if proc is None:
            proc = await asyncio.create_subprocess_exec(
                *spec.entrypoint, cwd=workdir, env=env,
                stdout=asyncio.subprocess.PIPE,
                stderr=asyncio.subprocess.PIPE,
                preexec_fn=preexec)

        handle = ContainerHandle(container_id=spec.container_id, pid=proc.pid,
                                 state=RuntimeState.RUNNING)
        self._procs[spec.container_id] = proc
        self._handles[spec.container_id] = handle
        self._specs[spec.container_id] = spec

        async def pump(stream, name):
            while True:
                line = await stream.readline()
                if not line:
                    break
                if log_cb is not None:
                    try:
                        log_cb(line.decode(errors="replace").rstrip("\n"), name)
                    except Exception:
                        pass

        self._log_tasks[spec.container_id] = [
            asyncio.create_task(pump(proc.stdout, "stdout")),
            asyncio.create_task(pump(proc.stderr, "stderr")),
        ]

        async def reap():
            code = await proc.wait()
            tasks = self._log_tasks.get(spec.container_id, [])
            if tasks:
                # asyncio.wait (ASY003/ASY001): never consumes a child's
                # error or converts OUR cancel into a return — a cancelled
                # reap stops updating state instead of half-finishing
                done, pending = await asyncio.wait(tasks, timeout=2.0)
                for t in pending:
                    t.cancel()
                for t in done:
                    if not t.cancelled():
                        exc = t.exception()
                        if exc is not None:
                            # readline/decode failures (pump only guards
                            # the log_cb call) — log loss must be visible
                            import logging
                            logging.getLogger("tpu9.worker").warning(
                                "log pump for %s died: %r",
                                spec.container_id, exc)
            handle.exit_code = code
            handle.state = (RuntimeState.STOPPED if code == 0
                            else RuntimeState.FAILED)

        self._waiters[spec.container_id] = asyncio.create_task(reap())
        return handle

    async def kill(self, container_id: str, signal_num: int = 15) -> bool:
        proc = self._procs.get(container_id)
        if proc is None or proc.returncode is not None:
            return False
        try:
            os.killpg(os.getpgid(proc.pid), signal_num)
        except ProcessLookupError:
            return False
        if signal_num != signal.SIGKILL:
            # escalate if it ignores the polite signal — STRONG ref: the
            # loop only weak-refs tasks, and a GC'd escalation would let a
            # SIGTERM-trapping container live forever while the scheduler
            # believes it stopped
            async def escalate():
                try:
                    # cancellable_wait, not wait_for: a cancel racing the
                    # exit must cancel the escalation, not be swallowed
                    await cancellable_wait(proc.wait(), timeout=10.0)
                except asyncio.TimeoutError:
                    try:
                        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
                    except ProcessLookupError:
                        pass
            spawn(escalate(), name=f"kill-escalate-{container_id[-8:]}")
        return True

    async def state(self, container_id: str) -> Optional[ContainerHandle]:
        return self._handles.get(container_id)

    async def wait(self, container_id: str) -> int:
        proc = self._procs.get(container_id)
        if proc is None:
            handle = self._handles.get(container_id)
            return handle.exit_code if handle and handle.exit_code is not None else -1
        code = await proc.wait()
        waiter = self._waiters.get(container_id)
        if waiter:
            # shield: reap owns the container's TERMINAL state transition
            # and is shared by every wait() caller — cancelling one caller
            # must not cancel it (pre-existing hazard: the bare `await
            # waiter` propagated the cancel INTO reap, stranding
            # handle.state RUNNING forever). gather (ASY003): our cancel
            # still reaches the caller; a CRASHED reap keeps propagating
            # like it always did (its state updates never ran).
            res = (await asyncio.gather(asyncio.shield(waiter),
                                        return_exceptions=True))[0]
            if (isinstance(res, BaseException)
                    and not isinstance(res, asyncio.CancelledError)):
                raise res
        return code

    def _exec_cwd(self, container_id: str) -> str:
        """Exec runs where the container's entrypoint does (its workdir,
        where volume/disk mounts are linked), not the runtime scratch dir."""
        spec = self._specs.get(container_id)
        if spec is not None and spec.workdir not in ("", "/"):
            return spec.workdir
        return self.sandbox_dir(container_id)

    def fs_root(self, container_id: str):
        if container_id not in self._handles:
            return None
        return self._exec_cwd(container_id)

    async def exec(self, container_id: str, cmd: list[str]) -> tuple[int, str]:
        """Run a command in the container's sandbox/env context."""
        handle = self._handles.get(container_id)
        if handle is None or handle.state != RuntimeState.RUNNING:
            return (-1, "container not running")
        spec = self._specs.get(container_id)
        env = {k: v for k in _ENV_ALLOWLIST
               if (v := os.environ.get(k)) is not None}
        if spec is not None:
            env.update(spec.env)
        proc = await asyncio.create_subprocess_exec(
            *cmd, cwd=self._exec_cwd(container_id), env=env,
            stdout=asyncio.subprocess.PIPE, stderr=asyncio.subprocess.STDOUT)
        out, _ = await proc.communicate()
        return (proc.returncode or 0, out.decode(errors="replace"))

    async def exec_stream(self, container_id: str,
                          cmd: Optional[list[str]] = None) -> "_PtySession":
        """Interactive PTY exec in the container's sandbox/env context
        (the `tpu9 shell` transport)."""
        handle = self._handles.get(container_id)
        if handle is None or handle.state != RuntimeState.RUNNING:
            raise RuntimeError("container not running")
        spec = self._specs.get(container_id)
        env = {k: v for k in _ENV_ALLOWLIST
               if (v := os.environ.get(k)) is not None}
        if spec is not None:
            env.update(spec.env)
        env.setdefault("TERM", "xterm")
        env["PS1"] = r"tpu9:\w$ "
        cmd = cmd or [shutil.which("bash") or "/bin/sh", "-i"]

        import pty as _pty
        master, slave = _pty.openpty()
        proc = await asyncio.create_subprocess_exec(
            *cmd, cwd=self._exec_cwd(container_id), env=env,
            stdin=slave, stdout=slave, stderr=slave,
            preexec_fn=os.setsid, close_fds=True)
        os.close(slave)
        return _PtySession(master, proc)

    async def cleanup(self, container_id: str, remove_sandbox: bool = True) -> None:
        self._procs.pop(container_id, None)
        self._handles.pop(container_id, None)
        self._specs.pop(container_id, None)
        waiter = self._waiters.pop(container_id, None)
        if waiter:
            waiter.cancel()
        for t in self._log_tasks.pop(container_id, []):
            t.cancel()
        if remove_sandbox:
            shutil.rmtree(self.sandbox_dir(container_id), ignore_errors=True)

    def capabilities(self) -> set[str]:
        return {"exec", "exec_stream", "logs"}


class _PtySession(ShellSession):
    """PTY master wired into the event loop; output chunks land on the
    queue, writes go straight to the master fd."""

    def __init__(self, master_fd: int, proc: asyncio.subprocess.Process):
        super().__init__()
        self._fd = master_fd
        self._proc = proc
        self._loop = asyncio.get_running_loop()
        self._closed = False
        self._finished = False
        self._loop.add_reader(master_fd, self._on_readable)
        self._exit_task = asyncio.create_task(self._watch_exit())

    def _on_readable(self) -> None:
        try:
            data = os.read(self._fd, 65536)
        except OSError:          # EIO: slave side closed (process exited)
            data = b""
        if data:
            self.output.put_nowait(data)
        else:
            # fd EOF only closes the pipe; the None terminator comes from
            # the exit watcher AFTER exit_code is known — otherwise the
            # consumer reads the terminator with exit_code still unset
            self._close_fd()

    async def _watch_exit(self) -> None:
        self.exit_code = await self._proc.wait()
        # give the reader a beat to drain buffered output, then finish
        await asyncio.sleep(0.05)
        self._close_fd()
        self._finish()

    def _close_fd(self) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            self._loop.remove_reader(self._fd)
            os.close(self._fd)
        except OSError:
            pass

    def _finish(self) -> None:
        if not self._finished:
            self._finished = True
            self.output.put_nowait(None)

    async def write(self, data: bytes) -> None:
        if not self._closed:
            try:
                os.write(self._fd, data)
            except OSError:
                self._close_fd()

    def resize(self, rows: int, cols: int) -> None:
        if self._closed:
            return
        import fcntl
        import struct
        import termios
        try:
            fcntl.ioctl(self._fd, termios.TIOCSWINSZ,
                        struct.pack("HHHH", rows, cols, 0, 0))
        except OSError:
            pass

    async def close(self) -> None:
        if self._proc.returncode is None:
            try:
                os.killpg(os.getpgid(self._proc.pid), signal.SIGKILL)
            except ProcessLookupError:
                pass
        self._close_fd()
        # the exit watcher records the code and emits the terminator
