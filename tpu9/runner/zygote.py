"""Pre-warmed runner template (fork-server) — sub-second JAX cold starts.

Reference analogue: the reference kills runner cold-start cost with CRIU —
it auto-checkpoints a container right after readiness and restores that
image for every later start (``/root/reference/pkg/worker/criu.go:392``).
tpu9's TPU-first equivalent for the *process* runtime is a zygote: one
long-lived process per worker that has already paid the expensive imports
(jax, numpy, aiohttp, the tpu9 runner modules) **without initializing any
accelerator backend**, and forks a child per container. The child applies
the container's env/cwd/stdio, re-points JAX's config at the env it just
received (the zygote's import-time config must not leak in), and runs the
runner module — skipping interpreter boot + imports entirely.

Fork-safety contract (verified by tests/test_zygote.py):
- the zygote imports but NEVER runs a jax computation → no backend client,
  no XLA thread pools; after warmup only MainThread exists
- children initialize their own backend post-fork (``JAX_PLATFORMS`` of
  their env: ``cpu``, or ``tpu`` for a TPU assignment), so device state is
  never shared across forks

Protocol (SOCK_STREAM unix socket, one connection per spawn):
  worker → zygote: JSON line {"env": {...}, "cwd": ..., "module": ...,
                    "argv": [...]} with [stdout_w, stderr_w] fds attached
                    via SCM_RIGHTS on the first byte
  zygote → worker: {"pid": N}\n  …then, when the child exits…
                   {"exit": code}\n  (connection close = zygote died)
"""

from __future__ import annotations

import array
import json
import os
import selectors
import signal
import socket
import sys

PRELOADS = ("jax", "jax.numpy", "numpy", "aiohttp",
            "tpu9.runner.common", "tpu9.runner.endpoint",
            "tpu9.runner.taskqueue", "tpu9.runner.function")


def _warm_imports() -> None:
    import importlib
    # neutralize any ambient platform pin for the ZYGOTE process only: the
    # import must not dial an accelerator; children re-pin from their env
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    for mod in PRELOADS:
        try:
            importlib.import_module(mod)
        except Exception as exc:      # noqa: BLE001 — degraded, not fatal
            print(f"zygote: preload {mod} failed: {exc}", file=sys.stderr)


def _child_setup(req: dict, stdout_fd: int, stderr_fd: int) -> None:
    # undo the zygote's own signal handling: a runner child must die on
    # SIGTERM exactly like an exec'd runner would (the worker's stop path
    # sends SIGTERM and only escalates after a grace period)
    for sig in (signal.SIGTERM, signal.SIGINT, signal.SIGHUP):
        signal.signal(sig, signal.SIG_DFL)
    os.setsid()
    os.dup2(stdout_fd, 1)
    os.dup2(stderr_fd, 2)
    os.close(stdout_fd)
    os.close(stderr_fd)
    env = req.get("env", {})
    os.environ.clear()
    os.environ.update(env)
    cwd = req.get("cwd") or "/"
    os.chdir(cwd)
    # the interpreter is already up: PYTHONPATH in env is NOT re-read, so
    # mirror it into sys.path (front, preserving order) for app imports
    for entry in reversed(env.get("PYTHONPATH", "").split(os.pathsep)):
        if entry and entry not in sys.path:
            sys.path.insert(0, entry)
    if cwd not in sys.path:
        sys.path.insert(0, cwd)
    # re-point JAX at THIS container's platform/cache config — the values
    # were frozen from the zygote's env at import time
    try:
        import jax
        for env_key, cfg_key, conv in (
                ("JAX_PLATFORMS", "jax_platforms", str),
                ("JAX_COMPILATION_CACHE_DIR",
                 "jax_compilation_cache_dir", str),
                ("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                 "jax_persistent_cache_min_compile_time_secs", float),
                ("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES",
                 "jax_persistent_cache_min_entry_size_bytes", int)):
            if env_key in env:
                try:
                    jax.config.update(cfg_key, conv(env[env_key]))
                except (ValueError, AttributeError):
                    pass
    except Exception:                 # noqa: BLE001
        pass
    sys.argv = [req.get("module", "")] + list(req.get("argv", []))


def _spawn(conn: socket.socket, req: dict, fds: list[int],
           inherited: list[socket.socket]) -> int:
    pid = os.fork()
    if pid != 0:
        for fd in fds:
            os.close(fd)
        return pid
    # ---- child ----
    try:
        # drop EVERY inherited zygote fd: the listener and other children's
        # notify connections. A long-lived child holding a sibling's conn
        # open would keep the worker's exit-watch readline from ever seeing
        # EOF after a zygote crash — containers would look immortal.
        conn.close()
        for s in inherited:
            try:
                s.close()
            except OSError:
                pass
        _child_setup(req, fds[0], fds[1])
        module = req["module"]
        import importlib
        mod = importlib.import_module(module) \
            if module in sys.modules or module in PRELOADS else None
        if mod is not None and hasattr(mod, "main"):
            # preloaded runner: call its entrypoint directly (runpy would
            # warn about re-executing an already-imported module)
            mod.main()
        else:
            import runpy
            runpy.run_module(module, run_name="__main__", alter_sys=True)
        code = 0
    except SystemExit as exc:
        code = exc.code if isinstance(exc.code, int) else 0
    except BaseException:             # noqa: BLE001
        import traceback
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)


def _recv_request(conn: socket.socket):
    """First datagram carries the fds; read until newline for the JSON."""
    buf = bytearray()
    fds: list[int] = []
    while b"\n" not in buf:
        if not fds:
            msg, anc, _flags, _addr = conn.recvmsg(
                65536, socket.CMSG_LEN(2 * array.array("i").itemsize))
            for level, typ, data in anc:
                if level == socket.SOL_SOCKET and typ == socket.SCM_RIGHTS:
                    a = array.array("i")
                    a.frombytes(data[:len(data) - len(data) % a.itemsize])
                    fds.extend(a)
        else:
            msg = conn.recv(65536)
        if not msg:
            return None, fds
        buf.extend(msg)
    line = bytes(buf).split(b"\n", 1)[0]
    return json.loads(line), fds


def serve(sock_path: str) -> None:
    _warm_imports()
    try:
        os.unlink(sock_path)
    except OSError:
        pass
    srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    srv.bind(sock_path)
    srv.listen(64)
    srv.setblocking(False)
    print("zygote: ready", flush=True)

    sel = selectors.DefaultSelector()
    sel.register(srv, selectors.EVENT_READ, "accept")
    children: dict[int, socket.socket] = {}    # pid -> notify conn

    def close_conn(conn: socket.socket) -> None:
        try:
            sel.unregister(conn)
        except (KeyError, ValueError):
            pass
        try:
            conn.close()
        except OSError:
            pass

    def reap() -> None:
        while True:
            try:
                pid, status = os.waitpid(-1, os.WNOHANG)
            except ChildProcessError:
                return
            if pid == 0:
                return
            conn = children.pop(pid, None)
            if conn is not None:
                code = (os.WEXITSTATUS(status) if os.WIFEXITED(status)
                        else 128 + os.WTERMSIG(status))
                try:
                    conn.sendall(json.dumps({"exit": code}).encode() + b"\n")
                except OSError:
                    pass
                close_conn(conn)

    while True:
        events = sel.select(timeout=0.2)
        reap()
        for key, _mask in events:
            if key.data == "accept":
                try:
                    conn, _ = srv.accept()
                except OSError:
                    continue
                # bounded handshake: a half-open client must not wedge the
                # single-threaded fork-server (every later spawn would
                # stall into its exec fallback, then fork a duplicate
                # whenever the zygote unwedged)
                conn.settimeout(10.0)
                try:
                    req, fds = _recv_request(conn)
                    conn.settimeout(None)
                except (OSError, ValueError):
                    conn.close()
                    continue
                if req is None or len(fds) < 2:
                    for fd in fds:
                        os.close(fd)
                    conn.close()
                    continue
                pid = _spawn(conn, req, fds,
                             [srv] + list(children.values()))
                children[pid] = conn
                # watch the worker's end: the protocol has no further
                # client→zygote traffic, so the only READ event on this
                # conn is EOF — the worker died or abandoned the spawn
                # (e.g. its pid-reply read timed out). Its child must not
                # keep running unsupervised while the worker falls back to
                # exec and forks a duplicate (advisor r04).
                sel.register(conn, selectors.EVENT_READ, ("client", pid))
                try:
                    conn.sendall(json.dumps({"pid": pid}).encode() + b"\n")
                except OSError:
                    pass
            else:
                _kind, pid = key.data
                conn = key.fileobj
                try:
                    data = conn.recv(4096)
                except OSError:
                    data = b""
                if data:
                    continue               # stray bytes: ignore, stay open
                if children.pop(pid, None) is not None:
                    # the child setsid()s at startup (pgid == pid) and
                    # runner workloads fork their own subprocesses — kill
                    # the whole group, or the grandchildren survive as the
                    # very duplicates this path exists to prevent
                    try:
                        os.killpg(pid, signal.SIGKILL)
                    except ProcessLookupError:
                        # pre-setsid race: fall back to the lone pid
                        try:
                            os.kill(pid, signal.SIGKILL)
                        except ProcessLookupError:
                            pass
                close_conn(conn)


def main() -> None:
    sock_path = sys.argv[sys.argv.index("--sock") + 1] \
        if "--sock" in sys.argv else os.environ.get("TPU9_ZYGOTE_SOCK", "")
    if not sock_path:
        print("usage: python -m tpu9.runner.zygote --sock PATH",
              file=sys.stderr)
        sys.exit(2)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(0))
    serve(sock_path)


if __name__ == "__main__":
    main()
