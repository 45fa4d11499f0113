"""The system under test as a user runs it: a gateway process and a worker
process (``tpu9 gateway`` / ``tpu9 worker --tpu v5e``, both on the CPU
backend), and an ``@endpoint(runner="llm")`` deployment whose runner container
is the one process that holds the chips.

A copy of ``chip_smoke.py``'s ``Stack`` (proven on the chip in PR 21), kept
here because the yardstick may not depend on a file a later PR can change.
The process that uses it never imports jax.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

from benchmark import chips

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class StackError(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise StackError(msg)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _descendants(pid: int) -> list:
    """Every live descendant of ``pid`` (runner containers setsid, so a
    process-group kill alone would miss them)."""
    kids: dict = {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            continue
        kids.setdefault(ppid, []).append(int(entry))
    out, todo = [], [pid]
    while todo:
        for kid in kids.get(todo.pop(), []):
            out.append(kid)
            todo.append(kid)
    return out


# what a user deploys (README quickstart); the handler returns the engine
APP = """\
from tpu9 import QueueDepthAutoscaler, endpoint


def load():
    from benchmark import serve          # the checkout is on the runner's path
    return serve.build_engine({args!r})


app = endpoint(tpu={tpu!r}, cpu=4, memory={memory!r}, runner="llm",
               keep_warm_seconds=900, timeout=1500, concurrent_requests=256,
               autoscaler=QueueDepthAutoscaler(max_containers=1),
               env={env!r})(load)
"""


class Stack:
    def __init__(self, workdir: str, env: dict, n_chips: int,
                 fake_chips: bool):
        self.workdir = workdir
        self.env = dict(env, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
                        PYTHONUNBUFFERED="1")
        self.n_chips = n_chips
        self.fake_chips = fake_chips
        self.procs: list = []
        self.url = self.token = ""
        self.chips_wait_s = 0.0

    def start(self) -> dict:
        # the last run on this machine, by this harness or an older one, may
        # still hold the chips (``chips.py``): a worker started now would lose
        # its first replica. No code of the program runs in this wait, and it
        # is reported apart (`chips_wait_s`), not as set-up.
        try:
            self.chips_wait_s = chips.wait_free()
        except chips.ChipsBusy as exc:
            raise StackError(f"the chips are not free: {exc}") from None
        t0 = time.time()
        w = self.workdir
        http_port, state_port = _free_port(), _free_port()
        cfg = {
            "gateway": {"http_port": http_port, "state_port": state_port},
            "database": {"path": f"{w}/gateway.db"},
            "storage": {"local_root": f"{w}/workspaces"},
            "cache": {"data_dir": f"{w}/cache"},
            "image": {"registry_dir": f"{w}/registry"},
            "worker": {k: f"{w}/{v}" for k, v in (
                ("images_dir", "images"), ("containers_dir", "containers"),
                ("storage_root", "workspaces"), ("logs_dir", "logs"),
                ("checkpoint_dir", "checkpoints"), ("disks_dir", "disks"),
                ("vcache_dir", "vcache"))},
        }
        cfg_path = f"{w}/config.json"
        with open(cfg_path, "w") as f:
            json.dump(cfg, f)            # JSON is YAML
        self.url = f"http://127.0.0.1:{http_port}"
        cli = [sys.executable, "-m", "tpu9.cli.main"]
        gw_log = f"{w}/gateway.log"
        self._spawn(cli + ["gateway", "--config", cfg_path], gw_log, self.env)
        boot = self._wait_lines(gw_log, ("token:", "worker-token:", "state:"))
        self.token = boot["token:"]
        wenv = dict(self.env)
        if self.fake_chips:             # CPU rehearsal only
            wenv["TPU9_FAKE_TPU_CHIPS"] = str(self.n_chips)
        self._spawn(cli + ["worker", "--gateway-state", boot["state:"],
                           "--gateway-url", self.url,
                           "--token", boot["worker-token:"],
                           "--tpu", "v5e", "--config", cfg_path],
                    f"{w}/worker.log", wenv)
        deadline = time.time() + 60
        workers: list = []
        while time.time() < deadline and not workers:
            time.sleep(0.1)
            self._check_alive()
            workers = self.api("GET", "/api/v1/worker")
        check(workers, "no worker registered within 60 s")
        found = workers[0].get("tpu_chip_count")
        check(found == self.n_chips,
              f"the worker found {found} TPU chips on this machine, the cell "
              f"asks for {self.n_chips}")
        return {"seconds": round(time.time() - t0, 2), "worker_chips": found,
                "chips_wait_s": round(self.chips_wait_s, 3)}

    def _spawn(self, cmd: list, log_path: str, env: dict) -> None:
        with open(log_path, "w") as log:
            self.procs.append(subprocess.Popen(
                cmd, env=env, cwd=self.workdir, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True))

    def _check_alive(self) -> None:
        for p in self.procs:
            check(p.poll() is None,
                  f"{' '.join(p.args[3:5])} exited {p.returncode}: "
                  + self.log_tail(p.args[3]))

    def log_tail(self, which: str, n: int = 1500) -> str:
        try:
            with open(f"{self.workdir}/{which}.log") as f:
                return f.read()[-n:]
        except OSError:
            return ""

    def _wait_lines(self, path: str, keys: tuple, timeout: float = 60):
        deadline = time.time() + timeout
        found: dict = {}
        while time.time() < deadline and len(found) < len(keys):
            time.sleep(0.1)
            self._check_alive()
            with open(path) as f:
                for line in f:
                    for key in keys:
                        if line.startswith(key):
                            found[key] = line[len(key):].strip()
        check(len(found) == len(keys), f"gateway never printed {keys}")
        return found

    def api(self, method: str, path: str, body=None, timeout: float = 60):
        req = urllib.request.Request(
            self.url + path, method=method,
            data=None if body is None else json.dumps(body).encode(),
            headers={"Authorization": f"Bearer {self.token}",
                     "Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            text = resp.read().decode()
        return json.loads(text) if text else {}

    def deploy(self, name: str, source: str) -> dict:
        """Write the app file and deploy it with the real CLI, from a
        'user' directory, the way the README quickstart does."""
        appdir = f"{self.workdir}/apps/{name}"
        os.makedirs(appdir)
        with open(f"{appdir}/app.py", "w") as f:
            f.write(source)
        proc = subprocess.run(
            [sys.executable, "-m", "tpu9.cli.main", "deploy", "app.py:app",
             "--name", name],
            env=dict(self.env, TPU9_GATEWAY_URL=self.url,
                     TPU9_TOKEN=self.token),
            cwd=appdir, capture_output=True, text=True, timeout=120)
        check(proc.returncode == 0,
              f"tpu9 deploy {name} failed: {proc.stderr.strip()[-1500:]}")
        return json.loads(proc.stdout[proc.stdout.index("{"):])

    def failed_starts(self) -> list:
        """The worker's own record of containers it could not start. A
        replica that comes up on the second try has hidden a fault."""
        return [line.strip() for line in
                self.log_tail("worker", 1 << 20).splitlines()
                if "failed to start" in line or "OOM kill" in line]

    def dump_logs(self) -> None:
        """A failed run's evidence, to stderr: what every runner container
        printed, and the ends of the gateway's and the worker's logs."""
        if not self.procs:
            return
        import re
        cids = set(re.findall(r"ct-[0-9a-f]+", self.log_tail("worker", 1 << 20)))
        try:
            for cid in sorted(cids):
                lines = [e.get("line", "") for e in self.api(
                    "GET", f"/api/v1/container/{cid}/logs")]
                print(f"--- container {cid} (last lines)\n"
                      + "\n".join(lines[-60:]), file=sys.stderr)
        except (urllib.error.URLError, OSError, ValueError) as exc:
            print(f"--- container logs unavailable: {exc}", file=sys.stderr)
        for which in ("gateway", "worker"):
            print(f"--- {which}.log (end)\n{self.log_tail(which, 3000)}",
                  file=sys.stderr)

    def stop(self) -> None:
        """SIGTERM (the worker tears its containers down), then SIGKILL of
        whatever is left of both process trees, and wait for each."""
        pids = [pid for p in self.procs
                for pid in [p.pid] + _descendants(p.pid)]
        for p in reversed(self.procs):
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.time() + 20
        for p in self.procs:
            try:
                p.wait(max(deadline - time.time(), 0.1))
            except subprocess.TimeoutExpired:
                pass
        for pid in pids:
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        for p in self.procs:
            try:
                p.wait(10)
            except subprocess.TimeoutExpired:
                pass
        deadline = time.time() + 10
        while time.time() < deadline and any(
                os.path.exists(f"/proc/{pid}") and _alive(pid)
                for pid in pids):
            time.sleep(0.05)

    def release(self) -> float:
        """After ``stop``: the seconds until the chips are free again, which
        on four chips is after the process trees have gone, so that the next
        run on this machine finds nothing to wait for. A run's result does
        not hang on it: chips that stay busy are the next start's to name."""
        if not self.procs:      # nothing was started: nothing to let go
            return 0.0
        try:
            return chips.wait_free()
        except chips.ChipsBusy as exc:
            print(f"benchmark: the chips are not free: {exc}", file=sys.stderr)
            return chips.WAIT_S


def _alive(pid: int) -> bool:
    """False for a zombie that only waits to be reaped by init."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except (OSError, IndexError):
        return False
