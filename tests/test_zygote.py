"""Pre-warmed runner zygote (fork-server): fork-safety, env isolation, and
the cold-start win it exists for (VERDICT r03 #4).

Reference analogue: CRIU auto-checkpoint-after-ready
(/root/reference/pkg/worker/criu.go:392) — the reference restores a warmed
runner image instead of cold-booting; tpu9 forks from a warmed template.
"""

import asyncio
import os

import pytest

from tpu9.runtime.zygote_client import ZygoteClient

pytestmark = pytest.mark.e2e


async def _pump_all(reader: asyncio.StreamReader) -> str:
    out = []
    while True:
        line = await reader.readline()
        if not line:
            break
        out.append(line.decode())
    return "".join(out)


async def test_zygote_spawn_env_cwd_exit(tmp_path):
    zy = ZygoteClient(str(tmp_path / "zy.sock"))
    assert await zy.ensure_started()
    try:
        # a fake runner module on PYTHONPATH of the CHILD (not the zygote):
        # proves sys.path mirroring happens post-fork
        mod_dir = tmp_path / "mods"
        mod_dir.mkdir()
        (mod_dir / "fakerunner.py").write_text(
            "import os, sys\n"
            "print('env=' + os.environ.get('TPU9_MARK', ''))\n"
            "print('cwd=' + os.getcwd())\n"
            "sys.stderr.write('err-stream\\n')\n"
            "sys.exit(7)\n")
        wd = tmp_path / "wd"
        wd.mkdir()
        proc = await zy.spawn(
            {"TPU9_MARK": "forked", "PYTHONPATH": str(mod_dir),
             "PATH": os.environ.get("PATH", "")},
            str(wd), "fakerunner")
        assert proc.pid > 0
        out, err, code = await asyncio.gather(
            _pump_all(proc.stdout), _pump_all(proc.stderr), proc.wait())
        assert "env=forked" in out
        assert f"cwd={wd}" in out
        assert "err-stream" in err
        assert code == 7
    finally:
        await zy.stop()


async def test_zygote_children_are_isolated(tmp_path):
    """Two forks must not share env mutations or module globals."""
    zy = ZygoteClient(str(tmp_path / "zy.sock"))
    assert await zy.ensure_started()
    try:
        mod_dir = tmp_path / "mods"
        mod_dir.mkdir()
        (mod_dir / "mutator.py").write_text(
            "import os\n"
            "import tpu9.runner.common as c\n"
            "prev = getattr(c, 'ZYGOTE_TAINT', None)\n"
            "c.ZYGOTE_TAINT = os.environ['WHO']\n"
            "print(f\"who={os.environ['WHO']} prev={prev}\")\n")
        env = {"PYTHONPATH": str(mod_dir), "PATH": os.environ.get("PATH", "")}
        p1 = await zy.spawn({**env, "WHO": "a"}, str(tmp_path), "mutator")
        out1, _ = await asyncio.gather(_pump_all(p1.stdout), p1.wait())
        p2 = await zy.spawn({**env, "WHO": "b"}, str(tmp_path), "mutator")
        out2, _ = await asyncio.gather(_pump_all(p2.stdout), p2.wait())
        assert "who=a prev=None" in out1
        # fork isolation: child b must NOT see child a's module mutation
        assert "who=b prev=None" in out2
    finally:
        await zy.stop()


async def test_zygote_child_runs_jax(tmp_path):
    """The whole point: a forked child must be able to init its own CPU
    backend and jit — with the imports already paid."""
    zy = ZygoteClient(str(tmp_path / "zy.sock"))
    assert await zy.ensure_started()
    try:
        mod_dir = tmp_path / "mods"
        mod_dir.mkdir()
        (mod_dir / "jaxer.py").write_text(
            "import time\n"
            "t0 = time.perf_counter()\n"
            "import jax, jax.numpy as jnp\n"
            "y = float(jax.jit(lambda x: (x @ x).sum())(jnp.ones((32, 32))))\n"
            "print(f'y={y} import_and_jit={time.perf_counter()-t0:.3f}')\n")
        proc = await zy.spawn(
            {"PYTHONPATH": str(mod_dir), "PATH": os.environ.get("PATH", ""),
             "JAX_PLATFORMS": "cpu"},
            str(tmp_path), "jaxer")
        out, code = await asyncio.gather(_pump_all(proc.stdout), proc.wait())
        assert code == 0, out
        assert "y=32768.0" in out
    finally:
        await zy.stop()


async def test_zygote_child_with_a_tpu_assignment_leaves_the_cpu_pin(
        tmp_path, monkeypatch):
    """ISSUE 21 finding 2: the zygote imports jax pinned to ``cpu`` and a
    forked child re-points ``jax_platforms`` from its own env — so the env a
    TPU assignment carries must name the platform, or a plain
    ``@endpoint(tpu=...)`` container silently computes on the CPU."""
    from tpu9.types import ContainerRequest
    from tpu9.worker.tpu_manager import TpuDeviceManager
    monkeypatch.delenv("TPU9_FAKE_TPU_CHIPS", raising=False)
    monkeypatch.setattr(TpuDeviceManager, "_inventory",
                        staticmethod(lambda: ["/dev/vfio/1"]))
    assignment = TpuDeviceManager(generation="v5e").assign(
        ContainerRequest(container_id="c1", tpu="v5e-1"))
    assert assignment.devices == ["/dev/vfio/1"]
    assert assignment.env["TPU_ACCELERATOR_TYPE"] == "v5litepod-1"
    assert "PJRT_DEVICE" not in assignment.env

    zy = ZygoteClient(str(tmp_path / "zy.sock"))
    assert await zy.ensure_started()
    try:
        mod_dir = tmp_path / "mods"
        mod_dir.mkdir()
        # config only: initialising the backend would need the chip
        (mod_dir / "whichplatform.py").write_text(
            "import jax\n"
            "print('jax_platforms=' + str(jax.config.jax_platforms))\n")
        proc = await zy.spawn(
            {**assignment.env, "PYTHONPATH": str(mod_dir),
             "PATH": os.environ.get("PATH", "")},
            str(tmp_path), "whichplatform")
        out, code = await asyncio.gather(_pump_all(proc.stdout), proc.wait())
        assert code == 0, out
        assert "jax_platforms=tpu" in out
    finally:
        await zy.stop()


async def test_zygote_kill_and_fallback(tmp_path):
    """A zygote that dies mid-flight must not wedge the runtime: spawn
    raises, ProcessRuntime falls back to exec."""
    import sys

    from tpu9.runtime.base import ContainerSpec
    from tpu9.runtime.process import ProcessRuntime

    rt = ProcessRuntime(base_dir=str(tmp_path))
    # break the zygote deliberately
    rt._zygote._broken = True
    spec = ContainerSpec(
        container_id="zy-fb",
        entrypoint=[sys.executable, "-m", "tpu9.runner.function"],
        env={"TPU9_HANDLER": "", "PATH": os.environ.get("PATH", ""),
             "PYTHONPATH": os.path.dirname(os.path.dirname(
                 os.path.dirname(os.path.abspath(__file__))))})
    # function runner with empty handler exits fast — exec fallback path
    handle = await rt.run(spec)
    assert handle.pid > 0
    code = await asyncio.wait_for(rt.wait("zy-fb"), 60)
    assert code != 0        # empty handler is an error, but it RAN
    await rt.cleanup("zy-fb")


async def test_zygote_kills_orphan_on_client_disconnect(tmp_path):
    """Advisor r04: a spawn whose pid-reply path dies after the handshake
    left the forked child running unsupervised while the caller fell back
    to exec (duplicate container). The zygote must SIGKILL the child the
    moment the reply socket sees EOF."""
    import json
    import socket

    zy = ZygoteClient(str(tmp_path / "zy.sock"))
    assert await zy.ensure_started()
    try:
        mod_dir = tmp_path / "mods"
        mod_dir.mkdir()
        (mod_dir / "sleeper.py").write_text("import time\ntime.sleep(600)\n")
        stdout_r, stdout_w = os.pipe()
        stderr_r, stderr_w = os.pipe()
        payload = json.dumps(
            {"env": {"PYTHONPATH": str(mod_dir),
                     "PATH": os.environ.get("PATH", "")},
             "cwd": str(tmp_path), "module": "sleeper",
             "argv": []}).encode() + b"\n"

        def handshake():
            s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            s.settimeout(30.0)
            s.connect(zy.sock_path)
            socket.send_fds(s, [payload], [stdout_w, stderr_w])
            line = s.makefile("rb").readline()
            return s, json.loads(line)["pid"]

        s, pid = await asyncio.to_thread(handshake)
        for fd in (stdout_w, stderr_w):
            os.close(fd)
        os.kill(pid, 0)                     # child is alive
        s.close()                           # worker "dies" mid-spawn
        for _ in range(100):
            try:
                os.kill(pid, 0)
            except ProcessLookupError:
                break
            await asyncio.sleep(0.1)
        else:
            import pytest as _pytest
            _pytest.fail("orphan child survived client disconnect")
        os.close(stdout_r)
        os.close(stderr_r)
    finally:
        await zy.stop()
