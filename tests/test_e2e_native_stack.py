"""Full gateway→scheduler→worker→NativeRuntime stack under real
containment, asserting the privilege posture tenants actually get
(VERDICT r03 #2 'Done' criteria: in-container uid != 0, mount fails,
CapEff ≈ 0 — for the DEFAULT serving path, not a hand-built spec).

Reference analogue: the hardened base OCI spec every reference container
inherits (pkg/runtime/base_runc_config.json) and the gVisor syscall
sandbox (pkg/runtime/runsc.go:52).
"""

import asyncio
import os

import pytest

from tpu9.runtime import NativeRuntime

pytestmark = [
    pytest.mark.e2e,
    pytest.mark.skipif(not NativeRuntime.supported(),
                       reason="needs root + t9container + iproute2"),
]

PROBE_APP = """
import os, subprocess

def handler(**kwargs):
    caps = ""
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith(("CapEff", "NoNewPrivs")):
                caps += line
    mount_rc = subprocess.run(
        ["mount", "-t", "tmpfs", "none", "/tmp"],
        capture_output=True).returncode
    # the workspace must stay writable for the dropped identity
    with open("probe.txt", "w") as f:
        f.write("ok")
    return {"uid": os.getuid(), "gid": os.getgid(), "status": caps,
            "mount_rc": mount_rc}
"""


def test_default_endpoint_runs_unprivileged(monkeypatch):
    monkeypatch.setenv("TPU9_RUNTIME", "native")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    from tpu9.testing.localstack import LocalStack

    async def run():
        async with LocalStack() as stack:
            dep = await stack.deploy_endpoint(
                "priv-probe", {"app.py": PROBE_APP}, "app:handler")
            return await stack.invoke(dep, {})

    resp = asyncio.run(run())
    assert resp["uid"] == 65534, resp
    assert resp["gid"] == 65534, resp
    assert "CapEff:\t0000000000000000" in resp["status"], resp
    assert "NoNewPrivs:\t1" in resp["status"], resp
    assert resp["mount_rc"] != 0, resp


LAZY_APP = """
import hashlib, os

def handler(op="", **kwargs):
    blob = os.environ["BLOB_PATH"]
    if op == "read":
        data = open(blob, "rb").read()
        return {"sha": hashlib.sha256(data).hexdigest(), "n": len(data)}
    return {"size": os.path.getsize(blob), "uid": os.getuid()}
"""


def test_lazy_image_under_native_containment(monkeypatch, built):
    """Lazy-streamed image + netns + ro bundle bind + dropped uid all at
    once: the shim's fault socket must be reachable from inside the netns
    (fs socket over the rw bind of its directory) and the gated read must
    return real bytes."""
    import hashlib
    import shutil
    monkeypatch.setenv("TPU9_RUNTIME", "native")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    from tpu9.testing.localstack import LocalStack

    async def run():
        async with LocalStack() as stack:
            stack.cfg.cache.lazy_threshold_mb = 8
            status, out = await stack.api(
                "POST", "/rpc/image/build", json_body={
                    "commands": ["mkdir -p env && for i in 1 2 3 4 5 6; do "
                                 "head -c 2097152 /dev/urandom > env/f$i.bin;"
                                 " done"]})
            assert status == 200, out
            image_id = out["image_id"]
            for _ in range(600):
                _, st = await stack.api("GET",
                                        f"/rpc/image/status/{image_id}")
                if st["status"] in ("ready", "failed"):
                    break
                await asyncio.sleep(0.1)
            assert st["status"] == "ready", st
            bundle = os.path.join(stack.cfg.cache.data_dir, "bundles",
                                  image_id)
            shutil.rmtree(bundle, ignore_errors=True)
            blob = os.path.join(bundle, "env", "f2.bin")
            dep = await stack.deploy_endpoint(
                "lazy-native", {"app.py": LAZY_APP}, "app:handler",
                config_extra={"runtime": {"image_id": image_id,
                                          "cpu_millicores": 500,
                                          "memory_mb": 512},
                              "env": {"BLOB_PATH": blob}})
            first = await stack.invoke(dep, {})
            ready_early = not os.path.exists(
                os.path.join(bundle, ".tpu9-complete"))
            read = await stack.invoke(dep, {"op": "read"})
            manifest = await stack._manifest_fetch(image_id)
            entry = next(e for e in manifest.files
                         if e.path == "env/f2.bin")
            chunks = []
            for c in entry.chunks:
                for w in stack.workers:
                    blob_data = await w.cache.client.get(c)
                    if blob_data is not None:
                        chunks.append(blob_data)
                        break
            want = hashlib.sha256(b"".join(chunks)).hexdigest()
            fill = next((w.cache.puller._fills[image_id]
                         for w in stack.workers
                         if image_id in w.cache.puller._fills), None)
            return first, read, want, ready_early, fill is not None

    first, read, want, ready_early, lazy_used = asyncio.run(run())
    assert first["size"] == 2097152
    assert first["uid"] == 65534          # containment stacked on top
    assert read["sha"] == want
    assert lazy_used, "pull did not go through the lazy path"


SECCOMP_PROBE_APP = """
import ctypes, os

libc = ctypes.CDLL(None, use_errno=True)

def try_sys(nr, *args):
    ctypes.set_errno(0)
    r = libc.syscall(ctypes.c_long(nr), *[ctypes.c_long(a) for a in args])
    return ctypes.get_errno() if r < 0 else 0

def handler(**kwargs):
    # x86_64 numbers: io_uring_setup=425 (off-list kernel surface),
    # unshare=272 (namespace escape vector)
    import subprocess, tempfile
    d = tempfile.mkdtemp()
    open(d + "/a", "w").write("x")
    mv_rc = subprocess.run(["mv", d + "/a", d + "/b"]).returncode
    return {"io_uring_errno": try_sys(425, 4, 0),
            "unshare_errno": try_sys(272, 0),
            "mv_rc": mv_rc,
            "pid": os.getpid()}
"""


def test_default_seccomp_is_allowlist(monkeypatch):
    """VERDICT r04 #2 'Done': an off-list syscall (io_uring_setup) fails
    EPERM inside the DEFAULT serving container — default-deny polarity —
    while the endpoint itself (python + asyncio + sockets) runs normally."""
    monkeypatch.setenv("TPU9_RUNTIME", "native")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    from tpu9.testing.localstack import LocalStack

    async def run():
        async with LocalStack() as stack:
            dep = await stack.deploy_endpoint(
                "seccomp-probe", {"app.py": SECCOMP_PROBE_APP},
                "app:handler")
            return await stack.invoke(dep, {})

    resp = asyncio.run(run())
    import errno
    assert resp["io_uring_errno"] == errno.EPERM, resp
    assert resp["unshare_errno"] == errno.EPERM, resp
    # coreutils `mv` uses renameat2 with ENOSYS-only fallback — the
    # allow-list must cover the *at family or everyday userland breaks
    assert resp["mv_rc"] == 0, resp
    assert resp["pid"] > 0


def test_seccomp_deny_fallback_mode(monkeypatch):
    """--seccomp-mode deny (legacy polarity, via TPU9_SECCOMP_MODE): the
    escape surface (unshare) still EPERMs but an off-list-yet-harmless
    syscall like io_uring_setup reaches the kernel (errno reflects its own
    arg validation — EFAULT/EINVAL/ENOSYS — never seccomp's EPERM)."""
    monkeypatch.setenv("TPU9_RUNTIME", "native")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("TPU9_SECCOMP_MODE", "deny")
    from tpu9.testing.localstack import LocalStack

    async def run():
        async with LocalStack() as stack:
            dep = await stack.deploy_endpoint(
                "seccomp-deny-probe", {"app.py": SECCOMP_PROBE_APP},
                "app:handler")
            return await stack.invoke(dep, {})

    resp = asyncio.run(run())
    import errno
    assert resp["unshare_errno"] == errno.EPERM, resp
    assert resp["io_uring_errno"] != errno.EPERM, resp
