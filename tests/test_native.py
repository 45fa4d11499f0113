"""Native C++ components: vcache LD_PRELOAD shim and t9proc supervisor.

Builds via make (g++ baked into the image); tests drive the real binaries.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

pytestmark = pytest.mark.skipif(shutil.which("g++") is None,
                                reason="no C++ toolchain")


def test_vcache_redirects_cached_reads(built, tmp_path):
    vol = tmp_path / "volumes" / "models"
    cache = tmp_path / "cache" / "models"
    vol.mkdir(parents=True)
    cache.mkdir(parents=True)
    (vol / "weights.bin").write_text("SLOW-ORIGINAL")
    (cache / "weights.bin").write_text("FAST-CACHED")
    (vol / "uncached.txt").write_text("ONLY-IN-VOLUME")

    stats = tmp_path / "stats.jsonl"
    env = dict(os.environ)
    env.update({
        "LD_PRELOAD": os.path.join(built, "vcache_preload.so"),
        "TPU9_VCACHE_MAP": f"{vol}={cache}",
        "TPU9_VCACHE_STATS": str(stats),
    })
    code = (
        f"data = open({str(vol / 'weights.bin')!r}).read()\n"
        f"other = open({str(vol / 'uncached.txt')!r}).read()\n"
        "print(data); print(other)\n"
        # writes must NOT be redirected
        f"open({str(vol / 'new.txt')!r}, 'w').write('NEW')\n"
    )
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=30)
    assert out.returncode == 0, out.stderr
    lines = out.stdout.strip().splitlines()
    assert lines[0] == "FAST-CACHED"          # cached read redirected
    assert lines[1] == "ONLY-IN-VOLUME"       # miss falls through
    assert (vol / "new.txt").read_text() == "NEW"   # write hit the volume
    assert not (cache / "new.txt").exists()
    stat = json.loads(stats.read_text().splitlines()[-1])
    assert stat["hits"] >= 1 and stat["misses"] >= 1


def test_t9proc_spawn_reap_signal(built):
    proc = subprocess.Popen([os.path.join(built, "t9proc")],
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                            text=True, bufsize=1)
    try:
        events = []

        def read_until(kind, limit=50):
            for _ in range(limit):
                line = proc.stdout.readline()
                if not line:
                    break
                e = json.loads(line)
                events.append(e)
                if e.get("event") == kind:
                    return e
            raise AssertionError(f"never saw {kind}: {events}")

        assert read_until("ready")["pid"] == proc.pid

        proc.stdin.write(json.dumps(
            {"op": "spawn", "id": "t1",
             "argv": ["sh", "-c", "echo hello-from-t9proc"]}) + "\n")
        spawned = read_until("spawned")
        assert spawned["id"] == "t1" and spawned["pid"] > 0
        out = read_until("stdout")
        import base64
        assert b"hello-from-t9proc" in base64.b64decode(out["data_b64"])
        assert read_until("exit")["code"] == 0

        # long-running child + signal
        proc.stdin.write(json.dumps(
            {"op": "spawn", "id": "t2", "argv": ["sleep", "30"]}) + "\n")
        read_until("spawned")
        proc.stdin.write(json.dumps({"op": "list"}) + "\n")
        listing = read_until("list")
        assert [p["id"] for p in listing["procs"]] == ["t2"]
        proc.stdin.write(json.dumps(
            {"op": "signal", "id": "t2", "signum": 9}) + "\n")
        read_until("signaled")
        assert read_until("exit")["code"] == 137   # 128 + SIGKILL

        proc.stdin.write(json.dumps({"op": "shutdown"}) + "\n")
        proc.stdin.close()
        assert proc.wait(timeout=10) == 0
    finally:
        proc.kill()


def test_t9cdi_spec_generation(built, tmp_path):
    """CDI spec generator (reference: nvidia-ctk CDI generation,
    pkg/worker/nvidia.go:92-203): enumerate a fake /dev tree, validate the
    emitted CDI v0.6.0 JSON shape."""
    dev = tmp_path / "dev"
    (dev / "vfio").mkdir(parents=True)
    for i in range(4):
        (dev / f"accel{i}").write_bytes(b"")
    (dev / "vfio" / "0").write_bytes(b"")
    (dev / "accelerators").mkdir()       # non-numeric suffix: ignored
    libtpu = tmp_path / "libtpu.so"
    libtpu.write_bytes(b"\x7fELF")

    out = tmp_path / "tpu9.json"
    rc = subprocess.run(
        [os.path.join(built, "t9cdi"), "--dev-root", str(dev),
         "--libtpu", str(libtpu), "--out", str(out)],
        capture_output=True, text=True)
    assert rc.returncode == 0, rc.stderr
    assert "4 chips, 1 vfio groups" in rc.stderr

    spec = json.loads(out.read_text())
    assert spec["cdiVersion"] == "0.6.0"
    assert spec["kind"] == "tpu9.dev/accel"
    names = [d["name"] for d in spec["devices"]]
    assert names == ["0", "1", "2", "3", "all"]
    dev0 = spec["devices"][0]["containerEdits"]
    assert dev0["deviceNodes"] == [{"path": str(dev / "accel0")}]
    assert "TPU_VISIBLE_CHIPS=0" in dev0["env"]
    alld = spec["devices"][-1]["containerEdits"]
    node_paths = {n["path"] for n in alld["deviceNodes"]}
    assert str(dev / "accel3") in node_paths
    assert str(dev / "vfio" / "0") in node_paths
    assert "TPU_VISIBLE_CHIPS=0,1,2,3" in alld["env"]
    assert alld["mounts"][0]["hostPath"] == str(libtpu)
    assert alld["mounts"][0]["containerPath"] == "/usr/lib/libtpu.so"


def test_t9cdi_sparse_and_vfio_only_hosts(built, tmp_path):
    """Chip ids come from the node suffix (a failed chip must not shift
    the id↔node mapping); vfio-only hosts still enumerate; zero devices
    is a refusal, not an empty spec."""
    # sparse: accel0 + accel2 (chip 1 failed)
    dev = tmp_path / "sparse"
    dev.mkdir()
    (dev / "accel0").write_bytes(b"")
    (dev / "accel2").write_bytes(b"")
    rc = subprocess.run([os.path.join(built, "t9cdi"),
                         "--dev-root", str(dev)],
                        capture_output=True, text=True)
    spec = json.loads(rc.stdout)
    names = [d["name"] for d in spec["devices"]]
    assert names == ["0", "2", "all"]
    dev2 = next(d for d in spec["devices"] if d["name"] == "2")
    assert dev2["containerEdits"]["deviceNodes"][0]["path"] \
        == str(dev / "accel2")
    alld = spec["devices"][-1]["containerEdits"]
    assert "TPU_VISIBLE_CHIPS=0,2" in alld["env"]
    assert "TPU_CHIPS_PER_PROCESS_BOUNDS=1,2,1" in alld["env"]

    # vfio-only
    dev = tmp_path / "vfio-only"
    (dev / "vfio").mkdir(parents=True)
    for i in range(4):
        (dev / "vfio" / str(i)).write_bytes(b"")
    rc = subprocess.run([os.path.join(built, "t9cdi"),
                         "--dev-root", str(dev)],
                        capture_output=True, text=True)
    spec = json.loads(rc.stdout)
    assert len(spec["devices"]) == 5          # 4 chips + all
    alld = spec["devices"][-1]["containerEdits"]
    assert "TPU_VISIBLE_CHIPS=0,1,2,3" in alld["env"]
    assert "TPU_CHIPS_PER_PROCESS_BOUNDS=2,2,1" in alld["env"]

    # empty host: refuse loudly
    empty = tmp_path / "none"
    empty.mkdir()
    rc = subprocess.run([os.path.join(built, "t9cdi"),
                         "--dev-root", str(empty)],
                        capture_output=True, text=True)
    assert rc.returncode == 2
    assert "refusing" in rc.stderr


def _unskipped(root: str) -> list[str]:
    """The tests of test_native_runtime.py that a run from ``root`` would
    not skip: ``--setup-plan`` evaluates the ``skipif`` marks and lists
    the rest without running them."""
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/test_native_runtime.py",
         "--setup-plan", "-q", "-p", "no:cacheprovider", "-p", "no:xdist"],
        cwd=root, capture_output=True, text=True, timeout=300)
    assert out.returncode in (0, 5), out.stdout + out.stderr
    return sorted(ln.split()[0] for ln in out.stdout.splitlines()
                  if ln.strip().startswith("tests/test_native_runtime.py::"))


def test_collection_does_not_depend_on_a_left_over_build(built, tmp_path):
    """The count of tier-1 is a function of the commit: a checkout that no
    run has built yet (``native/build/`` is git-ignored) selects the same
    tests as a built tree, because ``pytest_configure`` builds before the
    ``skipif(not NativeRuntime.supported())`` marks are evaluated."""
    from tpu9.runtime import NativeRuntime
    if not NativeRuntime.supported():
        pytest.skip("needs root + ip: the marks skip on either tree")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fresh = tmp_path / "checkout"
    (fresh / "tests").mkdir(parents=True)
    shutil.copytree(os.path.join(repo, "native"), fresh / "native",
                    ignore=shutil.ignore_patterns("build"))
    for name in ("conftest.py", "test_native_runtime.py"):
        shutil.copy(os.path.join(repo, "tests", name), fresh / "tests")
    shutil.copy(os.path.join(repo, "pyproject.toml"), fresh)
    # the package by link: native_binary() resolves under the checkout
    # the package is imported from, and abspath keeps the link
    os.symlink(os.path.join(repo, "tpu9"), fresh / "tpu9")
    assert not (fresh / "native" / "build").exists()
    here = _unskipped(repo)
    assert len(here) >= 8, here
    assert _unskipped(str(fresh)) == here
    assert (fresh / "native" / "build" / "t9container").exists()
