"""CacheFS read-through volume mounts with overlay write-back (VERDICT
r04 #5): a container must be READY before a multi-GB volume is local,
reads must fault exactly the chunks touched, and writes must persist to
the object store on exit.

Reference analogue: per-workspace S3 FUSE mounts
(``/root/reference/pkg/storage/storage.go:24-31``,
``pkg/worker/storage_manager.go:36``).
Root-gated: needs /dev/fuse + the t9cachefs binary + overlayfs.
"""

import asyncio
import hashlib
import os
import sys
import time

import aiohttp
import pytest

from tpu9.cache.fusefs import CacheFsManager
from tpu9.config import AppConfig, WorkerConfig
from tpu9.gateway import Gateway
from tpu9.statestore import MemoryStore

pytestmark = [
    pytest.mark.e2e,
    pytest.mark.skipif(not CacheFsManager.supported(),
                       reason="needs root + /dev/fuse + t9cachefs"),
]


def _cfg(tmp_path) -> AppConfig:
    cfg = AppConfig()
    cfg.gateway.http_port = 0
    cfg.gateway.state_port = 0
    cfg.database.path = ":memory:"
    cfg.storage.local_root = str(tmp_path / "ws")
    cfg.image.registry_dir = str(tmp_path / "registry")
    return cfg


async def test_volume_cachefs_mount_reads_and_writes_back(short_tmp):
    from tpu9.cache import CacheClient, DiskStore
    from tpu9.images.manifest import ImageManifest
    from tpu9.repository import ContainerRepository
    from tpu9.runtime import ProcessRuntime
    from tpu9.storage.volmount import VolumeMounter
    from tpu9.types import ContainerRequest, Mount
    from tpu9.worker.lifecycle import ContainerLifecycle
    from tpu9.worker.tpu_manager import TpuDeviceManager

    gw = Gateway(_cfg(short_tmp), store=MemoryStore())
    await gw.start()
    base_url = f"http://127.0.0.1:{gw.port}"
    ws_id = gw.default_workspace.workspace_id
    # a "big" dataset volume: 24 MiB spans several 4 MiB chunks
    payload = os.urandom(24 * 1024 * 1024)
    await gw.volume_files.write(ws_id, "data", "big/dataset.bin", payload)
    await gw.volume_files.write(ws_id, "data", "README", b"hello volume")

    session = aiohttp.ClientSession(
        headers={"Authorization": f"Bearer {gw.worker_token}"})

    async def volume_manifest(workspace_id, name):
        async with session.get(
                f"{base_url}/rpc/internal/volume/"
                f"{workspace_id}/{name}/manifest") as resp:
            if resp.status != 200:
                return None
            return ImageManifest.from_json(await resp.text())

    pushed = []

    async def volume_push(workspace_id, name, local_dir):
        for dirpath, _dirs, files in os.walk(local_dir):
            for fn in files:
                full = os.path.join(dirpath, fn)
                rel = os.path.relpath(full, local_dir).replace(os.sep, "/")
                with open(full, "rb") as f:
                    await gw.volume_files.write(workspace_id, name, rel,
                                                f.read())
                pushed.append(rel)

    # worker-side cache whose SOURCE is the gateway chunk endpoint — the
    # same fetch path a cross-host worker uses
    async def source(digest):
        async with session.get(
                f"{base_url}/rpc/image/chunk/{digest}") as resp:
            return await resp.read() if resp.status == 200 else None

    async def peers():
        return []

    store = DiskStore(str(short_tmp / "chunkstore"))
    client = CacheClient(store, peers, source=source)
    fusefs = CacheFsManager(client, str(short_tmp / "fuse"))
    mounter = VolumeMounter(fusefs, volume_manifest, volume_push,
                            str(short_tmp / "volmounts"),
                            min_bytes=1024 * 1024)

    cfg = WorkerConfig(containers_dir=str(short_tmp / "c"),
                       storage_root=str(short_tmp / "unshared"),
                       storage_shared=False)
    lc = ContainerLifecycle(
        "w1", cfg, ProcessRuntime(base_dir=cfg.containers_dir),
        ContainerRepository(MemoryStore()), TpuDeviceManager())
    lc.volmount = mounter
    lc.volume_push = volume_push

    app = (
        "import hashlib, os, time\n"
        "t0 = time.time()\n"
        "sha = hashlib.sha256(\n"
        "    open('vol/data/big/dataset.bin', 'rb').read()).hexdigest()\n"
        "open('vol/data/result.txt', 'w').write(\n"
        "    sha + ' ' + open('vol/data/README').read())\n")
    req = ContainerRequest(
        container_id="c-volmnt", stub_id="s", workspace_id=ws_id,
        stub_type="pod",
        entrypoint=[sys.executable, "-c", app],
        mounts=[Mount(source="data", target="/vol/data", kind="volume")])

    try:
        t0 = time.perf_counter()
        await lc.run_container(req)
        start_s = time.perf_counter() - t0
        # READY fast: nothing of the 24 MiB was copied at start (the
        # mount is a manifest view) — generous bound for CI noise, the
        # real assertion is the fault counters below
        assert start_s < 20.0
        mounts = mounter._mounts.get("c-volmnt")
        assert mounts, "volume was synced, not CacheFS-mounted"
        cfs = mounts[0][2]

        await lc.runtime.wait("c-volmnt")
        for _ in range(200):              # supervisor runs unmount+push
            if "result.txt" in pushed:
                break
            await asyncio.sleep(0.05)
        assert "result.txt" in pushed, pushed
        # chunk-proven reads: the container's read faulted chunks through
        # the cache (cold store → every chunk came via the fault socket)
        assert cfs.stats["faults"] > 0, cfs.stats

        out = await gw.volume_files.read(ws_id, "data", "result.txt")
        want = hashlib.sha256(payload).hexdigest() + " hello volume"
        assert out is not None and out.decode() == want
        # ONLY the written file pushed back (overlay upper = the delta),
        # not a re-upload of the 24 MiB dataset
        assert "big/dataset.bin" not in pushed
        # the unmodified dataset is untouched in the store
        back = await gw.volume_files.read(ws_id, "data", "big/dataset.bin")
        assert back == payload
    finally:
        await mounter.close()
        await session.close()
        await gw.stop()


async def test_small_volume_falls_back_to_sync(tmp_path):
    """Below the size threshold the mounter declines and the existing
    sync-down path serves the volume (one copy beats FUSE round-trips)."""
    from tpu9.storage.volmount import VolumeMounter

    async def manifest_fetch(ws, name):
        from tpu9.images.manifest import FileEntry, ImageManifest
        m = ImageManifest(image_id="small", kind="env")
        m.files.append(FileEntry(path="x", mode=0o644, size=10,
                                 chunks=["d"]))
        m.total_bytes = 10
        return m

    mounter = VolumeMounter(object(), manifest_fetch, None,
                            str(tmp_path / "vm"), min_bytes=1024)
    assert await mounter.try_mount("ws", "vol", "c1") is None
