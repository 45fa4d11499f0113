"""FUSE CacheFS (VERDICT r03 missing #5): read-through mounts whose page
faults stream chunks from the distributed cache — covering mmap and
static-binary readers the LD_PRELOAD shims cannot.

Reference analogue: pkg/cache/cachefs.go:47 (+ cachefs_node.go).
Root-gated: needs /dev/fuse and the t9cachefs binary.
"""

import asyncio
import hashlib
import mmap
import os

import pytest

from tpu9.cache import CacheClient, DiskStore
from tpu9.cache.fusefs import CacheFsManager
from tpu9.images.manifest import snapshot_dir

pytestmark = [
    pytest.mark.e2e,
    pytest.mark.skipif(not CacheFsManager.supported(),
                       reason="needs root + /dev/fuse + t9cachefs"),
]


async def _setup(short_tmp, populate_store: bool):
    """A manifest over a small tree; chunks live either in the local store
    (warm) or only behind the client's source fn (cold → fault path)."""
    src = short_tmp / "src"
    (src / "sub").mkdir(parents=True)
    big = os.urandom(5 * 1024 * 1024 + 333)       # spans chunks
    (src / "sub" / "weights.bin").write_bytes(big)
    (src / "hello.txt").write_bytes(b"hi fuse\n")
    os.symlink("hello.txt", src / "link.txt")

    origin: dict[str, bytes] = {}
    manifest = snapshot_dir(str(src), chunk_bytes=2 * 1024 * 1024,
                            put_chunk=lambda d, h: origin.__setitem__(h, d))
    manifest.image_id = "cfs-test"

    store = DiskStore(str(short_tmp / "store"))

    async def peers():
        return []

    async def source(digest):
        return origin.get(digest)

    client = CacheClient(store, peers, source=source)
    if populate_store:
        for h, d in origin.items():
            await store.put(d, h)
    return manifest, client, big


async def test_warm_mount_reads_and_mmap(short_tmp):
    manifest, client, big = await _setup(short_tmp, populate_store=True)
    mgr = CacheFsManager(client, str(short_tmp / "fuse"))
    mnt = str(short_tmp / "mnt")
    mount = await mgr.mount(manifest, mnt)
    try:
        assert sorted(os.listdir(mnt)) == ["hello.txt", "link.txt", "sub"]
        assert open(os.path.join(mnt, "hello.txt"), "rb").read() \
            == b"hi fuse\n"
        assert os.readlink(os.path.join(mnt, "link.txt")) == "hello.txt"
        p = os.path.join(mnt, "sub", "weights.bin")
        assert os.path.getsize(p) == len(big)
        data = open(p, "rb").read()
        assert hashlib.sha256(data).hexdigest() \
            == hashlib.sha256(big).hexdigest()
        # mmap — the reader class LD_PRELOAD fundamentally cannot gate
        with open(p, "rb") as f:
            mm = mmap.mmap(f.fileno(), 0, prot=mmap.PROT_READ)
            assert mm[2 * 1024 * 1024 - 5:2 * 1024 * 1024 + 5] \
                == big[2 * 1024 * 1024 - 5:2 * 1024 * 1024 + 5]
            mm.close()
        assert mount.stats["faults"] == 0      # everything was local
    finally:
        await mgr.close()


async def test_cold_mount_faults_chunks_through_cache(short_tmp):
    """Chunks absent from the local store: reads must fault them in via
    the socket → CacheClient → source, then succeed with correct bytes."""
    manifest, client, big = await _setup(short_tmp, populate_store=False)
    mgr = CacheFsManager(client, str(short_tmp / "fuse"))
    mnt = str(short_tmp / "mnt")
    mount = await asyncio.wait_for(mgr.mount(manifest, mnt), 30)
    try:
        p = os.path.join(mnt, "sub", "weights.bin")

        # faulted reads must run OFF the event loop: this test process
        # hosts the fault server, and a blocking read on the loop thread
        # would deadlock it (production readers are tenant processes)
        def read_head():
            with open(p, "rb") as f:
                return f.read(100)

        head = await asyncio.wait_for(asyncio.to_thread(read_head), 30)
        assert head == big[:100]
        assert mount.stats["faults"] >= 1
        first_faults = mount.stats["faults"]
        # full read faults the rest and matches
        data = await asyncio.wait_for(
            asyncio.to_thread(lambda: open(p, "rb").read()), 30)
        assert data == big
        assert mount.stats["faults"] >= first_faults
        assert mount.stats["fault_failures"] == 0
        # read-through populated the store with the READ file's chunks
        # (untouched files stay cold — that's the point of on-demand)
        weights = next(e for e in manifest.files
                       if e.path.endswith("weights.bin"))
        for digest in weights.chunks:
            assert client.store.has(digest), digest
    finally:
        await mgr.close()


async def test_missing_chunk_is_eio_not_zeros(short_tmp):
    """A chunk nobody can produce must fail the read loudly — never
    silently serve placeholder zeros."""
    manifest, client, _ = await _setup(short_tmp, populate_store=False)

    async def broken_source(digest):
        return None

    client.source = broken_source
    mgr = CacheFsManager(client, str(short_tmp / "fuse"))
    mnt = str(short_tmp / "mnt")
    mount = await mgr.mount(manifest, mnt)
    try:
        def read_all():
            return open(os.path.join(mnt, "sub", "weights.bin"),
                        "rb").read()

        with pytest.raises(OSError):
            await asyncio.wait_for(asyncio.to_thread(read_all), 30)
        assert mount.stats["fault_failures"] >= 1
    finally:
        await mgr.close()


async def test_lazy_oci_bundle_is_fuse_mounted(short_tmp):
    """OCI rootfs manifests ≥ the lazy threshold become FUSE mounts (the
    overlay lowerdir streams on demand) instead of eager materialization —
    closing the 'OCI images stay eager' gap."""
    import shutil

    from tpu9.images.manifest import snapshot_dir
    from tpu9.images.puller import ImagePuller

    src = short_tmp / "tree"
    (src / "rootfs" / "usr").mkdir(parents=True)
    payload = os.urandom(3 * 1024 * 1024)
    (src / "rootfs" / "usr" / "big.bin").write_bytes(payload)

    origin: dict[str, bytes] = {}
    manifest = snapshot_dir(str(src), chunk_bytes=1024 * 1024,
                            put_chunk=lambda d, h: origin.__setitem__(h, d))
    manifest.image_id = "img-ocilazy"
    manifest.kind = "oci"
    manifest.env = {"FROM_IMAGE": "1"}

    store = DiskStore(str(short_tmp / "store"))

    async def peers():
        return []

    async def source(digest):
        return origin.get(digest)

    client = CacheClient(store, peers, source=source)
    mgr = CacheFsManager(client, str(short_tmp / "fuse"))
    puller = ImagePuller(client, str(short_tmp / "bundles"),
                         lazy_threshold=1024 * 1024, fusefs=mgr)

    bundle = await puller.pull("img-ocilazy", manifest=manifest)
    try:
        assert "img-ocilazy" in puller._fuse_mounts
        # the lifecycle's metadata probe works inside the mount
        import json
        meta = json.load(open(os.path.join(bundle, ".tpu9-env.json")))
        assert meta["kind"] == "oci" and meta["env"]["FROM_IMAGE"] == "1"
        # overlay over the FUSE lowerdir: the exact shape NativeRuntime
        # mounts for OCI bundles (rootfs as lowerdir)
        lower = os.path.join(bundle, "rootfs")
        upper, work, merged = (str(short_tmp / d) for d in
                               ("up", "wk", "mg"))
        for d in (upper, work, merged):
            os.makedirs(d)
        import subprocess
        rc = subprocess.run(
            ["mount", "-t", "overlay", "overlay", "-o",
             f"lowerdir={lower},upperdir={upper},workdir={work}", merged],
            capture_output=True, text=True)
        assert rc.returncode == 0, rc.stderr
        try:
            def read_all():
                return open(os.path.join(merged, "usr", "big.bin"),
                            "rb").read()

            data = await asyncio.wait_for(asyncio.to_thread(read_all), 30)
            assert data == payload            # faulted through the cache
            with open(os.path.join(merged, "usr", "scratch"), "wb") as f:
                f.write(b"upper-write")       # writes land in upper
        finally:
            subprocess.run(["umount", merged], capture_output=True)
        # second pull of a mounted image is a refcount, not a remount
        again = await puller.pull("img-ocilazy", manifest=manifest)
        assert again == bundle
        # gc must not rmtree a live mount
        await puller.gc(keep=0)
        assert os.path.exists(os.path.join(bundle, ".tpu9-env.json"))
    finally:
        await puller.close()
        shutil.rmtree(str(short_tmp / "bundles"), ignore_errors=True)
