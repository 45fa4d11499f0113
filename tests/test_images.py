import os

import pytest

from tpu9.cache import CacheClient, DiskStore
from tpu9.images import ImageBuilder, ImageManifest, ImagePuller, ImageSpec
from tpu9.images.manifest import materialize, snapshot_dir


def test_spec_id_deterministic():
    a = ImageSpec(python_packages=["jax", "flax"], commands=["echo hi"])
    b = ImageSpec(python_packages=["jax", "flax"], commands=["echo hi"])
    c = ImageSpec(python_packages=["jax"])
    assert a.image_id == b.image_id != c.image_id


def test_snapshot_and_materialize_roundtrip(tmp_path):
    src = tmp_path / "src"
    (src / "sub").mkdir(parents=True)
    (src / "a.txt").write_bytes(b"A" * 10)
    big = os.urandom(3 * 1024 * 1024)
    (src / "sub" / "big.bin").write_bytes(big)
    os.chmod(src / "a.txt", 0o640)
    os.symlink("a.txt", src / "link.txt")

    chunks: dict[str, bytes] = {}
    manifest = snapshot_dir(str(src), chunk_bytes=1 << 20,
                            put_chunk=lambda d, h: chunks.__setitem__(h, d))
    assert manifest.total_bytes == 10 + len(big)
    big_entry = next(f for f in manifest.files if f.path.endswith("big.bin"))
    assert len(big_entry.chunks) == 3
    link = next(f for f in manifest.files if f.path == "link.txt")
    assert link.link_target == "a.txt"

    dest = tmp_path / "dest"
    materialize(manifest, str(dest), chunks.get)
    assert (dest / "a.txt").read_bytes() == b"A" * 10
    assert (dest / "sub" / "big.bin").read_bytes() == big
    assert oct((dest / "a.txt").stat().st_mode & 0o777) == "0o640"
    assert os.readlink(dest / "link.txt") == "a.txt"

    # manifest json roundtrip
    back = ImageManifest.from_json(manifest.to_json())
    assert back.manifest_hash == manifest.manifest_hash


async def test_builder_commands_and_dedupe(tmp_path):
    builder = ImageBuilder(str(tmp_path / "registry"))
    spec = ImageSpec(commands=["mkdir -p env && echo marker > env/file.txt"])
    logs = []
    m1 = await builder.build(spec, log_cb=logs.append)
    assert builder.has_image(spec.image_id)
    assert any("file.txt" in f.path for f in m1.files)
    # second build returns cached manifest without running commands
    m2 = await builder.build(spec)
    assert m2.manifest_hash == m1.manifest_hash


async def test_builder_failure_surfaces(tmp_path):
    from tpu9.images.builder import BuildError
    builder = ImageBuilder(str(tmp_path / "registry"))
    spec = ImageSpec(commands=["exit 3"])
    with pytest.raises(BuildError):
        await builder.build(spec)
    assert not builder.has_image(spec.image_id)


async def test_puller_end_to_end(tmp_path):
    builder = ImageBuilder(str(tmp_path / "registry"))
    spec = ImageSpec(commands=["mkdir -p env && echo data > env/x.txt"],
                     env={"IMGVAR": "1"})
    manifest = await builder.build(spec)

    store = DiskStore(str(tmp_path / "cache"))

    async def peers():
        return []

    async def source(digest):
        return builder.read_chunk(digest)

    client = CacheClient(store, peers, source=source)
    puller = ImagePuller(client, str(tmp_path / "bundles"))
    bundle = await puller.pull(spec.image_id, manifest=manifest)
    assert os.path.exists(os.path.join(bundle, "env", "x.txt"))
    assert os.path.exists(os.path.join(bundle, ".tpu9-env.json"))
    # second pull is a no-op fast path
    bundle2 = await puller.pull(spec.image_id, manifest=manifest)
    assert bundle2 == bundle
    await client.close()


# ---------------------------------------------------------------------------
# lazy materialization (VERDICT r03 #3: containers start while images stream)
# ---------------------------------------------------------------------------

def _make_cache(tmp_path, builder):
    store = DiskStore(str(tmp_path / "cache"))

    async def peers():
        return []

    async def source(digest):
        return builder.read_chunk(digest)

    return CacheClient(store, peers, source=source)


async def test_lazy_pull_skeleton_then_fill(short_tmp):
    import asyncio
    import hashlib

    from tpu9.images.builder import ImageBuilder

    builder = ImageBuilder(str(short_tmp / "registry"))
    spec = ImageSpec(commands=[
        "mkdir -p env && for i in 1 2 3 4; do "
        "head -c 2097152 /dev/urandom > env/f$i.bin; done "
        "&& echo small > env/tiny.txt && ln -s tiny.txt env/link.txt"])
    manifest = await builder.build(spec)
    client = _make_cache(short_tmp, builder)
    puller = ImagePuller(client, str(short_tmp / "bundles"),
                         lazy_threshold=1)   # force lazy

    bundle = await puller.pull(spec.image_id, manifest=manifest)
    fill = puller.active_fill(spec.image_id)

    # skeleton contract: stat-correct tree before the bytes arrive
    f1 = os.path.join(bundle, "env", "f1.bin")
    assert os.path.getsize(f1) == 2097152
    assert os.readlink(os.path.join(bundle, "env", "link.txt")) == "tiny.txt"
    assert os.path.exists(os.path.join(bundle, ".tpu9-env.json"))
    assert os.path.exists(os.path.join(bundle, ".tpu9-lazy"))

    # fault one file on demand through the socket protocol
    if fill is not None and not fill.complete:
        reader, writer = await asyncio.open_unix_connection(
            puller.lazy_sock(spec.image_id))
        writer.write(f"REQ {f1}\n".encode())
        await writer.drain()
        assert (await reader.readline()).strip() == b"OK"
        writer.close()
        entry = next(e for e in manifest.files if e.path == "env/f1.bin")
        got = hashlib.sha256(open(f1, "rb").read()).hexdigest()
        want = hashlib.sha256(
            b"".join(builder.read_chunk(c) for c in entry.chunks)).hexdigest()
        assert got == want

    # background fill completes and publishes the marker
    if fill is not None:
        await asyncio.wait_for(fill.wait(), 60)
    assert os.path.exists(os.path.join(bundle, ".tpu9-complete"))
    assert not os.path.exists(os.path.join(bundle, ".tpu9-lazy"))
    for e in manifest.files:
        if e.link_target:
            continue
        data = open(os.path.join(bundle, e.path), "rb").read()
        want = b"".join(builder.read_chunk(c) for c in e.chunks)
        assert data == want, f"content mismatch for {e.path}"
    await puller.close()
    await client.close()


async def test_lazy_pull_restarts_after_crash(short_tmp):
    """No completion marker on disk → the next pull must re-skeleton and
    refill rather than trusting half-written placeholders."""
    from tpu9.images.builder import ImageBuilder

    builder = ImageBuilder(str(short_tmp / "registry"))
    spec = ImageSpec(commands=["mkdir -p env && echo hello > env/a.txt"])
    manifest = await builder.build(spec)
    client = _make_cache(short_tmp, builder)

    # simulate a crashed fill: placeholders present, no marker
    dest = os.path.join(str(short_tmp / "bundles"), spec.image_id)
    os.makedirs(os.path.join(dest, "env"), exist_ok=True)
    with open(os.path.join(dest, "env", "a.txt"), "wb") as f:
        f.truncate(6)

    puller = ImagePuller(client, str(short_tmp / "bundles"),
                         lazy_threshold=1)
    bundle = await puller.pull(spec.image_id, manifest=manifest)
    fill = puller.active_fill(spec.image_id)
    if fill is not None:
        import asyncio
        await asyncio.wait_for(fill.wait(), 30)
    assert open(os.path.join(bundle, "env", "a.txt")).read() == "hello\n"
    await puller.close()
    await client.close()


async def test_small_image_stays_eager(tmp_path):
    from tpu9.images.builder import ImageBuilder

    builder = ImageBuilder(str(tmp_path / "registry"))
    spec = ImageSpec(commands=["mkdir -p env && echo tiny > env/t.txt"])
    manifest = await builder.build(spec)
    client = _make_cache(tmp_path, builder)
    puller = ImagePuller(client, str(tmp_path / "bundles"))  # default 64 MB
    bundle = await puller.pull(spec.image_id, manifest=manifest)
    assert puller.active_fill(spec.image_id) is None
    assert os.path.exists(os.path.join(bundle, ".tpu9-complete"))
    await client.close()


def test_manifest_path_traversal_rejected(tmp_path):
    """Advisor r04: manifests can arrive over the wire and every writer
    (materialize / lazy skeleton / lazy fill) runs as root — entries that
    escape the bundle via '..' or a symlinked parent must be refused."""
    from tpu9.images.manifest import FileEntry, ImageManifest, safe_join

    dest = tmp_path / "bundle"
    dest.mkdir()
    for bad in ("../evil", "/abs/evil", "a/../../evil", ""):
        with pytest.raises(ValueError):
            safe_join(str(dest), bad)
    assert safe_join(str(dest), "ok/fine.txt").startswith(str(dest))

    # symlinked parent: entry 'out' links outside dest; 'out/x' must not
    # write through it
    outside = tmp_path / "outside"
    outside.mkdir()
    m = ImageManifest(image_id="evil", kind="env", files=[
        FileEntry(path="out", mode=0o777, size=0,
                  link_target=str(outside)),
        FileEntry(path="out/x", mode=0o644, size=4, chunks=["d1"]),
    ])
    with pytest.raises(ValueError):
        materialize(m, str(dest), {"d1": b"evil"}.get)
    assert not (outside / "x").exists()


def test_safe_join_second_pass_with_symlinks(tmp_path):
    """Review regression: safe_join must NOT resolve through the final
    component — an absolute-target venv-style symlink ('bin/python' ->
    /usr/bin/python3) exists after the first pass, and resume
    (_ensure_tree / re-materialize) must see the LINK path, not its
    resolved target, or every second pass over the bundle fails."""
    from tpu9.images.manifest import FileEntry, ImageManifest, safe_join

    dest = tmp_path / "bundle"
    m = ImageManifest(image_id="venv", kind="env", files=[
        FileEntry(path="bin/python", mode=0o777, size=0,
                  link_target="/usr/bin/python3"),
        FileEntry(path="link.cfg", mode=0o777, size=0,
                  link_target="real.cfg"),
        FileEntry(path="real.cfg", mode=0o644, size=2, chunks=["c1"]),
    ])
    chunks = {"c1": b"ok"}
    materialize(m, str(dest), chunks.get)
    # second pass over the same tree: must not raise and must address the
    # link itself
    materialize(m, str(dest), chunks.get)
    assert os.readlink(dest / "bin" / "python") == "/usr/bin/python3"
    assert safe_join(str(dest), "link.cfg").endswith("/link.cfg")
    assert (dest / "real.cfg").read_bytes() == b"ok"


def test_symlink_then_file_entry_cannot_write_through(tmp_path):
    """Round-5 review (high): a hostile manifest pairing a symlink entry
    with a SAME-PATH file entry must not write (or chmod) through the
    link as root — O_NOFOLLOW writers refuse the swapped-in link."""
    from tpu9.images.manifest import FileEntry, ImageManifest

    victim = tmp_path / "victim.txt"
    victim.write_text("precious")
    dest = tmp_path / "bundle"
    m = ImageManifest(image_id="evil2", kind="env", files=[
        FileEntry(path="x", mode=0o777, size=0,
                  link_target=str(victim)),
        FileEntry(path="x", mode=0o666, size=4, chunks=["d1"]),
    ])
    try:
        materialize(m, str(dest), {"d1": b"evil"}.get)
    except OSError:
        pass                              # refusing loudly is acceptable
    assert victim.read_text() == "precious"
    assert oct(victim.stat().st_mode & 0o777) != "0o666"

    # the lazy skeleton writer takes the same O_NOFOLLOW path
    from tpu9.images.lazy import LazyFill

    fill = LazyFill(m, str(tmp_path / "bundle2"), None,
                    str(tmp_path / "fill.sock"))
    try:
        fill._write_skeleton()
    except OSError:
        pass                              # refusing loudly is acceptable
    assert victim.read_text() == "precious"
    assert oct(victim.stat().st_mode & 0o777) != "0o666"
