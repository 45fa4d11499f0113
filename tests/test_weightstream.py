"""Weight-streaming restore path (ISSUE 1): the `.tpu9w` format, the
double-buffered shard pipeline, the warm weights pool, hedged peer reads,
and the CheckpointManager fast path that ties them together."""

import asyncio
import inspect
import os
import threading
import time

import numpy as np
import pytest

from tpu9.cache import CacheClient, DiskStore
from tpu9.cache.prefetch import Prefetcher
from tpu9.cache.store import chunk_hash
from tpu9.serving import weights as wfmt
from tpu9.statestore import wire
from tpu9.worker.checkpoint import CheckpointManager
from tpu9.worker.weightpool import WeightPool
from tpu9.worker.weightstream import stream_shards


# ---------------------------------------------------------------------------
# .tpu9w format
# ---------------------------------------------------------------------------

def _tree():
    rng = np.random.default_rng(7)
    return {"embed": rng.standard_normal((32, 16)).astype(np.float32),
            "layers": [{"w": rng.standard_normal((16, 16)).astype(np.float32),
                        "scale": np.float32(0.5)} for _ in range(3)],
            "step": 42, "name": "m", "flag": True, "none": None}


def _assert_tree_equal(a, b):
    if isinstance(a, dict):
        assert set(a) == set(b)
        for k in a:
            _assert_tree_equal(a[k], b[k])
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            _assert_tree_equal(x, y)
    elif isinstance(a, np.ndarray) or hasattr(a, "shape") and a.shape != ():
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    else:
        # scalars ride the skeleton; np scalar leaves come back as arrays
        assert np.asarray(a) == np.asarray(b)


def test_weights_roundtrip(tmp_path):
    tree = _tree()
    dest = str(tmp_path / "m.tpu9w")
    index = wfmt.save_params(tree, dest)
    assert index["format"] == wfmt.FORMAT
    assert wfmt.is_weights_dir(dest)
    back = wfmt.load_params(dest)
    _assert_tree_equal(tree, back)
    # mmap load pages shards lazily but must read identical values
    _assert_tree_equal(tree, wfmt.load_params(dest, mmap=True))


def test_weights_scalars_ride_the_index(tmp_path):
    dest = str(tmp_path / "s.tpu9w")
    index = wfmt.save_params({"lr": 0.1, "steps": 10, "w": np.ones(4)}, dest)
    # only the array leaf became a shard
    assert len(index["leaves"]) == 1
    back = wfmt.load_params(dest)
    assert back["lr"] == 0.1 and back["steps"] == 10


def test_weight_group_recognition():
    assert wfmt.weight_group_of("ck/params.tpu9w/000000.bin") \
        == "ck/params.tpu9w"
    assert wfmt.weight_group_of("ck/params.tpu9w/index.json") \
        == "ck/params.tpu9w"
    assert wfmt.weight_group_of("ck/code/app.py") is None
    # a FILE merely named *.tpu9w is not a group (groups are directories)
    assert wfmt.weight_group_of("ck/params.tpu9w") is None


# ---------------------------------------------------------------------------
# stream_shards: double-buffered pipeline
# ---------------------------------------------------------------------------

def _shard_entries(arrays):
    return [{"i": i, "key": f"k{i}", "file": f"{i:06d}.bin",
             "dtype": a.dtype.name, "shape": list(a.shape),
             "nbytes": int(a.nbytes)} for i, a in enumerate(arrays)]


async def _chunks_of(arrays, chunk=4096, delay=0.0):
    for a in arrays:
        raw = a.tobytes()
        for off in range(0, len(raw), chunk):
            if delay:
                await asyncio.sleep(delay)
            part = raw[off:off + chunk]
            yield chunk_hash(part), part


async def test_stream_shards_reassembles_in_order():
    rng = np.random.default_rng(0)
    arrays = [rng.standard_normal(1024).astype(np.float32)
              for _ in range(4)]
    out, st = await stream_shards(_shard_entries(arrays),
                                  _chunks_of(arrays),
                                  consume=lambda e, a: a.copy())
    assert st["shards"] == 4
    assert st["bytes"] == sum(a.nbytes for a in arrays)
    for want, got in zip(arrays, out):
        np.testing.assert_array_equal(want, got)


async def test_stream_shards_truncated_stream_raises():
    arrays = [np.ones(256, np.float32)]
    entries = _shard_entries(arrays)
    entries[0]["nbytes"] *= 2          # expect more bytes than arrive

    with pytest.raises(IOError, match="ended early"):
        await stream_shards(entries, _chunks_of(arrays),
                            consume=lambda e, a: a)


async def test_stream_shards_missing_chunk_raises():
    async def chunks():
        yield "deadbeef", None

    with pytest.raises(IOError, match="missing chunk"):
        await stream_shards(_shard_entries([np.ones(8, np.float32)]),
                            chunks(), consume=lambda e, a: a)


async def test_streamed_restore_overlaps_fetch_and_device_put():
    """The acceptance proof: with an injected slow fetch and slow
    device-put, streamed wall-clock must be BELOW the sum of the two
    phases — fetch of shard i+1 overlaps the device transfer of shard i."""
    n, fetch_d, put_d = 6, 0.04, 0.04
    arrays = [np.full(64, i, np.float32) for i in range(n)]

    def slow_put(entry, arr):
        time.sleep(put_d)               # runs in a worker thread
        return arr

    t0 = time.perf_counter()
    out, st = await stream_shards(
        _shard_entries(arrays),
        _chunks_of(arrays, chunk=1 << 20, delay=fetch_d),
        consume=slow_put)
    wall = time.perf_counter() - t0
    serial = n * (fetch_d + put_d)
    assert wall < serial * 0.8, (wall, serial, st)
    # blocked-on-consumer time is a fraction of total consumer work —
    # the other shards' puts ran while the loop fetched
    assert st["put_s"] < n * put_d * 0.7, st
    for want, got in zip(arrays, out):
        np.testing.assert_array_equal(want, got)


# ---------------------------------------------------------------------------
# warm weights pool
# ---------------------------------------------------------------------------

def _entry(mb: int):
    return {"leaves": []}, [np.zeros(mb << 20, np.uint8)]


def test_weight_pool_lru_eviction_under_byte_cap():
    pool = WeightPool(max_bytes=10 << 20)
    for key, mb in (("a", 4), ("b", 4), ("c", 4)):
        idx, arrs = _entry(mb)
        assert pool.put(key, idx, arrs)
    # inserting c (4 MiB) over the 10 MiB cap evicted LRU "a"
    assert pool.get("a") is None
    assert pool.get("b") is not None and pool.get("c") is not None
    assert pool.used_bytes <= pool.max_bytes
    assert pool.stats["evictions"] == 1

    # the gets above touched b then c, so b is now LRU; d evicts b
    idx, arrs = _entry(4)
    pool.put("d", idx, arrs)
    assert pool.get("b") is None and pool.get("c") is not None


def test_weight_pool_rejects_oversize_group():
    pool = WeightPool(max_bytes=1 << 20)
    idx, arrs = _entry(2)
    assert not pool.put("huge", idx, arrs)
    assert pool.stats["rejected"] == 1 and len(pool) == 0


def test_weight_pool_refresh_same_key_keeps_one_copy():
    pool = WeightPool(max_bytes=64 << 20)
    idx, arrs = _entry(4)
    pool.put("k", idx, arrs)
    pool.put("k", idx, arrs)
    assert len(pool) == 1 and pool.used_bytes == arrs[0].nbytes
    snap = pool.snapshot()
    assert snap["inserts"] == 2 and snap["entries"] == 1


# ---------------------------------------------------------------------------
# Prefetcher close: no pending tasks / leaked fetches
# ---------------------------------------------------------------------------

async def test_prefetcher_close_mid_flight_leaves_nothing_pending():
    release = asyncio.Event()
    inflight: set = set()

    async def fetch(d):
        inflight.add(d)
        try:
            await release.wait()
            return d.encode()
        finally:
            inflight.discard(d)

    pf = Prefetcher(fetch, [f"d{i}" for i in range(10)], window=4)
    getter = asyncio.create_task(pf.get("d0"))
    await asyncio.sleep(0.02)
    assert len(inflight) == 4          # window filled, all blocked
    getter.cancel()                    # consumer aborts the restore
    await asyncio.gather(getter, return_exceptions=True)
    await pf.close()
    await asyncio.sleep(0)
    assert pf._tasks == {}
    assert not inflight, "close() left fetches running"
    # close is sticky: a racing get cannot re-open the read-ahead window
    release.set()
    assert await pf.get("d5") == b"d5"     # direct fetch still works
    assert pf._tasks == {}


# ---------------------------------------------------------------------------
# hedged peer reads
# ---------------------------------------------------------------------------

class FakePeer:
    """Wire-compatible chunk peer with injectable latency and payloads."""

    def __init__(self, data: dict, delay: float = 0.0):
        self.data = dict(data)
        self.delay = delay
        self.address = ""
        self.gets = 0
        self._server = None

    async def start(self) -> "FakePeer":
        self._server = await asyncio.start_server(self._handle,
                                                  "127.0.0.1", 0)
        port = self._server.sockets[0].getsockname()[1]
        self.address = f"127.0.0.1:{port}"
        return self

    async def stop(self) -> None:
        self._server.close()
        await self._server.wait_closed()

    async def _handle(self, reader, writer):
        try:
            while True:
                req = await wire.read_frame(reader)
                if req.get("op") == "get":
                    self.gets += 1
                    await asyncio.sleep(self.delay)
                    blob = self.data.get(req["hash"])
                    if blob is None:
                        writer.write(wire.pack({"ok": False}))
                    else:
                        writer.write(wire.pack({"ok": True,
                                                "len": len(blob)}))
                        writer.write(blob)
                    await writer.drain()
                elif req.get("op") == "put":
                    blob = await reader.readexactly(int(req["len"]))
                    self.data[req["hash"]] = blob
                    writer.write(wire.pack({"ok": True}))
                    await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionResetError,
                asyncio.CancelledError):
            pass
        finally:
            writer.close()


async def test_hedged_read_races_slow_primary(tmp_path):
    from tpu9.cache.client import hrw_order
    blob = b"h" * 50_000
    digest = chunk_hash(blob)
    p1 = await FakePeer({digest: blob}).start()
    p2 = await FakePeer({digest: blob}).start()
    addrs = [p1.address, p2.address]
    ordered = hrw_order(digest, addrs)
    by_addr = {p1.address: p1, p2.address: p2}
    by_addr[ordered[0]].delay = 0.5        # primary is slow
    by_addr[ordered[1]].delay = 0.0

    client = CacheClient(DiskStore(str(tmp_path)), peers=lambda: _aret(addrs),
                         hedge_delay_s=0.02)
    try:
        t0 = time.perf_counter()
        got = await client.get(digest)
        dt = time.perf_counter() - t0
        assert got == blob
        assert dt < 0.4, "hedge did not cut the slow primary's latency"
        assert client.stats["hedged_reads"] >= 1
        assert client.stats["hedge_wins"] >= 1
        # the cancelled loser's connection was dropped, not left dirty
        assert ordered[0] not in client._conns
        assert not client._bg_tasks
    finally:
        await client.close()
        assert not client._conns, "close() leaked peer connections"
        await p1.stop()
        await p2.stop()


async def test_hedged_read_never_returns_unverified(tmp_path):
    from tpu9.cache.client import hrw_order
    good = b"verified content" * 1000
    digest = chunk_hash(good)
    pa = await FakePeer({}).start()
    pb = await FakePeer({}).start()
    addrs = [pa.address, pb.address]
    ordered = hrw_order(digest, addrs)
    by_addr = {pa.address: pa, pb.address: pb}
    # fast primary serves CORRUPT bytes; slow hedge has the real thing
    by_addr[ordered[0]].data[digest] = b"x" * len(good)
    by_addr[ordered[1]].data[digest] = good
    by_addr[ordered[1]].delay = 0.05

    client = CacheClient(DiskStore(str(tmp_path)), peers=lambda: _aret(addrs),
                         hedge_delay_s=0.01)
    try:
        assert await client.get(digest) == good
        # and with NO valid holder anywhere, the read must miss, not lie
        evil = chunk_hash(b"never stored")
        pa.data[evil] = b"garbage"
        pb.data[evil] = b"garbage"
        assert await client.get(evil) is None
    finally:
        await client.close()
        await pa.stop()
        await pb.stop()


async def test_hedge_disabled_stays_sequential(tmp_path):
    blob = b"seq" * 1000
    digest = chunk_hash(blob)
    p1 = await FakePeer({digest: blob}, delay=0.05).start()
    client = CacheClient(DiskStore(str(tmp_path)),
                         peers=lambda: _aret([p1.address]),
                         hedge_delay_s=-1.0)
    try:
        assert await client.get(digest) == blob
        assert client.stats["hedged_reads"] == 0
    finally:
        await client.close()
        await p1.stop()


def _aret(value):
    fut = asyncio.get_running_loop().create_future()
    fut.set_result(value)
    return fut


# ---------------------------------------------------------------------------
# CheckpointManager: streamed restore + warm pool, end to end
# ---------------------------------------------------------------------------

class _Ckpts:
    def __init__(self):
        self.manifests = {}

    async def record(self, stub, ws, cid):
        return f"ck-{len(self.manifests)}"

    async def store(self, cid, blob):
        self.manifests[cid] = blob

    async def fetch(self, cid):
        return self.manifests.get(cid)


async def _make_cm(tmp_path, pool=None, **kw):
    store = DiskStore(str(tmp_path / "cache"))
    client = CacheClient(store, peers=lambda: _aret([]))
    cks = _Ckpts()
    cm = CheckpointManager(client, record=cks.record,
                           store_manifest=cks.store,
                           fetch_manifest=cks.fetch,
                           weight_pool=pool, **kw)
    return cm, client


def _write_src(tmp_path) -> str:
    src = str(tmp_path / "src")
    os.makedirs(src)
    rng = np.random.default_rng(3)
    tree = {"w": [rng.standard_normal(4096).astype(np.float32)
                  for _ in range(3)], "bias": rng.standard_normal(7),
            "step": 9}
    wfmt.save_params(tree, os.path.join(src, "params.tpu9w"))
    with open(os.path.join(src, "app.py"), "w") as f:
        f.write("print('hi')\n")
    return src


async def test_second_replica_restore_hits_warm_pool(tmp_path):
    pool = WeightPool(1 << 30)
    cm, client = await _make_cm(tmp_path, pool=pool)
    src = _write_src(tmp_path)
    ckpt = await cm.create("stub", "ws", "c0", src)
    assert ckpt

    try:
        dest1 = str(tmp_path / "r1")
        assert await cm.restore(ckpt, dest1)
        m1 = dict(cm.last_restore_metrics)
        assert m1["weight_groups"] == 1 and not m1["warm_pool_hit"]
        assert m1["weight_stream_bytes"] > 0

        dest2 = str(tmp_path / "r2")
        assert await cm.restore(ckpt, dest2)
        m2 = dict(cm.last_restore_metrics)
        assert m2["warm_pool_hit"], "second replica missed the warm pool"
        assert pool.stats["hits"] == 1 and pool.stats["misses"] == 1

        # both replicas restored byte-identical state, pool or stream
        for rel in ("params.tpu9w/index.json", "params.tpu9w/000000.bin",
                    "app.py"):
            with open(os.path.join(dest1, rel), "rb") as a, \
                    open(os.path.join(dest2, rel), "rb") as b:
                assert a.read() == b.read(), rel
        _assert_tree_equal(
            wfmt.load_params(os.path.join(dest1, "params.tpu9w")),
            wfmt.load_params(os.path.join(dest2, "params.tpu9w")))
    finally:
        await client.close()


async def test_restore_params_direct_to_device(tmp_path):
    pool = WeightPool(1 << 30)
    cm, client = await _make_cm(tmp_path, pool=pool)
    src = _write_src(tmp_path)
    ckpt = await cm.create("stub", "ws", "c0", src)

    put_calls = []

    def fake_put(entry, arr):
        put_calls.append(entry["key"])
        return arr * 2                      # "device" transform

    try:
        trees, metrics = await cm.restore_params(ckpt, device_put=fake_put)
        assert not metrics["warm_pool_hit"]
        assert set(trees) == {"params.tpu9w"}
        want = wfmt.load_params(os.path.join(src, "params.tpu9w"))
        got = trees["params.tpu9w"]
        np.testing.assert_array_equal(got["bias"], np.asarray(want["bias"]) * 2)
        assert got["step"] == 9
        assert len(put_calls) == 4          # 3 layer shards + bias

        # Nth replica: pooled host arrays go straight through device_put
        trees2, metrics2 = await cm.restore_params(ckpt,
                                                   device_put=fake_put)
        assert metrics2["warm_pool_hit"]
        np.testing.assert_array_equal(trees2["params.tpu9w"]["bias"],
                                      got["bias"])
    finally:
        await client.close()


async def test_streamed_restore_falls_back_on_corrupt_group(tmp_path):
    """A weight group whose index is gone from the cache must fall back to
    classic materialization — never turn a restorable snapshot into a cold
    boot."""
    cm, client = await _make_cm(tmp_path)
    src = _write_src(tmp_path)
    ckpt = await cm.create("stub", "ws", "c0", src)

    # sabotage the plan: shrink the index entry's size in the manifest so
    # the group plan rejects it (size mismatch) and classic fallback runs
    import json as _json
    from tpu9.images.manifest import ImageManifest
    blob = await cm.fetch_manifest(ckpt)
    man = ImageManifest.from_json(blob)
    for e in man.files:
        if e.path.endswith("000000.bin"):
            e.size -= 1
    cks_blob = man.to_json()
    assert _json.loads(cks_blob)
    cm.fetch_manifest = _make_fetch(cks_blob)

    try:
        dest = str(tmp_path / "r")
        assert await cm.restore(ckpt, dest)
        # the shard still restored (classic path), bytes intact
        with open(os.path.join(src, "params.tpu9w/000000.bin"), "rb") as a, \
                open(os.path.join(dest, "params.tpu9w/000000.bin"),
                     "rb") as b:
            assert a.read() == b.read()
    finally:
        await client.close()


def _make_fetch(blob):
    async def fetch(cid):
        return blob
    return fetch


async def test_restore_params_overlap_with_slow_io(tmp_path):
    """restore_params-level overlap: chunk fetches overlap each other (the
    read-ahead window holds several open at once) AND the device puts (a
    chunk is in flight while a shard is being placed). Asserted on events,
    not on the clock — sleeps of 50 ms raced under six xdist workers (D10).
    A fetch counts the fetches open beside it; and past the read-ahead
    window a fetch stays open until a put has begun, while the first put
    holds its thread until such a fetch is open: the two meet only in a
    pipeline that really runs them side by side. One that fetched
    everything and then placed it would leave that fetch waiting for a
    put that cannot start, and fail at the bound."""
    window = inspect.signature(
        CacheClient.get_stream).parameters["window"].default
    # more shards than the window: the fetches past it are only issued as
    # the consumer takes chunks, i.e. once shards are being handed to puts
    n_shards = window + 4
    loop = asyncio.get_running_loop()
    put_begun = asyncio.Event()          # set from the put's thread
    fetch_open = threading.Event()       # set on the loop, read by the put
    fetches = {"begun": 0, "open": 0, "most_open": 0, "held": 0}
    met: list = []

    class GatedStore(DiskStore):
        async def get(self, digest):
            fetches["begun"] += 1
            fetches["open"] += 1
            fetches["most_open"] = max(fetches["most_open"],
                                       fetches["open"])
            try:
                if fetches["begun"] > window + 1:    # + 1: the group index
                    fetches["held"] += 1
                    fetch_open.set()
                    await asyncio.wait_for(put_begun.wait(), 30)
                else:
                    await asyncio.sleep(0)   # let the window's others begin
                return await super().get(digest)
            finally:
                fetches["open"] -= 1

    src = str(tmp_path / "src")
    os.makedirs(src)
    tree = {"w": [np.full(256, i, np.float32) for i in range(n_shards)]}
    wfmt.save_params(tree, os.path.join(src, "params.tpu9w"))

    store = GatedStore(str(tmp_path / "cache"))
    client = CacheClient(store, peers=lambda: _aret([]))
    cks = _Ckpts()
    cm = CheckpointManager(client, record=cks.record,
                           store_manifest=cks.store,
                           fetch_manifest=cks.fetch)
    ckpt = await cm.create("stub", "ws", "c0", src)
    fetches.update(begun=0, most_open=0)

    def gated_put(entry, arr):
        loop.call_soon_threadsafe(put_begun.set)
        met.append(fetch_open.wait(30))
        return arr

    try:
        trees, metrics = await cm.restore_params(ckpt, device_put=gated_put)
        assert trees
        assert fetches["begun"] >= n_shards and len(met) == n_shards, (
            fetches, met)
        # fetches overlap EACH OTHER (the prefetch window holds several
        # chunk reads open at once)...
        assert fetches["most_open"] >= 2, fetches
        # ...and fetches overlap the device puts (fetch ∥ consume): every
        # put ran with a chunk in flight or after one had been
        assert fetches["held"] >= 1 and all(met), (fetches, met, metrics)
    finally:
        await client.close()


# ---------------------------------------------------------------------------
# cache-plane accounting: per-peer EWMAs, hedge outcomes, wasted bytes
# (ISSUE 13)
# ---------------------------------------------------------------------------

async def test_per_peer_ewma_diverges_with_one_slow_peer(tmp_path):
    """One slow peer must inflate ONLY its own EWMA (satellite: the old
    single global EWMA stretched the adaptive hedge delay for everyone)."""
    blobs = {chunk_hash(bytes([i]) * 2000): bytes([i]) * 2000
             for i in range(6)}
    fast = await FakePeer(dict(blobs), delay=0.0).start()
    slow = await FakePeer(dict(blobs), delay=0.08).start()
    client = CacheClient(DiskStore(str(tmp_path)),
                         peers=lambda: _aret([fast.address, slow.address]))
    try:
        for digest in blobs:
            assert await client._peer_get_verified(fast.address, digest)
            assert await client._peer_get_verified(slow.address, digest)
        snap = client.snapshot()
        lat_fast = snap["peers"][fast.address]["lat_ewma_s"]
        lat_slow = snap["peers"][slow.address]["lat_ewma_s"]
        assert lat_slow > lat_fast * 3, (lat_fast, lat_slow)
        assert client._lat_estimate(slow.address) == \
            pytest.approx(lat_slow, abs=1e-5)
        assert client._lat_estimate(fast.address) == \
            pytest.approx(lat_fast, abs=1e-5)
        # cold peer falls back to the global prior (which both fed)
        assert client._lat_estimate("10.9.9.9:1") == \
            pytest.approx(snap["lat_ewma_global_s"], abs=1e-5)
        assert snap["lat_ewma_global_s"] > 0
        # per-peer bytes + histograms populated; slow peer's mass sits in
        # higher buckets than the fast peer's
        for peer in (fast.address, slow.address):
            entry = snap["peers"][peer]
            assert entry["exchanges"] == len(blobs)
            assert entry["bytes"] == sum(len(b) for b in blobs.values())
            assert sum(entry["hist"]) == len(blobs)
        hist_f = snap["peers"][fast.address]["hist"]
        hist_s = snap["peers"][slow.address]["hist"]
        centroid = lambda h: (sum(i * n for i, n in enumerate(h))
                              / max(sum(h), 1))          # noqa: E731
        assert centroid(hist_s) > centroid(hist_f)
    finally:
        await client.close()
        await fast.stop()
        await slow.stop()


async def test_hedge_accounting_slow_primary(tmp_path):
    """End-to-end hedge ledger with an artificially slow primary: the
    hedge fires, wins, and the per-peer EWMAs diverge (the satellite's
    acceptance shape)."""
    from tpu9.cache.client import hrw_order
    blobs = {chunk_hash(bytes([i]) * 30_000): bytes([i]) * 30_000
             for i in range(4)}
    p1 = await FakePeer(dict(blobs)).start()
    p2 = await FakePeer(dict(blobs)).start()
    by_addr = {p1.address: p1, p2.address: p2}
    client = CacheClient(DiskStore(str(tmp_path)),
                         peers=lambda: _aret([p1.address, p2.address]),
                         hedge_delay_s=0.02)
    slow_addr = p1.address      # p1 slow regardless of HRW rank
    by_addr[slow_addr].delay = 0.5
    wins_expected = 0
    try:
        for digest in blobs:
            if hrw_order(digest, [p1.address, p2.address])[0] == slow_addr:
                wins_expected += 1       # hedge must beat the slow primary
            assert await client.get(digest) == blobs[digest]
        assert client.stats["hedge_wins"] == wins_expected
        assert client.stats["hedged_reads"] >= wins_expected
        snap = client.snapshot()
        if wins_expected and snap["peers"].get(slow_addr):
            # any completed exchange on the slow peer fed ITS ewma only
            fast_addr = p2.address
            if snap["peers"].get(fast_addr):
                assert snap["peers"][slow_addr]["lat_ewma_s"] > \
                    snap["peers"][fast_addr]["lat_ewma_s"]
    finally:
        await client.close()
        await p1.stop()
        await p2.stop()


async def test_hedge_wasted_bytes_counted_for_completed_loser(tmp_path):
    """A hedge loser that completes with verified data after the race is
    decided counts its bytes as waste — the cost side of the ledger."""
    client = CacheClient(DiskStore(str(tmp_path)),
                         peers=lambda: _aret([]), hedge_delay_s=0.0)
    blob = b"w" * 12_345
    release = asyncio.Event()

    async def fake_verified(peer, digest):
        await release.wait()            # both racers finish together
        return blob

    client._peer_get_verified = fake_verified
    task = asyncio.create_task(
        client._hedged_peer_get(["pA:1", "pB:1"], "d0"))
    await asyncio.sleep(0.05)           # let both racers launch and park
    release.set()
    got, served_by = await task
    assert got == blob
    # deterministic winner preference: earliest-ranked completed task
    # wins the same-wakeup tie → the OTHER completed try is pure waste
    assert served_by == "pA:1"
    assert client.stats["hedge_wins"] == 0
    assert client.stats["hedge_wasted_bytes"] == len(blob)
    assert client.stats["hedged_reads"] == 1
    await client.close()


# ---------------------------------------------------------------------------
# restore trace span tree + decomposition record (ISSUE 13)
# ---------------------------------------------------------------------------

def _spans_by_name(spans, name):
    return [s for s in spans if s["name"] == name]


async def test_streamed_restore_emits_gapless_span_tree(tmp_path):
    from tpu9.observability import coldstart as cs
    from tpu9.observability.trace import tracer

    pool = WeightPool(1 << 30)
    cm, client = await _make_cm(tmp_path, pool=pool)
    src = _write_src(tmp_path)
    ckpt = await cm.create("stub", "ws", "c0", src)
    try:
        with tracer.span("worker.cold_start",
                         attrs={"workspace_id": "ws-1",
                                "container_id": "ct-1"}) as root:
            assert await cm.restore(ckpt, str(tmp_path / "r1"))
        metrics = cm.last_restore_metrics
        spans = tracer.export(trace_id=root.trace_id)
        req = _spans_by_name(spans, cs.SPAN_REQUEST)
        fetch = _spans_by_name(spans, cs.SPAN_FETCH)
        put = _spans_by_name(spans, cs.SPAN_DEVICE_PUT)
        assert len(req) == 1 and len(fetch) == 1 and len(put) == 1

        # parentage: request under cold_start, fetch/put under request
        assert req[0]["parentSpanId"] == root.span_id
        for sp in fetch + put:
            assert sp["parentSpanId"] == req[0]["spanId"]
            # identity stamps inherited from the cold_start attrs — the
            # per-SPAN tenancy /api/v1/traces scopes on
            assert sp["attributes"]["workspace_id"] == "ws-1"
            assert sp["attributes"]["container_id"] == "ct-1"

        # wall-anchor containment (50 ms slack, same as the e2e gate)
        slack = 50e6
        for sp in fetch + put:
            assert sp["startTimeUnixNano"] >= \
                req[0]["startTimeUnixNano"] - slack
            assert sp["endTimeUnixNano"] <= \
                req[0]["endTimeUnixNano"] + slack

        # tier/bytes attributes: everything came from the local store
        assert fetch[0]["attributes"]["tier"] == "local"
        assert fetch[0]["attributes"]["bytes"] == \
            metrics["weight_stream_bytes"] > 0
        assert fetch[0]["attributes"]["bytes_local"] > 0
        assert put[0]["attributes"]["consumer"] == "workdir_spill"

        # decomposition record: tiers/hedge/overlap/groups_detail
        assert metrics["tiers"]["local"] > 0
        assert metrics["tiers"]["pool"] == 0
        assert metrics["hedge"] == {"fired": 0, "wins": 0,
                                    "wasted_bytes": 0}
        assert metrics["groups_detail"][0]["group"] == "params.tpu9w"
        assert 0.0 <= metrics["overlap_frac"] <= 1.0
        assert metrics["trace_id"] == root.trace_id

        # traced intervals agree with the record's intervals (the bench
        # cross-check, unit-sized): fetch span duration == fetch window
        g = metrics["groups_detail"][0]
        traced = cs.decompose_spans(spans)
        want_fetch = g["fetch_iv"][1] - g["fetch_iv"][0]
        assert cs.agreement(traced["fetch_s"], want_fetch) < 0.10

        # Nth replica: pool hit → ONE device_put span, tier="pool"
        with tracer.span("worker.cold_start",
                         attrs={"workspace_id": "ws-1",
                                "container_id": "ct-2"}) as root2:
            assert await cm.restore(ckpt, str(tmp_path / "r2"))
        spans2 = tracer.export(trace_id=root2.trace_id)
        assert not _spans_by_name(spans2, cs.SPAN_FETCH)
        put2 = _spans_by_name(spans2, cs.SPAN_DEVICE_PUT)
        assert len(put2) == 1
        assert put2[0]["attributes"]["tier"] == "pool"
        assert cm.last_restore_metrics["tiers"]["pool"] > 0
    finally:
        await client.close()


async def test_restore_params_span_tree_direct_to_device(tmp_path):
    from tpu9.observability import coldstart as cs
    from tpu9.observability.trace import tracer

    cm, client = await _make_cm(tmp_path)
    src = _write_src(tmp_path)
    ckpt = await cm.create("stub", "ws", "c0", src)
    try:
        trees, metrics = await cm.restore_params(
            ckpt, device_put=lambda e, a: a)
        assert trees
        spans = tracer.export(trace_id=metrics["trace_id"])
        req = _spans_by_name(spans, cs.SPAN_REQUEST)
        assert len(req) == 1
        assert req[0]["attributes"]["mode"] == "direct_to_device"
        put = _spans_by_name(spans, cs.SPAN_DEVICE_PUT)
        assert put and put[0]["attributes"]["consumer"] == "device_put"
    finally:
        await client.close()


async def test_get_stream_ledger_excludes_concurrent_traffic(tmp_path):
    """Review regression (ISSUE 13): per-group tier/hedge evidence comes
    from a per-call ledger, not a global-counter delta — a concurrent
    caller (the classic materialize task) fetching through the same
    client must not leak into the group's attribution."""
    store = DiskStore(str(tmp_path))
    client = CacheClient(store, peers=lambda: _aret([]))
    stream_blobs = [bytes([i]) * 1000 for i in range(4)]
    noise_blobs = [bytes([100 + i]) * 5000 for i in range(8)]
    stream_d = [await store.put(b) for b in stream_blobs]
    noise_d = [await store.put(b) for b in noise_blobs]

    async def noise():
        for d in noise_d:
            assert await client.get(d) is not None

    ledger: dict = {}

    async def consume_stream():
        agen = client.get_stream(stream_d, ledger=ledger)
        try:
            async for _d, data in agen:
                assert data is not None
                await asyncio.sleep(0.001)   # interleave with noise()
        finally:
            await agen.aclose()

    await asyncio.gather(consume_stream(), noise())
    assert ledger["bytes_local"] == sum(len(b) for b in stream_blobs)
    assert ledger["local_hits"] == len(stream_blobs)
    assert "bytes_peer" not in ledger and "hedged_reads" not in ledger
    # the GLOBAL counters saw everything — that is exactly why the
    # ledger exists
    assert client.stats["bytes_local"] == \
        sum(len(b) for b in stream_blobs + noise_blobs)
    await client.close()
