"""Cache client with rendezvous (HRW) routing and hedged peer reads.

Reference analogue: ``pkg/cache/client.go:187,272`` — highest-random-weight
hashing over discovered hosts picks the canonical holder for each chunk;
reads try local disk, then the HRW-ordered peers, then the source of truth;
writes land locally and on the replica peers. Peer discovery is injected (the
worker registry advertises cache addresses), so the client is transport-pure
and unit-testable.

Peer reads are *hedged* (λScale-style tail cutting, arXiv:2502.09922): the
primary HRW holder gets a short head start (``hedge_delay_s``), then the
next-ranked peer is raced against it and the first *hash-verified* result
wins; the loser is cancelled and its connection dropped so a half-read
response can never poison the persistent per-peer stream. A slow or dead
primary therefore costs ~25 ms, not a full IO timeout, on the restore path.
"""

from __future__ import annotations

import asyncio
import hashlib
import logging
from typing import AsyncIterator, Awaitable, Callable, Optional, Sequence

from ..statestore import wire
from .store import DiskStore, chunk_hash

log = logging.getLogger("tpu9.cache")

# async () -> list of peer addresses ("host:port")
PeerFn = Callable[[], Awaitable[Sequence[str]]]
# async (hash) -> bytes | None — source of truth (registry dir, GCS, ...)
SourceFn = Callable[[str], Awaitable[Optional[bytes]]]


def hrw_order(digest: str, peers: Sequence[str]) -> list[str]:
    """Peers ordered by highest-random-weight for this chunk."""
    def weight(peer: str) -> int:
        return int.from_bytes(
            hashlib.sha256(f"{digest}|{peer}".encode()).digest()[:8], "big")

    return sorted(peers, key=weight, reverse=True)


class CacheClient:
    def __init__(self, store: DiskStore, peers: PeerFn,
                 source: Optional[SourceFn] = None,
                 self_address: str = "", replicas: int = 1,
                 connect_timeout: float = 2.0,
                 hedge_delay_s: float = 0.025):
        self.store = store
        self.peers = peers
        self.source = source
        self.self_address = self_address
        self.replicas = replicas
        self.connect_timeout = connect_timeout
        # head start the best-ranked peer gets before the next one is raced
        # against it; < 0 disables hedging (strictly sequential tries).
        # The effective delay adapts upward to ~2x the observed exchange
        # time (EWMA) — a healthy 4 MiB transfer on a slow link must not
        # trip a hedge on every chunk and double cache traffic; only
        # stragglers relative to this client's own history do.
        self.hedge_delay_s = hedge_delay_s
        # global EWMA is the COLD PRIOR only: the adaptive hedge delay for
        # a peer we have exchanged with uses that peer's own history — one
        # slow peer must not inflate the delay applied to fast peers
        # (ISSUE 13 satellite; the global kept a fleet-wide average that
        # did exactly that)
        self._peer_lat_ewma = 0.0
        self._peer_lat: dict[str, float] = {}
        # per-peer accounting surfaced by snapshot(): exchange counts,
        # bytes, errors and a fixed log-scale latency histogram. Plain
        # dict/list math only — the per-chunk hot path must not grow a
        # registry dependency (the worker heartbeat publishes gauges).
        self._peer_stats: dict[str, dict] = {}
        self._conns: dict[str, tuple[asyncio.StreamReader,
                                     asyncio.StreamWriter]] = {}
        self._conn_locks: dict[str, asyncio.Lock] = {}
        # fire-and-forget work (source→primary seeding): a bare create_task
        # holds no strong reference, so the event loop may GC the task
        # mid-flight — the set keeps it alive and close() drains it
        self._bg_tasks: set[asyncio.Task] = set()
        # scale-out plane (ISSUE 17): content keys of COMPLETE shard
        # groups this cache can re-serve to joining peers. The restore
        # path advertises a group only once its last shard landed — a
        # half-consumed group must never become a tree parent.
        self.groups: set[str] = set()
        self.stats = {"local_hits": 0, "peer_hits": 0, "source_fetches": 0,
                      "peer_errors": 0, "hedged_reads": 0, "hedge_wins": 0,
                      "hedge_wasted_bytes": 0, "bytes_local": 0,
                      "bytes_peer": 0, "bytes_source": 0,
                      # kv: namespace (ISSUE 16) — shipped KV-block
                      # payload traffic, split out from weight chunks so
                      # the cache-plane evidence can tell a restore storm
                      # from a migration storm
                      "kv_puts": 0, "kv_gets": 0, "kv_misses": 0,
                      "kv_bytes_put": 0, "kv_bytes_get": 0}
        # fault-injection plane (ISSUE 15): env-gated, None in production
        # — peer_read_error / peer_read_slow hooks in _peer_get exercise
        # the hedged-read + failover machinery deterministically
        self._faults = None
        from ..config import env_faults_spec
        if env_faults_spec():
            from ..testing.faults import FaultPlane
            self._faults = FaultPlane.from_env()

    def _spawn_bg(self, coro) -> asyncio.Task:
        task = asyncio.create_task(coro)
        self._bg_tasks.add(task)
        task.add_done_callback(self._bg_tasks.discard)
        return task

    async def close(self) -> None:
        for task in list(self._bg_tasks):
            task.cancel()
        if self._bg_tasks:
            await asyncio.gather(*self._bg_tasks, return_exceptions=True)
        self._bg_tasks.clear()
        for _, writer in self._conns.values():
            writer.close()
        self._conns.clear()

    # -- wire ---------------------------------------------------------------

    async def _conn(self, peer: str):
        entry = self._conns.get(peer)
        if entry is not None and not entry[1].is_closing():
            return entry
        host, _, port = peer.rpartition(":")
        reader, writer = await asyncio.wait_for(
            asyncio.open_connection(host, int(port)), self.connect_timeout)
        self._conns[peer] = (reader, writer)
        return reader, writer

    # bound on the WHOLE request/response exchange with a peer: an
    # established-but-dead connection (peer host hung) would otherwise
    # block read_frame forever, pin the per-peer lock, and hang every
    # restore routed through that peer instead of falling to the source
    IO_TIMEOUT_S = 30.0

    async def _peer_get(self, peer: str, digest: str) -> Optional[bytes]:
        if self._faults is not None:
            delay = self._faults.delay_s("peer_read_slow")
            if delay > 0:
                await asyncio.sleep(delay)
            if self._faults.fire("peer_read_error"):
                self.stats["peer_errors"] += 1
                self._peer_entry(peer)["errors"] += 1
                log.debug("fault plane: induced peer read error (%s)", peer)
                return None
            # tree_peer_loss (ISSUE 17): kill reads against ONE peer —
            # the tree parent — mid-transfer; the hedged read falls
            # through the surviving preference list, which IS the
            # worker-side re-plan the chaos leg proves
            if self._faults.fire_peer("tree_peer_loss", peer):
                self.stats["peer_errors"] += 1
                self._peer_entry(peer)["errors"] += 1
                self._drop_conn(peer)
                log.debug("fault plane: induced tree peer loss (%s)", peer)
                return None
        lock = self._conn_locks.setdefault(peer, asyncio.Lock())
        async with lock:
            try:
                return await asyncio.wait_for(
                    self._peer_get_io(peer, digest), self.IO_TIMEOUT_S)
            except (OSError, asyncio.TimeoutError,
                    asyncio.IncompleteReadError) as exc:
                self.stats["peer_errors"] += 1
                self._peer_entry(peer)["errors"] += 1
                self._drop_conn(peer)
                log.debug("peer %s get failed: %s", peer, exc)
                return None
            except asyncio.CancelledError:
                # hedge loser: the request may be mid-exchange — a reused
                # connection would serve the NEXT caller this response's
                # leftover bytes. Drop it so the stream is never dirty.
                self._drop_conn(peer)
                raise

    async def _peer_get_io(self, peer: str, digest: str) -> Optional[bytes]:
        reader, writer = await self._conn(peer)
        writer.write(wire.pack({"op": "get", "hash": digest}))
        await writer.drain()
        head = await wire.read_frame(reader)
        if not head.get("ok"):
            return None
        return await reader.readexactly(int(head["len"]))

    def _drop_conn(self, peer: str) -> None:
        entry = self._conns.pop(peer, None)
        if entry is not None:
            try:
                entry[1].close()
            except Exception:   # noqa: BLE001 — already dead
                pass

    async def _peer_put(self, peer: str, digest: str, data: bytes) -> bool:
        lock = self._conn_locks.setdefault(peer, asyncio.Lock())
        async with lock:
            try:
                return await asyncio.wait_for(
                    self._peer_put_io(peer, digest, data),
                    self.IO_TIMEOUT_S)
            except (OSError, asyncio.TimeoutError,
                    asyncio.IncompleteReadError):
                self.stats["peer_errors"] += 1
                self._drop_conn(peer)
                return False
            except asyncio.CancelledError:
                # same discipline as _peer_get: a put cancelled mid-frame
                # (parallel replica puts under a cancelled caller) must not
                # leave half a request on a pooled connection
                self._drop_conn(peer)
                raise

    async def _peer_put_io(self, peer: str, digest: str,
                           data: bytes) -> bool:
        reader, writer = await self._conn(peer)
        writer.write(wire.pack({"op": "put", "hash": digest,
                                "len": len(data)}))
        writer.write(data)
        await writer.drain()
        head = await wire.read_frame(reader)
        return bool(head.get("ok"))

    # -- accounting ---------------------------------------------------------

    # log-scale exchange-latency buckets (upper edges, seconds); the last
    # bucket is the +Inf overflow — small enough to ship on every worker
    # heartbeat, detailed enough to see a peer fall off a cliff
    LAT_BUCKETS_S = (0.001, 0.005, 0.025, 0.1, 0.5, 2.0)

    def _peer_entry(self, peer: str) -> dict:
        entry = self._peer_stats.get(peer)
        if entry is None:
            entry = self._peer_stats[peer] = {
                "exchanges": 0, "bytes": 0, "errors": 0, "total_s": 0.0,
                "hist": [0] * (len(self.LAT_BUCKETS_S) + 1)}
        return entry

    def _note_exchange(self, peer: str, dt: float, nbytes: int) -> None:
        """One verified peer exchange: per-peer EWMA + histogram + bytes
        (µs-scale dict math per multi-MiB chunk)."""
        prior = self._peer_lat.get(peer)
        self._peer_lat[peer] = dt if prior is None \
            else 0.2 * dt + 0.8 * prior
        self._peer_lat_ewma = dt if self._peer_lat_ewma == 0.0 \
            else 0.2 * dt + 0.8 * self._peer_lat_ewma
        entry = self._peer_entry(peer)
        entry["exchanges"] += 1
        entry["bytes"] += nbytes
        entry["total_s"] += dt
        for i, edge in enumerate(self.LAT_BUCKETS_S):
            if dt <= edge:
                entry["hist"][i] += 1
                break
        else:
            entry["hist"][-1] += 1

    def _lat_estimate(self, peer: str) -> float:
        """This peer's own EWMA, falling back to the global cold prior for
        a peer we have never exchanged with."""
        return self._peer_lat.get(peer) or self._peer_lat_ewma

    @staticmethod
    def _tally(ledger: Optional[dict], key: str, n: int = 1) -> None:
        """Per-CALL accounting sink: ``get``/``get_stream`` callers that
        need traffic attributed to THEM (the restore's per-group tier/
        hedge evidence) pass a ledger dict — the global ``stats`` counters
        are shared by every concurrent caller (a classic materialize
        running beside a weight stream), so differencing them would
        misattribute the neighbor's traffic."""
        if ledger is not None:
            ledger[key] = ledger.get(key, 0) + n

    def advertise_group(self, key: str) -> None:
        """Scale-out plane (ISSUE 17): mark one COMPLETE shard group
        (content key) as re-servable from this cache. The restore path
        calls this after a group's last shard landed; the worker
        heartbeat ships it via :meth:`snapshot`, and the coordinator
        turns it into tree edges for joining replicas."""
        if key:
            self.groups.add(key)

    def snapshot(self) -> dict:
        """Cache-plane evidence for the worker heartbeat → timeline /
        /api/v1/metrics path: tier counters, hedge outcomes, per-peer
        EWMAs/bytes/histograms (ISSUE 13), plus the complete shard
        groups this cache re-serves + its serve address (ISSUE 17 —
        the coordinator's holders/edge-weight inputs)."""
        peers = {}
        for peer, entry in self._peer_stats.items():
            peers[peer] = {
                "lat_ewma_s": round(self._peer_lat.get(peer, 0.0), 6),
                "mean_s": round(entry["total_s"] / entry["exchanges"], 6)
                if entry["exchanges"] else 0.0,
                "exchanges": entry["exchanges"], "bytes": entry["bytes"],
                "errors": entry["errors"], "hist": list(entry["hist"])}
        return {**self.stats,
                "lat_ewma_global_s": round(self._peer_lat_ewma, 6),
                "hist_buckets_s": list(self.LAT_BUCKETS_S),
                "addr": self.self_address,
                "groups": sorted(self.groups),
                "peers": peers}

    # -- public API ---------------------------------------------------------

    async def _peer_get_verified(self, peer: str,
                                 digest: str) -> Optional[bytes]:
        """A peer result counts ONLY if its hash matches — hedged or not,
        an unverified chunk must never win the race."""
        import time
        t0 = time.monotonic()
        data = await self._peer_get(peer, digest)
        if data is not None and chunk_hash(data) == digest:
            self._note_exchange(peer, time.monotonic() - t0, len(data))
            return data
        if data is not None:           # answered, but corrupt — count it
            # in BOTH ledgers: the per-peer series and the worker-level
            # peer_errors counter must not contradict each other
            self.stats["peer_errors"] += 1
            self._peer_entry(peer)["errors"] += 1
        return None

    async def _hedged_peer_get(self, ordered: Sequence[str], digest: str,
                               ledger: Optional[dict] = None
                               ) -> tuple[Optional[bytes], str]:
        """Race the HRW-ordered peers for one chunk: peer *i+1* launches
        only after peer *i* has had ``hedge_delay_s`` to answer; the first
        verified result wins and every other in-flight try is cancelled
        (with its connection dropped — see ``_peer_get``). Returns
        ``(data, winning_peer)`` so the caller can attribute the bytes to
        the serving replica (the per-edge evidence — ISSUE 17)."""
        if not ordered:
            return None, ""
        if len(ordered) == 1:
            # nobody to hedge with — skip the task/wait machinery, which
            # costs real throughput on the per-chunk hot path
            return (await self._peer_get_verified(ordered[0], digest),
                    ordered[0])
        tasks: list[asyncio.Task] = []
        task_peer: dict[asyncio.Task, str] = {}
        winner: Optional[bytes] = None
        winner_peer = ""
        try:
            nxt = 0
            pending: set[asyncio.Task] = set()
            while winner is None and (pending or nxt < len(ordered)):
                if nxt < len(ordered) and (not pending
                                           or self.hedge_delay_s >= 0):
                    task = asyncio.create_task(
                        self._peer_get_verified(ordered[nxt], digest))
                    tasks.append(task)
                    task_peer[task] = ordered[nxt]
                    pending.add(task)
                    nxt += 1
                # the head start adapts to the history of the PEER we are
                # waiting on (best-ranked still pending — tasks is launch
                # = rank order), not a global average: a slow peer
                # elsewhere in the fleet must not delay hedging against
                # THIS peer, and a known-slow primary earns a
                # proportionally longer window before its hedge fires
                waiting_on = next(
                    (task_peer[t] for t in tasks if t in pending),
                    ordered[0])
                timeout = None if (nxt >= len(ordered)
                                   or self.hedge_delay_s < 0) \
                    else max(self.hedge_delay_s,
                             2.0 * self._lat_estimate(waiting_on))
                done, pending = await asyncio.wait(
                    pending, timeout=timeout,
                    return_when=asyncio.FIRST_COMPLETED)
                if not done and nxt < len(ordered):
                    self.stats["hedged_reads"] += 1   # launching a hedge
                    self._tally(ledger, "hedged_reads")
                # deterministic preference: the EARLIEST-ranked completed
                # try wins a same-wakeup tie, so hedge_wins attribution is
                # stable and a completed loser's bytes count as waste
                for task in tasks:
                    if task not in done:
                        continue
                    try:
                        data = task.result()
                    except Exception:   # noqa: BLE001 — a lost racer only
                        data = None     # loses; the race itself survives
                    if data is None:
                        continue
                    if winner is None:
                        winner = data
                        winner_peer = task_peer[task]
                        if task is not tasks[0]:
                            self.stats["hedge_wins"] += 1
                            self._tally(ledger, "hedge_wins")
                    else:
                        # a hedge that completed after the race was
                        # decided moved real bytes for nothing — the
                        # cost side of the hedging ledger
                        self.stats["hedge_wasted_bytes"] += len(data)
                        self._tally(ledger, "hedge_wasted_bytes",
                                    len(data))
            return winner, winner_peer
        finally:
            for task in tasks:
                if not task.done():
                    task.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)

    async def get(self, digest: str,
                  ledger: Optional[dict] = None,
                  prefer: Optional[Sequence[str]] = None) -> Optional[bytes]:
        """local → hedged HRW peers → source (populating local + primary).
        ``ledger`` receives THIS call's tier/hedge accounting (see
        :meth:`_tally`). ``prefer`` (ISSUE 17) is the distribution tree's
        parent preference list: those peers are raced FIRST, in order,
        with the HRW remainder behind them — so a dead parent falls
        through to surviving holders inside the same hedged read, and
        the source tier stays the last resort either way."""
        data = await self.store.get(digest)
        if data is not None:
            self.stats["local_hits"] += 1
            self.stats["bytes_local"] += len(data)
            self._tally(ledger, "local_hits")
            self._tally(ledger, "bytes_local", len(data))
            return data

        peers = [p for p in await self.peers() if p != self.self_address]
        ordered = hrw_order(digest, peers)[: max(self.replicas, 1) + 1]
        if prefer:
            tree = [p for p in prefer
                    if p in peers and p != self.self_address]
            ordered = tree + [p for p in ordered if p not in tree]
        data, served_by = await self._hedged_peer_get(ordered, digest,
                                                      ledger=ledger)
        if data is not None:
            self.stats["peer_hits"] += 1
            self.stats["bytes_peer"] += len(data)
            self._tally(ledger, "peer_hits")
            self._tally(ledger, "bytes_peer", len(data))
            if served_by:
                # per-EDGE attribution (ISSUE 17 satellite: the coldstart
                # record's one "peer" tier hid which replica served what)
                self._tally(ledger, f"bytes_peer:{served_by}", len(data))
            await self.store.put(data, digest)
            return data

        if self.source is not None:
            data = await self.source(digest)
            if data is not None:
                self.stats["source_fetches"] += 1
                self.stats["bytes_source"] += len(data)
                self._tally(ledger, "source_fetches")
                self._tally(ledger, "bytes_source", len(data))
                await self.store.put(data, digest)
                # seed the canonical holder so the next reader hits a peer
                ordered = hrw_order(digest, peers)
                if ordered:
                    self._spawn_bg(self._peer_put(ordered[0], digest, data))
                return data
        return None

    async def get_stream(self, digests: Sequence[str],
                         window: int = 8,
                         ledger: Optional[dict] = None,
                         prefer: Optional[Sequence[str]] = None
                         ) -> AsyncIterator[
                             tuple[str, Optional[bytes]]]:
        """Yield ``(digest, data)`` in the given (manifest) order through a
        read-ahead window — the streaming-restore feed: chunk *i+1* is in
        flight while the consumer deserializes chunk *i*. Duplicate digests
        are served again (second fetch is a local-store hit). ``ledger``
        attributes exactly this stream's tier/hedge traffic to the caller
        (the per-group restore evidence); ``prefer`` carries the tree
        parents for the group this stream restores (ISSUE 17)."""
        from .prefetch import Prefetcher

        async def fetch(digest: str) -> Optional[bytes]:
            return await self.get(digest, ledger=ledger, prefer=prefer)

        pf = Prefetcher(fetch, list(dict.fromkeys(digests)),
                        window=window)
        try:
            for digest in digests:
                yield digest, await pf.get(digest)
        finally:
            await pf.close()

    async def put(self, data: bytes, digest: str = "") -> str:
        digest = digest or chunk_hash(data)
        await self.store.put(data, digest)
        peers = [p for p in await self.peers() if p != self.self_address]
        ordered = hrw_order(digest, peers)[: self.replicas]
        if ordered:
            # replica fan-out in parallel: N sequential peer round-trips
            # serialized every snapshot upload (ISSUE 1 satellite)
            await asyncio.gather(*[self._peer_put(peer, digest, data)
                                   for peer in ordered])
        return digest

    # -- kv: namespace (ISSUE 16) -------------------------------------------
    # Shipped paged-KV blocks ride the SAME content-addressed transport
    # as weight chunks (HRW placement, hedged verified reads, replica
    # fan-out) — digests stay plain chunk hashes because peer reads
    # verify `chunk_hash(data) == digest`. The namespace is a ledger
    # split, not a wire change: these wrappers attribute the traffic.

    async def put_kv(self, payload: bytes) -> str:
        """Publish one kvwire payload; returns its content digest (the
        key an SSE ``kv_key`` event / drain hand-off carries)."""
        digest = await self.put(payload)
        self.stats["kv_puts"] += 1
        self.stats["kv_bytes_put"] += len(payload)
        return digest

    async def get_kv(self, digest: str) -> Optional[bytes]:
        """Fetch one shipped payload (local → hedged peers → source)."""
        data = await self.get(digest)
        if data is None:
            self.stats["kv_misses"] += 1
            return None
        self.stats["kv_gets"] += 1
        self.stats["kv_bytes_get"] += len(data)
        return data

    async def get_many(self, digests: Sequence[str],
                       max_parallel: int = 8) -> dict[str, Optional[bytes]]:
        """Parallel fetch with bounded concurrency (prefetch window —
        reference prefetcher.go:49)."""
        sem = asyncio.Semaphore(max_parallel)
        out: dict[str, Optional[bytes]] = {}

        async def one(d: str) -> None:
            async with sem:
                out[d] = await self.get(d)

        await asyncio.gather(*[one(d) for d in dict.fromkeys(digests)])
        return out
