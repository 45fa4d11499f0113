"""Fleet-global prefix directory (ISSUE 20): who can serve which KV
prefix, from which tier, at what cost.

The affinity table (:mod:`tpu9.router.affinity`) is a TTL'd *guess* —
"this replica served this prefix recently, its cache probably still has
it". The directory is *evidence*: each replica's pressure heartbeat
carries a bounded top-K summary of the prefix keys it actually holds
(``kvtier_keys``, with the serving tier per key), an eviction delta
(``kvtier_evicted`` — retractions for entries destroyed since the last
accepted beat, closing the silent prefix-loss window), and the peer-cache
publications it made (``kvtier_peer`` — digests that survive the replica
itself). Placement then prefers the replica that can serve the LONGEST
prefix from the CHEAPEST tier (device < host < peer), and when only the
peer cache holds a prefix the router hands the chosen replica an
``adopt_kv`` hint so it pulls the tier instead of recomputing.

Staleness semantics: summaries are snapshots — a key absent from a
replica's latest summary drops that replica's claim (reconciliation),
an eviction delta drops it immediately, and claims older than ``ttl_s``
expire. The directory can still be briefly wrong (an eviction in the
beat gap); consumers must treat every hit as a HINT — the engine
degrades a lost prefix to recompute, never an error, and the regression
test pins that.

Key digests are the first 16 hex chars of the engine's prefix key (the
sha1 of the prefix's tokens as 64-bit little-endian integers; its one owner
is :func:`tpu9.utils.prefixkey.prefix_keys`, which the engine's
``PrefixCache`` and this router's ``block_keys`` both call, so a replica's
advertisement and a request's walk name a prefix alike) — long enough that
collisions are noise-level for fleet-sized key sets, short enough that a
48-entry summary rides a heartbeat in ~1.3 KB. The directory holds them in
memory only, under a TTL.
"""

from __future__ import annotations

import time
from typing import Optional

from .affinity import block_keys

# tier cost order: serving from device HBM is free, host DRAM pays one
# up-page, the peer cache pays a transport round-trip + splice
TIER_COST = {"d": 0, "h": 1, "p": 2}
MAX_CLAIMS = 4096          # directory-wide key bound (LRU-ish trim)


class PrefixDirectory:
    def __init__(self, block_tokens: int = 16, ttl_s: float = 30.0,
                 peer_ttl_s: float = 600.0):
        self.block_tokens = max(int(block_tokens), 1)
        self.ttl_s = float(ttl_s)
        self.peer_ttl_s = float(peer_ttl_s)
        # key_hex16 -> {container_id: (tier, n_tokens, seen_mono)}
        self._claims: dict[str, dict[str, tuple[str, int, float]]] = {}
        # peer residency outlives replicas: key_hex16 -> (digest,
        # n_tokens, seen_mono). Deliberately NOT dropped by
        # forget_replica — surviving replica death is the point.
        self._peer: dict[str, tuple[str, int, float]] = {}
        self.hits = 0
        self.misses = 0
        self.retractions = 0

    # -- heartbeat fold ------------------------------------------------------

    def observe_replica(self, container_id: str, stats: dict) -> None:
        """Fold one replica's heartbeat snapshot. Reconciliation: the
        summary is authoritative for this replica — claims it no longer
        advertises are dropped, then the eviction delta retracts
        anything destroyed since the summary was built."""
        now = time.monotonic()
        raw = str(stats.get("kvtier_keys", "") or "")
        if raw or "kvtier_evicted" in stats or "kvtier_peer" in stats:
            seen: dict[str, tuple[str, int]] = {}
            for item in raw.split(","):
                parts = item.split(":")
                if len(parts) != 3 or not parts[0]:
                    continue
                try:
                    seen[parts[0]] = (parts[1], int(parts[2]))
                except ValueError:
                    continue
            for hx in list(self._claims):
                claims = self._claims[hx]
                if container_id in claims and hx not in seen:
                    del claims[container_id]
                    if not claims:
                        del self._claims[hx]
            for hx, (tier, n_tok) in seen.items():
                self._claims.setdefault(hx, {})[container_id] = \
                    (tier, n_tok, now)
            for hx in str(stats.get("kvtier_evicted", "") or "").split(","):
                if not hx:
                    continue
                claims = self._claims.get(hx)
                if claims and container_id in claims:
                    del claims[container_id]
                    self.retractions += 1
                    if not claims:
                        del self._claims[hx]
            for item in str(stats.get("kvtier_peer", "") or "").split(","):
                parts = item.split(":")
                if len(parts) != 3 or not parts[0] or not parts[1]:
                    continue
                try:
                    self._peer[parts[0]] = (parts[1], int(parts[2]), now)
                except ValueError:
                    continue
        self._trim(now)

    def _trim(self, now: float) -> None:
        for hx in list(self._claims):
            claims = self._claims[hx]
            for cid in list(claims):
                if now - claims[cid][2] > self.ttl_s:
                    del claims[cid]
            if not claims:
                del self._claims[hx]
        if len(self._claims) > MAX_CLAIMS:
            # oldest-claim-first trim; rare (bounded per-replica top-K ×
            # fleet size normally stays far under the cap)
            by_age = sorted(
                self._claims,
                key=lambda h: max(s for _, _, s in
                                  self._claims[h].values()))
            for hx in by_age[:len(self._claims) - MAX_CLAIMS]:
                del self._claims[hx]
        for hx in list(self._peer):
            if now - self._peer[hx][2] > self.peer_ttl_s:
                del self._peer[hx]

    def forget_replica(self, container_id: str) -> None:
        """Replica died/drained: its residency claims are gone. Its peer
        publications SURVIVE — the peer cache holds them, not the
        replica."""
        for hx in list(self._claims):
            claims = self._claims[hx]
            if container_id in claims:
                del claims[container_id]
                if not claims:
                    del self._claims[hx]

    # -- lookup --------------------------------------------------------------

    def lookup(self, body: bytes, live: Optional[set] = None) -> dict:
        """One directory lookup for a request body: walk its block-
        aligned prefix keys longest-first; the first key with any
        residency wins. Returns ``{}`` on a miss, else a dict with
        ``key``/``n_tokens`` plus either ``cid``+``tier`` (a live
        replica serves it; cheapest tier among claimants) or
        ``peer_digest`` (only the peer cache holds it — the router
        injects an adopt hint). ``live`` restricts claims to currently
        routable replicas."""
        now = time.monotonic()
        for kb in block_keys(body, self.block_tokens):
            hx = kb.hex()[:16]
            claims = self._claims.get(hx)
            if claims:
                ranked = sorted(
                    (TIER_COST.get(tier, 3), cid, tier, n_tok)
                    for cid, (tier, n_tok, seen) in claims.items()
                    if now - seen <= self.ttl_s
                    and (live is None or cid in live))
                if ranked:
                    cost, cid, tier, n_tok = ranked[0]
                    self.hits += 1
                    return {"key": hx, "cid": cid, "tier": tier,
                            "n_tokens": n_tok}
            peer = self._peer.get(hx)
            if peer is not None and now - peer[2] <= self.peer_ttl_s:
                self.hits += 1
                return {"key": hx, "peer_digest": peer[0],
                        "n_tokens": peer[1]}
        self.misses += 1
        return {}

    def stats(self) -> dict:
        return {"keys": len(self._claims), "peer_keys": len(self._peer),
                "hits": self.hits, "misses": self.misses,
                "retractions": self.retractions}
