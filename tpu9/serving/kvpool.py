"""Paged KV-pool management for the serving engine (ISSUE 9 engine split).

The engine split's KV third: pool sizing (equal-HBM int8 auto sizing —
ISSUE 6), the trash-block discipline, slot→physical-block bookkeeping,
worst-case reservations and the host block table. Everything here is
HOST-side and topology-OBLIVIOUS: block ids are global integers, tables
are replicated, and admission/eviction arithmetic is identical on one
chip and on a tp×fsdp submesh — only the resident layout of the pool
arrays is sharded, and that placement goes through the
:mod:`tpu9.serving.shard` policy handed in at construction.

The allocator/prefix-cache primitives stay in :mod:`tpu9.serving.paged_kv`
(they predate the split and are imported by the router's admission math
via stats, not by code); this module owns their engine-side composition.
"""

from __future__ import annotations

import collections
from typing import Any, Optional

import numpy as np

from ..models import kvstate
from .paged_kv import BlockAllocator, PrefixCache, blocks_for

Params = dict[str, Any]

# host-tier spill scoring (ISSUE 20): a reaped host entry whose
# hits×recency score clears this goes to the peer cache instead of
# dying — system prompts and chat-session heads score high, one-shot
# prompts decay to zero and are simply dropped
PEER_SPILL_SCORE = 1.0
PEER_SPILL_HALF_LIFE_S = 300.0
PEER_SPILL_QUEUE_MAX = 8


class HostKvTier:
    """Host-DRAM second tier for the paged KV pool (ISSUE 20).

    Stores CANONICAL (full-head, topology-independent) pool planes per
    prefix key as plain numpy — the same layout ``kvwire`` ships — so a
    down-page is one gather off the device, an up-page is one
    policy-placed scatter back, and a peer-tier spill is a pure host
    ``kvwire.encode_blocks`` with zero device work. With ``kv_quant``
    the planes are int8 (+f32 scales), so host DRAM holds ~2× the
    prefixes the same bytes would in bf16.

    Byte budget is enforced on insert: LRU entries are reaped (the pool
    scores them for peer spill first). Pinned prefix-cache entries are
    never reaped — the ``skip`` predicate wires that in."""

    def __init__(self, capacity_bytes: int):
        self.capacity_bytes = int(capacity_bytes)
        # key -> {"planes", "n_tokens", "n_blocks", "nbytes"}
        self._entries: "collections.OrderedDict[bytes, dict]" = \
            collections.OrderedDict()
        self.used_bytes = 0
        self.inserts = 0
        self.evictions = 0
        self.rejected = 0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: bytes) -> bool:
        return key in self._entries

    def get(self, key: bytes) -> Optional[dict]:
        ent = self._entries.get(key)
        if ent is not None:
            self._entries.move_to_end(key)
        return ent

    def peek(self, key: bytes) -> Optional[dict]:
        return self._entries.get(key)

    def pop(self, key: bytes) -> Optional[dict]:
        ent = self._entries.pop(key, None)
        if ent is not None:
            self.used_bytes -= ent["nbytes"]
        return ent

    def put(self, key: bytes, planes: dict, n_tokens: int,
            n_blocks: int, skip=None) -> tuple[bool, list]:
        """Insert (or refresh) an entry, reaping LRU entries to fit.
        Returns ``(stored, reaped)`` where ``reaped`` is the list of
        ``(key, entry)`` pairs evicted to make room — the pool scores
        those for peer spill. ``skip(key)`` excludes unpinned-unsafe
        entries from reaping."""
        if key in self._entries:
            self.pop(key)
        nbytes = sum(int(p.nbytes) for p in planes.values())
        if nbytes > self.capacity_bytes:
            self.rejected += 1
            return False, []
        reaped: list = []
        while self.used_bytes + nbytes > self.capacity_bytes:
            victim = self._reap_one(skip)
            if victim is None:
                self.rejected += 1
                # put back nothing; entry does not fit without touching
                # skip-protected residents
                return False, reaped
            reaped.append(victim)
        self._entries[key] = {"planes": planes, "n_tokens": int(n_tokens),
                              "n_blocks": int(n_blocks), "nbytes": nbytes}
        self.used_bytes += nbytes
        self.inserts += 1
        return True, reaped

    def _reap_one(self, skip=None):
        for key in self._entries:           # OrderedDict: LRU first
            if skip is not None and skip(key):
                continue
            ent = self.pop(key)
            self.evictions += 1
            return key, ent
        return None

    def stats(self) -> dict:
        return {"entries": len(self._entries),
                "bytes": self.used_bytes,
                "capacity_bytes": self.capacity_bytes,
                "inserts": self.inserts, "evictions": self.evictions,
                "rejected": self.rejected}


class KvPool:
    """One engine's paged KV pool: device arrays (built once via
    :meth:`init_arrays`), the block allocator + prefix cache, and the
    per-slot physical-block state the serve loop mutates."""

    def __init__(self, cfg, ecfg, kv_quant: bool, policy,
                 host_pool_mb: int = 0):
        b, s = ecfg.max_batch, ecfg.max_seq_len
        bs = ecfg.kv_block_size
        self.cfg = cfg
        self.ecfg = ecfg
        self.kv_quant = kv_quant
        self.policy = policy
        if ecfg.kv_pool_blocks:
            base_blocks = ecfg.kv_pool_blocks
        else:
            # dense parity, in cache entries (positions, for plain attention)
            base_blocks = b * cfg.kv_entries_peak(s) // bs
            if kv_quant:
                # equal-HBM sizing: the int8 pool spends the same bytes
                # the bf16 pool would have — ~2x the blocks, which is the
                # whole point (capacity == admission headroom == the
                # router's kv_blocks signal)
                base_blocks = (base_blocks
                               * kvstate.block_bytes(cfg, bs, False)
                               // kvstate.block_bytes(cfg, bs, True))
        # +1: one dedicated TRASH block absorbs splice writes of the
        # padded tail of a non-block-aligned final chunk
        self.n_blocks = base_blocks + 1
        # table width: +1 ALWAYS-TRASH column — a decode write at
        # position S (cache full; callers should bound it, but a
        # regression must not corrupt data) computes pos // bs == S/bs
        # which would otherwise CLAMP onto the last real block and
        # overwrite valid KV; the extra column absorbs it harmlessly
        # (attention masks by cache_len, so it is never read). A column is
        # a block of cache ENTRIES: ``kv_entry(pos) // bs`` is a token's
        self.mb = cfg.kv_entries_peak(s) // bs + 1   # table width
        self.allocator = BlockAllocator(self.n_blocks, bs)
        self.trash_block = self.allocator.alloc(1)[0]
        # inactive decode lanes scatter through their (zero-padded) table
        # rows every step — push_table pads rows with the trash block
        # explicitly, but the freshly-zeroed initial table relies on the
        # trash block being physical block 0
        assert self.trash_block == 0, self.trash_block
        # the trash block is held forever — reservations must not count
        # on it
        self.allocator.reserve_capacity = self.n_blocks - 1
        self.prefix_cache = PrefixCache(self.allocator,
                                        ecfg.prefix_cache_blocks)
        self.slot_blocks: list[list[int]] = [[] for _ in range(b)]
        self.slot_reserved = [0] * b
        self.table_np = np.zeros((b, self.mb), dtype=np.int32)
        self.kv_allocs = 0           # lifetime block allocations
        # -- host-DRAM second tier (ISSUE 20); inert at 0 MB -----------------
        self.host_pool_mb = int(host_pool_mb)
        self.host_tier: Optional[HostKvTier] = None
        self.downpages = 0
        self.uppages = 0
        self.peer_spills = 0
        # (key_hex, payload, n_tokens) encoded for the peer cache; the
        # runner drains this — the serving plane never touches transport
        self.peer_spill_queue: collections.deque = \
            collections.deque(maxlen=PEER_SPILL_QUEUE_MAX)
        # kv_tier decision journal (ISSUE 19/20): plain dicts the RUNNER
        # drains into the decision ledger on its heartbeat loop — the
        # serving plane must not import tpu9.observability.decisions
        # (BND001), the same one-way evidence flow as spans and health
        self.kv_decisions: collections.deque = collections.deque(maxlen=256)
        if self.host_pool_mb > 0:
            self.host_tier = HostKvTier(self.host_pool_mb * (1 << 20))
            # an entry re-prefilled on-device drops its stale host copy
            self.prefix_cache.on_host_drop = self.host_tier.pop

    def array_shapes(self) -> dict:
        """``name -> (shape, dtype)`` for every pool array — the ONE shape
        source :meth:`init_arrays` allocates from and
        :meth:`array_specs` abstracts from (they cannot drift): the pool's
        planes as ``models.kvstate`` has them, the block table, and beside
        the pool the state the engine keeps by LANE (no pages, no table) and
        by BLOCK (a tail a page: ``kvstate.block_tail_shapes``)."""
        return {**kvstate.pool_shapes(self.cfg, self.n_blocks,
                                      self.ecfg.kv_block_size,
                                      self.kv_quant),
                kvstate.TABLE: (self.table_np.shape, np.int32),
                **kvstate.lane_shapes(self.cfg, self.ecfg.max_batch),
                **kvstate.block_tail_shapes(self.cfg, self.n_blocks)}

    def init_arrays(self) -> Params:
        """The pool's device state: payload (+ int8 scale planes) and the
        block table — placed through the sharding policy (head axis over
        tp on a mesh; plain single-device arrays otherwise)."""
        kv = {name: self.policy.zeros(shape, dt, name)
              for name, (shape, dt) in self.array_shapes().items()
              if name != kvstate.TABLE}
        kv[kvstate.TABLE] = self.policy.device_table(self.table_np)
        return kv

    def array_specs(self) -> Params:
        """Abstract (``jax.ShapeDtypeStruct``) twin of :meth:`init_arrays`
        — the device-free face graphcheck and compile-ahead lower
        against. Plain structs, no shardings: callers route them through
        ``policy.abstract(..., kv=True)`` exactly as the engine does."""
        import jax
        return {name: jax.ShapeDtypeStruct(shape, dt)
                for name, (shape, dt) in self.array_shapes().items()}

    # -- block allocation ----------------------------------------------------

    def alloc_blocks(self, n: int) -> list[int]:
        """Allocate physical blocks; evicts prefix-cache holdings if the
        free list runs short. Reservations make failure impossible."""
        if n <= 0:
            return []
        got = self.allocator.alloc(n)
        if got is None:
            self.prefix_cache.evict_for_space(n)
            got = self.allocator.alloc(n)
        if got is None:
            raise RuntimeError(
                f"KV pool exhausted: need {n}, free "
                f"{self.allocator.free_count} (reservation bug)")
        self.kv_allocs += n
        return got

    # -- kvwire export / import (ISSUE 16) -----------------------------------

    def wire_names(self) -> list[str]:
        """The pool's own arrays (payload + scale planes): what the splice,
        gather and group programs take and return, and what ships on the
        wire. Not the table, which is host bookkeeping (block ids are
        pool-local), nor state kept by lane."""
        return list(kvstate.paged_planes(self.cfg, self.kv_quant))

    def program_names(self) -> list[str]:
        """What the splice and group programs take and return: the pool's
        own arrays and, beside them, the state it keeps a BLOCK (no wire
        format ships that: kvwire and the host tier are refused beside
        state a lane)."""
        return self.wire_names() + list(
            kvstate.block_tail_shapes(self.cfg, 1))

    def export_blocks(self, kv, blocks: list[int], prefix_key: bytes,
                      n_tokens: int) -> bytes:
        """Gather ``blocks`` of every pool plane into one kvwire payload.
        Planes come out CANONICAL (full-head) via ``policy.gather_kv``,
        so the payload is topology-independent. The caller must hold a
        pin on the blocks for the duration (prefix-cache export pin or a
        slot's own refs) — the gather syncs the device and an eviction
        interleaved at that boundary must not recycle them."""
        from . import kvwire
        meta = kvwire.geometry(self.cfg, self.ecfg, self.kv_quant)
        meta.update({"n_blocks": len(blocks), "n_tokens": int(n_tokens),
                     "prefix_key": prefix_key.hex(),
                     "topology": self.policy.describe()})
        idx = np.asarray(blocks, dtype=np.int32)
        planes = {name: self.policy.gather_kv(name, kv[name])[:, idx]
                  for name in self.wire_names()}
        return kvwire.encode_blocks(meta, planes)

    def import_blocks(self, kv, payload: bytes):
        """Validate + splice a kvwire payload into fresh pool blocks and
        adopt them into the prefix cache under the exporter's key.

        Returns ``(kv, adopted, header)`` — ``kv`` rebound with the
        written (and re-placed) planes. All validation happens BEFORE
        any allocation or write: a bad payload leaves the pool
        untouched. ``adopted=False`` means the entry could not fit the
        prefix budget (blocks were released; caller falls back to
        re-prefill)."""
        from . import kvwire
        header, planes = kvwire.decode_blocks(payload)
        kvwire.check_geometry(
            header, kvwire.geometry(self.cfg, self.ecfg, self.kv_quant))
        try:
            nb = int(header["n_blocks"])
            n_tokens = int(header["n_tokens"])
            key = bytes.fromhex(header["prefix_key"])
        except (KeyError, TypeError, ValueError) as exc:
            raise kvwire.KvWireError(
                f"kvwire: missing/malformed prefix metadata: {exc}") from exc
        if nb <= 0 or not key:
            raise kvwire.KvWireError(
                f"kvwire: empty prefix payload (n_blocks={nb})")
        for name, (want, _) in kvstate.pool_shapes(
                self.cfg, nb, self.ecfg.kv_block_size, self.kv_quant).items():
            if name not in planes:
                raise kvwire.KvWireError(
                    f"kvwire: payload missing plane {name!r}")
            if tuple(planes[name].shape) != want:
                raise kvwire.KvWireError(
                    f"kvwire: plane {name!r} shape "
                    f"{tuple(planes[name].shape)} != pool slice {want}")
        if self.prefix_cache.contains(key):
            # this replica already holds the prefix (raced a local
            # prefill): the adopt is a no-op hit, zero pool work
            return kv, True, header
        blocks = self.alloc_blocks(nb)
        try:
            new_kv = self.place_host_blocks(kv, planes, blocks)
        except Exception:
            self.allocator.release(blocks)
            raise
        if not self.prefix_cache.adopt(key, blocks, n_tokens):
            self.allocator.release(blocks)
            return new_kv, False, header
        return new_kv, True, header

    def place_host_blocks(self, kv, planes: dict, blocks: list[int]):
        """Splice canonical host planes into ``blocks`` of every pool
        array and re-pin the resident layout through the sharding policy
        (head axis over tp on a mesh; identity on one chip). Shared by
        kvwire import and the host-tier up-page — one scatter path means
        the MeshPolicy bit-exactness proof covers both."""
        import jax.numpy as jnp
        idx = jnp.asarray(blocks, dtype=jnp.int32)
        new_kv = dict(kv)
        for name, (_, dt) in kvstate.paged_planes(self.cfg,
                                                  self.kv_quant).items():
            arr = jnp.asarray(np.ascontiguousarray(planes[name]), dtype=dt)
            new_kv[name] = new_kv[name].at[:, idx].set(arr)
        # the scatter above lets GSPMD infer an output sharding;
        # place_kv restores the declared head-axis layout
        placed = self.policy.place_kv(
            {n: new_kv[n] for n in self.wire_names()})
        new_kv.update(placed)
        return new_kv

    # -- host-DRAM tier: down-page / up-page / peer spill (ISSUE 20) ---------

    @property
    def tiered(self) -> bool:
        return self.host_tier is not None

    def downpage(self, kv, entry) -> bool:
        """Move one unpinned device prefix entry to the host tier:
        gather its blocks' canonical planes to host DRAM, release the
        pool blocks, keep the entry alive under ``tier="host"``. Called
        at window boundaries only — the gather is a device sync and must
        never sit on the per-token path. False = the host tier could not
        fit it (the caller lets eviction destroy it as before)."""
        if self.host_tier is None or entry.pins or not entry.blocks:
            return False
        idx = np.asarray(entry.blocks, dtype=np.int32)  # tpu9: noqa[JAX001] host-side block-index list, no device value involved
        planes = {
            name: np.asarray(self.policy.gather_kv(name, kv[name])[:, idx])  # tpu9: noqa[JAX001] intended sync point: window-boundary down-page gather (same class as the drain's batched device_get)
            for name in self.wire_names()}
        stored, reaped = self.host_tier.put(
            entry.key, planes, entry.n_tokens, len(entry.blocks),
            skip=self._host_pin_guard)
        self._reap_to_peer(reaped)
        if not stored:
            return False
        self.prefix_cache.spill_to_host(entry)
        self.downpages += 1
        return True

    def _host_pin_guard(self, key: bytes) -> bool:
        """Host-tier reap skip predicate: a pinned host entry has an
        up-page in flight — its planes must not vanish mid-copy."""
        ent = self.prefix_cache._entries.get(key)
        return ent is not None and ent.pins > 0

    def uppage_planes(self, entry) -> Optional[dict]:
        """The host planes backing a host-tier entry (None = lost a race
        with a host reap; caller degrades to recompute)."""
        if self.host_tier is None:
            return None
        ent = self.host_tier.get(entry.key)
        return None if ent is None else ent["planes"]

    def complete_uppage(self, kv, entry, planes: dict):
        """Finish an up-page: scatter the planes into freshly-allocated
        blocks via the sharding policy and promote the entry back to
        device residency. Returns the rebound ``kv``. The entry must be
        PINNED by the caller for the whole up-page (lookup pins it)."""
        blocks = self.alloc_blocks(len(entry.blocks) or
                                   blocks_for(entry.n_tokens,
                                              self.ecfg.kv_block_size))
        try:
            new_kv = self.place_host_blocks(kv, planes, blocks)
        except Exception:
            self.allocator.release(blocks)
            raise
        self.prefix_cache.promote_to_device(entry, blocks)
        if self.host_tier is not None:
            self.host_tier.pop(entry.key)
        self.uppages += 1
        return new_kv

    def _reap_to_peer(self, reaped: list) -> None:
        """Score host-tier reap victims on the hits×recency clock;
        winners serialize through kvwire onto the peer-spill queue (the
        runner ships them under the ``kv:`` namespace), losers die and
        their prefix-cache entries are journaled as evicted. Either way
        the choice leaves a ``kv_tier`` decision record."""
        from . import kvwire
        now = self.prefix_cache.clock()
        for key, ent in reaped:
            pe = self.prefix_cache._entries.get(key)
            score = 0.0
            if pe is not None:
                age = max(0.0, now - pe.last_used)
                score = pe.hits * 0.5 ** (age / PEER_SPILL_HALF_LIFE_S)
            if score >= PEER_SPILL_SCORE:
                meta = kvwire.geometry(self.cfg, self.ecfg, self.kv_quant)
                meta.update({"n_blocks": ent["n_blocks"],
                             "n_tokens": ent["n_tokens"],
                             "prefix_key": key.hex(),
                             "topology": self.policy.describe()})
                payload = kvwire.encode_blocks(meta, ent["planes"])
                self.peer_spill_queue.append(
                    (key.hex()[:16], payload, ent["n_tokens"]))
                self.peer_spills += 1
                self.prefix_cache.drop(key, kind="peer")
                self.kv_decisions.append(
                    {"decision": "spill",
                     "chosen": f"peer:{key.hex()[:16]}",
                     "signals": {"score": round(score, 4),
                                 "n_tokens": ent["n_tokens"]}})
            else:
                self.prefix_cache.drop(key, kind="evict")
                self.kv_decisions.append(
                    {"decision": "evict", "chosen": "drop",
                     "rejected": [{"alternative": f"peer:{key.hex()[:16]}",
                                   "reason": "score_below_spill_threshold"}],
                     "signals": {"score": round(score, 4),
                                 "n_tokens": ent["n_tokens"]}})

    def drain_peer_spills(self) -> list:
        """Hand the queued peer-cache payloads to the transport owner
        (the runner). Destructive read; bounded by the deque cap."""
        out = list(self.peer_spill_queue)
        self.peer_spill_queue.clear()
        return out

    def tier_stats(self) -> dict:
        """Flat occupancy/counter snapshot for the ``kvtier_`` stats
        family (bytes price the DEVICE pool dtype for the device side
        and actual numpy bytes for the host side)."""
        bb = kvstate.block_bytes(self.cfg, self.ecfg.kv_block_size,
                                 self.kv_quant)
        held = self.prefix_cache.held_blocks
        out = {"device_blocks": held, "device_bytes": held * bb,
               "downpages": self.downpages, "uppages": self.uppages,
               "peer_spills": self.peer_spills,
               "host_blocks": 0, "host_bytes": 0, "host_entries": 0,
               "host_evictions": 0}
        if self.host_tier is not None:
            hs = self.host_tier.stats()
            out.update({
                "host_bytes": hs["bytes"], "host_entries": hs["entries"],
                "host_blocks": sum(e["n_blocks"] for e in
                                   self.host_tier._entries.values()),
                "host_evictions": hs["evictions"]})
        return out

    # -- the host block table ------------------------------------------------

    def device_table(self):
        return self.policy.device_table(self.table_np)

    def push_table(self, slot: int):
        """Refresh one slot's table row from its block list (trash-padded)
        and return the new device table for the engine to install."""
        row = np.full((self.mb,), self.trash_block, dtype=np.int32)
        blocks = self.slot_blocks[slot]
        row[:len(blocks)] = blocks
        self.table_np[slot] = row
        return self.device_table()

    def ensure_slot_blocks(self, slot: int, n_tokens: int) -> bool:
        """Grow the slot's physical block list to cover the cache entries a
        sequence addresses on its way to ``n_tokens`` positions. Returns True
        when the table changed (the caller must install :meth:`device_table`
        / the value from :meth:`push_table`)."""
        need = blocks_for(self.cfg.kv_entries_peak(n_tokens),
                          self.ecfg.kv_block_size)
        have = len(self.slot_blocks[slot])
        if need <= have:
            return False
        self.slot_blocks[slot].extend(self.alloc_blocks(need - have))
        return True

    def release_slot(self, slot: int):
        """Retirement: physical blocks back to the pool (prefix-cache refs
        keep shared prefix blocks alive), worst-case reservation released.
        Returns the refreshed device table."""
        self.allocator.release(self.slot_blocks[slot])
        self.slot_blocks[slot] = []
        table = self.push_table(slot)
        self.allocator.unreserve(self.slot_reserved[slot])
        self.slot_reserved[slot] = 0
        return table
