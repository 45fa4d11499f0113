"""KV-cache tiering (ISSUE 20): HBM → host-DRAM → peer cache, plus the
fleet prefix directory.

Tier moves are judged BIT-exact: a down-page gathers canonical planes,
an up-page re-places them through the sharding policy, and the gathered
result must reproduce the original pool bytes — single-device and
head-sharded mesh alike (the up-page shares ``place_host_blocks`` with
the kvwire import, so one scatter path carries both proofs). Directory
hits are HINTS: every stale-window test pins that a lost host/peer copy
degrades to recompute, never an error. ``TPU9_KV_TIER=0`` must leave
the pool bit-identical to the untiered baseline.
"""

import asyncio
import json
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu9.models import init_decoder
from tpu9.models.llama import LLAMA_PRESETS
from tpu9.router.affinity import block_keys
from tpu9.router.prefixdir import PrefixDirectory
from tpu9.serving import kvwire
from tpu9.serving.engine import EngineConfig, InferenceEngine
from tpu9.serving.kvpool import HostKvTier, KvPool
from tpu9.serving.paged_kv import BlockAllocator, PrefixCache
from tpu9.serving.shard import make_policy

TINY = LLAMA_PRESETS["llama-tiny"]
TINYF = replace(TINY, dtype=jnp.float32)
BS = 32


def _ecfg(**kw):
    base = dict(max_batch=2, max_seq_len=256, prefill_buckets=(32, 64),
                decode_steps=(1, 4), kv_block_size=BS, kv_pool_blocks=16,
                prefill_chunk=32, prefix_cache_blocks=8)
    base.update(kw)
    return EngineConfig(**base)


def _pool(kv_quant=False, topology=None, host_mb=64, cfg=TINY, **kw):
    policy = make_policy(topology)
    pool = KvPool(cfg, _ecfg(**kw), kv_quant, policy, host_pool_mb=host_mb)
    return pool, pool.init_arrays()


def _fill(pool, kv, blocks, seed=0):
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(blocks, dtype=jnp.int32)
    new = dict(kv)
    for name in pool.wire_names():
        shape, dt = pool.array_shapes()[name]
        sub = (shape[0], len(blocks)) + tuple(shape[2:])
        if np.dtype(dt) == np.dtype(np.int8):
            vals = rng.integers(-127, 128, size=sub, dtype=np.int8)
        else:
            vals = rng.standard_normal(sub).astype(np.float32)
        new[name] = new[name].at[:, idx].set(jnp.asarray(vals, dtype=dt))
    new.update(pool.policy.place_kv({n: new[n] for n in pool.wire_names()}))
    return new


def _gather(pool, kv, blocks):
    return {name: np.asarray(pool.policy.gather_kv(
                name, kv[name]))[:, np.asarray(blocks)]
            for name in pool.wire_names()}


def _seed_entry(pool, kv, n_blocks=2, seed=0, start=1):
    """Fill + insert one prefix entry; returns (kv, tokens, entry)."""
    blocks = pool.alloc_blocks(n_blocks)
    kv = _fill(pool, kv, blocks, seed=seed)
    tokens = list(range(start, start + n_blocks * BS))
    pool.prefix_cache.insert(tokens, blocks)
    pool.allocator.release(blocks)
    return kv, tokens, pool.prefix_cache._entries[PrefixCache._key(tokens)]


# ---------------------------------------------------------------------------
# HostKvTier: byte budget, LRU reap, pin guard
# ---------------------------------------------------------------------------

def _planes(nbytes):
    return {"k": np.zeros(nbytes // 2, dtype=np.int8),
            "v": np.zeros(nbytes - nbytes // 2, dtype=np.int8)}


def test_host_tier_budget_lru_reap_and_skip():
    tier = HostKvTier(1000)
    assert tier.put(b"a", _planes(400), 32, 1)[0]
    assert tier.put(b"b", _planes(400), 32, 1)[0]
    # oversize entry refused outright, residents untouched
    stored, reaped = tier.put(b"huge", _planes(2000), 64, 2)
    assert not stored and not reaped and len(tier) == 2
    # budget overflow reaps LRU first ("a"), not MRU
    tier.get(b"b")
    stored, reaped = tier.put(b"c", _planes(400), 32, 1)
    assert stored and [k for k, _ in reaped] == [b"a"]
    assert tier.used_bytes <= 1000 and b"b" in tier
    # a skip-protected resident can make an insert impossible: refused,
    # protected entries never reaped
    stored, reaped = tier.put(b"d", _planes(900), 32, 1,
                              skip=lambda k: True)
    assert not stored and not reaped
    assert b"b" in tier and b"c" in tier
    st = tier.stats()
    assert st["entries"] == 2 and st["rejected"] == 2
    assert st["evictions"] == 1


# ---------------------------------------------------------------------------
# down-page / up-page: tier transitions, pins, bit-exactness
# ---------------------------------------------------------------------------

def test_downpage_moves_entry_to_host_and_frees_blocks():
    pool, kv = _pool()
    kv, tokens, entry = _seed_entry(pool, kv)
    used0 = pool.allocator.used_count
    assert pool.downpage(kv, entry)
    assert entry.tier == "host" and entry.blocks == []
    assert pool.allocator.used_count == used0 - 2
    assert PrefixCache._key(tokens) in pool.host_tier
    # lookup still finds it — and classifies the hit by tier
    hit = pool.prefix_cache.lookup(tokens + [999])
    assert hit is entry and pool.prefix_cache.hits_host == 1
    pool.prefix_cache.release_pin(entry)
    ts = pool.tier_stats()
    assert ts["downpages"] == 1 and ts["host_entries"] == 1
    assert ts["host_bytes"] > 0


def test_eviction_storm_keeps_prefixes_findable_only_with_a_host_tier():
    """Twenty two-block prefixes through a sixteen-block pool, each wave
    of pressure spilling what it can before it evicts: with a host tier
    more of them are still findable at the end than the untiered pool
    keeps (which destroys what it evicts)."""
    def alive_after_storm(host_mb: int) -> int:
        pool, kv = _pool(host_mb=host_mb, prefix_cache_blocks=12)
        keys = []
        for i in range(20):
            blocks = pool.alloc_blocks(2)
            tokens = [(i * 97 + j) % 241 + 1 for j in range(2 * BS)]
            pool.prefix_cache.insert(tokens, blocks)
            pool.allocator.release(blocks)
            keys.append(PrefixCache._key(tokens))
            if pool.allocator.free_count < 6:
                if pool.tiered:
                    for entry in pool.prefix_cache.spill_candidates(2):
                        pool.downpage(kv, entry)
                pool.prefix_cache.evict_for_space(4)
        return sum(pool.prefix_cache.contains(k) for k in keys)

    untiered, tiered = alive_after_storm(0), alive_after_storm(64)
    assert untiered < 20            # the storm really evicted
    assert tiered > untiered, (tiered, untiered)


def test_downpage_never_moves_a_pinned_entry():
    """Down-page vs lookup pin: an admission holding the lookup pin is
    about to retain the blocks — moving them mid-splice would hand it a
    blockless entry."""
    pool, kv = _pool()
    kv, tokens, _ = _seed_entry(pool, kv)
    entry = pool.prefix_cache.lookup(tokens + [999])    # pinned
    assert entry is not None
    assert pool.downpage(kv, entry) is False
    assert entry.tier == "device" and entry.blocks
    assert entry not in pool.prefix_cache.spill_candidates(8)
    pool.prefix_cache.release_pin(entry)
    assert entry in pool.prefix_cache.spill_candidates(8)
    assert pool.downpage(kv, entry)


def test_uppage_pin_blocks_host_reap_and_eviction():
    """Up-page vs eviction pressure: while a lookup pin holds a
    host-tier entry (up-page in flight), neither the host tier's LRU
    reap nor ``evict_for_space`` may destroy it."""
    pool, kv = _pool()
    kv, tokens, entry = _seed_entry(pool, kv)
    assert pool.downpage(kv, entry)
    pinned = pool.prefix_cache.lookup(tokens + [999])
    assert pinned is entry and entry.pins == 1
    # device-side eviction pressure: host entries are not its victims
    pool.prefix_cache.evict_for_space(16)
    assert pool.prefix_cache.contains(entry.key)
    # host-side budget pressure: the pin guard refuses to reap it
    pool.host_tier.capacity_bytes = pool.host_tier.used_bytes
    stored, reaped = pool.host_tier.put(
        b"intruder", _planes(64), BS, 1, skip=pool._host_pin_guard)
    assert not stored and not reaped
    assert entry.key in pool.host_tier
    pool.prefix_cache.release_pin(entry)


@pytest.mark.parametrize("kv_quant", [False, True],
                         ids=["bf16", "int8+scales"])
def test_downpage_uppage_roundtrip_bit_exact(kv_quant):
    """down-page → up-page reproduces the pool bytes bitwise in every
    wire plane (scales included): the host tier stores the same
    canonical planes kvwire ships."""
    pool, kv = _pool(kv_quant)
    blocks = pool.alloc_blocks(3)
    kv = _fill(pool, kv, blocks)
    tokens = [(i * 7) % 211 + 1 for i in range(3 * BS)]
    before = _gather(pool, kv, blocks)
    pool.prefix_cache.insert(tokens, blocks)
    pool.allocator.release(blocks)
    entry = pool.prefix_cache._entries[PrefixCache._key(tokens)]
    assert pool.downpage(kv, entry)
    planes = pool.uppage_planes(entry)
    assert planes is not None
    kv = pool.complete_uppage(kv, entry, planes)
    assert entry.tier == "device" and len(entry.blocks) == 3
    assert entry.key not in pool.host_tier           # host copy retired
    after = _gather(pool, kv, entry.blocks)
    for name in before:
        assert before[name].tobytes() == after[name].tobytes(), name
    assert pool.tier_stats()["uppages"] == 1


@pytest.mark.multichip
def test_mesh_uppage_replaces_head_sharded_bit_exact():
    """MeshPolicy head-axis sharding: an up-page on a tp=2 mesh re-pins
    the declared layout through the shared ``place_host_blocks`` scatter
    and the re-gathered planes match the pre-spill bytes exactly."""
    pool, kv = _pool(topology="2x1")
    blocks = pool.alloc_blocks(3)
    kv = _fill(pool, kv, blocks)
    tokens = [(i * 11) % 199 + 1 for i in range(3 * BS)]
    before = _gather(pool, kv, blocks)
    pool.prefix_cache.insert(tokens, blocks)
    pool.allocator.release(blocks)
    entry = pool.prefix_cache._entries[PrefixCache._key(tokens)]
    assert pool.downpage(kv, entry)
    kv = pool.complete_uppage(kv, entry, pool.uppage_planes(entry))
    after = _gather(pool, kv, entry.blocks)
    for name in before:
        assert before[name].tobytes() == after[name].tobytes(), name


def test_host_tier_entry_invisible_to_export():
    """Spill vs export_blocks: a host-tier entry holds no pool blocks —
    ``acquire_for_export`` must skip it (shorter device prefix or None),
    never hand the exporter an empty block list."""
    pool, kv = _pool()
    kv, tokens, entry = _seed_entry(pool, kv)
    assert pool.prefix_cache.acquire_for_export(tokens) is entry
    pool.prefix_cache.release_pin(entry)
    assert pool.downpage(kv, entry)
    assert pool.prefix_cache.acquire_for_export(tokens) is None


def test_insert_upgrades_host_entry_in_place():
    """A recompute that beat the up-page re-inserts the same prefix:
    the entry upgrades to device tier and the stale host copy drops."""
    pool, kv = _pool()
    kv, tokens, entry = _seed_entry(pool, kv)
    assert pool.downpage(kv, entry)
    assert entry.key in pool.host_tier
    blocks = pool.alloc_blocks(2)
    pool.prefix_cache.insert(tokens, blocks)
    pool.allocator.release(blocks)
    assert entry.tier == "device" and entry.blocks == blocks
    assert entry.key not in pool.host_tier


# ---------------------------------------------------------------------------
# eviction-delta journal (satellite: the silent prefix-loss window)
# ---------------------------------------------------------------------------

def test_eviction_journals_delta_for_next_heartbeat():
    a = BlockAllocator(8, 4)
    pc = PrefixCache(a, max_blocks=4)
    blocks = a.alloc(2)
    pc.insert(list(range(8)), blocks)
    a.release(blocks)
    key_hex = PrefixCache._key(list(range(8))).hex()[:16]
    deltas, seq = pc.deltas_since(0)
    assert deltas == []                       # inserts journal nothing
    pc.evict_for_space(8)
    deltas, seq2 = pc.deltas_since(seq)
    assert ("evict", key_hex) in deltas and seq2 > seq
    # cursor semantics: a re-read past the cursor is empty (the runner
    # only advances after an ACCEPTED heartbeat, so a rejected beat
    # re-reads the same window)
    assert pc.deltas_since(seq2) == ([], seq2)
    assert pc.deltas_since(seq)[0] == deltas


def test_spill_and_peer_transitions_journal_distinct_kinds():
    pool, kv = _pool()
    kv, tokens, entry = _seed_entry(pool, kv)
    assert pool.downpage(kv, entry)
    deltas, seq = pool.prefix_cache.deltas_since(0)
    key_hex = entry.key.hex()[:16]
    assert ("spill", key_hex) in deltas       # still locally resident
    pool.prefix_cache.drop(entry.key, kind="peer")
    deltas, _ = pool.prefix_cache.deltas_since(seq)
    assert deltas == [("peer", key_hex)]      # locally retracted


# ---------------------------------------------------------------------------
# peer-cache spill: scoring, wire payload, decision journal
# ---------------------------------------------------------------------------

def test_reap_scores_hot_prefix_to_peer_and_drops_cold():
    pool, kv = _pool()
    kv, tok_hot, hot = _seed_entry(pool, kv, seed=1, start=1)
    kv, tok_cold, cold = _seed_entry(pool, kv, seed=2, start=1000)
    assert pool.downpage(kv, hot) and pool.downpage(kv, cold)
    hot.hits = 5                              # a returning session head
    cold.hits = 0                             # a one-shot prompt
    reaped = [(hot.key, pool.host_tier.pop(hot.key)),
              (cold.key, pool.host_tier.pop(cold.key))]
    pool._reap_to_peer(reaped)
    spills = pool.drain_peer_spills()
    assert [s[0] for s in spills] == [hot.key.hex()[:16]]
    assert pool.drain_peer_spills() == []     # destructive read
    # the payload is ordinary kvwire — any replica can adopt it
    header, planes = kvwire.decode_blocks(spills[0][1])
    assert header["prefix_key"] == hot.key.hex()
    assert header["n_tokens"] == hot.n_tokens
    # both entries are locally gone either way
    assert not pool.prefix_cache.contains(hot.key)
    assert not pool.prefix_cache.contains(cold.key)
    # every choice left a kv_tier decision for the runner to ledger
    kinds = [(d["decision"], d["chosen"]) for d in pool.kv_decisions]
    assert (f"spill", f"peer:{hot.key.hex()[:16]}") in kinds
    assert ("evict", "drop") in kinds
    rejected = [d for d in pool.kv_decisions
                if d["decision"] == "evict"][0]["rejected"]
    assert rejected[0]["reason"] == "score_below_spill_threshold"


# ---------------------------------------------------------------------------
# engine integration: up-page on hit, stale-window recompute, parity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_f32():
    return init_decoder(jax.random.PRNGKey(0), TINYF)


def _engine(params, **kw):
    return InferenceEngine(params, TINYF, _ecfg(**kw))


def _run(coro):
    return asyncio.run(coro)


def test_engine_uppage_hit_greedy_parity(tiny_f32, monkeypatch):
    """A host-tier prefix hit re-places through the policy and the
    generation matches the all-device run exactly; the hit is counted
    against the host tier."""
    monkeypatch.delenv("TPU9_KV_TIER", raising=False)
    monkeypatch.delenv("TPU9_KV_HOST_POOL_MB", raising=False)
    prompt = [(i * 5) % 200 + 1 for i in range(80)]

    async def go():
        eng = _engine(tiny_f32, kv_host_pool_mb=64)
        assert eng.pool.tiered
        await eng.start()
        ref = await eng.generate(list(prompt), max_new_tokens=8)
        entry = eng.prefix_cache.acquire_for_export(prompt)
        assert entry is not None
        eng.prefix_cache.release_pin(entry)
        assert eng.pool.downpage(eng._pool_dict(), entry)
        out = await eng.generate(list(prompt), max_new_tokens=8)
        await eng.stop()
        return ref, out, eng

    ref, out, eng = _run(go())
    assert out == ref
    st = eng.stats()
    assert st["kvtier_uppages"] == 1
    assert st["kvtier_hits_host"] == 1
    assert st["kvtier_uppage_failures"] == 0
    # occupancy keys ride the same stats surface the heartbeat forwards
    assert "kvtier_device_blocks" in st and "kvtier_host_bytes" in st
    # the pull decision is journaled for the runner's ledger
    assert any(d["decision"] == "pull"
               for d in eng.drain_kvtier_decisions())


def test_stale_directory_hit_degrades_to_recompute(tiny_f32, monkeypatch):
    """Satellite regression: the directory (or the entry itself) can
    advertise a host copy that a reap already destroyed. The admission
    must recompute and serve the exact same tokens — never error."""
    monkeypatch.delenv("TPU9_KV_TIER", raising=False)
    monkeypatch.delenv("TPU9_KV_HOST_POOL_MB", raising=False)
    prompt = [(i * 3) % 150 + 1 for i in range(80)]

    async def go():
        eng = _engine(tiny_f32, kv_host_pool_mb=64)
        await eng.start()
        ref = await eng.generate(list(prompt), max_new_tokens=8)
        entry = eng.prefix_cache.acquire_for_export(prompt)
        eng.prefix_cache.release_pin(entry)
        assert eng.pool.downpage(eng._pool_dict(), entry)
        eng.pool.host_tier.pop(entry.key)     # the reap the beat missed
        out = await eng.generate(list(prompt), max_new_tokens=8)
        await eng.stop()
        return ref, out, eng

    ref, out, eng = _run(go())
    assert out == ref
    st = eng.stats()
    assert st["kvtier_uppage_failures"] == 1
    assert st["kvtier_uppages"] == 0
    decs = eng.drain_kvtier_decisions()
    rec = [d for d in decs if d["decision"] == "recompute"]
    assert rec and rec[0]["rejected"][0]["reason"] == "host_copy_lost"


def test_peer_tier_survives_replica_death(tiny_f32, monkeypatch):
    """The scale-to-zero / replica-death path end to end: a hot prefix
    down-pages, the host reap spills it to the peer wire format, the
    replica dies, and a FRESH replica adopts the payload and continues
    with exact greedy parity."""
    monkeypatch.delenv("TPU9_KV_TIER", raising=False)
    monkeypatch.delenv("TPU9_KV_HOST_POOL_MB", raising=False)
    prompt = [(i * 9) % 180 + 1 for i in range(80)]

    async def victim_go():
        eng = _engine(tiny_f32, kv_host_pool_mb=64)
        await eng.start()
        ref = await eng.generate(list(prompt), max_new_tokens=8)
        entry = eng.prefix_cache.acquire_for_export(prompt)
        eng.prefix_cache.release_pin(entry)
        assert eng.pool.downpage(eng._pool_dict(), entry)
        entry.hits = 10                       # hot: clears spill score
        ent = eng.pool.host_tier.pop(entry.key)
        eng.pool._reap_to_peer([(entry.key, ent)])
        spills = eng.drain_kv_spills()
        await eng.stop()
        return ref, spills

    ref, spills = _run(victim_go())
    assert len(spills) == 1
    _khex, payload, n_tokens = spills[0]
    assert n_tokens == 64                     # two full blocks

    async def survivor_go():
        eng = _engine(tiny_f32)               # untiered survivor is fine
        assert eng.adopt_kv(payload)
        await eng.start()
        out = await eng.generate(list(prompt), max_new_tokens=8)
        await eng.stop()
        return out, eng

    out, survivor = _run(survivor_go())
    assert out == ref
    assert survivor.prefix_cache.stats()["adopted"] == 1
    assert survivor.stats()["kvwire_import_hits"] == 1


def test_kv_tier_off_is_bit_identical_to_baseline(tiny_f32, monkeypatch):
    """TPU9_KV_TIER=0 master gate: the pool carries no host tier, the
    stats surface carries no kvtier_ keys, and generation matches the
    untiered baseline token for token."""
    prompt = [(i * 7) % 190 + 1 for i in range(80)]
    monkeypatch.delenv("TPU9_KV_TIER", raising=False)
    monkeypatch.delenv("TPU9_KV_HOST_POOL_MB", raising=False)

    async def gen(eng):
        await eng.start()
        out = await eng.generate(list(prompt), max_new_tokens=8)
        await eng.stop()
        return out

    base_eng = _engine(tiny_f32)
    base = _run(gen(base_eng))

    monkeypatch.setenv("TPU9_KV_TIER", "0")
    gated = _engine(tiny_f32, kv_host_pool_mb=64)
    assert not gated.pool.tiered and gated.pool.host_tier is None
    out = _run(gen(gated))
    assert out == base
    assert not any(k.startswith("kvtier_") for k in gated.stats())


# ---------------------------------------------------------------------------
# prefix directory: fold, tiers, retraction, peer survival
# ---------------------------------------------------------------------------

def _body(tokens):
    return json.dumps({"tokens": tokens}).encode()


def test_directory_digest_matches_engine_prefix_keys():
    """The directory's lookup keys are the engine's prefix-cache keys:
    block_keys at kv_block_size granularity reproduces PrefixCache._key
    hex16 at every block boundary — placement and engine-level reuse
    agree on what 'the same prefix' means."""
    tokens = list(range(1, 2 * BS + 2))
    keys = block_keys(_body(tokens), BS)
    assert keys[0].hex()[:16] == \
        PrefixCache._key(tokens[:2 * BS]).hex()[:16]


def test_directory_prefers_longest_prefix_from_cheapest_tier():
    d = PrefixDirectory(block_tokens=BS)
    tokens = list(range(1, 3 * BS + 2))
    long_key = PrefixCache._key(tokens[:3 * BS]).hex()[:16]
    short_key = PrefixCache._key(tokens[:2 * BS]).hex()[:16]
    # r1 serves the long prefix from host; r2 only the short one from
    # device: the LONGER prefix wins even from the dearer tier
    d.observe_replica("r1", {"kvtier_keys": f"{long_key}:h:96"})
    d.observe_replica("r2", {"kvtier_keys": f"{short_key}:d:64"})
    hit = d.lookup(_body(tokens))
    assert hit["cid"] == "r1" and hit["tier"] == "h"
    # same length on both: the cheaper tier wins
    d.observe_replica("r2", {"kvtier_keys": f"{long_key}:d:96"})
    hit = d.lookup(_body(tokens))
    assert hit["cid"] == "r2" and hit["tier"] == "d"
    # live-set filter: r2 unroutable → back to the host claimant
    hit = d.lookup(_body(tokens), live={"r1"})
    assert hit["cid"] == "r1"


def test_directory_retracts_on_eviction_delta_and_reconciles():
    d = PrefixDirectory(block_tokens=BS)
    tokens = list(range(1, 2 * BS + 2))
    key = PrefixCache._key(tokens[:2 * BS]).hex()[:16]
    d.observe_replica("r1", {"kvtier_keys": f"{key}:d:64"})
    assert d.lookup(_body(tokens))["cid"] == "r1"
    # eviction delta retracts immediately — the silent-loss window closes
    # on the next beat, not at TTL
    d.observe_replica("r1", {"kvtier_keys": "", "kvtier_evicted": key})
    assert d.lookup(_body(tokens)) == {}
    assert d.retractions >= 0 and d.stats()["keys"] == 0
    # snapshot reconciliation: a key absent from the latest summary drops
    # even without an explicit delta
    d.observe_replica("r1", {"kvtier_keys": f"{key}:d:64"})
    d.observe_replica("r1", {"kvtier_keys": "deadbeefdeadbeef:d:32"})
    assert d.lookup(_body(tokens)) == {}


def test_directory_peer_residency_survives_replica_forget():
    d = PrefixDirectory(block_tokens=BS)
    tokens = list(range(1, 2 * BS + 2))
    key = PrefixCache._key(tokens[:2 * BS]).hex()[:16]
    d.observe_replica("r1", {"kvtier_keys": f"{key}:d:64",
                             "kvtier_peer": f"{key}:sha999:64"})
    assert d.lookup(_body(tokens))["cid"] == "r1"
    d.forget_replica("r1")                    # the replica dies
    hit = d.lookup(_body(tokens))
    assert hit == {"key": key, "peer_digest": "sha999", "n_tokens": 64}


def test_fleet_router_promotes_directory_target_and_adopt_hint(monkeypatch):
    monkeypatch.delenv("TPU9_KV_TIER", raising=False)
    from tpu9.config import RouterConfig
    from tpu9.observability.decisions import ledger
    from tpu9.router.fleet import FleetRouter

    router = FleetRouter(RouterConfig(affinity_block_tokens=BS),
                         None, None)
    assert router.prefix_dir is not None
    tokens = list(range(1, 2 * BS + 2))
    key = PrefixCache._key(tokens[:2 * BS]).hex()[:16]
    router.prefix_dir.observe_replica(
        "r2", {"kvtier_keys": f"{key}:d:64"})
    order, hit = router._directory_promote(
        _body(tokens), ["r1", "r2", "r3"], set())
    assert order == ["r2", "r1", "r3"] and hit["cid"] == "r2"
    recs = ledger.query(plane="kv_tier")
    assert any(r["decision"] == "place" and r["chosen"] == "d:r2"
               and r["signals"].get("key") == key for r in recs)
    # a saturated claimant is NOT promoted (availability beats placement)
    order, _ = router._directory_promote(
        _body(tokens), ["r1", "r2"], {"r2"})
    assert order == ["r1", "r2"]
    # peer-only residency: no promotion, but the adopt hint fires
    router.prefix_dir.forget_replica("r2")
    router.prefix_dir.observe_replica(
        "r9", {"kvtier_peer": f"{key}:shaabc:64"})
    router.prefix_dir.forget_replica("r9")
    assert router.kv_adopt_hint(_body(tokens)) == \
        {"key": "shaabc", "n_tokens": 64}
    # a live-replica hit returns no adopt hint (tiers pull locally)
    router.prefix_dir.observe_replica(
        "r5", {"kvtier_keys": f"{key}:h:64"})
    assert router.kv_adopt_hint(_body(tokens)) is None


def test_fleet_router_directory_off_with_env_gate(monkeypatch):
    monkeypatch.setenv("TPU9_KV_TIER", "0")
    from tpu9.config import RouterConfig
    from tpu9.router.fleet import FleetRouter

    router = FleetRouter(RouterConfig(), None, None)
    assert router.prefix_dir is None
    # the fold and hint paths are inert, not errors
    order, hit = router._directory_promote(b"{}", ["r1"], set())
    assert order == ["r1"] and hit is None
    assert router.kv_adopt_hint(b"{}") is None
