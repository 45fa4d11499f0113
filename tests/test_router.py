"""Fleet inference router unit tests (ISSUE 2): DRR fairness, KV-affinity
ordering, admission budgets, SLO shedding, graceful drain.

All deterministic: fake replica fleets (no LocalStack, no sockets), the
router's own asyncio machinery driven directly.
"""

import asyncio
import hashlib
import json
import time

import pytest

from tpu9.config import RouterConfig
from tpu9.abstractions.common.buffer import ForwardResult
from tpu9.router import (AffinityRouter, FleetRouter, QueuedRequest,
                         ReplicaBudgets, TenantFairQueue, block_keys,
                         estimate_cost)
from tpu9.router.affinity import MAX_KEY_BLOCKS
from tpu9.serving.paged_kv import BlockAllocator, PrefixCache, prefix_keys
from tpu9.statestore import MemoryStore
from tpu9.types import ContainerState, ContainerStatus, Stub, StubConfig


def _req(tenant, cost, n):
    return QueuedRequest(tenant=tenant, cost=cost, item=n)


def _body(tokens_n, max_new=64):
    return json.dumps({"tokens": list(range(1, tokens_n + 1)),
                       "max_new_tokens": max_new}).encode()


class FakeContainers:
    """containers_by_stub returning a fixed RUNNING fleet."""

    def __init__(self, cids):
        self.states = [ContainerState(container_id=c, stub_id="s",
                                      status=ContainerStatus.RUNNING.value,
                                      address=f"127.0.0.1:{4000 + i}")
                       for i, c in enumerate(cids)]

    async def containers_by_stub(self, stub_id, status=None):
        return [s for s in self.states
                if status is None or s.status == status]


def make_router(cids=("r0", "r1"), **cfg_kw) -> FleetRouter:
    cfg = RouterConfig(**cfg_kw)
    return FleetRouter(cfg, MemoryStore(), FakeContainers(list(cids)))


def make_stub(timeout_s=30.0) -> Stub:
    return Stub(stub_id="s", name="s", workspace_id="ws-own",
                config=StubConfig(timeout_s=timeout_s))


# ---------------------------------------------------------------------------
# deficit round-robin
# ---------------------------------------------------------------------------

def test_drr_interleaves_flood_with_light_tenant():
    """Tenant A floods 40 heavy requests before B's 5 arrive; DRR must
    still serve B's work interleaved, not behind the whole flood."""
    q = TenantFairQueue(quantum_tokens=500)
    for i in range(40):
        q.put(_req("A", 450, f"a{i}"))
    for i in range(5):
        q.put(_req("B", 450, f"b{i}"))
    order = []
    while True:
        r = q.pop()
        if r is None:
            break
        order.append(r.tenant)
    assert len(order) == 45
    # every B request served within the first ~2×(2×5) pops: one A and one
    # B per ring round while both lanes are non-empty
    last_b = max(i for i, t in enumerate(order) if t == "B")
    assert last_b < 12, order[:15]


def test_drr_weight_gives_proportional_share():
    q = TenantFairQueue(quantum_tokens=100)
    for i in range(30):
        q.put(_req("heavy", 100, i), weight=1.0)
        q.put(_req("prio", 100, i), weight=3.0)
    first20 = [q.pop().tenant for _ in range(20)]
    # weight 3 tenant gets ~3× the slots of weight 1 in any window
    assert first20.count("prio") >= 2 * first20.count("heavy")


def test_drr_carries_deficit_for_oversized_request():
    """A request costing more than one quantum must eventually go (the
    lane banks deficit across ring visits), not starve forever."""
    q = TenantFairQueue(quantum_tokens=100)
    q.put(_req("big", 350, "jumbo"))
    q.put(_req("small", 50, "s1"))
    served = []
    while True:
        r = q.pop()
        if r is None:
            break
        served.append(r.item)
    assert "jumbo" in served and "s1" in served


def test_drop_completed_purges_dead_requests():
    q = TenantFairQueue(quantum_tokens=100)
    loop = asyncio.new_event_loop()
    try:
        fut = loop.create_future()
        fut.set_result(None)
        dead = QueuedRequest(tenant="A", cost=10, future=fut)
        q.put(dead)
        q.put(_req("A", 10, "live"))
        assert q.depth == 2
        assert q.drop_completed() == 1
        assert q.depth == 1
    finally:
        loop.close()


def test_oversized_cost_cannot_spin_the_pop_loop():
    """Regression: a forged max_new_tokens of 10**12 used to make pop()
    top the lane deficit one quantum per iteration until it covered the
    head — ~cost/quantum synchronous spins freezing the gateway loop.
    Cost is clamped AND a sole tenant bypasses deficit accounting."""
    from tpu9.router.fairness import MAX_COST_TOKENS
    body = json.dumps({"tokens": [1, 2, 3],
                       "max_new_tokens": 10**12}).encode()
    assert estimate_cost(body) == MAX_COST_TOKENS
    q = TenantFairQueue(quantum_tokens=100)
    q.put(_req("A", MAX_COST_TOKENS, "huge"))
    t0 = time.monotonic()
    assert q.pop().item == "huge"            # sole-tenant fast path
    # two tenants: the clamped cost bounds rotations to cost/quantum
    q.put(_req("A", MAX_COST_TOKENS, "huge2"))
    q.put(_req("B", 10, "small"))
    served = {q.pop().item, q.pop().item}
    assert served == {"huge2", "small"}
    assert time.monotonic() - t0 < 5.0


def test_drop_completed_does_not_duplicate_ring_entry():
    """Regression: drop_completed() emptying a lane left its tenant in
    the ring; the next put() appended it AGAIN, doubling that tenant's
    quantum per rotation — rewarding exactly the flooder whose requests
    timed out."""
    q = TenantFairQueue(quantum_tokens=100)
    loop = asyncio.new_event_loop()
    try:
        fut = loop.create_future()
        fut.set_result(None)
        q.put(QueuedRequest(tenant="A", cost=10, future=fut))
        q.drop_completed()                   # lane empty, 'A' still ringed
        q.put(_req("A", 100, "a1"))
        q.put(_req("A", 100, "a2"))
        q.put(_req("B", 100, "b1"))
        assert list(q._ring).count("A") == 1
        # fair interleave, not double service for A
        assert [q.pop().item for _ in range(3)] == ["a1", "b1", "a2"]
    finally:
        loop.close()


def test_estimate_cost_shapes():
    assert estimate_cost(_body(100, max_new=28)) == 128
    assert estimate_cost(b"not json at all") >= 1
    text = json.dumps({"prompt": "x" * 400, "max_new_tokens": 10}).encode()
    assert estimate_cost(text) > 100


# ---------------------------------------------------------------------------
# affinity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bs, n", [
    (16, 49),                       # 3 blocks and a token (the first case)
    (16, 48), (16, 47), (128, 5 * 128), (128, 5 * 128 - 1),
    (128, 5 * 128 + 1), (16, 70 * 16 + 3)],
    ids=["16-over", "16-at", "16-under", "128-at", "128-under", "128-over",
         "past-the-cap"])
def test_block_keys_match_engine_prefix_cache_keying(bs, n):
    """The router's token keys must be EXACTLY PrefixCache._key at the
    same block boundaries — otherwise placement and engine-level reuse
    silently diverge. They are the same function's (``prefix_keys``):
    strict, longest first, the cap on the blocks changing no key."""
    tokens = list(range(1, n + 1))
    keys = block_keys(json.dumps({"tokens": tokens}).encode(),
                      block_tokens=bs)
    # strict prefix: (49-1)//16 = 3 blocks → keys for 48, 32, 16 tokens
    nb = min((n - 1) // bs, MAX_KEY_BLOCKS)
    assert len(keys) == nb
    assert keys == [PrefixCache._key(tokens[:k * bs])
                    for k in range(nb, 0, -1)]
    assert keys == prefix_keys(tokens, bs, strict=True,
                               max_blocks=MAX_KEY_BLOCKS)[::-1]


def test_a_routed_prompt_finds_the_engines_entry():
    """End to end over the two planes: what the engine's cache inserts for a
    served prompt is what the router's walk of the next turn's body names,
    at the engine's page size."""
    alloc = BlockAllocator(8, 16)
    pc = PrefixCache(alloc, 8)
    served = list(range(100, 100 + 3 * 16))
    pc.insert(served, alloc.alloc(3))
    nxt = json.dumps({"tokens": served + [7, 8, 9]}).encode()
    assert [pc.contains(k) for k in block_keys(nxt, 16)] \
        == [True, False, False]
    assert pc.lookup(served + [7, 8, 9]).key == block_keys(nxt, 16)[0]


def test_block_keys_text_fallback():
    body = json.dumps({"prompt": "p" * 200}).encode()
    keys = block_keys(body, block_tokens=16)
    assert keys and all(isinstance(k, bytes) for k in keys)
    # stable across formatting noise in OTHER fields
    body2 = json.dumps({"prompt": "p" * 200, "temp": 0.9}).encode()
    assert block_keys(body2, block_tokens=16) == keys


def test_affinity_longest_prefix_wins_and_jsq_fallback():
    af = AffinityRouter(block_tokens=16)
    shared = list(range(1, 33))                      # 2 full blocks
    af.record_served(json.dumps({"tokens": shared + [40, 41]}).encode(), "r1")
    # same 2-block prefix, different suffix → r1 first
    body = json.dumps({"tokens": shared + [99] * 20}).encode()
    order = af.order(body, ["r0", "r1", "r2"],
                     load={"r0": 1.0, "r1": 5.0, "r2": 0.0})
    assert order[0] == "r1"
    # fallback for the rest is join-shortest-queue
    assert order[1:] == ["r2", "r0"]
    # saturated affinity target → pure JSQ, target at the tail
    order = af.order(body, ["r0", "r1", "r2"],
                     load={"r0": 1.0, "r1": 0.0, "r2": 3.0},
                     saturated={"r1"})
    assert order == ["r0", "r2", "r1"]


def test_affinity_forget_replica_rehomes():
    af = AffinityRouter(block_tokens=4)
    body = _body(64)
    af.record_served(body, "dying")
    assert af.target(body, {"dying", "other"}) == "dying"
    af.forget_replica("dying")
    assert af.target(body, {"dying", "other"}) == ""


# ---------------------------------------------------------------------------
# admission budgets
# ---------------------------------------------------------------------------

def test_budget_from_kv_headroom():
    b = ReplicaBudgets(default_inflight=8, kv_tokens_per_request=128,
                       max_inflight=64)
    # no stats → default
    assert b.budget_from_stats(None) == 8
    # 40 free blocks × 16 tokens = 640 tokens → 5 more requests on top of
    # the 2 already streaming
    stats = {"kv_blocks_free": 40, "kv_block_size": 16, "active_streams": 2}
    assert b.budget_from_stats(stats) == 7
    # full pool still admits 1 (no rotation deadlock)
    assert b.budget_from_stats({"kv_blocks_free": 0, "kv_block_size": 16,
                                "active_streams": 0}) == 1
    # ceiling clamps absurd headroom
    assert b.budget_from_stats({"kv_blocks_free": 10000,
                                "kv_block_size": 128}) == 64


def test_budget_acquire_release():
    b = ReplicaBudgets(default_inflight=2)
    assert b.try_acquire("r", 2)
    assert b.try_acquire("r", 2)
    assert not b.try_acquire("r", 2)
    b.release("r")
    assert b.try_acquire("r", 2)


# ---------------------------------------------------------------------------
# fleet: fairness end to end
# ---------------------------------------------------------------------------

async def test_flood_tenant_does_not_starve_light_tenant():
    """Tenant A floods 30 heavy requests; tenant B's 5 cheap requests
    keep bounded queue wait — dispatched interleaved, not after the
    flood. Deterministic: one replica slot, service order observed."""
    router = make_router(cids=("r0",), default_replica_inflight=1,
                         tenant_quantum_tokens=512, max_queue_depth=500,
                         max_queue_wait_s=30.0)
    stub = make_stub()
    dispatch_order = []

    def forward_for(tenant):
        async def forward(prefer):
            dispatch_order.append(tenant)
            await asyncio.sleep(0)
            return ForwardResult(status=200, body=b"{}",
                                 container_id="r0")
        return forward

    tasks = [asyncio.create_task(router.submit(
        stub, "A", _body(400), forward_for("A"))) for _ in range(30)]
    await asyncio.sleep(0)              # flood enqueued first
    tasks += [asyncio.create_task(router.submit(
        stub, "B", _body(8), forward_for("B"))) for _ in range(5)]
    results = await asyncio.gather(*tasks)
    await router.stop()

    assert all(r.status == 200 for r in results)
    assert dispatch_order.count("B") == 5
    # B's cheap requests ride DRR: all five dispatched well inside the
    # flood (p99 queue-wait bounded by ~5 round trips, not 30)
    last_b = max(i for i, t in enumerate(dispatch_order) if t == "B")
    assert last_b < 20, dispatch_order


async def test_weighted_tenant_gets_priority_share():
    class QuotaBackend:
        async def get_concurrency_limit(self, workspace_id):
            return {"tpu_chip_limit": 32} if workspace_id == "paid" else None

    router = make_router(cids=("r0",), default_replica_inflight=1,
                         tenant_quantum_tokens=256, max_queue_depth=500)
    router.backend = QuotaBackend()
    stub = make_stub()
    order = []

    def fwd(tenant):
        async def forward(prefer):
            order.append(tenant)
            return ForwardResult(status=200, body=b"{}")
        return forward

    tasks = []
    for _ in range(20):
        tasks.append(asyncio.create_task(
            router.submit(stub, "free", _body(240), fwd("free"))))
        tasks.append(asyncio.create_task(
            router.submit(stub, "paid", _body(240), fwd("paid"))))
    await asyncio.gather(*tasks)
    await router.stop()
    first10 = order[:10]
    # chip quota 32 → weight 8: the paid tenant dominates early slots
    assert first10.count("paid") > first10.count("free")


# ---------------------------------------------------------------------------
# fleet: shedding + deadlines
# ---------------------------------------------------------------------------

async def test_shed_429_with_retry_after_while_inflight_completes():
    router = make_router(cids=("r0",), default_replica_inflight=1,
                         max_queue_depth=2, max_queue_wait_s=10.0)
    stub = make_stub()
    release = asyncio.Event()
    served = []

    async def blocking_forward(prefer):
        await release.wait()
        served.append(1)
        return ForwardResult(status=200, body=b"{}", container_id="r0")

    # all five submits enqueue/shed before the dispatcher's first pop
    # (each runs to its first real suspension in creation order): two fit
    # under the depth cap, three shed at the door
    tasks = [asyncio.create_task(
        router.submit(stub, "t", _body(8), blocking_forward))
        for _ in range(5)]
    await asyncio.sleep(0.05)            # let dispatch start the first
    release.set()                        # admitted work completes
    results = await asyncio.gather(*tasks)
    statuses = sorted(r.status for r in results)
    assert statuses == [200, 200, 429, 429, 429]
    shed = next(r for r in results if r.status == 429)
    headers = dict(shed.headers)
    assert int(headers["Retry-After"]) >= 1
    assert b"retry_after_s" in shed.body
    assert len(served) == 2              # in-flight completed despite sheds
    assert router.signals.shed_rate("s") > 0
    await router.stop()


async def test_queue_wait_deadline_sheds_503():
    router = make_router(cids=("r0",), default_replica_inflight=1,
                         max_queue_depth=50, max_queue_wait_s=0.2)
    stub = make_stub()
    release = asyncio.Event()

    async def blocking_forward(prefer):
        await release.wait()
        return ForwardResult(status=200, body=b"{}", container_id="r0")

    first = asyncio.create_task(
        router.submit(stub, "t", _body(8), blocking_forward))
    await asyncio.sleep(0.01)
    # queued behind a stuck replica past the 0.2 s SLO budget → 503
    second = await router.submit(stub, "t", _body(8), blocking_forward)
    assert second.status == 503
    assert dict(second.headers).get("Retry-After")
    release.set()
    assert (await first).status == 200
    await router.stop()


async def test_cold_start_passthrough_without_replicas():
    """Zero RUNNING replicas: requests flow to the buffer (it owns the
    scale-from-zero wait), bounded by the cold stampede cap."""
    router = make_router(cids=(), default_replica_inflight=4)
    stub = make_stub()

    async def forward(prefer):
        assert prefer == []
        return ForwardResult(status=200, body=b"{}")

    out = await router.submit(stub, "t", _body(8), forward)
    assert out.status == 200
    await router.stop()


# ---------------------------------------------------------------------------
# fleet: affinity placement + drain
# ---------------------------------------------------------------------------

async def test_same_prefix_routes_to_same_replica():
    router = make_router(cids=("r0", "r1", "r2"))
    stub = make_stub()
    chosen = []

    def fwd():
        async def forward(prefer):
            # the buffer honors preference order when tokens allow — model
            # the happy path: first preferred replica serves
            cid = prefer[0] if prefer else "r?"
            chosen.append(cid)
            return ForwardResult(status=200, body=b"{}", container_id=cid)
        return forward

    body = _body(200)                   # >1 affinity block of prefix
    for _ in range(6):
        out = await router.submit(stub, "t", body, fwd())
        assert out.status == 200
    await router.stop()
    # first pick is JSQ (no table entry yet); every later request follows
    # the recorded replica
    assert len(set(chosen[1:])) == 1
    assert router.affinity.stats()["hits"] >= 4


@pytest.mark.parametrize("disagg", [True, False], ids=["on", "off"])
async def test_disagg_biases_long_prompts_to_the_prefill_partition(disagg):
    """Disaggregated placement (ISSUE 16) through the real router over a
    mixed queue: with it on, prompts past ``disagg_prefill_tokens`` land on
    the prefill partition (sorted ids, the first ceil(0.5 x 4) = r0, r1),
    short chats and KV-adopting resubmissions on the decode rest; with it
    off, placement ignores the split. Nothing is shed either way."""
    router = make_router(cids=("r0", "r1", "r2", "r3"),
                         disagg_enabled=disagg, disagg_prefill_tokens=512,
                         disagg_prefill_fraction=0.5, max_queue_depth=10000)
    stub = make_stub()
    placed = {"long": [], "short": [], "adopt": []}

    def fwd(kind):
        async def forward(prefer):
            placed[kind].append(prefer[0])
            return ForwardResult(status=200, body=b"{}",
                                 container_id=prefer[0])
        return forward

    async def one(i):
        kind = ("long", "short", "short", "adopt", "short")[i % 5]
        n = 48 if kind == "short" else 640
        payload = {"tokens": [(i * 17 + j) % 251 + 1 for j in range(n)],
                   "max_new_tokens": 16}
        if kind == "adopt":
            payload["adopt_kv"] = {"key": "k", "n_tokens": 600}
        res = await router.submit(stub, "mix", json.dumps(payload).encode(),
                                  fwd(kind))
        return res.status

    statuses = await asyncio.gather(*[one(i) for i in range(60)])
    await router.stop()
    assert statuses.count(200) == 60
    prefill, decode = {"r0", "r1"}, {"r2", "r3"}
    if disagg:
        assert set(placed["long"]) <= prefill, placed["long"]
        assert set(placed["short"]) <= decode, placed["short"]
        assert set(placed["adopt"]) <= decode, placed["adopt"]
    else:
        # no split: short chats are not kept off r0 / r1
        assert set(placed["short"]) & prefill, placed["short"]


async def test_drain_replica_stops_routing_and_waits_for_inflight():
    router = make_router(cids=("r0", "r1"), drain_timeout_s=2.0)
    stub = make_stub()
    release = asyncio.Event()
    targets = []

    async def slow_forward(prefer):
        targets.append(prefer[0])
        await release.wait()
        return ForwardResult(status=200, body=b"{}",
                             container_id=prefer[0])

    # land one in-flight request, learn its replica
    t1 = asyncio.create_task(router.submit(stub, "t", _body(8), slow_forward))
    while not targets:
        await asyncio.sleep(0)
    victim = targets[0]

    # drain must wait for the in-flight request, then report drained
    drain = asyncio.create_task(router.drain_replica(victim))
    await asyncio.sleep(0.05)
    assert not drain.done()             # still waiting on in-flight
    release.set()
    assert (await t1).status == 200
    assert await drain is True
    assert router.admission.is_draining(victim)

    # new traffic routes around the draining replica
    async def fast_forward(prefer):
        assert victim not in prefer
        return ForwardResult(status=200, body=b"{}",
                             container_id=prefer[0])

    out = await router.submit(stub, "t", _body(8), fast_forward)
    assert out.status == 200
    await router.stop()


async def test_stream_admission_sheds_and_budgets_ride_release():
    router = make_router(cids=("r0", "r1"), max_queue_depth=1)
    stub = make_stub()

    # admitted: preference order present, budget slot held until release
    shed, prefer = await router.admit_stream(stub, "t", _body(64))
    assert shed is None and set(prefer) == {"r0", "r1"}
    release = router.stream_started(stub, _body(64), prefer[0])
    assert router.budgets.inflight(prefer[0]) == 1
    release()
    release()                            # idempotent (close can race)
    assert router.budgets.inflight(prefer[0]) == 0
    # the stream recorded affinity: the next stream prefers its replica
    _, prefer2 = await router.admit_stream(stub, "t", _body(64))
    assert prefer2[0] == prefer[0]

    # queue full → stream sheds like the buffered path
    router.admission.max_queue_depth = 0
    shed, prefer3 = await router.admit_stream(stub, "t", _body(64))
    assert shed is not None and shed.status == 429 and prefer3 == []
    assert dict(shed.headers).get("Retry-After")
    await router.stop()


async def test_forward_exception_surfaces_as_502():
    router = make_router(cids=("r0",))
    stub = make_stub()

    async def broken_forward(prefer):
        raise RuntimeError("boom")

    out = await router.submit(stub, "t", _body(8), broken_forward)
    assert out.status == 502
    # budget slot was released despite the exception
    assert router.budgets.inflight("r0") == 0
    await router.stop()


async def test_pressure_signal_feeds_autoscaler():
    router = make_router(cids=("r0",), default_replica_inflight=1,
                         max_queue_depth=4)
    stub = make_stub()
    release = asyncio.Event()

    async def blocking_forward(prefer):
        await release.wait()
        return ForwardResult(status=200, body=b"{}", container_id="r0")

    tasks = [asyncio.create_task(
        router.submit(stub, "t", _body(8), blocking_forward))
        for _ in range(6)]               # 4 under the cap, 2 shed
    await asyncio.sleep(0)
    assert router.queue_depth("s") >= 3  # front-door queue the buffer
    #                                      can't see — autoscaler input
    assert router.pressure("s") == 1.0   # shedding saturates the signal
    # dispatch samples capacity once it runs
    for _ in range(100):
        await asyncio.sleep(0.01)
        if router.signals.queue_depth("s") > 0:
            break
    assert router.signals.queue_depth("s") > 0
    release.set()
    results = await asyncio.gather(*tasks)
    assert sorted(r.status for r in results) == [200] * 4 + [429] * 2
    await router.stop()


def test_spec_sample_aggregates_fleet_acceptance():
    """ISSUE 5: heartbeated per-engine spec counters fold into one
    fleet-wide acceptance rate (tpu9_router_spec_* + router snapshot)."""
    from tpu9.router.signals import RouterSignals
    sig = RouterSignals()
    sig.spec_sample([
        {"spec_proposed": "800", "spec_accepted": "600"},   # store hashes
        {"spec_proposed": 200, "spec_accepted": 100},       # are stringly
        None,                                               # dead replica
        {"queued": 3},                                      # spec off
    ])
    snap = sig.snapshot("s")
    assert snap["fleet_spec_proposed"] == 1000
    assert snap["fleet_spec_accepted"] == 700
    assert snap["fleet_spec_acceptance_rate"] == 0.7
    from tpu9.observability.metrics import metrics
    assert metrics.gauges.get("tpu9_router_spec_acceptance_rate") == 0.7


# ---------------------------------------------------------------------------
# gray-failure ejection (ISSUE 14): stalled health folds into routing
# ---------------------------------------------------------------------------

async def test_stalled_health_ejects_like_draining_and_recovers():
    router = make_router(cids=("r0", "r1"))
    stub = make_stub()
    # seed an affinity record onto the soon-to-stall replica
    body = _body(200)
    router.affinity.record_served(body, "r1")

    assert router.affinity._table                    # record landed
    router.note_replica_health("r1", "stalled", reason="no_progress")
    assert router.admission.is_stalled("r1")
    assert not router.admission.is_draining("r1")    # separate ledgers
    # affinity entries dropped: prefix traffic re-homes NOW, not at TTL
    assert not any(cid == "r1"
                   for cid, _ in router.affinity._table.values())

    async def forward(prefer):
        assert "r1" not in prefer, prefer
        return ForwardResult(status=200, body=b"{}", container_id="r0")

    for _ in range(4):
        out = await router.submit(stub, "t", _body(8), forward)
        assert out.status == 200

    # recovery: a healthy heartbeat restores routing immediately
    router.note_replica_health("r1", "ok")
    assert not router.admission.is_stalled("r1")

    async def forward_both(prefer):
        assert set(prefer) == {"r0", "r1"}
        return ForwardResult(status=200, body=b"{}", container_id="r1")

    out = await router.submit(stub, "t", _body(8), forward_both)
    assert out.status == 200
    await router.stop()


async def test_stalled_heartbeat_stats_eject_at_dispatch_time():
    """The dispatch path reads `health` off the pressure stats it already
    fetches: a stalled verdict ejects the replica even with no gateway
    observer folding health (bench driving the router directly)."""
    router = make_router(cids=("r0", "r1"))
    stub = make_stub()
    await router.store.hmset("llm:pressure:r1",
                             {"health": "stalled",
                              "health_reason": "no_progress_with_queued_work",
                              "queued": 0, "ts": time.time()})
    await router.store.hmset("llm:pressure:r0",
                             {"health": "ok", "queued": 0,
                              "ts": time.time()})

    async def forward(prefer):
        assert prefer and "r1" not in prefer, prefer
        return ForwardResult(status=200, body=b"{}", container_id="r0")

    out = await router.submit(stub, "t", _body(8), forward)
    assert out.status == 200
    assert router.admission.is_stalled("r1")
    # fleet capacity shrank to the healthy replica's budget only — the
    # autoscaler's queue_sample sees the missing replica as pressure
    order, budgets, capacity, _, _ = await router._preference(
        "s", _body(8), await router._running("s"))
    assert "r1" not in budgets and "r1" not in order
    await router.stop()


async def test_stalled_mark_ttl_expiry_reprobes_replica():
    """With no fresh verdict renewing the mark, expiry puts the replica
    back in the candidate set (the recovery probe for observer-less
    drivers)."""
    router = make_router(cids=("r0", "r1"), health_eject_ttl_s=0.05)
    router.note_replica_health("r1", "stalled")
    assert [s.container_id for s in await router._running("s")] == ["r0"]
    await asyncio.sleep(0.08)
    assert {s.container_id for s in await router._running("s")} == \
        {"r0", "r1"}
    await router.stop()


async def test_unknown_health_state_ejects_not_restores():
    """Review regression: the gauges map unknown verdicts to stalled
    (never-look-healthy); routing must agree — garbage from a
    version-skewed runner ejects, only known-routable states restore."""
    router = make_router(cids=("r0", "r1"))
    router.note_replica_health("r1", "stalled")
    assert router.admission.is_stalled("r1")
    router.note_replica_health("r1", "STALLED???")
    assert router.admission.is_stalled("r1")       # garbage ≠ recovery
    router.note_replica_health("r1", "degraded")
    assert not router.admission.is_stalled("r1")   # degraded still routes
    await router.stop()


# ---------------------------------------------------------------------------
# deadline propagation (ISSUE 15)
# ---------------------------------------------------------------------------

async def test_expired_deadline_is_504_at_the_door():
    """A request already past its propagated budget is never queued and
    never dispatched — 504 without Retry-After (the budget is spent)."""
    router = make_router()
    calls = []

    async def forward(prefer):
        calls.append(prefer)
        return ForwardResult(status=200, body=b"{}")

    res = await router.submit(make_stub(), "t", _body(4), forward,
                              deadline_mono=time.monotonic() - 0.1)
    assert res.status == 504
    assert b"deadline_exceeded" in res.body
    assert "Retry-After" not in dict(res.headers)
    assert calls == []
    await router.stop()


async def test_expired_deadline_stream_shed_at_the_door():
    router = make_router()
    shed, prefer = await router.admit_stream(
        make_stub(), "t", _body(4),
        deadline_mono=time.monotonic() - 0.1)
    assert shed is not None and shed.status == 504
    assert prefer == []
    await router.stop()


async def test_live_deadline_clamps_queue_wait_not_dispatch():
    """A healthy request with remaining budget dispatches normally; one
    whose budget expires while QUEUED is shed by the submit deadline arm
    instead of waiting out the full queue-wait SLO."""
    router = make_router()

    async def forward(prefer):
        return ForwardResult(status=200, body=b"{}", container_id="r0")

    res = await router.submit(make_stub(), "t", _body(4), forward,
                              deadline_mono=time.monotonic() + 30.0)
    assert res.status == 200

    # saturated fleet: the dispatcher can never launch; the 0.3s budget
    # must answer the caller LONG before max_queue_wait_s (30s)
    slow = make_router(cids=("r0",), default_replica_inflight=1,
                       max_replica_inflight=1)
    assert slow.budgets.try_acquire("r0", 1)      # eat the only slot
    t0 = time.monotonic()
    res = await slow.submit(make_stub(), "t", _body(4), forward,
                            deadline_mono=time.monotonic() + 0.3)
    waited = time.monotonic() - t0
    assert res.status in (503, 504)
    assert waited < 5.0, waited
    await router.stop()
    await slow.stop()


def test_note_dispatch_failure_drops_affinity_not_routing():
    """Gateway failover feedback (ISSUE 15): a failed dispatch drops the
    replica's affinity entries (repeat prefixes re-home immediately) but
    does NOT eject it from routing — eligibility is the health plane's
    verdict, not one failed request's."""
    router = make_router()
    body = _body(8)
    router.affinity.record_served(body, "r0")
    assert router.affinity.order(body, ["r0", "r1"], {"r0": 0, "r1": 0},
                                 set())[0] == "r0"
    router.note_dispatch_failure("r0")
    # no affinity steer left toward r0 ...
    hits0 = router.affinity.hits
    router.affinity.order(body, ["r0", "r1"], {"r0": 0, "r1": 0}, set())
    assert router.affinity.hits == hits0
    # ... and r0 is still routable (not stalled, not draining)
    assert not router.admission.is_stalled("r0")
    assert not router.admission.is_draining("r0")
