"""Unit tests for the request-survivability core (ISSUE 15):
watermark splice semantics, deadline deduction across attempts,
idempotent double-submit through the journal, failure classification,
SSE parsing, and the failover driver."""

import asyncio
import json
import time

import pytest

from tpu9.abstractions.common.buffer import ForwardResult
from tpu9.gateway import survival as sv
from tpu9.statestore import MemoryStore
from tpu9.utils.backoff import BackoffPolicy


# -- watermark splice ---------------------------------------------------------

def test_resume_payload_splices_at_the_watermark():
    res = sv.StreamResumption([1, 2, 3], 10,
                              {"tokens": [1, 2, 3], "max_new_tokens": 10,
                               "temperature": 0})
    for t in (7, 8, 9):
        res.note_token(t)
    body = json.loads(res.resume_payload())
    # delivered tokens JOIN the prompt; budget is what is still owed
    assert body["tokens"] == [1, 2, 3, 7, 8, 9]
    assert body["max_new_tokens"] == 7
    assert body["stream"] is True
    assert body["temperature"] == 0          # extra payload keys survive


@pytest.mark.parametrize("prompt,max_new,die_after", [
    ([3, 1, 4], 12, 5),
    ([1, 1], 8, 1),            # killed after the first token
    ([2, 4], 9, 8),            # killed with one token still owed
    ([7, 5], 16, 9),
    ([9, 11], 2, 1),           # the shortest stream a kill can split
    ([12, 3], 13, 12),
])
def test_splice_produces_duplicate_free_sequence_across_a_kill(
        prompt, max_new, die_after):
    """Simulate the whole failover: a deterministic 'model' generates
    f(prefix) token by token; the first replica dies mid-stream; the
    resumed attempt replays prompt+delivered and continues. The client
    must see exactly the sequence an unkilled replica would have sent,
    wherever in the stream the kill lands."""
    def model_next(prefix: list) -> int:
        return (sum(prefix) * 31 + len(prefix)) % 997

    def serve(prompt, max_new, die_after=None):
        toks, ctx = [], list(prompt)
        for i in range(max_new):
            if die_after is not None and i >= die_after:
                return toks, True            # replica died
            t = model_next(ctx)
            toks.append(t)
            ctx.append(t)
        return toks, False

    reference, died = serve(prompt, max_new)
    assert not died

    res = sv.StreamResumption(prompt, max_new, {"tokens": prompt,
                                                "max_new_tokens": max_new})
    got, died = serve(prompt, max_new, die_after=die_after)
    for t in got:
        res.note_token(t)
    assert died and res.watermark == die_after \
        and res.remaining == max_new - die_after
    body = json.loads(res.resume_payload())
    got2, died2 = serve(body["tokens"], body["max_new_tokens"])
    assert not died2
    for t in got2:
        res.note_token(t)
    # no duplicated, no skipped token across the splice
    assert res.delivered == reference
    assert res.done_event() == {"done": True, "tokens": reference}


def test_zero_remaining_needs_no_replay():
    res = sv.StreamResumption([1], 2, {})
    res.note_token(5)
    res.note_token(6)
    assert res.remaining == 0


def test_parse_llm_stream_body():
    ok = sv.parse_llm_stream_body(
        json.dumps({"tokens": [1, 2], "max_new_tokens": 4}).encode())
    assert ok == {"prompt": [1, 2], "max_new": 4,
                  "payload": {"tokens": [1, 2], "max_new_tokens": 4}}
    assert sv.parse_llm_stream_body(b"not json") is None
    assert sv.parse_llm_stream_body(b'{"tokens": []}') is None
    assert sv.parse_llm_stream_body(b'{"other": 1}') is None
    assert sv.parse_llm_stream_body(
        b'{"tokens": [1], "max_new_tokens": 0}') is None


# -- deadline deduction -------------------------------------------------------

def test_budget_header_mints_one_monotonic_deadline():
    ctx = sv.RequestContext.from_headers({sv.BUDGET_HEADER: "5.0"})
    r = ctx.remaining_s()
    assert r is not None and 4.5 < r <= 5.0
    assert not ctx.expired()
    assert sv.RequestContext.from_headers({}).remaining_s() is None
    assert sv.RequestContext.from_headers(
        {sv.BUDGET_HEADER: "garbage"}).remaining_s() is None
    # an explicit non-positive budget is expired at the door
    assert sv.RequestContext.from_headers(
        {sv.BUDGET_HEADER: "0"}).expired()


async def test_deadline_is_deducted_across_attempts_not_reset():
    """Each retry must see the ORIGINAL deadline minus spent time: the
    forwarded budget strictly decreases across attempts."""
    ctx = sv.RequestContext.from_headers({sv.BUDGET_HEADER: "10.0"})
    seen = []

    async def attempt(attempt, avoid):
        seen.append(ctx.remaining_s())
        await asyncio.sleep(0.05)            # this attempt SPENDS budget
        return ForwardResult(status=502, body=b"{}")

    budget = sv.FailoverBudget(3, BackoffPolicy(base_s=0.01, jitter=0.0),
                               deadline_mono=ctx.deadline_mono)
    result = await sv.submit_with_failover(attempt, budget)
    assert result.status == 502 and len(seen) == 3
    assert seen[0] > seen[1] > seen[2]
    assert seen[0] - seen[2] >= 0.1          # ≥ 2 × 50ms spent


def test_failover_budget_never_sleeps_past_the_deadline():
    b = sv.FailoverBudget(10, BackoffPolicy(base_s=60.0, jitter=0.0),
                          deadline_mono=time.monotonic() + 0.2)
    d = b.next_delay()
    assert d is not None and d <= 0.2


def test_failover_budget_exhausts_on_attempts_and_deadline():
    b = sv.FailoverBudget(2, BackoffPolicy(base_s=0.01, jitter=0.0))
    assert b.next_delay() is not None
    assert b.next_delay() is None            # 2 attempts total
    expired = sv.FailoverBudget(5, BackoffPolicy(base_s=0.01, jitter=0.0),
                                deadline_mono=time.monotonic() - 1)
    assert expired.next_delay() is None


# -- classification -----------------------------------------------------------

def test_classify_result_matrix():
    C = sv.classify_result
    assert C(200) == sv.OK
    assert C(502, b'{"error":"ClientConnectorError"}') == sv.RETRYABLE
    assert C(503, b'{"error": "not ready"}') == sv.RETRYABLE
    assert C(500, b'{"error":"RuntimeError: engine is dead: x"}') \
        == sv.RETRYABLE
    assert C(500, b'{"error":"engine failure: boom"}') == sv.RETRYABLE
    assert C(500, b'{"error":"engine stopped"}') == sv.RETRYABLE
    # router sheds / client errors / spent budgets are FINAL
    assert C(429, b"{}") == sv.FATAL
    assert C(503, b'{"error":"fleet at capacity"}') == sv.FATAL
    assert C(504, b'{"error":"deadline_exceeded"}') == sv.FATAL
    assert C(400, b"{}") == sv.FATAL
    assert C(500, b'{"error":"ZeroDivisionError"}') == sv.FATAL


# -- failover driver ----------------------------------------------------------

async def test_submit_with_failover_recovers_and_avoids_failed_replica():
    calls = []

    async def attempt(attempt, avoid):
        calls.append((attempt, set(avoid)))
        if attempt < 3:
            return ForwardResult(status=502, body=b"{}",
                                 container_id=f"r{attempt}")
        return ForwardResult(status=200, body=b"ok", container_id="r3")

    failovers = []
    budget = sv.FailoverBudget(3, BackoffPolicy(base_s=0.001, jitter=0.0))
    result = await sv.submit_with_failover(
        attempt, budget,
        on_failover=lambda a, failed, d: failovers.append(
            (a, failed.container_id, d)))
    assert result.status == 200
    assert calls == [(1, set()), (2, {"r1"}), (3, {"r1", "r2"})]
    assert [f[1] for f in failovers] == ["r1", "r2"]


async def test_seeded_chaos_through_the_router_loses_no_request():
    """A fake three-replica fleet behind the REAL FleetRouter and the REAL
    failover driver, under one seeded fault plan — crashes that keep a
    replica down for its next calls, stalls that end in a stream-gap 502,
    transport resets: every request must end in a 200, and the plan must
    really have injected faults and forced failovers. No clock in it: an
    outage is counted in calls and the backoff does not sleep."""
    from tpu9.config import RouterConfig
    from tpu9.router import FleetRouter
    from tpu9.testing.faults import FaultPlane, parse_spec
    from tpu9.types import (ContainerState, ContainerStatus, Stub,
                            StubConfig)

    class Fleet:
        states = [ContainerState(container_id=f"r{i}", stub_id="s",
                                 status=ContainerStatus.RUNNING.value,
                                 address=f"127.0.0.1:{9100 + i}")
                  for i in range(3)]

        async def containers_by_stub(self, stub_id, status=None):
            return list(self.states)

    plane = FaultPlane(parse_spec(
        "crash:prob=0.03,times=5;stall:prob=0.04;rpc_error:prob=0.05"),
        seed=1994)
    cfg = RouterConfig(default_replica_inflight=8, max_queue_depth=10000,
                       max_queue_wait_s=10.0, failover_max_attempts=6)
    router = FleetRouter(cfg, MemoryStore(), Fleet())
    stub = Stub(stub_id="s", name="s", workspace_id="w",
                config=StubConfig(timeout_s=30.0))
    injected = {"crash": 0, "stall": 0, "rpc_error": 0}
    down_for: dict = {}            # replica -> calls it still refuses
    failovers = []

    def forward_for(avoid):
        async def forward(prefer):
            # the buffer's avoid semantics: failed replicas are passed
            # over unless nothing else exists
            cands = [c for c in (prefer or ["r0"])
                     if c not in avoid] or list(prefer or ["r0"])
            cid = cands[0]
            if down_for.get(cid, 0) > 0:
                down_for[cid] -= 1
                return ForwardResult(
                    status=502, body=b'{"error":"ConnectRefused"}',
                    container_id=cid)
            for kind, status, body, outage in (
                    ("crash", 500, b'{"error":"engine failure: induced"}',
                     4),
                    ("rpc_error", 502, b'{"error":"ConnectionResetError"}',
                     0),
                    ("stall", 502, b'{"error":"stream_gap"}', 0)):
                if plane.fire(kind):
                    injected[kind] += 1
                    down_for[cid] = outage
                    return ForwardResult(status=status, body=body,
                                         container_id=cid)
            await asyncio.sleep(0)
            return ForwardResult(status=200, body=b'{"ok":1}',
                                 container_id=cid)
        return forward

    async def no_sleep(delay):
        await asyncio.sleep(0)

    async def one(i: int) -> int:
        body = json.dumps({"tokens": [i % 7, i % 11, i % 13],
                           "max_new_tokens": 8}).encode()

        async def attempt(attempt, avoid):
            return await router.submit(stub, "chaos", body,
                                       forward_for(avoid))

        budget = sv.FailoverBudget(cfg.failover_max_attempts,
                                   BackoffPolicy(base_s=0.001, jitter=0.0))
        res = await sv.submit_with_failover(
            attempt, budget, sleep=no_sleep,
            on_failover=lambda a, failed, d: failovers.append(a))
        return res.status

    try:
        statuses = await asyncio.gather(*[one(i) for i in range(120)])
    finally:
        await router.stop()
    assert statuses.count(200) == 120, [s for s in statuses if s != 200]
    assert failovers and sum(injected.values()) >= 5, (injected, failovers)


async def test_submit_with_failover_returns_last_failure_on_exhaustion():
    async def attempt(attempt, avoid):
        return ForwardResult(status=502, body=b'{"error":"x"}',
                             container_id="r1")

    budget = sv.FailoverBudget(2, BackoffPolicy(base_s=0.001, jitter=0.0))
    result = await sv.submit_with_failover(attempt, budget)
    assert result.status == 502


async def test_submit_with_failover_never_retries_fatal():
    calls = []

    async def attempt(attempt, avoid):
        calls.append(attempt)
        return ForwardResult(status=429, body=b"{}")

    budget = sv.FailoverBudget(5, BackoffPolicy(base_s=0.001, jitter=0.0))
    result = await sv.submit_with_failover(attempt, budget)
    assert result.status == 429 and calls == [1]


# -- SSE parser ---------------------------------------------------------------

def test_sse_parser_handles_split_frames_and_raw():
    p = sv.SseParser()
    assert p.feed(b'data: {"tok') == []
    evs = p.feed(b'en": 5}\n\ndata: {"done": true, "tokens": [5]}\n\n')
    assert evs == [{"token": 5}, {"done": True, "tokens": [5]}]
    assert p.feed(b": keepalive comment\n\n") == \
        [{"_raw": b": keepalive comment"}]
    assert p.feed(b"data: not-json\n\n") == [{"_raw": b"data: not-json"}]


# -- idempotency journal ------------------------------------------------------

async def test_journal_double_submit_is_idempotent():
    store = MemoryStore()
    j = sv.RequestJournal(store, ttl_s=60.0)
    state, rec = await j.begin("ws1", "req-1")
    assert state == sv.NEW
    # a concurrent/duplicate submit of the SAME id does not execute
    state2, rec2 = await j.begin("ws1", "req-1")
    assert state2 == sv.INFLIGHT
    # a different workspace's identical id is a different request
    state3, _ = await j.begin("ws2", "req-1")
    assert state3 == sv.NEW


async def test_journal_replays_completed_results():
    store = MemoryStore()
    j = sv.RequestJournal(store, ttl_s=60.0)
    await j.begin("ws", "r1")
    await j.finish("ws", "r1", 200, b'{"tokens": [1, 2]}', watermark=2,
                   attempts=2)
    state, rec = await j.begin("ws", "r1")
    assert state == sv.DONE
    assert rec["status"] == 200 and rec["watermark"] == 2
    assert sv.RequestJournal.replay_body(rec) == b'{"tokens": [1, 2]}'


async def test_journal_caps_replay_body():
    store = MemoryStore()
    j = sv.RequestJournal(store, ttl_s=60.0, body_cap=8)
    await j.begin("ws", "big")
    await j.finish("ws", "big", 200, b"x" * 100)
    state, rec = await j.begin("ws", "big")
    assert state == sv.DONE
    assert sv.RequestJournal.replay_body(rec) is None   # too big to replay


async def test_journal_update_records_watermark_and_attempts():
    store = MemoryStore()
    j = sv.RequestJournal(store, ttl_s=60.0)
    await j.begin("ws", "r2")
    await j.update("ws", "r2", watermark=17, attempts=2)
    state, rec = await j.begin("ws", "r2")
    assert state == sv.INFLIGHT
    assert rec["watermark"] == 17 and rec["attempts"] == 2


async def test_journal_clears_shed_and_5xx_outcomes():
    """A 429/503/504 told the CLIENT to retry — pinning that failure
    under its request id would make the instructed retry replay the
    failure instead of executing. Those outcomes clear the entry."""
    store = MemoryStore()
    j = sv.RequestJournal(store, ttl_s=60.0)
    for status in (429, 503, 504, 502, 500, 499):
        await j.begin("ws", f"r-{status}")
        await j.finish("ws", f"r-{status}", status, b"{}")
        state, _ = await j.begin("ws", f"r-{status}")
        assert state == sv.NEW, status          # retry executes afresh
    # deterministic client errors DO replay (a 400 is a 400 forever)
    await j.finish("ws", "r-400", 400, b'{"error":"bad"}')
    state, rec = await j.begin("ws", "r-400")
    assert state == sv.DONE and rec["status"] == 400


async def test_journal_expired_race_never_double_owns():
    """Two racers hitting an expired entry must not BOTH win ownership
    (the second cas closes the set-after-get race)."""
    store = MemoryStore()
    j = sv.RequestJournal(store, ttl_s=60.0)

    real_cas = store.cas
    calls = {"n": 0}

    async def flaky_cas(key, expected, value, ttl=None):
        calls["n"] += 1
        if calls["n"] == 1:
            # racer A's first cas "loses" (B won it just before)
            await real_cas(key, None, {"state": sv.INFLIGHT,
                                       "watermark": 0, "attempts": 1,
                                       "ts": 0}, ttl=ttl)
            return False
        return await real_cas(key, expected, value, ttl=ttl)

    store.cas = flaky_cas
    state, _ = await j.begin("ws", "raced")
    assert state == sv.INFLIGHT         # B owns it; A must not execute


async def test_journal_entry_expires():
    store = MemoryStore()
    j = sv.RequestJournal(store, ttl_s=0.05)
    await j.begin("ws", "r3")
    await asyncio.sleep(0.1)
    state, _ = await j.begin("ws", "r3")
    assert state == sv.NEW                   # idempotency window elapsed


async def test_journal_records_content_type_for_replay():
    store = MemoryStore()
    j = sv.RequestJournal(store, ttl_s=60.0)
    await j.begin("ws", "csv")
    await j.finish("ws", "csv", 200, b"a,b\n1,2\n", content_type="text/csv")
    _, rec = await j.begin("ws", "csv")
    assert rec["ctype"] == "text/csv"


async def test_journal_is_scoped_per_stub():
    store = MemoryStore()
    j = sv.RequestJournal(store, ttl_s=60.0)
    state, _ = await j.begin("ws", "rid", stub_id="stubA")
    assert state == sv.NEW
    # the same id against a DIFFERENT deployment is a different request
    state, _ = await j.begin("ws", "rid", stub_id="stubB")
    assert state == sv.NEW
    state, _ = await j.begin("ws", "rid", stub_id="stubA")
    assert state == sv.INFLIGHT


def test_resume_ended_on_eos_with_declared_eos():
    res = sv.StreamResumption([1, 2], 10, {"tokens": [1, 2],
                                           "max_new_tokens": 10,
                                           "eos_id": 7})
    res.note_token(4)
    assert not res.ended_on_eos
    res.note_token(7)
    assert res.ended_on_eos            # finished; a resume would sample
    #                                    past EOS — synthesize done instead
    # without a declared eos_id the gateway cannot know (documented gap)
    res2 = sv.StreamResumption([1], 10, {"tokens": [1]})
    res2.note_token(7)
    assert not res2.ended_on_eos
