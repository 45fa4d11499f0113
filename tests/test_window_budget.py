"""A lane's budget ends on the device (ISSUE 65): the decode program is told
how many steps of a window each lane may run and parks the lane itself, so
the scheduler sizes a window by the lane with the MOST left. The program
family by family (a K = 8 call against K = 1 calls, bit for bit, and what a
parked lane leaves alone), the scheduler's table, and the engine under
staggered budgets: the tokens each request gets alone, no block beyond a
reservation, windows that stay wide, the prefix cache's pages intact."""

import asyncio
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_granite_layers import ONE as GRANITE
from test_hybrid_layers import SMALL as LING
from test_kimi_layers import SMALL as KIMI
from test_lfm2_layers import SMALL as LFM2
from test_looped import TINY as OURO
from test_nemotron_layers import SMALL as NEMOTRON
from test_summary_attention import TINY as EVABYTE
from tpu9.models import init_decoder, kvstate, moe
from tpu9.models.llama import LLAMA_PRESETS
from tpu9.models.mixtral import MIXTRAL_PRESETS
from tpu9.serving.engine import EngineConfig, InferenceEngine
from tpu9.serving.graphs import GraphFactory
from tpu9.serving.kvpool import KvPool
from tpu9.serving.schedule import WindowScheduler
from tpu9.serving.shard import make_policy
from tpu9.serving.shard.policy import SingleDevicePolicy

LLAMA = replace(LLAMA_PRESETS["llama-tiny"], dtype=jnp.float32)
MIXTRAL = replace(MIXTRAL_PRESETS["mixtral-tiny"], dtype=jnp.float32)

# ---------------------------------------------------------------------------
# the program: a K = 8 call with steps_left [8, 3, 0]
# ---------------------------------------------------------------------------

B, S, BS, C, K = 3, 256, 16, 16, 8
# lane 0 crosses a page (and EvaByte's window) at 128; lane 1 is parked at
# 64, a page's first row and the first of EvaByte's second window, which its
# parked steps must not open; lane 2 sits every step out, mid-page
LENS = (123, 61, 30)
STEPS = (8, 3, 0)
# family -> (configuration, int8 pool): every kind of state a lane can hold
FAMILIES = {
    "paged-gqa": (LLAMA, False),
    "paged-gqa-int8": (LLAMA, True),
    "held-experts": (MIXTRAL, False),
    "kda-and-latent-pages": (LING, False),
    "latent-pages": (KIMI, False),
    "ssm-state": (GRANITE, False),
    "ssm-state-latent-experts": (NEMOTRON, False),
    "convolution-tails": (LFM2, False),
    "summary-cache": (EVABYTE, False),
    "looped": (OURO, False),
}


def _random_state(cfg, ecfg, quant, rng):
    """A pool, a table and the lanes' state of random content: every lane
    its own blocks (none block 0, the trash block), in every column."""
    pool = KvPool(cfg, ecfg, quant, SingleDevicePolicy())
    kv = {}
    for name, (shape, dt) in pool.array_shapes().items():
        if name == kvstate.TABLE:
            lanes, mb = shape
            kv[name] = jnp.asarray(
                1 + np.arange(lanes * (mb - 1)).reshape(lanes, mb - 1)
                if mb > 1 else np.zeros((lanes, 0)), jnp.int32)
            kv[name] = jnp.pad(kv[name], ((0, 0), (0, 1)))   # trash column
        elif np.dtype(dt) == np.int8:
            kv[name] = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        elif name.endswith("_scale"):
            kv[name] = jnp.asarray(rng.uniform(0.005, 0.02, shape), dt)
        else:
            kv[name] = jnp.asarray(0.3 * rng.standard_normal(shape), dt)
    return kv


def _valid_rows(cfg, kv, lane: int, n_tokens: int) -> dict:
    """The entries a lane of ``n_tokens`` tokens holds, in entry order, of
    each plane of the pool (an int8 pool's dequantized, latents unpacked)."""
    row = kv[kvstate.TABLE][lane]
    n = int(cfg.kv_entries(n_tokens))
    out = {}
    for name in ("k", "v"):
        blocks = np.asarray(kvstate.read_blocks(kv, name, row))
        flat = blocks.reshape((blocks.shape[0], -1) + blocks.shape[3:])
        out[name] = flat[:, :n]
    return out


def _lane_state(cfg, kv, lane: int) -> dict:
    return {name: np.asarray(kv[name][:, lane])
            for name in kvstate.lane_shapes(cfg, 1)}


def _same(got: dict, want: dict, what: str) -> None:
    assert set(got) == set(want)
    for name in want:
        np.testing.assert_array_equal(got[name], want[name],
                                      err_msg=f"{what}: {name}")


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_window_parks_each_lane_at_its_own_number(family, monkeypatch):
    """One K = 8 call with ``steps_left`` [8, 3, 0] against eight K = 1
    calls that give lane 0 a step each, lane 1 its first three and lane 2
    none: the same tokens, lengths and state bit for bit — and lane 1's five
    parked steps, like lane 2's eight, move nothing the lane holds."""
    cfg, quant = FAMILIES[family]
    # a test's widths: the step's form of an expert layer, not the one-hot
    monkeypatch.setattr(moe, "HELD_MIN_STACK_BYTES", 0)
    ecfg = EngineConfig(max_batch=B, max_seq_len=S, decode_steps=(1, K),
                        kv_block_size=BS, kv_pool_blocks=B * (S // BS + 4),
                        prefill_chunk=C, kv_quant="int8" if quant else "")
    graphs = GraphFactory(cfg, ecfg, SingleDevicePolicy(), chunk=C,
                          kv_quant=quant)
    params = init_decoder(jax.random.PRNGKey(65), cfg)
    rng = np.random.default_rng(65)
    start = _random_state(cfg, ecfg, quant, rng)
    last = jnp.asarray([[7], [11], [13]], jnp.int32)
    clen = jnp.asarray(LENS, jnp.int32)
    key = jax.random.PRNGKey(0)

    def fresh():                                    # the pool is donated
        return {n: jnp.array(a) for n, a in start.items()}

    last8, kv8, clen8, _, toks8, *beside8 = graphs.decode_k(K)(
        params, fresh(), last, clen, jnp.asarray(STEPS, jnp.int32), key)
    toks8 = np.asarray(toks8)

    kv, step_last, step_len, r = fresh(), last, clen, key
    toks, beside, after_three = [], [], None
    for j in range(K):
        step_last, kv, step_len, r, tok, *rest = graphs.decode_k(1)(
            params, kv, step_last, step_len,
            jnp.asarray([s > j for s in STEPS], jnp.int32), r)
        toks.append(np.asarray(tok)[0])
        beside.append([np.asarray(x)[0] for x in rest])
        if j == 2:
            after_three = (_valid_rows(cfg, kv, 1, LENS[1] + 3),
                           _lane_state(cfg, kv, 1))
    toks = np.stack(toks)

    # the tokens: lane 0's eight, lane 1's three
    np.testing.assert_array_equal(toks8[:, 0], toks[:, 0])
    np.testing.assert_array_equal(toks8[:3, 1], toks[:3, 1])
    assert int(last8[0, 0]) == int(step_last[0, 0]) == toks[-1, 0]
    # ... and what rides beside them (exit passes, chosen experts)
    for got, want in zip(beside8, zip(*beside)):
        got, want = np.asarray(got), np.stack(want)
        np.testing.assert_array_equal(got[:, 0], want[:, 0])
        np.testing.assert_array_equal(got[:3, 1], want[:3, 1])
    # the lengths: a parked lane's stands where its last live step left it
    assert np.asarray(clen8).tolist() == np.asarray(step_len).tolist() \
        == [n + s for n, s in zip(LENS, STEPS)]
    # every array the program carries, bit for bit
    _same({n: np.asarray(a) for n, a in kv8.items()},
          {n: np.asarray(a) for n, a in kv.items()}, "K = 8 against 8 x 1")
    # lane 1's five parked steps: its rows and its state are where its
    # third step left them
    _same(_valid_rows(cfg, kv8, 1, LENS[1] + 3), after_three[0],
          "lane 1's rows")
    _same(_lane_state(cfg, kv8, 1), after_three[1], "lane 1's state")
    # lane 2 is untouched
    _same(_valid_rows(cfg, kv8, 2, LENS[2]),
          _valid_rows(cfg, start, 2, LENS[2]), "lane 2's rows")
    _same(_lane_state(cfg, kv8, 2), _lane_state(cfg, start, 2),
          "lane 2's state")
    # and lane 0 moved: the comparison above compared something
    for name, before in _lane_state(cfg, start, 0).items():
        assert (np.asarray(kv8[name][:, 0]) != before).any(), name
    # state kept a block is the prefill's: no decode step writes it
    for name in kvstate.block_tail_shapes(cfg, 1):
        np.testing.assert_array_equal(np.asarray(kv8[name]),
                                      np.asarray(start[name]))


def test_a_parked_lane_of_the_summary_cache_writes_to_the_trash_block():
    """EvaByte's lane parked at a window's first position has NOT
    summarised the window it closed, so the entry of its next position lies
    inside that window's rows: its parked steps write through a table of
    trash blocks, and every block of its own keeps every row."""
    cfg = EVABYTE
    ecfg = EngineConfig(max_batch=B, max_seq_len=S, decode_steps=(1, K),
                        kv_block_size=BS, kv_pool_blocks=B * (S // BS + 4),
                        prefill_chunk=C)
    graphs = GraphFactory(cfg, ecfg, SingleDevicePolicy(), chunk=C)
    params = init_decoder(jax.random.PRNGKey(65), cfg)
    start = _random_state(cfg, ecfg, False, np.random.default_rng(3))
    w = cfg.attn_window
    # the lane's next position opens window 1; the entry it would take
    assert cfg.kv_entry(w) < cfg.kv_entries(w)
    _, kv, clen, *_ = graphs.decode_k(K)(
        params, {n: jnp.array(a) for n, a in start.items()},
        jnp.asarray([[7], [11], [13]], jnp.int32),
        jnp.asarray([w, w, 0], jnp.int32),
        jnp.asarray([0, 0, 0], jnp.int32), jax.random.PRNGKey(0))
    assert np.asarray(clen).tolist() == [w, w, 0]
    own = np.asarray(start[kvstate.TABLE])[:2, :-1].ravel()
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(kv[name])[:, own],
                                      np.asarray(start[name])[:, own])
        # (the trash block took the rows)
        assert (np.asarray(kv[name])[:, 0] != np.asarray(start[name])[:, 0]
                ).any()
    np.testing.assert_array_equal(np.asarray(kv[kvstate.TABLE]),
                                  np.asarray(start[kvstate.TABLE]))


# ---------------------------------------------------------------------------
# the scheduler: (lanes' remaining, room, steps in flight) -> (K, steps_left)
# ---------------------------------------------------------------------------

class _Req:
    def __init__(self, remaining):
        self.max_new_tokens, self.generated = remaining, []


def _scheduler(lanes, decode_steps=(1, 8), max_seq_len=512, queued=False):
    """A scheduler over a stand-in engine: ``lanes`` is a (remaining budget,
    cache room, steps in flight) a lane, None an idle one."""
    n = len(lanes)
    live = [lane is not None for lane in lanes]

    class _Queue:
        _queue = [_Req(4)] if queued else []

    class _Engine:
        ecfg = EngineConfig(max_batch=n, max_seq_len=max_seq_len,
                            decode_steps=decode_steps)
        active = np.asarray(live)
        slot_req = [_Req(lane[0]) if lane else None for lane in lanes]
        _host_len = np.asarray(
            [max_seq_len - 1 - lane[1] if lane else 0 for lane in lanes])
        _lane_inflight = np.asarray(
            [lane[2] if lane else 0 for lane in lanes], np.int32)
        paged, _wait_room, _queue, _pick_reason = False, [], _Queue(), ""

        @staticmethod
        def _room_for(req):
            return True

    return WindowScheduler(_Engine())


# lanes, then what the scheduler answers: K, steps_left, why, and the
# lane-steps the window's lanes sit out parked (K - min(K, steps_left) each)
TABLE = {
    # the parent's rule gave this batch K = 1: lane 1 has three steps left
    "one-nearly-done-lane": (
        [(100, 400, 0), (3, 400, 0), (50, 400, 0)], {}, 8, [100, 3, 50],
        "max", 5),
    "the-longest-decides": (
        [(5, 400, 0), (3, 400, 0)], {}, 1, [5, 3], "budget", 0),
    "the-longest-fills-eight-exactly": (
        [(8, 400, 0), (1, 400, 0)], {}, 8, [8, 1], "max", 7),
    "steps-in-flight-are-spent": (
        [(100, 400, 8), (10, 400, 8)], {}, 8, [92, 2], "max", 6),
    # a lane whose budget ends inside the window in flight: 0 in the next
    "budget-ends-in-flight": (
        [(100, 400, 8), (6, 400, 6)], {}, 8, [92, 0], "max", 8),
    # (a lane the window in flight parked has fewer steps in it than its K)
    "parked-in-flight-keeps-the-rest": (
        [(100, 400, 8), (20, 400, 3)], {}, 8, [92, 17], "max", 0),
    "room-is-the-same-number": (
        [(100, 4, 0), (100, 2, 0)], {}, 1, [4, 2], "budget", 0),
    "room-less-than-budget": (
        [(100, 400, 0), (100, 11, 8)], {}, 8, [100, 3], "max", 5),
    "an-idle-lane-has-none": (
        [None, (30, 400, 0), None], {}, 8, [0, 30, 0], "max", 0),
    # nothing fills a bucket: the smallest (the engine drains first)
    "every-lane-spent": (
        [(8, 400, 8), (3, 400, 3)], {}, 1, [0, 0], "budget", 2),
    "the-smallest-bucket-when-none-fills": (
        [(3, 400, 0), (2, 400, 0)], {"decode_steps": (4, 8)}, 4, [3, 2],
        "budget", 3),
    "a-middle-bucket": (
        [(7, 400, 0), (30, 400, 24)], {"decode_steps": (1, 4, 16)}, 4,
        [7, 6], "budget", 0),
    "an-admission-could-proceed": (
        [(100, 400, 0), (3, 400, 0), None], {"queued": True}, 1,
        [100, 3, 0], "admission", 0),
}


@pytest.mark.parametrize("case", list(TABLE))
def test_the_longest_lane_sizes_the_window(case):
    lanes, kw, k, left, why, parked = TABLE[case]
    scheduler = _scheduler(lanes, **kw)
    got = scheduler.lane_steps()
    assert got.dtype == np.int32 and got.tolist() == left
    assert scheduler.pick_steps(got) == k == scheduler.pick_steps()
    assert scheduler.engine._pick_reason == why
    live = np.asarray([lane is not None for lane in lanes])
    assert int((k - np.minimum(got, k))[live].sum()) == parked


# ---------------------------------------------------------------------------
# the engine: staggered budgets
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    return LLAMA, init_decoder(jax.random.PRNGKey(0), LLAMA)


def _engine(tiny, **kw):
    cfg, params = tiny
    base = dict(max_batch=8, max_seq_len=256, decode_steps=(1, 8),
                kv_block_size=16, kv_pool_blocks=96, prefill_chunk=16)
    base.update(kw)
    policy = base.pop("policy", None)
    return InferenceEngine(params, cfg, EngineConfig(**base), policy=policy)


def _serve(engine, requests, together=True):
    """``requests``: (prompt, max_new_tokens) each; together, or one after
    the other (each then alone in the batch)."""
    async def go():
        await engine.start()
        if together:
            out = await asyncio.gather(*(
                engine.generate(p, max_new_tokens=n) for p, n in requests))
        else:
            out = [await engine.generate(p, max_new_tokens=n)
                   for p, n in requests]
        await engine.stop()
        return out
    return asyncio.run(go())


def _prompts(n, rng, lo=5, hi=40):
    return [rng.integers(3, 250, int(rng.integers(lo, hi))).tolist()
            for _ in range(n)]


def _parked_by_the_records(engine) -> int:
    """Lane-steps parked, from what each window DELIVERED: its steps times
    its lanes, less the tokens its fan-out handed out (no request here meets
    an end of sequence, is cancelled or expires)."""
    return sum(r["k"] * r["batch"] - sum(r["tokens"].values())
               for r in engine.flight_records() if r["kind"] == "decode")


# six whole windows of decode steps for the longest; the others end inside
# windows, a few steps apart — under the parent's rule some lane was within
# eight steps of its end most of the time: this batch ran 58 steps in 30
# windows on the parent commit (1.93 a window), and runs 57 in 8 here
BUDGETS = (12, 20, 27, 35, 44, 49)


@pytest.mark.parametrize("topology", ["1x1", "2x1"])
def test_staggered_budgets_keep_the_window_wide(tiny, topology):
    requests = list(zip(_prompts(len(BUDGETS), np.random.default_rng(7)),
                        BUDGETS))
    policy = make_policy(topology)
    engine = _engine(tiny, policy=policy)
    got = _serve(engine, requests)
    alone = _serve(_engine(tiny, policy=policy), requests, together=False)
    # exactly each budget, and the tokens each request gets alone
    assert [len(t) for t in got] == list(BUDGETS)
    assert got == alone
    st = engine.stats()
    assert st["decode_steps"] / st["windows_processed"] >= 4, st
    # the counter, the records' ``parked`` and what the fan-outs delivered
    parked = st["decode_lane_steps_parked"]
    assert parked > 0
    assert parked == sum(r["parked"] for r in engine.flight_records()
                         if r["kind"] == "decode")
    assert parked == _parked_by_the_records(engine)
    picks = {r["pick"] for r in engine.flight_records()
             if r["kind"] == "decode"}
    assert picks <= {"max", "budget", "admission", "interleave"}
    assert "max" in picks


def test_no_block_is_taken_beyond_a_requests_own_tokens(tiny):
    """Random budgets and prompts through a small pool: a slot's blocks never
    pass the pages of ``prompt + max_new_tokens`` — the reservation's slack
    for windows that overshoot is only spare — and never its reservation."""
    rng = np.random.default_rng(11)
    requests = [(p, int(rng.integers(1, 60)))
                for p in _prompts(24, rng, 3, 70)]
    engine = _engine(tiny, max_batch=6, kv_pool_blocks=40)
    grow = engine.pool.ensure_slot_blocks
    worst = []

    def checked(slot, n_tokens):
        changed = grow(slot, n_tokens)
        req = engine.slot_req[slot] or engine._admitting
        held = len(engine.pool.slot_blocks[slot])
        own = -(-(len(req.prompt) + req.max_new_tokens) // 16)
        worst.append((held - own, held - engine.pool.slot_reserved[slot]))
        return changed

    engine.pool.ensure_slot_blocks = checked
    got = _serve(engine, requests)
    assert [len(t) for t in got] == [n for _, n in requests]
    assert worst and max(w[0] for w in worst) <= 0
    assert max(w[1] for w in worst) <= 0
    assert engine.stats()["decode_lane_steps_parked"] > 0
    assert engine.allocator.reserved == 0
    assert engine.allocator.used_count == 1          # the trash block
    assert got == _serve(_engine(tiny, max_batch=6, kv_pool_blocks=40),
                         requests, together=False)


def test_a_second_turn_hits_the_first_turns_pages(tiny):
    """With the prefix cache on, lanes that share a prompt's pages park
    beside each other inside wide windows: a parked lane's row lands past
    its own last token, never in a shared page — the second turn of the
    session and a request behind the same prompt read what a cold engine
    computes."""
    rng = np.random.default_rng(5)
    shared = rng.integers(3, 250, 48).tolist()          # three whole pages
    other = rng.integers(3, 250, 33).tolist()
    first = [(shared, 12), (shared + [9, 8, 7], 37), (other, 49)]
    engine = _engine(tiny, prefix_cache_blocks=32)
    cold = _engine(tiny)

    async def go(eng, requests):
        return await asyncio.gather(*(
            eng.generate(p, max_new_tokens=n) for p, n in requests))

    async def session(eng):
        await eng.start()
        turn1 = await go(eng, first)
        second = [(shared + turn1[0] + [5, 6], 21), (shared + [4], 30),
                  (other + turn1[2][:20], 17)]
        turn2 = await go(eng, second)
        await eng.stop()
        return turn1, turn2

    warm1, warm2 = asyncio.run(session(engine))
    cold1, cold2 = asyncio.run(session(cold))
    assert warm1 == cold1 and warm2 == cold2
    assert [len(t) for t in warm2] == [21, 30, 17]
    admits = [r for r in engine.flight_records() if r["kind"] == "admit"]
    assert sum(r["cached_tokens"] > 0 for r in admits[3:]) == 3, admits
    st = engine.stats()
    assert st["decode_lane_steps_parked"] > 0
    assert st["decode_steps"] / st["windows_processed"] >= 4


def test_a_traced_requests_decode_span_says_its_parked_steps(tiny):
    from tpu9.observability.trace import tracer
    engine = _engine(tiny, max_batch=2)
    trace_id = "65" * 16

    async def go():
        await engine.start()
        out = await asyncio.gather(
            engine.generate(list(range(3, 20)), max_new_tokens=12,
                            trace=(trace_id, "ab" * 8)),
            engine.generate(list(range(5, 30)), max_new_tokens=33))
        await engine.stop()
        return out

    out = asyncio.run(go())
    assert [len(t) for t in out] == [12, 33]
    spans = [s for s in tracer.finished
             if s.trace_id == trace_id and s.name == "engine.decode"]
    assert len(spans) == 1
    attrs = spans[0].attrs
    # eleven decode steps, the last of them inside a window of eight that
    # the other lane fills: the steps of its windows less its tokens
    assert attrs["tokens"] == 11
    steps = attrs["k1_windows"] + K * (attrs["windows"] - attrs["k1_windows"])
    assert attrs["parked_steps"] == steps - 11 > 0
