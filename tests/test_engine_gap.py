"""A token's gap, told from inside (ISSUE 57): a window's period as its lanes
see it, the part of it the admission clock moved by, a stream's own gap at
retire, the runner's and the gateway's leg, and what a prefill dispatch held.
Event order and identities on the stamps the engine took — the same floats,
so ``==`` — and no wall-time threshold anywhere."""

import asyncio
import json

import jax
import pytest

from tpu9.models import init_decoder
from tpu9.models.llama import LLAMA_PRESETS
from tpu9.observability.trace import tracer
from tpu9.serving.engine import EngineConfig, InferenceEngine

ENGINE = dict(max_batch=2, max_seq_len=256, prefill_buckets=(32, 64),
              decode_steps=(1, 4), kv_block_size=16, kv_pool_blocks=48,
              prefill_chunk=32, prefix_cache_blocks=16)


@pytest.fixture(scope="module")
def tiny():
    cfg = LLAMA_PRESETS["llama-tiny"]
    return cfg, init_decoder(jax.random.PRNGKey(0), cfg)


class Watched:
    """An engine whose every fan-out is kept as the engine saw it: the
    window, its marks, and the lanes it delivered to with each lane's own
    previous stamp — copied before the next window moves them."""

    def __init__(self, tiny, **kw):
        cfg, params = tiny
        self.engine = eng = InferenceEngine(
            params, cfg, EngineConfig(**dict(ENGINE, **kw)))
        self.windows: list = []
        self.retired: list = []
        inner_gap, inner_end = eng._obs_gap, eng._obs_stream_gap

        def obs_gap(win, t_host0, delivered):
            before = {slot: (win.reqs[slot].gap_last
                             or win.reqs[slot].gap_first)
                      for slot in delivered}
            # the lanes the window before also delivered to: ONE period
            shared = sum(b is eng._gap_prev for b in before.values())
            inner_gap(win, t_host0, delivered)
            self.windows.append(dict(
                win=win, marks=eng._gap_marks(t_host0), before=before,
                shared=shared,
                reqs={slot: win.reqs[slot] for slot in delivered}))

        def obs_end(req):
            self.retired.append(dict(req=req, first=req.gap_first,
                                     last=req.gap_last, gap_max=req.gap_max,
                                     tokens=len(req.generated)))
            inner_end(req)

        eng._obs_gap, eng._obs_stream_gap = obs_gap, obs_end

    def lane_sums(self, windows=None) -> tuple:
        """(period, steps) summed over the lanes the window before also
        delivered to, of ``windows`` (default: the clean ones)."""
        if windows is None:
            windows = [x for x in self.windows if x["win"].clean]
        return (sum(x["shared"] * x["win"].period_s for x in windows),
                sum(x["shared"] * x["win"].k for x in windows))

    def sat_through(self, req) -> list:
        """The (period, admit part) of every window that delivered to
        ``req``, from the lane's own stamps."""
        out = []
        for w in self.windows:
            for slot, r in w["reqs"].items():
                if r is req:
                    b = w["before"][slot]
                    out.append((w["marks"][0] - b[0], w["marks"][1] - b[1]))
        return out


def _run(coro):
    return asyncio.run(coro)


def _prompt(seed: int, n: int) -> list:
    return [(seed * 131 + 7 * i) % 250 + 3 for i in range(n)]


def test_a_stream_alone_has_no_admit_part_and_its_periods_sum_to_its_span(tiny):
    w = Watched(tiny)
    eng = w.engine

    async def go():
        await eng.start()
        out = await eng.generate(_prompt(1, 20), max_new_tokens=24)
        await eng.stop()
        return out

    assert len(_run(go())) == 24
    (rec,) = w.retired
    periods = w.sat_through(rec["req"])
    assert len(periods) >= 2
    # the identity, on the stamps themselves
    assert sum(p for p, _ in periods) == rec["last"][0] - rec["first"][0]
    assert all(a == 0.0 for _, a in periods)
    assert rec["last"][1] - rec["first"][1] == 0.0
    assert rec["gap_max"] == max(p for p, _ in periods)
    st = eng.stats()
    assert st["gap_lane_admit_s"] == 0.0
    assert st["gap_lane_period_s"] == rec["last"][0] - rec["first"][0]
    assert st["gap_tokens"] == 23 and st["gap_admissions"] == 1
    assert st["gap_lane_steps"] == sum(x["win"].k for x in w.windows)
    # every window after the lane's first continued the one before, and no
    # admission touched any: all of them are clean
    assert [x["win"].clean for x in w.windows] == \
        [False] + [True] * (len(periods) - 1)
    assert st["gap_clean_lane_steps"] == sum(x["win"].k
                                             for x in w.windows[1:])
    assert st["gap_clean_lane_period_s"] == sum(p for p, _ in periods[1:])
    lat = st["latency"]
    assert lat["tpot_count"] == 1 and lat["gap_max_count"] == 1
    assert lat["tpot_mean_s"] == pytest.approx(
        (rec["last"][0] - rec["first"][0]) / 23, abs=1e-6)
    assert lat["gap_max_mean_s"] == pytest.approx(rec["gap_max"], abs=1e-6)


def _two_streams(w, first_len=20, second_len=100, first_out=64,
                 second_out=8, trace=None):
    eng = w.engine

    async def go():
        await eng.start()
        a = await eng.generate(_prompt(2, first_len), max_new_tokens=first_out,
                               stream=True, trace=trace)
        got = [await a.queue.get() for _ in range(6)]   # it is running
        b = await eng.generate(_prompt(3, second_len),
                               max_new_tokens=second_out, stream=True)
        for req in (a, b):
            while await req.queue.get() is not None:
                pass
        await eng.stop()
        return a, b, got

    return _run(go())


def test_an_admission_mid_stream_is_the_first_streams_admit_part(tiny):
    w = Watched(tiny)
    trace = ("9e" * 16, "7a" * 8)
    a, b, _ = _two_streams(w, trace=trace)
    eng = w.engine
    by_req = {id(r["req"]): r for r in w.retired}
    ra, rb = by_req[id(a)], by_req[id(b)]
    pa, pb = w.sat_through(a), w.sat_through(b)
    for rec, periods in ((ra, pa), (rb, pb)):
        assert sum(p for p, _ in periods) == rec["last"][0] - rec["first"][0]
        assert sum(x for _, x in periods) == rec["last"][1] - rec["first"][1]
    # the first stream sat behind the second's admission
    stall = ra["last"][1] - ra["first"][1]
    assert stall > 0.0
    assert ra["last"][2] - ra["first"][2] >= 1          # admissions behind
    behind = [p for p, x in pa if x > 0.0]
    assert behind and ra["gap_max"] >= max(behind)
    assert ra["gap_max"] == max(p for p, _ in pa)
    # the second's only admit part is what of its own episode came after
    # its first token (no other admission began after it)
    assert rb["last"][2] - rb["first"][2] == 0
    # the cumulative counters are the two streams', to the last bit
    st = eng.stats()
    assert st["gap_lane_period_s"] == sum(
        r["last"][0] - r["first"][0] for r in (ra, rb))
    assert st["gap_lane_admit_s"] == sum(
        r["last"][1] - r["first"][1] for r in (ra, rb))
    assert st["gap_tokens"] == (64 - 1) + (8 - 1)
    assert st["gap_admissions"] == 2
    assert eng._admit_clock(0.0) >= stall       # no episode is open
    # a window the admission touched is not among the clean ones
    assert any(x["win"].admit_s > 0.0 for x in w.windows)
    assert not any(x["win"].clean for x in w.windows
                   if x["win"].admit_s > 0.0)
    # steps and period weigh by lanes: both streams' where both ran
    assert st["gap_lane_steps"] == sum(len(x["reqs"]) * x["win"].k
                                       for x in w.windows)
    assert (st["gap_clean_lane_period_s"],
            st["gap_clean_lane_steps"]) == w.lane_sums()
    assert any(x["shared"] == 2 and x["win"].clean for x in w.windows)
    # the flight record of a window carries the three
    recs = [r for r in eng.flight_records() if r["kind"] == "decode"]
    assert all({"period_s", "admit_s", "lanes"} <= set(r) for r in recs)
    assert any(r["admit_s"] > 0 for r in recs)
    assert max(r["lanes"] for r in recs) == 2
    # the traced stream's ONE decode span tells the same
    spans = [s for s in tracer.export(trace_id=trace[0])
             if s["name"] == "engine.decode"]
    assert len(spans) == 1
    attrs = spans[0]["attributes"]
    assert attrs["tokens"] == 63
    assert attrs["admissions_behind"] == ra["last"][2] - ra["first"][2]
    assert attrs["admit_stall_ms"] == round(stall * 1e3, 3)
    assert attrs["gap_max_ms"] == round(ra["gap_max"] * 1e3, 3)
    assert attrs["gap_mean_ms"] == round(
        (ra["last"][0] - ra["first"][0]) / 63 * 1e3, 3)
    assert spans[0]["durationMs"] == pytest.approx(
        (ra["last"][0] - ra["first"][0]) * 1e3, abs=2e-3)
    lat = st["latency"]
    assert lat["tpot_count"] == 2 and lat["gap_max_count"] == 2


def test_an_interleaved_window_inside_a_long_admission_ends_a_period(tiny):
    """A 200-token prompt is seven chunks: the running stream's decode
    windows are dispatched between them and fanned out behind the
    admission, each ending a period of its own; only the first of them
    carries the admission, and none of them counts as clean."""
    w = Watched(tiny, admit_group_chunks=1, decode_steps=(1, 2, 8))
    a, b, _ = _two_streams(w, second_len=200, first_out=96)
    inter = [x for x in w.windows if x["win"].pick == "interleave"]
    assert len(inter) >= 2
    assert w.engine.stats()["admit_interleaved_windows"] == len(inter)
    for x in inter:
        assert any(r is a for r in x["reqs"].values())
        assert x["win"].delivered
        # dispatched inside the episode: the clock at its dispatch lies
        # past the clock at the delivery before the episode
        assert x["win"].gap_clock0 < x["marks"][1]
    pa = w.sat_through(a)
    ra = next(r for r in w.retired if r["req"] is a)
    assert sum(p for p, _ in pa) == ra["last"][0] - ra["first"][0]
    assert sum(x for _, x in pa) == ra["last"][1] - ra["first"][1]
    # the first of them carries the admission, the rest nearly nothing —
    # and none is clean: counting those would put steps with no time
    # beside them under ``decode_period_ms``
    assert inter[0]["win"].admit_s > 0.0
    st = w.engine.stats()

    def untouched(x):
        clock = x["marks"][1]
        return (x["win"].admit_s == 0.0 and clock == x["win"].gap_clock0
                and any(b[1] == clock and b[0] == x["marks"][0]
                        - x["win"].period_s for b in x["before"].values()))
    clean = [x for x in w.windows if untouched(x)]
    assert not any(x is y for x in clean for y in inter)
    assert (st["gap_clean_lane_period_s"],
            st["gap_clean_lane_steps"]) == w.lane_sums(clean)
    # so the interleaved windows' steps are among those the stall is
    # reckoned over, paid for at the clean step and not as admission
    assert st["gap_lane_steps"] - st["gap_clean_lane_steps"] >= sum(
        len(x["reqs"]) * x["win"].k for x in inter)


def test_a_readmitted_slot_never_inherits_the_old_requests_stamps(tiny):
    """One slot: the second request is admitted into the slot the first
    left, with the first's last window possibly still in flight."""
    w = Watched(tiny, max_batch=1)
    eng = w.engine

    async def go():
        await eng.start()
        a = await eng.generate(_prompt(4, 20), max_new_tokens=12, stream=True)
        b = await eng.generate(_prompt(5, 20), max_new_tokens=12, stream=True)
        for req in (a, b):
            while await req.queue.get() is not None:
                pass
        await eng.stop()
        return a, b

    a, b = _run(go())
    ra, rb = (next(r for r in w.retired if r["req"] is q) for q in (a, b))
    assert ra["last"][0] <= rb["first"][0]
    for req, rec in ((a, ra), (b, rb)):
        periods = w.sat_through(req)
        assert sum(p for p, _ in periods) == rec["last"][0] - rec["first"][0]
        # its first period starts at its OWN first token
        first_window = next(x for x in w.windows
                            if any(r is req for r in x["reqs"].values()))
        slot = next(s for s, r in first_window["reqs"].items() if r is req)
        assert first_window["before"][slot] is rec["first"]
    # told once each, though the slot's next window still names the first
    assert len(w.retired) == 2
    assert a.gap_last == () and b.gap_last == ()


def test_admit_tokens_count_the_suffix_behind_a_prefix_hit(tiny):
    eng = InferenceEngine(tiny[1], tiny[0], EngineConfig(**ENGINE))
    doc = _prompt(6, 96)                    # three whole chunks

    async def go():
        await eng.start()
        await eng.generate(doc, max_new_tokens=2)
        s0 = eng.stats()
        await eng.generate(doc + _prompt(7, 20), max_new_tokens=2)
        s1 = eng.stats()
        await eng.stop()
        return s0, s1

    s0, s1 = _run(go())
    assert (s0["admit_tokens"], s0["admit_tokens_padded"]) == (96, 96)
    assert s1["prefix_cache"]["hits"] == 1
    cached = 116 - (s1["admit_tokens"] - s0["admit_tokens"])
    assert cached >= 80 and cached % ENGINE["kv_block_size"] == 0
    # the suffix alone, and its chunks whole
    chunks = s1["admit_chunks"] - s0["admit_chunks"]
    assert chunks == -(-(116 - cached) // 32)
    assert s1["admit_tokens_padded"] - s0["admit_tokens_padded"] == chunks * 32
    assert s1["admit_tokens"] - s0["admit_tokens"] < \
        s1["admit_tokens_padded"] - s0["admit_tokens_padded"]


def test_the_runners_gap_is_told_once_and_over_the_tokens_less_one(tiny):
    eng = InferenceEngine(tiny[1], tiny[0], EngineConfig(**ENGINE))
    trace = ("5c" * 16, "3b" * 8)

    async def go():
        await eng.start()
        req = await eng.generate(_prompt(8, 20), max_new_tokens=9,
                                 stream=True, trace=trace)
        eng.note_ingest(req, (1000.0, 50.0), 50.001, 50.002)
        n = 0
        while await req.queue.get() is not None:
            n += 1
            if n == 1:
                eng.note_first_write(req)
        t_first = req.t_first_write_mono
        eng.note_last_write(req, t_first + 0.4)
        eng.note_last_write(req, t_first + 0.9)        # a second call: nothing
        short = await eng.generate(_prompt(9, 20), max_new_tokens=1,
                                   stream=True)
        eng.note_ingest(short, (1000.0, 50.0), 50.001, 50.002)
        while await short.queue.get() is not None:
            eng.note_first_write(short)
        eng.note_last_write(short, short.t_first_write_mono + 0.1)
        await eng.stop()
        return n

    assert _run(go()) == 9
    lat = eng.stats()["latency"]
    assert lat["runner_gap_count"] == 1                 # one token: no gap
    assert lat["runner_gap_mean_s"] == pytest.approx(0.4 / 8, abs=1e-6)
    spans = [s for s in tracer.export(trace_id=trace[0])
             if s["name"] == "runner.stream"]
    assert len(spans) == 1
    assert spans[0]["attributes"]["tokens"] == 9
    assert spans[0]["parentSpanId"] == trace[1]
    assert spans[0]["durationMs"] == pytest.approx(400.0, abs=1e-2)


# ---------------------------------------------------------------------------
# the gateway's leg: the relay of one attempt
# ---------------------------------------------------------------------------

class _Handle:
    container_id = "c-1"

    def __init__(self, chunks):
        self.chunks = chunks

    async def iter_chunks(self):
        for c in self.chunks:
            yield c


class _Sink:
    def __init__(self):
        self.wrote = []

    async def write(self, data):
        self.wrote.append(data)


def _sse(ev: dict) -> bytes:
    return f"data: {json.dumps(ev)}\n\n".encode()


@pytest.mark.parametrize("ending, told", [
    (_sse({"done": True, "tokens": [5, 6, 7]}), 1),
    (_sse({"error": "deadline_exceeded: budget exhausted mid-decode"}), 0),
    (b"", 0),                       # the replica died: no done event
])
def test_the_gateways_relay_tells_the_last_write_at_the_done_event(
        ending, told):
    import time

    from tpu9.gateway import survival as sv
    from tpu9.gateway.gateway import Gateway
    resume = sv.StreamResumption([1, 2, 3], 8)
    events = [_sse({"token": t}) for t in (5, 6, 7)]
    firsts, dones = [], []
    sink = _Sink()
    t0 = time.monotonic()
    outcome = _run(Gateway._relay_stream_events(
        None, _Handle(events + [ending]), resume, sink,
        lambda: firsts.append(time.monotonic()), dones.append))
    assert len(sink.wrote) >= 3 and resume.delivered == [5, 6, 7]
    assert len(firsts) == 1 and len(dones) == told
    if told:
        assert outcome.kind == "done"
        # the stamp of the LAST token's write, not of the done event
        assert t0 <= firsts[0] <= dones[0] <= time.monotonic()
    # a later attempt passes no callbacks: nothing is told
    assert _run(Gateway._relay_stream_events(
        None, _Handle(events + [ending]), resume, _Sink())) is not None
    assert len(firsts) == 1 and len(dones) == told
