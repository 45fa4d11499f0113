"""Fleet SLO / timeline / goodput layer (ISSUE 12): the bounded
time-series store, multi-window burn-rate evaluation, per-tenant goodput
decomposition, the autoscaler pressure fold, and stale-replica aging."""

import time

import pytest

from tpu9.config import SloConfig, SloObjectiveConfig
from tpu9.observability.slo import (GoodputAccountant, SloEvaluator,
                                    WASTE_BUCKETS)
from tpu9.observability.timeline import TimelineStore
from tpu9.router.signals import RouterSignals
from tpu9.types import Stub


# ---------------------------------------------------------------------------
# timeline store: bounded memory, query semantics
# ---------------------------------------------------------------------------

def test_timeline_ring_capacity_is_enforced():
    tl = TimelineStore(capacity=4)
    for i in range(100):
        tl.record("s", float(i))
    assert tl.sample_count() == 4                      # memory bound
    samples = tl.query(["s"])["s"]
    assert [v for _, v in samples] == [96.0, 97.0, 98.0, 99.0]


def test_timeline_max_series_evicts_longest_idle():
    tl = TimelineStore(capacity=8, max_series=2)
    tl.record("a", 1.0)
    tl.record("b", 2.0)
    tl.record("b", 3.0)                                # keeps b hot
    tl.record("c", 4.0)                                # evicts a (idle)
    assert tl.series_names() == ["b", "c"]


def test_timeline_query_prefix_since_limit():
    tl = TimelineStore(capacity=16)
    t0 = time.time()
    tl.record("router.s1.queue_depth", 1.0, ts=t0 - 100)
    tl.record("router.s1.queue_depth", 2.0, ts=t0)
    tl.record("router.s1.ttft_p95_s", 0.5, ts=t0)
    tl.record("engine.c1.tokens_per_sec", 9.0, ts=t0)
    out = tl.query(["router.s1.*"])
    assert set(out) == {"router.s1.queue_depth", "router.s1.ttft_p95_s"}
    assert tl.query(["router.s1.queue_depth"],
                    since=t0 - 1) == {"router.s1.queue_depth": [[t0, 2.0]]}
    limited = tl.query(["router.s1.queue_depth"], limit=1)
    assert limited["router.s1.queue_depth"] == [[t0, 2.0]]
    assert tl.query(["nope"]) == {}


def test_timeline_counter_delta_handles_reset():
    tl = TimelineStore(capacity=16)
    for v in (10.0, 20.0, 30.0):
        tl.record("c", v)
    delta, n = tl.counter_delta("c", 60.0)
    assert (delta, n) == (20.0, 3)
    # counter reset (replica restart): the rewound value stands in
    tl.record("c", 5.0)
    delta, _ = tl.counter_delta("c", 60.0)
    assert delta == 5.0


def test_timeline_prune_drops_idle_series():
    tl = TimelineStore(capacity=8)
    tl.record("dead", 1.0)
    tl.record("live", 1.0)
    assert tl.prune(idle_s=3600.0) == 0                # nothing is old
    assert tl.prune(idle_s=0.0) == 2                   # everything is
    assert tl.series_names() == []


# ---------------------------------------------------------------------------
# burn-rate evaluation
# ---------------------------------------------------------------------------

def _objectives():
    return [
        SloObjectiveConfig(name="ttft", kind="latency",
                           metric="ttft_p95_s", target=2.0,
                           attainment=0.99, fast_window_s=300.0,
                           slow_window_s=3600.0),
        SloObjectiveConfig(name="availability", kind="availability",
                           target=0.999, fast_window_s=300.0,
                           slow_window_s=3600.0),
    ]


def test_availability_burn_attributes_to_shed():
    tl = TimelineStore(capacity=64)
    ev = SloEvaluator(tl, _objectives())
    for sub, shed in ((0, 0), (40, 2), (90, 10)):
        tl.record("router.s1.submitted_total", float(sub))
        tl.record("router.s1.shed_total", float(shed))
    out = ev.evaluate("s1")
    avail = out["availability"]
    # 10 sheds over 100 outcomes vs a 0.1% error budget: burning hard
    assert avail["fast"]["burn"] > 1.0
    assert avail["fast"]["sheds"] == 10
    assert avail["fast"]["error_rate"] == pytest.approx(0.1)
    assert avail["attribution"] == "shed"
    assert avail["warning"]
    assert ev.max_fast_burn(out) >= avail["fast"]["burn"]


def test_latency_burn_thresholds_sampled_estimates():
    tl = TimelineStore(capacity=64)
    ev = SloEvaluator(tl, _objectives())
    for v in [0.1] * 5 + [3.0] * 5:                    # half over target
        tl.record("router.s1.ttft_p95_s", v)
    out = ev.evaluate("s1")
    ttft = out["ttft"]
    assert ttft["fast"]["error_rate"] == pytest.approx(0.5)
    assert ttft["fast"]["burn"] > 1.0                  # 0.5 / 0.01 budget
    assert ttft["fast"]["value"] == 3.0
    assert ttft["metric"] == "ttft_p95_s"


def test_no_data_reads_as_zero_burn():
    tl = TimelineStore(capacity=8)
    ev = SloEvaluator(tl, _objectives())
    out = ev.evaluate("ghost")
    for entry in out.values():
        assert entry["fast"]["burn"] == 0.0
        assert not entry["burning"]


def test_healthy_traffic_does_not_burn():
    tl = TimelineStore(capacity=64)
    ev = SloEvaluator(tl, _objectives())
    for i in range(10):
        tl.record("router.s1.submitted_total", float(i * 50))
        tl.record("router.s1.shed_total", 0.0)
        tl.record("router.s1.ttft_p95_s", 0.2)
    out = ev.evaluate("s1")
    assert out["availability"]["fast"]["burn"] == 0.0
    assert out["ttft"]["fast"]["burn"] == 0.0


# ---------------------------------------------------------------------------
# goodput decomposition
# ---------------------------------------------------------------------------

def test_goodput_decomposition_fractions_partition_chip_seconds(monkeypatch):
    now = [1000.0]
    monkeypatch.setattr(time, "monotonic", lambda: now[0])
    acc = GoodputAccountant(window_s=600.0)
    base = {"tokens_generated": 0, "spec_proposed": 0, "spec_accepted": 0,
            "graph_compile_stall_s": 0.0, "prefill_count": 0,
            "prefill_mean_s": 0.0, "decode_window_count": 0,
            "decode_window_mean_s": 0.0, "topo_n_chips": 1}
    acc.engine_sample("c1", "ws", "st", base)
    acc.router_sample("st", "ws", 0, 0, 0.0)
    now[0] += 10.0
    # 10s interval: 2s prefill + 6s decode busy, 1s recompile stall,
    # 800 useful tokens + 200 rolled-back draft tokens, 5 request-seconds
    # of queue wait, 10 sheds out of 100 outcomes
    acc.engine_sample("c1", "ws", "st", {
        "tokens_generated": 800, "spec_proposed": 250, "spec_accepted": 50,
        "graph_compile_stall_s": 1.0,
        "prefill_count": 4, "prefill_mean_s": 0.5,
        "decode_window_count": 60, "decode_window_mean_s": 0.1,
        "topo_n_chips": 1})
    acc.router_sample("st", "ws", 90, 10, 5.0)
    snap = acc.snapshot()
    row = snap["ws"]
    assert row["chip_seconds"] == pytest.approx(10.0)
    assert row["useful_tokens"] == 800
    assert row["rollback_tokens"] == 200
    assert row["goodput_tokens_per_chip_second"] == pytest.approx(80.0)
    waste = row["waste"]
    assert set(waste) == set(WASTE_BUCKETS)
    # busy 8s splits 80/20 by token usefulness; 1s stall; 1s idle splits
    # by demand weights (queue-wait 0.5, shed 0.1, reservation 0.4)
    assert row["goodput_frac"] == pytest.approx(0.64, abs=1e-6)
    assert waste["spec_rollback"] == pytest.approx(0.16, abs=1e-6)
    assert waste["recompile_stall"] == pytest.approx(0.10, abs=1e-6)
    assert waste["queue_wait"] == pytest.approx(0.05, abs=1e-6)
    assert waste["shed"] == pytest.approx(0.01, abs=1e-6)
    assert waste["idle_reservation"] == pytest.approx(0.04, abs=1e-6)
    # the acceptance invariant: each ∈ [0,1], sum with goodput == 1
    for frac in [row["goodput_frac"], *waste.values()]:
        assert 0.0 <= frac <= 1.0
    assert row["goodput_frac"] + sum(waste.values()) == pytest.approx(1.0)
    # per-stub detail carries the same shape
    assert "st" in row["stubs"]
    assert set(row["stubs"]["st"]["waste"]) == set(WASTE_BUCKETS)


def test_goodput_busy_overrun_is_clamped_not_negative(monkeypatch):
    """Accounting noise (phase seconds × chips exceeding metered time)
    must clamp, never produce negative idle or fractions > 1."""
    now = [0.0]
    monkeypatch.setattr(time, "monotonic", lambda: now[0])
    acc = GoodputAccountant(window_s=600.0)
    acc.engine_sample("c1", "ws", "st", {"tokens_generated": 0,
                                         "decode_window_count": 0,
                                         "decode_window_mean_s": 0.0,
                                         "topo_n_chips": 1})
    now[0] += 1.0
    acc.engine_sample("c1", "ws", "st", {"tokens_generated": 100,
                                         "decode_window_count": 100,
                                         "decode_window_mean_s": 0.05,
                                         "topo_n_chips": 1})   # 5s busy in 1s
    row = acc.snapshot()["ws"]
    total = row["goodput_frac"] + sum(row["waste"].values())
    assert total == pytest.approx(1.0)
    for frac in [row["goodput_frac"], *row["waste"].values()]:
        assert 0.0 <= frac <= 1.0


def test_goodput_counter_reset_and_no_data(monkeypatch):
    now = [0.0]
    monkeypatch.setattr(time, "monotonic", lambda: now[0])
    acc = GoodputAccountant(window_s=600.0)
    assert acc.snapshot() == {}
    acc.engine_sample("c1", "ws", "st", {"tokens_generated": 500,
                                         "topo_n_chips": 1})
    now[0] += 5.0
    # replica restarted: cumulative counter rewound — the new value is
    # the interval's delta, not a negative
    acc.engine_sample("c1", "ws", "st", {"tokens_generated": 40,
                                         "topo_n_chips": 1})
    row = acc.snapshot()["ws"]
    assert row["useful_tokens"] == 40


def test_goodput_usage_join_overrides_denominator(monkeypatch):
    now = [0.0]
    monkeypatch.setattr(time, "monotonic", lambda: now[0])
    acc = GoodputAccountant(window_s=600.0)
    acc.engine_sample("c1", "ws", "st", {"tokens_generated": 0,
                                         "topo_n_chips": 1})
    now[0] += 10.0
    acc.engine_sample("c1", "ws", "st", {"tokens_generated": 100,
                                         "topo_n_chips": 1})
    # usage.py metered 40 chip-seconds (4-chip replica the local
    # accumulation undercounted): the billing join wins
    row = acc.snapshot(usage_chip_seconds={"ws": 40.0})["ws"]
    assert row["chip_seconds"] == pytest.approx(40.0)
    assert row["metered_chip_seconds"] == pytest.approx(40.0)
    assert row["goodput_tokens_per_chip_second"] == pytest.approx(2.5)


# ---------------------------------------------------------------------------
# autoscaler pressure fold (router/signals.py)
# ---------------------------------------------------------------------------

def test_slo_burn_raises_pressure_before_queue_depth():
    sig = RouterSignals()
    sig.queue_sample("s1", depth=0, capacity=100)      # empty queue
    assert sig.pressure("s1") == 0.0
    sig.slo_sample("s1", 1.0)                          # budget-pace burn
    assert sig.pressure("s1") == pytest.approx(0.5)
    sig.slo_sample("s1", 2.0)                          # sustained burn
    assert sig.pressure("s1") == 1.0                   # saturates
    snap = sig.snapshot("s1")
    assert snap["slo_burn"] == 2.0
    assert snap["slo_pressure"] == 1.0


def test_stale_slo_evaluation_does_not_pin_pressure():
    sig = RouterSignals()
    sig.slo_sample("s1", 2.0)
    sig._slo_burn["s1"] = (2.0, time.monotonic() - 60.0)   # sampler died
    assert sig.slo_pressure("s1") == 0.0
    assert sig.pressure("s1") == 0.0


def test_queue_pressure_still_wins_when_higher():
    sig = RouterSignals()
    sig.queue_sample("s1", depth=80, capacity=100)
    sig.slo_sample("s1", 0.5)                          # pressure 0.25
    assert sig.pressure("s1") == pytest.approx(0.8)


def test_spec_sample_excludes_stale_heartbeats():
    sig = RouterSignals()
    fresh = {"spec_proposed": 10, "spec_accepted": 5, "ts": time.time()}
    stale = {"spec_proposed": 1000, "spec_accepted": 0,
             "ts": time.time() - 100}
    sig.spec_sample([fresh, stale], max_age_s=6.0)
    assert sig._spec_proposed == 10                    # corpse excluded
    assert sig._spec_accepted == 5
    sig.spec_sample([fresh, stale])                    # no aging: folds all
    assert sig._spec_proposed == 1010


# ---------------------------------------------------------------------------
# FleetObserver: heartbeat ingest, sampler tick, stale aging
# ---------------------------------------------------------------------------

class _FakeRouter:
    """Duck-typed FleetRouter face the observer samples."""

    def __init__(self, stubs):
        self.signals = RouterSignals()
        self._stubs = stubs

    def active_stubs(self):
        return self._stubs


def _observer(stubs=(), **cfg_kw):
    from tpu9.gateway.fleetobs import FleetObserver
    from tpu9.statestore import MemoryStore
    cfg = SloConfig(**cfg_kw)
    router = _FakeRouter(list(stubs))
    return FleetObserver(cfg, MemoryStore(), fleet_router=router), router


def test_ingest_heartbeat_records_engine_series_and_prices_mfu():
    obs, _ = _observer()
    obs.ingest_heartbeat(
        "c1", "ws", "st", token_pressure=0.4, active_streams=2,
        extra={"tokens_per_sec": 100.0, "kv_blocks_free": 7,
               "queued": 1, "spec_acceptance_rate": 0.5,
               "graph_compiles_post_warmup": 0,
               "decode_bytes_per_token_per_chip": 8.19e9,
               "decode_flops_per_token_per_chip": 1.97e12,
               "device_kind": "TPU v5e"})
    names = obs.timeline.series_names()
    assert "engine.c1.tokens_per_sec" in names
    assert "engine.c1.kv_blocks_free" in names
    # 100 tok/s × the constants above == exactly the v5e peaks → MBU=MFU=1
    mbu = obs.timeline.query(["engine.c1.mbu"])["engine.c1.mbu"][-1][1]
    mfu = obs.timeline.query(["engine.c1.mfu"])["engine.c1.mfu"][-1][1]
    assert mbu == pytest.approx(100 * 8.19e9 / (819.0 * 1e9))
    assert mfu == pytest.approx(100 * 1.97e12 / (197.0 * 1e12))


def test_ingest_heartbeat_prices_nothing_for_an_unknown_device():
    obs, _ = _observer()
    obs.ingest_heartbeat(
        "c2", "ws", "st", token_pressure=0.4, active_streams=2,
        extra={"tokens_per_sec": 100.0,
               "decode_bytes_per_token_per_chip": 8.19e9,
               "decode_flops_per_token_per_chip": 1.97e12,
               "device_kind": "cpu"})
    names = obs.timeline.series_names()
    assert "engine.c2.tokens_per_sec" in names
    assert "engine.c2.mbu" not in names and "engine.c2.mfu" not in names


async def test_sampler_tick_records_router_series_and_folds_burn():
    stub = Stub(stub_id="s1", workspace_id="ws")
    obs, router = _observer([stub])
    sig = router.signals
    await obs.sample()                  # baseline tick (counters at 0)
    # an overload between ticks: 90 admitted, 10 shed
    for _ in range(90):
        sig.submitted("s1", "ws")
    for _ in range(10):
        sig.shed("s1", "ws", "queue_full")
    await obs.sample()                  # the burn window sees the rise
    names = obs.timeline.series_names()
    assert "router.s1.queue_depth" in names
    assert "router.s1.submitted_total" in names
    assert "slo.s1.availability.burn_fast" in names
    # the burn landed in the autoscaler pressure feed
    assert sig.slo_pressure("s1") > 0.0
    payload = obs.slo_payload()
    avail = payload["stubs"]["s1"]["objectives"]["availability"]
    assert avail["fast"]["burn"] > 1.0
    assert avail["attribution"] == "shed"
    assert payload["stubs"]["s1"]["pressure"] == 1.0   # shed saturation
    # goodput router counters flowed into the per-workspace snapshot
    # (two ticks: the first establishes the delta base)
    snap = await obs.goodput_snapshot()
    assert "ws" in snap and "s1" in snap["ws"]["stubs"]
    # timeline payload shapes
    listing = obs.timeline_payload("", 0.0, None)
    assert "router.s1.queue_depth" in listing["series_names"]
    q = obs.timeline_payload("router.s1.*", 0.0, 8)
    assert "router.s1.shed_total" in q["series"]


def test_filter_engines_ages_out_silent_replicas():
    obs, _ = _observer(stale_after_s=6.0)
    now = time.time()
    engines = {
        "live": {"ts": now - 1.0, "tokens_per_sec": 5.0},
        "dead": {"ts": now - 30.0, "tokens_per_sec": 9.0},
        "unstamped": {"tokens_per_sec": 1.0},          # pre-aging writer
    }
    out = obs.filter_engines(engines)
    assert "dead" not in out                           # silent > 3 beats
    assert out["live"]["age_s"] == pytest.approx(1.0, abs=0.5)
    assert out["live"]["last_seen"] == pytest.approx(now - 1.0, abs=0.01)
    assert "unstamped" in out                          # fails open


# ---------------------------------------------------------------------------
# Prometheus exposition: stable tpu9_slo_* / tpu9_goodput_* naming
# ---------------------------------------------------------------------------

def test_slo_and_goodput_publish_use_stable_prometheus_names():
    from tpu9.observability import metrics as global_metrics
    tl = TimelineStore(capacity=16)
    tl.record("router.sX.submitted_total", 0.0)
    tl.record("router.sX.submitted_total", 50.0)
    tl.record("router.sX.shed_total", 0.0)
    tl.record("router.sX.shed_total", 10.0)
    ev = SloEvaluator(tl, _objectives())
    ev.publish("sX", ev.evaluate("sX"))
    acc = GoodputAccountant()
    acc.publish({"wsX": {"goodput_tokens_per_chip_second": 2.5,
                         "goodput_frac": 0.5,
                         "waste": {"queue_wait": 0.1, "shed": 0.0,
                                   "spec_rollback": 0.2,
                                   "recompile_stall": 0.0,
                                   "idle_reservation": 0.2}}})
    text = global_metrics.prometheus_text()
    for needle in (
            'tpu9_slo_burn_rate{objective="availability",stub="sX",'
            'window="fast"}',
            'tpu9_slo_burn_rate{objective="ttft",stub="sX",window="slow"}',
            'tpu9_slo_burning{objective="availability",stub="sX"}',
            'tpu9_goodput_frac{workspace="wsX"} 0.5',
            'tpu9_goodput_tokens_per_chip_second{workspace="wsX"} 2.5',
            'tpu9_goodput_waste_frac{bucket="spec_rollback",'
            'workspace="wsX"} 0.2'):
        assert needle in text, needle


# ---------------------------------------------------------------------------
# tpu9 top renderer
# ---------------------------------------------------------------------------

def test_render_top_composes_engine_slo_goodput_tables():
    from tpu9.cli.main import _render_top
    metrics_data = {
        "engines": {"c-1234567890ab": {
            "tokens_per_sec": "123.4", "kv_blocks_free": "17",
            "spec_acceptance_rate": "0.87",
            "graph_compiles_post_warmup": "0", "age_s": 1.2}},
        "goodput": {"ws-default": {
            "goodput_tokens_per_chip_second": 80.0, "goodput_frac": 0.64,
            "waste": {"queue_wait": 0.05, "shed": 0.01,
                      "spec_rollback": 0.16, "recompile_stall": 0.10,
                      "idle_reservation": 0.04}}},
    }
    slo_data = {"stubs": {"stub-1": {
        "pressure": 1.0,
        "objectives": {
            "availability": {"fast": {"burn": 90.9}, "slow": {"burn": 2.0},
                             "burning": True, "warning": True,
                             "attribution": "shed"},
            "ttft": {"fast": {"burn": 0.2}, "slow": {"burn": 0.1},
                     "burning": False, "warning": False}}}}}
    timeline_data = {"series": {
        "router.stub-1.queue_depth": [[0, 0.0], [1, 2.0], [2, 5.0]],
        "router.stub-1.ttft_p95_s": [[0, 0.1], [1, 0.4]],
        "engine.c-1234567890ab.tokens_per_sec": [[0, 100.0], [1, 140.0]],
    }}
    frame = _render_top(metrics_data, slo_data, timeline_data)
    assert "ENGINES (1 replicas)" in frame
    assert "123.4" in frame                  # engine tok/s
    assert "BURNING (shed)" in frame         # slo status + attribution
    assert "ws-default" in frame and "64.0%" in frame
    assert "▁" in frame or "█" in frame      # sparklines rendered
    # empty payloads must render, not crash (cold gateway)
    assert _render_top({}, {}, {})


def test_render_top_health_column_and_hbm_headroom():
    """ISSUE 14 satellite: the engines table carries the watchdog
    verdict + HBM headroom; a non-ok replica shows its reason instead of
    the throughput sparkline."""
    from tpu9.cli.main import _render_top
    metrics_data = {"engines": {
        "c-ok": {"tokens_per_sec": "10.0", "health": "ok",
                 "hbm_used_gb_per_chip": "12.0",
                 "hbm_limit_gb_per_chip": "16.0", "age_s": 1.0},
        "c-bad": {"tokens_per_sec": "0.0", "health": "stalled",
                  "health_reason": "no_progress_with_queued_work",
                  "hbm_used_gb_per_chip": "16.0",
                  "hbm_limit_gb_per_chip": "16.0", "age_s": 1.0},
        "ccpu": {"tokens_per_sec": "5.0", "health": "ok", "age_s": 1.0},
    }}
    frame = _render_top(metrics_data, {}, {})
    assert "health" in frame and "hbm%" in frame
    ok_line = next(ln for ln in frame.splitlines() if "c-ok" in ln)
    bad_line = next(ln for ln in frame.splitlines() if "c-bad" in ln)
    cpu_line = next(ln for ln in frame.splitlines() if "ccpu" in ln)
    assert "ok" in ok_line and "25%" in ok_line
    assert "stalled" in bad_line
    assert "!! no_progress_with_queued_work" in bad_line
    assert "0%" in bad_line                  # ~0 headroom
    # no memory stats (CPU): headroom renders '-', never a fake number
    # (cid chosen dash-free so this asserts the COLUMN, not the name)
    assert "-" in cpu_line and "%" not in cpu_line
    # legacy engines payload without health fields still renders
    assert _render_top({"engines": {"c0": {"tokens_per_sec": "1.0"}}},
                       {}, {})


# ---------------------------------------------------------------------------
# stub churn (ISSUE 18 regression): a deleted stub takes its per-stub
# gauge series and rolling state with it — set_gauge-only registries
# otherwise hold a dead stub's last value forever and grow without bound
# ---------------------------------------------------------------------------

def test_router_signals_forget_stub_drops_state_and_gauges():
    from tpu9.observability import metrics
    sig = RouterSignals()
    sig.queue_sample("dead-stub", depth=5, capacity=10)
    sig.slo_sample("dead-stub", 1.5)
    assert any("dead-stub" in k for k in metrics.gauges)
    sig.forget_stub("dead-stub")
    assert not any("dead-stub" in k for k in metrics.gauges)
    assert "dead-stub" not in sig._queue_depth
    assert "dead-stub" not in sig._slo_burn
    # forgetting is idempotent and unknown stubs are a no-op
    sig.forget_stub("dead-stub")
    sig.forget_stub("never-seen")


def test_slo_evaluator_forget_stub_removes_published_series():
    from tpu9.observability import metrics
    tl = TimelineStore(capacity=64)
    ev = SloEvaluator(tl, _objectives())
    for i in range(6):
        tl.record("replica.s9.ttft_p95_s", 1.0)
    ev.publish("s9", ev.evaluate("s9"))
    assert any('stub="s9"' in k for k in metrics.gauges)
    ev.forget_stub("s9")
    assert not any('stub="s9"' in k for k in metrics.gauges)


def test_goodput_accountant_forget_stub_drops_router_window():
    acc = GoodputAccountant(window_s=600.0)
    acc.router_sample("s9", "ws", submitted_total=10.0, shed_total=1.0,
                      queue_wait_total_s=2.0)
    acc.router_sample("s9", "ws", submitted_total=20.0, shed_total=1.0,
                      queue_wait_total_s=3.0)
    assert ("ws", "s9") in acc._acc
    acc.forget_stub("s9")
    assert ("ws", "s9") not in acc._acc
    assert "router:s9" not in acc._last
    assert "s9" not in acc._stub_ws
