"""Scale-out plane (ISSUE 17): tree planner, group ledger, predictive
controller, coordinator glue, router admission fence, and the cache-plane
chaos path (``tree_peer_loss`` mid-transfer → survivors, never a failed
restore).
"""

import asyncio
import json
import os
import types

import pytest

from tpu9.cache import CacheClient, ChunkServer, DiskStore
from tpu9.config import ScaleoutConfig
from tpu9.scaleout import predictive_on, scaleout_on
from tpu9.scaleout.controller import (Decision, burn_slope, decide_scale,
                                      predictive_policy)
from tpu9.scaleout.coordinator import (PLAN_KEY, ScaleoutCoordinator,
                                       build_report)
from tpu9.scaleout.ledger import GroupLedger
from tpu9.scaleout.tree import (SOURCE, TreePlan, plan_tree, replan,
                                source_edge_count)

# -- tree planner --------------------------------------------------------


def test_plan_tree_no_source_edges_with_live_holders():
    plan = plan_tree(["j0", "j1", "j2", "j3"],
                     {"g1": ["seed"], "g2": ["seed"]})
    assert source_edge_count(plan) == 0
    # every joiner has a preference list for every group
    for j in ("j0", "j1", "j2", "j3"):
        for g in ("g1", "g2"):
            assert plan.peer_prefs(j, g), f"{j}/{g} got no parents"


def test_plan_tree_holderless_group_gets_exactly_one_source_edge():
    plan = plan_tree(["b", "a", "c"], {"g": []})
    assert source_edge_count(plan) == 1
    # deterministic designation: lexicographically-first joiner
    assert plan.parents("a", "g") == [SOURCE]
    # everyone else chains off that root, never the source
    assert plan.peer_prefs("b", "g") and plan.peer_prefs("c", "g")
    assert SOURCE not in plan.parents("b", "g")
    assert SOURCE not in plan.parents("c", "g")
    # peer_prefs strips the marker — the cache client never sees it
    assert plan.peer_prefs("a", "g") == []


def test_plan_tree_fanout_bounds_children_per_parent():
    joiners = [f"j{i}" for i in range(7)]
    plan = plan_tree(joiners, {"g": ["seed"]}, fanout=2)
    primaries = [plan.parents(j, "g")[0] for j in joiners]
    for parent in set(primaries):
        assert primaries.count(parent) <= 2, \
            f"{parent} serves {primaries.count(parent)} children"
    # the cascade actually deepens: someone's primary is another joiner
    assert any(p != "seed" for p in primaries)


def test_plan_tree_is_deterministic_and_latency_weighted():
    args = (["j0", "j1"], {"g": ["fast", "slow"]})
    lat = {"fast": 0.001, "slow": 0.4}
    p1 = plan_tree(*args, fanout=4, peer_lat=lat)
    p2 = plan_tree(*args, fanout=4, peer_lat=lat)
    assert p1.prefs == p2.prefs
    # with spare fanout everywhere, both children pick the fast parent
    assert p1.parents("j0", "g")[0] == "fast"
    assert p1.parents("j1", "g")[0] == "fast"
    # the slow holder survives as a backup, not dropped
    assert "slow" in p1.parents("j0", "g")


def test_plan_roundtrips_through_dict():
    plan = plan_tree(["j0", "j1"], {"g": ["seed"]})
    again = TreePlan.from_dict(json.loads(json.dumps(plan.to_dict())))
    assert again.prefs == plan.prefs and again.fanout == plan.fanout


def test_replan_moves_incomplete_children_to_survivors():
    plan = plan_tree(["j0", "j1"], {"g": ["dead", "live"]},
                     peer_lat={"dead": 0.001, "live": 0.1})
    assert plan.parents("j0", "g")[0] == "dead"
    fresh = replan(plan, ["dead"], {"g": ["dead", "live"]},
                   incomplete={"j0": ["g"], "j1": []})
    # in-flight child re-planned onto the survivor
    assert fresh.parents("j0", "g")[0] == "live"
    assert "dead" not in fresh.parents("j0", "g")
    # completed child keeps its historical edge (report evidence)
    assert fresh.parents("j1", "g") == plan.parents("j1", "g")


def test_replan_falls_to_source_only_when_no_peer_holds_the_group():
    plan = plan_tree(["j0"], {"g": ["dead"]})
    fresh = replan(plan, ["dead"], {"g": ["dead"]})
    # no survivor holds the group: the plan's last resort is the source
    assert fresh.parents("j0", "g") == [SOURCE]
    # ...which the cache client sees as "no preference" (HRW + source)
    assert fresh.peer_prefs("j0", "g") == []


# -- group ledger --------------------------------------------------------


def test_ledger_held_vs_ready_are_distinct_facts():
    led = GroupLedger(stale_after_s=10.0)
    led.note_held("w0", "10.0.0.1:70", ["k1", "k2"], now=100.0)
    led.note_ready("c0", ["g0.tpu9w"], 0.5, total=2, now=100.0)
    snap = led.snapshot(now=100.0)
    assert snap["w0"]["held"] == ["k1", "k2"]
    assert snap["w0"]["ready"] == []
    assert snap["c0"]["ready"] == ["g0.tpu9w"]
    assert snap["c0"]["ready_frac"] == 0.5
    assert led.readiness("c0") == 0.5


def test_ledger_staleness_ages_replicas_out_of_holder_sets():
    led = GroupLedger(stale_after_s=5.0)
    led.note_held("w0", "a:1", ["k"], now=100.0)
    led.note_held("w1", "b:1", ["k"], now=104.0)
    assert led.holders(now=105.0)["k"] == ["a:1", "b:1"]
    # w0's last report is now 6s old — it must stop receiving children
    assert led.holders(now=106.0)["k"] == ["b:1"]
    assert led.snapshot(now=106.0)["w0"]["stale"] is True
    led.forget("w1")
    assert led.holders(now=106.0) == {}


def test_ledger_addrless_rows_never_become_holders_or_joiners():
    led = GroupLedger(stale_after_s=10.0)
    led.note_ready("c0", ["g"], 0.5, now=100.0)   # serving-plane only
    assert led.holders(now=100.0) == {}
    assert led.joiners(["k"], now=100.0) == []


def test_ledger_joiners_are_replicas_missing_any_group():
    led = GroupLedger(stale_after_s=10.0)
    led.note_held("w0", "a:1", ["k1", "k2"], now=100.0)
    led.note_held("w1", "b:1", ["k1"], now=100.0)
    led.note_held("w2", "c:1", [], now=100.0)
    assert led.joiners(["k1", "k2"], now=100.0) == ["b:1", "c:1"]


# -- predictive controller ----------------------------------------------


def _cfg(**kw):
    base = dict(slope_window_s=120.0, burn_horizon_s=300.0,
                scale_up_max_step=2, bringup_safety=2.0,
                stale_after_s=6.0, default_bringup_s=30.0)
    base.update(kw)
    return ScaleoutConfig(**base)


def _ramp(rate_per_s, *, n=13, dt=5.0, t0=1000.0, slow=0.1):
    """Fast-window burn rising linearly at ``rate_per_s``."""
    return [(t0 + i * dt, rate_per_s * i * dt, slow) for i in range(n)]


def test_burn_slope_least_squares_and_degenerate_cases():
    series = _ramp(0.01)
    assert burn_slope(series, window_s=120.0) == pytest.approx(0.01)
    assert burn_slope([], window_s=120.0) == 0.0
    assert burn_slope(series[:1], window_s=120.0) == 0.0
    # points outside the window are ignored
    assert burn_slope(series, window_s=0.5) == 0.0


def test_step_ramp_scales_up_before_the_slow_window_trips():
    # fast burn climbs 0.005/s: at the last sample fast=0.3 (<1, so the
    # reactive floor has NOT fired) and slow=0.1 (the paging signal has
    # NOT tripped) — only the slope projection sees 0.3+0.005*300=1.8
    series = _ramp(0.005)
    d = decide_scale(series, replicas=2, cfg=_cfg(),
                     now=series[-1][0], bringup_s=20.0)
    assert d.action == "up" and d.desired == 3
    assert series[-1][1] < 1.0 and series[-1][2] < 1.0


def test_steep_spike_earns_the_full_scale_step_and_caps_at_max():
    series = _ramp(0.02)   # projected 1.2 + 6.0 — way past 2x budget
    d = decide_scale(series, replicas=2, cfg=_cfg(),
                     now=series[-1][0], bringup_s=20.0, max_replicas=3)
    assert d.action == "up" and d.desired == 3   # clamped, not 2+2


def test_diurnal_decline_scales_down_when_bringup_fits_budget():
    t0 = 1000.0
    series = [(t0 + i * 5.0, max(0.0, 0.5 - 0.01 * i * 5.0), 0.1)
              for i in range(13)]   # fades to 0 by the end
    d = decide_scale(series, replicas=4, cfg=_cfg(),
                     now=series[-1][0], bringup_s=20.0,
                     slow_window_s=3600.0, min_replicas=1)
    assert d.action == "down" and d.desired == 3


def test_spike_and_fade_holds_then_releases():
    t0 = 1000.0
    spike = [(t0 + i * 5.0, 0.9 if 3 <= i <= 5 else 0.0, 0.2)
             for i in range(13)]
    mid = decide_scale(spike[:6], replicas=2, cfg=_cfg(),
                       now=spike[5][0], bringup_s=20.0)
    assert mid.action == "up"          # mid-spike: projection crosses
    faded = decide_scale(spike, replicas=3, cfg=_cfg(),
                         now=spike[-1][0], bringup_s=20.0)
    assert faded.action == "down"      # burn back to 0, slope <= 0


def test_scale_down_respects_measured_bringup_time():
    quiet = [(1000.0 + i * 5.0, 0.0, 0.45) for i in range(5)]
    # burn budget left: (1-0.45)*100 = 55s; 30s bringup x2 safety = 60s
    d = decide_scale(quiet, replicas=4, cfg=_cfg(),
                     now=quiet[-1][0], bringup_s=30.0,
                     slow_window_s=100.0)
    assert d.action == "hold" and d.desired == 4
    assert "bringup" in d.reason
    # a fast-restoring deployment (measured 5s) may release capacity
    d2 = decide_scale(quiet, replicas=4, cfg=_cfg(),
                      now=quiet[-1][0], bringup_s=5.0,
                      slow_window_s=100.0)
    assert d2.action == "down" and d2.desired == 3


def test_stale_series_yields_fallback_never_an_opinion():
    series = _ramp(0.02)   # would scream "up" if fresh
    d = decide_scale(series, replicas=1, cfg=_cfg(),
                     now=series[-1][0] + 60.0, bringup_s=20.0)
    assert d.action == "fallback" and d.desired == 1
    assert decide_scale([], replicas=1, cfg=_cfg(),
                        now=0.0).action == "fallback"


def _sample(active):
    return types.SimpleNamespace(active_containers=active)


def _base_policy(desired, reason="reactive"):
    def decide(samples):
        return types.SimpleNamespace(desired=desired, reason=reason)
    return decide


def test_predictive_policy_up_takes_the_max_of_both():
    series = _ramp(0.02)
    pol = predictive_policy(_base_policy(2), cfg=_cfg(),
                            burns=lambda: series,
                            bringup=lambda: 20.0, max_containers=8,
                            clock=lambda: series[-1][0])
    res = pol([_sample(2)])
    assert res.desired == 4 and res.reason.startswith("predictive:")


def test_predictive_policy_never_suppresses_a_reactive_scale_up():
    series = _ramp(0.005)   # predictive wants 2+1=3
    pol = predictive_policy(_base_policy(6), cfg=_cfg(),
                            burns=lambda: series,
                            bringup=lambda: 20.0, max_containers=8,
                            clock=lambda: series[-1][0])
    assert pol([_sample(2)]).desired == 6   # base's bigger jump wins


def test_predictive_policy_bringup_guard_floors_a_reactive_down():
    quiet = [(1000.0 + i * 5.0, 0.0, 0.45) for i in range(5)]
    pol = predictive_policy(_base_policy(1), cfg=_cfg(),
                            burns=lambda: quiet,
                            bringup=lambda: 30.0, max_containers=8,
                            slow_window_s=100.0,
                            clock=lambda: quiet[-1][0])
    res = pol([_sample(4)])
    assert res.desired == 4   # hold vetoes the base's removal
    assert "bringup" in res.reason


def test_predictive_policy_down_takes_the_min():
    quiet = [(1000.0 + i * 5.0, 0.0, 0.1) for i in range(5)]
    pol = predictive_policy(_base_policy(4), cfg=_cfg(),
                            burns=lambda: quiet,
                            bringup=lambda: 5.0, max_containers=8,
                            min_containers=1,
                            clock=lambda: quiet[-1][0])
    assert pol([_sample(4)]).desired == 3


def test_stale_sampler_can_never_pin_the_fleet_at_max():
    # the PR 12 pattern: a ramp that screamed "up", then the sampler
    # dies. The predictive layer must pass the base's decision through
    # untouched — otherwise the last "up" opinion pins capacity at max.
    series = _ramp(0.02)
    dead_clock = series[-1][0] + 300.0
    pol = predictive_policy(_base_policy(1, "reactive idle"),
                            cfg=_cfg(), burns=lambda: series,
                            bringup=lambda: 20.0, max_containers=8,
                            clock=lambda: dead_clock)
    res = pol([_sample(8)])
    assert res.desired == 1 and res.reason == "reactive idle"


def test_feature_gates_env_beats_config(monkeypatch):
    monkeypatch.delenv("TPU9_SCALEOUT", raising=False)
    monkeypatch.delenv("TPU9_SCALEOUT_PREDICTIVE", raising=False)
    assert scaleout_on(ScaleoutConfig(enabled=True))
    assert not scaleout_on(ScaleoutConfig(enabled=False))
    monkeypatch.setenv("TPU9_SCALEOUT", "0")
    assert not scaleout_on(ScaleoutConfig(enabled=True))
    monkeypatch.setenv("TPU9_SCALEOUT", "1")
    assert scaleout_on(ScaleoutConfig(enabled=False))
    assert not predictive_on(ScaleoutConfig())   # default OFF
    monkeypatch.setenv("TPU9_SCALEOUT_PREDICTIVE", "1")
    assert predictive_on(ScaleoutConfig())


# -- coordinator ---------------------------------------------------------


def test_coordinator_plans_over_snapshots_and_heartbeats():
    coord = ScaleoutCoordinator(ScaleoutConfig(tree_fanout=2,
                                               stale_after_s=5.0))
    coord.observe_worker("seed", {"cache": {
        "addr": "s:1", "groups": ["k1", "k2"],
        "peers": {"j:1": {"lat_ewma_s": 0.002}}}}, now=100.0)
    coord.observe_worker("w1", {"cache": {"addr": "j:1", "groups": []}},
                         now=100.0)
    plan = coord.refresh(now=100.0)
    assert plan.parents("j:1", "k1") == ["s:1"]
    assert coord.stats()["edges"] == 2
    assert coord.stats()["source_edges"] == 0
    # pressure-heartbeat readiness lands on the serving-plane side; a
    # heartbeat without the scaleout extras is ignored entirely
    coord.observe_heartbeat("c1", {"tokens_per_sec": 10}, now=101.0)
    coord.observe_heartbeat("c1", {"scaleout_ready_frac": 0.5,
                                   "scaleout_ready_groups": "g0,g1",
                                   "scaleout_groups_total": 4}, now=101.0)
    snap = coord.ledger.snapshot(now=101.0)
    assert snap["c1"]["ready"] == ["g0", "g1"]
    assert snap["c1"]["ready_frac"] == 0.5
    # confirmed peer death: forget + replan drops the holder
    coord.forget("seed", now=101.0)
    assert coord.ledger.holders(now=101.0) == {}
    assert PLAN_KEY == "scaleout:tree"


def test_build_report_splits_bytes_by_edge():
    led = GroupLedger(stale_after_s=10.0)
    led.note_held("c0", "a:1", ["k"], now=100.0)
    led.note_held("c1", "b:1", [], now=100.0)
    plan = plan_tree(["b:1"], {"k": ["a:1"]})
    records = {"c1": {"restore": {
        "peer_bytes": {"a:1": 4096}, "tiers": {"peer": 4096, "source": 7,
                                               "pool": 0, "local": 0}}}}
    rep = build_report(led.snapshot(now=100.0), plan, records=records)
    rows = {r["replica"]: r for r in rep["replicas"]}
    assert rows["c1"]["tree_parents"]["k"] == "a:1"
    assert rows["c1"]["bytes_by_edge"] == {"a:1": 4096}
    assert rows["c1"]["bytes_source"] == 7
    assert rows["c0"]["children"] == ["b:1"]
    assert rep["tree"]["source_edges"] == 0
    assert rep["tree"]["edges"] == [
        {"child": "b:1", "group": "k", "parent": "a:1"}]


# -- router admission fence ---------------------------------------------


def _admit(body, order, readiness):
    from tpu9.router.fleet import FleetRouter
    return FleetRouter._scaleout_admit(body, order, readiness)


def test_scaleout_admit_fences_partial_replicas(monkeypatch):
    monkeypatch.delenv("TPU9_SCALEOUT_PARTIAL", raising=False)
    ready = {"full": (1.0, set()), "half": (0.5, {"g0"})}
    hinted = json.dumps({"weight_groups": ["g0"]}).encode()
    # group-hinted request may use the half-restored replica
    assert _admit(hinted, ["half", "full"], ready) == ["half", "full"]
    # a request needing an unbound group may not
    other = json.dumps({"weight_groups": ["g1"]}).encode()
    assert _admit(other, ["half", "full"], ready) == ["full"]
    # an un-hinted request requires full readiness (conservative default)
    assert _admit(b"{}", ["half", "full"], ready) == ["full"]
    assert _admit(b"", ["half"], ready) == []
    # unknown replicas are treated as fully ready (no heartbeat yet)
    assert _admit(b"{}", ["new"], ready) == ["new"]
    # malformed hint bodies degrade to the conservative fence, not a 500
    assert _admit(b"\xff{not json", ["half", "full"], ready) == ["full"]


def test_scaleout_admit_partial_kill_switch(monkeypatch):
    monkeypatch.setenv("TPU9_SCALEOUT_PARTIAL", "0")
    ready = {"half": (0.5, {"g0"})}
    hinted = json.dumps({"weight_groups": ["g0"]}).encode()
    assert _admit(hinted, ["half"], ready) == []


# -- fault plane: tree_peer_loss ----------------------------------------


def test_fire_peer_targets_the_victim_only():
    from tpu9.testing.faults import FaultPlane, parse_spec
    plane = FaultPlane(parse_spec("tree_peer_loss:peer=10.0.0.7"))
    # calls against other peers neither fire nor advance the counter
    assert not plane.fire_peer("tree_peer_loss", "10.0.0.8:70")
    assert plane.specs["tree_peer_loss"].calls == 0
    assert plane.fire_peer("tree_peer_loss", "10.0.0.7:70")
    # dead stays dead: unbounded fires, unlike oneshot crash kinds
    for _ in range(5):
        assert plane.fire_peer("tree_peer_loss", "10.0.0.7:70")


def test_fire_peer_after_calls_counts_victim_attempts_only():
    from tpu9.testing.faults import FaultPlane, parse_spec
    plane = FaultPlane(parse_spec(
        "tree_peer_loss:peer=10.0.0.7,after_calls=3"))
    assert not plane.fire_peer("tree_peer_loss", "10.0.0.7:70")  # call 1
    assert not plane.fire_peer("tree_peer_loss", "10.0.0.8:70")  # skipped
    assert not plane.fire_peer("tree_peer_loss", "10.0.0.7:70")  # call 2
    assert plane.fire_peer("tree_peer_loss", "10.0.0.7:70")      # call 3
    assert plane.specs["tree_peer_loss"].calls == 3


def test_fire_peer_addr_with_port_survives_spec_grammar():
    from tpu9.testing.faults import parse_spec
    specs = parse_spec("tree_peer_loss:peer=127.0.0.1:39709,after_calls=2")
    assert specs["tree_peer_loss"].extra["peer"] == "127.0.0.1:39709"
    assert specs["tree_peer_loss"].after_calls == 2


# -- cache plane: prefer order, per-edge ledger, chaos ------------------


async def _serve(tmp_path, name, chunks):
    store = DiskStore(str(tmp_path / name))
    for data in chunks:
        await store.put(data)
    srv = await ChunkServer(store).start()
    return srv


async def test_prefer_order_overrides_hrw_and_ledger_attributes_edges(
        tmp_path):
    chunks = [os.urandom(50_000) for _ in range(3)]
    srv_a = await _serve(tmp_path, "a", chunks)
    srv_b = await _serve(tmp_path, "b", chunks)

    async def peers():
        return [srv_a.address, srv_b.address]

    cl = CacheClient(DiskStore(str(tmp_path / "c")), peers,
                     hedge_delay_s=5.0)   # no hedge: attribution is exact
    try:
        from tpu9.cache.store import chunk_hash
        ledger: dict = {}
        for data in chunks:
            got = await cl.get(chunk_hash(data), ledger=ledger,
                               prefer=[srv_b.address, srv_a.address])
            assert got == data
        # every byte attributed to the TREE parent, regardless of HRW
        assert ledger[f"bytes_peer:{srv_b.address}"] == \
            sum(len(c) for c in chunks)
        assert f"bytes_peer:{srv_a.address}" not in ledger
        # group advertisement rides the snapshot for the coordinator
        cl.advertise_group("k1")
        cl.advertise_group("")
        snap = cl.snapshot()
        assert snap["groups"] == ["k1"]
        assert "addr" in snap
    finally:
        await cl.close()
        await srv_a.stop()
        await srv_b.stop()


async def test_tree_peer_loss_falls_through_to_survivors(tmp_path,
                                                         monkeypatch):
    """Satellite 1: mid-transfer death of the tree parent — the hedged
    read must fall through the surviving preference list with zero
    failed reads and ZERO source traffic (a live peer holds the group).
    """
    chunks = [os.urandom(40_000) for _ in range(4)]
    victim = await _serve(tmp_path, "victim", chunks)
    survivor = await _serve(tmp_path, "survivor", chunks)

    async def peers():
        return [victim.address, survivor.address]

    source_calls = []

    async def source(digest):
        source_calls.append(digest)
        return None

    # the fault plane arms at client CONSTRUCTION from the env — same
    # order a worker booting into a chaos run sees it
    monkeypatch.setenv(
        "TPU9_FAULTS",
        f"tree_peer_loss:peer={victim.address},after_calls=2")
    cl = CacheClient(DiskStore(str(tmp_path / "j")), peers, source=source)
    try:
        from tpu9.cache.store import chunk_hash
        ledger: dict = {}
        prefer = [victim.address, survivor.address]
        for data in chunks:
            got = await cl.get(chunk_hash(data), ledger=ledger,
                               prefer=prefer)
            assert got == data, "restore failed under tree_peer_loss"
        assert cl.stats["peer_errors"] > 0          # the fault DID fire
        assert cl.stats["bytes_source"] == 0
        assert source_calls == []
        # the survivor served the post-death bytes (per-edge evidence)
        assert ledger.get(f"bytes_peer:{survivor.address}", 0) > 0
    finally:
        await cl.close()
        await victim.stop()
        await survivor.stop()


async def test_tree_peer_loss_source_fallback_when_no_peer_holds(
        tmp_path, monkeypatch):
    """The OTHER half of satellite 1: when no live peer holds the group,
    the source tier is the legitimate last resort — peer death must
    degrade to source, never to a failed read."""
    chunks = [os.urandom(30_000) for _ in range(2)]
    victim = await _serve(tmp_path, "only", chunks)
    by_hash = {}
    from tpu9.cache.store import chunk_hash
    for data in chunks:
        by_hash[chunk_hash(data)] = data

    async def peers():
        return [victim.address]

    async def source(digest):
        return by_hash.get(digest)

    monkeypatch.setenv("TPU9_FAULTS",
                       f"tree_peer_loss:peer={victim.address}")
    cl = CacheClient(DiskStore(str(tmp_path / "j")), peers, source=source)
    try:
        for data in chunks:
            assert await cl.get(chunk_hash(data)) == data
        assert cl.stats["bytes_source"] == sum(len(c) for c in chunks)
    finally:
        await cl.close()
        await victim.stop()


async def test_restore_params_replans_mid_transfer_onto_survivor(
        tmp_path, monkeypatch):
    """End-to-end satellite 1: a real multi-group checkpoint restore
    whose tree parent dies mid-transfer. The coordinator's preference
    list (parent first, survivors behind) IS the worker-side re-plan —
    the restore completes, advertises its groups, and pulls nothing
    from the source tier."""
    import numpy as np

    from tpu9.serving import weights as wfmt
    from tpu9.worker.checkpoint import CheckpointManager

    src = tmp_path / "src"
    rng = np.random.default_rng(3)
    for g in range(2):
        tree = {"w": [rng.standard_normal(16384, dtype=np.float32)
                      for _ in range(2)]}
        wfmt.save_params(tree, str(src / f"g{g}.tpu9w"))

    manifests = {}

    async def record(stub, ws, cid):
        return "ckpt"

    async def store_manifest(cid, blob):
        manifests[cid] = blob

    async def fetch_manifest(cid):
        return manifests.get(cid)

    async def no_peers():
        return []

    def ident(entry, arr):
        return arr

    # two seeded holders: the victim parent and the survivor
    holders = []
    for name in ("victim", "survivor"):
        st = DiskStore(str(tmp_path / name))
        cl = CacheClient(st, no_peers)
        cm = CheckpointManager(cl, record=record,
                               store_manifest=store_manifest,
                               fetch_manifest=fetch_manifest)
        ckpt = await cm.create("s", "w", name, str(src))
        assert ckpt
        trees, _ = await cm.restore_params(ckpt, device_put=ident)
        assert trees and len(trees) == 2
        srv = await ChunkServer(st, groups_fn=lambda c=cl: c.groups
                                ).start()
        cl.self_address = srv.address
        holders.append((cl, srv))
    (victim_cl, victim_srv), (surv_cl, surv_srv) = holders
    group_keys = sorted(victim_cl.groups)
    assert len(group_keys) == 2

    # the coordinator plans the joiner's edges over the advertisements
    coord = ScaleoutCoordinator()
    coord.observe_worker("victim", {"cache": victim_cl.snapshot()},
                         now=100.0)
    coord.observe_worker("survivor", {"cache": surv_cl.snapshot()},
                         now=100.0)
    coord.observe_worker("joiner",
                         {"cache": {"addr": "127.0.0.1:1", "groups": []}},
                         now=100.0)
    plan = coord.refresh(now=100.0)
    prefs = plan.peer_prefs("127.0.0.1:1", group_keys[0])
    assert len(prefs) == 2   # a parent AND a live backup

    async def all_peers():
        return [victim_srv.address, surv_srv.address]

    async def hints(key):
        # force the victim primary so the death is actually on-path
        others = [p for p in plan.peer_prefs("127.0.0.1:1", key)
                  if p != victim_srv.address]
        return [victim_srv.address] + others

    async def source(digest):
        raise AssertionError("source tier touched with live holders")

    monkeypatch.setenv(
        "TPU9_FAULTS",
        f"tree_peer_loss:peer={victim_srv.address},after_calls=2")
    join_cl = CacheClient(DiskStore(str(tmp_path / "join")), all_peers,
                          source=source)
    join_cl.self_address = "127.0.0.1:1"
    try:
        cm = CheckpointManager(join_cl, fetch_manifest=fetch_manifest,
                               tree_hints=hints)
        bound = []
        trees, metrics = await cm.restore_params(
            "ckpt", device_put=ident,
            on_group=lambda g, t, done, total: bound.append((g, done,
                                                             total)))
        assert trees and len(trees) == 2     # ZERO failed restores
        assert join_cl.stats["peer_errors"] > 0
        assert join_cl.stats["bytes_source"] == 0
        # survivor carried bytes after the death (per-edge attribution)
        assert metrics["peer_bytes"].get(surv_srv.address, 0) > 0
        # per-group readiness fired as groups landed, not at the end
        assert [b[1:] for b in bound] == [(1, 2), (2, 2)]
        # the joiner now re-serves what it consumed (next wave's parent)
        assert sorted(join_cl.groups) == group_keys
    finally:
        await join_cl.close()
        for cl, srv in holders:
            await cl.close()
            await srv.stop()


async def test_wave_of_joiners_costs_the_holder_its_fanout_not_its_count(
        tmp_path):
    """One holder, three joiners, ``tree_fanout`` 1, over real ChunkServers
    and the real coordinator: the plan chains the joiners (holder → j → j →
    j), each restores along its planned edge and re-serves what it took.
    The holder ships ONE copy of the checkpoint however many join, every
    other copy comes off a joiner, and the source tier is never touched —
    the bytes at the top of the tree are O(fanout), not O(N)."""
    import numpy as np

    from tpu9.serving import weights as wfmt
    from tpu9.worker.checkpoint import CheckpointManager

    src = tmp_path / "src"
    rng = np.random.default_rng(5)
    for g in range(2):
        wfmt.save_params(
            {"w": [rng.standard_normal(16384, dtype=np.float32)
                   for _ in range(2)]}, str(src / f"g{g}.tpu9w"))
    manifests = {}

    async def record(stub, ws, cid):
        return "ckpt"

    async def store_manifest(cid, blob):
        manifests[cid] = blob

    async def fetch_manifest(cid):
        return manifests.get(cid)

    async def no_peers():
        return []

    async def source(digest):
        raise AssertionError("source tier touched with a live holder")

    def ident(entry, arr):
        return arr

    async def serve(client, store):
        srv = await ChunkServer(store, groups_fn=lambda: client.groups
                                ).start()
        client.self_address = srv.address
        return srv

    seed_store = DiskStore(str(tmp_path / "seed"))
    seed = CacheClient(seed_store, no_peers)
    seed_cm = CheckpointManager(seed, record=record,
                                store_manifest=store_manifest,
                                fetch_manifest=fetch_manifest)
    assert await seed_cm.create("s", "w", "seed", str(src))
    trees, _ = await seed_cm.restore_params("ckpt", device_put=ident)
    assert len(trees) == 2
    servers = [await serve(seed, seed_store)]
    keys = sorted(seed.groups)

    holding: set = set()

    async def fleet():
        # the peers a joiner knows: the replicas that hold the checkpoint
        return sorted(holding)

    joiners = []
    for i in range(3):
        store = DiskStore(str(tmp_path / f"j{i}"))
        # no hedge: every byte is attributed to the edge it came over
        cl = CacheClient(store, fleet, source=source, hedge_delay_s=5.0)
        servers.append(await serve(cl, store))
        joiners.append(cl)

    coord = ScaleoutCoordinator(ScaleoutConfig(tree_fanout=1))
    coord.observe_worker("seed", {"cache": seed.snapshot()}, now=100.0)
    for i, cl in enumerate(joiners):
        coord.observe_worker(f"j{i}", {"cache": cl.snapshot()}, now=100.0)
    plan = coord.refresh(now=100.0)
    assert coord.stats()["source_edges"] == 0

    def primary(cl):
        return plan.parents(cl.self_address, keys[0])[0]

    # restore in tree order: a joiner goes once its parent holds the group
    served_by: dict = {}
    holding.add(seed.self_address)
    try:
        while len(holding) < 4:
            ready = [cl for cl in joiners
                     if cl.self_address not in holding
                     and primary(cl) in holding]
            assert ready, "the plan does not chain off the holder"
            for cl in ready:
                async def hints(key, cl=cl):
                    return plan.peer_prefs(cl.self_address, key)
                cm = CheckpointManager(cl, fetch_manifest=fetch_manifest,
                                       tree_hints=hints)
                trees, metrics = await cm.restore_params(
                    "ckpt", device_put=ident)
                assert len(trees) == 2
                assert cl.stats["bytes_source"] == 0
                for addr, n in metrics["peer_bytes"].items():
                    served_by[addr] = served_by.get(addr, 0) + n
                holding.add(cl.self_address)
    finally:
        for cl in joiners + [seed]:
            await cl.close()
        for srv in servers:
            await srv.stop()
    one_copy = served_by[seed.self_address]
    assert one_copy == 2 * 2 * 16384 * 4        # every shard, once
    # three joiners took three copies, the holder shipped one of them
    assert sum(served_by.values()) == 3 * one_copy
    assert sorted(served_by.values()) == [one_copy] * 3
