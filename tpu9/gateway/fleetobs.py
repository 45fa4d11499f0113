"""FleetObserver: the gateway's fleet-evidence sampler (ISSUE 12).

Owns the bounded :class:`~tpu9.observability.timeline.TimelineStore`, the
:class:`~tpu9.observability.slo.SloEvaluator` and the
:class:`~tpu9.observability.slo.GoodputAccountant`, and wires them to the
cadences the system already has:

- **pressure-heartbeat cadence** (``/rpc/llm/pressure`` ingest): every
  accepted engine heartbeat records that replica's timeline series
  (tokens/sec, KV blocks, spec acceptance, recompile sentinel, MFU/MBU
  priced from the shipped physics constants) and feeds the goodput
  accountant's engine counters;
- **sampler tick** (``slo.sample_interval_s``): per-stub router series
  (queue depth, shed/submitted counters, TTFT/queue-wait percentiles,
  pressure), SLO burn-rate evaluation folded into the autoscaler
  pressure feed via ``RouterSignals.slo_sample``, goodput router
  counters, Prometheus gauge publication, and timeline pruning.

The observer also owns stale-replica aging for the ``/api/v1/metrics``
``engines`` merge: a replica silent longer than ``slo.stale_after_s``
(default 3 runner heartbeats) is dropped (and its accountant delta base forgotten) instead
of serving dead stats until the store TTL.
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import Optional

from ..observability.slo import GoodputAccountant, SloEvaluator
from ..observability.timeline import TimelineStore
from ..observability.usage import bucket_of, usage_key
from ..utils.aio import event_wait, reap

log = logging.getLogger("tpu9.gateway")

# engine heartbeat fields mirrored 1:1 into per-replica timeline series
ENGINE_SERIES = ("tokens_per_sec", "token_pressure", "queued",
                 "kv_blocks_free", "kv_blocks_used", "kv_blocks_reserved",
                 "spec_acceptance_rate", "graph_compiles_post_warmup",
                 "active_streams",
                 # replica health plane (ISSUE 14): HBM watermarks (live
                 # vs planner-predicted — the drift graph) + the liveness
                 # watermark ages behind the watchdog's verdict
                 "hbm_used_gb_per_chip", "hbm_peak_gb_per_chip",
                 "hbm_predicted_gb_per_chip", "hbm_limit_gb_per_chip",
                 "windows_processed", "last_dispatch_age_s",
                 "last_progress_age_s",
                 # replica-level prefix-cache effectiveness (ISSUE 2
                 # satellite shipped it; ISSUE 18's wirecheck caught that
                 # no gateway consumer ever read it): the per-replica
                 # twin of the router-side tpu9_router_prefix_hit_rate —
                 # divergence between the two is the affinity router
                 # mis-steering
                 "prefix_hits", "prefix_misses", "prefix_hit_rate",
                 # kvwire block-ship plane (ISSUE 16): export/import
                 # ledger + ship latency — `tpu9 top`'s migration view
                 "kvwire_blocks_exported", "kvwire_blocks_imported",
                 "kvwire_bytes_exported", "kvwire_bytes_imported",
                 "kvwire_import_hits", "kvwire_import_fallbacks",
                 "kvwire_ship_p50_s", "kvwire_ship_p95_s",
                 # scale-out plane (ISSUE 17): execute-while-scaling
                 # per-group weight readiness — the router's admission
                 # fence and `tpu9 scaleout`'s readiness fraction
                 "scaleout_groups_total", "scaleout_groups_ready",
                 "scaleout_ready_frac",
                 # KV tiering plane (ISSUE 20): tier occupancy + paging
                 # traffic — `tpu9 top`'s KV-tier columns and the
                 # hit-rate-by-tier split
                 "kvtier_device_blocks", "kvtier_device_bytes",
                 "kvtier_host_blocks", "kvtier_host_bytes",
                 "kvtier_host_entries", "kvtier_host_evictions",
                 "kvtier_downpages", "kvtier_uppages",
                 "kvtier_uppage_failures", "kvtier_peer_spills",
                 "kvtier_hits_device", "kvtier_hits_host",
                 "kvtier_downpage_p50_s", "kvtier_downpage_p95_s",
                 "kvtier_uppage_p50_s", "kvtier_uppage_p95_s")
# router snapshot fields mirrored into per-stub timeline series
ROUTER_SERIES = ("queue_depth", "shed_rate", "pressure")
# worker-heartbeated cache-plane counters mirrored 1:1 into per-worker
# cache.* timeline series (ISSUE 13)
CACHE_SERIES = ("local_hits", "peer_hits", "source_fetches", "peer_errors",
                "hedged_reads", "hedge_wins", "hedge_wasted_bytes",
                "bytes_local", "bytes_peer", "bytes_source")
WEIGHTPOOL_SERIES = ("hits", "misses", "evictions", "rejected", "inserts",
                     "entries", "bytes")


def _num(d: dict, key: str, default: float = 0.0) -> float:
    try:
        return float(d.get(key, default))
    except (TypeError, ValueError):
        return default


class FleetObserver:
    def __init__(self, cfg, store, fleet_router=None, scaleout=None):
        """``cfg`` is an AppConfig.slo (SloConfig). ``scaleout`` is an
        optional :class:`~tpu9.scaleout.coordinator.ScaleoutCoordinator`
        (ISSUE 17): when present, worker cache-plane snapshots and engine
        heartbeats feed its group ledger, and every sampler tick
        republishes the refreshed multicast tree plan to the store."""
        self.cfg = cfg
        self.store = store
        self.fleet_router = fleet_router
        self.scaleout = scaleout
        self.timeline = TimelineStore(
            capacity=cfg.timeline_capacity,
            max_series=cfg.timeline_max_series,
            idle_ttl_s=cfg.timeline_idle_ttl_s)
        self.evaluator = SloEvaluator(self.timeline, cfg.objectives,
                                      burn_alert=cfg.burn_alert)
        self.goodput = GoodputAccountant(window_s=cfg.goodput_window_s)
        self._task: Optional[asyncio.Task] = None
        self._stopping = asyncio.Event()

    @property
    def stale_after_s(self) -> float:
        return self.cfg.stale_after_s

    # -- lifecycle -----------------------------------------------------------

    async def start(self) -> "FleetObserver":
        if self._task is None:
            self._task = asyncio.create_task(self._loop())
        return self

    async def stop(self) -> None:
        self._stopping.set()
        if self._task is not None:
            await reap(self._task)
            self._task = None

    async def _loop(self) -> None:
        while not self._stopping.is_set():
            try:
                await self.sample()
            except Exception:   # noqa: BLE001 — evidence collection must
                log.exception("fleet observer tick failed")  # not die
            await event_wait(self._stopping, self.cfg.sample_interval_s)

    # -- heartbeat-cadence ingest (called from /rpc/llm/pressure) ------------

    def ingest_heartbeat(self, container_id: str, workspace_id: str,
                         stub_id: str, token_pressure: float,
                         active_streams: int,
                         extra: Optional[dict] = None) -> None:
        """One accepted engine heartbeat → per-replica timeline series +
        goodput engine counters. Values arrive as the flat scalars the
        runner ships (strings after a store round-trip are fine)."""
        stats = dict(extra or {})
        stats["token_pressure"] = token_pressure
        stats["active_streams"] = active_streams
        prefix = f"engine.{container_id}."
        for key in ENGINE_SERIES:
            if key in stats:
                self.timeline.record(prefix + key, _num(stats, key))
        # replica health (ISSUE 14): numeric state series (0 ok /
        # 1 degraded / 2 stalled), tpu9_health_*/tpu9_hbm_* gauges, and
        # the routing fold — a `stalled` verdict ejects the replica from
        # affinity/JSQ the way draining does, a recovered one restores it
        if "health" in stats:
            from ..observability.health import health_code, publish_health
            state = str(stats.get("health", ""))
            self.timeline.record(prefix + "health", health_code(state))
            publish_health(container_id, stats)
            note = getattr(self.fleet_router, "note_replica_health", None)
            if note is not None:     # duck-typed router fakes in tests
                note(container_id, state,
                     reason=str(stats.get("health_reason", "")))
        # kvwire gauges (ISSUE 16): only for replicas that ship blocks —
        # a fleet with shipping off mints zero extra series
        if any(k.startswith("kvwire_") for k in stats):
            from ..observability.health import publish_kvwire
            publish_kvwire(container_id, stats)
        # KV tiering gauges (ISSUE 20): only replicas running a host tier
        # emit kvtier_* scalars, so an untiered fleet mints zero series
        if any(k.startswith("kvtier_") for k in stats):
            from ..observability.health import publish_kvtier
            publish_kvtier(container_id, stats)
            # the directory fold also rides the observer path so
            # heartbeats reach it even between dispatches (the dispatch
            # path re-folds the same snapshot — observe is idempotent)
            pdir = getattr(self.fleet_router, "prefix_dir", None)
            if pdir is not None:
                pdir.observe_replica(container_id, stats)
        # scale-out plane (ISSUE 17): per-group readiness → coordinator
        # ledger (serving-plane truth for the report + admission fence),
        # measured bring-up → router signals (the predictive controller's
        # scale-down guard must use MEASURED re-acquisition cost)
        if self.scaleout is not None and "scaleout_ready_frac" in stats:
            self.scaleout.observe_heartbeat(container_id, stats)
        ready_s = _num(stats, "coldstart_ready_s")
        if ready_s > 0 and self.fleet_router is not None:
            note = getattr(self.fleet_router.signals, "note_bringup", None)
            if note is not None:    # duck-typed router fakes in tests
                note(stub_id, ready_s)
        # MFU/MBU priced control-plane-side from the engine's physics
        # constants (bytes / FLOPs per token per chip) × tokens/sec,
        # against the chip's public peaks. A replica on a device the peak
        # table does not know (a CPU host) publishes neither series.
        tps = _num(stats, "tokens_per_sec")
        bpt = _num(stats, "decode_bytes_per_token_per_chip")
        fpt = _num(stats, "decode_flops_per_token_per_chip")
        if tps > 0 and (bpt > 0 or fpt > 0):
            from ..benchsuite.physics import chip_spec
            try:
                spec = chip_spec(str(stats.get("device_kind", "")))
            except KeyError:
                spec = None
            if spec is not None:
                self.timeline.record(prefix + "mbu",
                                     tps * bpt / (spec.hbm_gbps * 1e9))
                self.timeline.record(
                    prefix + "mfu",
                    tps * fpt / (spec.peak_bf16_tflops * 1e12))
        self.goodput.engine_sample(container_id, workspace_id, stub_id,
                                   stats)

    # -- sampler tick --------------------------------------------------------

    async def sample(self) -> None:
        """One observer tick: router series, SLO evaluation + pressure
        fold, goodput router counters, gauge publication, pruning."""
        if self.fleet_router is not None:
            signals = self.fleet_router.signals
            seen_stubs: set = set()
            for stub in self.fleet_router.active_stubs():
                sid = stub.stub_id
                seen_stubs.add(sid)
                snap = signals.snapshot(sid)
                prefix = f"router.{sid}."
                # LIVE fair-queue depth, not the last dispatch-time
                # sample: a burst that sheds between dispatch passes
                # must still show the queue it built
                if hasattr(self.fleet_router, "queue_depth"):
                    snap["queue_depth"] = self.fleet_router.queue_depth(sid)
                for key in ROUTER_SERIES:
                    self.timeline.record(prefix + key,
                                         float(snap.get(key, 0.0)))
                # cumulative counters the burn windows differentiate
                self.timeline.record(prefix + "submitted_total",
                                     float(snap.get("submitted", 0)))
                self.timeline.record(prefix + "shed_total",
                                     float(snap.get("shed", 0)))
                lat = snap.get("latency") or {}
                qw_total = 0.0
                for phase, row in lat.items():
                    self.timeline.record(f"{prefix}{phase}_p50_s",
                                         row.get("p50_s", 0.0))
                    self.timeline.record(f"{prefix}{phase}_p95_s",
                                         row.get("p95_s", 0.0))
                    if phase == "queue_wait":
                        # count × mean == cumulative queue-wait seconds
                        qw_total = (row.get("count", 0)
                                    * row.get("mean_s", 0.0))
                # SLO burn: evaluate, publish, fold into pressure
                evaluated = self.evaluator.evaluate(sid)
                for name, entry in evaluated.items():
                    self.timeline.record(
                        f"slo.{sid}.{name}.burn_fast",
                        entry["fast"]["burn"])
                    self.timeline.record(
                        f"slo.{sid}.{name}.burn_slow",
                        entry["slow"]["burn"])
                self.evaluator.publish(sid, evaluated)
                # worst slow-window burn rides along (ISSUE 17): the
                # predictive controller projects the FAST burn's slope
                # against the slow window's remaining budget
                signals.slo_sample(
                    sid, self.evaluator.max_fast_burn(evaluated),
                    max((e["slow"]["burn"] for e in evaluated.values()),
                        default=0.0))
                self.goodput.router_sample(
                    sid, stub.workspace_id,
                    submitted_total=float(snap.get("submitted", 0)),
                    shed_total=float(snap.get("shed", 0)),
                    queue_wait_total_s=qw_total)
            # stub churn (ISSUE 18): a stub that left active_stubs()
            # takes its per-stub gauges and rolling state with it — the
            # same prune filter_engines applies to replica series, at
            # the stub granularity
            for sid in getattr(self, "_sampled_stubs", set()) - seen_stubs:
                signals.forget_stub(sid)
                self.evaluator.forget_stub(sid)
                self.goodput.forget_stub(sid)
            self._sampled_stubs = seen_stubs
        await self.sample_cache_plane()
        self.sample_decisions()
        self.goodput.publish(await self.goodput_snapshot())
        self.timeline.prune()
        # decision-ledger index pruning rides the same tick (ISSUE 19):
        # finished requests' chains age out with timeline retention
        from ..observability.decisions import ledger as decision_ledger
        decision_ledger.prune()

    def sample_decisions(self) -> None:
        """Autoscaler verdicts → ``scaleout.{stub}.*`` timeline series
        (ISSUE 19 satellite): each predictive tick already left one
        ledger record; mirror its direction / projection / guard signals
        into the bounded rings so `tpu9 scaleout` and the dashboards get
        scaling history, not just the latest verdict. Seq-cursored so a
        record is sampled exactly once."""
        from ..observability.decisions import ledger as decision_ledger
        direction = {"up": 1.0, "down": -1.0, "hold": 0.0, "fallback": 0.0}
        recs, self._dec_cursor = decision_ledger.export_new(
            since_seq=getattr(self, "_dec_cursor", 0), limit=1000)
        for rec in recs:
            if rec.get("plane") != "autoscaler" \
                    or rec.get("decision") != "decide_scale":
                continue
            sid = rec.get("stub_id") or "fleet"
            sig = rec.get("signals") or {}
            prefix = f"scaleout.{sid}."
            self.timeline.record(prefix + "direction",
                                 direction.get(sig.get("action", ""), 0.0),
                                 ts=rec.get("ts"))
            for name in ("projected", "desired", "bringup_guard"):
                if name in sig:
                    self.timeline.record(prefix + name,
                                         _num(sig, name), ts=rec.get("ts"))

    async def sample_cache_plane(self) -> None:
        """Worker-heartbeated cache/weight-pool snapshots → per-worker
        (and per-peer) timeline series (ISSUE 13): the restore and
        weight-distribution plane's history — what the ROADMAP item-3
        scale-out bench reads to see N replicas share one peer tree."""
        import json
        for key in await self.store.keys("worker:cache:*"):
            raw = await self.store.get(key)
            if not raw:
                continue
            try:
                snap = json.loads(raw)
            except (ValueError, TypeError):
                continue
            wid = key.rsplit(":", 1)[-1]
            cache = snap.get("cache") or {}
            if self.scaleout is not None:
                # cache-plane truth for the multicast tree (ISSUE 17):
                # which replica HOLDS which shard groups, and the
                # per-peer latency EWMAs the edge picker weighs
                self.scaleout.observe_worker(wid, snap)
            prefix = f"cache.{wid}."
            for name in CACHE_SERIES:
                if name in cache:
                    self.timeline.record(prefix + name, _num(cache, name))
            for tier in ("local", "peer", "source"):
                rate = f"{tier}_bytes_per_s"
                if rate in snap:
                    self.timeline.record(prefix + rate, _num(snap, rate))
            # per-peer latency/bytes: bounded by fleet size, the evidence
            # hedging decisions and KV-shipping (ROADMAP item 2) read
            for peer, ps in (cache.get("peers") or {}).items():
                ppre = f"cache.{wid}.peer.{peer}."
                self.timeline.record(ppre + "lat_ewma_s",
                                     _num(ps, "lat_ewma_s"))
                self.timeline.record(ppre + "bytes", _num(ps, "bytes"))
                self.timeline.record(ppre + "errors", _num(ps, "errors"))
            pool = snap.get("weightpool") or {}
            for name in WEIGHTPOOL_SERIES:
                if name in pool:
                    self.timeline.record(f"weightpool.{wid}.{name}",
                                         _num(pool, name))
        if self.scaleout is not None:
            # re-plan the multicast tree over fresh holders and publish
            # it where joining workers' tree_hints read it; short TTL so
            # a dead gateway's plan ages out instead of steering forever
            from ..scaleout.coordinator import PLAN_KEY
            plan = self.scaleout.refresh()
            await self.store.set(
                PLAN_KEY, json.dumps(plan.to_dict()),
                ttl=max(int(self.cfg.sample_interval_s * 6), 30))

    # -- engines-section aging (ISSUE 12 satellite) --------------------------

    def filter_engines(self, engines: dict) -> dict:
        """Stamp ``last_seen``/``age_s`` from each heartbeat's wall stamp
        and drop replicas silent > N beats — /api/v1/metrics must not
        serve dead stats until the store TTL. Aged-out replicas also lose
        their goodput delta base (a restart starts a fresh interval)."""
        now = time.time()
        out: dict = {}
        for cid, snap in engines.items():
            ts = _num(snap, "ts")
            age = max(now - ts, 0.0) if ts else 0.0
            if ts and age > self.stale_after_s:
                self.goodput.forget_replica(cid)
                # drop its health/HBM gauges too (ISSUE 14): the dead
                # replica's last verdict must not alert forever, and
                # per-cid gauge series must not accumulate under churn
                from ..observability.health import forget_replica
                forget_replica(cid)
                continue
            row = dict(snap)
            row["last_seen"] = ts
            row["age_s"] = round(age, 3)
            out[cid] = row
        return out

    # -- endpoint payloads ---------------------------------------------------

    def timeline_payload(self, series: str, since: float,
                         limit: Optional[int]) -> dict:
        if not series:
            return {"series_names": self.timeline.series_names(),
                    "capacity": self.timeline.capacity,
                    "samples": self.timeline.sample_count()}
        names = [s.strip() for s in series.split(",") if s.strip()]
        return {"series": self.timeline.query(names, since=since,
                                              limit=limit)}

    def slo_payload(self) -> dict:
        stubs: dict = {}
        known = (self.fleet_router.active_stubs()
                 if self.fleet_router is not None else [])
        signals = (self.fleet_router.signals
                   if self.fleet_router is not None else None)
        for stub in known:
            sid = stub.stub_id
            evaluated = self.evaluator.evaluate(sid)
            row = {"workspace_id": stub.workspace_id,
                   "objectives": evaluated}
            if signals is not None:
                row["slo_pressure"] = signals.slo_pressure(sid)
                row["pressure"] = signals.pressure(sid)
            stubs[sid] = row
        return {
            "objectives": [{
                "name": o.name, "kind": o.kind, "target": o.target,
                "metric": o.metric if o.kind == "latency" else "",
                "attainment": o.attainment if o.kind == "latency" else None,
                "fast_window_s": o.fast_window_s,
                "slow_window_s": o.slow_window_s,
            } for o in self.cfg.objectives],
            "burn_alert": self.cfg.burn_alert,
            "stubs": stubs,
        }

    async def goodput_snapshot(self) -> dict:
        """Per-workspace decomposition joined against usage.py's metered
        chip-second buckets (the billing denominator; the accountant's
        own replica-seconds stand in when the meter reads zero — CPU dev
        fleets meter 0 chips)."""
        workspaces = self.goodput.workspaces()
        metered: dict[str, float] = {}
        window_h = max(int(self.goodput.window_s // 3600), 0) + 1
        now = time.time()
        window_start = now - self.goodput.window_s
        for ws in workspaces:
            total = 0.0
            for h in range(window_h + 1):
                bucket_start = (now // 3600 - h) * 3600
                # prorate by the overlap between the accounting window
                # and the bucket's DATA span (metering stops at `now`
                # for the current bucket; chip-seconds assumed uniform
                # within the span): summing whole buckets would count up
                # to an extra hour of denominator at the top of each
                # hour, understating goodput by up to ~2x on a metered
                # fleet
                span_end = min(now, bucket_start + 3600)
                span = span_end - bucket_start
                overlap = span_end - max(window_start, bucket_start)
                if overlap <= 0 or span <= 0:
                    continue
                hot = await self.store.hgetall(
                    usage_key(ws, bucket_of(bucket_start)))
                if hot:
                    chips = _num(hot, "chip_seconds")
                    if chips > 0:
                        total += chips * min(overlap / span, 1.0)
            metered[ws] = total
        return self.goodput.snapshot(usage_chip_seconds=metered)

    async def metrics_section(self) -> dict:
        return await self.goodput_snapshot()
