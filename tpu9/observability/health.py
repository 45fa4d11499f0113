"""Replica health plane (ISSUE 14): gray-failure watchdog + black box.

The serve stack's failure-path evidence layer. Three pieces, all passive
dict-in/dict-out (this module never imports serving or router — the
boundary the BND001 contract closes):

- :class:`EngineWatchdog` — classifies one engine's liveness from the
  progress watermark the engine stamps into ``stats()`` (windows
  processed, tokens delivered, admit dispatches). The failure mode this
  exists for is *gray failure*: a replica whose runner still heartbeats
  while its serve loop is wedged (device hang, deadlock, compile storm)
  keeps receiving affinity-routed traffic forever — the runner feeds the
  watchdog each pressure beat and ships the verdict on the same
  heartbeat, so the fleet sees ``stalled`` within a beat budget instead
  of never.

  State machine (assessed per beat)::

      ok ── work waiting + no watermark movement ≥ degraded_after_s ──▶ degraded
      ok/degraded ── no movement ≥ stall_after_s (or engine_dead) ────▶ stalled
      degraded ◀── post-warmup compile within storm_window_s ── ok
      any ── watermark moves (or queue empties) ─────────────────────▶ ok

  An *idle* replica (no queued work, no active streams) is always ``ok``
  — a frozen watermark only indicts the loop when there is work it
  should be moving.

- HBM watermarks — the engine samples ``device.memory_stats()`` on the
  ``stats()`` read path (heartbeat cadence, zero serve-loop cost) into
  current/peak/limit gauges next to the planner's predicted residency,
  so planner-vs-reality drift is a graphable number
  (``engine.<cid>.hbm_*`` timeline series, ``tpu9_hbm_*`` gauges).

- post-mortem black box — :func:`build_postmortem` assembles, and
  :func:`clamp_postmortem` size-bounds, the forensic record a dying or
  wedged engine leaves behind (last-K flight windows, recent spans,
  KV-pool + scheduler state, HBM breakdown, exception). The runner ships
  it over ``/rpc/llm/postmortem``; the gateway stores it under
  ``postmortem:<container_id>`` and merges at ``GET /api/v1/postmortem``
  — evidence that survives the process it describes.
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass
from typing import Optional

from .metrics import metrics

# health states, in severity order
OK = "ok"
DEGRADED = "degraded"
STALLED = "stalled"
_STATE_CODE = {OK: 0, DEGRADED: 1, STALLED: 2}

# black-box storage contract (gateway side)
POSTMORTEM_KEY = "postmortem:{cid}"
POSTMORTEM_TTL_S = 24 * 3600.0
MAX_POSTMORTEM_RECORDS = 8       # retained per replica (newest win)
MAX_POSTMORTEM_BYTES = 256 * 1024   # one record's JSON bound
FLIGHT_TAIL = 64                 # flight windows carried in a record
SPAN_TAIL = 128                  # spans carried in a record


def health_code(state: str) -> int:
    """Numeric gauge encoding (0 ok / 1 degraded / 2 stalled); unknown
    strings read as stalled — an unparseable health report must never
    look healthy."""
    return _STATE_CODE.get(str(state), _STATE_CODE[STALLED])


def _num(d: dict, key: str, default: float = 0.0) -> float:
    try:
        return float(d.get(key, default))
    except (TypeError, ValueError):
        return default


@dataclass
class WatchdogConfig:
    """Watchdog thresholds. The defaults assume the runner's 2 s
    pressure-beat cadence: degraded after ~2 missed-progress beats,
    stalled after ~3 — aligned with the fleet's 3-beat staleness budget
    (SloConfig.stale_after_s) so a gray failure is ejected on the same
    clock a silent one ages out on."""
    stall_after_s: float = 6.0       # work waiting, watermark frozen
    degraded_after_s: float = 2.5    # early warning, same condition
    storm_window_s: float = 30.0     # degraded-sticky after a post-warmup
    #                                  compile (the ISSUE 11 sentinel)
    hbm_pressure_frac: float = 0.97  # used/limit above this = degraded

    @classmethod
    def from_env(cls, env: Optional[dict] = None) -> "WatchdogConfig":
        e = env if env is not None else os.environ

        def f(key: str, default: float) -> float:
            try:
                return float(e.get(key, "") or default)
            except (TypeError, ValueError):
                return default

        return cls(
            stall_after_s=f("TPU9_HEALTH_STALL_S", cls.stall_after_s),
            degraded_after_s=f("TPU9_HEALTH_DEGRADED_S",
                               cls.degraded_after_s),
            storm_window_s=f("TPU9_HEALTH_STORM_S", cls.storm_window_s),
            hbm_pressure_frac=f("TPU9_HEALTH_HBM_FRAC",
                                cls.hbm_pressure_frac))


class EngineWatchdog:
    """Per-replica liveness classifier over successive ``stats()``
    snapshots. Pure host arithmetic on plain scalars — safe to run on
    the runner's heartbeat loop next to a wedged serve loop (it never
    touches the engine beyond the dict it is handed)."""

    def __init__(self, cfg: Optional[WatchdogConfig] = None):
        self.cfg = cfg or WatchdogConfig()
        self.state = OK
        self.reason = ""
        self._since = time.monotonic()
        self._watermark: Optional[tuple] = None
        self._progress_mono = time.monotonic()
        self._compiles_seen: Optional[int] = None
        self._storm_until = 0.0
        self._stall_trip = False

    @property
    def in_state_s(self) -> float:
        return max(time.monotonic() - self._since, 0.0)

    def pop_stall_trip(self) -> bool:
        """True exactly once per entry into ``stalled`` — the runner's
        cue to ship a watchdog-trip post-mortem. Re-arms on recovery."""
        trip, self._stall_trip = self._stall_trip, False
        return trip

    def assess(self, stats: dict,
               now: Optional[float] = None) -> tuple[str, str]:
        """Classify one snapshot; returns ``(state, reason)`` and keeps
        them on ``self``. Call once per heartbeat."""
        now = time.monotonic() if now is None else now
        queued = int(_num(stats, "queued"))
        active = int(_num(stats, "active_streams"))
        work_waiting = queued > 0 or active > 0
        watermark = (int(_num(stats, "windows_processed")),
                     int(_num(stats, "tokens_generated")),
                     int(_num(stats, "admit_dispatches")))
        if self._watermark is None or watermark != self._watermark:
            self._watermark = watermark
            self._progress_mono = now
        if not work_waiting:
            # idle: a frozen watermark indicts nothing — keep the
            # progress clock fresh so the first post-idle request starts
            # a new stall window instead of inheriting the idle age
            self._progress_mono = now
        age = now - self._progress_mono

        compiles = int(_num(stats, "graph_compiles_post_warmup"))
        if self._compiles_seen is None:
            self._compiles_seen = compiles   # baseline, not an incident
        elif compiles > self._compiles_seen:
            self._compiles_seen = compiles
            self._storm_until = now + self.cfg.storm_window_s

        state, reason = OK, ""
        if stats.get("engine_dead"):
            state, reason = STALLED, "engine_dead"
        elif work_waiting and age >= self.cfg.stall_after_s:
            state, reason = STALLED, "no_progress_with_queued_work"
        elif now < self._storm_until:
            state, reason = DEGRADED, "compile_storm"
        elif work_waiting and age >= self.cfg.degraded_after_s:
            state, reason = DEGRADED, "slow_progress"
        else:
            limit = _num(stats, "hbm_limit_gb_per_chip")
            used = _num(stats, "hbm_used_gb_per_chip")
            if limit > 0 and used / limit >= self.cfg.hbm_pressure_frac:
                state, reason = DEGRADED, "hbm_pressure"

        if state != self.state:
            if state == STALLED:
                self._stall_trip = True
            self.state, self._since = state, now
        self.reason = reason
        return state, reason


# -- gauge publication (gateway side, heartbeat cadence) ---------------------

# every per-replica gauge publish_health/publish_kvwire may mint —
# forget_replica must drop exactly this set or dead replicas alert
# forever
_REPLICA_GAUGES = ("tpu9_health_state", "tpu9_health_stalled",
                   "tpu9_hbm_used_gb", "tpu9_hbm_peak_gb",
                   "tpu9_hbm_predicted_gb", "tpu9_hbm_limit_gb",
                   "tpu9_hbm_headroom_frac")
# kvwire block-ship plane (ISSUE 16): gauge name ↔ heartbeat scalar
_KVWIRE_GAUGES = (
    ("tpu9_kvwire_blocks_exported", "kvwire_blocks_exported"),
    ("tpu9_kvwire_blocks_imported", "kvwire_blocks_imported"),
    ("tpu9_kvwire_bytes_exported", "kvwire_bytes_exported"),
    ("tpu9_kvwire_bytes_imported", "kvwire_bytes_imported"),
    ("tpu9_kvwire_import_hits", "kvwire_import_hits"),
    ("tpu9_kvwire_import_fallbacks", "kvwire_import_fallbacks"),
    ("tpu9_kvwire_ship_p50_s", "kvwire_ship_p50_s"),
    ("tpu9_kvwire_ship_p95_s", "kvwire_ship_p95_s"))
# KV tiering plane (ISSUE 20): occupancy + paging traffic per replica —
# gauge name ↔ heartbeat scalar, same lifecycle as the kvwire set
_KVTIER_GAUGES = (
    ("tpu9_kvtier_device_blocks", "kvtier_device_blocks"),
    ("tpu9_kvtier_device_bytes", "kvtier_device_bytes"),
    ("tpu9_kvtier_host_blocks", "kvtier_host_blocks"),
    ("tpu9_kvtier_host_bytes", "kvtier_host_bytes"),
    ("tpu9_kvtier_host_entries", "kvtier_host_entries"),
    ("tpu9_kvtier_host_evictions", "kvtier_host_evictions"),
    ("tpu9_kvtier_downpages", "kvtier_downpages"),
    ("tpu9_kvtier_uppages", "kvtier_uppages"),
    ("tpu9_kvtier_uppage_failures", "kvtier_uppage_failures"),
    ("tpu9_kvtier_peer_spills", "kvtier_peer_spills"),
    ("tpu9_kvtier_hits_device", "kvtier_hits_device"),
    ("tpu9_kvtier_hits_host", "kvtier_hits_host"))


def forget_replica(container_id: str) -> None:
    """Drop a dead replica's health/HBM/kvwire/kvtier gauges (called when
    the fleet observer ages it out of the engines merge): its last
    verdict — typically ``stalled`` — must not keep alerting for a
    container that no longer exists, and under scale-to-zero churn
    container ids are unbounded, so leaked series grow monotonically."""
    labels = {"replica": container_id}
    for gauge in _REPLICA_GAUGES:
        metrics.remove_gauge(gauge, labels=labels)
    for gauge, _key in _KVWIRE_GAUGES:
        metrics.remove_gauge(gauge, labels=labels)
    for gauge, _key in _KVTIER_GAUGES:
        metrics.remove_gauge(gauge, labels=labels)


def publish_kvwire(container_id: str, stats: dict) -> None:
    """``tpu9_kvwire_*`` gauges for one replica heartbeat (ISSUE 16):
    the block-ship ledger — exported/imported blocks+bytes, adopt hits
    vs re-prefill fallbacks, ship latency percentiles. Same replica-
    label lifecycle as the health gauges (forget_replica drops them)."""
    labels = {"replica": container_id}
    for gauge, key in _KVWIRE_GAUGES:
        if key in stats:
            metrics.set_gauge(gauge, _num(stats, key), labels=labels)


def publish_kvtier(container_id: str, stats: dict) -> None:
    """``tpu9_kvtier_*`` gauges for one replica heartbeat (ISSUE 20):
    tier occupancy (device/host bytes + blocks), up/down-page counters
    and latency percentiles, prefix hits split by serving tier. Same
    replica-label lifecycle as the kvwire set (forget_replica drops
    them)."""
    labels = {"replica": container_id}
    for gauge, key in _KVTIER_GAUGES:
        if key in stats:
            metrics.set_gauge(gauge, _num(stats, key), labels=labels)


def publish_health(container_id: str, stats: dict) -> None:
    """``tpu9_health_*`` / ``tpu9_hbm_*`` gauge families for one replica
    heartbeat. Label cardinality is bounded by fleet size (replica ids),
    the same contract as the per-stub ``tpu9_slo_*`` gauges; values are
    the flat scalars the runner shipped."""
    labels = {"replica": container_id}
    state = str(stats.get("health", OK) or OK)
    metrics.set_gauge("tpu9_health_state", health_code(state),
                      labels=labels)
    metrics.set_gauge("tpu9_health_stalled",
                      1.0 if state == STALLED else 0.0, labels=labels)
    for gauge, key in (("tpu9_hbm_used_gb", "hbm_used_gb_per_chip"),
                       ("tpu9_hbm_peak_gb", "hbm_peak_gb_per_chip"),
                       ("tpu9_hbm_predicted_gb",
                        "hbm_predicted_gb_per_chip"),
                       ("tpu9_hbm_limit_gb", "hbm_limit_gb_per_chip")):
        if key in stats:
            metrics.set_gauge(gauge, _num(stats, key), labels=labels)
    limit = _num(stats, "hbm_limit_gb_per_chip")
    if limit > 0:
        headroom = max(1.0 - _num(stats, "hbm_used_gb_per_chip") / limit,
                       0.0)
        metrics.set_gauge("tpu9_hbm_headroom_frac", headroom,
                          labels=labels)


# -- post-mortem black box ---------------------------------------------------

def build_postmortem(*, reason: str, exception: str = "",
                     container_id: str = "",
                     stats: Optional[dict] = None,
                     scheduler: Optional[dict] = None,
                     kv_pool: Optional[dict] = None,
                     hbm: Optional[dict] = None,
                     flight: Optional[list] = None,
                     spans: Optional[list] = None) -> dict:
    """Assemble one bounded forensic record. Every field is plain-JSON;
    the caller hands in whatever evidence survived (a crashed engine may
    only have stats + flight)."""
    rec = {
        "reason": str(reason),
        "exception": str(exception)[:2000],
        "container_id": container_id,
        "ts": round(time.time(), 3),
        "stats": {k: v for k, v in (stats or {}).items()
                  if isinstance(v, (int, float, str, bool))},
        "scheduler": dict(scheduler or {}),
        "kv_pool": dict(kv_pool or {}),
        "hbm": dict(hbm or {}),
        "flight": list(flight or [])[-FLIGHT_TAIL:],
        "spans": list(spans or [])[-SPAN_TAIL:],
    }
    return clamp_postmortem(rec)


# the record schema's whole key surface: clamping WHITELISTS these, so a
# forged record cannot smuggle unbounded payload under a novel key
_RECORD_KEYS = ("reason", "exception", "container_id", "ts",
                "workspace_id", "stub_id",
                "stats", "scheduler", "kv_pool", "hbm", "flight", "spans")
_HEADER_KEYS = ("reason", "exception", "container_id", "ts",
                "workspace_id", "stub_id")


def clamp_postmortem(rec: dict,
                     max_bytes: int = MAX_POSTMORTEM_BYTES) -> dict:
    """Bound one record to the schema AND the byte budget: unknown keys
    are dropped, header strings truncated, the oldest flight windows then
    the oldest spans then the evidence dicts shed — and if a (possibly
    hostile) record STILL exceeds the budget, everything but the
    truncated header goes. The gateway re-clamps every shipped record
    through here, so the black box can never be the thing that OOMs the
    statestore, whatever a container token holder POSTs."""
    rec = {k: rec[k] for k in _RECORD_KEYS if k in rec}
    rec["reason"] = str(rec.get("reason", ""))[:200]
    rec["exception"] = str(rec.get("exception", ""))[:2000]
    for key in ("container_id", "workspace_id", "stub_id"):
        if key in rec:
            rec[key] = str(rec[key])[:128]
    try:
        rec["ts"] = round(float(rec.get("ts", 0.0)), 3)
    except (TypeError, ValueError):
        rec["ts"] = 0.0
    # section TYPES are part of the schema too: every consumer (`tpu9
    # postmortem`, dashboards) calls .get on the dicts and iterates the
    # lists as dicts — a shape-hostile record must coerce here, at the
    # gateway's single re-clamp, not crash each consumer separately
    for key in ("stats", "scheduler", "kv_pool", "hbm"):
        if not isinstance(rec.get(key), dict):
            rec[key] = {}
    for key in ("flight", "spans"):
        items = rec.get(key)
        rec[key] = [it for it in (items if isinstance(items, list) else [])
                    if isinstance(it, dict)]
    rec["flight"] = rec["flight"][-FLIGHT_TAIL:]
    rec["spans"] = rec["spans"][-SPAN_TAIL:]

    def size() -> int:
        try:
            return len(json.dumps(rec))
        except (TypeError, ValueError):
            # unserializable leaf somewhere: keep only the header
            for key in ("flight", "spans", "stats", "scheduler",
                        "kv_pool", "hbm"):
                rec[key] = [] if key in ("flight", "spans") else {}
            return len(json.dumps(rec, default=str))

    while size() > max_bytes and rec["flight"]:
        rec["flight"] = rec["flight"][len(rec["flight"]) // 2 + 1:]
    while size() > max_bytes and rec["spans"]:
        rec["spans"] = rec["spans"][len(rec["spans"]) // 2 + 1:]
    if size() > max_bytes:
        for key in ("stats", "scheduler", "kv_pool", "hbm"):
            rec[key] = {}
    if size() > max_bytes:
        # pathological header-adjacent payload: truncated header only
        rec = {k: rec[k] for k in _HEADER_KEYS if k in rec}
        rec["flight"], rec["spans"] = [], []
    return rec


async def store_postmortem(store, container_id: str, rec: dict) -> None:
    """Persist one record under the replica's black-box key: an ATOMIC
    list append (rpush) + cap (ltrim) + TTL refresh — the gateway's
    heartbeat-shipped records and the worker's exit records land on the
    same key from different processes, and a get→append→set
    read-modify-write here would let one writer silently erase the
    other's evidence (exactly the engine-crash + process-exit pair)."""
    key = POSTMORTEM_KEY.format(cid=container_id)
    await store.rpush(key, json.dumps(rec))
    await store.ltrim(key, -MAX_POSTMORTEM_RECORDS, -1)
    await store.expire(key, POSTMORTEM_TTL_S)


async def load_postmortems(store, key: str) -> list:
    """A replica's stored records, oldest first; unparseable elements
    are skipped, never fatal (the read side of :func:`store_postmortem`,
    kept here so the gateway and tests agree on the contract)."""
    out = []
    for raw in await store.lrange(key):
        try:
            rec = json.loads(raw)
        except (ValueError, TypeError):
            continue
        if isinstance(rec, dict):
            out.append(rec)
    return out
