"""Fleet decision ledger: "why" evidence for control-plane choices
(ISSUE 19).

PR 8's traces show *what happened* to a request; this module records
*why*. Five planes make consequential choices — admission (shed vs
deadline vs budget), placement (affinity / JSQ / disagg bias / health
ejection / scale-out fence), failover (retry classification, block-ship
vs re-prefill resume), migration (drain export / adopt), and the
autoscaler (reactive vs predictive verdicts) — and each leaves one
structured record here at the moment it decides:

    {plane, decision, chosen, rejected: [{alternative, reason}],
     signals: {...flat scalars...}, request_id, stub_id, workspace_id,
     ts, mono, seq}

``request_id`` IS the trace id (the ``X-Tpu9-Trace`` id PR 8 already
propagates), so ``tpu9 why <request-id>`` can interleave the decision
chain with the request's span tree without a second correlation scheme.

Memory is bounded the same three ways as ``timeline.py``:

- one global ``deque(maxlen=capacity)`` ring — old records fall off;
- the per-request index holds at most ``max_requests`` entries of at
  most ``per_request`` records each — a new request past the cap evicts
  the longest-idle entry first;
- index entries idle longer than ``idle_ttl_s`` are pruned by the
  sampler tick, so finished requests' chains don't outlive retention.

Records carry BOTH clocks (OBS001): ``ts`` is a wall anchor for display
and ``since`` filtering; ``mono`` + the monotonic ``seq`` counter order
the chain and drive the heartbeat ship cursor (``export_new`` mirrors
the tracer's retry-don't-drop watermark — runners ship their ledger on
the pressure beat and only advance once the gateway accepted it).
"""

from __future__ import annotations

import time
from collections import deque
from typing import Iterable, Optional

from .metrics import metrics

# the plane inventory — one slug per decision site family; wirecheck's
# WIR002 assertion for tpu9_decision_records_total enumerates these
PLANES = ("admission", "placement", "failover", "migration", "autoscaler",
          "kv_tier")


def rej(alternative: str, reason: str) -> dict:
    """One rejected-alternative entry. A helper, not a class: records
    are plain dicts end to end (they ride heartbeats and HTTP as JSON)."""
    return {"alternative": alternative, "reason": reason}


class DecisionLedger:
    def __init__(self, capacity: int = 2048, max_requests: int = 1024,
                 per_request: int = 32, idle_ttl_s: float = 900.0):
        self.capacity = max(int(capacity), 1)
        self.max_requests = max(int(max_requests), 1)
        self.per_request = max(int(per_request), 1)
        self.idle_ttl_s = float(idle_ttl_s)
        self._ring: deque = deque(maxlen=self.capacity)
        self._index: dict[str, deque] = {}
        self._touched: dict[str, float] = {}   # request_id -> last mono
        self._seq = 0

    def configure(self, capacity: Optional[int] = None,
                  max_requests: Optional[int] = None,
                  per_request: Optional[int] = None,
                  idle_ttl_s: Optional[float] = None) -> None:
        """Re-bound the module singleton from config at process boot.
        Existing records are kept (re-ringed under the new caps) — boot
        order must not silently erase early bring-up decisions."""
        if capacity is not None and int(capacity) != self.capacity:
            self.capacity = max(int(capacity), 1)
            self._ring = deque(self._ring, maxlen=self.capacity)
        if max_requests is not None:
            self.max_requests = max(int(max_requests), 1)
            while len(self._index) > self.max_requests:
                self._evict_one()
        if per_request is not None and int(per_request) != self.per_request:
            self.per_request = max(int(per_request), 1)
            self._index = {k: deque(v, maxlen=self.per_request)
                           for k, v in self._index.items()}
        if idle_ttl_s is not None:
            self.idle_ttl_s = float(idle_ttl_s)

    # -- recording -----------------------------------------------------------

    def record(self, plane: str, decision: str, *, request_id: str = "",
               chosen: str = "", rejected: Iterable[dict] = (),
               signals: Optional[dict] = None, stub_id: str = "",
               workspace_id: str = "", ts: Optional[float] = None,
               mono: Optional[float] = None) -> dict:
        """Append one decision record. Hot path (runs inside admission /
        dispatch): one dict build + two deque appends + a counter bump."""
        self._seq += 1
        m = mono if mono is not None else time.monotonic()
        rec = {"plane": plane, "decision": decision, "chosen": chosen,
               "rejected": list(rejected), "signals": signals or {},
               "request_id": request_id, "stub_id": stub_id,
               "workspace_id": workspace_id,
               "ts": ts if ts is not None else time.time(),
               "mono": m, "seq": self._seq}
        self._ring.append(rec)
        if request_id:
            ring = self._index.get(request_id)
            if ring is None:
                if len(self._index) >= self.max_requests:
                    self._evict_one()
                ring = self._index[request_id] = deque(
                    maxlen=self.per_request)
            ring.append(rec)
            self._touched[request_id] = m
        metrics.inc("tpu9_decision_records_total", labels={"plane": plane})
        return rec

    def _evict_one(self) -> None:
        """Drop the longest-idle request's index entry to make room for a
        new one (the global ring keeps its records until they age off)."""
        if not self._index:
            return
        victim = min(self._touched, key=self._touched.get)
        self._index.pop(victim, None)
        self._touched.pop(victim, None)

    def prune(self, idle_s: Optional[float] = None) -> int:
        """Drop index entries idle longer than ``idle_s`` (default the
        ledger's TTL): finished requests' chains must not pin memory
        forever under churn."""
        cutoff = time.monotonic() - (idle_s if idle_s is not None
                                     else self.idle_ttl_s)
        victims = [r for r, t in self._touched.items() if t < cutoff]
        for request_id in victims:
            self._index.pop(request_id, None)
            self._touched.pop(request_id, None)
        return len(victims)

    # -- reading -------------------------------------------------------------

    def record_count(self) -> int:
        return len(self._ring)

    def request_count(self) -> int:
        return len(self._index)

    def query(self, request_id: str = "", plane: str = "",
              since: float = 0.0, limit: int = 500) -> list[dict]:
        """Records in seq order. ``request_id`` reads the per-request
        index (O(chain), survives global-ring churn for hot requests);
        otherwise scans the global ring. ``since`` filters on the wall
        anchor (what HTTP callers have); ``limit`` keeps the newest N."""
        source = (self._index.get(request_id, ()) if request_id
                  else self._ring)
        out = [rec for rec in source
               if (not plane or rec["plane"] == plane)
               and rec["ts"] >= since]
        if limit > 0:
            out = out[-limit:]
        return out

    def export_new(self, since_seq: int = 0,
                   limit: int = 1000) -> tuple[list[dict], int]:
        """Records past the ``seq`` watermark, plus the new watermark —
        the ship-on-heartbeat cursor (the tracer's ``export_new``
        analogue, but seq-keyed: records are minted in seq order so the
        cursor is exact, not clock-dependent). Callers ship the batch
        and only advance once the receiver accepted it."""
        out: list[dict] = []
        hi = since_seq
        for rec in self._ring:
            if rec["seq"] > since_seq:
                out.append(rec)
                hi = rec["seq"]
                if len(out) >= limit:
                    break
        return out, hi


# process-wide ledger (mirrors the tracer / metrics registry pattern)
ledger = DecisionLedger()
