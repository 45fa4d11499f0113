"""Cold-start decomposition: the shared schema for restore/bring-up evidence.

ISSUE 13: the restore/weight-distribution plane emits one span tree per
replica bring-up (``restore.request`` ⊃ per-group ``restore.fetch`` ∥
``restore.device_put``, plus ``restore.load`` / ``restore.compile_ahead`` /
``restore.bind`` on the runner side) and one *readiness record* per replica
(plan→fetch→put→compile→ready wall intervals, bytes by cache tier, hedge
outcomes). Its consumers must agree on its shape:

- the gateway's ``GET /api/v1/coldstart`` (merges the worker-half record
  shipped on the heartbeat with the runner-half ``coldstart_*`` pressure
  extras),
- the chip benchmark's ``bringup_load_s`` / ``bringup_compile_s``, read
  off the runner's ``coldstart_*`` fields on ``/health``,
- the restore tests, which hold a phase's measured seconds against the
  traced span intervals (``decompose_spans`` + ``agreement``).

This module is that single source of truth: span names, the interval
helpers, and the trace→decomposition fold. It is a passive leaf like the
rest of ``tpu9.observability`` — plain dict math, no reverse imports.
"""

from __future__ import annotations

from typing import Optional

# span names, one per restore/bring-up phase (ARCHITECTURE.md span map)
SPAN_REQUEST = "restore.request"          # whole checkpoint restore
SPAN_FETCH = "restore.fetch"              # per-group chunk stream window
SPAN_DEVICE_PUT = "restore.device_put"    # per-group consume window
SPAN_LOAD = "restore.load"                # runner-side host param load
SPAN_COMPILE_AHEAD = "restore.compile_ahead"   # overlapped XLA compile
SPAN_BIND = "restore.bind"                # param binding into the engine
SPAN_WARMUP = "restore.warmup"            # pre-readiness graph warmup
SPAN_BRINGUP = "runner.bringup"           # runner-side bring-up root

# the phases a decomposition record reports, in bring-up order
PHASES = ("plan", "fetch", "device_put", "load", "compile_ahead", "bind",
          "warmup")


def interval_overlap_s(a: Optional[tuple], b: Optional[tuple]) -> float:
    """Overlap of two (start, end) intervals in seconds (0 when either is
    missing or they are disjoint)."""
    if not a or not b or a[0] is None or b[0] is None:
        return 0.0
    lo = max(a[0], b[0])
    hi = min(a[1], b[1])
    return max(hi - lo, 0.0)


def overlap_frac(fetch: Optional[tuple], put: Optional[tuple]) -> float:
    """Fetch∥consume pipeline efficiency: how much of the SHORTER phase ran
    under the other one. 1.0 = the cheaper phase was fully hidden (ideal
    double buffering); 0.0 = strictly serial."""
    if not fetch or not put or fetch[0] is None or put[0] is None:
        return 0.0
    shorter = min(fetch[1] - fetch[0], put[1] - put[0])
    if shorter <= 0:
        return 0.0
    return min(interval_overlap_s(fetch, put) / shorter, 1.0)


def decompose_spans(spans: list[dict]) -> dict:
    """Fold one trace's span dicts (``Span.to_dict`` shape) into per-phase
    interval sums — the traced side of the tests' agreement check. Spans of
    the same phase are summed; the request/bringup roots are reported as
    wall envelopes, not added into the phase sum."""
    out = {"fetch_s": 0.0, "device_put_s": 0.0, "load_s": 0.0,
           "compile_ahead_s": 0.0, "bind_s": 0.0, "warmup_s": 0.0,
           "request_s": 0.0, "bringup_s": 0.0, "groups": 0, "bytes": 0}
    name_key = {SPAN_FETCH: "fetch_s", SPAN_DEVICE_PUT: "device_put_s",
                SPAN_LOAD: "load_s", SPAN_COMPILE_AHEAD: "compile_ahead_s",
                SPAN_BIND: "bind_s", SPAN_WARMUP: "warmup_s"}
    for sp in spans:
        dur = float(sp.get("durationMs", 0.0)) / 1000.0
        name = sp.get("name", "")
        if name == SPAN_REQUEST:
            out["request_s"] += dur
        elif name == SPAN_BRINGUP:
            out["bringup_s"] += dur
        elif name in name_key:
            out[name_key[name]] += dur
            attrs = sp.get("attributes") or {}
            if name == SPAN_FETCH:
                out["groups"] += 1
                out["bytes"] += int(attrs.get("bytes", 0) or 0)
    return {k: round(v, 4) if isinstance(v, float) else v
            for k, v in out.items()}


def agreement(traced_s: float, measured_s: float) -> float:
    """Relative disagreement between a traced interval sum and a measured
    time for the same phase (0.0 = identical)."""
    denom = max(traced_s, measured_s)
    if denom <= 0:
        return 0.0
    return abs(traced_s - measured_s) / denom


def merge_record(worker_half: Optional[dict],
                 runner_extras: Optional[dict]) -> dict:
    """One replica's readiness record from its two halves: the worker's
    restore record (``coldstart:<container_id>`` store key) and the
    runner's flat ``coldstart_*`` heartbeat extras. Either half may be
    missing (plain endpoints have no runner heartbeat; a warm-pool replica
    on a fresh node may have no restore)."""
    out: dict = dict(worker_half or {})
    runner: dict = {}
    for key, value in (runner_extras or {}).items():
        if key.startswith("coldstart_"):
            runner[key[len("coldstart_"):]] = value
    if runner:
        out["runner"] = runner
    return out
