"""Whose idle time it is: the device's idle intervals charged to the serve
loop's host phases.

The program emits each phase of its serve loop as a
``jax.profiler.TraceAnnotation`` (``tpu9.observability.trace.phase``), so a
profiler trace holds them on the ``/host:CPU`` plane, on the line of the
thread that ran them and on the clock of the device planes. Here:

- the phase line is the host line that holds ``engine.window.dispatch``
  events; its ``engine.*`` / ``runner.*`` events nest by time, and the
  innermost one owns an instant;
- chip 0's idle intervals are the complement of the union of its ``XLA Ops``
  intervals between its first operation's start and its last one's end;
- every idle nanosecond goes to the innermost phase that covers it, else to
  ``unnamed``.

A trace is read once per file (``load``), for this module's readers and for
``device_scopes``: the four-chip file is large. A program without the phases
(an older commit) gives ``None`` everywhere: the metric is left out.
"""

from __future__ import annotations

import bisect
import re
import statistics

from benchmark.trace import MODULES_LINE, OPS_LINE, program_key

MARKER = "engine.window.dispatch"
PHASE = re.compile(r"^(engine|runner)\.")
UNNAMED = "unnamed"
# No correction between the planes' clocks: the clock check
# (``clock_margins``) read the host and the device planes within a
# millisecond of each other on the chip (PERF.md, PR 24).

# which phases each idle share is read under. A phase no group names (one a
# later PR adds) counts as unnamed until a group takes it.
GROUPS = {
    "admit": ("engine.admit", "engine.admit.lookup", "engine.admit.plan",
              "engine.admit.dispatch", "engine.admit.finish",
              "engine.deliver_first"),
    "window": ("engine.window.dispatch", "engine.window.fanout",
               "engine.kvtier_tick"),
    "eventloop": ("engine.yield", "runner.heartbeat"),
    # the host itself waits: for a window's tokens or the first tokens of a
    # batch of admissions (result transfer; a window too short to hide the
    # fan-out), or for work
    "blocked": ("engine.window.sync", "engine.first_sync", "engine.park"),
}

_loaded: dict = {}      # trace file -> what ``pick`` took from it
_reduced: dict = {}     # trace file -> ``reduce`` of that


def load(path: str) -> dict:
    """``{"phases": [(name, start_ns, dur_ns)] or None, "ops": [...],
    "modules": [...]}``: the phase line of the host plane and the first
    chip's operations and program runs."""
    if path not in _loaded:
        from jax.profiler import ProfileData
        _loaded[path] = pick(ProfileData.from_file(path).planes)
    return _loaded[path]


def pick(planes) -> dict:
    """The same, from planes in ``ProfileData``'s form (``name``, ``lines``
    of ``name`` and ``events`` of ``name``, ``start_ns``, ``duration_ns``)."""
    planes = list(planes)       # ``ProfileData.planes`` can be read once
    out = {"phases": None, "ops": [], "modules": []}
    chips = sorted((int(m.group(1)), p) for p in planes for m in
                   [re.match(r"^/device:(?:TPU|GPU):(\d+)$", p.name)] if m)
    if chips:
        for line in chips[0][1].lines:
            if line.name in (OPS_LINE, MODULES_LINE):
                key = "ops" if line.name == OPS_LINE else "modules"
                out[key] = [(ev.name, float(ev.start_ns),
                             float(ev.duration_ns)) for ev in line.events]
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                      for ev in line.events if PHASE.match(ev.name)]
            if any(name == MARKER for name, _, _ in events):
                out["phases"] = events
                return out
    return out


def innermost(phases: list) -> list:
    """Phase events that nest by time -> ``[(start, end, name)]`` segments,
    ordered and disjoint, each owned by the innermost phase open in it."""
    segments: list = []
    stack: list = []            # (end, name) of the open phases

    def emit(a, b):
        if stack and b > a:
            segments.append((a, b, stack[-1][1]))

    cursor = 0.0
    for name, start, dur in sorted(phases, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][0] <= start:
            emit(cursor, stack[-1][0])
            cursor = max(cursor, stack.pop()[0])
        emit(cursor, start)
        cursor = max(cursor, start) if stack else start
        stack.append((end, name))
    while stack:
        emit(cursor, stack[-1][0])
        cursor = max(cursor, stack.pop()[0])
    return segments


def idle_intervals(ops: list) -> tuple:
    """``([(start, end)] of no operation running, first start, last end)``."""
    spans = sorted((a, a + d) for _, a, d in ops)
    if not spans:
        return [], 0.0, 0.0
    idle, end = [], spans[0][0]
    for a, b in spans:
        if a > end:
            idle.append((end, a))
        end = max(end, b)
    return idle, spans[0][0], end


def charge(idle: list, segments: list) -> dict:
    """Idle nanoseconds by owning phase; what no segment covers is
    ``unnamed``."""
    out: dict = {}
    starts = [s[0] for s in segments]
    for a, b in idle:
        covered = 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(segments) and segments[i][0] < b:
            s0, s1, name = segments[i]
            part = min(b, s1) - max(a, s0)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                covered += part
            i += 1
        out[UNNAMED] = out.get(UNNAMED, 0.0) + (b - a) - covered
    return out


def reduce(data: dict):
    """``{"span_ns", "idle_ns", "by_phase": {phase: idle ns}}`` of chip 0,
    or None without a phase line or without operations."""
    if not data["phases"] or not data["ops"]:
        return None
    idle, first, last = idle_intervals(data["ops"])
    by_phase = charge(idle, innermost(data["phases"]))
    return {"span_ns": last - first, "idle_ns": sum(b - a for a, b in idle),
            "by_phase": by_phase}


def idle_by_group(red: dict) -> dict:
    """Idle share of the traced span, in %, by group of ``GROUPS`` and
    ``unnamed``: the five add up to the chip's idle share."""
    span = red["span_ns"]
    out = {g: 100.0 * sum(red["by_phase"].get(p, 0.0) for p in names) / span
           for g, names in GROUPS.items()}
    out[UNNAMED] = 100.0 * red["idle_ns"] / span - sum(out.values())
    return out


def idle_share(ctx: dict, group: str):
    """One reader's number: the share of the traced span in which chip 0
    was idle under the phases of ``group``, in %."""
    path = (ctx.get("trace") or {}).get("file")
    if not path:
        return None
    if path not in _reduced:        # five readers, one reduction
        _reduced[path] = reduce(load(path))
    red = _reduced[path]
    return idle_by_group(red)[group] if red else None


def clock_margins(data: dict, program: str = "jit_decode"):
    """The clock check: for each run of ``program`` on chip 0, by how much
    the last ``engine.window.dispatch`` to start before it started before
    it, and by how much the first ``engine.window.sync`` to end after it
    ended after it. Medians, in ms; both are positive when the host and the
    device planes share a clock."""
    if not data["phases"] or not data["modules"]:
        return None
    dispatch = sorted(a for n, a, _ in data["phases"] if n == MARKER)
    sync = sorted(a + d for n, a, d in data["phases"]
                  if n == "engine.window.sync")
    lead, lag = [], []
    for name, a, d in data["modules"]:
        if program_key(name) != program:
            continue
        i = bisect.bisect_right(dispatch, a) - 1
        if i >= 0:
            lead.append(a - dispatch[i])
        j = bisect.bisect_left(sync, a + d)
        if j < len(sync):
            lag.append(sync[j] - (a + d))
    if not lead or not lag:
        return None
    return {"runs": len(lead),
            "dispatch_leads_module_ms": statistics.median(lead) / 1e6,
            "dispatch_leads_module_min_ms": min(lead) / 1e6,
            "sync_ends_after_module_ms": statistics.median(lag) / 1e6,
            "sync_ends_after_module_min_ms": min(lag) / 1e6}
