"""From a profiler trace (``.xplane.pb``) to numbers: the one reduction every
PR's device metrics go through. Read with ``jax.profiler.ProfileData``,
nothing else.

A TPU trace has one plane per chip (``/device:TPU:<n>``). On it, the line
``XLA Modules`` holds one event per program run (``jit_decode(<id>)``) and
``XLA Ops`` one event per operation run, named by its whole HLO text
(``%fusion.14 = bf16[...] fusion(...)``); the operations of a loop's body are
events of their own inside the loop's event. Asynchronous copies are on a line
of their own and are not counted as busy. Busy time is the union of the op
intervals; the traced window runs from the first op's start to the last op's
end over all chips.
"""

from __future__ import annotations

import glob
import os
import re
import statistics

OPS_LINE, MODULES_LINE, ASYNC_LINE = "XLA Ops", "XLA Modules", "Async XLA Ops"
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "all_reduce", "all_gather",
               "reduce_scatter", "all_to_all", "collective_permute")


def find_xplane(trace_dir: str):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_planes(path: str) -> list:
    """``[{"name", "lines": {line: [(name, start_ns, dur_ns), ...]}}]`` for
    the device planes of a trace file."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not is_device_plane(plane.name):
            continue
        lines = {}
        for line in plane.lines:
            if line.name in (OPS_LINE, MODULES_LINE, ASYNC_LINE):
                lines[line.name] = [
                    (ev.name, float(ev.start_ns), float(ev.duration_ns))
                    for ev in line.events]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def is_device_plane(name: str) -> bool:
    return bool(re.match(r"^/device:(TPU|GPU):\d+$", name))


def op_key(name: str) -> str:
    """One name for every run of the same operation: the HLO instruction
    name the trace gives, without the ``%`` and what follows the name."""
    return name.lstrip("%").split(" ", 1)[0].split("(", 1)[0]


def op_group(name: str) -> str:
    """One name for the same operation in every layer: the instruction's
    name without its number, and the shape of its result:
    ``%fusion.14 = bf16[8,32,4096]{2,1,0} fusion(...)`` -> ``fusion:bf16[8,32,4096]``."""
    head, _, rest = name.partition(" = ")
    base = re.sub(r"[.\d]+$", "", head.lstrip("%").split("(", 1)[0])
    shape = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return f"{base}:{shape}" if shape else base


def program_key(name: str) -> str:
    """``jit_decode(123456)`` -> ``jit_decode``."""
    return name.split("(", 1)[0]


def union_ns(intervals: list) -> float:
    busy, end = 0.0, -1.0
    for a, d in sorted(intervals):
        b = a + d
        if b <= end:
            continue
        busy += b - max(a, end)
        end = b
    return busy


def reduce_planes(planes: list, step_marker: str = "",
                  calls_per_step: int = 0) -> dict:
    """Everything the per-layer readers and the result line take from a
    trace. Times in seconds; sums over chips are divided by the number of
    chips, so a four-chip cell reads per chip. Decode steps are counted by
    the calls of the kernel ``step_marker``, ``calls_per_step`` to a step
    (the configuration's family says both); without them, no steps."""
    planes = [p for p in planes if p["lines"].get(OPS_LINE)]
    if not planes:
        return {}
    n = len(planes)
    t_first = min(a for p in planes for _, a, _ in p["lines"][OPS_LINE])
    t_last = max(a + d for p in planes for _, a, d in p["lines"][OPS_LINE])
    busy = sum(union_ns([(a, d) for _, a, d in p["lines"][OPS_LINE]])
               for p in planes) / n

    by_op: dict = {}
    collective = 0.0
    import bisect
    for p in planes:
        runs = sorted((a, a + d, program_key(nm))
                      for nm, a, d in p["lines"].get(MODULES_LINE, []))
        starts = [r[0] for r in runs]
        for name, a, d in p["lines"][OPS_LINE]:
            key = op_key(name)
            if any(c in key for c in COLLECTIVES):
                collective += d / n
            if key.startswith(CONTAINERS):
                continue        # its body's operations are events themselves
            i = bisect.bisect_right(starts, a) - 1
            prog = runs[i][2] if i >= 0 and a < runs[i][1] else "?"
            group = f"{prog}/{op_group(name)}"
            by_op[group] = by_op.get(group, 0.0) + d / n
        # a collective the compiler made asynchronous is on the async line,
        # from its start to its done: the time it was in flight
        for name, _, d in p["lines"].get(ASYNC_LINE, []):
            if any(c in op_key(name) for c in COLLECTIVES):
                collective += d / n

    # programs, their steps, and the gaps between them, on the first chip
    # (every chip of a mesh runs the same programs in step)
    first = planes[0]
    mods = sorted((a, d, program_key(nm))
                  for nm, a, d in first["lines"].get(MODULES_LINE, []))
    # by the operation's OWN name: the full text of a consumer names it too
    marks = sorted(a for nm, a, _ in first["lines"][OPS_LINE]
                   if step_marker and op_key(nm).startswith(step_marker))
    programs: dict = {}
    decode_step_ns = []
    for a, d, key in mods:
        rec = programs.setdefault(key, {"runs": 0, "seconds": 0.0})
        rec["runs"] += 1
        rec["seconds"] += d / 1e9
        if key.endswith("decode") and calls_per_step:
            calls = bisect.bisect_left(marks, a + d) \
                - bisect.bisect_left(marks, a)
            steps = round(calls / calls_per_step)
            if steps >= 1:
                decode_step_ns.append(d / steps)
                rec["steps"] = rec.get("steps", 0) + steps
    gaps: dict = {}
    for (a0, d0, k0), (a1, _, k1) in zip(mods, mods[1:]):
        gap = a1 - (a0 + d0)
        if gap > 0:
            key = f"{k0}->{k1}"
            gaps[key] = gaps.get(key, 0.0) + gap / 1e9

    def top(table: dict, scale: float = 1.0) -> list:
        return [[k, v * scale] for k, v in
                sorted(table.items(), key=lambda kv: -kv[1])[:10]]

    return {"chips": n, "window_s": (t_last - t_first) / 1e9,
            "busy_s": busy / 1e9, "collective_s": collective / 1e9,
            "programs": programs,
            "decode_step_ms": (statistics.median(decode_step_ns) / 1e6
                               if decode_step_ns else None),
            "op_seconds": {k: v / 1e9 for k, v in by_op.items()},
            "device_ops": top(by_op, 1e-9), "idle_gaps": top(gaps)}


def reduce_dir(trace_dir: str, step_marker: str = "",
               calls_per_step: int = 0) -> dict:
    path = find_xplane(trace_dir)
    if not path:
        return {}
    out = reduce_planes(read_planes(path), step_marker, calls_per_step)
    if out:
        out["file"] = path
    return out


def describe(path: str, limit: int = 12) -> dict:
    """The shape of a trace file, for a builder who has not seen one: every
    plane, its lines, and the first few event names of each."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            events = list(line.events)
            names: dict = {}
            for ev in events:
                names[ev.name] = names.get(ev.name, 0) + 1
            stats = {}
            if events:
                try:
                    stats = {str(k): str(v)[:80] for k, v in events[0].stats}
                except Exception:   # noqa: BLE001 — description only
                    pass
            lines[line.name] = {
                "events": len(events),
                "names": sorted(names.items(), key=lambda kv: -kv[1])[:limit],
                "first_stats": stats}
        out[plane.name] = lines
    return out
