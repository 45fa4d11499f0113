"""Device time, and trips, of the operations under named scopes, in every
program of a trace that runs them.

``device_scopes.py`` sums the decode programs' time by scope for the three
decode shares. An operation that runs inside a device LOOP of a data-
dependent number of trips (the summarise of a closing window, which decode,
chunk and group programs all run) needs two things more: every program that
holds it, and how many trips ran, because what the trips NEED in bytes is
counted a trip. The program puts such a loop's BODY, and nothing else, under
its scope, so every instruction under the scope runs once a trip, and the
most runs any one of them has in a program is that program's trips.

As in ``device_scopes.py``, the engine's ``/health`` says which HLO
instructions belong to which scope (``device_scopes``: ``{program: {scope:
[instruction names]}}``), and each program id of the trace takes the
reported map that knows most of its operations.
"""

from __future__ import annotations

import bisect

from benchmark import host_phases
from benchmark.trace import CONTAINERS, op_key, program_key

# a traced program's name -> the prefix of its maps' names on ``/health``
# (``GraphFactory.precompile`` names a map after the program's cache key)
PROGRAM_MAPS = {"jit_decode": "decode_", "jit_chunk": "chunk_",
                "jit_group": "chunkgroup_"}


def under(ops: list, modules: list, maps: dict, scopes: tuple) -> dict:
    """``{"seconds", "trips"}`` of the operations under ``scopes`` inside
    the runs of the programs of ``PROGRAM_MAPS``; zeros where the programs
    ran and none of them ran a trip; empty where no map names the scopes
    (a program without them) or the trace holds no such program."""
    wanted = set(scopes)
    candidates = {
        jit: [{i for s, names in m.items() if s in wanted for i in names}
              for key, m in (maps or {}).items() if key.startswith(prefix)]
        for jit, prefix in PROGRAM_MAPS.items()}
    if not any(names for sets in candidates.values() for names in sets):
        return {}
    known = {jit: [{i for names in m.values() for i in names}
                   for key, m in (maps or {}).items()
                   if key.startswith(prefix)]
             for jit, prefix in PROGRAM_MAPS.items()}
    runs = sorted((a, a + d, name) for name, a, d in modules
                  if program_key(name) in PROGRAM_MAPS)
    if not runs:
        return {}
    starts = [r[0] for r in runs]
    per_id: dict = {}       # program id -> {instruction: [seconds, runs]}
    for name, a, d in ops:
        key = op_key(name)
        if key.startswith(CONTAINERS):
            continue
        i = bisect.bisect_right(starts, a) - 1
        if i < 0 or a >= runs[i][1]:
            continue
        cell = per_id.setdefault(runs[i][2], {}).setdefault(key, [0.0, 0])
        cell[0] += d / 1e9
        cell[1] += 1
    seconds, trips = 0.0, 0
    for program, table in per_id.items():
        jit = program_key(program)
        if not known[jit]:
            continue
        best = max(range(len(known[jit])),
                   key=lambda j: sum(1 for k in table if k in known[jit][j]))
        mine = [v for k, v in table.items() if k in candidates[jit][best]]
        seconds += sum(v[0] for v in mine)
        trips += max((v[1] for v in mine), default=0)
    return {"seconds": seconds, "trips": trips}


_read: dict = {}        # (trace file, scopes) -> ``under`` of it


def read(ctx: dict, scopes: tuple) -> dict:
    """``under`` of a run's trace on chip 0, with the engine's maps."""
    path = (ctx.get("trace") or {}).get("file")
    maps = (ctx.get("health_ready") or {}).get("device_scopes")
    if not path or not maps or not scopes:
        return {}
    key = (path, tuple(scopes))
    if key not in _read:
        data = host_phases.load(path)
        _read[key] = under(data["ops"], data["modules"], maps, scopes)
    return _read[key]
