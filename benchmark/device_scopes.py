"""Decode-program device time by part of the model.

The program runs its forward pass under ``jax.named_scope`` names; which of
them each decode share sums is the configuration's family's to say
(``SCOPE_GROUPS`` in ``families/<f>.py``). A TPU trace names a device
operation by its HLO instruction and carries no scope, so the engine reports
on ``/health``, for every program it compiled ahead, which instructions
belong to which scope (``device_scopes``: ``{program: {scope: [instruction
names]}}``, read from the executables' own ``op_name`` metadata). Here the
operations inside each run of the decode program are summed by scope.

Two decode programs (one per window length) share the name ``jit_decode``
and number their instructions apart: each program id of the trace takes the
reported map that knows most of its operations. Container operations
(``while``, ``call``) are left out, as in ``trace.reduce_planes``: their
bodies' operations are events of their own.
"""

from __future__ import annotations

import bisect
import json
import os

from benchmark import host_phases
from benchmark.trace import CONTAINERS, op_key, program_key

OTHER = "other"


def by_scope(ops: list, modules: list, maps: dict,
             program: str = "decode") -> dict:
    """``{scope: seconds}`` over the operations inside the runs of the
    programs called ``jit_<program>``; ``other`` is what no scope of the
    chosen map names. Empty without such runs or without a map."""
    candidates = {k: {i: scope for scope, names in m.items() for i in names}
                  for k, m in (maps or {}).items() if k.startswith(program)}
    runs = sorted((a, a + d, name) for name, a, d in modules
                  if program_key(name) == "jit_" + program)
    if not candidates or not runs:
        return {}
    starts = [r[0] for r in runs]
    per_id: dict = {}           # program id -> {instruction: seconds}
    for name, a, d in ops:
        key = op_key(name)
        if key.startswith(CONTAINERS):
            continue
        i = bisect.bisect_right(starts, a) - 1
        if i < 0 or a >= runs[i][1]:
            continue
        table = per_id.setdefault(runs[i][2], {})
        table[key] = table.get(key, 0.0) + d / 1e9
    out: dict = {}
    for table in per_id.values():
        chosen = max(candidates.values(),
                     key=lambda m: sum(1 for k in table if k in m))
        for key, seconds in table.items():
            scope = chosen.get(key, OTHER)
            out[scope] = out.get(scope, 0.0) + seconds
    return out


_seconds: dict = {}     # trace file -> ``by_scope`` of it


def decode_seconds(ctx: dict) -> dict:
    """``by_scope`` of a run's trace on chip 0, with the engine's maps;
    three readers, one sum."""
    path = (ctx.get("trace") or {}).get("file")
    maps = (ctx.get("health_ready") or {}).get("device_scopes")
    if not path or not maps:
        return {}
    if path in _seconds:
        return _seconds[path]
    data = host_phases.load(path)
    seconds = _seconds[path] = by_scope(data["ops"], data["modules"], maps)
    if os.path.isfile(path):    # beside it, for ``tools/phases.py``
        try:
            with open(os.path.join(os.path.dirname(path),
                                   "decode_scope_seconds.json"), "w") as f:
                json.dump(seconds, f)
        except OSError:
            pass
    return seconds


def share(ctx: dict, group: str):
    """One reader's number: the share of the decode programs' device time
    spent under the scopes of ``group`` (``kv_pool``, ``attention``, ``ffn``:
    the ``SCOPE_GROUPS`` of the configuration's family), in %."""
    seconds = decode_seconds(ctx)
    total = sum(seconds.values())
    if not total:
        return None
    scopes = ctx["family"].SCOPE_GROUPS[group]
    return 100.0 * sum(seconds.get(s, 0.0) for s in scopes) / total
