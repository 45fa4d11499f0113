#!/usr/bin/env python3
"""Builder's tool: what a traced run's trace says about the host — chip 0's
idle time by phase and by group, the clock check between the host and the
device planes, the decode programs' device time by scope (written beside the
trace by the run's own readers) and the size of the trace file.

    python3 benchmark/tools/phases.py [run_dir ...]     (default: the newest)
"""

from __future__ import annotations

import glob
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import host_phases, trace  # noqa: E402


def describe(run_dir: str) -> dict:
    path = trace.find_xplane(os.path.join(run_dir, "trace"))
    if not path:
        return {"run": run_dir, "error": "no trace"}
    out = {"run": os.path.basename(run_dir), "xplane_bytes": os.path.getsize(path)}
    data = host_phases.load(path)
    out["phase_events"] = len(data["phases"] or [])
    red = host_phases.reduce(data)
    if red:
        span = red["span_ns"]
        out["span_s"] = span / 1e9
        out["idle_share_pct"] = 100.0 * red["idle_ns"] / span
        out["idle_by_group_pct"] = host_phases.idle_by_group(red)
        out["idle_by_phase_pct"] = {
            k: 100.0 * v / span for k, v in sorted(
                red["by_phase"].items(), key=lambda kv: -kv[1])}
        host: dict = {}
        for a, b, name in host_phases.innermost(data["phases"]):
            host[name] = host.get(name, 0.0) + (b - a)
        out["host_self_ms_by_phase"] = {
            k: v / 1e6 for k, v in sorted(host.items(), key=lambda kv: -kv[1])}
    out["clock"] = host_phases.clock_margins(data)
    scopes = os.path.join(os.path.dirname(path), "decode_scope_seconds.json")
    if os.path.exists(scopes):
        with open(scopes) as f:
            seconds = json.load(f)
        total = sum(seconds.values()) or 1.0
        out["decode_scope_pct"] = {
            k: 100.0 * v / total for k, v in sorted(
                seconds.items(), key=lambda kv: -kv[1])}
        out["decode_device_s"] = total
    return out


def main() -> int:
    dirs = sys.argv[1:] or sorted(
        glob.glob(os.path.join(ROOT, "benchmark", "out", "*.trace1")),
        key=os.path.getmtime)[-1:]
    for run_dir in dirs:
        print(json.dumps(describe(run_dir)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
