#!/usr/bin/env python3
"""Builder's tool: the shape of the newest trace under ``benchmark/out`` (or
of the given run directory), and a short recording of its device planes in
the form ``trace.reduce_planes`` takes, for the reduction's test.

    python3 benchmark/tools/describe_trace.py [run_dir] [--record seconds]
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace  # noqa: E402


def main() -> int:
    argv = sys.argv[1:]
    record_s = 1.0
    if "--record" in argv:
        i = argv.index("--record")
        record_s = float(argv[i + 1])
        del argv[i:i + 2]
    args = argv
    dirs = args or sorted(glob.glob(os.path.join(ROOT, "benchmark", "out", "*")),
                          key=os.path.getmtime)[-1:]
    path = trace.find_xplane(os.path.join(dirs[0], "trace"))
    if not path:
        print(f"no trace under {dirs}", file=sys.stderr)
        return 1
    out_dir = os.path.join(ROOT, "chiprun_out", "trace")
    os.makedirs(out_dir, exist_ok=True)
    print("trace file", path, os.path.getsize(path), "bytes")
    if os.path.getsize(path) < 48 << 20:
        import shutil
        shutil.copy(path, os.path.join(out_dir, "trace.xplane.pb"))
    desc = trace.describe(path)
    with open(os.path.join(out_dir, "describe.json"), "w") as f:
        json.dump(desc, f, indent=1)
    for plane, lines in desc.items():
        print(plane, {k: v["events"] for k, v in lines.items()})
    planes = trace.read_planes(path)
    # a short recording from the middle of the trace
    if planes and planes[0]["lines"].get(trace.OPS_LINE):
        ops = planes[0]["lines"][trace.OPS_LINE]
        lo = ops[len(ops) // 2][1]
        hi = lo + record_s * 1e9
        small = [{"name": p["name"],
                  "lines": {ln: [e for e in evs if lo <= e[1] < hi]
                            for ln, evs in p["lines"].items()}}
                 for p in planes]
        with gzip.open(os.path.join(out_dir, "recorded_planes.json.gz"),
                       "wt") as f:
            json.dump(small, f)
    print(json.dumps({k: v for k, v in trace.reduce_planes(planes).items()
                      if k != "op_seconds"}, indent=1)[:6000])
    return 0


if __name__ == "__main__":
    sys.exit(main())
