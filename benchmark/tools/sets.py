#!/usr/bin/env python3
"""Builder's tool: several runs of one cell in one call, each with its own
seed, and the spread of every metric as the contract defines it (distance
between the first and third quartile of ``statistics.quantiles(values, n=4)``
as a share of the median).

    python3 benchmark/tools/sets.py --workload <cell> --seeds 11,12,13 \
        [--seconds 45] [--trace 0] [--tag name]

Each run's output goes to ``chiprun_out/<tag>/``; its last line is echoed.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spread(values: list) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", default=None)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--tag", default="")
    ap.add_argument("--extra", default="", help="further arguments of run.py")
    a = ap.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out", a.tag or a.workload)
    os.makedirs(out_dir, exist_ok=True)
    lines = []
    for seed in a.seeds.split(","):
        cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
               "--workload", a.workload, "--seed", seed, "--trace", a.trace]
        if a.seconds:
            cmd += ["--seconds", a.seconds]
        cmd += a.extra.split()
        t0 = time.time()
        proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        wall = time.time() - t0
        stem = os.path.join(out_dir, f"seed{seed}.trace{a.trace}")
        with open(stem + ".out", "w") as f:
            f.write(proc.stdout)
        with open(stem + ".err", "w") as f:
            f.write(proc.stderr)
        records = os.path.join(ROOT, "benchmark", "out",
                               f"{a.workload}.seed{seed}.trace{a.trace}",
                               "records.json")
        if os.path.exists(records):
            n = len([f for f in os.listdir(out_dir) if f.endswith(".records.json")])
            shutil.copy(records, f"{stem}.run{n}.records.json")
        last = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
        print(f"seed {seed}: exit {proc.returncode}, wall {wall:.1f} s: "
              f"{last[:1500]}", flush=True)
        if proc.returncode != 0:
            print(proc.stderr[-3000:], flush=True)
            continue
        try:
            lines.append(json.loads(last))
        except ValueError:
            pass
    names = sorted({k for ln in lines for k in ln.get("metrics", {})})
    table = {}
    for name in names:
        vals = [ln["metrics"][name]["value"] for ln in lines
                if name in ln["metrics"]]
        if len(vals) >= 2:
            table[name] = {"median": statistics.median(vals),
                           "spread": round(spread(vals), 5), "values": vals}
    print(json.dumps({"workload": a.workload, "runs": len(lines),
                      "spread": table}), flush=True)
    with open(os.path.join(out_dir, f"summary.trace{a.trace}.json"), "w") as f:
        json.dump({"lines": lines, "spread": table}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
