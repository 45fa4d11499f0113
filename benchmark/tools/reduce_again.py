#!/usr/bin/env python3
"""Builder's tool: the per-layer metrics of a saved traced run once more,
from what the run left in its directory (``context.json``: what the readers
read; ``trace/``: the profiler's file) and with the readers of THIS checkout.
For writing a reader against a chip run already made, and for showing that a
change to the harness reads the same numbers from the same run.

    python3 benchmark/tools/reduce_again.py [run_dir ...]   (default: newest)

Prints one JSON object a run: ``{"run", "cell", "metrics": {name: value}}``.
"""

from __future__ import annotations

import glob
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"     # reading a trace opens no chip
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest, metrics, trace  # noqa: E402


def reduce_again(run_dir: str) -> dict:
    with open(os.path.join(run_dir, "context.json")) as f:
        ctx = json.load(f)
    family = manifest.family(ctx.pop("config"))
    ctx["family"] = family
    ctx["trace"] = trace.reduce_dir(
        os.path.join(run_dir, "trace"), family.STEP_MARKER,
        family.marker_calls_per_step(ctx["model"]))
    out = {}
    for m in manifest.cell_metrics(manifest.load(), ctx["cell"], "per_layer"):
        value = manifest.layer_reader(m["name"]).read(ctx)
        if metrics.finite(value):
            out[m["name"]] = value
    return {"run": os.path.basename(run_dir), "cell": ctx["cell"],
            "metrics": out}


def main() -> int:
    dirs = sys.argv[1:] or sorted(
        glob.glob(os.path.join(ROOT, "benchmark", "out", "*.trace1")),
        key=os.path.getmtime)[-1:]
    for run_dir in dirs:
        print(json.dumps(reduce_again(run_dir)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
