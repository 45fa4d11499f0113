#!/usr/bin/env python3
"""graphcheck gate (ISSUE 11) — fails on ANY graph-invariant finding.

Tier-1 wiring next to lint_gate.py (tests/test_graphcheck.py runs it):
Pass A lowers the full preset × topology
matrix on a forced 8-device CPU mesh and verifies sharding / dtype /
donation / closed-signature invariants; Pass B gates the SHD/DTY AST
rules against the triaged lint baseline. Unlike the lint ratchet there
is NO baseline for Pass A findings — a graph invariant is either intact
or the gate is red.

    python scripts/graph_gate.py                 # full matrix
    python scripts/graph_gate.py --cell llama3-8b@2x1
    python scripts/graph_gate.py --budget-s 400  # enforce the runtime gate

When the forced CPU mesh is unavailable (caller pinned XLA_FLAGS without
the device-count forcing), the gate SKIPS LOUDLY with the re-run recipe
and exits 0 — mirroring the multichip test marker: a silent red would
block CI on an environment quirk, a silent green would claim coverage
that never ran.

Exit codes: 0 clean (or loud skip), 1 findings / budget exceeded,
2 internal errors.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpu9.utils import force_cpu  # noqa: E402

# must happen before anything imports jax (the graphcheck CLI does the
# same); harmless no-op when conftest already forced it
force_cpu(host_devices=8)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cell", action="append", default=None)
    ap.add_argument("--budget-s", type=float, default=400.0,
                    help="fail when the full matrix takes more than this "
                         "much CPU time of this process, all threads (0 "
                         "disables; default %(default)s — the tier-1 "
                         "contract). CPU time and not wall clock: the "
                         "matrix is 50-60 s of wall clock alone and about "
                         "160 s of CPU, and under a parallel test run its "
                         "wall clock says how busy the machine is, not what "
                         "the matrix costs")
    ap.add_argument("--no-compile", action="store_true")
    ap.add_argument("--repo-root", default=None)
    ap.add_argument("--strict-stale", action="store_true",
                    help="fail when lint-baseline entries for the graph "
                         "AST rules no longer fire (shared ratchet "
                         "semantics with lint_gate/wire_gate)")
    args = ap.parse_args(argv)

    from tpu9.analysis import load_baseline, run_analysis
    from tpu9.analysis.graphcheck import GRAPH_AST_RULES
    from tpu9.analysis.graphcheck import passes
    from tpu9.analysis.graphcheck.matrix import find_cells
    from tpu9.analysis.runner import (DEFAULT_BASELINE, find_repo_root,
                                      gate)

    guard = passes.device_guard()
    if guard is not None:
        print(f"graph_gate: SKIP — {guard}", file=sys.stderr)
        return 0

    t0 = time.perf_counter()
    try:
        cells = find_cells(args.cell)
    except KeyError as exc:
        # bad --cell name only — an internal error inside the matrix run
        # must keep its traceback, not masquerade as a CLI typo
        print(f"graph_gate: {exc}", file=sys.stderr)
        return 2
    report = passes.run_matrix(cells, compile_jobs=not args.no_compile)

    repo_root = args.repo_root or find_repo_root()
    result = run_analysis(repo_root, select=set(GRAPH_AST_RULES))
    baseline = load_baseline(os.path.join(repo_root, DEFAULT_BASELINE))
    lint_new, _known, lint_stale = gate(result, baseline)
    # this pass only ran the graph AST rules — staleness elsewhere in
    # the lint ledger is lint_gate's business, not ours
    lint_stale = [e for e in lint_stale
                  if e.get("rule") in set(GRAPH_AST_RULES)]

    findings = list(report["findings"]) + lint_new
    for f in findings:
        print(f"FAIL {f.format()}")
    elapsed = time.perf_counter() - t0
    matrix_s, cpu_s = report["elapsed_s"], report["cpu_s"]
    n_graphs = sum(s["jobs"] for s in report["cells"])
    print(f"graph_gate: {len(report['cells'])} cells / {n_graphs} graphs "
          f"in {matrix_s:.1f}s, {cpu_s:.1f}s of CPU (+ lint, total "
          f"{elapsed:.1f}s) — {len(findings)} findings")

    if findings:
        print("graph_gate: FAIL — graph invariants violated (Pass A "
              "findings have no baseline: fix the graph or the policy).",
              file=sys.stderr)
        return 1
    # the budget is the MATRIX contract — Pass B's repo-wide lint scan
    # scales with repo size, not with the matrix, and must not bill it
    if args.budget_s and not args.cell and cpu_s > args.budget_s:
        print(f"graph_gate: FAIL — full matrix took {cpu_s:.1f}s of CPU > "
              f"budget {args.budget_s:.0f}s (trim the matrix or move a "
              "cell to the slow tier)", file=sys.stderr)
        return 1
    if args.strict_stale and lint_stale:
        for e in lint_stale:
            print(f"stale baseline entry (prune or lint_gate "
                  f"--update-baseline): {e['rule']} {e['path']} "
                  f"[{e.get('symbol')}]")
        print("graph_gate: FAIL — stale baseline entries (--strict-stale)",
              file=sys.stderr)
        return 1
    print("graph_gate: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
