"""tpu9 wirecheck — static contract verification of the string-keyed wire
surfaces (ISSUE 18).

The fleet's control plane speaks in untyped string-keyed dicts:
``engine.stats()`` → pressure-heartbeat extras → fleetobs/watchdog/
goodput/scaleout consumers → ``/api/v1/metrics`` → ``tpu9 top``, plus
store key namespaces, ``TPU9_*`` env knobs, ``tpu9_*`` metric names and
``/rpc/*`` routes. Every producer/consumer pair on those surfaces is a
silent-drift hazard: a renamed field fails no test, it just reads 0.0
forever. wirecheck AST-extracts both sides of each surface and asserts
agreement against the declarative ``tpu9/analysis/contracts.toml``.

Same machinery as tpu9lint (PR 7): the shared Finding schema, inline
``# tpu9: noqa[RULE] reason`` suppressions, and a triaged baseline at
``scripts/wire_baseline.json``. Gate entry: ``scripts/wire_gate.py``;
CLI: ``python -m tpu9.analysis.wirecheck``.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field

from ..findings import (Finding, apply_suppressions, assign_occurrences,
                        parse_suppressions)
from . import checks as _checks
from .checks import ALL_CHECKS, WireContracts

DEFAULT_CONTRACTS = "tpu9/analysis/contracts.toml"
DEFAULT_BASELINE = "scripts/wire_baseline.json"
DEFAULT_ROOTS = ("tpu9", "scripts", "examples", "tests")

WIRE_RULES = {
    "WIR001": "stats/heartbeat field consumed-but-never-produced (and "
              "produced-but-never-consumed dead telemetry, warn tier)",
    "WIR002": "tpu9_* metric asserted-vs-emitted drift; per-replica "
              "gauges without remove_gauge coverage",
    "KEY001": "store key namespace undeclared / cross-plane write / "
              "non-atomic multi-writer op / missing TTL discipline",
    "ENV001": "TPU9_* env read outside tpu9/config.py or its declared "
              "reader; divergent inline defaults",
    "RPC001": "registered route without caller / call without handler",
}


@dataclass
class WirecheckResult:
    findings: list = field(default_factory=list)
    warnings: list = field(default_factory=list)
    suppressed: list = field(default_factory=list)
    parse_errors: list = field(default_factory=list)
    files_scanned: int = 0
    elapsed_s: float = 0.0

    def by_rule(self) -> dict:
        out: dict = {}
        for f in self.findings:
            out.setdefault(f.rule, []).append(f)
        return out


def _iter_files(repo_root: str, roots) -> list[str]:
    out = []
    for root in roots:
        full = os.path.join(repo_root, root)
        if os.path.isfile(full) and root.endswith(".py"):
            out.append(root)
            continue
        for dirpath, dirnames, filenames in os.walk(full):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", ".git")]
            for name in sorted(filenames):
                if name.endswith(".py"):
                    rel = os.path.relpath(os.path.join(dirpath, name),
                                          repo_root)
                    out.append(rel.replace(os.sep, "/"))
    return sorted(set(out))


def run_wirecheck(repo_root: str, roots=None, select=None,
                  contracts_path: str = None) -> WirecheckResult:
    """Full-repo wire scan. ``roots``/``select`` only *filter* the
    reported findings (surface agreement is inherently cross-file, so
    extraction always sees the whole repo); the gate preserves
    out-of-scope baseline entries the same way tpu9lint does."""
    t0 = time.monotonic()
    res = WirecheckResult()
    cpath = contracts_path or os.path.join(repo_root, DEFAULT_CONTRACTS)
    try:
        contracts = WireContracts.load(cpath)
    except (OSError, ValueError) as exc:
        res.parse_errors.append(f"{DEFAULT_CONTRACTS}: {exc}")
        res.elapsed_s = time.monotonic() - t0
        return res

    ctx = _checks.CheckContext(repo_root, contracts,
                               contracts_path=cpath)
    files = _iter_files(repo_root, DEFAULT_ROOTS)
    ctx.scan(files)
    res.files_scanned = len(files)
    res.parse_errors.extend(ctx.parse_errors)

    findings: list[Finding] = []
    warnings: list[Finding] = []
    for rule, check in ALL_CHECKS.items():
        if select and rule not in select:
            continue
        f, w = check(ctx)
        findings += f
        warnings += w

    # inline noqa suppressions, file by file (shared tpu9lint semantics);
    # contract-side findings anchor to contracts.toml, which has no
    # Python comments — those are baseline-only
    by_path: dict[str, list[Finding]] = {}
    for f in findings:
        by_path.setdefault(f.path, []).append(f)
    kept_all: list[Finding] = []
    for path, fs in by_path.items():
        if not path.endswith(".py"):
            kept_all.extend(fs)
            continue
        full = os.path.join(repo_root, path)
        try:
            with open(full, encoding="utf-8") as fh:
                sups = parse_suppressions(fh.read())
        except OSError:
            kept_all.extend(fs)
            continue
        kept, suppressed = apply_suppressions(fs, sups, path)
        # SUP001 minting is tpu9lint's job over the whole tree — only
        # keep wire-rule findings and their suppressions here
        kept_all.extend(f for f in kept if f.rule != "SUP001")
        res.suppressed.extend(suppressed)
    # warnings honour noqa too, without minting SUP001
    warn_by_path: dict[str, list[Finding]] = {}
    for w in warnings:
        warn_by_path.setdefault(w.path, []).append(w)
    kept_warns: list[Finding] = []
    for path, ws in warn_by_path.items():
        full = os.path.join(repo_root, path)
        try:
            with open(full, encoding="utf-8") as fh:
                sups = parse_suppressions(fh.read())
        except OSError:
            kept_warns.extend(ws)
            continue
        kept, suppressed = apply_suppressions(ws, sups, path)
        kept_warns.extend(w for w in kept if w.rule != "SUP001")
        res.suppressed.extend(suppressed)

    if roots:
        def _in(f):
            return any(f.path == r or f.path.startswith(r.rstrip("/") + "/")
                       for r in roots)
        kept_all = [f for f in kept_all if _in(f)]
        kept_warns = [w for w in kept_warns if _in(w)]

    res.findings = assign_occurrences(kept_all)
    res.warnings = assign_occurrences(kept_warns)
    res.findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    res.warnings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    res.elapsed_s = time.monotonic() - t0
    return res
