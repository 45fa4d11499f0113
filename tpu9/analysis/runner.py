"""tpu9lint driver: walk the tree, run every checker, apply suppressions
and the triaged baseline, and report.

Designed to be cheap enough for tier-1: one AST parse per file, every
per-file rule in a single visitor pass, and the two whole-program passes
(JAX001 hot path, BND001 boundaries) reuse the same parsed trees.
"""

from __future__ import annotations

import ast
import os
import time
from dataclasses import dataclass, field
from typing import Optional

from . import boundaries as bnd
from . import rules
from .findings import (Baseline, Finding, apply_suppressions,
                       assign_occurrences, load_baseline, parse_suppressions)

DEFAULT_ROOTS = ("tpu9", "scripts", "examples")
DEFAULT_BASELINE = os.path.join("scripts", "lint_baseline.json")
BOUNDARIES_TOML = os.path.join(os.path.dirname(__file__), "boundaries.toml")

ALL_RULES = {
    "ASY001": "asyncio.wait_for wrapping a cancellable .get()/.wait()",
    "ASY002": "fire-and-forget create_task/ensure_future (weak-ref'd task)",
    "ASY003": "BaseException/bare except in a coroutine without re-raise",
    "ASY004": "blocking call (sleep/subprocess/socket/file IO) in async def",
    "JAX001": "host-device sync reachable from the engine serve loop",
    "JAX002": "jit recompile hazard (inline jit call / jit built in a loop)",
    "OBS001": "wall-clock (time.time) arithmetic for a duration/deadline "
              "in serving/router/worker hot-path files",
    "OBS002": "unbounded metric-label cardinality (request/trace/prompt "
              "ids as metrics.inc/observe/set_gauge label values)",
    "TMO001": "network-facing await without a timeout/deadline in "
              "gateway/router/runner/worker/cache/statestore hot paths",
    "BND001": "import-boundary contract violation (boundaries.toml)",
    "SHD001": "jax.jit opened outside the GraphFactory in mesh-capable "
              "serving modules (no explicit out_shardings)",
    "SHD002": "donated buffer read after the donating jit call",
    "DTY001": "raw int8 KV symbol imported outside the declared carrier "
              "modules (boundaries.toml [graphcheck])",
    "SUP001": "noqa suppression without a mandatory reason",
}


def find_repo_root(start: Optional[str] = None) -> str:
    d = os.path.abspath(start or os.getcwd())
    while True:
        if os.path.exists(os.path.join(d, "pyproject.toml")):
            return d
        parent = os.path.dirname(d)
        if parent == d:
            # fall back to the package's grandparent (repo checkout layout)
            return os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
        d = parent


@dataclass
class AnalysisResult:
    findings: list[Finding] = field(default_factory=list)   # post-noqa
    suppressed: list[Finding] = field(default_factory=list)  # inline noqa'd
    parse_errors: list[str] = field(default_factory=list)
    files_scanned: int = 0
    elapsed_s: float = 0.0

    def by_rule(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for f in self.findings:
            out[f.rule] = out.get(f.rule, 0) + 1
        return out


def iter_py_files(repo_root: str, roots) -> list[str]:
    out = []
    for root in roots:
        abs_root = os.path.join(repo_root, root)
        if os.path.isfile(abs_root):
            if abs_root.endswith(".py"):
                out.append(root)
            continue
        for dirpath, dirnames, filenames in os.walk(abs_root):
            dirnames[:] = [d for d in dirnames
                           if d != "__pycache__" and not d.startswith(".")]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    rel = os.path.relpath(os.path.join(dirpath, fn),
                                          repo_root)
                    out.append(rel.replace(os.sep, "/"))
    return sorted(set(out))


def run_analysis(repo_root: Optional[str] = None,
                 roots=DEFAULT_ROOTS,
                 select: Optional[set[str]] = None,
                 boundaries_toml: Optional[str] = None) -> AnalysisResult:
    t0 = time.perf_counter()
    repo_root = repo_root or find_repo_root()
    result = AnalysisResult()

    trees: dict[str, ast.AST] = {}
    sources: dict[str, str] = {}
    for rel in iter_py_files(repo_root, roots):
        try:
            with open(os.path.join(repo_root, rel), encoding="utf-8") as f:
                src = f.read()
            trees[rel] = ast.parse(src, filename=rel)
            sources[rel] = src
        except (SyntaxError, UnicodeDecodeError, OSError) as exc:
            result.parse_errors.append(f"{rel}: {exc}")
    result.files_scanned = len(trees)

    cfg_path = boundaries_toml or BOUNDARIES_TOML
    cfg = (bnd.BoundaryConfig.load(cfg_path)
           if os.path.exists(cfg_path) else bnd.BoundaryConfig())

    from .graphcheck.astrules import GraphLintConfig, check_graph_file
    gcfg = GraphLintConfig.from_dict(cfg.graph)
    raw: list[Finding] = []
    for rel, tree in trees.items():
        raw.extend(rules.check_file(rel, tree))
        raw.extend(check_graph_file(rel, tree, gcfg))

    raw.extend(bnd.check_boundaries(trees, cfg))

    hot = {rel: tree for rel, tree in trees.items()
           if rel in set(cfg.jax_hotpath_files)}
    if hot and cfg.jax_roots:
        raw.extend(rules.check_jax_hotpath(hot, cfg.jax_roots))

    if select:
        raw = [f for f in raw if f.rule in select]

    # inline suppressions, then stable occurrence numbering.
    # (select is re-applied below: apply_suppressions can mint SUP001) Every scanned
    # file is parsed for noqa — not just files with findings — so a
    # reason-less (or dead) suppression in an otherwise-clean file still
    # raises SUP001 instead of rotting invisibly.
    by_path: dict[str, list[Finding]] = {}
    for f in raw:
        by_path.setdefault(f.path, []).append(f)
    for rel in sorted(sources):
        sups = parse_suppressions(sources[rel])
        if not sups and rel not in by_path:
            continue
        kept, suppressed = apply_suppressions(by_path.get(rel, []), sups,
                                              rel)
        result.findings.extend(kept)
        result.suppressed.extend(suppressed)
    if select:
        result.findings = [f for f in result.findings if f.rule in select]
    assign_occurrences(result.findings)
    result.findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    result.elapsed_s = time.perf_counter() - t0
    return result


def gate(result: AnalysisResult, baseline: Baseline
         ) -> tuple[list[Finding], list[Finding], list[dict]]:
    """Split post-noqa findings against the baseline: (new, known, stale)."""
    return baseline.split(result.findings)


def run_gate(repo_root: Optional[str] = None,
             roots=DEFAULT_ROOTS,
             baseline_path: Optional[str] = DEFAULT_BASELINE,
             boundaries_toml: Optional[str] = None):
    repo_root = repo_root or find_repo_root()
    result = run_analysis(repo_root, roots, boundaries_toml=boundaries_toml)
    bl_path = (os.path.join(repo_root, baseline_path)
               if baseline_path and not os.path.isabs(baseline_path)
               else baseline_path)
    baseline = load_baseline(bl_path)
    new, known, stale = gate(result, baseline)
    return result, new, known, stale
