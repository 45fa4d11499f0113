#!/usr/bin/env python3
"""tpu9lint ratchet gate (ISSUE 7) — fails on any NEW finding.

The fast suite runs this (tests/test_lint.py): the triaged debt lives in
scripts/lint_baseline.json, inline ``# tpu9: noqa[RULE] reason``
suppressions cover reviewed sites, and anything else is a regression that
fails CI. Gate semantics (scoped stale filtering,
baseline updates that preserve out-of-scope triage, ``--strict-stale``)
are shared with wire_gate.py via tpu9/analysis/gatelib.py.

    python scripts/lint_gate.py                    # gate the repo
    python scripts/lint_gate.py --update-baseline --reason "why"
    python scripts/lint_gate.py --strict-stale     # also fail on stale debt

Exit codes: 0 clean, 1 new findings (or stale with --strict-stale),
2 parse/internal errors.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpu9.analysis import DEFAULT_BASELINE, run_analysis  # noqa: E402
from tpu9.analysis.gatelib import ratchet_main  # noqa: E402


def _run(repo_root, roots, select, args):
    kwargs = {}
    if roots:
        kwargs["roots"] = roots
    if args.boundaries:
        kwargs["boundaries_toml"] = args.boundaries
    return run_analysis(repo_root, select=select, **kwargs)


def main(argv=None) -> int:
    return ratchet_main(
        "lint_gate", _run, DEFAULT_BASELINE, argv=argv,
        doc=__doc__.splitlines()[0],
        add_args=lambda ap: ap.add_argument(
            "--boundaries", default=None,
            help="override boundaries.toml (tests)"))


if __name__ == "__main__":
    sys.exit(main())
