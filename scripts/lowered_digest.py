#!/usr/bin/env python3
"""What a change did to programs it should not touch: sha256 of the lowered
text (no source locations) of every serving program of graphcheck's matrix
(llama3-8b, gemma-7b, mixtral-8x7b and ouro-2.6b at their widths, two layers
deep: paged on one, two and four chips, int8 weights and pool, the dense
cache, the verify programs), one line a program. The tree is the one on
``PYTHONPATH``, so two trees are compared by

    PYTHONPATH=<parent checkout> python3 scripts/lowered_digest.py > a
    PYTHONPATH=<this checkout>   python3 scripts/lowered_digest.py > b
    diff a b        # no output: these programs lower as before
"""

from __future__ import annotations

import hashlib

from tpu9.utils import force_cpu

force_cpu(host_devices=8)     # before anything imports jax

from tpu9.analysis.graphcheck.matrix import MATRIX  # noqa: E402
from tpu9.analysis.graphcheck.passes import build_cell  # noqa: E402


def main() -> None:
    for cell in MATRIX:
        _, _, _, factory, params, st, buckets, spec_lens = build_cell(cell)
        for key, fn, args in factory.lowering_jobs(
                params, st["kv_cache"], st["pool"], st["scratch"], st["mb"],
                buckets, spec_lens, st["rng"]):
            text = fn.lower(*args).as_text()
            print(cell.name, key, hashlib.sha256(text.encode()).hexdigest())


if __name__ == "__main__":
    main()
