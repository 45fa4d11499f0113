"""Stratified draws: every seed gets the SAME multiset of values — the
quantile midpoints of the stated distribution — and decides only their order.

PR 22's cells drew lengths and gaps at random, so each seed offered different
work and its medians moved with the draw. Here request count, token count and
class shares are identical in every run of a cell.
"""

from __future__ import annotations

import math

import numpy as np


def midpoints(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def quantiles(spec: dict, n: int) -> list:
    """The ``n`` quantile midpoints of ``spec``, ascending. Token counts
    (``loguniform``, ``uniform``, ``fixed``) come back as whole numbers,
    ``exponential`` gaps as seconds."""
    u = midpoints(n)
    dist = spec["dist"]
    if dist == "fixed":
        return [int(spec["value"])] * n
    if dist == "uniform":
        return [int(round(x)) for x in spec["lo"] + u * (spec["hi"] - spec["lo"])]
    if dist == "loguniform":
        lo, hi = math.log(spec["lo"]), math.log(spec["hi"])
        return [int(round(x)) for x in np.exp(lo + u * (hi - lo))]
    if dist == "exponential":
        return [float(x) for x in -np.log1p(-u) / spec["rate"]]
    raise ValueError(f"unknown distribution {dist!r}")


def stratify(rng: np.random.Generator, n: int, columns: int,
             blocks: int) -> list:
    """Seeded rows for a run cut into ``blocks`` stretches, from ``columns``
    ascending lists of ``n`` values each (prompt lengths, output lengths):
    returns one list per stretch of index tuples ``(i_0, .., i_columns-1)``.

    In every column, each run of ``blocks`` neighbouring quantiles puts one
    value into every stretch, at random; inside a stretch the columns are
    paired at random and the rows are in random order. So every stretch of
    every seed holds a stratified sample of every column, and no seed piles
    its long requests into one half of the window. ``blocks`` <= 1 is one
    stretch: plain permutations."""
    blocks = max(1, min(blocks, n))
    tail = n % blocks
    # the stretches that take the short last group, the same in every column
    tail_to = rng.permutation(blocks)[:tail]
    per_column = []
    for _ in range(columns):
        stretch = [[] for _ in range(blocks)]
        for start in range(0, n - tail, blocks):
            for off, b in enumerate(rng.permutation(blocks)):
                stretch[b].append(start + off)
        for off, b in enumerate(rng.permutation(tail_to)):
            stretch[b].append(n - tail + off)
        per_column.append([rng.permutation(s).tolist() for s in stretch])
    return [list(zip(*(col[b] for col in per_column)))
            for b in range(blocks)]


def run_order(rng: np.random.Generator, stretches: list) -> list:
    """Concatenate stretches, each in a seeded order."""
    out = []
    for rows in stretches:
        out.extend(rows[i] for i in rng.permutation(len(rows)))
    return out


def token_ids(rng: np.random.Generator, n: int, vocab: int) -> list:
    """``n`` token ids, uniform over the vocabulary less the first three
    (conventionally pad, bos, eos)."""
    return rng.integers(3, vocab, size=n).tolist()


def class_counts(classes: list, n: int) -> list:
    """Whole-number request counts per class that sum to ``n`` exactly:
    largest remainders, ties to the earlier class."""
    raw = [c["share"] * n for c in classes]
    counts = [int(x) for x in raw]
    by_rem = sorted(range(len(raw)), key=lambda i: (counts[i] - raw[i], i))
    for i in by_rem[:n - sum(counts)]:
        counts[i] += 1
    return counts
