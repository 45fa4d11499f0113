"""The prefix key: what names a block-aligned token prefix everywhere a
deployment compares prefixes — the engine's prefix cache
(``serving.paged_kv.PrefixCache``) and its host tier, kvwire's ``prefix_key``,
the router's affinity table (``router.affinity.block_keys``) and the prefix
directory's 16-hex digests. One function makes it, here in the leaf both the
serving stack and the router may import, so the planes agree by construction.
Keys live in memory and on the wire between processes of one deployment;
nothing persists one."""

from __future__ import annotations

import array
import hashlib
import sys
from typing import Optional


def prefix_keys(tokens: list[int], block_s: int, *, strict: bool,
                max_blocks: Optional[int] = None) -> list[bytes]:
    """The key of every block-aligned prefix of ``tokens``, shortest first:
    ``keys[i]`` names ``tokens[:(i + 1) * block_s]``. THE one owner of the
    prefix key — the prefix cache's entries, the router's affinity table
    and prefix directory (``router.affinity.block_keys``) and kvwire's
    ``prefix_key`` are all made here, so they agree by construction.

    A key is the sha1 of its prefix's tokens, each laid out as a 64-bit
    two's-complement little-endian integer. The bytes of a prefix lead the
    bytes of every longer one, so ONE pass makes them all: the tokens are
    converted once, a running hash takes a block at a time and is copied at
    each boundary — O(n) for a whole walk, where hashing each prefix from
    scratch was O(n²) on a miss. A key therefore does not depend on
    ``block_s``, ``strict`` or ``max_blocks``: they only say which
    boundaries are returned.

    ``strict`` leaves at least one token past the longest prefix (an
    admission samples its first output from the suffix's logits);
    ``max_blocks`` caps the walk (and the work) at that many blocks."""
    nb = (len(tokens) - (1 if strict else 0)) // block_s
    if max_blocks is not None:
        nb = min(nb, max_blocks)
    if nb <= 0:
        return []
    head = tokens[:nb * block_s]
    try:
        raw = array.array("q", head)
    except (OverflowError, TypeError):
        # an id no vocabulary has (a hostile body at the router, a float):
        # the same 64 bits, the slow way — a key never raises
        raw = array.array("Q", [int(t) & 0xFFFFFFFFFFFFFFFF for t in head])
    if sys.byteorder == "big":
        raw.byteswap()
    view = memoryview(raw)
    h = hashlib.sha1()
    keys = []
    for i in range(nb):
        h.update(view[i * block_s:(i + 1) * block_s])
        keys.append(h.copy().digest())
    return keys
