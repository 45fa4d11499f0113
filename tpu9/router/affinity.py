"""KV-affinity replica selection: block-boundary prefix keys + JSQ fallback.

The engine's :class:`tpu9.serving.paged_kv.PrefixCache` caches KV for
FULL, block-aligned prompt prefixes, keyed by a hash of the token prefix:
the sha1 of the prefix's tokens as 64-bit little-endian integers, made for
every boundary of a prompt in one pass by
:func:`tpu9.utils.prefixkey.prefix_keys`, the key's one owner. A fleet
router that wants its placement to turn into engine-level cache hits must
therefore key on the SAME boundaries with the SAME function:
hashing the whole prompt (or a fixed byte prefix, like the per-instance
``LlmRouter``) makes "shares a 2-block system prompt" and "identical
request" look different, and the replica that holds the prefix is never
found. λScale (arxiv 2502.09922) calls this locality-aware dispatch; the
reference's pod/llm.go:211 approximates it with byte-prefix hashes.

Routing walks the prompt's block-aligned prefix keys from LONGEST to
shortest — the first key any replica has served is the best possible KV
reuse — then falls back to join-shortest-queue over replica load
snapshots when there is no affinity hit or the target is saturated or
draining. The table is process-local (the gateway is the single front
door for its fleet) with TTL'd entries, so a replaced replica ages out
instead of attracting traffic forever.
"""

from __future__ import annotations

import hashlib
import json
import time
from typing import Callable, Optional

from ..utils.prefixkey import prefix_keys

# longest prefix worth keying, in blocks: bounds per-request hash work and
# table growth on pathological prompts (64 blocks × 16 tok = 1k tokens of
# prefix discrimination, far past where decode cost dominates prefill reuse)
MAX_KEY_BLOCKS = 64


def extract_prompt_tokens(body: bytes) -> Optional[list[int]]:
    """Token list from a generate-request body (the llm runner's wire
    shape), or None for non-token payloads."""
    try:
        payload = json.loads(body)
    except (ValueError, TypeError):
        return None
    if not isinstance(payload, dict):
        return None
    tokens = payload.get("tokens") or payload.get("prompt_tokens")
    if isinstance(tokens, list) and tokens and \
            all(isinstance(t, (int, float)) for t in tokens):
        return [int(t) for t in tokens]
    return None


def block_keys(body: bytes, block_tokens: int) -> list[bytes]:
    """Block-aligned prefix keys for a request body, longest first.

    Token bodies take the engine's keys from the engine's function
    (``prefix_keys``, strict, at most ``MAX_KEY_BLOCKS`` blocks: a key does
    not depend on the cap). Text payloads (prompt /
    messages / raw bytes) approximate a block as ``4 × block_tokens``
    characters — byte-prefix blocks keep the longest-first walk semantics
    even when the gateway never sees token ids.
    """
    bs = max(block_tokens, 1)
    tokens = extract_prompt_tokens(body)
    if tokens is not None:
        # the engine's own walk (``utils.prefixkey.prefix_keys``, the one
        # owner of the key): the router's table key and the engine's cache
        # key agree because they are made by the same code, in one pass
        # over the tokens (this runs 2-3 times per routed request on the
        # gateway's single thread). Strict, like PrefixCache.lookup: at
        # least one token must remain to prefill.
        return prefix_keys(tokens, bs, strict=True,
                           max_blocks=MAX_KEY_BLOCKS)[::-1]
    raw = body
    try:
        payload = json.loads(body)
        if isinstance(payload, dict):
            for key in ("prompt", "messages", "input", "text"):
                if key in payload:
                    raw = json.dumps(payload[key]).encode()
                    break
    except (ValueError, TypeError):
        pass
    char_block = bs * 4
    nb = min(len(raw) // char_block, MAX_KEY_BLOCKS)
    h = hashlib.sha1()
    keys = []
    for n in range(1, nb + 1):
        h.update(raw[(n - 1) * char_block: n * char_block])
        keys.append(h.copy().digest())
    return keys[::-1]


class AffinityRouter:
    """Block-prefix → replica table with TTL and load-aware fallback."""

    def __init__(self, block_tokens: int = 16, ttl_s: float = 300.0,
                 max_entries: int = 65536,
                 clock: Callable[[], float] = time.monotonic):
        self.block_tokens = block_tokens
        self.ttl_s = ttl_s
        self.max_entries = max_entries
        self._clock = clock
        # key -> (container_id, expires_at)
        self._table: dict[bytes, tuple[str, float]] = {}
        self.hits = 0
        self.misses = 0

    # -- table ----------------------------------------------------------------

    def _lookup(self, key: bytes) -> str:
        entry = self._table.get(key)
        if entry is None:
            return ""
        cid, expires = entry
        if self._clock() > expires:
            del self._table[key]
            return ""
        return cid

    def record_served(self, body: bytes, container_id: str) -> None:
        """Register every block prefix of the served prompt: a future
        request sharing only the system-prompt blocks still finds the
        replica through its shorter keys."""
        expires = self._clock() + self.ttl_s
        for key in block_keys(body, self.block_tokens):
            self._table[key] = (container_id, expires)
        if len(self._table) > self.max_entries:
            self._prune()

    def forget_replica(self, container_id: str) -> None:
        """Drop a drained/stopped replica's entries so its traffic
        re-homes immediately instead of waiting out the TTL."""
        self._table = {k: v for k, v in self._table.items()
                       if v[0] != container_id}

    def _prune(self) -> None:
        now = self._clock()
        self._table = {k: v for k, v in self._table.items() if v[1] >= now}
        if len(self._table) > self.max_entries:
            # still over (hot table): drop the soonest-expiring half
            keep = sorted(self._table.items(), key=lambda kv: -kv[1][1])
            self._table = dict(keep[: self.max_entries // 2])

    # -- selection -------------------------------------------------------------

    def target(self, body: bytes, live: set[str]) -> str:
        """Longest-prefix affinity target among ``live`` replicas, or ""."""
        for key in block_keys(body, self.block_tokens):
            cid = self._lookup(key)
            if cid and cid in live:
                return cid
        return ""

    def order(self, body: bytes, replicas: list[str],
              load: dict[str, float],
              saturated: Optional[set[str]] = None) -> list[str]:
        """Preference order: affinity target first (unless saturated),
        then join-shortest-queue by the caller's load snapshot. Saturated
        replicas keep their JSQ order at the tail — admission budgets are
        the hard gate; ordering only expresses preference."""
        saturated = saturated or set()
        target = self.target(body, set(replicas))
        if target:
            if target not in saturated:
                self.hits += 1
                rest = [r for r in replicas if r != target]
                rest.sort(key=lambda r: (r in saturated,
                                         load.get(r, 0.0), r))
                return [target] + rest
            # affinity hit on a saturated replica counts as a miss for the
            # hit-rate signal: the KV reuse did NOT happen
        self.misses += 1
        out = list(replicas)
        out.sort(key=lambda r: (r in saturated, load.get(r, 0.0), r))
        return out

    def stats(self) -> dict:
        total = self.hits + self.misses
        return {"entries": len(self._table), "hits": self.hits,
                "misses": self.misses,
                "hit_rate": self.hits / total if total else 0.0}
