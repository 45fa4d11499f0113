"""What the prefix cache holds (ISSUE 54): a pool page counts once against
the budget however many entries name it, through every transition an entry
makes; and the schedule of the ``kimi-docs`` cell replayed against the real
``PrefixCache`` and ``BlockAllocator`` with a clock the test sets — at the
parent the budget, which summed an entry's pages whoever shared them, let
the documents of sessions that had not started yet go. And how its keys
are made (ISSUE 58): every block-aligned prefix of a prompt from one pass
over it, so a lookup hashes a prompt once however far down its entry lies.
Host only: no engine, no jax."""

import hashlib
import heapq
import math
import struct

import pytest

from tpu9.serving.paged_kv import (BlockAllocator, PrefixCache, blocks_for,
                                   prefix_keys)

BS = 4


def _recount(pc):
    """``held_blocks`` from scratch."""
    return len({b for e in pc._entries.values() if e.tier == "device"
                for b in e.blocks})


def _admit(pc, alloc, prompt, new):
    """One admission as the engine makes it: the prompt's keys walked once
    for the lookup and the insert both, the hit's pages retained, the rest
    allocated (the cache evicts for space where the pool is short), the
    prompt's whole pages inserted. Returns the cached tokens and the slot's
    blocks, which the caller releases when the sequence ends."""
    keys = pc.walk(prompt)
    hit = pc.lookup(prompt, keys)
    shared = list(hit.blocks) if hit else []
    alloc.retain(shared)
    if hit:
        pc.release_pin(hit)
    need = blocks_for(len(prompt) + new, alloc.block_s) - len(shared)
    fresh = alloc.alloc(need)
    if fresh is None:
        pc.evict_for_space(need)
        fresh = alloc.alloc(need)
    pc.insert(prompt, shared + fresh, keys)
    return (hit.n_tokens if hit else 0), shared + fresh


def _turns(pc, alloc, n_turns, first=0, base=0):
    """A session's consecutive turns, each one page longer than the last and
    admitted behind a hit on it; the slot retires after every turn, so only
    the cache holds the pages. Returns the prompts."""
    prompts = []
    for t in range(first + 1, first + n_turns + 1):
        prompts.append([base + i for i in range(t * BS)])
        _, blocks = _admit(pc, alloc, prompts[-1], 0)
        alloc.release(blocks)
    return prompts


def _cache(n_blocks=32, budget=16):
    alloc = BlockAllocator(n_blocks, BS)
    pc = PrefixCache(alloc, budget)
    now = [0.0]

    def clock():
        now[0] += 1.0
        return now[0]

    pc.clock = clock
    return pc, alloc


def test_a_shared_page_counts_once_and_is_freed_by_its_last_holder():
    pc, alloc = _cache()
    older, newer = _turns(pc, alloc, 2, first=2)     # 3 pages, then 4
    assert len(pc._entries) == 2
    assert pc.held_blocks == alloc.used_count == 4   # the parent read 7
    assert pc.stats()["held_blocks"] == 4

    # the older turn's entry rides on the newer one's pages
    pc.drop(pc._key(older))
    assert pc.held_blocks == alloc.used_count == 4
    assert (pc.evictions, pc.evictions_freed) == (1, 0)

    # a live slot still holds the first two pages: the newer entry's going
    # takes them off the cache's count but frees only the two it alone held
    entry = pc.lookup(newer + [1])
    alloc.retain(entry.blocks[:2])
    pc.release_pin(entry)
    pc.drop(pc._key(newer))
    assert pc.held_blocks == 0 and alloc.used_count == 2
    assert (pc.evictions, pc.evictions_freed) == (2, 1)
    assert pc.stats()["evictions_freed"] == 1


def test_eviction_walks_the_free_riders_in_one_ordered_pass():
    pc, alloc = _cache(n_blocks=8)
    _turns(pc, alloc, 4)                 # entries of 1..4 pages, 4 pages held
    other = _turns(pc, alloc, 1, first=1, base=500)[0]       # 2 of its own
    assert pc.held_blocks == 6 and alloc.free_count == 2
    # three blocks short of five: the session's three older turns go first
    # (LRU, nothing freed), then its newest (4 pages); the other stays
    pc.evict_for_space(5)
    assert alloc.free_count == 6 and pc.held_blocks == 2
    assert (pc.evictions, pc.evictions_freed) == (4, 1)
    assert [e.key for e in pc._entries.values()] == [pc._key(other)]


def _spill(pc, alloc, prompts):
    pc.spill_to_host(pc._entries[pc._key(prompts[-1])])


def _spill_and_promote(pc, alloc, prompts):
    entry = pc._entries[pc._key(prompts[-1])]
    pc.spill_to_host(entry)
    pc.promote_to_device(entry, alloc.alloc(len(prompts)))


def _spill_and_upgrade(pc, alloc, prompts):
    """A host-tier entry prefilled again on the device: ``insert`` upgrades
    it in place over the slot's pages, two of them another entry's."""
    entry = pc._entries[pc._key(prompts[-1])]
    pc.spill_to_host(entry)
    shared = list(pc._entries[pc._key(prompts[1])].blocks)
    alloc.retain(shared)
    blocks = shared + alloc.alloc(len(prompts) - len(shared))
    pc.insert(prompts[-1], blocks)
    alloc.release(blocks)
    assert entry.tier == "device" and entry.blocks == blocks


def _adopt(pc, alloc, prompts):
    assert pc.adopt(b"k" * 20, alloc.alloc(3), 3 * BS)
    assert not pc.adopt(b"k" * 20, [0], BS)          # the key is taken


def _over_budget(pc, alloc, prompts):
    pc.max_blocks = 5
    _turns(pc, alloc, 1, first=3, base=900)          # 4 pages of its own


TRANSITIONS = {
    "insert": lambda pc, alloc, prompts: _turns(pc, alloc, 2, first=4),
    "adopt": _adopt,
    "drop-older": lambda pc, alloc, prompts: pc.drop(pc._key(prompts[0])),
    "drop-newest": lambda pc, alloc, prompts: pc.drop(pc._key(prompts[-1])),
    "evict-for-space": lambda pc, alloc, prompts: pc.evict_for_space(30),
    "evict-to-budget": _over_budget,
    "spill": _spill,
    "spill-promote": _spill_and_promote,
    "spill-upgrade": _spill_and_upgrade,
}


@pytest.mark.parametrize("name", sorted(TRANSITIONS))
def test_the_count_equals_a_recount_after_every_transition(name):
    pc, alloc = _cache()
    prompts = _turns(pc, alloc, 4)       # entries of 1..4 pages over 4 pages
    assert pc.held_blocks == _recount(pc) == 4
    TRANSITIONS[name](pc, alloc, prompts)
    assert pc.held_blocks == _recount(pc)
    assert pc.held_blocks <= pc.max_blocks
    # the cache's references are all the allocator holds (slots retired)
    assert alloc.used_count == pc.held_blocks
    assert sum(pc._block_entries) == sum(
        len(e.blocks) for e in pc._entries.values())


def test_the_budget_bounds_the_entries_too():
    """No knob for the number of entries: no two end on the same page, so
    the free riders (older turns under a newer one) cannot outnumber the
    pages the budget allows."""
    pc, alloc = _cache(n_blocks=64, budget=6)
    for base in (0, 1000, 2000):
        _turns(pc, alloc, 5, base=base)
        device = [e for e in pc._entries.values() if e.tier == "device"]
        assert len(device) <= pc.held_blocks <= pc.max_blocks
        assert pc.held_blocks == _recount(pc) == alloc.used_count
    assert pc.evictions > 0
    # the newest session's turns are what is left, whole
    assert pc.lookup([2000 + i for i in range(5 * BS)] + [1]).n_tokens \
        == 5 * BS


# -- the kimi-docs schedule ---------------------------------------------------

PAGE, CHUNK, POOL, BUDGET = 128, 512, 4864, 8192
SESSIONS, STAGGER_S, WINDOW_S = 16, 0.5, 45.0
CHUNK_S = 0.0603            # a 512-token chunk + its share of the gather


def _parent_hashed(prompt, p, bs=PAGE):
    """The tokens the parent's arithmetic hashed for one admission that hit
    ``p`` cached tokens (0: a miss): ``lookup`` hashed every block-aligned
    strict prefix from the longest down to the hit from scratch, and
    ``insert`` the prompt's whole pages once more."""
    walked = range(max(p // bs, 1), (len(prompt) - 1) // bs + 1)
    return sum(nb * bs for nb in walked) + len(prompt) // bs * bs


@pytest.mark.parametrize("gap_ms", [23.0, 17.0])
def test_no_document_is_lost_in_the_kimi_docs_schedule(gap_ms):
    """16 documents of 16,384–40,960 tokens built in set-up, session ``i``
    starts ``i x 0.5`` s into the window, every turn hits the newest entry
    and inserts one two pages longer, a pool of 4,864 pages under a budget
    of 8,192: no lookup misses and no suffix reaches four chunks in 45 s,
    at the gap the cell has (23 ms) and at a faster one. With the real key
    (ISSUE 58): a turn's prompt passes through the hash once, where the
    parent's walk — a sha1 from scratch a page it stepped down, and the
    insert's again — passed it about four times."""
    now = [0.0]
    alloc = BlockAllocator(POOL + 1, PAGE)
    alloc.alloc(1)                                   # the trash page
    pc = PrefixCache(alloc, BUDGET)
    pc.clock = lambda: now[0]

    admitted = parent_hashed = 0

    def admit(prompt, new):
        nonlocal admitted, parent_hashed
        p, blocks = _admit(pc, alloc, prompt, new)
        admitted += len(prompt)
        parent_hashed += _parent_hashed(prompt, p)
        return -(-(len(prompt) - p) // CHUNK), blocks

    lo, hi = math.log(16384), math.log(40960)
    history = []
    for i in range(SESSIONS):
        now[0] = -120.0 + 6.0 * i                    # set-up, one at a time
        n = round(math.exp(lo + (hi - lo) * (i + 0.5) / SESSIONS))
        document = [i << 20] + [0] * (n - 1)
        _, blocks = admit(document, 1)
        alloc.release(blocks)
        history.append(document + [0])
    assert pc.held_blocks == alloc.used_count - 1 == 3343
    # set-up's sixteen misses: a document hashed once, not once a page
    assert pc.tokens_hashed <= admitted
    assert parent_hashed > 100 * admitted
    hashed0, admitted, parent_hashed = pc.tokens_hashed, 0, 0

    events = [(i * STAGGER_S, i) for i in range(SESSIONS)]
    slots = [[] for _ in range(SESSIONS)]
    turns = 0
    while events:
        at, i = heapq.heappop(events)
        if at >= WINDOW_S:
            continue
        now[0] = at
        alloc.release(slots[i])                      # the turn before ended
        prompt = history[i] + [0] * 128
        chunks, slots[i] = admit(prompt, 128)
        assert chunks < 4, (at, i, chunks)
        history[i] = prompt + [0] * 128
        turns += 1
        heapq.heappush(events,
                       (at + chunks * CHUNK_S + 128 * gap_ms / 1e3, i))
    assert pc.misses == SESSIONS                     # the documents' own
    assert pc.evictions == 0
    assert pc.held_blocks == _recount(pc) <= alloc.used_count <= POOL
    assert turns > 200
    assert len(pc._entries) == SESSIONS + turns <= pc.held_blocks
    # the window's turns: what the cell's engine counts as
    # prefix_tokens_hashed over prompt_rows_admitted
    assert 0.99 < (pc.tokens_hashed - hashed0) / admitted <= 1.0
    assert 3.5 < parent_hashed / admitted < 5.0


# -- the key ------------------------------------------------------------------

def _plain_key(tokens):
    """The key from its definition: the sha1 of the tokens as 64-bit
    little-endian integers."""
    return hashlib.sha1(struct.pack("<%dq" % len(tokens), *tokens)).digest()


@pytest.mark.parametrize("strict", [True, False])
@pytest.mark.parametrize("over", [-1, 0, 1])
@pytest.mark.parametrize("bs", [16, 128])
def test_one_walk_makes_the_key_of_every_prefix(bs, over, strict):
    """``prefix_keys`` at every boundary is ``PrefixCache._key`` of that
    prefix and the plain sha1 of its bytes, for a prompt that ends on a
    boundary, one token under and one over; strict leaves a token to
    prefill, and a cap on the blocks changes no key."""
    tokens = [(i * 7919 + 13) % 163840 for i in range(5 * bs + over)]
    keys = prefix_keys(tokens, bs, strict=strict)
    assert len(keys) == (len(tokens) - strict) // bs
    for i, key in enumerate(keys):
        prefix = tokens[:(i + 1) * bs]
        assert key == PrefixCache._key(prefix) == _plain_key(prefix)
    assert prefix_keys(tokens, bs, strict=strict, max_blocks=2) == keys[:2]
    assert prefix_keys(tokens[:bs - 1], bs, strict=strict) == []


@pytest.mark.parametrize("tokens", [
    [2 ** 31 - 1, 2 ** 31, 2 ** 40, 0], [-1, -2 ** 63, 5, 6],
    [2 ** 63, 2 ** 70, 1, 2], [1.0, 2.0, 3, 4], [True, 0, 7, 9]],
    ids=["past-int32", "negative", "past-int64", "floats", "bools"])
def test_a_key_never_raises(tokens):
    """Whatever id a body carries, the walk gives a key (the router hashes
    bodies no engine has checked), equal for equal ids: an id that fits 64
    bits is keyed as itself, a larger one by its low 64 bits."""
    keys = prefix_keys(tokens, 2, strict=False)
    assert len(keys) == 2 and len(keys[1]) == 20
    as_ints = [int(t) for t in tokens]
    assert keys == prefix_keys(as_ints, 2, strict=False)
    if all(-2 ** 63 <= t < 2 ** 63 for t in as_ints):
        assert keys[1] == _plain_key(as_ints)
    assert PrefixCache._key([]) == hashlib.sha1().digest()


def _long_prompt_cache():
    """A 320-page prompt (and a token to prefill) over a cache that holds its
    first 317 pages: the hit of a ``kimi-docs`` turn, three pages down."""
    alloc = BlockAllocator(400, PAGE)
    pc = PrefixCache(alloc, 400)
    prompt = [(i * 31 + 7) % 163840 for i in range(320 * PAGE)] + [1]
    blocks = alloc.alloc(317)
    pc.insert(prompt[:317 * PAGE], blocks)
    alloc.release(blocks)                    # the cache's references remain
    pc.tokens_hashed = 0
    return pc, prompt


def test_a_lookup_hashes_a_long_prompt_once():
    """The entry lies 3 pages below the top of a 40,960-token prompt: the
    lookup finds it, pinned and counted as before, having hashed no more
    than the prompt — the parent hashed it four times (163,328 tokens) —
    and the insert behind it, handed the walk, hashes nothing."""
    pc, prompt = _long_prompt_cache()
    keys = pc.walk(prompt)
    entry = pc.lookup(prompt, keys)
    assert entry.n_tokens == 317 * PAGE and entry.key == keys[316]
    assert (entry.pins, pc.pinned) == (1, 1)
    assert (pc.hits, pc.misses, pc.hits_device) == (1, 0, 1)
    assert pc.tokens_reused == 317 * PAGE
    pc.release_pin(entry)
    pc.allocator.retain(entry.blocks)
    pc.insert(prompt, entry.blocks + pc.allocator.alloc(4), keys)
    assert pc.tokens_hashed == 320 * PAGE <= len(prompt)
    assert _parent_hashed(prompt, 317 * PAGE) == (320 + 319 + 318 + 317
                                                  + 320) * PAGE
    assert pc.lookup(prompt + [2] * PAGE).n_tokens == 320 * PAGE
    # a walk the caller did not make is made (and counted) by the cache
    assert pc.tokens_hashed == 320 * PAGE + 321 * PAGE


def test_a_miss_hashes_a_long_prompt_once_not_once_a_page():
    """Another document of the same length misses: 320 probes, one pass over
    the tokens — the parent's walk hashed 51,360 pages' worth on its way
    down to zero."""
    pc, prompt = _long_prompt_cache()
    other = [9] + prompt[1:]
    assert pc.lookup(other) is None
    assert (pc.hits, pc.misses, pc.pinned, pc.tokens_reused) == (0, 1, 0, 0)
    assert pc.tokens_hashed == 320 * PAGE <= len(other)
    assert _parent_hashed(other, 0) == (320 * 321 // 2 + 320) * PAGE
    assert pc.stats()["tokens_hashed"] == pc.tokens_hashed


def test_an_export_walks_once_and_skips_what_lookup_counts():
    """``acquire_for_export`` probes the same walk, not strict (a prompt that
    ends on a page is its own longest prefix), pins the entry and leaves the
    admission counters alone."""
    pc, prompt = _long_prompt_cache()
    entry = pc.acquire_for_export(prompt[:317 * PAGE])
    assert entry.n_tokens == 317 * PAGE and entry.pins == 1
    assert (pc.hits, pc.misses, pc.tokens_reused) == (0, 0, 0)
    assert pc.tokens_hashed == 317 * PAGE
    pc.release_pin(entry)
    assert pc.acquire_for_export([9] + prompt[1:]) is None
    assert pc.pinned == 0
