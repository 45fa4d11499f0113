"""What the prefix cache holds (ISSUE 54): a pool page counts once against
the budget however many entries name it, through every transition an entry
makes; and the schedule of the ``kimi-docs`` cell replayed against the real
``PrefixCache`` and ``BlockAllocator`` with a clock the test sets — at the
parent the budget, which summed an entry's pages whoever shared them, let
the documents of sessions that had not started yet go. Host only: no
engine, no jax."""

import heapq
import math

import pytest

from tpu9.serving.paged_kv import BlockAllocator, PrefixCache, blocks_for

BS = 4


def _recount(pc):
    """``held_blocks`` from scratch."""
    return len({b for e in pc._entries.values() if e.tier == "device"
                for b in e.blocks})


def _admit(pc, alloc, prompt, new):
    """One admission as the engine makes it: the hit's pages retained, the
    rest allocated (the cache evicts for space where the pool is short), the
    prompt's whole pages inserted. Returns the cached tokens and the slot's
    blocks, which the caller releases when the sequence ends."""
    hit = pc.lookup(prompt)
    shared = list(hit.blocks) if hit else []
    alloc.retain(shared)
    if hit:
        pc.release_pin(hit)
    need = blocks_for(len(prompt) + new, alloc.block_s) - len(shared)
    fresh = alloc.alloc(need)
    if fresh is None:
        pc.evict_for_space(need)
        fresh = alloc.alloc(need)
    pc.insert(prompt, shared + fresh)
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


def _cheap_key(tokens):
    """A session's prompts are prefixes of ONE sequence that no other
    session shares, so (first token, length) names a prefix exactly; the
    real sha1 over 40 k tokens, once a page a lookup walks down, is 10 s of
    a replay and no part of what it shows."""
    return b"%d:%d" % (tokens[0], len(tokens))


@pytest.mark.parametrize("gap_ms", [23.0, 17.0])
def test_no_document_is_lost_in_the_kimi_docs_schedule(gap_ms, monkeypatch):
    """16 documents of 16,384–40,960 tokens built in set-up, session ``i``
    starts ``i x 0.5`` s into the window, every turn hits the newest entry
    and inserts one two pages longer, a pool of 4,864 pages under a budget
    of 8,192: no lookup misses and no suffix reaches four chunks in 45 s,
    at the gap the cell has (23 ms) and at a faster one."""
    monkeypatch.setattr(PrefixCache, "_key", staticmethod(_cheap_key))
    now = [0.0]
    alloc = BlockAllocator(POOL + 1, PAGE)
    alloc.alloc(1)                                   # the trash page
    pc = PrefixCache(alloc, BUDGET)
    pc.clock = lambda: now[0]

    def admit(prompt, new):
        p, blocks = _admit(pc, alloc, prompt, new)
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
